"""The comparison with the plain reference that decides ``correct`` for a
model's logits: max |got - want| in bfloat16 ulps of the largest reference
logit (after ``chip_smoke.logit_gap`` / ``check_gaps``, PR 21). bfloat16
keeps 8 significand bits, so one ulp at magnitude m is m * 2**-8. Compared
are logits, never sampled tokens: with random weights the argmax flips on
rounding."""

BF16_ULP = 2.0 ** -8


def logit_gap_ulps(got, want):
    """A device scalar; infinite when ``got`` is not finite."""
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    gap = jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) * BF16_ULP)
    return jnp.where(jnp.isfinite(got).all(), gap, jnp.inf)
