"""From ``--seed`` to a JAX key. The driver's seeds are large (a little
over 2**31), more than a signed 32-bit key seed holds, so the high bits are
folded in."""


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)
