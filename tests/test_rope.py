"""Rotary position embedding (cfg.pos_encoding='rope').

Oracles: (a) the rotation's defining property — attention scores
depend only on RELATIVE positions (shifting every position by a
constant leaves q·kᵀ unchanged); (b) sp-sharded training (ring AND
ulysses, which depend on GLOBAL positions being used) matches the
single device exactly; (c) KV-cache decode (keys cached rotated)
matches the O(n^2) recompute oracle, including combined with GQA;
(d) pipeline parallelism runs; (e) rope vs sincos genuinely differ
(the flag is wired, not ignored).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from rlo_tpu.models.generate import generate
from rlo_tpu.models.transformer import (TransformerConfig, _rope,
                                        forward, init_params,
                                        train_step)
from rlo_tpu.parallel.mesh import make_mesh, shard_jit

ROPE = TransformerConfig(vocab=89, d_model=32, n_heads=4, n_layers=2,
                         d_ff=64, dtype="float32",
                         pos_encoding="rope")


def tokens_for(cfg, batch=2, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)),
                       jnp.int32)


def test_scores_depend_on_relative_positions_only():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((2, 8, 3, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 8, 3, 16)), jnp.float32)
    pos = jnp.arange(8)

    def scores(shift):
        qr = _rope(q, pos + shift)
        kr = _rope(k, pos + shift)
        return np.asarray(jnp.einsum("bqhd,bkhd->bhqk", qr, kr))

    np.testing.assert_allclose(scores(0), scores(137), rtol=1e-4,
                               atol=1e-4)
    # and rotation is not a no-op: absolute q.k changes
    assert not np.allclose(
        scores(0), np.asarray(jnp.einsum("bqhd,bkhd->bhqk", q, k)),
        atol=1e-3)


class TestRopeScaling:
    """Context-extension levers (cfg.rope_scaling, round 4)."""

    def test_linear_is_position_interpolation(self):
        """'linear' at scale s == the unscaled rotation evaluated at
        pos/s — the defining identity of position interpolation."""
        rng = np.random.default_rng(3)
        t = jnp.asarray(rng.standard_normal((2, 8, 3, 16)), jnp.float32)
        pos = jnp.arange(0, 64, 8)  # positions beyond a 'trained' range
        got = _rope(t, pos, scaling="linear", scale=4.0)
        want = _rope(t, pos.astype(jnp.float32) / 4.0)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    def test_ntk_rescales_base(self):
        """'ntk' at scale s == plain rotary with base
        10000 * s^(hd/(hd-2)) — computed directly."""
        rng = np.random.default_rng(4)
        hd = 16
        t = jnp.asarray(rng.standard_normal((2, 8, 3, hd)), jnp.float32)
        pos = jnp.arange(8)
        got = np.asarray(_rope(t, pos, scaling="ntk", scale=8.0))
        base = 10000.0 * 8.0 ** (hd / (hd - 2))
        half = hd // 2
        freqs = np.exp(-np.log(base) * np.arange(half) / half)
        ang = np.arange(8)[:, None] * freqs[None, :]
        cos = np.cos(ang)[None, :, None, :]
        sin = np.sin(ang)[None, :, None, :]
        tn = np.asarray(t)
        t1, t2 = tn[..., :half], tn[..., half:]
        want = np.concatenate([t1 * cos - t2 * sin,
                               t1 * sin + t2 * cos], -1)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_ntk_preserves_high_freq_extends_low(self):
        """The NTK property itself: the highest-frequency pair's angle
        moves <10% while the lowest-frequency pair's period grows by
        ~the scale factor."""
        hd, s = 64, 16.0
        half = hd // 2
        base0, base1 = 10000.0, 10000.0 * s ** (hd / (hd - 2))
        f0 = np.exp(-np.log(base0) * np.arange(half) / half)
        f1 = np.exp(-np.log(base1) * np.arange(half) / half)
        assert f1[0] == f0[0]                      # highest: untouched
        assert abs(f1[1] / f0[1] - 1) < 0.1        # near-highest: <10%
        # lowest-frequency period grows ~s (up to the (d-2)/d exponent)
        growth = f0[-1] / f1[-1]
        assert s * 0.5 < growth <= s * 1.01

    @pytest.mark.parametrize("scaling", ["linear", "ntk"])
    def test_scaled_model_trains_and_decodes(self, scaling):
        """End-to-end: a scaled-rope config trains (finite loss,
        params move) and KV-cache decode still matches the O(n^2)
        recompute oracle (keys cached rotated with the SAME scaled
        rotation)."""
        cfg = dataclasses.replace(ROPE, rope_scaling=scaling,
                                  rope_scale=4.0)
        params = init_params(jax.random.PRNGKey(5), cfg)
        new_params, loss = train_step(params, tokens_for(cfg), cfg,
                                      lr=1e-2)
        assert np.isfinite(float(loss))
        prompt = tokens_for(cfg, seq=6, seed=7)
        got = np.asarray(generate(params, prompt, cfg, max_new=6))
        seq = np.asarray(prompt)
        for _ in range(6):
            logits = np.asarray(forward(params, jnp.asarray(seq), cfg)
                                )[:, -1, :]
            nxt = logits.argmax(-1).astype(np.int32)
            seq = np.concatenate([seq, nxt[:, None]], axis=1)
        np.testing.assert_array_equal(got, seq[:, prompt.shape[1]:])

    def test_scaled_sp_sharded_matches_single_device(self):
        """Scaling composes with sp sharding (global positions scale
        uniformly across shards)."""
        cfg = dataclasses.replace(ROPE, rope_scaling="ntk",
                                  rope_scale=2.0)
        mesh = make_mesh((2,), ("sp",))
        params = init_params(jax.random.PRNGKey(6), cfg)
        toks = tokens_for(cfg, seq=32, seed=8)
        step = shard_jit(
            lambda p, t: train_step(p, t, cfg, lr=1e-2, sp_axis="sp"),
            mesh, (P(), P(None, "sp")), (P(), P()))
        _, loss_sp = step(params, toks)
        _, loss_one = train_step(params, toks, cfg, lr=1e-2)
        assert abs(float(loss_sp) - float(loss_one)) < 1e-4

    def test_invalid_configs_rejected(self):
        toks = tokens_for(ROPE, seq=4)
        bad1 = dataclasses.replace(ROPE, rope_scaling="dynamic")
        params = init_params(jax.random.PRNGKey(0), bad1)
        with pytest.raises(ValueError, match="unknown rope_scaling"):
            forward(params, toks, bad1)
        bad2 = dataclasses.replace(ROPE, pos_encoding="sincos",
                                   rope_scaling="ntk")
        with pytest.raises(ValueError, match="requires"):
            forward(params, toks, bad2)
        bad3 = dataclasses.replace(ROPE, rope_scaling="linear",
                                   rope_scale=0.5)
        with pytest.raises(ValueError, match=">= 1"):
            forward(params, toks, bad3)


def test_rope_differs_from_sincos():
    params_shape_cfg = dataclasses.replace(ROPE, pos_encoding="sincos")
    params = init_params(jax.random.PRNGKey(0), ROPE)
    toks = tokens_for(ROPE)
    a = np.asarray(forward(params, toks, ROPE))
    b = np.asarray(forward(params, toks, params_shape_cfg))
    assert not np.allclose(a, b, atol=1e-3)


@pytest.mark.parametrize("sp_attention", ["ring", "ulysses"])
def test_rope_sequence_parallel_matches_single_device(sp_attention):
    """Global positions under sharding: shard r must rotate with its
    own global slice, or the sharded loss diverges."""
    cfg = dataclasses.replace(ROPE, sp_attention=sp_attention)
    mesh = make_mesh((2,), ("sp",))
    params = init_params(jax.random.PRNGKey(2), cfg)
    toks = tokens_for(cfg, seq=32, seed=3)
    step = shard_jit(
        lambda p, t: train_step(p, t, cfg, lr=1e-2, sp_axis="sp"),
        mesh, (P(), P(None, "sp")), (P(), P()))
    _, loss_sp = step(params, toks)
    _, loss_one = train_step(params, toks, cfg, lr=1e-2)
    assert abs(float(loss_sp) - float(loss_one)) < 1e-4


@pytest.mark.parametrize("n_kv_heads", [None, 2])
def test_rope_decode_matches_naive_loop(n_kv_heads):
    cfg = dataclasses.replace(ROPE, n_kv_heads=n_kv_heads)
    params = init_params(jax.random.PRNGKey(3), cfg)
    prompt = tokens_for(cfg, seq=6, seed=4)
    max_new = 8
    got = np.asarray(generate(params, prompt, cfg, max_new=max_new))
    seq = np.asarray(prompt)
    for _ in range(max_new):
        logits = np.asarray(forward(params, jnp.asarray(seq), cfg)
                            )[:, -1, :]
        nxt = logits.argmax(-1).astype(np.int32)
        seq = np.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, seq[:, prompt.shape[1]:])


def test_rope_pipeline_parallel():
    from rlo_tpu.models.pipeline import (pipeline_pspecs,
                                         pipeline_train_step,
                                         stack_layers)

    mesh = make_mesh((2,), ("pp",))
    params = init_params(jax.random.PRNGKey(4), ROPE)
    pparams = stack_layers(params)
    specs = pipeline_pspecs("pp", cfg=ROPE)
    toks = tokens_for(ROPE, batch=4, seq=16, seed=5)
    step = shard_jit(
        lambda p, t: pipeline_train_step(p, t, ROPE, "pp", n_micro=2,
                                         lr=1e-2),
        mesh, (specs, P()), (specs, P()))
    _, loss = step(pparams, toks)
    assert np.isfinite(float(loss))


def test_rope_train_step_moves_params():
    params = init_params(jax.random.PRNGKey(5), ROPE)
    new_params, loss = train_step(params, tokens_for(ROPE), ROPE,
                                  lr=1e-2)
    assert np.isfinite(float(loss))
    delta = sum(float(np.abs(np.asarray(a) - np.asarray(b)).sum())
                for a, b in zip(jax.tree.leaves(new_params),
                                jax.tree.leaves(params)))
    assert delta > 0
