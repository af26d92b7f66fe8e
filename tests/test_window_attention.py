"""Window and full attention in one model (Trinity-Large-Preview, perf/
configs/trinity-large-ep16) on the serving path, at the configuration's
tiny preset (window 16) with seeded float32 weights on the CPU, against its
plain reference (perf/configs/trinity-large-ep16.py: full forwards under
band and causal masks, no cache, no ring):

  (a) the layer: a NoPE full layer, the attention gate and the sandwich
      norms against equations written out here;
  (b) a sliding layer's ring (kvcache): prefill, decode steps across the
      ring's wrap, a round's tail and its fold across the wrap, an extend
      chunk whose queries straddle the wrap — each against the reference;
  (c) the window kernels in interpret mode (flash_decode / flash_block_
      decode with a window, with and without the tail; the ring's block
      write; flash_attention's band, forward and gradient) against the
      einsum forms;
  (d) DecodeServer on prompts past the window and the ring: its tokens
      are the dense generate's, and the ``serve.window.*`` counters;
  (e) the share test of the model-configs guide for the one-group sigmoid
      router with a shared expert;
  (f) the configurations the benchmark already had trace the programs they
      traced before sliding layers existed, and Trinity the ones it traced
      before hyper-connections did (digests of their parameter trees and
      jaxprs, kernel bodies included).
"""

import dataclasses
import hashlib
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models import kvcache, moe
from rlo_tpu.models.generate import (block_decode, decode_step, generate,
                                     init_kv_cache, prefill)
from rlo_tpu.models.serve import DecodeServer
from rlo_tpu.models.transformer import (TransformerConfig, apply_layer,
                                        forward, init_params)
from rlo_tpu.utils.metrics import Registry

CONFIGS = Path(__file__).resolve().parent.parent / "perf" / "configs"
_spec = importlib.util.spec_from_file_location(
    "trinity_reference", CONFIGS / "trinity-large-ep16.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

MODEL = dict(json.loads((CONFIGS / "trinity-large-ep16.json").read_text())
             ["tiny"]["model"], dtype="float32", param_dtype="float32")
CFG = TransformerConfig(**MODEL)
W = CFG.sliding_window
SPARE = 8       # rings of W + 8 = 24 positions: a 60-token row wraps twice


def _close(got, want, tol=5e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with pre-sublayer gains that are not all ones, so a
    norm left out or swapped shows."""
    p = init_params(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for L in p["layers"]:
        for n in ("ln1", "ln2", "q_norm", "k_norm"):
            L[n]["g"] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                      L[n]["g"].shape)
    return p


@pytest.fixture(scope="module")
def toks():
    return jax.random.randint(jax.random.PRNGKey(2), (2, 64), 0, CFG.vocab)


@pytest.fixture(scope="module")
def want(params, toks):
    """The reference's logits at every position, routing by its own
    scores (the program's f32 scores agree with them to the last bits)."""
    return np.asarray(REF.logits(params, toks, MODEL))


def test_config_names_each_layer_kind():
    assert [CFG.layer_window(i) for i in range(CFG.n_layers)] == [
        W, W, 0, W, W]
    assert CFG.windowed and not TransformerConfig().windowed
    with pytest.raises(ValueError):
        TransformerConfig(n_layers=2, layer_types=["sliding_attention"] * 2)
    with pytest.raises(ValueError):
        TransformerConfig(n_layers=2, sliding_window=4,
                          layer_types=["sliding", "full_attention"])


def test_program_forward_is_the_reference(params, toks, want):
    _close(forward(params, toks, CFG), want)


@pytest.mark.parametrize("window", [0, W])
def test_layer_against_written_out_equations(params, window):
    """One attention sublayer written out: q/k head norms, rotation on a
    sliding layer only (NoPE on the full one), the band, the sigmoid gate
    on the heads' output before wo, and the post-attention norm."""
    L = params["layers"][2 if not window else 1]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, CFG.d_model))
    pos = jnp.arange(40)
    got = apply_layer(x, L, CFG, pos=pos, window=window)[0]
    eps, H, KV, hd = CFG.norm_eps, CFG.n_heads, CFG.kv_heads, CFG.head_dim

    def rms(t, g):
        return t / jnp.sqrt(jnp.mean(t * t, -1, keepdims=True) + eps) * g

    h = rms(x, L["ln1"]["g"])
    q = (h @ L["wq"]).reshape(1, 40, H, hd)
    kv = h @ L["wkv"].reshape(CFG.d_model, -1)
    k = kv[..., :KV * hd].reshape(1, 40, KV, hd)
    v = kv[..., KV * hd:].reshape(1, 40, KV, hd)
    q, k = rms(q, L["q_norm"]["g"]), rms(k, L["k_norm"]["g"])
    if window:
        half = hd // 2
        ang = np.arange(40)[:, None] * CFG.rope_theta ** (
            -np.arange(half) / half)
        c, s = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]

        def rot(t):
            a, b = t[..., :half], t[..., half:]
            return jnp.concatenate([a * c - b * s, a * s + b * c], -1)
        q, k = rot(q), rot(k)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    i, j = np.arange(40)[:, None], np.arange(40)[None, :]
    mask = (j <= i) & ((j > i - window) if window else True)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(mask, sc, -jnp.inf), -1), v).reshape(1, 40, H * hd)
    a = a * jax.nn.sigmoid(h @ L["attn_gate"])
    x1 = x + rms(a @ L["wo"], L["ln_attn_out"]["g"])
    h2 = rms(x1, L["ln2"]["g"])
    if "moe" in L:
        f, _ = moe.routed_ffn(L["moe"], h2, CFG)
    else:
        f = (jax.nn.silu(h2 @ L["wg"]) * (h2 @ L["wu"])) @ L["wd"]
    _close(got, x1 + rms(f, L["ln_ffn_out"]["g"]))


def test_embed_scale_is_the_mup_input_scale(params, toks):
    from rlo_tpu.models.transformer import embed_tokens
    _close(embed_tokens(params["embed"], toks, None, CFG),
           params["embed"][toks] * math.sqrt(CFG.d_model))


def _ring_shapes(cache):
    return [c["k"].shape[3] for c in cache]


def test_ring_decode_across_the_wrap(params, toks, want):
    """Prefill 20, then one step at a time to 60: each sliding layer's
    ring of 24 wraps twice, the full layer keeps every position."""
    cache = init_kv_cache(CFG, 2, 64, ring_spare=SPARE)
    assert _ring_shapes(cache) == [W + SPARE] * 2 + [64] + [W + SPARE] * 2
    lg, cache = prefill(params, toks[:, :20], cache, CFG)
    _close(lg, want[:, 19])
    step = jax.jit(lambda p, t, m, c: decode_step(p, t, m, c, CFG))
    for t in range(20, 60):
        lg, cache = step(params, toks[:, t], jnp.int32(t), cache)
        _close(lg, want[:, t])


def test_prefill_longer_than_the_ring_keeps_its_last_positions(
        params, toks, want):
    cache = init_kv_cache(CFG, 2, 64, ring_spare=SPARE)
    lg, cache = prefill(params, toks[:, :50], cache, CFG)
    _close(lg, want[:, 49])
    lg, cache = decode_step(params, toks[:, 50], jnp.int32(50), cache, CFG)
    _close(lg, want[:, 50])


def test_round_tail_and_fold_across_the_wrap(params, toks, want):
    """A round of 8 steps through the write-behind tail at positions whose
    rows cross the ring's end (ring 24: 42..49 land at 18..23 and 0..1),
    then the fold: its logits are the reference's, and the folded cache
    is what eight per-step writes leave."""
    cache = init_kv_cache(CFG, 2, 64, ring_spare=SPARE)
    _, cache = prefill(params, toks[:, :42], cache, CFG)
    per_step = cache
    tail = kvcache.init_kv_tail(cache, 8)
    windows = [CFG.layer_window(i) for i in range(CFG.n_layers)]
    for s in range(8):
        lg, tail = decode_step(params, toks[:, 42 + s], jnp.int32(42 + s),
                               cache, CFG, tail=(tail, s))
        _close(lg, want[:, 42 + s])
        _, per_step = decode_step(params, toks[:, 42 + s],
                                  jnp.int32(42 + s), per_step, CFG)
    folded = kvcache.fold_kv_tail(cache, tail, jnp.int32(42), windows)
    for a, b in zip(jax.tree.leaves(folded), jax.tree.leaves(per_step)):
        _close(a, b)
    lg, _ = decode_step(params, toks[:, 50], jnp.int32(50), folded, CFG)
    _close(lg, want[:, 50])


def test_extend_chunk_straddles_the_wrap(params, toks, want):
    """A block of 8 queries at 20..27 through rings of 24: its keys wrap
    to the ring's start, its queries see their bands across the wrap."""
    cache = init_kv_cache(CFG, 2, 64, ring_spare=SPARE)
    _, cache = prefill(params, toks[:, :20], cache, CFG)
    lg, cache = block_decode(params, toks[:, 20:28], jnp.asarray([20, 20]),
                             cache, CFG)
    _close(lg, want[:, 20:28])
    lg, cache = block_decode(params, toks[:, 28:36], jnp.asarray([28, 28]),
                             cache, CFG)
    _close(lg, want[:, 28:36])
    with pytest.raises(ValueError, match="ring"):
        block_decode(params, toks[:, 36:48], jnp.asarray([36, 36]), cache,
                     CFG)


# ---- (c) the kernels in interpret mode -----------------------------------

def _kv(b, nkv, d, R):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    kc = jax.random.normal(k1, (b, nkv, d, R)).astype(jnp.bfloat16)
    vc = jax.random.normal(k2, (b, nkv, d, R)).astype(jnp.bfloat16)
    return kc, vc, k3


@pytest.mark.parametrize("T", [1, 4])
def test_flash_window_kernel_matches_the_einsum(T):
    """Rows before the ring has come round, across one wrap and three:
    4 ring tiles of 128, a window of 200, (block) queries at pos0 + t."""
    from rlo_tpu.pallas.decode import flash_block_decode
    b, nkv, r, d, R, win = 3, 2, 3, 128, 512, 200
    kc, vc, key = _kv(b, nkv, d, R)
    q = jax.random.normal(key, (b, T, nkv * r, d)).astype(jnp.bfloat16)
    pos0 = jnp.asarray([100, 700, 1300], jnp.int32)
    got = flash_block_decode(q, kc, vc, pos0, 0.1, block_k=128,
                             interpret=True, window=win)
    ref = kvcache._attend_cache_block(
        q, kc, vc, pos0[:, None] + jnp.arange(T), 0.1, use_flash=False,
        window=win)
    _close(got, ref, 2e-3)


@pytest.mark.parametrize("newest", [0, 5])
def test_flash_window_kernel_with_the_tail_matches_the_einsum(newest):
    from rlo_tpu.pallas.decode import flash_block_decode
    b, nkv, r, d, R, win, kk = 3, 2, 3, 128, 512, 200, 8
    kc, vc, key = _kv(b, nkv, d, R)
    q = jax.random.normal(key, (b, 1, nkv * r, d)).astype(jnp.bfloat16)
    tk = jax.random.normal(jax.random.PRNGKey(6),
                           (kk, b, nkv, d)).astype(jnp.bfloat16)
    upto = jnp.asarray([-1, 700, 1300], jnp.int32)
    tail = (tk, tk * 0.5, jnp.int32(newest))
    got = flash_block_decode(q, kc, vc, upto, 0.1, block_k=128,
                             interpret=True, window=win, tail=tail)
    ref = kvcache._attend_cache_block(q, kc, vc, upto[:, None], 0.1,
                                      use_flash=False, window=win,
                                      tail=tail)
    _close(got, ref, 2e-3)


def test_window_work_list_walks_the_live_ring_tiles():
    from rlo_tpu.pallas.decode import window_tiles, window_work_list
    lo, hi = jnp.asarray([0, 300, 900, 5]), jnp.asarray([100, 700, 1300, 4])
    row_of, tile_of, n = window_work_list(lo, hi, 128, 4)
    assert int(n) == 1 + 4 + 4 + 1
    assert row_of.tolist()[:10] == [0, 1, 1, 1, 1, 2, 2, 2, 2, 3]
    assert tile_of.tolist()[:10] == [0, 2, 3, 0, 1, 3, 0, 1, 2, 0]
    count, first = window_tiles(np.asarray([0, 300, 900, 5]),
                                np.asarray([100, 700, 1300, 4]), 128, 4,
                                xp=np)
    assert count.tolist() == [1, 4, 4, 1] and first.tolist() == [0, 2, 3, 0]


def test_ring_block_write_wraps():
    from rlo_tpu.pallas.decode import write_kv_block
    b, nkv, d, R = 3, 2, 128, 512
    kc, _, key = _kv(b, nkv, d, R)
    rows = jax.random.normal(key, (b, nkv, d, 100)).astype(jnp.bfloat16)
    start = jnp.asarray([0, 450, 300], jnp.int32)
    got = write_kv_block(kc, rows, start, interpret=True, ring=True)
    cols = (start[:, None] + jnp.arange(100)) % R
    want = kc.at[jnp.arange(b)[:, None, None, None],
                 jnp.arange(nkv)[None, :, None, None],
                 jnp.arange(d)[None, None, :, None],
                 cols[:, None, None, :]].set(rows)
    assert bool((got == want).all())


def test_flash_attention_band_forward_and_gradient():
    """flash.py's band (prefill of a sliding layer longer than its window)
    against the oracle, value and gradient (the backward recomputes the
    same masked tiles)."""
    from rlo_tpu.ops.ring_attention import full_attention
    from rlo_tpu.pallas.flash import flash_attention
    q, k, v = (jax.random.normal(kk, (256, 2, 128)) for kk in
               jax.random.split(jax.random.PRNGKey(7), 3))

    def f(fn, **kw):
        return lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v, causal=True,
                                                  window=40, **kw)))

    got = jax.value_and_grad(f(flash_attention, block_q=128,
                               interpret=True), (0, 1, 2))(q, k, v)
    ref = jax.value_and_grad(f(full_attention), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        _close(a, b, 2e-4)


# ---- (d) the server -------------------------------------------------------

def test_server_runs_past_the_window_and_the_ring(params):
    """Prompts under and past the window, one past the widest bucket (an
    extend chunk through the ring), outputs that run past the ring: every
    request's tokens are the dense generate's, and the window counters
    hold their identities."""
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=3, max_len=128, round_len=8,
                       prompt_buckets=(16,), metrics=reg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab, n) for n in (9, 30, 70, 12)]
    outs = [40, 25, 30, 20]
    for p, n in zip(prompts, outs):
        srv.submit(p, n)
    got = srv.run()
    for p, n, g in zip(prompts, outs, got):
        ref = generate(params, jnp.asarray(p)[None], CFG, max_new=n,
                       max_len=128)
        np.testing.assert_array_equal(np.asarray(ref)[0], g)
    c = reg.snapshot()["counters"]
    assert 0 < c["serve.window.keys_in_window"] < c[
        "serve.window.keys_in_context"]
    assert "serve.window.lanes_fetched" not in c    # no kernel on the CPU
    ring = kvcache._ring_entry(srv.cache, CFG)["k"].shape[3]
    assert ring == W + 32       # the long extend chunk of buckets (16,)


def test_window_counters_follow_the_kernels_rule():
    """serve.window.* from the host's pos, as the round's attends run
    them: context, min(context, window), and whole ring tiles of the
    window's span (the tail's rows are not in the ring)."""
    srv = DecodeServer.__new__(DecodeServer)
    srv.cfg, srv.max_len, srv.metrics = CFG, 1024, Registry()
    srv.pos = np.asarray([3, 100], np.int32)
    srv._kv_tail, srv._window_tiling = True, (8, 4)    # a ring of 32
    srv._count_window(2)
    c = srv.metrics.snapshot()["counters"]
    n_win = 4
    ctx = np.asarray([[4, 5], [101, 102]])
    assert c["serve.window.keys_in_context"] == n_win * ctx.sum()
    assert c["serve.window.keys_in_window"] == n_win * np.minimum(
        ctx, W).sum()
    # row 0: positions 0..2 in tile 0; row 1: 85..99 and 86..99 -> three
    # tiles each (columns 21..3 and 22..3 of the ring)
    assert c["serve.window.lanes_fetched"] == n_win * 8 * (1 + 1 + 3 + 3)


# ---- (e) the share test ---------------------------------------------------

def test_expert_shares_sum_to_the_uncut_layer():
    """The held experts' parts of an expert layer, over all the shares of
    its router's experts, with the shared expert (and the attention before
    it) counted once, add up to the uncut layer."""
    from rlo_tpu.models.transformer import _rmsnorm
    whole = dataclasses.replace(CFG, n_experts_held=CFG.n_experts)
    L = init_params(jax.random.PRNGKey(9), whole)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 12, CFG.d_model))
    pos = jnp.arange(12)
    uncut = apply_layer(x, L, whole, pos=pos, window=W)[0]
    # the attention half, every chip's alike: the layer with an FFN that
    # adds nothing (its sandwich norm of zeros is zero)
    d = CFG.d_model
    none = {n: jnp.zeros(s_) for n, s_ in
            (("wg", (d, 1)), ("wu", (d, 1)), ("wd", (1, d)))}
    x1 = apply_layer(x, dict({k: v for k, v in L.items() if k != "moe"},
                             **none), whole, pos=pos, window=W)[0]
    h2 = _rmsnorm(x1, L["ln2"]["g"], CFG.norm_eps)
    held, M = CFG.experts_held, L["moe"]
    parts = 0.0
    for first in range(0, CFG.n_experts, held):
        share = dict(M, **{n: M[n][first:first + held]
                           for n in ("wg", "wu", "wd")})
        part, _ = moe.routed_ffn(
            share, h2, dataclasses.replace(CFG, expert_first=first))
        parts = parts + part
    shared = moe._gated(h2, M["swg"], M["swu"], M["swd"])
    f = parts - (CFG.n_experts // held - 1) * shared
    _close(x1 + _rmsnorm(f, L["ln_ffn_out"]["g"], CFG.norm_eps), uncut)


# ---- (f) the programs the benchmark's configurations already traced --------

#: sha256 (first 16 hex) of each existing configuration's parameter tree
#: (shapes and dtypes) and of the jaxprs of its forward, prefill, decode
#: step, block decode and DecodeServer round, at its tiny preset, as
#: PR 37 traced them: on the CPU, and with the kernel gates open for the
#: TPU (kernel bodies included). A PR that changes one of these programs
#: ON PURPOSE records the new digest here and says so: deepseek-v3.2-ep32's
#: TPU decode, block and round select through the topk_select kernel.
#: trinity-large-ep16's entries were recorded before hyper-connections,
#: attention sinks, shared selection and the clamped SwiGLU joined the
#: layer code, and hold after it (off: nothing changes).
DIGESTS = {"cpu": {
                "gpt2-medium.init": "dd8c92089d6d711c",
                "gpt2-medium.forward": "05c3c695836fd569",
                "gpt2-medium.prefill": "be1921211b614a5d",
                "gpt2-medium.decode": "8f7437b391ff517a",
                "gpt2-medium.block": "c2e4509c75fc9917",
                "gpt2-medium.round": "250078b4729af40d",
                "deepseek-v3-ep16.init": "4163ae0a579d9e36",
                "deepseek-v3-ep16.forward": "db6cfc54e41ba19a",
                "deepseek-v3-ep16.prefill": "e293e88d073ec863",
                "deepseek-v3-ep16.decode": "2a147d78fcaaf550",
                "deepseek-v3-ep16.block": "2758c1f05e3d8b84",
                "deepseek-v3-ep16.round": "05dd0d5dc3578d73",
                "deepseek-v3.2-ep32.init": "7febe268d0d9f783",
                "deepseek-v3.2-ep32.forward": "e622e11a7a303da3",
                "deepseek-v3.2-ep32.prefill": "3050885944e16bfc",
                "deepseek-v3.2-ep32.decode": "c456975b96786fbc",
                "deepseek-v3.2-ep32.block": "3c7d7af3f262bab3",
                "deepseek-v3.2-ep32.round": "bae24238dfe3a0e7",
                "sdar-30b-a3b-6l.init": "e2d9c7cb45311477",
                "sdar-30b-a3b-6l.forward": "6fd3a4668ad10944",
                "sdar-30b-a3b-6l.block": "243b46d4f482e513",
                "sdar-30b-a3b-6l.round": "e20950c7150a025d",
                "trinity-large-ep16.init": "db07edb5b5f99c7f",
                "trinity-large-ep16.forward": "59f60d00e7dc46f1",
                "trinity-large-ep16.prefill": "db39b0f41938e578",
                "trinity-large-ep16.decode": "f7f5be696541f799",
                "trinity-large-ep16.block": "39d5f74f2e0d920b",
                "trinity-large-ep16.round": "19ba1a4fa2ec1821",},
           "tpu": {
                "gpt2-medium.init": "dd8c92089d6d711c",
                "gpt2-medium.forward": "25bf5ab646b0f0fb",
                "gpt2-medium.prefill": "537e75ee81d0df5a",
                "gpt2-medium.decode": "705821b14c3d748c",
                "gpt2-medium.block": "052ecf9bcd7da2d1",
                "gpt2-medium.round": "08d2aea365f263f3",
                "deepseek-v3-ep16.init": "4163ae0a579d9e36",
                "deepseek-v3-ep16.forward": "8a0e311426028c17",
                "deepseek-v3-ep16.prefill": "a97dc524213aab29",
                "deepseek-v3-ep16.decode": "8ba295e5207e2d01",
                "deepseek-v3-ep16.block": "0e4ecab81a353f6c",
                "deepseek-v3-ep16.round": "37ad9dbb755ad047",
                "deepseek-v3.2-ep32.init": "7febe268d0d9f783",
                "deepseek-v3.2-ep32.forward": "09a20d8bb8882ff7",
                "deepseek-v3.2-ep32.prefill": "3a52625620d06a85",
                "deepseek-v3.2-ep32.decode": "37b4edf758549f2d",
                "deepseek-v3.2-ep32.block": "def86255f6685c69",
                "deepseek-v3.2-ep32.round": "d2b1d1e900dcb2a3",
                "sdar-30b-a3b-6l.init": "e2d9c7cb45311477",
                "sdar-30b-a3b-6l.forward": "8e95b39c5e0056bc",
                "sdar-30b-a3b-6l.block": "adc67bcc30b40356",
                "sdar-30b-a3b-6l.round": "24c36315fac99605",
                "trinity-large-ep16.init": "db07edb5b5f99c7f",
                "trinity-large-ep16.forward": "3c4e828c9b112238",
                "trinity-large-ep16.prefill": "2108ceed0efe7442",
                "trinity-large-ep16.decode": "031af144e5ab66de",
                "trinity-large-ep16.block": "76d35c8f7cae0060",
                "trinity-large-ep16.round": "4156b264007af7ee",}}


def _digests(tpu: bool, monkeypatch) -> dict:
    import rlo_tpu.pallas.reduce as red
    if tpu:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(red, "_on_tpu", lambda: True)

    def h(x):
        return hashlib.sha256(str(x).encode()).hexdigest()[:16]

    out = {}
    for name in ["gpt2-medium", "deepseek-v3-ep16", "deepseek-v3.2-ep32",
                 "sdar-30b-a3b-6l", "trinity-large-ep16"]:
        cfg = TransformerConfig(**json.loads(
            (CONFIGS / f"{name}.json").read_text())["tiny"]["model"])
        shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                                jax.random.PRNGKey(0))
        out[name + ".init"] = h(jax.tree.map(
            lambda a: (a.shape, str(a.dtype)), shapes))
        p = init_params(jax.random.PRNGKey(0), cfg)
        cache = init_kv_cache(cfg, 4, 512)
        toks = jnp.zeros((4, 16), jnp.int32)
        out[name + ".forward"] = h(jax.make_jaxpr(
            lambda p, t: forward(p, t, cfg))(p, toks))
        if not cfg.block_len:
            out[name + ".prefill"] = h(jax.make_jaxpr(
                lambda p, t, c: prefill(p, t, c, cfg,
                                        last_index=jnp.array([3, 4, 5, 6])))(
                    p, toks, cache))
            out[name + ".decode"] = h(jax.make_jaxpr(
                lambda p, t, c: decode_step(p, t, jnp.array([3, 4, 5, 6]),
                                            c, cfg))(
                    p, toks[:, 0], cache))
        T = 4 if cfg.block_len else 128
        out[name + ".block"] = h(jax.make_jaxpr(
            lambda p, t, c: block_decode(p, t, jnp.array([4, 8, 12, 16]), c,
                                         cfg))(
                p, jnp.zeros((4, T), jnp.int32), cache))
        srv = DecodeServer(p, cfg, n_slots=4, max_len=512, metrics=Registry(),
                           prompt_buckets=(16,) if cfg.dsa else (16, 64))
        z = jnp.zeros((4,), jnp.int32)
        if cfg.block_len:
            B = cfg.block_len
            tr = srv._round.trace(p, srv.cache, jnp.zeros((4, B), jnp.int32),
                                  jnp.zeros((4, B), bool), z, z, z,
                                  kk=srv.round_len)
        else:
            tr = srv._round.trace(p, srv.cache, z, z, kk=srv.round_len)
        out[name + ".round"] = h(tr.jaxpr)
    return out


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_existing_configurations_trace_what_they_traced(backend,
                                                        monkeypatch):
    assert _digests(backend == "tpu", monkeypatch) == DIGESTS[backend]
