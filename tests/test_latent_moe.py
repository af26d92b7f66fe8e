"""Latent attention with a latent cache, sigmoid group-limited routing over
one chip's share of the experts, and their kernels, against the plain
reference of the ``deepseek-v3-ep16`` configuration
(perf/configs/deepseek-v3-ep16.py) at small widths on the CPU. Activations
are float32 so that selections are exact."""

import dataclasses
import importlib.util
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models import moe
from rlo_tpu.models.generate import (block_decode, decode_step,
                                     init_kv_cache, prefill)
from rlo_tpu.models.serve import DecodeServer
from rlo_tpu.models.transformer import (TransformerConfig, _rope_cfg,
                                        _yarn_freqs, forward, init_params)
from rlo_tpu.pallas.decode import (can_flash_decode, flash_block_decode,
                                   flash_decode, flash_decode_tile)
from rlo_tpu.pallas import expert_ffn as ek
from rlo_tpu.pallas.expert_ffn import (buffer_rows, can_expert_ffn,
                                       expert_ffn, steps_per_tile)
from rlo_tpu.utils.metrics import Registry

PERF = Path(__file__).resolve().parent.parent / "perf"


def _reference():
    path = PERF / "configs" / "deepseek-v3-ep16.py"
    spec = importlib.util.spec_from_file_location("dsv3_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()
CONFIG = json.loads((PERF / "configs" / "deepseek-v3-ep16.json").read_text())

#: the configuration's ``tiny`` overlay in float32, 8 of 32 experts held,
#: one dense and one expert layer
MODEL = dict(CONFIG["tiny"]["model"], dtype="float32",
             param_dtype="float32", n_layers=2)
CFG = TransformerConfig(**MODEL)


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(8), (2, 40), 0, CFG.vocab)


def _close(got, want, rtol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max(), np.abs(want).max())


# ---- the configuration file -------------------------------------------

def test_config_file_keeps_every_published_key_and_no_width_differs():
    pub, reduced = CONFIG["published"], set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    for key, value in pub.items():
        assert key in CONFIG, key
        if key not in reduced:
            assert CONFIG[key] == value, key
    m = CONFIG["model"]
    assert (m["d_model"], m["d_ff"], m["moe_d_ff"]) == (
        pub["hidden_size"], pub["intermediate_size"],
        pub["moe_intermediate_size"])
    assert (m["q_lora_rank"], m["kv_lora_rank"], m["qk_nope_head_dim"],
            m["qk_rope_head_dim"], m["v_head_dim"], m["n_heads"]) == (
        pub["q_lora_rank"], pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["num_attention_heads"])
    assert (m["n_experts"], m["experts_per_tok"], m["n_group"],
            m["topk_group"], m["routed_scale"]) == (
        pub["n_routed_experts"], pub["num_experts_per_tok"],
        pub["n_group"], pub["topk_group"], pub["routed_scaling_factor"])
    assert (m["n_layers"], m["n_dense_layers"], m["n_experts_held"],
            m["vocab"]) == (CONFIG["num_hidden_layers"],
                            CONFIG["first_k_dense_replace"],
                            CONFIG["n_routed_experts"],
                            CONFIG["vocab_size"])
    TransformerConfig(**m)  # every key is a setting the program has


# ---- YaRN ---------------------------------------------------------------

def test_yarn_frequencies_against_hand_values():
    """64 rope dims, theta 10000, factor 40, original length 4096, beta
    32 / 1: cd(32) = 64 ln(4096 / 64 pi) / (2 ln 1e4) = 10.47 -> low 10;
    cd(1) = 22.51 -> high 23. Pairs 0..10 keep theta^(-i/32), pairs
    23..31 are divided by 40, pair 16 blends (16 - 10) / 13 of the way."""
    f = _yarn_freqs(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    plain = 10000.0 ** (-np.arange(32) / 32)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-6)
    r = 6 / 13
    np.testing.assert_allclose(f[16], plain[16] * (1 - r)
                               + plain[16] / 40 * r, rtol=1e-6)
    np.testing.assert_allclose(f[1], 10000.0 ** (-1 / 32), rtol=1e-6)
    model = CONFIG["model"]
    np.testing.assert_allclose(f, REF.yarn_frequencies(model), rtol=1e-6)
    cfg = TransformerConfig(**model)
    m = 0.1 * math.log(40) + 1
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * m * m)
    # mscale / mscale_all_dim = 1: a rotation, lengths kept
    t = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 64))
    rot = _rope_cfg(t, jnp.arange(5) + 4000, cfg)
    np.testing.assert_allclose(jnp.linalg.norm(rot, axis=-1),
                               jnp.linalg.norm(t, axis=-1), rtol=1e-5)


# ---- the model against the reference -----------------------------------

def test_full_forward_matches_reference(params, tokens):
    _close(forward(params, tokens, CFG), REF.logits(params, tokens, MODEL))


def test_prefill_then_decode_matches_reference(params, tokens):
    want = REF.logits(params, tokens, MODEL)
    cache = init_kv_cache(CFG, 2, 64)
    assert set(cache[0]) == {"k"} and cache[0]["k"].shape == (
        2, 1, CFG.kv_lora_rank + CFG.qk_rope_head_dim, 64)
    plens = jnp.asarray([24, 32])
    padded = tokens[:, :32] * (jnp.arange(32)[None] < plens[:, None])
    lg, cache = prefill(params, padded, cache, CFG, last_index=plens - 1)
    _close(lg, want[jnp.arange(2), plens - 1])
    for s in range(6):
        pos = plens + s
        lg, cache = decode_step(params, tokens[jnp.arange(2), pos], pos,
                                cache, CFG)
        _close(lg, want[jnp.arange(2), pos])


def test_block_decode_matches_reference(params, tokens):
    want = REF.logits(params, tokens, MODEL)
    cache = init_kv_cache(CFG, 2, 64)
    _, cache = prefill(params, tokens[:, :16], cache, CFG)
    lg, _ = block_decode(params, tokens[:, 16:24], jnp.asarray([16, 16]),
                         cache, CFG)
    _close(lg, want[:, 16:24])


def test_absorbed_equals_unabsorbed(params, tokens):
    """Decode (absorbed, against latent rows) and prefill (plain form)
    of the same positions."""
    cache = init_kv_cache(CFG, 2, 64)
    lg_p, _ = prefill(params, tokens[:, :20], cache, CFG)
    _, cache = prefill(params, tokens[:, :19], init_kv_cache(CFG, 2, 64),
                       CFG)
    lg_d, _ = decode_step(params, tokens[:, 19], 19, cache, CFG)
    _close(lg_d, lg_p, rtol=1e-5)


# ---- routing ------------------------------------------------------------

def _route(x, p):
    return moe.route(x, p["wr"], p["br"], n_group=CFG.n_group,
                     topk_group=CFG.topk_group, top_k=CFG.experts_per_tok,
                     routed_scale=CFG.routed_scale)


def test_routing_matches_reference(params):
    p = params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(3), (64, CFG.d_model))
    ids, w, choice = _route(x, p)
    scores = jax.nn.sigmoid(x @ p["wr"])
    np.testing.assert_allclose(choice, scores + p["br"], atol=1e-6)
    want_ids, margin = REF.select(choice, MODEL)
    np.testing.assert_array_equal(np.sort(ids, -1), want_ids)
    assert float(margin.min()) > 0
    # group limit: the chosen experts lie in at most topk_group groups
    groups = np.asarray(ids) // (CFG.n_experts // CFG.n_group)
    assert max(len(set(g)) for g in groups) <= CFG.topk_group
    # the bias moves the choice and not the weights
    no_bias, _ = REF.select(scores, MODEL)
    assert (np.asarray(no_bias) != np.asarray(want_ids)).any()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * CFG.routed_scale,
        rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), CFG.routed_scale,
                               rtol=1e-5)


@pytest.mark.parametrize("path", ["oracle", "kernel"])
def test_expert_layer_matches_reference(params, path):
    p = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 24, CFG.d_model))
    want, rec = REF._experts(h, p, MODEL, None)
    x = h.reshape(-1, CFG.d_model)
    ids, w, _ = _route(x, p)
    out, stats = moe.held_experts_ffn(
        p, x, ids, w, CFG.expert_first, use_kernel=path == "kernel",
        interpret=True)
    shared = moe._gated(x, p["swg"], p["swu"], p["swd"])
    _close((out + shared).reshape(h.shape), want)
    held = (np.asarray(rec["ids"]) < CFG.experts_held).sum()
    tokens_, here, rows, hit, dropped = (int(s) for s in stats)
    assert (tokens_, here, dropped) == (48, held, 0)
    assert rows >= here and rows % moe.row_tile(48 * 4, CFG.n_experts) == 0
    assert 1 <= hit <= CFG.experts_held


@pytest.mark.parametrize("path", ["oracle", "kernel"])
def test_no_drop_when_every_token_goes_to_one_expert(params, path):
    """The worst skew: every token's every choice lands on held experts,
    most on one. Nothing is dropped and the result is still exact."""
    p = dict(params["layers"][1]["moe"])
    br = np.full((CFG.n_experts,), -1.0, np.float32)
    br[[2, 0, 1, 3]] = [3.0, 1.0, 1.0, 1.0]    # experts 0-3, all held
    p["br"] = jnp.asarray(br)
    x = jax.random.normal(jax.random.PRNGKey(5), (96, CFG.d_model))
    ids, w, _ = _route(x, p)
    assert set(np.asarray(ids).ravel()) == {0, 1, 2, 3}
    want, _ = REF._experts(x, p, MODEL, None)
    out, stats = moe.held_experts_ffn(
        p, x, ids, w, 0, use_kernel=path == "kernel", interpret=True)
    _close(out + moe._gated(x, p["swg"], p["swu"], p["swd"]), want)
    assert int(stats[1]) == 96 * CFG.experts_per_tok   # every one here
    assert int(stats[4]) == 0                          # none dropped


def test_shares_add_up_to_the_uncut_layer(params):
    """The share test of the model-configs guide: the parts that the four
    shares of 8 experts give, with the shared expert counted once, add
    up to what the uncut layer (all 32 held) gives."""
    whole_cfg = dataclasses.replace(CFG, n_experts_held=0)
    whole = moe.init_routed_params(jax.random.PRNGKey(11), whole_cfg)
    h = jax.random.normal(jax.random.PRNGKey(12), (2, 16, CFG.d_model))
    uncut, _ = moe.routed_ffn(whole, h, whole_cfg)
    want, _ = REF._experts(h, whole, dict(MODEL, n_experts_held=32), None)
    _close(uncut, want)
    x = h.reshape(-1, CFG.d_model)
    shared = moe._gated(x, whole["swg"], whole["swu"], whole["swd"])
    total = -3.0 * shared           # four shares count it four times
    for share in range(4):
        first = 8 * share
        cfg = dataclasses.replace(CFG, expert_first=first,
                                  n_experts_held=8)
        mine = {k: (v[first:first + 8] if k in ("wg", "wu", "wd") else v)
                for k, v in whole.items()}
        part, info = moe.routed_ffn(mine, h, cfg)
        ref_part, _ = REF._experts(
            h, mine, dict(MODEL, expert_first=first), None)
        _close(part, ref_part)
        total = total + part.reshape(-1, CFG.d_model)
    _close(total, uncut.reshape(-1, CFG.d_model))


# ---- kernels, interpreted ----------------------------------------------

def _ffn_against_einsum(te, n_live, seed=0, d=256, f=384, held=4, tile=16):
    """expert_ffn, interpreted, on the first ``n_live`` of the tiles
    whose experts ``te`` names, against the einsum over each row's own
    expert."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    n_rows = len(te) * tile
    x = jax.random.normal(ks[0], (n_rows, d))
    wg = jax.random.normal(ks[1], (held, d, f)) * d ** -0.5
    wu = jax.random.normal(ks[2], (held, d, f)) * d ** -0.5
    wd = jax.random.normal(ks[3], (held, f, d)) * f ** -0.5
    out = expert_ffn(x, wg, wu, wd, jnp.asarray(te, jnp.int32), n_live,
                     tile=tile, interpret=True)
    assert out.shape == x.shape
    e = np.repeat(te, tile)[:n_live * tile]
    xs = x[:n_live * tile]
    want = jnp.einsum("rf,rfd->rd", jax.nn.silu(
        jnp.einsum("rd,rdf->rf", xs, wg[e])) * jnp.einsum(
            "rd,rdf->rf", xs, wu[e]), wd[e])
    if n_live:
        _close(out[:n_live * tile], want, rtol=1e-5)


@pytest.mark.parametrize("n_live,skew", [(3, False), (8, True), (0, False)])
def test_expert_ffn_kernel_against_einsum(n_live, skew):
    held, tile = 4, 16
    n_tiles = buffer_rows(64, held, tile) // tile
    # skew: one expert owns every live tile but the last
    te = np.where(np.arange(n_tiles) < 7, 1, 3) if skew else \
        np.minimum(np.arange(n_tiles), held - 1)
    _ffn_against_einsum(te, n_live, seed=n_live)


@pytest.mark.parametrize("d,f,itemsize,chunks,steps", [
    (2048, 768, 2, (2048, 768), 1),     # sdar-30b-a3b: whole, one step
    (7168, 2048, 2, (1024, 256), 15),   # deepseek-v3: 4 and 3.5 MiB blocks
    (7168, 2048, 4, (512, 128), 30),
    (256, 384, 4, (256, 384), 1),       # this file's toy shapes
    (128, 128, 4, (128, 128), 1)])
def test_expert_ffn_chunks_follow_the_byte_budget(d, f, itemsize, chunks,
                                                  steps):
    """The blocks a grid step presents: the widest 128-multiple
    divisors whose weight blocks fit ``_STEP_BYTES``, whole where the
    matrices fit (one step a tile then), never 0 where the gate says
    yes."""
    assert can_expert_ffn(d, f, 16)
    dk, fb = ek._chunks(d, f, itemsize)
    assert (dk, fb) == chunks
    assert steps_per_tile(d, f, itemsize) == steps
    for c, n, row in ((dk, d, f), (fb, f, d)):
        assert c and c % 128 == 0 and n % c == 0
        assert c * row * itemsize <= ek._STEP_BYTES
        wider = [w for w in range(c + 128, n + 1, 128) if n % w == 0]
        assert all(w * row * itemsize > ek._STEP_BYTES for w in wider)


# tiles of 16 rows over 4 held experts: expert 0 owns three tiles,
# expert 1 none, 2 and 3 one each; the last two tiles are dead
_SKEWED = np.array([0, 0, 0, 2, 3, 3, 3])


@pytest.mark.parametrize("d,f,budget,chunks", [
    (256, 384, None, (256, 384)),           # whole: the one-step body
    (256, 384, 128 * 384 * 4, (128, 128)),  # 2 + 3 steps a tile
    (512, 256, 256 * 256 * 4, (256, 128))])  # 2 + 2
@pytest.mark.parametrize("te,n_live", [(_SKEWED, 5), (np.arange(4), 4)],
                         ids=["skewed", "one_tile_each"])
def test_expert_ffn_kernel_at_each_chunking(monkeypatch, d, f, budget,
                                            chunks, te, n_live):
    """Both bodies, and the index maps that keep a fetch in flight, give
    the einsum's result whatever the budget chose."""
    if budget:
        monkeypatch.setattr(ek, "_STEP_BYTES", budget)
    assert ek._chunks(d, f, 4) == chunks
    assert steps_per_tile(d, f, 4) == (
        1 if budget is None else d // chunks[0] + f // chunks[1])
    _ffn_against_einsum(te, n_live, seed=7, d=d, f=f)


def test_expert_ffn_refuses_shapes_outside_its_gate():
    assert can_expert_ffn(7168, 2048, 16) and can_expert_ffn(128, 128, 16)
    assert not can_expert_ffn(200, 128, 16)
    assert not can_expert_ffn(128, 128, 12)
    x = jnp.zeros((32, 200))
    w = jnp.zeros((2, 200, 128))
    with pytest.raises(ValueError, match="expert_ffn"):
        expert_ffn(x, w, w, jnp.zeros((2, 128, 200)),
                   jnp.zeros((2,), jnp.int32), 1, tile=16, interpret=True)


def _latent_oracle(q, cache, pos, scale, v_dim):
    """q (b, T, H, d) against the latent stream (b, 1, d, L): query t of
    row b attends positions <= pos[b] + t."""
    b, T, H, d = q.shape
    L = cache.shape[3]
    s = jnp.einsum("bthd,bdk->bhtk", q, cache[:, 0]) * scale
    at = pos[:, None] + jnp.arange(T)[None, :]
    mask = jnp.arange(L)[None, None, :] <= at[:, :, None]
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhtk,bvk->bthv", p, cache[:, 0, :v_dim])


@pytest.mark.parametrize("T,L,block_k", [(1, 512, 128), (1, 384, 512),
                                         (3, 256, 128)])
def test_latent_attend_kernel_against_einsum(T, L, block_k):
    b, H, d, v_dim = 3, 4, 144, 128
    ks = jax.random.split(jax.random.PRNGKey(L + T), 2)
    q = jax.random.normal(ks[0], (b, T, H, d))
    cache = jax.random.normal(ks[1], (b, 1, d, L))
    pos = jnp.asarray([5, L - T, 200])
    got = flash_block_decode(q, cache, None, pos, 0.11, v_dim=v_dim,
                             block_k=block_k, interpret=True)
    assert got.shape == (b, T, H, v_dim)
    _close(got, _latent_oracle(q, cache, pos, 0.11, v_dim), rtol=1e-5)
    if T == 1:
        again = flash_decode(q, cache, None, pos, 0.11, v_dim=v_dim,
                             block_k=block_k, interpret=True)
        np.testing.assert_array_equal(np.asarray(again), np.asarray(got))


def test_latent_attend_gate_and_refusals():
    assert can_flash_decode(4096, 576, v_dim=512)
    assert not can_flash_decode(4096, 576)            # per-head cache rule
    assert not can_flash_decode(4096, 570, v_dim=512)  # ragged sublanes
    assert not can_flash_decode(4096, 576, v_dim=500)  # lane-hostile v
    assert not can_flash_decode(4096, 256, v_dim=512)  # v wider than k
    # gpt2-medium's choice is as it was; the latent cache has its own
    gpt2 = jax.ShapeDtypeStruct((96, 16, 64, 1024), jnp.bfloat16)
    assert flash_decode_tile(gpt2, 16) == 256
    latent = jax.ShapeDtypeStruct((128, 1, 576, 4096), jnp.bfloat16)
    assert flash_decode_tile(latent, 128, latent=True) in (256, 512, 1024)
    q = jnp.zeros((2, 1, 4, 144))
    cache = jnp.zeros((2, 1, 144, 256))
    with pytest.raises(ValueError, match="latent cache"):
        flash_decode(q, cache, None, 3, 1.0, interpret=True)   # no v_dim
    with pytest.raises(ValueError, match="latent cache"):
        flash_decode(q, cache, cache, 3, 1.0, v_dim=128, interpret=True)
    with pytest.raises(ValueError, match="latent cache"):
        flash_decode(q, cache, None, 3, 1.0, v_dim=100, interpret=True)


# ---- the server ---------------------------------------------------------

def test_server_counts_expert_work_and_matches_generate(params):
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=3, max_len=64, round_len=4,
                       prompt_buckets=(16, 32), metrics=reg)
    rng = np.random.default_rng(0)
    lens = (9, 20, 13, 30)
    prompts = [rng.integers(0, CFG.vocab, size=n) for n in lens]
    for pr in prompts:
        srv.submit(pr, 7)
    outs = srv.run()
    from rlo_tpu.models.generate import generate
    padded = np.zeros((4, 32), np.int32)
    for i, pr in enumerate(prompts):
        padded[i, :len(pr)] = pr
    want = generate(params, jnp.asarray(padded), CFG, max_new=7,
                    max_len=64, prompt_lengths=jnp.asarray(lens))
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, np.asarray(want)[i])
    snap = reg.snapshot()
    c, g = snap["counters"], snap["gauges"]
    n_moe = CFG.n_layers - CFG.n_dense_layers
    assert c["serve.moe.tokens"] == c["serve.steps"] * 3 * n_moe
    assert c["serve.moe.dropped"] == 0
    assert 0 < c["serve.moe.assignments_held"] <= c["serve.moe.rows_computed"]
    assert 0 < c["serve.moe.experts_hit"] <= (
        c["serve.steps"] * n_moe * CFG.experts_held)
    width = CFG.kv_lora_rank + CFG.qk_rope_head_dim
    assert g["serve.cache_bytes_per_token"] == width * 4 * CFG.n_layers
    # the expert kernel's byte rule at the routed layers' shape: whole
    assert g["serve.moe.ffn_steps_per_tile"] == steps_per_tile(
        CFG.d_model, CFG.moe_d_ff, 4) == 1
    # every round kept its latent rows in the write-behind tail: one
    # tensor a layer, two 128-lane blocks a slot in the fold
    assert c["serve.kv_tail.rounds"] == c["serve.rounds"]
    assert c["serve.kv_tail.rows"] == c["serve.slot_steps"]
    assert c["serve.kv_flush_blocks"] == (
        c["serve.rounds"] * 2 * 3 * CFG.n_layers)
