"""Generation by diffusion over blocks (SDAR-30B-A3B, perf/configs/
sdar-30b-a3b-6l) on the serving path, at the configuration's tiny preset
with seeded float32 weights on the CPU, against its plain reference
(perf/configs/sdar-30b-a3b-6l.py: full forwards under the block-causal
mask, the sampler as a plain loop):

  (a) the layer: head_dim apart from d_model / n_heads, per-head q/k norm,
      the softmax top-k router with renormalised weights;
  (b) prefill then passes through the cache equal the reference's full
      forward at the block's positions, whatever plen mod 4;
  (c) with a head scaled until confidences pass 0.9, rows unmask 1 to 4
      positions a pass and finish blocks at different paces in one round,
      and DecodeServer delivers the reference sampler's tokens;
  (d) outputs that 4 does not divide, admission in the middle of a run, a
      slot reused, a prompt past the widest bucket;
and the counter identities of ``serve.diffusion.*``, and the share test of
the model-configs guide for the softmax router.
"""

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models import moe
from rlo_tpu.models.generate import (block_decode, denoise_update,
                                     init_kv_cache, prefill)
from rlo_tpu.models.serve import BLOCK_STATS, DecodeServer
from rlo_tpu.models.transformer import (TransformerConfig, forward,
                                        init_params)
from rlo_tpu.utils.metrics import Registry

CONFIGS = Path(__file__).resolve().parent.parent / "perf" / "configs"
_spec = importlib.util.spec_from_file_location(
    "sdar_reference", CONFIGS / "sdar-30b-a3b-6l.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

MODEL = dict(json.loads((CONFIGS / "sdar-30b-a3b-6l.json").read_text())
             ["tiny"]["model"], dtype="float32", param_dtype="float32")
CFG = TransformerConfig(**MODEL)
B = CFG.block_len


def _close(got, want, tol=2e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.fixture(scope="module")
def params():
    """Seeded weights whose norm gains are not all ones, so that a gain
    left out shows."""
    p = init_params(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))

    def gains(tree):
        return {"g": 1.0 + 0.3 * jax.random.normal(next(keys),
                                                   tree["g"].shape)}

    p["ln_f"] = gains(p["ln_f"])
    for layer in p["layers"]:
        for name in ("ln1", "ln2", "q_norm", "k_norm"):
            layer[name] = gains(layer[name])
    return p


def _sharp(params, alpha=20.0):
    """The same model with its head scaled: confidences on both sides of
    0.9, so a pass unmasks 1, 2, 3 or 4 positions."""
    return dict(params, head=params["head"] * alpha)


def _prompt(seed, plen):
    return np.random.default_rng(seed).integers(0, CFG.vocab, size=plen)


# ---- (a) the layer -------------------------------------------------------

def test_the_configuration_sets_the_head_apart_from_the_width(params):
    assert CFG.head_dim == 32 != CFG.d_model // CFG.n_heads
    layer = params["layers"][0]
    assert layer["wq"].shape == (CFG.d_model, CFG.n_heads * 32)
    assert layer["wkv"].shape == (CFG.d_model, 2, CFG.kv_heads * 32)
    assert layer["wo"].shape == (CFG.n_heads * 32, CFG.d_model)
    assert layer["q_norm"]["g"].shape == layer["k_norm"]["g"].shape == (32,)
    assert "br" not in layer["moe"]


def test_forward_equals_the_reference_under_the_block_mask(params):
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 6 * B), 0,
                              CFG.vocab)
    _close(forward(params, toks, CFG), REF.logits(params, toks, MODEL))


def test_block_mask_is_not_the_causal_one(params):
    """Changing the LAST token of a block moves the logits of the block's
    first position, and of no earlier block."""
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (1, 3 * B),
                                         0, CFG.vocab))
    other = toks.copy()
    other[0, 2 * B - 1] = (other[0, 2 * B - 1] + 1) % CFG.vocab
    a = np.asarray(forward(params, jnp.asarray(toks), CFG))
    b = np.asarray(forward(params, jnp.asarray(other), CFG))
    assert np.abs(a[0, :B] - b[0, :B]).max() == 0
    assert np.abs(a[0, B] - b[0, B]).max() > 1e-4


def test_softmax_router_keeps_the_top_k_and_renormalises(params):
    m = params["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, CFG.d_model))
    ids, w, scores = moe.route_softmax(x, m["wr"], top_k=CFG.experts_per_tok)
    _, rec = REF._experts(x, m, MODEL, None)
    _close(scores, rec["choice"])
    assert (np.sort(np.asarray(ids), -1) == np.asarray(rec["ids"])).all()
    _close(jnp.sum(w, -1), np.ones(40))
    _close(jnp.sum(scores, -1), np.ones(40))
    # the weights are the chosen probabilities over their own sum
    picked = np.take_along_axis(np.asarray(scores), np.asarray(ids), -1)
    _close(w, picked / picked.sum(-1, keepdims=True))


@pytest.mark.parametrize("path", ["oracle", "kernel"])
def test_expert_layer_equals_the_reference(params, path):
    m = params["layers"][0]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (3, 8, CFG.d_model))
    want, _ = REF._experts(h, m, MODEL, None)
    x = h.reshape(-1, CFG.d_model)
    ids, w, _ = moe.route_softmax(x, m["wr"], top_k=CFG.experts_per_tok)
    out, stats = moe.held_experts_ffn(m, x, ids, w, 0,
                                      use_kernel=path == "kernel",
                                      interpret=True)
    _close(out.reshape(h.shape), want)
    assert int(stats[1]) == 24 * CFG.experts_per_tok and int(stats[4]) == 0


def test_shares_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, for the softmax router:
    the parts that the four shares of 2 of 8 experts give add up to what
    the uncut layer (all 8 held) gives; the layer keeps what expert
    parallelism will ask of it."""
    whole_cfg = dataclasses.replace(CFG, n_experts=8, n_experts_held=0,
                                    experts_per_tok=3)
    model = dict(MODEL, n_experts=8, experts_per_tok=3)
    whole = moe.init_routed_params(jax.random.PRNGKey(11), whole_cfg)
    h = jax.random.normal(jax.random.PRNGKey(12), (2, 16, CFG.d_model))
    uncut, _ = moe.routed_ffn(whole, h, whole_cfg)
    want, _ = REF._experts(h, whole, dict(model, n_experts_held=8), None)
    _close(uncut, want)
    total = 0.0
    for share in range(4):
        first = 2 * share
        cfg = dataclasses.replace(whole_cfg, expert_first=first,
                                  n_experts_held=2)
        mine = {k: (v[first:first + 2] if k in ("wg", "wu", "wd") else v)
                for k, v in whole.items()}
        part, info = moe.routed_ffn(mine, h, cfg)
        ref_part, _ = REF._experts(
            h, mine, dict(model, expert_first=first), None)
        _close(part, ref_part)
        assert int(info["stats"][4]) == 0
        total = total + part
    _close(total, uncut)


# ---- (b) prefill, then passes through the cache -------------------------

@pytest.mark.parametrize("plen", [8, 9, 11])
def test_prefill_then_passes_equal_the_full_forward(params, plen):
    """plen mod 4 in {0, 1, 3}: the whole blocks go through the bucket
    prefill (padded, no head), the leftover opens the first block; each
    pass's logits at the block's positions are the reference's full
    forward on the same tokens, masks included, and a committed block's
    K/V rows stand for the next block."""
    n_full = plen // B * B
    seq = _prompt(plen, n_full + 2 * B)
    prompt = np.zeros((1, 16), np.int32)
    prompt[0, :plen] = seq[:plen]
    cache = init_kv_cache(CFG, 1, 64)
    none, cache = prefill(params, jnp.asarray(prompt), cache, CFG,
                          need_logits=False)
    assert none is None
    step = jax.jit(lambda p, blk, at, c: block_decode(p, blk, at, c, CFG))
    ref = jax.jit(lambda p, t: REF.logits(p, t, MODEL))
    for start in (n_full, n_full + B):      # two blocks
        known = plen - n_full if start == n_full else 0
        for shown in range(known, B + 1):   # denoise ... then the commit
            blk = seq[start:start + B].copy()
            blk[shown:] = CFG.mask_id
            got, cache = step(params, jnp.asarray(blk[None]),
                              jnp.asarray([start]), cache)
            full = np.concatenate([seq[:start], blk])[None]
            want = ref(params, jnp.asarray(full))
            _close(got[0], want[0, start:], tol=5e-5)


def test_unmask_rule_against_the_reference():
    """1 to 4 positions a pass, the lone best one where none is sure, the
    lowest position on a tie, nothing on a block with no mask."""
    rng = np.random.default_rng(0)
    lg = rng.normal(size=(6, B, 32)).astype(np.float32)
    lg[0, :, 5] += 20.0                       # all four sure
    lg[1, 1, 7] += 20.0                       # one sure
    lg[2, :, :] = 0.0                         # a four-way tie: lowest
    lg[3, 2, 3] += 20.0
    lg[3, 3, 3] += 20.0                       # two sure
    masked = np.ones((6, B), bool)
    masked[2, 0] = False                      # tie among positions 1..3
    masked[4] = False                         # a commit: nothing to do
    masked[5, :2] = False
    block = rng.integers(0, 32, size=(6, B)).astype(np.int32)
    new, still, gone = denoise_update(jnp.asarray(lg), jnp.asarray(block),
                                      jnp.asarray(masked), 0.9)
    for r in range(6):
        if not masked[r].any():
            assert (np.asarray(new[r]) == block[r]).all()
            assert not np.asarray(gone[r]).any()
            continue
        cand, go = REF.unmask(lg[r], masked[r], 0.9)
        assert (np.asarray(gone[r]) == go).all(), r
        assert (np.asarray(still[r]) == (masked[r] & ~go)).all()
        assert (np.asarray(new[r]) == np.where(go, cand, block[r])).all()
    assert [int(np.asarray(gone[r]).sum()) for r in range(4)] == [4, 1, 1, 2]
    assert int(np.argmax(np.asarray(gone[2]))) == 1


# ---- (c), (d) the server --------------------------------------------------

def _serve(params, requests, *, n_slots=3, round_len=4, buckets=(8, 16),
           late=()):
    """``requests`` (and, after the first round, ``late``) through a
    DecodeServer; returns (outputs, counters, server)."""
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=n_slots, max_len=64,
                       round_len=round_len, prompt_buckets=buckets,
                       metrics=reg)
    for prompt, max_new in requests:
        srv.submit(prompt, max_new)
    if late:
        srv.step_round()
        for prompt, max_new in late:
            srv.submit(prompt, max_new)
    return srv.run(), reg.snapshot()["counters"], srv


def _held_to_the_reference(params, requests, outs):
    for (prompt, max_new), out in zip(requests, outs):
        want = REF.sample(params, prompt, max_new, MODEL)
        assert out.tolist() == want.tolist(), (len(prompt), max_new)


def _identities(c, requests, n_slots):
    d = {name: c.get("serve.diffusion." + name, 0) for name in BLOCK_STATS}
    assert d["row_passes"] == d["denoise_passes"] + d["commit_passes"]
    assert d["blocks_committed"] == d["commit_passes"]
    assert d["leftover_committed"] == sum(len(p) % B for p, _ in requests)
    assert d["tokens_committed"] == (B * d["blocks_committed"]
                                     - d["surplus_dropped"]
                                     - d["leftover_committed"])
    assert d["tokens_committed"] == c["serve.tokens_out"] == sum(
        n for _, n in requests)
    assert c["serve.moe.dropped"] == 0
    assert c["serve.moe.tokens"] == (c["serve.steps"] * B * n_slots
                                     * CFG.n_layers)
    return d


def test_rows_unmask_one_to_four_a_pass_at_their_own_pace(params):
    """(c): a sharpened head; four rows side by side in one round in
    different phases; the delivered tokens are the reference sampler's."""
    sharp = _sharp(params)
    requests = [(_prompt(3, 8), 24), (_prompt(4, 8), 24),
                (_prompt(5, 12), 20), (_prompt(6, 12), 20)]
    paces = []
    for prompt, max_new in requests:
        REF.sample(sharp, prompt, max_new, MODEL, trace=paces)
    assert set(paces) == {1, 2, 3, 4}
    reg = Registry()
    srv = DecodeServer(sharp, CFG, n_slots=4, max_len=64, round_len=8,
                       prompt_buckets=(8, 16), metrics=reg)
    for prompt, max_new in requests:
        srv.submit(prompt, max_new)
    srv.step_round()
    # one round of 8 passes: the rows stand at different blocks
    blocks_done = (srv.pos - np.array([8, 8, 12, 12])) // B
    assert len(set(blocks_done.tolist())) > 1, blocks_done
    outs = srv.run()
    _held_to_the_reference(sharp, requests, outs)
    c = reg.snapshot()["counters"]
    d = _identities(c, requests, 4)
    assert d["tokens_unmasked"] > d["denoise_passes"]
    assert d["tokens_unmasked"] == sum(paces)
    assert d["denoise_passes"] == len(paces)
    # more than the floor of the rule: 0.8 tokens a pass and row
    assert d["tokens_committed"] / d["row_passes"] > 0.8


def test_ragged_outputs_late_admission_and_a_reused_slot(params):
    """(d): outputs 4 does not divide, prompts with 0 to 3 leftover
    tokens, one shorter than a block, one past the widest bucket; two
    slots for seven requests, two of them submitted after the first
    round. At random weights a denoise pass unmasks exactly one
    position."""
    first = [(_prompt(10, 8), 8), (_prompt(11, 9), 5), (_prompt(12, 11), 10),
             (_prompt(13, 3), 7), (_prompt(14, 21), 6)]
    late = [(_prompt(15, 10), 1), (_prompt(16, 6), 9)]
    outs, c, srv = _serve(params, first, n_slots=2, late=late)
    requests = first + late
    assert [len(o) for o in outs] == [n for _, n in requests]
    _held_to_the_reference(params, requests, outs)
    d = _identities(c, requests, 2)
    assert d["tokens_unmasked"] == d["denoise_passes"]
    assert c["serve.admissions"] == c["serve.requests_completed"] == 7
    assert c["serve.admit.batched_rows"] == 6     # all but the long prompt
    assert c["serve.prefill_tokens"] == sum(len(p) // B * B
                                            for p, _ in requests)
    hist = srv.stats()["histograms"]
    assert hist["serve.ttft_usec"]["count"] == 7
    assert srv.stats()["gauges"]["serve.cache_bytes_per_token"] == (
        CFG.n_layers * 2 * CFG.kv_heads * CFG.head_dim * 4)
    assert srv.stats()["gauges"]["serve.moe.ffn_steps_per_tile"] == 1
    assert srv.slot_ownership() == (None, None)


def test_floor_of_the_rule_is_four_fifths_of_a_token_a_pass(params):
    """Random weights: confidences far under 0.9, so a block of 4 takes 4
    denoise passes and 1 commit; with outputs 4 divides and no leftover,
    tokens_committed / row_passes is exactly 0.8."""
    requests = [(_prompt(20, 8), 8), (_prompt(21, 16), 12)]
    outs, c, _ = _serve(params, requests, round_len=5)
    d = _identities(c, requests, 3)
    assert d["surplus_dropped"] == d["leftover_committed"] == 0
    assert d["tokens_committed"] * 5 == d["row_passes"] * 4


def test_eos_ends_a_row_inside_a_block(params):
    prompt = _prompt(30, 8)
    want = REF.sample(params, prompt, 12, MODEL).tolist()
    eos = want[5]
    cut = want.index(eos) + 1
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=64, round_len=4,
                       prompt_buckets=(8, 16), metrics=reg)
    srv.submit(prompt, 12, eos_id=eos)
    assert srv.run()[0].tolist() == want[:cut]


def test_what_the_server_refuses(params):
    with pytest.raises(ValueError, match="dense scheduler"):
        DecodeServer(params, CFG, n_slots=2, max_len=64, paged=True,
                     page_size=16)
    with pytest.raises(ValueError, match="whole blocks"):
        DecodeServer(params, CFG, n_slots=2, max_len=64,
                     prompt_buckets=(6,))
    srv = DecodeServer(params, CFG, n_slots=2, max_len=62,
                       prompt_buckets=(8,), metrics=Registry())
    with pytest.raises(ValueError, match="rounded up"):
        srv.submit(_prompt(0, 9), 53)       # 62 fits, 64 does not
