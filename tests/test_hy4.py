"""Hy4-preview (perf/configs/hy4-preview-ep32) on the serving path, at the
configuration's tiny preset (index_topk 16, the published 5-layer index
pattern full, full, shared, shared, shared) with seeded float32 weights on
the CPU, against its plain reference (perf/configs/hy4-preview-ep32.py: four
streams, full forwards, no cache, no kernels):

  (a) the configuration file and the program's settings;
  (b) the hyper-connection boundary against its equations at its three
      places (the first, between two sublayers, after the last), and
      the Sinkhorn's row and column sums;
  (c) the model against the reference: the forward, then prefill, extend
      chunks and decode steps through the cache across the point where
      the selector starts to choose (logits);
  (d) shared selection: a shared layer attends its full layer's set, bit
      for bit, and holds no selector;
  (e) the attention sink and the clamped SwiGLU against equations written
      out here, in every form that computes them;
  (f) the share test of the model-configs guide;
  (g) the server: its tokens are the step functions' greedy continuation,
      and its counters of scored and reused selections;
  (h) the paths that carry one residual or select per layer refuse.
"""

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models import kvcache, moe
from rlo_tpu.models.generate import (block_decode, decode_step,
                                     init_kv_cache, prefill)
from rlo_tpu.models.serve import DecodeServer
from rlo_tpu.models.transformer import (HC_SINKHORN_ITERS, TransformerConfig,
                                        _rmsnorm, apply_layer,
                                        attention_with_sinks, forward,
                                        hc_boundary, hc_enter, hc_mix,
                                        init_hc_params, init_params)
from rlo_tpu.utils.metrics import Registry

CONFIGS = Path(__file__).resolve().parent.parent / "perf" / "configs"
_spec = importlib.util.spec_from_file_location(
    "hy4_reference", CONFIGS / "hy4-preview-ep32.py")
REF = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(REF)

CONFIG = json.loads((CONFIGS / "hy4-preview-ep32.json").read_text())
MODEL = dict(CONFIG["tiny"]["model"], dtype="float32", param_dtype="float32")
CFG = TransformerConfig(**MODEL)
TOPK = CFG.index_topk
N = 64          # tokens a row: the selector chooses from position 16 on


def _close(got, want, tol=5e-5):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with norm gains that are not all ones."""
    p = init_params(jax.random.PRNGKey(0), CFG)
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 64))
    for L in p["layers"]:
        for n in ("ln1", "ln2", "q_norm", "kv_norm"):
            L[n]["g"] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                      L[n]["g"].shape)
    return p


@pytest.fixture(scope="module")
def toks():
    return jax.random.randint(jax.random.PRNGKey(2), (2, N), 0, CFG.vocab)


@pytest.fixture(scope="module")
def reference(params, toks):
    """The reference's logits at every position and its records (its own
    token sets and expert choices)."""
    lg, rec = jax.jit(lambda p, t: REF.forward(p, t, MODEL))(params, toks)
    return np.asarray(lg), rec


# ---- (a) the configuration ----------------------------------------------

def test_config_file_keeps_every_published_key_and_no_width_differs():
    pub, reduced = CONFIG["published"], set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "n_routed_experts",
                       "vocab_size", "indexer_types", "layer_types",
                       "mlp_layer_types", "num_nextn_predict_layers"}
    assert set(CONFIG["reduced_how"]) == reduced
    for key, value in pub.items():
        assert key in CONFIG, key
        if key not in reduced:
            assert CONFIG[key] == value, key
    for key in ("indexer_types", "layer_types", "mlp_layer_types"):
        assert CONFIG[key] == pub[key][:CONFIG["num_hidden_layers"]]
    m = CONFIG["model"]
    assert m["index_types"] == CONFIG["indexer_types"]
    assert (m["d_model"], m["n_heads"], m["q_lora_rank"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["d_ff"], m["moe_d_ff"], m["experts_per_tok"], m["n_experts"],
            m["index_n_heads"], m["index_head_dim"], m["index_topk"],
            m["hc_mult"], m["hc_magnitude"], m["hc_eps"], m["swiglu_limit"],
            m["routed_scale"], m["norm_eps"], m["rope_theta"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["q_lora_rank"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["num_experts_per_tok"], pub["n_routed_experts"],
        pub["index_n_heads"], pub["index_head_dim"], pub["index_topk"],
        pub["hc_mult"], pub["hc_magnitude"], pub["hc_eps"],
        pub["swiglu_limit"], pub["routed_scaling_factor"],
        pub["rms_norm_eps"], pub["rope_parameters"]["rope_theta"])
    assert (m["n_layers"], m["n_experts_held"], m["vocab"]) == (
        CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
        CONFIG["vocab_size"])
    assert m["n_dense_layers"] == CONFIG["mlp_layer_types"].count("dense")
    assert m["attn_gate"] and m["attn_sinks"] and m["lm_head_fp32"]
    assert "32 chips" in CONFIG["deployment"]
    for name in ("hc_sinkhorn_iters", "hc_readout", "hc_init",
                 "attn_gate_input", "routing", "swiglu_limit", "max_len"):
        assert name in CONFIG["assumed"], name
    assert "num_key_value_heads" in CONFIG["not_meaningful"]
    cfg = TransformerConfig(**m)
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 256))
    # five latent rows and two index keys: 6272 B a token
    assert kvcache.bytes_per_position(cache) == 6272
    assert REF.SINKHORN_ITERS == HC_SINKHORN_ITERS


def test_index_types_name_each_layers_selector():
    assert [CFG.index_kind(i) for i in range(CFG.n_layers)] == [
        "full", "full", "shared", "shared", "shared"]
    assert [CFG.selects(i) for i in range(CFG.n_layers)] == [
        True, True, False, False, False]
    v32 = dataclasses.replace(CFG, index_types=None)
    assert all(v32.selects(i) for i in range(v32.n_layers))
    for bad in (["shared", "full", "full", "full", "full"],
                ["full"] * 4, ["full", "sparse", "full", "full", "full"]):
        with pytest.raises(ValueError, match="index_types"):
            dataclasses.replace(CFG, index_types=tuple(bad))
    with pytest.raises(ValueError, match="index_types"):
        dataclasses.replace(CFG, index_topk=0)


def test_only_full_layers_hold_a_selector_and_index_keys(params):
    for i, L in enumerate(params["layers"]):
        assert ("wiq" in L) == CFG.selects(i)
        assert L["attn_sink"].shape == (CFG.n_heads,)
        assert L["attn_gate"].shape == (CFG.d_model,
                                        CFG.n_heads * CFG.v_head_dim)
        for name in ("hc_attn", "hc_ffn"):
            assert L[name]["phi"].shape == (24, 4 * CFG.d_model)
    assert params["head"].dtype == jnp.float32
    cache = init_kv_cache(CFG, 2, 128)
    assert [sorted(c) for c in cache] == [["ik", "k"]] * 2 + [["k"]] * 3


# ---- (b) the hyper-connection boundary ----------------------------------

@pytest.fixture(scope="module")
def streams():
    n, d = CFG.hc_mult, CFG.d_model
    hc = init_hc_params(jax.random.PRNGKey(3), dataclasses.replace(
        CFG, param_dtype="bfloat16"))
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    x = jax.random.normal(k1, (32, n * d)).astype(jnp.bfloat16)
    out = jax.random.normal(k2, (32, d)).astype(jnp.bfloat16)
    coef = hc_mix(x, None, None, hc, CFG)[2]
    return hc, x, out, coef


def _coefficients(mixed, hc):
    """The equations of a boundary's coefficients from the streams
    ``mixed`` (rows, n, d) float32: the norm over n·d, phi, the sigmoids
    and the Sinkhorn. Returns (h_pre, h_post, H)."""
    n, d = CFG.hc_mult, CFG.d_model
    xh = mixed.reshape(-1, n * d)
    xh = xh / np.sqrt(np.mean(xh * xh, -1, keepdims=True) + CFG.norm_eps)
    a = xh @ np.asarray(hc["phi"], np.float32).T
    s, b = np.asarray(hc["scale"]), np.asarray(hc["bias"])
    sig = lambda t: 1 / (1 + np.exp(-t))        # noqa: E731
    pre = sig(s[0] * a[:, :n] + b[:n])
    post = CFG.hc_magnitude * sig(s[1] * a[:, n:2 * n] + b[n:2 * n])
    H = np.exp(s[2] * a[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    for _ in range(HC_SINKHORN_ITERS):
        H = H / (H.sum(-1, keepdims=True) + CFG.hc_eps)
        H = H / (H.sum(-2, keepdims=True) + CFG.hc_eps)
    return pre, post, H


def _mixed(x, out, coef):
    """x'_i = sum_j H[i, j] x_j + h_post[i] out, rounded to bfloat16."""
    n, d = CFG.hc_mult, CFG.d_model
    c = np.asarray(coef)
    mixed = (np.einsum("rij,rjd->rid", c[:, 2 * n:].reshape(-1, n, n),
                       np.asarray(x, np.float32).reshape(-1, n, d))
             + c[:, n:2 * n, None] * np.asarray(out, np.float32)[:, None])
    return np.asarray(jnp.asarray(mixed).astype(jnp.bfloat16), np.float32)


@pytest.mark.parametrize("place", ["first", "after_the_last"])
def test_the_boundary_at_its_ends_against_its_equations(streams, place):
    """The first boundary (no sublayer before it: the streams pass as
    given, the coefficients and u come from them) and the one after the
    last sublayer (no coefficients: the streams' sum)."""
    hc, x, out, coef = streams
    n, d = CFG.hc_mult, CFG.d_model
    if place == "first":
        got_x, got_u, got_c = hc_mix(x, None, None, hc, CFG)
        np.testing.assert_array_equal(np.asarray(got_x, np.float32),
                                      np.asarray(x, np.float32))
        X = np.asarray(x, np.float32).reshape(-1, n, d)
        pre, post, H = _coefficients(X, hc)
        _close(got_c, np.concatenate([pre, post, H.reshape(-1, n * n)],
                                     -1), 2e-5)
        _close(got_u, np.einsum("ri,rid->rd", pre, X), 2e-2)
    else:
        got = hc_mix(x, out, coef, None, CFG)
        assert got.shape == (x.shape[0], d) and got.dtype == x.dtype
        _close(got, _mixed(x, out, coef).sum(1), 2e-2)


def test_hc_boundary_is_hc_mix_on_every_row(streams):
    """The model's boundary on (b, T, ...) state is hc_mix on its rows."""
    hc, x, out, coef = streams
    lead = (2, x.shape[0] // 2)
    state = tuple(t.reshape(lead + t.shape[1:]) for t in (x, out, coef))
    for got, want in zip(hc_boundary(state, hc, CFG),
                         hc_mix(x, out, coef, hc, CFG)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(want, np.float32).reshape(got.shape))


def test_sinkhorn_gives_a_doubly_stochastic_mixing(streams):
    _, _, _, coef = streams
    n = CFG.hc_mult
    H = np.asarray(coef[:, 2 * n:]).reshape(-1, n, n)
    assert (H > 0).all()
    np.testing.assert_allclose(H.sum(-1), 1.0, atol=1e-5)
    np.testing.assert_allclose(H.sum(-2), 1.0, atol=1e-5)
    post = np.asarray(coef[:, n:2 * n])
    assert (post > 0).all() and (post < CFG.hc_magnitude).all()


def test_the_boundary_against_its_equations(streams):
    """hc_mix written out: the pending output into the streams, then
    the norm, phi, the sigmoids and the Sinkhorn."""
    hc, x, out, coef = streams
    n, d = CFG.hc_mult, CFG.d_model
    mixed = _mixed(x, out, coef)
    pre, post, H = _coefficients(mixed, hc)
    got_x, got_u, got_c = hc_mix(x, out, coef, hc, CFG)
    np.testing.assert_allclose(     # to a bfloat16 step
        np.asarray(got_x, np.float32).reshape(-1, n, d), mixed,
        rtol=2 ** -7, atol=1e-6)
    _close(got_c, np.concatenate([pre, post, H.reshape(-1, n * n)], -1),
           2e-5)
    _close(got_u, np.einsum("ri,rid->rd", pre, mixed), 2e-2)


# ---- (c) the model against the reference ---------------------------------

def test_program_forward_is_the_reference(params, toks, reference):
    """A block attended whole (no selection: TOPK positions) through the
    streams, the gate, the sinks, the clamp and the float32 head."""
    want, _ = reference
    _close(forward(params, toks[:, :TOPK], CFG), want[:, :TOPK])


@pytest.mark.parametrize("plen,chunk", [(16, 24), (12, 9)])
def test_prefill_extend_decode_agree_with_the_reference(
        params, toks, reference, plen, chunk):
    """Prefill, then extend chunks (block_decode) whose queries pass the
    point where the full layers start to choose and the shared layers to
    follow them, then decode steps: the reference's logits at every
    position."""
    want, _ = reference
    cache = init_kv_cache(CFG, 2, 128)
    lg, cache = jax.jit(lambda p, t, c: prefill(p, t, c, CFG))(
        params, toks[:, :plen], cache)
    _close(lg, want[:, plen - 1])
    extend = jax.jit(lambda p, t, q, c: block_decode(p, t, q, c, CFG))
    pos = plen
    while pos < 48:
        lg, cache = extend(params, toks[:, pos:pos + chunk],
                           jnp.full((2,), pos, jnp.int32), cache)
        _close(lg, want[:, pos:pos + chunk])
        pos += chunk
    step = jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, CFG))
    for t in range(pos, N):
        lg, cache = step(params, toks[:, t], jnp.full((2,), t, jnp.int32),
                         cache)
        _close(lg, want[:, t])


# ---- (d) shared selection -------------------------------------------------

def test_shared_layers_attend_their_full_layers_set(params, toks,
                                                    reference):
    """Every shared layer's record is its full layer's set, bit for bit, at
    a decode step and in an extend chunk; the full layers' sets are the
    reference's own (the float32 scores agree far inside the margins at
    this size), and nothing past the full layers scores."""
    _, rec = reference
    cache = init_kv_cache(CFG, 2, 128)
    _, cache = prefill(params, toks[:, :16], cache, CFG)
    info = []
    _, cache = block_decode(params, toks[:, 16:40],
                            jnp.full((2,), 16, jnp.int32), cache, CFG,
                            dsa_info=info)
    assert [("shared" in r) for r in info] == [False, False, True, True,
                                               True]
    assert all("scores" not in r for r in info[2:])
    for r in info[2:]:
        np.testing.assert_array_equal(np.asarray(r["select"]),
                                      np.asarray(info[1]["select"]))
    for j in (0, 1):
        own = np.asarray(jnp.unpackbits(rec[j]["dsa"]["keep"], axis=-1,
                                        count=N), bool)
        np.testing.assert_array_equal(
            np.asarray(info[j]["select"])[:, :, :N], own[:, 16:40])
    info = []
    _, cache = decode_step(params, toks[:, 40], jnp.full((2,), 40, jnp.int32),
                           cache, CFG, dsa_info=info)
    assert len(info) == 5 and info[2]["shared"] and info[4]["shared"]
    for r in info[2:]:
        np.testing.assert_array_equal(np.asarray(r["select"]),
                                      np.asarray(info[1]["select"]))
        assert int(np.asarray(r["select"]).sum()) == 2 * TOPK


# ---- (e) the sink and the clamp, written out -------------------------------

def _sink_softmax(s, sink):
    """softmax over [s | sink] without the sink's column; s (..., n)."""
    m = np.maximum(s.max(-1, keepdims=True), sink)
    e = np.exp(s - m)
    return e / (e.sum(-1, keepdims=True) + np.exp(sink - m))


@pytest.mark.parametrize("form", ["einsum", "blocked", "kernel"])
def test_latent_attend_with_sinks_against_the_equations(monkeypatch, form):
    """a_h = sum_j exp(s_hj - m) v_j / (sum_j exp(s_hj - m) +
    exp(sink_h - m)) over a selected latent cache: the einsum, the
    blocked online softmax and the decode kernel (interpret mode)."""
    b, H, d, vd, L = 2, 4, 144, 128, 256
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(k1, (b, 1, H, d))
    cache = jax.random.normal(k2, (b, 1, d, L))
    pos = jnp.array([100, 255], jnp.int32)
    live = np.arange(L)[None] <= np.asarray(pos)[:, None]
    select = live & np.asarray(jax.random.uniform(k3, (b, L)) < 0.3)
    sinks = 2.0 + jax.random.normal(k4, (H,))
    C, Q = np.asarray(cache)[:, 0], np.asarray(q)[:, 0]
    s = np.einsum("bhd,bdk->bhk", Q, C) * 0.2
    s = np.where(select[:, None], s, -np.inf)
    want = np.einsum("bhk,bck->bhc", _sink_softmax(s, np.asarray(
        sinks)[None, :, None]), C[:, :vd])[:, None]
    if form == "kernel":
        from rlo_tpu.pallas.decode import flash_decode
        got = flash_decode(q, cache, None, pos, 0.2, v_dim=vd, block_k=128,
                           select=jnp.asarray(select), interpret=True,
                           sinks=sinks)
    else:
        if form == "blocked":
            monkeypatch.setattr(kvcache, "_CTX_TILE", 128)
            monkeypatch.setattr(kvcache, "_EINSUM_SCORE_BYTES", 0)
        got = kvcache._attend_cache_block(
            q, cache, None, pos[:, None], 0.2, v_dim=vd,
            select=jnp.asarray(select)[:, None], use_flash=False,
            sinks=sinks)
    _close(got, want, 2e-5)


def test_prompt_attention_with_sinks_against_the_equations():
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(6), 4)
    q, k = (jax.random.normal(kk, (1, 12, 3, 8)) for kk in (k1, k2))
    v = jax.random.normal(k3, (1, 12, 3, 5))
    sinks = jax.random.normal(k4, (3,))
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k)) * 0.3
    s = np.where(np.tril(np.ones((12, 12), bool)), s, -np.inf)
    p = _sink_softmax(s, np.asarray(sinks)[None, :, None, None])
    _close(attention_with_sinks(q, k, v, 0.3, sinks),
           np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v)), 1e-5)


@pytest.mark.parametrize("form", ["oracle", "kernel", "shared"])
def test_clamped_swiglu_against_the_equations(form):
    """silu(min(x W_g, L)) * clip(x W_u, -L, L) W_d in the held experts'
    XLA form, pallas.expert_ffn (interpret mode) and the shared expert,
    with pre-activations scaled so that the clamp bites."""
    lim = CFG.swiglu_limit
    d, f, E = 128, 128, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    x = 6.0 * jax.random.normal(ks[0], (16, d))
    wg, wu = (jax.random.normal(kk, (E, d, f)) * 0.5 for kk in ks[1:3])
    wd = jax.random.normal(ks[3], (E, f, d)) * f ** -0.5

    def want_of(e):
        g = np.minimum(np.asarray(x @ wg[e]), lim)
        u = np.clip(np.asarray(x @ wu[e]), -lim, lim)
        return (g / (1 + np.exp(-g)) * u) @ np.asarray(wd[e])

    assert (np.abs(np.asarray(x @ wu[0])) > lim).mean() > 0.3
    if form == "shared":
        _close(moe._gated(x, wg[0], wu[0], wd[0], lim), want_of(0), 1e-4)
        return
    ids = jnp.zeros((16, 1), jnp.int32).at[8:].set(2)
    w = jnp.ones((16, 1))
    out, _ = moe.held_experts_ffn(
        {"wg": wg, "wu": wu, "wd": wd, "wr": jnp.zeros((d, 16))}, x, ids,
        w, 0, use_kernel=form == "kernel", interpret=True, limit=lim)
    want = np.concatenate([want_of(0)[:8], want_of(2)[8:]])
    _close(out, want, 1e-3)


# ---- (f) the share test -----------------------------------------------------

def test_expert_shares_sum_to_the_uncut_layer():
    """The held experts' parts of an expert layer, over all the shares of
    its router's experts, with the shared expert counted once, add up to
    the uncut layer's FFN output (what goes into the streams); the
    attention sublayer before it is every chip's alike."""
    whole = dataclasses.replace(CFG, n_experts_held=CFG.n_experts)
    L = init_params(jax.random.PRNGKey(9), whole)["layers"][2]
    x = jax.random.normal(jax.random.PRNGKey(10), (2, 12, CFG.d_model))
    state, pos = hc_enter(x, CFG), jnp.arange(12)
    (streams, uncut, _), _ = apply_layer(state, L, whole, pos=pos)
    held, M = CFG.experts_held, L["moe"]
    parts = 0.0
    for first in range(0, CFG.n_experts, held):
        share = dict(L, moe=dict(M, **{n: M[n][first:first + held]
                                       for n in ("wg", "wu", "wd")}))
        (s2, part, _), _ = apply_layer(state, share, dataclasses.replace(
            CFG, expert_first=first), pos=pos)
        _close(s2, streams, 1e-6)       # the attention: every chip alike
        parts = parts + part
    _, u, _ = hc_mix(streams, None, None, L["hc_ffn"], CFG)
    h2 = _rmsnorm(u, L["ln2"]["g"], CFG.norm_eps)
    shared = moe._gated(h2, M["swg"], M["swu"], M["swd"], CFG.swiglu_limit)
    _close(parts - (CFG.n_experts // held - 1) * shared, uncut, 1e-4)


# ---- (g) the server -----------------------------------------------------------

def _greedy(params, prompt, n):
    """The greedy continuation of ``prompt`` through the step functions
    alone: a prefill of at most index_topk tokens, one block_decode of the
    rest, then decode steps."""
    cache = init_kv_cache(CFG, 1, 128)
    head = min(len(prompt), TOPK)
    lg, cache = jax.jit(lambda p, t, c: prefill(p, t, c, CFG))(
        params, jnp.asarray(prompt[:head])[None], cache)
    if len(prompt) > head:
        lg, cache = jax.jit(lambda p, t, c: block_decode(
            p, t, jnp.array([head]), c, CFG))(
                params, jnp.asarray(prompt[head:])[None], cache)
        lg = lg[:, -1]
    step = jax.jit(lambda p, t, q, c: decode_step(p, t, q, c, CFG))
    out = []
    for pos in range(len(prompt), len(prompt) + n):
        out.append(int(jnp.argmax(lg[0])))
        lg, cache = step(params, jnp.array([out[-1]]), jnp.array([pos]),
                         cache)
    return np.asarray(out)


def test_server_decodes_and_counts_scored_and_reused_selections(params):
    """Prompts past the widest bucket a selector allows (extend chunks),
    decoding past index_topk: every request's tokens are the step
    functions' greedy continuation; keys are scored on the 2 full layers alone, and of the
    layer-steps that chose a set 3 in 5 reused one."""
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=128, round_len=4,
                       prompt_buckets=(16,), metrics=reg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab, n) for n in (40, 10)]
    for p in prompts:
        srv.submit(p, 12)
    got = srv.run()
    for p, g in zip(prompts, got):
        np.testing.assert_array_equal(_greedy(params, p, 12), g)
    c = reg.snapshot()["counters"]
    # three rounds of 4 steps from pos (40, 10): contexts pos + s + 1
    ctx = np.array([40, 10])[:, None] + 1 + np.arange(12)[None]
    scored = ctx[:, (ctx > TOPK).any(0)].sum()
    assert c["serve.dsa.keys_scored"] == 2 * scored
    assert c["serve.dsa.rows_attended"] == 5 * np.minimum(ctx, TOPK).sum()
    steps = int((ctx > TOPK).any(0).sum())
    assert (c["serve.dsa.selections_scored"],
            c["serve.dsa.selections_reused"]) == (2 * steps, 3 * steps)
    assert c.get("serve.retraces", 0) == 0


def test_selection_counters_read_what_the_round_traced(params, monkeypatch):
    """The counters follow the program, not cfg.index_types: a round whose
    shared layers take no set (they attend everything) counts no
    layer-step as reused, the 2 full layers' as scored."""
    from rlo_tpu.models import generate
    sharing = generate._shared_selection

    def unshared(cfg, select_full):
        pick = sharing(cfg, select_full)
        return lambda i, index, entry, info=None: (
            (None, None) if cfg.index_kind(i) == "shared"
            else pick(i, index, entry, info))

    monkeypatch.setattr(generate, "_shared_selection", unshared)
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=128, round_len=4,
                       prompt_buckets=(16,), metrics=reg)
    rng = np.random.default_rng(1)
    for n in (40, 10):
        srv.submit(rng.integers(0, CFG.vocab, n), 8)
    srv.run()
    c = reg.snapshot()["counters"]
    ctx = np.array([40, 10])[:, None] + 1 + np.arange(8)[None]
    steps = int((ctx > TOPK).any(0).sum())
    assert steps
    assert (c["serve.dsa.selections_scored"],
            c["serve.dsa.selections_reused"]) == (2 * steps, 0)
    assert c["serve.dsa.keys_scored"] == 2 * ctx[:, (ctx > TOPK).any(0)].sum()


# ---- (h) refusals -----------------------------------------------------------

def test_paged_pipeline_and_sharded_paths_refuse(params):
    from rlo_tpu.models.paged import init_page_pool
    from rlo_tpu.models.pipeline import _make_stage_fn
    with pytest.raises(ValueError, match="dense scheduler"):
        DecodeServer(params, CFG, n_slots=2, max_len=128, paged=True,
                     page_size=16)
    with pytest.raises(ValueError, match="dense cache"):
        init_page_pool(CFG, 4, 16)
    with pytest.raises(NotImplementedError, match="hyper-connected"):
        _make_stage_fn(CFG)
    with pytest.raises(NotImplementedError, match="shared selection"):
        _make_stage_fn(dataclasses.replace(CFG, hc_mult=0))
    x = hc_enter(jnp.zeros((1, 4, CFG.d_model)), CFG)
    for axis in ("sp_axis", "tp_axis"):
        with pytest.raises(ValueError, match="hyper-connections"):
            apply_layer(x, params["layers"][0], CFG, pos=jnp.arange(4),
                        **{axis: "sp"})
