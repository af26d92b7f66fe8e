"""The plain reference of the ``deepseek-v3-ep16`` configuration: DeepSeek-V3's
forward pass as straightforward ``jax.numpy`` in float32 at matmul precision
``highest``, with no kernels, no cache, no absorbed form and nothing imported
from ``rlo_tpu``. It reads its sizes from the configuration's ``model``
section and takes the program's parameter tree (bfloat16 storage is converted
to float32 here, one layer at a time, so that it fits beside 9 GB of weights):

    embed (V, d); head (V, d), untied; ln_f.g (d,); per layer ln1.g, ln2.g,
    wdq (d, 1536), q_norm.g, wuq (1536, H x (128 + 64)), wdkv (d, 512 + 64),
    kv_norm.g, wuk (512, H, 128), wuv (512, H, 128), wo (H x 128, d);
    layer 0: wg, wu
    (d, 18432), wd (18432, d); layers 1..: moe = wr (d, 256), br (256,),
    wg, wu (held, d, 2048), wd (held, 2048, d), swg, swu (d, 2048), swd.

Equations (x is the residual stream, rms(t) = t / sqrt(mean(t^2) + 1e-6) * g):

Attention, every layer, on h = rms(x):
    c_q = rms(h W_dq);  q = c_q W_uq -> H heads x (128 nope | 64 rope)
    [c_kv | k_r] = h W_dkv (512 | 64);  c_kv = rms(c_kv);  k_r = rope(k_r),
    one rotated key part shared by all heads
    k_nope,h = c_kv W_uk,h (128);  v_h = c_kv W_uv,h (128)
    s = (q_nope . k_nope + rope(q_rope) . k_r) * scale, causal softmax,
    o = P v, heads concatenated (H x 128) -> W_o -> d
    rope: YaRN over the 64 rotated dims. Pair i of 32 turns at
    theta^(-i/32) (extrapolated) or at that / factor (interpolated), blended
    by a linear ramp between the correction dims
    low = floor(cd(beta_fast)), high = ceil(cd(beta_slow)),
    cd(r) = 64 ln(original_len / (2 pi r)) / (2 ln theta): pairs below low
    keep their frequency, pairs above high are interpolated. cos and sin are
    multiplied by mscale(factor, mscale) / mscale(factor, mscale_all_dim),
    mscale(f, m) = 0.1 m ln f + 1, and scale = 192^-0.5 * mscale(factor,
    mscale_all_dim)^2. The rotated pairs are (i, i + 32) (``assumed`` in the
    configuration file: with random weights a fixed permutation of the rope
    dims changes nothing).

Feed-forward on h = rms(x). Layers before ``n_dense_layers``:
    down(silu(h W_g) * (h W_u)), width 18432. Expert layers:
    s = sigmoid(h W_r) in float32 (256 scores); the choice is made on s + b:
    a group's score is the sum of the top 2 of its 32, the best 4 of the 8
    groups stay (the others are masked with -inf, as DeepSeek's own
    inference code does), the top 8 experts among them are chosen;
    w = s (without b) at those 8, normalised to sum 1, times 2.5;
    y = sum_e w_e ffn_e(h) + ffn_shared(h), each ffn gated, width 2048.
    This chip holds experts [expert_first, expert_first + n_experts_held):
    only their terms and the shared expert's are added. What the other
    experts would add is left out, here as in the program, and that partial
    result goes on to the next layer.

Not run: the multi-token-prediction module (it follows the last layer of the
full model, which the cut in depth leaves out; the main model's logits do not
depend on it).

``forced``: the on-chip check (perf/kinds/serve_moe.py) hands the program's
own expert sets back in, after holding each against this file's scores: where
two scores tie within the bfloat16 error of a score, either choice is the
model, and the reference then follows the program's so that every later
position can still be compared exactly. Without ``forced`` the reference
routes by its own scores.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


#: None, or a dtype that every activation entering a matrix product is
#: rounded to. Set once by hand, to float8_e4m3fn, for the reading that the
#: tolerances in the configuration file are set against (PERF.md, PR 27):
#: computed one precision below bfloat16, this file must FAIL its own check.
ACT_DTYPE = None


def _r(x):
    return x if ACT_DTYPE is None else x.astype(ACT_DTYPE).astype(x.dtype)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(model: dict) -> np.ndarray:
    """The 32 rotation frequencies of the 64 rope dims."""
    hd, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    factor, orig = float(model["rope_scale"]), model["rope_original_len"]
    half = hd // 2
    extrapolated = theta ** (-np.arange(half) / half)
    interpolated = extrapolated / factor

    def correction_dim(rotations):
        return hd * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(correction_dim(model["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(model["rope_beta_slow"])), hd - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return interpolated * ramp + extrapolated * (1 - ramp)


def _rope(t, model):
    """t (b, n, heads, 64) at positions 0..n-1: pairs (i, i + 32)."""
    n, half = t.shape[1], t.shape[-1] // 2
    amp = (_mscale(model["rope_scale"], model["rope_mscale"])
           / _mscale(model["rope_scale"], model["rope_mscale_all_dim"]))
    ang = (np.arange(n)[:, None] * yarn_frequencies(model)[None, :])
    cos = jnp.asarray(np.cos(ang) * amp, jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang) * amp, jnp.float32)[None, :, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(h, L, model):
    b, n, _ = h.shape
    H, eps = model["n_heads"], model["norm_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    kl = model["kv_lora_rank"]
    h = _r(h)
    c_q = _r(_rms(h @ L["wdq"], L["q_norm"]["g"], eps))
    q = (c_q @ L["wuq"]).reshape(b, n, H, nope + rope)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], model)
    ckv = h @ L["wdkv"]
    c_kv = _r(_rms(ckv[..., :kl], L["kv_norm"]["g"], eps))
    k_r = _r(_rope(ckv[:, :, None, kl:], model)[:, :, 0])  # (b, n, 64)
    q_nope, q_rope = _r(q_nope), _r(q_rope)
    k_nope = jnp.einsum("bnc,chw->bnhw", c_kv, L["wuk"])
    v = jnp.einsum("bnc,chw->bnhw", c_kv, L["wuv"])
    m = _mscale(model["rope_scale"], model["rope_mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * m * m
    s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
         + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r)) * scale
    causal = jnp.tril(jnp.ones((n, n), bool))
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _r(p), _r(v)).reshape(b, n, -1)
    return _r(o) @ L["wo"]


def _gated(h, wg, wu, wd):
    h = _r(h)
    return _r(jax.nn.silu(h @ wg) * (h @ wu)) @ wd


def select(choice, model: dict):
    """Choice scores (..., E) -> (ids (..., k) ascending, margin (...)).
    ``margin`` is how far the choice is from flipping, in units of one
    score: the gap between the last expert chosen and the first left out
    among the kept groups, or half the gap between the last group kept and
    the first dropped (a group's score is the sum of two)."""
    G, keep_g = model["n_group"], model["topk_group"]
    k = model["experts_per_tok"]
    E = choice.shape[-1]
    grouped = choice.reshape(choice.shape[:-1] + (G, E // G))
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    by_score = jnp.sort(group_score, axis=-1)[..., ::-1]
    kept = group_score >= by_score[..., keep_g - 1:keep_g]
    if keep_g < G:
        group_margin = (by_score[..., keep_g - 1] - by_score[..., keep_g]) / 2
    else:
        group_margin = jnp.full(choice.shape[:-1], jnp.inf)
    masked = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(
        choice.shape)
    top, ids = jax.lax.top_k(masked, k + 1)
    margin = jnp.minimum(top[..., k - 1] - top[..., k], group_margin)
    return jnp.sort(ids[..., :k], axis=-1), margin


def _experts(h, M, model, forced):
    E_held = M["wg"].shape[0]
    first = model["expert_first"]
    scores = jax.nn.sigmoid(_r(h) @ M["wr"])
    choice = scores + M["br"]
    own, margin = select(choice, model)
    ids = own if forced is None else jnp.where(forced[..., :1] >= 0,
                                               forced, own)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True) * model["routed_scale"]
    y = _gated(h, M["swg"], M["swu"], M["swd"]) if "swg" in M else 0.0
    for e in range(E_held):     # this chip's experts; the others: left out
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        y = y + w_e[..., None] * _gated(h, M["wg"][e], M["wu"][e],
                                        M["wd"][e])
    return y, {"ids": own, "margin": margin, "choice": choice}


def embed(params, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer(L, x, model: dict, forced=None):
    """One layer on the residual stream ``x`` (b, n, d) float32, positions
    0..n-1. Returns (x, record): ``record`` is None for a dense layer and
    the routing record of an expert layer ({"ids", "margin", "choice"})."""
    with jax.default_matmul_precision("highest"):
        L = _f32(L)
        eps = model["norm_eps"]
        x = x + _attention(_rms(x, L["ln1"]["g"], eps), L, model)
        h = _rms(x, L["ln2"]["g"], eps)
        if "moe" in L:
            y, record = _experts(h, L["moe"], model, forced)
            return x + y, record
        return x + _gated(h, L["wg"], L["wu"], L["wd"]), None


def head(params, x, model: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"]["g"].astype(jnp.float32),
                 model["norm_eps"])
        return _r(x) @ params["head"].astype(jnp.float32).T


def forward(params, tokens, model: dict, forced=None):
    """(b, n) tokens -> ((b, n, V) float32 logits, one routing record per
    expert layer). ``forced``: one (b, n, k) int array per expert layer;
    a position whose first entry is negative routes by its own scores."""
    x = embed(params, tokens)
    records = []
    for L in params["layers"]:
        want = None
        if "moe" in L and forced is not None:
            want = forced[len(records)]
        x, record = layer(L, x, model, want)
        if record is not None:
            records.append(record)
    return head(params, x, model), records


def logits(params, tokens, model: dict):
    """(b, n) int tokens -> (b, n, V) float32 logits."""
    return forward(params, tokens, model)[0]
