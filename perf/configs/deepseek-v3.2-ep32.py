"""The plain reference of the ``deepseek-v3.2-ep32`` configuration:
DeepSeek-V3.2's forward pass as straightforward ``jax.numpy`` in float32 at
matmul precision ``highest``, with no kernels, no cache, no absorbed form and
nothing imported from ``rlo_tpu``. It is its own copy of DeepSeek-V3's
reference (perf/configs/deepseek-v3-ep16.py: latent attention, YaRN, the
sigmoid group-limited router over this chip's share of the experts; read
that file's head for those equations) plus what V3.2 adds, the token
selector ("lightning indexer") and sparse attention over its choice.

Parameters beyond V3's, per layer: wiq (1536, 64 x 128), wik (d, 128),
ik_norm.g / ik_norm.b (128,), wiw (d, 64).

For token t with h_t = rms(x_t) (the attention norm's output) and latent
attention's query latent c_q,t = rms(h_t W_dq):

    q_I,t,j = (c_q,t W_iq)_j,  j = 1..64, 128 wide
    k_I,s   = LayerNorm(h_s W_ik) (gain, bias, eps = rms_norm_eps), 128 wide:
              ONE key a token, shared by the 64 index heads
    rope on the first 64 features of every q_I,t,j and of k_I,s at the
    token's position, the model's YaRN frequencies, pairs (i, i + 32); the
    other 64 features pass
    w_t,j   = (h_t W_iw)_j * 64^-0.5 * 128^-0.5
    I_t,s   = sum_j w_t,j relu(q_I,t,j . k_I,s),  s <= t
    S_t     = the min(index_topk, t + 1) positions s <= t of largest I_t,s,
              ties to the lowest position
    attention of token t (V3's, every head) runs over s in S_t only.

One reading differs from the V3 reference's: ``select`` keeps EXACTLY
``topk_group`` groups, as DeepSeek's own code does (a top-k, then a scatter):
where two group scores are exactly equal the lower index stays and the other
goes, as in the expert choice. The V3 file keeps every group that ties with
the last one kept; its check meets no such tie in 2 000 token-layers, this
configuration's meets one in about ten runs of 60 000.

Departures, each named in the configuration file's ``assumed``: no FP8 and
no Hadamard rotation of q_I / k_I (orthogonal: every q . k unchanged), the
rope pairing, bfloat16 index keys in the program.

Queries are projected, scored and attended in blocks of ``QUERY_BLOCK`` and
the feed-forward half runs over blocks of ``TOKEN_BLOCK`` positions, so that
a 20 000-token sequence fits beside the weights (a (heads, block, n) score
tensor at a time; with ``at`` no (n, n) tensor leaves a block).

``chosen``: the on-chip check (perf/kinds/serve_dsa.py) hands the program's
own selections back in, after holding them against this file's scores: where
the 2048th and 2049th scores tie within the program's error of a score,
either set is the model, and the reference then attends the program's so
that every later layer and position can still be compared. Without
``chosen`` (or at a position whose row of it is empty: a set is never
empty) the reference attends its own choice. EVERY set handed in is held
against this file's own scores where it is used: its size, how many of its
positions this file's own set lacks, and how many positions lie on the wrong
side of this file's ``index_topk``-th score by more than ``band``
(``"size"``, ``"differs"``, ``"outside"``, a number a query). ``forced`` does
the same for the experts (see the V3 reference).
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


def _rope_tables(n: int, model):
    """(cos, sin), (n, 32) float32 each, of positions 0..n-1, from angles
    taken in float64."""
    amp = (_mscale(model["rope_scale"], model["rope_mscale"])
           / _mscale(model["rope_scale"], model["rope_mscale_all_dim"]))
    ang = (np.arange(n)[:, None] * yarn_frequencies(model)[None, :])
    return (jnp.asarray(np.cos(ang) * amp, jnp.float32),
            jnp.asarray(np.sin(ang) * amp, jnp.float32))


def _rope(t, model, tables=None):
    """t (b, n, heads, 64) at positions 0..n-1, or at the positions whose
    rows of _rope_tables ``tables`` holds: pairs (i, i + 32)."""
    half = t.shape[-1] // 2
    cos, sin = (a[None, :, None, :] for a in (
        tables or _rope_tables(t.shape[1], model)))
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


#: queries attended at a time: a (heads, block, n) score tensor and its
#: softmax at once; at 64 a 20 000-token sequence takes 5.1 GiB beside the
#: weights where 128 takes 6.4 (the v5e compiler's own count, PR 31)
QUERY_BLOCK = 64


def _layernorm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g + b


def _rope_leading(t, model, tables=None):
    rope = model["qk_rope_head_dim"]
    return jnp.concatenate([_rope(t[..., :rope], model, tables),
                            t[..., rope:]], -1)


def select_topk(scores, k: int):
    """Index scores (..., n), -inf where a position may not be attended ->
    bool mask of the k largest, ties to the lowest position (a stable
    descending sort); everything finite where fewer than k are."""
    n = scores.shape[-1]
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < min(k, n)) & jnp.isfinite(scores)


def _index_keys(h, L, model):
    """(k_I (b, n, 128), w (b, n, 64)) of positions 0..n-1."""
    hi, di = model["index_n_heads"], model["index_head_dim"]
    k = _layernorm(h @ L["wik"], L["ik_norm"]["g"], L["ik_norm"]["b"],
                   model["norm_eps"])
    k = _rope_leading(k[:, :, None, :], model)[:, :, 0]
    return _r(k), (h @ L["wiw"]) * (hi ** -0.5 * di ** -0.5)


def _index_queries(c_q, L, model, tables):
    """q_I (b, m, 64, 128) of the m queries whose latents ``c_q`` and rows
    of _rope_tables ``tables`` hold."""
    b, m, _ = c_q.shape
    q = (c_q @ L["wiq"]).reshape(b, m, model["index_n_heads"],
                                 model["index_head_dim"])
    return _r(_rope_leading(q, model, tables))


def _index_scores(q_i, w_i, k_i, at_q):
    """I[t, s] of the queries at positions ``at_q`` (m,): q_i (b, m, 64,
    128), w_i (b, m, 64) against every key k_i (b, n, 128) -> (b, m, n),
    -inf at s > t."""
    s_i = jnp.einsum("bqjd,bkd->bqjk", q_i, k_i)
    scores = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(s_i), w_i)
    causal = jnp.arange(k_i.shape[1])[None, :] <= at_q[:, None]
    return jnp.where(causal[None], scores, -jnp.inf)


def _attention(h, L, model, chosen=None, at=None, band=None):
    """(attention output, selector record or None). ``chosen`` (b, n, n)
    bool, or (b, n, ceil(n / 8)) uint8 packed along the last axis: the sets
    to attend in place of this file's own, each judged as it is used
    (``band``: see the head). A block of queries is projected, scored and
    attended at a time: only what every block needs (the keys, the values)
    is held for the whole sequence. The record holds the index scores
    and this file's own sets of every query, (b, n, n), or with ``at`` of
    the queries at those positions alone, (b, len(at), n)."""
    b, n, _ = h.shape
    H, eps = model["n_heads"], model["norm_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    kl = model["kv_lora_rank"]
    h = _r(h)
    c_q = _r(_rms(h @ L["wdq"], L["q_norm"]["g"], eps))
    ckv = h @ L["wdkv"]
    c_kv = _r(_rms(ckv[..., :kl], L["kv_norm"]["g"], eps))
    k_r = _r(_rope(ckv[:, :, None, kl:], model)[:, :, 0])  # (b, n, 64)
    k_nope = jnp.einsum("bnc,chw->bnhw", c_kv, L["wuk"])
    v = jnp.einsum("bnc,chw->bnhw", c_kv, L["wuv"])
    m = _mscale(model["rope_scale"], model["rope_mscale_all_dim"])
    scale = (nope + rope) ** -0.5 * m * m
    topk = model.get("index_topk", 0)
    if topk:
        k_i, w_i = _index_keys(h, L, model)
    v = _r(v)
    bq = min(QUERY_BLOCK, n)
    pad = -n % bq
    starts = jnp.arange(0, n + pad, bq)
    tables = _rope_tables(n + pad, model)

    def padded(t):
        return jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))

    cq_p = padded(c_q)
    if topk:
        wi_p = padded(w_i)
    if chosen is not None:
        chosen_p = padded(chosen)

    def block(q0):
        def rows(t, axis=1):
            return jax.lax.dynamic_slice_in_dim(t, q0, bq, axis=axis)

        at_q = q0 + jnp.arange(bq)
        here = tuple(rows(t, 0) for t in tables)
        q = (rows(cq_p) @ L["wuq"]).reshape(b, bq, H, nope + rope)
        q_nope, q_rope = _r(q[..., :nope]), _r(_rope(q[..., nope:], model,
                                                     here))
        causal = jnp.arange(n)[None, :] <= at_q[:, None]       # (bq, n)
        keep = jnp.broadcast_to(causal[None], (b, bq, n))
        scores = own = judged = None
        if topk:
            scores = _index_scores(_index_queries(rows(cq_p), L, model,
                                                  here), rows(wi_p), k_i,
                                   at_q)
            own = select_topk(scores, topk)
            keep = own
            if chosen is not None:  # an empty row: no set was handed in
                given = rows(chosen_p)
                if given.dtype == jnp.uint8:
                    given = jnp.unpackbits(given, axis=-1,
                                           count=n).astype(bool)
                given = given & causal[None]
                has = given.any(-1, keepdims=True)
                keep = jnp.where(has, given, own)
                kth = jnp.min(jnp.where(own, scores, jnp.inf), -1,
                              keepdims=True)
                outside = (given & (scores < kth - width)) | (
                    ~given & causal[None] & (scores > kth + width))
                judged = has * jnp.stack(
                    [given.sum(-1), (given & ~own).sum(-1),
                     outside.sum(-1)], -1)                     # (b, bq, 3)
            if at is not None:      # no (n, n) tensor leaves the block
                scores = own = None
        s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
             + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r)) * scale
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", _r(p), v).reshape(b, bq, -1)
        return o, scores, own, judged

    width = jnp.inf if band is None else band
    o, scores, own, judged = jax.lax.map(block, starts)

    def flat(t):
        return t.transpose(1, 0, 2, 3).reshape(b, n + pad, -1)[:, :n]

    record = None
    if topk:
        if at is None:
            scores, own = flat(scores), flat(own)
        else:
            scores = _index_scores(
                _index_queries(c_q[:, at], L, model,
                               tuple(t[at] for t in tables)),
                w_i[:, at], k_i, at)
            own = select_topk(scores, topk)
        record = {"scores": scores, "select": own}
        if judged is not None:
            judged = flat(judged)
            record.update(size=judged[..., 0], differs=judged[..., 1],
                          outside=judged[..., 2])
    return _r(flat(o)) @ L["wo"], record


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
    # EXACTLY keep_g groups, as DeepSeek's own code keeps them (topk then
    # scatter): two groups of equal score do not both stay; the lower
    # index wins, as in the expert choice below
    best = jax.lax.top_k(group_score, keep_g)[1]
    kept = (best[..., None] == jnp.arange(G)).any(-2)
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


#: positions the feed-forward half of a layer takes at a time: what it
#: holds a token (three (d_ff,) rows of the dense layer) beside 20 000 tokens
TOKEN_BLOCK = 2048


def _over_tokens(fn, *per_token):
    """``fn`` over blocks of TOKEN_BLOCK positions of (b, n, ...) arrays,
    each position by itself; its outputs joined along the positions."""
    b, n = per_token[0].shape[:2]
    blk = min(TOKEN_BLOCK, n)
    pad = -n % blk

    def split(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return t.reshape((b, -1, blk) + t.shape[2:]).swapaxes(0, 1)

    def join(t):
        return t.swapaxes(0, 1).reshape((b, n + pad) + t.shape[3:])[:, :n]

    return jax.tree.map(join, jax.lax.map(
        lambda a: fn(*a), tuple(split(t) for t in per_token)))


def embed(params, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer(L, x, model: dict, forced=None, chosen=None, at=None, band=None):
    """One layer on the residual stream ``x`` (b, n, d) float32, positions
    0..n-1. Returns (x, record): ``record`` is the routing record of an
    expert layer ({"ids", "margin", "choice"}; {} for a dense layer) with,
    under ``"dsa"``, the selector's {"scores", "select"}: (b, n, n), or
    (b, len(at), n) at the query positions ``at``, and with ``chosen`` its
    verdict on every set handed in ({"size", "differs", "outside"}, (b, n)
    each). ``forced``, ``chosen``, ``band``: the program's expert sets and
    token sets to follow, and the band they are held to (see the head)."""
    with jax.default_matmul_precision("highest"):
        L = _f32(L)
        eps = model["norm_eps"]
        att, dsa = _attention(_rms(x, L["ln1"]["g"], eps), L, model,
                              chosen, at, band)
        x = x + att
        h = _rms(x, L["ln2"]["g"], eps)
        record = {}
        if "moe" not in L:
            y = _over_tokens(lambda t: _gated(t, L["wg"], L["wu"], L["wd"]),
                             h)
        elif forced is None:
            y, record = _over_tokens(
                lambda t: _experts(t, L["moe"], model, None), h)
        else:
            y, record = _over_tokens(
                lambda t, f: _experts(t, L["moe"], model, f), h, forced)
        if dsa is not None:
            record["dsa"] = dsa
        return x + y, record


def head(params, x, model: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"]["g"].astype(jnp.float32),
                 model["norm_eps"])
        return _r(x) @ params["head"].astype(jnp.float32).T


def forward(params, tokens, model: dict, forced=None, chosen=None):
    """(b, n) tokens -> ((b, n, V) float32 logits, one record per layer).
    ``forced``: one (b, n, k) int array per EXPERT layer; a position whose
    first entry is negative routes by its own scores. ``chosen``: one
    (b, n, n) bool array per layer."""
    x = embed(params, tokens)
    records, n_moe = [], 0
    for i, L in enumerate(params["layers"]):
        want = None
        if "moe" in L:
            want = None if forced is None else forced[n_moe]
            n_moe += 1
        x, record = layer(L, x, model, want,
                          None if chosen is None else chosen[i])
        records.append(record)
    return head(params, x, model), records


def logits(params, tokens, model: dict):
    """(b, n) int tokens -> (b, n, V) float32 logits."""
    return forward(params, tokens, model)[0]
