"""The plain reference of the ``hy4-preview-ep32`` configuration: Hy4-preview's
forward pass as straightforward ``jax.numpy`` in float32 at matmul precision
``highest``, with no kernels, no cache, no absorbed form and nothing imported
from ``rlo_tpu``.

Notation: rms(t) = t / sqrt(mean(t^2) + eps) * g, eps = rms_norm_eps (1e-5).

Residual: FOUR streams X_1..X_4 of width d (hyper-connections, hc_mult = 4,
the manifold-constrained form of Xie et al., arXiv 2512.24880). At the
embedding every stream is the token's embedding e. Each sublayer F
(attention, then the FFN) of each layer has its own phi (24, 4d), scale
(alpha_pre, alpha_post, alpha_res) and bias b (24,):

    x^     = X / rms over all 4d features (no gain, eps = rms_norm_eps)
    a      = x^ phi^T                                     (24 numbers)
    h_pre  = sigmoid(alpha_pre a[0:4] + b[0:4])
    h_post = 2 sigmoid(alpha_post a[4:8] + b[4:8])          (hc_magnitude 2)
    H      = Sinkhorn(exp(alpha_res a[8:24] + b[8:24]) as 4 x 4, row-major):
             rows, then columns, divided by their sum + hc_eps (1e-6),
             SINKHORN_ITERS times
    u      = sum_i h_pre[i] X_i
    X_i   <- sum_j H[i, j] X_j + h_post[i] F(u)

After the last layer h = sum_i X_i, then rms(h) (ln_f) and the head in
float32.

Attention of one layer, on h = rms(u) (ln1), 64 heads, latent ranks 2048 (q)
and 512 (kv), per head 192 dims without position and 64 rotated, values 256
wide:

    c_q = rms(h W_dq) (q_norm);   q = c_q W_uq -> (q_nope 192 | q_rope 64)
    [c | k_r] = h W_dkv;  c_kv = rms(c) (kv_norm);  k_nope = c_kv W_uk,
    v = c_kv W_uv;  k_r shared by all heads
    rope: plain rotary at theta 1e7 (no scaling), pairs (i, i + 32) of the
          64 rotated dims, at the token's position
    s_hj = (q_nope,h . k_nope,hj + q_rope,h . k_r,j) 256^-0.5,  j in S_t
    a_h  = sum_j exp(s_hj - m) v_hj / (sum_j exp(s_hj - m) + exp(sink_h - m))
           (a learned sink a head in the denominator; m cancels)
    out  = (a * sigmoid(h W_gate)) W_o           (the elementwise output gate)

Token selection (the lightning indexer, 32 heads of 128, top 2048) on the
layers whose ``index_types`` entry is "full":

    q_I,t,j = (c_q,t W_iq)_j,  k_I,s = LayerNorm(h_s W_ik) (eps rms_norm_eps),
    both with their first 64 features rotated as above;
    w_t,j   = (h_t W_iw)_j 32^-0.5 128^-0.5
    I_t,s   = sum_j w_t,j relu(q_I,t,j . k_I,s),  s <= t
    S_t     = the min(2048, t + 1) positions s <= t of largest I_t,s, ties to
              the lowest position

A "shared" layer has no indexer: its query at position t attends EXACTLY the
set S_t of the nearest full layer before it.

FFN: layer 0 dense, silu(h W_g) (h W_u) W_d, d_ff 18432, NOT clamped. Layers
1-4: sigmoid scores s_e = sigmoid(h W_r) over 256 experts, the top 8 of
s_e + bias (one group), weights s_e of the chosen normalised to sum 1 and
times 2.827; this chip's experts 0-7 and the shared expert, each
silu(min(h W_g, 10)) clip(h W_u, -10, 10) W_d (swiglu_limit 10).

Each departure and assumption is named in the configuration file.

Size: the check hands in a parity row of 32 800 tokens. The streams are held
(b, 4, n, d) and updated IN PLACE a block of TOKEN_BLOCK positions at a time
(``read`` and ``write`` take any run of positions: the on-chip check holds
the streams as blocks of TOKEN_BLOCK positions, one array each); queries are selected and attended in blocks of QUERY_BLOCK, the heads of the
attend in groups of HEAD_GROUP (so that the decompressed keys and values of
a group, and its weights in float32, not those of all 64 heads, are held
for the whole sequence), the FFN over TOKEN_BLOCK positions and
HIDDEN_BLOCK hidden units at a time.

``chosen``: the on-chip check (perf/kinds/serve_hc.py) hands the program's
own selections back in, packed (b, n, ceil(n / 8)) uint8, after holding them
against this file's scores; a full layer judges EVERY set handed in where it
uses it (its ``size``, how many of its positions this file's own set lacks,
``differs``, how many lie on the wrong side of this file's 2048th score by
more than ``band``, ``outside``) and attends it; a shared layer attends the
set handed in for its full layer. ``forced`` does the same for the experts
(see deepseek-v3.2-ep32.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: None, or a dtype that every activation entering a matrix product is
#: rounded to: set by hand to bfloat16 or float8_e4m3fn for the readings the
#: tolerances are set against (the configuration file's ``tolerance``)
ACT_DTYPE = None

#: Sinkhorn normalisations of a hyper-connection's mixing matrix
SINKHORN_ITERS = 20

#: queries selected and attended at a time
QUERY_BLOCK = 64

#: heads attended at a time
HEAD_GROUP = 8

#: hidden units of the dense FFN taken at a time (its float32 weights are
#: never held whole)
HIDDEN_BLOCK = 2048

#: positions a hyper-connection update or the FFN takes at a time
TOKEN_BLOCK = 2048


def _r(x):
    return x if ACT_DTYPE is None else x.astype(ACT_DTYPE).astype(x.dtype)


def _w(a):
    return a.astype(jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _w(g)


def _layernorm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _w(g) + _w(b)


def _rope_tables(at, model):
    """(cos, sin), (len(at), 32) float32, of positions ``at``."""
    hd, theta = model["qk_rope_head_dim"], float(model["rope_theta"])
    half = hd // 2
    freqs = theta ** (-np.arange(half) / half)
    ang = jnp.asarray(at, jnp.float32)[:, None] * jnp.asarray(
        freqs, jnp.float32)[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _rope(t, tables):
    """t (b, m, heads, 64) at the positions of ``tables``: pairs (i, i+32)."""
    half = t.shape[-1] // 2
    cos, sin = (a[None, :, None, :] for a in tables)
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _rope_leading(t, model, tables):
    rope = model["qk_rope_head_dim"]
    return jnp.concatenate([_rope(t[..., :rope], tables), t[..., rope:]],
                           -1)


def select_topk(scores, k: int):
    """Index scores (..., n), -inf where a position may not be attended ->
    bool mask of the k largest, ties to the lowest position."""
    n = scores.shape[-1]
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    return (rank < min(k, n)) & jnp.isfinite(scores)


def _unpack(bits, n):
    return jnp.unpackbits(bits, axis=-1, count=n).astype(bool)


# ---- hyper-connections --------------------------------------------------

def _hc_coefficients(X, hc, model):
    """X (b, 4, m, d) -> (h_pre (b, m, 4), h_post (b, m, 4), H (b, m, 4, 4))."""
    n = X.shape[1]
    ms = jnp.mean(X * X, axis=(1, 3))                         # (b, m)
    xh = X * jax.lax.rsqrt(ms + model["norm_eps"])[:, None, :, None]
    phi = _w(hc["phi"]).reshape(-1, n, X.shape[3])           # (24, 4, d)
    a = jnp.einsum("bimd,kid->bmk", _r(xh), phi)
    al, b = _w(hc["scale"]), _w(hc["bias"])
    pre = jax.nn.sigmoid(al[0] * a[..., :n] + b[:n])
    post = model["hc_magnitude"] * jax.nn.sigmoid(
        al[1] * a[..., n:2 * n] + b[n:2 * n])
    H = jnp.exp(al[2] * a[..., 2 * n:] + b[2 * n:]).reshape(
        a.shape[:-1] + (n, n))
    for _ in range(SINKHORN_ITERS):
        H = H / (H.sum(-1, keepdims=True) + model["hc_eps"])
        H = H / (H.sum(-2, keepdims=True) + model["hc_eps"])
    return pre, post, H


def _for_blocks(m: int, blk: int, body, carry):
    """``carry = body(start, size, carry)`` over positions [0, m) in blocks
    of ``blk``: a loop over the whole blocks, then the rest (a static size
    of its own). Every array the body writes is the carry, updated in
    place."""
    nb, rem = divmod(m, blk)
    if nb:
        carry = jax.lax.fori_loop(
            0, nb, lambda i, c: body(i * blk, blk, c), carry)
    if rem:
        carry = body(nb * blk, rem, carry)
    return carry


def _at(t, start, size, axis=1):
    return jax.lax.dynamic_slice_in_dim(t, start, size, axis=axis)


def _put(t, part, start, axis=1):
    return jax.lax.dynamic_update_slice_in_dim(t, part, start, axis=axis)


def read(X, hc, g, model):
    """The sublayer's normed input rms(u) (gain ``g``), u = sum_i h_pre[i]
    X_i, (b, m, d), and (h_post, H) of every position, a block of
    TOKEN_BLOCK positions at a time."""
    b, n_s, m, d = X.shape

    def body(s, size, carry):
        u, post, H = carry
        x = _at(X, s, size, axis=2)
        pre, p, h = _hc_coefficients(x, hc, model)
        u_s = jnp.einsum("bmi,bimd->bmd", pre, x)
        return (_put(u, _rms(u_s, g, model["norm_eps"]), s),
                _put(post, p, s), _put(H, h, s))

    zeros = (jnp.zeros((b, m, d), jnp.float32),
             jnp.zeros((b, m, n_s), jnp.float32),
             jnp.zeros((b, m, n_s, n_s), jnp.float32))
    return _for_blocks(m, TOKEN_BLOCK, body, zeros)


def write(X, out, post, H):
    """X_i <- sum_j H[i, j] X_j + h_post[i] out, in place, a block of
    positions at a time."""
    def body(s, size, X):
        x = _at(X, s, size, axis=2)
        new = (jnp.einsum("bmij,bjmd->bimd", _at(H, s, size), x)
               + jnp.einsum("bmi,bmd->bimd", _at(post, s, size),
                            _at(out, s, size)))
        return _put(X, new, s, axis=2)

    return _for_blocks(X.shape[2], TOKEN_BLOCK, body, X)


# ---- attention ------------------------------------------------------------

def _index_scores(c_q, w_i, k_i, L, model, at_q):
    """I[t, s] of the queries at positions ``at_q`` (m,) whose latents
    ``c_q`` (b, m, 2048) and weights ``w_i`` (b, m, 32) are given, against
    every key k_i (b, n, 128) -> (b, m, n), -inf at s > t."""
    b, m, _ = c_q.shape
    q = (c_q @ _w(L["wiq"])).reshape(b, m, model["index_n_heads"],
                                     model["index_head_dim"])
    q = _r(_rope_leading(q, model, _rope_tables(at_q, model)))
    s_i = jnp.einsum("bqjd,bkd->bqjk", q, k_i)
    scores = jnp.einsum("bqjk,bqj->bqk", jax.nn.relu(s_i), w_i)
    causal = jnp.arange(k_i.shape[1])[None, :] <= at_q[:, None]
    return jnp.where(causal[None], scores, -jnp.inf)


def attention(h, L, model, kind, chosen=None, at=None, band=None):
    """(attention output (b, n, d), the selector's record or None) of the
    normed input ``h`` (b, n, d). ``kind`` "full" selects (and with
    ``chosen`` judges and attends the sets handed in); "shared" attends
    ``chosen``, its full layer's sets. Queries a block of QUERY_BLOCK at
    a time, the attend a group of HEAD_GROUP heads at a time."""
    b, n, d = h.shape
    H, eps = model["n_heads"], model["norm_eps"]
    nope, rope = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    kl, dv = model["kv_lora_rank"], model["v_head_dim"]
    topk = model["index_topk"]
    h = _r(h)
    c_q = _r(_rms(h @ _w(L["wdq"]), L["q_norm"]["g"], eps))
    ckv = h @ _w(L["wdkv"])
    c_kv = _r(_rms(ckv[..., :kl], L["kv_norm"]["g"], eps))
    k_r = _r(_rope(ckv[:, :, None, kl:], _rope_tables(np.arange(n),
                                                       model))[:, :, 0])
    scale = (nope + rope) ** -0.5
    n_bytes = -(-n // 8)
    record = None
    width = jnp.inf if band is None else band

    # ---- which positions each query attends: packed (b, n, n/8) sets
    if kind == "full":
        k_i = _layernorm(h @ _w(L["wik"]), L["ik_norm"]["g"],
                         L["ik_norm"]["b"], eps)
        k_i = _r(_rope_leading(k_i[:, :, None, :], model, _rope_tables(
            np.arange(n), model))[:, :, 0])
        w_i = (h @ _w(L["wiw"])) * (model["index_n_heads"] ** -0.5
                                    * model["index_head_dim"] ** -0.5)

        def pick(q0, size, carry):
            keep, verdict = carry
            at_q = q0 + jnp.arange(size)
            scores = _index_scores(_at(c_q, q0, size), _at(w_i, q0, size),
                                   k_i, L, model, at_q)
            own = select_topk(scores, topk)
            if chosen is None:
                return _put(keep, jnp.packbits(own, axis=-1), q0), verdict
            causal = jnp.arange(n)[None, :] <= at_q[:, None]
            given = _unpack(_at(chosen, q0, size), n) & causal[None]
            has = given.any(-1, keepdims=True)
            kth = jnp.min(jnp.where(own, scores, jnp.inf), -1, keepdims=True)
            outside = (given & (scores < kth - width)) | (
                ~given & causal[None] & (scores > kth + width))
            v = has * jnp.stack(
                [given.sum(-1), (given & ~own).sum(-1), outside.sum(-1)],
                -1).astype(jnp.int32)
            return (_put(keep, jnp.packbits(jnp.where(has, given, own),
                                            axis=-1), q0),
                    _put(verdict, v, q0))

        keep, judged = _for_blocks(n, QUERY_BLOCK, pick, (
            jnp.zeros((b, n, n_bytes), jnp.uint8),
            jnp.zeros((b, n, 3), jnp.int32)))
        where = jnp.arange(n) if at is None else jnp.asarray(at)
        scores = _index_scores(c_q[:, where], w_i[:, where], k_i, L, model,
                               where)
        record = {"scores": scores, "select": select_topk(scores, topk)}
        if chosen is not None:
            record.update(size=judged[..., 0], differs=judged[..., 1],
                          outside=judged[..., 2])
        if at is None:
            record["keep"] = keep
    else:               # a shared layer: its full layer's sets
        keep = chosen

    # ---- the attend, a group of heads (and of their weights) at a time:
    # a loop, so that one group's keys and values are held at once
    hg = min(HEAD_GROUP, H)

    def group(gi, out):
        def heads(t, axis):
            return jax.lax.dynamic_slice_in_dim(t, gi * hg, hg, axis)

        wuq = _w(heads(L["wuq"].reshape(-1, H, nope + rope), 1))
        gate_w = _w(heads(L["attn_gate"].reshape(d, H, dv), 1))
        wo = _w(heads(L["wo"].reshape(H, dv, d), 0))
        sink = _w(heads(L["attn_sink"], 0))
        k_nope = _r(jnp.einsum("bnc,chw->bnhw", c_kv, _w(heads(L["wuk"], 1))))
        v = _r(jnp.einsum("bnc,chw->bnhw", c_kv, _w(heads(L["wuv"], 1))))

        def attend(q0, size, out):
            at_q = q0 + jnp.arange(size)
            q = jnp.einsum("bqc,chw->bqhw", _at(c_q, q0, size), wuq)
            q_nope = _r(q[..., :nope])
            q_rope = _r(_rope(q[..., nope:], _rope_tables(at_q, model)))
            s = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope)
                 + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_r)) * scale
            mask = (jnp.arange(n)[None, None, :] <= at_q[None, :, None]) \
                & _unpack(_at(keep, q0, size), n)
            s = jnp.where(mask[:, None], s, -jnp.inf)
            s_sink = jnp.broadcast_to(sink[None, :, None, None],
                                      s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, s_sink], -1),
                               -1)[..., :n]
            o = jnp.einsum("bhqk,bkhd->bqhd", _r(p), v)
            gate = jax.nn.sigmoid(jnp.einsum("bqd,dhw->bqhw",
                                             _at(h, q0, size), gate_w))
            part = jnp.einsum("bqhw,hwd->bqd", _r(o * gate), wo)
            return _put(out, _at(out, q0, size) + part, q0)

        return _for_blocks(n, QUERY_BLOCK, attend, out)

    out = jax.lax.fori_loop(0, H // hg, group,
                            jnp.zeros((b, n, d), jnp.float32))
    return out, record


# ---- the FFN ----------------------------------------------------------------

def _gated(h, wg, wu, wd, limit=None):
    """silu(h W_g) (h W_u) W_d (clamped with ``limit``), HIDDEN_BLOCK
    hidden units at a time."""
    h = _r(h)
    f = wg.shape[1]

    def part(c0, size, y):
        g = h @ _w(jax.lax.dynamic_slice_in_dim(wg, c0, size, axis=1))
        u = h @ _w(jax.lax.dynamic_slice_in_dim(wu, c0, size, axis=1))
        if limit:
            g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
        return y + _r(jax.nn.silu(g) * u) @ _w(
            jax.lax.dynamic_slice_in_dim(wd, c0, size, axis=0))

    return _for_blocks(f, HIDDEN_BLOCK, part, jnp.zeros(
        h.shape[:-1] + (wd.shape[1],), jnp.float32))


def select(choice, model: dict):
    """Choice scores (..., E) -> (ids (..., k) ascending, margin (...)):
    the top ``experts_per_tok`` of one group, and the gap between the last
    expert chosen and the first left out."""
    k = model["experts_per_tok"]
    top, ids = jax.lax.top_k(choice, k + 1)
    return jnp.sort(ids[..., :k], axis=-1), top[..., k - 1] - top[..., k]


def _experts(h, M, model, forced):
    first, limit = model["expert_first"], model["swiglu_limit"]
    scores = jax.nn.sigmoid(_r(h) @ _w(M["wr"]))
    choice = scores + _w(M["br"])
    own, margin = select(choice, model)
    ids = own if forced is None else jnp.where(forced[..., :1] >= 0,
                                               forced, own)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True) * model["routed_scale"]
    y = _gated(h, M["swg"], M["swu"], M["swd"], limit)
    for e in range(M["wg"].shape[0]):   # this chip's experts; the others:
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        y = y + w_e[..., None] * _gated(h, M["wg"][e], M["wu"][e],
                                        M["wd"][e], limit)   # left out
    return y, {"ids": own, "margin": margin, "choice": choice}


def ffn(h, L, model, forced=None):
    """(y (b, n, d), routing record) of the normed input, a block of
    TOKEN_BLOCK positions at a time."""
    b, m, d = h.shape
    if "moe" not in L:
        def dense(s, size, y):
            return _put(y, _gated(_at(h, s, size), L["wg"], L["wu"],
                                  L["wd"]), s)
        return _for_blocks(m, TOKEN_BLOCK, dense,
                           jnp.zeros((b, m, d), jnp.float32)), {}
    k, E = model["experts_per_tok"], L["moe"]["wr"].shape[1]

    def routed(s, size, carry):
        y, rec = carry
        f = None if forced is None else _at(forced, s, size)
        y_s, r_s = _experts(_at(h, s, size), L["moe"], model, f)
        return _put(y, y_s, s), {n_: _put(rec[n_], r_s[n_], s) for n_ in rec}

    rec = {"ids": jnp.zeros((b, m, k), jnp.int32),
           "margin": jnp.zeros((b, m), jnp.float32),
           "choice": jnp.zeros((b, m, E), jnp.float32)}
    return _for_blocks(m, TOKEN_BLOCK, routed,
                       (jnp.zeros((b, m, d), jnp.float32), rec))


# ---- the model ------------------------------------------------------------

def embed(params, tokens, model):
    """(b, n) tokens -> the streams (b, 4, n, d): the embedding in each."""
    e = _w(params["embed"])[tokens]
    return jnp.broadcast_to(e[:, None], (e.shape[0], model["hc_mult"])
                            + e.shape[1:]) + 0.0


def layer(L, X, model: dict, kind, forced=None, chosen=None, at=None,
          band=None):
    """One layer on the streams X (b, 4, n, d) float32, positions 0..n-1:
    ``read``, ``attention``, ``write``, ``read``, ``ffn``, ``write``, the
    phases a caller at full length runs as programs of their own (so that
    ``write`` updates the streams in place and nothing else of a phase
    outlives it). ``kind``: the layer's index type, "full" or "shared".
    Returns (X, record): the routing record of an expert layer ({"ids",
    "margin", "choice"}; {} for the dense one) with, on a full layer, under
    "dsa" the selector's {"scores", "select"} of the queries at ``at`` (all
    of them, and their packed sets "keep", without), and with ``chosen``
    its verdict on every set handed in ({"size", "differs", "outside"},
    (b, n) each)."""
    with jax.default_matmul_precision("highest"):
        h, post, H = read(X, L["hc_attn"], L["ln1"]["g"], model)
        att, dsa = attention(h, L, model, kind, chosen, at, band)
        X = write(X, att, post, H)
        h, post, H = read(X, L["hc_ffn"], L["ln2"]["g"], model)
        y, record = ffn(h, L, model, forced)
        X = write(X, y, post, H)
        if dsa is not None:
            record["dsa"] = dsa
        return X, record


def head(params, X, model: dict):
    """The streams (b, 4, m, d) -> (b, m, V) float32 logits."""
    with jax.default_matmul_precision("highest"):
        h = _rms(X.sum(1), params["ln_f"]["g"], model["norm_eps"])
        return _r(h) @ _w(params["head"]).T


def forward(params, tokens, model: dict, forced=None):
    """(b, n) tokens -> ((b, n, V) float32 logits, one record per layer):
    every full layer chooses its own sets, every shared layer attends its
    full layer's. ``forced``: one (b, n, k) int array per EXPERT layer."""
    X = embed(params, tokens, model)
    records, n_moe, keep = [], 0, None
    for i, L in enumerate(params["layers"]):
        want = None
        if "moe" in L:
            want = None if forced is None else forced[n_moe]
            n_moe += 1
        kind = model["index_types"][i]
        X, record = layer(L, X, model, kind, want,
                          None if kind == "full" else keep)
        if kind == "full":
            keep = record["dsa"]["keep"]
        records.append(record)
    return head(params, X, model), records


def logits(params, tokens, model: dict):
    return forward(params, tokens, model)[0]
