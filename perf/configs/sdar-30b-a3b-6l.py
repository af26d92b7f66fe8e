"""The plain reference of the ``sdar-30b-a3b-6l`` configuration: SDAR-30B-A3B's
forward pass and its block-diffusion sampler as straightforward ``jax.numpy``
in float32 at matmul precision ``highest``, with no kernels, no cache and
nothing imported from ``rlo_tpu``. It reads its sizes from the configuration's
``model`` section and takes the program's parameter tree (bfloat16 storage is
converted to float32 here, an expert at a time, so that it fits beside 8.7 GB
of weights):

    embed (V, d); head (V, d), untied; ln_f.g (d,); per layer ln1.g, ln2.g,
    wq (d, H x 128), wkv (d, 2, KV x 128), q_norm.g, k_norm.g (128,),
    wo (H x 128, d); moe = wr (d, E), wg, wu (held, d, f), wd (held, f, d).

Equations (x is the residual stream, rms(t) = t / sqrt(mean(t^2) + eps) * g,
B the block length):

Attention on h = rms(x): q = h W_q as H heads of 128, [k | v] = h W_kv as KV
    heads of 128 each; q <- rms_head(q), k <- rms_head(k) (a gain of 128 a
    layer, over each head's width, BEFORE the rotation: Qwen3's, ``assumed``);
    q, k rotated at their positions, theta 1e6, pairs (i, i + 64); query
    head i uses kv head i // (H / KV); s = q . k * 128^-0.5; position i
    attends position j iff j // B <= i // B (block-causal: all of its own
    block, and every block before it); softmax; o = P v; heads concatenated
    (H x 128) -> W_o -> d. No biases.

Feed-forward on h = rms(x): s = softmax(h W_r) over all E experts in float32;
    the 8 largest stay (ties to the lowest expert), w = s at those, divided
    by their sum; y = sum_e w_e W_d,e (silu(W_g,e h) * W_u,e h). This chip
    holds experts [expert_first, expert_first + n_experts_held) (all of them
    in the configuration as run): only their terms are added.

The sampler (``sample``; greedy, rule 'low_confidence_dynamic', JetLM's
published block-diffusion sampler with denoising_steps = B): the prompt, then
blocks of B positions that start as the mask id. One pass = one full forward
of everything up to the block's end. At each masked position c = max softmax
(logits), in float32, and its argmax; every masked position with
c > confidence is unmasked, or, if there is none, the one with the largest c
(the lowest position on a tie). With no mask left the block stands and the
next begins; positions past what was asked for are dropped. Which positions
are masked is state of the sampler, not a test for the mask id among the
tokens (a prompt may hold that id).

``forced``: the on-chip check (perf/kinds/serve_diffusion.py) hands the
program's own expert sets back in, after holding each against this file's
scores, as perf/configs/deepseek-v3-ep16.py does and for the same reason.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


#: None, or a dtype that every activation entering a matrix product is
#: rounded to. Set by hand, to float8_e4m3fn, for the reading that the
#: tolerances in the configuration file are set against (PERF.md, PR 34):
#: computed one precision below bfloat16, this file must FAIL its own check.
ACT_DTYPE = None


def _r(x):
    return x if ACT_DTYPE is None else x.astype(ACT_DTYPE).astype(x.dtype)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(t, theta):
    """t (b, n, heads, hd) at positions 0..n-1: pairs (i, i + hd / 2)."""
    n, half = t.shape[1], t.shape[-1] // 2
    ang = np.arange(n)[:, None] * float(theta) ** (
        -np.arange(half) / half)[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def block_mask(n: int, block_len: int):
    """(n, n) bool: query i attends key j iff j // B <= i // B."""
    blk = np.arange(n) // block_len
    return jnp.asarray(blk[None, :] <= blk[:, None])


def _attention(h, L, model):
    b, n, _ = h.shape
    H, KV = model["n_heads"], model["n_kv_heads"]
    hd, eps = model["attn_head_dim"], model["norm_eps"]
    h = _r(h)
    q = (h @ L["wq"]).reshape(b, n, H, hd)
    wkv = L["wkv"]
    kv = h @ wkv.reshape(wkv.shape[0], -1)
    k, v = (t.reshape(b, n, KV, hd) for t in jnp.split(kv, 2, axis=-1))
    q = _rope(_rms(q, L["q_norm"]["g"], eps), model["rope_theta"])
    k = _rope(_rms(k, L["k_norm"]["g"], eps), model["rope_theta"])
    k = jnp.repeat(k, H // KV, axis=2)      # query head i: kv head i // 8
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", _r(q), _r(k)) * hd ** -0.5
    mask = block_mask(n, model["block_len"])
    p = jax.nn.softmax(jnp.where(mask[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", _r(p), _r(v)).reshape(b, n, -1)
    return _r(o) @ L["wo"]


def select(choice, model: dict):
    """Choice scores (..., E) -> (ids (..., k) ascending, margin (...)):
    the k largest, ties to the lowest expert; ``margin`` is the gap between
    the last expert chosen and the first left out."""
    k = model["experts_per_tok"]
    top, ids = jax.lax.top_k(choice, k + 1)
    return jnp.sort(ids[..., :k], axis=-1), top[..., k - 1] - top[..., k]


def _experts(h, M, model, forced):
    held = M["wg"].shape[0]
    first = model["expert_first"]
    scores = jax.nn.softmax(_r(h) @ M["wr"].astype(jnp.float32), axis=-1)
    own, margin = select(scores, model)
    ids = own if forced is None else jnp.where(forced[..., :1] >= 0,
                                               forced, own)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / w.sum(-1, keepdims=True)
    hr = _r(h)

    def add(y, e):      # this chip's experts, one at a time
        wg, wu, wd = (M[n][e].astype(jnp.float32) for n in ("wg", "wu", "wd"))
        w_e = jnp.sum(jnp.where(ids == first + e, w, 0.0), axis=-1)
        out = _r(jax.nn.silu(hr @ wg) * (hr @ wu)) @ wd
        return y + w_e[..., None] * out, None

    y, _ = jax.lax.scan(add, jnp.zeros_like(h), jnp.arange(held))
    return y, {"ids": own, "margin": margin, "choice": scores}


def embed(params, tokens):
    return params["embed"].astype(jnp.float32)[tokens]


def layer(L, x, model: dict, forced=None):
    """One layer on the residual stream ``x`` (b, n, d) float32, positions
    0..n-1, under the block-causal mask. Returns (x, routing record)."""
    with jax.default_matmul_precision("highest"):
        M = L["moe"]
        L = _f32({k: v for k, v in L.items() if k != "moe"})
        eps = model["norm_eps"]
        x = x + _attention(_rms(x, L["ln1"]["g"], eps), L, model)
        y, record = _experts(_rms(x, L["ln2"]["g"], eps), M, model, forced)
        return x + y, record


def head(params, x, model: dict):
    with jax.default_matmul_precision("highest"):
        x = _rms(x, params["ln_f"]["g"].astype(jnp.float32),
                 model["norm_eps"])
        return _r(x) @ params["head"].astype(jnp.float32).T


def forward(params, tokens, model: dict, forced=None):
    """(b, n) tokens -> ((b, n, V) float32 logits, one routing record per
    layer). ``forced``: one (b, n, k) int array per layer; a position whose
    first entry is negative routes by its own scores."""
    x = embed(params, tokens)
    records = []
    for i, L in enumerate(params["layers"]):
        x, record = layer(L, x, model,
                          None if forced is None else forced[i])
        records.append(record)
    return head(params, x, model), records


def logits(params, tokens, model: dict):
    """(b, n) int tokens -> (b, n, V) float32 logits."""
    return forward(params, tokens, model)[0]


def unmask(lg, masked, confidence: float):
    """The rule on one block: ``lg`` (B, V) float32 logits, ``masked`` (B,)
    bool -> (candidates (B,), which positions to unmask (B,) bool)."""
    lg = np.asarray(lg, np.float32)
    p = np.exp(lg - lg.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    conf, cand = p.max(-1), p.argmax(-1)
    sure = masked & (conf > confidence)
    if not sure.any():
        sure = np.zeros_like(masked)
        sure[int(np.argmax(np.where(masked, conf, -1.0)))] = True
    return cand, sure


def sample(params, prompt, max_new: int, model: dict, trace=None):
    """``prompt`` (plen,) ints -> (max_new,) ints: the sampler as a plain
    loop, every pass a full forward of the whole sequence without a cache
    (the blocks after the current one, all mask ids, are attended by
    nothing before them). ``trace``: a list that receives the number of
    positions each denoise pass unmasked."""
    B, mask_id = model["block_len"], model["mask_id"]
    prompt = [int(t) for t in prompt]
    toks = list(prompt)
    masked = [False] * len(toks)
    want = len(prompt) + max_new
    fwd = jax.jit(lambda p, t: logits(p, t, model))
    while len(toks) % B or len(toks) < want:    # whole blocks
        toks.append(mask_id)
        masked.append(True)
    for start in range(len(prompt) // B * B, len(toks), B):
        while any(masked[start:start + B]):
            lg = fwd(params, jnp.asarray([toks]))[0, start:start + B]
            cand, go = unmask(lg, np.array(masked[start:start + B]),
                              model["confidence"])
            for i in np.flatnonzero(go):
                toks[start + i], masked[start + i] = int(cand[i]), False
            if trace is not None:
                trace.append(int(go.sum()))
    return np.array(toks[len(prompt):want], np.int32)
