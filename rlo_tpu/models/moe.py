"""Mixture-of-experts FFN with expert parallelism over a mesh axis.

Net-new capability (the reference has no model code or parallelism
strategies — SURVEY.md §5); completes the framework's strategy set
(dp / sp / tp / ep) on the same collective substrate: expert dispatch
and return are the `all_to_all` collective (rlo_tpu.ops.tpu_collectives),
the one communication pattern the other strategies don't use.

Design (switch-style top-1 routing with static capacity, the
TPU-friendly formulation — everything is dense one-hot einsums, no
dynamic shapes, so XLA tiles it onto the MXU):

  - router: logits = h @ wr -> softmax gate; each token goes to its
    argmax expert, carrying the gate probability (the only path the
    gradient needs through the discrete choice);
  - capacity C = ceil(cap_factor * T / E) per expert per shard; tokens
    beyond an expert's capacity are dropped (output 0 for them, the
    residual stream carries them unchanged);
  - dispatch: one-hot (T, E, C) tensor; expert inputs are
    einsum('tec,td->ecd') — and the combine on the way back multiplies
    by the gate, so dropped slots vanish;
  - expert parallelism: experts are sharded over `ep_axis` (each shard
    owns E/ep experts); the (E, C, d) dispatch block reshapes to
    (ep, E_local, C, d) and one all_to_all ships every shard's slice of
    my experts to me; after the local expert FFNs, a second all_to_all
    ships results back;
  - aux load-balancing loss (Switch Transformer form):
    E * sum_e fraction_dispatched(e) * mean_gate_prob(e).

The second layer here, ``routed_ffn`` (``moe_router='sigmoid_group'``),
is sparse experts as large open models deploy them, for the serving
path: sigmoid scores in float32, a bias that moves the selection only,
the top ``experts_per_tok`` experts among the best ``topk_group`` of
``n_group`` groups, their scores normalised to sum 1 and times
``routed_scale``, ``n_shared_experts`` always-on experts, gated FFNs,
and NO dropped token at any skew. ``moe_router='softmax_topk'`` is the
same layer behind the other common router (``route_softmax``): a
softmax over all experts in float32, the ``experts_per_tok`` largest
stay, their weights divided by their sum; no groups, no bias. The
layer is told which experts it
holds (``expert_first``, ``n_experts_held``): it routes over all
``n_experts`` and adds the terms of its own experts and of the shared
expert — one chip's share of an expert-parallel layer. On one chip
there is no exchange; what the absent experts would add is left out.
On the TPU the held experts run as one grouped product over rows
sorted by expert (pallas.expert_ffn), elsewhere as a dense einsum.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from rlo_tpu.ops import tpu_collectives as tc
from rlo_tpu.pallas.expert_ffn import swiglu


def init_moe_params(rng: jax.Array, d_model: int, d_ff: int,
                    n_experts: int) -> dict:
    """Router + per-expert FFN weights. Expert-indexed leading axes are
    the ones `ep` shards (see transformer.param_pspecs)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = d_model ** -0.5
    scale_out = d_ff ** -0.5
    return {
        "wr": jax.random.normal(k1, (d_model, n_experts),
                                jnp.float32) * scale_in,
        "w1": jax.random.normal(k2, (n_experts, d_model, d_ff),
                                jnp.float32) * scale_in,
        "w2": jax.random.normal(k3, (n_experts, d_ff, d_model),
                                jnp.float32) * scale_out,
    }


def moe_ffn(params: dict, h, n_experts: int, *,
            capacity_factor: float = 2.0,
            ep_axis: Optional[str] = None,
            all_to_all_algorithm: str = "xla") -> Tuple[jax.Array,
                                                        jax.Array]:
    """Apply the MoE FFN to ``h`` (..., d). Returns (out, aux_loss).

    With ``ep_axis``: ``params['w1']/['w2']`` arrive sharded to this
    shard's E/ep experts; ``h`` is this shard's tokens. Tokens cross
    shards only inside the two all_to_all calls.
    """
    orig_shape = h.shape
    dt = h.dtype
    d = h.shape[-1]
    x = h.reshape(-1, d)
    t = x.shape[0]
    ep = lax.axis_size(ep_axis) if ep_axis is not None else 1
    e_local = params["w1"].shape[0]
    n_exp = n_experts
    assert e_local * ep == n_exp, (
        f"expert shards {e_local}x{ep} != n_experts {n_exp}")
    cap = max(1, math.ceil(capacity_factor * t / n_exp))

    # ---- router (float32 for a stable softmax) ----
    logits = x.astype(jnp.float32) @ params["wr"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)          # (T, E)
    expert = jnp.argmax(gates, axis=-1)              # (T,)
    prob = jnp.max(gates, axis=-1)                   # (T,)

    onehot = jax.nn.one_hot(expert, n_exp, dtype=jnp.float32)   # (T, E)
    # position of each token within its expert's queue
    pos = (jnp.cumsum(onehot, axis=0) - onehot) * onehot         # (T, E)
    keep = (pos < cap) * onehot                                  # (T, E)
    slot = jax.nn.one_hot(jnp.sum(pos, axis=-1).astype(jnp.int32), cap,
                          dtype=jnp.float32)                     # (T, C)
    dispatch = (keep[:, :, None] * slot[:, None, :]).astype(dt)  # (T,E,C)

    # aux load-balance loss: fraction routed vs mean gate mass per expert
    frac = jnp.mean(onehot, axis=0)
    mean_gate = jnp.mean(gates, axis=0)
    aux = n_exp * jnp.sum(frac * mean_gate)

    # the heavy einsums run in the activation dtype (bf16 on TPU — the
    # MXU path, like the dense FFN); only the router needed float32
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)           # (E,C,d)

    if ep_axis is not None:
        blocks = expert_in.reshape(ep, e_local, cap, d)
        # dispatch: shard s's slice for my experts arrives at row s
        blocks = tc.all_to_all(blocks, ep_axis,
                               algorithm=all_to_all_algorithm)
        xin = jnp.moveaxis(blocks, 0, 1).reshape(e_local, ep * cap, d)
    else:
        xin = expert_in                                          # (E,C,d)

    h1 = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xin,
                                params["w1"].astype(dt)))
    out_blocks = jnp.einsum("ecf,efd->ecd", h1, params["w2"].astype(dt))

    if ep_axis is not None:
        back = jnp.moveaxis(
            out_blocks.reshape(e_local, ep, cap, d), 1, 0)
        back = tc.all_to_all(back, ep_axis,
                             algorithm=all_to_all_algorithm)
        expert_out = back.reshape(n_exp, cap, d)
    else:
        expert_out = out_blocks

    combine = dispatch * prob[:, None, None].astype(dt)          # (T,E,C)
    out = jnp.einsum("tec,ecd->td", combine, expert_out,
                     preferred_element_type=jnp.float32)
    return out.reshape(orig_shape).astype(dt), aux


# ---- sigmoid-scored, group-limited, dropless experts -----------------

#: per-layer counts routed_ffn reports, in this order (int32)
STATS = ("tokens", "assignments_held", "rows_computed", "experts_hit",
         "dropped")

#: the ``moe_router`` settings routed_ffn serves
ROUTED = ("sigmoid_group", "softmax_topk")


def init_routed_params(rng: jax.Array, cfg) -> dict:
    """Router (d, n_experts) and ('sigmoid_group') its selection bias
    ``br``, the held experts' gated FFNs — ``wg``/``wu`` (held, d, f), ``wd``
    (held, f, d) — and the shared expert's (``swg``/``swu``/``swd``,
    width f x n_shared_experts), stored in ``cfg.param_dtype``. The
    bias is drawn at 0.01, twice the median gap between the last expert
    chosen and the first left out: it changes most selections while
    every expert stays about equally loaded, which is what the bias is
    trained for."""
    d, f, pdt = cfg.d_model, cfg.moe_d_ff, cfg.weight_dtype
    held = cfg.experts_held
    ks = jax.random.split(rng, 8)

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(pdt)

    out_scale = (2 * f * cfg.n_layers) ** -0.5
    p = {"wr": norm(ks[0], (d, cfg.n_experts), d ** -0.5),
         "wg": norm(ks[2], (held, d, f), d ** -0.5),
         "wu": norm(ks[3], (held, d, f), d ** -0.5),
         "wd": norm(ks[4], (held, f, d), out_scale)}
    if cfg.moe_router == "sigmoid_group":
        p["br"] = jax.random.normal(ks[1], (cfg.n_experts,),
                                    jnp.float32) * 0.01
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p.update(swg=norm(ks[5], (d, fs), d ** -0.5),
                 swu=norm(ks[6], (d, fs), d ** -0.5),
                 swd=norm(ks[7], (fs, d), out_scale))
    return p


def route(x, wr, br, *, n_group: int, topk_group: int, top_k: int,
          routed_scale: float):
    """Tokens ``x`` (T, d) -> (ids (T, k) int32, weights (T, k) f32,
    choice scores (T, E) f32). Scores are sigmoid(x wr) in float32 at
    full matmul precision; the choice is made on scores + ``br``: a
    group's score is the sum of its top 2, the best ``topk_group``
    groups stay, the top ``top_k`` experts among them are chosen. The
    weights are the chosen experts' scores WITHOUT the bias, normalised
    to sum 1, times ``routed_scale``."""
    t = x.shape[0]
    n_exp = wr.shape[1]
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), wr.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    choice = scores + br.astype(jnp.float32)
    grouped = choice.reshape(t, n_group, n_exp // n_group)
    group_score = lax.top_k(grouped, 2)[0].sum(-1)        # (T, G)
    _, kept = lax.top_k(group_score, topk_group)
    keep = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], kept].set(True)
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf)
    _, ids = lax.top_k(masked.reshape(t, n_exp), top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * routed_scale
    return ids.astype(jnp.int32), w, choice


def route_softmax(x, wr, *, top_k: int):
    """Tokens ``x`` (T, d) -> (ids (T, k) int32, weights (T, k) f32,
    choice scores (T, E) f32): ``softmax(x wr)`` over ALL experts in
    float32 at full matmul precision, the ``top_k`` largest stay (ties
    to the lowest expert), their probabilities divided by their sum."""
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), wr.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    w, ids = lax.top_k(scores, top_k)
    return (ids.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True),
            scores)


def row_tile(n_assign: int, n_experts: int) -> int:
    """Rows of one tile of the grouped product: a power of two from 16
    (a bf16 sublane tile) to 256, about twice the rows one of the
    ``n_experts`` routed over expects, so that an expert's rows mostly
    fit one tile and its weights stream once."""
    want = max(16, 2 * n_assign // max(n_experts, 1))
    return min(256, 1 << (want - 1).bit_length())


def _gated(x, wg, wu, wd, limit: float = 0.0):
    dt = x.dtype
    if limit:
        return swiglu(x @ wg.astype(dt), x @ wu.astype(dt), limit) \
            @ wd.astype(dt)
    gate = jax.nn.silu(x @ wg.astype(dt))
    return (gate * (x @ wu.astype(dt))) @ wd.astype(dt)


def held_experts_ffn(params: dict, x, ids, w, first: int,
                     use_kernel=None, interpret=None, limit: float = 0.0):
    """Sum over the held experts e in [first, first + held) of
    w[t, e] * ffn_e(x[t]) for tokens ``x`` (T, d) -> ((T, d) f32,
    stats). Kernel path: every (token, choice) that landed on a held
    expert becomes one row; rows are sorted by expert, each expert's
    run is padded to whole tiles of ``row_tile`` rows, and
    pallas.expert_ffn runs the live tiles (an expert with no row costs
    nothing). The buffer holds the worst case — every assignment here —
    so nothing is ever dropped. Oracle path: every held expert on every
    token, masked. ``limit`` > 0: the clamped SwiGLU
    (pallas.expert_ffn.swiglu)."""
    from rlo_tpu.pallas import expert_ffn as ek
    from rlo_tpu.pallas.reduce import kernel_gate
    t, d = x.shape
    k = ids.shape[1]
    held, _, f = params["wg"].shape
    n_assign = t * k
    tm = row_tile(n_assign, params["wr"].shape[1])
    local = ids - first
    here = (local >= 0) & (local < held)                 # (T, k)
    # (T, k, held): which held expert a choice landed on, if any
    onto = jax.nn.one_hot(jnp.where(here, local, held), held + 1,
                          dtype=jnp.float32)[..., :held]
    counts = jnp.sum(onto, axis=(0, 1)).astype(jnp.int32)  # (held,)
    padded = -(-counts // tm) * tm
    n_rows = ek.buffer_rows(n_assign, held, tm)
    placed = jnp.minimum(jnp.sum(padded), n_rows)  # rows in live tiles

    def stats(dropped):
        return jnp.stack([
            jnp.int32(t), jnp.sum(counts), placed,
            jnp.sum(counts > 0).astype(jnp.int32),
            jnp.asarray(dropped, jnp.int32)])
    if use_kernel is None:
        ok = ek.can_expert_ffn(d, f, tm)
        use_kernel = ok if interpret else kernel_gate(
            ok, f"expert_ffn (d={d}, f={f}, tile={tm})")
    if not use_kernel:
        # every held expert on every token; weights of the choices that
        # landed here, summed per expert
        we = jnp.sum(onto * w[..., None], axis=1)        # (T, held)
        dt = x.dtype
        if limit:
            act = swiglu(
                jnp.einsum("td,edf->tef", x, params["wg"].astype(dt)),
                jnp.einsum("td,edf->tef", x, params["wu"].astype(dt)),
                limit)
        else:
            gate = jax.nn.silu(jnp.einsum("td,edf->tef", x,
                                          params["wg"].astype(dt)))
            act = gate * jnp.einsum("td,edf->tef", x,
                                    params["wu"].astype(dt))
        out = jnp.einsum("tef,efd->ted", act, params["wd"].astype(dt))
        return (jnp.einsum("te,ted->td", we, out.astype(jnp.float32)),
                stats(0))

    # rows sorted by expert; an assignment elsewhere sorts last
    key = jnp.where(here, local, held).reshape(n_assign)
    order = jnp.argsort(key, stable=True)
    rank = jnp.zeros((n_assign,), jnp.int32).at[order].set(
        jnp.arange(n_assign, dtype=jnp.int32))           # sorted position
    start = jnp.cumsum(counts) - counts                  # unpadded
    pstart = jnp.cumsum(padded) - padded                 # tile-aligned
    safe = jnp.minimum(key, held - 1)
    dest = jnp.where(key < held,
                     pstart[safe] + rank - start[safe], n_rows)
    token_of_row = jnp.zeros((n_rows,), jnp.int32).at[dest].set(
        jnp.arange(n_assign, dtype=jnp.int32) // k, mode="drop")
    n_tiles = n_rows // tm
    tile_expert = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(n_tiles, dtype=jnp.int32) * tm,
        side="right"), held - 1).astype(jnp.int32)
    n_live = (placed // tm).astype(jnp.int32)
    rows = ek.expert_ffn(x[token_of_row], params["wg"], params["wu"],
                         params["wd"], tile_expert, n_live, tile=tm,
                         interpret=interpret, limit=limit)  # (n_rows, d)
    # back to tokens: each choice reads its own row (a select, never a
    # product: a tile that did not run holds no number)
    picked = rows[jnp.minimum(dest, n_rows - 1).reshape(t, k)]
    picked = jnp.where(here[..., None], picked.astype(jnp.float32), 0.0)
    dropped = jnp.sum((key < held) & (dest >= n_rows))
    return jnp.einsum("tk,tkd->td", w, picked), stats(dropped)


def routed_ffn(params: dict, h, cfg):
    """The routed expert layer (``cfg.moe_router`` in ROUTED) on ``h``
    (..., d): routed terms of the held experts plus the shared expert,
    if any. Returns (out, info); ``info`` = {"ids" (T, k), "choice"
    (T, E) f32: the scores the selection was made on, "stats" int32
    (5,) in STATS order}."""
    shape, dt = h.shape, h.dtype
    x = h.reshape(-1, shape[-1])
    with jax.named_scope("moe.route"):
        if cfg.moe_router == "softmax_topk":
            ids, w, choice = route_softmax(x, params["wr"],
                                           top_k=cfg.experts_per_tok)
        else:
            ids, w, choice = route(
                x, params["wr"], params["br"], n_group=cfg.n_group,
                topk_group=cfg.topk_group, top_k=cfg.experts_per_tok,
                routed_scale=cfg.routed_scale)
    with jax.named_scope("moe.experts"):
        out, stats = held_experts_ffn(params, x, ids, w,
                                      cfg.expert_first,
                                      limit=cfg.swiglu_limit)
    if "swg" in params:
        with jax.named_scope("moe.shared"):
            out = out + _gated(x, params["swg"], params["swu"],
                               params["swd"],
                               cfg.swiglu_limit).astype(jnp.float32)
    info = {"ids": ids, "choice": choice, "stats": stats}
    return out.reshape(shape).astype(dt), info
