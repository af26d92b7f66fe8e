"""Pipeline parallelism: transformer layers sharded over a `pp` mesh
axis, microbatches streamed stage-to-stage with `lax.ppermute`.

Net-new capability completing the strategy set (dp/sp/tp/ep/pp; the
reference has none — SURVEY.md §5). GPipe-style schedule expressed the
TPU way: one SPMD program under shard_map where every stage runs the
same `lax.scan` over M + pp - 1 pipeline ticks; at each tick a stage
applies its local layer block and hands the activation to its successor
through a single CollectivePermute (the chain permutation
[(0,1), (1,2), ...] — no wraparound, so stage 0's inbound edge is the
zeros the schedule expects during fill). Stage 0 injects a fresh
microbatch each tick; the last stage collects finished activations and
computes logits + loss; the per-stage work is itself a `lax.scan` over
the stage's stacked layer parameters. No data-dependent control flow —
bubbles are masked arithmetic, so XLA overlaps the ppermute with the
next tick's matmuls.

Parameters: `stack_layers` converts the flagship model's per-layer list
(models.transformer.init_params) into leaves stacked over a leading
layer axis, which `pipeline_pspecs` shards over `pp` (each stage owns
n_layers/pp layers); embed and final-norm are replicated (the embedding
is used by stage 0 to embed and by the last stage to unembed — its
gradient contributions from both ends combine through vma's automatic
psum over pp).

Gradients flow through the scan + ppermute chain by ordinary reverse AD
(the transpose of a chain ppermute is the reverse chain), so stage-local
layer grads stay local and `train_step`-style SGD applies shard-wise.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from rlo_tpu.models.transformer import (TransformerConfig, _rmsnorm,
                                        embed_tokens, _vma_active, apply_layer,
                                        next_token_targets, nll_sum)


def stack_layers(params: dict) -> dict:
    """Convert init_params' per-layer list into stacked (L, ...) leaves
    (scan-able; the leading axis is what `pp` shards)."""
    layers = params["layers"]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    return {"embed": params["embed"], "ln_f": params["ln_f"],
            "stacked": stacked}


def unstack_layers(pparams: dict, n_layers: int) -> dict:
    """Inverse of `stack_layers` (global view)."""
    layers = [jax.tree.map(lambda x: x[i], pparams["stacked"])
              for i in range(n_layers)]
    return {"embed": pparams["embed"], "ln_f": pparams["ln_f"],
            "layers": layers}


def pipeline_pspecs(pp_axis: Optional[str] = None,
                    cfg: Optional[TransformerConfig] = None):
    """PartitionSpec tree for `stack_layers` output: stacked layer
    leaves sharded over `pp` on the layer axis, embed/ln_f replicated.
    Pass ``cfg`` so the attention-projection leaves match it (GQA
    configs carry wq/wkv instead of the fused wqkv) — omitting it
    assumes MHA, like param_pspecs' default tree."""
    from jax.sharding import PartitionSpec as P
    layer = {
        "ln1": {"g": P(pp_axis, None)},
        "wo": P(pp_axis, None, None),
        "ln2": {"g": P(pp_axis, None)},
        "w1": P(pp_axis, None, None),
        "w2": P(pp_axis, None, None),
    }
    if cfg is not None and cfg.kv_heads != cfg.n_heads:
        layer["wq"] = P(pp_axis, None, None)
        layer["wkv"] = P(pp_axis, None, None, None)
    else:
        layer["wqkv"] = P(pp_axis, None, None, None)
    return {"embed": P(), "ln_f": {"g": P()}, "stacked": layer}


def _make_stage_fn(cfg: TransformerConfig):
    """Apply this stage's local stacked layers to an activation block —
    a lax.scan over transformer.apply_layer, THE layer math (shared with
    forward, so the block cannot diverge between the two)."""
    if cfg.windowed:
        raise NotImplementedError(
            "a scan over stacked layers has one attention kind: sliding "
            "layers train through models.transformer.train_step so far")
    if cfg.hc or cfg.index_types is not None:
        raise NotImplementedError(
            "a pipeline stage passes one residual and no token selection "
            "between layers: hyper-connected streams and a shared "
            "selection run through models.transformer.forward and the "
            "dense DecodeServer so far")

    def one_layer(x, lp):
        pos = jnp.arange(x.shape[1])  # full sequence per microbatch
        x, _aux = apply_layer(x, lp, cfg, pos=pos)
        return x, None

    def stage(stacked_local, x):
        out, _ = lax.scan(one_layer, x, stacked_local)
        return out

    return stage


def pipeline_loss(pparams: dict, tokens, cfg: TransformerConfig,
                  pp_axis: str, n_micro: int):
    """Mean next-token cross-entropy, computed through the pipeline.

    tokens: (batch, blk), replicated across pp (batch % n_micro == 0).
    Equals models.transformer.loss_fn on the same params/tokens exactly
    (microbatching only reorders batch-independent work).
    """
    if cfg.n_experts > 0:
        raise NotImplementedError(
            "pipeline parallelism currently supports dense layers only; "
            "MoE (n_experts > 0) composes with dp/sp/ep via "
            "models.transformer.train_step instead")
    pp = lax.axis_size(pp_axis)
    stage_idx = lax.axis_index(pp_axis)
    b, blk = tokens.shape
    assert b % n_micro == 0, f"batch {b} % n_micro {n_micro} != 0"
    mb = b // n_micro
    dt = cfg.act_dtype
    stage_fn = _make_stage_fn(cfg)
    tokens_mb = tokens.reshape(n_micro, mb, blk)
    pos = jnp.arange(blk)
    chain = [(i, i + 1) for i in range(pp - 1)]  # no wraparound

    def embed_mb(tok):
        return embed_tokens(pparams["embed"], tok, pos, cfg)

    state0 = jnp.zeros((mb, blk, cfg.d_model), dt)
    try:
        # the chain ppermute makes the carry varying over pp, and
        # dp-sharded tokens make it varying over dp — pre-vary the init
        # over both so the scan carry type is stable
        need = ({pp_axis} | set(jax.typeof(tokens).vma)) \
            - set(jax.typeof(state0).vma)
        if need:
            state0 = lax.pcast(state0, tuple(sorted(need)), to="varying")
    except (AttributeError, TypeError):
        pass

    def tick(state, t):
        m = jnp.clip(t, 0, n_micro - 1)
        fresh = embed_mb(lax.dynamic_index_in_dim(tokens_mb, m, 0,
                                                  keepdims=False))
        inp = jnp.where(stage_idx == 0, fresh, state)
        out = stage_fn(pparams["stacked"], inp)
        send = lax.ppermute(out, pp_axis, chain)
        return send, out

    _, outs = lax.scan(tick, state0, jnp.arange(n_micro + pp - 1))
    # the last stage finished microbatch m at tick m + pp - 1
    finished = lax.dynamic_slice_in_dim(outs, pp - 1, n_micro, 0)

    def mb_loss(x, tok):
        x = _rmsnorm(x, pparams["ln_f"]["g"])
        logits = (x @ pparams["embed"].T.astype(dt)).astype(jnp.float32)
        targets, valid = next_token_targets(tok)
        return nll_sum(logits, targets, valid)

    sums, counts = jax.vmap(mb_loss)(finished, tokens_mb)
    local = jnp.sum(sums) / jnp.sum(counts)
    # only the last stage computed real losses; psum of the masked value
    # broadcasts it (and types the result invariant over pp)
    return lax.psum(jnp.where(stage_idx == pp - 1, local, 0.0), pp_axis)


def pipeline_cost(schedule: str, pp: int, n_micro: int) -> dict:
    """Analytic schedule model (round-5 VERDICT item 8), same role as
    tpu_collectives.allreduce_cost: the numbers the lowered program
    must exhibit, pinned by jaxpr inspection in
    tests/test_pipeline_parallel.py.

    GPipe here = forward scan of M + pp - 1 ticks, backward derived by
    reverse AD (its transpose runs the mirrored schedule), one chain
    ppermute per tick each way. Peak boundary-activation storage is
    the scan's stacked carry history: M + pp - 1 microbatch blocks per
    stage (plus AD's per-tick layer residuals unless remat).

    1F1B = ONE explicit scan of M + 2(pp - 1) ticks doing a masked
    forward AND a masked backward sub-step per tick (two ppermutes:
    activations down the chain, cotangents back up). Stage backward
    recomputes its block (remat) from a ring buffer of saved INPUTS,
    so peak boundary storage is the ring: 2*pp - 1 blocks regardless
    of M — the point of 1F1B. Same bubble fraction class as GPipe
    (2(pp-1) idle of M + 2(pp-1) combined ticks vs GPipe's 2(pp-1) of
    2(M + pp - 1)); the win is memory, not bubbles.
    """
    if pp < 1 or n_micro < 1:
        raise ValueError("pp >= 1 and n_micro >= 1 required")
    if schedule == "gpipe":
        fwd = n_micro + pp - 1
        return {"fwd_ticks": fwd, "total_ticks": 2 * fwd,
                "permutes_per_tick": 1,
                "bubble_fraction": (pp - 1) / fwd,
                "peak_boundary_blocks": fwd}
    if schedule == "1f1b":
        ticks = n_micro + 2 * (pp - 1)
        return {"fwd_ticks": ticks, "total_ticks": ticks,
                "permutes_per_tick": 2,
                "bubble_fraction": 2 * (pp - 1) / ticks,
                "peak_boundary_blocks": min(2 * pp - 1, n_micro + pp - 1)}
    raise ValueError(f"no cost model for schedule {schedule!r}")


def pipeline_1f1b_train_step(pparams: dict, tokens,
                             cfg: TransformerConfig, pp_axis: str,
                             n_micro: int, lr: float = 1e-2,
                             dp_axis: Optional[str] = None
                             ) -> Tuple[dict, jax.Array]:
    """One SGD step on the 1F1B schedule — gradients EQUAL the GPipe
    step's (tests pin it): same math, different schedule.

    One lax.scan over M + 2(pp-1) ticks; tick t at stage s runs
      forward  of microbatch m_f = t - s            (masked in-range)
      backward of microbatch m_b = t - 2(pp-1) + s  (masked in-range)
    The backward sub-step recomputes the stage block from the saved
    stage INPUT (a (2pp-1)-slot ring buffer — the only boundary
    storage) and pulls the successor's cotangent through jax.vjp;
    cotangents ride the REVERSE chain ppermute. At the last stage
    m_b == m_f every tick, so the loss seed is computed in place.
    Per-microbatch loss seeds are UNNORMALIZED (d nll_sum); all grads
    scale by 1/total_valid_count at the end (grads are linear in the
    seed), which makes the step exactly the mean-loss gradient without
    knowing the total count up front.
    """
    assert _vma_active(pp_axis), (
        "pipeline training requires shard_jit's vma typing "
        "(check_vma=True)")
    if cfg.n_experts > 0:
        raise NotImplementedError("dense layers only (as pipeline_loss)")
    pp = lax.axis_size(pp_axis)
    stage_idx = lax.axis_index(pp_axis)
    b, blk = tokens.shape
    assert b % n_micro == 0, f"batch {b} % n_micro {n_micro} != 0"
    mb = b // n_micro
    dt = cfg.act_dtype
    stage_fn = _make_stage_fn(cfg)
    tokens_mb = tokens.reshape(n_micro, mb, blk)
    pos = jnp.arange(blk)
    chain = [(i, i + 1) for i in range(pp - 1)]
    rchain = [(i + 1, i) for i in range(pp - 1)]
    S = min(2 * pp - 1, n_micro + pp - 1)      # ring slots
    T = n_micro + 2 * (pp - 1)                 # ticks
    W = pparams["stacked"]

    def embed_mb(e, tok):
        return embed_tokens(e, tok, pos, cfg)

    def mb_loss_sum(x, lnf_g, e, tok):
        xn = _rmsnorm(x, lnf_g)
        logits = (xn @ e.T.astype(dt)).astype(jnp.float32)
        targets, valid = next_token_targets(tok)
        s, c = nll_sum(logits, targets, valid)
        return s, c

    def _vary(x):
        # every carry leaf must be varying over pp (and dp when tokens
        # are) from tick 0, or the scan carry type flips mid-loop
        try:
            need = ({pp_axis} | set(jax.typeof(tokens).vma)) \
                - set(jax.typeof(x).vma)
            if need:
                return lax.pcast(x, tuple(sorted(need)), to="varying")
        except (AttributeError, TypeError):
            pass
        return x

    zeros_x = _vary(jnp.zeros((mb, blk, cfg.d_model), dt))
    ring0 = jnp.zeros((S,) + zeros_x.shape, dt) + zeros_x  # varying too
    g0 = jax.tree.map(jnp.zeros_like, pparams)
    # embed/ln_f are REPLICATED (vma-invariant over pp) — a vjp wrt an
    # invariant input auto-psums the cotangent across stages, which
    # would leak every stage's masked-out garbage into the last
    # stage's loss-head grads. Differentiate VARYING copies instead:
    # each stage gets its own cotangent, masked locally, psummed ONCE
    # at the end.
    emb_v = _vary(pparams["embed"])
    lnf_v = _vary(pparams["ln_f"]["g"])
    # same trap on the stacked weights when composing with dp: they
    # are pp-sharded (varying over pp) but dp-REPLICATED, so a vjp wrt
    # them auto-psums dW over dp inside every tick — double-counting
    # once the final pmean runs. Differentiate a dp-varying copy.
    W_v = jax.tree.map(_vary, W)

    def tick(carry, t):
        recv_f, recv_b, ring, g, loss_s, loss_c = carry
        # ---- forward sub-step -------------------------------------
        m_f = t - stage_idx
        ok_f = (m_f >= 0) & (m_f < n_micro)
        mf_c = jnp.clip(m_f, 0, n_micro - 1)
        tok_f = lax.dynamic_index_in_dim(tokens_mb, mf_c, 0,
                                         keepdims=False)
        fresh = embed_mb(emb_v, tok_f)
        inp = jnp.where(stage_idx == 0, fresh, recv_f)
        inp = jnp.where(ok_f, inp, zeros_x)
        out = stage_fn(W, inp)
        # invalid ticks must NOT write: the clipped slot index would
        # clobber a LIVE slot with zeros (stage 0's last backwards
        # would then recompute from zeros — rmsnorm blows them up)
        prev = lax.dynamic_index_in_dim(ring, mf_c % S, 0,
                                        keepdims=False)
        ring = lax.dynamic_update_index_in_dim(
            ring, jnp.where(ok_f, inp, prev), mf_c % S, 0)
        send_f = lax.ppermute(out, pp_axis, chain)

        # ---- backward sub-step ------------------------------------
        m_b = t - 2 * (pp - 1) + stage_idx
        ok_b = (m_b >= 0) & (m_b < n_micro)
        mb_c = jnp.clip(m_b, 0, n_micro - 1)
        xin = lax.dynamic_index_in_dim(ring, mb_c % S, 0,
                                       keepdims=False)
        tok_b = lax.dynamic_index_in_dim(tokens_mb, mb_c, 0,
                                         keepdims=False)
        # recompute the block (remat) + pullback
        out_b, pull = jax.vjp(lambda w, x: stage_fn(w, x), W_v, xin)
        # cotangent seed: last stage = d(nll_sum)/d(out) in place;
        # other stages = the successor's cotangent off the wire
        (l_s, l_c), pull_loss = jax.vjp(
            lambda x, lg, e: mb_loss_sum(x, lg, e, tok_b),
            out_b, lnf_v, emb_v)
        from rlo_tpu.parallel.mesh import vary_like
        dx_loss, d_lnf, d_emb_un = pull_loss(
            (vary_like(jnp.float32(1.0), l_s),
             vary_like(jnp.float32(0.0), l_c)))
        is_last = stage_idx == pp - 1
        cot = jnp.where(is_last, dx_loss.astype(dt), recv_b)
        cot = jnp.where(ok_b, cot, zeros_x)
        dW, dx_in = pull(cot)
        # stage 0: pull the input cotangent through the embedding
        _, pull_embed = jax.vjp(lambda e: embed_mb(e, tok_b),
                                emb_v)
        (d_emb_in,) = pull_embed(dx_in)
        okb_f = ok_b.astype(jnp.float32)
        okl = (ok_b & is_last).astype(jnp.float32)
        ok0 = (ok_b & (stage_idx == 0)).astype(jnp.float32)
        g = {
            "stacked": jax.tree.map(
                lambda a, d: a + okb_f * d.astype(a.dtype),
                g["stacked"], dW),
            "ln_f": {"g": g["ln_f"]["g"]
                     + okl * d_lnf.astype(g["ln_f"]["g"].dtype)},
            "embed": (g["embed"]
                      + okl * d_emb_un.astype(g["embed"].dtype)
                      + ok0 * d_emb_in.astype(g["embed"].dtype)),
        }
        loss_s = loss_s + jnp.where(ok_b & is_last, l_s, 0.0)
        loss_c = loss_c + jnp.where(ok_b & is_last, l_c, 0.0)
        # the predecessor needs dL/d(my INPUT) — the pullback's dx_in,
        # masked so bubble garbage never rides the reverse chain
        send_b = lax.ppermute(
            jnp.where(ok_b, dx_in.astype(dt), zeros_x), pp_axis,
            rchain)
        return (send_f, send_b, ring, g, loss_s, loss_c), None

    carry0 = jax.tree.map(_vary, (zeros_x, zeros_x, ring0, g0,
                                  jnp.float32(0.0), jnp.float32(0.0)))
    (_, _, _, g, loss_s, loss_c), _ = lax.scan(
        tick, carry0, jnp.arange(T))
    # embed/ln_f contributions live on different stages — combine
    total_c = lax.psum(jnp.where(stage_idx == pp - 1, loss_c, 0.0),
                       pp_axis)
    scale = 1.0 / jnp.maximum(total_c, 1.0)
    grads = {
        "stacked": jax.tree.map(lambda x: x * scale, g["stacked"]),
        "ln_f": {"g": lax.psum(g["ln_f"]["g"], pp_axis) * scale},
        "embed": lax.psum(g["embed"], pp_axis) * scale,
    }
    loss = lax.psum(jnp.where(stage_idx == pp - 1, loss_s, 0.0),
                    pp_axis) / jnp.maximum(total_c, 1.0)
    if dp_axis is not None:
        # manual grads carry no vma auto-psum over dp — combine
        # explicitly (pmean == the GPipe step's AD psum + /n)
        grads = jax.tree.map(lambda gg: lax.pmean(gg, dp_axis), grads)
        loss = lax.pmean(loss, dp_axis)
    new_params = jax.tree.map(lambda p, gg: p - lr * gg.astype(p.dtype),
                              pparams, grads)
    return new_params, loss


def pipeline_train_step(pparams: dict, tokens, cfg: TransformerConfig,
                        pp_axis: str, n_micro: int, lr: float = 1e-2,
                        dp_axis: Optional[str] = None
                        ) -> Tuple[dict, jax.Array]:
    """One SGD step through the pipeline; composes with dp (tokens
    additionally sharded over `dp_axis`). Stage-local layer grads stay
    on their stage; embed/ln_f grads combine over pp via vma's automatic
    psum."""
    # without vma typing, the cross-stage psum of embed/ln_f cotangents
    # never happens and every stage silently takes a different step
    assert _vma_active(pp_axis), (
        "pipeline training requires shard_jit's vma typing "
        "(check_vma=True)")
    loss, grads = jax.value_and_grad(pipeline_loss)(pparams, tokens, cfg,
                                                    pp_axis, n_micro)
    if dp_axis is not None:
        n = lax.axis_size(dp_axis)
        grads = jax.tree.map(lambda g: g / n, grads)
        loss = lax.pmean(loss, dp_axis)
    new_params = jax.tree.map(lambda p, g: p - lr * g, pparams, grads)
    return new_params, loss
