"""Ring attention: sequence-parallel attention over the ppermute ring.

Long-context support, first-class on the same substrate as the data
collectives: the sequence axis is sharded over a mesh axis, Q blocks stay
resident, and K/V blocks rotate around the ring with `jax.lax.ppermute`
(the identical `topology.ring_perm` schedule the ring allreduce uses —
the skip-ring neighbor structure of the reference generalized from 32 KB
control frames, rootless_ops.c:1489, to streaming KV blocks). Softmax is
accumulated online (running max / denominator / weighted sum), so no
shard ever materializes the full attention matrix — memory per shard is
O(block² / ws) while supporting sequences ws× longer than one chip holds.

Why this shape on TPU: each ring step is one CollectivePermute (ICI
remote-DMA) overlapped by XLA with the block matmuls on the MXU; the
per-step state update (rescale + accumulate) is exactly the fused-combine
pattern of rlo_tpu.pallas.reduce applied to the (o, m, l) triple.

The reference has no attention (SURVEY.md §5 records the absence); this
is the net-new long-context capability the rebuild is required to carry.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from rlo_tpu import topology

from rlo_tpu.parallel.mesh import vary_like as _vary_like

_NEG = -1e30  # large-negative mask value (finite: keeps exp/max NaN-free)


def _block_update(q, k, v, m, l, o, q_pos, k_pos, causal, scale):
    """One online-softmax update of (m, l, o) with a K/V block.

    q: (Lq, H, D); k, v: (Lk, H, D); m, l: (H, Lq); o: (Lq, H, D).
    q_pos: (Lq,) and k_pos: (Lk,) are global token positions for masking.
    """
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (k_pos[None, :] <= q_pos[:, None])[None, :, :]  # (1,Lq,Lk)
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)  # (H, Lq)
    l_new = l * corr + p.sum(axis=-1)
    o_new = o * corr.T[..., None] + jnp.einsum(
        "hqk,khd->qhd", p, v.astype(jnp.float32))
    return m_new, l_new, o_new


def stripe_sequence(x, ws: int):
    """Reorder a full sequence (axis 0) into the STRIPED layout: shard r
    of a striped ring holds tokens {r, r+ws, r+2ws, ...}. Apply before
    sharding with layout='striped'; invert with unstripe_sequence.

    Why: with contiguous sharding and causal masking, ring step s on
    shard r is fully masked whenever the arriving K/V block comes from
    a later shard — up to half the steps do no useful work and the
    critical path is set by the last shard. Striding every shard's
    tokens across the whole sequence makes every (q block, kv block)
    pair ~half-unmasked, balancing useful work across all steps
    (Striped Attention; the masking here is position-driven, so only
    the position arrays change)."""
    seq = x.shape[0]
    if seq % ws:
        raise ValueError(f"sequence {seq} must divide by ws {ws}")
    blk = seq // ws
    return jnp.moveaxis(x.reshape(blk, ws, *x.shape[1:]), 1, 0) \
        .reshape(seq, *x.shape[1:])


def unstripe_sequence(x, ws: int):
    """Inverse of stripe_sequence (axis 0)."""
    seq = x.shape[0]
    if seq % ws:
        raise ValueError(f"sequence {seq} must divide by ws {ws}")
    blk = seq // ws
    return jnp.moveaxis(x.reshape(ws, blk, *x.shape[1:]), 0, 1) \
        .reshape(seq, *x.shape[1:])


def ring_attention(q, k, v, axis: str, *, causal: bool = False,
                   scale: Optional[float] = None,
                   use_pallas: Optional[bool] = None,
                   block_q: int = 256, block_k: Optional[int] = None,
                   layout: str = "contiguous"):
    """Sequence-parallel attention; call inside shard_map over ``axis``.

    q, k, v: this shard's (block_len, n_heads, head_dim) slice of the
    sequence; k/v may carry FEWER heads (block_len, n_kv_heads,
    head_dim) for grouped-query attention — query head h attends K/V
    head h // (n_heads/n_kv_heads). Only the COMPACT K/V rotates
    around the ring, so GQA's n_heads/n_kv_heads reduction in ICI
    bytes is realized per step (the fused path also streams compact
    K/V from HBM — the group dim folds into the kernel's Q axis, see
    pallas.flash.flash_block_update_hld). Returns the (block_len,
    n_heads, head_dim) attention output for the local Q block,
    numerically equal to full softmax attention over the whole
    sequence.

    ``layout`` declares how the sequence was sharded: 'contiguous'
    (shard r holds tokens [r*block, (r+1)*block)) or 'striped' (shard
    r holds tokens {r, r+ws, ...} — pre-permute the full sequence with
    stripe_sequence). Striping balances CAUSAL work across ring steps:
    contiguous causal sharding fully masks every step whose K/V block
    comes from a later shard, so up to half the schedule is wasted;
    striped blocks are ~half-unmasked everywhere. Only the position
    arrays differ — the masking is position-driven.

    ``use_pallas`` selects the fused flash kernel
    (rlo_tpu.pallas.flash) for the per-step online-softmax update: the
    (BQ, BK) score tile lives and dies in VMEM instead of the unfused
    einsum path materializing (H, Lq, Lk) scores in HBM between ops.
    Default: on TPU when ``can_flash`` accepts the shape — the block
    length must tile by block_q AND a VMEM-feasible K tile must exist
    (single-tile when it fits, block_k-wide otherwise; see
    pallas.flash._select_bk). Interpret mode exercises the same kernel
    in tests. The pallas path carries everything in the kernel's
    head-leading layout across the ring loop — one transpose in, one
    out.
    """
    ws = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    blk, h, d = q.shape
    hk = k.shape[1]
    if h % hk:
        raise ValueError(
            f"query heads {h} must be a multiple of K/V heads {hk}")
    g = h // hk
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if use_pallas is None:
        from rlo_tpu.pallas.flash import can_flash
        from rlo_tpu.pallas.reduce import kernel_gate
        use_pallas = kernel_gate(
            can_flash(blk, blk, d, block_q, block_k, groups=g),
            f"ring attention step (block={blk}, head_dim={d}, "
            f"groups={g})")
    # K/V travel rank -> rank+1, so the block held at step s originated
    # at shard (idx - s) mod ws — same schedule as the ring allreduce.
    perm = list(topology.ring_perm(ws))
    if layout == "contiguous":
        def positions(shard):
            return shard * blk + jnp.arange(blk)
    elif layout == "striped":
        def positions(shard):
            return shard + ws * jnp.arange(blk)
    else:
        raise ValueError(f"unknown layout {layout!r}")
    q_pos = positions(idx)

    if use_pallas:
        from rlo_tpu.pallas.flash import flash_block_update_hld
        # GQA fold applied ONCE outside the ring loop: q (H, Lq, D) ->
        # (Hkv, G*Lq, D) with group-tiled positions; the loop then
        # carries everything in the kernel's folded head-leading layout
        # and only the COMPACT (Hkv, Lq, D) K/V rotates over ICI
        q_hld = q.astype(jnp.float32).transpose(1, 0, 2) \
            .reshape(hk, g * blk, d)
        qp = jnp.tile(q_pos.astype(jnp.int32).reshape(1, blk), (1, g))

        def update(s, kc, vc, m, l, o):
            src = (idx - s) % ws
            kp = positions(src).astype(jnp.int32).reshape(1, blk)
            # pallas_fast backward: the l-normalization after the ring
            # loop makes the dropped max-routing term analytically
            # zero (see pallas.flash._pallas_bwd)
            return flash_block_update_hld(
                q_hld, kc, vc, m, l, o, qp, kp, causal=causal,
                scale=scale, block_q=block_q, block_k=block_k,
                bwd="pallas_fast")

        def step(s, carry):
            kc, vc, m, l, o = carry
            m, l, o = update(s, kc, vc, m, l, o)
            kc = lax.ppermute(kc, axis, perm)
            vc = lax.ppermute(vc, axis, perm)
            return kc, vc, m, l, o

        m0 = _vary_like(jnp.full((hk, 1, g * blk), _NEG, jnp.float32), q)
        l0 = _vary_like(jnp.zeros((hk, 1, g * blk), jnp.float32), q)
        o0 = _vary_like(jnp.zeros((hk, g * blk, d), jnp.float32), q)
        kc0 = k.transpose(1, 0, 2)                        # (Hkv, Lk, D)
        vc0 = v.transpose(1, 0, 2)
        kc, vc, m, l, o = lax.fori_loop(0, ws - 1, step,
                                        (kc0, vc0, m0, l0, o0))
        m, l, o = update(ws - 1, kc, vc, m, l, o)
        lt = l.transpose(0, 2, 1)                         # (Hkv, G*Lq, 1)
        denom = jnp.where(lt > 0, lt, 1.0)
        return (o / denom).reshape(h, blk, d) \
            .transpose(1, 0, 2).astype(q.dtype)

    q32 = q.astype(jnp.float32)

    def update(s, kc, vc, m, l, o):
        src = (idx - s) % ws
        k_pos = positions(src)
        # compact K/V rotated; the grouped expand happens locally, so
        # ICI still carries only Hkv heads per step
        ke = jnp.repeat(kc, g, axis=1) if g > 1 else kc
        ve = jnp.repeat(vc, g, axis=1) if g > 1 else vc
        return _block_update(q32, ke.astype(jnp.float32), ve, m, l, o,
                             q_pos, k_pos, causal, scale)

    def step(s, carry):
        kc, vc, m, l, o = carry
        m, l, o = update(s, kc, vc, m, l, o)
        kc = lax.ppermute(kc, axis, perm)
        vc = lax.ppermute(vc, axis, perm)
        return kc, vc, m, l, o

    m0 = _vary_like(jnp.full((h, blk), _NEG, jnp.float32), q)
    l0 = _vary_like(jnp.zeros((h, blk), jnp.float32), q)
    o0 = _vary_like(jnp.zeros((blk, h, d), jnp.float32), q)
    # ws-1 rotate-and-update steps, then the last arrived block outside
    # the loop — the final rotation would only be thrown away, and
    # collectives inside fori_loop are never dead-code-eliminated
    kc, vc, m, l, o = lax.fori_loop(0, ws - 1, step, (k, v, m0, l0, o0))
    m, l, o = update(ws - 1, kc, vc, m, l, o)

    # causal guarantees l > 0 (every q sees itself); for safety against
    # fully-masked rows divide-where
    denom = jnp.where(l.T[..., None] > 0, l.T[..., None], 1.0)
    return (o / denom).astype(q.dtype)


def full_attention(q, k, v, *, causal: bool = False,
                   scale: Optional[float] = None, q_until=None):
    """Unsharded reference implementation (the test oracle).
    ``q_until`` (Lq,): with ``causal``, the last key position each
    query attends, in place of its own (a block-causal mask)."""
    qn, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        kn = k.shape[0]
        until = jnp.arange(qn) if q_until is None else q_until
        mask = jnp.arange(kn)[None, :] <= until[:, None]
        s = jnp.where(mask[None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
