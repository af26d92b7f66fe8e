"""Ulysses-style sequence parallelism: all-to-all head-scatter attention.

The second classic long-context strategy next to ring attention (the
task's "ring attention or all-to-all sequence/context parallelism"; the
reference has neither — SURVEY.md §5). Where ring attention keeps heads
whole and streams K/V blocks around the ring (ws-1 ppermute steps,
overlappable with compute), Ulysses transposes the sharding instead:

  in:   every shard holds its SEQUENCE slice of all heads
        (blk, H, D), blk = seq / ws
  a2a:  one all_to_all per tensor re-shards to all SEQUENCE of a HEAD
        slice (seq, H/ws, D)
  attn: plain full softmax attention per local head — no communication
        in the quadratic part, any attention kernel drops in
  a2a:  one all_to_all on the output transposes back to (blk, H, D)

Four all_to_alls total (q, k, v in; o out) of the activation size,
versus ring's ws-1 K/V rotations — Ulysses wins when heads are
plentiful and the per-step ring latency dominates; ring wins when
H < ws or activations dwarf ICI bandwidth. Both live on the same
substrate (rlo_tpu.ops.tpu_collectives.all_to_all == the expert-dispatch
collective), so the choice is a one-line swap.

Requires n_heads % ws == 0; causal masking uses GLOBAL positions, which
stay consistent because each shard ends up with full sequences.
"""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp
from jax import lax

from rlo_tpu.ops import tpu_collectives as tc
from rlo_tpu.ops.ring_attention import full_attention


def _seq_to_heads(x, axis: str, ws: int, algorithm: str):
    """(blk, H, D) per shard -> (seq, H/ws, D): scatter heads, gather
    sequence."""
    blk, h, d = x.shape
    if h % ws:
        raise ValueError(
            f"ulysses needs the axis size ({ws}) to divide the head "
            f"count ({h}); use ring_attention for few-head configs")
    # (blk, H, D) -> (ws, blk, H/ws, D): chunk the head axis
    chunks = jnp.moveaxis(x.reshape(blk, ws, h // ws, d), 1, 0)
    out = tc.all_to_all(chunks, axis, algorithm=algorithm)
    # row s now holds shard s's sequence slice of MY heads
    return out.reshape(ws * blk, h // ws, d)


def _heads_to_seq(x, axis: str, ws: int, algorithm: str):
    """(seq, H/ws, D) -> (blk, H, D): the inverse transpose."""
    seq, hl, d = x.shape
    blk = seq // ws
    chunks = x.reshape(ws, blk, hl, d)
    out = tc.all_to_all(chunks, axis, algorithm=algorithm)
    # row g = my sequence slice of shard g's heads
    return jnp.moveaxis(out, 0, 1).reshape(blk, ws * hl, d)


def ulysses_attention(q, k, v, axis: str, *, causal: bool = False,
                      scale: Optional[float] = None,
                      algorithm: str = "xla",
                      use_pallas: Optional[bool] = None,
                      block_q: int = 256,
                      block_k: Optional[int] = None):
    """Sequence-parallel attention via head-scatter all_to_all; call
    inside shard_map over ``axis``.

    q, k, v: this shard's (block_len, n_heads, head_dim) sequence slice
    (shard r holds tokens [r*block, (r+1)*block) — the same contract as
    ring_attention, so the two are drop-in interchangeable). k/v may
    carry FEWER heads (block_len, n_kv_heads, head_dim) for
    grouped-query attention: when n_kv_heads divides the axis size,
    only the COMPACT K/V crosses the all_to_alls (shard s's query-head
    chunk lines up with its K/V-head chunk because h/ws = g * hkv/ws);
    otherwise K/V is repeated by the smallest factor restoring
    divisibility first. Returns the (block_len, n_heads, head_dim)
    output slice, numerically equal to full attention over the whole
    sequence.

    ``use_pallas`` runs the communication-free quadratic part as the
    fused flash kernel (pallas/flash.py, one whole-sequence block
    update; the K/V axis streams through VMEM in block_k tiles, so
    sequence length is not VMEM-bound). Default: on TPU when the full
    sequence tiles by both block sizes.
    """
    from rlo_tpu.pallas.reduce import kernel_gate

    ws = lax.axis_size(axis)
    hq, hk = q.shape[1], k.shape[1]
    if hq % hk:
        raise ValueError(
            f"query heads {hq} must be a multiple of K/V heads {hk}")
    g = hq // hk
    if hk % ws and hq % ws == 0:
        # the head-scatter needs ws | heads: repeat K/V by the SMALLEST
        # factor restoring divisibility (repeat composes exactly with
        # grouping — expanded head hq//g' copies original hq//g), so
        # e.g. hkv=2 on a 4-wide axis ships 4 heads, not n_heads.
        # r=g always qualifies (hk*g = hq, divisible by ws); when hq
        # itself does not divide, _seq_to_heads raises the clear error
        r = next(r for r in range(1, g + 1)
                 if g % r == 0 and (hk * r) % ws == 0)
        k = jnp.repeat(k, r, axis=1)
        v = jnp.repeat(v, r, axis=1)
        hk *= r
        g //= r
    qh = _seq_to_heads(q, axis, ws, algorithm)
    kh = _seq_to_heads(k, axis, ws, algorithm)
    vh = _seq_to_heads(v, axis, ws, algorithm)
    seq, _, d = qh.shape
    if use_pallas is None:
        from rlo_tpu.pallas.flash import can_flash
        use_pallas = kernel_gate(
            can_flash(seq, seq, d, block_q, block_k, groups=g),
            f"ulysses attention (seq={seq}, head_dim={d}, groups={g})")
    # full sequence, local heads: the quadratic part is communication-
    # free and positions are globally consistent (causal masks included)
    if use_pallas:
        # grouped K/V attends natively (the kernel folds the group dim
        # into its Q axis) — compact K/V streams from HBM too
        from rlo_tpu.pallas.flash import flash_attention
        oh = flash_attention(qh, kh, vh, causal=causal, scale=scale,
                             block_q=block_q, block_k=block_k)
    else:
        if g > 1:  # local expand AFTER the a2a: ICI carried compact K/V
            kh = jnp.repeat(kh, g, axis=1)
            vh = jnp.repeat(vh, g, axis=1)
        oh = full_attention(qh, kh, vh, causal=causal, scale=scale)
    return _heads_to_seq(oh, axis, ws, algorithm)
