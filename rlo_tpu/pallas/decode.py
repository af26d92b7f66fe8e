"""Pallas flash-decode kernel: one query against the KV cache.

Decode attention is the other half of the serving HBM story: each step
reads every row's live cache prefix (and, tile-rounded, no more: see
the work-list paragraph), and the XLA einsum path
(models.generate._attend_cache) was measured 2-4x off the
weight+cache streaming bound at batch 32 / plen 1024 on v5e — and,
worse, de-optimized the int8 cache (XLA materializes the dequantized
cache as an f32/bf16 scratch buffer at that shape, paying MORE HBM
traffic than it saves; benchmarks/decode_bench.py, BENCH_extra
`decode_longctx_*`). This kernel streams cache tiles through VMEM
with the online-softmax accumulator — the flash pattern of
rlo_tpu.pallas.flash specialized to a single query row — and
dequantizes int8 tiles in VMEM, so the cache's HBM traffic is its
stored bytes, exactly.

The work per position is tiny (a (r, d) x (d, BK) dot), so the grid
must be coarse or per-program launch overhead dominates — the first
cut ran one program per (batch, kv-head, tile) and measured 2x SLOWER
than the einsum at batch 32 (12k programs/step of ~100 ns of useful
bandwidth each). A grid step is one (row, cache-tile) with ALL kv
heads resident per program (a batched dot over the head axis), two
orders of magnitude fewer launches, each streaming kvh*BK*d cache
bytes.

The grid is a WORK LIST, as long as the round's contexts and no
longer. A row reads only the tiles its live context reaches — tile 0
up to the one holding position pos + T - 1 (_last_live_tile) — and
only those are grid steps: decode_work_list turns ``pos`` into the
live (row, tile) pairs, rows in order, a row's tiles ascending, and the
kernel runs a 1-D grid over them whose bound, n_work, is read on the
device. ``row_of`` and ``tile_of`` are scalar-prefetched (beside
``pos``); the int8 scales' and the selection's index maps are
(row_of[i], 0, 0, tile_of[i]), q's, the tail's and the output's
(row_of[i], 0, 0, 0). A wholly masked tile would contribute p = 0 and
corr = 1, so leaving it out changes no bit of the output. (The grid
used to span (batch, max_len / BK) with the dead steps skipped in the
body: an empty grid step cost 0.2-0.35 us, 58% of the steps of a
server's mix at BK 256; PERF.md, PR 30.)

Where a step streams longer than it computes (_ring_slots: one query
a head, a verify, a block of 4 over grouped heads) K and V are NOT
BlockSpec operands: they stay in HBM (pl.ANY) and the kernel FETCHES
ITS OWN TILES (_ring_fetch, PR 37). The BlockSpec pipeline
double-buffers, one copy a tensor in flight, so at every row end the
next tile's start-up stood in the stream, and a tile is the unit of
its fetch, so a row's last tile came whole (Mosaic here refuses
pl.Buffered(3)). The kernel holds a ring of _RING_SLOTS VMEM slots a
tensor with a DMA semaphore each; the work list is whole in SMEM, so
step i can name any later step's (row, tile): it starts the copy of
step i + _RING_SLOTS - 1 (step 0 primes the ring: _ring_span), then
waits for its own tile and computes from its slot. Copies run across
row ends, and the next row's q and tail rows still come a step ahead
on their BlockSpecs. And a copy is only AS LONG AS THE ROW IS LIVE:
the kernel knows pos, so a row's last tile is copied up to the next
granule past its last attended position (_copy_lanes; 128 lanes for
tiles up to 512, a quarter of a wider one: a DMA's shape is static, so
one copy a size under a switch of at most four arms, _copy_sizes) and
the rest of the slot keeps an older tile's lanes, which the masks
treat like any dead position (scores selected to _NEG, V zeroed,
never multiplied). The tile width BK is therefore the granularity of
the STEPS, the granule that of the bytes (_pick_bk;
decode_lanes_fetched is the rule for callers that count). A step
that COMPUTES longer than it streams — 128 heads on one latent stream:
~2.5 us of matmul and softmax on a tile of 1.4 us — hides its bytes
whoever fetches them and shows every scalar cycle of the fetch's loop
and switches, so it keeps K (and V) on BlockSpecs with the index map
(row_of[i], 0, 0, tile_of[i]): same body, same bits, whole tiles. The
list depends on ``pos``, T and the cache's
shape alone, so a step builds it once for all its layers
(models.kvcache.attend_work) and a lone call builds its own. The grid
is sequential ('arbitrary'): the accumulators cross its steps. A v5e
has one TensorCore, so the 'parallel' batch axis of the old grid
bought nothing there; a chip with two would want the list split in
two halves of equal work, one a core.

Shapes (GQA-grouped, head-leading, SEQ-MINOR — models.generate
stores the cache with max_len as the minor dim so HBM tiles stream at
full 128-lane width; head_dim=64-minor measured half the bandwidth,
benchmarks/attend_sweep.py). The kernel is T-query generalized (the
speculative-decoding verify shape, flash_block_decode): the query
axis carries T*r rows t-major — row t*r+rr is block token t, group
member rr, at sequence position pos0_b + t — and T=1 IS single-token
decode, so both paths share one kernel and its numerics:
  q        (b, kv_heads, T*r, head_dim)  r = n_heads / kv_heads
  k/v      (b, kv_heads, head_dim, max_len)  act dtype or int8
  ks/vs    (b, kv_heads, max_len) f32 scales (int8 caches only)
  row_of, tile_of  (b * n_k + 1,) int32, scalar-prefetched (SMEM):
           the work list, n_work live entries then a sentinel
  pos      (b,) int32, scalar-prefetched — query t of row b masks
           prefix [0, pos_b + t]
  out      (b, kv_heads, T*r, head_dim) f32
  scratch  m/l (kv_heads, T*r), o (kv_heads, T*r, head_dim) f32;
           the K and V rings (_RING_SLOTS, kv_heads, head_dim, BK) in
           the cache's dtype, DMA semaphores (tensors, _RING_SLOTS)

A LATENT cache (latent attention's absorbed decode) is the same kernel
with no V operand: every head attends ONE stream — k (b, 1, d, max_len),
a latent row [c_kv | rotated key dims] per position — and the values
are the stream's leading ``v_dim`` features, so a tile fetched once
serves scores and values, for all heads (r = n_heads query rows):
  q        (b, 1, T*n_heads, d)     d = kv_lora + rope dims (576)
  k        (b, 1, d, max_len)       v = k[:, :, :v_dim]  (512)
  out      (b, 1, T*n_heads, v_dim) f32
Tile picking and the work list are the same code (_pick_bk with its
own bytes-a-step constants, wider from a cache length on: _tile_rule;
decode_work_list).

A WRITE-BEHIND TAIL (T = 1; DecodeServer's round, docs/DESIGN.md §4):
the rows a round has produced so far wait token-major beside the cache
and reach it once a round (write_kv_tail), because one new column costs
a whole 128-lane block a row to store. The kernel takes them as
  tk/tv    (kk, b, kv_heads, head_dim)  cache dtype; no tv if latent
  newest   (1,) int32, prefetched after pos — rows 0..newest are live,
           row t is position pos_b + 1 + t
and folds them into the same softmax at a row's first grid step (its
tile 0, which every row has).

A SELECTION (T = 1; a token selector's choice, models.kvcache
.select_tokens) rides the same kernel as one more operand, (b, 1, 1,
max_len) f32 ones and zeros streamed tile by tile beside the cache and
ANDed into the causal mask: the masked form of a sparse attend, which
reads every live tile whole. The selector's own kernel, index_score
(at the end of this file), walks the same kind of work list at its own
tile width and writes one f32 score a cached token.

Dots run in bf16 with f32 accumulation (int8 -> bf16 is lossless;
f32 caches keep f32 dots — their tiles are smaller than VMEM allows
anyway). (m, l, o) accumulate in VMEM scratch — initialised at a
row's first grid step (tile_of[i] == 0) and flushed at its last
(row_of[i + 1] is another row, or the sentinel that ends the list);
what a slot holds past its copy — a short last tile's stale lanes, the
end of a cache BK does not divide — is masked (and V zeroed under the
mask, so garbage can never ride a 0*NaN into the accumulator).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rlo_tpu.pallas.reduce import out_struct

_NEG = -1e30

#: cache-axis tile width; ceil-divides max_len (padded tail is masked)
_BLOCK_K = 512

#: rows of one bf16 sublane tile: a latent cache's feature axis (576) is
#: the tile's sublane axis and must come in whole groups of these
_SUBLANES = 16


def _copy_granule(bk: int) -> int:
    """The lanes a K/V copy is a multiple of: a quarter of the tile,
    and at least one 128-lane block of the cache axis (so a copy takes
    one of at most four sizes: _copy_sizes) — or the whole tile where
    that is no multiple of 128 (interpret mode's small caches)."""
    return -(-bk // 512) * 128 if bk % 128 == 0 else bk


def _copy_lanes(end, tile, bk: int, max_len: int, xp=jnp):
    """How many lanes of cache tile ``tile`` a row copies whose queries
    attend positions < ``end``: the tile as far as it is live, rounded
    up to _copy_granule (never none: an empty row still owns tile 0),
    and no further than the tile or the cache goes. A row's earlier
    tiles come out whole; only its last is cut. The kernel's rule and,
    through decode_lanes_fetched, the host's counters'."""
    g = _copy_granule(bk)
    lanes = xp.maximum((end - tile * bk + g - 1) // g, 1) * g
    return xp.minimum(xp.minimum(lanes, bk), max_len - tile * bk)


def _copy_sizes(bk: int, max_len: int):
    """Every value _copy_lanes can take, ascending: a DMA's shape is
    static, so the kernel holds one copy a size and picks by the live
    context (a switch of at most four arms, and one more for the short
    last tile of a cache that bk does not divide)."""
    g = _copy_granule(bk)
    # lists and calls only: the prover evaluates this rule too (P3)
    return sorted(set(list(range(g, bk, g)) + [bk, max_len % bk or bk]))


def _ring_span(i, n_work, n_slots: int, xp=jnp):
    """The grid steps [lo, hi) whose copies step ``i`` starts, so that
    n_slots - 1 steps beyond the one being computed are under way: step
    0 primes the ring with steps 0 .. n_slots - 1, every later step
    starts step i + n_slots - 1 alone — into the slot step i - 1 has
    just been computed from — and no step past the list's end."""
    return (xp.where(i == 0, 0, i + (n_slots - 1)),
            xp.minimum(i + n_slots, n_work))


def _ring_fetch(i, row, tile, row_ref, tile_ref, pos_ref, streams, sem, *,
                bk: int, max_len: int, T: int, whole: bool = False):
    """The kernel's own K/V fetch: bring grid step i's tile (``tile`` of
    ``row``) into VMEM and return the ring slot that holds it.
    ``streams`` is one (cache in HBM, ring (n_slots, kv_heads, head_dim,
    bk) in VMEM) pair a tensor, ``sem`` a DMA semaphore (tensor, slot).
    The work list is whole in SMEM, so step i names step j's (row,
    tile) for any j: it starts the copies _ring_span gives it, then
    waits for its own. So n_slots - 1 copies a tensor are in flight
    while a step computes, where the BlockSpec pipeline holds one: a
    copy's start-up no longer stands between two tiles of the stream.
    A copy is _copy_lanes wide; the lanes of a slot past it hold an
    older tile's and are masked like any dead position. ``whole``: every
    copy is the whole tile (a sliding layer's ring, whose live keys need
    not end in a row's last step)."""
    n_slots = streams[0][1].shape[0]
    if whole:
        short, whole = [], bk
    else:
        *short, whole = _copy_sizes(bk, max_len)

    def copies(j, act, row, tile):
        """``act`` (start or wait) on the copies of step j, tile
        ``tile`` of row ``row``. The whole tile first: every tile of a
        row but its last takes that arm and no other compare."""
        lanes = _copy_lanes(jnp.minimum(pos_ref[row] + T, max_len), tile,
                            bk, max_len)
        base = tile * bk
        if bk % 128 == 0:
            base = pl.multiple_of(base, 128)
        slot = j % n_slots

        def arm(n):
            for s, (hbm, ring) in enumerate(streams):
                act(pltpu.make_async_copy(
                    hbm.at[row, :, :, pl.ds(base, n)],
                    ring.at[slot, :, :, pl.ds(0, n)], sem.at[s, slot]))

        if not short:
            return arm(whole)
        pl.when(lanes == whole)(functools.partial(arm, whole))

        @pl.when(lanes != whole)
        def _a_rows_last_tile():
            if len(short) == 1:
                return arm(short[0])
            for n in short:
                pl.when(lanes == n)(functools.partial(arm, n))

    def start(j, carry):
        copies(j, lambda cp: cp.start(), row_ref[j], tile_ref[j])
        return carry

    jax.lax.fori_loop(*_ring_span(i, pl.num_programs(0), n_slots), start, 0)
    copies(i, lambda cp: cp.wait(), row, tile)
    return i % n_slots


def _decode_kernel(row_ref, tile_ref, pos_ref, *refs, scale: float,
                   bk: int, max_len: int, quant: bool,
                   r: int, T: int, v_dim: int = 0, n_tail: int = 0,
                   selected: bool = False, block_len: int = 0,
                   n_slots: int = 0, window: int = 0, sinks: bool = False):
    if n_tail:          # a fourth prefetched scalar: the tail's newest row
        newest_ref, refs = refs[0], refs[1:]
    if window:          # and a ring's lap: the position its column 0 holds
        lap_ref, refs = refs[0], refs[1:]
    # K and V: the caches themselves, in HBM, where the kernel copies
    # its own tiles (n_slots: the ring's depth); else this step's tiles
    q_ref, k_ref, *rest = refs
    if not v_dim:       # a V operand; a latent cache has none
        v_ref, rest = rest[0], rest[1:]
    if n_tail:          # the round's own rows, after the cache operands
        tk_ref, rest = rest[0], rest[1:]
        if not v_dim:
            tv_ref, rest = rest[0], rest[1:]
    if selected:        # the positions this row may attend, 1.0 or 0.0
        sel_ref, rest = rest[0], rest[1:]
    if sinks:           # a logit a query row in its softmax's denominator
        sink_ref, rest = rest[0], rest[1:]
    if quant:
        ks_ref, vs_ref, rest = rest[0], rest[1], rest[2:]
    o_ref, m_s, l_s, o_s, *rings = rest
    # grid step i of the work list (decode_work_list): tile ik of row ib
    i = pl.program_id(0)
    ib = row_ref[i]
    ik = tile_ref[i]
    # dots in bf16 (f32 accumulate): int8 -> bf16 is lossless, bf16 is
    # the MXU-native width, and an f32 cast would materialize 4x the
    # tile bytes in VMEM. f32 caches keep f32 (exactness; their tiles
    # fit).
    dot_dt = jnp.float32 if k_ref.dtype == jnp.float32 else jnp.bfloat16
    if n_slots:
        *rings, sem = rings
        caches = (k_ref,) if v_dim else (k_ref, v_ref)
        slot = _ring_fetch(i, ib, ik, row_ref, tile_ref, pos_ref,
                           list(zip(caches, rings)), sem, bk=bk,
                           max_len=max_len, T=T, whole=bool(window))
        k_tile = rings[0].at[slot]
        v_tile = None if v_dim else rings[1].at[slot]
    else:
        k_tile, v_tile = k_ref.at[0], None if v_dim else v_ref.at[0]

    # a row's first grid step: its tile 0 — or, walking a ring from the
    # tile its window starts in, the step after another row's last
    first = ik == 0
    if window:
        first = (i == 0) | (row_ref[jnp.maximum(i - 1, 0)] != ib)

    @pl.when(first)
    def _init():
        if not n_tail:
            m_s[...] = jnp.full_like(m_s[...], _NEG)
            l_s[...] = jnp.zeros_like(l_s[...])
            o_s[...] = jnp.zeros_like(o_s[...])
            return
        # the write-behind tail (models.generate.decode_step): rows
        # 0..newest of this round, token-major, row t at position
        # pos + 1 + t. It is one more tile of the same online softmax,
        # with K arriving (kk, d) instead of (d, BK), and it goes
        # FIRST — it starts the accumulators where zeros would — at
        # the row's first grid step, its block fetched a step ahead
        # like any other (behind the previous row's last cache tile).
        # Rows past ``newest`` are the zeros the
        # tail was made of; a position at or past max_len is one the
        # per-step write would have dropped; a row with neither
        # leaves m = _NEG, l = 0, o = 0, as no tail does.
        q = q_ref[0].astype(dot_dt)                      # (g, r, d)
        if v_dim:
            kt = tk_ref[:, 0, 0, :][None].astype(dot_dt)  # (1, kk, d)
            vt = kt[:, :, :v_dim]
        else:                                 # (kk, g, d) -> (g, kk, d)
            kt = jnp.swapaxes(tk_ref[:, 0], 0, 1).astype(dot_dt)
            vt = jnp.swapaxes(tv_ref[:, 0], 0, 1).astype(dot_dt)
        t = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_tail), 2)
        if window:      # a ring drops nothing; the band counts back
            live = (t <= newest_ref[0]) & (t > newest_ref[0] - window)
        else:
            live = (t <= newest_ref[0]) & (pos_ref[ib] + 1 + t < max_len)
        s = jax.lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, _NEG)                     # (g, r, kk)
        m = s.max(axis=-1)
        p = jnp.where(live, jnp.exp(s - m[..., None]), 0.0)
        m_s[...] = m
        l_s[...] = p.sum(axis=-1)
        o_s[...] = jax.lax.dot_general(
            p.astype(dot_dt), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    pos = pos_ref[ib]

    # every step of the list is a live tile: one that starts at or
    # before the last position a query of this row attends
    # (pos + T - 1). The tiles past it would be masked to p = 0, m
    # unchanged, corr = 1 — leaving them out is bit-identical to
    # computing them — and they are not in the list.

    # g = kvh heads batched per program. The query axis holds
    # T*r rows, t-major: row t*r+rr is block token t, group-member rr,
    # at sequence position pos + t (T=1 recovers single-token decode).
    q = q_ref[0].astype(dot_dt)                      # (g, T*r, d)
    k = k_tile[...].astype(dot_dt)                   # (g, d, BK)
    # latent: the values are the key stream's leading features
    v = (k[:, :v_dim, :] if v_dim
         else v_tile[...].astype(dot_dt))            # (g, d, BK)
    # masks built >=2-D from iota: Mosaic cannot insert a minor dim on
    # sub-32-bit (bool) values, so never reshape a 1-D mask
    base = ik * bk
    row = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    # per-query causal position: query row t*r+rr masks at pos + t —
    # or, block-causal, at the last position of block token t's block
    qoff = jax.lax.broadcasted_iota(jnp.int32, (1, T * r, 1), 1) // r
    if block_len:
        qoff = qoff // block_len * block_len + (block_len - 1)
    if window:
        mask_row, mask_col = _window_masks(row, pos, qoff, lap_ref[ib],
                                           newest_ref[0] + 1 if n_tail
                                           else 0, T=T, window=window,
                                           ring=max_len)
    else:
        mask_row = (row <= pos + qoff) & (row < max_len)  # (1, T*r, BK)
    if selected:        # a token selector's choice, every head the same
        mask_row = mask_row & (sel_ref[0] > 0.0)     # (1, 1, BK)
    if not window:
        # V zeroing: any key a query of this block may attend
        # (<= pos+T-1) — seq-minor V masks over its LAST axis
        mask_col = (row <= pos + (T - 1)) & (row < max_len)  # (1, 1, BK)

    # batched over the head axis, contracting head_dim — the seq-minor
    # cache arrives as the MXU-native (d, BK) operand
    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * ks_ref[0]                            # (g, 1, BK)
    s = jnp.where(mask_row, s, _NEG)                 # (g, T*r, BK)
    # zero V under the mask: a padded tail tile may hold uninitialized
    # VMEM, and 0 * NaN would poison the accumulator
    v = jnp.where(mask_col, v, jnp.zeros((), dot_dt))

    m = m_s[...]                                     # (g, T*r)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.where(mask_row, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    m_s[...] = m_new
    l_s[...] = l_s[...] * corr + p.sum(axis=-1)
    # fold the v dequant into the probabilities (f32, no relayout of
    # v) — AFTER the l accumulation (the softmax denominator must sum
    # the unscaled probabilities) and re-masked: the padded tail's vs
    # tile is uninitialized VMEM and p's zeros would ride 0*NaN into
    # the accumulator, the same hazard v is zeroed for above
    pv = jnp.where(mask_row, p * vs_ref[0], 0.0) if quant else p
    # p (g, R, BK) x v (g, d, BK), contracting BK
    o_s[...] = o_s[...] * corr[..., None] + jax.lax.dot_general(
        pv.astype(dot_dt), v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    # a row's last step: the next entry is another row's, or the
    # sentinel that ends the list
    @pl.when(row_ref[i + 1] != ib)
    def _flush():
        if sinks:
            den = l_s[...] + jnp.exp(sink_ref[...] - m_s[...])
            o_ref[0] = o_s[...] / den[..., None]
            return
        o_ref[0] = o_s[...] / l_s[...][..., None]


def _window_masks(col, pos, qoff, lap, later: int, *, T: int, window: int,
                  ring: int):
    """A ring's masks at columns ``col`` (1, 1, BK): the position a
    column holds is ``lap`` + col, less the ring's length where the
    column lies past the newest position's (pos + T - 1) — the lap
    before; negative where the ring has not come round yet. Query row
    qoff attends its own position pos + ``later`` + qoff and the
    ``window`` - 1 before it (``later``: with a tail, the query comes
    newest + 1 after the cache's last position pos). Returns (mask_row
    (1, T*r, BK), mask_col (1, 1, BK)), _decode_kernel's two."""
    newest = pos + (T - 1)
    held = lap + col - jnp.where(col > newest - lap, ring, 0)
    at = pos + later + qoff                           # (1, T*r, 1)
    mask_row = (held >= 0) & (held <= at) & (held > at - window)
    mask_col = ((held >= 0) & (held <= newest)
                & (held > pos + later - window))
    return mask_row, mask_col


def _write_row_kernel(pos_ref, row_ref, cache_ref, out_ref, *,
                      n_blocks: int, per_row: bool):
    """Write one (nkv, hd) row into the lane at GLOBAL position
    ``pos`` of the cache block containing it (grid = batch; the block
    index_map selected column min(pos // 128, n_blocks-1)).
    Everything else copies through — out is input_output_aliased, so
    only THIS 128-lane block moves. The comparison is against the
    GLOBAL column: an out-of-range pos (serve advances retired slots
    past max_len) matches no column and the write is dropped, exactly
    like the XLA scatter this replaced (a local pos%128 match would
    silently alias into the clamped last block)."""
    ib = pl.program_id(0) if per_row else 0
    blk = jnp.minimum(pos_ref[ib] // 128, n_blocks - 1)
    col = blk * 128 + jax.lax.broadcasted_iota(jnp.int32,
                                               (1, 1, 1, 128), 3)
    # row arrives (1, nkv, d, 1): Mosaic cannot INSERT a minor dim
    # inside the kernel (tpu.reshape to ...x1 fails to lower), so the
    # caller pre-shapes it; the where broadcasts it over the lanes
    out_ref[...] = jnp.where(col == pos_ref[ib], row_ref[...],
                             cache_ref[...])


def can_write_row(max_len: int) -> bool:
    """The aliased row-write kernel addresses whole 128-lane blocks: a
    ragged tail past the last full block would be unreachable (the
    position clamps to block L//128 - 1 and the write is dropped)."""
    return max_len >= 128 and max_len % 128 == 0


def _wrap_block(blk, n_blocks: int):
    """A ring's 128-lane block index: past the last block, round to the
    first (the unrolled index is at most one lap on)."""
    return jnp.where(blk >= n_blocks, blk - n_blocks, blk)


def _write_block_kernel(pos_ref, rows_ref, cache_ref, out_ref, *,
                        T: int, n_blocks: int, ring: bool = False):
    """Write T consecutive columns starting at pos0 into the cache.
    Grid (b, 2): the T columns span at most two adjacent 128-lane
    blocks; program j covers block min(pos0//128 + j, n_blocks-1)
    (when both programs clamp to the same block they compute
    identical output — benign double write)."""
    ib = pl.program_id(0)
    j = pl.program_id(1)
    start = pos_ref[ib]
    if ring:    # columns counted on past the ring's end, one lap at most
        base = (start // 128 + j) * 128
    else:
        blk = jnp.minimum(start // 128 + j, n_blocks - 1)
        base = blk * 128
    col = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 128), 3)
    # single range-compare (start <= col < start + T) instead of a
    # T-deep masked-select chain (round-5 advisor finding #4): each
    # in-window lane picks its row through a (T, 128) one-hot
    # contraction (exact — exactly one nonzero term per lane), and ONE
    # select applies the window; out-of-window lanes copy the cache
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (T, 128), 0)
    c_iota = base + jax.lax.broadcasted_iota(jnp.int32, (T, 128), 1)
    onehot = (c_iota == start + t_iota).astype(jnp.float32)
    vals = jax.lax.dot_general(
        rows_ref[...].astype(jnp.float32), onehot,
        (((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(cache_ref.dtype)
    in_window = (col >= start) & (col < start + T)
    out_ref[...] = jnp.where(in_window, vals, cache_ref[...])


def can_write_block(max_len: int) -> bool:
    return max_len >= 256 and max_len % 128 == 0


def write_kv_block(cache, rows, pos0, *,
                   interpret: Optional[bool] = None,
                   name: str = "write_kv_block", ring: bool = False):
    """Aliased T-column cache write: ``cache`` (b, kvh, hd, L)
    seq-minor, ``rows`` (b, kvh, hd, T) — column t of row b lands at
    [b, :, :, pos0_b + t]. The block_decode analogue of write_kv_row:
    the XLA lane-index scatter it replaces lowers to a generic scatter
    that measured 1.2 ms PER VERIFY at batch 1 (block_decode 1.65 ms
    vs 0.46 ms for a decode step with the same weights) — the whole
    speculative-decoding margin. Requires L >= 256 (two slidable
    128-lane blocks). A column at or past L is DROPPED, as T
    successive write_kv_row calls would drop it: the kernel matches
    GLOBAL columns against blocks clamped into the cache, so a row
    with pos0 >= L (serve advances retired slots past max_len) writes
    nothing and one that crosses L writes the columns below it
    (tests/test_flash_decode.py pins both). ``ring``: the cache is a
    sliding layer's ring and ``pos0`` < L a column of it: columns past
    L wrap to its start (the second block is block 0), none is
    dropped."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, nkv, d, L = cache.shape
    T = rows.shape[3]
    if T > 128:
        # two slidable 128-lane blocks cover pos%128 + T <= 255 only
        raise ValueError(f"write_kv_block supports T <= 128, got {T}")
    n_blocks = L // 128
    pos0 = jnp.asarray(pos0, jnp.int32)
    pos0 = jnp.full((b,), pos0) if pos0.ndim == 0 else pos0.reshape(b)
    from rlo_tpu.parallel.mesh import vary_like
    pos0 = vary_like(pos0, cache)
    rows = vary_like(rows, cache)
    if ring:
        block_map = lambda ib, j, pos_ref: (  # noqa: E731
            ib, 0, 0, _wrap_block(pos_ref[ib] // 128 + j, n_blocks))
    else:
        block_map = lambda ib, j, pos_ref: (  # noqa: E731
            ib, 0, 0, jnp.minimum(pos_ref[ib] // 128 + j, n_blocks - 1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, 2),
        in_specs=[
            pl.BlockSpec((1, nkv, d, T),
                         lambda ib, j, pos_ref: (ib, 0, 0, 0)),
            pl.BlockSpec((1, nkv, d, 128), block_map),
        ],
        out_specs=pl.BlockSpec((1, nkv, d, 128), block_map),
    )
    return pl.pallas_call(
        functools.partial(_write_block_kernel, T=T,
                          n_blocks=n_blocks, ring=ring),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        name=name,
    )(pos0, rows.astype(cache.dtype), cache)


def write_kv_tail(cache, tail, pos0, *,
                  interpret: Optional[bool] = None, ring: bool = False):
    """Fold a round's write-behind tail into the cache: ``tail``
    (kk, b, kvh, hd) token-major (models.generate.init_kv_tail), row t
    of batch row b lands at [b, :, :, pos0_b + t]; columns at or past L
    are dropped (write_kv_block's rule). This IS the row write of a
    decode round, kk rows at a time — a 128-lane block costs the same
    to rewrite for kk new columns as for one — so it runs
    write_kv_block's body under write_kv_row's name (one body, two
    names, like flash_decode / flash_block_decode). ``ring``: a sliding
    layer's ring (write_kv_block)."""
    return write_kv_block(cache, tail.transpose(1, 2, 3, 0), pos0,
                          interpret=interpret, name="write_kv_row",
                          ring=ring)


def write_kv_row(cache, row, pos, *, interpret: Optional[bool] = None):
    """Aliased single-position cache write: ``cache`` (b, kvh, hd, L)
    seq-minor, ``row`` (b, kvh, hd), ``pos`` (b,) int32 — returns the
    cache with row b written at [b, :, :, pos_b]; a pos at or past L
    matches no column and the write is dropped.

    Exists because the XLA dynamic-update-slice at a LANE offset
    fights the flash kernel over layout: layout assignment prefers a
    transposed layout for the lane-granular DUS and then inserts a
    full-cache copy per layer per step to feed the pallas custom call.
    As a pallas kernel with input_output_aliasing there is no
    XLA-level DUS: every cache consumer is a custom call wanting the
    default layout, and only the one 128-lane block containing pos is
    read + written.

    That block is still 128 columns moved to store one: at 96 rows x
    16 heads x 64 bf16 a call moves 50 MB (and the (…, 1) row it is
    fed pads to 128 lanes, 25 MB more) for 196 KB of new values —
    0.093 ms a call, 4.5 ms of a 15.6 ms gpt2-medium decode step over
    48 calls, with 2.7 ms of row copies beside it (PERF_LEDGER, PR 27).
    A loop that owns its steps should not pay that per step:
    DecodeServer's round keeps the new rows in a token-major tail and
    folds them in once a round (write_kv_tail; docs/DESIGN.md §4)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, nkv, d, L = cache.shape
    pos = jnp.asarray(pos, jnp.int32)
    # SCALAR pos (plain generate's scan: every row at the same
    # position): batch-chunked blocks instead of the (b,) grid, so a
    # call is a few launches and not b of them. The bytes are the
    # same: one 128-lane block a row.
    per_row = pos.ndim != 0
    pos = jnp.full((b,), pos) if pos.ndim == 0 else pos.reshape(b)
    # shard_map vma alignment: a replicated pos/row must carry the
    # same varying-axes set as the tp-sharded cache (same cast
    # flash_block_decode does)
    from rlo_tpu.parallel.mesh import vary_like
    pos = vary_like(pos, cache)
    row = vary_like(row, cache)
    if per_row:
        in_specs = [
            pl.BlockSpec((1, nkv, d, 1),
                         lambda ib, pos_ref: (ib, 0, 0, 0)),
            # clamp: an out-of-range pos (serve advances retired
            # slots past max_len) must select a legal block — the
            # in-kernel GLOBAL col == pos match then fails, so the
            # write is dropped exactly like the scatter it replaced
            pl.BlockSpec((1, nkv, d, 128),
                         lambda ib, pos_ref, _n=L // 128: (
                             ib, 0, 0,
                             jnp.minimum(pos_ref[ib] // 128,
                                         _n - 1))),
        ]
        out_specs = pl.BlockSpec(
            (1, nkv, d, 128),
            lambda ib, pos_ref, _n=L // 128: (
                ib, 0, 0,
                jnp.minimum(pos_ref[ib] // 128, _n - 1)))
        grid = (b,)
    else:
        # batch-chunked: the largest row-chunk whose cache block fits
        # ~8 MB of VMEM (in + aliased out), so a 32-row write is 2
        # launches instead of 32
        itemsize = cache.dtype.itemsize
        # Mosaic double-buffers every block across grid steps: the
        # scoped-VMEM cost is ~2x(cache-in + aliased-out) = 4x the
        # block bytes (a 2x budget OOM'd at 24 MB on the 16 MB limit)
        bb = b
        while bb > 1 and (4 * bb * nkv * d * 128 * itemsize
                          > (12 << 20) or b % bb):
            bb -= 1
        in_specs = [
            pl.BlockSpec((bb, nkv, d, 1),
                         lambda i, pos_ref: (i, 0, 0, 0)),
            pl.BlockSpec((bb, nkv, d, 128),
                         lambda i, pos_ref, _n=L // 128: (
                             i, 0, 0,
                             jnp.minimum(pos_ref[0] // 128,
                                         _n - 1))),
        ]
        out_specs = pl.BlockSpec(
            (bb, nkv, d, 128),
            lambda i, pos_ref, _n=L // 128: (
                i, 0, 0,
                jnp.minimum(pos_ref[0] // 128, _n - 1)))
        grid = (b // bb,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
    )
    return pl.pallas_call(
        functools.partial(_write_row_kernel, n_blocks=L // 128,
                          per_row=per_row),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        input_output_aliases={2: 0},  # cache (after pos, row) -> out
        interpret=interpret,
        name="write_kv_row",
    )(pos, row.astype(cache.dtype)[..., None], cache)


def _write_page_row_kernel(page_ref, off_ref, row_ref, pool_ref,
                           out_ref, *, ps: int):
    """Write one (nkv, hd) row into lane ``off`` of pool page
    ``page`` (grid = batch; the block index_map selected the page).
    An off of ps (the DROP sentinel — inactive slots) matches no lane
    and the write copies through, exactly like an out-of-range XLA
    scatter index. Distinct active rows never share a page (the COW
    invariant), so revisiting a block only happens for dropped writes
    — identical output, benign."""
    ib = pl.program_id(0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, ps), 3)
    out_ref[...] = jnp.where(col == off_ref[ib], row_ref[...],
                             pool_ref[...])


def write_kv_page_row(pool, row, page, off, *,
                      interpret: Optional[bool] = None):
    """Aliased paged row write: ``pool`` (P, kvh, hd, ps) — the paged
    twin of write_kv_row — ``row`` (b, kvh, hd), ``page``/``off``
    (b,) int32; row b lands at [page_b, :, :, off_b], off == ps drops
    the write. Only the b touched pages move (~page bytes per slot
    instead of the dense layout's whole 128-lane column across the
    slot pool)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    P, nkv, d, ps = pool.shape
    b = row.shape[0]
    page = jnp.minimum(jnp.asarray(page, jnp.int32).reshape(b), P - 1)
    off = jnp.asarray(off, jnp.int32).reshape(b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, nkv, d, 1),
                         lambda ib, page_ref, off_ref: (ib, 0, 0, 0)),
            pl.BlockSpec((1, nkv, d, ps),
                         lambda ib, page_ref, off_ref: (
                             page_ref[ib], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, nkv, d, ps),
            lambda ib, page_ref, off_ref: (page_ref[ib], 0, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_write_page_row_kernel, ps=ps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},  # pool (after page, off, row)
        interpret=interpret,
        name="write_kv_page_row",
    )(page, off, row.astype(pool.dtype)[..., None], pool)


def _write_page_block_kernel(page_ref, off_ref, nv_ref, rows_ref,
                             pool_ref, out_ref, *, T: int, ps: int):
    """Write ``nv`` consecutive lanes starting at ``off0`` of ONE pool
    page from a (nkv, hd, T) chunk — the chunked-prefill write. A
    chunk never crosses a page boundary (off0 + nv <= ps, scheduled by
    the server), so a single program owns every written lane: in-window
    lanes pick their row through a (T, ps) one-hot contraction (the
    _write_block_kernel technique), everything else copies through."""
    off0 = off_ref[0]
    nv = nv_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, ps), 3)
    t_iota = jax.lax.broadcasted_iota(jnp.int32, (T, ps), 0)
    c_iota = jax.lax.broadcasted_iota(jnp.int32, (T, ps), 1)
    onehot = ((c_iota == off0 + t_iota) &
              (t_iota < nv)).astype(jnp.float32)
    vals = jax.lax.dot_general(
        rows_ref[...].astype(jnp.float32), onehot,
        (((3,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(pool_ref.dtype)
    in_window = (col >= off0) & (col < off0 + nv)
    out_ref[...] = jnp.where(in_window, vals, pool_ref[...])


def write_kv_page_block(pool, rows, page, off0, n_valid, *,
                        interpret: Optional[bool] = None):
    """Aliased paged chunk write: ``pool`` (P, kvh, hd, ps), ``rows``
    (kvh, hd, T) seq-minor, scalars ``page``/``off0``/``n_valid`` —
    token t < n_valid lands at [page, :, :, off0 + t]; pads beyond
    n_valid never touch the pool. Requires off0 + n_valid <= ps (the
    page-aligned chunk schedule guarantees it)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    P, nkv, d, ps = pool.shape
    T = rows.shape[2]
    if T > ps:
        raise ValueError(f"chunk T={T} exceeds page size {ps}")
    page = jnp.minimum(jnp.asarray(page, jnp.int32).reshape(1), P - 1)
    off0 = jnp.asarray(off0, jnp.int32).reshape(1)
    nv = jnp.asarray(n_valid, jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((1, nkv, d, T),
                         lambda i, page_ref, off_ref, nv_ref: (
                             0, 0, 0, 0)),
            pl.BlockSpec((1, nkv, d, ps),
                         lambda i, page_ref, off_ref, nv_ref: (
                             page_ref[0], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, nkv, d, ps),
            lambda i, page_ref, off_ref, nv_ref: (
                page_ref[0], 0, 0, 0)),
    )
    return pl.pallas_call(
        functools.partial(_write_page_block_kernel, T=T, ps=ps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={4: 0},
        interpret=interpret,
        name="write_kv_page_block",
    )(page, off0, nv, rows.astype(pool.dtype)[None], pool)


def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, n_k: int, ps: int,
                         quant: bool, r: int, T: int):
    """The paged twin of _decode_kernel: the grid's cache axis walks
    the slot's LOGICAL pages (tile ik = positions [ik*ps, (ik+1)*ps))
    while the BlockSpec index_map resolved the PHYSICAL page through
    the prefetched table — masking and online-softmax accumulation are
    position-identical to the dense kernel at bk = ps, so the page
    indirection is invisible to the math. Unmapped tiles resolve to
    the null page (zeros) and mask out entirely."""
    if quant:
        ks_ref, vs_ref, o_ref, m_s, l_s, o_s = rest
    else:
        o_ref, m_s, l_s, o_s = rest
    ib = pl.program_id(0)
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s[...], _NEG)
        l_s[...] = jnp.zeros_like(l_s[...])
        o_s[...] = jnp.zeros_like(o_s[...])

    dot_dt = jnp.float32 if k_ref.dtype == jnp.float32 else jnp.bfloat16
    q = q_ref[0].astype(dot_dt)                      # (g, T*r, d)
    k = k_ref[0].astype(dot_dt)                      # (g, d, ps)
    v = v_ref[0].astype(dot_dt)                      # (g, d, ps)
    pos = pos_ref[ib]
    base = ik * ps
    row = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    qoff = jax.lax.broadcasted_iota(jnp.int32, (1, T * r, 1), 1) // r
    mask_row = row <= pos + qoff                     # (1, T*r, ps)
    mask_col = row <= pos + (T - 1)                  # (1, 1, ps)

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * ks_ref[0]                            # (g, 1, ps)
    s = jnp.where(mask_row, s, _NEG)
    v = jnp.where(mask_col, v, jnp.zeros((), dot_dt))

    m = m_s[...]
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.where(mask_row, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    m_s[...] = m_new
    l_s[...] = l_s[...] * corr + p.sum(axis=-1)
    pv = jnp.where(mask_row, p * vs_ref[0], 0.0) if quant else p
    o_s[...] = o_s[...] * corr[..., None] + jax.lax.dot_general(
        pv.astype(dot_dt), v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        o_ref[0] = o_s[...] / l_s[...][..., None]


def can_paged_flash(page_size: int, head_dim: int) -> bool:
    """Shape gate for the paged decode kernel: a page must be a legal
    128-lane cache block and head_dim lane-friendly (the
    can_flash_decode rule at bk = page_size)."""
    return page_size % 128 == 0 and (head_dim % 128 == 0
                                     or head_dim == 64)


def paged_flash_decode(q, k_pool, v_pool, table, pos0, scale,
                       ks_pool=None, vs_pool=None, *,
                       interpret: Optional[bool] = None):
    """Fused paged decode attention: ``q`` (b, T, n_heads, head_dim)
    with row b's query t at position pos0_b + t (T=1 is single-token
    decode), pools (P, kv_heads, head_dim, ps) seq-minor pages,
    ``table`` (b, mp) int32 mapping slot b's logical page i to its
    physical page. The cache-axis grid walks logical pages and the
    kernel streams the PHYSICAL page through VMEM via scalar-prefetch
    indirection — HBM cache traffic is exactly the live pages' stored
    bytes, shared prefix pages included. int8 pools pass
    (P, kv_heads, ps) f32 scale sidecar pools. Returns
    (b, T, n_heads, head_dim) f32."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, T, nh, d = q.shape
    P, nkv, _, ps = k_pool.shape
    mp = table.shape[1]
    r = nh // nkv
    R = T * r
    quant = ks_pool is not None

    qg = (q.reshape(b, T, nkv, r, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, nkv, R, d))
    posv = jnp.asarray(pos0, jnp.int32)
    posv = jnp.full((b,), posv) if posv.ndim == 0 else posv.reshape(b)
    tablev = jnp.minimum(jnp.asarray(table, jnp.int32), P - 1)

    q_spec = pl.BlockSpec((1, nkv, R, d),
                          lambda ib, ik, pt, ps_: (ib, 0, 0, 0))
    kv_spec = pl.BlockSpec((1, nkv, d, ps),
                           lambda ib, ik, pt, ps_: (
                               pt[ib, ik], 0, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    args = [qg, k_pool, v_pool]
    if quant:
        s_spec = pl.BlockSpec((1, nkv, 1, ps),
                              lambda ib, ik, pt, ps_: (
                                  pt[ib, ik], 0, 0, 0))
        in_specs += [s_spec, s_spec]
        args += [ks_pool[:, :, None, :], vs_pool[:, :, None, :]]

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"))
    scratch = [pltpu.VMEM((nkv, R), jnp.float32),
               pltpu.VMEM((nkv, R), jnp.float32),
               pltpu.VMEM((nkv, R, d), jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, mp),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, R, d),
                               lambda ib, ik, pt, ps_: (ib, 0, 0, 0)),
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=float(scale),
                          n_k=mp, ps=ps, quant=quant, r=r, T=T),
        grid_spec=grid_spec,
        out_shape=out_struct((b, nkv, R, d), jnp.float32, q, k_pool),
        interpret=interpret,
        name="paged_flash_decode",
        **kwargs,
    )(tablev, posv, *args)
    return (out.reshape(b, nkv, T, r, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, T, nh, d))


def can_flash_decode(max_len: int, head_dim: int,
                     block_k: int = _BLOCK_K, v_dim: int = 0) -> bool:
    """Shape gate: a lane-friendly head_dim, and a cache axis of whole
    128-lane blocks, tiled in multiples of 128: the kernel's own K/V
    copies slice that axis, and Mosaic slices it in 128s only. A
    max_len that is no multiple of 128 (a ragged last tile, or a cache
    shorter than a block) is refused here, by its shape, and the
    einsum takes it. A latent
    cache (``v_dim`` > 0: values are the leading v_dim features of the
    key stream) has head_dim as the tile's sublane axis only: whole
    bf16 sublane groups, and a lane-friendly v_dim for the output."""
    if not _head_dims_ok(head_dim, v_dim) or max_len < 1:
        return False
    bk = min(block_k, max_len)
    return bk % 128 == 0 and max_len % 128 == 0


def _head_dims_ok(head_dim: int, v_dim: int) -> bool:
    """can_flash_decode's rule for the feature axes alone."""
    if v_dim:
        return not (head_dim % _SUBLANES or v_dim % 128
                    or v_dim > head_dim)
    return head_dim % 128 == 0 or head_dim == 64


#: K bytes (kv_heads x head_dim x BK x itemsize) one grid step should
#: stream. The tile is the granularity of the STEPS; since the kernel
#: copies a row's last tile only as far as it is live (_copy_lanes) it
#: is no longer that of the bytes, so a wide tile costs no over-read.
#: Measured at 96 rows x 16 heads x 64 x 1024 bf16 on a v5e
#: (benchmarks/attend_fetch_bench.py; PERF.md §6, PR 37), one call with
#: a 32-row tail on a server's mix of contexts (mean 265) / with every
#: row at max_len, ring of 3: BK 128 (256 KiB) 0.250 / 0.649 ms; 256
#: (512 KiB) 0.233 / 0.635; 512 (1 MiB) 0.234 / 0.592. (The BlockSpec
#: pipeline before it, whole tiles, one copy in flight: 0.304 / 0.773,
#: 0.295 / 0.651, 0.348 / 0.719.) With the compute stubbed out the
#: same copies take the same time: the step IS its bytes, at the
#: 620-680 GB/s these copies stream at (512-byte runs of a seq-minor
#: tile at BK 256; 819 is the chip's figure), plus ~0.05 us. What PR 30
#: read as "a grid step's fixed cost, ~0.25 us on top of the tile's
#: bytes" was that rate and a copy's start-up at every row end, which
#: the ring now hides. 256 and 512 read alike on the mix; 256 keeps the
#: ring at 3 MiB and every caller's accumulation order.
_TILE_BYTES = 512 << 10

#: the same for a latent cache (one stream of d = 576 rows a tile, 128
#: query rows), and the widest tile it may take. A latent step
#: COMPUTES: at 128 rows x 576 x 4096 bf16 with a 32-row tail a call
#: takes 0.644 ms on a reasoning server's contexts (mean 940) and
#: 1.495 with every row at max_len where the same copies with the
#: compute stubbed out take 0.234 and 0.837 (PERF.md §6, PR 37): ~2.5
#: us of matmul and softmax on a 1024-lane tile against 1.4 us of
#: bytes. So the tile trades the compute on a row's dead lanes (a
#: shorter copy does not shorten it) against the steps' own work, not
#: bytes against steps. Ring of 3, mix / every row at 300 / at
#: max_len: BK 256 0.834 / 0.488 / 2.715 ms; 512 0.647 / 0.374 /
#: 1.818; 1024 (1152 KiB) 0.644 / 0.498 / 1.495; 2048 0.783 / 0.742 /
#: 1.321. (The BlockSpec pipeline: 512 0.624 / 0.361 / 1.723; 1024
#: 0.614 / 0.482 / 1.422. What PR 30 called "~0.9 us a step before its
#: bytes" was this compute.) At 32 rows x 576 x 24576 under a
#: selection (contexts 8k-20k / at max_len): 512 1.525 / 2.465; 1024
#: 1.276 / 2.023; 2048 1.179 / 1.792 — long contexts take 2048, a
#: reasoning server's do not: the rule goes by the cache's length
#: (_tile_rule, below).
_LATENT_TILE_BYTES = 1152 << 10
_LATENT_BLOCK_K = 1024

#: from this cache length on a latent cache takes tiles of up to
#: _LATENT_LONG_BLOCK_K lanes (one step's K: 2048 x 576 x 2 B). A
#: deployment that holds 16k positions a row serves contexts of
#: several thousand, where a step's fixed work (the pipeline's step, q,
#: the rescale of the (heads x v_dim) accumulator) outweighs the compute
#: on a row's dead lanes. The length, not the contexts: every block size
#: must tile the cache alike (_pick_bk). The BlockSpec pipeline the
#: rule keeps at 128 and 64 heads, ms a call, the cell's contexts /
#: every row at max_len (PERF.md §6): 32 rows x 128 heads x 576 x
#: 24576 under a selection (mean context 14.8k), BK 1024 1.206 /
#: 1.900; 1536 1.132 / 1.754; 2048 1.111 / 1.694. 16 rows x 64 heads x
#: 576 x 49152, a selection and a sink a head (mean 31.3k): 1024
#: 0.962 / 1.459; 1536 0.868 / 1.294; 2048 0.849 / 1.262 (the same
#: copies with the compute stubbed out 0.852 / 1.255: at 64 heads a
#: step of 2048 is its bytes). 128 rows x 4096 with a 32-row tail
#: (mean 940): 1024 0.618 / 1.421; 2048 0.749 / 1.253 — on short
#: contexts the dead lanes cost more than the steps save.
_LATENT_LONG_LEN = 16384
_LATENT_LONG_TILE_BYTES = 2304 << 10
_LATENT_LONG_BLOCK_K = 2048


#: slots of the kernel's K/V ring (_ring_fetch): one being computed
#: from and _RING_SLOTS - 1 copies in flight behind it. 3 and 4 read
#: alike at every shape and tile (PERF.md §6, PR 37)
_RING_SLOTS = 3

#: matmul flops a byte of K/V from which a grid step computes longer
#: than it streams: half of a v5e's ridge (197 TFLOP/s over 819 GB/s =
#: 240). Such a step hides its bytes whoever fetches them and shows
#: every scalar cycle, so it keeps the BlockSpec pipeline's fetch: the
#: kernel's own costs it ~0.1 us a step (128 heads on one latent stream
#: read 241 and ran 3-5% slower with the ring; one query a head reads
#: 1, a block of 4 over 8 heads a kv head 32: PERF.md §6, PR 37)
_COMPUTE_BOUND = 120


def _ring_slots(R: int, d: int, dv: int, streams: int,
                itemsize: int) -> int:
    """Depth of the ring a call fetches its K/V through, by its shape:
    _RING_SLOTS where a step streams longer than it computes — R query
    rows a kv head against ``streams`` tensors of d features and
    ``itemsize`` bytes, dv of them values — and 0, the pipeline's
    whole-tile fetch, where it is _COMPUTE_BOUND."""
    flops_a_byte = 2 * R * (d + dv) / (streams * d * itemsize)
    return 0 if flops_a_byte >= _COMPUTE_BOUND else _RING_SLOTS


def _tile_rule(latent: bool, L: int):
    """(widest tile, K bytes a grid step) of _pick_bk for a per-head
    K/V cache or a latent one of ``L`` positions a row."""
    if not latent:
        return _BLOCK_K, _TILE_BYTES
    if L >= _LATENT_LONG_LEN:
        return _LATENT_LONG_BLOCK_K, _LATENT_LONG_TILE_BYTES
    return _LATENT_BLOCK_K, _LATENT_TILE_BYTES


def _ring_bytes(nkv: int, d: int, bk: int, itemsize: int,
                streams: int) -> int:
    """VMEM the kernel's K/V ring holds: _RING_SLOTS tiles a tensor
    (``streams``: K and V, or a latent cache's one)."""
    return _RING_SLOTS * streams * nkv * bk * d * itemsize


def _pick_bk(L: int, d: int, nkv: int, r: int, itemsize: int,
             block_k: int, tile_bytes: int = _TILE_BYTES,
             streams: int = 2) -> int:
    """Cache-tile width: at most ``block_k``, no wider than streams
    _TILE_BYTES of K a grid step (finer tiles skip more of a short
    row's dead context), and within the T=1 VMEM budget (the ring's
    _RING_SLOTS (kvh, bk, d) tiles a tensor in the dot dtype + the f32
    score/probability tensors within ~10 MB). A rule of the shape
    alone, deliberately independent of T: every block size must tile
    the cache identically or verify/decode numerics diverge."""
    bk = min(block_k, max(L, 1))
    if bk > 128:
        bk = min(bk, max(128, tile_bytes // (nkv * d * itemsize)
                         // 128 * 128))
    if bk < L and L % 128 == 0:
        # prefer a DIVISOR of L: a non-dividing bk makes Mosaic pad
        # the whole cache operand (materialized XLA pads per step)
        while bk > 128 and L % bk:
            bk -= 128
    while bk > 128 and (_ring_bytes(nkv, d, bk, itemsize, streams)
                        + 2 * nkv * r * bk * 4) > (10 << 20):
        # halve, but stay on the multiple-of-128 grid can_flash_decode
        # gated on (e.g. 384 -> 192 would fail Mosaic tiling; use 128)
        bk = max(128, (bk // 2) // 128 * 128)
        while bk > 128 and L % 128 == 0 and L % bk:
            bk -= 128
    return bk


def flash_decode_tile(k_cache, n_heads: int, latent: bool = False) -> int:
    """The cache-tile width flash_decode / flash_block_decode run a
    (b, kv_heads, head_dim, max_len) cache at (anything with its
    ``shape`` and ``dtype``): the granularity at which a row's live
    context is rounded up. For callers that count tiles (DecodeServer's
    ``serve.attend_tiles``, kvcache.attend_work); the rule lives
    here."""
    _, nkv, d, L = k_cache.shape
    itemsize = 4 if k_cache.dtype == jnp.float32 else 2
    return _pick_bk(L, d, nkv, n_heads // nkv, itemsize,
                    *_tile_rule(latent, L), streams=1 if latent else 2)


def flash_decode_slots(k_cache, n_heads: int, v_dim: int = 0,
                       T: int = 1) -> int:
    """Slots of the K/V ring flash_decode / flash_block_decode fetch a
    (b, kv_heads, head_dim, max_len) cache through for T queries a row
    (_ring_slots; one less is the copies a tensor in flight; ``v_dim``
    as the calls take it: a latent cache's value width), 0 where the
    pipeline fetches whole tiles. For a server's
    ``serve.attend_fetch_depth``, and decode_lanes_fetched."""
    _, nkv, d, _ = k_cache.shape
    return _ring_slots(T * n_heads // nkv, d, v_dim or d,
                       1 if v_dim else 2, k_cache.dtype.itemsize)


def decode_lanes_fetched(pos, T: int, bk: int, max_len: int,
                         slots: int = _RING_SLOTS):
    """The cache positions ONE call's K/V fetch moves for each row of
    ``pos`` (rows,), on the host: the row's tiles before its last
    whole, the last by _copy_lanes where the kernel copies for itself
    (``slots``: flash_decode_slots) — the kernel's own rule, exported
    as flash_decode_tile is, for callers that count (DecodeServer's
    ``serve.attend_lanes_fetched``) — and whole too where it does
    not."""
    pos = np.asarray(pos, np.int64)
    last = np.clip((pos + (T - 1)) // bk, 0, -(-max_len // bk) - 1)
    if not slots:
        return np.minimum((last + 1) * bk, max_len)
    return last * bk + _copy_lanes(np.minimum(pos + T, max_len), last,
                                   bk, max_len, xp=np)


def _last_live_tile(pos, T: int, bk: int, n_k: int):
    """The last cache tile a row attends into: the one holding
    position pos + T - 1, its T-th query's own. Clipped into
    [0, n_k): serve advances retired slots past max_len."""
    return jnp.clip((pos + (T - 1)) // bk, 0, n_k - 1)


def decode_work_list(pos, T: int, bk: int, n_k: int):
    """The grid of one flash_decode / flash_block_decode call: its live
    (row, tile) pairs, in the order the kernel walks them. From ``pos``
    (b,) and _last_live_tile's rule, row r owns ``last_r + 1`` steps —
    never 0: tile 0 is always a row's, which gives the tail its step
    and every output block its write — rows in order, a row's tiles
    ascending. Returns ``(row_of, tile_of, n_work)``: two int32 arrays
    of the static length b * n_k + 1 and the number of pairs
    (b <= n_work <= b * n_k), the grid's dynamic bound. The entries
    from n_work on are a sentinel, ``row_of = b`` and ``tile_of = 0``:
    the kernel reads ``row_of[i + 1]`` to see a row end, and the index
    maps clamp the row when the pipeline looks one step ahead.

    Compares and sums over (b * n_k + 1, b), no sort and no loop. All
    layers of a step share ``pos`` and the cache's shape, so a caller
    with many layers builds the list once (kvcache.attend_work) and
    hands it to each call (``work=``)."""
    b = pos.shape[0]
    count = _last_live_tile(pos, T, bk, n_k) + 1            # (b,)
    ends = jnp.cumsum(count)
    step = jnp.arange(b * n_k + 1, dtype=jnp.int32)
    # done[i, r]: row r's steps all come before step i
    done = step[:, None] >= ends[None, :]
    row_of = done.sum(axis=1, dtype=jnp.int32)
    first = jnp.where(done, count[None, :], 0).sum(axis=1,
                                                    dtype=jnp.int32)
    tile_of = jnp.where(row_of < b, step - first, 0)
    return row_of, tile_of, ends[b - 1]


def window_tiles(lo, hi, bk: int, n_k: int, xp=jnp):
    """The tiles of a ring of n_k tiles of ``bk`` that one row's window
    attend walks, as (count, first tile): the ring columns of positions
    ``lo`` .. ``hi`` (position p in column p mod n_k * bk), from the
    tile of ``lo``'s column on and round past the ring's end, every
    tile once at most; one step (the tile of ``hi``'s column, or 0)
    where the span is empty — a row always has its step. The kernel's
    rule (window_work_list) and, on the host, its counters'."""
    R = n_k * bk
    n = hi - lo + 1
    start = xp.where(n > 0, lo, xp.maximum(hi, 0)) % R
    count = xp.where(n > 0, (start % bk + n - 1) // bk + 1, 1)
    return xp.minimum(count, n_k), start // bk


def window_work_list(lo, hi, bk: int, n_k: int):
    """decode_work_list for a sliding layer's ring: row r's steps are
    window_tiles' (count, first tile) — the tiles that hold its live
    positions ``lo`` .. ``hi`` (b,) and no other — rows in order, a
    row's tiles from its window's first round the ring. Same
    ``(row_of, tile_of, n_work)`` form, sentinel and static length;
    the kernel's window variant starts a row's accumulators at its
    first step, whatever its tile."""
    b = lo.shape[0]
    count, first_tile = window_tiles(lo, hi, bk, n_k)
    ends = jnp.cumsum(count)
    step = jnp.arange(b * n_k + 1, dtype=jnp.int32)
    done = step[:, None] >= ends[None, :]
    row_of = done.sum(axis=1, dtype=jnp.int32)
    before = jnp.where(done, count[None, :], 0).sum(axis=1,
                                                     dtype=jnp.int32)
    tile_of = jnp.where(
        row_of < b, (first_tile[jnp.minimum(row_of, b - 1)]
                     + step - before) % n_k, 0)
    return row_of, tile_of.astype(jnp.int32), ends[b - 1]


def _block_fits_vmem(L: int, d: int, nkv: int, r: int, T: int,
                     itemsize: int, block_k: int = _BLOCK_K,
                     tile_bytes: int = _TILE_BYTES,
                     streams: int = 2) -> bool:
    """Whether a T-query block fits VMEM at the T=1 tile size (the
    only tile size that preserves shared numerics with plain decode):
    the K/V ring, the f32 scores and probabilities, the accumulator."""
    bk = _pick_bk(L, d, nkv, r, itemsize, block_k, tile_bytes, streams)
    return (_ring_bytes(nkv, d, bk, itemsize, streams)
            + 2 * nkv * T * r * bk * 4
            + nkv * T * r * d * 4) <= (14 << 20)


def flash_decode(q, k_cache, v_cache, pos, scale, k_scale=None,
                 v_scale=None, *, block_k: Optional[int] = None,
                 interpret: Optional[bool] = None, v_dim: int = 0,
                 tail=None, work=None, select=None, window: int = 0,
                 sinks=None):
    """Fused decode attention. ``q`` is (b, 1, n_heads, head_dim) (the
    _attend_cache caller layout); caches head-leading as in
    models.generate. ``pos`` scalar or (b,). Returns
    (b, 1, n_heads, head_dim) f32. A latent cache passes ``v_cache``
    None and ``v_dim``; ``tail`` is a round's write-behind rows;
    ``work`` a prebuilt work list; ``select`` a token selector's choice;
    ``window`` a sliding layer's ring; ``sinks`` a logit a head in the
    softmax's denominator (all six: see flash_block_decode)."""
    assert q.shape[1] == 1, q.shape  # single query; flash_block_decode for T>1
    return flash_block_decode(q, k_cache, v_cache, pos, scale,
                              k_scale=k_scale, v_scale=v_scale,
                              block_k=block_k, interpret=interpret,
                              v_dim=v_dim, tail=tail, work=work,
                              select=select, window=window, sinks=sinks)


def flash_block_decode(q, k_cache, v_cache, pos0, scale, k_scale=None,
                       v_scale=None, *, block_k: Optional[int] = None,
                       interpret: Optional[bool] = None, v_dim: int = 0,
                       tail=None, work=None, select=None,
                       block_len: int = 0, window: int = 0, sinks=None):
    """Fused T-query block decode attention (the speculative-decoding
    verify shape): ``q`` is (b, T, n_heads, head_dim) where row b's
    query t sits at sequence position ``pos0[b] + t`` and attends
    cache positions <= it (write-then-attend covers in-block
    causality, as in models.generate.block_decode). ``pos0`` scalar or
    (b,). T=1 IS single-token flash decode — one kernel, so the
    speculative verify and the plain decode step share numerics (the
    losslessness of greedy speculative decoding rides on their
    argmaxes agreeing; tests/test_speculative.py pins parity).
    Returns (b, T, n_heads, head_dim) f32.

    A LATENT cache (``v_cache`` None, ``v_dim`` > 0): ``k_cache`` is
    (b, 1, d, max_len), every head attends it, and the values are its
    leading ``v_dim`` features; returns (b, T, n_heads, v_dim).

    A write-behind ``tail`` (T = 1, no int8): ``(tk, tv, newest)`` with
    tk/tv (kk, b, kv_heads, head_dim) token-major in the cache's dtype
    (tv None for a latent cache) and ``newest`` an int32 scalar. Row
    b's tail row t is the key/value of position pos0_b + 1 + t, which
    the cache does not hold yet; the query attends cache positions
    <= pos0_b AND tail rows 0..newest, one softmax over both (the tail
    is folded in at a row's first grid step, ahead of the cache tiles).
    Tail positions at or past max_len are left out, as the per-step
    write drops them. Without a tail the kernel is, to the bit, the
    one it was.

    The grid is the call's work list (decode_work_list): one step a
    live (row, tile) pair, ``n_work`` steps in all, a bound read on
    the device. ``work`` is that list built by the caller for this
    ``pos0``, T and cache shape (every layer of a step shares them);
    with none the call builds its own.

    ``select`` (T = 1, no int8): (b, max_len) bool, the cache positions
    a row's query may attend beside the causal mask — a token
    selector's choice (models.kvcache.select_tokens), the same for
    every head. It streams with the cache as one f32 lane a position;
    the tiles are read whole, selected or not: the MASKED form of a
    sparse attend. With every live position selected the result is,
    to the bit, the call's without it.

    ``block_len`` > 0 (a divisor of T; ``pos0`` a multiple of it): the
    BLOCK-causal mask of generation by diffusion over blocks — query t
    attends every position of its own block too, positions <=
    pos0 + t // block_len * block_len + block_len - 1, all of which
    write-then-attend has put in the cache. With T = block_len every
    query of the call attends positions < pos0 + T. The tiles and the
    work list are the causal call's: the last position any query
    attends is pos0 + T - 1 either way.

    ``window`` > 0: ``k_cache`` / ``v_cache`` are a sliding layer's ring
    (models.kvcache): position p in column p mod max_len, the newest the
    ring holds pos0 + T - 1. Query t attends the positions
    pos0 + t - window < p <= pos0 + t (with a tail, T = 1: the query is
    at pos0 + newest + 1 and the band counts back over the tail rows
    too), each column masked by the position it holds
    (_window_masks). The grid walks only the ring tiles that hold a
    row's window (window_work_list, ``work``), whole tiles, and the
    kernel is ``flash_decode_window`` in the trace. Per-head K/V without
    scales, selection or block mask.

    ``sinks`` (n_heads,): a logit a head that joins its softmax's
    denominator beside the keys (attention sinks): the output is
    sum_j exp(s_j - m) v_j / (sum_j exp(s_j - m) + exp(sink - m)),
    applied when a row's last tile is flushed. Without it the kernel is,
    to the bit, the one it was."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, T, nh, d = q.shape
    if window and (k_scale is not None or v_cache is None
                   or select is not None or block_len):
        raise ValueError(
            "flash_block_decode: a ring takes per-head K/V without int8 "
            "scales, selection or a block-causal mask")
    if block_len and T % block_len:
        raise ValueError(
            f"flash_block_decode: a block-causal call takes whole "
            f"blocks, got T={T} at block_len={block_len}")
    nkv, L = k_cache.shape[1], k_cache.shape[3]
    r = nh // nkv
    R = T * r
    quant = k_scale is not None
    latent = v_cache is None
    if select is not None and (T != 1 or quant or tail is not None
                               or select.shape != (b, L)):
        raise ValueError(
            f"flash_block_decode: a selection goes with one query a "
            f"row, an unquantized cache and no tail, as (b, max_len); "
            f"got T={T}, int8={quant}, tail {tail is not None}, "
            f"select {select.shape}")
    widest, tile_bytes = _tile_rule(latent, L)
    if block_k is None:
        block_k = widest
    if latent != bool(v_dim) or (latent and (
            quant or not _head_dims_ok(d, v_dim))):
        raise ValueError(
            f"flash_block_decode: a latent cache comes without v_cache "
            f"and scales and with v_dim (a 128-multiple <= head_dim "
            f"{d}, head_dim a 16-multiple); got v_dim={v_dim}, "
            f"v_cache {'absent' if latent else 'given'}")
    dv = v_dim or d
    n_tail = 0
    if tail is not None:
        tk, tv, newest = tail
        n_tail = tk.shape[0]
        if (T != 1 or quant or (tv is None) != latent
                or tk.shape != (n_tail, b, nkv, d)):
            raise ValueError(
                f"flash_block_decode: a tail goes with one query a row "
                f"and an unquantized cache, as (kk, b, kv_heads, "
                f"head_dim) rows (and no V rows for a latent cache); "
                f"got T={T}, int8={quant}, tail {tk.shape}")
    # bk comes from the T=1 budget — identical for every T, or the
    # verify kernel's tile partition (and so its accumulation order)
    # would differ from plain decode's, breaking the shared-numerics
    # guarantee speculative losslessness rests on.
    itemsize = 4 if k_cache.dtype == jnp.float32 else 2
    streams = 1 if latent else 2
    bk = _pick_bk(L, d, nkv, r, itemsize, block_k, tile_bytes, streams)
    # the T-scaled tensors at that same bk must still fit VMEM; a
    # block too big to share the T=1 tiling cannot share numerics, so
    # refuse rather than silently retile (caller falls back to einsum)
    if not _block_fits_vmem(L, d, nkv, r, T, itemsize, block_k,
                            tile_bytes, streams):
        raise ValueError(
            f"flash_block_decode: T={T} block exceeds the VMEM budget "
            f"at the T=1 tile size bk={bk} (nkv={nkv}, r={r}, d={d}) "
            f"— use the einsum block attend for this shape")
    n_k = -(-L // bk)
    posv = jnp.asarray(pos0, jnp.int32)
    posv = jnp.full((b,), posv) if posv.ndim == 0 else posv.reshape(b)
    if window and L % bk:
        raise ValueError(f"flash_block_decode: a ring of {L} positions "
                         f"in tiles of {bk}")
    if work is None and window:
        first = posv if tail is None else posv + 1 + jnp.asarray(
            tail[2], jnp.int32).reshape(())
        work = window_work_list(jnp.maximum(first - window + 1, 0),
                                posv + (T - 1), bk, n_k)
    if work is None:
        work = decode_work_list(posv, T, bk, n_k)
    if work[0].shape != (b * n_k + 1,) or work[1].shape != work[0].shape:
        raise ValueError(
            f"flash_block_decode: a work list for {b} rows of {n_k} "
            f"tiles has {b * n_k + 1} entries, got {work[0].shape} and "
            f"{work[1].shape}")
    if tail is not None:
        tail = (tk, tv, jnp.asarray(newest, jnp.int32).reshape(1))
    if sinks is not None:
        if sinks.shape != (nh,):
            raise ValueError(f"flash_block_decode: a sink a head, "
                             f"({nh},); got {sinks.shape}")
        # the kernel's query rows, head g * r + rr at row t * r + rr
        sinks = jnp.tile(sinks.astype(jnp.float32).reshape(nkv, 1, r),
                         (1, T, 1)).reshape(nkv, R)
    return _flash_call(q, k_cache, v_cache, posv, k_scale, v_scale, tail,
                       tuple(work), select, sinks, scale=float(scale),
                       bk=bk,
                       n_slots=_ring_slots(R, d, dv, streams,
                                           k_cache.dtype.itemsize),
                       interpret=bool(interpret), v_dim=v_dim,
                       block_len=block_len, window=window)


@functools.partial(jax.jit, static_argnames=(
    "scale", "bk", "n_slots", "interpret", "v_dim", "block_len", "window"))
def _flash_call(q, k_cache, v_cache, posv, k_scale, v_scale, tail, work,
                select, sinks=None, *, scale: float, bk: int, n_slots: int,
                interpret: bool, v_dim: int, block_len: int,
                window: int):
    """flash_block_decode's kernel call, its shapes and tiling settled.
    A jitted function of its own so that a program traces and lowers
    the kernel body ONCE for all the layers that call it alike, and a
    process once for all its programs, instead of once a call: the
    body holds the fetch's loop and switches beside the attend, and
    every round, admission and check program calls it in every layer
    (PERF.md §6, PR 36 and 37: set-up is an end-to-end metric)."""
    b, T, nh, d = q.shape
    nkv, L = k_cache.shape[1], k_cache.shape[3]
    r = nh // nkv
    R = T * r
    quant = k_scale is not None
    latent = v_cache is None
    streams = 1 if latent else 2
    dv = v_dim or d
    n_tail = 0 if tail is None else tail[0].shape[0]
    # t-major query rows: row t*r + rr = block token t, group member rr
    qg = (q.reshape(b, T, nkv, r, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, nkv, R, d))
    row_of, tile_of, n_work = work
    # the list, then pos, are scalar-prefetched: the kernel's own K/V
    # copies read steps i .. i + n_slots - 1's (row, tile) from it
    # (and pos, for a row's last copy), the index maps of what stays
    # on BlockSpecs step i's, so the pipeline's look-ahead has the
    # next row's q and tail rows under way behind step i. Past the
    # last pair an index map reads the sentinel, clamped to a block
    # that exists. (A tail brings one more prefetched scalar,
    # ``newest``; no index map reads it or pos.)
    row_map = lambda i, row_ref, tile_ref, pos_ref, _newest=None, _lap=None: (  # noqa: E731
        jnp.minimum(row_ref[i], b - 1), 0, 0, 0)
    cache_map = lambda i, row_ref, tile_ref, pos_ref, _newest=None, _lap=None: (  # noqa: E731
        jnp.minimum(row_ref[i], b - 1), 0, 0, tile_ref[i])
    # K and V stay where they are and the kernel copies its own tiles
    # (_ring_fetch) — or, for a step that computes longer than it
    # streams (n_slots 0: _ring_slots), come tile by tile on the list
    kv_spec = (pl.BlockSpec(memory_space=pl.ANY) if n_slots
               else pl.BlockSpec((1, nkv, d, bk), cache_map))
    in_specs = [pl.BlockSpec((1, nkv, R, d), row_map), kv_spec]
    args = [qg, k_cache]
    if not latent:
        in_specs += [kv_spec]
        args += [v_cache]
    # inside shard_map (vma typing) every kernel operand must carry
    # the same varying-axes set: the replicated scalars (the list,
    # pos, the grid's bound) ride along with the tp-sharded q/cache
    from rlo_tpu.parallel.mesh import vary_like
    scalars = [row_of, tile_of, posv]
    if n_tail:
        tk, tv, newest = tail
        t_spec = pl.BlockSpec(
            (n_tail, 1, nkv, d),
            lambda i, row_ref, tile_ref, pos_ref, _newest, _lap=None: (
                0, jnp.minimum(row_ref[i], b - 1), 0, 0))
        for rows in (tk,) if latent else (tk, tv):
            in_specs += [t_spec]
            args += [vary_like(rows.astype(k_cache.dtype), k_cache)]
        scalars += [newest]
    if window:
        # the position column 0 holds in the lap of the ring's newest
        newest_pos = posv + (T - 1)
        scalars += [newest_pos - jnp.mod(newest_pos, L)]
    if select is not None:
        in_specs += [pl.BlockSpec((1, 1, 1, bk), cache_map)]
        args += [vary_like(select.astype(jnp.float32)[:, None, None, :],
                           k_cache)]
    if sinks is not None:
        in_specs += [pl.BlockSpec(
            (nkv, R), lambda i, row_ref, tile_ref, pos_ref, _newest=None,
            _lap=None: (0, 0))]
        args += [vary_like(sinks, k_cache)]
    if quant:
        # scales reshaped (b, kvh, 1, L): the (1, bk) trailing block
        # dims satisfy Mosaic's tiling rule for any bk multiple of 128
        s_spec = pl.BlockSpec((1, nkv, 1, bk), cache_map)
        in_specs += [s_spec, s_spec]
        args += [k_scale[:, :, None, :], v_scale[:, :, None, :]]
    scalars = [vary_like(vary_like(x, q), k_cache) for x in scalars]
    n_work = vary_like(vary_like(n_work, q), k_cache)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(n_work,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, nkv, R, dv), row_map),
        scratch_shapes=[pltpu.VMEM((nkv, R), jnp.float32),
                        pltpu.VMEM((nkv, R), jnp.float32),
                        pltpu.VMEM((nkv, R, dv), jnp.float32)]
        + ([pltpu.VMEM((n_slots, nkv, d, bk), k_cache.dtype)] * streams
           + [pltpu.SemaphoreType.DMA((streams, n_slots))]
           if n_slots else []),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale,
                          bk=bk, max_len=L, quant=quant, r=r, T=T,
                          v_dim=v_dim, n_tail=n_tail,
                          selected=select is not None,
                          block_len=block_len, n_slots=n_slots,
                          window=window,
                          **({"sinks": True} if sinks is not None else {})),
        grid_spec=grid_spec,
        out_shape=out_struct((b, nkv, R, dv), jnp.float32, q, k_cache),
        interpret=interpret,
        # one kernel body, four names: the T=1 decode step and the
        # T>1 extend/verify block, each of a full layer or of a sliding
        # layer's ring, are told apart in program text
        name=(("flash_decode_window" if T == 1
               else "flash_block_decode_window") if window
              else "flash_decode" if T == 1 else "flash_block_decode"),
        **kwargs,
    )(*scalars, *args)
    return (out.reshape(b, nkv, T, r, dv).transpose(0, 2, 1, 3, 4)
            .reshape(b, T, nh, dv))


# ---- the token selector's score ----------------------------------------

#: cache-axis tile of index_score: (index dim 128) x 2048 bf16 is 512 KiB
#: of keys a grid step, against a step's fixed cost of ~0.3 us
_INDEX_BLOCK_K = 2048


def _index_score_kernel(row_ref, tile_ref, q_ref, w_ref, k_ref, o_ref):
    # grid step i of the work list: tile tile_ref[i] of row row_ref[i];
    # the index maps did the addressing
    q = q_ref[0]                                     # (heads, d)
    k = k_ref[0, 0]                                  # (d, BK)
    s = jax.lax.dot_general(q, k.astype(q.dtype), (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.maximum(s, 0.0) * w_ref[0]               # (heads, BK) x (heads, 1)
    o_ref[0] = s.sum(axis=0, keepdims=True)          # (1, BK)


def index_score_tile(max_len: int, block_k: int = _INDEX_BLOCK_K) -> int:
    """index_score's tile of the cache axis: at most ``block_k``, a
    128-multiple that divides max_len (or the whole axis)."""
    bk = min(block_k, max_len)
    while bk > 128 and max_len % bk:
        bk -= 128
    return bk


def can_index_score(max_len: int, head_dim: int) -> bool:
    """Shape gate: whole 128-lane blocks of the cache axis, whole bf16
    sublane groups of the index key."""
    return (max_len >= 128 and max_len % 128 == 0
            and head_dim % _SUBLANES == 0)


def index_score(q, w, k_cache, pos, *, work=None,
                block_k: Optional[int] = None,
                interpret: Optional[bool] = None):
    """A token selector's scores of a row's cached index keys, for one
    query a row: ``q`` (b, heads, d), ``w`` (b, heads) f32, ``k_cache``
    (b, 1, d, max_len) seq-minor (one key a token, shared by the
    heads), ``pos`` (b,). Returns (b, max_len) f32,
    ``sum_j w_j relu(q_j . k_s)`` at positions s <= pos_b and -inf past
    them. A row's keys stream ONCE, in tiles, over the live (row, tile)
    pairs of decode_work_list (``work``: the list for T = 1 at
    index_score_tile's width; with none the call builds its own); per
    tile (heads, d) x (d, BK) on the MXU in the cache's dtype with f32
    accumulation, ReLU, the heads' weighted sum. XLA's form of it
    writes and re-reads a (b, heads, max_len) f32 tensor, twice the
    keys' own bytes. Tiles past a row's context are not visited: the
    mask that follows covers what they hold."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, nh, d = q.shape
    L = k_cache.shape[3]
    if block_k is None:
        block_k = _INDEX_BLOCK_K
    bk = index_score_tile(L, block_k)
    n_k = -(-L // bk)
    posv = jnp.asarray(pos, jnp.int32)
    posv = jnp.full((b,), posv) if posv.ndim == 0 else posv.reshape(b)
    if work is None:
        work = decode_work_list(posv, 1, bk, n_k)
    row_of, tile_of, n_work = work
    if row_of.shape != (b * n_k + 1,):
        raise ValueError(
            f"index_score: a work list for {b} rows of {n_k} tiles has "
            f"{b * n_k + 1} entries, got {row_of.shape}")
    row_map = lambda i, row_ref, tile_ref: (  # noqa: E731
        jnp.minimum(row_ref[i], b - 1), 0, 0)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_work,),
        in_specs=[
            pl.BlockSpec((1, nh, d), row_map),
            pl.BlockSpec((1, nh, 1), row_map),
            pl.BlockSpec((1, 1, d, bk),
                         lambda i, row_ref, tile_ref: (
                             jnp.minimum(row_ref[i], b - 1), 0, 0,
                             tile_ref[i])),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, bk), lambda i, row_ref, tile_ref: (
                jnp.minimum(row_ref[i], b - 1), 0, tile_ref[i])),
    )
    out = pl.pallas_call(
        _index_score_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, L), jnp.float32),
        interpret=interpret,
        name="index_score",
        **kwargs,
    )(row_of, tile_of, q.astype(k_cache.dtype),
      w.astype(jnp.float32)[..., None], k_cache)
    live = jnp.arange(L)[None, :] <= posv[:, None]
    return jnp.where(live, out[:, 0], -jnp.inf)


# ---- the token selector's exact top-k ----------------------------------

#: rows of scores one grid step of topk_select holds: a row a sublane of
#: two f32 vregs. Alone at 32 x 24576 on a v5e 8 rows took 0.087 ms, 16
#: rows 0.074; at 256 x 24576 0.428 and 0.314; at 256 x 49152 0.733 and
#: 0.563 (PERF.md §6)
_TOPK_ROWS = 16
#: lanes of one step of a count's walk over the block: (16, 1024) is 16
#: vregs a compare and 16 accumulators (512 lanes measured slower)
_TOPK_LANES = 1024
#: VMEM a step may take: the scores' block and the mask's, each as many
#: times as it is buffered, and the keys' scratch (5 x 16 x n x 4 B,
#: 7.5 MiB, at n = 24576)
_TOPK_VMEM_BYTES = 12 << 20

_SIGN = np.int32(-2 ** 31)


def _topk_kernel(s_ref, o_ref, key_ref, cut_ref, *, k: int, lanes: int):
    # one block of rows, whole, in VMEM: the keys are written once, then
    # every count is a walk over them (compare, accumulate lane-wise,
    # one cross-lane sum a row at its end)
    rows, n = s_ref.shape
    steps = n // lanes
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, lanes), 1)

    def at(j):
        return pl.ds(pl.multiple_of(j * lanes, lanes), lanes)

    def image(j, c):
        # the floats' order as signed int32: negatives' magnitude bits
        # flipped (-0.0 -> -1 < +0.0 -> 0, -inf the least finite key)
        raw = jax.lax.bitcast_convert_type(s_ref[:, at(j)], jnp.int32)
        key_ref[:, at(j)] = raw ^ ((raw >> 31) & 0x7FFFFFFF)
        return c

    jax.lax.fori_loop(0, steps, image, 0)

    def count(*preds):
        # per row, how many keys each predicate of (keys, step) holds
        # for: (rows, 1)s
        def walk(j, accs):
            key = key_ref[:, at(j)]
            return tuple(a + jnp.where(p(key, j), 1, 0)
                         for a, p in zip(accs, preds))
        zero = jnp.zeros((rows, lanes), jnp.int32)
        accs = jax.lax.fori_loop(0, steps, walk, (zero,) * len(preds))
        return [jnp.sum(a, axis=1, keepdims=True) for a in accs]

    def wide(x):
        return jnp.broadcast_to(x, (rows, lanes))

    def bit(i, t):
        # t: the k-th largest key's bits above bit 31 - i, in the
        # unsigned order; keep the next one where k keys reach it
        cand = t | jnp.left_shift(jnp.int32(1), 31 - i)
        low = wide(cand ^ _SIGN)
        n_ge, = count(lambda key, j: key >= low)
        return jnp.where(n_ge >= k, cand, t)

    kth = jax.lax.fori_loop(0, 32, bit,
                            jnp.zeros((rows, 1), jnp.int32)) ^ _SIGN
    kth_w = wide(kth)
    above, ties = count(lambda key, j: key > kth_w,
                        lambda key, j: key == kth_w)
    room = k - above            # ties at the k-th value still taken
    cut_ref[...] = jnp.full((rows, 1), np.iinfo(np.int32).max, jnp.int32)

    @pl.when(jnp.max(jnp.where(ties > room, 1, 0)) > 0)
    def _cut():
        # a row whose ties overflow its room takes the lowest positions:
        # the largest P with count(tie at a position <= P) <= room
        bits = max(1, (n - 1).bit_length())

        def pbit(i, p):
            cand = p | jnp.left_shift(jnp.int32(1), bits - 1 - i)
            cand_w = wide(cand)
            n_le, = count(lambda key, j: (key == kth_w)
                          & (j * lanes + col <= cand_w))
            return jnp.where(n_le <= room, cand, p)

        cut_ref[...] = jax.lax.fori_loop(0, bits, pbit,
                                         jnp.zeros((rows, 1), jnp.int32))

    cut_w = wide(cut_ref[...])

    def write(j, c):
        key = key_ref[:, at(j)]
        o_ref[:, at(j)] = jnp.where(
            (key > kth_w) | ((key == kth_w) & (j * lanes + col <= cut_w)),
            1, 0)
        return c

    jax.lax.fori_loop(0, steps, write, 0)


def _topk_vmem(buffers: int, n: int) -> int:
    """VMEM of a topk_select step over rows of ``n`` scores: the scores'
    block and the mask's, ``buffers`` deep, and the keys' scratch."""
    return (2 * buffers + 1) * _TOPK_ROWS * n * 4


def _topk_buffers(n: int) -> int:
    """How deep topk_select buffers its score and mask blocks at rows of
    ``n``: two where VMEM holds them (rows up to 39 296), else one. The
    kernel walks a block 34 times or more for each copy of it: on a v5e
    both forms took the same time at rows of 49152 (PERF.md §6)."""
    return 2 if _topk_vmem(2, n) <= _TOPK_VMEM_BYTES else 1


def can_topk_select(n: int, k: int) -> bool:
    """Shape gate of topk_select: whole 128-lane blocks of the row, more
    positions than are kept, and a block of rows whole in VMEM, single-
    buffered (rows up to 65 536)."""
    return (n % 128 == 0 and 0 < k < n
            and _topk_vmem(1, n) <= _TOPK_VMEM_BYTES)


def topk_select(scores, k: int, *, interpret: Optional[bool] = None):
    """models.kvcache.topk_mask as one kernel: the EXACT top-``k`` of
    ``scores`` (..., n) f32 as a bool mask, to the bit — the k largest,
    ties at the k-th value to the lowest positions, the floats ordered
    by their integer image (-0.0 below +0.0; -inf last, taken only where
    fewer than k others exist).

    A grid step holds _TOPK_ROWS rows whole in VMEM, read from HBM once
    (_topk_buffers deep):
    their keys (the signed image ``raw ^ ((raw >> 31) & 0x7fffffff)``,
    the order of topk_mask's unsigned one) go to a scratch, the k-th
    largest key is settled bit by bit in 32 counts over it, then one
    count of the keys above it and of the ties. Where a row's ties fit
    its room (k less those above) the mask is key >= k-th; only in a
    block where some row's do not, the position cut — the largest P
    with count(tie at a position <= P) <= room, at most
    ceil(log2 n) more counts — runs, under a pl.when. The mask is
    written once. XLA's form (topk_mask) reads the whole score array
    from HBM in each of its 32 counts and cuts the ties with a running
    sum (PERF.md §6 has both timed on a v5e)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = scores.shape
    n = shape[-1]
    if not can_topk_select(n, k):
        raise ValueError(f"topk_select: no kernel for rows of {n} "
                         f"keeping {k} (can_topk_select)")
    x = scores.astype(jnp.float32).reshape(-1, n)
    rows = x.shape[0]
    pad = -rows % _TOPK_ROWS
    if pad:
        # distinct filler: no ties, so no position cut for its block
        x = jnp.concatenate(
            [x, jnp.broadcast_to(jnp.arange(n, dtype=jnp.float32),
                                 (pad, n))])
    lanes = _TOPK_LANES
    while n % lanes:
        lanes //= 2
    out = _topk_call(x, k=k, block=_TOPK_ROWS, buffers=_topk_buffers(n),
                     lanes=lanes, interpret=bool(interpret))
    return (out[:rows] != 0).reshape(shape)


@functools.partial(jax.jit, static_argnames=("k", "block", "buffers",
                                             "lanes", "interpret"))
def _topk_call(x, *, k: int, block: int, buffers: int, lanes: int,
               interpret: bool):
    """topk_select's kernel call over (rows, n) scores, rows a multiple
    of ``block``, the scores' and the mask's blocks ``buffers`` deep: a
    jitted function of its own, so that a program traces and lowers the
    kernel body once for all the layers that call it, and a process once
    for all its programs (as _flash_call)."""
    rows, n = x.shape
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    # two buffers are the pipeline's default: ask for none there, so
    # that rows up to 39 296 keep the program they had
    mode = {} if buffers == 2 else {"pipeline_mode": pl.Buffered(buffers)}
    return pl.pallas_call(
        functools.partial(_topk_kernel, k=k, lanes=lanes),
        grid=(rows // block,),
        in_specs=[pl.BlockSpec((block, n), lambda i: (i, 0), **mode)],
        out_specs=pl.BlockSpec((block, n), lambda i: (i, 0), **mode),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block, n), jnp.int32),
                        pltpu.VMEM((block, 1), jnp.int32)],
        interpret=interpret,
        name="topk_select",
        **kwargs,
    )(x)
