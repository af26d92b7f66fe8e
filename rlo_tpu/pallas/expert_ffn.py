"""Pallas grouped gated FFN over the experts a chip holds.

One decode step of a sparse-expert layer gives each held expert a
handful of rows (128 rows x 8 choices over 256 experts: 4 an expert),
so the layer's time is the stream of the hit experts' weights (three
(d, f) matrices each) and nothing else. A dense einsum over every held
expert streams them all and multiplies mostly padding; a per-expert
Python loop cannot follow a routing that is only known on the device.
This kernel runs the rows SORTED BY EXPERT, in tiles of ``tile`` rows
that never straddle two experts (models.moe.held_experts_ffn pads each
expert's run to whole tiles):

  x           (n_rows, d)   rows, tile-aligned per expert
  wg, wu      (E, d, f)     gate and up projections of the E held
  wd          (E, f, d)     down projection
  tile_expert (n_tiles,)    int32, scalar-prefetched: whose rows tile i holds
  n_live      (1,)          int32, scalar-prefetched: tiles that hold rows
  out         (n_rows, d)   down(silu(x wg) * (x wu)) per row, x's dtype

Grid (tiles, steps). The weight blocks are CONTIGUOUS (whole rows of the
stored matrix, so every DMA is one run of bytes) and as wide as
``_STEP_BYTES`` allows (``_chunk``): ``dk`` rows of wg and wu, ``fb``
rows of wd.

  * An expert whose matrices fit the budget whole (dk = d, fb = f) is
    ONE step a tile: gate, up, activation and down in one body, while
    the next tile's three matrices stream in.
  * A larger one is two phases: the contraction over d in d / dk steps,
    accumulating gate and up in float32 scratch; then, the activation
    formed once, the down projection over f in f / fb steps,
    accumulating the output.

Pallas fetches a step's blocks while the step before it computes, so a
step whose successor presents the blocks it holds already runs with NO
copy in flight, and the stream stands still for as long as it
computes. That, not the number of steps, is what the time follows
(``_STEP_BYTES``): the index maps therefore give every step a fetch to
cover. Through phase 1 wd stays on the block the tile BEFORE used
last, so this tile's first wd block streams under its last contraction
step; through phase 2 wg and wu stay on their last block, so the next
tile's first blocks stream under this tile's last down step.

Work follows the rows that landed here: tiles past ``n_live`` do not
run, and their index maps repeat the last live tile's blocks, for which
Pallas issues no copy — an expert with no row is skipped, weights and
all. The grid itself is static and a dead tile is still its steps,
empty (~0.35 us each), so ``expert_ffn`` compiles the grid twice: ``E``
tiles (one an expert, the common case) and the whole buffer (any
skew), chosen by ``n_live`` on the device. Static shapes, no token
dropped.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Most bytes a weight block may hold: the chunk of the contraction over
#: d (phase 1: dk rows of wg and wu, dk * f * itemsize each) and over f
#: (phase 2: fb rows of wd). Timed alone on a v5e in bf16 (PERF.md,
#: PR 35, step 0; us a call, the bytes' time at 819 GB/s in brackets):
#:
#:   128 experts x 2048 x 768, 768 tokens x 8, tiles of 128 (1536):
#:     (dk, fb) = (512, 256), 7 steps a tile, wd held on the block it
#:     will use first: 2117; (1024, 768), 3 steps: 2228; (2048, 768),
#:     2 steps: 2498 -- FEWER steps, SLOWER: the last contraction step
#:     had nothing to fetch (wd's first block came with wg's), and the
#:     wider the chunk the longer the stream stood still.
#:     With wd held on the block used last (module docstring): 7 steps
#:     1960, 2 steps 1970 -- a step costs nothing the stream does not
#:     hide. Whole matrices in ONE step: 1820.
#:   16 experts x 7168 x 2048, 128 tokens x 8, tiles of 16 (1723):
#:     (512, 256), 22 steps, as it was: 1919; with wd held on the block
#:     used last: (512, 256) 1893, (1024, 512) 1899, (1792, 512) 1897,
#:     (3584, 1024) 1906, (3584, 2048) at 115 MB of VMEM 1911 -- the
#:     first blocks of a call stream with nothing to hide behind, so
#:     wider is slower, by 1%.
#:
#: So the budget is what holds a small expert's matrix whole (2048 x 768
#: in bf16: 3 MiB), for the sake of the one-step body, and no wider.
_STEP_BYTES = 4 << 20


def _chunk(n: int, row_bytes: int) -> int:
    """Largest multiple of 128 that divides ``n`` and whose rows of
    ``row_bytes`` fit ``_STEP_BYTES`` (0 when there is none)."""
    for c in range(n - n % 128, 0, -128):
        if n % c == 0 and c * row_bytes <= _STEP_BYTES:
            return c
    return 0


def _chunks(d: int, f: int, itemsize: int):
    """(dk, fb): rows of wg / wu and of wd a grid step presents."""
    return _chunk(d, f * itemsize), _chunk(f, d * itemsize)


def can_expert_ffn(d: int, f: int, tile: int) -> bool:
    """Shape gate: d and f split into 128-multiples whose blocks fit
    the step's budget (at float32, the widest operand, so at any), a
    tile of whole sublane groups."""
    return all(_chunks(d, f, 4)) and tile % 8 == 0


def steps_per_tile(d: int, f: int, itemsize: int) -> int:
    """Grid steps a live tile takes at (d, f) in operands of
    ``itemsize`` bytes: 1 when the expert's matrices fit a step whole,
    else d / dk + f / fb (0 outside the kernel's shapes)."""
    dk, fb = _chunks(d, f, itemsize)
    if not (dk and fb):
        return 0
    return 1 if (dk, fb) == (d, f) else d // dk + f // fb


def buffer_rows(n_assign: int, n_held: int, tile: int) -> int:
    """Rows of the sorted buffer that holds ANY routing of ``n_assign``
    (token, choice) pairs over ``n_held`` experts: every pair here,
    each expert's run padded to whole tiles."""
    return -(-(n_assign + n_held * (tile - 1)) // tile) * tile


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32)


def swiglu(g, u, limit: float = 0.0):
    """silu(g) * u, or with ``limit`` > 0 the clamped form
    silu(min(g, limit)) * clip(u, -limit, limit): the kernel's body and
    models.moe's XLA forms alike."""
    if limit:
        g = jnp.minimum(g, limit)
        u = jnp.clip(u, -limit, limit)
    return jax.nn.silu(g) * u


def _kernel(te_ref, nl_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            *scratch, nd: int, nf: int, fb: int, limit: float = 0.0):
    i = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(i < nl_ref[0])
    def _live():
        if not scratch:          # the expert's matrices whole: one step
            x = x_ref[...]
            if limit:
                h = swiglu(_dot(x, wg_ref[0]), _dot(x, wu_ref[0]),
                           limit)
            else:
                h = jax.nn.silu(_dot(x, wg_ref[0])) * _dot(x, wu_ref[0])
            o_ref[...] = _dot(h.astype(x.dtype),
                              wd_ref[0]).astype(o_ref.dtype)
            return
        g_s, u_s, h_s, acc_s = scratch

        @pl.when(s < nd)
        def _contract():
            x = x_ref[...]                               # (tm, dk)
            g = _dot(x, wg_ref[0])
            u = _dot(x, wu_ref[0])

            @pl.when(s == 0)
            def _first():
                g_s[...] = g
                u_s[...] = u

            @pl.when(s > 0)
            def _rest():
                g_s[...] += g
                u_s[...] += u

        @pl.when(s == nd - 1)
        def _activate():
            if limit:
                h = swiglu(g_s[...], u_s[...], limit).astype(h_s.dtype)
            else:
                h = (jax.nn.silu(g_s[...]) * u_s[...]).astype(h_s.dtype)
            for j in range(nf):                          # static slices
                h_s[j] = h[:, j * fb:(j + 1) * fb]

        @pl.when(s >= nd)
        def _down():
            j = s - nd
            part = _dot(h_s[j], wd_ref[0])

            @pl.when(j == 0)
            def _first():
                acc_s[...] = part

            @pl.when(j > 0)
            def _rest():
                acc_s[...] += part

        @pl.when(s == nd + nf - 1)
        def _flush():
            o_ref[...] = acc_s[...].astype(o_ref.dtype)


def _call(x, wg, wu, wd, tile_expert, n_live, *, tile: int,
          n_tiles: int, interpret: bool, limit: float = 0.0):
    n_rows, d = x.shape
    _, _, f = wg.shape
    item = x.dtype.itemsize
    dk, fb = _chunks(d, f, item)
    nd, nf = d // dk, f // fb
    steps = steps_per_tile(d, f, item)

    def live(i, nl):        # the tile whose blocks step (i, .) presents
        return jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))

    def d_chunk(i, s, nl):  # phase 1's chunk; held at its last after
        return jnp.where(i < nl[0], jnp.minimum(s, nd - 1), nd - 1)

    def x_map(i, s, te, nl):
        return live(i, nl), d_chunk(i, s, nl)

    def up_map(i, s, te, nl):
        return te[live(i, nl)], d_chunk(i, s, nl), 0

    def down_map(i, s, te, nl):
        # through a phase 1 that a phase 2 follows: the block the tile
        # before used last (the very first tile: the one it will use
        # first), so that phase 1's last step has this tile's first
        # block to fetch; a dead tile: the last live tile's last
        t = live(i, nl)
        before = (s < nd) & (0 < i) & (i < nl[0]) & (steps > 1)
        return (te[jnp.where(before, t - 1, t)],
                jnp.where(before | (i >= nl[0]), nf - 1,
                          jnp.maximum(s - nd, 0)), 0)

    def out_map(i, s, te, nl):
        return live(i, nl), 0

    # float32 gate, up and output accumulators and the activation
    scratch = [((tile, f), jnp.float32), ((tile, f), jnp.float32),
               ((nf, tile, fb), x.dtype), ((tile, d), jnp.float32)]
    kwargs = {}
    if not interpret:
        # two buffers of every block; the scratch arrays, and as much
        # twice over for the values the body holds (gate, up, their
        # product, a partial output: the same shapes)
        blocks = (tile * dk + 2 * dk * f + fb * d + tile * d) * item
        held = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                   for shape, dt in scratch)
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * blocks + 3 * held)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, steps),
        in_specs=[pl.BlockSpec((tile, dk), x_map),
                  pl.BlockSpec((1, dk, f), up_map),
                  pl.BlockSpec((1, dk, f), up_map),
                  pl.BlockSpec((1, fb, d), down_map)],
        out_specs=pl.BlockSpec((tile, d), out_map),
        scratch_shapes=[] if steps == 1 else [
            pltpu.VMEM(shape, dt) for shape, dt in scratch],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, nf=nf, fb=fb,
                          **({"limit": limit} if limit else {})),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, d), x.dtype),
        interpret=interpret,
        name="expert_ffn",
        **kwargs,
    )(tile_expert, n_live.reshape(1), x, wg.astype(x.dtype),
      wu.astype(x.dtype), wd.astype(x.dtype))


def expert_ffn(x, wg, wu, wd, tile_expert, n_live, *, tile: int,
               interpret: Optional[bool] = None, limit: float = 0.0):
    """Grouped gated FFN: row r of ``x`` (n_rows, d), in tile r // tile
    whose expert is ``tile_expert[r // tile]``, through that expert's
    down(silu(x wg) * (x wu)) — with ``limit`` > 0, down(silu(min(x wg,
    limit)) * clip(x wu, -limit, limit)), a static setting of the
    body. Only the first ``n_live`` tiles run; the rows of the others are
    not written. Returns (n_rows, d)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n_rows, d = x.shape
    n_held, _, f = wg.shape
    if n_rows % tile or not can_expert_ffn(d, f, tile):
        raise ValueError(
            f"expert_ffn: {n_rows} rows in tiles of {tile}, d={d}, "
            f"f={f} is outside the kernel's shapes (can_expert_ffn)")
    n_tiles = n_rows // tile
    tile_expert = jnp.asarray(tile_expert, jnp.int32).reshape(n_tiles)
    n_live = jnp.asarray(n_live, jnp.int32).reshape(())
    run = functools.partial(_call, x, wg, wu, wd, tile_expert, n_live,
                            tile=tile, interpret=interpret,
                            **({"limit": float(limit)} if limit else {}))
    if n_tiles <= n_held:
        return run(n_tiles=n_tiles)
    # the common case, at most a tile an expert, on a grid of n_held
    # tiles: a dead tile is its steps, empty
    return lax.cond(n_live <= n_held,
                    lambda: run(n_tiles=n_held),
                    lambda: run(n_tiles=n_tiles))
