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

Grid (tiles, steps). A tile's steps are two phases over CONTIGUOUS
weight blocks (a block of whole rows of the stored matrix, so every DMA
is one run of bytes): first the contraction over d in ``_DK`` chunks —
wg and wu blocks (1, dk, f) — accumulating gate and up in float32
scratch; then, the activation formed once, the down projection over f
in ``_FB`` chunks — wd blocks (1, fb, d) — accumulating the output.
The index maps hold a phase's idle operand on the block it last used
(or will use first), so nothing is fetched twice.

Work follows the rows that landed here: tiles past ``n_live`` do not
run, and their index maps repeat the last live tile's blocks, for which
Pallas issues no copy — an expert with no row is skipped, weights and
all. The grid itself is static, and an empty grid step still costs
~0.35 us, so ``expert_ffn`` compiles the grid twice: ``E`` tiles (one
an expert, the common case) and the whole buffer (any skew), chosen by
``n_live`` on the device. Static shapes, no token dropped.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: widest chunk of the contraction over d (phase 1) and of f (phase 2)
_DK = 512
_FB = 256


def _chunk(n: int, most: int) -> int:
    """Largest multiple of 128 that divides ``n`` and is <= ``most``
    (0 when there is none)."""
    for c in range(most, 0, -128):
        if n % c == 0:
            return c
    return 0


def can_expert_ffn(d: int, f: int, tile: int) -> bool:
    """Shape gate: d and f split into 128-multiples, a tile of whole
    sublane groups."""
    return bool(_chunk(d, _DK) and _chunk(f, _FB)) and tile % 8 == 0


def buffer_rows(n_assign: int, n_held: int, tile: int) -> int:
    """Rows of the sorted buffer that holds ANY routing of ``n_assign``
    (token, choice) pairs over ``n_held`` experts: every pair here,
    each expert's run padded to whole tiles."""
    return -(-(n_assign + n_held * (tile - 1)) // tile) * tile


def _kernel(te_ref, nl_ref, x_ref, wg_ref, wu_ref, wd_ref, o_ref,
            g_s, u_s, h_s, acc_s, *, nd: int, nf: int, fb: int):
    i = pl.program_id(0)
    s = pl.program_id(1)

    @pl.when(i < nl_ref[0])
    def _live():
        @pl.when(s < nd)
        def _contract():
            x = x_ref[...]                               # (tm, dk)
            g = jnp.dot(x, wg_ref[0],
                        preferred_element_type=jnp.float32)
            u = jnp.dot(x, wu_ref[0],
                        preferred_element_type=jnp.float32)

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
            h = (jax.nn.silu(g_s[...]) * u_s[...]).astype(h_s.dtype)
            for j in range(nf):                          # static slices
                h_s[j] = h[:, j * fb:(j + 1) * fb]

        @pl.when(s >= nd)
        def _down():
            j = s - nd
            part = jnp.dot(h_s[j], wd_ref[0],
                           preferred_element_type=jnp.float32)

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
          n_tiles: int, interpret: bool):
    n_rows, d = x.shape
    _, _, f = wg.shape
    dk, fb = _chunk(d, _DK), _chunk(f, _FB)
    nd, nf = d // dk, f // fb

    def live(i, nl):        # the tile whose blocks step (i, .) presents
        return jnp.minimum(i, jnp.maximum(nl[0] - 1, 0))

    def d_chunk(i, s, nl):  # phase 1's chunk; held at its last after
        return jnp.where(i < nl[0], jnp.minimum(s, nd - 1), nd - 1)

    def x_map(i, s, te, nl):
        return live(i, nl), d_chunk(i, s, nl)

    def up_map(i, s, te, nl):
        return te[live(i, nl)], d_chunk(i, s, nl), 0

    def down_map(i, s, te, nl):
        return (te[live(i, nl)],
                jnp.where(i < nl[0], jnp.maximum(s - nd, 0), nf - 1), 0)

    def out_map(i, s, te, nl):
        return live(i, nl), 0

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles, nd + nf),
        in_specs=[pl.BlockSpec((tile, dk), x_map),
                  pl.BlockSpec((1, dk, f), up_map),
                  pl.BlockSpec((1, dk, f), up_map),
                  pl.BlockSpec((1, fb, d), down_map)],
        out_specs=pl.BlockSpec((tile, d), out_map),
        scratch_shapes=[pltpu.VMEM((tile, f), jnp.float32),
                        pltpu.VMEM((tile, f), jnp.float32),
                        pltpu.VMEM((nf, tile, fb), x.dtype),
                        pltpu.VMEM((tile, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, nd=nd, nf=nf, fb=fb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, d), x.dtype),
        interpret=interpret,
        name="expert_ffn",
        **kwargs,
    )(tile_expert, n_live.reshape(1), x, wg.astype(x.dtype),
      wu.astype(x.dtype), wd.astype(x.dtype))


def expert_ffn(x, wg, wu, wd, tile_expert, n_live, *, tile: int,
               interpret: Optional[bool] = None):
    """Grouped gated FFN: row r of ``x`` (n_rows, d), in tile r // tile
    whose expert is ``tile_expert[r // tile]``, through that expert's
    down(silu(x wg) * (x wu)). Only the first ``n_live`` tiles run;
    the rows of the others are not written. Returns (n_rows, d)."""
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
                            tile=tile, interpret=interpret)
    if n_tiles <= n_held:
        return run(n_tiles=n_tiles)
    # the common case, at most a tile an expert, on a grid of n_held
    # tiles: a dead tile is nd + nf empty grid steps
    return lax.cond(n_live <= n_held,
                    lambda: run(n_tiles=n_held),
                    lambda: run(n_tiles=n_tiles))
