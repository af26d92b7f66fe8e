"""Pallas fused combine kernels — the per-step partial reduction of the
ring/recursive-doubling collectives.

BASELINE.json's north star asks for "the per-step partial reduction fused as
a Pallas kernel" (the TPU analogue of the reference's in-place vote merge
``vote &= v``, rootless_ops.c:1060, generalized from 1-bit AND to tensor
sum/min/max/and). The kernel fuses: upcast to f32 accumulation (for bf16
payloads), the combine, and the downcast — one VMEM-resident pass instead of
three HBM round-trips.

On non-TPU platforms the same kernel runs in Pallas interpret mode so tests
exercise the identical code path; tile shapes follow the v5e constraints
(lane dim 128, sublane multiples of 8 for f32 / 16 for bf16 — see
/opt/skills/guides/pallas_guide.md "Tiling Constraints").
"""

from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128  # rlo-prover: lane-pinned (XLA lane width; page contract)
# 2048*128*4B = 1 MB/operand per grid block. Block-shape sweep recorded
# 2026-07-30 on one v5e chip (256 MB fp32 operands, k=256 chained
# timing, benchmarks/pallas_sweep.py; not re-measured since): 2048 rows
# ~731 GB/s vs 512 rows ~657 and XLA-fused ~727 (parity); wider lane
# layouts (256-1024-wide rows) were 2-3x SLOWER — the (rows, 128)
# native lane layout wins.
_DEFAULT_BLOCK_ROWS = 2048


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


class KernelFallbackWarning(RuntimeWarning):
    """The tpu backend is live but a call took the XLA reference path
    because its shape failed a kernel's gate."""


def kernel_gate(ok: bool, what: str) -> bool:
    """The auto-enable decision shared by every kernel call site that
    has an XLA reference path: kernels on the tpu backend when the shape
    gate accepts, the reference everywhere else (tests reach the kernels
    through ``interpret=True``). A rejected shape ON tpu still runs —
    the reference is correct, only slower — but says so once per call
    site and shape; chip_smoke.py turns the warning into an error.
    Callers that pass their own use_flash/use_pallas never get here."""
    if not _on_tpu():
        return False
    if not ok:
        warnings.warn(
            f"{what}: shape rejected by the Pallas kernel gate on the "
            f"tpu backend; running the XLA reference path instead",
            KernelFallbackWarning, stacklevel=3)
    return ok


_F32_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}
_INT_OPS = {"and": jnp.bitwise_and, "or": jnp.bitwise_or}


def _combine_kernel(op_name: str, out_dtype):
    if op_name in _F32_OPS:
        fn = _F32_OPS[op_name]

        def kernel(a_ref, b_ref, o_ref):
            a = a_ref[...].astype(jnp.float32)
            b = b_ref[...].astype(jnp.float32)
            o_ref[...] = fn(a, b).astype(out_dtype)
    else:
        fn = _INT_OPS[op_name]

        def kernel(a_ref, b_ref, o_ref):
            o_ref[...] = fn(a_ref[...], b_ref[...])
    return kernel


def out_struct(shape, dtype, *arrays):
    """ShapeDtypeStruct carrying the union of ``arrays``' varying-mesh-
    axes annotations, so kernels work inside shard_map (check_vma=True).
    Shared by every pallas kernel in the package (reduce, flash)."""
    vma: set = set()
    for a in arrays:
        vma |= set(jax.typeof(a).vma)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=frozenset(vma))
    return jax.ShapeDtypeStruct(shape, dtype)


def _out_struct(a):
    return out_struct(a.shape, a.dtype, a)


def _fused_combine_2d(a, b, op: str, block_rows: int, interpret: bool,
                      in_place: bool):
    rows, width = a.shape
    grid = (pl.cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, width), lambda i: (i, 0))
    kwargs = {}
    if not interpret:
        # 'parallel' lets Mosaic pipeline block DMA with compute
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    if in_place:
        # alias operand 0's (internal, padded-layout) buffer into the
        # output, saving the output allocation on the accumulate path
        kwargs["input_output_aliases"] = {0: 0}
    return pl.pallas_call(
        _combine_kernel(op, a.dtype),
        out_shape=_out_struct(a),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
        name="fused_combine",
        **kwargs,
    )(a, b)


def fused_combine(a, b, op: str = "sum", block_rows: int = _DEFAULT_BLOCK_ROWS,
                  interpret: bool | None = None, in_place: bool = True,
                  lane: int = _LANE):
    """Elementwise ``op(a, b)`` with f32 accumulation, as one Pallas kernel.

    Accepts any shape/dtype; internally lays the data out as
    (rows, ``lane``) with the tail padded (``lane`` must be a multiple
    of the 128-wide vector lane; wider rows mean larger contiguous DMA
    blocks — retune with benchmarks/pallas_sweep.py). ``interpret=None``
    auto-selects: compiled on TPU, interpreter elsewhere. ``in_place``
    aliases the kernel's first operand — the internal staging buffer,
    not the caller's array — into the output, dropping one staging
    allocation per call on the accumulate path; the caller's ``a`` is
    never mutated.
    """
    if a.shape != b.shape or a.dtype != b.dtype:
        raise ValueError(f"operand mismatch: {a.shape}/{a.dtype} vs "
                         f"{b.shape}/{b.dtype}")
    if op not in _F32_OPS and op not in _INT_OPS:
        raise ValueError(f"unknown op {op!r}")
    if lane <= 0 or lane % _LANE:
        raise ValueError(
            f"lane {lane} must be a positive multiple of {_LANE}")
    if interpret is None:
        interpret = not _on_tpu()
    orig_shape = a.shape
    n = a.size
    rows = -(-n // lane)
    # sublane alignment: round rows up so every grid block is full
    sub = 16 if a.dtype == jnp.bfloat16 else 8
    rows = -(-rows // sub) * sub
    pad = rows * lane - n
    af = jnp.concatenate([a.reshape(-1), jnp.zeros(pad, a.dtype)]) \
        .reshape(rows, lane)
    bf = jnp.concatenate([b.reshape(-1), jnp.zeros(pad, b.dtype)]) \
        .reshape(rows, lane)
    block = min(block_rows, rows)
    out = _fused_combine_2d(af, bf, op, block, interpret, in_place)
    return out.reshape(-1)[:n].reshape(orig_shape)
