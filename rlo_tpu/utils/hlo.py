"""Lowered-StableHLO text accounting helpers.

The byte-pinning discipline (allreduce_cost / hierarchical_allreduce_cost
/ all_to_all_cost vs the program XLA actually builds) needs to read
collective operand shapes out of `lowered.as_text()`. The regexes are
brittle against JAX printing changes by nature, so they live in exactly
one place — tests/test_tpu_collectives.py, __graft_entry__ and
chip_smoke.py all import from here. The same goes for finding Pallas
kernels in a program: chip_smoke.py proves a step really ran its
kernels by counting their Mosaic custom calls (``mosaic_kernels``).
"""

from __future__ import annotations

import re

#: element widths as StableHLO prints them (jax 0.9.0: signless ``iN``,
#: unsigned ``uiN``, predicates ``i1`` stored one byte each). A dtype
#: that is not here raises in ``_nbytes`` — a collective of an unknown
#: width must not be skipped or counted as zero.
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2,
                "i64": 8, "i32": 4, "i16": 2, "i8": 1, "i1": 1,
                "ui64": 8, "ui32": 4, "ui16": 2, "ui8": 1}

_PERMUTE_RE = re.compile(
    r'collective_permute"?\(?[^\n]*?source_target_pairs\s*=\s*'
    r'dense<\[\[(\d+),\s*(\d+)\][^\n]*?'
    r'tensor<([0-9x]*)x?([A-Za-z][A-Za-z0-9]*)>\)?\s*$',
    re.MULTILINE)

_MOSAIC_RE = re.compile(
    r'custom_call @tpu_custom_call\([^\n]*?kernel_name = "([^"]+)"')


def _elems(dims: str) -> int:
    n = 1
    for d in dims.split("x"):
        if d:
            n *= int(d)
    return n


def _nbytes(dims: str, dtype: str) -> int:
    if dtype not in _DTYPE_BYTES:
        raise ValueError(
            f"collective operand dtype {dtype!r} has no width in "
            f"rlo_tpu/utils/hlo.py _DTYPE_BYTES — add it")
    return _elems(dims) * _DTYPE_BYTES[dtype]


def _check_matched(n: int, what: str, require: bool) -> None:
    """Byte-pinning guard: when the caller KNOWS the collective is in
    the program, zero regex matches means the StableHLO printer
    changed shape — fail loudly instead of letting a silent 0 win a
    `total == model` comparison (or, worse, a `0 <= budget` one)."""
    if require and n == 0:
        raise ValueError(
            f"no {what} ops matched the lowered text, but the caller "
            f"asserts the collective exists — the StableHLO printer "
            f"likely changed; update the regexes in rlo_tpu/utils/hlo.py")


def permute_total_bytes(lowered_text: str, require: bool = False):
    """Total collective_permute operand bytes + launch count,
    pattern-agnostic (ring, XOR halving/doubling, shift-o hops all
    counted). ``require=True`` raises if NOTHING matched (use wherever
    the program is known to contain permutes)."""
    total = n = 0
    for m in _PERMUTE_RE.finditer(lowered_text):
        total += _nbytes(m.group(3), m.group(4))
        n += 1
    _check_matched(n, "collective_permute", require)
    return total, n


def permute_entries(lowered_text: str, require: bool = False):
    """Per-launch (src, dst, nbytes) of the first source-target pair of
    every collective_permute — enough to classify ring direction or
    shift offset. ``require=True`` raises on zero matches."""
    out = []
    for m in _PERMUTE_RE.finditer(lowered_text):
        out.append((int(m.group(1)), int(m.group(2)),
                    _nbytes(m.group(3), m.group(4))))
    _check_matched(len(out), "collective_permute", require)
    return out


def all_gather_operands(lowered_text: str, require: bool = False):
    """(elems, dtype) of every all_gather operand in the text.
    ``require=True`` raises on zero matches."""
    out = [(_elems(dims), dt) for dims, dt in re.findall(
        r'all_gather[^\n]*?:\s*\(tensor<([0-9x]*)x?'
        r'([A-Za-z][A-Za-z0-9]*)>\)', lowered_text)]
    _check_matched(len(out), "all_gather", require)
    return out


def mosaic_kernels(lowered_text: str, require: bool = False) -> dict:
    """Pallas kernels that lowered to Mosaic custom calls, as
    {kernel name: number of call sites in the text}. The name is the
    ``name=`` each ``pl.pallas_call`` in rlo_tpu/pallas passes; a
    scan/while body is one call site however many times it runs, and an
    interpreted kernel (any non-TPU lowering) does not appear at all.
    ``require=True`` raises on zero matches (use wherever the program
    is known to hold kernels)."""
    out: dict = {}
    for m in _MOSAIC_RE.finditer(lowered_text):
        out[m.group(1)] = out.get(m.group(1), 0) + 1
    _check_matched(len(out), "tpu_custom_call", require)
    return out


def mosaic_call_count(compiled_text: str, require: bool = False) -> int:
    """Mosaic custom calls left in COMPILED HLO text
    (``jit(f).lower(...).compile().as_text()``): what XLA kept after
    dead-code elimination. The compiled text carries no kernel names —
    pair it with ``mosaic_kernels`` on the lowered text."""
    n = compiled_text.count('custom_call_target="tpu_custom_call"')
    _check_matched(n, "tpu_custom_call (compiled)", require)
    return n
