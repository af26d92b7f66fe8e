"""Operations and HBM bytes that the ALGORITHM of a block attend needs, from
its shapes alone: the numerator of ``block_attend_roofline``. Beside
``kernel_costs.py`` and under its conventions (a multiply-add is 2
operations; ``itemsize`` is the activation's width in bytes; what an
implementation streams or recomputes beyond the mathematics gets no credit,
so a share computed from these counts cannot pass 100%)."""

from __future__ import annotations

from typing import Iterable, Tuple


def block_attend(contexts: Iterable[int], n_heads: int, kv_heads: int,
                 head_dim: int, block_len: int,
                 itemsize: int = 2) -> Tuple[float, float]:
    """One pass of one layer of generation by diffusion over blocks, over
    rows whose blocks attend ``contexts`` positions each (everything before
    the block and the block itself: its first position + ``block_len``).
    All ``block_len`` x ``n_heads`` query rows of a row see the same
    positions, so its ``ctx x kv_heads x head_dim`` keys and as many values
    are needed ONCE, whatever the block's length and the heads a group;
    beside them the block's queries in and its outputs out. QK^T and PV over
    the attended positions for every query row."""
    flops = nbytes = 0.0
    for ctx in contexts:
        flops += 2 * 2 * block_len * n_heads * ctx * head_dim
        nbytes += (2 * kv_heads * head_dim * ctx
                   + 2 * block_len * n_heads * head_dim) * itemsize
    return flops, nbytes
