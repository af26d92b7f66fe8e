"""Operations and HBM bytes that the ALGORITHMS of a learned token selector
and of the sparse latent attend over its choice need, from their shapes
alone: the numerators of ``index_score_roofline`` and
``sparse_attend_roofline``. Beside ``kernel_costs.py`` and
``kernel_costs_moe.py`` and under their conventions (a multiply-add is 2
operations; ``itemsize`` is the activation's width in bytes; what an
implementation streams or recomputes beyond the mathematics gets no credit,
so a share computed from these counts cannot pass 100%)."""

from __future__ import annotations

from typing import Iterable, Tuple

from lib import kernel_costs_moe


def index_score(contexts: Iterable[int], n_heads: int, head_dim: int,
                itemsize: int = 2) -> Tuple[float, float]:
    """One step of one layer's selector over rows whose live contexts are
    ``contexts`` (index keys already in the cache, the new one included):
    a row's ``ctx x head_dim`` key bytes once (ONE key a token, shared by
    the heads), its (heads x head_dim) queries and (heads) f32 weights in,
    ``ctx`` f32 scores out; per key and head a ``head_dim`` dot product,
    the ReLU's weight and the sum over heads: 2 x heads x (head_dim + 1)."""
    flops = nbytes = 0.0
    for ctx in contexts:
        flops += 2 * n_heads * ctx * (head_dim + 1)
        nbytes += (ctx * head_dim + n_heads * head_dim) * itemsize \
            + 4 * (n_heads + ctx)
    return flops, nbytes


def sparse_attend(contexts: Iterable[int], topk: int, n_heads: int,
                  latent_dim: int, v_dim: int,
                  itemsize: int = 2) -> Tuple[float, float]:
    """The absorbed latent attend of one step and layer over the SELECTED
    rows alone: ``kernel_costs_moe.mla_decode``'s count at
    ``min(ctx, topk)`` rows a query. Reading the rest of the context, a
    gather, or a mask get no credit."""
    return kernel_costs_moe.mla_decode(
        (min(int(ctx), topk) for ctx in contexts), n_heads, latent_dim,
        v_dim, itemsize)
