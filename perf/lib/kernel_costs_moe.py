"""Operations and HBM bytes that the ALGORITHMS of latent attention's
absorbed decode and of a grouped expert FFN need, from their shapes alone:
the numerators of ``mla_decode_roofline`` and ``expert_ffn_roofline``.
Beside ``kernel_costs.py`` and under its conventions (a multiply-add is 2
operations; ``itemsize`` is the activation's width in bytes; what an
implementation streams or recomputes beyond the mathematics gets no credit,
so a share computed from these counts cannot pass 100%)."""

from __future__ import annotations

from typing import Iterable, Tuple


def mla_decode(contexts: Iterable[int], n_heads: int, latent_dim: int,
               v_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One absorbed decode step of one layer over rows whose live contexts
    are ``contexts`` (latent rows already in the cache, the new one
    included). Every head attends the SAME latent stream, so a row's
    ``ctx x latent_dim`` cache bytes are needed once, whatever the number
    of heads; beside them the absorbed queries in (heads x latent_dim) and
    the latent outputs out (heads x v_dim). Scores contract latent_dim,
    values the stream's leading v_dim features."""
    flops = nbytes = 0.0
    for ctx in contexts:
        flops += 2 * n_heads * ctx * (latent_dim + v_dim)
        nbytes += (ctx * latent_dim + n_heads * (latent_dim + v_dim)) \
            * itemsize
    return flops, nbytes


def expert_ffn(experts_hit: int, rows: int, d_model: int, d_ff: int,
               itemsize: int = 2) -> Tuple[float, float]:
    """Gated FFNs over ``rows`` (token, choice) assignments that landed on
    ``experts_hit`` (expert, layer, step) triples: each hit expert's three
    (d_model x d_ff) matrices read once for that step, each row in and out
    once, three matrix products a row. Padding rows count for nothing."""
    flops = 2.0 * 3 * rows * d_model * d_ff
    nbytes = (3.0 * experts_hit * d_model * d_ff
              + 2.0 * rows * d_model) * itemsize
    return flops, nbytes
