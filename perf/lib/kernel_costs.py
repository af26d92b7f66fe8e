"""Operations and HBM bytes that each kernel's ALGORITHM needs, from its
shapes alone. These are the numerators of every ``<kernel>_roofline``; they
live with the benchmark so that no PR that claims a gain can move them.

Each function counts what the mathematics requires, never what an
implementation happens to do: a kernel that streams padding, recomputes
more than it must or reads a tensor twice gets no credit for it, so a
share computed from these counts cannot pass 100%.

Conventions: a multiply-add is 2 operations; ``itemsize`` is the width in
bytes of the activation (or payload) type.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def flash_decode(contexts: Iterable[int], n_heads: int, kv_heads: int,
                 head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One decode step of one layer over a batch of rows whose live
    contexts are ``contexts`` (tokens already in the cache, the new one
    included). Needs: K and V of every live position once, q in and the
    output out; QK^T and PV over the live positions only."""
    flops = nbytes = 0.0
    for ctx in contexts:
        flops += 2 * 2 * n_heads * head_dim * ctx
        nbytes += (2 * kv_heads * head_dim * ctx * itemsize
                   + 2 * n_heads * head_dim * itemsize)
    return flops, nbytes


def flash_fwd(seq: int, folded_heads: int, head_dim: int,
              itemsize: int = 2) -> Tuple[float, float]:
    """Causal self-attention forward over ``folded_heads`` (batch x heads)
    independent heads of ``seq`` positions. QK^T and PV over the lower
    triangle (half the square); q, k, v read and the output written once,
    plus one float32 row statistic per position for the backward pass."""
    pairs = seq * (seq + 1) / 2
    flops = 2 * 2 * pairs * head_dim * folded_heads
    nbytes = folded_heads * seq * (4 * head_dim * itemsize + 4)
    return flops, nbytes


def flash_bwd(seq: int, folded_heads: int, head_dim: int,
              itemsize: int = 2) -> Tuple[float, float]:
    """Causal self-attention backward (dq, dk, dv) of the flash algorithm:
    the scores are not stored, so one recomputation of QK^T is part of the
    algorithm; then dP = dO V^T, dV = P^T dO, dK = dS^T Q, dQ = dS K:
    five matrix products over the lower triangle. A kernel split that
    recomputes QK^T or dP a second time gets no credit for it. Bytes: q,
    k, v, o, dO read and dq, dk, dv written once, plus two float32 row
    statistics (log-sum-exp, delta) per position."""
    pairs = seq * (seq + 1) / 2
    flops = 5 * 2 * pairs * head_dim * folded_heads
    nbytes = folded_heads * seq * (8 * head_dim * itemsize + 8)
    return flops, nbytes


def fused_combine(n_elems: int, itemsize: int = 4) -> Tuple[float, float]:
    """Elementwise combine of two operands of ``n_elems`` elements: one
    operation per element; two reads and one write."""
    return float(n_elems), 3.0 * n_elems * itemsize


def train_flops_per_token(n_params: int, n_layers: int, d_model: int,
                          seq: int) -> float:
    """Model FLOPs one trained token requires (copied from
    ``benchmarks/train_bench.py::flops_per_token``): 6 per parameter
    (forward 2, backward 4; the tied embedding counts once, as the output
    head) plus causal attention's 12 * layers * d_model * seq / 2.
    Recomputed operations do not count."""
    return 6.0 * n_params + 12.0 * n_layers * d_model * seq * 0.5


def allreduce_busbw(nbytes_per_rank: int, n_ranks: int,
                    seconds: float) -> float:
    """Bus bandwidth in bytes/s: ``2 (n-1)/n * bytes / time``, the figure
    that is comparable across rank counts (NCCL-tests' definition, the
    one BASELINE.json's bar is stated in)."""
    return 2.0 * (n_ranks - 1) / n_ranks * nbytes_per_rank / seconds
