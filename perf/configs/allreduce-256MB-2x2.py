"""The plain reference of the ``allreduce-256MB-2x2`` deployment: the same
operation on the same data in numpy float64, independent of ``rlo_tpu``,
and the check of the guarantees the configuration states."""

from __future__ import annotations

import numpy as np

RTOL, ATOL = 1e-5, 1e-4


def allreduce_sum(per_rank: np.ndarray) -> np.ndarray:
    """(ranks, n) inputs -> (n,) float64 sum over ranks."""
    return np.sum(np.asarray(per_rank, np.float64), axis=0)


def check(per_rank_in: np.ndarray, per_rank_out: np.ndarray) -> str:
    """'' when every rank's output equals the float64 sum of all inputs
    within float32 rounding and all ranks agree; else what failed."""
    want = allreduce_sum(per_rank_in)
    out = np.asarray(per_rank_out, np.float64)
    if not np.isfinite(out).all():
        return "non-finite values in the result"
    for r in range(out.shape[0]):
        if not np.allclose(out[r], want, rtol=RTOL, atol=ATOL):
            return (f"rank {r}: max |got - sum| "
                    f"{np.max(np.abs(out[r] - want)):.3g}")
        if not np.array_equal(per_rank_out[r], per_rank_out[0]):
            return f"rank {r} disagrees with rank 0"
    return ""
