"""Kernels: least time for the traced calls' ``fused_combine`` kernels
(two reads and one write of each combined chunk, as the trace counts the
calls) over their time in the trace. The chunk is 1 / (2 x ranks x 2) of
the per-rank buffer (two directions, ``ranks`` rows, two pipeline
sub-chunks)."""
from lib import kernel_costs
from lib.peaks import least_time_s

PIPELINE_CHUNKS = 2     # tpu_collectives._default_pipeline_chunks() on TPU


def read(ctx):
    took = ctx.reduced.kernel_seconds.get("fused_combine")
    calls = ctx.reduced.kernel_calls.get("fused_combine")
    if not took or ctx.peaks is None:
        return None
    f = ctx.facts
    chunk = -(-f["elements_per_rank"] // (2 * f["ranks"] * PIPELINE_CHUNKS))
    flops, nbytes = kernel_costs.fused_combine(chunk)
    least, bound = least_time_s(flops * calls, nbytes * calls, ctx.peaks)
    ctx.note(f"fused_combine: {calls} calls of {chunk} elements need "
             f"{nbytes * calls:.4g} B, least {least:.5f}s ({bound}-bound), "
             f"took {took:.5f}s per chip")
    return 100.0 * least / took
