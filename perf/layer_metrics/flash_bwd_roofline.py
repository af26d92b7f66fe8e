"""Kernels: least time for the traced steps' attention backward
(lib/kernel_costs.flash_bwd, once per layer per step) over the time of
``flash_bwd_dq`` + ``flash_bwd_dkv`` + ``flash_bwd_rowstats`` together."""
from lib import kernel_costs
from lib.peaks import least_time_s

PARTS = ("flash_bwd_dq", "flash_bwd_dkv", "flash_bwd_rowstats")


def read(ctx):
    took = sum(ctx.reduced.kernel_seconds.get(k, 0.0) for k in PARTS)
    calls = ctx.reduced.kernel_calls.get("flash_bwd_dkv")
    if not took or not calls or ctx.peaks is None:
        return None
    f = ctx.facts
    flops, nbytes = kernel_costs.flash_bwd(
        f["seq"], f["batch"] * f["n_heads"], f["head_dim"])
    least, bound = least_time_s(flops * calls, nbytes * calls, ctx.peaks)
    parts = {k: round(ctx.reduced.kernel_seconds.get(k, 0.0), 5)
             for k in PARTS}
    ctx.note(f"flash_bwd: {calls} backward passes need {flops * calls:.4g} "
             f"FLOP and {nbytes * calls:.4g} B, least {least:.5f}s "
             f"({bound}-bound), took {took:.5f}s {parts}")
    return 100.0 * least / took
