"""Kernels: least time for the traced steps' ``flash_fwd`` calls (one per
layer per step, batch folded into the heads) over their time in the
trace."""
from lib import kernel_costs
from lib.peaks import least_time_s


def read(ctx):
    took = ctx.reduced.kernel_seconds.get("flash_fwd")
    calls = ctx.reduced.kernel_calls.get("flash_fwd")
    if not took or ctx.peaks is None:
        return None
    f = ctx.facts
    flops, nbytes = kernel_costs.flash_fwd(
        f["seq"], f["batch"] * f["n_heads"], f["head_dim"])
    least, bound = least_time_s(flops * calls, nbytes * calls, ctx.peaks)
    ctx.note(f"flash_fwd: {calls} calls need {flops * calls:.4g} FLOP and "
             f"{nbytes * calls:.4g} B, least {least:.5f}s ({bound}-bound), "
             f"took {took:.5f}s")
    return 100.0 * least / took
