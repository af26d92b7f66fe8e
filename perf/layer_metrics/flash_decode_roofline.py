"""Kernels: the least time the chip could take for the traced rounds'
flash_decode calls, over the time the kernel took in the trace. Needed
work is that of the LIVE contexts of rows that still owe tokens
(lib/kernel_costs.flash_decode), not of ``max_len``."""
from lib import kernel_costs
from lib.peaks import least_time_s


def read(ctx):
    took = ctx.reduced.kernel_seconds.get("flash_decode")
    if not took or ctx.peaks is None:
        return None
    f = ctx.facts
    flops = nbytes = 0.0
    for pos, budget, kk in f["traced_rounds"]:
        for p, b in zip(pos, budget):
            steps = int(min(b, kk))
            fl, by = kernel_costs.flash_decode(
                range(int(p) + 1, int(p) + 1 + steps), f["n_heads"],
                f["kv_heads"], f["head_dim"])
            flops += fl * f["n_layers"]
            nbytes += by * f["n_layers"]
    least, bound = least_time_s(flops, nbytes, ctx.peaks)
    ctx.note(f"flash_decode: needs {flops:.4g} FLOP and {nbytes:.4g} B, "
             f"least {least:.5f}s ({bound}-bound), took {took:.5f}s in "
             f"{ctx.reduced.kernel_calls.get('flash_decode')} calls")
    return 100.0 * least / took
