"""Kernels: the least time the chip could take for the traced rounds' token
selector scores (``index_score``: a row's 128-wide index keys streamed once
against 64 query heads), over the time the kernel took in the trace. Needed
work is that of the LIVE contexts of every row (lib/kernel_costs_dsa
.index_score): step s of a row at position p scores p + s + 1 keys. A
program without the kernel (the parent) leaves the metric out."""
from lib.peaks import least_time_s


def read(ctx):
    model = ctx.config.get("model", {})
    took = ctx.reduced.kernel_seconds.get("index_score")
    if not took or ctx.peaks is None or not model.get("index_topk"):
        return None
    from lib import kernel_costs_dsa
    flops = nbytes = 0.0
    for pos, _budget, kk in ctx.facts["traced_rounds"]:
        for p in pos:
            fl, by = kernel_costs_dsa.index_score(
                range(int(p) + 1, int(p) + 1 + int(kk)),
                model["index_n_heads"], model["index_head_dim"])
            flops += fl * model["n_layers"]
            nbytes += by * model["n_layers"]
    least, bound = least_time_s(flops, nbytes, ctx.peaks)
    ctx.note(f"index_score: needs {flops:.4g} FLOP and {nbytes:.4g} B, "
             f"least {least:.5f}s ({bound}-bound), took {took:.5f}s in "
             f"{ctx.reduced.kernel_calls.get('index_score')} calls")
    return 100.0 * least / took
