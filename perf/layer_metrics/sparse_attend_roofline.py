"""Kernels: the least time the chip could take for the traced rounds'
latent attends if they read the SELECTED rows alone (at most ``index_topk``
a query: lib/kernel_costs_dsa.sparse_attend), over the time
``flash_decode`` took in the trace. An attend that takes the selection as a
mask streams the whole live context and reads low by that factor; a gather
earns no credit either. Only for a configuration with a token selector."""
from lib.peaks import least_time_s


def read(ctx):
    model = ctx.config.get("model", {})
    took = ctx.reduced.kernel_seconds.get("flash_decode")
    if not took or ctx.peaks is None or not model.get("index_topk"):
        return None
    from lib import kernel_costs_dsa
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    flops = nbytes = 0.0
    for pos, _budget, kk in ctx.facts["traced_rounds"]:
        for p in pos:
            fl, by = kernel_costs_dsa.sparse_attend(
                range(int(p) + 1, int(p) + 1 + int(kk)),
                model["index_topk"], model["n_heads"], latent,
                model["kv_lora_rank"])
            flops += fl * model["n_layers"]
            nbytes += by * model["n_layers"]
    least, bound = least_time_s(flops, nbytes, ctx.peaks)
    ctx.note(f"flash_decode over the selected rows: needs {flops:.4g} FLOP "
             f"and {nbytes:.4g} B, least {least:.5f}s ({bound}-bound), "
             f"took {took:.5f}s in "
             f"{ctx.reduced.kernel_calls.get('flash_decode')} calls")
    return 100.0 * least / took
