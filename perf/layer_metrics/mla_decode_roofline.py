"""Kernels: the least time the chip could take for the traced rounds'
latent attends (``flash_decode`` on a latent cache: one 576-wide stream a
row that all heads attend, values its leading 512 features), over the time
the kernel took in the trace. Needed work is that of the LIVE contexts of
rows that still owe tokens (lib/kernel_costs_moe.mla_decode), as
``flash_decode_roofline`` counts: not of ``max_len``, not of whole tiles."""
from lib.peaks import least_time_s


def read(ctx):
    model = ctx.config.get("model", {})
    took = ctx.reduced.kernel_seconds.get("flash_decode")
    if not took or ctx.peaks is None or not model.get("kv_lora_rank"):
        return None
    from lib import kernel_costs_moe
    latent = model["kv_lora_rank"] + model["qk_rope_head_dim"]
    flops = nbytes = 0.0
    for pos, budget, kk in ctx.facts["traced_rounds"]:
        for p, b in zip(pos, budget):
            steps = int(min(b, kk))
            fl, by = kernel_costs_moe.mla_decode(
                range(int(p) + 1, int(p) + 1 + steps), model["n_heads"],
                latent, model["kv_lora_rank"])
            flops += fl * model["n_layers"]
            nbytes += by * model["n_layers"]
    least, bound = least_time_s(flops, nbytes, ctx.peaks)
    ctx.note(f"flash_decode (latent): needs {flops:.4g} FLOP and "
             f"{nbytes:.4g} B, least {least:.5f}s ({bound}-bound), took "
             f"{took:.5f}s in "
             f"{ctx.reduced.kernel_calls.get('flash_decode')} calls")
    return 100.0 * least / took
