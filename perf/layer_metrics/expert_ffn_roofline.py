"""Kernels: the least time the chip could take for the grouped expert FFNs
of the traced rounds, over the time ``expert_ffn`` took in the trace. Needed
(lib/kernel_costs_moe.expert_ffn): the weights of the experts HIT, once a
step and layer, and the rows that landed here in and out; from the program's
counters ``serve.moe.experts_hit`` and ``serve.moe.assignments_held`` over
the traced units. A dense product over every held expert, tile padding, and
weights streamed once per tile are not credited. The kernel also runs at
admission (the prefill's expert layers), which the counters do not count:
the share reads low by that much."""
from lib.peaks import least_time_s


def read(ctx):
    model = ctx.config.get("model", {})
    took = ctx.reduced.kernel_seconds.get("expert_ffn")
    c = ctx.facts.get("traced_counters") or {}
    if not took or ctx.peaks is None or not c.get("serve.moe.experts_hit"):
        return None
    from lib import kernel_costs_moe
    flops, nbytes = kernel_costs_moe.expert_ffn(
        c["serve.moe.experts_hit"], c["serve.moe.assignments_held"],
        model["d_model"], model["moe_d_ff"])
    least, bound = least_time_s(flops, nbytes, ctx.peaks)
    ctx.note(f"expert_ffn: {c['serve.moe.experts_hit']} expert-steps hit, "
             f"{c['serve.moe.assignments_held']} rows: needs {flops:.4g} "
             f"FLOP and {nbytes:.4g} B, least {least:.5f}s ({bound}-bound),"
             f" took {took:.5f}s in "
             f"{ctx.reduced.kernel_calls.get('expert_ffn')} calls")
    return 100.0 * least / took
