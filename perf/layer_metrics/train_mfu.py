"""Model step: model FLOP/s utilization, named for what it is: the FLOPs a
trained token requires (lib/kernel_costs.train_flops_per_token; recomputed
operations do not count) x tokens per second of the untraced window, over
the chip's published bf16 peak. Not a kernel's roofline share."""


def read(ctx):
    if ctx.peaks is None:
        return None
    return (100.0 * ctx.facts["flops_per_token"]
            * ctx.quantities["tokens_per_s"] / ctx.peaks.bf16_flops)
