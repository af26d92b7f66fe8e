"""Kernels: the least time the chip could take for the traced rounds' block
attends (``flash_block_decode``: every query of a row's block of
``block_len`` positions against everything before the block and the block
itself), over the time the kernel took in the trace. Needed work is that of
the blocks of rows that still owe tokens, pass by pass
(lib/kernel_costs_diffusion.block_attend): a row's keys and values once for
all its ``block_len`` x heads query rows; not ``max_len``, not whole tiles.
Never clipped. A program without the kernel, or a runner that kept no
contexts, leaves the metric out."""
from lib.peaks import least_time_s


def read(ctx):
    took = ctx.reduced.kernel_seconds.get("flash_block_decode")
    contexts = ctx.facts.get("block_contexts")
    if not took or ctx.peaks is None or not contexts:
        return None
    from lib import kernel_costs_diffusion
    f = ctx.facts
    flops = nbytes = 0.0
    for ctx_of_rows in contexts:        # one entry a traced pass
        fl, by = kernel_costs_diffusion.block_attend(
            ctx_of_rows.tolist(), f["n_heads"], f["kv_heads"],
            f["head_dim"], f["block_len"])
        flops += fl * f["n_layers"]
        nbytes += by * f["n_layers"]
    least, bound = least_time_s(flops, nbytes, ctx.peaks)
    ctx.note(f"flash_block_decode: {len(contexts)} passes, needs "
             f"{flops:.4g} FLOP and {nbytes:.4g} B, least {least:.5f}s "
             f"({bound}-bound), took {took:.5f}s in "
             f"{ctx.reduced.kernel_calls.get('flash_block_decode')} calls")
    return 100.0 * least / took
