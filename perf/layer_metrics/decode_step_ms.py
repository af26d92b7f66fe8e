"""Model step: device time of the server's jitted round
(``jit_round_fn`` on the trace's ``XLA Modules`` line) per decode step."""


def read(ctx):
    rounds = ctx.reduced.module_ms.get("jit_round_fn")
    steps = sum(kk for _pos, _budget, kk in ctx.facts["traced_rounds"])
    if not rounds or not steps:
        return None
    return sum(rounds) / steps
