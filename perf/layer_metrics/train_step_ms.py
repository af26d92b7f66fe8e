"""Model step: mean device time of the step's module
(``jit_train_step_optax`` on the trace's ``XLA Modules`` line)."""


def read(ctx):
    steps = ctx.reduced.module_ms.get("jit_train_step_optax")
    return sum(steps) / len(steps) if steps else None
