"""Collectives: median device time of one call of the library's allreduce
module (``jit_allreduce_<algorithm>`` on ``XLA Modules``, every chip)."""
import statistics


def read(ctx):
    calls = ctx.reduced.module_ms.get(
        f"jit_allreduce_{ctx.facts['algorithm']}")
    return statistics.median(calls) if calls else None
