"""Model step: the share of the server's tracing seconds spent in traces
opened INSIDE another trace or lowering (a jitted callee, a primitive, a
kernel's body traced in the body of a ``scan`` / ``fori_loop`` / ``cond``),
which the chip's host pays about double (PERF.md §6, PR 32 -> 33):
100 x ``trace_nested_ns`` / (``trace_ns`` + ``trace_nested_ns``) over the
records of the program's build log (``rlo_tpu.utils.tracing.BUILDS``) that
began under a span of the server (``perf.serve.*``), read absolutely. The
note names the three functions with the most nested seconds. A program
without the log leaves the metric out."""

SERVER = "perf.serve."


def read(ctx):
    from rlo_tpu.utils import tracing
    log = getattr(tracing, "BUILDS", None)
    if log is None:
        return None
    inside = [r for r in log.records if (r.span or "").startswith(SERVER)]
    t = tracing.build_totals(inside)
    traced = t["trace_ns"] + t["trace_nested_ns"]
    if not traced:
        return None
    rows = sorted(tracing.build_table(inside),
                  key=lambda row: -row["trace_nested_s"])[:3]
    ctx.note("build log, server: most nested trace seconds in " + ", ".join(
        f"{row['fun_name']} {row['trace_nested_s']:.3f} s (own "
        f"{row['trace_s']:.3f} s, under {'/'.join(row['spans'])})"
        for row in rows))
    return 100.0 * t["trace_nested_ns"] / traced
