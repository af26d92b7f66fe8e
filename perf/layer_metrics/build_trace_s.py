"""Model step: seconds of set-up the host spent TRACING and LOWERING the
server's programs, each second once: the Python of the model's layers, the
kernels' bodies and the lowering rules, which no cache keeps. From the
program's own build log (``rlo_tpu.utils.tracing.BUILDS``), read
absolutely, because set-up is over when the window begins: the records that
began under a span of the server (``perf.serve.*``), their own
``trace_ns`` and ``lower_ns`` and the traces nested in them
(``trace_nested_ns``). The harness's ``jaxpr_trace_s + to_mlir_s`` adds a
nested trace to every trace around it and reads higher. The note gives what
was built OUTSIDE the server (the harness's weights, reference check and
kernel check), so that the two add up. A program without the log leaves the
metric out."""

SERVER = "perf.serve."


def read(ctx):
    from rlo_tpu.utils import tracing
    log = getattr(tracing, "BUILDS", None)
    if log is None:
        return None
    inside, outside = [], []
    for r in log.records:
        (inside if (r.span or "").startswith(SERVER) else outside).append(r)
    if not inside:
        return None
    t, o = tracing.build_totals(inside), tracing.build_totals(outside)
    ctx.note(
        f"build log, server: {len(inside)} roots, trace "
        f"{t['trace_ns'] / 1e9:.3f} s own + {t['trace_nested_ns'] / 1e9:.3f} "
        f"s nested, lower {t['lower_ns'] / 1e9:.3f} s; outside the server: "
        f"{len(outside)} roots, trace {o['trace_ns'] / 1e9:.3f} s own + "
        f"{o['trace_nested_ns'] / 1e9:.3f} s nested, lower "
        f"{o['lower_ns'] / 1e9:.3f} s, compile {o['compile_ns'] / 1e9:.3f} s "
        f"for {o['programs']} programs, the costliest "
        + ", ".join(f"{row['fun_name']} {row['total_s']:.3f} s"
                    for row in tracing.build_table(outside)[:3])
        + f"; {log.events} listener calls")
    return (t["trace_ns"] + t["trace_nested_ns"] + t["lower_ns"]) / 1e9
