"""Server: seconds of set-up in which JAX obtained the server's executables,
by compiling them or by reading the persistent cache: ``compile_ns`` of the
records of the program's build log (``rlo_tpu.utils.tracing.BUILDS``) that
began under a span of the server (``perf.serve.*``), read absolutely. Small
on a warm cache; on a cold one most of what a machine's first start costs
beyond a later one. The note gives the programs, the cache's hits and misses
and the five costliest programs with the stage each was obtained in. A
program without the log leaves the metric out."""

SERVER = "perf.serve."


def read(ctx):
    from rlo_tpu.utils import tracing
    log = getattr(tracing, "BUILDS", None)
    if log is None:
        return None
    inside = [r for r in log.records if (r.span or "").startswith(SERVER)]
    t = tracing.build_totals(inside)
    if not t["programs"]:
        return None
    rows = sorted(tracing.build_table(inside),
                  key=lambda row: -row["compile_s"])[:5]
    ctx.note(
        f"build log, server: {t['programs']} programs, cache hits "
        f"{t['cache_hits']} misses {t['cache_misses']}; the costliest "
        + ", ".join(f"{row['fun_name']} {row['compile_s']:.3f} s "
                    f"({'/'.join(row['spans'])})" for row in rows))
    return t["compile_ns"] / 1e9
