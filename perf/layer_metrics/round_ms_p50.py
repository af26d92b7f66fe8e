"""Server: median host-clock time of one ``step_round()`` with its
admissions, over the untraced window's units."""
import statistics


def read(ctx):
    return 1e3 * statistics.median(dt for dt, _ in ctx.window.units)
