"""Model step: share of the rows the grouped expert product computed that
were assignments a token made to an expert held here. Counters over the
untraced window: ``serve.moe.assignments_held`` / ``serve.moe.rows_computed``
(rows of live tiles, tile padding included). The note gives the other
expert-layer counters for PERF.md."""


def read(ctx):
    c = ctx.counters
    rows = c.get("serve.moe.rows_computed", 0)
    if not rows:
        return None
    ctx.note("expert layers over the untraced window: " + ", ".join(
        f"{k} {c[k]}" for k in sorted(c) if k.startswith("serve.moe.")))
    return 100.0 * c["serve.moe.assignments_held"] / rows
