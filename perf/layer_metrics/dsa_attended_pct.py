"""Model step: latent rows the attends streamed for every 100 index keys
the selector scored. Counters over the untraced window:
``serve.dsa.latent_rows_read`` / ``serve.dsa.keys_scored``: about
100 x index_topk / context when the attend reads the selection, 100 when it
takes the selection as a mask over the whole context. The note gives the
selector's other counters for PERF.md. A program without them (the parent)
leaves the metric out."""


def read(ctx):
    c = ctx.counters
    scored = c.get("serve.dsa.keys_scored", 0)
    if not scored:
        return None
    ctx.note("token selector over the untraced window: " + ", ".join(
        f"{k} {c[k]}" for k in sorted(c) if k.startswith("serve.dsa.")))
    return 100.0 * c.get("serve.dsa.latent_rows_read", 0) / scored
