"""Server: tokens a pass over a row's block yields, for rows that still owe
tokens. The program's own counters over the untraced window, counted on the
device pass by pass: ``serve.diffusion.tokens_committed`` /
``serve.diffusion.row_passes``. 0.8 is the floor of the published rule at a
block of 4 (4 denoise passes that unmask one position each, then the
commit); a trained model, or a commit fused into the next block's first
pass, reads higher. A program without the counters leaves the metric out.
The note gives the other counters for PERF.md."""


def read(ctx):
    c = ctx.counters
    passes = c.get("serve.diffusion.row_passes", 0)
    if not passes:
        return None
    ctx.note("block diffusion over the untraced window: " + ", ".join(
        f"{k} {c[k]}" for k in sorted(c)
        if k.startswith("serve.diffusion.")))
    return c.get("serve.diffusion.tokens_committed", 0) / passes
