"""Model step: share of the layer-steps that chose a token set in which the
layer took the set a full layer before it chose for the same query (a
shared layer of ``index_types``) instead of scoring its own. Counters over
the untraced window: ``serve.dsa.selections_reused`` / (``reused`` +
``serve.dsa.selections_scored``), the layers of each kind as the server's
traced round recorded its selections: a layer whose selection scored counts
as scored, one that took a full layer's set as reused. 3 shared layers of 5
read 60; anything lower means a shared layer scored. A program without the
counters (the parent) leaves the metric out."""


def read(ctx):
    c = ctx.counters
    reused = c.get("serve.dsa.selections_reused", 0)
    scored = c.get("serve.dsa.selections_scored", 0)
    if not reused + scored:
        return None
    ctx.note(f"token selections over the untraced window: {scored} scored, "
             f"{reused} reused (layer-steps)")
    return 100.0 * reused / (reused + scored)
