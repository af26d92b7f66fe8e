"""Server: share of the slot-steps the rounds computed that produced a
token a request asked for. Counters over the untraced window:
(``serve.tokens_out`` - one first token per admission, which prefill made)
/ (``serve.steps`` x ``n_slots``). A row that finishes mid-round decodes
garbage to the round's end."""


def read(ctx):
    c = ctx.counters
    steps = c.get("serve.steps", 0) * ctx.facts["n_slots"]
    if not steps:
        return None
    # each admission produced exactly one token outside the rounds
    return 100.0 * (c["serve.tokens_out"] - c["perf.admitted"]) / steps
