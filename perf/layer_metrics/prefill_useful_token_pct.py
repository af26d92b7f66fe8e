"""Server: share of the token positions the prefills ran that were
prompt tokens a prefill had to compute; the rest is padding up to the
prompt bucket. The program's own counters over the untraced window:
100 x ``serve.prefill_tokens`` / ``serve.prefill_padded_tokens``."""


def read(ctx):
    c = ctx.counters
    padded = c.get("serve.prefill_padded_tokens", 0)
    if not padded:
        return None
    return 100.0 * c.get("serve.prefill_tokens", 0) / padded
