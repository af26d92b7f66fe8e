"""Server: time the host is blocked, per admitted request, until that
request's prefill and scatter have finished on the device and its first
token is on the host. The program's own span total over the untraced
window: ``serve.admit.first_token_sync_ns`` / ``serve.admissions``."""


def read(ctx):
    c = ctx.counters
    admitted = c.get("serve.admissions", 0)
    if not admitted or "serve.admit.first_token_sync_ns" not in c:
        return None
    return c["serve.admit.first_token_sync_ns"] * 1e-6 / admitted
