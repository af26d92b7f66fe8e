"""Server: host time of admission per admitted request, the first-token
sync included. The program's own span total and count over the untraced
window: ``serve.admit_ns`` / ``serve.admissions``."""


def read(ctx):
    c = ctx.counters
    admitted = c.get("serve.admissions", 0)
    if not admitted or "serve.admit_ns" not in c:
        return None
    return c["serve.admit_ns"] * 1e-6 / admitted
