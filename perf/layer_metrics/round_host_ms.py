"""Server: host time of one round that is not the wait for the device's
round. The program's own span totals over the untraced window:
(``serve.step_round_ns`` - ``serve.round.wait_ns``) / ``serve.rounds``.
What is left of a unit beside it is the runner's ``top_up`` and
``harvest``. A program without the spans has no such counters. The note
gives every stage's share of a round, for PERF.md's "where the time goes"."""


def read(ctx):
    c = ctx.counters
    rounds = c.get("serve.rounds", 0)
    if not rounds or "serve.step_round_ns" not in c:
        return None
    stages = {k[len("serve."):-len("_ns")]: (v, c.get(k[:-3] + "_n", 0))
              for k, v in c.items()
              if k.startswith("serve.") and k.endswith("_ns")}
    ctx.note("server stages over the untraced window, ms a round (calls a "
             "round): " + ", ".join(
                 f"{name} {ns * 1e-6 / rounds:.3f} ({n / rounds:.2f})"
                 for name, (ns, n) in sorted(stages.items())))
    ctx.note("server work counters over the untraced window: " + ", ".join(
        f"{k} {c[k]}" for k in sorted(c) if k.startswith((
            "serve.admissions", "serve.prefill_", "serve.slot_steps",
            "serve.retraces", "serve.rounds"))))
    return (c["serve.step_round_ns"]
            - c.get("serve.round.wait_ns", 0)) * 1e-6 / rounds
