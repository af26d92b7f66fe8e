"""rlo-scope + collective instrumentation (docs/DESIGN.md §21).

Four contracts:

  1. **Measured equals predicted**: an instrumented sim-substrate
     allreduce produces exactly the ledger's step identities, send
     counts, and payload bytes — zero findings, exit 0.

  2. **Bit-for-bit reproducibility**: the full ``--json`` report is a
     pure function of (schedule, n, nbytes, seed).

  3. **Disabled path**: an uninstrumented ``Comm`` emits nothing and
     leaves the SimWorld delivery schedule (digest, event count,
     virtual span) byte-identical to the instrumented run — probes
     observe, they never perturb.  The always-on counters still count.

  4. **Trace-time hooks**: ``tpu_collectives.set_step_hook`` fires
     once per Python-unrolled schedule step during jax tracing, in
     ledger order, and restores cleanly.

Plus the timeline contract: STEP events render as ``cat: coll``
Chrome slices with per-hop flow edges, and the merged trace stays
schema-valid.
"""

import json

import numpy as np
import pytest

from rlo_tpu.observe.ledger import ledger
from rlo_tpu.ops.collectives import Comm
from rlo_tpu.tools import rlo_scope
from rlo_tpu.tools.rlo_scope import analyze, run_sim_collective
from rlo_tpu.transport.sim import SimWorld
from rlo_tpu.utils.timeline import (merge_timeline, trace_stats,
                                    validate_chrome_trace)
from rlo_tpu.utils.tracing import Tracer

N = 4
NBYTES = 4096


def _analyze(run):
    return analyze(run["events"], run["schedule"], run["nbytes"],
                   measured_steps=run["coll_steps"],
                   measured_bytes=run["coll_bytes"],
                   min_delay_usec=run["min_delay_usec"],
                   result_correct=run["result_correct"])


# ---------------------------------------------------------------------------
# 1. measured == predicted
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("schedule", rlo_scope.SIM_SCHEDULES)
def test_instrumented_sim_run_matches_ledger(schedule):
    run = run_sim_collective(schedule, N, NBYTES, seed=0)
    led = ledger(schedule, N, NBYTES)
    assert run["result_correct"]
    # one STEP event per (rank, ledger step); counters agree exactly
    assert len(run["events"]) == N * led.num_steps
    assert run["coll_steps"] == [led.num_steps] * N
    assert run["coll_bytes"] == led.sent_bytes_by_rank()
    assert sum(run["coll_bytes"]) == led.total_bytes

    report, findings = _analyze(run)
    assert findings == []
    assert report["measured"]["ops"] == 1
    assert report["bus_fraction"] is not None
    assert [(r["algorithm"], r["step"]) for r in report["steps"]] == \
        sorted((s.algorithm, s.index) for s in led.steps)
    assert report["ledger"]["digest"] == led.digest()


def test_render_covers_every_step():
    run = run_sim_collective("ring_allreduce", N, NBYTES, seed=0)
    report, _ = _analyze(run)
    text = rlo_scope.render(report)
    assert "bus utilisation" in text
    for row in report["steps"]:
        assert f"{row['algorithm']}:{row['step']}" in text


# ---------------------------------------------------------------------------
# 2. bit-for-bit reproducibility
# ---------------------------------------------------------------------------

def test_report_is_bit_for_bit_reproducible():
    docs = []
    for _ in range(2):
        report, findings = _analyze(
            run_sim_collective("ring_allreduce", N, NBYTES, seed=7))
        assert findings == []
        docs.append(json.dumps(report, sort_keys=True))
    assert docs[0] == docs[1]
    # ...and a different seed moves the measured timings, not the join
    other, _ = _analyze(
        run_sim_collective("ring_allreduce", N, NBYTES, seed=8))
    assert json.dumps(other, sort_keys=True) != docs[0]
    assert other["ledger"] == json.loads(docs[0])["ledger"]


def test_cli_json_is_reproducible_and_clean(capsys):
    argv = ["--schedule", "recursive_doubling", "--n", str(N),
            "--nbytes", str(NBYTES), "--seed", "0", "--json"]
    outs = []
    for _ in range(2):
        assert rlo_scope.main(argv) == 0
        outs.append(capsys.readouterr().out)
    assert outs[0] == outs[1]
    doc = json.loads(outs[0])
    assert doc["findings"] == []
    assert doc["seed"] == 0 and "sim_schedule_digest" in doc


def test_cli_rejects_bad_invocations(capsys):
    assert rlo_scope.main(["--schedule", "nope", "--json"]) == 2
    assert rlo_scope.main(["--n", "1", "--json"]) == 2
    capsys.readouterr()


# ---------------------------------------------------------------------------
# findings fire on contract violations
# ---------------------------------------------------------------------------

def test_findings_fire_on_drift():
    run = run_sim_collective("ring_allreduce", N, NBYTES, seed=0)

    # S1: a dropped step (instrumentation lost events)
    pruned = dict(run)
    pruned["events"] = [e for e in run["events"]
                        if e["c"] % 1024 != 0 or e["a"] != 2]
    _, findings = _analyze(pruned)
    assert any(f.rule == "S1" and "no measured" in f.msg
               for f in findings)

    # S1: counter drift on one rank
    bad = dict(run)
    bad["coll_steps"] = [run["coll_steps"][0] + 1] + \
        run["coll_steps"][1:]
    _, findings = _analyze(bad)
    assert any(f.rule == "S1" and "coll_steps" in f.msg
               for f in findings)

    # S2: byte drift
    bad = dict(run)
    bad["coll_bytes"] = [run["coll_bytes"][0] - 4] + \
        run["coll_bytes"][1:]
    _, findings = _analyze(bad)
    assert any(f.rule == "S2" for f in findings)

    # S3: wrong reduction
    bad = dict(run)
    bad["result_correct"] = False
    _, findings = _analyze(bad)
    assert any(f.rule == "S3" for f in findings)


# ---------------------------------------------------------------------------
# 3. disabled path: observe, never perturb
# ---------------------------------------------------------------------------

def _drive(seed, instrument):
    world = SimWorld(N, seed=seed)
    comms = [Comm(world.transport(r)) for r in range(N)]
    tracer = Tracer(enabled=True)
    if instrument:
        for c in comms:
            c.instrument(world.clock, tracer)
    xs = [np.full(NBYTES // 4, float(r + 1), dtype=np.float32)
          for r in range(N)]
    coros = [c.allreduce(x, algorithm="ring")
             for c, x in zip(comms, xs)]
    results = [None] * N
    alive = set(range(N))
    while alive:
        for i in list(alive):
            try:
                next(coros[i])
            except StopIteration as e:
                results[i] = e.value
                alive.discard(i)
        if alive:
            world.step()
    return world, comms, tracer, results


def test_uninstrumented_run_is_silent_and_unperturbed():
    w_on, c_on, t_on, r_on = _drive(seed=3, instrument=True)
    w_off, c_off, t_off, r_off = _drive(seed=3, instrument=False)
    # no probe -> zero events collected
    assert len(t_off.events()) == 0
    assert len(t_on.events()) == N * ledger("ring_allreduce", N,
                                            NBYTES).num_steps
    # the delivery schedule is byte-identical: probes never send
    assert w_off.schedule_digest() == w_on.schedule_digest()
    assert w_off.events == w_on.events
    assert w_off.now == w_on.now
    # the always-on counters count either way
    assert [c.coll_steps for c in c_off] == \
        [c.coll_steps for c in c_on]
    assert [c.coll_bytes for c in c_off] == \
        [c.coll_bytes for c in c_on]
    for a, b in zip(r_on, r_off):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# timeline: coll slices + flow edges
# ---------------------------------------------------------------------------

def test_timeline_renders_coll_slices_and_flows():
    run = run_sim_collective("ring_allreduce", N, NBYTES, seed=0)
    trace = merge_timeline([run["events"]])
    validate_chrome_trace(trace)
    slices = [e for e in trace["traceEvents"]
              if e.get("cat") == "coll"]
    assert len(slices) == len(run["events"])
    # every received hop gets a sender-start -> receiver-end edge
    starts = [e for e in trace["traceEvents"]
              if e.get("cat") == "coll_flow" and e.get("ph") == "s"]
    finishes = [e for e in trace["traceEvents"]
                if e.get("cat") == "coll_flow" and e.get("ph") == "f"]
    assert len(starts) == len(finishes) == len(slices)
    stats = trace_stats(trace)
    per_alg = {}
    for r in stats["ranks"].values():
        for alg, slot in r["coll"].items():
            per_alg[alg] = per_alg.get(alg, 0) + slot["count"]
    assert sum(per_alg.values()) == len(slices)
    assert set(per_alg) == {"ring_reduce_scatter", "ring_all_gather"}


# ---------------------------------------------------------------------------
# 4. trace-time step hooks (jax executor)
# ---------------------------------------------------------------------------

def test_tpu_step_hook_fires_in_ledger_order():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.ops import tpu_collectives
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit

    mesh = make_mesh((N,), ("x",))
    x = jnp.ones((N, 64), jnp.float32)

    for alg, phases in [
            ("recursive_doubling", ("recursive_doubling",)),
            ("halving_doubling", ("halving_reduce_scatter",
                                  "doubling_all_gather"))]:
        calls = []
        prev = tpu_collectives.set_step_hook(
            lambda a, s, ws, _c=calls: _c.append((a, s, ws)))
        try:
            fn = shard_jit(
                lambda v, _a=alg: tpu_collectives.allreduce(
                    x=v, axis="x", algorithm=_a),
                mesh, P("x"), P(), check_vma=False)
            fn.lower(x)  # trace only — hooks are trace-time
        finally:
            assert tpu_collectives.set_step_hook(prev) is not None
        led = ledger(alg, N, 64 * N * 4)
        want = [(s.algorithm, None, N) for s in led.steps]
        assert [(a, None, ws) for a, _s, ws in calls] == want
        # per-phase step indices restart at 0 and ascend
        for phase in phases:
            idxs = [s for a, s, _ in calls if a == phase]
            assert idxs == list(range(len(idxs)))


def test_tpu_step_hook_fires_for_bcast():
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.ops import tpu_collectives
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit

    mesh = make_mesh((N,), ("x",))
    x = jnp.ones((N, 8), jnp.float32)

    calls = []
    prev = tpu_collectives.set_step_hook(
        lambda a, s, ws: calls.append((a, s, ws)))
    try:
        fn = shard_jit(
            lambda v: tpu_collectives.rootless_bcast(
                v, origin=0, axis="x", schedule="binomial"),
            mesh, P("x"), P("x"), check_vma=False)
        fn.lower(x)
    finally:
        tpu_collectives.set_step_hook(prev)
    led = ledger("binomial_bcast", N, 8 * 4, origin=0)
    assert calls == [("binomial_bcast", i, N)
                     for i in range(led.num_steps)]
