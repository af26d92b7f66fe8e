"""Tracing + metrics: Python and C engines emit the same streams.

The reference has no tracing (SURVEY.md §5); the rebuild's oracle is
cross-implementation: the identical scenario (one bcast + one vetoed IAR
round on the same world size) must produce the same multiset of protocol
events — AND the same metrics-registry snapshot (counter keys identical,
deterministic values equal) — from the Python engine and the native C
core, and the jax.profiler integration must annotate device work without
error.
"""

import copy
from collections import Counter

import pytest

from rlo_tpu.engine import ProgressEngine, EngineManager, drain
from rlo_tpu.native import bindings as nb
from rlo_tpu.transport.loopback import LoopbackWorld
from rlo_tpu.utils.tracing import TRACER, Ev, Tracer, annotate

WS = 8


def run_python_scenario(metrics: bool = False):
    """One bcast from rank 2 + one vetoed proposal from rank 0."""
    world = LoopbackWorld(WS)
    mgr = EngineManager()
    engines = [ProgressEngine(
        world.transport(r),
        judge_cb=lambda payload, ctx, r=r: 0 if r == WS - 1 else 1,
        manager=mgr) for r in range(WS)]
    if metrics:
        for e in engines:
            e.enable_metrics()
    engines[2].bcast(b"hello")
    drain([world], engines)
    for e in engines:
        while e.pickup_next() is not None:
            pass
    engines[0].submit_proposal(b"prop", pid=0)
    drain([world], engines)
    snaps = [e.metrics() for e in engines]
    for e in engines:
        e.cleanup()
    return snaps


def run_native_scenario(metrics: bool = False):
    with nb.NativeWorld(WS) as world:
        engines = [nb.NativeEngine(
            world, r,
            judge_cb=lambda payload, ctx, r=r: 0 if r == WS - 1 else 1)
            for r in range(WS)]
        if metrics:
            for e in engines:
                e.enable_metrics()
        engines[2].bcast(b"hello")
        world.drain()
        for e in engines:
            while e.pickup_next() is not None:
                pass
        rc = engines[0].submit_proposal(b"prop", pid=0)
        if rc == -1:
            world.drain()
        return [e.metrics() for e in engines]


def python_event_counts():
    TRACER.clear()
    with TRACER.enable():
        run_python_scenario()
    counts = Counter(e.kind.name for e in TRACER.events())
    TRACER.clear()
    return counts


def native_event_counts():
    nb.trace_clear()
    nb.trace_set(True)
    try:
        run_native_scenario()
    finally:
        nb.trace_set(False)
    events = nb.trace_drain()
    return Counter(e["kind"] for e in events)


def test_python_and_native_emit_identical_streams():
    py = python_event_counts()
    nat = native_event_counts()
    assert py == nat, (py, nat)
    # structural sanity: three initiations (payload bcast + proposal
    # bcast + decision bcast — all ride the rootless broadcast path)
    assert py["BCAST_INIT"] == 3
    assert py["PROPOSAL_SUBMIT"] == 1
    assert py["DECISION"] == 1
    # every non-origin rank picked up the payload bcast (decisions stay
    # queued — the scenario never drains pickups after the IAR round)
    assert py["DELIVER"] == WS - 1
    # every non-proposer judged the proposal (the veto rank too)
    assert py["JUDGE"] == WS - 1


def _scrub_timing(snap):
    """Zero the wall-clock-dependent metric fields (histogram
    sum/min/max/bucket spread, RTT EWMA) so snapshots compare on the
    deterministic parts; every KEY stays, so schema parity is asserted
    in full."""
    snap = copy.deepcopy(snap)
    for link in snap["links"].values():
        link["rtt_ewma_usec"] = 0.0
    for h in snap["op_latency_usec"].values():
        h["sum"] = h["min"] = h["max"] = 0.0
        h["buckets"] = [0] * len(h["buckets"])
    return snap


def test_python_and_native_report_identical_metrics():
    """Metrics parity (the registry face of the event-parity oracle):
    same scenario -> identical counter keys AND matching deterministic
    values — per-link frame/byte counts, ARQ counters, queue depths,
    histogram counts — from both engines. Only wall-clock-derived
    fields (latency sums/extremes, RTT EWMA) are exempt."""
    py = [_scrub_timing(s) for s in run_python_scenario(metrics=True)]
    nat = [_scrub_timing(s) for s in run_native_scenario(metrics=True)]
    for r in range(WS):
        assert py[r] == nat[r], (r, py[r], nat[r])
    # structural sanity: rank 2's bcast fan-out was accounted, every
    # rank delivered it, and the histograms saw the ops complete
    assert py[2]["counters"]["sent_bcast"] == 1
    assert py[2]["op_latency_usec"]["bcast_complete"]["count"] == 1
    assert py[0]["op_latency_usec"]["proposal_resolve"]["count"] == 1
    for r in range(WS):
        if r == 2:
            continue
        assert py[r]["op_latency_usec"]["pickup_wait"]["count"] >= 1
        total_rx = sum(l["rx_frames"] for l in py[r]["links"].values())
        assert total_rx >= 1


def test_metrics_disabled_schema_is_stable():
    """metrics() with collection off returns the same keys (zeros in
    the gated sections) — dashboards need one schema, not two."""
    on = run_python_scenario(metrics=True)[0]
    off = run_python_scenario(metrics=False)[0]

    def keys(d, prefix=""):
        out = set()
        for k, v in d.items():
            out.add(f"{prefix}{k}")
            if isinstance(v, dict):
                out |= keys(v, f"{prefix}{k}.")
        return out

    assert keys(on) == keys(off)
    assert all(l["tx_frames"] == 0 for l in off["links"].values())
    # counters are always live — they predate the registry
    assert off["counters"]["sent_bcast"] == on["counters"]["sent_bcast"]


def test_tracer_rings_report_dropped_consistently():
    """Overflow accounting satellite: both rings at capacity report
    `dropped` with the same semantics — emitted minus capacity — and
    keep exactly `capacity` newest events."""
    # Python ring (capacity is a constructor knob)
    cap, extra = 64, 9
    t = Tracer(capacity=cap)
    with t.enable():
        for i in range(cap + extra):
            t.emit(0, Ev.DELIVER, i)
    assert t.dropped == extra
    evs = t.events()
    assert len(evs) == cap
    assert [e.a for e in evs] == list(range(extra, cap + extra))

    # C ring (fixed capacity, same overwrite-oldest semantics)
    ccap = nb.trace_capacity()
    nb.trace_clear()
    nb.trace_set(True)
    try:
        for i in range(ccap + extra):
            nb.trace_emit(0, int(Ev.DELIVER), i)
    finally:
        nb.trace_set(False)
    assert nb.trace_dropped() == extra
    evs = nb.trace_drain(ccap + extra)
    assert len(evs) == ccap
    assert evs[0]["a"] == extra and evs[-1]["a"] == ccap + extra - 1
    nb.trace_clear()


def test_tracer_disabled_emits_nothing():
    t = Tracer()
    t.emit(0, Ev.BCAST_INIT, 1, 2)
    assert t.events() == []


def test_tracer_ring_drops_oldest():
    t = Tracer(capacity=4)
    with t.enable():
        for i in range(10):
            t.emit(i, Ev.DELIVER)
    assert len(t.events()) == 4
    assert t.dropped == 6
    assert [e.rank for e in t.events()] == [6, 7, 8, 9]


def test_dump_jsonl(tmp_path):
    t = Tracer()
    with t.enable():
        t.emit(1, Ev.VOTE, 5, 1)
    path = tmp_path / "trace.jsonl"
    assert t.dump_jsonl(str(path)) == 1
    import json
    rec = json.loads(path.read_text().strip())
    assert rec["kind"] == "VOTE" and rec["rank"] == 1 and rec["a"] == 5


def test_profiler_annotation_smoke():
    import jax.numpy as jnp
    with annotate("rlo-allreduce"):
        x = jnp.ones((8, 8)) @ jnp.ones((8, 8))
    assert float(x[0, 0]) == 8.0


def test_annotate_totals_into_a_registry_and_hands_out_its_bracket():
    """The one span helper: elapsed perf_counter_ns into ``<c>_ns``,
    one into ``<c>_n``, the bracket in perf_counter seconds to
    ``emit`` — also when the body raises."""
    import time

    from rlo_tpu.utils.metrics import Registry

    reg = Registry()
    got = []
    t_before = time.perf_counter()
    for _ in range(3):
        with annotate("perf.stage.x", reg, "stage.x",
                      lambda t0, t1: got.append((t0, t1)), rid=4):
            time.sleep(0.002)
    with pytest.raises(KeyError):
        with annotate("perf.stage.x", reg, "stage.x"):
            raise KeyError("body")
    t_after = time.perf_counter()
    c = reg.snapshot()["counters"]
    assert c["stage.x_n"] == 4
    assert 3 * 2_000_000 <= c["stage.x_ns"] <= (t_after - t_before) * 1e9
    assert len(got) == 3
    assert all(t_before <= t0 <= t1 <= t_after for t0, t1 in got)
    assert sum(t1 - t0 for t0, t1 in got) * 1e9 <= c["stage.x_ns"] + 1e3
    # the counter defaults to the span's name; no registry, no counters
    with annotate("plain", reg):
        pass
    with annotate("nowhere"):
        pass
    assert reg.snapshot()["counters"]["plain_n"] == 1
    assert "nowhere_n" not in reg.snapshot()["counters"]


def test_tracing_module_imports_without_jax():
    """The engine stack imports utils/tracing.py without JAX: the
    profiler import, and the build log's listeners, wait for the first
    span."""
    import subprocess
    import sys
    code = ("import sys; sys.modules['jax'] = None; "
            "import rlo_tpu.utils.tracing as t; "
            "assert t._TraceAnnotation is None; "
            "assert not t.BUILDS._armed and not t.BUILDS.records; "
            "t.Tracer().emit(0, t.Ev.VOTE); print('ok')")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


# ---- the build log -------------------------------------------------------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"


def test_build_log_folds_nested_events_into_their_root(monkeypatch):
    """The listeners on a clock worked by hand: a trace with a trace
    nested two deep and an eager compile inside it is ONE record whose
    nanoseconds, by phase, add up to its interval; an exit that never
    entered (the log armed inside it) is dropped."""
    from rlo_tpu.utils import tracing
    from rlo_tpu.utils.metrics import Registry

    clock = iter([0, 10, 12, 15, 30, 40, 70, 100, 200, 300, 350])
    monkeypatch.setattr(tracing.time, "perf_counter_ns",
                        lambda: next(clock))
    log = tracing.BuildLog()
    reg = Registry()
    span = annotate("perf.x.stage", reg, "x.stage")
    monkeypatch.setattr(tracing._LOCAL, "spans", [span], raising=False)
    log._on_exit(_TRACE, 0.0, 0.0, fun_name="never_entered")    # t=0
    log._on_enter(_TRACE, 0.0, fun_name="outer")                # 10
    log._on_enter(_TRACE, 0.0, fun_name="inner")                # 12
    log._on_enter("/jax/other", 0.0)            # not a phase: no clock
    log._on_enter(_TRACE, 0.0, fun_name="sin")                  # 15
    log._on_exit(_TRACE, 0.0, 0.0, fun_name="sin")              # 30
    log._on_exit(_TRACE, 0.0, 0.0, fun_name="inner")            # 40
    log._on_enter(_COMPILE, 0.0, fun_name="jit(iota)")          # 70
    log._on_cache("/jax/compilation_cache/cache_misses")
    log._on_exit(_COMPILE, 0.0, 0.0, fun_name="jit(iota)")      # 100
    assert not log.records                      # outer is still open
    log._on_exit(_TRACE, 0.0, 0.0, fun_name="outer")            # 200
    log._on_cache("/jax/compilation_cache/cache_hits")  # none open
    log._on_enter(_COMPILE, 0.0, fun_name="jit(outer)")         # 300
    log._on_cache("/jax/compilation_cache/cache_hits")
    log._on_exit(_COMPILE, 0.0, 0.0, fun_name="jit(outer)")     # 350
    first, second = log.records
    assert (first.fun_name, first.phase, first.span, first.t0,
            first.t1) == ("outer", "trace", "perf.x.stage", 10, 200)
    # inner 12..40 less sin's 15 = 13, sin 15; iota 30; the rest outer's
    assert first.counts == {
        "programs": 1, "trace_ns": 190 - 28 - 30, "trace_nested_ns": 28,
        "lower_ns": 0, "compile_ns": 30, "cache_hits": 0,
        "cache_misses": 1}
    assert (second.fun_name, second.phase, second.counts["compile_ns"],
            second.counts["cache_hits"]) == ("outer", "compile", 50, 1)
    assert log.events == 10
    c = reg.snapshot()["counters"]
    assert {k: v for k, v in c.items() if k.startswith("x.build.")} == {
        "x.build." + k: first.counts[k] + second.counts[k]
        for k in tracing.BUILD_COUNTS}
    assert tracing.build_totals(log.records)["programs"] == 2
    (row,) = tracing.build_table(log.records)
    assert row == {
        "fun_name": "outer", "calls": 1, "programs": 2,
        "trace_s": 132e-9, "trace_nested_s": 28e-9, "lower_s": 0.0,
        "compile_s": 80e-9, "total_s": 240e-9, "cache_hits": 1,
        "cache_misses": 1, "spans": ["perf.x.stage"]}


def test_build_log_records_one_root_for_a_jit_that_calls_a_jit():
    """A jitted ``outer`` that calls a jitted ``inner`` in a fori_loop
    body, under a span: one trace root, ``outer``'s, whose own and
    nested nanoseconds add up to its interval, the nested part no less
    than JAX's own duration of ``inner``'s trace; every root lies
    inside the span, and the span's registry counts the programs an
    independent listener saw compiled."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from rlo_tpu.utils.metrics import Registry
    from rlo_tpu.utils.tracing import BUILDS, build_table

    seen = {"inner_s": [], "compiles": 0}

    def listen(event, secs, fun_name="", **_kw):
        if event == _TRACE and fun_name == "build_log_inner":
            seen["inner_s"].append(secs)
        seen["compiles"] += event == _COMPILE

    @jax.jit
    def build_log_inner(x):
        return jnp.sin(x) * 2

    @jax.jit
    def build_log_outer(x):
        return lax.fori_loop(0, 3, lambda i, c: build_log_inner(c) + 1, x)

    reg = Registry()
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with annotate("perf.t.build", reg, "t.stage"):
            x = jnp.ones((4,)) + 1      # eager programs are roots too
            build_log_outer(x).block_until_ready()
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    mine = [r for r in BUILDS.records if r.metrics is reg]
    assert mine and all(r.span == "perf.t.build" for r in mine)
    assert all(r.t1 - r.t0 == sum(n for k, n in r.counts.items()
                                  if k.endswith("_ns")) for r in mine)
    assert [r.phase for r in mine
            if r.fun_name == "build_log_outer"] == ["trace", "lower",
                                                    "compile"]
    assert not [r for r in mine if r.fun_name == "build_log_inner"]
    root = next(r for r in mine if r.fun_name == "build_log_outer")
    assert root.counts["trace_ns"] > 0
    # the two clocks are read a listener call apart
    (inner_s,) = seen["inner_s"]
    assert root.counts["trace_nested_ns"] >= 0.9 * inner_s * 1e9
    c = reg.snapshot()["counters"]
    assert sum(r.t1 - r.t0 for r in mine) <= c["t.stage_ns"]
    assert c["t.build.programs"] == seen["compiles"] >= 2
    assert c["t.build.trace_ns"] + c["t.build.trace_nested_ns"] \
        + c["t.build.lower_ns"] + c["t.build.compile_ns"] == \
        sum(r.t1 - r.t0 for r in mine)
    table = build_table(mine)
    row = next(r for r in table if r["fun_name"] == "build_log_outer")
    assert row["calls"] == 1 and row["programs"] == 1
    assert row["spans"] == ["perf.t.build"]
    costs = [r["total_s"] for r in table]
    assert costs == sorted(costs, reverse=True)


def test_build_log_without_a_registry_or_a_span():
    """A root under a span with no ``metrics`` is recorded under the
    span's name and writes no counter; a program built by another
    thread, or with no span open, is under no span."""
    import threading

    import jax
    import jax.numpy as jnp

    from rlo_tpu.utils.metrics import Registry
    from rlo_tpu.utils.tracing import BUILDS

    reg = Registry()

    def build(name):
        def f(x):
            return x * 3 + 1
        f.__name__ = name
        jax.jit(f)(jnp.ones((3,))).block_until_ready()

    with annotate("perf.t.outer", reg):
        with annotate("perf.t.plain"):
            build("build_log_plain")
        worker = threading.Thread(target=build, args=("build_log_thread",))
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive()
    build("build_log_bare")
    # by name, over the whole log: it keeps the newest 16384 roots, and
    # a test worker that has built that many before this test (the log
    # is the process's) has no "records from here on" to slice
    spans = {r.fun_name: (r.span, r.metrics) for r in list(BUILDS.records)}
    assert spans["build_log_plain"] == ("perf.t.plain", None)
    assert spans["build_log_thread"] == (None, None)
    assert spans["build_log_bare"] == (None, None)
    assert not [k for k in reg.snapshot()["counters"] if ".build." in k]
