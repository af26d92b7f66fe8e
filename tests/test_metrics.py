"""Metrics registry (rlo_tpu/utils/metrics.py) + engine integration.

Primitive semantics (log2 histogram layout is shared with the C core's
rlo_hist — bucket index = bit_length of the integer part), registry
snapshots, and the ProgressEngine metrics surface: per-link accounting
symmetric across a healthy run, RTT EWMA measured from ARQ ack timing,
ARQ counters folded into the snapshot while the PR-1 attribute aliases
stay live, heartbeat-age-carrying FAILURE events, and the structured
warning on a local failure declaration.
"""

import logging

import pytest

from rlo_tpu.engine import EngineManager, ProgressEngine, drain
from rlo_tpu.transport.loopback import LoopbackWorld
from rlo_tpu.utils.metrics import (HIST_BUCKETS, Counter, Gauge, Histogram,
                                   LinkStats, Registry, hist_quantile)
from rlo_tpu.utils.tracing import TRACER, Ev


class TestPrimitives:
    def test_counter_gauge(self):
        c, g = Counter(), Gauge()
        c.inc()
        c.inc(4)
        g.set(7)
        g.set(3)
        assert c.value == 5 and g.value == 3

    def test_histogram_buckets_are_log2(self):
        h = Histogram()
        assert Histogram.bucket_index(0) == 0
        assert Histogram.bucket_index(1) == 1
        assert Histogram.bucket_index(2) == 2
        assert Histogram.bucket_index(3) == 2
        assert Histogram.bucket_index(1024) == 11
        assert Histogram.bucket_index(2 ** 40) == HIST_BUCKETS - 1
        for v in (0, 1, 3, 1024, 2.5e6):
            h.observe(v)
        s = h.snapshot()
        assert s["count"] == 5
        assert s["min"] == 0 and s["max"] == 2.5e6
        assert s["sum"] == pytest.approx(2.5e6 + 1028)
        assert sum(s["buckets"]) == 5

    def test_histogram_bucket_placement(self):
        h = Histogram()
        h.observe(2)
        h.observe(3)
        assert h.buckets[2] == 2  # [2, 4) is bucket 2 (bit_length 2)

    def test_quantile_from_snapshot(self):
        h = Histogram()
        for v in [1] * 90 + [1000] * 10:
            h.observe(v)
        s = h.snapshot()
        assert hist_quantile(s, 0.5) == 2.0   # bucket upper bound of 1
        assert hist_quantile(s, 0.99) == 1024.0
        assert hist_quantile({"count": 0, "buckets": []}, 0.5) is None

    def test_registry_snapshot_and_reuse(self):
        r = Registry()
        r.counter("a").inc()
        assert r.counter("a") is r.counter("a")
        r.gauge("g").set(2)
        r.histogram("h").observe(5)
        s = r.snapshot()
        assert s["counters"] == {"a": 1}
        assert s["gauges"] == {"g": 2}
        assert s["histograms"]["h"]["count"] == 1
        r.clear()
        assert r.snapshot() == {"counters": {}, "gauges": {},
                                "histograms": {}}

    def test_linkstats_rtt_ewma(self):
        ls = LinkStats()
        ls.rtt_sample(800.0)
        assert ls.rtt_ewma_usec == 800.0
        ls.rtt_sample(1600.0)  # +1/8 of the delta
        assert ls.rtt_ewma_usec == pytest.approx(900.0)


def _world(ws=4, **kw):
    world = LoopbackWorld(ws, **kw)
    mgr = EngineManager()
    engines = [ProgressEngine(world.transport(r), manager=mgr,
                              arq_rto=0.005) for r in range(ws)]
    for e in engines:
        e.enable_metrics()
    return world, engines


class TestEngineMetrics:
    def test_link_accounting_is_symmetric(self):
        """Without loss, every frame rank A accounts tx toward B shows
        up as rx at B from A — byte-exact."""
        world, engines = _world(latency=2, seed=5)
        for i in range(5):
            engines[i % 4].bcast(f"payload {i}".encode())
        drain([world], engines)
        for e in engines:
            while e.pickup_next() is not None:
                pass
        snaps = [e.metrics() for e in engines]
        for a in range(4):
            for b in range(4):
                if a == b:
                    continue
                tx = snaps[a]["links"][str(b)]
                rx = snaps[b]["links"][str(a)]
                assert tx["tx_frames"] == rx["rx_frames"]
                assert tx["tx_bytes"] == rx["rx_bytes"]
        for e in engines:
            e.cleanup()

    def test_rtt_ewma_measured_under_arq(self):
        """ARQ ack timing populates the per-link RTT EWMA on links
        that carried reliable traffic."""
        world, engines = _world(latency=2, seed=3)
        for i in range(4):
            engines[0].bcast(f"rtt {i}".encode())
        drain([world], engines)
        snap = engines[0].metrics()
        measured = [l["rtt_ewma_usec"] for l in snap["links"].values()
                    if l["tx_frames"]]
        assert measured and all(r > 0 for r in measured)
        for e in engines:
            e.cleanup()

    def test_arq_counter_aliases_and_registry_agree(self):
        """Satellite: the PR-1 ad-hoc ARQ counters are registry-backed
        now; the attribute aliases and the snapshot always agree."""
        world, engines = _world()
        world.drop_next(0, 1, 1)
        world.dup_next(0, 2, 1)
        engines[0].bcast(b"lossy")
        drain([world], engines)
        e0 = engines[0]
        snap = e0.metrics()["counters"]
        assert snap["arq_retransmits"] == e0.arq_retransmits >= 1
        assert snap["arq_gave_up"] == e0.arq_gave_up
        assert snap["arq_unacked"] == e0.arq_unacked() == 0
        dups = sum(e.metrics()["counters"]["arq_dup_drops"]
                   for e in engines)
        assert dups == sum(e.arq_dup_drops for e in engines) >= 1
        # per-link attribution: the dup drop landed on rank 2's link
        # from rank 0, the retransmit on rank 0's link toward rank 1
        assert engines[2].metrics()["links"]["0"]["dup_drops"] >= 1
        assert e0.metrics()["links"]["1"]["retransmits"] >= 1
        for e in engines:
            e.cleanup()

    def test_pickup_backlog_and_wait(self):
        """Queue-depth gauges expose the pickup backlog; draining it
        feeds the pickup-wait histogram."""
        world, engines = _world()
        engines[0].bcast(b"one")
        engines[1].bcast(b"two")
        drain([world], engines)
        s = engines[2].metrics()
        assert s["queues"]["pickup"] + s["queues"]["wait_and_pickup"] == 2
        while engines[2].pickup_next() is not None:
            pass
        s = engines[2].metrics()
        assert s["queues"]["pickup"] == 0
        assert s["op_latency_usec"]["pickup_wait"]["count"] == 2
        for e in engines:
            e.cleanup()

    def test_failure_event_carries_heartbeat_age(self, caplog):
        """Satellite: Ev.FAILURE from a local detection carries the
        last-seen heartbeat age (usec) in c, and declaration logs one
        structured warning."""
        clock = [0.0]
        world = LoopbackWorld(4)
        mgr = EngineManager()
        engines = [ProgressEngine(world.transport(r), manager=mgr,
                                  failure_timeout=1.0,
                                  clock=lambda: clock[0])
                   for r in range(4)]
        TRACER.clear()
        with TRACER.enable(), caplog.at_level(
                logging.WARNING, logger="rlo_tpu.engine"):
            for t in (0.3, 0.6, 0.9):  # heartbeats flow, all healthy
                clock[0] = t
                mgr.progress_all()
            world.kill_rank(2)
            engines[2].cleanup()  # a dead process's engine stops too
            clock[0] = 2.5  # > timeout since rank 2's last frame
            for _ in range(20):
                mgr.progress_all()
        local = [e for e in TRACER.events(Ev.FAILURE) if e.b == 1]
        assert local, "no local failure declaration"
        ev = local[0]
        assert ev.a == 2
        # age is the declared silence: > timeout, <= the full window
        assert 1.0e6 < ev.c <= 2.5e6
        warnings = [r for r in caplog.records
                    if "FAILED" in r.getMessage() and r.name ==
                    "rlo_tpu.engine"]
        assert len(warnings) == 1
        assert "rank 2" in warnings[0].getMessage()
        assert "timeout" in warnings[0].getMessage()
        TRACER.clear()
        for e in engines:
            e.cleanup()

    def test_disabled_metrics_skip_collection(self):
        """With metrics off, links stay zeroed and histograms empty
        (the one-branch disabled path), while plain counters advance."""
        world = LoopbackWorld(2)
        mgr = EngineManager()
        engines = [ProgressEngine(world.transport(r), manager=mgr)
                   for r in range(2)]
        engines[0].bcast(b"x")
        drain([world], engines)
        s = engines[0].metrics()
        assert s["counters"]["sent_bcast"] == 1
        assert all(v == 0 for l in s["links"].values()
                   for k, v in l.items())
        assert all(h["count"] == 0
                   for h in s["op_latency_usec"].values())
        for e in engines:
            e.cleanup()


# ---------------------------------------------------------------------------
# Histogram(keep=N): exact percentiles beside the log2 buckets
# ---------------------------------------------------------------------------

class TestKeptSamples:
    @pytest.mark.parametrize("n", [1, 2, 7, 400])
    def test_quantiles_are_exact_over_kept_samples(self, n):
        import random
        import statistics
        rng = random.Random(n)
        data = [rng.lognormvariate(13.0, 0.4) for _ in range(n)]
        h = Histogram(keep=1024)
        for v in data:
            h.observe(v)
        if n == 1:
            cuts = {q: data[0] for q in (50, 90, 99)}
        else:
            c = statistics.quantiles(data, n=100, method="inclusive")
            cuts = {q: c[q - 1] for q in (50, 90, 99)}
        assert h.p50() == pytest.approx(cuts[50], rel=1e-12)
        assert h.p90() == pytest.approx(cuts[90], rel=1e-12)
        assert h.p99() == pytest.approx(cuts[99], rel=1e-12)
        assert h.quantile(0.0) == min(data)
        assert h.quantile(1.0) == max(data)
        s = h.summary()
        assert (s["p50"], s["p90"], s["p99"]) == (h.p50(), h.p90(),
                                                  h.p99())
        # the log2 estimate of the same data is a bucket's upper bound
        plain = Histogram()
        for v in data:
            plain.observe(v)
        assert plain.p50() == float(2 ** Histogram.bucket_index(
            sorted(data)[(n - 1) // 2]))

    def test_newest_n_rule(self):
        h = Histogram(keep=4)
        for v in range(1, 11):
            h.observe(v)
        assert list(h.samples) == [7.0, 8.0, 9.0, 10.0]
        assert h.quantile(0.0) == 7.0 and h.p50() == 8.5
        # count/sum/min/max and the buckets still cover all ten
        assert (h.count, h.sum, h.min, h.max) == (10, 55.0, 1.0, 10.0)
        assert sum(h.buckets) == 10

    def test_snapshot_layout_is_the_same_with_and_without_keep(self):
        kept, plain = Histogram(keep=8), Histogram()
        assert kept.quantile(0.5) is None and plain.samples is None
        for v in (0, 1, 3, 1024, 2.5e6):
            kept.observe(v)
            plain.observe(v)
        assert kept.snapshot() == plain.snapshot()
        assert list(kept.snapshot()) == ["count", "sum", "min", "max",
                                         "buckets"]
        assert len(kept.snapshot()["buckets"]) == HIST_BUCKETS

    def test_registry_keep_is_set_at_creation_and_summaries(self):
        reg = Registry()
        a = reg.histogram("a_usec", keep=16)
        assert reg.histogram("a_usec") is a          # found, not re-made
        assert reg.histogram("a_usec", keep=2).samples.maxlen == 16
        b = reg.histogram("b_usec")
        for v in (100.0, 300.0, 700.0):
            a.observe(v)
            b.observe(v)
        s = reg.summaries()
        assert list(s) == ["a_usec", "b_usec"]
        assert s["a_usec"]["p50"] == 300.0           # exact
        assert s["b_usec"]["p50"] == 512.0           # bucket upper bound
        assert reg.snapshot()["histograms"]["a_usec"] == \
            reg.snapshot()["histograms"]["b_usec"]
        # a snapshot alone still summarizes as before
        assert hist_quantile(a.snapshot(), 0.5) == 512.0
