"""Traffic-laboratory benchmark — BENCH_workload.json
(docs/DESIGN.md §14; ROADMAP item 4's "planet-scale traffic lab").

Pins the whole workloads subsystem seed-exact through
``rlo_tpu.tools.perf_gate``:

  - **trace generators** (rlo_tpu/workloads/traces.py): request count
    + SHA-256 trace digest for every canned workload shape (diurnal /
    mmpp / flash / swarm) at fixed seeds — a generator edit that moves
    one token fails here with a named cause.
  - **calendar-queue scale**: the n=10,000-rank protocol-only fan-out
    AND post-kill membership-convergence datapoints, run on
    ``SimWorld(scheduler="calendar")`` — virtual time and schedule
    length gate exact. An in-bench oracle check first replays the
    n=256 fan-out on BOTH schedulers and hard-asserts identical
    (vtime, events): the §14 pop-order-equivalence rule, enforced at
    run time on top of the unit tests.
  - **trace-driven serving**: one swarm trace through the 4-rank
    serving fabric (StubBackend over the deterministic simulator —
    drain vtime / events / requeues exact) and one mmpp trace through
    the real tiny-model ``DecodeServer`` open loop (rounds / occupancy
    / efficiency exact) — each with its trace digest pinned, so
    "millions of users" is a replayable input, not a synthetic knob.

``--quick`` shrinks the scale legs (n=1024, no jax serving leg) for
unit-test reproducibility runs; the committed baseline and the
check.sh gate use the FULL config under a wall-time budget (the
10k-rank smoke).

Usage:
    python benchmarks/workload_bench.py --out BENCH_workload.json
    python benchmarks/workload_bench.py --quick
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

#: the big-world datapoint the acceptance criterion names; --quick
#: drops it to 1024 so tests stay fast
BIG_N_FULL = 10_000
BIG_N_QUICK = 1024

#: canned generator pins: (kind, seed, overrides) — defaults
#: everywhere else so the pinned digests cover the default configs
TRACE_PINS = (
    ("diurnal", 0, {}),
    ("mmpp", 0, {}),
    ("flash", 0, {}),
    ("swarm", 0, {}),
)


def exact(value):
    return {"value": value, "direction": "exact", "tolerance": None}


def info(value):
    return {"value": value, "direction": "higher", "tolerance": None}


def _load_bench(name: str):
    """Sibling benchmark module by file path (benchmarks/ is not a
    package)."""
    spec = importlib.util.spec_from_file_location(
        name, Path(__file__).resolve().parent / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trace_metrics():
    from rlo_tpu.workloads.traces import make_trace

    metrics = {}
    for kind, seed, overrides in TRACE_PINS:
        t0 = time.perf_counter()
        tr = make_trace(kind, seed, **overrides)
        dt = time.perf_counter() - t0
        metrics[f"trace.{kind}.requests"] = exact(len(tr.requests))
        metrics[f"trace.{kind}.digest"] = exact(tr.digest())
        print(f"trace {kind} seed={seed}: {len(tr.requests)} reqs, "
              f"digest {tr.digest()[:12]}, {dt * 1e3:.0f} ms",
              file=sys.stderr)
    return metrics


def scale_metrics(big_n: int, sim_bench):
    """Calendar-queue scale legs + the heap-oracle equivalence
    assertion (docs/DESIGN.md §14)."""
    metrics = {}
    # oracle: same fan-out, both schedulers, identical results
    h = sim_bench.bench_fanout(256, scheduler="heap")
    c = sim_bench.bench_fanout(256, scheduler="calendar")
    assert (h[0], h[1]) == (c[0], c[1]), (
        f"calendar scheduler diverged from the heapq oracle at "
        f"n=256: heap (vtime={h[0]}, events={h[1]}) vs calendar "
        f"(vtime={c[0]}, events={c[1]})")
    metrics["oracle.n256.schedulers_match"] = exact(1)
    print(f"oracle n=256: heap == calendar "
          f"(vtime {h[0]:.4f}, {h[1]} events)", file=sys.stderr)

    vt, events, n_bcast, wdt = sim_bench.bench_fanout(
        big_n, n_bcast=1, scheduler="calendar")
    metrics[f"fanout.n{big_n}.vtime"] = exact(vt)
    metrics[f"fanout.n{big_n}.events_per_bcast"] = exact(
        events / n_bcast)
    metrics[f"fanout.n{big_n}.wall_events_per_sec"] = info(
        events / wdt if wdt > 0 else 0.0)
    print(f"fanout n={big_n}: {vt:.3f} vsec, "
          f"{events / n_bcast:.0f} events/bcast, {wdt:.1f}s wall",
          file=sys.stderr)

    vt, ev, wdt = sim_bench.bench_membership(big_n,
                                             scheduler="calendar")
    metrics[f"member.n{big_n}.converge_vtime"] = exact(vt)
    metrics[f"member.n{big_n}.events"] = exact(ev)
    metrics[f"member.n{big_n}.wall_events_per_sec"] = info(
        ev / wdt if wdt > 0 else 0.0)
    print(f"member n={big_n}: converged {vt:.2f} vsec after kill, "
          f"{ev} events, {wdt:.1f}s wall", file=sys.stderr)
    return metrics


def fabric_trace_metrics(fabric_bench):
    """One swarm trace through the 4-rank serving fabric."""
    from rlo_tpu.workloads.traces import make_trace

    tr = make_trace("swarm", 5, horizon=30.0, rate=0.8,
                    n_prefixes=4, prefix_len=(4, 8), plen=(2, 6),
                    budget=(4, 16), vocab=32000)
    doc = fabric_bench.trace_doc(tr, n=4)
    return {f"fabric.{k}": v for k, v in doc["metrics"].items()}


def serve_trace_metrics(serve_bench):
    """One mmpp trace through the real tiny-model DecodeServer."""
    import jax

    from rlo_tpu.models.transformer import (TransformerConfig,
                                            init_params)
    from rlo_tpu.utils.device import enable_compile_cache
    from rlo_tpu.workloads.traces import make_trace

    enable_compile_cache()
    cfg = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                            n_layers=2, d_ff=256, dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tr = make_trace("mmpp", 3, horizon=24.0, tenants=3,
                    tenant_rate=1.0, mean_on=6.0, mean_off=10.0,
                    vocab=128, plen=(3, 8), budget=(4, 12))
    doc = serve_bench.trace_leg(params, cfg, tr, tiny=True, slots=2,
                                round_len=4, max_len=64,
                                buckets=(16,))
    return {f"serve.{k}": v for k, v in doc["metrics"].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="n=1024 scale leg, no jax serving leg (the "
                         "committed baseline uses the FULL config)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import logging
    logging.getLogger("rlo_tpu").setLevel(logging.ERROR)

    big_n = BIG_N_QUICK if args.quick else BIG_N_FULL
    sim_bench = _load_bench("sim_bench")
    fabric_bench = _load_bench("fabric_bench")
    metrics = {}
    metrics.update(trace_metrics())
    metrics.update(scale_metrics(big_n, sim_bench))
    metrics.update(fabric_trace_metrics(fabric_bench))
    if not args.quick:
        serve_bench = _load_bench("serve_bench")
        metrics.update(serve_trace_metrics(serve_bench))
    doc = {
        "suite": "workload_bench",
        "schema": 1,
        "quick": bool(args.quick),
        "config": {"big_n": big_n, "quick": bool(args.quick)},
        "metrics": metrics,
    }
    text = json.dumps(doc, indent=1, sort_keys=True)
    if args.out:
        Path(args.out).write_text(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
