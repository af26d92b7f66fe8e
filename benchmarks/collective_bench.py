"""Collective data-plane benchmark — BENCH_collective.json
(docs/DESIGN.md §21).

Two leg families, one perf_gate document:

**Seed-exact sim legs** (``sim_*``): run each instrumented schedule
(ring allreduce, recursive doubling) over the deterministic SimWorld
substrate at n in {4, 8, 16} and pin, at ZERO tolerance:

  - ``steps``: Ev.STEP events the instrumentation emitted — the
    ledger's step count times ranks; any dropped or duplicated probe
    emission moves it;
  - ``bytes``: the fleet's ``coll_bytes`` counter total, which must
    equal the cost ledger's fleet-wide byte account exactly (the
    measured-equals-predicted contract rlo-scope enforces as S2);
  - ``events``: the simulator's delivery-schedule length — the
    substrate message cost of the schedule, instrumentation included
    (instrumentation must NOT change it: probes never send);
  - ``vtime_usec``: virtual drain time — seed-exact latency;
  - ``ledger_digest``: the schedule's canonical per-step/edge listing
    hash — pins the proven schedule shape itself.

**Informational wall-clock legs** (``wall_*``): per-algorithm achieved
GB/s of the jax executor (ops/tpu_collectives.allreduce) against
``lax.psum`` on a shard_map mesh over 4 devices of whatever backend the
caller started the process on. On CPU (this repo's CI: JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=4, as check.sh passes)
the figures are informational only (CPU
serializes every ppermute through one memory bus — see
``allreduce_cost``'s model notes); on a real TPU slice the same legs
become the ROADMAP item 2 bandwidth bar. ``direction: higher`` with
null tolerance: perf_gate requires presence, not level.

Usage:
    python benchmarks/collective_bench.py --out BENCH_collective.json
    python benchmarks/collective_bench.py --quick   # sim legs only
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

#: per-rank payload for every leg: 256 KiB f32 (divisible by every
#: leg's n, so chunking is exact and the ledger's byte figures match
#: the closed forms with no padding residue)
NBYTES = 1 << 18

SIM_NS = (4, 8, 16)
SIM_SCHEDULES = ("ring_allreduce", "recursive_doubling")

WALL_ALGORITHMS = ("psum", "ring", "recursive_doubling",
                   "halving_doubling")
WALL_DEVICES = 4
WALL_ITERS = 20


def exact(value):
    return {"value": value, "direction": "exact", "tolerance": None}


def info(value):
    return {"value": value, "direction": "higher", "tolerance": None}


def sim_legs() -> dict:
    """The seed-exact family: every figure is a pure function of
    (schedule, n, seed) and gates at zero tolerance."""
    from rlo_tpu.observe.ledger import ledger
    from rlo_tpu.tools.rlo_scope import run_sim_collective

    metrics = {}
    for schedule in SIM_SCHEDULES:
        for n in SIM_NS:
            run = run_sim_collective(schedule, n, NBYTES, seed=0)
            led = ledger(schedule, n, NBYTES)
            if not run["result_correct"]:
                raise RuntimeError(
                    f"{schedule} n={n}: wrong allreduce result on "
                    f"the sim substrate")
            fleet_bytes = sum(run["coll_bytes"])
            if fleet_bytes != led.total_bytes:
                raise RuntimeError(
                    f"{schedule} n={n}: measured fleet bytes "
                    f"{fleet_bytes} != ledger {led.total_bytes}")
            pfx = f"sim_{schedule}_n{n}"
            metrics[f"{pfx}.steps"] = exact(len(run["events"]))
            metrics[f"{pfx}.bytes"] = exact(fleet_bytes)
            metrics[f"{pfx}.events"] = exact(run["sim_events"])
            metrics[f"{pfx}.vtime_usec"] = exact(
                run["drain_vtime_usec"])
            metrics[f"{pfx}.ledger_digest"] = exact(led.digest())
            print(f"{pfx}: {len(run['events'])} step events, "
                  f"{fleet_bytes} B, {run['sim_events']} sim events, "
                  f"drain {run['drain_vtime_usec']}us",
                  file=sys.stderr)
    return metrics


def wall_legs() -> dict:
    """The informational family: jax executor GB/s per algorithm vs
    lax.psum on a shard_map mesh over the first WALL_DEVICES devices of
    the live backend (the caller picks it: the chips, or
    JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=4)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.observe.ledger import ledger
    from rlo_tpu.ops import tpu_collectives
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit
    from rlo_tpu.utils.device import (enable_compile_cache,
                                      require_devices)

    require_devices(WALL_DEVICES)
    enable_compile_cache()
    n_dev = len(jax.devices())
    n = WALL_DEVICES
    mesh = make_mesh((n,), ("x",))
    x = jnp.ones((n, NBYTES // 4), jnp.float32)

    # ring-allreduce bus bytes per chip from the ledger — the same
    # single source of truth bench.py uses
    bus_bytes = ledger("ring_allreduce", n, NBYTES).bytes_per_rank

    metrics = {}
    t_psum = None
    for alg in WALL_ALGORITHMS:
        if alg == "psum":
            def body(v):
                return jax.lax.psum(v, "x")
        else:
            def body(v, _alg=alg):
                return tpu_collectives.allreduce(
                    x=v, axis="x", algorithm=_alg)
        fn = shard_jit(body, mesh, P("x"), P(), check_vma=False)
        fn(x).block_until_ready()  # compile outside the timed window
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(WALL_ITERS):
                out = fn(x)
            out.block_until_ready()
            best = min(best, (time.perf_counter() - t0) / WALL_ITERS)
        gbps = bus_bytes / best / 1e9
        if alg == "psum":
            t_psum = best
        metrics[f"wall_{alg}_n{n}.gbps"] = info(round(gbps, 3))
        if t_psum is not None and alg != "psum":
            metrics[f"wall_{alg}_n{n}.vs_psum"] = info(
                round(t_psum / best, 4))
        print(f"wall_{alg}_n{n}: {best * 1e3:.3f} ms/iter "
              f"({gbps:.2f} GB/s)", file=sys.stderr)
    metrics["wall.devices"] = exact(n)
    metrics["wall.backend_tpu"] = exact(
        1 if jax.default_backend() == "tpu" else 0)
    print(f"wall legs: backend={jax.default_backend()} "
          f"devices={n_dev} (using {n})", file=sys.stderr)
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="sim legs only (skip the jax wall legs)")
    ap.add_argument("--out", help="write benchmark JSON here")
    args = ap.parse_args(argv)

    metrics = sim_legs()
    if not args.quick:
        metrics.update(wall_legs())

    doc = {
        "suite": "collective_bench",
        "config": {"nbytes": NBYTES, "seed": 0,
                   "sim_ns": list(SIM_NS),
                   "sim_schedules": list(SIM_SCHEDULES),
                   "wall_devices": WALL_DEVICES,
                   "wall_iters": WALL_ITERS},
        "metrics": metrics,
    }
    text = json.dumps(doc, indent=1, sort_keys=True)
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
