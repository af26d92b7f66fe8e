"""BASELINE.json config benchmark suite — one JSON line per config.

The reference publishes no benchmark numbers (its README is untouched
boilerplate; SURVEY.md §6) — only run-time-printed harnesses. The rebuild's
targets come from BASELINE.json's five configs; this suite makes each one a
runnable, self-describing benchmark:

  1  float32 allreduce, 1 MB buffer, 8 ranks on the engine substrate
     (the reference's `mpirun on CPU` analogue: C core vs pure Python)
  2  rootless bcast over an 8-device mesh (static ppermute spanning tree
     vs the all_gather 'gather' strategy)
  3  bf16 recursive-doubling allreduce with the Pallas fused add, vs psum
  4  reduce-scatter + all-gather (recursive halving/doubling) for large
     gradient tensors, vs one XLA psum
  5  rootless leaderless consensus (IAR) throughput on the engine
     substrate, vs the 1k ops/s north-star target

Configs 2-4 build a 1-D mesh over every device of the live backend and
fail when it has fewer than two; the program never picks a platform. For
a CPU-mesh run start it with JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8. Sizes shrink on CPU
(the numbers then demonstrate the harness and relative behavior, not TPU
bandwidth). ``--tiny`` shrinks further for smoke tests.

Usage:  python benchmarks/suite.py --config {1..5|all} [--tiny]
Each config prints exactly one JSON line on stdout:
  {"config": N, "metric": ..., "value": V, "unit": ..., "vs_baseline": B}
Diagnostics go to stderr. `--config all` runs each config in a fresh
subprocess (jax backend setup is per-process) and relays the lines.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))


def _emit(config: int, metric: str, value: float, unit: str,
          vs_baseline: float, **extra) -> None:
    line = {"config": config, "metric": metric, "value": round(value, 3),
            "unit": unit, "vs_baseline": round(vs_baseline, 4), **extra}
    print(json.dumps(line))


def _fmt_bytes(nbytes: int) -> str:
    if nbytes >= 1 << 20:
        return f"{nbytes >> 20} MB"
    return f"{nbytes >> 10} KB"


def _wall_median(fn, reps: int = 5) -> float:
    fn()  # warmup
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


# ---------------------------------------------------------------------------
# Config 1 — engine-substrate allreduce (CPU, 8 ranks): C core vs Python
# ---------------------------------------------------------------------------

def bench_config1(tiny: bool) -> None:
    """C engines vs Python engines running the IDENTICAL algorithm:
    allreduce as bcast-gather over the rootless broadcast overlay (the
    reference's any-rank-initiates notion generalized to tensors, the
    NativeBackend data-collective path). The C side runs wholly inside
    the library (rlo_bench_allreduce) so the measurement is the engine
    substrate, not the ctypes boundary."""
    import numpy as np
    from rlo_tpu.engine import EngineManager, ProgressEngine, drain
    from rlo_tpu.native.bindings import bench_allreduce
    from rlo_tpu.ops.collectives import _pack_array, _unpack_array
    from rlo_tpu.transport.loopback import LoopbackWorld

    ws = 8
    n = ((64 << 10) if tiny else (1 << 20)) // 4  # BASELINE: 1 MB fp32
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(n).astype(np.float32) for _ in range(ws)]
    want = np.sum(xs, axis=0)
    reps = 3 if tiny else 7

    t_c = bench_allreduce(ws, n, reps) / 1e6

    world = LoopbackWorld(ws)
    mgr = EngineManager()
    engines = [ProgressEngine(world.transport(r), manager=mgr,
                              msg_size_max=n * 4 + 64) for r in range(ws)]

    def op_python():  # same bcast-gather, pure-Python engines
        for r, e in enumerate(engines):
            e.bcast(_pack_array(xs[r]))
        drain([world], engines)
        for r, e in enumerate(engines):
            acc = xs[r].copy()
            for _ in range(ws - 1):
                acc += _unpack_array(e.pickup_next().data)
            # single-element oracle, mirroring the C harness's check so
            # the timed work is identical on both sides
            if r == 0 and abs(float(acc[0]) - float(want[0])) > 1e-3:
                raise AssertionError(f"bad reduction: {acc[0]} vs {want[0]}")
    t_py = _wall_median(op_python, reps=reps)
    # observability rides along (docs/DESIGN.md §7): re-run one rep
    # with the metrics registry on (enabled AFTER timing so the
    # accounting never pollutes the measured number) and emit the
    # engine snapshot alongside the timing JSON
    for e in engines:
        e.enable_metrics()
    op_python()
    metrics_snap = engines[0].metrics()
    for e in engines:
        e.cleanup()

    print(f"config1 C: {t_c*1e6:.0f} usec  python: {t_py*1e6:.0f} usec",
          file=sys.stderr)
    _emit(1, f"engine-substrate allreduce (bcast-gather over the rootless "
             f"overlay), {_fmt_bytes(n*4)} fp32, {ws} ranks, C core "
             f"(baseline = pure-Python engines, same algorithm)",
          t_c * 1e6, "usec", t_py / t_c,
          metrics_substrate="python-engines",
          metrics_scope="links/histograms: one un-timed rep; "
                        "counters: engine lifetime (all reps)",
          metrics=metrics_snap)

    # ring vs bcast-gather, both substrates (rlo_coll.c vs the Python
    # coroutine Comm): the bandwidth-optimal 2*(ws-1) chunk rounds
    # against the O(ws^2) overlay gather
    from rlo_tpu.native.bindings import bench_allreduce_ring
    from rlo_tpu.ops.collectives import Comm, run_collectives
    from rlo_tpu.transport.loopback import LoopbackWorld as LW

    t_c_ring = bench_allreduce_ring(ws, n, reps) / 1e6

    ring_world = LW(ws)
    comms = [Comm(ring_world.transport(r)) for r in range(ws)]

    def op_python_ring():
        outs = run_collectives(
            [c.allreduce(xs[r], algorithm="ring")
             for r, c in enumerate(comms)])
        if abs(float(outs[0][0]) - float(want[0])) > 1e-3:
            raise AssertionError("bad ring reduction")
    t_py_ring = _wall_median(op_python_ring, reps=reps)
    print(f"config1 ring C: {t_c_ring*1e6:.0f} usec  ring python: "
          f"{t_py_ring*1e6:.0f} usec  (C ring is "
          f"{t_c/t_c_ring:.2f}x faster than C bcast-gather)",
          file=sys.stderr)
    _emit(1, f"engine-substrate RING allreduce (rlo_coll.c), "
             f"{_fmt_bytes(n*4)} fp32, {ws} ranks, C core "
             f"(baseline = C bcast-gather, same substrate)",
          t_c_ring * 1e6, "usec", t_c / t_c_ring)

    import re
    import subprocess
    from pathlib import Path
    native = Path(__file__).resolve().parent.parent / "rlo_tpu" / "native"

    # ring vs bcast-gather across REAL OS processes (shm transport, one
    # process per rank — the config's "via mpirun" run shape). A leg
    # that cannot build, run or be parsed fails the config: a printed
    # "skipped" with exit 0 reads as a pass.
    subprocess.run(["make", "-s", "demo"], cwd=native, check=True,
                   capture_output=True, timeout=120)
    proc = subprocess.run(
        [str(native / "rlo_demo"), "-n", str(ws), "-c", "bench",
         "-m", "3" if tiny else "5", "-b", str(n * 4)],
        capture_output=True, text=True, timeout=280, check=True)
    mg = re.search(r"bcast-gather.*median (\d+) usec", proc.stdout)
    mr = re.search(r"ring allreduce.*median (\d+) usec", proc.stdout)
    if not (mg and mr):
        raise RuntimeError(
            f"config1 shm-process leg: no medians in rlo_demo output:\n"
            f"{proc.stdout}")
    t_bg, t_ring = float(mg.group(1)), float(mr.group(1))
    print(f"config1 shm processes: ring {t_ring:.0f} usec  "
          f"bcast-gather {t_bg:.0f} usec", file=sys.stderr)
    _emit(1, f"engine-substrate RING allreduce across {ws} real "
             f"OS processes (shm transport, {_fmt_bytes(n*4)} "
             f"fp32; baseline = bcast-gather, same processes)",
          t_ring, "usec", t_bg / t_ring)

    # overlay bcast vs the native library broadcast over REAL MPI
    # processes — the reference's native_benchmark_single_point_bcast
    # (rootless_ops.c:1675-1709), run via femtompirun + the nbcast demo
    # case. The overlay loses (store-and-forward through a polled
    # engine vs a direct library collective); reported honestly.
    subprocess.run(["make", "-s", "mpidemo"], cwd=native, check=True,
                   capture_output=True, timeout=120)
    reps_b = 8 if tiny else 32
    bytes_b = 4096 if tiny else 65536  # VERDICT item 6: 64 KB leg
    proc = subprocess.run(
        [str(native / "femtompirun"), "-n", str(ws), "-t", "240",
         str(native / "rlo_demo_mpi"), "-c", "nbcast",
         "-m", str(reps_b), "-b", str(bytes_b)],
        capture_output=True, text=True, timeout=280, check=True)
    m = re.search(r"overlay skip-ring ([\d.]+) / flat [\d.]+ / "
                  r"MPI_Bcast ([\d.]+) usec/bcast", proc.stdout)
    if not m:
        raise RuntimeError(
            f"config1 nbcast leg: no timings in rlo_demo_mpi output:\n"
            f"{proc.stdout}")
    t_ov, t_nat = float(m.group(1)), float(m.group(2))
    print(f"config1 nbcast overlay: {t_ov:.1f} usec  "
          f"MPI_Bcast: {t_nat:.1f} usec", file=sys.stderr)
    _emit(1, f"rootless overlay bcast vs native MPI_Bcast "
             f"({bytes_b >> 10} KB, {ws} real MPI processes "
             f"via femtompi; reference rootless_ops.c:1675)",
          t_ov, "usec/bcast", t_nat / t_ov)


# ---------------------------------------------------------------------------
# Configs 2-4 — mesh collectives (shared scaffolding)
# ---------------------------------------------------------------------------

def _mesh_setup():
    """A 1-D mesh over every live device (at least two, or the configs
    have nothing to communicate over). The backend is whatever the
    process was started on: the chips, or — from outside, as check.sh
    and tests/test_bench_suite.py do — JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=8."""
    from rlo_tpu.utils.device import (enable_compile_cache,
                                      require_devices)
    require_devices(2)
    enable_compile_cache()
    import jax

    from rlo_tpu.parallel.mesh import make_mesh
    n = len(jax.devices())
    return jax.default_backend(), n, make_mesh((n,), ("x",))


def _sharded_rows(mesh, n: int, per: int, dtype):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    def make(idx):
        rows = idx[0]
        seed = rows.start if isinstance(rows, slice) else int(rows)
        rng = np.random.default_rng(seed)
        return rng.standard_normal((1, per)).astype(dtype)

    return jax.make_array_from_callback(
        (n, per), NamedSharding(mesh, P("x")), make)


def _chain(fn_of_v_k, x):
    """bench.py's chained-iteration timing (amortizes the per-call
    dispatch latency and escalates k above the noise floor)."""
    import bench

    def loop(v, k):
        return fn_of_v_k(v, int(k))
    return bench._chain_time(loop, x, k=8)


def bench_config2(tiny: bool) -> None:
    backend, n, mesh = _mesh_setup()
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.ops import tpu_collectives as tc
    from rlo_tpu.parallel.mesh import shard_jit

    on_tpu = backend == "tpu"
    per = ((64 << 10) if tiny else (4 << 20) if not on_tpu
           else (64 << 20)) // 4
    x = _sharded_rows(mesh, n, per, np.float32)
    origin = 3 % n

    def chained(schedule):
        def inner(v, k):
            def it(i, acc):
                return tc.rootless_bcast(acc, origin=origin, axis="x",
                                         schedule=schedule)
            return lax.fori_loop(0, k, it, v)
        f = shard_jit(inner, mesh, (P("x"), P()), P("x"))
        return lambda v, k: f(v, k)

    t_tree = _chain(chained("binomial"), x)
    t_gather = _chain(chained("gather"), x)
    print(f"config2 binomial: {t_tree*1e6:.0f} usec  "
          f"gather: {t_gather*1e6:.0f} usec", file=sys.stderr)
    _emit(2, f"rootless bcast ({_fmt_bytes(per*4)} fp32, origin {origin}) "
             f"over {n}-device {backend} mesh, static binomial ppermute "
             f"tree (baseline = all_gather strategy)",
          t_tree * 1e6, "usec", t_gather / t_tree)


def bench_config3(tiny: bool) -> None:
    backend, n, mesh = _mesh_setup()
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.ops import tpu_collectives as tc
    from rlo_tpu.parallel.mesh import shard_jit, vary_like

    on_tpu = backend == "tpu"
    per = ((64 << 10) if tiny else (1 << 20) if not on_tpu
           else (64 << 20)) // 2
    x = _sharded_rows(mesh, n, per, jnp.bfloat16)

    def chained(algorithm):
        def inner(v, k):
            def it(i, acc):
                out = tc.allreduce(acc, "x", algorithm=algorithm,
                                   use_pallas=on_tpu)
                # psum results are typed invariant; cast back to the
                # carry's varying type so the fori_loop carry is stable
                return vary_like((out / jnp.bfloat16(n)).astype(v.dtype),
                                 v)
            return lax.fori_loop(0, k, it, v)
        f = shard_jit(inner, mesh, (P("x"), P()), P("x"))
        return lambda v, k: f(v, k)

    t_rd = _chain(chained("recursive_doubling"), x)
    t_psum = _chain(chained("psum"), x)
    print(f"config3 rd+pallas: {t_rd*1e6:.0f} usec  psum: "
          f"{t_psum*1e6:.0f} usec", file=sys.stderr)
    _emit(3, f"bf16 recursive-doubling allreduce ({_fmt_bytes(per*2)}"
             f"/shard, Pallas fused add on TPU) over {n}-device "
             f"{backend} mesh (baseline = lax.psum)",
          t_rd * 1e6, "usec", t_psum / t_rd)


def bench_config4(tiny: bool) -> None:
    backend, n, mesh = _mesh_setup()
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.ops import tpu_collectives as tc
    from rlo_tpu.parallel.mesh import shard_jit, vary_like

    on_tpu = backend == "tpu"
    # BASELINE asks for 256 MB gradient tensors on TPU; scale down on CPU
    per = ((64 << 10) if tiny else (16 << 20) if not on_tpu
           else (256 << 20)) // 4
    x = _sharded_rows(mesh, n, per, np.float32)

    def inner_ours(v, k):
        def it(i, acc):
            flat = acc[0]
            rs = tc.reduce_scatter(flat, "x", algorithm="halving",
                                   use_pallas=on_tpu)
            ag = tc.all_gather(rs, "x", algorithm="doubling")
            out = ag.reshape(-1)[:flat.size] / jnp.float32(n)
            return vary_like(out[None], v)
        return lax.fori_loop(0, k, it, v)

    def inner_base(v, k):
        def it(i, acc):
            return vary_like(lax.psum(acc, "x") / jnp.float32(n), v)
        return lax.fori_loop(0, k, it, v)

    f_ours = shard_jit(inner_ours, mesh, (P("x"), P()), P("x"))
    f_base = shard_jit(inner_base, mesh, (P("x"), P()), P("x"))
    t_ours = _chain(lambda v, k: f_ours(v, k), x)
    t_base = _chain(lambda v, k: f_base(v, k), x)
    print(f"config4 halving/doubling RS+AG: {t_ours*1e6:.0f} usec  "
          f"psum: {t_base*1e6:.0f} usec", file=sys.stderr)
    _emit(4, f"reduce-scatter + all-gather (recursive halving/doubling, "
             f"{_fmt_bytes(per*4)}/shard fp32) over {n}-device {backend} "
             f"mesh (baseline = one lax.psum)",
          t_ours * 1e6, "usec", t_base / t_ours)


# ---------------------------------------------------------------------------
# Config 5 — leaderless consensus (IAR) throughput on the engine substrate
# ---------------------------------------------------------------------------

def bench_config5(tiny: bool) -> None:
    from rlo_tpu.native.bindings import NativeEngine, NativeWorld

    ws = 8
    rounds = 20 if tiny else 200
    with NativeWorld(ws) as world:
        engines = [NativeEngine(world, r) for r in range(ws)]
        engines[0].submit_proposal(b"warm", pid=0)  # warmup round
        world.drain()
        engines[0].proposal_reset()
        t0 = time.perf_counter()
        for i in range(rounds):
            proposer = engines[i % ws]
            rc = proposer.submit_proposal(b"go", pid=i % ws)
            while rc == -1:
                world.progress_all()
                rc = proposer.vote_my_proposal()
            if rc != 1:  # a declined round must not count as an op
                raise AssertionError(f"round {i}: decision {rc}, want 1")
            world.drain()
            proposer.proposal_reset()
        dt = time.perf_counter() - t0
        # observability rides along: one extra (un-timed) round with
        # the C-side metrics registry on; the native rlo_engine_stats
        # snapshot travels with the timing line
        for e in engines:
            e.enable_metrics()
        rc = engines[0].submit_proposal(b"obs", pid=0)
        if rc == -1:
            world.drain()
        engines[0].proposal_reset()
        metrics_snap = engines[0].metrics()
    rate = rounds / dt
    print(f"config5: {rounds} IAR rounds in {dt*1e3:.1f} ms "
          f"({rate:.0f} ops/s)", file=sys.stderr)
    _emit(5, f"rootless leaderless consensus (IAR) throughput, {ws} ranks, "
             f"rotating proposer, C engine substrate (baseline = 1k ops/s "
             f"north-star target)",
          rate, "ops/s", rate / 1000.0,
          metrics_substrate="native-c-engine",
          metrics_scope="links/histograms: one un-timed round; "
                        "counters: engine lifetime (all rounds)",
          metrics=metrics_snap)

    # TPU-side decision step: the device pmin vote-merge round-trip on
    # real hardware, measured two ways (the 1k ops/s target needs a
    # device-path number, not just the CPU engine substrate):
    #   - chained: K pmin rounds inside one jit (bench.py methodology)
    #     = the device cost of the vote reduction itself;
    #   - dispatch: one jit call + blocking readback per round = the
    #     end-to-end floor when every round must return to the host for
    #     the judge/action callbacks (dominated by host<->device
    #     latency — reported honestly).
    import jax
    if jax.default_backend() != "tpu":
        return
    import numpy as np_
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import bench
    from rlo_tpu.ops import tpu_collectives as tc
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit
    from rlo_tpu.utils.device import enable_compile_cache

    enable_compile_cache()
    mesh = make_mesh((len(jax.devices()),), ("x",))
    f = shard_jit(
        lambda v, k: jax.lax.fori_loop(
            0, int(k) if not hasattr(k, "dtype") else k,
            lambda i, a: tc.consensus(jnp.minimum(a, 1), "x"), v),
        mesh, (P(), P()), P())
    v0 = jnp.ones((), jnp.int32)

    bound_only = False
    try:
        t_chained = bench._chain_time(lambda v, k: f(v, jnp.int32(k)),
                                      v0, k=1 << 20)
    except RuntimeError:
        # even 2^20 chained rounds sit below the dispatch noise floor:
        # the per-round cost is BOUNDED by noise/k but was not
        # measured. A bound must never travel through the same field as
        # a measurement (round-2 VERDICT item 8a) — emit it labeled.
        t_chained = 0.005 / (1 << 20)
        bound_only = True
    one = jax.jit(lambda v: f(v, jnp.int32(1)))
    one(v0).block_until_ready()
    t0 = time.perf_counter()
    reps_rt = 5
    for _ in range(reps_rt):
        np_.asarray(one(v0))
    t_rt = (time.perf_counter() - t0) / reps_rt
    kind = "BOUND (not measured)" if bound_only else "measured"
    print(f"config5 TPU pmin [{kind}]: chained {t_chained*1e6:.3f} "
          f"usec/round ({1/t_chained:.0f} ops/s), host round-trip "
          f"{t_rt*1e3:.1f} ms ({1/t_rt:.1f} ops/s)", file=sys.stderr)
    if bound_only:
        # labeled lower bound on the rate; vs_baseline is zeroed so no
        # consumer keying on it can mistake the bound for a measured
        # comparison (the bound itself rides "value" + bound=True)
        _emit(5, f"device consensus vote-merge (pmin) on "
                 f"{len(jax.devices())}-chip TPU: LOWER BOUND only "
                 f"(chain below dispatch noise floor); host-round-trip "
                 f"floor {t_rt*1e3:.1f} ms/round",
              1 / t_chained, "ops/s", 0.0, bound=True)
    else:
        _emit(5, f"device consensus vote-merge (pmin) on "
                 f"{len(jax.devices())}-chip TPU, chained in-jit rounds; "
                 f"host-round-trip floor {t_rt*1e3:.1f} ms/round "
                 f"(baseline = 1k ops/s north-star target)",
              1 / t_chained, "ops/s", (1 / t_chained) / 1000.0)


# ---------------------------------------------------------------------------

CONFIGS = {1: bench_config1, 2: bench_config2, 3: bench_config3,
           4: bench_config4, 5: bench_config5}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="all",
                    help="1..5 or 'all' (default)")
    ap.add_argument("--tiny", action="store_true",
                    help="smoke-test sizes")
    args = ap.parse_args()

    if args.config == "all":
        # fresh subprocess per config, and this parent stays off jax:
        # a chip belongs to one process at a time
        rc = 0
        for c in sorted(CONFIGS):
            cmd = [sys.executable, str(Path(__file__).resolve()),
                   "--config", str(c)] + (["--tiny"] if args.tiny else [])
            proc = subprocess.run(cmd, text=True, capture_output=True)
            sys.stderr.write(proc.stderr)
            sys.stdout.write(proc.stdout)
            if proc.returncode != 0:
                print(f"config {c} FAILED (rc={proc.returncode})",
                      file=sys.stderr)
                rc = 1
        return rc

    CONFIGS[int(args.config)](args.tiny)
    return 0


if __name__ == "__main__":
    sys.exit(main())
