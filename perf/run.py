#!/usr/bin/env python3
"""The benchmark's one command: run one cell once.

    python3 perf/run.py --workload W --seed N --seconds S --trace 0|1 [--tiny]

It reads ``BENCHMARK.json`` for the cell, finds ``perf/configs/<config>.json``
and ``perf/traffic/<traffic>.json`` by name, loads the runner that the
traffic file names from ``perf/kinds/``, and in a traced run one reader per
per-layer metric from ``perf/layer_metrics/<name>.py``. Nothing here knows a
cell, a configuration, a mix or a metric by name: a later PR adds files and
one entry (perf/README.md).

A run: set-up (imports, weights on the device from the seed, the check
against the plain reference, warm units; all of it ``setup_s``), then whole
units back to back until ``--seconds`` have passed. With ``--trace 1`` a
few more units run under the profiler and the per-layer metrics are
printed in place of the end-to-end ones. The last line of stdout is the
contract's one JSON object; lines before it start with ``#``.

Without ``--tiny`` (the CPU rehearsal, which the driver never passes) a
backend other than ``tpu``, a device kind without published peaks, or
fewer chips than the cell asks for ends the run with a non-zero code and
no result line.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

PERF = Path(__file__).resolve().parent
ROOT = PERF.parent
sys.path.insert(0, str(ROOT))       # rlo_tpu, the system under test
sys.path.insert(0, str(PERF))       # lib.*


def note(msg: str) -> None:
    print(f"# {msg}", flush=True)


def load_module(path: Path):
    """A file of the benchmark as a module; its name may hold '-' or '.'"""
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} does not exist")
    name = "perf_" + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_data(path: Path, tiny: bool) -> dict:
    """A configuration's or a mix's JSON; ``--tiny`` lays its ``tiny``
    section over the top-level keys."""
    if not path.is_file():
        raise FileNotFoundError(f"{path.relative_to(ROOT)} does not exist")
    data = json.loads(path.read_text())
    over = data.pop("tiny", {})
    if tiny:
        data.update(over)
    return data


def listed(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def place_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` where it is set (JAX reads it; nothing
    is set in code), else ``<checkout>/.jax_cache``: a fixed path, because
    the path is part of the cache's key. Small programs are cached too."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_facts(chips: int, tiny: bool):
    """(device dict for the result line, peaks or None). Refuses what the
    cell cannot be measured on."""
    import jax
    from lib import peaks as peaks_lib
    devs = jax.devices()
    facts = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX has "
                         f"{len(devs)} {facts['platform']} devices")
    if facts["platform"] != "tpu":
        if not tiny:
            raise SystemExit(
                f"the live JAX backend is {facts['platform']!r} "
                f"({facts['kind']}): the benchmark measures the chip and "
                f"does not fall back (use --tiny for the CPU rehearsal)")
        return facts, None
    return facts, peaks_lib.peaks(facts["kind"])


def memory_peak(chips: int) -> int:
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_window(runner, seconds: float):
    """Whole units back to back, one clock read between two units, until
    the first unit boundary at or after ``seconds``."""
    units = []
    t_prev = t0 = time.perf_counter()
    while True:
        work = runner.unit()
        now = time.perf_counter()
        units.append((now - t_prev, work))
        t_prev = now
        if now - t0 >= seconds:
            return units


def run_traced(runner, n_units: int, dump: str | None, need_device: bool):
    """``n_units`` more units under the profiler, inside one host span,
    then the reduction. The Python tracer stays off: it slows the host
    loop that the idle gaps are about."""
    import jax
    from lib import trace_reduce as tr
    log_dir = ROOT / ".perf_trace"
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            for _ in range(n_units):
                with jax.profiler.TraceAnnotation("perf.unit"):
                    runner.unit(traced=True)
    finally:
        jax.profiler.stop_trace()
    trace = tr.load_xplane(tr.find_xplane(str(log_dir)))
    shutil.rmtree(log_dir, ignore_errors=True)
    if dump:
        Path(dump).parent.mkdir(parents=True, exist_ok=True)
        Path(dump).write_text(json.dumps(trace.to_json()))
    return tr.reduce_trace(trace, need_device=need_device)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; never a device number")
    ap.add_argument("--dump-trace", default=None, metavar="FILE",
                    help="with --trace 1, also write the trace in "
                         "lib/trace_reduce.py's plain form")
    args = ap.parse_args()

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        raise SystemExit(f"no workload {args.workload!r} in BENCHMARK.json; "
                         f"it has {sorted(cells)}")
    cell = cells[args.workload]
    config = load_data(PERF / "configs" / f"{cell['config']}.json", args.tiny)
    traffic = load_data(PERF / "traffic" / f"{cell['traffic']}.json",
                        args.tiny)
    kind = load_module(PERF / "kinds" / f"{traffic['kind']}.py")
    reference = load_module(PERF / "configs" / f"{cell['config']}.py")

    parts = {}
    t_mark = [T_START]

    def part(name: str) -> None:
        now = time.perf_counter()
        parts[name] = round(parts.get(name, 0.0) + now - t_mark[0], 3)
        t_mark[0] = now

    cache_dir = place_compile_cache()
    import jax
    from lib.compile_meter import CompileMeter
    device, peaks = device_facts(cell["chips"], args.tiny)
    import rlo_tpu  # noqa: F401  the system under test; absent -> exit != 0
    meter = CompileMeter()
    part("imports")
    note(f"cell {cell['name']} config {cell['config']} traffic "
         f"{cell['traffic']} seed {args.seed} seconds {args.seconds} trace "
         f"{args.trace} tiny {args.tiny}")
    note(f"device {device} (cell uses {cell['chips']}); compile cache "
         f"{cache_dir}")

    ctx = SimpleNamespace(
        cell=cell, config=config, traffic=traffic, reference=reference,
        seed=args.seed, tiny=args.tiny, trace=bool(args.trace),
        peaks=peaks, part=part, note=note, facts={}, problems=[])
    runner = kind.Runner(ctx)
    runner.setup()
    part("other")
    setup_s = time.perf_counter() - T_START
    compiled = meter.snapshot()
    note(f"setup_s {setup_s:.3f} parts {parts} compile {compiled}")

    units = run_window(runner, args.seconds)
    in_window = meter.programs - compiled["programs"]
    if in_window:
        ctx.problems.append(f"{in_window} programs were compiled or loaded "
                            f"inside the measured window")
    elapsed = sum(dt for dt, _ in units)
    times = [dt for dt, _ in units]
    third = max(1, len(times) // 3)
    note(f"units {len(units)} window {elapsed:.4f}s; unit ms median "
         f"{1e3 * statistics.median(times):.3f} min {1e3 * min(times):.3f} "
         f"max {1e3 * max(times):.3f}; median of the first third "
         f"{1e3 * statistics.median(times[:third]):.3f}, of the last "
         f"{1e3 * statistics.median(times[-third:]):.3f}")
    ctx.window = SimpleNamespace(
        units=units, elapsed=elapsed, work=sum(w for _, w in units))
    ctx.quantities = runner.quantities(ctx.window)
    ctx.reduced = None
    if args.trace:
        ctx.reduced = run_traced(runner, int(traffic["trace_units"]),
                                 args.dump_trace, peaks is not None)
    attempted, failed = runner.finish()

    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not listed(m, cell["name"]):
                continue
            reader = load_module(PERF / "layer_metrics" / f"{m['name']}.py")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        for m in bench["end_to_end"]:
            if not listed(m, cell["name"]):
                continue
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = ctx.quantities[traffic["end_to_end"][m["name"]]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device["memory_peak_bytes"] = memory_peak(cell["chips"])
    result = {"correct": not ctx.problems and failed == 0,
              "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": device}
    if ctx.reduced is not None:
        device["busy_s"] = ctx.reduced.busy_s
        device["window_s"] = ctx.reduced.window_s
        result["breakdown"] = ctx.reduced.breakdown()
        note(f"traced {ctx.reduced.window_s:.4f}s on {ctx.reduced.n_devices} "
             f"device planes; idle share {ctx.reduced.idle_share:.4f}")
    for p in ctx.problems:
        note(f"NOT CORRECT: {p}")
    note(f"quantities {ctx.quantities}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
