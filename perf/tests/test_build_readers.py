"""The three readers of the program's build log (``build_trace_s``,
``build_nested_trace_pct``, ``build_compile_s``), each on records made by
hand, and the whole command at the toy size on the CPU printing all three
for ``gpt2m-decode-sat``. Run by hand: ``python3 -m pytest perf/tests -q``."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

PERF = Path(__file__).resolve().parents[1]
ROOT = PERF.parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(PERF))
import run as perf_run  # noqa: E402

NAMES = ("build_trace_s", "build_nested_trace_pct", "build_compile_s")
CELLS = ["gpt2m-decode-sat", "dsv3-ep16-reason-sat",
         "dsv32-ep32-longctx-decode", "sdar-6l-blockgen-sat"]

#: a start worked by hand, (fun_name, phase, span, counts): the server's
#: round (traced 6 s of its own with 3 s nested, lowered 2 s with 1 s of
#: traces nested, read from the cache in 0.5 s), its admission (compiled,
#: 4 s) and an eager program of its constructor; the harness's weights and
#: its kernel check, under no span, and another subsystem's program
RECORDS = [
    ("round_fn", "trace", "perf.serve.round.dispatch",
     {"trace_ns": 6_000_000_000, "trace_nested_ns": 3_000_000_000}),
    ("round_fn", "lower", "perf.serve.round.dispatch",
     {"lower_ns": 2_000_000_000, "trace_nested_ns": 1_000_000_000}),
    ("round_fn", "compile", "perf.serve.round.dispatch",
     {"compile_ns": 500_000_000, "programs": 1, "cache_hits": 1}),
    ("admit_rows", "compile", "perf.serve.admit.prefill_dispatch",
     {"compile_ns": 4_000_000_000, "programs": 1, "cache_misses": 1}),
    ("broadcast_in_dim", "trace", "perf.serve.init",
     {"trace_ns": 500_000_000}),
    ("<lambda>", "trace", None,
     {"trace_ns": 7_000_000_000, "trace_nested_ns": 11_000_000_000}),
    ("round_fn", "lower", None, {"lower_ns": 5_000_000_000}),
    ("allreduce", "compile", "perf.fabric.step",
     {"compile_ns": 9_000_000_000, "programs": 1}),
]
WANT = {"build_trace_s": 12.5, "build_nested_trace_pct": 100 * 4 / 10.5,
        "build_compile_s": 4.5}


def reader(name):
    return perf_run.load_module(PERF / "layer_metrics" / f"{name}.py")


def hand_made_log(tracing, records):
    log = tracing.BuildLog()
    for fun_name, phase, span, counts in records:
        span = span and SimpleNamespace(name=span, metrics=None,
                                        counter="serve.x")
        build = tracing.Build(fun_name, phase, span, 0)
        build.counts.update(counts)
        log.records.append(build)
    return log


@pytest.mark.parametrize("name", NAMES)
def test_reader_on_hand_made_records(name, monkeypatch):
    from rlo_tpu.utils import tracing
    notes = []
    ctx = SimpleNamespace(counters={}, note=notes.append)
    monkeypatch.setattr(tracing, "BUILDS", hand_made_log(tracing, RECORDS))
    assert reader(name).read(ctx) == pytest.approx(WANT[name], rel=1e-12)
    (said,) = notes
    if name == "build_trace_s":     # what is not the server's is printed
        assert "server: 5 roots, trace 6.500 s own + 4.000 s nested, " \
            "lower 2.000 s" in said
        assert "outside the server: 3 roots, trace 7.000 s own + 11.000 " \
            "s nested, lower 5.000 s, compile 9.000 s for 1 programs, " \
            "the costliest <lambda> 18.000 s, allreduce 9.000 s, " \
            "round_fn 5.000 s" in said
    elif name == "build_nested_trace_pct":
        assert "round_fn 4.000 s (own 6.000 s, under " \
            "perf.serve.round.dispatch)" in said
    else:
        assert "2 programs, cache hits 1 misses 1; the costliest " \
            "admit_rows 4.000 s (perf.serve.admit.prefill_dispatch), " \
            "round_fn 0.500 s (perf.serve.round.dispatch)" in said
    # a log that holds nothing of the server, and a program without the
    # log (the parent commit), leave the metric out and do not raise
    monkeypatch.setattr(tracing, "BUILDS",
                        hand_made_log(tracing, RECORDS[5:]))
    assert reader(name).read(ctx) is None
    monkeypatch.delattr(tracing, "BUILDS")
    assert reader(name).read(ctx) is None
    assert len(notes) == 1


def test_every_build_reader_has_its_entry_and_the_four_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NAMES)
    layers = dict(zip(NAMES, ("model step", "model step", "server")))
    for m in bench["per_layer"][-3:]:
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            layers[m["name"]], "setup_s", "program_counter", "lower")
        assert m["workloads"] == CELLS


def test_cpu_rehearsal_prints_the_three_build_metrics():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload",
         "gpt2m-decode-sat", "--seed", "1", "--seconds", "2", "--trace",
         "1", "--tiny"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = {name: line["metrics"][name]["value"] for name in NAMES}
    # the harness's meter counts a nested trace again in every trace
    # around it, and the whole process: the log's seconds lie under it
    parts = next(ln for ln in out.stdout.splitlines()
                 if ln.startswith("# setup_s"))
    meter = json.loads(parts[parts.index("compile {") + len("compile "):]
                       .replace("'", '"'))
    assert 0 < got["build_trace_s"] < \
        meter["jaxpr_trace_s"] + meter["to_mlir_s"]
    assert 0 < got["build_nested_trace_pct"] < 100
    assert 0 < got["build_compile_s"] <= meter["backend_compile_s"] + 0.01
    said = [ln for ln in out.stdout.splitlines()
            if ln.startswith("# build log, server")]
    assert len(said) == 3 and "round_fn" in said[1] and \
        "outside the server" in said[0]
