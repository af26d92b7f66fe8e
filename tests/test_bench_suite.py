"""Smoke tests for the BASELINE-config benchmark suite.

Each config must run end-to-end at --tiny sizes and print exactly one
valid JSON line with the contract fields. Every config runs in a
subprocess, exactly as `--config all` drives them; the scripts never
pick a platform, so the 8-device CPU mesh is named here, in the child's
environment.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

CPU_ENV = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")

SUITE = Path(__file__).resolve().parent.parent / "benchmarks" / "suite.py"
TRAIN = Path(__file__).resolve().parent.parent / "benchmarks" / "train_bench.py"


def test_decode_bench_emits_json_line():
    """The KV-cache decode benchmark must run end-to-end at --tiny
    sizes and emit one valid JSON line."""
    bench = Path(__file__).resolve().parent.parent / "benchmarks" / \
        "decode_bench.py"
    proc = subprocess.run(
        [sys.executable, str(bench), "--tiny"],
        capture_output=True, text=True, timeout=600, env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["unit"] == "tokens/s" and rec["value"] > 0


def test_train_bench_emits_json_line():
    """The train-step MFU benchmark (round-2 VERDICT item 5) must run
    end-to-end at --tiny sizes and emit one valid JSON line."""
    proc = subprocess.run(
        [sys.executable, str(TRAIN), "--tiny"],
        capture_output=True, text=True, timeout=600, env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["unit"] == "tokens/s" and rec["value"] > 0


#: configs that emit several comparison lines (ring vs bcast-gather +
#: the MPI_Bcast leg for 1; the TPU device leg for 5 when a chip is up)
MULTI_LINE = {1: (2, 4), 5: (1, 2)}


@pytest.mark.parametrize("config", [1, 2, 3, 4, 5])
def test_config_emits_json_line(config):
    proc = subprocess.run(
        [sys.executable, str(SUITE), "--config", str(config), "--tiny"],
        capture_output=True, text=True, timeout=600, env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    lo, hi = MULTI_LINE.get(config, (1, 1))
    assert lo <= len(lines) <= hi, proc.stdout
    for ln in lines:
        rec = json.loads(ln)
        assert rec["config"] == config
        assert set(rec) >= {"config", "metric", "value", "unit",
                            "vs_baseline"}
        assert rec["value"] > 0
        if rec.get("bound"):  # labeled bound: no comparison claimed
            assert rec["vs_baseline"] == 0
        else:
            assert rec["vs_baseline"] > 0


def test_native_bench_allreduce_correctness_gate():
    # the C-side harness self-verifies the reduction; a wrong result
    # raises instead of reporting a time
    from rlo_tpu.native.bindings import bench_allreduce
    t = bench_allreduce(4, 1024, reps=3)
    assert t > 0


def test_spec_bench_emits_json_line():
    """The speculative-decoding infra bench must run end-to-end at
    --tiny sizes and emit one valid JSON line."""
    bench = Path(__file__).resolve().parent.parent / "benchmarks" / \
        "spec_bench.py"
    proc = subprocess.run(
        [sys.executable, str(bench), "--tiny"],
        capture_output=True, text=True, timeout=600, env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    assert rec["unit"] == "x" and rec["value"] > 0


def test_attend_fetch_bench_rehearses_the_long_latent_shape():
    """The decode-attention bench at --tiny sizes (interpret mode): the
    Hy4 shape, a selection and a sink a head, runs every variant and
    the pipe@W ones at the widths they name."""
    bench = Path(__file__).resolve().parent.parent / "benchmarks" / \
        "attend_fetch_bench.py"
    proc = subprocess.run(
        [sys.executable, str(bench), "--tiny", "hy4"],
        capture_output=True, text=True, timeout=600, env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert len(lines) == 1, proc.stdout
    rows = json.loads(lines[0])["attend_fetch"]["hy4"]
    for ctx in ("mix", "full"):
        assert rows[f"{ctx}/pipe@1024"]["bk"] == 1024
        assert rows[f"{ctx}/pipe@2048"]["bk"] == 2048
        assert all(r["ms"] > 0 for r in rows.values())
