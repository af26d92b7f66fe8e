"""The four readers of the server's own span totals and work counters, each
on a hand-made ``ctx.counters``, and the whole command at the toy size on
the CPU printing all eight per-layer metrics of ``gpt2m-decode-sat``. Run by
hand: ``python3 -m pytest perf/tests -q``."""

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

PERF = Path(__file__).resolve().parents[1]
ROOT = PERF.parent
sys.path.insert(0, str(PERF))
import run as perf_run  # noqa: E402

#: 38 rounds of a window worked by hand: 30.4 s inside step_round of which
#: 27.74 s waiting for the device, 323 admissions of 7 ms each, 3.8 ms of
#: that blocked on the first token, prompts of 31 654 tokens run as 62 016
COUNTERS = {
    "serve.rounds": 38,
    "serve.step_round_ns": 30_400_000_000,
    "serve.round.wait_ns": 27_740_000_000,
    "serve.admissions": 323,
    "serve.admit_ns": 2_261_000_000,
    "serve.admit.first_token_sync_ns": 1_227_400_000,
    "serve.prefill_tokens": 31_654,
    "serve.prefill_padded_tokens": 62_016,
}
#: reader -> (value on COUNTERS, the counter its divisor is)
WANT = {
    "round_host_ms": (70.0, "serve.rounds"),
    "admit_ms_per_request": (7.0, "serve.admissions"),
    "first_token_sync_ms": (3.8, "serve.admissions"),
    "prefill_useful_token_pct": (100.0 * 31_654 / 62_016,
                                 "serve.prefill_padded_tokens"),
}


def reader(name):
    return perf_run.load_module(PERF / "layer_metrics" / f"{name}.py")


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_made_counters(name):
    value, divisor = WANT[name]
    read = reader(name).read
    notes = []
    ctx = lambda counters: SimpleNamespace(counters=counters,
                                           note=notes.append)
    assert read(ctx(dict(COUNTERS))) == pytest.approx(value, rel=1e-12)
    # a divisor of 0 and a program without the counters (the parent
    # commit) both leave the metric out of the line, and do not raise
    assert read(ctx({**COUNTERS, divisor: 0})) is None
    assert read(ctx({"serve.rounds": 38, "serve.tokens_out": 1,
                     "perf.admitted": 9})) is None
    if name == "round_host_ms":     # and says where the 70 ms went
        assert "round.wait 730.000 (0.00)" in notes[0]


def test_every_new_reader_has_its_entry_and_the_cell():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in WANT:
        m = by_name[name]
        assert (m["layer"], m["moves"], m["source"]) == (
            "server", "serve_tok_s", "program_counter")
        assert m["workloads"] == ["gpt2m-decode-sat"]


def test_cpu_rehearsal_prints_all_eight_per_layer_metrics():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(PERF / "run.py"), "--workload",
         "gpt2m-decode-sat", "--seed", "1", "--seconds", "2", "--trace",
         "1", "--tiny"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # no device plane on the CPU: the two device_trace readers find
    # nothing to read there, as on any trace without the kernels
    on_cpu = {"round_ms_p50", "slot_step_useful_pct", *WANT}
    assert on_cpu <= set(got) <= on_cpu | {"decode_step_ms",
                                           "flash_decode_roofline"}
    assert 0 < got["round_host_ms"]["value"] < got["round_ms_p50"]["value"]
    assert 0 < got["first_token_sync_ms"]["value"] < \
        got["admit_ms_per_request"]["value"]
    assert 0 < got["prefill_useful_token_pct"]["value"] <= 100
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert not gaps or any(k.startswith("serve.") for k in gaps)
