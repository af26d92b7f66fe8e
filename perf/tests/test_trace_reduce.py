"""The trace reduction against a small trace worked by hand
(data/small_trace.json, in lib/trace_reduce.py's plain form). Run by hand:

    python3 -m pytest perf/tests -q        (or python3 perf/tests/test_trace_reduce.py)

Window [1000, 11000] ns. Plane 0 is busy [1000,2000] + [2500,4500] (two
overlapping kernels) + [6000,7000] + [9000,11000] (clipped) = 6000 ns; plane
1 is busy 4000 ns. Plane 0's gaps: [2000,2500], [4500,6000], [7000,9000].
"""

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from lib import trace_reduce as tr  # noqa: E402

TRACE = tr.Trace.from_json(json.loads(
    (Path(__file__).parent / "data" / "small_trace.json").read_text()))


def close(a, b):
    return abs(a - b) < 1e-15


def test_busy_union_and_idle_share():
    r = tr.reduce_trace(TRACE)
    assert r.n_devices == 2
    assert close(r.window_s, 10000e-9)
    assert close(r.busy_s, (6000e-9 + 4000e-9) / 2)   # mean over the chips
    assert abs(r.idle_share - 0.5) < 1e-12


def test_union_and_gaps():
    busy = tr.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert busy == [(1, 4), (5, 8)]
    assert tr.gaps(busy, 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_kernel_sum_by_exact_name():
    r = tr.reduce_trace(TRACE)
    # durations are summed (not unioned), clipped to the window, and the
    # mean over the two planes is taken
    assert close(r.kernel_seconds["flash_decode"], 2500e-9 / 2)
    assert r.kernel_calls["flash_decode"] == 2
    # paged_flash_decode is its own kernel, never flash_decode
    assert close(r.kernel_seconds["paged_flash_decode"], 1000e-9 / 2)
    # autodiff wrappers are stripped; the event is clipped at the window
    assert close(r.kernel_seconds["flash_bwd_dkv"], 2000e-9 / 2)
    assert close(r.op_seconds["flash_decode_f32_2_4_1_64_"], 2500e-9)
    assert "transpose_jvp_flash_bwd_dkv___f32_4_8_64_" in r.op_seconds
    # the event before the window is not counted
    assert close(r.op_seconds["fusion_f32_8_"], 1000e-9 + 4000e-9)


def test_gap_attribution_goes_to_the_innermost_span():
    r = tr.reduce_trace(TRACE)
    want = {"admit": 700e-9, "prefill": 300e-9, "unit": 2500e-9,
            "round": 500e-9}
    assert set(r.idle_by_span) == set(want)
    for k, v in want.items():
        assert close(r.idle_by_span[k], v), (k, r.idle_by_span[k], v)
    assert close(sum(r.idle_by_span.values()), 4000e-9)


def test_unannotated_gap():
    t = tr.Trace(ops={"/device:TPU:0": [("%a = f32[1] add()", 10, 10)]},
                 host=[("perf.window", 0, 100)])
    r = tr.reduce_trace(t)
    assert close(r.idle_by_span["unannotated"], 90e-9)


def test_modules_inside_the_window_only():
    r = tr.reduce_trace(TRACE)
    assert list(r.module_ms) == ["jit_round_fn"]
    assert len(r.module_ms["jit_round_fn"]) == 1
    assert abs(r.module_ms["jit_round_fn"][0] - 2200e-6) < 1e-12


def test_breakdown_is_ranked_and_capped():
    b = tr.reduce_trace(TRACE).breakdown(top=2)
    assert [k for k, _ in b["device_ops"]] == [
        "fusion_f32_8_", "flash_decode_f32_2_4_1_64_"]
    assert b["idle_gaps"][0][0] == "unit" and len(b["idle_gaps"]) == 2


def test_containers_are_recognised():
    assert tr._CONTAINERS.search(
        "%while.5 = (s32[], f32[3]{0}) while((s32[], f32[3]{0}) %t.3), "
        "condition=%c, body=%b")
    assert not tr._CONTAINERS.search(
        "%fusion.1 = f32[3]{0} fusion(f32[3]{0} %while.5), kind=kLoop")


if __name__ == "__main__":
    for name, fn in sorted(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
