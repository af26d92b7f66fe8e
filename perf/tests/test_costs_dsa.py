"""The counts behind ``index_score_roofline`` and ``sparse_attend_roofline``,
worked by hand, and the longctx-decode mix's fixed set."""
import json
import sys
from pathlib import Path

PERF = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(PERF))

from lib import kernel_costs_dsa, kernel_costs_moe, traffic  # noqa: E402


def test_index_score_counts_one_key_stream_for_all_heads():
    fl, by = kernel_costs_dsa.index_score([13000, 9000], 64, 128)
    # per key and head: a 128-wide dot, the weight, the sum over heads
    assert fl == 2 * 64 * (13000 + 9000) * 129
    # keys once whatever the heads; q bf16 and w f32 in, f32 scores out
    assert by == ((13000 + 9000) * 128 * 2 + 2 * 64 * 128 * 2
                  + 2 * 64 * 4 + (13000 + 9000) * 4)
    fl1, by1 = kernel_costs_dsa.index_score([13000, 9000], 1, 128)
    assert fl == 64 * fl1 and by - by1 == 2 * 63 * (128 * 2 + 4)


def test_sparse_attend_counts_the_selected_rows_alone():
    got = kernel_costs_dsa.sparse_attend([13000, 1500], 2048, 128, 576, 512)
    assert got == kernel_costs_moe.mla_decode([2048, 1500], 128, 576, 512)
    # 13k of context cost what 2048 rows cost
    assert got == kernel_costs_dsa.sparse_attend([2048, 1500], 2048, 128,
                                                 576, 512)


def test_longctx_decode_is_a_fixed_set_of_32():
    mix = json.loads((PERF / "traffic" / "longctx-decode.json").read_text())
    req = mix["requests"]
    pairs = traffic.multiset(req)
    assert len(pairs) == 32 == mix["server"]["n_slots"]
    assert min(p for p, _ in pairs) == 8192
    assert max(p for p, _ in pairs) == 18432
    assert {o for _, o in pairs} == {6144}
    assert max(p + o for p, o in pairs) <= mix["server"]["max_len"]
    lens = sorted(p for p, _ in pairs)
    assert 12500 < lens[15] < 13000 < lens[16] < 13500     # the median
    # every seed replays the same multiset, in another order
    a = [next(s) for s in [traffic.ordered(req, 1)] for _ in range(32)]
    b = [next(s) for s in [traffic.ordered(req, 2)] for _ in range(32)]
    assert sorted(a) == sorted(b) == sorted(pairs) and a != b
