"""The traffic generator and the kernels' operation-and-byte counts. Run by
hand: ``python3 -m pytest perf/tests -q`` (or this file with python3)."""

import itertools
import json
import sys
from pathlib import Path

PERF = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(PERF))
from lib import kernel_costs as kc  # noqa: E402
from lib import traffic  # noqa: E402

REQ = json.loads((PERF / "traffic" / "decode-sat.json").read_text())["requests"]


def stream(seed, n):
    return list(itertools.islice(traffic.ordered(REQ, seed), n))


def test_multiset_is_the_same_for_every_seed_and_the_order_differs():
    pool = REQ["pool"]
    fixed = sorted(traffic.multiset(REQ))
    a, b = stream(1, pool), stream(3_000_000_019, pool)
    assert sorted(a) == sorted(b) == fixed
    assert a != b
    # and again on the second pass over the pool
    assert sorted(stream(7, 2 * pool)[pool:]) == fixed


def test_lengths_are_what_the_file_says():
    pairs = traffic.multiset(REQ)
    prompts = sorted(p for p, _ in pairs)
    outputs = sorted(o for _, o in pairs)
    assert (prompts[0], prompts[-1]) == (32, 256)
    assert (outputs[0], outputs[-1]) == (128, 768)
    assert prompts[len(prompts) // 2] in (96, 97)
    assert outputs[len(outputs) // 2] in (320, 321)
    assert max(p + o for p, o in pairs) <= 1024


def test_every_block_is_a_fair_sample():
    block, pool = REQ["block"], REQ["pool"]
    got = stream(11, pool)
    total = sum(o for _, o in got) / pool
    for k in range(pool // block):
        mean = sum(o for _, o in got[k * block:(k + 1) * block]) / block
        assert abs(mean - total) / total < 0.03, (k, mean, total)


def test_same_seed_same_tokens():
    a = traffic.token_ids(3_000_000_019, 5, 40, 50257)
    b = traffic.token_ids(3_000_000_019, 5, 40, 50257)
    assert (a == b).all() and a.min() >= 0 and a.max() < 50257
    assert (traffic.token_ids(3_000_000_019, 6, 40, 50257) != a).any()


def test_stationary_cut_is_stratified():
    cuts = traffic.stationary_cut(REQ, 5, 96)
    assert len(cuts) == 96 and min(cuts) > 0 and max(cuts) < 1
    assert abs(sum(cuts) / 96 - 0.5) < 1e-9


def test_flash_decode_counts():
    # one row, context 100, 16 heads of 64, MHA, bf16:
    # K and V: 2 * 16 * 64 * 100 * 2 B = 409600; q and o: 2 * 16*64*2 = 4096
    # QK^T and PV: 2 products * 2 ops * 16 * 64 * 100 = 409600
    assert kc.flash_decode([100], 16, 16, 64) == (409600.0, 413696.0)
    # two rows add up
    f, b = kc.flash_decode([100, 300], 16, 16, 64)
    assert f == 4 * 409600.0 and b == 4 * 409600.0 + 2 * 4096


def test_flash_fwd_and_bwd_counts():
    # seq 4, one head of 8: 10 (q, k) pairs in the lower triangle
    # forward: 2 products * 2 ops * 10 * 8 = 320; bytes 4 * (4*8*2 + 4) = 272
    assert kc.flash_fwd(4, 1, 8) == (320.0, 272.0)
    # backward: 5 products -> 800; bytes 4 * (8*8*2 + 8) = 544
    assert kc.flash_bwd(4, 1, 8) == (800.0, 544.0)
    # gpt2-medium at batch 16: 256 folded heads of 64 at 1024 positions
    f, b = kc.flash_fwd(1024, 256, 64)
    assert f == 4 * (1024 * 1025 / 2) * 64 * 256
    assert b == 256 * 1024 * (4 * 64 * 2 + 4)


def test_fused_combine_and_busbw():
    assert kc.fused_combine(1 << 22) == (float(1 << 22), 3.0 * (1 << 24))
    # 256 MiB on 4 ranks in 16.6 ms: 2 * 3/4 * 268435456 / 0.0166
    bw = kc.allreduce_busbw(1 << 28, 4, 0.0166)
    assert abs(bw - 1.5 * 268435456 / 0.0166) < 1


def test_train_flops_per_token():
    # 6 N + 12 L d seq / 2
    assert kc.train_flops_per_token(100, 2, 8, 16) == 600 + 12 * 2 * 8 * 8


if __name__ == "__main__":
    for name, fn in sorted(globals().items()):
        if name.startswith("test_"):
            fn()
            print("ok", name)
