"""Record the auxiliary perf numbers as an in-repo artifact.

Round-4 VERDICT item 3: the MFU / decode / TTFT / GQA numbers lived in
code comments and stderr — nothing a reviewer could regression-track.
This runs each auxiliary bench as a subprocess — one at a time, because
a chip belongs to one process at a time, and for the same reason this
parent never imports jax: the device facts in ``meta`` come from a first
leg that prints them and exits — and writes BENCH_extra.json at the repo
root: one entry per leg with the bench's own JSON line (or its
diagnostic tail, for text-only legs like flash_bench) plus the exit
status, so a failed leg is recorded as failed instead of silently
absent.

Usage: python benchmarks/record_extra.py [--skip NAME ...] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: (name, argv, timeout_sec) — argv relative to the repo root
LEGS = [
    ("train_mfu_batch4",
     [sys.executable, "benchmarks/train_bench.py"], 2400),
    ("train_mfu_batch8",
     [sys.executable, "benchmarks/train_bench.py", "--batch", "8"], 2400),
    ("decode_tok_s",
     [sys.executable, "benchmarks/decode_bench.py"], 2400),
    ("ttft_blockwise_prefill_b1",
     [sys.executable, "benchmarks/decode_bench.py", "--ttft",
      "--plen", "1024", "--batch", "1"], 2400),
    ("ttft_blockwise_prefill_b4",
     [sys.executable, "benchmarks/decode_bench.py", "--ttft",
      "--plen", "1024", "--batch", "4"], 2400),
    ("flash_gqa_compact_vs_repeated",
     [sys.executable, "benchmarks/flash_bench.py", "--seq", "4096",
      "--heads", "8", "--dim", "128", "--gqa", "2"], 2400),
    # GQA where it is measurable on one chip (round-5 item 3): the
    # decode cache-bandwidth win at long prompt, and the servable-
    # capacity win proven by allocation + a real decode step
    ("decode_gqa_compare",
     [sys.executable, "benchmarks/decode_bench.py", "--compare-gqa"],
     2400),
    ("decode_capacity",
     [sys.executable, "benchmarks/decode_bench.py", "--capacity"],
     2400),
    # long-context decode: the cache (not the weights) is the HBM
    # bound. decode_longctx records the absolute number through the
    # flash-decode kernel; decode_kv_compare measures the int8-cache
    # speedup with INTERLEAVED pairs (separate runs sit in different
    # chip-throughput windows; their ratio is meaningless) — measured
    # 1.17-1.43x across windows at batch 32 / plen 1024 (2026-07-31).
    ("decode_longctx",
     [sys.executable, "benchmarks/decode_bench.py",
      "--prompt-len", "1024"], 2400),
    ("decode_kv_compare",
     [sys.executable, "benchmarks/decode_bench.py",
      "--compare-kv"], 2400),
    # speculative-decoding infra costs at batch 1 (the latency-bound
    # serving case): round-4 recorded verify of gamma=4 = 1.12 decode
    # steps (~90% of ideal), draft step 0.04-0.08 of a target step
    ("spec_verify_b1",
     [sys.executable, "benchmarks/spec_bench.py", "--batch", "1"],
     2400),
    # round-5 item 2: the REALIZED speculative speedup — distill a
    # draft on-chip, measure acceptance and end-to-end tokens/s
    ("spec_e2e_b1",
     [sys.executable, "benchmarks/spec_bench.py", "--e2e",
      "--gamma", "8", "--draft-layers", "1", "--draft-dim", "256"],
     3000),
    # round-5 item 1: the decode HBM budget decomposition (per-
    # component GB/s vs a same-window streaming probe)
    ("decode_budget",
     [sys.executable, "benchmarks/decode_analysis.py",
      "--plen", "1024"], 3300),
    # round-5 item 6: continuous batching vs naive batch-restart
    ("serve_continuous",
     [sys.executable, "benchmarks/serve_bench.py"], 2400),
]


def run_leg(name, argv, timeout):
    t0 = time.time()
    try:
        proc = subprocess.run(argv, cwd=str(REPO), capture_output=True,
                              text=True, timeout=timeout)
        rc = proc.returncode
        out, err = proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc, out, err = -1, e.stdout or "", f"timeout after {timeout}s"
        out = out if isinstance(out, str) else out.decode()
    rec = {"name": name, "argv": argv[1:], "rc": rc,
           "wall_s": round(time.time() - t0, 1)}
    # the benches print exactly one JSON line on stdout; text-only
    # legs (flash_bench) get their informative stdout tail instead
    for line in reversed(out.strip().splitlines()):
        try:
            rec["result"] = json.loads(line)
            break
        except ValueError:
            continue
    if "result" not in rec:
        # every leg must emit a parseable JSON result line — a leg
        # that does not is recorded as BROKEN, not silently tailed
        # (round-4's flash_gqa leg regression-tracked nothing)
        rec["unparsed"] = True
        rec["stdout_tail"] = out.strip().splitlines()[-8:]
        if rc == 0:
            rc = 1          # broken, not silently tailed
            rec["rc"] = 1
    if rc != 0:
        rec["stderr_tail"] = (err or "").strip().splitlines()[-8:]
    print(f"  {name}: rc={rc} ({rec['wall_s']}s)", file=sys.stderr)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--skip", action="append", default=[])
    ap.add_argument("--only", action="append", default=[])
    ap.add_argument("--out", default=str(REPO / "BENCH_extra.json"))
    args = ap.parse_args()

    # the device as a child sees it (rlo_tpu.utils.device.describe);
    # the child exits, and frees the chip, before the first bench leg
    dev = run_leg("device", [sys.executable, "-m",
                             "rlo_tpu.utils.device"], 300)
    if dev["rc"] != 0:
        print(json.dumps(dev, indent=1), file=sys.stderr)
        return 1
    meta = {"device": dev["result"], "recorded_unix": int(time.time())}
    legs = []
    for name, argv, timeout in LEGS:
        if name in args.skip or (args.only and name not in args.only):
            continue
        legs.append(run_leg(name, argv, timeout))
    out_path = Path(args.out)
    if (args.only or args.skip) and out_path.exists():
        # partial rerun: merge into the existing artifact by leg name
        # so re-measuring one flaky leg keeps the rest; the replaced
        # measurement moves into the leg's `prior` list — run-to-run
        # variance is itself part of the record
        prev = json.loads(out_path.read_text())
        merged = {r["name"]: r for r in prev.get("legs", [])}
        for r in legs:
            old = merged.get(r["name"])
            if old is not None:
                r["prior"] = old.pop("prior", []) + [old]
            merged[r["name"]] = r
        legs_out = [merged[n] for n, _, _ in LEGS if n in merged]
    else:
        legs_out = legs
    out = {"meta": meta, "legs": legs_out}
    out_path.write_text(json.dumps(out, indent=1) + "\n")
    print(f"wrote {args.out} ({len(legs_out)} legs)", file=sys.stderr)
    return 0 if all(r["rc"] == 0 for r in legs) else 1


if __name__ == "__main__":
    sys.exit(main())
