"""Diagnose the train-step MFU cliff (batch 4 ~80% -> batch 6-8 ~55%).

Round-4 VERDICT item 3: a 30-point MFU collapse from batch 4 to 6 on a
memory-rich chip needs a mechanism, not a comment. Written when no
profiler trace could be taken, so it uses the two compiler surfaces
that were available per batch size:

  - compiled.cost_analysis(): flops / bytes accessed -> arithmetic
    intensity the compiler thinks the program has;
  - compiled.memory_analysis(): peak / argument / output / temp HBM
    bytes -> whether a batch step crosses an allocation threshold that
    changes XLA's fusion or forces rematerialization;
  - the HLO module text, grep-counted for fusion kinds and all-reduce/
    copy/convert ops, to spot structural changes between batches.

Prints one summary line per batch plus a JSON artifact on stdout.

Usage: python benchmarks/mfu_analysis.py [--batches 2,4,6,8] [--seq N]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from rlo_tpu.models.transformer import (TransformerConfig,  # noqa: E402
                                        init_params, train_step)
from rlo_tpu.utils.device import bench_device  # noqa: E402


def analyze(cfg, params, batch, seq, peaks):
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)),
                         jnp.int32)

    @jax.jit
    def step(p, t):
        return train_step(p, t, cfg, lr=1e-4)

    lowered = step.lower(params, tokens)
    compiled = lowered.compile()
    rec = {"batch": batch}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        rec["flops"] = float(ca.get("flops", float("nan")))
        rec["bytes_accessed"] = float(ca.get("bytes accessed",
                                             float("nan")))
        if rec["bytes_accessed"]:
            rec["arith_intensity"] = rec["flops"] / rec["bytes_accessed"]
        if peaks is not None:
            # the roofline the compiler's own numbers imply
            t_flops = rec["flops"] / peaks.bf16_flops
            t_bytes = rec["bytes_accessed"] / peaks.hbm_bytes_per_s
            rec["compiler_roofline_bound"] = (
                "compute" if t_flops >= t_bytes else "memory")
            rec["t_flops_ms"] = t_flops * 1e3
            rec["t_bytes_ms"] = t_bytes * 1e3
    except Exception as e:  # noqa: BLE001 - record, don't die
        rec["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        for name in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "alias_size_in_bytes",
                     "generated_code_size_in_bytes"):
            v = getattr(ma, name, None)
            if v is not None:
                rec[name] = int(v)
        if "temp_size_in_bytes" in rec:
            rec["temp_gib"] = round(rec["temp_size_in_bytes"] / 2**30, 3)
    except Exception as e:  # noqa: BLE001
        rec["memory_analysis_error"] = repr(e)
    try:
        hlo = compiled.as_text()
        rec["hlo_counts"] = {
            "fusion": len(re.findall(r"\bfusion\b", hlo)),
            "kLoop": hlo.count("kLoop"),
            "kOutput": hlo.count("kOutput"),
            "custom-call": hlo.count("custom-call"),
            "copy": len(re.findall(r"\bcopy\(", hlo)),
            "convert": len(re.findall(r"\bconvert\b", hlo)),
            "while": len(re.findall(r"\bwhile\b", hlo)),
            "reduce": len(re.findall(r"\breduce\(", hlo)),
        }
    except Exception as e:  # noqa: BLE001
        rec["hlo_error"] = repr(e)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batches", default="2,4,6,8")
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    kind, peaks = bench_device(args.tiny)

    if args.tiny:
        cfg = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                n_layers=2, d_ff=256, dtype="float32")
        seq = min(args.seq, 64)
    else:
        cfg = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                n_layers=8, d_ff=4096, dtype="bfloat16")
        seq = args.seq
    params = init_params(jax.random.PRNGKey(0), cfg)

    out = []
    for b in [int(x) for x in args.batches.split(",")]:
        rec = analyze(cfg, params, b, seq, peaks)
        out.append(rec)
        flat = {k: v for k, v in rec.items() if k != "hlo_counts"}
        print(f"batch {b}: " + json.dumps(flat), file=sys.stderr)
    print(json.dumps({"device": kind, "seq": seq, "per_batch": out}))


if __name__ == "__main__":
    main()
