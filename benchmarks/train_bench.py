"""Flagship train-step benchmark: tokens/s and MFU on the live chip.

Round-2 VERDICT item 5: all recorded perf was collective
microbenchmarks; the model-driven entry (`__graft_entry__.entry`) had
never been timed. This measures the full causal-transformer train step
(forward, loss, grads, SGD update — the same `train_step` the dryrun
shards) with bench.py's chained methodology: K serially-dependent steps
inside one jit (params carry), minus the empty-chain dispatch floor.

MFU accounting (PaLM-style):
  flops/token = 6 * n_params                (fwd+bwd matmuls)
              + 12 * n_layers * d_model * seq * 0.5   (causal attention
                q·k and p·v, fwd+bwd, halved by the causal mask)
  MFU = achieved flops/s / peak, peak from rlo_tpu.utils.device.PEAKS
  for the device_kind found (v5e: 197e12 bf16).

Prints one JSON line; diagnostics to stderr. --tiny runs a toy config
(CPU-safe smoke shape for tests).
"""

import argparse
import json
import os
import sys
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import bench  # noqa: E402
from rlo_tpu.models.transformer import (TransformerConfig,  # noqa: E402
                                        init_params, train_step)
from rlo_tpu.utils.device import bench_device  # noqa: E402


def flops_per_token(cfg, n_params: int, seq: int) -> float:
    return (6.0 * n_params
            + 12.0 * cfg.n_layers * cfg.d_model * seq * 0.5)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="toy shapes (CPU smoke test)")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--seq", type=int, default=None)
    args = ap.parse_args()
    kind, peaks = bench_device(args.tiny)
    on_tpu = peaks is not None

    if args.tiny:
        cfg = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                n_layers=2, d_ff=256, dtype="float32")
        batch, seq = args.batch or 2, args.seq or 32
        k = 4
    else:
        # ~134M params; batch tuned on the chip (2026-07-30 sweep:
        # batch 2 -> 66%, 4 -> 74-84%, 6 -> 54%, 8 -> 56%, 16 -> 51%
        # MFU — batch 4 is a sharp sweet spot. Chunked loss
        # (cfg.loss_vocab_chunk) was tried and measured SLOWER at
        # every batch, so the falloff above 4 is not the logits
        # working set; left at the empirical optimum.
        cfg = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                n_layers=8, d_ff=4096, dtype="bfloat16")
        batch, seq = args.batch or 4, args.seq or 1024
        k = 8

    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)),
                         jnp.int32)

    @partial(jax.jit, static_argnames=("kk",))
    def chain(p, t, kk):
        def it(i, p):
            new_p, _ = train_step(p, t, cfg, lr=1e-4)
            return new_p
        return jax.lax.fori_loop(0, kk, it, p)

    def loop(p, t, kk):
        return jax.tree.leaves(chain(p, t, kk))[0]

    # median, not min: at batch >= 8 the dispatch floor is a sizable
    # fraction of the chain and min() picks the rep with the most
    # inflated floor estimate (a recorded batch-8 MFU of 1.22 — above
    # the physical peak — came from exactly that; see _chain_time)
    t_step = bench._chain_time(loop, params, tokens, k=k, stat="median")
    tok_per_step = batch * seq
    tok_s = tok_per_step / t_step
    fl_tok = flops_per_token(cfg, n_params, seq)
    achieved = tok_s * fl_tok
    mfu = achieved / peaks.bf16_flops if on_tpu else float("nan")

    # window-relative MFU: the recorded runs' DELIVERED throughput
    # drifted ~1.6x between windows (identical code recorded 0.52 and
    # 0.86 nominal MFU), so also time a roofline probe — a big bf16
    # matmul chain — in the SAME window and report the step's flops as
    # a fraction of the probe's achieved flops. This ratio is the
    # drift-immune number: how close the train step is to what the
    # chip will actually give you right now.
    mfu_rel = float("nan")
    if on_tpu:
        mm = 2048
        a = jnp.asarray(np.random.default_rng(1).standard_normal(
            (mm, mm)), jnp.bfloat16)

        @partial(jax.jit, static_argnames=("kk",))
        def mm_chain(a, kk):
            def it(i, x):
                return jnp.tanh(x @ a)  # tanh blocks trivial fusion
            return jax.lax.fori_loop(0, kk, it, a)

        t_mm = bench._chain_time(lambda x, kk: mm_chain(x, kk), a,
                                 k=256, stat="median")
        probe_flops = 2.0 * mm ** 3 / t_mm
        mfu_rel = achieved / probe_flops
        print(f"roofline probe: {probe_flops/1e12:.1f} TFLOP/s "
              f"({probe_flops/peaks.bf16_flops:.1%} of nominal peak this "
              f"window); window-relative MFU {mfu_rel:.1%}",
              file=sys.stderr)
    print(f"params={n_params/1e6:.1f}M batch={batch} seq={seq} "
          f"step={t_step*1e3:.2f} ms  {tok_s:,.0f} tok/s  "
          f"{achieved/1e12:.1f} TFLOP/s"
          + (f"  MFU={mfu:.1%} of {kind} bf16 peak" if on_tpu else
             "  (not a TPU: no MFU)"),
          file=sys.stderr)
    note = ""
    if on_tpu and mfu > 1.0:
        # same physical gate as bench.py's 819 GB/s clamp: an MFU
        # above peak proves floor-subtraction corruption, not speed
        note = (f" [measured {mfu:.3f} > 1.0 physical peak: floor-"
                f"corrupted rep; clamped]")
        print(f"WARNING: impossible MFU {mfu:.3f}{note}",
              file=sys.stderr)
        mfu = 1.0
        tok_s = min(tok_s, peaks.bf16_flops / fl_tok)
    rec = {
        "metric": f"causal-transformer train step, {n_params/1e6:.0f}M "
                  f"params, batch {batch} x seq {seq}, "
                  f"{kind}" + note,
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu, 4) if on_tpu else 0.0,
        "vs_baseline_meaning": "MFU fraction of the device_kind's bf16 "
                               "peak (rlo_tpu.utils.device.PEAKS)",
    }
    if on_tpu and mfu_rel == mfu_rel:
        rec["mfu_window_relative"] = round(mfu_rel, 4)
        rec["mfu_window_relative_meaning"] = (
            "step flops / same-window roofline-matmul flops — "
            "drift-immune (the chip's delivered peak moves ~1.6x "
            "between windows)")
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
