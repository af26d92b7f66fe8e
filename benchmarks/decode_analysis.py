"""Dissect the decode step's HBM budget (round-5 VERDICT item 1).

decode_bench recorded 44-46% of the v5e's 819 GB/s weight+cache
streaming ceiling and more than half the bound was unaccounted. This does for
decode what mfu_analysis.py did for the train-step MFU cliff: split
the step into its streaming components, measure each AT ITS EXACT
DECODE SHAPE in the same chip window, and reconcile against both the
compiler's own byte accounting and a same-window streaming probe.

Two accounting surfaces:

1. Compiler: `jit(decode_step).lower().compile().cost_analysis()`
   gives the bytes XLA thinks the program touches — if that exceeds
   the model's weight+cache bytes, XLA is moving extra traffic
   (un-hoisted converts, cache copies); if it matches, the gap is
   delivery rate, not extra bytes.

2. Chip, per component (chained fori_loops, median stat, all in one
   window alongside a big-matmul streaming probe):
     - ffn matmuls   (b, d) x (d, ff) x (ff, d)      - weights stream
     - qkv + wo      (b, d) x (d, 3d), (b, d) x (d, d)
     - logits head   (b, d) x (d, vocab)
     - cache attend  flash_decode at (b, kvh, hd, max_len)
     - full step     decode_step (fixed mid-window position)
   Component GB/s = known bytes / measured time; the residual
   (step - sum of parts) is elementwise + scan overhead.

The streaming probe's achieved GB/s is the window's DELIVERED
bandwidth — the fraction-of-deliverable number is drift-immune the
same way train_bench's window-relative MFU is.

Usage: python benchmarks/decode_analysis.py [--tiny] [--batch N]
       [--plen N]   (the JSON record always prints on stdout)
"""

from __future__ import annotations

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

from rlo_tpu.models.generate import decode_step, init_kv_cache  # noqa: E402
from rlo_tpu.models.transformer import (TransformerConfig,  # noqa: E402
                                        init_params)
from rlo_tpu.utils.device import bench_device  # noqa: E402

#: only sizes the chain (how fast a component could possibly stream);
#: never printed, never a denominator
_CHAIN_SEED_BYTES_PER_S = 819e9


def chain_time(run, x0, exp_bytes, *, pairs=7, label="", max_k=4096):
    """Per-op seconds for a chained loop ``run(x0, kk)``.

    Compile-budget-aware replacement for bench._chain_time, whose
    escalating calibration recompiles at every k. Here k comes from
    the component's own byte model (chain long enough that k ops dwarf
    the per-call dispatch floor), exactly TWO compiles per probe (k
    and 2k), and per-op = median over interleaved pairs of
    (t(2k) - t(k)) / k — the floor and window drift cancel inside
    each pair."""
    import time
    t_exp = max(exp_bytes / _CHAIN_SEED_BYTES_PER_S, 2e-7)
    k = int(min(max_k, max(8, 0.25 / t_exp)))
    np.asarray(run(x0, k))
    np.asarray(run(x0, 2 * k))  # compile + warm both
    np.asarray(run(x0, k))
    diffs = []
    for _ in range(pairs):
        t0 = time.perf_counter()
        np.asarray(run(x0, 2 * k))
        t1 = time.perf_counter()
        np.asarray(run(x0, k))
        t2 = time.perf_counter()
        diffs.append((t1 - t0) - (t2 - t1))
    med = float(np.median(diffs))
    if med <= 0:
        raise RuntimeError(f"{label}: chained diff swallowed by noise "
                           f"(median {med*1e3:.3f} ms at k={k})")
    mad = float(np.median(np.abs(np.asarray(diffs) - med)))
    print(f"  {label}: k={k} per-op {med/k*1e6:.1f} us "
          f"(spread {mad/med:.0%})", file=sys.stderr)
    return med / k


def _count_params(params):
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


def compiler_accounting(params, cfg, batch, max_len, pos):
    """XLA's own byte/flop accounting for ONE decode step."""
    cache = init_kv_cache(cfg, batch, max_len)
    tok = jnp.zeros((batch,), jnp.int32)

    @jax.jit
    def step(p, t, c):
        return decode_step(p, t, pos, c, cfg)

    compiled = step.lower(params, tok, cache).compile()
    rec = {}
    try:
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        rec["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
        rec["flops"] = float(ca.get("flops", 0.0))
    except Exception as e:  # noqa: BLE001
        rec["cost_analysis_error"] = repr(e)
    try:
        ma = compiled.memory_analysis()
        rec["temp_bytes"] = int(getattr(ma, "temp_size_in_bytes", 0))
        rec["arg_bytes"] = int(getattr(ma, "argument_size_in_bytes", 0))
        rec["out_bytes"] = int(getattr(ma, "output_size_in_bytes", 0))
    except Exception as e:  # noqa: BLE001
        rec["memory_analysis_error"] = repr(e)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--plen", type=int, default=16)
    ap.add_argument("--n-window", type=int, default=192,
                    help="decode window (max_len = plen + window), "
                         "matching decode_bench's n2")
    args = ap.parse_args()
    kind, peaks = bench_device(args.tiny)
    on_tpu = peaks is not None

    if args.tiny:
        cfg = TransformerConfig(vocab=512, d_model=128, n_heads=4,
                                n_layers=2, d_ff=512, dtype="float32")
        batch, plen, win = 2, 8, 16
    else:
        cfg = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                n_layers=8, d_ff=4096,
                                dtype="bfloat16")
        batch, plen, win = args.batch, args.plen, args.n_window

    # production caches round the seq axis to the 128-lane tile
    # (init_kv_cache) — the probes must measure the same shape or the
    # attend leg pays materialized pads production avoids
    max_len = -(-(plen + win) // 128) * 128
    # mid-differencing-window position (decode_bench differences
    # max_new = win/3 vs win): component probes use it; the flash
    # attend streams the FULL allocated max_len regardless
    pos = plen + (win // 3 + win) // 2
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = _count_params(params)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    wbytes = 2 if cfg.dtype == "bfloat16" else 4
    rng = np.random.default_rng(0)

    d, ff, vocab = cfg.d_model, cfg.d_ff, cfg.vocab
    nl, kvh, hd = cfg.n_layers, cfg.kv_heads, cfg.head_dim
    nh = cfg.n_heads

    # ---- component byte model (per step) ---------------------------
    comp_bytes = {
        "ffn": nl * 2 * d * ff * wbytes,
        "qkv_wo": nl * (d * (nh + 2 * kvh) * hd + nh * hd * d) * wbytes,
        "logits": d * vocab * wbytes,
        "attend": nl * 2 * batch * kvh * max_len * hd * wbytes,
    }
    other_w = (n_params * wbytes
               - comp_bytes["ffn"] - comp_bytes["qkv_wo"]
               - comp_bytes["logits"])  # embed gather table, norms
    model_bytes = n_params * wbytes + comp_bytes["attend"]

    # ---- compiler accounting ---------------------------------------
    ca = compiler_accounting(params, cfg, batch, max_len, pos)
    print(f"component byte model: "
          + "  ".join(f"{n}={b/2**20:.0f}MB"
                      for n, b in comp_bytes.items())
          + f"  other-weights={other_w/2**20:.0f}MB  "
          f"total={model_bytes/2**20:.0f}MB/step", file=sys.stderr)
    if "bytes_accessed" in ca:
        print(f"compiler: bytes_accessed={ca['bytes_accessed']/2**20:.0f}"
              f"MB ({ca['bytes_accessed']/model_bytes:.2f}x the model) "
              f"temp={ca.get('temp_bytes', 0)/2**20:.0f}MB",
              file=sys.stderr)

    # ---- chip probes (one window) ----------------------------------
    x0 = jnp.asarray(rng.standard_normal((batch, d)), dt)

    def chain(body):
        @partial(jax.jit, static_argnames=("kk",))
        def run(x, kk):
            def it(i, x):
                return body(x)
            return jax.lax.fori_loop(0, kk, it, x)
        return run

    # streaming probe: weights too big for VMEM residency, re-read
    # from HBM every iteration — the window's delivered GB/s
    mm = 4096
    W_probe = jnp.asarray(rng.standard_normal((mm, mm)), dt)
    xp = jnp.asarray(rng.standard_normal((batch, mm)), dt)
    probe = chain(lambda x: jnp.tanh(x @ W_probe))
    t_probe = chain_time(probe, xp, mm * mm * wbytes, label="probe")
    gbps_window = mm * mm * wbytes / t_probe / 1e9

    # ffn at decode shape
    W1 = jnp.asarray(rng.standard_normal((d, ff)) * 0.02, dt)
    W2 = jnp.asarray(rng.standard_normal((ff, d)) * 0.02, dt)
    ffn = chain(lambda x: jnp.tanh((jnp.tanh(x @ W1)) @ W2))
    t_ffn1 = chain_time(ffn, x0, 2 * d * ff * wbytes, label="ffn")

    # qkv + wo at decode shape
    Wqkv = jnp.asarray(
        rng.standard_normal((d, (nh + 2 * kvh) * hd)) * 0.02, dt)
    Wo = jnp.asarray(rng.standard_normal((nh * hd, d)) * 0.02, dt)
    qkv = chain(lambda x: jnp.tanh(
        (jnp.tanh(x @ Wqkv)[:, :nh * hd]) @ Wo))
    t_qkv1 = chain_time(
        qkv, x0, (d * (nh + 2 * kvh) * hd + nh * hd * d) * wbytes,
        label="qkv_wo")

    # logits head at decode shape (+ fold back so the chain stays
    # (b, d) -> (b, d) and data-dependent)
    We = jnp.asarray(rng.standard_normal((vocab, d)) * 0.02, dt)
    fold = jnp.asarray(rng.standard_normal((vocab, d)) * 1e-4, dt)
    logits_c = chain(lambda x: jnp.tanh((x @ We.T) @ fold))
    t_logits = chain_time(logits_c, x0, 2 * d * vocab * wbytes,
                          label="logits")
    logits_extra = d * vocab * wbytes  # the fold matrix also streams

    # cache attend at decode shape (one layer; x8 in accounting)
    kc = jnp.asarray(rng.standard_normal((batch, kvh, hd, max_len)),
                     dt)
    vc = jnp.asarray(rng.standard_normal((batch, kvh, hd, max_len)),
                     dt)
    from rlo_tpu.models.generate import _attend_cache
    scale = 1.0 / np.sqrt(hd)

    @partial(jax.jit, static_argnames=("kk",))
    def attend_chain(q, kk):
        def it(i, q):
            o = _attend_cache(q, kc, vc, pos, scale)
            return o.astype(dt)
        return jax.lax.fori_loop(0, kk, it, q)

    q0 = jnp.asarray(rng.standard_normal((batch, 1, nh, hd)), dt)
    t_attend1 = chain_time(
        attend_chain, q0, 2 * batch * kvh * max_len * hd * wbytes,
        label="attend")

    # the full decode step: whole-`generate` length differencing
    # (decode_bench.py's methodology). Same interleaved-pair protocol:
    # per-step =
    # median[(t(n2) - t(n1)) pair] / (n2 - n1).
    import time as _time
    from rlo_tpu.models.generate import generate
    n1, n2 = win // 3, win
    prompt = jnp.asarray(rng.integers(0, vocab, (batch, plen)),
                         jnp.int32)

    def build(max_new):
        f = jax.jit(lambda p, t: generate(p, t, cfg, max_new=max_new,
                                          max_len=max_len))
        np.asarray(f(params, prompt))
        return lambda: np.asarray(f(params, prompt))

    run_hi, run_lo = build(n2), build(n1)
    run_hi(), run_lo()
    sdiffs = []
    for _ in range(9):
        t0 = _time.perf_counter()
        run_hi()
        t1 = _time.perf_counter()
        run_lo()
        t2 = _time.perf_counter()
        sdiffs.append((t1 - t0) - (t2 - t1))
    smed = float(np.median(sdiffs))
    if smed <= 0:
        raise RuntimeError("step differencing swallowed by noise")
    t_step = smed / (n2 - n1)
    print(f"  step: generate-differenced per-op {t_step*1e6:.1f} us",
          file=sys.stderr)

    # ---- budget table ----------------------------------------------
    # the logits probe streams the fold matrix too (d*vocab extra
    # bytes the real step does not have) — charge the step's budget
    # only the head's byte share of the probe time, or the residual
    # is understated by the fold's stream time
    head_share = (d * vocab * wbytes) / (d * vocab * wbytes
                                         + logits_extra)
    comp_t = {"ffn": t_ffn1 * nl, "qkv_wo": t_qkv1 * nl,
              "logits": t_logits * head_share, "attend": t_attend1 * nl}
    meas_bytes = dict(comp_bytes)
    resid = t_step - sum(comp_t.values())
    print(f"\nwindow streaming probe: {gbps_window:.0f} GB/s delivered"
          + (f" ({gbps_window*1e9/peaks.hbm_bytes_per_s:.1%} of {kind} "
             f"nominal)" if on_tpu else ""), file=sys.stderr)
    print(f"{'component':>10} {'bytes/step':>11} {'t (ms)':>8} "
          f"{'GB/s':>6} {'vs window':>9}", file=sys.stderr)
    for name in comp_t:
        gbps = meas_bytes[name] / comp_t[name] / 1e9
        print(f"{name:>10} {meas_bytes[name]/2**20:>9.0f}MB "
              f"{comp_t[name]*1e3:>8.3f} {gbps:>6.0f} "
              f"{gbps/gbps_window:>8.1%}", file=sys.stderr)
    print(f"{'step':>10} {model_bytes/2**20:>9.0f}MB "
          f"{t_step*1e3:>8.3f} {model_bytes/t_step/1e9:>6.0f} "
          f"{model_bytes/t_step/1e9/gbps_window:>8.1%}",
          file=sys.stderr)
    print(f"{'residual':>10} {'':>11} {resid*1e3:>8.3f} "
          f"(elementwise + scan overhead, "
          f"{resid/t_step:.1%} of step)", file=sys.stderr)

    frac_window = model_bytes / t_step / 1e9 / gbps_window
    rec = {
        "metric": f"decode-step HBM budget, {n_params/1e6:.0f}M params,"
                  f" batch {batch}, max_len {max_len}, "
                  f"{kind}",
        "value": round(t_step * 1e3, 3),
        "unit": "ms/step",
        "vs_baseline": round(frac_window, 4),
        "vs_baseline_meaning": "step streaming rate / same-window "
                               "probe rate (drift-immune fraction of "
                               "DELIVERED bandwidth)",
        "window_probe_gbps": round(gbps_window, 1),
        "components_ms": {n: round(t * 1e3, 3)
                          for n, t in comp_t.items()},
        "component_bytes_mb": {n: round(b / 2**20, 1)
                               for n, b in meas_bytes.items()},
        "residual_ms": round(resid * 1e3, 3),
        "compiler": {kk: vv for kk, vv in ca.items()},
    }
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
