"""KV-cache decode throughput for the flagship model on the live chip.

Methodology: one jitted `generate` is a single XLA program (prefill
scan + decode scan, static shapes). The per-call dispatch floor and the
prefill cost cancel by differencing two generation lengths:

    tokens/s = (N2 - N1) / (t(N2) - t(N1))

where t(N2) - t(N1) is the MEDIAN OF INTERLEAVED PAIRS (paired_diff):
the two programs are timed back-to-back within each pair so the
chip's between-window throughput drift — the source of the round-3
numbers' ±30% run-to-run scatter — cancels, the same cure bench.py's
paired-ratio protocol applies to the headline number. Each recorded
value now also prints its own MAD/median spread.

Decode is matvec-bound (one (1, d) activation against every weight
matrix per token), so the interesting ceiling is HBM bandwidth over
the ~param bytes read per token, reported as achieved/ceiling.

--ttft measures time-to-first-token: the one-forward-pass blockwise
prefill (models.generate.prefill, flash-kernel path) vs the
token-at-a-time scan oracle at a given prompt length — the round-4
VERDICT item making prefill O(plen/block) instead of O(plen) serial
decode steps. Methodology: every timed unit is a whole `generate`
call, CHAINED k data-dependent times inside one jit so
millisecond-scale costs amortize over the per-call dispatch floor:
prefill cost = per-op cost of chained generate(max_new=4) minus 4
decode steps; decode-step cost = interleaved paired difference of two
chains whose max_new differs by 64 (pairing cancels window drift;
each pair carries k*64 steps of signal). Both carry bench.py's
physical floors: a prefill below the 2*n_params*tokens/peak-FLOP/s
floor is flagged and clamped. The per-token scan-prefill baseline IS
a decode step (same decode_step, same cache math), so scan TTFT =
plen * decode-step cost without compiling a plen-long scan program.

Usage: python benchmarks/decode_bench.py [--tiny] [--ttft] [--plen N]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from rlo_tpu.models.generate import generate  # noqa: E402
from rlo_tpu.models.transformer import (TransformerConfig,  # noqa: E402
                                        init_params)
from rlo_tpu.utils.device import bench_device  # noqa: E402


def paired_diff(params, hi_args, lo_args, cfg, pairs=9, label="decode"):
    """Median of interleaved per-pair differences t(hi) - t(lo).

    The round-3 decode numbers carried ~±30% run-to-run drift because
    the two legs of the differencing were timed in separate blocks:
    the recorded chip's throughput drifted between measurement windows
    (docs/DESIGN.md §4, ~1.6x between windows in the 2026-07/08
    records), so any
    window shift between block t(N1) and block t(N2) lands directly in
    the difference. Same cure as bench.py's paired-ratio protocol
    (round-2 VERDICT item 2): compile and warm BOTH programs, then
    alternate hi/lo timings back-to-back and take the median of the
    per-pair differences — drift slow relative to one pair cancels.
    The median runs over ALL pairs including non-positive ones —
    dropping negative pairs before the median would censor the noise
    distribution one-sidedly and bias the estimate up (and made the
    tiny smoke test flaky); only a non-positive MEDIAN means the gap
    is genuinely inside dispatch noise, and that raises.
    Returns (median_diff_seconds, relative_spread) where the spread is
    MAD/median over all pairs — the number carries its own
    uncertainty instead of hiding it.
    """
    def build(args):
        prompt, max_new, max_len = args
        f = jax.jit(lambda p, t: generate(p, t, cfg, max_new=max_new,
                                          max_len=max_len))
        np.asarray(f(params, prompt))  # compile + warm
        return lambda: np.asarray(f(params, prompt))

    run_hi, run_lo = build(hi_args), build(lo_args)
    run_hi(), run_lo()  # second warm pass after both are compiled
    diffs = []
    for _ in range(pairs):
        t0 = time.perf_counter()
        run_hi()
        t1 = time.perf_counter()
        run_lo()
        t2 = time.perf_counter()
        diffs.append((t1 - t0) - (t2 - t1))
    med = float(np.median(diffs))
    if med <= 0:
        raise RuntimeError(
            f"{label} paired differencing failed: median pair "
            f"difference {med*1e3:.3f} ms <= 0 over {pairs} pairs "
            f"(hi={hi_args[1:]}, lo={lo_args[1:]}) — the timing gap "
            f"is inside dispatch noise; widen the length gap")
    mad = float(np.median(np.abs(np.asarray(diffs) - med)))
    return med, mad / med


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--cast-weights", action="store_true",
                    help="store weights in HBM as bf16 (measured "
                         "SLOWER on v5e — see comment at the ceiling)")
    ap.add_argument("--ttft", action="store_true",
                    help="time-to-first-token: blockwise prefill vs "
                         "the scan oracle")
    ap.add_argument("--plen", type=int, default=1024,
                    help="prompt length for --ttft")
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="prompt length for the decode measurement — "
                         "long prompts make each decode step read a "
                         "long cache (the regime where the cache, not "
                         "the weights, bounds decode)")
    ap.add_argument("--kv-dtype", choices=["act", "int8"], default="act",
                    help="KV-cache storage: activation dtype (exact) "
                         "or int8 (cfg.kv_cache_dtype='int8' — half "
                         "the cache HBM traffic)")
    ap.add_argument("--compare-kv", action="store_true",
                    help="measure act vs int8 cache decode in "
                         "INTERLEAVED pairs (drift-immune ratio; two "
                         "separate runs of this bench sit in "
                         "different chip-throughput windows and their "
                         "ratio is not trustworthy)")
    ap.add_argument("--compare-gqa", action="store_true",
                    help="MHA (16q/16kv) vs GQA (16q/4kv) decode in "
                         "interleaved pairs at long prompt — the "
                         "cache-bandwidth win GQA exists for")
    ap.add_argument("--capacity", action="store_true",
                    help="max servable batch at --prompt-len context "
                         "before HBM exhaustion: kv=16/4/4+int8, "
                         "each PROVEN by allocating the cache and "
                         "running a decode step at the claimed size")
    args = ap.parse_args()
    args.device = bench_device(args.tiny)  # (kind label, peaks|None)

    if args.compare_kv:
        return compare_kv(args)
    if args.compare_gqa:
        return compare_gqa(args)
    if args.capacity:
        return capacity(args)

    if args.ttft:
        return ttft(args)

    if args.tiny:
        cfg = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                n_layers=2, d_ff=256, dtype="float32")
        # wide length gap: at toy sizes the two timings are micro-
        # seconds apart and host contention (e.g. the full test suite)
        # can invert a narrow pair, tripping the differencing guard
        batch, n1, n2 = args.batch or 2, 4, 48
    else:
        cfg = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                n_layers=8, d_ff=4096, dtype="bfloat16")
        # batch swept on the chip (2026-07-30): 8 -> 8.9k tok/s, 32 ->
        # 28-40k across runs (weight reads amortized), 64 -> 27.4k
        # (cache-attention traffic dominates); 32 is the knee
        batch, n1, n2 = args.batch or 32, 64, 192

    params = init_params(jax.random.PRNGKey(0), cfg)
    # Weight residency: init_params keeps f32 (training layout); the
    # in-scan .astype(dt) is hoisted by XLA into a one-time bf16 copy,
    # so the streamed bytes are 2/param either way and the ceiling
    # below reflects the streamed copy (review finding). Pre-casting
    # the tree (--cast-weights) measured no better on the chip
    # (2026-07-30: 22.3k vs 22-40k tok/s default across runs — decode
    # differencing drifted ~±30% run to run in those records, so treat
    # single-run comparisons here with suspicion).
    if args.cast_weights and cfg.dtype == "bfloat16":
        params = jax.tree.map(
            lambda p: p.astype(jnp.bfloat16)
            if p.dtype == jnp.float32 else p, params)
    if args.kv_dtype == "int8":
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    rng = np.random.default_rng(0)
    plen = min(args.prompt_len, 16) if args.tiny else args.prompt_len
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (batch, plen)),
                         jnp.int32)
    max_len = prompt.shape[1] + n2
    diff, spread = paired_diff(params, (prompt, n2, max_len),
                               (prompt, n1, max_len), cfg)
    steps_s = (n2 - n1) / diff
    tok_s = steps_s * batch
    print(f"paired differencing spread (MAD/median): {spread:.1%}",
          file=sys.stderr)
    kind, peaks = args.device
    on_tpu = peaks is not None
    # HBM ceiling: every decode step reads at least the param bytes
    # PLUS the live K/V cache prefix (dominant at long prompt_len) —
    # cache bytes/step use the midpoint position of the differenced
    # window, per the storage dtype
    wdt = 2 if cfg.dtype == "bfloat16" else 4
    kv_elem = (1 + 4 / cfg.head_dim  # int8 + f32 scale per head row
               ) if cfg.kv_cache_dtype == "int8" else wdt
    mid_pos = plen + (n1 + n2) / 2
    cache_bytes = (2 * cfg.n_layers * batch * mid_pos * cfg.kv_heads
                   * cfg.head_dim * kv_elem)
    bytes_per_step = n_params * wdt + cache_bytes
    frac = (steps_s * bytes_per_step / peaks.hbm_bytes_per_s
            if on_tpu else float("nan"))
    print(f"params={n_params/1e6:.1f}M batch={batch} plen={plen} "
          f"cache={args.kv_dtype}: {steps_s:,.0f} steps/s, "
          f"{tok_s:,.0f} tok/s"
          + (f", {frac:.1%} of the HBM weight+cache streaming ceiling "
             f"({cache_bytes/2**20:.0f} MB cache read/step)"
             if on_tpu else " (not a TPU)"),
          file=sys.stderr)
    print(json.dumps({
        "metric": f"KV-cache greedy decode, {n_params/1e6:.0f}M params, "
                  f"batch {batch}, prompt {plen}, "
                  f"{args.kv_dtype} cache, "
                  f"{kind}",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(frac, 4) if on_tpu else 0.0,
        "vs_baseline_meaning": "fraction of the HBM weight+cache "
                               "streaming ceiling (the device_kind's "
                               "peak, rlo_tpu.utils.device.PEAKS)",
    }))


def compare_kv(args):
    """act-vs-int8 cache decode ratio, drift-immune: each iteration
    times all four programs (act/int8 x n1/n2) back-to-back, diffs
    out the prefill+floor per variant, and takes the median of the
    per-iteration RATIOS — chip-throughput window drift cancels
    inside an iteration instead of landing between two separate
    bench invocations."""
    import dataclasses
    if args.tiny:
        cfg = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                n_layers=2, d_ff=256, dtype="float32")
        batch, n1, n2, plen = args.batch or 2, 4, 48, 16
    else:
        cfg = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                n_layers=8, d_ff=4096,
                                dtype="bfloat16")
        batch, n1, n2 = args.batch or 32, 64, 192
        plen = args.prompt_len if args.prompt_len > 16 else 1024
    params = init_params(jax.random.PRNGKey(0), cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab, (batch, plen)),
                         jnp.int32)
    max_len = plen + n2

    def build(kv_dtype, max_new):
        c = (dataclasses.replace(cfg, kv_cache_dtype="int8")
             if kv_dtype == "int8" else cfg)
        f = jax.jit(lambda p, t: generate(p, t, c, max_new=max_new,
                                          max_len=max_len))
        np.asarray(f(params, prompt))  # compile + warm
        return lambda: np.asarray(f(params, prompt))

    runs = {(kv, n): build(kv, n) for kv in ("act", "int8")
            for n in (n1, n2)}
    for f in runs.values():
        f()  # second warm pass after all four are compiled
    ratios, d_acts, d_ints = [], [], []
    for _ in range(9):
        t = {}
        for key, f in runs.items():
            t0 = time.perf_counter()
            f()
            t[key] = time.perf_counter() - t0
        d_act = t[("act", n2)] - t[("act", n1)]
        d_int = t[("int8", n2)] - t[("int8", n1)]
        if d_act > 0 and d_int > 0:
            ratios.append(d_act / d_int)
            d_acts.append(d_act)
            d_ints.append(d_int)
    if len(ratios) < 5:
        raise RuntimeError("compare-kv: too few valid iterations")
    ratio = float(np.median(ratios))
    tok_act = (n2 - n1) * batch / float(np.median(d_acts))
    tok_int = (n2 - n1) * batch / float(np.median(d_ints))
    kind, peaks = args.device
    on_tpu = peaks is not None
    print(f"compare-kv batch={batch} plen={plen}: act "
          f"{tok_act:,.0f} tok/s  int8 {tok_int:,.0f} tok/s  "
          f"interleaved speedup {ratio:.3f}x "
          f"({len(ratios)}/9 valid iterations)", file=sys.stderr)
    print(json.dumps({
        "metric": f"int8-vs-act KV cache decode speedup, "
                  f"{n_params/1e6:.0f}M params, "
                  f"batch {batch}, prompt {plen}, "
                  f"{kind}"
                  f" (interleaved paired ratio)",
        "value": round(ratio, 4),
        "unit": "x",
        "vs_baseline": round(ratio, 4),
        "vs_baseline_meaning": "decode-step time ratio act/int8; "
                               ">1 means the int8 cache is faster",
    }))


def compare_gqa(args):
    """MHA vs GQA decode, drift-immune (round-5 VERDICT item 3a): the
    kv-heads sweep through the flash-decode kernel at long prompt,
    where each step's HBM traffic is weights + the live K/V cache and
    GQA's 4x-smaller cache is a direct bandwidth win. Same interleaved
    four-program protocol as compare_kv. The GQA config also has
    smaller K/V projections (that is part of what GQA buys); the
    metric line reports both models' parameter counts."""
    import dataclasses
    if args.tiny:
        base = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                 n_layers=2, d_ff=256, dtype="float32")
        batch, n1, n2, plen, kvg = args.batch or 2, 4, 48, 16, 2
    else:
        base = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                 n_layers=8, d_ff=4096,
                                 dtype="bfloat16")
        batch, n1, n2 = args.batch or 32, 64, 192
        plen = args.prompt_len if args.prompt_len > 16 else 1024
        kvg = 4
    gqa = dataclasses.replace(base, n_kv_heads=kvg)
    params = {"mha": init_params(jax.random.PRNGKey(0), base),
              "gqa": init_params(jax.random.PRNGKey(0), gqa)}
    cfgs = {"mha": base, "gqa": gqa}
    n_par = {k: sum(int(np.prod(p.shape))
                    for p in jax.tree.leaves(v))
             for k, v in params.items()}
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, base.vocab, (batch, plen)),
                         jnp.int32)
    max_len = plen + n2

    def build(kind, max_new):
        c = cfgs[kind]
        f = jax.jit(lambda p, t: generate(p, t, c, max_new=max_new,
                                          max_len=max_len))
        np.asarray(f(params[kind], prompt))  # compile + warm
        return lambda: np.asarray(f(params[kind], prompt))

    runs = {(k, n): build(k, n) for k in ("mha", "gqa")
            for n in (n1, n2)}
    for f in runs.values():
        f()
    ratios, d_m, d_g = [], [], []
    for _ in range(9):
        t = {}
        for key, f in runs.items():
            t0 = time.perf_counter()
            f()
            t[key] = time.perf_counter() - t0
        dm = t[("mha", n2)] - t[("mha", n1)]
        dg = t[("gqa", n2)] - t[("gqa", n1)]
        if dm > 0 and dg > 0:
            ratios.append(dm / dg)
            d_m.append(dm)
            d_g.append(dg)
    if len(ratios) < 5:
        raise RuntimeError("compare-gqa: too few valid iterations")
    ratio = float(np.median(ratios))
    tok_m = (n2 - n1) * batch / float(np.median(d_m))
    tok_g = (n2 - n1) * batch / float(np.median(d_g))
    kind, peaks = args.device
    on_tpu = peaks is not None
    print(f"compare-gqa batch={batch} plen={plen}: "
          f"{base.n_heads}q/{base.kv_heads}kv {tok_m:,.0f} tok/s  "
          f"{gqa.n_heads}q/{gqa.kv_heads}kv {tok_g:,.0f} tok/s  "
          f"interleaved speedup {ratio:.3f}x "
          f"({len(ratios)}/9 valid)", file=sys.stderr)
    print(json.dumps({
        "metric": f"GQA decode speedup {base.n_heads}q/"
                  f"{gqa.kv_heads}kv vs MHA, batch {batch}, prompt "
                  f"{plen} ({n_par['mha']/1e6:.0f}M vs "
                  f"{n_par['gqa']/1e6:.0f}M params, "
                  f"{kind}"
                  f", interleaved paired ratio)",
        "value": round(ratio, 4),
        "unit": "x",
        "vs_baseline": round(ratio, 4),
        "vs_baseline_meaning": "decode-step time ratio MHA/GQA at the "
                               "same q heads; >1 means the compact "
                               "cache is faster",
    }))


def capacity(args):
    """Servable capacity (round-5 VERDICT item 3b): the largest batch
    of --plen-context rows whose KV cache fits HBM next to the
    weights, for MHA / GQA / GQA+int8 — PROVEN by allocating the full
    cache and running one decode step at that size (an analytic claim
    would hide allocator overheads); the recorded ratio is capacity
    vs the MHA baseline."""
    import dataclasses
    if args.tiny:
        base = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                 n_layers=2, d_ff=256, dtype="float32")
        L, budget, kvh_g = 128, 64 << 20, 2
    else:
        base = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                 n_layers=8, d_ff=4096,
                                 dtype="bfloat16")
        L = args.prompt_len if args.prompt_len > 16 else 4096
        budget = int(12.5e9)  # leave headroom of the 16 GB for
        # weights (0.8 GB f32+bf16), activations, and runtime slack
        kvh_g = 4
    variants = {
        "mha": base,
        "gqa4": dataclasses.replace(base, n_kv_heads=kvh_g),
        "gqa4_int8": dataclasses.replace(base, n_kv_heads=kvh_g,
                                         kv_cache_dtype="int8"),
    }
    rows = {}
    for name, cfg in variants.items():
        elem = (1 + 4 / cfg.head_dim) if cfg.kv_cache_dtype == "int8" \
            else (2 if cfg.dtype == "bfloat16" else 4)
        per_row = 2 * cfg.n_layers * cfg.kv_heads * L * cfg.head_dim \
            * elem
        b = max(1, int(budget / per_row))
        from rlo_tpu.models.generate import decode_step, init_kv_cache
        params = init_params(jax.random.PRNGKey(0), cfg)
        tok = jnp.zeros((b,), jnp.int32)
        cache = init_kv_cache(cfg, b, L)
        # donate the cache: input+output copies would double the
        # budget and OOM the 16 GB chip the leg sizes itself for
        step = jax.jit(lambda p, t, c, cfg=cfg: decode_step(
            p, t, L - 1, c, cfg), donate_argnums=(2,))
        logits, cache = step(params, tok, cache)
        np.asarray(logits[0, :4])  # force execution
        del cache, logits, params
        rows[name] = b
        print(f"capacity {name}: {per_row/2**20:.0f} MB/row at "
              f"context {L} -> {b} rows allocated AND decoded "
              f"({b * L / 1e6:.2f}M tokens of live context)",
              file=sys.stderr)
    kind, peaks = args.device
    on_tpu = peaks is not None
    print(json.dumps({
        "metric": f"servable capacity at context {L}: rows allocated+"
                  f"decoded within a {budget/1e9:.1f} GB cache budget "
                  f"(mha {rows['mha']}, gqa4 {rows['gqa4']}, "
                  f"gqa4+int8 {rows['gqa4_int8']}), "
                  f"{kind}",
        "value": rows["gqa4_int8"] * L / 1e6,
        "unit": "Mtokens live context",
        "vs_baseline": round(rows["gqa4_int8"] / rows["mha"], 2),
        "vs_baseline_meaning": "capacity ratio gqa4+int8 / MHA "
                               "(gqa4 alone: "
                               f"{round(rows['gqa4'] / rows['mha'], 2)}"
                               "x)",
    }))


def ttft(args):
    if args.tiny:
        cfg = TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                n_layers=2, d_ff=256, dtype="float32")
        batch = args.batch or 2
        plen = min(args.plen, 128)
        n_dec = 4
    else:
        cfg = TransformerConfig(vocab=32768, d_model=1024, n_heads=16,
                                n_layers=8, d_ff=4096, dtype="bfloat16")
        batch = args.batch or 4
        plen = args.plen
        n_dec = 4
    p0 = 16
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)

    def prompt_of(n):
        return jnp.asarray(rng.integers(0, cfg.vocab, (batch, n)),
                           jnp.int32)

    # blockwise prefill cost: chain k data-dependent generate calls
    # (prefill + n_dec decode steps each) inside ONE jit — the chained
    # methodology bench.py uses everywhere, which resolves a
    # millisecond-scale op against the per-call dispatch floor by
    # amortizing it over a calibrated k. (The previous protocol
    # differenced two SINGLE programs by prompt length; at batch 1 the
    # ~2 ms gap sat inside the noise and one recorded leg printed
    # 0.34 ms for 1008 tokens = 2.3x the chip's peak flops.)
    # The chained unit is a whole generate; each
    # iteration's prompt depends on the previous iteration's last
    # token, which defeats loop-invariant hoisting/CSE.
    import bench
    from functools import partial

    prompt_hi = prompt_of(plen)

    @partial(jax.jit, static_argnames=("kk",))
    def gen_chain(params, pr, kk):
        def it(i, carry):
            pr, acc = carry
            toks = generate(params, pr, cfg, max_new=n_dec,
                            max_len=plen + n_dec)
            pr = pr.at[0, 0].set(toks[0, -1] % cfg.vocab)
            return (pr, acc + toks[0, -1])
        _, acc = jax.lax.fori_loop(0, kk, it, (pr, jnp.int32(0)))
        return acc

    t_gen_op = bench._chain_time(
        lambda pr, kk: gen_chain(params, pr, kk), prompt_hi, k=4,
        stat="median")

    # scan-prefill baseline: one token of scan prefill IS one decode
    # step (same decode_step, same cache attend), so the baseline is
    # the decode-step cost. Measured by interleaving two CHAINED
    # programs whose max_new differs by m=64: chains amortize the
    # dispatch floor (a batch-1 step is ~0.1 ms — single-program
    # differencing of ~110 ms programs measured it with >1000%
    # spread), pairing cancels window drift, and each pair resolves
    # k*m decode steps of signal.
    m = 64
    prompt_lo = prompt_of(p0)

    @partial(jax.jit, static_argnames=("kk", "extra"))
    def dec_chain(params, pr, kk, extra):
        def it(i, carry):
            pr, acc = carry
            toks = generate(params, pr, cfg, max_new=n_dec + extra,
                            max_len=p0 + n_dec + m)
            pr = pr.at[0, 0].set(toks[0, -1] % cfg.vocab)
            return (pr, acc + toks[0, -1])
        _, acc = jax.lax.fori_loop(0, kk, it, (pr, jnp.int32(0)))
        return acc

    def loop_hi(pr, kk):
        return dec_chain(params, pr, kk, m)

    def loop_lo(pr, kk):
        return dec_chain(params, pr, kk, 0)

    k_dec = bench._calibrate_chain(loop_hi, prompt_lo, k=4)
    for f in (loop_hi, loop_lo):
        np.asarray(f(prompt_lo, k_dec))  # compile + warm both
    diffs = []
    for _ in range(9):
        t0 = time.perf_counter()
        np.asarray(loop_hi(prompt_lo, k_dec))
        t1 = time.perf_counter()
        np.asarray(loop_lo(prompt_lo, k_dec))
        t2 = time.perf_counter()
        diffs.append((t1 - t0) - (t2 - t1))
    d_med = float(np.median(diffs))
    if d_med <= 0:
        raise RuntimeError(
            f"ttft decode baseline failed: median chained diff "
            f"{d_med*1e3:.3f} ms <= 0 (k={k_dec}, m={m})")
    spread_d = float(np.median(np.abs(np.asarray(diffs) - d_med))
                     ) / d_med
    t_step = d_med / (k_dec * m)
    t_scan = t_step * plen  # scan-prefilling the WHOLE prompt
    # one chained generate op = blockwise prefill + n_dec decode steps
    t_block = t_gen_op - n_dec * t_step
    if t_block <= 0:
        raise RuntimeError(
            f"prefill cost non-positive: generate op "
            f"{t_gen_op*1e3:.3f} ms <= {n_dec} decode steps x "
            f"{t_step*1e3:.3f} ms")
    print(f"ttft: chained generate op {t_gen_op*1e3:.3f} ms, decode "
          f"spread {spread_d:.1%}", file=sys.stderr)

    kind, peaks = args.device
    on_tpu = peaks is not None
    if on_tpu:
        # physical floor (same gate as bench.py's HBM-peak clamp): the
        # prefill's forward matmuls alone cost 2*n_params flops/token;
        # a differenced time below that at the device's bf16 peak is
        # floor corruption, not speed (a recorded batch-1 leg once
        # printed 0.34 ms for 1008 tokens = 2.3x the chip's peak)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        t_floor = 2.0 * n_params * batch * plen / peaks.bf16_flops
        if t_block < t_floor:
            print(f"WARNING: prefill diff {t_block*1e3:.3f} ms below "
                  f"the {t_floor*1e3:.3f} ms FLOP floor — clamped "
                  f"(floor-corrupted differencing)", file=sys.stderr)
            t_block = t_floor
    print(f"ttft plen={plen} batch={batch}: blockwise prefill of "
          f"{plen} tokens {t_block*1e3:.2f} ms  scan "
          f"{t_scan*1e3:.2f} ms ({t_step*1e3:.3f} ms/token decode-"
          f"differenced)  speedup {t_scan/t_block:.1f}x",
          file=sys.stderr)
    print(json.dumps({
        "metric": f"time-to-first-token, blockwise prefill of "
                  f"{plen} prompt tokens, batch {batch}, "
                  f"{kind}",
        "value": round(t_block * 1e3, 3),
        "unit": "ms",
        "vs_baseline": round(t_scan / t_block, 2),
        "vs_baseline_meaning": "speedup over token-at-a-time prefill "
                               "(= decode-step cost per token, "
                               "length-differenced)",
    }))


if __name__ == "__main__":
    main()
