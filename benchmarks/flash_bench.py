"""Flash vs unfused ring-attention block update on the live chip.

Measures the per-ring-step online-softmax update both ways (the Pallas
kernel rlo_tpu/pallas/flash.py vs the einsum path
ring_attention._block_update) with bench.py's chained-iteration timing,
after checking numerics against full_attention.

Recorded 2026-07-30 on one v5e chip, not re-measured (causal, 8 heads,
head_dim 128, bf16 inputs, block_q 512):
  seq block 2048 (single K tile in VMEM):
    einsum block update: 0.502 ms   flash: 0.153 ms   -> 3.3x
    fwd+bwd einsum:      1.276 ms   flash: 0.295 ms   -> 4.3x
  seq block 8192 (K/V streamed through VMEM in 512-wide tiles):
    einsum block update: 8.997 ms   flash: 4.404 ms   -> 2.0x
    fwd+bwd einsum:     23.864 ms   flash: 10.77 ms   -> 2.2x
The unfused path materializes the (H, Lq, Lk) score/probability tensors
in HBM between ops (its backward re-materializes them again); the
kernel keeps each (BQ, Lk) tile in VMEM, the ring loop carries all
state in the kernel's head-leading layout (one transpose in, one out),
and the round-3 custom_vjp backward (pallas dq / dkv kernels)
recomputes score tiles in VMEM instead of saving them.

The --gqa leg measures grouped-query attention through the SAME kernel
two ways: compact K/V (n_kv_heads streamed from HBM, the group dim
folded into the kernel's Q axis) vs K/V explicitly repeated to n_heads
first (what the training path did before round 4). Measured 2026-07-31
(seq 4096, 8q/2kv heads, dim 128, bf16, paired-ratio protocol):
fwd 0.993x, fwd+bwd 0.976x — PARITY, and that is the expected result:
per-step K/V tile traffic is grid-identical (the fold trades the head
grid dim for Q tiles; total K reads = (total q rows / block_q) * Lk
either way) and these shapes are MXU-bound. The compact path's real
wins are structural, not kernel-time: n_heads/n_kv_heads fewer ICI
bytes per ring-attention step (pinned by the ppermute-shape tests in
tests/test_gqa_flash.py — only measurable on real multi-chip ICI), an
n_heads/n_kv_heads smaller K/V footprint (no repeated HBM copies
materialized), and the decode cache (where the K/V-HBM-bound regime
actually lives — see decode_bench.py). The leg exists so regressions
from kernel changes show up, not to claim a single-chip speedup.

Usage: python benchmarks/flash_bench.py [--seq N] [--heads H] [--dim D]
       [--gqa KV_HEADS]
"""

from __future__ import annotations

import argparse
import sys
from functools import partial
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import jax                              # noqa: E402
import jax.numpy as jnp                 # noqa: E402
import numpy as np                      # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import bench                            # noqa: E402
from rlo_tpu.utils.device import bench_device  # noqa: E402
from rlo_tpu.ops.ring_attention import (full_attention,  # noqa: E402
                                        ring_attention)
from rlo_tpu.parallel.mesh import make_mesh, shard_jit  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=128)
    ap.add_argument("--block-q", type=int, default=512)
    ap.add_argument("--gqa", type=int, default=0, metavar="KV_HEADS",
                    help="also run the grouped-vs-repeated K/V leg "
                         "with this many K/V heads")
    args = ap.parse_args()
    kind, _ = bench_device()  # times the chip: no other backend

    mesh = make_mesh((1,), ("sp",))
    rng = np.random.default_rng(0)

    def mk():
        return jnp.asarray(rng.standard_normal(
            (args.seq, args.heads, args.dim)) * 0.3, jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def make(use_pallas):
        f = shard_jit(lambda q_, k_, v_: ring_attention(
            q_, k_, v_, "sp", causal=True, use_pallas=use_pallas,
            block_q=args.block_q),
            mesh, (P("sp"), P("sp"), P("sp")), P("sp"))

        @partial(jax.jit, static_argnames=("kk",))
        def loop(q_, kk):
            return jax.lax.fori_loop(
                0, kk, lambda i, acc: f(acc, k, v).astype(jnp.bfloat16),
                q_)
        return lambda x, kk: loop(x, kk)

    want = np.asarray(full_attention(q, k, v, causal=True), np.float32)
    got = np.asarray(make(True)(q, 1), np.float32)
    np.testing.assert_allclose(got, want, rtol=5e-2, atol=5e-2)
    print("numerics ok", file=sys.stderr)

    t_einsum = bench._chain_time(make(False), q, k=16)
    t_flash = bench._chain_time(make(True), q, k=16)
    print(f"einsum block update: {t_einsum*1e3:.3f} ms  "
          f"flash: {t_flash*1e3:.3f} ms  "
          f"speedup {t_einsum/t_flash:.2f}x")

    # -- training: forward + backward through the attention (the path
    # the round-3 custom_vjp unlocked; bwd = the pallas dq/dkv kernels
    # recomputing score tiles in VMEM vs XLA autodiff of the einsum
    # path materializing (H, Lq, Lk) tensors) --
    def make_grad(use_pallas):
        # check_vma off for BOTH: reverse-mode through the ring's
        # ppermute/fori_loop doesn't thread varying-manual-axes types
        # (same rough edge the grad-parity tests document)
        f = shard_jit(lambda q_, k_, v_: ring_attention(
            q_, k_, v_, "sp", causal=True, use_pallas=use_pallas,
            block_q=args.block_q),
            mesh, (P("sp"), P("sp"), P("sp")), P("sp"),
            check_vma=False)

        def loss(q_, k_, v_):
            return jnp.sum(f(q_, k_, v_).astype(jnp.float32) ** 2)

        g = jax.grad(loss, argnums=(0, 1, 2))

        @partial(jax.jit, static_argnames=("kk",))
        def loop(q_, kk):
            def it(i, acc):
                dq, dk, dv = g(acc, k, v)
                return (acc + 1e-6 * (dq + dk + dv)).astype(jnp.bfloat16)
            return jax.lax.fori_loop(0, kk, it, q_)
        return lambda x, kk: loop(x, kk)

    gf = jax.grad(lambda q_: jnp.sum(ring_attention(
        q_, k, v, "sp", causal=True, use_pallas=True,
        block_q=args.block_q).astype(jnp.float32) ** 2))
    gu = jax.grad(lambda q_: jnp.sum(ring_attention(
        q_, k, v, "sp", causal=True, use_pallas=False)
        .astype(jnp.float32) ** 2))
    fgf = shard_jit(gf, mesh, (P("sp"),), P("sp"), check_vma=False)
    fgu = shard_jit(gu, mesh, (P("sp"),), P("sp"), check_vma=False)
    np.testing.assert_allclose(np.asarray(fgf(q), np.float32),
                               np.asarray(fgu(q), np.float32),
                               rtol=5e-2, atol=5e-2)
    print("grad numerics ok", file=sys.stderr)
    t_gu = bench._chain_time(make_grad(False), q, k=16)
    t_gp = bench._chain_time(make_grad(True), q, k=16)
    print(f"fwd+bwd einsum: {t_gu*1e3:.3f} ms  "
          f"fwd+bwd flash (pallas vjp): {t_gp*1e3:.3f} ms  "
          f"speedup {t_gu/t_gp:.2f}x")

    if args.gqa:
        gqa_leg(args.seq, args.heads, args.gqa, args.dim, args.block_q,
                kind)
    return 0


def gqa_leg(seq, h, hkv, d, block_q, kind):
    """Compact vs repeated K/V through the flash kernel (fwd and
    fwd+bwd): the single-chip-measurable HBM-bytes reduction of GQA."""
    from rlo_tpu.pallas.flash import flash_attention

    g = h // hkv
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((seq, h, d)) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((seq, hkv, d)) * 0.3,
                    jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((seq, hkv, d)) * 0.3,
                    jnp.bfloat16)

    def att(q_, k_, v_, compact):
        if not compact:
            k_, v_ = (jnp.repeat(t, g, axis=1) for t in (k_, v_))
        return flash_attention(q_, k_, v_, causal=True, block_q=block_q)

    # parity first
    a = np.asarray(jax.jit(partial(att, compact=True))(q, k, v),
                   np.float32)
    b = np.asarray(jax.jit(partial(att, compact=False))(q, k, v),
                   np.float32)
    np.testing.assert_allclose(a, b, rtol=5e-2, atol=5e-2)
    print("gqa numerics ok", file=sys.stderr)

    def make(compact, with_grad):
        def fwd_it(i, acc):
            return att(acc, k, v, compact).astype(jnp.bfloat16)

        def grad_it(i, acc):
            gq, gk, gv = jax.grad(
                lambda q_, k_, v_: jnp.sum(
                    att(q_, k_, v_, compact).astype(jnp.float32) ** 2),
                argnums=(0, 1, 2))(acc, k, v)
            return (acc + 1e-6 * gq).astype(jnp.bfloat16)

        it = grad_it if with_grad else fwd_it

        @partial(jax.jit, static_argnames=("kk",))
        def loop(q_, kk):
            return jax.lax.fori_loop(0, kk, it, q_)
        return lambda x, kk: loop(x, kk)

    # drift-immune paired protocol (bench.py): each rep times
    # [empty, repeated, compact] back-to-back; median per-pair ratio
    import json
    ratios = {}
    for label, with_grad in (("fwd", False), ("fwd+bwd", True)):
        base = make(False, with_grad)
        chain = bench._calibrate_chain(base, q, k=16)
        results, _ = bench._paired_race(
            base, [("compact", make(True, with_grad))], q, k=chain)
        r = results["compact"]
        ratios[label] = r["ratio"]
        print(f"gqa {label} ({h}q/{hkv}kv heads): compact "
              f"{r['t_med']*1e3:.3f} ms/op, median paired ratio "
              f"repeated/compact = {r['ratio']:.3f}x", file=sys.stderr)
    print(json.dumps({
        "metric": f"GQA compact vs repeated K/V through the flash "
                  f"kernel, seq {seq}, {h}q/{hkv}kv, dim {d}, "
                  f"{kind}"
                  f" (regression guard: parity expected — these shapes "
                  f"are MXU-bound; the GQA wins are ICI bytes, "
                  f"footprint, and the decode cache, see "
                  f"decode_bench --compare-gqa)",
        "value": round(ratios["fwd"], 4),
        "unit": "x",
        "vs_baseline": round(ratios["fwd+bwd"], 4),
        "vs_baseline_meaning": "fwd+bwd median paired ratio "
                               "repeated/compact",
    }))


if __name__ == "__main__":
    sys.exit(main())
