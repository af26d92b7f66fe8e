"""Block-shape sweep for the Pallas fused-combine kernel on the live
chip. Prints one line per configuration (GB/s, chained-iteration
methodology from bench.py) plus the XLA-fused baseline; use the winner
to retune rlo_tpu/pallas/reduce.py's defaults.

Usage: python benchmarks/pallas_sweep.py [--bytes N]
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

import bench                            # noqa: E402
from rlo_tpu.pallas.reduce import fused_combine  # noqa: E402
from rlo_tpu.utils.device import bench_device  # noqa: E402

CONFIGS = [  # (block_rows, lane)
    (256, 128), (512, 128), (1024, 128), (2048, 128),
    (128, 256), (256, 256), (512, 256),
    (64, 512), (128, 512), (256, 512),
    (32, 1024), (64, 1024), (128, 1024),
]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--bytes", type=int, default=256 << 20)
    args = ap.parse_args()
    kind, _ = bench_device()  # times the chip: refuses another backend
    print(f"device: {kind}", flush=True)
    n = args.bytes // 4
    rows = n // 128
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((rows, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((rows, 128)), jnp.float32)
    nbytes = a.size * 4
    want = np.asarray(a[0, :4] + 2 * b[0, :4])  # oracle after k=2 chain

    @partial(jax.jit, static_argnames=("k",))
    def xla_loop(x, y, k):
        return jax.lax.fori_loop(0, k, lambda i, acc: acc + y, x)

    t = bench._chain_time(xla_loop, a, b)
    base = 3 * nbytes / t / 1e9
    print(f"xla fused baseline: {base:.1f} GB/s", flush=True)

    results = []
    for block_rows, lane in CONFIGS:
        @partial(jax.jit, static_argnames=("k",))
        def ploop(x, y, k, block_rows=block_rows, lane=lane):
            return jax.lax.fori_loop(
                0, k, lambda i, acc: fused_combine(
                    acc, y, op="sum", block_rows=block_rows, lane=lane),
                x)
        try:
            got = np.asarray(ploop(a, b, 2)[0, :4])
            np.testing.assert_allclose(got, want, rtol=1e-5)
            t = bench._chain_time(ploop, a, b)
            gbps = 3 * nbytes / t / 1e9
            results.append((gbps, block_rows, lane))
            print(f"block_rows={block_rows:5d} lane={lane:4d}: "
                  f"{gbps:7.1f} GB/s ({gbps/base:.3f}x xla)", flush=True)
        except Exception as e:  # a block shape Mosaic refuses, VMEM
            print(f"block_rows={block_rows:5d} lane={lane:4d}: "
                  f"FAILED ({type(e).__name__}: {str(e)[:80]})",
                  flush=True)
    if results:
        best = max(results)
        print(f"BEST: block_rows={best[1]} lane={best[2]} "
              f"{best[0]:.1f} GB/s ({best[0]/base:.3f}x xla)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
