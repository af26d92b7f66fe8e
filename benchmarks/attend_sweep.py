"""Flash-decode attend bandwidth diagnosis (round-5 item 1 follow-up).

decode_analysis measured the cache attend at ~370 GB/s while every
matmul component streams at ~700+ GB/s in the same window. Leading
hypothesis: a head-minor cache layout (b, kvh, L, head_dim=64) has a 64-wide
minor dimension — half a (8, 128) native lane tile — so HBM tiles are
lane-padded and the DMA streams at half width. This sweep pins it by
measuring the SAME cache bytes under different shapes/layouts in one
window:

  a. flash (32, 16, ., 64)    - production shape (hd 64)
  b. flash (32, 8, ., 128)    - same bytes, wider head_dim
  c. flash block_k=128        - finer cache tiles (DMA pipelining)
  d. einsum same shape        - the XLA path for reference
  e. L = 1280 (plen-1024 serving regime) variants of a/b

RESULT (2026-07-31, pre-fix head-minor layout): hd64 365 GB/s vs
hd128 703 GB/s at identical bytes — confirmed the lane-padding
hypothesis, and the cache layout was flipped to SEQ-MINOR
(models.generate.init_kv_cache); this sweep now measures the new
layout, where hd64 and hd128 should both stream at full width.

Usage: python benchmarks/attend_sweep.py [--tiny]
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

from benchmarks.decode_analysis import chain_time  # noqa: E402
from rlo_tpu.models.generate import _attend_cache  # noqa: E402
from rlo_tpu.utils.device import bench_device  # noqa: E402


def attend_leg(batch, kvh, L, hd, *, peaks, block_k=None, use_flash=True,
               dt=jnp.bfloat16, label=""):
    rng = np.random.default_rng(0)
    nh = 16  # total query heads fixed: (kvh, hd) vary, bytes constant
    kc = jnp.asarray(rng.standard_normal((batch, kvh, hd, L)), dt)
    vc = jnp.asarray(rng.standard_normal((batch, kvh, hd, L)), dt)
    q0 = jnp.asarray(rng.standard_normal((batch, 1, nh, hd)), dt)
    scale = 1.0 / np.sqrt(hd)
    pos = L - 8

    kwargs = {}
    if block_k is not None:
        from rlo_tpu.pallas.decode import flash_decode

        @partial(jax.jit, static_argnames=("kk",))
        def run(q, kk):
            def it(i, q):
                o = flash_decode(q, kc, vc, pos, scale,
                                 block_k=block_k)
                return o.astype(dt)
            return jax.lax.fori_loop(0, kk, it, q)
    else:
        @partial(jax.jit, static_argnames=("kk",))
        def run(q, kk):
            def it(i, q):
                o = _attend_cache(q, kc, vc, pos, scale,
                                  use_flash=use_flash)
                return o.astype(dt)
            return jax.lax.fori_loop(0, kk, it, q)

    nbytes = 2 * batch * kvh * L * hd * (2 if dt == jnp.bfloat16 else 4)
    t = chain_time(run, q0, nbytes, label=label)
    gbps = nbytes / t / 1e9
    print(f"{label}: {t*1e6:.1f} us, {nbytes/2**20:.1f} MB -> "
          f"{gbps:.0f} GB/s"
          + (f" ({gbps*1e9/peaks.hbm_bytes_per_s:.0%} of nominal)"
             if peaks is not None else ""), file=sys.stderr)
    return gbps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args()
    kind, peaks = bench_device(args.tiny)
    leg = partial(attend_leg, peaks=peaks)
    if args.tiny:
        legs = {
            "hd64": leg(2, 4, 64, 64, dt=jnp.float32,
                        label="hd64"),
            "hd128": leg(2, 2, 64, 128, dt=jnp.float32,
                         label="hd128"),
        }
    else:
        legs = {}
        legs["hd64_L208"] = leg(32, 16, 208, 64,
                                label="hd64_L208")
        legs["hd128_L208"] = leg(32, 8, 208, 128,
                                 label="hd128_L208")
        legs["hd64_L208_bk128"] = leg(32, 16, 208, 64,
                                      block_k=128,
                                      label="hd64_L208_bk128")
        legs["hd64_L208_einsum"] = leg(32, 16, 208, 64,
                                       use_flash=False,
                                       label="hd64_L208_einsum")
        legs["hd64_L1280"] = leg(32, 16, 1280, 64,
                                 label="hd64_L1280")
        legs["hd128_L1280"] = leg(32, 8, 1280, 128,
                                  label="hd128_L1280")
        legs["hd64_L1280_bk128"] = leg(32, 16, 1280, 64,
                                       block_k=128,
                                       label="hd64_L1280_bk128")
    print(json.dumps({"device": kind,
                      "attend_gbps": {k: round(v, 1)
                                      for k, v in legs.items()}}))


if __name__ == "__main__":
    main()
