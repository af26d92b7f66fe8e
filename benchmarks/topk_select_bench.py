"""The token selector's exact top-k alone: the kernel against XLA's form
(PERF.md §6).

At a layer's score shape of deepseek-v3.2-ep32 x longctx-decode — the
decode step's (32, 1, 24576) and an admission's 256-token extend chunk,
(1, 256, 24576) — and of hy4-preview-ep32 x longctx32k-decode — (16, 1,
49152) and (1, 256, 49152) — keeping 2048, each form is timed as a
jitted chain of calls on one score array (the scores are an argument of the chain,
never a constant baked into it; each call's mask feeds one element of
the next call's input, so no call can be dropped or hoisted):

  kernel         pallas.decode.topk_select
  xla            models.kvcache.topk_mask
  xla@round      topk_mask inside a lax.cond inside a lax.scan of 32
                 steps: the form a DecodeServer round runs it in
  kernel@round   topk_select in the same scan of conds
  kernel/Lw      the kernel with another lane width of its counts' walk
                 (_TOPK_LANES)
  kernel/RrBb    the kernel with r rows a grid step (_TOPK_ROWS), the
                 scores' and the mask's blocks b deep (_topk_buffers)

The scores are drawn the way the round's are: non-negative sums of
ReLUs with the positions past each row's context at -inf (contexts at
the cell's quantiles), so the ties cut runs nowhere; ``ties`` adds a
form whose scores are rounded to quarter steps, where it runs in every
block. Every form's mask is checked against topk_mask's, bit for bit.

Usage: python benchmarks/topk_select_bench.py [--tiny] [--shapes a,b]
--tiny runs toy sizes in interpret mode on the CPU: a rehearsal, not a
measurement.
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

from rlo_tpu.models.kvcache import topk_mask  # noqa: E402
from rlo_tpu.pallas import decode  # noqa: E402
from rlo_tpu.utils.device import bench_device  # noqa: E402

# name: (score shape, k)
SHAPES = {"decode": ((32, 1, 24576), 2048),
          "extend": ((1, 256, 24576), 2048),
          "hy4_decode": ((16, 1, 49152), 2048),
          "hy4_extend": ((1, 256, 49152), 2048)}
TINY = {"decode": ((4, 1, 1024), 64), "extend": ((1, 12, 1024), 64),
        "hy4_decode": ((16, 1, 2048), 64),
        "hy4_extend": ((1, 24, 2048), 64)}
#: the grid-step forms timed beside the one the rule picks, by shape
FORMS = {"decode": ["kernel/R8B2"], "extend": ["kernel/R8B2"],
         "hy4_decode": ["kernel/R8B2", "kernel/R16B1", "kernel/R16B2"],
         "hy4_extend": ["kernel/R8B2", "kernel/R16B1", "kernel/R8B1",
                        "kernel/R16B2"]}
STEPS = 32          # steps of a round


def scores_for(shape, ties, seed=39):
    """Non-negative scores, -inf past each row's context (a third to
    five sixths of the row), optionally rounded to quarter steps."""
    rng = np.random.default_rng(seed)
    n = shape[-1]
    s = np.abs(rng.standard_normal(shape)).astype(np.float32)
    rows = int(np.prod(shape[:-1]))
    ctx = np.linspace(n // 3, n * 5 // 6, rows).astype(np.int64)
    rng.shuffle(ctx)
    s = s.reshape(rows, n)
    s[np.arange(n)[None, :] > ctx[:, None]] = -np.inf
    if ties:
        s = np.round(s * 4) / 4
    return jnp.asarray(s.reshape(shape))


def form(name, k, interpret):
    def kernel(s):
        return decode.topk_select(s, k, interpret=interpret)

    def xla(s):
        return topk_mask(s, k)

    one = kernel if name.startswith("kernel") else xla
    if not name.endswith("@round"):
        return one, 1

    def round_form(s, p):
        # the round's shape: a scan of STEPS steps, each choosing
        # through a cond on the step's largest position (select_tokens)
        def step(s, i):
            m = jax.lax.cond(p[i] < 0, lambda s: jnp.ones(s.shape, bool),
                             one, s)
            return s.at[..., :1].add(m[..., :1] * 1e-30), None
        return jax.lax.scan(step, s, jnp.arange(STEPS))[0]
    return round_form, STEPS


def run(name, shape, k, tiny, ties):
    s0 = scores_for(shape, ties)
    want = np.asarray(jax.jit(lambda s: topk_mask(s, k))(s0))
    p = jnp.arange(STEPS, dtype=jnp.int32)      # every step chooses
    calls = 2 if tiny else 16
    plans = ["kernel", "xla", "xla@round", "kernel@round"] + FORMS[name]
    if not ties and not name.startswith("hy4"):
        plans += ["kernel/L512", "kernel/L2048"]
    out = {}
    saved = (decode._TOPK_LANES, decode._TOPK_ROWS, decode._topk_buffers)
    for plan in plans:
        try:
            if "/L" in plan:
                decode._TOPK_LANES = int(plan.split("/L")[1])
            if "/R" in plan:
                r, b = map(int, plan.split("/R")[1].split("B"))
                decode._TOPK_ROWS = r
                decode._topk_buffers = lambda n, b=b: b
            f1, per = form(plan, k, tiny or None)
            got = np.asarray(jax.jit(
                lambda s: (decode.topk_select(s, k, interpret=tiny or None)
                           if plan.startswith("kernel")
                           else topk_mask(s, k)))(s0))
            assert (got == want).all(), plan

            def chain(s, p):
                def call(_, s):
                    if per == 1:
                        m = f1(s)
                        return s.at[..., :1].add(m[..., :1] * 1e-30)
                    return f1(s, p)
                return jax.lax.fori_loop(0, calls, call, s)

            f = jax.jit(chain)
            jax.block_until_ready(f(s0, p))
            ts = []
            for _ in range(1 if tiny else 7):
                t = time.perf_counter()
                jax.block_until_ready(f(s0, p))
                ts.append((time.perf_counter() - t) / (calls * per))
        finally:
            (decode._TOPK_LANES, decode._TOPK_ROWS,
             decode._topk_buffers) = saved
        out[plan] = {"ms": round(min(ts) * 1e3, 4),
                     "ms_median": round(float(np.median(ts)) * 1e3, 4)}
        print(f"{name}{'/ties' if ties else ''} {shape} {plan:14s} "
              f"{min(ts) * 1e3:.4f} ms a call (median "
              f"{np.median(ts) * 1e3:.4f})", file=sys.stderr, flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--shapes", default=",".join(SHAPES),
                    help="the shapes to time, by name")
    args = ap.parse_args()
    kind, _ = bench_device(args.tiny)
    table = TINY if args.tiny else SHAPES
    res = {}
    for name in args.shapes.split(","):
        shape, k = table[name]
        res[name] = run(name, shape, k, args.tiny, False)
        res[name + "/ties"] = run(name, shape, k, args.tiny, True)
    print(json.dumps({"device": kind, "topk_select": res}))


if __name__ == "__main__":
    main()
