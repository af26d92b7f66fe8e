"""The decode-attention kernel alone: ring depth, tile width, and the
stream's own floor (PERF.md §6, PR 37; the tables in the comments of
pallas.decode._TILE_BYTES / _LATENT_TILE_BYTES come from here).

One call of flash_decode / flash_block_decode at a cell's cache shape,
on the contexts of that cell's traffic at a random moment ("mix") and
with every row at max_len ("full"), timed as a jitted chain of calls on
one work list (the way a decode step runs its layers). Per shape:

  pipe           K and V on the BlockSpec pipeline: whole tiles, one
                 copy a tensor in flight (what a compute-bound shape
                 keeps: pallas.decode._ring_slots)
  N2 / N3 / N4   the kernel's own fetch through a ring of that many
                 slots, a row's last tile copied as far as it is live
  stub@N         a fetch (N = 0: the pipeline's) with the body's compute
                 replaced by one small read of the tile: what the stream
                 alone costs
  @W             the same at another tile width (pipe@W: the pipeline's
                 fetch at W lanes, whatever width the rule would take)

Each variant forces its form whatever the shape's own rule says; the
rule's choice is printed beside the shape.

Usage: python benchmarks/attend_fetch_bench.py [--tiny] [shape ...]
(shapes: gpt2 dsv3 dsv32 sdar hy4). --tiny runs toy sizes in interpret mode
on the CPU: a rehearsal, not a measurement.
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
from jax.experimental import pallas as pl  # noqa: E402

from rlo_tpu.pallas import decode  # noqa: E402
from rlo_tpu.utils.device import bench_device  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# name: (rows, heads, kv heads, head_dim, max_len, v_dim, T, block_len,
#        tail rows, selection, the cell's traffic file, a sink a head)
SHAPES = {
    "gpt2": (96, 16, 16, 64, 1024, 0, 1, 0, 32, False, "decode-sat",
             False),
    "dsv3": (128, 128, 1, 576, 4096, 512, 1, 0, 32, False, "reason-sat",
             False),
    "dsv32": (32, 128, 1, 576, 24576, 512, 1, 0, 0, True,
              "longctx-decode", False),
    "sdar": (192, 32, 4, 128, 1536, 0, 4, 4, 0, False, "blockgen-sat",
             False),
    "hy4": (16, 64, 1, 576, 49152, 512, 1, 0, 0, True,
            "longctx32k-decode", True),
}
TINY = {
    "gpt2": (4, 4, 4, 64, 1024, 0, 1, 0, 8, False, "decode-sat", False),
    "dsv3": (3, 16, 1, 576, 2048, 512, 1, 0, 8, False, "reason-sat",
             False),
    "dsv32": (2, 16, 1, 576, 4096, 512, 1, 0, 0, True, "reason-sat",
              False),
    "sdar": (4, 8, 2, 128, 1536, 0, 4, 4, 0, False, "blockgen-sat",
             False),
    "hy4": (2, 8, 1, 576, 4096, 512, 1, 0, 0, True, "reason-sat", True),
}
# (label, ring slots, stub, tile width or None for the rule's)
PLANS = {
    "gpt2": [("pipe", 0, False, None), ("stub@0", 0, True, None),
             ("N2", 2, False, None), ("N3", 3, False, None),
             ("N4", 4, False, None), ("stub@3", 3, True, None),
             ("N3@128", 3, False, 128), ("N3@512", 3, False, 512)],
    "dsv3": [("pipe", 0, False, None), ("stub@0", 0, True, None),
             ("N2", 2, False, None), ("N3", 3, False, None),
             ("N4", 4, False, None), ("stub@3", 3, True, None),
             ("N3@512", 3, False, 512), ("N3@2048", 3, False, 2048),
             ("pipe@1536", 0, False, 1536), ("pipe@2048", 0, False, 2048)],
    "dsv32": [("pipe", 0, False, None), ("stub@0", 0, True, None),
              ("N2", 2, False, None), ("N3", 3, False, None),
              ("N4", 4, False, None), ("stub@3", 3, True, None),
              ("N3@2048", 3, False, 2048), ("pipe@1024", 0, False, 1024),
              ("pipe@1536", 0, False, 1536), ("pipe@2048", 0, False, 2048)],
    "hy4": [("pipe", 0, False, None), ("stub@0", 0, True, None),
            ("N3", 3, False, None), ("N3@2048", 3, False, 2048),
            ("pipe@1024", 0, False, 1024), ("pipe@1536", 0, False, 1536),
            ("pipe@2048", 0, False, 2048)],
    "sdar": [("pipe", 0, False, None), ("stub@0", 0, True, None),
             ("N2", 2, False, None), ("N3", 3, False, None),
             ("N4", 4, False, None), ("stub@3", 3, True, None),
             ("N3@256", 3, False, 256)],
}


def stub_kernel(row_ref, tile_ref, pos_ref, *refs, bk, max_len, T, r,
                v_dim=0, n_tail=0, selected=False, n_slots=0, sinks=False,
                **_):
    """_decode_kernel's fetch with no compute behind it."""
    refs = refs[bool(n_tail):]              # the tail's prefetched scalar
    streams = 1 if v_dim else 2
    caches = refs[1:1 + streams]
    # past q, the caches, the tail's rows (one a tensor), a selection
    # and the sinks
    skip = (1 + streams + (streams if n_tail else 0) + bool(selected)
            + bool(sinks))
    o_ref, m_s, _l, _o, *rings = refs[skip:]
    i = pl.program_id(0)
    if n_slots:
        *rings, sem = rings
        slot = decode._ring_fetch(i, row_ref[i], tile_ref[i], row_ref,
                                  tile_ref, pos_ref,
                                  list(zip(caches, rings)), sem, bk=bk,
                                  max_len=max_len, T=T)
        tile = rings[0][slot]
    else:
        tile = caches[0][0]
    m_s[...] = m_s[...] + tile[:, 0, :T * r].astype(jnp.float32)

    @pl.when(row_ref[i + 1] != row_ref[i])
    def _flush():
        o_ref[0] = jnp.zeros_like(o_ref[0]) + m_s[...][..., None]


def lognormal(rng, n, spec):
    x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    return np.clip(x, spec["min"], spec["max"])


def traffic_mix(rng, n, traffic, max_len):
    """Contexts of a closed loop's rows at a random moment: the prompt
    plus a uniform share of the output (perf/traffic/<name>.json)."""
    with open(os.path.join(ROOT, "perf", "traffic", traffic + ".json")) as f:
        req = json.load(f)["requests"]
    p = lognormal(rng, n, req["prompt_len"])
    if req["output_len"]["dist"] == "uniform":
        o = np.full(n, req["output_len"]["max"] / 2)
    else:
        o = lognormal(rng, n, req["output_len"])
    o = np.minimum(o, max_len - p)
    return np.minimum(p + rng.uniform(0, 1, n) * o,
                      max_len - 1).astype(np.int32)


def run_shape(name, shape, tiny):
    (b, nh, nkv, d, L, v_dim, T, block_len, n_tail, select, traffic,
     sinks) = shape
    dt = jnp.bfloat16
    keys = jax.random.split(jax.random.key(1), 5)
    kc = jax.random.normal(keys[0], (b, nkv, d, L), dt)
    vc = None if v_dim else jax.random.normal(keys[1], (b, nkv, d, L), dt)
    q0 = jax.random.normal(keys[2], (b, T, nh, d), dt)
    tail = None
    if n_tail:
        tk = jax.random.normal(keys[3], (n_tail, b, nkv, d), dt)
        tv = None if v_dim else jax.random.normal(
            keys[4], (n_tail, b, nkv, d), dt)
        tail = (tk, tv, jnp.int32(n_tail // 2))
    sel = jnp.ones((b, L), bool) if select else None
    sink = jnp.full((nh,), 2.0, jnp.float32) if sinks else None
    rng = np.random.default_rng(37)
    ctxs = {"mix": traffic_mix(rng, b, traffic, L - T),
            "full": np.full(b, L - T, np.int32)}
    calls = 4 if tiny else 40 if L < 8192 else 16
    real = decode._decode_kernel
    widths = ("_TILE_BYTES", "_LATENT_TILE_BYTES",
              "_LATENT_LONG_TILE_BYTES", "_BLOCK_K", "_LATENT_BLOCK_K",
              "_LATENT_LONG_BLOCK_K")
    saved = ([getattr(decode, w) for w in widths], decode._RING_SLOTS,
             decode._COMPUTE_BOUND)
    rule = decode.flash_decode_slots(kc, nh, v_dim, T)
    out = {}
    for cname, pos in ctxs.items():
        pos = pos // max(block_len, 1) * max(block_len, 1)
        posj = jnp.asarray(pos)
        live = int(np.minimum(pos + T, L).sum())
        print(f"## {name} {cname}: {b} rows x {nkv} x {d} x {L}, T={T}, "
              f"tail {n_tail}, sinks {sinks}, mean context "
              f"{pos.mean():.0f}; the rule "
              f"takes {'a ring of %d' % rule if rule else 'the pipeline'}",
              file=sys.stderr, flush=True)
        for label, slots, stub, width in PLANS[name]:
            if width is not None and width > L:
                continue
            try:
                if width is not None:
                    for w in widths:
                        setattr(decode, w, width if w.endswith("_K")
                                else nkv * d * width * 2)
                decode._RING_SLOTS = slots or saved[1]
                decode._COMPUTE_BOUND = float("inf") if slots else 0
                decode._decode_kernel = stub_kernel if stub else real
                # the kernel body is no static argument of the jitted
                # call: drop what was traced with the other one
                decode._flash_call.clear_cache()
                bk = decode.flash_decode_tile(kc, nh, latent=bool(v_dim))
                work = decode.decode_work_list(posj, T, bk, -(-L // bk))

                # the caches are arguments of the jitted chain: closed
                # over, they would be baked into it as constants
                def chain(q, kc, vc, tail, sel, sink, work):
                    def call(_, q):
                        o = decode.flash_block_decode(
                            q, kc, vc, posj, 1.0 / np.sqrt(d),
                            v_dim=v_dim, tail=tail, work=work,
                            select=sel, block_len=block_len, sinks=sink,
                            interpret=tiny or None)
                        return q + (o[..., :1] * 1e-30).astype(q.dtype)
                    return jax.lax.fori_loop(0, calls, call, q)

                f = jax.jit(chain)
                ops = (q0, kc, vc, tail, sel, sink, work)
                jax.block_until_ready(f(*ops))
                ts = []
                for _ in range(1 if tiny else 7):
                    t = time.perf_counter()
                    jax.block_until_ready(f(*ops))
                    ts.append((time.perf_counter() - t) / calls)
            finally:
                for w, v in zip(widths, saved[0]):
                    setattr(decode, w, v)
                decode._RING_SLOTS, decode._COMPUTE_BOUND = saved[1:]
                decode._decode_kernel = real
                decode._flash_call.clear_cache()
            steps = int(work[2])
            fetched = int(decode.decode_lanes_fetched(pos, T, bk, L,
                                                      slots).sum())
            out[f"{cname}/{label}"] = {
                "bk": bk, "steps": steps, "ms": round(min(ts) * 1e3, 4),
                "us_per_step": round(min(ts) * 1e6 / steps, 3),
                "lanes_fetched_per_live": round(fetched / live, 3)}
            print(f"{label:8s} bk={bk:5d} steps={steps:6d} "
                  f"{min(ts) * 1e3:.4f} ms  {min(ts) * 1e6 / steps:.3f} "
                  f"us/step  fetched/live {fetched / live:.3f}",
                  file=sys.stderr, flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("shapes", nargs="*", default=list(SHAPES))
    args = ap.parse_args()
    kind, _ = bench_device(args.tiny)
    table = TINY if args.tiny else SHAPES
    print(json.dumps({"device": kind, "attend_fetch": {
        n: run_shape(n, table[n], args.tiny) for n in args.shapes}}))


if __name__ == "__main__":
    main()
