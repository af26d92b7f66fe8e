#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the
chip. One process; run it from the checkout root on the machine that
holds the TPU:

    python3 chip_smoke.py

It drives the main train/serve path once through the entry points a user
calls, at the full width of the flagship model (vocab 32768, d_model
1024, 16 heads, d_ff 4096, bf16, 8 layers; weights random from a seed),
and — when the host has four chips — the rootless collectives and the
sharded train steps. Each phase checks its own result by the repo's own
means and raises on a miss; nothing catches a failed phase. What a phase
proves, beyond "it ran":

  * kernels really ran: the jitted step's lowered text must hold the
    expected number of Mosaic custom calls by kernel name
    (rlo_tpu.utils.hlo.mosaic_kernels), and the compiled text must keep
    them. The ``can_*`` shape gates are what is being checked, so they
    are not asked; a gate that falls back to the XLA reference path
    raises here (KernelFallbackWarning is an error in this process).
  * numerics at full width: LOGITS of the kernel path against the repo's
    reference path (the training forward with the unfused attention
    oracle, no cache, no kernels) on the same teacher-forced tokens,
    within a stated tolerance in bf16 ulps of the largest reference
    logit. Never sampled tokens: with seeded random weights the argmax
    flips on rounding.
  * four chips: results against numpy, ``collective_permute`` ops and the
    fused-combine kernel in the ring programs' text, and every output
    spread over four distinct devices.

It refuses to start — non-zero exit, nothing compiled, no result line —
unless ``jax.default_backend() == "tpu"`` and the device kind is in
rlo_tpu.utils.device.PEAKS. The last line of stdout of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

The phases are functions of a ``SmokeConfig`` so the same code can be
pre-flighted on the CPU mesh at a toy size before chip time is spent
(tests/test_chip_smoke.py, ``slow``): ``run(TINY)`` skips the TPU check
and the kernel expectations and never prints the result line.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import sys
import time
import warnings
from typing import Callable, Dict, Optional, Sequence, Tuple

#: bf16 keeps 8 significand bits: one ulp at magnitude m is m * 2**-8
BF16_ULP = 2.0 ** -8


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Everything a phase sizes itself from. FLAGSHIP is what the chip
    runs; TINY is the CPU pre-flight of the same control flow."""
    model: dict                       # TransformerConfig kwargs
    # trainer
    train_batch: int
    train_seq: int
    train_steps: int
    lr: float
    # servers
    n_slots: int
    max_len: int
    #: (prompt_len, max_new) per dense request; one prompt exceeds the
    #: largest prompt bucket that fits max_len, so the block_decode
    #: extend path runs
    dense_requests: Tuple[Tuple[int, int], ...]
    page_size: int
    paged_round_len: int
    #: paged wave 1: independent prompts. Budgets are 1 + k*round_len so
    #: budget-clipped rounds keep one static length (one compile).
    paged_wave1: Tuple[Tuple[int, int], ...]
    #: paged wave 2, submitted after wave 1's first prompt is prefilled
    #: and in the trie: (shared prefix tokens of that prompt, fresh
    #: suffix tokens, max_new)
    paged_wave2: Tuple[Tuple[int, int, int], ...]
    #: teacher-forced parity: ragged prompt lengths (padded to
    #: parity_bucket), then one decode step, then a block of
    #: parity_block tokens
    parity_plens: Tuple[int, int]
    parity_bucket: int
    parity_block: int
    #: tolerance on max|kernel - reference|, in bf16 ulps of the largest
    #: reference value. Measured on the v5e at full width: 1.6-2.5 ulps
    #: on logits (bf16 and int8 caches alike), 1.0 on ring attention; a
    #: wrong mask, page or scale moves it by tens.
    tol_ulps: float
    # four chips
    allreduce_elems: int              # fp32 elements per rank
    ring_seq: int                     # ring-attention global sequence
    expect_kernels: bool


FLAGSHIP = SmokeConfig(
    model=dict(vocab=32768, d_model=1024, n_heads=16, n_layers=8,
               d_ff=4096, dtype="bfloat16"),
    train_batch=4, train_seq=1024, train_steps=4, lr=0.1,
    n_slots=8, max_len=2048,
    dense_requests=((32, 40), (48, 16), (100, 8), (200, 33), (300, 64),
                    (500, 24), (700, 5), (1200, 12)),
    page_size=128, paged_round_len=8,
    paged_wave1=((300, 17), (32, 9), (500, 25), (700, 9), (129, 33)),
    paged_wave2=((300, 40, 17), (256, 100, 9), (300, 0, 9)),
    parity_plens=(200, 256), parity_bucket=256, parity_block=128,
    tol_ulps=8.0,
    allreduce_elems=16 << 20, ring_seq=4096,
    expect_kernels=True)

TINY = SmokeConfig(
    model=dict(vocab=256, d_model=128, n_heads=2, n_layers=2, d_ff=256,
               dtype="bfloat16"),
    train_batch=4, train_seq=128, train_steps=3, lr=0.1,
    n_slots=4, max_len=512,
    dense_requests=((8, 5), (40, 3), (100, 40), (300, 4)),
    page_size=128, paged_round_len=4,
    paged_wave1=((200, 5), (16, 9), (129, 5)),
    paged_wave2=((200, 20, 5), (128, 30, 9), (200, 0, 5)),
    parity_plens=(100, 128), parity_bucket=128, parity_block=128,
    tol_ulps=8.0,
    allreduce_elems=1 << 12, ring_seq=512,
    expect_kernels=False)


# ---------------------------------------------------------------------------
# scaffolding: kernel accounting, comparisons
# ---------------------------------------------------------------------------

def check_kernels(cfg: SmokeConfig, what: str, jitted, args: Sequence,
                  expect: Dict[str, int], static: Optional[dict] = None
                  ) -> Dict[str, int]:
    """Require ``expect`` ({kernel name: Mosaic call sites}) in the
    lowered text of ``jitted`` at ``args`` and as many custom calls left
    in the compiled text. Returns what was found. Skipped (returns {})
    where the config expects no kernels — the CPU pre-flight, whose
    lowering interprets them."""
    if not cfg.expect_kernels:
        return {}
    from rlo_tpu.utils import hlo
    lowered = jitted.lower(*args, **(static or {}))
    found = hlo.mosaic_kernels(lowered.as_text(), require=True)
    for name, n in expect.items():
        if found.get(name, 0) != n:
            raise AssertionError(
                f"{what}: expected {n} Mosaic call sites of kernel "
                f"{name!r}, the lowered program has "
                f"{found.get(name, 0)} (all kernels found: {found})")
    kept = hlo.mosaic_call_count(lowered.compile().as_text(),
                                 require=True)
    if kept < sum(found.values()):
        raise AssertionError(
            f"{what}: {sum(found.values())} Mosaic calls lowered, only "
            f"{kept} left after compilation")
    return found


def logit_gap(got, want) -> Tuple[float, float]:
    """(max|got - want|, max|want|) in f32; ``got`` must be finite."""
    import jax.numpy as jnp
    import numpy as np
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if not bool(jnp.isfinite(got).all()):
        raise AssertionError("non-finite values on the kernel path")
    return (float(np.asarray(jnp.max(jnp.abs(got - want)))),
            float(np.asarray(jnp.max(jnp.abs(want)))))


def check_gaps(what: str, gaps: Dict[str, Tuple[float, float]],
               tol_ulps: float) -> Dict[str, float]:
    """Every gap within ``tol_ulps`` bf16 ulps of its largest reference
    logit; returns the gaps in ulps."""
    out = {name: round(err / (scale * BF16_ULP), 2)
           for name, (err, scale) in gaps.items()}
    if not all(u <= tol_ulps for u in out.values()):
        raise AssertionError(
            f"{what}: max|kernel - reference| in bf16 ulps of the "
            f"largest reference value {out} exceeds the tolerance "
            f"{tol_ulps} (raw (err, scale): {gaps})")
    return out


def check_falling(what: str, losses: Sequence[float]) -> None:
    import numpy as np
    if not all(np.isfinite(losses)) or not all(
            b < a for a, b in zip(losses, losses[1:])):
        raise AssertionError(
            f"{what}: loss not finite and falling every step: {losses}")


def distinct_devices(x) -> int:
    return len({s.device for s in x.addressable_shards})


def make_model(cfg: SmokeConfig, **override):
    import jax
    from rlo_tpu.models.transformer import TransformerConfig, init_params
    mcfg = TransformerConfig(**{**cfg.model, **override})
    return mcfg, init_params(jax.random.PRNGKey(0), mcfg)


def reference_logits(params, tokens, mcfg):
    """The repo's reference path for every logits comparison here: the
    training forward over the whole teacher-forced sequence with the
    unfused attention oracle — no kernels, no cache."""
    import jax.numpy as jnp
    from rlo_tpu.models.transformer import (_local_attention, _rmsnorm,
                                            apply_layer, embed_tokens)
    pos = jnp.arange(tokens.shape[1])
    x = embed_tokens(params["embed"], tokens, pos, mcfg)
    for layer in params["layers"]:
        x, _ = apply_layer(
            x, layer, mcfg, pos=pos,
            attention=lambda q, k, v: _local_attention(
                q, k, v, use_flash=False))
    x = _rmsnorm(x, params["ln_f"]["g"])
    return (x @ params["embed"].T.astype(mcfg.act_dtype)).astype(
        jnp.float32)


def _tokens(seed: int, shape, vocab: int):
    import numpy as np
    return np.random.default_rng(seed).integers(
        0, vocab, shape).astype(np.int32)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_train(cfg: SmokeConfig) -> dict:
    """models.transformer.train_step under jax.jit on a repeated batch:
    loss finite and falling at every step, parameters moved, flash
    forward and backward kernels in the step, and the kernel forward's
    logits against the reference forward."""
    import jax
    import jax.numpy as jnp
    from rlo_tpu.models.transformer import forward, train_step

    mcfg, params = make_model(cfg)
    tokens = jnp.asarray(_tokens(1, (cfg.train_batch, cfg.train_seq),
                                 mcfg.vocab))
    step = jax.jit(lambda p, t: train_step(p, t, mcfg, lr=cfg.lr))
    p, losses = params, []
    for _ in range(cfg.train_steps):
        p, loss = step(p, tokens)
        losses.append(float(loss))
    check_falling("train_step", losses)
    moved = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(p), jax.tree.leaves(params)))
    if not moved > 0:
        raise AssertionError("train step changed no parameter")
    L = mcfg.n_layers
    kernels = check_kernels(cfg, "train_step", step, (params, tokens),
                            {"flash_fwd": L, "flash_bwd_dq": L,
                             "flash_bwd_dkv": L})
    got = jax.jit(lambda p, t: forward(p, t, mcfg))(params, tokens)
    want = jax.jit(lambda p, t: reference_logits(p, t, mcfg))(params,
                                                            tokens)
    ulps = check_gaps("train", {"forward": logit_gap(got, want)},
                      cfg.tol_ulps)
    return {"losses": [round(x, 4) for x in losses], "kernels": kernels,
            "logit_gap_ulps": ulps}


def _check_outputs(what: str, outs, requests, vocab: int) -> int:
    import numpy as np
    total = 0
    for i, (out, max_new) in enumerate(zip(outs, requests)):
        out = np.asarray(out)
        if out.shape != (max_new,):
            raise AssertionError(
                f"{what}: request {i} returned {out.shape[0]} tokens, "
                f"asked for {max_new}")
        if out.min() < 0 or out.max() >= vocab:
            raise AssertionError(f"{what}: request {i} token out of "
                                 f"vocabulary")
        total += max_new
    return total


def phase_serve_dense(cfg: SmokeConfig) -> dict:
    """models.serve.DecodeServer, dense cache, default buckets: every
    request completes at its length; bucket prefill, block_decode extend
    chunks and decode rounds all ran and all hold their kernels."""
    import jax.numpy as jnp
    from rlo_tpu.models.generate import init_kv_cache
    from rlo_tpu.models.serve import DecodeServer
    from rlo_tpu.utils.metrics import Registry

    mcfg, params = make_model(cfg)
    srv = DecodeServer(params, mcfg, n_slots=cfg.n_slots,
                       max_len=cfg.max_len, metrics=Registry())
    if not any(p > srv.buckets[-1] for p, _ in cfg.dense_requests):
        raise AssertionError("no prompt exceeds the largest bucket: the "
                             "extend path would not run")
    for i, (plen, max_new) in enumerate(cfg.dense_requests):
        srv.submit(_tokens(100 + i, (plen,), mcfg.vocab), max_new)
    outs = srv.run()
    n_tok = _check_outputs("dense", outs,
                           [m for _, m in cfg.dense_requests],
                           mcfg.vocab)
    if srv.rounds_run < 2:
        raise AssertionError(f"only {srv.rounds_run} decode rounds ran")

    L = mcfg.n_layers
    i32 = jnp.int32
    slots = jnp.zeros((cfg.n_slots,), i32)
    row = init_kv_cache(mcfg, 1, cfg.max_len)
    kernels = {
        "round": check_kernels(
            cfg, "dense round", srv._round,
            (params, srv.cache, slots, slots),
            {"flash_decode": L, "write_kv_row": 2 * L},
            static={"kk": srv.round_len}),
        "prefill": check_kernels(
            cfg, "dense prefill", srv._prefill,
            (params, jnp.zeros((1, srv.buckets[-1]), i32),
             jnp.ones((1,), i32)),
            {"flash_fwd": L}),
        "extend": check_kernels(
            cfg, "dense extend chunk", srv._extend,
            (params, row, jnp.zeros((1, srv._chunk_w), i32), i32(0),
             i32(1)),
            {"flash_block_decode": L, "write_kv_block": 2 * L}),
    }
    return {"requests": len(outs), "tokens": n_tok,
            "rounds": srv.rounds_run, "kernels": kernels}


def phase_serve_paged(cfg: SmokeConfig,
                      kv_cache_dtype: Optional[str] = None) -> dict:
    """DecodeServer(paged=True): chunked prefill, the prefix trie (two
    later prompts share pages of an earlier one), one copy-on-write
    page copy, and the three paged kernels; with ``int8`` the scale
    sidecars ride the same kernels (twice the write call sites)."""
    import jax.numpy as jnp
    import numpy as np
    from rlo_tpu.models.serve import DecodeServer
    from rlo_tpu.utils.metrics import Registry

    mcfg, params = make_model(cfg, kv_cache_dtype=kv_cache_dtype)
    reg = Registry()
    srv = DecodeServer(params, mcfg, n_slots=cfg.n_slots,
                       max_len=cfg.max_len, paged=True,
                       page_size=cfg.page_size,
                       round_len=cfg.paged_round_len, metrics=reg)
    prompts = [_tokens(200 + i, (plen,), mcfg.vocab)
               for i, (plen, _) in enumerate(cfg.paged_wave1)]
    budgets = [m for _, m in cfg.paged_wave1]
    for prompt, max_new in zip(prompts, budgets):
        srv.submit(prompt, max_new)
    # one round: wave 1 prefills and registers its pages in the trie,
    # so wave 2's shared prefixes can hit
    srv.step_round()
    for i, (shared, fresh, max_new) in enumerate(cfg.paged_wave2):
        srv.submit(np.concatenate([
            prompts[0][:shared],
            _tokens(300 + i, (fresh,), mcfg.vocab)]), max_new)
        budgets.append(max_new)
    outs = srv.run()
    n_tok = _check_outputs("paged", outs, budgets, mcfg.vocab)

    counters = reg.snapshot()["counters"]
    want_hits = sum(1 for s, _, _ in cfg.paged_wave2 if s > 0)
    if counters.get("serve.prefix_hits", 0) < want_hits:
        raise AssertionError(
            f"prefix trie hit {counters.get('serve.prefix_hits', 0)} "
            f"times, {want_hits} prompts shared a prefix")
    if counters.get("serve.cow_copies", 0) < 1:
        raise AssertionError("no copy-on-write page copy ran")
    if counters.get("serve.prefill_chunks", 0) <= len(budgets):
        raise AssertionError("no prompt was prefilled in several chunks")
    if srv.allocator.pages_in_use != (srv.trie.entries if srv.trie
                                      else 0):
        raise AssertionError(
            f"{srv.allocator.pages_in_use} pages still in use after "
            f"every request retired, the trie holds {srv.trie.entries}")

    L = mcfg.n_layers
    w = 4 if kv_cache_dtype == "int8" else 2   # k, v (+ ks, vs)
    i32 = jnp.int32
    slots = jnp.zeros((cfg.n_slots,), i32)
    table = jnp.zeros((cfg.n_slots, srv.max_pages), i32)
    kernels = {
        "round": check_kernels(
            cfg, "paged round", srv._round_paged,
            (params, srv.pools, table, slots, slots,
             jnp.zeros((cfg.n_slots,), bool)),
            {"paged_flash_decode": L, "write_kv_page_row": w * L},
            static={"kk": srv.round_len}),
        "chunk": check_kernels(
            cfg, "paged prefill chunk", srv._chunk,
            (params, srv.pools, table[:1],
             jnp.zeros((1, cfg.page_size), i32), i32(0), i32(1)),
            {"paged_flash_decode": L, "write_kv_page_block": w * L}),
    }
    return {"requests": len(outs), "tokens": n_tok,
            "rounds": srv.rounds_run,
            "prefix_hits": counters["serve.prefix_hits"],
            "cow_copies": counters["serve.cow_copies"],
            "prefill_chunks": counters["serve.prefill_chunks"],
            "kernels": kernels}


def _parity_sequences(cfg: SmokeConfig, vocab: int):
    """Two teacher-forced rows: ragged prompts, one decoded token, one
    block. Returns (tokens (2, S) zero-padded, total lengths)."""
    import numpy as np
    lens = [p + 1 + cfg.parity_block for p in cfg.parity_plens]
    toks = np.zeros((2, max(lens)), np.int32)
    for r, n in enumerate(lens):
        toks[r, :n] = _tokens(400 + r, (n,), vocab)
    return toks, lens


def phase_parity_dense(cfg: SmokeConfig) -> dict:
    """The dense serving path's logits against the reference forward on
    the same tokens: bucket-padded ragged ``prefill`` (flash forward),
    one ragged ``decode_step`` (flash_decode + write_kv_row), one
    ``block_decode`` (flash_block_decode + write_kv_block)."""
    import jax
    import jax.numpy as jnp
    from rlo_tpu.models.generate import (block_decode, decode_step,
                                         init_kv_cache, prefill)
    mcfg, params = make_model(cfg)
    toks, _ = _parity_sequences(cfg, mcfg.vocab)
    plens = jnp.asarray(cfg.parity_plens, jnp.int32)
    B, T = cfg.parity_bucket, cfg.parity_block
    want = jax.jit(lambda p, t: reference_logits(p, t, mcfg))(
        params, jnp.asarray(toks))

    def at(offset):        # reference logits at per-row position
        return jnp.stack([want[r, int(plens[r]) + offset]
                          for r in range(2)])

    prompt = jnp.asarray(toks[:, :B]) * (
        jnp.arange(B)[None, :] < plens[:, None])   # pad past each plen
    cache = init_kv_cache(mcfg, 2, cfg.max_len)
    lg_pre, cache = jax.jit(lambda p, t, c, n: prefill(
        p, t, c, mcfg, last_index=n - 1))(params, prompt, cache, plens)
    nxt = jnp.asarray([toks[r, cfg.parity_plens[r]] for r in range(2)])
    lg_dec, cache = jax.jit(lambda p, t, n, c: decode_step(
        p, t, n, c, mcfg))(params, nxt, plens, cache)
    blk = jnp.asarray([toks[r, cfg.parity_plens[r] + 1:
                            cfg.parity_plens[r] + 1 + T]
                       for r in range(2)])
    lg_blk, cache = jax.jit(lambda p, t, n, c: block_decode(
        p, t, n, c, mcfg))(params, blk, plens + 1, cache)
    want_blk = jnp.stack([want[r, cfg.parity_plens[r] + 1:
                               cfg.parity_plens[r] + 1 + T]
                          for r in range(2)])
    return {"logit_gap_ulps": check_gaps("dense", {
        "prefill": logit_gap(lg_pre, at(-1)),
        "decode_step": logit_gap(lg_dec, at(0)),
        "block_decode": logit_gap(lg_blk, want_blk)}, cfg.tol_ulps)}


def phase_parity_paged(cfg: SmokeConfig,
                       kv_cache_dtype: Optional[str] = None) -> dict:
    """The paged path's logits against the same reference: each row's
    prompt through page-aligned ``paged_prefill_chunk`` calls, one
    ragged ``paged_decode_step`` over both rows, then a mid-page chunk
    (non-zero page offset) on row 0."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from rlo_tpu.models.paged import (init_page_pool, paged_decode_step,
                                      paged_prefill_chunk)
    mcfg, params = make_model(cfg, kv_cache_dtype=kv_cache_dtype)
    toks, lens = _parity_sequences(cfg, mcfg.vocab)
    ps = cfg.page_size
    mp = -(-cfg.max_len // ps)
    want = jax.jit(lambda p, t: reference_logits(p, t, mcfg))(
        params, jnp.asarray(toks))
    # row r owns consecutive physical pages after the null page
    table = np.zeros((2, mp), np.int32)
    nxt_page = 1
    for r, n in enumerate(lens):
        need = -(-n // ps)
        table[r, :need] = np.arange(nxt_page, nxt_page + need)
        nxt_page += need
    pools = init_page_pool(mcfg, nxt_page, ps)
    table = jnp.asarray(table)
    chunk = jax.jit(lambda p, pools, row, t, a, n: paged_prefill_chunk(
        p, t, a, n, pools, row, mcfg), donate_argnums=(1,))

    def run_chunk(pools, r, a, end):
        t = np.zeros((1, ps), np.int32)
        t[0, :end - a] = toks[r, a:end]
        return chunk(params, pools, table[r:r + 1], jnp.asarray(t),
                     jnp.int32(a), jnp.int32(end - a))

    gaps = {}
    for r, plen in enumerate(cfg.parity_plens):
        a = 0
        while a < plen:
            end = min(plen, (a // ps + 1) * ps)
            lg, pools = run_chunk(pools, r, a, end)
            a = end
        gaps[f"prefill_row{r}"] = logit_gap(lg[0], want[r, plen - 1])
    plens = jnp.asarray(cfg.parity_plens, jnp.int32)
    nxt = jnp.asarray([toks[r, cfg.parity_plens[r]] for r in range(2)])
    lg, pools = jax.jit(lambda p, t, n, pools, tb: paged_decode_step(
        p, t, n, pools, tb, jnp.ones((2,), bool), mcfg),
        donate_argnums=(3,))(params, nxt, plens, pools, table)
    gaps["decode_step"] = logit_gap(lg, jnp.stack(
        [want[r, cfg.parity_plens[r]] for r in range(2)]))
    a = cfg.parity_plens[0] + 1
    end = min(lens[0], (a // ps + 1) * ps)
    lg, pools = run_chunk(pools, 0, a, end)
    gaps["midpage_chunk"] = logit_gap(lg[0], want[0, end - 1])
    return {"logit_gap_ulps": check_gaps(
        f"paged[{kv_cache_dtype or 'bf16'}]", gaps, cfg.tol_ulps)}


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

WS = 4


def phase_collectives(cfg: SmokeConfig) -> dict:
    """rlo_tpu.init(backend="tpu", world_size=4): every allreduce
    schedule on a buffer of real size against numpy, and the rest of the
    op surface. Ring programs must hold collective_permute ops and the
    fused-combine kernel; outputs must sit on four distinct devices."""
    import numpy as np

    import rlo_tpu
    from rlo_tpu.utils import hlo

    n = cfg.allreduce_elems
    rng = np.random.default_rng(7)
    xs = [rng.standard_normal(n, dtype=np.float32) for _ in range(WS)]
    want = np.sum(np.stack(xs), axis=0, dtype=np.float64)
    facts: dict = {"bytes_per_rank": 4 * n}
    stacked = np.stack(xs)
    with rlo_tpu.init(backend="tpu", world_size=WS) as be:
        for alg in ("psum", "ring", "bidir_ring", "recursive_doubling",
                    "halving_doubling"):
            outs = be.allreduce(xs, algorithm=alg)
            for r in range(WS):
                np.testing.assert_allclose(
                    outs[r], want, rtol=1e-5, atol=1e-4,
                    err_msg=f"allreduce {alg} rank {r}")
            # the facade compiled one program per (op, schedule,
            # shape); read it back for the text and placement checks
            prog = be._cache[("allreduce", "sum", alg, xs[0].shape,
                              "float32")]
            if distinct_devices(prog(stacked)) != WS:
                raise AssertionError(
                    f"allreduce {alg}: output not on {WS} devices")
            if alg == "psum":
                continue
            text = prog.lower(stacked).as_text()
            nbytes, nperm = hlo.permute_total_bytes(text, require=True)
            facts[alg] = {"permutes": nperm, "permute_bytes": nbytes}
            if cfg.expect_kernels:
                facts[alg]["fused_combine"] = hlo.mosaic_kernels(
                    text, require=True)["fused_combine"]
        del outs, stacked

        small = [rng.standard_normal(1 << 16, dtype=np.float32) + r
                 for r in range(WS)]
        for r, got in enumerate(be.bcast(2, small[2])):
            np.testing.assert_array_equal(got, small[2],
                                          err_msg=f"bcast rank {r}")
        if be.consensus([1, 1, 1, 1]) != 1:
            raise AssertionError("unanimous consensus did not approve")
        if be.consensus([1, 1, 0, 1]) != 0:
            raise AssertionError("a veto did not veto")
        total = np.sum(np.stack(small), axis=0)
        for r, got in enumerate(be.reduce_scatter(small)):
            np.testing.assert_allclose(
                got, total.reshape(WS, -1)[r], rtol=1e-5, atol=1e-5,
                err_msg=f"reduce_scatter rank {r}")
        for r, got in enumerate(be.all_gather(small)):
            np.testing.assert_array_equal(got, np.stack(small),
                                          err_msg=f"all_gather rank {r}")
        grid = [[np.full((256,), 10 * s + d, np.float32)
                 for d in range(WS)] for s in range(WS)]
        a2a = be.all_to_all(grid)
        for d in range(WS):
            for s in range(WS):
                np.testing.assert_array_equal(a2a[d][s], grid[s][d])
        be.barrier()
    return facts


def phase_hybrid(cfg: SmokeConfig) -> dict:
    """backend="hybrid": the C engines decide, the mesh executes. One
    approved propose_collective round and one vetoed from the device —
    a shard holding a NaN votes no, so no collective runs."""
    import jax.numpy as jnp
    import numpy as np

    import rlo_tpu

    rng = np.random.default_rng(8)
    xs = [rng.standard_normal(1 << 16, dtype=np.float32)
          for _ in range(WS)]

    def judge(v):
        return jnp.all(jnp.isfinite(v)).astype(jnp.int32)

    with rlo_tpu.init(backend="hybrid", world_size=WS) as be:
        decision, outs = be.propose_collective(
            "allreduce", xs, proposer=1, device_judge=judge)
        if decision != 1:
            raise AssertionError("a healthy proposal was not approved")
        want = np.sum(np.stack(xs), axis=0)
        for r in range(WS):
            np.testing.assert_allclose(outs[r], want, rtol=1e-5,
                                       atol=1e-5)
        bad = [x.copy() for x in xs]
        bad[3][5] = np.nan
        decision, outs = be.propose_collective(
            "allreduce", bad, proposer=2, device_judge=judge)
        if decision != 0 or outs is not None:
            raise AssertionError("rank 3's NaN shard did not veto")
    return {"approved": 1, "vetoed": 1}


def _sharded_train(cfg: SmokeConfig, what: str, mesh_shape, axes,
                   step_kw: dict, param_specs_of: Callable,
                   check_vma: bool, extra_kernels: Dict[str, int]
                   ) -> dict:
    """A flagship train step on a four-chip mesh against the one-chip
    step on the same parameters and batch: first loss and the first
    update agree, loss falls, parameters live on four devices."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.models.transformer import train_step
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit
    from rlo_tpu.utils import hlo

    mcfg, params = make_model(cfg)
    tokens = jnp.asarray(_tokens(1, (cfg.train_batch, cfg.train_seq),
                                 mcfg.vocab))
    mesh = make_mesh(mesh_shape, axes)
    specs = param_specs_of(mcfg)
    step = shard_jit(
        lambda p, t: train_step(p, t, mcfg, lr=cfg.lr, **step_kw),
        mesh, (specs, P("dp")), (specs, P()), check_vma=check_vma)
    one_p, one_loss = jax.jit(
        lambda p, t: train_step(p, t, mcfg, lr=cfg.lr))(params, tokens)
    p, losses = params, []
    for i in range(cfg.train_steps - 1):
        p, loss = step(p, tokens)
        losses.append(float(loss))
        if i == 0:
            first = p
    check_falling(what, losses)
    if abs(losses[0] - float(one_loss)) > 2e-2:
        raise AssertionError(
            f"{what}: first loss {losses[0]} vs one chip "
            f"{float(one_loss)}")
    # the first update against the one-chip update, on the two largest
    # kinds of leaf; a lost shard's gradient moves this by >= 1/4
    rel = {}
    for name, pick in (("embed", lambda t: t["embed"]),
                       ("w1", lambda t: t["layers"][0]["w1"])):
        d_one = np.asarray(pick(one_p)) - np.asarray(pick(params))
        d_got = np.asarray(pick(first)) - np.asarray(pick(params))
        rel[name] = float(np.linalg.norm(d_got - d_one)
                          / np.linalg.norm(d_one))
        if not rel[name] < 0.1:
            raise AssertionError(
                f"{what}: update of {name} differs from the one-chip "
                f"update by {rel[name]:.3f} of its norm")
    spread = min(distinct_devices(x) for x in jax.tree.leaves(p))
    if spread != WS:
        raise AssertionError(f"{what}: a parameter sits on {spread} "
                             f"devices, not {WS}")
    L = mcfg.n_layers
    facts = {"losses": [round(x, 4) for x in losses],
             "one_chip_loss": round(float(one_loss), 4),
             "update_rel_err": {k: round(v, 4) for k, v in rel.items()}}
    facts["kernels"] = check_kernels(
        cfg, what, step, (params, tokens),
        {"flash_fwd": L, "flash_bwd_dq": L, "flash_bwd_dkv": L,
         **extra_kernels})
    if step_kw.get("grad_algorithm") == "ring":
        text = step.lower(params, tokens).as_text()
        nbytes, nperm = hlo.permute_total_bytes(text, require=True)
        facts["permutes"], facts["permute_bytes"] = nperm, nbytes
    return facts


def phase_train_dp_tp(cfg: SmokeConfig) -> dict:
    """(dp=2, tp=2) via shard_jit + param_pspecs."""
    from rlo_tpu.models.transformer import param_pspecs
    return _sharded_train(
        cfg, "train (dp=2, tp=2)", (2, 2), ("dp", "tp"),
        dict(dp_axis="dp", tp_axis="tp"),
        lambda mcfg: param_pspecs(mcfg, "tp"), True, {})


def phase_train_ring(cfg: SmokeConfig) -> dict:
    """Pure dp=4 with grad_algorithm="ring": the ppermute ring with the
    Pallas fused combine, one per gradient leaf; vma typing off, as a
    manual-ring result cannot be typed invariant."""
    from jax.sharding import PartitionSpec as P
    n_leaves = 2 + 6 * cfg.model["n_layers"]
    return _sharded_train(
        cfg, "train (dp=4, ring)", (4,), ("dp",),
        dict(dp_axis="dp", grad_algorithm="ring"),
        lambda mcfg: P(), False, {"fused_combine": n_leaves})


def phase_ring_attention(cfg: SmokeConfig) -> dict:
    """ops.ring_attention over sp=4 with the flash block update against
    the unsharded oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.ops.ring_attention import full_attention, ring_attention
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit
    from rlo_tpu.utils import hlo

    heads = cfg.model["n_heads"]
    hd = cfg.model["d_model"] // heads
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.standard_normal((cfg.ring_seq, heads, hd)),
                           jnp.bfloat16) for _ in range(3))
    mesh = make_mesh((WS,), ("sp",))
    ring = shard_jit(
        lambda q, k, v: ring_attention(q, k, v, "sp", causal=True),
        mesh, (P("sp"),) * 3, P("sp"))
    got = ring(q, k, v)
    want = jax.jit(lambda q, k, v: full_attention(q, k, v, causal=True))(
        q, k, v)
    ulps = check_gaps("ring attention", {"out": logit_gap(got, want)},
                      cfg.tol_ulps)
    if distinct_devices(got) != WS:
        raise AssertionError("ring attention output not on four devices")
    text = ring.lower(q, k, v).as_text()
    nbytes, nperm = hlo.permute_total_bytes(text, require=True)
    facts = {"gap_ulps": ulps, "permutes": nperm,
             "permute_bytes": nbytes}
    # one call site in the fori_loop body, one for the last block
    facts["kernels"] = check_kernels(cfg, "ring attention", ring,
                                     (q, k, v), {"flash_fwd": 2})
    return facts


ONE_CHIP: Tuple[Tuple[str, Callable], ...] = (
    ("train", phase_train),
    ("serve_dense", phase_serve_dense),
    ("serve_paged", phase_serve_paged),
    ("serve_paged_int8",
     lambda cfg: phase_serve_paged(cfg, kv_cache_dtype="int8")),
    ("parity_dense", phase_parity_dense),
    ("parity_paged", phase_parity_paged),
    ("parity_paged_int8",
     lambda cfg: phase_parity_paged(cfg, kv_cache_dtype="int8")),
)
FOUR_CHIPS: Tuple[Tuple[str, Callable], ...] = (
    ("collectives", phase_collectives),
    ("hybrid", phase_hybrid),
    ("train_dp_tp", phase_train_dp_tp),
    ("train_ring", phase_train_ring),
    ("ring_attention", phase_ring_attention),
)


def run(cfg: SmokeConfig) -> dict:
    """Run the phases of ``cfg`` in order and print one summary line
    each. A failed phase raises; nothing here catches it. (To bring up
    one phase, call it: ``chip_smoke.phase_train_ring(FLAGSHIP)``.)"""
    import jax

    from rlo_tpu.pallas.reduce import KernelFallbackWarning
    from rlo_tpu.utils.tracing import BUILDS, build_totals
    warnings.simplefilter("error", KernelFallbackWarning)
    BUILDS.arm()

    def built() -> Tuple[float, int, int]:
        # seconds tracing, lowering and obtaining executables, each
        # once, and the persistent cache's hits and misses so far
        t = build_totals(BUILDS.records)
        return (1e-9 * sum(n for key, n in t.items() if key.endswith("_ns")),
                t["cache_hits"], t["cache_misses"])

    n_dev = len(jax.devices())
    phases = list(ONE_CHIP)
    if n_dev >= WS:
        phases += FOUR_CHIPS
    results: dict = {}
    for name, fn in phases:
        c0, h0, m0 = built()
        t0 = time.perf_counter()
        facts = fn(cfg)
        wall = time.perf_counter() - t0
        c1, h1, m1 = built()
        results[name] = facts
        print(f"phase {name}: ok wall={wall:.1f}s compile={c1 - c0:.1f}s "
              f"cache_hits={h1 - h0} cache_misses={m1 - m0} "
              f"{json.dumps(facts, sort_keys=True)}", flush=True)
        gc.collect()
    if n_dev < WS:
        print(f"multichip: not_run ({n_dev} devices)", flush=True)
    return results


def main() -> int:
    import jax

    from rlo_tpu.utils import device
    device.require_tpu()       # raises, naming the backend it found
    cache_dir = device.enable_compile_cache()
    info = device.describe()
    print(f"device: {json.dumps(info)}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)
    t0 = time.perf_counter()
    run(FLAGSHIP)
    print(f"total wall={time.perf_counter() - t0:.1f}s", flush=True)
    d = jax.devices()
    print(json.dumps({"ok": True, "device": {
        "platform": d[0].platform, "kind": d[0].device_kind,
        "count": len(d)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
