"""Continuous batching: a persistent decode loop with slot admission.

The serving shape after ragged prompts (round-5 VERDICT item 6): a
fixed pool of ``n_slots`` batch rows decodes forever; when a row
finishes its request, the slot is re-filled by the next pending request
without restarting the batch — the reference-side analogue is the
engine manager multiplexing independent engines over one progress loop
(/root/reference/rootless_ops.c:33-47: many engines, one
`RLO_make_progress_all`), here it is many REQUESTS multiplexing one
jitted decode program.

TPU-shaped design decisions:
  - The decode program is ONE jit over the whole slot pool — static
    shapes (n_slots, max_len), per-row positions/masks from the ragged
    machinery (models.generate decode_step with a (b,) pos vector), so
    admission never recompiles.
  - Admission granularity is a ROUND of ``round_len`` decode steps
    (one lax.scan inside one jit): every host round trip costs a
    dispatch and a readback, which per token would dominate a decode
    step; round_len amortizes it. Iteration-level batching a la Orca.
  - DENSE mode (the original): a fresh request prefills into its slot
    with the blockwise prefill (one forward at a padded prompt bucket),
    then the row's cache is scattered into the pool cache at the slot
    index. The requests one admission pass takes of one bucket are ONE
    launch — a device loop over them, a row at a time — and one read
    of their first tokens (DecodeServer._admit). Prompts longer than
    the largest bucket extend past it in
    jitted ``block_decode`` chunks — admission never rejects a prompt
    that fits ``max_len - max_new`` — of 128 tokens, or of twice that
    for a prompt that runs further than one such chunk past the bucket
    (extend_widths: half the passes over the weights; a latent cache's
    attend of such a chunk goes context tile by context tile,
    models.kvcache._attend_latent_blocked).
  - PAGED mode (``paged=True``, docs/DESIGN.md §12): the per-slot
    dense cache becomes a global pool of ``page_size``-token seq-minor
    pages plus a per-slot int32 page table
    (models.paged / serving.pages). Prompts stream through CHUNKED
    prefill (page-aligned ≤ page_size-token forwards interleaved with
    decode rounds — no prompt buckets, no padding waste), shared
    prompt prefixes map the same physical pages copy-on-write through
    a radix trie, and rounds clip to the shortest active budget so
    finished rows never burn slot-steps.
  - Finished rows keep decoding masked garbage until the round ends
    (their budget exhausted); outputs are truncated to the request's
    max_new, and slot reuse is safe because every attend masks at the
    row's own position and cache writes overwrite in order.
  - BLOCK DIFFUSION (``cfg.block_len`` > 0, dense scheduler): a model
    that generates block by block by masked denoising. A slot's state
    is its current block (``block_len`` token ids, which of them still
    hold the mask id) at ``pos``, the block's first position; a round
    is ``round_len`` PASSES in one jitted program, and a pass is one
    forward of every row's block (generate.block_decode under the
    block-causal mask, write-then-attend): a row with a mask left
    DENOISES (generate.denoise_update unmasks 1 to block_len of its
    positions; ``pos`` stays, the K/V rows it wrote are provisional), a
    row with none COMMITS (the same forward on the final tokens, whose
    K/V rows stand; the block is delivered, ``pos`` moves by block_len,
    a new all-mask block begins). One program serves rows in either
    phase side by side; a pass yields 0 to block_len tokens a row.
    Admission prefills the prompt's whole blocks without the head and
    takes no first token; the leftover ``plen mod block_len`` prompt
    tokens open the first block, unmasked.

Oracle (tests/test_serve.py, tests/test_paged.py): any stream of
requests produces, per request, EXACTLY the tokens of its dense
`generate` — continuous batching, chunked prefill, and page
indirection are scheduling/layout changes, not numerics changes.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rlo_tpu.models import kvcache, moe
from rlo_tpu.models.generate import (block_decode, decode_step,
                                     denoise_update, prefill, _decode_cfg)
from rlo_tpu.models.kvcache import (fold_kv_tail, init_kv_cache,
                                    init_kv_tail)
from rlo_tpu.models.transformer import TransformerConfig
from rlo_tpu.observe.spans import Stage
from rlo_tpu.pallas import expert_ffn
from rlo_tpu.pallas.reduce import _on_tpu
from rlo_tpu.utils.metrics import Registry, SERVING
from rlo_tpu.utils.tracing import BUILDS, annotate, build_table

# the programs of a start that precede its server (a caller's weights)
# are in the build log too, under no span
BUILDS.arm()

#: newest samples kept beside the log2 buckets of the four latency
#: histograms, so stats() percentiles are exact (8 bytes a sample)
_HIST_KEEP = 4096


@dataclasses.dataclass
class Request:
    """One generation request: ``prompt`` (plen,) int32, ``max_new``
    tokens to generate. ``eos_id`` optionally ends the row early (the
    emitted tokens still include the eos)."""
    prompt: np.ndarray
    max_new: int
    eos_id: Optional[int] = None


#: the dense scheduler's prompt buckets, unless the caller names others
PROMPT_BUCKETS = (64, 256, 1024)

#: what a round of block diffusion counts of itself, in this order
#: (``serve.diffusion.<name>``; DecodeServer's docstring)
BLOCK_STATS = ("row_passes", "denoise_passes", "commit_passes",
               "tokens_unmasked", "blocks_committed", "tokens_committed",
               "surplus_dropped", "leftover_committed")


def prompt_buckets_for(cfg: TransformerConfig, max_len: int,
                       prompt_buckets: Tuple[int, ...]) -> Tuple[int, ...]:
    """The dense scheduler's prefill buckets: those of
    ``prompt_buckets`` that fit the cache. A prompt block is attended
    whole, before any selection, so a token selector's buckets stop
    at what it would keep anyway."""
    widest = min(max_len, cfg.index_topk) if cfg.dsa else max_len
    buckets = tuple(b for b in sorted(prompt_buckets) if b <= widest)
    if not buckets:
        raise ValueError(
            f"no prompt bucket fits max_len {max_len} "
            f"(buckets {tuple(sorted(prompt_buckets))})")
    return buckets


def extend_widths(buckets: Tuple[int, ...]) -> Tuple[int, int]:
    """(chunk, long chunk): the widths in which a prompt longer than
    the widest bucket extends past it (jitted block_decode chunks). A
    prompt that runs further than one long chunk past the bucket takes
    the long width for all of its chunks, any other the short one: two
    shapes of the extend program. The long width is two short ones, 256
    tokens at the default buckets: a v5e admits 8k-18k-token prompts of
    a latent cache at 5.6 / 6.1 / 5.5 / 4.9 / 4.4 k tokens a second in
    chunks of 128 / 256 / 512 / 1024 / 2048 (PERF.md, PR 31: a chunk's
    pass over the weights against a block attend that slows as its
    (heads, T, tile) scores grow)."""
    chunk = min(128, buckets[-1])
    return chunk, 2 * chunk


def _bucket(plen: int, buckets: Tuple[int, ...]) -> int:
    for b in buckets:
        if plen <= b:
            return b
    raise ValueError(f"prompt length {plen} exceeds the largest "
                     f"bucket {buckets[-1]}")


class DecodeServer:
    """Continuous-batching server over ``n_slots`` rows.

    submit() queues requests; run() drives rounds until every request
    completes and returns the per-request token arrays in submission
    order. step_round() is the unit the throughput bench times.

    Serving telemetry (docs/DESIGN.md §7) records into ``metrics``
    (default: the process-wide ``metrics.SERVING`` registry, shared
    with ``generate_timed``): TTFT (submit, or the request's ``due``
    time, -> first token, ``serve.ttft_usec``), admission-queue wait
    (``serve.queue_wait_usec``), per-request end-to-end latency
    (-> last token, ``serve.e2e_usec``) and per-round decode latency
    (``serve.round_usec``) — log2 histograms that also keep their
    newest 4096 samples, so ``stats()`` percentiles are exact —
    request/token counters, and live queue-depth gauges.

    Every stage of the host loop runs inside ONE span helper
    (``utils.tracing.annotate``, always on): a profiler annotation
    ``perf.serve.<stage>`` on the profiler's clock, and its elapsed
    time in the counters ``serve.<stage>_ns`` / ``_n``. Stages:
    ``init`` (the constructor: cache allocation, the jitted functions,
    whatever runs eagerly); ``step_round``; ``admit`` with
    ``admit.stage_input``,
    ``admit.prefill_dispatch`` and ``admit.first_token_sync`` — dense
    scheduler: once a LAUNCH, which admits a pass's requests of one
    prompt bucket, so a group's requests share one sync and TTFT ends
    there; once a request for a prompt past the widest bucket, which
    also runs ``admit.extend`` and ``admit.scatter_dispatch`` —
    (paged: ``admit.map_pages``, ``admit.prefill_chunk``,
    ``page_gauges``); ``round.dispatch``, ``round.wait``,
    ``round.readback``; ``distribute``. Work counters sit at the same
    boundaries: ``serve.admissions``, ``serve.admit.launches``
    (admission programs launched) and ``serve.admit.batched_rows``
    (requests admitted through them: every one but the long prompts),
    ``serve.prefill_tokens`` against
    ``serve.prefill_padded_tokens``, ``serve.slot_steps`` against
    ``serve.slot_steps_useful``, ``serve.attend_tiles`` against
    ``serve.attend_tiles_live`` and ``serve.attend_steps`` (dense
    scheduler, flash_decode path: cache tiles of a grid over max_len,
    those a row's live context reaches, and the grid steps the kernel
    runs — its work list holds the live tiles alone),
    ``serve.attend_lanes_fetched`` against ``serve.attend_lanes_live``
    (cache positions the kernel's own copies move, a row's last tile
    rounded up to the copy's granule, and those the contexts hold) and
    the gauge
    ``serve.attend_fetch_depth`` (slots of the kernel's K/V ring;
    absent where the attend is the einsum or a step computes longer
    than it streams, a latent cache under many heads, and the
    pipeline fetches whole tiles), the gauge
    ``serve.cache_bytes_per_token`` (dense scheduler: what one position
    of one slot holds over all layers), ``serve.dsa.keys_scored``,
    ``serve.dsa.rows_attended``, ``serve.dsa.dense_row_steps`` and
    ``serve.dsa.latent_rows_read`` for a token selector
    (_count_dsa: once a round from the host's ``pos``, by the rule
    kvcache.select_counts keeps beside the program's own, the attend's
    form as the traced program reported it; keys are scored on the
    layers whose selection scored alone), ``serve.dsa.
    selections_scored`` and ``serve.dsa.selections_reused`` (layer-
    steps that chose a set by scoring it, or took the one the full
    layer before them chose: cfg.index_types; the layers of each kind
    as the traced round's step recorded its selections) and the gauge
    ``serve.dsa.select_kernel`` (1 where the traced round's selections
    ran the kernel pallas.decode.topk_select, 0 where XLA's
    kvcache.topk_mask),
    ``serve.moe.<count>`` for 'sigmoid_group' expert layers
    (models.moe.STATS, summed over the
    round's steps and layers on the device and read back with the
    tokens: ``tokens``, ``assignments_held``, ``rows_computed`` — tile
    padding included —, ``experts_hit``, ``dropped``, which stays 0)
    and the gauge ``serve.moe.ffn_steps_per_tile`` (grid steps a live
    tile of pallas.expert_ffn takes at the routed layers' shape: what
    its byte rule chose),
    ``serve.diffusion.<count>`` under block diffusion (BLOCK_STATS,
    counted on the device pass by pass over the rows that still owe
    tokens, summed over the round and read back with its tokens:
    ``row_passes`` = ``denoise_passes`` + ``commit_passes``,
    ``tokens_unmasked``, ``blocks_committed``, ``tokens_committed`` =
    block_len x ``blocks_committed`` - ``surplus_dropped`` -
    ``leftover_committed``: a last block's positions past what the
    request owed, computed and dropped, and the prompt's leftover
    tokens that opened a first block; ``serve.ttft_usec`` then ends at
    a request's first committed block, and ``serve.steps`` counts
    passes),
    ``serve.retraces`` (with
    ``serve.retraces.<fn>``): trace-cache entries of the server's own
    jitted functions beyond the shapes it was built for,
    and ``serve.build.<count>`` (utils.tracing.BUILD_COUNTS): the
    programs JAX obtained while a stage of this server was open and the
    nanoseconds it spent tracing, lowering and compiling them, each
    once, from the process's build log; in a steady round they stand
    still. ``stats()["build"]`` is the same log by function.

    PAGED mode adds the page-pool telemetry (docs/DESIGN.md §12):
    ``serve.pages_in_use`` / ``serve.pages_free`` gauges, prefix-cache
    counters (``serve.prefix_hits``, ``serve.prefix_tokens_shared``,
    ``serve.cow_copies``, ``serve.trie_evictions``), chunked-prefill
    counters (``serve.prefill_chunks``), and
    ``serve.admission_stalls`` (allocator backpressure).

    Paged knobs: ``page_size`` (128 on TPU — one lane block; smaller
    is legal off-TPU for tests), ``n_pages`` (pool size; default fits
    every slot at max_len plus the null page), ``prefill_budget``
    (max prompt tokens prefilled per slot per round — None finishes a
    prompt's prefill in its admission round; a finite budget
    interleaves long prompts' chunks with decode rounds, bounding
    their latency interference), ``prefix_cache`` (the radix trie),
    and ``clip_rounds`` (clip each round to the shortest active
    budget so finished rows never decode garbage; defaults on in
    paged mode, the dense scheduler is left byte-for-byte alone).
    """

    def __init__(self, params, cfg: TransformerConfig, *,
                 n_slots: int, max_len: int, round_len: int = 32,
                 prompt_buckets: Tuple[int, ...] = PROMPT_BUCKETS,
                 metrics: Optional[Registry] = None,
                 # rlo-prover: lane-pinned (one 128-lane cache block)
                 paged: bool = False, page_size: int = 128,
                 n_pages: Optional[int] = None,
                 prefill_budget: Optional[int] = None,
                 prefix_cache: bool = True,
                 clip_rounds: Optional[bool] = None):
        self.metrics = SERVING if metrics is None else metrics
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.round_len = round_len
        self.paged = paged
        self.pos = np.zeros((n_slots,), np.int32)
        self.last_tok = np.zeros((n_slots,), np.int32)
        self.budget = np.zeros((n_slots,), np.int64)  # tokens still due
        # block diffusion: a slot's current block at ``pos``, which of
        # its positions still hold the mask id, and how many of its
        # leading positions are the prompt's leftover tokens
        self.blk = np.zeros((n_slots, cfg.block_len), np.int32)
        self.masked = np.zeros((n_slots, cfg.block_len), bool)
        self.given = np.zeros((n_slots,), np.int32)
        self.req_of_slot: List[Optional[int]] = [None] * n_slots
        self._queue: List[Tuple[int, Request]] = []
        self._out: List[Optional[List[int]]] = []
        self._eos: List[Optional[int]] = []
        self._submit_ts: dict = {}  # rid -> submit time (perf_counter)
        # rid -> submit time, RETAINED until completion (the e2e
        # latency stamp; _submit_ts is popped at admission for the
        # queue-wait/TTFT numbers)
        self._accept_ts: dict = {}
        self._canceled: set = set()
        # newly completed (rid, tokens) pairs awaiting poll_completed()
        # — the serving fabric's incremental face (docs/DESIGN.md §11)
        self._completed_log: List[Tuple[int, np.ndarray]] = []
        self.rounds_run = 0
        self.steps_run = 0
        # optional rlo-trace hooks (docs/DESIGN.md §19): a SpanRecorder
        # plus a server-rid -> fabric-rid resolver, attached by
        # ModelBackend when the owning fabric traces; _span() hands
        # them the stages that carry a fabric Stage.
        self.spans = None
        self.span_rid_of = None
        # re-traces already counted, by function (see self._jits)
        self._retraced: Dict[str, int] = {}

        cfg_d = _decode_cfg(cfg)
        if paged and (cfg.block_len or cfg.windowed):
            raise ValueError("block diffusion and sliding layers run on "
                             "the dense scheduler so far (paged=False)")
        if paged and (cfg.hc or cfg.index_types is not None):
            raise ValueError(
                "hyper-connections and a shared token selection run on "
                "the dense scheduler so far (paged=False): the page pool "
                "holds per-head K/V without index keys")
        # what the server allocates, builds and runs eagerly before its
        # first request is a stage like any other: its seconds, and the
        # programs JAX obtains in them, are the server's (BUILDS)
        with self._span("init"):
            if paged:
                self._init_paged(cfg_d, page_size, n_pages,
                                 prefill_budget, prefix_cache,
                                 True if clip_rounds is None
                                 else clip_rounds)
            else:
                self._init_dense(cfg_d, prompt_buckets, bool(clip_rounds))

    def _init_dense(self, cfg_d: TransformerConfig,
                    prompt_buckets: Tuple[int, ...], clip_rounds: bool):
        params, cfg = self.params, self.cfg
        n_slots, max_len = self.n_slots, self.max_len
        self.clip_rounds = clip_rounds
        self.buckets = prompt_buckets_for(cfg, max_len, prompt_buckets)
        if cfg.block_len and any(b % cfg.block_len for b in self.buckets):
            raise ValueError(
                f"prompt buckets {self.buckets} must be whole blocks of "
                f"{cfg.block_len}: a long prompt goes on from the widest")
        # long prompts (plen > the largest bucket) extend the
        # bucket-prefilled row cache through jitted block_decode
        # chunks — the chunked-prefill unit on the dense path, so
        # admission never rejects a prompt that fits max_len - max_new
        self._chunk_w, self._long_w = extend_widths(self.buckets)
        # a sliding layer's ring keeps its window and the widest block
        # one call writes through it: a long prompt's chunk (a round's
        # tail reaches the ring once the round is over)
        self.cache = init_kv_cache(cfg, n_slots, max_len,
                                   ring_spare=self._long_w)
        self.metrics.gauge("serve.cache_bytes_per_token").set(
            kvcache.bytes_per_position(self.cache))
        self._window_tiling = None
        if cfg.windowed:
            ring = kvcache._ring_entry(self.cache, cfg)["k"].shape[3]
            if self.buckets[-1] > ring:
                # a ring keeps a prompt block's last R positions; padding
                # past a prompt would stand in for keys a lap back
                raise ValueError(
                    f"prompt buckets {self.buckets} must fit a sliding "
                    f"layer's ring of {ring} positions")
            self._window_tiling = (kvcache.window_tiling(self.cache, cfg)
                                   if _on_tpu() else None)
        # the decode kernel's tiling of the cache axis as (tile width,
        # tiles), for the attend-tile counters; None where decode_step
        # attends through the einsum (off the tpu backend, or a shape
        # can_flash_decode refuses): nothing is tiled, nothing counted
        tiling = kvcache.attend_tiling(self.cache, cfg) if _on_tpu() \
            else None
        self._attend_tiling = None if cfg.block_len else tiling
        # the ring the kernel fetches its own K/V through, where it
        # does: 0 where a step computes longer than it streams and the
        # pipeline brings whole tiles
        self._attend_slots = 0
        if tiling is not None:
            from rlo_tpu.pallas.decode import flash_decode_slots
            self._attend_slots = flash_decode_slots(
                self.cache[0]["k"], cfg.n_heads,
                cfg.kv_lora_rank if cfg.mla else 0, cfg.block_len or 1)
        if self._attend_slots:
            self.metrics.gauge("serve.attend_fetch_depth").set(
                self._attend_slots)
        # routed expert layers report their routing counts
        # (models.moe.STATS): the round then carries their sum over its
        # steps and layers and returns it as its last output
        routed = [layer["moe"] for layer in params["layers"]
                  if "moe" in layer]
        moe_stats = cfg.moe_router in moe.ROUTED and bool(routed)
        if moe_stats:
            # what pallas.expert_ffn's byte rule chose for their shape
            _, d, f = routed[0]["wg"].shape
            self.metrics.gauge("serve.moe.ffn_steps_per_tile").set(
                expert_ffn.steps_per_tile(d, f, cfg.act_dtype.itemsize))
        # the round owns its kk steps and nobody reads the cache in
        # between, so the new K/V rows wait in a write-behind tail and
        # reach the seq-minor cache once a round (init_kv_tail). An
        # int8 cache has no tail: it keeps the write of every step.
        self._kv_tail = kvcache.keeps_tail(self.cache)
        # a token selector's threshold, where the cache is long
        # enough for it to choose (_count_dsa); else 0. How its attends
        # read a selection is what the round's program says of itself
        # when it is traced (kvcache.select_counts' ``reads``), and so
        # is the form its selections ran (the gauge
        # serve.dsa.select_kernel, set when the round is traced)
        self._dsa_topk = cfg.index_topk if cfg.dsa and \
            max_len > cfg.index_topk else 0
        self._dsa_reads = None
        self._dsa_layers = None     # (layers that scored, that reused)

        # a sliding layer's ring takes the fold modulo its length
        windows = [cfg.layer_window(i) for i in range(cfg.n_layers)]

        def round_fn(params, cache, last_tok, pos, kk):
            tail = self._kv_tail        # read when the round is traced

            def body(carry, s):
                tok, kv, *stats = carry
                info = []
                dsa = [] if self._dsa_topk else None
                # kv is the tail (the cache is closed over, read only)
                # or, without one, the cache itself
                logits, kv = decode_step(
                    params, tok, pos + s, cache if tail else kv, cfg_d,
                    moe_info=info, tail=(kv, s) if tail else None,
                    dsa_info=dsa)
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                stats = [n + sum(i["stats"] for i in info) for n in stats]
                if dsa:         # traced: the forms the round took
                    self._dsa_reads = dsa[0]["reads"]
                    self.metrics.gauge("serve.dsa.select_kernel").set(
                        dsa[0]["select_kernel"])
                    self._dsa_layers = (
                        sum(1 for d in dsa if "keys_scored" in d),
                        sum(1 for d in dsa if d.get("shared")))
                return (tok, kv, *stats), tok

            stats = ([jnp.zeros((len(moe.STATS),), jnp.int32)]
                     if moe_stats else [])
            kv = init_kv_tail(cache, kk) if tail else cache
            (tok, kv, *stats), toks = lax.scan(
                body, (last_tok, kv, *stats),
                jnp.arange(kk, dtype=jnp.int32))
            cache = fold_kv_tail(cache, kv, pos, windows) if tail else kv
            return (tok, pos + kk, cache, jnp.transpose(toks),  # (b, kk)
                    *stats)

        if cfg.block_len:   # a round of passes over blocks instead
            round_fn = self._block_round_fn(cfg_d, moe_stats)
        # donate the pool cache: without aliasing, every round would
        # double-buffer the full n_slots x max_len cache in HBM
        self._round = jax.jit(round_fn, static_argnames=("kk",),
                              donate_argnums=(1,))

        def prefill_slot(params, prompt, length):
            # one padded row through the blockwise prefill; returns the
            # row cache + the first generated token
            row = init_kv_cache(cfg, 1, max_len, ring_spare=self._long_w)
            if cfg.block_len:
                # no head and no token: the passes generate (the
                # positions past the prompt's whole blocks hold what
                # the first pass overwrites)
                _, row = prefill(params, prompt, row, cfg,
                                 need_logits=False)
                return row, jnp.zeros((1,), jnp.int32)
            logits, row = prefill(params, prompt, row, cfg,
                                  last_index=length - 1)
            first = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return row, first

        # the one-row prefill as a program of its own: a prompt past the
        # widest bucket starts with it, and an admission program calls
        # it, a row at a time
        one_row = self._prefill = jax.jit(prefill_slot)

        def admit_rows(params, cache, staged):
            # one (pass, bucket) group in ONE launch: row i of
            # ``staged`` is [prompt padded to the bucket | length |
            # slot], live rows first (a live row's length is >= 1). A
            # device loop over the live rows alone runs, for each, the
            # one-row bucket prefill above and the scatter into its
            # slot: what the device computes for a request is what a
            # launch of its own would, and a padded row costs nothing.
            bucket = staged.shape[1] - 2
            live = jnp.sum(staged[:, bucket] > 0)

            def body(i, carry):
                cache, firsts = carry
                r = lax.dynamic_slice_in_dim(staged, i, 1)
                row, first = one_row(params, r[:, :bucket], r[:, bucket])
                cache = kvcache.scatter_slot(cache, row, r[0, bucket + 1])
                return cache, lax.dynamic_update_slice(firsts, first, (i,))

            return lax.fori_loop(
                0, live, body,
                (cache, jnp.zeros((staged.shape[0],), jnp.int32)))

        self._admit_rows = jax.jit(admit_rows, donate_argnums=(1,))
        # buckets whose admission program is built: a bucket's is built
        # when a request of the bucket first arrives (_launch_group)
        self._admit_built: set = set()

        def extend_chunk(params, row, toks, pos0, n_valid):
            logits, row = block_decode(params, toks,
                                       pos0[None], row, cfg)
            if cfg.block_len:   # whole blocks of the prompt; no token
                return jnp.zeros((1,), jnp.int32), row
            idx = jnp.clip(n_valid - 1, 0,
                           toks.shape[1] - 1)[None, None, None]
            xl = jnp.take_along_axis(
                logits, jnp.broadcast_to(
                    idx, (1, 1, logits.shape[-1])), axis=1)[:, 0]
            first = jnp.argmax(xl, axis=-1).astype(jnp.int32)
            return first, row

        self._extend = jax.jit(extend_chunk, donate_argnums=(1,))

        def scatter_slot(cache, row, slot):
            # this server's own function: jit keeps its trace cache,
            # which _observe_round counts, by function
            return kvcache.scatter_slot(cache, row, slot)

        self._scatter = jax.jit(scatter_slot, donate_argnums=(0,))
        # jitted function -> trace-cache entries it was built to hold
        # (what _cache_size() shows beyond that is a re-trace); held by
        # the jitted object: callers may wrap the attributes on the
        # instance
        self._jits = {"_round": (self._round, 1),
                      # one admission program a bucket, built when a
                      # request of the bucket first arrives
                      "_admit_rows": (self._admit_rows,
                                      len(self.buckets)),
                      # the widest bucket's, for long prompts
                      "_prefill": (self._prefill, 1),
                      # the 128-token chunk and the long prompts' own
                      "_extend": (self._extend, 2),
                      "_scatter": (self._scatter, 1)}

    # ---- block diffusion ----------------------------------------------
    def _block_round_fn(self, cfg_d: TransformerConfig, moe_stats: bool):
        """The round of a model that generates by diffusion over blocks:
        ``kk`` passes in one program. ``blk`` / ``masked`` (b, B) are
        the rows' current blocks at ``pos`` (b,), ``owed`` (b,) the
        tokens a row still owes (0: the slot is free or done, and its
        state stands still) and ``given`` (b,) how many leading
        positions of a first block are prompt tokens. A pass runs every
        row's block through the model; a row with a mask left takes
        the unmask rule, a row with none commits. Returns (cache, blk,
        masked, pos, given, tokens (kk, b, B): the block each pass ran,
        commits (kk, b) bool: the passes that delivered theirs,
        BLOCK_STATS' counts[, models.moe.STATS' counts])."""
        cfg = self.cfg
        B = cfg.block_len

        def round_fn(params, cache, blk, masked, pos, owed, given, kk):
            def body(carry, _):
                blk, masked, pos, owed, given, cache, counts, *stats = carry
                info = []
                logits, cache = block_decode(params, blk, pos, cache,
                                             cfg_d, moe_info=info)
                live = owed > 0
                commit = live & ~jnp.any(masked, axis=-1)
                # a commit's row has no mask left: the rule leaves it
                new_blk, new_masked, unmasked = denoise_update(
                    logits, blk, masked, cfg.confidence)
                with jax.named_scope("diff.commit"):
                    fresh = B - given       # positions that are not prompt
                    delivered = jnp.minimum(fresh, owed)

                    def at_commit(x):
                        return jnp.sum(jnp.where(commit, x, 0))

                    counts = counts + jnp.stack([
                        jnp.sum(live), jnp.sum(live & ~commit),
                        jnp.sum(commit),
                        jnp.sum(unmasked & live[:, None]),
                        jnp.sum(commit), at_commit(delivered),
                        at_commit(fresh - delivered),
                        at_commit(given)]).astype(jnp.int32)
                    ran = blk
                    denoise = (live & ~commit)[:, None]
                    blk = jnp.where(commit[:, None], jnp.int32(cfg.mask_id),
                                    jnp.where(denoise, new_blk, blk))
                    masked = commit[:, None] | jnp.where(
                        denoise, new_masked, masked)
                    pos = pos + jnp.where(commit, B, 0)
                    owed = owed - jnp.where(commit, delivered, 0)
                    given = jnp.where(commit, 0, given)
                stats = [n + sum(i["stats"] for i in info) for n in stats]
                return ((blk, masked, pos, owed, given, cache, counts,
                         *stats), (ran, commit))

            stats = ([jnp.zeros((len(moe.STATS),), jnp.int32)]
                     if moe_stats else [])
            counts = jnp.zeros((len(BLOCK_STATS),), jnp.int32)
            (blk, masked, pos, owed, given, cache, counts, *stats), (
                toks, commits) = lax.scan(
                    body, (blk, masked, pos, owed, given, cache, counts,
                           *stats), None, length=kk)
            return (cache, blk, masked, pos, given, toks, commits, counts,
                    *stats)

        return round_fn

    # ---- paged mode (docs/DESIGN.md §12) -----------------------------
    def _init_paged(self, cfg_d, page_size, n_pages, prefill_budget,
                    prefix_cache, clip_rounds):
        from rlo_tpu.models.paged import (copy_page, init_page_pool,
                                          paged_decode_step,
                                          paged_prefill_chunk)
        from rlo_tpu.serving.pages import PageAllocator, PrefixTrie
        if jax.default_backend() == "tpu" and page_size % 128:
            raise ValueError(
                f"TPU pages must be 128-lane multiples, got "
                f"{page_size}")
        self.page_size = page_size
        self.max_pages = -(-self.max_len // page_size)
        if n_pages is None:
            n_pages = self.n_slots * self.max_pages + 1
        self.n_pages = n_pages
        self.clip_rounds = clip_rounds
        self.prefill_budget = prefill_budget
        self.pools = init_page_pool(self.cfg, n_pages, page_size)
        self.allocator = PageAllocator(n_pages, page_size)
        self.trie = PrefixTrie(page_size) if prefix_cache else None
        self.table = np.zeros((self.n_slots, self.max_pages), np.int32)
        self.active = np.zeros((self.n_slots,), bool)
        #: pages owned (one reference each) per slot, table order
        self._slot_pages: List[List[int]] = \
            [[] for _ in range(self.n_slots)]
        #: slot -> in-flight chunked prefill state
        self._prefilling: Dict[int, dict] = {}

        def round_fn(params, pools, table, last_tok, pos, active, kk):
            def body(carry, _):
                tok, pos, pools = carry
                logits, pools = paged_decode_step(
                    params, tok, pos, pools, table, active, cfg_d)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                tok = jnp.where(active, nxt, tok)
                pos = pos + active.astype(pos.dtype)
                return (tok, pos, pools), tok

            (tok, pos, pools), toks = lax.scan(
                body, (last_tok, pos, pools), None, length=kk)
            return tok, pos, pools, jnp.transpose(toks)  # (b, kk)

        self._round_paged = jax.jit(round_fn, static_argnames=("kk",),
                                    donate_argnums=(1,))

        def chunk_fn(params, pools, table_row, toks, pos0, n_valid):
            return paged_prefill_chunk(params, toks, pos0, n_valid,
                                       pools, table_row, self.cfg)

        self._chunk = jax.jit(chunk_fn, donate_argnums=(1,))
        self._copy = jax.jit(copy_page, donate_argnums=(0,))
        self._jits = {"_round_paged": (self._round_paged, 1),
                      "_chunk": (self._chunk, 1),
                      "_copy": (self._copy, 1)}

    # ---- request lifecycle ------------------------------------------
    def submit(self, prompt, max_new: int,
               eos_id: Optional[int] = None,
               due: Optional[float] = None) -> int:
        """Queue a request; returns its id (position in results).
        Any prompt with plen + max_new <= max_len is admissible (long
        prompts stream through chunked prefill); only truly oversized
        requests are rejected. ``due`` is the ``time.perf_counter()``
        reading at which the request was due to arrive (an open-loop
        driver's schedule): TTFT, queue wait and end-to-end latency
        count from it when given, from this call when not."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            # an empty prompt has no last token to take logits from —
            # the paged prefill would wedge at next=-1 and the dense
            # prefill would index position -1; reject it cleanly
            raise ValueError("empty prompt")
        if len(prompt) + max_new > self.max_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new} exceeds "
                f"max_len {self.max_len}")
        B = self.cfg.block_len
        if B and -(-(len(prompt) + max_new) // B) * B > self.max_len:
            # the last block's surplus positions are attended by the
            # tokens that are kept: the cache has to hold them
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new}, rounded up "
                f"to whole blocks of {B}, exceeds max_len {self.max_len}")
        if self.paged:
            need = -(-(len(prompt) + max_new) // self.page_size)
            if need > self.n_pages - 1:
                raise ValueError(
                    f"request spans {need} pages but the pool holds "
                    f"only {self.n_pages - 1} allocatable pages")
        rid = len(self._out)
        self._queue.append((rid, Request(prompt, max_new, eos_id)))
        self._out.append(None)
        self._eos.append(eos_id)
        t_sub = time.perf_counter() if due is None else float(due)
        self._submit_ts[rid] = t_sub
        self._accept_ts[rid] = t_sub
        self.metrics.counter("serve.requests_submitted").inc()
        self.metrics.gauge("serve.queue_depth").set(len(self._queue))
        return rid

    def _span(self, stage: str, rid: Optional[int] = None,
              fabric_stage: Optional[int] = None, **ids) -> annotate:
        """The span around one stage of the host loop: the profiler
        annotation ``perf.serve.<stage>`` (with ``rid`` when the stage
        belongs to a request, and any further ``ids``) and the counters
        ``serve.<stage>_ns`` / ``_n``. A stage that carries a
        ``fabric_stage`` is also emitted as that ``Ev.SPAN`` when the
        fabric attached a recorder AND the fabric-level request is
        sampled."""
        emit = None
        recorder = self.spans
        if (fabric_stage is not None and recorder is not None
                and rid is not None and self.span_rid_of is not None):
            frid = self.span_rid_of(rid)
            if frid is not None and recorder.sampled(frid):
                emit = functools.partial(recorder.emit, frid,
                                         fabric_stage)
        if rid is not None:
            ids["rid"] = rid
        return annotate("perf.serve." + stage, self.metrics,
                        "serve." + stage, emit, **ids)

    def _hist(self, name: str):
        return self.metrics.histogram(name, _HIST_KEEP)

    def _admit(self) -> int:
        """Fill every free slot from the queue; returns the number of
        requests that COMPLETED during admission (max_new=1 or an
        immediate eos retires the slot at once — the freed slot is
        re-offered to the queue in the same admission, and the
        completion count keeps step_round truthful about progress).

        Dense scheduler: the queue's head goes to the lowest free slot,
        the next to the next, as many as there are free slots: one
        PASS. Its requests that fit a prompt bucket are admitted a
        bucket at a time: one launch (``_admit_rows``) and one read of
        all its first tokens a (pass, bucket) GROUP, every group
        launched before any is read, so a request's TTFT ends at its
        group's sync and a group of one runs the program a group of
        nine does. A prompt past the widest bucket is admitted alone,
        after the groups (``_admit_long``). Slots that came free in a
        pass are offered again in the next."""
        with self._span("admit"):
            if self.paged:
                return self._admit_paged()
            return self._admit_dense()

    def _admit_dense(self) -> int:
        completed = 0
        while True:
            free = [s for s in range(self.n_slots)
                    if self.req_of_slot[s] is None]
            groups: Dict[int, list] = {}    # bucket -> its requests
            long = []
            for slot in free[:len(self._queue)]:
                rid, req = self._queue.pop(0)
                t_sub = self._submit_ts.pop(rid, None)
                if t_sub is not None:
                    self._hist("serve.queue_wait_usec").observe(
                        (time.perf_counter() - t_sub) * 1e6)
                taken = (slot, rid, req, t_sub)
                if len(req.prompt) > self.buckets[-1]:
                    long.append(taken)
                else:
                    groups.setdefault(_bucket(len(req.prompt),
                                              self.buckets),
                                      []).append(taken)
            if not (groups or long):
                return completed
            self.metrics.gauge("serve.queue_depth").set(len(self._queue))
            launched = [(bucket, rows, self._launch_group(bucket, rows))
                        for bucket, rows in sorted(groups.items())]
            for bucket, rows, firsts in launched:
                if self.cfg.block_len:
                    # no first token to read: the rows are seated while
                    # the device prefills them, and the round follows
                    for slot, rid, req, _ in rows:
                        completed += self._seat_block(slot, rid, req,
                                                      bucket)
                    continue
                with self._span("admit.first_token_sync", rows=len(rows)):
                    # the host blocks here until the group's prefills
                    # and scatters finished on the device
                    firsts = np.asarray(firsts)
                for taken, first in zip(rows, firsts):
                    completed += self._seat(*taken, int(first), bucket)
            for taken in long:
                completed += self._admit_long(*taken)

    def _launch_group(self, bucket: int, rows: list):
        """Stage the in-bucket requests ``rows`` of one pass into one
        host array and launch their admission; returns the first
        tokens, on the device, a row each in ``rows``' order."""
        with self._span("admit.stage_input", rows=len(rows)):
            staged = np.zeros((self.n_slots, bucket + 2), np.int32)
            for i, (slot, _, req, _) in enumerate(rows):
                plen = len(req.prompt)
                staged[i, :plen] = req.prompt
                staged[i, bucket:] = plen, slot
            staged = jnp.asarray(staged)
        with self._span("admit.prefill_dispatch", rows=len(rows)):
            if bucket not in self._admit_built:
                # The layers are traced HERE, where no other trace is
                # open: jit keeps the one-row function's jaxpr, and the
                # loop's body, traced next, finds it. The same trace
                # made inside the body cost a 24-layer server 6.7 s
                # more of set-up on the chip's host (PERF.md §6, PR 33).
                self._admit_built.add(bucket)
                self._jits["_prefill"][0].trace(
                    self.params,
                    jax.ShapeDtypeStruct((1, bucket), jnp.int32),
                    jax.ShapeDtypeStruct((1,), jnp.int32))
            self.cache, firsts = self._admit_rows(self.params, self.cache,
                                                  staged)
        self.metrics.counter("serve.admit.launches").inc()
        self.metrics.counter("serve.admit.batched_rows").inc(len(rows))
        return firsts

    def _admit_long(self, slot: int, rid: int, req: Request,
                    t_sub: Optional[float]) -> int:
        """Admit one prompt longer than the widest bucket: the bucket
        prefill of its head, then jitted block_decode chunks
        (write-then-attend; the final chunk's last-position logits
        seed the first token), the scatter and a sync of its own."""
        plen = len(req.prompt)
        B = self.cfg.block_len
        if B:   # the cache takes the prompt's whole blocks (_seat_block)
            plen -= plen % B
        head = self.buckets[-1]
        with self._span("admit.stage_input", rid):
            prompt = jnp.asarray(req.prompt[None, :head])
            length = jnp.asarray([head], jnp.int32)
        with self._span("admit.prefill_dispatch", rid):
            row, first = self._prefill(self.params, prompt, length)
        off = head
        ran = head      # positions the prefill ran, padding included
        width = (self._long_w if plen - head > self._long_w
                 else self._chunk_w)
        while off < plen:
            n = min(width, plen - off)
            toks = np.zeros((1, width), np.int32)
            toks[0, :n] = req.prompt[off:off + n]
            with self._span("admit.extend", rid):
                first, row = self._extend(
                    self.params, row, jnp.asarray(toks),
                    jnp.int32(off), jnp.int32(n))
            off += n
            ran += width
        with self._span("admit.scatter_dispatch", rid):
            self.cache = self._scatter(self.cache, row, jnp.int32(slot))
        if B:
            return self._seat_block(slot, rid, req, ran)
        with self._span("admit.first_token_sync", rid):
            # the host blocks here until this request's prefill,
            # chunks and scatter finished on the device
            first = int(np.asarray(first).reshape(-1)[0])
        return self._seat(slot, rid, req, t_sub, first, ran)

    def _seat(self, slot: int, rid: int, req: Request,
              t_sub: Optional[float], first: int, ran: int) -> int:
        """``req`` holds ``slot`` from here: its first token is on the
        host (TTFT = submit, or due, -> here, queue wait included), its
        prefill ran ``ran`` positions, padding included. Returns 1 if
        the request completed at once (the slot is free again)."""
        if t_sub is not None:
            self._hist("serve.ttft_usec").observe(
                (time.perf_counter() - t_sub) * 1e6)
        count = self.metrics.counter
        count("serve.admissions").inc()
        count("serve.prefill_tokens").inc(len(req.prompt))
        count("serve.prefill_padded_tokens").inc(ran)
        count("serve.tokens_out").inc()
        self.req_of_slot[slot] = rid
        self._out[rid] = [first]
        self.pos[slot] = len(req.prompt)
        self.last_tok[slot] = first
        self.budget[slot] = req.max_new - 1
        if req.eos_id is not None and first == req.eos_id:
            self.budget[slot] = 0
        self._retire_if_done(slot)
        return int(self.req_of_slot[slot] is None)

    def _seat_block(self, slot: int, rid: int, req: Request,
                    ran: int) -> int:
        """_seat under block diffusion: the prompt's whole blocks are in
        the cache (or on their way: nothing was read), its leftover
        tokens open the slot's first block, unmasked, before the mask
        ids. No token exists yet: TTFT ends at the first committed
        block (_distribute_blocks)."""
        B = self.cfg.block_len
        plen = len(req.prompt)
        left = plen % B
        count = self.metrics.counter
        count("serve.admissions").inc()
        count("serve.prefill_tokens").inc(plen - left)
        count("serve.prefill_padded_tokens").inc(ran)
        self.req_of_slot[slot] = rid
        self._out[rid] = []
        self.pos[slot] = plen - left
        self.blk[slot] = self.cfg.mask_id
        self.blk[slot, :left] = req.prompt[plen - left:]
        self.masked[slot] = np.arange(B) >= left
        self.given[slot] = left
        self.budget[slot] = req.max_new
        self._retire_if_done(slot)
        return int(self.req_of_slot[slot] is None)

    # ---- paged admission / chunked prefill ---------------------------
    def _try_map(self, slot: int, req: Request) -> bool:
        """Reserve and map every page the request will ever touch
        (positions 0..plen+max_new-1) into the slot's table row:
        trie-shared leading pages are retained in place, the one
        shared page the request must write into is copied-on-write,
        the rest come fresh off the free list. All-at-admission
        reservation means a mapped request can never stall mid-decode
        on an empty pool — backpressure is an admission-time-only
        phenomenon. Returns False (nothing mapped) when the pool
        cannot cover it even after trie eviction."""
        ps = self.page_size
        plen = len(req.prompt)
        need_pages = -(-(plen + req.max_new) // ps)
        shared: List[int] = []
        covered = 0
        if self.trie is not None:
            shared, covered = self.trie.match(req.prompt)
        # always recompute at least the last prompt token (the first
        # generated token needs its logits; the cache alone has none)
        prefill_from = min(covered, plen - 1)
        n_keep = min(len(shared), prefill_from // ps)
        n_cow = len(shared) - n_keep      # 0 or 1 by construction
        n_new = need_pages - n_keep       # COW copies + fresh pages
        # pin every matched page across the eviction call: un-retained
        # refcount-1 trie pages are exactly what evict() frees
        for p in shared:
            self.allocator.retain(p)
        if not self.allocator.can_alloc(n_new):
            if self.trie is not None:
                ev = self.trie.evict(
                    self.allocator,
                    n_new - self.allocator.free_pages)
                if ev:
                    self.metrics.counter(
                        "serve.trie_evictions").inc(ev)
            if not self.allocator.can_alloc(n_new):
                for p in shared:
                    self.allocator.release(p)
                return False
        pages: List[int] = list(shared[:n_keep])
        for src in shared[n_keep:]:
            dst = self.allocator.alloc()
            self.pools = self._copy(self.pools, jnp.int32(src),
                                    jnp.int32(dst))
            self.allocator.release(src)   # drop the COW pin
            pages.append(dst)
            self.metrics.counter("serve.cow_copies").inc()
        for _ in range(need_pages - len(pages)):
            pages.append(self.allocator.alloc())
        self.table[slot, :] = 0
        self.table[slot, :need_pages] = pages
        self._slot_pages[slot] = pages
        if covered > 0:
            self.metrics.counter("serve.prefix_hits").inc()
            self.metrics.counter("serve.prefix_tokens_shared").inc(
                prefill_from)
        self._prefilling[slot] = {
            "req": req, "next": prefill_from, "plen": plen}
        return True

    def _release_slot_pages(self, slot: int) -> None:
        for p in self._slot_pages[slot]:
            self.allocator.release(p)
        self._slot_pages[slot] = []
        self.table[slot, :] = 0
        self.active[slot] = False
        self._prefilling.pop(slot, None)

    def _admit_paged(self) -> int:
        """Paged admission + the chunked-prefill tick. Head-of-line
        FIFO: when the queue head cannot reserve its pages the whole
        admission stalls (deterministic backpressure — decode rounds
        keep draining, retirements free pages, the head admits next
        round)."""
        for slot in range(self.n_slots):
            if self.req_of_slot[slot] is not None or not self._queue:
                continue
            rid, req = self._queue[0]
            with self._span("admit.map_pages", rid):
                mapped = self._try_map(slot, req)
            if not mapped:
                self.metrics.counter("serve.admission_stalls").inc()
                break
            self._queue.pop(0)
            self.metrics.counter("serve.admissions").inc()
            # what the prefill has to compute: the prompt less the
            # prefix the trie shared
            st = self._prefilling[slot]
            self.metrics.counter("serve.prefill_tokens").inc(
                st["plen"] - st["next"])
            t_sub = self._submit_ts.pop(rid, None)
            if t_sub is not None:
                self._hist("serve.queue_wait_usec").observe(
                    (time.perf_counter() - t_sub) * 1e6)
            self.metrics.gauge("serve.queue_depth").set(
                len(self._queue))
            self.req_of_slot[slot] = rid
            self._out[rid] = []
        completed, _ = self._prefill_tick()
        self._page_gauges()
        return completed

    def _prefill_tick(self) -> Tuple[int, bool]:
        """Advance every prefilling slot by up to ``prefill_budget``
        prompt tokens (None = finish it now) in page-aligned chunks.
        Returns (requests completed at prefill time, any progress)."""
        completed = 0
        progressed = False
        ps = self.page_size
        for slot in list(self._prefilling):
            st = self._prefilling[slot]
            req, plen = st["req"], st["plen"]
            budget = (plen if self.prefill_budget is None
                      else self.prefill_budget)
            rid = self.req_of_slot[slot]
            logits = None
            while st["next"] < plen and budget > 0:
                a = st["next"]
                end = min(plen, (a // ps + 1) * ps, a + budget)
                n = end - a
                toks = np.zeros((1, ps), np.int32)
                toks[0, :n] = req.prompt[a:end]
                with self._span("admit.prefill_chunk", rid,
                                Stage.PREFILL_CHUNK):
                    logits, self.pools = self._chunk(
                        self.params, self.pools,
                        jnp.asarray(self.table[slot:slot + 1]),
                        jnp.asarray(toks), jnp.int32(a), jnp.int32(n))
                st["next"] = end
                budget -= n
                progressed = True
                self.metrics.counter("serve.prefill_chunks").inc()
                self.metrics.counter(
                    "serve.prefill_padded_tokens").inc(ps)
            if st["next"] < plen:
                continue  # budget spent; more chunks next round
            # prefill complete: seed the first token, open decoding
            with self._span("admit.first_token_sync", rid):
                first = int(np.asarray(
                    jnp.argmax(logits, axis=-1)).reshape(-1)[0])
            t_sub = self._accept_ts.get(rid)
            if t_sub is not None:
                self._hist("serve.ttft_usec").observe(
                    (time.perf_counter() - t_sub) * 1e6)
            self.metrics.counter("serve.tokens_out").inc()
            self._out[rid] = [first]
            self.pos[slot] = plen
            self.last_tok[slot] = first
            self.budget[slot] = req.max_new - 1
            if req.eos_id is not None and first == req.eos_id:
                self.budget[slot] = 0
            self.active[slot] = True
            del self._prefilling[slot]
            if self.trie is not None:
                self.trie.register(req.prompt, plen,
                                   self.table[slot], self.allocator)
            self._retire_if_done(slot)
            if self.req_of_slot[slot] is None:
                completed += 1
        return completed, progressed

    def _page_gauges(self) -> None:
        with self._span("page_gauges"):
            self.metrics.gauge("serve.pages_in_use").set(
                self.allocator.pages_in_use)
            self.metrics.gauge("serve.pages_free").set(
                self.allocator.free_pages)

    def _retire_if_done(self, slot: int):
        rid = self.req_of_slot[slot]
        if rid is None:
            return
        if self.budget[slot] <= 0:
            self.req_of_slot[slot] = None
            if self.paged:
                self._release_slot_pages(slot)
            self.metrics.counter("serve.requests_completed").inc()
            self._completed_log.append(
                (rid, np.asarray(self._out[rid], np.int32)))
            t_sub = self._accept_ts.pop(rid, None)
            if t_sub is not None:
                # end-to-end latency: submit -> last token, queue wait
                # and every decode round included (the fail-over-aware
                # fleet twin is fabric.e2e_usec, docs/DESIGN.md §11)
                self._hist("serve.e2e_usec").observe(
                    (time.perf_counter() - t_sub) * 1e6)

    # ---- fabric-facing hooks (docs/DESIGN.md §11) --------------------
    def poll_completed(self) -> List[Tuple[int, np.ndarray]]:
        """Drain the (rid, tokens) pairs completed since the last
        poll — the incremental completion face the serving fabric
        consumes round by round (``run()`` remains the drive-to-empty
        batch face)."""
        out, self._completed_log = self._completed_log, []
        return out

    def cancel(self, rid: int) -> bool:
        """Withdraw a request: de-queue it, or free its slot mid-
        decode (the fabric's re-queue/ownership-move hook). Returns
        False when the rid is unknown or already completed. A canceled
        request's ``run()`` output is its partial prefix — the caller
        owns whatever exactly-once story spans the re-queue (the
        fabric dedups by its own request id)."""
        if not 0 <= rid < len(self._out) or rid in self._canceled:
            return False
        for i, (qrid, _) in enumerate(self._queue):
            if qrid == rid:
                del self._queue[i]
                self._canceled.add(rid)
                self._submit_ts.pop(rid, None)
                self._accept_ts.pop(rid, None)
                self.metrics.counter("serve.requests_canceled").inc()
                self.metrics.gauge("serve.queue_depth").set(
                    len(self._queue))
                return True
        for slot in range(self.n_slots):
            if self.req_of_slot[slot] == rid:
                self.req_of_slot[slot] = None
                self.budget[slot] = 0
                if self.paged:
                    self._release_slot_pages(slot)
                self._canceled.add(rid)
                self._accept_ts.pop(rid, None)
                self.metrics.counter("serve.requests_canceled").inc()
                return True
        return False

    def has_work(self) -> bool:
        """Queued or in-flight requests remain."""
        return bool(self._queue) or any(
            r is not None for r in self.req_of_slot)

    def free_slots(self) -> int:
        return sum(1 for r in self.req_of_slot if r is None)

    def queue_depth(self) -> int:
        return len(self._queue)

    def slot_ownership(self) -> Tuple[Optional[int], ...]:
        """Which rid occupies each slot (None = free) — the
        slot-ownership view the fabric's placement records reason
        about."""
        return tuple(self.req_of_slot)

    # ---- the decode loop --------------------------------------------
    def step_round(self):
        """Admit pending requests, run one jitted round of ragged
        decode steps (``round_len`` of them; paged mode clips the
        round to the shortest active budget), distribute tokens."""
        with self._span("step_round"):
            if self.paged:
                return self._step_round_paged()
            return self._step_round_dense()

    def _step_round_dense(self):
        completed = self._admit()
        if all(r is None for r in self.req_of_slot):
            return completed > 0
        if self.cfg.block_len:
            return self._step_round_blocks()
        kk = self.round_len
        if self.clip_rounds:
            kk = max(1, min(kk, int(min(
                self.budget[s] for s in range(self.n_slots)
                if self.req_of_slot[s] is not None))))
        t0 = time.perf_counter()
        with self._span("round.dispatch"):
            tok, pos, cache, toks, *stats = self._round(
                self.params, self.cache, jnp.asarray(self.last_tok),
                jnp.asarray(self.pos), kk)
            self.cache = cache
        self._count_kv_tail(kk)       # while the device runs the round
        self._count_attend_tiles(kk)
        self._count_dsa(kk)
        self._count_window(kk)
        with self._span("round.wait"):
            # the host blocks here for the device's whole round
            toks = np.asarray(toks)
        with self._span("round.readback"):
            self.last_tok = np.asarray(tok).copy()
            self.pos = np.asarray(pos).copy()
            for counts in stats:    # expert layers' counts, if any
                self._count_named("serve.moe.", moe.STATS, counts)
        dt = time.perf_counter() - t0  # toks materialized: round done
        self._observe_round(dt, kk)
        self._distribute(toks, kk)
        return True

    def _step_round_blocks(self):
        """A round of block diffusion: ``round_len`` passes over every
        slot's current block in one launch (_block_round_fn), then the
        committed blocks to their requests."""
        kk = self.round_len
        occupied = np.array([r is not None for r in self.req_of_slot])
        owed = np.where(occupied, np.maximum(self.budget, 0), 0)
        given = self.given
        t0 = time.perf_counter()
        with self._span("round.dispatch"):
            (self.cache, blk, masked, pos, given_now, toks, commits,
             counts, *stats) = self._round(
                self.params, self.cache, jnp.asarray(self.blk),
                jnp.asarray(self.masked), jnp.asarray(self.pos),
                jnp.asarray(owed, jnp.int32), jnp.asarray(given), kk)
        with self._span("round.wait"):
            # the host blocks here for the device's whole round
            toks = np.asarray(toks)
        with self._span("round.readback"):
            commits = np.asarray(commits)
            self.blk = np.asarray(blk).copy()
            self.masked = np.asarray(masked).copy()
            self.pos = np.asarray(pos).copy()
            self.given = np.asarray(given_now).copy()
            self._count_named("serve.diffusion.", BLOCK_STATS, counts)
            for moe_counts in stats:
                self._count_named("serve.moe.", moe.STATS, moe_counts)
        dt = time.perf_counter() - t0
        self._observe_round(dt, kk)
        self._distribute_blocks(toks, commits, given)
        return True

    def _step_round_paged(self):
        """The paged round: admission + chunked-prefill tick, then a
        budget-clipped decode round over the active slots, then token
        distribution and page release."""
        completed = self._admit()
        if not self.active.any():
            return completed > 0 or bool(self._prefilling)
        kk = self.round_len
        if self.clip_rounds:
            kk = max(1, min(kk, int(min(
                self.budget[s] for s in range(self.n_slots)
                if self.active[s]))))
        t0 = time.perf_counter()
        with self._span("round.dispatch"):
            tok, pos, pools, toks = self._round_paged(
                self.params, self.pools, jnp.asarray(self.table),
                jnp.asarray(self.last_tok), jnp.asarray(self.pos),
                jnp.asarray(self.active), kk)
            self.pools = pools
        with self._span("round.wait"):
            toks = np.asarray(toks)
        with self._span("round.readback"):
            self.last_tok = np.asarray(tok).copy()
            self.pos = np.asarray(pos).copy()
        dt = time.perf_counter() - t0
        self._observe_round(dt, kk)
        self._distribute(toks, kk, only_active=True)
        self._page_gauges()
        return True

    def _count_named(self, prefix: str, names, counts) -> None:
        """Counts a round summed on the device, into ``prefix<name>``."""
        for name, n in zip(names, np.asarray(counts)):
            self.metrics.counter(prefix + name).inc(int(n))

    def _count_kv_tail(self, kk: int) -> None:
        """A dense round that ran with the write-behind tail:
        ``serve.kv_tail.rounds``, ``serve.kv_tail.rows`` (kk x n_slots
        rows attended from the tail and folded in) and
        ``serve.kv_flush_blocks`` (128-lane cache blocks the fold
        rewrote: two a slot, layer and tensor, write_kv_block's grid)."""
        if not self._kv_tail:
            return
        count = self.metrics.counter
        count("serve.kv_tail.rounds").inc()
        count("serve.kv_tail.rows").inc(kk * self.n_slots)
        count("serve.kv_flush_blocks").inc(
            2 * self.n_slots * len(jax.tree.leaves(self.cache)))

    def _count_attend_tiles(self, kk: int) -> None:
        """What flash_decode's grid walks, from the host's own ``pos``
        by the kernel's rule (its tile width, _last_live_tile's
        clip): ``serve.attend_tiles`` is every (row, step, cache tile)
        of a grid over max_len, the denominator of the live share;
        ``serve.attend_tiles_live`` those a row's context reaches;
        ``serve.attend_steps`` the grid steps the round's attends run.
        The grid is the work list of live (row, tile) pairs
        (decode_work_list), so steps == tiles_live says it engages; a
        grid over max_len would read == tiles. The kernel copies its
        own tiles, a row's last only as far as it is live:
        ``serve.attend_lanes_fetched`` is the cache positions those
        copies move (decode_lanes_fetched, the kernel's rule: rounded
        up to the copy's granule at a row's last tile; whole tiles
        would read tiles_live x bk) and ``serve.attend_lanes_live``
        the positions the rows' contexts hold. With the tail every
        step of the round attends the cache as the round found it,
        positions < pos: tiles 0 .. (pos - 1) // bk, the same in every
        step (tile 0 alone for an empty row). Without it step s
        attends positions <= pos + s. Every slot counts, free and
        finished ones too: the kernel runs them."""
        if self._attend_tiling is None:
            return
        from rlo_tpu.pallas.decode import decode_lanes_fetched
        bk, n_k = self._attend_tiling
        # the last position each attend of the round reaches: (rows, 1)
        # with the tail, kk times over; (rows, kk) without
        held = (self.pos[:, None] - 1 if self._kv_tail
                else self.pos[:, None] + np.arange(kk))
        times = kk if self._kv_tail else 1
        live = times * int((np.clip(held // bk, 0, n_k - 1) + 1).sum())
        count = self.metrics.counter
        count("serve.attend_tiles").inc(kk * self.n_slots * n_k)
        count("serve.attend_tiles_live").inc(live)
        count("serve.attend_steps").inc(live)
        count("serve.attend_lanes_fetched").inc(times * int(
            decode_lanes_fetched(held, 1, bk, self.max_len,
                                 self._attend_slots).sum()))
        count("serve.attend_lanes_live").inc(times * int(
            np.clip(held + 1, 0, self.max_len).sum()))

    def _count_dsa(self, kk: int) -> None:
        """A token selector's work in the round just launched
        (kvcache.SELECT_STATS), from the host's own ``pos``: step s of
        row r has ctx = pos_r + s + 1 positions to choose from (the row
        writes, then selects, then attends), cut at max_len. The rule
        is kvcache.select_counts', kept beside select_tokens; how the
        attend read a selection is what the traced round reported.
        Summed over the layers that do each, as the traced round's
        step recorded them (``_dsa_layers``): ``keys_scored`` over the
        layers whose selection scored, the attends' counts over all;
        ``dense_row_steps`` are row-steps. ``selections_scored`` and
        ``selections_reused`` count layer-steps that chose a set: by
        scoring it, or (a shared layer of cfg.index_types) by taking
        the set the full layer before it chose. Every slot counts, free
        and finished ones too: the program runs them."""
        if not self._dsa_topk:
            return
        ctx = np.minimum(self.pos[:, None].astype(np.int64) + 1
                         + np.arange(kk), self.max_len)    # (rows, kk)
        counts = kvcache.select_counts(ctx, self._dsa_topk,
                                       self._dsa_reads)
        scored, reused = self._dsa_layers
        for name, n in counts.items():
            self.metrics.counter("serve.dsa." + name).inc(
                n if name == "dense_row_steps" else
                n * scored if name == "keys_scored" else
                n * self.cfg.n_layers)
        chose = int((ctx > self._dsa_topk).any(axis=0).sum())  # steps
        self.metrics.counter("serve.dsa.selections_scored").inc(
            chose * scored)
        self.metrics.counter("serve.dsa.selections_reused").inc(
            chose * reused)

    def _count_window(self, kk: int) -> None:
        """The sliding layers' attends in the round just launched, from
        the host's own ``pos`` (no device sync): step s of row r attends
        from ctx = pos_r + s + 1 positions of context (cut at max_len)
        the last min(ctx, window). ``serve.window.keys_in_context`` sums
        ctx, ``serve.window.keys_in_window`` the min, each over the rows,
        steps and sliding layers; ``serve.window.lanes_fetched`` (on the
        kernel's path) the ring positions flash_decode_window's copies
        move for them — whole tiles of the ring, by its work list's own
        rule (pallas.decode.window_tiles): with the tail, the ring's
        keys of the window, positions < pos; without it, through
        pos + s. Every slot counts, free ones too: the program runs
        them."""
        if not self.cfg.windowed:
            return
        W = self.cfg.sliding_window
        n_win = sum(1 for i in range(self.cfg.n_layers)
                    if self.cfg.layer_window(i))
        at = self.pos[:, None].astype(np.int64) + np.arange(kk)  # (rows, kk)
        ctx = np.minimum(at + 1, self.max_len)
        count = self.metrics.counter
        count("serve.window.keys_in_context").inc(n_win * int(ctx.sum()))
        count("serve.window.keys_in_window").inc(
            n_win * int(np.minimum(ctx, W).sum()))
        if self._window_tiling is None:
            return
        from rlo_tpu.pallas.decode import window_tiles
        bk, n_k = self._window_tiling
        hi = (self.pos[:, None].astype(np.int64) - 1 if self._kv_tail
              else at)
        tiles, _ = window_tiles(np.maximum(at - W + 1, 0),
                                np.broadcast_to(hi, at.shape), bk, n_k,
                                xp=np)
        count("serve.window.lanes_fetched").inc(
            n_win * bk * int(tiles.sum()))

    def _observe_round(self, dt: float, kk: int) -> None:
        self._hist("serve.round_usec").observe(dt * 1e6)
        self.metrics.counter("serve.rounds").inc()
        self.metrics.counter("serve.steps").inc(kk)
        self.metrics.counter("serve.slot_steps").inc(kk * self.n_slots)
        self.rounds_run += 1
        self.steps_run += kk
        # once a round: did a jitted function of the server trace a
        # shape it was not built for?
        for name, (fn, built_for) in self._jits.items():
            extra = fn._cache_size() - built_for
            new = extra - self._retraced.get(name, 0)
            if new > 0:
                self._retraced[name] = extra
                self.metrics.counter("serve.retraces").inc(new)
                self.metrics.counter("serve.retraces." + name).inc(new)

    def _distribute(self, toks, kk: int,
                    only_active: bool = False) -> None:
        with self._span("distribute"):
            kept = 0
            for slot in range(self.n_slots):
                rid = self.req_of_slot[slot]
                if rid is None:
                    continue
                if only_active and not self.active[slot]:
                    continue  # mid-prefill: nothing decoded this round
                take = int(min(self.budget[slot], kk))
                seq = toks[slot, :take].tolist()
                eos = self._eos[rid]
                if eos is not None and eos in seq:
                    seq = seq[:seq.index(eos) + 1]
                    self.budget[slot] = 0
                else:
                    self.budget[slot] -= take
                self._out[rid].extend(seq)
                kept += len(seq)
                self._retire_if_done(slot)
            # the slot-steps of this round that gave a token a request
            # asked for; the rest decoded past a row's end
            self.metrics.counter("serve.tokens_out").inc(kept)
            self.metrics.counter("serve.slot_steps_useful").inc(kept)

    def _distribute_blocks(self, toks, commits, given) -> None:
        """The blocks a round committed to their requests: ``toks``
        (kk, n_slots, B) is the block each pass ran, ``commits``
        (kk, n_slots) the passes that delivered theirs, ``given`` the
        leading prompt positions of a slot's first block as the round
        found it. A request gets exactly what it owes: the positions
        past that are the last block's surplus. TTFT ends here, at a
        request's first committed block."""
        with self._span("distribute"):
            kept = 0
            now = time.perf_counter()
            for slot in range(self.n_slots):
                rid = self.req_of_slot[slot]
                passes = np.flatnonzero(commits[:, slot])
                if rid is None or not passes.size:
                    continue
                seq = toks[passes, slot].reshape(-1)[given[slot]:]
                seq = seq[:int(self.budget[slot])].tolist()
                eos = self._eos[rid]
                if eos is not None and eos in seq:
                    seq = seq[:seq.index(eos) + 1]
                    self.budget[slot] = 0
                else:
                    self.budget[slot] -= len(seq)
                t_sub = self._accept_ts.get(rid)
                if seq and not self._out[rid] and t_sub is not None:
                    self._hist("serve.ttft_usec").observe(
                        (now - t_sub) * 1e6)
                self._out[rid].extend(seq)
                kept += len(seq)
                self._retire_if_done(slot)
            self.metrics.counter("serve.tokens_out").inc(kept)

    def run(self) -> List[np.ndarray]:
        """Drive rounds until every submitted request completes."""
        while self._queue or any(r is not None
                                 for r in self.req_of_slot):
            progressed = self.step_round()
            if not progressed and self._queue:  # pragma: no cover
                raise RuntimeError("queue stuck with no free slots")
        # a request canceled before admission never produced tokens
        return [np.asarray(o if o is not None else [], np.int32)
                for o in self._out]

    def stats(self) -> dict:
        """Serving-telemetry snapshot: counters and gauges verbatim
        (the span totals ``serve.<stage>_ns`` / ``_n`` among them),
        histograms as percentile SUMMARIES (count/mean/min/max +
        p50/p90/p99: exact over the newest 4096 samples for the four
        the server keeps them on, log2 estimates for any other,
        metrics.hist_summary) — dashboards read quantiles, not raw
        28-bucket dumps. The bucket layout stays available through
        ``self.metrics.snapshot()`` for anyone who wants it. ``build``
        is a row a function JAX traced, lowered or compiled under a
        span of this registry, the costliest first
        (utils.tracing.build_table). Paged servers add the allocator's
        own counters under ``pages``."""
        snap = self.metrics.snapshot()
        snap["histograms"] = self.metrics.summaries()
        # a copy: another thread's build may append meanwhile
        snap["build"] = build_table(r for r in tuple(BUILDS.records)
                                    if r.metrics is self.metrics)
        if self.paged:
            snap["pages"] = self.allocator.stats()
            if self.trie is not None:
                snap["pages"]["trie_entries"] = self.trie.entries
        return snap
