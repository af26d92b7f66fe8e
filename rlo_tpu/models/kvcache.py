"""The format of a KV-cache entry: layout, write, tail, fold, attend.

One layer's cache entry is a dict of SEQ-MINOR arrays, one of three
kinds the code tells apart by looking at it:

  - ``{"k", "v"}``: per-head keys and values, (batch, kv_heads,
    head_dim, max_len) in the activation dtype (kv_heads < n_heads for
    GQA);
  - ``{"k", "v", "ks", "vs"}``: the same in int8 with per-(batch, head,
    position) f32 scale sidecars (batch, kv_heads, max_len)
    (``cfg.kv_cache_dtype='int8'``);
  - ``{"k"}``: latent attention's one row [c_kv | rotated key dims] a
    token, (batch, 1, width, max_len); the values are the row's leading
    kv_lora_rank features;
  - ``{"k", "ik"}``: the same beside a token selector's index keys
    (batch, 1, index_head_dim, max_len) — two kinds of state of
    different width in one entry (``cfg.dsa``). ``select_tokens``
    scores them and picks the positions a query attends.

A sliding layer (``cfg.layer_window(i)`` > 0) keeps the same tensors as
a RING of ``ring_len`` positions in place of max_len: position p lives
in column p mod R, R its window plus the widest block one call writes
(RING_SPARE), so every key a query may attend is still there when it
attends. Nothing in the entry says it is a ring: the step functions know
each layer's window and hand it to the functions below (``window=``),
which write modulo R (a block or a fold that passes the ring's end
writes two spans) and mask a column by the POSITION it holds —
derived from the newest position the ring holds, never by the column
(_ring_positions).

Everything that depends on that — how a step's new keys and values
become columns of an entry (``new_row``, ``new_block``), how they are
written (``write_row``, ``write_block``, ``store_prompt``; the round's
write-behind tail: ``init_kv_tail``, ``store_tail_row``,
``fold_kv_tail``), how an entry is attended (``attend``,
``attend_block``, and ``attend_work``: what all layers of a step
share) and what a server asks of a cache (``scatter_slot``,
``bytes_per_position``, ``keeps_tail``, ``attend_tiling``) — is one
function here that LOOPS OVER THE ENTRY'S TENSORS; a scale sidecar
rides the write kernels through its free (batch, kv_heads, 1, max_len)
view. The step functions above (models.generate, models.paged) hold
positions and hooks and never name a tensor; the kernels below
(pallas.decode) never see an entry. models.paged owns the format of
the page POOL and takes its columns from here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rlo_tpu.models.transformer import TransformerConfig
from rlo_tpu.ops.ring_attention import _NEG
from rlo_tpu.pallas.reduce import (KernelFallbackWarning, _on_tpu,
                                   kernel_gate)


#: positions a sliding layer's ring keeps beyond its window: the widest
#: block one call writes through it — a long prompt's extend chunk
#: (models.serve.extend_widths: 256 at the default buckets; a round's
#: tail, round_len rows, is narrower). A query of such a block still
#: finds every key of its window in the ring
RING_SPARE = 256


def ring_len(cfg: TransformerConfig, max_len: int,
             spare: int = RING_SPARE) -> int:
    """Positions a sliding layer's ring holds: its window plus
    ``spare``, no more than the cache's max_len — rounded up to the
    128-lane tile on the tpu backend, as max_len is."""
    n = cfg.sliding_window + spare
    if jax.default_backend() == "tpu":
        n, max_len = (-(-x // 128) * 128 for x in (n, max_len))
    return min(n, max_len)


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  tp_axis: Optional[str] = None,
                  ring_spare: int = RING_SPARE):
    """Zeroed per-layer K/V cache: a list of {"k","v"} arrays shaped
    (batch, kv_heads, head_dim, max_len) in the activation dtype —
    SEQUENCE-MINOR. The minor dimension is what HBM tiles pad to the
    128-lane width: the previous (…, max_len, head_dim) layout put
    head_dim=64 in the lanes and measured HALF the deliverable cache
    bandwidth (365 vs 703 GB/s at identical bytes,
    benchmarks/attend_sweep.py, 2026-07-31) because every (16, 128)
    bf16 tile was half padding. max_len is >= 128 in any real serving
    config, so the seq-minor layout streams at full width; the
    flash-decode kernel's dots contract head_dim as the sublane axis,
    which is the MXU-native (d, L) matmul orientation anyway. GQA
    configs (n_kv_heads < n_heads) store only the K/V heads, the
    n_heads/kv_heads memory win that motivates GQA. Inside shard_map
    with ``tp_axis``, each shard allocates only its kv_heads/tp local
    heads (matching apply_layer's column-parallel K/V projections).

    ``cfg.kv_cache_dtype='int8'``: entries are int8 with per-(batch,
    head, position) f32 scale sidecars ``ks``/``vs`` — half the bf16
    cache's bytes in HBM; the dequant folds into the attend's score /
    probability tensors so the cache reads stay int8 on the wire.

    A sliding layer's entry is a ring of ring_len(cfg, max_len,
    ``ring_spare``) positions (``ring_spare``: the widest block one call
    will write, RING_SPARE by default); a full layer's keeps max_len."""
    ntp = lax.axis_size(tp_axis) if tp_axis is not None else 1
    assert cfg.kv_heads % ntp == 0
    kvh = cfg.kv_heads // ntp
    if cfg.mla and (tp_axis is not None or cfg.kv_cache_dtype):
        raise ValueError("the latent cache is unsharded and in the "
                         "activation dtype so far")
    if cfg.windowed and (cfg.mla or cfg.kv_cache_dtype):
        raise ValueError("a sliding layer's ring holds per-head K/V in "
                         "the activation dtype so far")
    if jax.default_backend() == "tpu":
        # round the seq axis up to the 128-lane tile: a non-multiple
        # max_len makes EVERY pallas call pad the whole cache (16
        # materialized pad ops per step at plen 1024 — measured); the
        # tail is position-masked everywhere, so +<=127 slots is
        # semantics-free and removes the pads
        max_len = -(-max_len // 128) * 128
    if cfg.mla:
        # latent attention: ONE row [c_kv | rotated key dims] a token
        # and layer, whatever the number of heads, in the same
        # sequence-minor layout; no "v" (the values are the row's
        # leading kv_lora_rank features)
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        # a token selector's index key beside the row, on the layers that
        # choose for themselves (a shared layer attends another's choice)
        widths = [{"k": width, "ik": cfg.index_head_dim} if cfg.selects(i)
                  else {"k": width} for i in range(cfg.n_layers)]
        return [{name: jnp.zeros((batch, 1, w, max_len), cfg.act_dtype)
                 for name, w in layer.items()} for layer in widths]
    shape = (batch, kvh, cfg.head_dim, max_len)
    # DISTINCT buffers per entry: sharing one zeros array across k/v/
    # layers breaks donation ("attempt to donate the same buffer
    # twice") for any jit that takes the cache donated (serve.py's
    # round, capacity probes)
    if cfg.kv_cache_dtype == "int8":
        return [{"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "ks": jnp.zeros((batch, kvh, max_len), jnp.float32),
                 "vs": jnp.zeros((batch, kvh, max_len), jnp.float32)}
                for _ in range(cfg.n_layers)]
    if cfg.kv_cache_dtype is not None:
        raise ValueError(
            f"unknown kv_cache_dtype {cfg.kv_cache_dtype!r}")
    if cfg.windowed:
        ring = ring_len(cfg, max_len, ring_spare)
        shapes = [shape[:3] + (ring if cfg.layer_window(i) else max_len,)
                  for i in range(cfg.n_layers)]
        return [{"k": jnp.zeros(sh, cfg.act_dtype),
                 "v": jnp.zeros(sh, cfg.act_dtype)} for sh in shapes]
    return [{"k": jnp.zeros(shape, cfg.act_dtype),
             "v": jnp.zeros(shape, cfg.act_dtype)}
            for _ in range(cfg.n_layers)]


def kv_cache_pspecs(cfg: TransformerConfig,
                    tp_axis: Optional[str] = None):
    """PartitionSpec tree matching init_kv_cache output: the K/V head
    axis shards over ``tp_axis`` (like the wkv projections in
    param_pspecs); batch/positions replicated. Pass as the cache
    in/out spec for shard_jit'd decode."""
    from jax.sharding import PartitionSpec as P
    if cfg.mla:
        raise ValueError("the latent cache is unsharded so far")
    spec = P(None, tp_axis, None, None)
    if cfg.kv_cache_dtype == "int8":
        sspec = P(None, tp_axis, None)
        return [{"k": spec, "v": spec, "ks": sspec, "vs": sspec}
                for _ in range(cfg.n_layers)]
    return [{"k": spec, "v": spec} for _ in range(cfg.n_layers)]


def _tensors(entry):
    """(name, tensor) over an entry: values, then their scales — the
    order every write keeps, however the dict was put together (a jit
    boundary rebuilds it with sorted keys)."""
    return [(name, entry[name]) for name in ("k", "v", "ks", "vs", "ik")
            if name in entry]


def keeps_tail(cache) -> bool:
    """Whether a loop that owns its steps may keep this cache's new
    rows in a write-behind tail (init_kv_tail): an int8 cache, whose
    rows come with scale sidecars, keeps the write of every step, and
    so does one with index keys, whose newest rows a selector scores
    with the others (a step's write of such an entry is 704 features
    x one 128-lane block a row, PERF.md PR 31)."""
    return not any("ks" in lc or "ik" in lc for lc in cache)


def init_kv_tail(cache, n: int):
    """A decode round's write-behind tail: per layer, ``n`` zeroed rows
    TOKEN-MAJOR, (n, batch, kv_heads, head_dim) for each tensor of the
    cache entry ((n, batch, 1, width) for a latent cache), in the
    cache's dtype. A loop that runs n decode steps with nobody else
    reading the cache hands it to decode_step (``tail=``): step s
    stores its row at [s] — a contiguous store on the leading axis,
    where the seq-minor cache would rewrite a 128-lane block a row to
    change one lane of it — and attends the cache up to where the
    loop began plus tail rows 0..s; fold_kv_tail writes all n rows
    into the cache once. int8 caches (scale sidecars) keep the
    per-step write."""
    if not keeps_tail(cache):
        raise ValueError("an int8 cache, or one with index keys, has "
                         "no write-behind tail")
    return [{name: jnp.zeros((n,) + a.shape[:3], a.dtype)
             for name, a in lc.items()} for lc in cache]


def fold_kv_tail(cache, tail, pos0, windows=None):
    """The cache with every tail row in place: layer by layer, row t
    of ``tail`` (init_kv_tail's layout) lands at column pos0_b + t of
    batch row b; columns at or past max_len are dropped, as the
    per-step write drops them. Equal, entry for entry, to the cache n
    decode_step calls without a tail leave. ``windows``: each layer's
    window (cfg.layer_window), where some are sliding: a ring takes
    row t at column (pos0_b + t) mod R, in two spans where it wraps."""
    from rlo_tpu.pallas.decode import can_write_block, write_kv_tail
    pos0 = jnp.asarray(pos0, jnp.int32)
    out = []
    for i, (lc, tl) in enumerate(zip(cache, tail)):
        entry = {}
        ring = bool(windows and windows[i])
        for name, big in lc.items():
            rows = tl[name]
            n, b, L = rows.shape[0], big.shape[0], big.shape[3]
            if kernel_gate(can_write_block(L) and n <= 128,
                           f"cache tail fold (max_len={L}, rows={n})"):
                entry[name] = (write_kv_tail(big, rows, pos0 % L, ring=True)
                               if ring else write_kv_tail(big, rows, pos0))
            else:
                cols = (jnp.broadcast_to(pos0, (b,))[:, None]
                        + jnp.arange(n))                     # (b, n)
                if ring:
                    cols = cols % L
                entry[name] = big.at[
                    jnp.arange(b)[:, None, None, None],
                    jnp.arange(big.shape[1])[None, :, None, None],
                    jnp.arange(big.shape[2])[None, None, :, None],
                    cols[:, None, None, :]].set(
                        rows.transpose(1, 2, 3, 0), mode="drop")
        out.append(entry)
    return out


def _quantize_kv(x):
    """(..., head_dim) -> (int8 values, f32 scale over the last axis).
    Symmetric per-(batch, position, head) quantization: scale =
    amax/127, so dequant error is at most scale/2 per element."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, jnp.float32(1e-30)) / 127.0
    q = jnp.round(xf / scale[..., None]).astype(jnp.int8)
    return q, scale


def _as_stored(entry, k, v):
    """Keys and values (..., head_dim) by tensor name as ``entry`` stores
    them: an int8 entry's quantized over head_dim, with their scales."""
    if "ks" not in entry:
        return {"k": k, "v": v}
    (kq, ks), (vq, vs) = _quantize_kv(k), _quantize_kv(v)
    return {"k": kq, "v": vq, "ks": ks, "vs": vs}


def _latent_tensors(k, index):
    """A latent entry's new (b, T, width) rows by tensor name: the
    latent rows and, with a token selector, its index keys."""
    return {"k": k} if index is None else {"k": k, "ik": index["k"]}


def new_row(entry, k, v=None, index=None):
    """One step's new keys and values as ``entry`` stores them, by
    tensor name. apply_layer's hook hands ``k`` and ``v`` (b, 1,
    kv_heads, head_dim) — rope keys arrive rotated and are cached
    rotated — or, under latent attention, the (b, 1, width) latent
    rows as ``k`` alone (and ``index``, a token selector's
    projections, whose key rides in the same entry). Returns
    (b, kv_heads | 1, head_dim | width) rows; an int8 entry's are
    quantized at append, with their (b, kv_heads) scales."""
    if v is None:
        return {name: x[:, 0][:, None, :]
                for name, x in _latent_tensors(k, index).items()}
    return _as_stored(entry, k[:, 0], v[:, 0])


def _head_major(entry, k, v):
    """T tokens' keys and values (b, T, kv_heads, head_dim) head-major,
    (b, kv_heads, T, head_dim), by tensor name. An int8 entry's are
    quantized over head_dim HERE, before the seq-minor flip; their
    (b, kv_heads, T) scales are seq-minor as they are."""
    return _as_stored(entry, k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3))


def _seq_minor(x):
    """_head_major's (b, kv_heads, T, head_dim) flipped to the cache's
    (b, kv_heads, head_dim, T); scales stay."""
    return x.transpose(0, 1, 3, 2) if x.ndim == 4 else x


def new_block(entry, k, v=None, index=None):
    """T tokens' new keys and values (new_row's arguments, T in place
    of 1) as ``entry`` stores them: by tensor name the seq-minor
    (b, kv_heads | 1, head_dim | width, T) block, beside an int8
    entry's its (b, kv_heads, T) scales."""
    if v is None:
        return {name: x.transpose(0, 2, 1)[:, None]
                for name, x in _latent_tensors(k, index).items()}
    return {name: _seq_minor(x)
            for name, x in _head_major(entry, k, v).items()}


# ---- the writes ---------------------------------------------------------

def _through_hd_view(kernel, big, new, *args, axis: int = 2):
    """An aliased write kernel of pallas.decode — which takes a
    (…, kv_heads, head_dim, lanes) tensor — on one tensor of an entry.
    A scale sidecar has no head_dim axis: it rides the same kernel
    through the free (…, kv_heads, 1, lanes) view, its new values
    taking the size-1 axis at ``axis``. (A lane-offset
    dynamic_update_slice in the kernel's place brings back the
    whole-tensor layout copies the kernels exist to avoid.)"""
    if big.ndim == 4:
        return kernel(big, new, *args)
    return kernel(big[:, :, None, :], jnp.expand_dims(new, axis),
                  *args)[:, :, 0, :]


def _on_axis(x, axis: int, rank: int):
    """1-D ``x`` along ``axis`` of a rank-``rank`` index array."""
    return jnp.expand_dims(x, [i for i in range(rank) if i != axis])


def write_row(entry, row, pos, window: int = 0):
    """``entry`` with ``row`` (new_row's) in column pos_b of batch row
    b; ``pos`` is a scalar (every row at the same position) or (b,).
    A column at or past max_len is dropped. ``window`` > 0: the entry
    is a sliding layer's ring, the column pos_b mod its length."""
    from rlo_tpu.pallas.decode import can_write_row, write_kv_row
    pos = jnp.asarray(pos)
    if window:
        pos = pos % entry["k"].shape[3]
    max_len = entry["k"].shape[3]
    # aliased pallas write: an XLA lane-offset DUS makes layout
    # assignment transpose the cache and copy it back for the flash
    # kernel every step. Still a whole 128-lane block a row and call
    # (see write_kv_row): a loop that owns its steps keeps a tail
    use_kernel = kernel_gate(can_write_row(max_len),
                             f"cache row write (max_len={max_len})")
    out, lanes = {}, {}
    for name, big in _tensors(entry):
        new = row[name]
        if use_kernel:
            out[name] = _through_hd_view(write_kv_row, big, new, pos)
        elif pos.ndim:
            # seq-minor: the new row lands in ONE lane per (b, head,
            # dim); tensors of one shape share the index arrays
            ahead, rank = big.shape[:-1], big.ndim - 1
            if ahead not in lanes:
                lanes[ahead] = tuple(
                    _on_axis(jnp.arange(n), axis, rank)
                    for axis, n in enumerate(ahead)) + (
                    _on_axis(pos, 0, rank),)
            out[name] = big.at[lanes[ahead]].set(new.astype(big.dtype))
        else:
            out[name] = lax.dynamic_update_slice(
                big, new[..., None].astype(big.dtype),
                (0,) * (big.ndim - 1) + (pos,))
    return out


def write_block(entry, block, pos0, cols, window: int = 0):
    """``entry`` with ``block`` (new_block's, T columns) at columns
    ``cols`` (b, T) = pos0_b + t of batch row b: the caller holds both,
    they are its rotary positions. A column at or past max_len is
    dropped. ``window`` > 0: a sliding layer's ring, every column mod
    its length (a block that passes the ring's end wraps to its
    start)."""
    from rlo_tpu.pallas.decode import can_write_block, write_kv_block
    max_len, T = entry["k"].shape[3], block["k"].shape[3]
    # the XLA lane-index scatter lowers to a generic scatter, measured
    # ~1.2 ms PER VERIFY at batch 1 (block_decode 1.65 ms vs 0.46 ms
    # decode step; builder's run on a v5e, 2026-08) — the aliased
    # pallas block write replaces it
    # (a block wider than the kernel's 128 columns goes in pieces)
    use_kernel = kernel_gate(
        can_write_block(max_len),
        f"cache block write (max_len={max_len}, T={T})")
    if window:
        cols = cols % max_len
    out, lanes = {}, {}
    for name, big in _tensors(entry):
        new = block[name].astype(big.dtype)
        if use_kernel:
            for t0 in range(0, T, 128):
                if window:
                    big = _through_hd_view(
                        functools.partial(write_kv_block, ring=True), big,
                        new[..., t0:t0 + 128], (pos0 + t0) % max_len)
                    continue
                big = _through_hd_view(write_kv_block, big,
                                       new[..., t0:t0 + 128], pos0 + t0)
            out[name] = big
            continue
        ahead, rank = big.shape[:-1], big.ndim
        if ahead not in lanes:
            lanes[ahead] = tuple(
                _on_axis(jnp.arange(n), axis, rank)
                for axis, n in enumerate(ahead)) + (
                jnp.expand_dims(cols, list(range(1, rank - 1))),)
        out[name] = big.at[lanes[ahead]].set(new)
    return out


def store_prompt(entry, k, v=None, index=None, window: int = 0):
    """``entry`` with a whole prompt's keys and values (new_block's
    arguments) in columns 0..plen-1, and the ``(k, v)`` the causal
    attend over the prompt block must see — what decode will read
    back: an int8 entry's DEQUANTIZED block (or the blockwise prefill
    and the decode-step scan diverge by the quantization envelope),
    any other entry's as they came. ``window`` > 0: a sliding layer's
    ring of R columns, which keeps the block's last R positions, each
    at its position mod R."""
    def put(big, block):
        n, R = block.shape[-1], big.shape[-1]
        if window and n > R:
            block = jnp.roll(block[..., n - R:], (n - R) % R, axis=-1)
        return lax.dynamic_update_slice(big, block.astype(big.dtype),
                                        (0,) * big.ndim)

    if v is None:
        return {name: put(entry[name], x) for name, x in new_block(
            entry, k, index=index).items()}, k, v
    rows = _head_major(entry, k, v)
    out = {name: put(big, _seq_minor(rows[name]))
           for name, big in _tensors(entry)}
    if "ks" not in entry:
        return out, k, v

    def read_back(x, s):
        return (rows[x].astype(jnp.float32) * rows[s][..., None]) \
            .transpose(0, 2, 1, 3).astype(k.dtype)

    return out, read_back("k", "ks"), read_back("v", "vs")


def store_tail_row(tail, row, newest):
    """One layer's ``tail`` (init_kv_tail's) with ``row`` (new_row's)
    at [newest], in the cache's dtype."""
    return {name: lax.dynamic_update_slice(
        rows, row[name][None].astype(rows.dtype),
        (newest,) + (0,) * (rows.ndim - 1))
        for name, rows in tail.items()}


def attend_work(cache, cfg: TransformerConfig, pos, T: int = 1,
                tp_axis: Optional[str] = None):
    """What the attends of ONE step share, built once for all layers:
    the flash-decode kernel's work list (pallas.decode.decode_work_list)
    for T queries a row from position ``pos`` (a scalar or (b,)) on —
    the list is a function of ``pos``, T and the cache's shape, and
    every layer's entry has the same shape. Handed to ``attend`` /
    ``attend_block`` as ``work``. None where the attend is the einsum
    (off the tpu backend, or a shape can_flash_decode refuses)."""
    from rlo_tpu.pallas.decode import decode_work_list
    ntp = lax.axis_size(tp_axis) if tp_axis is not None else 1
    tiling = (attend_tiling(cache, cfg, cfg.n_heads // ntp)
              if _on_tpu() else None)
    if tiling is None:
        return None
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                           cache[0]["k"].shape[:1])
    return decode_work_list(pos, T, *tiling)


def _ring_entry(cache, cfg: TransformerConfig):
    """A sliding layer's entry (all of them have one shape), or None."""
    return next((lc for i, lc in enumerate(cache)
                 if cfg.layer_window(i)), None)


def window_work(cache, cfg: TransformerConfig, pos, T: int = 1,
                first=None):
    """attend_work for the sliding layers' rings, built once a step for
    all of them: flash_decode's window work list
    (pallas.decode.window_work_list) of the ring tiles that hold a row's
    live keys, positions max(0, first - window + 1) .. pos + T - 1
    (``first``: the first query's position, ``pos`` where a write-behind
    tail puts it later). None where the attend is the einsum or the
    model has no sliding layer."""
    from rlo_tpu.pallas.decode import window_work_list
    ring = _ring_entry(cache, cfg)
    if ring is None or not _on_tpu():
        return None
    tiling = _tiling(ring["k"], cfg, cfg.n_heads)
    if tiling is None:
        return None
    b = ring["k"].shape[0]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    first = pos if first is None else jnp.broadcast_to(
        jnp.asarray(first, jnp.int32), (b,))
    return window_work_list(
        jnp.maximum(first - cfg.sliding_window + 1, 0), pos + (T - 1),
        *tiling)


def attend(q, entry, pos, scale, *, v_dim: int = 0, tail=None,
           work=None, select=None, info: Optional[dict] = None,
           window: int = 0, sinks=None):
    """One query a row, q (b, 1, H, head_dim), against ``entry``'s
    positions <= pos with the entry's own scales (_attend_cache).
    ``v_dim``: latent attention's kv_lora_rank — a latent entry cannot
    say where its values end. ``tail`` ``(rows, newest)``: one layer's
    write-behind rows, attended beside the cache. ``work``: the step's
    attend_work, for this ``pos``. ``select`` (b, 1, max_len) bool:
    select_tokens' choice, the positions the query attends among
    those. ``info``: select_tokens' record of that choice; the attend
    adds how it read it (select_counts' ``reads``). ``window`` > 0: a
    sliding layer's ring, whose keys past the window are masked by the
    position each column holds (``work``: window_work's list). ``sinks``
    (H,): a logit a head in the softmax's denominator (_attend_cache)."""
    if tail is not None:
        rows, newest = tail
        tail = (rows["k"], rows.get("v"), newest)
    return _attend_cache(q, entry["k"], entry.get("v"), pos, scale,
                         k_scale=entry.get("ks"),
                         v_scale=entry.get("vs"), v_dim=v_dim, tail=tail,
                         work=work, select=select, info=info,
                         window=window, sinks=sinks)


def attend_block(q, entry, pos_q, scale, *, pos0, v_dim: int = 0,
                 work=None, select=None, block_len: int = 0,
                 window: int = 0, sinks=None):
    """T queries a row, q (b, T, H, head_dim), query i at position
    pos_q[b, i] = pos0_b + i (_attend_cache_block). ``work``: the
    step's attend_work, for ``pos0`` and this T. ``select``
    (b, T, max_len) bool: select_tokens' choice, query by query.
    ``block_len`` > 0: the block-causal mask (whole blocks, ``pos0`` a
    multiple of it): a query attends every position of its block.
    ``window`` > 0: a sliding layer's ring (attend). ``sinks``: see
    attend."""
    return _attend_cache_block(q, entry["k"], entry.get("v"), pos_q,
                               scale, k_scale=entry.get("ks"),
                               v_scale=entry.get("vs"), pos0=pos0,
                               v_dim=v_dim, work=work, select=select,
                               block_len=block_len, window=window,
                               sinks=sinks)


# ---- the token selector ------------------------------------------------

#: what a server counts of one layer's selections and attends
#: (select_counts)
SELECT_STATS = ("keys_scored", "rows_attended", "dense_row_steps",
                "latent_rows_read")


#: how an attend read a selection (select_counts' ``reads``): the whole
#: live context under the selection as a mask, or the selected rows
#: alone. Small ints, so that a record that holds one may leave a jit
READS_CONTEXT, READS_SELECTION = 0, 1


def select_counts(ctx, topk: int, reads: int) -> dict:
    """SELECT_STATS of one layer, on the host, by select_tokens' own
    rule: ``ctx`` (rows, calls) int, the positions each query of each
    call may attend. ``keys_scored``: the contexts the selector scored
    — every row of a call in which ANY row has more than ``topk``
    positions, none of a call in which none has (select_tokens' one
    branch). ``rows_attended``: min(ctx, topk), what the attends had to
    read. ``dense_row_steps``: queries at ctx <= topk, whose selection
    is the identity. ``latent_rows_read``: what the attend that ran
    did read, by the form it reported when it was traced (``reads``,
    which _attend_cache writes into select_tokens' record):
    READS_CONTEXT or READS_SELECTION."""
    ctx = np.asarray(ctx, np.int64)
    kept = np.minimum(ctx, topk)
    scored = (ctx > topk).any(axis=0)                   # per call
    return {"keys_scored": int(ctx[:, scored].sum()),
            "rows_attended": int(kept.sum()),
            "dense_row_steps": int((ctx <= topk).sum()),
            "latent_rows_read": int({READS_CONTEXT: ctx,
                                     READS_SELECTION: kept}[reads].sum())}


#: context tile of the blocked XLA forms (index scores, the latent
#: block attend): no (heads, T, max_len) tensor is ever formed
_CTX_TILE = 1024

#: f32 score bytes up to which a block attend may form the whole
#: (heads, T, max_len) tensor in one einsum: twice what a 128-token
#: chunk of 128 heads forms over a cache of 4096 positions (256 MiB)
_EINSUM_SCORE_BYTES = 512 << 20


def _ctx_tiles(cache, pos_q):
    """The blocked XLA forms' walk over ``cache`` (b, 1, d, max_len):
    (tile width, tiles that hold a position <= max pos_q, dot dtype).
    A cache axis that _CTX_TILE does not divide is one tile."""
    L = cache.shape[3]
    bt = _CTX_TILE if L % _CTX_TILE == 0 else L
    dot_dt = jnp.float32 if cache.dtype == jnp.float32 else jnp.bfloat16
    return bt, jnp.clip(jnp.max(pos_q) // bt + 1, 1, L // bt), dot_dt


def _index_scores(index, ik_cache, pos_q):
    """The selector's scores in plain XLA: index["q"] (b, T, heads, d),
    index["w"] (b, T, heads) against ``ik_cache`` (b, 1, d, max_len) ->
    (b, T, max_len) f32, ``sum_j w_j relu(q_j . k_s)`` at s <= pos_q
    and -inf past it. Context tile by context tile (the (b, T, heads,
    tile) products of one tile at a time), live tiles only."""
    q, w = index["q"], index["w"].astype(jnp.float32)
    b, T = q.shape[:2]
    L = ik_cache.shape[3]
    bt, n_live, dot_dt = _ctx_tiles(ik_cache, pos_q)

    def tile(i, out):
        k = lax.dynamic_slice_in_dim(ik_cache[:, 0], i * bt, bt, axis=2)
        s = jnp.einsum("bthd,bdk->bthk", q.astype(dot_dt),
                       k.astype(dot_dt),
                       preferred_element_type=jnp.float32)
        s = jnp.einsum("bthk,bth->btk", jnp.maximum(s, 0.0), w)
        return lax.dynamic_update_slice_in_dim(out, s, i * bt, axis=2)

    out = lax.fori_loop(0, n_live, tile,
                        jnp.zeros((b, T, L), jnp.float32))
    live = jnp.arange(L)[None, None, :] <= pos_q[:, :, None]
    return jnp.where(live, out, -jnp.inf)


def topk_mask(scores, k: int):
    """EXACT top-``k`` of ``scores`` (..., n) f32 as a bool mask: the k
    largest, ties to the lowest position; every position where n <= k.
    No sort: the k-th largest value is found bit by bit (32 counts over
    the row, on the order-preserving integer image of the floats), then
    the ties at that value are taken in position order. -inf entries
    rank last and are taken only when fewer than k others exist; mask
    them off afterwards. (Settling 2 / 4 / 8 bits a pass, one count
    against 3 / 15 / 255 candidates, measured 0.139 / 0.200 / 2.77 ms
    against 0.134 at 32 x 24576 on a v5e: PERF.md §6.) The CPU's
    form and the reference of pallas.decode.topk_select, which computes
    the same mask in one kernel on the tpu backend (select_tokens): here
    every count re-reads the whole score array."""
    n = scores.shape[-1]
    if n <= k:
        return jnp.ones(scores.shape, bool)
    raw = lax.bitcast_convert_type(scores.astype(jnp.float32), jnp.int32)
    # float order -> unsigned integer order
    key = jnp.where(raw < 0, ~raw, raw | jnp.int32(-2 ** 31))
    key = lax.bitcast_convert_type(key, jnp.uint32)

    def bit(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(key >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    kth = lax.fori_loop(0, 32, bit,
                        jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > kth[..., None]
    ties = key == kth[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                            <= room[..., None]))


def select_work(cache, cfg: TransformerConfig, pos):
    """index_score's work list for one step (what attend_work is to the
    attend): built once from ``pos`` for all layers. None where the
    scores come from XLA (off the tpu backend, a refused shape) or the
    configuration selects nothing."""
    from rlo_tpu.pallas.decode import (can_index_score, decode_work_list,
                                       index_score_tile)
    if "ik" not in cache[0] or not _on_tpu():
        return None
    L = cache[0]["ik"].shape[3]
    if L <= cfg.index_topk or not can_index_score(L, cfg.index_head_dim):
        return None
    bk = index_score_tile(L)
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32),
                           cache[0]["ik"].shape[:1])
    return decode_work_list(pos, 1, bk, L // bk)


def select_tokens(index, entry, pos_q, topk: int, *, work=None,
                  info: Optional[list] = None):
    """The positions each query attends, as a (b, T, max_len) bool
    mask: ``index`` (transformer.index_project) scores ``entry``'s
    index keys at positions <= pos_q (b, T) — which the entry already
    holds: write, then select, then attend — and the ``topk`` best
    stay, exactly, ties to the lowest position; a query with no more
    than ``topk`` positions keeps them all. None (attend as without a
    selector) where the cache itself is no longer than ``topk``. While
    NO query of the call has more than ``topk`` positions, nothing is
    scored (one branch on the largest position). ``work``: the step's
    select_work. ``info``: a list that receives {"scores", "select",
    "keys_scored"} (the live contexts this call scored: 0 where it
    scored nothing) and "select_kernel" (1 where the choice ran
    pallas.decode.topk_select, 0 where it ran topk_mask) for callers
    that check them; the attend that follows adds how it read the
    choice (_attend_cache)."""
    from rlo_tpu.pallas.decode import (can_index_score, can_topk_select,
                                       index_score, topk_select)
    ik = entry["ik"]
    b, T = pos_q.shape
    L = ik.shape[3]
    if L <= topk:
        return None
    live = jnp.arange(L)[None, None, :] <= pos_q[:, :, None]
    ctx = jnp.minimum(pos_q + 1, L)
    use_kernel = kernel_gate(can_topk_select(L, topk),
                             f"exact top-k (max_len={L}, topk={topk})")

    def chosen():
        with jax.named_scope("dsa.score"):
            if T == 1 and kernel_gate(
                    can_index_score(L, ik.shape[2]),
                    f"index score (max_len={L}, dim={ik.shape[2]})"):
                scores = index_score(index["q"][:, 0], index["w"][:, 0],
                                     ik, pos_q[:, 0], work=work)[:, None]
            else:
                scores = _index_scores(index, ik, pos_q)
        with jax.named_scope("dsa.select"):
            mask = (topk_select(scores, topk) if use_kernel
                    else topk_mask(scores, topk))
            return scores, mask & live, jnp.sum(ctx)

    def everything():
        return jnp.where(live, 0.0, -jnp.inf), live, jnp.int32(0)

    scores, select, scored = lax.cond(jnp.max(pos_q) < topk, everything,
                                      chosen)
    if info is not None:
        info.append({"scores": scores, "select": select,
                     "keys_scored": scored,
                     "select_kernel": int(use_kernel)})
    return select


# ---- what a server asks of a cache ------------------------------------

def scatter_slot(cache, row, slot):
    """The pool ``cache`` with a one-row cache (a prefilled request's)
    at batch row ``slot``, tensor by tensor."""
    def put(big, small):
        return lax.dynamic_update_slice(
            big, small.astype(big.dtype),
            (slot,) + (0,) * (big.ndim - 1))
    return jax.tree.map(put, cache, row)


def bytes_per_position(cache) -> int:
    """What one position of one batch row holds, over all layers and
    tensors (a sliding layer's ring: while it holds that position)."""
    return sum(sum(a.size * a.dtype.itemsize
                   for a in jax.tree.leaves(lc))
               // (lc["k"].shape[0] * lc["k"].shape[3]) for lc in cache)


def attend_tiling(cache, cfg: TransformerConfig,
                  n_heads: Optional[int] = None):
    """flash_decode's tiling of the cache axis as (tile width, tiles);
    None for a shape can_flash_decode refuses (the attend is the
    einsum: nothing is tiled). ``n_heads``: the query heads beside
    this cache, where a shard holds fewer than cfg.n_heads. Of
    the first full layer's entry: a sliding layer's ring has its own
    (window_tiling)."""
    full = next((lc for i, lc in enumerate(cache)
                 if not cfg.layer_window(i)), None)
    return None if full is None else _tiling(full["k"], cfg, n_heads)


def window_tiling(cache, cfg: TransformerConfig):
    """attend_tiling of the sliding layers' rings; None without any."""
    ring = _ring_entry(cache, cfg)
    return None if ring is None else _tiling(ring["k"], cfg, cfg.n_heads)


def _tiling(k, cfg: TransformerConfig, n_heads: Optional[int] = None):
    from rlo_tpu.pallas.decode import can_flash_decode, flash_decode_tile
    latent = cfg.kv_lora_rank if cfg.mla else 0
    if not can_flash_decode(k.shape[3], k.shape[2], v_dim=latent):
        return None
    bk = flash_decode_tile(k, n_heads or cfg.n_heads, latent=cfg.mla)
    return bk, -(-k.shape[3] // bk)


# ---- the attend itself -------------------------------------------------

def _ring_positions(newest, R: int):
    """The position each column of a ring of ``R`` holds, (b, R), from
    the newest position written into it ``newest`` (b,): the largest
    p <= newest with p = column (mod R) — negative where the ring has not
    come round to the column yet (nothing there to attend)."""
    col = jnp.arange(R, dtype=jnp.int32)[None, :]
    newest = jnp.asarray(newest, jnp.int32)[:, None]
    return newest - jnp.mod(newest - col, R)


def _attend_cache(q, k_cache, v_cache, pos, scale,
                  k_scale=None, v_scale=None, use_flash=None,
                  v_dim: int = 0, tail=None, work=None, select=None,
                  info: Optional[dict] = None, window: int = 0,
                  sinks=None):
    """q (b, 1, H, hd) against the cache prefix [0, pos]: full-length
    matmul over the static cache, masked beyond the position. ``pos``
    is a scalar (all rows at the same position) or a (b,) vector
    (ragged decode: each row masks at its own position). The cache
    may hold fewer (grouped) K/V heads: each group of H/kv_heads
    query heads attends its shared K/V head directly — no repeat is
    ever materialized.

    Quantized caches (cfg.kv_cache_dtype='int8') pass per-(batch,
    head, position) ``k_scale``/``v_scale`` (b, kv_heads, max_len):
    the dequant is FOLDED into the score and probability tensors —
    scores scale per key position, probabilities pre-multiply the
    value scale — so the (b, kv, hd, max_len) cache operands enter
    their matmuls as stored int8 and the big HBM reads stay 1
    byte/element.

    A LATENT cache passes ``v_cache`` None and ``v_dim``: one stream
    (b, 1, hd, max_len) that every head attends, whose leading
    ``v_dim`` features are the values; returns (b, 1, H, v_dim).

    ``tail`` ``(tk, tv, newest)``: a round's write-behind rows
    (init_kv_tail's layout; tv None for a latent cache). The query
    attends cache positions <= pos AND tail rows 0..newest — row t is
    position pos + 1 + t, which the cache does not hold yet — in one
    softmax; tail positions at or past max_len are left out, as the
    per-step write drops them.

    ``work``: the kernel's work list for this ``pos``, where the
    caller built one for all its layers (attend_work); the einsum has
    no use for it. ``select`` (b, 1, max_len) bool: a token selector's
    choice among the positions <= pos (select_tokens); the kernel
    takes it as a mask over the tiles it streams whole. ``info``: the
    selection's record, which receives the form this attend read it
    in (select_counts' ``reads``).

    ``window`` > 0: ``k_cache`` / ``v_cache`` are a sliding layer's ring
    (kvcache's module docstring) whose newest position is ``pos``; the
    query (at ``pos``, or with a tail at pos + 1 + newest) attends the
    keys of its last ``window`` positions alone, each column masked by
    the position it holds.

    ``sinks`` (H,): attention sinks, a logit a head that joins its
    softmax's denominator beside the keys: sum_j exp(s_j - m) v_j /
    (sum_j exp(s_j - m) + exp(sink - m))."""
    b, one, nh, hd = q.shape
    nkv, max_len = k_cache.shape[1], k_cache.shape[3]
    if info is not None:
        # the MASKED form, kernel and einsum alike: every row's whole
        # live context is read, selected or not
        info["reads"] = READS_CONTEXT
    if use_flash is None:
        from rlo_tpu.pallas.decode import can_flash_decode
        use_flash = kernel_gate(
            can_flash_decode(max_len, hd, v_dim=v_dim),
            f"decode attend (max_len={max_len}, head_dim={hd}, "
            f"v_dim={v_dim})")
    if use_flash:
        # fused decode attention: cache tiles stream through VMEM
        # (int8 tiles dequantize there — the einsum path measured XLA
        # materializing the dequant at batch 32), online softmax, one
        # pass — rlo_tpu.pallas.decode
        from rlo_tpu.pallas.decode import flash_decode
        if sinks is not None:
            with jax.named_scope("mla.sink"):
                sinks = sinks.astype(jnp.float32)
        return flash_decode(
            q, k_cache, v_cache, pos, scale, k_scale, v_scale,
            v_dim=v_dim, tail=tail, work=work,
            select=None if select is None else select[:, 0],
            window=window, sinks=sinks)
    # the einsum path IS the T=1 case of the block attend — one
    # implementation, so a dequant/mask/dtype fix can never diverge
    # decode_step from block_decode (speculative decoding's
    # losslessness rides on their agreement)
    posv = jnp.asarray(pos, jnp.int32)
    pos_q = (jnp.full((b, 1), posv) if posv.ndim == 0
             else posv.reshape(b, 1))
    return _attend_cache_block(q, k_cache, v_cache, pos_q, scale,
                               k_scale=k_scale, v_scale=v_scale,
                               v_dim=v_dim, tail=tail, select=select,
                               window=window, sinks=sinks)


def _attend_cache_block(q, k_cache, v_cache, pos_q, scale,
                        k_scale=None, v_scale=None, pos0=None,
                        use_flash=None, v_dim: int = 0, tail=None,
                        work=None, select=None, block_len: int = 0,
                        window: int = 0, sinks=None):
    """Block variant of the cache attend: q (b, T, nh, hd) where query
    i of row b sits at position pos_q[b, i] and attends cache
    positions <= pos_q[b, i]. Because the block's own K/V rows are
    written into the cache BEFORE attending (write-then-attend, as in
    decode_step), that single mask covers in-block causality too.
    Used by the speculative-decoding verify step (T = gamma tokens
    through the target in ONE forward); T=1 recovers decode_step's
    attend shape.

    ``pos0`` (b,) asserts the positions are CONTIGUOUS per row
    (pos_q[b, i] == pos0[b] + i) — a static property of the caller,
    not checkable on traced values — which enables the fused
    flash-block path on TPU: the SAME kernel family decode_step's
    attend uses (T=1), so speculative verify logits and plain decode
    logits share numerics (losslessness of greedy speculative decoding
    needs their argmaxes to agree).

    ``tail`` (T = 1, the einsum path only; see _attend_cache): the
    tail's scores join the cache's before the softmax. ``work``: see
    _attend_cache (for ``pos0`` and this T).

    ``select`` (b, T, max_len) bool: a token selector's choice, query
    by query, one more mask (the XLA forms only: the T > 1 kernel takes
    none). A LATENT block whose (heads, T, max_len) f32 scores would
    pass _EINSUM_SCORE_BYTES — admission's wide chunks of a long
    prompt — is attended context tile by context tile instead
    (_attend_latent_blocked), on the chip and off it.

    ``block_len`` > 0: generation by diffusion over blocks. Query i
    attends every position of its own block of ``block_len`` as well
    (write-then-attend has put them there): positions <= pos_q
    rounded up to its block's last (``pos0`` is a multiple of
    block_len, T a whole number of blocks). The kernel and the einsum
    take the same mask; per-head K/V caches only.

    ``window`` > 0: a sliding layer's ring (_attend_cache), whose newest
    position is the last query's pos_q (with a tail: pos_q itself, the
    queries at pos_q + 1 + newest); a query attends its last ``window``
    positions alone.

    ``sinks`` (H,): attention sinks (_attend_cache), in every form."""
    b, T, nh, hd = q.shape
    nkv, max_len = k_cache.shape[1], k_cache.shape[3]
    if window and (block_len or v_dim or select is not None
                   or k_scale is not None):
        raise ValueError("a ring is attended by per-head queries, "
                         "without block mask, selection or int8 scales")
    if block_len:
        if v_dim or tail is not None or select is not None or T % block_len:
            raise ValueError(
                "a block-causal attend takes whole blocks of a per-head "
                "K/V cache, without tail or selection")
        # the last position a query attends: its block's last
        pos_q = pos_q - pos_q % block_len + (block_len - 1)
    blocked = bool(v_dim) and tail is None and (
        4 * b * nh * T * max_len > _EINSUM_SCORE_BYTES)
    if select is not None and T > 1:
        use_flash = False       # a mask a query: the XLA forms
    if use_flash is None:
        from rlo_tpu.pallas.decode import (_block_fits_vmem,
                                           _tile_rule,
                                           can_flash_decode)
        itemsize = 4 if k_cache.dtype == jnp.float32 else 2
        # where the kernel does not run, which XLA form does
        path = (f"the latent attend blocked over context tiles of "
                f"{_CTX_TILE} (online softmax)" if blocked
                else "the einsum over the whole cache")
        gate = pos0 is not None and kernel_gate(
            can_flash_decode(max_len, hd, v_dim=v_dim),
            f"block attend (max_len={max_len}, head_dim={hd}, "
            f"v_dim={v_dim}; XLA form: {path})")
        fits = gate and _block_fits_vmem(
            max_len, hd, nkv, nh // nkv, T, itemsize,
            *_tile_rule(bool(v_dim)), streams=1 if v_dim else 2)
        if gate and not fits:
            # T=1 would flash but this block cannot share its tiling:
            # the XLA fallback DIVERGES numerically from the flash
            # decode step, so speculative greedy parity degrades to
            # near-tie class in this regime — warn, don't hide it
            import warnings
            warnings.warn(
                f"block attend T={T} exceeds the VMEM budget at the "
                f"T=1 flash tiling (nkv={nkv}, head_dim={hd}, "
                f"max_len={max_len}); running {path} in XLA — verify "
                f"numerics will NOT match the flash decode step "
                f"(use a smaller gamma for exact speculative parity)",
                KernelFallbackWarning, stacklevel=2)
        use_flash = fits
    if use_flash:
        from rlo_tpu.pallas.decode import flash_block_decode
        if sinks is not None:
            with jax.named_scope("mla.sink"):
                sinks = sinks.astype(jnp.float32)
        return flash_block_decode(
            q, k_cache, v_cache, pos0, scale, k_scale, v_scale,
            v_dim=v_dim, work=work, block_len=block_len,
            select=None if select is None else select[:, 0],  # T == 1
            window=window, sinks=sinks)
    if blocked:
        return _attend_latent_blocked(q, k_cache, pos_q, scale, v_dim,
                                      select, sinks)
    if v_dim:  # latent: the values are the stream's leading features
        v_cache = k_cache[:, :, :v_dim]
    rep = nh // nkv
    qg = q.reshape(b, T, nkv, rep, hd)
    cache_dt = jnp.bfloat16 if (k_scale is not None and
                                jax.default_backend() == "tpu") \
        else jnp.float32
    s = jnp.einsum("bqgrd,bgdk->bgrqk", qg.astype(cache_dt),
                   k_cache.astype(cache_dt),
                   preferred_element_type=jnp.float32) * scale
    s = s.astype(jnp.float32)
    if k_scale is not None:
        s = s * k_scale[:, :, None, None, :]
    if window:
        # a ring: each column by the position it holds, the newest the
        # last query's (write-then-attend), or with a tail the cache's
        held = _ring_positions(pos_q[:, -1], max_len)[:, None, :]
        at = pos_q if tail is None else pos_q + 1 + tail[2]
        mask = ((held >= 0) & (held <= pos_q[:, :, None])
                & (held > at[:, :, None] - window))
    else:
        mask = jnp.arange(max_len)[None, None, :] <= pos_q[:, :, None]
    if select is not None:
        mask = mask & select
    s = jnp.where(mask[:, None, None, :, :], s, _NEG)
    if tail is not None:
        tk, tv, newest = tail
        if v_dim:
            tv = tk[..., :v_dim]
        t = jnp.arange(tk.shape[0])
        if window:      # a ring drops nothing; the band counts back
            live = jnp.broadcast_to((t <= newest) & (t > newest - window),
                                    (b, tk.shape[0]))
        else:
            live = (t <= newest) & (pos_q + 1 + t < max_len)   # (b, kk)
        s_t = jnp.einsum("bqgrd,tbgd->bgrqt", qg.astype(cache_dt),
                         tk.astype(cache_dt),
                         preferred_element_type=jnp.float32) * scale
        s = jnp.concatenate(
            [s, jnp.where(live[:, None, None, None, :], s_t, _NEG)], -1)
    if sinks is not None:
        with jax.named_scope("mla.sink"):
            sink = jnp.broadcast_to(
                sinks.astype(jnp.float32).reshape(1, nkv, rep, 1, 1),
                s.shape[:-1] + (1,))
            p = jax.nn.softmax(jnp.concatenate([s, sink], -1),
                               axis=-1)[..., :-1]
    else:
        p = jax.nn.softmax(s, axis=-1)
    if tail is not None:
        p, p_t = p[..., :max_len], p[..., max_len:]
    if v_scale is not None:
        p = p * v_scale[:, :, None, None, :]
    out = jnp.einsum("bgrqk,bgdk->bqgrd", p.astype(cache_dt),
                     v_cache.astype(cache_dt),
                     preferred_element_type=jnp.float32)
    if tail is not None:
        out = out + jnp.einsum("bgrqt,tbgd->bqgrd", p_t.astype(cache_dt),
                               tv.astype(cache_dt),
                               preferred_element_type=jnp.float32)
    return out.astype(jnp.float32).reshape(b, T, nh, v_dim or hd)



def _attend_latent_blocked(q, k_cache, pos_q, scale, v_dim: int,
                           select=None, sinks=None):
    """_attend_cache_block's einsum for a latent cache with NO
    (heads, T, max_len) tensor: the live context tiles of width
    _CTX_TILE one after another, (m, l, o) carried through an online
    softmax. q (b, T, H, d), k_cache (b, 1, d, max_len), pos_q (b, T),
    ``select`` (b, T, max_len) bool or None; returns (b, T, H, v_dim)
    f32. What a (heads, T, tile) f32 block costs is the caller's
    choice of T. ``sinks`` (H,): attention sinks (_attend_cache), added
    to the denominator once the tiles are done."""
    b, T, nh, d = q.shape
    bt, n_live, dot_dt = _ctx_tiles(k_cache, pos_q)
    qh = q.transpose(0, 2, 1, 3).astype(dot_dt)          # (b, H, T, d)

    def tile(i, carry):
        m, l, o = carry
        k = lax.dynamic_slice_in_dim(k_cache[:, 0], i * bt, bt,
                                     axis=2).astype(dot_dt)  # (b, d, bt)
        s = jnp.einsum("bhtd,bdk->bhtk", qh, k,
                       preferred_element_type=jnp.float32) * scale
        at = i * bt + jnp.arange(bt)
        mask = at[None, None, :] <= pos_q[:, :, None]     # (b, T, bt)
        if select is not None:
            mask = mask & lax.dynamic_slice_in_dim(select, i * bt, bt,
                                                   axis=2)
        mask = mask[:, None]
        s = jnp.where(mask, s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.where(mask, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhtk,bck->bhtc", p.astype(dot_dt), k[:, :v_dim],
            preferred_element_type=jnp.float32)
        return m_new, l, o

    init = (jnp.full((b, nh, T), _NEG, jnp.float32),
            jnp.zeros((b, nh, T), jnp.float32),
            jnp.zeros((b, nh, T, v_dim), jnp.float32))
    m, l, o = lax.fori_loop(0, n_live, tile, init)
    if sinks is not None:
        with jax.named_scope("mla.sink"):
            l = l + jnp.exp(sinks.astype(jnp.float32)[None, :, None] - m)
    return (o / l[..., None]).transpose(0, 2, 1, 3)
