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
    kv_lora_rank features.

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

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from rlo_tpu.models.transformer import TransformerConfig
from rlo_tpu.ops.ring_attention import _NEG
from rlo_tpu.pallas.reduce import (KernelFallbackWarning, _on_tpu,
                                   kernel_gate)


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  tp_axis: Optional[str] = None):
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
    probability tensors so the cache reads stay int8 on the wire."""
    ntp = lax.axis_size(tp_axis) if tp_axis is not None else 1
    assert cfg.kv_heads % ntp == 0
    kvh = cfg.kv_heads // ntp
    if cfg.mla and (tp_axis is not None or cfg.kv_cache_dtype):
        raise ValueError("the latent cache is unsharded and in the "
                         "activation dtype so far")
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
        return [{"k": jnp.zeros((batch, 1, width, max_len),
                                cfg.act_dtype)}
                for _ in range(cfg.n_layers)]
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
    return [(name, entry[name]) for name in ("k", "v", "ks", "vs")
            if name in entry]


def keeps_tail(cache) -> bool:
    """Whether a loop that owns its steps may keep this cache's new
    rows in a write-behind tail (init_kv_tail): an int8 cache, whose
    rows come with scale sidecars, keeps the write of every step."""
    return not any("ks" in lc for lc in cache)


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
        raise ValueError("an int8 cache has no write-behind tail")
    return [{name: jnp.zeros((n,) + a.shape[:3], a.dtype)
             for name, a in lc.items()} for lc in cache]


def fold_kv_tail(cache, tail, pos0):
    """The cache with every tail row in place: layer by layer, row t
    of ``tail`` (init_kv_tail's layout) lands at column pos0_b + t of
    batch row b; columns at or past max_len are dropped, as the
    per-step write drops them. Equal, entry for entry, to the cache n
    decode_step calls without a tail leave."""
    from rlo_tpu.pallas.decode import can_write_block, write_kv_tail
    pos0 = jnp.asarray(pos0, jnp.int32)
    out = []
    for lc, tl in zip(cache, tail):
        entry = {}
        for name, big in lc.items():
            rows = tl[name]
            n, b, L = rows.shape[0], big.shape[0], big.shape[3]
            if kernel_gate(can_write_block(L) and n <= 128,
                           f"cache tail fold (max_len={L}, rows={n})"):
                entry[name] = write_kv_tail(big, rows, pos0)
            else:
                cols = (jnp.broadcast_to(pos0, (b,))[:, None]
                        + jnp.arange(n))                     # (b, n)
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


def new_row(entry, k, v=None):
    """One step's new keys and values as ``entry`` stores them, by
    tensor name. apply_layer's hook hands ``k`` and ``v`` (b, 1,
    kv_heads, head_dim) — rope keys arrive rotated and are cached
    rotated — or, under latent attention, the (b, 1, width) latent
    rows as ``k`` alone. Returns (b, kv_heads | 1, head_dim | width)
    rows; an int8 entry's are quantized at append, with their
    (b, kv_heads) scales."""
    if v is None:
        return {"k": k[:, 0][:, None, :]}
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


def new_block(entry, k, v=None):
    """T tokens' new keys and values (new_row's arguments, T in place
    of 1) as ``entry`` stores them: by tensor name the seq-minor
    (b, kv_heads | 1, head_dim | width, T) block, beside an int8
    entry's its (b, kv_heads, T) scales."""
    if v is None:
        return {"k": k.transpose(0, 2, 1)[:, None]}
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


def write_row(entry, row, pos):
    """``entry`` with ``row`` (new_row's) in column pos_b of batch row
    b; ``pos`` is a scalar (every row at the same position) or (b,).
    A column at or past max_len is dropped."""
    from rlo_tpu.pallas.decode import can_write_row, write_kv_row
    pos = jnp.asarray(pos)
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
            # dim); tensors of one rank share the index arrays
            rank = big.ndim - 1
            if rank not in lanes:
                ahead = [jnp.arange(n) for n in big.shape[:-1]]
                lanes[rank] = tuple(
                    _on_axis(a, axis, rank)
                    for axis, a in enumerate(ahead)) + (
                    _on_axis(pos, 0, rank),)
            out[name] = big.at[lanes[rank]].set(new.astype(big.dtype))
        else:
            out[name] = lax.dynamic_update_slice(
                big, new[..., None].astype(big.dtype),
                (0,) * (big.ndim - 1) + (pos,))
    return out


def write_block(entry, block, pos0, cols):
    """``entry`` with ``block`` (new_block's, T columns) at columns
    ``cols`` (b, T) = pos0_b + t of batch row b: the caller holds both,
    they are its rotary positions. A column at or past max_len is
    dropped."""
    from rlo_tpu.pallas.decode import can_write_block, write_kv_block
    max_len, T = entry["k"].shape[3], block["k"].shape[3]
    # the XLA lane-index scatter lowers to a generic scatter, measured
    # ~1.2 ms PER VERIFY at batch 1 (block_decode 1.65 ms vs 0.46 ms
    # decode step; builder's run on a v5e, 2026-08) — the aliased
    # pallas block write replaces it
    use_kernel = kernel_gate(
        can_write_block(max_len) and T <= 128,
        f"cache block write (max_len={max_len}, T={T})")
    out, lanes = {}, {}
    for name, big in _tensors(entry):
        new = block[name].astype(big.dtype)
        if use_kernel:
            out[name] = _through_hd_view(write_kv_block, big, new, pos0)
            continue
        rank = big.ndim
        if rank not in lanes:
            lanes[rank] = tuple(
                _on_axis(jnp.arange(n), axis, rank)
                for axis, n in enumerate(big.shape[:-1])) + (
                jnp.expand_dims(cols, list(range(1, rank - 1))),)
        out[name] = big.at[lanes[rank]].set(new)
    return out


def store_prompt(entry, k, v=None):
    """``entry`` with a whole prompt's keys and values (new_block's
    arguments) in columns 0..plen-1, and the ``(k, v)`` the causal
    attend over the prompt block must see — what decode will read
    back: an int8 entry's DEQUANTIZED block (or the blockwise prefill
    and the decode-step scan diverge by the quantization envelope),
    any other entry's as they came."""
    def put(big, block):
        return lax.dynamic_update_slice(big, block.astype(big.dtype),
                                        (0,) * big.ndim)

    if v is None:
        return {"k": put(entry["k"], new_block(entry, k)["k"])}, k, v
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


def attend(q, entry, pos, scale, *, v_dim: int = 0, tail=None,
           work=None):
    """One query a row, q (b, 1, H, head_dim), against ``entry``'s
    positions <= pos with the entry's own scales (_attend_cache).
    ``v_dim``: latent attention's kv_lora_rank — a latent entry cannot
    say where its values end. ``tail`` ``(rows, newest)``: one layer's
    write-behind rows, attended beside the cache. ``work``: the step's
    attend_work, for this ``pos``."""
    if tail is not None:
        rows, newest = tail
        tail = (rows["k"], rows.get("v"), newest)
    return _attend_cache(q, entry["k"], entry.get("v"), pos, scale,
                         k_scale=entry.get("ks"),
                         v_scale=entry.get("vs"), v_dim=v_dim, tail=tail,
                         work=work)


def attend_block(q, entry, pos_q, scale, *, pos0, v_dim: int = 0,
                 work=None):
    """T queries a row, q (b, T, H, head_dim), query i at position
    pos_q[b, i] = pos0_b + i (_attend_cache_block). ``work``: the
    step's attend_work, for ``pos0`` and this T."""
    return _attend_cache_block(q, entry["k"], entry.get("v"), pos_q,
                               scale, k_scale=entry.get("ks"),
                               v_scale=entry.get("vs"), pos0=pos0,
                               v_dim=v_dim, work=work)


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
    tensors."""
    k = cache[0]["k"]
    return sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(cache)) // (k.shape[0]
                                                    * k.shape[3])


def attend_tiling(cache, cfg: TransformerConfig,
                  n_heads: Optional[int] = None):
    """flash_decode's tiling of the cache axis as (tile width, tiles);
    None for a shape can_flash_decode refuses (the attend is the
    einsum: nothing is tiled). ``n_heads``: the query heads beside
    this cache, where a shard holds fewer than cfg.n_heads."""
    from rlo_tpu.pallas.decode import can_flash_decode, flash_decode_tile
    k = cache[0]["k"]
    latent = cfg.kv_lora_rank if cfg.mla else 0
    if not can_flash_decode(k.shape[3], k.shape[2], v_dim=latent):
        return None
    bk = flash_decode_tile(k, n_heads or cfg.n_heads, latent=cfg.mla)
    return bk, -(-k.shape[3] // bk)


# ---- the attend itself -------------------------------------------------

def _attend_cache(q, k_cache, v_cache, pos, scale,
                  k_scale=None, v_scale=None, use_flash=None,
                  v_dim: int = 0, tail=None, work=None):
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
    no use for it."""
    b, one, nh, hd = q.shape
    nkv, max_len = k_cache.shape[1], k_cache.shape[3]
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
        return flash_decode(q, k_cache, v_cache, pos, scale,
                            k_scale, v_scale, v_dim=v_dim, tail=tail,
                            work=work)
    # the einsum path IS the T=1 case of the block attend — one
    # implementation, so a dequant/mask/dtype fix can never diverge
    # decode_step from block_decode (speculative decoding's
    # losslessness rides on their agreement)
    posv = jnp.asarray(pos, jnp.int32)
    pos_q = (jnp.full((b, 1), posv) if posv.ndim == 0
             else posv.reshape(b, 1))
    return _attend_cache_block(q, k_cache, v_cache, pos_q, scale,
                               k_scale=k_scale, v_scale=v_scale,
                               v_dim=v_dim, tail=tail)


def _attend_cache_block(q, k_cache, v_cache, pos_q, scale,
                        k_scale=None, v_scale=None, pos0=None,
                        use_flash=None, v_dim: int = 0, tail=None,
                        work=None):
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
    _attend_cache (for ``pos0`` and this T)."""
    b, T, nh, hd = q.shape
    nkv, max_len = k_cache.shape[1], k_cache.shape[3]
    if use_flash is None:
        from rlo_tpu.pallas.decode import (_block_fits_vmem,
                                           _tile_rule,
                                           can_flash_decode)
        itemsize = 4 if k_cache.dtype == jnp.float32 else 2
        gate = pos0 is not None and kernel_gate(
            can_flash_decode(max_len, hd, v_dim=v_dim),
            f"block attend (max_len={max_len}, head_dim={hd}, "
            f"v_dim={v_dim})")
        fits = gate and _block_fits_vmem(
            max_len, hd, nkv, nh // nkv, T, itemsize,
            *_tile_rule(bool(v_dim)))
        if gate and not fits:
            # T=1 would flash but this block cannot share its tiling:
            # the einsum fallback DIVERGES numerically from the flash
            # decode step, so speculative greedy parity degrades to
            # near-tie class in this regime — warn, don't hide it
            import warnings
            warnings.warn(
                f"block attend T={T} exceeds the VMEM budget at the "
                f"T=1 flash tiling (nkv={nkv}, head_dim={hd}, "
                f"max_len={max_len}); falling back to einsum — verify "
                f"numerics will NOT match the flash decode step "
                f"(use a smaller gamma for exact speculative parity)",
                KernelFallbackWarning, stacklevel=2)
        use_flash = fits
    if use_flash:
        from rlo_tpu.pallas.decode import flash_block_decode
        return flash_block_decode(q, k_cache, v_cache, pos0, scale,
                                  k_scale, v_scale, v_dim=v_dim,
                                  work=work)
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
    mask = jnp.arange(max_len)[None, None, :] <= pos_q[:, :, None]
    s = jnp.where(mask[:, None, None, :, :], s, _NEG)
    if tail is not None:
        tk, tv, newest = tail
        if v_dim:
            tv = tk[..., :v_dim]
        t = jnp.arange(tk.shape[0])
        live = (t <= newest) & (pos_q + 1 + t < max_len)   # (b, kk)
        s_t = jnp.einsum("bqgrd,tbgd->bgrqt", qg.astype(cache_dt),
                         tk.astype(cache_dt),
                         preferred_element_type=jnp.float32) * scale
        s = jnp.concatenate(
            [s, jnp.where(live[:, None, None, None, :], s_t, _NEG)], -1)
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

