"""Device side of the paged KV cache (docs/DESIGN.md §12).

The dense serving cache (models.generate.init_kv_cache) allocates one
(max_len)-long seq-minor row per slot; this module replaces it with a
GLOBAL pool of ``page_size``-token pages per layer plus a per-slot
int32 page table, so slots only pin the pages their live prefix
actually spans and identical prompt prefixes can map the same physical
pages (rlo_tpu.serving.pages owns who-maps-what; this module only
moves bytes).

Layout: each layer's pool is (n_pages, kv_heads, head_dim, page_size)
in the activation dtype — a page IS one 128-lane block of the dense
seq-minor cache (the round-5 layout), so the pallas decode kernels
need only an index indirection, not a new tiling: logical tile ik of
slot b lives at physical page table[b, ik]. int8 caches carry
(n_pages, kv_heads, page_size) f32 scale sidecar pools at the same
page indexes.

Three entry points mirror models.generate. The two step functions run
models.generate's own layer loop and head (_forward, _head: embedding,
apply_layer with the attention hooked, final norm at cfg.norm_eps, the
tied or untied head) and take their new columns from models.kvcache, so
a model fact is stated once for the dense and the paged step; what is
theirs is the page arithmetic, the pool write and the table attend.
(Through PR 28 they carried copies of that loop, which had drifted: a
fixed norm eps, the tied head, 1/sqrt(head_dim) — tests/test_kvcache.py
holds the two paths together at a configuration where those differ.)

  - ``paged_decode_step``: one token per slot through all layers;
    writes go to page table[b, pos_b // ps] (inactive slots write
    nothing: the offset sentinel drops the scatter), attends gather
    through the table.
  - ``paged_prefill_chunk``: ≤ page_size prompt tokens of ONE slot in
    one forward (the chunked-prefill unit — a chunk never crosses a
    page boundary, so its writes touch exactly one page).
  - ``copy_page``: the COW primitive (dst := src across every layer's
    pools).

On TPU the attends run through ``pallas.decode.paged_flash_decode``
(page-table scalar prefetch; cache HBM traffic = the live pages'
stored bytes) and the writes through the aliased page-write kernels;
everywhere else a gather + the einsum block attend keeps the numerics
in the exact class of the dense path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from rlo_tpu.models.generate import (_cache_hook, _decode_cfg, _forward,
                                     _head)
from rlo_tpu.models.kvcache import (_attend_cache_block, _on_axis,
                                    _tensors, _through_hd_view,
                                    new_block, new_row)
from rlo_tpu.models.transformer import TransformerConfig
from rlo_tpu.pallas.reduce import kernel_gate


def init_page_pool(cfg: TransformerConfig, n_pages: int,
                   page_size: int):
    """Zeroed per-layer page pools: a list of {"k","v"} arrays shaped
    (n_pages, kv_heads, head_dim, page_size) — the dense cache's
    seq-minor layout with the sequence axis cut into pages. Page 0 is
    the reserved null page (pages.NULL_PAGE). On TPU the page size
    must be a 128-lane multiple so a page is a legal cache block.
    ``cfg.kv_cache_dtype='int8'`` adds (n_pages, kv_heads, page_size)
    f32 scale sidecars at the same page indexes."""
    if cfg.hc or cfg.index_types is not None:
        raise ValueError("a page pool carries one residual's per-head K/V: "
                         "hyper-connections and a shared token selection "
                         "run on the dense cache so far")
    # rlo-prover: lane-pinned (a page IS one 128-lane cache block)
    if jax.default_backend() == "tpu" and page_size % 128:
        raise ValueError(
            f"TPU pages must be 128-lane multiples, got {page_size}")
    shape = (n_pages, cfg.kv_heads, cfg.head_dim, page_size)
    sshape = (n_pages, cfg.kv_heads, page_size)
    if cfg.kv_cache_dtype == "int8":
        return [{"k": jnp.zeros(shape, jnp.int8),
                 "v": jnp.zeros(shape, jnp.int8),
                 "ks": jnp.zeros(sshape, jnp.float32),
                 "vs": jnp.zeros(sshape, jnp.float32)}
                for _ in range(cfg.n_layers)]
    if cfg.kv_cache_dtype is not None:
        raise ValueError(
            f"unknown kv_cache_dtype {cfg.kv_cache_dtype!r}")
    return [{"k": jnp.zeros(shape, cfg.act_dtype),
             "v": jnp.zeros(shape, cfg.act_dtype)}
            for _ in range(cfg.n_layers)]


def paged_view(entry, table):
    """Gather a layer's logical per-slot caches out of its pool:
    ``table`` (b, mp) int32 -> (k, v, ks, vs) where k/v are
    (b, kv_heads, head_dim, mp*page_size) — the dense attend layout —
    and ks/vs are the matching scale views (None for plain caches).
    Unmapped table entries point at the null page (zeros)."""
    b, mp = table.shape

    def g(x):                              # (P, kvh, [hd,] ps)
        got = x[table]                     # (b, mp, kvh, [hd,] ps)
        got = jnp.moveaxis(got, 1, -2)     # (b, kvh, [hd,] mp, ps)
        return got.reshape(b, *x.shape[1:-1], mp * x.shape[-1])

    ks = g(entry["ks"]) if "ks" in entry else None
    vs = g(entry["vs"]) if "vs" in entry else None
    return g(entry["k"]), g(entry["v"]), ks, vs


def _scatter_lanes(entry, new, page, lane):
    """The XLA arm of both page writes: update i of ``new[name]``
    (n, kv_heads[, head_dim]) lands at [page_i, :, :, lane_i] of the
    tensor's pool; a lane of page_size (the DROP sentinel) drops it.
    Tensors of one rank share the index arrays."""
    out, index = {}, {}
    for name, pool in _tensors(entry):
        rank = pool.ndim - 1
        if rank not in index:
            heads_dims = tuple(
                _on_axis(jnp.arange(n), axis, rank)
                for axis, n in enumerate(pool.shape[1:-1], 1))
            index[rank] = ((_on_axis(page, 0, rank),) + heads_dims
                           + (_on_axis(lane, 0, rank),))
        out[name] = pool.at[index[rank]].set(
            new[name].astype(pool.dtype), mode="drop")
    return out


def paged_write_rows(entry, row, page, off):
    """Write one new row per slot (models.kvcache.new_row's, by tensor
    name) into its pool page: ``page``/``off`` are (b,) int32, row b
    lands at [page_b, :, :, off_b]. An off of page_size (the DROP
    sentinel — inactive or masked slots) drops the write entirely.
    Slots never share a writable page (the COW invariant), so the
    scatter indexes are disjoint."""
    ps = entry["k"].shape[3]
    if kernel_gate(ps % 128 == 0, f"page row write (page_size={ps})"):
        from rlo_tpu.pallas.decode import write_kv_page_row
        return {name: _through_hd_view(write_kv_page_row, pool,
                                       row[name], page, off)
                for name, pool in _tensors(entry)}
    return _scatter_lanes(entry, row, page, off)


def paged_write_chunk(entry, block, page, off0, n_valid):
    """Write one slot's prefill chunk: ``block`` (models.kvcache.
    new_block's at batch 1, by tensor name: (1, kvh, hd, T) seq-minor),
    token t landing at [page, :, :, off0 + t] for t < n_valid (pads
    dropped). The chunk never crosses a page boundary (off0 + n_valid
    <= page_size, caller-scheduled), so ONE page takes every lane —
    which is what makes the aliased TPU block write legal (a single
    program owns the block)."""
    ps = entry["k"].shape[3]
    if kernel_gate(ps % 128 == 0, f"page chunk write (page_size={ps})"):
        from rlo_tpu.pallas.decode import write_kv_page_block
        return {name: _through_hd_view(write_kv_page_block, pool,
                                       block[name][0], page, off0,
                                       n_valid, axis=1)
                for name, pool in _tensors(entry)}
    # the scatter path: T updates into one page, pads dropped via the
    # page_size offset sentinel
    t = jnp.arange(block["k"].shape[3])
    offs = jnp.where(t < n_valid, off0 + t, ps)             # (T,)
    return _scatter_lanes(
        entry, {name: jnp.moveaxis(x[0], -1, 0)             # token-major
                for name, x in block.items()},
        jnp.full(t.shape, page), offs)


def _paged_attend(q, entry, table, pos_q, scale):
    """q (b, T, nh, hd) against the table-mapped pages: query i of row
    b sits at position pos_q[b, i] and attends positions <= it
    (write-then-attend, exactly like the dense block attend). TPU
    takes the page-prefetch flash kernel; everywhere else the gather +
    einsum block attend (the dense path's own fallback, so numerics
    stay in one class)."""
    ps = entry["k"].shape[3]
    d = q.shape[3]
    from rlo_tpu.pallas.decode import can_paged_flash
    if kernel_gate(can_paged_flash(ps, d),
                   f"paged attend (page_size={ps}, head_dim={d})"):
        from rlo_tpu.pallas.decode import paged_flash_decode
        # contiguous per-row positions: pos0 = first query position
        return paged_flash_decode(
            q, entry["k"], entry["v"], table, pos_q[:, 0], scale,
            entry.get("ks"), entry.get("vs"))
    kg, vg, ksg, vsg = paged_view(entry, table)
    return _attend_cache_block(q, kg, vg, pos_q, scale, k_scale=ksg,
                               v_scale=vsg, use_flash=False)


def paged_decode_step(params: dict, token, pos, pools, table, active,
                      cfg: TransformerConfig):
    """One token (b,) int32 per slot at per-slot positions ``pos``
    (b,) through all layers over the paged pool. ``table`` (b, mp)
    int32 maps logical page i of slot b to its physical page;
    ``active`` (b,) bool gates the cache writes (inactive slots — mid
    prefill, retired, idle — compute garbage that is never written or
    read, the dense server's masked-row discipline). Returns (logits
    (b, vocab) f32, new pools). The layer math IS apply_layer with the
    cache attend swapped in — the same single-source structure as
    models.generate.decode_step."""
    cfg = _decode_cfg(cfg)
    posv = jnp.asarray(pos, jnp.int32)
    ps = pools[0]["k"].shape[3]
    mp = table.shape[1]
    page_i = jnp.clip(posv // ps, 0, mp - 1)
    page = jnp.take_along_axis(table, page_i[:, None], axis=1)[:, 0]
    ok = active & (posv >= 0) & (posv < mp * ps)
    page = jnp.where(ok, page, 0)
    off = jnp.where(ok, posv % ps, ps)     # ps = the drop sentinel
    pos_arr = posv[:, None]

    def step(lc, k, v, index=None):
        entry = paged_write_rows(lc, new_row(lc, k, v), page, off)
        return entry, lambda q: _paged_attend(q, entry, table, pos_arr,
                                              cfg.attn_scale)

    x, new_pools = _forward(params, token[:, None], pos_arr, pools, cfg,
                            _cache_hook(cfg, step))
    return _head(params, x, cfg, 0), new_pools


def paged_prefill_chunk(params: dict, tokens, pos0, n_valid, pools,
                        table, cfg: TransformerConfig):
    """One slot's prompt chunk in one forward: ``tokens`` (1, T) int32
    (pad beyond ``n_valid`` with any valid id), token i at position
    pos0 + i, K/V written to page table[0, pos0 // ps] for the first
    ``n_valid`` tokens only. The chunk must not cross a page boundary:
    pos0 % page_size + n_valid <= page_size (the server schedules
    page-aligned chunks). Returns (logits at position
    pos0 + n_valid - 1, new pools) — the final chunk's logits seed the
    first generated token, earlier chunks' are discarded.

    Queries attend the table-mapped prefix [0, pos0 + i]: earlier
    chunks' pages (shared prefix pages included) plus this chunk's own
    just-written rows — write-then-attend, so in-chunk causality rides
    the same mask as models.generate.block_decode. MoE configs route
    drop-free (pads must be inert), the ragged-prefill rule."""
    cfg = _decode_cfg(cfg)
    T = tokens.shape[1]
    ps = pools[0]["k"].shape[3]
    mp = table.shape[1]
    pos0 = jnp.asarray(pos0, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    page = table[0, jnp.clip(pos0 // ps, 0, mp - 1)]
    off0 = pos0 % ps
    pos_arr = pos0 + jnp.arange(T, dtype=jnp.int32)[None, :]  # (1, T)

    def step(lc, k, v, index=None):
        entry = paged_write_chunk(lc, new_block(lc, k, v), page, off0,
                                  n_valid)
        return entry, lambda q: _paged_attend(q, entry, table, pos_arr,
                                              cfg.attn_scale)

    x, new_pools = _forward(params, tokens, pos_arr, pools, cfg,
                            _cache_hook(cfg, step))
    return _head(params, x, cfg,
                 jnp.clip(n_valid - 1, 0, T - 1)), new_pools


def copy_page(pools, src, dst):
    """The COW primitive: dst := src across every layer's pools (K, V
    and the int8 scale sidecars). Jit with donated pools so the copy
    is in-place at the XLA level."""
    return [{name: pool.at[dst].set(pool[src])
             for name, pool in _tensors(entry)} for entry in pools]
