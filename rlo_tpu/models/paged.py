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

Three entry points mirror models.generate exactly (the layer math IS
apply_layer via the same attention-hook pattern, so paged decode can
never drift from dense decode by construction):

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

from typing import Optional

import jax
import jax.numpy as jnp

from rlo_tpu.models.generate import (_attend_cache_block, _decode_cfg,
                                     _quantize_kv)
from rlo_tpu.models.transformer import (TransformerConfig, apply_layer,
                                        embed_tokens, _rmsnorm)
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

    def g(x):                              # (P, kvh, hd, ps)
        got = x[table]                     # (b, mp, kvh, hd, ps)
        got = jnp.moveaxis(got, 1, 3)      # (b, kvh, hd, mp, ps)
        return got.reshape(b, x.shape[1], x.shape[2],
                           mp * x.shape[3])

    def gs(x):                             # (P, kvh, ps)
        got = x[table]                     # (b, mp, kvh, ps)
        got = jnp.moveaxis(got, 1, 2)      # (b, kvh, mp, ps)
        return got.reshape(b, x.shape[1], mp * x.shape[2])

    ks = gs(entry["ks"]) if "ks" in entry else None
    vs = gs(entry["vs"]) if "vs" in entry else None
    return g(entry["k"]), g(entry["v"]), ks, vs


def paged_write_rows(entry, k_row, v_row, ks_new, vs_new, page, off):
    """Write one (kvh, hd) K/V row per slot into its pool page:
    ``page``/``off`` are (b,) int32, row b lands at
    [page_b, :, :, off_b]. An off of page_size (the DROP sentinel —
    inactive or masked slots) drops the write entirely. Slots never
    share a writable page (the COW invariant), so the scatter indexes
    are disjoint."""
    ps = entry["k"].shape[3]
    kvh, hd = entry["k"].shape[1], entry["k"].shape[2]
    quant = ks_new is not None
    store_dt = entry["k"].dtype
    if kernel_gate(ps % 128 == 0, f"page row write (page_size={ps})"):
        from rlo_tpu.pallas.decode import write_kv_page_row
        kc = write_kv_page_row(entry["k"], k_row, page, off)
        vc = write_kv_page_row(entry["v"], v_row, page, off)
        out = {"k": kc, "v": vc}
        if quant:
            # sidecars (P, kvh, ps) ride the same kernel via the free
            # (P, kvh, 1, ps) view (the write_kv_row trick)
            out["ks"] = write_kv_page_row(
                entry["ks"][:, :, None, :], ks_new[:, :, None],
                page, off)[:, :, 0, :]
            out["vs"] = write_kv_page_row(
                entry["vs"][:, :, None, :], vs_new[:, :, None],
                page, off)[:, :, 0, :]
        return out
    heads = jnp.arange(kvh)[None, :, None]
    dims = jnp.arange(hd)[None, None, :]
    idx = (page[:, None, None], heads, dims, off[:, None, None])
    out = {"k": entry["k"].at[idx].set(k_row.astype(store_dt),
                                       mode="drop"),
           "v": entry["v"].at[idx].set(v_row.astype(store_dt),
                                       mode="drop")}
    if quant:
        sidx = (page[:, None], jnp.arange(kvh)[None, :],
                off[:, None])
        out["ks"] = entry["ks"].at[sidx].set(ks_new, mode="drop")
        out["vs"] = entry["vs"].at[sidx].set(vs_new, mode="drop")
    return out


def paged_write_chunk(entry, kt, vt, ks_new, vs_new, page, off0,
                      n_valid):
    """Write one slot's prefill chunk: ``kt``/``vt`` (kvh, hd, T)
    seq-minor, token t landing at [page, :, :, off0 + t] for
    t < n_valid (pads dropped). The chunk never crosses a page
    boundary (off0 + n_valid <= page_size, caller-scheduled), so ONE
    page takes every lane — which is what makes the aliased TPU block
    write legal (a single program owns the block)."""
    ps = entry["k"].shape[3]
    kvh = entry["k"].shape[1]
    T = kt.shape[2]
    store_dt = entry["k"].dtype
    quant = ks_new is not None
    if kernel_gate(ps % 128 == 0, f"page chunk write (page_size={ps})"):
        from rlo_tpu.pallas.decode import write_kv_page_block
        kc = write_kv_page_block(entry["k"], kt, page, off0, n_valid)
        vc = write_kv_page_block(entry["v"], vt, page, off0, n_valid)
        out = {"k": kc, "v": vc}
        if quant:
            out["ks"] = write_kv_page_block(
                entry["ks"][:, :, None, :], ks_new[:, None, :],
                page, off0, n_valid)[:, :, 0, :]
            out["vs"] = write_kv_page_block(
                entry["vs"][:, :, None, :], vs_new[:, None, :],
                page, off0, n_valid)[:, :, 0, :]
        return out
    # the scatter path: T updates into one page, pads dropped via the
    # page_size offset sentinel
    t = jnp.arange(T)
    offs = jnp.where(t < n_valid, off0 + t, ps)         # (T,)
    pagev = jnp.full((T,), page)
    heads = jnp.arange(kvh)[None, :, None]
    dims = jnp.arange(entry["k"].shape[2])[None, None, :]
    idx = (pagev[:, None, None], heads, dims, offs[:, None, None])
    krows = jnp.moveaxis(kt, 2, 0)                      # (T, kvh, hd)
    vrows = jnp.moveaxis(vt, 2, 0)
    out = {"k": entry["k"].at[idx].set(krows.astype(store_dt),
                                       mode="drop"),
           "v": entry["v"].at[idx].set(vrows.astype(store_dt),
                                       mode="drop")}
    if quant:
        sidx = (pagev[:, None], jnp.arange(kvh)[None, :],
                offs[:, None])
        out["ks"] = entry["ks"].at[sidx].set(
            jnp.moveaxis(ks_new, 1, 0), mode="drop")
        out["vs"] = entry["vs"].at[sidx].set(
            jnp.moveaxis(vs_new, 1, 0), mode="drop")
    return out


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
    dt = cfg.act_dtype
    posv = jnp.asarray(pos, jnp.int32)
    b = token.shape[0]
    ps = pools[0]["k"].shape[3]
    mp = table.shape[1]
    page_i = jnp.clip(posv // ps, 0, mp - 1)
    page = jnp.take_along_axis(table, page_i[:, None], axis=1)[:, 0]
    ok = active & (posv >= 0) & (posv < mp * ps)
    page = jnp.where(ok, page, 0)
    off = jnp.where(ok, posv % ps, ps)     # ps = the drop sentinel
    pos_arr = posv[:, None]
    x = embed_tokens(params["embed"], token[:, None], pos_arr, cfg)
    scale = 1.0 / (cfg.head_dim ** 0.5)
    new_pools = []
    for layer, lc in zip(params["layers"], pools):
        def attend(q, k, v, lc=lc):
            quant = "ks" in lc
            k_row, v_row = k[:, 0], v[:, 0]          # (b, kvh, hd)
            ks_new = vs_new = None
            if quant:
                k_row, ks_new = _quantize_kv(k_row)
                v_row, vs_new = _quantize_kv(v_row)
            entry = paged_write_rows(lc, k_row, v_row, ks_new,
                                     vs_new, page, off)
            new_pools.append(entry)
            return _paged_attend(q, entry, table, pos_arr,
                                 scale).astype(dt)

        x, _ = apply_layer(x, layer, cfg, attention=attend,
                           pos=pos_arr)
    x = _rmsnorm(x, params["ln_f"]["g"])
    logits = (x[:, 0, :] @ params["embed"].T.astype(dt)) \
        .astype(jnp.float32)
    return logits, new_pools


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
    dt = cfg.act_dtype
    b, T = tokens.shape
    ps = pools[0]["k"].shape[3]
    mp = table.shape[1]
    pos0 = jnp.asarray(pos0, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    page = table[0, jnp.clip(pos0 // ps, 0, mp - 1)]
    off0 = pos0 % ps
    pos_arr = pos0 + jnp.arange(T, dtype=jnp.int32)[None, :]  # (1, T)
    x = embed_tokens(params["embed"], tokens, pos_arr, cfg)
    scale = 1.0 / (cfg.head_dim ** 0.5)
    new_pools = []
    for layer, lc in zip(params["layers"], pools):
        def attend(q, k, v, lc=lc):
            quant = "ks" in lc
            kt = k[0].transpose(1, 2, 0)             # (kvh, hd, T)
            vt = v[0].transpose(1, 2, 0)
            ks_new = vs_new = None
            if quant:
                # quantize over hd per position BEFORE the seq-minor
                # flip (the block_decode ordering)
                kq, ks_new = _quantize_kv(k[0])      # (T, kvh, hd)
                vq, vs_new = _quantize_kv(v[0])
                kt = kq.transpose(1, 2, 0)
                vt = vq.transpose(1, 2, 0)
                ks_new = ks_new.transpose(1, 0)      # (kvh, T)
                vs_new = vs_new.transpose(1, 0)
            entry = paged_write_chunk(lc, kt, vt, ks_new, vs_new,
                                      page, off0, n_valid)
            new_pools.append(entry)
            return _paged_attend(q, entry, table, pos_arr,
                                 scale).astype(dt)

        x, _ = apply_layer(x, layer, cfg, attention=attend,
                           pos=pos_arr)
    x = _rmsnorm(x, params["ln_f"]["g"])
    idx = jnp.clip(n_valid - 1, 0, T - 1)[None, None, None]
    xl = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)[:, 0]
    logits = (xl @ params["embed"].T.astype(dt)).astype(jnp.float32)
    return logits, new_pools


def copy_page(pools, src, dst):
    """The COW primitive: dst := src across every layer's pools (K, V
    and the int8 scale sidecars). Jit with donated pools so the copy
    is in-place at the XLA level."""
    out = []
    for entry in pools:
        e = {"k": entry["k"].at[dst].set(entry["k"][src]),
             "v": entry["v"].at[dst].set(entry["v"][src])}
        if "ks" in entry:
            e["ks"] = entry["ks"].at[dst].set(entry["ks"][src])
            e["vs"] = entry["vs"].at[dst].set(entry["vs"][src])
        out.append(e)
    return out
