"""KV-cache autoregressive generation for the flagship transformer.

The training side (models.transformer) recomputes full attention every
step; generation wants O(1) work per new token: each layer's keys and
values are cached HEAD-LEADING, SEQ-MINOR at (batch, kv_heads,
head_dim, max_len) — kv_heads < n_heads for GQA configs, and the
sequence-minor trailing dim streams HBM tiles at full 128-lane width
(see init_kv_cache; head_dim-minor measured half the bandwidth) — and
a decode step attends the
single new query against the cache prefix (grouped, never repeated).
Shapes stay STATIC (the cache is allocated at max_len up front and
masked by the traced position) so the whole generate loop is one
`lax.scan` inside one jit — XLA-friendly control flow, no per-token
retrace.

Scope: dense and MoE decode, single-device or tensor-parallel
(decode_step/generate take tp_axis inside shard_map: sharded params
per param_pspecs, sharded cache per kv_cache_pspecs; MoE experts can
shard over ep_axis). Sampling is greedy or temperature-softmax. The
math mirrors apply_layer exactly — rmsnorm/qkv/attention/wo/ffn with
the same weights — pinned by a logits-parity test against the training
`forward` at every generated position (tests/test_generate.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from rlo_tpu.models.transformer import (TransformerConfig, apply_layer,
                                        embed_tokens, head_weights,
                                        mla_unabsorbed, _rmsnorm)
from rlo_tpu.ops.ring_attention import _NEG
from rlo_tpu.pallas.reduce import KernelFallbackWarning, kernel_gate


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
    if any("ks" in lc for lc in cache):
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


def _decode_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """Decode-time config: MoE routing is DROP-FREE (capacity >= the
    tokens in one step). Training-time capacity dropping is inherently
    order-dependent across the flattened token axis (moe.moe_ffn's
    cumsum queue), i.e. not causal — so decode routes every token to
    its argmax expert and parity with the training forward holds
    exactly when the forward drops nothing (capacity_factor >=
    n_experts guarantees that)."""
    if cfg.n_experts == 0 or cfg.moe_router != "switch":
        return cfg      # no experts, or a router that never drops
    return dataclasses.replace(
        cfg, capacity_factor=max(cfg.capacity_factor,
                                 float(cfg.n_experts)))


def _attend_cache(q, k_cache, v_cache, pos, scale,
                  k_scale=None, v_scale=None, use_flash=None,
                  v_dim: int = 0, tail=None):
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
    per-step write drops them."""
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
                            k_scale, v_scale, v_dim=v_dim, tail=tail)
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
                        use_flash=None, v_dim: int = 0, tail=None):
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
    tail's scores join the cache's before the softmax."""
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
                                  k_scale, v_scale, v_dim=v_dim)
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


def _mla_absorbed(q_nope, q_rope, layer, cfg, attend):
    """Latent attention against the cache in the absorbed form: each
    head's query is carried into the latent space (q_nope W_uk^T beside
    the rotated dims), ``attend`` (q (b, T, H, kv_lora + rope)) ->
    (b, T, H, kv_lora) attends the latent rows as keys and, in their
    leading kv_lora features, as values, and W_uv brings the result
    out: no per-head key or value is ever formed."""
    dt = q_nope.dtype
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bthn,chn->bthc", q_nope,
                           layer["wuk"].astype(dt))
        q = jnp.concatenate([q_lat, q_rope], -1)
    with jax.named_scope("mla.attend"):
        o_lat = attend(q).astype(dt)
    with jax.named_scope("mla.v_up"):
        return jnp.einsum("bthc,chv->bthv", o_lat,
                          layer["wuv"].astype(dt))


def decode_step(params: dict, token, pos, cache, cfg: TransformerConfig,
                tp_axis: Optional[str] = None,
                ep_axis: Optional[str] = None,
                moe_info: Optional[list] = None,
                tail: Optional[tuple] = None
                ) -> Tuple[jax.Array, list]:
    """One token (b,) int32 at position ``pos`` through all layers
    using the K/V cache. Returns (logits (b, vocab) f32, new cache).
    The layer math IS apply_layer (single source); only the attention
    is swapped for the cache-attend via its ``attention`` hook.

    ``pos`` is a scalar (every row at the same position) or a (b,)
    int32 vector — RAGGED decode: each row writes its cache slot and
    masks its attention at its own position (per-row rotary/sincos
    positions included).

    ``tp_axis`` (inside shard_map): tensor-parallel decode — params
    arrive sharded per param_pspecs, the cache per kv_cache_pspecs;
    each shard attends its local (kv-)heads and the row-parallel
    output projections combine with the framework allreduce, exactly
    like training. MoE configs route drop-free (see _decode_cfg);
    ``ep_axis`` shards the experts with all_to_all dispatch.

    Latent attention (``cfg.mla``): the cache holds one latent row a
    token and layer; the step writes it (write_kv_row, as ever) and
    attends in the absorbed form (_mla_absorbed). ``moe_info``: see
    apply_layer.

    ``tail`` ``(rows, newest)``: step ``newest`` of a loop that keeps
    its new K/V rows in a write-behind tail (init_kv_tail; the loop
    began at position pos - newest). The step stores its rows at
    [newest] of each layer's tail, attends the cache up to where the
    loop began and tail rows 0..newest (same keys, values and
    positions as write-then-attend: _attend_cache), and returns
    (logits, new tail rows); the cache is read, not written."""
    cfg = _decode_cfg(cfg)
    dt = cfg.act_dtype
    posv = jnp.asarray(pos)
    ragged = posv.ndim == 1
    b = token.shape[0]
    if tail is not None:
        tail_rows, newest = tail
        newest = jnp.asarray(newest, jnp.int32)
        before = posv - newest - 1      # the last position the cache holds

        def store(rows, row):           # at [newest], in the cache's dtype
            return lax.dynamic_update_slice(
                rows, row[None].astype(rows.dtype), (newest, 0, 0, 0))
    else:
        tail_rows = [None] * len(cache)
    # (1,) shared positions, or (b, 1) per-row, for embed/rope
    pos_arr = posv[:, None] if ragged else posv[None]
    x = embed_tokens(params["embed"], token[:, None], pos_arr, cfg)
    scale = cfg.attn_scale
    new_cache = []
    for layer, lc, tl in zip(params["layers"], cache, tail_rows):
        def attend_latent(q_nope, q_rope, latent, lc=lc, layer=layer,
                          tl=tl):
            from rlo_tpu.pallas.decode import can_write_row, write_kv_row
            row = latent[:, 0][:, None, :]           # (b, 1, width)
            if tl is not None:
                tk = store(tl["k"], row)
                new_cache.append({"k": tk})
                return _mla_absorbed(
                    q_nope, q_rope, layer, cfg, lambda q: _attend_cache(
                        q, lc["k"], None, before, scale,
                        v_dim=cfg.kv_lora_rank, tail=(tk, None, newest)))
            max_len_c = lc["k"].shape[3]
            if kernel_gate(can_write_row(max_len_c),
                           f"cache row write (max_len={max_len_c})"):
                kc = write_kv_row(lc["k"], row, posv)
            elif ragged:
                idx = (jnp.arange(b)[:, None], 0,
                       jnp.arange(row.shape[2])[None, :], posv[:, None])
                kc = lc["k"].at[idx].set(row[:, 0].astype(dt))
            else:
                kc = lax.dynamic_update_slice(
                    lc["k"], row[..., None].astype(dt), (0, 0, 0, pos))
            new_cache.append({"k": kc})
            return _mla_absorbed(
                q_nope, q_rope, layer, cfg, lambda q: _attend_cache(
                    q, kc, None, posv, scale, v_dim=cfg.kv_lora_rank))

        def attend(q, k, v, lc=lc, tl=tl):
            # rope configs: q/k arrive rotated from apply_layer; keys
            # are cached rotated (standard RoPE decode). k/v arrive
            # (b, 1, kvh, hd); the cache is head-leading — transpose
            # the new entry to (b, kvh, hd) rows
            quant = "ks" in lc
            k_row, v_row = k[:, 0], v[:, 0]          # (b, kvh, hd)
            if tl is not None:
                tk, tv = store(tl["k"], k_row), store(tl["v"], v_row)
                new_cache.append({"k": tk, "v": tv})
                return _attend_cache(q, lc["k"], lc["v"], before, scale,
                                     tail=(tk, tv, newest)).astype(dt)
            if quant:  # int8 cache: quantize the new entry at append
                k_row, ks_new = _quantize_kv(k_row)
                v_row, vs_new = _quantize_kv(v_row)
                store_dt = jnp.int8
            else:
                store_dt = dt
            from rlo_tpu.pallas.decode import (can_write_row,
                                               write_kv_row)
            max_len_c = lc["k"].shape[3]
            use_wr = kernel_gate(can_write_row(max_len_c),
                                 f"cache row write (max_len={max_len_c})")
            if use_wr:
                # aliased pallas write: an XLA lane-offset DUS makes
                # layout assignment transpose the cache and copy it
                # back for the flash kernel every step. Still a whole
                # 128-lane block a row and call (see write_kv_row):
                # a loop that owns its steps passes ``tail``
                kc = write_kv_row(lc["k"], k_row, posv)
                vc = write_kv_row(lc["v"], v_row, posv)
            elif ragged:
                rows = jnp.arange(b)
                heads = jnp.arange(lc["k"].shape[1])
                # seq-minor: the new row lands in ONE lane per
                # (b, head, dim) — idx over the last axis
                dims = jnp.arange(lc["k"].shape[2])
                idx = (rows[:, None, None], heads[None, :, None],
                       dims[None, None, :], posv[:, None, None])
                kc = lc["k"].at[idx].set(k_row.astype(store_dt))
                vc = lc["v"].at[idx].set(v_row.astype(store_dt))
            else:
                kc = lax.dynamic_update_slice(
                    lc["k"], k_row[..., None].astype(store_dt),
                    (0, 0, 0, pos))
                vc = lax.dynamic_update_slice(
                    lc["v"], v_row[..., None].astype(store_dt),
                    (0, 0, 0, pos))
            entry = {"k": kc, "v": vc}
            ks = vs = None
            if quant:
                if use_wr:
                    # the scale sidecars are seq-minor too — a lane-
                    # offset DUS would reintroduce the layout-war
                    # copies; view (b, kvh, L) as (b, kvh, 1, L) (a
                    # free reshape) and ride the same aliased kernel
                    ks = write_kv_row(lc["ks"][:, :, None, :],
                                      ks_new[:, :, None],
                                      posv)[:, :, 0, :]
                    vs = write_kv_row(lc["vs"][:, :, None, :],
                                      vs_new[:, :, None],
                                      posv)[:, :, 0, :]
                elif ragged:
                    rows = jnp.arange(b)
                    heads = jnp.arange(lc["k"].shape[1])
                    sidx = (rows[:, None], heads[None, :],
                            posv[:, None])
                    ks = lc["ks"].at[sidx].set(ks_new)
                    vs = lc["vs"].at[sidx].set(vs_new)
                else:
                    ks = lax.dynamic_update_slice(
                        lc["ks"], ks_new[:, :, None], (0, 0, pos))
                    vs = lax.dynamic_update_slice(
                        lc["vs"], vs_new[:, :, None], (0, 0, pos))
                entry.update(ks=ks, vs=vs)
            new_cache.append(entry)
            return _attend_cache(q, kc, vc, posv, scale,
                                 k_scale=ks, v_scale=vs).astype(dt)

        x, _ = apply_layer(x, layer, cfg,
                           attention=attend_latent if cfg.mla else attend,
                           tp_axis=tp_axis, ep_axis=ep_axis,
                           pos=pos_arr, moe_info=moe_info)
    x = _rmsnorm(x, params["ln_f"]["g"], cfg.norm_eps)
    logits = (x[:, 0, :] @ head_weights(params).T.astype(dt)) \
        .astype(jnp.float32)
    return logits, new_cache


def block_decode(params: dict, tokens, pos0, cache,
                 cfg: TransformerConfig,
                 tp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None):
    """Process T tokens (b, T) through the cache in ONE forward: row
    b's token i sits at position pos0[b] + i. Returns
    (logits (b, T, vocab) f32, cache). The verify step of speculative
    decoding (the target judges all gamma draft tokens at once); also
    a building block for chunked cache extension. Write-then-attend
    with per-(row, i) masks, so rejected drafts' cache entries are
    simply garbage beyond the accepted position — masked out and
    overwritten by later writes, exactly like ragged decode."""
    cfg = _decode_cfg(cfg)
    dt = cfg.act_dtype
    b, T = tokens.shape
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(b)
    pos_arr = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)  # (b, T)
    x = embed_tokens(params["embed"], tokens, pos_arr, cfg)
    scale = cfg.attn_scale
    new_cache = []
    for layer, lc in zip(params["layers"], cache):
        def attend_latent(q_nope, q_rope, latent, lc=lc, layer=layer):
            from rlo_tpu.pallas.decode import (can_write_block,
                                               write_kv_block)
            rows = latent.transpose(0, 2, 1)[:, None]  # (b, 1, width, T)
            L = lc["k"].shape[3]
            if kernel_gate(can_write_block(L) and T <= 128,
                           f"cache block write (max_len={L}, T={T})"):
                kc = write_kv_block(lc["k"], rows.astype(dt), pos0)
            else:
                kc = lc["k"].at[
                    jnp.arange(b)[:, None, None], 0,
                    jnp.arange(rows.shape[2])[None, :, None],
                    pos_arr[:, None, :]].set(rows[:, 0].astype(dt))
            new_cache.append({"k": kc})
            return _mla_absorbed(
                q_nope, q_rope, layer, cfg,
                lambda q: _attend_cache_block(
                    q, kc, None, pos_arr, scale, pos0=pos0,
                    v_dim=cfg.kv_lora_rank))

        def attend(q, k, v, lc=lc):
            quant = "ks" in lc
            kt = k.transpose(0, 2, 1, 3)           # (b, kvh, T, hd)
            vt = v.transpose(0, 2, 1, 3)
            if quant:  # quantize over hd BEFORE the seq-minor flip
                kt, ks_new = _quantize_kv(kt)
                vt, vs_new = _quantize_kv(vt)
                store_dt = jnp.int8
            else:
                store_dt = dt
            kt = kt.transpose(0, 1, 3, 2)          # (b, kvh, hd, T)
            vt = vt.transpose(0, 1, 3, 2)
            kvh = lc["k"].shape[1]
            from rlo_tpu.pallas.decode import (can_write_block,
                                               write_kv_block)
            use_wb = kernel_gate(
                can_write_block(lc["k"].shape[3]) and T <= 128,
                f"cache block write (max_len={lc['k'].shape[3]}, "
                f"T={T})")
            if use_wb:
                # the XLA lane-index scatter lowers to a generic
                # scatter measured ~1.2 ms PER VERIFY at batch 1
                # (block_decode 1.65 ms vs 0.46 ms decode step) —
                # the aliased pallas block write replaces it
                kc = write_kv_block(lc["k"], kt.astype(store_dt),
                                    pos0)
                vc = write_kv_block(lc["v"], vt.astype(store_dt),
                                    pos0)
            else:
                rows = jnp.arange(b)[:, None, None, None]
                heads = jnp.arange(kvh)[None, :, None, None]
                dims = jnp.arange(lc["k"].shape[2])[None, None, :,
                                                    None]
                posw = pos_arr[:, None, None, :]   # (b, 1, 1, T)
                kc = lc["k"].at[rows, heads, dims, posw].set(
                    kt.astype(store_dt))
                vc = lc["v"].at[rows, heads, dims, posw].set(
                    vt.astype(store_dt))
            entry = {"k": kc, "v": vc}
            ks = vs = None
            if quant:
                if use_wb:
                    # sidecars (b, kvh, L) ride the same kernel via
                    # the free (b, kvh, 1, L) view
                    ks = write_kv_block(lc["ks"][:, :, None, :],
                                        ks_new[:, :, None, :],
                                        pos0)[:, :, 0, :]
                    vs = write_kv_block(lc["vs"][:, :, None, :],
                                        vs_new[:, :, None, :],
                                        pos0)[:, :, 0, :]
                else:
                    # scale sidecars stay (b, kvh, L): 3-D scatter
                    r3 = jnp.arange(b)[:, None, None]
                    h3 = jnp.arange(kvh)[None, :, None]
                    p3 = pos_arr[:, None, :]       # (b, 1, T)
                    ks = lc["ks"].at[r3, h3, p3].set(ks_new)
                    vs = lc["vs"].at[r3, h3, p3].set(vs_new)
                entry.update(ks=ks, vs=vs)
            new_cache.append(entry)
            return _attend_cache_block(q, kc, vc, pos_arr, scale,
                                       k_scale=ks, v_scale=vs,
                                       pos0=pos0).astype(dt)

        x, _ = apply_layer(x, layer, cfg,
                           attention=attend_latent if cfg.mla else attend,
                           tp_axis=tp_axis, ep_axis=ep_axis,
                           pos=pos_arr)
    x = _rmsnorm(x, params["ln_f"]["g"], cfg.norm_eps)
    logits = jnp.einsum("btd,vd->btv", x,
                        head_weights(params).astype(dt)
                        ).astype(jnp.float32)
    return logits, new_cache


def prefill(params: dict, tokens, cache, cfg: TransformerConfig,
            tp_axis: Optional[str] = None,
            ep_axis: Optional[str] = None,
            last_index=None, moe_info: Optional[list] = None):
    """Fill the cache with the whole prompt in ONE forward pass.
    Returns (logits of the last prompt position, filled cache).
    ``last_index`` (b,) selects a PER-ROW logits position instead of
    the final one (ragged prompts: row i's prompt ends at
    last_index[i]; positions beyond it hold padding whose cache
    entries are never attended — decode masks at the row's own
    position and overwrites them in order).
    MoE prompts route with the TRAINING capacity semantics (the whole
    prompt is one token set — exact forward parity); decode steps then
    route drop-free (_decode_cfg). RAGGED MoE prompts instead route
    DROP-FREE too: the training-capacity cumsum queue runs over the
    whole flattened padded token set, so padding would consume expert
    capacity and displace real tokens — drop-free routing makes
    padding inert, and per-row parity with the dense generate then
    holds exactly when the dense forward drops nothing (the same
    capacity_factor >= n_experts condition as decode).

    The prompt is a causal prefix, so causal attention over the prompt
    block IS attention against the (empty-beyond-it) cache — one
    batched forward through the flash kernel (apply_layer's training
    dispatch) replaces plen serial decode steps. The attention hook
    stashes each layer's COMPACT K/V block into the cache on the way
    through (rope keys are cached rotated, exactly like decode_step).
    Logits-parity with the one-token-at-a-time scan is pinned in
    tests/test_generate.py (exactly for plain caches; quantized
    caches attend the DEQUANTIZED block — the same values decode
    reads back — so the parity is within matmul association error,
    not the quantization envelope); measured ~two orders of magnitude
    faster at plen 1024 on the v5e chip (decode_bench.py --ttft).

    Latent attention (``cfg.mla``): the block is attended in the plain
    form (every head's keys and values decompressed from the latent:
    mla_unabsorbed) and the hook stashes the LATENT rows, the same rows
    decode_step writes and then attends absorbed. ``moe_info``: see
    apply_layer.
    """
    b, plen = tokens.shape
    if last_index is not None:
        cfg = _decode_cfg(cfg)  # ragged MoE: padding must be inert
    dt = cfg.act_dtype
    pos = jnp.arange(plen)
    x = embed_tokens(params["embed"], tokens, pos, cfg)
    new_cache = []
    for layer, lc in zip(params["layers"], cache):
        def attend_latent(q_nope, q_rope, latent, lc=lc, layer=layer):
            new_cache.append({"k": lax.dynamic_update_slice(
                lc["k"], latent.transpose(0, 2, 1)[:, None].astype(dt),
                (0, 0, 0, 0))})
            return mla_unabsorbed(q_nope, q_rope, latent, layer, cfg)

        def attend(q, k, v, lc=lc):
            # k/v arrive (b, plen, kvh, hd); the cache is head-leading
            # and SEQ-MINOR: (b, kvh, hd, plen)
            kt = k.transpose(0, 2, 1, 3)             # (b, kvh, plen, hd)
            vt = v.transpose(0, 2, 1, 3)
            if "ks" in lc:  # int8 cache: quantize the whole block
                qk, ks = _quantize_kv(kt)
                qv, vs = _quantize_kv(vt)
                new_cache.append({
                    "k": lax.dynamic_update_slice(
                        lc["k"], qk.transpose(0, 1, 3, 2),
                        (0, 0, 0, 0)),
                    "v": lax.dynamic_update_slice(
                        lc["v"], qv.transpose(0, 1, 3, 2),
                        (0, 0, 0, 0)),
                    "ks": lax.dynamic_update_slice(lc["ks"], ks,
                                                   (0, 0, 0)),
                    "vs": lax.dynamic_update_slice(lc["vs"], vs,
                                                   (0, 0, 0))})
                # attend the DEQUANTIZED block: the prompt K/V the
                # prefill logits see must be the values decode will
                # read back from the cache, or the blockwise prefill
                # and the decode-step scan diverge by the quantization
                # envelope on quantized configs
                k = (qk.astype(jnp.float32) * ks[..., None]) \
                    .transpose(0, 2, 1, 3).astype(dt)
                v = (qv.astype(jnp.float32) * vs[..., None]) \
                    .transpose(0, 2, 1, 3).astype(dt)
            else:
                new_cache.append({
                    "k": lax.dynamic_update_slice(
                        lc["k"], kt.transpose(0, 1, 3, 2).astype(dt),
                        (0, 0, 0, 0)),
                    "v": lax.dynamic_update_slice(
                        lc["v"], vt.transpose(0, 1, 3, 2).astype(dt),
                        (0, 0, 0, 0))})
            from rlo_tpu.models.transformer import _local_attention
            return _local_attention(q, k, v).astype(dt)

        x, _ = apply_layer(x, layer, cfg,
                           attention=attend_latent if cfg.mla else attend,
                           tp_axis=tp_axis, ep_axis=ep_axis, pos=pos,
                           moe_info=moe_info)
    x = _rmsnorm(x, params["ln_f"]["g"], cfg.norm_eps)
    if last_index is None:
        xl = x[:, -1, :]
    else:
        idx = jnp.asarray(last_index, jnp.int32)[:, None, None]
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)[:, 0]
    logits = (xl @ head_weights(params).T.astype(dt)).astype(jnp.float32)
    return logits, new_cache


def prefill_scan(params: dict, tokens, cache, cfg: TransformerConfig,
                 tp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None):
    """One-token-at-a-time prefill (scan over decode_step) — the
    parity oracle for `prefill` and a fallback exercising exactly the
    decode path."""
    b, plen = tokens.shape

    def step(carry, t):
        cache, pos, _ = carry
        logits, cache = decode_step(params, t, pos, cache, cfg,
                                    tp_axis=tp_axis, ep_axis=ep_axis)
        return (cache, pos + 1, logits), None

    z = jnp.zeros((b, cfg.vocab), jnp.float32)
    (cache, _, logits), _ = lax.scan(step, (cache, 0, z),
                                     jnp.transpose(tokens))
    return logits, cache


def generate(params: dict, prompt, cfg: TransformerConfig, *,
             max_new: int, max_len: Optional[int] = None,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None,
             tp_axis: Optional[str] = None,
             ep_axis: Optional[str] = None,
             prompt_lengths=None):
    """Autoregressive continuation of ``prompt`` (b, plen) int32:
    returns (b, max_new) int32 new tokens. temperature 0 = greedy;
    > 0 samples from softmax(logits/T) (needs ``rng``). Jittable as a
    whole (static shapes; one lax.scan over the new positions).
    With ``tp_axis`` (inside shard_map): tensor-parallel decode over
    sharded params + cache (see decode_step).

    ``prompt_lengths`` (b,) int32 enables RAGGED prompts (the serving
    shape: one batch, different prompt lengths): row i's prompt is
    prompt[i, :prompt_lengths[i]], the rest is padding (any valid
    token id). Row i's continuation starts right after its own last
    prompt token — per-row positions, cache slots, and attention
    masks throughout — and equals the dense generate of the truncated
    row exactly (the padded positions' cache entries are never
    attended: decode masks at the row's position and overwrites them
    in order). MoE configs: the ragged prefill routes drop-free so
    padding cannot consume expert capacity (see prefill); per-row
    parity then holds under the same capacity_factor >= n_experts
    condition as MoE decode."""
    logits, cache, pos0 = _generate_prefill(
        params, prompt, cfg, max_new=max_new, max_len=max_len,
        temperature=temperature, rng=rng, tp_axis=tp_axis,
        ep_axis=ep_axis, prompt_lengths=prompt_lengths)
    keys = (jax.random.split(rng, max_new) if rng is not None
            else jnp.zeros((max_new, 2), jnp.uint32))
    return _generate_decode(params, logits, cache, pos0, cfg, keys,
                            temperature, tp_axis, ep_axis)


def _generate_prefill(params, prompt, cfg, *, max_new, max_len,
                      temperature, rng, tp_axis, ep_axis,
                      prompt_lengths):
    """generate()'s argument checks + cache init + prefill; returns
    (logits, cache, pos0). Shared with generate_timed so the timed
    variant can never drift from the jitted one."""
    b, plen = prompt.shape
    max_len = max_len or (plen + max_new)
    if plen + max_new > max_len:
        raise ValueError(f"prompt {plen} + max_new {max_new} exceeds "
                         f"max_len {max_len}")
    if temperature > 0 and rng is None:
        # argument error: raise before any cache/prefill work is spent
        raise ValueError("sampling (temperature > 0) needs rng")
    cache = init_kv_cache(cfg, b, max_len, tp_axis=tp_axis)
    if prompt_lengths is None:
        pos0 = plen
        logits, cache = prefill(params, prompt, cache, cfg,
                                tp_axis=tp_axis, ep_axis=ep_axis)
    else:
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        pos0 = lengths                                   # (b,) ragged
        logits, cache = prefill(params, prompt, cache, cfg,
                                tp_axis=tp_axis, ep_axis=ep_axis,
                                last_index=lengths - 1)
    return logits, cache, pos0


def _pick_token(logits, key, temperature: float):
    if temperature == 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(
        key, logits / temperature, axis=-1).astype(jnp.int32)


def _generate_decode(params, logits, cache, pos0, cfg, keys,
                     temperature, tp_axis, ep_axis):
    """generate()'s decode loop: one lax.scan over the new positions."""
    def step(carry, key):
        logits, cache, pos = carry
        tok = _pick_token(logits, key, temperature)
        logits, cache = decode_step(params, tok, pos, cache, cfg,
                                    tp_axis=tp_axis, ep_axis=ep_axis)
        return (logits, cache, pos + 1), tok

    (_, _, _), toks = lax.scan(step, (logits, cache, pos0), keys)
    return jnp.transpose(toks)  # (b, max_new)


def generate_timed(params: dict, prompt, cfg: TransformerConfig, *,
                   max_new: int, max_len: Optional[int] = None,
                   temperature: float = 0.0,
                   rng: Optional[jax.Array] = None,
                   prompt_lengths=None, metrics=None):
    """`generate` with serving telemetry: identical tokens, plus TTFT
    (call -> first token materialized on the host, ``serve.ttft_usec``)
    and per-token decode latency (``serve.tok_usec``) recorded into
    ``metrics`` — default the process-wide ``metrics.SERVING``
    registry, the same one ``DecodeServer`` records into, so one
    snapshot covers both serving paths.

    Eager by design (the host round-trips after prefill and after the
    scan are the measurement points); inside jit use plain
    ``generate``. The first token is computed once here for the TTFT
    stamp and recomputed inside the scan — picks are deterministic
    functions of (logits, key), so outputs equal ``generate`` exactly
    (pinned by test)."""
    from rlo_tpu.utils.metrics import SERVING
    reg = SERVING if metrics is None else metrics
    t0 = time.perf_counter()
    logits, cache, pos0 = _generate_prefill(
        params, prompt, cfg, max_new=max_new, max_len=max_len,
        temperature=temperature, rng=rng, tp_axis=None, ep_axis=None,
        prompt_lengths=prompt_lengths)
    keys = (jax.random.split(rng, max_new) if rng is not None
            else jnp.zeros((max_new, 2), jnp.uint32))
    if max_new > 0:  # max_new=0: no first token exists to stamp
        jax.block_until_ready(
            _pick_token(logits, keys[0], temperature))
        t1 = time.perf_counter()
        reg.histogram("serve.ttft_usec").observe((t1 - t0) * 1e6)
    else:
        t1 = time.perf_counter()
    toks = _generate_decode(params, logits, cache, pos0, cfg, keys,
                            temperature, None, None)
    jax.block_until_ready(toks)
    if max_new > 0:
        t2 = time.perf_counter()
        reg.histogram("serve.tok_usec").observe(
            (t2 - t1) * 1e6 / max_new)
        reg.counter("serve.tokens_out").inc(int(toks.shape[0]) * max_new)
    return toks
