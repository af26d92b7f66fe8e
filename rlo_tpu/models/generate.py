"""KV-cache autoregressive generation for the flagship transformer.

The training side (models.transformer) recomputes full attention every
step; generation wants O(1) work per new token: each layer's keys and
values are cached HEAD-LEADING, SEQ-MINOR at (batch, kv_heads,
head_dim, max_len) — the format of a cache entry and every operation
on one belong to models.kvcache — and a decode step attends the
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

from rlo_tpu.models import kvcache
# the cache's own functions, at the import path they have always had
from rlo_tpu.models.kvcache import (  # noqa: F401
    _attend_cache, _attend_cache_block, _quantize_kv, fold_kv_tail,
    init_kv_cache, init_kv_tail, kv_cache_pspecs)
from rlo_tpu.models.transformer import (TransformerConfig, apply_layer,
                                        check_unselected, embed_tokens,
                                        hc_boundary, hc_enter, head_logits,
                                        head_weights, mla_unabsorbed,
                                        _local_attention, _rmsnorm)


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


def _mla_absorbed(q_nope, q_rope, layer, cfg, attend):
    """Latent attention against the cache in the absorbed form: each
    head's query is carried into the latent space (q_nope W_uk^T beside
    the rotated dims), ``attend`` (q (b, T, H, kv_lora + rope)) ->
    (b, T, H, kv_lora) attends the latent rows as keys and, in their
    leading kv_lora features, as values, and W_uv brings the result
    out: no per-head key or value is ever formed. A layer's attention
    sinks (``attn_sink``) go to the attend as its ``sinks``."""
    dt = q_nope.dtype
    with jax.named_scope("mla.absorb"):
        q_lat = jnp.einsum("bthn,chn->bthc", q_nope,
                           layer["wuk"].astype(dt))
        q = jnp.concatenate([q_lat, q_rope], -1)
    with jax.named_scope("mla.attend"):
        o_lat = (attend(q, sinks=layer["attn_sink"]) if cfg.attn_sinks
                 else attend(q)).astype(dt)
    with jax.named_scope("mla.v_up"):
        return jnp.einsum("bthc,chv->bthv", o_lat,
                          layer["wuv"].astype(dt))


def _forward(params: dict, tokens, pos, caches, cfg: TransformerConfig,
             hook, *, tp_axis: Optional[str] = None,
             ep_axis: Optional[str] = None,
             moe_info: Optional[list] = None):
    """THE layer loop of every step function, here and in models.paged:
    embed ``tokens`` (b, T) at positions ``pos``, then each layer
    through apply_layer — the one source of layer math — with its
    attention swapped for ``hook(layer, lc, *args) -> (attended, new
    entry)``: ``lc`` is the layer's item of ``caches``, ``args`` what
    apply_layer hands an attention hook ((q, k, v); under latent
    attention (q_nope, q_rope, latent, index)). Then the final norm.
    Returns
    (x (b, T, d), the layers' new entries). A sliding layer's window
    goes to apply_layer (its rotation); the step functions hand it to
    the cache functions beside ``lc`` (_windows). Hyper-connections
    (``cfg.hc``): the embedding enters as the streams' state and their
    sum leaves (transformer.hc_enter / hc_boundary)."""
    x = embed_tokens(params["embed"], tokens, pos, cfg)
    if cfg.hc:
        x = hc_enter(x, cfg)
    entries = []
    for i, (layer, lc) in enumerate(zip(params["layers"], caches)):
        def attention(*args, layer=layer, lc=lc):
            out, entry = hook(layer, lc, *args)
            entries.append(entry)
            return out

        x, _ = apply_layer(x, layer, cfg, attention=attention,
                           tp_axis=tp_axis, ep_axis=ep_axis, pos=pos,
                           moe_info=moe_info, window=cfg.layer_window(i))
    if cfg.hc:      # the last output into the streams, and their sum
        x = hc_boundary(x, None, cfg)
    return _rmsnorm(x, params["ln_f"]["g"], cfg.norm_eps), entries


def _head(params: dict, x, cfg: TransformerConfig, at=None):
    """f32 logits of _forward's ``x`` (b, T, d) under the output head
    (tied or not: head_weights): at position ``at`` of every row (an
    int), at row b's own position ``at`` (b,) (a scalar: the same for
    all rows, but traced), or at all T positions (None): the product
    is transformer.head_logits' (in float32 under ``cfg.lm_head_fp32``)."""
    dt = cfg.act_dtype
    if at is None:
        if cfg.lm_head_fp32:
            return head_logits(params, x, cfg)
        return jnp.einsum("btd,vd->btv", x, head_weights(params).astype(dt)
                          ).astype(jnp.float32)
    if isinstance(at, int):
        xl = x[:, at, :]
    else:
        at = jnp.asarray(at, jnp.int32)
        idx = jnp.expand_dims(at, tuple(range(at.ndim, 3)))
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[-1])),
            axis=1)[:, 0]
    return head_logits(params, xl, cfg)


def _windows(cache, cfg: TransformerConfig):
    """Each layer's window (cfg.layer_window), zipped by the step
    functions beside a layer's item of ``cache``; all 0 without sliding
    layers."""
    return [cfg.layer_window(i) for i in range(len(cache))]


def _shared_selection(cfg: TransformerConfig, select_full):
    """The step functions' token selection, layer by layer in the order
    _forward runs them: ``pick(i, index, entry) -> (select, record)``
    calls ``select_full(index, entry)`` (-> (select, record)) on a layer
    that chooses for itself and keeps its choice; a shared layer
    (cfg.index_types) takes the choice the nearest full layer before it
    made for the same queries, scores nothing, and is recorded as
    {"select", "shared": True}. Without a selector: (None, None)."""
    last = [None]

    def pick(i, index, entry, info=None):
        kind = cfg.index_kind(i)
        if kind is None:
            return None, None
        if kind == "shared":
            record = None
            if info is not None and last[0] is not None:
                record = {"select": last[0], "shared": True}
                info.append(record)
            return last[0], record
        last[0], record = select_full(index, entry)
        return last[0], record

    return pick


def _cache_hook(cfg: TransformerConfig, step):
    """_forward's ``hook`` for a step through a cache, from the part
    that is the step function's own: ``step(lc, k, v, index) -> (new
    entry, attend)`` puts the layer's new keys and values where they go
    and says how a query ``q`` then attends (``attend(q)``). Under
    latent attention ``k`` is the latent rows, ``v`` None, ``index`` a
    token selector's projections (None without one: the other layers'
    too), and the attend runs in the absorbed form (_mla_absorbed)."""
    def hook(layer, lc, *args):
        if cfg.mla:
            q_nope, q_rope, latent, index = args
            entry, attend = step(lc, latent, None, index)
            return _mla_absorbed(q_nope, q_rope, layer, cfg,
                                 attend), entry
        q, k, v = args
        entry, attend = step(lc, k, v, None)
        return attend(q).astype(cfg.act_dtype), entry

    return hook


def decode_step(params: dict, token, pos, cache, cfg: TransformerConfig,
                tp_axis: Optional[str] = None,
                ep_axis: Optional[str] = None,
                moe_info: Optional[list] = None,
                tail: Optional[tuple] = None,
                dsa_info: Optional[list] = None
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
    token and layer; the step writes it like any other row
    (kvcache.write_row, or the tail) and attends in the absorbed form
    (_mla_absorbed). ``moe_info``: see apply_layer. With a token
    selector (``cfg.dsa``) the row's index key is written with it, the
    selector picks among the positions the cache then holds
    (kvcache.select_tokens) and the attend runs over its choice;
    ``dsa_info`` receives each layer's scores and choice, and how the
    attend read it (kvcache.select_tokens).

    ``tail`` ``(rows, newest)``: step ``newest`` of a loop that keeps
    its new K/V rows in a write-behind tail (init_kv_tail; the loop
    began at position pos - newest). The step stores its rows at
    [newest] of each layer's tail, attends the cache up to where the
    loop began and tail rows 0..newest (same keys, values and
    positions as write-then-attend: _attend_cache), and returns
    (logits, new tail rows); the cache is read, not written."""
    cfg = _decode_cfg(cfg)
    posv = jnp.asarray(pos)
    v_dim = cfg.kv_lora_rank if cfg.mla else 0
    # upto: the last cache position a query attends
    if tail is None:
        tails, upto = [None] * len(cache), posv
    else:
        tails, newest = tail
        newest = jnp.asarray(newest, jnp.int32)
        upto = posv - newest - 1        # the last position the cache holds
    # (1,) shared positions, or (b, 1) per-row, for embed/rope
    pos_arr = posv[:, None] if posv.ndim == 1 else posv[None]

    # the attend kernel's work list up to there: one for all layers
    work = kvcache.attend_work(cache, cfg, upto, tp_axis=tp_axis)
    # and one for the sliding layers' rings, whose queries at posv read
    # their window's last keys alone (the window variant of the kernel)
    wwork = (kvcache.window_work(cache, cfg, upto, first=posv)
             if cfg.windowed else None)
    # the same for the selector's score kernel, and its positions
    iwork = upto_q = None
    if cfg.dsa:
        iwork = kvcache.select_work(cache, cfg, upto)
        upto_q = jnp.broadcast_to(upto, token.shape)[:, None]

    def select_full(index, entry):
        select = kvcache.select_tokens(index, entry, upto_q,
                                       cfg.index_topk, work=iwork,
                                       info=dsa_info)
        # this layer's record: the attend adds how it read the set
        return select, (dsa_info[-1] if dsa_info is not None
                        and select is not None else None)

    pick = _shared_selection(cfg, select_full)

    def step(lc_tl, k, v, index):
        lc, tl, window, i = lc_tl
        row = kvcache.new_row(lc, k, v, index)
        if window:
            return _window_step(lc, tl, row, window)
        if tl is None:
            entry = kvcache.write_row(lc, row, posv)
            select, record = pick(i, index, entry, dsa_info)
            return entry, lambda q, **kw: kvcache.attend(
                q, entry, upto, cfg.attn_scale, v_dim=v_dim, work=work,
                select=select, info=record, **kw)
        tl = kvcache.store_tail_row(tl, row, newest)
        return tl, lambda q, **kw: kvcache.attend(
            q, lc, upto, cfg.attn_scale, v_dim=v_dim, tail=(tl, newest),
            work=work, **kw)

    def _window_step(lc, tl, row, window):
        # a sliding layer's ring: the same write and attend, modulo its
        # length and within its window
        if tl is None:
            entry = kvcache.write_row(lc, row, posv, window=window)
            return entry, lambda q: kvcache.attend(
                q, entry, upto, cfg.attn_scale, work=wwork, window=window)
        tl = kvcache.store_tail_row(tl, row, newest)
        return tl, lambda q: kvcache.attend(
            q, lc, upto, cfg.attn_scale, tail=(tl, newest), work=wwork,
            window=window)

    x, new = _forward(params, token[:, None], pos_arr,
                      list(zip(cache, tails, _windows(cache, cfg),
                               range(len(cache)))), cfg,
                      _cache_hook(cfg, step),
                      tp_axis=tp_axis, ep_axis=ep_axis, moe_info=moe_info)
    return _head(params, x, cfg, 0), new


def block_decode(params: dict, tokens, pos0, cache,
                 cfg: TransformerConfig,
                 tp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None,
                 moe_info: Optional[list] = None,
                 dsa_info: Optional[list] = None):
    """Process T tokens (b, T) through the cache in ONE forward: row
    b's token i sits at position pos0[b] + i. Returns
    (logits (b, T, vocab) f32, cache). The verify step of speculative
    decoding (the target judges all gamma draft tokens at once); also
    a building block for chunked cache extension. Write-then-attend
    with per-(row, i) masks, so rejected drafts' cache entries are
    simply garbage beyond the accepted position — masked out and
    overwritten by later writes, exactly like ragged decode. A token
    selector chooses query by query (decode_step). ``moe_info``,
    ``dsa_info``: see decode_step.

    Under ``cfg.block_len`` (generation by diffusion over blocks) the
    mask is block-causal: the T tokens are whole blocks, ``pos0`` a
    multiple of block_len, and every query attends all of its own
    block — a denoise or commit PASS over a row's current block (T =
    block_len: each of the block's positions sees positions
    < pos0 + T), and a long prompt's chunk alike."""
    cfg = _decode_cfg(cfg)
    b, T = tokens.shape
    pos0 = jnp.asarray(pos0, jnp.int32).reshape(b)
    pos_arr = pos0[:, None] + jnp.arange(T, dtype=jnp.int32)  # (b, T)
    v_dim = cfg.kv_lora_rank if cfg.mla else 0

    work = kvcache.attend_work(cache, cfg, pos0, T, tp_axis=tp_axis)
    if cfg.windowed:
        wwork = kvcache.window_work(cache, cfg, pos0, T)
        ring = cache[next(i for i, w in enumerate(_windows(cache, cfg))
                          if w)]["k"].shape[3]
        if T > ring - cfg.sliding_window + 1:
            raise ValueError(
                f"a block of {T} through a ring of {ring} positions "
                f"would overwrite keys of its own window "
                f"{cfg.sliding_window}: size the ring's spare for it "
                f"(init_kv_cache's ring_spare)")

    pick = _shared_selection(cfg, lambda index, entry: (
        kvcache.select_tokens(index, entry, pos_arr, cfg.index_topk,
                              info=dsa_info), None))

    def step(lc_w, k, v, index):
        lc, window, i = lc_w
        if window:      # a sliding layer's ring
            entry = kvcache.write_block(lc, kvcache.new_block(lc, k, v),
                                        pos0, pos_arr, window=window)
            return entry, lambda q: kvcache.attend_block(
                q, entry, pos_arr, cfg.attn_scale, pos0=pos0, work=wwork,
                window=window)
        entry = kvcache.write_block(
            lc, kvcache.new_block(lc, k, v, index), pos0, pos_arr)
        select, _ = pick(i, index, entry, dsa_info)
        return entry, lambda q, **kw: kvcache.attend_block(
            q, entry, pos_arr, cfg.attn_scale, pos0=pos0, v_dim=v_dim,
            work=work, select=select, block_len=cfg.block_len, **kw)

    x, new = _forward(params, tokens, pos_arr,
                      list(zip(cache, _windows(cache, cfg),
                               range(len(cache)))), cfg,
                      _cache_hook(cfg, step), tp_axis=tp_axis,
                      ep_axis=ep_axis, moe_info=moe_info)
    return _head(params, x, cfg), new


def prefill(params: dict, tokens, cache, cfg: TransformerConfig,
            tp_axis: Optional[str] = None,
            ep_axis: Optional[str] = None,
            last_index=None, moe_info: Optional[list] = None,
            need_logits: bool = True):
    """Fill the cache with the whole prompt in ONE forward pass.
    Returns (logits of the last prompt position, filled cache);
    ``need_logits=False`` skips the head and returns (None, cache): a
    model that generates by diffusion over blocks takes no token from
    its prompt's logits. Under ``cfg.block_len`` the block is attended
    block-causally (_local_attention).
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
    not the quantization envelope). A builder's run of 2026-08-01, from
    before the ledger (BENCH_extra.json, decode_bench.py --ttft, v5e,
    plen 1024): 189x the token-at-a-time prefill at batch 1, 57x at 4.

    Latent attention (``cfg.mla``): the block is attended in the plain
    form (every head's keys and values decompressed from the latent:
    mla_unabsorbed) and the hook stashes the LATENT rows, the same rows
    decode_step writes and then attends absorbed. ``moe_info``: see
    apply_layer. A token selector's index keys are stored beside the
    rows and nothing is selected: a prompt block is no longer than
    index_topk (longer prompts go on through block_decode).
    """
    if last_index is not None:
        cfg = _decode_cfg(cfg)  # ragged MoE: padding must be inert
    check_unselected(cfg, tokens.shape[1])

    def hook(layer, lc_w, *args):
        # the COMPACT K/V block goes into the cache on the way through
        # (head-leading and SEQ-MINOR; rope keys rotated); the block
        # itself is attended causally, as in training (a sliding layer:
        # within its band, its ring keeping the block's last positions)
        lc, window = lc_w
        if cfg.mla:
            q_nope, q_rope, latent, index = args
            entry, _, _ = kvcache.store_prompt(lc, latent, index=index)
            return mla_unabsorbed(q_nope, q_rope, latent, layer,
                                  cfg), entry
        q, k, v = args
        if window:
            entry, k, v = kvcache.store_prompt(lc, k, v, window=window)
            return _local_attention(q, k, v, window=window).astype(
                cfg.act_dtype), entry
        entry, k, v = kvcache.store_prompt(lc, k, v)
        return _local_attention(
            q, k, v, block_len=cfg.block_len).astype(cfg.act_dtype), entry

    x, new = _forward(params, tokens, jnp.arange(tokens.shape[1]),
                      list(zip(cache, _windows(cache, cfg))), cfg, hook,
                      tp_axis=tp_axis, ep_axis=ep_axis, moe_info=moe_info)
    if not need_logits:
        return None, new
    return _head(params, x, cfg,
                 -1 if last_index is None else last_index), new


# ---- generation by diffusion over blocks --------------------------------

def denoise_update(logits, block, masked, threshold: float):
    """The unmask rule of one denoise pass ('low_confidence_dynamic',
    greedy): ``logits`` (b, B, vocab) f32 of a pass over ``block``
    (b, B) int32 whose positions ``masked`` (b, B) bool still hold the
    mask id. At each masked position the confidence is the largest
    softmax probability, in float32, and the candidate its argmax;
    every masked position whose confidence passes ``threshold`` takes
    its candidate, or, if none does, the one with the largest
    confidence (the lowest position on a tie). A row with no mask left
    (its pass was a commit) is returned as it came. Returns
    (block, masked, unmasked (b, B) bool)."""
    with jax.named_scope("diff.unmask"):
        logits = logits.astype(jnp.float32)
        top = jnp.max(logits, axis=-1)
        conf = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
        cand = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sure = masked & (conf > threshold)
        # argmax takes the first of equal confidences: the lowest position
        best = jnp.argmax(jnp.where(masked, conf, -1.0), axis=-1)
        lone = (jnp.arange(block.shape[1])[None, :] == best[:, None]) \
            & masked & ~jnp.any(sure, axis=-1, keepdims=True)
        unmasked = sure | lone
        return (jnp.where(unmasked, cand, block), masked & ~unmasked,
                unmasked)


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
