"""Flagship model: decoder-only transformer, sequence-parallel by ring
attention, tensor-parallel Megatron-style, data-parallel by the
framework's ring allreduce.

The reference ships no model code (SURVEY.md §5 records the absence);
this is the net-new capability demonstrating the substrate end-to-end on
a (dp, sp, tp) mesh:

  - the sequence axis is sharded over `sp`: attention runs as
    rlo_tpu.ops.ring_attention (K/V streaming over the ppermute ring),
    every other sublayer is position-local and needs no communication;
  - attention heads and FFN hidden units are sharded over `tp`
    (column-parallel wqkv/w1, row-parallel wo/w2): each device computes
    its local heads/hidden slice and the partial output projections are
    summed with the framework's allreduce — the two classic
    tensor-parallel collectives per layer (`param_pspecs` gives the
    matching PartitionSpec tree);
  - the batch axis is sharded over `dp`: gradients are combined with the
    framework's ring allreduce + Pallas fused combine
    (rlo_tpu.ops.tpu_collectives.allreduce), the data-collective path the
    BASELINE.json configs benchmark;
  - cross-shard label shift (next-token prediction across the sp
    boundary) is one ppermute of the first token column.

Pure-functional JAX: params are a pytree, `train_step` is jit/shard_map
compatible, bfloat16 activations with float32 params and accumulation.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from rlo_tpu import topology
from rlo_tpu.models import moe
from rlo_tpu.ops import tpu_collectives as tc
from rlo_tpu.ops.ring_attention import full_attention, ring_attention


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 512
    dtype: str = "bfloat16"  # activation dtype (params: param_dtype)
    # mixture-of-experts FFN (0 = dense). Experts shard over `ep_axis`
    # with all_to_all dispatch/return — see rlo_tpu.models.moe.
    n_experts: int = 0
    capacity_factor: float = 2.0
    moe_aux_coef: float = 1e-2
    # sequence-parallel attention strategy: 'ring' (K/V streaming over
    # the ppermute ring) or 'ulysses' (all_to_all head-scatter; needs
    # local heads divisible by the sp size) — rlo_tpu.ops.{ring_attention,
    # ulysses}
    sp_attention: str = "ring"
    # grouped-query attention: number of K/V heads (must divide
    # n_heads); None = n_heads (MHA). Each group of
    # n_heads/n_kv_heads query heads shares one K/V head — smaller
    # projections and an n_heads/n_kv_heads-times smaller decode
    # KV cache (models.generate stores only the K/V heads).
    n_kv_heads: Optional[int] = None
    # position encoding: 'sincos' (additive at the embedding) or
    # 'rope' (rotary: q/k rotated per position inside every layer —
    # relative-position attention; composes with sp sharding because
    # the rotation uses GLOBAL positions, and with the KV cache
    # because keys are cached rotated)
    pos_encoding: str = "sincos"
    # RoPE context extension (rope configs only). rope_scaling:
    #   None      — plain rotary at base 10000
    #   'linear'  — position interpolation: positions divided by
    #               rope_scale, squeezing a rope_scale-times longer
    #               context into the trained rotation range
    #   'ntk'     — NTK-aware base rescale: base *= scale^(d/(d-2)),
    #               extending low-frequency dims' range while keeping
    #               high-frequency (local-order) resolution
    # Both are inference-time levers for running a model past its
    # training length; rope_scale is the extension factor.
    rope_scaling: Optional[str] = None
    rope_scale: float = 1.0
    # KV-cache storage dtype for generation (models.generate):
    #   None   — cache in the activation dtype (exact decode)
    #   'int8' — per-(position, head) symmetric quantization: HALF the
    #            cache memory and HBM bytes of bf16, error one
    #            quantization half-step per read; 2x the servable
    #            batch x context per chip. The flash-decode kernel
    #            (pallas/decode.py) dequantizes tiles in VMEM. No cell
    #            of the benchmark runs it (ROADMAP D12); a builder's
    #            run of 2026-08-01, from before the ledger
    #            (BENCH_extra.json, decode_bench.py --compare-kv, v5e,
    #            batch 32, plen 1024): 1.17-1.43x decode tok/s.
    kv_cache_dtype: Optional[str] = None
    # rematerialize each layer in the backward pass (jax.checkpoint):
    # trades ~one extra forward of FLOPs for O(layers) less activation
    # HBM — the standard long-context memory lever
    remat: bool = False
    # cross-entropy vocab chunking (MEMORY lever, off by default): the
    # plain loss materializes two (batch, block, vocab) fp32 tensors
    # (logits + log-probs) plus backward residuals; N > 0 streams the
    # vocab axis through an online logsumexp (flash attention's
    # softmax trick applied to the LM head) in N-wide chunks and never
    # materializes either — O(batch*block*N) instead of
    # O(batch*block*vocab). Use when the loss working set OOMs (huge
    # vocab / long sequence). NOT a speed lever on v5e: measured
    # 10-15% SLOWER at vocab 32k (the scan serializes the head matmul
    # and the checkpointed backward recomputes it), so 0/None = off.
    loss_vocab_chunk: Optional[int] = None
    # ---- what a published configuration sets (the "model" section of
    # perf/configs/<name>.json; README "Model settings"). The defaults
    # are the block every earlier configuration runs.
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # storage dtype of the weights ('float32' | 'bfloat16'); every use
    # casts to the activation dtype
    param_dtype: str = "float32"
    # False: a separate output head ``params["head"]`` (vocab, d)
    tie_embeddings: bool = True
    # 'gelu': two matrices w1/w2; 'swiglu': down(silu(gate x) * up x)
    # with three (wg/wu/wd), of width d_ff
    ffn: str = "gelu"
    # latent attention (MLA), selected by kv_lora_rank > 0: queries
    # through a q_lora_rank bottleneck, keys and values through one
    # shared kv_lora_rank latent plus qk_rope_head_dim rotated dims that
    # all heads share; the decode cache holds that latent row only
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # rope_scaling='yarn' (rope_scale is its factor): per-dimension
    # blend of interpolated and extrapolated frequencies (_yarn_freqs)
    rope_original_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # expert layers. moe_router 'switch' is models.moe.moe_ffn (top-1,
    # static capacity); 'sigmoid_group' is models.moe.routed_ffn:
    # sigmoid scores, a selection-only bias, top experts_per_tok among
    # the topk_group best of n_group groups, normalised weights times
    # routed_scale, n_shared_experts always-on experts, no token
    # dropped; 'softmax_topk' is the same layer behind a softmax over
    # all experts in float32 whose experts_per_tok largest stay, their
    # weights divided by their sum (no groups, no bias). The first
    # n_dense_layers layers keep the dense FFN. The
    # layer holds experts [expert_first, expert_first + n_experts_held)
    # of the n_experts it routes over (0 = all of them) and computes
    # their part of the result.
    moe_router: str = "switch"
    n_dense_layers: int = 0
    moe_d_ff: int = 0
    n_shared_experts: int = 0
    experts_per_tok: int = 1
    n_group: int = 1
    topk_group: int = 1
    routed_scale: float = 1.0
    expert_first: int = 0
    n_experts_held: int = 0
    # learned sparse attention over a latent cache (the "lightning
    # indexer"), selected by index_topk > 0: index_n_heads small query
    # heads of index_head_dim score every cached token against ONE
    # index key a token (cached beside the latent row), and latent
    # attention runs over the index_topk best alone (mla_project,
    # models.kvcache.select_tokens). 0 changes nothing.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # width of a query/key/value head where the model sets it apart from
    # d_model // n_heads (0): wq is (d, heads x width), wo its transpose
    attn_head_dim: int = 0
    # a gain-only RMS norm over each head's width on q and on k, before
    # the rotation (``q_norm`` / ``k_norm`` (head_dim,) a layer)
    qk_norm: bool = False
    # generation by diffusion over blocks, selected by block_len > 0:
    # position i attends position j iff j // block_len <= i // block_len
    # (prefill and block_decode alike), and a server generates block by
    # block by masked denoising (models.generate.denoise_update): a
    # block starts as ``mask_id``s and a pass unmasks every masked
    # position whose confidence passes ``confidence``, or the best one
    block_len: int = 0
    mask_id: int = 0
    confidence: float = 0.9

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def dsa(self) -> bool:
        return self.index_topk > 0

    @property
    def head_dim(self) -> int:
        if self.mla:  # width of a query/key head
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.attn_head_dim:
            return self.attn_head_dim
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def attn_scale(self) -> float:
        """Softmax scale: head_dim^-0.5, times yarn's m^2 where the
        configuration sets mscale_all_dim."""
        scale = self.head_dim ** -0.5
        if self.rope_scaling == "yarn" and self.rope_mscale_all_dim:
            m = _yarn_mscale(self.rope_scale, self.rope_mscale_all_dim)
            scale *= m * m
        return scale

    @property
    def weight_dtype(self):
        return jnp.dtype(self.param_dtype)

    def layer_is_moe(self, i: int) -> bool:
        return self.n_experts > 0 and i >= self.n_dense_layers

    @property
    def kv_heads(self) -> int:
        if self.n_kv_heads is None:
            return self.n_heads
        assert self.n_heads % self.n_kv_heads == 0, \
            f"n_kv_heads {self.n_kv_heads} must divide n_heads " \
            f"{self.n_heads}"
        return self.n_kv_heads

    @property
    def act_dtype(self):
        return jnp.dtype(self.dtype)


def init_params(rng: jax.Array, cfg: TransformerConfig) -> dict:
    """Scaled-normal init in ``cfg.param_dtype``; the embedding is tied
    with the output head unless ``cfg.tie_embeddings`` is off (then
    ``head`` (vocab, d)).

    ``wqkv`` has shape (d, 3, d): axis 1 selects q/k/v and axis 2 is
    (heads x head_dim) flattened, so sharding axis 2 over `tp` splits
    each of q, k, v by head (the memory layout equals the fused
    (d, 3*d) [q|k|v] matrix). Latent attention (``cfg.mla``) has
    ``wdq`` (d, q_lora), ``q_norm``, ``wuq`` (q_lora, heads x
    (nope + rope)), ``wdkv`` (d, kv_lora + rope), ``kv_norm``, ``wuk``
    (kv_lora, heads, nope) and ``wuv`` (kv_lora, heads, v) — two
    tensors: the absorbed decode uses each alone, and a slice of a
    fused one is a copy of it every step — and ``wo`` (heads x v, d).
    A gated FFN
    has ``wg``/``wu`` (d, f) and ``wd`` (f, d); expert layers hold
    ``moe`` (models.moe)."""
    keys = jax.random.split(rng, 2 + 6 * cfg.n_layers)
    d, f = cfg.d_model, cfg.d_ff
    pdt = cfg.weight_dtype

    def norm(key, shape, scale):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(pdt)

    def ones(n):
        return jnp.ones((n,), pdt)

    params = {
        "embed": norm(keys[0], (cfg.vocab, d), 0.02),
        "ln_f": {"g": ones(d)},
        "layers": [],
    }
    if not cfg.tie_embeddings:
        params["head"] = norm(keys[1], (cfg.vocab, d), d ** -0.5)
    k = 2
    for i in range(cfg.n_layers):
        att_out = cfg.n_heads * (cfg.v_head_dim if cfg.mla
                                 else cfg.head_dim)
        layer = {
            "ln1": {"g": ones(d)},
            "wo": norm(keys[k + 1], (att_out, d),
                       (2 * att_out * cfg.n_layers) ** -0.5),
            "ln2": {"g": ones(d)},
        }
        if cfg.mla:
            ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
            nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
            k1, k2, k3, k4, k5 = jax.random.split(keys[k], 5)
            layer["wdq"] = norm(k1, (d, ql), d ** -0.5)
            layer["q_norm"] = {"g": ones(ql)}
            layer["wuq"] = norm(k2, (ql, cfg.n_heads * (nope + rope)),
                                ql ** -0.5)
            layer["wdkv"] = norm(k3, (d, kl + rope), d ** -0.5)
            layer["kv_norm"] = {"g": ones(kl)}
            layer["wuk"] = norm(k4, (kl, cfg.n_heads, nope), kl ** -0.5)
            layer["wuv"] = norm(k5, (kl, cfg.n_heads, cfg.v_head_dim),
                                kl ** -0.5)
            if cfg.dsa:     # its own keys: the others draw what they drew
                hi, di = cfg.index_n_heads, cfg.index_head_dim
                k6, k7, k8 = jax.random.split(
                    jax.random.fold_in(keys[k], 1), 3)
                layer["wiq"] = norm(k6, (ql, hi * di), ql ** -0.5)
                layer["wik"] = norm(k7, (d, di), d ** -0.5)
                layer["ik_norm"] = {"g": ones(di),
                                    "b": jnp.zeros((di,), pdt)}
                layer["wiw"] = norm(k8, (d, hi), d ** -0.5)
        elif cfg.kv_heads == cfg.n_heads:
            layer["wqkv"] = norm(keys[k], (d, 3, att_out), d ** -0.5)
        else:  # GQA: smaller K/V projections, separate q
            dkv = cfg.kv_heads * cfg.head_dim
            kq, kkv = jax.random.split(keys[k])
            layer["wq"] = norm(kq, (d, att_out), d ** -0.5)
            layer["wkv"] = norm(kkv, (d, 2, dkv), d ** -0.5)
        if cfg.qk_norm and not cfg.mla:
            layer["q_norm"] = {"g": ones(cfg.head_dim)}
            layer["k_norm"] = {"g": ones(cfg.head_dim)}
        if cfg.layer_is_moe(i) and cfg.moe_router in moe.ROUTED:
            layer["moe"] = moe.init_routed_params(keys[k + 2], cfg)
        elif cfg.layer_is_moe(i):
            layer["moe"] = moe.init_moe_params(keys[k + 2], d, f,
                                               cfg.n_experts)
        elif cfg.ffn == "swiglu":
            layer["wg"] = norm(keys[k + 2], (d, f), d ** -0.5)
            layer["wu"] = norm(keys[k + 4], (d, f), d ** -0.5)
            layer["wd"] = norm(keys[k + 3], (f, d),
                               (2 * f * cfg.n_layers) ** -0.5)
        elif cfg.ffn == "gelu":
            layer["w1"] = norm(keys[k + 2], (d, f), d ** -0.5)
            layer["w2"] = norm(keys[k + 3], (f, d),
                               (2 * f * cfg.n_layers) ** -0.5)
        else:
            raise ValueError(f"unknown ffn {cfg.ffn!r}; known: 'gelu', "
                             f"'swiglu'")
        params["layers"].append(layer)
        k += 6
    return params


def head_weights(params: dict):
    """The output head (vocab, d): ``head`` where the configuration
    unties it, else the embedding."""
    return params.get("head", params["embed"])


def param_pspecs(cfg: TransformerConfig, tp_axis: Optional[str] = None,
                 ep_axis: Optional[str] = None):
    """PartitionSpec tree matching `init_params` output.

    With ``tp_axis``: wqkv and w1 are column-parallel (outputs sharded by
    head / hidden unit), wo and w2 row-parallel (inputs sharded). With
    ``ep_axis`` (MoE configs): the expert-indexed leading axis of the
    per-expert FFN weights is sharded; the router is replicated.
    Everything else is replicated. Pass as shard_map in/out specs for the
    params argument."""
    from jax.sharding import PartitionSpec as P
    if cfg.mla or cfg.moe_router != "switch" or cfg.ffn != "gelu" \
            or not cfg.tie_embeddings or cfg.qk_norm:
        raise ValueError(
            "param_pspecs knows the MHA/GQA block with a gelu FFN and "
            "switch experts; latent attention, gated FFNs, an untied "
            "head and sigmoid_group experts run on one chip so far")
    t = tp_axis
    layer = {
        "ln1": {"g": P()},
        "wo": P(t, None),
        "ln2": {"g": P()},
    }
    if cfg.kv_heads == cfg.n_heads:
        layer["wqkv"] = P(None, None, t)
    else:  # GQA: q and kv column-parallel by (kv-)head
        layer["wq"] = P(None, t)
        layer["wkv"] = P(None, None, t)
    if cfg.n_experts > 0:
        layer["moe"] = {"wr": P(), "w1": P(ep_axis, None, None),
                        "w2": P(ep_axis, None, None)}
    else:
        layer["w1"] = P(None, t)
        layer["w2"] = P(t, None)
    return {"embed": P(), "ln_f": {"g": P()},
            "layers": [dict(layer, ln1={"g": P()}, ln2={"g": P()})
                       for _ in range(cfg.n_layers)]}


def _rmsnorm(x, g, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * g.astype(
        x.dtype)


def _sincos(pos, d_model, dtype):
    """Sinusoidal positions for GLOBAL token positions (works sharded).
    ``pos`` is (blk,) shared across the batch, or (b, blk) per-row
    (ragged decode); returns (blk, d) or (b, blk, d) accordingly."""
    half = d_model // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half) / half)
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                           axis=-1).astype(dtype)


def embed_tokens(embed, tokens, pos, cfg: TransformerConfig):
    """THE token-embedding path — training (_features), pipeline
    microbatches, and decode all call it, so the position-encoding
    guard lives exactly once. 'sincos' adds the absolute encoding
    here; 'rope' embeds plain (the rotation happens on q/k inside
    every apply_layer)."""
    if cfg.pos_encoding not in ("sincos", "rope"):
        raise ValueError(
            f"unknown pos_encoding {cfg.pos_encoding!r}; "
            f"known: 'sincos', 'rope'")
    if cfg.mla and (cfg.pos_encoding != "rope" or cfg.qk_rope_head_dim % 2
                    or min(cfg.q_lora_rank, cfg.qk_nope_head_dim,
                           cfg.qk_rope_head_dim, cfg.v_head_dim) < 1):
        raise ValueError(
            "latent attention (kv_lora_rank > 0) needs pos_encoding="
            "'rope', q_lora_rank, qk_nope_head_dim, v_head_dim and an "
            "even qk_rope_head_dim")
    if cfg.pos_encoding == "rope" and cfg.head_dim % 2:
        raise ValueError(
            f"rope rotates (i, i+head_dim/2) dim pairs and needs an "
            f"even head_dim; got head_dim={cfg.head_dim} "
            f"(d_model={cfg.d_model}, n_heads={cfg.n_heads})")
    if cfg.rope_scaling is not None:
        if cfg.pos_encoding != "rope":
            raise ValueError(
                f"rope_scaling={cfg.rope_scaling!r} requires "
                f"pos_encoding='rope' (got {cfg.pos_encoding!r})")
        if cfg.rope_scaling not in ("linear", "ntk", "yarn"):
            raise ValueError(
                f"unknown rope_scaling {cfg.rope_scaling!r}; "
                f"known: 'linear', 'ntk', 'yarn'")
        if cfg.rope_scale < 1.0:
            raise ValueError(
                f"rope_scale must be >= 1 (an extension factor); got "
                f"{cfg.rope_scale}")
    x = embed[tokens].astype(cfg.act_dtype)
    if cfg.pos_encoding == "sincos":
        x = x + _sincos(pos, cfg.d_model, cfg.act_dtype)
    return x


def _yarn_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * float(np.log(scale)) + 1.0 if scale > 1 else 1.0


def _yarn_freqs(hd: int, base: float, factor: float, original_len: int,
                beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's hd/2 rotation frequencies: dimension pair i rotates at
    base^(-2i/hd) (extrapolated, kept for the pairs that turn more than
    ``beta_fast`` times over ``original_len``), at that over ``factor``
    (interpolated, for those that turn fewer than ``beta_slow`` times),
    and at a linear blend over the ramp between the two correction
    dims."""
    half = hd // 2
    extra = base ** (-np.arange(half, dtype=np.float64) / half)
    inter = extra / factor

    def correction_dim(n_rot):
        return hd * np.log(original_len / (n_rot * 2 * np.pi)) / (
            2 * np.log(base))

    low = max(int(np.floor(correction_dim(beta_fast))), 0)
    high = min(int(np.ceil(correction_dim(beta_slow))), hd - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def _rope_cfg(t, pos, cfg: TransformerConfig):
    """_rope with the configuration's base, scaling and yarn settings."""
    return _rope(t, pos, cfg.rope_scaling, cfg.rope_scale,
                 base=cfg.rope_theta, yarn=(
                     cfg.rope_original_len, cfg.rope_beta_fast,
                     cfg.rope_beta_slow, cfg.rope_mscale,
                     cfg.rope_mscale_all_dim))


def _rope(t, pos, scaling: Optional[str] = None, scale: float = 1.0,
          base: float = 10000.0, yarn=None):
    """Rotary position embedding: rotate dim pairs (i, i+hd/2) of
    ``t`` (b, blk, heads, head_dim) by position-dependent angles
    (pos (blk,) GLOBAL token positions — sp shards pass their own
    slice, decode passes the single position). Attention scores then
    depend only on RELATIVE positions (the rotation of q·kᵀ composes
    to pos_q − pos_k).

    ``pos`` is (blk,) shared across the batch, or (b, blk) PER-ROW
    (ragged decode: each row sits at its own global position).

    ``scaling``/``scale`` extend the context window (cfg.rope_scaling):
    'linear' divides positions by ``scale`` (position interpolation —
    identical to evaluating the unscaled rotation at pos/scale); 'ntk'
    rescales the base by scale^(hd/(hd-2)) so the lowest frequency's
    period grows ~scale-fold while the highest stays ~unchanged; 'yarn'
    blends both per dimension (_yarn_freqs; ``yarn`` = (original_len,
    beta_fast, beta_slow, mscale, mscale_all_dim)) and multiplies cos
    and sin by mscale(scale, mscale) / mscale(scale, mscale_all_dim)."""
    hd = t.shape[-1]
    half = hd // 2
    posf = pos.astype(jnp.float32)
    amp = 1.0
    if scaling == "linear":
        posf = posf / scale
    elif scaling == "ntk":
        base = base * float(scale) ** (hd / (hd - 2))
    elif scaling == "yarn":
        orig, fast, slow, msc, msc_all = yarn
        amp = _yarn_mscale(scale, msc) / _yarn_mscale(scale, msc_all)
    elif scaling is not None:
        raise ValueError(
            f"unknown rope_scaling {scaling!r}; known: 'linear', 'ntk', "
            f"'yarn'")
    if scaling == "yarn":
        freqs = jnp.asarray(_yarn_freqs(hd, base, scale, orig, fast,
                                        slow))
    else:
        freqs = jnp.exp(-np.log(base) * jnp.arange(half) / half)
    ang = posf[..., None] * freqs          # (blk, half) | (b, blk, half)
    if ang.ndim == 2:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:                                  # per-row positions
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    if amp != 1.0:
        cos, sin = cos * amp, sin * amp
    t32 = t.astype(jnp.float32)
    t1, t2 = t32[..., :half], t32[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin,
                            t1 * sin + t2 * cos], -1).astype(t.dtype)


def _local_attention(q, k, v, use_flash=None, interpret=None,
                     scale: Optional[float] = None, block_len: int = 0):
    """Unsharded causal attention: q (b, L, H, D); k/v (b, L, Hkv, D)
    with Hkv ≤ H (grouped-query attention — query head h attends K/V
    head h // (H/Hkv)). ``scale`` defaults to D^-0.5. ``block_len`` > 0
    makes the mask BLOCK-causal: query i attends every position of its
    own block of ``block_len`` too (j // block_len <= i // block_len) —
    to the kernel and to the oracle alike, a query that attends until
    the last position of its block instead of until its own. ``v`` may be
    narrower than q and k (latent attention: 128 beside 192): the
    kernel has one width, so v rides zero-padded to D and the output is
    cut back — half again the PV products and V bytes it needs (PERF.md
    §7); the oracle attends it as it is.

    On TPU this is the fused flash kernel (pallas/flash.py — trainable
    since the custom_vjp landed): the batch folds into the head axis
    (attention is per-head independent; the causal mask is purely
    position-driven, identical for every batch row), so the whole batch
    is ONE kernel launch instead of a vmapped per-row program — and the
    batch-folded head indices keep the GQA group mapping intact
    (b·H + h ↦ b·Hkv + h//G), so compact K/V streams from HBM. Off-TPU
    it is the unfused oracle; a shape the kernel rejects on TPU takes
    the oracle too and warns (pallas.reduce.kernel_gate).
    ``use_flash`` overrides the gate; ``interpret`` passes
    through to the kernel unchanged (interpret=True also enables flash
    off-TPU, where the compiled kernel cannot run)."""
    b, L, nh, hd = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    from rlo_tpu.pallas.flash import auto_block_q, can_flash
    from rlo_tpu.pallas.reduce import kernel_gate
    # adaptive Q tile: the batch folds into the kernel's head grid, so
    # large batches mean many programs — bigger tiles claw back the
    # per-program overhead (the round-4 MFU-cliff mechanism; measured
    # bq 1024 = 1.14x bq 256 at 128 folded heads)
    bq = auto_block_q(g * L, L, hd)
    # the last position each query attends: its own, or its block's
    until = None
    if block_len:
        until = (jnp.arange(L, dtype=jnp.int32) // block_len * block_len
                 + (block_len - 1))
    if use_flash is None:
        ok = can_flash(L, L, hd, block_q=bq, groups=g)
        use_flash = ok if interpret else kernel_gate(
            ok, f"causal attention (L={L}, head_dim={hd}, groups={g})")
    if not use_flash:
        if g > 1:
            k = jnp.repeat(k, g, axis=2)
            v = jnp.repeat(v, g, axis=2)
        return jax.vmap(lambda q_, k_, v_: full_attention(
            q_, k_, v_, causal=True, scale=scale, q_until=until))(q, k, v)
    from rlo_tpu.pallas.flash import flash_attention
    vd = v.shape[-1]
    if vd != hd:
        v = jnp.pad(v, ((0, 0),) * 3 + ((0, hd - vd),))

    def fold(t):
        n = t.shape[2]
        return t.transpose(1, 0, 2, 3).reshape(L, b * n, hd)

    out = flash_attention(fold(q), fold(k), fold(v), causal=True,
                          scale=scale, block_q=bq, interpret=interpret,
                          q_until=until)
    return out.reshape(L, b, nh, hd).transpose(1, 0, 2, 3)[..., :vd]


def _layernorm(x, g, b, eps: float):
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * g.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _rope_leading(t, pos, cfg: TransformerConfig):
    """``t`` (b, blk, heads, width) with its first qk_rope_head_dim
    features rotated at ``pos``; the others pass."""
    rope = cfg.qk_rope_head_dim
    return jnp.concatenate(
        [_rope_cfg(t[..., :rope], pos, cfg), t[..., rope:]], -1)


def index_project(h, c_q, layer: dict, cfg: TransformerConfig, pos):
    """The token selector's projections of the normed activation ``h``
    (b, blk, d) and of latent attention's query latent ``c_q``: ``q``
    (b, blk, index heads, index dim), ``k`` (b, blk, index dim) — ONE
    key a token for all the index heads, LayerNormed — both with their
    first qk_rope_head_dim features rotated at ``pos``, and ``w``
    (b, blk, index heads) f32, a query's weight of each head's score,
    times heads^-0.5 and dim^-0.5. A token's score of a key is
    sum_j w_j relu(q_j . k) (kvcache.select_tokens); the decode cache
    stores ``k`` beside the latent row."""
    b, blk, _ = h.shape
    dt = h.dtype
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    with jax.named_scope("dsa.index_q"):
        q = _rope_leading((c_q @ layer["wiq"].astype(dt)).reshape(
            b, blk, hi, di), pos, cfg)
        w = jnp.einsum("btd,dh->bth", h, layer["wiw"].astype(dt),
                       preferred_element_type=jnp.float32) * (
                           hi ** -0.5 * di ** -0.5)
    with jax.named_scope("dsa.index_k"):
        k = _layernorm(h @ layer["wik"].astype(dt), layer["ik_norm"]["g"],
                       layer["ik_norm"]["b"], cfg.norm_eps)
        k = _rope_leading(k[:, :, None, :], pos, cfg)[:, :, 0]
    return {"q": q, "k": k, "w": w}


def mla_project(h, layer: dict, cfg: TransformerConfig, pos):
    """Latent attention's projections of the normed activation ``h``
    (b, blk, d): (q_nope (b, blk, H, nope), q_rope (b, blk, H, rope)
    rotated, latent (b, blk, kv_lora + rope), index). The latent row is
    [rms(c_kv) | rope(k_r)]: what the decode cache stores, once per
    token and layer, whatever the number of heads. ``index`` is the
    token selector's (index_project), None without one."""
    b, blk, _ = h.shape
    dt = h.dtype
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla.down"):
        c_q = _rmsnorm(h @ layer["wdq"].astype(dt),
                       layer["q_norm"]["g"], cfg.norm_eps)
        ckv = h @ layer["wdkv"].astype(dt)
        c_kv = _rmsnorm(ckv[..., :cfg.kv_lora_rank],
                        layer["kv_norm"]["g"], cfg.norm_eps)
        k_r = _rope_cfg(ckv[..., None, cfg.kv_lora_rank:], pos,
                        cfg)[:, :, 0]
    with jax.named_scope("mla.q_up"):
        q = (c_q @ layer["wuq"].astype(dt)).reshape(
            b, blk, cfg.n_heads, nope + rope)
        q_rope = _rope_cfg(q[..., nope:], pos, cfg)
    index = index_project(h, c_q, layer, cfg, pos) if cfg.dsa else None
    return (q[..., :nope], q_rope, jnp.concatenate([c_kv, k_r], -1),
            index)


def check_unselected(cfg: TransformerConfig, blk: int) -> None:
    """A causal block attended whole (mla_unabsorbed) is the model only
    while no query has more than index_topk positions to choose from."""
    if cfg.dsa and blk > cfg.index_topk:
        raise ValueError(
            f"a block of {blk} tokens attended without selection "
            f"exceeds index_topk {cfg.index_topk}: extend it through "
            f"the cache (models.generate.block_decode)")


def mla_unabsorbed(q_nope, q_rope, latent, layer: dict,
                   cfg: TransformerConfig):
    """Causal latent attention over a whole block in its plain form:
    every head's k_nope and v are decompressed from the latent, the
    rotated key dims are shared by all heads. Returns (b, blk, H, v)."""
    b, blk, nh, nope = q_nope.shape
    dt = q_nope.dtype
    kl = cfg.kv_lora_rank
    with jax.named_scope("mla.kv_up"):
        c_kv = latent[..., :kl]
        k_nope = jnp.einsum("btc,chn->bthn", c_kv, layer["wuk"].astype(dt))
        v = jnp.einsum("btc,chv->bthv", c_kv, layer["wuv"].astype(dt))
        k_r = jnp.broadcast_to(latent[:, :, None, kl:],
                               (b, blk, nh, cfg.qk_rope_head_dim))
        k = jnp.concatenate([k_nope, k_r], -1)
        q = jnp.concatenate([q_nope, q_rope], -1)
    with jax.named_scope("mla.attend"):
        return _local_attention(q, k, v, scale=cfg.attn_scale)


def _ffn(h, layer: dict, cfg: TransformerConfig, tp_sum, ep_axis,
         moe_info):
    """The layer's second sublayer on the normed ``h``: which kind is
    read off the layer's own parameters (leading dense layers and
    expert layers share one model). Returns (out, aux)."""
    dt = h.dtype
    zero = jnp.zeros((), jnp.float32)
    if "moe" in layer and cfg.moe_router in moe.ROUTED:
        out, info = moe.routed_ffn(layer["moe"], h, cfg)
        if moe_info is not None:
            moe_info.append(info)
        return out, zero
    if "moe" in layer:
        return moe.moe_ffn(
            layer["moe"], h, cfg.n_experts,
            capacity_factor=cfg.capacity_factor, ep_axis=ep_axis)
    if "wg" in layer:
        gate = jax.nn.silu(h @ layer["wg"].astype(dt))
        return tp_sum((gate * (h @ layer["wu"].astype(dt)))
                      @ layer["wd"].astype(dt)), zero
    h = jax.nn.gelu(h @ layer["w1"].astype(dt))
    return tp_sum(h @ layer["w2"].astype(dt)), zero


def apply_layer(x, layer: dict, cfg: TransformerConfig, *,
                sp_axis: Optional[str] = None,
                tp_axis: Optional[str] = None,
                tp_algorithm: str = "psum",
                ep_axis: Optional[str] = None,
                attention=None,
                pos: Optional[jax.Array] = None,
                moe_info: Optional[list] = None):
    """One transformer layer (attention + FFN sublayers) on activation
    ``x`` (b, blk, d). Returns (x, aux). The single source of the layer
    math — `forward` iterates it, the pipeline stage (models.pipeline)
    scans it, and the KV-cache decode (models.generate) calls it with a
    custom ``attention`` callable — so the block cannot silently
    diverge between them. ``attention(q, k, v)`` receives q as
    (b, blk, heads, head_dim) and k/v as (b, blk, KV_heads, head_dim)
    — fewer heads than q on GQA configs (the hook owns the grouping,
    so e.g. the decode cache stays compact) — and returns the q shape;
    None selects the training dispatch (local flash / ring / ulysses),
    which also attends the compact grouped K/V directly — no repeat
    is materialized anywhere on the training path.

    Latent attention (``cfg.mla``): the hook is ``attention(q_nope,
    q_rope, latent, index)`` (mla_project's outputs; ``index`` None
    without a token selector) and returns (b, blk, heads, v_head_dim);
    None attends the block in the plain form (mla_unabsorbed), which
    selects nothing: a block longer than index_topk is refused.
    ``moe_info``: a list that each routed expert layer (models.moe
    .ROUTED) appends its routing record to (models.moe.routed_ffn), for
    callers that count or check it."""
    b, blk, _ = x.shape
    dt = x.dtype
    ntp = lax.axis_size(tp_axis) if tp_axis is not None else 1
    assert cfg.n_heads % ntp == 0 and cfg.d_ff % ntp == 0, \
        f"tp={ntp} must divide n_heads {cfg.n_heads} and d_ff {cfg.d_ff}"
    nh_local = cfg.n_heads // ntp

    def tp_sum(t):
        if tp_axis is None:
            return t
        return tc.allreduce(t, tp_axis, algorithm=tp_algorithm).astype(
            t.dtype)

    def ffn_sublayer(x):
        h = _rmsnorm(x, layer["ln2"]["g"], cfg.norm_eps)
        out, aux = _ffn(h, layer, cfg, tp_sum, ep_axis, moe_info)
        return x + out, aux

    h = _rmsnorm(x, layer["ln1"]["g"], cfg.norm_eps)
    if cfg.mla:
        if sp_axis is not None or tp_axis is not None:
            raise ValueError("latent attention runs unsharded so far "
                             "(no sp_axis / tp_axis)")
        assert pos is not None, "rope needs per-layer positions"
        q_nope, q_rope, latent, index = mla_project(h, layer, cfg, pos)
        if attention is not None:
            att = attention(q_nope, q_rope, latent, index)
        else:
            check_unselected(cfg, blk)
            att = mla_unabsorbed(q_nope, q_rope, latent, layer, cfg)
        with jax.named_scope("mla.out"):
            x = x + att.reshape(b, blk, -1).astype(dt) @ layer[
                "wo"].astype(dt)
        return ffn_sublayer(x)
    if cfg.kv_heads == cfg.n_heads:
        w = layer["wqkv"].astype(dt)   # (d, 3, local heads x hd)
        qkv = h @ w.reshape(w.shape[0], -1)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        nkv_local = nh_local
    else:  # GQA
        assert cfg.kv_heads % ntp == 0, \
            f"tp={ntp} must divide n_kv_heads {cfg.kv_heads}"
        nkv_local = cfg.kv_heads // ntp
        q = h @ layer["wq"].astype(dt)
        wkv = layer["wkv"].astype(dt)
        kv = h @ wkv.reshape(wkv.shape[0], -1)
        k, v = jnp.split(kv, 2, axis=-1)

    def heads(t, n):
        return t.reshape(b, blk, n, cfg.head_dim)

    q = heads(q, nh_local)
    k, v = heads(k, nkv_local), heads(v, nkv_local)
    if cfg.qk_norm:
        with jax.named_scope("attn.qk_norm"):
            q = _rmsnorm(q, layer["q_norm"]["g"], cfg.norm_eps)
            k = _rmsnorm(k, layer["k_norm"]["g"], cfg.norm_eps)
    if cfg.pos_encoding == "rope":
        assert pos is not None, "rope needs per-layer positions"
        q = _rope_cfg(q, pos, cfg)
        k = _rope_cfg(k, pos, cfg)  # compact
        # k: pre-grouping (the hook/caches see rotated compact keys)

    # GQA K/V stay COMPACT on every dispatch path: the attention ops
    # attend grouped heads natively (the flash kernel folds the group
    # dim into its Q axis; ring rotates and ulysses all_to_alls only
    # kv_heads worth of bytes — the ICI/HBM reduction GQA exists for),
    # and a custom ``attention`` hook receives the compact heads so
    # the decode cache stores only kv_heads
    if attention is not None:
        att = attention(q, k, v)
    elif sp_axis is None:
        att = _local_attention(q, k, v, block_len=cfg.block_len)
    elif cfg.block_len:
        raise ValueError("the block-causal mask runs unsharded so far "
                         "(no sp_axis)")
    elif cfg.sp_attention == "ulysses":
        from rlo_tpu.ops.ulysses import ulysses_attention
        att = jax.vmap(lambda q_, k_, v_: ulysses_attention(
            q_, k_, v_, sp_axis, causal=True), in_axes=0)(q, k, v)
    elif cfg.sp_attention == "ring":
        att = jax.vmap(lambda q_, k_, v_: ring_attention(
            q_, k_, v_, sp_axis, causal=True), in_axes=0)(q, k, v)
    else:
        raise ValueError(
            f"unknown sp_attention {cfg.sp_attention!r}; "
            f"known: 'ring', 'ulysses'")
    att = att.reshape(b, blk, nh_local * cfg.head_dim)
    x = x + tp_sum(att @ layer["wo"].astype(dt))
    return ffn_sublayer(x)


def next_token_targets(tokens):
    """Dense (non-sp) next-token labels: shift left, zero-pad, and mask
    each row's final position. Shared by loss_fn and the pipeline's
    last-stage loss."""
    b, blk = tokens.shape
    targets = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
    valid = jnp.concatenate(
        [jnp.ones((b, blk - 1), jnp.float32),
         jnp.zeros((b, 1), jnp.float32)], axis=1)
    return targets, valid


def nll_sum(logits, targets, valid):
    """Summed masked next-token NLL and the valid-token count."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(nll * valid), jnp.sum(valid)


def nll_sum_chunked(x, embed, targets, valid, chunk: int):
    """nll_sum computed from the PRE-HEAD activations with the vocab
    axis streamed in ``chunk``-wide slices: nll = logsumexp(x·Eᵀ) −
    x·E[target], with the logsumexp accumulated online (running
    max/sumexp — flash attention's softmax trick applied to the LM
    head). Neither the (b, blk, vocab) logits nor log-probs ever
    exist; jax.checkpoint on the chunk step makes the backward
    recompute each chunk's logits instead of saving them. Exact (same
    value as nll_sum up to fp accumulation order)."""
    v, d = embed.shape
    # operands in the activation dtype, f32 accumulation — the same
    # mixed precision as the unfused head matmul (bf16 on the MXU)
    xd = x
    ed = embed.astype(x.dtype)
    tgt_logit = jnp.einsum("btd,btd->bt", xd, ed[targets],
                           preferred_element_type=jnp.float32)
    n_chunks = -(-v // chunk)
    pad = n_chunks * chunk - v
    epad = jnp.pad(ed, ((0, pad), (0, 0)))
    echunks = epad.reshape(n_chunks, chunk, d)
    # padded rows would contribute exp(0·x)=1 to the sumexp: mask them
    row_ok = (jnp.arange(n_chunks * chunk) < v).reshape(n_chunks, chunk)
    b, blk = targets.shape
    m0 = jnp.full((b, blk), -jnp.inf, jnp.float32)
    s0 = jnp.zeros((b, blk), jnp.float32)

    @jax.checkpoint
    def step(carry, ch):
        m, s = carry
        emb, ok = ch
        lg = jnp.einsum("btd,cd->btc", xd, emb,
                        preferred_element_type=jnp.float32)
        lg = jnp.where(ok[None, None, :], lg, -jnp.inf)
        m2 = jnp.maximum(m, lg.max(axis=-1))
        s = s * jnp.exp(m - m2) + jnp.exp(
            lg - m2[..., None]).sum(axis=-1)
        return (m2, s), None

    (m, s), _ = lax.scan(step, (m0, s0), (echunks, row_ok))
    lse = m + jnp.log(s)
    nll = lse - tgt_logit
    return jnp.sum(nll * valid), jnp.sum(valid)


def opt_state_pspecs(opt_state, params: dict, param_specs):
    """PartitionSpec tree for an optax optimizer state: subtrees shaped
    like the param tree (Adam moments etc.) inherit the params' specs —
    so tp/ep-sharded weights get sharded moments — and every other leaf
    (step counts, scalars) is replicated. Pass as the opt_state in/out
    spec for shard_jit alongside `param_pspecs`."""
    from jax.sharding import PartitionSpec as P
    pdef = jax.tree_util.tree_structure(params)

    def params_like(node):
        try:
            return jax.tree_util.tree_structure(node) == pdef
        except Exception:
            return False

    return jax.tree_util.tree_map(
        lambda n: param_specs if params_like(n) else P(),
        opt_state, is_leaf=params_like)


def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            sp_axis: Optional[str] = None,
            tp_axis: Optional[str] = None,
            tp_algorithm: str = "psum",
            ep_axis: Optional[str] = None,
            with_aux: bool = False):
    """Logits for next-token prediction; causal. Returns logits, or
    (logits, aux_loss) when ``with_aux`` (MoE load-balancing term; 0 for
    dense configs).

    tokens: (batch, block) int32 — `block` is the LOCAL sequence slice
    when sp_axis is set (shard r holds tokens [r*block, (r+1)*block)).

    With ``tp_axis`` the layer weights arrive sharded per `param_pspecs`:
    this device computes its n_heads/tp heads and d_ff/tp hidden units,
    and the row-parallel output projections produce partial sums that
    are combined with the framework allreduce (``tp_algorithm`` picks
    psum / ring / recursive_doubling / halving_doubling). With
    ``ep_axis`` (MoE configs) the per-expert FFN weights arrive sharded
    by expert, and tokens cross shards via all_to_all (models.moe).
    """
    x, aux_total = _features(params, tokens, cfg, sp_axis, tp_axis,
                             tp_algorithm, ep_axis)
    dt = cfg.act_dtype
    logits = (x @ head_weights(params).T.astype(dt)).astype(jnp.float32)
    if with_aux:
        return logits, aux_total
    return logits


def _features(params: dict, tokens: jax.Array, cfg: TransformerConfig,
              sp_axis: Optional[str] = None,
              tp_axis: Optional[str] = None,
              tp_algorithm: str = "psum",
              ep_axis: Optional[str] = None):
    """The transformer body up to (and including) the final norm:
    (b, blk, d) pre-head activations + the MoE aux loss. Split out of
    `forward` so the chunked loss can apply the LM head per vocab
    slice (nll_sum_chunked) instead of materializing full logits."""
    b, blk = tokens.shape
    dt = cfg.act_dtype
    if sp_axis is not None:
        pos0 = lax.axis_index(sp_axis) * blk
    else:
        pos0 = 0
    pos = pos0 + jnp.arange(blk)

    x = embed_tokens(params["embed"], tokens, pos, cfg)
    aux_total = jnp.zeros((), jnp.float32)

    def block(x, layer):
        return apply_layer(x, layer, cfg, sp_axis=sp_axis,
                           tp_axis=tp_axis, tp_algorithm=tp_algorithm,
                           ep_axis=ep_axis, pos=pos)

    if cfg.remat:
        # recompute each layer's activations in the backward pass
        block = jax.checkpoint(block)
    for layer in params["layers"]:
        x, aux = block(x, layer)
        aux_total = aux_total + aux

    return _rmsnorm(x, params["ln_f"]["g"], cfg.norm_eps), aux_total


def loss_fn(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            sp_axis: Optional[str] = None,
            tp_axis: Optional[str] = None,
            ep_axis: Optional[str] = None) -> jax.Array:
    """Mean next-token cross-entropy (+ the MoE load-balancing aux term
    for expert configs). With sp sharding, the label for a shard's last
    position is the next shard's first token — one ppermute — and the
    final global position is masked out."""
    x, aux = _features(params, tokens, cfg, sp_axis, tp_axis,
                       ep_axis=ep_axis)
    b, blk = tokens.shape
    if sp_axis is None:
        targets, valid = next_token_targets(tokens)
    else:
        ws = lax.axis_size(sp_axis)
        idx = lax.axis_index(sp_axis)
        # shard r receives shard (r+1)'s first column: ppermute r+1 -> r
        nxt_first = lax.ppermute(tokens[:, :1], sp_axis,
                                 list(topology.ring_perm(ws, -1)))
        targets = jnp.concatenate([tokens[:, 1:], nxt_first], axis=1)
        is_last_shard = (idx == ws - 1)
        valid = jnp.concatenate(
            [jnp.ones((b, blk - 1), jnp.float32),
             jnp.where(is_last_shard, 0.0, 1.0) * jnp.ones(
                 (b, 1), jnp.float32)], axis=1)
    chunk = cfg.loss_vocab_chunk or 0
    if chunk:
        local, count = nll_sum_chunked(x, head_weights(params), targets,
                                       valid, chunk)
    else:
        logits = (x @ head_weights(params).T.astype(cfg.act_dtype)) \
            .astype(jnp.float32)
        local, count = nll_sum(logits, targets, valid)
    if sp_axis is not None:
        local = lax.psum(local, sp_axis)
        count = lax.psum(count, sp_axis)
    loss = local / count
    if cfg.n_experts > 0 and cfg.moe_router == "switch":
        if sp_axis is not None:
            # each sp shard routed its own token slice: average the
            # local aux terms so the total loss is sp-invariant like
            # the cross-entropy term
            aux = lax.pmean(aux, sp_axis)
        loss = loss + cfg.moe_aux_coef * aux
    return loss


def _vma_active(axis: str) -> bool:
    """Whether varying-manual-axes typing is live for ``axis``.

    Probed by pcasting a fresh scalar to varying: under check_vma=True
    the result's vma contains the axis; under check_vma=False `.vma` is
    an empty frozenset for EVERYTHING — which must not be mistaken for
    'already reduced'."""
    try:
        probe = lax.pcast(jnp.zeros(()), (axis,), to="varying")
        return axis in jax.typeof(probe).vma
    except (AttributeError, TypeError, ValueError):
        return False


def train_step(params: dict, tokens: jax.Array, cfg: TransformerConfig,
               lr: float = 1e-2, sp_axis: Optional[str] = None,
               dp_axis: Optional[str] = None,
               tp_axis: Optional[str] = None,
               ep_axis: Optional[str] = None,
               grad_algorithm: str = "psum",
               dcn_axis: Optional[str] = None,
               dcn_algorithm: str = "psum"):
    """One SGD step; returns (new_params, loss). Run under shard_jit
    (check_vma=True by default).

    Gradient synchronization. Under varying-manual-axes typing, the
    reductions of replicated-param grads over sp, tp, AND dp are
    inserted by shard_map's AD itself (lowering to XLA AllReduce — the
    optimal 2(n-1)/n schedule; grads of tp-sharded matrices stay local,
    as they must); this function then only rescales by the dp size.
    The EXPLICIT framework combine — grad_algorithm='ring': ppermute
    ring with the Pallas fused per-step combine, the BASELINE benchmark
    path — engages on a pure-dp mesh under shard_jit(...,
    check_vma=False), where per-shard grads are well-defined without vma
    bookkeeping (no collective appears in the forward). A manual-ring
    result cannot be typed invariant under vma (only psum is), so vma
    runs route dp through the automatic path regardless of
    grad_algorithm.
    """
    loss, grads = grads_and_loss(params, tokens, cfg, sp_axis=sp_axis,
                                 dp_axis=dp_axis, tp_axis=tp_axis,
                                 ep_axis=ep_axis,
                                 grad_algorithm=grad_algorithm,
                                 dcn_axis=dcn_axis,
                                 dcn_algorithm=dcn_algorithm)
    new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
    return new_params, loss


def grads_and_loss(params: dict, tokens: jax.Array,
                   cfg: TransformerConfig,
                   sp_axis: Optional[str] = None,
                   dp_axis: Optional[str] = None,
                   tp_axis: Optional[str] = None,
                   ep_axis: Optional[str] = None,
                   grad_algorithm: str = "psum",
                   dcn_axis: Optional[str] = None,
                   dcn_algorithm: str = "psum"):
    """(loss, fully-synchronized grads) — the shared gradient pipeline
    behind train_step (plain SGD) and train_step_optax.

    ``dcn_axis``: second, slower data-parallel tier (multi-slice DP,
    one mesh axis per make_multislice_mesh). On the explicit combine
    path the dp gradient sync becomes
    tpu_collectives.hierarchical_allreduce — reduce-scatter in-slice,
    cross-slice allreduce on only the scattered shard, all-gather
    in-slice — so per-chip DCN bytes shrink by the in-slice dp size.
    Under vma typing, AD inserts the (already hierarchical-aware) XLA
    AllReduce over both axes and only the rescale differs."""
    if sp_axis is not None or tp_axis is not None or ep_axis is not None:
        # without vma typing the sp/tp/ep cotangent reductions never
        # happen and every shard would silently take a different step
        assert _vma_active(sp_axis or tp_axis or ep_axis), (
            "sp/tp/ep training requires shard_jit's vma typing "
            "(check_vma=True); only the pure-dp explicit-ring path may "
            "run with check_vma=False")
    loss, grads = jax.value_and_grad(loss_fn)(params, tokens, cfg, sp_axis,
                                              tp_axis, ep_axis)
    if dcn_axis is not None and dp_axis is None:
        raise ValueError("dcn_axis requires dp_axis (it is the second, "
                         "cross-slice tier of data parallelism)")
    if dp_axis is not None:
        n = lax.axis_size(dp_axis)
        if dcn_axis is not None:
            n *= lax.axis_size(dcn_axis)
        if _vma_active(dp_axis):
            if dcn_axis is not None and dcn_algorithm != "psum":
                # unlike grad_algorithm (whose vma fallback is also
                # psum-shaped, just XLA's own), a silently-dropped
                # int8 request means the user believes DCN traffic is
                # compressed when it is not — refuse instead
                raise ValueError(
                    f"dcn_algorithm={dcn_algorithm!r} requires the "
                    f"explicit combine path: run under "
                    f"shard_jit(..., check_vma=False); the vma path's "
                    f"AD-inserted AllReduce cannot be compressed")
            # vma AD already summed grads over dp (and dcn); rescale
            grads = jax.tree.map(lambda g: g / n, grads)
        elif dcn_axis is not None:
            # two-tier explicit combine: in-slice RS, DCN allreduce of
            # the scattered shard only, in-slice AG
            grads = jax.tree.map(
                lambda g: tc.hierarchical_allreduce(
                    g, dp_axis, dcn_axis,
                    dcn_algorithm=dcn_algorithm) / n,
                grads)
        else:
            # explicit framework combine of per-shard grads
            grads = jax.tree.map(
                lambda g: tc.allreduce(g, dp_axis,
                                       algorithm=grad_algorithm) / n,
                grads)
        loss = lax.pmean(loss, dp_axis)
        if dcn_axis is not None:
            loss = lax.pmean(loss, dcn_axis)
    if ep_axis is not None:
        # ep is a second data axis: tokens are sharded over it, so the
        # (vma-inserted) cross-shard grad sums — psum for replicated
        # params, the all_to_all transpose for expert weights — need the
        # same 1/n rescale as dp, and the local losses average
        nep = lax.axis_size(ep_axis)
        grads = jax.tree.map(lambda g: g / nep, grads)
        loss = lax.pmean(loss, ep_axis)
    return loss, grads


def train_step_optax(params: dict, opt_state, tokens: jax.Array,
                     cfg: TransformerConfig, optimizer,
                     sp_axis: Optional[str] = None,
                     dp_axis: Optional[str] = None,
                     tp_axis: Optional[str] = None,
                     ep_axis: Optional[str] = None,
                     grad_algorithm: str = "psum",
                     dcn_axis: Optional[str] = None,
                     dcn_algorithm: str = "psum"):
    """One optimizer step with any optax GradientTransformation
    (`optimizer.init(params)` builds opt_state); returns
    (new_params, new_opt_state, loss). Optimizer state mirrors the
    param tree, so tp/ep-sharded leaves carry sharded moments — the
    update math is elementwise and runs shard-local.
    """
    import optax

    loss, grads = grads_and_loss(params, tokens, cfg, sp_axis=sp_axis,
                                 dp_axis=dp_axis, tp_axis=tp_axis,
                                 ep_axis=ep_axis,
                                 grad_algorithm=grad_algorithm,
                                 dcn_axis=dcn_axis,
                                 dcn_algorithm=dcn_algorithm)
    updates, opt_state = optimizer.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, loss
