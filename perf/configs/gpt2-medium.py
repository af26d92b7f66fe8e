"""The plain reference of the ``gpt2-medium`` configuration: the forward
pass and the loss as straightforward ``jax.numpy`` in float32, with no
kernels, no cache and no batching tricks, independent of ``rlo_tpu``'s
model code. It computes the published GPT-2 block with the four departures
that ``gpt2-medium.json`` names (gain-only RMS norm, no linear biases,
sine/cosine positions, random weights), on the program's parameter tree:

    embed (V, d) tied with the output head; ln_f.g (d,); per layer
    ln1.g, wqkv (d, 3, d) [q|k|v, heads x head_dim flattened last],
    wo (d, d), ln2.g, w1 (d, f), w2 (f, d).

On a TPU a float32 matmul runs in lower precision unless the precision is
raised, so ``logits`` and ``loss`` run under ``highest``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

RMS_EPS = 1e-6


def _rms_norm(x, g):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + RMS_EPS) * g


def _positions(n, d):
    half = d // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half) / half)
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, layer, n_heads):
    b, n, d = x.shape
    hd = d // n_heads
    h = _rms_norm(x, layer["ln1"]["g"])
    qkv = jnp.einsum("bnd,dce->bnce", h, layer["wqkv"])
    q, k, v = (qkv[:, :, c].reshape(b, n, n_heads, hd) for c in range(3))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, n, d)
    x = x + att @ layer["wo"]
    h = _rms_norm(x, layer["ln2"]["g"])
    return x + _gelu_new(h @ layer["w1"]) @ layer["w2"]


def logits(params, tokens, model: dict):
    """(b, n) int tokens -> (b, n, V) float32 logits."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = p["embed"][tokens] + _positions(tokens.shape[1],
                                            model["d_model"])[None]
        for layer in p["layers"]:
            x = _block(x, layer, model["n_heads"])
        x = _rms_norm(x, p["ln_f"]["g"])
        return x @ p["embed"].T


def loss(params, tokens, model: dict):
    """Mean next-token cross-entropy over every position but the last."""
    lg = logits(params, tokens, model)[:, :-1]
    logp = jax.nn.log_softmax(lg, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)
