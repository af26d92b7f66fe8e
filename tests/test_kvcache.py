"""models.kvcache: the one owner of a cache entry's format.

Every write of every kind of entry against a plain numpy placement, through
the XLA arm and through the Pallas arm (interpret mode); the paged step
functions against the dense ones at a configuration whose norm eps and head
differ from the defaults (the copies of the layer loop they carried through
PR 28 had drifted there); and a reading of the sources: nothing above
kvcache.py names a tensor of an entry or a cache-write kernel."""

import ast
import dataclasses
import io
import tokenize
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models import kvcache
from rlo_tpu.models.generate import block_decode, decode_step
from rlo_tpu.models.paged import (init_page_pool, paged_decode_step,
                                  paged_prefill_chunk)
from rlo_tpu.models.transformer import TransformerConfig, init_params
from rlo_tpu.pallas import reduce

MODELS = Path(kvcache.__file__).resolve().parent
BASE = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                         d_ff=64, dtype="float32")
KINDS = {
    "mha": BASE,
    "gqa": dataclasses.replace(BASE, n_kv_heads=2, pos_encoding="rope"),
    "int8": dataclasses.replace(BASE, kv_cache_dtype="int8"),
    "latent": dataclasses.replace(
        BASE, pos_encoding="rope", q_lora_rank=16, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8),
}
B, MAX_LEN = 3, 256     # two 128-lane blocks: both write kernels' gates open


def _entry(kind, rng):
    """A cache entry of the kind, every element set (a write must leave
    what it does not address as it was), as numpy."""
    lc = kvcache.init_kv_cache(KINDS[kind], B, MAX_LEN)[0]
    out = {}
    for name, a in lc.items():
        if a.dtype == jnp.int8:
            out[name] = rng.integers(-127, 128, a.shape).astype(np.int8)
        else:
            out[name] = rng.standard_normal(a.shape).astype(np.float32)
    return out


def _new(kind, rng, T):
    """What apply_layer's hook hands a step for T new tokens."""
    cfg = KINDS[kind]
    if cfg.mla:
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return (rng.standard_normal((B, T, width)).astype(np.float32),)
    shape = (B, T, cfg.kv_heads, cfg.head_dim)
    return (rng.standard_normal(shape).astype(np.float32),
            rng.standard_normal(shape).astype(np.float32))


def _quantize(x):
    """_quantize_kv in numpy: symmetric int8 over the last axis."""
    amax = np.abs(x).max(-1)
    scale = (np.maximum(amax, np.float32(1e-30))
             / np.float32(127.0)).astype(np.float32)
    return np.round(x / scale[..., None]).astype(np.int8), scale


def _place(entry, new, cols):
    """The plain placement: token t of batch row b goes to column
    cols[b, t] of every tensor of the entry; a column at or past max_len
    is dropped."""
    want = {name: a.copy() for name, a in entry.items()}
    if len(new) == 1:                       # latent rows (b, T, width)
        vals = {"k": new[0][:, :, None, :]}          # one "head"
    else:
        vals = {"k": new[0], "v": new[1]}
        if "ks" in entry:
            (vals["k"], vals["ks"]), (vals["v"], vals["vs"]) = \
                _quantize(new[0]), _quantize(new[1])
    for b in range(cols.shape[0]):
        for t in range(cols.shape[1]):
            col = cols[b, t]
            if col >= MAX_LEN:
                continue
            for name, x in vals.items():
                if x.ndim == 4:     # (b, T, heads, dim) -> [b, :, :, col]
                    want[name][b, :, :, col] = x[b, t]
                else:               # scales (b, T, heads) -> [b, :, col]
                    want[name][b, :, col] = x[b, t]
    return want


def _row_ragged(entry, kind, rng):
    pos = np.array([5, 127, MAX_LEN + 44])      # the last row is dropped
    new = _new(kind, rng, 1)
    got = kvcache.write_row(entry, kvcache.new_row(entry, *new),
                            jnp.asarray(pos, jnp.int32))
    return got, new, pos[:, None]


def _row_scalar(entry, kind, rng):
    new = _new(kind, rng, 1)
    got = kvcache.write_row(entry, kvcache.new_row(entry, *new),
                            jnp.int32(130))
    return got, new, np.full((B, 1), 130)


def _block(entry, kind, rng):
    T = 6       # crosses a 128-lane block; crosses max_len; neither
    pos0 = np.array([125, MAX_LEN - 3, 0])
    cols = pos0[:, None] + np.arange(T)
    new = _new(kind, rng, T)
    got = kvcache.write_block(entry, kvcache.new_block(entry, *new),
                              jnp.asarray(pos0, jnp.int32),
                              jnp.asarray(cols, jnp.int32))
    return got, new, cols


def _prompt(entry, kind, rng):
    plen = 9
    new = _new(kind, rng, plen)
    got, k_seen, v_seen = kvcache.store_prompt(entry, *new)
    if "ks" in entry:   # the attend sees what decode will read back
        for x, seen in zip(new, (k_seen, v_seen)):
            q, s = _quantize(x)
            np.testing.assert_allclose(
                np.asarray(seen), q.astype(np.float32) * s[..., None],
                rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(k_seen), new[0])
    return got, new, np.broadcast_to(np.arange(plen), (B, plen))


def _tail_fold(entry, kind, rng):
    kk = 4
    pos0 = np.array([126, MAX_LEN - 2, 3])
    new = _new(kind, rng, kk)
    tail = kvcache.init_kv_tail([entry], kk)[0]
    for s in range(kk):
        row = kvcache.new_row(entry, *(x[:, s:s + 1] for x in new))
        tail = kvcache.store_tail_row(tail, row, jnp.int32(s))
    got = kvcache.fold_kv_tail([entry], [tail],
                               jnp.asarray(pos0, jnp.int32))[0]
    return got, new, pos0[:, None] + np.arange(kk)


WRITES = {"row_ragged": _row_ragged, "row_scalar": _row_scalar,
          "block_across_128": _block, "prompt_block": _prompt,
          "tail_then_fold": _tail_fold}


@pytest.mark.parametrize("arm", ["xla", "kernel"])
@pytest.mark.parametrize("write", list(WRITES))
@pytest.mark.parametrize("kind", list(KINDS))
def test_write_equals_the_plain_placement(monkeypatch, kind, write, arm):
    """Each write of kvcache, on each kind of entry, through each arm of
    its ladder, leaves exactly what a column-by-column numpy placement
    leaves: new values (quantized, with their scales, in an int8 entry)
    in their columns, columns at or past max_len dropped, the rest of
    every tensor untouched."""
    if arm == "kernel":     # the gates open; the kernels interpret
        monkeypatch.setattr(reduce, "_on_tpu", lambda: True)
    rng = np.random.default_rng(29)
    entry = _entry(kind, rng)
    as_jax = {name: jnp.asarray(a) for name, a in entry.items()}
    if write == "tail_then_fold" and kind == "int8":
        assert not kvcache.keeps_tail([as_jax])
        with pytest.raises(ValueError, match="no write-behind tail"):
            kvcache.init_kv_tail([as_jax], 4)
        return
    got, new, cols = WRITES[write](as_jax, kind, rng)
    want = _place(entry, new, cols)
    assert set(got) == set(want)
    for name in want:
        assert got[name].dtype == as_jax[name].dtype
        np.testing.assert_array_equal(np.asarray(got[name]), want[name],
                                      err_msg=name)


def test_paged_steps_equal_dense_steps_where_eps_and_head_differ():
    """paged_prefill_chunk / paged_decode_step against block_decode /
    decode_step at norm_eps 1e-5 and an untied head: one layer loop and
    one head (generate._forward, _head), so what the dense step knows of
    the model the paged step knows too."""
    cfg = dataclasses.replace(BASE, n_layers=2, norm_eps=1e-5,
                              tie_embeddings=False)
    params = init_params(jax.random.PRNGKey(3), cfg)
    assert "head" in params
    ps, max_len, plen, steps = 16, 64, 10, 3
    toks = np.asarray(jax.random.randint(
        jax.random.PRNGKey(4), (2, plen + steps), 0, cfg.vocab))
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pools = init_page_pool(cfg, 9, ps)
    cache = kvcache.init_kv_cache(cfg, 2, max_len)

    # the prompts: one padded chunk a slot / one block of the dense cache
    padded = np.zeros((2, ps), np.int32)
    padded[:, :plen] = toks[:, :plen]
    want, cache = block_decode(params, jnp.asarray(padded),
                               jnp.zeros((2,), jnp.int32), cache, cfg)
    for slot in range(2):
        got, pools = paged_prefill_chunk(
            params, jnp.asarray(padded[slot:slot + 1]), 0, plen, pools,
            table[slot:slot + 1], cfg)
        np.testing.assert_allclose(np.asarray(got[0]),
                                   np.asarray(want[slot, plen - 1]),
                                   rtol=1e-5, atol=1e-5)
    active = jnp.asarray([True, True])
    for s in range(steps):
        pos = jnp.full((2,), plen + s, jnp.int32)
        tok = jnp.asarray(toks[:, plen + s])
        want, cache = decode_step(params, tok, pos, cache, cfg)
        got, pools = paged_decode_step(params, tok, pos, pools, table,
                                       active, cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # and the default eps or the tied head would have shown
    tied = dict(params)
    tied.pop("head")
    drift, _ = paged_decode_step(tied, tok, pos, pools, table, active,
                                 dataclasses.replace(cfg,
                                                     tie_embeddings=True))
    assert np.abs(np.asarray(drift) - np.asarray(want)).max() > 1e-3


def _format_names(source: str):
    """What in ``source`` names a tensor of an entry beyond "k" and "v",
    or a cache-write kernel or its gate: string literals "ks" / "vs" and
    identifiers write_kv_* / can_write_* (comments and docstrings may
    speak of them)."""
    found = []
    for tok in tokenize.generate_tokens(io.StringIO(source).readline):
        if tok.type == tokenize.STRING and tok.string.strip("'\"") in (
                "ks", "vs"):
            found.append((tok.start[0], tok.string))
        if tok.type == tokenize.NAME and tok.string.startswith(
                ("write_kv_", "can_write_")):
            found.append((tok.start[0], tok.string))
    return found


def test_nothing_above_kvcache_names_a_tensor_or_a_write_kernel():
    """The format is known in kvcache.py, in paged.py's pool functions
    and in pallas/decode.py: the step functions, the server and the
    speculative loop name neither a scale sidecar nor a write kernel."""
    for name in ("generate.py", "serve.py", "speculative.py"):
        assert _format_names((MODELS / name).read_text()) == [], name
    paged = (MODELS / "paged.py").read_text()
    steps = {node.name: ast.get_source_segment(paged, node)
             for node in ast.parse(paged).body
             if isinstance(node, ast.FunctionDef)
             and node.name in ("paged_decode_step", "paged_prefill_chunk")}
    assert len(steps) == 2
    for name, source in steps.items():
        assert _format_names(source) == [], name
    # the reading finds what it looks for where it is allowed
    assert _format_names((MODELS / "kvcache.py").read_text())
