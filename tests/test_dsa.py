"""Learned sparse attention over a latent cache (DeepSeek-V3.2's token
selector): the index keys beside the latent rows in one cache entry, the
score kernel, the exact top-k, the attend over the chosen positions and
the long-prompt admission, against the plain reference of the
``deepseek-v3.2-ep32`` configuration (perf/configs/deepseek-v3.2-ep32.py)
at small widths on the CPU. Activations are float32 so that selections
are exact wherever the reference's margin is clear of rounding."""

import dataclasses
import importlib.util
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rlo_tpu.models import kvcache, moe
from rlo_tpu.models.generate import (block_decode, decode_step,
                                     init_kv_cache, prefill)
from rlo_tpu.models.serve import DecodeServer, extend_widths
from rlo_tpu.models.transformer import (TransformerConfig, forward,
                                        init_params)
from rlo_tpu.pallas.decode import (decode_work_list, flash_decode,
                                   index_score, index_score_tile)
from rlo_tpu.utils.metrics import Registry

PERF = Path(__file__).resolve().parent.parent / "perf"


def _load(name):
    path = PERF / "configs" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "ref_" + name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("deepseek-v3.2-ep32")
CONFIG = json.loads((PERF / "configs" / "deepseek-v3.2-ep32.json")
                    .read_text())
V3 = json.loads((PERF / "configs" / "deepseek-v3-ep16.json").read_text())
GPT2 = json.loads((PERF / "configs" / "gpt2-medium.json").read_text())

#: the configuration's ``tiny`` overlay in float32: one dense and one
#: expert layer, 4 index heads of 32, the 16 best positions kept
MODEL = dict(CONFIG["tiny"]["model"], dtype="float32",
             param_dtype="float32", n_layers=2)
CFG = TransformerConfig(**MODEL)
TOPK = CFG.index_topk
N, MAX_LEN = 96, 128


@pytest.fixture(scope="module")
def params():
    return init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(8), (2, N), 0, CFG.vocab)


@pytest.fixture(scope="module")
def reference(params, tokens):
    """The reference's full forward on its own choices: logits and, per
    layer, its index scores and selections at every position."""
    logits, records = jax.jit(lambda p, t: REF.forward(p, t, MODEL))(
        params, tokens)
    return np.asarray(logits), jax.tree.map(np.asarray, records)


def _close(got, want, rtol=2e-4):
    got, want = np.asarray(got), np.asarray(want)
    assert np.abs(got - want).max() <= rtol * np.abs(want).max(), (
        np.abs(got - want).max(), np.abs(want).max())


def _margin(scores, k):
    """How far each row's k-th best score is from its (k + 1)-th."""
    top = -np.sort(-np.where(np.isfinite(scores), scores, -1e30), -1)
    return top[..., k - 1] - top[..., k]


# ---- the configuration file -------------------------------------------

def test_config_file_keeps_every_published_key_and_no_width_differs():
    pub, reduced = CONFIG["published"], set(CONFIG["reduced"])
    assert reduced == {"num_hidden_layers", "first_k_dense_replace",
                       "n_routed_experts", "vocab_size",
                       "num_nextn_predict_layers"}
    assert set(CONFIG["reduced_how"]) == reduced
    for key, value in pub.items():
        assert key in CONFIG, key
        if key not in reduced:
            assert CONFIG[key] == value, key
    # what V3.2 adds to V3 is three keys, a mechanism and no size
    assert {k for k in pub if k not in V3["published"]} == {
        "index_head_dim", "index_n_heads", "index_topk"}
    assert all(pub[k] == v for k, v in V3["published"].items()
               if k != "model_type")
    m = CONFIG["model"]
    assert (m["index_n_heads"], m["index_head_dim"], m["index_topk"]) == (
        pub["index_n_heads"], pub["index_head_dim"], pub["index_topk"])
    assert (m["d_model"], m["q_lora_rank"], m["kv_lora_rank"],
            m["n_heads"], m["moe_d_ff"], m["experts_per_tok"]) == (
        pub["hidden_size"], pub["q_lora_rank"], pub["kv_lora_rank"],
        pub["num_attention_heads"], pub["moe_intermediate_size"],
        pub["num_experts_per_tok"])
    assert (m["n_layers"], m["n_dense_layers"], m["n_experts_held"],
            m["vocab"], m["n_experts"]) == (
        CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
        CONFIG["n_routed_experts"], CONFIG["vocab_size"],
        pub["n_routed_experts"])
    assert "32 chips" in CONFIG["deployment"]
    for name in ("index_score_precision", "index_hadamard",
                 "index_rope_pairing", "index_key_storage", "max_len"):
        assert name in CONFIG["assumed"], name
    cfg = TransformerConfig(**m)  # every key is a setting the program has
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, 2, 256))
    assert kvcache.bytes_per_position(cache) == 7040


# ---- index_topk == 0 changes nothing -----------------------------------

@pytest.mark.parametrize("model", [V3["tiny"]["model"],
                                   GPT2["tiny"]["model"]],
                         ids=["deepseek-v3", "gpt2"])
def test_no_selector_no_index_keys(model):
    cfg = TransformerConfig(**model)
    assert not cfg.dsa
    cache = init_kv_cache(cfg, 2, 128)
    want = ["k"] if cfg.mla else ["k", "v"]
    for entry in cache:
        assert "ik" not in entry
        assert [name for name, _ in kvcache._tensors(entry)] == want
    assert kvcache.keeps_tail(cache)
    layer = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))["layers"][-1]
    assert not {"wiq", "wik", "ik_norm", "wiw"} & set(layer)


def test_selector_weights_leave_the_other_draws_alone(params):
    """The V3 configuration of the same seed draws the same weights."""
    plain = init_params(jax.random.PRNGKey(7), dataclasses.replace(
        CFG, index_topk=0, index_n_heads=0, index_head_dim=0))
    for got, want in zip(params["layers"], plain["layers"]):
        assert set(got) - set(want) == {"wiq", "wik", "ik_norm", "wiw"}
        for name in ("wdq", "wuq", "wdkv", "wuk", "wuv", "wo"):
            np.testing.assert_array_equal(got[name], want[name])


# ---- the exact top-k ----------------------------------------------------

@pytest.mark.parametrize("n,k,ties", [(200, 17, False), (200, 17, True),
                                      (64, 64, True), (40, 64, False),
                                      (300, 1, True), (257, 256, True)])
def test_topk_mask_is_the_stable_sort(n, k, ties):
    s = jax.random.normal(jax.random.PRNGKey(n + k), (3, 5, n))
    if ties:        # quarter steps, and rows of -inf past a "context"
        s = jnp.round(s * 4) / 4
        s = jnp.where(jnp.arange(n) < n - 7, s, -jnp.inf)
    got = np.asarray(jax.jit(lambda s: kvcache.topk_mask(s, k))(s))
    order = np.argsort(-np.asarray(s), axis=-1, kind="stable")[..., :k]
    want = np.zeros(s.shape, bool)
    np.put_along_axis(want, order, True, -1)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, np.asarray(REF.select_topk(s, k)) | (got & ~np.isfinite(s)))


# ---- the score kernel ---------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_index_score_kernel_against_its_einsum(dtype):
    b, hi, di, L = 3, 4, 32, 512
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(k1, (b, hi, di), dtype)
    w = jax.random.normal(k2, (b, hi), jnp.float32)
    ik = jax.random.normal(k3, (b, 1, di, L), dtype)
    pos = jnp.array([5, 300, 511], jnp.int32)
    bk = index_score_tile(L, 128)
    assert bk == 128
    work = decode_work_list(pos, 1, bk, L // bk)
    assert int(work[2]) == 1 + 3 + 4       # live tiles only
    got = index_score(q, w, ik, pos, work=work, block_k=128,
                      interpret=True)
    want = kvcache._index_scores({"q": q[:, None], "w": w[:, None]}, ik,
                                 pos[:, None])[:, 0]
    live = np.arange(L)[None] <= np.asarray(pos)[:, None]
    assert np.isneginf(np.asarray(got)[~live]).all()
    _close(np.asarray(got)[live], np.asarray(want)[live],
           2e-6 if dtype == jnp.float32 else 2e-2)
    own = index_score(q, w, ik, pos, block_k=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(got))


def test_selected_attend_kernel_against_the_masked_einsum():
    b, H, d, vd, L = 2, 4, 144, 128, 256
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(k1, (b, 1, H, d))
    cache = jax.random.normal(k2, (b, 1, d, L))
    pos = jnp.array([100, 255], jnp.int32)
    live = jnp.arange(L)[None] <= pos[:, None]
    select = live & (jax.random.uniform(k3, (b, L)) < 0.3)
    got = flash_decode(q, cache, None, pos, 0.2, v_dim=vd, block_k=128,
                       select=select, interpret=True)
    want = kvcache._attend_cache(q, cache, None, pos, 0.2, v_dim=vd,
                                 select=select[:, None], use_flash=False)
    _close(got, want, 1e-5)
    # every live position selected: to the bit the call without it
    np.testing.assert_array_equal(
        np.asarray(flash_decode(q, cache, None, pos, 0.2, v_dim=vd,
                                block_k=128, select=live, interpret=True)),
        np.asarray(flash_decode(q, cache, None, pos, 0.2, v_dim=vd,
                                block_k=128, interpret=True)))
    with pytest.raises(ValueError, match="selection"):
        flash_decode(q, cache, None, pos, 0.2, v_dim=vd, select=select[:1],
                     interpret=True)


def test_a_one_query_block_takes_its_selection_to_the_kernel(monkeypatch):
    """T == 1 through the block attend's kernel path: the selection goes
    with it (without it the kernel would attend the whole context)."""
    from rlo_tpu.pallas import decode
    b, H, d, vd, L = 2, 4, 144, 128, 256
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k1, (b, 1, H, d))
    cache = jax.random.normal(k2, (b, 1, d, L))
    pos = jnp.array([100, 255], jnp.int32)
    select = (jnp.arange(L)[None] <= pos[:, None]) & (
        jax.random.uniform(k3, (b, L)) < 0.3)
    kernel = decode.flash_block_decode
    monkeypatch.setattr(
        decode, "flash_block_decode",
        lambda *a, **kw: kernel(*a, block_k=128, interpret=True, **kw))
    got = kvcache._attend_cache_block(
        q, cache, None, pos[:, None], 0.2, pos0=pos, v_dim=vd,
        use_flash=True, select=select[:, None])
    want = kvcache._attend_cache_block(
        q, cache, None, pos[:, None], 0.2, v_dim=vd, use_flash=False,
        select=select[:, None])
    _close(got, want, 1e-5)


def _loops(fn, *shapes):
    """Does ``fn``'s program at these (shape, dtype) operands hold a loop."""
    text = str(jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, d)
                                    for s, d in shapes)))
    return "while[" in text


@pytest.mark.parametrize("name,max_len,blocked", [
    ("deepseek-v3-ep16", 4096, False), ("deepseek-v3.2-ep32", 24576, True)])
def test_which_latent_block_attend_a_cells_128_token_chunk_takes(
        name, max_len, blocked):
    """At the cells' own shapes (traced, never run): deepseek-v3-ep16's
    extend chunk forms its (heads, 128, 4096) scores in one einsum, as
    before this configuration; the same chunk over 24 576 positions goes
    context tile by context tile."""
    model = json.loads((PERF / "configs" / f"{name}.json").read_text())[
        "model"]
    H = model["n_heads"]
    d = model["kv_lora_rank"] + model["qk_rope_head_dim"]

    def attend(q, cache, pos_q):
        return kvcache._attend_cache_block(q, cache, None, pos_q, 0.1,
                                           v_dim=model["kv_lora_rank"])

    assert _loops(attend, ((1, 128, H, d), jnp.float32),
                  ((1, 1, d, max_len), jnp.bfloat16),
                  ((1, 128), jnp.int32)) == blocked


@pytest.mark.parametrize("selected", [False, True])
def test_blocked_latent_attend_is_the_einsum(monkeypatch, selected):
    b, T, H, d, vd, L = 2, 5, 4, 48, 32, 256
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(6), 3)
    q = jax.random.normal(k1, (b, T, H, d))
    cache = jax.random.normal(k2, (b, 1, d, L))
    pos_q = jnp.array([[60], [130]]) + jnp.arange(T)[None]
    select = None
    if selected:
        select = jax.random.uniform(k3, (b, T, L)) < 0.5
        select = select | (jnp.arange(L) == 0)      # never an empty set
    want = kvcache._attend_cache_block(q, cache, None, pos_q, 0.2,
                                       v_dim=vd, select=select)
    monkeypatch.setattr(kvcache, "_CTX_TILE", 64)
    got = kvcache._attend_latent_blocked(q, cache, pos_q, 0.2, vd, select)
    _close(got, want, 1e-5)
    # and the block attend takes that path by itself past the budget
    monkeypatch.setattr(kvcache, "_EINSUM_SCORE_BYTES", 1)
    np.testing.assert_array_equal(
        np.asarray(kvcache._attend_cache_block(
            q, cache, None, pos_q, 0.2, v_dim=vd, select=select)),
        np.asarray(got))


# ---- the entry's two tensors through every write ------------------------

def test_index_keys_ride_every_write_of_the_entry(params, tokens):
    cache = init_kv_cache(CFG, 2, MAX_LEN)
    assert [n for n, _ in kvcache._tensors(cache[0])] == ["k", "ik"]
    assert cache[0]["ik"].shape == (2, 1, CFG.index_head_dim, MAX_LEN)
    assert kvcache.bytes_per_position(cache) == 4 * CFG.n_layers * (
        CFG.kv_lora_rank + CFG.qk_rope_head_dim + CFG.index_head_dim)
    # no write-behind tail: the newest rows are scored with the others
    assert not kvcache.keeps_tail(cache)
    with pytest.raises(ValueError, match="tail"):
        kvcache.init_kv_tail(cache, 4)
    # prompt store, block write and row write agree on what they store
    _, whole = prefill(params, tokens[:, :12], cache, CFG)
    _, head = prefill(params, tokens[:, :8], cache, CFG)
    _, blocks = block_decode(params, tokens[:, 8:11], jnp.array([8, 8]),
                             head, CFG)
    _, rows = decode_step(params, tokens[:, 11], jnp.array([11, 11]),
                          blocks, CFG)
    for a, b in zip(whole, rows):
        for name in ("k", "ik"):
            _close(b[name][..., :12], a[name][..., :12], 1e-5)
            assert not np.asarray(b[name][..., 12:]).any()
    # a server's scatter carries both
    pool = init_kv_cache(CFG, 3, MAX_LEN)
    row = jax.tree.map(lambda a: a[1:2], whole)
    pool = kvcache.scatter_slot(pool, row, jnp.int32(2))
    for a, b in zip(pool, whole):
        for name in ("k", "ik"):
            np.testing.assert_array_equal(a[name][2], b[name][1])
            assert not np.asarray(a[name][:2]).any()


# ---- against the reference ----------------------------------------------

def _through_the_cache(params, tokens, plen, chunk, steps):
    """Bucket prefill of ``plen`` tokens, block_decode chunks of
    ``chunk`` up to N - steps, then decode steps. Returns (logits by
    position, per layer the (b, N, N) sets each position attended)."""
    b = tokens.shape[0]
    cache = init_kv_cache(CFG, b, MAX_LEN)
    got = {}
    chosen = [np.tril(np.ones((N, N), bool))[None].repeat(b, 0)
              for _ in range(CFG.n_layers)]
    lg, cache = jax.jit(lambda p, t, c: prefill(p, t, c, CFG))(
        params, tokens[:, :plen], cache)
    got[plen - 1] = lg

    def keep(info, at):
        for layer, rec in zip(chosen, info):
            layer[:, at] = np.asarray(rec["select"])[:, :, :N]

    def extend(p, t, m, c):
        info = []
        lg, c = block_decode(p, t, m, c, CFG, dsa_info=info)
        return lg, c, info

    def one(p, t, m, c):
        info = []
        lg, c = decode_step(p, t, m, c, CFG, dsa_info=info)
        return lg, c, info

    extend, one = jax.jit(extend), jax.jit(one)
    off = plen
    while off < N - steps:
        n = min(chunk, N - steps - off)
        lg, cache, info = extend(params, tokens[:, off:off + n],
                                 jnp.full((b,), off, jnp.int32), cache)
        keep(info, slice(off, off + n))
        for i in range(n):
            got[off + i] = lg[:, i]
        off += n
    for pos in range(off, N):
        lg, cache, info = one(params, tokens[:, pos],
                              jnp.full((b,), pos, jnp.int32), cache)
        keep(info, slice(pos, pos + 1))
        got[pos] = lg
    return got, chosen


@pytest.mark.parametrize("plen,chunk", [(8, 16), (12, 7), (16, 32)])
def test_prefill_extend_decode_agree_with_the_reference(
        params, tokens, reference, plen, chunk):
    """Both regimes: contexts up to index_topk = 16, where the selection
    is the identity, and past it. The rule is the chip's
    (perf/kinds/serve_dsa.py): a set may differ from the reference's own
    only where the reference's margin is within rounding, and the
    reference then attends the program's sets, so that every position
    is compared whatever ties came before it."""
    _, records = reference
    got, chosen = _through_the_cache(params, tokens, plen, chunk, steps=6)
    assert sorted(got) == list(range(plen - 1, N))
    for rec, mine in zip(records, chosen):
        margin = _margin(rec["dsa"]["scores"], TOPK)
        differs = (mine != rec["dsa"]["select"]).any(-1)
        assert not (differs & (margin > 1e-4)).any()
        assert differs.mean() < 0.05
        count = np.minimum(np.arange(N) + 1, TOPK)
        np.testing.assert_array_equal(mine.sum(-1), count[None].repeat(2, 0))
    want, _ = jax.jit(lambda p, t, c: REF.forward(p, t, MODEL, chosen=c))(
        params, tokens, [jnp.asarray(c) for c in chosen])
    for pos, lg in got.items():
        _close(lg, want[:, pos], 5e-4)


def test_selection_is_the_references_where_its_margin_is_clear(
        params, tokens, reference):
    _, records = reference
    cache = init_kv_cache(CFG, 2, MAX_LEN)
    _, cache = prefill(params, tokens[:, :8], cache, CFG)
    info = []
    block_decode(params, tokens[:, 8:], jnp.array([8, 8]), cache, CFG,
                 dsa_info=info)
    assert len(info) == CFG.n_layers
    for rec, mine in zip(records, info):
        want = rec["dsa"]["select"][:, 8:]                  # (b, n, N)
        scores = rec["dsa"]["scores"][:, 8:]
        got = np.asarray(mine["select"])[:, :, :N]
        assert not np.asarray(mine["select"])[:, :, N:].any()
        count = np.minimum(np.arange(8, N) + 1, TOPK)
        np.testing.assert_array_equal(got.sum(-1), count[None].repeat(2, 0))
        live = np.isfinite(scores)
        _close(np.asarray(mine["scores"])[:, :, :N][live], scores[live],
               1e-4)
        margin = _margin(scores, TOPK)
        clear = (margin > 1e-4) | (margin == 0)     # 0: by position
        clear |= np.arange(8, N)[None] < TOPK
        assert clear.mean() > 0.9
        np.testing.assert_array_equal(got[clear], want[clear])


def test_topk_no_smaller_than_the_cache_is_the_v3_path(params, tokens):
    """index_topk >= max_len: nothing is scored or selected and the
    logits are, to the bit, those of the same weights without a selector;
    index_topk >= every context of a longer cache: the same through the
    branch that scores nothing."""
    plain = dataclasses.replace(CFG, index_topk=0, index_n_heads=0,
                                index_head_dim=0)
    wide = dataclasses.replace(CFG, index_topk=MAX_LEN)

    def run(cfg, max_len):
        cache = init_kv_cache(cfg, 2, max_len)
        lg0, cache = prefill(params, tokens[:, :12], cache, cfg)
        lg1, cache = block_decode(params, tokens[:, 12:20],
                                  jnp.array([12, 12]), cache, cfg)
        lg2, cache = decode_step(params, tokens[:, 20],
                                 jnp.array([20, 20]), cache, cfg)
        return [np.asarray(x) for x in (lg0, lg1, lg2)]

    want = run(plain, MAX_LEN)
    for got in (run(wide, MAX_LEN),
                run(dataclasses.replace(CFG, index_topk=32), MAX_LEN)):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)


def test_a_block_attended_whole_is_refused_past_index_topk(params, tokens):
    cache = init_kv_cache(CFG, 2, MAX_LEN)
    with pytest.raises(ValueError, match="index_topk"):
        prefill(params, tokens[:, :TOPK + 1], cache, CFG)
    with pytest.raises(ValueError, match="index_topk"):
        forward(params, tokens[:, :TOPK + 1], CFG)
    forward(params, tokens[:, :TOPK], CFG)


# ---- the server ---------------------------------------------------------

def test_server_admits_long_prompts_and_counts_the_selector(
        params, tokens, reference):
    """Prompts past the widest bucket a selector allows go on in
    block_decode chunks, the long ones in chunks twice as wide;
    the round writes every step (no tail), counts its own selections
    and attends, and the tokens are the reference's greedy continuation
    wherever its top logit is clear."""
    want, _ = reference
    reg = Registry()
    srv = DecodeServer(params, CFG, n_slots=2, max_len=MAX_LEN,
                       round_len=4, prompt_buckets=(8, 16, 64),
                       metrics=reg)
    assert srv.buckets == (8, 16)           # none wider than index_topk
    assert (srv._chunk_w, srv._long_w) == extend_widths(srv.buckets) \
        == (16, 32)
    assert not srv._kv_tail
    toks = np.asarray(tokens)
    plens = (90, 20)            # 74 past the bucket: long chunks; 4: short
    rids = [srv.submit(toks[r, :plen], 6) for r, plen in enumerate(plens)]
    out = srv.run()
    for r, plen in enumerate(plens):
        assert out[rids[r]][0] == int(np.argmax(want[r, plen - 1]))
    c = reg.snapshot()["counters"]
    assert c["serve.prefill_tokens"] == sum(plens)
    # 90: bucket 16 + 3 chunks of 32; 20: bucket 16 + one chunk of 16
    assert c["serve.prefill_padded_tokens"] == 16 + 3 * 32 + 16 + 16
    assert srv._extend._cache_size() == 2
    assert "serve.kv_tail.rounds" not in c
    assert reg.snapshot()["gauges"]["serve.cache_bytes_per_token"] == \
        kvcache.bytes_per_position(srv.cache)
    # two rounds of 4 steps from pos (90, 20): contexts pos + s + 1;
    # what the program counted of itself is what its rule says
    ctx = np.array(plens)[:, None] + 1 + np.arange(8)[None]
    layers = CFG.n_layers
    assert c["serve.dsa.keys_scored"] == layers * ctx.sum()
    assert c["serve.dsa.rows_attended"] == layers * np.minimum(
        ctx, TOPK).sum()
    assert c["serve.dsa.dense_row_steps"] == 0
    assert c["serve.dsa.latent_rows_read"] == layers * ctx.sum()
    assert c.get("serve.retraces", 0) == 0


@pytest.mark.parametrize("pos", [(3, 9), (3, 40), (70, 20)])
def test_the_hosts_count_is_what_the_step_did(params, tokens, pos):
    """kvcache.select_counts (what the server counts a round by) against
    one decode step's own records: the contexts it scored, the sizes of
    the sets it kept, and the form its attend reported."""
    cache = init_kv_cache(CFG, 2, MAX_LEN)
    info = []
    decode_step(params, tokens[:, 0], jnp.asarray(pos, jnp.int32), cache,
                CFG, dsa_info=info)
    assert len(info) == CFG.n_layers
    ctx = np.asarray(pos)[:, None] + 1
    for rec in info:
        want = kvcache.select_counts(ctx, TOPK, rec["reads"])
        assert int(rec["keys_scored"]) == want["keys_scored"]
        assert int(rec["select"].sum()) == want["rows_attended"]
        assert rec["reads"] == kvcache.READS_CONTEXT
        assert want["latent_rows_read"] == ctx.sum()
    assert kvcache.select_counts(ctx, TOPK, kvcache.READS_SELECTION)[
        "latent_rows_read"] == np.minimum(ctx, TOPK).sum()
    assert set(want) == set(kvcache.SELECT_STATS)


def test_server_without_a_selector_counts_none(params):
    plain = dataclasses.replace(CFG, index_topk=0, index_n_heads=0,
                                index_head_dim=0)
    reg = Registry()
    srv = DecodeServer(params, plain, n_slots=2, max_len=MAX_LEN,
                       round_len=4, metrics=reg)
    assert srv.buckets == (64,) and srv._kv_tail
    srv.submit(np.arange(5), 3)
    srv.run()
    assert not [k for k in reg.snapshot()["counters"] if ".dsa." in k]


# ---- the chip's share of the experts ------------------------------------

def test_the_32_shares_add_up_to_the_uncut_layer(params):
    """The guide's share test for this configuration's reading of
    n_routed_experts (8 of 256 held by each of 32 chips; at the tiny
    size 8 of 32 by each of 4): the routed parts of all shares plus the
    shared expert, counted once, are the layer with every expert held."""
    shares = CFG.n_experts // CFG.experts_held
    assert CONFIG["published"]["n_routed_experts"] // CONFIG[
        "n_routed_experts"] == 32 and shares == 4
    whole = dataclasses.replace(CFG, n_experts_held=0)
    full = moe.init_routed_params(jax.random.PRNGKey(3), whole)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 9, CFG.d_model))
    want, _ = moe.routed_ffn(full, h, whole)
    shared = {k: jnp.zeros_like(v) for k, v in full.items()
              if k in ("swg", "swu", "swd")}
    total = 0.0
    for i in range(shares):
        first = i * CFG.experts_held
        cfg_i = dataclasses.replace(CFG, expert_first=first)
        part = dict(full, **{k: full[k][first:first + CFG.experts_held]
                             for k in ("wg", "wu", "wd")})
        with_shared, _ = moe.routed_ffn(part, h, cfg_i)
        routed, _ = moe.routed_ffn(dict(part, **shared), h, cfg_i)
        total = total + routed + (with_shared - routed if i == 0 else 0.0)
    _close(total, want, 1e-5)
    # and the reference reads the same share
    model_i = dict(MODEL, expert_first=CFG.experts_held)
    part = dict(full, **{k: full[k][CFG.experts_held:2 * CFG.experts_held]
                         for k in ("wg", "wu", "wd")})
    got, _ = moe.routed_ffn(part, h, dataclasses.replace(
        CFG, expert_first=CFG.experts_held))
    ref_y, _ = REF._experts(h, part, model_i, None)
    _close(got, ref_y, 1e-5)
