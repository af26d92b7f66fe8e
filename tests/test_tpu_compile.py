"""The serving kernels compiled by the real TPU compiler at the benchmark
cells' shapes (gpt2-medium: decode attention at 96 rows x max_len 1024;
deepseek-v3-ep16: the latent attend at 128 rows x 576 x 4096 and the
grouped expert FFN over 16 held experts at 7168 x 2048; both cells'
round: the attend with a 32-row write-behind tail and the tail's fold;
every attend on its work-list grid, a 1-D grid with a dynamic bound;
gpt2-medium's admission program, a device loop around the one-row
prefill with the pool cache in its carry;
deepseek-v3.2-ep32: the selector's score and exact top-2048 kernels
at 32 x 24576 and an extend chunk's 256 x 24576;
sdar-30b-a3b-6l: a pass's block write and block-causal attend of 4
queries at 192 rows x 32 / 4 heads x 128 x 1536, and the grouped expert
FFN over 128 held experts at 2048 x 768; trinity-large-ep16: a sliding
layer's window attend over its ring of 4352 positions at 48 rows x 48 / 8
heads x 128, with and without the round's tail, and the ring's writes;
hy4-preview-ep32: the hyper-connection boundary (XLA's ops) at 16, 256
and 1024 rows of 4 x 6144, the selected latent attend of 64 heads with a sink
a head at 16 x 49152, the exact top-2048 of a decode step's 16 rows and an
extend chunk's 256 of 49152, and the clamped expert FFN at 6144 x 2048),
without a chip: Mosaic's refusals (block shapes, scoped VMEM, scalar-prefetch index
maps) show up here, numerics and times do not. The topology is described
inside a fixture, never at import (only one process may load libtpu, and
xdist workers all import this file); keep such tests in this one file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from rlo_tpu.pallas.decode import (decode_work_list, flash_block_decode,
                                   flash_decode_tile, write_kv_block,
                                   write_kv_row, write_kv_tail)
from rlo_tpu.pallas.expert_ffn import (buffer_rows, expert_ffn,
                                       steps_per_tile)

B, NH, D, L = 96, 16, 64, 1024
L_GPT2 = L


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("T,cache_dtype", [(1, jnp.bfloat16),
                                           (4, jnp.bfloat16),
                                           (1, jnp.int8)])
def test_flash_decode_compiles_for_v5e(one_chip, T, cache_dtype):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    args = [shape((B, T, NH, D), jnp.bfloat16),
            shape((B, NH, D, L), cache_dtype),
            shape((B, NH, D, L), cache_dtype), shape((B,), jnp.int32)]
    if cache_dtype == jnp.int8:
        args += [shape((B, NH, L), jnp.float32)] * 2

    def attend(q, k, v, pos, *scales):
        return flash_block_decode(q, k, v, pos, 0.125, *scales,
                                  interpret=False)

    text = jax.jit(attend).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    assert ("flash_decode" if T == 1 else "flash_block_decode") in text


# four cells' caches as (kv heads, head_dim, max_len, query heads,
# queries a row, latent, the tile the rule picks): a latent cache of
# 16384 positions or more takes 2048 lanes, a shorter one 1024
CELL_CACHES = {
    "gpt2m-decode-sat": (16, 64, 1024, 16, 1, False, 256),
    "dsv3-ep16-reason-sat": (1, 576, 4096, 128, 1, True, 1024),
    "dsv32-ep32-longctx-decode": (1, 576, 24576, 128, 1, True, 2048),
    "sdar-6l-blockgen-sat": (4, 128, 1536, 32, 4, False, 512),
}


@pytest.mark.parametrize("cell", sorted(CELL_CACHES))
def test_the_ring_is_in_the_vmem_budget_and_fits_v5e(one_chip, cell,
                                                     monkeypatch):
    """The kernel fetches K/V through a ring of _RING_SLOTS VMEM slots
    a tensor: the tile rule and the block gate count every slot (not
    the pipeline's two), the cells keep their tiles, and the deepest
    ring the rule was measured at (4) still compiles inside v5e's
    scoped VMEM at each cell's shape — at the tile the rule takes for
    it, which for a long latent cache is 1024, not 2048 (four slots of
    2048 x 576 and the scores pass _pick_bk's 10 MB)."""
    from rlo_tpu.pallas import decode
    nkv, d, max_len, nh, T, latent, tile = CELL_CACHES[cell]
    streams = 1 if latent else 2
    cache = jax.ShapeDtypeStruct((2, nkv, d, max_len), jnp.bfloat16,
                                 sharding=one_chip)
    assert flash_decode_tile(cache, nh, latent=latent) == tile
    monkeypatch.setattr(decode, "_RING_SLOTS", 4)
    # the ring at every cell, those whose step computes longer than
    # it streams and keep the pipeline (_ring_slots) too
    monkeypatch.setattr(decode, "_COMPUTE_BOUND", float("inf"))
    if 4 * streams * nkv * d * tile * 2 + 2 * nh * tile * 4 > 10 << 20:
        tile //= 2
    assert flash_decode_tile(cache, nh, latent=latent) == tile
    ring = decode._ring_bytes(nkv, d, tile, 2, streams)
    assert ring == 4 * streams * nkv * d * tile * 2 <= 5 << 20
    rule = decode._tile_rule(latent, max_len)
    assert decode._block_fits_vmem(max_len, d, nkv, nh // nkv, T, 2,
                                   *rule, streams)
    # the gate counts the ring: the largest block that fits beside two
    # tiles a tensor does not fit beside the ring
    per_t = 2 * nh * tile * 4 + nh * d * 4
    t_max = ((14 << 20) - ring // 2) // per_t
    assert not decode._block_fits_vmem(max_len, d, nkv, nh // nkv,
                                       t_max, 2, *rule, streams)

    def attend(q, k, pos):
        return flash_block_decode(q, k, None if latent else k, pos, 0.125,
                                  v_dim=512 if latent else 0,
                                  interpret=False)

    text = jax.jit(attend).lower(
        jax.ShapeDtypeStruct((2, T, nh, d), jnp.bfloat16,
                             sharding=one_chip), cache,
        jax.ShapeDtypeStruct((2,), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert ("flash_decode" if T == 1 else "flash_block_decode") in text


# deepseek-v3-ep16 x reason-sat: 128 slots, 128 heads, latent rows of
# 512 + 64, 16 of 256 experts held
SLOTS, HEADS, LATENT, V_DIM, MAX_LEN = 128, 128, 576, 512, 4096
D_MODEL, D_EXPERT, HELD = 7168, 2048, 16


def test_latent_attend_and_row_write_compile_for_v5e(one_chip):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def step(q, cache, row, pos):
        cache = write_kv_row(cache, row, pos, interpret=False)
        return flash_block_decode(q, cache, None, pos, 0.135,
                                  v_dim=V_DIM, interpret=False), cache

    text = jax.jit(step).lower(
        shape((SLOTS, 1, HEADS, LATENT), jnp.bfloat16),
        shape((SLOTS, 1, LATENT, MAX_LEN), jnp.bfloat16),
        shape((SLOTS, 1, LATENT), jnp.bfloat16),
        shape((SLOTS,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "flash_decode" in text and "write_kv_row" in text


LATENT_TILE = 1024      # _LATENT_BLOCK_K, as re-measured in PR 30


def test_latent_attend_on_a_shared_work_list_compiles_for_v5e(one_chip):
    """The latent attend at the tile the rule picks, as a decode step
    runs it: the work list built once from pos (a dynamic grid bound
    and two prefetched lists) and handed to every layer's call."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cache = shape((SLOTS, 1, LATENT, MAX_LEN), jnp.bfloat16)
    bk = flash_decode_tile(cache, HEADS, latent=True)
    assert bk == LATENT_TILE

    def step(q, c0, c1, pos):
        work = decode_work_list(pos, 1, bk, MAX_LEN // bk)
        return [flash_block_decode(q, c, None, pos, 0.135, v_dim=V_DIM,
                                   interpret=False, work=work)
                for c in (c0, c1)]

    text = jax.jit(step).lower(
        shape((SLOTS, 1, HEADS, LATENT), jnp.bfloat16), cache, cache,
        shape((SLOTS,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") >= 2 and "flash_decode" in text


ROUND_LEN = 32      # DecodeServer's default: rows in a round's tail


@pytest.mark.parametrize("cell", ["gpt2m-decode-sat",
                                  "dsv3-ep16-reason-sat"])
def test_round_kernels_with_a_tail_compile_for_v5e(one_chip, cell):
    """What a dense round runs since PR 28: the attend over the cache
    and the round's token-major tail (a step of the scan), then the
    fold of the tail into the cache (once a round). The benchmark looks
    for both kernels in the round's program by these names."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    latent = cell == "dsv3-ep16-reason-sat"
    b, nh, nkv, d, L = ((SLOTS, HEADS, 1, LATENT, MAX_LEN) if latent
                        else (B, NH, NH, D, L_GPT2))
    bf16 = jnp.bfloat16
    rows = shape((ROUND_LEN, b, nkv, d), bf16)

    def step_and_fold(q, pos, newest, *kv):
        caches, tails = kv[:len(kv) // 2], kv[len(kv) // 2:]
        out = flash_block_decode(
            q, caches[0], None if latent else caches[1], pos - 1, 0.13,
            v_dim=V_DIM if latent else 0, interpret=False,
            tail=(tails[0], None if latent else tails[1], newest))
        return out, [write_kv_tail(c, t, pos, interpret=False)
                     for c, t in zip(caches, tails)]

    from rlo_tpu.utils import hlo
    n = 1 if latent else 2
    lowered = jax.jit(step_and_fold).lower(
        shape((b, 1, nh, d), bf16), shape((b,), jnp.int32),
        shape((), jnp.int32), *[shape((b, nkv, d, L), bf16)] * n,
        *[rows] * n)
    # the benchmark's own reading of the lowered text (perf/kinds/serve.py)
    assert hlo.mosaic_kernels(lowered.as_text()) == {
        "flash_decode": 1, "write_kv_row": n}
    assert lowered.compile().as_text().count("tpu_custom_call") >= 1 + n


@pytest.mark.parametrize("tokens,tile", [(128, 16), (256, 16), (1024, 64)])
def test_expert_ffn_compiles_for_v5e(one_chip, tokens, tile):
    """A decode step of 128 rows, and prefill buckets of 256 and 1024:
    every (token, choice) pair here in the worst case. The byte rule's
    choice at 7168 x 2048 in bf16 is 7 + 8 steps a tile, blocks of 4 and
    3.5 MiB in two buffers: a wider one has to fit scoped VMEM HERE."""
    assert steps_per_tile(D_MODEL, D_EXPERT, 2) == 15

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    n_rows = buffer_rows(tokens * 8, HELD, tile)

    def ffn(x, wg, wu, wd, tile_expert, n_live):
        return expert_ffn(x, wg, wu, wd, tile_expert, n_live, tile=tile,
                          interpret=False)

    text = jax.jit(ffn).lower(
        shape((n_rows, D_MODEL), jnp.bfloat16),
        shape((HELD, D_MODEL, D_EXPERT), jnp.bfloat16),
        shape((HELD, D_MODEL, D_EXPERT), jnp.bfloat16),
        shape((HELD, D_EXPERT, D_MODEL), jnp.bfloat16),
        shape((n_rows // tile,), jnp.int32),
        shape((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "expert_ffn" in text


# sdar-30b-a3b-6l x blockgen-sat: 192 slots of 1536 positions, blocks of
# 4, 32 query heads on 4 K/V heads of 128; all 128 experts of 2048 x 768
SDAR_SLOTS, SDAR_LEN, SDAR_BLOCK = 192, 1536, 4
SDAR_HEADS, SDAR_KV, SDAR_HD = 32, 4, 128
SDAR_D, SDAR_F, SDAR_EXPERTS = 2048, 768, 128


def test_block_pass_write_and_attend_compile_for_v5e(one_chip):
    """A pass of generation by diffusion over blocks, a layer's worth:
    the block's 4 provisional K/V columns written into the cache, then
    its 4 x 32 queries against everything before the block and the block
    itself (the block-causal mask), on a work list built once."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf16 = jnp.bfloat16
    cache = shape((SDAR_SLOTS, SDAR_KV, SDAR_HD, SDAR_LEN), bf16)
    rows = shape((SDAR_SLOTS, SDAR_KV, SDAR_HD, SDAR_BLOCK), bf16)
    bk = flash_decode_tile(cache, SDAR_HEADS)

    def step(q, k, v, k_new, v_new, pos0):
        k = write_kv_block(k, k_new, pos0, interpret=False)
        v = write_kv_block(v, v_new, pos0, interpret=False)
        work = decode_work_list(pos0, SDAR_BLOCK, bk, SDAR_LEN // bk)
        return flash_block_decode(
            q, k, v, pos0, SDAR_HD ** -0.5, interpret=False, work=work,
            block_len=SDAR_BLOCK), k, v

    from rlo_tpu.utils import hlo
    lowered = jax.jit(step, donate_argnums=(1, 2)).lower(
        shape((SDAR_SLOTS, SDAR_BLOCK, SDAR_HEADS, SDAR_HD), bf16),
        cache, cache, rows, rows, shape((SDAR_SLOTS,), jnp.int32))
    # the benchmark's own reading of the lowered text
    # (perf/kinds/serve_diffusion.py)
    assert hlo.mosaic_kernels(lowered.as_text()) == {
        "flash_block_decode": 1, "write_kv_block": 2}
    assert lowered.compile().as_text().count("tpu_custom_call") >= 3


@pytest.mark.parametrize("tokens", [768, 64, 256, 1024])
def test_small_expert_ffn_compiles_for_v5e(one_chip, tokens):
    """All 128 experts of 2048 x 768 held: a pass of 192 rows x 4
    positions (48 rows an expert, tiles of 128) and the prefill buckets,
    every (token, choice) pair here in the worst case, at the tile
    models.moe.row_tile picks. An expert's three 3 MiB matrices are one
    block each: ONE step a tile."""
    from rlo_tpu.models.moe import row_tile
    assert steps_per_tile(SDAR_D, SDAR_F, 2) == 1

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    tile = row_tile(tokens * 8, SDAR_EXPERTS)
    n_rows = buffer_rows(tokens * 8, SDAR_EXPERTS, tile)

    def ffn(x, wg, wu, wd, tile_expert, n_live):
        return expert_ffn(x, wg, wu, wd, tile_expert, n_live, tile=tile,
                          interpret=False)

    bf16 = jnp.bfloat16
    text = jax.jit(ffn).lower(
        shape((n_rows, SDAR_D), bf16),
        shape((SDAR_EXPERTS, SDAR_D, SDAR_F), bf16),
        shape((SDAR_EXPERTS, SDAR_D, SDAR_F), bf16),
        shape((SDAR_EXPERTS, SDAR_F, SDAR_D), bf16),
        shape((n_rows // tile,), jnp.int32),
        shape((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "expert_ffn" in text


# deepseek-v3.2-ep32 x longctx-decode: 32 slots of 24576 positions, the
# token selector's 64 heads of 128 over one 128-wide key a token, the
# attend over the 2048 positions it keeps
DSA_SLOTS, DSA_LEN, INDEX_HEADS, INDEX_DIM, INDEX_TOPK = 32, 24576, 64, 128, 2048
DSA_TILE = 2048     # _LATENT_LONG_BLOCK_K: a latent cache of >= 16384


def test_index_score_and_selected_attend_compile_for_v5e(one_chip):
    """A decode step of the sparse-attention cell, a layer's worth: the
    row write of both tensors of the entry, index_score on its own
    work list, the exact top-2048 as a mask (the topk_select kernel),
    and the latent attend with that mask streamed beside the cache."""
    from rlo_tpu.pallas.decode import (index_score, index_score_tile,
                                       topk_select)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    bf16 = jnp.bfloat16
    latent = shape((DSA_SLOTS, 1, LATENT, DSA_LEN), bf16)
    keys = shape((DSA_SLOTS, 1, INDEX_DIM, DSA_LEN), bf16)
    bk = flash_decode_tile(latent, HEADS, latent=True)
    ibk = index_score_tile(DSA_LEN)
    assert (bk, ibk) == (DSA_TILE, 2048)

    def step(q, qi, w, row, irow, cache, ik, pos):
        cache = write_kv_row(cache, row, pos, interpret=False)
        ik = write_kv_row(ik, irow, pos, interpret=False)
        scores = index_score(
            qi, w, ik, pos, interpret=False,
            work=decode_work_list(pos, 1, ibk, DSA_LEN // ibk))
        select = topk_select(scores, INDEX_TOPK, interpret=False)
        out = flash_block_decode(
            q, cache, None, pos, 0.135, v_dim=V_DIM, interpret=False,
            work=decode_work_list(pos, 1, bk, DSA_LEN // bk),
            select=select)
        return out, cache, ik

    from rlo_tpu.utils import hlo
    lowered = jax.jit(step).lower(
        shape((DSA_SLOTS, 1, HEADS, LATENT), bf16),
        shape((DSA_SLOTS, INDEX_HEADS, INDEX_DIM), bf16),
        shape((DSA_SLOTS, INDEX_HEADS), jnp.float32),
        shape((DSA_SLOTS, 1, LATENT), bf16),
        shape((DSA_SLOTS, 1, INDEX_DIM), bf16), latent, keys,
        shape((DSA_SLOTS,), jnp.int32))
    assert hlo.mosaic_kernels(lowered.as_text()) == {
        "flash_decode": 1, "index_score": 1, "topk_select": 1,
        "write_kv_row": 2}
    assert lowered.compile().as_text().count("tpu_custom_call") >= 5


def test_topk_select_compiles_for_an_extend_chunk_for_v5e(one_chip):
    """The exact top-2048 of an admission's 256-token extend chunk in
    the sparse-attention cell: (1, 256, 24576) scores, 32 blocks of 8
    rows held whole in VMEM."""
    from rlo_tpu.pallas.decode import can_topk_select, topk_select
    from rlo_tpu.utils import hlo
    assert can_topk_select(DSA_LEN, INDEX_TOPK)
    lowered = jax.jit(lambda s: topk_select(s, INDEX_TOPK,
                                            interpret=False)).lower(
        jax.ShapeDtypeStruct((1, 256, DSA_LEN), jnp.float32,
                             sharding=one_chip))
    assert hlo.mosaic_kernels(lowered.as_text()) == {"topk_select": 1}
    assert lowered.compile().as_text().count("tpu_custom_call") == 1


def test_admission_program_runs_its_loop_in_place_for_v5e(one_chip,
                                                          monkeypatch):
    """gpt2-medium x decode-sat's admission program (PR 33: one launch a
    prompt bucket, a device loop over the group's rows) at the cell's 96
    slots x 1024 positions, two layers deep: the TPU compiler takes the
    while loop with the flash kernel in its body, and the pool cache
    rides the loop's carry IN PLACE — all of it aliased to the donated
    input, temporaries a hundredth of it. A carry that were copied would
    double the cache's 9.7 GB on the chip."""
    from rlo_tpu.models.kvcache import init_kv_cache
    from rlo_tpu.models.serve import DecodeServer
    from rlo_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig(vocab=1024, d_model=1024, n_heads=NH,
                            n_layers=2, d_ff=4096, dtype="bfloat16")
    params = init_params(jax.random.PRNGKey(0), cfg)
    srv = DecodeServer(params, cfg, n_slots=2, max_len=L_GPT2)
    # from here the model's kernel gates see the chip being compiled for
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def abstract(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    cache = abstract(jax.eval_shape(
        lambda: init_kv_cache(cfg, B, L_GPT2)))
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    compiled = srv._admit_rows.lower(
        abstract(params), cache, jax.ShapeDtypeStruct(
            (B, 256 + 2), jnp.int32, sharding=one_chip)).compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and "while" in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes == cache_bytes
    assert memory.temp_size_in_bytes < cache_bytes // 100


# trinity-large-ep16 x swa-longctx-decode: 48 slots, 48 query heads over
# 8 kv heads of 128, a sliding layer's ring of its 4096-position window
# and the 256-token extend chunk
W_SLOTS, W_HEADS, W_KV, W_D, W_RING, W_WINDOW = 48, 48, 8, 128, 4352, 4096


@pytest.mark.parametrize("tail", [0, 32])
def test_window_attend_compiles_for_v5e(one_chip, tail):
    """The window variant of the decode kernel (flash_decode_window: a
    ring's tiles walked from where a row's window starts, whole tiles,
    each column masked by the position it holds) on its own work list,
    with the round's 32-row tail and without it."""
    from rlo_tpu.pallas.decode import window_work_list

    def shape(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cache = shape((W_SLOTS, W_KV, W_D, W_RING))
    bk = flash_decode_tile(cache, W_HEADS)
    assert W_RING % bk == 0

    def attend(q, k, v, pos, *rows):
        work = window_work_list(jnp.maximum(pos - W_WINDOW + 1, 0),
                                pos, bk, W_RING // bk)
        t = (rows[0], rows[1], jnp.int32(tail - 1)) if rows else None
        return flash_block_decode(q, k, v, pos, 0.088, interpret=False,
                                  tail=t, work=work, window=W_WINDOW)

    args = [shape((W_SLOTS, 1, W_HEADS, W_D)), cache, cache,
            shape((W_SLOTS,), jnp.int32)]
    if tail:
        args += [shape((tail, W_SLOTS, W_KV, W_D))] * 2
    text = jax.jit(attend).lower(*args).compile().as_text()
    assert "flash_decode_window" in text


def test_ring_writes_compile_for_v5e(one_chip):
    """A round's 32-row fold and a 128-column block write into a ring,
    each free to pass the ring's end and wrap to its start."""
    cache = jax.ShapeDtypeStruct((W_SLOTS, W_KV, W_D, W_RING),
                                 jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((W_SLOTS,), jnp.int32, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((32, W_SLOTS, W_KV, W_D), jnp.bfloat16,
                                sharding=one_chip)
    block = jax.ShapeDtypeStruct((1, W_KV, W_D, 128), jnp.bfloat16,
                                 sharding=one_chip)
    one = jax.ShapeDtypeStruct((1, W_KV, W_D, W_RING), jnp.bfloat16,
                               sharding=one_chip)
    fold = jax.jit(lambda c, t, p: write_kv_tail(
        c, t, p, interpret=False, ring=True), donate_argnums=(0,))
    text = fold.lower(cache, rows, pos).compile().as_text()
    assert "write_kv_row" in text
    put = jax.jit(lambda c, b, p: write_kv_block(
        c, b, p, interpret=False, ring=True), donate_argnums=(0,))
    text = put.lower(one, block, jax.ShapeDtypeStruct(
        (1,), jnp.int32, sharding=one_chip)).compile().as_text()
    assert "write_kv_block" in text


# hy4-preview-ep32 x longctx32k-decode: 16 slots of 49152 positions, 64
# heads on one latent stream of 576 with a sink a head, the 32-head
# selector's top-2048; 4 hyper-connected streams of 6144; 8 held experts
# of 6144 x 2048 under the clamped SwiGLU
HY4_SLOTS, HY4_LEN, HY4_HEADS, HY4_D, HY4_STREAMS = 16, 49152, 64, 6144, 4


@pytest.mark.parametrize("rows,mode", [(16, "start"), (16, "mix"),
                                       (16, "readout"), (256, "mix"),
                                       (1024, "mix")])
def test_hc_boundary_compiles_for_v5e(one_chip, rows, mode):
    """One hyper-connection boundary (transformer.hc_mix, XLA's ops on
    every backend) at a decode step's 16 rows, an extend chunk's 256 and
    the widest prompt bucket's 1024: no kernel, and temporaries of at
    most three float32 copies of the streams (none up to 256 rows, where
    one fusion holds the boundary; three at 1024, 0.3 GB beside a
    prompt bucket's program)."""
    from rlo_tpu.models.transformer import TransformerConfig, hc_mix
    from rlo_tpu.utils import hlo
    n, d = HY4_STREAMS, HY4_D
    width = 2 * n + n * n
    cfg = TransformerConfig(vocab=256, d_model=d, n_heads=HY4_HEADS,
                            n_layers=1, hc_mult=n, dtype="bfloat16")

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def boundary(x, out, coef, phi, scale, bias):
        hc = None if mode == "readout" else {"phi": phi, "scale": scale,
                                             "bias": bias}
        return hc_mix(x, None if mode == "start" else out,
                      None if mode == "start" else coef, hc, cfg)

    lowered = jax.jit(boundary).lower(
        shape((rows, n * d), jnp.bfloat16), shape((rows, d), jnp.bfloat16),
        shape((rows, width), jnp.float32),
        shape((width, n * d), jnp.bfloat16), shape((3,), jnp.float32),
        shape((width,), jnp.float32))
    assert hlo.mosaic_kernels(lowered.as_text()) == {}
    memory = lowered.compile().memory_analysis()
    assert memory.temp_size_in_bytes <= 3 * rows * n * d * 4 + (1 << 20)


def test_selected_latent_attend_with_sinks_compiles_for_v5e(one_chip):
    """The latent attend of the cell's decode step: 64 heads over 576 x
    49152 with the selection streamed beside the cache and a sink a head
    joining each softmax when a row's last tile is flushed."""
    from rlo_tpu.utils import hlo

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    cache = shape((HY4_SLOTS, 1, LATENT, HY4_LEN), jnp.bfloat16)
    bk = flash_decode_tile(cache, HY4_HEADS, latent=True)
    assert bk == DSA_TILE

    def step(q, c, pos, select, sinks):
        return flash_block_decode(
            q, c, None, pos, 256 ** -0.5, v_dim=V_DIM, interpret=False,
            work=decode_work_list(pos, 1, bk, HY4_LEN // bk),
            select=select, sinks=sinks)

    lowered = jax.jit(step).lower(
        shape((HY4_SLOTS, 1, HY4_HEADS, LATENT), jnp.bfloat16), cache,
        shape((HY4_SLOTS,), jnp.int32), shape((HY4_SLOTS, HY4_LEN), bool),
        shape((HY4_HEADS,), jnp.float32))
    assert hlo.mosaic_kernels(lowered.as_text()) == {"flash_decode": 1}
    assert lowered.compile().as_text().count("tpu_custom_call") >= 1


@pytest.mark.parametrize("shape", [(HY4_SLOTS, 1, HY4_LEN),
                                   (1, 256, HY4_LEN)])
def test_topk_select_compiles_at_rows_of_49152_for_v5e(one_chip, shape):
    """The exact top-2048 of the cell's decode step, (16, 1, 49152)
    scores, and of an admission's 256-token extend chunk: 16 rows a grid
    step, their blocks single-buffered, 9 MiB of VMEM (double-buffered
    they would take 15)."""
    from rlo_tpu.pallas import decode
    from rlo_tpu.utils import hlo
    assert decode._TOPK_ROWS == 16 and decode._topk_buffers(HY4_LEN) == 1
    assert decode._topk_vmem(1, HY4_LEN) <= decode._TOPK_VMEM_BYTES \
        < decode._topk_vmem(2, HY4_LEN)
    lowered = jax.jit(lambda s: decode.topk_select(
        s, INDEX_TOPK, interpret=False)).lower(
        jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip))
    assert hlo.mosaic_kernels(lowered.as_text()) == {"topk_select": 1}
    assert lowered.compile().as_text().count("tpu_custom_call") == 1


def test_clamped_expert_ffn_compiles_for_v5e(one_chip):
    """The cell's grouped expert FFN, 16 rows x 8 choices over 8 held
    experts of 6144 x 2048, with the SwiGLU clamp at 10 in the body."""
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    held, f, tile = 8, 2048, 16
    n_rows = buffer_rows(HY4_SLOTS * 8, held, tile)

    def ffn(x, wg, wu, wd, tile_expert, n_live):
        return expert_ffn(x, wg, wu, wd, tile_expert, n_live, tile=tile,
                          interpret=False, limit=10.0)

    text = jax.jit(ffn).lower(
        shape((n_rows, HY4_D), jnp.bfloat16),
        shape((held, HY4_D, f), jnp.bfloat16),
        shape((held, HY4_D, f), jnp.bfloat16),
        shape((held, f, HY4_D), jnp.bfloat16),
        shape((n_rows // tile,), jnp.int32),
        shape((), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "expert_ffn" in text
