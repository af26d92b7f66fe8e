"""Pallas flash-decode kernel vs the einsum cache-attend oracle
(rlo_tpu.pallas.decode vs models.generate._attend_cache).

The kernel is exact-class against the f32 oracle (same masking, online
softmax changes only association order); for int8 caches it is MORE
precise than the einsum path (f32 accumulation vs the bf16 matmul), so
the quantized comparison targets the dequantized-f32 reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rlo_tpu.models.generate import (_attend_cache, _attend_cache_block,
                                     _quantize_kv)
from rlo_tpu.pallas import decode as decode_mod
from rlo_tpu.pallas.decode import (can_flash_decode, decode_work_list,
                                   flash_block_decode, flash_decode,
                                   flash_decode_tile)

B, NH, NKV, D, L = 3, 8, 4, 64, 48


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, 1, NH, D)), jnp.float32)
    # SEQ-MINOR cache layout (b, kvh, head_dim, L) — models.generate
    kc = jnp.asarray(rng.standard_normal((B, NKV, D, L)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, NKV, D, L)), jnp.float32)
    return q, kc, vc, 1.0 / np.sqrt(D)


def _oracle(q, kc, vc, pos, scale, ks=None, vs=None):
    return np.asarray(_attend_cache(q, kc, vc, pos, scale, k_scale=ks,
                                    v_scale=vs, use_flash=False))


@pytest.mark.parametrize("pos", [0, 7, L - 1])
def test_scalar_pos_matches_oracle(data, pos):
    q, kc, vc, scale = data
    got = np.asarray(flash_decode(q, kc, vc, pos, scale,
                                  interpret=True, block_k=16))
    np.testing.assert_allclose(got, _oracle(q, kc, vc, pos, scale),
                               rtol=2e-5, atol=2e-5)


def test_padded_tail_block(data):
    """block_k that does not divide max_len: the tail tile is padded
    and masked; garbage beyond max_len must not reach the output."""
    q, kc, vc, scale = data
    got = np.asarray(flash_decode(q, kc, vc, 40, scale,
                                  interpret=True, block_k=32))
    np.testing.assert_allclose(got, _oracle(q, kc, vc, 40, scale),
                               rtol=2e-5, atol=2e-5)


def test_ragged_per_row_positions(data):
    q, kc, vc, scale = data
    posv = jnp.asarray([3, L - 1, 11], jnp.int32)
    got = np.asarray(flash_decode(q, kc, vc, posv, scale,
                                  interpret=True, block_k=16))
    np.testing.assert_allclose(got, _oracle(q, kc, vc, posv, scale),
                               rtol=2e-5, atol=2e-5)


def test_mha_no_grouping(data):
    """nkv == nh (r = 1): the degenerate group size."""
    q, _, _, scale = data
    rng = np.random.default_rng(1)
    kc = jnp.asarray(rng.standard_normal((B, NH, D, L)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, NH, D, L)), jnp.float32)
    got = np.asarray(flash_decode(q, kc, vc, 20, scale,
                                  interpret=True, block_k=16))
    np.testing.assert_allclose(got, _oracle(q, kc, vc, 20, scale),
                               rtol=2e-5, atol=2e-5)


def _quant_seqminor(kc):
    """Quantize a seq-minor (b, g, d, L) cache per (b, g, L) position:
    run _quantize_kv on the head-minor view, flip back."""
    qk, ks = _quantize_kv(kc.transpose(0, 1, 3, 2))
    return qk.transpose(0, 1, 3, 2), ks


def test_int8_matches_f32_dequant_reference(data):
    """The kernel dequantizes in VMEM — compare against the f32
    dequantized einsum. int8 tiles matmul in bf16 (int8 -> bf16 is
    lossless; the rounding is in the f32 q cast and the products), so
    the tolerance is bf16-matmul class, the same class as the einsum
    path's own bf16 trick — the point of the kernel is bandwidth, and
    correctness is pinned exactly by the f32 legs above."""
    q, kc, vc, scale = data
    qk, ks = _quant_seqminor(kc)
    qv, vs = _quant_seqminor(vc)
    kd = jnp.asarray(np.asarray(qk, np.float32)
                     * np.asarray(ks)[:, :, None, :])
    vd = jnp.asarray(np.asarray(qv, np.float32)
                     * np.asarray(vs)[:, :, None, :])
    want = _oracle(q, kd, vd, 30, scale)
    got = np.asarray(flash_decode(q, qk, qv, 30, scale, ks, vs,
                                  interpret=True, block_k=16))
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_int8_padded_tail(data):
    """Quantized + non-dividing block_k: the tail tile's SCALE block
    is uninitialized too — pv must be re-masked or 0*NaN rides into
    the accumulator (the v-zeroing alone does not cover vs)."""
    q, kc, vc, scale = data
    qk, ks = _quant_seqminor(kc)
    qv, vs = _quant_seqminor(vc)
    got = np.asarray(flash_decode(q, qk, qv, 40, scale, ks, vs,
                                  interpret=True, block_k=32))
    kd = jnp.asarray(np.asarray(qk, np.float32)
                     * np.asarray(ks)[:, :, None, :])
    vd = jnp.asarray(np.asarray(qv, np.float32)
                     * np.asarray(vs)[:, :, None, :])
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _oracle(q, kd, vd, 40, scale),
                               rtol=1e-2, atol=1e-2)


def test_gate():
    assert can_flash_decode(1024, 64)
    assert can_flash_decode(128, 128)
    assert not can_flash_decode(128, 80)  # lane-hostile head_dim
    assert can_flash_decode(1280, 64)   # any whole number of 128s
    # the kernel's own K/V copies slice the cache axis, and Mosaic
    # slices it in 128s only: a ragged last tile (1216 = 9.5 x 128) or
    # a cache shorter than one block goes to the einsum, by its shape
    assert not can_flash_decode(1216, 64)
    assert not can_flash_decode(16, 128)
    assert not can_flash_decode(0, 64)


def test_attend_cache_flash_flag_parity(data):
    """_attend_cache(use_flash=True) in interpret mode must agree with
    its own einsum path — the production gate swaps implementations,
    not semantics. (On CPU the gate defaults to einsum; force the
    kernel through the interpret default.)"""
    q, kc, vc, scale = data
    a = np.asarray(_attend_cache(q, kc, vc, 25, scale, use_flash=True))
    b = np.asarray(_attend_cache(q, kc, vc, 25, scale, use_flash=False))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def _block_oracle(q, kc, vc, pos0, scale, ks=None, vs=None):
    b, T = q.shape[0], q.shape[1]
    p0 = jnp.asarray(pos0, jnp.int32)
    p0 = jnp.full((b,), p0) if p0.ndim == 0 else p0
    pos_q = p0[:, None] + jnp.arange(T, dtype=jnp.int32)
    return np.asarray(_attend_cache_block(q, kc, vc, pos_q, scale,
                                          k_scale=ks, v_scale=vs,
                                          use_flash=False))


@pytest.mark.parametrize("T", [1, 4])
def test_block_decode_matches_block_oracle(data, T):
    """flash_block_decode (the speculative verify kernel) vs the
    einsum block attend: per-query causal masks at pos0 + t."""
    _, kc, vc, scale = data
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((B, T, NH, D)), jnp.float32)
    got = np.asarray(flash_block_decode(q, kc, vc, 9, scale,
                                        interpret=True, block_k=16))
    np.testing.assert_allclose(got, _block_oracle(q, kc, vc, 9, scale),
                               rtol=2e-5, atol=2e-5)


def test_block_decode_ragged_pos0(data):
    _, kc, vc, scale = data
    rng = np.random.default_rng(8)
    T = 3
    q = jnp.asarray(rng.standard_normal((B, T, NH, D)), jnp.float32)
    pos0 = jnp.asarray([0, L - T, 17], jnp.int32)
    got = np.asarray(flash_block_decode(q, kc, vc, pos0, scale,
                                        interpret=True, block_k=16))
    np.testing.assert_allclose(got,
                               _block_oracle(q, kc, vc, pos0, scale),
                               rtol=2e-5, atol=2e-5)


def test_block_decode_int8(data):
    _, kc, vc, scale = data
    rng = np.random.default_rng(9)
    T = 4
    q = jnp.asarray(rng.standard_normal((B, T, NH, D)), jnp.float32)
    qk, ks = _quant_seqminor(kc)
    qv, vs = _quant_seqminor(vc)
    kd = jnp.asarray(np.asarray(qk, np.float32)
                     * np.asarray(ks)[:, :, None, :])
    vd = jnp.asarray(np.asarray(qv, np.float32)
                     * np.asarray(vs)[:, :, None, :])
    got = np.asarray(flash_block_decode(q, qk, qv, 21, scale, ks, vs,
                                        interpret=True, block_k=32))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, _block_oracle(q, kd, vd, 21, scale),
                               rtol=1e-2, atol=1e-2)


def test_block_T1_is_flash_decode(data):
    """T=1 block == single-token flash decode BITWISE — the shared-
    numerics argument speculative losslessness rests on requires the
    degenerate case to be the same computation, not a near one."""
    q, kc, vc, scale = data
    a = np.asarray(flash_decode(q, kc, vc, 13, scale, interpret=True,
                                block_k=16))
    b = np.asarray(flash_block_decode(q, kc, vc, 13, scale,
                                      interpret=True, block_k=16))
    np.testing.assert_array_equal(a, b)


# -- dead-tile skip: tiles wholly past a row's last attended position
# are not in the call's work list (decode_work_list), so they are
# neither fetched nor computed nor a grid step. L = 48: block_k 16 gives three
# tiles with edges at 16 and 32; block_k 32 two, the second padded.
_EDGE = 16
SKIP_CASES = {
    # name: (T, pos0, int8, block_k)
    "T1-pos0": (1, 0, False, _EDGE),
    "T1-last-of-tile0": (1, _EDGE - 1, False, _EDGE),
    "T1-first-of-tile1": (1, _EDGE, False, _EDGE),
    "T1-last-position": (1, L - 1, False, _EDGE),
    "T1-ragged": (1, [0, L - 1, _EDGE], False, _EDGE),
    "T4-crosses-edge": (4, _EDGE - 2, False, _EDGE),
    "T4-ends-on-edge": (4, _EDGE - 4, False, _EDGE),
    "T4-ragged": (4, [_EDGE - 3, 0, L - 4], False, _EDGE),
    "T1-int8-edge": (1, _EDGE, True, _EDGE),
    "T4-int8-ragged": (4, [_EDGE - 1, 2 * _EDGE, 3], True, _EDGE),
    "T1-padded-tail-skipped": (1, 31, False, 32),
    "T1-padded-tail-live": (1, [32, L - 1, 5], False, 32),
    "T4-padded-tail-crossed": (4, 29, False, 32),
    "T1-int8-padded-tail": (1, [31, 40, 0], True, 32),
}


def _skip_case(data, name):
    T, pos0, quant, block_k = SKIP_CASES[name]
    _, kc, vc, scale = data
    rng = np.random.default_rng(sorted(SKIP_CASES).index(name))
    q = jnp.asarray(rng.standard_normal((B, T, NH, D)), jnp.float32)
    pos0 = jnp.asarray(pos0, jnp.int32)
    if not quant:
        return q, (kc, vc), (kc, vc), pos0, scale, block_k
    qk, ks = _quant_seqminor(kc)
    qv, vs = _quant_seqminor(vc)
    kd = jnp.asarray(np.asarray(qk, np.float32)
                     * np.asarray(ks)[:, :, None, :])
    vd = jnp.asarray(np.asarray(qv, np.float32)
                     * np.asarray(vs)[:, :, None, :])
    return q, (qk, qv, ks, vs), (kd, vd), pos0, scale, block_k


def _run_kernel(q, cache, pos0, scale, block_k):
    k, v, *scales = cache
    return np.asarray(flash_block_decode(q, k, v, pos0, scale, *scales,
                                         interpret=True,
                                         block_k=block_k))


@pytest.mark.parametrize("name", sorted(SKIP_CASES))
def test_skip_matches_oracle(data, name):
    q, cache, dequant, pos0, scale, block_k = _skip_case(data, name)
    got = _run_kernel(q, cache, pos0, scale, block_k)
    assert np.isfinite(got).all()
    tol = 1e-2 if len(cache) == 4 else 2e-5
    np.testing.assert_allclose(
        got, _block_oracle(q, *dequant, pos0, scale), rtol=tol, atol=tol)


@pytest.mark.parametrize("name", sorted(SKIP_CASES))
def test_skip_is_bitwise_the_unskipped_kernel(data, name, monkeypatch):
    """A wholly masked tile adds p = 0 with corr = 1, so leaving it
    out changes no bit. The unskipped evaluation is the same kernel
    with the rule switched off: the work list then holds every tile
    of every row, each presented and computed, masked, as before the
    skip existed."""
    q, cache, _, pos0, scale, block_k = _skip_case(data, name)
    got = _run_kernel(q, cache, pos0, scale, block_k)
    monkeypatch.setattr(decode_mod, "_last_live_tile",
                        lambda pos, T, bk, n_k: jnp.full_like(pos, n_k - 1))
    want = _run_kernel(q, cache, pos0, scale, block_k)
    np.testing.assert_array_equal(got, want)


def _work_rows(pos, T, bk, n_k):
    """decode_work_list's answer for ``pos`` as plain ints."""
    row_of, tile_of, n_work = decode_work_list(
        jnp.asarray(pos, jnp.int32), T, bk, n_k)
    return ([int(x) for x in row_of], [int(x) for x in tile_of],
            int(n_work))


# (bk, n_k, T, before): ``before`` = 1 is the tail's rule — a round
# attends the cache as it found it, positions < pos, so the list is
# built from pos - 1 (-1 for an empty row: tile 0 alone)
@pytest.mark.parametrize("bk,n_k,T,before", [
    (bk, n_k, T, 0) for bk, n_k in [(16, 3), (32, 2), (512, 2), (128, 8)]
    for T in (1, 4)] + [(128, 8, 1, 1)])
def test_work_list_rule(bk, n_k, T, before):
    """The list the grid walks and the index maps read (interpret mode
    would hide a tile fetched for nothing: the mask zeroes it either
    way). For ``pos`` at every tile edge +- {T, 1, 0}, at 0, and a
    retired slot's far past max_len, in every row of a batch of three:
    n_work is the sum of the rows' live tiles; rows come in order, each
    at least once, a row's tiles 0..last ascending; from n_work on the
    entries are the sentinel; and every block an index map names, at a
    step of the grid or one past it, exists."""
    L, b = bk * n_k, 3
    edges = {e + d for e in range(0, L + bk, bk) for d in (-T, -1, 0, 1)}
    for pos in sorted(p for p in edges | {0, 2 * L, 10 * L} if p >= 0):
        for ib in range(b):
            posv = np.asarray([7, 7, 7])
            posv[ib] = pos
            posv = posv - before
            last = [min(max((int(p) + T - 1) // bk, 0), n_k - 1)
                    for p in posv]
            row_of, tile_of, n_work = _work_rows(posv, T, bk, n_k)
            assert len(row_of) == len(tile_of) == b * n_k + 1
            assert n_work == sum(x + 1 for x in last)
            assert b <= n_work <= b * n_k
            want = [(r, t) for r in range(b) for t in range(last[r] + 1)]
            assert list(zip(row_of, tile_of))[:n_work] == want, (posv, T)
            assert set(row_of[:n_work]) == set(range(b))
            # a live tile starts at or before the row's last attended
            # position (or is tile 0)
            assert all(t == 0 or t * bk <= min(posv[r] + T - 1, L - 1)
                       for r, t in want)
            assert row_of[n_work:] == [b] * (b * n_k + 1 - n_work)
            assert tile_of[n_work:] == [0] * (b * n_k + 1 - n_work)
            # the index maps: (min(row_of[i], b - 1), 0, 0, tile_of[i])
            for i in range(n_work + 1):
                assert 0 <= min(row_of[i], b - 1) < b
                assert 0 <= tile_of[i] < n_k
            # _flush's test: a row ends where the next entry differs
            ends = [i for i in range(n_work) if row_of[i + 1] != row_of[i]]
            assert len(ends) == b and ends[-1] == n_work - 1


def test_work_list_is_checked_and_reusable(data):
    """A caller's own list (kvcache.attend_work builds one a step for
    all layers) gives the bits the call's own would; one built for
    another tiling is refused."""
    q, kc, vc, scale = data
    pos = jnp.asarray([3, _EDGE, L - 1], jnp.int32)
    want = flash_decode(q, kc, vc, pos, scale, interpret=True,
                        block_k=_EDGE)
    work = decode_work_list(pos, 1, _EDGE, L // _EDGE)
    got = jax.jit(lambda w: flash_decode(
        q, kc, vc, pos, scale, interpret=True, block_k=_EDGE, work=w))(
            work)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    with pytest.raises(ValueError, match="a work list for 3 rows"):
        flash_decode(q, kc, vc, pos, scale, interpret=True, block_k=32,
                     work=work)


def test_skip_with_poisoned_dead_context(data):
    """NaN in K and inf in V at every position past the rows'
    contexts: the live tile's own tail is masked as before, and a row
    whose later tiles are not in the work list still flushes its
    output, at its last live tile."""
    q, kc, vc, scale = data
    posv = np.asarray([3, _EDGE, L - 1], np.int32)
    col = np.arange(L)[None, None, None, :]
    dead = col > posv[:, None, None, None]
    kp = jnp.where(dead, jnp.nan, kc)
    vp = jnp.where(dead, jnp.inf, vc)
    got = np.asarray(flash_decode(q, kp, vp, jnp.asarray(posv), scale,
                                  interpret=True, block_k=_EDGE))
    np.testing.assert_allclose(got, _oracle(q, kc, vc, posv, scale),
                               rtol=2e-5, atol=2e-5)


def test_tile_width_export():
    """flash_decode_tile is the kernel's own choice for a cache (what
    DecodeServer's attend-tile counters divide by), a legal Mosaic
    tile that divides a lane-aligned cache: no wider than streams
    _TILE_BYTES of K a grid step, 512 at most."""
    for L_, d, nkv, nh, dtype, want in [
            (1024, 64, 16, 16, jnp.bfloat16, 256),   # the benchmark's
            (1024, 64, 16, 16, jnp.int8, 256),       # dots in bf16
            (1024, 64, 16, 16, jnp.float32, 128),
            (1024, 64, 4, 8, jnp.bfloat16, 512),     # GQA: fewer heads
            (2048, 128, 8, 32, jnp.bfloat16, 256),
            (256, 64, 4, 8, jnp.bfloat16, 256),
            (128, 128, 2, 2, jnp.bfloat16, 128)]:
        cache = jax.ShapeDtypeStruct((2, nkv, d, L_), dtype)
        bk = flash_decode_tile(cache, nh)
        assert bk == want, (L_, d, nkv, nh, dtype)
        assert bk % 128 == 0 and L_ % bk == 0 and bk <= L_
        assert can_flash_decode(L_, d)


def test_jittable_and_sharded(data):
    """jit + tp-sharded shard_map. The shard_map leg runs
    check_vma=False: pallas's HLO interpreter slices blocks with
    unvarying grid indices, which the vma checker rejects for varying
    operands (JAX's error message itself prescribes check_vma=False);
    the Mosaic path on real TPU does not go through that interpreter,
    and flash_decode pre-varies its pos operand so the kernel's
    operands stay vma-uniform there."""
    from jax.sharding import PartitionSpec as P

    from rlo_tpu.parallel.mesh import make_mesh, shard_jit
    q, kc, vc, scale = data
    f = jax.jit(lambda q, k, v: flash_decode(q, k, v, 12, scale,
                                             interpret=True,
                                             block_k=16))
    np.testing.assert_allclose(np.asarray(f(q, kc, vc)),
                               _oracle(q, kc, vc, 12, scale),
                               rtol=2e-5, atol=2e-5)
    mesh = make_mesh((2,), ("tp",))
    g = shard_jit(
        lambda q, k, v: flash_decode(q, k, v, 12, scale,
                                     interpret=True, block_k=16),
        mesh, (P(None, None, "tp"), P(None, "tp"), P(None, "tp")),
        P(None, None, "tp"), check_vma=False)
    np.testing.assert_allclose(np.asarray(g(q, kc, vc)),
                               _oracle(q, kc, vc, 12, scale),
                               rtol=2e-5, atol=2e-5)


class TestWriteKvRow:
    """Aliased single-position cache write kernel vs the DUS oracle."""

    def _mk(self, dtype=jnp.float32, L=256):
        rng = np.random.default_rng(11)
        cache = jnp.asarray(rng.standard_normal((B, NKV, D, L)), dtype)
        row = jnp.asarray(rng.standard_normal((B, NKV, D)), dtype)
        return cache, row

    def test_matches_dus_scalar_pos(self):
        from rlo_tpu.pallas.decode import write_kv_row
        cache, row = self._mk()
        got = np.asarray(write_kv_row(cache, row, 129, interpret=True))
        want = np.asarray(cache).copy()
        want[:, :, :, 129] = np.asarray(row)
        np.testing.assert_array_equal(got, want)

    def test_matches_dus_ragged(self):
        from rlo_tpu.pallas.decode import write_kv_row
        cache, row = self._mk()
        pos = jnp.asarray([0, 255, 131], jnp.int32)
        got = np.asarray(write_kv_row(cache, row, pos, interpret=True))
        want = np.asarray(cache).copy()
        for bidx, p in enumerate(np.asarray(pos)):
            want[bidx, :, :, p] = np.asarray(row)[bidx]
        np.testing.assert_array_equal(got, want)

    def test_int8(self):
        from rlo_tpu.pallas.decode import write_kv_row
        rng = np.random.default_rng(12)
        cache = jnp.asarray(rng.integers(-127, 127, (B, NKV, D, 128)),
                            jnp.int8)
        row = jnp.asarray(rng.integers(-127, 127, (B, NKV, D)),
                          jnp.int8)
        got = np.asarray(write_kv_row(cache, row, 127, interpret=True))
        want = np.asarray(cache).copy()
        want[:, :, :, 127] = np.asarray(row)
        np.testing.assert_array_equal(got, want)

    def test_gate(self):
        from rlo_tpu.pallas.decode import can_write_row
        assert can_write_row(128) and can_write_row(1280)
        # a ragged tail past the last full 128-lane block is unreachable
        assert not can_write_row(64) and not can_write_row(1216)


class TestWriteKvBlock:
    """Aliased T-column cache write (the verify-path scatter killer)."""

    def _mk(self, L=384, T=5):
        rng = np.random.default_rng(21)
        cache = jnp.asarray(rng.standard_normal((B, NKV, D, L)),
                            jnp.float32)
        rows = jnp.asarray(rng.standard_normal((B, NKV, D, T)),
                           jnp.float32)
        return cache, rows

    @pytest.mark.parametrize("pos0", [0, 100, 126, 256, 379])
    def test_matches_scatter(self, pos0):
        from rlo_tpu.pallas.decode import write_kv_block
        cache, rows = self._mk()
        T = rows.shape[3]
        got = np.asarray(write_kv_block(cache, rows, pos0,
                                        interpret=True))
        want = np.asarray(cache).copy()
        want[:, :, :, pos0:pos0 + T] = np.asarray(rows)
        np.testing.assert_array_equal(got, want)

    def test_ragged_pos0_straddles_blocks(self):
        from rlo_tpu.pallas.decode import write_kv_block
        cache, rows = self._mk()
        T = rows.shape[3]
        pos0 = jnp.asarray([125, 0, 379], jnp.int32)  # straddle/edge
        got = np.asarray(write_kv_block(cache, rows, pos0,
                                        interpret=True))
        want = np.asarray(cache).copy()
        for bi, p in enumerate(np.asarray(pos0)):
            want[bi, :, :, p:p + T] = np.asarray(rows)[bi]
        np.testing.assert_array_equal(got, want)

    def test_gate(self):
        from rlo_tpu.pallas.decode import can_write_block
        assert can_write_block(256) and can_write_block(1280)
        assert not can_write_block(128)   # needs two slidable blocks
        assert not can_write_block(200)   # non-x128


def test_write_row_oob_pos_is_dropped():
    """serve advances retired slots past max_len: an out-of-range pos
    must write NOTHING (the scatter it replaced dropped OOB writes)."""
    from rlo_tpu.pallas.decode import write_kv_row
    rng = np.random.default_rng(31)
    cache = jnp.asarray(rng.standard_normal((B, NKV, D, 256)),
                        jnp.float32)
    row = jnp.asarray(rng.standard_normal((B, NKV, D)), jnp.float32)
    pos = jnp.asarray([256, 300, 10_000], jnp.int32)
    got = np.asarray(write_kv_row(cache, row, pos, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(cache))


# -- the write-behind tail (PR 28): a round's new K/V rows wait in a
# token-major tail; the attend covers cache positions <= pos and tail
# rows 0..newest (row t = position pos + 1 + t), the fold writes all
# rows into the cache at once.
TAIL_KK, TAIL_L = 8, 256    # two cache tiles of 128
# row by row: an empty cache, mid-cache, a tail that crosses max_len
# part-way, a cache that ends exactly where the tail begins to drop,
# and a retired slot already past max_len
TAIL_POS0 = [0, 130, TAIL_L - 3, TAIL_L, TAIL_L + 5]
TAIL_KINDS = {"mha": (4, 4, 64, 0), "gqa": (8, 4, 64, 0),
              "latent": (8, 1, 576, 512)}   # nh, nkv, d, v_dim


def _tail_case(kind, seed=0):
    nh, nkv, d, v_dim = TAIL_KINDS[kind]
    nb = len(TAIL_POS0)
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    q = draw(nb, 1, nh, d)
    kc = draw(nb, nkv, d, TAIL_L)
    tk = draw(TAIL_KK, nb, nkv, d)
    vc, tv = (None, None) if v_dim else (draw(nb, nkv, d, TAIL_L),
                                         draw(TAIL_KK, nb, nkv, d))
    return q, kc, vc, tk, tv, v_dim, 1.0 / np.sqrt(d)


def _written(cache, tail, pos0, upto):
    """``cache`` after the per-step path stored tail rows 0..upto: row t
    at column pos0_b + t, dropped at or past max_len (plain numpy)."""
    if cache is None:
        return None
    out = np.asarray(cache).copy()
    for bi, p in enumerate(pos0):
        for t in range(upto + 1):
            if p + t < out.shape[3]:
                out[bi, :, :, p + t] = np.asarray(tail)[t, bi]
    return jnp.asarray(out)


@pytest.mark.parametrize("path", ["kernel", "einsum"])
@pytest.mark.parametrize("newest", [0, 3, TAIL_KK - 1])
@pytest.mark.parametrize("kind", sorted(TAIL_KINDS))
def test_tail_attend_is_write_then_attend(kind, newest, path):
    """Attending cache and tail in one softmax == writing rows
    0..newest into the cache and attending it at pos0 + newest (the
    float32 einsum oracle), for ragged pos0 up to and past max_len."""
    q, kc, vc, tk, tv, v_dim, scale = _tail_case(kind)
    pos0 = jnp.asarray(TAIL_POS0, jnp.int32)
    tail = (tk, tv, jnp.int32(newest))
    if path == "kernel":
        got = flash_decode(q, kc, vc, pos0 - 1, scale, interpret=True,
                           block_k=128, v_dim=v_dim, tail=tail)
    else:
        got = _attend_cache(q, kc, vc, pos0 - 1, scale, use_flash=False,
                            v_dim=v_dim, tail=tail)
    assert np.isfinite(np.asarray(got)).all()
    want = _attend_cache(q, _written(kc, tk, TAIL_POS0, newest),
                         _written(vc, tv, TAIL_POS0, newest),
                         pos0 + newest, scale, use_flash=False,
                         v_dim=v_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_tail_refuses_what_it_cannot_hold(data):
    q, kc, vc, scale = data
    tk = jnp.zeros((4, B, NKV, D), jnp.float32)
    with pytest.raises(ValueError, match="a tail goes with one query"):
        flash_block_decode(jnp.zeros((B, 2, NH, D)), kc, vc, 3, scale,
                           interpret=True, tail=(tk, tk, 0))
    qk, ks = _quant_seqminor(kc)
    with pytest.raises(ValueError, match="a tail goes with one query"):
        flash_decode(q, qk, qk, 3, scale, ks, ks, interpret=True,
                     tail=(tk, tk, 0))


@pytest.mark.parametrize("path", ["kernel", "scatter"])
@pytest.mark.parametrize("kind", ["per-head", "latent"])
def test_tail_fold_drops_what_row_writes_drop(kind, path, monkeypatch):
    """Folding kk tail rows == kk successive write_kv_row calls, bit
    for bit: rows that cross a 128-lane block, cross max_len part-way
    (the columns below it land, the rest are dropped), start at
    max_len and lie far past it."""
    from rlo_tpu.models import generate
    from rlo_tpu.pallas.decode import write_kv_row, write_kv_tail
    kk, L = 32, 256
    nkv, d = (1, 48) if kind == "latent" else (NKV, D)
    pos0 = jnp.asarray([5, 120, L - 10, L, 10_000], jnp.int32)
    rng = np.random.default_rng(41)
    cache = jnp.asarray(rng.standard_normal((5, nkv, d, L)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((kk, 5, nkv, d)), jnp.float32)
    want = cache
    for t in range(kk):
        want = write_kv_row(want, tail[t], pos0 + t, interpret=True)
    if path == "kernel":
        got = write_kv_tail(cache, tail, pos0, interpret=True)
    else:       # off the tpu backend the gate is shut: the XLA scatter
        got = generate.fold_kv_tail([{"k": cache}], [{"k": tail}],
                                    pos0)[0]["k"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got)[2], np.asarray(cache)[2])
    np.testing.assert_array_equal(np.asarray(got)[3:],
                                  np.asarray(cache)[3:])


# the call as PR 36 left it, before the kernel fetched its own K/V: the
# kernel (verbatim but for comments) with K, V and the scales on
# BlockSpecs whose index maps read the work list, double-buffered by the
# pipeline. What "same tile, same bits" is held to (ROADMAP D14: the one
# oracle; the (b, n_k) grid of PR 29 that stood here went with it).
def _pr36_decode_kernel(row_ref, tile_ref, pos_ref, *refs, scale: float,
                   bk: int, max_len: int, quant: bool,
                   r: int, T: int, v_dim: int = 0, n_tail: int = 0,
                   selected: bool = False, block_len: int = 0,
                   sinks: bool = False):
    if n_tail:          # a fourth prefetched scalar: the tail's newest row
        newest_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, *rest = refs
    if not v_dim:       # a V operand; a latent cache has none
        v_ref, rest = rest[0], rest[1:]
    if n_tail:          # the round's own rows, after the cache operands
        tk_ref, rest = rest[0], rest[1:]
        if not v_dim:
            tv_ref, rest = rest[0], rest[1:]
    if selected:        # the positions this row may attend, 1.0 or 0.0
        sel_ref, rest = rest[0], rest[1:]
    if sinks:           # a logit a query row, joined at the flush
        sink_ref, rest = rest[0], rest[1:]
    if quant:
        ks_ref, vs_ref, o_ref, m_s, l_s, o_s = rest
    else:
        o_ref, m_s, l_s, o_s = rest
    i = pl.program_id(0)
    ib = row_ref[i]
    ik = tile_ref[i]
    dot_dt = jnp.float32 if k_ref.dtype == jnp.float32 else jnp.bfloat16

    @pl.when(ik == 0)
    def _init():
        if not n_tail:
            m_s[...] = jnp.full_like(m_s[...], decode_mod._NEG)
            l_s[...] = jnp.zeros_like(l_s[...])
            o_s[...] = jnp.zeros_like(o_s[...])
            return
        q = q_ref[0].astype(dot_dt)                      # (g, r, d)
        if v_dim:
            kt = tk_ref[:, 0, 0, :][None].astype(dot_dt)  # (1, kk, d)
            vt = kt[:, :, :v_dim]
        else:                                 # (kk, g, d) -> (g, kk, d)
            kt = jnp.swapaxes(tk_ref[:, 0], 0, 1).astype(dot_dt)
            vt = jnp.swapaxes(tv_ref[:, 0], 0, 1).astype(dot_dt)
        t = jax.lax.broadcasted_iota(jnp.int32, (1, 1, n_tail), 2)
        live = (t <= newest_ref[0]) & (pos_ref[ib] + 1 + t < max_len)
        s = jax.lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(live, s, decode_mod._NEG)                     # (g, r, kk)
        m = s.max(axis=-1)
        p = jnp.where(live, jnp.exp(s - m[..., None]), 0.0)
        m_s[...] = m
        l_s[...] = p.sum(axis=-1)
        o_s[...] = jax.lax.dot_general(
            p.astype(dot_dt), vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    pos = pos_ref[ib]


    q = q_ref[0].astype(dot_dt)                      # (g, T*r, d)
    k = k_ref[0].astype(dot_dt)                      # (g, d, BK)
    v = (k[:, :v_dim, :] if v_dim
         else v_ref[0].astype(dot_dt))               # (g, d, BK)
    base = ik * bk
    row = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bk), 2)
    qoff = jax.lax.broadcasted_iota(jnp.int32, (1, T * r, 1), 1) // r
    if block_len:
        qoff = qoff // block_len * block_len + (block_len - 1)
    mask_row = (row <= pos + qoff) & (row < max_len)  # (1, T*r, BK)
    if selected:        # a token selector's choice, every head the same
        mask_row = mask_row & (sel_ref[0] > 0.0)     # (1, 1, BK)
    mask_col = (row <= pos + (T - 1)) & (row < max_len)  # (1, 1, BK)

    s = jax.lax.dot_general(q, k, (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32) * scale
    if quant:
        s = s * ks_ref[0]                            # (g, 1, BK)
    s = jnp.where(mask_row, s, decode_mod._NEG)                 # (g, T*r, BK)
    v = jnp.where(mask_col, v, jnp.zeros((), dot_dt))

    m = m_s[...]                                     # (g, T*r)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.where(mask_row, jnp.exp(s - m_new[..., None]), 0.0)
    corr = jnp.exp(m - m_new)
    m_s[...] = m_new
    l_s[...] = l_s[...] * corr + p.sum(axis=-1)
    pv = jnp.where(mask_row, p * vs_ref[0], 0.0) if quant else p
    o_s[...] = o_s[...] * corr[..., None] + jax.lax.dot_general(
        pv.astype(dot_dt), v, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)

    @pl.when(row_ref[i + 1] != ib)
    def _flush():
        if sinks:
            den = l_s[...] + jnp.exp(sink_ref[...] - m_s[...])
            o_ref[0] = o_s[...] / den[..., None]
            return
        o_ref[0] = o_s[...] / l_s[...][..., None]


def _pr36_flash_block_decode(q, k_cache, v_cache, pos0, scale, k_scale=None,
                             v_scale=None, *, block_k=None, v_dim=0,
                             tail=None, select=None, block_len=0,
                             sinks=None):
    b, T, nh, d = q.shape
    nkv, L_ = k_cache.shape[1], k_cache.shape[3]
    r, R, dv = nh // nkv, T * (nh // nkv), v_dim or d
    quant, latent = k_scale is not None, v_cache is None
    widest, tile_bytes = decode_mod._tile_rule(latent, L_)
    itemsize = 4 if k_cache.dtype == jnp.float32 else 2
    bk = decode_mod._pick_bk(L_, d, nkv, r, itemsize, block_k or widest,
                             tile_bytes, streams=1 if latent else 2)
    n_k = -(-L_ // bk)
    qg = (q.reshape(b, T, nkv, r, d).transpose(0, 2, 1, 3, 4)
          .reshape(b, nkv, R, d))
    posv = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32), (b,))
    row_of, tile_of, n_work = decode_work_list(posv, T, bk, n_k)
    row_map = lambda i, row_ref, tile_ref, pos_ref, _newest=None: (  # noqa: E731
        jnp.minimum(row_ref[i], b - 1), 0, 0, 0)
    cache_map = lambda i, row_ref, tile_ref, pos_ref, _newest=None: (  # noqa: E731
        jnp.minimum(row_ref[i], b - 1), 0, 0, tile_ref[i])
    kv_spec = pl.BlockSpec((1, nkv, d, bk), cache_map)
    in_specs = [pl.BlockSpec((1, nkv, R, d), row_map), kv_spec]
    args, scalars, n_tail = [qg, k_cache], [row_of, tile_of, posv], 0
    if not latent:
        in_specs += [kv_spec]
        args += [v_cache]
    if quant:
        s_spec = pl.BlockSpec((1, nkv, 1, bk), cache_map)
        in_specs += [s_spec, s_spec]
        args += [k_scale[:, :, None, :], v_scale[:, :, None, :]]
    if tail is not None:
        tk, tv, newest = tail
        n_tail = tk.shape[0]
        t_spec = pl.BlockSpec(
            (n_tail, 1, nkv, d),
            lambda i, row_ref, tile_ref, pos_ref, _newest: (
                0, jnp.minimum(row_ref[i], b - 1), 0, 0))
        for rows in (tk,) if latent else (tk, tv):
            in_specs += [t_spec]
            args += [rows.astype(k_cache.dtype)]
        scalars += [jnp.asarray(newest, jnp.int32).reshape(1)]
    if select is not None:
        in_specs += [pl.BlockSpec((1, 1, 1, bk), cache_map)]
        args += [select.astype(jnp.float32)[:, None, None, :]]
    if sinks is not None:   # head g * r + rr at query row t * r + rr
        in_specs += [pl.BlockSpec((nkv, R), lambda *_: (0, 0))]
        args += [jnp.tile(sinks.astype(jnp.float32).reshape(nkv, 1, r),
                          (1, T, 1)).reshape(nkv, R)]
    out = pl.pallas_call(
        functools.partial(_pr36_decode_kernel, scale=float(scale), bk=bk,
                          max_len=L_, quant=quant, r=r, T=T, v_dim=v_dim,
                          n_tail=n_tail, selected=select is not None,
                          block_len=block_len, sinks=sinks is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(n_work,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, nkv, R, dv), row_map),
            scratch_shapes=[pltpu.VMEM((nkv, R), jnp.float32),
                            pltpu.VMEM((nkv, R), jnp.float32),
                            pltpu.VMEM((nkv, R, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, nkv, R, dv), jnp.float32),
        interpret=True)(*scalars, *args)
    return (out.reshape(b, nkv, T, r, dv).transpose(0, 2, 1, 3, 4)
            .reshape(b, T, nh, dv))


PARENT_CASES = {
    # name: (T, pos0, int8, latent, block_k)
    "T1": (1, [0, L - 1, _EDGE], False, False, _EDGE),
    "T4-verify": (4, [_EDGE - 3, 0, L - 4], False, False, _EDGE),
    "T1-int8": (1, [31, 40, 0], True, False, 32),
    "T4-int8": (4, [_EDGE - 1, 2 * _EDGE, 3], True, False, _EDGE),
    "T1-padded-tail-tile": (1, [32, L - 1, 5], False, False, 32),
    "T1-latent": (1, [0, L - 1, _EDGE], False, True, None),
    "T4-latent": (4, [_EDGE - 3, 0, L - 4], False, True, None),
    "T1-retired-slot": (1, [2 * L, 3, 10 * L], False, False, _EDGE),
    # 128 heads on one latent stream: a step that computes longer than
    # it streams keeps the pipeline's whole-tile fetch (_ring_slots)
    "T1-latent-128-heads": (1, [0, L - 1, _EDGE], False, True, None),
    "T1-select": (1, [0, L - 1, _EDGE], False, False, _EDGE),
    "T4-block-causal": (4, [_EDGE - 4, 0, L - 4], False, False, _EDGE),
}


@pytest.mark.parametrize("name", sorted(PARENT_CASES))
def test_is_bitwise_the_parent_call(data, name):
    """At an unchanged tile width a row's tiles arrive in the same
    order into the same accumulators: every caller (generate's scan,
    the speculative verify, the long-prompt extend, int8 and latent
    caches, a selection, the block-causal mask) gets the bits the
    BlockSpec pipeline's tiles gave."""
    T, pos0, quant, latent, block_k = PARENT_CASES[name]
    _, kc, vc, scale = data
    rng = np.random.default_rng(sorted(PARENT_CASES).index(name))
    pos0 = jnp.asarray(pos0, jnp.int32)
    if latent:
        nh = 128 if name == "T1-latent-128-heads" else NH
        q = jnp.asarray(rng.standard_normal((B, T, nh, 144)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, 1, 144, L)), jnp.float32)
        args, kw = (q, k, None, pos0, scale), {"v_dim": 128}
        assert bool(decode_mod.flash_decode_slots(k, nh, 128, T)) == (
            nh == NH)
    elif quant:
        q = jnp.asarray(rng.standard_normal((B, T, NH, D)), jnp.float32)
        qk, ks = _quant_seqminor(kc)
        qv, vs = _quant_seqminor(vc)
        args, kw = (q, qk, qv, pos0, scale, ks, vs), {}
    else:
        q = jnp.asarray(rng.standard_normal((B, T, NH, D)), jnp.float32)
        args, kw = (q, kc, vc, pos0, scale), {}
    if name == "T1-select":     # a selector's choice: about half of all
        kw["select"] = jnp.asarray(rng.random((B, L)) < 0.5)
    if name == "T4-block-causal":
        kw["block_len"] = 4
    got = np.asarray(flash_block_decode(*args, interpret=True,
                                        block_k=block_k, **kw))
    want = np.asarray(_pr36_flash_block_decode(*args, block_k=block_k,
                                               **kw))
    np.testing.assert_array_equal(got, want)


# -- a long latent cache's tile: from _LATENT_LONG_LEN positions a row
# the rule takes tiles of 2048 lanes, below it 1024; a per-head cache
# keeps its own rule at any length.
@pytest.mark.parametrize("shape,heads,latent,want", [
    ((32, 1, 576, 24576), 128, True, 2048),   # deepseek-v3.2-ep32's
    ((16, 1, 576, 49152), 64, True, 2048),    # hy4-preview-ep32's
    ((128, 1, 576, 4096), 128, True, 1024),   # deepseek-v3-ep16's
    ((2, 1, 576, 16384), 128, True, 2048),    # the threshold
    ((2, 1, 576, 12288), 128, True, 1024),    # under it
    ((48, 8, 128, 16384), 48, False, 256),    # trinity's full layer
    ((2, 8, 128, 32768), 32, False, 256)])
def test_tile_follows_the_cache_length(shape, heads, latent, want):
    cache = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert flash_decode_tile(cache, heads, latent=latent) == want
    assert shape[3] % want == 0
    assert can_flash_decode(shape[3], shape[2], v_dim=512 if latent else 0)


LONG_L, LONG_BK = decode_mod._LATENT_LONG_LEN, 2048
LONG_CASES = {
    # name: pos by row (the last position a row's query attends)
    "last-tile-partial": [LONG_BK, 5000, LONG_L - 700],
    "ends-on-a-tile-boundary": [LONG_BK - 1, 2 * LONG_BK - 1, LONG_L - 1],
    "shorter-than-a-tile": [100, 0, LONG_BK - 2],
}


@pytest.mark.parametrize("heads", [16, 128])
@pytest.mark.parametrize("name", sorted(LONG_CASES))
def test_long_latent_tile_is_bitwise_the_parent_call(name, heads):
    """A latent cache of _LATENT_LONG_LEN positions at the rule's 2048
    lanes, under a selection and with a sink a head: the tiles reach
    the same accumulators in the same order as the BlockSpec call's
    at that width, through the kernel's own ring (16 heads) and the
    pipeline (128: a step that computes longer than it streams)."""
    rng = np.random.default_rng(sorted(LONG_CASES).index(name))
    pos = jnp.asarray(LONG_CASES[name], jnp.int32)
    nb = pos.shape[0]
    q = jnp.asarray(rng.standard_normal((nb, 1, heads, 144), np.float32))
    k = jnp.asarray(rng.standard_normal((nb, 1, 144, LONG_L), np.float32))
    assert flash_decode_tile(k, heads, latent=True) == LONG_BK
    assert bool(decode_mod.flash_decode_slots(k, heads, 128)) == (
        heads == 16)
    kw = {"v_dim": 128, "select": jnp.asarray(rng.random((nb, LONG_L))
                                              < 0.5),
          "sinks": jnp.asarray(2.0 + rng.standard_normal(heads),
                               jnp.float32)}
    got = np.asarray(flash_block_decode(q, k, None, pos, 144 ** -0.5,
                                        interpret=True, **kw))
    want = np.asarray(_pr36_flash_block_decode(q, k, None, pos, 144 ** -0.5,
                                               block_k=LONG_BK, **kw))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("newest", [0, TAIL_KK - 1])
@pytest.mark.parametrize("kind", sorted(TAIL_KINDS))
def test_tail_is_bitwise_the_parent_call(kind, newest):
    """The same with a round's tail, for ragged pos0 up to and past
    max_len (two tiles of 128: rows of one and of two live tiles)."""
    q, kc, vc, tk, tv, v_dim, scale = _tail_case(kind, seed=3)
    pos0 = jnp.asarray(TAIL_POS0, jnp.int32)
    tail = (tk, tv, jnp.int32(newest))
    got = flash_decode(q, kc, vc, pos0 - 1, scale, interpret=True,
                       block_k=128, v_dim=v_dim, tail=tail)
    want = _pr36_flash_block_decode(q, kc, vc, pos0 - 1, scale,
                                    block_k=128, v_dim=v_dim, tail=tail)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- the kernel's own fetch (PR 37): K and V stay in HBM and the kernel
# copies its tiles into a ring of VMEM slots, _ring_span's steps ahead,
# a row's last tile only as far as it is live (_copy_lanes: whole
# 128-lane blocks). Two kv heads x 64 x 1024 in tiles of 256: a copy is
# 128 or 256 lanes; what a slot holds past a short copy is an older
# tile's.
RING_L, RING_BK, RING_NH, RING_NKV = 1024, 256, 4, 2
RING_CASES = {
    # name: (T, pos0 row by row)
    "fewer-steps-than-slots": (1, [5]),                 # n_work = 1
    "one-step-short-of-the-ring": (1, [RING_BK + 40]),  # n_work = N - 1
    "as-many-steps-as-slots": (1, [2 * RING_BK + 40]),  # n_work = N
    "every-row-one-tile": (1, [3, 100, 255, 0, 17, 128, 127]),
    "retired-slot-past-max-len": (1, [5 * RING_L, 3, 2 * RING_L, 300]),
    "last-tile-live-in-1-lane": (1, [RING_BK, 700, 2 * RING_BK, 0]),
    "last-tile-live-in-128": (1, [RING_BK + 127, 5, 127, 900]),
    "last-tile-live-whole": (1, [2 * RING_BK - 1, 1023, 255, 64]),
    "T4-block-crosses-a-copy-edge": (4, [126, 254, 380, 0, 1020]),
    "T4-block-causal": (4, [124, 252, 0, 1020]),
}


def _ring_case(name, dtype=jnp.float32):
    T, pos0 = RING_CASES[name]
    nb = len(pos0)
    rng = np.random.default_rng(sorted(RING_CASES).index(name))
    q = jnp.asarray(rng.standard_normal((nb, T, RING_NH, D)), dtype)
    kc = jnp.asarray(rng.standard_normal((nb, RING_NKV, D, RING_L)), dtype)
    vc = jnp.asarray(rng.standard_normal((nb, RING_NKV, D, RING_L)), dtype)
    kw = {"block_len": 4} if name == "T4-block-causal" else {}
    return q, kc, vc, jnp.asarray(pos0, jnp.int32), 1.0 / np.sqrt(D), kw


def test_fetch_form_follows_the_shape():
    """_ring_slots: the kernel copies its own K/V where a grid step
    streams longer than it computes (the matmuls' flops a byte of K/V
    it reads under half a v5e's ridge), and leaves a compute-bound
    step to the pipeline's whole tiles. The four cells' caches, a
    speculative verify, a long-prompt chunk; decode_lanes_fetched
    counts whole tiles where there is no ring."""
    def cache(nkv, d, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((2, nkv, d, 1024), dtype)

    slots = decode_mod.flash_decode_slots
    n = decode_mod._RING_SLOTS
    assert slots(cache(16, 64), 16) == n                  # gpt2-medium
    assert slots(cache(16, 64, jnp.int8), 16) == n
    assert slots(cache(16, 64), 16, T=8) == n             # a verify of 8
    assert slots(cache(16, 64), 16, T=256) == 0           # a prompt chunk
    assert slots(cache(4, 128), 32, T=4) == n             # sdar's block
    assert slots(cache(1, 576), 128, 512) == 0            # DeepSeek's latent
    assert slots(cache(1, 576), 16, 512) == n             # a shard of it
    pos = np.asarray([5, 300, 1023, 5000])
    np.testing.assert_array_equal(
        decode_mod.decode_lanes_fetched(pos, 1, 256, 1024, slots=0),
        [256, 512, 1024, 1024])
    np.testing.assert_array_equal(
        decode_mod.decode_lanes_fetched(pos, 1, 256, 1024),
        [128, 384, 1024, 1024])


def test_ring_cases_hit_what_they_name():
    """The three short lists are n_work = 1, N - 1 and N at the
    kernel's own ring depth, and the copy rule takes every static size
    somewhere in the cases."""
    n = decode_mod._RING_SLOTS
    steps = {name: int(decode_work_list(
        jnp.asarray(pos0, jnp.int32), T, RING_BK, RING_L // RING_BK)[2])
        for name, (T, pos0) in RING_CASES.items()}
    assert steps["fewer-steps-than-slots"] == 1 < n
    assert steps["one-step-short-of-the-ring"] == n - 1
    assert steps["as-many-steps-as-slots"] == n
    assert steps["every-row-one-tile"] == 7
    assert decode_mod._copy_sizes(RING_BK, RING_L) == [128, 256]
    assert decode_mod._copy_sizes(512, 1536) == [128, 256, 384, 512]
    # a cache the tile does not divide: its last tile's own width
    assert decode_mod._copy_sizes(32, 48) == [16, 32]
    assert decode_mod._copy_sizes(48, 48) == [48]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("name", sorted(RING_CASES))
def test_ring_is_bitwise_the_parent_call(name, dtype):
    """Whatever the ring's depth against the list's length and however
    short a row's last copy, the tiles reach the same accumulators in
    the same order with the same live lanes: the bits of the parent's
    BlockSpec call."""
    q, kc, vc, pos0, scale, kw = _ring_case(name, jnp.dtype(dtype))
    got = flash_block_decode(q, kc, vc, pos0, scale, interpret=True,
                             block_k=RING_BK, **kw)
    want = _pr36_flash_block_decode(q, kc, vc, pos0, scale,
                                    block_k=RING_BK, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("latent", [False, True])
def test_ring_stale_lanes_are_masked(latent):
    """NaN in K and inf in V at every dead position, and rows ordered
    so that a short copy (a context under 128: 128 lanes) lands in a
    slot whose last tenant copied 256 lanes, the upper half of them
    dead: the slot's stale half then holds NaN that no copy of this
    row brought, and it must reach nothing."""
    pos = np.asarray([200] * 4 + [5] * 4 + [130] * 4 + [100] * 4, np.int32)
    nb, (d, v_dim) = len(pos), (144, 128) if latent else (D, 0)
    nkv = 1 if latent else RING_NKV
    rng = np.random.default_rng(37)
    q = jnp.asarray(rng.standard_normal((nb, 1, RING_NH, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((nb, nkv, d, RING_L)), jnp.float32)
    vc = None if latent else jnp.asarray(
        rng.standard_normal((nb, nkv, d, RING_L)), jnp.float32)
    dead = np.arange(RING_L)[None, None, None, :] > pos[:, None, None, None]
    kp = jnp.where(dead, jnp.nan, kc)
    vp = None if latent else jnp.where(dead, jnp.inf, vc)
    scale = 1.0 / np.sqrt(d)
    got = np.asarray(flash_decode(q, kp, vp, jnp.asarray(pos), scale,
                                  interpret=True, block_k=RING_BK,
                                  v_dim=v_dim))
    assert np.isfinite(got).all()
    want = _attend_cache(q, kc, vc, jnp.asarray(pos), scale,
                         use_flash=False, v_dim=v_dim)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("bk,max_len,T", [
    (256, 1024, 1), (512, 1536, 4), (1024, 4096, 1), (128, 1024, 1),
    (32, 48, 1), (48, 48, 1)])
def test_copy_rule(bk, max_len, T):
    """_copy_lanes / decode_lanes_fetched, the rule the kernel copies
    by and the server counts by: for pos at every granule edge +- 1, at
    -1 (an empty row under the tail's rule) and far past max_len, a
    row's copies cover its live positions, stop inside its last live
    tile and inside the cache, come in whole granules (or end with the
    cache), and every one is a size the kernel holds a copy for."""
    g = decode_mod._copy_granule(bk)
    sizes = decode_mod._copy_sizes(bk, max_len)
    n_k = -(-max_len // bk)
    edges = {e + d for e in range(0, max_len + g, g) for d in (-1, 0, 1)}
    pos = np.asarray(sorted(p for p in edges | {-1, 3 * max_len}
                            if p >= -1), np.int64)
    fetched = decode_mod.decode_lanes_fetched(pos, T, bk, max_len)
    live = np.clip(pos + T, 0, max_len)
    last = np.clip((pos + T - 1) // bk, 0, n_k - 1)
    assert (fetched >= np.maximum(live, 1)).all()
    assert (fetched <= np.minimum((last + 1) * bk, max_len)).all()
    assert ((fetched % g == 0) | (fetched == max_len)).all()
    assert (fetched - np.maximum(live, 1) < g).all()
    for p, f_, l_ in zip(pos, fetched, last):
        lanes = int(decode_mod._copy_lanes(
            min(int(p) + T, max_len), int(l_), bk, max_len, xp=np))
        assert lanes in sizes and f_ == l_ * bk + lanes, (p, lanes)
