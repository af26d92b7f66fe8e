"""Pallas flash-attention kernel for the ring-attention block update.

Fuses the per-ring-step online-softmax update — q·kᵀ on the MXU, causal
masking, the running-max rescale, and the (m, l, o) accumulation — into
one VMEM-resident kernel (VERDICT round-1 item 5). The unfused XLA path
(rlo_tpu/ops/ring_attention.py:_block_update) materializes the (H, Lq,
Lk) score and probability tensors in HBM between ops; here each (BQ, Lk)
score tile lives and dies in VMEM, so the only HBM traffic is the
operands and the carried state. Measured on the v5e chip (causal, block
2048, 8 heads, head_dim 128, bf16): 0.142 ms vs 0.610 ms unfused —
4.3x (benchmarks/flash_bench.py).

The kernel is the *step* of ring attention, not a whole attention: the
K/V block rotating in from the ppermute ring is consumed against the
resident Q block, updating the (m, l, o) accumulators in place
(input_output_aliases). Same numerics as _block_update; parity-tested in
interpret mode on CPU and compiled on TPU.

Layouts are head-leading — q/k/v/o as (H, L, D), m/l as (H, 1, L) — so
every block's trailing two dims are (sublane, lane) shaped (Mosaic's
tiling constraint). `flash_block_update_hld` takes and returns that
layout directly (ring_attention carries it across the whole ring loop —
one transpose in, one out, instead of per step); `flash_block_update`
is the convenience wrapper in ring_attention's caller layout.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from rlo_tpu.pallas.reduce import _on_tpu, out_struct

_NEG = -1e30  # matches ring_attention._NEG (finite: exp/max NaN-free)

#: auto-tiling width when a single K tile would overflow VMEM
_AUTO_BLOCK_K = 512


def _vmem_fits(bq: int, bk: int, d: int, budget: int = 12 << 20) -> bool:
    """Per-grid-step f32 working set of the kernel: score + probability
    tiles, the K/V casts, and the q/o blocks."""
    return 4 * (2 * bq * bk + 2 * bk * d + 2 * bq * d) <= budget


def _select_bk(bq: int, lk: int, d: int,
               block_k: Optional[int]) -> Optional[int]:
    """THE K-tile policy, shared by the gate (can_flash) and the kernel
    wrapper (flash_block_update_hld) so they can never disagree.
    Returns the chosen tile width, or None when no valid choice exists
    (Lk does not tile, or the per-step working set overflows VMEM).
    block_k=None auto-selects: a single tile when it fits (biggest MXU
    matmuls, no scratch round-trips — measured 3-4x vs the tiled shape
    on the ring step), _AUTO_BLOCK_K otherwise; an explicit block_k is
    honored exactly (tests force multi-tile with it)."""
    if block_k is None:
        if _vmem_fits(bq, lk, d):
            return lk
        bk = min(_AUTO_BLOCK_K, lk)
    else:
        bk = min(block_k, lk)
    if lk % bk or not _vmem_fits(bq, bk, d):
        return None
    return bk


def auto_block_q(lq: int, lk: int, d: int,
                 candidates=(512, 256)) -> int:
    """Largest feasible Q tile for these shapes. Bigger tiles mean
    fewer grid programs, which matters when the (folded) head count is
    large: measured on the v5e chip at 128 folded heads x Lq 1024 x
    d 64, bq 512 runs the fwd+bwd attention 1.11x faster than bq 256
    (bq 1024 measured 1.14x standalone but its BACKWARD kernel
    overflows the 16 MB scoped-VMEM stack inside the full train step
    — _vmem_fits models the forward working set only — so 512 is the
    trainable cap; bq 128 is 0.79x) — per-program scheduling overhead
    is what makes big-batch attention scale superlinearly, the
    round-4 MFU-cliff finding. Falls back to min(256, lq)."""
    for bq in candidates:
        if bq <= lq and lq % bq == 0 and \
                _select_bk(bq, lk, d, None) is not None:
            return bq
    return min(256, lq)


def can_flash(lq: int, lk: int, d: int, block_q: int = 256,
              block_k: Optional[int] = None, groups: int = 1) -> bool:
    """True when the kernel accepts these shapes (Lq tiles by block_q
    and _select_bk finds a VMEM-feasible K tile). The auto-enable gates
    in ring_attention and ulysses_attention use this, so every shape
    the kernel accepts takes the fused path and every shape it would
    reject falls back to the unfused path instead of failing.

    ``groups`` is the GQA query-group count (n_heads / n_kv_heads):
    grouped calls fold the group dim into the Q axis (see
    flash_block_update_hld), so the effective Q length is groups*lq."""
    lq = groups * lq
    bq = min(block_q, lq)
    if lq % bq:
        return False
    bk = _select_bk(bq, lk, d, block_k)
    # Mosaic's block rule: the (1, 1, bq) stats block and the (1, bk)
    # position block put bq and bk in the lane dim, which must be a
    # 128-multiple or the whole axis (libtpu 0.0.34 refuses e.g.
    # block_q=64 at Lq=256). The interpreter has no such rule, so it
    # lives in the gate and not in _select_bk.
    return bk is not None and all(
        t == full or t % 128 == 0 for t, full in ((bq, lq), (bk, lk)))


def _kernel(q_ref, k_ref, v_ref, m_ref, l_ref, o_ref, qp_ref, kp_ref,
            m_out, l_out, o_out, m_s, l_s, o_s, *,
            causal: bool, scale: float, n_k: int):
    """Grid (H, Lq/BQ, Lk/BK); the K/V axis is innermost and sequential
    ('arbitrary'), accumulating through VMEM scratch (the canonical
    flash shape): scratch initializes from the carried (m, l, o) INPUT
    state at ik == 0 — this kernel is a block *update*, not a from-zero
    attention — and flushes to the outputs at ik == n_k-1."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_s[...] = m_ref[0, 0]
        l_s[...] = l_ref[0, 0]
        o_s[...] = o_ref[0]

    q = q_ref[0].astype(jnp.float32)                # (BQ, D)
    k = k_ref[0].astype(jnp.float32)                # (BK, D)
    v = v_ref[0].astype(jnp.float32)                # (BK, D)
    m = m_s[...]                                    # (BQ,)
    l = l_s[...]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        mask = kp_ref[0, :][None, :] <= qp_ref[0, :][:, None]
        s = jnp.where(mask, s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))          # (BQ,)
    p = jnp.exp(s - m_new[:, None])                 # (BQ, BK)
    if causal:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)                       # (BQ,)
    m_s[...] = m_new
    l_s[...] = l * corr + p.sum(axis=-1)
    o_s[...] = o_s[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        m_out[0, 0] = m_s[...]
        l_out[0, 0] = l_s[...]
        o_out[0] = o_s[...]


def _flash_fwd_call(q, k, v, m, l, o, q_pos, k_pos, *, causal: bool,
                    scale: float, bq: int, bk: int, interpret: bool,
                    alias: bool):
    """The raw forward pallas_call (resolved tile sizes). ``alias``
    donates the (m, l, o) carries into the outputs — the inference path
    keeps it; the custom_vjp forward disables it because the carries are
    saved as backward residuals and must stay live."""
    h, lq, d = q.shape
    lk = k.shape[1]
    n_k = lk // bk
    grid = (h, lq // bq, n_k)

    q_spec = pl.BlockSpec((1, bq, d), lambda hh, iq, ik: (hh, iq, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda hh, iq, ik: (hh, ik, 0))
    ml_spec = pl.BlockSpec((1, 1, bq), lambda hh, iq, ik: (hh, 0, iq))
    qp_spec = pl.BlockSpec((1, bq), lambda hh, iq, ik: (0, iq))
    kp_spec = pl.BlockSpec((1, bk), lambda hh, iq, ik: (0, ik))

    kwargs = {}
    if not interpret:
        # the kv axis accumulates through scratch: sequential
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    def struct(shape):
        return out_struct(shape, jnp.float32, q, k, v, m, l, o)

    scratch = [pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq,), jnp.float32),
               pltpu.VMEM((bq, d), jnp.float32)]

    return pl.pallas_call(
        functools.partial(_kernel, causal=causal, scale=float(scale),
                          n_k=n_k),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, ml_spec, ml_spec, q_spec,
                  qp_spec, kp_spec],
        out_specs=[ml_spec, ml_spec, q_spec],
        out_shape=[struct((h, 1, lq)), struct((h, 1, lq)),
                   struct((h, lq, d))],
        scratch_shapes=scratch,
        # accumulate in place: the (m, l, o) carries alias the outputs
        input_output_aliases={3: 0, 4: 1, 5: 2} if alias else {},
        interpret=interpret,
        name="flash_fwd",
        **kwargs,
    )(q, k, v, m, l, o, q_pos, k_pos)


def _ref_block_update_hld(q, k, v, m, l, o, q_pos, k_pos, causal, scale):
    """Pure-JAX head-leading restatement of the kernel math — the grad
    oracle (``bwd='xla'`` differentiates through this) and the parity
    target for the hand-written pallas backward. Must stay numerically
    identical to _kernel up to tiling/accumulation order."""
    s = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = (k_pos[0][None, :] <= q_pos[0][:, None])[None]
        s = jnp.where(mask, s, _NEG)
    m_in = m[:, 0, :]
    m_new = jnp.maximum(m_in, s.max(axis=-1))
    u = jnp.exp(s - m_new[..., None])
    p = jnp.where(mask, u, 0.0) if causal else u
    corr = jnp.exp(m_in - m_new)
    l_new = l[:, 0, :] * corr + p.sum(axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "hqk,hkd->hqd", p, v.astype(jnp.float32))
    return m_new[:, None, :], l_new[:, None, :], o_new


def _scores(q_ref, k_ref, qp_ref, kp_ref, causal, scale):
    """Recompute one (BQ, BK) masked score tile — bitwise identical to
    the forward's (same ops, same tile shapes), which the backward's
    argmax-equality routing relies on. Returns (s̃, mask)."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        mask = kp_ref[0, :][None, :] <= qp_ref[0, :][:, None]
        s = jnp.where(mask, s, _NEG)
    else:
        mask = None
    return s, mask


def _rowstats_kernel(q_ref, k_ref, m2_ref, qp_ref, kp_ref, cnt_out,
                     cnt_s, *, causal: bool, scale: float, n_k: int):
    """Per-row count of score positions tying the running max
    (s̃ == m'), accumulated over K tiles. Feeds the backward's exact
    reduce_max cotangent routing: jax divides the max's cotangent
    equally among tied argmax positions (measure-zero for real data,
    but structural for padded/degenerate rows), so the backward needs
    the tie count before it can distribute."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        cnt_s[...] = jnp.zeros_like(cnt_s)

    s, _ = _scores(q_ref, k_ref, qp_ref, kp_ref, causal, scale)
    m2 = m2_ref[0, 0]                               # (BQ,)
    cnt_s[...] += (s == m2[:, None]).astype(jnp.float32).sum(axis=-1)

    @pl.when(ik == n_k - 1)
    def _flush():
        cnt_out[0, 0] = cnt_s[...]


def _ds_tile(s, mask, v_ref, m2_ref, dl2_ref, do2_ref, route_ref,
             causal):
    """The score-cotangent tile ds̃ = u ⊙ du + routed-max term, shared
    by the dq and dk/dv kernels. u = exp(s̃ − m') is the pre-mask
    probability; du = mask(dl' + do'·vᵀ); the route term distributes
    the m' cotangent onto argmax-tied positions (killed by the mask,
    matching where(mask, s, NEG)'s zero cotangent at masked slots)."""
    v = v_ref[0].astype(jnp.float32)
    m2 = m2_ref[0, 0]                               # (BQ,)
    dl2 = dl2_ref[0, 0]
    do2 = do2_ref[0].astype(jnp.float32)            # (BQ, D)
    u = jnp.exp(s - m2[:, None])
    dp = dl2[:, None] + jax.lax.dot_general(
        do2, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        dp = jnp.where(mask, dp, 0.0)
    ds = u * dp
    routed = jnp.where(s == m2[:, None], route_ref[0, 0][:, None], 0.0)
    if causal:
        routed = jnp.where(mask, routed, 0.0)
    return ds + routed, u, do2


def _bwd_dq_kernel(q_ref, k_ref, v_ref, m2_ref, dl2_ref, do2_ref,
                   route_ref, qp_ref, kp_ref, dq_out, dq_s, *,
                   causal: bool, scale: float, n_k: int):
    """dq = scale * ds̃ @ k accumulated over K/V tiles. Grid
    (H, Lq/BQ, Lk/BK), K innermost and sequential."""
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        dq_s[...] = jnp.zeros_like(dq_s)

    s, mask = _scores(q_ref, k_ref, qp_ref, kp_ref, causal, scale)
    ds, _, _ = _ds_tile(s, mask, v_ref, m2_ref, dl2_ref, do2_ref,
                        route_ref, causal)
    k = k_ref[0].astype(jnp.float32)
    dq_s[...] += scale * jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == n_k - 1)
    def _flush():
        dq_out[0] = dq_s[...]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, m2_ref, dl2_ref, do2_ref,
                    route_ref, qp_ref, kp_ref, dk_out, dv_out, dk_s,
                    dv_s, *, causal: bool, scale: float, n_q: int):
    """dv = pᵀ @ do' and dk = scale * ds̃ᵀ @ q accumulated over Q
    tiles. Grid (H, Lk/BK, Lq/BQ), Q innermost and sequential — the
    mirror of the dq kernel with the accumulation axis swapped."""
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    s, mask = _scores(q_ref, k_ref, qp_ref, kp_ref, causal, scale)
    ds, u, do2 = _ds_tile(s, mask, v_ref, m2_ref, dl2_ref, do2_ref,
                          route_ref, causal)
    q = q_ref[0].astype(jnp.float32)
    p = jnp.where(mask, u, 0.0) if causal else u
    dv_s[...] += jax.lax.dot_general(
        p, do2, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # pᵀ @ do' (BK, D)
    dk_s[...] += scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)         # ds̃ᵀ @ q (BK, D)

    @pl.when(iq == n_q - 1)
    def _flush():
        dk_out[0] = dk_s[...]
        dv_out[0] = dv_s[...]


def _pallas_bwd(q, k, v, m, l, o, qp, kp, m2, l2, o2, dm2, dl2, do2, *,
                causal: bool, scale: float, bq: int, bk: int,
                interpret: bool, exact_max: bool):
    """Hand-written VJP of the block update (the flash backward).

    The per-row pieces are plain XLA (elementwise, fused for free):
      corr = exp(m − m'); dl = dl'·corr; do = do'·corr
      dcorr = dl'·l + Σ_d do'·o        (cotangent of corr)
      dm'_acc = dm' − dl'·l' − Σ_d do'·o'
    The last line is the closed form of the m' cotangent after
    accumulating all its uses (∂l'/∂m' = −l', ∂o'/∂m' = −o'). It then
    routes through m' = max(m, rowmax(s̃)) with jax's tie semantics
    (maximum splits 0.5/0.5 at equality; reduce_max divides equally
    among tied argmax slots): the m share goes to dm here, the rowmax
    share is pre-divided by the tie count (the _rowstats_kernel
    prepass) and distributed onto s̃ == m' positions inside the score
    kernels. Exactness is pinned against the autodiff oracle on raw
    cotangents in tests/test_flash_grad.py — not just through the
    normalized chain (where the m' cotangent is analytically zero).

    ``exact_max`` selects the routing fidelity. True: the full
    semantics above, at the cost of a third score pass (the
    _rowstats_kernel tie-count prepass). False ('pallas_fast'): skip
    the prepass, route dm'_acc wholly to dm when m won and drop the
    argmax share — exact whenever the consumer normalizes by l' and
    discards the final m (ring/ulysses/flash_attention all do), where
    dm'_acc is analytically zero and the dropped term is rounding
    noise. The attention ops default to the fast path; the exact path
    is pinned against the autodiff oracle on raw cotangents in
    tests/test_flash_grad.py.

    The quadratic pieces recompute the score tile in VMEM in two
    passes (three with the prepass): dq (accumulates over K tiles) and
    dk/dv (accumulates over Q tiles) — no (H, Lq, Lk) tensor ever
    touches HBM, matching the forward's memory story for training."""
    h, lq, d = q.shape
    lk = k.shape[1]
    corr = jnp.exp(m - m2)                            # (H, 1, Lq)
    corr_col = corr.transpose(0, 2, 1)                # (H, Lq, 1)
    dl_in = dl2 * corr
    do_in = do2 * corr_col
    dcorr = dl2 * l + (do2 * o).sum(-1)[:, None, :]
    dmacc = dm2 - dl2 * l2 - (do2 * o2).sum(-1)[:, None, :]

    n_q, n_k = lq // bq, lk // bk

    def specs(q_leads):
        """The five operand BlockSpecs for a (H, outer, inner) grid;
        ``q_leads`` says whether grid position 1 indexes Q tiles (the
        dq/rowstats grid) or K tiles (the dkv grid)."""
        def ix(iq, ik):
            return (iq, ik) if q_leads else (ik, iq)
        return dict(
            q=pl.BlockSpec((1, bq, d),
                           lambda hh, a, b: (hh, ix(a, b)[0], 0)),
            kv=pl.BlockSpec((1, bk, d),
                            lambda hh, a, b: (hh, ix(a, b)[1], 0)),
            ml=pl.BlockSpec((1, 1, bq),
                            lambda hh, a, b: (hh, 0, ix(a, b)[0])),
            qp=pl.BlockSpec((1, bq), lambda hh, a, b: (0, ix(a, b)[0])),
            kp=pl.BlockSpec((1, bk), lambda hh, a, b: (0, ix(a, b)[1])),
        )

    sp = specs(True)
    sp2 = specs(False)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    def struct(shape):
        return out_struct(shape, jnp.float32, q, k, v, m, l, o, dm2,
                          dl2, do2)

    def scr(shape):
        return pltpu.VMEM(shape, jnp.float32)

    if exact_max:
        cnt = pl.pallas_call(
            functools.partial(_rowstats_kernel, causal=causal,
                              scale=float(scale), n_k=n_k),
            grid=(h, n_q, n_k),
            in_specs=[sp["q"], sp["kv"], sp["ml"], sp["qp"], sp["kp"]],
            out_specs=[sp["ml"]],
            out_shape=[struct((h, 1, lq))],
            scratch_shapes=[scr((bq,))],
            interpret=interpret,
            name="flash_bwd_rowstats",
            **kwargs,
        )(q, k, m2, qp, kp)[0]

        # jax tie semantics: maximum(m, rowmax) splits 0.5/0.5 at
        # equality (m == m' AND rowmax == m', i.e. cnt > 0);
        # reduce_max divides its share equally among the cnt tied slots
        m_won = m == m2
        max_hit = cnt > 0
        w_m = jnp.where(m_won, jnp.where(max_hit, 0.5, 1.0), 0.0)
        w_s = jnp.where(max_hit, jnp.where(m_won, 0.5, 1.0), 0.0)
        dm_in = dcorr * corr + w_m * dmacc
        route = w_s * dmacc / jnp.maximum(cnt, 1.0)   # (H, 1, Lq)
    else:
        dm_in = dcorr * corr + jnp.where(m == m2, dmacc, 0.0)
        route = jnp.zeros_like(dmacc)

    operands = (q, k, v, m2, dl2, do2, route, qp, kp)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal,
                          scale=float(scale), n_k=n_k),
        grid=(h, n_q, n_k),
        in_specs=[sp["q"], sp["kv"], sp["kv"], sp["ml"], sp["ml"],
                  sp["q"], sp["ml"], sp["qp"], sp["kp"]],
        out_specs=[sp["q"]],
        out_shape=[struct((h, lq, d))],
        scratch_shapes=[scr((bq, d))],
        interpret=interpret,
        name="flash_bwd_dq",
        **kwargs,
    )(*operands)[0]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal,
                          scale=float(scale), n_q=n_q),
        grid=(h, n_k, n_q),
        in_specs=[sp2["q"], sp2["kv"], sp2["kv"], sp2["ml"], sp2["ml"],
                  sp2["q"], sp2["ml"], sp2["qp"], sp2["kp"]],
        out_specs=[sp2["kv"], sp2["kv"]],
        out_shape=[struct((h, lk, d)), struct((h, lk, d))],
        scratch_shapes=[scr((bk, d)), scr((bk, d))],
        interpret=interpret,
        name="flash_bwd_dkv",
        **kwargs,
    )(*operands)
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            dm_in, dl_in, do_in)


@functools.lru_cache(maxsize=None)
def _vjp_block_update(causal: bool, scale: float, bq: int, bk: int,
                      interpret: bool, bwd_impl: str):
    """custom_vjp wrapper factory, cached per static config so repeated
    calls (every ring step, every jit retrace) reuse one function
    identity — jax's trace cache then hits.

    This is what makes the flash path trainable at all: pallas_call has
    no JVP rule for aliased accumulators (jax.grad through the raw
    kernel raises "JVP with aliasing not supported" — the round-2
    VERDICT's confirmed crash), so the VJP is supplied whole: forward
    re-runs the kernel without donation and stashes inputs+outputs as
    residuals; backward is the hand-written pallas pair
    (``bwd_impl='pallas'`` with exact max-tie routing, default;
    ``'pallas_fast'`` skips the tie prepass — see _pallas_bwd) or
    autodiff through the pure-JAX restatement (``'xla'``, the
    oracle)."""
    kw = dict(causal=causal, scale=scale, bq=bq, bk=bk,
              interpret=interpret)

    @jax.custom_vjp
    def f(q, k, v, m, l, o, qp, kp):
        return _flash_fwd_call(q, k, v, m, l, o, qp, kp, alias=True,
                               **kw)

    def fwd(q, k, v, m, l, o, qp, kp):
        outs = _flash_fwd_call(q, k, v, m, l, o, qp, kp, alias=False,
                               **kw)
        return outs, (q, k, v, m, l, o, qp, kp) + tuple(outs)

    def bwd(res, cots):
        q, k, v, m, l, o, qp, kp, m2, l2, o2 = res
        dm2, dl2, do2 = cots
        if bwd_impl == "xla":
            _, vjp = jax.vjp(
                lambda q_, k_, v_, m_, l_, o_: _ref_block_update_hld(
                    q_, k_, v_, m_, l_, o_, qp, kp, causal, scale),
                q, k, v, m, l, o)
            dq, dk, dv, dm, dl, do = vjp((dm2, dl2, do2))
        else:
            dq, dk, dv, dm, dl, do = _pallas_bwd(
                q, k, v, m, l, o, qp, kp, m2, l2, o2, dm2, dl2, do2,
                exact_max=(bwd_impl == "pallas"), **kw)

        def z(x):  # integer positions: float0 symbolic-zero cotangent
            return np.zeros(x.shape, jax.dtypes.float0)

        return dq, dk, dv, dm, dl, do, z(qp), z(kp)

    f.defvjp(fwd, bwd)
    return f


def flash_block_update_hld(q, k, v, m, l, o, q_pos, k_pos, *,
                           causal: bool = False, scale: float = 1.0,
                           block_q: int = 256,
                           block_k: Optional[int] = None,
                           interpret: Optional[bool] = None,
                           bwd: str = "pallas"):
    """Head-leading-layout fused update: q (H, Lq, D) any float dtype;
    k, v (Hkv, Lk, D); m, l (H, 1, Lq) float32; o (H, Lq, D) float32;
    q_pos (1, Lq), k_pos (1, Lk) int32. Returns (m', l', o') in the
    same layouts. Grid = (H, Lq/block_q, Lk/block_k) — the K/V axis is
    tiled, so arbitrarily long K/V blocks stream through VMEM instead
    of having to fit in it.

    Grouped-query attention is native: Hkv may be smaller than H (H %
    Hkv == 0), in which case query head h attends K/V head h //
    (H/Hkv) — jnp.repeat semantics, but the compact K/V is what
    streams from HBM (the n_heads/n_kv_heads bandwidth reduction GQA
    exists for). Implementation: the group dim folds into the Q-length
    axis — q (H, Lq, D) reshapes to (Hkv, G*Lq, D) with positions
    tiled per group — so the kernel itself never changes; masking is
    per-row position-driven and rows are independent.

    Differentiable: jax.grad works through this (custom_vjp; the
    backward recomputes score tiles in VMEM — _pallas_bwd). ``bwd``
    selects the backward implementation: 'pallas' (fused kernels,
    exact max-tie routing, default), 'pallas_fast' (drops the tie
    prepass — exact when the consumer normalizes by l' and discards
    the final m, as all the attention ops do), or 'xla' (autodiff
    through the unfused restatement, the test oracle)."""
    h, lq, d = q.shape
    hk, lk = k.shape[0], k.shape[1]
    if hk != h:
        # GQA fold: group dim -> Q-length axis, then the plain kernel
        if h % hk:
            raise ValueError(
                f"query heads {h} must be a multiple of K/V heads {hk}")
        g = h // hk
        m2, l2, o2 = flash_block_update_hld(
            q.reshape(hk, g * lq, d), k, v,
            m.reshape(hk, 1, g * lq), l.reshape(hk, 1, g * lq),
            o.reshape(hk, g * lq, d),
            jnp.tile(q_pos, (1, g)), k_pos, causal=causal, scale=scale,
            block_q=block_q, block_k=block_k, interpret=interpret,
            bwd=bwd)
        return (m2.reshape(h, 1, lq), l2.reshape(h, 1, lq),
                o2.reshape(h, lq, d))
    if interpret is None:
        interpret = not _on_tpu()
    bq = min(block_q, lq)
    if lq % bq:
        raise ValueError(
            f"block_q (clamped to {bq}) must divide Lq {lq}")
    bk = _select_bk(bq, lk, d, block_k)
    if bk is None:
        raise ValueError(
            f"no valid K tile for Lk={lk}, block_q={bq}, d={d}, "
            f"block_k={block_k}: the tile must divide Lk and its "
            f"working set must fit VMEM (see _select_bk)")
    if bwd not in ("pallas", "pallas_fast", "xla"):
        raise ValueError(f"unknown bwd implementation {bwd!r}")
    f = _vjp_block_update(bool(causal), float(scale), bq, bk,
                          bool(interpret), bwd)
    return f(q, k, v, m, l, o, q_pos, k_pos)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, block_q: int = 256,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None, q_until=None):
    """Whole attention as ONE fused block update from the initial
    (m, l, o) state — the communication-free quadratic part of Ulysses
    sequence parallelism (each shard holds full sequences of its local
    heads), or plain single-device attention. q: (Lq, H, D); k, v:
    (Lk, Hkv, D) — Hkv < H is grouped-query attention (query head h
    attends K/V head h // (H/Hkv); the compact K/V is what streams
    from HBM); positions are the global 0..L ranges. The K/V axis is
    tiled by ``block_k``, so arbitrarily long sequences stream through
    VMEM (per-step working set ~ block_q x block_k). ``q_until`` (Lq,)
    int32: with ``causal``, the last key position each query attends,
    in place of its own — the kernel's mask is ``k_pos <= q_pos`` on
    the positions it is handed (a block-causal mask hands every query
    its block's last position)."""
    from rlo_tpu.parallel.mesh import vary_like

    lq, h, d = q.shape
    lk = k.shape[0]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    m0 = vary_like(jnp.full((h, 1, lq), _NEG, jnp.float32), q)
    l0 = vary_like(jnp.zeros((h, 1, lq), jnp.float32), q)
    o0 = vary_like(jnp.zeros((h, lq, d), jnp.float32), q)
    qp = jnp.arange(lq, dtype=jnp.int32) if q_until is None else q_until
    qp = vary_like(qp.astype(jnp.int32).reshape(1, lq), q)
    kp = vary_like(jnp.arange(lk, dtype=jnp.int32).reshape(1, lk), q)
    # pallas_fast: the l-normalization below makes the dropped max-
    # routing term exactly zero analytically (see _pallas_bwd)
    m, l, o = flash_block_update_hld(
        q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        m0, l0, o0, qp, kp, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, interpret=interpret, bwd="pallas_fast")
    lt = l.transpose(0, 2, 1)
    denom = jnp.where(lt > 0, lt, 1.0)
    return (o / denom).transpose(1, 0, 2).astype(q.dtype)


def flash_block_update(q, k, v, m, l, o, q_pos, k_pos, *,
                       causal: bool = False, scale: float = 1.0,
                       block_q: int = 256,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """One fused online-softmax update in ring_attention's caller
    layout: q, o (Lq, H, D); k, v (Lk, H, D); m, l (H, Lq); q_pos
    (Lq,), k_pos (Lk,). Returns (m', l', o'). Convenience wrapper —
    the ring loop itself uses flash_block_update_hld and transposes
    once outside the loop instead of per step."""
    lq, h, d = q.shape
    lk = k.shape[0]
    m2, l2, o2 = flash_block_update_hld(
        q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
        m.reshape(h, 1, lq), l.reshape(h, 1, lq),
        o.astype(jnp.float32).transpose(1, 0, 2),
        q_pos.astype(jnp.int32).reshape(1, lq),
        k_pos.astype(jnp.int32).reshape(1, lk),
        causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return (m2.reshape(h, lq), l2.reshape(h, lq), o2.transpose(1, 0, 2))
