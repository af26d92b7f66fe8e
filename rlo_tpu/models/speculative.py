"""Speculative decoding: a cheap draft proposes, the target verifies.

Serving-side latency lever (net-new; the reference has no model stack
at all, SURVEY.md §5): decode is HBM-bound — every step reads the full
weights for ONE token per row — so a small draft model proposes
``gamma`` tokens autoregressively and the big target model judges all
of them in ONE forward (models.generate.block_decode), reading its
weights once per round instead of once per token. Greedy speculative
decoding is LOSSLESS: the emitted tokens each round are the target's
own argmax predictions ``t_pred[0..j]`` (a draft token is accepted
exactly when it equals the target's prediction, so the accepted prefix
and the bonus token are all target predictions), hence the output
equals plain greedy decode token for token — the parity oracle
tests/test_speculative.py pins.

Numerics caveat on that claim: "the target's prediction" must mean
the SAME floating-point logits plain decode would compute, or a
near-tie argmax can flip between the two paths. On TPU both paths now
route through one kernel family — plain decode_step uses the Pallas
flash-decode kernel at T=1 and the verify block_decode uses the same
kernel at T=gamma (pallas.decode.flash_block_decode), with identical
tile shapes, accumulation order, and dot dtypes per query row — and on
CPU both take the einsum path, so the parity holds by shared numerics
on both backends. One carve-out: a gamma-wide block too large for
VMEM at the T=1 tiling (pallas.decode._block_fits_vmem; extreme
nkv*gamma*head_dim, or a latent cache of 16384 positions or more,
whose tiles are 2048 lanes wide: from gamma 4 at 128 heads, 7 at 64)
falls back to einsum with a RuntimeWarning and the parity degrades to
near-tie class there. On the chip, chip_smoke.py pins the T=1 and the
T=128 kernel's LOGITS against the reference forward; speculative
token-for-token parity itself has not been re-run on the chip since
the 2026-08 records. The CPU oracles in tests/test_speculative.py
hold always.

Cache bookkeeping rides the same masking trick as ragged decode:
rejected drafts leave garbage cache entries BEYOND each row's valid
position, which are never attended (every attend masks at the row's
own position) and are overwritten by later rounds. Per-row acceptance
lengths make the whole loop ragged; positions, cache writes, and
output writes are all per-row. One `lax.while_loop` over rounds (the
trip count is data-dependent — rows finish at different speeds), each
round = gamma draft decode_steps + 1 target block_decode.

Speedup economics: a round emits j+1 in [1, gamma] tokens for the cost
of gamma draft steps + one gamma-wide target forward. With draft cost
c_d (fraction of a target step) and acceptance-driven yield E[j+1],
speedup = E[j+1] / (gamma * c_d + c_verify). benchmarks/spec_bench.py
measures the two cost terms on the chip and the realized yield.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from rlo_tpu.models.generate import (block_decode, decode_step,
                                     init_kv_cache, prefill)
from rlo_tpu.models.transformer import TransformerConfig


def speculative_generate(params: dict, draft_params: dict, prompt,
                         cfg: TransformerConfig,
                         draft_cfg: TransformerConfig, *,
                         max_new: int, gamma: int = 4,
                         max_len: Optional[int] = None,
                         temperature: float = 0.0,
                         rng=None, return_rounds: bool = False):
    """Speculative continuation of ``prompt`` (b, plen) int32: returns
    (b, max_new) int32. ``gamma`` = draft tokens proposed per round.
    Both configs must share the vocabulary; the draft is typically a
    much smaller model (fewer layers / narrower).

    temperature == 0 (default): greedy — IDENTICAL to
    ``generate(params, prompt, cfg, max_new=max_new)`` by the
    lossless-acceptance construction; the draft only changes how fast
    the tokens arrive.

    temperature > 0 (needs ``rng``): LOSSLESS speculative SAMPLING —
    the standard rejection scheme: the draft SAMPLES x_i ~ p_d, the
    target accepts x_i with probability min(1, p_t(x_i)/p_d(x_i)), and
    the first rejected position resamples from the residual
    norm(max(p_t - p_d, 0)). Each emitted token is distributed exactly
    as plain temperature sampling from the target — in DISTRIBUTION,
    not trajectory (the rejection scheme spends randomness differently
    than `generate`'s per-step categorical, so token-for-token equality
    is not defined; tests/test_speculative.py pins the distributional
    equality statistically and the all-accept behavior exactly).
    A round emits n_acc + 1 tokens (the accepted prefix + the
    adjustment sample), capped at gamma when every draft is accepted —
    the same [1, gamma] per-round yield as the greedy path.

    ``return_rounds``: also return the number of verify rounds taken
    (b-invariant scalar) — rounds * (gamma draft steps + 1 verify) is
    the realized cost, and max_new / rounds the realized per-round
    yield, which benchmarks/spec_bench.py turns into the measured
    acceptance-driven speedup.
    """
    if cfg.vocab != draft_cfg.vocab:
        raise ValueError(
            f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}")
    if gamma < 1:
        raise ValueError("gamma >= 1 required")
    if temperature > 0 and rng is None:
        raise ValueError("sampling (temperature > 0) needs rng")
    b, plen = prompt.shape
    # + gamma slack: the last round's block writes reach at most
    # position plen + max_new - 1 + gamma (garbage tail, never read)
    max_len = max_len or (plen + max_new + gamma)
    if plen + max_new + gamma > max_len:
        raise ValueError(f"max_len {max_len} < plen {plen} + max_new "
                         f"{max_new} + gamma {gamma}")

    # hoist the f32 -> act-dtype weight cast OUT of the round loop:
    # XLA's LICM does this for `generate`'s scan but NOT for the
    # while_loop here, so every round re-converted the full f32
    # weights (~1.1 ms/round at 134M params — measured as a 0.95x
    # "speedup" until hoisted; same values, same numerics, the cast
    # is exactly the one apply_layer would do)
    def _cast(tree, dt):
        # MoE router weights ('wr') deliberately compute in f32
        # (moe.moe_ffn) — downcasting them would let a bf16-rounded
        # top-1 flip diverge speculative output from plain generate
        def f(path, p):
            if p.dtype != jnp.float32:
                return p
            if any(getattr(k, "key", None) == "wr" for k in path):
                return p
            return p.astype(dt)
        return jax.tree_util.tree_map_with_path(f, tree)

    params = _cast(params, cfg.act_dtype)
    draft_params = _cast(draft_params, draft_cfg.act_dtype)

    t_cache = init_kv_cache(cfg, b, max_len)
    d_cache = init_kv_cache(draft_cfg, b, max_len)
    t_logits, t_cache = prefill(params, prompt, t_cache, cfg)
    _, d_cache = prefill(draft_params, prompt, d_cache, draft_cfg)

    sampling = temperature > 0
    if sampling:
        rng, k0 = jax.random.split(rng)
        first = jax.random.categorical(
            k0, t_logits / temperature, axis=-1).astype(jnp.int32)
        key0 = rng
    else:
        first = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)  # (b,)
        key0 = jnp.zeros((2,), jnp.uint32)  # unused carry slot

    # first token: the target's own prefill prediction (or sample).
    # Invariant from here on (per row): out[:n_out] emitted; both
    # caches are validly filled through position pos-1 and last_tok
    # has NOT been processed by either model yet; its position is pos.
    out = jnp.zeros((b, max_new), jnp.int32)
    out = out.at[:, 0].set(first)
    n_out = jnp.ones((b,), jnp.int32)
    pos = jnp.full((b,), plen, jnp.int32)
    last_tok = first
    rows = jnp.arange(b)

    def round_body(state):
        out, n_out, pos, last_tok, t_cache, d_cache, rounds, key = state
        done = n_out >= max_new
        # per-LANE liveness: under vmap the while_loop iterates until
        # every lane finishes and the body runs for finished lanes
        # too — an unconditional rounds+1 would report the batch MAX
        # instead of each lane's own round count (the acceptance
        # metric spec_bench records)
        live = jnp.any(n_out < max_new).astype(jnp.int32)
        if sampling:
            key, kd, ka, kr = jax.random.split(key, 4)
            dkeys = jax.random.split(kd, gamma)

        # --- draft rollout: gamma ragged decode steps as ONE lax.scan
        # (unrolled python steps measured ~0.13 ms EACH of pure
        # overhead inside the while body on the v5e chip; the same
        # step inside a scan — plain generate's structure — runs at
        # ~4 us for a 1-layer draft) ---------------------------------
        def droll(carry, xs):
            cur, dc = carry
            i, key = xs
            logits, dc = decode_step(draft_params, cur, pos + i, dc,
                                     draft_cfg)
            if sampling:
                probs = jax.nn.softmax(
                    logits.astype(jnp.float32) / temperature, axis=-1)
                nxt = jax.random.categorical(
                    key, logits / temperature,
                    axis=-1).astype(jnp.int32)
            else:
                probs = jnp.zeros((b, 0), jnp.float32)  # unused
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, dc), (nxt, probs)

        scan_keys = (dkeys if sampling
                     else jnp.zeros((gamma, 2), jnp.uint32))
        (_, dc), (d_seq, d_prob_seq) = lax.scan(
            droll, (last_tok, d_cache),
            (jnp.arange(gamma, dtype=jnp.int32), scan_keys))
        d_mat = jnp.transpose(d_seq)                       # (b, gamma)

        # --- verify: ONE target forward over [last_tok, d_1..d_{g-1}]
        block = jnp.concatenate([last_tok[:, None],
                                 d_mat[:, :gamma - 1]], axis=1)
        v_logits, tc = block_decode(params, block, pos, t_cache, cfg)

        if not sampling:
            t_pred = jnp.argmax(v_logits, axis=-1).astype(jnp.int32)
            # --- lossless greedy acceptance -------------------------
            acc = (d_mat == t_pred)                        # (b, gamma)
            n_acc = jnp.cumprod(acc, axis=1).sum(axis=1)   # in [0, g]
            j = jnp.minimum(n_acc, gamma - 1)              # (b,)
            # emitted tokens this round are t_pred[:, :j+1] — the
            # target's own predictions (accepted drafts EQUAL them;
            # the bonus IS one): the whole losslessness argument
            n_emit_raw = j + 1
            emit_at = lambda i: t_pred[:, i]  # noqa: E731
            emit_ok = lambda i: i <= j        # noqa: E731
            new_last_live = t_pred[rows, j]
        else:
            # --- lossless rejection sampling ------------------------
            # accept x_i with prob min(1, p_t(x_i) / p_d(x_i)); first
            # rejection resamples from norm(max(p_t - p_d, 0)) — each
            # emitted token is exactly target-temperature-distributed
            p_t = jax.nn.softmax(
                v_logits.astype(jnp.float32) / temperature, axis=-1)
            p_d = jnp.moveaxis(d_prob_seq, 0, 1)       # (b, g, V)
            idx = d_mat[..., None]
            pt_x = jnp.take_along_axis(p_t, idx, -1)[..., 0]  # (b, g)
            pd_x = jnp.take_along_axis(p_d, idx, -1)[..., 0]
            u = jax.random.uniform(ka, (b, gamma))
            accept = u * pd_x < pt_x       # u < pt/pd, division-free
            n_acc = jnp.cumprod(accept, axis=1).sum(axis=1)  # [0, g]
            j = jnp.minimum(n_acc, gamma - 1)
            # residual distribution at the first rejected position
            p_t_j = jnp.take_along_axis(p_t, j[:, None, None],
                                        1)[:, 0]          # (b, V)
            p_d_j = jnp.take_along_axis(p_d, j[:, None, None],
                                        1)[:, 0]
            resid = jnp.maximum(p_t_j - p_d_j, 0.0)
            s = resid.sum(-1, keepdims=True)
            res_logits = jnp.where(resid > 0,
                                   jnp.log(jnp.maximum(resid, 1e-38)),
                                   -1e30)
            # p_t == p_d exactly (s == 0): the residual is empty and
            # any sample from p_t is already correct — fall back
            fb_logits = jnp.log(jnp.maximum(p_t_j, 1e-38))
            y = jax.random.categorical(
                kr, jnp.where(s > 0, res_logits, fb_logits),
                axis=-1).astype(jnp.int32)                # (b,)
            # all gamma accepted -> emit them all (no bonus: the
            # target never processed x_{gamma-1}, same as greedy);
            # else the accepted prefix + the adjustment sample
            n_emit_raw = jnp.where(n_acc == gamma, gamma, n_acc + 1)
            emit_at = lambda i: jnp.where(  # noqa: E731
                i < n_acc, d_mat[:, i], y)
            emit_ok = lambda i: i < n_emit_raw  # noqa: E731
            new_last_live = jnp.where(n_acc == gamma,
                                      d_mat[:, gamma - 1], y)

        n_emit = jnp.where(done, 0, n_emit_raw)
        for i in range(gamma):
            idxw = jnp.minimum(n_out + i, max_new - 1)
            ok = emit_ok(i) & (n_out + i < max_new) & ~done
            old = out[rows, idxw]
            out = out.at[rows, idxw].set(
                jnp.where(ok, emit_at(i), old))
        new_last = jnp.where(done, last_tok, new_last_live)
        n_out = jnp.minimum(n_out + n_emit, max_new)
        pos = jnp.where(done, pos, pos + n_emit)
        return (out, n_out, pos, new_last, tc, dc, rounds + live, key)

    def cond(state):
        _, n_out, _, _, _, _, rounds, _ = state
        # every round emits >= 1 token per unfinished row, so max_new
        # rounds always suffice — the bound makes divergence impossible
        return jnp.any(n_out < max_new) & (rounds < max_new)

    state = (out, n_out, pos, last_tok, t_cache, d_cache,
             jnp.int32(0), key0)
    final = lax.while_loop(cond, round_body, state)
    if return_rounds:
        return final[0], final[6]
    return final[0]
