"""Runner for serving cells whose model routes tokens to sparse experts.

It is ``kinds/serve.py``'s ``Runner`` (the closed loop, the units, the
counters, the kernel demand) with another ``check_parity``, because the
plain check cannot hold a top-k router to a float32 reference: where the
reference's 8th and 9th scores (or 4th and 5th groups) nearly tie, bfloat16
activations choose the other expert, both choices are the model, and one
flip moves a logit by far more than any rounding tolerance.

The rule, in which THE REFERENCE ALONE DECIDES what is a tie:

1. The program runs as the server runs it: each parity prompt alone through
   the bucket prefill, its row cache scattered into a pool of ``n_slots``
   slots (every slot holds a copy of one of the prompts), then decode steps
   over all slots: the timed shapes. It hands out, per expert layer, the
   scores its choice was made on and the experts it chose.
2. Its scores must sit within ``score_eps / 2`` of the reference's (the
   reference follows the program's routing, so both see the same stream).
3. Its choice must be exactly what the reference's own selection function
   gives on the PROGRAM's scores: groups, bias, top-k are checked to the
   bit, whatever the ties.
4. A token-layer is undecidable where the reference's margin (to the next
   expert, or half the margin to the next group) is under ``score_eps``;
   their share is reported and bounded. Where decidable, the program's
   expert set must equal the reference's. (At 256 experts the median margin
   is a sixth of bfloat16's largest error of a score, so nearly every
   token-layer is undecidable at an ``score_eps`` the program can be held
   to.) What binds instead: the share of ALL token-layers whose set
   differs from the reference's own choice is bounded
   (``routing_differs_share_max``), between what bfloat16 and what
   float8 activations give.
5. The reference then routes with the program's sets (it has just checked
   each), so every position after a tie can still be compared: logits at
   every position the program produced them, in bfloat16 ulps of the
   largest reference logit, at least ``compared_positions_min`` of them.

Every tolerance is in the configuration file with its reason. The check
also demands ``expert_ffn`` beside the base runner's two kernels, and
``finish`` refuses a run in which ``serve.moe.dropped`` moved.
"""

from __future__ import annotations

import importlib.util
import time
from pathlib import Path

import numpy as np

from lib import traffic as traffic_lib
from lib.compare import logit_gap_ulps

_spec = importlib.util.spec_from_file_location(
    "perf_kinds_serve", Path(__file__).with_name("serve.py"))
serve = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve)


class Runner(serve.Runner):

    def __init__(self, ctx):
        super().__init__(ctx)
        self.unit_log = []      # (seconds, admissions) of every unit

    def check_parity(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from rlo_tpu.models.generate import (decode_step, init_kv_cache,
                                             prefill)
        from rlo_tpu.utils import hlo
        ctx, mcfg, ref = self.ctx, self.mcfg, self.ctx.reference
        model = ctx.config["model"]
        tol = ctx.config["tolerance"]
        par = ctx.traffic["parity"]
        plens, bucket = list(par["prompt_lens"]), int(par["bucket"])
        steps = int(par["decode_steps"])
        n_slots = int(ctx.traffic["server"]["n_slots"])
        max_len = int(ctx.traffic["server"]["max_len"])
        R, total = len(plens), max(plens) + steps
        toks = np.stack([traffic_lib.token_ids(
            ctx.seed, 10_000 + r, total, mcfg.vocab) for r in range(R)])
        moe_layers = [i for i, L in enumerate(self.params["layers"])
                      if "moe" in L]
        k = mcfg.experts_per_tok

        # ---- 1. the program, at the server's shapes --------------------
        def prefill_row(p, t, n):
            info = []
            row = init_kv_cache(mcfg, 1, max_len)
            lg, row = prefill(p, t, row, mcfg, last_index=n - 1,
                              moe_info=info)
            return lg, row, [(i["ids"], i["choice"]) for i in info]

        def scatter(cache, row, slot):
            return jax.tree.map(lambda big, small: lax.dynamic_update_slice(
                big, small.astype(big.dtype),
                (slot,) + (0,) * (big.ndim - 1)), cache, row)

        def step_fn(p, t, m, c):
            info = []
            lg, c = decode_step(p, t, m, c, mcfg, moe_info=info)
            # the parity rows are slots 0..R-1
            return lg[:R], c, [(i["ids"][:R], i["choice"][:R])
                               for i in info]

        prefill_row = jax.jit(prefill_row)
        scatter = jax.jit(scatter, donate_argnums=(0,))
        step = jax.jit(step_fn, donate_argnums=(3,))

        forced = [np.full((R, total, k), -1, np.int32) for _ in moe_layers]
        scores = [np.zeros((R, total, mcfg.n_experts), np.float32)
                  for _ in moe_layers]
        got = {}                      # (row, position) -> logits (V,)
        cache = init_kv_cache(mcfg, n_slots, max_len)
        rows = []
        for r, plen in enumerate(plens):
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :plen] = toks[r, :plen]
            lg, row, info = prefill_row(self.params, jnp.asarray(prompt),
                                        jnp.asarray([plen], jnp.int32))
            rows.append(row)
            got[(r, plen - 1)] = np.asarray(lg[0])
            for j, (ids, choice) in enumerate(info):
                forced[j][r, :plen] = np.asarray(ids)[:plen]
                scores[j][r, :plen] = np.asarray(choice)[:plen]
        for slot in range(n_slots):
            cache = scatter(cache, rows[slot % R], jnp.int32(slot))
        del rows
        slot_row = np.arange(n_slots) % R
        base = np.asarray(plens)[slot_row]
        for s in range(steps):
            pos = base + s
            lg, cache, info = step(
                self.params, jnp.asarray(toks[slot_row, pos]),
                jnp.asarray(pos, jnp.int32), cache)
            for r, plen in enumerate(plens):
                got[(r, plen + s)] = np.asarray(lg[r])
                for j, (ids, choice) in enumerate(info):
                    forced[j][r, plen + s] = np.asarray(ids[r])
                    scores[j][r, plen + s] = np.asarray(choice[r])
        if ctx.peaks is not None:   # on the chip: the step's kernels
            i32 = jnp.int32
            slots = jnp.zeros((n_slots,), i32)
            found = hlo.mosaic_kernels(step.lower(
                self.params, slots, slots, cache).as_text())
            for name in ("flash_decode", "write_kv_row", "expert_ffn"):
                if not found.get(name):
                    ctx.problems.append(
                        f"kernel {name} is not in the decode step's "
                        f"program (found {found})")
            ctx.note(f"kernels in the decode step's program: {found}")
        del cache

        # ---- the reference, one layer at a time, on the program's sets --
        dense = jax.jit(lambda L, x: ref.layer(L, x, model)[0])
        sparse = jax.jit(lambda L, x, f: ref.layer(L, x, model, f))
        x = jax.jit(ref.embed)(self.params, jnp.asarray(toks))
        records = []
        for i, L in enumerate(self.params["layers"]):
            if i in moe_layers:
                x, rec = sparse(L, x, jnp.asarray(forced[len(records)]))
                records.append(jax.tree.map(np.asarray, rec))
            else:
                x = dense(L, x)
        want = np.asarray(jax.jit(lambda p, x: ref.head(p, x, model))(
            self.params, x))
        select = jax.jit(lambda c: ref.select(c, model)[0])

        # ---- 2.-5. the rule ---------------------------------------------
        eps = float(tol["score_eps"])
        valid = np.arange(total)[None, :] < (np.asarray(plens)
                                             + steps)[:, None]
        n_valid = int(valid.sum())
        worst_score, by_layer = 0.0, []
        wrong_rule = wrong_set = n_undecidable = n_differs = 0
        decidable_all = valid.copy()
        for j, rec in enumerate(records):
            by_layer.append(float(np.abs(
                scores[j] - rec["choice"])[valid].max()))
            worst_score = max(worst_score, by_layer[-1])
            mine = np.sort(forced[j], axis=-1)
            by_rule = np.asarray(select(jnp.asarray(scores[j])))
            wrong_rule += int((mine != by_rule).any(-1)[valid].sum())
            tie = rec["margin"] < eps
            n_undecidable += int(tie[valid].sum())
            decidable_all &= ~tie
            differs = (mine != rec["ids"]).any(-1)
            n_differs += int(differs[valid].sum())
            wrong_set += int((differs & ~tie)[valid].sum())
        share = n_undecidable / (n_valid * len(records))
        differs_share = n_differs / (n_valid * len(records))
        gaps = {pos: float(logit_gap_ulps(lg, want[pos]))
                for pos, lg in got.items()}
        gap = max(gaps.values())
        gap_decidable = max([g for pos, g in gaps.items()
                             if decidable_all[pos]], default=0.0)
        facts = {"score_gap": worst_score, "score_gap_by_layer": by_layer,
                 "score_eps": eps,
                 "undecidable_share": share,
                 "routing_differs_share": differs_share,
                 "token_layers": n_valid * len(records),
                 "compared_positions": len(gaps),
                 "compared_decidable_in_every_layer": int(sum(
                     bool(decidable_all[pos]) for pos in gaps)),
                 "logit_gap_ulps": gap,
                 "logit_gap_ulps_decidable": gap_decidable}
        ctx.note(f"reference check under the near-tie rule: {facts} "
                 f"(tolerances {tol['logit_ulps_bf16']} ulps, score gap "
                 f"{eps / 2}, undecidable share "
                 f"{tol['undecidable_share_max']}, differing share "
                 f"{tol['routing_differs_share_max']}, at least "
                 f"{tol['compared_positions_min']} positions)")
        ctx.facts["near_tie_check"] = facts
        if not worst_score <= eps / 2:
            ctx.problems.append(
                f"router scores are {worst_score} from the reference's, "
                f"over score_eps / 2 = {eps / 2}")
        if wrong_rule:
            ctx.problems.append(
                f"{wrong_rule} token-layers chose other experts than the "
                f"reference's selection gives on the program's own scores")
        if wrong_set:
            ctx.problems.append(
                f"{wrong_set} decidable token-layers chose other experts "
                f"than the reference")
        if share > float(tol["undecidable_share_max"]):
            ctx.problems.append(
                f"undecidable share {share} exceeds "
                f"{tol['undecidable_share_max']}")
        if differs_share > float(tol["routing_differs_share_max"]):
            ctx.problems.append(
                f"{differs_share} of the token-layers chose other experts "
                f"than the reference's own choice, over "
                f"{tol['routing_differs_share_max']}")
        if len(gaps) < int(tol["compared_positions_min"]):
            ctx.problems.append(
                f"only {len(gaps)} positions were compared, under "
                f"{tol['compared_positions_min']}")
        if not gap <= float(tol["logit_ulps_bf16"]):
            ctx.problems.append(
                f"logit gap {gap} exceeds {tol['logit_ulps_bf16']} bf16 "
                f"ulps ({gaps})")

    def unit(self, traced: bool = False) -> int:
        if traced and not hasattr(self, "traced_base"):
            self.traced_base = self.counters()  # before the first traced
        t0 = time.perf_counter()
        admitted = self.reg.counter("serve.admissions").value
        work = super().unit(traced)
        self.unit_log.append((
            time.perf_counter() - t0,
            self.reg.counter("serve.admissions").value - admitted))
        return work

    def finish(self):
        times = sorted(t for t, _ in self.unit_log)
        if times:   # units that took half again the median: where, why
            median = times[len(times) // 2]
            slow = [(i, round(1e3 * t, 1), n) for i, (t, n) in enumerate(
                self.unit_log) if t > 1.5 * median]
            self.ctx.note(f"units over 1.5 x the median {1e3 * median:.1f}"
                          f" ms, as (index from the first warm unit, ms, "
                          f"admissions): {slow}")
        if hasattr(self, "traced_base"):
            now = self.counters()
            self.ctx.facts["traced_counters"] = {
                k: now[k] - self.traced_base.get(k, 0) for k in now}
        dropped = self.reg.counter("serve.moe.dropped").value
        tokens = self.reg.counter("serve.moe.tokens").value
        if dropped or not tokens:
            self.ctx.problems.append(
                f"serve.moe.dropped {dropped}, serve.moe.tokens {tokens}: "
                f"an expert layer dropped assignments or reported none")
        return super().finish()
