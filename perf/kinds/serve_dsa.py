"""Runner for serving cells whose model selects the tokens it attends
(learned sparse attention: an indexer scores every cached token, the
``index_topk`` best are kept, latent attention runs over those alone) and
routes tokens to sparse experts.

It is ``kinds/serve_moe.py``'s ``Runner`` with two changes.

THE TRAFFIC is a fixed set of sessions, one a slot, replayed on every seed:
the mix's ``requests`` multiset is submitted once (the seed orders it and
draws the tokens), every prompt is admitted through the server's own
admission during set-up (the first warm unit), each owes more output than
the window consumes, so that no request finishes and none is admitted
inside the window. ``finish`` holds every slot to that: its owner, its
position and what it still owes.

THE CHECK. A selection is a top-k over nearly tied scores, as routing is:
at 13k keys the 2048th and 2049th scores lie closer than bfloat16's error
of a score, both sets are the model, and one swapped token moves a logit by
more than a rounding tolerance. The rule is ``serve_moe``'s, and THE
REFERENCE ALONE DECIDES what is a tie:

1. The program runs as the server runs it: each parity prompt alone through
   the bucket prefill and the long prompts' extend chunks (the bucket and
   the chunk width by the server's own rule, ``serve.extend_widths``), its
   row scattered into a pool of ``n_slots`` slots at ``max_len``, then
   decode steps over all slots: the timed shapes. The longest prompt
   reaches into the last cache tile the window's longest context reads, so
   every tile the window reads is compared. It hands out, per layer, the set every position
   attended and, at the checked positions (some prompt positions, and the
   decode steps of EVERY slot: each decodes its copy of a parity row at its
   own row of the batch), its index scores; per expert layer its choice
   scores and experts, as in ``serve_moe``.
2. Its index scores must sit within ``index_eps / 2`` of the reference's
   (the reference follows the program's sets and routing, so both see the
   same stream).
3. Its set must be EXACTLY the top ``index_topk`` of its own scores, ties to
   the lowest position.
4. Every selected position's reference score must be at least the
   reference's ``index_topk``-th best less ``index_eps``, every unselected
   one at most that plus ``index_eps``.
5. The share of selected positions that the reference's own set lacks is
   bounded (``selection_differs_share_max``), between what bfloat16 and
   what float8 index keys give.
6. The reference attends the program's sets (and routes with its experts)
   and logits are compared at every checked position, in bfloat16 ulps of
   the largest reference logit.
7. Rules 4 and 5 hold at EVERY position of the parity sequences, not the
   checked ones alone, and every set has ``min(context, index_topk)``
   positions: the reference judges each set as it attends it (its
   ``size``, ``differs``, ``outside``), so a wrong selection at a position
   whose scores were never handed out is not followed in silence.

Routing keeps ``serve_moe``'s rule (score gap, the exact choice on the
program's own scores, the differing share), over every position of the
parity sequences: 15 000 tokens x 4 expert layers, thirty times
``serve_moe``'s, enough to meet two GROUP scores that are exactly equal
at the boundary (one run in fifteen, PR 31). This configuration's
reference keeps exactly ``topk_group`` groups there, the lower index, as
DeepSeek's code and the program do, so the rule holds to the bit; a
token-layer that breaks it is printed with the reference's margin. Every tolerance is in the
configuration file with its reason. The run is not ``correct`` without the
Mosaic kernel ``index_score`` in the round's program.
"""

from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np

from lib import traffic as traffic_lib
from lib.compare import logit_gap_ulps

_spec = importlib.util.spec_from_file_location(
    "perf_kinds_serve_moe", Path(__file__).with_name("serve_moe.py"))
serve_moe = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_moe)


def checked_positions(plen: int, steps: int, topk: int, n_prompt: int):
    """The positions of one parity sequence whose logits, scores and sets
    are compared: ``n_prompt`` prompt positions past ``topk`` (evenly
    spread), the last prompt position, and the decode steps."""
    lo = min(topk + 1, plen - 2)
    spread = np.unique(np.linspace(lo, plen - 2, n_prompt).astype(int))
    return [int(p) for p in spread] + [plen - 1 + s for s in
                                       range(steps + 1)]


def stable_topk(scores: np.ndarray, k: int) -> np.ndarray:
    """Bool mask of the k largest of ``scores`` (n,), ties to the lowest
    position; -inf entries never."""
    order = np.argsort(-scores, kind="stable")[:k]
    out = np.zeros(scores.shape, bool)
    out[order] = True
    return out & np.isfinite(scores)


def judge_selection(prog: dict, ref: dict, topk: int, eps: float) -> dict:
    """Rules 2-5 over every (layer, slot, checked position). ``prog`` maps
    (layer, slot, position) -> {"scores" (n,), "select" (n,) bool},
    ``ref`` the same keys to the reference's scores and OWN choice there."""
    gap = 0.0
    wrong_rule = outside = n_sel = n_differ = 0
    margins = []
    for key, p in prog.items():
        r = ref[key]
        live = np.isfinite(r["scores"])
        gap = max(gap, float(np.abs(p["scores"][live]
                                    - r["scores"][live]).max()))
        if (p["select"] != stable_topk(p["scores"], topk)).any():
            wrong_rule += 1
        best = np.sort(r["scores"][live])[::-1]
        if best.size > topk:
            kth = best[topk - 1]
            margins.append(float(kth - best[topk]))
            sel, rest = p["select"] & live, ~p["select"] & live
            outside += int((r["scores"][sel] < kth - eps).sum()
                           + (r["scores"][rest] > kth + eps).sum())
        n_sel += int(p["select"].sum())
        n_differ += int((p["select"] & ~r["select"]).sum())
    return {"index_score_gap": gap, "index_eps": eps,
            "sets_not_topk_of_own_scores": wrong_rule,
            "positions_outside_the_band": outside,
            "selection_differs_share": n_differ / max(n_sel, 1),
            "sets_checked": len(prog),
            "reference_margin_median": float(np.median(margins))
            if margins else None}


class Runner(serve_moe.Runner):

    def __init__(self, ctx):
        super().__init__(ctx)
        self.session_of = {}        # rid -> (prompt length, output owed)

    # ---- the traffic: a fixed set, submitted once -----------------------
    def top_up(self) -> None:
        if self.session_of:
            return
        pairs = self.stream
        for idx in range(self.n_slots):
            plen, out = next(pairs)
            rid = self.srv.submit(traffic_lib.token_ids(
                self.ctx.seed, idx, plen, self.mcfg.vocab), out)
            self.sent[rid] = out
            self.session_of[rid] = (plen, out)
        self.n_sent = self.n_slots

    def setup(self) -> None:
        super().setup()
        import jax.numpy as jnp
        from rlo_tpu.utils import hlo
        ctx, srv = self.ctx, self.srv
        if ctx.peaks is not None:
            slots = jnp.zeros((self.n_slots,), jnp.int32)
            found = hlo.mosaic_kernels(srv._jits["_round"][0].lower(
                self.params, srv.cache, slots, slots,
                kk=srv.round_len).as_text())
            if not found.get("index_score"):
                ctx.problems.append(
                    f"kernel index_score is not in the round's program "
                    f"(found {found})")
            ctx.part("kernel_check")
        ctx.facts.update(index_topk=self.mcfg.index_topk,
                         index_n_heads=self.mcfg.index_n_heads,
                         index_head_dim=self.mcfg.index_head_dim)
        self.window_base = dict(self.base)

    def finish(self):
        """No request finished and none was admitted inside the window (or
        the traced units); every session still holds its slot, where the
        rounds left it."""
        ctx, srv = self.ctx, self.srv
        now = self.counters()
        moved = {k: now.get(k, 0) - self.window_base.get(k, 0) for k in (
            "serve.admissions", "serve.requests_completed")}
        if any(moved.values()) or srv.queue_depth():
            ctx.problems.append(
                f"the fixed set moved inside the window: {moved}, "
                f"{srv.queue_depth()} queued")
        steps = now.get("serve.steps", 0)
        owners = srv.slot_ownership()
        self.checked, self.failed = len(self.session_of), 0
        for rid, (plen, out) in self.session_of.items():
            slot = owners.index(rid) if rid in owners else None
            if slot is None or int(srv.pos[slot]) != plen + steps or int(
                    srv.budget[slot]) != out - 1 - steps:
                self.failed += 1
        ctx.note(f"fixed set: {self.checked} sessions, {steps} steps each "
                 f"since admission, contexts now "
                 f"{int(srv.pos.min())}-{int(srv.pos.max())}")
        return super().finish()

    # ---- the check ------------------------------------------------------
    def check_parity(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax import lax
        from rlo_tpu.models.generate import (block_decode, decode_step,
                                             init_kv_cache, prefill)
        from rlo_tpu.models.serve import (PROMPT_BUCKETS, extend_widths,
                                          prompt_buckets_for)
        from rlo_tpu.utils import hlo
        ctx, mcfg, ref = self.ctx, self.mcfg, self.ctx.reference
        model = ctx.config["model"]
        tol = ctx.config["tolerance"]
        par, server = ctx.traffic["parity"], ctx.traffic["server"]
        plens, steps = list(par["prompt_lens"]), int(par["decode_steps"])
        n_slots, max_len = int(server["n_slots"]), int(server["max_len"])
        # admission's shapes by the server's own rule
        bucket = prompt_buckets_for(mcfg, max_len, tuple(server.get(
            "prompt_buckets", PROMPT_BUCKETS)))[-1]
        chunk_w, long_w = extend_widths((bucket,))
        widths = [long_w if p - bucket > long_w else chunk_w for p in plens]
        if min(plens) <= bucket:
            ctx.problems.append(f"a parity prompt fits the bucket {bucket}")
        topk, k = mcfg.index_topk, mcfg.experts_per_tok
        index_eps = float(tol["index_eps"])
        R, total = len(plens), max(plens) + steps
        lens = [p + steps for p in plens]       # a row's own sequence
        n_layers = len(self.params["layers"])
        toks = np.stack([traffic_lib.token_ids(
            ctx.seed, 10_000 + r, total, mcfg.vocab) for r in range(R)])
        moe_layers = [i for i, L in enumerate(self.params["layers"])
                      if "moe" in L]
        at = [checked_positions(p, steps, topk, int(par["prompt_checks"]))
              for p in plens]
        P = max(len(a) for a in at)

        # ---- 1. the program, at the server's shapes --------------------
        def routing(info):
            return [(i["ids"], i["choice"]) for i in info]

        def prefill_row(p, t, n):
            info = []
            row = init_kv_cache(mcfg, 1, max_len)
            lg, row = prefill(p, t, row, mcfg, last_index=n - 1,
                              moe_info=info)
            return lg, row, routing(info)

        def extend(p, row, t, pos0, local):
            info, dsa = [], []
            lg, row = block_decode(p, t, pos0[None], row, mcfg,
                                   moe_info=info, dsa_info=dsa)
            return (lg[0, local], row, routing(info),
                    [jnp.packbits(d["select"][0, :, :total], axis=-1)
                     for d in dsa],
                    [d["scores"][0, local, :total] for d in dsa])

        def scatter(cache, row, slot):
            return jax.tree.map(lambda big, small: lax.dynamic_update_slice(
                big, small.astype(big.dtype),
                (slot,) + (0,) * (big.ndim - 1)), cache, row)

        def step_fn(p, t, m, c):    # every slot's logits, sets and scores
            info, dsa = [], []
            lg, c = decode_step(p, t, m, c, mcfg, moe_info=info,
                                dsa_info=dsa)
            return (lg, c, routing(info),
                    [d["select"][:, 0, :total] for d in dsa],
                    [d["scores"][:, 0, :total] for d in dsa])

        prefill_row = jax.jit(prefill_row)
        extend = jax.jit(extend, donate_argnums=(1,))
        scatter = jax.jit(scatter, donate_argnums=(0,))
        step = jax.jit(step_fn, donate_argnums=(3,))

        forced = [np.full((R, total, k), -1, np.int32) for _ in moe_layers]
        scores = [np.zeros((R, total, mcfg.n_experts), np.float32)
                  for _ in moe_layers]
        # chosen[r][i]: the set each position of row r attended in layer
        # i, one packed row of the row's own length in bits a position
        chosen = [[np.zeros((n, -(-n // 8)), np.uint8)
                   for _ in range(n_layers)] for n in lens]
        prog = {}       # (layer, slot, position) -> scores, select
        got = {}        # (slot, position) -> logits (V,)

        def keep_routing(info, r, where):
            for j, (ids, choice) in enumerate(info):
                forced[j][r, where] = np.asarray(ids).reshape(
                    -1, k)[:forced[j][r, where].shape[0]]
                scores[j][r, where] = np.asarray(choice).reshape(
                    -1, mcfg.n_experts)[:scores[j][r, where].shape[0]]

        cache = init_kv_cache(mcfg, n_slots, max_len)
        rows = []
        for r, plen in enumerate(plens):
            head, width, n_r = bucket, widths[r], lens[r]
            prompt = toks[r:r + 1, :head]
            lg, row, info = prefill_row(self.params, jnp.asarray(prompt),
                                        jnp.asarray([head], jnp.int32))
            keep_routing(info, r, slice(0, head))
            before = np.zeros((head, n_r), bool)    # a prompt block:
            before[:, :head] = np.tril(np.ones((head, head), bool))
            for layer in chosen[r]:                 # everything before
                layer[:head] = np.packbits(before, axis=-1)
            if head - 1 in at[r]:
                got[(r, head - 1)] = np.asarray(lg[0])
            off = head
            while off < plen:
                n = min(width, plen - off)
                piece = np.zeros((1, width), np.int32)
                piece[0, :n] = toks[r, off:off + n]
                mine = [p - off for p in at[r] if off <= p < off + n]
                local = np.zeros((P,), np.int32)
                local[:len(mine)] = mine
                lg, row, info, sel, sc = extend(
                    self.params, row, jnp.asarray(piece), jnp.int32(off),
                    jnp.asarray(local))
                keep_routing(info, r, slice(off, off + n))
                for i in range(n_layers):
                    bits = np.asarray(sel[i])
                    chosen[r][i][off:off + n] = bits[:n, :-(-n_r // 8)]
                    for j, p in enumerate(mine):
                        prog[(i, r, p + off)] = {
                            "scores": np.asarray(sc[i][j])[:n_r],
                            "select": np.unpackbits(
                                bits[p], count=n_r).astype(bool)}
                for j, p in enumerate(mine):
                    got[(r, p + off)] = np.asarray(lg[j])
                off += n
            rows.append(row)
        for slot in range(n_slots):
            cache = scatter(cache, rows[slot % R], jnp.int32(slot))
        del rows, row
        # every slot decodes its copy of a parity row: the same sequence
        # at another row of the batch, held to the same reference
        slot_row = np.arange(n_slots) % R
        base = np.asarray(plens)[slot_row]
        twins_differ = 0
        for s in range(steps):
            pos = base + s
            lg, cache, info, sel, sc = step(
                self.params, jnp.asarray(toks[slot_row, pos]),
                jnp.asarray(pos, jnp.int32), cache)
            lg = np.asarray(lg)
            sel = [np.asarray(a) for a in sel]
            sc = [np.asarray(a) for a in sc]
            for r, plen in enumerate(plens):    # the reference follows
                keep_routing([(i[r], c[r]) for i, c in info], r,  # slot r
                             slice(plen + s, plen + s + 1))
                for i in range(n_layers):
                    chosen[r][i][plen + s] = np.packbits(
                        sel[i][r, :lens[r]])
            for slot, r in enumerate(slot_row):
                p, n_r = int(pos[slot]), lens[r]
                got[(slot, p)] = lg[slot]
                twins_differ += int((lg[slot] != lg[r]).any())
                for i in range(n_layers):
                    prog[(i, slot, p)] = {"scores": sc[i][slot, :n_r],
                                          "select": sel[i][slot, :n_r]}
        if ctx.peaks is not None:   # on the chip: the step's kernels
            slots = jnp.zeros((n_slots,), jnp.int32)
            found = hlo.mosaic_kernels(step.lower(
                self.params, slots, slots, cache).as_text())
            for name in ("flash_decode", "write_kv_row", "expert_ffn",
                         "index_score"):
                if not found.get(name):
                    ctx.problems.append(
                        f"kernel {name} is not in the decode step's "
                        f"program (found {found})")
            ctx.note(f"kernels in the decode step's program: {found}")
        del cache

        # ---- the reference, a row and a layer at a time, on the
        # program's token sets and expert sets; it judges EVERY set ------
        layer_fn = jax.jit(lambda L, x, f, c, a: ref.layer(
            L, x, model, f, c, a, index_eps))
        dense_fn = jax.jit(lambda L, x, c, a: ref.layer(
            L, x, model, None, c, a, index_eps))
        embed = jax.jit(ref.embed)
        head_fn = jax.jit(lambda p, x: ref.head(p, x, model))
        select = jax.jit(lambda c: ref.select(c, model))
        want, ref_dsa = {}, {}
        ref_ids = [np.zeros((R, total, k), np.int32) for _ in moe_layers]
        ref_choice = [np.zeros((R, total, mcfg.n_experts), np.float32)
                      for _ in moe_layers]
        sizes_wrong = outside_all = sel_all = differ_all = 0
        for r, n_r in enumerate(lens):
            where = jnp.asarray(at[r] + [0] * (P - len(at[r])), jnp.int32)
            size_due = np.minimum(np.arange(n_r) + 1, topk)
            past = np.arange(n_r) >= topk       # a choice was made there
            x = embed(self.params, jnp.asarray(toks[r:r + 1, :n_r]))
            for i, L in enumerate(self.params["layers"]):
                c = jnp.asarray(chosen[r][i])[None]
                if i in moe_layers:
                    j = moe_layers.index(i)
                    x, rec = layer_fn(
                        L, x, jnp.asarray(forced[j][r:r + 1, :n_r]), c,
                        where)
                    ref_ids[j][r, :n_r] = np.asarray(rec["ids"][0])
                    ref_choice[j][r, :n_r] = np.asarray(rec["choice"][0])
                else:
                    x, rec = dense_fn(L, x, c, where)
                dsa = {n: np.asarray(a[0]) for n, a in rec["dsa"].items()}
                for j, p in enumerate(at[r]):
                    ref_dsa[(i, r, p)] = {"scores": dsa["scores"][j],
                                          "select": dsa["select"][j]}
                sizes_wrong += int((dsa["size"] != size_due).sum())
                outside_all += int(dsa["outside"].sum())
                sel_all += int(dsa["size"][past].sum())
                differ_all += int(dsa["differs"][past].sum())
            lg = np.asarray(head_fn(self.params, x)[0])
            for p in at[r]:
                want[(r, p)] = lg[p]
        del chosen

        # ---- 2.-6. the rules --------------------------------------------
        facts = judge_selection(
            prog, {(i, slot, p): ref_dsa[(i, slot % R, p)]
                   for i, slot, p in prog}, topk, index_eps)
        facts.update(
            sets_judged_at_every_position=n_layers * sum(lens),
            set_sizes_wrong=sizes_wrong,
            positions_outside_the_band_everywhere=outside_all,
            selection_differs_share_everywhere=differ_all / max(sel_all, 1),
            decode_slots_compared=n_slots,
            decode_slots_not_their_twin_to_the_bit=twins_differ)
        valid = np.arange(total)[None, :] < np.asarray(lens)[:, None]
        eps = float(tol["score_eps"])
        worst_score = 0.0
        wrong_rule = n_differs = 0
        offenders = []
        for j in range(len(moe_layers)):
            worst_score = max(worst_score, float(np.abs(
                scores[j] - ref_choice[j])[valid].max()))
            mine = np.sort(forced[j], axis=-1)
            by_rule, margin = (np.asarray(a) for a in select(
                jnp.asarray(scores[j])))
            wrong = (mine != by_rule).any(-1) & valid
            wrong_rule += int(wrong.sum())
            offenders += [(j, int(r), int(p), float(margin[r, p]),
                           mine[r, p].tolist(), by_rule[r, p].tolist())
                          for r, p in zip(*np.nonzero(wrong))][:4]
            n_differs += int((mine != ref_ids[j]).any(-1)[valid].sum())
        token_layers = int(valid.sum()) * max(len(moe_layers), 1)
        gaps = {(slot, p): float(logit_gap_ulps(lg, want[(slot % R, p)]))
                for (slot, p), lg in got.items()}
        facts.update(
            score_gap=worst_score, score_eps=eps,
            routing_not_by_rule=wrong_rule,
            routing_differs_share=n_differs / token_layers,
            compared_positions=len(gaps), logit_gap_ulps=max(gaps.values()),
            logit_gap_ulps_by_kind={
                "prompt": max(g for (slot, p), g in gaps.items()
                              if p < plens[slot % R]),
                "decode": max(g for (slot, p), g in gaps.items()
                              if p >= plens[slot % R])})
        ctx.note(f"reference check under the near-tie rules: {facts} "
                 f"(tolerances: {tol['logit_ulps_bf16']} ulps, index score "
                 f"gap {index_eps / 2}, differing selections "
                 f"{tol['selection_differs_share_max']}, router score gap "
                 f"{eps / 2}, differing routing "
                 f"{tol['routing_differs_share_max']}, at least "
                 f"{tol['compared_positions_min']} positions; extend "
                 f"chunks of {sorted(set(widths))} past a bucket of "
                 f"{bucket})")
        ctx.facts["near_tie_check"] = facts
        differs_max = float(tol["selection_differs_share_max"])
        limits = [
            (facts["index_score_gap"] <= index_eps / 2,
             f"index scores are {facts['index_score_gap']} from the "
             f"reference's, over index_eps / 2"),
            (not facts["sets_not_topk_of_own_scores"],
             f"{facts['sets_not_topk_of_own_scores']} sets are not the top "
             f"{topk} of the program's own scores"),
            (not facts["positions_outside_the_band"] and not outside_all,
             f"{facts['positions_outside_the_band']} positions of the "
             f"checked sets and {outside_all} of all sets were chosen or "
             f"left out against the reference by more than index_eps"),
            (not sizes_wrong,
             f"{sizes_wrong} sets do not hold min(context, {topk}) "
             f"positions"),
            (max(facts["selection_differs_share"],
                 facts["selection_differs_share_everywhere"])
             <= differs_max,
             f"{facts['selection_differs_share']} of the selected positions "
             f"at the checked positions and "
             f"{facts['selection_differs_share_everywhere']} at all past "
             f"{topk} are not in the reference's own sets, over "
             f"{differs_max}"),
            (worst_score <= eps / 2,
             f"router scores are {worst_score} from the reference's, over "
             f"score_eps / 2 = {eps / 2}"),
            (not wrong_rule,
             f"{wrong_rule} token-layers chose other experts than the "
             f"reference's selection gives on the program's own scores; as "
             f"(expert layer, row, position, the reference's margin on "
             f"those scores, the program's set, the rule's): {offenders}"),
            (facts["routing_differs_share"]
             <= float(tol["routing_differs_share_max"]),
             f"{facts['routing_differs_share']} of the token-layers chose "
             f"other experts than the reference's own choice"),
            (len(gaps) >= int(tol["compared_positions_min"]),
             f"only {len(gaps)} positions were compared"),
            (facts["logit_gap_ulps"] <= float(tol["logit_ulps_bf16"]),
             f"logit gap {facts['logit_gap_ulps']} exceeds "
             f"{tol['logit_ulps_bf16']} bf16 ulps ({gaps})"),
        ]
        ctx.problems.extend(msg for ok, msg in limits if not ok)
