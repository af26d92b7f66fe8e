"""Runner for serving cells whose model generates by diffusion over blocks.

It is ``kinds/serve_moe.py``'s ``Runner`` (the closed loop, the units, the
counters, the near-tie rule for expert choice, ``serve.moe.dropped == 0``)
for a server whose step does not yield one token a row. A unit is still one
``step_round()``, now ``round_len`` PASSES over every slot's current block of
``block_len`` positions; a pass denoises a row (0 tokens) or commits its block
(up to ``block_len`` tokens), so the unit's work, ``serve.tokens_out``, is the
tokens COMMITTED and delivered in it.

What differs from the base runners:

* ``check_parity``: each parity prompt alone through the server's bucket
  prefill (block-causal, no head), its row cache scattered into a pool of
  ``n_slots`` slots (every slot a copy of one of the prompts), then
  ``parity.passes`` passes of ``block_decode`` over ALL slots, the timed
  shape, rows in different phases side by side. The blocks are
  TEACHER-FORCED: the check, not the model, says which position a pass
  unmasks (one a pass, in a seeded order) and with which token (seeded), and
  a block with no mask left gets its commit pass and is followed by an
  all-mask block: two whole blocks and more in 12 passes, whatever
  ``plen mod block_len``. Compared are the LOGITS of every pass at the
  block's positions (masked ones included: they decide what is unmasked)
  against the reference's full forward, no cache, on the same tokens: one
  reference row a (prompt, pass), the committed blocks as the program
  committed them. Expert choice is held by serve_moe.py's near-tie rule
  (its steps 2 to 5), with the reference's margin between the 8th and the
  9th probability as what decides a tie.
* the kernels demanded of the round's program are the block attend
  (``flash_block_decode``), the block write (``write_kv_block``) and
  ``expert_ffn``.
* ``finish``: the counter identities of ``serve.diffusion.*`` over the whole
  run, every slot held to its owner, position and debt, and for the readers
  the live contexts of every traced pass.
* the stationary start: ``paired_cuts`` hands out the shares of
  ``traffic.stationary_cut`` by a lattice over the rank of a request's
  output length, not by a shuffle. An admission here is a one-row prefill
  through 128 experts (8 to 16 ms, 12% of a unit), so the number of requests
  that happen to end inside a 10 s window IS the spread of ``serve_tok_s``.

Every tolerance is in the configuration file with its reason.
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
serve = serve_moe.serve

KERNELS = ("flash_block_decode", "write_kv_block", "expert_ffn")


def paired_cuts(req: dict, seed: int, n: int) -> list:
    """The shares by which the first ``n`` requests' outputs are cut: the
    multiset of ``traffic.stationary_cut`` (stratified uniform), handed
    out so that (rank of the output length, share) is a rank-1 lattice
    rotated by the seed instead of a seeded shuffle. Requests of like
    length then start at evenly spread ages, and the number that ends in
    any stretch of rounds is the steady state's to within one or two,
    where the shuffle's count is binomial (101 +- 4.5 of 192 in the
    window, +- 1.7 so; each costs a prefill and the rest of its round)."""
    stream = traffic_lib.ordered(req, seed)
    first = [next(stream) for _ in range(n)]
    shares = sorted(traffic_lib.stationary_cut(req, seed, n))
    turn = float(np.random.default_rng([int(seed), 0x6c61]).random())
    golden = (5 ** 0.5 - 1) / 2
    lattice = np.argsort(np.argsort((np.arange(n) * golden + turn) % 1.0))
    by_output = sorted(range(n), key=lambda i: (first[i][1], first[i][0], i))
    cuts = [0.0] * n
    for rank, i in enumerate(by_output):
        cuts[i] = shares[int(lattice[rank])]
    return cuts


class Runner(serve_moe.Runner):

    def __init__(self, ctx):
        super().__init__(ctx)
        self.plen_of = {}       # rid -> prompt length, while in flight
        self.traced = []        # (pos, budget, given, kk, commits) a round

    # ---- set-up ----------------------------------------------------------
    def setup(self) -> None:
        import jax.numpy as jnp
        from rlo_tpu.models.serve import DecodeServer
        from rlo_tpu.models.transformer import TransformerConfig
        from rlo_tpu.utils import hlo
        from rlo_tpu.utils.metrics import Registry
        ctx, tr = self.ctx, self.ctx.traffic
        self.mcfg = mcfg = TransformerConfig(**ctx.config["model"])
        self.params = serve.make_params(ctx.seed, mcfg)
        self.params["embed"].block_until_ready()
        ctx.part("weights")

        self.check_parity()
        ctx.part("reference_check")

        self.reg = Registry()
        self.srv = srv = DecodeServer(self.params, mcfg, metrics=self.reg,
                                      **dict(tr["server"]))
        self.n_slots = srv.n_slots
        round_jit = srv._round
        if ctx.trace:
            self.capture_rounds(srv)
            serve.wrap_spans(srv)
        self.stream = traffic_lib.ordered(tr["requests"], ctx.seed)
        self.cuts = (paired_cuts(tr["requests"], ctx.seed, self.n_slots)
                     if tr.get("stationary_start") else [])
        for _ in range(int(tr["warm_units"])):
            self.unit()
        if self.failed:
            ctx.problems.append(f"{self.failed} requests failed in warm-up")
        self.checked = self.failed = 0
        ctx.part("warm_units")

        if ctx.peaks is not None:   # on the chip: the kernels by name
            i32 = jnp.int32
            B = mcfg.block_len
            slots = jnp.zeros((self.n_slots,), i32)
            blk = jnp.zeros((self.n_slots, B), i32)
            text = round_jit.lower(
                self.params, srv.cache, blk, blk.astype(bool), slots, slots,
                slots, kk=srv.round_len).as_text()
            found = hlo.mosaic_kernels(text)
            for name in KERNELS:
                if not found.get(name):
                    ctx.problems.append(
                        f"kernel {name} is not in the round's program "
                        f"(found {found})")
            ctx.note(f"kernels in the round's program: {found}")
            ctx.part("kernel_check")
        ctx.facts.update(
            n_slots=self.n_slots, round_len=srv.round_len,
            n_layers=mcfg.n_layers, n_heads=mcfg.n_heads,
            kv_heads=mcfg.kv_heads, head_dim=mcfg.head_dim,
            block_len=mcfg.block_len)
        self.base = self.counters()

    # ---- the check -------------------------------------------------------
    def run_program(self):
        """The serving path at the timed shapes on teacher-forced blocks.
        Returns (rows, logits, forced, scores): ``rows`` is one
        (tokens (n,), first fresh position, end) a reference row — a
        (prompt, pass) pair: the prompt's whole blocks, the blocks
        committed before the pass as they were committed, the pass's
        block; ``logits[i]`` (B, V) the program's at the block's positions;
        ``forced[layer]`` (rows, total, k) the expert sets the program
        chose at every position of a row (-1 past its end) and
        ``scores[layer]`` (rows, total, E) what it chose them on."""
        import jax
        import jax.numpy as jnp
        from rlo_tpu.models import kvcache
        from rlo_tpu.models.generate import (block_decode, init_kv_cache,
                                             prefill)
        from rlo_tpu.models.serve import PROMPT_BUCKETS, _bucket, \
            prompt_buckets_for
        from rlo_tpu.utils import hlo
        ctx, mcfg = self.ctx, self.mcfg
        par, server = ctx.traffic["parity"], ctx.traffic["server"]
        plens, passes = list(par["prompt_lens"]), int(par["passes"])
        n_slots, max_len = int(server["n_slots"]), int(server["max_len"])
        buckets = prompt_buckets_for(mcfg, max_len, tuple(server.get(
            "prompt_buckets", PROMPT_BUCKETS)))
        B, k, E = mcfg.block_len, mcfg.experts_per_tok, mcfg.n_experts
        n_layers, R = mcfg.n_layers, len(plens)
        if len({_bucket(p, buckets) for p in plens}) < min(2, len(buckets)) \
                or not any(p % B for p in plens) \
                or not any(p % B == 0 for p in plens):
            ctx.problems.append(
                f"parity prompts {plens} must cover two buckets of "
                f"{buckets}, and lengths that {B} does and does not divide")
        total = -(-(max(plens) + passes) // B) * B + B

        def prefill_row(p, t):
            info = []
            row = init_kv_cache(mcfg, 1, max_len)
            _, row = prefill(p, t, row, mcfg, moe_info=info,
                             need_logits=False)
            return row, [(i["ids"], i["choice"]) for i in info]

        def step_fn(p, blk, pos, c):
            info = []
            lg, c = block_decode(p, blk, pos, c, mcfg, moe_info=info)
            # the parity rows are slots 0..R-1
            return lg[:R], c, [(i["ids"][:R * B].reshape(R, B, k),
                                i["choice"][:R * B].reshape(R, B, E))
                               for i in info]

        prefill_row = jax.jit(prefill_row)
        scatter = jax.jit(kvcache.scatter_slot, donate_argnums=(0,))
        step = jax.jit(step_fn, donate_argnums=(3,))

        # what the program chose, by parity row and position
        sets = [np.full((R, total, k), -1, np.int32) for _ in range(n_layers)]
        probs = [np.zeros((R, total, E), np.float32) for _ in range(n_layers)]
        toks = np.zeros((R, total), np.int64)
        state = []
        cache = init_kv_cache(mcfg, n_slots, max_len)
        row_caches = []
        for r, plen in enumerate(plens):
            text = traffic_lib.token_ids(ctx.seed, 10_000 + r, plen,
                                         mcfg.vocab)
            bucket = _bucket(plen, buckets)
            prompt = np.zeros((1, bucket), np.int32)
            prompt[0, :plen] = text
            row, info = prefill_row(self.params, jnp.asarray(prompt))
            row_caches.append(row)
            n_full = plen // B * B
            toks[r, :n_full] = text[:n_full]
            for j, (ids, choice) in enumerate(info):
                sets[j][r, :n_full] = np.asarray(ids)[:n_full]
                probs[j][r, :n_full] = np.asarray(choice)[:n_full]
            blk = np.full((B,), mcfg.mask_id, np.int64)
            blk[:plen - n_full] = text[n_full:]
            state.append({
                "rng": np.random.default_rng([int(ctx.seed), 0x6466, r]),
                "start": n_full, "blk": blk,
                "masked": np.arange(B) >= plen - n_full})
        for slot in range(n_slots):
            cache = scatter(cache, row_caches[slot % R], jnp.int32(slot))
        del row_caches
        slot_row = np.arange(n_slots) % R

        rows, logits = [], []
        forced = [[] for _ in range(n_layers)]
        scores = [[] for _ in range(n_layers)]
        commits = 0
        for _ in range(passes):
            blk = np.stack([s["blk"] for s in state])
            pos = np.array([s["start"] for s in state])
            lg, cache, info = step(
                self.params, jnp.asarray(blk[slot_row], jnp.int32),
                jnp.asarray(pos[slot_row], jnp.int32), cache)
            lg = np.asarray(lg)
            for r, s in enumerate(state):
                at = s["start"]
                seq = toks[r].copy()
                seq[at:at + B] = s["blk"]
                rows.append((seq, at, at + B))
                logits.append(lg[r])
                for j, (ids, choice) in enumerate(info):
                    f, c = sets[j][r].copy(), probs[j][r].copy()
                    f[at:at + B] = np.asarray(ids[r])
                    c[at:at + B] = np.asarray(choice[r])
                    f[at + B:] = -1
                    forced[j].append(f)
                    scores[j].append(c)
                    if not s["masked"].any():   # a commit: the rows stand
                        sets[j][r], probs[j][r] = f, c
                if s["masked"].any():           # unmask one, by the seed
                    i = s["rng"].choice(np.flatnonzero(s["masked"]))
                    s["blk"][i] = s["rng"].integers(0, mcfg.vocab)
                    s["masked"][i] = False
                else:
                    commits += 1
                    toks[r, at:at + B] = s["blk"]
                    s["start"] = at + B
                    s["blk"] = np.full((B,), mcfg.mask_id, np.int64)
                    s["masked"] = np.ones((B,), bool)
        if ctx.peaks is not None:   # on the chip: the pass's kernels
            slots = jnp.zeros((n_slots,), jnp.int32)
            found = hlo.mosaic_kernels(step.lower(
                self.params, jnp.zeros((n_slots, B), jnp.int32), slots,
                cache).as_text())
            for name in KERNELS:
                if not found.get(name):
                    ctx.problems.append(
                        f"kernel {name} is not in the pass's program "
                        f"(found {found})")
            ctx.note(f"kernels in the pass's program: {found}")
        del cache
        ctx.note(f"parity: prompts {plens}, {passes} passes over {n_slots} "
                 f"slots, {commits} commits, {len(rows)} reference rows of "
                 f"{total} positions")
        return (rows, logits, [np.stack(f) for f in forced],
                [np.stack(c) for c in scores])

    def run_reference(self, rows, forced, chunk: int):
        """The plain reference on the rows' tokens, ``chunk`` rows at a
        time and a layer at a time, following ``forced``. Returns
        (logits at each row's block (rows, B, V), one routing record per
        layer)."""
        import jax
        import jax.numpy as jnp
        ctx, ref, model = self.ctx, self.ctx.reference, self.ctx.config[
            "model"]
        B = self.mcfg.block_len
        embed = jax.jit(ref.embed)
        layer = jax.jit(lambda L, x, f: ref.layer(L, x, model, f))
        head = jax.jit(lambda p, x, at: ref.head(
            p, jax.vmap(lambda xr, a: jax.lax.dynamic_slice_in_dim(
                xr, a, B))(x, at), model))
        toks = np.stack([seq for seq, _, _ in rows])
        starts = np.array([at for _, at, _ in rows], np.int32)
        want, records = [], [[] for _ in self.params["layers"]]
        for lo in range(0, len(rows), chunk):
            x = embed(self.params, jnp.asarray(toks[lo:lo + chunk]))
            for j, L in enumerate(self.params["layers"]):
                x, rec = layer(L, x, jnp.asarray(forced[j][lo:lo + chunk]))
                records[j].append(jax.tree.map(np.asarray, rec))
            want.append(np.asarray(head(
                self.params, x, jnp.asarray(starts[lo:lo + chunk]))))
        records = [{key: np.concatenate([r[key] for r in recs])
                    for key in recs[0]} for recs in records]
        return np.concatenate(want), records

    def judge(self, rows, logits, forced, scores, want, records) -> dict:
        """serve_moe.py's rule, steps 2 to 5, over the FRESH token-layers
        of every row: the positions whose sets the row's own pass chose
        (its block; for a prompt's first pass the prefilled positions
        too). Returns the facts; problems go to ``ctx.problems``."""
        import jax
        import jax.numpy as jnp
        ctx, ref, model = self.ctx, self.ctx.reference, self.ctx.config[
            "model"]
        tol = ctx.config["tolerance"]
        eps = float(tol["score_eps"])
        total = forced[0].shape[1]
        fresh = np.zeros((len(rows), total), bool)
        for i, (_, at, end) in enumerate(rows):
            fresh[i, at:end] = True
        # a prompt's prefilled positions count once: in its first row
        # (the rows are pass-major: the first pass's come first)
        for i in range(len(ctx.traffic["parity"]["prompt_lens"])):
            fresh[i, :rows[i][1]] = True
        n_fresh = int(fresh.sum())
        select = jax.jit(lambda c: ref.select(c, model)[0])
        worst_score, by_layer = 0.0, []
        wrong_rule = wrong_set = n_undecidable = n_differs = 0
        for j, rec in enumerate(records):
            by_layer.append(float(np.abs(
                scores[j] - rec["choice"])[fresh].max()))
            worst_score = max(worst_score, by_layer[-1])
            mine = np.sort(forced[j], axis=-1)
            by_rule = np.asarray(select(jnp.asarray(scores[j])))
            wrong_rule += int((mine != by_rule).any(-1)[fresh].sum())
            tie = rec["margin"] < eps
            n_undecidable += int(tie[fresh].sum())
            differs = (mine != rec["ids"]).any(-1)
            n_differs += int(differs[fresh].sum())
            wrong_set += int((differs & ~tie)[fresh].sum())
        n_token_layers = n_fresh * len(records)
        share = n_undecidable / n_token_layers
        differs_share = n_differs / n_token_layers
        gaps = [float(logit_gap_ulps(lg, w)) for lg, w in zip(logits, want)]
        gap = max(gaps)
        facts = {"score_gap": worst_score, "score_gap_by_layer": by_layer,
                 "score_eps": eps, "undecidable_share": share,
                 "routing_differs_share": differs_share,
                 "token_layers": n_token_layers,
                 "compared_positions": len(gaps) * self.mcfg.block_len,
                 "logit_gap_ulps": gap,
                 "logit_gap_ulps_median": float(np.median(gaps))}
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
        if facts["compared_positions"] < int(tol["compared_positions_min"]):
            ctx.problems.append(
                f"only {facts['compared_positions']} positions were "
                f"compared, under {tol['compared_positions_min']}")
        if not gap <= float(tol["logit_ulps_bf16"]):
            ctx.problems.append(
                f"logit gap {gap} exceeds {tol['logit_ulps_bf16']} bf16 "
                f"ulps")
        return facts

    def check_parity(self) -> None:
        ctx, tol = self.ctx, self.ctx.config["tolerance"]
        rows, logits, forced, scores = self.run_program()
        want, records = self.run_reference(
            rows, forced, int(ctx.traffic["parity"]["reference_rows"]))
        facts = self.judge(rows, logits, forced, scores, want, records)
        ctx.note(f"reference check under the near-tie rule: {facts} "
                 f"(tolerances {tol['logit_ulps_bf16']} ulps, score gap "
                 f"{float(tol['score_eps']) / 2}, undecidable share "
                 f"{tol['undecidable_share_max']}, differing share "
                 f"{tol['routing_differs_share_max']}, at least "
                 f"{tol['compared_positions_min']} positions)")
        ctx.facts["near_tie_check"] = facts

    # ---- the loop --------------------------------------------------------
    def top_up(self) -> None:
        """kinds/serve.py's, which also keeps a request's prompt length
        while it is in flight (``finish`` holds its slot to it)."""
        srv = self.srv
        while srv.queue_depth() < self.n_slots:
            plen, out = next(self.stream)
            idx = self.n_sent
            self.n_sent += 1
            if idx < len(self.cuts):
                out = max(1, int(round(out * self.cuts[idx])))
            rid = srv.submit(traffic_lib.token_ids(
                self.ctx.seed, idx, plen, self.mcfg.vocab), out)
            self.sent[rid] = out
            self.plen_of[rid] = plen

    def harvest(self) -> None:
        done = set(self.sent)
        super().harvest()
        for rid in done - set(self.sent):
            del self.plen_of[rid]

    def capture_rounds(self, srv) -> None:
        """In a traced unit, keep what each round started from and which
        passes committed (left on the device until ``finish``: nothing is
        read inside the window)."""
        inner = srv._round

        def call(params, cache, blk, masked, pos, owed, given, kk):
            out = inner(params, cache, blk, masked, pos, owed, given, kk)
            if self.capturing:
                self.traced.append((np.asarray(pos).copy(),
                                    np.asarray(owed).copy(),
                                    np.asarray(given).copy(), int(kk),
                                    out[6]))
            return out

        srv._round = call

    # ---- results ---------------------------------------------------------
    def traced_passes(self):
        """For the readers: (pos, budget, kk) a traced round, as
        ``decode_step_ms`` takes them, and for every traced pass the
        positions each LIVE row's block attended (its block's end)."""
        B = self.mcfg.block_len
        rounds, contexts = [], []
        for pos, owed, given, kk, commits in self.traced:
            rounds.append((pos, owed, kk))
            pos, owed, given = (a.astype(np.int64) for a in
                                (pos, owed, given))
            for commit in np.asarray(commits):
                contexts.append((pos + B)[owed > 0])
                delivered = np.minimum(B - given, owed)
                owed = owed - np.where(commit, delivered, 0)
                given = np.where(commit, 0, given)
                pos = pos + np.where(commit, B, 0)
        return rounds, contexts

    def finish(self):
        ctx, srv, B = self.ctx, self.srv, self.mcfg.block_len
        rounds, contexts = self.traced_passes()
        ctx.facts["block_contexts"] = contexts
        c = {name: self.reg.counter("serve.diffusion." + name).value
             for name in ("row_passes", "denoise_passes", "commit_passes",
                          "tokens_unmasked", "blocks_committed",
                          "tokens_committed", "surplus_dropped",
                          "leftover_committed")}
        out = self.reg.counter("serve.tokens_out").value
        held = {
            "row_passes = denoise_passes + commit_passes":
                c["row_passes"] == c["denoise_passes"] + c["commit_passes"],
            "tokens_committed = B blocks_committed - surplus_dropped - "
            "leftover_committed":
                c["tokens_committed"] == B * c["blocks_committed"]
                - c["surplus_dropped"] - c["leftover_committed"],
            "tokens_unmasked = denoise_passes (random weights)":
                c["tokens_unmasked"] == c["denoise_passes"],
            "tokens_committed = serve.tokens_out":
                c["tokens_committed"] == out,
            "row_passes > 0": c["row_passes"] > 0}
        broken = [name for name, ok in held.items() if not ok]
        if broken:
            ctx.problems.append(f"counter identities broken: {broken} "
                                f"({c}, tokens_out {out})")
        # every slot to its owner, position and debt: the blocks a slot
        # has committed account for exactly the tokens its request got
        wrong = 0
        for slot, rid in enumerate(srv.slot_ownership()):
            if rid is None:
                continue
            plen, owes = self.plen_of[rid], self.sent.get(rid)
            blocks, off = divmod(int(srv.pos[slot]) - plen // B * B, B)
            got = None if owes is None else owes - int(srv.budget[slot])
            if (owes is None or off or blocks < 0
                    or got != min(owes, max(0, blocks * B - plen % B))):
                wrong += 1
        if wrong:
            ctx.problems.append(
                f"{wrong} slots are not where their owner's prompt, "
                f"blocks and debt put them")
        ctx.note(f"diffusion counters over the run: {c}; slots held to "
                 f"owner, position and debt: "
                 f"{sum(r is not None for r in srv.slot_ownership())}")
        result = super().finish()
        ctx.facts["traced_rounds"] = rounds
        return result
