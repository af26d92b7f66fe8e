"""Runner for serving cells whose model carries several hyper-connected
residual streams and shares one token selection between layers (a "full"
layer's indexer chooses, the "shared" layers after it attend its choice),
with latent attention under a token selector and sparse experts.

It is ``kinds/serve_dsa.py``'s ``Runner`` for THE TRAFFIC: a fixed set of
sessions, one a slot, replayed on every seed, every prompt admitted through
the server's own admission during set-up, none finishing and none admitted
inside the window (``serve_dsa``'s ``top_up`` and ``finish``). Its set-up is
``serve_moe``'s, with the round's program held to the selector's kernel
``index_score`` beside the attend, the row write and the experts.

THE CHECK runs on the timed server itself, before the fixed set takes its
slots (``kinds/serve_window.py``'s order), and holds what the server's own
programs produced to the plain reference:

1. The server admits parity prompt ``slot % R`` into every one of its
   ``n_slots`` slots (``submit`` and its own admission: the bucket
   prefill, the extend chunks at its widths, the scatter, into the cache
   it sized): one row past 32 768 tokens and shorter rows past the
   selector's ``index_topk``, so that every slot's round selects. Then it
   runs ONE of its rounds: ``round_len`` steps of its own program (the
   scan, the selection and the shared layers' reuse inside it, the
   hyper-connections' state carried from step to step, the donated cache).
   Every slot's request owes exactly the first token and that round, so
   the slots are free again for the fixed set.
2. Every token the server emitted - the first from its admission, the
   round's ``round_len`` - must be a NEAR-ARGMAX of the reference's logits
   at the position before it: within twice the logit tolerance of the
   reference's largest. Tokens, not logits, are what the round returns.
3. A probe on the server's cache as the round left it: the server's step
   (``decode_step`` through the cache, as the round runs it) over the
   ``round_len`` positions after the round, fed the round's last token and
   seeded tokens (it reads the rows the round wrote), then over the
   round's own positions fed the round's tokens (it reads the rows the
   admission wrote). It hands out, per layer and position, logits, the set
   every layer attended, the full layers' index scores, and per expert
   layer the choice scores and experts. The prompt positions' come from
   each prompt run alone through the bucket prefill and the extend chunks
   at the server's widths.
4. A shared layer's set must be its full layer's, BIT FOR BIT, at every
   prompt position and every probed step of every slot; a shared layer
   hands out no scores (it scores nothing).
5. The full layers' sets are held to ``serve_dsa``'s rules: index scores
   within ``index_eps / 2`` of the reference's at the checked positions
   (the probed steps, prompt positions past ``index_topk`` and the last),
   each set exactly the top ``index_topk`` of its own scores, every
   position of EVERY set inside the reference's band, the share the
   reference's own sets lack bounded, every set of
   ``min(context, index_topk)`` positions.
6. The reference (perf/configs/<config>.py: four streams in float32)
   attends the program's sets - the full layer's on its shared layers -
   and routes with its expert sets; logits are compared at every checked
   position in bfloat16 ulps of the largest reference logit; routing by
   ``serve_moe``'s rule at every position of every slot's sequence.

``program_run`` and ``judge`` are apart so that the tolerances' readings -
the reference with activations rounded to bfloat16 and to float8_e4m3fn
(its ``ACT_DTYPE``) - are taken against ONE run of the program. The judge
runs in ``finish``, after the window and with the server's cache freed: the
reference's float32 streams would not fit beside it. Every tolerance is in
the configuration file with its reason. The run is not ``correct`` without
the Mosaic kernel ``index_score`` in the round's program.
"""

from __future__ import annotations

import hashlib
import importlib.util
from pathlib import Path

import numpy as np

from lib import traffic as traffic_lib
from lib.compare import BF16_ULP

_spec = importlib.util.spec_from_file_location(
    "perf_kinds_serve_dsa", Path(__file__).with_name("serve_dsa.py"))
serve_dsa = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(serve_dsa)
serve_moe = serve_dsa.serve_moe

#: kernels the round's program holds
ROUND_KERNELS = ("flash_decode", "write_kv_row", "expert_ffn",
                 "index_score")


def _gaps_ulps(got, want):
    """Per row of (n, V) logits: max |got - want| in bfloat16 ulps of the
    row's largest reference logit; infinite where ``got`` is not finite."""
    gap = np.abs(got - want).max(-1) / (np.abs(want).max(-1) * BF16_ULP)
    return np.where(np.isfinite(got).all(-1), gap, np.inf)


class Runner(serve_dsa.Runner):

    def __init__(self, ctx):
        super().__init__(ctx)
        self.check_steps = 0    # the check's round, run by the server

    def setup(self) -> None:
        serve_moe.Runner.setup(self)
        import jax.numpy as jnp
        from rlo_tpu.utils import hlo
        ctx, srv, mcfg = self.ctx, self.srv, self.mcfg
        if ctx.peaks is not None:
            slots = jnp.zeros((self.n_slots,), jnp.int32)
            found = hlo.mosaic_kernels(srv._jits["_round"][0].lower(
                self.params, srv.cache, slots, slots,
                kk=srv.round_len).as_text())
            for name in ROUND_KERNELS:
                if not found.get(name):
                    ctx.problems.append(
                        f"kernel {name} is not in the round's program "
                        f"(found {found})")
            ctx.part("kernel_check")
        kinds = [mcfg.index_kind(i) for i in range(mcfg.n_layers)]
        ctx.facts.update(index_topk=mcfg.index_topk,
                         index_n_heads=mcfg.index_n_heads,
                         index_head_dim=mcfg.index_head_dim,
                         index_types=kinds, hc_mult=mcfg.hc_mult,
                         full_layers=kinds.count("full"))
        self.window_base = dict(self.base)

    # ---- the check ------------------------------------------------------
    def check_parity(self) -> None:
        """Nothing before the server exists: the check runs on it, at the
        first unit (``top_up``)."""

    def top_up(self) -> None:
        if not self.session_of:
            self.ctx.part("server")
            self.prog = self.program_run()
            self.ctx.part("reference_check")
        super().top_up()

    def counters(self) -> dict:
        """The program's counters, ``serve.steps`` less the check's round:
        ``serve_dsa.finish`` holds every session to its prompt plus the
        steps since the fixed set took the slots."""
        now = super().counters()
        now["serve.steps"] = now.get("serve.steps", 0) - self.check_steps
        return now

    def finish(self):
        """The reference's judgement of the check (the server's cache freed
        first), then serve_dsa's (the fixed set held)."""
        self.srv.cache = None
        self.ctx.facts["reference_check"] = self.judge(self.prog)
        return super().finish()

    def program_run(self) -> dict:
        """Rules 1, 3 and 4: the server's admission and one of its rounds,
        the probe on the cache it left, each prompt alone; what the
        programs handed out, as numpy arrays."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from rlo_tpu.models.generate import (block_decode, decode_step,
                                             init_kv_cache, prefill)
        ctx, mcfg, srv = self.ctx, self.mcfg, self.srv
        par = ctx.traffic["parity"]
        plens, R = [int(p) for p in par["prompt_lens"]], \
            len(par["prompt_lens"])
        kk, n_slots, max_len = srv.round_len, srv.n_slots, srv.max_len
        bucket, (chunk_w, long_w) = srv.buckets[-1], (srv._chunk_w,
                                                      srv._long_w)
        topk, k, E = mcfg.index_topk, mcfg.experts_per_tok, mcfg.n_experts
        if min(plens) <= topk or topk < bucket or n_slots < R or \
                max(plens) + 2 * kk > max_len:
            ctx.problems.append(
                f"parity prompts {plens} must pass index_topk {topk} (not "
                f"under the bucket {bucket}: the checked prompt positions "
                f"lie in extend chunks), fit {n_slots} slots and, with two "
                f"rounds of {kk}, max_len {max_len}")
        n_layers = mcfg.n_layers
        kinds = [mcfg.index_kind(i) for i in range(n_layers)]
        full = [i for i in range(n_layers) if kinds[i] == "full"]
        owner = [max(j for j in full if j <= i) for i in range(n_layers)]
        seeded = [traffic_lib.token_ids(ctx.seed, 10_000 + r,
                                        plens[r] + 2 * kk, mcfg.vocab)
                  for r in range(R)]
        row_of = np.arange(n_slots) % R
        plen = np.asarray(plens)[row_of]                     # (slots,)
        n_of = plen + 2 * kk             # a slot's sequence, probed to
        nb_of = -(-n_of // 8)            # its packed sets' bytes
        shared = {"sets": 0, "differ": 0}

        def shared_check(sets):
            """Rule 4 on one call's packed sets (one array a layer)."""
            if len(sets) != n_layers:
                ctx.problems.append(f"{len(sets)} selection records for "
                                    f"{n_layers} layers")
                return
            for i in range(n_layers):
                if kinds[i] == "shared":
                    shared["sets"] += 1
                    shared["differ"] += int(
                        (sets[i] != sets[owner[i]]).any())

        def routing(info):
            return [(i["ids"], i["choice"]) for i in info]

        # ---- 1. the server: its admission, then one round ---------------
        rids = [srv.submit(seeded[r][:plens[r]], kk + 1) for r in row_of]
        srv._admit()
        seated = srv.slot_ownership()
        if list(seated) != rids or not (srv.pos == plen).all():
            ctx.problems.append(f"the parity requests {rids} were not "
                                f"seated in order: {seated}")
        steps0 = srv.steps_run
        srv.step_round()
        self.check_steps = srv.steps_run - steps0
        done = dict(srv.poll_completed())
        emitted = np.zeros((n_slots, kk + 1), np.int32)
        for slot, rid in enumerate(rids):
            out = np.asarray(done.get(rid, ()), np.int32)
            if out.shape != (kk + 1,):
                ctx.problems.append(f"parity request {rid} returned "
                                    f"{out.shape[0]} tokens, not {kk + 1}")
                continue
            emitted[slot] = out
        if any(r is not None for r in srv.slot_ownership()):
            ctx.problems.append("the check's requests still hold slots")
        # a slot's sequence: its prompt, the first token and the round's
        # (positions plen..plen+kk), then seeded tokens to plen+2kk
        seqs = [np.concatenate([seeded[row_of[s]][:plen[s]], emitted[s],
                                seeded[row_of[s]][plen[s] + kk + 1:]])
                for s in range(n_slots)]

        # ---- 3. the probe: the server's step through the server's cache,
        # as its round runs it (this cache keeps no write-behind tail)
        def probe(p, toks, pos, cache):
            def body(c, s):
                info, dsa = [], []
                lg, c = decode_step(p, toks[:, s], pos + s, c, mcfg,
                                    moe_info=info, dsa_info=dsa)
                return c, (lg, routing(info),
                           [jnp.packbits(d["select"][:, 0], axis=-1)
                            for d in dsa],
                           [d["scores"][:, 0] for d in dsa
                            if "scores" in d])

            return lax.scan(body, cache, jnp.arange(kk, dtype=jnp.int32))

        probe = jax.jit(probe, donate_argnums=(3,))
        # per slot, the probed positions plen..plen+2kk: logits, routing,
        # packed sets and scores of the full layers
        d_lg = np.zeros((n_slots, 2 * kk, mcfg.vocab), np.float32)
        d_ids = [np.zeros((n_slots, 2 * kk, k), np.int32) for _ in range(
            sum(1 for L in self.params["layers"] if "moe" in L))]
        d_choice = [np.zeros((n_slots, 2 * kk, E), np.float32)
                    for _ in d_ids]
        d_sets = [[np.zeros((2 * kk, nb_of[s]), np.uint8) for _ in full]
                  for s in range(n_slots)]
        d_scores = [[np.zeros((2 * kk, n_of[s]), np.float32) for _ in full]
                    for s in range(n_slots)]
        # the rows the round wrote first, then over them again
        cache = srv.cache
        srv.cache = None
        for half, start in ((slice(kk, 2 * kk), plen + kk),
                            (slice(0, kk), plen)):
            toks = np.stack([q[p:p + kk] for q, p in zip(seqs, start)])
            cache, (lg, info, sel, sc) = probe(
                self.params, jnp.asarray(toks),
                jnp.asarray(start, jnp.int32), cache)
            d_lg[:, half] = np.swapaxes(np.asarray(lg), 0, 1)
            del lg
            for j, (ids, ch) in enumerate(info):
                d_ids[j][:, half] = np.swapaxes(
                    np.asarray(ids).reshape(kk, n_slots, k), 0, 1)
                d_choice[j][:, half] = np.swapaxes(
                    np.asarray(ch).reshape(kk, n_slots, E), 0, 1)
            sel = [np.asarray(a) for a in sel]      # (kk, slots, bytes)
            for t in range(kk):
                shared_check([a[t] for a in sel])
            for j, i in enumerate(full):
                sc_j = np.asarray(sc[j])
                for s in range(n_slots):
                    d_sets[s][j][half] = sel[i][:, s, :nb_of[s]]
                    d_scores[s][j][half] = sc_j[:, s, :n_of[s]]
            del sc, sel
        srv.cache = cache
        del cache
        unlike = int((np.argmax(d_lg[:, :kk], -1) != emitted[:, 1:]).sum())

        # ---- the prompt positions: each prompt alone, at the server's
        # bucket and widths (sets and routing at every position; scores
        # and logits at the checked ones) ---------------------------------
        def prefill_row(p, t):
            info = []
            row = init_kv_cache(mcfg, 1, max_len)
            lg, row = prefill(p, t, row, mcfg, moe_info=info)
            return lg, row, routing(info)

        def extend(p, row, t, pos0, local):
            info, dsa = [], []
            lg, row = block_decode(p, t, pos0[None], row, mcfg,
                                   moe_info=info, dsa_info=dsa)
            return (lg[0, local], row, routing(info),
                    [jnp.packbits(d["select"][0], axis=-1) for d in dsa],
                    [d["scores"][0, local] for d in dsa if "scores" in d])

        prefill_row = jax.jit(prefill_row)
        extend = jax.jit(extend, donate_argnums=(1,))
        at = [serve_dsa.checked_positions(p, 0, topk,
                                          int(par["prompt_checks"]))
              for p in plens]
        P = max(len(a) for a in at)
        p_ids, p_choice, p_sets, p_scores, p_got = [], [], [], [], []
        widths = set()
        for r, n_r in enumerate(plens):
            nb = -(-(n_r + 2 * kk) // 8)
            ids_r = [np.zeros((n_r, k), np.int32) for _ in d_ids]
            choice_r = [np.zeros((n_r, E), np.float32) for _ in d_ids]
            sets_r = [np.zeros((n_r, nb), np.uint8) for _ in full]
            scores_r = {}       # (full layer, position) -> scores, select

            def keep(info, lo, hi):
                for j, (ids, ch) in enumerate(info):
                    ids_r[j][lo:hi] = np.asarray(ids).reshape(-1, k)[
                        :hi - lo]
                    choice_r[j][lo:hi] = np.asarray(ch).reshape(-1, E)[
                        :hi - lo]

            lg, row, info = prefill_row(self.params,
                                        jnp.asarray(seeded[r][None, :bucket]))
            keep(info, 0, bucket)
            before = np.tril(np.ones((bucket, n_r + 2 * kk), bool))
            for s_j in sets_r:      # a prompt block: everything before
                s_j[:bucket] = np.packbits(before, axis=-1)
            off = bucket
            width = long_w if n_r - bucket > long_w else chunk_w
            widths.add(width)
            pos, logits = [], []
            while off < n_r:
                n = min(width, n_r - off)
                piece = np.zeros((1, width), np.int32)
                piece[0, :n] = seeded[r][off:off + n]
                mine = [p - off for p in at[r] if off <= p < off + n]
                local = np.zeros((P,), np.int32)
                local[:len(mine)] = mine
                lg, row, info, sel, sc = extend(
                    self.params, row, jnp.asarray(piece), jnp.int32(off),
                    jnp.asarray(local))
                keep(info, off, off + n)
                sel = [np.asarray(a)[:n, :nb] for a in sel]
                shared_check(sel)
                for j, i in enumerate(full):
                    sets_r[j][off:off + n] = sel[i]
                    for q, p in enumerate(mine):
                        scores_r[(i, p + off)] = {
                            "scores": np.asarray(sc[j][q])[:n_r + 2 * kk],
                            "select": np.unpackbits(
                                sel[i][p], count=n_r + 2 * kk).astype(bool)}
                pos += [p + off for p in mine]
                logits.append(np.asarray(lg)[:len(mine)])
                off += n
            del row
            p_ids.append(ids_r)
            p_choice.append(choice_r)
            p_sets.append(sets_r)
            p_scores.append(scores_r)
            p_got.append((np.asarray(pos), np.concatenate(logits)))
        first_unlike = sum(int(np.argmax(p_got[row_of[s]][1][-1])
                               != emitted[s, 0]) for s in range(n_slots))
        return {"seqs": seqs, "plen": plen, "row_of": row_of, "kk": kk,
                "emitted": emitted, "kinds": kinds, "full": full,
                "owner": owner, "at": at,
                "d_lg": d_lg, "d_ids": d_ids, "d_choice": d_choice,
                "d_sets": d_sets, "d_scores": d_scores,
                "p_ids": p_ids, "p_choice": p_choice, "p_sets": p_sets,
                "p_scores": p_scores, "p_got": p_got,
                "shared_sets": shared["sets"],
                "shared_differ": shared["differ"],
                "widths": sorted(widths), "bucket": bucket,
                "round_tokens_unlike_probe": unlike,
                "first_tokens_unlike_prompt_run": first_unlike}

    def judge(self, prog: dict) -> dict:
        """Rules 2, 4-6: the reference on the program's sets and expert
        sets, a distinct slot sequence and a layer at a time; the problems
        go to ``ctx.problems``. Returns the facts."""
        import jax
        import jax.numpy as jnp
        ctx, mcfg, ref = self.ctx, self.mcfg, self.ctx.reference
        model, tol = ctx.config["model"], ctx.config["tolerance"]
        seqs, plen, row_of, kk = (prog["seqs"], prog["plen"],
                                  prog["row_of"], prog["kk"])
        kinds, full, owner, emitted = (prog["kinds"], prog["full"],
                                       prog["owner"], prog["emitted"])
        n_slots, n_moe = len(seqs), len(prog["d_ids"])
        k, topk = mcfg.experts_per_tok, mcfg.index_topk
        index_eps = float(tol["index_eps"])
        eps = float(tol["score_eps"])
        lim = float(tol["logit_ulps_bf16"])

        def sets_of(s, j):          # slot s's packed sets, full layer j
            return np.concatenate([prog["p_sets"][row_of[s]][j],
                                   prog["d_sets"][s][j]])

        def routed_of(s, j):        # its experts and choice scores
            r = row_of[s]
            return (np.concatenate([prog["p_ids"][r][j],
                                    prog["d_ids"][j][s]]),
                    np.concatenate([prog["p_choice"][r][j],
                                    prog["d_choice"][j][s]]))

        # the distinct slot sequences: a slot that emitted, chose and
        # routed as another did is judged with it
        groups = {}
        for s in range(n_slots):
            key = hashlib.sha256(seqs[s].tobytes())
            for j in range(len(full)):
                key.update(sets_of(s, j).tobytes())
            for j in range(n_moe):
                key.update(routed_of(s, j)[0].tobytes())
            groups.setdefault(key.digest(), []).append(s)
        groups = list(groups.values())
        # one shape a length class (the next power of two): the positions
        # past a sequence's end cannot reach back into it (causal)
        cls = [int(np.ceil(np.log2(len(q)))) for q in seqs]
        pad = {c: max(len(q) for q, c2 in zip(seqs, cls) if c2 == c)
               for c in set(cls)}
        # the checked positions of a slot: the prompt's checked ones and
        # every probed step, one count for every slot
        where_of = [np.concatenate([prog["at"][row_of[s]],
                                    plen[s] + np.arange(2 * kk)])
                    for s in range(n_slots)]
        Q = max(len(w) for w in where_of)

        # ---- the reference, as phases of their own. The streams (4 x n x
        # d float32, 3.2 GB at 32 864 tokens) are a list of blocks of
        # TOKEN_BLOCK positions, one array each, so that ``write`` updates
        # a block in place with nothing beside it but the block; they wait
        # on the host while the attention or the FFN runs (their
        # temporaries would not fit beside them and the weights)
        def precise(fn):
            def run(*a):
                with jax.default_matmul_precision("highest"):
                    return fn(*a)
            return run

        blk = ref.TOKEN_BLOCK
        read = jax.jit(precise(lambda X, hc, g: ref.read(X, hc, g, model)))
        write = jax.jit(precise(ref.write), donate_argnums=(0,))
        attend = {kind: jax.jit(precise(
            lambda h, A, c, a, kind=kind: ref.attention(
                h, A, model, kind, c, a, index_eps)))
            for kind in ("full", "shared")}
        ffn = jax.jit(precise(lambda h, L, f: ref.ffn(h, L, model, f)))
        dense = jax.jit(precise(lambda h, L: ref.ffn(h, L, model)))
        joined = jax.jit(lambda *parts: jnp.concatenate(parts, axis=1))
        streams_at = jax.jit(lambda *parts: jnp.concatenate(parts, axis=2))
        part_of = jax.jit(lambda t, s: jax.lax.dynamic_slice_in_dim(
            t, s, blk, 1))

        def sublayer(Xs, hc, g, run):
            got = [read(X, hc, g) for X in Xs]
            h = joined(*(h_b for h_b, _, _ in got))
            got = [(post, H) for _, post, H in got]     # h's blocks go
            streams = [jax.device_get(X) for X in Xs]
            for X in Xs:
                X.delete()
            out, rec = run(h)
            h.delete()
            Xs = []
            for i, (X, (post, H)) in enumerate(zip(streams, got)):
                n_b = X.shape[2]
                o = (part_of(out, i * blk) if n_b == blk
                     else out[:, i * blk:i * blk + n_b])
                Xs.append(write(jax.device_put(X), o, post, H))
            return Xs, rec

        def layer(i, L, Xs, f, c, a):
            A = {n: v for n, v in L.items()
                 if n not in ("moe", "wg", "wu", "wd")}
            Xs, dsa = sublayer(Xs, L["hc_attn"], L["ln1"]["g"],
                               lambda h: attend[kinds[i]](h, A, c, a))
            Xs, rec = sublayer(Xs, L["hc_ffn"], L["ln2"]["g"],
                               (lambda h: ffn(h, L, f)) if "moe" in L
                               else (lambda h: dense(h, L)))
            if dsa is not None:
                rec["dsa"] = dsa
            return Xs, rec

        embed = jax.jit(lambda p, t: ref.embed(p, t, model))
        head_fn = jax.jit(lambda p, X: ref.head(p, X, model))
        select = jax.jit(lambda c: ref.select(c, model))
        prog_sets, ref_sets = {}, {}    # (layer, slot, position) -> ...
        worst = 0.0
        wrong_rule = n_differs = token_layers = 0
        offenders = []
        sizes_wrong = outside_all = sel_all = differ_all = 0
        gaps = {"prompt": [], "round": [], "after": []}
        token_gap = 0.0
        prompt_judged = set()
        for members in groups:
            s0 = members[0]
            r, p_s, n_s = int(row_of[s0]), int(plen[s0]), len(seqs[s0])
            n_pad = pad[cls[s0]]
            nb_pad = -(-n_pad // 8)
            toks = np.zeros((1, n_pad), np.int32)
            toks[0, :n_s] = seqs[s0]
            where = np.zeros((Q,), np.int32)
            where[:len(where_of[s0])] = where_of[s0]
            size_due = np.minimum(np.arange(n_s) + 1, topk)
            past = np.arange(n_s) >= topk       # a choice was made there
            Xs = [embed(self.params, jnp.asarray(toks[:, s:s + blk]))
                  for s in range(0, n_pad, blk)]
            j_moe = 0
            for i, L in enumerate(self.params["layers"]):
                c = np.zeros((1, n_pad, nb_pad), np.uint8)
                got_sets = sets_of(s0, full.index(owner[i]))
                c[0, :n_s, :got_sets.shape[1]] = got_sets
                f = None
                if "moe" in L:
                    f = np.full((1, n_pad, k), -1, np.int32)
                    f[0, :n_s] = routed_of(s0, j_moe)[0]
                    f = jnp.asarray(f)
                Xs, rec = layer(i, L, Xs, f, jnp.asarray(c),
                                jnp.asarray(where))
                if "moe" in L:
                    ids, mine_sc = routed_of(s0, j_moe)
                    ref_ids = np.asarray(rec["ids"][0])[:n_s]
                    ref_choice = np.asarray(rec["choice"][0])[:n_s]
                    worst = max(worst, float(np.abs(
                        mine_sc - ref_choice).max()))
                    mine = np.sort(ids, axis=-1)
                    by_rule, margin = (np.asarray(a) for a in select(
                        jnp.asarray(mine_sc)))
                    wrong = (mine != by_rule).any(-1)
                    wrong_rule += int(wrong.sum())
                    offenders += [(j_moe, s0, int(p), float(margin[p]),
                                   mine[p].tolist(), by_rule[p].tolist())
                                  for p in np.nonzero(wrong)[0]][:4]
                    n_differs += int((mine != ref_ids).any(-1).sum())
                    token_layers += n_s
                    j_moe += 1
                if kinds[i] != "full":
                    continue
                j = full.index(i)
                dsa = {n: np.asarray(a[0]) for n, a in rec["dsa"].items()}
                for q, p in enumerate(where_of[s0]):
                    p = int(p)
                    ref_sets[(i, s0, p)] = {
                        "scores": dsa["scores"][q][:n_s],
                        "select": dsa["select"][q][:n_s]}
                    if p >= p_s:
                        t = p - p_s
                        prog_sets[(i, s0, p)] = {
                            "scores": prog["d_scores"][s0][j][t],
                            "select": np.unpackbits(
                                prog["d_sets"][s0][j][t],
                                count=n_s).astype(bool)}
                    else:
                        prog_sets[(i, s0, p)] = prog["p_scores"][r][(i, p)]
                sizes_wrong += int((dsa["size"][:n_s] != size_due).sum())
                outside_all += int(dsa["outside"][:n_s].sum())
                sel_all += int(dsa["size"][:n_s][past].sum())
                differ_all += int(dsa["differs"][:n_s][past].sum())
            # the streams at the checked positions (ascending), a block
            # at a time
            at_blk = [[int(p) - lo for p in where_of[s0]
                       if lo <= p < lo + blk] for lo in range(0, n_pad, blk)]
            X = streams_at(*(X[:, :, np.asarray(a)]
                             for X, a in zip(Xs, at_blk) if a))
            del Xs
            want = np.asarray(head_fn(self.params, X)[0])
            del X
            row = {int(q): w for q, w in zip(where_of[s0], want)}
            p_at, p_lg = prog["p_got"][r]
            if r not in prompt_judged:      # a prompt run, once a row
                prompt_judged.add(r)
                gaps["prompt"].append(_gaps_ulps(
                    p_lg, np.stack([row[int(q)] for q in p_at])).max())
            em_at = p_s - 1 + np.arange(kk + 1)
            w_em = np.stack([row[int(q)] for q in em_at])
            d_want = np.stack([row[p_s + t] for t in range(2 * kk)])
            for s in members:
                g = _gaps_ulps(prog["d_lg"][s], d_want)
                gaps["round"].append(g[:kk].max())
                gaps["after"].append(g[kk:].max())
                top = w_em.max(-1)
                mine = np.take_along_axis(w_em, emitted[s][:, None],
                                          -1)[:, 0]
                token_gap = max(token_gap, float(
                    ((top - mine) / (np.abs(w_em).max(-1) * BF16_ULP)).max()))

        # ---- the rules ----------------------------------------------------
        facts = serve_dsa.judge_selection(prog_sets, ref_sets, topk,
                                          index_eps)
        n_compared = n_slots * 2 * kk + sum(
            len(prog["p_got"][r][0]) for r in prompt_judged)
        by_kind = {kind: float(max(v, default=0.0))
                   for kind, v in gaps.items()}
        token_layers = max(token_layers, 1)
        facts.update(
            index_types=kinds,
            shared_sets_checked=prog["shared_sets"],
            shared_sets_not_their_full_layers=prog["shared_differ"],
            sets_judged_at_every_position=len(full) * sum(
                len(seqs[g[0]]) for g in groups),
            set_sizes_wrong=sizes_wrong,
            positions_outside_the_band_everywhere=outside_all,
            selection_differs_share_everywhere=differ_all / max(sel_all, 1),
            score_gap=worst, score_eps=eps,
            routing_not_by_rule=wrong_rule,
            routing_differs_share=n_differs / token_layers,
            sequences_judged=len(groups),
            compared_positions=n_compared,
            logit_gap_ulps=max(by_kind.values()),
            logit_gap_ulps_by_kind=by_kind,
            emitted_tokens=int(emitted.size),
            emitted_token_gap_ulps=token_gap,
            round_tokens_unlike_probe=prog["round_tokens_unlike_probe"],
            first_tokens_unlike_prompt_run=prog[
                "first_tokens_unlike_prompt_run"],
            act_dtype=str(getattr(ref, "ACT_DTYPE", None)))
        ctx.note(f"reference check (the server's admission and round, a "
                 f"probe on its cache, shared sets bit for bit): {facts} "
                 f"(tolerances: {lim} ulps, emitted tokens {2 * lim}, "
                 f"index score gap {index_eps / 2}, differing selections "
                 f"{tol['selection_differs_share_max']}, router score gap "
                 f"{eps / 2}, differing routing "
                 f"{tol['routing_differs_share_max']}, at least "
                 f"{tol['compared_positions_min']} positions; extend "
                 f"chunks of {prog['widths']} past a bucket of "
                 f"{prog['bucket']}, rounds of {kk})")
        shared_sets, shared_differ = (prog["shared_sets"],
                                      prog["shared_differ"])
        differs_max = float(tol["selection_differs_share_max"])
        limits = [
            (not shared_differ and shared_sets,
             f"{shared_differ} of {shared_sets} shared layers' sets are not "
             f"their full layer's"),
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
            (worst <= eps / 2,
             f"router scores are {worst} from the reference's, over "
             f"score_eps / 2 = {eps / 2}"),
            (not wrong_rule,
             f"{wrong_rule} token-layers chose other experts than the "
             f"reference's selection gives on the program's own scores; as "
             f"(expert layer, slot, position, the reference's margin on "
             f"those scores, the program's set, the rule's): {offenders}"),
            (facts["routing_differs_share"]
             <= float(tol["routing_differs_share_max"]),
             f"{facts['routing_differs_share']} of the token-layers chose "
             f"other experts than the reference's own choice"),
            (n_compared >= int(tol["compared_positions_min"]),
             f"only {n_compared} positions were compared"),
            (facts["logit_gap_ulps"] <= lim,
             f"logit gap {facts['logit_gap_ulps']} exceeds {lim} bf16 ulps "
             f"({by_kind})"),
            (token_gap <= 2 * lim,
             f"a token the server emitted is {token_gap} bf16 ulps under "
             f"the reference's largest logit, over {2 * lim}"),
        ]
        ctx.problems.extend(msg for ok, msg in limits if not ok)
        return facts
