"""Runner for cells that serve requests through ``DecodeServer``.

Closed loop: before every ``step_round()`` the queue is topped up to
``n_slots`` pending requests, so every row is always busy. A unit is one
``step_round()``: admission of the rows that retired, one jitted round of
``round_len`` decode steps, tokens on the host. Its work is the useful
tokens the round produced (``serve.tokens_out``).

From the program this file takes ``DecodeServer``, its counters and the
jitted functions it holds; requests, their lengths and the check of every
output are the benchmark's own (lib/traffic.py).
"""

from __future__ import annotations

import numpy as np

from lib import traffic as traffic_lib
from lib.compare import logit_gap_ulps
from lib.seeds import seed_key

#: host spans put around the server's own stages, on the instance: the
#: program is not edited (spans inside it are the tracing issue's)
SPANS = ("_admit", "_prefill", "_extend", "_scatter", "_round",
         "_distribute")


def make_params(seed: int, mcfg):
    """The weights, on the device, in one jitted call from the seed."""
    import jax
    from rlo_tpu.models.transformer import init_params
    return jax.jit(lambda k: init_params(k, mcfg))(seed_key(seed))


def wrap_spans(srv) -> None:
    import jax

    def spanned(name, fn):
        def call(*a, **kw):
            with jax.profiler.TraceAnnotation(f"perf.{name}"):
                return fn(*a, **kw)
        return call

    for attr in SPANS:
        if hasattr(srv, attr):
            setattr(srv, attr, spanned(attr.lstrip("_"), getattr(srv, attr)))


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.sent = {}          # rid -> max_new
        self.n_sent = 0
        self.checked = 0
        self.failed = 0
        self.traced_rounds = []  # (pos, budget, kk) of each traced round
        self.capturing = False

    # ---- set-up ----------------------------------------------------------
    def setup(self) -> None:
        import jax.numpy as jnp
        from rlo_tpu.models.serve import DecodeServer
        from rlo_tpu.models.transformer import TransformerConfig
        from rlo_tpu.utils import hlo
        from rlo_tpu.utils.metrics import Registry
        ctx, tr = self.ctx, self.ctx.traffic
        self.mcfg = mcfg = TransformerConfig(**ctx.config["model"])
        self.params = make_params(ctx.seed, mcfg)
        self.params["embed"].block_until_ready()
        ctx.part("weights")

        self.check_parity()
        ctx.part("reference_check")

        self.reg = Registry()
        srv_kw = dict(tr["server"])
        self.srv = srv = DecodeServer(self.params, mcfg, metrics=self.reg,
                                      **srv_kw)
        self.n_slots = srv.n_slots
        round_jit = srv._round
        if ctx.trace:
            self.capture_rounds(srv)
            wrap_spans(srv)
        self.stream = traffic_lib.ordered(tr["requests"], ctx.seed)
        self.cuts = (traffic_lib.stationary_cut(
            tr["requests"], ctx.seed, self.n_slots)
            if tr.get("stationary_start") else [])
        for _ in range(int(tr["warm_units"])):
            self.unit()
        if self.failed:
            ctx.problems.append(f"{self.failed} requests failed in warm-up")
        self.checked = self.failed = 0
        ctx.part("warm_units")

        if ctx.peaks is not None:   # on the chip: the kernels by name
            i32 = jnp.int32
            slots = jnp.zeros((self.n_slots,), i32)
            text = round_jit.lower(self.params, srv.cache, slots, slots,
                                    kk=srv.round_len).as_text()
            found = hlo.mosaic_kernels(text)
            for name in ("flash_decode", "write_kv_row"):
                if not found.get(name):
                    ctx.problems.append(
                        f"kernel {name} is not in the round's program "
                        f"(found {found})")
            ctx.note(f"kernels in the round's program: {found}")
            ctx.part("kernel_check")
        ctx.facts.update(
            n_slots=self.n_slots, round_len=srv.round_len,
            n_layers=mcfg.n_layers, n_heads=mcfg.n_heads,
            kv_heads=mcfg.kv_heads, head_dim=mcfg.head_dim)
        self.base = self.counters()

    def check_parity(self) -> None:
        """The serving path's logits (bucket-padded ragged prefill, then
        decode steps through the cache) against the plain float32
        reference on two seeded sequences, at the configuration's width."""
        import jax
        import jax.numpy as jnp
        from rlo_tpu.models.generate import (decode_step, init_kv_cache,
                                             prefill)
        ctx, mcfg = self.ctx, self.mcfg
        par = ctx.traffic["parity"]
        plens, bucket = list(par["prompt_lens"]), int(par["bucket"])
        steps = int(par["decode_steps"])
        max_len = int(ctx.traffic["server"]["max_len"])
        total = max(plens) + steps
        toks = np.stack([traffic_lib.token_ids(ctx.seed, 10_000 + r, total,
                                               mcfg.vocab)
                         for r in range(len(plens))])
        want = jax.jit(lambda p, t: ctx.reference.logits(
            p, t, ctx.config["model"]))(self.params, jnp.asarray(toks))
        n = jnp.asarray(plens, jnp.int32)
        prompt = jnp.asarray(toks[:, :bucket]) * (
            jnp.arange(bucket)[None, :] < n[:, None])
        cache = init_kv_cache(mcfg, len(plens), max_len)
        lg, cache = jax.jit(lambda p, t, c, m: prefill(
            p, t, c, mcfg, last_index=m - 1))(self.params, prompt, cache, n)
        rows = np.arange(len(plens))
        gaps = {"prefill": float(logit_gap_ulps(
            lg, want[rows, np.array(plens) - 1]))}
        step = jax.jit(lambda p, t, m, c: decode_step(p, t, m, c, mcfg))
        worst = 0.0
        for s in range(steps):
            pos = np.array(plens) + s
            lg, cache = step(self.params, jnp.asarray(toks[rows, pos]),
                             jnp.asarray(pos, jnp.int32), cache)
            worst = max(worst, float(logit_gap_ulps(lg, want[rows, pos])))
        gaps["decode_steps"] = worst
        tol = float(ctx.config["tolerance"]["logit_ulps_bf16"])
        ctx.note(f"reference check: max |logit gap| in bf16 ulps of the "
                 f"largest reference logit {gaps} (tolerance {tol})")
        ctx.facts["logit_gap_ulps"] = gaps
        if not all(g <= tol for g in gaps.values()):
            ctx.problems.append(f"logit gaps {gaps} exceed {tol} bf16 ulps")

    # ---- the loop --------------------------------------------------------
    def top_up(self) -> None:
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

    def capture_rounds(self, srv) -> None:
        """In a traced unit, keep each round's live contexts as the server
        hands them to its jitted round (after admission): flash_decode's
        needed bytes are computed from them."""
        inner = srv._round

        def call(params, cache, last_tok, pos, kk):
            if self.capturing:
                self.traced_rounds.append(
                    (srv.pos.copy(), srv.budget.copy(), int(kk)))
            return inner(params, cache, last_tok, pos, kk)

        srv._round = call

    def unit(self, traced: bool = False) -> int:
        before = self.reg.counter("serve.tokens_out").value
        self.capturing = traced
        self.top_up()
        self.srv.step_round()
        self.harvest()
        return self.reg.counter("serve.tokens_out").value - before

    def harvest(self) -> None:
        vocab = self.mcfg.vocab
        for rid, toks in self.srv.poll_completed():
            self.checked += 1
            want = self.sent.pop(rid)
            if (toks.shape != (want,) or toks.min() < 0
                    or toks.max() >= vocab):
                self.failed += 1

    def counters(self) -> dict:
        """The program's counters, and the admissions so far: every request
        sent is either still queued or was admitted."""
        return {**self.reg.snapshot()["counters"],
                "perf.admitted": self.n_sent - self.srv.queue_depth()}

    # ---- results ---------------------------------------------------------
    def quantities(self, window) -> dict:
        now = self.counters()
        delta = {k: now[k] - self.base.get(k, 0) for k in now}
        self.ctx.counters = delta
        return {"tokens_per_s": window.work / window.elapsed,
                "requests_completed": delta.get(
                    "serve.requests_completed", 0)}

    def finish(self):
        self.ctx.facts["traced_rounds"] = self.traced_rounds
        if self.checked == 0:
            self.ctx.problems.append("no request completed")
        return self.checked, self.failed
