"""Runner for cells that train on one chip with ``train_step_optax``.

A unit is ``steps_per_unit`` optimizer steps launched back to back without
reading the loss, blocked on the last one; its work is the tokens of those
steps. Losses stay on the device until the window is over. The step is the
program's ``train_step_optax`` under the benchmark's own ``jax.jit``, which
donates parameters and optimizer state as a training loop does; it is a
named function, so the trace's module event is ``jit_train_step_optax``.
"""

from __future__ import annotations

import math

import numpy as np

from lib import kernel_costs
from lib import traffic as traffic_lib
from lib.compare import logit_gap_ulps
from lib.seeds import seed_key


def make_optimizer(spec: dict):
    import optax
    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    return optax.adamw(spec["learning_rate"], b1=spec["b1"], b2=spec["b2"],
                       weight_decay=spec["weight_decay"])


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.losses = []        # device scalars, read after the window
        self.n_steps = 0

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from rlo_tpu.models import transformer as T
        from rlo_tpu.utils import hlo
        ctx, tr = self.ctx, self.ctx.traffic
        self.mcfg = mcfg = T.TransformerConfig(**ctx.config["model"])
        self.batch, self.seq = int(tr["batch"]), int(tr["seq"])
        if self.seq > int(ctx.config["n_positions"]):
            raise ValueError("the mix's sequences exceed the model's "
                             "n_positions")
        key = seed_key(ctx.seed)
        params = jax.jit(lambda k: T.init_params(k, mcfg))(key)
        self.n_params = sum(int(a.size) for a in jax.tree.leaves(params))
        params["embed"].block_until_ready()
        ctx.part("weights")

        self.check_parity(params)
        ctx.part("reference_check")

        optimizer = make_optimizer(tr["optimizer"])
        opt_state = jax.jit(optimizer.init)(params)
        n_pool = int(tr["pool_batches"])
        self.pool = jax.jit(lambda k: jax.random.randint(
            k, (n_pool, self.batch, self.seq), 0, mcfg.vocab, jnp.int32))(
            jax.random.fold_in(key, 1))
        self.pool = [self.pool[i] for i in range(n_pool)]

        def train_step_optax(p, s, t):
            return T.train_step_optax(p, s, t, mcfg, optimizer)

        lowered = jax.jit(train_step_optax, donate_argnums=(0, 1)).lower(
            params, opt_state, self.pool[0])
        if ctx.peaks is not None:   # on the chip: the kernels by name
            found = hlo.mosaic_kernels(lowered.as_text())
            for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
                if found.get(name, 0) != mcfg.n_layers:
                    ctx.problems.append(
                        f"kernel {name}: {found.get(name, 0)} call sites in "
                        f"the step's program, {mcfg.n_layers} layers")
            ctx.note(f"kernels in the step's program: {found}")
        self.step = lowered.compile()
        self.state = (params, opt_state)
        del params, opt_state
        ctx.part("step_program")

        self.steps_per_unit = int(tr["steps_per_unit"])
        for _ in range(int(tr["warm_units"])):
            self.unit()
        self.first_loss = float(self.losses[0])
        self.losses.clear()
        self.n_steps = 0
        ctx.part("warm_units")
        ctx.facts.update(
            n_params=self.n_params, batch=self.batch, seq=self.seq,
            n_layers=mcfg.n_layers, n_heads=mcfg.n_heads,
            head_dim=mcfg.head_dim, d_model=mcfg.d_model,
            flops_per_token=kernel_costs.train_flops_per_token(
                self.n_params, mcfg.n_layers, mcfg.d_model, self.seq))

    def check_parity(self, params) -> None:
        """The training forward's logits (flash forward kernel) and the
        program's loss against the plain float32 reference, on seeded
        sequences of the mix's length."""
        import jax
        import jax.numpy as jnp
        from rlo_tpu.models.transformer import forward, loss_fn
        ctx, mcfg = self.ctx, self.mcfg
        n = int(ctx.traffic["parity"]["sequences"])
        toks = jnp.asarray(np.stack([traffic_lib.token_ids(
            ctx.seed, 20_000 + r, self.seq, mcfg.vocab) for r in range(n)]))
        model = ctx.config["model"]

        def gap(p, t):
            return (logit_gap_ulps(forward(p, t, mcfg),
                                   ctx.reference.logits(p, t, model)),
                    loss_fn(p, t, mcfg), ctx.reference.loss(p, t, model))

        ulps, loss, want_loss = (float(x) for x in
                                 jax.jit(gap)(params, toks))
        tol = ctx.config["tolerance"]
        ctx.note(f"reference check: forward logits {ulps:.2f} bf16 ulps of "
                 f"the largest reference logit (tolerance "
                 f"{tol['logit_ulps_bf16']}); loss {loss:.5f} vs reference "
                 f"{want_loss:.5f}")
        ctx.facts["logit_gap_ulps"] = {"forward": ulps}
        if not ulps <= tol["logit_ulps_bf16"]:
            ctx.problems.append(f"forward logits {ulps} bf16 ulps from the "
                                f"reference")
        if not abs(loss - want_loss) <= tol["loss_rel"] * abs(want_loss):
            ctx.problems.append(f"loss {loss} against reference {want_loss}")

    def unit(self, traced: bool = False) -> int:
        params, opt_state = self.state
        for _ in range(self.steps_per_unit):
            tokens = self.pool[self.n_steps % len(self.pool)]
            params, opt_state, loss = self.step(params, opt_state, tokens)
            self.losses.append(loss)
            self.n_steps += 1
        self.state = (params, opt_state)
        loss.block_until_ready()
        return self.steps_per_unit * self.batch * self.seq

    def quantities(self, window) -> dict:
        self.window_losses = [float(x) for x in self.losses]
        return {"tokens_per_s": window.work / window.elapsed,
                "steps": len(self.window_losses)}

    def finish(self):
        losses = self.window_losses
        failed = sum(1 for x in losses if not math.isfinite(x))
        last = losses[-self.steps_per_unit:]
        mean_last = sum(last) / len(last)
        self.ctx.note(f"loss: first warm-up step {self.first_loss:.4f}, "
                      f"window first {losses[0]:.4f} last unit mean "
                      f"{mean_last:.4f}")
        if not mean_last < self.first_loss:
            self.ctx.problems.append(
                f"the last unit's mean loss {mean_last} is not under the "
                f"first warm-up step's {self.first_loss}")
        return len(losses), failed
