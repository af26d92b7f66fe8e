"""Runner for cells that time one collective of the library across chips.

The program under test is the one the library's facade itself compiles for
``allreduce`` (``rlo_tpu/backend.py``): ``shard_jit`` of
``tc.allreduce(v, "x", algorithm=...)`` over ``make_mesh()``, here called on
arrays that stay on the devices. A unit is ``calls_per_unit`` calls
launched back to back, cycling over the configuration's resident buffers,
blocked on the last; its work is the calls. The functions are named, so the
trace's module events are ``jit_allreduce_<algorithm>``.
"""

from __future__ import annotations

import time

import numpy as np

from lib import kernel_costs
from lib.seeds import seed_key


class Runner:
    def __init__(self, ctx):
        self.ctx = ctx
        self.calls = 0
        self.failed = 0
        self.out = None

    def program(self, algorithm: str, op: str):
        from jax.sharding import PartitionSpec as P
        from rlo_tpu.ops import tpu_collectives as tc
        from rlo_tpu.parallel.mesh import shard_jit

        def fn(v):
            return tc.allreduce(v, "x", op=op, algorithm=algorithm)

        fn.__name__ = fn.__qualname__ = f"allreduce_{algorithm}"
        return shard_jit(fn, self.mesh, P("x"), P("x"))

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from rlo_tpu.parallel.mesh import make_mesh
        from rlo_tpu.utils import hlo
        ctx, tr, cfg = self.ctx, self.ctx.traffic, self.ctx.config
        self.ranks, self.n = int(cfg["ranks"]), int(cfg["elements_per_rank"])
        if ctx.cell["chips"] != self.ranks:
            raise ValueError(f"the cell asks for {ctx.cell['chips']} chips, "
                             f"the configuration has {self.ranks} ranks")
        if cfg["dtype"] != "float32" or cfg["op"] != "sum":
            raise ValueError("only float32 sum is implemented")
        self.mesh = make_mesh((self.ranks,), ("x",))
        sharding = NamedSharding(self.mesh, P("x"))
        n_buf = int(cfg["resident_buffers"])
        shape = (self.ranks, self.n)
        self.pool = list(jax.jit(
            lambda k: tuple(jax.random.normal(jax.random.fold_in(k, i),
                                              shape, jnp.float32)
                            for i in range(n_buf)),
            out_shardings=(sharding,) * n_buf)(seed_key(ctx.seed)))
        self.pool[-1].block_until_ready()
        ctx.part("weights")

        lowered = self.program(tr["algorithm"], cfg["op"]).lower(self.pool[0])
        if ctx.peaks is not None:
            text = lowered.as_text()
            nbytes, n_perm = hlo.permute_total_bytes(text, require=True)
            found = hlo.mosaic_kernels(text)
            ctx.note(f"program: {n_perm} collective permutes of {nbytes} "
                     f"bytes per rank, kernels {found}")
            if not found.get("fused_combine"):
                ctx.problems.append("no fused_combine kernel in the program")
        self.fn = lowered.compile()
        self.ref_fn = self.program(tr["reference_algorithm"],
                                   cfg["op"]).lower(self.pool[0]).compile()
        ctx.part("step_program")

        self.check_reference()
        ctx.part("reference_check")

        self.calls_per_unit = int(tr["calls_per_unit"])
        for i in range(int(tr["warm_calls"])):
            self.fn(self.pool[i % n_buf]).block_until_ready()
            self.ref_fn(self.pool[i % n_buf]).block_until_ready()
        ctx.part("warm_units")
        ctx.facts.update(
            ranks=self.ranks, elements_per_rank=self.n,
            bytes_per_rank=4 * self.n, calls_per_unit=self.calls_per_unit,
            algorithm=tr["algorithm"])

    def sample(self, x) -> np.ndarray:
        """(ranks, n) device array -> every ``sample_stride``-th element of
        each rank's row, on the host."""
        return np.asarray(x[:, ::int(self.ctx.traffic["sample_stride"])])

    def check_reference(self) -> None:
        """The guarantees of the configuration: each rank's result against
        the float64 numpy sum on a strided sample, all ranks equal, and
        max |library - psum| over the whole buffer on the device."""
        import jax
        import jax.numpy as jnp
        ctx = self.ctx
        x = self.pool[0]
        out = self.fn(x)
        x_sample = self.sample(x)
        why = ctx.reference.check(x_sample, self.sample(out))
        # P("x") in and out: row r of `out` is rank r's copy of the sum
        diff = float(jax.jit(lambda a, b: jnp.max(jnp.abs(a - b)))(
            out, self.ref_fn(x)))
        ctx.note(f"reference check: sample of {x_sample.shape[1]} "
                 f"elements per rank against numpy float64: "
                 f"{why or 'equal within rtol 1e-5, atol 1e-4'}; max "
                 f"|{ctx.traffic['algorithm']} - "
                 f"{ctx.traffic['reference_algorithm']}| {diff:.3g}")
        if why:
            ctx.problems.append(f"allreduce result: {why}")
        if not diff <= 1e-4 * 4:
            ctx.problems.append(f"max |library - reference| {diff}")

    def run_calls(self, fn) -> None:
        out = None
        for _ in range(self.calls_per_unit):
            out = fn(self.pool[self.calls % len(self.pool)])
            self.calls += 1
        out.block_until_ready()
        self.out = out

    def unit(self, traced: bool = False) -> int:
        try:
            self.run_calls(self.fn)
        except Exception as e:          # a call that raises is a failed call
            self.failed += 1
            self.ctx.problems.append(f"allreduce raised: {e!r}")
        return self.calls_per_unit

    def quantities(self, window) -> dict:
        per_call = window.elapsed / window.work
        q = {"busbw_GBps": kernel_costs.allreduce_busbw(
                 4 * self.n, self.ranks, per_call) * 1e-9,
             "ms_per_call": per_call * 1e3}
        self.window_calls = int(window.work)
        if self.ctx.trace:
            # the reference schedule on the same buffers, same process
            calls = self.calls
            t0 = time.perf_counter()
            for _ in range(int(self.ctx.traffic["reference_units"])):
                self.run_calls(self.ref_fn)
            per_ref = (time.perf_counter() - t0) / (self.calls - calls)
            q["reference_busbw_GBps"] = kernel_costs.allreduce_busbw(
                4 * self.n, self.ranks, per_ref) * 1e-9
            self.run_calls(self.fn)     # leave the library's result last
        return q

    def finish(self):
        # the last call's result still meets the guarantees
        last_in = self.pool[(self.calls - 1) % len(self.pool)]
        why = self.ctx.reference.check(self.sample(last_in),
                                       self.sample(self.out))
        if why:
            self.ctx.problems.append(f"last call's result: {why}")
        return self.window_calls, self.failed
