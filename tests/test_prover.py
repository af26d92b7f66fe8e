"""rlo-prover self-verification + oracle cross-check
(docs/DESIGN.md §16).

Mirror of tests/test_lint.py / test_sentinel.py's two-halves pattern,
plus a third half unique to the prover:

  1. The clean-tree contract: ``run_prover`` on this checkout reports
     zero findings — every committed schedule is a valid, delivering
     CollectivePermute program and every Pallas kernel's geometry is
     legal, in tier-1, on every run.

  2. Mutation fixtures: for each rule family P1–P5 a temp copy of the
     tree is seeded with exactly one violation and the prover must
     trip with the right rule ID — a rule that never fires is
     indistinguishable from no rule.  The S0 integration fixture
     proves a stale ``rlo-prover:`` anchor is flagged by
     rlo-sentinel's shared stale-anchor audit.

  3. Oracle cross-check: the prover's symbolic schedule simulator is
     pinned against REAL executors on tiny meshes (n in {2, 3, 4, 8},
     every bcast origin) so the symbolic model cannot silently diverge
     from what ships — a numpy executor that replays the committed
     topology schedules with the exact per-round update semantics of
     ``tpu_collectives.rootless_bcast``, the engine-substrate ring
     collectives over the loopback transport (``ops.collectives``,
     which shares ``ring_reduce_scatter_chunk`` with the TPU
     lowering), and — where this jax build exposes ``jax.shard_map``
     — the lowered collectives themselves on a virtual CPU mesh.
"""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from rlo_tpu import topology
from rlo_tpu.tools.rlo_prover import (run_prover, simulate_bcast,
                                      simulate_doubling_all_gather,
                                      simulate_halving_reduce_scatter,
                                      simulate_rd_allreduce,
                                      simulate_ring_allreduce)

REPO_ROOT = Path(__file__).resolve().parents[1]

_IGNORE = shutil.ignore_patterns(
    "__pycache__", ".pytest_cache", "*.so", "*.o", "*.pyc",
    "rlo_selftest*", "rlo_demo", "rlo_demo_mpi", "rlo_demo_tsan",
    "rlo_demo_asan", "femtompirun")

ORACLE_NS = [2, 3, 4, 8]


@pytest.fixture()
def tree(tmp_path):
    """An analyzable copy of the source tree (sources only) that
    fixtures may mutate freely."""
    shutil.copytree(REPO_ROOT / "rlo_tpu", tmp_path / "rlo_tpu",
                    ignore=_IGNORE)
    return tmp_path


def mutate(root: Path, rel: str, old: str, new: str) -> int:
    """Replace ``old`` (must occur exactly once) with ``new``; returns
    the 1-indexed line of the edit."""
    path = root / rel
    text = path.read_text()
    assert text.count(old) == 1, \
        f"fixture drift: {old!r} occurs {text.count(old)}x in {rel}"
    line = text[:text.index(old)].count("\n") + 1
    path.write_text(text.replace(old, new))
    return line


def findings_for(root: Path, rule: str):
    return [f for f in run_prover(root) if f.rule == rule]


# ---------------------------------------------------------------------------
# 1. clean tree
# ---------------------------------------------------------------------------

def test_head_is_clean():
    """Zero findings on this checkout — the tier-1 drift gate."""
    findings = run_prover(REPO_ROOT)
    assert findings == [], "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# 2. one seeded violation per rule family
# ---------------------------------------------------------------------------

def test_p1_fires_on_dst_collision(tree):
    """Collapsing the binomial round's dst formula makes every rank of
    a round deliver into one dst — the CollectivePermute contract the
    schedule compiles onto forbids it."""
    mutate(tree, "rlo_tpu/topology.py",
           "(((r + origin) % world_size), "
           "((r + step + origin) % world_size))",
           "(((r + origin) % world_size), "
           "((step + origin) % world_size))")
    hits = findings_for(tree, "P1")
    assert any(f.file == "rlo_tpu/topology.py" and
               "collision" in f.msg and "binomial" in f.msg
               for f in hits), hits


def test_p2_fires_on_dropped_contribution(tree):
    """Truncating one pair from every recursive-doubling round drops a
    rank's contribution from the other subcube — the token algebra
    catches the incomplete final multiset."""
    mutate(tree, "rlo_tpu/topology.py",
           "        rounds.append(xor_perm(world_size, 1 << i))",
           "        rounds.append(xor_perm(world_size, 1 << i)[:-1])")
    hits = findings_for(tree, "P2")
    assert any("recursive_doubling" in f.msg and
               ("dropped" in f.msg or "no partner" in f.msg)
               for f in hits), hits


def test_p2_fires_on_chunk_misalignment(tree):
    """Skewing ring_reduce_scatter_chunk by one step makes senders and
    receivers disagree about which chunk is in flight."""
    mutate(tree, "rlo_tpu/topology.py",
           "    return (rank - step) % world_size",
           "    return (rank - step - 1) % world_size")
    hits = findings_for(tree, "P2")
    assert any("misalignment" in f.msg or "double-count" in f.msg
               for f in hits), hits


def test_p3_fires_on_missized_blockspec(tree):
    """A 100-lane pool block is neither the whole page nor a 128-lane
    multiple — Mosaic would reject or silently pad the tiling."""
    mutate(tree, "rlo_tpu/pallas/decode.py",
           "            pl.BlockSpec((1, nkv, d, ps),\n"
           "                         lambda i, page_ref, off_ref, "
           "nv_ref: (\n"
           "                             page_ref[0], 0, 0, 0)),",
           "            pl.BlockSpec((1, nkv, d, 100),\n"
           "                         lambda i, page_ref, off_ref, "
           "nv_ref: (\n"
           "                             page_ref[0], 0, 0, 0)),")
    hits = findings_for(tree, "P3")
    assert any(f.file == "rlo_tpu/pallas/decode.py" and
               "lane dim 100" in f.msg for f in hits), hits


def test_p3_fires_on_unclamped_scalar_index(tree):
    """Dropping the jnp.minimum clamp in write_kv_row's block
    index_map lets a retired slot's out-of-range position select an
    illegal cache block — the hostile scalar-prefetch probe catches
    it."""
    mutate(tree, "rlo_tpu/pallas/decode.py",
           "            pl.BlockSpec((1, nkv, d, 128),\n"
           "                         lambda ib, pos_ref, _n=L // 128: (\n"
           "                             ib, 0, 0,\n"
           "                             jnp.minimum(pos_ref[ib] // 128,\n"
           "                                         _n - 1))),",
           "            pl.BlockSpec((1, nkv, d, 128),\n"
           "                         lambda ib, pos_ref, _n=L // 128: (\n"
           "                             ib, 0, 0,\n"
           "                             pos_ref[ib] // 128)),")
    hits = findings_for(tree, "P3")
    assert any("out of range" in f.msg and "write_kv_row" in f.msg
               for f in hits), hits


# flash_block_decode's K and V are pl.ANY operands: no block, no index
# map. P3 walks the kernel's own fetch (_ring_fetch) over the probes'
# work lists with the module's _ring_span / _copy_lanes / _copy_sizes.
RING_MUTATIONS = {
    # the refill's bound dropped: the last steps name steps past n_work
    "look-ahead-past-the-list": (
        "            xp.minimum(i + n_slots, n_work))",
        "            i + n_slots)",
        "past the work list"),
    # the refill one step further ahead: step N's copy is never
    # started, and its wait would never end
    "a-step-nobody-fetches": (
        "    return (xp.where(i == 0, 0, i + (n_slots - 1)),",
        "    return (xp.where(i == 0, 0, i + n_slots),",
        "no step has started"),
    # the refill one step early: it lands in the slot of the step
    # being computed, a copy started twice
    "refill-into-a-live-slot": (
        "    return (xp.where(i == 0, 0, i + (n_slots - 1)),",
        "    return (xp.where(i == 0, 0, i + (n_slots - 2)),",
        "started twice or never"),
    # a row's last tile copied one granule short of nothing held: the
    # size is none of the static copies, so no copy starts
    "a-size-the-kernel-holds-no-copy-for": (
        "    return xp.minimum(xp.minimum(lanes, bk), "
        "max_len - tile * bk)",
        "    return xp.minimum(lanes, bk) + 64",
        "static sizes"),
    # the copy no longer stops where the tile does
    "copy-past-the-cache": (
        "    return xp.minimum(xp.minimum(lanes, bk), "
        "max_len - tile * bk)",
        "    return lanes + bk",
        "static sizes"),
    # a retired slot's pos past max_len, unclipped: tiles that are not
    # there
    "tile-past-max-len": (
        "    return jnp.clip((pos + (T - 1)) // bk, 0, n_k - 1)",
        "    return (pos + (T - 1)) // bk",
        "flash_block_decode"),
}


@pytest.mark.parametrize("name", sorted(RING_MUTATIONS))
def test_p3_grounds_the_kernels_own_copies(tree, name):
    old, new, want = RING_MUTATIONS[name]
    mutate(tree, "rlo_tpu/pallas/decode.py", old, new)
    hits = findings_for(tree, "P3")
    assert any(f.file == "rlo_tpu/pallas/decode.py" and want in f.msg
               and "flash_block_decode" in f.msg for f in hits), hits


def test_p3_refuses_an_unblocked_operand_it_cannot_ground(tree):
    """A pl.ANY operand is proven or reported, never skipped: without
    the kernel's static tile width the copies cannot be walked."""
    mutate(tree, "rlo_tpu/pallas/decode.py",
           "                          bk=bk, max_len=L, quant=quant, r=r, "
           "T=T,",
           "                          max_len=L, quant=quant, r=r, T=T,")
    hits = findings_for(tree, "P3")
    assert any("did not ground" in f.msg and "pl.ANY" in f.msg
               for f in hits), hits


def test_p4_fires_on_hardcoded_axis(tree):
    """A literal axis name in a per-shard collective drifts silently
    when the mesh is renamed — it must flow from a parameter."""
    mutate(tree, "rlo_tpu/ops/ring_attention.py",
           "            kc = lax.ppermute(kc, axis, perm)\n"
           "            vc = lax.ppermute(vc, axis, perm)",
           "            kc = lax.ppermute(kc, \"ring\", perm)\n"
           "            vc = lax.ppermute(vc, axis, perm)")
    hits = findings_for(tree, "P4")
    assert any(f.file == "rlo_tpu/ops/ring_attention.py" and
               "'ring'" in f.msg for f in hits), hits


def test_p4_axis_ok_anchor_suppresses(tree):
    """The same literal, anchored, is sanctioned — and consumed, so
    the S0 audit stays quiet too."""
    mutate(tree, "rlo_tpu/ops/ring_attention.py",
           "            kc = lax.ppermute(kc, axis, perm)\n"
           "            vc = lax.ppermute(vc, axis, perm)",
           "            # rlo-prover: axis-ok fixture-sanctioned\n"
           "            kc = lax.ppermute(kc, \"ring\", perm)\n"
           "            vc = lax.ppermute(vc, axis, perm)")
    assert findings_for(tree, "P4") == []
    from rlo_tpu.tools.rlo_sentinel import run_sentinel
    assert [f for f in run_sentinel(tree) if f.rule == "S0"] == []


def test_p5_fires_on_drifted_page_size(tree):
    """A 64-token default page drifts from the 128-lane device page
    contract the kernels and the pool layout assume."""
    mutate(tree, "rlo_tpu/models/serve.py",
           "paged: bool = False, page_size: int = 128,",
           "paged: bool = False, page_size: int = 64,")
    hits = findings_for(tree, "P5")
    assert any(f.file == "rlo_tpu/models/serve.py" and
               "page_size default = 64" in f.msg for f in hits), hits


def test_s0_fires_on_stale_prover_anchor(tree):
    """The shared anchor grammar: an rlo-prover anchor nothing
    consumes is flagged by rlo-sentinel's S0 audit (satellite of the
    single-namespace design in tools/runner.py)."""
    from rlo_tpu.tools.rlo_sentinel import run_sentinel
    mutate(tree, "rlo_tpu/ops/ring_attention.py",
           "    ws = lax.axis_size(axis)\n"
           "    idx = lax.axis_index(axis)\n"
           "    blk, h, d = q.shape",
           "    # rlo-prover: axis-ok suppresses nothing here\n"
           "    ws = lax.axis_size(axis)\n"
           "    idx = lax.axis_index(axis)\n"
           "    blk, h, d = q.shape")
    hits = [f for f in run_sentinel(tree) if f.rule == "S0"]
    assert any("rlo-prover: axis-ok" in f.msg and "stale" in f.msg
               for f in hits), hits


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

def test_cli_exit_codes_and_json(tree):
    mutate(tree, "rlo_tpu/models/serve.py",
           "paged: bool = False, page_size: int = 128,",
           "paged: bool = False, page_size: int = 64,")
    proc = subprocess.run(
        [sys.executable, "-m", "rlo_tpu.tools.rlo_prover",
         "--root", str(tree)],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "P5" in proc.stdout
    # findings print as file:line: diagnostics (the check.sh contract)
    assert any(ln.split(":")[0].endswith(".py") and
               ln.split(":")[1].isdigit()
               for ln in proc.stdout.splitlines() if "P5" in ln)
    # machine-readable output carries the same findings
    proc = subprocess.run(
        [sys.executable, "-m", "rlo_tpu.tools.rlo_prover",
         "--root", str(tree), "--json"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 1
    data = json.loads(proc.stdout)
    assert any(d["rule"] == "P5" and d["line"] > 0 and
               d["severity"] == "error" for d in data), data
    # rule selection: a family that is still clean exits 0
    proc = subprocess.run(
        [sys.executable, "-m", "rlo_tpu.tools.rlo_prover",
         "--root", str(tree), "--rules", "P1,P2"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_clean_head_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "rlo_tpu.tools.rlo_prover"],
        capture_output=True, text=True, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# 3. oracle cross-check: symbolic model vs real executors
# ---------------------------------------------------------------------------

def _np_exec_bcast(rounds, xs):
    """Replay a bcast schedule on concrete per-rank values with the
    exact round semantics of tpu_collectives.rootless_bcast: one
    ppermute per round, every round-dst takes the permuted value."""
    xs = list(xs)
    for rnd in rounds:
        old = list(xs)
        for src, dst in rnd:
            xs[dst] = old[src]
    return xs


@pytest.mark.parametrize("n", ORACLE_NS)
@pytest.mark.parametrize("schedule", ["binomial_bcast_schedule",
                                      "skip_ring_bcast_schedule"])
def test_oracle_bcast_every_origin(n, schedule):
    """The symbolic token state maps 1:1 onto a concrete replay of the
    same schedule, for every origin."""
    gen = getattr(topology, schedule)
    for origin in range(n):
        rounds = gen(n, origin).rounds
        tok = simulate_bcast(rounds, n)
        xs = [float(100 + r) for r in range(n)]
        got = _np_exec_bcast(rounds, xs)
        assert got == [xs[t] for t in tok]
        assert tok == [origin] * n  # and the model says it delivers


@pytest.mark.parametrize("n", ORACLE_NS)
def test_oracle_ring_allreduce_matches_loopback_engine(n):
    """The symbolic ring model's claimed contribution sets translate
    to the numbers the REAL engine-substrate ring (ops.collectives
    over the loopback transport — same ring_reduce_scatter_chunk
    schedule as the TPU lowering) actually produces."""
    from rlo_tpu.ops.collectives import Comm, run_collectives
    from rlo_tpu.transport import make_world
    gathered, defects = simulate_ring_allreduce(n, topology)
    assert defects == []
    xs = [np.arange(4, dtype=np.float64) * 0 + 2.0 ** r
          for r in range(n)]
    world, comms = make_world("loopback", n), None
    comms = [Comm(world.transport(r)) for r in range(n)]
    got = run_collectives(
        [c.allreduce(x, algorithm="ring") for c, x in zip(comms, xs)])
    # powers of two make the sum a readable contribution bitmask:
    # sum == mask means exactly-once per rank
    for r in range(n):
        for chunk_mask in gathered[r]:
            assert chunk_mask == (1 << n) - 1
        assert np.allclose(got[r], float((1 << n) - 1))


@pytest.mark.parametrize("n", [2, 4, 8])
def test_oracle_pow2_symbolic_models(n):
    """Recursive-doubling / halving-doubling symbolic results match
    the loopback recursive-doubling executor and numpy sums."""
    from rlo_tpu.ops.collectives import Comm, run_collectives
    from rlo_tpu.transport import make_world
    acc, defects = simulate_rd_allreduce(n, topology)
    assert defects == [] and all(a == (1 << n) - 1 for a in acc)
    owned, defects = simulate_halving_reduce_scatter(n, topology)
    assert defects == []
    assert [c for c, _ in owned] == list(range(n))
    final, defects = simulate_doubling_all_gather(n, owned, topology)
    assert defects == []
    assert all(m == (1 << n) - 1 for row in final for m in row)
    xs = [np.full(3, 2.0 ** r) for r in range(n)]
    world = make_world("loopback", n)
    comms = [Comm(world.transport(r)) for r in range(n)]
    got = run_collectives(
        [c.allreduce(x, algorithm="recursive_doubling")
         for c, x in zip(comms, xs)])
    for r in range(n):
        assert np.allclose(got[r], float((1 << n) - 1))


def _shard_map_gate():
    """None when the lowered-program oracle can run, else the skip
    reason — which must be PROVABLY version-caused.  ``jax.shard_map``
    is a top-level API from jax 0.6 (mesh.shard_jit also needs its
    check_vma typing); on an older pin the skip is legitimate.  On a
    0.6+ jax where the symbol is nonetheless missing something else
    broke, and a silent skip would let the oracle rot invisibly — so
    that case asserts instead of skipping."""
    import jax
    if hasattr(jax, "shard_map"):
        return None
    ver = tuple(int(p) for p in jax.__version__.split(".")[:2])
    assert ver < (0, 6), (
        f"jax {jax.__version__} should expose jax.shard_map but does "
        f"not — the oracle's version gate has rotted; investigate "
        f"instead of skipping")
    return (f"version gate: jax {jax.__version__} < 0.6 has no "
            f"top-level jax.shard_map")


def test_oracle_skip_is_version_caused():
    """The oracle may only ever be skipped BY THE VERSION GATE: when
    the gate returns a reason it names the pinned jax version, and
    when it returns None the oracle genuinely has jax.shard_map."""
    import jax
    reason = _shard_map_gate()
    if reason is None:
        assert hasattr(jax, "shard_map")
    else:
        assert "version gate" in reason and jax.__version__ in reason


@pytest.mark.parametrize("n", [2, 4, 8])
def test_oracle_lowered_collectives_on_cpu_mesh(n):
    """Where this jax build exposes jax.shard_map, pin the symbolic
    model against the ACTUAL lowered program on a virtual CPU mesh."""
    reason = _shard_map_gate()
    if reason is not None:
        pytest.skip(reason)
    import jax
    from jax.sharding import PartitionSpec as P
    from rlo_tpu.ops import tpu_collectives as tc
    from rlo_tpu.parallel.mesh import make_mesh, shard_jit
    mesh = make_mesh((n,), ("x",))
    xs = np.stack([np.full(4, 2.0 ** r, np.float32)
                   for r in range(n)])
    for origin in range(n):
        for schedule in ("binomial", "skip_ring"):
            fn = shard_jit(
                lambda v, o=origin, s=schedule:
                tc.rootless_bcast(v, o, "x", schedule=s),
                mesh, (P("x"),), P("x"))
            got = np.asarray(jax.device_get(fn(xs)))
            gen = (topology.binomial_bcast_schedule
                   if schedule == "binomial"
                   else topology.skip_ring_bcast_schedule)
            tok = simulate_bcast(gen(n, origin).rounds, n)
            want = np.stack([xs[t] for t in tok])
            np.testing.assert_allclose(got, want)
    fn = shard_jit(lambda v: tc.allreduce(v, "x", algorithm="ring"),
                   mesh, (P("x"),), P("x"))
    got = np.asarray(jax.device_get(fn(xs)))
    np.testing.assert_allclose(got,
                               np.broadcast_to(xs.sum(0), got.shape))
