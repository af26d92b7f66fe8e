"""rlo-prover — symbolic collective-schedule verifier + device-layer
geometry lint.

rlo-lint (docs/DESIGN.md §9) pins host-side surface parity and
rlo-sentinel (§15) checks the host/C engines' flow properties; both
leave the DEVICE layer — the precomputed ``ppermute`` schedules in
``rlo_tpu/topology.py``/``ops/tpu_collectives.py`` and the Pallas
kernel geometry in ``rlo_tpu/pallas/`` — unanalyzed.  rlo-prover
closes that gap: it proves, statically and without importing jax or
touching a device, that every committed schedule is a valid
CollectivePermute program that delivers/reduces correctly, and that
every ``pallas_call`` in the package is geometrically legal.  Rule
catalogue (docs/DESIGN.md §16):

  P1 permutation validity — enumerate every committed schedule
     generator (binomial/skip-ring bcast for every origin; ring /
     recursive-doubling / halving-doubling allreduce; ring/halving
     reduce_scatter; ring/doubling all_gather) for all n <= 64 and
     prove each step's (src, dst) pairs form a valid partial
     permutation: the XLA CollectivePermute contract (no src appears
     twice — ppermute cannot multicast — no dst collisions, every
     rank in [0, n)).
  P2 delivery/reduction correctness — a symbolic token algebra over
     the same sweep: broadcast ends with every rank holding the
     origin's token; allreduce ends with every rank's contribution
     set equal to exactly-one-contribution-per-rank (bitmask union
     with overlap detection, so double-counts AND drops are caught);
     reduce_scatter/all_gather shard coverage is exact and in index
     order; chunk identities are tracked end to end so a send/recv
     index misalignment is flagged at the step it happens; and step
     counts are pinned against the documented bounds (binomial =
     ceil(log2 n) rounds, skip-ring <= 2*ceil(log2 n), ring = 2(n-1)
     chunk steps, recursive doubling = log2 n, halving-doubling =
     2 log2 n) so an accidentally-degraded schedule fails
     mechanically.
  P3 Pallas geometry — AST-extract every ``pallas_call`` in
     ``pallas/{decode,flash,reduce}.py`` (grid, BlockSpec block
     shapes, index_maps, out_specs, scalar-prefetch operands,
     input_output_aliases) by symbolically executing the wrapper
     function bodies under committed shape bindings (a mini
     interpreter — nothing is imported), then check: lane-dim
     legality (last block dim a multiple of 128 or the whole axis),
     sublane tiling legality (second-minor a multiple of 8 or the
     whole axis), block rank == operand rank, block <= logical
     shape, index_map arity == grid rank (+ scalar-prefetch refs),
     and every index_map value in range over the ENTIRE grid for
     every operand — including hostile scalar-prefetch values (an
     out-of-range slot position / page id must be clamped to a legal
     block, the paged NULL-page-0 discipline).  Aliased outputs must
     shape-match their input.  An operand with no block and no index
     map (``memory_space=pl.ANY``: flash_block_decode's K and V,
     which the kernel copies for itself) is proven by walking the
     kernel's own fetch over the probe's work list with the module's
     ``_ring_span`` / ``_copy_lanes`` / ``_copy_sizes``: every copy's
     source slice inside the operand, every size one the kernel holds
     a copy for, every step's copy started once, before its wait,
     into a free slot, none past the list.
  P4 shard_map axis discipline — axis names consumed by
     ``lax.ppermute/psum/pmin/...`` or the ``tpu_collectives``
     wrappers inside per-shard code must flow from a parameter, never
     a hard-coded string: a literal drifting from the mesh axis names
     bound in ``parallel/mesh.py``/``backend.py`` compiles a
     collective onto the wrong (or no) axis.  A module that itself
     constructs the mesh (``backend.py``) may use exactly the
     literals it binds via ``make_mesh``; ``# rlo-prover: axis-ok``
     sanctions a deliberate literal elsewhere.
  P5 device-layer constant pinning — the 128-lane page contract
     across the host/device boundary (rlo-lint R1-style pinning):
     pallas/reduce.py ``_LANE``, models/serve.py's TPU default
     ``page_size``, the ``% 128`` page gates in models/paged.py,
     models/serve.py and pallas/decode.py, serving/pages.py
     ``NULL_PAGE == 0`` and the paged write sentinels in
     models/paged.py (inactive slots map page -> NULL_PAGE, offset ->
     ``ps``) must all agree; pinned sites carry a
     ``# rlo-prover: lane-pinned`` anchor consumed by this rule (the
     S0 stale-anchor audit covers the namespace).

Usage:
  python -m rlo_tpu.tools.rlo_prover [--root DIR] [--rules P1,P3]
                                     [--json] [-q]

Exit codes: 0 clean, 1 findings, 2 bad invocation / unparseable
inputs.  The full n <= 64 sweep completes in ~2 s; check.sh runs the
CLI under a hard timeout.  Soundness caveats are documented in
docs/DESIGN.md §16 — chiefly: P3 proves geometry for the committed
shape bindings in ``P3_PROBES`` (representative, hostile-scalar
included), not for all shapes, and P1/P2 verify the schedule
*generators*, not the lowered HLO (tests/test_prover.py's oracle
cross-check pins the symbolic model to a real executor).
"""

from __future__ import annotations

import argparse
import ast
import importlib.util
import itertools
import math
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Set, Tuple

from rlo_tpu.tools.runner import (AnchorRegistry, Finding, ToolError,
                                  emit, find_anchor)

RULE_IDS = ("P1", "P2", "P3", "P4", "P5")

#: schedule sweep bound (every generator, every origin where relevant)
N_MAX = 64

TOPOLOGY_PY = "rlo_tpu/topology.py"
PALLAS_FILES = ("rlo_tpu/pallas/decode.py", "rlo_tpu/pallas/flash.py",
                "rlo_tpu/pallas/reduce.py")
#: per-shard modules whose collective axis names must be parameters
P4_FILES = ("rlo_tpu/ops/tpu_collectives.py",
            "rlo_tpu/ops/ring_attention.py", "rlo_tpu/ops/ulysses.py",
            "rlo_tpu/models/transformer.py", "rlo_tpu/models/moe.py",
            "rlo_tpu/models/pipeline.py", "rlo_tpu/models/generate.py",
            "rlo_tpu/models/kvcache.py",
            "rlo_tpu/parallel/consensus.py", "rlo_tpu/backend.py")
SERVE_PY = "rlo_tpu/models/serve.py"
PAGED_PY = "rlo_tpu/models/paged.py"
PAGES_PY = "rlo_tpu/serving/pages.py"
DECODE_PY = "rlo_tpu/pallas/decode.py"
REDUCE_PY = "rlo_tpu/pallas/reduce.py"

#: the XLA vector-lane width every P5 site must agree on
LANE = 128
#: f32 sublane granularity (Mosaic tiling constraint)
SUBLANE = 8

AXIS_OK_ANCHOR = "rlo-prover: axis-ok"
LANE_PINNED_ANCHOR = "rlo-prover: lane-pinned"


class ProverError(ToolError):
    """Unrecoverable analyzer failure (missing input, unparseable
    source) — exit code 2, distinct from findings."""


# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------

@dataclass
class PyMod:
    path: str
    raw: str
    lines: List[str]
    tree: ast.Module


def _parse_py(root: Path, rel: str) -> PyMod:
    try:
        raw = (root / rel).read_text()
    except OSError as e:
        raise ProverError(f"cannot read {rel}: {e}")
    try:
        tree = ast.parse(raw, filename=rel)
    except SyntaxError as e:
        raise ProverError(f"cannot parse {rel}: {e}")
    return PyMod(path=rel, raw=raw, lines=raw.splitlines(), tree=tree)


_topo_seq = itertools.count()


def load_topology(root: Path):
    """Import ``<root>/rlo_tpu/topology.py`` by path under a unique
    module name, so mutated fixture trees analyze THEIR schedules, not
    this checkout's.  topology.py is stdlib-pure (no jax)."""
    path = Path(root) / TOPOLOGY_PY
    if not path.exists():
        raise ProverError(f"{TOPOLOGY_PY} not found under {root}")
    name = f"_rlo_prover_topology_{next(_topo_seq)}"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclass decorators resolve the module
    try:
        spec.loader.exec_module(mod)
    except Exception as e:
        raise ProverError(f"cannot load {TOPOLOGY_PY}: {e}")
    finally:
        sys.modules.pop(name, None)
    return mod


class ProverContext:
    def __init__(self, root: Path, registry: AnchorRegistry):
        self.root = root
        self.registry = registry
        self.py: Dict[str, PyMod] = {}
        self._topo: object = None
        #: def-line cache for findings anchored at generator functions
        self.topo_lines: Dict[str, int] = {}

    @property
    def topo(self):
        """Loaded lazily: only P1/P2 execute topology.py.  The
        AST-only rules (P3–P5 — and through them the rlo-sentinel S0
        consumption run) stay decoupled from its runtime behavior, so
        a topology.py that fails to import breaks the schedule rules,
        not every analyzer that shares the runner."""
        if self._topo is None:
            self._topo = load_topology(self.root)
        return self._topo

    def mod(self, rel: str) -> PyMod:
        if rel not in self.py:
            self.py[rel] = _parse_py(self.root, rel)
        return self.py[rel]

    def topo_line(self, fn_name: str) -> int:
        if not self.topo_lines:
            for node in self.mod(TOPOLOGY_PY).tree.body:
                if isinstance(node, ast.FunctionDef):
                    self.topo_lines[node.name] = node.lineno
        return self.topo_lines.get(fn_name, 1)


def build_context(root: Path,
                  registry: Optional[AnchorRegistry] = None
                  ) -> ProverContext:
    return ProverContext(
        Path(root).resolve(),
        registry if registry is not None else AnchorRegistry())


# ---------------------------------------------------------------------------
# P1 — permutation validity
# ---------------------------------------------------------------------------

def _check_perm(f: List[Finding], ctx: ProverContext, gen: str,
                pairs: Sequence[Tuple[int, int]], n: int,
                what: str) -> bool:
    """One ppermute step's (src, dst) pairs against the
    CollectivePermute contract.  Returns True when valid."""
    line = ctx.topo_line(gen)
    ok = True
    srcs: Set[int] = set()
    dsts: Set[int] = set()
    for src, dst in pairs:
        if not (0 <= src < n and 0 <= dst < n):
            f.append(Finding("P1", TOPOLOGY_PY, line,
                             f"{what}: edge ({src}, {dst}) out of rank "
                             f"range [0, {n})"))
            ok = False
        if src in srcs:
            f.append(Finding("P1", TOPOLOGY_PY, line,
                             f"{what}: src {src} appears twice — "
                             f"CollectivePermute cannot multicast"))
            ok = False
        if dst in dsts:
            f.append(Finding("P1", TOPOLOGY_PY, line,
                             f"{what}: dst {dst} collision — two "
                             f"sources deliver into one rank in a "
                             f"single permute"))
            ok = False
        srcs.add(src)
        dsts.add(dst)
    return ok


def _bcast_schedules(ctx: ProverContext):
    """(generator-name, n, origin, rounds) for both bcast families
    over the full sweep."""
    t = ctx.topo
    for n in range(2, N_MAX + 1):
        for origin in range(n):
            for gen in ("binomial_bcast_schedule",
                        "skip_ring_bcast_schedule"):
                try:
                    sched = getattr(t, gen)(n, origin)
                except Exception as e:
                    yield gen, n, origin, None, e
                    continue
                yield gen, n, origin, sched.rounds, None


def rule_p1(ctx: ProverContext) -> List[Finding]:
    f: List[Finding] = []
    t = ctx.topo
    seen_bad: Set[str] = set()  # one finding per (gen, defect) class
    per_gen: Dict[str, int] = {}

    def once(key: str, finding: Finding) -> None:
        gen_key = key.split("/", 1)[0]
        if key in seen_bad or per_gen.get(gen_key, 0) >= 10:
            return
        seen_bad.add(key)
        per_gen[gen_key] = per_gen.get(gen_key, 0) + 1
        f.append(finding)

    for gen, n, origin, rounds, err in _bcast_schedules(ctx):
        if err is not None:
            once(f"{gen}/raise", Finding(
                "P1", TOPOLOGY_PY, ctx.topo_line(gen),
                f"{gen}(n={n}, origin={origin}) raised: {err}"))
            continue
        sub: List[Finding] = []
        for i, rnd in enumerate(rounds):
            _check_perm(sub, ctx, gen, rnd, n,
                        f"{gen}(n={n}, origin={origin}) round {i}")
        for fnd in sub:
            once(f"{gen}/{fnd.msg.split(':')[-1][:40]}", fnd)

    def gen(name: str, fn, *args):
        """One generator call; a raise is a P1 finding (the schedule
        cannot be built), never a prover crash — mutated fixture
        trees are a supported input."""
        try:
            return fn(*args)
        except Exception as e:
            once(f"{name}/raise", Finding(
                "P1", TOPOLOGY_PY, ctx.topo_line(name),
                f"{name}{args} raised: {e}"))
            return None

    def checked(gname: str, pairs, n: int, what: str) -> bool:
        """_check_perm funneled through the per-generator once() cap
        (same flood control the bcast path uses)."""
        sub: List[Finding] = []
        ok = _check_perm(sub, ctx, gname, pairs, n, what)
        for fnd in sub:
            once(f"{gname}/{fnd.msg.split(':')[-1][:40]}", fnd)
        return ok

    for n in range(2, N_MAX + 1):
        for off in (1, -1):
            pairs = gen("ring_perm", t.ring_perm, n, off)
            if pairs is not None:
                checked("ring_perm", pairs, n,
                        f"ring_perm(n={n}, offset={off})")
        if gen("is_power_of_2", t.is_power_of_2, n):
            rounds = gen("recursive_doubling_rounds",
                         t.recursive_doubling_rounds, n)
            for i, rnd in enumerate(rounds or ()):
                checked("recursive_doubling_rounds", rnd, n,
                        f"recursive_doubling_rounds(n={n}) round {i}")
            dists = gen("halving_doubling_distances",
                        t.halving_doubling_distances, n)
            for dist in dists or ():
                pairs = gen("xor_perm", t.xor_perm, n, dist)
                if pairs is None:
                    continue
                if checked("xor_perm", pairs, n,
                           f"xor_perm(n={n}, dist={dist})"):
                    # the halving/doubling phases rely on the exchange
                    # being an involution: both directions in one call
                    m = dict(pairs)
                    for a, b in pairs:
                        if m.get(b) != a:
                            once(f"xor_perm/involution", Finding(
                                "P1", TOPOLOGY_PY,
                                ctx.topo_line("xor_perm"),
                                f"xor_perm(n={n}, dist={dist}) is not "
                                f"self-inverse: {a}->{b} but {b}->"
                                f"{m.get(b)}"))
                            break
    return f


# ---------------------------------------------------------------------------
# P2 — delivery / reduction correctness (symbolic token algebra)
# ---------------------------------------------------------------------------

def simulate_bcast(rounds: Sequence[Sequence[Tuple[int, int]]],
                   n: int) -> List[int]:
    """Token state after executing ``rounds`` with the exact per-round
    semantics of ``tpu_collectives.rootless_bcast``: every dst of a
    round unconditionally takes what its src held BEFORE the round.
    Rank r starts holding token r; broadcast is correct iff the final
    state is [origin] * n."""
    tok = list(range(n))
    for rnd in rounds:
        old = list(tok)
        for src, dst in rnd:
            tok[dst] = old[src]
    return tok


def simulate_ring_allreduce(n: int, topo) -> Tuple[
        List[List[int]], List[str]]:
    """Symbolic ring allreduce (reduce-scatter + all-gather) driven by
    the SAME schedule functions the implementation uses
    (``ring_perm``, ``ring_reduce_scatter_chunk``).  State is one
    contribution bitmask per (rank, chunk); merges detect overlap
    (double-count) mechanically.  Returns (final gathered masks per
    rank per chunk, defect strings)."""
    defects: List[str] = []
    full = (1 << n) - 1
    state = [[1 << r for _ in range(n)] for r in range(n)]
    perm = dict(topo.ring_perm(n, 1))  # src -> dst
    recv_from = {d: s for s, d in perm.items()}
    if sorted(recv_from) != list(range(n)):
        # P1 reports the malformed permutation itself; the token
        # algebra cannot run a ring where some rank receives nothing
        defects.append(
            f"ring_perm(n={n}) is not a complete permutation "
            f"(receivers {sorted(recv_from)}) — delivery simulation "
            f"aborted")
        return [], defects
    for s in range(n - 1):
        old = [row[:] for row in state]
        for r in range(n):
            src = recv_from[r]
            send_idx = topo.ring_reduce_scatter_chunk(n, src, s)
            recv_idx = (r - s - 1) % n
            if send_idx != recv_idx:
                defects.append(
                    f"ring RS step {s}: rank {src} sends chunk "
                    f"{send_idx} but rank {r} accumulates into chunk "
                    f"{recv_idx} — chunk misalignment")
                continue
            if old[src][send_idx] & old[r][recv_idx]:
                defects.append(
                    f"ring RS step {s}: merging chunk {recv_idx} at "
                    f"rank {r} double-counts contributions "
                    f"{old[src][send_idx] & old[r][recv_idx]:#x}")
            state[r][recv_idx] = old[r][recv_idx] | old[src][send_idx]
    for r in range(n):
        own = (r + 1) % n
        if state[r][own] != full:
            defects.append(
                f"ring RS: rank {r} owns chunk {own} with "
                f"contributions {state[r][own]:#x}, expected all "
                f"{n} ranks — dropped contribution")
    # all-gather: rank r carries (chunk_idx, mask), rotates n-1 steps
    out: List[List[Optional[int]]] = [[None] * n for _ in range(n)]
    carry = [((r + 1) % n, state[r][(r + 1) % n]) for r in range(n)]
    for r in range(n):
        out[r][carry[r][0]] = carry[r][1]
    for s in range(n - 1):
        old_c = list(carry)
        for r in range(n):
            idx, mask = old_c[recv_from[r]]
            arr_idx = (r - s) % n
            if idx != arr_idx:
                defects.append(
                    f"ring AG step {s}: rank {r} files arriving chunk "
                    f"{idx} under index {arr_idx}")
            out[r][idx] = mask
            carry[r] = (idx, mask)
    gathered = [[m if m is not None else 0 for m in row] for row in out]
    return gathered, defects


def simulate_rd_allreduce(n: int, topo) -> Tuple[List[int], List[str]]:
    """Recursive doubling: full-vector masks, one exchange per round."""
    defects: List[str] = []
    acc = [1 << r for r in range(n)]
    rounds = topo.recursive_doubling_rounds(n)
    if len(rounds) != n.bit_length() - 1:
        defects.append(
            f"recursive doubling at n={n}: {len(rounds)} rounds, "
            f"documented bound is log2(n) = {n.bit_length() - 1}")
    for i, rnd in enumerate(rounds):
        m = dict(rnd)
        old = list(acc)
        for r in range(n):
            if r not in m:
                defects.append(
                    f"recursive doubling round {i}: rank {r} has no "
                    f"partner — its contribution is dropped from the "
                    f"other subcube")
                continue
            p = m[r]
            if old[r] & old[p]:
                defects.append(
                    f"recursive doubling round {i}: ranks {r}<->{p} "
                    f"merge overlapping contribution sets — "
                    f"double-count")
            acc[r] = old[r] | old[p]
    return acc, defects


def simulate_halving_reduce_scatter(n: int, topo) -> Tuple[
        List[Tuple[int, int]], List[str]]:
    """Recursive-halving reduce-scatter: per rank a shrinking run of
    (global chunk, mask) rows.  Returns each rank's final (chunk,
    mask) and defect strings."""
    defects: List[str] = []
    rows = {r: [(c, 1 << r) for c in range(n)] for r in range(n)}
    dists = list(topo.halving_doubling_distances(n))
    if dists != [n >> k for k in range(1, n.bit_length())]:
        defects.append(
            f"halving_doubling_distances(n={n}) = {dists}, expected "
            f"{[n >> k for k in range(1, n.bit_length())]} — the "
            f"log2(n)-round bound is broken")
    for dist in dists:
        new = {}
        for r in range(n):
            p = r ^ dist
            cur, pcur = rows[r], rows[p]
            if len(cur) != 2 * dist:
                defects.append(
                    f"halving RS dist {dist}: rank {r} holds "
                    f"{len(cur)} rows, expected {2 * dist}")
                return [], defects
            upper = (r & dist) != 0
            keep = cur[dist:] if upper else cur[:dist]
            # partner sends the half of ITS range that my subtree owns
            psend = pcur[dist:] if upper else pcur[:dist]
            merged = []
            for (c1, m1), (c2, m2) in zip(keep, psend):
                if c1 != c2:
                    defects.append(
                        f"halving RS dist {dist}: rank {r} combines "
                        f"chunk {c1} with partner chunk {c2} — "
                        f"misaligned exchange")
                if m1 & m2:
                    defects.append(
                        f"halving RS dist {dist}: rank {r} chunk {c1} "
                        f"double-counts {m1 & m2:#x}")
                merged.append((c1, m1 | m2))
            new[r] = merged
        rows = new
    out = []
    for r in range(n):
        if len(rows[r]) != 1:
            defects.append(f"halving RS: rank {r} ends with "
                           f"{len(rows[r])} chunks, expected 1")
            out.append((-1, 0))
        else:
            out.append(rows[r][0])
    return out, defects


def simulate_doubling_all_gather(n: int, start: List[Tuple[int, int]],
                                 topo) -> Tuple[List[List[int]],
                                                List[str]]:
    """Recursive-doubling all-gather from per-rank (chunk, mask)."""
    defects: List[str] = []
    out: List[List[Optional[Tuple[int, int]]]] = \
        [[None] * n for _ in range(n)]
    for r, (c, m) in enumerate(start):
        if 0 <= c < n:
            out[r][c] = (c, m)
    for dist in reversed(list(topo.halving_doubling_distances(n))):
        snapshot = [list(row) for row in out]
        for r in range(n):
            p = r ^ dist
            # partner's assembled block of `dist` rows lands at my
            # block start XOR dist (== the partner's block start)
            p_start = (p // dist) * dist
            blk = snapshot[p][p_start:p_start + dist]
            dst = (r // dist) * dist ^ dist
            for i, cell in enumerate(blk):
                if cell is None:
                    defects.append(
                        f"doubling AG dist {dist}: rank {r} receives "
                        f"an unassembled slot from rank {p}")
                    continue
                out[r][dst + i] = cell
    final = []
    for r in range(n):
        row = []
        for c in range(n):
            cell = out[r][c]
            if cell is None:
                defects.append(
                    f"doubling AG: rank {r} slot {c} never filled")
                row.append(0)
            elif cell[0] != c:
                defects.append(
                    f"doubling AG: rank {r} slot {c} holds chunk "
                    f"{cell[0]} — out of index order")
                row.append(0)
            else:
                row.append(cell[1])
        final.append(row)
    return final, defects


def simulate_ring_all_gather(n: int, topo) -> Tuple[List[List[int]],
                                                    List[str]]:
    """Ring all-gather from rank r holding chunk r (tokens, not
    masks): n-1 forwarding steps on ring_perm(+1)."""
    defects: List[str] = []
    recv_from = {d: s for s, d in topo.ring_perm(n, 1)}
    if sorted(recv_from) != list(range(n)):
        defects.append(
            f"ring_perm(n={n}) is not a complete permutation — "
            f"all-gather simulation aborted (P1 has the root cause)")
        return [], defects
    out: List[List[Optional[int]]] = [[None] * n for _ in range(n)]
    carry = list(range(n))
    for r in range(n):
        out[r][r] = r
    for s in range(n - 1):
        old = list(carry)
        for r in range(n):
            got = old[recv_from[r]]
            arr = (r - s - 1) % n
            if got != arr:
                defects.append(
                    f"ring AG step {s}: rank {r} files chunk {got} "
                    f"under index {arr}")
            out[r][arr] = got
            carry[r] = got
    for r in range(n):
        for c in range(n):
            if out[r][c] != c:
                defects.append(f"ring AG: rank {r} slot {c} holds "
                               f"{out[r][c]}")
    return [[m if m is not None else -1 for m in row] for row in out], \
        defects


def rule_p2(ctx: ProverContext) -> List[Finding]:
    f: List[Finding] = []
    t = ctx.topo
    seen: Set[str] = set()
    per_gen: Dict[str, int] = {}

    def once(gen: str, n: int, msg: str) -> None:
        # dedup exact repeats AND cap per generator: a broken
        # generator fails at every (n, step, rank) — ten findings
        # localize it, fifty thousand bury it
        key = f"{gen}/{msg[:60]}"
        if key in seen or per_gen.get(gen, 0) >= 10:
            return
        seen.add(key)
        per_gen[gen] = per_gen.get(gen, 0) + 1
        f.append(Finding("P2", TOPOLOGY_PY, ctx.topo_line(gen),
                         f"{gen} at n={n}: {msg}"))

    # --- broadcast delivery + round pins ---
    bounds = {"binomial_bcast_schedule":
              lambda n: math.ceil(math.log2(n)),
              "skip_ring_bcast_schedule":
              lambda n: 2 * math.ceil(math.log2(n))}
    exact = {"binomial_bcast_schedule"}
    for gen, n, origin, rounds, err in _bcast_schedules(ctx):
        if err is not None:
            continue  # P1 already reported the raise
        tok = simulate_bcast(rounds, n)
        bad = [r for r in range(n) if tok[r] != origin]
        if bad:
            once(gen, n,
                 f"origin {origin}: ranks {bad[:6]} end holding "
                 f"tokens {[tok[r] for r in bad[:6]]}, not the "
                 f"origin's — broadcast does not deliver")
        bound = bounds[gen](n)
        if gen in exact and len(rounds) != bound:
            once(gen, n,
                 f"origin {origin}: {len(rounds)} rounds, pinned to "
                 f"exactly ceil(log2 n) = {bound}")
        elif len(rounds) > bound:
            once(gen, n,
                 f"origin {origin}: {len(rounds)} rounds exceeds the "
                 f"pinned bound {bound} — schedule degraded")

    def sim(gen: str, n: int, fn, *args):
        """One simulator run; a raise inside the schedule functions it
        drives is a P2 finding, never a prover crash (the bcast
        generators get the same treatment in _bcast_schedules)."""
        try:
            return fn(*args)
        except Exception as e:
            once(gen, n, f"simulation raised: {e}")
            return None

    # --- allreduce / reduce_scatter / all_gather token algebra ---
    full = lambda n: (1 << n) - 1  # noqa: E731
    for n in range(2, N_MAX + 1):
        res = sim("ring_reduce_scatter_chunk", n,
                  simulate_ring_allreduce, n, t)
        if res is not None:
            gathered, defects = res
            for d in defects:
                once("ring_reduce_scatter_chunk", n, d)
            if not defects:
                for r in range(n):
                    if any(m != full(n) for m in gathered[r]):
                        once("ring_reduce_scatter_chunk", n,
                             f"rank {r} gathered masks "
                             f"{[hex(m) for m in gathered[r]]} != all-"
                             f"ones — allreduce incomplete")
                        break
        # reduce_scatter 'ring': post-RS rotate puts chunk r on rank r
        # (structural consequence of the simulated ownership (r+1));
        # checked via the ownership the simulator derived above.
        res = sim("ring_perm", n, simulate_ring_all_gather, n, t)
        for d in (res[1] if res is not None else ()):
            once("ring_perm", n, d)
        if not sim("is_power_of_2", n, t.is_power_of_2, n):
            continue
        res = sim("recursive_doubling_rounds", n,
                  simulate_rd_allreduce, n, t)
        if res is not None:
            acc, defects = res
            for d in defects:
                once("recursive_doubling_rounds", n, d)
            if not defects and any(a != full(n) for a in acc):
                once("recursive_doubling_rounds", n,
                     f"final contribution sets "
                     f"{[hex(a) for a in acc[:4]]}... incomplete")
        res = sim("halving_doubling_distances", n,
                  simulate_halving_reduce_scatter, n, t)
        if res is not None:
            owned, defects = res
            for d in defects:
                once("halving_doubling_distances", n, d)
            if not defects:
                for r, (c, m) in enumerate(owned):
                    if c != r or m != full(n):
                        once("halving_doubling_distances", n,
                             f"rank {r} ends owning chunk {c} with "
                             f"mask {m:#x}, expected chunk {r} with "
                             f"every contribution")
                        break
                res = sim("halving_doubling_distances", n,
                          simulate_doubling_all_gather, n, owned, t)
                if res is not None:
                    final, ag_d = res
                    for d in ag_d:
                        once("halving_doubling_distances", n, d)
                    if not ag_d:
                        for r in range(n):
                            if any(m != full(n) for m in final[r]):
                                once("halving_doubling_distances", n,
                                     f"rank {r} reassembles "
                                     f"incomplete chunks after the "
                                     f"doubling AG")
                                break
    return f

# ---------------------------------------------------------------------------
# P3 — Pallas geometry (mini symbolic interpreter over the wrapper ASTs)
# ---------------------------------------------------------------------------
#
# The kernel wrapper functions in pallas/{decode,flash,reduce}.py are
# symbolically executed under committed shape bindings (P3_PROBES):
# plain ints/bools flow exactly, arrays are shape-tracked ``ArrayVal``s
# (with concrete int data for the scalar-prefetch operands, hostile
# values included), jnp/pl/pltpu calls resolve to small pure stubs, and
# ``pl.pallas_call`` records a KernelSite instead of launching.
# Anything outside the modeled fragment evaluates to ``OPAQUE`` and
# propagates; a site whose geometry stays opaque is itself a finding —
# an unprovable kernel is a maintenance bug, not a pass.


class _Opaque:
    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "OPAQUE"


OPAQUE = _Opaque()


def _is_op(*vals) -> bool:
    return any(v is OPAQUE for v in vals)


class DTypeVal:
    def __init__(self, name: str, itemsize: int):
        self.name = name
        self.itemsize = itemsize

    def __eq__(self, other):
        return isinstance(other, DTypeVal) and self.name == other.name

    def __hash__(self):
        return hash(self.name)

    def __repr__(self):
        return f"dtype:{self.name}"


_DTYPES = {"float32": 4, "bfloat16": 2, "int8": 1, "int32": 4,
           "float16": 2}


def _dt(name: str) -> DTypeVal:
    return DTypeVal(name, _DTYPES.get(name, 4))


class ArrayVal:
    """Shape-tracked array; optional flat int data (scalar-prefetch
    operands) so index_maps evaluate with real values."""

    def __init__(self, shape, data=None, dtype="float32"):
        self.shape = tuple(int(s) for s in shape)
        self.data = None if data is None else [int(v) for v in data]
        self.dtype = dtype if isinstance(dtype, DTypeVal) else _dt(dtype)
        if self.data is not None and len(self.data) != self.size:
            raise ProverError(f"ArrayVal data/shape mismatch "
                              f"{len(self.data)} vs {self.shape}")

    @property
    def ndim(self):
        return len(self.shape)

    @property
    def size(self):
        return math.prod(self.shape) if self.shape else 1

    def reshape(self, *dims):
        if len(dims) == 1 and isinstance(dims[0], (tuple, list)):
            dims = tuple(dims[0])
        dims = tuple(int(d) for d in dims)
        if -1 in dims:
            rest = math.prod(d for d in dims if d != -1)
            dims = tuple(self.size // max(rest, 1) if d == -1 else d
                         for d in dims)
        data = self.data if math.prod(dims or (1,)) == self.size \
            else None
        return ArrayVal(dims, data, self.dtype)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        shape = tuple(self.shape[a] for a in axes)
        return ArrayVal(shape, None, self.dtype)  # data order dropped

    def astype(self, _dtype):
        return ArrayVal(self.shape, self.data, self.dtype)

    def item_at(self, idx: Tuple[int, ...]):
        if self.data is None:
            return OPAQUE
        if len(idx) != len(self.shape):
            return OPAQUE
        flat = 0
        for i, (v, s) in enumerate(zip(idx, self.shape)):
            if not (0 <= v < s):
                return OPAQUE
            flat = flat * s + v
        return self.data[flat]

    def __repr__(self):
        return f"Array{self.shape}"


def _broadcast(a, b):
    sa = a.shape if isinstance(a, ArrayVal) else ()
    sb = b.shape if isinstance(b, ArrayVal) else ()
    out = []
    for x, y in itertools.zip_longest(reversed(sa), reversed(sb),
                                      fillvalue=1):
        if x != 1 and y != 1 and x != y:
            return None
        out.append(max(x, y))
    return tuple(reversed(out))


def _elemwise(op, a, b):
    """Arithmetic on ints / data-carrying arrays / shape-only arrays."""
    if _is_op(a, b):
        return OPAQUE
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        try:
            return op(a, b)
        except (ZeroDivisionError, ValueError):
            return OPAQUE
    if isinstance(a, ArrayVal) or isinstance(b, ArrayVal):
        shape = _broadcast(a, b)
        if shape is None:
            return OPAQUE
        dt = a.dtype if isinstance(a, ArrayVal) else b.dtype
        # data survives where both sides carry it (a Python scalar
        # does): the clamp/offset chains the scalar operands go
        # through, and the compares and sums a work list is built from
        da, db = _flat_broadcast(a, shape), _flat_broadcast(b, shape)
        if da is None or db is None:
            return ArrayVal(shape, None, dt)
        return ArrayVal(shape, [op(x, y) for x, y in zip(da, db)], dt)
    return OPAQUE


def _flat_broadcast(x, shape):
    """The flat values of ``x`` broadcast to ``shape``; None where it
    carries none."""
    n = math.prod(shape or (1,))
    if isinstance(x, (int, float)):
        return [x] * n
    if not isinstance(x, ArrayVal) or x.data is None:
        return None
    if x.shape == tuple(shape):
        return x.data
    dims = (1,) * (len(shape) - x.ndim) + x.shape
    strides, acc = [], 1
    for d in reversed(dims):
        strides.append(0 if d == 1 else acc)
        acc *= d
    strides.reverse()
    return [x.data[sum(i * st for i, st in zip(idx, strides))]
            for idx in itertools.product(*(range(d) for d in shape))]


def _sum_last_axis(arr: "ArrayVal"):
    """``arr.sum(axis=-1)`` with its values, where it carries them."""
    data = None
    if arr.data is not None and arr.ndim >= 1:
        w = arr.shape[-1]
        data = [sum(arr.data[i:i + w])
                for i in range(0, arr.size, w)] if w else None
    return ArrayVal(arr.shape[:-1], data, arr.dtype)


class StubModule:
    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"<stub {self.name}>"


class BlockSpecVal:
    def __init__(self, block, index_map, memory_space=None):
        self.block = block          # tuple of ints (or OPAQUE)
        self.index_map = index_map  # ClosureVal or None
        self.memory_space = memory_space

    @property
    def unblocked(self) -> bool:
        """``memory_space=pl.ANY``: the operand stays where it is and
        the kernel copies from it itself — no block, no index map."""
        return self.memory_space == ("stub", "pl", "ANY")


class PartialVal:
    """``functools.partial(kernel, **static)``: the kernel body is never
    executed, but its static parameters say what its own copies do."""

    def __init__(self, kwargs):
        self.kwargs = kwargs


class GridSpecVal:
    def __init__(self, grid, in_specs, out_specs, num_scalar_prefetch,
                 scratch_shapes=()):
        self.grid = grid
        self.in_specs = in_specs
        self.out_specs = out_specs
        self.num_scalar_prefetch = num_scalar_prefetch
        self.scratch_shapes = scratch_shapes


class ShapeStructVal:
    def __init__(self, shape):
        self.shape = tuple(shape) if not _is_op(shape) else OPAQUE


@dataclass
class KernelSite:
    func: str
    file: str
    line: int
    grid: object
    in_specs: List[object]
    out_specs: List[object]
    out_shapes: List[object]
    operands: List[object]
    num_scalar_prefetch: int
    aliases: Dict[int, int]
    #: the kernel's static parameters, its VMEM scratch shapes and the
    #: module's helpers: what a kernel that copies for itself goes by
    static: Dict[str, object] = field(default_factory=dict)
    scratch: List[Tuple[int, ...]] = field(default_factory=list)
    env: Dict[str, object] = field(default_factory=dict)


class PallasCallable:
    def __init__(self, interp, line, kwargs, kernel=None):
        self.interp = interp
        self.line = line
        self.kwargs = kwargs
        self.kernel = kernel

    def __call__(self, *operands):
        kw = self.kwargs
        gs = kw.get("grid_spec")
        if isinstance(gs, GridSpecVal):
            grid, in_specs, out_specs = gs.grid, gs.in_specs, \
                gs.out_specs
            npf = gs.num_scalar_prefetch
            scratch = gs.scratch_shapes
        else:
            scratch = kw.get("scratch_shapes", ())
            grid = kw.get("grid", OPAQUE)
            in_specs, out_specs = kw.get("in_specs", OPAQUE), \
                kw.get("out_specs", OPAQUE)
            npf = 0
        out_shape = kw.get("out_shape", OPAQUE)
        out_list = out_shape if isinstance(out_shape, list) \
            else [out_shape]
        spec_list = out_specs if isinstance(out_specs, list) \
            else [out_specs]
        aliases = kw.get("input_output_aliases") or {}
        self.interp.sites.append(KernelSite(
            func=self.interp.func_name, file=self.interp.file,
            line=self.line, grid=grid,
            in_specs=in_specs if isinstance(in_specs, list) else [],
            out_specs=spec_list, out_shapes=out_list,
            operands=list(operands), num_scalar_prefetch=npf,
            aliases=aliases if isinstance(aliases, dict) else {},
            static=self.kernel.kwargs
            if isinstance(self.kernel, PartialVal) else {},
            scratch=[x.shape for x in scratch
                     if isinstance(x, ShapeStructVal)
                     and x.shape is not OPAQUE]
            if isinstance(scratch, (list, tuple)) else [],
            env=self.interp.module_env))
        outs = [ArrayVal(o.shape) if isinstance(o, ShapeStructVal)
                and o.shape is not OPAQUE else OPAQUE
                for o in out_list]
        return outs[0] if not isinstance(out_shape, list) else outs


class ScalarRefVal:
    """Scalar-prefetch ref as seen by an index_map: subscripting with
    grid indices yields the operand's concrete int values."""

    def __init__(self, arr: ArrayVal):
        self.arr = arr

    def __getitem__(self, idx):
        if not isinstance(idx, tuple):
            idx = (idx,)
        if self.arr.data is None or _is_op(*idx):
            return OPAQUE
        return self.arr.item_at(tuple(int(i) for i in idx))


class ClosureVal:
    """A lambda / nested def captured with its defining environment."""

    def __init__(self, interp, node, env):
        self.interp = interp
        self.node = node
        self.env = env

    @property
    def params(self):
        return [a.arg for a in self.node.args.args]

    def __call__(self, *args, **kwargs):
        a = self.node.args
        env = dict(self.env)
        names = [x.arg for x in a.args]
        # defaults align right
        defaults = a.defaults or []
        for name, dflt in zip(names[len(names) - len(defaults):],
                              defaults):
            env[name] = self.interp.eval(dflt, self.env)
        for name, val in zip(names, args):
            env[name] = val
        env.update(kwargs)
        for kw, dflt in zip(a.kwonlyargs, a.kw_defaults):
            if kw.arg not in env and dflt is not None:
                env[kw.arg] = self.interp.eval(dflt, self.env)
        if isinstance(self.node, ast.Lambda):
            return self.interp.eval(self.node.body, env)
        return self.interp.exec_block(self.node.body, env)


class _Return(Exception):
    def __init__(self, value):
        self.value = value


def _jnp_minimum(a, b):
    return _elemwise(min, a, b)


def _jnp_maximum(a, b):
    return _elemwise(max, a, b)


def _jnp_clip(a, lo, hi):
    return _jnp_minimum(_jnp_maximum(a, lo), hi)


def _jnp_asarray(x, *_a, **_k):
    return x


def _jnp_zeros(shape, dtype=None, **_k):
    if _is_op(shape):
        return OPAQUE
    if isinstance(shape, int):
        shape = (shape,)
    return ArrayVal(shape, [0] * math.prod(shape or (1,)),
                    dtype if isinstance(dtype, DTypeVal) else "float32")


def _jnp_full(shape, val, dtype=None, **_k):
    if _is_op(shape, val):
        return OPAQUE
    if isinstance(shape, int):
        shape = (shape,)
    n = math.prod(shape or (1,))
    if isinstance(val, ArrayVal):
        data = ([val.data[0]] * n if val.data and val.size == 1
                else None)
    elif isinstance(val, (int, float)):
        data = [int(val)] * n
    else:
        data = None
    return ArrayVal(shape, data,
                    dtype if isinstance(dtype, DTypeVal) else "float32")


def _jnp_arange(n, dtype=None, **_k):
    if _is_op(n):
        return OPAQUE
    return ArrayVal((int(n),), list(range(int(n))), "int32")


def _jnp_where(cond, a, b):
    if isinstance(cond, bool):
        return a if cond else b
    shape = _broadcast(cond if isinstance(cond, ArrayVal)
                       else ArrayVal(()), a if isinstance(a, ArrayVal)
                       else ArrayVal(()))
    if shape is None or _is_op(cond, a, b):
        return OPAQUE
    shape2 = _broadcast(ArrayVal(shape),
                        b if isinstance(b, ArrayVal) else ArrayVal(()))
    dt = a.dtype if isinstance(a, ArrayVal) else \
        (b.dtype if isinstance(b, ArrayVal) else _dt("float32"))
    shape = shape2 or shape
    flat = [_flat_broadcast(x, shape) for x in (cond, a, b)]
    data = None if any(x is None for x in flat) else \
        [y if c else z for c, y, z in zip(*flat)]
    return ArrayVal(shape, data, dt)


def _jnp_cumsum(x, *_a, **_k):
    if not isinstance(x, ArrayVal) or x.ndim != 1:
        return OPAQUE
    data = None if x.data is None else \
        list(itertools.accumulate(x.data))
    return ArrayVal(x.shape, data, x.dtype)


def _jnp_concatenate(arrs, axis=0, **_k):
    if _is_op(arrs) or any(_is_op(a) for a in arrs):
        return OPAQUE
    arrs = [a for a in arrs if isinstance(a, ArrayVal)]
    if not arrs:
        return OPAQUE
    base = list(arrs[0].shape)
    base[axis] = sum(a.shape[axis] for a in arrs)
    return ArrayVal(base, None, arrs[0].dtype)


def _jnp_broadcast_to(x, shape, **_k):
    if _is_op(x, shape) or any(_is_op(d) for d in shape):
        return OPAQUE
    return ArrayVal(shape, None,
                    x.dtype if isinstance(x, ArrayVal) else "float32")


def _jnp_elemwise1(x, *a, **k):
    """exp / abs / zeros_like-style shape-preserving unary."""
    if isinstance(x, ArrayVal):
        return ArrayVal(x.shape, None, x.dtype)
    return OPAQUE if _is_op(x) else x


_JNP_FNS = {
    "minimum": _jnp_minimum, "maximum": _jnp_maximum, "clip": _jnp_clip,
    "asarray": _jnp_asarray, "zeros": _jnp_zeros, "full": _jnp_full,
    "arange": _jnp_arange, "where": _jnp_where,
    "concatenate": _jnp_concatenate, "exp": _jnp_elemwise1,
    "zeros_like": _jnp_elemwise1, "abs": _jnp_elemwise1,
    "cumsum": _jnp_cumsum, "broadcast_to": _jnp_broadcast_to,
}


class Interp:
    """Restricted sequential evaluator for one wrapper function body."""

    MAX_STEPS = 200_000

    def __init__(self, file: str, module_env: Dict[str, object]):
        self.file = file
        self.module_env = module_env
        self.sites: List[KernelSite] = []
        self.func_name = "?"
        self.steps = 0

    # -- statements -----------------------------------------------------
    def run_function(self, fn: ast.FunctionDef,
                     binding: Dict[str, object]) -> None:
        self.func_name = fn.name
        env: Dict[str, object] = dict(binding)
        a = fn.args
        names = [x.arg for x in a.args] + [x.arg for x in a.kwonlyargs]
        defaults = dict(zip([x.arg for x in
                             a.args[len(a.args) - len(a.defaults or []):]],
                            a.defaults or []))
        defaults.update({kw.arg: d for kw, d in
                         zip(a.kwonlyargs, a.kw_defaults)
                         if d is not None})
        for name in names:
            if name not in env:
                env[name] = self.eval(defaults[name], env) \
                    if name in defaults else OPAQUE
        try:
            self.exec_block(fn.body, env)
        except _Return:
            pass

    def exec_block(self, stmts, env):
        try:
            for st in stmts:
                self.exec_stmt(st, env)
        except _Return as r:
            raise r
        return None

    def _tick(self):
        self.steps += 1
        if self.steps > self.MAX_STEPS:
            raise ProverError(f"{self.file}:{self.func_name}: symbolic "
                              f"execution exceeded {self.MAX_STEPS} "
                              f"steps")

    def exec_stmt(self, st, env):
        self._tick()
        if isinstance(st, ast.Assign):
            val = self.eval(st.value, env)
            for tgt in st.targets:
                self.assign(tgt, val, env)
        elif isinstance(st, ast.AnnAssign) and st.value is not None:
            self.assign(st.target, self.eval(st.value, env), env)
        elif isinstance(st, ast.AugAssign):
            cur = self.eval(st.target, env)
            rhs = self.eval(st.value, env)
            if isinstance(st.op, ast.Add) and isinstance(cur, list) \
                    and isinstance(rhs, list):
                val = cur + rhs
            else:
                val = self._binop(st.op, cur, rhs)
            self.assign(st.target, val, env)
        elif isinstance(st, ast.If):
            test = self.eval(st.test, env)
            if isinstance(test, bool) or isinstance(test, int):
                self.exec_block(st.body if test else st.orelse, env)
            # opaque test: execute neither branch (documented caveat)
        elif isinstance(st, ast.While):
            for _ in range(10_000):
                test = self.eval(st.test, env)
                if not isinstance(test, (bool, int)) or not test:
                    break
                self.exec_block(st.body, env)
        elif isinstance(st, ast.Return):
            raise _Return(self.eval(st.value, env)
                          if st.value else None)
        elif isinstance(st, ast.FunctionDef):
            env[st.name] = ClosureVal(self, st, env)
        elif isinstance(st, ast.ImportFrom):
            for alias in st.names:
                name = alias.asname or alias.name
                env[name] = self.module_env.get(
                    alias.name, lambda *a, **k: (a[0] if a else OPAQUE))
        elif isinstance(st, ast.Expr):
            self.eval(st.value, env)
        elif isinstance(st, (ast.Try,)):
            self.exec_block(st.body, env)
        elif isinstance(st, (ast.Raise, ast.Assert, ast.Pass,
                             ast.Import)):
            pass
        # anything else: skipped (For over arrays etc. not needed)

    def assign(self, tgt, val, env):
        if isinstance(tgt, ast.Name):
            # registry pins win over opaque in-body reassignments so a
            # probe can ground names the fragment cannot compute
            if val is OPAQUE and tgt.id in env and \
                    env[tgt.id] is not OPAQUE:
                return
            env[tgt.id] = val
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            vals = list(val) if isinstance(val, (tuple, list)) else None
            if vals is None or len(vals) != len(tgt.elts):
                vals = [OPAQUE] * len(tgt.elts)
            for t, v in zip(tgt.elts, vals):
                self.assign(t, v, env)
        elif isinstance(tgt, ast.Subscript):
            base = self.eval(tgt.value, env)
            key = self.eval(tgt.slice, env)
            if isinstance(base, dict) and not _is_op(key):
                base[key] = val
        # attribute targets: ignored

    # -- expressions ----------------------------------------------------
    _BINOPS = {ast.Add: lambda a, b: a + b,
               ast.Sub: lambda a, b: a - b,
               ast.Mult: lambda a, b: a * b,
               ast.FloorDiv: lambda a, b: a // b,
               ast.Mod: lambda a, b: a % b,
               ast.Div: lambda a, b: a / b,
               ast.Pow: lambda a, b: a ** b,
               ast.LShift: lambda a, b: a << b,
               ast.RShift: lambda a, b: a >> b,
               ast.BitAnd: lambda a, b: a & b,
               ast.BitOr: lambda a, b: a | b,
               ast.BitXor: lambda a, b: a ^ b}

    _CMPOPS = {ast.Eq: lambda a, b: a == b, ast.NotEq: lambda a, b: a != b,
               ast.Lt: lambda a, b: a < b, ast.LtE: lambda a, b: a <= b,
               ast.Gt: lambda a, b: a > b, ast.GtE: lambda a, b: a >= b}

    def _binop(self, op, a, b):
        fn = self._BINOPS.get(type(op))
        if fn is None:
            return OPAQUE
        if isinstance(a, list) and (
                isinstance(op, ast.Add) and isinstance(b, list)
                or isinstance(op, ast.Mult) and isinstance(b, int)):
            return fn(a, b)
        return _elemwise(fn, a, b)

    def eval(self, node, env):
        self._tick()
        if node is None:
            return None
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in env:
                return env[node.id]
            return self.module_env.get(node.id, OPAQUE)
        if isinstance(node, (ast.Tuple, ast.List)):
            vals = [self.eval(e, env) for e in node.elts]
            return tuple(vals) if isinstance(node, ast.Tuple) else vals
        if isinstance(node, ast.Dict):
            out = {}
            for k, v in zip(node.keys, node.values):
                kk = self.eval(k, env) if k is not None else OPAQUE
                if _is_op(kk):
                    continue
                out[kk] = self.eval(v, env)
            return out
        if isinstance(node, ast.Attribute):
            base = self.eval(node.value, env)
            if isinstance(base, StubModule):
                if base.name == "jnp" and node.attr in _DTYPES:
                    return _dt(node.attr)
                return ("stub", base.name, node.attr)
            if isinstance(base, ArrayVal):
                if node.attr == "shape":
                    return base.shape
                if node.attr == "ndim":
                    return base.ndim
                if node.attr == "size":
                    return base.size
                if node.attr == "dtype":
                    return base.dtype
                if node.attr in ("reshape", "transpose", "astype"):
                    return getattr(base, node.attr)
                if node.attr == "sum":
                    def _sum(*a, axis=None, **_k):
                        axis = a[0] if a else axis
                        if axis in (-1, base.ndim - 1):
                            return _sum_last_axis(base)
                        return ArrayVal((), None, base.dtype)
                    return _sum
                return OPAQUE
            if isinstance(base, DTypeVal) and node.attr == "itemsize":
                return base.itemsize
            if isinstance(base, dict):
                return base.get(node.attr, OPAQUE)
            return OPAQUE
        if isinstance(node, ast.BinOp):
            return self._binop(node.op, self.eval(node.left, env),
                               self.eval(node.right, env))
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand, env)
            if isinstance(node.op, ast.USub):
                return _elemwise(lambda a, _b: -a, v, 0)
            if isinstance(node.op, ast.Not):
                return OPAQUE if _is_op(v) else not v
            return OPAQUE
        if isinstance(node, ast.BoolOp):
            vals = [self.eval(v, env) for v in node.values]
            if any(_is_op(v) for v in vals):
                return OPAQUE
            if isinstance(node.op, ast.And):
                out = vals[0]
                for v in vals[1:]:
                    out = out and v
                return out
            out = vals[0]
            for v in vals[1:]:
                out = out or v
            return out
        if isinstance(node, ast.Compare):
            left = self.eval(node.left, env)
            out = True
            for op, cmp_ in zip(node.ops, node.comparators):
                right = self.eval(cmp_, env)
                r = self._compare(op, left, right)
                if r is OPAQUE:
                    return OPAQUE
                if isinstance(r, ArrayVal):
                    return r
                out = out and r
                left = right
            return out
        if isinstance(node, ast.IfExp):
            test = self.eval(node.test, env)
            if _is_op(test) or isinstance(test, ArrayVal):
                return OPAQUE
            return self.eval(node.body if test else node.orelse, env)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, env)
        if isinstance(node, ast.Call):
            return self._call(node, env)
        if isinstance(node, ast.Lambda):
            return ClosureVal(self, node, dict(env))
        if isinstance(node, ast.Starred):
            return self.eval(node.value, env)
        if isinstance(node, ast.JoinedStr):
            return "<fstr>"
        return OPAQUE

    def _compare(self, op, a, b):
        if isinstance(a, ArrayVal) or isinstance(b, ArrayVal):
            if isinstance(op, (ast.Is, ast.IsNot)):
                return isinstance(op, ast.IsNot)
            fn = self._CMPOPS.get(type(op))
            out = _elemwise(lambda x, y: int(fn(x, y)), a, b) \
                if fn is not None else OPAQUE
            if isinstance(out, ArrayVal):
                return out
            shape = _broadcast(a if isinstance(a, ArrayVal)
                               else ArrayVal(()),
                               b if isinstance(b, ArrayVal)
                               else ArrayVal(()))
            return ArrayVal(shape or (), None, "int32")
        if isinstance(op, ast.Is):
            return (a is None and b is None) or a is b
        if isinstance(op, ast.IsNot):
            return not ((a is None and b is None) or a is b)
        if _is_op(a, b):
            return OPAQUE
        try:
            if isinstance(op, ast.Eq):
                return a == b
            if isinstance(op, ast.NotEq):
                return a != b
            if isinstance(op, ast.Lt):
                return a < b
            if isinstance(op, ast.LtE):
                return a <= b
            if isinstance(op, ast.Gt):
                return a > b
            if isinstance(op, ast.GtE):
                return a >= b
            if isinstance(op, ast.In):
                return a in b
            if isinstance(op, ast.NotIn):
                return a not in b
        except TypeError:
            return OPAQUE
        return OPAQUE

    def _subscript(self, node, env):
        base = self.eval(node.value, env)
        if _is_op(base):
            return OPAQUE
        sl = node.slice
        if isinstance(base, ScalarRefVal):
            idx = self.eval(sl, env)
            return base[idx]
        if isinstance(base, (tuple, list)):
            idx = self.eval(sl, env)
            if isinstance(idx, int):
                try:
                    return base[idx]
                except IndexError:
                    return OPAQUE
            return OPAQUE
        if isinstance(base, dict):
            idx = self.eval(sl, env)
            return base.get(idx, OPAQUE) if not _is_op(idx) else OPAQUE
        if isinstance(base, ArrayVal):
            return self._array_subscript(base, sl, env)
        return OPAQUE

    def _array_subscript(self, arr: ArrayVal, sl, env):
        """The handful of indexing shapes the pallas wrappers use:
        int rows, [None] prepend, [..., None] append, and tuples of
        full-slice / None / int."""
        if isinstance(sl, ast.Constant) and sl.value is None:
            return ArrayVal((1,) + arr.shape, arr.data, arr.dtype)
        if isinstance(sl, ast.Tuple):
            elems = sl.elts
            if elems and isinstance(elems[0], ast.Constant) and \
                    elems[0].value is Ellipsis and \
                    len(elems) == 2 and \
                    isinstance(elems[1], ast.Constant) and \
                    elems[1].value is None:
                return ArrayVal(arr.shape + (1,), arr.data, arr.dtype)
            shape = []
            src = list(arr.shape)
            data_ok = True
            for e in elems:
                if isinstance(e, ast.Constant) and e.value is None:
                    shape.append(1)
                    continue
                if not src:
                    return OPAQUE
                dim = src.pop(0)
                if isinstance(e, ast.Slice):
                    if e.lower is None and e.upper is None and \
                            e.step is None:
                        shape.append(dim)
                        continue
                    return OPAQUE
                iv = self.eval(e, env)
                if isinstance(iv, int):
                    data_ok = False  # dropping data on int-index
                    continue
                return OPAQUE
            shape.extend(src)
            return ArrayVal(tuple(shape),
                            arr.data if data_ok and
                            math.prod(shape or (1,)) == arr.size
                            else None, arr.dtype)
        iv = self.eval(sl, env)
        if isinstance(iv, int) and arr.ndim >= 1:
            if arr.data is not None and arr.ndim == 1 and \
                    0 <= iv < arr.size:
                return arr.data[iv]
            return ArrayVal(arr.shape[1:], None, arr.dtype)
        return OPAQUE

    def _call(self, node, env):
        fn = self.eval(node.func, env)
        args = []
        for a in node.args:
            v = self.eval(a, env)
            if isinstance(a, ast.Starred) and isinstance(v, (tuple,
                                                             list)):
                args.extend(v)
            else:
                args.append(v)
        kwargs = {}
        for kw in node.keywords:
            if kw.arg is not None:
                kwargs[kw.arg] = self.eval(kw.value, env)
            else:  # **mapping: merge the evaluated dict's str keys
                mapping = self.eval(kw.value, env)
                if isinstance(mapping, dict):
                    kwargs.update({k: v for k, v in mapping.items()
                                   if isinstance(k, str)})
        if isinstance(fn, tuple) and len(fn) == 3 and fn[0] == "stub":
            return self._stub_call(fn[1], fn[2], node, args, kwargs,
                                   env)
        if callable(fn) and not _is_op(fn):
            try:
                return fn(*args, **kwargs)
            except _Return as r:
                return r.value
            except ProverError:
                raise
            except Exception:
                return OPAQUE
        return OPAQUE

    def _stub_call(self, mod, attr, node, args, kwargs, env):
        if mod == "pl":
            if attr == "BlockSpec":
                block = args[0] if args else kwargs.get("block_shape")
                imap = args[1] if len(args) > 1 else \
                    kwargs.get("index_map")
                return BlockSpecVal(block, imap,
                                    kwargs.get("memory_space"))
            if attr == "cdiv":
                if _is_op(*args):
                    return OPAQUE
                return -(-args[0] // args[1])
            if attr == "pallas_call":
                return PallasCallable(self, node.lineno, kwargs,
                                      args[0] if args else None)
        if mod == "pltpu":
            if attr == "PrefetchScalarGridSpec":
                return GridSpecVal(
                    kwargs.get("grid", OPAQUE),
                    kwargs.get("in_specs", OPAQUE),
                    kwargs.get("out_specs", OPAQUE),
                    kwargs.get("num_scalar_prefetch", 0),
                    kwargs.get("scratch_shapes", ()))
            if attr == "VMEM":
                return ShapeStructVal(args[0]) if args and \
                    not _is_op(args[0]) else OPAQUE
            return OPAQUE
        if mod == "jax" and attr == "ShapeDtypeStruct":
            return ShapeStructVal(args[0]) if args and \
                not _is_op(args[0]) else OPAQUE
        if mod == "jnp" and attr in _JNP_FNS:
            try:
                return _JNP_FNS[attr](*args, **kwargs)
            except Exception:
                return OPAQUE
        if mod == "functools" and attr == "partial":
            # the kernel body itself is never executed
            return PartialVal(kwargs)
        return OPAQUE


def _builtin_env() -> Dict[str, object]:
    return {"min": min, "max": max, "len": len, "int": int,
            "float": float, "abs": abs, "range": range, "dict": dict,
            "set": set, "tuple": tuple, "list": list, "sorted": sorted,
            "True": True, "False": False, "None": None}


def _stub_out_struct(shape, _dtype=None, *_arrays, **_k):
    return ShapeStructVal(shape) if not _is_op(shape) else OPAQUE


def build_module_env(interp: Interp, tree: ast.Module
                     ) -> Dict[str, object]:
    """Evaluate a pallas module's top level into the interpreter env:
    import stubs, constants, and every def as a ClosureVal (so wrapper
    functions can call module helpers like ``_pick_bk``)."""
    env = interp.module_env
    env.update(_builtin_env())
    for name in ("pl", "pltpu", "jnp", "jax", "np", "functools",
                 "lax"):
        env.setdefault(name, StubModule(name))
    env.setdefault("out_struct", _stub_out_struct)
    env.setdefault("vary_like", lambda x, *_a, **_k: x)
    env.setdefault("_on_tpu", lambda: False)

    def top(stmts):
        for st in stmts:
            if isinstance(st, ast.FunctionDef):
                env[st.name] = ClosureVal(interp, st, env)
            elif isinstance(st, ast.Assign) and len(st.targets) == 1 \
                    and isinstance(st.targets[0], ast.Name):
                try:
                    env[st.targets[0].id] = interp.eval(st.value, env)
                except ProverError:
                    raise
                except Exception:
                    env[st.targets[0].id] = OPAQUE
            elif isinstance(st, ast.Try):
                top(st.body)
            elif isinstance(st, ast.ImportFrom):
                for alias in st.names:
                    nm = alias.asname or alias.name
                    if nm not in env:
                        env[nm] = env.get(
                            alias.name,
                            lambda x=None, *_a, **_k: x
                            if x is not None else OPAQUE)
    top(tree.body)
    return env


# -- probe registry ---------------------------------------------------------

def A(shape, data=None, dtype="float32"):
    return ArrayVal(shape, data, dtype)


@dataclass
class Probe:
    file: str
    func: str
    bindings: List[Dict[str, object]]
    #: pallas_call sites each binding must ground (an int applies to
    #: every binding; a list gives the count per binding)
    sites: object

    def want_sites(self, bi: int) -> int:
        return self.sites[bi] if isinstance(self.sites, list) \
            else self.sites


def _p3_probes() -> List[Probe]:
    """Committed shape bindings per kernel wrapper.  Shapes mirror the
    shipped serving/training configs (page_size 128, head_dim 64/128,
    _BLOCK_K 512); scalar operands carry hostile values (out-of-range
    positions / page ids) so the clamp discipline is part of the
    proof.  interpret is pinned True so backend probes never branch on
    a device."""
    cache = dict(cache=A((4, 4, 64, 1024)), interpret=True)
    pool = dict(pool=A((16, 4, 64, 128)), interpret=True)
    return [
        Probe("rlo_tpu/pallas/reduce.py", "_fused_combine_2d", [
            dict(a=A((4096, 128)), b=A((4096, 128)), op="sum",
                 block_rows=2048, interpret=True, in_place=True),
            dict(a=A((8, 128)), b=A((8, 128)), op="max", block_rows=8,
                 interpret=True, in_place=False),
        ], sites=1),
        Probe("rlo_tpu/pallas/decode.py", "write_kv_block", [
            dict(rows=A((4, 4, 64, 8)),
                 pos0=A((4,), [0, 100, 900, 1016]), **cache),
        ], sites=1),
        Probe("rlo_tpu/pallas/decode.py", "write_kv_row", [
            # per-row positions incl. an out-of-range retired slot
            dict(row=A((4, 4, 64)), pos=A((4,), [0, 5, 1023, 2048]),
                 **cache),
            # scalar pos (plain generate): batch-chunked branch
            dict(row=A((4, 4, 64)), pos=A((), [3]), **cache),
        ], sites=1),
        Probe("rlo_tpu/pallas/decode.py", "write_kv_page_row", [
            dict(row=A((4, 4, 64)), page=A((4,), [1, 3, 15, 200]),
                 off=A((4,), [0, 64, 127, 128]), **pool),
        ], sites=1),
        Probe("rlo_tpu/pallas/decode.py", "write_kv_page_block", [
            dict(rows=A((4, 64, 16)), page=A((), [200]),
                 off0=A((), [64]), n_valid=A((), [16]), **pool),
        ], sites=1),
        Probe("rlo_tpu/pallas/decode.py", "paged_flash_decode", [
            dict(q=A((2, 2, 8, 64)), k_pool=A((8, 4, 64, 128)),
                 v_pool=A((8, 4, 64, 128)),
                 table=A((2, 3), [0, 1, 7, 2, 300, 0]),
                 pos0=A((2,), [5, 383]), scale=0.125, ks_pool=None,
                 vs_pool=None, interpret=True),
            dict(q=A((2, 1, 8, 64)), k_pool=A((8, 4, 64, 128)),
                 v_pool=A((8, 4, 64, 128)),
                 table=A((2, 2), [0, 1, 7, 300]),
                 pos0=A((2,), [0, 200]), scale=0.125,
                 ks_pool=A((8, 4, 128)), vs_pool=A((8, 4, 128)),
                 interpret=True),
        ], sites=1),
        # the grid is the call's own work list (decode_work_list),
        # built here from these positions: n_work grounds the grid,
        # and every step's (row, tile) must name a block that exists
        Probe("rlo_tpu/pallas/decode.py", "flash_block_decode", [
            # T = 2; the LAST row short: the list ends on its tile 0
            dict(q=A((2, 2, 8, 64)), k_cache=A((2, 4, 64, 1024)),
                 v_cache=A((2, 4, 64, 1024)), pos0=A((2,), [800, 0]),
                 scale=0.125, k_scale=None, v_scale=None,
                 interpret=True),
            # int8: the scale tiles follow the K/V index map
            dict(q=A((2, 1, 8, 64)), k_cache=A((2, 4, 64, 1024)),
                 v_cache=A((2, 4, 64, 1024)), pos0=A((2,), [1023, 512]),
                 scale=0.125, k_scale=A((2, 4, 1024)),
                 v_scale=A((2, 4, 1024)), interpret=True),
            # a retired slot's pos past max_len (every tile, no more),
            # beside a row of one tile
            dict(q=A((2, 1, 8, 64)), k_cache=A((2, 4, 64, 1024)),
                 v_cache=A((2, 4, 64, 1024)), pos0=A((2,), [4096, 3]),
                 scale=0.125, k_scale=None, v_scale=None,
                 interpret=True),
        ], sites=1),
        # a token selector's scores on its own work list: the index
        # keys' tiles and the score blocks follow (row, tile)
        Probe("rlo_tpu/pallas/decode.py", "index_score", [
            dict(q=A((2, 8, 128)), w=A((2, 8)),
                 k_cache=A((2, 1, 128, 4096)), pos=A((2,), [3000, 0]),
                 work=None, block_k=None, interpret=True),
            # a retired slot's pos past max_len beside a row of one tile
            dict(q=A((2, 8, 128)), w=A((2, 8)),
                 k_cache=A((2, 1, 128, 4096)), pos=A((2,), [9000, 5]),
                 work=None, block_k=1024, interpret=True),
        ], sites=1),
        # the selector's exact top-k: blocks of whole rows, a step each
        Probe("rlo_tpu/pallas/decode.py", "topk_select", [
            # the cell's decode step and extend chunk
            dict(scores=A((32, 1, 24576)), k=2048, interpret=True),
            dict(scores=A((1, 256, 24576)), k=2048, interpret=True),
            # hy4's decode step: rows of 49152, blocks single-buffered
            dict(scores=A((16, 1, 49152)), k=2048, interpret=True),
            # rows no multiple of the block: the filler rows pad it
            dict(scores=A((3, 5, 384)), k=17, interpret=True),
        ], sites=1),
        Probe("rlo_tpu/pallas/flash.py", "_flash_fwd_call", [
            dict(q=A((8, 1024, 128)), k=A((8, 2048, 128)),
                 v=A((8, 2048, 128)), m=A((8, 1, 1024)),
                 l=A((8, 1, 1024)), o=A((8, 1024, 128)),
                 q_pos=A((1, 1024)), k_pos=A((1, 2048)), causal=True,
                 scale=0.08, bq=256, bk=512, interpret=True,
                 alias=True),
        ], sites=1),
        Probe("rlo_tpu/pallas/flash.py", "_pallas_bwd", [
            dict(q=A((8, 1024, 64)), k=A((8, 2048, 64)),
                 v=A((8, 2048, 64)), m=A((8, 1, 1024)),
                 l=A((8, 1, 1024)), o=A((8, 1024, 64)),
                 qp=A((1, 1024)), kp=A((1, 2048)), m2=A((8, 1, 1024)),
                 l2=A((8, 1, 1024)), o2=A((8, 1024, 64)),
                 dm2=A((8, 1, 1024)), dl2=A((8, 1, 1024)),
                 do2=A((8, 1024, 64)), causal=True, scale=0.125,
                 bq=256, bk=512, interpret=True, exact_max=True),
            dict(q=A((8, 1024, 64)), k=A((8, 2048, 64)),
                 v=A((8, 2048, 64)), m=A((8, 1, 1024)),
                 l=A((8, 1, 1024)), o=A((8, 1024, 64)),
                 qp=A((1, 1024)), kp=A((1, 2048)), m2=A((8, 1, 1024)),
                 l2=A((8, 1, 1024)), o2=A((8, 1024, 64)),
                 dm2=A((8, 1, 1024)), dl2=A((8, 1, 1024)),
                 do2=A((8, 1024, 64)), causal=True, scale=0.125,
                 bq=256, bk=512, interpret=True, exact_max=False),
        ], sites=[3, 2]),  # rowstats+dq+dkv with exact_max, 2 without
    ]


#: the committed probe registry — the maintained surface a new
#: pallas_call must join (the P3 coverage finding names it)
P3_PROBES = _p3_probes()


# -- geometry checks --------------------------------------------------------

def _grid_points(grid: Tuple[int, ...]):
    return itertools.product(*(range(g) for g in grid))


def _check_spec_against(f: List[Finding], site: KernelSite,
                        which: str, spec, operand, grid,
                        scalar_refs) -> None:
    where = f"{site.func} {which}"
    if not isinstance(spec, BlockSpecVal):
        f.append(Finding("P3", site.file, site.line,
                         f"{where}: spec did not ground to a "
                         f"BlockSpec (got {spec!r})"))
        return
    block = spec.block
    if _is_op(block) or not isinstance(block, tuple) or \
            any(not isinstance(b, int) for b in block):
        f.append(Finding("P3", site.file, site.line,
                         f"{where}: block shape did not ground "
                         f"({block!r})"))
        return
    if any(b < 1 for b in block):
        f.append(Finding("P3", site.file, site.line,
                         f"{where}: non-positive block dim in "
                         f"{block}"))
        return
    logical = None
    if isinstance(operand, ArrayVal):
        logical = operand.shape
    elif isinstance(operand, ShapeStructVal) and \
            operand.shape is not OPAQUE:
        logical = operand.shape
    if logical is not None:
        if len(block) != len(logical):
            f.append(Finding(
                "P3", site.file, site.line,
                f"{where}: block rank {len(block)} != operand rank "
                f"{len(logical)} (block {block}, operand {logical})"))
            return
        for b, s in zip(block, logical):
            if b > s:
                f.append(Finding(
                    "P3", site.file, site.line,
                    f"{where}: block {block} exceeds logical shape "
                    f"{logical}"))
                break
        # lane (minor) dim: full axis or a 128-lane multiple
        if block[-1] != logical[-1] and block[-1] % LANE:
            f.append(Finding(
                "P3", site.file, site.line,
                f"{where}: lane dim {block[-1]} of block {block} is "
                f"neither the whole axis ({logical[-1]}) nor a "
                f"multiple of {LANE} — Mosaic rejects or pads this "
                f"tiling"))
        # sublane (second-minor): full axis or a multiple of 8
        if len(block) >= 2 and block[-2] != logical[-2] and \
                block[-2] % SUBLANE:
            f.append(Finding(
                "P3", site.file, site.line,
                f"{where}: sublane dim {block[-2]} of block {block} "
                f"is neither the whole axis ({logical[-2]}) nor a "
                f"multiple of {SUBLANE}"))
    imap = spec.index_map
    if imap is None or not isinstance(imap, ClosureVal):
        f.append(Finding("P3", site.file, site.line,
                         f"{where}: index_map did not ground"))
        return
    want_arity = len(grid) + len(scalar_refs)
    n_params = len(imap.params)
    n_required = n_params - len(imap.node.args.defaults or [])
    # pallas passes exactly (grid indices..., prefetch refs...);
    # trailing defaulted params (the `_n=L // 128` closure idiom) are
    # legal padding
    if not n_required <= want_arity <= n_params:
        f.append(Finding(
            "P3", site.file, site.line,
            f"{where}: index_map takes {n_required}..{n_params} args, "
            f"grid rank {len(grid)} + {len(scalar_refs)} "
            f"scalar-prefetch refs = {want_arity}"))
        return
    if logical is None:
        return  # cannot bound-check without the operand shape
    bounds = [max(1, -(-s // b)) for s, b in zip(logical, block)]
    for pt in _grid_points(grid):
        try:
            out = imap(*pt, *scalar_refs)
        except ProverError:
            raise
        except Exception as e:
            f.append(Finding(
                "P3", site.file, site.line,
                f"{where}: index_map raised at grid point {pt}: {e}"))
            return
        if not isinstance(out, tuple) or len(out) != len(block):
            f.append(Finding(
                "P3", site.file, site.line,
                f"{where}: index_map returned {out!r} at {pt}, "
                f"expected a rank-{len(block)} block index"))
            return
        for axis, (v, bound) in enumerate(zip(out, bounds)):
            if _is_op(v):
                f.append(Finding(
                    "P3", site.file, site.line,
                    f"{where}: index_map axis {axis} did not ground "
                    f"at grid point {pt} (scalar-prefetch value "
                    f"unresolved)"))
                return
            if not isinstance(v, int) or not 0 <= v < bound:
                f.append(Finding(
                    "P3", site.file, site.line,
                    f"{where}: block index {v} on axis {axis} out of "
                    f"range [0, {bound}) at grid point {pt} — an "
                    f"unclamped scalar (hostile pos/page id) selects "
                    f"an illegal block"))
                return


def _check_ring_fetch(f: List[Finding], site: KernelSite, which: str,
                      operand, scalar_ops) -> None:
    """An operand the kernel copies for itself (pallas.decode
    ._ring_fetch): walk the grid as the kernel does — step i starts the
    copies of the steps _ring_span gives it, then waits for its own —
    with the module's own _ring_span, _copy_lanes and _copy_sizes, over
    the site's work list, and prove every copy's source slice inside
    the operand, every size one the kernel holds a copy for, every
    step's copy started once, before its wait, into a slot whose last
    tile has been computed from, and no step named past the list."""
    where = f"{site.func} {which}"

    def bad(msg):
        f.append(Finding("P3", site.file, site.line, f"{where}: {msg}"))

    fns = [site.env.get(n) for n in ("_ring_span", "_copy_lanes",
                                     "_copy_sizes")]
    bk, max_len, T = (site.static.get(k) for k in ("bk", "max_len", "T"))
    rings = [x for x in site.scratch if len(x) == 4]
    if not all(isinstance(fn, ClosureVal) for fn in fns) or \
            not all(isinstance(v, int) for v in (bk, max_len, T)) or \
            not rings or not isinstance(operand, ArrayVal) or \
            operand.ndim != 4 or len(scalar_ops) < 3 or \
            any(not isinstance(op, ArrayVal) or op.data is None
                for op in scalar_ops[:3]):
        return bad("an unblocked (pl.ANY) operand whose copies did not "
                   "ground: the kernel's bk / max_len / T, its ring "
                   "scratch, the work list or the module's _ring_span "
                   "/ _copy_lanes / _copy_sizes")
    def module_fn(fn):
        def call(*args):
            try:
                return fn(*args)
            except _Return as r:
                return r.value
        return call

    span, copy_lanes, copy_sizes = map(module_fn, fns)
    row_of, tile_of, pos = (op.data for op in scalar_ops[:3])
    b, L = operand.shape[0], operand.shape[3]
    n_slots, n_work = rings[0][0], site.grid[0]
    if any(r != (n_slots,) + operand.shape[1:3] + (bk,) for r in rings) \
            or L != max_len:
        return bad(f"ring slots {rings} do not hold (n_slots, "
                   f"{operand.shape[1]}, {operand.shape[2]}, bk={bk}) "
                   f"tiles of an operand {operand.shape}")
    sizes = copy_sizes(bk, max_len)
    if not isinstance(sizes, list) or \
            any(not isinstance(n, int) for n in sizes):
        return bad(f"_copy_sizes did not ground ({sizes!r})")
    started = 0        # steps 0 .. started - 1 have had their copies started
    for i in range(n_work):
        lo, hi = span(i, n_work, n_slots)
        if not isinstance(lo, int) or not isinstance(hi, int):
            return bad(f"_ring_span did not ground at step {i}")
        for j in range(lo, hi):
            if j >= n_work:
                return bad(f"step {i} starts the copy of step {j}, past "
                           f"the work list's {n_work} steps")
            if j != started:
                return bad(f"step {i} starts step {j}'s copy, expected "
                           f"step {started}'s: a copy started twice or "
                           f"never")
            if j - n_slots >= i:
                return bad(f"step {i} starts step {j}'s copy into the "
                           f"slot step {j - n_slots} has not been "
                           f"computed from yet ({n_slots} slots)")
            started += 1
            row, tile = row_of[j], tile_of[j]
            if not 0 <= row < b:
                return bad(f"step {j}'s copy reads row {row} of {b}")
            lanes = copy_lanes(min(pos[row] + T, max_len), tile, bk,
                               max_len)
            if not isinstance(lanes, int) or lanes not in sizes:
                return bad(f"step {j} copies {lanes!r} lanes, not one "
                           f"of the kernel's static sizes {sizes}: "
                           f"nothing would be started and its wait "
                           f"never ends")
            if tile < 0 or lanes < 1 or tile * bk + lanes > L:
                return bad(f"step {j}'s copy [{tile * bk}, "
                           f"{tile * bk + lanes}) of row {row} runs "
                           f"outside the operand's {L} positions")
            if (tile * bk % LANE or lanes % LANE) and lanes != L:
                return bad(f"step {j}'s copy [{tile * bk}, "
                           f"{tile * bk + lanes}) is neither whole "
                           f"{LANE}-lane blocks nor the whole axis")
        if started <= i:
            return bad(f"step {i} waits for a copy no step has started")
    if started != n_work:
        bad(f"{started} of {n_work} steps' copies were started")


def _check_site(f: List[Finding], site: KernelSite) -> None:
    grid = site.grid
    if _is_op(grid) or not isinstance(grid, tuple) or \
            any(not isinstance(g, int) or g < 1 for g in grid):
        f.append(Finding("P3", site.file, site.line,
                         f"{site.func}: grid did not ground to "
                         f"positive ints ({grid!r})"))
        return
    npf = site.num_scalar_prefetch
    scalar_ops = site.operands[:npf]
    refs = []
    for i, op in enumerate(scalar_ops):
        if not isinstance(op, ArrayVal) or op.data is None:
            f.append(Finding(
                "P3", site.file, site.line,
                f"{site.func}: scalar-prefetch operand {i} carries no "
                f"concrete values — cannot prove the index_map range"))
            refs.append(ScalarRefVal(ArrayVal((1,), [0])))
        else:
            refs.append(ScalarRefVal(op))
    data_ops = site.operands[npf:]
    if len(site.in_specs) != len(data_ops):
        f.append(Finding(
            "P3", site.file, site.line,
            f"{site.func}: {len(site.in_specs)} in_specs but "
            f"{len(data_ops)} data operands"))
    for i, (spec, op) in enumerate(zip(site.in_specs, data_ops)):
        if isinstance(spec, BlockSpecVal) and spec.unblocked:
            _check_ring_fetch(f, site, f"in_specs[{i}]", op, scalar_ops)
            continue
        _check_spec_against(f, site, f"in_specs[{i}]", spec, op, grid,
                            refs)
    if len(site.out_specs) != len(site.out_shapes):
        f.append(Finding(
            "P3", site.file, site.line,
            f"{site.func}: {len(site.out_specs)} out_specs but "
            f"{len(site.out_shapes)} out_shapes — an unmatched "
            f"output would go unproven"))
    for i, (spec, out) in enumerate(zip(site.out_specs,
                                        site.out_shapes)):
        _check_spec_against(f, site, f"out_specs[{i}]", spec, out,
                            grid, refs)
    for src, dst in sorted(site.aliases.items()):
        if not (isinstance(src, int) and isinstance(dst, int)):
            continue
        if src >= len(site.operands) or dst >= len(site.out_shapes):
            f.append(Finding(
                "P3", site.file, site.line,
                f"{site.func}: input_output_aliases {{{src}: {dst}}} "
                f"names a missing operand/output"))
            continue
        a, b = site.operands[src], site.out_shapes[dst]
        sa = a.shape if isinstance(a, ArrayVal) else None
        sb = b.shape if isinstance(b, ShapeStructVal) and \
            b.shape is not OPAQUE else None
        if sa is not None and sb is not None and sa != sb:
            f.append(Finding(
                "P3", site.file, site.line,
                f"{site.func}: aliased operand {src} shape {sa} != "
                f"output {dst} shape {sb} — in-place donation would "
                f"corrupt"))


def rule_p3(ctx: ProverContext) -> List[Finding]:
    f: List[Finding] = []
    probes = P3_PROBES
    probed = {(p.file, p.func) for p in probes}
    funcs: Dict[Tuple[str, str], ast.FunctionDef] = {}
    for rel in PALLAS_FILES:
        for node in ctx.mod(rel).tree.body:
            if isinstance(node, ast.FunctionDef):
                funcs[(rel, node.name)] = node
    reached: Set[Tuple[str, int]] = set()   # call sites a probe grounded
    for probe in probes:
        mod = ctx.mod(probe.file)
        fn = funcs.get((probe.file, probe.func))
        if fn is None:
            f.append(Finding("P3", probe.file, 1,
                             f"probed wrapper {probe.func} not found"))
            continue
        for bi, binding in enumerate(probe.bindings):
            interp = Interp(probe.file, {})
            build_module_env(interp, mod.tree)
            try:
                interp.run_function(fn, dict(binding))
            except ProverError as e:
                f.append(Finding("P3", probe.file, fn.lineno, str(e)))
                continue
            want = probe.want_sites(bi)
            if len(interp.sites) != want:
                f.append(Finding(
                    "P3", probe.file, fn.lineno,
                    f"{probe.func} binding {bi}: grounded "
                    f"{len(interp.sites)} pallas_call sites, "
                    f"expected {want} — the wrapper no longer "
                    f"evaluates under the committed shapes"))
            for site in interp.sites:
                reached.add((site.file, site.line))
                _check_site(f, site)
    # coverage: every pallas_call in the pallas package must sit in a
    # probed function, or in a helper a probed wrapper's run reached
    # (a jitted call function of its own) — a new kernel without a
    # probe is a finding, not a silent gap
    for rel in PALLAS_FILES:
        for node in ast.walk(ctx.mod(rel).tree):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "pallas_call":
                owner = None
                for (r, name), fn in funcs.items():
                    if r == rel and fn.lineno <= node.lineno <= \
                            max(getattr(fn, "end_lineno", fn.lineno),
                                fn.lineno):
                        owner = (r, name)
                if owner not in probed and \
                        (rel, node.lineno) not in reached:
                    f.append(Finding(
                        "P3", rel, node.lineno,
                        f"pallas_call outside any probed wrapper "
                        f"(enclosing: {owner and owner[1]}) — add a "
                        f"P3_PROBES entry so its geometry is proven"))
    return f


# ---------------------------------------------------------------------------
# P4 — shard_map axis discipline
# ---------------------------------------------------------------------------

#: axis argument slots per collective entry point.  Values are
#: (positional index, keyword names) — a call is checked wherever the
#: axis lands.
_LAX_AXIS = {
    "ppermute": (1, ("axis_name",)), "psum": (1, ("axis_name",)),
    "pmin": (1, ("axis_name",)), "pmax": (1, ("axis_name",)),
    "all_gather": (1, ("axis_name",)),
    "all_to_all": (1, ("axis_name",)),
    "axis_index": (0, ("axis_name",)), "axis_size": (0, ("axis_name",)),
    "pmean": (1, ("axis_name",)),
    "pbroadcast": (1, ("axis_name",)), "pcast": (1, ("axes",)),
}
_TC_AXIS = {
    "allreduce": ((1,), ("axis",)),
    "reduce_scatter": ((1,), ("axis",)),
    "all_gather": ((1,), ("axis",)),
    "all_to_all": ((1,), ("axis",)),
    "rootless_bcast": ((2,), ("axis",)),
    "consensus": ((1,), ("axis",)),
    "barrier": ((0,), ("axis",)),
    "hierarchical_allreduce": ((1, 2), ("ici_axis", "dcn_axis")),
}
_TC_MODULE_NAMES = {"tc", "tpu_collectives"}


def _axis_exprs(call: ast.Call) -> List[ast.AST]:
    """Axis-argument expressions of one collective call, or []."""
    fn = call.func
    if not isinstance(fn, ast.Attribute) or \
            not isinstance(fn.value, ast.Name):
        return []
    base, attr = fn.value.id, fn.attr
    out: List[ast.AST] = []
    if base == "lax" and attr in _LAX_AXIS:
        pos, kws = _LAX_AXIS[attr]
        if len(call.args) > pos:
            out.append(call.args[pos])
        out.extend(kw.value for kw in call.keywords if kw.arg in kws)
    elif base in _TC_MODULE_NAMES and attr in _TC_AXIS:
        poss, kws = _TC_AXIS[attr]
        for pos in poss:
            if len(call.args) > pos:
                out.append(call.args[pos])
        out.extend(kw.value for kw in call.keywords if kw.arg in kws)
    return out


def _declared_mesh_literals(tree: ast.Module) -> Set[str]:
    """Axis-name string literals a module itself binds into a mesh via
    make_mesh / make_multislice_mesh / Mesh — the only literals that
    module may legitimately consume as collective axis names."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else None)
            if name in ("make_mesh", "make_multislice_mesh", "Mesh"):
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Constant) and \
                            isinstance(sub.value, str):
                        out.add(sub.value)
    return out


def rule_p4(ctx: ProverContext) -> List[Finding]:
    f: List[Finding] = []
    for rel in P4_FILES:
        if not (ctx.root / rel).exists():
            continue
        mod = ctx.mod(rel)
        declared = _declared_mesh_literals(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            for expr in _axis_exprs(node):
                for sub in ast.walk(expr):
                    if not (isinstance(sub, ast.Constant) and
                            isinstance(sub.value, str)):
                        continue
                    if sub.value in declared:
                        continue
                    at = find_anchor(mod.lines, node.lineno,
                                     AXIS_OK_ANCHOR)
                    if at is not None:
                        ctx.registry.consume(mod.path, at)
                        continue
                    f.append(Finding(
                        "P4", rel, node.lineno,
                        f"hard-coded axis name {sub.value!r} in a "
                        f"collective call — axis names must flow from "
                        f"a parameter bound at the parallel/mesh.py "
                        f"wrapper (or match a mesh literal this "
                        f"module itself binds); a drifted string "
                        f"compiles the collective onto the wrong "
                        f"axis. '# {AXIS_OK_ANCHOR} <why>' sanctions "
                        f"a deliberate literal"))
    return f


# ---------------------------------------------------------------------------
# P5 — device-layer constant pinning
# ---------------------------------------------------------------------------

def _pin(ctx: ProverContext, f: List[Finding], mod: PyMod, line: int,
         what: str, got: object, want: object,
         anchored: bool = False) -> None:
    if got != want:
        f.append(Finding(
            "P5", mod.path, line,
            f"{what} = {got!r} drifts from the pinned lane/page "
            f"contract ({want!r}) — the host and device sides of the "
            f"paged cache no longer agree"))
    if anchored:
        at = find_anchor(mod.lines, line, LANE_PINNED_ANCHOR)
        if at is None:
            f.append(Finding(
                "P5", mod.path, line,
                f"pinned constant site {what} lacks a "
                f"'# {LANE_PINNED_ANCHOR}' anchor comment"))
        else:
            ctx.registry.consume(mod.path, at)


def _find_funcdef(tree: ast.AST, name: str,
                  cls: Optional[str] = None) -> Optional[ast.FunctionDef]:
    for node in ast.walk(tree):
        if cls is not None and isinstance(node, ast.ClassDef) and \
                node.name == cls:
            for sub in node.body:
                if isinstance(sub, ast.FunctionDef) and \
                        sub.name == name:
                    return sub
        elif cls is None and isinstance(node, ast.FunctionDef) and \
                node.name == name:
            return node
    return None


def _mod_literals(fn: ast.AST) -> List[Tuple[int, int]]:
    """(value, line) of every integer RHS of a ``x % <int>`` in fn."""
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.BinOp) and \
                isinstance(node.op, ast.Mod) and \
                isinstance(node.right, ast.Constant) and \
                isinstance(node.right.value, int):
            out.append((node.right.value, node.lineno))
    return out


def rule_p5(ctx: ProverContext) -> List[Finding]:
    f: List[Finding] = []

    # pallas/reduce.py: _LANE, the kernel-side lane constant
    reduce = ctx.mod(REDUCE_PY)
    lane_line, lane_val = None, None
    for node in reduce.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "_LANE" and \
                isinstance(node.value, ast.Constant):
            lane_line, lane_val = node.lineno, node.value.value
    if lane_line is None:
        f.append(Finding("P5", REDUCE_PY, 1, "_LANE not defined"))
    else:
        _pin(ctx, f, reduce, lane_line, "pallas/reduce.py _LANE",
             lane_val, LANE, anchored=True)

    # models/serve.py: the TPU default page_size + its % gate
    serve = ctx.mod(SERVE_PY)
    init = _find_funcdef(serve.tree, "__init__", cls="DecodeServer")
    pinned_default = False
    if init is not None:
        args = init.args
        pairs = list(zip(
            [a.arg for a in
             args.args[len(args.args) - len(args.defaults or []):]],
            args.defaults or []))
        pairs += [(kw.arg, d) for kw, d in
                  zip(args.kwonlyargs, args.kw_defaults)
                  if d is not None]
        for name, d in pairs:
            if name == "page_size" and isinstance(d, ast.Constant):
                _pin(ctx, f, serve, d.lineno,
                     "models/serve.py DecodeServer page_size default",
                     d.value, LANE, anchored=True)
                pinned_default = True
    if not pinned_default:
        f.append(Finding(
            "P5", SERVE_PY, 1,
            "DecodeServer.__init__ page_size default not found — the "
            "TPU page-size pin has no anchor point"))
    ip = _find_funcdef(serve.tree, "_init_paged", cls="DecodeServer")
    for val, line in _mod_literals(ip) if ip is not None else []:
        _pin(ctx, f, serve, line,
             "models/serve.py _init_paged page gate modulus", val,
             LANE)

    # models/paged.py: the pool-layout % gate, pool shape order, and
    # the inactive-slot write sentinels
    paged = ctx.mod(PAGED_PY)
    ipp = _find_funcdef(paged.tree, "init_page_pool")
    gates = _mod_literals(ipp) if ipp is not None else []
    if not gates:
        f.append(Finding("P5", PAGED_PY, 1,
                         "init_page_pool has no % page gate — the "
                         "128-lane page contract is unenforced"))
    for val, line in gates:
        _pin(ctx, f, paged, line,
             "models/paged.py init_page_pool page gate modulus", val,
             LANE, anchored=True)
    if ipp is not None:
        ok_shape = False
        for node in ast.walk(ipp):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) and \
                    node.targets[0].id == "shape" and \
                    isinstance(node.value, ast.Tuple) and \
                    node.value.elts:
                last = node.value.elts[-1]
                ok_shape = isinstance(last, ast.Name) and \
                    last.id == "page_size"
        if not ok_shape:
            f.append(Finding(
                "P5", PAGED_PY, ipp.lineno,
                "init_page_pool pool shape no longer ends in "
                "page_size — pages must stay the lane-minor axis the "
                "decode kernels index"))
    step = _find_funcdef(paged.tree, "paged_decode_step")
    found_page_sentinel = found_off_sentinel = False
    if step is not None:
        for node in ast.walk(step):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "where" and len(node.args) == 3:
                a1, a2 = node.args[1], node.args[2]
                if isinstance(a1, ast.Name) and a1.id == "page":
                    found_page_sentinel = True
                    if not (isinstance(a2, ast.Constant) and
                            a2.value == 0):
                        f.append(Finding(
                            "P5", PAGED_PY, node.lineno,
                            "inactive slots must map to the NULL page "
                            "(0, serving/pages.NULL_PAGE); this "
                            "jnp.where routes them elsewhere"))
                if isinstance(a1, ast.BinOp) and \
                        isinstance(a1.op, ast.Mod):
                    found_off_sentinel = True
                    if not (isinstance(a2, ast.Name) and
                            a2.id == "ps"):
                        f.append(Finding(
                            "P5", PAGED_PY, node.lineno,
                            "the paged write DROP sentinel must be "
                            "the page size ('ps') — any other "
                            "offset lands a masked write on a real "
                            "lane"))
    if step is not None and not (found_page_sentinel and
                                 found_off_sentinel):
        f.append(Finding(
            "P5", PAGED_PY, step.lineno,
            "paged_decode_step no longer masks inactive slots via "
            "the page->NULL / off->page_size sentinels"))

    # serving/pages.py: NULL_PAGE — the host side of the sentinel
    pages = ctx.mod(PAGES_PY)
    np_line = None
    for node in pages.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "NULL_PAGE" and \
                isinstance(node.value, ast.Constant):
            np_line = node.lineno
            _pin(ctx, f, pages, np_line, "serving/pages.py NULL_PAGE",
                 node.value.value, 0, anchored=True)
    if np_line is None:
        f.append(Finding("P5", PAGES_PY, 1, "NULL_PAGE not defined"))

    # pallas/decode.py: the shape gates' lane moduli and the
    # write-row lane floor
    decode = ctx.mod(DECODE_PY)
    for fname in ("can_paged_flash", "can_flash_decode",
                  "can_write_block"):
        fn = _find_funcdef(decode.tree, fname)
        if fn is None:
            f.append(Finding("P5", DECODE_PY, 1,
                             f"shape gate {fname} not found"))
            continue
        for val, line in _mod_literals(fn):
            # (head_dim == 64 is an equality special case, never a
            # modulus — every % literal in the gates is a lane pin)
            _pin(ctx, f, decode, line,
                 f"pallas/decode.py {fname} lane modulus", val, LANE)
    cwr = _find_funcdef(decode.tree, "can_write_row")
    if cwr is not None:
        for node in ast.walk(cwr):
            if isinstance(node, ast.Compare) and len(node.ops) == 1 \
                    and isinstance(node.ops[0], ast.GtE) and \
                    isinstance(node.comparators[0], ast.Constant):
                _pin(ctx, f, decode, node.lineno,
                     "pallas/decode.py can_write_row lane floor",
                     node.comparators[0].value, LANE)
    return f


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_RULES = {"P1": rule_p1, "P2": rule_p2, "P3": rule_p3, "P4": rule_p4,
          "P5": rule_p5}

#: the rule families that consume suppression anchors — what the
#: rlo-sentinel S0 audit runs for its consumption footprint.  A new
#: prover rule that learns an anchor spelling must join this tuple or
#: its anchors will be flagged stale.
ANCHOR_RULES = ("P4", "P5")


def audit_files(root: Path) -> List[str]:
    """Files whose ``rlo-prover:`` anchors fall under the rlo-sentinel
    S0 stale-anchor audit (the files the prover reads)."""
    rels = [TOPOLOGY_PY, SERVE_PY, PAGED_PY, PAGES_PY] + \
        list(PALLAS_FILES) + list(P4_FILES)
    seen: List[str] = []
    for rel in rels:
        if rel not in seen and (Path(root) / rel).exists():
            seen.append(rel)
    return seen


def run_prover(root: Path, rules: Optional[Sequence[str]] = None,
               registry: Optional[AnchorRegistry] = None
               ) -> List[Finding]:
    """Run the selected rule families (default: all) against the tree
    at ``root``; returns findings sorted by file/line.  ``registry``
    (when given) accumulates the anchor lines the rules consumed — the
    input to rlo-sentinel's S0 stale-anchor audit."""
    ctx = build_context(root, registry)
    out: List[Finding] = []
    for rid in rules or RULE_IDS:
        if rid not in _RULES:
            raise ProverError(f"unknown rule {rid!r} (have "
                              f"{', '.join(RULE_IDS)})")
        out.extend(_RULES[rid](ctx))
    out.sort(key=lambda x: (x.file, x.line, x.rule))
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m rlo_tpu.tools.rlo_prover",
        description="Symbolic collective-schedule verifier + "
                    "device-layer geometry lint (rule catalogue: "
                    "docs/DESIGN.md §16).")
    ap.add_argument("--root", type=Path,
                    default=Path(__file__).resolve().parents[2],
                    help="repo root to analyze (default: this checkout)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule families (default: all), "
                         "e.g. --rules P1,P3")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings on stdout")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="findings only, no summary line")
    args = ap.parse_args(argv)
    rules = ([r.strip().upper() for r in args.rules.split(",") if
              r.strip()] if args.rules else None)
    try:
        findings = run_prover(args.root, rules)
    except ToolError as e:
        print(f"rlo-prover: error: {e}", file=sys.stderr)
        return 2
    return emit(findings, prog="rlo-prover",
                ran=",".join(rules or RULE_IDS), root=args.root,
                as_json=args.json, quiet=args.quiet)


if __name__ == "__main__":
    sys.exit(main())
