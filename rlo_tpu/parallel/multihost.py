"""Multi-controller deployment: one OS process per host, real everywhere.

The reference's ranks are arbitrary MPI processes — including across
machines (`RLO_progress_engine_new` dup's any communicator,
/root/reference/rootless_ops.c:467, 1461; nothing in the library assumes
one host). The round-2 rebuild's TPU data plane was a single JAX
controller *simulating* ranks; this module is the real deployment shape
(round-2 VERDICT "What's missing" #1). Each OS process runs

  - its own ENGINE rank over the MPI transport — femtompi shared-memory
    rings between processes on one host (rlo_tpu/native/femtompi), the
    same `rlo_mpi.c` against a real MPI library across hosts; and
  - its own JAX controller, federated by `jax.distributed.initialize`
    into ONE global device mesh (CPU devices locally, the host's TPU
    chips in production — docs/DEPLOY.md maps a v5e-16 pod).

The consensus-gated collective is then genuinely distributed end to end:
the proposal/vote/decision frames are real cross-process engine traffic
(any process may initiate — rootless), each process judges its OWN local
state, and the approved action is one XLA AllReduce over the global mesh
(cross-process CPU collectives locally; ICI/DCN on a pod). A veto by any
single process blocks the device collective on every process.

Launch (single host, 4 "hosts" as processes):

    rlo_tpu/native/femtompirun -n 4 python your_prog.py

with `JAX_PLATFORMS=cpu` in the environment and a
free coordinator port in `RLO_COORDINATOR` (see
tests/test_multihost.py / benchmarks/multihost_demo.py).
"""

from __future__ import annotations

import os
from typing import Callable, Optional, Tuple

import numpy as np

#: default coordination-service endpoint (process 0 binds it)
_DEFAULT_COORD = "127.0.0.1:28741"


class MultiHostContext:
    """Engine control plane + federated JAX data plane for one process.

    Construction order matters: `jax.distributed.initialize` must run
    before the first JAX backend touch, and needs (rank, world_size),
    which come from the engine world — so the engine backend comes up
    first (pure ctypes, no JAX).
    """

    def __init__(self, coordinator: Optional[str] = None,
                 transport: Optional[str] = None):
        """``transport``: 'mpi' (femtompi shm rings locally, a real MPI
        across hosts) or 'tcp' (the socket-mesh transport, rlo_tcp.c —
        crosses hosts with no MPI installed; launch via tcprun or with
        RLO_TCP_HOSTS). Default: $RLO_TRANSPORT, else autodetect from
        the launcher's env (RLO_TCP_RANK -> tcp)."""
        from rlo_tpu.backend import MpiBackend, TcpBackend

        transport = (transport or os.environ.get("RLO_TRANSPORT")
                     or ("tcp" if os.environ.get("RLO_TCP_RANK")
                         else "mpi"))
        if transport not in ("mpi", "tcp"):
            raise ValueError(f"unknown transport {transport!r}")
        self.backend = (TcpBackend if transport == "tcp"
                        else MpiBackend)()
        self.rank = self.backend.rank
        self.world_size = self.backend.world_size

        import jax

        coordinator = (coordinator
                       or os.environ.get("RLO_COORDINATOR")
                       or _DEFAULT_COORD)
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=self.world_size,
                                   process_id=self.rank)
        self._jax = jax
        # one mesh row per PROCESS: the first local device of each
        # process, in process order — every shard of a mesh-sharded
        # array then lives in a different OS process
        by_proc = {}
        for d in jax.devices():
            by_proc.setdefault(d.process_index, []).append(d)
        if sorted(by_proc) != list(range(self.world_size)):
            raise RuntimeError(
                f"jax.distributed federated {sorted(by_proc)} processes; "
                f"expected {self.world_size} (is JAX_PLATFORMS=cpu set "
                f"in the environment, before python starts?)")
        from jax.sharding import Mesh

        self.mesh_devices = [by_proc[p][0]
                             for p in range(self.world_size)]
        self.mesh = Mesh(np.array(self.mesh_devices), ("hosts",))
        self._psum_cache: dict = {}

    # -- data plane ----------------------------------------------------
    def _global_array(self, local: np.ndarray):
        """Assemble the (ws, *local.shape) global array whose row r is
        process r's local tensor, sharded one row per process."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        jax = self._jax
        local = np.asarray(local)
        sharding = NamedSharding(self.mesh, P("hosts"))
        shard = jax.device_put(local[None],
                               self.mesh_devices[self.rank])
        return jax.make_array_from_single_device_arrays(
            (self.world_size, *local.shape), sharding, [shard])

    def device_allreduce(self, local: np.ndarray,
                         op: str = "sum") -> np.ndarray:
        """One XLA AllReduce across all processes' device memories;
        returns this process's (replicated) result. This is the real
        cross-process data plane — not a host gather."""
        from jax.sharding import PartitionSpec as P

        jax = self._jax
        key = (op, np.asarray(local).shape, str(np.asarray(local).dtype))
        if key not in self._psum_cache:
            from rlo_tpu.ops import tpu_collectives as tc

            def step(v):
                return tc.allreduce(v[0], "hosts", op=op,
                                    use_pallas=False)[None]

            self._psum_cache[key] = jax.jit(jax.shard_map(
                step, mesh=self.mesh, in_specs=P("hosts"),
                out_specs=P("hosts")))
        out = self._psum_cache[key](self._global_array(local))
        return np.asarray(out.addressable_shards[0].data[0])

    # -- the bridge ----------------------------------------------------
    def propose_collective(self, local: np.ndarray, *,
                           proposer: int = 0,
                           judge: Optional[Callable] = None,
                           op: str = "sum") -> Tuple[int, Optional[np.ndarray]]:
        """Leaderless-consensus-gated cross-process collective.

        Process ``proposer`` (ANY process — rootless) initiates; every
        process runs ``judge(local)`` on its OWN tensor and votes; the
        votes AND-merge up the engine's skip-ring tree as real
        cross-process frames; the decision broadcasts. Only on approval
        does the device collective run — a veto on one process blocks
        it on all (the distributed form of HybridBackend
        .propose_collective, which simulated ranks in one controller).

        Returns (decision, result): (1, summed array) on approval,
        (0, None) when any process vetoed.
        """
        vote = 1 if judge is None else int(bool(judge(local)))
        decision = self.backend.consensus(vote, proposer=proposer)
        if not decision:
            return 0, None
        return 1, self.device_allreduce(local, op=op)

    def sub_context(self, members) -> Optional["MultiHostContext"]:
        """Scoped context over a subset of the hosts (round-4 VERDICT:
        consensus over a rank subset on the REAL-process path).
        Collective — every process must call it with the same members.
        Member processes get a context whose control plane is the
        engine sub-communicator (backend.sub_group: subset frames on
        their own comm, demuxed on the same transport) and whose data
        plane is the sub-mesh of the members' devices; a veto by any
        member blocks the subset collective on every member, while
        non-members (who get None) keep using the parent. Matches the
        reference's engine-on-any-communicator (rootless_ops.c:467,
        1461)."""
        sub = self.backend.sub_group(members)
        if sub is None:
            return None
        return _SubContext(self, sub, sorted(set(int(m)
                                                 for m in members)))

    def close(self) -> None:
        self.backend.close()


class _SubContext(MultiHostContext):
    """Member-scoped MultiHostContext: ``rank`` is the SUBSET POSITION
    and the mesh spans only the members' devices. Ops are inherited —
    the indexing contract (positions everywhere) is what changes."""

    def __init__(self, parent: MultiHostContext, sub_backend, members):
        from jax.sharding import Mesh

        self.backend = sub_backend
        self.rank = sub_backend.pos
        self.world_size = sub_backend.world_size
        self._jax = parent._jax
        self.mesh_devices = [parent.mesh_devices[m] for m in members]
        self.mesh = Mesh(np.array(self.mesh_devices), ("hosts",))
        self._psum_cache: dict = {}

    def sub_context(self, members):
        raise NotImplementedError("nested sub-contexts are not supported")
