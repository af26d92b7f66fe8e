"""Device mesh discovery and shard_map helpers.

TPU equivalent of the reference's communicator setup (MPI_Comm_dup +
size/rank discovery in bcomm_init, /root/reference/rootless_ops.c:1461-1468):
on TPU the "communicator" is a `jax.sharding.Mesh` over the ICI topology and
"ranks" are mesh axis indices.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def make_mesh(shape: Optional[Sequence[int]] = None,
              axis_names: Optional[Sequence[str]] = None) -> Mesh:
    """Build a mesh over the available devices.

    Default: 1-D mesh named 'x' over all devices. Pass e.g.
    shape=(2, 4), axis_names=('dp', 'tp') for multi-axis layouts.
    """
    devices = np.asarray(jax.devices())
    if shape is None:
        shape = (len(devices),)
    if axis_names is None:
        axis_names = ("x",) if len(shape) == 1 else \
            tuple(f"axis{i}" for i in range(len(shape)))
    need = math.prod(shape)
    if need > len(devices):
        raise ValueError(f"mesh shape {tuple(shape)} needs {need} devices, "
                         f"have {len(devices)}")
    return Mesh(devices[:need].reshape(shape), tuple(axis_names))


def make_multislice_mesh(ici_shape: Sequence[int],
                         ici_axis_names: Sequence[str],
                         dcn_axis_name: str = "dcn") -> Mesh:
    """Mesh for multi-slice TPU jobs: a leading data-center-network axis
    over slices, then the per-slice ICI axes.

    On a multi-slice platform (devices carry distinct ``slice_index``),
    devices are grouped so that the ICI axes stay INSIDE a slice — the
    bandwidth-heavy collectives (tp/sp/ep, ring allreduce) ride ICI,
    while only the ``dcn`` axis (put your dp/gradient averaging there)
    crosses the slower cross-slice network. On single-slice or CPU
    platforms the dcn axis degrades to size 1, so programs written
    against the (dcn, *ici) layout run unchanged anywhere.
    """
    import warnings

    devices = jax.devices()
    slices: dict = {}
    for d in devices:
        slices.setdefault(getattr(d, "slice_index", 0), []).append(d)
    n_slices = len(slices)
    per = math.prod(ici_shape)
    for idx, devs in slices.items():
        if len(devs) < per:
            raise ValueError(
                f"slice {idx} has {len(devs)} devices, ICI shape "
                f"{tuple(ici_shape)} needs {per}")
        if len(devs) > per:
            warnings.warn(
                f"slice {idx}: ICI shape {tuple(ici_shape)} uses {per} "
                f"of {len(devs)} devices; the rest sit idle",
                stacklevel=2)
    arr = np.empty((n_slices,) + tuple(ici_shape), dtype=object)
    for i, idx in enumerate(sorted(slices)):
        arr[i] = np.asarray(slices[idx][:per]).reshape(ici_shape)
    return Mesh(arr, (dcn_axis_name,) + tuple(ici_axis_names))


def shard_jit(fn, mesh: Mesh, in_specs, out_specs, check_vma: bool = True):
    """jit(shard_map(fn)) — one SPMD program over the mesh.

    check_vma (varying-manual-axes typing) is ON by default: it makes
    jax.grad correct under shard_map by auto-inserting the cotangent
    psums for replicated params (without it, the transpose of psum is
    psum and per-shard grads of replicated params are wrong). Code that
    wants explicit control of a gradient collective (e.g. the dp ring
    allreduce) opts out per-param with `vary_over` instead of disabling
    the typing."""
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=check_vma))


def vary_like(x, like):
    """Mark ``x`` varying over the mesh axes ``like`` varies over.

    Under check_vma, fori_loop carries must keep a constant vma type:
    zeros-initialized accumulators start unvarying while the loop body
    makes them varying — cast the inits up front. No-op when vma typing
    is off or ``like`` carries no vma."""
    need = set(jax.typeof(like).vma) - set(jax.typeof(x).vma)
    if not need:
        return x
    return jax.lax.pcast(x, tuple(sorted(need)), to="varying")
