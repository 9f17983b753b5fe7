"""Device-mesh topology — the TPU replacement for MPIContext.

The reference builds a two-level topology from MPI communicator splits
(/root/reference/src/common/mpi_context.cc:25-35): a node-local "local"
communicator and a per-local-rank "cross" communicator. On TPU the same
hierarchy is a 2-D ``jax.sharding.Mesh`` with a fast intra-slice **ICI** axis
and a cross-slice **DCN** axis; XLA schedules the actual transport
(SURVEY.md §5.8). Axis names used throughout the framework:

* ``"intra"`` — ICI (the reference's local/SHM level)
* ``"cross"`` — DCN (the reference's cross-node MPI level)
* flat data-parallel meshes use a single ``"dp"`` axis.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

INTRA_AXIS = "intra"
CROSS_AXIS = "cross"
DP_AXIS = "dp"


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Multi-host bootstrap — the analogue of the reference's once-only
    ``MPI_Init_thread`` (ProcessGroupCGX.cc:242-257), built on
    ``jax.distributed.initialize`` (DCN control plane).

    Call once per process before building meshes. On Cloud TPU pods all
    arguments are auto-detected; elsewhere pass them explicitly or via
    ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``.
    Returns True if the distributed runtime was (or already is) initialized,
    False when running single-host with no coordinator configured (no-op).
    """
    import os

    # NOT jax.process_count(): that initializes the XLA backend, after which
    # jax.distributed.initialize() unconditionally raises.
    from ..utils.compat import distributed_is_initialized

    if distributed_is_initialized():
        return True
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_np = os.environ.get("JAX_NUM_PROCESSES")
    num_processes = num_processes or (int(env_np) if env_np else None)
    env_pid = os.environ.get("JAX_PROCESS_ID")
    process_id = process_id if process_id is not None else (
        int(env_pid) if env_pid else None
    )
    on_pod = any(
        k in os.environ for k in ("TPU_WORKER_HOSTNAMES", "MEGASCALE_COORDINATOR_ADDRESS")
    )
    if coordinator_address is None and not on_pod:
        return False  # single host — nothing to bootstrap
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def flat_mesh(devices: Optional[Sequence] = None, axis: str = DP_AXIS) -> Mesh:
    """Single-axis data-parallel mesh over all (or given) devices."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def hierarchical_mesh(
    devices: Optional[Sequence] = None,
    intra_size: Optional[int] = None,
) -> Mesh:
    """2-D (cross, intra) mesh.

    ``intra_size`` defaults to the number of devices per process/host (the
    reference's node-local world, MPI_Comm_split_type(SHARED)) or, failing
    that, the largest power-of-two divisor <= 8.
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = len(devices)
    if intra_size is None:
        local = jax.local_device_count()
        intra_size = local if (0 < local <= n and n % local == 0) else _pow2_div(n)
    if n % intra_size != 0:
        raise ValueError(f"{n} devices not divisible by intra_size={intra_size}")
    arr = np.asarray(devices).reshape(n // intra_size, intra_size)
    return Mesh(arr, (CROSS_AXIS, INTRA_AXIS))


def _pow2_div(n: int) -> int:
    p = 1
    while p * 2 <= min(n, 8) and n % (p * 2) == 0:
        p *= 2
    return p


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]


def survivor_mesh(mesh: Mesh, survivors: Sequence[int], axis: str = DP_AXIS) -> Mesh:
    """Re-derive a mesh after the recovery supervisor evicted ranks: keep
    only the ``survivors`` positions along ``axis`` (sorted — survivor
    order must be identical on every rank or the reassembled meshes
    disagree), preserving every other axis.

    Also bumps the config registry version: the layout LRU
    (``allreduce._tree_layout``) and ``make_train_step``'s trace cache
    both key on it, so every plan derived for the dead world size is
    invalidated rather than silently reused — SRA/Ring chunking is a pure
    function of the axis size (``reducers.chunk_layout``) and re-derives
    at the next trace.
    """
    from .. import config as cfg

    names = list(mesh.axis_names)
    idx = names.index(axis)
    keep = sorted(int(s) for s in survivors)
    extent = mesh.devices.shape[idx]
    bad = [s for s in keep if not 0 <= s < extent]
    if bad:
        raise ValueError(
            f"survivor positions {bad} out of range for axis {axis!r} "
            f"(extent {extent})"
        )
    if not keep:
        raise ValueError("survivor_mesh: empty survivor set")
    arr = np.take(mesh.devices, keep, axis=idx)
    cfg._bump_registry_version()
    return Mesh(arr, tuple(names))


def make_training_mesh(
    n_devices: Optional[int] = None,
    *,
    dp: Optional[int] = None,
    tp: int = 1,
    sp: int = 1,
    pp: int = 1,
    ep: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """General training mesh with (dp, pp, sp, tp, ep-folded-into-dp) axes.

    Axes with size 1 are still present so sharding specs are uniform; expert
    parallelism reuses the ``dp`` axis group by convention (experts sharded
    over dp) unless ``ep > 1`` which adds a dedicated axis.
    """
    devices = list(devices) if devices is not None else jax.devices()
    n = n_devices or len(devices)
    devices = devices[:n]
    used = tp * sp * pp * ep
    if dp is None:
        if n % used:
            raise ValueError(f"{n} devices not divisible by tp*sp*pp*ep={used}")
        dp = n // used
    if dp * used != n:
        raise ValueError(f"dp*tp*sp*pp*ep={dp * used} != {n} devices")
    names = ("dp", "pp", "sp", "tp", "ep")
    shape = (dp, pp, sp, tp, ep)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, names)
