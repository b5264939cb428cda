"""Multi-host mesh building blocks — the NCCL/MPI-backend analogue.

The reference scales its comm backend across hosts with NCCL/MPI process
groups; the JAX equivalent is `jax.distributed` + one global
`('shard', 'time')` mesh whose collectives ride ICI within a slice and DCN
across slices (ref: SURVEY §2.9; the scaling-book recipe: pick a mesh,
annotate shardings, let XLA insert collectives).

SCOPE — read this before wiring a pod:

This module provides the verified building blocks (runtime join, global
mesh construction, per-host global-array assembly).  They degrade exactly
to the single-host path under one process, which is what CI exercises.
Driving `mesh.distributed_window_agg` across processes additionally requires
invariants the CALLER must establish (single-host runs get them for free):

1. **Globally consistent group slots.**  `pack_shards` assigns
   aggregation-group slots from a local registry; every process must pack
   with the SAME key->slot mapping and the same num_groups, or the psum
   mixes unrelated groups.  Distribute the mapping via the cluster control
   plane (parallel/cluster.py) or derive it from a shared catalog before
   packing.
2. **Globally agreed static arguments.**  `precorrected` and the presence
   of `vbase` are static to the SPMD program; all processes must agree or
   they compile mismatched programs.  Agree on them from the dataset
   schema (which is global), not from locally-present columns.
3. **Process-aligned shard axis.**  Each process owns a contiguous block
   of the 'shard' axis covering exactly its addressable devices —
   global_mesh() enforces this alignment or raises.
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from filodb_tpu.parallel.mesh import device_put_packed


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               auto: bool = False) -> None:
    """Join the multi-host runtime.

    auto=True calls jax.distributed.initialize() with no arguments, letting
    JAX auto-detect the pod topology from the platform's metadata (the
    normal mode on TPU pods).  Otherwise arguments default from
    JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID; with one
    process (or none of the variables set) this is a no-op so single-host
    tools run unchanged."""
    if auto:
        jax.distributed.initialize()
        return
    num = num_processes if num_processes is not None else int(
        os.environ.get("JAX_NUM_PROCESSES", "1"))
    if num <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address
        or os.environ.get("JAX_COORDINATOR_ADDRESS"),
        num_processes=num,
        process_id=process_id if process_id is not None else int(
            os.environ.get("JAX_PROCESS_ID", "0")))


def global_mesh(n_shard: Optional[int] = None, n_time: int = 1) -> Mesh:
    """('shard', 'time') mesh over ALL devices of every process (call after
    initialize()).  Devices are ordered process-major so each process's
    devices form contiguous 'shard' rows — the alignment assemble_global's
    per-process blocks rely on.  Raises if the shape would truncate a
    process's devices (harmless truncation is allowed only single-process)
    or split a shard row across processes."""
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    if n_shard is None:
        n_shard = len(devs) // n_time
    need = n_shard * n_time
    if len(devs) < need:
        raise ValueError(f"need {need} devices globally, have {len(devs)}")
    if jax.process_count() > 1:
        if need != len(devs):
            raise ValueError(
                f"mesh shape {n_shard}x{n_time} uses {need} of {len(devs)} "
                f"devices; multi-process meshes must cover every process")
        per_proc = len(devs) // jax.process_count()
        if per_proc % n_time != 0:
            raise ValueError(
                f"time axis {n_time} does not divide the {per_proc} devices "
                f"per process; a shard row would span two hosts")
    grid = np.array(devs[:need]).reshape(n_shard, n_time)
    return Mesh(grid, ("shard", "time"))


def assemble_global(mesh: Mesh, local: np.ndarray,
                    spec: Sequence[Optional[str]]) -> jax.Array:
    """Build one global array from this process's block of the data.

    `local` holds the slice this host owns along the sharded axes of
    `spec` (e.g. its shards' [D_local, S, T] block for spec
    ('shard', None, None)).  Under one process this is an ordinary
    device_put; under many, jax.make_array_from_process_local_data glues
    the per-host blocks into one global array without any host ever
    holding the whole tensor."""
    sharding = NamedSharding(mesh, P(*spec))
    if jax.process_count() == 1:
        return jax.device_put(local, sharding)
    return jax.make_array_from_process_local_data(sharding, local)


def device_put_packed_multihost(packed, mesh: Mesh):
    """Multi-host placement for a PackedShards whose arrays hold THIS
    process's shard block (D_local leading dim).  The caller owns the
    cross-process invariants listed in the module docstring (consistent
    group slots, agreed vbase/precorrected).  Single-process calls delegate
    to the local path so there is exactly one authoritative field list."""
    if jax.process_count() == 1:
        return device_put_packed(packed, mesh)
    import dataclasses
    data_spec = ("shard", None, None)
    row_spec = ("shard", None)
    return dataclasses.replace(
        packed,
        ts_off=assemble_global(mesh, packed.ts_off, data_spec),
        values=assemble_global(mesh, packed.values, data_spec),
        group_ids=assemble_global(mesh, packed.group_ids, row_spec),
        vbase=(assemble_global(mesh, packed.vbase, row_spec)
               if packed.vbase is not None else None))
