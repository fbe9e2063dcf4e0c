"""Device mesh construction.

Reference communication stacks (SURVEY.md §5.8): NCCL rings
(platform/collective_helper.*), MPI (boxps::MPICluster), Gloo
(fleet/gloo_wrapper.*), brpc PS RPC — all collapse into XLA collectives over
one jax Mesh: the "dp" axis carries both the data-parallel dense allreduce
(NCCL SyncParam role) and the embedding all-to-all (HeterComm P2P role),
riding ICI intra-slice and DCN across slices.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


DATA_AXIS = "dp"


def make_mesh(n_devices: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None,
              axis_name: str = DATA_AXIS) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (axis_name,))


def stacked_zeros(n: int, shape: Sequence[int], dtype) -> jax.Array:
    """``[n, *shape]`` zeros born SHARDED over the leading axis of
    ``make_mesh(n)``: slice s is allocated on device s and nowhere else
    (the per-shard table state and AUC tables of the mesh trainers —
    broadcast-and-copy on the default device would materialize all n
    slices on device 0 first). A pod's processes each initialize
    locally instead; ``train/multihost.globalize_state`` re-stages that
    state onto the global mesh."""
    full = (n,) + tuple(shape)
    if jax.process_count() > 1:
        return jnp.zeros(full, dtype)
    if n > len(jax.devices()):
        raise ValueError(
            f"{n} shards need {n} devices, found {len(jax.devices())}")
    return jnp.zeros(full, dtype, device=NamedSharding(
        make_mesh(n), PartitionSpec(DATA_AXIS)))


def data_axis_size(mesh: Mesh, axis_name: str = DATA_AXIS) -> int:
    return mesh.shape[axis_name]


ICI_AXIS = "ici"   # chips within a slice (fast interconnect)
DCN_AXIS = "dcn"   # across slices/pods (data-center network)


def make_hierarchical_mesh(n_slices: int,
                           devices_per_slice: Optional[int] = None,
                           devices: Optional[Sequence[jax.Device]] = None
                           ) -> Mesh:
    """2-level [dcn, ici] mesh — the topology the reference manages with
    separate stacks (intra-node NCCL rings + inter-node MPI,
    ps_gpu_wrapper.h:221-265 inner/inter comms; box_wrapper.h:686
    SyncDense). Collectives annotated per axis ride the right fabric.

    On real multi-slice hardware prefer device order from
    ``jax.experimental.mesh_utils.create_hybrid_device_mesh``; this
    reshape form is exact for tests/virtual devices and single-slice
    splits."""
    devs = list(devices) if devices is not None else jax.devices()
    per = devices_per_slice or len(devs) // n_slices
    if n_slices * per > len(devs):
        raise ValueError(f"need {n_slices * per} devices, have {len(devs)}")
    grid = np.array(devs[:n_slices * per]).reshape(n_slices, per)
    return Mesh(grid, (DCN_AXIS, ICI_AXIS))


def hierarchical_allreduce(x: jax.Array, ici_axis: str = ICI_AXIS,
                           dcn_axis: str = DCN_AXIS) -> jax.Array:
    """Bandwidth-optimal 2-level allreduce (inside shard_map over a
    [dcn, ici] mesh): reduce-scatter over ICI → allreduce of the 1/n_ici
    partial over DCN → all-gather over ICI. Exactly the reference's
    dense sync ladder — ncclReduceScatter → ``BoxWrapper::SyncDense``
    (inter-node) → ncclAllGather (boxps_worker.cc:1217-1234) — so the
    slow DCN hop carries only 1/n_ici of the bytes."""
    n = jax.lax.axis_size(ici_axis)
    flat = x.reshape(-1)
    pad = (-flat.size) % n
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros(pad, flat.dtype)])
    part = jax.lax.psum_scatter(flat, ici_axis, scatter_dimension=0,
                                tiled=True)
    part = jax.lax.psum(part, dcn_axis)
    out = jax.lax.all_gather(part, ici_axis, axis=0, tiled=True)
    return out[:x.size].reshape(x.shape)
