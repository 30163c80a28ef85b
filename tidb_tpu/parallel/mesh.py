"""Device mesh helpers.

Reference: the MPP task topology — fragments dispatched per store with
exchange between them (pkg/planner/core/fragment.go:149, copr/mpp.go:93).
TPU-native: one 1-D logical mesh axis "d" over all chips; row partitions
of every table shard over "d" (the analog of Region-partitioned scans,
SURVEY.md §2.7), and exchange ops are XLA collectives over ICI.
Multi-host: the same mesh spans hosts via jax.distributed — collectives
ride ICI within a slice and DCN across, with no code change here.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tidb_tpu.chunk import Batch

AXIS = "d"


shard_map = jax.shard_map


def reshard(a, sharding):
    """Pin `a` to `sharding`. The mesh axis is Auto (make_mesh), where
    the partitioner owns layouts and a sharding constraint is how a
    program states one; jax.sharding.reshard speaks for Explicit axes
    and leaves an Auto-mode output wherever the partitioner put it —
    across processes that is a result no host can fetch."""
    return jax.lax.with_sharding_constraint(a, sharding)


def pmax(v, axis: str = AXIS):
    """lax.pmax that the TPU compiler also takes for 64-bit integers.
    Its X64 rewriter lowers only SUM all-reduces of int64 ("Supported
    lowering only of Sum all reduce"), and the engine's cardinality
    scalars are int64 (WIDTH_STALE is 2^60), so a 64-bit max goes as two
    32-bit ones: the high words, then the low words of the shards that
    hold the high maximum."""
    if v.dtype != jnp.int64:
        return jax.lax.pmax(v, axis)
    hi = (v >> 32).astype(jnp.int32)
    lo = (v & 0xFFFFFFFF).astype(jnp.uint32)
    mhi = jax.lax.pmax(hi, axis)
    mlo = jax.lax.pmax(jnp.where(hi == mhi, lo, jnp.uint32(0)), axis)
    return (mhi.astype(jnp.int64) << 32) | mlo.astype(jnp.int64)


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if n > len(devs):
        # never a smaller mesh than was asked for: a server given four
        # chips that silently served from one would be measured as four
        raise ValueError(
            f"mesh of {n} devices asked for, JAX sees {len(devs)} "
            f"({devs[0].platform})"
        )
    # Auto, not jax 0.9's default Explicit: the engine reshapes and
    # concatenates sharded operands and leaves their layout to the
    # partitioner
    return jax.make_mesh(
        (n,), (AXIS,), devices=devs[:n],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


@functools.lru_cache(maxsize=None)
def shared_mesh(n_devices: int) -> Mesh:
    """THE mesh of width n in this process: every session of a server
    (and every executor a test builds) runs its programs over the same
    Mesh object and the same resident shards (storage/scan.py keys its
    cache by the mesh's width)."""
    return make_mesh(n_devices)


def init_multihost(
    coordinator: str,
    num_processes: int,
    process_id: int,
    local_device_count: Optional[int] = None,
) -> Mesh:
    """Bring up the cross-host runtime (DCN analog) and return the
    GLOBAL mesh spanning every process's devices.

    Reference: cross-store MPP dispatch (pkg/store/copr/mpp.go:93) +
    cluster membership via PD/etcd. JAX's multi-controller model
    replaces both: every host runs the same program, jax.distributed
    wires the processes together (coordinator = the PD analog), and
    collectives ride ICI within a slice / DCN across slices with no
    engine change — the mesh axis simply spans more devices.

    For CPU-based testing set JAX_PLATFORMS=cpu and
    xla_force_host_platform_device_count before calling; each process
    contributes its local devices to the global mesh.
    """
    if local_device_count is not None:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={local_device_count}"
            ).strip()
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return make_mesh()


def batch_spec() -> P:
    return P(AXIS)


def shard_batch(batch: Batch, mesh: Mesh) -> Batch:
    """Place a host-built global batch row-sharded over the mesh."""
    sharding = NamedSharding(mesh, P(AXIS))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def unshard_batch(batch: Batch) -> Batch:
    """Gather a sharded batch to host-replicated layout (materialization)."""
    return jax.tree.map(lambda x: np.asarray(x), batch)
