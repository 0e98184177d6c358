"""Distributed communication backend — multi-host init + collectives facade.

The reference has no comm backend of its own: every cross-process hop rides
Spark RPC/shuffle (SURVEY.md §2). The TPU-native story is explicit and
first-class here:

- **multi-host bring-up**: ``initialize`` wraps ``jax.distributed.initialize``
  so N hosts (each owning a slice of the pod) join one JAX process group —
  after which the SAME mesh code in ``parallel.mesh``/``parallel.gram`` spans
  hosts, with XLA routing collectives over ICI within a slice and DCN across
  slices. No NCCL/MPI analog is needed: the runtime owns transport.
- **collectives facade**: typed helpers (allreduce/allgather/broadcast over a
  mesh axis) used by the sharded kernels, plus a host-level fallback that
  reduces through the tree aggregator when no mesh program is running —
  the two reduction strategies SURVEY.md §2 calls out, behind one surface.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Sequence

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from spark_rapids_ml_tpu.parallel.tree_aggregate import tree_reduce

_initialized = False


def initialize(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join (or bootstrap) the multi-host process group.

    On a single host this is a no-op — local devices already form the mesh.
    On a pod slice each host calls this with the coordinator address before
    building meshes, exactly once per process.
    """
    global _initialized
    if _initialized or jax.process_count() > 1:
        _initialized = True
        return
    if coordinator_address is None:
        return  # single-process mode
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True


def process_info() -> dict:
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


# ---------------------------------------------------------------------------
# Mesh collectives facade
# ---------------------------------------------------------------------------


def mapreduce_data_axis(
    kernel, mesh: Mesh, *, replicated_args: int = 0, in_specs=None
):
    """shard_map a partition-stats kernel over the ``data`` axis and
    psum-combine its monoid output (replicated result).

    ``kernel(x_local, *replicated)`` takes the device-local row shard plus
    ``replicated_args`` fully-replicated operands and returns any pytree of
    summable statistics — the GramStats/MomentStats/KMeansStats pattern. This
    is the one place the collective scaffolding lives; every sharded
    estimator reducer is an instantiation. Pass explicit ``in_specs`` when
    the operands aren't the standard ([rows, n] sharded + replicated) shape
    (e.g. a label vector sharded as ``P(DATA_AXIS)``).
    """
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    if in_specs is None:
        in_specs = (P(DATA_AXIS, None),) + (P(),) * replicated_args

    @partial(jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False)
    def _run(*args):
        return jax.tree.map(lambda v: lax.psum(v, DATA_AXIS), kernel(*args))

    return _run


@lru_cache(maxsize=None)
def _allreduce_prog(mesh: Mesh, axis: str):
    @partial(jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False)
    def _psum(v):
        return lax.psum(v.sum(axis=0), axis)

    return jax.jit(_psum)


def allreduce(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Sum-reduce a [stacked, ...] array over its leading dim across one mesh
    axis: each device reduces its resident slices, one psum combines the
    rest. Returns the replicated [...] total."""
    return _allreduce_prog(mesh, axis)(x)


@lru_cache(maxsize=None)
def _allgather_prog(mesh: Mesh, axis: str):
    @partial(
        jax.shard_map, mesh=mesh, in_specs=P(axis), out_specs=P(), check_vma=False
    )
    def _gather(v):
        return lax.all_gather(v, axis, tiled=True)

    return jax.jit(_gather)


def allgather(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """Gather shards along the leading dim over one mesh axis."""
    return _allgather_prog(mesh, axis)(x)


def broadcast_host(value, root: int = 0):
    """Host-level broadcast via the multihost utils (cross-host model
    distribution — the analog of Spark closure-shipping the model)."""
    if jax.process_count() == 1:
        return value
    from jax.experimental import multihost_utils

    return multihost_utils.broadcast_one_to_all(value, is_source=jax.process_index() == root)


def host_reduce(partials: Sequence, combine) -> object:
    """Reduction outside any mesh program: balanced tree over host values —
    the portable path (reference parity: RapidsRowMatrix.scala:139)."""
    return tree_reduce(list(partials), combine)
