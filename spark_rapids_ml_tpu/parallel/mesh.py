"""Device-mesh construction helpers.

The reference has no mesh concept — its "cluster" is Spark dynamically
scheduling partition tasks, with cross-partition reduction through the JVM
(SURVEY.md §2 "Distributed communication backend"). The TPU-native design
inverts that: devices form a named ``jax.sharding.Mesh`` and XLA inserts ICI
collectives for every cross-device movement. Axis conventions used across
this package:

- ``"data"``  — row/batch parallelism (the reference's partition axis),
- ``"feat"``  — feature-dimension sharding (the capability the reference
  lacks: its n×n buffers must fit one device, RapidsRowMatrix.scala:50-52).
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
FEAT_AXIS = "feat"


def center_columns_shard(xl):
    """Shard-local mean-centering over the ``data`` axis.

    Call inside a shard_map body whose mesh has the data axis: one psum for
    the column sums, one for the global row count, subtract. Shared by the
    TSQR and sketched fit paths.
    """
    import jax.numpy as jnp
    from jax import lax

    s = lax.psum(jnp.sum(xl, axis=0), DATA_AXIS)
    c = lax.psum(jnp.asarray(xl.shape[0], xl.dtype), DATA_AXIS)
    return xl - (s / c)[None, :]


def create_mesh(
    data: int | None = None,
    feat: int = 1,
    *,
    devices=None,
) -> Mesh:
    """Build a (data, feat) mesh over the given (default: all) devices.

    With ``data=None`` the data axis absorbs all devices not used by
    ``feat``. The feat axis is innermost so feature-block ring transfers ride
    neighboring ICI links.
    """
    devices = list(devices if devices is not None else jax.devices())
    if data is None:
        if len(devices) % feat:
            raise ValueError(f"{len(devices)} devices not divisible by feat={feat}")
        data = len(devices) // feat
    count = data * feat
    if count > len(devices):
        raise ValueError(f"mesh {data}x{feat} needs {count} devices, have {len(devices)}")
    grid = np.array(devices[:count]).reshape(data, feat)
    return Mesh(grid, (DATA_AXIS, FEAT_AXIS))


def data_sharding(mesh: Mesh, *, feature_sharded: bool = False) -> NamedSharding:
    """Input sharding for a [rows, n] matrix on the mesh."""
    spec = P(DATA_AXIS, FEAT_AXIS) if feature_sharded else P(DATA_AXIS, None)
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def create_hybrid_mesh(feat: int = 1, *, slice_groups=None) -> Mesh:
    """Multi-slice (data, feat) mesh laid out so ``feat`` rides ICI.

    On a multi-slice TPU deployment devices within a slice talk over ICI
    (fast) and across slices over DCN (slow). The ring-Gram ``ppermute`` and
    the per-step collectives must therefore stay intra-slice, with only the
    once-per-fit Gram psum crossing DCN. This builds the mesh from
    ``mesh_utils.create_hybrid_device_mesh`` (DCN × ICI topology-aware
    ordering) and collapses it to the package's (data, feat) axes with
    ``feat`` innermost — i.e. entirely inside a slice.

    ``slice_groups`` overrides topology discovery with an explicit
    partition of device indices into equal-size slices (outer list =
    slices). Use it when the runtime does not report ``slice_index``
    (multi-host CPU rehearsals, some plugin backends) but the operator
    knows which devices share a fast interconnect — and to validate the
    multi-slice layout on a virtual mesh (``__graft_entry__`` path 8).
    The resulting grid places each slice's devices contiguously along the
    data axis with ``feat`` entirely inside one slice, so every feat-axis
    collective is intra-slice by construction and only the data-axis psum
    spans slices.

    Falls back to the flat ``create_mesh`` when the runtime reports a single
    slice/granule (e.g. CPU or single-host TPU) and no ``slice_groups``.
    """
    devices = jax.devices()
    if slice_groups is not None:
        groups = [list(g) for g in slice_groups]
        sizes = {len(g) for g in groups}
        if len(sizes) != 1 or 0 in sizes:
            raise ValueError("slice_groups must be equal-size and non-empty")
        seen = [i for g in groups for i in g]
        if sorted(seen) != list(range(len(seen))):
            raise ValueError(
                "slice_groups must partition device indices 0..n-1 exactly"
            )
        if len(seen) > len(devices):
            raise ValueError(
                f"slice_groups name {len(seen)} devices but the runtime "
                f"has {len(devices)}"
            )
        per_slice = sizes.pop()
        if per_slice % feat:
            raise ValueError(
                f"feat={feat} must divide devices-per-slice={per_slice}"
            )
        rows = [
            [devices[i] for i in g[r * feat : (r + 1) * feat]]
            for g in groups
            for r in range(per_slice // feat)
        ]
        return Mesh(np.array(rows), (DATA_AXIS, FEAT_AXIS))
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if None in slice_ids or len(slice_ids) == 1:
        return create_mesh(feat=feat)
    from jax.experimental import mesh_utils

    n_slices = len(slice_ids)
    per_slice = len(devices) // n_slices
    if per_slice % feat:
        raise ValueError(f"feat={feat} must divide devices-per-slice={per_slice}")
    grid = mesh_utils.create_hybrid_device_mesh(
        mesh_shape=(per_slice // feat, feat),
        dcn_mesh_shape=(n_slices, 1),
        devices=devices,
    )
    return Mesh(grid, (DATA_AXIS, FEAT_AXIS))


def factor_mesh(n_devices: int) -> tuple[int, int]:
    """Pick a (data, feat) factorization: feat gets the largest power of two
    ≤ √n so both axes are exercised whenever possible."""
    feat = 1
    while feat * 2 <= int(math.isqrt(n_devices)) and n_devices % (feat * 2) == 0:
        feat *= 2
    return n_devices // feat, feat
