"""Mesh-sharded random-forest build — level histograms psum'd over rows.

The per-level [nodes, k, bins, stats] histogram in ops/forest.build_tree is
a commutative monoid over rows, so the distributed build is the same shape
as every other mesh fit here (and as Spark MLlib's own RF aggregation): rows
sharded over the ``data`` axis, each device computes its shard's histogram,
ONE psum per level combines them, and every device takes identical split
decisions while routing only its own rows. The whole forest builds inside a
single shard_map program, ``jit__forest``; at a data axis of 1 it is the
one-device program of the resident fit.

Beside it, the programs that make a resident fit's inputs on the mesh: the
rows binned where they lie (:func:`make_sharded_binner`) and the trees'
bootstrap weights laid out as the rows are (:func:`make_sharded_weights`).
"""

from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import forest as FO
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

#: rows binned at a time on a device: the compares of a block are summed in
#: int32 before the narrow bins are written, so the int32 sum is a block's
_BIN_BLOCK_ROWS = 8192


@lru_cache(maxsize=32)
def make_sharded_forest(
    mesh: Mesh,
    *,
    max_depth: int,
    n_bins: int,
    k_features: int,
    impurity: str,
    group: int | None = None,
    capacity: int | None = None,
    block_bytes: int | None = None,
):
    """Compile ``run(keys, binned, row_stats, weights, min_inst, min_gain)
    -> TreeArrays [T, ...]`` with rows data-sharded (equal shards; pad rows
    carry weight 0) and trees/outputs replicated, ``group`` trees side by
    side (``ops.forest.tree_group``; one at a time by default), each over
    ``capacity`` rows of its shard (``ops.forest.row_capacity``), each level
    in blocks of slots of at most ``block_bytes`` (``ops.forest.level_plan``;
    one block a level by default). The same
    trees as the single-device :func:`ops.forest.build_forest` (tests
    assert equality: histogram sums are integer-valued, so psum order
    cannot perturb the argmax)."""

    def _forest(keys, binned, row_stats, weights, min_inst, min_gain):
        return FO.grow_forest(
            keys, binned, row_stats, weights, min_inst, min_gain,
            max_depth=max_depth, n_bins=n_bins, k_features=k_features,
            impurity=impurity, group=group, capacity=capacity,
            axis_name=DATA_AXIS, block_bytes=block_bytes,
        )

    specs = (
        P(), P(DATA_AXIS, None), P(DATA_AXIS, None), P(None, DATA_AXIS),
        P(), P(),
    )
    sharded = jax.shard_map(
        _forest, mesh=mesh, in_specs=specs, out_specs=P(), check_vma=False,
    )
    # a private function's name is the program's in a device trace
    # (``jit__forest``): benchmarks/layer_metrics/forest_hist_roofline.json
    # reads it, tests/test_forest_resident.py pins it
    return jax.jit(
        sharded,
        in_shardings=tuple(NamedSharding(mesh, s) for s in specs),
        out_shardings=NamedSharding(mesh, P()),
    )


@lru_cache(maxsize=32)
def make_sharded_binner(mesh: Mesh, n_bins: int):
    """``bin(xs, edges) -> bins``: each device bins its own rows
    (``ops.forest.bin_rows``, in blocks of :data:`_BIN_BLOCK_ROWS`) into
    ``ops.forest.bins_dtype(n_bins)``, laid out as the rows are."""

    def _bin(x, edges):
        rows, n = x.shape
        block = math.gcd(rows, _BIN_BLOCK_ROWS)

        def one(i, out):
            xb = lax.dynamic_slice_in_dim(x, i * block, block)
            bins = FO.bin_rows(xb, edges, n_bins)
            return lax.dynamic_update_slice_in_dim(out, bins, i * block, 0)

        # sliced where the rows lie: a reshape would copy them
        return lax.fori_loop(
            0, rows // block, one, jnp.zeros((rows, n), FO.bins_dtype(n_bins))
        )

    sharded = jax.shard_map(
        _bin, mesh=mesh, in_specs=(P(DATA_AXIS, None), P()),
        out_specs=P(DATA_AXIS, None), check_vma=False,
    )
    return jax.jit(sharded, out_shardings=NamedSharding(mesh, P(DATA_AXIS, None)))


@lru_cache(maxsize=32)
def make_sharded_weights(
    mesh: Mesh, *, seed: int, n_trees: int, rows: int, bootstrap: bool,
    rate: float,
):
    """``weights(ws) -> [T, padded_rows]``: the trees' sample counts of the
    ``rows`` true rows (``ops.forest.bootstrap_weights``) times the ingest's
    weights, which are 0 on pad rows, data-sharded as the rows are."""

    def _weights(ws):
        counts = FO.bootstrap_weights(
            seed, n_trees, rows, bootstrap=bootstrap, rate=rate
        )
        counts = jnp.pad(counts, ((0, 0), (0, ws.shape[0] - rows)))
        return counts.astype(ws.dtype) * ws[None, :]

    return jax.jit(
        _weights, out_shardings=NamedSharding(mesh, P(None, DATA_AXIS))
    )


@jax.jit
def quantile_edges(
    sample: jax.Array,  # [m, F] rows whose quantiles make the edges
    sample_w: jax.Array,  # [m] their weights: a row of weight 0 is left out
    qs: jax.Array,  # [B − 1] interior quantile levels
) -> jax.Array:
    """[F, B − 1] bin edges: each feature's quantiles of the sample's rows
    of positive weight, interpolated linearly (``np.quantile``'s default)."""
    x = jnp.where(sample_w[:, None] > 0, sample, jnp.nan)
    return jnp.nanquantile(x, qs.astype(sample.dtype), axis=0).T


@jax.jit
def label_facts(ys: jax.Array) -> jax.Array:
    """(least, largest, count of fractional) of device labels."""
    return jnp.stack([
        jnp.min(ys), jnp.max(ys), jnp.sum(ys != jnp.round(ys)).astype(ys.dtype)
    ])


@partial(jax.jit, static_argnums=1)
def one_hot(ys: jax.Array, n_classes: int) -> jax.Array:
    """[rows, C] class rows of device labels, laid out as the labels are."""
    return jax.nn.one_hot(ys.astype(jnp.int32), n_classes, dtype=ys.dtype)


@jax.jit
def moments(ys: jax.Array) -> jax.Array:
    """[rows, 3] regression stats [1, y, y²] of device labels."""
    return jnp.stack([jnp.ones_like(ys), ys, ys * ys], axis=1)
