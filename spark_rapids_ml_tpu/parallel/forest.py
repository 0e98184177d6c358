"""Mesh-sharded random-forest build — level histograms psum'd over rows.

The per-level [features, nodes, bins, stats] histogram in
ops/forest.build_tree is a commutative monoid over rows, so the
distributed build is the same shape as every other mesh fit here (and as
Spark MLlib's own RF aggregation): rows sharded over the ``data`` axis,
each device computes its shard's histogram, ONE psum per level combines
them, and every device takes identical split decisions while routing only
its own rows. The whole forest (vmap over trees) builds inside a single
shard_map program.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import forest as FO
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


@lru_cache(maxsize=32)
def make_sharded_forest(
    mesh: Mesh,
    *,
    max_depth: int,
    n_bins: int,
    k_features: int,
    impurity: str,
):
    """Compile ``run(keys, binned, row_stats, weights, min_inst, min_gain)
    -> TreeArrays [T, ...]`` with rows data-sharded (equal shards; pad rows
    carry weight 0) and trees/outputs replicated. Bit-identical to the
    single-device :func:`ops.forest.build_forest` (tests assert equality:
    histogram sums are integer-valued in f64, so psum order cannot
    perturb the argmax)."""

    def body(keys, binned, row_stats, weights, min_inst, min_gain):
        return jax.vmap(
            lambda k, w: FO.build_tree(
                k, binned, row_stats, w, min_inst, min_gain,
                max_depth=max_depth, n_bins=n_bins, k_features=k_features,
                impurity=impurity, axis_name=DATA_AXIS,
            )
        )(keys, weights)

    sharded = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(), P(DATA_AXIS, None), P(DATA_AXIS, None), P(None, DATA_AXIS),
            P(), P(),
        ),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(
        sharded,
        in_shardings=(
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(None, DATA_AXIS)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
