"""Mesh-sharded exact k-NN — distributed brute force as one SPMD program.

Same distribution shape as the other mesh fits (parallel/gram.py,
parallel/kmeans.py): the CORPUS is row-sharded over the ``data`` axis,
queries are replicated, and each device streams its shard through the
blocked tournament kernel (ops/neighbors.knn_topk) with its global index
base. One ``all_gather`` over the data axis brings every shard's [q, k]
candidates together and a final ``merge_topk`` keeps the global best —
k·ndev candidates cross ICI per query instead of the full distance row,
which is the classic TPU distributed top-k recipe.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import neighbors as NN
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


@lru_cache(maxsize=32)
def make_sharded_knn(
    mesh: Mesh, k: int, *, metric: str = "sqeuclidean", block_rows: int = 8192
):
    """Compile ``run(corpus, valid, queries) -> (scores, indices)``.

    ``corpus [rows, n]`` and ``valid [rows]`` data-sharded (equal shards,
    pad rows carrying valid=0), ``queries [q, n]`` replicated; replicated
    ``[q, k]`` outputs, scores descending-is-better (see ops/neighbors).
    ``k`` must not exceed the corpus rows on any single shard beyond what
    the shard holds — each shard contributes ``min(k, shard_rows)``
    candidates, padded to k with −inf so the cross-shard merge stays
    static-shaped.
    """

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def run(corpus, valid, queries):
        me = lax.axis_index(DATA_AXIS)
        shard_rows = corpus.shape[0]
        q = queries.shape[0]
        kk = min(k, shard_rows)
        scores, idx = NN.knn_topk(
            queries,
            corpus,
            valid,
            kk,
            metric=metric,
            block_rows=min(block_rows, shard_rows),
        )
        idx = idx + jnp.where(idx >= 0, me * shard_rows, 0).astype(idx.dtype)
        if kk < k:
            pad = k - kk
            scores = jnp.concatenate(
                [scores, jnp.full((q, pad), -jnp.inf, scores.dtype)], axis=1
            )
            idx = jnp.concatenate(
                [idx, jnp.full((q, pad), jnp.int32(-1))], axis=1
            )
        g_scores = lax.all_gather(scores, DATA_AXIS)  # [ndev, q, k]
        g_idx = lax.all_gather(idx, DATA_AXIS)
        ndev = g_scores.shape[0]
        flat_s = jnp.moveaxis(g_scores, 0, 1).reshape(q, ndev * k)
        flat_i = jnp.moveaxis(g_idx, 0, 1).reshape(q, ndev * k)
        best, which = lax.top_k(flat_s, k)
        return best, jnp.take_along_axis(flat_i, which, axis=1)

    return jax.jit(
        run,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
