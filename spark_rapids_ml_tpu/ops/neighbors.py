"""Exact k-nearest-neighbors device kernels — brute force on the MXU.

The modern spark-rapids-ml family ships an exact brute-force NearestNeighbors
built on RAFT's pairwise-distance + k-selection GPU kernels; the 22.12
reference this framework re-designs (SURVEY.md §2) stops at PCA, so this is
a capability-add in the same spirit as its KMeans sibling (ops/kmeans.py).

TPU-first formulation:

- distances are the same ‖x‖² + ‖y‖² − 2·x·yᵀ cross-term expansion KMeans
  uses — the [q, n]×[n, block] cross term is one MXU matmul per corpus
  block;
- k-selection is ``lax.top_k`` on NEGATED distances, merged blockwise: the
  running [q, k] winners concatenate with each block's [q, block] scores and
  a single top_k keeps the best k — a streaming tournament that never
  materializes the full [q, rows] distance matrix (HBM-bound otherwise);
- the corpus is scanned in fixed-size row blocks under ``lax.scan`` so one
  XLA program covers any corpus length with static shapes.

The mesh-sharded version (parallel/neighbors.py) runs this per shard and
merges candidates with one ``all_gather`` over the data axis.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.ops.policy import PrecisionPolicy
from spark_rapids_ml_tpu.ops.linalg import (
    DEFAULT_PRECISION,
    DEFAULT_POLICY,
    int8_quantized_matmul,
    policy_matmul,
)

#: metric → (score sign) — kernels rank by LARGEST score internally.
#: "sqeuclidean": score = −‖x−y‖² (top-k = nearest);
#: "dot":         score = x·y     (top-k = largest inner product).
_METRICS = ("sqeuclidean", "dot")


def _block_scores(
    queries: jax.Array, block: jax.Array, metric: str, precision,
    policy: str = DEFAULT_POLICY,
) -> jax.Array:
    """[q, block] ranking scores (larger = better neighbor).

    The cross term honors the precision ``policy`` (bf16 operands or the
    opt-in int8 quantized candidate scoring); norms stay full precision."""
    if policy == PrecisionPolicy.INT8_DIST.value:
        cross = int8_quantized_matmul(queries, block.T)
    else:
        cross = policy_matmul(queries, block.T, precision=precision,
                              policy=policy)
    if metric == "dot":
        return cross
    q_sq = jnp.sum(queries * queries, axis=1, keepdims=True)
    b_sq = jnp.sum(block * block, axis=1)[None, :]
    return -jnp.clip(q_sq + b_sq - 2.0 * cross, 0.0, None)


def merge_topk(
    scores_a: jax.Array,
    idx_a: jax.Array,
    scores_b: jax.Array,
    idx_b: jax.Array,
    k: int,
) -> tuple[jax.Array, jax.Array]:
    """Merge two candidate sets (scores descending-is-better) into the best
    k: one concat + one ``lax.top_k`` — the tournament step both the blocked
    scan and the cross-shard gather reuse."""
    scores = jnp.concatenate([scores_a, scores_b], axis=1)
    idx = jnp.concatenate([idx_a, idx_b], axis=1)
    best, which = lax.top_k(scores, k)
    return best, jnp.take_along_axis(idx, which, axis=1)


@partial(
    jax.jit,
    static_argnames=("k", "metric", "block_rows", "index_offset", "policy"),
)
def knn_topk(
    queries: jax.Array,
    corpus: jax.Array,
    valid: jax.Array,
    k: int,
    *,
    metric: str = "sqeuclidean",
    block_rows: int = 8192,
    index_offset: int = 0,
    precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> tuple[jax.Array, jax.Array]:
    """Best-k corpus rows per query, streamed over corpus blocks.

    ``valid`` masks corpus rows ([rows] bool/float; pad rows 0) — invalid
    rows score −inf and can never be selected. Returns
    ``(scores [q, k] descending, indices [q, k] int32)`` with indices
    offset by ``index_offset`` (the shard's global row base). Scores are
    negated squared distances for ``metric="sqeuclidean"`` and raw inner
    products for ``metric="dot"`` — the model layer converts to user-facing
    distances.
    """
    if metric not in _METRICS:
        raise ValueError(f"metric must be one of {_METRICS}, got {metric!r}")
    rows, n = corpus.shape
    q = queries.shape[0]
    if k > rows:
        raise ValueError(f"k={k} exceeds corpus rows={rows}")
    blk = min(block_rows, rows)
    nblk = -(-rows // blk)
    pad = nblk * blk - rows
    corpus = jnp.pad(corpus, ((0, pad), (0, 0)))
    validf = jnp.pad(valid.astype(bool), (0, pad), constant_values=False)
    blocks = corpus.reshape(nblk, blk, n)
    vblocks = validf.reshape(nblk, blk)
    base = index_offset + jnp.arange(nblk, dtype=jnp.int32) * blk

    neg_inf = jnp.asarray(-jnp.inf, queries.dtype)

    def step(carry, xs):
        best, bidx = carry
        block, vblock, b0 = xs
        scores = _block_scores(queries, block, metric, precision, policy)
        scores = jnp.where(vblock[None, :], scores, neg_inf)
        ids = jnp.broadcast_to(
            b0 + jnp.arange(blk, dtype=jnp.int32)[None, :], (q, blk)
        )
        return merge_topk(best, bidx, scores, ids, k), None

    init = (
        jnp.full((q, k), neg_inf, queries.dtype),
        jnp.full((q, k), jnp.int32(-1)),
    )
    (best, bidx), _ = lax.scan(step, init, (blocks, vblocks, base))
    return best, bidx
