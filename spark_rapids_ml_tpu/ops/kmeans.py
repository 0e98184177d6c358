"""KMeans device kernels — the stretch estimator (BASELINE.json config 5).

The reference family's KMeans runs RAFT pairwise-distance + argmin kernels
on GPU; the TPU-native formulation puts both hot ops on the MXU:

- distances: ‖x−c‖² expanded to ‖x‖² + ‖c‖² − 2·x·cᵀ — the cross term is a
  [rows, n]×[n, k] matmul;
- centroid accumulation: scatter-by-label recast as a one-hot matmul
  onehotᵀ·x ([k, rows]×[rows, n]) — a second MXU pass instead of the GPU's
  atomic scatters, which TPUs don't like. For float32 rows it takes three
  bfloat16 passes where the cross term takes ``HIGHEST``'s six: a one-hot
  is 0s and 1s, which bfloat16 holds exactly, so its mid and lo parts are
  zero and only the three parts of the rows are left to multiply
  (``exact_bf16_parts``, ``_onehot_sums``). Three parts are all of it:
  8 + 8 + 8 significant bits are float32's 24, so no term that is not
  zero is dropped. Neither operand of the cross term is exact in
  bfloat16, so it is not touched.

Row blocks are processed under ``lax.scan`` so the [block, k] distance and
one-hot tiles stay bounded in VMEM/HBM regardless of partition size (rows·k
would otherwise explode at k=1000). Per-partition ``KMeansStats`` are the
usual commutative monoid, reduced by the same tree/psum machinery as PCA.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

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


class KMeansStats(NamedTuple):
    """Sufficient statistics of one Lloyd iteration over a row shard."""

    sums: jax.Array  # [k, n] — per-cluster feature sums
    counts: jax.Array  # [k]   — per-cluster row counts
    cost: jax.Array  # []    — sum of min squared distances (inertia)


def combine_kmeans_stats(a: KMeansStats, b: KMeansStats) -> KMeansStats:
    return KMeansStats(a.sums + b.sums, a.counts + b.counts, a.cost + b.cost)


def pairwise_sq_dists(
    x: jax.Array, centers: jax.Array, *, precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> jax.Array:
    """[rows, k] squared distances via the MXU cross-term expansion.

    Only the cross term honors the precision ``policy`` (bf16 operands or
    the opt-in int8 quantized path); the row/center norms stay full
    precision, so ranking error is bounded by the cross-term quantization
    alone."""
    x_sq = jnp.sum(x * x, axis=1, keepdims=True)
    c_sq = jnp.sum(centers * centers, axis=1)[None, :]
    if policy == PrecisionPolicy.INT8_DIST.value:
        cross = int8_quantized_matmul(x, centers.T)
    else:
        cross = policy_matmul(x, centers.T, precision=precision,
                              policy=policy)
    return jnp.clip(x_sq + c_sq - 2.0 * cross, 0.0, None)


def assign_clusters(
    x: jax.Array, centers: jax.Array, *, precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> tuple[jax.Array, jax.Array]:
    """(labels [rows], min squared distances [rows])."""
    d = pairwise_sq_dists(x, centers, precision=precision, policy=policy)
    return jnp.argmin(d, axis=1), jnp.min(d, axis=1)


def exact_bf16_parts(dtype) -> int | None:
    """How many bfloat16 parts hold a value of ``dtype`` exactly, or None
    where the one-hot product has no such cut to take.

    float32 has 24 significant bits and bfloat16 its exponent range with 8:
    three roundings to nearest, each of what the last left, hold all 24.
    float64 has no such cut (its range is not bfloat16's) and bfloat16
    needs none: both keep the product as it is written. The kernel and the
    counter ``kmeans.split_iterations`` ask this one rule."""
    return 3 if jnp.dtype(dtype) == jnp.float32 else None


def split_bf16(v: jax.Array) -> list[jax.Array]:
    """``v`` cut in its ``exact_bf16_parts`` bfloat16 arrays, which sum back
    to it exactly, largest first.

    Each part is the rest rounded to bfloat16's 8 significant bits
    (``lax.reduce_precision``, which no compiler folds away as it may an
    ``astype`` pair under excess precision) and the rest goes on as the
    exact difference."""
    out = []
    for _ in range(exact_bf16_parts(v.dtype) - 1):
        head = lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        out.append(head.astype(jnp.bfloat16))
        v = v - head
    out.append(v.astype(jnp.bfloat16))
    return out


def _onehot_sums(member: jax.Array, rows: jax.Array) -> jax.Array:
    """``member.T @ rows`` ([k, n], the rows' float32) in as many bfloat16
    passes as the rows have exact parts.

    A one-hot's entries are 0 and 1, which bfloat16 holds exactly, so of
    the six passes a float32 product takes at ``HIGHEST`` (hi·hi, hi·mid,
    mid·hi, hi·lo, mid·mid, lo·hi) the three that multiply the one-hot's
    mid and lo parts add zeros. What is left is the one-hot in bfloat16
    against each part of the rows, accumulated in float32: no term that is
    not zero is dropped. The parts lie side by side as one [rows, 3n]
    operand, so the one-hot is built once (three products build it three
    times and read 0.13 s a fit more at 6.3M rows: PERF.md §6, PR 38); they
    are cut inside the fusion, block by block, and never written."""
    parts = split_bf16(rows)
    prod = lax.dot_general(
        member.astype(jnp.bfloat16), jnp.concatenate(parts, axis=1),
        (((0,), (0,)), ((), ())), preferred_element_type=rows.dtype,
    )
    # smallest first, as a float32 sum is best taken
    *heads, total = jnp.split(prod, len(parts), axis=1)
    for head in reversed(heads):
        total = total + head
    return total


@partial(jax.jit, static_argnames=("block_rows", "policy"))
def kmeans_stats(
    x: jax.Array,
    centers: jax.Array,
    weights: jax.Array | None = None,
    *,
    block_rows: int = 8192,
    policy: str = DEFAULT_POLICY,
) -> KMeansStats:
    """One Lloyd accumulation pass over a row shard, scanned in blocks.

    ``weights`` masks padded rows (0 weight) so shape bucketing stays exact.

    The sums follow the dtype of the rows (``exact_bf16_parts``), with no
    knob: float32 rows are weighed in float32 (exact where a weight is 0
    or 1, one rounding where it is an instance weight) and summed as the
    one-hot in bfloat16 against their three bfloat16 parts, which is every
    non-zero term of the float32 product in half its passes; any other
    dtype takes the product as it is written. ``counts`` and ``cost`` are
    float32 sums of the weights and of the weighed distances either way.
    """
    rows, n = x.shape
    k = centers.shape[0]
    if weights is None:
        weights = jnp.ones((rows,), x.dtype)

    pad = (-rows) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, (0, pad))
    nb = x.shape[0] // block_rows
    xb = x.reshape(nb, block_rows, n)
    wb = weights.reshape(nb, block_rows)

    def step(carry, blk):
        sums, counts, cost = carry
        xi, wi = blk
        labels, dists = assign_clusters(xi, centers, policy=policy)
        member = labels[:, None] == jnp.arange(k, dtype=labels.dtype)[None, :]
        if exact_bf16_parts(x.dtype) is None:
            onehot = member.astype(x.dtype) * wi[:, None]
            sums = sums + jnp.matmul(onehot.T, xi, precision=DEFAULT_PRECISION)
            counts = counts + jnp.sum(onehot, axis=0)
        else:
            sums = sums + _onehot_sums(member, xi * wi[:, None])
            counts = counts + jnp.sum(jnp.where(member, wi[:, None], 0.0), axis=0)
        cost = cost + jnp.sum(dists * wi)
        return (sums, counts, cost), None

    init = (
        jnp.zeros((k, n), x.dtype),
        jnp.zeros((k,), x.dtype),
        jnp.zeros((), x.dtype),
    )
    (sums, counts, cost), _ = lax.scan(step, init, (xb, wb))
    return KMeansStats(sums, counts, cost)


def update_centers(stats: KMeansStats, old_centers: jax.Array) -> jax.Array:
    """New centroids = sums/counts; empty clusters keep their old center
    (Spark MLlib behavior)."""
    counts = stats.counts[:, None]
    safe = jnp.where(counts > 0, counts, jnp.ones_like(counts))
    return jnp.where(counts > 0, stats.sums / safe, old_centers)


def center_shift_sq(old: jax.Array, new: jax.Array) -> jax.Array:
    """Max squared movement of any centroid — the convergence criterion."""
    return jnp.max(jnp.sum((old - new) ** 2, axis=1))


def kmeans_plus_plus_init(
    key: jax.Array, x: jax.Array, k: int, *, precision=DEFAULT_PRECISION
) -> jax.Array:
    """k-means++ seeding on a (sub)sample, fully jittable.

    D²-weighted sequential sampling (Arthur & Vassilvitskii); the estimator
    layer samples the dataset down before calling so rows stays modest —
    the same role Spark's k-means|| plays for its distributed init. The
    unweighted special case of ``weighted_kmeans_plus_plus_init``.
    """
    return weighted_kmeans_plus_plus_init(
        key, x, jnp.ones((x.shape[0],), x.dtype), k, precision=precision
    )


def min_sq_dists(
    x: jax.Array, centers: jax.Array, *, precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> jax.Array:
    """[rows] squared distance of each row to its nearest center."""
    return jnp.min(
        pairwise_sq_dists(x, centers, precision=precision, policy=policy),
        axis=1,
    )


def weighted_kmeans_plus_plus_init(
    key: jax.Array,
    x: jax.Array,
    w: jax.Array,
    k: int,
    *,
    precision=DEFAULT_PRECISION,
) -> jax.Array:
    """Weighted k-means++ — the finishing step of k-means‖ (Bahmani et al.,
    §3.4): reduce the oversampled candidate set to k seeds, sampling ∝ w·D².

    ``w`` are candidate weights (how many data rows each candidate owns);
    zero-weight candidates can never be drawn.
    """
    rows = x.shape[0]
    w = w.astype(x.dtype)
    tiny = jnp.finfo(x.dtype).tiny

    key, sub = jax.random.split(key)
    first = jax.random.choice(sub, rows, p=w / jnp.maximum(jnp.sum(w), tiny))
    centers0 = jnp.zeros((k, x.shape[1]), x.dtype).at[0].set(x[first])
    d0 = jnp.sum((x - centers0[0][None, :]) ** 2, axis=1)

    def body(i, carry):
        centers, dists, key = carry
        key, sub = jax.random.split(key)
        scores = w * dists
        probs = scores / jnp.maximum(jnp.sum(scores), tiny)
        idx = jax.random.choice(sub, rows, p=probs)
        c = x[idx]
        centers = centers.at[i].set(c)
        d_new = jnp.sum((x - c[None, :]) ** 2, axis=1)
        return centers, jnp.minimum(dists, d_new), key

    centers, _, _ = lax.fori_loop(1, k, body, (centers0, d0, key))
    return centers
