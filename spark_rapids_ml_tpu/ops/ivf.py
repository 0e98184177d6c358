"""IVF-Flat approximate k-NN device kernels.

The spark-rapids-ml family exposes ``approximate_nearest_neighbors`` with
cuML's ivfflat algorithm: cluster the corpus (KMeans), store each
cluster's members contiguously, and answer queries by scanning only the
``nprobe`` nearest clusters. This module is that algorithm TPU-first:

- the coarse quantizer IS this package's KMeans (ops/kmeans.py);
- cluster buckets are a dense padded [nlist, cap, n] tensor with a
  validity mask — XLA-friendly static shapes instead of CSR indirection.
  ``cap`` is a *percentile* of the cluster sizes (TPU_ML_ANN_CAP_PERCENTILE,
  default 99), not the largest cluster: one hot cluster no longer inflates
  the whole tensor. Members beyond the cap land on an exact **spill list**
  that every query scans unconditionally — nothing is ever dropped, so
  recall loss comes only from probing, never from indexing;
- search probes clusters one at a time under a Python-static ``nprobe``
  loop, blocked over query rows: each step gathers the probed buckets for
  one query tile ([block, cap, n] — the tile stays cache/VMEM-resident
  across its scoring, instead of one monolithic [q, cap, n] gather round-
  tripping through memory) and scores it with a batched matmul
  (``einsum('qn,qcn->qc')``), merging into a running top-k with the same
  tournament primitive exact k-NN uses (ops/neighbors.merge_topk). The
  spill list is scored with one reused [q, n]×[n, spill] MXU matmul;
- the distance cross terms honor the ``PrecisionPolicy``
  vocabulary exactly like exact k-NN (ops/neighbors._block_scores):
  ``bf16_f32acc`` casts operands to bfloat16 with f32 MXU accumulation,
  ``int8_dist`` runs the symmetric per-tensor int8 quantized cross term.
  Norms always stay full precision. Observed parity vs the f32 kernel on
  unit-scale data: bf16 distances agree to ~1e-2 relative, int8 to ~5e-2
  (tests/test_ivf.py pins both tolerances).

Honest TPU note (why the default stays exact brute force): the MXU makes
the full [q, rows] distance matmul so cheap that IVF's flop savings only
beat the gather overhead at large corpus sizes; below that, exact k-NN is
both faster AND exact. ivfflat is here for API + recall parity with the
reference family, and because at ~10⁷+ rows the memory story flips.

With ``nprobe == nlist`` every cluster (and the spill list) is scanned,
so f32 results must equal exact brute-force k-NN (the tests assert this).
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from spark_rapids_ml_tpu.ops.policy import PrecisionPolicy
from spark_rapids_ml_tpu.ops.linalg import (
    DEFAULT_PRECISION,
    DEFAULT_POLICY,
    int8_quantized_matmul,
    policy_matmul,
)
from spark_rapids_ml_tpu.ops.neighbors import merge_topk
from spark_rapids_ml_tpu.utils import knobs

ANN_CAP_PERCENTILE_VAR = knobs.ANN_CAP_PERCENTILE.name

# query rows per probe-scan tile: the gathered [block, cap, n] slab plus
# its [block, cap] scores stay cache/VMEM-resident through the cross term
# and merge, and 128 rows keeps the MXU tile shape happy
_SCAN_BLOCK_ROWS = 128


class IvfBuckets(NamedTuple):
    """One packed IVF index: dense per-cluster buckets + exact spill list.

    ``bucket_ids``/``spill_ids`` hold 0-based global item positions with
    −1 on padding slots. ``spill_items`` is [spill_pad, n] (zero rows when
    no cluster overflowed its cap) and is scanned by every query — spilled
    members cost one reused matmul, not a recall hole.
    """

    bucket_items: np.ndarray  # [nlist, cap, n]
    bucket_ids: np.ndarray    # [nlist, cap] int32, −1 = pad
    cap: int
    spill_items: np.ndarray   # [spill_pad, n]
    spill_ids: np.ndarray     # [spill_pad] int32, −1 = pad


def bucket_cap(counts: np.ndarray, cap_percentile: float) -> int:
    """The dense-bucket capacity for observed cluster sizes: the
    ``cap_percentile``-th percentile (ceil), floored at 1. 100 degenerates
    to the legacy pad-to-largest-cluster packing (empty spill)."""
    if not 0.0 < cap_percentile <= 100.0:
        raise ValueError(
            f"cap_percentile={cap_percentile} must be in (0, 100]"
        )
    if cap_percentile >= 100.0:
        return max(1, int(counts.max()))
    return max(1, int(np.ceil(np.percentile(counts, cap_percentile))))


def build_ivf_buckets(
    items: np.ndarray, labels: np.ndarray, nlist: int,
    *, cap_percentile: float | None = None,
) -> IvfBuckets:
    """Host-side packing of an assigned corpus into :class:`IvfBuckets`.

    Every item is stored — the first ``cap`` members of each cluster (in
    stable corpus order) fill the dense [nlist, cap, n] tensor; overflow
    beyond the cap goes to the spill list, padded to a power of two so
    rebuilt indexes of similar skew reuse compiled search programs. With
    the default 99th-percentile cap a single hot cluster costs O(its own
    size) spill rows instead of inflating every bucket (the former
    cap = largest-cluster packing made a 100:1-skewed corpus allocate
    ~100x the corpus footprint in padding).
    """
    if cap_percentile is None:
        cap_percentile = float(
            os.environ.get(
                ANN_CAP_PERCENTILE_VAR, knobs.ANN_CAP_PERCENTILE.default
            )
        )
    counts = np.bincount(labels, minlength=nlist)
    cap = bucket_cap(counts, cap_percentile)
    n = items.shape[1]
    bucket_items = np.zeros((nlist, cap, n), dtype=items.dtype)
    bucket_ids = np.full((nlist, cap), -1, dtype=np.int32)
    # fully vectorized packing (no per-item Python at the 10⁷-row scale
    # this index targets): sort by label, position = rank within cluster
    order = np.argsort(labels, kind="stable")
    sorted_labels = labels[order]
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos = np.arange(len(order)) - starts[sorted_labels]
    dense = pos < cap
    bucket_items[sorted_labels[dense], pos[dense]] = items[order[dense]]
    bucket_ids[sorted_labels[dense], pos[dense]] = order[dense]
    spill = order[~dense]
    spill_pad = 0 if spill.size == 0 else 1 << (int(spill.size) - 1).bit_length()
    spill_items = np.zeros((spill_pad, n), dtype=items.dtype)
    spill_ids = np.full(spill_pad, -1, dtype=np.int32)
    spill_items[: spill.size] = items[spill]
    spill_ids[: spill.size] = spill
    return IvfBuckets(bucket_items, bucket_ids, cap, spill_items, spill_ids)


def _policy_cross(a, b_t, precision, policy):
    """[q, m] cross term ``a @ b_t`` under the precision policy (the 2-D
    dispatch exact k-NN uses; norms never come through here)."""
    if policy == PrecisionPolicy.INT8_DIST.value:
        return int8_quantized_matmul(a, b_t)
    return policy_matmul(a, b_t, precision=precision, policy=policy)


def _policy_bucket_cross(queries, xj, precision, policy):
    """[q, cap] batched cross term ``einsum('qn,qcn->qc')`` under the
    precision policy — the probe-step analog of :func:`_policy_cross`."""
    if policy == PrecisionPolicy.INT8_DIST.value:
        def quant(t):
            amax = jnp.max(jnp.abs(t))
            scale = jnp.where(amax > 0, amax / 127.0, jnp.ones_like(amax))
            q = jnp.clip(jnp.round(t / scale), -127.0, 127.0)
            return q.astype(jnp.int8), scale
        qq, sq = quant(queries)
        qx, sx = quant(xj)
        acc = jnp.einsum(
            "qn,qcn->qc", qq, qx, preferred_element_type=jnp.int32
        )
        return acc.astype(queries.dtype) * (sq * sx)
    if policy == PrecisionPolicy.BF16_F32ACC.value:
        out = jnp.einsum(
            "qn,qcn->qc",
            queries.astype(jnp.bfloat16),
            xj.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return out.astype(queries.dtype)
    return jnp.einsum("qn,qcn->qc", queries, xj, precision=precision)


@partial(jax.jit, static_argnames=("k", "nprobe", "policy"))
def ivf_search(
    queries: jax.Array,  # [q, n]
    centroids: jax.Array,  # [nlist, n]
    bucket_items: jax.Array,  # [nlist, cap, n]
    bucket_ids: jax.Array,  # [nlist, cap] int32, −1 = pad
    k: int,
    nprobe: int,
    *,
    spill_items: jax.Array | None = None,  # [spill_pad, n]
    spill_ids: jax.Array | None = None,  # [spill_pad] int32, −1 = pad
    precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> tuple[jax.Array, jax.Array]:
    """(scores [q, k] descending −‖·‖², global ids [q, k]) over the
    ``nprobe`` nearest clusters per query, plus the whole spill list."""
    q, n = queries.shape
    nlist, cap = bucket_ids.shape
    nprobe = min(nprobe, nlist)

    # coarse pass: one [q, nlist] MXU matmul picks the probe set
    q_sq = jnp.sum(queries * queries, axis=1, keepdims=True)
    c_sq = jnp.sum(centroids * centroids, axis=1)[None, :]
    cd = q_sq + c_sq - 2.0 * _policy_cross(
        queries, centroids.T, precision, policy
    )
    _, probe = lax.top_k(-cd, nprobe)  # [q, nprobe]

    neg_inf = jnp.asarray(-jnp.inf, queries.dtype)

    # probe scan, blocked over queries: one monolithic [q, cap, n] gather
    # forces the whole gathered tensor through memory before the scoring
    # einsum can start; a [block, cap, n] tile instead stays cache/VMEM-
    # resident across its cross term, norms, and top-k merge (measured ~4x
    # on the scoring path at q=2048, cap=256). Blocking only partitions
    # query rows — every query still merges its probes in the same order
    # with the same values, so results are bit-identical to the unblocked
    # formulation.
    block = min(_SCAN_BLOCK_ROWS, q)
    n_blocks = -(-q // block)
    qpad = n_blocks * block
    pad = qpad - q

    def pad_rows(a):
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))

    def block_step(_, args):
        qg, probeg, q_sqg = args  # [block, n], [block, nprobe], [block, 1]
        best = jnp.full((block, k), neg_inf, queries.dtype)
        bidx = jnp.full((block, k), jnp.int32(-1))

        def step(carry, j):
            best, bidx = carry
            cluster = probeg[:, j]  # [block]
            xj = bucket_items[cluster]  # [block, cap, n] gather
            ids = bucket_ids[cluster]  # [block, cap]
            cross = _policy_bucket_cross(qg, xj, precision, policy)
            x_sq = jnp.sum(xj * xj, axis=2)
            scores = -(q_sqg + x_sq - 2.0 * cross)
            scores = jnp.where(ids >= 0, scores, neg_inf)
            return merge_topk(best, bidx, scores, ids, k), None

        (best, bidx), _ = lax.scan(
            step, (best, bidx), jnp.arange(nprobe)
        )
        return None, (best, bidx)

    _, (best, bidx) = lax.scan(
        block_step,
        None,
        (
            pad_rows(queries).reshape(n_blocks, block, n),
            pad_rows(probe).reshape(n_blocks, block, nprobe),
            pad_rows(q_sq).reshape(n_blocks, block, 1),
        ),
    )
    best = best.reshape(qpad, k)[:q]
    bidx = bidx.reshape(qpad, k)[:q]

    # exact spill tail: overflowed members ride one reused [q, spill]
    # matmul per batch — cheap precisely because it has cross-query reuse,
    # unlike the per-query bucket gathers above
    if spill_items is not None and spill_items.shape[0] > 0:
        s_sq = jnp.sum(spill_items * spill_items, axis=1)[None, :]
        cross = _policy_cross(queries, spill_items.T, precision, policy)
        scores = -(q_sq + s_sq - 2.0 * cross)
        scores = jnp.where(spill_ids[None, :] >= 0, scores, neg_inf)
        ids = jnp.broadcast_to(spill_ids[None, :], scores.shape)
        best, bidx = merge_topk(best, bidx, scores, ids, k)
    return best, bidx
