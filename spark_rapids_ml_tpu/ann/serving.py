"""IVF indexes as the ``"ann"`` servable family.

A registered index is an ordinary :class:`~..serving.registry.ServableEntry`:
the inverted lists (centroids + packed buckets + spill) ARE the params
pytree, so the HBM fleet manager pages them like any model's weights, and
the query program is AOT-compiled per (bucket, nprobe) through the same
two-level cache every family uses — ``_query_kernel`` below is
lru-cached on its static knobs, the registry's ``_compiled_for`` is
lru-cached on (entry token, bucket), and the executable survives paging
because it is shape-keyed, not buffer-keyed.

The one wrinkle vs the other families is the result shape: a query answer
is (distances, ids) — two arrays, one of them integral — but the dispatch
path moves exactly one array. The kernel therefore returns a packed
[rows, 2k] block: columns [:k] are scores, columns [k:] are the int32
neighbor positions **bitcast** to the score dtype (f32 bit patterns carry
any int32 exactly; under x64 the ids ride f64, exact to 2^53). The
``finalize`` hook decodes, converts scores to metric distances (the exact
logic of ``ApproximateNearestNeighborsModel._kneighbors_matrix``), maps
positions through the index's item ids, and re-packs as float64
``distances | ids`` so the wire stays a single matrix. JSON carries the
ids exactly (≤ 2^53); the binary-f32 wire truncates ids above 2^24 — use
JSON for corpora past sixteen million items.
"""

from __future__ import annotations

import functools

import numpy as np

from spark_rapids_ml_tpu.telemetry import trace_range
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY


@functools.lru_cache(maxsize=None)
def _query_kernel(k: int, nprobe: int):
    """The pure ``kernel(params, x)`` for one (k, nprobe) operating
    point — cached so every registered index at the same point shares one
    traceable, and the registry's AOT cache keys stay stable."""
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_ml_tpu.ops import ivf as IVF

    def kernel(params, x):
        scores, idx = IVF.ivf_search(
            x,
            params["centroids"],
            params["bucket_items"],
            params["bucket_ids"],
            k,
            nprobe,
            spill_items=params["spill_items"],
            spill_ids=params["spill_ids"],
        )
        if scores.dtype == jnp.float32:
            enc = lax.bitcast_convert_type(idx, jnp.float32)
        else:  # x64: f64 mantissa carries any int32 exactly
            enc = idx.astype(scores.dtype)
        return jnp.concatenate([scores, enc], axis=1)

    return kernel


def _make_prepare(metric: str):
    from spark_rapids_ml_tpu.models.neighbors import _prepare_rows

    def prepare(mat: np.ndarray) -> np.ndarray:
        return _prepare_rows(mat, metric)

    return prepare


def _make_finalize(k: int, metric: str, item_ids: np.ndarray):
    """Host post hook: packed kernel block → float64 ``distances | ids``."""
    from spark_rapids_ml_tpu.models.neighbors import _finalize_distances

    def finalize(out: np.ndarray, true_rows: int) -> np.ndarray:
        out = out[:true_rows]
        scores = out[:, :k]
        enc = np.ascontiguousarray(out[:, k:])
        if enc.dtype == np.float32:
            idx = enc.view(np.int32)
        else:
            idx = np.rint(enc).astype(np.int64)
        # the cosine branch of ApproximateNearestNeighborsModel
        # ._kneighbors_matrix: normalized sqeuclidean / 2, with unfilled
        # slots (score −inf) kept at inf instead of clipping to a legal 2.0
        if metric == "cosine":
            sq = np.clip(-scores, 0.0, None)
            dists = np.where(
                np.isfinite(sq), np.clip(sq / 2.0, 0.0, 2.0), np.inf
            )
        else:
            dists = _finalize_distances(scores, metric)
        ids = np.where(idx >= 0, item_ids[np.clip(idx, 0, None)], -1)
        packed = np.empty((out.shape[0], 2 * k), dtype=np.float64)
        packed[:, :k] = dists
        packed[:, k:] = ids
        return packed

    return finalize


def unpack_query_result(packed: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(distances float64 [rows, k], ids int64 [rows, k]) from the packed
    wire matrix (−1 ids mark unfilled slots)."""
    packed = np.asarray(packed, dtype=np.float64)
    if packed.ndim != 2 or packed.shape[1] % 2:
        raise ValueError(
            f"packed query result must be [rows, 2k], got {packed.shape}"
        )
    k = packed.shape[1] // 2
    return packed[:, :k], np.rint(packed[:, k:]).astype(np.int64)


def servable_from_index(name: str, model) -> "ServableEntry":
    """Build the ``"ann"`` family entry for a fitted IVF index model
    (``ApproximateNearestNeighborsModel`` or its streamed subclass)."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.serving import registry as R

    if getattr(model, "bucketItems", None) is None or getattr(
        model, "centroids", None
    ) is None:
        raise TypeError(
            f"{type(model).__name__} is not a fitted IVF index (no packed "
            "buckets)"
        )
    n = int(model.centroids.shape[1])
    k = model.getK()
    nlist = int(model.bucketItems.shape[0])
    nprobe = min(model.getNprobe(), nlist)
    metric = model.getMetric()
    x_dtype = R._device_dtype()
    spill_items = model.spillItems
    spill_ids = model.spillIds
    if spill_items is None:
        spill_items = np.zeros((0, n), dtype=model.bucketItems.dtype)
        spill_ids = np.full(0, -1, dtype=np.int32)
    params = {
        "centroids": jnp.asarray(model.centroids, dtype=x_dtype),
        "bucket_items": jnp.asarray(model.bucketItems, dtype=x_dtype),
        "bucket_ids": jnp.asarray(model.bucketIds, dtype=jnp.int32),
        "spill_items": jnp.asarray(spill_items, dtype=x_dtype),
        "spill_ids": jnp.asarray(spill_ids, dtype=jnp.int32),
    }
    return R.ServableEntry(
        name=name,
        family="ann",
        model_cls=type(model).__name__,
        n_features=n,
        kernel=_query_kernel(k, nprobe),
        params=params,
        prepare=_make_prepare(metric),
        finalize=_make_finalize(k, metric, np.asarray(model.itemIds)),
        x_dtype=x_dtype,
        model=model,
    )


def register_index(name: str, model, *, bucket_list=None) -> "ServableEntry":
    """Register a fitted IVF index in the serving runtime: AOT-compiles the
    query program across the bucket ladder and books the inverted lists
    against the HBM fleet budget. After this returns, queries up to the
    ladder cap never compile."""
    from spark_rapids_ml_tpu.serving import registry as R

    return R.get_registry().register(name, model, bucket_list=bucket_list)


def query(
    name: str, queries: np.ndarray, *, timeout: float = 30.0
) -> tuple[np.ndarray, np.ndarray]:
    """(distances, ids) through the full serving path — the in-process
    transport of the shared micro-batcher, so concurrent callers coalesce
    into padded-bucket dispatches exactly like HTTP/UDS traffic."""
    from spark_rapids_ml_tpu.serving import client as serve_client

    queries = np.asarray(queries)
    packed = serve_client.predict(name, queries, timeout=timeout)
    REGISTRY.counter_inc("ann.queries", queries.shape[0], index=name)
    return unpack_query_result(packed)


def query_direct(
    name: str,
    queries: np.ndarray,
    *,
    k: int | None = None,
    nprobe: int | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """(distances, ids) straight off the registered model, bypassing the
    batcher — the recall-sweep path: ``nprobe``/``k`` override the
    registered operating point per call (tools/ann_report.py probes one
    index at many operating points without re-registering)."""
    from spark_rapids_ml_tpu.serving import registry as R

    entry = R.get_registry().get(name)
    if entry.family != "ann":
        raise TypeError(f"{name!r} is a {entry.family} servable, not ann")
    model = entry.model
    queries = np.asarray(queries)
    with trace_range("ann query"):
        if hasattr(model, "search"):
            dists, ids = model.search(queries, k=k, nprobe=nprobe)
        elif nprobe is None:  # a plain ApproximateNearestNeighborsModel
            dists, ids = model._kneighbors_matrix(queries, k)
        else:
            prev = model._paramMap.get("nprobe")
            model._set(nprobe=int(nprobe))
            try:
                dists, ids = model._kneighbors_matrix(queries, k)
            finally:
                if prev is None:
                    del model._paramMap["nprobe"]
                else:
                    model._set(nprobe=prev)
    REGISTRY.counter_inc("ann.queries", queries.shape[0], index=name)
    return dists, ids
