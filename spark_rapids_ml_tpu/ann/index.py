"""Out-of-core IVF-Flat index build: streamed quantizer fit + bucket pack.

``ApproximateNearestNeighbors`` (models/neighbors.py) fits from a fully
materialized item matrix — fine for corpora that fit one host allocation
next to the packed index, a wall at the 10⁷+-row scale where IVF actually
beats exact search (ops/ivf.py module docstring). ``IVFFlatIndex`` is the
same index built without ever materializing the corpus on device:

1. **Sample** — one streaming pass fills a seeded reservoir
   (``TPU_ML_ANN_SAMPLE_ROWS``, algorithm R) that the kmeans|| init
   (Bahmani et al. — cost-proportional oversampling rounds, then a
   weighted k-means++ reduction, the same recipe as models/kmeans.py)
   trains the initial coarse quantizer on.
2. **Lloyd over the stream** — each iteration is one ``stream_fold`` pass:
   the chunk statistics fold into a donated ``(sums, counts, cost)``
   carry with the centers riding the carry as a traced passthrough (one
   compiled program for every iteration). With more than one device the
   fold is mesh-sharded via ``parallel/gram``'s stacked-partials protocol:
   chunks shard over the data axis (``ChunkPut``), each device folds its
   shard collective-free, and one allreduce per iteration
   (``finalize_chunk_fold``) produces the replicated statistics. Between
   passes, empty cells reseed at farthest-point sample rows and overfull
   cells are split (``_rebalance_cells``) — without this, an init that
   double-covers one natural cluster permanently merges another pair and
   doubles the packed bucket cap.
3. **Assign + pack** — a final streamed pass assigns chunks to centroids
   on device, then packs them host-side into the skew-capped
   [nlist, cap, n] buckets + exact spill list of ops/ivf.py using running
   per-cluster fill cursors — identical output to ``build_ivf_buckets``
   on the concatenated corpus, at O(chunk) device and O(index) host
   memory.

The product is an :class:`IVFFlatIndexModel` — the served/query surface of
``ApproximateNearestNeighborsModel`` (same kernels, same persistence
format via utils/persistence.py) plus a per-call ``search(..., nprobe=)``
override for recall sweeps, and it registers into the serving runtime as
the ``"ann"`` family (ann/serving.py).

Sources must be **re-iterable** (the build makes several passes): a
[rows, n] ndarray, a list/tuple of chunk arrays, or a zero-arg callable
returning a fresh chunk iterator.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from spark_rapids_ml_tpu.models.base import Estimator
from spark_rapids_ml_tpu.models.neighbors import (
    ApproximateNearestNeighborsModel,
    _ANNParams,
    _prepare_rows,
)
from spark_rapids_ml_tpu.ops import ivf as IVF
from spark_rapids_ml_tpu.ops import kmeans as KM
from spark_rapids_ml_tpu.telemetry import trace_range
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.utils import knobs

ANN_SAMPLE_ROWS_VAR = knobs.ANN_SAMPLE_ROWS.name

#: Convergence floor for the streamed Lloyd loop (squared center shift).
_SHIFT_TOL = 1e-4


def sample_rows_budget() -> int:
    """The quantizer training-sample row budget (``TPU_ML_ANN_SAMPLE_ROWS``;
    0 means the whole stream feeds the init)."""
    raw = os.environ.get(ANN_SAMPLE_ROWS_VAR, "")
    try:
        return max(0, int(raw) if raw else int(knobs.ANN_SAMPLE_ROWS.default))
    except ValueError:
        return int(knobs.ANN_SAMPLE_ROWS.default)


# -- streamed Lloyd fold -----------------------------------------------------


class _LloydCarry(NamedTuple):
    """The donated stream_fold carry of one Lloyd pass: running weighted
    cluster statistics plus the centers as a traced passthrough — centers
    change every iteration WITHOUT recompiling the fold program."""

    sums: jax.Array    # [k, n]
    counts: jax.Array  # [k]
    cost: jax.Array    # []
    centers: jax.Array  # [k, n]


def _lloyd_step(carry, x, w):
    st = KM.kmeans_stats(x, carry.centers, weights=w)
    return _LloydCarry(
        carry.sums + st.sums,
        carry.counts + st.counts,
        carry.cost + st.cost,
        carry.centers,
    )


#: Module-level jit with the carry donated — the [k, n] accumulator updates
#: in place chunk after chunk (stream_fold's donation contract).
_LLOYD_FOLD_STEP = jax.jit(_lloyd_step, donate_argnums=0)

#: Chunk assignment for the pack pass (models/kmeans.py idiom: one
#: module-level jitted program, centers as a traced argument).
_ASSIGN = jax.jit(KM.assign_clusters)


@lru_cache(maxsize=None)
def _lloyd_mesh_fold_prog(mesh):
    """Mesh-sharded Lloyd fold: the carry is the ``KMeansStats`` of a pass as
    [ndev, ...] stacked partials (parallel/gram stacked-partials protocol),
    each device folds its chunk shard into its own slice collective-free;
    the per-iteration allreduce happens once at finalize, not per chunk.
    The centers are a replicated traced argument: they change every
    iteration without recompiling the program."""
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(), P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    def _fold(carry, centers, xl, wl):
        st = KM.kmeans_stats(xl, centers, weights=wl)
        return jax.tree.map(lambda c, s: c + s[None], carry, st)

    # one program per mesh, built through this lru_cache factory
    # (parallel/gram._chunk_fold_prog rationale)  # tpulint: disable=TPL003
    return jax.jit(_fold, donate_argnums=0)


# -- host-side streaming helpers --------------------------------------------


def _chunk_source(source: Any, input_col: str | None) -> Callable:
    """Normalize a corpus source into a zero-arg factory of fresh chunk
    iterators (the build takes several passes)."""
    if callable(source):
        return source
    if isinstance(source, np.ndarray):
        if source.ndim != 2:
            raise ValueError(
                f"corpus array must be [rows, n], got shape {source.shape}"
            )
        from spark_rapids_ml_tpu.spark.ingest import stream_chunk_rows

        step = stream_chunk_rows()

        def from_array():
            for lo in range(0, source.shape[0], step):
                yield source[lo : lo + step]

        return from_array
    if isinstance(source, (list, tuple)):
        return lambda: iter(source)
    if hasattr(source, "matrices"):
        return source.matrices
    from spark_rapids_ml_tpu.utils import columnar

    ds = columnar.PartitionedDataset.from_any(source, input_col, None)
    return ds.matrices


def _reservoir_sample(
    chunks, budget: int, seed: int
) -> tuple[np.ndarray, int]:
    """(sample, total_rows): a seeded uniform row sample over a chunk
    stream (vectorized algorithm R) plus the stream's exact row count —
    this pass sees every row, so auto-nlist sizes off the true corpus.
    ``budget <= 0`` concatenates the whole stream instead."""
    if budget <= 0:
        parts = [np.asarray(c) for c in chunks]
        if not parts:
            raise ValueError("empty corpus: the source yielded no rows")
        whole = np.concatenate(parts, axis=0)
        return whole, whole.shape[0]
    rng = np.random.default_rng(seed)
    buf: np.ndarray | None = None
    filled = seen = 0
    for chunk in chunks:
        chunk = np.asarray(chunk)
        if buf is None:
            buf = np.empty((budget, chunk.shape[1]), chunk.dtype)
        take = min(budget - filled, chunk.shape[0])
        if take > 0:
            buf[filled : filled + take] = chunk[:take]
            filled += take
            seen += take
            chunk = chunk[take:]
        if chunk.shape[0] == 0:
            continue
        # row number i (1-based) replaces a uniform slot with p = budget/i;
        # duplicate slot hits resolve last-writer-wins — the sequential order
        slots = rng.integers(
            1, seen + 2 + np.arange(chunk.shape[0]), dtype=np.int64
        )
        hit = slots <= budget
        buf[slots[hit] - 1] = chunk[hit]
        seen += chunk.shape[0]
    if buf is None:
        raise ValueError("empty corpus: the source yielded no rows")
    return buf[:filled], seen


def _kmeans_parallel_init(
    sample: np.ndarray, k: int, seed: int, init_steps: int = 2
) -> np.ndarray:
    """kmeans|| on the reservoir sample (Bahmani et al., the models/kmeans
    recipe collapsed to one in-memory partition): ``init_steps`` rounds of
    cost-proportional Bernoulli oversampling with ℓ = 2k expected
    candidates per round, a candidate-weighting pass, then a weighted
    k-means++ reduction to exactly k centers."""
    rng = np.random.default_rng(seed)
    ell = 2.0 * k
    candidates = [sample[rng.integers(sample.shape[0])]]
    xs = jnp.asarray(sample)
    for _ in range(init_steps):
        cand = jnp.asarray(np.stack(candidates), dtype=sample.dtype)
        d2 = np.asarray(KM.min_sq_dists(xs, cand))
        phi = float(d2.sum())
        if phi <= 0.0:  # every row coincides with a candidate
            break
        sel = rng.random(sample.shape[0]) < np.minimum(1.0, ell * d2 / phi)
        if sel.any():
            candidates.extend(sample[sel])
    cand = np.stack(candidates)
    if len(cand) <= k:
        # degenerate oversampling (tiny sample): top up with uniform rows
        need = k - len(cand)
        if need > 0:
            idx = rng.choice(sample.shape[0], need, replace=False)
            cand = np.concatenate([cand, sample[idx]])
        return cand[:k]
    labels, _ = _ASSIGN(xs, jnp.asarray(cand, dtype=sample.dtype))
    counts = np.bincount(np.asarray(labels), minlength=len(cand))
    key = jax.random.PRNGKey(seed)
    centers = KM.weighted_kmeans_plus_plus_init(
        key, jnp.asarray(cand), jnp.asarray(counts.astype(sample.dtype)), k
    )
    return np.asarray(centers)


#: A cell whose stream count exceeds this multiple of the mean fill is
#: split between Lloyd passes (it sets the percentile bucket cap, which
#: every probe's gather pays for across the whole index). A merged pair
#: of equal natural clusters sits at exactly 2.0x the mean, so the
#: threshold must be strictly below that.
_OVERFULL_FACTOR = 1.5


def _rebalance_cells(
    centers: np.ndarray, counts: np.ndarray, sample: np.ndarray
) -> tuple[np.ndarray, int]:
    """Repair the two Lloyd local minima that inflate the bucket cap.

    The D²-proportional init has a coupon-collector tail: at large nlist
    its last few draws land in already-covered regions, so one natural
    cluster ends up with two centers (two half-full cells) and another
    with none — its rows pile onto some other cluster's cell, doubling
    its fill. Plain Lloyd can never escape this, and the IVF cost is
    direct: the merged cell doubles the percentile cap, and the cap is
    the bytes EVERY probe gathers. An IVF quantizer's objective is
    balanced fill, not just k-means cost, so between passes:

    * **empty cells** reseed at greedy farthest-point sample rows
      (distances updated after each pick so one uncovered region can't
      absorb every slot) — the streamed analogue of sklearn's
      ``_relocate_empty_clusters``;
    * **overfull cells** (stream count > ``_OVERFULL_FACTOR``× the mean)
      are split, FAISS-style: the currently smallest cell donates its
      center, reseeded at the overfull cell's farthest sample row — for
      a merged pair that row sits inside the absorbed cluster, so one
      repair fixes both the merge and the duplicate."""
    out, changed = centers, 0
    empty = np.flatnonzero(counts == 0)
    live = centers[counts > 0]
    if empty.size and len(live):
        d2 = np.asarray(
            KM.min_sq_dists(jnp.asarray(sample), jnp.asarray(live))
        )
        out = out.copy()
        for slot in empty:
            j = int(np.argmax(d2))
            out[slot] = sample[j]
            diff = sample - sample[j]
            d2 = np.minimum(d2, np.einsum("ij,ij->i", diff, diff))
        changed += int(empty.size)

    mean = float(counts.mean())
    over = np.flatnonzero(counts > _OVERFULL_FACTOR * mean)
    if over.size:
        labels, d2 = _ASSIGN(jnp.asarray(sample), jnp.asarray(out))
        labels, d2 = np.asarray(labels), np.asarray(d2)
        over_set = set(over.tolist()) | set(empty.tolist())
        donors = [
            int(i) for i in np.argsort(counts, kind="stable")
            if counts[i] < mean and int(i) not in over_set
        ]
        if out is centers:
            out = out.copy()
        # biggest offenders split first while donors last
        for cell in sorted(over.tolist(), key=lambda i: -counts[i]):
            in_cell = np.flatnonzero(labels == cell)
            if not donors or in_cell.size == 0:
                break
            donor = donors.pop(0)
            out[donor] = sample[in_cell[np.argmax(d2[in_cell])]]
            changed += 1
    return out, changed


# -- the estimator -----------------------------------------------------------


class IVFFlatIndex(_ANNParams, Estimator):
    """Streamed IVF-Flat index estimator (see the module docstring for the
    three-pass build). Shares the ``ApproximateNearestNeighbors`` parameter
    surface (k/metric/nlist/nprobe/maxIter/seed) and produces an
    :class:`IVFFlatIndexModel`."""

    def setK(self, value: int) -> "IVFFlatIndex":
        if value < 1:
            raise ValueError(f"k must be >= 1, got {value}")
        return self._set(k=value)

    def setMetric(self, value: str) -> "IVFFlatIndex":
        from spark_rapids_ml_tpu.models.neighbors import _ANN_METRICS

        if value not in _ANN_METRICS:
            raise ValueError(
                f"metric must be one of {_ANN_METRICS}, got {value!r}"
            )
        return self._set(metric=value)

    def setNlist(self, value: int) -> "IVFFlatIndex":
        if value < 0:
            raise ValueError(f"nlist must be >= 0, got {value}")
        return self._set(nlist=value)

    def setNprobe(self, value: int) -> "IVFFlatIndex":
        if value < 1:
            raise ValueError(f"nprobe must be >= 1, got {value}")
        return self._set(nprobe=value)

    def setMaxIter(self, value: int) -> "IVFFlatIndex":
        return self._set(maxIter=value)

    def setSeed(self, value: int) -> "IVFFlatIndex":
        return self._set(seed=value)

    # -- build ---------------------------------------------------------------

    def _mesh_or_none(self):
        import jax as _jax

        if _jax.device_count() <= 1:
            return None
        try:
            from spark_rapids_ml_tpu.parallel import mesh as M

            return M.create_mesh()
        except Exception:  # noqa: BLE001 - degraded single-device fold
            return None

    def fit(
        self,
        source: Any,
        *,
        ids: np.ndarray | None = None,
    ) -> "IVFFlatIndexModel":
        """Build the index from a re-iterable chunk source. ``ids`` maps
        0-based corpus positions to user item ids (default: the position
        itself). The exact row count comes free from the sampling pass."""
        metric = self.getMetric()
        seed = self.getOrDefault("seed")
        chunk_factory = _chunk_source(source, self._paramMap.get("inputCol"))
        # the index is a device artifact: build in the device float dtype
        # (f32 unless x64 is on), like the serving registry's param pages
        dt = np.dtype(np.float64 if jax.config.jax_enable_x64 else np.float32)

        def chunks():
            for c in chunk_factory():
                yield _prepare_rows(np.asarray(c).astype(dt, copy=False), metric)

        with trace_range("ann build"):
            sample, item_count = _reservoir_sample(
                chunks(), sample_rows_budget(), seed
            )
            n = sample.shape[1]
            nlist = self.getNlist() or max(1, int(np.sqrt(item_count)))
            nlist = min(nlist, sample.shape[0])
            centers = _kmeans_parallel_init(sample, nlist, seed).astype(dt)
            centers = self._lloyd(chunks, centers, n, item_count, dt, sample)
            packed = self._assign_and_pack(
                chunks, np.asarray(centers), nlist, item_count
            )

        REGISTRY.counter_inc("ann.build_rows", item_count, index=self.uid)
        spill_rows = int((packed.spill_ids >= 0).sum())
        REGISTRY.gauge_set(
            "ann.spill_fraction",
            spill_rows / item_count if item_count else 0.0,
            index=self.uid,
        )
        if ids is None:
            ids = np.arange(item_count, dtype=np.int64)
        elif len(ids) != item_count:
            raise ValueError(
                f"ids has {len(ids)} entries but the corpus streamed "
                f"{item_count} rows"
            )
        model = IVFFlatIndexModel(
            uid=self.uid,
            centroids=np.asarray(centers),
            bucketItems=packed.bucket_items,
            bucketIds=packed.bucket_ids,
            itemIds=np.asarray(ids),
            spillItems=packed.spill_items,
            spillIds=packed.spill_ids,
        )
        return self._copyValues(model)

    def _lloyd(self, chunks, centers, n, rows, dt, sample):
        """maxIter streamed Lloyd passes; every pass is one stream_fold
        over the source with the donated carry above. Empty and overfull
        cells are repaired from the reservoir sample
        (``_rebalance_cells``) before the next pass — and a repairing
        pass never takes the convergence exit, since a reseed moves
        centers arbitrarily far."""
        from spark_rapids_ml_tpu.spark import ingest

        mesh = self._mesh_or_none()
        k = centers.shape[0]
        for _ in range(self.getOrDefault("maxIter")):
            if mesh is None:
                res = ingest.stream_fold(
                    chunks(),
                    _LLOYD_FOLD_STEP,
                    n=n,
                    init=_LloydCarry(
                        sums=jnp.zeros((k, n), dt),
                        counts=jnp.zeros((k,), dt),
                        cost=jnp.zeros((), dt),
                        centers=jnp.asarray(centers),
                    ),
                    rows=rows,
                )
                stats = KM.KMeansStats(
                    res.carry.sums, res.carry.counts, res.carry.cost
                )
            else:
                # every device folds against its own full copy of the centers
                placed = jax.device_put(
                    np.asarray(centers), NamedSharding(mesh, PartitionSpec())
                )
                stats = ingest.stream_fold_over_mesh(
                    chunks(),
                    lambda c, x, w: _lloyd_mesh_fold_prog(mesh)(
                        c, placed, x, w
                    ),
                    KM.KMeansStats(
                        sums=jax.ShapeDtypeStruct((k, n), dt),
                        counts=jax.ShapeDtypeStruct((k,), dt),
                        cost=jax.ShapeDtypeStruct((), dt),
                    ),
                    mesh,
                    n=n,
                    rows=rows,
                ).carry
            old = jnp.asarray(centers)
            new = KM.update_centers(stats, old)
            shift = float(KM.center_shift_sq(old, new))
            centers, reseeded = _rebalance_cells(
                np.asarray(new), np.asarray(stats.counts), sample
            )
            if reseeded:
                REGISTRY.counter_inc(
                    "ann.cells_reseeded", reseeded, index=self.uid
                )
                continue
            if shift <= _SHIFT_TOL:
                break
        return centers

    def _assign_and_pack(self, chunk_factory, centers, nlist, total):
        """Streamed equivalent of ``ops.ivf.build_ivf_buckets``: pass A
        assigns every chunk on device keeping only the labels (8 bytes a
        row); the cap comes from the full label histogram; pass B
        re-streams the same chunks into the preallocated buckets with
        running per-cluster fill cursors. The corpus itself is never held
        — the only O(corpus) allocation is the packed index. Buckets are
        bit-identical to packing the concatenated corpus; the (order-
        agnostic, fully scanned) spill list holds the same rows in
        chunk-major instead of label-major order."""
        with trace_range("ann pack"):
            cd = jnp.asarray(centers)
            chunk_labels: list[np.ndarray] = []
            counts = np.zeros(nlist, dtype=np.int64)
            n = None
            dt = None
            for chunk in chunk_factory():
                chunk = np.asarray(chunk)
                if n is None:
                    n, dt = chunk.shape[1], chunk.dtype
                labels = np.asarray(_ASSIGN(jnp.asarray(chunk), cd)[0])
                chunk_labels.append(labels)
                counts += np.bincount(labels, minlength=nlist)
            if n is None:
                raise ValueError("empty corpus: the source yielded no rows")
            cap = IVF.bucket_cap(
                counts,
                float(os.environ.get(
                    IVF.ANN_CAP_PERCENTILE_VAR,
                    knobs.ANN_CAP_PERCENTILE.default,
                )),
            )
            bucket_items = np.zeros((nlist, cap, n), dtype=dt)
            bucket_ids = np.full((nlist, cap), -1, dtype=np.int32)
            spill_rows = int(np.maximum(counts - cap, 0).sum())
            spill_pad = (
                0 if spill_rows == 0 else 1 << (spill_rows - 1).bit_length()
            )
            spill_items = np.zeros((spill_pad, n), dtype=dt)
            spill_ids = np.full(spill_pad, -1, dtype=np.int32)
            fill = np.zeros(nlist, dtype=np.int64)
            g0 = 0
            at = 0
            for chunk, labels in zip(chunk_factory(), chunk_labels):
                chunk = np.asarray(chunk)
                if chunk.shape[0] != labels.shape[0]:
                    raise ValueError(
                        "corpus source is not re-iterable deterministically: "
                        f"pass B chunk has {chunk.shape[0]} rows where pass "
                        f"A saw {labels.shape[0]}"
                    )
                order = np.argsort(labels, kind="stable")
                sl = labels[order]
                cnt = np.bincount(labels, minlength=nlist)
                starts = np.concatenate([[0], np.cumsum(cnt)[:-1]])
                pos = fill[sl] + (np.arange(len(order)) - starts[sl])
                dense = pos < cap
                bucket_items[sl[dense], pos[dense]] = chunk[order[dense]]
                bucket_ids[sl[dense], pos[dense]] = g0 + order[dense]
                n_sp = int((~dense).sum())
                if n_sp:
                    spill_items[at : at + n_sp] = chunk[order[~dense]]
                    spill_ids[at : at + n_sp] = g0 + order[~dense]
                    at += n_sp
                fill += cnt
                g0 += chunk.shape[0]
            if g0 != total:
                raise ValueError(
                    "corpus source is not re-iterable deterministically: "
                    f"the pack pass streamed {g0} rows, the sampling pass "
                    f"saw {total}"
                )
        return IVF.IvfBuckets(
            bucket_items, bucket_ids, cap, spill_items, spill_ids
        )


class IVFFlatIndexModel(ApproximateNearestNeighborsModel):
    """A streamed-built IVF index: the full query/persistence surface of
    ``ApproximateNearestNeighborsModel`` plus a per-call ``nprobe``
    override — the recall-vs-nprobe sweep tools/ann_report.py renders
    probes one fitted index at many operating points without refitting."""

    def search(
        self,
        queries: np.ndarray,
        *,
        k: int | None = None,
        nprobe: int | None = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(distances, ids) for a [q, n] query block; ``nprobe`` overrides
        the fitted operating point for this call only."""
        if nprobe is None:
            return self._kneighbors_matrix(np.asarray(queries), k)
        if nprobe < 1:
            raise ValueError(f"nprobe must be >= 1, got {nprobe}")
        prev = self._paramMap.get("nprobe")
        self._set(nprobe=int(nprobe))
        try:
            return self._kneighbors_matrix(np.asarray(queries), k)
        finally:
            if prev is None:
                del self._paramMap["nprobe"]
            else:
                self._set(nprobe=prev)

    @property
    def nlist(self) -> int:
        return int(self.bucketItems.shape[0])
