"""KMeans estimator/model — the stretch estimator (BASELINE.json config 5).

Spark MLlib-shaped params (``k``, ``maxIter``, ``tol``, ``seed``,
``initMode``); Lloyd iterations run as per-partition device passes producing
``KMeansStats`` monoids, tree-reduced across partitions — structurally
identical to PCA's fit, so the same mesh/psum reducer swaps in for SPMD
execution. Seeding is k-means++ on a bounded row sample (the role Spark's
k-means|| plays at cluster scale).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.ops.policy import resolve_policy
from spark_rapids_ml_tpu.models.base import Estimator, Model
from spark_rapids_ml_tpu.models.params import HasInputCol, HasOutputCol, Param
from spark_rapids_ml_tpu.ops import kmeans as KM
from spark_rapids_ml_tpu.parallel.tree_aggregate import tree_reduce
from spark_rapids_ml_tpu.utils import columnar
from spark_rapids_ml_tpu.telemetry import trace_range

_MAX_INIT_SAMPLE = 16384

#: module-level jit so transform/computeCost reuse one compiled program per
#: shape bucket instead of retracing per call (tpulint TPL003 convention)
_assign_clusters_jit = jax.jit(KM.assign_clusters)


def _resume_kmeans_checkpoint(checkpoint_dir: str | None, k: int):
    """(centers-or-None, start_iter, cost, checkpointer-or-None) for a Lloyd
    loop, resuming from the newest durable checkpoint when one exists — the
    ONE resume contract both the core and Spark-path fits share (the KMeans
    analog of linear.py's ``_resume_newton_checkpoint``)."""
    if checkpoint_dir is None:
        return None, 0, np.inf, None
    from spark_rapids_ml_tpu.utils.checkpoint import TrainingCheckpointer

    ckpt = TrainingCheckpointer(checkpoint_dir)
    resumed = ckpt.latest()
    if resumed is None:
        return None, 0, np.inf, ckpt
    step, arrays, state = resumed
    if arrays["centers"].shape[0] != k:
        raise ValueError(
            f"checkpoint at {checkpoint_dir} holds "
            f"{arrays['centers'].shape[0]} centers but k={k}; "
            "point checkpoint_dir at a fresh directory to train "
            "with different params"
        )
    return arrays["centers"], step + 1, float(state.get("cost", np.inf)), ckpt


class _KMeansParams(HasInputCol, HasOutputCol):
    k = Param("k", "number of clusters", int)
    maxIter = Param("maxIter", "maximum Lloyd iterations", int)
    tol = Param("tol", "convergence tolerance on max centroid movement", float)
    seed = Param("seed", "random seed", int)
    initMode = Param(
        "initMode",
        "'k-means||' (distributed oversampling init, Bahmani et al. — "
        "Spark MLlib's default; scales to large k because candidates come "
        "from cost-proportional passes over ALL rows), 'k-means++' (on a "
        "bounded driver-side sample), or 'random'",
        str,
    )
    initSteps = Param(
        "initSteps", "number of k-means|| oversampling rounds (Spark: 2)", int
    )
    weightCol = Param(
        "weightCol",
        "optional instance-weight column (Spark ML weightCol contract); "
        "weighted Lloyd sums/counts/cost ride the same per-row vector that "
        "masks shape-bucketing padding",
        str,
    )

    def __init__(self, uid: str | None = None, **kwargs):
        super().__init__(uid, **kwargs)
        self._setDefault(
            maxIter=20, tol=1e-4, seed=0, initMode="k-means++", initSteps=2,
            outputCol="prediction",
        )

    def getK(self) -> int:
        return self.getOrDefault("k")

    def getMaxIter(self) -> int:
        return self.getOrDefault("maxIter")

    def getTol(self) -> float:
        return self.getOrDefault("tol")

    def getSeed(self) -> int:
        return self.getOrDefault("seed")

    def getInitMode(self) -> str:
        return self.getOrDefault("initMode")

    def getInitSteps(self) -> int:
        return self.getOrDefault("initSteps")


class KMeans(_KMeansParams, Estimator):
    def setK(self, value: int) -> "KMeans":
        return self._set(k=value)

    def setMaxIter(self, value: int) -> "KMeans":
        return self._set(maxIter=value)

    def setTol(self, value: float) -> "KMeans":
        return self._set(tol=value)

    def setSeed(self, value: int) -> "KMeans":
        return self._set(seed=value)

    def setInitMode(self, value: str) -> "KMeans":
        if value not in ("k-means||", "k-means++", "random"):
            raise ValueError(
                "initMode must be 'k-means||', 'k-means++', or 'random'"
            )
        return self._set(initMode=value)

    def setInitSteps(self, value: int) -> "KMeans":
        if value < 1:
            raise ValueError(f"initSteps must be >= 1, got {value}")
        return self._set(initSteps=value)

    def setWeightCol(self, value: str) -> "KMeans":
        return self._set(weightCol=value)

    def _init_centers(
        self,
        mats: list[np.ndarray],
        k: int,
        part_weights=None,
    ) -> np.ndarray:
        if self.getInitMode() == "k-means||":
            return self._kmeans_parallel_init(mats, part_weights, k)
        rng = np.random.default_rng(self.getSeed())
        # bounded sample across partitions for seeding; zero-weight rows are
        # excluded instances and must never seed a center (a zero-count
        # center would survive Lloyd updates unchanged)
        if part_weights is not None:
            mats = [m[w > 0] for m, w in zip(mats, part_weights)]
            mats = [m for m in mats if len(m)]
        total = sum(len(m) for m in mats)
        take = min(total, _MAX_INIT_SAMPLE)
        sample = np.concatenate(
            [m[rng.choice(len(m), max(1, int(take * len(m) / total)), replace=False)]
             for m in mats]
        )
        if self.getInitMode() == "random":
            idx = rng.choice(len(sample), k, replace=False)
            return sample[idx]
        key = jax.random.PRNGKey(self.getSeed())
        centers = KM.kmeans_plus_plus_init(key, jnp.asarray(sample), k)
        return np.asarray(centers)

    def _kmeans_parallel_init(
        self, mats: list[np.ndarray], part_weights, k: int
    ) -> np.ndarray:
        """k-means‖ (Bahmani et al., VLDB'12 — Spark MLlib's default init):
        ``initSteps`` rounds of cost-proportional oversampling (ℓ = 2k
        expected candidates per round) where EVERY row of every partition is
        a Bernoulli trial with p = ℓ·w·d²/φ, then a candidate-weighting pass
        (rows owned per candidate) and a weighted k-means++ reduction to k.
        Unlike the bounded-sample k-means++ path, candidate quality does not
        degrade with k: at k=1000 the candidate pool is ~2·initSteps·k points
        drawn from the full dataset's cost distribution (the r2 verdict's
        config-5 gap)."""
        rng = np.random.default_rng(self.getSeed())
        ell = 2.0 * k
        pairs = []
        for i, m in enumerate(mats):
            w = (
                np.ones(len(m), dtype=np.float64)
                if part_weights is None
                else np.asarray(part_weights[i], dtype=np.float64)
            )
            keep = w > 0
            if keep.any():
                pairs.append((m[keep], w[keep]))
        if not pairs:
            raise ValueError("no rows with positive weight to seed from")

        # first candidate: one weight-proportional row
        totals = np.array([w.sum() for _, w in pairs])
        pi = rng.choice(len(pairs), p=totals / totals.sum())
        m0, w0 = pairs[pi]
        candidates = [m0[rng.choice(len(m0), p=w0 / w0.sum())]]

        for _ in range(self.getInitSteps()):
            c = np.stack(candidates)
            d2s = [
                np.asarray(
                    KM.min_sq_dists(jnp.asarray(m), jnp.asarray(c, dtype=m.dtype))
                )
                for m, _ in pairs
            ]
            phi = sum(float(np.dot(d2, w)) for d2, (_, w) in zip(d2s, pairs))
            if phi <= 0.0:  # every row coincides with a candidate
                break
            for d2, (m, w) in zip(d2s, pairs):
                p_sel = np.minimum(1.0, ell * w * d2 / phi)
                sel = rng.random(len(m)) < p_sel
                if sel.any():
                    candidates.extend(m[sel])

        cand = np.stack(candidates)
        if len(cand) <= k:
            # degenerate oversampling (tiny data or phi collapsed): top up
            # with uniform rows so exactly k centers come out
            extra_pool = np.concatenate([m for m, _ in pairs])
            need = k - len(cand)
            if need > 0:
                idx = rng.choice(len(extra_pool), need, replace=False)
                cand = np.concatenate([cand, extra_pool[idx]])
            return cand[:k]

        # weighting pass: instance-weighted row counts owned by each candidate
        counts = np.zeros(len(cand), dtype=np.float64)
        for m, w in pairs:
            labels, _ = KM.assign_clusters(
                jnp.asarray(m), jnp.asarray(cand, dtype=m.dtype)
            )
            np.add.at(counts, np.asarray(labels), w)
        key = jax.random.PRNGKey(self.getSeed())
        centers = KM.weighted_kmeans_plus_plus_init(
            key, jnp.asarray(cand), jnp.asarray(counts), k
        )
        return np.asarray(centers)

    def fit(
        self,
        dataset: Any,
        num_partitions: int | None = None,
        *,
        sample_weight=None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 1,
    ) -> "KMeansModel":
        """Lloyd training with optional mid-training checkpoint/resume.

        With ``checkpoint_dir`` set, training state (centers, iteration,
        cost) is durably checkpointed every ``checkpoint_every`` iterations,
        and an interrupted fit pointed at the same directory resumes from the
        newest checkpoint instead of re-seeding — a capability the reference
        lacks entirely (model persistence only, SURVEY.md §5).
        """
        if checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
        input_col = self._paramMap.get("inputCol")
        ds = columnar.PartitionedDataset.from_any(dataset, input_col, num_partitions)
        k = self.getK()
        tol_sq = self.getTol() ** 2
        mats = list(ds.matrices())  # materialize ONCE (extraction may copy)
        part_weights = columnar.resolve_partition_weights(
            dataset, mats, self._paramMap.get("weightCol"), sample_weight
        )

        centers, start_iter, cost, ckpt = _resume_kmeans_checkpoint(
            checkpoint_dir, k
        )
        if centers is None:
            with trace_range("kmeans init"):
                centers = self._init_centers(mats, k, part_weights)

        # pre-pad partitions once; the weight vector masks padding (0) and
        # carries instance weights (1.0 when unweighted) on true rows
        padded = []
        for i, mat in enumerate(mats):
            pm, true_rows = columnar.pad_rows(mat)
            w = np.zeros(pm.shape[0], columnar.float_dtype_for(pm.dtype))
            w[:true_rows] = 1.0 if part_weights is None else part_weights[i]
            padded.append((jnp.asarray(pm), jnp.asarray(w)))

        n_cols = padded[0][0].shape[1]
        if centers.shape[1] != n_cols:
            raise ValueError(
                f"checkpoint/init centers have {centers.shape[1]} features but "
                f"the dataset has {n_cols}; is checkpoint_dir stale?"
            )

        # env-selected distance policy (bf16 or int8 cross terms); the
        # Lloyd accumulators inside kmeans_stats stay full precision
        dist_policy = resolve_policy(None)
        with trace_range("kmeans lloyd"):
            for it in range(start_iter, self.getMaxIter()):
                c = jnp.asarray(centers)
                partials = [
                    KM.kmeans_stats(x, c, w, policy=dist_policy)
                    for x, w in padded
                ]
                stats = tree_reduce(partials, KM.combine_kmeans_stats)
                new_centers = np.asarray(KM.update_centers(stats, c))
                cost = float(stats.cost)
                shift = float(KM.center_shift_sq(c, jnp.asarray(new_centers)))
                centers = new_centers
                if ckpt is not None and (it + 1) % checkpoint_every == 0:
                    ckpt.save(it, {"centers": centers}, {"cost": cost})
                if shift <= tol_sq:
                    break

        model = KMeansModel(uid=self.uid, clusterCenters=centers, trainingCost=cost)
        return self._copyValues(model)


class KMeansModel(_KMeansParams, Model):
    def __init__(
        self,
        uid: str | None = None,
        clusterCenters: np.ndarray | None = None,
        trainingCost: float = float("nan"),
    ):
        super().__init__(uid)
        self.clusterCenters = (
            None if clusterCenters is None else np.asarray(clusterCenters)
        )
        self.trainingCost = trainingCost

    def _predict_matrix(self, mat: np.ndarray) -> np.ndarray:
        padded, true_rows = columnar.pad_rows(mat)
        xd = jnp.asarray(padded)
        labels, _ = _assign_clusters_jit(
            xd, jnp.asarray(self.clusterCenters, dtype=xd.dtype)
        )
        return np.asarray(labels)[:true_rows]

    def transform(self, dataset: Any) -> Any:
        """Append an integer ``prediction`` column (Spark KMeansModel shape)."""
        with trace_range("kmeans transform"):
            return columnar.apply_column_transform(
                dataset,
                self._paramMap.get("inputCol"),
                self.getOutputCol(),
                self._predict_matrix,
            )

    def predict(self, row) -> int:
        """Single-row prediction (host path)."""
        d = np.sum((self.clusterCenters - np.asarray(row)[None, :]) ** 2, axis=1)
        return int(np.argmin(d))

    def computeCost(self, dataset: Any) -> float:
        """Sum of squared distances to nearest centroid (inertia)."""
        input_col = self._paramMap.get("inputCol")
        ds = columnar.PartitionedDataset.from_any(dataset, input_col)
        total = 0.0
        for mat in ds.matrices():
            padded, true_rows = columnar.pad_rows(mat)
            xd = jnp.asarray(padded)
            _, dists = _assign_clusters_jit(
                xd, jnp.asarray(self.clusterCenters, dtype=xd.dtype)
            )
            total += float(jnp.sum(dists[:true_rows]))
        return total

    def _saveData(self) -> dict[str, np.ndarray]:
        return {
            "clusterCenters": self.clusterCenters,
            "trainingCost": np.asarray([self.trainingCost]),
        }

    @classmethod
    def _fromSaved(cls, uid, data):
        return cls(
            uid=uid,
            clusterCenters=data["clusterCenters"],
            trainingCost=float(data["trainingCost"][0]),
        )
