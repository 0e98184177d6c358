"""RandomForestClassifier / RandomForestRegressor — the tree family.

Spark-ML-shaped API (params, fit/transform, persistence) over the
histogram-tree kernels in ops/forest.py. The modern spark-rapids-ml family
ships both estimators on cuML's GPU forest; the 22.12 reference this
framework re-designs stops at PCA (SURVEY.md §2), so this is a
capability-add with the same API surface Spark MLlib exposes
(pyspark.ml.classification.RandomForestClassifier /
pyspark.ml.regression.RandomForestRegressor).

Spark-semantics choices mirrored here:

- features are quantile-binned to ``maxBins`` histogram bins (Spark MLlib
  itself is a binned-tree implementation with the same param);
- bootstrap draws Poisson(subsamplingRate) per-row counts (Spark's
  BaggedPoint), multiplied into any ``weightCol`` instance weights;
- ``featureSubsetStrategy`` per-NODE feature subsets ('auto' = sqrt(F)
  for classification, F/3 for regression — Spark's defaults);
- classifier probability = average of per-tree leaf class distributions,
  rawPrediction = their sum (Spark RandomForestClassificationModel);
- regressor prediction = mean of per-tree leaf means;
- ``minInstancesPerNode`` gates on WEIGHTED counts (with unweighted data
  and bootstrap counts these are the sampled instance counts).
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_ml_tpu.models.base import Estimator, Model
from spark_rapids_ml_tpu.models.params import (
    HasFeaturesCol,
    HasLabelCol,
    HasPredictionCol,
    Param,
)
from spark_rapids_ml_tpu.ops import forest as FO
from spark_rapids_ml_tpu.utils import columnar
from spark_rapids_ml_tpu.telemetry import trace_range

#: rows sampled (not streamed) for quantile bin-edge estimation — the same
#: bounded-sample role Spark's findSplits sampling plays
_MAX_BIN_SAMPLE = 200_000
#: the fewest rows a resident fit's edge sample takes: Spark's findSplits
#: samples max(maxBins², 10,000) of them
_MIN_EDGE_SAMPLE = 10_000


def subset_size(strategy: str, n_features: int, *, classification: bool) -> int:
    """Spark featureSubsetStrategy → per-node feature count."""
    s = str(strategy).lower()
    if s == "auto":
        s = "sqrt" if classification else "onethird"
    if s == "all":
        return n_features
    # Spark CEILS the named strategies (RandomForestParams: sqrt → ceil(√F),
    # log2 → ceil(log₂F), onethird → ceil(F/3)) — floor under-samples, e.g.
    # F=10 must give 4 features for 'sqrt', not 3
    if s == "sqrt":
        return max(1, math.ceil(math.sqrt(n_features)))
    if s == "log2":
        return max(1, math.ceil(math.log2(n_features)))
    if s == "onethird":
        return max(1, math.ceil(n_features / 3.0))
    try:
        v = float(s)
    except ValueError:
        raise ValueError(
            f"featureSubsetStrategy must be auto/all/sqrt/log2/onethird or "
            f"a number, got {strategy!r}"
        ) from None
    if v >= 1.0:
        return min(n_features, int(v))
    if v > 0.0:
        # Spark ceils fractional strategies (RandomForest.getFeatureSubsetNumber)
        return min(n_features, max(1, math.ceil(v * n_features)))
    raise ValueError(f"featureSubsetStrategy must be > 0, got {strategy!r}")


def quantile_bin_edges(
    x: np.ndarray, n_bins: int, seed: int, w: np.ndarray | None = None
) -> np.ndarray:
    """[F, n_bins−1] interior quantile edges from a bounded row sample.

    Zero-weight rows are EXCLUDED before the quantile pass — an excluded
    instance must not stretch the bin grid any more than it may vote in a
    histogram (positive fractional weights still count one row each, the
    same approximation Spark's unweighted findSplits sampling makes)."""
    if w is not None:
        x = x[np.asarray(w) > 0]
    if x.shape[0] > _MAX_BIN_SAMPLE:
        rng = np.random.default_rng(seed)
        x = x[rng.choice(x.shape[0], _MAX_BIN_SAMPLE, replace=False)]
    return np.quantile(x, quantile_levels(n_bins), axis=0).T.astype(np.float64)


def edge_sample_ids(seed: int, rows: int, n_bins: int) -> np.ndarray:
    """Sorted ids of the rows whose quantiles make a resident fit's bin
    edges: Spark's findSplits sample size, max(maxBins², 10,000), drawn
    without replacement from the seed (all rows where there are fewer)."""
    m = max(n_bins * n_bins, _MIN_EDGE_SAMPLE)
    if m >= rows:
        return np.arange(rows)
    return np.sort(np.random.default_rng(seed).choice(rows, m, replace=False))


def quantile_levels(n_bins: int) -> np.ndarray:
    """The interior quantile levels of ``n_bins`` bins, in float64."""
    return np.linspace(0.0, 1.0, n_bins + 1)[1:-1]


def tree_feature_importances(
    trees: FO.TreeArrays, n_features: int
) -> np.ndarray:
    """Spark's TreeEnsembleModel.featureImportances: per tree, sum each
    split node's n-scaled impurity gain by feature and normalize to 1;
    average the per-tree vectors; normalize again. Shared by the forest
    and GBT models (both carry gains in the same heap arrays)."""
    T = trees.feature.shape[0]
    out = np.zeros((T, n_features))
    for t in range(T):
        feat = trees.feature[t]
        split = feat >= 0
        np.add.at(out[t], feat[split], trees.gain[t][split])
        tot = out[t].sum()
        if tot > 0:
            out[t] /= tot
    avg = out.mean(0)
    s = avg.sum()
    return avg / s if s > 0 else avg


def split_thresholds(trees: FO.TreeArrays, edges: np.ndarray) -> np.ndarray:
    """[T, nodes] raw-value split thresholds from (feature, split_bin) —
    bin b splits at edges[f, b] (go right when x > edge); leaves get 0.
    Shared by the forest and GBT fits so inference needs no binning."""
    feat = np.clip(trees.feature, 0, None)
    thresholds = np.take_along_axis(
        edges[feat.reshape(-1)],
        np.clip(trees.split_bin, 0, edges.shape[1] - 1).reshape(-1, 1),
        axis=1,
    ).reshape(trees.feature.shape)
    return np.where(trees.feature >= 0, thresholds, 0.0)


def bin_features(x: np.ndarray, edges: np.ndarray) -> np.ndarray:
    """[rows, F] int32 bin ids: bin b ⇔ edges[b−1] < x ≤ edges[b]."""
    out = np.empty(x.shape, dtype=np.int32)
    for j in range(x.shape[1]):
        out[:, j] = np.searchsorted(edges[j], x[:, j], side="left")
    return out


class _ForestParams(HasFeaturesCol, HasLabelCol, HasPredictionCol):
    numTrees = Param("numTrees", "number of trees", int)
    maxDepth = Param("maxDepth", "maximum tree depth (root = depth 0)", int)
    maxBins = Param("maxBins", "histogram bins per feature", int)
    minInstancesPerNode = Param(
        "minInstancesPerNode",
        "minimum weighted instance count per child for a split",
        float,
    )
    minInfoGain = Param("minInfoGain", "minimum impurity decrease", float)
    featureSubsetStrategy = Param(
        "featureSubsetStrategy",
        "features considered per node: auto/all/sqrt/log2/onethird or a "
        "count/fraction",
        str,
    )
    subsamplingRate = Param(
        "subsamplingRate", "bootstrap sample rate per tree", float
    )
    bootstrap = Param(
        "bootstrap",
        "Poisson bootstrap per tree (False = every tree sees all rows)",
        bool,
    )
    seed = Param("seed", "random seed", int)
    weightCol = Param(
        "weightCol", "optional instance-weight column", str
    )

    def __init__(self, uid: str | None = None, **kwargs):
        super().__init__(uid, **kwargs)
        self._setDefault(
            featuresCol="features", labelCol="label",
            predictionCol="prediction",
            numTrees=20, maxDepth=5, maxBins=32, minInstancesPerNode=1.0,
            minInfoGain=0.0, featureSubsetStrategy="auto",
            subsamplingRate=1.0, bootstrap=True, seed=0,
        )

    def getNumTrees(self) -> int:
        return self.getOrDefault("numTrees")

    def getMaxDepth(self) -> int:
        return self.getOrDefault("maxDepth")

    def getMaxBins(self) -> int:
        return self.getOrDefault("maxBins")

    def getSeed(self) -> int:
        return self.getOrDefault("seed")


class _ForestEstimator(_ForestParams, Estimator):
    _classification: bool  # set by subclasses
    _impurity_choices: tuple

    def setNumTrees(self, value: int):
        if value < 1:
            raise ValueError(f"numTrees must be >= 1, got {value}")
        return self._set(numTrees=value)

    def setMaxDepth(self, value: int):
        if not 0 <= value <= 14:
            raise ValueError(f"maxDepth must be in [0, 14], got {value}")
        return self._set(maxDepth=value)

    def setMaxBins(self, value: int):
        if value < 2:
            raise ValueError(f"maxBins must be >= 2, got {value}")
        return self._set(maxBins=value)

    def setMinInstancesPerNode(self, value: float):
        if value < 1:
            raise ValueError(f"minInstancesPerNode must be >= 1, got {value}")
        return self._set(minInstancesPerNode=float(value))

    def setMinInfoGain(self, value: float):
        return self._set(minInfoGain=float(value))

    def setFeatureSubsetStrategy(self, value):
        return self._set(featureSubsetStrategy=str(value))

    def setSubsamplingRate(self, value: float):
        if not 0.0 < value <= 1.0:
            raise ValueError(f"subsamplingRate must be in (0, 1], got {value}")
        return self._set(subsamplingRate=float(value))

    def setBootstrap(self, value: bool):
        return self._set(bootstrap=bool(value))

    def setSeed(self, value: int):
        return self._set(seed=value)

    def setWeightCol(self, value: str):
        return self._set(weightCol=value)

    def setImpurity(self, value: str):
        if value not in self._impurity_choices:
            raise ValueError(
                f"impurity must be one of {self._impurity_choices}, got {value!r}"
            )
        return self._set(impurity=value)

    def getImpurity(self) -> str:
        return self.getOrDefault("impurity")

    def _make_model(self, x, y, w):
        """THE fit-then-wrap handoff — one copy for every tree estimator;
        subclasses choose the model class via ``_model_cls``."""
        return self._wrap_arrays(*self._fit_arrays(x, y, w))

    def _wrap_arrays(self, trees, thresholds):
        model = self._model_cls(
            uid=self.uid, trees=trees, thresholds=thresholds,
            numFeatures=self._n_features_in,
        )
        return self._copyValues(model)

    def _build_static(self, n_features: int) -> dict:
        """The forest program's static arguments for ``n_features``."""
        return dict(
            max_depth=self.getMaxDepth(),
            n_bins=self.getMaxBins(),
            k_features=subset_size(
                self.getOrDefault("featureSubsetStrategy"),
                n_features,
                classification=self._classification,
            ),
            impurity=self.getImpurity(),
        )

    def _fit_arrays(self, x: np.ndarray, y: np.ndarray, w: np.ndarray | None):
        """(trees, thresholds) — the host fit body: edges and bins on the
        host, the forest program on the default device."""
        n_bins = self.getMaxBins()
        seed = self.getSeed()
        fdt = columnar.float_dtype_for(x.dtype)

        edges = quantile_bin_edges(x, n_bins, seed, w)
        binned = bin_features(x, edges).astype(FO.bins_dtype(n_bins))
        row_stats = self._row_stats(y, fdt)

        weights = np.asarray(FO.bootstrap_weights(
            seed, self.getNumTrees(), len(x),
            bootstrap=self.getOrDefault("bootstrap"),
            rate=self.getOrDefault("subsamplingRate"),
        )).astype(fdt)
        if w is not None:
            weights *= w.astype(fdt)[None, :]

        keys = jax.random.split(jax.random.PRNGKey(seed), self.getNumTrees())
        with trace_range("forest build"):
            trees = FO.build_forest(
                keys,
                jnp.asarray(binned),
                jnp.asarray(row_stats),
                jnp.asarray(weights),
                jnp.asarray(np.asarray(self.getOrDefault("minInstancesPerNode"), fdt)),
                jnp.asarray(np.asarray(self.getOrDefault("minInfoGain"), fdt)),
                **self._build_static(x.shape[1]),
            )
        self._n_features_in = x.shape[1]
        trees = FO.TreeArrays(*(np.asarray(a) for a in trees))
        return trees, split_thresholds(trees, edges)

    def _fit_resident(self, ing) -> tuple[FO.TreeArrays, np.ndarray]:
        """(trees, thresholds) from rows held on the device mesh (a
        ``spark.ingest.MeshIngest`` with labels and pad-mask weights): the
        edges are quantiles of a seeded sample of the resident rows
        (:func:`edge_sample_ids`), the rows are binned where they lie, the
        trees' sample counts are drawn there, and the trees come back to the
        host once. The rows themselves are let go once they are binned.

        Spans ``forest.bin`` (edges, bins and the wait for them) and
        ``forest build`` (the program's dispatch and the wait for its trees
        on the host); counters ``forest.trees``, ``forest.split_nodes``,
        ``forest.piece_select_levels`` (the trees' split levels whose
        selection took ``ops.forest._piece_bins``) and ``forest.level_blocks``
        (the blocks of slots the trees' split levels walked their histograms
        in, ``ops.forest.level_plan``), ``path="mesh-local"``, booked from
        the trees handed back and the program's static plan."""
        from spark_rapids_ml_tpu.parallel import forest as PF
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS
        from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

        mesh, xs = ing.mesh, ing.xs
        n_bins, seed, n_trees = self.getMaxBins(), self.getSeed(), self.getNumTrees()
        n = xs.shape[1]
        fdt = xs.dtype
        with trace_range("forest.bin"):
            ids = jnp.asarray(edge_sample_ids(seed, ing.rows, n_bins))
            edges = PF.quantile_edges(
                xs[ids], ing.ws[ids], jnp.asarray(quantile_levels(n_bins), fdt)
            )
            binned = PF.make_sharded_binner(mesh, n_bins)(xs, edges)
            row_stats = self._device_row_stats(ing.ys)
            jax.block_until_ready((binned, row_stats))
        ing.xs = xs = None  # the rows are their bins from here on

        static = self._build_static(n)
        shards = mesh.shape[DATA_AXIS]
        with trace_range("forest build"):
            weights = PF.make_sharded_weights(
                mesh, seed=seed, n_trees=n_trees, rows=ing.rows,
                bootstrap=bool(self.getOrDefault("bootstrap")),
                rate=float(self.getOrDefault("subsamplingRate")),
            )(ing.ws)
            capacity = FO.row_capacity(weights, shards)
            device = mesh.devices.flat[0]
            group = FO.tree_group(
                capacity, n, static["k_features"], n_bins, row_stats.shape[1],
                static["max_depth"], n_trees, device=device,
            )
            block_bytes = FO.level_budget(device)
            run = PF.make_sharded_forest(
                mesh, group=group, capacity=capacity, block_bytes=block_bytes,
                **static
            )
            trees = run(
                jax.random.split(jax.random.PRNGKey(seed), n_trees),
                binned, row_stats, weights,
                jnp.asarray(self.getOrDefault("minInstancesPerNode"), fdt),
                jnp.asarray(self.getOrDefault("minInfoGain"), fdt),
            )
            trees = FO.TreeArrays(*(np.asarray(a) for a in trees))
        REGISTRY.counter_inc("forest.trees", n_trees, path="mesh-local")
        REGISTRY.counter_inc(
            "forest.split_nodes", int(np.sum(trees.feature >= 0)),
            path="mesh-local",
        )
        REGISTRY.counter_inc(
            "forest.piece_select_levels",
            len(trees.feature) * FO.piece_select_levels(
                n, static["k_features"], n_bins, static["max_depth"]
            ),
            path="mesh-local",
        )
        REGISTRY.counter_inc(
            "forest.level_blocks",
            len(trees.feature) * FO.level_blocks(
                capacity, n, static["k_features"], n_bins, row_stats.shape[1],
                static["max_depth"], block_bytes,
            ),
            path="mesh-local",
        )
        self._n_features_in = n
        return trees, split_thresholds(trees, np.asarray(edges, np.float64))

    def fit(self, dataset: Any, num_partitions: int | None = None):
        parts = columnar.labeled_partitions(
            dataset,
            self.getOrDefault("featuresCol"),
            self.getOrDefault("labelCol"),
            num_partitions,
            weight_col=self._paramMap.get("weightCol"),
        )
        x = np.concatenate([p[0] for p in parts])
        y = np.concatenate([p[1] for p in parts])
        w = (
            np.concatenate([p[2] for p in parts])
            if parts[0][2] is not None
            else None
        )
        return self._make_model(x, y, w)


class _ForestModel(_ForestParams, Model):
    def __init__(
        self,
        uid: str | None = None,
        trees: FO.TreeArrays | None = None,
        thresholds: np.ndarray | None = None,
        numFeatures: int = -1,
    ):
        super().__init__(uid)
        self.trees = trees
        self.thresholds = (
            None if thresholds is None else np.asarray(thresholds)
        )
        self._num_features = int(numFeatures)

    @property
    def numFeatures(self) -> int:
        """Training feature count (Spark model API)."""
        return self._num_features

    def predict(self, row) -> float:
        return float(
            self._predict_matrix(np.asarray(row, dtype=np.float64)[None, :])[0]
        )

    def getNumTrees(self) -> int:  # fitted count, not the param
        return self.trees.feature.shape[0]

    @property
    def totalNumNodes(self) -> int:
        """Materialized (reachable) nodes across the forest — Spark's
        totalNumNodes analog for the heap layout."""
        reachable = np.sum(self.trees.leaf_stats.sum(-1) > 0, axis=1)
        return int(np.sum(np.maximum(reachable, 1)))

    def _leaf_stats_for(self, mat: np.ndarray) -> np.ndarray:
        """[T, rows, S] leaf stats via the device descent kernel."""
        max_depth = int(
            np.log2(self.trees.feature.shape[1] + 1) - 1
        )
        return np.asarray(
            FO.forest_apply(
                FO.TreeArrays(*(jnp.asarray(a) for a in self.trees)),
                jnp.asarray(mat),
                jnp.asarray(self.thresholds),
                max_depth=max_depth,
            )
        )

    @property
    def featureImportances(self) -> np.ndarray:
        """Impurity-based importances, Spark's recipe
        (RandomForest.featureImportances)."""
        return tree_feature_importances(self.trees, self._num_features)

    def _saveData(self) -> dict[str, np.ndarray]:
        return {
            "feature": self.trees.feature,
            "split_bin": self.trees.split_bin,
            "is_leaf": self.trees.is_leaf,
            "leaf_stats": self.trees.leaf_stats,
            "gain": self.trees.gain,
            "thresholds": self.thresholds,
            "numFeatures": np.asarray([self._num_features]),
        }

    @classmethod
    def _fromSaved(cls, uid, data):
        trees = FO.TreeArrays(
            data["feature"].astype(np.int32),
            data["split_bin"].astype(np.int32),
            data["is_leaf"].astype(bool),
            data["leaf_stats"],
            # pre-gain saves load with zero importances rather than failing
            data.get("gain", np.zeros(data["feature"].shape)),
        )
        return cls(
            uid=uid,
            trees=trees,
            thresholds=data["thresholds"],
            numFeatures=int(data["numFeatures"][0]),
        )


# ---------------------------------------------------------------------------
# Classifier
# ---------------------------------------------------------------------------


class _ClassifierCols:
    probabilityCol = Param("probabilityCol", "class-probability column", str)
    rawPredictionCol = Param(
        "rawPredictionCol", "summed per-tree distribution column", str
    )

    def __init__(self, uid=None, **kwargs):
        super().__init__(uid, **kwargs)
        self._setDefault(
            probabilityCol="probability", rawPredictionCol="rawPrediction",
            impurity="gini",
        )

    def setProbabilityCol(self, value: str):
        return self._set(probabilityCol=value)

    def setRawPredictionCol(self, value: str):
        return self._set(rawPredictionCol=value)


class RandomForestClassifier(_ClassifierCols, _ForestEstimator):
    impurity = Param("impurity", "'gini' or 'entropy'", str)
    _classification = True
    _impurity_choices = ("gini", "entropy")

    def _row_stats(self, y: np.ndarray, fdt) -> np.ndarray:
        classes = np.round(y).astype(np.int64)
        if (classes < 0).any() or not np.allclose(y, classes):
            raise ValueError(
                "classification labels must be non-negative integers "
                "(Spark ML label contract)"
            )
        return np.eye(int(classes.max()) + 1, dtype=fdt)[classes]

    def _device_row_stats(self, ys: jax.Array) -> jax.Array:
        """[rows, C] one-hot class rows of device labels, with the host
        path's label contract checked on the device (pad rows hold 0)."""
        from spark_rapids_ml_tpu.parallel import forest as PF

        low, high, fractional = (
            float(v) for v in jax.device_get(PF.label_facts(ys))
        )
        if low < 0 or fractional:
            raise ValueError(
                "classification labels must be non-negative integers "
                "(Spark ML label contract)"
            )
        return PF.one_hot(ys, int(high) + 1)

    @property
    def _model_cls(self):
        return RandomForestClassificationModel


class RandomForestClassificationModel(_ClassifierCols, _ForestModel):
    impurity = Param("impurity", "'gini' or 'entropy'", str)

    @property
    def numClasses(self) -> int:
        return self.trees.leaf_stats.shape[-1]

    def proba_and_predictions(self, mat):
        """([rows, C] averaged per-tree distributions, [rows] argmax) —
        Spark's RandomForestClassificationModel decision rule."""
        leaf = self._leaf_stats_for(mat)  # [T, rows, C]
        tot = leaf.sum(-1, keepdims=True)
        per_tree = np.divide(
            leaf, np.where(tot > 0, tot, 1.0), dtype=leaf.dtype
        )
        raw = per_tree.sum(0)
        proba = raw / leaf.shape[0]
        return proba, np.argmax(proba, axis=1).astype(np.float64)

    def _predict_matrix(self, mat: np.ndarray) -> np.ndarray:
        return self.proba_and_predictions(mat)[1]

    def transform(self, dataset: Any) -> Any:
        if columnar.has_named_columns(dataset):
            mat = columnar.extract_matrix(
                dataset, self.getOrDefault("featuresCol")
            )
            proba, preds = self.proba_and_predictions(mat)
            cols = [
                (self.getOrDefault("rawPredictionCol"), proba * len(self.trees.feature)),
                (self.getOrDefault("probabilityCol"), proba),
                (self.getOrDefault("predictionCol"), preds),
            ]
            return columnar.append_columns(dataset, cols)
        return columnar.apply_column_transform(
            dataset,
            self.getOrDefault("featuresCol"),
            self.getOrDefault("predictionCol"),
            self._predict_matrix,
        )


# ---------------------------------------------------------------------------
# Regressor
# ---------------------------------------------------------------------------


class RandomForestRegressor(_ForestEstimator):
    impurity = Param("impurity", "'variance'", str)
    _classification = False
    _impurity_choices = ("variance",)

    def __init__(self, uid=None, **kwargs):
        super().__init__(uid, **kwargs)
        self._setDefault(impurity="variance")

    def _row_stats(self, y: np.ndarray, fdt) -> np.ndarray:
        y = y.astype(fdt)
        return np.stack([np.ones_like(y), y, y * y], axis=1)

    def _device_row_stats(self, ys: jax.Array) -> jax.Array:
        """[rows, 3] regression stats [1, y, y²] of device labels."""
        from spark_rapids_ml_tpu.parallel import forest as PF

        return PF.moments(ys)

    @property
    def _model_cls(self):
        return RandomForestRegressionModel


class RandomForestRegressionModel(_ForestModel):
    impurity = Param("impurity", "'variance'", str)

    def _predict_matrix(self, mat: np.ndarray) -> np.ndarray:
        leaf = self._leaf_stats_for(mat)  # [T, rows, 3]
        w = leaf[..., 0]
        mean = leaf[..., 1] / np.where(w > 0, w, 1.0)
        return mean.mean(0)

    def transform(self, dataset: Any) -> Any:
        return columnar.apply_column_transform(
            dataset,
            self.getOrDefault("featuresCol"),
            self.getOrDefault("predictionCol"),
            self._predict_matrix,
        )


# ---------------------------------------------------------------------------
# Single decision trees (pyspark.ml parity: a forest of one)
# ---------------------------------------------------------------------------


class _SingleTreeDefaults:
    """pyspark.ml's DecisionTree* estimators are exactly the forest
    machinery at numTrees=1, no bootstrap, all features per node — the
    deterministic CART the forest randomizes. Depth of the model's single
    tree and its importances come from the shared ensemble arrays."""

    def __init__(self, uid=None, **kwargs):
        super().__init__(uid, **kwargs)
        self._setDefault(
            numTrees=1, bootstrap=False, featureSubsetStrategy="all"
        )

    def setNumTrees(self, value):  # a decision tree IS one tree
        raise AttributeError(
            "DecisionTree estimators fit exactly one tree; use the "
            "RandomForest estimators for ensembles"
        )


class DecisionTreeClassifier(_SingleTreeDefaults, RandomForestClassifier):
    @property
    def _model_cls(self):
        return DecisionTreeClassificationModel


def _require_single_tree(data):
    """DecisionTree*Model.load must reject multi-tree (forest) saves — the
    richer-subclass upgrade rule assumes added behavior, not structure."""
    n_trees = data["feature"].shape[0]
    if n_trees != 1:
        raise TypeError(
            f"save holds {n_trees} trees; a DecisionTree model is exactly "
            "one — load it through the RandomForest model class"
        )


class DecisionTreeClassificationModel(RandomForestClassificationModel):
    @classmethod
    def _fromSaved(cls, uid, data):
        _require_single_tree(data)
        return super()._fromSaved(uid, data)

    @property
    def depth(self) -> int:
        """Depth of the fitted tree (deepest materialized split + 1)."""
        split_nodes = np.flatnonzero(self.trees.feature[0] >= 0)
        if len(split_nodes) == 0:
            return 0
        return int(np.floor(np.log2(split_nodes.max() + 1)) + 1)


class DecisionTreeRegressor(_SingleTreeDefaults, RandomForestRegressor):
    @property
    def _model_cls(self):
        return DecisionTreeRegressionModel


class DecisionTreeRegressionModel(RandomForestRegressionModel):
    depth = DecisionTreeClassificationModel.depth

    @classmethod
    def _fromSaved(cls, uid, data):
        _require_single_tree(data)
        return super()._fromSaved(uid, data)
