"""Spark DataFrame-facing estimators — the drop-in layer over pyspark.

The reference's user story (README.md:24-37): change one import and your
Spark ML PCA pipeline runs accelerated, with ``setInputCol`` taking an
ArrayType column. ``SparkPCA`` here is that layer for TPU: it drives a real
``pyspark.sql.DataFrame`` through the Arrow plan functions in
``spark_rapids_ml_tpu.spark.arrow_fns``:

- ``fit``:    ``df.mapInArrow(fit_partition_fn) → collect → merge → eigh``
              — the §3.1 call stack with mapInArrow standing in for
              ColumnarRdd and an Arrow shuffle standing in for the breeze
              ``reduce``.
- ``transform``: ``df.mapInArrow(transform_partition_fn)`` — the columnar
              UDF analog (RapidsPCA.scala:128-161); batches are projected on
              the executor-local accelerator.

pyspark is an OPTIONAL dependency: this module imports lazily and raises an
actionable error if Spark isn't installed. Everything executor-side lives in
``arrow_fns`` and is tested without Spark.
"""

from __future__ import annotations

import logging
from typing import Any

import numpy as np

from spark_rapids_ml_tpu.models.kmeans import KMeans, KMeansModel
from spark_rapids_ml_tpu.models.linear import (
    LinearRegression,
    LinearRegressionModel,
    LinearSVC,
    LinearSVCModel,
    LogisticRegression,
    LogisticRegressionModel,
)
from spark_rapids_ml_tpu.models.dbscan import DBSCAN, DBSCANModel
from spark_rapids_ml_tpu.models.forest import (
    RandomForestClassificationModel,
    RandomForestClassifier,
    RandomForestRegressionModel,
    RandomForestRegressor,
)
from spark_rapids_ml_tpu.models.gbt import (
    GBTClassificationModel,
    GBTClassifier,
    GBTRegressionModel,
    GBTRegressor,
)
from spark_rapids_ml_tpu.models.fm import (
    FMClassificationModel,
    FMClassifier,
    FMRegressionModel,
    FMRegressor,
)
from spark_rapids_ml_tpu.models.isotonic import (
    IsotonicRegression,
    IsotonicRegressionModel,
)
from spark_rapids_ml_tpu.models.mlp import (
    MultilayerPerceptronClassificationModel,
    MultilayerPerceptronClassifier,
)
from spark_rapids_ml_tpu.models.naive_bayes import NaiveBayes, NaiveBayesModel
from spark_rapids_ml_tpu.models.ovr import OneVsRest, OneVsRestModel
from spark_rapids_ml_tpu.models.neighbors import (
    ApproximateNearestNeighbors,
    ApproximateNearestNeighborsModel,
    NearestNeighbors,
    NearestNeighborsModel,
)
from spark_rapids_ml_tpu.models.pca import PCA, PCAModel
from spark_rapids_ml_tpu.models.umap import UMAP, UMAPModel
from spark_rapids_ml_tpu.models import scaler as _scaler_mod
from spark_rapids_ml_tpu.models.selector import (
    VarianceThresholdSelector,
    VarianceThresholdSelectorModel,
)
from spark_rapids_ml_tpu.models.discretizer import (
    Bucketizer,
    QuantileDiscretizer,
    QuantileDiscretizerModel,
)
from spark_rapids_ml_tpu.models.scaler import (
    DCT,
    Binarizer,
    ElementwiseProduct,
    Imputer,
    ImputerModel,
    MaxAbsScaler,
    MaxAbsScalerModel,
    MinMaxScaler,
    MinMaxScalerModel,
    Normalizer,
    PolynomialExpansion,
    RobustScaler,
    RobustScalerModel,
    StandardScaler,
    StandardScalerModel,
    VectorSlicer,
)
from spark_rapids_ml_tpu.models.truncated_svd import TruncatedSVD, TruncatedSVDModel
from spark_rapids_ml_tpu.models.params import Param
from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.spark import arrow_fns
from spark_rapids_ml_tpu.utils import columnar
from spark_rapids_ml_tpu.telemetry import trace_range

logger = logging.getLogger("spark_rapids_ml_tpu")


def _mesh_or_fallback():
    """The driver's device mesh for a mesh-local streamed fit.

    A failure to create it raises, whatever its class (an injected fault at
    site ``device.init`` included): the fit was asked to run on this host's
    devices, the single-device fold needs the very backend that just failed,
    and a quiet CPU run is not what the caller asked for.

    The one way to the single-device fallback (returns None) is a fit
    admitted under ``TPU_ML_ADMISSION_POLICY=degrade`` while a health
    component is FAILING — the operator chose not to poke the sick
    accelerator again. It is loud: a warning and ``degraded.cpu_fallback``."""
    from spark_rapids_ml_tpu.parallel import mesh as M
    from spark_rapids_ml_tpu.resilience import faults
    from spark_rapids_ml_tpu.telemetry import health as health_mod
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    if health_mod.admission_degrade_active():
        logger.warning(
            "DEGRADED: admission control admitted this fit under the "
            "degrade policy (a health component is FAILING); skipping mesh "
            "creation and streaming through the single-device fallback path"
        )
        REGISTRY.counter_inc("degraded.cpu_fallback")
        return None
    faults.inject("device.init")
    return M.create_mesh()


def _require_pyspark():
    try:
        import pyspark  # noqa: F401
        from pyspark.sql import DataFrame  # noqa: F401
    except ImportError as e:  # pragma: no cover - exercised via message test
        raise ImportError(
            "spark_rapids_ml_tpu.spark.estimators requires pyspark "
            "(pip install pyspark>=3.4) for pyspark DataFrames; the core "
            "estimators work without it on pandas/Arrow/ndarray input, and "
            "spark_rapids_ml_tpu.localspark offers the DataFrame API "
            "without a JVM"
        ) from e


def _sql_mods(dataset):
    """(types, functions) modules for the dataset's SQL backend — pyspark's
    for a pyspark DataFrame, localspark's for the no-JVM engine. All plan
    construction below goes through this pair, so the two backends run the
    SAME estimator code."""
    mod = type(dataset).__module__ or ""
    if mod.startswith("pyspark."):
        _require_pyspark()
        from pyspark.sql import functions, types

        return types, functions
    from spark_rapids_ml_tpu.localspark import functions, types

    return types, functions


class _HasDistribution:
    """Mixin: the DataFrame-fit cross-partition reduction strategy param —
    ONE definition shared by every estimator that offers the SPMD barrier
    path (subclasses narrow/widen ``_ALLOWED_DISTRIBUTIONS``)."""

    _ALLOWED_DISTRIBUTIONS: tuple = ("driver-merge", "mesh-barrier")

    distribution = Param(
        "distribution",
        "cross-partition reduction strategy for DataFrame fits: "
        "'driver-merge' (per-partition stats rows merged on the driver — "
        "the portable path, architecture parity with the reference's JVM "
        "reduce, RapidsRowMatrix.scala:139), 'mesh-barrier' (all partition "
        "tasks form one jax.distributed SPMD mesh inside a barrier stage "
        "and the reduction is a psum collective in one XLA program — the "
        "driver receives a single pre-reduced row; see spark/spmd.py), or, "
        "where supported, 'mesh-local' (rows stream to the driver process, "
        "which runs the same psum program over ITS device mesh — the "
        "one-device-owner-per-host deployment where the driver holds all "
        "local chips; see utils/devicepolicy.py)",
        str,
    )

    def __init__(self, uid: str | None = None, **kwargs):
        super().__init__(uid, **kwargs)
        self._setDefault(distribution="driver-merge")

    def setDistribution(self, value: str):
        if value not in self._ALLOWED_DISTRIBUTIONS:
            raise ValueError(
                f"distribution must be one of {self._ALLOWED_DISTRIBUTIONS}"
            )
        return self._set(distribution=value)


class SparkPCA(_HasDistribution, PCA):
    """PCA whose ``fit``/``transform`` accept ``pyspark.sql.DataFrame``.

    Inherits every param (k, inputCol, outputCol, meanCentering, precision,
    solver) and the persistence format from the core :class:`PCA`; only the
    data path differs. Non-Spark inputs fall through to the core paths, so
    one estimator serves both worlds.
    """

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-barrier", "mesh-local")

    def fit(
        self, dataset: Any, num_partitions: int | None = None, **kwargs
    ) -> "SparkPCAModel":
        from spark_rapids_ml_tpu.utils.config import get_config

        checkpoint_dir, checkpoint_every = _parse_checkpoint_kwargs(
            kwargs, get_config().stream_checkpoint_every_chunks
        )
        if not _is_spark_df(dataset):
            if checkpoint_dir is not None:
                raise NotImplementedError(
                    "checkpoint_dir applies to the mesh-local streamed "
                    "DataFrame fit; local containers fit in one resident pass"
                )
            core = super().fit(dataset, num_partitions)
            return self._copyValues(
                SparkPCAModel(uid=core.uid, pc=core.pc,
                              explainedVariance=core.explainedVariance,
                              mean=core.mean, std=core.std)
            )
        T, _ = _sql_mods(dataset)
        input_col = self.getInputCol()
        with trace_range("compute cov"):  # NvtxRange analog, RapidsRowMatrix.scala:62
            selected = dataset.select(input_col)
            # infer n from one row, like RapidsPCA.scala:73-74
            first = selected.first()
            if first is None:
                raise ValueError("empty dataset")
            if first[0] is None:
                raise ValueError(
                    f"input column {input_col!r} contains null feature "
                    "vectors; drop or impute nulls before fit"
                )
            n = columnar.feature_dim(first[0])
            k = self.getK()
            # validate before launching the cluster-wide Gram pass
            if k > n:
                raise ValueError(f"k={k} must be <= number of features {n}")
            distribution = self.getOrDefault("distribution")
            if checkpoint_dir is not None and (
                distribution != "mesh-local"
                or self.getOrDefault("solver") == "svd"
            ):
                raise NotImplementedError(
                    "checkpoint_dir requires distribution='mesh-local' with "
                    "a covariance solver: only the streamed chunk fold has "
                    "a resumable cursor"
                )
            if self.getOrDefault("solver") == "svd":
                if self.getOrDefault("standardize"):
                    raise ValueError(
                        "standardize=True derives the scaled covariance "
                        "from GramStats and so requires a covariance solver "
                        "('full'/'randomized'/'auto'); solver='svd' "
                        "decomposes R factors of the raw rows"
                    )
                # direct TSQR→SVD(R) path: never forms XᵀX, works at cond(X)
                # instead of cond(X)² (ops/linalg.py:403-420 rationale)
                return self._fit_svd(selected, input_col, n, k, distribution)
            if distribution == "mesh-barrier":
                arrays = _mesh_gram_arrays(
                    selected, input_col, self.getOrDefault("precision"), n
                )
                stats = L.GramStats(
                    arrays["xtx"], arrays["col_sum"], np.float64(arrays["count"])
                )
            elif distribution == "mesh-local":
                stats = self._mesh_local_stats(
                    selected, input_col, n,
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                )
            else:
                fit_fn = arrow_fns.make_fit_partition_fn(
                    input_col, precision=self.getOrDefault("precision")
                )
                stats_df = selected.mapInArrow(
                    fit_fn, schema=_spark_arrays_type(T, ["xtx", "col_sum", "count"])
                )
                if hasattr(stats_df, "toArrow"):  # PySpark >= 4.0: stays columnar
                    stats = arrow_fns.stats_from_batches(stats_df.toArrow().to_batches())
                else:  # PySpark 3.4/3.5: tiny payload (one [n,n] row per partition)
                    stats = arrow_fns.stats_from_rows(stats_df.collect())
        with trace_range("eigh"):
            import jax.numpy as jnp

            jstats = L.GramStats(
                jnp.asarray(stats.xtx),
                jnp.asarray(stats.col_sum),
                jnp.asarray(stats.count),
            )
            mean = std = None
            if self.getOrDefault("standardize"):
                # fused StandardScaler→PCA (BASELINE config 4): the scaled
                # covariance comes from the SAME one-pass GramStats
                cov, mean, std = L.standardized_cov_from_stats(jstats)
            else:
                cov = L.covariance_from_stats(
                    jstats, mean_centering=self.getMeanCentering()
                )
            pc, ev = L.pca_fit_from_cov(
                cov, k, solver=self.getOrDefault("solver")
            )
        with trace_range("model.to_host"):
            # the host may wait here for the eager decomposition's last
            # programs: the eigh span closes once they are enqueued
            model = SparkPCAModel(
                uid=self.uid,
                pc=np.asarray(pc),
                explainedVariance=np.asarray(ev),
                mean=None if mean is None else np.asarray(mean),
                std=None if std is None else np.asarray(std),
            )
        return self._copyValues(model)

    def _fit_svd(
        self, selected, input_col: str, n: int, k: int, distribution: str
    ) -> "SparkPCAModel":
        """The solver='svd' DataFrame fit, per distribution: driver-merge
        ships per-partition ``qr_r`` rows through the one-row Arrow stats
        machinery and tree-merges them with ``combine_r`` (QR-of-stacked-
        pair, not an elementwise sum); mesh-local runs the butterfly-TSQR
        program over the driver's own device mesh; mesh-barrier runs it
        across the barrier stage's jax.distributed process mesh, so the
        driver receives only the finished (pc, ev). meanCentering on the
        driver-merge path costs one extra cheap moments pass for the global
        mean, applied worker-side before padding so pad rows stay zero;
        mesh-local centers on the driver pre-padding, and mesh-barrier
        centers in-program with the pad mask."""
        import jax.numpy as jnp

        mean_centering = self.getMeanCentering()
        if distribution == "mesh-local":
            from spark_rapids_ml_tpu.parallel import tsqr as TSQR
            from spark_rapids_ml_tpu.spark import ingest

            # streamed O(shard)-host ingestion; centering happens in-program
            # with the pad mask (zero pad rows are exact for the uncentered
            # QR, but (x−μ) would turn them into −μ rows — the masked
            # program re-masks after centering)
            ing = ingest.stream_to_mesh(
                selected, features_col=input_col, n=n,
                with_weights=mean_centering,
            )
            if mean_centering:
                fit_svd = TSQR.make_distributed_fit_svd_masked(
                    ing.mesh, k, mean_centering=True
                )
                pc, ev = fit_svd(ing.xs, ing.ws)
            else:
                fit_svd = TSQR.make_distributed_fit_svd(
                    ing.mesh, k, mean_centering=False
                )
                pc, ev = fit_svd(ing.xs)
        elif distribution == "mesh-barrier":
            # butterfly TSQR across the barrier stage's process mesh: the
            # driver receives only the finished (pc, ev)
            from spark_rapids_ml_tpu.spark import spmd

            with trace_range("svd mesh fit"):
                arrays = _barrier_single_row(
                    selected,
                    spmd.MeshSVDFitFn(input_col, k, mean_centering),
                    spmd.SVD_FIT_FIELDS,
                    {"pc": (n, k), "explainedVariance": (k,), "count": (),
                     "mesh_size": ()},
                )
            pc, ev = arrays["pc"], arrays["explainedVariance"]
        else:
            T, _ = _sql_mods(selected)
            mean = None
            if mean_centering:
                shapes = {"count": (), "total": (n,), "total_sq": (n,)}
                arrays = _collect_stats(
                    selected,
                    arrow_fns.make_moments_partition_fn(input_col),
                    list(shapes),
                    shapes,
                )
                mean = arrays["total"] / max(float(arrays["count"]), 1.0)
            fn = arrow_fns.QRPartitionFn(input_col, mean)
            r_df = selected.mapInArrow(
                fn, schema=_spark_arrays_type(T, ["r"])
            )
            if hasattr(r_df, "toArrow"):
                r = arrow_fns.r_from_batches(r_df.toArrow().to_batches(), n)
            else:
                r = arrow_fns.r_from_rows(r_df.collect(), n)
            with trace_range("svd from r"):
                pc, ev = L.svd_from_r(jnp.asarray(r), k)
        model = SparkPCAModel(
            uid=self.uid, pc=np.asarray(pc), explainedVariance=np.asarray(ev)
        )
        return self._copyValues(model)

    def _mesh_local_stats(
        self, selected, input_col: str, n: int, *,
        checkpoint_dir=None, checkpoint_every=None,
    ) -> L.GramStats:
        """'mesh-local': stream rows shard-by-shard onto the driver's own
        device mesh (spark/ingest.py — O(shard) host RSS) and run the psum
        Gram program (parallel/gram.py) — the deployment where one process
        owns every local chip and DataFrame workers only do ingestion. Same
        XLA program as the in-core mesh path; zero pad rows are exact, the
        true count overrides.

        Above the ``TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES`` cutover the fit
        goes out-of-core: stream_fold drives the donated per-chunk Gram fold
        (parallel.gram.sharded_gram_fold) so device memory stays
        O(chunk + n²) — the resident [rows, n] array is never assembled.
        A ``checkpoint_dir`` makes that streamed pass resumable (carry +
        chunk cursor every ``checkpoint_every`` chunks)."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.spark import ingest

        precision = L.PRECISIONS[self.getOrDefault("precision")]
        rows = selected.count()
        if ingest.use_streamed_fit(rows, n):
            from spark_rapids_ml_tpu.utils.checkpoint import TrainingCheckpointer

            ckpt = TrainingCheckpointer(checkpoint_dir) if checkpoint_dir else None
            dt = ingest.wire_dtype()
            mesh = _mesh_or_fallback()
            if mesh is None:  # admission-degraded: single-device fold
                res = ingest.stream_fold(
                    selected,
                    L.gram_fold_step(precision),
                    features_col=input_col,
                    n=n,
                    init=L.init_gram_carry(n, dt),
                    rows=rows,
                    checkpointer=ckpt,
                    checkpoint_every=checkpoint_every,
                )
                return res.carry
            example = L.GramStats(
                xtx=jax.ShapeDtypeStruct((n, n), dt),
                col_sum=jax.ShapeDtypeStruct((n,), dt),
                count=jax.ShapeDtypeStruct((), dt),
            )
            # weighted count == Σ true-row weights == rows; no override needed
            return ingest.stream_fold_over_mesh(
                selected,
                lambda c, x, w: G.sharded_gram_fold(
                    c, x, w, mesh, precision=precision
                ),
                example,
                mesh,
                features_col=input_col,
                n=n,
                rows=rows,
                checkpointer=ckpt,
                checkpoint_every=checkpoint_every,
            ).carry
        if checkpoint_dir is not None:
            raise NotImplementedError(
                "checkpoint_dir applies to the out-of-core streamed fit; "
                "this dataset fits resident in device memory (lower "
                "TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES to force streaming)"
            )
        ing = ingest.stream_to_mesh(
            selected, features_col=input_col, n=n, rows=rows
        )
        stats = G.sharded_gram_stats(
            ing.xs, ing.mesh, precision=precision
        )
        return L.GramStats(
            stats.xtx, stats.col_sum,
            jnp.asarray(float(ing.rows), stats.count.dtype),
        )


class SparkPCAModel(PCAModel):
    """Fitted model whose ``transform`` streams Spark DataFrames through the
    executor-local accelerator via mapInArrow."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        T, _ = _sql_mods(dataset)
        input_col = self.getInputCol()
        output_col = self.getOutputCol()
        fn = arrow_fns.make_transform_partition_fn(
            input_col, output_col, self.pc, self.mean, self.std
        )
        out_schema = T.StructType(
            dataset.schema.fields
            + [T.StructField(output_col, T.ArrayType(T.DoubleType()))]
        )
        with trace_range("pca transform"):
            return dataset.mapInArrow(fn, schema=out_schema)


def _is_spark_df(dataset: Any) -> bool:
    return columnar.is_spark_dataframe(dataset)


# ---------------------------------------------------------------------------
# Shared plan helpers for the stats-monoid estimators
# ---------------------------------------------------------------------------


def _spark_arrays_type(T, fields: list[str]):
    return T.StructType(
        [T.StructField(f, T.ArrayType(T.DoubleType())) for f in fields]
    )


def _barrier_single_row(df, fn, fields: list[str], shapes: dict[str, tuple]):
    """Run one barrier-stage SPMD pass (spark/spmd.py) and decode the ONE
    pre-reduced stats row it delivers; shared by every mesh-barrier fit."""
    from spark_rapids_ml_tpu.spark import spmd

    T, _ = _sql_mods(df)
    stats_df = df.mapInArrow(
        fn, schema=_spark_arrays_type(T, fields), barrier=True
    )
    if hasattr(stats_df, "toArrow"):
        batches = stats_df.toArrow().to_batches()
    else:  # PySpark 3.5 collect() fallback
        batches = [
            arrow_fns.arrays_to_batch(
                {f: np.asarray(r[f], dtype=np.float64) for f in fields}
            )
            for r in stats_df.collect()
        ]
    return spmd.single_row_from_batches(batches, fields, shapes)


def _mesh_gram_arrays(selected, input_col: str, precision: str, n: int) -> dict:
    """One barrier-stage psum Gram pass (MeshGramPartitionFn) decoded to
    host arrays — shared by every estimator whose mesh-barrier reduce is the
    Gram monoid (SparkPCA, SparkTruncatedSVD)."""
    from spark_rapids_ml_tpu.spark import spmd

    return _barrier_single_row(
        selected,
        spmd.MeshGramPartitionFn(input_col, precision=precision),
        spmd.MESH_FIELDS,
        {"xtx": (n, n), "col_sum": (n,), "count": (), "mesh_size": ()},
    )


def _collect_stats(
    df, partition_fn, fields: list[str], shapes: dict[str, tuple], combine=None
):
    """Run a stats mapInArrow pass and fold the per-partition rows on the
    driver (toArrow on PySpark >= 4, collect() fallback below). The fold is
    per-field np.add unless ``combine`` overrides it (the range scalers'
    min/max monoid)."""
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    T, _ = _sql_mods(df)
    stats_df = df.mapInArrow(partition_fn, schema=_spark_arrays_type(T, fields))
    if hasattr(stats_df, "toArrow"):
        out = arrow_fns.arrays_from_batches(
            stats_df.toArrow().to_batches(), shapes, combine
        )
    else:
        out = arrow_fns.arrays_from_rows(stats_df.collect(), shapes, combine)
    # what the driver-merge deployment actually ships executor→driver: one
    # stats bundle of these shapes per partition (post-fold we only see the
    # merged arrays; per-bundle size × partition count is booked elsewhere —
    # this counter records the merged payload as the lower bound)
    REGISTRY.counter_inc(
        "drivermerge.bytes",
        sum(getattr(v, "nbytes", 0) for v in out.values())
        if isinstance(out, dict)
        else 0,
    )
    REGISTRY.counter_inc("drivermerge.passes")
    return out


def _resolve_col(obj, *names) -> str | None:
    """First set-or-defaulted column param among ``names`` — plain
    ``_paramMap.get`` would miss defaults like featuresCol='features'."""
    for n in names:
        if obj.isSet(n) or obj.hasDefault(n):
            return obj.getOrDefault(n)
    return None


def _resolve_input_col(model) -> str:
    # Spark ML reads the "features" column when the param is unset
    return _resolve_col(model, "inputCol", "featuresCol") or "features"


def _spark_append(dataset, fn, fields):
    """mapInArrow with the input schema plus ``fields`` appended — the one
    dispatch site every model transform (single- or multi-output) uses.
    The ``transform.dispatch`` span times plan construction only (mapInArrow
    is lazy); execution time lands in the per-partition
    ``transform.partition_seconds`` booked by the instrumented partition
    functions themselves (arrow_fns._InstrumentedTransformFn)."""
    T, _ = _sql_mods(dataset)
    with trace_range("transform.dispatch"):
        schema = T.StructType(
            dataset.schema.fields
            + [T.StructField(name, typ) for name, typ in fields]
        )
        return dataset.mapInArrow(fn, schema=schema)


def _spark_transform(model, dataset, matrix_fn, output_col, scalar: bool):
    T, _ = _sql_mods(dataset)
    input_col = _resolve_input_col(model)
    with trace_range("transform.plan"):
        fn = arrow_fns.make_matrix_map_partition_fn(
            input_col, output_col, matrix_fn
        )
        out_type = (
            T.DoubleType() if scalar else T.ArrayType(T.DoubleType())
        )
    return _spark_append(dataset, fn, [(output_col, out_type)])


def _parse_checkpoint_kwargs(kwargs: dict, default_every: int) -> tuple:
    """(checkpoint_dir, checkpoint_every) with the SAME validation the core
    estimators apply on local containers — a typo or a bad checkpoint_every
    must not silently train differently per container."""
    kwargs = dict(kwargs)
    checkpoint_dir = kwargs.pop("checkpoint_dir", None)
    checkpoint_every = kwargs.pop("checkpoint_every", None)
    if kwargs:
        raise TypeError(f"unexpected fit() kwargs: {sorted(kwargs)}")
    if checkpoint_every is None:  # None = the estimator's default cadence
        checkpoint_every = default_every
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    return checkpoint_dir, checkpoint_every


def _infer_n(df, col: str) -> int:
    first = df.select(col).first()
    if first is None:
        raise ValueError("empty dataset")
    if first[0] is None:
        raise ValueError(
            f"input column {col!r} contains null feature vectors; "
            "drop or impute nulls before fit"
        )
    return columnar.feature_dim(first[0])


# ---------------------------------------------------------------------------
# GLMs
# ---------------------------------------------------------------------------


class SparkLinearRegression(_HasDistribution, LinearRegression):
    """LinearRegression over pyspark DataFrames: one mapInArrow stats pass,
    driver-side normal-equations solve. Non-Spark inputs fall through.

    ``distribution='mesh-barrier'`` replaces the driver-side sum-merge with
    one SPMD psum across the barrier stage's jax.distributed process group
    (spark/spmd.py MeshLinRegPartitionFn): the [n, n] normal-equations
    reductions ride the mesh interconnect and the driver receives a single
    pre-reduced row. ``'mesh-local'`` streams rows to the driver and runs
    the same psum program over ITS device mesh (the
    one-device-owner-per-host deployment, utils/devicepolicy.py)."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-barrier", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None, **kwargs):
        from spark_rapids_ml_tpu.utils.config import get_config

        checkpoint_dir, checkpoint_every = _parse_checkpoint_kwargs(
            kwargs, get_config().stream_checkpoint_every_chunks
        )
        if checkpoint_dir is not None and (
            not _is_spark_df(dataset)
            or self.getOrDefault("distribution") != "mesh-local"
        ):
            # the normal-equations solve is one closed-form pass — there is
            # no training loop to checkpoint; only the mesh-local STREAMED
            # stats fold has a resumable chunk cursor
            raise NotImplementedError(
                "LinearRegression trains in one closed-form pass; "
                "checkpoint/resume applies only to the mesh-local streamed "
                "DataFrame fit's chunk cursor"
            )
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkLinearRegressionModel(
                uid=core.uid, coefficients=core.coefficients, intercept=core.intercept
            )
            return self._copyValues(model)
        feats = self.getOrDefault("featuresCol")
        label = self.getOrDefault("labelCol")
        weight_col = self._paramMap.get("weightCol")
        cols = [feats, label] + ([weight_col] if weight_col else [])
        n = _infer_n(dataset, feats)
        shapes = {
            "xtx": (n, n), "xty": (n,), "x_sum": (n,),
            "y_sum": (), "y_sq": (), "count": (),
        }
        with trace_range("linreg stats"):
            distribution = self.getOrDefault("distribution")
            if distribution == "mesh-local":
                from spark_rapids_ml_tpu.parallel import linear as PL
                from spark_rapids_ml_tpu.spark import ingest

                selected = dataset.select(*cols)
                rows = selected.count()
                if ingest.use_streamed_fit(rows, n):
                    # out-of-core: donated per-chunk LinearStats fold at
                    # O(chunk + n²) device memory (see _mesh_local_stats)
                    import jax

                    from spark_rapids_ml_tpu.ops import linear as LIN
                    from spark_rapids_ml_tpu.parallel import gram as G
                    from spark_rapids_ml_tpu.utils.checkpoint import (
                        TrainingCheckpointer,
                    )

                    ckpt = (
                        TrainingCheckpointer(checkpoint_dir)
                        if checkpoint_dir else None
                    )
                    dt = ingest.wire_dtype()
                    mesh = _mesh_or_fallback()
                    if mesh is None:  # admission-degraded: single-device fold
                        res = ingest.stream_fold(
                            selected,
                            LIN.linear_fold_step(),
                            features_col=feats,
                            n=n,
                            label_col=label,
                            weight_col=weight_col,
                            init=LIN.init_linear_carry(n, dt),
                            rows=rows,
                            checkpointer=ckpt,
                            checkpoint_every=checkpoint_every,
                        )
                        stats = res.carry
                    else:
                        example = LIN.LinearStats(
                            xtx=jax.ShapeDtypeStruct((n, n), dt),
                            xty=jax.ShapeDtypeStruct((n,), dt),
                            x_sum=jax.ShapeDtypeStruct((n,), dt),
                            y_sum=jax.ShapeDtypeStruct((), dt),
                            y_sq=jax.ShapeDtypeStruct((), dt),
                            count=jax.ShapeDtypeStruct((), dt),
                        )
                        stats = ingest.stream_fold_over_mesh(
                            selected,
                            lambda c, x, y, w: G.sharded_linear_fold(
                                c, x, y, w, mesh
                            ),
                            example,
                            mesh,
                            features_col=feats,
                            n=n,
                            label_col=label,
                            weight_col=weight_col,
                            rows=rows,
                            checkpointer=ckpt,
                            checkpoint_every=checkpoint_every,
                        ).carry
                elif checkpoint_dir is not None:
                    raise NotImplementedError(
                        "checkpoint_dir applies to the out-of-core streamed "
                        "fit; this dataset fits resident in device memory "
                        "(lower TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES to "
                        "force streaming)"
                    )
                else:
                    ing = ingest.stream_to_mesh(
                        selected, features_col=feats, n=n,
                        label_col=label, weight_col=weight_col,
                        with_weights=True, rows=rows,
                    )
                    stats = PL.sharded_linear_stats_weighted(
                        ing.xs, ing.ys, ing.ws, ing.mesh
                    )
                arrays = {
                    k: np.asarray(v) for k, v in zip(stats._fields, stats)
                }
            elif distribution == "mesh-barrier":
                from spark_rapids_ml_tpu.spark import spmd

                arrays = _barrier_single_row(
                    dataset.select(*cols),
                    spmd.MeshLinRegPartitionFn(feats, label, weight_col),
                    spmd.LINREG_MESH_FIELDS,
                    {**shapes, "mesh_size": ()},
                )
                arrays.pop("mesh_size")
            else:
                fn = arrow_fns.make_linreg_partition_fn(feats, label, weight_col)
                arrays = _collect_stats(
                    dataset.select(*cols), fn, list(shapes), shapes
                )
            if weight_col and float(arrays["count"]) == 0.0:
                raise ValueError("all instance weights are zero")
        with trace_range("linreg solve"):
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.ops import linear as LIN

            stats = LIN.LinearStats(**{k: jnp.asarray(v) for k, v in arrays.items()})
            # solve_from_stats routes α=0 to the closed form and α>0 to the
            # FISTA elastic-net path — same reduced stats either way, so
            # every distribution mode supports the full regularizer family
            coef, intercept = LIN.solve_from_stats(stats, **self._solve_args())
        model = SparkLinearRegressionModel(
            uid=self.uid, coefficients=np.asarray(coef), intercept=float(intercept)
        )
        return self._copyValues(model)


class SparkLinearRegressionModel(LinearRegressionModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOrDefault("predictionCol"), scalar=True,
        )


class SparkLogisticRegression(_HasDistribution, LogisticRegression):
    """Distributed IRLS over pyspark DataFrames.

    ``distribution='driver-merge'`` (default): one Spark job per Newton
    iteration (current parameters broadcast in the task closure), replicated
    solve on the driver between jobs — required for ``checkpoint_dir``.
    ``distribution='mesh-barrier'``: the ENTIRE IRLS loop — binary sigmoid
    or >=3-class softmax, routed automatically — runs as one XLA program
    (lax.while_loop with the psum inside the body) across the barrier
    stage's jax.distributed mesh: zero driver round-trips during training
    (spark/spmd.py MeshLogRegFitFn / MeshSoftmaxFitFn).
    ``'mesh-local'``: rows stream to the driver, which runs the SAME
    whole-loop program over its own device mesh - the
    one-device-owner-per-host deployment."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-barrier", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None, **kwargs):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions, **kwargs)
            # copy EVERY fitted field: a >=3-class dataset trains multinomial,
            # whose state lives in coefficientMatrix/interceptVector
            model = SparkLogisticRegressionModel(
                uid=core.uid,
                coefficients=core.coefficients,
                intercept=core.intercept,
                coefficientMatrix=core.coefficientMatrix,
                interceptVector=core.interceptVector,
            )
            return self._copyValues(model)
        checkpoint_dir, checkpoint_every = _parse_checkpoint_kwargs(kwargs, 5)
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import linear as LIN

        feats = self.getOrDefault("featuresCol")
        label = self.getOrDefault("labelCol")
        weight_col = self._paramMap.get("weightCol")
        cols = [feats, label] + ([weight_col] if weight_col else [])
        selected = dataset.select(*cols)
        fit_intercept = self.getFitIntercept()
        distribution = self.getOrDefault("distribution")
        n = _infer_n(dataset, feats)
        # class-count detection: one cheap distinct-label pass over the
        # label column (the DataFrame analog of the core path's np.unique,
        # models/linear.py:278-292), so >=3-class datasets route to the
        # softmax path with the same validation the core estimator applies
        with trace_range("label scan"):
            all_labels = self._scan_labels(dataset.select(label), label)
        from spark_rapids_ml_tpu.models.linear import _MAX_CLASSES

        if not np.all(all_labels == np.round(all_labels)) or all_labels.min() < 0:
            raise ValueError(
                "logistic regression requires integer class labels "
                f"0..C-1, got {all_labels[:8]}"
            )
        n_classes = int(all_labels.max()) + 1
        if n_classes > _MAX_CLASSES:
            raise ValueError(
                f"labels imply {n_classes} classes (max label "
                f"{int(all_labels.max())}), over the supported cap of "
                f"{_MAX_CLASSES} — the full-Newton Hessian is [C·d, C·d]. "
                "Check for mislabeled/ID-like rows, or re-encode labels "
                "densely as 0..C-1"
            )
        if distribution == "mesh-local":
            return self._fit_mesh_local(
                selected, feats, label, weight_col, n, n_classes,
                fit_intercept, checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
            )
        if distribution == "mesh-barrier":
            if n_classes > 2:
                return self._fit_softmax_mesh_barrier(
                    selected, feats, label, weight_col, n, n_classes,
                    fit_intercept, checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                )
            return self._fit_binary_mesh_barrier(
                selected, feats, label, weight_col, n, fit_intercept,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
            )
        if n_classes > 2:
            return self._fit_multinomial_df(
                selected, feats, label, weight_col, n, n_classes, fit_intercept,
                checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            )
        from spark_rapids_ml_tpu.models.linear import _resume_newton_checkpoint

        d = n + 1 if fit_intercept else n
        shapes = {"hess": (d, d), "grad": (d,), "loss": (), "count": ()}
        # the SAME durable-checkpoint contract as the core path: Spark-path
        # Newton state persists between Spark jobs, and a killed fit pointed
        # at the same directory resumes mid-loop (core helper, same layout)
        w_full, start_iter, ckpt = _resume_newton_checkpoint(checkpoint_dir, d)
        with trace_range("logreg newton"):
            for it in range(start_iter, self.getMaxIter()):
                fn = arrow_fns.make_logreg_newton_partition_fn(
                    feats, label, w_full,
                    fit_intercept=fit_intercept, weight_col=weight_col,
                )
                arrays = _collect_stats(selected, fn, list(shapes), shapes)
                if weight_col and float(arrays["count"]) == 0.0:
                    raise ValueError("all instance weights are zero")
                stats = LIN.NewtonStats(
                    **{k: jnp.asarray(v) for k, v in arrays.items()}
                )
                new_w, step_norm = LIN.newton_update(
                    jnp.asarray(w_full), stats,
                    reg_param=self.getRegParam(),
                    elastic_net_param=self.getElasticNetParam(),
                    fit_intercept=fit_intercept,
                )
                w_full = np.asarray(new_w)
                if ckpt is not None and (it + 1) % checkpoint_every == 0:
                    ckpt.save(it, {"w": w_full}, {"loss": float(stats.loss)})
                if float(step_norm) <= self.getTol():
                    break
        return self._binary_model(w_full, fit_intercept)

    def _fit_binary_mesh_barrier(
        self, selected, feats, label, weight_col, n, fit_intercept,
        *, checkpoint_dir=None, checkpoint_every=5,
    ) -> "SparkLogisticRegressionModel":
        """One barrier stage = the whole binary Newton fit (spark/spmd.py).

        With ``checkpoint_dir`` (a path on a filesystem SHARED by the
        driver and every executor — the jvm stagingDir contract) the stage
        runs chunked with rank-0 saves; the driver resolves the resume
        before launching, so a preempted fit restarts mid-loop."""
        from spark_rapids_ml_tpu.models.linear import _resume_newton_checkpoint
        from spark_rapids_ml_tpu.spark import spmd

        d = n + 1 if fit_intercept else n
        w0, start_iter, ckpt = _resume_newton_checkpoint(checkpoint_dir, d)
        if ckpt is not None and start_iter >= self.getMaxIter():
            return self._binary_model(np.asarray(w0), fit_intercept)
        with trace_range("logreg mesh fit"):
            arrays = _barrier_single_row(
                selected,
                spmd.MeshLogRegFitFn(
                    feats, label, weight_col,
                    reg_param=self.getRegParam(),
                    elastic_net_param=self.getElasticNetParam(),
                    fit_intercept=fit_intercept,
                    max_iter=self.getMaxIter(),
                    tol=self.getTol(),
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                    w0=w0 if ckpt is not None else None,
                    start_iter=start_iter,
                ),
                spmd.LOGREG_FIT_FIELDS,
                {"w": (d,), "iterations": (), "count": (), "mesh_size": ()},
            )
        if weight_col and float(arrays["count"]) == 0.0:
            raise ValueError("all instance weights are zero")
        return self._binary_model(arrays["w"], fit_intercept)

    def _fit_softmax_mesh_barrier(
        self, selected, feats, label, weight_col, n, n_classes, fit_intercept,
        *, checkpoint_dir=None, checkpoint_every=5,
    ) -> "SparkLogisticRegressionModel":
        """One barrier stage = the whole softmax Newton fit (spark/spmd.py
        MeshSoftmaxFitFn); mirrors _fit_multinomial_df's model surface.
        Checkpointing follows _fit_binary_mesh_barrier's shared-filesystem
        rank-0 contract."""
        from spark_rapids_ml_tpu.models.linear import _resume_newton_checkpoint
        from spark_rapids_ml_tpu.spark import spmd

        d = n + 1 if fit_intercept else n
        cd = n_classes * d
        w0, start_iter, ckpt = _resume_newton_checkpoint(checkpoint_dir, cd)
        if ckpt is not None and start_iter >= self.getMaxIter():
            # resumed at the final iteration: build the model directly,
            # like the binary sibling (no stage launch, no fake stats row)
            return self._softmax_model(np.asarray(w0), n_classes, fit_intercept)
        with trace_range("softmax mesh fit"):
            arrays = _barrier_single_row(
                selected,
                spmd.MeshSoftmaxFitFn(
                    feats, label, weight_col, n_classes,
                    reg_param=self.getRegParam(),
                    elastic_net_param=self.getElasticNetParam(),
                    fit_intercept=fit_intercept,
                    max_iter=self.getMaxIter(),
                    tol=self.getTol(),
                    checkpoint_dir=checkpoint_dir,
                    checkpoint_every=checkpoint_every,
                    w0=w0 if ckpt is not None else None,
                    start_iter=start_iter,
                ),
                spmd.LOGREG_FIT_FIELDS,
                {"w": (cd,), "iterations": (), "count": (),
                 "mesh_size": ()},
            )
        if weight_col and float(arrays["count"]) == 0.0:
            raise ValueError("all instance weights are zero")
        return self._softmax_model(arrays["w"], n_classes, fit_intercept)

    def _fit_mesh_local(
        self, selected, feats, label, weight_col, n, n_classes, fit_intercept,
        *, checkpoint_dir=None, checkpoint_every=5,
    ) -> "SparkLogisticRegressionModel":
        """'mesh-local': stream-ingest onto the driver's own device mesh,
        run the whole-loop IRLS program (binary or softmax) over it -
        identical training program to the barrier path, minus the
        process-group bootstrap. With ``checkpoint_dir`` the loop runs in
        ``checkpoint_every``-iteration CHUNKS (one cached XLA program per
        chunk, a durable host checkpoint between chunks) so a preempted fit
        resumes instead of restarting — the r3 verdict's #6; driver
        round-trips stay 1-per-K rather than the driver-merge path's
        1-per-iteration."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import linear as LIN
        from spark_rapids_ml_tpu.parallel import linear as PL
        from spark_rapids_ml_tpu.spark import ingest
        from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

        ing = ingest.stream_to_mesh(
            selected, features_col=feats, n=n,
            label_col=label, weight_col=weight_col, with_weights=True,
            augment_intercept=fit_intercept,
        )
        if weight_col and float(ing.ws.sum()) == 0.0:
            raise ValueError("all instance weights are zero")
        xs, ys, ws, mesh = ing.xs, ing.ys, ing.ws, ing.mesh
        reg = dict(
            reg_param=self.getRegParam(),
            elastic_net_param=self.getElasticNetParam(),
            fit_intercept=fit_intercept,
        )
        max_iter, tol = self.getMaxIter(), self.getTol()
        if checkpoint_dir is not None:
            from spark_rapids_ml_tpu.models.linear import (
                _resume_newton_checkpoint,
            )

            d = n + 1 if fit_intercept else n
            cd = n_classes * d if n_classes > 2 else d
            w0, start_iter, ckpt = _resume_newton_checkpoint(
                checkpoint_dir, cd
            )
            if n_classes > 2:
                chunk_fn = PL.make_distributed_softmax_chunk(
                    mesh, n_classes, chunk_iters=checkpoint_every, tol=tol,
                    **reg,
                )
            else:
                chunk_fn = PL.make_distributed_logreg_chunk(
                    mesh, chunk_iters=checkpoint_every, tol=tol, **reg
                )
            with trace_range("logreg mesh-local chunked fit"):
                w, it = PL.run_chunked_newton(
                    chunk_fn, xs, ys, ws, w0,
                    start_iter=start_iter, max_iter=max_iter, tol=tol,
                    ckpt=ckpt,
                )
            w_final = np.asarray(w)
            done = it - start_iter
        else:
            # the span covers the wait: the program returns at dispatch, and
            # the copies to the host are where it is waited for
            with trace_range("logreg mesh-local fit"):
                if n_classes > 2:
                    fit_fn = PL.make_distributed_softmax_fit(
                        mesh, n_classes, max_iter=max_iter, tol=tol, **reg
                    )
                else:
                    fit_fn = PL.make_distributed_logreg_fit(
                        mesh, max_iter=max_iter, tol=tol, **reg
                    )
                w_dev, done, final_step = fit_fn(xs, ys, ws)
                LIN.check_newton_outcome(final_step, w_dev)
                w_final, done = np.asarray(w_dev), int(done)
        REGISTRY.counter_inc("logreg.iterations", done, path="mesh-local")
        if n_classes > 2:
            return self._softmax_model(w_final, n_classes, fit_intercept)
        return self._binary_model(w_final, fit_intercept)

    def _binary_model(
        self, w_full: np.ndarray, fit_intercept: bool
    ) -> "SparkLogisticRegressionModel":
        """The one place the fitted [d] parameter becomes a model — both
        distribution modes return identically-shaped results."""
        if fit_intercept:
            coef, intercept = w_full[:-1], float(w_full[-1])
        else:
            coef, intercept = w_full, 0.0
        model = SparkLogisticRegressionModel(
            uid=self.uid, coefficients=coef, intercept=intercept
        )
        return self._copyValues(model)

    def _softmax_model(
        self, w_flat: np.ndarray, n_classes: int, fit_intercept: bool
    ) -> "SparkLogisticRegressionModel":
        """The multinomial sibling of ``_binary_model``: flattened [C·d]
        parameter → coefficientMatrix/interceptVector model."""
        w_mat = np.asarray(w_flat).reshape(n_classes, -1)
        if fit_intercept:
            coef_matrix, intercepts = w_mat[:, :-1], w_mat[:, -1]
        else:
            coef_matrix, intercepts = w_mat, np.zeros(n_classes)
        model = SparkLogisticRegressionModel(
            uid=self.uid,
            coefficientMatrix=coef_matrix,
            interceptVector=intercepts,
        )
        return self._copyValues(model)

    @staticmethod
    def _scan_labels(label_df, label: str) -> np.ndarray:
        T, _ = _sql_mods(label_df)
        scan_df = label_df.mapInArrow(
            arrow_fns.LabelScanPartitionFn(label),
            schema=_spark_arrays_type(T, ["labels"]),
        )
        if hasattr(scan_df, "toArrow"):
            return arrow_fns.labels_from_batches(scan_df.toArrow().to_batches())
        return arrow_fns.labels_from_rows(scan_df.collect())

    def _fit_multinomial_df(
        self,
        selected,
        feats: str,
        label: str,
        weight_col: str | None,
        n: int,
        n_classes: int,
        fit_intercept: bool,
        *,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5,
    ) -> "SparkLogisticRegressionModel":
        """Softmax IRLS over DataFrames: one Spark job per Newton iteration
        on the flattened [C·d] parameter, mirroring the core path
        (models/linear.py:336-393) with SoftmaxStats riding the same one-row
        Arrow stats machinery as every other monoid."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import linear as LIN

        from spark_rapids_ml_tpu.models.linear import _resume_newton_checkpoint

        d = n + 1 if fit_intercept else n
        cd = n_classes * d
        shapes = {"hess": (cd, cd), "grad": (cd,), "loss": (), "count": ()}
        w_flat, start_iter, ckpt = _resume_newton_checkpoint(checkpoint_dir, cd)
        with trace_range("softmax newton"):
            for it in range(start_iter, self.getMaxIter()):
                fn = arrow_fns.SoftmaxNewtonPartitionFn(
                    feats, label, w_flat, n_classes,
                    fit_intercept=fit_intercept, weight_col=weight_col,
                )
                arrays = _collect_stats(selected, fn, list(shapes), shapes)
                if weight_col and float(arrays["count"]) == 0.0:
                    raise ValueError("all instance weights are zero")
                stats = LIN.SoftmaxStats(
                    **{k: jnp.asarray(v) for k, v in arrays.items()}
                )
                new_w, step_norm = LIN.softmax_newton_update(
                    jnp.asarray(w_flat), stats, n_classes,
                    reg_param=self.getRegParam(),
                    elastic_net_param=self.getElasticNetParam(),
                    fit_intercept=fit_intercept,
                )
                w_flat = np.asarray(new_w)
                if ckpt is not None and (it + 1) % checkpoint_every == 0:
                    ckpt.save(it, {"w": w_flat}, {"loss": float(stats.loss)})
                if float(step_norm) <= self.getTol():
                    break
        return self._softmax_model(w_flat, n_classes, fit_intercept)


class SparkLogisticRegressionModel(LogisticRegressionModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        proba_col = self.getProbabilityCol()
        if not proba_col:
            return _spark_transform(
                self, dataset, self._predict_matrix,
                self.getOrDefault("predictionCol"), scalar=True,
            )
        # one device pass emits BOTH Spark ML output columns
        T, _ = _sql_mods(dataset)
        pred_col = self.getOrDefault("predictionCol")
        fn = arrow_fns.ProbaPredictionPartitionFn(
            _resolve_input_col(self), proba_col, pred_col,
            self.proba_and_predictions,
        )
        with trace_range("logreg transform"):
            return _spark_append(
                dataset,
                fn,
                [
                    (proba_col, T.ArrayType(T.DoubleType())),
                    (pred_col, T.DoubleType()),
                ],
            )


# ---------------------------------------------------------------------------
# KMeans
# ---------------------------------------------------------------------------


class SparkKMeans(_HasDistribution, KMeans):
    """Lloyd over pyspark DataFrames: seeding runs driver-coordinated
    (bounded sample or k-means|| passes), then training either as one
    mapInArrow stats job per iteration with centers broadcast per job
    (``distribution='driver-merge'``, required for ``checkpoint_dir``) or
    as ONE barrier stage whose while_loop+psum program runs the entire
    Lloyd loop on the executor mesh (``'mesh-barrier'``, zero driver
    round-trips during training — spark/spmd.py MeshKMeansFitFn), or with
    rows streamed to the driver and the SAME while_loop+psum program run
    over the driver's own mesh (``'mesh-local'``)."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-barrier", "mesh-local")

    _INIT_SAMPLE = 4096

    def fit(self, dataset: Any, num_partitions: int | None = None, **kwargs):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions, **kwargs)
            model = SparkKMeansModel(
                uid=core.uid,
                clusterCenters=core.clusterCenters,
                trainingCost=core.trainingCost,
            )
            return self._copyValues(model)
        checkpoint_dir, checkpoint_every = _parse_checkpoint_kwargs(kwargs, 1)
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import kmeans as KM

        _, F = _sql_mods(dataset)

        input_col = _resolve_col(self, "inputCol") or "features"
        weight_col = self._paramMap.get("weightCol")
        cols = [input_col] + ([weight_col] if weight_col else [])
        selected = dataset.select(*cols)
        k = self.getK()

        distribution = self.getOrDefault("distribution")
        # resume BEFORE seeding: an interrupted Spark-path fit pointed at the
        # same checkpoint_dir continues mid-Lloyd (the SAME resume contract
        # and layout as the core path — shared helper)
        from spark_rapids_ml_tpu.models.kmeans import _resume_kmeans_checkpoint

        resumed_centers, start_iter, cost0, ckpt = _resume_kmeans_checkpoint(
            checkpoint_dir, k
        )
        if resumed_centers is not None:
            n_data = _infer_n(dataset, input_col)
            if resumed_centers.shape[1] != n_data:
                raise ValueError(
                    f"checkpoint centers have {resumed_centers.shape[1]} "
                    f"features but the dataset has {n_data}; is "
                    "checkpoint_dir stale?"
                )
            return self._lloyd_df(
                selected, input_col, weight_col, resumed_centers,
                ckpt=ckpt, checkpoint_every=checkpoint_every,
                start_iter=start_iter, cost0=cost0,
                checkpoint_dir=checkpoint_dir,
            )

        if self.getInitMode() == "k-means||":
            # mesh-local seeds IN-PROGRAM on the mesh (r3 verdict #8): the
            # sampling rounds run as psum/all_gather passes over the
            # already-ingested shards inside _lloyd_df (centers=None, span
            # "kmeans mesh init"), so the whole fit is driver-hop-free — no
            # candidates bounce through Spark jobs
            centers = None
            if distribution != "mesh-local":
                with trace_range("kmeans init"):
                    centers = self._kmeans_parallel_init_df(
                        selected, input_col, weight_col, k
                    )
            return self._lloyd_df(
                selected, input_col, weight_col, centers,
                ckpt=ckpt, checkpoint_every=checkpoint_every,
                checkpoint_dir=checkpoint_dir,
            )
        with trace_range("kmeans init"):
            # zero-weight rows are excluded instances: filter them in the
            # PLAN so the bounded sample only sees seedable rows
            seed_df = (
                selected.where(F.col(weight_col) > 0) if weight_col else selected
            )
            # RANDOM sample across all partitions, not limit() (which takes
            # the first rows in plan order — biased when data is sorted or
            # partition-clustered, and can yield pathological k-means++
            # seeds). df.sample needs a fraction: derive it from a count and
            # oversample 2x to absorb Bernoulli-sampling variance, then trim.
            total = seed_df.count()
            if total > self._INIT_SAMPLE:
                fraction = min(1.0, 2.0 * self._INIT_SAMPLE / total)
                sample_rows = seed_df.sample(
                    fraction=fraction, seed=self.getSeed()
                ).collect()
                if len(sample_rows) > self._INIT_SAMPLE:
                    # trim on the driver with an rng, NOT limit() — limit
                    # would re-bias toward whichever partitions plan first
                    rng = np.random.default_rng(self.getSeed())
                    keep = rng.choice(
                        len(sample_rows), self._INIT_SAMPLE, replace=False
                    )
                    sample_rows = [sample_rows[i] for i in keep]
                elif len(sample_rows) < self.getK():
                    # pathological sampling shortfall: take everything bounded
                    sample_rows = seed_df.limit(self._INIT_SAMPLE).collect()
            else:
                sample_rows = seed_df.collect()
            if len(sample_rows) < k:
                raise ValueError(
                    f"k={k} but only {len(sample_rows)} rows with positive "
                    "weight were found to seed centers from"
                )
            sample = np.stack(
                [columnar.row_vector_to_ndarray(r[0]) for r in sample_rows]
            )
            if self.getInitMode() == "random":
                rng = np.random.default_rng(self.getSeed())
                centers = sample[rng.choice(len(sample), k, replace=False)]
            else:
                key = jax.random.PRNGKey(self.getSeed())
                centers = np.asarray(
                    KM.kmeans_plus_plus_init(key, jnp.asarray(sample), k)
                )

        return self._lloyd_df(
            selected, input_col, weight_col, centers,
            ckpt=ckpt, checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir,
        )

    def _lloyd_df(
        self,
        selected,
        input_col: str,
        weight_col: str | None,
        centers: np.ndarray | None,
        *,
        ckpt=None,
        checkpoint_every: int = 1,
        start_iter: int = 0,
        cost0: float = np.inf,
        checkpoint_dir: str | None = None,
    ) -> "SparkKMeansModel":
        """The Lloyd loop over DataFrames: one mapInArrow stats job per
        iteration, centers broadcast in the task state; with ``ckpt`` set,
        durable training-state checkpoints between Spark jobs. ``cost0``
        carries the checkpointed cost so a resume at maxIter (zero further
        iterations) still reports the true trainingCost.

        ``centers=None`` means "seed on the mesh" (k-means|| rounds as one
        SPMD program over the ingested shards) and is ONLY meaningful for
        distribution='mesh-local'; every other mode requires concrete
        centers."""
        if centers is None and self.getOrDefault("distribution") != "mesh-local":
            raise ValueError(
                "centers=None (in-program k-means|| seeding) requires "
                "distribution='mesh-local'"
            )
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import kmeans as KM

        k = self.getK()
        if self.getOrDefault("distribution") == "mesh-local":
            import jax

            from spark_rapids_ml_tpu.parallel import kmeans as PK

            from spark_rapids_ml_tpu.spark import ingest
            from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

            n = (
                centers.shape[1]
                if centers is not None
                else _infer_n(selected, input_col)
            )
            ing = ingest.stream_to_mesh(
                selected, features_col=input_col, n=n,
                weight_col=weight_col, with_weights=True,
            )
            if weight_col and float(ing.ws.sum()) == 0.0:
                raise ValueError("all instance weights are zero")
            if centers is None:
                # k-means|| seeding ON the mesh: Bahmani rounds as one XLA
                # program over the ingested shards, weighted k-means++
                # k-reduction on-device — candidates never leave the mesh
                with trace_range("kmeans mesh init"):
                    # the seeding program (jit__kmeans_seed): its dispatch,
                    # and the wait for its counts on the host
                    with trace_range("kmeans.seed.rounds"):
                        init_fn = PK.make_distributed_kmeans_parallel_init(
                            ing.mesh, k, init_steps=self.getInitSteps()
                        )
                        cand, counts = init_fn(
                            ing.xs, ing.ws, jax.random.PRNGKey(self.getSeed())
                        )
                        owned = int((np.asarray(counts) > 0).sum())
                    if owned <= k:
                        # degenerate oversampling (tiny/collapsed data):
                        # the driver-pass init has the uniform top-up logic
                        centers = self._kmeans_parallel_init_df(
                            selected, input_col, weight_col, k
                        )
                    else:
                        # the eager reduction to k, and the centres' copy
                        with trace_range("kmeans.seed.reduce"):
                            centers = np.asarray(
                                KM.weighted_kmeans_plus_plus_init(
                                    jax.random.PRNGKey(self.getSeed() + 1),
                                    cand, counts, k,
                                )
                            )
            max_iter, tol = self.getMaxIter(), self.getTol()
            # the loop's carry is the shard's dtype (a float32 shard under
            # x64 would otherwise meet float64 centres from the host)
            centers = np.asarray(centers, dtype=ing.xs.dtype)
            if ckpt is not None:
                # chunked whole-loop Lloyd: checkpoint_every iterations per
                # cached XLA program, durable centers between chunks (the
                # same resume contract as the driver-merge loop)
                with trace_range("kmeans mesh-local chunked fit"):
                    c, cost, it = PK.run_chunked_lloyd(
                        PK.make_distributed_kmeans_chunk(
                            ing.mesh, chunk_iters=checkpoint_every, tol=tol
                        ),
                        ing.xs, ing.ws, centers,
                        start_iter=start_iter, max_iter=max_iter, tol=tol,
                        ckpt=ckpt, cost0=cost0,
                    )
                    c = np.asarray(c)
                done = it - start_iter
            else:
                fit_fn = PK.make_distributed_kmeans_fit(
                    ing.mesh, max_iter=max_iter, tol=tol
                )
                # the span covers the wait: the call above returns at
                # dispatch, and the copies to the host are where the program
                # is waited for
                with trace_range("kmeans mesh-local fit"):
                    c, cost, done = fit_fn(
                        ing.xs, ing.ws, jnp.asarray(centers)
                    )
                    c, cost, done = np.asarray(c), float(cost), int(done)
            REGISTRY.counter_inc("kmeans.iterations", done, path="mesh-local")
            if KM.exact_bf16_parts(ing.xs.dtype):
                REGISTRY.counter_inc(
                    "kmeans.split_iterations", done, path="mesh-local"
                )
            model = SparkKMeansModel(
                uid=self.uid, clusterCenters=c, trainingCost=cost
            )
            return self._copyValues(model)
        if self.getOrDefault("distribution") == "mesh-barrier":
            from spark_rapids_ml_tpu.spark import spmd

            if start_iter >= self.getMaxIter():
                # resumed at the final iteration: nothing left to run
                model = SparkKMeansModel(
                    uid=self.uid, clusterCenters=centers,
                    trainingCost=float(cost0),
                )
                return self._copyValues(model)
            with trace_range("kmeans mesh fit"):
                arrays = _barrier_single_row(
                    selected,
                    spmd.MeshKMeansFitFn(
                        input_col, centers, weight_col,
                        max_iter=self.getMaxIter(), tol=self.getTol(),
                        checkpoint_dir=checkpoint_dir,
                        checkpoint_every=checkpoint_every,
                        start_iter=start_iter,
                    ),
                    spmd.KMEANS_FIT_FIELDS,
                    {"centers": (k, centers.shape[1]), "cost": (),
                     "iterations": (), "count": (), "mesh_size": ()},
                )
            if weight_col and float(arrays["count"]) == 0.0:
                raise ValueError("all instance weights are zero")
            model = SparkKMeansModel(
                uid=self.uid,
                clusterCenters=arrays["centers"],
                trainingCost=float(arrays["cost"]),
            )
            return self._copyValues(model)
        tol_sq = self.getTol() ** 2
        n = centers.shape[1]
        shapes = {"sums": (k, n), "counts": (k,), "cost": ()}
        cost = cost0
        with trace_range("kmeans lloyd"):
            for it in range(start_iter, self.getMaxIter()):
                fn = arrow_fns.make_kmeans_partition_fn(
                    input_col, centers, weight_col
                )
                arrays = _collect_stats(selected, fn, list(shapes), shapes)
                if weight_col and float(arrays["counts"].sum()) == 0.0:
                    raise ValueError("all instance weights are zero")
                stats = KM.KMeansStats(
                    **{f: jnp.asarray(v) for f, v in arrays.items()}
                )
                new_centers = np.asarray(
                    KM.update_centers(stats, jnp.asarray(centers))
                )
                cost = float(stats.cost)
                shift = float(
                    KM.center_shift_sq(jnp.asarray(centers), jnp.asarray(new_centers))
                )
                centers = new_centers
                if ckpt is not None and (it + 1) % checkpoint_every == 0:
                    ckpt.save(it, {"centers": centers}, {"cost": cost})
                if shift <= tol_sq:
                    break
        model = SparkKMeansModel(
            uid=self.uid, clusterCenters=centers, trainingCost=cost
        )
        return self._copyValues(model)

    def _kmeans_parallel_init_df(
        self, selected, input_col: str, weight_col: str | None, k: int
    ) -> np.ndarray:
        """k-means‖ over DataFrames (Bahmani et al. — the distributed init
        the r2 verdict's config-5 gap called for): per round, one cost job
        (φ) and one Bernoulli-oversampling job (ℓ = 2k expected candidates,
        p = ℓ·w·d²/φ per row), candidates collected to the driver; then one
        weighting job (rows owned per candidate) and a weighted k-means++
        reduction to k. Mirrors the core path (models/kmeans.py
        _kmeans_parallel_init) with Spark jobs as the passes."""
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import kmeans as KM

        T, F = _sql_mods(selected)
        ell = 2.0 * k
        seed = self.getSeed()
        # zero-weight rows are excluded instances and must never become
        # candidates — same invariant as the k-means++ branch and the core
        # path (models/kmeans.py keep = w > 0). The sampling fn's p ∝ w
        # already zeroes them; the probe and top-up draw from this plan.
        seedable = (
            selected.where(F.col(weight_col) > 0) if weight_col else selected
        )

        def run_pass(df, fn, schema, decode_batches, decode_rows):
            out_df = df.mapInArrow(fn, schema=schema)
            if hasattr(out_df, "toArrow"):
                return decode_batches(out_df.toArrow().to_batches())
            return decode_rows(out_df.collect())

        # first candidate: one row from a small random sample (uniform-ish
        # across partitions; .first() alone would bias to plan order)
        probe = seedable.sample(fraction=0.05, seed=seed).first() or seedable.first()
        if probe is None:
            raise ValueError("no rows with positive weight to seed from")
        candidates = columnar.row_vector_to_ndarray(probe[0])[None, :]

        assign_shapes = lambda m: {"counts": (m,), "cost": ()}  # noqa: E731
        for step in range(self.getInitSteps()):
            arrays = run_pass(
                selected,
                arrow_fns.KMeansAssignStatsFn(input_col, candidates, weight_col),
                _spark_arrays_type(T, ["counts", "cost"]),
                lambda b: arrow_fns.arrays_from_batches(
                    b, assign_shapes(len(candidates))
                ),
                lambda r: arrow_fns.arrays_from_rows(
                    r, assign_shapes(len(candidates))
                ),
            )
            phi = float(arrays["cost"])
            if phi <= 0.0:  # every (weighted) row coincides with a candidate
                break
            new = run_pass(
                selected,
                arrow_fns.KMeansParallelSampleFn(
                    input_col, candidates, ell / phi, seed + step + 1, weight_col
                ),
                T.StructType(
                    [T.StructField("candidate", T.ArrayType(T.DoubleType()))]
                ),
                arrow_fns.candidates_from_batches,
                arrow_fns.candidates_from_rows,
            )
            if new.size:
                candidates = np.concatenate([candidates, new], axis=0)

        if len(candidates) <= k:
            # degenerate oversampling: top up from a bounded uniform sample
            # of seedable (positive-weight) rows
            extra = seedable.sample(
                fraction=min(1.0, (4.0 * k) / max(seedable.count(), 1)),
                seed=seed,
            ).collect()
            pool = np.stack(
                [columnar.row_vector_to_ndarray(r[0]) for r in extra]
            ) if extra else np.zeros((0, candidates.shape[1]))
            need = k - len(candidates)
            if need > 0:
                if len(pool) < need:
                    raise ValueError(
                        f"k={k} but only {len(candidates) + len(pool)} "
                        "candidate rows could be drawn"
                    )
                rng = np.random.default_rng(seed)
                candidates = np.concatenate(
                    [candidates, pool[rng.choice(len(pool), need, replace=False)]]
                )
            return candidates[:k]

        # weighting pass: instance-weighted row counts owned by each
        # candidate (counts only — the Lloyd fn's [k, n] sums would dominate
        # the shuffle for nothing here)
        arrays = run_pass(
            selected,
            arrow_fns.KMeansAssignStatsFn(input_col, candidates, weight_col),
            _spark_arrays_type(T, ["counts", "cost"]),
            lambda b: arrow_fns.arrays_from_batches(
                b, assign_shapes(len(candidates))
            ),
            lambda r: arrow_fns.arrays_from_rows(r, assign_shapes(len(candidates))),
        )
        key = jax.random.PRNGKey(seed)
        return np.asarray(
            KM.weighted_kmeans_plus_plus_init(
                key, jnp.asarray(candidates), jnp.asarray(arrays["counts"]), k
            )
        )


class SparkKMeansModel(KMeansModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOutputCol(), scalar=True,
        )

    def computeCost(self, dataset: Any) -> float:
        """Sum of squared distances to the nearest centroid; on DataFrames
        one mapInArrow assignment pass (KMeansAssignStatsFn) — the cost
        reduces executor-side, only a scalar row reaches the driver."""
        if not _is_spark_df(dataset):
            return super().computeCost(dataset)
        input_col = _resolve_col(self, "inputCol") or "features"
        shapes = {"counts": (len(self.clusterCenters),), "cost": ()}
        try:
            arrays = _collect_stats(
                dataset.select(input_col),
                arrow_fns.KMeansAssignStatsFn(input_col, self.clusterCenters),
                ["counts", "cost"],
                shapes,
            )
        except ValueError as e:
            if "no partition statistics" in str(e):
                return 0.0  # every partition empty: match the core path
            raise
        return float(arrays["cost"])


# ---------------------------------------------------------------------------
# StandardScaler
# ---------------------------------------------------------------------------


class SparkStandardScaler(_HasDistribution, StandardScaler):
    """StandardScaler over pyspark DataFrames: one mapInArrow moments pass;
    ``distribution='mesh-barrier'`` reduces the moments as one SPMD psum
    across the barrier stage's process group (spark/spmd.py);
    ``'mesh-local'`` streams rows to the driver and runs the same psum
    program over its own device mesh."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-barrier", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkStandardScalerModel(
                uid=core.uid, mean=core.mean, std=core.std
            )
            return self._copyValues(model)
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import scaler as S

        input_col = _resolve_col(self, "inputCol") or "features"
        n = _infer_n(dataset, input_col)
        shapes = {"count": (), "total": (n,), "total_sq": (n,)}
        with trace_range("scaler moments"):
            if self.getOrDefault("distribution") == "mesh-local":
                from spark_rapids_ml_tpu.parallel import gram as G

                from spark_rapids_ml_tpu.spark import ingest

                selected = dataset.select(input_col)
                rows = selected.count()
                if ingest.use_streamed_fit(rows, n):
                    # out-of-core: donated per-chunk moments fold at
                    # O(chunk + n) device memory (see _mesh_local_stats)
                    import jax

                    from spark_rapids_ml_tpu.parallel import mesh as M

                    mesh = M.create_mesh()
                    dt = ingest.wire_dtype()
                    example = S.MomentStats(
                        count=jax.ShapeDtypeStruct((), dt),
                        total=jax.ShapeDtypeStruct((n,), dt),
                        total_sq=jax.ShapeDtypeStruct((n,), dt),
                    )
                    mstats = ingest.stream_fold_over_mesh(
                        selected,
                        lambda c, x, w: G.sharded_moment_fold(c, x, w, mesh),
                        example,
                        mesh,
                        features_col=input_col,
                        n=n,
                        rows=rows,
                    ).carry
                    arrays = {
                        # count = Σw: 1.0 true rows / 0.0 pads, so it IS
                        # the true row count — no override needed
                        "count": np.asarray(mstats.count),
                        "total": np.asarray(mstats.total),
                        "total_sq": np.asarray(mstats.total_sq),
                    }
                else:
                    ing = ingest.stream_to_mesh(
                        selected, features_col=input_col, n=n, rows=rows
                    )
                    mstats = G.sharded_moment_stats(ing.xs, ing.mesh)
                    arrays = {
                        "count": np.float64(ing.rows),  # pads are zero rows
                        "total": np.asarray(mstats.total),
                        "total_sq": np.asarray(mstats.total_sq),
                    }
            elif self.getOrDefault("distribution") == "mesh-barrier":
                from spark_rapids_ml_tpu.spark import spmd

                arrays = _barrier_single_row(
                    dataset.select(input_col),
                    spmd.MeshMomentsPartitionFn(input_col),
                    spmd.MOMENTS_MESH_FIELDS,
                    {**shapes, "mesh_size": ()},
                )
                arrays.pop("mesh_size")
            else:
                fn = arrow_fns.make_moments_partition_fn(input_col)
                arrays = _collect_stats(
                    dataset.select(input_col), fn, list(shapes), shapes
                )
            stats = S.MomentStats(**{f: jnp.asarray(v) for f, v in arrays.items()})
            mean, std = S.finalize_moments(stats)
        model = SparkStandardScalerModel(
            uid=self.uid, mean=np.asarray(mean), std=np.asarray(std)
        )
        return self._copyValues(model)


class SparkStandardScalerModel(StandardScalerModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._scale, self.getOutputCol(), scalar=False
        )

class SparkMinMaxScaler(_HasDistribution, MinMaxScaler):
    """MinMaxScaler over pyspark DataFrames: one range-stats pass per fit —
    mapInArrow rows folded on the driver with the min/max monoid
    ('driver-merge'), or streamed onto the driver's device mesh and folded
    with pmin/pmax collectives ('mesh-local')."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkMinMaxScalerModel(
                uid=core.uid,
                originalMin=core.originalMin,
                originalMax=core.originalMax,
            )
            return self._copyValues(model)
        self._check_range()
        stats = _collect_range_stats(self, dataset)
        model = SparkMinMaxScalerModel(
            uid=self.uid,
            originalMin=np.asarray(stats.min),
            originalMax=np.asarray(stats.max),
        )
        return self._copyValues(model)


class SparkMinMaxScalerModel(MinMaxScalerModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._scale, self.getOutputCol(), scalar=False
        )


class SparkMaxAbsScaler(_HasDistribution, MaxAbsScaler):
    """MaxAbsScaler over pyspark DataFrames (same range-stats pass, both
    distributions)."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkMaxAbsScalerModel(uid=core.uid, maxAbs=core.maxAbs)
            return self._copyValues(model)
        stats = _collect_range_stats(self, dataset)
        model = SparkMaxAbsScalerModel(
            uid=self.uid, maxAbs=np.asarray(stats.max_abs)
        )
        return self._copyValues(model)


class SparkMaxAbsScalerModel(MaxAbsScalerModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._scale, self.getOutputCol(), scalar=False
        )


class SparkRobustScaler(_HasDistribution, RobustScaler):
    """RobustScaler over pyspark DataFrames: the range pass then the
    histogram pass. 'driver-merge': two mapInArrow jobs (the histogram
    monoid is additive, so the generic sum-merge decoders fold it).
    'mesh-local': one ingest onto the driver mesh serves BOTH passes —
    pmin/pmax collectives, then psum'd per-shard scatter-add histograms."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkRobustScalerModel(
                uid=core.uid, median=core.median, range=core.range
            )
            return self._copyValues(model)
        self._check_quantile_bounds()
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import scaler as S

        input_col = _resolve_col(self, "inputCol") or "features"
        n = _infer_n(dataset, input_col)
        rstats, ing = _collect_range_stats(self, dataset, return_ingest=True)
        mins = np.asarray(rstats.min)
        maxs = np.asarray(rstats.max)
        bins = self.getNumBins()
        hist = _collect_histogram(
            dataset, ing, input_col, n, mins, maxs, bins
        )
        jm, jmin, jmax = (jnp.asarray(v) for v in (hist, mins, maxs))
        med = np.asarray(S.quantile_from_histogram(jm, jmin, jmax, 0.5))
        lo = np.asarray(
            S.quantile_from_histogram(jm, jmin, jmax, self.getLower())
        )
        hi = np.asarray(
            S.quantile_from_histogram(jm, jmin, jmax, self.getUpper())
        )
        model = SparkRobustScalerModel(
            uid=self.uid, median=med, range=hi - lo
        )
        return self._copyValues(model)


def _collect_histogram(dataset, ing, input_col, n, mins, maxs, bins):
    """The sketch's second pass: psum'd on-mesh when the range pass already
    ingested the shards ('mesh-local'), one mapInArrow job otherwise."""
    with trace_range("quantile sketch histogram"):
        if ing is not None:
            import jax.numpy as jnp

            from spark_rapids_ml_tpu.parallel import gram as G

            return np.asarray(
                G.sharded_histogram(
                    ing.xs, ing.ws, jnp.asarray(mins), jnp.asarray(maxs),
                    bins=bins, mesh=ing.mesh,
                )
            )
        arrays = _collect_stats(
            dataset.select(input_col),
            arrow_fns.HistogramPartitionFn(input_col, mins, maxs, bins),
            ["hist"],
            {"hist": (n, bins)},
        )
        return arrays["hist"]


class SparkRobustScalerModel(RobustScalerModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._scale, self.getOutputCol(), scalar=False
        )


class SparkImputer(_HasDistribution, Imputer):
    """Imputer over pyspark DataFrames: mean is one NaN-aware moments
    mapInArrow pass; median is the NaN-aware range pass + the missing-
    routed histogram pass."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge",)

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkImputerModel(uid=core.uid, surrogate=core.surrogate)
            return self._copyValues(model)
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import scaler as S

        input_col = _resolve_col(self, "inputCol") or "features"
        n = _infer_n(dataset, input_col)
        missing = self.getMissingValue()
        selected = dataset.select(input_col)
        with trace_range("imputer fit"):
            if self.getStrategy() == "mean":
                arrays = _collect_stats(
                    selected,
                    arrow_fns.NanMomentsPartitionFn(input_col, missing),
                    ["count", "total"],
                    {"count": (n,), "total": (n,)},
                )
                count = arrays["count"]
                surrogate = arrays["total"] / np.maximum(count, 1.0)
            else:  # median
                arrays = _collect_stats(
                    selected,
                    arrow_fns.NanRangePartitionFn(input_col, missing),
                    list(S.NanRangeStats._fields),
                    {f: (n,) for f in S.NanRangeStats._fields},
                    combine=arrow_fns.RANGE_COMBINE,
                )
                count = arrays["count"]
                mins = np.where(np.isfinite(arrays["min"]), arrays["min"], 0.0)
                maxs = np.where(np.isfinite(arrays["max"]), arrays["max"], 0.0)
                bins = self.getNumBins()
                harr = _collect_stats(
                    selected,
                    arrow_fns.HistogramPartitionFn(
                        input_col, mins, maxs, bins, missing=missing
                    ),
                    ["hist"],
                    {"hist": (n, bins)},
                )
                surrogate = np.asarray(
                    S.quantile_from_histogram(
                        jnp.asarray(harr["hist"]),
                        jnp.asarray(mins),
                        jnp.asarray(maxs),
                        0.5,
                    )
                )
            surrogate = _scaler_mod._apply_empty_surrogate(
                count, np.asarray(surrogate)
            )
        model = SparkImputerModel(uid=self.uid, surrogate=np.asarray(surrogate))
        return self._copyValues(model)


class SparkImputerModel(ImputerModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._fill, self.getOutputCol(), scalar=False
        )


class SparkVarianceThresholdSelector(_HasDistribution, VarianceThresholdSelector):
    """VarianceThresholdSelector over pyspark DataFrames: one mapInArrow
    moments pass (the same statistic SparkStandardScaler reduces)."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge",)

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkVarianceThresholdSelectorModel(
                uid=core.uid, selectedFeatures=core.selectedFeatures
            )
            return self._copyValues(model)
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import scaler as S

        features_col = _resolve_col(self, "featuresCol") or "features"
        n = _infer_n(dataset, features_col)
        shapes = {"count": (), "total": (n,), "total_sq": (n,)}
        with trace_range("variance selector fit"):
            arrays = _collect_stats(
                dataset.select(features_col),
                arrow_fns.make_moments_partition_fn(features_col),
                list(shapes),
                shapes,
            )
            stats = S.MomentStats(
                **{f: jnp.asarray(v) for f, v in arrays.items()}
            )
            _, std = S.finalize_moments(stats)
        from spark_rapids_ml_tpu.models.selector import select_by_variance

        selected = select_by_variance(
            np.asarray(std) ** 2, self.getVarianceThreshold()
        )
        model = SparkVarianceThresholdSelectorModel(
            uid=self.uid, selectedFeatures=selected
        )
        return self._copyValues(model)


class SparkVarianceThresholdSelectorModel(VarianceThresholdSelectorModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._select, self.getOutputCol(), scalar=False
        )


def _collect_range_stats(est, dataset, *, return_ingest: bool = False):
    """The range-statistic pass behind MinMax/MaxAbs/Robust/Discretizer
    DataFrame fits. ``distribution='driver-merge'``: one mapInArrow pass,
    min/max driver fold. ``'mesh-local'``: rows stream onto the driver's
    device mesh and the fold is pmin/pmax collectives in one SPMD program
    (`parallel.gram.sharded_range_stats`). With ``return_ingest`` the
    mesh-local ingest is handed back so histogram-needing callers reuse
    the already-device-resident shards for their second pass."""
    from spark_rapids_ml_tpu.ops import scaler as S

    input_col = _resolve_col(est, "inputCol") or "features"
    n = _infer_n(dataset, input_col)
    with trace_range("scaler range stats"):
        if est.getOrDefault("distribution") == "mesh-local":
            from spark_rapids_ml_tpu.parallel import gram as G

            from spark_rapids_ml_tpu.spark import ingest as ING

            ing = ING.stream_to_mesh(
                dataset.select(input_col),
                features_col=input_col,
                n=n,
                with_weights=True,
            )
            stats = G.sharded_range_stats(ing.xs, ing.ws, ing.mesh)
            return (stats, ing) if return_ingest else stats
        arrays = _collect_stats(
            dataset.select(input_col),
            arrow_fns.make_range_stats_partition_fn(input_col),
            arrow_fns.RANGE_STATS_FIELDS,
            arrow_fns.range_stats_shapes(n),
            combine=arrow_fns.RANGE_COMBINE,
        )
        stats = S.RangeStats(**arrays)
    return (stats, None) if return_ingest else stats


# ---------------------------------------------------------------------------
# TruncatedSVD / Normalizer
# ---------------------------------------------------------------------------


class SparkTruncatedSVD(_HasDistribution, TruncatedSVD):
    """TruncatedSVD over pyspark DataFrames — the LSA/recommender sibling of
    SparkPCA: one Gram stats pass (solver 'gram'/'randomized'/'auto') or one
    R-factor pass (solver 'svd', cond(X) accuracy) through mapInArrow, then
    the replicated decomposition on the driver; ``distribution=
    'mesh-barrier'`` reduces on the barrier stage's SPMD mesh instead (psum
    Gram, or the butterfly-TSQR R merge for solver='svd');
    ``'mesh-local'`` streams rows to the driver and runs the psum Gram (or
    the pad-masked TSQR for solver='svd') over its own device mesh."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-barrier", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkTruncatedSVDModel(
                uid=core.uid,
                components=core.components,
                singularValues=core.singularValues,
            )
            return self._copyValues(model)
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models import truncated_svd as TSVD

        input_col = _resolve_col(self, "inputCol") or "features"
        selected = dataset.select(input_col)
        n = _infer_n(dataset, input_col)
        k = self.getK()
        if k > n:
            raise ValueError(f"k={k} must be <= number of features {n}")
        solver = self.getOrDefault("solver")
        distribution = self.getOrDefault("distribution")
        if distribution == "mesh-local":
            return self._fit_mesh_local(selected, input_col, n, k, solver)
        if distribution == "mesh-barrier" and solver == "svd":
            from spark_rapids_ml_tpu.spark import spmd

            with trace_range("tsvd mesh fit"):
                arrays = _barrier_single_row(
                    selected,
                    spmd.MeshTSVDFitFn(input_col, k),
                    spmd.TSVD_FIT_FIELDS,
                    {"components": (n, k), "singularValues": (k,),
                     "count": (), "mesh_size": ()},
                )
            model = SparkTruncatedSVDModel(
                uid=self.uid,
                components=arrays["components"],
                singularValues=arrays["singularValues"],
            )
            return self._copyValues(model)
        with trace_range("tsvd reduce"):
            if solver == "svd":
                T, _ = _sql_mods(dataset)
                r_df = selected.mapInArrow(
                    arrow_fns.QRPartitionFn(input_col),
                    schema=_spark_arrays_type(T, ["r"]),
                )
                if hasattr(r_df, "toArrow"):
                    r = arrow_fns.r_from_batches(r_df.toArrow().to_batches(), n)
                else:
                    r = arrow_fns.r_from_rows(r_df.collect(), n)
            elif distribution == "mesh-barrier":
                xtx = _mesh_gram_arrays(
                    selected, input_col, self.getOrDefault("precision"), n
                )["xtx"]
            else:
                fn = arrow_fns.make_fit_partition_fn(
                    input_col, precision=self.getOrDefault("precision")
                )
                xtx = _collect_stats(
                    selected, fn, ["xtx", "col_sum", "count"],
                    {"xtx": (n, n), "col_sum": (n,), "count": ()},
                )["xtx"]
        with trace_range("tsvd decompose"):
            if solver == "svd":
                components, sv = L.svd_components_from_r(jnp.asarray(r), k)
            else:
                components, sv = TSVD._decompose_gram_jit(
                    jnp.asarray(xtx), k, solver
                )
        model = SparkTruncatedSVDModel(
            uid=self.uid,
            components=np.asarray(components),
            singularValues=np.asarray(sv[:k]),
        )
        return self._copyValues(model)


    def _fit_mesh_local(
        self, selected, input_col: str, n: int, k: int, solver: str
    ) -> "SparkTruncatedSVDModel":
        """'mesh-local': streamed driver-side ingestion, then the sharded
        Gram psum (gram-route solvers) or the butterfly TSQR
        (solver='svd') over the driver's own device mesh."""
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models import truncated_svd as TSVD
        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import tsqr as TSQR
        from spark_rapids_ml_tpu.spark import ingest

        ing = ingest.stream_to_mesh(selected, features_col=input_col, n=n)
        xs, mesh = ing.xs, ing.mesh
        with trace_range("tsvd mesh-local fit"):
            if solver == "svd":
                # zero pad rows are exact for the UNcentered QR
                # (R of [X; 0] == R of X), so the plain butterfly TSQR
                # applies; the replicated SVD of R finishes on the driver
                r = TSQR.tsqr_r(xs, mesh)
                components, sv = L.svd_components_from_r(jnp.asarray(r), k)
            else:
                stats = G.sharded_gram_stats(
                    xs, mesh,
                    precision=L.PRECISIONS[self.getOrDefault("precision")],
                )
                components, sv = TSVD._decompose_gram_jit(
                    stats.xtx, k, solver
                )
        model = SparkTruncatedSVDModel(
            uid=self.uid,
            components=np.asarray(components),
            singularValues=np.asarray(sv[:k]),
        )
        return self._copyValues(model)


class SparkTruncatedSVDModel(TruncatedSVDModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._project_matrix, self.getOutputCol(),
            scalar=False,
        )


class SparkBinarizer(Binarizer):
    """Stateless thresholding over pyspark DataFrames (one mapInArrow pass,
    same matrix fn as the local path)."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._binarize, self.getOutputCol(), scalar=False
        )


class SparkDCT(DCT):
    """Row-wise unitary DCT over pyspark DataFrames."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._apply_dct, self.getOutputCol(), scalar=False
        )


class SparkElementwiseProduct(ElementwiseProduct):
    """Componentwise rescaling over pyspark DataFrames."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        if not self.isSet("scalingVec"):
            raise ValueError("scalingVec must be set before transform")
        return _spark_transform(
            self, dataset, self._apply, self.getOutputCol(), scalar=False
        )


class SparkPolynomialExpansion(PolynomialExpansion):
    """Polynomial expansion over pyspark DataFrames (Spark's exact output
    ordering — differential-tested against stock MLlib in the CI matrix)."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._expand, self.getOutputCol(), scalar=False
        )


class SparkVectorSlicer(VectorSlicer):
    """Feature subsetting over pyspark DataFrames."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        if not self.isSet("indices"):
            raise ValueError("indices must be set before transform")
        return _spark_transform(
            self, dataset, self._slice, self.getOutputCol(), scalar=False
        )


class SparkBucketizer(Bucketizer):
    """Elementwise binning over pyspark DataFrames."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        if not self.isSet("splits"):
            raise ValueError("splits must be set before transform")
        return _spark_transform(
            self, dataset, self._bucket, self.getOutputCol(), scalar=False
        )


class SparkQuantileDiscretizer(_HasDistribution, QuantileDiscretizer):
    """QuantileDiscretizer over pyspark DataFrames: the range pass then the
    histogram pass (mapInArrow under 'driver-merge'; one shared mesh ingest
    under 'mesh-local'), quantile grid resolved on the driver."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkQuantileDiscretizerModel(
                uid=core.uid, splits=core.splits
            )
            return self._copyValues(model)
        from spark_rapids_ml_tpu.models.discretizer import (
            check_finite_range,
            splits_from_histogram,
        )

        input_col = _resolve_col(self, "inputCol") or "features"
        n = _infer_n(dataset, input_col)
        rstats, ing = _collect_range_stats(self, dataset, return_ingest=True)
        check_finite_range(rstats.min, rstats.max)
        mins = np.asarray(rstats.min)
        maxs = np.asarray(rstats.max)
        hist = _collect_histogram(
            dataset, ing, input_col, n, mins, maxs, self.getNumBins()
        )
        splits = splits_from_histogram(
            hist, mins, maxs, self.getNumBuckets()
        )
        model = SparkQuantileDiscretizerModel(uid=self.uid, splits=splits)
        return self._copyValues(model)


class SparkQuantileDiscretizerModel(QuantileDiscretizerModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._bucket, self.getOutputCol(), scalar=False
        )


class SparkNormalizer(Normalizer):
    """Stateless row p-normalization over pyspark DataFrames: one
    mapInArrow pass running the same matrix fn as the local path."""

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._normalize_matrix, self.getOutputCol(),
            scalar=False,
        )


# ---------------------------------------------------------------------------
# r5 model families: NearestNeighbors, DBSCAN, RandomForest
# ---------------------------------------------------------------------------


def _collect_xyw(dataset, feats, label_col=None, weight_col=None):
    """Concatenate a Spark DataFrame's (features[, label][, weight]) columns
    on the driver through the memory-bounded ingest chunker — the
    driver-merge collection step the r5 families share. ``est_bytes`` is
    computed (one count job on pyspark) so datasets above the Arrow cutover
    actually take the streaming toLocalIterator path."""
    from spark_rapids_ml_tpu.spark import ingest

    cols = [feats] + ([label_col] if label_col else []) + (
        [weight_col] if weight_col else []
    )
    selected = dataset.select(*cols)
    if hasattr(selected, "_parts"):  # localspark streams natively
        est_bytes = 0
    else:
        n = _infer_n(dataset, feats)
        est_bytes = dataset.count() * (n + len(cols) - 1) * 8
    xs, ys, ws = [], [], []
    for x, y, w in ingest._iter_chunks(
        selected, feats, label_col, weight_col, est_bytes=est_bytes
    ):
        xs.append(x)
        if y is not None:
            ys.append(y)
        if w is not None:
            ws.append(w)
    if not xs:
        raise ValueError("dataset has no rows")
    return (
        np.concatenate(xs),
        np.concatenate(ys) if ys else None,
        np.concatenate(ws) if ws else None,
    )



def _knn_collect_items(est, dataset):
    """(items, int64-coerced ids) from a Spark DataFrame — the fit-side
    collection both k-NN wrappers share (mirrors the core
    _extract_items_and_ids semantics: k bound, positional default ids,
    integral coercion)."""
    feats = _resolve_col(est, "inputCol") or "features"
    id_col = est._paramMap.get("idCol")
    items, ids, _ = _collect_xyw(dataset, feats, label_col=id_col)
    if items.shape[0] < est.getK():
        raise ValueError(
            f"k={est.getK()} exceeds the fitted item count {items.shape[0]}"
        )
    if ids is None:
        ids = np.arange(items.shape[0], dtype=np.int64)
    elif np.all(ids == np.round(ids)):
        ids = ids.astype(np.int64)
    return items, ids


def _knn_spark_kneighbors(model, dataset, kk, trace_label):
    """The query-side mapInArrow plan both k-NN wrappers share: indices
    column type follows the fitted id dtype (the declared schema and the
    worker's cast must agree exactly — real pyspark enforces it)."""
    T, _ = _sql_mods(dataset)
    int_ids = np.issubdtype(model.itemIds.dtype, np.integer)
    id_np = np.int64 if int_ids else np.float64
    id_sql = T.LongType() if int_ids else T.DoubleType()

    def matrix_fn(mat, _m=model, _k=kk):
        d, i = _m._kneighbors_matrix(mat, _k)
        return i, d

    fn = arrow_fns.MultiOutputPartitionFn(
        _resolve_col(model, "inputCol") or "features",
        [("indices", id_np), ("distances", np.float64)],
        matrix_fn,
    )
    with trace_range(trace_label):
        return _spark_append(
            dataset,
            fn,
            [
                ("indices", T.ArrayType(id_sql)),
                ("distances", T.ArrayType(T.DoubleType())),
            ],
        )


class SparkNearestNeighbors(NearestNeighbors):
    """Exact brute-force k-NN over pyspark DataFrames: ``fit`` collects the
    item set into the model (k-NN's training IS ingestion, as in
    spark-rapids-ml's NearestNeighbors), and the model's query side runs as
    an embarrassingly parallel mapInArrow pass — the item matrix ships to
    executors inside the plan function, each batch computes its own
    blocked-tournament top-k on the local accelerator."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            model = SparkNearestNeighborsModel(
                uid=core.uid, items=core.items, itemIds=core.itemIds
            )
            return self._copyValues(model)
        items, ids = _knn_collect_items(self, dataset)
        model = SparkNearestNeighborsModel(
            uid=self.uid, items=items, itemIds=ids
        )
        return self._copyValues(model)


class SparkNearestNeighborsModel(NearestNeighborsModel):
    def kneighbors(self, dataset: Any, k: int | None = None):
        """Spark DataFrame in → DataFrame out with ``indices`` (item-id
        arrays) and ``distances`` appended; array inputs keep the core
        (distances, ids) ndarray contract."""
        if not _is_spark_df(dataset):
            return super().kneighbors(dataset, k)
        return _knn_spark_kneighbors(
            self, dataset, self.getK() if k is None else k,
            "knn spark transform",
        )

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return self.kneighbors(dataset)


class SparkDBSCAN(DBSCAN):
    """DBSCAN over pyspark DataFrames — see SparkDBSCANModel.transform."""

    def fit(self, dataset: Any = None) -> "SparkDBSCANModel":
        return self._copyValues(SparkDBSCANModel(uid=self.uid))


class SparkDBSCANModel(DBSCANModel):
    """Density clustering needs EVERY pairwise relation, so the Spark path
    is collect-and-cluster: the DataFrame is gathered to the driver
    (memory-bounded chunker), labels are computed on the driver's device
    mesh when it has more than one chip (the sharded label-propagation
    program, parallel/dbscan.py) or on one device otherwise, and the result
    comes back as a DataFrame with the prediction column appended — row
    order preserved. O(rows·features) driver memory; the O(n²) compute that
    dominates DBSCAN runs on the accelerator either way (spark-rapids-ml's
    cuML DBSCAN is equally single-worker-global)."""

    def _compute_labels(self, x, weights, eps_sq, min_samples) -> np.ndarray:
        """Kernel hook override: mesh-sharded label propagation when the
        driver owns >1 device (rows padded to an equal-shard multiple),
        the single-device kernel otherwise — identical outputs (tests
        assert so). All eps/dtype/relabel semantics stay in the base
        ``_cluster_matrix``."""
        import jax

        ndev = len(jax.devices())
        if ndev <= 1:
            return super()._compute_labels(x, weights, eps_sq, min_samples)
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.parallel.dbscan import make_sharded_dbscan
        from spark_rapids_ml_tpu.parallel.mesh import create_mesh

        rows = x.shape[0]
        per = -(-rows // ndev)
        xp, w, valid = self._pad_inputs(x, weights, per * ndev)
        run = make_sharded_dbscan(create_mesh(data=ndev))
        return np.asarray(
            run(
                jnp.asarray(xp), jnp.asarray(w), jnp.asarray(valid),
                jnp.asarray(eps_sq), jnp.asarray(min_samples),
            )
        )[:rows]

    def clusterLabels(self, dataset: Any) -> np.ndarray:
        if not _is_spark_df(dataset):
            return super().clusterLabels(dataset)
        _, labels = self._collect_and_cluster(dataset)
        return labels

    def _collect_and_cluster(self, dataset):
        """ONE collection feeding both the clustering and the output table:
        a second collect could legally return rows in a different order
        (nondeterministic plans), silently misaligning labels."""
        feats = _resolve_col(self, "inputCol") or "features"
        weight_col = self._paramMap.get("weightCol")
        if hasattr(dataset, "_parts"):  # localspark: exact Arrow round-trip
            table = dataset.toArrow()
        else:
            table = dataset.toPandas()
        x = columnar.extract_matrix(table, feats)
        w = None
        if weight_col is not None:
            w = columnar.validate_weights(
                columnar.extract_vector(table, weight_col), x.shape[0]
            )
        with trace_range("dbscan spark cluster"):
            return table, self._cluster_matrix(x, w)

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        table, labels = self._collect_and_cluster(dataset)
        session = getattr(dataset, "sparkSession", None) or dataset._session
        if hasattr(dataset, "_parts"):
            import pyarrow as pa

            table = table.append_column(
                self.getPredictionCol(), pa.array(labels, type=pa.int32())
            )
        else:
            table[self.getPredictionCol()] = labels
        return session.createDataFrame(table)


class SparkRandomForestClassifier(_HasDistribution, RandomForestClassifier):
    """RandomForestClassifier over pyspark DataFrames.

    ``driver-merge`` collects (features, label, weight) through the
    memory-bounded chunker and builds on the driver's default device;
    ``mesh-local`` routes the SAME build through the mesh-sharded program
    (rows sharded, one histogram psum per level, parallel/forest.py) on the
    driver's device mesh — bit-identical trees."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            return self._wrap(core)
        x, y, w = _collect_xyw(
            dataset,
            self.getOrDefault("featuresCol"),
            label_col=self.getOrDefault("labelCol"),
            weight_col=self._paramMap.get("weightCol"),
        )
        builder = (
            _mesh_forest_builder()
            if self.getOrDefault("distribution") == "mesh-local"
            else None
        )
        return self._wrap(self._make_model(x, y, w, builder=builder))

    def _wrap(self, core):
        model = SparkRandomForestClassificationModel(
            uid=core.uid, trees=core.trees, thresholds=core.thresholds,
            numFeatures=core.numFeatures,
        )
        return self._copyValues(model)


class SparkRandomForestClassificationModel(RandomForestClassificationModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        model = self
        n_trees = self.trees.feature.shape[0]

        def matrix_fn(mat, _m=model, _t=n_trees):
            proba, pred = _m.proba_and_predictions(mat)
            return proba * _t, proba, pred

        return _classifier_columns_transform(
            self, dataset, matrix_fn, "rf transform"
        )


class SparkRandomForestRegressor(_HasDistribution, RandomForestRegressor):
    """RandomForestRegressor over pyspark DataFrames — distribution modes
    as SparkRandomForestClassifier."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            return self._wrap(core)
        x, y, w = _collect_xyw(
            dataset,
            self.getOrDefault("featuresCol"),
            label_col=self.getOrDefault("labelCol"),
            weight_col=self._paramMap.get("weightCol"),
        )
        builder = (
            _mesh_forest_builder()
            if self.getOrDefault("distribution") == "mesh-local"
            else None
        )
        return self._wrap(self._make_model(x, y, w, builder=builder))

    def _wrap(self, core):
        model = SparkRandomForestRegressionModel(
            uid=core.uid, trees=core.trees, thresholds=core.thresholds,
            numFeatures=core.numFeatures,
        )
        return self._copyValues(model)


class SparkRandomForestRegressionModel(RandomForestRegressionModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOrDefault("predictionCol"), scalar=True,
        )


def _mesh_forest_builder():
    """A drop-in for ops.forest.build_forest that routes the build through
    the mesh-sharded program on THIS process's device mesh: rows padded to
    an equal-shard multiple (pad weight 0 — histogram-invisible), one
    psum per level. Bit-identical trees to the local build."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.parallel.forest import make_sharded_forest
    from spark_rapids_ml_tpu.parallel.mesh import create_mesh

    def build(keys, binned, row_stats, weights, min_inst, min_gain, **static):
        ndev = len(jax.devices())
        if ndev <= 1:
            from spark_rapids_ml_tpu.ops.forest import build_forest

            return build_forest(
                keys, binned, row_stats, weights, min_inst, min_gain, **static
            )
        rows = binned.shape[0]
        per = -(-rows // ndev)
        pad = per * ndev - rows
        if pad:
            binned = jnp.pad(binned, ((0, pad), (0, 0)))
            row_stats = jnp.pad(row_stats, ((0, pad), (0, 0)))
            weights = jnp.pad(weights, ((0, 0), (0, pad)))
        run = make_sharded_forest(create_mesh(data=ndev), **static)
        return run(keys, binned, row_stats, weights, min_inst, min_gain)

    return build


class SparkLinearSVC(_HasDistribution, LinearSVC):
    """LinearSVC over pyspark DataFrames.

    ``driver-merge`` collects (features, label, weight) through the
    memory-bounded chunker and runs the core Newton loop; ``mesh-local``
    streams rows to the driver mesh and runs the ENTIRE squared-hinge
    Newton loop as one XLA program (the logistic whole-loop builder with
    ``loss='squared_hinge'`` — parallel/linear.py)."""

    _ALLOWED_DISTRIBUTIONS = ("driver-merge", "mesh-local")

    def fit(self, dataset: Any, num_partitions: int | None = None, **kwargs):
        checkpoint_dir, checkpoint_every = _parse_checkpoint_kwargs(kwargs, 5)
        if not _is_spark_df(dataset):
            core = super().fit(
                dataset, num_partitions,
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
            )
            return self._wrap(core)
        feats = self.getOrDefault("featuresCol")
        label = self.getOrDefault("labelCol")
        weight_col = self._paramMap.get("weightCol")
        if self.getOrDefault("distribution") == "mesh-local":
            from spark_rapids_ml_tpu.parallel import linear as PL
            from spark_rapids_ml_tpu.spark import ingest

            fit_intercept = self.getFitIntercept()
            cols = [feats, label] + ([weight_col] if weight_col else [])
            n = _infer_n(dataset, feats)
            ing = ingest.stream_to_mesh(
                dataset.select(*cols), features_col=feats, n=n,
                label_col=label, weight_col=weight_col, with_weights=True,
                augment_intercept=fit_intercept,
            )
            if weight_col and float(ing.ws.sum()) == 0.0:
                raise ValueError("all instance weights are zero")
            true_labels = np.unique(
                np.asarray(ing.ys)[np.asarray(ing.ws) > 0]
            )
            if not np.all(np.isin(true_labels, (0.0, 1.0))):
                raise ValueError(
                    f"LinearSVC requires binary 0/1 labels, got "
                    f"{true_labels[:8]}"
                )
            max_iter, tol = self.getMaxIter(), self.getTol()
            d = n + 1 if fit_intercept else n
            from spark_rapids_ml_tpu.models.linear import (
                _resume_newton_checkpoint,
            )

            w0, start_iter, ckpt = _resume_newton_checkpoint(
                checkpoint_dir, d
            )
            chunk_fn = PL.make_distributed_logreg_chunk(
                ing.mesh,
                reg_param=self.getRegParam(),
                fit_intercept=fit_intercept,
                chunk_iters=(
                    checkpoint_every if checkpoint_dir is not None else max_iter
                ),
                tol=tol,
                loss="squared_hinge",
            )
            with trace_range("svc mesh-local fit"):
                # run_chunked_newton applies the NaN-outcome check itself
                w_dev, _ = PL.run_chunked_newton(
                    chunk_fn, ing.xs, ing.ys, ing.ws, w0,
                    start_iter=start_iter, max_iter=max_iter, tol=tol,
                    ckpt=ckpt,
                )
            w_np = np.asarray(w_dev)
            if fit_intercept:
                coef, intercept = w_np[:-1], float(w_np[-1])
            else:
                coef, intercept = w_np, 0.0
            core = LinearSVCModel(
                uid=self.uid, coefficients=coef, intercept=intercept
            )
        else:
            x, y, w = _collect_xyw(
                dataset, feats, label_col=label, weight_col=weight_col
            )
            core = LinearSVC._copyValues(
                self, LinearSVC(uid=self.uid)
            ).fit(
                (x, y) if w is None else (x, y, w),
                checkpoint_dir=checkpoint_dir,
                checkpoint_every=checkpoint_every,
            )
        return self._wrap(core)

    def _wrap(self, core):
        model = SparkLinearSVCModel(
            uid=core.uid,
            coefficients=core.coefficients,
            intercept=core.intercept,
        )
        return self._copyValues(model)


class SparkLinearSVCModel(LinearSVCModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        T, _ = _sql_mods(dataset)
        model = self

        def matrix_fn(mat, _m=model):
            m = _m.margins(mat)
            return (
                np.stack([-m, m], axis=1),
                (m > _m.getThreshold()).astype(np.float64),
            )

        fn = arrow_fns.MultiOutputPartitionFn(
            self.getOrDefault("featuresCol"),
            [
                (self.getOrDefault("rawPredictionCol"), np.float64),
                (self.getOrDefault("predictionCol"), np.float64),
            ],
            matrix_fn,
        )
        with trace_range("svc transform"):
            return _spark_append(
                dataset,
                fn,
                [
                    (
                        self.getOrDefault("rawPredictionCol"),
                        T.ArrayType(T.DoubleType()),
                    ),
                    (self.getOrDefault("predictionCol"), T.DoubleType()),
                ],
            )


class SparkApproximateNearestNeighbors(ApproximateNearestNeighbors):
    """IVF-Flat ANN over pyspark DataFrames: ``fit`` collects the item set
    and builds the index on the driver (clustering + bucket packing need
    the whole corpus); the query side runs as an embarrassingly parallel
    mapInArrow pass with the index shipped inside the plan function —
    the same split as SparkNearestNeighbors."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            return self._wrap(core)
        items, ids = _knn_collect_items(self, dataset)
        return self._wrap(self._fit_items(items, ids))

    def _wrap(self, core):
        model = SparkApproximateNearestNeighborsModel(
            uid=core.uid,
            centroids=core.centroids,
            bucketItems=core.bucketItems,
            bucketIds=core.bucketIds,
            itemIds=core.itemIds,
        )
        return self._copyValues(model)


class SparkApproximateNearestNeighborsModel(ApproximateNearestNeighborsModel):
    def kneighbors(self, dataset: Any, k: int | None = None):
        if not _is_spark_df(dataset):
            return super().kneighbors(dataset, k)
        return _knn_spark_kneighbors(
            self, dataset, self.getK() if k is None else k,
            "ann spark transform",
        )

    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return self.kneighbors(dataset)


class SparkUMAP(UMAP):
    """UMAP over pyspark DataFrames: ``fit`` collects the dataset (the
    fuzzy graph and layout are global — the same collect-and-compute shape
    as SparkDBSCAN, with the O(n²) k-NN graph and the SGD layout on the
    driver's accelerator); the fitted model's out-of-sample ``transform``
    runs as an embarrassingly parallel mapInArrow pass (each batch embeds
    against the shipped reference set)."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            return self._wrap(core)
        feats = _resolve_col(self, "inputCol") or "features"
        x, _, _ = _collect_xyw(dataset, feats)
        # a plain core fit on the collected ndarray (inputCol is ignored
        # for matrix input), rewrapped like the non-Spark branch
        return self._wrap(UMAP.fit(self, x))

    def _wrap(self, core):
        model = SparkUMAPModel(
            uid=core.uid, rawData=core.rawData, embedding=core.embedding_,
            a=core.a, b=core.b,
        )
        return self._copyValues(model)


class SparkUMAPModel(UMAPModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._embed_matrix,
            self.getOrDefault("outputCol"), scalar=False,
        )


class SparkGBTClassifier(GBTClassifier):
    """GBTClassifier over pyspark DataFrames: boosting is sequential, so
    fit collects (features, label, weight) through the memory-bounded
    chunker and boosts on the driver's accelerator; transform runs as an
    embarrassingly parallel mapInArrow pass."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        x, y, w = _collect_xyw(
            dataset,
            self.getOrDefault("featuresCol"),
            label_col=self.getOrDefault("labelCol"),
            weight_col=self._paramMap.get("weightCol"),
        )
        return self._wrap(self._boost(x, y, w))

    def _wrap(self, core):
        model = SparkGBTClassificationModel(
            uid=core.uid, trees=core.trees, thresholds=core.thresholds,
            treeWeights=core.treeWeights, numFeatures=core.numFeatures,
            trainLosses=core.trainLosses,
        )
        return self._copyValues(model)


class SparkGBTClassificationModel(GBTClassificationModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        model = self

        def matrix_fn(mat, _m=model):
            # one margin pass, raw derived directly ([−2F, 2F]) — matching
            # the core transform; a sigmoid round-trip would saturate to
            # ±inf at |F| ≳ 18 where the margin itself stays finite
            from scipy.special import expit

            F = _m._margins(mat)
            p1 = expit(2.0 * F)
            proba = np.stack([1.0 - p1, p1], axis=1)
            return (
                np.stack([-2.0 * F, 2.0 * F], axis=1),
                proba,
                (F > 0).astype(np.float64),
            )

        return _classifier_columns_transform(
            self, dataset, matrix_fn, "gbt transform"
        )


class SparkGBTRegressor(GBTRegressor):
    """GBTRegressor over pyspark DataFrames — collection as
    SparkGBTClassifier."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        x, y, w = _collect_xyw(
            dataset,
            self.getOrDefault("featuresCol"),
            label_col=self.getOrDefault("labelCol"),
            weight_col=self._paramMap.get("weightCol"),
        )
        return self._wrap(self._boost(x, y, w))

    def _wrap(self, core):
        model = SparkGBTRegressionModel(
            uid=core.uid, trees=core.trees, thresholds=core.thresholds,
            treeWeights=core.treeWeights, numFeatures=core.numFeatures,
            trainLosses=core.trainLosses,
        )
        return self._copyValues(model)


class SparkGBTRegressionModel(GBTRegressionModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOrDefault("predictionCol"), scalar=True,
        )


class SparkOneVsRest(OneVsRest):
    """OneVsRest over pyspark DataFrames: fit collects (features, label)
    through the memory-bounded chunker and trains the per-class fleet on
    the driver (each sub-fit is whatever the wrapped classifier's core fit
    is); transform runs as an embarrassingly parallel mapInArrow pass."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if self.classifier is None:  # before any cluster work
            raise ValueError("setClassifier(...) before fit")
        if not _is_spark_df(dataset):
            core = super().fit(dataset, num_partitions)
            return self._wrap(core)
        x, y, _ = _collect_xyw(
            dataset,
            self.getOrDefault("featuresCol"),
            label_col=self.getOrDefault("labelCol"),
        )
        return self._wrap(self._fit_xy(x, y, num_partitions))

    def _wrap(self, core):
        model = SparkOneVsRestModel(uid=core.uid, models=core.models)
        return self._copyValues(model)


class SparkOneVsRestModel(OneVsRestModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOrDefault("predictionCol"), scalar=True,
        )


def _collect_fit_wrap(est, dataset, wrap, core_fit, *, weighted=True):
    """The thin supervised-wrapper fit shared by the r5-close families:
    collect (features, label[, weight]) through the memory-bounded chunker,
    run the bound core fit on the arrays, re-wrap as the Spark model
    class."""
    x, y, w = _collect_xyw(
        dataset,
        est.getOrDefault("featuresCol"),
        label_col=est.getOrDefault("labelCol"),
        weight_col=(est._paramMap.get("weightCol") if weighted else None),
    )
    data = (x, y) if w is None else (x, y, w)
    return wrap(core_fit(data))


def _classifier_columns_transform(model, dataset, matrix_fn, trace_label):
    """raw/probability/prediction in one mapInArrow pass (the classifier
    wrapper transform every family shares); ``matrix_fn(mat)`` returns the
    three arrays in that order."""
    T, _ = _sql_mods(dataset)
    fn = arrow_fns.MultiOutputPartitionFn(
        model.getOrDefault("featuresCol"),
        [
            (model.getOrDefault("rawPredictionCol"), np.float64),
            (model.getOrDefault("probabilityCol"), np.float64),
            (model.getOrDefault("predictionCol"), np.float64),
        ],
        matrix_fn,
    )
    with trace_range(trace_label):
        return _spark_append(
            dataset,
            fn,
            [
                (
                    model.getOrDefault("rawPredictionCol"),
                    T.ArrayType(T.DoubleType()),
                ),
                (
                    model.getOrDefault("probabilityCol"),
                    T.ArrayType(T.DoubleType()),
                ),
                (model.getOrDefault("predictionCol"), T.DoubleType()),
            ],
        )


class SparkNaiveBayes(NaiveBayes):
    """NaiveBayes over pyspark DataFrames (collect + core monoid fit; the
    core estimator's own 'mesh-local' distribution applies unchanged)."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        return _collect_fit_wrap(self, dataset, self._wrap, super().fit)

    def _wrap(self, core):
        model = SparkNaiveBayesModel(
            uid=core.uid, pi=core.pi, theta=core.theta, sigma=core.sigma
        )
        return self._copyValues(model)


class SparkNaiveBayesModel(NaiveBayesModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        model = self

        def matrix_fn(mat, _m=model):
            raw = _m._raw_scores(mat)
            proba, preds = _m._from_raw(raw)
            return raw, proba, preds

        return _classifier_columns_transform(
            self, dataset, matrix_fn, "naive bayes transform"
        )


class SparkMultilayerPerceptronClassifier(MultilayerPerceptronClassifier):
    """MLP over pyspark DataFrames (collect + the one-XLA-program fit)."""

    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        return _collect_fit_wrap(self, dataset, self._wrap, super().fit, weighted=False)

    def _wrap(self, core):
        model = SparkMultilayerPerceptronClassificationModel(
            uid=core.uid, weights=core.weights,
            trainLoss=core.trainLoss, iterations=core.iterations,
        )
        return self._copyValues(model)


class SparkMultilayerPerceptronClassificationModel(
    MultilayerPerceptronClassificationModel
):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        model = self

        def matrix_fn(mat, _m=model):
            logits = _m._logits(mat)
            proba, preds = _m._from_logits(logits)
            return logits, proba, preds

        return _classifier_columns_transform(
            self, dataset, matrix_fn, "mlp transform"
        )


class SparkFMClassifier(FMClassifier):
    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        return _collect_fit_wrap(self, dataset, self._wrap, super().fit, weighted=False)

    def _wrap(self, core):
        model = SparkFMClassificationModel(
            uid=core.uid, flatWeights=core.flatWeights,
            numFeatures=core.numFeatures, trainLoss=core.trainLoss,
            iterations=core.iterations,
        )
        return self._copyValues(model)


class SparkFMClassificationModel(FMClassificationModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        model = self

        def matrix_fn(mat, _m=model):
            s = _m._scores(mat)
            proba, preds = _m._outputs_from_scores(s)
            return np.stack([-s, s], axis=1), proba, preds

        return _classifier_columns_transform(
            self, dataset, matrix_fn, "fm transform"
        )


class SparkFMRegressor(FMRegressor):
    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        return _collect_fit_wrap(self, dataset, self._wrap, super().fit, weighted=False)

    def _wrap(self, core):
        model = SparkFMRegressionModel(
            uid=core.uid, flatWeights=core.flatWeights,
            numFeatures=core.numFeatures, trainLoss=core.trainLoss,
            iterations=core.iterations,
        )
        return self._copyValues(model)


class SparkFMRegressionModel(FMRegressionModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOrDefault("predictionCol"), scalar=True,
        )


class SparkIsotonicRegression(IsotonicRegression):
    def fit(self, dataset: Any, num_partitions: int | None = None):
        if not _is_spark_df(dataset):
            return self._wrap(super().fit(dataset, num_partitions))
        return _collect_fit_wrap(self, dataset, self._wrap, super().fit)

    def _wrap(self, core):
        model = SparkIsotonicRegressionModel(
            uid=core.uid, boundaries=core.boundaries,
            predictions=core.predictions,
        )
        return self._copyValues(model)


class SparkIsotonicRegressionModel(IsotonicRegressionModel):
    def transform(self, dataset: Any) -> Any:
        if not _is_spark_df(dataset):
            return super().transform(dataset)
        return _spark_transform(
            self, dataset, self._predict_matrix,
            self.getOrDefault("predictionCol"), scalar=True,
        )
