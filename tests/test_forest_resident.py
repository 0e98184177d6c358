"""The resident ``SparkRandomForestClassifier.fit(df)`` (and its regressor
twin) under ``distribution='mesh-local'`` that the benchmark's
``rf3000_fit_resident`` cell times: the trees against the ``driver-merge``
host path on one localspark DataFrame, on one device and on a mesh of four;
edges and bins made on the device; the program name, spans and counters its
metrics read; and the host never holding the table as one matrix."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.models import forest as MF
from spark_rapids_ml_tpu.ops import forest as FO
from spark_rapids_ml_tpu.parallel import forest as PF
from spark_rapids_ml_tpu.parallel import mesh as M
from spark_rapids_ml_tpu.spark import (
    SparkRandomForestClassifier,
    SparkRandomForestRegressor,
    estimators,
    ingest,
)
from spark_rapids_ml_tpu.telemetry import REGISTRY

ROWS, F = 1500, 9


@pytest.fixture(scope="module")
def session():
    from spark_rapids_ml_tpu.localspark import LocalSparkSession

    s = LocalSparkSession(parallelism=2, num_workers=1)
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def empty_holder():
    ingest.release_staging()
    yield
    ingest.release_staging()


def on_devices(monkeypatch, ndev: int) -> None:
    """The mesh-local fit builds its mesh from every device there is: give
    it the first ``ndev`` of the eight virtual ones."""
    create = M.create_mesh
    monkeypatch.setattr(
        M, "create_mesh",
        lambda *a, **kw: create(*a, **{"devices": jax.devices()[:ndev], **kw}),
    )


def table(session, classification: bool, seed: int = 3):
    """Features on a grid of quarters (every edge either form computes falls
    between grid values or on one, so host and device bin alike) and a label
    that depends on three of them."""
    rng = np.random.default_rng(seed)
    x = np.round(rng.normal(size=(ROWS, F)) * 4) / 4
    signal = 1.5 * x[:, 0] - x[:, 4] + 0.5 * x[:, 7] + rng.normal(scale=0.5, size=ROWS)
    y = (signal > 0).astype(float) if classification else signal
    import pyarrow as pa

    offsets = pa.array(np.arange(0, x.size + 1, F, dtype=np.int32))
    feats = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
    return session.createDataFrame(
        pa.Table.from_arrays([feats, pa.array(y)], names=["features", "label"])
    ), x, y


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize(
    "cls,classification",
    [(SparkRandomForestClassifier, True), (SparkRandomForestRegressor, False)],
    ids=["classifier", "regressor"],
)
def test_mesh_local_grows_the_driver_merge_trees(session, monkeypatch, cls, classification, ndev):
    """Edges, bins and bootstrap on the device against the host path's: the
    same trees, on one device and with the rows sharded over four."""
    on_devices(monkeypatch, ndev)
    df, x, _ = table(session, classification)
    est = cls().setNumTrees(4).setMaxDepth(5).setMaxBins(32).setSeed(7)
    host = est.copy().setDistribution("driver-merge").fit(df)
    dev = est.copy().setDistribution("mesh-local").fit(df)
    np.testing.assert_array_equal(host.trees.feature, dev.trees.feature)
    np.testing.assert_array_equal(host.trees.split_bin, dev.trees.split_bin)
    np.testing.assert_allclose(host.trees.leaf_stats, dev.trees.leaf_stats, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(host.thresholds, dev.thresholds, rtol=1e-12)
    assert (dev.trees.feature >= 0).sum() > 4 * 5  # trees worth comparing
    np.testing.assert_allclose(host._predict_matrix(x), dev._predict_matrix(x), rtol=1e-12)


def test_the_host_never_gathers_the_table(session, monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("mesh-local gathered the table on the host")

    monkeypatch.setattr(estimators, "_collect_xyw", refuse)
    df, _, _ = table(session, True)
    model = SparkRandomForestClassifier(distribution="mesh-local").setNumTrees(2).fit(df)
    assert model.numFeatures == F


def test_spans_counters_and_the_program_name(session):
    df, _, _ = table(session, True)
    before = REGISTRY.snapshot()
    model = (
        SparkRandomForestClassifier(distribution="mesh-local")
        .setNumTrees(3).setMaxDepth(4).setSeed(1).fit(df)
    )
    moved = REGISTRY.snapshot().delta(before)
    assert moved.counter("forest.trees", path="mesh-local") == 3
    assert moved.counter("forest.split_nodes", path="mesh-local") == int(
        np.sum(model.trees.feature >= 0)
    )
    for phase in ("forest mesh-local fit", "mesh.ingest", "forest.bin", "forest build"):
        assert moved.hist("span.seconds", phase=phase).count == 1, phase

    mesh = M.create_mesh(data=1, devices=jax.devices()[:1])
    run = PF.make_sharded_forest(
        mesh, max_depth=2, n_bins=8, k_features=2, impurity="gini", group=1,
    )
    lowered = run.lower(
        jax.random.split(jax.random.PRNGKey(0), 2), jnp.zeros((64, 4), jnp.uint8),
        jnp.zeros((64, 2)), jnp.ones((2, 64)), jnp.asarray(1.0), jnp.asarray(0.0),
    )
    # benchmarks/layer_metrics/forest_hist_roofline.json reads this name
    assert lowered.as_text().startswith("module @jit__forest")


def test_device_edges_are_the_sample_quantiles():
    """The edges of a sample of rows, positive weight only, against
    np.quantile of the same rows; the sample is Spark's size."""
    assert MF.edge_sample_ids(0, 5000, 32).tolist() == list(range(5000))
    ids = MF.edge_sample_ids(4, 100_000, 128)
    assert len(ids) == 16_384 and np.all(np.diff(ids) > 0)
    assert len(MF.edge_sample_ids(4, 100_000, 16)) == 10_000
    rng = np.random.default_rng(2)
    x = rng.normal(size=(3000, 6))
    w = np.where(rng.random(3000) < 0.1, 0.0, 1.0)
    got = PF.quantile_edges(jnp.asarray(x), jnp.asarray(w), jnp.asarray(MF.quantile_levels(32)))
    np.testing.assert_allclose(np.asarray(got), MF.quantile_bin_edges(x, 32, 0, w), rtol=1e-12)


def test_labels_outside_the_contract_are_refused(session):
    import pyarrow as pa

    x = np.random.default_rng(0).normal(size=(64, 3))
    offsets = pa.array(np.arange(0, x.size + 1, 3, dtype=np.int32))
    feats = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
    df = session.createDataFrame(pa.Table.from_arrays(
        [feats, pa.array(np.full(64, 0.5))], names=["features", "label"]))
    with pytest.raises(ValueError, match="non-negative integers"):
        SparkRandomForestClassifier(distribution="mesh-local").setNumTrees(1).fit(df)


def test_the_forest_program_compiles_its_trees_once(session):
    """Two fits of the same table and params: no new program, and trees
    equal bit for bit (the same draws, the same edges)."""
    df, _, _ = table(session, True, seed=9)
    est = SparkRandomForestClassifier(distribution="mesh-local").setNumTrees(2).setSeed(5)
    first = est.fit(df)
    before = REGISTRY.snapshot()
    second = est.fit(df)
    assert REGISTRY.snapshot().delta(before).counter("compile.cache_misses") == 0
    for a, b in zip(first.trees, second.trees):
        np.testing.assert_array_equal(a, b)
    assert isinstance(first.trees, FO.TreeArrays)
