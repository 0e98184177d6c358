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


@pytest.mark.parametrize("max_bins,levels", [(32, 2), (300, 0)])
def test_the_piece_selection_counter(session, max_bins, levels):
    """``forest.piece_select_levels`` books trees × the split levels whose
    selection took the pieces: at depth 6 the levels of 16 and 32 nodes (the
    dense product takes those of up to 8); none where bins are not bytes."""
    df, _, _ = table(session, True)
    before = REGISTRY.snapshot()
    model = (
        SparkRandomForestClassifier(distribution="mesh-local")
        .setNumTrees(3).setMaxDepth(6).setMaxBins(max_bins).setSeed(1).fit(df)
    )
    moved = REGISTRY.snapshot().delta(before)
    assert FO.piece_select_levels(F, 3, max_bins, 6) == levels
    assert moved.counter("forest.piece_select_levels", path="mesh-local") == 3 * levels
    assert (model.trees.feature[:, 15:] >= 0).any()  # the trees reach those levels


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


def grid_table(session, seed: int = 5):
    """The regressor's table with its label on a grid of quarters: every
    float sum of the build is exact, whatever order a program sums in."""
    df, x, y = table(session, False, seed=seed)
    import pyarrow as pa

    y = np.round(y * 4) / 4
    offsets = pa.array(np.arange(0, x.size + 1, F, dtype=np.int32))
    feats = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
    return session.createDataFrame(
        pa.Table.from_arrays([feats, pa.array(y)], names=["features", "label"])
    ), x, y


def numpy_regression_tree(binned, y, w, subsets, *, max_depth, n_bins, min_inst, eps):
    """One regression tree of the algorithm in float64 NumPy: level by level,
    each node's histogram of [w, w·y, w·y²] over its own subset, the variance
    gain of every (slot, bin), valid over the floor max(1e-12, 32·eps·Σw·y²),
    the first best (slot, bin) taken."""
    rows = len(y)
    max_nodes = 2 ** (max_depth + 1) - 1
    feature = np.full(max_nodes, -1)
    split_bin = np.zeros(max_nodes, int)
    leaf_stats = np.zeros((max_nodes, 3))
    node = np.zeros(rows, int)
    active = w > 0
    stats = np.stack([np.ones(rows), y, y * y], axis=1)

    def var_n(s):
        safe = np.where(s[..., 0] > 0, s[..., 0], 1.0)
        return np.where(s[..., 0] > 0, np.maximum(s[..., 2] - s[..., 1] ** 2 / safe, 0.0), 0.0)

    for d in range(max_depth + 1):
        offset = 2 ** d - 1
        for n in range(2 ** d):
            at = active & (node == offset + n)
            total = (stats[at] * w[at, None]).sum(0)
            leaf_stats[offset + n] = total
            if d == max_depth or not at.any():
                continue
            best, pick = -np.inf, None
            for j, f in enumerate(subsets[d][n]):
                hist = np.zeros((n_bins, 3))
                np.add.at(hist, binned[at, f], stats[at] * w[at, None])
                left = np.cumsum(hist, axis=0)
                gain = var_n(total) - var_n(left) - var_n(total - left)
                ok = ((left[:, 0] >= min_inst) & (total[0] - left[:, 0] >= min_inst)
                      & (np.arange(n_bins) < n_bins - 1)
                      & (gain > max(1e-12, 32 * eps * total[2])))
                if ok.any() and gain[ok].max() > best:
                    b = int(np.flatnonzero(ok & (gain == gain[ok].max()))[0])
                    best, pick = gain[b], (f, b)
            if pick is not None:
                feature[offset + n], split_bin[offset + n] = pick
        if d < max_depth:
            f = feature[node]
            go = active & (f >= 0)
            right = binned[np.arange(rows), np.maximum(f, 0)] > split_bin[node]
            node = np.where(go, 2 * node + 1 + right, node)
            active = go
    return feature, split_bin, leaf_stats


def test_the_regressor_grows_the_float64_reference_trees(session):
    """``SparkRandomForestRegressor`` mesh-local at the published forest's
    settings, cut to a toy (a third of the features a node, variance, a
    Poisson(1) bootstrap): every tree node for node the float64 NumPy
    reference's, grown from the program's own draws on the same bins."""
    df, x, y = grid_table(session)
    T, depth, B = 3, 6, 16
    est = (SparkRandomForestRegressor(distribution="mesh-local")
           .setNumTrees(T).setMaxDepth(depth).setMaxBins(B).setSeed(11))
    model = est.fit(df)
    k = MF.subset_size("auto", F, classification=False)
    assert k == 3
    edges = MF.quantile_bin_edges(x, B, 0)
    binned = MF.bin_features(x, edges)
    weights = np.asarray(FO.bootstrap_weights(11, T, ROWS, bootstrap=True, rate=1.0))
    keys = jax.random.split(jax.random.PRNGKey(11), T)
    eps = float(np.finfo(model.trees.leaf_stats.dtype).eps)
    for t in range(T):
        subsets = [np.asarray(FO.node_subsets(keys[t], d, F, k, model.trees.leaf_stats.dtype))
                   for d in range(depth)]
        feature, split_bin, leaf_stats = numpy_regression_tree(
            binned, y, weights[t], subsets, max_depth=depth, n_bins=B, min_inst=1.0, eps=eps)
        np.testing.assert_array_equal(model.trees.feature[t], feature)
        np.testing.assert_array_equal(model.trees.split_bin[t][feature >= 0], split_bin[feature >= 0])
        np.testing.assert_allclose(model.trees.leaf_stats[t], leaf_stats, rtol=1e-12, atol=1e-9)
    assert (model.trees.feature >= 0).sum() > T * 10  # trees worth comparing


@pytest.mark.parametrize(
    "cls", [SparkRandomForestClassifier, SparkRandomForestRegressor],
    ids=["classifier", "regressor"],
)
def test_a_smaller_fit_grows_the_first_trees_of_a_larger_one(session, cls):
    """``numTrees`` is a cut and not another forest: the trees of a 2-tree fit
    are the first two of a 5-tree fit with the same seed (the subsets' keys
    and the bootstrap draw tree by tree from the seed), though the larger
    fit keeps more rows a tree and grows its trees side by side."""
    df = grid_table(session)[0] if cls is SparkRandomForestRegressor else table(session, True)[0]
    est = cls(distribution="mesh-local").setMaxDepth(5).setMaxBins(32).setSeed(4)
    small = est.copy().setNumTrees(2).fit(df)
    large = est.copy().setNumTrees(5).fit(df)
    for name in ("feature", "split_bin", "is_leaf", "leaf_stats"):
        np.testing.assert_array_equal(
            getattr(small.trees, name), getattr(large.trees, name)[:2], err_msg=name)
    assert (small.trees.feature >= 0).sum() > 2 * 5


def test_the_level_blocks_counter(session, monkeypatch):
    """``forest.level_blocks`` books trees × the blocks of slots the trees'
    split levels took (``ops.forest.level_plan``): one a level where the
    device reports no memory, more where a block holds less."""
    on_devices(monkeypatch, 1)
    df, _, _ = grid_table(session)
    est = (SparkRandomForestRegressor(distribution="mesh-local")
           .setNumTrees(2).setMaxDepth(4).setMaxBins(16).setSeed(1))
    before = REGISTRY.snapshot()
    whole = est.fit(df)
    assert REGISTRY.snapshot().delta(before).counter("forest.level_blocks", path="mesh-local") == 2 * 4
    small = 2 * FO._slot_bytes(ROWS, 1, 3, 16)
    monkeypatch.setattr(FO, "level_budget", lambda device=None: small)
    before = REGISTRY.snapshot()
    blocked = est.fit(df)
    moved = REGISTRY.snapshot().delta(before).counter("forest.level_blocks", path="mesh-local")
    capacity = FO.row_capacity(FO.bootstrap_weights(1, 2, ROWS, bootstrap=True, rate=1.0))
    assert moved == 2 * FO.level_blocks(capacity, F, 3, 16, 3, 4, small) > 2 * 4
    for a, b in zip(whole.trees, blocked.trees):
        np.testing.assert_array_equal(a, b)
