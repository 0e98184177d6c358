"""Random-forest differential tests.

Three oracle layers (SURVEY.md §4 strategy):
1. an exact-spec NumPy mirror of the histogram tree builder — node-for-node
   equality (stats are integer-valued, so f64 arithmetic is exact and even
   argmax tie-breaks match);
2. sklearn as a QUALITY oracle — our binned forest must land within a few
   points of sklearn's exact-split forest on held-out synthetic data;
3. invariances: seed determinism, weight≡duplication, mesh≡local.
"""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.models.forest import (
    RandomForestClassificationModel,
    RandomForestClassifier,
    RandomForestRegressor,
    bin_features,
    quantile_bin_edges,
    subset_size,
)
from spark_rapids_ml_tpu.ops import forest as FO


# ---------------------------------------------------------------------------
# exact-spec NumPy mirror (all features per node — no subset randomness)
# ---------------------------------------------------------------------------


def _imp_n(stats, impurity):
    if impurity == "variance":
        w = stats[..., 0]
        safe = np.where(w > 0, w, 1.0)
        return np.where(w > 0, np.maximum(stats[..., 2] - stats[..., 1] ** 2 / safe, 0.0), 0.0)
    n = stats.sum(-1)
    safe = np.where(n > 0, n, 1.0)
    if impurity == "gini":
        return np.where(n > 0, n - (stats * stats).sum(-1) / safe, 0.0)
    ratio = np.where(stats > 0, stats / safe[..., None], 1.0)
    return np.where(n > 0, -safe * (ratio * np.log(ratio)).sum(-1), 0.0)


def _count(stats, impurity):
    return stats[..., 0] if impurity == "variance" else stats.sum(-1)


def numpy_tree(binned, row_stats, w, *, max_depth, n_bins, min_inst, min_gain, impurity):
    rows, F = binned.shape
    S = row_stats.shape[1]
    max_nodes = 2 ** (max_depth + 1) - 1
    feature = np.full(max_nodes, -1, np.int32)
    split_bin = np.zeros(max_nodes, np.int32)
    is_leaf = np.ones(max_nodes, bool)
    leaf_stats = np.zeros((max_nodes, S))
    node = np.zeros(rows, np.int32)
    active = np.ones(rows, bool)

    for d in range(max_depth + 1):
        nodes_d = 2 ** d
        offset = nodes_d - 1
        local = np.clip(node - offset, 0, nodes_d - 1)
        wa = np.where(active, w, 0.0)
        hist = np.zeros((F, nodes_d, n_bins, S))
        for f in range(F):
            np.add.at(hist[f], (local, binned[:, f]), row_stats * wa[:, None])
        total = hist[0].sum(1)
        leaf_stats[offset : offset + nodes_d] = total
        if d == max_depth:
            break
        left = np.cumsum(hist, axis=2)
        right = total[None, :, None, :] - left
        gain = _imp_n(total, impurity)[None, :, None] - _imp_n(left, impurity) - _imp_n(right, impurity)
        n_tot = _count(total, impurity)
        ok = (
            (_count(left, impurity) >= min_inst)
            & (_count(right, impurity) >= min_inst)
            & (gain / np.where(n_tot > 0, n_tot, 1.0)[None, :, None] >= min_gain)
            & (gain > 1e-12)
            & (np.arange(n_bins)[None, None, :] < n_bins - 1)
        )
        masked = np.where(ok, gain, -np.inf)
        flat = masked.transpose(1, 0, 2).reshape(nodes_d, F * n_bins)
        best = flat.argmax(1)
        best_gain = flat[np.arange(nodes_d), best]
        bf, bb = best // n_bins, best % n_bins
        do = best_gain > -np.inf
        feature[offset : offset + nodes_d] = np.where(do, bf, -1)
        split_bin[offset : offset + nodes_d] = np.where(do, bb, 0)
        is_leaf[offset : offset + nodes_d] = ~do
        row_split = active & do[local]
        rb = binned[np.arange(rows), np.clip(bf[local], 0, F - 1)]
        node = np.where(row_split, 2 * node + 1 + (rb > bb[local]), node)
        active = active & row_split
    return feature, split_bin, is_leaf, leaf_stats


@pytest.fixture(scope="module")
def clf_data():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(3000, 8))
    logits = 1.5 * x[:, 0] - 2.0 * x[:, 3] + x[:, 5] * x[:, 0]
    y = (logits + rng.normal(scale=0.5, size=3000) > 0).astype(float)
    return x[:2000], y[:2000], x[2000:], y[2000:]


@pytest.fixture(scope="module")
def reg_data():
    rng = np.random.default_rng(1)
    x = rng.normal(size=(3000, 6))
    y = np.sin(x[:, 0]) * 3 + x[:, 2] ** 2 + rng.normal(scale=0.2, size=3000)
    return x[:2000], y[:2000], x[2000:], y[2000:]


@pytest.mark.parametrize("impurity,S", [("gini", 3), ("entropy", 3), ("variance", 3)])
def test_tree_matches_numpy_oracle(impurity, S):
    rng = np.random.default_rng(7)
    rows, F, B = 600, 5, 8
    binned = rng.integers(0, B, size=(rows, F)).astype(np.int32)
    if impurity == "variance":
        yv = rng.normal(size=rows)
        row_stats = np.stack([np.ones(rows), yv, yv * yv], axis=1)
    else:
        y = rng.integers(0, 3, size=rows)
        row_stats = np.eye(3)[y]
    w = rng.poisson(1.0, size=rows).astype(float)

    got = FO.build_tree(
        jax.random.PRNGKey(0),
        jnp.asarray(binned), jnp.asarray(row_stats), jnp.asarray(w),
        jnp.asarray(2.0), jnp.asarray(0.0),
        max_depth=4, n_bins=B, k_features=F, impurity=impurity,
    )
    ref_f, ref_b, ref_l, ref_s = numpy_tree(
        binned, row_stats, w,
        max_depth=4, n_bins=B, min_inst=2.0, min_gain=0.0, impurity=impurity,
    )
    np.testing.assert_array_equal(np.asarray(got.feature), ref_f)
    np.testing.assert_array_equal(np.asarray(got.split_bin), ref_b)
    np.testing.assert_array_equal(np.asarray(got.is_leaf), ref_l)
    np.testing.assert_allclose(np.asarray(got.leaf_stats), ref_s, rtol=1e-12)


def test_classifier_quality_vs_sklearn(clf_data):
    sklearn = pytest.importorskip("sklearn.ensemble")
    xtr, ytr, xte, yte = clf_data
    model = (
        RandomForestClassifier().setNumTrees(30).setMaxDepth(7).setSeed(3)
        .fit((xtr, ytr))
    )
    ours = (model._predict_matrix(xte) == yte).mean()
    sk = sklearn.RandomForestClassifier(
        n_estimators=30, max_depth=7, random_state=3
    ).fit(xtr, ytr)
    theirs = sk.score(xte, yte)
    assert ours >= theirs - 0.04, (ours, theirs)


def test_regressor_quality_vs_sklearn(reg_data):
    sklearn = pytest.importorskip("sklearn.ensemble")
    xtr, ytr, xte, yte = reg_data
    # sklearn's regressor default is max_features=1.0 (ALL features per
    # split) where Spark's 'auto' means F/3 — compare like-for-like, and
    # give the histogram trade (global bins vs exact splits) 128 bins
    model = (
        RandomForestRegressor().setNumTrees(30).setMaxDepth(8).setSeed(3)
        .setFeatureSubsetStrategy("all").setMaxBins(128)
        .fit((xtr, ytr))
    )
    pred = model._predict_matrix(xte)
    ours = 1 - ((pred - yte) ** 2).mean() / yte.var()
    sk = sklearn.RandomForestRegressor(
        n_estimators=30, max_depth=8, random_state=3
    ).fit(xtr, ytr)
    theirs = sk.score(xte, yte)
    assert ours >= theirs - 0.03, (ours, theirs)


def test_probability_columns_and_determinism(clf_data):
    pd = pytest.importorskip("pandas")
    xtr, ytr, xte, _ = clf_data
    df = pd.DataFrame({"features": list(xtr), "label": ytr})
    m1 = RandomForestClassifier().setNumTrees(9).setSeed(5).fit(df)
    m2 = RandomForestClassifier().setNumTrees(9).setSeed(5).fit(df)
    out = m1.transform(pd.DataFrame({"features": list(xte)}))
    assert {"probability", "rawPrediction", "prediction"} <= set(out.columns)
    p = np.stack(out["probability"])
    assert np.allclose(p.sum(1), 1.0)
    raw = np.stack(out["rawPrediction"])
    np.testing.assert_allclose(raw, p * 9, rtol=1e-12)
    np.testing.assert_array_equal(
        m1._predict_matrix(xte), m2._predict_matrix(xte)
    )
    m3 = RandomForestClassifier().setNumTrees(9).setSeed(6).fit(df)
    assert not np.array_equal(
        np.asarray(m1.trees.feature), np.asarray(m3.trees.feature)
    )


def test_weight_equals_duplication():
    """Kernel invariant: doubling a row's weight builds the identical tree
    as physically duplicating the row (same binning by construction)."""
    rng = np.random.default_rng(11)
    rows, F, B = 300, 4, 8
    binned = rng.integers(0, B, size=(rows, F)).astype(np.int32)
    y = rng.integers(0, 2, size=rows)
    row_stats = np.eye(2)[y]
    dup_idx = np.arange(0, rows, 3)
    w = np.ones(rows)
    w[dup_idx] = 2.0

    static = dict(max_depth=4, n_bins=B, k_features=F, impurity="gini")
    key = jax.random.PRNGKey(0)
    t_w = FO.build_tree(
        key, jnp.asarray(binned), jnp.asarray(row_stats), jnp.asarray(w),
        jnp.asarray(1.0), jnp.asarray(0.0), **static,
    )
    b_dup = np.concatenate([binned, binned[dup_idx]])
    s_dup = np.concatenate([row_stats, row_stats[dup_idx]])
    t_d = FO.build_tree(
        key, jnp.asarray(b_dup), jnp.asarray(s_dup),
        jnp.asarray(np.ones(len(b_dup))),
        jnp.asarray(1.0), jnp.asarray(0.0), **static,
    )
    np.testing.assert_array_equal(np.asarray(t_w.feature), np.asarray(t_d.feature))
    np.testing.assert_array_equal(np.asarray(t_w.split_bin), np.asarray(t_d.split_bin))
    np.testing.assert_allclose(
        np.asarray(t_w.leaf_stats), np.asarray(t_d.leaf_stats), rtol=1e-12
    )


def test_min_info_gain_and_depth_zero(clf_data):
    xtr, ytr, _, _ = clf_data
    stump = (
        RandomForestClassifier().setNumTrees(3).setMaxDepth(0)
        .setBootstrap(False)  # exact prior needs every tree on all rows
        .fit((xtr, ytr))
    )
    assert np.all(np.asarray(stump.trees.is_leaf[:, 0]))
    prior = ytr.mean()
    p, _ = stump.proba_and_predictions(xtr[:5])
    np.testing.assert_allclose(p[:, 1], prior, rtol=1e-6)

    huge_gain = (
        RandomForestClassifier().setNumTrees(3).setMinInfoGain(10.0)
        .fit((xtr, ytr))
    )
    assert np.all(np.asarray(huge_gain.trees.is_leaf[:, 0]))


def test_pure_labels_single_leaf():
    x = np.random.default_rng(2).normal(size=(100, 3))
    y = np.ones(100)
    m = RandomForestClassifier().setNumTrees(2).fit((x, y))
    assert np.all(np.asarray(m.trees.is_leaf[:, 0]))


def test_persistence_roundtrip(tmp_path, clf_data, reg_data):
    xtr, ytr, xte, _ = clf_data
    m = RandomForestClassifier().setNumTrees(5).setMaxDepth(4).fit((xtr, ytr))
    path = str(tmp_path / "rfc")
    m.save(path)
    loaded = RandomForestClassificationModel.load(path)
    assert loaded.numClasses == 2
    np.testing.assert_array_equal(
        loaded._predict_matrix(xte), m._predict_matrix(xte)
    )
    p0, _ = m.proba_and_predictions(xte)
    p1, _ = loaded.proba_and_predictions(xte)
    np.testing.assert_allclose(p0, p1)

    xr, yr, xq, _ = reg_data
    mr = RandomForestRegressor().setNumTrees(4).fit((xr, yr))
    rpath = str(tmp_path / "rfr")
    mr.save(rpath)
    from spark_rapids_ml_tpu.models.forest import RandomForestRegressionModel

    lr = RandomForestRegressionModel.load(rpath)
    np.testing.assert_allclose(lr._predict_matrix(xq), mr._predict_matrix(xq))


def test_feature_importances_identify_signal(clf_data):
    """Impurity importances concentrate on the informative features and
    correlate with sklearn's (same weighted-impurity-decrease family)."""
    sklearn = pytest.importorskip("sklearn.ensemble")
    xtr, ytr, _, _ = clf_data
    m = (
        RandomForestClassifier().setNumTrees(20).setMaxDepth(6).setSeed(1)
        .fit((xtr, ytr))
    )
    imp = m.featureImportances
    assert imp.shape == (xtr.shape[1],)
    np.testing.assert_allclose(imp.sum(), 1.0, rtol=1e-9)
    # the generative model uses features 0, 3, 5 — they must dominate
    top3 = set(np.argsort(imp)[-3:])
    assert top3 == {0, 3, 5}, (top3, imp)
    sk = sklearn.RandomForestClassifier(
        n_estimators=20, max_depth=6, random_state=1
    ).fit(xtr, ytr)
    corr = np.corrcoef(imp, sk.feature_importances_)[0, 1]
    assert corr > 0.9, (corr, imp, sk.feature_importances_)


def test_feature_importances_survive_persistence(tmp_path, clf_data):
    xtr, ytr, _, _ = clf_data
    m = RandomForestClassifier().setNumTrees(4).setMaxDepth(3).fit((xtr, ytr))
    path = str(tmp_path / "rf_imp")
    m.save(path)
    loaded = RandomForestClassificationModel.load(path)
    np.testing.assert_allclose(
        loaded.featureImportances, m.featureImportances, rtol=1e-12
    )


def test_subset_size_strategies():
    assert subset_size("auto", 100, classification=True) == 10
    assert subset_size("auto", 99, classification=False) == 33
    assert subset_size("all", 7, classification=True) == 7
    assert subset_size("log2", 64, classification=True) == 6
    assert subset_size("0.5", 10, classification=True) == 5
    assert subset_size("0.15", 10, classification=True) == 2  # Spark ceils
    assert subset_size("4", 10, classification=True) == 4
    # Spark ceils the named strategies too (RandomForestParams):
    # ceil(√10)=4 not 3, ceil(log₂10)=4 not 3, ceil(10/3)=4 not 3
    assert subset_size("sqrt", 10, classification=True) == 4
    assert subset_size("log2", 10, classification=True) == 4
    assert subset_size("onethird", 10, classification=False) == 4
    assert subset_size("auto", 10, classification=True) == 4
    with pytest.raises(ValueError):
        subset_size("bogus", 10, classification=True)


def test_num_features_and_no_bootstrap_subsampling(clf_data):
    xtr, ytr, _, _ = clf_data
    m = RandomForestClassifier().setNumTrees(2).setMaxDepth(2).fit((xtr, ytr))
    assert m.numFeatures == xtr.shape[1]
    # numFeatures survives persistence even for all-stump forests
    stump = RandomForestClassifier().setNumTrees(1).fit((xtr[:50], np.ones(50)))
    assert stump.numFeatures == xtr.shape[1]

    # bootstrap=False + subsamplingRate<1 = Bernoulli without-replacement
    # sampling (Spark BaggedPoint): trees must differ
    m2 = (
        RandomForestClassifier().setNumTrees(2).setBootstrap(False)
        .setSubsamplingRate(0.5).setFeatureSubsetStrategy("all").setSeed(1)
        .fit((xtr, ytr))
    )
    t = np.asarray(m2.trees.feature)
    assert not np.array_equal(t[0], t[1])


def test_sharded_forest_matches_local():
    from spark_rapids_ml_tpu.parallel.mesh import create_mesh
    from spark_rapids_ml_tpu.parallel.forest import make_sharded_forest

    rng = np.random.default_rng(13)
    ndev = len(jax.devices())
    rows = 1000
    per = -(-rows // ndev)
    F, B, T = 6, 16, 4
    x = rng.normal(size=(rows, F))
    y = rng.integers(0, 2, size=rows)
    edges = quantile_bin_edges(x, B, 0)
    binned = np.zeros((per * ndev, F), np.int32)
    binned[:rows] = bin_features(x, edges)
    row_stats = np.zeros((per * ndev, 2))
    row_stats[:rows] = np.eye(2)[y]
    w = np.zeros((T, per * ndev))
    w[:, :rows] = rng.poisson(1.0, size=(T, rows))
    keys = jax.random.split(jax.random.PRNGKey(0), T)

    static = dict(max_depth=4, n_bins=B, k_features=F, impurity="gini")
    local = FO.build_forest(
        keys, jnp.asarray(binned), jnp.asarray(row_stats), jnp.asarray(w),
        jnp.asarray(1.0), jnp.asarray(0.0), **static,
    )
    run = make_sharded_forest(create_mesh(data=ndev), **static)
    sharded = run(
        keys, jnp.asarray(binned), jnp.asarray(row_stats), jnp.asarray(w),
        jnp.asarray(1.0), jnp.asarray(0.0),
    )
    for a, b in zip(local, sharded):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_decision_tree_matches_sklearn_quality(clf_data):
    """A single deterministic CART: close to sklearn's DecisionTree and
    exactly reproducible (no bootstrap, all features)."""
    sk_tree = pytest.importorskip("sklearn.tree")
    from spark_rapids_ml_tpu.classification import DecisionTreeClassifier
    from spark_rapids_ml_tpu.regression import DecisionTreeRegressor

    xtr, ytr, xte, yte = clf_data
    m = DecisionTreeClassifier().setMaxDepth(6).setMaxBins(64).fit((xtr, ytr))
    assert m.trees.feature.shape[0] == 1  # a forest of one
    ours = (m._predict_matrix(xte) == yte).mean()
    sk = sk_tree.DecisionTreeClassifier(max_depth=6, random_state=0).fit(xtr, ytr)
    assert ours >= sk.score(xte, yte) - 0.05, (ours, sk.score(xte, yte))
    assert 1 <= m.depth <= 6
    # deterministic: two fits agree exactly
    m2 = DecisionTreeClassifier().setMaxDepth(6).setMaxBins(64).fit((xtr, ytr))
    np.testing.assert_array_equal(
        np.asarray(m.trees.feature), np.asarray(m2.trees.feature)
    )
    with pytest.raises(AttributeError, match="exactly one tree"):
        DecisionTreeClassifier().setNumTrees(5)

    reg = DecisionTreeRegressor().setMaxDepth(5).fit((xtr, xtr[:, 0] * 2))
    pred = reg._predict_matrix(xte)
    r2 = 1 - ((pred - xte[:, 0] * 2) ** 2).mean() / (xte[:, 0] * 2).var()
    assert r2 > 0.85, r2


def test_decision_tree_persistence(tmp_path, clf_data):
    from spark_rapids_ml_tpu.classification import (
        DecisionTreeClassificationModel,
        DecisionTreeClassifier,
    )

    xtr, ytr, xte, _ = clf_data
    m = DecisionTreeClassifier().setMaxDepth(4).fit((xtr, ytr))
    path = str(tmp_path / "dt")
    m.save(path)
    loaded = DecisionTreeClassificationModel.load(path)
    assert isinstance(loaded, DecisionTreeClassificationModel)
    np.testing.assert_array_equal(
        loaded._predict_matrix(xte), m._predict_matrix(xte)
    )
    assert loaded.depth == m.depth


def test_decision_tree_load_rejects_forest_saves(tmp_path, clf_data):
    """The richer-subclass upgrade rule must not let a 5-tree forest pose
    as a decision tree."""
    from spark_rapids_ml_tpu.classification import (
        DecisionTreeClassificationModel,
    )

    xtr, ytr, _, _ = clf_data
    rf = RandomForestClassifier().setNumTrees(5).setMaxDepth(2).fit((xtr, ytr))
    path = str(tmp_path / "rf5")
    rf.save(path)
    with pytest.raises(TypeError, match="5 trees"):
        DecisionTreeClassificationModel.load(path)


# ---------------------------------------------------------------------------
# the level step over per-node subsets
# ---------------------------------------------------------------------------

# today's kernel before the subset step, kept as a second oracle: a level's
# histogram over EVERY feature, the subset a mask on the gains. The subset
# draw is unchanged, so the trees must match bit for bit.
def _old_impurity_n(stats: jax.Array, impurity: str) -> jax.Array:
    """n·impurity over the trailing stats axis; 0 for empty cells."""
    if impurity == "variance":
        w = stats[..., 0]
        safe = jnp.where(w > 0, w, 1.0)
        v = stats[..., 2] - stats[..., 1] * stats[..., 1] / safe
        return jnp.where(w > 0, jnp.maximum(v, 0.0), 0.0)
    n = jnp.sum(stats, axis=-1)
    safe = jnp.where(n > 0, n, 1.0)
    if impurity == "gini":
        return jnp.where(
            n > 0, n - jnp.sum(stats * stats, axis=-1) / safe, 0.0
        )
    # entropy: Σ c·log(n/c) — 0·log(·) := 0
    c = stats
    ratio = jnp.where(c > 0, c / safe[..., None], 1.0)
    return jnp.where(n > 0, -safe * jnp.sum(ratio * jnp.log(ratio), axis=-1), 0.0)


def _old_node_count(stats: jax.Array, impurity: str) -> jax.Array:
    """Weighted instance count per cell from the stats vector."""
    return stats[..., 0] if impurity == "variance" else jnp.sum(stats, axis=-1)


@partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_bins", "k_features", "impurity", "axis_name",
    ),
)
def old_build_tree(
    key: jax.Array,
    binned: jax.Array,  # [rows, F] int32 bin ids in [0, n_bins)
    row_stats: jax.Array,  # [rows, S] per-row stats (UNweighted)
    w: jax.Array,  # [rows] bootstrap × instance weights (0 = excluded)
    min_instances: jax.Array,  # weighted count floor per child
    min_info_gain: jax.Array,
    *,
    max_depth: int,
    n_bins: int,
    k_features: int,
    impurity: str,
    axis_name: str | None = None,
) -> FO.TreeArrays:
    """Grow one histogram tree level-order; fully jittable, fixed shapes.

    With ``axis_name`` set (mesh build), the per-level histogram and root
    total are psum'd over that axis — rows are sharded, decisions
    replicated. ``vmap`` over (key, w) grows a forest.
    """
    if impurity not in FO.IMPURITIES:
        raise ValueError(f"impurity must be one of {FO.IMPURITIES}")
    rows, n_feat = binned.shape
    S = row_stats.shape[1]
    max_nodes = 2 ** (max_depth + 1) - 1
    fdt = row_stats.dtype

    feature = jnp.full((max_nodes,), -1, jnp.int32)
    split_bin = jnp.zeros((max_nodes,), jnp.int32)
    is_leaf = jnp.ones((max_nodes,), bool)
    leaf_stats = jnp.zeros((max_nodes, S), fdt)
    gain = jnp.zeros((max_nodes,), fdt)

    node = jnp.zeros((rows,), jnp.int32)  # current heap node per row
    active = jnp.ones((rows,), bool)

    for d in range(max_depth + 1):
        nodes_d = 2 ** d
        offset = nodes_d - 1
        # inactive rows keep the stale heap id of the level they went leaf
        # at, so their local id is clipped into range — they contribute 0
        # to histograms (wa=0) and never route (active gates row_split)
        local = jnp.clip(node - offset, 0, nodes_d - 1)
        wa = jnp.where(active, w, 0.0)
        contrib = row_stats * wa[:, None]

        # [F, nodes_d·B, S] histograms in one vmapped segment-sum pass
        def hist_feature(bins_f):
            seg = local * n_bins + bins_f
            return jax.ops.segment_sum(
                contrib, seg, num_segments=nodes_d * n_bins
            )

        hist = jax.vmap(hist_feature)(binned.T)
        if axis_name is not None:
            hist = lax.psum(hist, axis_name)
        hist = hist.reshape(n_feat, nodes_d, n_bins, S)

        total = jnp.sum(hist[0], axis=1)  # [nodes_d, S]
        leaf_stats = lax.dynamic_update_slice(leaf_stats, total, (offset, 0))

        if d == max_depth:
            break  # depth-capped: this level is all leaves

        left = jnp.cumsum(hist, axis=2)  # [F, nodes_d, B, S]
        right = total[None, :, None, :] - left
        gain_n = (
            _old_impurity_n(total, impurity)[None, :, None]
            - _old_impurity_n(left, impurity)
            - _old_impurity_n(right, impurity)
        )
        n_tot = _old_node_count(total, impurity)  # [nodes_d]
        n_l = _old_node_count(left, impurity)
        n_r = _old_node_count(right, impurity)
        safe_tot = jnp.where(n_tot > 0, n_tot, 1.0)
        ok = (
            (n_l >= min_instances)
            & (n_r >= min_instances)
            & (gain_n / safe_tot[None, :, None] >= min_info_gain)
            & (gain_n > 1e-12)
        )
        # the last bin's "split" puts everything left — structurally invalid
        ok = ok & (jnp.arange(n_bins)[None, None, :] < n_bins - 1)

        if k_features < n_feat:
            # Spark's per-node feature subsampling: k distinct features per
            # node via Gumbel top-k (sampling without replacement)
            kd = jax.random.fold_in(key, d)
            g = jax.random.gumbel(kd, (nodes_d, n_feat), fdt)
            kth = lax.top_k(g, k_features)[0][:, -1]
            ok = ok & (g.T[:, :, None] >= kth[None, :, None])

        masked = jnp.where(ok, gain_n, -jnp.inf)
        flat = masked.transpose(1, 0, 2).reshape(nodes_d, n_feat * n_bins)
        best = jnp.argmax(flat, axis=1)
        best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
        best_f = (best // n_bins).astype(jnp.int32)
        best_b = (best % n_bins).astype(jnp.int32)
        do_split = best_gain > -jnp.inf  # [nodes_d]

        feature = lax.dynamic_update_slice(
            feature, jnp.where(do_split, best_f, -1), (offset,)
        )
        split_bin = lax.dynamic_update_slice(
            split_bin, jnp.where(do_split, best_b, 0), (offset,)
        )
        is_leaf = lax.dynamic_update_slice(is_leaf, ~do_split, (offset,))
        gain = lax.dynamic_update_slice(
            gain, jnp.where(do_split, best_gain, 0.0), (offset,)
        )

        # route rows: split nodes send rows to 2·node+1 (+1 if bin > b)
        row_split = active & do_split[local]
        rf = best_f[local]
        rb = best_b[local]
        row_bin = jnp.take_along_axis(binned, rf[:, None], axis=1)[:, 0]
        goes_right = (row_bin > rb).astype(jnp.int32)
        node = jnp.where(row_split, 2 * node + 1 + goes_right, node)
        active = active & row_split

    return FO.TreeArrays(feature, split_bin, is_leaf, leaf_stats, gain)



def numpy_subset_tree(binned, row_stats, w, key, *, max_depth, n_bins, k, min_inst,
                      min_gain, impurity):
    """:func:`numpy_tree` where each node may split on its own k features
    alone (the program's draw, ``ops.forest.node_subsets``), histograms over
    those features only."""
    rows, F = binned.shape
    S = row_stats.shape[1]
    max_nodes = 2 ** (max_depth + 1) - 1
    feature = np.full(max_nodes, -1, np.int32)
    split_bin = np.zeros(max_nodes, np.int32)
    is_leaf = np.ones(max_nodes, bool)
    leaf_stats = np.zeros((max_nodes, S))
    node = np.zeros(rows, np.int32)
    active = np.ones(rows, bool)
    for d in range(max_depth + 1):
        nodes_d = 2 ** d
        offset = nodes_d - 1
        local = np.clip(node - offset, 0, nodes_d - 1)
        wa = np.where(active, w, 0.0)
        if d == max_depth:
            np.add.at(leaf_stats, offset + local, row_stats * wa[:, None])
            break
        sub = np.asarray(FO.node_subsets(key, d, F, k, row_stats.dtype))
        hist = np.zeros((nodes_d, k, n_bins, S))
        for j in range(k):
            np.add.at(hist[:, j], (local, binned[np.arange(rows), sub[local, j]]),
                      row_stats * wa[:, None])
        total = hist[:, 0].sum(1)
        leaf_stats[offset : offset + nodes_d] = total
        left = np.cumsum(hist, axis=2)
        right = total[:, None, None, :] - left
        gain = _imp_n(total, impurity)[:, None, None] - _imp_n(left, impurity) - _imp_n(right, impurity)
        n_tot = _count(total, impurity)
        ok = (
            (_count(left, impurity) >= min_inst)
            & (_count(right, impurity) >= min_inst)
            & (gain / np.where(n_tot > 0, n_tot, 1.0)[:, None, None] >= min_gain)
            & (gain > 1e-12)
            & (np.arange(n_bins)[None, None, :] < n_bins - 1)
        )
        flat = np.where(ok, gain, -np.inf).reshape(nodes_d, k * n_bins)
        best = flat.argmax(1)
        do = flat[np.arange(nodes_d), best] > -np.inf
        bj, bb = best // n_bins, best % n_bins
        bf = sub[np.arange(nodes_d), bj]
        feature[offset : offset + nodes_d] = np.where(do, bf, -1)
        split_bin[offset : offset + nodes_d] = np.where(do, bb, 0)
        is_leaf[offset : offset + nodes_d] = ~do
        row_split = active & do[local]
        rb = binned[np.arange(rows), bf[local]]
        node = np.where(row_split, 2 * node + 1 + (rb > bb[local]), node)
        active = active & row_split
    return feature, split_bin, is_leaf, leaf_stats


def _toy(impurity, rows=600, F=12, B=8, seed=7):
    rng = np.random.default_rng(seed)
    binned = rng.integers(0, B, size=(rows, F)).astype(np.int32)
    if impurity == "variance":
        yv = rng.normal(size=rows)
        row_stats = np.stack([np.ones(rows), yv, yv * yv], axis=1)
    else:
        row_stats = np.eye(3)[rng.integers(0, 3, size=rows)]
    w = rng.poisson(1.0, size=rows).astype(float)
    return binned, row_stats, w


@pytest.mark.parametrize("impurity", ["gini", "entropy", "variance"])
def test_subset_step_matches_numpy_oracle(impurity):
    """k = 4 of 12 features a node, drawn per node: node for node the
    oracle's tree, whose histograms hold the node's own features only."""
    binned, row_stats, w = _toy(impurity)
    key = jax.random.PRNGKey(3)
    got = FO.build_tree(
        key, jnp.asarray(binned), jnp.asarray(row_stats), jnp.asarray(w),
        jnp.asarray(2.0), jnp.asarray(0.0),
        max_depth=5, n_bins=8, k_features=4, impurity=impurity,
    )
    ref_f, ref_b, ref_l, ref_s = numpy_subset_tree(
        binned, row_stats, w, key, max_depth=5, n_bins=8, k=4, min_inst=2.0,
        min_gain=0.0, impurity=impurity,
    )
    np.testing.assert_array_equal(np.asarray(got.feature), ref_f)
    np.testing.assert_array_equal(np.asarray(got.split_bin), ref_b)
    np.testing.assert_array_equal(np.asarray(got.is_leaf), ref_l)
    np.testing.assert_allclose(np.asarray(got.leaf_stats), ref_s, rtol=1e-12, atol=1e-12)
    assert (ref_f >= 0).sum() > 5  # the toy grows a tree worth comparing


@pytest.mark.parametrize("k", [3, 12])
@pytest.mark.parametrize("impurity", ["gini", "entropy"])
def test_subset_step_is_bit_identical_to_the_full_histogram_kernel(impurity, k):
    """The same subsets, drawn as before: today's kernel (every feature's
    histogram, a mask on the gains) and the subset step grow the same tree,
    every array bit for bit (integer counts: the sums are exact)."""
    binned, row_stats, w = _toy(impurity, rows=700, F=12, B=16, seed=k)
    args = (jax.random.PRNGKey(11), jnp.asarray(binned), jnp.asarray(row_stats),
            jnp.asarray(w), jnp.asarray(1.0), jnp.asarray(0.0))
    static = dict(max_depth=6, n_bins=16, k_features=k, impurity=impurity)
    new = FO.build_tree(*args, **static)
    old = old_build_tree(*args, **static)
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_capped_level_holds_node_totals():
    """The depth-capped level computes totals only: each of its nodes holds
    the weighted stats of the rows the tree routes there."""
    binned, row_stats, w = _toy("gini", rows=500, F=6, B=8)
    tree = FO.build_tree(
        jax.random.PRNGKey(0), jnp.asarray(binned), jnp.asarray(row_stats),
        jnp.asarray(w), jnp.asarray(1.0), jnp.asarray(0.0),
        max_depth=3, n_bins=8, k_features=6, impurity="gini",
    )
    leaf = np.asarray(FO.tree_apply_binned(tree, jnp.asarray(binned), max_depth=3))
    deepest = np.asarray(tree.leaf_stats)[7:15]
    node = np.zeros(len(binned), np.int64)
    feat, sb = np.asarray(tree.feature), np.asarray(tree.split_bin)
    for _ in range(3):
        f = feat[node]
        go = f >= 0
        node = np.where(go, 2 * node + 1 + (binned[np.arange(len(node)), np.maximum(f, 0)] > sb[node]), node)
    want = np.zeros((15, 3))
    np.add.at(want, node, row_stats * w[:, None])
    np.testing.assert_array_equal(deepest, want[7:15])
    assert leaf.shape == row_stats.shape


def test_bins_are_bytes_up_to_256_and_int32_beyond():
    from spark_rapids_ml_tpu.parallel import forest as PF
    from spark_rapids_ml_tpu.parallel.mesh import create_mesh

    assert FO.bins_dtype(128) == jnp.uint8 and FO.bins_dtype(256) == jnp.uint8
    assert FO.bins_dtype(257) == jnp.int32
    rng = np.random.default_rng(5)
    x = rng.normal(size=(512, 5))
    for n_bins in (128, 300):
        edges = quantile_bin_edges(x, n_bins, 0)
        got = PF.make_sharded_binner(create_mesh(data=1, devices=jax.devices()[:1]), n_bins)(
            jnp.asarray(x), jnp.asarray(edges))
        assert got.dtype == FO.bins_dtype(n_bins)
        np.testing.assert_array_equal(np.asarray(got), bin_features(x, edges))


def test_byte_bins_and_kept_rows_grow_the_same_tree():
    """uint8 bins against int32, and each tree's rows of positive weight kept
    in fewer rows against all of them: the same forest."""
    binned, row_stats, w = _toy("gini", rows=4096, F=8, B=64)
    T = 3
    weights = np.stack([np.random.default_rng(t).poisson(1.0, 4096) for t in range(T)]).astype(float)
    keys = jax.random.split(jax.random.PRNGKey(1), T)
    static = dict(max_depth=5, n_bins=64, k_features=3, impurity="gini")
    capacity = FO.row_capacity(jnp.asarray(weights))
    assert capacity < 4096 and capacity % 1024 == 0
    full = FO.forest_program(group=T, capacity=None, **static)(
        keys, jnp.asarray(binned), jnp.asarray(row_stats), jnp.asarray(weights),
        jnp.asarray(1.0), jnp.asarray(0.0))
    kept = FO.forest_program(group=1, capacity=capacity, **static)(
        keys, jnp.asarray(binned.astype(np.uint8)), jnp.asarray(row_stats),
        jnp.asarray(weights), jnp.asarray(1.0), jnp.asarray(0.0))
    for a, b in zip(full, kept):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bootstrap_draws_do_not_depend_on_the_tree_count():
    a = np.asarray(FO.bootstrap_weights(9, 2, 1000, bootstrap=True, rate=1.0))
    b = np.asarray(FO.bootstrap_weights(9, 5, 1000, bootstrap=True, rate=1.0))
    np.testing.assert_array_equal(a, b[:2])
    assert 0.95 < a.mean() < 1.05 and a.min() == 0
    half = np.asarray(FO.bootstrap_weights(9, 2, 1000, bootstrap=False, rate=0.5))
    assert set(np.unique(half)) == {0.0, 1.0}
    ones = np.asarray(FO.bootstrap_weights(9, 2, 1000, bootstrap=False, rate=1.0))
    assert (ones == 1.0).all()


@pytest.mark.parametrize("estimator", ["forest", "gbt"])
def test_float32_regression_trees_do_not_depend_on_the_label_unit(reg_data, estimator):
    """In float32, as a TPU runs with x64 off, a regressor on y·2⁻¹⁰ grows
    the trees it grows on y: a power of two scales every sum and gain
    exactly, so only a gain floor in the wrong unit (a count where the
    variance is in y²) could tell them apart. GBT's later stages fit
    residuals that shrink stage by stage."""
    from spark_rapids_ml_tpu.regression import GBTRegressor

    x = reg_data[0][:1000].astype(np.float32)
    y = reg_data[1][:1000].astype(np.float32)

    def fit(labels):
        if estimator == "gbt":
            est = GBTRegressor().setMaxIter(6).setMaxDepth(6).setStepSize(0.5)
        else:
            est = RandomForestRegressor().setNumTrees(3).setMaxDepth(8).setSeed(2)
        return est.setMaxBins(32).fit((x, labels)).trees

    big, small = fit(y), fit(y * np.float32(2.0 ** -10))
    assert np.asarray(big.leaf_stats).dtype == np.float32
    for name in ("feature", "split_bin", "is_leaf"):
        np.testing.assert_array_equal(
            np.asarray(getattr(small, name)), np.asarray(getattr(big, name)))
    assert (np.asarray(big.feature) >= 0).sum() > 20


def test_tree_group_fills_a_sixteenth_of_the_device():
    class Device:
        def __init__(self, limit):
            self.limit = limit

        def memory_stats(self):
            return None if self.limit is None else {"bytes_limit": self.limit}

    # the CPU reports no limit: every tree at once, as the vmapped build did
    assert FO.tree_group(1000, 10, 3, 16, 2, 5, 7, device=Device(None)) == 7
    # the rf-3000-d13 cell on a v5e: a tree's deepest level is most of a
    # sixteenth of 15.75 GiB, so one tree a step
    assert FO.tree_group(317_440, 3000, 55, 128, 2, 13, 5, device=Device(15.75 * 2**30)) == 1
    assert FO.tree_group(1000, 10, 3, 16, 2, 5, 7, device=Device(16e9)) == 7


def _layout(case):
    """(local, nodes, rows) of one level for the piece selection's cases."""
    rng = np.random.default_rng(17)
    if case == "one node":
        return np.zeros(640, np.int64), 1, 640
    if case == "empty nodes":  # nodes 0, 3 and 5..7 hold no row
        return rng.choice([1, 2, 4], size=700), 8, 700
    if case == "a node over many tiles":
        local = np.full(1024, 2)
        local[:5], local[-3:] = 0, 3
        return rng.permutation(local), 4, 1024
    if case == "128 one-row nodes in a tile":
        return np.concatenate([np.arange(128), np.full(384, 130)])[rng.permutation(512)], 256, 512
    if case == "rows off the tile":
        return rng.integers(0, 32, size=1000), 32, 1000
    # inactive rows keep a stale heap id, clipped into the level's range
    node = rng.integers(0, 64, size=900)
    return np.clip(node - 15, 0, 15), 16, 900


@pytest.mark.parametrize("n_bins", [128, 256])
@pytest.mark.parametrize("case", [
    "one node", "empty nodes", "a node over many tiles", "128 one-row nodes in a tile",
    "rows off the tile", "inactive rows",
])
def test_piece_selection_is_each_rows_bins_on_its_nodes_subset(case, n_bins):
    """Each piece's rows hold ``binned[r, subset[local[r], j]]`` exactly, and
    every row is in exactly one piece; at 256 bins the byte 255 included."""
    local, nodes, rows = _layout(case)
    F, k = 40, 7
    rng = np.random.default_rng(3)
    binned = rng.integers(0, n_bins, size=(rows, F)).astype(np.uint8)
    binned[::7, ::3] = n_bins - 1
    subset = FO.node_subsets(jax.random.PRNGKey(5), int(np.log2(nodes)), F, k, jnp.float32)
    pieces = FO._level_pieces(jnp.asarray(local), nodes)
    words = FO._byte_words(jnp.asarray(binned))
    got = np.asarray(jax.jit(FO._piece_bins)(words, subset, pieces))
    order = np.arange(rows) if pieces.order is None else np.asarray(pieces.order)
    subset = np.asarray(subset)
    seen = np.zeros(rows, int)
    R = FO._TILE_ROWS
    for p, (s, e, t, n) in enumerate(zip(*(np.asarray(a) for a in pieces[1:]))):
        for pos in range(s, min(e, rows)):
            r = order[pos]
            assert local[r] == n
            np.testing.assert_array_equal(got[p, pos - t * R], binned[r, subset[n]])
            seen[r] += 1
    assert (seen == 1).all()
    assert got.dtype == np.int32 and (n_bins - 1 in got)


def _by_compares(words, subset, pieces, dtype=jnp.int32):
    """``_piece_bins`` by the compares of ``_subset_bins``: the rows' bytes
    unpacked from their words, each row's node read back from the pieces."""
    rows = words.shape[0]
    binned = jnp.concatenate([(words >> (8 * q)) & 255 for q in range(4)], axis=1)
    at = jnp.arange(rows)
    node = pieces.node[jnp.searchsorted(pieces.start, at, side="right") - 1]
    if pieces.order is not None:
        node = jnp.zeros_like(node).at[pieces.order].set(node)
    return FO._pieces_of(FO._subset_bins(binned, subset[node]), pieces).astype(dtype)


def _oracle_selection(monkeypatch):
    """Every byte level's bins by the compares (``_subset_bins``), laid out
    in pieces, in place of the matrix unit's product."""
    from spark_rapids_ml_tpu.parallel import forest as PF

    monkeypatch.setattr(FO, "_piece_bins", _by_compares)
    PF.make_sharded_forest.cache_clear()
    FO.forest_program.cache_clear()
    jax.clear_caches()


@pytest.mark.parametrize("impurity", ["gini", "entropy", "variance"])
def test_piece_selection_grows_the_compares_trees(monkeypatch, impurity):
    """``build_tree`` and the sharded forest on four devices, with the piece
    selection and with the compares in its place: every array bit for bit."""
    from spark_rapids_ml_tpu.parallel import forest as PF
    from spark_rapids_ml_tpu.parallel.mesh import create_mesh

    binned, row_stats, w = _toy(impurity, rows=2048, F=12, B=16, seed=4)
    binned = binned.astype(np.uint8)
    T = 3
    weights = np.stack([np.random.default_rng(t).poisson(1.0, 2048) for t in range(T)]).astype(float)
    keys = jax.random.split(jax.random.PRNGKey(2), T)
    static = dict(max_depth=7, n_bins=16, k_features=4, impurity=impurity)
    assert FO.piece_select_levels(12, 4, 16, 7) == 3  # the levels of 16, 32 and 64 nodes
    args = (jnp.asarray(binned), jnp.asarray(row_stats))
    gate = (jnp.asarray(1.0), jnp.asarray(0.0))
    mesh = create_mesh(data=4, devices=jax.devices()[:4])

    def grow():
        one = FO.build_tree(keys[0], *args, jnp.asarray(weights[0]), *gate, **static)
        run = PF.make_sharded_forest(mesh, **static)
        return one, run(keys, *args, jnp.asarray(weights), *gate)

    PF.make_sharded_forest.cache_clear()
    jax.clear_caches()
    pieces = grow()
    _oracle_selection(monkeypatch)
    compares = grow()
    for got, want in zip(pieces, compares):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(pieces[1].feature) >= 0).sum() > T * 20  # trees worth comparing
    monkeypatch.undo()
    PF.make_sharded_forest.cache_clear()
    jax.clear_caches()


def test_no_byte_level_falls_back_to_the_compares(monkeypatch):
    """With ``_subset_bins`` refusing, a tree of byte bins still grows, and
    every level past the dense product's nodes takes the pieces; int32 bins
    over 256 keep the compares."""
    calls = []
    piece_bins = FO._piece_bins

    def refuse(*a):
        raise AssertionError("a byte level selected its bins by the compares")

    def spy(*a):
        calls.append(a[1].shape[0])
        return piece_bins(*a)

    monkeypatch.setattr(FO, "_subset_bins", refuse)
    monkeypatch.setattr(FO, "_piece_bins", spy)
    jax.clear_caches()
    binned, row_stats, w = _toy("gini", rows=1500, F=12, B=64, seed=9)
    args = (jax.random.PRNGKey(4), jnp.asarray(binned.astype(np.uint8)),
            jnp.asarray(row_stats), jnp.asarray(w), jnp.asarray(1.0), jnp.asarray(0.0))
    tree = FO.build_tree(*args, max_depth=6, n_bins=64, k_features=4, impurity="gini")
    assert (np.asarray(tree.feature) >= 0).sum() > 10
    assert sorted(calls) == [2 ** d for d in range(6) if 2 ** d > FO._DENSE_SELECT_NODES]
    assert FO.piece_select_levels(12, 4, 64, 6) == len(calls)
    assert FO.piece_select_levels(12, 4, 257, 6) == 0
    assert FO.piece_select_levels(12, 12, 64, 6) == 0  # every feature: nothing to select
    jax.clear_caches()


@pytest.mark.parametrize("impurity", ["gini", "variance"])
def test_a_level_walked_in_blocks_grows_the_same_trees(impurity):
    """Each split level's histogram walked a few subset slots at a time (the
    block forced small through the program's ``block_bytes``), the last block
    pulled back over slots taken before, against the whole subset at once:
    ``build_tree`` and the sharded forest on four devices grow every array
    bit for bit. The labels lie on a grid, so every float sum is exact
    whatever order a block sums it in."""
    from spark_rapids_ml_tpu.parallel import forest as PF
    from spark_rapids_ml_tpu.parallel.mesh import create_mesh

    rows, F, B, k = 2048, 30, 16, 10
    binned, row_stats, w = _toy("gini", rows=rows, F=F, B=B, seed=6)
    binned = binned.astype(np.uint8)
    if impurity == "variance":
        y = np.round(np.random.default_rng(6).normal(size=rows) * 4) / 4
        row_stats = np.stack([np.ones(rows), y, y * y], axis=1)
    T = 3
    weights = np.stack([np.random.default_rng(t).poisson(1.0, rows) for t in range(T)]).astype(float)
    keys = jax.random.split(jax.random.PRNGKey(2), T)
    static = dict(max_depth=6, n_bins=B, k_features=k, impurity=impurity)
    S = row_stats.shape[1]
    small = 3 * FO._slot_bytes(rows // 4, 1, S, B)  # 3 slots a block at the root
    plans = [FO.level_plan(r, k, B, S, 2 ** d, small) for r in (rows, rows // 4)
             for d in range(6)]
    assert all(p.blocks > 1 for p in plans) and any(p.blocks * p.slots > k for p in plans)
    args = (jnp.asarray(binned), jnp.asarray(row_stats))
    gate = (jnp.asarray(1.0), jnp.asarray(0.0))
    mesh = create_mesh(data=4, devices=jax.devices()[:4])

    def grow(block_bytes):
        one = FO.build_tree(keys[0], *args, jnp.asarray(weights[0]), *gate,
                            block_bytes=block_bytes, **static)
        run = PF.make_sharded_forest(mesh, block_bytes=block_bytes, **static)
        return one, run(keys, *args, jnp.asarray(weights), *gate)

    whole, blocked = grow(None), grow(small)
    for got, want in zip(blocked, whole):
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(whole[1].feature) >= 0).sum() > T * 20  # trees worth comparing


def test_the_level_plan_at_the_forest_cells_shapes():
    """On a v5e's 15.75 GiB: the classifier cell's 55 slots stay one block at
    every level (it runs the program it ran before blocks), the regressor
    cell's 1,000 are walked in blocks that hold at most a sixth of the chip,
    and either grows one tree a step."""
    class Device:
        def memory_stats(self):
            return {"bytes_limit": int(15.75 * 2**30)}

    budget = FO.level_budget(Device())
    assert budget == int(15.75 * 2**30) // 6
    rows = 317_440
    assert FO.level_blocks(rows, 3000, 55, 128, 2, 13, budget) == 13
    plans = [FO.level_plan(rows, 1000, 128, 3, 2 ** d, budget) for d in range(13)]
    assert all(p.blocks > 1 and p.slots * p.blocks >= 1000 for p in plans)
    assert all(p.slots * FO._slot_bytes(rows, 2 ** d, 3, 128) <= budget
               for d, p in enumerate(plans))
    assert FO.level_blocks(rows, 3000, 1000, 128, 3, 13, budget) == sum(p.blocks for p in plans)
    assert FO.level_blocks(rows, 3000, 1000, 128, 3, 13, None) == 13
    for k, S in ((55, 2), (1000, 3)):
        assert FO.tree_group(rows, 3000, k, 128, S, 13, 5, device=Device()) == 1
    # the dense selection builds [rows, nodes·k]: at 1,000 slots no level takes it
    assert FO.piece_select_levels(3000, 1000, 128, 13) == 13
    assert FO.piece_select_levels(3000, 55, 128, 13) == 9


def test_a_split_that_changes_nothing_is_not_taken(monkeypatch):
    """A float32 regression node whose label is one constant: every split's
    variance gain is 0, and what float32 reads of it is rounding, ulps of
    the node's Σw·y² and far over 1e-12. Under a floor of 1e-12 the tree
    splits on it; the floor of 32 ulps of Σw·y² refuses it, and the root
    stays a leaf."""
    rows, B = 1000, 8
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, B, size=(rows, 4)).astype(np.uint8))
    y = np.full(rows, 0.1, np.float32)
    row_stats = jnp.asarray(np.stack([np.ones_like(y), y, y * y], axis=1))
    w = jnp.asarray(rng.poisson(1.0, rows).astype(np.float32))

    def grow():  # eager, so that the floor in force is the one read
        return FO._grow(
            jax.random.PRNGKey(0), binned, row_stats, w, jnp.asarray(1.0, jnp.float32),
            jnp.asarray(0.0, jnp.float32), max_depth=3, n_bins=B, k_features=4,
            impurity="variance", axis_name=None,
        )

    floor = FO.gain_floor
    with monkeypatch.context() as m:
        m.setattr(FO, "gain_floor", lambda total, impurity: jnp.full(
            total.shape[1:], 1e-12, total.dtype))
        rounding = grow()
    assert 1e-12 < float(np.asarray(rounding.gain)[0]) < float(
        floor(jnp.asarray(np.asarray(rounding.leaf_stats)[:1].T), "variance")[0])
    tree = grow()
    assert np.asarray(tree.leaf_stats).dtype == np.float32
    assert (np.asarray(tree.feature) == -1).all()
    assert float(np.asarray(tree.leaf_stats)[0, 0]) == float(np.asarray(w).sum())
