"""The ``rfreg3000_fit_resident`` cell at a toy size on the CPU: a whole run as
``benchmarks/run.py`` makes it comes out correct, and with the timed path broken
underneath it comes out not correct, held to the limits of the cell's own
configuration file. The harness's look for a chip is skipped (``run.run``'s
rehearsal).

The control (the statistics at one bfloat16 part) is the program's own, the
weighted stats rounded before the level's one-hot products, and the
reference's, the root's sum at one part: both come out not correct.
"""

import argparse

import numpy as np
import pytest

from benchmarks import data_regression, manifest as M, reference_forest_reg
from benchmarks.selfcheck import kept_environment

CELL = "rfreg3000_fit_resident"
SEED = 2_147_483_659
# 4,096 rows of 30 features in 8 blocks of 4 kinds, 10 of them informative;
# 10 features a node (a third), 16 bins, 2 trees of depth 6, each level in
# blocks of slots (the CPU reports no memory, so the budget is forced)
PARAMS = {
    "numTrees": 2, "maxDepth": 6, "maxBins": 16, "featureSubsetStrategy": "auto",
    "impurity": "variance", "minInstancesPerNode": 1.0, "minInfoGain": 0.0,
    "bootstrap": True, "subsamplingRate": 1.0, "seed": 42,
    "distribution": "mesh-local",
}
TOY = {
    "config": {"n_features": 30, "rows": 4096, "env": {}, "params": PARAMS,
               "data": {"n_informative": 10, "bias": 0.0, "noise": 0.0}},
    "traffic": {"block_rows": 512},
}


def rehearse(trace: int = 0, **params):
    from benchmarks import run

    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.3, trace=trace)
    toy = {**TOY, "config": {**TOY["config"], "params": {**PARAMS, **params}}}
    with kept_environment():
        return run.run(args, rehearsal=toy)


def over(result) -> set:
    return {n for n, c in result["compared"].items() if c["value"] > c["limit"]}


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    import jax

    from spark_rapids_ml_tpu.ops import forest as FO
    from spark_rapids_ml_tpu.parallel import forest as PF

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal is for the CPU")
    # a budget that walks every level of the toy in several blocks of slots
    monkeypatch.setattr(FO, "level_budget", lambda device=None: 60_000)
    make = PF.make_sharded_forest
    make.cache_clear()
    yield
    make.cache_clear()
    jax.clear_caches()


def test_sound_run_is_correct():
    from spark_rapids_ml_tpu.ops import forest as FO

    assert FO.level_blocks(2560, 30, 10, 16, 3, 6, 60_000) > 6  # blocked levels
    result = rehearse()
    assert result["correct"] and result["attempted"] and not result["failed"], result
    assert set(result["metrics"]) == {"fit_rows_per_s", "setup_s"}


def test_traced_run_reads_the_spans_and_counters():
    result = rehearse(trace=1)
    assert result["correct"], result["compared"]
    manifest = M.load()
    want = {m["name"] for m in M.metrics_for(manifest, "per_layer", CELL)}
    # no device plane and no table of peaks on the CPU: the roofline finds nothing
    assert set(result["metrics"]) == want - {"rfreg_hist_roofline"}
    fits = result["attempted"]
    assert result["metrics"]["rfreg.split_nodes"]["value"] > 2 * fits
    blocks = result["metrics"]["rfreg.level_blocks"]["value"]
    assert blocks > 2 * 6 * fits and blocks % (2 * fits) == 0


def _patch_forest(monkeypatch, **change):
    from spark_rapids_ml_tpu.parallel import forest as PF

    make = PF.make_sharded_forest

    def changed(mesh, **static):
        for name, how in change.items():
            static[name] = how(static[name], static)
        return make(mesh, **static)

    monkeypatch.setattr(PF, "make_sharded_forest", changed)


def bootstrap_ignored(monkeypatch):
    from spark_rapids_ml_tpu.parallel import forest as PF

    monkeypatch.setattr(
        PF, "make_sharded_weights",
        lambda mesh, *, n_trees, **kw: (lambda ws: np.broadcast_to(ws, (n_trees,) + ws.shape)),
    )
    return {"leaf_count_gap"}


def bins_shifted_by_one(monkeypatch):
    from spark_rapids_ml_tpu.parallel import forest as PF

    make = PF.make_sharded_binner

    def shifted(mesh, n_bins):
        binner = make(mesh, n_bins)
        return lambda x, edges: np.minimum(np.asarray(binner(x, edges)) + 1, n_bins - 1)

    monkeypatch.setattr(PF, "make_sharded_binner", shifted)
    return {"leaf_count_gap"}


def one_level_short(monkeypatch):
    """The last level's nodes stay leaves that had valid splits; their
    totals are the program's own, so only the regret tells."""
    _patch_forest(monkeypatch, max_depth=lambda d, static: d - 1)
    return {"split_regret"}


def stats_at_one_part(monkeypatch):
    """The program's control: [w, w.y, w.y^2] at one bfloat16 part where
    float32 takes three."""
    import jax

    from spark_rapids_ml_tpu.ops import forest as FO

    sums = FO._onehot_sums
    monkeypatch.setattr(FO, "_onehot_sums", lambda bins, stats, n_bins: sums(
        bins, jax.lax.reduce_precision(stats, exponent_bits=8, mantissa_bits=7), n_bins))
    return {"leaf_sum_gap"}


FAULTS = [bootstrap_ignored, bins_shifted_by_one, one_level_short, stats_at_one_part]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    caught_by = fault(monkeypatch)
    result = rehearse()
    assert result["attempted"] and not result["correct"], result["compared"]
    assert over(result) >= caught_by, result["compared"]


def test_control_is_not_correct():
    """The reference a precision below the configuration's: each node's Σw·y
    of a tree's nodes with every term at one bfloat16 part, against the
    float64 sums, over leaf_sum_gap's limit."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import forest as FO

    _, config, _ = M.load_cell(CELL)
    blocks = data_regression.make_blocks(SEED, 30, 512, 4, **TOY["config"]["data"])
    x = np.concatenate([b[0] for b in blocks]).astype(np.float32)
    y = np.concatenate([b[1] for b in blocks]).astype(np.float32)
    edges = reference_forest_reg.quantile_edges(x.astype(np.float64), 16).astype(np.float32)
    bins = reference_forest_reg.bin_rows(x, edges)
    w = np.random.default_rng(0).poisson(20.0, len(y)).astype(np.float32)
    tree = FO.build_tree(
        jax.random.PRNGKey(1), jnp.asarray(bins.astype(np.uint8)),
        jnp.asarray(np.stack([np.ones_like(y), y, y * y], 1)), jnp.asarray(w),
        jnp.asarray(1.0, jnp.float32), jnp.asarray(0.0, jnp.float32),
        max_depth=6, n_bins=16, k_features=10, impurity="variance")
    tree = {name: np.asarray(getattr(tree, name)) for name in ("feature", "split_bin")}
    gap = reference_forest_reg.control_sum_gap(tree, bins, y.astype(np.float64),
                                               w.astype(np.float64), 6)
    assert gap > config["limits"]["leaf_sum_gap"], gap
