"""The ``rf3000_fit_resident`` cell at a toy size on the CPU: a whole run as
``benchmarks/run.py`` makes it comes out correct, and with the timed path broken
underneath it comes out not correct, held to the limits of the cell's own
configuration file. The harness's look for a chip is skipped (``run.run``'s
rehearsal); the toy is this cell's own, not the self-check's PCA one.

The control (the histograms accumulated in bfloat16) is the reference
computed that way, and the program's own control holds each level's
histograms in bfloat16: both come out not correct.
"""

import argparse

import numpy as np
import pytest

from benchmarks import data, data_logreg, manifest as M, reference_forest
from benchmarks.selfcheck import kept_environment

CELL = "rf3000_fit_resident"
SEED = 2_147_483_659
# 4,096 rows of 29 features in 8 blocks of 4 kinds; 6 features a node, 16
# bins, 3 trees of depth 6
PARAMS = {
    "numTrees": 3, "maxDepth": 6, "maxBins": 16, "featureSubsetStrategy": "auto",
    "impurity": "gini", "minInstancesPerNode": 1.0, "minInfoGain": 0.0,
    "bootstrap": True, "subsamplingRate": 1.0, "seed": 42,
    "distribution": "mesh-local",
}
TOY = {
    "config": {"n_features": 29, "rows": 4096, "env": {}, "params": PARAMS},
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
def on_the_cpu():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal is for the CPU")


def test_sound_run_is_correct():
    result = rehearse()
    assert result["correct"] and result["attempted"] and not result["failed"], result
    assert set(result["metrics"]) == {"fit_rows_per_s", "setup_s"}


def test_traced_run_reads_the_spans_and_counters():
    result = rehearse(trace=1)
    assert result["correct"], result["compared"]
    manifest = M.load()
    want = {m["name"] for m in M.metrics_for(manifest, "per_layer", CELL)}
    # no device plane and no table of peaks on the CPU: the roofline finds nothing
    assert set(result["metrics"]) == want - {"forest_hist_roofline"}
    fits = result["attempted"]
    assert result["metrics"]["forest.trees"]["value"] == 3 * fits
    assert result["metrics"]["forest.split_nodes"]["value"] > 3 * fits


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


def every_feature_at_every_node(monkeypatch):
    _patch_forest(monkeypatch, k_features=lambda k, static: 29)
    return {"subset_off"}


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


def half_the_rows(monkeypatch):
    from spark_rapids_ml_tpu.spark import ingest

    stream = ingest.stream_to_mesh

    def changed(*args, **kw):
        ing = stream(*args, **kw)
        ing.ws = ing.ws.at[ing.rows // 2 :].set(0.0)
        return ing

    monkeypatch.setattr(ingest, "stream_to_mesh", changed)
    return {"leaf_count_gap"}


FAULTS = [
    bootstrap_ignored,
    every_feature_at_every_node,
    bins_shifted_by_one,
    one_level_short,
    half_the_rows,
]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    caught_by = fault(monkeypatch)
    result = rehearse()
    assert result["attempted"] and not result["correct"], result["compared"]
    assert over(result) >= caught_by, result["compared"]


def test_program_control_is_not_correct(monkeypatch):
    """Each level's histograms held in bfloat16 where the program sums them
    in float32: the node totals come out rounded. (At 4 bins a bin holds
    some 500 rows of a class here, past bfloat16's exact integers; at the
    cell's 128 bins it holds thousands.)"""
    import jax

    from spark_rapids_ml_tpu.ops import forest as FO
    from spark_rapids_ml_tpu.parallel import forest as PF

    level = FO._level_hist
    # rounded where no compiler folds the rounding away, as it may an astype pair
    monkeypatch.setattr(
        FO, "_level_hist",
        lambda *a: jax.lax.reduce_precision(level(*a), exponent_bits=8, mantissa_bits=7),
    )
    PF.make_sharded_forest.cache_clear()  # the program is traced again
    jax.clear_caches()
    result = rehearse(maxBins=4)
    PF.make_sharded_forest.cache_clear()
    jax.clear_caches()
    assert result["attempted"] and not result["correct"], result["compared"]
    assert "leaf_count_gap" in over(result), result["compared"]


def test_control_is_not_correct():
    """The root's class counts over every weighted row summed in bfloat16,
    against the exact counts: over leaf_count_gap's limit of 0."""
    _, config, _ = M.load_cell(CELL)
    blocks = data_logreg.make_blocks(SEED, 29, 512, 4, **config["data"])
    order = data.block_order(8, 4)
    labels = np.concatenate([blocks[kind][1] for kind in order])
    weights = np.random.default_rng(0).poisson(1.0, len(labels)).astype(np.float64)
    exact = np.stack([np.sum(weights * (labels == c)) for c in (0, 1)])[None]
    gap = reference_forest.control_root_gap(weights, labels, exact, 2)
    assert gap > config["limits"]["leaf_count_gap"], gap
