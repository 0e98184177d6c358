"""The ``kmeans128_fit_resident`` cell at a toy size on the CPU: a whole run
as the driver makes it comes out correct, and with the timed path broken
underneath it comes out not correct, held to the limits of the cell's own
configuration file. The harness's look for a chip is skipped (``run.run``'s
rehearsal); the toy is this cell's own, not the self-check's PCA one.

The control (the cross term of the distances at one bfloat16 pass) has no
param to set, so here it is the reference computed that way and compared as a
fit would be.
"""

import argparse

import numpy as np
import pytest

from benchmarks import data, data_blobs, manifest as M, reference_kmeans
from benchmarks.selfcheck import kept_environment

CELL = "kmeans128_fit_resident"
SEED = 2_147_483_659
# 8,192 rows of 16 features in 8 blocks of 4 kinds, 16 blobs for 16 clusters;
# the blobs lie further apart than the cell's, for the few dimensions
TOY = {
    "config": {
        "n_features": 16,
        "rows": 8192,
        "params": {"k": 16, "maxIter": 20, "tol": 0.0, "initMode": "k-means||",
                   "initSteps": 2, "distribution": "mesh-local"},
        "data": {"spread": 2.5, "flatten": 0.5},
    },
    "traffic": {"block_rows": 1024},
}


def rehearse(trace: int = 0):
    from benchmarks import run

    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=0.3, trace=trace)
    with kept_environment():
        return run.run(args, rehearsal=TOY)


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
    # no device plane and no table of peaks on the CPU: the two shares find nothing
    assert set(result["metrics"]) == want - {"lloyd_roofline", "kmeans_fit_mfu"}
    fits = result["attempted"]
    assert result["metrics"]["kmeans.iterations"]["value"] == 20 * fits


def loop_cut_one_iteration_short(monkeypatch):
    from spark_rapids_ml_tpu.parallel import kmeans as PK

    make = PK.make_distributed_kmeans_fit
    monkeypatch.setattr(
        PK, "make_distributed_kmeans_fit",
        lambda mesh, *, max_iter, tol: make(mesh, max_iter=max(0, max_iter - 1), tol=tol),
    )
    return {"iterations_off_plan"}


def half_the_rows_dropped(monkeypatch):
    from spark_rapids_ml_tpu.spark import ingest

    stream = ingest.stream_to_mesh

    def half(*args, **kw):
        ing = stream(*args, **kw)
        ing.ws = ing.ws.at[ing.rows // 2 :].set(0.0)
        return ing

    monkeypatch.setattr(ingest, "stream_to_mesh", half)
    return {"center_gap_med", "center_gap", "cost_gap"}


def every_centre_nudged(monkeypatch):
    """Each centre moved by a hundredth of its own norm where the loop hands
    it back (not the seeding's: a fit of no iteration is left alone)."""
    from spark_rapids_ml_tpu.parallel import kmeans as PK

    make = PK.make_distributed_kmeans_fit

    def nudged(mesh, *, max_iter, tol):
        fit = make(mesh, max_iter=max_iter, tol=tol)
        if not max_iter:
            return fit

        def run(x, w, centres0):
            centres, cost, done = fit(x, w, centres0)
            return centres * 1.01, cost, done

        return run

    monkeypatch.setattr(PK, "make_distributed_kmeans_fit", nudged)
    return {"center_gap_med"}


def one_centre_left_where_it_started(monkeypatch):
    from spark_rapids_ml_tpu.parallel import kmeans as PK

    make = PK.make_distributed_kmeans_fit

    def stuck(mesh, *, max_iter, tol):
        fit = make(mesh, max_iter=max_iter, tol=tol)

        def run(x, w, centres0):
            first = centres0[0] + 0.0  # centres0 is donated
            centres, cost, done = fit(x, w, centres0)
            return centres.at[0].set(first), cost, done

        return run

    monkeypatch.setattr(PK, "make_distributed_kmeans_fit", stuck)
    return {"center_gap"}


def seeding_replaced_by_the_first_k_rows(monkeypatch):
    from spark_rapids_ml_tpu.ops import kmeans as KM

    monkeypatch.setattr(
        KM, "weighted_kmeans_plus_plus_init", lambda key, cand, counts, k, **kw: cand[:k]
    )
    from spark_rapids_ml_tpu.parallel import kmeans as PK

    def first_rows(mesh, k, **kw):
        return lambda x, w, key: (x[: 4 * k], np.ones(4 * k, np.float32))

    monkeypatch.setattr(PK, "make_distributed_kmeans_parallel_init", first_rows)
    return {"seed_cost_ratio"}


FAULTS = [
    loop_cut_one_iteration_short,
    half_the_rows_dropped,
    every_centre_nudged,
    one_centre_left_where_it_started,
    seeding_replaced_by_the_first_k_rows,
]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    caught_by = fault(monkeypatch)
    result = rehearse()
    assert result["attempted"] and not result["correct"], result["compared"]
    assert over(result) & caught_by, result["compared"]


def test_control_is_not_correct_and_the_sound_reference_is():
    """One bfloat16 pass for the cross term, as ``Precision.DEFAULT`` takes it
    on the chip, against the float64 reference from the same start: over the
    configuration's limits by the median gap of the centres; the same loop at
    full precision from the same start reads zero."""
    _, config, _ = M.load_cell(CELL)
    toy = TOY["config"]
    k, n = toy["params"]["k"], toy["n_features"]
    blocks = data_blobs.make_blocks(SEED, n, k, 1024, 4, **toy["data"])
    order = data.block_order(8, 4)
    start = np.asarray(blocks[0][:k], dtype=np.float32)
    ref = reference_kmeans.lloyd(blocks, order, start, 20)
    limits = config["limits"]
    ctrl = reference_kmeans.lloyd(blocks, order, start, 20, passes=config["control"]["passes"])
    read = reference_kmeans.compare(ctrl["centres"], ctrl["cost"], ref)
    assert read["center_gap_med"] > limits["center_gap_med"], read
    again = reference_kmeans.compare(ref["centres"], ref["cost"], ref)
    assert all(again[name] <= limits[name] for name in again), again
