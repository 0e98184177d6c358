"""The ``logreg3000_fit_resident`` cell at a toy size on the CPU: a whole run
as the driver makes it comes out correct, and with the timed path broken
underneath it comes out not correct, held to the limits of the cell's own
configuration file. The harness's look for a chip is skipped (``run.run``'s
rehearsal); the toy is this cell's own, not the self-check's PCA one.

The control (the statistics' three products at one bfloat16 pass) has no
param to set, so here it is the reference computed that way and compared as a
fit would be.
"""

import argparse

import pytest

from benchmarks import data, data_logreg, manifest as M, reference_logreg
from benchmarks.selfcheck import kept_environment

CELL = "logreg3000_fit_resident"
SEED = 2_147_483_659
# 4,096 rows of 29 features in 8 blocks of 4 kinds: 2,048 distinct rows for 30 unknowns
TOY = {
    "config": {"n_features": 29, "rows": 4096, "env": {}},
    "traffic": {"block_rows": 512},
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
    assert set(result["metrics"]) == want - {"newton_roofline", "logreg_fit_mfu"}
    fits = result["attempted"]
    assert result["metrics"]["logreg.iterations"]["value"] == 8 * fits
    assert result["metrics"]["logreg.pad_rows"]["value"] == 0  # 4,096 rows fill their shard


def loop_cut_one_step_short(monkeypatch):
    """The seventh step is under float32's rounding of the weights already,
    so only the counter can tell."""
    from spark_rapids_ml_tpu.parallel import linear as PL

    make = PL.make_distributed_logreg_fit
    monkeypatch.setattr(
        PL, "make_distributed_logreg_fit",
        lambda mesh, *, max_iter, **kw: make(mesh, max_iter=max(0, max_iter - 1), **kw),
    )
    return {"iterations_off_plan"}


def _ingest_then(monkeypatch, change):
    from spark_rapids_ml_tpu.spark import ingest

    stream = ingest.stream_to_mesh

    def changed(*args, **kw):
        ing = stream(*args, **kw)
        change(ing)
        return ing

    monkeypatch.setattr(ingest, "stream_to_mesh", changed)


def half_the_rows_dropped(monkeypatch):
    def change(ing):
        ing.ws = ing.ws.at[ing.rows // 2 :].set(0.0)

    _ingest_then(monkeypatch, change)
    return {"coef_gap", "grad_norm"}


def labels_of_one_block_flipped(monkeypatch):
    def change(ing):
        block = TOY["traffic"]["block_rows"]
        ing.ys = ing.ys.at[:block].set(1.0 - ing.ys[:block])

    _ingest_then(monkeypatch, change)
    return {"coef_gap", "grad_norm"}


def intercept_column_left_out(monkeypatch):
    """The column of ones staged as zeros: the intercept stays where it
    started."""
    def change(ing):
        ing.xs = ing.xs.at[:, -1].set(0.0)

    _ingest_then(monkeypatch, change)
    return {"coef_gap", "grad_norm"}


def regulariser_dropped(monkeypatch):
    """regParam 1e-5 against Hessian diagonals of m/8: the optimum without it
    lies 2e-4 of the weights' norm away here, 3.4e-4 at the cell's size (the
    float64 reference, PERF.md section 2), where sound fits read 4e-7."""
    from spark_rapids_ml_tpu.parallel import linear as PL

    make = PL.make_distributed_logreg_fit
    monkeypatch.setattr(
        PL, "make_distributed_logreg_fit",
        lambda mesh, *, reg_param, **kw: make(mesh, reg_param=0.0, **kw),
    )
    return {"coef_gap", "grad_norm"}


FAULTS = [
    regulariser_dropped,
    loop_cut_one_step_short,
    half_the_rows_dropped,
    labels_of_one_block_flipped,
    intercept_column_left_out,
]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault, monkeypatch):
    caught_by = fault(monkeypatch)
    result = rehearse()
    assert result["attempted"] and not result["correct"], result["compared"]
    assert over(result) >= caught_by, result["compared"]


def test_control_is_not_correct_and_the_sound_reference_is():
    """One bfloat16 pass for the statistics' products, as
    ``Precision.DEFAULT`` takes them on the chip, against the float64
    reference: over the configuration's limits by the weights' gap and by the
    gradient; the reference against itself reads zero."""
    _, config, _ = M.load_cell(CELL)
    n, reg = TOY["config"]["n_features"], config["params"]["regParam"]
    blocks = data_logreg.make_blocks(SEED, n, 512, 4, **config["data"])
    order = data.block_order(8, 4)
    limits = config["limits"]
    ref = reference_logreg.irls(blocks, order, 8, reg)
    ctrl = reference_logreg.irls(blocks, order, 8, reg, passes=config["control"]["passes"])
    read = reference_logreg.compare(blocks, order, ctrl["w"][:-1], ctrl["w"][-1], ref, reg)
    assert read["coef_gap"] > limits["coef_gap"], read
    assert read["grad_norm"] > limits["grad_norm"], read
    again = reference_logreg.compare(blocks, order, ref["w"][:-1], ref["w"][-1], ref, reg)
    assert all(again[name] <= limits[name] for name in again), again
