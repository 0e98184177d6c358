"""What the four-chip cell ``pca2048_fit_stream_x4`` adds to the benchmark,
checked without a chip: the manifest with its entries, the cap on four-chip
cells, one chip's share of a chunk as the roofline's work, and the reader of
a program's device milliseconds on the recorded trace."""

import copy
from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks import opcount, selfcheck
from benchmarks.sources import trace_program_ms, work
from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry

CELL = "pca2048_fit_stream_x4"


def test_the_manifest_with_the_new_entries_passes():
    manifest = M.load()
    assert M.check(manifest) == []
    cell = M.workload(manifest, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "pca-2048-k50-x4", "refit_stream", 4)
    mine = [m for m in manifest["per_layer"] if m["name"].startswith("x4.")]
    assert len(mine) == 12 and all(m["workloads"] == [CELL] for m in mine)
    # nothing the benchmark had lists the new cell
    others = [m for m in manifest["per_layer"] if not m["name"].startswith("x4.")]
    assert all(CELL not in m["workloads"] for m in others)
    assert {m["name"] for m in M.metrics_for(manifest, "end_to_end", CELL)} == {
        "fit_rows_per_s", "setup_s"}


def test_a_second_cell_on_four_chips_is_refused():
    manifest = copy.deepcopy(M.load())
    fifth = dict(M.workload(manifest, CELL), name="pca512_fit_stream_x4", config="pca-512-k50")
    manifest["workloads"].append(fifth)
    assert any("4 chips" in e for e in M.check(manifest)), M.check(manifest)


def test_one_chip_share_of_a_chunk_is_the_roofline_work():
    _, config, _ = M.load_cell(CELL)
    spec = M.load_json("layer_metrics/x4.gram_roofline.json")["reader"]
    # 524,288 rows of 2048: 2*524288*2048^2 FLOP; 4 GiB of chunk, 32 MiB of carry
    assert work(spec, config) == opcount.gram_fold(524288, 2048) == {
        "flops": 2.0 * 524288 * 2048 ** 2, "bytes": 4.0 * 2 ** 30 + 8.0 * 2 ** 22}
    assert 4 * config["per_chip"]["chunk_rows"] == int(config["env"]["TPU_ML_STREAM_CHUNK_ROWS"])
    # the one-chip cell's reader takes the whole chunk: here four times too much
    old = M.load_json("layer_metrics/gram_roofline.json")["reader"]
    assert work(old, config)["flops"] == 4 * work(spec, config)["flops"]


def window(fits: int, trace):
    registry = MetricsRegistry()
    before = registry.snapshot()
    for _ in range(fits):
        registry.histogram_record("span.seconds", 1.0, phase="compute cov", estimator="SparkPCA")
    return SimpleNamespace(registry=registry.snapshot().delta(before), trace=trace)


def test_trace_program_ms_on_the_recorded_trace():
    spec = M.load_json("layer_metrics/x4.allreduce_ms.json")["reader"]
    trace = selfcheck.reduce_recorded_trace()
    psum = trace["programs"]["jit__psum"]
    assert psum["count"] > 0 and psum["seconds"] > 0
    assert trace_program_ms.read(spec, window(2, trace)) == pytest.approx(
        psum["seconds"] * 1000.0 / 2)
    fold = dict(spec, program="jit__fold")
    assert trace_program_ms.read(fold, window(8, trace)) == pytest.approx(
        trace["programs"]["jit__fold"]["seconds"] * 1000.0 / 8)


@pytest.mark.parametrize(
    "fits, trace",
    [
        (0, "recorded"),                        # no fit closed in the window
        (2, None),                              # an untraced run
        (2, {"programs": {}}),                  # a program without the all-reduce
        (2, {"programs": {"jit__psum": {"count": 0, "seconds": 0.0}}}),
    ],
)
def test_trace_program_ms_finds_nothing(fits, trace):
    spec = M.load_json("layer_metrics/x4.allreduce_ms.json")["reader"]
    if trace == "recorded":
        trace = selfcheck.reduce_recorded_trace()
    assert trace_program_ms.read(spec, window(fits, trace)) is None
