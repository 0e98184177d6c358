"""What PR 37 adds to the benchmark, checked without a chip: the manifest with
the new entries, the five new reader kinds on registry deltas made by hand (a
reader with nothing to read, or nothing to divide by, returns None), and the
PCA toy's rehearsal reading every new streamed metric as a number."""

from types import SimpleNamespace

import pytest

from benchmarks import manifest as M
from benchmarks import selfcheck
from benchmarks.sources import (
    counter_per_span_s, counter_ratio, hist_mean, span_zero, trace_program_s,
)
from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry

ONE_CHIP = ["pca2048_fit_stream", "pca512_fit_stream"]
NEW = {
    "h2d.link_busy_share": ONE_CHIP, "h2d.link_gbps": ONE_CHIP, "h2d.transfer_ms": ONE_CHIP,
    "h2d.wait_ms": ONE_CHIP, "fold.wait_ms": ONE_CHIP, "stage.reclaim_ms": ONE_CHIP,
    "x4.link_busy_share": ["pca2048_fit_stream_x4"], "x4.links_overlap": ["pca2048_fit_stream_x4"],
    "x4.link_gbps": ["pca2048_fit_stream_x4"], "x4.wait_ms": ["pca2048_fit_stream_x4"],
    "kmeans.transfer_s": ["kmeans128_fit_resident"], "kmeans.link_gbps": ["kmeans128_fit_resident"],
    "kmeans.seed_rounds_s": ["kmeans128_fit_resident"],
    "kmeans.seed_reduce_s": ["kmeans128_fit_resident"],
    "kmeans.seed_device_s": ["kmeans128_fit_resident"],
    "logreg.transfer_s": ["logreg3000_fit_resident"], "logreg.link_gbps": ["logreg3000_fit_resident"],
}


def test_the_manifest_with_the_new_entries_passes():
    manifest = M.load()
    assert M.check(manifest) == []
    assert selfcheck.check_files() == []
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name, cells in NEW.items():
        assert entries[name]["workloads"] == cells and entries[name]["moves"] == "fit_rows_per_s"
        assert M.load_json(f"layer_metrics/{name}.json")["name"] == name
    # the new entries stand at the end of the list, after every accepted one
    assert [m["name"] for m in manifest["per_layer"]][-len(NEW):] == list(NEW)


def window(counters=(), spans=(), hists=()):
    registry = MetricsRegistry()
    before = registry.snapshot()
    for name, value, labels in counters:
        registry.counter_inc(name, value, **labels)
    for phase, seconds in spans:
        registry.histogram_record("span.seconds", seconds, phase=phase, estimator="SparkPCA")
    for name, value, labels in hists:
        registry.histogram_record(name, value, **labels)
    return SimpleNamespace(registry=registry.snapshot().delta(before), trace=None)


STREAM = {"path": "stream"}
SHARE = {"counter": "h2d.link_busy_seconds", "labels": STREAM, "phase": "compute cov", "scale": 100.0}
RATE = {"counter": "h2d.transfer_bytes", "labels": STREAM, "per_counter": "h2d.link_busy_seconds",
        "per_labels": STREAM, "scale": 1e-9}
MEAN = {"hist": "h2d.transfer_seconds", "labels": STREAM, "scale": 1000.0}
WAIT = {"phase": "h2d.wait", "per_span": "fold.dispatch", "scale": 1000.0}


@pytest.mark.parametrize(
    "reader, spec, ctx, want",
    [
        # two fits of 1 s of compute cov, the link busy for 1.5 s of them
        (counter_per_span_s, SHARE,
         window([("h2d.link_busy_seconds", 1.5, STREAM)], [("compute cov", 1.0)] * 2), 75.0),
        # another path's seconds are another series
        (counter_per_span_s, SHARE,
         window([("h2d.link_busy_seconds", 1.5, {"path": "mesh"})], [("compute cov", 1.0)]), None),
        # the parent: the span, and no such counter
        (counter_per_span_s, SHARE, window([], [("compute cov", 1.0)]), None),
        (counter_per_span_s, SHARE, window([("h2d.link_busy_seconds", 1.5, STREAM)]), None),
        # 21 GB ready in 2 s of a busy link
        (counter_ratio, RATE,
         window([("h2d.transfer_bytes", 21e9, STREAM), ("h2d.link_busy_seconds", 2.0, STREAM)]), 10.5),
        # nothing to divide by (the CPU's transfers are ready at once), nothing to divide
        (counter_ratio, RATE, window([("h2d.transfer_bytes", 21e9, STREAM)]), None),
        (counter_ratio, RATE, window([("h2d.link_busy_seconds", 2.0, STREAM)]), None),
        (counter_ratio, RATE, window(), None),
        # the mean over the devices' series of one path
        (hist_mean, MEAN,
         window(hists=[("h2d.transfer_seconds", 0.05, {"path": "stream", "device": "0"}),
                       ("h2d.transfer_seconds", 0.07, {"path": "stream", "device": "1"}),
                       ("h2d.transfer_seconds", 9.0, {"path": "mesh", "device": "0"})]), 60.0),
        (hist_mean, MEAN, window(), None),
        # a wait that two of four chunks entered; one that none did; no chunk at all
        (span_zero, WAIT, window(spans=[("fold.dispatch", 0.04)] * 4 + [("h2d.wait", 0.03)] * 2), 15.0),
        (span_zero, WAIT, window(spans=[("fold.dispatch", 0.04)] * 4), 0.0),
        (span_zero, WAIT, window(spans=[("h2d.wait", 0.03)]), None),
    ],
)
def test_the_new_readers(reader, spec, ctx, want):
    got = reader.read(spec, ctx)
    assert got is None if want is None else got == pytest.approx(want)


def test_a_programs_device_seconds_for_each_fit():
    spec = M.load_json("layer_metrics/kmeans.seed_device_s.json")["reader"]
    assert spec["kind"] == "trace_program_s"
    ctx = window(spans=[("kmeans mesh init", 1.0)] * 4)
    assert trace_program_s.read(spec, ctx) is None  # no trace
    ctx.trace = {"programs": {"jit__kmeans_seed": {"count": 4.0, "seconds": 3.36}}}
    assert trace_program_s.read(spec, ctx) == pytest.approx(0.84)
    ctx.trace = {"programs": {"jit__lloyd": {"count": 4.0, "seconds": 10.0}}}
    assert trace_program_s.read(spec, ctx) is None  # the parent's trace of another program


def test_the_pca_toy_reads_every_new_streamed_metric_as_a_number():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal is for the CPU")
    result = selfcheck.rehearse("pca2048_fit_stream", 1)
    assert result["correct"] and not result["failed"]
    got = result["metrics"]
    for name, cells in NEW.items():
        if cells == ONE_CHIP and name != "h2d.link_gbps":
            assert isinstance(got[name]["value"], float), name
    # a rate off the CPU means nothing, and must still be a number or left out
    assert isinstance(got.get("h2d.link_gbps", {"value": 0.0})["value"], float)
    # (no ceiling here: a toy's transfers are ready at once, and what is booked
    # is when their waiting thread got its turn on a loaded CPU)
    assert got["h2d.link_busy_share"]["value"] > 0
