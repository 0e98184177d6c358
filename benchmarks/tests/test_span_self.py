"""The reader of a span's self time (``sources/span_self.py``) on registry
deltas made by hand: with self time booked, without it (a program from before
spans knew their parent), and with no occurrence of ``per_span``."""

from types import SimpleNamespace

import pytest

from benchmarks.sources import span_self
from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry

SPEC = {"kind": "span_self", "phase": "compute cov", "per_span": "compute cov", "scale": 1.0}


def window(spans, self_seconds):
    """The delta of a window in which ``spans`` (phase, seconds) closed and
    ``self_seconds`` (phase, seconds) were booked beside them."""
    registry = MetricsRegistry()
    before = registry.snapshot()
    for phase, seconds in spans:
        registry.histogram_record("span.seconds", seconds, phase=phase, estimator="SparkPCA")
    for phase, seconds in self_seconds:
        registry.histogram_record("span.self_seconds", seconds, phase=phase, estimator="SparkPCA")
    return SimpleNamespace(registry=registry.snapshot().delta(before))


@pytest.mark.parametrize(
    "spans, self_seconds, spec, want",
    [
        # two fits: 0.4 s and 0.6 s of compute cov that no child span covered
        ([("compute cov", 30.0), ("compute cov", 31.0), ("ingest.stage", 12.0)],
         [("compute cov", 0.4), ("compute cov", 0.6), ("ingest.stage", 12.0)],
         SPEC, 0.5),
        # per another span, and scaled: fold.dispatch's own ms for each chunk
        ([("fold.dispatch", 5.0)] * 4, [("fold.dispatch", 0.002)] * 4,
         {"phase": "fold.dispatch", "per_span": "fold.dispatch", "scale": 1000.0}, 2.0),
        # the scale is optional
        ([("eigh", 1.0)], [("eigh", 0.25)], {"phase": "eigh", "per_span": "eigh"}, 0.25),
        # the parent: spans, and no self time booked for any of them
        ([("compute cov", 30.0), ("compute cov", 31.0)], [], SPEC, None),
        # self time of other phases only
        ([("compute cov", 30.0)], [("eigh", 0.1)], SPEC, None),
        # an empty window
        ([], [], SPEC, None),
    ],
)
def test_span_self(spans, self_seconds, spec, want):
    got = span_self.read(spec, window(spans, self_seconds))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_manifest_names_it_for_fit_cov_self_s():
    from benchmarks import manifest as M

    spec = M.load_json("layer_metrics/fit.cov_self_s.json")["reader"]
    assert spec["kind"] == "span_self"
    assert spec["phase"] == spec["per_span"] == "compute cov"
    entry = next(m for m in M.load()["per_layer"] if m["name"] == "fit.cov_self_s")
    assert entry["source"] == "program_span" and entry["moves"] == "fit_rows_per_s"
