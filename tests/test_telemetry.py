"""Telemetry subsystem: registry math, span accounting, FitReport, JSONL.

Covers the ISSUE-2 satellite list: histogram percentile math against known
distributions, exception-path span accounting (the trace_range try/finally
fix), registry thread-safety under concurrent recording (the localspark
partition-executor load shape), FitReport presence on PCA / StandardScaler /
LinearRegression after both in-core and streamed fits, and the JSONL sink
round-trip.
"""

from __future__ import annotations

import json
import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu import telemetry as T
from spark_rapids_ml_tpu.models.linear import LinearRegression
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.models.scaler import StandardScaler
from spark_rapids_ml_tpu.utils.config import get_config, set_config
from spark_rapids_ml_tpu.telemetry import metrics, reset_metrics, trace_range


@pytest.fixture(autouse=True)
def clean_registry():
    T.reset_metrics()
    yield
    T.reset_metrics()


@pytest.fixture
def force_streamed(monkeypatch):
    old = get_config().stream_fit_max_resident_bytes
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "128")
    set_config(stream_fit_max_resident_bytes=1)
    yield
    set_config(stream_fit_max_resident_bytes=old)


@pytest.fixture
def data():
    rng = np.random.default_rng(23)
    x = np.asarray(rng.normal(size=(600, 8)), np.float64)
    y = x @ rng.normal(size=8) + 0.1 * rng.normal(size=600)
    return x, y


class TestHistogram:
    def test_exact_count_sum_min_max(self):
        h = T.Histogram()
        vals = [0.5, 1.5, 2.5, 10.0, 0.001]
        for v in vals:
            h.record(v)
        assert h.count == len(vals)
        assert h.total == pytest.approx(sum(vals))
        assert h.vmin == min(vals)
        assert h.vmax == max(vals)

    def test_percentiles_within_bucket_tolerance(self):
        # uniform 1..1000: log-bucket quantiles are within half a bucket
        # (GROWTH=2^0.25 ⇒ ~9.5%) of the exact order statistic
        h = T.Histogram()
        vals = np.linspace(1.0, 1000.0, 1000)
        for v in vals:
            h.record(float(v))
        for q in (50, 90, 99):
            exact = float(np.percentile(vals, q))
            got = h.percentile(q)
            assert got == pytest.approx(exact, rel=0.15), (q, got, exact)

    def test_percentile_extremes_are_clamped_exact(self):
        h = T.Histogram()
        for v in (3.0, 7.0, 42.0):
            h.record(v)
        assert h.percentile(0) >= h.vmin
        assert h.percentile(100) <= h.vmax

    def test_zero_and_negative_values_bucket_safely(self):
        h = T.Histogram()
        h.record(0.0)
        h.record(-1.0)
        h.record(5.0)
        assert h.count == 3
        assert h.percentile(1) == 0.0  # the zero bucket ranks first

    def test_empty_percentile_is_zero(self):
        assert T.Histogram().percentile(50) == 0.0

    def test_delta_subtracts_earlier_window(self):
        h = T.Histogram()
        for v in range(1, 11):
            h.record(float(v))
        snap = h.copy()
        for v in range(1, 11):
            h.record(float(v) * 100)
        d = h.delta(snap)
        assert d.count == 10
        assert d.total == pytest.approx(sum(range(1, 11)) * 100)

    def test_to_dict_shape(self):
        h = T.Histogram()
        h.record(1.0)
        d = h.to_dict()
        assert set(d) == {"count", "sum", "min", "max", "p50", "p90", "p99"}
        assert T.Histogram().to_dict() == {"count": 0, "sum": 0.0}


class TestSpans:
    def test_trace_range_books_elapsed_on_raise(self):
        # satellite (a): a body that raises must still account its time
        with pytest.raises(RuntimeError):
            with trace_range("boom.phase"):
                raise RuntimeError("body died")
        m = metrics()
        assert m["boom.phase"]["count"] == 1
        assert m["boom.phase"]["seconds"] >= 0.0

    def test_legacy_metrics_shape(self):
        with trace_range("p1"):
            pass
        with trace_range("p1"):
            pass
        m = metrics()
        assert m["p1"]["count"] == 2
        assert "seconds" in m["p1"]

    def test_estimator_label_groups_spans(self):
        token = T.set_current_estimator("DemoEst")
        try:
            with trace_range("labelled"):
                pass
        finally:
            T.reset_current_estimator(token)
        snap = T.REGISTRY.snapshot()
        h = snap.hist("span.seconds", phase="labelled", estimator="DemoEst")
        assert h.count == 1


def _nest(tree: dict) -> None:
    """Open the spans of ``tree`` ({name: subtree | Exception}) in order; an
    exception as a subtree is raised inside that span and caught outside."""
    for name, sub in tree.items():
        try:
            with trace_range(name):
                if isinstance(sub, Exception):
                    raise sub
                _nest(sub)
        except RuntimeError:
            pass


def _children(tree: dict):
    """(name, names of its direct children) for every span of ``tree``."""
    for name, sub in tree.items():
        sub = {} if isinstance(sub, Exception) else sub
        yield name, list(sub)
        yield from _children(sub)


class TestSpanParentage:
    """A span adds its seconds to its parent's frame, so span.self_seconds
    is the span's duration less what its child spans (same context) took."""

    TREES = {
        "leaf": {"t.a": {}},
        "child": {"t.a": {"t.b": {}}},
        "grandchild": {"t.a": {"t.b": {"t.c": {}}}},
        "siblings": {"t.a": {"t.b": {}, "t.c": {}, "t.d": {"t.e": {}}}},
        "child_raises": {"t.a": {"t.b": RuntimeError("died"), "t.c": {}}},
    }

    @pytest.mark.parametrize("shape", list(TREES))
    def test_self_seconds_is_duration_less_children(self, shape):
        tree = self.TREES[shape]
        _nest(tree)
        snap = T.REGISTRY.snapshot()
        for name, kids in _children(tree):
            dur = snap.hist("span.seconds", phase=name)
            own = snap.hist("span.self_seconds", phase=name)
            # a body that raises still books both
            assert dur.count == own.count == 1, name
            covered = sum(
                snap.hist("span.seconds", phase=k).total for k in kids
            )
            assert own.total == pytest.approx(dur.total - covered, abs=1e-9)
            assert 0.0 <= own.total <= dur.total
            # only DIRECT children come off: a grandchild is already inside
            # its own parent's seconds
            assert covered <= dur.total

    def test_repeated_child_comes_off_once_each(self):
        with trace_range("t.loop"):
            for _ in range(5):
                with trace_range("t.step"):
                    pass
        snap = T.REGISTRY.snapshot()
        steps = snap.hist("span.seconds", phase="t.step")
        assert steps.count == 5
        assert snap.hist("span.self_seconds", phase="t.loop").total == (
            pytest.approx(
                snap.hist("span.seconds", phase="t.loop").total - steps.total,
                abs=1e-9,
            )
        )

    def test_span_on_another_thread_is_not_subtracted(self):
        import time

        def work():
            with trace_range("t.elsewhere"):
                time.sleep(0.02)

        with trace_range("t.main"):
            th = threading.Thread(target=work)
            th.start()
            th.join()
        snap = T.REGISTRY.snapshot()
        assert snap.hist("span.seconds", phase="t.elsewhere").total >= 0.02
        main = snap.hist("span.seconds", phase="t.main").total
        # the other thread's span has no parent: the whole of t.main is its own
        assert snap.hist("span.self_seconds", phase="t.main").total == (
            pytest.approx(main, abs=1e-9)
        )

    def test_open_frame_is_restored_after_a_raise(self):
        with trace_range("t.outer"):
            with pytest.raises(RuntimeError):
                with trace_range("t.bad"):
                    raise RuntimeError("died")
            with trace_range("t.after"):
                pass
        events = {
            e["name"]: e for e in T.TIMELINE.events() if e["name"].startswith("t.")
        }
        assert events["t.after"]["args"]["parent"] == "t.outer"

    def test_timeline_event_carries_parent(self):
        seq = T.TIMELINE.seq()
        _nest({"t.a": {"t.b": {"t.c": {}}}})
        parents = {
            e["name"]: e["args"].get("parent")
            for e in T.TIMELINE.events(seq)
            if e["cat"] == "span"
        }
        # a root span carries no parent key (empty labels are dropped)
        assert parents == {"t.a": None, "t.b": "t.a", "t.c": "t.b"}

    def test_phase_table_carries_self(self):
        _nest({"t.a": {"t.b": {}}})
        table = T.REGISTRY.snapshot().phase_table()
        assert table["t.b"]["self"] == pytest.approx(table["t.b"]["sum"])
        assert table["t.a"]["self"] == pytest.approx(
            table["t.a"]["sum"] - table["t.b"]["sum"], abs=1e-9
        )


class TestCompileMonitoring:
    """telemetry.compilemon's mapping of jax.monitoring events, fed by hand
    (the listeners are plain functions)."""

    def test_cache_retrieval_is_booked_as_load_seconds(self):
        from spark_rapids_ml_tpu.telemetry import compilemon

        compilemon._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.25
        )
        compilemon._on_duration(
            "/jax/compilation_cache/cache_retrieval_time_sec", 0.5
        )
        h = T.REGISTRY.snapshot().hist("compile.cache_load_seconds")
        assert (h.count, h.total) == (2, 0.75)
        # no longer swept into the catch-all
        assert T.REGISTRY.snapshot().hist("compile.other_seconds").count == 0

    @pytest.mark.parametrize(
        "saved, want", [([1.5, 2.0], 3.5), ([-0.4], 0.0), ([2.0, -5.0, 1.0], 3.0)]
    )
    def test_time_saved_never_goes_down(self, saved, want):
        from spark_rapids_ml_tpu.telemetry import compilemon

        seen = []
        for s in saved:
            compilemon._on_duration(
                "/jax/compilation_cache/compile_time_saved_sec", s
            )
            seen.append(
                T.REGISTRY.snapshot().counter("compile.cache_time_saved_s")
            )
        assert seen == sorted(seen)
        assert seen[-1] == pytest.approx(want)

    def test_compile_seconds_are_booked_by_program(self):
        from spark_rapids_ml_tpu.telemetry import compilemon

        event = "/jax/core/compile/backend_compile_duration"
        compilemon._on_duration(event, 166.0, fun_name="eigh")
        compilemon._on_duration(event, 2.0, fun_name="_fold")
        compilemon._on_duration(event, 1.0, fun_name="_fold")
        compilemon._on_duration(event, 0.5)  # an older JAX: no keyword
        # tracing durations carry fun_name too, and are not compiles
        compilemon._on_duration(
            "/jax/core/compile/jaxpr_trace_duration", 9.0, fun_name="eigh"
        )
        snap = T.REGISTRY.snapshot()
        assert snap.hist("compile.seconds").count == 4
        assert snap.hist("compile.seconds").total == pytest.approx(169.5)
        by = snap.hist("compile.program_seconds", program="eigh")
        assert (by.count, by.total) == (1, 166.0)
        fold = snap.hist("compile.program_seconds", program="_fold")
        assert (fold.count, fold.total) == (2, 3.0)

    def test_a_real_compile_is_booked_by_program(self):
        import jax
        import jax.numpy as jnp

        T.install_monitoring()

        def _tpu_ml_compilemon_probe(x):
            return x * 3 + 1

        jax.jit(_tpu_ml_compilemon_probe)(jnp.arange(7.0)).block_until_ready()
        snap = T.REGISTRY.snapshot()
        programs = {
            dict(labels).get("program")
            for (name, labels) in snap.hists
            if name == "compile.program_seconds"
        }
        assert any("_tpu_ml_compilemon_probe" in (p or "") for p in programs)

    def test_fit_report_carries_cache_load_seconds(self, data):
        x, _ = data
        r = StandardScaler().fit(x).fit_report
        assert r.compile["cache_load_seconds"] >= 0.0
        assert r.compile["cache_time_saved_s"] >= 0.0


class TestRegistryThreadSafety:
    def test_concurrent_counters_and_spans_exact(self):
        # the localspark partition-executor load shape: many threads, one
        # registry. Totals must be exact — the lock satellite.
        n_threads, per_thread = 8, 500
        start = threading.Barrier(n_threads)

        def work():
            start.wait()
            for _ in range(per_thread):
                T.counter_inc("t.count")
                T.counter_inc("t.bytes", 3, path="x")
                T.REGISTRY.histogram_record("t.h", 0.5)
                with trace_range("t.span"):
                    pass

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = T.REGISTRY.snapshot()
        total = n_threads * per_thread
        assert snap.counter("t.count") == total
        assert snap.counter("t.bytes") == 3 * total
        assert snap.hist("t.h").count == total
        assert metrics()["t.span"]["count"] == total


class TestFitReport:
    def test_in_core_pca(self, data):
        x, _ = data
        m = PCA().setInputCol("f").setK(3).fit(x)
        r = m.fit_report
        assert r is not None
        assert r.estimator == "PCA"
        assert r.wall_seconds > 0
        assert r.phases  # compute cov / eigh spans
        for p in r.phases.values():
            assert {"count", "sum"}.issubset(p)

    def test_in_core_scaler_and_linreg(self, data):
        x, y = data
        ms = StandardScaler().fit(x)
        assert ms.fit_report is not None
        assert ms.fit_report.estimator == "StandardScaler"
        ml = LinearRegression().fit((x, y))
        assert ml.fit_report is not None
        assert ml.fit_report.estimator == "LinearRegression"

    def test_streamed_fits_report_rows(self, data, force_streamed):
        x, y = data
        for est, arg in (
            (PCA().setInputCol("f").setK(3), x),
            (StandardScaler(), x),
            (LinearRegression(), (x, y)),
        ):
            T.reset_metrics()
            m = est.fit(arg, num_partitions=3)
            r = m.fit_report
            assert r is not None, type(est).__name__
            assert r.rows_ingested == len(x), type(est).__name__
            assert r.bytes_ingested > 0
            # the streamed pipeline's spans are attributed to this fit
            assert "fold.dispatch" in r.phases, r.phases.keys()
            assert "fold.wait" in r.phases

    def test_report_isolated_per_fit(self, data):
        x, _ = data
        m1 = StandardScaler().fit(x)
        m2 = StandardScaler().fit(x[:100])
        # each report is a snapshot delta, not the accumulated registry
        assert m2.fit_report.phases != {} or m1.fit_report.phases != {}
        c1 = sum(p["count"] for p in m1.fit_report.phases.values())
        c2 = sum(p["count"] for p in m2.fit_report.phases.values())
        assert c2 <= c1 * 2  # second fit didn't inherit the first's spans

    def test_report_roundtrips_via_dict(self, data):
        x, _ = data
        r = StandardScaler().fit(x).fit_report
        back = T.FitReport.from_dict(json.loads(json.dumps(r.to_dict())))
        assert back.estimator == r.estimator
        assert back.wall_seconds == pytest.approx(r.wall_seconds)
        assert back.phases.keys() == r.phases.keys()

    def test_loaded_model_has_no_report(self, data, tmp_path):
        x, _ = data
        from spark_rapids_ml_tpu.models.scaler import StandardScalerModel

        m = StandardScaler().fit(x)
        m.save(str(tmp_path / "m"))
        loaded = StandardScalerModel.load(str(tmp_path / "m"))
        assert loaded.fit_report is None


class TestJsonlSink:
    def test_round_trip(self, data, tmp_path):
        x, _ = data
        path = str(tmp_path / "telemetry.jsonl")
        old = get_config().telemetry_path
        set_config(telemetry_path=path)
        try:
            PCA().setInputCol("f").setK(3).fit(x)
            StandardScaler().fit(x)
        finally:
            set_config(telemetry_path=old)
        records = T.read_jsonl(path)
        assert [r["estimator"] for r in records] == ["PCA", "StandardScaler"]
        for r in records:
            assert r["type"] == "fit_report"
            assert r["schema"] == 7
            assert len(r["fit_id"]) == 12  # log<->report join key
            assert r["wall_seconds"] > 0
            assert isinstance(r["phases"], dict)
            assert "compile" in r and "device_memory" in r

    def test_disabled_by_default(self, data, tmp_path):
        x, _ = data
        assert get_config().telemetry_path == ""
        m = StandardScaler().fit(x)
        assert m.fit_report is not None  # report still attaches, no sink

    def test_export_failure_never_raises(self, data):
        x, _ = data
        old = get_config().telemetry_path
        set_config(telemetry_path="/nonexistent-dir/nope/t.jsonl")
        try:
            m = StandardScaler().fit(x)  # export fails, fit must not
            assert m.fit_report is not None
        finally:
            set_config(telemetry_path=old)

    def test_read_jsonl_skips_corrupt_lines(self, tmp_path):
        p = tmp_path / "t.jsonl"
        p.write_text('{"type":"fit_report","estimator":"A"}\n{oops\n\n')
        recs = T.read_jsonl(str(p))
        assert len(recs) == 1 and recs[0]["estimator"] == "A"


class TestConfigValidation:
    def test_telemetry_path_must_be_str(self):
        with pytest.raises(TypeError):
            set_config(telemetry_path=7)

    def test_int_keys_still_reject_str(self):
        with pytest.raises(TypeError):
            set_config(min_bucket="128")


class TestDeviceMemorySampling:
    def test_sample_never_raises(self):
        # CPU backend: memory_stats() is None — must return empty, not throw
        out = T.sample_device_memory()
        assert isinstance(out, dict)

    def test_install_monitoring_idempotent(self):
        assert T.install_monitoring() == T.install_monitoring()
