"""Serve-side telemetry: TransformReport, per-partition counters, the
analytical cost model, and the transform_id log join key.

Covers the ISSUE-5 transform-path list: a fitted SparkPCA.transform over a
multi-partition localspark DataFrame produces a TransformReport whose
per-partition rows/bytes/latency merge correctly from worker processes
(telemetry trailer), the report round-trips through the JSONL sink and
TransformReport.from_dict, lazy plans finalize only at materialization,
in-core array transforms finalize eagerly, cost-model FLOPs/bytes are
stamped on both fit and transform windows, and package log records inside
a transform window carry %(transform_id)s.
"""

from __future__ import annotations

import json
import logging

import numpy as np
import pytest

from spark_rapids_ml_tpu import telemetry as T
from spark_rapids_ml_tpu.telemetry import costmodel
from spark_rapids_ml_tpu.telemetry.report import TransformReport
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils.config import get_config, set_config


@pytest.fixture(autouse=True)
def clean():
    T.reset_metrics()
    TIMELINE.clear()
    yield
    T.reset_metrics()
    TIMELINE.clear()


@pytest.fixture
def pca_df_and_model():
    """A 3-partition localspark DataFrame and a SparkPCA model fitted on it."""
    from spark_rapids_ml_tpu.localspark import types as LT
    from spark_rapids_ml_tpu.localspark.session import LocalSparkSession
    from spark_rapids_ml_tpu.spark import SparkPCA

    rng = np.random.default_rng(11)
    x = rng.normal(size=(600, 8))
    schema = LT.StructType(
        [LT.StructField("features", LT.ArrayType(LT.DoubleType()))]
    )
    with LocalSparkSession(parallelism=3, num_workers=2) as spark:
        df = spark.createDataFrame([(r.tolist(),) for r in x], schema)
        model = SparkPCA().setInputCol("features").setK(3).fit(df)
        yield df, model


class TestTransformReport:
    def test_multipartition_counters_merge(self, pca_df_and_model, tmp_path):
        """The acceptance path: per-partition rows/bytes/latency from the
        worker trailer roll into one TransformReport, exported as JSONL."""
        df, model = pca_df_and_model
        path = str(tmp_path / "telemetry.jsonl")
        old = get_config().telemetry_path
        set_config(telemetry_path=path)
        try:
            out = model.transform(df)
            # the plan is lazy: no report until an action materializes it
            assert model.transform_report is None
            table = out.toArrow()
        finally:
            set_config(telemetry_path=old)
        assert table.num_rows == 600

        rep = model.transform_report
        assert rep is not None
        assert rep.transformer == "SparkPCAModel"
        assert len(rep.transform_id) == 12
        assert rep.wall_seconds > 0
        assert rep.rows == 600
        assert rep.bytes > 0

        # 3 input partitions ran through the instrumented arrow fn; their
        # counters merge per partition label and sum to the total
        assert len(rep.partitions) == 3
        assert sum(p["rows"] for p in rep.partitions.values()) == 600
        for p in rep.partitions.values():
            assert p["rows"] > 0 and p["bytes"] > 0 and p["batches"] >= 1
            assert p["seconds"] > 0
        lat = rep.partition_latency
        assert lat["count"] == 3
        for q in ("p50", "p90", "p99"):
            assert lat[q] > 0
        assert lat["p50"] <= lat["p99"] * (1 + 1e-9)
        # the window's trace_range spans (plan/dispatch/worker) made it in
        assert rep.phases

        # the JSONL sink got the transform_report (the fixture's fit ran
        # before the path was set) and the record round-trips losslessly
        records = [
            json.loads(line)
            for line in open(path, encoding="utf-8")
            if line.strip()
        ]
        rec = [r for r in records if r["type"] == "transform_report"][-1]
        assert rec["schema"] == 1
        back = TransformReport.from_dict(rec)
        assert back.rows == rep.rows
        assert back.transform_id == rep.transform_id
        assert set(back.partitions) == set(rep.partitions)
        assert rec == TransformReport.from_dict(rec).to_dict()

    def test_cost_model_stamped_on_fit_and_transform(self, pca_df_and_model):
        """Analytical FLOPs/bytes from XLA's AOT cost model reach both
        reports — including when the kernels dispatched in worker
        processes (counter-driven rollup over the trailer)."""
        df, model = pca_df_and_model
        fit_cm = model.fit_report.cost_model
        assert "linalg.gram_stats" in fit_cm.get("kernels", {})
        assert fit_cm["analytical_flops"] > 0
        # the CPU is not in the peak table: no roofline figure, not the v5e's
        assert "peak_flops" not in fit_cm
        assert "roofline_utilization" not in fit_cm

        model.transform(df).toArrow()
        cm = model.transform_report.cost_model
        assert "linalg.project" in cm.get("kernels", {})
        k = cm["kernels"]["linalg.project"]
        assert k["calls"] == 3  # one dispatch per partition
        assert k["flops"] > 0 and k["bytes_accessed"] > 0
        assert cm["analytical_flops"] >= k["flops"] * 3 * (1 - 1e-6)
        assert cm["analytical_bytes"] > 0
        assert "roofline_utilization" not in cm

    def test_transform_timeline_exported_with_transform_id(
        self, pca_df_and_model, tmp_path
    ):
        df, model = pca_df_and_model
        tl_path = str(tmp_path / "timeline.jsonl")
        old = get_config().timeline_path
        set_config(timeline_path=tl_path)
        try:
            model.transform(df).toArrow()
        finally:
            set_config(timeline_path=old)
        records = [
            json.loads(line)
            for line in open(tl_path, encoding="utf-8")
            if line.strip()
        ]
        assert records, "transform materialization exported no timeline"
        rec = records[-1]
        assert rec["type"] == "timeline"
        assert rec["transform_id"] == model.transform_report.transform_id
        names = {e.get("name") for e in rec["events"]}
        assert "transform.partition" in names

    def test_in_core_array_transform_finalizes_eagerly(self):
        from spark_rapids_ml_tpu.models.pca import PCA

        x = np.random.default_rng(3).normal(size=(256, 6))
        model = PCA().setInputCol("f").setK(2).fit(x)
        out = model.transform(x)
        assert np.asarray(out).shape == (256, 2)
        rep = model.transform_report
        assert rep is not None  # arrays are not lazy plans
        assert rep.transformer == "PCAModel"
        assert rep.wall_seconds > 0
        cm = rep.cost_model
        assert "linalg.project" in cm.get("kernels", {})


class TestTransformIdLogFilter:
    def test_log_records_carry_transform_id(self, caplog):
        cap = T.begin_transform("Demo", "uid0")
        try:
            with caplog.at_level(logging.WARNING, logger="spark_rapids_ml_tpu"):
                logging.getLogger("spark_rapids_ml_tpu").warning("inside")
        finally:
            rep = T.end_transform(cap)
        assert caplog.records[-1].transform_id == rep.transform_id
        # outside any window the filter stamps the "-" placeholder
        logging.getLogger("spark_rapids_ml_tpu").warning("outside")
        assert caplog.records[-1].transform_id == "-"

    def test_release_is_idempotent(self):
        cap = T.begin_transform("Demo")
        T.release_transform_context(cap)
        T.release_transform_context(cap)  # second release is a no-op
        rep = T.end_transform(cap)  # end after release still reports
        assert rep.transformer == "Demo"
        assert len(rep.transform_id) == 12


class TestWindowSummaryUnit:
    def test_counter_driven_rollup(self, monkeypatch):
        """window_summary needs only the costmodel.* counters — the shape
        of worker-side captures arriving via the telemetry trailer."""
        from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

        monkeypatch.setenv("TPU_ML_PEAK_TFLOPS", "2.5")  # explicit override
        snap = REGISTRY.snapshot()
        REGISTRY.counter_inc("costmodel.calls", 2, kernel="k")
        REGISTRY.counter_inc("costmodel.flops", 200.0, kernel="k")
        REGISTRY.counter_inc("costmodel.bytes", 64.0, kernel="k")
        delta = REGISTRY.snapshot().delta(snap)
        cm = costmodel.window_summary(delta, wall_seconds=2.0)
        assert cm["kernels"]["k"] == pytest.approx(
            {"calls": 2, "flops": 100.0, "bytes_accessed": 32.0}
        )
        assert cm["analytical_flops"] == 200.0
        assert cm["achieved_flop_s"] == 100.0
        assert cm["peak_flops"] == 2.5e12
        assert cm["roofline_utilization"] == pytest.approx(100.0 / 2.5e12)

    def test_peak_is_keyed_by_device_kind(self, monkeypatch):
        """v5e is in the table; a device that is not gets no peak."""
        import jax

        monkeypatch.delenv("TPU_ML_PEAK_TFLOPS", raising=False)
        assert costmodel.PEAK_TFLOPS_BY_DEVICE_KIND["TPU v5 lite"] == 197.0
        assert jax.devices()[0].device_kind not in (
            costmodel.PEAK_TFLOPS_BY_DEVICE_KIND
        )
        assert costmodel.peak_flops() is None

    def test_empty_window_is_empty_dict(self):
        from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

        snap = REGISTRY.snapshot()
        delta = REGISTRY.snapshot().delta(snap)
        assert costmodel.window_summary(delta, 1.0) == {}


class TestNestedTransformGuard:
    def test_chained_stages_book_rows_once(self):
        """Chained lazy plans drive transform generators re-entrantly in one
        thread; only the outermost stage may book the volume counters, or a
        two-stage pipeline double-counts every input row. Per-stage latency
        stays unconditional — stage timing is real work."""
        import pyarrow as pa

        from spark_rapids_ml_tpu.spark import arrow_fns
        from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

        class _Stage(arrow_fns._InstrumentedTransformFn):
            def _run(self, batches):
                yield from batches

        batch = pa.RecordBatch.from_arrays(
            [pa.array([1.0, 2.0, 3.0])], names=["x"]
        )
        snap = REGISTRY.snapshot()
        out = list(_Stage()(_Stage()(iter([batch]))))
        assert out[0].num_rows == 3
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("transform.rows") == 3  # once, not per stage
        assert delta.counter("transform.batches") == 1
        assert delta.hist("transform.partition_seconds").count == 2
