"""Flight recorder (telemetry/timeline.py) + the worker→driver telemetry
trailer + the fit timeline export pipeline.

Covers the ISSUE-4 list: ring bounding and event ordering under concurrent
recording, Chrome trace-event export validity, the localspark task
protocol's telemetry trailer round-trip (worker events land driver-side
labeled by partition), the streamed-SparkPCA acceptance path (driver
spans + injected-fault/retry instants + overlap_fraction on the report,
rendered/exported by tools/trace_timeline.py), the TPU_ML_PROGRESS
heartbeat, the fit_id log filter, and the Prometheus exposition +
tools/metrics_dump.py satellite.
"""

from __future__ import annotations

import json
import logging
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from spark_rapids_ml_tpu import telemetry as T
from spark_rapids_ml_tpu.resilience import faults
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY, MetricsRegistry
from spark_rapids_ml_tpu.telemetry.timeline import (
    TIMELINE,
    Timeline,
    chrome_trace,
    timeline_capacity,
)
from spark_rapids_ml_tpu.utils.config import get_config, set_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TL_CLI = os.path.join(REPO, "tools", "trace_timeline.py")
MD_CLI = os.path.join(REPO, "tools", "metrics_dump.py")


@pytest.fixture(autouse=True)
def clean():
    T.reset_metrics()
    TIMELINE.clear()
    faults.reset_faults()
    yield
    T.reset_metrics()
    TIMELINE.clear()
    faults.reset_faults()


@pytest.fixture
def force_streamed(monkeypatch):
    old = get_config().stream_fit_max_resident_bytes
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "128")
    set_config(stream_fit_max_resident_bytes=1)
    yield
    set_config(stream_fit_max_resident_bytes=old)


class TestTimelineUnit:
    def test_span_and_instant_event_shape(self):
        tl = Timeline(capacity=16)
        tl.record_span("fold", 1.0, 1.5, estimator="PCA", empty="")
        tl.record_instant("retry", site="fold.dispatch", attempt=1)
        spans = [e for e in tl.events() if e["ph"] == "X"]
        instants = [e for e in tl.events() if e["ph"] == "i"]
        assert len(spans) == 1 and len(instants) == 1
        s = spans[0]
        assert s["name"] == "fold"
        assert s["ts"] == 1_000_000 and s["dur"] == 500_000
        assert s["pid"] == os.getpid()
        assert s["args"] == {"estimator": "PCA"}  # falsy labels dropped
        i = instants[0]
        assert i["s"] == "t"
        assert i["args"] == {"site": "fold.dispatch", "attempt": 1}

    def test_who_records_is_asked_of_the_kernel_once_and_again_after_a_fork(
        self, monkeypatch
    ):
        """``os.getpid()`` and ``threading.get_native_id()`` are system calls
        (0.2 ms under gVisor): an event reads what was asked once a process
        and once a thread, and a forked child asks again."""
        import threading

        from spark_rapids_ml_tpu.telemetry import timeline

        tl = Timeline(capacity=16)
        tl.record_span("fold", 1.0, 1.5)  # this thread's id is known by now
        asked = []
        monkeypatch.setattr(os, "getpid", lambda: asked.append("pid") or 4242)
        monkeypatch.setattr(
            threading, "get_native_id", lambda: asked.append("tid") or 77
        )
        for _ in range(3):
            tl.record_span("fold", 1.0, 1.5)
            tl.record_instant("retry")
        assert asked == []
        other = threading.Thread(target=tl.record_instant, args=("retry",))
        other.start()
        other.join(timeout=30)
        # (a thread asks for its own id as it starts, too)
        assert "pid" not in asked and tl.events()[-1]["tid"] == 77
        try:
            timeline._forget_who()  # what a forked child runs first
            tl.record_instant("retry")
            assert tl.events()[-1]["pid"] == 4242 and tl.events()[-1]["tid"] == 77
        finally:
            monkeypatch.undo()
            timeline._forget_who()
        tl.record_instant("retry")
        assert tl.events()[-1]["pid"] == os.getpid()
        assert tl.events()[-1]["tid"] == threading.get_native_id()

    def test_ring_stays_within_bound(self):
        tl = Timeline(capacity=64)
        for k in range(1000):
            tl.record_instant("e", k=k + 1)
        assert len(tl) == 64
        evs = tl.events()
        # oldest fell off; the survivors are exactly the LAST 64, in order
        assert [e["args"]["k"] for e in evs] == list(range(937, 1001))
        assert evs[-1]["seq"] == 1000

    def test_zero_capacity_disables_recording(self):
        tl = Timeline(capacity=0)
        tl.record_span("x", 0.0, 1.0)
        tl.record_instant("y")
        tl.merge([{"name": "z", "ts": 1}])
        assert len(tl) == 0

    def test_capacity_env(self, monkeypatch):
        monkeypatch.setenv("TPU_ML_TIMELINE_EVENTS", "128")
        assert timeline_capacity() == 128
        assert Timeline().capacity == 128
        monkeypatch.setenv("TPU_ML_TIMELINE_EVENTS", "banana")
        with pytest.raises(ValueError, match="not an integer"):
            timeline_capacity()
        monkeypatch.setenv("TPU_ML_TIMELINE_EVENTS", "-1")
        with pytest.raises(ValueError, match=">= 0"):
            timeline_capacity()

    def test_since_seq_window(self):
        tl = Timeline(capacity=16)
        tl.record_instant("a")
        mark = tl.seq()
        tl.record_instant("b")
        tl.record_instant("c")
        assert [e["name"] for e in tl.events(since_seq=mark)] == ["b", "c"]

    def test_concurrent_recording_bounded_and_ordered(self):
        """The localspark load shape: many threads record concurrently. No
        lost updates (every append got a distinct seq), the ring bound
        holds, and events() comes out seq-ordered."""
        tl = Timeline(capacity=256)
        n_threads, per_thread = 8, 500

        def work(t):
            for k in range(per_thread):
                tl.record_instant("e", thread=t + 1, k=k + 1)

        threads = [
            threading.Thread(target=work, args=(t,)) for t in range(n_threads)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert len(tl) == 256
        evs = tl.events()
        seqs = [e["seq"] for e in evs]
        assert seqs == sorted(seqs)
        assert len(set(seqs)) == len(seqs)
        assert tl.seq() == n_threads * per_thread  # no lost seq update

    def test_merge_stamps_labels_and_drops_malformed(self):
        tl = Timeline(capacity=16)
        foreign = [
            {"name": "worker.task", "ph": "X", "ts": 5, "dur": 2,
             "pid": 99999, "tid": 1, "args": {"x": 1}},
            "not-a-dict",
            {"ph": "i", "ts": 7},  # no name
            {"name": "noline"},  # no ts
        ]
        tl.merge(foreign, partition="3", empty="")
        evs = tl.events()
        assert len(evs) == 1
        e = evs[0]
        assert e["pid"] == 99999 and e["ts"] == 5  # foreign clock preserved
        assert e["args"] == {"x": 1, "partition": "3"}

    def test_chrome_trace_valid_and_named(self):
        tl = Timeline(capacity=16)
        tl.record_span("driver.span", 0.0, 1.0)
        tl.merge(
            [{"name": "worker.task", "ph": "X", "ts": 1, "dur": 1,
              "pid": 4242, "tid": 1, "args": {}}],
            partition="7",
        )
        trace = json.loads(json.dumps(chrome_trace(tl.events())))
        evs = trace["traceEvents"]
        assert all("seq" not in e for e in evs)
        meta = {e["pid"]: e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert meta[4242] == "worker partition 7"
        assert meta[os.getpid()].startswith("driver")


class TestWorkerTrailer:
    def test_mapinarrow_round_trip_labels_partitions(self):
        """The tentpole protocol change: worker registry deltas and timeline
        events ship on the success frame and merge driver-side labeled by
        partition."""
        from spark_rapids_ml_tpu.localspark.session import LocalSparkSession

        with LocalSparkSession(parallelism=3, num_workers=2) as spark:
            df = spark.createDataFrame(
                [(float(i), float(2 * i)) for i in range(30)], ["a", "b"]
            )

            def fn(it):
                yield from it

            assert len(df.mapInArrow(fn, df.schema).collect()) == 30

        snap = REGISTRY.snapshot()
        # worker-side span histogram arrived, one series per partition
        assert snap.hist("span.seconds", phase="worker.task").count == 3
        for p in ("0", "1", "2"):
            assert (
                snap.hist("span.seconds", phase="worker.task", partition=p).count
                == 1
            )
        # timeline events arrived with the foreign pid preserved
        tasks = [
            e for e in TIMELINE.events() if e["name"] == "worker.task"
        ]
        assert sorted(e["args"]["partition"] for e in tasks) == ["0", "1", "2"]
        assert all(e["pid"] != os.getpid() for e in tasks)

    def test_worker_counters_merge_with_partition_label(self):
        """A counter a plan function records inside the worker becomes
        visible in the driver registry, labeled by its partition."""
        from spark_rapids_ml_tpu.localspark.session import LocalSparkSession

        def fn(it):
            from spark_rapids_ml_tpu.telemetry.registry import REGISTRY as R

            for b in it:
                R.counter_inc("test.worker_rows", b.num_rows)
                yield b

        with LocalSparkSession(parallelism=2, num_workers=2) as spark:
            df = spark.createDataFrame(
                [(float(i),) for i in range(20)], ["a"]
            )
            df.mapInArrow(fn, df.schema).collect()
        snap = REGISTRY.snapshot()
        assert snap.counter("test.worker_rows") == 20
        assert snap.counter("test.worker_rows", partition="0") == 10
        assert snap.counter("test.worker_rows", partition="1") == 10

    def test_failed_task_ships_no_telemetry(self):
        from spark_rapids_ml_tpu.localspark.session import (
            LocalSparkSession,
            WorkerException,
        )

        def bad(it):
            raise ValueError("boom")
            yield  # pragma: no cover

        with LocalSparkSession(parallelism=2, num_workers=1) as spark:
            df = spark.createDataFrame([(1.0,), (2.0,)], ["a"])
            with pytest.raises(WorkerException, match="boom"):
                df.mapInArrow(bad, df.schema).collect()
            # the protocol stream stayed in sync: the SAME worker runs the
            # next task fine (an unread trailer would desynchronize it)
            def ok(it):
                yield from it

            assert len(df.mapInArrow(ok, df.schema).collect()) == 2
        assert [e for e in TIMELINE.events() if e["name"] == "worker.task"]


class TestFitTimelineExport:
    def test_streamed_sparkpca_exports_loadable_chrome_trace(
        self, force_streamed, monkeypatch, tmp_path
    ):
        """The acceptance path: a streamed SparkPCA.fit (mesh-local, with
        one injected-then-retried fault) plus a worker-path fit, exported
        via TPU_ML_TIMELINE_PATH and rendered by tools/trace_timeline.py
        into Chrome trace JSON holding driver spans, partition-labeled
        worker spans and the fault/retry instants."""
        from spark_rapids_ml_tpu.localspark.session import LocalSparkSession
        from spark_rapids_ml_tpu.localspark import types as LT
        from spark_rapids_ml_tpu.spark import SparkPCA

        tl_path = str(tmp_path / "timeline.jsonl")
        old = get_config().timeline_path
        set_config(timeline_path=tl_path)
        # first fold dispatch fails with a transient I/O error, the shared
        # retry recovers it — the flight recorder must show both instants
        monkeypatch.setenv("TPU_ML_FAULT_PLAN", "fold.dispatch:io:1")
        try:
            rng = np.random.default_rng(7)
            x = rng.normal(size=(600, 8))
            schema = LT.StructType(
                [LT.StructField("features", LT.ArrayType(LT.DoubleType()))]
            )
            with LocalSparkSession(parallelism=2, num_workers=1) as spark:
                df = spark.createDataFrame([(r.tolist(),) for r in x], schema)
                model = (
                    SparkPCA().setInputCol("features").setK(3)
                    .setDistribution("mesh-local").fit(df)
                )
                monkeypatch.delenv("TPU_ML_FAULT_PLAN")
                faults.reset_faults()
                # worker-path fit: driver-merge runs partition stats through
                # mapInArrow workers, contributing partition-labeled spans
                SparkPCA().setInputCol("features").setK(3).fit(df)
        finally:
            set_config(timeline_path=old)

        rep = model.fit_report
        assert rep is not None and len(rep.fit_id) == 12
        assert rep.overlap_fraction is not None
        assert 0.0 <= rep.overlap_fraction <= 1.0

        records = [
            json.loads(line)
            for line in open(tl_path, encoding="utf-8")
            if line.strip()
        ]
        assert [r["type"] for r in records] == ["timeline", "timeline"]
        assert records[0]["fit_id"] == rep.fit_id
        assert records[0]["overlap_fraction"] == rep.overlap_fraction

        out_json = str(tmp_path / "trace.json")
        proc = subprocess.run(
            [sys.executable, TL_CLI, tl_path, "--out", out_json],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert "overlap fraction" in proc.stdout
        with open(out_json, encoding="utf-8") as f:
            trace = json.load(f)  # valid Chrome trace JSON
        evs = trace["traceEvents"]
        phases = {e.get("ph") for e in evs}
        assert {"X", "i", "M"} <= phases
        driver_spans = [
            e for e in evs
            if e.get("ph") == "X" and e.get("pid") == os.getpid()
        ]
        assert driver_spans  # fold.dispatch / fold.wait etc.
        worker_spans = [
            e for e in evs
            if e.get("ph") == "X" and (e.get("args") or {}).get("partition")
        ]
        assert worker_spans  # partition-labeled, from the trailer
        instants = {e["name"] for e in evs if e.get("ph") == "i"}
        assert "fault.injected" in instants
        assert "retry" in instants
        assert "stream.chunk" in instants

    def test_no_export_without_timeline_path(self, tmp_path):
        from spark_rapids_ml_tpu.models.pca import PCA

        assert get_config().timeline_path == ""
        x = np.random.default_rng(0).normal(size=(128, 4))
        model = PCA().setInputCol("f").setK(2).fit(x)
        assert model.fit_report.fit_id  # fit identity minted regardless

    def test_in_core_fit_has_no_overlap_fraction(self):
        from spark_rapids_ml_tpu.models.pca import PCA

        x = np.random.default_rng(0).normal(size=(128, 4))
        model = PCA().setInputCol("f").setK(2).fit(x)
        assert model.fit_report.overlap_fraction is None


class TestProgressHeartbeat:
    def test_heartbeat_line_on_stderr(self, monkeypatch, capsys):
        from spark_rapids_ml_tpu.ops import linalg as L
        from spark_rapids_ml_tpu.spark import ingest

        monkeypatch.setenv("TPU_ML_PROGRESS", "1e-9")
        rng = np.random.default_rng(3)
        x = np.asarray(rng.normal(size=(1024, 16)), ingest.wire_dtype())
        res = ingest.stream_fold(
            iter(np.array_split(x, 8)),
            L.gram_fold_step(),
            n=16,
            init=L.init_gram_carry(16, x.dtype),
            chunk_rows=128,
        )
        assert res.chunks == 8
        err = capsys.readouterr().err
        assert "[tpu-ml progress" in err
        assert "rows=" in err and "rows/s" in err and "retries=" in err

    def test_heartbeat_off_by_default(self, capsys):
        from spark_rapids_ml_tpu.ops import linalg as L
        from spark_rapids_ml_tpu.spark import ingest

        assert ingest.progress_interval() == 0.0
        x = np.asarray(
            np.random.default_rng(3).normal(size=(256, 8)),
            ingest.wire_dtype(),
        )
        ingest.stream_fold(
            iter(np.array_split(x, 2)),
            L.gram_fold_step(),
            n=8,
            init=L.init_gram_carry(8, x.dtype),
            chunk_rows=128,
        )
        assert "[tpu-ml progress" not in capsys.readouterr().err

    def test_bad_interval_rejected(self, monkeypatch):
        from spark_rapids_ml_tpu.spark import ingest

        monkeypatch.setenv("TPU_ML_PROGRESS", "often")
        with pytest.raises(ValueError, match="TPU_ML_PROGRESS"):
            ingest.progress_interval()


class TestFitIdFilter:
    def test_package_log_records_carry_fit_id(self, caplog):
        from spark_rapids_ml_tpu.models.pca import PCA

        x = np.random.default_rng(0).normal(size=(128, 4))
        with caplog.at_level(logging.DEBUG, logger="spark_rapids_ml_tpu"):
            model = PCA().setInputCol("f").setK(2).fit(x)
        fid = model.fit_report.fit_id
        stamped = [
            r for r in caplog.records if getattr(r, "fit_id", "-") == fid
        ]
        assert stamped  # span debug lines inside the fit window
        # outside any fit, records still format: the filter stamps "-"
        logging.getLogger("spark_rapids_ml_tpu").warning("outside")
        assert caplog.records[-1].fit_id == "-"


class TestPrometheusExposition:
    def test_text_format(self):
        reg = MetricsRegistry()
        reg.counter_inc("retry.attempts", 3, site="fold.dispatch")
        reg.gauge_set("chunk.rows", 512)
        reg.histogram_record("span.seconds", 0.5, phase="fit")
        reg.histogram_record("span.seconds", 2.0, phase="fit")
        text = reg.to_prometheus()
        assert "# TYPE tpu_ml_retry_attempts counter" in text
        assert 'tpu_ml_retry_attempts{site="fold.dispatch"} 3' in text
        assert "# TYPE tpu_ml_chunk_rows gauge" in text
        assert "# TYPE tpu_ml_span_seconds histogram" in text
        assert 'le="+Inf"' in text
        assert 'tpu_ml_span_seconds_count{phase="fit"} 2' in text
        assert 'tpu_ml_span_seconds_sum{phase="fit"} 2.5' in text
        # cumulative buckets: the +Inf bucket equals the count
        assert text.endswith("\n")

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter_inc("c", 1, site='we"ird\\x')
        assert 'site="we\\"ird\\\\x"' in reg.to_prometheus()

    def test_empty_registry_renders_empty(self):
        assert MetricsRegistry().to_prometheus() == ""

    def test_metrics_dump_cli(self, tmp_path):
        from spark_rapids_ml_tpu.models.pca import PCA
        from spark_rapids_ml_tpu.telemetry.export import export_fit_report

        x = np.random.default_rng(0).normal(size=(256, 6))
        model = PCA().setInputCol("f").setK(2).fit(x)
        path = str(tmp_path / "telemetry.jsonl")
        assert export_fit_report(model.fit_report, path=path)
        proc = subprocess.run(
            [sys.executable, MD_CLI, path],
            capture_output=True, text=True, timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert '# TYPE tpu_ml_fits counter' in proc.stdout
        assert 'tpu_ml_fits{estimator="PCA"} 1' in proc.stdout
        assert "# TYPE tpu_ml_fit_wall_seconds histogram" in proc.stdout

    def test_metrics_dump_cli_no_records(self, tmp_path):
        path = tmp_path / "empty.jsonl"
        path.write_text("")
        proc = subprocess.run(
            [sys.executable, MD_CLI, str(path)],
            capture_output=True, text=True, timeout=60,
        )
        assert proc.returncode == 1

    def test_metrics_dump_renders_every_names_family(self, tmp_path, capsys):
        """Meta-check: every metric family declared in telemetry.names
        survives the report→dump→Prometheus pipeline. A family the dump
        silently drops (filters, sanitization, renames) would otherwise
        vanish from dashboards without any test noticing."""
        import importlib.util

        from spark_rapids_ml_tpu.telemetry import names

        spec = importlib.util.spec_from_file_location("metrics_dump", MD_CLI)
        md = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(md)

        rec = {
            "type": "fit_report",
            "schema": 5,
            "estimator": "Meta",
            "wall_seconds": 1.0,
            "rows_ingested": 10,
            "bytes_ingested": 80,
            "h2d_bytes": 80,
            "overlap_fraction": 0.5,
            "collectives": {"count": 1, "bytes": 8, "tree_combines": 1},
            "compile": {
                "count": 1, "seconds": 0.1, "trace_seconds": 0.05,
                "lower_seconds": 0.02, "cache_hits": 1, "cache_misses": 1,
                "cache_time_saved_s": 0.1,
            },
            "cost_model": {
                "analytical_flops": 100, "analytical_bytes": 100,
                "roofline_utilization": 0.1,
            },
            "tuning": {
                "decisions": [
                    {"kernel": "stream.fold_step", "source": "cache",
                     "cache_hit": True, "config": {}},
                ],
            },
            # every declared family as a raw window counter: the generic
            # pass-through must re-emit ALL of them
            "counters": {name: 1.0 for name in sorted(names.METRICS)},
        }
        path = tmp_path / "t.jsonl"
        path.write_text(json.dumps(rec) + "\n")
        assert md.main([str(path)]) == 0
        out = capsys.readouterr().out

        def prom_name(name):
            return "tpu_ml_" + "".join(
                c if c.isalnum() or c == "_" else "_" for c in name
            )

        missing, wrong_kind = [], []
        for n in sorted(names.METRICS):
            pn = prom_name(n)
            if n in names.HISTOGRAMS:
                kind, probe = "histogram", pn + "_count"
            elif n in names.GAUGES:
                kind, probe = "gauge", pn
            else:
                kind, probe = "counter", pn
            if probe + "{" not in out and probe + " " not in out:
                missing.append(n)
            # the declared kind must be the rendered TYPE: a histogram
            # family silently rendering as a counter would rate() into
            # garbage on a dashboard without any test noticing
            elif f"# TYPE {pn} {kind}" not in out:
                wrong_kind.append(f"{n} (want {kind})")
        assert not missing, f"families dropped by metrics_dump: {missing}"
        assert not wrong_kind, (
            f"families rendered under the wrong TYPE: {wrong_kind} — "
            "declare the kind in telemetry.names HISTOGRAMS/GAUGES"
        )
        # the v5 record above predates the tuner's removal: its ``tuning``
        # trail is read past, not rendered
        assert "tpu_ml_autotune" not in out

    def test_metrics_dump_renders_perf_ledger_serving(self, tmp_path, capsys):
        """A perf_ledger record's serving/refresh/fleet evidence renders
        the serve.*/refresh.* families — queue_delay_us as a histogram,
        transports labeled, swap/fold counters, version gauges."""
        import importlib.util

        spec = importlib.util.spec_from_file_location("metrics_dump", MD_CLI)
        md = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(md)

        rec = {
            "type": "perf_ledger",
            "serving": {
                "requests": 52, "errors": 1, "rows": 400, "batches": 9,
                "hedges": 2, "shed": 0,
                "transport_mix": {"http/json": 20, "uds/fast": 32},
                "bucket_hits": {"8": 40, "16": 12},
                "json_codec": {"encode": 3, "decode": 3},
                "trace": {"minted": 52, "latency_exemplars": []},
                "latency": {"count": 52, "sum": 1.0, "p50": 0.01,
                            "p99": 0.08},
                "queue_delay_us": {"count": 52, "sum": 900.0, "p50": 10.0,
                                   "p99": 120.0},
                "hbm_bytes": 1024,
            },
            "refresh": {
                "refresh": {
                    "swaps": 1, "swap_refused": 0, "rollbacks": 0,
                    "folds": 2, "rows": 8192, "finalizes": 1,
                    "checkpoints": 2, "resumes": 0,
                    "swap_blackout": {"count": 1, "sum": 0.002,
                                      "p50": 0.002, "p99": 0.002},
                    "lag_seconds": 0.5,
                    "versions": {"bench_refresh": 2},
                },
            },
            "fleet": {
                "replicas": 2,
                "routing": {"hits": 90, "misses": 4},
                "rolling_restart": {"drain_events": 1,
                                    "replica_restarts": 1},
            },
        }
        path = tmp_path / "ledger.jsonl"
        path.write_text(json.dumps(rec) + "\n")
        assert md.main([str(path)]) == 0
        out = capsys.readouterr().out
        assert "# TYPE tpu_ml_serve_queue_delay_us histogram" in out
        assert "tpu_ml_serve_queue_delay_us_count 2" in out
        assert "tpu_ml_serve_requests 52" in out
        assert 'tpu_ml_serve_transport{transport="uds",wire="fast"} 32' in out
        assert "tpu_ml_serve_traces 52" in out
        assert "# TYPE tpu_ml_serve_latency histogram" in out
        assert "tpu_ml_serve_swaps 1" in out
        assert "tpu_ml_refresh_folds 2" in out
        assert "# TYPE tpu_ml_refresh_lag_seconds gauge" in out
        assert 'tpu_ml_serve_model_version{model="bench_refresh"} 2' in out
        assert "# TYPE tpu_ml_serve_fleet_replicas gauge" in out
        assert "tpu_ml_serve_route_hits 90" in out
        assert "tpu_ml_serve_drain_events 1" in out


class TestTraceTimelineCli:
    def _record(self, **over):
        events = [
            {"name": "fold.dispatch", "ph": "X", "ts": 1_000_000,
             "dur": 100_000, "pid": 10, "tid": 1, "args": {}},
            {"name": "fold.dispatch", "ph": "X", "ts": 4_000_000,
             "dur": 100_000, "pid": 10, "tid": 1, "args": {}},
            {"name": "worker.task", "ph": "X", "ts": 1_100_000,
             "dur": 50_000, "pid": 11, "tid": 1,
             "args": {"partition": "0"}},
            {"name": "retry", "ph": "i", "ts": 1_200_000, "pid": 10,
             "tid": 1, "s": "t", "args": {"site": "fold.dispatch"}},
        ]
        rec = {
            "type": "timeline", "schema": 1, "fit_id": "feedc0ffee12",
            "estimator": "SparkPCA", "uid": "", "overlap_fraction": 0.5,
            "events": events,
        }
        rec.update(over)
        return rec

    def test_summary_and_strict_gap_gate(self, tmp_path):
        import importlib.util

        spec = importlib.util.spec_from_file_location("trace_timeline", TL_CLI)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        p = tmp_path / "t.jsonl"
        p.write_text(json.dumps(self._record()) + "\n")
        # the driver track has a 2.9 s gap between its two spans
        assert mod.main([str(p)]) == 0  # default threshold 1.0, not strict
        assert mod.main([str(p), "--strict", "--gap-threshold", "1.0"]) == 2
        assert mod.main([str(p), "--strict", "--gap-threshold", "10"]) == 0
        assert mod.main([str(p), "--fit", "nope"]) == 1

    def test_out_roundtrips_through_itself(self, tmp_path):
        """--out writes a Chrome trace the tool itself accepts as input."""
        import importlib.util

        spec = importlib.util.spec_from_file_location("trace_timeline", TL_CLI)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)

        p = tmp_path / "t.jsonl"
        p.write_text(json.dumps(self._record()) + "\n")
        out = str(tmp_path / "trace.json")
        assert mod.main([str(p), "--out", out]) == 0
        trace = json.load(open(out, encoding="utf-8"))
        names = {
            e["args"]["name"]
            for e in trace["traceEvents"]
            if e["ph"] == "M"
        }
        assert "worker partition 0" in names
        assert mod.main([out]) == 0  # chrome-trace input mode
