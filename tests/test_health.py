"""Live health & SLO engine (telemetry.health / telemetry.slo /
telemetry.httpd).

Covers the ISSUE-8 acceptance scenarios without hardware:

- an injected ``device.init`` hang holds the inline liveness probe past
  its deadline → the component escalates to FAILING and ``/healthz``
  flips 200 → 503;
- the sliding-window SLO engine breaches only after the burn streak and
  books ``slo.breach`` counter + timeline instant;
- the HTTP exporter scraped MID-STREAM (from inside a streamed fold's
  source iterator) returns parse-clean Prometheus text including the
  live ``stream.active`` gauge and rolling SLO percentiles;
- the monitor thread (and any straggling probe thread) shuts down
  cleanly — no dangling named threads after ``stop()``;
- FitReport (schema >= 5) carries the monitor's ``health`` summary.
"""

from __future__ import annotations

import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from spark_rapids_ml_tpu.resilience import faults
from spark_rapids_ml_tpu.telemetry import health, httpd
from spark_rapids_ml_tpu.telemetry import slo as slo_mod
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY, MetricsRegistry
from spark_rapids_ml_tpu.telemetry import reset_metrics
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE


@pytest.fixture(autouse=True)
def clean(monkeypatch):
    """Isolated registry/faults/singletons per test; always tear down any
    monitor or exporter a test started."""
    monkeypatch.delenv(faults.FAULT_PLAN_VAR, raising=False)
    faults.reset_faults()
    reset_metrics()
    yield
    httpd.stop_http_server(timeout=10.0)
    health.stop_monitor(timeout=10.0)
    faults.reset_faults()
    reset_metrics()


def _get(url: str) -> tuple[int, str]:
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# one Prometheus sample line: name{labels} value  (labels optional)
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? \S+$"
)


def _assert_parse_clean_prometheus(text: str) -> None:
    assert text, "empty exposition"
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        assert _SAMPLE.match(line), f"malformed exposition line: {line!r}"
        value = line.rsplit(" ", 1)[1]
        float(value)  # must parse (inf/nan spellings included)


# -- SLO engine --------------------------------------------------------------


class TestSloEngine:
    def test_parse_objectives_grammar(self):
        objs = slo_mod.parse_objectives(
            " fold.wait:p99:2.0, ingest.rows:min_rate:50000 "
        )
        assert [o.key for o in objs] == [
            "fold.wait:p99", "ingest.rows:min_rate",
        ]
        assert objs[0].target == 2.0
        assert slo_mod.parse_objectives("") == ()
        with pytest.raises(ValueError, match="series:kind:target"):
            slo_mod.parse_objectives("fold.wait:p99")
        with pytest.raises(ValueError, match="neither"):
            slo_mod.parse_objectives("fold.wait:mean:2.0")
        with pytest.raises(ValueError, match="not a"):
            slo_mod.parse_objectives("fold.wait:p99:fast")

    def test_latency_breach_fires_only_after_burn_streak(self):
        reg = MetricsRegistry()
        eng = slo_mod.SloEngine(
            slo_mod.parse_objectives("fold.wait:p95:0.001"),
            window_s=60.0, burn=2, registry=reg,
        )
        tl0 = TIMELINE.seq()
        reg.histogram_record("span.seconds", 0.5, phase="fold.wait")
        r1 = eng.evaluate()
        (o1,) = r1["objectives"]
        assert o1["breached"] is True and o1["streak"] == 1
        assert r1["total_breaches"] == 0  # burn not reached yet

        reg.histogram_record("span.seconds", 0.6, phase="fold.wait")
        r2 = eng.evaluate()
        (o2,) = r2["objectives"]
        assert o2["streak"] == 2 and o2["breaches"] == 1
        assert r2["total_breaches"] == 1
        snap = reg.snapshot()
        assert snap.counter("slo.breach") == 1
        breach_events = [
            e for e in TIMELINE.events(tl0) if e.get("name") == "slo.breach"
        ]
        assert breach_events, "slo.breach timeline instant missing"
        assert breach_events[0]["args"]["objective"] == "fold.wait:p95"

    def test_min_rate_floor_needs_traffic_to_judge(self):
        reg = MetricsRegistry()
        eng = slo_mod.SloEngine(
            slo_mod.parse_objectives("ingest.rows:min_rate:1000000"),
            window_s=60.0, burn=1, registry=reg,
        )
        r = eng.evaluate()
        (o,) = r["objectives"]
        assert o["value"] is None and o["breached"] is False
        # moving but far below the floor → breach
        reg.counter_inc("ingest.rows", 5)
        r = eng.evaluate()
        (o,) = r["objectives"]
        assert o["value"] is not None and o["breached"] is True
        assert r["total_breaches"] == 1

    def test_rolling_percentiles_published_without_objectives(self):
        reg = MetricsRegistry()
        eng = slo_mod.SloEngine((), window_s=60.0, registry=reg)
        reg.histogram_record("span.seconds", 0.1, phase="ingest.chunk")
        reg.histogram_record("span.seconds", 0.3, phase="ingest.chunk")
        r = eng.evaluate()
        assert "ingest.chunk" in r["rolling"]
        assert set(r["rolling"]["ingest.chunk"]) == {"p50", "p95", "p99"}
        snap = reg.snapshot()
        keys = {
            snap_key for (name, snap_key) in snap.gauges
            if name == "slo.rolling"
        }
        assert any("ingest.chunk" in str(k) for k in keys)


# -- health monitor ----------------------------------------------------------


class TestHealthMonitor:
    def test_probe_runs_in_this_process_or_not_at_all(self, monkeypatch):
        """A child cannot ask about a chip its parent holds: the probe modes
        are ``inline`` and ``off``, from the knob as from the argument."""
        assert health.PROBE_MODES == ("off", "inline")
        with pytest.raises(ValueError, match="must be one of"):
            health.HealthMonitor(probe_mode="subprocess")
        monkeypatch.setenv(health.PROBE_VAR, "subprocess")
        with pytest.raises(ValueError, match="must be one of"):
            health.HealthMonitor()

    def test_all_ok_rollup(self):
        mon = health.HealthMonitor(
            interval_s=60.0, probe_mode="inline",
            probe_fn=lambda: (True, "stub ok"),
        )
        r = mon.poll_once()
        assert r["state"] == "OK"
        assert set(r["components"]) == set(health.COMPONENTS)
        assert r["polls"] == 1 and r["transitions"] == 0
        mon.stop()

    def test_injected_device_init_hang_times_out_probe_to_failing(
        self, monkeypatch
    ):
        """The acceptance scenario: a chaos-plan hang on device.init wedges
        the default inline probe past its deadline; with failing_after=1
        the transport component goes straight to FAILING and the
        transition is counted + recorded on the timeline."""
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "device.init:hang:1:1.0")
        faults.reset_faults()
        mon = health.HealthMonitor(
            interval_s=60.0, probe_mode="inline",
            probe_timeout_s=0.1, failing_after=1,
        )
        tl0 = TIMELINE.seq()
        r = mon.poll_once()
        transport = r["components"]["transport"]
        assert transport["state"] == "FAILING"
        assert "did not complete" in transport["detail"]
        assert r["state"] == "FAILING"
        snap = REGISTRY.snapshot()
        assert snap.counter(
            "health.transitions", component="transport", to="FAILING"
        ) == 1
        assert any(
            e.get("name") == "health.transition"
            and e["args"].get("component") == "transport"
            for e in TIMELINE.events(tl0)
        )
        # the wedged probe thread is joined (bounded) by stop()
        mon.stop(timeout=5.0)
        assert "tpu-ml-health-probe" not in {
            t.name for t in threading.enumerate() if t.is_alive()
        }

    def test_probe_failure_streak_escalates_degraded_then_failing(self):
        mon = health.HealthMonitor(
            interval_s=60.0, probe_mode="inline", probe_timeout_s=1.0,
            failing_after=2, probe_fn=lambda: (False, "synthetic down"),
        )
        r1 = mon.poll_once()
        assert r1["components"]["transport"]["state"] == "DEGRADED"
        r2 = mon.poll_once()
        assert r2["components"]["transport"]["state"] == "FAILING"
        mon.stop()

    def test_stream_heartbeat_staleness(self):
        mon = health.HealthMonitor(
            interval_s=60.0, probe_mode="off", stale_s=60.0, failing_after=2,
        )
        # no active stream → OK regardless of beats
        assert mon.poll_once()["components"]["stream"]["state"] == "OK"
        REGISTRY.gauge_set("stream.active", 1)
        REGISTRY.gauge_set("stream.last_beat", time.monotonic() - 120.0)
        assert mon.poll_once()["components"]["stream"]["state"] == "DEGRADED"
        assert mon.poll_once()["components"]["stream"]["state"] == "FAILING"
        # stream ends (ingest clears the gauge in its finally) → back to OK
        REGISTRY.gauge_set("stream.active", 0)
        assert mon.poll_once()["components"]["stream"]["state"] == "OK"
        # fresh beat while active → OK
        REGISTRY.gauge_set("stream.active", 1)
        REGISTRY.gauge_set("stream.last_beat", time.monotonic())
        assert mon.poll_once()["components"]["stream"]["state"] == "OK"
        mon.stop()

    def test_worker_trailer_recency(self):
        mon = health.HealthMonitor(
            interval_s=60.0, probe_mode="off", stale_s=60.0,
        )
        assert mon.poll_once()["components"]["workers"]["state"] == "OK"
        REGISTRY.gauge_set("worker.last_trailer", time.monotonic() - 300.0)
        assert mon.poll_once()["components"]["workers"]["state"] == "DEGRADED"
        REGISTRY.gauge_set("worker.last_trailer", time.monotonic())
        assert mon.poll_once()["components"]["workers"]["state"] == "OK"
        mon.stop()

    def test_resilience_signals_window(self):
        mon = health.HealthMonitor(
            interval_s=60.0, probe_mode="off", retry_storm=8,
        )
        assert mon.poll_once()["components"]["resilience"]["state"] == "OK"
        REGISTRY.counter_inc("retry.attempts", 10, site="fold.dispatch")
        r = mon.poll_once()
        assert r["components"]["resilience"]["state"] == "DEGRADED"
        assert "retry storm" in r["components"]["resilience"]["detail"]
        # storm passed: the NEXT window is quiet again
        assert mon.poll_once()["components"]["resilience"]["state"] == "OK"
        # cpu fallback is cumulative, not windowed: it marks the whole run
        REGISTRY.counter_inc("degraded.cpu_fallback")
        r = mon.poll_once()
        assert r["components"]["resilience"]["state"] == "DEGRADED"
        assert "cpu fallback" in r["components"]["resilience"]["detail"]
        mon.stop()

    def test_monitor_thread_starts_polls_and_stops_cleanly(self):
        mon = health.HealthMonitor(
            interval_s=0.05, probe_mode="inline",
            probe_fn=lambda: (True, "ok"),
        )
        mon.start()
        assert mon.running
        deadline = time.monotonic() + 10.0
        while mon.polls < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert mon.polls >= 2
        mon.stop(timeout=10.0)
        assert not mon.running
        assert "tpu-ml-health-monitor" not in {
            t.name for t in threading.enumerate() if t.is_alive()
        }

    def test_singleton_start_get_stop(self):
        assert health.get_monitor() is None
        mon = health.start_monitor(
            interval_s=3600.0, probe_mode="inline",
            probe_fn=lambda: (True, "ok"),
        )
        assert health.get_monitor() is mon
        assert health.start_monitor() is mon  # idempotent
        health.stop_monitor()
        assert health.get_monitor() is None
        assert health.current_summary() == {}


# -- HTTP exporter -----------------------------------------------------------


class TestHttpExporter:
    def test_healthz_flips_200_to_503_when_probe_wedges(self):
        state = {"ok": True}

        def probe():
            return state["ok"], "stub"

        mon = health.start_monitor(
            interval_s=3600.0, probe_mode="inline", probe_timeout_s=1.0,
            failing_after=1, probe_fn=probe,
        )
        server = httpd.start_http_server(0, with_monitor=False)
        code, body = _get(server.url + "/healthz")
        assert code == 200
        payload = json.loads(body)
        assert payload["state"] == "OK"
        assert payload["components"]["transport"]["state"] == "OK"

        state["ok"] = False
        mon.poll_once()
        code, body = _get(server.url + "/healthz")
        assert code == 503
        payload = json.loads(body)
        assert payload["state"] == "FAILING"
        assert payload["components"]["transport"]["state"] == "FAILING"

    def test_healthz_unknown_without_monitor(self):
        server = httpd.start_http_server(0, with_monitor=False)
        code, body = _get(server.url + "/healthz")
        assert code == 200
        assert json.loads(body)["state"] == "UNKNOWN"

    def test_metrics_scraped_mid_stream_is_parse_clean(self):
        """Scrape /metrics and /healthz from INSIDE a streamed fold's
        source iterator — the live-watchability acceptance check."""
        from spark_rapids_ml_tpu.ops import linalg as L
        from spark_rapids_ml_tpu.spark import ingest

        server = httpd.start_http_server(0)  # also starts the monitor
        mon = health.get_monitor()
        scraped: dict = {}
        rng = np.random.default_rng(3)

        def source():
            for i in range(3):
                if i == 2:
                    mon.poll_once()  # force a fresh SLO/rolling publish
                    scraped["metrics"] = _get(server.url + "/metrics")
                    scraped["healthz"] = _get(server.url + "/healthz")
                yield np.asarray(rng.normal(size=(128, 6)), np.float64)

        ingest.stream_fold(
            source(), L.gram_fold_step(), n=6,
            init=L.init_gram_carry(6, np.float64), chunk_rows=128,
        )
        code, text = scraped["metrics"]
        assert code == 200
        _assert_parse_clean_prometheus(text)
        # the stream was live at scrape time
        assert "tpu_ml_stream_active 1" in text
        assert "tpu_ml_stream_last_beat" in text
        assert "tpu_ml_ingest_rows" in text
        assert "tpu_ml_health_state" in text
        # rolling SLO percentile gauges for the default watchlist
        assert 'tpu_ml_slo_rolling{q="p99",series="ingest.chunk"}' in text
        hcode, hbody = scraped["healthz"]
        assert hcode == 200 and json.loads(hbody)["state"] == "OK"
        # after the stream, the active gauge is cleared
        code, text = _get(server.url + "/metrics")
        assert code == 200
        assert "tpu_ml_stream_active 0" in text

    def test_slo_report_and_404_endpoints(self):
        health.start_monitor(
            interval_s=3600.0, probe_mode="inline",
            probe_fn=lambda: (True, "ok"),
        ).poll_once()
        server = httpd.start_http_server(0, with_monitor=False)
        code, body = _get(server.url + "/slo")
        assert code == 200
        payload = json.loads(body)
        assert "window_s" in payload and "objectives" in payload
        code, body = _get(server.url + "/report")
        assert code == 200
        assert "reports" in json.loads(body)
        code, body = _get(server.url + "/nope")
        assert code == 404
        # request counters are booked per path
        snap = REGISTRY.snapshot()
        assert snap.counter("http.requests", path="/slo") == 1
        assert snap.counter("http.requests", path="/nope") == 1

    def test_ensure_started_is_off_without_port_env(self, monkeypatch):
        monkeypatch.delenv(httpd.HTTP_PORT_VAR, raising=False)
        assert httpd.ensure_started() is None
        assert httpd.get_http_server() is None

    def test_ensure_started_with_env_port_is_idempotent(self, monkeypatch):
        monkeypatch.setenv(httpd.HTTP_PORT_VAR, "0")
        server = httpd.ensure_started()
        assert server is not None
        assert httpd.ensure_started() is server
        assert httpd.get_http_server() is server
        assert health.get_monitor() is not None  # monitor came up alongside

    def test_stop_http_server_joins_threads(self):
        server = httpd.start_http_server(0)
        assert _get(server.url + "/healthz")[0] in (200, 503)
        httpd.stop_http_server(timeout=10.0)
        assert httpd.get_http_server() is None
        assert health.get_monitor() is None
        alive = {t.name for t in threading.enumerate() if t.is_alive()}
        assert "tpu-ml-httpd" not in alive
        assert "tpu-ml-health-monitor" not in alive


# -- FitReport health stamping -----------------------------------------------


class TestFitReportHealthStamp:
    def test_fit_report_carries_health_summary(self):
        from spark_rapids_ml_tpu.models.pca import PCA
        from spark_rapids_ml_tpu.telemetry.report import SCHEMA_VERSION, FitReport

        assert SCHEMA_VERSION == 7
        health.start_monitor(
            interval_s=3600.0, probe_mode="inline",
            probe_fn=lambda: (True, "ok"),
        ).poll_once()
        x = np.random.default_rng(0).normal(size=(128, 4))
        model = PCA().setInputCol("f").setK(2).fit(x)
        rep = model.fit_report
        assert rep.health["state"] in ("OK", "DEGRADED", "FAILING")
        assert set(rep.health["components"]) == set(health.COMPONENTS)
        assert rep.health["polls"] >= 1
        assert "slo_breaches" in rep.health
        d = rep.to_dict()
        assert d["schema"] == 7 and d["health"] == rep.health
        # a v6 record (with the autotuner's stamp of then) still reads
        old = FitReport.from_dict({**d, "schema": 6, "tuning": {"source": "cache"}})
        assert old.schema == 6 and old.health == rep.health
        assert "tuning" not in old.to_dict()

    def test_fit_report_health_empty_without_monitor(self):
        from spark_rapids_ml_tpu.models.pca import PCA
        from spark_rapids_ml_tpu.telemetry.report import FitReport

        x = np.random.default_rng(1).normal(size=(128, 4))
        model = PCA().setInputCol("f").setK(2).fit(x)
        assert model.fit_report.health == {}
        # older records load with an empty default
        assert FitReport.from_dict({"estimator": "X"}).health == {}
