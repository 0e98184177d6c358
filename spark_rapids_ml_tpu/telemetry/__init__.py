"""Structured telemetry: metrics registry, spans, fit reports, JSONL export.

Public surface (everything the rest of the framework and user code needs):

- ``REGISTRY`` / ``counter_inc`` / ``gauge_set`` / ``histogram_record`` —
  the process-local metric store (:mod:`.registry`).
- ``trace_range`` — host+device trace span with latency accounting
  (:mod:`.spans`); ``metrics()`` / ``reset_metrics()`` keep the read
  shape of the long-removed ``utils.tracing`` module.
- ``FitReport`` / ``begin_fit`` / ``end_fit`` — per-fit capture windows
  (:mod:`.report`), wired automatically through ``models.base``.
- ``TransformReport`` / ``begin_transform`` / ``end_transform`` — the
  serve-side capture windows (:mod:`.report`), wired automatically through
  ``models.base`` transform instrumentation.
- ``costmodel`` — analytical kernel FLOPs/bytes + roofline accounting
  (:mod:`.costmodel`), captured at jitted dispatch sites.
- ``export_fit_report`` / ``export_transform_report`` / ``read_jsonl`` —
  the ``TPU_ML_TELEMETRY_PATH`` JSONL sink (:mod:`.export`).
- ``install_monitoring`` / ``sample_device_memory`` — jax.monitoring
  compile listeners and device-memory gauges (:mod:`.compilemon`).
- ``snapshot_dict`` — full-registry JSON snapshot (bench embedding).
- ``health`` / ``slo`` / ``httpd`` — the live health monitor, the
  sliding-window SLO engine and the /metrics + /healthz HTTP exporter
  (:mod:`.health`, :mod:`.slo`, :mod:`.httpd`); ``HealthMonitor`` /
  ``start_monitor`` / ``stop_monitor`` / ``start_http_server`` /
  ``stop_http_server`` re-exported for the common paths.
"""

from spark_rapids_ml_tpu.telemetry.registry import (
    REGISTRY,
    Histogram,
    MetricsRegistry,
    RegistrySnapshot,
    counter_inc,
    gauge_set,
    histogram_record,
    metrics,
    render_key,
    reset_metrics,
)
from spark_rapids_ml_tpu.telemetry.spans import (
    current_estimator,
    current_fit_id,
    current_span,
    current_transform_id,
    install_fit_id_filter,
    reset_current_estimator,
    reset_current_fit_id,
    reset_current_transform_id,
    set_current_estimator,
    set_current_fit_id,
    set_current_transform_id,
    trace_range,
)
from spark_rapids_ml_tpu.telemetry.timeline import (
    TIMELINE,
    Timeline,
    chrome_trace,
    record_instant,
    timeline_capacity,
)
from spark_rapids_ml_tpu.telemetry.compilemon import (
    install_monitoring,
    sample_device_memory,
)
from spark_rapids_ml_tpu.telemetry import costmodel
from spark_rapids_ml_tpu.telemetry.report import (
    FitReport,
    TransformReport,
    attach_report,
    attach_transform_report,
    begin_fit,
    begin_transform,
    end_fit,
    end_transform,
    release_transform_context,
    snapshot_dict,
)
from spark_rapids_ml_tpu.telemetry.export import (
    export_fit_report,
    export_timeline,
    export_transform_report,
    read_jsonl,
    telemetry_path,
    timeline_path,
)
from spark_rapids_ml_tpu.telemetry import slo
from spark_rapids_ml_tpu.telemetry import health
from spark_rapids_ml_tpu.telemetry import httpd
from spark_rapids_ml_tpu.telemetry.health import (
    HealthMonitor,
    start_monitor,
    stop_monitor,
)
from spark_rapids_ml_tpu.telemetry.httpd import (
    start_http_server,
    stop_http_server,
)

__all__ = [
    "REGISTRY",
    "Histogram",
    "MetricsRegistry",
    "RegistrySnapshot",
    "counter_inc",
    "gauge_set",
    "histogram_record",
    "metrics",
    "render_key",
    "reset_metrics",
    "current_estimator",
    "current_fit_id",
    "current_span",
    "current_transform_id",
    "install_fit_id_filter",
    "reset_current_estimator",
    "reset_current_fit_id",
    "reset_current_transform_id",
    "set_current_estimator",
    "set_current_fit_id",
    "set_current_transform_id",
    "trace_range",
    "TIMELINE",
    "Timeline",
    "chrome_trace",
    "record_instant",
    "timeline_capacity",
    "install_monitoring",
    "sample_device_memory",
    "FitReport",
    "TransformReport",
    "attach_report",
    "attach_transform_report",
    "begin_fit",
    "begin_transform",
    "end_fit",
    "end_transform",
    "release_transform_context",
    "costmodel",
    "snapshot_dict",
    "export_fit_report",
    "export_timeline",
    "export_transform_report",
    "read_jsonl",
    "telemetry_path",
    "timeline_path",
    "slo",
    "health",
    "httpd",
    "HealthMonitor",
    "start_monitor",
    "stop_monitor",
    "start_http_server",
    "stop_http_server",
]
