"""Per-fit telemetry capture: the ``FitReport`` attached to every model.

The registry accumulates per-process; a user asking "where did THIS fit's
time go" needs the interval. ``begin_fit``/``end_fit`` bracket one
``Estimator.fit`` call (wired once in ``models.base`` so all estimators —
core and Spark-facing — get it without per-estimator code): snapshot the
registry, stamp the estimator name into the span context, and on exit build
a :class:`FitReport` from the snapshot delta — per-phase latency
percentiles, rows/bytes ingested, H2D bytes, collective count/payload,
compile count/seconds/cache traffic, and the per-device peak memory sampled
at fit end.

Nested fits (CrossValidator → estimator, SparkPCA → core PCA, OneVsRest →
per-class fits) each get their own report — the inner report is a subset
window of the outer — but only the OUTERMOST fit is exported to the JSONL
sink, so one user-visible ``fit()`` is one sink line.
"""

from __future__ import annotations

import collections
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any

from spark_rapids_ml_tpu.telemetry import compilemon, costmodel, spans
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY, render_key
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils.config import enable_compilation_cache

# v2: + fit_id (log↔report correlation) and overlap_fraction (H2D↔compute
# overlap evidence from the streamed fold). v3: + cost_model (analytical
# FLOPs/bytes + roofline utilization from telemetry.costmodel). v4: + tuning
# (an autotuner's decisions). v5: + health (the live monitor's component
# rollup at fit end — empty when no monitor runs). v6: + admission (the
# health-driven admission-control decision taken at fit start —
# policy/action/health_state/reason; empty when no check ran). v7: - tuning
# (the autotuner is gone; ``from_dict`` reads a v4-v6 record and drops the
# field). Readers must tolerate other versions (tools/trace_report.py
# skips-with-note rather than KeyError).
SCHEMA_VERSION = 7

# TransformReport wire schema (independent of the fit schema above).
TRANSFORM_SCHEMA_VERSION = 1


@dataclass
class FitReport:
    """Everything observed during one ``fit()`` call.

    ``phases`` maps span name → ``{count, sum, min, max, p50, p90, p99}``
    seconds. ``rows_ingested``/``bytes_ingested`` count the data-path layer
    that actually ran: the streamed/mesh ingest counters when the fit went
    through ``spark.ingest``, else the columnar extraction counters.
    ``device_memory`` is the fit-end ``memory_stats()`` sample per device
    (``peak_bytes_in_use`` is process-lifetime peak — an upper bound for
    the fit, exact when the fit is the process's big allocation).
    """

    estimator: str
    uid: str
    wall_seconds: float
    phases: dict[str, dict[str, float]] = field(default_factory=dict)
    rows_ingested: int = 0
    bytes_ingested: int = 0
    h2d_bytes: int = 0
    collectives: dict[str, float] = field(default_factory=dict)
    compile: dict[str, float] = field(default_factory=dict)
    device_memory: dict[str, dict[str, int]] = field(default_factory=dict)
    counters: dict[str, float] = field(default_factory=dict)
    timestamp_unix: float = 0.0
    # log↔report join key: stamped on package log records (%(fit_id)s) and
    # timeline events recorded inside this fit's window
    fit_id: str = ""
    # mean streamed-fold overlap (overlapped dispatches / chunks) across
    # the fit's stream_fold calls; None when nothing streamed
    overlap_fraction: float | None = None
    # analytical kernel cost rollup (telemetry.costmodel.window_summary):
    # per-kernel calls + per-call FLOPs/bytes, window totals, roofline
    # utilization. Empty when no captured kernel dispatched in the window.
    cost_model: dict = field(default_factory=dict)
    # live health rollup at fit end (v5): overall + per-component states,
    # poll/transition counts and the window's SLO breach total from the
    # background HealthMonitor. Empty when no monitor was running.
    health: dict = field(default_factory=dict)
    # admission-control decision at fit start (v6):
    # {policy, action, health_state, reason} from health.admission_check —
    # proves WHY a fit ran degraded (or that the gate saw a healthy system)
    admission: dict = field(default_factory=dict)
    schema: int = SCHEMA_VERSION

    @property
    def peak_device_bytes(self) -> int:
        """Max ``peak_bytes_in_use`` across devices (0 when unavailable)."""
        return max(
            (m.get("peak_bytes_in_use", 0) for m in self.device_memory.values()),
            default=0,
        )

    def to_dict(self) -> dict:
        return {
            "type": "fit_report",
            "schema": self.schema,
            "estimator": self.estimator,
            "uid": self.uid,
            "fit_id": self.fit_id,
            "overlap_fraction": self.overlap_fraction,
            "timestamp_unix": self.timestamp_unix,
            "wall_seconds": self.wall_seconds,
            "phases": self.phases,
            "rows_ingested": self.rows_ingested,
            "bytes_ingested": self.bytes_ingested,
            "h2d_bytes": self.h2d_bytes,
            "collectives": self.collectives,
            "compile": self.compile,
            "device_memory": self.device_memory,
            "peak_device_bytes": self.peak_device_bytes,
            "counters": self.counters,
            "cost_model": self.cost_model,
            "health": self.health,
            "admission": self.admission,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "FitReport":
        return cls(
            estimator=d.get("estimator", ""),
            uid=d.get("uid", ""),
            wall_seconds=float(d.get("wall_seconds", 0.0)),
            phases=d.get("phases", {}),
            rows_ingested=int(d.get("rows_ingested", 0)),
            bytes_ingested=int(d.get("bytes_ingested", 0)),
            h2d_bytes=int(d.get("h2d_bytes", 0)),
            collectives=d.get("collectives", {}),
            compile=d.get("compile", {}),
            device_memory=d.get("device_memory", {}),
            counters=d.get("counters", {}),
            timestamp_unix=float(d.get("timestamp_unix", 0.0)),
            fit_id=d.get("fit_id", ""),
            overlap_fraction=d.get("overlap_fraction"),
            cost_model=d.get("cost_model", {}) or {},
            health=d.get("health", {}) or {},
            admission=d.get("admission", {}) or {},
            schema=int(d.get("schema", SCHEMA_VERSION)),
        )


class _FitCapture:
    __slots__ = (
        "estimator", "uid", "token", "snap", "t0", "t_unix",
        "fit_id", "fit_id_token", "tl_seq", "admission",
    )

    def __init__(
        self, estimator: str, uid: str, token, snap, t0: float,
        fit_id: str, fit_id_token, tl_seq: int,
        admission: dict | None = None,
    ):
        self.estimator = estimator
        self.uid = uid
        self.token = token
        self.snap = snap
        self.t0 = t0
        self.t_unix = time.time()
        self.fit_id = fit_id
        self.fit_id_token = fit_id_token
        self.tl_seq = tl_seq
        self.admission = admission or {}


def begin_fit(estimator: str, uid: str = "") -> _FitCapture:
    """Open a capture window: place the compile cache and install the
    compile listeners and the fit_id log filter (first call only), snapshot
    the registry and the timeline watermark, mint a fit_id, and label
    subsequent spans with the estimator name."""
    enable_compilation_cache()
    compilemon.install_monitoring()
    spans.install_fit_id_filter()
    # with TPU_ML_HTTP_PORT set, the first fit brings up the /metrics +
    # /healthz exporter and the health monitor (lazy import: httpd reads
    # this module's recent-reports ring)
    from spark_rapids_ml_tpu.telemetry import httpd

    httpd.ensure_started()
    # health-driven admission control: while a component is FAILING, the
    # fit is refused (default) or pinned to the CPU-degraded path for its
    # whole window — the decision rides on the report either way
    from spark_rapids_ml_tpu.telemetry import health as health_mod

    admission = health_mod.admission_check()
    if admission["action"] == "refuse":
        raise health_mod.AdmissionRefused(
            f"fit of {estimator} refused by admission control: "
            f"{admission['reason']} (set {health_mod.ADMISSION_POLICY_VAR}="
            "degrade/off to override)"
        )
    if admission["action"] == "degrade":
        health_mod.begin_degrade_window()
    fit_id = uuid.uuid4().hex[:12]
    return _FitCapture(
        estimator=estimator,
        uid=uid,
        token=spans.set_current_estimator(estimator),
        snap=REGISTRY.snapshot(),
        t0=time.perf_counter(),
        fit_id=fit_id,
        fit_id_token=spans.set_current_fit_id(fit_id),
        tl_seq=TIMELINE.seq(),
        admission=admission,
    )


# Ring of the most recent report dicts (fit and transform), served by the
# HTTP exporter's /report endpoint. Bounded; lock-guarded (reports finish on
# whatever thread ran the fit).
_REPORTS_LOCK = threading.Lock()
_RECENT_REPORTS: collections.deque = collections.deque(maxlen=16)


def _remember_report(d: dict) -> None:
    with _REPORTS_LOCK:
        _RECENT_REPORTS.append(d)


def recent_reports() -> list[dict]:
    """The latest report dicts, oldest first (the ``/report`` payload)."""
    with _REPORTS_LOCK:
        return list(_RECENT_REPORTS)


# counters folded into dedicated report fields; everything else lands in
# FitReport.counters verbatim
_INGEST_ROWS = "ingest.rows"
_INGEST_BYTES = "ingest.bytes"
_COLUMNAR_ROWS = "columnar.rows"
_COLUMNAR_BYTES = "columnar.bytes"


def end_fit(cap: _FitCapture) -> FitReport:
    """Close a capture window and build the report from the delta. Always
    call (a ``finally`` in the fit wrapper) so the estimator span label is
    restored even when the fit raised."""
    wall = time.perf_counter() - cap.t0
    spans.reset_current_estimator(cap.token)
    spans.reset_current_fit_id(cap.fit_id_token)
    from spark_rapids_ml_tpu.telemetry import health as health_mod

    if cap.admission.get("action") == "degrade":
        health_mod.end_degrade_window()
    device_memory = compilemon.sample_device_memory()
    delta = REGISTRY.snapshot().delta(cap.snap)

    # mean per-stream overlap fraction recorded by stream_fold; None when
    # the fit never streamed (resident path, plain array fits)
    ov = delta.hist("stream.overlap_fraction")
    overlap_fraction = (ov.total / ov.count) if ov.count else None

    health = health_mod.current_summary()

    ingest_rows = int(delta.counter(_INGEST_ROWS))
    ingest_bytes = int(delta.counter(_INGEST_BYTES))
    # the streamed/mesh ingest layer re-extracts through columnar, so when
    # it ran, its counters are THE data-path numbers; pure in-core fits only
    # ever touch the columnar extractors
    rows = ingest_rows or int(delta.counter(_COLUMNAR_ROWS))
    nbytes = ingest_bytes or int(delta.counter(_COLUMNAR_BYTES))

    compile_hist = delta.hist("compile.seconds")
    counters = {
        render_key(k): v
        for k, v in sorted(delta.counters.items())
        if k[0]
        not in (_INGEST_ROWS, _INGEST_BYTES, _COLUMNAR_ROWS, _COLUMNAR_BYTES)
        and not k[0].startswith(
            ("compile.", "collective.", "h2d.", "costmodel.")
        )
    }
    report = FitReport(
        estimator=cap.estimator,
        uid=cap.uid,
        wall_seconds=wall,
        phases=delta.phase_table(),
        rows_ingested=rows,
        bytes_ingested=nbytes,
        h2d_bytes=int(delta.counter("h2d.bytes")),
        collectives={
            "count": delta.counter("collective.count"),
            "bytes": delta.counter("collective.bytes"),
            "tree_combines": delta.counter("collective.tree_combines"),
        },
        compile={
            "count": compile_hist.count,
            "seconds": compile_hist.total,
            "trace_seconds": delta.hist("compile.trace_seconds").total,
            "lower_seconds": delta.hist("compile.lower_seconds").total,
            "cache_hits": delta.counter("compile.cache_hits"),
            "cache_misses": delta.counter("compile.cache_misses"),
            "cache_time_saved_s": delta.counter("compile.cache_time_saved_s"),
            "cache_load_seconds": delta.hist("compile.cache_load_seconds").total,
        },
        device_memory=device_memory,
        counters=counters,
        timestamp_unix=cap.t_unix,
        fit_id=cap.fit_id,
        overlap_fraction=overlap_fraction,
        cost_model=costmodel.window_summary(delta, wall),
        health=health,
        admission=cap.admission,
    )
    _remember_report(report.to_dict())
    return report


@dataclass
class TransformReport:
    """Everything observed during one ``transform()`` call — the serve-side
    sibling of :class:`FitReport`.

    ``partitions`` maps partition label (``"0"``, ``"1"``, ... for
    localspark workers, ``"driver"`` for in-process execution) →
    ``{rows, bytes, seconds, batches}`` accumulated by the instrumented
    arrow partition functions. ``partition_latency`` is the merged
    ``transform.partition_seconds`` histogram (count/sum/min/max/p50/p90/
    p99) — per-partition-call latency across all partitions. For lazy
    plans (localspark ``mapInArrow``), ``wall_seconds`` spans transform()
    entry through first full materialization of the returned DataFrame.
    """

    transformer: str
    uid: str
    wall_seconds: float
    phases: dict[str, dict[str, float]] = field(default_factory=dict)
    rows: int = 0
    bytes: int = 0
    partitions: dict[str, dict[str, float]] = field(default_factory=dict)
    partition_latency: dict[str, float] = field(default_factory=dict)
    cost_model: dict = field(default_factory=dict)
    counters: dict[str, float] = field(default_factory=dict)
    timestamp_unix: float = 0.0
    # log↔report join key, stamped as %(transform_id)s on package log
    # records emitted inside the window (including lazy materialization)
    transform_id: str = ""
    schema: int = TRANSFORM_SCHEMA_VERSION

    def to_dict(self) -> dict:
        return {
            "type": "transform_report",
            "schema": self.schema,
            "transformer": self.transformer,
            "uid": self.uid,
            "transform_id": self.transform_id,
            "timestamp_unix": self.timestamp_unix,
            "wall_seconds": self.wall_seconds,
            "phases": self.phases,
            "rows": self.rows,
            "bytes": self.bytes,
            "partitions": self.partitions,
            "partition_latency": self.partition_latency,
            "cost_model": self.cost_model,
            "counters": self.counters,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TransformReport":
        return cls(
            transformer=d.get("transformer", ""),
            uid=d.get("uid", ""),
            wall_seconds=float(d.get("wall_seconds", 0.0)),
            phases=d.get("phases", {}),
            rows=int(d.get("rows", 0)),
            bytes=int(d.get("bytes", 0)),
            partitions=d.get("partitions", {}),
            partition_latency=d.get("partition_latency", {}),
            cost_model=d.get("cost_model", {}) or {},
            counters=d.get("counters", {}),
            timestamp_unix=float(d.get("timestamp_unix", 0.0)),
            transform_id=d.get("transform_id", ""),
            schema=int(d.get("schema", TRANSFORM_SCHEMA_VERSION)),
        )


class _TransformCapture:
    __slots__ = (
        "transformer", "uid", "token", "snap", "t0", "t_unix",
        "transform_id", "transform_id_token", "tl_seq", "released",
    )

    def __init__(
        self, transformer: str, uid: str, token, snap, t0: float,
        transform_id: str, transform_id_token, tl_seq: int,
    ):
        self.transformer = transformer
        self.uid = uid
        self.token = token
        self.snap = snap
        self.t0 = t0
        self.t_unix = time.time()
        self.transform_id = transform_id
        self.transform_id_token = transform_id_token
        self.tl_seq = tl_seq
        self.released = False


def begin_transform(transformer: str, uid: str = "") -> _TransformCapture:
    """Open a serve-side capture window: mirror of :func:`begin_fit` minting
    a ``transform_id`` instead of a ``fit_id``."""
    enable_compilation_cache()
    compilemon.install_monitoring()
    spans.install_fit_id_filter()
    transform_id = uuid.uuid4().hex[:12]
    return _TransformCapture(
        transformer=transformer,
        uid=uid,
        token=spans.set_current_estimator(transformer),
        snap=REGISTRY.snapshot(),
        t0=time.perf_counter(),
        transform_id=transform_id,
        transform_id_token=spans.set_current_transform_id(transform_id),
        tl_seq=TIMELINE.seq(),
    )


def release_transform_context(cap: _TransformCapture) -> None:
    """Restore the estimator/transform_id contextvars (idempotent).

    Split out of :func:`end_transform` because lazy plans finalize their
    report from a *different* execution context (the DataFrame's
    materialization) where the original tokens are unusable — the wrapper
    resets them at transform() exit, the report is built later.
    """
    if cap.released:
        return
    cap.released = True
    try:
        spans.reset_current_estimator(cap.token)
        spans.reset_current_transform_id(cap.transform_id_token)
    except ValueError:  # pragma: no cover - reset from a foreign Context
        spans.set_current_estimator(None)
        spans.set_current_transform_id(None)


def end_transform(cap: _TransformCapture) -> TransformReport:
    """Close a serve-side capture window and build the report from the
    registry delta. Per-partition rows/bytes/seconds come from the
    ``transform.*`` counters/histograms the instrumented arrow partition
    functions recorded — worker-side values arrive with a ``partition=N``
    label via the localspark telemetry trailer; unlabeled values (in-process
    execution) are booked under ``"driver"``."""
    wall = time.perf_counter() - cap.t0
    release_transform_context(cap)
    delta = REGISTRY.snapshot().delta(cap.snap)

    partitions: dict[str, dict[str, float]] = {}

    def _bucket(labels) -> dict[str, float]:
        part = dict(labels).get("partition", "") or "driver"
        return partitions.setdefault(
            part, {"rows": 0, "bytes": 0, "seconds": 0.0, "batches": 0}
        )

    counter_fields = {
        "transform.rows": "rows",
        "transform.bytes": "bytes",
        "transform.batches": "batches",
    }
    for (name, labels), v in delta.counters.items():
        dest = counter_fields.get(name)
        if dest is not None:
            _bucket(labels)[dest] += int(v)
    for (name, labels), h in delta.hists.items():
        if name == "transform.partition_seconds":
            b = _bucket(labels)
            b["seconds"] += h.total

    rows = int(delta.counter("transform.rows"))
    nbytes = int(delta.counter("transform.bytes"))
    if not rows:  # in-core array transforms never run a partition fn
        rows = int(delta.counter(_COLUMNAR_ROWS))
        nbytes = nbytes or int(delta.counter(_COLUMNAR_BYTES))

    counters = {
        render_key(k): v
        for k, v in sorted(delta.counters.items())
        if not k[0].startswith(
            ("transform.", "compile.", "collective.", "h2d.", "costmodel.")
        )
        and k[0]
        not in (_INGEST_ROWS, _INGEST_BYTES, _COLUMNAR_ROWS, _COLUMNAR_BYTES)
    }
    report = TransformReport(
        transformer=cap.transformer,
        uid=cap.uid,
        wall_seconds=wall,
        phases=delta.phase_table(),
        rows=rows,
        bytes=nbytes,
        partitions=partitions,
        partition_latency=delta.hist("transform.partition_seconds").to_dict(),
        cost_model=costmodel.window_summary(delta, wall),
        counters=counters,
        timestamp_unix=cap.t_unix,
        transform_id=cap.transform_id,
    )
    _remember_report(report.to_dict())
    return report


def attach_transform_report(model: Any, report: TransformReport) -> None:
    """Best-effort ``model.transform_report = report`` (mirror of
    :func:`attach_report`)."""
    try:
        model.transform_report = report
    except (AttributeError, TypeError):  # pragma: no cover - exotic models
        pass


def snapshot_dict(percentiles=(50, 90, 99)) -> dict:
    """The full registry state as a JSON-shaped dict — what ``bench.py``
    embeds in its emitted line so rounds are phase-attributable."""
    return REGISTRY.snapshot().to_dict(percentiles)


def attach_report(model: Any, report: FitReport) -> None:
    """Best-effort ``model.fit_report = report`` (never breaks a fit over a
    slots/frozen model class)."""
    try:
        model.fit_report = report
    except (AttributeError, TypeError):  # pragma: no cover - exotic models
        pass
