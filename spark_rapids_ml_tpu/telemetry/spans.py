"""Tracing / profiling annotations — the NVTX-range analog, registry-backed.

The reference wraps its two training phases in NVTX ranges visible in Nsight
(``NvtxRange("compute cov", RED)`` / ``NvtxRange("cuSolver SVD", BLUE)``,
RapidsRowMatrix.scala:62,70). On TPU the equivalent surface is xprof /
TensorBoard: ``jax.profiler.TraceAnnotation`` marks host spans and
``jax.named_scope`` tags the traced HLO so the phases are findable in a
device profile. ``trace_range`` layers both, plus wall-clock accounting into
the telemetry registry as a ``span.seconds`` histogram labeled with the
phase name and the estimator currently fitting (set by the ``models.base``
fit instrumentation) — so one fit later reads back as per-phase latency
percentiles, not just sums.

Spans know what caused them: a context variable holds the open span's frame,
a closing span adds its seconds to its parent's frame, and beside
``span.seconds`` it books ``span.self_seconds`` — its duration less what its
child spans (same context, so same thread) covered. An outer span's self time
is the seconds of it that no narrower span names.

Accounting is in a ``finally`` block: a body that raises still books its
elapsed time (a fit that dies 40 s into ``compute cov`` must show those
40 s, or the post-mortem blames the wrong phase).
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import time

from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE

logger = logging.getLogger("spark_rapids_ml_tpu")

# Which estimator's fit() this thread/context is inside — stamps every span
# recorded during the fit so phase latencies group by estimator without each
# trace_range call site threading a label through.
_current_estimator: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "tpu_ml_current_estimator", default=None
)

# The fit_id of the same window — stamped into timeline events AND every
# package log record (via _FitIdFilter), so `grep <fit_id>` joins the log
# stream with the JSONL report of one specific fit.
_current_fit_id: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "tpu_ml_current_fit_id", default=None
)

# The transform_id of the serve-side window — the transform-path sibling of
# fit_id, minted by models.base transform instrumentation and stamped into
# timeline events and log records for the lifetime of one transform (through
# lazy localspark materialization).
_current_transform_id: contextvars.ContextVar[str | None] = (
    contextvars.ContextVar("tpu_ml_current_transform_id", default=None)
)


class _Frame:
    """An open span: its name, and the seconds its closed children took."""

    __slots__ = ("name", "child_seconds")

    def __init__(self, name: str):
        self.name = name
        self.child_seconds = 0.0


# The innermost span open in this context (None outside any). A new thread
# starts with an empty context, so a span opened there has no parent and
# takes nothing off a span of the thread that started it.
_open_span: contextvars.ContextVar[_Frame | None] = contextvars.ContextVar(
    "tpu_ml_open_span", default=None
)


def current_span() -> str:
    """The name of the innermost span open in this context ("" outside
    any): what work handed to another thread names as its cause."""
    frame = _open_span.get()
    return frame.name if frame is not None else ""


def current_estimator() -> str | None:
    return _current_estimator.get()


def set_current_estimator(name: str | None):
    """Returns the reset token (contextvars protocol)."""
    return _current_estimator.set(name)


def reset_current_estimator(token) -> None:
    _current_estimator.reset(token)


def current_fit_id() -> str | None:
    return _current_fit_id.get()


def set_current_fit_id(fit_id: str | None):
    """Returns the reset token (contextvars protocol)."""
    return _current_fit_id.set(fit_id)


def reset_current_fit_id(token) -> None:
    _current_fit_id.reset(token)


def current_transform_id() -> str | None:
    return _current_transform_id.get()


def set_current_transform_id(transform_id: str | None):
    """Returns the reset token (contextvars protocol)."""
    return _current_transform_id.set(transform_id)


def reset_current_transform_id(token) -> None:
    _current_transform_id.reset(token)


class _FitIdFilter(logging.Filter):
    """Stamps ``record.fit_id`` and ``record.transform_id`` (the current
    window ids, or ``"-"``) onto every record of the package logger, so a
    format string with ``%(fit_id)s`` / ``%(transform_id)s`` correlates log
    lines with exported Fit/TransformReports. A Filter rather than a
    LoggerAdapter: it covers every module-level ``logger`` in the package
    without changing any call site."""

    def filter(self, record: logging.LogRecord) -> bool:
        record.fit_id = _current_fit_id.get() or "-"
        record.transform_id = _current_transform_id.get() or "-"
        return True


def install_fit_id_filter() -> None:
    """Attach the fit_id filter to the package logger (idempotent)."""
    pkg = logging.getLogger("spark_rapids_ml_tpu")
    if not any(isinstance(f, _FitIdFilter) for f in pkg.filters):
        pkg.addFilter(_FitIdFilter())


@contextlib.contextmanager
def trace_range(name: str):
    """Host+device trace span with registry-backed latency accounting."""
    # deferred so importing telemetry (and through it columnar/ingest, which
    # run in jax-free worker ingestion processes) never pulls in jax; after
    # the first call this is one sys.modules lookup
    import jax

    parent = _open_span.get()
    frame = _Frame(name)
    token = _open_span.set(frame)
    start = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name), jax.named_scope(name):
            yield
    finally:
        end = time.perf_counter()
        _open_span.reset(token)
        elapsed = end - start
        if parent is not None:
            parent.child_seconds += elapsed
        estimator = _current_estimator.get() or ""
        REGISTRY.histogram_record(
            "span.seconds", elapsed, phase=name, estimator=estimator
        )
        REGISTRY.histogram_record(
            "span.self_seconds",
            max(0.0, elapsed - frame.child_seconds),
            phase=name,
            estimator=estimator,
        )
        TIMELINE.record_span(
            name,
            start,
            end,
            parent=parent.name if parent is not None else "",
            estimator=estimator,
            fit_id=_current_fit_id.get() or "",
            transform_id=_current_transform_id.get() or "",
        )
        logger.debug("trace %s: %.3fs", name, elapsed)
