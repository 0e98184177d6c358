"""Thread-safe metrics registry: counters, gauges, log-scale histograms.

The reference has no metrics story at all — its observability is two NVTX
ranges (RapidsRowMatrix.scala:62,70) visible only inside an attached Nsight
session. This registry is the process-local aggregation point the framework
reports through instead: every span, byte count, collective and compile
event lands here, keyed by metric name plus a small label set
(``estimator``, ``phase``, ``device``), and the whole state snapshots into
plain dicts for the JSONL sink (:mod:`.export`), the ``FitReport``
delta capture (:mod:`.report`) and the bench record.

Design constraints that shaped it:

- **Lock-guarded, not lock-free** — localspark partition tasks run on a
  thread pool (``parallel.executor``) and all record into one registry; a
  plain ``dict``/``list`` accumulation corrupts counts under that load
  (ISSUE 2 satellite). One ``RLock`` around tiny dict updates is far below
  the cost of anything being measured.
- **Log-scale histograms, not sums** — a span that runs 1000× tells you
  nothing from its total. Buckets grow by ``2**0.25`` (~19% resolution, 4
  buckets per octave), so percentiles over any latency range cost O(1)
  memory and never need the raw samples. Count/sum/min/max are tracked
  exactly; only the quantiles are bucket-resolution approximations.
- **Snapshot/delta algebra** — ``FitReport`` needs "what happened during
  THIS fit" while the registry accumulates per-process. Histograms and
  counters both support subtraction, so a fit is bracketed by two
  snapshots and reported as the difference.
"""

from __future__ import annotations

import math
import os
import threading

from spark_rapids_ml_tpu.utils import knobs


def _exemplar_budget() -> int:
    """Slowest-sample exemplars retained per histogram series
    (``TPU_ML_TRACE_EXEMPLARS``); consulted only on records that carry an
    exemplar, so untraced hot paths never read the environment."""
    raw = os.environ.get(knobs.TRACE_EXEMPLARS.name, "")
    try:
        budget = int(raw) if raw else int(knobs.TRACE_EXEMPLARS.default)
    except ValueError:
        budget = int(knobs.TRACE_EXEMPLARS.default)
    return max(budget, 0)

# Bucket boundaries at GROWTH**i: 4 buckets per power of two keeps the
# worst-case quantile error under ~9.5% (half a bucket in log space) while
# a span living anywhere from 1 µs to 1 h stays under ~130 live buckets.
GROWTH = 2.0 ** 0.25
_LOG_GROWTH = math.log(GROWTH)
# values <= 0 land in a dedicated bucket so records of 0.0 (legal for byte
# counts) never hit math.log
_ZERO_BUCKET = -(1 << 30)


class Histogram:
    """Log-scale histogram with exact count/sum/min/max.

    Not internally locked — the registry serializes access; standalone use
    (tests, single-threaded tools) is safe as-is.
    """

    __slots__ = ("count", "total", "vmin", "vmax", "buckets")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf
        self.buckets: dict[int, int] = {}

    @staticmethod
    def bucket_index(value: float) -> int:
        if value <= 0.0:
            return _ZERO_BUCKET
        return math.floor(math.log(value) / _LOG_GROWTH)

    def record(self, value: float) -> None:
        value = float(value)
        self.count += 1
        self.total += value
        if value < self.vmin:
            self.vmin = value
        if value > self.vmax:
            self.vmax = value
        idx = self.bucket_index(value)
        self.buckets[idx] = self.buckets.get(idx, 0) + 1

    def percentile(self, q: float) -> float:
        """The q-th percentile (0..100) as the geometric midpoint of the
        bucket holding that rank, clamped to the exact [min, max] — so p0
        and p100 are exact and interior quantiles are within half a bucket
        (~9.5%) in log space."""
        if self.count == 0:
            return 0.0
        rank = max(1, math.ceil(q / 100.0 * self.count))
        cum = 0
        for idx in sorted(self.buckets):
            cum += self.buckets[idx]
            if cum >= rank:
                if idx == _ZERO_BUCKET:
                    return 0.0
                mid = math.exp((idx + 0.5) * _LOG_GROWTH)
                return min(max(mid, self.vmin), self.vmax)
        return self.vmax  # unreachable unless buckets/count disagree

    def copy(self) -> "Histogram":
        h = Histogram()
        h.count = self.count
        h.total = self.total
        h.vmin = self.vmin
        h.vmax = self.vmax
        h.buckets = dict(self.buckets)
        return h

    def delta(self, prev: "Histogram | None") -> "Histogram":
        """This histogram minus an earlier snapshot of the same series.

        min/max cannot be un-merged, so the delta keeps the current
        extremes — still correct bounds for the interval, just not tight
        ones when the earlier window held the extreme value.
        """
        if prev is None:
            return self.copy()
        h = Histogram()
        h.count = self.count - prev.count
        h.total = self.total - prev.total
        h.vmin = self.vmin
        h.vmax = self.vmax
        h.buckets = {
            k: v - prev.buckets.get(k, 0)
            for k, v in self.buckets.items()
            if v - prev.buckets.get(k, 0)
        }
        if h.count <= 0:
            return Histogram()
        return h

    def to_dict(self, percentiles=(50, 90, 99)) -> dict[str, float]:
        if self.count == 0:
            return {"count": 0, "sum": 0.0}
        out = {
            "count": self.count,
            "sum": self.total,
            "min": self.vmin,
            "max": self.vmax,
        }
        for q in percentiles:
            out[f"p{q}"] = self.percentile(q)
        return out

    def to_wire(self) -> dict:
        """JSON-safe full state (buckets included — unlike ``to_dict``,
        this round-trips): the worker→driver telemetry trailer payload.
        Bucket keys are stringified for JSON; infinities (empty histogram
        extremes) are omitted rather than serialized."""
        out: dict = {
            "count": self.count,
            "total": self.total,
            "buckets": {str(k): v for k, v in self.buckets.items()},
        }
        if self.count:
            out["vmin"] = self.vmin
            out["vmax"] = self.vmax
        return out

    def merge_wire(self, wire: dict) -> None:
        """Fold a ``to_wire`` payload into this histogram."""
        self.count += int(wire.get("count", 0))
        self.total += float(wire.get("total", 0.0))
        if "vmin" in wire:
            self.vmin = min(self.vmin, float(wire["vmin"]))
        if "vmax" in wire:
            self.vmax = max(self.vmax, float(wire["vmax"]))
        for k, v in (wire.get("buckets") or {}).items():
            idx = int(k)
            self.buckets[idx] = self.buckets.get(idx, 0) + int(v)


def _key(name: str, labels: dict) -> tuple:
    return (name, tuple(sorted((k, v) for k, v in labels.items() if v)))


def render_key(key: tuple) -> str:
    """``name{label=value,...}`` — the flat string form snapshots export."""
    name, labels = key
    if not labels:
        return name
    return name + "{" + ",".join(f"{k}={v}" for k, v in labels) + "}"


def _prom_escape(v) -> str:
    """Prometheus label-value escaping (backslash, quote, newline)."""
    return (
        str(v)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


class MetricsRegistry:
    """The process-local metric store. All mutation goes through a lock."""

    def __init__(self):
        self._lock = threading.RLock()
        self._counters: dict[tuple, float] = {}
        self._gauges: dict[tuple, float] = {}
        self._hists: dict[tuple, Histogram] = {}
        # per-series slowest-sample exemplars: key -> [(value, trace_id)]
        # descending by value, capped at TPU_ML_TRACE_EXEMPLARS
        self._exemplars: dict[tuple, list] = {}

    # -- mutation -----------------------------------------------------------

    def counter_inc(self, name: str, value: float = 1, **labels) -> None:
        k = _key(name, labels)
        with self._lock:
            self._counters[k] = self._counters.get(k, 0) + value

    def gauge_set(self, name: str, value: float, **labels) -> None:
        with self._lock:
            self._gauges[_key(name, labels)] = value

    def histogram_record(
        self, name: str, value: float, exemplar: str = "", **labels
    ) -> None:
        k = _key(name, labels)
        with self._lock:
            h = self._hists.get(k)
            if h is None:
                h = self._hists[k] = Histogram()
            h.record(value)
            if exemplar:
                self._exemplar_add(k, float(value), exemplar)

    def _exemplar_add(self, k: tuple, value: float, exemplar: str) -> None:
        """Keep the top-K slowest (value, trace_id) pairs per series —
        how a p99 bucket stays attributable to actual traces. Caller
        holds the lock."""
        budget = _exemplar_budget()
        if budget <= 0:
            return
        ex = self._exemplars.setdefault(k, [])
        if len(ex) >= budget and value <= ex[-1][0]:
            return
        ex.append((value, exemplar))
        ex.sort(key=lambda pair: -pair[0])
        del ex[budget:]

    def merge_wire(self, wire: dict, **extra_labels) -> None:
        """Fold a :meth:`RegistrySnapshot.to_wire` payload — typically a
        worker's registry delta shipped over the task protocol — into this
        registry, stamping ``extra_labels`` (e.g. ``partition="3"``) onto
        every merged series so driver-side reads attribute them."""
        extra = {k: v for k, v in extra_labels.items() if v}
        with self._lock:
            for name, labels, value in wire.get("counters", ()):
                k = _key(name, {**labels, **extra})
                self._counters[k] = self._counters.get(k, 0) + value
            for name, labels, value in wire.get("gauges", ()):
                self._gauges[_key(name, {**labels, **extra})] = value
            for name, labels, hwire in wire.get("hists", ()):
                k = _key(name, {**labels, **extra})
                h = self._hists.get(k)
                if h is None:
                    h = self._hists[k] = Histogram()
                h.merge_wire(hwire)
            for name, labels, pairs in wire.get("exemplars", ()):
                k = _key(name, {**labels, **extra})
                for value, trace_id in pairs:
                    self._exemplar_add(k, float(value), str(trace_id))

    def to_prometheus(self) -> str:
        """Current state in the Prometheus text exposition format."""
        return self.snapshot().to_prometheus()

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._exemplars.clear()

    # -- read ---------------------------------------------------------------

    def snapshot(self) -> "RegistrySnapshot":
        with self._lock:
            return RegistrySnapshot(
                counters=dict(self._counters),
                gauges=dict(self._gauges),
                hists={k: h.copy() for k, h in self._hists.items()},
                exemplars={
                    k: list(v) for k, v in self._exemplars.items()
                },
            )

    def span_totals(self) -> dict[str, dict[str, float]]:
        """Read shape of the removed ``utils.tracing`` module's
        ``metrics()``: per-span-name wall totals and counts, aggregated
        over every other label."""
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            for (name, labels), h in self._hists.items():
                if name != "span.seconds":
                    continue
                phase = dict(labels).get("phase", "")
                m = out.setdefault(phase, {"seconds": 0.0, "count": 0})
                m["seconds"] += h.total
                m["count"] += h.count
        return out


class RegistrySnapshot:
    """Immutable-ish copy of registry state; supports delta and JSON dump."""

    def __init__(self, counters, gauges, hists, exemplars=None):
        self.counters = counters
        self.gauges = gauges
        self.hists = hists
        self.exemplars = exemplars or {}

    def delta(self, prev: "RegistrySnapshot | None") -> "RegistrySnapshot":
        if prev is None:
            return self
        counters = {
            k: v - prev.counters.get(k, 0)
            for k, v in self.counters.items()
            if v - prev.counters.get(k, 0)
        }
        hists = {}
        for k, h in self.hists.items():
            d = h.delta(prev.hists.get(k))
            if d.count:
                hists[k] = d
        # exemplars are a top-K sample, not cumulative — the window keeps
        # the current extremes for every series live in the window
        exemplars = {k: v for k, v in self.exemplars.items() if k in hists}
        return RegistrySnapshot(
            counters=counters, gauges=dict(self.gauges), hists=hists,
            exemplars=exemplars,
        )

    def counter(self, name: str, **labels) -> float:
        """Sum of a counter across label sets; with labels given, the exact
        series only."""
        if labels:
            return self.counters.get(_key(name, labels), 0)
        return sum(v for (n, _), v in self.counters.items() if n == name)

    def exemplars_for(self, name: str, **labels) -> list:
        """Merged slowest-sample exemplars for ``name`` across matching
        label sets: ``[(value, trace_id), ...]`` descending by value."""
        want = tuple(sorted((k, v) for k, v in labels.items() if v))
        merged: list = []
        for (n, lbl), pairs in self.exemplars.items():
            if n != name:
                continue
            if want and not set(want).issubset(set(lbl)):
                continue
            merged.extend(pairs)
        merged.sort(key=lambda pair: -pair[0])
        return merged

    def hist(self, name: str, **labels) -> Histogram:
        """Merged histogram for ``name`` across matching label sets."""
        merged = Histogram()
        want = tuple(sorted((k, v) for k, v in labels.items() if v))
        for (n, lbl), h in self.hists.items():
            if n != name:
                continue
            if want and not set(want).issubset(set(lbl)):
                continue
            merged.count += h.count
            merged.total += h.total
            merged.vmin = min(merged.vmin, h.vmin)
            merged.vmax = max(merged.vmax, h.vmax)
            for k, v in h.buckets.items():
                merged.buckets[k] = merged.buckets.get(k, 0) + v
        return merged

    def phase_table(self, percentiles=(50, 90, 99)) -> dict[str, dict[str, float]]:
        """Per-phase span statistics (the FitReport/trace-report payload):
        ``{phase: {count, sum, self, min, max, p50, p90, p99}}`` aggregated
        over the estimator label; ``self`` is the phase's seconds that no
        child span covered (``span.self_seconds``)."""
        phases: dict[str, Histogram] = {}
        self_s: dict[str, float] = {}
        for (name, labels), h in self.hists.items():
            if name not in ("span.seconds", "span.self_seconds"):
                continue
            phase = dict(labels).get("phase", "")
            if name == "span.self_seconds":
                self_s[phase] = self_s.get(phase, 0.0) + h.total
                continue
            if phase in phases:
                m = phases[phase]
                m.count += h.count
                m.total += h.total
                m.vmin = min(m.vmin, h.vmin)
                m.vmax = max(m.vmax, h.vmax)
                for k, v in h.buckets.items():
                    m.buckets[k] = m.buckets.get(k, 0) + v
            else:
                phases[phase] = h.copy()
        return {
            p: {**h.to_dict(percentiles), "self": self_s.get(p, h.total)}
            for p, h in sorted(phases.items())
        }

    def to_wire(self) -> dict:
        """JSON-safe lossless form — labels kept structured, histogram
        buckets included — for the worker→driver telemetry trailer. The
        receiving side replays it with :meth:`MetricsRegistry.merge_wire`.
        """
        return {
            "counters": [
                [name, dict(labels), v]
                for (name, labels), v in sorted(self.counters.items())
            ],
            "gauges": [
                [name, dict(labels), v]
                for (name, labels), v in sorted(self.gauges.items())
            ],
            "hists": [
                [name, dict(labels), h.to_wire()]
                for (name, labels), h in sorted(self.hists.items())
            ],
            "exemplars": [
                [name, dict(labels), [[v, t] for v, t in pairs]]
                for (name, labels), pairs in sorted(self.exemplars.items())
            ],
        }

    def to_prometheus(self) -> str:
        """The snapshot in the Prometheus text exposition format: counters
        and gauges verbatim, histograms as cumulative ``_bucket{le=...}``
        series (upper bound = the log-bucket's right edge) plus ``_sum`` /
        ``_count``. Metric names are sanitized to the Prometheus charset
        under a ``tpu_ml_`` prefix."""
        lines: list[str] = []

        def prom_name(name: str) -> str:
            return "tpu_ml_" + "".join(
                c if c.isalnum() or c == "_" else "_" for c in name
            )

        def prom_labels(labels, extra: str = "") -> str:
            parts = [f'{k}="{_prom_escape(v)}"' for k, v in labels]
            if extra:
                parts.append(extra)
            return "{" + ",".join(parts) + "}" if parts else ""

        by_name: dict[str, list] = {}
        for (name, labels), v in sorted(self.counters.items()):
            by_name.setdefault(name, []).append((labels, v))
        for name, series in by_name.items():
            pn = prom_name(name)
            lines.append(f"# TYPE {pn} counter")
            for labels, v in series:
                lines.append(f"{pn}{prom_labels(labels)} {v:g}")

        by_name = {}
        for (name, labels), v in sorted(self.gauges.items()):
            by_name.setdefault(name, []).append((labels, v))
        for name, series in by_name.items():
            pn = prom_name(name)
            lines.append(f"# TYPE {pn} gauge")
            for labels, v in series:
                lines.append(f"{pn}{prom_labels(labels)} {v:g}")

        by_name = {}
        for (name, labels), h in sorted(self.hists.items()):
            by_name.setdefault(name, []).append((labels, h))
        for name, series in by_name.items():
            pn = prom_name(name)
            lines.append(f"# TYPE {pn} histogram")
            for labels, h in series:
                cum = 0
                for idx in sorted(h.buckets):
                    cum += h.buckets[idx]
                    le = 0.0 if idx == _ZERO_BUCKET else GROWTH ** (idx + 1)
                    le_label = 'le="%g"' % le
                    lines.append(
                        f"{pn}_bucket{prom_labels(labels, le_label)} {cum}"
                    )
                inf_label = 'le="+Inf"'
                lines.append(
                    f"{pn}_bucket{prom_labels(labels, inf_label)} {h.count}"
                )
                lines.append(f"{pn}_sum{prom_labels(labels)} {h.total:g}")
                lines.append(f"{pn}_count{prom_labels(labels)} {h.count}")
        return "\n".join(lines) + ("\n" if lines else "")

    def to_dict(self, percentiles=(50, 90, 99)) -> dict:
        """Flat JSON form: rendered-key counters/gauges plus span and
        non-span histogram summaries."""
        return {
            "counters": {
                render_key(k): v for k, v in sorted(self.counters.items())
            },
            "gauges": {render_key(k): v for k, v in sorted(self.gauges.items())},
            "spans": self.phase_table(percentiles),
            "histograms": {
                render_key(k): h.to_dict(percentiles)
                for k, h in sorted(self.hists.items())
                if k[0] != "span.seconds"
            },
        }


# The ONE process-wide registry. Everything in the framework records here;
# tests and the bench reset it between measured regions.
REGISTRY = MetricsRegistry()

counter_inc = REGISTRY.counter_inc
gauge_set = REGISTRY.gauge_set
histogram_record = REGISTRY.histogram_record


def metrics() -> dict[str, dict[str, float]]:
    """Snapshot of accumulated span timings (legacy tracing shape)."""
    return REGISTRY.span_totals()


def reset_metrics() -> None:
    REGISTRY.reset()
