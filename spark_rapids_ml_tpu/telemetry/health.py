"""Live component health: a background monitor with a tiny state machine.

Nothing else inside the framework watches device health *while work runs*.
A daemon :class:`HealthMonitor` thread polls a fixed set of components every
``TPU_ML_HEALTH_INTERVAL_S`` seconds and rolls the results into
per-component states:

    OK (0) → DEGRADED (1) → FAILING (2)

Components and their evidence:

- ``device``      — HBM watermark from ``memory_stats()`` gauges
  (:func:`telemetry.compilemon.sample_device_memory`): DEGRADED above
  ``TPU_ML_HEALTH_HBM_WATERMARK`` of ``bytes_limit``.
- ``transport``   — a bounded-deadline liveness probe of the device runtime
  from inside this process (the process that holds the chip is the only one
  that can ask): ``inline`` (default) runs a cheap check on a throwaway
  thread; ``off`` disables. Consecutive failures escalate DEGRADED →
  FAILING after ``TPU_ML_HEALTH_FAILING_AFTER`` polls. The inline probe
  passes the ``device.init`` fault gate, so a chaos plan's injected hang
  exercises the timeout path end to end.
- ``stream``      — streamed-fit heartbeat staleness: ``spark.ingest``
  stamps ``stream.last_beat`` per dispatch and ``stream.active`` around
  each stream; a beat older than ``TPU_ML_HEALTH_STALE_S`` while a stream
  is active degrades (then fails after the consecutive threshold).
- ``workers``     — localspark trailer recency (``worker.last_trailer``,
  stamped by the session on every merged trailer).
- ``resilience``  — windowed signals from the resilience layer: a
  ``retry.attempts`` delta ≥ ``TPU_ML_HEALTH_RETRY_STORM`` per poll
  (retry storm), any ``degraded.cpu_fallback``, or fault injection
  firing, each flag DEGRADED.
- ``scheduler``   — worker-slot supervision (``resilience.supervisor``):
  any quarantined slot (``worker.quarantined`` gauge) is DEGRADED; every
  slot quarantined is FAILING — the session cannot run a stage.

The monitor also feeds **admission control**: :func:`admission_check`
consults the rollup before a fit starts and — per
``TPU_ML_ADMISSION_POLICY`` — refuses (:class:`AdmissionRefused`) or
CPU-degrades fits while any component is FAILING, instead of letting them
burn hours against a sick device.

Every state change sets ``health.state{component}``, counts
``health.transitions{component,to}`` and records a ``health.transition``
timeline instant — the flight recorder shows *when* a component sickened
relative to the chunks/retries around it. Each poll also drives the
sliding-window SLO engine (:mod:`.slo`), so breach detection runs at the
same cadence.

The module-level singleton (``start_monitor``/``get_monitor``/
``stop_monitor``) backs the HTTP exporter's ``/healthz`` and the
``health`` summary stamped onto FitReport schema 5.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from spark_rapids_ml_tpu.telemetry import compilemon
from spark_rapids_ml_tpu.telemetry import slo as slo_mod
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils import knobs

logger = logging.getLogger("spark_rapids_ml_tpu.health")

INTERVAL_VAR = knobs.HEALTH_INTERVAL_S.name
PROBE_VAR = knobs.HEALTH_PROBE.name
PROBE_TIMEOUT_VAR = knobs.HEALTH_PROBE_TIMEOUT_S.name
HBM_WATERMARK_VAR = knobs.HEALTH_HBM_WATERMARK.name
STALE_VAR = knobs.HEALTH_STALE_S.name
FAILING_AFTER_VAR = knobs.HEALTH_FAILING_AFTER.name
RETRY_STORM_VAR = knobs.HEALTH_RETRY_STORM.name
ADMISSION_POLICY_VAR = knobs.ADMISSION_POLICY.name

OK, DEGRADED, FAILING = 0, 1, 2
STATE_NAMES = {OK: "OK", DEGRADED: "DEGRADED", FAILING: "FAILING"}

COMPONENTS = (
    "device", "transport", "stream", "workers", "resilience", "scheduler",
)

PROBE_MODES = ("off", "inline")

ADMISSION_POLICIES = ("off", "refuse", "degrade")


class AdmissionRefused(RuntimeError):
    """A fit was refused admission because a health component is FAILING
    and ``TPU_ML_ADMISSION_POLICY=refuse`` (the default). Fix the failing
    component, stop the monitor, or set the policy to ``degrade``/``off``."""


def _env_float(var: str, default: float) -> float:
    try:
        return float(os.environ.get(var, "") or default)
    except ValueError:
        return default


def _env_int(var: str, default: int) -> int:
    try:
        return int(os.environ.get(var, "") or default)
    except ValueError:
        return default


def default_inline_probe() -> tuple[bool, str]:
    """The cheap in-process liveness check: pass the ``device.init`` fault
    gate (so chaos plans can wedge/err it deterministically) then sample
    device memory — which touches the initialized backend without ever
    *initiating* one, the same never-spin-up contract
    :func:`telemetry.compilemon.sample_device_memory` already keeps."""
    from spark_rapids_ml_tpu.resilience import faults, sites

    faults.inject(sites.DEVICE_INIT)
    stats = compilemon.sample_device_memory()
    return True, f"sampled {len(stats)} device(s)"


class HealthMonitor:
    """Periodic component health polling with OK/DEGRADED/FAILING rollup.

    Construction reads the ``TPU_ML_HEALTH_*`` knobs; every threshold is
    also injectable for tests. ``probe_fn`` replaces the inline probe body
    (still deadline-bounded by the monitor). Not started implicitly —
    call :meth:`start`, or use :func:`start_monitor`.
    """

    def __init__(
        self,
        *,
        interval_s: float | None = None,
        probe_mode: str | None = None,
        probe_timeout_s: float | None = None,
        hbm_watermark: float | None = None,
        stale_s: float | None = None,
        failing_after: int | None = None,
        retry_storm: int | None = None,
        probe_fn=None,
        slo_engine: slo_mod.SloEngine | None = None,
    ):
        self.interval_s = (
            _env_float(INTERVAL_VAR, 5.0) if interval_s is None else interval_s
        )
        mode = (
            os.environ.get(PROBE_VAR, "inline") or "inline"
            if probe_mode is None
            else probe_mode
        )
        if mode not in PROBE_MODES:
            raise ValueError(
                f"{PROBE_VAR}={mode!r} must be one of {PROBE_MODES}"
            )
        self.probe_mode = mode
        self.probe_timeout_s = (
            _env_float(PROBE_TIMEOUT_VAR, 20.0)
            if probe_timeout_s is None
            else probe_timeout_s
        )
        self.hbm_watermark = (
            _env_float(HBM_WATERMARK_VAR, 0.92)
            if hbm_watermark is None
            else hbm_watermark
        )
        self.stale_s = (
            _env_float(STALE_VAR, 60.0) if stale_s is None else stale_s
        )
        self.failing_after = max(
            1,
            _env_int(FAILING_AFTER_VAR, 3)
            if failing_after is None
            else failing_after,
        )
        self.retry_storm = max(
            1,
            _env_int(RETRY_STORM_VAR, 8) if retry_storm is None else retry_storm,
        )
        self._probe_fn = probe_fn
        self.slo = slo_engine if slo_engine is not None else slo_mod.SloEngine()

        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._probe_thread: threading.Thread | None = None
        self._states = {c: OK for c in COMPONENTS}
        self._details = {c: "" for c in COMPONENTS}
        self._streaks = {c: 0 for c in COMPONENTS}
        self._polls = 0
        self._transitions = 0
        self._prev_snap = None
        self._last_slo: dict = {}

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "HealthMonitor":
        """Start the daemon poll thread (idempotent)."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="tpu-ml-health-monitor", daemon=True
            )
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        """Signal the poll loop and join it (and any straggling probe
        thread) within ``timeout`` — tests assert no dangling threads."""
        self._stop.set()
        with self._lock:
            t, self._thread = self._thread, None
            pt, self._probe_thread = self._probe_thread, None
        deadline = time.monotonic() + timeout
        if t is not None:
            t.join(max(0.0, deadline - time.monotonic()))
        if pt is not None:
            pt.join(max(0.0, deadline - time.monotonic()))

    @property
    def running(self) -> bool:
        with self._lock:
            return self._thread is not None and self._thread.is_alive()

    @property
    def polls(self) -> int:
        with self._lock:
            return self._polls

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll_once()
            except Exception:
                # the monitor must never die of a transient sampling error;
                # the next poll retries from scratch
                logger.exception("health poll failed")
            self._stop.wait(self.interval_s)

    # -- one poll cycle ------------------------------------------------------

    def poll_once(self) -> dict:
        """Evaluate every component once, publish gauges/transitions, run
        the SLO engine, and return the rollup dict."""
        now = time.monotonic()
        snap = REGISTRY.snapshot()

        self._eval_device()
        self._eval_transport()
        self._eval_stream(snap, now)
        self._eval_workers(snap, now)
        self._eval_resilience(snap)
        self._eval_scheduler(snap)

        last_slo = self.slo.evaluate(now)
        with self._lock:
            self._last_slo = last_slo
            self._polls += 1
            self._prev_snap = snap
            overall = max(self._states.values())
        REGISTRY.gauge_set("health.state", overall, component="overall")
        return self.rollup()

    def _set_state(self, component: str, state: int, detail: str) -> None:
        with self._lock:
            old = self._states[component]
            self._states[component] = state
            self._details[component] = detail
            changed = state != old
            if changed:
                self._transitions += 1
        if changed:
            REGISTRY.gauge_set("health.state", state, component=component)
            REGISTRY.counter_inc(
                "health.transitions",
                component=component,
                to=STATE_NAMES[state],
            )
            TIMELINE.record_instant(
                "health.transition",
                component=component,
                frm=STATE_NAMES[old],
                to=STATE_NAMES[state],
                detail=detail[:160],
            )
            log = logger.warning if state > old else logger.info
            log(
                "health: %s %s -> %s (%s)",
                component, STATE_NAMES[old], STATE_NAMES[state], detail,
            )
        elif state == OK:
            # keep the gauge fresh even without a transition so a scraped
            # registry always carries every component
            REGISTRY.gauge_set("health.state", state, component=component)

    def _escalate(self, component: str, bad: bool) -> int:
        """Consecutive-degraded streak → DEGRADED, then FAILING."""
        with self._lock:
            streak = self._streaks[component] + 1 if bad else 0
            self._streaks[component] = streak
        if not bad:
            return OK
        return FAILING if streak >= self.failing_after else DEGRADED

    def _eval_device(self) -> None:
        stats = compilemon.sample_device_memory()
        if not stats:
            self._set_state("device", OK, "no device memory stats")
            return
        worst, worst_dev = 0.0, ""
        for dev, s in stats.items():
            limit = s.get("bytes_limit", 0)
            if limit:
                frac = s.get("bytes_in_use", 0) / limit
                if frac > worst:
                    worst, worst_dev = frac, dev
        if worst > self.hbm_watermark:
            self._set_state(
                "device",
                DEGRADED,
                f"HBM watermark {worst:.0%} > {self.hbm_watermark:.0%} "
                f"on {worst_dev}",
            )
        else:
            self._set_state("device", OK, f"HBM watermark {worst:.0%}")

    def _eval_transport(self) -> None:
        if self.probe_mode == "off":
            self._set_state("transport", OK, "probe off")
            return
        ok, detail, took = self._run_probe()
        REGISTRY.histogram_record("health.probe_seconds", took)
        state = self._escalate("transport", not ok)
        self._set_state(
            "transport",
            state,
            detail if ok else f"probe failed ({took:.2f}s): {detail}",
        )

    def _run_probe(self) -> tuple[bool, str, float]:
        t0 = time.monotonic()
        # the probe body runs on a throwaway daemon thread so a hung call
        # cannot stall the monitor loop past the deadline
        result: dict = {}
        done = threading.Event()

        def _probe() -> None:
            try:
                ok, detail = (self._probe_fn or default_inline_probe)()
                result["ok"], result["detail"] = bool(ok), str(detail)
            except BaseException as e:  # noqa: BLE001 - reported as failure
                result["ok"] = False
                result["detail"] = f"{type(e).__name__}: {e}"
            finally:
                done.set()

        t = threading.Thread(
            target=_probe, name="tpu-ml-health-probe", daemon=True
        )
        t.start()
        done.wait(self.probe_timeout_s)
        took = time.monotonic() - t0
        if not done.is_set():
            with self._lock:
                self._probe_thread = t  # joined (bounded) by stop()
            return (
                False,
                f"probe did not complete within {self.probe_timeout_s}s",
                took,
            )
        return result["ok"], result["detail"], took

    def _eval_stream(self, snap, now: float) -> None:
        active = _gauge_max(snap, "stream.active")
        beat = _gauge_max(snap, "stream.last_beat")
        if not active or beat is None:
            with self._lock:
                self._streaks["stream"] = 0
            self._set_state("stream", OK, "no active stream")
            return
        age = now - beat
        state = self._escalate("stream", age > self.stale_s)
        self._set_state(
            "stream",
            state,
            f"heartbeat {age:.1f}s old"
            + ("" if state == OK else f" (> {self.stale_s:.0f}s stale)"),
        )

    def _eval_workers(self, snap, now: float) -> None:
        last = _gauge_max(snap, "worker.last_trailer")
        if last is None:
            self._set_state("workers", OK, "no worker trailers yet")
            return
        age = now - last
        if age > self.stale_s:
            self._set_state(
                "workers", DEGRADED, f"last trailer {age:.1f}s old"
            )
        else:
            self._set_state("workers", OK, f"last trailer {age:.1f}s old")

    def _eval_resilience(self, snap) -> None:
        with self._lock:
            prev = self._prev_snap
        window = snap.delta(prev) if prev is not None else snap
        reasons = []
        retries = window.counter("retry.attempts")
        if retries >= self.retry_storm:
            reasons.append(
                f"retry storm: {retries:g} attempts in one poll window"
            )
        if snap.counter("degraded.cpu_fallback"):
            reasons.append("running on degraded cpu fallback")
        if window.counter("fault.injected"):
            reasons.append("fault injection active")
        if reasons:
            self._set_state("resilience", DEGRADED, "; ".join(reasons))
        else:
            self._set_state("resilience", OK, "quiet")

    def _eval_scheduler(self, snap) -> None:
        slots = _gauge_max(snap, "worker.slots")
        quarantined = _gauge_max(snap, "worker.quarantined") or 0
        if slots is None:
            self._set_state("scheduler", OK, "no supervised workers")
            return
        if slots and quarantined >= slots:
            self._set_state(
                "scheduler",
                FAILING,
                f"all {int(slots)} worker slot(s) quarantined "
                "(circuit breaker open everywhere)",
            )
        elif quarantined > 0:
            self._set_state(
                "scheduler",
                DEGRADED,
                f"{int(quarantined)}/{int(slots)} worker slot(s) quarantined",
            )
        else:
            self._set_state(
                "scheduler", OK, f"{int(slots)} worker slot(s) healthy"
            )

    # -- rollup --------------------------------------------------------------

    def rollup(self) -> dict:
        """The current health picture (the ``/healthz`` payload)."""
        with self._lock:
            states = dict(self._states)
            details = dict(self._details)
            polls = self._polls
            transitions = self._transitions
            last_slo = dict(self._last_slo)
        overall = max(states.values()) if states else OK
        out = {
            "state": STATE_NAMES[overall],
            "components": {
                c: {"state": STATE_NAMES[states[c]], "detail": details[c]}
                for c in COMPONENTS
            },
            "polls": polls,
            "transitions": transitions,
            "slo": last_slo,
        }
        # live lease/quarantine state from any supervised worker pools, so
        # /healthz shows per-slot evidence alongside the component verdict
        try:
            from spark_rapids_ml_tpu.resilience import supervisor as sup_mod

            sched = sup_mod.active_summary()
        except Exception:  # pragma: no cover - rollup must never break
            sched = {}
        if sched:
            out["scheduler"] = sched
        return out

    def fit_summary(self) -> dict:
        """Compact rollup stamped onto FitReport schema 6 (no per-poll SLO
        detail — the breach counter already rides in ``counters``)."""
        r = self.rollup()
        return {
            "state": r["state"],
            "components": {
                c: v["state"] for c, v in r["components"].items()
            },
            "polls": r["polls"],
            "transitions": r["transitions"],
            "slo_breaches": self.slo.total_breaches(),
        }


def _gauge_max(snap, name: str) -> float | None:
    """Max value of a gauge across label sets; None when never set."""
    vals = [v for (n, _), v in snap.gauges.items() if n == name]
    return max(vals) if vals else None


# -- module singleton (the instance /healthz and FitReport stamping read) ---

_LOCK = threading.Lock()
_MONITOR: HealthMonitor | None = None


def start_monitor(**kwargs) -> HealthMonitor:
    """Start (or return) the process-wide monitor."""
    global _MONITOR
    with _LOCK:
        if _MONITOR is None:
            _MONITOR = HealthMonitor(**kwargs)
        _MONITOR.start()
        return _MONITOR


def get_monitor() -> HealthMonitor | None:
    with _LOCK:
        return _MONITOR


def stop_monitor(timeout: float = 5.0) -> None:
    """Stop and forget the process-wide monitor (no-op when absent)."""
    global _MONITOR
    with _LOCK:
        mon = _MONITOR
        _MONITOR = None
    if mon is not None:
        mon.stop(timeout)


def current_summary() -> dict:
    """The running monitor's :meth:`HealthMonitor.fit_summary`, or ``{}``
    when no monitor is active — what ``end_fit`` stamps on the report."""
    mon = get_monitor()
    if mon is None:
        return {}
    try:
        return mon.fit_summary()
    except Exception:  # pragma: no cover - stamping must never break a fit
        logger.exception("health summary failed")
        return {}


# -- health-driven admission control ----------------------------------------


def admission_policy() -> str:
    """The configured ``TPU_ML_ADMISSION_POLICY`` (``refuse`` by default)."""
    v = os.environ.get(ADMISSION_POLICY_VAR, "refuse") or "refuse"
    if v not in ADMISSION_POLICIES:
        raise ValueError(
            f"{ADMISSION_POLICY_VAR}={v!r} must be one of {ADMISSION_POLICIES}"
        )
    return v


def admission_check() -> dict:
    """Consult the live monitor before admitting a fit.

    Returns the decision dict stamped onto FitReport schema 6:
    ``{"policy", "action", "health_state", "reason"}`` where ``action`` is
    ``admit``, ``refuse`` or ``degrade``. Decisions other than ``admit``
    are counted (``scheduler.admission{action}``) and land on the timeline;
    actually *enforcing* them (raising :class:`AdmissionRefused`, opening
    the degrade window) is the caller's job — ``telemetry.report.begin_fit``.
    Without a monitor, or before its first poll, there is no evidence and
    the fit is admitted.
    """
    policy = admission_policy()
    decision = {
        "policy": policy,
        "action": "admit",
        "health_state": "UNKNOWN",
        "reason": "",
    }
    if policy == "off":
        decision["reason"] = "admission control off"
        return decision
    mon = get_monitor()
    if mon is None or mon.polls == 0:
        decision["reason"] = "no health evidence (monitor absent or unpolled)"
        return decision
    r = mon.rollup()
    decision["health_state"] = r["state"]
    if r["state"] != STATE_NAMES[FAILING]:
        decision["reason"] = f"health {r['state']}"
        return decision
    failing = [
        c for c, v in r["components"].items()
        if v["state"] == STATE_NAMES[FAILING]
    ]
    detail = "; ".join(
        f"{c}: {r['components'][c]['detail']}" for c in failing
    )
    decision["action"] = policy  # "refuse" or "degrade"
    decision["reason"] = (
        f"component(s) {', '.join(failing)} FAILING — {detail}"[:300]
    )
    REGISTRY.counter_inc("scheduler.admission", action=policy)
    TIMELINE.record_instant(
        "scheduler.admission", action=policy, components=",".join(failing)
    )
    logger.warning("admission control: %s fit (%s)", policy, decision["reason"])
    return decision


# Degrade window: while a fit admitted under policy "degrade" runs, mesh
# creation must not touch the failing accelerator — estimators consult
# admission_degrade_active() and take the CPU fallback path instead.
# Thread-local because fits are (report.py's _fit_depth contract).
_DEGRADE = threading.local()


def begin_degrade_window() -> None:
    _DEGRADE.depth = getattr(_DEGRADE, "depth", 0) + 1


def end_degrade_window() -> None:
    _DEGRADE.depth = max(0, getattr(_DEGRADE, "depth", 0) - 1)


def admission_degrade_active() -> bool:
    """True inside a fit the admission controller degraded to CPU."""
    return getattr(_DEGRADE, "depth", 0) > 0
