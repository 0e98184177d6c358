"""Deterministic fault injection at named sites, driven by an env plan.

A *fault plan* is a comma-separated list of ``site:kind:nth[:arg]`` entries
in ``TPU_ML_FAULT_PLAN``; the ``nth`` is the 1-based occurrence of that
site *in this process* (each worker subprocess counts its own occurrences —
which is exactly what lets a plan kill "the first task any worker runs").

    TPU_ML_FAULT_PLAN="fold.dispatch:oom:3"        # 3rd dispatch OOMs
    TPU_ML_FAULT_PLAN="ingest.chunk:io:2,fold.wait:hang:1:0.5"

Kinds:

- ``oom``        raise :class:`InjectedResourceExhausted` — a synthetic
                 ``RESOURCE_EXHAUSTED``-style device OOM, classified like
                 the ``JaxRuntimeError`` family it imitates.
- ``io``         raise :class:`InjectedTransientIOError` (an ``IOError``
                 subclass) — a transient I/O failure, retryable.
- ``hang``       sleep ``arg`` seconds (default 0.25) — a slow/hung call;
                 pair with the ``fold.wait`` timeout bound to exercise the
                 hang diagnosis.
- ``nonfinite``  corrupt the data passing through the site (first element
                 becomes NaN) — exercises the non-finite row policy.
- ``preempt``    raise :class:`InjectedPreemption` — simulated preemption;
                 classified FATAL (a real preemption kills the process, so
                 recovery is checkpoint/resume, never in-process retry).
- ``kill``       ``os._exit(KILL_EXIT_CODE)`` — actually die, for
                 crashed-worker-replacement coverage. Only ever fires when
                 the plan explicitly asks for it.

Why nth-occurrence and not probability: chaos tests must be deterministic
(the same plan always fails the same call), and a transient fault must
clear on retry — the retry re-enters the site, the occurrence counter
advances past ``nth``, and the call succeeds. One mechanism gives both.

Every fired injection is counted in the telemetry registry
(``fault.injected{site,kind}``), so a fit report proves the fault happened
AND the recovery counters (``retry.attempts``, ``chunk.bisections``)
prove it was handled. The hot-path cost with no plan set is one
``os.environ`` read per site call.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass
from typing import Any

from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils import knobs

FAULT_PLAN_VAR = knobs.FAULT_PLAN.name

KINDS = ("oom", "io", "hang", "nonfinite", "preempt", "kill")

# distinguishable in a WorkerException from a device-probe failure (17) or
# a plan-function crash
KILL_EXIT_CODE = 113

DEFAULT_HANG_SECONDS = 0.25


class FaultInjected(RuntimeError):
    """Base of all synthetic faults raised by the injection layer.

    ``error_class`` names the :class:`~.retry.ErrorClass` member the fault
    imitates (a string, so this module never imports the classifier).
    """

    error_class = "FATAL"


class InjectedResourceExhausted(FaultInjected):
    """Synthetic device OOM — the XLA ``RESOURCE_EXHAUSTED`` family."""

    error_class = "RESOURCE_EXHAUSTED"


class InjectedTransientIOError(FaultInjected, IOError):
    """Synthetic transient I/O failure — clears on retry."""

    error_class = "TRANSIENT"


class InjectedPreemption(FaultInjected):
    """Simulated preemption: the process would have died at this point.

    FATAL on purpose — in-process retry cannot survive a real preemption;
    the recovery path is the durable checkpoint + resume."""

    error_class = "FATAL"


@dataclass(frozen=True)
class FaultSpec:
    site: str
    kind: str
    nth: int
    arg: float | None = None


def parse_plan(raw: str) -> tuple[FaultSpec, ...]:
    """Parse a ``site:kind:nth[:arg]`` comma list; '' → no faults."""
    specs: list[FaultSpec] = []
    for entry in raw.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) not in (3, 4):
            raise ValueError(
                f"{FAULT_PLAN_VAR} entry {entry!r}: expected site:kind:nth[:arg]"
            )
        site, kind, nth_raw = parts[0], parts[1], parts[2]
        if kind not in KINDS:
            raise ValueError(
                f"{FAULT_PLAN_VAR} entry {entry!r}: kind {kind!r} not one of {KINDS}"
            )
        try:
            nth = int(nth_raw)
        except ValueError:
            raise ValueError(
                f"{FAULT_PLAN_VAR} entry {entry!r}: nth {nth_raw!r} is not an int"
            ) from None
        if nth < 1:
            raise ValueError(
                f"{FAULT_PLAN_VAR} entry {entry!r}: nth must be >= 1 (1-based)"
            )
        arg = float(parts[3]) if len(parts) == 4 else None
        specs.append(FaultSpec(site, kind, nth, arg))
    return tuple(specs)


# plan cache keyed on the raw env string (so a test monkeypatching the env
# re-parses) + per-site occurrence counters, both behind one lock
_lock = threading.Lock()
_cached_raw: str | None = None
_cached_plan: tuple[FaultSpec, ...] = ()
_site_calls: dict[str, int] = {}


def _plan() -> tuple[FaultSpec, ...]:
    global _cached_raw, _cached_plan
    raw = os.environ.get(FAULT_PLAN_VAR, "")
    if raw != _cached_raw:
        _cached_plan = parse_plan(raw)
        _cached_raw = raw
    return _cached_plan


def reset_faults() -> None:
    """Forget site occurrence counters and the cached plan (tests)."""
    global _cached_raw, _cached_plan
    with _lock:
        _site_calls.clear()
        _cached_raw = None
        _cached_plan = ()


def inject(site: str, data: Any = None) -> Any:
    """The fault-site gate: count this occurrence of ``site`` and fire any
    matching plan entry. Returns ``data`` (corrupted for ``nonfinite``
    entries); raising kinds raise; with no plan this is a no-op pass-through.

    Call it at the TOP of the protected operation — before any state the
    operation cannot roll back (in particular before a donated-carry fold
    consumes its buffers), so a retry of the site re-runs cleanly.
    """
    with _lock:
        plan = _plan()
        if not plan:
            return data
        n = _site_calls.get(site, 0) + 1
        _site_calls[site] = n
        hits = [s for s in plan if s.site == site and s.nth == n]
    for spec in hits:
        REGISTRY.counter_inc("fault.injected", site=site, kind=spec.kind)
        TIMELINE.record_instant("fault.injected", site=site, kind=spec.kind)
        if spec.kind == "oom":
            raise InjectedResourceExhausted(
                f"RESOURCE_EXHAUSTED: injected device OOM at {site!r} "
                f"(occurrence {n})"
            )
        if spec.kind == "io":
            raise InjectedTransientIOError(
                f"injected transient I/O failure at {site!r} (occurrence {n})"
            )
        if spec.kind == "preempt":
            raise InjectedPreemption(
                f"injected preemption at {site!r} (occurrence {n}) — the "
                "process would have been killed here"
            )
        if spec.kind == "kill":
            os._exit(KILL_EXIT_CODE)
        if spec.kind == "hang":
            time.sleep(spec.arg if spec.arg is not None else DEFAULT_HANG_SECONDS)
        elif spec.kind == "nonfinite" and data is not None:
            data = _corrupt(data)
    return data


def _corrupt(x):
    import numpy as np

    x = np.array(x, copy=True)
    x.reshape(-1)[0] = np.nan
    return x
