"""Error classification + the one shared retry policy.

Spark gave the reference a uniform answer to every task failure: fail the
task, re-schedule it ``spark.task.maxFailures`` times (SURVEY.md §5). This
framework's failures are more differentiated — a ``jax.errors.JaxRuntimeError``
can mean a transient runtime blip (retry), device memory exhaustion
(retry *smaller* — stream_fold bisects), or a poisoned PJRT client that no
in-process retry will ever fix — so retries here start with a classifier:

- ``TRANSIENT``            — I/O and connection errors, timeouts, and the
  retryable XLA status families (UNAVAILABLE / DEADLINE_EXCEEDED /
  ABORTED / CANCELLED / UNKNOWN). Retry in place.
- ``RESOURCE_EXHAUSTED``   — device/host OOM. Retrying the identical call
  is usually futile; retrying a *smaller* call works (chunk bisection).
- ``POISONED``             — the backend/client is wedged (dead PJRT
  client, hung fold). Only a fresh process helps.
- ``FATAL``                — everything else (shape errors, value errors,
  simulated preemption). Never retried.

A device error is a ``jax.errors.JaxRuntimeError`` (``isinstance``; jax is
imported only once an exception is in hand). Synthetic faults declare the
class they imitate and never reach that test.

:func:`call_with_retry` is the single backoff loop the framework uses —
exponential with deterministic jitter, capped, under an optional deadline,
counting every retry in the telemetry registry (``retry.attempts{site}``)
— replacing the hand-rolled loop in ``parallel/executor``. By
construction it never sleeps after the final failed attempt (the executor
bug the migration fixed): the sleep only happens when a retry is actually
coming.
"""

from __future__ import annotations

import enum
import logging
import random
import time
from dataclasses import dataclass
from typing import Callable, FrozenSet

from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE

logger = logging.getLogger("spark_rapids_ml_tpu")


class ErrorClass(enum.Enum):
    TRANSIENT = "transient"
    RESOURCE_EXHAUSTED = "resource_exhausted"
    POISONED = "poisoned"
    FATAL = "fatal"


class FoldHangTimeout(RuntimeError):
    """A bounded device wait expired — the fold is hung, not slow.

    Classified POISONED: the wait's daemon thread is still blocked inside
    the backend, so this process cannot simply re-issue the work."""


# XLA status families, matched against the upper-cased message
_XLA_TRANSIENT = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED", "UNKNOWN")
_XLA_OOM = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED", "OUT OF MEMORY", "ALLOCATION FAILURE")
_XLA_POISONED = ("PJRT CLIENT", "BACKEND WAS", "HEARTBEAT")


def classify(exc: BaseException) -> ErrorClass:
    """Map an exception to its :class:`ErrorClass`."""
    # synthetic faults declare the class they imitate (faults.FaultInjected)
    declared = getattr(exc, "error_class", None)
    if isinstance(declared, str):
        try:
            return ErrorClass[declared]
        except KeyError:
            pass
    if isinstance(exc, MemoryError):
        return ErrorClass.RESOURCE_EXHAUSTED
    if isinstance(exc, FoldHangTimeout):
        return ErrorClass.POISONED
    from jax.errors import JaxRuntimeError

    if isinstance(exc, JaxRuntimeError):
        msg = str(exc).upper()
        if any(m in msg for m in _XLA_OOM):
            return ErrorClass.RESOURCE_EXHAUSTED
        if any(m in msg for m in _XLA_POISONED):
            return ErrorClass.POISONED
        if any(m in msg for m in _XLA_TRANSIENT):
            return ErrorClass.TRANSIENT
        return ErrorClass.FATAL
    if isinstance(exc, (OSError, ConnectionError, TimeoutError, EOFError)):
        return ErrorClass.TRANSIENT
    return ErrorClass.FATAL


# default retry set: transient blips and OOM (the caller may be retrying a
# smaller unit of work, as stream_fold's bisection does)
RETRYABLE_DEFAULT: FrozenSet[ErrorClass] = frozenset(
    {ErrorClass.TRANSIENT, ErrorClass.RESOURCE_EXHAUSTED}
)
# Spark-task semantics: ANY failure consumes one of maxFailures attempts
RETRY_ANY: FrozenSet[ErrorClass] = frozenset(ErrorClass)


@dataclass
class RetryPolicy:
    """Exponential backoff with deterministic jitter under a deadline.

    ``sleep_s(k)`` is the pause after the k-th failed attempt (1-based):
    ``backoff_s * multiplier**(k-1)`` capped at ``max_backoff_s``, then
    jittered by ±``jitter`` fraction via a seeded RNG — deterministic for
    a given (seed, attempt), so tests and replayed runs sleep identically.
    """

    max_attempts: int = 4
    backoff_s: float = 0.05
    multiplier: float = 2.0
    max_backoff_s: float = 2.0
    jitter: float = 0.1
    deadline_s: float | None = 300.0
    seed: int = 0

    @classmethod
    def from_config(cls, **overrides) -> "RetryPolicy":
        """Policy from the runtime config knobs (TPU_ML_RETRY_MAX_ATTEMPTS /
        TPU_ML_RETRY_DEADLINE_S; deadline 0 means unbounded)."""
        from spark_rapids_ml_tpu.utils.config import get_config

        cfg = get_config()
        kw: dict = {
            "max_attempts": cfg.retry_max_attempts,
            "deadline_s": float(cfg.retry_deadline_s) or None,
        }
        kw.update(overrides)
        return cls(**kw)

    def sleep_s(self, attempt: int) -> float:
        base = min(
            self.backoff_s * self.multiplier ** (attempt - 1), self.max_backoff_s
        )
        if not self.jitter:
            return base
        r = random.Random(self.seed * 1_000_003 + attempt)
        return base * (1.0 + self.jitter * (2.0 * r.random() - 1.0))


def call_with_retry(
    fn: Callable,
    *,
    site: str = "",
    policy: RetryPolicy | None = None,
    retry_on: FrozenSet[ErrorClass] = RETRYABLE_DEFAULT,
    classify_fn: Callable[[BaseException], ErrorClass] = classify,
    on_failure: Callable[[int, BaseException, bool], None] | None = None,
    sleep: Callable[[float], None] | None = None,
) -> object:
    """Run ``fn()`` under the shared retry policy.

    Retries only classes in ``retry_on``, only while attempts and the
    deadline remain — and sleeps only when another attempt is coming, never
    after the final failure. Each retry is counted as
    ``retry.attempts{site}`` in the telemetry registry (which flows into
    the per-fit report and the trace-report anomaly checks).

    ``on_failure(attempt, exc, will_retry)`` observes every failed attempt
    (callers keep their own log formats); the default logs a warning.
    """
    pol = policy if policy is not None else RetryPolicy.from_config()
    start = time.monotonic()
    attempt = 0
    while True:
        attempt += 1
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — classified below
            cls = classify_fn(e)
            within_deadline = (
                pol.deadline_s is None
                or time.monotonic() - start < pol.deadline_s
            )
            will_retry = (
                cls in retry_on and attempt < pol.max_attempts and within_deadline
            )
            if on_failure is not None:
                on_failure(attempt, e, will_retry)
            else:
                logger.warning(
                    "%s attempt %d/%d failed (%s): %s",
                    site or "retryable call", attempt, pol.max_attempts,
                    cls.value, e,
                )
            if not will_retry:
                raise
            REGISTRY.counter_inc("retry.attempts", site=site or "unlabeled")
            TIMELINE.record_instant(
                "retry", site=site or "unlabeled", attempt=attempt,
                error_class=cls.value,
            )
            # late-bound so tests monkeypatching time.sleep observe it
            (sleep if sleep is not None else time.sleep)(pol.sleep_s(attempt))
