"""Fault injection + recovery — the framework's failure story.

The reference delegates failure handling wholesale: "native errors become
Java exceptions, the task fails, Spark re-schedules it" (SURVEY.md §5,
parallel/executor.py:3-7). With no Spark underneath, this package owns the
contract instead, in two halves:

- :mod:`.faults` — a deterministic, env-driven fault-injection layer.
  ``TPU_ML_FAULT_PLAN`` describes *which* named site fails, *how*, on its
  *nth* occurrence; production code calls :func:`faults.inject` at each
  choke point (``ingest.chunk``, ``fold.dispatch``, ``collective``,
  ``worker.task``, ``fold.wait``, ``device.init``) and pays one env read
  when no plan is set. Every injection is counted in the telemetry
  registry, so chaos tests can assert both the injection AND the recovery.

- :mod:`.retry` — the one shared retry policy. Errors are classified
  (transient / resource-exhausted / poisoned-backend / fatal, recognizing
  ``jax.errors.JaxRuntimeError`` families by status string), and
  :func:`retry.call_with_retry` drives exponential backoff with jitter
  under a deadline. It replaces the ad-hoc loop in ``parallel/executor``
  — and unlike the loop it replaced, it never sleeps after the final
  failed attempt.

- :mod:`.supervisor` — worker-slot supervision for ``localspark``: leases
  (spawn time, task count, last-trailer heartbeat), bounded respawn with
  exponential backoff, and a per-slot circuit breaker that quarantines a
  crash-looping slot instead of respawning it forever. The elastic stage
  scheduler in ``localspark.session`` builds on it to migrate a dead
  worker's partitions to survivors and hedge stragglers.

The recovery behaviors themselves live at the choke points they protect:
``spark.ingest.stream_fold`` self-heals device OOM by bisecting the chunk
size, checkpoints its carry + chunk cursor through
``utils.checkpoint.TrainingCheckpointer`` so preempted streamed fits
resume, and bounds the terminal ``fold.wait`` with a hang diagnosis.
"""

from spark_rapids_ml_tpu.resilience.faults import (  # noqa: F401
    FAULT_PLAN_VAR,
    FaultInjected,
    FaultSpec,
    InjectedPreemption,
    InjectedResourceExhausted,
    InjectedTransientIOError,
    inject,
    parse_plan,
    reset_faults,
)
from spark_rapids_ml_tpu.resilience.supervisor import (  # noqa: F401
    SlotLease,
    WorkerSupervisor,
    active_summary,
    hedge_config,
)
from spark_rapids_ml_tpu.resilience.retry import (  # noqa: F401
    ErrorClass,
    FoldHangTimeout,
    RetryPolicy,
    call_with_retry,
    classify,
)
