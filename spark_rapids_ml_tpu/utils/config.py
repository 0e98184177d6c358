"""Runtime configuration — the env/conf tier of the config system.

The reference's config is three-tier (SURVEY.md §5): (1) per-estimator ML
Params, (2) Spark runtime confs (``spark.rapids.sql.enabled``, GPU resource
amounts), (3) build-time flags. Tier 1 lives in ``models.params``. This
module is tier 2 for the TPU build — process-level knobs read from
``TPU_ML_*`` environment variables once at first use, overridable in code:

- ``TPU_ML_MIN_BUCKET``      (int, default 128)  — row-bucket floor for
  static-shape padding: of a partition's power-of-two bucket
  (utils.columnar.bucket_rows) and of a resident shard's step
  (utils.columnar.shard_rows, an eighth of an octave).
- ``TPU_ML_MAX_WORKERS``     (int, default 4)    — partition executor pool.
- ``TPU_ML_TASK_RETRIES``    (int, default 3)    — per-task retry budget
  (the ``spark.task.maxFailures`` analog).
- ``TPU_ML_DEFAULT_PRECISION`` ('highest'|'high'|'default') — estimator-level
  default for the Gram/projection matmul precision.
- ``TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES`` (int, default 2**31) — cutover
  for the out-of-core streamed fit: DataFrame fits whose estimated device
  footprint (rows × n × wire-dtype bytes) exceeds this stream chunk-wise
  through the donated-carry fold pipeline (spark.ingest.stream_fold) at
  O(chunk + n²) device memory instead of materializing the full resident
  array. Small data keeps the resident path — it is still fastest when it
  fits.
- ``TPU_ML_TELEMETRY_PATH``  (path, default ``''`` = disabled) — JSONL sink
  for per-fit telemetry reports (``telemetry.export``). Each completed
  ``fit()`` appends one ``fit_report`` record; render with
  ``python tools/trace_report.py <path>``.
- ``TPU_ML_TIMELINE_PATH``   (path, default ``''`` = disabled) — JSONL sink
  for per-fit flight-recorder timelines (``telemetry.timeline``): one
  ``timeline`` record of raw span/instant events per outermost ``fit()``.
  May point at the same file as ``TPU_ML_TELEMETRY_PATH`` (readers filter
  by record type). Export to Perfetto-loadable Chrome trace JSON with
  ``python tools/trace_timeline.py <path> --out trace.json``.
- ``TPU_ML_TIMELINE_EVENTS`` (int, default 4096; 0 disables; read directly
  by ``telemetry.timeline``, not cached here) — ring-buffer capacity of
  the flight recorder. Old events fall off; aggregate truth stays in the
  metrics registry.
- ``TPU_ML_PROGRESS`` (float seconds, default unset = off; read directly
  by ``spark.ingest.stream_fold``) — emit a live progress heartbeat line
  to stderr every N seconds during a streamed fit: rows done, rows/s,
  current chunk size, retries/bisections so far.
- ``TPU_ML_RETRY_MAX_ATTEMPTS`` (int, default 4) — attempt budget for the
  shared retry policy (``resilience.retry.RetryPolicy.from_config``):
  classified-transient failures at the data-movement/compute choke points
  retry up to this many total attempts.
- ``TPU_ML_RETRY_DEADLINE_S`` (int, default 300; 0 = unbounded) — wall
  deadline across one call's retries; once exceeded, no further attempt
  is made.
- ``TPU_ML_STREAM_CHECKPOINT_EVERY_CHUNKS`` (int, default 64) — with a
  ``checkpoint_dir``, the streamed fit durably checkpoints its carry +
  chunk cursor every this many chunks so a preempted fit resumes instead
  of restarting.
- ``TPU_ML_FOLD_WAIT_TIMEOUT_S`` (int, default 600; 0 = unbounded) — bound
  on the streamed fit's terminal device wait; a wedged device surfaces as
  a diagnosable ``FoldHangTimeout`` instead of blocking forever.
- ``TPU_ML_NONFINITE_POLICY`` ('raise'|'skip'|'allow', default 'raise') —
  streamed-fit handling of non-finite input rows, asked once a chunk of the
  chunk that was put (on its device, in the dtype the device holds): fail
  the fit, mask and count them (``rows.nonfinite_skipped``), or ask nothing.
- ``TPU_ML_FAULT_PLAN`` (read by ``resilience.faults``, not cached here) —
  deterministic fault-injection plan for chaos testing; see the Resilience
  README section. Never set in production.
- ``TPU_ML_LOG_LEVEL``       (logging level name or number, default unset) —
  sets the ``spark_rapids_ml_tpu`` logger level at package import. The
  package attaches only a ``logging.NullHandler``; output routing stays the
  application's choice.
- ``TPU_ML_PEAK_TFLOPS`` (float, default unset = looked up by
  ``device_kind``; read directly by ``telemetry.costmodel``) — explicit
  device peak for the cost model's roofline-utilization denominator stamped
  into Fit/TransformReports. A device the table does not know gets none.
- ``TPU_ML_PERF_LEDGER_PATH`` (path, default ``bench_history.jsonl`` next to
  ``bench.py``; empty string disables; read directly by ``bench.py``) —
  history file each bench run appends its metrics + cost-model numbers to;
  compared across runs by ``tools/perf_sentinel.py``. Never the driver's
  ``PERF_LEDGER.jsonl``.
- ``TPU_ML_PERF_SENTINEL`` (``1`` to enable; read directly by ``bench.py``)
  — after appending the ledger entry, the bench runs
  ``tools/perf_sentinel.py --strict`` on it and fails on regressions
  beyond the threshold — the opt-in CI perf gate for ``bench --smoke``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

from spark_rapids_ml_tpu.utils import knobs

VALID_PRECISIONS = ("highest", "high", "default")
VALID_NONFINITE_POLICIES = ("raise", "skip", "allow")

# config fields whose values are strings (everything else is int-typed)
_STR_KEYS = (
    "default_precision",
    "telemetry_path",
    "timeline_path",
    "nonfinite_policy",
)


def _int_env(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        raise ValueError(
            f"{name}={os.environ[name]!r} is not an integer"
        ) from None


def _precision_env() -> str:
    v = os.environ.get(knobs.DEFAULT_PRECISION.name, "highest")
    if v not in VALID_PRECISIONS:
        raise ValueError(
            f"{knobs.DEFAULT_PRECISION.name}={v!r} must be one of "
            f"{VALID_PRECISIONS}"
        )
    return v


def _nonfinite_env() -> str:
    v = os.environ.get(knobs.NONFINITE_POLICY.name, "raise")
    if v not in VALID_NONFINITE_POLICIES:
        raise ValueError(
            f"{knobs.NONFINITE_POLICY.name}={v!r} must be one of "
            f"{VALID_NONFINITE_POLICIES}"
        )
    return v


@dataclass
class RuntimeConfig:
    min_bucket: int = field(
        default_factory=lambda: _int_env(knobs.MIN_BUCKET.name, 128)
    )
    max_workers: int = field(
        default_factory=lambda: _int_env(knobs.MAX_WORKERS.name, 4)
    )
    task_retries: int = field(
        default_factory=lambda: _int_env(knobs.TASK_RETRIES.name, 3)
    )
    default_precision: str = field(default_factory=_precision_env)
    stream_fit_max_resident_bytes: int = field(
        default_factory=lambda: _int_env(
            knobs.STREAM_FIT_MAX_RESIDENT_BYTES.name, 1 << 31
        )
    )
    telemetry_path: str = field(
        default_factory=lambda: os.environ.get(knobs.TELEMETRY_PATH.name, "")
    )
    timeline_path: str = field(
        default_factory=lambda: os.environ.get(knobs.TIMELINE_PATH.name, "")
    )
    retry_max_attempts: int = field(
        default_factory=lambda: _int_env(knobs.RETRY_MAX_ATTEMPTS.name, 4)
    )
    retry_deadline_s: int = field(
        default_factory=lambda: _int_env(knobs.RETRY_DEADLINE_S.name, 300)
    )
    stream_checkpoint_every_chunks: int = field(
        default_factory=lambda: _int_env(
            knobs.STREAM_CHECKPOINT_EVERY_CHUNKS.name, 64
        )
    )
    fold_wait_timeout_s: int = field(
        default_factory=lambda: _int_env(knobs.FOLD_WAIT_TIMEOUT_S.name, 600)
    )
    nonfinite_policy: str = field(default_factory=_nonfinite_env)


_config: RuntimeConfig | None = None

# <repo root>/.jax_cache: fixed, because the directory is part of what a
# cache entry is found by — one that moves with $HOME, a pid or the time
# never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """The one rule for the persistent XLA compilation cache (idempotent).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX took the directory when it
    was imported, and nothing is set here (the same holds for a directory an
    embedding application configured); otherwise the cache is
    :data:`DEFAULT_COMPILE_CACHE_DIR`. Either way every compile is kept,
    however short: the serve kernels compile in milliseconds and a fresh
    process must still find them. Returns the directory in force.

    Every entry point that compiles calls this first (estimator ``fit`` and
    ``transform`` windows, worker plan functions, servable registration), so
    the process's first compile already goes through the cache.
    """
    import jax

    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def get_config() -> RuntimeConfig:
    global _config
    if _config is None:
        _config = RuntimeConfig()
    return _config


def set_config(**overrides) -> RuntimeConfig:
    """Override runtime knobs in code (tests, notebooks)."""
    cfg = get_config()
    for k, v in overrides.items():
        if not hasattr(cfg, k):
            raise KeyError(f"unknown config key {k!r}")
        if k == "default_precision" and v not in VALID_PRECISIONS:
            raise ValueError(
                f"default_precision={v!r} must be one of {VALID_PRECISIONS}"
            )
        if k == "nonfinite_policy" and v not in VALID_NONFINITE_POLICIES:
            raise ValueError(
                f"nonfinite_policy={v!r} must be one of "
                f"{VALID_NONFINITE_POLICIES}"
            )
        if k in _STR_KEYS:
            if not isinstance(v, str):
                raise TypeError(f"{k} must be a str, got {type(v).__name__}")
        elif not isinstance(v, int):
            raise TypeError(f"{k} must be an int, got {type(v).__name__}")
        setattr(cfg, k, v)
    return cfg
