"""THE canonical inventory of ``TPU_ML_*`` environment knobs.

Every environment variable the framework (package, bench, tools) reads is
declared here once — name, type, default, one-line doc, and the module that
consumes it. Consumers re-export the env-var *name* from their declaration
here (``FAULT_PLAN_VAR = knobs.FAULT_PLAN.name`` style) instead of minting
their own string literal; ``tools/tpulint.py`` rule TPL006 rejects any
``TPU_ML_*`` literal outside this module, so an undeclared knob cannot
ship, and ``python -m tools.tpulint --list-knobs`` renders this inventory
(the README knob table is generated from it and drift-checked in CI).

This module is import-pure on purpose: no jax, no package siblings — the
linter, the README generator, and every consumer (including jax-free worker
ingestion processes) can import it with zero side effects.
"""

from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Knob:
    """One declared environment knob."""

    name: str          # the TPU_ML_* environment variable
    type: str          # "int" | "float" | "str" | "path" | "flag" | "enum"
    default: str       # rendered default ("" = unset/disabled)
    doc: str           # one-line meaning, README-table ready
    module: str        # the consuming module (dotted path or tool file)


_DECLARATIONS = (
    # -- core runtime (utils.config caches these in RuntimeConfig) ----------
    Knob("TPU_ML_MIN_BUCKET", "int", "128",
         "row-bucket floor for static-shape padding (bounds distinct "
         "compiled shapes)", "utils.config"),
    Knob("TPU_ML_MAX_WORKERS", "int", "4",
         "partition executor thread pool size", "utils.config"),
    Knob("TPU_ML_TASK_RETRIES", "int", "3",
         "per-task retry budget (the `spark.task.maxFailures` analog)",
         "utils.config"),
    Knob("TPU_ML_DEFAULT_PRECISION", "enum", "highest",
         "`highest`/`high`/`default` matmul precision for Gram/projection "
         "kernels", "utils.config"),
    Knob("TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES", "int", str(1 << 31),
         "device-footprint cutover above which DataFrame fits stream "
         "chunk-wise instead of materializing", "utils.config"),
    Knob("TPU_ML_LOG_LEVEL", "str", "",
         "package logger level (name or number) set at import",
         "spark_rapids_ml_tpu"),
    # -- telemetry ----------------------------------------------------------
    Knob("TPU_ML_TELEMETRY_PATH", "path", "",
         "JSONL sink for per-fit/transform telemetry reports (empty "
         "disables)", "utils.config"),
    Knob("TPU_ML_TIMELINE_PATH", "path", "",
         "JSONL sink for flight-recorder timelines (empty disables)",
         "utils.config"),
    Knob("TPU_ML_TIMELINE_EVENTS", "int", "4096",
         "flight-recorder ring-buffer capacity (0 disables)",
         "telemetry.timeline"),
    Knob("TPU_ML_PROGRESS", "float", "",
         "emit a live streamed-fit heartbeat to stderr every N seconds "
         "(unset = off)", "spark.ingest"),
    Knob("TPU_ML_PEAK_TFLOPS", "float", "",
         "explicit device peak for the cost model's roofline denominator "
         "(unset = looked up by device_kind; an unknown device gets no "
         "roofline figure)", "telemetry.costmodel"),
    # -- resilience ---------------------------------------------------------
    Knob("TPU_ML_RETRY_MAX_ATTEMPTS", "int", "4",
         "shared retry-policy attempt budget per call site", "utils.config"),
    Knob("TPU_ML_RETRY_DEADLINE_S", "int", "300",
         "wall-clock ceiling across one call's retries (0 = unbounded)",
         "utils.config"),
    Knob("TPU_ML_STREAM_CHECKPOINT_EVERY_CHUNKS", "int", "64",
         "checkpoint the streamed-fit carry every K full chunks (with a "
         "checkpoint_dir)", "utils.config"),
    Knob("TPU_ML_FOLD_WAIT_TIMEOUT_S", "int", "600",
         "bound on each of the streamed fit's device waits, a chunk's "
         "landing and the terminal one (0 = unbounded)",
         "utils.config"),
    Knob("TPU_ML_NONFINITE_POLICY", "enum", "raise",
         "`raise`/`skip`/`allow` for non-finite input rows in streamed "
         "fits: asked once a chunk, of the chunk as put (on its device, in "
         "the device's dtype); `skip` masks and counts, `allow` asks "
         "nothing", "utils.config"),
    Knob("TPU_ML_FAULT_PLAN", "str", "",
         "`site:kind:nth[:arg]` comma list of deterministic synthetic "
         "faults (chaos tests only — never production)",
         "resilience.faults"),
    # -- elastic stage scheduler (resilience.supervisor + localspark) -------
    Knob("TPU_ML_HEDGE_FACTOR", "float", "4.0",
         "speculatively re-dispatch a partition once its runtime exceeds "
         "this multiple of the completed-partition p50 (0 disables "
         "hedging)", "resilience.supervisor"),
    Knob("TPU_ML_HEDGE_FLOOR_S", "float", "1.0",
         "minimum straggler runtime before a hedge may fire (keeps tiny "
         "tasks from hedging on scheduler noise)", "resilience.supervisor"),
    Knob("TPU_ML_BARRIER_RETRIES", "int", "1",
         "barrier-stage epoch retries after an infrastructure rank failure "
         "(fresh workers per epoch; plan errors never retry)",
         "localspark.session"),
    Knob("TPU_ML_WORKER_BREAKER_THRESHOLD", "int", "3",
         "consecutive crashes after which a worker slot's circuit breaker "
         "opens and the slot is quarantined", "resilience.supervisor"),
    Knob("TPU_ML_WORKER_RESPAWN_BACKOFF_S", "float", "0.05",
         "base of the exponential backoff between respawns of a crashed "
         "worker slot", "resilience.supervisor"),
    Knob("TPU_ML_WORKER_SLOT", "int", "",
         "slot index the supervisor stamps into each worker's environment "
         "(diagnostics and slot-targeted chaos plans; never set manually)",
         "resilience.supervisor"),
    Knob("TPU_ML_ADMISSION_POLICY", "enum", "refuse",
         "`off`/`refuse`/`degrade`: what begin_fit does while the live "
         "health monitor reports FAILING — admit anyway, raise "
         "AdmissionRefused, or force the CPU-degraded fallback path",
         "telemetry.health"),
    # -- ingestion / streaming (spark.ingest) -------------------------------
    Knob("TPU_ML_MESH_LOCAL_WIRE_DTYPE", "enum", "float64",
         "wire dtype for mesh-local ingestion staging (`float32` halves "
         "the footprint)", "spark.ingest"),
    Knob("TPU_ML_MESH_LOCAL_MAX_BYTES", "int", "",
         "hard cap on mesh-local resident ingestion bytes (unset = "
         "uncapped)", "spark.ingest"),
    Knob("TPU_ML_MESH_LOCAL_ARROW_MAX_BYTES", "int", str(1 << 30),
         "Arrow-batch staging cutover for mesh-local ingestion",
         "spark.ingest"),
    Knob("TPU_ML_STREAM_CHUNK_ROWS", "int", "65536",
         "streamed-fit chunk size in rows", "spark.ingest"),
    Knob("TPU_ML_STREAM_CHUNK_FLOOR", "int", "8",
         "smallest chunk the OOM bisection may produce", "spark.ingest"),
    # -- worker device policy (localspark session <-> worker contract) ------
    Knob("TPU_ML_BARRIER_TIMEOUT_S", "float", "120",
         "barrier-stage rendezvous timeout", "localspark.session"),
    Knob("TPU_ML_WORKER_PLATFORM", "str", "",
         "jax platform a worker must initialize (env contract with the "
         "session)", "utils.devicepolicy"),
    Knob("TPU_ML_WORKER_PROBE", "flag", "",
         "`1`: workers run a bounded-time device probe at startup",
         "utils.devicepolicy"),
    Knob("TPU_ML_WORKER_PROBE_TIMEOUT", "float", "60.0",
         "seconds the worker device probe may take before failing",
         "utils.devicepolicy"),
    Knob("TPU_ML_WORKER_SCRUB_VARS", "str", "",
         "extra comma-separated env vars scrubbed from cpu-policy worker "
         "environments", "utils.devicepolicy"),
    # -- bench / perf ledger ------------------------------------------------
    Knob("TPU_ML_PERF_LEDGER_PATH", "path", "bench_history.jsonl",
         "history file bench runs append to (empty disables); never the "
         "driver's PERF_LEDGER.jsonl", "bench.py"),
    Knob("TPU_ML_PERF_SENTINEL", "flag", "",
         "`1`: bench runs tools/perf_sentinel.py --strict after appending "
         "the ledger entry", "bench.py"),
    # -- kernel precision (spark_rapids_ml_tpu.ops.policy) -------------------
    Knob("TPU_ML_PRECISION_POLICY", "enum", "f32",
         "`f32`/`bf16_f32acc`/`int8_dist` mixed-precision kernel policy "
         "default (accumulators stay f32)", "ops.policy"),
    # -- ANN vector search (spark_rapids_ml_tpu.ann + ops.ivf) --------------
    Knob("TPU_ML_ANN_CAP_PERCENTILE", "float", "99.0",
         "IVF bucket-cap percentile over cluster sizes; members beyond the "
         "cap land on the exact spill list (100 = pad every bucket to the "
         "largest cluster)", "ops.ivf"),
    Knob("TPU_ML_ANN_SAMPLE_ROWS", "int", "32768",
         "row budget of the sampled kmeans|| coarse-quantizer training set "
         "for streamed IVF index builds (0 = train on the full stream)",
         "ann.index"),
    # -- warm-path serving runtime (spark_rapids_ml_tpu.serving) ------------
    Knob("TPU_ML_SERVE_MIN_BUCKET", "int", "8",
         "serve-path row-bucket floor (smaller than the fit-path "
         "TPU_ML_MIN_BUCKET so single-row scoring pads less)",
         "serving.buckets"),
    Knob("TPU_ML_SERVE_MAX_BATCH_ROWS", "int", "4096",
         "largest serve row bucket; caps one micro-batched dispatch and "
         "bounds the AOT-compiled signature ladder", "serving.buckets"),
    Knob("TPU_ML_SERVE_MAX_DELAY_US", "float", "2000",
         "micro-batcher coalescing window CEILING: a queued request waits "
         "at most this long for same-(model,bucket) company before dispatch "
         "(the adaptive window shrinks below it under load)",
         "serving.batcher"),
    Knob("TPU_ML_SERVE_ADAPTIVE_WINDOW", "flag", "1",
         "`1`: the coalescing window tracks the observed device dispatch "
         "time (drain latency ~= device time); `0`: fixed "
         "TPU_ML_SERVE_MAX_DELAY_US window", "serving.batcher"),
    Knob("TPU_ML_SERVE_UDS_PATH", "path", "",
         "Unix-domain-socket path for the framing-free serve listener "
         "(empty = UDS transport off; co-located callers skip HTTP "
         "entirely)", "serving.server"),
    Knob("TPU_ML_SERVE_HBM_BUDGET_BYTES", "int", "",
         "byte budget of the HBM fleet manager for resident model params "
         "(unset = live device bytes_limit x TPU_ML_HEALTH_HBM_WATERMARK; "
         "cold models page to host beyond it)", "serving.hbm"),
    Knob("TPU_ML_SERVE_P99_GATE_MS", "float", "",
         "absolute serve_p99_ms ceiling bench stamps on the ledger entry "
         "for tools/perf_sentinel.py to enforce (unset = relative history "
         "gating only; also gates fleet_p99_ms in the fleet bench stage)",
         "bench.py"),
    Knob("TPU_ML_SERVE_HEDGE_FLOOR_US", "float", "2000",
         "serve-scale floor (microseconds) of the hedged-dispatch "
         "threshold: a micro-batch is re-issued when the primary dispatch "
         "exceeds max(this, TPU_ML_HEDGE_FACTOR x device-time EWMA); "
         "TPU_ML_HEDGE_FACTOR=0 disables serve hedging too",
         "serving.batcher"),
    Knob("TPU_ML_SERVE_FLEET_REPLICAS", "int", "0",
         "replica count of the multi-process serve fleet (0 = fleet off; "
         "each replica is a UDS server process with its own AOT cache "
         "warmed from the shared compile cache)", "serving.fleet"),
    Knob("TPU_ML_SERVE_FLEET_SOCKET_DIR", "path", "",
         "directory for fleet replica + router UDS sockets (empty = a "
         "fresh tempdir per fleet; must be short enough for AF_UNIX's "
         "~100-byte path limit)", "serving.fleet"),
    Knob("TPU_ML_SERVE_DRAIN_TIMEOUT_S", "float", "30",
         "rolling drain bound: max seconds the fleet router waits for a "
         "draining replica's in-flight requests to reach zero before the "
         "replica is restarted anyway", "serving.fleet"),
    # -- distributed tracing (telemetry.tracectx) ---------------------------
    Knob("TPU_ML_TRACE_SAMPLE", "float", "1.0",
         "fraction of admitted serve requests that mint a trace context "
         "(carried over HTTP/UDS/fastlane and stitched fleet-wide; 0 "
         "disables request tracing)", "telemetry.tracectx"),
    Knob("TPU_ML_TRACE_EXEMPLARS", "int", "4",
         "slowest-request exemplars (value + trace_id) retained per "
         "latency-histogram series and surfaced in serving evidence "
         "(0 disables exemplar capture)", "telemetry.tracectx"),
    # -- closed-loop model refresh (spark_rapids_ml_tpu.refresh) ------------
    Knob("TPU_ML_REFRESH_INTERVAL_S", "float", "30",
         "seconds between refresh-daemon cycles (fold pending deltas, "
         "checkpoint, attempt a hot-swap)", "refresh.daemon"),
    Knob("TPU_ML_REFRESH_MIN_ROWS", "int", "1",
         "delta rows that must fold before the daemon finalizes a "
         "candidate and attempts a swap", "refresh.daemon"),
    Knob("TPU_ML_REFRESH_CHECKPOINT_DIR", "path", "",
         "directory for the refresh daemon's durable carry checkpoints "
         "(atomic npz; empty = memory-only, no restart survival)",
         "refresh.daemon"),
    Knob("TPU_ML_SWAP_SHADOW_ROWS", "int", "256",
         "held-back sample rows the shadow-scoring gate scores a swap "
         "candidate against the live model on (0 disables the gate)",
         "refresh.daemon"),
    Knob("TPU_ML_SWAP_SHADOW_TOLERANCE", "float", "0.25",
         "max relative divergence between candidate and live outputs on "
         "the shadow sample before the swap is refused", "serving.registry"),
    Knob("TPU_ML_SWAP_PROBATION_S", "float", "60",
         "post-swap probation window: an SLO burn inside it rolls back to "
         "the prior version (which stays HBM-resident until probation "
         "clears)", "refresh.daemon"),
    # -- live health monitor (telemetry.health) -----------------------------
    Knob("TPU_ML_HEALTH_INTERVAL_S", "float", "5.0",
         "seconds between HealthMonitor poll cycles", "telemetry.health"),
    Knob("TPU_ML_HEALTH_PROBE", "enum", "inline",
         "`off`/`inline` device liveness probe mode of the health monitor",
         "telemetry.health"),
    Knob("TPU_ML_HEALTH_PROBE_TIMEOUT_S", "float", "20.0",
         "deadline of one health-monitor liveness probe", "telemetry.health"),
    Knob("TPU_ML_HEALTH_HBM_WATERMARK", "float", "0.92",
         "bytes_in_use/bytes_limit fraction above which the device "
         "component degrades", "telemetry.health"),
    Knob("TPU_ML_HEALTH_STALE_S", "float", "60.0",
         "stream-heartbeat / worker-trailer staleness threshold",
         "telemetry.health"),
    Knob("TPU_ML_HEALTH_FAILING_AFTER", "int", "3",
         "consecutive degraded polls before a component turns FAILING",
         "telemetry.health"),
    Knob("TPU_ML_HEALTH_RETRY_STORM", "int", "8",
         "retry.attempts delta per poll window that flags a retry storm",
         "telemetry.health"),
    # -- sliding-window SLOs (telemetry.slo) --------------------------------
    Knob("TPU_ML_SLO", "str", "",
         "comma list of `series:pNN:ceiling_s` latency objectives and "
         "`counter:min_rate:floor_per_s` throughput floors (empty = rolling "
         "percentiles only)", "telemetry.slo"),
    Knob("TPU_ML_SLO_WINDOW_S", "float", "300",
         "sliding evaluation window of the SLO engine", "telemetry.slo"),
    Knob("TPU_ML_SLO_BURN", "int", "2",
         "consecutive breached evaluations before slo.breach fires (burn "
         "rate)", "telemetry.slo"),
    # -- HTTP exporter (telemetry.httpd) ------------------------------------
    Knob("TPU_ML_HTTP_PORT", "int", "",
         "serve /metrics,/healthz,/slo,/report on this port (0 = ephemeral; "
         "unset = exporter off)", "telemetry.httpd"),
)

KNOBS: dict[str, Knob] = {k.name: k for k in _DECLARATIONS}

if len(KNOBS) != len(_DECLARATIONS):  # pragma: no cover - declaration bug
    raise RuntimeError("duplicate TPU_ML_* knob declaration")

# Named handles for consumers that re-export the env-var name locally
# (keeps call sites grep-able while the literal lives only here).
MIN_BUCKET = KNOBS["TPU_ML_MIN_BUCKET"]
MAX_WORKERS = KNOBS["TPU_ML_MAX_WORKERS"]
TASK_RETRIES = KNOBS["TPU_ML_TASK_RETRIES"]
DEFAULT_PRECISION = KNOBS["TPU_ML_DEFAULT_PRECISION"]
STREAM_FIT_MAX_RESIDENT_BYTES = KNOBS["TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES"]
LOG_LEVEL = KNOBS["TPU_ML_LOG_LEVEL"]
TELEMETRY_PATH = KNOBS["TPU_ML_TELEMETRY_PATH"]
TIMELINE_PATH = KNOBS["TPU_ML_TIMELINE_PATH"]
TIMELINE_EVENTS = KNOBS["TPU_ML_TIMELINE_EVENTS"]
PROGRESS = KNOBS["TPU_ML_PROGRESS"]
PEAK_TFLOPS = KNOBS["TPU_ML_PEAK_TFLOPS"]
RETRY_MAX_ATTEMPTS = KNOBS["TPU_ML_RETRY_MAX_ATTEMPTS"]
RETRY_DEADLINE_S = KNOBS["TPU_ML_RETRY_DEADLINE_S"]
STREAM_CHECKPOINT_EVERY_CHUNKS = KNOBS["TPU_ML_STREAM_CHECKPOINT_EVERY_CHUNKS"]
FOLD_WAIT_TIMEOUT_S = KNOBS["TPU_ML_FOLD_WAIT_TIMEOUT_S"]
NONFINITE_POLICY = KNOBS["TPU_ML_NONFINITE_POLICY"]
FAULT_PLAN = KNOBS["TPU_ML_FAULT_PLAN"]
HEDGE_FACTOR = KNOBS["TPU_ML_HEDGE_FACTOR"]
HEDGE_FLOOR_S = KNOBS["TPU_ML_HEDGE_FLOOR_S"]
BARRIER_RETRIES = KNOBS["TPU_ML_BARRIER_RETRIES"]
WORKER_BREAKER_THRESHOLD = KNOBS["TPU_ML_WORKER_BREAKER_THRESHOLD"]
WORKER_RESPAWN_BACKOFF_S = KNOBS["TPU_ML_WORKER_RESPAWN_BACKOFF_S"]
WORKER_SLOT = KNOBS["TPU_ML_WORKER_SLOT"]
ADMISSION_POLICY = KNOBS["TPU_ML_ADMISSION_POLICY"]
MESH_LOCAL_WIRE_DTYPE = KNOBS["TPU_ML_MESH_LOCAL_WIRE_DTYPE"]
MESH_LOCAL_MAX_BYTES = KNOBS["TPU_ML_MESH_LOCAL_MAX_BYTES"]
MESH_LOCAL_ARROW_MAX_BYTES = KNOBS["TPU_ML_MESH_LOCAL_ARROW_MAX_BYTES"]
STREAM_CHUNK_ROWS = KNOBS["TPU_ML_STREAM_CHUNK_ROWS"]
STREAM_CHUNK_FLOOR = KNOBS["TPU_ML_STREAM_CHUNK_FLOOR"]
BARRIER_TIMEOUT_S = KNOBS["TPU_ML_BARRIER_TIMEOUT_S"]
WORKER_PLATFORM = KNOBS["TPU_ML_WORKER_PLATFORM"]
WORKER_PROBE = KNOBS["TPU_ML_WORKER_PROBE"]
WORKER_PROBE_TIMEOUT = KNOBS["TPU_ML_WORKER_PROBE_TIMEOUT"]
WORKER_SCRUB_VARS = KNOBS["TPU_ML_WORKER_SCRUB_VARS"]
PERF_LEDGER_PATH = KNOBS["TPU_ML_PERF_LEDGER_PATH"]
PERF_SENTINEL = KNOBS["TPU_ML_PERF_SENTINEL"]
PRECISION_POLICY = KNOBS["TPU_ML_PRECISION_POLICY"]
ANN_CAP_PERCENTILE = KNOBS["TPU_ML_ANN_CAP_PERCENTILE"]
ANN_SAMPLE_ROWS = KNOBS["TPU_ML_ANN_SAMPLE_ROWS"]
SERVE_MIN_BUCKET = KNOBS["TPU_ML_SERVE_MIN_BUCKET"]
SERVE_MAX_BATCH_ROWS = KNOBS["TPU_ML_SERVE_MAX_BATCH_ROWS"]
SERVE_MAX_DELAY_US = KNOBS["TPU_ML_SERVE_MAX_DELAY_US"]
SERVE_ADAPTIVE_WINDOW = KNOBS["TPU_ML_SERVE_ADAPTIVE_WINDOW"]
SERVE_UDS_PATH = KNOBS["TPU_ML_SERVE_UDS_PATH"]
SERVE_HBM_BUDGET_BYTES = KNOBS["TPU_ML_SERVE_HBM_BUDGET_BYTES"]
SERVE_P99_GATE_MS = KNOBS["TPU_ML_SERVE_P99_GATE_MS"]
SERVE_HEDGE_FLOOR_US = KNOBS["TPU_ML_SERVE_HEDGE_FLOOR_US"]
SERVE_FLEET_REPLICAS = KNOBS["TPU_ML_SERVE_FLEET_REPLICAS"]
SERVE_FLEET_SOCKET_DIR = KNOBS["TPU_ML_SERVE_FLEET_SOCKET_DIR"]
SERVE_DRAIN_TIMEOUT_S = KNOBS["TPU_ML_SERVE_DRAIN_TIMEOUT_S"]
TRACE_SAMPLE = KNOBS["TPU_ML_TRACE_SAMPLE"]
TRACE_EXEMPLARS = KNOBS["TPU_ML_TRACE_EXEMPLARS"]
REFRESH_INTERVAL_S = KNOBS["TPU_ML_REFRESH_INTERVAL_S"]
REFRESH_MIN_ROWS = KNOBS["TPU_ML_REFRESH_MIN_ROWS"]
REFRESH_CHECKPOINT_DIR = KNOBS["TPU_ML_REFRESH_CHECKPOINT_DIR"]
SWAP_SHADOW_ROWS = KNOBS["TPU_ML_SWAP_SHADOW_ROWS"]
SWAP_SHADOW_TOLERANCE = KNOBS["TPU_ML_SWAP_SHADOW_TOLERANCE"]
SWAP_PROBATION_S = KNOBS["TPU_ML_SWAP_PROBATION_S"]
HEALTH_INTERVAL_S = KNOBS["TPU_ML_HEALTH_INTERVAL_S"]
HEALTH_PROBE = KNOBS["TPU_ML_HEALTH_PROBE"]
HEALTH_PROBE_TIMEOUT_S = KNOBS["TPU_ML_HEALTH_PROBE_TIMEOUT_S"]
HEALTH_HBM_WATERMARK = KNOBS["TPU_ML_HEALTH_HBM_WATERMARK"]
HEALTH_STALE_S = KNOBS["TPU_ML_HEALTH_STALE_S"]
HEALTH_FAILING_AFTER = KNOBS["TPU_ML_HEALTH_FAILING_AFTER"]
HEALTH_RETRY_STORM = KNOBS["TPU_ML_HEALTH_RETRY_STORM"]
SLO = KNOBS["TPU_ML_SLO"]
SLO_WINDOW_S = KNOBS["TPU_ML_SLO_WINDOW_S"]
SLO_BURN = KNOBS["TPU_ML_SLO_BURN"]
HTTP_PORT = KNOBS["TPU_ML_HTTP_PORT"]


def markdown_table() -> str:
    """The README knob table, generated (see tools/tpulint.py
    --list-knobs --markdown and the --check-readme drift gate)."""
    lines = [
        "| knob | type | default | meaning | read by |",
        "|------|------|---------|---------|---------|",
    ]
    for k in _DECLARATIONS:
        default = f"`{k.default}`" if k.default else "unset"
        lines.append(
            f"| `{k.name}` | {k.type} | {default} | {k.doc} | `{k.module}` |"
        )
    return "\n".join(lines)
