"""Canonical registry of telemetry names: metrics, span phases, instants.

A typo'd name at a call site does not crash — it silently mints a fresh
metric family that no dashboard, FitReport consumer, or trace-report
anomaly check ever reads. This module is the single declaration point the
linter (``tools/tpulint.py`` rule TPL005) cross-checks every string literal
passed to ``counter_inc``/``gauge_set``/``histogram_record``,
``trace_range``, ``record_span``/``record_instant`` and
``resilience.faults.inject`` against — adding a new series means adding it
here first, which is exactly the point.

Import-pure: no jax, no package siblings, usable from the linter and from
jax-free worker processes.
"""

from __future__ import annotations

# -- metric families (telemetry.registry counter/gauge/histogram names) ----

METRICS: frozenset[str] = frozenset({
    # ingestion / data movement
    "ingest.rows",
    "ingest.bytes",
    "ingest.chunk_rows",
    "h2d.bytes",
    # addressable shards the streamed fold put, once a chunk (path="stream"):
    # the data axis of the mesh, so chunks x devices over a fit
    "h2d.shards",
    # pieces of its chunks the streamed fold put, most of them while the
    # chunk was still staged (path="stream"); the resident ingest puts none
    "h2d.pieces",
    # chunks whose pieces stopped being put ahead after a put or a landing
    # failed in a way the dispatch retries or bisects (path="stream")
    "h2d.put_ahead_abandoned",
    # every host-to-device transfer from its issue to its landing, booked
    # when its arrays are ready (spark.ingest._Transfers; path="stream" or
    # "mesh"): issue to ready by device (histogram); summed over the devices,
    # the seconds a device had a transfer issued and not ready, and the
    # seconds any device had one (exact unions, by a count in flight); the
    # bytes that became ready; the waits that raised
    "h2d.transfer_seconds",
    "h2d.link_busy_seconds",
    "h2d.any_link_busy_seconds",
    "h2d.transfer_bytes",
    "h2d.transfers_failed",
    # rows the resident ingest padded its shards with (padded_rows - rows,
    # once an ingest): zero rows of weight 0 that every pass walks
    "mesh.pad_rows",
    "columnar.rows",
    "columnar.bytes",
    # collectives / distributed aggregation
    "collective.bytes",
    "collective.count",
    "collective.tree_combines",
    "collective.dispatch",
    "drivermerge.passes",
    "drivermerge.bytes",
    # streamed-fit lifecycle
    "stream.checkpoints",
    "stream.resumes",
    "stream.overlap_fraction",
    "chunk.bisections",
    "rows.nonfinite_skipped",
    # chunks whose H2D transfer was still in flight when their fold was
    # enqueued: the fold's device time then holds a wait for the DMA
    "fold.input_in_flight",
    # one increment a chunk staged: state="reused" (a kept staging set
    # written again), "fresh" (none to reuse) or "aliased" (the arrays put
    # from the old one share its memory, so a new one was taken)
    "stage.buffers",
    # one increment for each slice of a batch copied into a staging set
    # (a batch, or each part of one that straddles a chunk's end), by the
    # path its copy took: path="pool" (cut by rows over the host pass's
    # threads) or "inline" (too small to cut: the caller's thread)
    "ingest.batches",
    # one increment for each chunk the streamed fold asked about (whether
    # it is finite everywhere, before its fold): where="device" (what put
    # returned is a jax.Array: program jit__chunk_finite) or "host" (a host
    # array: the staged buffers), clean="yes" or "no"
    "ingest.verdicts",
    # Lloyd iterations a fit's program ran, by path (a loop that met its
    # tolerance or a fixed point runs fewer than maxIter)
    "kmeans.iterations",
    # those of them whose sums were the one-hot in bfloat16 against the
    # exact bfloat16 parts of the rows (ops.kmeans.exact_bf16_parts says
    # which dtypes have such parts: float32 rows), by path
    "kmeans.split_iterations",
    # Newton iterations a logistic fit's program ran (binary or softmax), by
    # path (a loop that met its tolerance runs fewer than maxIter)
    "logreg.iterations",
    # trees a forest fit grew, and the split nodes among them, booked from
    # the trees handed back to the host, by path (a shallow build shows as
    # fewer split nodes for the same trees)
    "forest.trees",
    "forest.split_nodes",
    # spans: duration, and duration less what child spans covered
    "span.seconds",
    "span.self_seconds",
    # compile monitoring (telemetry.compilemon event mappings)
    "compile.count",
    "compile.seconds",
    "compile.program_seconds",
    "compile.trace_seconds",
    "compile.lower_seconds",
    "compile.other_seconds",
    "compile.cache_hits",
    "compile.cache_misses",
    "compile.cache_time_saved_s",
    "compile.cache_load_seconds",
    # resilience
    "retry.attempts",
    "fault.injected",
    "degraded.cpu_fallback",
    # elastic stage scheduler (resilience.supervisor + localspark.session)
    "scheduler.tasks",
    "scheduler.hedge",
    "scheduler.reassign",
    "scheduler.barrier_retry",
    "scheduler.admission",
    "worker.respawn",
    "worker.quarantine",
    "worker.slots",
    "worker.quarantined",
    # live health monitor (telemetry.health)
    "health.state",
    "health.transitions",
    "health.probe_seconds",
    "stream.last_beat",
    "stream.active",
    "worker.last_trailer",
    # sliding-window SLO engine (telemetry.slo)
    "slo.breach",
    "slo.value",
    "slo.target",
    "slo.rolling",
    # HTTP exporter (telemetry.httpd)
    "http.requests",
    # warm-path serving runtime (spark_rapids_ml_tpu.serving)
    "serve.requests",
    "serve.rows",
    "serve.errors",
    "serve.latency",
    "serve.queue_delay_seconds",
    "serve.batches",
    "serve.batch_rows",
    "serve.bucket_hits",
    "serve.models",
    "serve.aot_compiles",
    "serve.cold_compiles",
    # serving fast path (transports, continuous batching, HBM fleet)
    "serve.transport",
    "serve.joined_in_flight",
    "serve.window_effective_seconds",
    "serve.page_in",
    "serve.page_out",
    "serve.hbm_bytes",
    "serve.shed",
    # serve tail hunt: µs queue-delay series, JSON-free lane, hedged
    # dispatch, multi-process fleet (serving.fastlane / serving.fleet)
    "serve.queue_delay_us",
    "serve.json_codec",
    "serve.hedges",
    "serve.hedge_wins",
    "serve.fleet_replicas",
    "serve.route_hits",
    "serve.route_misses",
    "serve.drain_events",
    "serve.replica_restarts",
    # distributed tracing (telemetry.tracectx): traces minted at admission
    "serve.traces",
    # closed-loop model refresh / atomic hot-swap (refresh + serving.registry)
    "serve.swaps",
    "serve.swap_refused",
    "serve.rollback",
    "serve.swap_blackout_seconds",
    "serve.model_version",
    "refresh.folds",
    "refresh.rows",
    "refresh.checkpoints",
    "refresh.resumes",
    "refresh.finalizes",
    "refresh.lag_seconds",
    # ANN vector search subsystem (spark_rapids_ml_tpu.ann)
    "ann.queries",
    "ann.build_rows",
    "ann.spill_fraction",
    "ann.cells_reseeded",
    # serve path
    "transform.rows",
    "transform.bytes",
    "transform.batches",
    "transform.partitions",
    "transform.partition_seconds",
    # cost model
    "costmodel.calls",
    "costmodel.flops",
    "costmodel.bytes",
    "costmodel.roofline_utilization",
    # report re-aggregation (tools/metrics_dump.py Prometheus export)
    "fits",
    "fit.wall_seconds",
    "transforms",
    "transform.wall_seconds",
})

# Metric families minted with a dynamic suffix (one registered prefix per
# family; the dynamic tail is data, not a name).
METRIC_PREFIXES: tuple[str, ...] = (
    "device.",  # telemetry.compilemon device memory gauges: device.<stat>
    # metrics_dump re-emits a transform report's latency digest as
    # representative histogram samples, one family per quantile
    "transform.partition_seconds_",
)

# -- metric family kinds ----------------------------------------------------
# Families not listed below are counters. tools/metrics_dump.py routes each
# family through its natural kind when re-aggregating, and the names-family
# meta-check (tests/test_timeline.py) asserts every family's Prometheus
# TYPE matches the kind declared here — adding a histogram or gauge family
# to METRICS without declaring it fails CI before it silently renders as a
# counter on a dashboard.

HISTOGRAMS: frozenset[str] = frozenset({
    "span.seconds",
    "span.self_seconds",
    "h2d.transfer_seconds",
    "compile.seconds",
    "compile.program_seconds",
    "compile.cache_load_seconds",
    "compile.trace_seconds",
    "compile.lower_seconds",
    "compile.other_seconds",
    "health.probe_seconds",
    "ingest.chunk_rows",
    "stream.overlap_fraction",
    "transform.partition_seconds",
    "costmodel.roofline_utilization",
    "fit.wall_seconds",
    "transform.wall_seconds",
    "serve.latency",
    "serve.queue_delay_seconds",
    "serve.queue_delay_us",
    "serve.window_effective_seconds",
    "serve.batch_rows",
    "serve.swap_blackout_seconds",
})

GAUGES: frozenset[str] = frozenset({
    "stream.active",
    "stream.last_beat",
    "worker.last_trailer",
    "health.state",
    "slo.value",
    "slo.target",
    "slo.rolling",
    "worker.slots",
    "worker.quarantined",
    "serve.models",
    "serve.model_version",
    "serve.hbm_bytes",
    "serve.fleet_replicas",
    "refresh.lag_seconds",
})

# -- span phases (trace_range names -> span.seconds{phase=...}) ------------

SPAN_PHASES: frozenset[str] = frozenset({
    # distributed request tracing (telemetry.tracectx + serving plane)
    "serve.request",
    "serve.queue",
    "serve.dispatch",
    "serve.relay",
    "refresh.fold",
    "refresh.swap",
    "refresh.probation",
    # streamed-fit / dispatch machinery
    "fold.dispatch",
    "fold.enqueue",
    "fold.wait",
    "fold.finalize",
    "h2d.put",
    "h2d.wait",
    # a transfer from the moment its waiting thread turns to it to its ready
    # (spark.ingest._Transfers: a timeline span and a TraceAnnotation on
    # that thread, no span.seconds series)
    "h2d.transfer",
    "ingest.chunk",
    "ingest.scan",
    "ingest.stage",
    "stage.reclaim",
    "mesh.ingest",
    "model.to_host",
    "transform.plan",
    "transform.dispatch",
    # cross-process timeline span events
    "worker.task",
    "transform.partition",
    # linalg / decomposition
    "compute cov",
    "eigh",
    "svd from r",
    "svd mesh fit",
    "tsvd decompose",
    "tsvd reduce",
    "tsvd transform",
    "tsvd mesh fit",
    "tsvd mesh-local fit",
    "pca transform",
    # scalers / preprocessing
    "scaler moments",
    "scaler range stats",
    "scaler transform",
    "robust scaler histogram",
    "robust transform",
    "maxabs transform",
    "minmax transform",
    "normalize",
    "binarize",
    "bucketize",
    "quantile bucketize",
    "quantile discretizer histogram",
    "quantile sketch histogram",
    "impute",
    "imputer fit",
    "polynomial expansion",
    "elementwise product",
    "vector slicer",
    "dct",
    "variance selector fit",
    "variance selector transform",
    "label scan",
    # linear family
    "linreg solve",
    "linreg stats",
    "logreg newton",
    "logreg transform",
    "logreg mesh fit",
    "logreg mesh-local fit",
    "logreg mesh-local chunked fit",
    "softmax newton",
    "softmax mesh fit",
    "svc mesh-local fit",
    "svc transform",
    "isotonic pav",
    # clustering
    "kmeans init",
    "kmeans lloyd",
    "kmeans transform",
    "kmeans mesh fit",
    "kmeans mesh init",
    # its two halves: the seeding program's dispatch and the wait for its
    # counts; the eager weighted k-means++ and the centres' copy to the host
    "kmeans.seed.rounds",
    "kmeans.seed.reduce",
    "kmeans mesh-local fit",
    "kmeans mesh-local chunked fit",
    "dbscan cluster",
    "dbscan spark cluster",
    # trees / ensembles / misc models
    "forest build",
    # a resident forest fit (SparkRandomForest*, mesh-local): the whole fit,
    # and its bin edges and bins made on the device with the wait for them
    "forest mesh-local fit",
    "forest.bin",
    "gbt boost",
    "fm train",
    "mlp train",
    "naive bayes stats",
    "naive bayes stats (mesh)",
    "naive bayes variance pass",
    "one-vs-rest fit",
    "one-vs-rest transform",
    # neighbors / umap
    "knn kneighbors",
    "ivf build",
    "ivf kneighbors",
    "ann build",
    "ann pack",
    "ann query",
    "umap init",
    "umap knn graph",
    "umap fuzzy graph",
    "umap layout",
    "umap transform",
})

# -- timeline instant events (flight-recorder record_instant names) --------

INSTANTS: frozenset[str] = frozenset({
    "stream.chunk",
    "stream.checkpoint",
    "stream.resume",
    "chunk.bisection",
    "collective.dispatch",
    "retry",
    "fault.injected",
    "health.transition",
    "slo.breach",
    "scheduler.hedge",
    "scheduler.reassign",
    "scheduler.barrier_retry",
    "scheduler.admission",
    "worker.quarantine",
    "serve.swap",
    "serve.rollback",
})
