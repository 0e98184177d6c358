"""Benchmark: one JSON line — kernel fit time (primary) + end-to-end and
accuracy metrics (extras).

**Primary metric** (unchanged program since r1): PCA.fit device wall-clock
on the flagship path. Workload: BASELINE.json config-2 shape scaled to a
single chip — k=50 on 2M×512 f32, data device-resident (matching the
reference's semantics, where ColumnarRdd hands fit() device-resident cudf
tables). The measured program is the full fit exactly as the reference
observably computes it (RapidsRowMatrix.scala:111-117: uncentered Gram) —
Gram on the MXU (3-pass bf16 split, Precision.HIGH) + randomized subspace
decomposition + sign-flip + explained variance.

Methodology: whether ``block_until_ready`` is a sound fence and how large
the per-dispatch constant is are unverified on this machine (ROADMAP S1
measures both), so single-dispatch timing is not used yet. We time a
``lax.scan`` chain of N fits inside ONE program — each iteration's input
multiplied by (1 + carry·1e-38) so XLA can neither hoist nor
dead-code-eliminate the work, and the outputs consumed via full
reductions — and take the slope between N=12 and N=2 runs. r2 showed
27% round-to-round drift with min-of-3 single-slope timing, so the slope is
now computed per (short, long) PAIR and the reported value is the MEDIAN of
5 pairs, with the spread published alongside.

**Extras** (VERDICT r2 weak #4/#5 — measure what users run, and make the
accuracy claim an artifact, not a comment):
- ``pca_transform_throughput``: BASELINE config-3 proxy — device rows/s of
  PCAModel's projection on the same 2M×512 → k=50 shape.
- ``df_fit_end_to_end``: wall-clock of a LIVE DataFrame fit through
  localspark (ingestion + worker hop + Arrow collect + device Gram on the
  driver mesh, distribution='mesh-local' — the one-device-owner-per-host
  deployment this machine runs).
- ``eigvec_min_cosine``: min per-component |cosine| of THIS bench's exact
  program (HIGH-precision Gram + randomized solver, uncentered) vs an f64
  host oracle on a 200k×512 slice, executed on the real chip every round;
  ``accuracy_ok`` records the ≥0.9999 north-star bar (BASELINE.md); a miss
  also exits non-zero AFTER emitting the JSON line, so pipelines gate on it.
- ``kmeans_lloyd_rows_per_s``: BASELINE config-5 proxy (the stretch
  estimator: 50M×128 k=1000 scaled to one chip's HBM) — device rows/s of
  one full Lloyd iteration (blocked pairwise distances + argmin + the
  KMeansStats monoid) at 4M×128, k=1000, f32. The blocked kernel turns
  the distance matrix into [block,128]×[128,1000] MXU matmuls
  (ops/kmeans.py), so this measures the same roofline the RAFT
  pairwise-distance kernel chases on the A100.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
comparison point is the north-star proxy: an A100 running the RAFT f64 path
on the same shape. Model: cov GEMM 2·rows·n² = 1.05 TFLOP at ~70% of A100's
19.5 TF/s f64 tensor-core peak, +20% for syevd/transfers ≈ 0.092 s.
vs_baseline = a100_estimate / measured (higher is better; >1 beats it).
"""

import json
import os
import statistics
import sys
import time

import numpy as np

from spark_rapids_ml_tpu.utils import knobs

ROWS = 2_000_000
N = 512
K = 50
A100_ESTIMATE_S = 0.092
PAIRS = 5
ACCURACY_ROWS = 200_000
DF_ROWS = 250_000  # streamed mesh-local ingest (r4): host RSS is O(shard),
DF_N = 256         # so the end-to-end shape is no longer driver-RAM-bound
KM_ROWS = 4_000_000
KM_N = 128
KM_K = 1000
KNN_CORPUS = 262_144  # exact brute-force k-NN throughput (r5 family)
KNN_QUERIES = 2_048
KNN_N = 256
KNN_K = 10
RF_ROWS = 1_048_576  # random-forest build throughput (r5 family)
RF_FEATURES = 32
RF_TREES = 8
RF_DEPTH = 6
RF_BINS = 32
SF_ROWS = 1_048_576  # out-of-core streamed fit (this PR): donated-carry
SF_N = 512           # chunk fold pipeline, spark.ingest.stream_fold
SF_CHUNK = 65_536
ANN_ROWS = 4_194_304   # streamed IVF vector search (this PR): the corpus
ANN_N = 64             # is only ever resident one chunk at a time
ANN_NLIST = 2_048
ANN_NPROBE = 2
ANN_K = 10             # recall@10 is the ledger accuracy metric
ANN_CHUNK = 65_536
ANN_QUERY_BATCH = 2_048
ANN_ORACLE_QUERIES = 256

# --smoke: run the WHOLE bench pipeline at tiny shapes on the CPU backend.
# Rationale (r3 post-mortem): the bench script itself was only ever executed
# at snapshot time on the real chip, so pipeline bitrot surfaced as rc=1
# with zero recorded numbers. The smoke mode proves every stage (data gen,
# paired-slope timing, transform/KMeans/accuracy/DataFrame metrics, JSON
# contract) end-to-end in seconds, with numbers that are meaningless as
# performance but exercise identical code.
SMOKE = "--smoke" in sys.argv

if SMOKE:
    ROWS, N, K = 20_000, 64, 8
    ACCURACY_ROWS = 5_000
    DF_ROWS, DF_N = 4_000, 32
    KM_ROWS, KM_N, KM_K = 20_000, 16, 20
    KNN_CORPUS, KNN_QUERIES, KNN_N, KNN_K = 4_096, 256, 32, 5
    RF_ROWS, RF_FEATURES, RF_TREES, RF_DEPTH, RF_BINS = 8_192, 8, 2, 3, 8
    SF_ROWS, SF_N, SF_CHUNK = 16_384, 32, 2_048
    # the ANN shape shrinks least: the 100x-vs-exact and recall@10 gates
    # are real acceptance bars even in smoke, and both need a corpus big
    # enough that an inverted index actually pays for its coarse pass.
    # nprobe drops to 1: on the CPU backend the per-query bucket gather,
    # not the MXU cross term, is the scan cost, and the well-separated
    # smoke clusters keep recall@10 ~1.0 with a single probe
    ANN_ROWS, ANN_N, ANN_NLIST, ANN_NPROBE = 1_048_576, 32, 2_048, 1
    PAIRS = 2


def _paired_slope(short_call, long_call, iter_delta: int, reps: int):
    """(median per-iteration slope, raw slopes) — THE timing methodology
    every metric here shares: time a short and a long dependent-op chain
    back to back, difference out the per-dispatch constant, repeat
    ``reps`` times, take the median (r2 weak #4: min-of-N drifted 27%).
    Raises on a non-positive median — a noisy inversion must fail the
    metric loudly, never publish a negative throughput."""
    from spark_rapids_ml_tpu.telemetry import reset_metrics

    slopes = []
    for _ in range(reps):
        # per-pair registry window: phase numbers in the embedded telemetry
        # snapshot attribute to the LAST (short, long) pair of the last
        # metric, never to the whole accumulated session
        reset_metrics()
        t0 = time.perf_counter()
        short_call()
        t_short = time.perf_counter() - t0
        t0 = time.perf_counter()
        long_call()
        t_long = time.perf_counter() - t0
        slopes.append((t_long - t_short) / iter_delta)
    med = statistics.median(slopes)
    if med <= 0:
        raise RuntimeError(
            f"non-positive paired slope {med!r}: timing noise swamped the "
            "chain difference"
        )
    return med, slopes


def _ledger_path() -> str:
    """This script's own run history: ``TPU_ML_PERF_LEDGER_PATH`` override,
    or ``bench_history.jsonl`` next to this script ('' disables it). Never
    ``PERF_LEDGER.jsonl`` — that file is the driver's record."""
    env = os.environ.get(knobs.PERF_LEDGER_PATH.name)
    if env is not None:
        return env
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_history.jsonl"
    )


def _ledger_entry(record: dict) -> dict:
    """Flatten one bench JSON record into a perf-ledger line: every metric
    as ``name -> {value, unit}`` (what tools/perf_sentinel.py compares
    across runs) plus the run's analytical cost-model numbers."""
    metrics = {
        record["metric"]: {"value": record["value"], "unit": record["unit"]}
    }
    for extra in record.get("extra_metrics", []):
        metrics[extra["metric"]] = {
            "value": extra["value"],
            "unit": extra.get("unit", ""),
        }
        # a declared absolute bound rides the ledger entry itself so the
        # sentinel can enforce it regardless of history (and --bless
        # cannot wave it through)
        if isinstance(extra.get("ceiling"), (int, float)):
            metrics[extra["metric"]]["ceiling"] = extra["ceiling"]
    from spark_rapids_ml_tpu.telemetry import REGISTRY, costmodel

    snap = REGISTRY.snapshot()
    cost = {
        "kernels": costmodel.kernel_costs(),
        "analytical_flops": snap.counter("costmodel.flops"),
        "analytical_bytes": snap.counter("costmodel.bytes"),
        "peak_flops": costmodel.peak_flops(),
    }
    entry = {
        "type": "perf_ledger",
        "schema": 1,
        "timestamp_unix": time.time(),
        "smoke": SMOKE,
        "metrics": metrics,
        "cost_model": cost,
        "derived": record.get("derived"),
        # overall health-monitor verdict at bench time (the _bench_health
        # stage's rollup): a DEGRADED/FAILING stamp tells the sentinel's
        # reader that a slow entry may be environment, not regression
        "health_state": (record.get("health") or {}).get("state"),
        # serving-stage evidence blob (bucket hits, queue delay, compiles)
        # so tools/serve_report.py renders straight off the ledger
        "serving": record.get("serving"),
        # hot-swap-under-load proof (blackout, refresh lag, probation):
        # serve_report's torn-swap checks read this off the same line
        "refresh": record.get("refresh"),
        # fleet-stage evidence (routing, rolling restart, cross-process
        # trace coverage + clock offsets): serve_report's fleet tracing
        # render and orphan-span anomaly read it off the ledger entry
        "fleet": record.get("fleet"),
        # elastic-scheduler counters for the whole bench process: a ledger
        # entry whose wall-clock regressed WITH nonzero hedges/reassigns/
        # quarantines is a sick run, not a perf regression — the sentinel's
        # reader needs that distinction on the entry itself
        "scheduler": {
            "hedges": snap.counter("scheduler.hedge"),
            "reassigns": snap.counter("scheduler.reassign"),
            "quarantines": snap.counter("worker.quarantine"),
            "barrier_retries": snap.counter("scheduler.barrier_retry"),
        },
    }
    # stamp the tuning signature ONLY when the run deviates from the
    # default (a non-f32 precision policy): default runs omit the key, so
    # their sentinel signature stays "{}" and keeps matching the ledger
    # history from before the field (tools/perf_sentinel.py)
    from spark_rapids_ml_tpu.ops.policy import resolve_policy

    policy = resolve_policy(None)
    if policy != "f32":
        entry["tuning"] = {"policy": policy}
    return entry


def _emit_result(record: dict) -> None:
    """Print the bench JSON line, append it to the perf ledger, and — under
    ``TPU_ML_PERF_SENTINEL=1`` — gate the run on tools/perf_sentinel.py
    ``--strict`` (regression vs the median of prior ledger entries fails
    the process). The opt-in keeps tier-1 deterministic while CI can turn
    ``bench --smoke`` into a perf regression gate."""
    print(json.dumps(record))
    path = _ledger_path()
    appended = False
    if path:
        try:
            with open(path, "a", encoding="utf-8") as f:
                f.write(
                    json.dumps(_ledger_entry(record), sort_keys=True) + "\n"
                )
            appended = True
        except OSError as e:
            print(f"perf ledger append to {path} failed: {e}",
                  file=sys.stderr)
    if appended and os.environ.get(knobs.PERF_SENTINEL.name) == "1":
        import subprocess

        sentinel = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "tools", "perf_sentinel.py",
        )
        proc = subprocess.run(
            [sys.executable, sentinel, path, "--strict"],
            capture_output=False,
        )
        if proc.returncode != 0:
            raise SystemExit(proc.returncode)


def main() -> None:
    # A measurement path that finds no chip fails: the full bench runs on
    # the TPU or not at all, and --smoke is the CPU contract test. Neither
    # selects a platform — that is the caller's JAX_PLATFORMS.
    import jax

    platform = jax.devices()[0].platform
    want = "cpu" if SMOKE else "tpu"
    if platform != want:
        raise SystemExit(
            f"bench.py{' --smoke' if SMOKE else ''} runs on {want!r} but JAX "
            f"reports platform {platform!r} — nothing was run"
        )
    from spark_rapids_ml_tpu.utils.config import enable_compilation_cache

    enable_compilation_cache()  # the raw kernels below precede any fit()

    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_ml_tpu.ops import linalg as L

    # Generate device-side (correlated data: realistic spectrum) — pushing
    # 8 GB of host-generated randoms over the host link would dominate
    # setup time and prove nothing.
    @jax.jit
    def make_data(seed):
        kb, km, kn = jax.random.split(jax.random.PRNGKey(seed), 3)
        base = jax.random.normal(kb, (ROWS, 64), jnp.float32)
        mix = jax.random.normal(km, (64, N), jnp.float32)
        return base @ mix + 0.1 * jax.random.normal(kn, (ROWS, N), jnp.float32)

    x = make_data(7)
    float(jnp.sum(x[0]))  # force materialization

    def fit_pca(a):
        # Precision.HIGH: 3-pass bf16 split for the Gram — at the measured
        # MXU roofline (16.7 ms of the total; a hand-written Pallas
        # upper-triangle kernel reached 23 ms despite 37.5% fewer flops —
        # see ops/pallas_gram.py). Decomposition: HMT randomized subspace
        # iteration with oversample=20 (k=50 ≪ n=512 makes the O(n²·l)
        # solver strictly profitable vs the O(n³)+refinement eigh).
        # mean_centering=False is the reference's observable fit (its
        # centering is a TODO stub, RapidsRowMatrix.scala:111-117).
        return L.pca_fit_from_cov(
            L.gram(a, precision=lax.Precision.HIGH),
            K,
            solver="randomized",
            oversample=20,
        )

    # one compiled program for both the transform-proxy and accuracy
    # sections below (a fresh jax.jit per use would retrace); main() runs
    # once per bench process  # tpulint: disable=TPL003
    fit_pca_jit = jax.jit(fit_pca)

    def fit_consumed(a):
        pc, ev = fit_pca(a)
        return jnp.sum(pc) + jnp.sum(ev)

    def make_chain(n_iter):
        @jax.jit
        def f(a):
            def step(c, _):
                return fit_consumed(a * (1.0 + c * 1e-38)), None

            out, _ = lax.scan(step, jnp.float32(0), None, length=n_iter)
            return out

        return f

    short_chain, long_chain = make_chain(2), make_chain(12)
    float(short_chain(x)), float(long_chain(x))  # compile + warm up

    per_fit, slopes = _paired_slope(
        lambda: float(short_chain(x)), lambda: float(long_chain(x)), 10, PAIRS
    )

    # --- config-3 proxy: transform (projection) throughput ----------------
    # same paired-slope methodology as the fit metric — single-dispatch
    # timing would fold the per-dispatch constant into the number
    pc, _ = fit_pca_jit(x)

    def make_transform_chain(n_iter):
        @jax.jit
        def f(a, p):
            def step(c, _):
                return c + jnp.sum(L.project(a * (1.0 + c * 1e-38), p)), None

            out, _ = lax.scan(step, jnp.float32(0), None, length=n_iter)
            return out

        return f

    tr_short, tr_long = make_transform_chain(2), make_transform_chain(12)
    float(tr_short(x, pc)), float(tr_long(x, pc))  # warm up
    tr_med, _ = _paired_slope(
        lambda: float(tr_short(x, pc)), lambda: float(tr_long(x, pc)), 10, 3
    )
    transform_rows_per_s = ROWS / tr_med

    # --- config-5 proxy: KMeans Lloyd iteration throughput ----------------
    # chained REAL Lloyd iterations (update_centers feeds the next step's
    # centers) so XLA can neither hoist nor elide any iteration; slope
    # between chain lengths removes dispatch latency like the fit metric.
    from spark_rapids_ml_tpu.ops import kmeans as KM

    @jax.jit
    def make_km_data(seed):
        kb, kc = jax.random.split(jax.random.PRNGKey(seed))
        pts = jax.random.normal(kb, (KM_ROWS, KM_N), jnp.float32)
        # pull rows toward KM_K anchor points for a realistic cluster shape
        anchors = 4.0 * jax.random.normal(kc, (KM_K, KM_N), jnp.float32)
        return pts + anchors[jnp.arange(KM_ROWS) % KM_K]

    xk = make_km_data(11)
    centers0 = xk[:: KM_ROWS // KM_K][:KM_K]
    w = jnp.ones((KM_ROWS,), jnp.float32)

    def make_lloyd_chain(n_iter):
        @jax.jit
        def f(a, c0):
            def step(c, _):
                stats = KM.kmeans_stats(a, c, w)
                return KM.update_centers(stats, c), stats.cost

            c, costs = lax.scan(step, c0, None, length=n_iter)
            return jnp.sum(c) + jnp.sum(costs)

        return f

    km_short, km_long = make_lloyd_chain(1), make_lloyd_chain(4)
    float(km_short(xk, centers0)), float(km_long(xk, centers0))  # warm up
    km_med, _ = _paired_slope(
        lambda: float(km_short(xk, centers0)),
        lambda: float(km_long(xk, centers0)),
        3,
        3,
    )
    kmeans_rows_per_s = KM_ROWS / km_med
    del xk  # free ~2 GB of HBM before the accuracy pass

    # --- exact k-NN query throughput (r5 family; MXU tournament) ----------
    # guarded: a failure here must never cost the primary metric
    try:
        knn_qps = _bench_knn()
    except Exception as e:  # pragma: no cover - defensive
        print(f"# knn bench skipped: {e!r}", file=sys.stderr)
        knn_qps = None

    # --- random-forest build throughput (r5 family) -----------------------
    try:
        rf_rows_per_s = _bench_forest()
    except Exception as e:  # pragma: no cover - defensive
        print(f"# forest bench skipped: {e!r}", file=sys.stderr)
        rf_rows_per_s = None

    # --- out-of-core streamed fit throughput (this PR) --------------------
    try:
        sf_rows_per_s, sf_overlapped, sf_overlap_fraction = _bench_streamed_fit()
    except Exception as e:  # pragma: no cover - defensive
        print(f"# streamed-fit bench skipped: {e!r}", file=sys.stderr)
        sf_rows_per_s = sf_overlapped = sf_overlap_fraction = None

    # --- live health/SLO exporter proof (this PR) -------------------------
    # the exporter must serve a parse-clean scrape of the counters the
    # streamed-fit stage above just recorded, and /healthz must say OK on
    # this healthy process; hard contract in --smoke, guarded on-chip
    try:
        health_evidence = _bench_health()
    except Exception as e:
        if SMOKE:
            raise
        print(f"# health bench skipped: {e!r}", file=sys.stderr)
        health_evidence = None

    # --- warm-path serving runtime proof (this PR) ------------------------
    # AOT registry + bucket ladder + micro-batcher over real HTTP: after a
    # 2-request warmup per bucket, 50 mixed-size concurrent requests must
    # cause ZERO backend compiles; hard contract in --smoke, guarded
    # on-chip like its siblings
    try:
        serving_evidence = _bench_serving()
    except Exception as e:
        if SMOKE:
            raise
        print(f"# serving bench skipped: {e!r}", file=sys.stderr)
        serving_evidence = None

    # --- closed-loop refresh proof (this PR) ------------------------------
    # live in-process load across an atomic hot-swap: the refresh daemon
    # folds a delta off the hot path, the shadow-gated swap publishes with
    # a lock-hold blackout, zero failed requests, zero post-swap compiles,
    # and probation promotes; hard contract in --smoke, guarded on-chip
    # like its siblings
    try:
        refresh_evidence = _bench_refresh()
    except Exception as e:
        if SMOKE:
            raise
        print(f"# refresh bench skipped: {e!r}", file=sys.stderr)
        refresh_evidence = None

    # --- multi-process serve fleet proof (this PR) ------------------------
    # 2 supervised replicas behind the consistent-hash router, loadgen on
    # both wires, a rolling drain/restart mid-window with zero failed
    # requests and a cache-warm respawn; a hard contract where it runs
    if platform == "cpu":
        fleet_evidence = _bench_fleet()
    else:
        # every replica is a process of its own that initializes JAX, and
        # this parent holds the chip: a CPU-only construction until a cell
        # decides the fleet's shape (ROADMAP D5/R6)
        print(f"# fleet bench not run on {platform!r}", file=sys.stderr)
        fleet_evidence = None

    # --- ANN vector-search proof (this PR) --------------------------------
    # streamed IVF build → "ann" servable family → recall@10 and q/s vs
    # the exact-KNN oracle stamped on the same corpus; hard contract in
    # --smoke, recall/ratio guarded on-chip (the zero-recompile contract
    # inside stays fatal everywhere, like the serving stage's)
    try:
        ann_evidence = _bench_ann()
    except Exception as e:
        if SMOKE:
            raise
        print(f"# ann bench skipped: {e!r}", file=sys.stderr)
        ann_evidence = None

    # --- accuracy: bench program vs f64 host oracle, on THIS chip ---------
    min_cosine = L.min_cosine_vs_f64_oracle(
        x[:ACCURACY_ROWS], fit_pca_jit(x[:ACCURACY_ROWS])[0], K
    )

    # --- end-to-end DataFrame fit (ingestion + worker hop + device Gram) --
    df_seconds = _bench_df_fit()

    # --- elastic-scheduler healthy-path contract (this PR) ----------------
    # the DataFrame fit above ran through the supervised work-queue
    # scheduler: on a healthy host it must complete with ZERO speculative
    # hedges and ZERO quarantined worker slots — a nonzero count here means
    # the hedge threshold is firing on normal latency or a worker is
    # crash-looping in the clean path; hard contract in --smoke, reported
    # (not fatal) on the real chip where ambient stragglers are possible
    from spark_rapids_ml_tpu.telemetry import REGISTRY as _SCHED_REG

    _sched_snap = _SCHED_REG.snapshot()
    _hedges = _sched_snap.counter("scheduler.hedge")
    _quarantines = _sched_snap.counter("worker.quarantine")
    if _hedges or _quarantines:
        msg = (
            f"healthy-path scheduler contract violated: "
            f"{_hedges:g} hedge(s), {_quarantines:g} quarantine(s) "
            "during a fault-free bench run"
        )
        if SMOKE:
            raise SystemExit(msg)
        print(f"# {msg}", file=sys.stderr)

    accuracy_ok = bool(min_cosine >= 0.9999)
    tag = "_SMOKE" if SMOKE else ""

    # full-registry telemetry snapshot riding the JSON line: per-phase span
    # percentiles + ingest/collective/compile counters make each BENCH_r*
    # round phase-attributable without a separate profiling session
    from spark_rapids_ml_tpu.telemetry import snapshot_dict

    telemetry_snapshot = snapshot_dict()
    # Raw throughput alongside the modeled vs_baseline (r3 verdict weak #4:
    # "publishing the raw TF/s and MXU-utilization makes it harder to fool
    # ourselves" — the A100 roofline model stays, but these numbers are
    # model-free): logical FLOPs of the measured program's dominant term
    # (the Gram GEMM, 2·rows·n²; the decomposition is O(n²·(k+l)) ≪ that),
    # and utilization against the published v5e-1 bf16 peak with the 3-pass
    # Precision.HIGH multiplier made explicit — the MXU executes 3 bf16
    # passes per logical f32-accurate multiply on this configuration.
    V5E_BF16_PEAK_TFLOPS = 197.0
    logical_tflop = 2.0 * ROWS * N * N / 1e12
    achieved_tflops = logical_tflop / per_fit
    hw_tflops_high = 3.0 * achieved_tflops  # 3-pass bf16 split
    derived = (
        None  # tiny-shape CPU exercise — utilization vs MXU peak is noise
        if SMOKE
        else {
            "gram_logical_tflop": round(logical_tflop, 4),
            "achieved_logical_tflop_s": round(achieved_tflops, 2),
            "hw_bf16_tflop_s_at_3pass": round(hw_tflops_high, 2),
            "v5e1_bf16_peak_tflop_s": V5E_BF16_PEAK_TFLOPS,
            "mxu_utilization": round(hw_tflops_high / V5E_BF16_PEAK_TFLOPS, 3),
        }
    )
    _emit_result(
        (
            {
                # the non-smoke name is the cross-round primary-metric key:
                # it must stay byte-identical from run to run
                "metric": (
                    f"pca_fit_uncentered_device_wall_clock_{ROWS // 1000}k"
                    f"x{N}_k{K}{tag}"
                    if SMOKE
                    else "pca_fit_uncentered_device_wall_clock_2Mx512_k50"
                ),
                "value": round(per_fit, 5),
                "unit": "seconds",
                # --smoke runs a 100× smaller shape: comparing it against the
                # full-shape A100 roofline (or the v5e MXU peak) would print a
                # meaningless ratio that could be misread as a perf claim, so
                # both modeled fields are nulled there (ADVICE r4)
                "vs_baseline": (
                    None if SMOKE else round(A100_ESTIMATE_S / per_fit, 3)
                ),
                "spread": {
                    "median": round(per_fit, 5),
                    "min": round(min(slopes), 5),
                    "max": round(max(slopes), 5),
                    "pairs": PAIRS,
                },
                "derived": derived,
                # exporter evidence rides as a plain record field, NOT an
                # extra_metric: the scrape byte count is diagnostics, not
                # a perf metric
                "health": health_evidence,
                # serving evidence rides as a record field for
                # tools/serve_report.py; only its three headline numbers
                # enter the sentinel as extra_metrics below
                "serving": serving_evidence,
                # refresh evidence rides whole for tools/serve_report.py
                # (swap/rollback/probation trail); its blackout + lag
                # numbers enter the sentinel as extra_metrics below
                "refresh": refresh_evidence,
                # fleet evidence rides whole for tools/serve_report.py;
                # its headline p99/qps/hedge numbers enter the sentinel
                # as extra_metrics below
                "fleet": fleet_evidence,
                # ann evidence likewise rides whole for tools/ann_report.py
                # (recall-vs-nprobe curve, bucket fill skew, spill); its
                # three headline numbers enter the sentinel below
                "ann": ann_evidence,
                "telemetry": telemetry_snapshot,
                "extra_metrics": [
                    {
                        "metric": f"pca_transform_throughput_{N}f_k{K}",
                        "value": round(transform_rows_per_s),
                        "unit": "rows/s",
                        "note": "BASELINE config-3 proxy (device projection)",
                    },
                    {
                        "metric": f"df_fit_end_to_end_{DF_ROWS}x{DF_N}",
                        "value": round(df_seconds, 3),
                        "unit": "seconds",
                        "note": "localspark mesh-local: ingestion + worker "
                        "hop + Arrow collect + device Gram",
                    },
                    {
                        "metric": (
                            f"kmeans_lloyd_rows_per_s_{KM_N}f_k{KM_K}"
                        ),
                        "value": round(kmeans_rows_per_s),
                        "unit": "rows/s",
                        "note": "BASELINE config-5 proxy (one full device "
                        "Lloyd iteration: blocked MXU distances + argmin + "
                        "stats monoid)",
                    },
                    {
                        "metric": f"eigvec_min_cosine_vs_f64_oracle_{ACCURACY_ROWS}x{N}",
                        "value": min_cosine,
                        "unit": "cosine",
                        "accuracy_ok": accuracy_ok,
                    },
                ]
                + (
                    [
                        {
                            "metric": (
                                f"knn_exact_queries_per_s_"
                                f"{KNN_CORPUS // 1000}kcorpus_{KNN_N}f_k{KNN_K}"
                            ),
                            "value": round(knn_qps),
                            "unit": "queries/s",
                            "note": "r5 family: blocked MXU distance "
                            "tournament (ops/neighbors.knn_topk), paired-"
                            "slope chain timing",
                        }
                    ]
                    if knn_qps is not None
                    else []
                )
                + (
                    [
                        {
                            "metric": (
                                f"forest_build_rows_per_s_"
                                f"{RF_TREES}trees_d{RF_DEPTH}_{RF_FEATURES}f"
                            ),
                            "value": round(rf_rows_per_s),
                            "unit": "rows/s",
                            "note": "r5 family: level-order histogram "
                            "forest build (ops/forest.build_forest), "
                            "rows x trees / wall-clock",
                        }
                    ]
                    if rf_rows_per_s is not None
                    else []
                )
                + (
                    [
                        {
                            "metric": "serve_p50_ms",
                            "value": serving_evidence["serve_p50_ms"],
                            "unit": "ms",
                            "note": "warm-path predict latency (AOT "
                            "registry + micro-batcher), mixed-size "
                            "mixed-transport concurrent window",
                        },
                        {
                            "metric": "serve_p99_ms",
                            "value": serving_evidence["serve_p99_ms"],
                            "unit": "ms",
                            **(
                                {
                                    "ceiling": serving_evidence[
                                        "serve_p99_gate_ms"
                                    ]
                                }
                                if serving_evidence.get("serve_p99_gate_ms")
                                else {}
                            ),
                        },
                        {
                            "metric": "serve_recompiles_after_warmup",
                            "value": serving_evidence[
                                "serve_recompiles_after_warmup"
                            ],
                            "unit": "count",
                            "note": "backend compiles in the measured "
                            "window; the warm-path contract pins this to 0",
                        },
                        {
                            "metric": "serve_hedges",
                            "value": serving_evidence.get("hedges", 0) or 0,
                            "unit": "count",
                            "note": "tail-aware hedged serve dispatches "
                            "issued in the measured window (second-device "
                            "re-issue past the hedge threshold; first "
                            "result wins)",
                        },
                        {
                            "metric": "trace_coverage",
                            "value": (
                                serving_evidence.get("trace_coverage")
                                or {}
                            ).get("coverage", 1.0),
                            "unit": "fraction",
                            "note": "sampled requests stitching into one "
                            "complete span tree (zero orphans) over the "
                            "serving window; the stage pins this >= 0.99",
                        },
                    ]
                    if serving_evidence is not None
                    else []
                )
                + (
                    [
                        {
                            "metric": "swap_blackout_ms",
                            "value": refresh_evidence["swap_blackout_ms"],
                            "unit": "ms",
                            "note": "registry lock-hold during the atomic "
                            "hot-swap publish (in-flight dispatches finish "
                            "on the old kernel; candidate AOT + shadow gate "
                            "run outside the blackout)",
                        },
                        {
                            "metric": "refresh_lag_s",
                            "value": refresh_evidence["refresh_lag_s"],
                            "unit": "seconds",
                            "note": "last delta fold -> candidate serving "
                            "(finalize + AOT warm + shadow gate + publish)",
                        },
                    ]
                    if refresh_evidence is not None
                    else []
                )
                + (
                    [
                        {
                            "metric": "fleet_p99_ms",
                            "value": fleet_evidence["fleet_p99_ms"],
                            "unit": "ms",
                            "note": "fleet-wide p99 through the router "
                            "(mixed wires) with a rolling replica "
                            "restart mid-window",
                            **(
                                {
                                    "ceiling": fleet_evidence[
                                        "fleet_p99_gate_ms"
                                    ]
                                }
                                if fleet_evidence.get("fleet_p99_gate_ms")
                                else {}
                            ),
                        },
                        {
                            "metric": "fleet_qps",
                            "value": fleet_evidence["fleet_qps"],
                            "unit": "queries/s",
                            "note": (
                                "closed-loop q/s through the "
                                f"{fleet_evidence['replicas']}-replica "
                                "router; qps_ratio_vs_single "
                                f"{fleet_evidence['qps_ratio_vs_single']}"
                            ),
                        },
                        {
                            "metric": "fleet_trace_coverage",
                            "value": (
                                fleet_evidence.get("trace_coverage")
                                or {}
                            ).get("coverage", 1.0),
                            "unit": "fraction",
                            "note": "sampled cross-process traces "
                            "(router relay + replica fragments) stitching "
                            "complete across the rolling-restart window; "
                            "the stage pins this >= 0.99 with zero "
                            "orphan spans",
                        },
                    ]
                    if fleet_evidence is not None
                    else []
                )
                + (
                    [
                        {
                            "metric": "knn_qps",
                            "value": ann_evidence["knn_qps"],
                            "unit": "queries/s",
                            "note": "exact brute-force baseline on the "
                            "ANN corpus (same rows/features/batch as "
                            "ann_qps) — the denominator of the 100x "
                            "index gate",
                        },
                        {
                            "metric": "ann_qps",
                            "value": ann_evidence["ann_qps"],
                            "unit": "queries/s",
                            "note": "serving-native IVF queries through "
                            "the registered bucket ladder + "
                            "micro-batcher, zero-recompile window",
                        },
                        {
                            "metric": "ann_recall_at_10",
                            "value": ann_evidence["ann_recall_at_10"],
                            "unit": "recall",
                            "note": "vs the exact oracle at the "
                            "registered nprobe operating point",
                        },
                    ]
                    if ann_evidence is not None
                    else []
                )
                + (
                    [
                        {
                            "metric": "streamed_fit_rows_per_s",
                            "value": round(sf_rows_per_s),
                            "unit": "rows/s",
                            "shape": f"{SF_ROWS}x{SF_N}_chunk{SF_CHUNK}",
                            "overlapped_dispatches": sf_overlapped,
                            "overlap_fraction": (
                                round(sf_overlap_fraction, 3)
                                if sf_overlap_fraction is not None
                                else None
                            ),
                            "note": "out-of-core fit: donated-carry Gram "
                            "chunk fold (spark.ingest.stream_fold), H2D "
                            "of chunk i+1 overlapping chunk i's fold",
                        }
                    ]
                    if sf_rows_per_s is not None
                    else []
                ),
            }
        )
    )
    if not accuracy_ok and not SMOKE:
        # the JSON line above is already emitted for the record; a failed
        # accuracy bar must also fail the process so pipelines gate on it.
        # (--smoke numbers are tiny-shape pipeline exercises, not claims —
        # the randomized solver is legitimately noisier there, so the gate
        # reports but does not fail.)
        raise SystemExit(
            f"eigvec_min_cosine {min_cosine:.10f} below the 0.9999 bar"
        )


def _bench_knn() -> float:
    """Exact-kNN queries/s via the same paired-slope chain methodology as
    the primary metric (the per-dispatch constant could otherwise dominate
    a single ~ms kernel call): a lax.scan of dependent knn_topk calls, the
    N=6 vs N=2 slope taken as the per-iteration time."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_ml_tpu.ops import neighbors as NNops

    rng = np.random.default_rng(3)
    corpus = jnp.asarray(
        rng.normal(size=(KNN_CORPUS, KNN_N)).astype(np.float32)
    )
    queries = jnp.asarray(
        rng.normal(size=(KNN_QUERIES, KNN_N)).astype(np.float32)
    )
    valid = jnp.ones((KNN_CORPUS,), bool)

    def make_chain(n_iter):
        @jax.jit
        def f(q):
            def step(qc, _):
                s, i = NNops.knn_topk(qc, corpus, valid, KNN_K)
                # data dependency so XLA cannot collapse the chain
                qc2 = qc + 1e-12 * s[:, :1]
                return qc2, jnp.sum(s) + jnp.sum(i)

            qq, ss = lax.scan(step, q, None, length=n_iter)
            return jnp.sum(qq) + jnp.sum(ss)

        return f

    short, long_ = make_chain(2), make_chain(6)
    float(short(queries)), float(long_(queries))  # warm / compile
    med, _ = _paired_slope(
        lambda: float(short(queries)), lambda: float(long_(queries)), 4, 3
    )
    return KNN_QUERIES / med


def _bench_forest() -> float:
    """Random-forest build throughput: rows×trees processed per second of
    one full level-order build. The build is a multi-second program at
    this shape, so plain median-of-3 timing suffices (the per-dispatch
    constant is noise at this duration, unlike the per-ms kernels that
    need the chain-slope methodology). Completion is forced by a host
    float() transfer, NOT block_until_ready — that fence is unverified on
    this machine (see the module doc), which is why every metric in this
    file reads a scalar back."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ops import forest as FOops

    rng = np.random.default_rng(5)
    binned = jnp.asarray(
        rng.integers(0, RF_BINS, size=(RF_ROWS, RF_FEATURES)).astype(np.int32)
    )
    y = rng.integers(0, 2, size=RF_ROWS)
    row_stats = jnp.asarray(np.eye(2, dtype=np.float32)[y])
    weights = jnp.asarray(
        rng.poisson(1.0, size=(RF_TREES, RF_ROWS)).astype(np.float32)
    )
    keys = jax.random.split(jax.random.PRNGKey(0), RF_TREES)
    static = dict(
        max_depth=RF_DEPTH, n_bins=RF_BINS,
        k_features=max(1, int(np.sqrt(RF_FEATURES))), impurity="gini",
    )

    def run():
        trees = FOops.build_forest(
            keys, binned, row_stats, weights,
            jnp.asarray(np.float32(1.0)), jnp.asarray(np.float32(0.0)),
            **static,
        )
        return float(jnp.sum(trees.leaf_stats) + jnp.sum(trees.gain))

    run()  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return RF_ROWS * RF_TREES / statistics.median(times)


def _bench_streamed_fit() -> tuple[float, int, float | None]:
    """Out-of-core streamed-fit throughput: rows/s through the donated-carry
    Gram chunk-fold pipeline (spark.ingest.stream_fold +
    ops.linalg.gram_fold_step). One host chunk is generated and re-yielded
    N times — the pipeline copies it into a fresh staging buffer per
    dispatch, so the measured path (H2D put overlapping the previous
    chunk's MXU fold, no per-chunk [n, n] realloc) is identical to distinct
    data while host RSS stays one chunk. Returns (rows/s, overlapped
    dispatch count from the timed run, mean overlap fraction) —
    overlapped > 0 is the double-buffering evidence.

    Also the flight-recorder contract check: the timed reps' timeline
    window must serialize as valid Chrome trace JSON (structure only — no
    absolute-time assertions; wall-clock is load-dependent)."""
    from spark_rapids_ml_tpu.ops import linalg as L
    from spark_rapids_ml_tpu.spark import ingest
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
    from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE, chrome_trace

    rng = np.random.default_rng(9)
    n_chunks = SF_ROWS // SF_CHUNK
    chunk = rng.normal(size=(SF_CHUNK, SF_N)).astype(ingest.wire_dtype())

    def run():
        return ingest.stream_fold(
            (chunk for _ in range(n_chunks)),
            L.gram_fold_step(),
            n=SF_N,
            init=L.init_gram_carry(SF_N, ingest.wire_dtype()),
            chunk_rows=SF_CHUNK,
        )

    run()  # compile + warm
    tl_seq = TIMELINE.seq()
    reg0 = REGISTRY.snapshot()
    times, overlapped = [], 0
    for _ in range(3):
        t0 = time.perf_counter()
        res = run()
        times.append(time.perf_counter() - t0)
        overlapped = res.overlapped

    trace = chrome_trace(TIMELINE.events(since_seq=tl_seq))
    if not isinstance(json.loads(json.dumps(trace)).get("traceEvents"), list):
        raise RuntimeError("timeline did not round-trip as Chrome trace JSON")
    ov = REGISTRY.snapshot().delta(reg0).hist("stream.overlap_fraction")
    overlap_fraction = (ov.total / ov.count) if ov.count else None
    return SF_ROWS / statistics.median(times), overlapped, overlap_fraction


def _bench_health() -> dict:
    """Prove the live health/SLO exporter end to end in this process: start
    the HTTP server on an ephemeral port (monitor included), force one
    poll, and scrape /healthz + /metrics over real HTTP. /healthz must be
    200 (this process is healthy — the streamed fit above completed and no
    faults are planned) and the /metrics body must contain the streamed-fit
    counter families that stage just recorded, proving the exporter serves
    the same registry the fit wrote into. Returns the evidence dict that
    rides the bench JSON line; its overall state also stamps the perf
    ledger as ``health_state``."""
    import urllib.request

    from spark_rapids_ml_tpu.telemetry import health, httpd

    server = httpd.start_http_server(0)
    try:
        rollup = health.get_monitor().poll_once()
        with urllib.request.urlopen(server.url + "/healthz", timeout=10) as r:
            hz_status = r.status
        with urllib.request.urlopen(server.url + "/metrics", timeout=10) as r:
            metrics = r.read().decode("utf-8")
        if hz_status != 200:
            raise RuntimeError(f"/healthz returned {hz_status} (expected 200)")
        missing = [
            fam for fam in ("tpu_ml_ingest_rows", "tpu_ml_health_state")
            if fam not in metrics
        ]
        if missing:
            raise RuntimeError(
                f"/metrics scrape missing expected families: {missing}"
            )
        return {
            "port": server.port,
            "healthz": hz_status,
            "state": rollup.get("state"),
            "components": {
                c: (v or {}).get("state")
                for c, v in (rollup.get("components") or {}).items()
            },
            "metrics_scrape_bytes": len(metrics),
        }
    finally:
        httpd.stop_http_server()


def _bench_serving() -> dict:
    """Prove the serving fast path end to end in this process: register a
    fitted PCA + linear model (AOT-compiling the serve bucket ladder),
    warm every bucket and every transport, then fire 52 mixed-size
    concurrent requests spread across the four transport/wire combinations
    (HTTP+JSON, HTTP+binary f32, UDS+JSON, UDS+binary) plus the in-process
    client — with a streamed Gram fit looping on the same device for the
    whole measured window — and assert ZERO new backend compiles: the
    compiled-signature set must be total after warmup, fit contention
    included. Returns the evidence dict riding the bench JSON line; its
    p50/p99 and recompile count also land on the perf ledger as
    ``serve_p50_ms`` / ``serve_p99_ms`` / ``serve_recompiles_after_warmup``
    (with ``TPU_ML_SERVE_P99_GATE_MS`` set, serve_p99_ms carries that
    absolute ceiling for tools/perf_sentinel.py). A declared ``TPU_ML_SLO``
    serve.latency objective is evaluated over the measured window and a
    breach is fatal (the --strict serving gate)."""
    import json as _json
    import socket
    import tempfile
    import threading
    import urllib.request
    from concurrent.futures import ThreadPoolExecutor

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.models.linear import LinearRegression
    from spark_rapids_ml_tpu.ops import linalg as L
    from spark_rapids_ml_tpu.serving import client as serve_client
    from spark_rapids_ml_tpu.serving import registry as serve_registry
    from spark_rapids_ml_tpu.serving import server as serve_server
    from spark_rapids_ml_tpu.spark import ingest
    from spark_rapids_ml_tpu.telemetry import slo as slo_mod
    from spark_rapids_ml_tpu.telemetry import tracectx
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
    from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE

    rng = np.random.default_rng(23)
    n = 16
    xs = rng.normal(size=(256, n))
    ys = xs @ rng.normal(size=n) + 0.25
    pca = PCA().setInputCol("features").setK(4).fit(xs)
    lin = LinearRegression().fit((xs, ys))

    serve_buckets = (8, 16, 32, 64, 128)
    models = ("bench_pca", "bench_linear")
    reg = serve_registry.get_registry()
    reg.register(models[0], pca, bucket_list=serve_buckets)
    reg.register(models[1], lin, bucket_list=serve_buckets)
    uds_path = os.path.join(
        tempfile.gettempdir(), f"tpu-ml-serve-bench-{os.getpid()}.sock"
    )
    server = serve_server.start_serving(
        0, with_monitor=False, uds_path=uds_path
    )
    _uds_local = threading.local()
    try:
        url = server.url

        def post(model: str, rows: np.ndarray) -> dict:
            body = _json.dumps({"instances": rows.tolist()}).encode()
            req = urllib.request.Request(
                f"{url}/v1/models/{model}:predict", data=body
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return _json.load(r)

        def post_binary(model: str, rows: np.ndarray) -> np.ndarray:
            x32 = np.ascontiguousarray(rows, dtype="<f4")
            req = urllib.request.Request(
                f"{url}/v1/models/{model}:predict",
                data=x32.tobytes(),
                headers={
                    "Content-Type": serve_server.BINARY_CONTENT_TYPE,
                    serve_server.SHAPE_HEADER: (
                        f"{x32.shape[0]},{x32.shape[1]}"
                    ),
                    "Accept": serve_server.BINARY_CONTENT_TYPE,
                },
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                return np.frombuffer(r.read(), dtype="<f4")

        def uds_call(model: str, rows: np.ndarray, wire: str) -> dict:
            conn = getattr(_uds_local, "conn", None)
            if conn is None:
                s = socket.socket(socket.AF_UNIX)
                s.connect(uds_path)
                conn = (s, s.makefile("rb"), s.makefile("wb"))
                _uds_local.conn = conn
            _, rf, wf = conn
            if wire == "binary":
                x32 = np.ascontiguousarray(rows, dtype="<f4")
                header = {
                    "model": model, "wire": "binary", "accept": "binary",
                    "shape": list(x32.shape), "payload_bytes": x32.nbytes,
                }
                payload = x32.tobytes()
            else:
                header = {
                    "model": model, "wire": "json",
                    "instances": rows.tolist(),
                }
                payload = b""
            raw = _json.dumps(header).encode()
            wf.write(len(raw).to_bytes(4, "big") + raw + payload)
            wf.flush()
            resp = _json.loads(rf.read(int.from_bytes(rf.read(4), "big")))
            if resp.get("payload_bytes"):
                rf.read(int(resp["payload_bytes"]))
            if not resp.get("ok"):
                raise RuntimeError(
                    f"uds predict failed: {resp.get('error')}"
                )
            return resp

        transports = (
            lambda m, r: post(m, r),
            lambda m, r: post_binary(m, r),
            lambda m, r: uds_call(m, r, "json"),
            lambda m, r: uds_call(m, r, "binary"),
        )

        # 2-request warmup per (model, bucket) over HTTP+JSON — the bucket
        # ladder is already AOT-compiled at registration, so this warms the
        # dispatch path (executable lookup, batcher, HTTP) rather than XLA
        # — plus one pass per transport and the in-process client
        warmup = 0
        for model in models:
            for b in serve_buckets:
                for _ in range(2):
                    post(model, xs[:b])
                    warmup += 1
            for call in transports[1:]:
                call(model, xs[:8])
                warmup += 1
            serve_client.predict(model, xs[:8])
            warmup += 1

        # the concurrent streamed fit contending for the same device during
        # the measured window (warmed first: its compile must not land in
        # the recompile budget)
        fit_chunk = rng.normal(size=(SF_CHUNK, SF_N)).astype(
            ingest.wire_dtype()
        )

        def one_fit():
            return ingest.stream_fold(
                (fit_chunk for _ in range(2)),
                L.gram_fold_step(),
                n=SF_N,
                init=L.init_gram_carry(SF_N, ingest.wire_dtype()),
                chunk_rows=SF_CHUNK,
            )

        one_fit()
        fit_stop = threading.Event()
        fit_rounds = [0]

        def fit_loop():
            while not fit_stop.is_set():
                one_fit()
                fit_rounds[0] += 1

        # declared serve.latency objectives (TPU_ML_SLO) get their own
        # engine seeded at the start of the measured window, burn=1: any
        # breach inside the window is a gate failure, no streak grace
        slo_objectives = tuple(
            o for o in slo_mod.parse_objectives(
                os.environ.get(knobs.SLO.name, "")
            )
            if o.series == "serve.latency"
        )
        slo_engine = (
            slo_mod.SloEngine(slo_objectives, burn=1)
            if slo_objectives
            else None
        )

        snap_warm = REGISTRY.snapshot()
        seq_warm = TIMELINE.seq()
        fit_thread = threading.Thread(target=fit_loop, daemon=True)
        fit_thread.start()
        sizes = (1, 2, 3, 5, 8, 12, 17, 30, 40, 100)
        # mixed traffic: every 13th request rides the in-process client,
        # the rest cycle through HTTP+JSON / HTTP+binary / UDS+JSON /
        # UDS+binary — all five combinations land in the measured window
        reqs = [
            (
                (lambda m, r: serve_client.predict(m, r))
                if i % 13 == 12
                else transports[i % len(transports)],
                models[i % 2],
                xs[: sizes[i % len(sizes)]],
            )
            for i in range(52)
        ]
        try:
            with ThreadPoolExecutor(max_workers=8) as pool:
                list(pool.map(lambda cmr: cmr[0](cmr[1], cmr[2]), reqs))
        finally:
            fit_stop.set()
            fit_thread.join(timeout=60)
        window = REGISTRY.snapshot().delta(snap_warm)

        # the zero-recompile contract: compile.seconds counts every backend
        # compile (telemetry.compilemon), so its delta over the measured
        # window IS the recompiles-after-warmup number
        recompiles = int(window.hist("compile.seconds").count)
        if recompiles:
            raise SystemExit(
                f"serving warm-path contract violated: {recompiles} backend "
                "compile(s) during the measured window — the AOT bucket "
                "ladder did not cover steady-state traffic"
            )
        lat = window.hist("serve.latency")
        if lat.count < len(reqs):
            raise RuntimeError(
                f"serve.latency counted {lat.count} request(s), expected "
                f">= {len(reqs)} — the serve handler is not booking the "
                "SLO series"
            )
        slo_breaches = 0
        if slo_engine is not None:
            slo_breaches = int(
                slo_engine.evaluate().get("total_breaches", 0)
            )
            if slo_breaches:
                raise SystemExit(
                    f"declared serve.latency SLO breached {slo_breaches} "
                    "time(s) during the serving smoke window"
                )

        # trace-stitching contract over the measured window: every sampled
        # request must form exactly one complete span tree (>=99% stitched,
        # zero orphan spans) — a dropped context on any wire or a missing
        # span parent fails the stage, not a dashboard three weeks later
        trace_cov = tracectx.coverage(TIMELINE.events(seq_warm))
        sampled_all = tracectx.trace_sample_rate() >= 1.0
        if (
            not trace_cov["traces"]
            or (sampled_all and trace_cov["traces"] < len(reqs))
            or trace_cov["coverage"] < 0.99
            or trace_cov["orphan_spans"]
        ):
            raise SystemExit(
                "serving trace contract violated: "
                f"{trace_cov['complete']}/{trace_cov['traces']} trace(s) "
                f"stitched complete ({trace_cov['coverage']:.1%}) with "
                f"{trace_cov['orphan_spans']} orphan span(s) across "
                f"{len(reqs)} measured request(s)"
            )

        gate_raw = os.environ.get(knobs.SERVE_P99_GATE_MS.name, "").strip()
        evidence = serve_server.serve_summary(window)
        evidence.pop("type", None)
        evidence.update(
            port=server.port,
            uds_path=uds_path,
            models=list(models),
            buckets=list(serve_buckets),
            warmup_requests=warmup,
            measured_requests=len(reqs),
            concurrent_streamed_fit={
                "rounds": fit_rounds[0],
                "chunk_rows": SF_CHUNK,
                "n": SF_N,
            },
            serve_p50_ms=round(lat.percentile(50) * 1e3, 3),
            serve_p99_ms=round(lat.percentile(99) * 1e3, 3),
            serve_p99_gate_ms=float(gate_raw) if gate_raw else None,
            serve_recompiles_after_warmup=recompiles,
            trace_coverage=trace_cov,
            slo={
                "declared": bool(slo_objectives),
                "breaches": slo_breaches,
            },
        )
        return evidence
    finally:
        serve_server.stop_serving(stop_monitor=False)


def _bench_refresh() -> dict:
    """Closed-loop refresh proof: serve live in-process traffic while the
    refresh daemon folds a data delta off the hot path, checkpoints it
    durably, and atomically hot-swaps the finalized candidate into the
    registry. Hard contracts: ZERO failed requests across the swap window,
    ZERO backend compiles after the publish (the candidate AOT-compiles
    over the live ladder strictly pre-publish), the swap passes the shadow
    gate, and probation clears to promotion. The swap blackout (registry
    lock-hold) and refresh lag (last delta fold -> candidate serving) land
    on the perf ledger as ``swap_blackout_ms`` / ``refresh_lag_s`` for
    tools/serve_report.py and the sentinel."""
    import tempfile
    import threading
    import time as _time

    from spark_rapids_ml_tpu.models.incremental import (
        IncrementalLinearRegression,
    )
    from spark_rapids_ml_tpu.refresh import RefreshDaemon
    from spark_rapids_ml_tpu.serving import client as serve_client
    from spark_rapids_ml_tpu.serving import server as serve_server
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    rng = np.random.default_rng(29)
    n = 16
    coef = rng.normal(size=n)

    def _delta(rows: int, seed: int):
        r = np.random.default_rng(seed)
        x = r.normal(size=(rows, n))
        return x, x @ coef + 0.25

    name = "bench_refresh"
    ck_dir = tempfile.mkdtemp(prefix="tpu-ml-refresh-bench-")
    daemon = RefreshDaemon(
        name,
        IncrementalLinearRegression(),
        checkpoint_dir=ck_dir,
        min_rows=1,
        shadow_rows=64,
        probation_s=0.0,
        probation_slo="serve.latency:p99:10",
    )
    try:
        # v1: seed batch folds, checkpoints, registers (full serve ladder
        # AOT-compiled at registration — the swap later reuses exactly
        # these warm buckets)
        daemon.fold(_delta(4096, 1))
        daemon.checkpoint()
        status = daemon.try_swap()
        if status.get("status") != "registered":
            raise RuntimeError(f"refresh v1 registration failed: {status}")

        probe = _delta(8, 99)[0]
        for _ in range(4):  # dispatch-path warmup (AOT is already done)
            serve_client.predict(name, probe)

        stop = threading.Event()
        failures: list[Exception] = []
        completed = [0]

        def hammer():
            while not stop.is_set():
                try:
                    serve_client.predict(name, probe)
                    completed[0] += 1
                except Exception as e:  # noqa: BLE001 - asserted empty below
                    failures.append(e)
                    return

        snap_warm = REGISTRY.snapshot()
        threads = [threading.Thread(target=hammer) for _ in range(2)]
        for t in threads:
            t.start()
        try:
            # the delta arrives, folds off the hot path, and swaps in
            daemon.fold(_delta(4096, 2))
            daemon.checkpoint()
            res = daemon.try_swap()
            if res.get("status") != "swapped":
                raise SystemExit(
                    f"refresh swap did not publish under live load: {res}"
                )
            snap_postswap = REGISTRY.snapshot()
            _time.sleep(0.25)  # post-swap traffic in the measured window
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        now = REGISTRY.snapshot()
        window = now.delta(snap_warm)
        post = now.delta(snap_postswap)

        if failures:
            raise SystemExit(
                f"refresh swap contract violated: {len(failures)} client "
                f"request(s) failed across the swap ({failures[0]!r})"
            )
        post_recompiles = int(post.hist("compile.seconds").count)
        if post_recompiles:
            raise SystemExit(
                f"refresh swap contract violated: {post_recompiles} backend "
                "compile(s) AFTER the publish — the candidate ladder was "
                "not fully AOT-warmed pre-publish"
            )
        promotion = daemon.probation_check()
        if promotion.get("status") != "promoted":
            raise SystemExit(
                f"refresh probation did not promote: {promotion}"
            )

        blackout = window.hist("serve.swap_blackout_seconds").to_dict()
        evidence = serve_server.serve_summary(window)
        evidence.pop("type", None)
        evidence.update(
            model=name,
            swap_version=res["version"],
            swap_blackout_ms=round(blackout.get("max", 0.0) * 1e3, 3),
            refresh_lag_s=round(res["refresh_lag_s"], 3),
            requests_during_swap=completed[0],
            failed_requests=len(failures),
            post_swap_recompiles=post_recompiles,
            probation=promotion,
            checkpoint_dir=ck_dir,
        )
        return evidence
    finally:
        serve_client.reset_client()


def _bench_fleet() -> dict:
    """Multi-process serve-fleet proof: spawn a 2-replica fleet behind the
    consistent-hash router, drive it with ``tools/serve_loadgen.py``'s
    closed-loop generator on both wires, and stamp fleet-wide p99 and q/s
    on the ledger (``fleet_p99_ms`` carries the same absolute
    ``TPU_ML_SERVE_P99_GATE_MS`` ceiling as the single-process
    ``serve_p99_ms``). The same window also proves the operational story:

      * a single-replica baseline is measured first (loadgen straight at
        replica 0's socket) so the stamped ``qps_ratio`` is
        fleet-vs-one-server on identical traffic — on an N-chip host this
        is the scale-out number; on a 1-core CI host it documents the
        host ceiling rather than replica scaling,
      * mid-window, replica 1 takes a rolling drain/restart under live
        load — ZERO failed requests is a hard contract (the router walks
        the ring past the draining replica; the respawn re-admits on
        READY),
      * the respawned replica's shutdown report must show
        ``cache_misses == 0``: it re-AOT'd entirely from the shared
        persistent compile cache (zero fresh XLA compiles after restart).

    Runs only where the platform is the CPU (see main): every replica is a
    process that initializes JAX."""
    import threading

    from spark_rapids_ml_tpu import PCA
    from spark_rapids_ml_tpu.models.linear import LinearRegression
    from spark_rapids_ml_tpu.serving import fleet as serve_fleet
    from spark_rapids_ml_tpu.telemetry import tracectx
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
    from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
    from tools.serve_loadgen import run_load

    rng = np.random.default_rng(29)
    n = 16
    xs = rng.normal(size=(256, n))
    ys = xs @ rng.normal(size=n) + 0.25
    models = {
        "fleet_pca": PCA().setInputCol("features").setK(4).fit(xs),
        "fleet_linear": LinearRegression().fit((xs, ys)),
    }

    replicas = 2
    connections = 64 if SMOKE else 500
    duration = 2.0 if SMOKE else 5.0
    # trace a slice of the loadgen window: at full rate a multi-thousand-
    # request window would blow through the flight-recorder ring
    # (TPU_ML_TIMELINE_EVENTS) and evict span parents, manufacturing
    # orphans. 2% keeps every process's ring comfortable while still
    # stitching tens of cross-process traces. The router mints in THIS
    # process, so the env var has to move here too, not just to replicas.
    fleet_sample = "0.02"
    prev_sample = os.environ.get(knobs.TRACE_SAMPLE.name)
    os.environ[knobs.TRACE_SAMPLE.name] = fleet_sample
    seq_fleet = TIMELINE.seq()
    snap0 = REGISTRY.snapshot()
    fleet = serve_fleet.ServeFleet(
        models,
        replicas=replicas,
        bucket_list=(8, 16),
        extra_env={knobs.TRACE_SAMPLE.name: fleet_sample},
    ).start()
    restarted_worker = None
    try:
        # single-replica baseline: identical closed-loop traffic straight
        # at replica 0 (no router), the denominator of qps_ratio
        single = run_load(
            fleet.replica_socket(0), "fleet_linear",
            connections=connections, duration_s=duration,
            wire="fast", rows=4, cols=n,
        )

        # fleet window: same traffic through the router on both wires,
        # with a rolling restart of replica 1 landing mid-window
        fleet_result: dict = {}

        def drive():
            fleet_result.update(run_load(
                fleet.router_path, "fleet_linear",
                connections=connections, duration_s=duration,
                wire="mixed", rows=4, cols=n,
            ))

        loader = threading.Thread(target=drive)
        loader.start()
        time.sleep(duration / 3.0)
        restart_ok = fleet.restart_replica(1)
        loader.join(timeout=duration * 10 + 60)
        if loader.is_alive():
            raise RuntimeError("fleet loadgen wedged past its window")
        restarted_worker = fleet._supervisor._slots[1].worker

        if not restart_ok:
            raise SystemExit(
                "fleet rolling restart failed: the respawned replica "
                "never reported READY"
            )
        if fleet_result.get("failures", 1) or not fleet_result.get(
            "requests"
        ):
            raise SystemExit(
                "fleet contract violated: "
                f"{fleet_result.get('failures')} failed request(s) "
                f"across {fleet_result.get('requests')} during the "
                "rolling-restart window — drain/reroute must make a "
                "replica restart invisible to clients"
            )
        stats = fleet.stats()
    finally:
        fleet.stop()
        if prev_sample is None:
            os.environ.pop(knobs.TRACE_SAMPLE.name, None)
        else:
            os.environ[knobs.TRACE_SAMPLE.name] = prev_sample

    # cross-process trace stitching: router relay spans + both replicas'
    # harvested fragments (live STATS scrapes + teardown trailers, the
    # restarted replica's pre-restart fragment included) must merge into
    # complete trees — >=99% stitched, zero orphan spans — with the
    # rolling restart landing mid-window. Scoped to this stage's router
    # events so earlier stages' ring residue can't skew the audit.
    pid_self = os.getpid()
    fleet_events = [
        e for e in fleet.fleet_events()
        if e.get("pid") != pid_self or e.get("seq", 0) > seq_fleet
    ]
    trace_cov = tracectx.coverage(fleet_events)
    if (
        not trace_cov["traces"]
        or trace_cov["coverage"] < 0.99
        or trace_cov["orphan_spans"]
    ):
        raise SystemExit(
            "fleet trace contract violated: "
            f"{trace_cov['complete']}/{trace_cov['traces']} cross-process "
            f"trace(s) stitched complete ({trace_cov['coverage']:.1%}) "
            f"with {trace_cov['orphan_spans']} orphan span(s) across the "
            "rolling-restart window"
        )

    # the respawned replica's shutdown report: cache_misses == 0 means it
    # re-AOT'd entirely from the shared persistent cache
    respawn_misses = (
        restarted_worker.cache_misses
        if restarted_worker is not None
        else None
    )
    if respawn_misses:
        raise SystemExit(
            f"fleet warm-respawn contract violated: {respawn_misses} "
            "compile-cache miss(es) on the restarted replica — the "
            "respawn recompiled instead of loading the shared AOT cache"
        )

    window = REGISTRY.snapshot().delta(snap0)
    hits = window.counter("serve.route_hits")
    misses = window.counter("serve.route_misses")
    gate_raw = os.environ.get(knobs.SERVE_P99_GATE_MS.name, "").strip()
    return {
        "replicas": replicas,
        "connections": connections,
        "duration_s": duration,
        "placement": stats["placement"],
        "single_replica": single,
        "fleet": fleet_result,
        "fleet_qps": fleet_result["qps"],
        "fleet_p50_ms": fleet_result["p50_ms"],
        "fleet_p99_ms": fleet_result["p99_ms"],
        "fleet_p99_gate_ms": float(gate_raw) if gate_raw else None,
        "qps_ratio_vs_single": (
            round(fleet_result["qps"] / single["qps"], 3)
            if single["qps"]
            else None
        ),
        "routing": {
            "hits": hits,
            "misses": misses,
            "hit_rate": round(hits / (hits + misses), 4)
            if (hits + misses)
            else None,
        },
        "trace_coverage": trace_cov,
        "trace_sample_rate": float(fleet_sample),
        "clock_offsets_us": stats.get("clock_offsets_us"),
        "rolling_restart": {
            "ok": True,
            "drain_events": window.counter("serve.drain_events"),
            "replica_restarts": window.counter("serve.replica_restarts"),
            "respawn_cache_hits": restarted_worker.cache_hits
            if restarted_worker is not None
            else None,
            "respawn_cache_misses": respawn_misses,
            "failed_requests": fleet_result["failures"],
        },
        "served_per_replica": stats["served_per_replica"],
    }


def _bench_ann() -> dict:
    """Streamed-IVF vector-search proof: build the index out-of-core with
    ``IVFFlatIndex`` (the corpus is only ever resident one chunk at a
    time), register it as the ``"ann"`` servable family, and measure
    serving-native query throughput plus recall@10 against the exact
    brute-force oracle on the SAME corpus. Three contracts ride the
    ledger:

      * ``ann_recall_at_10`` >= 0.95 vs the exact oracle,
      * ``ann_qps`` >= 100x ``knn_qps`` — the exact-KNN baseline is
        stamped HERE, on the same corpus / batch / chip, so the ratio is
        the honest "what did the index buy" number, not a cross-geometry
        coincidence,
      * ZERO backend compiles across the timed query window (the AOT
        bucket ladder must fully cover steady-state query traffic).

    The recall/ratio gates are fatal in --smoke and report-only on the
    real chip (geometry differs); the zero-recompile contract stays fatal
    everywhere, like the serving stage's. The evidence dict (recall-vs-
    nprobe sweep, bucket fill-skew stats, spill fraction) rides the bench
    JSON line for tools/ann_report.py."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.ann import serving as ann_serving
    from spark_rapids_ml_tpu.ann.index import IVFFlatIndex
    from spark_rapids_ml_tpu.ops import neighbors as NNops
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

    n_chunks = ANN_ROWS // ANN_CHUNK
    rng = np.random.default_rng(29)
    centers = rng.normal(
        scale=10.0, size=(ANN_NLIST, ANN_N)
    ).astype(np.float32)

    # balanced, well-separated clusters, generated chunk-at-a-time and
    # seeded per chunk: the streamed build makes two passes over the
    # source and must see identical bytes on both
    def make_chunk(ci: int) -> np.ndarray:
        crng = np.random.default_rng(1_000 + ci)
        labels = (ci * ANN_CHUNK + np.arange(ANN_CHUNK)) % ANN_NLIST
        return (
            centers[labels]
            + crng.normal(scale=0.5, size=(ANN_CHUNK, ANN_N))
        ).astype(np.float32)

    def corpus_chunks():
        return (make_chunk(ci) for ci in range(n_chunks))

    # 32/cluster training samples: the D²-init's coupon-collector tail
    # merges ~1% of cells at nlist=2048 with the 16/cluster default; the
    # Lloyd empty-cell reseeding fixes the merges, and the bigger sample
    # is the pool it reseeds from
    os.environ[knobs.ANN_SAMPLE_ROWS.name] = str(32 * ANN_NLIST)
    t0 = time.perf_counter()
    model = IVFFlatIndex(
        k=ANN_K, nlist=ANN_NLIST, nprobe=ANN_NPROBE, maxIter=2, seed=31
    ).fit(corpus_chunks)
    build_s = time.perf_counter() - t0

    # queries are perturbed corpus rows: the true neighbors sit inside the
    # same tight cluster, so recall@10 measures the index, not the data
    qrng = np.random.default_rng(37)
    queries = (
        make_chunk(0)[:ANN_QUERY_BATCH]
        + qrng.normal(scale=0.05, size=(ANN_QUERY_BATCH, ANN_N))
    ).astype(np.float32)

    # --- the exact-KNN baseline, on THIS corpus at THIS batch size --------
    # (the oracle is the one consumer that materializes the corpus; the
    # index build above never did)
    corpus_dev = jnp.asarray(np.concatenate(list(corpus_chunks()), axis=0))
    valid = jnp.ones((ANN_ROWS,), bool)
    q_dev = jnp.asarray(queries)

    @jax.jit
    def exact(q):
        return NNops.knn_topk(q, corpus_dev, valid, ANN_K)

    _, oi = exact(q_dev)  # compile + warm; also the recall oracle
    oracle_ids = np.asarray(oi)[:ANN_ORACLE_QUERIES]
    times = []
    for _ in range(2):
        t0 = time.perf_counter()
        s, i = exact(q_dev)
        float(jnp.sum(s) + jnp.sum(i))  # host read forces completion
        times.append(time.perf_counter() - t0)
    knn_qps = ANN_QUERY_BATCH / statistics.median(times)
    del corpus_dev

    # --- serving-native query throughput ----------------------------------
    ann_serving.register_index(
        "bench_ann", model, bucket_list=(ANN_QUERY_BATCH,)
    )
    for _ in range(2):  # dispatch-path warmup; XLA is AOT-warm already
        ann_serving.query("bench_ann", queries)
    snap_warm = REGISTRY.snapshot()
    times = []
    ids = None
    for _ in range(6):
        t0 = time.perf_counter()
        _, ids = ann_serving.query("bench_ann", queries)
        times.append(time.perf_counter() - t0)
    window = REGISTRY.snapshot().delta(snap_warm)
    recompiles = int(window.hist("compile.seconds").count)
    if recompiles:
        raise SystemExit(
            f"ann warm-path contract violated: {recompiles} backend "
            "compile(s) during the timed query window — the AOT ladder "
            "did not cover steady-state query traffic"
        )
    ann_qps = ANN_QUERY_BATCH / statistics.median(times)

    def _recall(got: np.ndarray) -> float:
        return float(np.mean([
            len(set(a.tolist()) & set(b.tolist())) / ANN_K
            for a, b in zip(got, oracle_ids)
        ]))

    recall = _recall(ids[:ANN_ORACLE_QUERIES])
    ratio = ann_qps / knn_qps
    problems = []
    if recall < 0.95:
        problems.append(f"ann_recall_at_10 {recall:.4f} below the 0.95 bar")
    if ratio < 100.0:
        problems.append(
            f"ann_qps/knn_qps ratio {ratio:.1f} below the 100x bar"
        )
    if problems:
        msg = "; ".join(problems)
        print(
            f"# ann evidence at failure: qps={ann_qps:.0f} knn={knn_qps:.0f}"
            f" ratio={ratio:.1f} recall={recall:.4f}"
            f" cap={int(model.bucketItems.shape[1])} build_s={build_s:.1f}",
            file=sys.stderr,
        )
        if SMOKE:
            raise SystemExit(f"ann contract violated: {msg}")
        print(f"# ann gate: {msg}", file=sys.stderr)

    # recall-vs-nprobe operating curve (after the timed window — each
    # nprobe is a distinct static point, so the sweep compiles)
    sweep = []
    for nprobe in (1, 2, 4, 8, 16):
        if nprobe > model.nlist:
            break
        _, si = ann_serving.query_direct(
            "bench_ann", queries[:ANN_ORACLE_QUERIES], nprobe=nprobe
        )
        sweep.append(
            {"nprobe": nprobe, "recall_at_10": round(_recall(si), 4)}
        )

    fill = (np.asarray(model.bucketIds) >= 0).sum(axis=1)
    spill_rows = int((np.asarray(model.spillIds) >= 0).sum())
    return {
        "rows": ANN_ROWS,
        "n_features": ANN_N,
        "nlist": int(model.nlist),
        "nprobe": ANN_NPROBE,
        "k": ANN_K,
        "query_batch": ANN_QUERY_BATCH,
        "oracle_queries": ANN_ORACLE_QUERIES,
        "build_seconds": round(build_s, 3),
        "build_rows_per_s": round(ANN_ROWS / build_s),
        "bucket_cap": int(model.bucketItems.shape[1]),
        "bucket_fill": {
            "mean": round(float(fill.mean()), 1),
            "p50": int(np.percentile(fill, 50)),
            "p99": int(np.percentile(fill, 99)),
            "max": int(fill.max()),
        },
        "spill_rows": spill_rows,
        "spill_fraction": round(spill_rows / ANN_ROWS, 5),
        "ann_qps": round(ann_qps),
        "knn_qps": round(knn_qps),
        "qps_ratio": round(ratio, 1),
        "ann_recall_at_10": round(recall, 4),
        "recall_vs_nprobe": sweep,
        "ann_recompiles_after_warmup": recompiles,
    }


def _bench_df_fit() -> float:
    """Wall-clock of one live DataFrame fit on this machine's deployment
    (localspark workers on CPU for ingestion, device Gram on the driver's
    mesh). Returns seconds; ingestion data is built outside the timer."""
    import pyarrow as pa

    from spark_rapids_ml_tpu.localspark import LocalSparkSession
    from spark_rapids_ml_tpu.localspark.dataframe import dataframe_from_partitions
    from spark_rapids_ml_tpu.localspark import types as LT
    from spark_rapids_ml_tpu.spark import SparkPCA

    rng = np.random.default_rng(0)
    xdf = rng.normal(size=(DF_ROWS, DF_N))
    schema = LT.StructType(
        [LT.StructField("features", LT.ArrayType(LT.DoubleType()))]
    )
    n_parts = 4
    parts = []
    for sl in np.array_split(xdf, n_parts):
        flat = pa.array(sl.reshape(-1))
        offsets = pa.array(np.arange(0, sl.size + 1, DF_N, dtype=np.int32))
        batch = pa.RecordBatch.from_arrays(
            [pa.ListArray.from_arrays(offsets, flat)], names=["features"]
        )
        parts.append([batch])
    with LocalSparkSession(parallelism=n_parts) as s:
        df = dataframe_from_partitions(s, schema, parts)
        est = (
            SparkPCA().setInputCol("features").setK(16)
            .setDistribution("mesh-local")
        )
        est.fit(df)  # warm (worker spawn + compile)
        t0 = time.perf_counter()
        est.fit(df)
        return time.perf_counter() - t0


if __name__ == "__main__":
    main()
