#!/usr/bin/env python
"""Render per-fit/transform telemetry JSONL (TPU_ML_TELEMETRY_PATH).

Usage::

    python tools/trace_report.py /path/to/telemetry.jsonl [--last N] [--strict]

For each ``fit_report`` or ``transform_report`` record (newest last;
``--last N`` keeps only the final N): a per-phase latency table (count /
total / p50 / p90 / p99 / max), throughput and collective/compile
summaries, the analytical cost-model line (FLOPs, bytes accessed, roofline
utilization vs TPU_ML_PEAK_TFLOPS), per-partition breakdowns for
transforms, peak device memory, and a set of anomaly checks — heuristics
that turn the numbers into a diagnosis:

- ``fold.wait`` total > 2× ``fold.dispatch`` total ⇒ the streamed-fit
  pipeline is NOT overlapping H2D with compute (the terminal block is
  eating what double-buffering should hide).
- compile seconds > 50% of fit wall ⇒ compile-dominated fit (check the
  persistent compile cache directory and shape-bucketing). Where the
  persistent cache is in use, only its misses and their seconds count:
  loading sixty cached programs is not compiling them.
- zero rows ingested with nonzero wall ⇒ the fit never saw the data path
  this report instruments (fine for array fits fed device arrays; worth a
  look for DataFrame fits).
- nonzero ``retry.attempts`` / ``chunk.bisections`` counters ⇒ the fit
  completed but only by recovering (transient retries, OOM chunk
  bisection) — healthy output, unhealthy ride; worth investigating
  before it becomes a hard failure.
- nonzero ``fault.injected`` ⇒ a TPU_ML_FAULT_PLAN was active; expected
  only in chaos tests, never in a production report.
- nonzero ``slo.breach`` counted during the fit window ⇒ a declared
  ``TPU_ML_SLO`` latency ceiling or throughput floor burned through its
  tolerance while the fit ran (``slo-breach-during-fit``).
- backend compiles far exceeding the distinct cost-model kernel count ⇒
  recompile storm: static-shape bucketing is not holding, so the same
  logical kernels keep recompiling per shape (check TPU_ML_MIN_BUCKET and
  the compile cache directory).
- ``scheduler.hedge`` count > 20% of ``scheduler.tasks`` ⇒ hedge storm:
  speculative duplicates are no longer the exception — the hedge
  threshold is mis-tuned for this workload or most partitions are
  stragglers (check TPU_ML_HEDGE_FACTOR / TPU_ML_HEDGE_FLOOR_S and the
  partition sizing).
- nonzero ``worker.quarantine`` ⇒ a worker slot crash-looped until its
  circuit breaker opened; the fit finished on the surviving slots with
  reduced parallelism.
- transform reports: slowest partition > 3× the median partition ⇒
  partition skew; one straggler sets the wall clock.

The reader is tolerant by design: a record from a newer schema than this
tool understands, or one missing the fields a renderer needs, is skipped
with a note — never a KeyError traceback — so one odd record cannot hide
the rest of the file.

Exit status: 0 normally; with ``--strict``, 2 when any anomaly fired OR
any record had to be skipped (CI gate). Stdlib-only on the read path —
the report must render on hosts without jax installed.
"""

from __future__ import annotations

import argparse
import json
import sys

# highest fit_report schema this renderer understands (telemetry.report
# .SCHEMA_VERSION); newer records are skipped with a note, older ones
# render with defaults for the fields they predate
SUPPORTED_SCHEMA = 7

# highest transform_report schema understood
# (telemetry.report.TRANSFORM_SCHEMA_VERSION)
SUPPORTED_TRANSFORM_SCHEMA = 1


def _fmt_s(v: float) -> str:
    if v >= 1.0:
        return f"{v:.3f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.2f}ms"
    return f"{v * 1e6:.0f}us"


def _fmt_bytes(v: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(v) < 1024.0 or unit == "TiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{int(v)}B"
        v /= 1024.0
    return f"{v:.1f}TiB"


def _table(rows: list[list[str]], header: list[str]) -> str:
    widths = [
        max(len(str(r[i])) for r in [header] + rows) for i in range(len(header))
    ]
    def line(cells):
        return "  ".join(str(c).ljust(w) for c, w in zip(cells, widths)).rstrip()
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([line(header), sep] + [line(r) for r in rows])


def check_anomalies(rec: dict) -> list[str]:
    """The heuristic diagnoses for one fit_report record."""
    out: list[str] = []
    phases = rec.get("phases", {})
    wait = phases.get("fold.wait", {}).get("sum", 0.0)
    dispatch = phases.get("fold.dispatch", {}).get("sum", 0.0)
    if dispatch > 0 and wait > 2.0 * dispatch:
        out.append(
            f"pipeline not overlapping: fold.wait total {_fmt_s(wait)} > 2x "
            f"fold.dispatch total {_fmt_s(dispatch)} — the terminal block is "
            "absorbing the fold work; H2D is not hiding behind compute "
            "(check donate_argnums on the fold step and chunk sizing)"
        )
    wall = rec.get("wall_seconds", 0.0)
    compile_s = _compiled(rec)[1]
    if wall > 0 and compile_s > 0.5 * wall:
        out.append(
            f"compile-dominated fit: {_fmt_s(compile_s)} of {_fmt_s(wall)} "
            "wall went to XLA compiles (check the compile cache directory and that "
            "input shapes hit the row buckets)"
        )
    if wall > 0 and not rec.get("rows_ingested"):
        out.append(
            "no rows counted: the fit bypassed the instrumented ingest/"
            "columnar path (expected for fits fed pre-built device arrays)"
        )
    retries = _counter_total(rec, "retry.attempts")
    bisections = _counter_total(rec, "chunk.bisections")
    if retries or bisections:
        out.append(
            f"recovered-but-degraded fit: {retries:g} retried attempt(s), "
            f"{bisections:g} chunk bisection(s) — the fit finished only by "
            "recovering; investigate the flaking transport / device memory "
            "headroom before it becomes a hard failure"
        )
    injected = _counter_total(rec, "fault.injected")
    if injected:
        out.append(
            f"fault injection active: {injected:g} synthetic fault(s) fired "
            "— TPU_ML_FAULT_PLAN is set; expected only in chaos tests, "
            "never in production"
        )
    breaches = _counter_total(rec, "slo.breach")
    if breaches:
        out.append(
            f"slo-breach-during-fit: {breaches:g} windowed SLO breach(es) "
            "fired while this fit ran — a declared TPU_ML_SLO target "
            "(latency ceiling or throughput floor) burned through its "
            "tolerance; see the slo.breach timeline instants and the "
            "/slo endpoint for which objective"
        )
    storm = _recompile_storm(rec)
    if storm:
        out.append(storm)
    hedges = _counter_total(rec, "scheduler.hedge")
    tasks = _counter_total(rec, "scheduler.tasks")
    if tasks > 0 and hedges > 0.2 * tasks:
        out.append(
            f"hedge-storm: {hedges:g} speculative hedge(s) for {tasks:g} "
            "scheduled task(s) (> 20%) — hedging should be the exception, "
            "not the norm; the straggler threshold is mis-tuned for this "
            "workload (check TPU_ML_HEDGE_FACTOR / TPU_ML_HEDGE_FLOOR_S "
            "and the partition sizing)"
        )
    quarantined = _counter_total(rec, "worker.quarantine")
    if quarantined:
        out.append(
            f"worker-quarantined: {quarantined:g} worker slot(s) crash-"
            "looped until the circuit breaker opened — the fit finished on "
            "the surviving slots with reduced parallelism; inspect the "
            "worker.quarantine timeline instants and the slot's last error "
            "in /healthz before the next run"
        )
    return out


def _compiled(rec: dict) -> tuple[float, float]:
    """Programs actually compiled in the window, and the seconds that took.

    ``compile.count``/``seconds`` hold one event per compile *request*, and
    with the persistent cache in use most requests are loads (an eager
    ``eigh`` makes sixty): there the programs compiled are the cache's
    misses and their seconds are the requests' less the loads'."""
    comp = rec.get("compile") or {}
    count, seconds = comp.get("count", 0), comp.get("seconds", 0.0)
    if comp.get("cache_hits", 0) or comp.get("cache_misses", 0):
        count = comp.get("cache_misses", 0)
        seconds = max(0.0, seconds - comp.get("cache_load_seconds", 0.0))
        if not count:
            seconds = 0.0
    return count, seconds


def _recompile_storm(rec: dict) -> str | None:
    """Backend compiles >> distinct cost-model kernels ⇒ recompile storm.

    Each captured kernel legitimately costs up to two compiles (the AOT
    cost-analysis lowering plus the real dispatch), and a fit also runs a
    few auxiliary jitted helpers the cost model does not capture — hence
    the 2x + slack budget before the check fires.
    """
    kernels = (rec.get("cost_model") or {}).get("kernels") or {}
    count = _compiled(rec)[0]
    if kernels and count > 2 * len(kernels) + 2:
        return (
            f"recompile storm: {count:g} backend compiles for "
            f"{len(kernels)} distinct cost-model kernel(s) — the same "
            "logical kernels are recompiling per input shape (check "
            "TPU_ML_MIN_BUCKET row-bucketing and the compile cache directory; "
            "if a code path builds jax.jit programs per call, "
            "`python -m tools.tpulint` rule TPL003 finds it statically)"
        )
    return None


def check_transform_anomalies(rec: dict) -> list[str]:
    """The heuristic diagnoses for one transform_report record."""
    out: list[str] = []
    wall = rec.get("wall_seconds", 0.0)
    if wall > 0 and not rec.get("rows"):
        out.append(
            "no rows counted: the transform plan was built but never "
            "materialized through the instrumented arrow path (lazy plans "
            "only report after an action consumes them)"
        )
    parts = rec.get("partitions") or {}
    secs = sorted(
        p.get("seconds", 0.0) for p in parts.values() if p.get("seconds")
    )
    if len(secs) >= 2:
        median = secs[len(secs) // 2]
        if median > 0 and secs[-1] > 3.0 * median:
            out.append(
                f"partition skew: slowest partition took {_fmt_s(secs[-1])} "
                f"vs median {_fmt_s(median)} — one straggler is setting the "
                "wall clock (check the input partitioning)"
            )
    retries = _counter_total(rec, "retry.attempts")
    if retries:
        out.append(
            f"recovered-but-degraded transform: {retries:g} retried "
            "attempt(s) — the transform finished only by recovering"
        )
    injected = _counter_total(rec, "fault.injected")
    if injected:
        out.append(
            f"fault injection active: {injected:g} synthetic fault(s) fired "
            "— TPU_ML_FAULT_PLAN is set; expected only in chaos tests, "
            "never in production"
        )
    storm = _recompile_storm(rec)
    if storm:
        out.append(storm)
    return out


def _counter_total(rec: dict, name: str) -> float:
    """Sum a counter across its label sets: report counters are keyed
    ``name`` or ``name{label=value,...}`` (telemetry.registry.render_key)."""
    total = 0.0
    for key, val in (rec.get("counters") or {}).items():
        if key == name or key.startswith(name + "{"):
            total += val
    return total


def _print_phase_table(rec: dict, out) -> None:
    phases = rec.get("phases", {})
    if not phases:
        print("(no spans recorded)", file=out)
        return
    rows = []
    for name, p in sorted(
        phases.items(), key=lambda kv: -kv[1].get("sum", 0.0)
    ):
        rows.append([
            name,
            int(p.get("count", 0)),
            _fmt_s(p.get("sum", 0.0)),
            _fmt_s(p.get("self", p.get("sum", 0.0))),
            _fmt_s(p.get("p50", 0.0)),
            _fmt_s(p.get("p90", 0.0)),
            _fmt_s(p.get("p99", 0.0)),
            _fmt_s(p.get("max", 0.0)),
        ])
    print(
        _table(rows, ["phase", "count", "total", "self", "p50", "p90", "p99", "max"]),
        file=out,
    )


def _print_cost_model(rec: dict, out) -> None:
    """The analytical FLOPs/bytes + roofline line (telemetry.costmodel)."""
    cm = rec.get("cost_model") or {}
    kernels = cm.get("kernels") or {}
    if not kernels and not cm.get("analytical_flops"):
        return
    line = (
        f"cost model: {cm.get('analytical_flops', 0):,.0f} analytical FLOPs, "
        f"{_fmt_bytes(cm.get('analytical_bytes', 0))} accessed, "
        f"{len(kernels)} kernel(s)"
    )
    util = cm.get("roofline_utilization")
    if util is not None:
        line += (
            f"; roofline {util:.3%} of "
            f"{cm.get('peak_flops', 0) / 1e12:.0f} TFLOP/s peak"
        )
    print(line, file=out)
    for name, k in sorted(kernels.items()):
        calls = k.get("calls", 0)
        detail = (
            f"  kernel {name}: {calls:g} call(s), "
            f"{k.get('flops', 0):,.0f} FLOPs/call, "
            f"{_fmt_bytes(k.get('bytes_accessed', 0))}/call"
        )
        if k.get("temp_bytes"):
            detail += f", temp {_fmt_bytes(k['temp_bytes'])}"
        print(detail, file=out)


def _print_admission(rec: dict, out) -> None:
    """The admission-control decision stamped at fit start (fit_report
    schema >= 6): which policy ran and what it decided. Only non-plain
    admits are printed — a healthy admit under the default policy is the
    uninteresting common case."""
    adm = rec.get("admission") or {}
    if not adm:
        return
    action = adm.get("action", "?")
    policy = adm.get("policy", "?")
    if action == "admit" and policy in ("refuse", "degrade"):
        return  # healthy-path admit: no news is good news
    print(
        f"admission: action={action} policy={policy} "
        f"health={adm.get('health_state', '?')} — {adm.get('reason', '')}",
        file=out,
    )


def _print_health(rec: dict, out) -> None:
    """The live-monitor rollup stamped at fit end (fit_report schema >= 5):
    worst component state, any non-OK components, and counted SLO
    breaches. Absent (empty) when no monitor ran — nothing is printed."""
    health = rec.get("health") or {}
    if not health:
        return
    components = health.get("components") or {}
    bad = ", ".join(
        f"{c}={s}" for c, s in sorted(components.items()) if s != "OK"
    )
    line = f"health: {health.get('state', '?')}"
    if bad:
        line += f" ({bad})"
    line += (
        f"; {health.get('polls', 0)} poll(s), "
        f"{health.get('transitions', 0)} transition(s), "
        f"{health.get('slo_breaches', 0)} SLO breach(es)"
    )
    print(line, file=out)


def render_record(rec: dict, out=sys.stdout) -> list[str]:
    """Print one fit_report; returns its anomaly list."""
    est = rec.get("estimator", "?")
    uid = rec.get("uid", "")
    wall = rec.get("wall_seconds", 0.0)
    fit_id = rec.get("fit_id", "")
    tag = f" [{uid}]" if uid else ""
    tag += f" fit={fit_id}" if fit_id else ""
    print(f"\n=== {est}{tag} — {_fmt_s(wall)} ===", file=out)
    ov = rec.get("overlap_fraction")
    if ov is not None:
        print(
            f"streamed H2D<->compute overlap: {ov:.2f} "
            f"({'overlapped' if ov > 0 else 'NOT overlapped'}; "
            "see tools/trace_timeline.py for the event view)",
            file=out,
        )

    _print_phase_table(rec, out)

    rows_in = rec.get("rows_ingested", 0)
    if rows_in:
        line = (
            f"ingest: {rows_in} rows, {_fmt_bytes(rec.get('bytes_ingested', 0))}"
        )
        if wall > 0:
            line += f" ({rows_in / wall:,.0f} rows/s)"
        if rec.get("h2d_bytes"):
            line += f"; h2d {_fmt_bytes(rec['h2d_bytes'])}"
        pad_rows = rec.get("counters", {}).get("mesh.pad_rows")
        if pad_rows:
            line += f"; {pad_rows:g} pad rows on the mesh"
        print(line, file=out)
    coll = rec.get("collectives", {})
    if coll.get("count") or coll.get("tree_combines"):
        print(
            f"collectives: {coll.get('count', 0):g} launches, "
            f"{_fmt_bytes(coll.get('bytes', 0))} payload, "
            f"{coll.get('tree_combines', 0):g} tree combines",
            file=out,
        )
    comp = rec.get("compile", {})
    if comp.get("count"):
        print(
            f"compile: {comp['count']:g} compile requests, "
            f"{_fmt_s(comp.get('seconds', 0.0))} "
            f"(trace {_fmt_s(comp.get('trace_seconds', 0.0))}; "
            f"cache {comp.get('cache_hits', 0):g} hits / "
            f"{comp.get('cache_misses', 0):g} misses, "
            f"loads {_fmt_s(comp.get('cache_load_seconds', 0.0))})",
            file=out,
        )
    _print_cost_model(rec, out)
    _print_health(rec, out)
    _print_admission(rec, out)
    peak = rec.get("peak_device_bytes", 0)
    if peak:
        print(f"peak device memory: {_fmt_bytes(peak)}", file=out)

    anomalies = check_anomalies(rec)
    for a in anomalies:
        print(f"  !! {a}", file=out)
    if not anomalies:
        print("  anomaly checks: ok", file=out)
    return anomalies


def render_transform_record(rec: dict, out=sys.stdout) -> list[str]:
    """Print one transform_report; returns its anomaly list."""
    name = rec.get("transformer", "?")
    uid = rec.get("uid", "")
    wall = rec.get("wall_seconds", 0.0)
    transform_id = rec.get("transform_id", "")
    tag = f" [{uid}]" if uid else ""
    tag += f" transform={transform_id}" if transform_id else ""
    print(f"\n=== {name}{tag} — {_fmt_s(wall)} (transform) ===", file=out)

    _print_phase_table(rec, out)

    rows_out = rec.get("rows", 0)
    if rows_out:
        line = f"output: {rows_out} rows, {_fmt_bytes(rec.get('bytes', 0))}"
        if wall > 0:
            line += f" ({rows_out / wall:,.0f} rows/s)"
        print(line, file=out)

    parts = rec.get("partitions") or {}
    if parts:
        def _pkey(kv):
            pid = kv[0]
            return (0, int(pid)) if pid.isdigit() else (1, 0)
        rows = []
        for pid, p in sorted(parts.items(), key=_pkey):
            rows.append([
                pid,
                int(p.get("rows", 0)),
                _fmt_bytes(p.get("bytes", 0)),
                int(p.get("batches", 0)),
                _fmt_s(p.get("seconds", 0.0)),
            ])
        print(
            _table(rows, ["partition", "rows", "bytes", "batches", "seconds"]),
            file=out,
        )
    lat = rec.get("partition_latency") or {}
    if lat.get("count"):
        print(
            f"partition latency: {lat['count']:g} partition(s), "
            f"p50 {_fmt_s(lat.get('p50', 0.0))} / "
            f"p90 {_fmt_s(lat.get('p90', 0.0))} / "
            f"p99 {_fmt_s(lat.get('p99', 0.0))}, "
            f"max {_fmt_s(lat.get('max', 0.0))}",
            file=out,
        )

    _print_cost_model(rec, out)

    anomalies = check_transform_anomalies(rec)
    for a in anomalies:
        print(f"  !! {a}", file=out)
    if not anomalies:
        print("  anomaly checks: ok", file=out)
    return anomalies


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Render spark_rapids_ml_tpu telemetry JSONL"
    )
    ap.add_argument("path", help="telemetry JSONL file (TPU_ML_TELEMETRY_PATH)")
    ap.add_argument(
        "--last", type=int, default=0, metavar="N",
        help="only render the last N fit reports",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="exit 2 when any anomaly check fires",
    )
    args = ap.parse_args(argv)

    records = []
    try:
        with open(args.path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    print(f"# skipping corrupt line", file=sys.stderr)
                    continue
                if rec.get("type") in ("fit_report", "transform_report"):
                    records.append(rec)
    except OSError as e:
        print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    if not records:
        print(
            f"no fit_report/transform_report records in {args.path}",
            file=sys.stderr,
        )
        return 1
    if args.last > 0:
        records = records[-args.last:]

    n_fit = sum(1 for r in records if r.get("type") == "fit_report")
    print(
        f"{n_fit} fit report(s), {len(records) - n_fit} transform "
        f"report(s) from {args.path}"
    )
    any_anomaly = False
    skipped = 0
    for i, rec in enumerate(records):
        is_transform = rec.get("type") == "transform_report"
        supported = (
            SUPPORTED_TRANSFORM_SCHEMA if is_transform else SUPPORTED_SCHEMA
        )
        schema = rec.get("schema", 1)
        if isinstance(schema, (int, float)) and schema > supported:
            print(
                f"# skipping record {i}: schema {schema} is newer than this "
                f"tool understands (<= {supported}) — upgrade "
                "tools/trace_report.py",
                file=sys.stderr,
            )
            skipped += 1
            continue
        try:
            renderer = render_transform_record if is_transform else render_record
            if renderer(rec):
                any_anomaly = True
        except Exception as e:  # noqa: BLE001 — a bad record must not
            # hide the rest of the file
            print(
                f"# skipping unrenderable record {i} "
                f"({type(e).__name__}: {e})",
                file=sys.stderr,
            )
            skipped += 1
    if skipped:
        print(f"# {skipped} record(s) skipped", file=sys.stderr)
    return 2 if (args.strict and (any_anomaly or skipped)) else 0


if __name__ == "__main__":
    raise SystemExit(main())
