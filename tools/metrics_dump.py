#!/usr/bin/env python
"""One-shot Prometheus text exposition from per-fit telemetry JSONL.

Usage::

    python tools/metrics_dump.py /path/to/telemetry.jsonl [--last N]

There is no long-lived server process to scrape — fits run inside batch
jobs — so this re-aggregates the ``fit_report`` and ``transform_report``
records of a JSONL sink (``TPU_ML_TELEMETRY_PATH``) into a fresh
:class:`~spark_rapids_ml_tpu.telemetry.registry.MetricsRegistry` and
prints :meth:`to_prometheus` text, suitable for a node-exporter textfile
collector or a pushgateway::

    python tools/metrics_dump.py telemetry.jsonl \\
        > /var/lib/node_exporter/textfile/tpu_ml.prom

Counter keys are parsed back from their rendered ``name{k=v,...}`` form
and re-emitted through their *declared kind*: every family listed in
``telemetry.names.HISTOGRAMS`` records a histogram sample, every family
in ``names.GAUGES`` sets a gauge, everything else increments a counter —
so ``serve.queue_delay_us`` renders with ``# TYPE ... histogram``, not as
a counter that a dashboard would rate(). The names-family meta-check in
tests/test_timeline.py asserts the TYPE line matches the declared kind
for every family, so a new family added to names.py without a kind
declaration (or a dump renderer) fails CI.

The report's dedicated fields re-emit as counters (``rows_ingested``,
``h2d_bytes``, ``collective.count``, the full ``compile.*`` family from
``telemetry.compilemon`` — count / cache hits+misses / cache time saved —
and the cost model's ``costmodel.flops`` / ``costmodel.bytes``) and
per-record scalars (``fit.wall_seconds``, ``transform.wall_seconds``,
``compile.seconds`` / ``trace_seconds`` / ``lower_seconds``) as
one-sample-per-record histograms, all labeled by estimator/transformer.

``perf_ledger`` records (bench's JSONL) render too: their serving /
refresh / fleet evidence blobs re-emit the ``serve.*`` and ``refresh.*``
families — request/error/transport counters, the latency and
µs-queue-delay digests as representative histogram samples (p50/p99 per
window, the transform-latency idiom), swap/rollback/fold counters and
the version/replica gauges — so a scrape of the ledger shows the serving
plane, not just fits. Importing the registry does not pull in jax, so
this runs on telemetry-collection hosts without it.
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable straight from a checkout: the registry import needs the repo
# root, which `python tools/metrics_dump.py` does not put on sys.path
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def parse_rendered_key(key: str) -> tuple[str, dict[str, str]]:
    """Invert ``telemetry.registry.render_key``: ``name{k=v,...}`` →
    ``(name, labels)``. Values never contain ``,`` or ``=`` (label values
    are estimator/site/phase identifiers)."""
    if "{" not in key:
        return key, {}
    name, _, rest = key.partition("{")
    labels = {}
    for part in rest.rstrip("}").split(","):
        if "=" in part:
            k, _, v = part.partition("=")
            labels[k] = v
    return name, labels


def _record_by_kind(reg, name: str, value: float, **labels) -> None:
    """Route one sample through the family's declared kind
    (``telemetry.names`` HISTOGRAMS / GAUGES; counters otherwise), so the
    re-aggregated registry renders the same Prometheus TYPE as the live
    one."""
    from spark_rapids_ml_tpu.telemetry import names

    if name in names.HISTOGRAMS or name.startswith(
        "transform.partition_seconds_"
    ):
        reg.histogram_record(name, value, **labels)
    elif name in names.GAUGES:
        reg.gauge_set(name, value, **labels)
    else:
        reg.counter_inc(name, value, **labels)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Dump telemetry JSONL as Prometheus exposition text"
    )
    ap.add_argument("path", help="telemetry JSONL file (TPU_ML_TELEMETRY_PATH)")
    ap.add_argument(
        "--last", type=int, default=0, metavar="N",
        help="only aggregate the last N fit reports",
    )
    args = ap.parse_args(argv)

    from spark_rapids_ml_tpu.telemetry.export import read_jsonl
    from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry

    try:
        records = [
            r for r in read_jsonl(args.path)
            if r.get("type")
            in ("fit_report", "transform_report", "perf_ledger")
        ]
    except OSError as e:
        print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    if not records:
        print(
            f"no fit_report/transform_report/perf_ledger records in "
            f"{args.path}",
            file=sys.stderr,
        )
        return 1
    if args.last > 0:
        records = records[-args.last:]

    reg = MetricsRegistry()
    for rec in records:
        if rec.get("type") == "transform_report":
            _aggregate_transform(reg, rec)
            continue
        if rec.get("type") == "perf_ledger":
            _aggregate_serving(reg, rec)
            continue
        est = rec.get("estimator", "")
        for key, v in (rec.get("counters") or {}).items():
            name, labels = parse_rendered_key(key)
            _record_by_kind(reg, name, v, **labels)
        for name, v in (
            ("rows_ingested", rec.get("rows_ingested", 0)),
            ("bytes_ingested", rec.get("bytes_ingested", 0)),
            ("h2d_bytes", rec.get("h2d_bytes", 0)),
        ):
            if v:
                reg.counter_inc(name, v, estimator=est)
        coll = rec.get("collectives") or {}
        for name, k in (
            ("collective.count", "count"),
            ("collective.bytes", "bytes"),
            ("collective.tree_combines", "tree_combines"),
        ):
            if coll.get(k):
                reg.counter_inc(name, coll[k], estimator=est)
        comp = rec.get("compile") or {}
        for name, k in (
            ("compile.count", "count"),
            ("compile.cache_hits", "cache_hits"),
            ("compile.cache_misses", "cache_misses"),
            ("compile.cache_time_saved_s", "cache_time_saved_s"),
        ):
            if comp.get(k):
                reg.counter_inc(name, comp[k], estimator=est)
        reg.counter_inc("fits", 1, estimator=est)
        reg.histogram_record(
            "fit.wall_seconds", rec.get("wall_seconds", 0.0), estimator=est
        )
        for name, k in (
            ("compile.seconds", "seconds"),
            ("compile.trace_seconds", "trace_seconds"),
            ("compile.lower_seconds", "lower_seconds"),
        ):
            if comp.get(k):
                reg.histogram_record(name, comp[k], estimator=est)
        _aggregate_cost_model(reg, rec, estimator=est)
        ov = rec.get("overlap_fraction")
        if ov is not None:
            reg.histogram_record("stream.overlap_fraction", ov, estimator=est)

    sys.stdout.write(reg.to_prometheus())
    return 0


def _aggregate_cost_model(reg, rec: dict, **labels) -> None:
    """Re-emit a record's analytical cost-model totals as counters."""
    cm = rec.get("cost_model") or {}
    if cm.get("analytical_flops"):
        reg.counter_inc("costmodel.flops", cm["analytical_flops"], **labels)
    if cm.get("analytical_bytes"):
        reg.counter_inc("costmodel.bytes", cm["analytical_bytes"], **labels)
    util = cm.get("roofline_utilization")
    if util is not None:
        reg.histogram_record("costmodel.roofline_utilization", util, **labels)


def _aggregate_serving(reg, rec: dict) -> None:
    """Fold one perf_ledger record's serving/refresh/fleet evidence into
    the registry: the ``serve.*`` / ``refresh.*`` families a scrape of the
    bench ledger should show. Histogram digests re-emit as representative
    samples (p50/p99 of the measured window — the transform-latency
    idiom), counters and gauges verbatim."""

    def digest(name: str, d: dict | None, **labels) -> None:
        for q in ("p50", "p99"):
            if d and d.get("count") and d.get(q) is not None:
                reg.histogram_record(name, d[q], **labels)

    serving = rec.get("serving")
    if isinstance(serving, dict):
        for name, key in (
            ("serve.requests", "requests"),
            ("serve.errors", "errors"),
            ("serve.rows", "rows"),
            ("serve.batches", "batches"),
            ("serve.aot_compiles", "aot_compiles"),
            ("serve.cold_compiles", "cold_compiles"),
            ("serve.joined_in_flight", "joined_in_flight"),
            ("serve.shed", "shed"),
            ("serve.page_in", "page_in"),
            ("serve.page_out", "page_out"),
            ("serve.hedges", "hedges"),
        ):
            if serving.get(key):
                reg.counter_inc(name, serving[key])
        if serving.get("hbm_bytes"):
            reg.gauge_set("serve.hbm_bytes", serving["hbm_bytes"])
        for lane, count in (serving.get("transport_mix") or {}).items():
            transport, _, wire = str(lane).partition("/")
            reg.counter_inc(
                "serve.transport", count, transport=transport, wire=wire
            )
        for bucket, hits in (serving.get("bucket_hits") or {}).items():
            reg.counter_inc("serve.bucket_hits", hits, bucket=str(bucket))
        for op in ("encode", "decode"):
            if (serving.get("json_codec") or {}).get(op):
                reg.counter_inc(
                    "serve.json_codec", serving["json_codec"][op], op=op
                )
        if (serving.get("trace") or {}).get("minted"):
            reg.counter_inc("serve.traces", serving["trace"]["minted"])
        digest("serve.latency", serving.get("latency"))
        digest("serve.queue_delay_seconds", serving.get("queue_delay"))
        digest("serve.queue_delay_us", serving.get("queue_delay_us"))
        digest(
            "serve.window_effective_seconds",
            serving.get("window_effective"),
        )
        digest("serve.batch_rows", serving.get("batch_rows"))

    # the serving blob's nested refresh view and the dedicated refresh
    # evidence share a schema; render whichever the record carries
    refresh = rec.get("refresh")
    refresh_view = (
        (refresh.get("refresh") if isinstance(refresh, dict) else None)
        or (serving.get("refresh") if isinstance(serving, dict) else None)
    )
    if isinstance(refresh_view, dict):
        for name, key in (
            ("serve.swaps", "swaps"),
            ("serve.swap_refused", "swap_refused"),
            ("serve.rollback", "rollbacks"),
            ("refresh.folds", "folds"),
            ("refresh.rows", "rows"),
            ("refresh.finalizes", "finalizes"),
            ("refresh.checkpoints", "checkpoints"),
            ("refresh.resumes", "resumes"),
        ):
            if refresh_view.get(key):
                reg.counter_inc(name, refresh_view[key])
        digest(
            "serve.swap_blackout_seconds", refresh_view.get("swap_blackout")
        )
        if refresh_view.get("lag_seconds"):
            reg.gauge_set("refresh.lag_seconds", refresh_view["lag_seconds"])
        for model, version in (refresh_view.get("versions") or {}).items():
            reg.gauge_set("serve.model_version", version, model=str(model))

    fleet = rec.get("fleet")
    fleet_view = (
        fleet
        if isinstance(fleet, dict)
        else (serving.get("fleet") if isinstance(serving, dict) else None)
    )
    if isinstance(fleet_view, dict):
        if fleet_view.get("replicas"):
            reg.gauge_set("serve.fleet_replicas", fleet_view["replicas"])
        # two shapes: the serving blob's flat fleet sub-dict vs the bench
        # fleet evidence (routing + rolling_restart sub-dicts)
        routing = fleet_view.get("routing") or {}
        restart = fleet_view.get("rolling_restart") or {}
        for name, value in (
            ("serve.route_hits",
             routing.get("hits", fleet_view.get("route_hits"))),
            ("serve.route_misses",
             routing.get("misses", fleet_view.get("route_misses"))),
            ("serve.drain_events",
             restart.get("drain_events", fleet_view.get("drain_events"))),
            ("serve.replica_restarts",
             restart.get(
                 "replica_restarts", fleet_view.get("replica_restarts")
             )),
        ):
            if value:
                reg.counter_inc(name, value)


def _aggregate_transform(reg, rec: dict) -> None:
    """Fold one transform_report into the registry (transformer-labeled)."""
    tr = rec.get("transformer", "")
    for key, v in (rec.get("counters") or {}).items():
        name, labels = parse_rendered_key(key)
        _record_by_kind(reg, name, v, **labels)
    for name, v in (
        ("transform.rows", rec.get("rows", 0)),
        ("transform.bytes", rec.get("bytes", 0)),
        ("transform.partitions", len(rec.get("partitions") or {})),
    ):
        if v:
            reg.counter_inc(name, v, transformer=tr)
    reg.counter_inc("transforms", 1, transformer=tr)
    reg.histogram_record(
        "transform.wall_seconds", rec.get("wall_seconds", 0.0), transformer=tr
    )
    # one sample per partition is gone by now; re-emit the report's own
    # latency digest as representative samples so the hist survives export
    lat = rec.get("partition_latency") or {}
    for q in ("p50", "p99"):
        if lat.get(q) is not None and lat.get("count"):
            reg.histogram_record(
                f"transform.partition_seconds_{q}", lat[q], transformer=tr
            )
    _aggregate_cost_model(reg, rec, transformer=tr)


if __name__ == "__main__":
    raise SystemExit(main())
