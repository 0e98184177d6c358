#!/usr/bin/env python
"""Bench-history regression sentinel over bench_history.jsonl.

``bench.py`` appends one ``perf_ledger`` record per run (every emitted
metric as ``name -> {value, unit}`` plus the analytical cost-model
numbers). This tool compares the LAST entry against the median of the
preceding ``--last N`` entries, metric by metric, and flags any move beyond
``--threshold`` in the *worse* direction — the direction is derived from
the unit (``rows/s`` up is good, ``seconds`` up is bad), so one rule covers
throughputs, latencies and accuracy bars alike::

    python tools/perf_sentinel.py bench_history.jsonl            # report
    python tools/perf_sentinel.py bench_history.jsonl --strict   # CI gate

``--strict`` exits 2 on any regression, which is how ``bench --smoke``
becomes a perf gate (``TPU_ML_PERF_SENTINEL=1`` makes the bench invoke this
itself after appending). A fresh ledger (fewer than 2 entries) always
passes — there is no history to regress against. Smoke and full-shape runs
are never compared with each other (filtered on the entry's ``smoke``
flag), runs under different precision policies likewise (filtered on the
entry's ``tuning`` signature, so a bench run under ``bf16_f32acc`` never
judges — or poisons — the default-config history), and metrics absent
from history are reported as new, not judged.

Two gates stack on top of the history comparison:

- **Vanished metrics.** A metric present in every comparable history
  entry but absent from the current one is itself a regression — the
  gated series (``serve_p99_ms``, throughputs, ...) cannot silently drop
  out of the bench and out of gating with it.
- **Absolute ceilings.** A metric may carry a ``ceiling`` alongside its
  value/unit (``bench.py`` stamps one on ``serve_p99_ms`` when
  ``TPU_ML_SERVE_P99_GATE_MS`` is set); crossing it in the unit's worse
  direction is a regression regardless of history — and since ceilings
  ride the entry itself, ``--bless`` cannot wave one through.

Blessing an intentional perf change: ``--bless`` truncates the ledger to
its last entry, making the new numbers the baseline history (see
CONTRIBUTING.md for the workflow).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys

# runnable straight from a checkout (matches the other tools/ CLIs)
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

DEFAULT_LAST = 5
DEFAULT_THRESHOLD = 0.35  # relative move considered a regression

# units where a LOWER value is better; every other unit (rows/s, queries/s,
# cosine, ...) reads higher-is-better
_LOWER_IS_BETTER_UNITS = ("seconds", "s", "ms", "bytes")


def load_ledger(path: str) -> list[dict]:
    entries: list[dict] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            if rec.get("type") == "perf_ledger":
                entries.append(rec)
    return entries


def lower_is_better(unit: str) -> bool:
    return unit.strip().lower() in _LOWER_IS_BETTER_UNITS


def tuning_signature(entry: dict) -> str:
    """Canonical form of an entry's ``tuning`` stamp (bench.py writes the
    precision policy there when it is not ``f32``).

    Entries written before the field existed — and entries from
    default-config runs, which omit it — normalize to the same ``"{}"``
    signature, so that history keeps judging default runs."""
    return json.dumps(entry.get("tuning") or {}, sort_keys=True)


def compare(
    current: dict,
    history: list[dict],
    threshold: float,
) -> tuple[list[dict], list[str]]:
    """(regressions, notes) of the current entry vs the history median.

    A regression is a metric whose value moved more than ``threshold``
    (relative) in the worse direction for its unit, crossed its declared
    absolute ``ceiling``, or vanished from the current entry despite being
    present in every history entry. Notes cover metrics with no usable
    history (new metric, zero baseline).
    """
    regressions: list[dict] = []
    notes: list[str] = []
    current_metrics = current.get("metrics") or {}
    # a gated metric must not silently drop out of the bench: present in
    # every comparable history entry + absent now = regression
    for name in sorted(
        set.intersection(
            *(set(e.get("metrics") or {}) for e in history)
        ) - set(current_metrics)
        if history else ()
    ):
        regressions.append({
            "metric": name,
            "unit": "",
            "value": None,
            "baseline_median": None,
            "ratio": None,
            "n_history": len(history),
            "vanished": True,
        })
    for name, cur in sorted(current_metrics.items()):
        try:
            value = float(cur.get("value"))
        except (TypeError, ValueError):
            continue
        unit = str(cur.get("unit", ""))
        ceiling = cur.get("ceiling")
        if isinstance(ceiling, (int, float)):
            beyond = (
                value > float(ceiling) if lower_is_better(unit)
                else value < float(ceiling)
            )
            if beyond:
                regressions.append({
                    "metric": name,
                    "unit": unit,
                    "value": value,
                    "baseline_median": float(ceiling),
                    "ratio": value / ceiling if ceiling else float("inf"),
                    "n_history": 0,
                    "ceiling": True,
                })
                continue
        past = []
        for entry in history:
            m = (entry.get("metrics") or {}).get(name)
            if m is None:
                continue
            try:
                past.append(float(m.get("value")))
            except (TypeError, ValueError):
                continue
        if not past:
            notes.append(f"{name}: no history (new metric)")
            continue
        baseline = statistics.median(past)
        if baseline == 0:
            notes.append(f"{name}: zero baseline, skipped")
            continue
        ratio = value / baseline
        worse = ratio > 1.0 + threshold if lower_is_better(unit) \
            else ratio < 1.0 - threshold
        if worse:
            regressions.append({
                "metric": name,
                "unit": unit,
                "value": value,
                "baseline_median": baseline,
                "ratio": ratio,
                "n_history": len(past),
            })
    return regressions, notes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Flag bench regressions against the perf-ledger history"
    )
    ap.add_argument("path", help="bench_history.jsonl (appended by bench.py)")
    ap.add_argument(
        "--last", type=int, default=DEFAULT_LAST, metavar="N",
        help=f"history window: median of the last N prior entries "
             f"(default {DEFAULT_LAST})",
    )
    ap.add_argument(
        "--threshold", type=float, default=DEFAULT_THRESHOLD,
        help=f"relative move in the worse direction that counts as a "
             f"regression (default {DEFAULT_THRESHOLD})",
    )
    ap.add_argument(
        "--strict", action="store_true",
        help="exit 2 when any metric regressed (the CI gate)",
    )
    ap.add_argument(
        "--bless", action="store_true",
        help="accept the current numbers: truncate the ledger to its last "
             "entry so future runs compare against the new baseline",
    )
    args = ap.parse_args(argv)

    try:
        entries = load_ledger(args.path)
    except OSError as e:
        print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    if not entries:
        print(f"perf-sentinel: no ledger entries in {args.path} — pass")
        return 0

    current = entries[-1]
    # never judge a smoke run against full-shape history or vice versa,
    # and never cross-compare runs under different tuning stamps
    history = [
        e for e in entries[:-1]
        if bool(e.get("smoke")) == bool(current.get("smoke"))
        and tuning_signature(e) == tuning_signature(current)
    ]
    if args.last > 0:
        history = history[-args.last:]

    if args.bless:
        with open(args.path, "w", encoding="utf-8") as f:
            f.write(json.dumps(current, sort_keys=True) + "\n")
        print(
            f"perf-sentinel: blessed — ledger truncated to the latest entry "
            f"({len(entries) - 1} historical entries dropped)"
        )
        return 0

    regressions, notes = compare(current, history, args.threshold)
    if not history:
        # a declared absolute ceiling rides the entry itself, so it gates
        # even a fresh ledger (and right after --bless); history-relative
        # notes are meaningless without comparable history
        regressions = [r for r in regressions if r.get("ceiling")]
        notes = []
        if not regressions:
            print(
                "perf-sentinel: fresh ledger (no comparable history) — pass"
            )
            return 0
    for note in notes:
        print(f"  note: {note}")
    if not regressions:
        print(
            f"perf-sentinel: OK — {len(current.get('metrics') or {})} "
            f"metrics within {args.threshold:.0%} of the median of "
            f"{len(history)} prior runs"
        )
        return 0

    print(
        f"perf-sentinel: {len(regressions)} regression(s) beyond "
        f"{args.threshold:.0%} vs the median of {len(history)} prior runs:"
    )
    for r in regressions:
        if r.get("vanished"):
            print(
                f"  REGRESSION {r['metric']}: present in all "
                f"{r['n_history']} comparable history entries but missing "
                "from the current entry — the gated series dropped out of "
                "the bench"
            )
            continue
        if r.get("ceiling"):
            bound = "ceiling" if lower_is_better(r["unit"]) else "floor"
            print(
                f"  REGRESSION {r['metric']}: {r['value']:g} {r['unit']} "
                f"crossed the declared absolute {bound} "
                f"{r['baseline_median']:g} ({r['ratio']:.2f}x)"
            )
            continue
        direction = "slower" if lower_is_better(r["unit"]) else "lower"
        print(
            f"  REGRESSION {r['metric']}: {r['value']:g} {r['unit']} vs "
            f"median {r['baseline_median']:g} "
            f"({r['ratio']:.2f}x, {direction}; n={r['n_history']})"
        )
    print(
        "  intentional? bless the new baseline: "
        f"python tools/perf_sentinel.py {args.path} --bless"
    )
    return 2 if args.strict else 0


if __name__ == "__main__":
    raise SystemExit(main())
