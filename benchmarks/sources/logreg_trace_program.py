"""A logistic-regression device program's share of its roofline: the least
time the chip could take for the program's traced runs (``opcount_logreg``),
over the device time of its events. ``trace_program`` with the Newton
counts."""

from __future__ import annotations

from benchmarks import opcount, opcount_logreg


def read(spec: dict, ctx) -> float | None:
    if not (ctx.trace and ctx.peak):
        return None
    runs = ctx.trace["programs"].get(spec["program"])
    if not runs or not runs["seconds"]:
        return None
    least, _ = opcount.least_seconds(opcount_logreg.work(spec, ctx.config), ctx.peak)
    return 100.0 * least * runs["count"] / runs["seconds"]


def binding(spec: dict, ctx) -> str:
    return opcount.least_seconds(opcount_logreg.work(spec, ctx.config), ctx.peak)[1]
