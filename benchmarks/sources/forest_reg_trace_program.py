"""The regression forest program's share of its roofline: the least time the
chip could take for the program's traced runs (``opcount_forest_reg``: the
histogram build's bytes at the HBM peak), over the device time of its
events. ``forest_trace_program`` with the regressor's count."""

from __future__ import annotations

from benchmarks import opcount, opcount_forest_reg


def read(spec: dict, ctx) -> float | None:
    if not (ctx.trace and ctx.peak):
        return None
    runs = ctx.trace["programs"].get(spec["program"])
    if not runs or not runs["seconds"]:
        return None
    least, _ = opcount.least_seconds(opcount_forest_reg.work(spec, ctx.config), ctx.peak)
    return 100.0 * least * runs["count"] / runs["seconds"]


def binding(spec: dict, ctx) -> str:
    return opcount.least_seconds(opcount_forest_reg.work(spec, ctx.config), ctx.peak)[1]
