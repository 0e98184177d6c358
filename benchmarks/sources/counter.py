"""How far one of the program's counters moved over the window."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    return float(ctx.registry.counter(spec["counter"], **spec.get("labels", {})))
