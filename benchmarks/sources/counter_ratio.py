"""One of the program's counters over another, both as they moved over the
window (each an exact series, ``labels`` and ``per_labels``), times
``scale``. With nothing to divide, or nothing to divide by, it reads as
nothing."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    over = ctx.registry.counter(spec["counter"], **spec.get("labels", {}))
    under = ctx.registry.counter(spec["per_counter"], **spec.get("per_labels", {}))
    if not over or not under:
        return None
    return float(over) / float(under) * spec.get("scale", 1.0)
