"""How far one of the program's counters moved over the window, for each
second inside the host span ``phase`` (``span.seconds{phase}``), times
``scale``: a counter of seconds over a span's seconds is a share. A program
without the counter, or a window without the span, reads as nothing."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    moved = ctx.registry.counter(spec["counter"], **spec.get("labels", {}))
    seconds = ctx.registry.hist("span.seconds", phase=spec["phase"]).total
    if not moved or not seconds:
        return None
    return float(moved) / seconds * spec.get("scale", 1.0)
