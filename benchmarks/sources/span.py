"""Seconds inside one of the program's host spans (``span.seconds{phase}``
in its registry), over the window, for each occurrence of ``per_span``."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    spans = ctx.registry.hist("span.seconds", phase=spec["phase"])
    per = ctx.registry.hist("span.seconds", phase=spec["per_span"]).count
    if not spans.count or not per:
        return None
    return spans.total / per * spec.get("scale", 1.0)
