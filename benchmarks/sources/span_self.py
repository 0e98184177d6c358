"""Seconds of one of the program's host spans that none of its child spans
covered (``span.self_seconds{phase}`` in its registry), over the window, for
each occurrence of ``per_span``. A program that books no self time (one from
before spans knew their parent) reads as nothing."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    own = ctx.registry.hist("span.self_seconds", phase=spec["phase"])
    per = ctx.registry.hist("span.seconds", phase=spec["per_span"]).count
    if not own.count or not per:
        return None
    return own.total / per * spec.get("scale", 1.0)
