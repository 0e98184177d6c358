"""``span`` for a span that need not occur (a wait that is only entered where
there is something to wait for): seconds inside ``phase`` over the window for
each occurrence of ``per_span``, and 0.0 where ``per_span`` occurred and
``phase`` never did. A window without ``per_span`` reads as nothing."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    per = ctx.registry.hist("span.seconds", phase=spec["per_span"]).count
    if not per:
        return None
    spans = ctx.registry.hist("span.seconds", phase=spec["phase"])
    return spans.total / per * spec.get("scale", 1.0)
