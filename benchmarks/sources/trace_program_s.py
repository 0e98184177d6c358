"""Device seconds of a named program (the mean over the devices, as
``trace_reduce`` hands it over) for each occurrence of the host span
``per_span`` in the window: ``trace_program_ms`` in seconds."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    if not ctx.trace:
        return None
    runs = ctx.trace["programs"].get(spec["program"])
    per = ctx.registry.hist("span.seconds", phase=spec["per_span"]).count
    if not runs or not runs["count"] or not per:
        return None
    return runs["seconds"] / per
