"""The mean of the samples one of the program's histograms took over the
window (every series of ``hist`` whose labels hold ``labels``), times
``scale``. No sample reads as nothing."""

from __future__ import annotations


def read(spec: dict, ctx) -> float | None:
    samples = ctx.registry.hist(spec["hist"], **spec.get("labels", {}))
    if not samples.count:
        return None
    return samples.total / samples.count * spec.get("scale", 1.0)
