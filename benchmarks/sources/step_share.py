"""The whole step's share of the chips' peak: useful operations of all the
work completed in the window, over elapsed time x chips x the bf16 peak."""

from __future__ import annotations

from benchmarks.sources import work


def read(spec: dict, ctx) -> float | None:
    if not (ctx.completed and ctx.elapsed_s and ctx.peak):
        return None
    flops = work(spec, ctx.config)["flops"] * ctx.completed
    return 100.0 * flops / (ctx.elapsed_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
