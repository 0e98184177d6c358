"""A whole KMeans fit's share of the chips' peak: the useful operations
(``opcount_kmeans``) of every fit completed in the window, over elapsed time x
chips x the bf16 peak. ``step_share`` with the clustering counts."""

from __future__ import annotations

from benchmarks import opcount_kmeans


def read(spec: dict, ctx) -> float | None:
    if not (ctx.completed and ctx.elapsed_s and ctx.peak):
        return None
    flops = opcount_kmeans.work(spec, ctx.config)["flops"] * ctx.completed
    return 100.0 * flops / (ctx.elapsed_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
