"""A whole logistic-regression fit's share of the chips' peak: the useful
operations (``opcount_logreg``) of every fit completed in the window, over
elapsed time x chips x the bf16 peak. ``step_share`` with the Newton counts."""

from __future__ import annotations

from benchmarks import opcount_logreg


def read(spec: dict, ctx) -> float | None:
    if not (ctx.completed and ctx.elapsed_s and ctx.peak):
        return None
    flops = opcount_logreg.work(spec, ctx.config)["flops"] * ctx.completed
    return 100.0 * flops / (ctx.elapsed_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
