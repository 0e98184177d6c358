"""The least bytes a random-forest fit's histogram build moves, from the
configuration alone: the algorithm's count, whatever implements the build,
so no later change can move it. What an implementation adds (rows of padding,
the pieces of a sorted level, the gains' own passes) is not counted, nor any
operation: the build is bound by memory, and its arithmetic is a handful of
adds a byte.

For each tree and each level d below the depth cap, every configured row is
read once (its k subset bins of 1 byte, its node, weight and label of 4 bytes
each) and the level's histogram written once (2^d nodes x k features x B bins
x S classes, float32); the capped level reads each row's node, weight and
label for its totals. Every configured row is counted at every level, the
rows a tree's bootstrap weighs 0 (about 37% under Poisson(1)) and the rows
already at a leaf among them: a build that skips them reads fewer bytes than
this count. At the rf-3000-d13 cell's shape that is 0.9 GB a tree, 1.1 ms at
the v5e's 819 GB/s."""

from __future__ import annotations

import math

from benchmarks import sources


def forest_fit(
    rows: float, n: float, max_depth: float, n_bins: float, n_trees: float,
) -> dict[str, float]:
    """One fit of ``n_trees`` binary classification trees with 'auto'
    features a node (ceil(sqrt(F))): bytes as above, no operations counted."""
    k = math.ceil(math.sqrt(n))
    classes = 2.0
    levels = int(max_depth)
    per_tree = sum(
        rows * (k * 1.0 + 12.0) + (2.0 ** d) * k * n_bins * classes * 4.0
        for d in range(levels)
    ) + rows * 12.0
    return {"flops": 0.0, "bytes": n_trees * per_tree}


def work(spec: dict, config: dict) -> dict[str, float]:
    """The bytes of one unit of the work ``spec`` names, as
    ``sources.work`` finds them for ``opcount``."""
    args = {name: sources.lookup(config, path) for name, path in spec["args"].items()}
    return globals()[spec["work"]](**args)
