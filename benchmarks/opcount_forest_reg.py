"""The least bytes a random-forest regressor fit's histogram build moves,
from the configuration alone: ``opcount_forest``'s count with a regressor's
subset and statistics, whatever implements the build, so no later change can
move it. What an implementation adds (rows of padding, the pieces of a
sorted level, the blocks a level is walked in, the gains' own passes) is not
counted, nor any operation.

For each tree and each level d below the depth cap, every configured row is
read once (its k = ceil(F/3) subset bins of 1 byte, its node, weight and
label of 4 bytes each) and the level's histogram written once (2^d nodes x k
features x B bins x 3 statistics [w, w.y, w.y^2], float32); the capped
level reads each row's node, weight and label for its totals. At the
rfreg-3000-d13 cell's shape that is 19.2 GB a tree, 23 ms at the v5e's
819 GB/s: the deep levels' histograms are most of it."""

from __future__ import annotations

import math

from benchmarks import sources


def forest_fit(
    rows: float, n: float, max_depth: float, n_bins: float, n_trees: float,
) -> dict[str, float]:
    """One fit of ``n_trees`` regression trees with 'auto' features a node
    (ceil(F/3)): bytes as above, no operations counted."""
    k = math.ceil(n / 3.0)
    stats = 3.0
    per_tree = sum(
        rows * (k * 1.0 + 12.0) + (2.0 ** d) * k * n_bins * stats * 4.0
        for d in range(int(max_depth))
    ) + rows * 12.0
    return {"flops": 0.0, "bytes": n_trees * per_tree}


def work(spec: dict, config: dict) -> dict[str, float]:
    """The bytes of one unit of the work ``spec`` names."""
    args = {name: sources.lookup(config, path) for name, path in spec["args"].items()}
    return globals()[spec["work"]](**args)
