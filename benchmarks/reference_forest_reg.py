"""The plain reference for a random-forest regressor fit: float64 NumPy with
no kernel, and the numbers that decide ``correct``. Imports nothing of the
program; what the program drew at random (each tree's bootstrap counts and
node subsets, the rows of the edge sample) and its bin edges are handed in.
The binning, the edges, the fold of copies onto distinct rows and the tie of
thresholds to edges are ``reference_forest``'s.

A tree is judged one step at a time from the program's own tree, as the
classifier's is: the rows reach a node by the program's splits, on the
reference's own bins; there the reference

- sums the rows' weights, weighted labels and weighted squared labels
  ``[Σw, Σw·y, Σw·y²]`` in float64, the label as the program holds it (its
  float32). Σw must equal the program's node total exactly
  (``leaf_count_gap``: integer weights in float32 sums); Σw·y may differ by
  float32's rounding, ``|Σw·y − ref| / Σw·|y|`` (``leaf_sum_gap``);
- builds the node's histogram over the program's subset F_n (``k`` features,
  ``B`` bins) and from it every candidate split's variance gain

      var_n(s) = Σw·y² − (Σw·y)² / Σw,   gain = var_n(T) − var_n(L) − var_n(R)

  valid when Σw(L) ≥ minInstancesPerNode, Σw(R) ≥ minInstancesPerNode, the
  bin is not the last and the gain is over the floor (``ops.forest``'s rule
  in float64: 1e-12, or 32 of the program's ulps of the node's Σw·y² where
  that is more);
- checks the program's split: its feature in F_n (``subset_off``), valid
  (``invalid_splits``: the counts and the bin as above, and a gain over 0,
  since float32 may read a gain a floor's width off), and how far its gain
  falls under the best valid one beyond the floor, over var_n(T), the
  node's n·variance (``split_regret``); a node the program left a leaf
  above the depth cap stands at the floor. The floor is float32's
  resolution of a gain at the node: where the node's mean is far from its
  spread, Σw·y² is var_n(T) times (1 + mean²/variance), and two gains that
  differ by less than its ulps are a tie to the program (a node of 4
  distinct rows, mean 588, read 19,552.60 where the best is 19,553.53,
  with a float32 ulp of Σw·y² of 2).

A node's levels are judged side by side on a pool of threads (the sorts and
sums below release the interpreter's lock): the rows' route is the
program's, so no level waits for another's verdict.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.reference_forest import (  # noqa: F401  (the classifier's, shared)
    BROKEN,
    bin_rows,
    distinct_of,
    edge_gap,
    fold,
    quantile_edges,
    thresholds_off,
)

COMPARED = (
    "bins_off", "thresholds_off", "edge_gap", "leaf_count_gap", "leaf_sum_gap",
    "subset_off", "invalid_splits", "split_regret",
)
GAIN_FLOOR = 1e-12
#: subset slots whose (row, slot) pairs one pass takes: bounds a level's memory
SLOTS_A_SORT = 128
#: a level counts its (node, slot, bin) cells whole where a pass has at most
#: this many, and sorts the cells its rows fill where it has more
DENSE_CELLS = 1 << 22


def var_n(stats: np.ndarray) -> np.ndarray:
    """n·variance of ``[Σw, Σw·y, Σw·y²]`` over the trailing axis; 0 for an
    empty cell."""
    w = stats[..., 0]
    safe = np.where(w > 0, w, 1.0)
    return np.where(w > 0, np.maximum(stats[..., 2] - stats[..., 1] ** 2 / safe, 0.0), 0.0)


def gain_floor(sum_wy2: np.ndarray, eps: float) -> np.ndarray:
    """``ops.forest.gain_floor``'s rule for variance, in float64: 1e-12, or 32
    of the program's ulps of the node's Σw·y² where that is more."""
    return np.maximum(GAIN_FLOOR, 32.0 * eps * sum_wy2)


def judge_tree(tree: dict, bins: np.ndarray, labels: np.ndarray, w: np.ndarray,
               subsets: list[np.ndarray], *, max_depth: int, n_bins: int,
               min_instances: float, eps: float, pool: ThreadPoolExecutor | None = None,
               ) -> dict[str, float]:
    """One program tree against the reference, node by node.

    ``tree``: the program's heap arrays ``feature``, ``split_bin``,
    ``leaf_stats`` ([nodes, 3]); ``bins`` [rows, F] the reference's bins;
    ``labels`` [rows] as the program holds them; ``w`` [rows] the tree's
    folded weights; ``subsets[d]`` [2^d, k] the program's node subsets of
    level d; ``eps`` the program's float epsilon (:func:`gain_floor`)."""
    nodes_all = 2 ** (max_depth + 1) - 1
    feature = np.full(nodes_all, -1, np.int64)
    split_bin = np.zeros(nodes_all, np.int64)
    leaf_stats = np.zeros((nodes_all, 3))
    have = min(nodes_all, len(tree["feature"]))
    feature[:have] = tree["feature"][:have]
    split_bin[:have] = tree["split_bin"][:have]
    leaf_stats[:have] = np.asarray(tree["leaf_stats"], np.float64)[:have, :3]

    y = np.asarray(labels, np.float64)
    levels = []
    for d, rows, node in _route(feature, split_bin, bins, w, max_depth):
        stats = np.stack([w[rows], w[rows] * y[rows], w[rows] * y[rows] ** 2], axis=1)
        levels.append((d, rows, node, stats, w[rows] * np.abs(y[rows])))

    def one(level) -> dict[str, float]:
        d, rows, node, stats, abs_y = level
        offset, nodes_d = 2 ** d - 1, 2 ** d
        local = node - offset
        totals = np.stack([np.bincount(local, stats[:, c], nodes_d) for c in range(3)], 1)
        got = leaf_stats[offset : offset + nodes_d]
        scale = np.bincount(local, abs_y, nodes_d)
        out = {
            "leaf_count_gap": float(np.max(np.abs(got[:, 0] - totals[:, 0]))),
            "leaf_sum_gap": float(np.max(
                np.abs(got[:, 1] - totals[:, 1]) / np.where(scale > 0, scale, 1.0))),
        }
        if d < max_depth and len(rows):
            level_slice = slice(offset, offset + nodes_d)
            out.update(_judge_level(
                local, bins[rows], stats, totals, subsets[d], feature[level_slice],
                split_bin[level_slice], n_bins, min_instances, eps))
        return out

    judged = list(pool.map(one, levels)) if pool is not None else [one(v) for v in levels]
    out = dict.fromkeys(("leaf_count_gap", "leaf_sum_gap", "subset_off", "invalid_splits",
                         "split_regret"), 0.0)
    for read in judged:
        for name, value in read.items():
            out[name] = out[name] + value if name in ("subset_off", "invalid_splits") \
                else max(out[name], value)
    return out


def _route(feature, split_bin, bins, w, max_depth):
    """(depth, rows, heap node of each) level by level: the rows of positive
    weight routed by the program's splits; rows at its leaves stop."""
    rows = np.flatnonzero(w > 0)
    node = np.zeros(len(rows), np.int64)
    for d in range(max_depth + 1):
        yield d, rows, node
        if d == max_depth or not len(rows):
            return
        f = feature[node]
        goes = f >= 0
        right = bins[rows, np.maximum(f, 0)] > split_bin[node]
        node = (2 * node + 1 + right)[goes]
        rows = rows[goes]


def best_valid_gains(local: np.ndarray, bins: np.ndarray, stats: np.ndarray,
                     totals: np.ndarray, subset: np.ndarray, n_bins: int,
                     min_instances: float, eps: float) -> np.ndarray:
    """[nodes] each node's best valid gain over its subset, -inf where it has
    none, SLOTS_A_SORT slots at a time: each (node, slot)'s histogram over
    its bins, a cumsum along them, and every bin's split. A level of few
    nodes counts its (node, slot, bin) cells whole; one of many sorts the
    cells its rows fill."""
    nodes, k = subset.shape
    n = len(local)
    best = np.full(nodes, -np.inf)
    floor = gain_floor(totals[:, 2], eps)
    whole = var_n(totals)
    for j0 in range(0, k, SLOTS_A_SORT):
        sub = subset[:, j0 : j0 + SLOTS_A_SORT]
        c = sub.shape[1]
        slot_bins = np.take_along_axis(bins, sub[local], axis=1)  # [rows, c]
        key = ((local[:, None] * c + np.arange(c)) * n_bins + slot_bins).reshape(-1)
        if nodes * c * n_bins <= DENSE_CELLS:
            cells = nodes * c * n_bins
            sums = np.stack([np.bincount(key, np.repeat(stats[:, s], c), cells)
                             for s in range(3)], axis=1)
            left = np.cumsum(sums.reshape(nodes * c, n_bins, 3), axis=1).reshape(-1, 3)
            keys = np.arange(cells)
        else:
            shift = max(1, int(n).bit_length())
            packed = np.sort((key << shift) | np.repeat(np.arange(n, dtype=np.int64), c))
            keys = packed >> shift
            cut = np.r_[0, np.flatnonzero(np.diff(keys)) + 1]
            sums = np.add.reduceat(stats[packed & ((1 << shift) - 1)], cut, axis=0)
            keys = keys[cut]
            cum = np.cumsum(sums, axis=0)
            group = keys // n_bins
            first = np.r_[0, np.flatnonzero(np.diff(group)) + 1]
            start = np.repeat(first, np.diff(np.r_[first, len(keys)]))
            left = cum - np.where(start[:, None] > 0, cum[np.maximum(start - 1, 0)], 0.0)
        node_of = keys // (c * n_bins)  # ascending, as the keys are
        right = totals[node_of] - left
        gain = whole[node_of] - var_n(left) - var_n(right)
        valid = ((left[:, 0] >= min_instances) & (right[:, 0] >= min_instances)
                 & (keys % n_bins < n_bins - 1) & (gain > floor[node_of]))
        runs = np.r_[0, np.flatnonzero(np.diff(node_of)) + 1]
        here = np.maximum.reduceat(np.where(valid, gain, -np.inf), runs)
        best[node_of[runs]] = np.maximum(best[node_of[runs]], here)
    return best


def _judge_level(local, bins, stats, totals, subset, feature, split_bin,
                 n_bins, min_instances, eps) -> dict[str, float]:
    """Every node of one level: the program's split against the best valid
    split over its subset. ``bins`` are the level's rows'."""
    nodes = subset.shape[0]
    best = best_valid_gains(local, bins, stats, totals, subset, n_bins, min_instances, eps)
    has_best = np.isfinite(best)
    scale = var_n(totals)
    scale = np.where(scale > 0, scale, 1.0)

    # the program's own split at every node, on the same rows
    split = feature >= 0
    f_row = np.maximum(feature[local], 0)
    goes_left = (bins[np.arange(len(local)), f_row] <= split_bin[local]) & split[local]
    lft = np.stack([np.bincount(local, stats[:, c] * goes_left, nodes) for c in range(3)], 1)
    rgt = totals - lft
    g = var_n(totals) - var_n(lft) - var_n(rgt)
    floor = gain_floor(totals[:, 2], eps)
    # a split float32 read over the floor may lie under it by float32's
    # resolution, which is the floor itself; one that gains nothing is not
    ok = ((lft[:, 0] >= min_instances) & (rgt[:, 0] >= min_instances)
          & (split_bin < n_bins - 1) & (g > 0.0))
    in_subset = (subset == feature[:, None]).any(axis=1)

    # the shortfall beyond float32's resolution of a gain at the node (the
    # floor): a leaf above the cap stands at the floor, the least it could
    # have taken; a shortfall inside the floor is float32's tie
    taken = np.where(split, g, floor)
    short = np.where(has_best & (split & ok | ~split), best - taken - floor, 0.0)
    regret = np.maximum(short, 0.0) / scale
    return {
        "subset_off": float(np.sum(split & ~in_subset)),
        "invalid_splits": float(np.sum(split & ~ok)),
        "split_regret": float(np.max(regret, initial=0.0)),
    }


def control_sum_gap(tree: dict, bins: np.ndarray, labels: np.ndarray, w: np.ndarray,
                    max_depth: int) -> float:
    """The contract's control: ``leaf_sum_gap`` of the reference computed a
    precision below the configuration's, each w·y at one bfloat16 part
    (summed in float64), against its float64 sums, over every node the
    program's tree routes rows to."""
    from ml_dtypes import bfloat16

    y = np.asarray(labels, np.float64)
    wy = w * y
    rounded = wy.astype(bfloat16).astype(np.float64)
    feature = np.asarray(tree["feature"], np.int64)
    split_bin = np.asarray(tree["split_bin"], np.int64)
    worst = 0.0
    for d, rows, node in _route(feature, split_bin, bins, w, max_depth):
        local = node - (2 ** d - 1)
        exact = np.bincount(local, wy[rows], 2 ** d)
        scale = np.bincount(local, np.abs(wy[rows]), 2 ** d)
        gap = np.abs(np.bincount(local, rounded[rows], 2 ** d) - exact)
        worst = max(worst, float(np.max(gap / np.where(scale > 0, scale, 1.0))))
    return worst
