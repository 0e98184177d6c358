"""The plain reference for a random-forest classifier fit: float64 NumPy with
no kernel, and the numbers that decide ``correct``. Imports nothing of the
program; what the program drew at random (each tree's bootstrap counts and
node subsets, the rows of the edge sample) and its bin edges are handed in.

A tree is judged one step at a time from the program's own tree, so that
one near-tie cannot send the two down different trees (a comparison of two
trajectories is chaotic: the KMeans cell's ``center_gap``). The rows reach a node by
the program's splits, on the reference's own bins; there the reference

- sums the rows' weighted class counts, which must equal the program's node
  totals (``leaf_count_gap``, exact: integer weights in float32 sums);
- builds the node's histogram over the program's subset F_n (``k`` features,
  ``B`` bins, 2 classes), and from it every candidate split's gini gain

      gini_n(s) = N(s) − Σ_c s_c² / N(s),   gain = gini_n(T) − gini_n(L) − gini_n(R)

  valid when N(L) ≥ minInstancesPerNode, N(R) ≥ minInstancesPerNode, the bin
  is not the last and the gain is over the floor (``ops.forest.build_tree``'s
  rule, in float64: 1e-12, or 32 of the program's ulps of N(T) where that is
  more, under which the program's float32 reads a zero gain);
- checks the program's split: its feature in F_n (``subset_off``), valid
  (``invalid_splits``), and how far its gain falls under the best valid one,
  over gini_n(T) (``split_regret``); a node the program left a leaf above the
  depth cap reads the best valid gain itself.

A distinct row that stands in the table several times is one row here with
its copies' bootstrap counts summed: the same sums.
"""

from __future__ import annotations

import numpy as np

COMPARED = (
    "bins_off", "thresholds_off", "edge_gap", "leaf_count_gap", "subset_off",
    "invalid_splits", "split_regret",
)
BROKEN = float("inf")
GAIN_FLOOR = 1e-12


def gini_n(stats: np.ndarray) -> np.ndarray:
    """n·gini over the trailing class axis; 0 for an empty cell."""
    n = stats.sum(-1)
    safe = np.where(n > 0, n, 1.0)
    return np.where(n > 0, n - (stats * stats).sum(-1) / safe, 0.0)


def distinct_of(order: list[int], block_rows: int) -> np.ndarray:
    """[table rows] the distinct row each table row is: kind·block_rows + i."""
    kinds = np.repeat(np.asarray(order, np.int64), block_rows)
    return kinds * block_rows + np.tile(np.arange(block_rows), len(order))


def fold(weights: np.ndarray, distinct: np.ndarray, n_distinct: int) -> np.ndarray:
    """[T, distinct] the table rows' weights summed onto their distinct row."""
    return np.stack([np.bincount(distinct, w, n_distinct) for w in weights])


def quantile_edges(sample: np.ndarray, n_bins: int) -> np.ndarray:
    """[F, B − 1] the sample's interior quantiles in float64, np.quantile's
    linear interpolation."""
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    return np.quantile(sample, qs, axis=0).T


def edge_gap(edges: np.ndarray, reference: np.ndarray) -> float:
    """The largest gap of the program's edges to the reference's, over the
    span of the feature's reference edges (first to last)."""
    span = reference[:, -1] - reference[:, 0]
    span = np.where(span > 0, span, 1.0)
    return float(np.max(np.abs(edges.astype(np.float64) - reference) / span[:, None]))


def bin_rows(x32: np.ndarray, edges32: np.ndarray) -> np.ndarray:
    """[rows, F] int16 bins: np.searchsorted(edges[f], x[:, f], side='left')
    of the float32 values against the float32 edges."""
    out = np.empty(x32.shape, np.int16)
    for f in range(x32.shape[1]):
        out[:, f] = np.searchsorted(edges32[f], x32[:, f], side="left")
    return out


def thresholds_off(feature: np.ndarray, split_bin: np.ndarray,
                   thresholds: np.ndarray, edges32: np.ndarray) -> float:
    """Split nodes whose raw threshold is not the edge its (feature, bin)
    names: the tie between the fit's edges and the edges handed in."""
    split = feature >= 0
    want = edges32[feature[split], split_bin[split]].astype(np.float64)
    return float(np.sum(thresholds[split] != want))


def judge_tree(tree: dict, bins: np.ndarray, labels: np.ndarray, w: np.ndarray,
               subsets: list[np.ndarray], *, max_depth: int, n_bins: int,
               n_classes: int, min_instances: float, eps: float,
               accumulate=np.float64) -> dict[str, float]:
    """One program tree against the reference, node by node.

    ``tree``: the program's heap arrays ``feature``, ``split_bin``,
    ``is_leaf``, ``leaf_stats`` (a tree of fewer levels is read as one whose
    deeper nodes hold nothing); ``bins`` [rows, F] the reference's bins;
    ``w`` [rows] the tree's folded weights; ``subsets[d]`` [2^d, k] the
    program's node subsets of level d; ``eps`` the program's float epsilon,
    which sets the least gain that is a gain (:func:`gain_floor`).
    ``accumulate`` is the dtype the node totals are summed in (the control
    sums them in bfloat16)."""
    nodes_all = 2 ** (max_depth + 1) - 1
    feature = np.full(nodes_all, -1, np.int64)
    split_bin = np.zeros(nodes_all, np.int64)
    leaf_stats = np.zeros((nodes_all, n_classes))
    have = min(nodes_all, len(tree["feature"]))
    feature[:have] = tree["feature"][:have]
    split_bin[:have] = tree["split_bin"][:have]
    got = np.asarray(tree["leaf_stats"], np.float64)[:have]
    leaf_stats[:have, : got.shape[1]] = got[:, :n_classes]

    rows = np.flatnonzero(w > 0)  # the rows a level still holds
    y_hot = np.eye(n_classes)[labels[rows].astype(np.int64)] * w[rows, None]
    node = np.zeros(len(rows), np.int64)  # heap node of each of them
    out = dict.fromkeys(("leaf_count_gap", "subset_off", "invalid_splits",
                         "split_regret"), 0.0)
    for d in range(max_depth + 1):
        offset = 2 ** d - 1
        nodes_d = 2 ** d
        local = node - offset
        totals = _node_totals(local, y_hot, nodes_d, accumulate)
        gap = np.abs(totals - leaf_stats[offset : offset + nodes_d])
        out["leaf_count_gap"] = max(out["leaf_count_gap"], float(gap.max()))
        if d == max_depth or not len(rows):
            break
        level = slice(offset, offset + nodes_d)
        _judge_level(out, local, bins, rows, y_hot, totals, subsets[d],
                     feature[level], split_bin[level], n_bins, min_instances, eps)
        # route by the program's splits; rows at its leaves stop
        f = feature[node]
        goes = f >= 0
        right = bins[rows, np.maximum(f, 0)] > split_bin[node]
        node = (2 * node + 1 + right)[goes]
        rows, y_hot = rows[goes], y_hot[goes]
    return out


def gain_floor(n_tot: np.ndarray, eps: float) -> np.ndarray:
    """``ops.forest.gain_floor``'s rule for gini, in float64: 1e-12, or 32 of the
    program's ulps of the node's weight where that is more (what its float
    rounding leaves of a gain that is 0)."""
    return np.maximum(GAIN_FLOOR, 32.0 * eps * n_tot)


def _node_totals(local, y_hot, nodes, accumulate) -> np.ndarray:
    if accumulate is np.float64:
        return np.stack([np.bincount(local, y_hot[:, c], nodes)
                         for c in range(y_hot.shape[1])], axis=1)
    # the control: each node's rows summed in ``accumulate`` (numpy's
    # pairwise sum in that dtype)
    out = np.zeros((nodes, y_hot.shape[1]))
    order = np.argsort(local, kind="stable")
    bounds = np.searchsorted(local[order], np.arange(nodes + 1))
    hot = y_hot[order].astype(accumulate)
    for n in np.flatnonzero(np.diff(bounds)):
        out[n] = hot[bounds[n] : bounds[n + 1]].sum(axis=0, dtype=accumulate)
    return out


def _judge_level(out, local, bins, rows, y_hot, totals, subset, feature, split_bin,
                 n_bins, min_instances, eps) -> None:
    """Every node of one level: the program's split against the best valid
    split over its subset, from the level's sorted (node, slot, bin) keys."""
    nodes, k = subset.shape
    C = y_hot.shape[1]
    # (row, slot) pairs: each row's bin on each feature of its node's subset
    slot_bins = bins[rows[:, None], subset[local]]  # [rows, k]
    key = (local[:, None] * k + np.arange(k)[None, :]) * n_bins + slot_bins
    keys, inverse = np.unique(key.reshape(-1), return_inverse=True)
    counts = np.stack([
        np.bincount(inverse, np.repeat(y_hot[:, c], k), len(keys)) for c in range(C)
    ], axis=1)  # [keys, C]
    group = keys // n_bins  # node·k + slot
    b = keys % n_bins
    cum = np.cumsum(counts, axis=0)
    first = np.r_[0, np.flatnonzero(np.diff(group)) + 1]
    start = np.repeat(first, np.diff(np.r_[first, len(keys)]))
    before = np.where(start[:, None] > 0, cum[np.maximum(start - 1, 0)], 0.0)
    left = cum - before
    node_of = group // k  # ascending, as the keys are
    total = totals[node_of]
    right = total - left
    gain = gini_n(total) - gini_n(left) - gini_n(right)
    valid = ((left.sum(1) >= min_instances) & (right.sum(1) >= min_instances)
             & (b < n_bins - 1) & (gain > gain_floor(total.sum(1), eps)))
    runs = np.r_[0, np.flatnonzero(np.diff(node_of)) + 1]
    best = np.full(nodes, -np.inf)
    best[node_of[runs]] = np.maximum.reduceat(np.where(valid, gain, -np.inf), runs)
    has_best = np.isfinite(best)
    scale = gini_n(totals)
    scale = np.where(scale > 0, scale, 1.0)

    # the program's own split at every node, on the same rows
    split = feature >= 0
    f_row = np.maximum(feature[local], 0)
    goes_left = (bins[rows, f_row] <= split_bin[local]) & split[local]
    lft = np.stack([np.bincount(local, y_hot[:, c] * goes_left, nodes) for c in range(C)], 1)
    rgt = totals - lft
    g = gini_n(totals) - gini_n(lft) - gini_n(rgt)
    ok = ((lft.sum(1) >= min_instances) & (rgt.sum(1) >= min_instances)
          & (split_bin < n_bins - 1) & (g > gain_floor(totals.sum(1), eps)))
    in_subset = (subset == feature[:, None]).any(axis=1)

    out["subset_off"] += float(np.sum(split & ~in_subset))
    out["invalid_splits"] += float(np.sum(split & ~ok))
    judged = split & ok & has_best
    regret = np.where(judged, (best - g) / scale, 0.0)
    # a leaf above the cap that had a valid split
    regret = np.where(~split & has_best, best / scale, regret)
    out["split_regret"] = max(out["split_regret"], float(np.max(regret)))


def control_root_gap(weights: np.ndarray, labels: np.ndarray, leaf_stats: np.ndarray,
                     n_classes: int) -> float:
    """The contract's control: the root's class counts over every table row
    (unfolded) accumulated in bfloat16, against the program's root totals."""
    from ml_dtypes import bfloat16

    hot = (np.eye(n_classes)[labels.astype(np.int64)] * weights[:, None]).astype(bfloat16)
    root = hot.sum(axis=0, dtype=bfloat16).astype(np.float64)
    return float(np.max(np.abs(root - np.asarray(leaf_stats[0], np.float64)[:n_classes])))
