"""Random-forest device kernels — histogram trees grown level-order on TPU.

The modern spark-rapids-ml family ships RandomForestClassifier/Regressor on
cuML's GPU forest builder; the 22.12 reference this framework re-designs
stops at PCA (SURVEY.md §2), so this is a capability-add in the
KMeans/NearestNeighbors/DBSCAN spirit — same Spark ML API surface,
TPU-native internals.

Why histogram trees, and why breadth-first:

- exact-split tree building (sort every feature at every node) is
  pointer-chasing — hostile to both the MXU and XLA's static shapes.
  Quantile-binned HISTOGRAM building (the XGBoost/LightGBM formulation,
  also what Spark MLlib itself does with maxBins) turns split finding into
  dense fixed-shape reductions;
- LEVEL-ORDER growth makes every depth a fixed-shape program: each row
  selects its bins on its node's k-feature subset, and all 2^d nodes of a
  level build their [stats, k, bins] histograms in one pass over the rows
  sorted by node (one-hot products of pieces of them, summed by node:
  :func:`_level_hist`); split selection is a cumsum +
  argmax over a dense [nodes, k, B] gain tensor. No per-node recursion
  ever reaches XLA. Histograms over the subset and not over every feature
  are what keep the deep levels in memory: at 3,000 features, 128 bins and
  depth 12 a level over every feature is 12.6 GB, over 55 it is 231 MB;
  where the subset is wide (a regressor's F/3: 1,000 slots, 6.3 GB a
  level) the level walks its histogram in blocks of slots that fit a
  share of the device (:func:`level_plan`), each reduced to its nodes'
  best split before the next;
- the per-level histogram is a commutative monoid over rows — the mesh
  version (parallel/forest.py) psums it across row shards and every device
  takes identical split decisions, the same distribution shape as every
  other fit here (and as Spark MLlib's own RF aggregation);
- a forest is one program (``jit__forest``) that grows its trees in steps
  of :func:`tree_group` side by side, never all of them vmapped: one
  tree's deepest level is most of a step's memory.

Trees live in fixed heap-layout arrays (root 0, children 2i+1/2i+2, size
2^(maxDepth+1)−1): ``feature``/``split_bin`` per node, ``is_leaf``, and
``leaf_stats`` (class counts, or [w, wy, wy²] for regression) written for
every materialized node so prediction can stop at any depth. Rows carry
their current heap node; leaf rows go inactive (weight 0 in histograms).

Stats convention: classification S=C per-class weighted counts;
regression S=3 ([w, w·y, w·y²]). Impurities (gini/entropy/variance) are
computed in n-scaled form (n·impurity), where gain·n_total =
imp_n(parent) − imp_n(left) − imp_n(right) — no divisions until the gate.
"""

from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

IMPURITIES = ("gini", "entropy", "variance")
#: ulps of a node's weight under which a gain is rounding (:func:`gain_floor`)
_GAIN_ULPS = 32
#: rows of a tile of a level's histogram (:func:`_level_hist`): the
#: contraction of one piece's one-hot product
_TILE_ROWS = 128
#: rows a block of the subset selection (:func:`_subset_bins`) compares at once
_SELECT_BLOCK_ROWS = 1024
#: pieces whose bins one step of :func:`_piece_bins` takes on the matrix unit
_SELECT_BLOCK_PIECES = 64
#: levels of at most this many nodes select their rows' bins on the matrix
#: unit (:func:`_subset_bins_dense`): its work grows with the nodes
_DENSE_SELECT_NODES = 8
#: and of at most this many nodes·slots: its [rows, nodes·k] result, 8
#: nodes of 55 slots, 0.56 GB at the rf-3000-d13 cell's kept rows
_DENSE_SELECT_COLUMNS = 440
#: the share of the device's memory one block of a level's histogram may
#: hold (:func:`level_budget`)
_BLOCK_SHARE = 6
#: a tree's kept rows are a multiple of this (:func:`row_capacity`), so that
#: a tree's shape changes with its weights only by whole blocks
_CAPACITY_STEP = 1024


class _Pieces(NamedTuple):
    """A level's rows sorted by node and cut in pieces (:func:`_level_pieces`):
    a piece is one node's run of sorted rows inside one tile of
    :data:`_TILE_ROWS`."""

    order: jax.Array | None  # [rows] the rows sorted by node, stably (None: as they are)
    start: jax.Array  # [pieces] each piece's first sorted row
    end: jax.Array  # [pieces] one past its last
    tile: jax.Array  # [pieces] the tile it lies in
    node: jax.Array  # [pieces] its node, ascending


class TreeArrays(NamedTuple):
    """One tree (or a [T, ...] stack) in heap layout."""

    feature: jax.Array  # [nodes] int32, −1 at leaves
    split_bin: jax.Array  # [nodes] int32 — go left when bin ≤ split_bin
    is_leaf: jax.Array  # [nodes] bool
    leaf_stats: jax.Array  # [nodes, S]
    gain: jax.Array  # [nodes] n-scaled impurity decrease at split nodes (0 at leaves) — feeds featureImportances


def _impurity_n(stats: jax.Array, impurity: str) -> jax.Array:
    """n·impurity over the LEADING stats axis; 0 for empty cells. The stats
    lead because, as the minor axis of a TPU array, their few floats would
    each be padded to a 128-lane tile."""
    if impurity == "variance":
        w = stats[0]
        safe = jnp.where(w > 0, w, 1.0)
        v = stats[2] - stats[1] * stats[1] / safe
        return jnp.where(w > 0, jnp.maximum(v, 0.0), 0.0)
    n = jnp.sum(stats, axis=0)
    safe = jnp.where(n > 0, n, 1.0)
    if impurity == "gini":
        return jnp.where(n > 0, n - jnp.sum(stats * stats, axis=0) / safe, 0.0)
    # entropy: Σ c·log(n/c) — 0·log(·) := 0
    ratio = jnp.where(stats > 0, stats / safe[None], 1.0)
    return jnp.where(n > 0, -safe * jnp.sum(ratio * jnp.log(ratio), axis=0), 0.0)


def gain_floor(total: jax.Array, impurity: str) -> jax.Array:
    """The least n-scaled gain that is a gain, for nodes of totals
    ``total`` [S, nodes]: 1e-12, or what the dtype's rounding leaves of a
    zero gain where that is more. A gain is a difference of impurities as
    large as the node's weight (gini, entropy) or its Σw·y² (variance), so
    in float32 a split that changes nothing reads a few ulps of it: a pure
    node's, whose n − Σs²/n is 0 only as far as the division is exact, and
    a TPU's is not (435 to 526 such splits of some 8,400 a fit at the
    rf-3000-d13 cell's size), or a constant label's. The floor is 32 ulps
    of the weight, or of Σw·y², which is in the label's unit squared as the
    variance is: a regression tree does not depend on the label's unit. In
    float64 the floor is 1e-12 up to nodes of 140 weighted rows (of Σw·y²
    140 for variance)."""
    scale = total[2] if impurity == "variance" else jnp.sum(total, axis=0)
    return jnp.maximum(1e-12, _GAIN_ULPS * jnp.finfo(total.dtype).eps * scale)


def _node_count(stats: jax.Array, impurity: str) -> jax.Array:
    """Weighted instance count per cell from the (leading) stats axis."""
    return stats[0] if impurity == "variance" else jnp.sum(stats, axis=0)


def bins_dtype(n_bins: int):
    """The narrowest dtype that holds a bin id: a byte up to 256 bins."""
    return jnp.uint8 if n_bins <= 256 else jnp.int32


def node_subsets(
    key: jax.Array, depth: int, n_feat: int, k_features: int, dtype
) -> jax.Array:
    """[2^depth, k] ascending feature ids that each node of level ``depth``
    may split on: Spark's per-node feature subsampling, k distinct features
    a node by Gumbel top-k (sampling without replacement) from
    ``fold_in(key, depth)``. Every feature, in order, where k covers them."""
    nodes = 2 ** depth
    if k_features >= n_feat:
        return jnp.broadcast_to(
            jnp.arange(n_feat, dtype=jnp.int32), (nodes, n_feat)
        )
    g = jax.random.gumbel(jax.random.fold_in(key, depth), (nodes, n_feat), dtype)
    return jnp.sort(lax.top_k(g, k_features)[1].astype(jnp.int32), axis=1)


def _subset_bins(binned: jax.Array, sub_rows: jax.Array) -> jax.Array:
    """[rows, k] int32 ``binned[r, sub_rows[r, j]]``: each row's bins on its
    node's subset, as a sum of compares over the row's features, a block of
    rows at a time (sliced where the rows lie: a reshape would copy them).
    On a TPU this runs on the vector unit five times faster than the gather
    it stands for (79 ms against 404 at 524,288 rows of 3,000 bytes and 55
    slots: PERF.md section 6); the selection of bins over 256, which one
    bfloat16 pass does not hold (:func:`_piece_bins` takes those under)."""
    rows, n_feat = binned.shape
    k = sub_rows.shape[1]
    block = math.gcd(rows, _SELECT_BLOCK_ROWS)
    features = jnp.arange(n_feat, dtype=jnp.int32)

    def select(i, out):
        b = lax.dynamic_slice_in_dim(binned, i * block, block)
        sub = lax.dynamic_slice_in_dim(sub_rows, i * block, block)
        hit = features[None, None, :] == sub[:, :, None]
        got = jnp.sum(
            jnp.where(hit, b[:, None, :].astype(jnp.int32), 0), axis=-1,
            dtype=jnp.int32,
        )
        return lax.dynamic_update_slice_in_dim(out, got, i * block, 0)

    return lax.fori_loop(
        0, rows // block, select, jnp.zeros((rows, k), jnp.int32)
    )


def _subset_bins_dense(
    binned: jax.Array, subset: jax.Array, local: jax.Array
) -> jax.Array:
    """:func:`_subset_bins` for a level of few nodes: the rows times every
    node's one-hot selection [F, nodes·k] on the matrix unit, then each row
    keeps its own node's k. Bins under 256 and a one-hot are exact at one
    bfloat16 pass, and each sum has one term that is not zero."""
    nodes, k = subset.shape
    n_feat = binned.shape[1]
    pick = jnp.arange(n_feat, dtype=jnp.int32)[:, None] == subset.reshape(1, -1)
    every = jnp.dot(
        binned.astype(jnp.float32), pick.astype(jnp.float32),
        precision=lax.Precision.DEFAULT,
    ).reshape(-1, nodes, k)
    mine = local[:, None, None] == jnp.arange(nodes, dtype=local.dtype)[None, :, None]
    return jnp.sum(jnp.where(mine, every, 0.0), axis=1).astype(jnp.int32)


def _selects_by_pieces(nodes: int, n_feat: int, k: int, n_bins: int) -> bool:
    """Whether a level of ``nodes`` nodes selects its rows' ``k`` bins of
    ``n_feat`` by :func:`_piece_bins`: bins under 256, which one bfloat16
    pass holds exactly, a subset to select, and more nodes than the dense
    product (:func:`_subset_bins_dense`) is quicker for, or a result
    [rows, nodes·k] wider than it may build."""
    return k < n_feat and n_bins <= 256 and (
        nodes > _DENSE_SELECT_NODES or nodes * k > _DENSE_SELECT_COLUMNS
    )


def piece_select_levels(n_feat: int, k_features: int, n_bins: int, max_depth: int) -> int:
    """The split levels of a tree whose selection takes :func:`_piece_bins`."""
    k = min(k_features, n_feat)
    return sum(_selects_by_pieces(2 ** d, n_feat, k, n_bins) for d in range(max_depth))


def _tiles(rows: int) -> tuple[int, int]:
    """(padded rows, tiles): ``rows`` cut in tiles of :data:`_TILE_ROWS`."""
    padded = -(-rows // _TILE_ROWS) * _TILE_ROWS
    return padded, padded // _TILE_ROWS


class LevelPlan(NamedTuple):
    """How a split level walks its histogram: ``blocks`` blocks of
    ``slots`` subset slots (the last block's start is pulled back to end at
    the last slot)."""

    slots: int
    blocks: int


def _slot_bytes(rows: int, nodes: int, n_stats: int, n_bins: int) -> int:
    """Bytes one subset slot of a level's histogram holds at once: its
    pieces' sums, the stats' three bfloat16 parts side by side and their
    total (:func:`_onehot_sums`), and the nodes' histogram with its cumsum,
    right half and gains."""
    pieces = _tiles(rows)[1] + nodes
    return 4 * n_bins * n_stats * 4 * (pieces + nodes)


def level_plan(
    rows: int, k: int, n_bins: int, n_stats: int, nodes: int,
    block_bytes: int | None,
) -> LevelPlan:
    """The blocks of subset slots a level of ``nodes`` nodes over ``rows``
    rows walks its ``k`` slots in: as few as keep a block within
    ``block_bytes`` (:func:`level_budget`), as even as they come. One block
    where ``block_bytes`` is None."""
    if block_bytes is None:
        return LevelPlan(k, 1)
    most = max(1, block_bytes // _slot_bytes(rows, nodes, n_stats, n_bins))
    blocks = -(-k // most)
    return LevelPlan(-(-k // blocks), blocks)


def level_blocks(
    rows: int, n_feat: int, k_features: int, n_bins: int, n_stats: int,
    max_depth: int, block_bytes: int | None,
) -> int:
    """The blocks all of a tree's split levels take (:func:`level_plan`)."""
    k = min(k_features, n_feat)
    return sum(
        level_plan(rows, k, n_bins, n_stats, 2 ** d, block_bytes).blocks
        for d in range(max_depth)
    )


def level_budget(device=None) -> int | None:
    """Bytes one block of a level's histogram may hold on ``device``: a
    :data:`_BLOCK_SHARE`-th of its memory, which keeps the rf-3000-d13
    cell's 55 slots one block at every level. None (every level one block)
    where the device reports no limit (the CPU)."""
    device = device if device is not None else jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    return int(limit) // _BLOCK_SHARE if limit else None


def _level_pieces(local: jax.Array, nodes: int) -> _Pieces:
    """A level's rows sorted by node and cut in tiles of :data:`_TILE_ROWS`;
    the node boundaries cut the tiles again into at most tiles + nodes
    pieces, each a run of one node's rows inside one tile. Computed once a
    level: the selection (:func:`_piece_bins`) and the histogram
    (:func:`_level_hist`) both read it."""
    rows = local.shape[0]
    R = _TILE_ROWS
    padded, tiles = _tiles(rows)
    # one node's rows are in order already, and are not moved; the sort
    # carries the nodes with it, where a gather of them takes 2 ms on a v5e
    order, sorted_local = None, local
    if nodes > 1:
        sorted_local, order = lax.sort_key_val(
            local, jnp.arange(rows, dtype=jnp.int32), is_stable=True
        )
    node_of = jnp.pad(sorted_local, (0, padded - rows), constant_values=nodes - 1)
    node_starts = jnp.searchsorted(
        node_of, jnp.arange(nodes, dtype=node_of.dtype), side="left"
    )
    start = jnp.sort(
        jnp.concatenate([jnp.arange(tiles, dtype=node_starts.dtype) * R, node_starts])
    )
    end = jnp.concatenate([start[1:], jnp.full((1,), padded, start.dtype)])
    tile = jnp.minimum(start // R, tiles - 1)
    node = node_of[jnp.minimum(start, padded - 1)]
    return _Pieces(order, start, end, tile, node)


def _sorted_tiles(x: jax.Array, pieces: _Pieces) -> jax.Array:
    """[tiles, R, ...] the rows of ``x`` in the level's sorted order, padded
    with zeros to whole tiles."""
    padded, tiles = _tiles(x.shape[0])
    pad = ((0, padded - x.shape[0]),) + ((0, 0),) * (x.ndim - 1)
    rows = x if pieces.order is None else x[pieces.order]
    return jnp.pad(rows, pad).reshape(tiles, _TILE_ROWS, *x.shape[1:])


def _pieces_of(row_bins: jax.Array, pieces: _Pieces) -> jax.Array:
    """[pieces, R, k] per-row bins laid out as :func:`_level_hist` reads
    them: each piece's tile of the sorted rows."""
    return _sorted_tiles(row_bins, pieces)[pieces.tile]


def _byte_words(binned: jax.Array) -> jax.Array:
    """[rows, W] uint32: a row's bins under 256 four to a word, W a whole
    number of 128 lanes: byte q of word c holds feature q·W + c (zeros past
    the last feature), so the words are four slices of the row side by side
    and unpack into the row as it was. A TPU moves a row of words as whole
    lanes where it unpacks a row of bytes: 13 against 22 ms for 317,440
    rows of 3,000 on a v5e (PERF.md section 6), and a level moves its rows
    once."""
    rows, n_feat = binned.shape
    width = -(-n_feat // (4 * 128)) * 128
    words = jnp.zeros((rows, width), jnp.uint32)
    for q in range(-(-n_feat // width)):
        part = binned[:, q * width:(q + 1) * width].astype(jnp.uint32)
        part = jnp.pad(part, ((0, 0), (0, width - part.shape[1])))
        words = words | (part << (8 * q))
    return words


def _piece_bins(
    words: jax.Array, subset: jax.Array, pieces: _Pieces, dtype=jnp.int32
) -> jax.Array:
    """[pieces, R, k] ``dtype``: each piece's tile of sorted rows on its node's
    subset, ``binned[r, subset[node, j]]`` for bins under 256, from the
    rows' words (:func:`_byte_words` of ``binned``). One product a piece
    on the matrix unit, the tile's bins [R, 4W] against the node's [k, 4W]
    one-hot, :data:`_SELECT_BLOCK_PIECES` pieces a step: bins under 256 and
    a one-hot are exact at one bfloat16 pass, and each sum has one term that
    is not zero. Its work grows with the pieces, tiles + nodes, where a
    row's compares over all its features (:func:`_subset_bins`) grow with
    the rows times the slots. A piece's rows of other nodes hold bins on its
    node's subset too; the histogram's mask drops them. The rows move once,
    into the level's order."""
    k = subset.shape[1]
    width = words.shape[1]
    tiles_ = _sorted_tiles(words, pieces)
    count = pieces.tile.shape[0]
    block = min(_SELECT_BLOCK_PIECES, count)
    features = jnp.arange(4 * width, dtype=subset.dtype)

    def select(i, out):
        # the last step takes the last block, some of its pieces again
        first = jnp.minimum(i * block, count - block)
        tile = lax.dynamic_slice_in_dim(pieces.tile, first, block)
        node = lax.dynamic_slice_in_dim(pieces.node, first, block)
        x = tiles_[tile]  # [block, R, W]
        x = jnp.concatenate([(x >> (8 * q)) & 255 for q in range(4)], axis=-1)
        hot = features[None, None, :] == subset[node][:, :, None]  # [block, k, 4W]
        got = jnp.einsum(
            "prf,pkf->prk", x.astype(jnp.float32), hot.astype(jnp.float32),
            precision=lax.Precision.DEFAULT,
        )
        return lax.dynamic_update_slice_in_dim(out, got.astype(dtype), first, 0)

    return lax.fori_loop(
        0, -(-count // block), select,
        jnp.zeros((count, _TILE_ROWS, k), dtype),
    )


def _onehot_sums(tile_bins: jax.Array, tile_stats: jax.Array, n_bins: int) -> jax.Array:
    """[..., S, k, B] Σ over a tile's rows r of onehot(tile_bins[..., r, j])
    ⊗ tile_stats[..., r, :]: a batch of products on the matrix unit whose
    one-hot operand is the compare itself. A one-hot is exact in bfloat16,
    so float32 stats go in as their three exact bfloat16 parts side by side
    (``ops.kmeans.split_bf16``): one pass, and the sums are float32's. Other
    dtypes take the product as it is written."""
    from spark_rapids_ml_tpu.ops import kmeans as KM

    S = tile_stats.shape[-1]
    hot = tile_bins[..., None] == jnp.arange(n_bins, dtype=tile_bins.dtype)
    if KM.exact_bf16_parts(tile_stats.dtype) is None:
        return jnp.einsum(
            "...rkb,...rs->...skb", hot.astype(tile_stats.dtype), tile_stats,
            precision=lax.Precision.HIGHEST,
        )
    # the parts are held in float32 and multiplied at one bfloat16 pass,
    # which takes them exactly (the CPU's batched products have no
    # bfloat16 x bfloat16 = float32)
    parts = KM.split_bf16(tile_stats)
    prod = jnp.einsum(
        "...rkb,...rq->...qkb", hot.astype(tile_stats.dtype),
        jnp.concatenate(parts, axis=-1).astype(tile_stats.dtype),
        precision=lax.Precision.DEFAULT,
    )
    # smallest first, as a float32 sum is best taken
    *heads, total = jnp.split(prod, len(parts), axis=-3)
    for head in reversed(heads):
        total = total + head
    return total


def _level_hist(
    piece_bins: jax.Array,  # [pieces, R, k] each piece's rows' bins on its node's subset
    pieces: _Pieces,  # the level's layout (:func:`_level_pieces`)
    contrib: jax.Array,  # [rows, S] weighted stats (0 for inactive rows)
    nodes: int,
    n_bins: int,
) -> jax.Array:
    """[S, nodes, k, B] histograms of one level, with no scatter of rows.

    A piece's histogram is a masked one-hot product over its tile
    (:func:`_onehot_sums`), and a node's is the sum of its pieces (a segment
    sum over sorted pieces). A scatter-add of the rows·k (row, slot) pairs is
    what this replaces: on a TPU it sorts them first, 320 ms a level at
    524,288 rows and 55 slots."""
    k = piece_bins.shape[2]
    S = contrib.shape[1]
    R = _TILE_ROWS
    where = pieces.tile[:, None] * R + jnp.arange(R, dtype=pieces.start.dtype)[None, :]
    inside = (where >= pieces.start[:, None]) & (where < pieces.end[:, None])
    tile_stats = _sorted_tiles(contrib, pieces)
    sums = _onehot_sums(
        piece_bins, tile_stats[pieces.tile] * inside[..., None], n_bins
    )  # [pieces, S, k, B]
    hist = jax.ops.segment_sum(
        sums.reshape(sums.shape[0], -1), pieces.node, num_segments=nodes,
        indices_are_sorted=True,
    )
    return hist.reshape(nodes, S, k, n_bins).transpose(1, 0, 2, 3)


def _hist_best(hist, totals, fresh, n_bins, impurity, min_instances, min_info_gain):
    """A histogram [S, nodes, slots, B] reduced to each node's first best
    valid (gain, slot, bin), [nodes] each, gain −inf where it has none,
    beside the node totals [S, nodes] ``totals`` makes of its own (its slot
    0's). ``fresh`` [slots] marks the slots to take (None: all)."""
    nodes = hist.shape[1]
    total = totals(jnp.sum(hist[:, :, 0], axis=2))  # [S, nodes]
    left = jnp.cumsum(hist, axis=3)  # [S, nodes, slots, B]
    right = total[:, :, None, None] - left
    gain_n = (
        _impurity_n(total, impurity)[:, None, None]
        - _impurity_n(left, impurity)
        - _impurity_n(right, impurity)
    )
    n_tot = _node_count(total, impurity)  # [nodes]
    n_l = _node_count(left, impurity)
    n_r = _node_count(right, impurity)
    safe_tot = jnp.where(n_tot > 0, n_tot, 1.0)
    ok = (
        (n_l >= min_instances)
        & (n_r >= min_instances)
        & (gain_n / safe_tot[:, None, None] >= min_info_gain)
        & (gain_n > gain_floor(total, impurity)[:, None, None])
    )
    # the last bin's "split" puts everything left — structurally invalid
    ok = ok & (jnp.arange(n_bins)[None, None, :] < n_bins - 1)
    if fresh is not None:
        ok = ok & fresh[None, :, None]
    # subset slots ascend in feature id, so the first best (j, b) is the
    # first best (feature, bin)
    flat = jnp.where(ok, gain_n, -jnp.inf).reshape(nodes, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    return (
        total, best_gain, (best // n_bins).astype(jnp.int32),
        (best % n_bins).astype(jnp.int32),
    )


def _best_splits(
    piece_bins, pieces, contrib, nodes, n_bins, plan, impurity,
    min_instances, min_info_gain, reduce,
):
    """A split level's node totals [S, nodes] and each node's first best
    valid split over its subset: (gain, slot, bin), [nodes] each, gain −inf
    where it has none. The level's histogram (:func:`_level_hist`) is walked
    in ``plan``'s blocks of slots, each reduced to its nodes' best
    (:func:`_hist_best`) before the next; one block is the whole subset at
    once. ``reduce`` sums a histogram over the row shards."""
    k = piece_bins.shape[2]
    S = contrib.shape[1]

    def block_best(piece_bins, totals, fresh):
        hist = reduce(_level_hist(piece_bins, pieces, contrib, nodes, n_bins))
        return _hist_best(
            hist, totals, fresh, n_bins, impurity, min_instances, min_info_gain
        )

    if plan.blocks == 1:
        return block_best(piece_bins, lambda own: own, None)

    def walk(i, carry):
        # the last block ends at the last slot; what it takes again is not
        # fresh. Every block's gains take the first block's totals
        first = jnp.minimum(i * plan.slots, k - plan.slots)
        got = block_best(
            lax.dynamic_slice_in_dim(piece_bins, first, plan.slots, axis=2),
            lambda own: jnp.where(i == 0, own, carry[0]),
            first + jnp.arange(plan.slots) >= i * plan.slots,
        )
        # a later block takes a node only with a greater gain: the first
        # best in ascending slot order, as one block's argmax
        better = got[1] > carry[1]
        return (
            got[0], jnp.where(better, got[1], carry[1]),
            jnp.where(better, got[2] + first, carry[2]),
            jnp.where(better, got[3], carry[3]),
        )

    fdt = contrib.dtype
    return lax.fori_loop(0, plan.blocks, walk, (
        jnp.zeros((S, nodes), fdt), jnp.full((nodes,), -jnp.inf, fdt),
        jnp.zeros((nodes,), jnp.int32), jnp.zeros((nodes,), jnp.int32),
    ))


def _grow(
    key, binned, row_stats, w, min_instances, min_info_gain, *,
    max_depth, n_bins, k_features, impurity, axis_name, block_bytes=None,
) -> TreeArrays:
    """One tree, level by level (the body of :func:`build_tree`); each
    split level walks its histogram in the blocks :func:`level_plan` gives
    for ``block_bytes``."""
    if impurity not in IMPURITIES:
        raise ValueError(f"impurity must be one of {IMPURITIES}")
    rows, n_feat = binned.shape
    S = row_stats.shape[1]
    k = min(k_features, n_feat)
    max_nodes = 2 ** (max_depth + 1) - 1
    fdt = row_stats.dtype

    feature = jnp.full((max_nodes,), -1, jnp.int32)
    split_bin = jnp.zeros((max_nodes,), jnp.int32)
    is_leaf = jnp.ones((max_nodes,), bool)
    leaf_stats = jnp.zeros((max_nodes, S), fdt)
    gain = jnp.zeros((max_nodes,), fdt)

    node = jnp.zeros((rows,), jnp.int32)  # current heap node per row
    active = jnp.ones((rows,), bool)

    def reduce(x):
        return x if axis_name is None else lax.psum(x, axis_name)

    # a tree's rows as words once, for the levels that select by pieces
    words = (
        _byte_words(binned) if piece_select_levels(n_feat, k, n_bins, max_depth)
        else None
    )

    for d in range(max_depth + 1):
        nodes_d = 2 ** d
        offset = nodes_d - 1
        # inactive rows keep the stale heap id of the level they went leaf
        # at, so their local id is clipped into range — they contribute 0
        # to histograms (weight 0) and never route (active gates row_split)
        local = jnp.clip(node - offset, 0, nodes_d - 1)
        contrib = row_stats * jnp.where(active, w, 0.0)[:, None]

        if d == max_depth:
            # depth-capped: this level is all leaves, and only its totals
            # are wanted
            total = reduce(
                jax.ops.segment_sum(contrib, local, num_segments=nodes_d)
            )
            leaf_stats = lax.dynamic_update_slice(leaf_stats, total, (offset, 0))
            break

        subset = node_subsets(key, d, n_feat, k, fdt)  # [nodes_d, k]
        pieces = _level_pieces(local, nodes_d)
        plan = level_plan(rows, k, n_bins, S, nodes_d, block_bytes)
        # a level walked in blocks holds its rows' bins on every slot at once:
        # as bytes where they are
        narrow = bins_dtype(n_bins) if plan.blocks > 1 else jnp.int32
        if k == n_feat:
            piece_bins = _pieces_of(binned, pieces)
        elif _selects_by_pieces(nodes_d, n_feat, k, n_bins):
            piece_bins = _piece_bins(words, subset, pieces, narrow)
        elif n_bins <= 256:
            piece_bins = _pieces_of(
                _subset_bins_dense(binned, subset, local).astype(narrow), pieces
            )
        else:
            piece_bins = _pieces_of(_subset_bins(binned, subset[local]), pieces)

        total, best_gain, best_j, best_b = _best_splits(
            piece_bins, pieces, contrib, nodes_d, n_bins, plan, impurity,
            min_instances, min_info_gain, reduce,
        )
        leaf_stats = lax.dynamic_update_slice(leaf_stats, total.T, (offset, 0))
        best_f = jnp.take_along_axis(subset, best_j[:, None], axis=1)[:, 0]
        do_split = best_gain > -jnp.inf  # [nodes_d]

        feature = lax.dynamic_update_slice(
            feature, jnp.where(do_split, best_f, -1), (offset,)
        )
        split_bin = lax.dynamic_update_slice(
            split_bin, jnp.where(do_split, best_b, 0), (offset,)
        )
        is_leaf = lax.dynamic_update_slice(is_leaf, ~do_split, (offset,))
        gain = lax.dynamic_update_slice(
            gain, jnp.where(do_split, best_gain, 0.0), (offset,)
        )

        # route rows: split nodes send rows to 2·node+1 (+1 if bin > b); a
        # row's bin at its node's chosen feature is a compare over its bins
        decision = jnp.take(
            jnp.stack([do_split.astype(jnp.int32), best_f, best_b], axis=1),
            local, axis=0,
        )
        row_split = active & (decision[:, 0] > 0)
        chosen = jnp.arange(n_feat, dtype=jnp.int32)[None, :] == decision[:, 1:2]
        row_bin = jnp.sum(jnp.where(chosen, binned, 0), axis=1, dtype=jnp.int32)
        goes_right = (row_bin > decision[:, 2]).astype(jnp.int32)
        node = jnp.where(row_split, 2 * node + 1 + goes_right, node)
        active = active & row_split

    return TreeArrays(feature, split_bin, is_leaf, leaf_stats, gain)


@partial(
    jax.jit,
    static_argnames=(
        "max_depth", "n_bins", "k_features", "impurity", "axis_name",
        "block_bytes",
    ),
)
def build_tree(
    key: jax.Array,
    binned: jax.Array,  # [rows, F] bin ids in [0, n_bins): uint8 or int32
    row_stats: jax.Array,  # [rows, S] per-row stats (UNweighted)
    w: jax.Array,  # [rows] bootstrap × instance weights (0 = excluded)
    min_instances: jax.Array,  # weighted count floor per child
    min_info_gain: jax.Array,
    *,
    max_depth: int,
    n_bins: int,
    k_features: int,
    impurity: str,
    axis_name: str | None = None,
    block_bytes: int | None = None,
) -> TreeArrays:
    """Grow one histogram tree level-order; fully jittable, fixed shapes.

    A level selects each active row's bins on its node's k-feature subset
    (:func:`node_subsets`, :func:`_piece_bins`), accumulates them into ``[S, 2^d, k, B]``
    (:func:`_level_hist`), and
    takes the cumsum, the gains and the argmax over (subset slot, bin), in
    blocks of slots of at most ``block_bytes`` each (one block where it is
    None: :func:`level_plan`); the depth-capped level computes node totals
    only. With ``axis_name`` set (mesh build), each level's histogram and
    the leaf totals are psum'd over that axis — rows are sharded, decisions
    replicated.
    """
    return _grow(
        key, binned, row_stats, w, min_instances, min_info_gain,
        max_depth=max_depth, n_bins=n_bins, k_features=k_features,
        impurity=impurity, axis_name=axis_name, block_bytes=block_bytes,
    )


def tree_group(
    rows: int, n_feat: int, k_features: int, n_bins: int, n_stats: int,
    max_depth: int, n_trees: int, device=None,
) -> int:
    """How many trees the forest program grows side by side (vmapped) in
    each step of its loop over trees: as many as keep a group's working set
    within a sixteenth of the device's memory. The working set of a tree is
    its deepest split level's block of slots (:func:`level_plan` for
    :func:`level_budget`: the pieces' sums, the histogram with its cumsum,
    right half and gains), its pieces' bins on every slot and their stats
    (:func:`_level_hist`), its rows as words and their copy in the level's
    order (:func:`_byte_words`) with one step of the selection's tiles and
    one-hots (:func:`_piece_bins`), and its rows' nodes and weights. Every
    tree at once where the device reports no memory limit (the CPU)."""
    device = device if device is not None else jax.devices()[0]
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        return n_trees
    k = min(k_features, n_feat)
    deepest = 2 ** max(max_depth - 1, 0)
    pieces = _tiles(rows)[1] + deepest
    plan = level_plan(rows, k, n_bins, n_stats, deepest, level_budget(device))
    bin_bytes = 1 if plan.blocks > 1 and n_bins <= 256 else 4
    per_tree = (
        plan.slots * _slot_bytes(rows, deepest, n_stats, n_bins)
        + pieces * _TILE_ROWS * (bin_bytes * k + 4 * n_stats)
        + 2 * rows * -(-n_feat // 512) * 512
        + 4 * _SELECT_BLOCK_PIECES * n_feat * (_TILE_ROWS + k)
        + 4 * 4 * rows
    )
    return int(max(1, min(n_trees, limit // 16 // per_tree)))


def row_capacity(weights: jax.Array, shards: int = 1) -> int:
    """The rows of a shard that a tree's build keeps: the most rows of
    positive weight that any tree has in any of the ``shards`` equal row
    shards of ``weights`` ([T, rows]), rounded up to :data:`_CAPACITY_STEP`
    and at most the shard. A Poisson(1) bootstrap leaves 37% of the rows out
    of each tree, and every level of a tree walks what it keeps."""
    trees, rows = weights.shape
    local = rows // shards
    kept = jnp.sum((weights > 0).reshape(trees, shards, local), axis=2)
    most = int(jax.device_get(jnp.max(kept)))
    return min(local, -(-most // _CAPACITY_STEP) * _CAPACITY_STEP)


def grow_forest(
    keys, binned, row_stats, weights, min_instances, min_info_gain, *,
    max_depth, n_bins, k_features, impurity, group, capacity=None,
    axis_name=None, block_bytes=None,
) -> TreeArrays:
    """[T, ...] TreeArrays: the trees in steps of ``group`` side by side
    (``lax.map``), never all of them vmapped at once unless they fit. With
    ``capacity`` under the rows, each tree first keeps its rows of positive
    weight, in order, in ``capacity`` rows (:func:`row_capacity`), so its
    levels walk no row it leaves out; the trees are the same. Each level
    walks its histogram in blocks of at most ``block_bytes``
    (:func:`level_plan`): the trees are the same."""

    def one(tree):
        key, w = tree
        b, stats = binned, row_stats
        if capacity is not None and capacity < binned.shape[0]:
            keep = jnp.argsort(w <= 0, stable=True)[:capacity]
            b, stats, w = binned[keep], row_stats[keep], w[keep]
        return _grow(
            key, b, stats, w, min_instances, min_info_gain,
            max_depth=max_depth, n_bins=n_bins, k_features=k_features,
            impurity=impurity, axis_name=axis_name, block_bytes=block_bytes,
        )

    return lax.map(one, (keys, weights), batch_size=group)


@lru_cache(maxsize=32)
def forest_program(
    *, max_depth: int, n_bins: int, k_features: int, impurity: str,
    group: int, capacity: int | None = None, block_bytes: int | None = None,
):
    """The single-device forest program; a private function's name is the
    program's in a device trace (``jit__forest``)."""

    def _forest(keys, binned, row_stats, weights, min_instances, min_info_gain):
        return grow_forest(
            keys, binned, row_stats, weights, min_instances, min_info_gain,
            max_depth=max_depth, n_bins=n_bins, k_features=k_features,
            impurity=impurity, group=group, capacity=capacity,
            block_bytes=block_bytes,
        )

    return jax.jit(_forest)


def build_forest(
    keys: jax.Array,  # [T] PRNG keys (feature subsets)
    binned: jax.Array,
    row_stats: jax.Array,
    weights: jax.Array,  # [T, rows] per-tree bootstrap × instance weights
    min_instances,
    min_info_gain,
    **static,
) -> TreeArrays:
    """[T, ...] TreeArrays on the default device (program ``jit__forest``),
    :func:`tree_group` trees at a time over :func:`row_capacity` rows, each
    level in blocks of :func:`level_budget`."""
    weights = jnp.asarray(weights)
    capacity = row_capacity(weights)
    group = tree_group(
        capacity, binned.shape[1], static["k_features"], static["n_bins"],
        row_stats.shape[1], static["max_depth"], keys.shape[0],
    )
    return forest_program(
        group=group, capacity=capacity, block_bytes=level_budget(), **static
    )(keys, binned, row_stats, weights, min_instances, min_info_gain)


#: the bootstrap's stream of a fit's seed, apart from the subsets' keys
#: (``jax.random.split(PRNGKey(seed), T)``)
_BOOTSTRAP_STREAM = 0x626F6F74


@lru_cache(maxsize=32)
def _sample_counts(n_trees: int, rows: int, bootstrap: bool, rate: float):
    def one(key, t):
        k = jax.random.fold_in(key, t)
        if bootstrap:
            return jax.random.poisson(
                k, jnp.float32(rate), (rows,), dtype=jnp.int32
            ).astype(jnp.float32)
        if rate < 1.0:
            return (jax.random.uniform(k, (rows,), jnp.float32) < rate).astype(
                jnp.float32
            )
        return jnp.ones((rows,), jnp.float32)

    return jax.jit(
        lambda key: jax.vmap(lambda t: one(key, t))(jnp.arange(n_trees))
    )


def bootstrap_weights(
    seed: int, n_trees: int, rows: int, *, bootstrap: bool, rate: float
) -> jax.Array:
    """[T, rows] float32 per-tree sample counts of a fit's rows, drawn on
    the device: Poisson(rate) with ``bootstrap`` (Spark's BaggedPoint),
    Bernoulli(rate) without it (sampling without replacement), ones at rate
    1. Tree t draws from ``fold_in(key, t)``, so its counts do not depend on
    how many trees are grown, nor on how the rows are sharded."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), _BOOTSTRAP_STREAM)
    return _sample_counts(n_trees, rows, bool(bootstrap), float(rate))(key)


def bin_rows(x: jax.Array, edges: jax.Array, n_bins: int) -> jax.Array:
    """[rows, F] bin ids in :func:`bins_dtype`: how many of a feature's
    edges its value exceeds, which is ``np.searchsorted(edges[f], x[:, f],
    side='left')`` (bin b ⇔ edges[b−1] < x ≤ edges[b]). The count runs over
    the edges as the major axis, so it is a sum of compares a row tile at a
    time and not a gather."""
    above = x[None, :, :] > edges.T[:, None, :]
    return jnp.sum(above, axis=0, dtype=jnp.int32).astype(bins_dtype(n_bins))


@partial(jax.jit, static_argnames=("max_depth",))
def tree_apply_binned(
    tree: TreeArrays,  # ONE tree (unstacked)
    binned: jax.Array,  # [rows, F] int32 bin ids
    *,
    max_depth: int,
) -> jax.Array:
    """[rows, S] leaf stats by descending on BIN ids (go left when
    bin ≤ split_bin) — the training-time router gradient boosting uses to
    update its running prediction without converting back to raw
    thresholds."""
    node = jnp.zeros((binned.shape[0],), jnp.int32)
    for _ in range(max_depth):
        leaf = tree.is_leaf[node]
        f = jnp.maximum(tree.feature[node], 0)
        b = jnp.take_along_axis(binned, f[:, None], axis=1)[:, 0]
        goes_right = (b > tree.split_bin[node]).astype(jnp.int32)
        node = jnp.where(leaf, node, 2 * node + 1 + goes_right)
    return tree.leaf_stats[node]


@partial(jax.jit, static_argnames=("max_depth",))
def forest_apply(
    trees: TreeArrays,  # [T, ...] stack
    x: jax.Array,  # [rows, F] RAW feature values
    thresholds: jax.Array,  # [T, nodes] split values (edges[f, b])
    *,
    max_depth: int,
) -> jax.Array:
    """[T, rows, S] leaf stats: descend every tree with gathers —
    ``max_depth`` dependent steps, each one vectorized gather+compare."""

    def one_tree(tree, thr):
        node = jnp.zeros((x.shape[0],), jnp.int32)
        for _ in range(max_depth):
            leaf = tree.is_leaf[node]
            f = jnp.maximum(tree.feature[node], 0)
            xv = jnp.take_along_axis(x, f[:, None], axis=1)[:, 0]
            goes_right = (xv > thr[node]).astype(jnp.int32)
            node = jnp.where(leaf, node, 2 * node + 1 + goes_right)
        return tree.leaf_stats[node]

    return jax.vmap(one_tree)(trees, thresholds)
