#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from for a random
forest regressor cell, at the cell's own size on the chip, many seeds to a
process:

    python3 benchmarks/readings_forest_reg.py --workload <name> --seeds 1 2 3 \
        [--control N] [--trees N] [--levels]

For each seed it makes the cell's rows and labels, fits them once as the
configuration states (the lower readings) and judges every tree against the
plain reference exactly as a run's last fit is judged; a seed's judgement
runs on the host while the next seed fits. ``--control N`` then fits the
first N seeds once more with the statistics at one bfloat16 part (the
weighted stats rounded to bfloat16 before the level's one-hot products,
``ops.forest._onehot_sums``, for this process alone: the estimator has no
precision param), and reads the reference's own control
(``reference_forest_reg.control_sum_gap``) beside it. ``--trees`` fits
another number of trees than the configuration states, to time the fit the
configuration's cut of the trees rests on. ``--levels`` first times one
split level alone at each depth, at the cell's kept rows and subset, walked
in blocks of slots (the program's) and in blocks of nodes (Spark's
``maxMemoryInMB`` grouping, written here for the comparison). One JSON line
a reading; nothing here is a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as M  # noqa: E402
from benchmarks import reference_forest_reg  # noqa: E402

#: pieces a step of the node-blocked level's histogram takes
NODE_STEP_PIECES = 64


def node_blocked_best(piece_bins, pieces, contrib, nodes, n_bins, node_block, impurity):
    """A split level's best splits with its histogram walked in blocks of
    ``node_block`` nodes: each block's pieces (a run of the sorted pieces)
    summed :data:`NODE_STEP_PIECES` at a time into its [S, node_block, k, B]
    histogram, reduced to its nodes' best before the next block. The same
    gains and ties as ``ops.forest._best_splits``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from spark_rapids_ml_tpu.ops import forest as FO

    count, R, k = piece_bins.shape
    S = contrib.shape[1]
    C = min(NODE_STEP_PIECES, count)
    tile_stats = FO._sorted_tiles(contrib, pieces)
    rows_at = pieces.tile[:, None] * R + jnp.arange(R, dtype=pieces.start.dtype)[None, :]
    inside = (rows_at >= pieces.start[:, None]) & (rows_at < pieces.end[:, None])

    def block(b, carry):
        n0 = b * node_block
        p0 = jnp.searchsorted(pieces.node, n0, side="left")
        p1 = jnp.searchsorted(pieces.node, n0 + node_block, side="left")

        def step(state):
            at, hist = state
            first = jnp.minimum(at, count - C)
            idx = first + jnp.arange(C)
            keep = (idx >= at) & (idx < p1)
            stats = tile_stats[pieces.tile[idx]] * (inside[idx] & keep[:, None])[..., None]
            sums = FO._onehot_sums(lax.dynamic_slice_in_dim(piece_bins, first, C), stats, n_bins)
            seg = jnp.clip(pieces.node[idx] - n0, 0, node_block - 1)
            return at + C, hist + jax.ops.segment_sum(
                sums.reshape(C, -1), seg, num_segments=node_block)

        _, hist = lax.while_loop(
            lambda s: s[0] < p1, step,
            (p0, jnp.zeros((node_block, S * k * n_bins), contrib.dtype)))
        hist = hist.reshape(node_block, S, k, n_bins).transpose(1, 0, 2, 3)
        got = FO._hist_best(hist, lambda own: own, None, n_bins, impurity, 1.0, 0.0)
        return tuple(
            lax.dynamic_update_slice_in_dim(c, v, n0, axis=c.ndim - 1)
            for c, v in zip(carry, got))

    fdt = contrib.dtype
    return lax.fori_loop(0, nodes // node_block, block, (
        jnp.zeros((S, nodes), fdt), jnp.full((nodes,), -jnp.inf, fdt),
        jnp.zeros((nodes,), jnp.int32), jnp.zeros((nodes,), jnp.int32)))


def time_levels(config: dict, depths, budget: int) -> None:
    """One JSON line a depth: the seconds of one split level alone, its rows
    spread over its nodes at random, by slots (the program's plan) and by
    nodes, at the cell's kept rows (``ops.forest.row_capacity`` of its
    bootstrap), subset and bins."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_ml_tpu.models import forest as MF
    from spark_rapids_ml_tpu.ops import forest as FO

    params = config["params"]
    n, n_bins = int(config["n_features"]), int(params["maxBins"])
    k = MF.subset_size(params["featureSubsetStrategy"], n, classification=False)
    weights = FO.bootstrap_weights(int(params["seed"]), int(params["numTrees"]),
                                   int(config["rows"]), bootstrap=True, rate=1.0)
    rows = FO.row_capacity(weights)
    rng = np.random.default_rng(0)
    binned = jnp.asarray(rng.integers(0, n_bins, (rows, n), dtype=np.uint8))
    words = jax.block_until_ready(jax.jit(FO._byte_words)(binned))
    y = rng.normal(size=rows).astype(np.float32) * 1800
    contrib = jnp.asarray(np.stack([np.ones_like(y), y, y * y], 1)
                          * rng.poisson(1.0, rows)[:, None].astype(np.float32))

    def clock(fn, *args):
        jax.block_until_ready(fn(*args))  # compiles
        t0 = time.perf_counter()
        for _ in range(3):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 3

    for d in depths:
        nodes = 2 ** d
        local = jnp.asarray(rng.integers(0, nodes, rows).astype(np.int32))
        subset = FO.node_subsets(jax.random.PRNGKey(d), d, n, k, jnp.float32)
        plan = FO.level_plan(rows, k, n_bins, 3, nodes, budget)
        dtype = FO.bins_dtype(n_bins)
        # a block of nodes holds its histogram with its cumsum, right half
        # and gains beside one step's pieces' sums (the parts and their total)
        room = budget - 4 * 3 * 4 * k * n_bins * NODE_STEP_PIECES
        node_block = int(min(nodes, 2 ** int(np.log2(max(1, room // (4 * 3 * k * n_bins * 4))))))

        # the arrays go in as arguments: a closure would make them constants
        @jax.jit
        def select(words, local, subset):
            pieces = FO._level_pieces(local, nodes)
            return pieces, FO._piece_bins(words, subset, pieces, dtype)

        pieces, piece_bins = select(words, local, subset)

        @jax.jit
        def by_slots(piece_bins, pieces, contrib):
            return FO._best_splits(piece_bins, pieces, contrib, nodes, n_bins, plan,
                                   "variance", 1.0, 0.0, lambda x: x)

        @jax.jit
        def by_nodes(piece_bins, pieces, contrib):
            return node_blocked_best(piece_bins, pieces, contrib, nodes, n_bins,
                                     node_block, "variance")

        a, b = by_slots(piece_bins, pieces, contrib), by_nodes(piece_bins, pieces, contrib)
        same = all(bool(np.array_equal(np.asarray(x), np.asarray(z)))
                   for x, z in zip(a[1:], b[1:]))
        print(json.dumps({
            "depth": d, "nodes": nodes, "rows": rows, "k": k,
            "select_s": clock(select, words, local, subset),
            "slot_blocks": plan.blocks, "slots": plan.slots,
            "by_slots_s": clock(by_slots, piece_bins, pieces, contrib),
            "node_blocks": nodes // node_block, "node_block": node_block,
            "by_nodes_s": clock(by_nodes, piece_bins, pieces, contrib), "same_splits": same,
        }), flush=True)
        del pieces, piece_bins, a, b


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--trees", type=int, default=None)
    ap.add_argument("--levels", action="store_true")
    args = ap.parse_args(argv)

    cell, config, traffic = M.load_cell(args.workload)
    if args.trees is not None:
        config["params"]["numTrees"] = args.trees
    M.apply_env(config)

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"readings are taken on the chip, not on {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    from benchmarks.drivers import refit_resident_forest_reg as R
    from spark_rapids_ml_tpu.localspark import LocalSparkSession
    from spark_rapids_ml_tpu.ops import forest as FO
    from spark_rapids_ml_tpu.parallel import forest as PF

    if args.levels:
        time_levels(config, (0, 6, 9, 11, 12), FO.level_budget())

    program = R.resolve_program()
    n_trees = int(config["params"]["numTrees"])
    judging = ThreadPoolExecutor(1)

    def reopen(driver):
        driver.session = LocalSparkSession(
            parallelism=int(traffic["partitions"]), num_workers=int(traffic["workers"]))
        driver.df = driver.session.createDataFrame(driver.table)

    def fit(driver):
        t0 = time.perf_counter()
        model = driver.estimator().fit(driver.df)
        trees = {name: np.asarray(getattr(model.trees, name))
                 for name in ("feature", "split_bin", "is_leaf", "leaf_stats")}
        return {"trees": trees, "thresholds": np.asarray(model.thresholds),
                "seconds": time.perf_counter() - t0}

    def report(seed, what, answer, given):
        read = R.judge_fits(given, [answer], config, program)
        tree = {name: arr[0] for name, arr in answer["trees"].items()}
        read["control_sum_gap"] = reference_forest_reg.control_sum_gap(
            tree, reference_forest_reg.bin_rows(given["x32"], given["edges"]),
            given["y"].astype(given["x32"].dtype),
            reference_forest_reg.fold(given["weights"][:1], given["distinct"],
                                      len(given["x32"]))[0],
            int(config["params"]["maxDepth"]))
        print(json.dumps({
            "workload": cell["name"], "seed": seed, "fit": what,
            "seconds": answer["seconds"], "first_seconds": answer.get("first_seconds"),
            "trees": n_trees,
            "split_nodes": int(np.sum(answer["trees"]["feature"] >= 0)), **read,
        }), flush=True)

    drivers, pending = [], []
    for seed in args.seeds:
        driver = R.Driver(config, traffic, seed, cell["chips"])
        driver.make_data()
        first = fit(driver)["seconds"]  # the first fit compiles
        answer = fit(driver)
        answer["first_seconds"] = first
        driver.given = R.program_inputs(program, driver.blocks, driver.order, config)
        pending.append(judging.submit(report, seed, "sound", answer, driver.given))
        driver.session.stop()
        if len(drivers) < args.control:  # the rest are let go once judged
            drivers.append(driver)
    if args.control:
        sums = FO._onehot_sums
        FO._onehot_sums = lambda bins, stats, n_bins: sums(
            bins, jax.lax.reduce_precision(stats, exponent_bits=8, mantissa_bits=7), n_bins)
        PF.make_sharded_forest.cache_clear()
        jax.clear_caches()
        for driver in drivers:
            reopen(driver)
            driver.estimator().fit(driver.df)  # the first fit compiles
            pending.append(judging.submit(
                report, driver.seed, "control one bf16 part", fit(driver), driver.given))
            driver.session.stop()
    for job in pending:
        job.result()
    print(json.dumps({"peak_bytes_in_use": max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
