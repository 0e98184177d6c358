#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from for a random
forest cell, at the cell's own size on the chip, many seeds to a process:

    python3 benchmarks/readings_forest.py --workload <name> --seeds 1 2 3 [--control] [--trees N]

For each seed it makes the cell's rows and labels, fits them once as the
configuration states (the lower readings) and judges every tree against the
plain reference exactly as a run's last fit is judged. With ``--control``
every seed is then fitted once more with each level's histograms held in
bfloat16 (the result of ``ops.forest._level_hist`` rounded, for this process
alone: the estimator has no precision param), and the reference's own
control (``reference_forest.control_root_gap``: the root's counts over the
weighted rows summed in bfloat16) is read beside it. ``--trees`` fits
another number of trees than the configuration states, to time the fit the
configuration's cut of the trees rests on. One JSON line a fit; nothing here
is a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as M  # noqa: E402
from benchmarks import reference_forest  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--trees", type=int, default=None)
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
    from benchmarks.drivers import refit_resident_forest as R
    from spark_rapids_ml_tpu.ops import forest as FO
    from spark_rapids_ml_tpu.parallel import forest as PF

    from spark_rapids_ml_tpu.localspark import LocalSparkSession

    program = R.resolve_program()
    n_trees = int(config["params"]["numTrees"])

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

    def report(driver, what, answer, given):
        read = R.judge_fits(given, [answer], config, program)
        labels = given["y"][given["distinct"]]
        read["control_root_gap"] = reference_forest.control_root_gap(
            given["weights"][0], labels, answer["trees"]["leaf_stats"][0], 2)
        print(json.dumps({
            "workload": cell["name"], "seed": driver.seed, "fit": what,
            "seconds": answer["seconds"], "first_seconds": answer.get("first_seconds"),
            "trees": n_trees,
            "split_nodes": int(np.sum(answer["trees"]["feature"] >= 0)), **read,
        }), flush=True)

    drivers = []
    for seed in args.seeds:
        driver = R.Driver(config, traffic, seed, cell["chips"])
        driver.make_data()
        first = fit(driver)["seconds"]  # the first fit compiles
        answer = fit(driver)
        answer["first_seconds"] = first
        given = R.program_inputs(program, driver.blocks, driver.order, config)
        report(driver, "sound", answer, given)
        driver.given = given
        driver.session.stop()
        drivers.append(driver)
    if args.control:
        level = FO._level_hist
        FO._level_hist = lambda *a: jax.lax.reduce_precision(
            level(*a), exponent_bits=8, mantissa_bits=7)
        PF.make_sharded_forest.cache_clear()
        jax.clear_caches()
        for driver in drivers:
            reopen(driver)
            driver.estimator().fit(driver.df)  # the first fit compiles
            report(driver, "control bf16 histograms", fit(driver), driver.given)
            driver.session.stop()
    print(json.dumps({"peak_bytes_in_use": max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices())}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
