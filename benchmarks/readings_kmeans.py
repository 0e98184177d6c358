#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from for a KMeans cell,
at the cell's own size on the chip, many seeds to a process:

    python3 benchmarks/readings_kmeans.py --workload <name> --seeds 1 2 3 [--control]

For each seed it makes the cell's rows, fits them once as the configuration
states (the lower readings), learns where that fit started (a fit of no
iteration) and compares with the plain reference exactly as a run's fits are.
With ``--control`` every seed is then fitted once more with the cross term of
the Lloyd loop's distances at one bfloat16 pass (``Precision.DEFAULT``): the
estimator has no precision param, so the step below is made here, by handing
``ops.kmeans.assign_clusters`` that precision, for this process alone; the
seeding is left as it is, so both fits start from the same centres (the upper
readings). The references run on a thread of their own while the device fits
the next seed. One JSON line a fit; nothing here is a benchmark run.
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
from benchmarks import reference_kmeans  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    cell, config, traffic = M.load_cell(args.workload)
    M.apply_env(config)

    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print(f"readings are taken on the chip, not on {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    from benchmarks.drivers.refit_resident import Driver
    from spark_rapids_ml_tpu.localspark import LocalSparkSession
    from spark_rapids_ml_tpu.ops import kmeans as KM
    from spark_rapids_ml_tpu.parallel import kmeans as PK

    def fit(driver, **override):
        t0 = time.perf_counter()
        model = driver.estimator(**override).fit(driver.df)
        centres = np.asarray(model.clusterCenters)
        return centres, float(model.trainingCost), time.perf_counter() - t0

    def report(driver, what, centres, cost, seconds, ref, **more):
        print(json.dumps({
            "workload": cell["name"], "seed": driver.seed, "fit": what, "seconds": seconds,
            **reference_kmeans.compare(centres, cost, ref.result()), **more,
        }), flush=True)

    def referee(driver):
        ref = reference_kmeans.lloyd(
            driver.blocks, driver.order, driver.centres0, driver.max_iter)
        held = reference_kmeans.seeding(
            driver.blocks, driver.order, driver.centres0, driver.seed, ref["first_cost"])
        return {**ref, **held}

    def reopen(driver):
        driver.session = LocalSparkSession(
            parallelism=int(traffic["partitions"]), num_workers=int(traffic["workers"]))
        driver.df = driver.session.createDataFrame(driver.table)

    drivers, sound = [], []
    with ThreadPoolExecutor(max_workers=1) as pool:
        for seed in args.seeds:
            driver = Driver(config, traffic, seed, cell["chips"])
            driver.make_data()
            answer = fit(driver)
            driver.centres0, _, _ = fit(driver, maxIter=0)
            driver.session.stop()
            driver.ref = pool.submit(referee, driver)
            drivers.append(driver)
            sound.append(answer)
        for driver, answer in zip(drivers, sound):
            ref = driver.ref.result()
            report(driver, "sound", *answer, driver.ref,
                   seed_rows_off=ref["seed_rows_off"], seed_cost_ratio=ref["seed_cost_ratio"],
                   ref_iterations=ref["iterations"], ref_last_shift=ref["last_shift"])
        if args.control:
            assign = KM.assign_clusters
            KM.assign_clusters = lambda x, c, **kw: assign(
                x, c, **{**kw, "precision": jax.lax.Precision.DEFAULT})
            PK.make_distributed_kmeans_chunk.cache_clear()
            PK.make_distributed_kmeans_fit.cache_clear()
            jax.clear_caches()
            for driver in drivers:
                reopen(driver)
                report(driver, "control one bf16 pass", *fit(driver), driver.ref)
                driver.session.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
