#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from for a logistic
regression cell, at the cell's own size on the chip, many seeds to a process:

    python3 benchmarks/readings_logreg.py --workload <name> --seeds 1 2 3 [--control]

For each seed it makes the cell's rows and labels, fits them once as the
configuration states (the lower readings) and compares with the plain
reference exactly as a run's fits are. With ``--control`` every seed is then
fitted once more with the three products of the Newton statistics at one
bfloat16 pass (``Precision.DEFAULT``): the estimator has no precision param,
so the step below is made here, by handing ``ops.linear``'s two statistics
functions that precision, for this process alone. (On a TPU v5e that control
reads what the sound fit reads: the compiler takes the two matrix-vector
products as float32 multiply-reduce fusions whatever the precision says, so
only the Hessian's product changes, and that moves the path and not the
optimum. The upper readings are the reference's: ``reference_logreg.irls``
with ``passes=1`` or with the fault planted. PERF.md section 2.) The
references run on a thread of their own while the device fits the next seed.
One JSON line a fit; nothing here is a benchmark run.
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as M  # noqa: E402
from benchmarks import reference_logreg  # noqa: E402


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
    from benchmarks.drivers.refit_resident_labeled import Driver
    from spark_rapids_ml_tpu.localspark import LocalSparkSession
    from spark_rapids_ml_tpu.ops import linear as LIN
    from spark_rapids_ml_tpu.parallel import linear as PL

    def fit(driver):
        t0 = time.perf_counter()
        model = driver.estimator().fit(driver.df)
        coef, intercept = np.asarray(model.coefficients), float(model.intercept)
        return coef, intercept, time.perf_counter() - t0

    def report(driver, what, coef, intercept, seconds):
        ref = driver.ref.result()
        print(json.dumps({
            "workload": cell["name"], "seed": driver.seed, "fit": what, "seconds": seconds,
            **reference_logreg.compare(
                driver.blocks, driver.order, coef, intercept, ref,
                driver.reg_param, driver.fit_intercept),
            "ref_last_step": ref["last_step"] / float(np.linalg.norm(ref["w"])),
            "ref_objective": ref["objective"],
        }), flush=True)

    def referee(driver):
        return reference_logreg.irls(
            driver.blocks, driver.order, driver.max_iter, driver.reg_param,
            driver.fit_intercept)

    def reopen(driver):
        driver.session = LocalSparkSession(
            parallelism=int(traffic["partitions"]), num_workers=int(traffic["workers"]))
        driver.df = driver.session.createDataFrame(driver.table)

    drivers, sound = [], []
    with ThreadPoolExecutor(max_workers=1) as pool:
        for seed in args.seeds:
            driver = Driver(config, traffic, seed, cell["chips"])
            driver.make_data()
            sound.append(fit(driver))
            driver.session.stop()
            driver.ref = pool.submit(referee, driver)
            drivers.append(driver)
        for driver, answer in zip(drivers, sound):
            report(driver, "sound", *answer)
        if args.control:
            for name in ("logistic_newton_stats", "svc_newton_stats"):
                setattr(LIN, name, functools.partial(
                    getattr(LIN, name), precision=jax.lax.Precision.DEFAULT))
            PL.make_distributed_logreg_chunk.cache_clear()
            PL.make_distributed_logreg_fit.cache_clear()
            jax.clear_caches()
            for driver in drivers:
                reopen(driver)
                report(driver, "control one bf16 pass", *fit(driver))
                driver.session.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
