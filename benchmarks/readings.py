#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, at a cell's own
size on the chip, many seeds to a process:

    python3 benchmarks/readings.py --workload <name> --seeds 1 2 3 [--controls config precision=high] [--fault half]

For each seed it makes the cell's rows, fits them once as the configuration
states (the lower readings) and once more for each of ``--controls``:
``config`` stands for the configuration's own ``control`` params, the step
below the precision it states (the upper readings), and ``name=value`` for any
other param; ``--fault half`` fits once with the second half of every chunk
left out. Each fit is compared with the plain reference exactly as a run's
fits are. One JSON line a fit; nothing here is a benchmark run.
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
from benchmarks import reference  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", nargs="*", default=[])
    ap.add_argument("--fault", choices=("half",))
    args = ap.parse_args(argv)

    cell, config, traffic = M.load_cell(args.workload)
    M.apply_env(config)

    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"readings are taken on the chip, not on {jax.devices()[0].platform}",
              file=sys.stderr)
        return 2
    from benchmarks.drivers.refit_stream import Driver
    from spark_rapids_ml_tpu.parallel import gram as G

    def fit(driver, what, **override):
        t0 = time.perf_counter()
        model = driver.estimator(**override).fit(driver.df)
        read = reference.compare(model.pc, model.explainedVariance, *driver.ref)
        print(json.dumps({"workload": cell["name"], "seed": driver.seed, "fit": what,
                          "seconds": time.perf_counter() - t0, **read}), flush=True)

    for seed in args.seeds:
        driver = Driver(config, traffic, seed, cell["chips"])
        driver.make_data()
        driver.ref = getattr(reference, config["reference"])(
            driver.blocks, driver.order, driver.k)
        fit(driver, "sound")
        for spec in args.controls:
            params = (config["control"]["params"] if spec == "config"
                      else dict([spec.split("=", 1)]))
            fit(driver, f"control {params}", **params)
        if args.fault:
            fold = G.sharded_gram_fold
            G.sharded_gram_fold = lambda c, x, w, mesh, **kw: fold(
                c, x, w.at[w.shape[0] // 2:].set(0.0), mesh, **kw)
            try:
                fit(driver, "fault_half")
            finally:
                G.sharded_gram_fold = fold
        driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
