"""Traffic driver ``refit_resident_forest_reg``: one caller that fits a random
forest regressor on a two-column table (an ``array<double>`` of features and
a continuous ``double`` label) whose rows are held resident on the device,
waits for the trees on the host, and fits again (a closed loop of one).

``refit_resident_forest``'s loop, set-up and draws, on the regression table
(``data_regression``): set-up resolves what the reference will ask of the
program, makes the rows and labels from the seed, starts a localspark
session and runs one whole warm-up fit. After the window the float64
reference (``reference_forest_reg``) judges every tree of the window's last
fit and the first tree of each other fit, node by node from the program's
own tree, on a pool of threads.
"""

from __future__ import annotations

import math
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks import data, data_regression, reference_forest_reg
from benchmarks.drivers import refit_resident_forest
from benchmarks.drivers.refit_resident_forest import TREES, program_inputs, resolve_program

__all__ = ["Driver", "judge_fits", "program_inputs", "resolve_program"]


class Driver(refit_resident_forest.Driver):
    def make_data(self) -> None:
        from spark_rapids_ml_tpu import telemetry
        from spark_rapids_ml_tpu.localspark import LocalSparkSession

        telemetry.install_monitoring()
        self.telemetry = telemetry
        block_rows = int(self.traffic["block_rows"])
        blocks = math.ceil(self.rows / block_rows)
        if blocks * block_rows != self.rows:
            raise SystemExit(f"{self.rows} rows are not whole blocks of {block_rows}")
        kinds = int(self.traffic["kinds"])
        self.order = data.block_order(blocks, kinds)
        self.blocks = data_regression.make_blocks(
            self.seed, self.n, block_rows, kinds, **self.config["data"]
        )
        self.table = data_regression.to_table(self.blocks, self.order)
        self.session = LocalSparkSession(
            parallelism=int(self.traffic["partitions"]),
            num_workers=int(self.traffic["workers"]),
        )
        self.df = self.session.createDataFrame(self.table)

    def check(self) -> dict[str, dict]:
        """The trees of the window against the reference, each number beside
        its limit. The reference is run here, after the window."""
        limits = self.config["limits"]
        worst = dict.fromkeys(reference_forest_reg.COMPARED, 0.0)
        fits = [a for a in self.answers if not a["error"]]
        if fits:
            t0 = time.perf_counter()
            judged = judge_fits(self.handed(), fits, self.config, self.program)
            print(f"refit_resident_forest_reg: {judged.pop('trees_judged'):.0f} trees judged "
                  f"in {time.perf_counter() - t0:.1f} s", flush=True)
            worst.update(judged)
        else:
            worst = dict.fromkeys(worst, reference_forest_reg.BROKEN)
        compared = {
            name: {"value": float(value), "limit": float(limits[name])}
            for name, value in worst.items()
        }
        name, labels = TREES
        compared["trees_off_plan"] = {
            "value": float(abs(
                self.registry.counter(name, **labels) - self.attempted * self.n_trees
            )),
            "limit": 0.0,
        }
        compared["compiled_in_window"] = {
            "value": float(self.registry.counter("compile.cache_misses")),
            "limit": 0.0,
        }
        return compared


def judge_fits(given: dict, fits: list[dict], config: dict, program: dict) -> dict:
    """The worst of every compared number over the trees compared: every tree
    of the last fit and the first tree of each other fit (a tree equal to
    one already judged is judged once)."""
    import jax

    dt = jax.dtypes.canonicalize_dtype(np.float64)
    params = config["params"]
    n_bins, max_depth = int(params["maxBins"]), int(params["maxDepth"])
    n_trees, seed = int(params["numTrees"]), int(params["seed"])
    n = given["x32"].shape[1]
    k = program["subset_size"](params["featureSubsetStrategy"], n, classification=False)
    ref_bins = reference_forest_reg.bin_rows(given["x32"], given["edges"])
    out = {
        "bins_off": float(np.sum(ref_bins != given["dev_bins"])),
        "edge_gap": reference_forest_reg.edge_gap(
            given["edges"], reference_forest_reg.quantile_edges(given["sample64"], n_bins)
        ),
        "thresholds_off": 0.0,
    }
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    folded = reference_forest_reg.fold(given["weights"], given["distinct"], len(given["x32"]))
    # the label as the device holds it
    labels = given["y"].astype(dt).astype(np.float64)
    wanted = [(fits[-1], t) for t in range(n_trees)] + [(a, 0) for a in fits[:-1]]
    seen: dict[bytes, dict] = {}
    with ThreadPoolExecutor(max(1, min(12, (os.cpu_count() or 2) - 1))) as pool:
        for answer, t in wanted:
            tree = {name: arr[t] for name, arr in answer["trees"].items()}
            out["thresholds_off"] = max(out["thresholds_off"], reference_forest_reg.thresholds_off(
                tree["feature"], tree["split_bin"], answer["thresholds"][t], given["edges"]))
            mark = b"".join(np.ascontiguousarray(v).tobytes() for v in tree.values()) + bytes([t])
            if mark not in seen:
                subsets = [
                    np.asarray(program["subsets"](keys[t], d, n, k, dt))
                    for d in range(max_depth)
                ]
                seen[mark] = reference_forest_reg.judge_tree(
                    tree, ref_bins, labels, folded[t], subsets,
                    max_depth=max_depth, n_bins=n_bins,
                    min_instances=float(params["minInstancesPerNode"]),
                    eps=float(np.finfo(dt).eps), pool=pool,
                )
            for name, value in seen[mark].items():
                out[name] = max(out.get(name, 0.0), value)
    out["trees_judged"] = float(len(seen))
    return out
