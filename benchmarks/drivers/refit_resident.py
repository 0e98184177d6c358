"""Traffic driver ``refit_resident``: one caller that fits an iterative
estimator on rows held resident on the device, waits for the fitted model on
the host, and fits again (a closed loop of one).

Set-up makes the rows from the seed, starts a localspark session and runs one
whole warm-up fit: the programs are specialised on the padded rows, so nothing
shorter warms them. The window calls ``Spark<Estimator>.fit(df)`` back to
back with the same params, ``seed`` among them; nothing starts after
``seconds`` and the fit in flight finishes. What each fit returned is kept and
compared with the plain reference once the window has closed.

The reference has to start where the timed fits started, and the seeding's
random draws are the program's. So after the window has closed and the trace
has stopped, ``release`` fits once more with ``maxIter`` 0: the program's loop
then runs no iteration and hands back the centres it was given, which are the
seeding's (the same rows, the same ``seed`` and the same programs give the
same centres). Nothing is added to a timed fit and no param changes meaning.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import data, data_blobs, reference_kmeans
from benchmarks.drivers import refit_stream
from benchmarks.drivers.refit_stream import QUIET

# host spans whose seconds are printed for each fit, so that a slow fit says where it was slow
PHASES = ("mesh.ingest", "kmeans mesh init", "kmeans mesh-local fit")
ITERATIONS = ("kmeans.iterations", {"path": "mesh-local"})


class Driver(refit_stream.Driver):
    """The closed loop's bookkeeping (attempted, failed, completed,
    ``fit_rows_per_s``) is ``refit_stream``'s; the rows, the warm-up, what a
    fit returns and what it is compared with are this driver's."""

    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        super().__init__(config, traffic, seed, chips)
        self.max_iter = int(config["params"]["maxIter"])
        self.centres0 = None            # where every fit of the window started

    # -- set-up ---------------------------------------------------------------
    def estimator(self, **override):
        from spark_rapids_ml_tpu import spark

        # the estimator's own seed param is the cell's, the same every fit
        params = {**self.config["params"], "seed": self.seed % (2 ** 31 - 1), **override}
        return getattr(spark, self.config["estimator"])(**params).setInputCol(data.COLUMN)

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.make_data()
        t1 = time.perf_counter()
        self.estimator().fit(self.df)  # the warm-up: one whole fit
        self.setup_marks = {"data_s": t1 - t0, "warm_up_fit_s": time.perf_counter() - t1}

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
        self.blocks = data_blobs.make_blocks(
            self.seed, self.n, self.k, block_rows, kinds, **self.config["data"]
        )
        self.table = data.to_table(self.blocks, self.order)
        self.session = LocalSparkSession(
            parallelism=int(self.traffic["partitions"]),
            num_workers=int(self.traffic["workers"]),
        )
        self.df = self.session.createDataFrame(self.table)

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float) -> None:
        """Fits back to back; none starts after ``seconds``."""
        registry = self.telemetry.REGISTRY
        before = registry.snapshot()
        self.started_at = time.perf_counter()
        done = self.started_at
        while time.perf_counter() - self.started_at < seconds:
            snap = registry.snapshot()
            answer = {"centres": None, "cost": None, "error": None}
            self.answers.append(answer)
            t0 = time.perf_counter()
            try:
                model = self.estimator().fit(self.df)
                answer["centres"] = np.asarray(model.clusterCenters)
                answer["cost"] = float(model.trainingCost)
            except Exception as e:  # a failed fit is counted, and the loop goes on
                answer["error"] = f"{type(e).__name__}: {e}"
            done = time.perf_counter()
            answer["seconds"] = done - t0
            moved = registry.snapshot().delta(snap)
            answer["phases"] = {
                phase: round(moved.hist("span.seconds", phase=phase).total, 2)
                for phase in PHASES
            }
            noisy = {name: moved.counter(name) for name in QUIET if moved.counter(name)}
            if noisy and not answer["error"]:
                answer["error"] = f"the fit degraded or retried: {noisy}"
        self.elapsed_s = done - self.started_at
        self.registry = registry.snapshot().delta(before)

    # -- after the window -----------------------------------------------------
    def release(self) -> None:
        """Learn where the window's fits started (one fit of no iteration),
        then free the program's state; the seeded blocks stay for the
        reference."""
        try:
            self.centres0 = np.asarray(self.estimator(maxIter=0).fit(self.df).clusterCenters)
        except Exception as e:
            print(f"refit_resident: the fit of no iteration failed: {e!r}", flush=True)
        self.session.stop()
        self.df = self.table = self.session = None

    def check(self) -> dict[str, dict]:
        """Every fit of the window against the reference, each number beside
        its limit. The reference is run here, after the window."""
        limits = self.config["limits"]
        worst = dict.fromkeys(reference_kmeans.COMPARED, 0.0)
        held = {"seed_rows_off": reference_kmeans.BROKEN,
                "seed_cost_ratio": reference_kmeans.BROKEN}
        if self.centres0 is not None and self.centres0.shape == (self.k, self.n):
            ref = getattr(reference_kmeans, self.config["reference"])(
                self.blocks, self.order, self.centres0, self.max_iter
            )
            held = reference_kmeans.seeding(
                self.blocks, self.order, self.centres0, self.seed, ref["first_cost"]
            )
            print(f"refit_resident: the reference ran {ref['iterations']} iterations, "
                  f"the last moved a centre by {math.sqrt(ref['last_shift']):.3g}; "
                  f"cost {ref['cost']!r}", flush=True)
            for answer in self.answers:
                if answer["error"]:
                    continue
                for name, value in reference_kmeans.compare(
                    answer["centres"], answer["cost"], ref
                ).items():
                    worst[name] = max(worst[name], value)
        if not self.completed or self.centres0 is None:
            worst = dict.fromkeys(worst, reference_kmeans.BROKEN)
        compared = {
            name: {"value": value, "limit": float(limits[name])}
            for name, value in {**worst, **held}.items()
        }
        name, labels = ITERATIONS
        compared["iterations_off_plan"] = {
            "value": float(abs(
                self.registry.counter(name, **labels) - self.attempted * self.max_iter
            )),
            "limit": 0.0,
        }
        compared["compiled_in_window"] = {
            "value": float(self.registry.counter("compile.cache_misses")),
            "limit": 0.0,
        }
        return compared
