"""Traffic driver ``refit_resident_labeled``: one caller that fits a
supervised iterative estimator on a two-column table (an ``array<double>`` of
features and a ``double`` label) whose rows are held resident on the device,
waits for the coefficients and the intercept on the host, and fits again (a
closed loop of one).

Set-up makes the rows and the labels from the seed, starts a localspark
session and runs one whole warm-up fit: the Newton program is specialised on
the padded rows, so nothing shorter warms it. The window calls
``Spark<Estimator>.fit(df)`` back to back with the same params; nothing
starts after ``seconds`` and the fit in flight finishes. What each fit
returned is kept and compared with the plain reference once the window has
closed: the reference starts from zero, as the program does, so it needs
nothing from the program.

The closed loop's bookkeeping (attempted, failed, completed,
``fit_rows_per_s``) is ``refit_stream.Driver``'s.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import data, data_logreg, reference_logreg
from benchmarks.drivers import refit_stream
from benchmarks.drivers.refit_stream import QUIET

# host spans whose seconds are printed for each fit, so that a slow fit says where it was slow
PHASES = ("label scan", "mesh.ingest", "logreg mesh-local fit")
ITERATIONS = ("logreg.iterations", {"path": "mesh-local"})


class Driver(refit_stream.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        # not super().__init__: that one reads the PCA's k
        self.config, self.traffic, self.seed, self.chips = config, traffic, seed, chips
        self.n = int(config["n_features"])
        self.rows = int(config["rows"])
        params = config["params"]
        self.max_iter = int(params["maxIter"])
        self.reg_param = float(params["regParam"])
        self.fit_intercept = bool(params["fitIntercept"])
        self.answers: list[dict] = []   # one per fit started in the window
        self.started_at = self.elapsed_s = 0.0
        self.registry = None            # the program's registry delta over the window

    # -- set-up ---------------------------------------------------------------
    def estimator(self, **override):
        from spark_rapids_ml_tpu import spark

        params = {**self.config["params"], **override}
        est = getattr(spark, self.config["estimator"])(**params)
        return est.setFeaturesCol(data_logreg.FEATURES).setLabelCol(data_logreg.LABEL)

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.make_data()
        t1 = time.perf_counter()
        before = self.telemetry.REGISTRY.snapshot()
        self.estimator().fit(self.df)  # the warm-up: one whole fit
        warm = self.telemetry.REGISTRY.snapshot().delta(before)
        self.setup_marks = {
            "data_s": t1 - t0, "warm_up_fit_s": time.perf_counter() - t1,
            # what the warm-up compiled or loaded, by program, where it took half a second
            "compile_s": {
                dict(labels)["program"]: round(h.total, 2)
                for (name, labels), h in warm.hists.items()
                if name == "compile.program_seconds" and h.total >= 0.5
            },
            "cache_misses": warm.counter("compile.cache_misses"),
        }

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
        self.blocks = data_logreg.make_blocks(
            self.seed, self.n, block_rows, kinds, **self.config["data"]
        )
        self.table = data_logreg.to_table(self.blocks, self.order)
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
            answer = {"coef": None, "intercept": None, "error": None}
            self.answers.append(answer)
            t0 = time.perf_counter()
            try:
                model = self.estimator().fit(self.df)
                answer["coef"] = np.asarray(model.coefficients)
                answer["intercept"] = float(model.intercept)
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
    def check(self) -> dict[str, dict]:
        """Every fit of the window against the reference, each number beside
        its limit. The reference is run here, after the window."""
        limits = self.config["limits"]
        ref = getattr(reference_logreg, self.config["reference"])(
            self.blocks, self.order, self.max_iter, self.reg_param, self.fit_intercept
        )
        print(f"refit_resident_labeled: the reference took {ref['iterations']} Newton steps, "
              f"the last {ref['last_step'] / np.linalg.norm(ref['w']):.3g} of its weights' norm; "
              f"objective {ref['objective']!r}", flush=True)
        worst = dict.fromkeys(reference_logreg.COMPARED, 0.0)
        for answer in self.answers:
            if answer["error"]:
                continue
            for name, value in reference_logreg.compare(
                self.blocks, self.order, answer["coef"], answer["intercept"], ref,
                self.reg_param, self.fit_intercept,
            ).items():
                worst[name] = max(worst[name], value)
        if not self.completed:
            worst = dict.fromkeys(worst, reference_logreg.BROKEN)
        compared = {
            name: {"value": value, "limit": float(limits[name])}
            for name, value in worst.items()
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
