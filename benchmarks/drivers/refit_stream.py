"""Traffic driver ``refit_stream``: one caller that fits, waits for the
fitted model on the host, and fits again (a closed loop of one).

Set-up makes the rows from the seed, starts a localspark session and runs one
short warm-up fit through the same programs. The window calls
``Spark<Estimator>.fit(df)`` back to back; nothing starts after ``seconds``
and the fit in flight finishes. What each fit returned is kept and compared
with the plain reference once the window has closed.
"""

from __future__ import annotations

import math
import time

import numpy as np

from benchmarks import data, reference

QUIET = ("degraded.cpu_fallback", "retry.attempts")
# host spans whose seconds are printed for each fit, so that a slow fit says where it was slow
PHASES = ("compute cov", "fold.dispatch", "fold.wait", "eigh")


class Driver:
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        self.config, self.traffic, self.seed, self.chips = config, traffic, seed, chips
        self.n = int(config["n_features"])
        self.k = int(config["params"]["k"])
        self.rows = int(config["rows"])
        self.answers: list[dict] = []   # one per fit started in the window
        self.started_at = self.elapsed_s = 0.0
        self.registry = None            # the program's registry delta over the window

    # -- set-up ---------------------------------------------------------------
    def estimator(self, **override):
        from spark_rapids_ml_tpu import spark

        params = {**self.config["params"], **override}
        return getattr(spark, self.config["estimator"])(**params).setInputCol(data.COLUMN)

    def setup(self) -> None:
        t0 = time.perf_counter()
        self.make_data()
        t1 = time.perf_counter()
        self.warm_up()
        self.setup_marks = {"data_s": t1 - t0, "warm_up_fit_s": time.perf_counter() - t1}

    def make_data(self) -> None:
        from spark_rapids_ml_tpu import telemetry
        from spark_rapids_ml_tpu.localspark import LocalSparkSession

        telemetry.install_monitoring()
        self.telemetry = telemetry
        block_rows = max(1, int(self.traffic["block_bytes"]) // (8 * self.n))
        blocks = math.ceil(self.rows / block_rows)
        if blocks * block_rows != self.rows:
            raise SystemExit(f"{self.rows} rows are not whole blocks of {block_rows}")
        self.order = data.block_order(blocks, int(self.traffic["kinds"]))
        self.blocks = data.make_blocks(
            self.seed, self.n, self.k, block_rows, int(self.traffic["kinds"])
        )
        self.table = table = data.to_table(self.blocks, self.order)
        self.session = LocalSparkSession(
            parallelism=int(self.traffic["partitions"]),
            num_workers=int(self.traffic["workers"]),
        )
        self.df = self.session.createDataFrame(table)

    def warm_up(self) -> None:
        """One fit of the fewest whole blocks that still take the streamed
        branch: the same chunk shape and the same programs, with fewer bytes."""
        from spark_rapids_ml_tpu.spark import ingest

        block_rows = len(self.blocks[0])
        resident = 0
        while not ingest.use_streamed_fit(resident, self.n):
            resident += block_rows
        rows = min(resident, self.rows)
        self.estimator().fit(self.session.createDataFrame(self.table.slice(0, rows)))

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float) -> None:
        """Fits back to back; none starts after ``seconds``."""
        registry = self.telemetry.REGISTRY
        before = registry.snapshot()
        self.started_at = time.perf_counter()
        done = self.started_at
        while time.perf_counter() - self.started_at < seconds:
            snap = registry.snapshot()
            answer = {"pc": None, "ev": None, "error": None}
            self.answers.append(answer)
            t0 = time.perf_counter()
            try:
                model = self.estimator().fit(self.df)
                answer["pc"] = np.asarray(model.pc)
                answer["ev"] = np.asarray(model.explainedVariance)
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

    @property
    def attempted(self) -> int:
        return len(self.answers)

    @property
    def failed(self) -> int:
        return sum(1 for a in self.answers if a["error"])

    @property
    def completed(self) -> int:
        return self.attempted - self.failed

    def end_to_end(self) -> dict[str, float]:
        if not self.completed:
            return {}
        return {"fit_rows_per_s": self.completed * self.rows / self.elapsed_s}

    # -- after the window -----------------------------------------------------
    def release(self) -> None:
        """Free the program's state; the seeded blocks stay for the reference."""
        self.session.stop()
        self.df = self.table = self.session = None

    def expected_chunks(self) -> int:
        from spark_rapids_ml_tpu.spark import ingest

        return math.ceil(self.rows / ingest.stream_chunk_rows())

    def check(self) -> dict[str, dict]:
        """Every fit of the window against the reference, each number beside
        its limit. The reference is run here, after the window."""
        limits = self.config["limits"]
        ref_pc, ref_ev = getattr(reference, self.config["reference"])(
            self.blocks, self.order, self.k
        )
        worst = dict.fromkeys(reference.COMPARED, 0.0)
        for answer in self.answers:
            if answer["error"]:
                continue
            for name, value in reference.compare(
                answer["pc"], answer["ev"], ref_pc, ref_ev
            ).items():
                worst[name] = max(worst[name], value)
        if not self.completed:
            worst = {name: reference.BROKEN for name in worst}
        compared = {
            name: {"value": value, "limit": float(limits[name])}
            for name, value in worst.items()
        }
        chunks = self.registry.hist("span.seconds", phase="fold.dispatch").count
        compared["chunks_off_plan"] = {
            "value": float(abs(chunks - self.expected_chunks() * self.attempted)),
            "limit": 0.0,
        }
        compared["compiled_in_window"] = {
            "value": float(self.registry.counter("compile.cache_misses")),
            "limit": 0.0,
        }
        return compared
