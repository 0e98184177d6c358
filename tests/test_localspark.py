"""localspark engine tests: DataFrame semantics + the worker-process
execution boundary (cloudpickle, Arrow IPC, schema validation, reuse).

These are the engine's own unit tests; the estimator integration suite that
runs on BOTH localspark and real pyspark lives in
``test_spark_integration.py``.
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_ml_tpu.localspark import (
    LocalSparkSession,
    Row,
    functions as F,
    types as T,
)
from spark_rapids_ml_tpu.localspark.session import WorkerException


@pytest.fixture(scope="module")
def spark():
    with LocalSparkSession(parallelism=3) as s:
        yield s


def _features_df(spark, rows=30, dim=4, parallelism=None, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, dim))
    schema = T.StructType(
        [
            T.StructField("features", T.ArrayType(T.DoubleType())),
            T.StructField("idx", T.LongType()),
        ]
    )
    df = spark.createDataFrame(
        [(row.tolist(), i) for i, row in enumerate(x)],
        schema,
        numPartitions=parallelism,
    )
    return df, x


class TestTypes:
    def test_struct_arrow_round_trip(self):
        s = T.StructType(
            [
                T.StructField("a", T.ArrayType(T.DoubleType())),
                T.StructField("b", T.LongType()),
                T.StructField("c", T.StringType()),
            ]
        )
        arrow = s.to_arrow()
        assert arrow.field("a").type == pa.list_(pa.float64())
        assert T.from_arrow_schema(arrow) == s

    def test_equality(self):
        assert T.DoubleType() == T.DoubleType()
        assert T.ArrayType(T.DoubleType()) == T.ArrayType(T.DoubleType())
        assert T.ArrayType(T.DoubleType()) != T.ArrayType(T.LongType())


class TestDataFrameBasics:
    def test_create_and_collect(self, spark):
        df, x = _features_df(spark)
        rows = df.collect()
        assert len(rows) == 30
        # Row supports positional, by-name, and attribute access
        r = rows[7]
        assert r[1] == 7 and r["idx"] == 7 and r.idx == 7
        np.testing.assert_allclose(r["features"], x[7])

    def test_partitioning(self, spark):
        df, _ = _features_df(spark)
        assert df.rdd.getNumPartitions() == 3
        df8 = df.repartition(8)
        assert df8.rdd.getNumPartitions() == 8
        assert df8.count() == 30

    def test_select_first_limit(self, spark):
        df, x = _features_df(spark)
        sel = df.select("features")
        assert sel.schema.names == ["features"]
        first = sel.first()
        np.testing.assert_allclose(first[0], x[0])
        assert len(df.limit(5).collect()) == 5
        with pytest.raises(KeyError):
            df.select("nope")

    def test_where(self, spark):
        df, _ = _features_df(spark)
        assert df.where(F.col("idx") >= 20).count() == 10
        assert df.where((F.col("idx") >= 10) & (F.col("idx") < 12)).count() == 2

    def test_sample_seeded_and_unbiased_across_partitions(self, spark):
        df, _ = _features_df(spark, rows=600)
        s1 = df.sample(fraction=0.3, seed=7).collect()
        s2 = df.sample(fraction=0.3, seed=7).collect()
        assert [r.idx for r in s1] == [r.idx for r in s2]  # deterministic
        assert 100 < len(s1) < 260
        # rows must come from every partition, not a head
        idx = np.array([r.idx for r in s1])
        for lo in (0, 200, 400):
            assert ((idx >= lo) & (idx < lo + 200)).any()

    def test_random_split(self, spark):
        df, _ = _features_df(spark, rows=500)
        a, b = df.randomSplit([0.8, 0.2], seed=3)
        na, nb = a.count(), b.count()
        assert na + nb == 500
        assert 330 < na < 470
        # disjoint
        ia = {r.idx for r in a.collect()}
        ib = {r.idx for r in b.collect()}
        assert not (ia & ib)

    def test_to_arrow(self, spark):
        df, x = _features_df(spark)
        table = df.toArrow()
        assert table.num_rows == 30
        assert table.schema.field("features").type == pa.list_(pa.float64())

    def test_schema_inference_from_names(self, spark):
        df = spark.createDataFrame(
            [([1.0, 2.0], 3, "a"), ([0.5, 1.5], 4, "b")], ["vec", "n", "s"]
        )
        assert df.schema["vec"].dataType == T.ArrayType(T.DoubleType())
        assert df.schema["n"].dataType == T.LongType()
        assert df.schema["s"].dataType == T.StringType()

    def test_pandas_input(self, spark):
        pd = pytest.importorskip("pandas")
        pdf = pd.DataFrame({"a": [1.0, 2.0, 3.0], "b": [1, 2, 3]})
        df = spark.createDataFrame(pdf)
        assert df.count() == 3
        assert df.schema["a"].dataType == T.DoubleType()


class TestMapInArrowBoundary:
    def test_identity_roundtrip(self, spark):
        df, x = _features_df(spark)

        def ident(batches):
            yield from batches

        out = df.mapInArrow(ident, df.schema)
        assert out.count() == 30

    def test_closure_crosses_process(self, spark):
        """The plan function runs in ANOTHER PROCESS: module state mutated
        there must not be visible here, and captured state must arrive."""
        df, x = _features_df(spark)
        factor = 3.5  # captured in the closure -> cloudpickle must carry it

        def scale(batches):
            import os

            for b in batches:
                arr = np.asarray(
                    [np.asarray(v) * factor for v in b.column("features").to_pylist()]
                )
                flat = arr.reshape(-1)
                offsets = pa.array(
                    np.arange(0, flat.size + 1, arr.shape[1], dtype=np.int32)
                )
                col = pa.ListArray.from_arrays(offsets, pa.array(flat))
                pid = pa.array(np.full(b.num_rows, os.getpid(), dtype=np.int64))
                yield pa.RecordBatch.from_arrays(
                    [col, pid], schema=out_schema.to_arrow()
                )

        out_schema = T.StructType(
            [
                T.StructField("scaled", T.ArrayType(T.DoubleType())),
                T.StructField("pid", T.LongType()),
            ]
        )
        rows = df.select("features").mapInArrow(scale, out_schema).collect()
        import os as driver_os

        worker_pids = {r.pid for r in rows}
        assert driver_os.getpid() not in worker_pids  # really another process
        np.testing.assert_allclose(rows[0]["scaled"], x[0] * factor, rtol=1e-12)

    def test_worker_exception_carries_traceback(self, spark):
        df, _ = _features_df(spark)

        def boom(batches):
            for b in batches:
                raise ValueError("deliberate kaboom in worker")
            yield  # pragma: no cover

        out = df.mapInArrow(boom, df.schema)
        with pytest.raises(WorkerException, match="deliberate kaboom"):
            out.collect()

    def test_output_schema_mismatch_detected(self, spark):
        df, _ = _features_df(spark)

        def wrong_cols(batches):
            for b in batches:
                yield pa.RecordBatch.from_arrays(
                    [pa.array(np.zeros(b.num_rows))], names=["unexpected"]
                )

        declared = T.StructType([T.StructField("expected", T.DoubleType())])
        with pytest.raises(WorkerException, match="missing declared column"):
            df.mapInArrow(wrong_cols, declared).collect()

    def test_worker_print_does_not_corrupt_protocol(self, spark):
        df, _ = _features_df(spark)

        def chatty(batches):
            print("spamming stdout from the worker")
            yield from batches

        assert df.mapInArrow(chatty, df.schema).count() == 30

    def test_worker_reuse_across_jobs(self, spark):
        """Same worker process serves successive jobs (Spark's
        python.worker.reuse): per-process caches amortize."""
        df, _ = _features_df(spark)

        def tag_pid(batches):
            import os

            for b in batches:
                yield pa.RecordBatch.from_arrays(
                    [pa.array(np.full(b.num_rows, os.getpid(), dtype=np.int64))],
                    names=["pid"],
                )

        schema = T.StructType([T.StructField("pid", T.LongType())])
        pids1 = {r.pid for r in df.mapInArrow(tag_pid, schema).collect()}
        pids2 = {r.pid for r in df.mapInArrow(tag_pid, schema).collect()}
        assert pids1 == pids2 and len(pids1) == 1

    def test_two_workers_parallel(self, monkeypatch):
        # no hedging: on a loaded machine the first worker is warm and idle
        # before its sibling has started, and would take the sibling's task
        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "0")
        with LocalSparkSession(parallelism=4, num_workers=2) as s:
            df, _ = _features_df(s, rows=40)

            def tag_pid(batches):
                import os

                n = sum(b.num_rows for b in batches)
                yield pa.RecordBatch.from_arrays(
                    [pa.array(np.full(n, os.getpid(), dtype=np.int64))],
                    names=["pid"],
                )

            schema = T.StructType([T.StructField("pid", T.LongType())])
            pids = {r.pid for r in df.mapInArrow(tag_pid, schema).collect()}
            assert len(pids) == 2  # tasks really landed on two processes

    def test_empty_partition_runs_fn(self, spark):
        # 5 rows over 3 partitions + a filter that empties some: the fn must
        # still execute and emitting nothing must be fine
        df, _ = _features_df(spark, rows=5)
        empty = df.where(F.col("idx") > 100)

        def ident(batches):
            yield from batches

        assert empty.mapInArrow(ident, df.schema).count() == 0

    def test_unpicklable_fn_fails_at_submit(self, spark):
        df, _ = _features_df(spark)
        import threading

        lock = threading.Lock()  # unpicklable even for cloudpickle

        def bad(batches):
            with lock:
                yield from batches

        with pytest.raises(TypeError):
            df.mapInArrow(bad, df.schema).collect()

    def test_ddl_string_schema_rejected(self, spark):
        df, _ = _features_df(spark)
        with pytest.raises(TypeError, match="StructType"):
            df.mapInArrow(lambda it: it, "a double")


class TestReviewRegressions:
    def test_create_from_arrow_table(self, spark):
        table = pa.table({"a": [1.0, 2.0, 3.0], "b": [1, 2, 3]})
        df = spark.createDataFrame(table)
        assert df.count() == 3
        assert df.schema["a"].dataType == T.DoubleType()

    def test_sample_positional_forms(self, spark):
        df, _ = _features_df(spark, rows=200)
        kw = {r.idx for r in df.sample(fraction=0.5, seed=9).collect()}
        pos = {r.idx for r in df.sample(0.5, 9).collect()}
        assert kw == pos
        assert df.sample(0.5).count() > 0

    def test_dead_worker_is_replaced(self):
        with LocalSparkSession(parallelism=2) as s:
            df, _ = _features_df(s, rows=10)

            def suicide(batches):
                import os
                import signal

                os.kill(os.getpid(), signal.SIGKILL)
                yield  # pragma: no cover

            with pytest.raises(WorkerException, match="died mid-task"):
                df.mapInArrow(suicide, df.schema).collect()
            # the session recovers with a fresh worker on the next job
            assert df.count() == 10

            def ident(batches):
                yield from batches

            assert df.mapInArrow(ident, df.schema).count() == 10

    @pytest.mark.chaos
    def test_fault_plan_kill_replaces_worker(self, monkeypatch):
        # workers snapshot os.environ at spawn, so a TPU_ML_FAULT_PLAN set
        # before session creation rides into the worker process and kills it
        # mid-task (exit code 113); clearing the env before the next job
        # means the replacement worker spawns WITHOUT the plan and survives
        monkeypatch.setenv("TPU_ML_FAULT_PLAN", "worker.task:kill:1")
        with LocalSparkSession(parallelism=1) as s:
            df, _ = _features_df(s, rows=10)

            def ident(batches):
                yield from batches

            with pytest.raises(WorkerException, match="died mid-task"):
                df.mapInArrow(ident, df.schema).collect()
            doomed_pid = None
            if s._workers:  # the dead worker is still listed until _ensure_workers
                doomed_pid = s._workers[0].proc.pid

            monkeypatch.delenv("TPU_ML_FAULT_PLAN")
            assert df.mapInArrow(ident, df.schema).count() == 10
            assert s._workers[0].proc.pid != doomed_pid

    def test_missing_partition_result_raises_not_silent(self):
        # a None in the results list used to be yielded as an EMPTY batch
        # list — silent data loss dressed up as an empty partition. It must
        # raise, naming the partition(s) that never produced a payload.
        from spark_rapids_ml_tpu.localspark import session as S

        with pytest.raises(WorkerException, match=r"partition\(s\) \[1\]"):
            S._require_results([[], None, []], "mapInArrow")
        assert S._require_results([[], []], "mapInArrow") == [[], []]

    def test_rand_offset_continuation(self):
        # rand(seed) must yield the same per-row stream regardless of how a
        # partition is chunked: evaluating at row offset k must continue the
        # stream exactly where k prior rows left it
        c = F.rand(7)

        def batch(n):
            return pa.record_batch([pa.array(np.zeros(n))], names=["x"])

        full = np.asarray(c.evaluate(batch(30), 0, 0))
        head = np.asarray(c.evaluate(batch(10), 0, 0))
        tail = np.asarray(c.evaluate(batch(20), 0, 10))
        np.testing.assert_array_equal(np.concatenate([head, tail]), full)
        # different partitions get different streams
        other = np.asarray(c.evaluate(batch(30), 1, 0))
        assert not np.array_equal(full, other)


class TestRow:
    def test_row_api(self):
        r = Row([1.0, "x"], ["a", "b"])
        assert r[0] == 1.0 and r["b"] == "x" and r.a == 1.0
        assert r.asDict() == {"a": 1.0, "b": "x"}
        with pytest.raises(KeyError):
            r["nope"]
        with pytest.raises(AttributeError):
            r.nope
