"""Barrier-stage SPMD mesh execution through the DataFrame API.

The north-star test (VERDICT r2 #1): a multi-worker DataFrame fit whose
cross-partition Gram reduction happens as a psum collective inside ONE XLA
program spanning the barrier stage's jax.distributed process group — the
driver receives a single pre-reduced statistics row (never per-partition
xtx), and the result is differential-equal to the portable driver-merge
path (which is itself differential-tested against NumPy oracles).
"""

import json

import numpy as np
import pytest

from spark_rapids_ml_tpu.localspark import LocalSparkSession
from spark_rapids_ml_tpu.localspark import types as LT
from spark_rapids_ml_tpu.spark import SparkPCA
from spark_rapids_ml_tpu.spark import spmd


@pytest.fixture(scope="module")
def session():
    s = LocalSparkSession(
        parallelism=4,
        worker_env={
            "JAX_ENABLE_X64": "1",
        },
    )
    yield s
    s.stop()


def _features_df(session, x, partitions=4):
    schema = LT.StructType(
        [LT.StructField("features", LT.ArrayType(LT.DoubleType()))]
    )
    return session.createDataFrame(
        [(row.tolist(),) for row in x], schema, numPartitions=partitions
    )


class TestBarrierTaskContext:
    def test_all_gather_orders_by_rank(self, session):
        df = _features_df(session, np.eye(4), partitions=4)

        def fn(batches):
            import pyarrow as pa

            from spark_rapids_ml_tpu.localspark.taskcontext import (
                BarrierTaskContext,
            )

            list(batches)
            ctx = BarrierTaskContext.get()
            ctx.barrier()  # plain rendezvous round first
            gathered = ctx.allGather(json.dumps({"rank": ctx.partitionId()}))
            ranks = [json.loads(g)["rank"] for g in gathered]
            yield pa.RecordBatch.from_arrays(
                [
                    pa.array([ctx.partitionId()]),
                    pa.array([json.dumps(ranks)]),
                ],
                names=["rank", "ranks"],
            )

        out_schema = LT.StructType(
            [
                LT.StructField("rank", LT.LongType()),
                LT.StructField("ranks", LT.StringType()),
            ]
        )
        rows = df.mapInArrow(fn, out_schema, barrier=True).collect()
        assert sorted(r["rank"] for r in rows) == [0, 1, 2, 3]
        for r in rows:
            assert json.loads(r["ranks"]) == [0, 1, 2, 3]

    def test_outside_barrier_task_raises(self):
        from spark_rapids_ml_tpu.localspark.taskcontext import BarrierTaskContext

        with pytest.raises(RuntimeError, match="not inside a barrier task"):
            BarrierTaskContext.get()


class TestMeshGramStage:
    def test_single_prereduced_row_with_full_mesh(self, session, rng):
        """4 barrier tasks -> one jax.distributed group -> ONE stats row whose
        mesh_size proves the psum spanned all 4 processes."""
        x = rng.normal(size=(320, 6))
        df = _features_df(session, x, partitions=4)
        fn = spmd.MeshGramPartitionFn("features", precision="highest")
        schema = LT.StructType(
            [
                LT.StructField(f, LT.ArrayType(LT.DoubleType()))
                for f in spmd.MESH_FIELDS
            ]
        )
        batches = df.mapInArrow(fn, schema, barrier=True).toArrow().to_batches()
        stats, mesh_size = spmd.single_stats_from_batches(batches, 6)
        assert mesh_size == 4
        # the driver-visible payload is ALREADY globally reduced:
        np.testing.assert_allclose(stats.xtx, x.T @ x, rtol=1e-10)
        np.testing.assert_allclose(stats.col_sum, x.sum(axis=0), rtol=1e-10)
        assert float(stats.count) == 320.0

    def test_multiple_rows_rejected(self, rng):
        from spark_rapids_ml_tpu.spark import arrow_fns

        row = arrow_fns.arrays_to_batch(
            {
                "xtx": np.eye(2),
                "col_sum": np.zeros(2),
                "count": np.float64(1),
                "mesh_size": np.float64(1),
            }
        )
        with pytest.raises(AssertionError, match="exactly ONE pre-reduced"):
            spmd.single_stats_from_batches([row, row], 2)


class TestSparkPCAMeshBarrier:
    def test_differential_vs_driver_merge(self, session, rng):
        x = rng.normal(size=(320, 8)) + 2.0
        df = _features_df(session, x, partitions=4)
        base = SparkPCA().setInputCol("features").setK(3).setMeanCentering(True)
        mesh_model = base.copy().setDistribution("mesh-barrier").fit(df)
        merge_model = base.copy().setDistribution("driver-merge").fit(df)
        np.testing.assert_allclose(
            np.abs(mesh_model.pc), np.abs(merge_model.pc), atol=1e-8
        )
        np.testing.assert_allclose(
            mesh_model.explainedVariance,
            merge_model.explainedVariance,
            atol=1e-8,
        )

    def test_mesh_local_differential(self, session, rng):
        """'mesh-local': the driver's own (virtual 8-device) mesh runs the
        psum program on rows streamed through the DataFrame API."""
        x = rng.normal(size=(300, 7))
        df = _features_df(session, x, partitions=4)
        base = SparkPCA().setInputCol("features").setK(3)
        local_model = base.copy().setDistribution("mesh-local").fit(df)
        merge_model = base.copy().setDistribution("driver-merge").fit(df)
        np.testing.assert_allclose(
            np.abs(local_model.pc), np.abs(merge_model.pc), atol=1e-8
        )
        np.testing.assert_allclose(
            local_model.explainedVariance,
            merge_model.explainedVariance,
            atol=1e-8,
        )

    def test_bad_distribution_rejected(self):
        with pytest.raises(ValueError, match="distribution"):
            SparkPCA().setDistribution("gossip")


class TestMeshBarrierBeyondPCA:
    """The SPMD barrier machinery is estimator-generic (r3): every
    stats-monoid estimator reduces through one psum program."""

    def test_linreg_mesh_barrier_differential(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkLinearRegression

        x = rng.normal(size=(400, 5))
        coef = np.array([1.0, -2.0, 0.5, 3.0, 0.0])
        y = x @ coef + 1.5 + 0.01 * rng.normal(size=400)
        schema = LT.StructType(
            [
                LT.StructField("features", LT.ArrayType(LT.DoubleType())),
                LT.StructField("label", LT.DoubleType()),
            ]
        )
        df = session.createDataFrame(
            [(row.tolist(), float(lbl)) for row, lbl in zip(x, y)],
            schema,
            numPartitions=4,
        )
        base = SparkLinearRegression().setRegParam(1e-6)
        mesh = base.copy().setDistribution("mesh-barrier").fit(df)
        merge = base.copy().setDistribution("driver-merge").fit(df)
        np.testing.assert_allclose(mesh.coefficients, merge.coefficients, atol=1e-8)
        np.testing.assert_allclose(mesh.intercept, merge.intercept, atol=1e-8)

    def test_linreg_mesh_barrier_weighted(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkLinearRegression

        x = rng.normal(size=(300, 3))
        y = x @ np.ones(3)
        y_bad = y.copy()
        y_bad[150:] += 50.0
        w = np.ones(300)
        w[150:] = 1e-12
        schema = LT.StructType(
            [
                LT.StructField("features", LT.ArrayType(LT.DoubleType())),
                LT.StructField("label", LT.DoubleType()),
                LT.StructField("wt", LT.DoubleType()),
            ]
        )
        df = session.createDataFrame(
            [
                (row.tolist(), float(lbl), float(wi))
                for row, lbl, wi in zip(x, y_bad, w)
            ],
            schema,
            numPartitions=4,
        )
        model = (
            SparkLinearRegression().setWeightCol("wt")
            .setDistribution("mesh-barrier").fit(df)
        )
        np.testing.assert_allclose(model.coefficients, np.ones(3), atol=1e-4)

    def test_scaler_mesh_barrier_differential(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkStandardScaler

        x = rng.normal(size=(350, 6)) * 3.0 + 5.0
        df = _features_df(session, x, partitions=4)
        base = SparkStandardScaler().setInputCol("features")
        mesh = base.copy().setDistribution("mesh-barrier").fit(df)
        merge = base.copy().setDistribution("driver-merge").fit(df)
        np.testing.assert_allclose(mesh.mean, merge.mean, atol=1e-10)
        np.testing.assert_allclose(mesh.std, merge.std, atol=1e-10)

    def test_bad_distribution_rejected(self):
        from spark_rapids_ml_tpu.spark import (
            SparkLinearRegression,
            SparkStandardScaler,
        )

        # mesh-local became family-wide in r3 — it must be ACCEPTED now
        est = SparkLinearRegression().setDistribution("mesh-local")
        assert est.getOrDefault("distribution") == "mesh-local"
        with pytest.raises(ValueError, match="distribution"):
            SparkStandardScaler().setDistribution("gossip")


class TestFullLoopBarrierFits:
    """The r3 capstone: ENTIRE iterative fits as one XLA program across the
    barrier stage's process mesh — the driver sees only the final model."""

    def test_logreg_full_fit_differential(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkLogisticRegression

        x = rng.normal(size=(480, 4))
        p = 1.0 / (1.0 + np.exp(-(x @ np.array([2.0, -1.0, 0.5, 0.0]) - 0.3)))
        y = (rng.random(480) < p).astype(float)
        schema = LT.StructType(
            [
                LT.StructField("features", LT.ArrayType(LT.DoubleType())),
                LT.StructField("label", LT.DoubleType()),
            ]
        )
        df = session.createDataFrame(
            [(row.tolist(), float(lbl)) for row, lbl in zip(x, y)],
            schema,
            numPartitions=4,
        )
        base = SparkLogisticRegression().setRegParam(1e-3).setMaxIter(12)
        mesh = base.copy().setDistribution("mesh-barrier").fit(df)
        merge = base.copy().setDistribution("driver-merge").fit(df)
        np.testing.assert_allclose(
            mesh.coefficients, merge.coefficients, atol=1e-8
        )
        np.testing.assert_allclose(mesh.intercept, merge.intercept, atol=1e-8)

    def test_kmeans_full_fit_differential(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkKMeans

        centers_true = rng.normal(size=(5, 3)) * 7.0
        x = np.concatenate(
            [rng.normal(size=(60, 3)) * 0.4 + c for c in centers_true]
        )
        rng.shuffle(x)
        df = _features_df(session, x, partitions=4)
        base = (
            SparkKMeans().setInputCol("features").setK(5).setSeed(3)
            .setMaxIter(10).setTol(0.0)
        )
        mesh = base.copy().setDistribution("mesh-barrier").fit(df)
        merge = base.copy().setDistribution("driver-merge").fit(df)
        # same driver-side seeding, same Lloyd math -> identical trajectory
        np.testing.assert_allclose(
            mesh.clusterCenters, merge.clusterCenters, atol=1e-8
        )
        np.testing.assert_allclose(
            mesh.trainingCost, merge.trainingCost, rtol=1e-8
        )

    def test_multinomial_full_fit_differential(self, session, rng):
        # r3: >=3-class fits ALSO run the whole softmax loop on the mesh
        from spark_rapids_ml_tpu.spark import SparkLogisticRegression

        centers = np.array([[3.0, 0.0], [0.0, 3.0], [-3.0, -3.0]])
        x = np.vstack([rng.normal(size=(70, 2)) + c for c in centers])
        y = np.repeat([0.0, 1.0, 2.0], 70)
        perm = rng.permutation(len(y))
        x, y = x[perm], y[perm]
        schema = LT.StructType(
            [
                LT.StructField("features", LT.ArrayType(LT.DoubleType())),
                LT.StructField("label", LT.DoubleType()),
            ]
        )
        df = session.createDataFrame(
            [(row.tolist(), float(lbl)) for row, lbl in zip(x, y)],
            schema,
            numPartitions=4,
        )
        base = SparkLogisticRegression().setRegParam(1e-2).setMaxIter(8)
        mesh = base.copy().setDistribution("mesh-barrier").fit(df)
        merge = base.copy().setDistribution("driver-merge").fit(df)
        assert mesh.numClasses == 3
        # softmax has a flat class-shift direction that amplifies float
        # summation-order differences between the 8-device mesh psum and the
        # 4-partition driver merge; 1e-6 is still far inside model noise
        np.testing.assert_allclose(
            mesh.coefficientMatrix, merge.coefficientMatrix, atol=1e-6
        )
        np.testing.assert_allclose(
            mesh.interceptVector, merge.interceptVector, atol=1e-6
        )

    def test_checkpoint_on_mesh_barrier_writes_durable_steps(
        self, session, rng, tmp_path
    ):
        # r4: mesh-barrier ACCEPTS checkpoint_dir (rank-0 chunked saves on
        # a shared filesystem). Verify the stage leaves durable step dirs
        # and the resulting model is intact; trajectory-equality is covered
        # by tests/test_mesh_checkpoint.py's barrier resume tests.
        import os

        from spark_rapids_ml_tpu.spark import SparkKMeans

        x = np.vstack(
            [rng.normal(size=(30, 3)) + 4, rng.normal(size=(30, 3)) - 4]
        )
        df = _features_df(session, x)
        ckdir = str(tmp_path / "ck")
        m = (
            SparkKMeans().setInputCol("features").setK(2).setSeed(1)
            .setMaxIter(4).setTol(0.0).setDistribution("mesh-barrier")
            .fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        )
        assert m.clusterCenters.shape == (2, 3)
        steps = [d for d in os.listdir(ckdir) if d.startswith("step-")]
        assert steps, "rank-0 worker wrote no durable checkpoints"

    def test_all_zero_weights_rejected_on_mesh_barrier(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkLogisticRegression

        x = rng.normal(size=(40, 3))
        y = (rng.random(40) < 0.5).astype(float)
        schema = LT.StructType(
            [
                LT.StructField("features", LT.ArrayType(LT.DoubleType())),
                LT.StructField("label", LT.DoubleType()),
                LT.StructField("wt", LT.DoubleType()),
            ]
        )
        df = session.createDataFrame(
            [(r.tolist(), float(l), 0.0) for r, l in zip(x, y)], schema
        )
        est = (
            SparkLogisticRegression().setWeightCol("wt")
            .setDistribution("mesh-barrier").setMaxIter(3)
        )
        with pytest.raises(ValueError, match="all instance weights are zero"):
            est.fit(df)

    def test_weighted_logreg_mesh_barrier_differential(self, session, rng):
        from spark_rapids_ml_tpu.spark import SparkLogisticRegression

        x = rng.normal(size=(300, 3))
        p = 1.0 / (1.0 + np.exp(-(x @ np.array([1.5, -1.0, 0.5]))))
        y = (rng.random(300) < p).astype(float)
        w = rng.uniform(0.1, 2.0, size=300)
        schema = LT.StructType(
            [
                LT.StructField("features", LT.ArrayType(LT.DoubleType())),
                LT.StructField("label", LT.DoubleType()),
                LT.StructField("wt", LT.DoubleType()),
            ]
        )
        df = session.createDataFrame(
            [
                (r.tolist(), float(l), float(wi))
                for r, l, wi in zip(x, y, w)
            ],
            schema,
            numPartitions=4,
        )
        base = (
            SparkLogisticRegression().setWeightCol("wt")
            .setRegParam(1e-3).setMaxIter(10)
        )
        mesh = base.copy().setDistribution("mesh-barrier").fit(df)
        merge = base.copy().setDistribution("driver-merge").fit(df)
        np.testing.assert_allclose(
            mesh.coefficients, merge.coefficients, atol=1e-8
        )


class TestBarrierEdgeCases:
    def test_empty_partition_in_barrier_stage(self, session, rng):
        # a partition with zero rows must adopt the group's column count and
        # contribute nothing (zero shard) — not crash the rendezvous
        x = rng.normal(size=(3, 5))  # 3 rows over 4 partitions -> one empty
        df = _features_df(session, x, partitions=4)
        model = (
            SparkPCA().setInputCol("features").setK(2)
            .setDistribution("mesh-barrier").fit(df)
        )
        core = SparkPCA().setInputCol("features").setK(2).fit(x)
        np.testing.assert_allclose(np.abs(model.pc), np.abs(core.pc), atol=1e-8)
