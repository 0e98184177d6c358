"""Chunked-loop checkpointing for mesh-local fits (r3 verdict #6).

The whole-loop mesh programs used to reject ``checkpoint_dir`` outright —
a preempted 2-hour pod fit restarted from zero. The chunked variants run K
iterations per cached XLA program with a durable host checkpoint between
chunks; these tests assert the contract that matters: a partial fit plus a
resumed fit produces EXACTLY the model an uninterrupted fit produces
(same iteration trajectory, same programs), and mesh-barrier still rejects
with a pointer to the supported modes.
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu.localspark import LocalSparkSession
from spark_rapids_ml_tpu.localspark import types as LT
from spark_rapids_ml_tpu.spark import SparkKMeans, SparkLogisticRegression


@pytest.fixture(scope="module")
def session():
    s = LocalSparkSession(
        parallelism=2,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "JAX_ENABLE_X64": "1",
        },
    )
    yield s
    s.stop()


def _labeled_df(session, x, y):
    schema = LT.StructType(
        [
            LT.StructField("features", LT.ArrayType(LT.DoubleType())),
            LT.StructField("label", LT.DoubleType()),
        ]
    )
    return session.createDataFrame(
        [(r.tolist(), float(l)) for r, l in zip(x, y)], schema, numPartitions=2
    )


def _features_df(session, x):
    schema = LT.StructType(
        [LT.StructField("features", LT.ArrayType(LT.DoubleType()))]
    )
    return session.createDataFrame(
        [(r.tolist(),) for r in x], schema, numPartitions=2
    )


class TestLogRegMeshChunkedCheckpoint:
    def _data(self):
        rng = np.random.default_rng(41)
        x = rng.normal(size=(300, 4))
        p = 1.0 / (1.0 + np.exp(-(x @ np.array([2.0, -1.0, 0.5, 0.0]))))
        y = (rng.random(300) < p).astype(float)
        return x, y

    def _est(self, iters):
        return (
            SparkLogisticRegression(maxIter=iters, regParam=1e-3)
            .setTol(0.0)  # fixed-iteration trajectory: exact comparison
            .setDistribution("mesh-local")
        )

    def test_partial_then_resume_matches_uninterrupted(self, session, tmp_path):
        x, y = self._data()
        df = _labeled_df(session, x, y)
        ckdir = str(tmp_path / "lr_mesh_ck")
        uninterrupted = self._est(8).fit(df)
        # "preemption": a fit stopped after 3 iterations left checkpoints
        self._est(3).fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        resumed = self._est(8).fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        np.testing.assert_allclose(
            resumed.coefficients, uninterrupted.coefficients, atol=1e-10
        )
        np.testing.assert_allclose(
            resumed.intercept, uninterrupted.intercept, atol=1e-10
        )

    def test_chunked_equals_whole_loop_without_checkpoint(self, session, tmp_path):
        x, y = self._data()
        df = _labeled_df(session, x, y)
        ckdir = str(tmp_path / "lr_mesh_ck2")
        whole = self._est(6).fit(df)
        chunked = self._est(6).fit(df, checkpoint_dir=ckdir, checkpoint_every=4)
        np.testing.assert_allclose(
            chunked.coefficients, whole.coefficients, atol=1e-10
        )

    def test_softmax_partial_then_resume(self, session, tmp_path):
        rng = np.random.default_rng(42)
        centers = np.array([[3.0, 0.0], [0.0, 3.0], [-3.0, -3.0]])
        x = np.vstack([rng.normal(size=(60, 2)) + c for c in centers])
        y = np.repeat([0.0, 1.0, 2.0], 60)
        df = _labeled_df(session, x, y)
        ckdir = str(tmp_path / "mn_mesh_ck")

        def est(iters):
            return (
                SparkLogisticRegression(maxIter=iters, regParam=1e-2)
                .setTol(0.0)
                .setDistribution("mesh-local")
            )

        uninterrupted = est(6).fit(df)
        est(2).fit(df, checkpoint_dir=ckdir, checkpoint_every=1)
        resumed = est(6).fit(df, checkpoint_dir=ckdir, checkpoint_every=1)
        np.testing.assert_allclose(
            resumed.coefficientMatrix,
            uninterrupted.coefficientMatrix,
            atol=1e-10,
        )

    def test_barrier_partial_then_resume_matches_uninterrupted(
        self, session, tmp_path
    ):
        # mesh-barrier edition: rank 0 of the jax.distributed group saves
        # between chunks (shared filesystem — one host here), the DRIVER
        # resolves the resume before launching the next stage
        x, y = self._data()
        df = _labeled_df(session, x, y)
        ckdir = str(tmp_path / "lr_barrier_ck")

        def est(iters):
            return (
                SparkLogisticRegression(maxIter=iters, regParam=1e-3)
                .setTol(0.0)
                .setDistribution("mesh-barrier")
            )

        uninterrupted = est(6).fit(df)
        est(2).fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        resumed = est(6).fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        np.testing.assert_allclose(
            resumed.coefficients, uninterrupted.coefficients, atol=1e-10
        )

    def test_barrier_resume_at_max_iter_skips_the_stage(self, session, tmp_path):
        x, y = self._data()
        df = _labeled_df(session, x, y)
        ckdir = str(tmp_path / "lr_barrier_ck2")
        full = self._est_barrier(4).fit(
            df, checkpoint_dir=ckdir, checkpoint_every=1
        )
        resumed = self._est_barrier(4).fit(
            df, checkpoint_dir=ckdir, checkpoint_every=1
        )
        np.testing.assert_allclose(
            resumed.coefficients, full.coefficients, atol=1e-12
        )

    def _est_barrier(self, iters):
        return (
            SparkLogisticRegression(maxIter=iters, regParam=1e-3)
            .setTol(0.0)
            .setDistribution("mesh-barrier")
        )


class TestKMeansMeshChunkedCheckpoint:
    def _data(self):
        rng = np.random.default_rng(43)
        anchors = np.array([[4.0, 0.0, 0.0], [0.0, 4.0, 0.0], [0.0, 0.0, 4.0]])
        return np.vstack([rng.normal(size=(70, 3)) * 0.5 + a for a in anchors])

    def _est(self, iters):
        return (
            SparkKMeans(k=3, seed=7, maxIter=iters)
            .setTol(0.0)
            .setDistribution("mesh-local")
        )

    def test_partial_then_resume_matches_uninterrupted(self, session, tmp_path):
        x = self._data()
        df = _features_df(session, x)
        ckdir = str(tmp_path / "km_mesh_ck")
        uninterrupted = self._est(8).fit(df)
        self._est(3).fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        resumed = self._est(8).fit(df, checkpoint_dir=ckdir, checkpoint_every=2)
        np.testing.assert_allclose(
            resumed.clusterCenters, uninterrupted.clusterCenters, atol=1e-10
        )
        np.testing.assert_allclose(
            resumed.trainingCost, uninterrupted.trainingCost, rtol=1e-10
        )

    def test_resume_at_max_iter_reports_checkpointed_cost(self, session, tmp_path):
        x = self._data()
        df = _features_df(session, x)
        ckdir = str(tmp_path / "km_mesh_ck2")
        full = self._est(5).fit(df, checkpoint_dir=ckdir, checkpoint_every=1)
        resumed = self._est(5).fit(df, checkpoint_dir=ckdir, checkpoint_every=1)
        assert np.isfinite(resumed.trainingCost)
        np.testing.assert_allclose(
            resumed.clusterCenters, full.clusterCenters, atol=1e-12
        )

    def test_barrier_partial_then_resume_matches_uninterrupted(
        self, session, tmp_path
    ):
        x = self._data()
        df = _features_df(session, x)
        ckdir = str(tmp_path / "km_barrier_ck")

        def est(iters):
            return (
                SparkKMeans(k=3, seed=7, maxIter=iters)
                .setTol(0.0)
                .setDistribution("mesh-barrier")
            )

        uninterrupted = est(6).fit(df)
        est(2).fit(df, checkpoint_dir=ckdir, checkpoint_every=1)
        resumed = est(6).fit(df, checkpoint_dir=ckdir, checkpoint_every=1)
        np.testing.assert_allclose(
            resumed.clusterCenters, uninterrupted.clusterCenters, atol=1e-10
        )
