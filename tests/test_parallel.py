"""Distributed-layer tests on the 8-device virtual CPU mesh.

These validate the SPMD paths the reference never had: psum Gram allreduce,
the ring feature-sharded Gram, and the end-to-end sharded fit — all compiled
and executed over a real (virtual-device) Mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.parallel import gram as G
from spark_rapids_ml_tpu.parallel import mesh as M


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) == 8, "conftest must provide 8 virtual devices"
    return M.create_mesh(data=4, feat=2)


@pytest.fixture
def x(rng):
    return rng.normal(size=(256, 32))


class TestShardedGram:
    def test_matches_local(self, mesh8, x, rng):
        xs = jax.device_put(x, M.data_sharding(mesh8))
        stats = G.sharded_gram_stats(xs, mesh8)
        np.testing.assert_allclose(np.asarray(stats.xtx), x.T @ x, rtol=1e-10)
        np.testing.assert_allclose(np.asarray(stats.col_sum), x.sum(0), rtol=1e-10)
        assert int(stats.count) == 256

    def test_jit_compiles_once(self, mesh8, x):
        xs = jax.device_put(x, M.data_sharding(mesh8))
        fn = jax.jit(lambda a: G.sharded_gram_stats(a, mesh8))
        s1 = fn(xs)
        np.testing.assert_allclose(np.asarray(s1.xtx), x.T @ x, rtol=1e-10)


class TestRingGram:
    def test_matches_local(self, mesh8, x):
        xs = jax.device_put(x, M.data_sharding(mesh8, feature_sharded=True))
        g, col_sum, count = G.ring_gram(xs, mesh8)
        np.testing.assert_allclose(np.asarray(g), x.T @ x, rtol=1e-10)
        np.testing.assert_allclose(np.asarray(col_sum), x.sum(0), rtol=1e-10)
        assert int(count) == 256

    def test_gram_output_is_feature_sharded(self, mesh8, x):
        xs = jax.device_put(x, M.data_sharding(mesh8, feature_sharded=True))
        g, _, _ = G.ring_gram(xs, mesh8)
        # block-rows live on the feat axis: each shard is [n/feat, n]
        shard_shapes = {s.data.shape for s in g.addressable_shards}
        assert shard_shapes == {(16, 32)}

    def test_larger_feat_axis(self, x):
        mesh = M.create_mesh(data=2, feat=4)
        xs = jax.device_put(x, M.data_sharding(mesh, feature_sharded=True))
        g, _, _ = G.ring_gram(xs, mesh)
        np.testing.assert_allclose(np.asarray(g), x.T @ x, rtol=1e-10)


class TestDistributedFit:
    @pytest.mark.parametrize("feature_sharded", [False, True])
    @pytest.mark.parametrize("mean_centering", [False, True])
    def test_matches_single_device(self, mesh8, x, feature_sharded, mean_centering):
        fit = G.make_distributed_fit(
            mesh8, 5, mean_centering=mean_centering, feature_sharded=feature_sharded
        )
        pc, ev = fit(jnp.asarray(x))
        pc_ref, ev_ref = L.pca_fit_local(jnp.asarray(x), 5, mean_centering=mean_centering)
        np.testing.assert_allclose(np.asarray(pc), np.asarray(pc_ref), atol=1e-8)
        np.testing.assert_allclose(np.asarray(ev), np.asarray(ev_ref), atol=1e-10)

    def test_outputs_replicated(self, mesh8, x):
        fit = G.make_distributed_fit(mesh8, 3)
        pc, _ = fit(jnp.asarray(x))
        assert pc.sharding.is_fully_replicated

    def test_randomized_solver_distributed(self, mesh8, rng):
        """Sharded Gram + randomized Rayleigh–Ritz as one SPMD program."""
        base = rng.normal(size=(256, 4))
        x = base @ rng.normal(size=(4, 32)) + 0.01 * rng.normal(size=(256, 32))
        fit = G.make_distributed_fit(mesh8, 3, solver="randomized")
        pc, ev = fit(jnp.asarray(x))
        pc_ref, _ = L.pca_fit_local(jnp.asarray(x), 3)
        np.testing.assert_allclose(
            np.abs(np.asarray(pc)), np.abs(np.asarray(pc_ref)), atol=1e-6
        )
        assert pc.sharding.is_fully_replicated and ev.shape == (3,)


class TestMeshHelpers:
    def test_factor_mesh(self):
        assert M.factor_mesh(8) == (4, 2)
        assert M.factor_mesh(16) == (4, 4)
        assert M.factor_mesh(1) == (1, 1)
        assert M.factor_mesh(6) == (3, 2)

    def test_create_mesh_validates(self):
        with pytest.raises(ValueError):
            M.create_mesh(data=16, feat=2)

    def test_hybrid_mesh_falls_back_single_slice(self):
        # CPU devices report no slice topology → flat (data, feat) mesh
        mesh = M.create_hybrid_mesh(feat=2)
        assert mesh.axis_names == (M.DATA_AXIS, M.FEAT_AXIS)
        assert mesh.shape[M.FEAT_AXIS] == 2

    def test_hybrid_mesh_explicit_slice_groups_layout(self):
        # the DCN-aware layout contract: feat rows never cross a slice
        # boundary; the data axis concatenates slices
        import jax

        devices = jax.devices()
        groups = [[0, 1, 2, 3], [4, 5, 6, 7]]
        mesh = M.create_hybrid_mesh(feat=2, slice_groups=groups)
        assert mesh.shape[M.DATA_AXIS] == 4 and mesh.shape[M.FEAT_AXIS] == 2
        by_slice = {devices[i]: s for s, g in enumerate(groups) for i in g}
        for row in mesh.devices:
            assert len({by_slice[d] for d in row}) == 1

    def test_hybrid_mesh_slice_groups_validation(self):
        with pytest.raises(ValueError, match="equal-size"):
            M.create_hybrid_mesh(slice_groups=[[0, 1, 2], [3]])
        with pytest.raises(ValueError, match="partition"):
            M.create_hybrid_mesh(slice_groups=[[0, 1], [1, 2]])
        with pytest.raises(ValueError, match="feat=3"):
            M.create_hybrid_mesh(feat=3, slice_groups=[[0, 1, 2, 3]])

    def test_shard_map_decorator_form(self, mesh8):
        # the installed jax.shard_map, called the way parallel/ calls it
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        @jax.shard_map(mesh=mesh8, in_specs=P(M.DATA_AXIS), out_specs=P(), check_vma=False)
        def total(v):
            return lax.psum(v.sum(), M.DATA_AXIS)

        x = np.arange(16.0)
        assert float(total(x)) == x.sum()


class TestFullLoopFits:
    """The entire iterative fit as ONE XLA program (while_loop + psum inside
    shard_map) — must match the per-step driver loop exactly."""

    def test_logreg_full_loop_matches_core(self):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.linear import LogisticRegression
        from spark_rapids_ml_tpu.ops import linear as LIN
        from spark_rapids_ml_tpu.parallel import linear as PL
        from spark_rapids_ml_tpu.parallel import mesh as M

        rng = np.random.default_rng(50)
        rows, n = 512, 6
        x = rng.normal(size=(rows, n))
        p = 1.0 / (1.0 + np.exp(-(x @ rng.normal(size=n) - 0.2)))
        y = (rng.random(rows) < p).astype(np.float64)

        mesh = M.create_mesh(data=8, feat=1)
        xa = np.concatenate([x, np.ones((rows, 1))], axis=1)
        fit = PL.make_distributed_logreg_fit(
            mesh, reg_param=1e-3, max_iter=15, tol=1e-9
        )
        w, iters, step = fit(
            jax.device_put(jnp.asarray(xa), M.data_sharding(mesh)),
            jax.device_put(jnp.asarray(y), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(M.DATA_AXIS))),
            jax.device_put(jnp.ones(rows), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(M.DATA_AXIS))),
        )
        core = (
            LogisticRegression().setRegParam(1e-3).setMaxIter(15).setTol(1e-9)
            .fit((x, y))
        )
        np.testing.assert_allclose(
            np.asarray(w)[:-1], core.coefficients, atol=1e-8
        )
        np.testing.assert_allclose(float(np.asarray(w)[-1]), core.intercept, atol=1e-8)
        assert int(iters) >= 2

    def test_kmeans_full_loop_matches_core(self):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.models.kmeans import KMeans
        from spark_rapids_ml_tpu.parallel import kmeans as PK
        from spark_rapids_ml_tpu.parallel import mesh as M

        rng = np.random.default_rng(51)
        centers_true = rng.normal(size=(5, 4)) * 6.0
        x = np.concatenate(
            [rng.normal(size=(64, 4)) * 0.4 + c for c in centers_true]
        )
        rng.shuffle(x)
        init = x[:5].copy()

        mesh = M.create_mesh(data=8, feat=1)
        fit = PK.make_distributed_kmeans_fit(mesh, max_iter=12, tol=1e-6)
        centers, cost, iters = fit(
            jax.device_put(jnp.asarray(x), M.data_sharding(mesh)),
            jax.device_put(jnp.ones(len(x)), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(M.DATA_AXIS))),
            jnp.asarray(init),
        )
        # core loop from the same init: monkey-route by calling the ops loop
        from spark_rapids_ml_tpu.ops import kmeans as KM

        c = jnp.asarray(init)
        cost_ref = None
        for _ in range(12):
            stats = KM.kmeans_stats(jnp.asarray(x), c)
            new_c = KM.update_centers(stats, c)
            cost_ref = float(stats.cost)
            shift = float(KM.center_shift_sq(c, new_c))
            c = new_c
            if shift <= 1e-12:
                break
        np.testing.assert_allclose(np.asarray(centers), np.asarray(c), atol=1e-8)
        np.testing.assert_allclose(float(cost), cost_ref, rtol=1e-10)
