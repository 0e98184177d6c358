"""Mixed-precision policy tests (``ops.policy``).

Two claims:

1. VOCABULARY — ``resolve_policy`` reads ``TPU_ML_PRECISION_POLICY`` when no
   policy is passed, an explicit one wins, and the fold kernels' allow-list
   rejects ``int8_dist``.
2. NUMERICS — the ``bf16_f32acc`` policy passes the f64-oracle gates at
   the documented tolerances (PCA min |cosine| >= 0.99, linear coef
   rel err <= 5e-2, gram rel err <= 2e-3) with accumulator dtype preserved,
   and ``int8_dist`` keeps kmeans assignments >= 0.99 in agreement with
   full precision on separated clusters.
"""

import numpy as np
import pytest

from spark_rapids_ml_tpu.ops import kmeans as KM
from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.ops import linear as LIN
from spark_rapids_ml_tpu.ops.policy import FOLD_POLICIES, resolve_policy
from spark_rapids_ml_tpu.utils import knobs

# documented mixed-precision tolerances (mirrored in README's policy table)
BF16_GRAM_REL_ERR = 2e-3
BF16_PCA_MIN_COSINE = 0.99
BF16_LINEAR_COEF_REL_ERR = 5e-2
INT8_KMEANS_AGREEMENT = 0.99


@pytest.fixture(autouse=True)
def no_policy_env(monkeypatch):
    monkeypatch.delenv(knobs.PRECISION_POLICY.name, raising=False)


def test_resolve_policy_env_default(monkeypatch):
    assert resolve_policy(None) == "f32"
    monkeypatch.setenv(knobs.PRECISION_POLICY.name, "bf16_f32acc")
    assert resolve_policy(None) == "bf16_f32acc"
    # explicit beats env
    assert resolve_policy("f32") == "f32"


def test_fold_policies_exclude_int8(monkeypatch):
    monkeypatch.setenv(knobs.PRECISION_POLICY.name, "int8_dist")
    with pytest.raises(ValueError):
        resolve_policy(None, allowed=FOLD_POLICIES)


class TestMixedPrecisionNumerics:
    @pytest.fixture(scope="class")
    def spectral_data(self):
        rng = np.random.default_rng(7)
        n = 16
        # strongly decaying column scales: well-separated top eigenpairs so
        # the oracle comparison measures policy error, not eigengap noise
        x = rng.normal(size=(2000, n)) * (2.0 ** -np.arange(n))
        return np.asarray(x, np.float64)

    def _fold_gram(self, x, policy):
        import jax.numpy as jnp

        step = L.gram_fold_step(policy=policy)
        carry = L.init_gram_carry(x.shape[1], np.float64)
        for at in range(0, len(x), 500):
            chunk = jnp.asarray(x[at:at + 500])
            carry = step(carry, chunk, jnp.ones(len(chunk), jnp.float64))
        return carry

    def test_bf16_gram_rel_err_and_carry_dtype(self, spectral_data):
        x = spectral_data
        c = self._fold_gram(x, "bf16_f32acc")
        assert str(c.xtx.dtype) == "float64"  # accumulator NEVER narrows
        ref = x.T @ x
        rel = np.max(np.abs(np.asarray(c.xtx) - ref)) / np.max(np.abs(ref))
        assert 0 < rel <= BF16_GRAM_REL_ERR
        # count/col_sum stay exact: they never route through the matmul
        assert float(c.count) == len(x)
        np.testing.assert_allclose(np.asarray(c.col_sum), x.sum(axis=0))

    def test_bf16_pca_cosine_vs_f64_oracle(self, spectral_data):
        x = spectral_data
        k = 4
        c = self._fold_gram(x, "bf16_f32acc")
        pc, _ev = L.pca_fit_from_cov(c.xtx, k)
        assert L.min_cosine_vs_f64_oracle(x, pc, k) >= BF16_PCA_MIN_COSINE

    def test_bf16_linear_coef_vs_f64_oracle(self):
        import jax.numpy as jnp

        rng = np.random.default_rng(3)
        n = 8
        x = rng.normal(size=(4000, n))
        coef = rng.normal(size=n)
        y = x @ coef + 0.01 * rng.normal(size=len(x))

        step = LIN.linear_fold_step(policy="bf16_f32acc")
        carry = LIN.init_linear_carry(n, np.float64)
        for at in range(0, len(x), 1000):
            xc = jnp.asarray(x[at:at + 1000])
            yc = jnp.asarray(y[at:at + 1000])
            carry = step(carry, xc, yc, jnp.ones(len(xc), jnp.float64))
        got = np.linalg.solve(np.asarray(carry.xtx), np.asarray(carry.xty))
        oracle = np.linalg.solve(x.T @ x, x.T @ y)
        rel = np.linalg.norm(got - oracle) / np.linalg.norm(oracle)
        assert rel <= BF16_LINEAR_COEF_REL_ERR

    @pytest.mark.parametrize("policy", ["bf16_f32acc", "int8_dist"])
    def test_distance_policy_assignment_agreement(self, policy):
        import jax.numpy as jnp

        rng = np.random.default_rng(5)
        k, n = 8, 16
        centers = rng.normal(size=(k, n)) * 6.0  # separated
        labels = rng.integers(0, k, size=3000)
        x = centers[labels] + rng.normal(size=(3000, n))
        xd, cd = jnp.asarray(x), jnp.asarray(centers)
        base, _ = KM.assign_clusters(xd, cd)
        got, _ = KM.assign_clusters(xd, cd, policy=policy)
        agreement = float(np.mean(np.asarray(base) == np.asarray(got)))
        assert agreement >= INT8_KMEANS_AGREEMENT

    def test_int8_rejected_for_fold_kernels(self):
        with pytest.raises(ValueError):
            L.gram_fold_step(policy="int8_dist")
        with pytest.raises(ValueError):
            LIN.linear_fold_step(policy="int8_dist")
