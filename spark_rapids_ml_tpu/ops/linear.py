"""Linear-model device kernels — normal equations and IRLS, MXU-first.

The reference repo ships one estimator (PCA), but its family
(spark-rapids-ml's wider line-up) pairs it with GLMs. These kernels extend
the same architectural pattern the PCA path established (SURVEY.md §2
"parallelism strategies"): per-partition sufficient statistics as a
commutative monoid, combined by tree-aggregate or a mesh psum, with a tiny
replicated solve at the end.

- **LinearRegression** (closed form): the monoid is (XᵀX, Xᵀy, Σx, Σy, Σy²,
  m). Everything the [n, n] solve needs is one MXU pass over the data —
  structurally identical to PCA's Gram pass, so the hot loop hits the MXU
  with the same intensity.
- **LogisticRegression** (IRLS/Newton): each iteration's monoid is
  (XᵀWX, Xᵀ(y−p), loss) with W = p(1−p) — two matmuls per block. The
  replicated Newton solve is [n+1, n+1], negligible next to the data pass.

The intercept rides as an augmented all-ones feature column (``augment``),
so gradients/Hessians need no special-casing; L2 regularization masks the
intercept coordinate out of the penalty, matching Spark ML/sklearn.
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.ops.policy import FOLD_POLICIES, resolve_policy
from spark_rapids_ml_tpu.ops.linalg import (
    DEFAULT_PRECISION,
    DEFAULT_POLICY,
    policy_matmul,
)


def augment(x: jax.Array) -> jax.Array:
    """Append an all-ones intercept column: [rows, n] → [rows, n+1]."""
    return jnp.concatenate([x, jnp.ones((x.shape[0], 1), x.dtype)], axis=1)


# ---------------------------------------------------------------------------
# Linear regression (normal equations)
# ---------------------------------------------------------------------------


class LinearStats(NamedTuple):
    """Sufficient statistics for (optionally intercepted, L2) least squares."""

    xtx: jax.Array  # [n, n]
    xty: jax.Array  # [n]
    x_sum: jax.Array  # [n]
    y_sum: jax.Array  # []
    y_sq: jax.Array  # []
    count: jax.Array  # []


def linear_stats(
    x: jax.Array,
    y: jax.Array,
    weights: jax.Array | None = None,
    *,
    precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> LinearStats:
    """One-pass statistics over a row shard; ``weights`` masks padded rows.

    ``policy='bf16_f32acc'`` casts only the XᵀX/Xᵀy matmul operands
    (``linalg.policy_matmul``); the sums and count stay in the carry dtype."""
    if weights is not None:
        xw = x * weights[:, None]
        yw = y * weights
        count = jnp.sum(weights)
    else:
        xw, yw = x, y
        count = jnp.asarray(x.shape[0], x.dtype)
    return LinearStats(
        xtx=policy_matmul(x.T, xw, precision=precision, policy=policy),
        xty=policy_matmul(x.T, yw, precision=precision, policy=policy),
        x_sum=jnp.sum(xw, axis=0),
        y_sum=jnp.sum(yw),
        y_sq=jnp.sum(yw * y),
        count=count,
    )


def combine_linear_stats(a: LinearStats, b: LinearStats) -> LinearStats:
    return LinearStats(*(av + bv for av, bv in zip(a, b)))


def fold_linear_stats(
    carry: LinearStats,
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    *,
    precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> LinearStats:
    """One streamed-fit fold step: carry + weighted stats of one chunk
    (``w`` is the instance-weight/pad-mask vector, 0.0 on pads)."""
    return combine_linear_stats(
        carry, linear_stats(x, y, w, precision=precision, policy=policy)
    )


def linear_fold_step(precision=DEFAULT_PRECISION, policy: str | None = None):
    """Cached jitted fold with the carry donated — the [n, n] normal-equation
    accumulator updates in place and the dispatch returns before the device
    fold completes (ops.linalg.gram_fold_step rationale). ``policy=None``
    resolves ``TPU_ML_PRECISION_POLICY`` before the cache lookup."""
    return _linear_fold_step(
        precision, resolve_policy(policy, allowed=FOLD_POLICIES)
    )


@lru_cache(maxsize=None)
def _linear_fold_step(precision, policy: str):
    def _step(carry, x, y, w):
        return fold_linear_stats(carry, x, y, w, precision=precision,
                                 policy=policy)

    return jax.jit(_step, donate_argnums=0)


def init_linear_carry(n: int, dtype) -> LinearStats:
    """Zero device-resident LinearStats carry for :func:`linear_fold_step`."""
    z = jnp.zeros
    return LinearStats(
        xtx=z((n, n), dtype),
        xty=z((n,), dtype),
        x_sum=z((n,), dtype),
        y_sum=z((), dtype),
        y_sq=z((), dtype),
        count=z((), dtype),
    )


def solve_normal(
    stats: LinearStats, *, reg_param: float = 0.0, fit_intercept: bool = True
) -> tuple[jax.Array, jax.Array]:
    """(coefficients [n], intercept []) from reduced statistics.

    With an intercept the normal equations are solved on centered moments
    (A = XᵀX − m·μμᵀ, b = Xᵀy − m·μȳ), which never penalizes the intercept;
    λ follows Spark ML's convention of scaling with the row count
    (regParam multiplies m so results match sklearn Ridge(alpha=λ·m)).
    """
    m = jnp.maximum(stats.count, jnp.ones_like(stats.count))
    n = stats.xtx.shape[0]
    lam = reg_param * m
    if fit_intercept:
        mu = stats.x_sum / m
        ybar = stats.y_sum / m
        a = stats.xtx - m * jnp.outer(mu, mu)
        b = stats.xty - m * mu * ybar
    else:
        a = stats.xtx
        b = stats.xty
    a = a + lam * jnp.eye(n, dtype=a.dtype)
    coef = jax.scipy.linalg.solve(a, b, assume_a="pos")
    # Rank-deficient designs (constant/collinear columns, λ=0) break the
    # Cholesky path with NaNs; fall back to the min-norm lstsq solution.
    # The [n, n] solve is negligible next to the data pass, so computing
    # the fallback unconditionally keeps this jittable (no host branch).
    coef_lstsq = jnp.linalg.lstsq(a, b)[0]
    coef = jnp.where(jnp.all(jnp.isfinite(coef)), coef, coef_lstsq)
    intercept = (
        stats.y_sum / m - jnp.dot(stats.x_sum / m, coef)
        if fit_intercept
        else jnp.zeros((), coef.dtype)
    )
    return coef, intercept


def _soft_threshold(v: jax.Array, thresh) -> jax.Array:
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - thresh, 0.0)


def _power_lam_max(a: jax.Array) -> jax.Array:
    """λmax estimate of PSD ``a`` via power iteration.

    FISTA's step 1/L is only covered by the convergence guarantee when the
    L estimate is ≥ λmax_true, and 32 fixed iterations can sit slightly
    below it when the spectral gap is small. Defenses, in order: use
    ‖a·v‖ of the final unit iterate (≥ the Rayleigh quotient, still ≤
    λmax), inflate by 5% (a marginally smaller step costs a few
    iterations; an underestimated L makes FISTA blow up silently), and
    clamp into the always-valid PSD envelope [trace/n, trace] — the lower
    edge catches a collapsed iteration (v0 ⊥ range(a), e.g.
    exactly-cancelling column pairs zero out a·1) by falling back to the
    trace upper bound, and the upper edge keeps the inflation from
    overshooting past a bound we know holds."""
    n = a.shape[0]

    def power_body(_, v):
        v = a @ v
        return v / jnp.maximum(jnp.linalg.norm(v), 1e-30)

    v0 = jnp.ones((n,), a.dtype) / jnp.sqrt(jnp.asarray(n, a.dtype))
    v = lax.fori_loop(0, 32, power_body, v0)
    norm_bound = jnp.linalg.norm(a @ v)
    tr = jnp.trace(a)
    est = 1.05 * norm_bound
    return jnp.where(est >= tr / n, jnp.minimum(est, tr), tr)


def _fista(grad, thresh, eta, w0, max_iter, tol):
    """Beck–Teboulle accelerated proximal gradient, tol-gated.

    Minimizes smooth(w) + ‖thresh/eta ⊙ w‖₁ given the smooth part's
    ``grad`` and step ``eta``; ``thresh`` is the per-coordinate (or
    scalar) soft-threshold ``eta·λ₁``. Stops when the relative coefficient
    change drops below ``tol`` or after ``max_iter`` iterations — one
    jittable ``lax.while_loop``.
    """

    def cond(carry):
        _, _, _, it, delta = carry
        return (it < max_iter) & (delta > tol)

    def body(carry):
        w, z, t, it, _ = carry
        w_new = _soft_threshold(z - eta * grad(z), thresh)
        t_new = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        z_new = w_new + ((t - 1.0) / t_new) * (w_new - w)
        delta = jnp.max(jnp.abs(w_new - w)) / jnp.maximum(
            jnp.max(jnp.abs(w_new)), 1e-12
        )
        return w_new, z_new, t_new, it + 1, delta

    init = (
        w0,
        w0,
        jnp.ones((), w0.dtype),
        jnp.int32(0),
        jnp.asarray(jnp.inf, w0.dtype),
    )
    w, _, _, _, _ = lax.while_loop(cond, body, init)
    return w


def solve_elastic_net(
    stats: LinearStats,
    *,
    reg_param: float,
    elastic_net_param: float,
    fit_intercept: bool = True,
    max_iter: int = 500,
    tol: float = 1e-8,
) -> tuple[jax.Array, jax.Array]:
    """(coefficients [n], intercept []) for the elastic-net objective, from
    the SAME reduced statistics as the closed-form path.

    Objective (Spark ML's convention, regParam=λ, elasticNetParam=α):

        1/(2m)·‖y − Xw − b₀‖² + λ·(α‖w‖₁ + (1−α)/2·‖w‖²)

    equivalently ``sklearn.linear_model.ElasticNet(alpha=λ, l1_ratio=α)``.
    (Contrast with :func:`solve_normal`'s pure-L2, where the repo matches
    ``Ridge(alpha=λ·m)`` — both are the Spark convention; Ridge's sklearn
    loss is unnormalized, ElasticNet's is 1/(2m)-normalized.)

    The L1 term has no closed form, but it does NOT need another data pass:
    the smooth gradient is (Aw − b)/m + λ(1−α)w with A/b the centered
    second moments already reduced over the cluster, so the whole FISTA
    loop (accelerated proximal gradient, Beck & Teboulle) runs replicated
    on the tiny [n, n] problem — one distributed statistics pass, zero
    per-iteration communication. The step size is 1/L with
    L = λmax(A)/m + λ(1−α) from a fixed power-iteration loop; everything is
    one jittable ``lax.while_loop`` (no data-dependent Python control flow).

    Not implemented in the reference family at all; pyspark.ml gets it via
    breeze OWL-QN over full data passes per iteration.
    """
    _check_alpha(elastic_net_param)
    m = jnp.maximum(stats.count, jnp.ones_like(stats.count))
    n = stats.xtx.shape[0]
    if fit_intercept:
        mu = stats.x_sum / m
        ybar = stats.y_sum / m
        a = stats.xtx - m * jnp.outer(mu, mu)
        b = stats.xty - m * mu * ybar
    else:
        a = stats.xtx
        b = stats.xty
    lam1 = reg_param * elastic_net_param
    lam2 = reg_param * (1.0 - elastic_net_param)

    # Lipschitz constant of the smooth part: λmax(A)/m + λ₂ (power
    # iteration with the PSD trace fallback — _power_lam_max).
    lip = _power_lam_max(a) / m + lam2
    eta = 1.0 / jnp.maximum(lip, 1e-30)

    def grad(w):
        return (a @ w - b) / m + lam2 * w

    w0 = jnp.zeros((n,), a.dtype)
    coef = _fista(grad, eta * lam1, eta, w0, max_iter, tol)
    intercept = (
        stats.y_sum / m - jnp.dot(stats.x_sum / m, coef)
        if fit_intercept
        else jnp.zeros((), coef.dtype)
    )
    return coef, intercept


def solve_from_stats(
    stats: LinearStats,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 500,
    tol: float = 1e-8,
) -> tuple[jax.Array, jax.Array]:
    """Dispatch the linear solve on the reduced statistics: closed-form
    normal equations for pure L2 (α=0), FISTA for any L1 mixture. Every
    data path (core partitions, Spark driver-merge, barrier mesh, in-core
    mesh) funnels through here, so elastic net works on all of them from
    the same one-pass monoid."""
    if elastic_net_param == 0.0:
        return solve_normal(
            stats, reg_param=reg_param, fit_intercept=fit_intercept
        )
    return solve_elastic_net(
        stats,
        reg_param=reg_param,
        elastic_net_param=elastic_net_param,
        fit_intercept=fit_intercept,
        max_iter=max_iter,
        tol=tol,
    )


def predict_linear(
    x: jax.Array, coef: jax.Array, intercept: jax.Array, *, precision=DEFAULT_PRECISION
) -> jax.Array:
    return jnp.matmul(x, coef, precision=precision) + intercept


# ---------------------------------------------------------------------------
# Logistic regression (IRLS / Newton)
# ---------------------------------------------------------------------------


class NewtonStats(NamedTuple):
    """One Newton iteration's sufficient statistics over a row shard."""

    hess: jax.Array  # [d, d] — XᵀWX, W = p(1−p)
    grad: jax.Array  # [d]   — Xᵀ(y − p)
    loss: jax.Array  # []    — Σ log-loss
    count: jax.Array  # []


def combine_newton_stats(a: NewtonStats, b: NewtonStats) -> NewtonStats:
    return NewtonStats(*(av + bv for av, bv in zip(a, b)))


def logistic_newton_stats(
    x_aug: jax.Array,
    y: jax.Array,
    w_full: jax.Array,
    weights: jax.Array | None = None,
    *,
    precision=DEFAULT_PRECISION,
) -> NewtonStats:
    """Local gradient/Hessian/log-loss at ``w_full`` over an augmented shard.

    ``x_aug`` is [rows, d] with the intercept column appended (d = n+1 when
    fitting an intercept); ``w_full`` is the full [d] parameter vector.
    """
    z = jnp.matmul(x_aug, w_full, precision=precision)
    p = jax.nn.sigmoid(z)
    mask = (
        weights
        if weights is not None
        else jnp.ones(x_aug.shape[0], x_aug.dtype)
    )
    resid = (y - p) * mask
    w = p * (1.0 - p) * mask
    # log-loss via logaddexp for stability: log(1+e^z) − y·z
    loss = jnp.sum((jnp.logaddexp(0.0, z) - y * z) * mask)
    hess = jnp.matmul(x_aug.T * w[None, :], x_aug, precision=precision)
    grad = jnp.matmul(x_aug.T, resid, precision=precision)
    return NewtonStats(
        hess=hess,
        grad=grad,
        loss=loss,
        count=jnp.sum(mask),
    )


def svc_newton_stats(
    x_aug: jax.Array,
    y: jax.Array,
    w_full: jax.Array,
    weights: jax.Array | None = None,
    *,
    precision=DEFAULT_PRECISION,
) -> NewtonStats:
    """Squared-hinge (L2-SVM) Newton statistics over an augmented shard —
    the LinearSVC loss (cuML/sklearn's default; pyspark.ml's LinearSVC
    minimizes the non-smooth plain hinge with OWLQN, but the squared hinge
    is smooth, so the SAME IRLS/Newton machinery as logistic applies and
    converges in a handful of data passes).

    Labels arrive 0/1 (the Spark label contract) and map to ±1. With
    margin mᵢ = 1 − ŷᵢ·zᵢ and the active set mᵢ > 0:

        loss  = Σ cᵢ·mᵢ²                       (active)
        grad  = Σ 2cᵢ·ŷᵢ·mᵢ·xᵢ                 (ascent of −loss, active)
        hess  = Σ 2cᵢ·xᵢxᵢᵀ                    (active)

    — the same NewtonStats monoid as logistic, so every reducer
    (tree-aggregate, mesh psum, chunked checkpoints) applies unchanged.
    """
    z = jnp.matmul(x_aug, w_full, precision=precision)
    yy = 2.0 * y - 1.0
    c = (
        weights
        if weights is not None
        else jnp.ones(x_aug.shape[0], x_aug.dtype)
    )
    margin = jnp.maximum(1.0 - yy * z, 0.0)
    wa = 2.0 * c * (margin > 0)
    hess = jnp.matmul(x_aug.T * wa[None, :], x_aug, precision=precision)
    grad = jnp.matmul(x_aug.T, 2.0 * c * yy * margin, precision=precision)
    loss = jnp.sum(c * margin * margin)
    return NewtonStats(hess=hess, grad=grad, loss=loss, count=jnp.sum(c))


def _check_alpha(elastic_net_param: float) -> None:
    if not 0.0 <= elastic_net_param <= 1.0:
        raise ValueError(
            f"elastic_net_param must be in [0, 1], got {elastic_net_param}"
        )


def _regularized_newton_solve(
    w: jax.Array,
    hess: jax.Array,
    grad: jax.Array,
    pen: jax.Array,
    m: jax.Array,
    reg_param: float,
    elastic_net_param: float,
) -> tuple[jax.Array, jax.Array]:
    """Shared Newton-step tail for the binary AND softmax paths: closed-form
    solve at α=0, warm-started FISTA prox step otherwise. ``hess``/``grad``
    arrive with the L2 fold and the eps ridge already applied; ``grad`` is
    the ASCENT direction of the smooth model.

    Divergence guard: an unregularized fit on linearly separable data has
    no finite maximizer — the iterates grow until z=x·w overflows and the
    solve turns NaN. A non-finite proposal is rejected in favor of the
    incoming iterate, with the step-norm set to **NaN as a sentinel**:
    ``NaN > tol`` is False, so every tol-gated while_loop exits at the last
    finite iterate (the same "big finite weights, no error" outcome
    Spark's LBFGS gives separable data) — and the host can distinguish the
    outcome from a clean converge (:func:`check_newton_outcome` raises when
    the rejection happened on the very first step from the zero init, which
    means the DATA carried non-finite values, not that the fit diverged)."""
    if elastic_net_param == 0.0:
        delta = jax.scipy.linalg.solve(hess, grad, assume_a="pos")
        new_w, step = w + delta, jnp.linalg.norm(delta)
    else:
        lam1 = reg_param * elastic_net_param * m
        eta = 1.0 / jnp.maximum(_power_lam_max(hess), 1e-30)

        def sub_grad(z):
            return hess @ (z - w) - grad

        new_w = _fista(sub_grad, eta * lam1 * pen, eta, w, 200, 1e-10)
        step = jnp.linalg.norm(new_w - w)
    ok = jnp.isfinite(step) & jnp.all(jnp.isfinite(new_w))
    nan = jnp.asarray(jnp.nan, step.dtype)
    return jnp.where(ok, new_w, w), jnp.where(ok, step, nan)


def check_newton_outcome(step_norm, w) -> None:
    """Host-side decode of the Newton loops' final (step, w).

    NaN step + all-zero parameters means the FIRST step from the zero init
    was already non-finite — the input data contains NaN/Inf (a zero
    gradient at init would have produced step 0, not NaN) — so raise a
    diagnosable error instead of returning an all-zero model that silently
    predicts one class everywhere. NaN step with nonzero parameters is the
    separable-divergence outcome: the model holds the last finite iterate,
    which is the accepted behavior (see _regularized_newton_solve)."""
    import numpy as np

    if np.isnan(float(np.asarray(step_norm))) and not np.asarray(w).any():
        raise ValueError(
            "the first Newton step produced non-finite statistics from the "
            "zero initialization — the features, labels, or instance "
            "weights contain NaN/Inf values; clean or impute them before "
            "fit"
        )


def newton_update(
    w_full: jax.Array,
    stats: NewtonStats,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One Newton / proximal-Newton step: (new w, step-norm).

    Regularization follows the LinearRegression convention (Spark ML's):
    λ=regParam, α=elasticNetParam, objective

        (1/m)·Σ logloss + λ·(α‖w‖₁ + (1−α)/2·‖w‖²)

    with the intercept coordinate (last, when ``fit_intercept``) exempt
    from both penalties. α=0 is the exact closed-form IRLS step. α>0 is a
    **proximal Newton** step (Lee/Sun/Saunders): the L1 term has no
    closed-form solve, so the step minimizes the local quadratic model +
    L1 via FISTA on the replicated [d, d] Hessian — the distributed part
    of an iteration (the NewtonStats psum) is UNCHANGED, so L1 logistic
    costs the same communication per iteration as L2.
    """
    _check_alpha(elastic_net_param)
    d = w_full.shape[0]
    m = jnp.maximum(stats.count, jnp.ones_like(stats.count))
    pen = jnp.ones((d,), w_full.dtype)
    if fit_intercept:
        pen = pen.at[-1].set(0.0)
    lam2 = reg_param * (1.0 - elastic_net_param) * m * pen
    hess = stats.hess + jnp.diag(lam2)
    grad = stats.grad - lam2 * w_full  # ascent direction of the smooth part
    # √eps-scaled ridge keeps the solve well-posed when classes separate
    # perfectly, sized to the dtype so f32 rounding can't flip the Cholesky
    # (√eps(f64) ≈ 1.5e-8 — f64 behavior unchanged)
    eps = jnp.sqrt(jnp.finfo(hess.dtype).eps) * jnp.trace(hess) / d
    hess = hess + eps * jnp.eye(d, dtype=hess.dtype)
    return _regularized_newton_solve(
        w_full, hess, grad, pen, m, reg_param, elastic_net_param
    )


def predict_logistic_proba(
    x: jax.Array, coef: jax.Array, intercept: jax.Array, *, precision=DEFAULT_PRECISION
) -> jax.Array:
    return jax.nn.sigmoid(
        jnp.matmul(x, coef, precision=precision) + intercept
    )


# ---------------------------------------------------------------------------
# Multinomial (softmax) logistic regression — full-Newton IRLS
# ---------------------------------------------------------------------------


class SoftmaxStats(NamedTuple):
    """One softmax-Newton iteration's statistics over a row shard.

    The Hessian is the full [C·d, C·d] Fisher information — C(C+1)/2
    distinct [d, d] blocks H[c,c'] = Xᵀ diag(w·p_c(δ_cc' − p_c')) X, each one
    MXU matmul. C·d stays modest for classical multiclass problems (e.g.
    C=10, d=513 → 5130² ≈ 26M entries), and the full Newton keeps the
    quadratic convergence the binary path has.
    """

    hess: jax.Array  # [C·d, C·d]
    grad: jax.Array  # [C·d] — flattened [C, d]
    loss: jax.Array  # []
    count: jax.Array  # []


def combine_softmax_stats(a: SoftmaxStats, b: SoftmaxStats) -> SoftmaxStats:
    return SoftmaxStats(*(av + bv for av, bv in zip(a, b)))


def softmax_newton_stats(
    x_aug: jax.Array,
    y_idx: jax.Array,
    w_flat: jax.Array,
    n_classes: int,
    weights: jax.Array | None = None,
    *,
    precision=DEFAULT_PRECISION,
) -> SoftmaxStats:
    """Gradient/Hessian/NLL of the softmax model at ``w_flat`` over a shard.

    ``x_aug`` [rows, d] (intercept column appended when fitting one),
    ``y_idx`` [rows] integer class labels in [0, C), ``w_flat`` [C·d].
    """
    rows, d = x_aug.shape
    c = n_classes
    w = w_flat.reshape(c, d)
    mask = (
        weights if weights is not None else jnp.ones(rows, x_aug.dtype)
    )
    logits = jnp.matmul(x_aug, w.T, precision=precision)  # [rows, C]
    logz = jax.scipy.special.logsumexp(logits, axis=1)
    p = jnp.exp(logits - logz[:, None])  # [rows, C]
    onehot = jax.nn.one_hot(y_idx, c, dtype=x_aug.dtype)
    loss = jnp.sum((logz - jnp.sum(onehot * logits, axis=1)) * mask)
    resid = (onehot - p) * mask[:, None]  # [rows, C]
    grad = jnp.matmul(resid.T, x_aug, precision=precision).reshape(-1)

    # Hessian blocks, upper triangle: H[c,c'] = Xᵀ diag(v_cc') X with
    # v_cc' = w·p_c(δ − p_c'). The pair loop unrolls at trace time —
    # C(C+1)/2 MXU matmuls.
    blocks = [[None] * c for _ in range(c)]
    for ci in range(c):
        for cj in range(ci, c):
            delta = 1.0 if ci == cj else 0.0
            v = mask * p[:, ci] * (delta - p[:, cj])
            blk = jnp.matmul(x_aug.T * v[None, :], x_aug, precision=precision)
            blocks[ci][cj] = blk
            if ci != cj:
                blocks[cj][ci] = blk.T
    hess = jnp.block(blocks)
    return SoftmaxStats(
        hess=hess,
        grad=grad,
        loss=loss,
        count=jnp.sum(mask),
    )


def softmax_newton_update(
    w_flat: jax.Array,
    stats: SoftmaxStats,
    n_classes: int,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One Newton / proximal-Newton step on the flattened [C·d] parameter.

    L2 penalizes every coordinate except the per-class intercepts. The
    softmax parameterization has a flat direction (adding any vector to all
    classes leaves p unchanged); the L2 penalty pins the coefficients and the
    eps ridge pins the unpenalized intercept-shift direction — gradients are
    zero along it, so the regularized solve simply doesn't move there.
    α>0 swaps the closed-form solve for the same warm-started FISTA
    subproblem as the binary :func:`newton_update` — the per-class-coordinate
    L1 prox is the elementwise soft-threshold on the flat vector, so nothing
    about the C-class block structure changes. (With α=1 the L1 term alone
    does NOT pin the flat direction, but the prox is applied to a Newton
    model whose Hessian carries the eps ridge, and FISTA is warm-started at
    the current w — the step stays well-posed the same way the L2 path's
    ridge-only intercept direction does.)
    """
    _check_alpha(elastic_net_param)
    cd = w_flat.shape[0]
    d = cd // n_classes
    m = jnp.maximum(stats.count, jnp.ones_like(stats.count))
    pen = jnp.ones((n_classes, d), w_flat.dtype)
    if fit_intercept:
        pen = pen.at[:, -1].set(0.0)
    pen = pen.reshape(-1)
    lam2 = reg_param * (1.0 - elastic_net_param) * m * pen
    hess = stats.hess + jnp.diag(lam2)
    grad = stats.grad - lam2 * w_flat
    # √eps-scaled ridge: the exact Fisher matrix is PSD with a ZERO
    # eigenvalue along the class-shift flat direction, and dtype rounding
    # makes it slightly indefinite (measured ~-5e-5 in f32) — a fixed 1e-8
    # ridge NaNs the f32 Cholesky on the first step. √eps(f64) ≈ 1.5e-8, so
    # f64 behavior is unchanged.
    eps = jnp.sqrt(jnp.finfo(hess.dtype).eps) * jnp.trace(hess) / cd
    hess = hess + eps * jnp.eye(cd, dtype=hess.dtype)
    return _regularized_newton_solve(
        w_flat, hess, grad, pen, m, reg_param, elastic_net_param
    )


def predict_softmax_proba(
    x: jax.Array,
    coef: jax.Array,
    intercept: jax.Array,
    *,
    precision=DEFAULT_PRECISION,
) -> jax.Array:
    """[rows, C] class probabilities; ``coef`` [C, n], ``intercept`` [C]."""
    logits = jnp.matmul(x, coef.T, precision=precision) + intercept[None, :]
    return jax.nn.softmax(logits, axis=1)
