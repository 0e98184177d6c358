"""Sharded GLM training — normal equations and Newton steps as SPMD programs.

Same architecture as ``parallel.gram``/``parallel.kmeans``: the statistics
monoid is computed per device shard and psum-combined over the ``data``
axis; the small solve happens replicated. For LinearRegression the whole fit
is ONE XLA program; for LogisticRegression each Newton iteration is one.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import linear as LIN
from spark_rapids_ml_tpu.parallel.backend import mapreduce_data_axis
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


@lru_cache(maxsize=None)
def _linear_stats_prog(mesh: Mesh):
    return jax.jit(
        mapreduce_data_axis(
            LIN.linear_stats,
            mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
        )
    )


def sharded_linear_stats(
    x: jax.Array, y: jax.Array, mesh: Mesh
) -> LIN.LinearStats:
    """LinearStats over data-sharded (X [rows, n], y [rows]); replicated out."""
    return _linear_stats_prog(mesh)(x, y)


def distributed_linreg_fit(
    x: jax.Array,
    y: jax.Array,
    mesh: Mesh,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 500,
    tol: float = 1e-8,
) -> tuple[jax.Array, jax.Array]:
    """Full distributed least-squares / elastic-net fit: (coef, intercept).

    The statistics pass is the sharded psum either way; α>0 only changes
    the replicated solve (FISTA on the reduced moments, honoring
    ``max_iter``/``tol`` like the host paths) — no extra collectives, no
    extra data passes.
    """
    stats = sharded_linear_stats(x, y, mesh)
    return LIN.solve_from_stats(
        stats,
        reg_param=reg_param,
        elastic_net_param=elastic_net_param,
        fit_intercept=fit_intercept,
        max_iter=max_iter,
        tol=tol,
    )


@lru_cache(maxsize=32)
def make_distributed_linreg_fit(
    mesh: Mesh,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 500,
    tol: float = 1e-8,
):
    """jit with shardings bound: X/y data-sharded, outputs replicated."""
    return jax.jit(
        partial(
            distributed_linreg_fit,
            mesh=mesh,
            reg_param=reg_param,
            elastic_net_param=elastic_net_param,
            fit_intercept=fit_intercept,
            max_iter=max_iter,
            tol=tol,
        ),
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


@lru_cache(maxsize=None)
def _linear_stats_weighted_prog(mesh: Mesh):
    return jax.jit(
        mapreduce_data_axis(
            LIN.linear_stats,
            mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS)),
        )
    )


def sharded_linear_stats_weighted(
    x: jax.Array, y: jax.Array, w: jax.Array, mesh: Mesh
) -> LIN.LinearStats:
    """Weighted LinearStats over data-sharded operands — ``w`` carries
    instance weights on true rows and 0.0 on pad rows (the framework-wide
    masking convention), so padded shards reduce exactly."""
    return _linear_stats_weighted_prog(mesh)(x, y, w)


@lru_cache(maxsize=None)
def _newton_stats_prog(mesh: Mesh):
    return jax.jit(
        mapreduce_data_axis(
            LIN.logistic_newton_stats,
            mesh,
            in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
        )
    )


def sharded_newton_stats(
    x_aug: jax.Array, y: jax.Array, w_full: jax.Array, mesh: Mesh
) -> LIN.NewtonStats:
    """One logistic Newton statistics pass: X/y data-sharded, w replicated."""
    return _newton_stats_prog(mesh)(x_aug, y, w_full)


def distributed_newton_step(
    x_aug: jax.Array,
    y: jax.Array,
    w_full: jax.Array,
    mesh: Mesh,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """One full distributed IRLS / proximal-Newton iteration."""
    stats = sharded_newton_stats(x_aug, y, w_full, mesh)
    return LIN.newton_update(
        w_full,
        stats,
        reg_param=reg_param,
        elastic_net_param=elastic_net_param,
        fit_intercept=fit_intercept,
    )


@lru_cache(maxsize=32)
def make_distributed_newton_step(
    mesh: Mesh,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
):
    return jax.jit(
        partial(
            distributed_newton_step,
            mesh=mesh,
            reg_param=reg_param,
            elastic_net_param=elastic_net_param,
            fit_intercept=fit_intercept,
        ),
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


@lru_cache(maxsize=32)
def make_distributed_logreg_fit(
    mesh: Mesh,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 25,
    tol: float = 1e-6,
    loss: str = "logistic",
):
    """The ENTIRE binary IRLS training loop as ONE XLA program over the mesh.

    ``lax.while_loop`` runs inside ``shard_map``: each iteration computes the
    local NewtonStats on the device's row shard, one ``psum`` combines them,
    and the replicated [d, d] solve updates the carried parameter — no host
    round-trip anywhere in training (the per-step variant above exists for
    hosts that need to checkpoint between iterations). Inputs: ``x_aug``
    [rows, d] data-sharded WITH the intercept column already appended when
    ``fit_intercept``; ``y`` and the pad/instance-weight vector ``w`` sharded
    alike. Returns replicated (w_full [d], iterations, final step-norm).

    Implemented as ONE full-budget chunk of
    :func:`make_distributed_logreg_chunk` from the zero init — the
    per-iteration body exists in exactly one place, so the chunked-resume
    trajectory is the whole-loop trajectory by construction.
    """
    import jax.numpy as jnp

    chunk = make_distributed_logreg_chunk(
        mesh,
        reg_param=reg_param,
        elastic_net_param=elastic_net_param,
        fit_intercept=fit_intercept,
        chunk_iters=max_iter,
        tol=tol,
        loss=loss,
    )

    def fit(x_aug, y, w_vec):
        w0 = jnp.zeros((x_aug.shape[1],), x_aug.dtype)
        return chunk(x_aug, y, w_vec, w0, jnp.int32(max_iter))

    return fit


@lru_cache(maxsize=32)
def make_distributed_softmax_fit(
    mesh: Mesh,
    n_classes: int,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    max_iter: int = 25,
    tol: float = 1e-6,
):
    """The ENTIRE multinomial (softmax) IRLS loop as ONE XLA program — the
    C-class sibling of ``make_distributed_logreg_fit``: each iteration
    psums the SoftmaxStats monoid (full [C·d, C·d] Fisher Hessian as
    C(C+1)/2 MXU block matmuls per shard) and solves replicated. ``y``
    arrives as the float label vector (sharded like x) and is cast to class
    indices in-program. Returns replicated (w_flat [C·d], iterations,
    final step-norm). One full-budget chunk of
    :func:`make_distributed_softmax_chunk` (single copy of the body)."""
    import jax.numpy as jnp

    chunk = make_distributed_softmax_chunk(
        mesh,
        n_classes,
        reg_param=reg_param,
        elastic_net_param=elastic_net_param,
        fit_intercept=fit_intercept,
        chunk_iters=max_iter,
        tol=tol,
    )

    def fit(x_aug, y, w_vec):
        w0 = jnp.zeros((n_classes * x_aug.shape[1],), x_aug.dtype)
        return chunk(x_aug, y, w_vec, w0, jnp.int32(max_iter))

    return fit


@lru_cache(maxsize=32)
def make_distributed_logreg_chunk(
    mesh: Mesh,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    chunk_iters: int = 5,
    tol: float = 1e-6,
    loss: str = "logistic",
):
    """Up to ``chunk_iters`` binary-Newton iterations from a CARRIED
    parameter vector — the resumable building block of the chunked-
    checkpoint mesh fit (r3 verdict #6: a preempted whole-loop pod fit
    restarts from zero; K-iteration chunks with a host checkpoint between
    them bound the loss while keeping driver round-trips 1-per-K).

    ``run(x_aug, y, w_vec, w0, budget) -> (w, done, step)``: identical
    per-iteration body to :func:`make_distributed_logreg_fit`, but the loop
    starts at ``w0`` and stops at ``min(chunk_iters, budget)`` — ``budget``
    (remaining GLOBAL iterations) is a traced scalar, so the final short
    chunk reuses the same compiled program. ``done`` < chunk_iters means
    converged (or budget exhausted); ``step`` carries the NaN divergence
    sentinel exactly like the whole-loop program.

    ``loss`` selects the per-iteration statistics: ``"logistic"`` (IRLS)
    or ``"squared_hinge"`` (LinearSVC) — both produce the same NewtonStats
    monoid, so the loop/psum/solve body is literally shared.
    """
    import jax.numpy as jnp
    from jax import lax


    if loss not in ("logistic", "squared_hinge"):
        raise ValueError(f"loss must be 'logistic' or 'squared_hinge', got {loss!r}")
    stats_fn = (
        LIN.logistic_newton_stats
        if loss == "logistic"
        else LIN.svc_newton_stats
    )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def _newton(x_aug, y, w_vec, w0, budget):
        limit = jnp.minimum(jnp.int32(chunk_iters), budget.astype(jnp.int32))

        def cond(carry):
            _, it, step = carry
            return (it < limit) & (step > tol)

        def body(carry):
            w_full, it, _ = carry
            stats = stats_fn(x_aug, y, w_full, w_vec)
            stats = jax.tree.map(lambda v: lax.psum(v, DATA_AXIS), stats)
            new_w, step = LIN.newton_update(
                w_full, stats,
                reg_param=reg_param,
                elastic_net_param=elastic_net_param,
                fit_intercept=fit_intercept,
            )
            return new_w, it + 1, step

        init = (w0, jnp.int32(0), jnp.asarray(jnp.inf, x_aug.dtype))
        return lax.while_loop(cond, body, init)

    # a private function's name is the program's in a device trace
    # (``jit__newton``): benchmarks/layer_metrics/newton_roofline.json reads
    # it, tests/test_logreg_resident.py pins it
    return jax.jit(
        _newton,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
        # run_chunked_newton rebinds w to this chunk's output; the carried
        # weights are dead after dispatch — donate their buffer
        donate_argnums=3,
    )


@lru_cache(maxsize=32)
def make_distributed_softmax_chunk(
    mesh: Mesh,
    n_classes: int,
    *,
    reg_param: float = 0.0,
    elastic_net_param: float = 0.0,
    fit_intercept: bool = True,
    chunk_iters: int = 5,
    tol: float = 1e-6,
):
    """C-class sibling of :func:`make_distributed_logreg_chunk`:
    ``run(x_aug, y, w_vec, w0_flat, budget) -> (w_flat, done, step)``."""
    import jax.numpy as jnp
    from jax import lax


    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )
    def run(x_aug, y, w_vec, w0, budget):
        y_idx = y.astype(jnp.int32)
        limit = jnp.minimum(jnp.int32(chunk_iters), budget.astype(jnp.int32))

        def cond(carry):
            _, it, step = carry
            return (it < limit) & (step > tol)

        def body(carry):
            w_flat, it, _ = carry
            stats = LIN.softmax_newton_stats(
                x_aug, y_idx, w_flat, n_classes, w_vec
            )
            stats = jax.tree.map(lambda v: lax.psum(v, DATA_AXIS), stats)
            new_w, step = LIN.softmax_newton_update(
                w_flat, stats, n_classes,
                elastic_net_param=elastic_net_param,
                reg_param=reg_param, fit_intercept=fit_intercept,
            )
            return new_w, it + 1, step

        init = (w0, jnp.int32(0), jnp.asarray(jnp.inf, x_aug.dtype))
        return lax.while_loop(cond, body, init)

    return jax.jit(
        run,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
        # same contract as the logreg chunk: the carried flat weights are
        # rebound by run_chunked_newton, so donate their buffer
        donate_argnums=3,
    )


def run_chunked_newton(
    chunk_fn, x, y, w_vec, w0, *, start_iter, max_iter, tol, ckpt
):
    """THE host loop for chunked-checkpoint Newton fits — shared by the
    mesh-local estimator paths and both barrier FitFns so the subtle parts
    (budget arithmetic, the NaN-sentinel stop test, save-index convention)
    exist once. ``ckpt`` is a TrainingCheckpointer or None (barrier ranks
    other than 0 pass None but still run the identical loop, keeping the
    replicated carry and stop decision group-consistent).

    Returns (w [replicated device array], iterations_completed).
    """
    import jax.numpy as jnp
    import numpy as np

    w = jnp.asarray(w0)
    it = start_iter
    while it < max_iter:
        w, done, step = chunk_fn(x, y, w_vec, w, jnp.int32(max_iter - it))
        it += int(done)
        stop = not float(step) > tol  # NaN-sentinel stops too (step is NaN)
        if stop:
            # BEFORE the save: NaN-input rejection must not leave a junk
            # zeros checkpoint that a post-cleanup re-fit would silently
            # resume from one iteration in
            LIN.check_newton_outcome(step, w)
        if ckpt is not None:
            ckpt.save(it - 1, {"w": np.asarray(w)}, {})
        if stop:
            break
    return w, it
