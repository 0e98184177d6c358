"""Sharded KMeans — Lloyd iterations as SPMD mesh programs.

Same shape as ``parallel.gram``: each device runs the MXU Lloyd kernels on
its row shard, a psum over the ``data`` axis combines the KMeansStats
monoid, and the centroid update happens replicated — one XLA program per
iteration, collectives on ICI, no host round-trip for the reduction.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import kmeans as KM
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS


@lru_cache(maxsize=None)
def _kmeans_stats_prog(mesh: Mesh, block_rows: int):
    from spark_rapids_ml_tpu.parallel.backend import mapreduce_data_axis

    return jax.jit(
        mapreduce_data_axis(
            lambda xl, c: KM.kmeans_stats(
                xl, c, block_rows=min(block_rows, xl.shape[0])
            ),
            mesh,
            replicated_args=1,
        )
    )


def sharded_kmeans_stats(
    x: jax.Array,
    centers: jax.Array,
    mesh: Mesh,
    *,
    block_rows: int = 8192,
) -> KM.KMeansStats:
    """One Lloyd accumulation pass over a data-sharded [rows, n] X; centers
    replicated; replicated stats out. Compiled once per (mesh, block_rows) —
    the estimator loop calls this every iteration."""
    return _kmeans_stats_prog(mesh, block_rows)(x, centers)


def distributed_lloyd_step(
    x: jax.Array, centers: jax.Array, mesh: Mesh
) -> tuple[jax.Array, jax.Array]:
    """One full distributed Lloyd iteration: (new_centers, cost)."""
    stats = sharded_kmeans_stats(x, centers, mesh)
    return KM.update_centers(stats, centers), stats.cost


@lru_cache(maxsize=None)
def make_distributed_lloyd(mesh: Mesh):
    """jit the Lloyd step with shardings bound: X data-sharded, centers and
    outputs replicated."""
    return jax.jit(
        partial(distributed_lloyd_step, mesh=mesh),
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


@lru_cache(maxsize=32)
def make_distributed_kmeans_fit(
    mesh: Mesh, *, max_iter: int = 20, tol: float = 1e-4, block_rows: int = 8192
):
    """The ENTIRE Lloyd training loop as ONE XLA program over the mesh.

    ``lax.while_loop`` inside ``shard_map``: each iteration accumulates the
    device-local KMeansStats (weighted; the weight vector masks pad rows),
    one ``psum`` combines them, and the replicated centroid update advances
    the carry — zero host round-trips in training. Convergence matches the
    per-step estimator loop: stop when max squared centroid movement ≤ tol²
    or after ``max_iter`` iterations. Inputs: X [rows, n] and weights [rows]
    data-sharded, initial centers [k, n] replicated. Returns replicated
    (centers, cost, iterations). One full-budget chunk of
    :func:`make_distributed_kmeans_chunk` (single copy of the Lloyd body).
    """
    import jax.numpy as jnp

    chunk = make_distributed_kmeans_chunk(
        mesh, chunk_iters=max_iter, tol=tol, block_rows=block_rows
    )

    def fit(x, w, centers0):
        centers, cost, done, _ = chunk(x, w, centers0, jnp.int32(max_iter))
        return centers, cost, done

    return fit


@lru_cache(maxsize=32)
def make_distributed_kmeans_chunk(
    mesh: Mesh, *, chunk_iters: int = 5, tol: float = 1e-4, block_rows: int = 8192
):
    """Up to ``chunk_iters`` Lloyd iterations from CARRIED centers — the
    resumable building block of the chunked-checkpoint mesh fit (see
    parallel.linear.make_distributed_logreg_chunk for the rationale).

    ``run(x, w, centers0, budget) -> (centers, cost, done, shift_sq)``:
    same per-iteration body as :func:`make_distributed_kmeans_fit`; the
    host loop stops when ``shift_sq <= tol²`` or the global budget runs
    out, checkpointing centers between chunks.
    """
    import jax.numpy as jnp
    from jax import lax


    tol_sq = tol * tol

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
    def _lloyd(x, w, centers0, budget):
        limit = jnp.minimum(jnp.int32(chunk_iters), budget.astype(jnp.int32))

        def cond(carry):
            _, _, it, shift = carry
            return (it < limit) & (shift > tol_sq)

        def body(carry):
            centers, _, it, _ = carry
            stats = KM.kmeans_stats(
                x, centers, w, block_rows=min(block_rows, x.shape[0])
            )
            stats = jax.tree.map(lambda v: lax.psum(v, DATA_AXIS), stats)
            new_centers = KM.update_centers(stats, centers)
            shift = KM.center_shift_sq(centers, new_centers)
            return new_centers, stats.cost, it + 1, shift

        init = (
            centers0,
            jnp.asarray(jnp.inf, x.dtype),
            jnp.int32(0),
            jnp.asarray(jnp.inf, x.dtype),
        )
        return lax.while_loop(cond, body, init)

    # a private function's name is the program's in a device trace
    # (``jit__lloyd``): benchmarks/layer_metrics/lloyd_roofline.json reads it
    return jax.jit(
        _lloyd,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
        # the host loop rebinds its centers to this chunk's output, so the
        # incoming carry is dead after dispatch — donate its buffer
        donate_argnums=2,
    )


@lru_cache(maxsize=32)
def make_distributed_kmeans_parallel_init(
    mesh: Mesh, k: int, *, init_steps: int = 2, block_rows: int = 8192
):
    """k-means‖ oversampling as ONE SPMD mesh program — no driver hops.

    The driver-pass implementation (models/kmeans.py
    ``_kmeans_parallel_init`` and its Spark-jobs sibling) runs each
    Bahmani round as host-orchestrated passes with candidates bouncing
    through the driver; this program keeps the whole init on the mesh:
    per round, every shard scores its rows by w·D² against the replicated
    candidate buffer (blocked MXU distances), draws a FIXED ``s`` rows per
    shard by Gumbel-top-s (sampling without replacement ∝ w·D² — the
    static-shape counterpart of Bahmani's Bernoulli draw with expectation
    ℓ=2k per round; XLA needs fixed shapes, and ndev·s ≥ 2k preserves the
    oversampling rate), and an ``all_gather`` over the data axis appends
    the round's candidates replicated. A final blocked assignment pass
    psums the instance-weighted ownership counts.

    Returns ``run(x, w, key) -> (candidates [cap, n], counts [cap])`` with
    ``cap = 1 + init_steps·ndev·s``; never-filled slots carry count 0, so
    :func:`ops.kmeans.weighted_kmeans_plus_plus_init` (which draws ∝
    count·D²) consumes the buffers directly for the k-reduction. ``w`` is
    the framework's pad-mask/instance-weight vector: zero-weight rows can
    never be sampled.
    """
    import jax.numpy as jnp
    from jax import lax


    ndev = mesh.shape[DATA_AXIS]
    s = max(1, -(-2 * k // ndev))  # ndev*s >= ell = 2k candidates per round
    cap = 1 + init_steps * ndev * s

    def _blocked(fn, init, x, w=None):
        """scan ``fn(carry, (x_block[, w_block]))`` over padded row blocks —
        the ONE copy of the block/pad arithmetic both passes share. Pad rows
        carry zero weight, so weighted consumers ignore them; unweighted
        consumers must slice their [rows]-shaped outputs themselves."""
        rows = x.shape[0]
        blk = min(block_rows, rows)
        nblk = -(-rows // blk)
        xp = jnp.pad(x, ((0, nblk * blk - rows), (0, 0)))
        xs = xp.reshape(nblk, blk, -1)
        if w is None:
            return lax.scan(fn, init, xs)
        wp = jnp.pad(w, (0, nblk * blk - rows))
        return lax.scan(fn, init, (xs, wp.reshape(nblk, blk)))

    def _masked_d2(xb, buf, valid):
        """[blk, cap] squared distances with invalid slots at +inf — a
        where-mask, not an additive penalty, so no data magnitude can
        defeat it."""
        d2 = KM.pairwise_sq_dists(xb, buf)
        return jnp.where(valid[None, :], d2, jnp.inf)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P()),
        out_specs=(P(), P()),
        check_vma=False,
    )
    def _kmeans_seed(x, w, key):
        me = lax.axis_index(DATA_AXIS)
        rows, n = x.shape
        s_eff = min(s, rows)  # static: shards are equal-size padded
        tiny = jnp.finfo(x.dtype).tiny

        # first candidate: weight-proportional over ALL rows via Gumbel-max
        # (local argmax per shard, replicated argmax across shards)
        k0 = jax.random.fold_in(jax.random.fold_in(key, 17), me)
        g0 = jax.random.gumbel(k0, (rows,), x.dtype)
        score0 = jnp.where(w > 0, jnp.log(jnp.maximum(w, tiny)) + g0, -jnp.inf)
        bi = jnp.argmax(score0)
        all_best = lax.all_gather(score0[bi], DATA_AXIS)
        winner = jnp.argmax(all_best)
        cand0 = lax.psum(
            jnp.where(winner == me, x[bi], jnp.zeros((n,), x.dtype)), DATA_AXIS
        )
        buf = jnp.zeros((cap, n), x.dtype).at[0].set(cand0)
        valid = jnp.zeros((cap,), jnp.bool_).at[0].set(True)

        for r in range(init_steps):

            def min_d2_step(_, xb, buf=buf, valid=valid):
                return None, jnp.min(_masked_d2(xb, buf, valid), axis=1)

            _, mins = _blocked(min_d2_step, None, x)
            d2 = mins.reshape(-1)[:rows]
            score = jnp.where(
                (w > 0) & (d2 > 0),
                jnp.log(jnp.maximum(w * d2, tiny)),
                -jnp.inf,
            )
            kr = jax.random.fold_in(jax.random.fold_in(key, 100 + r), me)
            score = score + jax.random.gumbel(kr, (rows,), x.dtype)
            top_vals, top_idx = lax.top_k(score, s_eff)
            picked = x[top_idx]                         # [s_eff, n]
            picked_ok = top_vals > -jnp.inf
            gathered = lax.all_gather(picked, DATA_AXIS)      # [ndev, s_eff, n]
            gathered_ok = lax.all_gather(picked_ok, DATA_AXIS)
            at = 1 + r * ndev * s_eff
            buf = lax.dynamic_update_slice(
                buf, gathered.reshape(ndev * s_eff, n), (at, 0)
            )
            valid = lax.dynamic_update_slice(
                valid, gathered_ok.reshape(-1), (at,)
            )

        # ownership counts: blocked argmin assignment; invalid slots sit at
        # +inf so they can never win, zero-weight/pad rows contribute nothing
        def count_step(counts, xw):
            xb, wb = xw
            lab = jnp.argmin(_masked_d2(xb, buf, valid), axis=1)
            return counts.at[lab].add(wb), None

        counts, _ = _blocked(count_step, jnp.zeros((cap,), x.dtype), x, w)
        counts = lax.psum(counts, DATA_AXIS)
        return buf, jnp.where(valid, counts, 0.0)

    # ``jit__kmeans_seed`` in a device trace, apart from ``jit__lloyd``
    return jax.jit(
        _kmeans_seed,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            NamedSharding(mesh, P()),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )


def run_chunked_lloyd(
    chunk_fn, x, w_vec, centers0, *, start_iter, max_iter, tol, ckpt,
    cost0=float("inf"),
):
    """THE host loop for chunked-checkpoint Lloyd fits (see
    parallel.linear.run_chunked_newton — same sharing rationale; ``ckpt``
    None on non-writing ranks). Returns (centers, cost, iterations)."""
    import jax.numpy as jnp
    import numpy as np

    c = jnp.asarray(centers0)
    it, cost, tol_sq = start_iter, cost0, tol * tol
    while it < max_iter:
        c, cost_j, done, shift = chunk_fn(
            x, w_vec, c, jnp.int32(max_iter - it)
        )
        it += int(done)
        cost = float(cost_j)
        if ckpt is not None:
            ckpt.save(it - 1, {"centers": np.asarray(c)}, {"cost": cost})
        if float(shift) <= tol_sq:
            break
    return c, cost, it
