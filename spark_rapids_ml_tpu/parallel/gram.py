"""Sharded Gram/covariance accumulation — the ICI-native reducer.

This replaces the reference's entire communication story for fit(): instead
of per-partition GPU Gram matrices reduced on the JVM heap through Spark's
shuffle (RapidsRowMatrix.scala:122-139), the whole pass is ONE SPMD XLA
program over the device mesh:

- data-parallel path: each device computes the Gram of its row shard on the
  MXU, then a single ``psum`` allreduce over the ``data`` axis rides ICI —
  no host hop, no serialization, overlappable by XLA.
- feature-sharded path: when n is too large for an [n, n] buffer per device
  (the reference's hard wall, RapidsRowMatrix.scala:50-52), columns are
  sharded too, and the Gram is built by a **ring exchange** over the ``feat``
  axis: at each of F steps a device multiplies its resident column block
  against the visiting block and passes the visitor along the ring
  (``ppermute``) — the same neighbor-exchange schedule as ring attention,
  applied to XᵀX. Compute at step t overlaps the transfer for step t+1.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.ops.policy import FOLD_POLICIES, resolve_policy
from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, FEAT_AXIS
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.spans import trace_range
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE


def _count_collectives(kind: str, n_ops: float, payload_bytes: float) -> None:
    """Book cross-device traffic into the registry (and the flight
    recorder, so collective dispatches appear on the fit timeline).
    Collectives live inside jitted programs, so the accounting happens here
    at the host call sites: ``n_ops`` launches moving ``payload_bytes`` per
    launch (logical payload, not the ICI wire schedule XLA actually
    picks)."""
    REGISTRY.counter_inc("collective.count", n_ops, kind=kind)
    REGISTRY.counter_inc("collective.bytes", n_ops * payload_bytes, kind=kind)
    TIMELINE.record_instant(
        "collective.dispatch",
        kind=kind,
        n_ops=n_ops,
        payload_bytes=int(n_ops * payload_bytes),
    )


@lru_cache(maxsize=None)
def _gram_stats_prog(mesh: Mesh, precision):
    from spark_rapids_ml_tpu.parallel.backend import mapreduce_data_axis

    return jax.jit(
        mapreduce_data_axis(
            lambda xl: L.gram_stats(xl, precision=precision), mesh
        )
    )


def sharded_gram_stats(
    x: jax.Array,
    mesh: Mesh,
    *,
    precision=L.DEFAULT_PRECISION,
) -> L.GramStats:
    """Data-parallel GramStats: local MXU Gram + psum allreduce over ICI.

    ``x`` is [rows, n] sharded along ``data``; the result is replicated.
    The compiled program is cached per (mesh, precision) so repeated fits
    (the DataFrame path calls this once per ``fit()``) reuse the executable
    instead of re-tracing a fresh closure each time.
    """
    n = x.shape[1]
    # one psum of GramStats: [n, n] gram + [n] col_sum + scalar count
    _count_collectives("psum", 1, (n * n + n + 1) * x.dtype.itemsize)
    return _gram_stats_prog(mesh, precision)(x)


@lru_cache(maxsize=None)
def _moment_stats_prog(mesh: Mesh):
    from spark_rapids_ml_tpu.ops import scaler as S
    from spark_rapids_ml_tpu.parallel.backend import mapreduce_data_axis

    return jax.jit(mapreduce_data_axis(S.moment_stats, mesh))


def sharded_moment_stats(x: jax.Array, mesh: Mesh):
    """Data-parallel StandardScaler moments: local sums + psum over ICI."""
    n = x.shape[1]
    _count_collectives("psum", 1, (2 * n + 1) * x.dtype.itemsize)
    return _moment_stats_prog(mesh)(x)


def ring_gram(
    x: jax.Array,
    mesh: Mesh,
    *,
    precision=L.DEFAULT_PRECISION,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Feature-sharded Gram via a ring over the ``feat`` axis.

    ``x`` is [rows, n] sharded (data, feat). Returns ``(gram, col_sum,
    count)`` with ``gram`` [n, n] sharded by block-row over ``feat`` and the
    small statistics replicated. Device j owns column block Xⱼ and produces
    Gram block-row G[jC:(j+1)C, :]; the visiting block walks the ring so step
    t computes XⱼᵀX₍ⱼ₊ₜ₎ — F·(C×C) MXU matmuls per device, F−1 neighbor
    transfers, zero host involvement.
    """
    n_feat = mesh.shape[FEAT_AXIS]
    rows_local = x.shape[0] // max(mesh.shape[DATA_AXIS], 1)
    c = x.shape[1] // max(n_feat, 1)
    item = x.dtype.itemsize
    # F ring steps each moving a [rows_local, c] visiting block ...
    _count_collectives("ppermute", n_feat, rows_local * c * item)
    # ... then the block-row psum, col_sum psum+all_gather, count psum
    _count_collectives("psum", 3, (c * (c * n_feat) + c + 1) * item)
    return _ring_gram_prog(mesh, precision)(x)


@lru_cache(maxsize=None)
def _ring_gram_prog(mesh: Mesh, precision):
    n_feat = mesh.shape[FEAT_AXIS]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(DATA_AXIS, FEAT_AXIS),
        out_specs=(P(FEAT_AXIS, None), P(None), P()),
        check_vma=False,
    )
    def _ring(xl):
        c = xl.shape[1]
        j = lax.axis_index(FEAT_AXIS)
        out = jnp.zeros((c, c * n_feat), xl.dtype)
        perm = [(i, (i - 1) % n_feat) for i in range(n_feat)]

        def body(t, carry):
            buf, out = carry
            src = (j + t) % n_feat  # origin of the visiting block
            block = jnp.matmul(xl.T, buf, precision=precision)
            col = (src * c).astype(jnp.int32)
            out = lax.dynamic_update_slice(out, block, (jnp.int32(0), col))
            buf = lax.ppermute(buf, FEAT_AXIS, perm)
            return buf, out

        _, out = lax.fori_loop(0, n_feat, body, (xl, out))
        out = lax.psum(out, DATA_AXIS)
        col_sum = lax.psum(jnp.sum(xl, axis=0), DATA_AXIS)
        col_sum = lax.all_gather(col_sum, FEAT_AXIS, tiled=True)
        count = lax.psum(
            jnp.asarray(xl.shape[0], xl.dtype),
            DATA_AXIS,
        )
        return out, col_sum, count

    return jax.jit(_ring)


def distributed_pca_fit(
    x: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    mean_centering: bool = False,
    feature_sharded: bool = False,
    solver: str = "full",
    precision=L.DEFAULT_PRECISION,
) -> tuple[jax.Array, jax.Array]:
    """The full distributed training step as one jittable SPMD program.

    Gram accumulation is sharded per the flags; the n×n decomposition
    (refined eigh, or randomized subspace iteration when ``solver`` says so)
    runs on the replicated covariance — XLA gathers the block-rows over ICI
    when the feature-sharded path produced them.
    """
    if feature_sharded:
        g, col_sum, count = ring_gram(x, mesh, precision=precision)
        stats = L.GramStats(g, col_sum, count)
    else:
        stats = sharded_gram_stats(x, mesh, precision=precision)
    cov = L.covariance_from_stats(stats, mean_centering=mean_centering)
    return L.pca_fit_from_cov(cov, k, solver=solver)


@lru_cache(maxsize=32)
def make_distributed_fit(
    mesh: Mesh,
    k: int,
    *,
    mean_centering: bool = False,
    feature_sharded: bool = False,
    solver: str = "full",
):
    """jit-compile ``distributed_pca_fit`` with mesh shardings bound.

    Inputs are constrained to the (data[, feat]) sharding; outputs are
    replicated (the model is small and every host needs it — same reason the
    reference collects U/S to the driver, RapidsRowMatrix.scala:86).
    Cached per argument tuple so repeated fits share one executable.
    """
    in_spec = P(DATA_AXIS, FEAT_AXIS) if feature_sharded else P(DATA_AXIS, None)
    return jax.jit(
        partial(
            distributed_pca_fit,
            k=k,
            mesh=mesh,
            mean_centering=mean_centering,
            feature_sharded=feature_sharded,
            solver=solver,
        ),
        in_shardings=NamedSharding(mesh, in_spec),
        out_shardings=NamedSharding(mesh, P()),
    )


@lru_cache(maxsize=None)
def _range_stats_prog(mesh: Mesh):
    from spark_rapids_ml_tpu.ops import scaler as S

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    def _run(xl, wl):
        # ws pad-mask convention: 0 on pad rows; ONE masking kernel shared
        # with the partition-task path (ops.scaler.range_stats)
        local = S.range_stats(xl, valid=wl > 0)
        return S.RangeStats(
            count=lax.psum(local.count, DATA_AXIS),
            min=lax.pmin(local.min, DATA_AXIS),
            max=lax.pmax(local.max, DATA_AXIS),
            max_abs=lax.pmax(local.max_abs, DATA_AXIS),
        )

    return jax.jit(_run)


def sharded_range_stats(x: jax.Array, w: jax.Array, mesh: Mesh):
    """Data-parallel per-feature min/max/max-|x| over the mesh — the
    MinMax/MaxAbs/Robust/QuantileDiscretizer statistic: local masked
    reductions, then pmin/pmax (the family's one non-additive fold) over
    ICI. ``w`` is the ingest pad mask (0 on pad rows)."""
    # psum(count) + pmin + 2×pmax, each over an [n]-ish vector
    _count_collectives("preduce", 4, x.shape[1] * x.dtype.itemsize)
    return _range_stats_prog(mesh)(x, w)


@lru_cache(maxsize=None)
def _histogram_prog(mesh: Mesh, bins: int):
    from spark_rapids_ml_tpu.ops import scaler as S

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(), P()),
        out_specs=P(),
        check_vma=False,
    )
    def _run(xl, wl, mins, maxs):
        hist = S.histogram_stats(
            xl,
            jnp.asarray(xl.shape[0]),  # row mask handled via `valid`
            mins,
            maxs,
            bins=bins,
            valid=jnp.broadcast_to((wl > 0)[:, None], xl.shape),
        )
        return lax.psum(hist, DATA_AXIS)

    return jax.jit(_run)


def sharded_histogram(
    x: jax.Array, w: jax.Array, mins, maxs, *, bins: int, mesh: Mesh
):
    """Data-parallel fixed-bin histograms (the quantile sketch) over the
    mesh: one scatter-add per column per shard + a psum — pad rows carry
    zero weight and never count."""
    _count_collectives("psum", 1, x.shape[1] * bins * x.dtype.itemsize)
    return _histogram_prog(mesh, bins)(x, w, mins, maxs)


# ---------------------------------------------------------------------------
# Streamed-fit chunk folds: per-chunk sharded accumulation, psum at finalize
# ---------------------------------------------------------------------------
#
# The resident programs above reduce ONCE over a fully-materialized sharded
# array. The streamed fit (spark.ingest.stream_fold) instead folds a stream
# of fixed-shape chunks; running a psum per chunk would serialize every fold
# on the slowest link, so the carry here is the STACKED per-device partials —
# each leaf [ndev, ...] sharded over the data axis — and each fold is a
# collective-free shard_map: device d adds its chunk shard's local statistics
# into its own carry slice, with the carry donated (no per-chunk [n, n]
# realloc). One allreduce at finalize produces the replicated total.


class ChunkPut:
    """Where a streamed fold's chunks go: sharded by rows over ``mesh``'s
    data axis ([c, n] matrices as P(data, None), [c] vectors as P(data)), or
    on the default device, as ``jax.device_put`` picks it, with no mesh.

    Called with a host buffer it puts the whole chunk (``stream_fold``'s
    ``put_fn``; chunk_rows must divide by the data-axis size:
    ``spark.ingest.stream_fold_over_mesh`` sees to it). :meth:`shares` and
    :meth:`assemble` are for a stream that puts a chunk by pieces, each to
    the device a whole put would have sent its rows to
    (``spark.ingest._DeviceChunk``)."""

    def __init__(self, mesh: Mesh | None):
        self.mesh = mesh

    def sharding(self, ndim: int):
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(DATA_AXIS, *[None] * (ndim - 1)))

    def __call__(self, a):
        return jax.device_put(a, self.sharding(a.ndim))

    def shares(self, rows: int) -> list[tuple[int, int, jax.Device | None]]:
        """``(lo, hi, device)`` for every addressable device: the rows
        ``[lo:hi]`` of a chunk of ``rows`` rows that it holds, in their
        order (the device None is the default one)."""
        if self.mesh is None:
            return [(0, rows, None)]
        where = self.sharding(1).addressable_devices_indices_map((rows,))
        return sorted(
            ((*idx[0].indices(rows)[:2], d) for d, idx in where.items()),
            key=lambda share: (share[0], share[2].id),
        )

    def assemble(self, shape, parts):
        """The chunk's array of ``shape`` over the arrays that hold its
        shares (in :meth:`shares`' order), with no copy."""
        if self.mesh is None:
            return parts[0]
        return jax.make_array_from_single_device_arrays(
            shape, self.sharding(len(shape)), parts
        )


def init_chunk_carry(example, mesh: Mesh):
    """Zero stacked-partials carry from an example pytree of the UNSTACKED
    statistics (arrays or ShapeDtypeStructs): each leaf becomes
    [ndev, *shape] sharded over the data axis, ready for donation."""
    import numpy as np

    ndev = mesh.shape[DATA_AXIS]

    def mk(leaf):
        shard = NamedSharding(mesh, P(DATA_AXIS))
        return jax.device_put(
            np.zeros((ndev,) + tuple(leaf.shape), leaf.dtype), shard
        )

    return jax.tree.map(mk, example)


def finalize_chunk_fold(carry, mesh: Mesh):
    """Collapse the stacked per-device partials into the replicated total —
    the ONE cross-device reduction of a streamed fit (vs one per chunk).

    The carry is deliberately NOT donated here, so a transient collective
    failure (site ``collective``) is safe to retry in place — the partials
    are still valid.

    Span ``fold.finalize`` closes on the total being ready, not on its
    dispatch: the eager decomposition that follows needs it at once, so no
    overlap is lost, and the span reads the collective's exposed time on the
    host (otherwise whoever touches the total next would pay for it)."""
    from spark_rapids_ml_tpu.parallel.backend import allreduce
    from spark_rapids_ml_tpu.resilience import faults
    from spark_rapids_ml_tpu.resilience import retry as _retry

    leaves = jax.tree_util.tree_leaves(carry)
    _count_collectives(
        "allreduce",
        len(leaves),
        sum(getattr(leaf, "nbytes", 0) for leaf in leaves) / max(len(leaves), 1),
    )

    def run():
        faults.inject("collective")
        return jax.tree.map(lambda v: allreduce(v, mesh, DATA_AXIS), carry)

    with trace_range("fold.finalize"):
        return jax.block_until_ready(
            _retry.call_with_retry(
                run,
                site="collective",
                retry_on=frozenset({_retry.ErrorClass.TRANSIENT}),
            )
        )


def _chunk_fold_prog(mesh: Mesh, kernel, vec_args: int):
    """shard_map a local-stats kernel into a donated per-chunk fold: no
    collectives inside — each device folds its shard into its carry slice."""
    in_specs = (P(DATA_AXIS), P(DATA_AXIS, None)) + tuple(
        P(DATA_AXIS) for _ in range(vec_args)
    )

    # the compiled program is named after this function: the benchmark's
    # benchmarks/layer_metrics/gram_roofline.json finds the fold in the
    # device trace as "jit__fold" (pinned by tests/test_stream_fold.py)
    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=P(DATA_AXIS),
        check_vma=False,
    )
    def _fold(carry, xl, *vecs):
        local = kernel(xl, *vecs)
        return jax.tree.map(lambda c, s: c + s[None], carry, local)

    # every caller is an @lru_cache'd factory, so the program is built
    # once per (mesh, kernel) key  # tpulint: disable=TPL003
    return jax.jit(_fold, donate_argnums=0)


@lru_cache(maxsize=None)
def _gram_chunk_fold_prog(mesh: Mesh, precision, policy: str):
    return _chunk_fold_prog(
        mesh,
        lambda xl, wl: L.gram_stats_weighted(
            xl, wl, precision=precision, policy=policy
        ),
        1,
    )


def sharded_gram_fold(
    carry, x: jax.Array, w: jax.Array, mesh: Mesh, *,
    precision=L.DEFAULT_PRECISION, policy: str | None = None,
):
    """One streamed GramStats fold: carry leaves are [ndev, ...] stacked
    partials (init_chunk_carry), ``x``/``w`` one sharded chunk. Donated —
    reassign the carry and never touch the old one. ``policy=None``
    resolves ``TPU_ML_PRECISION_POLICY`` before the program-cache lookup."""
    policy = resolve_policy(policy, allowed=FOLD_POLICIES)
    return _gram_chunk_fold_prog(mesh, precision, policy)(carry, x, w)


@lru_cache(maxsize=None)
def _moment_chunk_fold_prog(mesh: Mesh):
    from spark_rapids_ml_tpu.ops import scaler as S

    return _chunk_fold_prog(mesh, S.moment_stats_weighted, 1)


def sharded_moment_fold(carry, x: jax.Array, w: jax.Array, mesh: Mesh):
    """One streamed MomentStats fold over a sharded chunk (donated carry)."""
    return _moment_chunk_fold_prog(mesh)(carry, x, w)


@lru_cache(maxsize=None)
def _linear_chunk_fold_prog(mesh: Mesh, precision, policy: str):
    from spark_rapids_ml_tpu.ops import linear as LIN

    return _chunk_fold_prog(
        mesh,
        lambda xl, yl, wl: LIN.linear_stats(
            xl, yl, wl, precision=precision, policy=policy
        ),
        2,
    )


def sharded_linear_fold(
    carry,
    x: jax.Array,
    y: jax.Array,
    w: jax.Array,
    mesh: Mesh,
    *,
    precision=L.DEFAULT_PRECISION,
    policy: str | None = None,
):
    """One streamed LinearStats fold over a sharded labeled chunk (donated
    carry; ``w`` is the instance-weight/pad mask). ``policy=None`` resolves
    ``TPU_ML_PRECISION_POLICY`` before the program-cache lookup."""
    policy = resolve_policy(policy, allowed=FOLD_POLICIES)
    return _linear_chunk_fold_prog(mesh, precision, policy)(carry, x, y, w)
