"""Distributed tall-skinny QR (TSQR) and the direct-SVD fit path.

The Gram route (parallel/gram.py) reduces n×n partial XᵀX matrices — the
reference's only strategy (RapidsRowMatrix.scala:122-139) — which squares
the condition number before the eigensolver runs. TSQR reduces **R factors**
instead: each device QRs its row shard, then R factors pairwise-merge in a
butterfly over the ``data`` axis (log₂D rounds of QR-of-stacked-pair, each
partner exchange a single ``ppermute`` hop riding ICI). The final R is
replicated; its SVD (n×n, tiny) yields the principal components at cond(X)
rather than cond(X)² accuracy.

This is the communication-avoiding QR of Demmel et al., which maps onto a
TPU mesh better than onto the reference's substrate: the butterfly partner
at round r is 2^r hops away on the data axis, every exchange is a fixed-size
[n, n] tile, and the whole fit stays one XLA program — no JVM heap, no
driver round-trips.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    center_columns_shard,
)


def _butterfly_r(r_local: jax.Array, n_data: int) -> jax.Array:
    """Merge per-device R factors to one replicated R via butterfly exchange.

    Runs inside shard_map over the ``data`` axis. At round t each device
    swaps its current R with the partner whose index differs in bit t
    (a single ppermute), stacks the pair in canonical (lower-index-first)
    order so both partners compute the *identical* QR, and keeps the merged
    R. After log₂(n_data) rounds every device holds the same R with
    RᵀR = Σᵢ RᵢᵀRᵢ = XᵀX.
    """
    j = lax.axis_index(DATA_AXIS)
    r = r_local
    t = 1
    while t < n_data:
        perm = [(i, i ^ t) for i in range(n_data)]
        recv = lax.ppermute(r, DATA_AXIS, perm)
        lo_hi = jnp.concatenate([r, recv], axis=0)
        hi_lo = jnp.concatenate([recv, r], axis=0)
        is_low = (j & t) == 0  # our index has bit t clear → we are "lower"
        stacked = jnp.where(is_low, lo_hi, hi_lo)
        r = jnp.linalg.qr(stacked, mode="r")
        t *= 2
    return r


def merge_r(r: jax.Array, n_data: int) -> jax.Array:
    """Merge per-device R factors over the ``data`` axis (shard_map context).

    Butterfly when the axis size is a power of two, all-gather + replicated
    QR otherwise. Returns the same replicated R on every device.
    """
    if n_data == 1:
        return r
    if n_data & (n_data - 1) == 0:
        return _butterfly_r(r, n_data)
    rs = lax.all_gather(r, DATA_AXIS)  # [D, n, n]
    return jnp.linalg.qr(rs.reshape(-1, r.shape[1]), mode="r")


def tsqr_r(x: jax.Array, mesh: Mesh) -> jax.Array:
    """R factor of a [rows, n] matrix row-sharded over the ``data`` axis.

    Butterfly merge when the data-axis size is a power of two (the normal
    TPU slice shape); otherwise a one-shot ``all_gather`` of the local R
    factors followed by a replicated QR of the [D·n, n] stack — same result,
    one collective, O(D·n³) replicated compute (fine for the small-D case
    where the butterfly doesn't apply).
    """
    return _tsqr_r_prog(mesh)(x)


@lru_cache(maxsize=None)
def _tsqr_r_prog(mesh: Mesh):
    n_data = mesh.shape[DATA_AXIS]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(DATA_AXIS, None),
        out_specs=P(),
        check_vma=False,
    )
    def _tsqr(xl):
        return merge_r(L.qr_r(xl), n_data)

    return jax.jit(_tsqr)


def distributed_pca_fit_svd(
    x: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    mean_centering: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Full SPMD direct-SVD fit: sharded rows → replicated (pc, ev).

    With centering, the global mean is one psum over the data axis, applied
    shard-locally before the local QR — the centered TSQR then proceeds
    identically. The final n×n SVD runs replicated (same rationale as the
    Gram path's replicated eigh: the model is tiny and every host wants it).
    """
    if mean_centering:

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=P(DATA_AXIS, None),
            out_specs=P(DATA_AXIS, None),
            check_vma=False,
        )
        def _center(xl):
            return center_columns_shard(xl)

        x = _center(x)
    r = tsqr_r(x, mesh)
    return L.svd_from_r(r, k)


@lru_cache(maxsize=32)
def make_distributed_fit_svd(mesh: Mesh, k: int, *, mean_centering: bool = False):
    """jit-compile ``distributed_pca_fit_svd`` with mesh shardings bound."""
    return jax.jit(
        partial(
            distributed_pca_fit_svd, k=k, mesh=mesh, mean_centering=mean_centering
        ),
        in_shardings=NamedSharding(mesh, P(DATA_AXIS, None)),
        out_shardings=NamedSharding(mesh, P()),
    )


@lru_cache(maxsize=32)
def make_distributed_fit_svd_masked(
    mesh: Mesh, k: int, *, mean_centering: bool = False
):
    """Pad-mask-aware TSQR fit for PADDED shards (the barrier path, where
    every process zero-pads to a common shard shape).

    Zero pad rows are already exact for the uncentered QR (R of [X; 0] = R
    of X), but centering would turn them into -mean rows and corrupt R — so
    the global mean uses the TRUE row count (psum of the mask) and the
    centered matrix is re-masked: (x − μ)·mask. ``w`` is the 1/0 pad mask,
    data-sharded like x.
    """
    import jax.numpy as jnp

    n_data = mesh.shape[DATA_AXIS]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False,
    )
    def run(xl, wl):
        if mean_centering:
            col_sum = lax.psum(jnp.sum(xl, axis=0), DATA_AXIS)  # pads are 0
            count = lax.psum(jnp.sum(wl), DATA_AXIS)
            mean = col_sum / jnp.maximum(count, 1.0)
            xl = (xl - mean[None, :]) * wl[:, None]
        r = merge_r(L.qr_r(xl), n_data)
        return L.svd_from_r(r, k)

    return jax.jit(
        run,
        in_shardings=(
            NamedSharding(mesh, P(DATA_AXIS, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
        ),
        out_shardings=NamedSharding(mesh, P()),
    )
