"""Sketched (randomized range-finder) PCA that never materializes XᵀX.

The ring Gram (parallel/gram.py) already shards the n×n Gram over the feat
axis, but each fit still builds, reduces, and decomposes all n² entries —
O(n²) memory somewhere and O(n³) eigh work. This module removes the n×n
object from the algorithm entirely, which is what actually breaks the
reference's column-count wall (its n×n device buffers,
RapidsRowMatrix.scala:50-52, and its documented >65535-column caveat):

    Y = XΩ           [rows, l]   l = k + oversample    (psum over feat)
    power iters      Y ← X(XᵀQ), Q from TSQR of Y      (psum data + feat)
    B  = QᵀX         [l, n]      feature-sharded       (psum over data)
    BBᵀ              [l, l]      replicated eigh       (psum over feat)
    V  = Bᵀ·U_B·S⁻¹  [n, k]      feature-sharded — the components

Per-device memory is O(rows/D·n/F + (n/F)·l): both X and every intermediate
stay sharded on BOTH mesh axes. All collectives are fixed-size and ride ICI;
the only replicated object is the l×l core. This is the HMT rSVD recipe
(PAPERS.md) laid out over a 2-D mesh, with the TSQR butterfly
(parallel/tsqr.py) as the orthonormalization step.

Accuracy: standard randomized-subspace-iteration bounds — tight when the
spectrum decays past index k (the regime where one uses top-k PCA at huge n);
for flat spectra use more ``power_iters``/``oversample`` or the exact paths.
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
    FEAT_AXIS,
    center_columns_shard,
)
from spark_rapids_ml_tpu.parallel.tsqr import merge_r


def _orthonormalize(y: jax.Array, n_data: int, precision) -> jax.Array:
    """Q factor of data-sharded Y via TSQR: Y·R⁺ with the replicated R.

    R⁺ rather than R⁻¹: when rank(X) < l = k + oversample, Y is rank
    deficient and R singular — a plain triangular solve would divide by
    (near-)zero diagonals and silently poison every downstream direction.
    The pseudo-inverse (via the tiny replicated l×l SVD) maps null
    directions to zero columns of Q instead; Rayleigh–Ritz then assigns
    them zero Ritz values, which the s⁻¹ guard in the caller already
    handles. All solve work is block-local — no collective beyond the merge
    inside ``merge_r``.
    """
    r = merge_r(L.qr_r(y), n_data)
    u, s, vt = jnp.linalg.svd(r)
    cutoff = jnp.finfo(s.dtype).eps * s.shape[0] * jnp.max(s)
    keep = s > cutoff
    sinv = jnp.where(keep, 1.0 / jnp.where(keep, s, 1.0), 0.0)
    pinv = jnp.matmul(vt.T * sinv[None, :], u.T, precision=precision)
    return jnp.matmul(y, pinv, precision=precision)


def sketched_pca_fit(
    x: jax.Array,
    k: int,
    mesh: Mesh,
    *,
    oversample: int = 10,
    power_iters: int = 2,
    seed: int = 0,
    mean_centering: bool = False,
    precision=L.DEFAULT_PRECISION,
) -> tuple[jax.Array, jax.Array]:
    """Top-k PCA of a (data, feat)-sharded [rows, n] matrix, no n×n anywhere.

    Returns ``(components [n, k], explainedVariance [k])`` with components
    feature-sharded by block-row (spec ``P(feat, None)``) — at the n this
    path exists for, a replicated [n, k] is exactly what must be avoided.
    Explained variance keeps the reference's sᵢ/Σs definition via the
    trace-based tail estimate (ops.linalg.explained_variance_from_partial);
    the trace is one scalar psum of Σx², not an n×n reduction. Sign
    orientation matches the reference rule (rapidsml_jni.cu:35-61), resolved
    across feature shards with an l-sized all_gather.
    """
    n = x.shape[1]
    l = min(n, k + oversample)
    n_data = mesh.shape[DATA_AXIS]
    mm = partial(jnp.matmul, precision=precision)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(DATA_AXIS, FEAT_AXIS),
        out_specs=(P(FEAT_AXIS, None), P()),
        check_vma=False,
    )
    def _fit(xl):
        j = lax.axis_index(FEAT_AXIS)
        if mean_centering:
            xl = center_columns_shard(xl)

        # Per-feature-block slice of the global sketch Ω — fold_in keeps the
        # blocks independent without materializing the full [n, l].
        key = jax.random.fold_in(jax.random.PRNGKey(seed), j)
        omega = jax.random.normal(key, (xl.shape[1], l), xl.dtype)

        y = lax.psum(mm(xl, omega), FEAT_AXIS)  # [r_l, l]
        for _ in range(power_iters):
            q = _orthonormalize(y, n_data, precision)
            z = lax.psum(mm(xl.T, q), DATA_AXIS)  # [c_l, l]
            y = lax.psum(mm(xl, z), FEAT_AXIS)
        q = _orthonormalize(y, n_data, precision)

        b = lax.psum(mm(q.T, xl), DATA_AXIS)  # [l, c_l] — B's feature block
        core = lax.psum(mm(b, b.T), FEAT_AXIS)  # [l, l] = BBᵀ, replicated
        evals, u_b = jnp.linalg.eigh(core)  # ascending
        evals = evals[::-1]
        u_b = u_b[:, ::-1]
        s_vals = jnp.sqrt(jnp.clip(evals, 0.0, None))
        safe = jnp.where(s_vals > 0, s_vals, jnp.ones_like(s_vals))
        v = mm(b.T, u_b / safe[None, :])  # [c_l, l] — V's feature block

        # Global sign flip: per column, the anchor is the element of largest
        # |value| across ALL feature blocks.
        local_idx = jnp.argmax(jnp.abs(v), axis=0)
        local_anchor = jnp.take_along_axis(v, local_idx[None, :], axis=0)[0]
        all_anchor = lax.all_gather(local_anchor, FEAT_AXIS)  # [F, l]
        owner = jnp.argmax(jnp.abs(all_anchor), axis=0)
        anchor = jnp.take_along_axis(all_anchor, owner[None, :], axis=0)[0]
        v = v * jnp.where(anchor < 0, -1.0, 1.0)[None, :]

        trace = lax.psum(jnp.sum(xl * xl), (DATA_AXIS, FEAT_AXIS))
        ev = L.explained_variance_from_partial(
            s_vals, trace, jnp.asarray(n - l, xl.dtype)
        )
        return v[:, :k], ev[:k]

    return _fit(x)


def sharded_column_means(x: jax.Array, mesh: Mesh) -> jax.Array:
    """Feature-sharded column means of a (data, feat)-sharded X — the μ a
    centered sketched fit needs at transform time, spec ``P(feat)``."""

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=P(DATA_AXIS, FEAT_AXIS),
        out_specs=P(FEAT_AXIS),
        check_vma=False,
    )
    def _mean(xl):
        s = lax.psum(jnp.sum(xl, axis=0), DATA_AXIS)
        c = lax.psum(jnp.asarray(xl.shape[0], xl.dtype), DATA_AXIS)
        return s / c

    return _mean(x)


def sharded_project(
    x: jax.Array,
    components: jax.Array,
    mesh: Mesh,
    *,
    mean: jax.Array | None = None,
    precision=L.DEFAULT_PRECISION,
) -> jax.Array:
    """Transform for feature-sharded components: Y = (X−μ)·V, no replication.

    ``x`` is [rows, n] sharded (data, feat); ``components`` is [n, k] sharded
    by block-row over ``feat`` (exactly what ``sketched_pca_fit`` emits).
    Each device contracts its feature block — [r_l, c_l]·[c_l, k] on the MXU
    — and one psum over ``feat`` completes the projection. Output [rows, k]
    is data-sharded. Completes the large-n story end-to-end: neither fit nor
    transform ever holds an n-sized replicated object.

    ``mean``: REQUIRED when the components came from a
    ``mean_centering=True`` fit — a feature-sharded [n] vector (spec
    ``P(feat)``, from ``sharded_column_means`` over the training data);
    omitting it silently offsets every projection by μ·V. The centering
    rides the same psum: (X−μ)·V = Σⱼ (Xⱼ−μⱼ)·Vⱼ.

    Reference contrast: its transform re-uploads the full [n, k] pc to the
    device on EVERY batch (rapidsml_jni.cu:85, SURVEY.md §3.2) — here the
    components never leave the mesh, let alone get replicated.
    """
    in_specs = [P(DATA_AXIS, FEAT_AXIS), P(FEAT_AXIS, None)]
    if mean is not None:
        in_specs.append(P(FEAT_AXIS))

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=P(DATA_AXIS, None),
        check_vma=False,
    )
    def _proj(xl, vl, *maybe_mu):
        if maybe_mu:
            xl = xl - maybe_mu[0][None, :]
        return lax.psum(jnp.matmul(xl, vl, precision=precision), FEAT_AXIS)

    args = (x, components) if mean is None else (x, components, mean)
    return _proj(*args)


@lru_cache(maxsize=None)
def make_sharded_project(mesh: Mesh, *, centered: bool = False):
    """jit-compile ``sharded_project`` with mesh shardings bound.

    With ``centered=True`` the returned function takes ``(x, components,
    mean)`` — use for components from a ``mean_centering=True`` fit.
    """
    in_sh = [
        NamedSharding(mesh, P(DATA_AXIS, FEAT_AXIS)),
        NamedSharding(mesh, P(FEAT_AXIS, None)),
    ]
    if centered:
        in_sh.append(NamedSharding(mesh, P(FEAT_AXIS)))

        def f(x, components, mean):
            return sharded_project(x, components, mesh, mean=mean)

    else:

        def f(x, components):
            return sharded_project(x, components, mesh)

    return jax.jit(
        f,
        in_shardings=tuple(in_sh),
        out_shardings=NamedSharding(mesh, P(DATA_AXIS, None)),
    )


@lru_cache(maxsize=32)
def make_sketched_fit(
    mesh: Mesh,
    k: int,
    *,
    oversample: int = 10,
    power_iters: int = 2,
    seed: int = 0,
    mean_centering: bool = False,
):
    """jit-compile ``sketched_pca_fit``: input (data, feat)-sharded,
    components feature-sharded, explained variance replicated."""
    return jax.jit(
        partial(
            sketched_pca_fit,
            k=k,
            mesh=mesh,
            oversample=oversample,
            power_iters=power_iters,
            seed=seed,
            mean_centering=mean_centering,
        ),
        in_shardings=NamedSharding(mesh, P(DATA_AXIS, FEAT_AXIS)),
        out_shardings=(
            NamedSharding(mesh, P(FEAT_AXIS, None)),
            NamedSharding(mesh, P()),
        ),
    )
