"""Core PCA linear algebra as pure JAX kernels.

This module is the TPU-native re-design of the reference's device math:

- Gram/covariance accumulation  (reference: cuBLAS gemm in ``dgemmCov``,
  native/src/rapidsml_jni.cu:109-127)
- symmetric eigendecomposition with descending reorder + sqrt + sign-flip
  (reference: ``calSVD`` → raft::linalg::eigDC + colReverse/rowReverse +
  seqRoot + signFlip, native/src/rapidsml_jni.cu:215-269)
- batched projection for transform (reference: ``dgemm``,
  native/src/rapidsml_jni.cu:75-107)

Design notes (TPU-first, not a translation):

- The Gram pass is the hot loop (O(rows·n²) FLOPs) and is a single large
  matmul — exactly what the MXU wants. We default matmul precision to
  ``HIGHEST`` so f32 inputs use multi-pass bf16 on TPU, which is what lets an
  f32 accumulation meet the ≥0.9999 eigenvector cosine-sim bar vs an f64 CPU
  oracle without paying TPU-emulated f64 in the hot loop.
- Partition-local statistics are carried as a ``GramStats`` triple
  (XᵀX, column sums, row count) so mean-centering can be applied *after* the
  cross-partition reduction: (X-μ)ᵀ(X-μ) = XᵀX − s·sᵀ/count. The reference
  accepts a ``meanCentering`` param but never implements it (TODO stub at
  RapidsRowMatrix.scala:111-117); we implement it for real and keep the
  uncentered Gram path for behavioral parity.
- The n×n eigh is negligible next to the Gram pass, runs once, and stays on
  device via ``jnp.linalg.eigh`` — no hand-written solver needed on TPU.

Numerical semantics preserved exactly from the reference (SURVEY.md §3.1):
descending eigenvalue order, singular values = √λ, explainedVariance =
sᵢ/Σs over the FULL spectrum then truncated to k (RapidsRowMatrix.scala:92-99),
and the signFlip orientation rule (rapidsml_jni.cu:35-61).
"""

from __future__ import annotations

from functools import lru_cache
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from spark_rapids_ml_tpu.ops.policy import (
    FOLD_POLICIES,
    PrecisionPolicy,
    resolve_policy,
)

# Matmul precision for the hot Gram/projection matmuls. HIGHEST on TPU means
# multi-pass bf16 (6-pass) which recovers ~f32 accuracy on the MXU.
DEFAULT_PRECISION = lax.Precision.HIGHEST

# The user-facing precision tiers (the estimators' ``precision`` param and
# the TPU_ML_DEFAULT_PRECISION config knob map through this).
PRECISIONS = {
    "highest": lax.Precision.HIGHEST,
    "high": lax.Precision.HIGH,
    "default": lax.Precision.DEFAULT,
}

DEFAULT_POLICY = PrecisionPolicy.F32.value


def policy_matmul(a: jax.Array, b: jax.Array, *,
                  precision=DEFAULT_PRECISION,
                  policy: str = DEFAULT_POLICY) -> jax.Array:
    """The policy-aware matmul every accumulation kernel funnels through.

    ``f32`` is the seed behavior (the ``precision`` knob applies verbatim).
    ``bf16_f32acc`` casts the *operands* to bfloat16 and forces f32 MXU
    accumulation with ``preferred_element_type``, then upcasts the result
    back to the operand dtype — the downstream add into the f32/f64 carry
    is exact in the carry dtype, so donation (TPL001) and bitwise
    checkpoint/resume semantics are untouched; only operand mantissa is
    traded (bf16 tile (16, 128) halves MXU operand bytes vs f32 (8, 128)).
    """
    if policy == PrecisionPolicy.BF16_F32ACC.value:
        out = jnp.matmul(
            a.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        return out.astype(a.dtype)
    return jnp.matmul(a, b, precision=precision)


def int8_quantized_matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """Symmetric per-tensor int8 quantized ``a·b`` — the ``int8_dist``
    policy's cross term for kmeans/knn candidate scoring.

    Max-abs scales map each operand onto [−127, 127]; the int8×int8 matmul
    accumulates in int32 (``preferred_element_type``, int8 MXU tile
    (32, 128)) and dequantizes by the scale product. Strictly opt-in and
    only ever used for *distance ranking* — never for Gram/linear
    accumulation, where quantization error would compound over chunks.
    """

    def quant(t):
        amax = jnp.max(jnp.abs(t))
        scale = jnp.where(amax > 0, amax / 127.0, jnp.ones_like(amax))
        q = jnp.clip(jnp.round(t / scale), -127.0, 127.0).astype(jnp.int8)
        return q, scale

    qa, sa = quant(a)
    qb, sb = quant(b)
    acc = jnp.matmul(qa, qb, preferred_element_type=jnp.int32)
    return acc.astype(a.dtype) * (sa * sb)


class GramStats(NamedTuple):
    """Partition-local sufficient statistics for (optionally centered) PCA.

    A commutative monoid: ``combine_gram_stats`` sums two of them, which is
    what rides the cross-partition reduction (psum over ICI on an SPMD mesh,
    or host tree-aggregation on the portable path). This replaces the
    reference's JVM-heap breeze ``reduce((a, b) => a + b)``
    (RapidsRowMatrix.scala:139).
    """

    xtx: jax.Array  # [n, n] — Xᵀ·X of the partition's rows
    col_sum: jax.Array  # [n]  — per-feature sums (for mean centering)
    count: jax.Array  # []   — number of rows


def gram(x: jax.Array, *, precision=DEFAULT_PRECISION) -> jax.Array:
    """Uncentered Gram matrix XᵀX of a row-major [rows, n] block.

    Parity target: ``dgemmCov`` (native/src/rapidsml_jni.cu:109-127), which
    runs cublasgemm(OP_N, OP_T) on the column-major device buffer — the same
    XᵀX contraction.
    """
    return jnp.matmul(x.T, x, precision=precision)


def gram_stats(x: jax.Array, *, precision=DEFAULT_PRECISION) -> GramStats:
    """Compute the full sufficient-statistics triple for one partition."""
    return GramStats(
        xtx=gram(x, precision=precision),
        col_sum=jnp.sum(x, axis=0),
        count=jnp.asarray(x.shape[0], dtype=x.dtype),
    )


def combine_gram_stats(a: GramStats, b: GramStats) -> GramStats:
    """Monoid combine — elementwise sum of the triples."""
    return GramStats(a.xtx + b.xtx, a.col_sum + b.col_sum, a.count + b.count)


def gram_stats_weighted(
    x: jax.Array, w: jax.Array, *, precision=DEFAULT_PRECISION,
    policy: str = DEFAULT_POLICY,
) -> GramStats:
    """GramStats under the framework-wide masking convention: ``w`` carries
    instance weights on true rows and 0.0 on pad rows, so XᵀWX, the weighted
    column sums, and the weight-sum count are exact over padded chunks with
    no count fix-up. With unit weights this reduces bit-for-bit to
    :func:`gram_stats` of the zero-padded block (x·1.0 == x).

    Under ``policy='bf16_f32acc'`` only the XᵀWX matmul operands are cast
    (``policy_matmul``); col_sum and count stay exact in the carry dtype."""
    xw = x * w[:, None]
    return GramStats(
        xtx=policy_matmul(x.T, xw, precision=precision, policy=policy),
        col_sum=jnp.sum(xw, axis=0),
        count=jnp.sum(w),
    )


def fold_gram_stats(
    carry: GramStats, x: jax.Array, w: jax.Array, *,
    precision=DEFAULT_PRECISION, policy: str = DEFAULT_POLICY,
) -> GramStats:
    """One streamed-fit fold step: carry + weighted stats of one chunk."""
    return combine_gram_stats(
        carry, gram_stats_weighted(x, w, precision=precision, policy=policy)
    )


def gram_fold_step(precision=DEFAULT_PRECISION, policy: str | None = None):
    """The cached jitted fold step for streamed fits, with the carry
    **donated**: the [n, n] accumulator is updated in place on device, so a
    stream of C chunks allocates ONE set of carry buffers, not C — and the
    jitted call returns as soon as it is dispatched (JAX async dispatch),
    which is what lets the next chunk's host ingest overlap this chunk's
    MXU fold. Use ``carry = step(carry, x, w)`` and never touch the old
    carry again — donation invalidates it.

    ``policy=None`` resolves the process default (``TPU_ML_PRECISION_POLICY``)
    *before* the cache lookup, so an env change selects a different cached
    program instead of a stale one."""
    return _gram_fold_step(
        precision, resolve_policy(policy, allowed=FOLD_POLICIES)
    )


@lru_cache(maxsize=None)
def _gram_fold_step(precision, policy: str):
    def _step(carry: GramStats, x: jax.Array, w: jax.Array) -> GramStats:
        return fold_gram_stats(carry, x, w, precision=precision,
                               policy=policy)

    return jax.jit(_step, donate_argnums=0)


def init_gram_carry(n: int, dtype) -> GramStats:
    """Zero device-resident GramStats carry for :func:`gram_fold_step`."""
    return GramStats(
        xtx=jnp.zeros((n, n), dtype),
        col_sum=jnp.zeros((n,), dtype),
        count=jnp.zeros((), dtype),
    )


def gram_fold_xtx_step(precision=DEFAULT_PRECISION,
                       policy: str | None = None):
    """Donated fold of the bare [n, n] Gram (the TruncatedSVD accumulator —
    no col_sum/count companions). Pad rows are zero so no mask is needed."""
    return _gram_fold_xtx_step(
        precision, resolve_policy(policy, allowed=FOLD_POLICIES)
    )


@lru_cache(maxsize=None)
def _gram_fold_xtx_step(precision, policy: str):
    def _step(carry: jax.Array, x: jax.Array) -> jax.Array:
        return carry + policy_matmul(x.T, x, precision=precision,
                                     policy=policy)

    return jax.jit(_step, donate_argnums=0)


def covariance_from_stats(stats: GramStats, *, mean_centering: bool) -> jax.Array:
    """Finalize the (scatter-form) covariance from reduced statistics.

    With ``mean_centering=False`` this is the raw Gram XᵀX — the reference's
    actual observable behavior (its meanCentering is a TODO stub,
    RapidsRowMatrix.scala:111-117). With ``True`` it is the centered scatter
    matrix (X-μ)ᵀ(X-μ) = XᵀX − s·sᵀ/count. No 1/(n-1) normalization is
    applied, matching the reference; eigenvectors and the explained-variance
    *ratio* are invariant to that scale.
    """
    if not mean_centering:
        return stats.xtx
    denom = jnp.maximum(stats.count, jnp.ones_like(stats.count))
    return stats.xtx - jnp.outer(stats.col_sum, stats.col_sum) / denom


def standardized_cov_from_stats(
    stats: GramStats,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(scatter of standardized X, mean, sample std) from RAW GramStats —
    the fused StandardScaler→PCA pipeline (BASELINE config 4) in ONE data
    pass: with Xs = (X − μ)/σ,  XsᵀXs = D⁻¹(XᵀX − m·μμᵀ)D⁻¹ with D =
    diag(σ), so the standardized covariance needs no second pass over the
    data. σ is the sample (m−1) std matching StandardScaler
    (ops/scaler.py finalize_moments); zero-variance features pass through
    unscaled, like ``standardize``."""
    from spark_rapids_ml_tpu.ops import scaler as S

    # diag(XᵀX) IS the per-feature sum of squares: the scaler's own
    # finalize_moments derives mean/sample-std, so the fused path can never
    # drift from the staged StandardScaler pipeline it must equal
    mean, std = S.finalize_moments(
        S.MomentStats(stats.count, stats.col_sum, jnp.diagonal(stats.xtx))
    )
    m = jnp.maximum(stats.count, jnp.ones_like(stats.count))
    safe = jnp.where(std > 0, std, jnp.ones_like(std))
    centered = stats.xtx - m * jnp.outer(mean, mean)
    cov = centered / jnp.outer(safe, safe)
    return cov, mean, std


def sign_flip(u: jax.Array) -> jax.Array:
    """Deterministic eigenvector orientation.

    Parity target: the ``signFlip`` thrust kernel
    (native/src/rapidsml_jni.cu:35-61): for each column, find the element of
    largest absolute value; if it is negative, negate the whole column.
    """
    idx = jnp.argmax(jnp.abs(u), axis=0)
    anchors = jnp.take_along_axis(u, idx[None, :], axis=0)[0]
    signs = jnp.where(anchors < 0, -jnp.ones_like(anchors), jnp.ones_like(anchors))
    return u * signs[None, :]


def refine_eigh(
    a: jax.Array,
    v: jax.Array,
    evals: jax.Array,
    *,
    iters: int = 2,
    precision=DEFAULT_PRECISION,
) -> tuple[jax.Array, jax.Array]:
    """Iterative refinement of an approximate symmetric eigendecomposition.

    Newton-style correction in the spirit of Ogita–Aishima: given nearly
    orthonormal eigenvector estimates ``v``, form B = VᵀAV, take refined
    eigenvalues from diag(B) and a first-order eigenvector correction
    Zᵢⱼ = Bᵢⱼ/(Bⱼⱼ−Bᵢᵢ); converges quadratically for well-separated spectra.

    Why this exists: XLA's eigh lowers to an approximate QDWH/Jacobi route
    (residual ~1e-4·‖A‖ even in f64 on this stack) and TPU f64 is emulated.
    Two refinement sweeps of plain matmuls — exactly what the MXU is good
    at — recover LAPACK-grade residuals without a native solver, keeping the
    whole fit a single XLA program. Near-degenerate eigenpairs (gap below
    ~√eps·‖A‖) are left uncorrected: their subspace mixing is inherently
    ill-determined, and a huge 1/gap would destroy orthogonality.
    """
    eps = jnp.finfo(v.dtype).eps
    for _ in range(iters):
        av = jnp.matmul(a, v, precision=precision)
        b = jnp.matmul(v.T, av, precision=precision)
        d = jnp.diagonal(b)
        gap = d[None, :] - d[:, None]
        scale = jnp.max(jnp.abs(d)) + eps
        safe = jnp.abs(gap) > jnp.sqrt(eps) * scale
        z = jnp.where(safe, b / jnp.where(safe, gap, jnp.ones_like(gap)), 0.0)
        z = z - jnp.diag(jnp.diagonal(z))
        v = v + jnp.matmul(v, z, precision=precision)
        # One Newton–Schulz step restores orthonormality lost to the
        # first-order update: V ← V(3I − VᵀV)/2.
        vtv = jnp.matmul(v.T, v, precision=precision)
        v = jnp.matmul(
            v, 1.5 * jnp.eye(v.shape[1], dtype=v.dtype) - 0.5 * vtv,
            precision=precision,
        )
        evals = d
    av = jnp.matmul(a, v, precision=precision)
    evals = jnp.sum(v * av, axis=0) / jnp.sum(v * v, axis=0)
    return v, evals


def eigh_descending(
    cov: jax.Array, *, refine_iters: int = 2
) -> tuple[jax.Array, jax.Array]:
    """Symmetric eigendecomposition in descending order with √λ and sign-flip.

    Returns ``(components, singular_values)`` where ``components`` is [n, n]
    (eigenvectors in columns, descending eigenvalue order, sign-flipped) and
    ``singular_values`` is √max(λ, 0) descending.

    Parity target: ``calSVD`` (native/src/rapidsml_jni.cu:215-269):
    raft eigDC (ascending) → colReverse/rowReverse → seqRoot → signFlip.
    """
    evals, evecs = jnp.linalg.eigh(cov)  # ascending, like cuSolver syevd
    if refine_iters:
        evecs, evals = refine_eigh(cov, evecs, evals, iters=refine_iters)
        order = jnp.argsort(evals)[::-1]  # refinement may reorder near-ties
        evals = evals[order]
        evecs = evecs[:, order]
    else:
        evals = evals[::-1]
        evecs = evecs[:, ::-1]
    singular_values = jnp.sqrt(jnp.clip(evals, 0.0, None))
    return sign_flip(evecs), singular_values


def randomized_eigh_descending(
    cov: jax.Array,
    k: int,
    *,
    oversample: int = 10,
    power_iters: int = 2,
    seed: int = 0,
    precision=DEFAULT_PRECISION,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Randomized top-k eigendecomposition of a PSD matrix (descending).

    Halko–Martinsson–Tropp randomized subspace iteration, shaped for the
    MXU: every step is a large dense matmul ([n, n]·[n, l] with
    l = k + oversample) plus a thin QR — O(n²·l) instead of the full eigh's
    O(n³). The win is real once n is a few thousand and k ≪ n (the regime
    the reference cannot reach at all: its n×n eig is single-GPU cuSolver,
    rapidsml_jni.cu:251).

    Returns ``(components [n, k], singular_values [l], tail_count)`` where
    singular values are √max(λ, 0) for ALL l = k + oversample Ritz values
    (the extra ones cost nothing and make the explained-variance tail
    estimate far tighter), components are the top-k Ritz vectors sign-flipped
    with the same orientation rule as the exact path, and ``tail_count`` =
    n − l is the count of eigenvalues not represented in the returned
    spectrum.
    """
    n = cov.shape[0]
    l = min(n, k + oversample)
    key = jax.random.PRNGKey(seed)
    omega = jax.random.normal(key, (n, l), dtype=cov.dtype)
    q, _ = jnp.linalg.qr(jnp.matmul(cov, omega, precision=precision))
    for _ in range(power_iters):
        q, _ = jnp.linalg.qr(jnp.matmul(cov, q, precision=precision))
    # Rayleigh–Ritz on the captured subspace: B = QᵀAQ, eigh of the small
    # l×l system, lift back with U = Q·V.
    aq = jnp.matmul(cov, q, precision=precision)
    b = jnp.matmul(q.T, aq, precision=precision)
    b = 0.5 * (b + b.T)
    evals, v = jnp.linalg.eigh(b)  # ascending
    evals = evals[::-1]
    v = v[:, ::-1][:, :k]
    u = sign_flip(jnp.matmul(q, v, precision=precision))
    singular_values = jnp.sqrt(jnp.clip(evals, 0.0, None))
    return u, singular_values, jnp.asarray(n - l, dtype=cov.dtype)


def explained_variance_from_partial(
    singular_values: jax.Array, trace: jax.Array, tail_count: jax.Array
) -> jax.Array:
    """Reference-shaped explainedVariance from a PARTIAL spectrum.

    The reference normalizes sᵢ over the FULL spectrum
    (RapidsRowMatrix.scala:92-93); a randomized solver only has the top
    l = k + oversample singular values. The unseen tail's Σ√λ is estimated
    from the leftover trace: Σλ_tail = trace − Σλ_top, and by concavity
    Σ√λ_tail ≤ √(tail_count·Σλ_tail); we use that bound as the estimate
    (exact when the tail is flat, conservative — ratios shrink — when it
    decays). Since everything below λ_l is ≤ the smallest computed Ritz
    value, the estimate is applied only to that sub-λ_l remainder — the
    oversampled Ritz values carry the rest — so the error is confined to
    the flattest part of the spectrum. Returns ratios for all input values;
    callers truncate to k.
    """
    top_sum = jnp.sum(singular_values)
    top_eval_sum = jnp.sum(singular_values**2)
    tail_eval_sum = jnp.clip(trace - top_eval_sum, 0.0, None)
    tail_sum = jnp.sqrt(tail_eval_sum * jnp.clip(tail_count, 0.0, None))
    total = top_sum + tail_sum
    safe_total = jnp.where(total > 0, total, jnp.ones_like(total))
    return singular_values / safe_total


def explained_variance(singular_values: jax.Array, k: int) -> jax.Array:
    """sᵢ/Σs over the FULL spectrum, truncated to the first k.

    This is the reference's (non-textbook) definition — singular-value
    proportions, normalized before truncation (RapidsRowMatrix.scala:92-99).
    """
    total = jnp.sum(singular_values)
    safe_total = jnp.where(total > 0, total, jnp.ones_like(total))
    return (singular_values / safe_total)[:k]


def randomized_profitable(n: int, k: int, *, oversample: int = 10) -> bool:
    """Shared 'auto' solver rule: the HMT subspace iteration wins when the
    captured subspace l = k + oversample is a small fraction of n. Both PCA
    and TruncatedSVD dispatch through this single predicate.

    Thresholds are TPU-measured, not asymptotic: on v5e at n=512, l=70 the
    randomized route saved ~6.7 ms over the refined eigh (XLA's QDWH-based
    eigh pays several n³ passes, so randomized profits far earlier than an
    O(n³)-vs-O(n²l) count suggests — bench.py records the measurement)."""
    return n >= 256 and (k + oversample) * 4 <= n


def pca_fit_from_cov(
    cov: jax.Array,
    k: int,
    *,
    solver: str = "full",
    oversample: int = 10,
    power_iters: int = 2,
    seed: int = 0,
) -> tuple[jax.Array, jax.Array]:
    """Decomposition stage: covariance → (pc [n, k], explained_variance [k]).

    ``solver``:
    - ``"full"`` — exact refined eigh (reference-parity path).
    - ``"randomized"`` — HMT subspace iteration, O(n²·(k+p)); explained
      variance uses the trace-based tail estimate.
    - ``"auto"`` — randomized when it is clearly profitable
      (n ≥ 256 and k + oversample ≤ n/4, the TPU-measured rule), else full.
    """
    n = cov.shape[0]
    if solver == "auto":
        solver = (
            "randomized"
            if randomized_profitable(n, k, oversample=oversample)
            else "full"
        )
    if solver == "randomized":
        u, s, tail_count = randomized_eigh_descending(
            cov, k, oversample=oversample, power_iters=power_iters, seed=seed
        )
        ev = explained_variance_from_partial(s, jnp.trace(cov), tail_count)
        return u, ev[:k]
    if solver != "full":
        raise ValueError(f"unknown solver {solver!r}")
    components, s = eigh_descending(cov)
    return components[:, :k], explained_variance(s, k)


def pca_fit_local(
    x: jax.Array,
    k: int,
    *,
    mean_centering: bool = False,
    precision=DEFAULT_PRECISION,
) -> tuple[jax.Array, jax.Array]:
    """Single-device end-to-end fit kernel: rows → (pc, explainedVariance).

    Fully jit-able with static ``k``/``mean_centering``. This is the
    whole reference fit() hot path (SURVEY.md §3.1) as one XLA program.

    When ``mean_centering=False`` (the reference's observable behavior —
    its centering is a TODO stub, RapidsRowMatrix.scala:111-117) the
    column-sum statistic is skipped entirely: that saves a second full
    HBM pass over X, leaving exactly the reference's computation
    (uncentered Gram + eig).
    """
    if not mean_centering:
        return pca_fit_from_cov(gram(x, precision=precision), k)
    stats = gram_stats(x, precision=precision)
    cov = covariance_from_stats(stats, mean_centering=True)
    return pca_fit_from_cov(cov, k)


def min_cosine_vs_f64_oracle(x_host, pc, k: int) -> float:
    """Min per-component |cosine| of fitted components vs the f64 host
    oracle (uncentered scatter eigh, descending) — the accuracy check the
    bench publishes per round and CI gates on (tests/test_accuracy_validation
    .py); ONE implementation so they can never desynchronize."""
    import numpy as np

    xa = np.asarray(x_host, dtype=np.float64)
    pc = np.asarray(pc, dtype=np.float64)
    _, evecs = np.linalg.eigh(xa.T @ xa)
    oracle = evecs[:, ::-1][:, :k]
    cosines = np.abs(np.sum(pc * oracle, axis=0)) / (
        np.linalg.norm(pc, axis=0) * np.linalg.norm(oracle, axis=0)
    )
    return float(cosines.min())


def qr_r(x: jax.Array) -> jax.Array:
    """R factor of a (tall) row block, always shaped [n, n].

    The building block of the direct-SVD fit path: R carries the complete
    sufficient statistic for X's right singular structure (RᵀR = XᵀX) while
    staying orthogonal-factor-accurate — unlike the Gram matrix, forming R
    never squares the condition number. Blocks with fewer than n rows are
    zero-padded (QR of [X; 0] has the same R up to the rows X determines).
    """
    rows, n = x.shape
    if rows < n:
        x = jnp.concatenate([x, jnp.zeros((n - rows, n), x.dtype)], axis=0)
    return jnp.linalg.qr(x, mode="r")


def combine_r(a: jax.Array, b: jax.Array) -> jax.Array:
    """Associative combine for R factors: QR of the stacked pair.

    (RᵃᵀRᵃ + RᵇᵀRᵇ) is preserved, so R factors reduce across partitions
    exactly like ``GramStats`` — a semigroup ridden by ``tree_reduce`` on
    the portable path and by the butterfly exchange in ``parallel.tsqr`` on
    the mesh path.
    """
    return jnp.linalg.qr(jnp.concatenate([a, b], axis=0), mode="r")


def svd_components_from_r(r: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """R → (components [n, k], singular values [n], both of X).

    The singular values of R are exactly the singular values of X (X = QR
    with Q orthonormal). Right singular vectors get the same deterministic
    sign-flip orientation as the eigh path (rapidsml_jni.cu:35-61). The one
    SVD(R) kernel both direct-path estimators (PCA solver='svd' and
    TruncatedSVD) decompose through.
    """
    _, s, vt = jnp.linalg.svd(r, full_matrices=False)  # descending already
    return sign_flip(vt.T[:, :k]), s


def svd_from_r(r: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Decomposition stage of the direct PCA path: R → (pc [n, k], ev [k]).

    The reference's explained-variance definition — sᵢ/Σs over the FULL
    spectrum, truncated to k (RapidsRowMatrix.scala:92-99) — transfers
    unchanged, computed here without ever forming XᵀX.
    """
    components, s = svd_components_from_r(r, k)
    return components, explained_variance(s, k)


def pca_fit_local_svd(
    x: jax.Array,
    k: int,
    *,
    mean_centering: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Single-device direct-SVD fit: rows → (pc, explainedVariance).

    Numerically superior alternative to the Gram path for ill-conditioned
    data: cond(XᵀX) = cond(X)², so the Gram route loses half the working
    digits before the eigensolver even starts; QR → SVD(R) works at
    cond(X). The reference has no such path (its only route is the Gram +
    cuSolver eig, SURVEY.md §3.1); this is a capability-add enabled by the
    TSQR reduction being mesh-friendly.
    """
    if mean_centering:
        x = x - jnp.mean(x, axis=0, keepdims=True)
    return svd_from_r(qr_r(x), k)


def project(x: jax.Array, pc: jax.Array, *, precision=DEFAULT_PRECISION) -> jax.Array:
    """Transform projection X·PC for a [rows, n] block and [n, k] components.

    Parity target: ``dgemm`` (native/src/rapidsml_jni.cu:75-107). The
    reference computes (X·PC)ᵀ with an OP_T transpose trick purely to land
    row-major data in its column-major LIST layout (RapidsPCA.scala:139-152);
    with row-major JAX arrays the plain contraction is the same math.
    """
    return jnp.matmul(x, pc, precision=precision)
