"""Pallas TPU kernel: fused split-precision Gram + feature moments.

The hot op of the whole framework is the Gram pass (SURVEY.md §3.1 "HOT
LOOP 1"). This kernel makes one HBM read of X produce, in a single pass:

- ``gram``    = XᵀX accumulated in f32 via the **bf16 split trick**: X is
  decomposed as hi + lo (two bf16s ≈ 16 mantissa bits); XᵀX ≈ hiᵀhi + hiᵀlo
  + loᵀhi — three MXU passes at full bf16 throughput, ~2× the FLOP cost of
  one pass instead of the 6× that f32 ``Precision.HIGHEST`` pays, with
  near-f32 accuracy (the dropped loᵀlo term is ~2⁻³² relative).
- ``col_sum`` and ``sum_sq`` — the mean-centering statistic PCA needs and
  the variance statistic StandardScaler needs. This is BASELINE config 4's
  "scaler fused into the PCA input pipeline" delivered at the kernel level:
  fitting a standardize→PCA pipeline costs ONE data pass, not three.

Grid: (n/bn, n/bn, rows/br) with rows innermost, so each [bn, bn] output
tile stays resident in VMEM while row blocks stream through (the canonical
Pallas accumulation pattern); moments accumulate on the i==0 wavefront only.

Measured on v5e-1 (2M×512): 53 ms vs XLA's 38 ms for ``Precision.HIGHEST``
Gram+moments and 22 ms for ``Precision.HIGH`` (which applies this same
bf16-split decomposition with better stream scheduling — one X read per
column-block pair vs this kernel's two). ``symmetric_gram_moments`` below
fixes the HBM re-reads (1-D grid, whole accumulator VMEM-resident) and skips
the lower-triangle block pairs — measured 23.3 ms, a 1.43× win over this
kernel's formulation, but still behind XLA HIGH's 16.7 ms: the 37.5% flop
skip (n=512, 128-blocks) is outweighed by Mosaic reaching ~65% MXU
efficiency on the 3-dot tile loop where XLA's tuned gemm reaches ~100%. The
XLA paths therefore stay the production default in ops.linalg; these kernels
remain as the interpret-testable statement of the fused one-pass stats and
the measured record of the symmetric-skip experiment (the skip becomes
profitable if Mosaic's gemm pipelining improves or nt grows).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CONTRACT_ROWS = (((0,), (0,)), ((), ()))  # aᵀb for row-major tiles


def _pad_and_split(x, block_rows, block_cols):
    """Shared kernel prologue: f32 cast, block padding, hi/lo bf16 split.

    Returns (hi, lo, n) where n is the pre-padding column count. Zero
    padding is exact for Gram/moment reductions; hi + lo carries ~16
    mantissa bits of the f32 input.
    """
    if x.dtype != jnp.float32:
        x = x.astype(jnp.float32)
    n = x.shape[1]
    pr = (-x.shape[0]) % block_rows
    pn = (-n) % block_cols
    if pr or pn:
        x = jnp.pad(x, ((0, pr), (0, pn)))
    hi = x.astype(jnp.bfloat16)
    lo = (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, lo, n


def _trim(gram, colsum, sumsq, n):
    """Shared epilogue: drop the column padding from all three outputs."""
    if gram.shape[0] != n:
        gram = gram[:n, :n]
        colsum = colsum[:, :n]
        sumsq = sumsq[:, :n]
    return gram, colsum[0], sumsq[0]


def _fused_kernel(hi_i, lo_i, hi_j, lo_j, gram_ref, colsum_ref, sumsq_ref):
    i = pl.program_id(0)
    r = pl.program_id(2)

    @pl.when(r == 0)
    def _init_gram():
        gram_ref[:] = jnp.zeros_like(gram_ref)

    a_hi, a_lo = hi_i[:], lo_i[:]
    b_hi, b_lo = hi_j[:], lo_j[:]
    dot = partial(
        jax.lax.dot_general,
        dimension_numbers=_CONTRACT_ROWS,
        preferred_element_type=jnp.float32,
    )
    gram_ref[:] += dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)

    @pl.when(i == 0)
    def _moments():
        @pl.when(r == 0)
        def _init_moments():
            colsum_ref[:] = jnp.zeros_like(colsum_ref)
            sumsq_ref[:] = jnp.zeros_like(sumsq_ref)

        xb = b_hi.astype(jnp.float32) + b_lo.astype(jnp.float32)
        colsum_ref[:] += jnp.sum(xb, axis=0, keepdims=True)
        sumsq_ref[:] += jnp.sum(xb * xb, axis=0, keepdims=True)


def _symmetric_kernel(
    hi_ref, lo_ref, gram_ref, colsum_ref, sumsq_ref, *, nt, bc, n_rows
):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        gram_ref[:] = jnp.zeros_like(gram_ref)
        colsum_ref[:] = jnp.zeros_like(colsum_ref)
        sumsq_ref[:] = jnp.zeros_like(sumsq_ref)

    dot = partial(
        jax.lax.dot_general,
        dimension_numbers=_CONTRACT_ROWS,
        preferred_element_type=jnp.float32,
    )
    # Upper-triangle block pairs only: the flops XLA's full gemm wastes on
    # the mirrored lower half are simply never issued.
    for bi in range(nt):
        a_hi = hi_ref[:, bi * bc : (bi + 1) * bc]
        a_lo = lo_ref[:, bi * bc : (bi + 1) * bc]
        for bj in range(bi, nt):
            b_hi = hi_ref[:, bj * bc : (bj + 1) * bc]
            b_lo = lo_ref[:, bj * bc : (bj + 1) * bc]
            acc = dot(a_hi, b_hi) + dot(a_hi, b_lo) + dot(a_lo, b_hi)
            gram_ref[bi * bc : (bi + 1) * bc, bj * bc : (bj + 1) * bc] += acc

    xb = hi_ref[:].astype(jnp.float32) + lo_ref[:].astype(jnp.float32)
    colsum_ref[:] += jnp.sum(xb, axis=0, keepdims=True)
    sumsq_ref[:] += jnp.sum(xb * xb, axis=0, keepdims=True)

    # Last row block: mirror the strict upper blocks into the lower half.
    @pl.when(r == n_rows - 1)
    def _mirror():
        for bi in range(nt):
            for bj in range(bi + 1, nt):
                gram_ref[bj * bc : (bj + 1) * bc, bi * bc : (bi + 1) * bc] = (
                    gram_ref[bi * bc : (bi + 1) * bc, bj * bc : (bj + 1) * bc].T
                )


def symmetric_gram_moments(
    x: jax.Array,
    *,
    block_rows: int = 1024,
    block_cols: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Symmetric one-wavefront (gram, col_sum, sum_sq) of a [rows, n] f32 X.

    The flops-skipping variant ``fused_gram_moments``'s docstring promises:

    - grid is 1-D over row blocks; the WHOLE [n, n] f32 accumulator plus the
      hi/lo bf16 row block stay VMEM-resident, so each X element is read
      from HBM exactly once (the (i, j, r) formulation re-reads each column
      block nt times — that made it HBM-bound and slower than XLA);
    - only upper-triangle block pairs are multiplied — nt(nt+1)/2 of nt²
      tiles, a 1.6-1.8× MXU-flop saving XLA's gemm cannot express since its
      output is not known-symmetric — with the lower half mirrored in VMEM
      on the final row block.

    Fits when the n×n f32 Gram + two bf16 row blocks fit VMEM: n ≤ ~1280 at
    the defaults, where Mosaic allocates 16.25 MiB — just past its 16 MiB
    default scoped limit (measured on v5e, jax 0.9.0), hence the explicit
    ``vmem_limit_bytes``. Callers gate on n and fall back to the XLA path
    above.
    """
    hi, lo, n = _pad_and_split(x, block_rows, block_cols)
    rows_p, n_p = hi.shape
    nt = n_p // block_cols
    n_row_blocks = rows_p // block_rows

    row_block = pl.BlockSpec((block_rows, n_p), lambda r: (r, 0))
    full_out = pl.BlockSpec((n_p, n_p), lambda r: (0, 0))
    moment_out = pl.BlockSpec((1, n_p), lambda r: (0, 0))

    gram, colsum, sumsq = pl.pallas_call(
        partial(
            _symmetric_kernel, nt=nt, bc=block_cols, n_rows=n_row_blocks
        ),
        grid=(n_row_blocks,),
        in_specs=[row_block, row_block],
        out_specs=(full_out, moment_out, moment_out),
        out_shape=(
            jax.ShapeDtypeStruct((n_p, n_p), jnp.float32),
            jax.ShapeDtypeStruct((1, n_p), jnp.float32),
            jax.ShapeDtypeStruct((1, n_p), jnp.float32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=3 * rows_p * n_p * n_p * (nt + 1) // nt,  # 3·2·r·n²·(upper/total)
            bytes_accessed=2 * rows_p * n_p * 2 + n_p * n_p * 4,
            transcendentals=0,
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=32 * 2**20),
        interpret=interpret,
    )(hi, lo)

    return _trim(gram, colsum, sumsq, n)


def fused_gram_moments(
    x: jax.Array,
    *,
    block_rows: int = 1024,
    block_cols: int = 256,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-pass (gram [n,n], col_sum [n], sum_sq [n]) of a [rows, n] f32 X.

    Zero-padding to block multiples is exact for all three reductions; the
    caller keeps true row counts (same contract as ops.linalg.GramStats).
    ``interpret=True`` runs the kernel on CPU for tests.
    """
    hi, lo, n = _pad_and_split(x, block_rows, block_cols)
    rows_p, n_p = hi.shape

    grid = (n_p // block_cols, n_p // block_cols, rows_p // block_rows)
    row_tile_i = pl.BlockSpec((block_rows, block_cols), lambda i, j, r: (r, i))
    row_tile_j = pl.BlockSpec((block_rows, block_cols), lambda i, j, r: (r, j))

    gram, colsum, sumsq = pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[row_tile_i, row_tile_i, row_tile_j, row_tile_j],
        out_specs=(
            pl.BlockSpec((block_cols, block_cols), lambda i, j, r: (i, j)),
            pl.BlockSpec((1, block_cols), lambda i, j, r: (0, j)),
            pl.BlockSpec((1, block_cols), lambda i, j, r: (0, j)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n_p, n_p), jnp.float32),
            jax.ShapeDtypeStruct((1, n_p), jnp.float32),
            jax.ShapeDtypeStruct((1, n_p), jnp.float32),
        ),
        cost_estimate=pl.CostEstimate(
            flops=3 * 2 * rows_p * n_p * n_p,
            bytes_accessed=2 * rows_p * n_p * 2 * (n_p // block_cols) + n_p * n_p * 4,
            transcendentals=0,
        ),
        interpret=interpret,
    )(hi, lo, hi, lo)

    return _trim(gram, colsum, sumsq, n)
