"""Operations and bytes that the algorithm needs, from shapes alone. The
same count whatever implements the work, so a later PR cannot move it."""

from __future__ import annotations


def gram_fold(chunk_rows: int, n: int) -> dict[str, float]:
    """One fold of a ``[chunk_rows, n]`` float32 chunk into the ``[n, n]``
    carry: XᵀX (2·c·n² FLOP), the chunk read once, the carry read and
    written once."""
    return {
        "flops": 2.0 * chunk_rows * n * n,
        "bytes": 4.0 * chunk_rows * n + 8.0 * n * n,
    }


def pca_fit(rows: int, n: int) -> dict[str, float]:
    """A whole fit's useful operations: the Gram of its rows. The
    decomposition's n³ is under a thousandth of it at these sizes and is left
    out, so the share reads a little low rather than high."""
    return {"flops": 2.0 * rows * n * n, "bytes": 4.0 * rows * n}


def least_seconds(work: dict[str, float], peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    compute = work["flops"] / peak["bf16_flops_per_s"]
    memory = work["bytes"] / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
