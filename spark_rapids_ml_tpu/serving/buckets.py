"""Serve-path shape bucketing: power-of-two row buckets + zero padding.

The fit path already buckets partition rows (``utils.columnar.bucket_rows``,
floor ``TPU_ML_MIN_BUCKET=128``) so XLA compiles one program per bucket
instead of one per batch (a shard held resident through an iterative fit is
sized finer, ``utils.columnar.shard_rows``: its padding is paid every
iteration, a serve rung's once a request). Serving needs the same idea with
different constants: a scoring request is often ONE row, and padding it to 128 wastes
latency-path FLOPs, so the serve ladder starts at ``TPU_ML_SERVE_MIN_BUCKET``
(default 8) and is capped at ``TPU_ML_SERVE_MAX_BATCH_ROWS`` (default 4096).
The cap matters twice over: it bounds one micro-batched dispatch AND it makes
the compiled-signature set *enumerable* — the registry AOT-compiles every
rung of :func:`bucket_ladder` at registration time, so after warmup an
arbitrary request size can never miss the compiled set. That is what turns
PR 5's recompile-storm anomaly from a diagnosis into a hard gate
(``serve_recompiles_after_warmup == 0`` on the perf ledger).

Zero padding is exact for every serve kernel we ship: projection, linear
prediction, standardization and tree descent are all row-independent, so a
padded row can only affect its own (discarded) output rows. ``pad_to_bucket``
returns the valid-row count alongside the padded block; callers slice the
kernel output back to it.

Import-pure apart from numpy — the linter and jax-free tooling can load it.
"""

from __future__ import annotations

import math
import os

import numpy as np

from spark_rapids_ml_tpu.utils import knobs

SERVE_MIN_BUCKET_VAR = knobs.SERVE_MIN_BUCKET.name
SERVE_MAX_BATCH_ROWS_VAR = knobs.SERVE_MAX_BATCH_ROWS.name


def _int_env(var: str, default: int) -> int:
    raw = os.environ.get(var, "")
    try:
        return int(raw) if raw else default
    except ValueError:
        return default


def min_bucket() -> int:
    """Serve-path bucket floor (``TPU_ML_SERVE_MIN_BUCKET``), clamped to a
    power of two >= 1 so the ladder stays aligned."""
    floor = max(1, _int_env(SERVE_MIN_BUCKET_VAR, int(knobs.SERVE_MIN_BUCKET.default)))
    return 1 << math.ceil(math.log2(floor))


def max_batch_rows() -> int:
    """Serve-path bucket cap (``TPU_ML_SERVE_MAX_BATCH_ROWS``), rounded up
    to a power of two and never below :func:`min_bucket`."""
    cap = max(
        1,
        _int_env(
            SERVE_MAX_BATCH_ROWS_VAR, int(knobs.SERVE_MAX_BATCH_ROWS.default)
        ),
    )
    return max(min_bucket(), 1 << math.ceil(math.log2(cap)))


def serve_bucket(rows: int) -> int:
    """Round a request row count up to its serve bucket.

    Raises ``ValueError`` above the ladder cap — an oversized request must
    be rejected at admission (HTTP 413), never silently compiled fresh.
    """
    if rows <= 0:
        raise ValueError(f"request must have at least one row (got {rows})")
    cap = max_batch_rows()
    if rows > cap:
        raise ValueError(
            f"request of {rows} rows exceeds the serve ladder cap {cap} "
            f"({SERVE_MAX_BATCH_ROWS_VAR}) — split the request or raise "
            "the cap"
        )
    return max(min_bucket(), 1 << math.ceil(math.log2(rows)))


def bucket_ladder() -> tuple[int, ...]:
    """Every serve bucket, smallest to largest — the FIXED set of row
    shapes the registry AOT-compiles per model at registration."""
    lo, hi = min_bucket(), max_batch_rows()
    out = []
    b = lo
    while b <= hi:
        out.append(b)
        b *= 2
    return tuple(out)


def pad_to_bucket(x: np.ndarray, bucket: int | None = None) -> tuple[np.ndarray, int]:
    """Zero-pad a [rows, n] request block to its serve bucket.

    Returns ``(padded, true_rows)``; callers slice kernel output back to
    ``true_rows``. A pre-chosen ``bucket`` (the micro-batcher's coalescing
    key) is honored as long as it holds the rows.
    """
    rows = x.shape[0]
    if bucket is None:
        bucket = serve_bucket(rows)
    elif rows > bucket:
        raise ValueError(f"{rows} rows do not fit the requested bucket {bucket}")
    if bucket == rows:
        return x, rows
    out = np.zeros((bucket, x.shape[1]), dtype=x.dtype)
    out[:rows] = x
    return out, rows
