"""Streamed mesh-local ingestion: DataFrame → sharded device arrays at
O(shard) peak host memory.

The reference never lands data on the driver — ColumnarRdd materializes
partitions straight into executor device memory
(RapidsRowMatrix.scala:118). The 'mesh-local' deployment (one device-owner
process per host, DataFrame workers doing ingestion only) must route rows
through the driver process, and the r3 implementation paid for it twice:
``np.concatenate`` of every partition into one [rows, n] f64 ndarray, then
a second zero-padded copy, before a single whole-matrix ``device_put`` —
~2× the dataset in host RSS, which walls far below the north-star shape
(BASELINE.md: 100M×2048 ≈ 1.6 TB per copy).

This module replaces that with a streaming fill:

- chunks drain from the DataFrame lazily (localspark partitions are
  generator-produced; real pyspark uses ``toLocalIterator`` which fetches
  one partition at a time);
- one stager (``_Stager``) copies each chunk into a staging set
  (``_StagingSet``) in the dtype the device holds (by row blocks over a
  small kept pool of threads where the batch is large enough to cut) and
  hands the set to its consumer, which ``device_put``s it, the moment it
  fills: a device's whole shard on the resident path (``stream_to_mesh``),
  one fold chunk on the streamed one (``stream_fold``;
  ``stream_fold_over_mesh`` owns its geometry over a mesh). The streamed
  fold alone also asks to be told of the rows as they are written, and puts
  the chunk by pieces into the one chunk its device holds while the rest is
  staged (``_DeviceChunk``), so the transfer runs under the host's copy and
  not after it. The one set is
  kept and rewritten under the buffer rule stated at ``_take_staging``,
  which finds out from the arrays whether a put aliased (``device_put`` of
  a host ndarray may alias rather than copy);
- the global array is assembled zero-copy on device with
  ``jax.make_array_from_single_device_arrays``.

Peak host footprint: one inbound chunk + the shard buffer being filled —
independent of dataset size. Wire dtype is selectable
(``TPU_ML_MESH_LOCAL_WIRE_DTYPE=float32`` halves both host RSS and HBM;
default float64 keeps the reference's FLOAT64 semantics,
rapidsml_jni.cu:89) where x64 is on; with x64 off the device holds
float32 whatever it says, and the streamed fold stages in that dtype, so
the one host copy of a chunk is also its cast. An optional hard cap
(``TPU_ML_MESH_LOCAL_MAX_BYTES``) turns the otherwise-undiagnosed device OOM
of oversized mesh-local ingests into a descriptive error naming the
alternatives.
"""

from __future__ import annotations

import collections
import functools
import logging
import os
import queue
import sys
import threading
import time
from concurrent import futures
from dataclasses import dataclass
from typing import Any, Iterator

import numpy as np

from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils import columnar, knobs

logger = logging.getLogger("spark_rapids_ml_tpu")

WIRE_DTYPE_VAR = knobs.MESH_LOCAL_WIRE_DTYPE.name
MAX_BYTES_VAR = knobs.MESH_LOCAL_MAX_BYTES.name
# real-pyspark ingest strategy cutover: datasets at or under this many
# estimated bytes use the columnar toArrow() fast path (O(dataset) driver
# Arrow memory, no per-row Python); larger ones stream via toLocalIterator
# (O(partition) memory, row-conversion cost). localspark always streams
# columnar (its partitions are lazy Arrow batches — both properties at once).
ARROW_CUTOVER_VAR = knobs.MESH_LOCAL_ARROW_MAX_BYTES.name
DEFAULT_ARROW_CUTOVER = 1 << 30
# rows per conversion chunk on the row-iterator (pyspark) path; Arrow-path
# chunks keep whatever batch size the engine produced
ROW_CHUNK = 65_536
# streamed-fit knobs: fits whose estimated resident footprint exceeds the
# cutover never assemble the global array — they fold fixed-shape chunks of
# STREAM_CHUNK rows through a donated device accumulator instead
STREAM_CUTOVER_VAR = knobs.STREAM_FIT_MAX_RESIDENT_BYTES.name
STREAM_CHUNK_VAR = knobs.STREAM_CHUNK_ROWS.name
DEFAULT_STREAM_CHUNK = 65_536
# floor (and alignment multiple) for the OOM chunk bisection; mesh callers
# pass min_chunk_rows >= the data-axis size so bisected chunks still shard
STREAM_CHUNK_FLOOR_VAR = knobs.STREAM_CHUNK_FLOOR.name
DEFAULT_STREAM_CHUNK_FLOOR = 8
FOLD_WAIT_TIMEOUT_VAR = knobs.FOLD_WAIT_TIMEOUT_S.name
# live progress heartbeat: float seconds between stderr lines during a
# streamed fold (unset/0 = silent — multi-minute fits opt in)
PROGRESS_VAR = knobs.PROGRESS.name


def wire_dtype() -> np.dtype:
    """Host-buffer/device dtype for mesh-local ingestion (env-selected)."""
    name = os.environ.get(WIRE_DTYPE_VAR, "float64")
    if name not in ("float32", "float64"):
        raise ValueError(
            f"{WIRE_DTYPE_VAR}={name!r}: expected float32 or float64"
        )
    return np.dtype(name)


@dataclass
class MeshIngest:
    """Sharded device-resident ingest of one DataFrame.

    ``ws`` follows the framework-wide masking convention: instance weights
    (1.0 when no weightCol) on true rows, 0.0 on pad rows — so the same
    vector serves as pad mask and Spark-style weighting in every mesh
    program (columnar.pad_labeled rationale).
    """

    xs: Any            # [padded_rows, n(+1)] global array, data-sharded
    ys: Any | None     # [padded_rows] labels, or None
    ws: Any | None     # [padded_rows] weights/pad-mask, or None
    mesh: Any
    rows: int          # true rows
    padded_rows: int   # shard * mesh.size


def _iter_chunks(
    selected,
    features_col: str,
    label_col: str | None,
    weight_col: str | None,
    est_bytes: int = 0,
) -> Iterator[tuple[np.ndarray, np.ndarray | None, np.ndarray | None]]:
    """Yield (x [c, n], y [c] | None, w [c] | None) chunks from the
    DataFrame, bounding driver memory.

    localspark: ``_parts()`` partitions are produced by a generator —
    columnar AND genuinely streaming. Real pyspark has no public streaming
    Arrow API, so it's a size-gated tradeoff: small datasets
    (≤ ARROW_CUTOVER) take whole-table columnar extraction — ``toArrow()``
    on pyspark 4.0+, arrow-enabled ``toPandas()`` on 3.x (both one driver
    job, O(dataset) columnar memory, no per-row Python); larger ones
    stream via ``toLocalIterator()`` (one partition per job, rows
    converted in ROW_CHUNK groups — columns by POSITION: callers select
    [features, label?, weight?] in that order). Anything else: one-shot
    ``collect()``.
    """
    if hasattr(selected, "_parts"):  # localspark
        for part in selected._parts():
            for b in part:
                if not b.num_rows:
                    continue
                x = columnar.extract_matrix(b, features_col)
                y = columnar.extract_vector(b, label_col) if label_col else None
                w = columnar.extract_vector(b, weight_col) if weight_col else None
                yield x, y, w
        return
    cutover = int(
        float(os.environ.get(ARROW_CUTOVER_VAR, DEFAULT_ARROW_CUTOVER))
    )
    if est_bytes <= cutover:
        to_arrow = getattr(selected, "toArrow", None)
        if callable(to_arrow):  # pyspark 4.0+
            for b in to_arrow().to_batches():
                if not b.num_rows:
                    continue
                x = columnar.extract_matrix(b, features_col)
                y = columnar.extract_vector(b, label_col) if label_col else None
                w = columnar.extract_vector(b, weight_col) if weight_col else None
                yield x, y, w
            return
        if _pandas_columnar_ok(selected, features_col):
            # pyspark 3.x (no toArrow): arrow-enabled toPandas IS a
            # columnar one-job collect for ArrayType columns — but only
            # then. VectorUDT columns and arrow-disabled sessions degrade
            # toPandas to a pickled per-row collect at O(dataset) memory,
            # strictly worse than the row iterator below, so the guard
            # sends those there.
            try:
                pdf = selected.toPandas()
            except ImportError:  # pandas went missing mid-probe
                pdf = None
            if pdf is not None:
                if len(pdf):
                    x = columnar.extract_matrix(pdf, features_col)
                    y = (
                        columnar.extract_vector(pdf, label_col)
                        if label_col
                        else None
                    )
                    w = (
                        columnar.extract_vector(pdf, weight_col)
                        if weight_col
                        else None
                    )
                    yield x, y, w
                return
    it = getattr(selected, "toLocalIterator", None)
    rows_iter = it() if callable(it) else iter(selected.collect())
    buf: list[Any] = []
    for row in rows_iter:
        buf.append(row)
        if len(buf) >= ROW_CHUNK:
            yield _chunk_from_rows(buf, label_col, weight_col)
            buf = []
    if buf:
        yield _chunk_from_rows(buf, label_col, weight_col)


def _pandas_columnar_ok(selected, features_col: str) -> bool:
    """True only when ``selected.toPandas()`` would actually be a columnar
    arrow collect: pandas importable, the session's arrow transfer enabled,
    and the features column an ArrayType (VectorUDT is not arrow-convertible
    — pyspark silently falls back to pickled rows). Anything unverifiable
    answers False; the row-iterator path is the safe default."""
    if not callable(getattr(selected, "toPandas", None)):
        return False
    try:
        import pandas  # noqa: F401
    except ImportError:
        return False
    try:
        dtype = selected.schema[features_col].dataType
        if type(dtype).__name__ != "ArrayType":
            return False
        enabled = selected.sparkSession.conf.get(
            "spark.sql.execution.arrow.pyspark.enabled"
        )
        return str(enabled).lower() == "true"
    except Exception:
        return False


def _chunk_from_rows(rows: list, label_col, weight_col):
    """Convert a ROW_CHUNK of driver-side rows to (x, y, w) arrays.

    This is the path large real-Spark datasets take (toLocalIterator), so
    the feature conversion is bulk, not per-row (r4 verdict weak #5): plain
    ArrayType rows convert in one C-level ``np.asarray`` over the whole
    chunk, DenseVector rows stack their backing ``values`` ndarrays, and
    only irregular chunks (sparse/mixed/VectorUDT-dict rows, which raise
    out of the bulk attempt) pay the exact per-row converter.
    """
    first = rows[0][0]
    try:
        if isinstance(first, (list, tuple, np.ndarray)):
            x = np.asarray([r[0] for r in rows], dtype=np.float64)
        elif hasattr(first, "values") and not hasattr(first, "indices"):
            # pyspark.ml DenseVector: .values IS the backing float64 ndarray
            x = np.asarray([r[0].values for r in rows], dtype=np.float64)
        else:
            raise ValueError("irregular rows")
        if x.ndim != 2:
            raise ValueError("ragged chunk")
    except (ValueError, AttributeError):
        x = np.stack([columnar.row_vector_to_ndarray(r[0]) for r in rows])
    y = (
        np.fromiter((r[1] for r in rows), dtype=np.float64, count=len(rows))
        if label_col
        else None
    )
    wi = 2 if label_col else 1  # columns arrive [features, label?, weight?]
    w = (
        np.fromiter((r[wi] for r in rows), dtype=np.float64, count=len(rows))
        if weight_col
        else None
    )
    return x, y, w


def _check_size(padded_rows: int, n_eff: int, dtype: np.dtype, mesh) -> None:
    est = padded_rows * n_eff * dtype.itemsize
    cap = os.environ.get(MAX_BYTES_VAR)
    if cap and est > int(float(cap)):
        raise ValueError(
            f"mesh-local ingest needs ~{est / 1e9:.2f} GB of device memory "
            f"({padded_rows}×{n_eff} {dtype.name}), over the "
            f"{MAX_BYTES_VAR}={cap} cap. Use distribution='mesh-barrier' "
            "(data stays sharded across workers) or 'driver-merge' (only "
            "[n, n] statistics reach the driver), or set "
            f"{WIRE_DTYPE_VAR}=float32 to halve the footprint."
        )


def stream_to_mesh(
    selected,
    *,
    features_col: str,
    n: int,
    label_col: str | None = None,
    weight_col: str | None = None,
    with_weights: bool = False,
    augment_intercept: bool = False,
    mesh=None,
    rows: int | None = None,
) -> MeshIngest:
    """Stream ``selected`` (columns ordered [features, label?, weight?])
    into data-sharded global arrays over the driver's device mesh.

    One extra ``count()`` pass sizes the shards up front (Spark recomputes
    an uncached plan the same way): a device's shard is
    ``columnar.shard_rows`` of its share of the rows, an eighth of an octave
    and not a power of two, and what that pads is booked in
    ``mesh.pad_rows``. The data pass then stages each device's shard in turn
    and ships it to its device as it fills.
    ``with_weights`` forces a ``ws`` vector even without a ``weight_col``
    (1.0 true rows / 0.0 pads — the pad-mask convention masked mesh
    programs consume).

    Span ``mesh.ingest`` covers both passes. The data pass is the one
    staging pipeline (:class:`_Stager` has its spans, counters and buffer
    rule); what is this function's own is the consumer: a full set is a
    device's shard, put to that device under ``h2d.put``, and the closing
    ``stage.reclaim`` waits for every shard, so the rows have landed on
    return and the set can be kept for the next ingest.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel import mesh as M
    from spark_rapids_ml_tpu.telemetry import trace_range

    if mesh is None:
        mesh = M.create_mesh()
    want_y = label_col is not None
    want_w = with_weights or bool(weight_col)
    x_parts: list[Any] = []
    y_parts: list[Any] = []
    w_parts: list[Any] = []

    with trace_range("mesh.ingest"):
        if rows is None:
            rows = selected.count()
        if rows == 0:
            raise ValueError("empty dataset")
        dt = wire_dtype()
        n_eff = n + 1 if augment_intercept else n
        ndev = mesh.size
        shard = columnar.shard_rows(-(-rows // ndev))
        padded_rows = shard * ndev
        _check_size(padded_rows, n_eff, dt, mesh)

        x_sharding = M.data_sharding(mesh)
        vec_sharding = NamedSharding(mesh, P(M.DATA_AXIS))
        devmap = x_sharding.addressable_devices_indices_map((padded_rows, n_eff))
        devices = sorted(devmap, key=lambda d: devmap[d][0].start or 0)

        def put_shard(staged: _StagingSet, fill: int) -> None:
            index = len(x_parts)
            d = devices[index]
            nbytes = 0
            with trace_range("h2d.put"):
                t0 = time.perf_counter()
                put = []
                for buf, parts, wanted in (
                    (staged.x, x_parts, True),
                    (staged.y, y_parts, want_y),
                    (staged.w, w_parts, want_w),
                ):
                    if wanted:
                        parts.append(jax.device_put(buf, d))
                        staged.placed.append(parts[-1])
                        put.append(parts[-1])
                        nbytes += buf.nbytes
                # a shard's buffers are one transfer to its device
                _transfers.issued(put, nbytes, (index,), "mesh", t0)
            REGISTRY.counter_inc("h2d.bytes", nbytes, path="mesh")

        # a set is a device's shard, in the dtype the device holds
        key = (
            shard, n_eff, np.dtype(jax.dtypes.canonicalize_dtype(dt)), want_y
        )
        with _Stager(
            lambda: key, put_shard, augment_intercept=augment_intercept
        ) as stager:
            for xc, yc, wc in _batches(
                _iter_chunks(
                    selected, features_col, label_col, weight_col,
                    est_bytes=rows * n * 8,
                ),
                n, features_col,
            ):
                if stager.rows + len(xc) > rows:
                    raise ValueError(
                        f"dataset produced more rows while streaming than "
                        f"count() reported ({rows}); cache() the DataFrame if "
                        "its source is nondeterministic"
                    )
                stager.feed(xc, yc, wc)
            if stager.rows != rows:
                raise ValueError(
                    f"dataset produced {stager.rows} rows while streaming but "
                    f"count() reported {rows}; cache() the DataFrame if its "
                    "source is nondeterministic"
                )
            while len(x_parts) < ndev:  # zero-pad the partial + empty tail shards
                stager.flush()
            # whoever asked for the rows needs them landed, and the set is
            # kept for the next ingest only once they are
            with trace_range("stage.reclaim"):
                jax.block_until_ready([x_parts, y_parts, w_parts])
        # zero rows of weight 0 that every pass of the fit's programs walks
        REGISTRY.counter_inc("mesh.pad_rows", padded_rows - rows)

        xs = jax.make_array_from_single_device_arrays(
            (padded_rows, n_eff), x_sharding, x_parts
        )
        ys = (
            jax.make_array_from_single_device_arrays(
                (padded_rows,), vec_sharding, y_parts
            )
            if want_y
            else None
        )
        ws = (
            jax.make_array_from_single_device_arrays(
                (padded_rows,), vec_sharding, w_parts
            )
            if want_w
            else None
        )
    return MeshIngest(
        xs=xs, ys=ys, ws=ws, mesh=mesh, rows=rows, padded_rows=padded_rows
    )


# ---------------------------------------------------------------------------
# Streamed fit: chunk-wise fold with a donated device accumulator
# ---------------------------------------------------------------------------


def use_streamed_fit(rows: int, n: int) -> bool:
    """Cutover rule for DataFrame fits: stream when the resident global
    array (rows × n at the wire dtype) would exceed
    ``TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES``. The resident path stays the
    default — it is still fastest when the data fits."""
    from spark_rapids_ml_tpu.utils.config import get_config

    return (
        rows * n * wire_dtype().itemsize
        > get_config().stream_fit_max_resident_bytes
    )


def stream_chunk_rows() -> int:
    """Rows per fold chunk (``TPU_ML_STREAM_CHUNK_ROWS``), bucketed to a
    power of two so every fold call shares ONE static XLA shape."""
    rows = int(os.environ.get(STREAM_CHUNK_VAR, DEFAULT_STREAM_CHUNK))
    if rows < 1:
        raise ValueError(f"{STREAM_CHUNK_VAR}={rows} must be >= 1")
    return columnar.bucket_rows(rows)


def stream_chunk_rows_for_mesh(mesh) -> int:
    """:func:`stream_chunk_rows` rounded up to a multiple of the data axis,
    so every chunk shards evenly (power-of-two buckets already divide
    power-of-two meshes; this covers odd device counts too)."""
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    ndev = mesh.shape[DATA_AXIS]
    return -(-stream_chunk_rows() // ndev) * ndev


def progress_interval() -> float:
    """Heartbeat period from ``TPU_ML_PROGRESS`` (seconds; 0/unset = off)."""
    raw = os.environ.get(PROGRESS_VAR, "")
    if not raw:
        return 0.0
    try:
        every = float(raw)
    except ValueError:
        raise ValueError(
            f"{PROGRESS_VAR}={raw!r} must be a number of seconds"
        ) from None
    return max(0.0, every)


@dataclass
class StreamFold:
    """Result of a streamed fold: the final carry plus pipeline evidence.

    ``overlapped`` counts fold dispatches issued while the PREVIOUS chunk's
    fold was still executing on device — the double-buffering observable
    (> 0 means ingest genuinely overlapped compute). ``max_put_bytes`` is
    the largest single host→device transfer: O(chunk), never O(rows),
    because the global array is never assembled. ``skipped_rows`` counts
    non-finite rows dropped under the ``skip`` policy, ``bisections`` the
    OOM-driven chunk splits, and ``resumed`` whether the fold continued
    from a durable checkpoint instead of starting cold.
    """

    carry: Any
    rows: int
    chunks: int
    overlapped: int
    max_put_bytes: int
    skipped_rows: int = 0
    bisections: int = 0
    resumed: bool = False


def _bounded_wait(carry, timeout_s: float, *, site: str | None = "fold.wait"):
    """``jax.block_until_ready`` with a bound: a device that stopped
    answering (a hung collective, a dead runtime) surfaces as a diagnosable
    :class:`~spark_rapids_ml_tpu.resilience.retry.FoldHangTimeout` instead
    of blocking the driver forever. The waiter runs on a daemon thread; on
    timeout the stuck wait is abandoned with the thread (the process is
    poisoned for further device work — see retry.ErrorClass.POISONED).
    ``site`` is the fault site the wait stands for (None: none of its own)."""
    import jax

    from spark_rapids_ml_tpu.resilience import faults
    from spark_rapids_ml_tpu.resilience.retry import FoldHangTimeout

    def inject():
        if site is not None:
            faults.inject(site)

    if not timeout_s or timeout_s <= 0:
        inject()
        return jax.block_until_ready(carry)
    box: dict[str, Any] = {}

    def _wait():
        try:
            inject()
            box["carry"] = jax.block_until_ready(carry)
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            box["error"] = e

    t = threading.Thread(target=_wait, name="tpu-ml-fold-wait", daemon=True)
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise FoldHangTimeout(
            f"{site or 'a wait for the device'} did not complete within "
            f"{timeout_s:g}s: the device "
            "fold is hung, not slow — most likely a collective that not "
            "every participant reached, or a device runtime that died (check "
            "device health). Raise "
            f"{FOLD_WAIT_TIMEOUT_VAR} to wait longer, or set it to 0 to "
            "disable the bound."
        )
    if "error" in box:
        raise box["error"]
    return box["carry"]


_CKPT_LEAF = "leaf_{:03d}"


def _save_stream_checkpoint(ckpt, carry, *, chunks, seen, skipped, chunk_rows):
    """Durably checkpoint the carry + chunk cursor. The carry is synced
    first (``block_until_ready``) so the bytes written are the fold of
    every dispatched chunk — the checkpoint IS the stream position."""
    import jax

    done = jax.block_until_ready(carry)
    leaves = jax.tree_util.tree_leaves(done)
    arrays = {
        _CKPT_LEAF.format(i): np.asarray(leaf) for i, leaf in enumerate(leaves)
    }
    ckpt.save(
        chunks,
        arrays,
        {
            "kind": "stream_fold",
            "rows_seen": int(seen),
            "skipped_rows": int(skipped),
            "chunks": int(chunks),
            "chunk_rows": int(chunk_rows),
        },
    )
    REGISTRY.counter_inc("stream.checkpoints")
    TIMELINE.record_instant(
        "stream.checkpoint", chunk=int(chunks), rows_seen=int(seen)
    )


def _restore_stream_checkpoint(ckpt, init_carry):
    """Latest stream_fold checkpoint restored onto ``init_carry``'s
    shardings (device placement follows the zero carry the caller built),
    or None. Foreign checkpoints (a different ``kind``) are ignored rather
    than misread."""
    import jax

    latest = ckpt.latest()
    if latest is None:
        return None
    step, arrays, state = latest
    if state.get("kind") != "stream_fold":
        return None
    leaves, treedef = jax.tree_util.tree_flatten(init_carry)
    restored = []
    for i, leaf in enumerate(leaves):
        loaded = arrays[_CKPT_LEAF.format(i)]
        sharding = getattr(leaf, "sharding", None)
        restored.append(
            jax.device_put(loaded, sharding) if sharding is not None else loaded
        )
    carry = jax.tree_util.tree_unflatten(treedef, restored)
    return carry, state


def _split_chunk_buffers(bx, by, bw, size: int):
    """Re-stage one failed fixed-shape chunk as ``size``-row chunks (the
    OOM bisection): slices are zero-padded to the new static shape, and the
    pads ride the w=0 mask — exact, same as any ragged tail."""
    out = []
    for at in range(0, len(bx), size):
        take = min(size, len(bx) - at)
        sx = np.zeros((size,) + bx.shape[1:], bx.dtype)
        sx[:take] = bx[at : at + take]
        sw = np.zeros(size, bw.dtype)
        sw[:take] = bw[at : at + take]
        sy = None
        if by is not None:
            sy = np.zeros(size, by.dtype)
            sy[:take] = by[at : at + take]
        out.append((sx, sy, sw))
    return out


# ---------------------------------------------------------------------------
# The host pass's pool: a batch's per-byte work, cut by rows over a few threads
# ---------------------------------------------------------------------------

# The cast-copy into the staging set (and a non-finite check, where the host
# is the one to make it) runs at one core's pace on one thread; NumPy's
# ufuncs, reductions and casting assignments release the GIL, so row blocks
# of one batch run side by side. The sizes are read off the chip host's curve
# (PERF.md section 6, PR 29: a 256 MiB float64 batch, 13 cores): both passes
# stop gaining at 4-8 threads, where memory sets the pace; blocks of 16 MiB
# are the fastest, and under 4 MiB handing a block over costs what running
# it there saves.
_POOL_BLOCK_BYTES = 16 << 20
_POOL_MIN_BLOCK_BYTES = 4 << 20
_POOL_MAX_WORKERS = 8


def _pool_workers() -> int:
    """Threads a batch may keep busy: half the cores this process may run
    on, which leaves the rest to the runtime's transfer threads and the
    source's decode, and no more than the curve above rewards."""
    if hasattr(os, "sched_getaffinity"):
        cores = len(os.sched_getaffinity(0))
    else:  # not Linux
        cores = os.cpu_count() or 1
    return max(1, min(_POOL_MAX_WORKERS, cores // 2))


def _row_blocks(rows: int, nbytes: int) -> list[tuple[int, int]]:
    """The ``(start, stop)`` row ranges a batch of ``nbytes`` is cut into:
    whole rounds of the workers, no block over ``_POOL_BLOCK_BYTES`` or
    under ``_POOL_MIN_BLOCK_BYTES``. One block — every toy, every
    row-iterator chunk, a host with one core to spare — means inline."""
    workers = _pool_workers()
    blocks = min(
        rows,
        nbytes // _POOL_MIN_BLOCK_BYTES,
        workers * -(-nbytes // (workers * _POOL_BLOCK_BYTES)),
    )
    if workers < 2 or blocks < 2:
        return [(0, rows)]
    step = -(-rows // blocks)
    return [(at, min(at + step, rows)) for at in range(0, rows, step)]


def _run_task(done: futures.Future, fn, start: int, stop: int) -> None:
    try:
        done.set_result(fn(start, stop))
    except BaseException as e:  # noqa: BLE001 — re-raised on the caller
        done.set_exception(e)


class _RowPool:
    """Daemon threads that run ``(future, fn, start, stop)`` tasks off one
    queue until each reads its end marker."""

    def __init__(self, workers: int):
        self.tasks: queue.SimpleQueue = queue.SimpleQueue()
        self.threads = [
            threading.Thread(
                target=self._work, name=f"tpu-ml-host-pass-{i}", daemon=True
            )
            for i in range(workers)
        ]
        for t in self.threads:
            t.start()

    def _work(self) -> None:
        # a task's names die with _run_task's frame, so an idle thread
        # holds no batch alive
        while (task := self.tasks.get()) is not None:
            _run_task(*task)
            del task

    def close(self) -> None:
        for _ in self.threads:
            self.tasks.put(None)


# the kept pool, made on first use
_pool: list[_RowPool] = []
_pool_lock = threading.Lock()


def _run_blocks(fn, blocks: list[tuple[int, int]]) -> list:
    """``[fn(start, stop) for start, stop in blocks]``: one block on the
    caller's thread, more on the pool, the caller waiting (inside whatever
    span it has open; workers open none) until EVERY block has ended, an
    error among them or not, and then raising the first error. Callers on
    several threads share the pool."""
    if len(blocks) == 1:
        return [fn(*blocks[0])]
    pending = [futures.Future() for _ in blocks]
    # under the lock, so that no task is queued behind the end markers of a
    # pool that release_staging() drops meanwhile
    with _pool_lock:
        if not _pool:
            _pool.append(_RowPool(_pool_workers()))
        for done, (start, stop) in zip(pending, blocks):
            _pool[0].tasks.put((done, fn, start, stop))
    futures.wait(pending)
    return [done.result() for done in pending]


def _all_finite(x: np.ndarray) -> bool:
    """``np.isfinite(x).all()`` of a staged buffer, in the set's dtype,
    answered block by block (a block's bool temporary is an eighth of the
    block, not of the buffer): the host's verdict on a chunk whose ``put_fn``
    handed back host arrays."""
    return all(
        _run_blocks(
            lambda start, stop: bool(np.isfinite(x[start:stop]).all()),
            _row_blocks(len(x), x.nbytes),
        )
    )


def _bad_rows(bufs: list[np.ndarray]) -> np.ndarray:
    """The rows of a staged chunk (``x`` first, then its vectors) with a
    non-finite feature, label or weight, as a mask (pads are zeros, so they
    are never among them); by the same row blocks. Only a chunk whose
    verdict was "no" pays for it."""

    def bad(start: int, stop: int) -> np.ndarray:
        mask = np.zeros(stop - start, bool)
        for b in bufs:
            finite = np.isfinite(b[start:stop])
            mask |= ~(finite.all(axis=1) if finite.ndim == 2 else finite)
        return mask

    return np.concatenate(_run_blocks(bad, _row_blocks(len(bufs[0]), bufs[0].nbytes)))


@functools.cache
def _chunk_finite_prog():
    """The device's verdict on a chunk that was put: one program (module
    ``jit__chunk_finite``; a test pins the name) that answers whether every
    element of every array is finite. ``isfinite`` fuses into the reduction,
    so nothing the size of the chunk is written; over a mesh the shards'
    answers meet in one replicated bool."""
    import jax
    import jax.numpy as jnp

    def _chunk_finite(arrays):
        return functools.reduce(
            jnp.logical_and, [jnp.isfinite(a).all() for a in arrays]
        )

    return jax.jit(_chunk_finite)


def _shares_memory(placed, buf: np.ndarray) -> bool:
    """Whether ``placed`` (what a ``put_fn`` returned) lives in ``buf``'s
    bytes: a host array by its bounds, a device array by where each
    addressable shard starts (the CPU backend puts an aligned ndarray with
    no copy). Anything that cannot say counts as sharing."""
    if isinstance(placed, np.ndarray):
        return np.may_share_memory(placed, buf)
    shards = getattr(placed, "addressable_shards", None)
    if shards is None:
        return True
    lo = buf.__array_interface__["data"][0]
    return any(
        lo <= s.data.unsafe_buffer_pointer() < lo + buf.nbytes for s in shards
    )


class _StagingSet:
    """The host buffers one chunk is staged in — ``x`` [chunk_rows, n_eff],
    ``y`` (or None) and ``w`` — in the dtype their device arrays will have,
    with the arrays put from them since they were last reclaimed. Rows
    ``[dirty:]`` are zero. ``_take_staging`` states when a set may be
    written again; :meth:`reclaim` is that rule's check."""

    def __init__(self, key):
        chunk_rows, n_eff, dtype, want_y = self.key = key
        self.x = np.zeros((chunk_rows, n_eff), dtype)
        self.y = np.zeros(chunk_rows, dtype) if want_y else None
        self.w = np.zeros(chunk_rows, dtype)
        self.dirty = 0
        self.placed: list[Any] = []

    def buffers(self):
        return [b for b in (self.x, self.y, self.w) if b is not None]

    def write(self, fill: int, xc, yc, wc, *, augment_intercept=False) -> None:
        """Copy a slice of a batch to rows ``[fill : fill + len(xc)]``: the
        one host copy of these rows, and their cast to the device's dtype
        (numpy rounds to nearest, as ``device_put`` did; each element is
        cast alone, so how the rows are cut changes no byte). The features
        go by row blocks over the host pass's pool (``_run_blocks``) and the
        call returns once every block is written; labels, weights and the
        intercept column, a row's 8 bytes each, on the caller's thread.
        Books ``ingest.batches{path}``: ``pool`` or, for a slice too small
        to cut, ``inline``. No ``wc`` means weight 1."""
        n, end = xc.shape[1], fill + len(xc)
        blocks = _row_blocks(len(xc), xc.nbytes)

        def copy(start: int, stop: int) -> None:
            self.x[fill + start : fill + stop, :n] = xc[start:stop]

        _run_blocks(copy, blocks)
        REGISTRY.counter_inc(
            "ingest.batches", path="pool" if len(blocks) > 1 else "inline"
        )
        if augment_intercept:
            self.x[fill:end, n] = 1.0
        if self.y is not None:
            self.y[fill:end] = yc
        self.w[fill:end] = 1.0 if wc is None else wc
        self.dirty = max(self.dirty, end)

    def zero_from(self, fill: int) -> None:
        """Zero rows ``[fill:dirty]``: what an earlier chunk left past a
        ragged tail (a fresh set has nothing there)."""
        for b in self.buffers():
            b[fill : self.dirty] = 0
        self.dirty = fill

    def reclaim(self, wait: bool = True) -> bool:
        """Let go of the placed arrays; True where the buffers may now be
        written again. Without ``wait`` a transfer still in flight answers
        False instead of blocking."""
        import jax

        placed, self.placed = self.placed, []
        on_device = [a for a in placed if isinstance(a, jax.Array)]
        if any(a.is_deleted() for a in on_device):
            return False  # donated on: nothing left to ask whether it landed
        if wait:
            jax.block_until_ready(on_device)
        elif not all(a.is_ready() for a in on_device):
            return False
        return not any(
            _shares_memory(a, b) for a in placed for b in self.buffers()
        )


# the one staging set kept between ingests, streamed or resident (one set,
# not two in rotation: PERF.md section 6, PR 27 has both readings)
_kept_staging: list[_StagingSet] = []
_kept_staging_lock = threading.Lock()


def _borrow_staging(key) -> _StagingSet | None:
    """Take the kept set out of the holder for one ingest; None
    where it is lent out already or was made for another key."""
    with _kept_staging_lock:
        if _kept_staging and _kept_staging[0].key == key:
            return _kept_staging.pop()
    return None


def _return_staging(staging: _StagingSet | None) -> None:
    """Give back what an ingest ends with. The holder keeps the
    newest and drops what it had; a set whose last transfer cannot be seen
    to have landed, or whose arrays share its memory, is not kept."""
    if staging is not None and staging.reclaim(wait=False):
        with _kept_staging_lock:
            _kept_staging[:] = [staging]


def _take_staging(key, candidate: _StagingSet | None) -> _StagingSet:
    """The set the next chunk (or shard) is staged in: ``candidate``, the
    set last put from, where it may be written again, else a new one. THE
    BUFFER RULE, which holds on every backend and is found out from the
    arrays, not from a platform's name: a set is written again only after
    (1) every array put from it is ready — a runtime may read the host
    buffer until the transfer completes, and not after — and (2) none of
    those arrays shares memory with it (the CPU backend puts an aligned
    ndarray with no copy, and an identity ``put_fn`` hands the buffer itself
    on): such a buffer goes with its array, and a new one is taken. Rows
    past a ragged tail are zeroed before the put (``zero_from``)."""
    from spark_rapids_ml_tpu.telemetry import trace_range

    state = "fresh"
    # another key: a bisection changed the chunk's shape
    if candidate is not None and candidate.key == key:
        with trace_range("stage.reclaim"):
            reusable = candidate.reclaim()
        if reusable:
            REGISTRY.counter_inc("stage.buffers", state="reused")
            return candidate
        state = "aliased"
    REGISTRY.counter_inc("stage.buffers", state=state)
    return _StagingSet(key)


class _Stager:
    """The one staging pipeline behind the resident ingest and the streamed
    fold: batches in, full staging sets out.

    :meth:`feed` copies a batch ``(xc, yc, wc)`` slice by slice into the set
    being filled — each slice under span ``ingest.stage``, the one host copy
    of those rows and their cast to the device's dtype
    (``_StagingSet.write``) — and hands every set that fills to
    ``consume(staged, fill)``; :meth:`flush` hands over the partial one,
    rows ``[fill:]`` zeroed first where an earlier chunk left any
    (``zero_from``, under ``ingest.stage`` too). The consumer puts the
    set's buffers and appends what it put to ``staged.placed``: the set it
    was handed becomes the candidate for the next
    (``_take_staging`` has THE BUFFER RULE, span ``stage.reclaim`` and
    counter ``stage.buffers{state}``). ``key()`` is asked for each new set,
    because a consumer may change its shape in mid-stream (the fold's OOM
    bisection); a set of another key is never rewritten.

    A consumer that asks for it (``on_rows``; the streamed fold alone does)
    is also told of a set that is still filling: after every slice written,
    ``on_rows(staged, fill)`` says that rows ``[:fill]`` of it are in place,
    so that it can put them by pieces while the rest is staged
    (:class:`_DeviceChunk` says what a piece is). A set that fills goes to
    ``consume`` as ever, and whoever did not ask is handed full sets alone.

    As a context manager it borrows the set kept from the last ingest on
    entry and gives back the newest on every exit, errors included
    (``_borrow_staging``, ``_return_staging``). ``rows`` counts the rows
    staged so far."""

    def __init__(
        self, key, consume, *, augment_intercept: bool = False, on_rows=None
    ):
        self.key = key
        self.consume = consume
        self.on_rows = on_rows
        self.augment_intercept = augment_intercept
        self.staged: _StagingSet | None = None  # the set being filled
        self.spare: _StagingSet | None = None  # the set last put from
        self.fill = 0
        self.rows = 0

    def __enter__(self):
        self.spare = _borrow_staging(self.key())
        return self

    def __exit__(self, *exc):
        _return_staging(self.staged or self.spare)

    def _take(self) -> _StagingSet:
        if self.staged is None:
            self.staged = _take_staging(self.key(), self.spare)
            self.spare = None
        return self.staged

    def feed(self, xc, yc, wc) -> None:
        from spark_rapids_ml_tpu.telemetry import trace_range

        if wc is not None:
            # the ONE weightCol contract enforcement point (all-zero is
            # checked globally by callers, hence allow_all_zero)
            wc = columnar.validate_weights(wc, len(xc), allow_all_zero=True)
        at = 0
        while at < len(xc):
            staged = self._take()
            take = min(len(staged.x) - self.fill, len(xc) - at)
            with trace_range("ingest.stage"):
                staged.write(
                    self.fill,
                    xc[at : at + take],
                    yc[at : at + take] if staged.y is not None else None,
                    wc[at : at + take] if wc is not None else None,
                    augment_intercept=self.augment_intercept,
                )
            self.fill += take
            self.rows += take
            at += take
            if self.fill == len(staged.x):
                self.flush()
            elif self.on_rows is not None:
                self.on_rows(staged, self.fill)

    def flush(self) -> None:
        """Hand the set being filled to the consumer as it stands (a ragged
        tail, or nothing but zeros: the resident ingest's empty tail
        shards)."""
        from spark_rapids_ml_tpu.telemetry import trace_range

        staged = self._take()
        if self.fill < staged.dirty:
            # a new set's rows past a ragged tail were zero for nothing; a
            # rewritten one's are an earlier chunk's, and under
            # nonfinite="allow" a stale row times w=0 is not zero
            with trace_range("ingest.stage"):
                staged.zero_from(self.fill)
        try:
            self.consume(staged, self.fill)
        finally:
            self.spare, self.staged = staged, None
            self.fill = 0


# ---------------------------------------------------------------------------
# Transfers in flight: every host-to-device transfer from its issue to its landing
# ---------------------------------------------------------------------------


class _Transfers:
    """What is on its way to which device. A ``device_put`` returns once a
    transfer is issued, and the host meets its end only where it happens to
    wait for something queued behind it; this is the one owner of the time
    between. The three places that issue a transfer tell it
    (:meth:`issued`): ``_DeviceChunk.put`` (a piece), ``stream_to_mesh``'s
    ``put_shard`` (a resident shard's buffers, one transfer a shard) and
    ``stream_fold``'s whole put (a caller's ``put_fn``, a bisection's halves:
    over a mesh one transfer on every device it touches, ``device="*"``).
    It learns of the landing by waiting for the arrays on a thread of its
    own, one a ``device`` label (``tpu-ml-h2d-wait-<device>``, daemon,
    started at that label's first transfer, ended by ``release_staging()``
    with the pool): a device's transfers are waited for in the order they
    were issued, and no device's wait stands behind another's. It holds the
    arrays until they are ready and no longer, raises nothing into the fit,
    compiles nothing and has no knob: it is always on, as the registry is.

    What it books when a transfer is ready (``path`` is ``"stream"`` or
    ``"mesh"``; ``device`` the index on the mesh's data axis, 0 without a
    mesh):

    - ``h2d.transfer_seconds{path, device}``: issue to ready;
    - ``h2d.link_busy_seconds{path}``: summed over the devices, the seconds
      in which that device had a transfer issued and not ready, by a count
      in flight a device (the exact union, no model of a link), booked up to
      every completion;
    - ``h2d.any_link_busy_seconds{path}``: the same count over all devices;
    - ``h2d.transfer_bytes{path}``: the bytes that became ready, so that
      bytes over busy seconds is one boundary's ratio;
    - ``h2d.transfers_failed{path}`` in place of the first and the fourth
      where the wait raised (a warning too), and the next is timed as ever;
    - a ``TraceAnnotation`` ``h2d.transfer`` on the waiting thread, so that a
      profiler's trace has a line a link beside the runtime's threads and
      the device, and a timeline span ``h2d.transfer`` with ``parent`` (the
      span that issued it, ``h2d.put``), ``estimator``, ``fit_id``,
      ``device``, ``path`` and ``bytes``: both from the moment the thread
      begins to wait for this transfer (its issue, or the one before it on
      that device becoming ready) to its ready.

    ``wait`` blocks until the arrays handed to it are ready
    (``jax.block_until_ready``; the tests' stubs sleep)."""

    def __init__(self, wait=None):
        self.wait = wait
        self.lock = threading.Lock()
        self.waiters: dict[str, tuple[queue.SimpleQueue, threading.Thread]] = {}
        # by (path, device), and (path, None) for "any device": the transfers
        # issued and not ready, and the time its busy seconds are booked up to
        self.flying: dict[tuple, int] = {}
        self.booked_to: dict[tuple, float] = {}

    @staticmethod
    def _keys(path: str, devices: tuple) -> list[tuple]:
        return [(path, d) for d in devices] + [(path, None)]

    def issued(self, arrays, nbytes: int, devices, path: str, t0: float) -> None:
        """``arrays`` (``nbytes`` of them) were just put to ``devices``
        (indices on the data axis) under ``path``, the put having begun at
        ``t0`` (``time.perf_counter()``). Called on the issuing thread,
        inside its ``h2d.put``: a thread of its own has no context
        variables."""
        from spark_rapids_ml_tpu.telemetry import (
            current_estimator, current_fit_id, current_span,
        )

        devices = tuple(devices)
        label = str(devices[0]) if len(devices) == 1 else "*"
        cause = (current_span(), current_estimator() or "", current_fit_id() or "")
        with self.lock:
            for key in self._keys(path, devices):
                if not self.flying.get(key):
                    self.booked_to[key] = max(self.booked_to.get(key, t0), t0)
                self.flying[key] = self.flying.get(key, 0) + 1
            if label not in self.waiters:
                tasks: queue.SimpleQueue = queue.SimpleQueue()
                thread = threading.Thread(
                    target=self._watch, args=(tasks,),
                    name=f"tpu-ml-h2d-wait-{label}", daemon=True,
                )
                self.waiters[label] = (tasks, thread)
                thread.start()
            # under the lock, so that nothing is queued behind the end
            # marker of a thread that close() ends meanwhile
            self.waiters[label][0].put(
                [arrays, nbytes, devices, path, label, t0, cause]
            )

    def _watch(self, tasks: queue.SimpleQueue) -> None:
        while (task := tasks.get()) is not None:
            self._await(task)
            del task

    def _await(self, task: list) -> None:
        """Wait for one transfer's arrays and book it. The task is emptied
        first, so the arrays have this frame's name alone and go the moment
        they are ready, before anything is booked."""
        import jax

        arrays, nbytes, devices, path, label, t0, cause = task
        task.clear()
        begun = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("h2d.transfer"):
                (self.wait or jax.block_until_ready)(arrays)
            end, ready = time.perf_counter(), True
        except Exception:  # noqa: BLE001 — this thread goes on to the next
            end, ready = time.perf_counter(), False
            logger.warning(
                "the wait for a host-to-device transfer failed; it is booked "
                "in h2d.transfers_failed and not timed", exc_info=True,
            )
        del arrays
        with self.lock:
            busy = []
            for key in self._keys(path, devices):
                self.flying[key] -= 1
                busy.append(max(0.0, end - self.booked_to[key]))
                self.booked_to[key] = max(self.booked_to[key], end)
        REGISTRY.counter_inc("h2d.link_busy_seconds", sum(busy[:-1]), path=path)
        REGISTRY.counter_inc("h2d.any_link_busy_seconds", busy[-1], path=path)
        if not ready:
            REGISTRY.counter_inc("h2d.transfers_failed", path=path)
            return
        REGISTRY.histogram_record(
            "h2d.transfer_seconds", end - t0, path=path, device=label
        )
        REGISTRY.counter_inc("h2d.transfer_bytes", nbytes, path=path)
        parent, estimator, fit_id = cause
        TIMELINE.record_span(
            "h2d.transfer", begun, end, parent=parent, estimator=estimator,
            fit_id=fit_id, device=label, path=path, bytes=nbytes,
        )

    def in_flight(self) -> int:
        """Transfers issued and not yet booked."""
        with self.lock:
            return sum(n for (_, d), n in self.flying.items() if d is None)

    def close(self) -> None:
        """End the threads, each once it has waited out what it was already
        handed; the next transfer starts another."""
        with self.lock:
            waiters, self.waiters = self.waiters, {}
        for tasks, _ in waiters.values():
            tasks.put(None)


# the one owner, fed by the three places that issue a transfer
_transfers = _Transfers()


# Pieces a device's share of a chunk is put in, and how many of them a device
# may hold that have not landed yet. A piece's transfer runs while the next is
# staged, and what a device holds beside its chunk is the pieces in flight:
# the host stages faster than a link carries, and while the last chunk's fold
# runs no landing can, so without a bound most of a second chunk piles up
# there. 5 of 16 is the room the link fills during a fold of a 4 GiB chunk at
# n=2048, and leaves the device's peak a fifth over one chunk's (PERF.md
# section 6, PR 36, has the readings these two were chosen by; PR 35 read
# pieces of 64 to 512 MiB within a tenth of one another). Properties of the
# mechanism, not knobs.
_PIECES = 16
_PIECES_IN_FLIGHT = 5


@functools.cache
def _land_piece_prog():
    """Write a piece's arrays into a share's at row ``at`` (module
    ``jit__land_piece``). The share's arrays are donated and the row is
    traced, so the update is in place and one executable a device and a
    shape serves every piece. The second result is ready once the landing
    has run: the piece's room on the device is free again."""
    import jax
    from jax import lax

    def _land_piece(share, piece, at):
        landed = [
            lax.dynamic_update_slice_in_dim(s, p, at, axis=0)
            for s, p in zip(share, piece)
        ]
        return landed, at + 1

    return jax.jit(_land_piece, donate_argnums=0)


@functools.cache
def _new_share_prog(key: tuple, device):
    """Zeroed arrays of ``key``'s shapes and dtypes made ON ``device`` (None:
    the default one): a program with no argument whose results live there.
    Not ``jnp.zeros(..., device=device)``: that fills its shard on the
    default device and copies it over, so on a host of four chips the first
    held three more shares of a chunk while a stream started (12.9-13.8 GB
    where 6.3 were meant: PERF.md section 6, PR 36)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    def _new_share():
        return [jnp.zeros(shape, dtype) for shape, dtype in key]

    if device is None:
        return jax.jit(_new_share)
    return jax.jit(_new_share, out_shardings=SingleDeviceSharding(device))


class _DeviceChunk:
    """The one chunk-sized set of device arrays a streamed fold owns (``x``,
    ``w`` and ``y`` where labels flow, each device's share of them where
    ``place``, a ``parallel.gram.ChunkPut``, says a whole put would leave
    it), and how much of the chunk being staged is in it already.

    A PIECE is one of ``_PIECES`` of a device's share of the chunk: a run
    of consecutive staged rows that go to one device. A piece that is
    whole in the staging set is put at once (``h2d.put``, asynchronous) and
    its landing enqueued: one program that writes it into the share's arrays
    in place, the arrays donated, so the transfer runs while the rest of the
    chunk is staged and a device never holds a second chunk, only the pieces
    in flight. The device runs its queue in order, so a landing enqueued
    after the last chunk's fold cannot overwrite rows that fold still reads.
    Every chunk lands all its pieces, the zeroed rows past a ragged tail
    among them, so nothing of the chunk before is left. Counter
    ``h2d.pieces{path="stream"}`` books one a piece put.

    Two things about when. A piece is landed one put late, just before the
    next put: a program's dispatch queues behind the runtime's host work on
    a transfer just issued (its layout change), which by the next put is
    over. And a device holds at most ``_PIECES_IN_FLIGHT`` pieces that have
    not landed: before a put that would be one more, the host waits for the
    oldest landing (span ``h2d.wait`` inside ``h2d.put``, bounded by
    ``wait``), which is the link setting the host's pace, not a stall: the
    bytes in flight are what keeps the link busy meanwhile.

    THE BUFFER RULE holds piece by piece through the chunk's: what the
    fold's ``staged.placed`` keeps is :meth:`arrays`, which are ready once
    every landing has run, and a landing runs only after its piece's
    transfer."""

    def __init__(self, place, wait):
        self.place = place
        self.wait = wait
        self.shares: list[tuple] = []  # (lo, hi, device): who holds which rows
        self.parts: list[list] | None = None  # each share's device arrays
        self.done: list[int] = []  # rows of each share put, of this chunk
        self.pending: tuple | None = None  # (share, piece, row): put, to land
        self.flying: list[collections.deque] = []  # each share's landings

    def drop(self) -> None:
        """Let go of the device arrays (a bisection needs their room; the
        stream has ended)."""
        self.parts, self.done, self.pending, self.flying = None, [], None, []

    def next_chunk(self) -> None:
        """The pieces start over: of the next chunk, or of this one again."""
        self.done = []

    @staticmethod
    def _buffers(staged: _StagingSet) -> list[np.ndarray]:
        """The set's buffers in the order the fold takes its arrays."""
        return [b for b in (staged.x, staged.w, staged.y) if b is not None]

    def _land(self) -> None:
        if self.pending is None:
            return
        (i, piece, at), self.pending = self.pending, None
        try:
            self.parts[i], landed = _land_piece_prog()(
                self.parts[i], piece, np.int32(at)
            )
        except BaseException:
            self.done = []  # its rows are not in the chunk: every piece again
            raise
        self.flying[i].append(landed)

    def put(self, staged: _StagingSet, fill: int) -> None:
        """Put every piece not yet put that is whole now that rows
        ``[:fill]`` of ``staged`` are written."""
        import jax

        from spark_rapids_ml_tpu.telemetry import trace_range

        bufs = self._buffers(staged)
        # a landing that failed took the donated arrays with it
        if self.parts is None or any(
            a.is_deleted() for part in self.parts for a in part
        ):
            self.drop()
            self.shares = self.place.shares(len(staged.x))
            self.parts = [
                _new_share_prog(
                    tuple(((hi - lo,) + b.shape[1:], b.dtype) for b in bufs), d
                )()
                for lo, hi, d in self.shares
            ]
            self.flying = [collections.deque() for _ in self.shares]
        if not self.done:
            self.done = [0] * len(self.shares)
        for i, (lo, hi, device) in enumerate(self.shares):
            rows = -(-(hi - lo) // _PIECES)
            while (at := self.done[i]) < hi - lo:
                take = min(rows, hi - lo - at)
                if lo + at + take > fill:
                    break
                with trace_range("h2d.put"):
                    self._land()
                    while len(self.flying[i]) >= _PIECES_IN_FLIGHT:
                        oldest = self.flying[i].popleft()
                        if not oldest.is_ready():
                            with trace_range("h2d.wait"):
                                self.wait(oldest)
                    t0 = time.perf_counter()
                    cut = [b[lo + at : lo + at + take] for b in bufs]
                    piece = [jax.device_put(c, device) for c in cut]
                    _transfers.issued(
                        piece, sum(c.nbytes for c in cut), (i,), "stream", t0
                    )
                self.pending = (i, piece, at)
                self.done[i] += take
                REGISTRY.counter_inc("h2d.pieces", path="stream")

    def arrays(self, staged: _StagingSet) -> list:
        """The chunk's arrays, every piece of it put and its landing
        enqueued: what a whole put of ``staged`` would have handed the
        fold."""
        self.put(staged, len(staged.x))
        self._land()
        return [
            self.place.assemble(b.shape, [part[k] for part in self.parts])
            for k, b in enumerate(self._buffers(staged))
        ]


def _batches(chunks: Iterator, n: int, features_col: str | None) -> Iterator:
    """The head of both ingests: each next ``(x, y, w)`` batch of ``chunks``
    pulled from the source alone under span ``ingest.chunk`` (the staging
    copy has its own, ``ingest.stage``),
    booked in ``ingest.rows`` / ``ingest.bytes`` / ``ingest.chunk_rows`` and
    held to the width ``n``."""
    from spark_rapids_ml_tpu.telemetry import trace_range

    while True:
        with trace_range("ingest.chunk"):
            try:
                xc, yc, wc = next(chunks)
            except StopIteration:
                return
        REGISTRY.counter_inc("ingest.rows", len(xc))
        REGISTRY.counter_inc("ingest.bytes", xc.nbytes)
        REGISTRY.histogram_record("ingest.chunk_rows", len(xc))
        if xc.ndim != 2 or xc.shape[1] != n:
            raise ValueError(
                f"feature dimension changed mid-stream: expected {n}, "
                f"got {xc.shape[1:]} in column {features_col!r}"
            )
        yield xc, yc, wc


def release_staging() -> None:
    """Drop the staging set kept between ingests (one chunk or shard of host
    memory, held so that the next ingest of the same shape writes into pages
    that are already mapped) and the host pass's pool (its threads end once
    the blocks already handed to them have; the next batch large enough to
    cut starts another), and end the threads that wait for the transfers in
    flight (``_Transfers``: each once it has waited out what it was handed;
    the next transfer starts another)."""
    with _kept_staging_lock:
        _kept_staging.clear()
    with _pool_lock:
        while _pool:
            _pool.pop().close()
    _transfers.close()


def stream_fold(
    source,
    fold_fn,
    *,
    n: int,
    init,
    features_col: str | None = None,
    label_col: str | None = None,
    weight_col: str | None = None,
    augment_intercept: bool = False,
    rows: int | None = None,
    chunk_rows: int | None = None,
    put_fn=None,
    checkpointer=None,
    checkpoint_every: int | None = None,
    min_chunk_rows: int | None = None,
    fold_wait_timeout_s: float | None = None,
    nonfinite: str | None = None,
) -> StreamFold:
    """Fold ``source`` chunk-wise through a donated device accumulator —
    the out-of-core fit pipeline. The full [rows, n] array is NEVER
    assembled: device memory stays O(chunk + carry), so fit() scales to
    row counts that cannot fit in HBM.

    The pipeline double-buffers via JAX async dispatch: ``fold_fn`` must be
    a jitted step with ``donate_argnums=0`` (ops.linalg.gram_fold_step and
    friends), whose call returns the moment it is dispatched — so while
    chunk i's fold executes on the MXU, the host is already extracting and
    ``device_put``-ing chunk i+1. Each phase is traced, so the overlap is
    observable and the host's seconds have names (telemetry.metrics()):
    ``ingest.chunk`` (the pull), the staging pipeline's ``ingest.stage``
    and ``stage.reclaim`` (:class:`_Stager`: a full set is one fold chunk
    here), ``fold.dispatch`` with ``h2d.put``, the chunk's verdict
    (``fold.wait``, ``ingest.scan``) and ``fold.enqueue`` inside it, and the
    terminal ``fold.wait``; ``fold.input_in_flight`` counts the chunks whose
    transfer had not landed when their fold was enqueued (under
    ``nonfinite="allow"`` alone: a chunk that is asked about has landed).

    PIECES. A chunk whose placement is a ``parallel.gram.ChunkPut`` (the
    default, and ``stream_fold_over_mesh``'s) is not put when its set is
    full but while it fills: this function asks the stager for the rows as
    they are written (``_Stager``'s ``on_rows``; the resident ingest does
    not ask, and is handed full sets as ever) and puts each piece (one of
    ``_PIECES`` of a device's share of the chunk) as soon as it is whole,
    into the one chunk-sized set of device arrays the stream owns
    (:class:`_DeviceChunk`: ``h2d.put`` a piece, most of them outside
    ``fold.dispatch``, with ``h2d.wait`` inside where the device holds as
    many pieces as it may; counter ``h2d.pieces``). The dispatch puts what
    is left, the last piece or a
    ragged tail's zeroed rows, and goes on as for a chunk put whole: one
    wait for the landings, one verdict, ONE fold of the chunk's shape. What
    a put by pieces changes is when the bytes move and nothing they are: the
    device arrays are what a whole put would have handed the fold, to the
    bit. No caller in the package passes another ``put_fn``: one that is
    not a ``ChunkPut`` is handed the full buffers, one put a chunk, which is
    the tests' reference for the pieces and their spies' way in; the halves
    of a bisected chunk are put whole too. Of whole puts ``overlapped``
    counts the dispatches that met the last fold still running; a stream by
    pieces has waited for a landing queued behind that fold by then
    (``h2d.wait``), and reads 0 there.

    The copy is the host's per-byte work on a batch, and a batch large enough
    to cut has it run by row blocks on the host pass's pool of threads
    (``_row_blocks``, ``_run_blocks``): this thread hands the blocks over and
    waits, inside ``ingest.stage``, so the span stays one a slice and reads
    the wall time of the pass. A batch too small to cut runs here, through
    the same helpers (``ingest.batches{path}`` says which).

    THE VERDICT. Whether a chunk is finite is asked once a chunk, of the
    chunk that was put and in the dtype the device holds, after ``put`` and
    before its fold is enqueued, so a chunk that is not clean never reaches
    the carry or a checkpoint. What ``put`` returned decides where: a
    ``jax.Array`` is waited for (span ``fold.wait``, bounded as the terminal
    wait is: with one staging set the host would wait for this landing
    before it writes the next chunk anyway, and the fold cannot start before
    it) and asked on its device by one fused reduction over ``x``, ``w`` and
    ``y`` (program ``jit__chunk_finite``; span ``ingest.scan`` is its
    dispatch and the read of its answer, nothing else); a host array (an
    identity ``put_fn``) is asked on the host of the staged buffers
    (``_all_finite``). Counter ``ingest.verdicts{where, clean}`` books each.
    After a "no" the staging buffers are still the host's, so the rows are
    found there (``_bad_rows``): ``raise`` ends the fit with their count;
    ``skip`` zeroes them with weight 0 (the framework's mask, exact as pads
    are), moves them from ``rows`` to ``skipped_rows`` (the resume cursor,
    their sum, does not move), puts the chunk again (every piece of it, into
    the same device arrays), asks again and folds it, unless no row of
    weight is left. The OOM bisection asks of each
    piece it puts, and the retry of a transient asks again. A float64 beyond
    float32's range is ``inf`` on a float32 device, and so a non-finite row.

    ``source`` is either a DataFrame-shaped object (localspark / pyspark —
    drained via the same strategy-gated ``_iter_chunks`` the resident
    ingest uses; requires ``features_col``) or any iterable of host chunks:
    bare ``[c, n]`` arrays or ``(x,)``/``(x, y)``/``(x, y, w)`` tuples.

    ``fold_fn(carry, x, w)`` — or ``fold_fn(carry, x, y, w)`` when labels
    flow — receives fixed-shape [chunk_rows, n(+1)] device chunks; ``w``
    follows the framework-wide masking convention (instance weights on true
    rows, 0.0 on pads), so ragged tails and chunk sizes that don't divide
    the row count are exact with no count fix-up. ``init`` is the zero
    carry (or a callable returning it); ``put_fn`` overrides chunk
    placement (``parallel.gram.ChunkPut(mesh)`` shards chunks over a mesh).

    The fold self-heals (resilience/ package):

    - fault sites ``ingest.chunk`` / ``fold.dispatch`` / ``fold.wait`` are
      injectable, and classified-transient dispatch failures retry under
      the shared :class:`~spark_rapids_ml_tpu.resilience.retry.RetryPolicy`
      (injection happens BEFORE the donated fold consumes its buffers, so
      the carry stays valid for the retry);
    - a ``RESOURCE_EXHAUSTED``-classified dispatch failure bisects: the
      failed chunk is re-staged at half the rows (w=0 pads keep it exact)
      and ``chunk_rows`` drops for the rest of the stream — one re-trace
      of the jitted fold at the new static shape, floor-bounded by
      ``min_chunk_rows`` (``TPU_ML_STREAM_CHUNK_FLOOR``; mesh callers pass
      the data-axis size so bisected chunks still shard evenly);
    - with a ``checkpointer`` (``utils.checkpoint.TrainingCheckpointer``),
      the carry + chunk cursor are durably saved every
      ``checkpoint_every`` chunks and a later call with the same
      checkpointer RESUMES: already-consumed source rows are skipped and
      the fold continues from the restored carry — bitwise-identical to
      the uninterrupted fit (same chunks, same fold order);
    - non-finite input rows follow ``nonfinite``
      (``TPU_ML_NONFINITE_POLICY``): ``raise`` (default), ``skip`` (mask +
      count ``rows.nonfinite_skipped``), or ``allow`` (nothing is asked);
    - the waits are bounded (``TPU_ML_FOLD_WAIT_TIMEOUT_S``): a hung
      device surfaces a ``FoldHangTimeout`` diagnosis, not a block.
    """
    import jax

    from spark_rapids_ml_tpu.parallel import gram as G
    from spark_rapids_ml_tpu.resilience import faults
    from spark_rapids_ml_tpu.resilience import retry as R
    from spark_rapids_ml_tpu.telemetry import current_fit_id, trace_range
    from spark_rapids_ml_tpu.utils.config import (
        VALID_NONFINITE_POLICIES,
        get_config,
    )

    cfg = get_config()
    dt = wire_dtype()
    # what a put of ``dt`` becomes on the device (float32 where x64 is off):
    # the staging set has that dtype, so the slice copy below is the one
    # cast and device_put finds nothing left to canonicalise on the host
    stage_dt = np.dtype(jax.dtypes.canonicalize_dtype(dt))
    n_eff = n + 1 if augment_intercept else n
    if chunk_rows is None:
        chunk_rows = stream_chunk_rows()
    if min_chunk_rows is None:
        min_chunk_rows = max(
            1,
            int(os.environ.get(STREAM_CHUNK_FLOOR_VAR, DEFAULT_STREAM_CHUNK_FLOOR)),
        )
    if checkpoint_every is None:
        checkpoint_every = cfg.stream_checkpoint_every_chunks
    if fold_wait_timeout_s is None:
        fold_wait_timeout_s = float(cfg.fold_wait_timeout_s)
    nonfinite = nonfinite or cfg.nonfinite_policy
    if nonfinite not in VALID_NONFINITE_POLICIES:
        raise ValueError(
            f"nonfinite={nonfinite!r} must be one of {VALID_NONFINITE_POLICIES}"
        )
    policy = R.RetryPolicy.from_config()
    transient_only = frozenset({R.ErrorClass.TRANSIENT})
    want_y = label_col is not None
    put = put_fn if put_fn is not None else G.ChunkPut(None)
    # a chunk that goes where a ChunkPut says is put by pieces while it is
    # staged; any other put_fn is handed whole chunks, as ever
    device_chunk = (
        _DeviceChunk(
            put, lambda a: _bounded_wait(a, fold_wait_timeout_s, site=None)
        )
        if isinstance(put, G.ChunkPut)
        else None
    )
    put_ahead = True  # until a piece put ahead fails, then from the next chunk

    df_like = features_col is not None and any(
        callable(getattr(source, attr, None))
        for attr in ("_parts", "toArrow", "toPandas", "toLocalIterator", "collect")
    )

    def chunks():
        if df_like:
            nonlocal rows
            if rows is None and callable(getattr(source, "count", None)):
                rows = source.count()
            yield from _iter_chunks(
                source, features_col, label_col, weight_col,
                est_bytes=(rows or 0) * n * 8,
            )
            return
        for item in source:
            if isinstance(item, tuple):
                x = np.asarray(item[0])
                y = np.asarray(item[1]) if len(item) > 1 and item[1] is not None else None
                w = np.asarray(item[2]) if len(item) > 2 and item[2] is not None else None
            else:
                x, y, w = np.asarray(item), None, None
            yield x, y, w

    carry = init() if callable(init) else init

    seen = 0
    skipped = 0
    n_chunks = 0
    overlapped = 0
    max_put = 0
    bisections = 0
    resumed = False
    resume_skip = 0  # raw source rows already consumed by a prior run
    last_ckpt = 0

    if checkpointer is not None:
        found = _restore_stream_checkpoint(checkpointer, carry)
        if found is not None:
            carry, state = found
            seen = int(state["rows_seen"])
            skipped = int(state["skipped_rows"])
            n_chunks = int(state["chunks"])
            # resume at the (possibly bisected) size the prior run settled
            # on — re-OOMing at the original size would be self-inflicted
            chunk_rows = min(chunk_rows, int(state["chunk_rows"]))
            last_ckpt = n_chunks
            resume_skip = seen + skipped
            resumed = True
            REGISTRY.counter_inc("stream.resumes")
            TIMELINE.record_instant(
                "stream.resume", chunk=n_chunks, rows_seen=seen
            )
            logger.warning(
                "resuming streamed fit from checkpoint (chunk %d, %d rows "
                "already folded)", n_chunks, seen,
            )

    # live-health heartbeat: the monitor (telemetry.health) compares
    # stream.last_beat against time.monotonic() and flags the stream stale
    # once the gap exceeds TPU_ML_HEALTH_STALE_S — but only while
    # stream.active is set, so an idle process stays OK. Unlike the opt-in
    # stderr progress line below this is always on: one gauge write per
    # dispatched chunk.
    REGISTRY.gauge_set("stream.active", 1)
    REGISTRY.gauge_set("stream.last_beat", time.monotonic())

    # live progress heartbeat (TPU_ML_PROGRESS): opt-in stderr line so a
    # multi-minute out-of-core fit is not silent. Retry counts come from
    # the registry delta (the retries happen inside call_with_retry below).
    progress_every = progress_interval()
    progress_t0 = time.perf_counter()
    last_beat = progress_t0
    retries0 = (
        REGISTRY.snapshot().counter("retry.attempts") if progress_every else 0
    )

    def maybe_heartbeat():
        nonlocal last_beat
        if not progress_every:
            return
        now = time.perf_counter()
        if now - last_beat < progress_every:
            return
        last_beat = now
        elapsed = max(now - progress_t0, 1e-9)
        retries = REGISTRY.snapshot().counter("retry.attempts") - retries0
        fid = current_fit_id() or ""
        print(
            f"[tpu-ml progress{' ' + fid if fid else ''}] "
            f"rows={seen} ({seen / elapsed:,.0f} rows/s) "
            f"chunks={n_chunks} chunk_rows={chunk_rows} "
            f"retries={retries:g} bisections={bisections}",
            file=sys.stderr,
            flush=True,
        )

    def shards_of(array) -> int:
        """One device's share each: the data axis of a mesh, 1 without one
        (and for a host array)."""
        return len(getattr(array, "addressable_shards", (array,)))

    def chunk_is_finite(arrays, bufs) -> bool:
        """The verdict on one chunk that was put, asked where ``put``
        left it: ``arrays`` on their device(s), once they have landed (the
        wait is span ``fold.wait``'s, bounded as the terminal one is; span
        ``ingest.scan`` holds the program's dispatch and the read of its
        answer alone), or, where ``put`` handed back host arrays, the staged
        ``bufs`` on the host."""
        on_device = isinstance(arrays[0], jax.Array)
        if on_device:
            with trace_range("fold.wait"):
                _bounded_wait(arrays, fold_wait_timeout_s)
        with trace_range("ingest.scan"):
            if on_device:
                clean = bool(_chunk_finite_prog()(arrays))
            else:
                clean = all(_all_finite(b) for b in bufs)
        REGISTRY.counter_inc(
            "ingest.verdicts",
            where="device" if on_device else "host",
            clean="yes" if clean else "no",
        )
        return clean

    def mask_bad_rows(bufs) -> None:
        """What a "no" costs: the host finds the rows in the staged
        buffers, which are still its own. ``raise`` ends the fit; ``skip``
        zeroes them with weight 0 (the framework's mask, exact as pads are)
        and moves them from ``seen`` to ``skipped``."""
        nonlocal seen, skipped
        bad = _bad_rows(bufs)
        n_bad = int(bad.sum())
        if not n_bad:
            raise ValueError(
                "a streamed chunk is not finite where put_fn left it, and "
                "every staged row of it is: put_fn may place a chunk, not "
                "change its values"
            )
        if nonfinite == "raise":
            raise ValueError(
                f"{n_bad} non-finite input row(s) in a streamed chunk; set "
                "TPU_ML_NONFINITE_POLICY=skip to drop and count them instead"
            )
        for b in bufs:
            b[bad] = 0
        seen -= n_bad
        skipped += n_bad
        REGISTRY.counter_inc("rows.nonfinite_skipped", n_bad)

    def attempt_fold(staged, xb, yb, wb):
        nonlocal carry, n_chunks, overlapped, max_put
        busy = any(
            not leaf.is_ready()
            for leaf in jax.tree_util.tree_leaves(carry)
            if hasattr(leaf, "is_ready")
        )
        bufs = [b for b in (xb, wb, yb) if b is not None]
        with trace_range("fold.dispatch"):
            # inject BEFORE the donated fold consumes its buffers, so the
            # carry is still valid when the retry re-enters
            faults.inject("fold.dispatch")
            # arrays put from the staging set are kept until it is reclaimed
            placed = staged.placed if xb is staged.x else []
            # the set itself goes by pieces, and most of them have gone; a
            # bisection's buffers, smaller than the chunk, are put whole
            pieced = device_chunk is not None and xb is staged.x

            def put_chunk():
                if pieced:
                    arrays = device_chunk.arrays(staged)
                else:
                    with trace_range("h2d.put"):
                        t0 = time.perf_counter()
                        arrays = [put(b) for b in bufs]
                        # one transfer on every device the chunk touches
                        _transfers.issued(
                            arrays, sum(b.nbytes for b in bufs),
                            range(shards_of(arrays[0])), "stream", t0,
                        )
                placed.extend(arrays)
                return arrays

            arrays = put_chunk()
            # the verdict, before the fold: a chunk that is not clean never
            # reaches the carry. The arrays have landed by then, so the
            # buffers they were put from may be written (THE BUFFER RULE; an
            # array that shares their memory changes with them)
            while nonfinite != "allow" and not chunk_is_finite(arrays, bufs):
                mask_bad_rows(bufs)
                if not wb.any():
                    return  # no true row left: nothing to fold
                # put again, the stale arrays let go first: one chunk resident
                # (by pieces: every piece again, into the same arrays)
                del placed[-len(arrays):]
                del arrays
                if pieced:
                    device_chunk.next_chunk()
                arrays = put_chunk()
            xd, wd = arrays[:2]
            # the fold's device time holds a wait for its chunk's DMA when
            # the chunk has not landed by now (is_ready: no sync), which
            # only a chunk nothing was asked of can be
            if hasattr(xd, "is_ready") and not xd.is_ready():
                REGISTRY.counter_inc("fold.input_in_flight")
            with trace_range("fold.enqueue"):
                carry = fold_fn(carry, xd, *arrays[2:], wd)
        if busy:
            overlapped += 1
        nbytes = sum(b.nbytes for b in bufs)
        max_put = max(max_put, nbytes)
        REGISTRY.counter_inc("h2d.bytes", nbytes, path="stream")
        REGISTRY.counter_inc("h2d.shards", shards_of(xd), path="stream")
        n_chunks += 1

    def dispatch_buffers(staged):
        """Fold one staged chunk, retrying transients and bisecting OOMs:
        a RESOURCE_EXHAUSTED-classified failure re-stages the chunk as
        smaller fixed-shape chunks (w=0 pads keep it exact) and drops
        ``chunk_rows`` for the rest of the stream."""
        nonlocal chunk_rows, bisections
        queue = [(staged.x, staged.y, staged.w)]
        while queue:
            bx, by, bw = queue.pop(0)
            try:
                R.call_with_retry(
                    lambda: attempt_fold(staged, bx, by, bw),
                    site="fold.dispatch",
                    policy=policy,
                    retry_on=transient_only,
                )
            except Exception as e:  # noqa: BLE001 — classified below
                if R.classify(e) is not R.ErrorClass.RESOURCE_EXHAUSTED:
                    raise
                cur = len(bx)
                half = cur // 2
                new = half - half % min_chunk_rows
                if new < min_chunk_rows or new >= cur:
                    raise  # floor reached: the OOM is not chunk-sized
                logger.warning(
                    "device OOM folding a %d-row chunk; bisecting to %d "
                    "rows and re-dispatching", cur, new,
                )
                REGISTRY.counter_inc("chunk.bisections")
                TIMELINE.record_instant(
                    "chunk.bisection", from_rows=cur, to_rows=new
                )
                bisections += 1
                if device_chunk is not None:
                    device_chunk.drop()  # of the shape that did not fit
                queue[:0] = _split_chunk_buffers(bx, by, bw, new)
                chunk_rows = min(chunk_rows, new)

    def fold_set(staged, fill):
        """The stager's consumer: a set is one fold chunk. It names nothing
        that holds the stager, so no cycle outlives the fold with a carry
        in it."""
        nonlocal seen, last_ckpt, put_ahead
        seen += fill
        dispatch_buffers(staged)
        if device_chunk is not None:
            device_chunk.next_chunk()
            put_ahead = True
        REGISTRY.gauge_set("stream.last_beat", time.monotonic())
        if fill < len(staged.x):
            return  # the ragged tail: the stream ends here
        maybe_heartbeat()
        if (
            checkpointer is not None
            and n_chunks - last_ckpt >= checkpoint_every
        ):
            _save_stream_checkpoint(
                checkpointer, carry, chunks=n_chunks, seen=seen,
                skipped=skipped, chunk_rows=chunk_rows,
            )
            last_ckpt = n_chunks

    def put_pieces(staged, fill):
        """The stager's word that rows ``[:fill]`` of a set still filling
        are written: put the pieces that are whole. This is the dispatch's
        work begun ahead of it, so a failure the dispatch would retry or
        bisect (TRANSIENT, RESOURCE_EXHAUSTED) is left to it: nothing more
        of this chunk is put ahead, and the dispatch puts whatever has not
        landed (counter ``h2d.put_ahead_abandoned``, and a warning). Anything
        else ends the stream here: a hung device (``FoldHangTimeout`` from
        ``h2d.wait``) or a poisoned runtime does not get better by the next
        slice."""
        nonlocal put_ahead
        if not put_ahead:
            return
        try:
            device_chunk.put(staged, fill)
        except Exception as e:  # noqa: BLE001 — classified here
            if R.classify(e) not in R.RETRYABLE_DEFAULT:
                raise
            put_ahead = False
            REGISTRY.counter_inc("h2d.put_ahead_abandoned", path="stream")
            logger.warning(
                "a piece put ahead of its chunk failed (%s: %s); the rest of "
                "the chunk is put at its dispatch", type(e).__name__, e,
            )

    # the key is asked for each new set: a bisection changes chunk_rows
    stager = _Stager(
        lambda: (chunk_rows, n_eff, stage_dt, want_y), fold_set,
        augment_intercept=augment_intercept,
        on_rows=put_pieces if device_chunk is not None else None,
    )
    try:
        with stager:
            for xc, yc, wc in _batches(chunks(), n, features_col):
                TIMELINE.record_instant(
                    "stream.chunk", rows=len(xc), nbytes=int(xc.nbytes)
                )
                if want_y and yc is None:
                    raise ValueError("label column missing from a streamed chunk")
                if resume_skip:
                    # replaying an already-checkpointed prefix: drop the raw
                    # rows a prior run consumed (seen + skipped: a masked row
                    # counts among them, so the cursor is exact whatever the
                    # non-finite policy)
                    drop = min(resume_skip, len(xc))
                    resume_skip -= drop
                    xc = xc[drop:]
                    yc = yc[drop:] if yc is not None else None
                    wc = wc[drop:] if wc is not None else None
                    if not len(xc):
                        continue
                xc = R.call_with_retry(
                    lambda: faults.inject("ingest.chunk", xc),
                    site="ingest.chunk",
                    policy=policy,
                    retry_on=transient_only,
                )
                stager.feed(xc, yc, wc)
            if stager.fill:
                stager.flush()  # ragged tail: pads ride the w=0 mask, exactly
            if seen == 0:
                raise ValueError("empty dataset")
            if rows is not None and seen + skipped != rows:
                raise ValueError(
                    f"dataset produced {seen + skipped} rows while streaming "
                    f"but count() reported {rows}; cache() the DataFrame if "
                    "its source is nondeterministic"
                )
            with trace_range("fold.wait"):
                carry = _bounded_wait(carry, fold_wait_timeout_s)
    finally:
        # clear on EVERY exit (raises included): the monitor treats an
        # inactive stream as OK regardless of beat age, so a dead stream
        # must not read as "wedged" forever
        REGISTRY.gauge_set("stream.active", 0)
        if device_chunk is not None:
            device_chunk.drop()
    # per-stream H2D↔compute overlap evidence: fraction of dispatches
    # issued while the prior fold was still on device. Recorded as a
    # histogram so end_fit's snapshot delta reads a per-fit mean into
    # FitReport.overlap_fraction.
    REGISTRY.histogram_record(
        "stream.overlap_fraction", overlapped / n_chunks if n_chunks else 0.0
    )
    return StreamFold(
        carry=carry,
        rows=seen,
        chunks=n_chunks,
        overlapped=overlapped,
        max_put_bytes=max_put,
        skipped_rows=skipped,
        bisections=bisections,
        resumed=resumed,
    )


def stream_fold_over_mesh(
    source,
    step,
    example,
    mesh,
    *,
    n: int,
    rows: int | None = None,
    features_col: str | None = None,
    label_col: str | None = None,
    weight_col: str | None = None,
    checkpointer=None,
    checkpoint_every: int | None = None,
    nonfinite: str | None = None,
) -> StreamFold:
    """:func:`stream_fold` over a device mesh, with the geometry of the
    stacked-partials protocol (``parallel.gram``) owned here and nowhere
    else: chunks of :func:`stream_chunk_rows_for_mesh` rows sharded over the
    data axis (``ChunkPut``), a zero carry of ``example``'s statistics
    stacked one slice a device (``init_chunk_carry``; ``example`` is the
    pytree of the UNSTACKED statistics, arrays or ShapeDtypeStructs), an OOM
    bisection that stops at the data-axis size and keeps to its multiples,
    and the one allreduce at the end (``finalize_chunk_fold``): the result's
    ``carry`` is the replicated total.

    ``step(carry, x, w)`` — ``step(carry, x, y, w)`` with a ``label_col`` —
    is a donated collective-free fold of one sharded chunk into the stacked
    carry (``parallel.gram.sharded_gram_fold`` and friends). The other
    arguments are :func:`stream_fold`'s."""
    from spark_rapids_ml_tpu.parallel import gram as G
    from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

    res = stream_fold(
        source,
        step,
        n=n,
        init=G.init_chunk_carry(example, mesh),
        features_col=features_col,
        label_col=label_col,
        weight_col=weight_col,
        rows=rows,
        chunk_rows=stream_chunk_rows_for_mesh(mesh),
        put_fn=G.ChunkPut(mesh),
        checkpointer=checkpointer,
        checkpoint_every=checkpoint_every,
        min_chunk_rows=mesh.shape[DATA_AXIS],
        nonfinite=nonfinite,
    )
    res.carry = G.finalize_chunk_fold(res.carry, mesh)
    return res
