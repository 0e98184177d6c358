"""Executors as an SPMD mesh — the barrier-stage fit path.

This is the north-star architecture move over the reference: its fit()
reduces per-partition Gram matrices through the JVM heap and Spark's shuffle
(RapidsRowMatrix.scala:133-139). Here, the N partition tasks of ONE barrier
stage bootstrap a ``jax.distributed`` process group and execute a single
SPMD XLA program in which the cross-partition reduction is a ``psum``
collective — ICI on a TPU pod, Gloo/DCN on CPU hosts — and the driver only
ever receives the one already-reduced statistics row. No per-partition
[n, n] buffer crosses a process boundary or touches the driver.

How the Spark scheduler meets the mesh (SURVEY.md §7 hard part 2):

1. the estimator launches ``mapInArrow(fn, schema, barrier=True)`` — Spark's
   barrier execution mode guarantees all N tasks run simultaneously;
2. inside each task, one ``allGather`` round (BarrierTaskContext — pyspark's
   or localspark's) exchanges ``{rank, rows, coordinator}``: rank 0 proposes
   its address plus a free port as the ``jax.distributed`` coordinator, and
   the row counts let every task agree on a common padded shard shape
   (collectives need identical per-shard shapes; zero rows are exact for
   every monoid we reduce);
3. each task calls ``jax.distributed.initialize(coord, N, rank)`` — which
   must be that interpreter's FIRST JAX backend touch, which is why barrier
   stages run in fresh worker processes (localspark does this natively; on
   real Spark set ``spark.python.worker.reuse=false`` for barrier fits);
4. the global mesh spans every device of every task's process; the stats
   kernel + ``psum`` compile as one program via the same
   ``backend.mapreduce_data_axis`` scaffolding the in-process mesh path
   uses (parallel/gram.py);
5. rank 0 emits the replicated result as a single Arrow row; other ranks
   emit nothing.

The machinery is estimator-generic: every stats-monoid estimator
instantiates ``_MeshReducePartitionFn`` with its own shard kernel —
``MeshGramPartitionFn`` (PCA), ``MeshLinRegPartitionFn``
(LinearRegression), ``MeshMomentsPartitionFn`` (StandardScaler). The
fallback when barrier scheduling is unavailable stays the portable
driver-merge path in ``estimators.py`` (reference-parity architecture).
"""

from __future__ import annotations

import json
import socket
from typing import Iterator

import numpy as np
import pyarrow as pa

from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.spark import arrow_fns
from spark_rapids_ml_tpu.utils import columnar

MESH_FIELDS = ["xtx", "col_sum", "count", "mesh_size"]
LINREG_MESH_FIELDS = [
    "xtx", "xty", "x_sum", "y_sum", "y_sq", "count", "mesh_size",
]
MOMENTS_MESH_FIELDS = ["total", "total_sq", "count", "mesh_size"]


def get_barrier_context():
    """The live BarrierTaskContext — pyspark's inside a real Spark barrier
    task, localspark's inside a ``mapInArrow(..., barrier=True)`` stage."""
    try:
        from pyspark import BarrierTaskContext as SparkCtx  # type: ignore

        ctx = SparkCtx.get()
        if ctx is not None:
            return ctx
    except Exception:  # pyspark absent or not in a barrier task
        pass
    from spark_rapids_ml_tpu.localspark.taskcontext import BarrierTaskContext

    return BarrierTaskContext.get()


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _pad_to(mat: np.ndarray, rows: int) -> np.ndarray:
    if mat.shape[0] == rows:
        return mat
    out = np.zeros((rows,) + mat.shape[1:], dtype=mat.dtype)
    out[: mat.shape[0]] = mat
    return out


class _MeshReducePartitionFn:
    """Base barrier-stage plan function: one SPMD psum of a sum-monoid.

    Subclasses set ``FIELDS`` (output stat names, which always end with
    ``count`` and ``mesh_size``) and implement ``_shard_kernel()``. With
    ``USES_VECTORS`` unset the kernel takes ``(x_shard,)`` only; with it set
    the kernel takes ``(x_shard, w_shard, y_shard)`` where ``w`` carries
    instance weights on true rows and 0.0 on pad rows (the framework-wide
    masking convention) and ``y`` is the label shard — the vector operands
    are built and transferred only when a kernel actually consumes them.

    Picklable by construction (plain column names + tags, like every plan fn
    in ``arrow_fns``); everything heavy happens inside the task.
    """

    FIELDS: list[str] = []
    #: count comes from the rendezvous row total (exact under zero-padding)
    #: unless the kernel emits a weighted count itself
    COUNT_FROM_KERNEL = False
    #: kernel signature: (x,) when False, (x, w, y) when True
    USES_VECTORS = False

    def __init__(
        self,
        input_col: str,
        label_col: str | None = None,
        weight_col: str | None = None,
        precision: str = "highest",
    ):
        self.input_col = input_col
        self.label_col = label_col
        self.weight_col = weight_col
        self.precision = precision

    # -- subclass hooks ------------------------------------------------------
    def _prepare_matrix(self, mat: np.ndarray) -> np.ndarray:
        """Worker-side feature-matrix preprocessing before the rendezvous
        (e.g. appending the intercept column) — identity by default."""
        return mat

    def _shard_kernel(self):
        raise NotImplementedError

    def _run_on_mesh(self, mesh, gx, gw, gy) -> dict[str, np.ndarray]:
        """Execute the SPMD program on the bootstrapped global mesh and
        return host arrays. Default: one psum of ``_shard_kernel``'s monoid;
        full-fit subclasses override with an entire training loop."""
        import jax
        from jax.sharding import PartitionSpec as P

        from spark_rapids_ml_tpu.parallel import backend as B
        from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS

        operands = [gx]
        specs = [P(DATA_AXIS, None)]
        if self.USES_VECTORS:
            operands += [gw, gy]
            specs += [P(DATA_AXIS), P(DATA_AXIS)]
        stats = B.mapreduce_data_axis(
            self._shard_kernel(), mesh, in_specs=tuple(specs)
        )(*operands)
        return {
            name: np.asarray(jax.device_get(v)) for name, v in stats.items()
        }

    # -- the mapInArrow body --------------------------------------------------
    def __call__(
        self, batches: Iterator[pa.RecordBatch]
    ) -> Iterator[pa.RecordBatch]:
        ctx = get_barrier_context()
        rank = ctx.partitionId()
        size = len(ctx.getTaskInfos())

        mats, ys, ws = [], [], []
        for b in batches:
            if not b.num_rows:
                continue
            mat = self._prepare_matrix(columnar.extract_matrix(b, self.input_col))
            mats.append(mat)
            if self.label_col:
                ys.append(
                    np.asarray(
                        b.column(self.label_col).to_numpy(zero_copy_only=False),
                        dtype=np.float64,
                    )
                )
            if self.weight_col:
                ws.append(
                    columnar.validate_weights(
                        b.column(self.weight_col).to_numpy(zero_copy_only=False),
                        len(mat),
                        allow_all_zero=True,
                    )
                )
        local = (
            np.concatenate(mats, axis=0)
            if mats
            else np.zeros((0, 0), dtype=np.float64)
        )
        y_local = np.concatenate(ys) if ys else np.zeros(local.shape[0])
        w_local = (
            np.concatenate(ws) if ws else np.ones(local.shape[0])
        )

        # Rendezvous round: rank 0 proposes the jax.distributed coordinator;
        # row counts establish the common shard shape every process pads to.
        my_addr = ctx.getTaskInfos()[rank].address if rank < size else "127.0.0.1"
        proposal = {
            "rank": rank,
            "rows": int(local.shape[0]),
            "n": int(local.shape[1]),
            "coord": f"{my_addr.split(':')[0]}:{_free_port()}" if rank == 0 else None,
        }
        gathered = [json.loads(m) for m in ctx.allGather(json.dumps(proposal))]
        by_rank = sorted(gathered, key=lambda g: g["rank"])
        coord = by_rank[0]["coord"]
        n = max(g["n"] for g in by_rank)
        total_rows = sum(g["rows"] for g in by_rank)
        max_rows = max(g["rows"] for g in by_rank)
        if local.shape[0] == 0 and local.shape[1] != n:
            # empty partition: adopt the group's column count so the padded
            # shard shape stays legal
            local = np.zeros((0, n), dtype=np.float64)

        # This must be the interpreter's first JAX backend touch (module
        # docstring, point 3) — fresh barrier workers guarantee it.
        import jax

        from spark_rapids_ml_tpu.utils.config import enable_compilation_cache

        enable_compilation_cache()  # barrier workers are fresh interpreters:
        # without the persistent XLA cache every barrier fit pays a cold
        # compile of the whole SPMD program
        jax.distributed.initialize(
            coordinator_address=coord, num_processes=size, process_id=rank
        )
        try:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from spark_rapids_ml_tpu.parallel.mesh import DATA_AXIS, create_mesh

            ldc = len(jax.local_devices())
            # common shard shape: the resident shard's rule for compile
            # stability, then round to the per-process device count so the
            # shard splits evenly
            shard_rows = columnar.shard_rows(max_rows)
            shard_rows = ((shard_rows + ldc - 1) // ldc) * ldc
            padded = _pad_to(local, shard_rows)

            # global mesh in process order, so shard r of the global array is
            # process r's rows
            devices = sorted(
                jax.devices(), key=lambda d: (d.process_index, d.id)
            )
            mesh = create_mesh(data=len(devices), feat=1, devices=devices)
            x_sharding = NamedSharding(mesh, P(DATA_AXIS, None))
            gx = jax.make_array_from_process_local_data(
                x_sharding, padded, (size * shard_rows, n)
            )
            gw = gy = None
            if self.USES_VECTORS:
                v_sharding = NamedSharding(mesh, P(DATA_AXIS))
                w_pad = _pad_to(w_local, shard_rows)  # pad rows get weight 0
                gw = jax.make_array_from_process_local_data(
                    v_sharding, w_pad, (size * shard_rows,)
                )
                if self.label_col:  # no dead transfer for label-free fits
                    y_pad = _pad_to(y_local, shard_rows)
                    gy = jax.make_array_from_process_local_data(
                        v_sharding, y_pad, (size * shard_rows,)
                    )
            host = self._run_on_mesh(mesh, gx, gw, gy)
        finally:
            try:
                jax.distributed.shutdown()
            except Exception:
                pass  # ephemeral worker exits right after the stage anyway

        if rank == 0:
            if not self.COUNT_FROM_KERNEL:
                # pad rows contribute zero to every statistic; the TRUE row
                # total comes from the rendezvous
                host["count"] = np.float64(total_rows)
            host["mesh_size"] = np.float64(size)
            yield arrow_fns.arrays_to_batch(
                {name: host[name] for name in self.FIELDS}
            )


class MeshGramPartitionFn(_MeshReducePartitionFn):
    """Fit-pass GramStats via one SPMD psum (the PCA barrier path)."""

    FIELDS = MESH_FIELDS

    def _shard_kernel(self):
        precision = L.PRECISIONS[self.precision]

        def kernel(x):  # zero pad rows are exact for the Gram monoid
            import jax.numpy as jnp

            return {
                "xtx": L.gram(x, precision=precision),
                "col_sum": jnp.sum(x, axis=0),
            }

        return kernel


class MeshLinRegPartitionFn(_MeshReducePartitionFn):
    """LinearStats via one SPMD psum — distributed normal equations where
    the [n, n]/[n] reductions ride ICI, not the driver."""

    FIELDS = LINREG_MESH_FIELDS
    COUNT_FROM_KERNEL = True  # weighted count (Σw) — w is 0 on pad rows
    USES_VECTORS = True

    def _shard_kernel(self):
        def kernel(x, w, y):
            from spark_rapids_ml_tpu.ops import linear as LIN

            s = LIN.linear_stats(x, y, w)
            return dict(zip(s._fields, s))

        return kernel


class MeshMomentsPartitionFn(_MeshReducePartitionFn):
    """MomentStats via one SPMD psum (the StandardScaler barrier path)."""

    FIELDS = MOMENTS_MESH_FIELDS

    def _shard_kernel(self):
        def kernel(x):
            import jax.numpy as jnp

            return {
                "total": jnp.sum(x, axis=0),
                "total_sq": jnp.sum(x * x, axis=0),
            }

        return kernel


LOGREG_FIT_FIELDS = ["w", "iterations", "count", "mesh_size"]
SVD_FIT_FIELDS = ["pc", "explainedVariance", "count", "mesh_size"]
TSVD_FIT_FIELDS = ["components", "singularValues", "count", "mesh_size"]
KMEANS_FIT_FIELDS = ["centers", "cost", "iterations", "count", "mesh_size"]


class MeshLogRegFitFn(_MeshReducePartitionFn):
    """The ENTIRE binary IRLS fit in one barrier stage: a ``lax.while_loop``
    of Newton iterations with the psum INSIDE the loop body
    (parallel/linear.py make_distributed_logreg_fit) — zero driver
    round-trips during training, vs one Spark job per iteration on the
    driver-merge path. The driver receives the final [d] parameter."""

    FIELDS = LOGREG_FIT_FIELDS
    USES_VECTORS = True
    COUNT_FROM_KERNEL = True

    def __init__(
        self,
        features_col: str,
        label_col: str,
        weight_col: str | None,
        *,
        reg_param: float,
        fit_intercept: bool,
        max_iter: int,
        tol: float,
        elastic_net_param: float = 0.0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5,
        w0: np.ndarray | None = None,
        start_iter: int = 0,
    ):
        super().__init__(features_col, label_col, weight_col)
        self.reg_param = float(reg_param)
        self.elastic_net_param = float(elastic_net_param)
        self.fit_intercept = bool(fit_intercept)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        # Chunked rank-0 checkpointing (the mesh-local contract, barrier
        # edition): ``checkpoint_dir`` MUST be on a filesystem shared by
        # the driver and every executor (the jvm stagingDir contract) —
        # process 0 of the jax.distributed group saves between chunks, and
        # the DRIVER resolves the resume (w0/start_iter) before launching
        # the stage so interrupted fits restart mid-loop.
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        self.w0 = None if w0 is None else np.asarray(w0)
        self.start_iter = int(start_iter)

    def _prepare_matrix(self, mat: np.ndarray) -> np.ndarray:
        if self.fit_intercept:
            return np.concatenate(
                [mat, np.ones((mat.shape[0], 1), mat.dtype)], axis=1
            )
        return mat

    def _make_fit(self, mesh):
        """The compiled full-loop program — the ONE hook subclasses override
        (softmax swaps the factory; the result packaging below is shared)."""
        from spark_rapids_ml_tpu.parallel import linear as PL

        return PL.make_distributed_logreg_fit(
            mesh,
            reg_param=self.reg_param,
            elastic_net_param=self.elastic_net_param,
            fit_intercept=self.fit_intercept,
            max_iter=self.max_iter,
            tol=self.tol,
        )

    def _make_chunk(self, mesh):
        from spark_rapids_ml_tpu.parallel import linear as PL

        return PL.make_distributed_logreg_chunk(
            mesh,
            reg_param=self.reg_param,
            elastic_net_param=self.elastic_net_param,
            fit_intercept=self.fit_intercept,
            chunk_iters=self.checkpoint_every,
            tol=self.tol,
        )

    def _param_dim(self, d: int) -> int:
        return d

    def _run_on_mesh(self, mesh, gx, gw, gy):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.ops import linear as LIN
        from spark_rapids_ml_tpu.parallel import linear as PL

        count = float(jnp.sum(gw))
        if count == 0.0:
            # all-zero weights: skip training (the stats are all zero and
            # the solve would NaN for the wrong reason); the DRIVER raises
            # its "all instance weights are zero" contract error on the
            # returned count
            cd = self._param_dim(gx.shape[1])
            return {
                "w": np.zeros(cd),
                "iterations": np.float64(0.0),
                "count": np.float64(0.0),
            }
        if self.checkpoint_dir is None:
            w, iters, final_step = self._make_fit(mesh)(gx, gy, gw)
            # same NaN-input diagnosis as every other Newton path
            LIN.check_newton_outcome(final_step, w)
        else:
            from spark_rapids_ml_tpu.utils.checkpoint import (
                TrainingCheckpointer,
            )

            # rank 0 of the process group owns the durable saves; every
            # rank runs the identical replicated loop (parallel.linear
            # run_chunked_newton), so the stop decision (and a NaN-input
            # raise) is group-consistent
            ckpt = (
                TrainingCheckpointer(self.checkpoint_dir)
                if jax.process_index() == 0
                else None
            )
            cd = self._param_dim(gx.shape[1])
            w, iters = PL.run_chunked_newton(
                self._make_chunk(mesh), gx, gy, gw,
                self.w0 if self.w0 is not None else np.zeros(cd),
                start_iter=self.start_iter, max_iter=self.max_iter,
                tol=self.tol, ckpt=ckpt,
            )
        return {
            "w": np.asarray(jax.device_get(w)),
            "iterations": np.float64(int(iters)),
            # weighted count (pad rows weigh 0): the driver enforces the
            # same all-zero-weights contract as the driver-merge path
            "count": np.float64(count),
        }


class MeshSoftmaxFitFn(MeshLogRegFitFn):
    """The multinomial sibling of ``MeshLogRegFitFn``: the whole softmax
    IRLS loop in one barrier stage via
    ``parallel.linear.make_distributed_softmax_fit``; ``w`` comes back
    flattened [C·d]."""

    def __init__(
        self,
        features_col: str,
        label_col: str,
        weight_col: str | None,
        n_classes: int,
        *,
        reg_param: float,
        fit_intercept: bool,
        max_iter: int,
        tol: float,
        elastic_net_param: float = 0.0,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5,
        w0: np.ndarray | None = None,
        start_iter: int = 0,
    ):
        super().__init__(
            features_col, label_col, weight_col,
            reg_param=reg_param, fit_intercept=fit_intercept,
            max_iter=max_iter, tol=tol,
            elastic_net_param=elastic_net_param,
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every,
            w0=w0, start_iter=start_iter,
        )
        self.n_classes = int(n_classes)

    def _make_fit(self, mesh):
        from spark_rapids_ml_tpu.parallel import linear as PL

        return PL.make_distributed_softmax_fit(
            mesh,
            self.n_classes,
            reg_param=self.reg_param,
            elastic_net_param=self.elastic_net_param,
            fit_intercept=self.fit_intercept,
            max_iter=self.max_iter,
            tol=self.tol,
        )

    def _make_chunk(self, mesh):
        from spark_rapids_ml_tpu.parallel import linear as PL

        return PL.make_distributed_softmax_chunk(
            mesh,
            self.n_classes,
            reg_param=self.reg_param,
            elastic_net_param=self.elastic_net_param,
            fit_intercept=self.fit_intercept,
            chunk_iters=self.checkpoint_every,
            tol=self.tol,
        )

    def _param_dim(self, d: int) -> int:
        return self.n_classes * d


class MeshSVDFitFn(_MeshReducePartitionFn):
    """The direct TSQR→SVD(R) PCA fit in one barrier stage: per-device QR,
    butterfly R merge over the process mesh, replicated SVD of R — the
    cond(X)-accurate solver running entirely on the mesh (parallel/tsqr.py
    make_distributed_fit_svd_masked). The pad mask rides the weight vector
    so mean-centering stays exact under the common padded shard shape."""

    FIELDS = SVD_FIT_FIELDS

    def __init__(self, input_col: str, k: int, mean_centering: bool):
        super().__init__(input_col)
        self.k = int(k)
        self.mean_centering = bool(mean_centering)
        # the 1/0 pad mask (PCA has no instance weights) is only consumed
        # by the centered program — skip building/transferring it otherwise
        self.USES_VECTORS = self.mean_centering

    def _run_on_mesh(self, mesh, gx, gw, gy):
        import jax

        from spark_rapids_ml_tpu.parallel import tsqr as TSQR

        if self.mean_centering:
            fit = TSQR.make_distributed_fit_svd_masked(
                mesh, self.k, mean_centering=True
            )
            pc, ev = fit(gx, gw)
        else:  # zero pad rows are already exact for the uncentered QR
            fit = TSQR.make_distributed_fit_svd(mesh, self.k)
            pc, ev = fit(gx)
        return {
            "pc": np.asarray(jax.device_get(pc)),
            "explainedVariance": np.asarray(jax.device_get(ev)),
        }


class MeshTSVDFitFn(_MeshReducePartitionFn):
    """TruncatedSVD's barrier fit: TSQR across the process mesh (uncentered
    by definition — zero pad rows are exact), replicated SVD of R emitting
    components + raw singular values (σ of X, not the PCA variance ratio)."""

    FIELDS = TSVD_FIT_FIELDS

    def __init__(self, input_col: str, k: int):
        super().__init__(input_col)
        self.k = int(k)

    def _run_on_mesh(self, mesh, gx, gw, gy):
        import jax

        from spark_rapids_ml_tpu.parallel import tsqr as TSQR

        r = TSQR.tsqr_r(gx, mesh)
        components, sv = L.svd_components_from_r(r, self.k)
        return {
            "components": np.asarray(jax.device_get(components)),
            "singularValues": np.asarray(jax.device_get(sv))[: self.k],
        }


class MeshKMeansFitFn(_MeshReducePartitionFn):
    """The ENTIRE Lloyd fit in one barrier stage (parallel/kmeans.py
    make_distributed_kmeans_fit): initial centers ride the task state, the
    while_loop + psum trains on the mesh, the driver receives final centers
    + cost. Weights mask pad rows and carry instance weights."""

    FIELDS = KMEANS_FIT_FIELDS
    USES_VECTORS = True
    COUNT_FROM_KERNEL = True

    def __init__(
        self,
        input_col: str,
        centers: np.ndarray,
        weight_col: str | None,
        *,
        max_iter: int,
        tol: float,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 5,
        start_iter: int = 0,
    ):
        super().__init__(input_col, None, weight_col)
        self.centers = np.asarray(centers)
        self.max_iter = int(max_iter)
        self.tol = float(tol)
        # rank-0 chunked checkpointing; shared-filesystem contract as in
        # MeshLogRegFitFn (the driver resolves resumed centers/start_iter)
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_every = int(checkpoint_every)
        self.start_iter = int(start_iter)

    def _run_on_mesh(self, mesh, gx, gw, gy):
        import jax
        import jax.numpy as jnp

        from spark_rapids_ml_tpu.parallel import kmeans as PK

        if self.checkpoint_dir is None:
            fit = PK.make_distributed_kmeans_fit(
                mesh, max_iter=self.max_iter, tol=self.tol
            )
            centers, cost, iters = fit(gx, gw, jnp.asarray(self.centers))
        else:
            from spark_rapids_ml_tpu.utils.checkpoint import (
                TrainingCheckpointer,
            )

            ckpt = (
                TrainingCheckpointer(self.checkpoint_dir)
                if jax.process_index() == 0
                else None
            )
            centers, cost, iters = PK.run_chunked_lloyd(
                PK.make_distributed_kmeans_chunk(
                    mesh, chunk_iters=self.checkpoint_every, tol=self.tol
                ),
                gx, gw, self.centers,
                start_iter=self.start_iter, max_iter=self.max_iter,
                tol=self.tol, ckpt=ckpt,
            )
        return {
            "centers": np.asarray(jax.device_get(centers)),
            "cost": np.float64(float(cost)),
            "iterations": np.float64(int(iters)),
            "count": np.float64(float(jnp.sum(gw))),  # weighted (see logreg)
        }


def single_row_from_batches(
    batches, fields: list[str], shapes: dict[str, tuple]
) -> dict[str, np.ndarray]:
    """Decode a barrier stage's output: EXACTLY one pre-reduced stats row.

    More than one row means per-partition statistics leaked to the driver —
    the architectural regression this path exists to prevent — so it raises
    rather than silently summing.
    """
    rows = 0
    arrays = None
    for b in batches:
        t = pa.Table.from_batches([b]) if isinstance(b, pa.RecordBatch) else b
        rows += t.num_rows
        if t.num_rows and arrays is None:
            arrays = {
                name: np.asarray(
                    t.column(name)[0].values.to_numpy(zero_copy_only=False)
                )
                for name in fields
            }
    if arrays is None:
        raise ValueError("no statistics received from the barrier stage")
    if rows != 1:
        raise AssertionError(
            f"mesh fit must deliver exactly ONE pre-reduced stats row to the "
            f"driver, got {rows} — per-partition statistics are leaking"
        )
    return {name: arrays[name].reshape(shapes[name]) for name in fields}


def single_stats_from_batches(
    batches, n: int
) -> tuple[L.GramStats, int]:
    """The PCA-shaped decode of ``single_row_from_batches``."""
    arrays = single_row_from_batches(
        batches,
        MESH_FIELDS,
        {"xtx": (n, n), "col_sum": (n,), "count": (), "mesh_size": ()},
    )
    stats = L.GramStats(
        arrays["xtx"], arrays["col_sum"], np.float64(arrays["count"])
    )
    return stats, int(arrays["mesh_size"])
