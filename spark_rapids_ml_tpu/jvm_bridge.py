"""JVM delegation entry point — the Scala shim's Python side.

The reference's product is a Scala estimator usable from JVM Spark with
zero code change (PCA.scala:27-37, packaged per pom.xml:345-396). Its JVM
surface exists because its ENGINE lives in the executor JVM (a spark-rapids
plugin + JNI). This framework's engine is the Python/JAX/XLA runtime, so
the JVM story inverts: a thin Scala estimator (``jvm/`` at the repo root)
hands the data off and THIS module runs the fit.

Contract (public Spark APIs only, no private Arrow hooks):

1. the Scala ``com.nvidia.spark.ml.feature.PCA``-shaped estimator writes
   ``dataset.select(inputCol)`` as parquet to a scratch dir;
2. it execs ``python -m spark_rapids_ml_tpu.jvm_bridge fit-pca --input
   <dir> --output <dir> ...`` (driver-side; the fit itself fans out over
   this host's TPU mesh — the one-device-owner-per-host deployment of
   utils/devicepolicy.py);
3. the model is written in ``layout="spark"`` — the stock Spark ML on-disk
   shape — so the Scala side finishes with
   ``org.apache.spark.ml.feature.PCAModel.load(path)`` and returns a STOCK
   Spark model: JVM-native transform, persistence, and Pipeline integration
   come for free, and the shim stays ~100 lines with no custom model class.

For batch inference the Scala ``TpuPCAModel`` wrapper execs the
``transform-pca`` subcommand: staged parquet in, device projection out,
row alignment carried by a row-id column (see :func:`transform_pca`).

Parquet written from either an ArrayType column or a pyspark.ml VectorUDT
column is accepted (utils/columnar.py handles both Arrow layouts).
"""

from __future__ import annotations

import argparse
import sys


def _read_matrix(input_path: str, input_col: str):
    import numpy as np
    import pyarrow.dataset as pads

    from spark_rapids_ml_tpu.utils import columnar

    table = pads.dataset(input_path, format="parquet").to_table()
    if input_col not in table.column_names:
        raise SystemExit(
            f"column {input_col!r} not in {input_path} "
            f"(has: {table.column_names})"
        )
    mats = [
        columnar.extract_matrix(batch, input_col)
        for batch in table.to_batches()
        if batch.num_rows
    ]
    if not mats:
        raise SystemExit(f"no rows under {input_path}")
    return np.concatenate(mats, axis=0)


def fit_pca(args: argparse.Namespace) -> None:
    from spark_rapids_ml_tpu.models.pca import PCA

    x = _read_matrix(args.input, args.input_col)
    est = (
        PCA()
        .setInputCol(args.input_col)
        .setOutputCol(args.output_col)
        .setK(args.k)
        .setMeanCentering(args.mean_centering)
        .setSolver(args.solver)
    )
    model = est.fit(x, num_partitions=args.num_partitions)
    model.save(args.output, overwrite=True, layout=args.layout)
    print(
        f"fit-pca ok rows={x.shape[0]} n={x.shape[1]} k={args.k} "
        f"-> {args.output} ({args.layout} layout)",
        file=sys.stderr,
    )


def transform_pca(args: argparse.Namespace) -> None:
    """Accelerated batch transform for the JVM shim (VERDICT r4 Next #3 —
    the reference's model registers a GPU columnar UDF so inference runs
    on-device, RapidsPCA.scala:128-161; this is that capability at the
    shim's process boundary).

    Streams the staged parquet batch-by-batch — host memory stays
    O(batch), never O(dataset) — projecting each batch's input column on
    the device mesh and writing ALL staged columns plus the appended
    projection column. Within every written batch the projection is
    row-aligned with the staged columns by construction; cross-system
    alignment is the CALLER's contract — the Scala ``TpuPCAModel`` stages a
    row-id column alongside the input and joins the projection back on it
    (TpuPCAModel.scala), which is why the passthrough columns here are
    whatever was staged, id included.
    """
    import numpy as np
    import pyarrow as pa
    import pyarrow.dataset as pads
    import pyarrow.parquet as pq

    from spark_rapids_ml_tpu.models.pca import PCAModel
    from spark_rapids_ml_tpu.utils import columnar

    model = PCAModel.load(args.model)  # native OR stock-Spark layout
    ds = pads.dataset(args.input, format="parquet")
    if args.input_col not in ds.schema.names:
        raise SystemExit(
            f"column {args.input_col!r} not in {args.input} "
            f"(has: {ds.schema.names})"
        )
    if args.output_col in ds.schema.names:
        raise SystemExit(
            f"output column {args.output_col!r} already exists in the input"
        )
    out_field = pa.field(
        args.output_col, pa.list_(pa.float64()), nullable=False
    )
    out_schema = pa.schema(list(ds.schema) + [out_field])
    import os

    os.makedirs(args.output, exist_ok=True)
    rows = 0
    out_path = os.path.join(args.output, "part-00000.parquet")
    with pq.ParquetWriter(out_path, out_schema) as writer:
        for batch in ds.to_batches(batch_size=args.batch_rows):
            if not batch.num_rows:
                continue
            x = columnar.extract_matrix(batch, args.input_col)
            proj = np.asarray(model._project_matrix(x), dtype=np.float64)
            proj_col = pa.FixedSizeListArray.from_arrays(
                pa.array(proj.reshape(-1)), proj.shape[1]
            ).cast(pa.list_(pa.float64()))
            writer.write_batch(
                pa.record_batch(
                    list(batch.columns) + [proj_col], schema=out_schema
                )
            )
            rows += batch.num_rows
    if not rows:
        raise SystemExit(f"no rows under {args.input}")
    print(
        f"transform-pca ok rows={rows} k={model.pc.shape[1]} "
        f"-> {args.output}",
        file=sys.stderr,
    )


def _assert_platform() -> None:
    """Own the device policy for this fresh interpreter (it is a driver-side
    entry point): bounded-probe the backend so a TPU that another process
    holds exits with a diagnosable error instead of hanging the invoking
    JVM, and hold JAX to an explicit single-platform ``JAX_PLATFORMS``
    request instead of whatever it fell back to."""
    import os

    from spark_rapids_ml_tpu.utils import devicepolicy

    requested = os.environ.get("JAX_PLATFORMS") or None
    if requested and "," in requested:
        requested = None  # a fallback list: any entry may legitimately win
    try:
        # timeout=None: env-driven (TPU_ML_WORKER_PROBE_TIMEOUT), the knob
        # the DevicePolicyError message recommends
        devicepolicy.probe_platform(expected=requested, timeout=None)
    except devicepolicy.DevicePolicyError as e:
        raise SystemExit(f"jvm_bridge: {e}") from None


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(
        prog="spark_rapids_ml_tpu.jvm_bridge",
        description="Driver-side fit entry point for the JVM (Scala) shim",
    )
    sub = parser.add_subparsers(dest="command", required=True)
    p = sub.add_parser("fit-pca", help="fit PCA from a parquet handoff")
    p.add_argument("--input", required=True, help="parquet dir of the input column")
    p.add_argument("--output", required=True, help="model output dir")
    p.add_argument("--input-col", default="features")
    p.add_argument("--output-col", default="pca_features")
    p.add_argument("--k", type=int, required=True)
    p.add_argument("--mean-centering", action="store_true")
    p.add_argument(
        "--solver", default="full", choices=["full", "randomized", "svd", "auto"]
    )
    p.add_argument(
        "--layout",
        default="spark",
        choices=["spark", "native"],
        help="'spark' (default) = stock pyspark.ml layout, loadable by "
        "org.apache.spark.ml.feature.PCAModel.load",
    )
    p.add_argument(
        "--num-partitions",
        type=int,
        default=None,
        help="row partitions for the local fit (default: one)",
    )
    p.set_defaults(func=fit_pca)

    t = sub.add_parser(
        "transform-pca",
        help="project a staged parquet dataset on-device (batch inference "
        "for the JVM shim's TpuPCAModel)",
    )
    t.add_argument("--input", required=True, help="parquet dir of staged rows")
    t.add_argument(
        "--model",
        required=True,
        help="model dir (native or stock-Spark-ML layout, auto-detected)",
    )
    t.add_argument("--output", required=True, help="parquet output dir")
    t.add_argument("--input-col", default="features")
    t.add_argument("--output-col", default="pca_features")
    t.add_argument(
        "--batch-rows",
        type=int,
        default=1 << 16,
        help="rows per streamed projection batch (host memory bound)",
    )
    t.set_defaults(func=transform_pca)

    args = parser.parse_args(argv)
    # after parsing: --help/usage errors must not pay (or hang on) JAX init
    _assert_platform()
    args.func(args)


if __name__ == "__main__":
    main()
