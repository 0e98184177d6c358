"""The localspark Python worker: a separate OS process that executes
mapInArrow plan functions, mirroring Spark's executor-side Python worker.

Faithfulness to Spark's boundaries is the point (SURVEY.md §4 — the
reference is only ever tested through a live executor):

- the plan function arrives **cloudpickle-serialized** (the serializer
  pyspark itself uses for Python UDFs), so un-picklable closures fail here
  exactly as they would on a cluster;
- partition data crosses as an **Arrow IPC stream**, so schema/layout
  assumptions are exercised at a process boundary, not in-process;
- the worker is a **fresh interpreter** (``python -m``) — module-level
  state of the driver process is NOT available; the function's own imports
  (including JAX device init) must work cold, like on an executor;
- output batches are **cast to the declared schema**, the validation Spark
  applies to mapInArrow results; a mismatch raises here, not downstream;
- workers are **reused** across jobs of a session (Spark's
  ``spark.python.worker.reuse``), so per-process caches (jitted kernels)
  amortize the way they do on real executors.

Framing protocol, little-endian u64 lengths, one task per request::

    driver -> worker:  b"LSPK" | fn | input-arrow-stream | target-schema
    driver -> worker:  b"LSPB" | fn | input-arrow-stream | target-schema
                       | json task-context               (barrier task)
    worker -> driver:  b"O" | output-arrow-stream
                       | json telemetry-trailer          (success)
                       b"E" | pickled traceback string   (failure)

A barrier frame additionally installs a ``BarrierTaskContext`` (see
``taskcontext.py``) before invoking the plan function, the way Spark's
worker exposes ``BarrierTaskContext.get()`` inside barrier stages.

The telemetry trailer on the success frame is what keeps worker-side
observability from dying with the process: everything the task recorded
into THIS worker's registry (a snapshot delta — columnar counters, spans,
fault injections) plus its flight-recorder timeline events, JSON-encoded.
The driver merges it into its own registry/timeline labeled by partition
(``session._Worker.run_task``). Serialization failures degrade to an empty
trailer — telemetry must never fail a task.

stdout is re-pointed at stderr after startup so user ``print``\\ s inside
plan functions cannot corrupt the protocol stream (Spark's workers talk
over a socket for the same reason).
"""

from __future__ import annotations

import io
import os
import struct
import sys
import time
import traceback

import pyarrow as pa

MAGIC = b"LSPK"
MAGIC_BARRIER = b"LSPB"


def write_block(stream, payload: bytes) -> None:
    stream.write(struct.pack("<Q", len(payload)))
    stream.write(payload)


def read_block(stream) -> bytes:
    header = stream.read(8)
    if len(header) != 8:
        raise EOFError("worker protocol stream truncated")
    (length,) = struct.unpack("<Q", header)
    payload = stream.read(length)
    if len(payload) != length:
        raise EOFError("worker protocol stream truncated")
    return payload


def batches_to_ipc(batches: list[pa.RecordBatch], schema: pa.Schema) -> bytes:
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, schema) as writer:
        for b in batches:
            writer.write_batch(b)
    return sink.getvalue()


def batches_from_ipc(payload: bytes) -> tuple[list[pa.RecordBatch], pa.Schema]:
    with pa.ipc.open_stream(pa.BufferReader(payload)) as reader:
        schema = reader.schema
        return list(reader), schema


def cast_to_declared(batch: pa.RecordBatch, target: pa.Schema) -> pa.RecordBatch:
    """Validate/cast one output batch against the declared mapInArrow schema.

    Matches Spark's behavior: columns are matched by NAME (order-free),
    value-compatible types are cast, anything else is an error naming the
    column — so a plan-function bug surfaces at the boundary with a
    message, not as corrupt downstream data.
    """
    if batch.schema.equals(target):
        return batch
    cols = []
    for field in target:
        idx = batch.schema.get_field_index(field.name)
        if idx < 0:
            raise ValueError(
                f"mapInArrow output is missing declared column {field.name!r}; "
                f"got columns {batch.schema.names}"
            )
        col = batch.column(idx)
        if col.type != field.type:
            try:
                col = col.cast(field.type)
            except pa.ArrowInvalid as e:
                raise ValueError(
                    f"mapInArrow output column {field.name!r} has type "
                    f"{col.type}, cannot cast to declared {field.type}: {e}"
                ) from e
        cols.append(col)
    return pa.RecordBatch.from_arrays(cols, schema=target)


def run_task(
    fn_bytes: bytes,
    data: bytes,
    schema_bytes: bytes,
    context: dict | None = None,
) -> bytes:
    """Execute one mapInArrow task; returns the output IPC stream bytes."""
    import cloudpickle

    from spark_rapids_ml_tpu.localspark.taskcontext import BarrierTaskContext

    fn = cloudpickle.loads(fn_bytes)
    batches, _ = batches_from_ipc(data)
    target = pa.ipc.read_schema(pa.BufferReader(schema_bytes))
    if context is not None:
        BarrierTaskContext._install(
            BarrierTaskContext(
                partition_id=context["partition_id"],
                num_tasks=context["num_tasks"],
                barrier_dir=context["barrier_dir"],
                timeout=context.get("timeout", 120.0),
            )
        )
    try:
        out = [cast_to_declared(b, target) for b in fn(iter(batches))]
    finally:
        if context is not None:
            BarrierTaskContext._install(None)
    return batches_to_ipc(out, target)


def main() -> None:
    import cloudpickle

    # keep the protocol fd private; user prints go to stderr
    proto_in = os.fdopen(os.dup(0), "rb")
    proto_out = os.fdopen(os.dup(1), "wb")
    os.dup2(2, 1)
    sys.stdout = sys.stderr

    # Device-policy probe BEFORE accepting tasks: if this process cannot
    # initialize JAX on its assigned platform within a bounded time, exit
    # with a diagnosable error instead of hanging the first fit() job
    # indefinitely (utils/devicepolicy.py). Armed by the session only on
    # hosts whose environment carries TPU topology variables,
    # because it costs the cold-interpreter fidelity documented above. The
    # driver maps PROBE_EXIT_CODE to a policy-specific WorkerException.
    from spark_rapids_ml_tpu.utils import devicepolicy

    if os.environ.get(devicepolicy.PROBE_VAR):
        try:
            devicepolicy.probe_platform()
        except devicepolicy.DevicePolicyError as e:
            print(f"[tpu-ml worker] device policy violation: {e}", file=sys.stderr)
            sys.stderr.flush()
            os._exit(devicepolicy.PROBE_EXIT_CODE)

    import json

    # jax-free on purpose: importing the registry/timeline must not trigger
    # a backend init in workers that never touch jax (pure-Arrow tasks)
    from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
    from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE

    while True:
        magic = proto_in.read(4)
        if not magic:
            return  # driver closed the pipe: clean shutdown
        if magic not in (MAGIC, MAGIC_BARRIER):
            raise RuntimeError(f"bad task frame magic: {magic!r}")
        fn_bytes = read_block(proto_in)
        data = read_block(proto_in)
        schema_bytes = read_block(proto_in)
        context = (
            json.loads(read_block(proto_in)) if magic == MAGIC_BARRIER else None
        )
        # bracket the task so the trailer carries exactly what IT recorded
        reg0 = REGISTRY.snapshot()
        tl_seq0 = TIMELINE.seq()
        t0 = time.perf_counter()
        try:
            # fault site for chaos tests: a worker-scoped TPU_ML_FAULT_PLAN
            # (e.g. worker.task:kill:1) crashes THIS process mid-job,
            # exercising the session's crashed-worker replacement
            from spark_rapids_ml_tpu.resilience import faults

            faults.inject("worker.task")
            payload, status = run_task(fn_bytes, data, schema_bytes, context), b"O"
        except BaseException:
            payload, status = cloudpickle.dumps(traceback.format_exc()), b"E"
        proto_out.write(status)
        write_block(proto_out, payload)
        if status == b"O":
            # the one span every task gets, recorded worker-side (plain
            # registry/timeline calls, not trace_range — that would drag a
            # jax import into pure-Arrow tasks)
            t1 = time.perf_counter()
            REGISTRY.histogram_record("span.seconds", t1 - t0, phase="worker.task")
            TIMELINE.record_span("worker.task", t0, t1)
            try:
                trailer = json.dumps(
                    {
                        "registry": REGISTRY.snapshot().delta(reg0).to_wire(),
                        "events": TIMELINE.events(since_seq=tl_seq0),
                    }
                ).encode()
            except Exception:
                trailer = b"{}"  # telemetry must never fail a task
            write_block(proto_out, trailer)
        proto_out.flush()


if __name__ == "__main__":
    main()
