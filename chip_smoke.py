#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, run as ``python chip_smoke.py`` from the repo root on a machine
with a TPU. It drives the two normal entry points once, at the north-star
width (n = 2048, k = 50, ``BASELINE.json``), on data made from a seed:

1. device      assert the platform is ``tpu``; print kind, count, versions;
               put a 1 GiB float32 host array, wait for it, overwrite the host
               array and read the device array back: it must read as it was
               put wherever ``stream_fold`` would write its staging set again
2. fit         ``SparkPCA().setDistribution("mesh-local").fit(df)`` on 65,536
               rows — the resident branch (ingest.stream_to_mesh + psum Gram)
3. fit         the same on 196,608 rows: three default chunks, past the 2 GiB
               cutover with no knob set, so ingest.stream_fold drives the
               donated mesh fold; then once more, which must compile nothing
4. serve       register the model (AOT over the whole bucket ladder),
               ``start_serving(0)`` in this process, and a few requests on the
               binary-f32 HTTP wire, one JSON request and one over UDS
5. transform   ``model.transform(df)`` on 8,192 rows through mapInArrow: the
               workers are children on XLA:CPU while this process holds the chip

Every check is made outside the timed windows and against float64 NumPy: the
fits against the repo's own eigenvector oracle, the projections against
``x @ pc``. Any failure raises and the exit code is not 0. The last two lines
of standard output are JSON objects: the summary (versions, cache directory,
counters, and per phase its wall, compiles and check values), then the verdict
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` and
nothing else.

The script selects no platform. ``--rehearse-cpu`` runs the same phases at a
toy size for debugging, only where JAX already reports ``cpu``, and its output
is marked a rehearsal and carries no ``"ok"``.
"""

from __future__ import annotations

import argparse
import contextlib
import faulthandler
import http.client
import importlib.metadata
import json
import os
import shutil
import socket
import sys
import tempfile
import time

SEED = 0
N, K = 2048, 50
RESIDENT_ROWS = 65_536
STREAMED_ROWS = 196_608          # three default 65,536-row chunks
TRANSFORM_ROWS = 8_192
PUT_ROWS = 131_072               # x N float32 = 1 GiB: the overwrite probe
SERVE_REQUEST_ROWS = (1, 8, 1000)
MIN_COSINE = 0.9999              # BASELINE.md's accuracy bar
# max |served − x·pc| over max |x·pc|: an f32 projection at HIGHEST is ~1e-6,
# a single bf16 pass ~4e-3
PROJECTION_RTOL = 1e-4
DEADLINE_S = 1100                # the driver allows 1200

_INPUT_COL, _OUTPUT_COL = "features", "pca_features"


def device_facts() -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_rows(rows: int, n: int, k: int):
    """[rows, n] float64 with a decaying spectrum: the leading k + 14
    directions fall off by 5% each, the rest sit a hundred times lower, and
    one Householder reflection makes every component dense."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    lead = min(n, k + 14)
    scale = np.full(n, 0.95 ** lead / 100.0)
    scale[:lead] = 0.95 ** np.arange(lead)
    x = rng.standard_normal((rows, n))
    x *= scale
    v = rng.standard_normal(n)
    v /= np.linalg.norm(v)
    for lo in range(0, rows, 16_384):  # in blocks: no [rows, n] temporary
        block = x[lo : lo + 16_384]
        block -= np.outer(block @ (2.0 * v), v)
    return x


def to_table(x):
    """ndarray → Arrow table with one ``array<double>`` column (zero-copy)."""
    import numpy as np
    import pyarrow as pa

    rows, n = x.shape
    offsets = pa.array(np.arange(0, x.size + 1, n, dtype=np.int32))
    col = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
    return pa.Table.from_arrays([col], names=[_INPUT_COL])


def describe_array(arr) -> dict:
    """Where a jax.Array lives: platform(s), dtype, shape, bytes per device."""
    shards = {str(s.device): int(s.data.nbytes) for s in arr.addressable_shards}
    return {
        "platforms": sorted({d.platform for d in arr.devices()}),
        "dtype": str(arr.dtype),
        "shape": list(arr.shape),
        "shard_bytes": shards,
    }


def check_placement(info: dict, what: str, platform: str, count: int) -> None:
    if info["platforms"] != [platform]:
        raise AssertionError(f"{what} is on {info['platforms']}, not {platform}")
    sizes = list(info["shard_bytes"].values())
    if len(sizes) != count or min(sizes) != max(sizes):
        raise AssertionError(
            f"{what} is not spread evenly over {count} device(s): {info}"
        )


@contextlib.contextmanager
def spy(module, name: str, record):
    """Observe one call boundary of the program without changing it."""
    original = getattr(module, name)

    def observed(*args, **kwargs):
        out = original(*args, **kwargs)
        record(args, kwargs, out)
        return out

    setattr(module, name, observed)
    try:
        yield
    finally:
        setattr(module, name, original)


def put_then_overwrite(rows: int, n: int) -> dict:
    """Does a device array still read its host source once it is ready? Put
    ``rows`` x ``n`` float32, wait, overwrite the host array, read the device
    array back and compare with what was put. ``stream_fold`` rewrites a
    staging set when its arrays are ready and ``ingest._shares_memory`` says
    they do not live in it: the check has to agree with what the write shows
    (the CPU backend aliases an aligned source, and there the set is not
    rewritten)."""
    import jax
    import numpy as np

    from spark_rapids_ml_tpu.spark import ingest

    host = np.arange(rows * n, dtype=np.float32).reshape(rows, n)
    placed = jax.block_until_ready(jax.device_put(host))
    shares = bool(ingest._shares_memory(placed, host))
    host[:] = -1.0
    back = np.array(placed)  # a copy: on an aliasing backend a view is the source
    host[:] = np.arange(rows * n, dtype=np.float32).reshape(rows, n)
    unchanged = bool(np.array_equal(back, host))
    if shares == unchanged:
        raise AssertionError(
            f"the device array {'kept' if unchanged else 'lost'} its contents "
            f"when its source was overwritten, and _shares_memory says {shares}"
        )
    return {
        "bytes": int(host.nbytes),
        "device_array_unchanged": unchanged,
        "shares_memory": shares,
    }


class Phases:
    """Times each phase and reads its compile activity from the registry."""

    def __init__(self):
        self.results: dict[str, dict] = {}
        self.delta = None  # registry delta of the phase that ended last

    @contextlib.contextmanager
    def timed(self, name: str):
        from spark_rapids_ml_tpu.telemetry import REGISTRY

        snap = REGISTRY.snapshot()
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        self.delta = delta = REGISTRY.snapshot().delta(snap)
        compiles = delta.hist("compile.seconds")
        self.results[name] = {
            "wall_s": round(wall, 3),
            "compiles": compiles.count,
            "compile_s": round(compiles.total, 3),
            # slowest single request, to within the histogram's ~10% bucket
            "compile_max_s": round(compiles.percentile(100), 3),
            "cache_hits": int(delta.counter("compile.cache_hits")),
            "cache_misses": int(delta.counter("compile.cache_misses")),
        }

    def note(self, name: str, **fields) -> None:
        self.results[name].update(fields)
        say(f"phase {name}: {json.dumps(self.results[name])}")


def http_predict(port: int, name: str, x32=None, instances=None):
    """One predict over HTTP: binary f32 wire when ``x32`` is given, JSON
    when ``instances`` is. Returns the [rows, k] float64 answer."""
    import numpy as np

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        path = f"/v1/models/{name}:predict"
        if x32 is not None:
            binary = "application/x-tpu-ml-f32"
            conn.request(
                "POST", path, body=x32.tobytes(),
                headers={
                    "Content-Type": binary,
                    "Accept": binary,
                    "X-Shape": f"{x32.shape[0]},{x32.shape[1]}",
                },
            )
            resp = conn.getresponse()
            body = resp.read()
            if resp.status != 200:
                raise AssertionError(f"HTTP {resp.status}: {body[:300]!r}")
            shape = [int(d) for d in resp.getheader("X-Shape").split(",")]
            return np.frombuffer(body, dtype="<f4").reshape(shape).astype(np.float64)
        conn.request(
            "POST", path, body=json.dumps({"instances": instances}),
            headers={"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        body = resp.read()
        if resp.status != 200:
            raise AssertionError(f"HTTP {resp.status}: {body[:300]!r}")
        return np.asarray(json.loads(body)["predictions"], dtype=np.float64)
    finally:
        conn.close()


def uds_predict(path: str, name: str, x32):
    """One binary predict over the Unix-socket listener's framed protocol."""
    import numpy as np

    def read_exact(rf, n: int) -> bytes:
        data = rf.read(n)
        if len(data) != n:
            raise EOFError("serve socket closed mid-frame")
        return data

    header = json.dumps({
        "model": name, "wire": "binary", "accept": "binary",
        "shape": list(x32.shape), "payload_bytes": x32.nbytes,
    }).encode()
    with socket.socket(socket.AF_UNIX) as s:
        s.settimeout(60)
        s.connect(path)
        s.sendall(len(header).to_bytes(4, "big") + header + x32.tobytes())
        with s.makefile("rb") as rf:
            resp = json.loads(read_exact(rf, int.from_bytes(read_exact(rf, 4), "big")))
            if not resp.get("ok"):
                raise AssertionError(f"UDS predict failed: {resp}")
            body = read_exact(rf, int(resp["payload_bytes"]))
    return np.frombuffer(body, dtype="<f4").reshape(resp["shape"]).astype(np.float64)


def projection_error(got, x, pc) -> float:
    """max |got − x·pc| / max |x·pc|, the reference in float64."""
    import numpy as np

    ref = np.asarray(x, dtype=np.float64) @ np.asarray(pc, dtype=np.float64)
    if got.shape != ref.shape or not np.all(np.isfinite(got)):
        raise AssertionError(f"answer has shape {got.shape}, want {ref.shape}, finite")
    return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))


def run(rehearse: bool):
    """All phases. Returns ``(summary, model)``; raises on the first failure."""
    want = "cpu" if rehearse else "tpu"
    device = device_facts()
    if device["platform"] != want:
        raise SystemExit(
            f"chip_smoke: JAX reports platform {device['platform']!r}, not "
            f"{want!r} — nothing was run"
        )
    import jax
    import numpy as np

    n, k = N, K
    resident_rows, streamed_rows = RESIDENT_ROWS, STREAMED_ROWS
    transform_rows = TRANSFORM_ROWS
    if rehearse:
        # toy size; the cutover and the chunk are shrunk with it, so the same
        # two branches run
        n, k = 64, 8
        resident_rows, streamed_rows, transform_rows = 4096, 12_288, 1024
        from spark_rapids_ml_tpu.utils import knobs

        os.environ[knobs.STREAM_FIT_MAX_RESIDENT_BYTES.name] = str(4096 * n * 8)
        os.environ[knobs.STREAM_CHUNK_ROWS.name] = "4096"

    from spark_rapids_ml_tpu import serving, telemetry
    from spark_rapids_ml_tpu.localspark import LocalSparkSession
    from spark_rapids_ml_tpu.ops import linalg as L
    from spark_rapids_ml_tpu.parallel import gram as G
    from spark_rapids_ml_tpu.spark import SparkPCA, ingest
    from spark_rapids_ml_tpu.utils import devicepolicy

    # nothing is printed before the program is known to be there
    versions = {
        pkg: importlib.metadata.version(pkg) for pkg in ("jax", "jaxlib", "libtpu")
    }
    say(f"device: {json.dumps(device)} versions: {json.dumps(versions)} "
        f"x64: {bool(jax.config.jax_enable_x64)}")

    telemetry.install_monitoring()
    before = telemetry.REGISTRY.snapshot()
    phases = Phases()
    ndev = device["count"]

    with phases.timed("put_then_overwrite"):
        verdict = put_then_overwrite(4096 if rehearse else PUT_ROWS, n)
    phases.note("put_then_overwrite", **verdict)

    x = make_rows(streamed_rows, n, k)
    table = to_table(x)
    say(f"data: {streamed_rows}x{n} float64 from seed {SEED} "
        f"({x.nbytes / 2**30:.2f} GiB)")

    seen: dict[str, dict] = {}

    def saw_ingest(args, kwargs, out):
        seen["ingested"] = describe_array(out.xs)

    def saw_finalize(args, kwargs, out):
        seen["carry"] = describe_array(args[0].xtx)

    def estimator():
        return (
            SparkPCA().setInputCol(_INPUT_COL).setOutputCol(_OUTPUT_COL)
            .setK(k).setDistribution("mesh-local")
        )

    # two partitions on two workers: each child's only task is its cold start
    with LocalSparkSession(parallelism=2, num_workers=2) as session, \
            spy(ingest, "stream_to_mesh", saw_ingest), \
            spy(G, "finalize_chunk_fold", saw_finalize):
        # -- 2. fit, resident branch -----------------------------------------
        df = session.createDataFrame(table.slice(0, resident_rows))
        with phases.timed("fit_resident"):
            resident = estimator().fit(df)
        if "ingested" not in seen or "carry" in seen:
            raise AssertionError("the resident fit did not take stream_to_mesh")
        check_placement(seen["ingested"], "the ingested array", want, ndev)
        cosine = L.min_cosine_vs_f64_oracle(x[:resident_rows], resident.pc, k)
        phases.note(
            "fit_resident", platform=want, rows=resident_rows,
            min_cosine_vs_f64=cosine, ingested=seen.pop("ingested"),
        )
        if not cosine >= MIN_COSINE:
            raise AssertionError(f"resident fit: min cosine {cosine} < {MIN_COSINE}")

        # -- 3. fit, streamed branch, twice ----------------------------------
        df = session.createDataFrame(table)
        with phases.timed("fit_streamed"):
            model = estimator().fit(df)
        if "carry" not in seen or "ingested" in seen:
            raise AssertionError("the streamed fit did not take stream_fold")
        check_placement(seen["carry"], "the fold carry", want, ndev)
        if seen["carry"]["shape"] != [ndev, n, n]:
            raise AssertionError(f"carry is {seen['carry']['shape']}, want [{ndev}, {n}, {n}]")
        chunks = phases.delta.hist("span.seconds", phase="fold.dispatch").count
        allreduces = int(phases.delta.counter("collective.count", kind="allreduce"))
        cosine = L.min_cosine_vs_f64_oracle(x, model.pc, k)
        phases.note(
            "fit_streamed", platform=want, rows=streamed_rows, chunks=chunks,
            allreduce_ops=allreduces, min_cosine_vs_f64=cosine,
            carry=seen.pop("carry"),
        )
        if chunks != 3:
            raise AssertionError(f"streamed fit folded {chunks} chunks, want 3")
        if not cosine >= MIN_COSINE:
            raise AssertionError(f"streamed fit: min cosine {cosine} < {MIN_COSINE}")

        with phases.timed("fit_streamed_repeat"):
            again = estimator().fit(df)
        drift = float(np.max(np.abs(again.pc - model.pc)))
        phases.note("fit_streamed_repeat", platform=want, max_abs_pc_diff=drift)
        if phases.results["fit_streamed_repeat"]["compiles"]:
            raise AssertionError("the repeated streamed fit compiled")
        if drift:
            raise AssertionError(f"the repeated fit moved pc by {drift}")

        # -- 4. serve ---------------------------------------------------------
        sock_dir = tempfile.mkdtemp(prefix="chip_smoke-")
        sock_path = os.path.join(sock_dir, "serve.sock")
        try:
            with phases.timed("serve_register"):
                entry = serving.get_registry().register("pca", model)
                server = serving.start_serving(0, uds_path=sock_path)
            param_platforms = sorted(
                {d.platform for p in entry.params for d in p.devices()}
            )
            phases.note(
                "serve_register", platform=want, buckets=sorted(entry.warm_buckets),
                params_on=param_platforms, x_dtype=str(entry.x_dtype),
            )
            if param_platforms != [want]:
                raise AssertionError(f"served params are on {param_platforms}")

            answers = []
            with phases.timed("serve_requests"):
                for rows in SERVE_REQUEST_ROWS:
                    x32 = np.ascontiguousarray(x[:rows], dtype="<f4")
                    answers.append((x32, http_predict(server.port, "pca", x32=x32)))
                answers.append((
                    x[:3], http_predict(server.port, "pca", instances=x[:3].tolist())
                ))
                x32 = np.ascontiguousarray(x[8:24], dtype="<f4")
                answers.append((x32, uds_predict(sock_path, "pca", x32)))
            worst = max(projection_error(got, xs, model.pc) for xs, got in answers)
            phases.note(
                "serve_requests", platform=want, requests=len(answers),
                max_rel_error_vs_f64=worst,
            )
            if phases.results["serve_requests"]["compiles"]:
                raise AssertionError("a served request compiled")
            if not worst <= PROJECTION_RTOL:
                raise AssertionError(f"served answers off by {worst} > {PROJECTION_RTOL}")
        finally:
            serving.stop_serving()
            shutil.rmtree(sock_dir, ignore_errors=True)

        # -- 5. DataFrame transform on CPU workers ---------------------------
        df = session.createDataFrame(table.slice(0, transform_rows))
        with phases.timed("transform_df"):
            out = model.transform(df).toArrow()
        got = np.asarray(
            out.column(_OUTPUT_COL).combine_chunks().flatten().to_numpy()
        ).reshape(transform_rows, k)
        worst = projection_error(got, x[:transform_rows], model.pc)
        phases.note(
            "transform_df", platform="cpu (workers)", rows=transform_rows,
            worker_probe_armed=devicepolicy.PROBE_VAR in devicepolicy.worker_env("cpu"),
            max_rel_error_vs_f64=worst,
        )
        if not worst <= PROJECTION_RTOL:
            raise AssertionError(f"transform off by {worst} > {PROJECTION_RTOL}")

    # -- nothing degraded, nothing retried, and still on the chip -----------
    total = telemetry.REGISTRY.snapshot().delta(before)
    quiet = {
        name: total.counter(name)
        for name in ("degraded.cpu_fallback", "retry.attempts",
                     "scheduler.hedge", "worker.quarantine")
    }
    if any(quiet.values()):
        raise AssertionError(f"the run degraded or retried: {quiet}")
    if device_facts() != device:
        raise AssertionError(f"device changed under the run: {device_facts()}")

    summary = {
        "device": device,
        "versions": versions,
        "shape": {"n": n, "k": k},
        "decomposition_and_registry_on": str(jax.devices()[0]),
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "counters": quiet,
        "phases": phases.results,
    }
    return summary, model


def report(summary: dict, rehearse: bool) -> None:
    """A run that got here passed. The summary first; the last line is the
    verdict and nothing else — a rehearsal gets no verdict."""
    if rehearse:
        print(json.dumps({"rehearsal": True, **summary}), flush=True)
        return
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="toy-size run for debugging where JAX already reports cpu; "
        "its output is marked a rehearsal",
    )
    args = ap.parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    summary, _ = run(args.rehearse_cpu)
    report(summary, args.rehearse_cpu)
    return 0


if __name__ == "__main__":
    sys.exit(main())
