"""Multi-process serve fleet: N replica servers behind one router.

One serve process tops out on the host, not the device — the GIL
serializes framing, and a single batcher thread owns every dispatch. The
fleet is the scale-out axis: ``ServeFleet`` spawns N **replica**
processes (each a full serve runtime: registry + AOT warmup + micro
batcher + UDS listener) and fronts them with an in-process **router**
that speaks the exact same UDS wire protocols the single server does —
JSON, binary, and the fast lane — so clients need no fleet awareness.

Design points, each riding machinery an earlier PR shipped:

- **Replica supervision (PR 9).** Replicas are spawned through
  ``resilience.supervisor.WorkerSupervisor`` — the same lease/breaker/
  backoff discipline the fit-path worker pool uses. A crash-looping
  replica trips its breaker instead of eating the fleet's wall clock;
  ``TPU_ML_WORKER_SLOT`` stamps each replica's identity.

- **Warm respawns (PR 13).** Every replica shares one compile cache
  (``JAX_COMPILATION_CACHE_DIR``, else ``<repo root>/.jax_cache``), so a
  respawned replica re-AOTs from the persistent XLA cache — zero fresh compiles after a rolling
  restart (asserted by test). Models travel to replicas as an
  ``.npz`` + JSON spec (param arrays + family), reconstructed and
  registered on the replica side.

- **Consistent-hash routing.** ``HashRing`` maps ``(model, bucket)`` to
  a preference order over replicas (md5, virtual nodes), so a given
  request shape always lands on the same replica — its AOT executables
  and HBM-resident weights stay hot. A request served by its home
  replica books ``serve.route_hits``; one re-routed around a draining or
  dead replica books ``serve.route_misses``.

- **Rolling drain/restart.** ``restart_replica`` marks the slot
  draining (the ring walks past it), waits for its in-flight count to
  reach zero (bounded by ``TPU_ML_SERVE_DRAIN_TIMEOUT_S``), respawns it
  through the supervisor, and re-admits it once it reports READY — under
  live load, zero requests fail (``serve.drain_events``,
  ``serve.replica_restarts``).

- **Placement vs HBM (PR 13).** ``plan_placement`` checks the fleet's
  per-replica param bytes against the HBM fleet manager's budget before
  spawn; an over-budget plan is surfaced (the in-replica HBM manager
  still pages, but the operator sees the pressure up front).

- **Unified observability plane.** The router is the fleet's trace
  admission point: it adopts a propagated context or mints one
  (``telemetry.tracectx``), injects it into the forwarded frame (fixed
  offset byte surgery on the fast lane — zero JSON), and records a
  ``serve.relay`` span per request; a silent retry leaves a ``retry``
  instant on the trace. Replicas answer a ``STATS`` frame on their serve
  socket (registry + flight-recorder tail) and persist a telemetry
  trailer next to their socket at READY and on teardown, so even a
  replica killed before its first request leaves its fragment behind.
  ``FleetExporter`` serves the merged view over one port: ``/metrics``
  (replica-labeled Prometheus rollup whose sums equal the per-replica
  registries), ``/healthz`` (worst-of component rollup), and
  ``/traces/<id>`` (stitched cross-process span trees).

The router is plain host orchestration — bytes in, bytes out; device
work happens only inside replicas. Per-device affinity: each replica
pins its default device to ``slot % device_count``, so an N-chip host
runs N replicas with one chip each.
"""

from __future__ import annotations

import argparse
import bisect
import hashlib
import http.server
import json
import logging
import os
import socket
import socketserver
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

from spark_rapids_ml_tpu.resilience.supervisor import WorkerSupervisor
from spark_rapids_ml_tpu.serving import buckets, fastlane
from spark_rapids_ml_tpu.telemetry import tracectx
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY, MetricsRegistry
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils import knobs

logger = logging.getLogger("spark_rapids_ml_tpu.serving")

SERVE_FLEET_REPLICAS_VAR = knobs.SERVE_FLEET_REPLICAS.name
SERVE_FLEET_SOCKET_DIR_VAR = knobs.SERVE_FLEET_SOCKET_DIR.name
SERVE_DRAIN_TIMEOUT_S_VAR = knobs.SERVE_DRAIN_TIMEOUT_S.name
WORKER_SLOT_VAR = knobs.WORKER_SLOT.name

_READY_SENTINEL = "READY"
_COMPILES_SENTINEL = "COMPILES"
_SPAWN_TIMEOUT_S = 120.0
# spill threshold: how far past the least-loaded replica the home
# replica's in-flight count may run before affinity yields to throughput
_SPILL_IN_FLIGHT = 8


def drain_timeout_s() -> float:
    raw = os.environ.get(SERVE_DRAIN_TIMEOUT_S_VAR, "")
    try:
        return max(
            0.0,
            float(raw) if raw else float(knobs.SERVE_DRAIN_TIMEOUT_S.default),
        )
    except ValueError:
        return float(knobs.SERVE_DRAIN_TIMEOUT_S.default)


# -- replica telemetry trailer -----------------------------------------------
#
# Each replica persists its registry + flight-recorder tail next to its
# socket: once right after READY (so a replica that dies before its first
# request still leaves its fragment behind — the crash-window gap the chaos
# matrix exercises) and again on graceful teardown (the final word). The
# router harvests the file exactly once per replica incarnation, so the
# fleet-wide /metrics sum and the stitched trace stream survive restarts.


def trailer_path(socket_path: str) -> str:
    return socket_path + ".trailer"


def write_trailer(socket_path: str) -> None:
    """Atomically persist this process's telemetry next to its socket."""
    trailer = {
        "pid": os.getpid(),
        "seq": TIMELINE.seq(),
        "mono_us": int(time.perf_counter() * 1e6),
        "registry": REGISTRY.snapshot().to_wire(),
        "events": TIMELINE.events(),
    }
    tmp = trailer_path(socket_path) + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(trailer, f)
    os.replace(tmp, trailer_path(socket_path))


def read_trailer(socket_path: str) -> dict | None:
    try:
        with open(trailer_path(socket_path), encoding="utf-8") as f:
            trailer = json.load(f)
    except (OSError, ValueError):
        return None
    return trailer if isinstance(trailer, dict) else None


# -- model spec: how fitted models travel to replica processes ---------------


def _model_arrays(model) -> tuple[str, dict[str, np.ndarray]]:
    """(family, arrays) a replica needs to reconstruct ``model``."""
    from spark_rapids_ml_tpu.models.linear import _GLMModel
    from spark_rapids_ml_tpu.models.pca import PCAModel

    if isinstance(model, PCAModel):
        arrays = {"pc": model.pc, "explainedVariance": model.explainedVariance}
        if model.mean is not None:
            arrays["mean"] = model.mean
            arrays["std"] = model.std
        return "pca", arrays
    if isinstance(model, _GLMModel) and model.coefficients is not None:
        return "linear", {
            "coefficients": model.coefficients,
            "intercept": np.asarray([model.intercept]),
        }
    raise TypeError(
        f"{type(model).__name__} has no fleet spec — the fleet ships pca "
        "and linear-family servables (extend _model_arrays for new "
        "families)"
    )


def _model_from_arrays(name: str, family: str, arrays: dict):
    if family == "pca":
        from spark_rapids_ml_tpu.models.pca import PCAModel

        return PCAModel(
            f"fleet-{name}",
            arrays["pc"],
            arrays["explainedVariance"],
            arrays.get("mean"),
            arrays.get("std"),
        )
    if family == "linear":
        from spark_rapids_ml_tpu.models.linear import LinearRegressionModel

        return LinearRegressionModel(
            uid=f"fleet-{name}",
            coefficients=arrays["coefficients"],
            intercept=float(arrays["intercept"][0]),
        )
    raise TypeError(f"unknown fleet spec family {family!r}")


def write_spec(path: str, models: dict[str, object]) -> dict[str, int]:
    """Write the fleet model spec (one ``.npz`` + manifest); returns the
    per-model param byte counts used by ``plan_placement``."""
    blobs: dict[str, np.ndarray] = {}
    manifest: dict[str, dict] = {}
    param_bytes: dict[str, int] = {}
    for name, model in sorted(models.items()):
        family, arrays = _model_arrays(model)
        manifest[name] = {"family": family, "arrays": sorted(arrays)}
        param_bytes[name] = int(
            sum(np.asarray(a).nbytes for a in arrays.values())
        )
        for field, arr in arrays.items():
            blobs[f"{name}::{field}"] = np.asarray(arr)
    np.savez(path, **blobs)
    with open(path + ".json", "w", encoding="utf-8") as f:
        json.dump(manifest, f)
    return param_bytes


def load_spec(path: str) -> dict[str, object]:
    with open(path + ".json", encoding="utf-8") as f:
        manifest = json.load(f)
    out: dict[str, object] = {}
    with np.load(path) as blobs:
        for name, meta in manifest.items():
            arrays = {
                field: blobs[f"{name}::{field}"] for field in meta["arrays"]
            }
            out[name] = _model_from_arrays(name, meta["family"], arrays)
    return out


def plan_placement(
    param_bytes: dict[str, int],
    replicas: int,
    *,
    budget_bytes: int | None = None,
) -> dict:
    """Check full-replication placement against the HBM budget.

    Routing is traffic placement, not weight placement: every replica
    registers every model (so any replica can absorb a re-route), and the
    per-replica HBM fleet manager pages cold weights within its budget.
    This plan surfaces the resident pressure up front: per-replica param
    bytes vs the budget the replicas will run under."""
    from spark_rapids_ml_tpu.serving import hbm

    if budget_bytes is None:
        budget_bytes = hbm.budget_bytes()
    total = int(sum(param_bytes.values()))
    fits = budget_bytes is None or total <= budget_bytes
    return {
        "replicas": replicas,
        "models": sorted(param_bytes),
        "param_bytes_per_replica": total,
        "budget_bytes": budget_bytes,
        "fits": fits,
    }


# -- consistent-hash ring ----------------------------------------------------


class HashRing:
    """Consistent hash over replica slots, keyed by (model, bucket).

    Virtual nodes flatten the load split; md5 keeps placement stable
    across processes and runs (``hash()`` is salted per process). The
    preference order lets the router walk past drained/dead replicas
    deterministically — the same key always tries the same sequence."""

    def __init__(self, slots: list[int], vnodes: int = 32):
        points: list[tuple[int, int]] = []
        for slot in slots:
            for v in range(vnodes):
                digest = hashlib.md5(
                    f"replica-{slot}:vnode-{v}".encode()
                ).digest()
                points.append((int.from_bytes(digest[:8], "big"), slot))
        points.sort()
        self._points = points
        self._hashes = [p[0] for p in points]
        self.slots = sorted(set(slots))

    @staticmethod
    def key(model: str, bucket: int) -> str:
        return f"{model}/{bucket}"

    def preference(self, key: str) -> list[int]:
        """Replica slots in routing-preference order for ``key`` (the
        first entry is the home replica; later entries absorb re-routes)."""
        if not self._points:
            return []
        h = int.from_bytes(
            hashlib.md5(key.encode()).digest()[:8], "big"
        )
        start = bisect.bisect_right(self._hashes, h) % len(self._points)
        seen: list[int] = []
        for i in range(len(self._points)):
            slot = self._points[(start + i) % len(self._points)][1]
            if slot not in seen:
                seen.append(slot)
                if len(seen) == len(self.slots):
                    break
        return seen


# -- replica process ---------------------------------------------------------


class ReplicaProcess:
    """One spawned replica server (the supervisor's worker contract:
    ``dead``/``proc``/``close()``)."""

    def __init__(
        self,
        slot: int,
        spec_path: str,
        socket_path: str,
        bucket_list: tuple[int, ...],
        extra_env: dict | None = None,
    ):
        self.slot = slot
        self.socket_path = socket_path
        cmd = [
            sys.executable, "-m", "spark_rapids_ml_tpu.serving.fleet",
            "--replica", "--spec", spec_path, "--socket", socket_path,
            "--buckets", ",".join(str(b) for b in bucket_list),
        ]
        env = dict(os.environ)
        env.update(extra_env or {})
        self.proc = subprocess.Popen(
            cmd,
            stdin=subprocess.PIPE,
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            env=env,
            text=True,
        )
        self._ready = False
        # filled by close() from the replica's shutdown report (the
        # warm-respawn proof reads these; None = no report). cache_misses
        # == 0 means every compile was a persistent-cache load.
        self.compiles: int | None = None
        self.cache_hits: int | None = None
        self.cache_misses: int | None = None
        # monotonic-clock handshake: the replica stamps its perf_counter
        # reading on the READY line; paired with the router's reading at
        # receipt it yields the per-replica clock offset the fleet trace
        # merge corrects with (0 on Linux, where perf_counter is the
        # system-wide CLOCK_MONOTONIC — but the correction is what makes
        # merged timelines portable)
        self.ready_mono_us: int | None = None
        self.ready_local_us: int | None = None

    @property
    def clock_offset_us(self) -> int:
        """Router-clock minus replica-clock at the READY handshake."""
        if self.ready_mono_us is None or self.ready_local_us is None:
            return 0
        return self.ready_local_us - self.ready_mono_us

    @property
    def dead(self) -> bool:
        return self.proc.poll() is not None

    def wait_ready(self, timeout: float = _SPAWN_TIMEOUT_S) -> bool:
        """Block until the replica prints READY (registration + AOT warmup
        done and the socket is listening) or dies."""
        if self._ready:
            return True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            line = self.proc.stdout.readline()
            if not line:
                return False  # died before READY
            if line.strip().startswith(_READY_SENTINEL):
                self.ready_local_us = int(time.perf_counter() * 1e6)
                parts = line.split()
                if len(parts) >= 3 and parts[2].isdigit():
                    self.ready_mono_us = int(parts[2])
                self._ready = True
                return True
        return False

    def close(self) -> None:
        """EOF on stdin is the shutdown sentinel; escalate if ignored."""
        try:
            if self.proc.stdin is not None:
                self.proc.stdin.close()
        except OSError:
            pass
        try:
            self.proc.wait(timeout=5.0)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait(timeout=5.0)
        try:
            # the replica's shutdown report ("COMPILES <n>") trails READY
            # on the same pipe; it is the evidence that a warm respawn
            # re-AOT'd from the shared cache instead of recompiling
            tail = self.proc.stdout.read() if self.proc.stdout else ""
            for line in (tail or "").splitlines():
                if line.startswith(_COMPILES_SENTINEL):
                    parts = line.split()
                    self.compiles = int(parts[1])
                    self.cache_hits = int(parts[2])
                    self.cache_misses = int(parts[3])
        except (OSError, ValueError, IndexError):
            pass
        try:
            os.unlink(self.socket_path)
        except OSError:
            pass


def _replica_main(argv: list[str]) -> int:
    """Entry point of one replica process: load the spec, register every
    model (AOT warmup against the shared compile cache), serve UDS until
    stdin EOF."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", required=True)
    ap.add_argument("--socket", required=True)
    ap.add_argument("--buckets", default="")
    args = ap.parse_args(argv)

    import jax

    # per-device affinity: replica i owns device i (mod device count), so
    # an N-chip host runs N replicas with one chip each
    slot = int(os.environ.get(WORKER_SLOT_VAR, "0") or 0)
    devices = jax.devices()
    if len(devices) > 1:
        jax.config.update("jax_default_device", devices[slot % len(devices)])

    from spark_rapids_ml_tpu.serving.batcher import MicroBatcher
    from spark_rapids_ml_tpu.serving.registry import get_registry
    from spark_rapids_ml_tpu.serving.server import ServeUDSListener

    bucket_list = tuple(
        int(b) for b in args.buckets.split(",") if b.strip()
    ) or None
    registry = get_registry()
    for name, model in load_spec(args.spec).items():
        registry.register(name, model, bucket_list=bucket_list)
    batcher = MicroBatcher(registry).start()
    listener = ServeUDSListener(args.socket, batcher).start()
    print(
        f"{_READY_SENTINEL} {args.socket} {int(time.perf_counter() * 1e6)}",
        flush=True,
    )
    # first trailer flush right after READY: a replica killed between
    # READY and its first request still leaves its telemetry fragment
    # behind for the router to merge
    write_trailer(args.socket)
    try:
        sys.stdin.read()  # blocks until the parent closes our stdin
    except KeyboardInterrupt:
        pass
    finally:
        listener.stop()
        batcher.stop()
        # final trailer flush on supervised teardown: the registry and
        # flight-recorder state the fleet aggregation folds in after this
        # process is gone
        try:
            write_trailer(args.socket)
        except OSError:
            pass
        # shutdown report: this replica's compile traffic. A respawn
        # warmed from the shared AOT cache reports cache_misses == 0 —
        # every registration-time compile was a disk load, not fresh XLA
        snap = REGISTRY.snapshot()
        print(
            f"{_COMPILES_SENTINEL} "
            f"{int(snap.hist('compile.seconds').count)} "
            f"{int(snap.counter('compile.cache_hits'))} "
            f"{int(snap.counter('compile.cache_misses'))}",
            flush=True,
        )
    return 0


# -- router ------------------------------------------------------------------


class _RouterHandler(socketserver.StreamRequestHandler):
    """One client connection: read a frame, pick a replica by consistent
    hash, forward the raw bytes, relay the raw response. Per-replica
    upstream connections persist for the life of the client connection,
    so a steady client pays connection setup once per replica."""

    def setup(self):
        super().setup()
        self._upstream: dict[int, socket.socket] = {}

    def finish(self):
        for s in self._upstream.values():
            try:
                s.close()
            except OSError:
                pass
        super().finish()

    # frame IO ---------------------------------------------------------------

    def _read_exact(self, rfile, n: int) -> bytes:
        chunks = []
        while n > 0:
            chunk = rfile.read(n)
            if not chunk:
                raise EOFError("peer closed mid-frame")
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def _read_request(self):
        """Read one client frame; returns ``(model, rows, raw_frame, ctx,
        parent)`` or None on clean EOF. The frame is parsed only far
        enough to route — and to thread the trace context through: a
        propagated context is adopted (the relay span re-parents it), an
        absent one is minted here (the router is the fleet's admission
        point), and the forwarded frame carries the relay span's identity
        so the replica's request span parents to it. On the fast lane the
        injection is fixed-offset byte surgery (zero JSON); on the JSON
        wire the header — already decoded for routing — is re-encoded
        through the counted codec."""
        head = self.rfile.read(4)
        if not head:
            return None
        if len(head) < 4:
            raise EOFError("peer closed mid-frame")
        if fastlane.is_fastlane_head(head):
            # fast lane: fixed struct carries (name_len, rows, cols) — the
            # router routes with zero JSON and zero dict churn, same as
            # the replica will serve it
            struct_raw = self._read_exact(self.rfile, fastlane.request_struct_size())
            name_len, rows, cols = fastlane.peek_request(struct_raw)
            name = self._read_exact(self.rfile, name_len)
            payload = self._read_exact(self.rfile, rows * cols * 4)
            parent = fastlane.peek_trace(struct_raw)
            ctx = (
                parent.child() if parent is not None
                else tracectx.mint(origin="router")
            )
            if ctx is not None:
                struct_raw = fastlane.rewrite_trace(struct_raw, ctx)
            return (
                name.decode("utf-8"), rows,
                b"".join((head, struct_raw, name, payload)),
                ctx, parent,
            )
        header_raw = self._read_exact(self.rfile, int.from_bytes(head, "big"))
        header = fastlane.json_loads(header_raw)
        model = str(header.get("model", ""))
        if header.get("wire") == "binary":
            payload = self._read_exact(
                self.rfile, int(header.get("payload_bytes", 0))
            )
            rows = int((header.get("shape") or [1])[0])
        else:
            payload = b""
            rows = len(header.get("instances") or [None])
        parent = tracectx.from_header(str(header.get("trace", "")))
        ctx = (
            parent.child() if parent is not None
            else tracectx.mint(origin="router")
        )
        if ctx is not None:
            header["trace"] = ctx.to_header()
            header_raw = fastlane.json_dumps(header).encode()
            head = len(header_raw).to_bytes(4, "big")
        return model, rows, head + header_raw + payload, ctx, parent

    def _relay_response(self, rfile) -> bytes:
        """Read one complete replica response frame, verbatim."""
        head = self._read_exact(rfile, 4)
        if fastlane.is_fastlane_head(head):
            struct_raw = self._read_exact(
                rfile, fastlane.response_struct_size()
            )
            payload_len = fastlane.peek_response_payload_len(struct_raw)
            return head + struct_raw + self._read_exact(rfile, payload_len)
        header_raw = self._read_exact(rfile, int.from_bytes(head, "big"))
        header = fastlane.json_loads(header_raw)
        payload = self._read_exact(rfile, int(header.get("payload_bytes", 0)))
        return head + header_raw + payload

    def _upstream_for(self, slot: int) -> socket.socket:
        s = self._upstream.get(slot)
        if s is None:
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.connect(self.server.fleet.replica_socket(slot))
            self._upstream[slot] = s
        return s

    def _drop_upstream(self, slot: int, s: socket.socket) -> None:
        self._upstream.pop(slot, None)
        try:
            s.close()
        except OSError:
            pass

    def _forward(self, slot: int, frame: bytes) -> bytes:
        cached = slot in self._upstream
        s = self._upstream_for(slot)
        try:
            s.sendall(frame)
            return self._relay_response(s.makefile("rb"))
        except (OSError, EOFError):
            self._drop_upstream(slot, s)
            if not cached:
                raise
        # the cached upstream went stale between requests (the replica
        # was rolling-restarted and its listener re-created); the frame
        # is fully buffered and nothing has been relayed to the client,
        # so one fresh-connection retry on the same slot is safe
        s = self._upstream_for(slot)
        try:
            s.sendall(frame)
            return self._relay_response(s.makefile("rb"))
        except (OSError, EOFError):
            self._drop_upstream(slot, s)
            raise

    def handle(self):
        fleet: ServeFleet = self.server.fleet
        try:
            while True:
                req = self._read_request()
                if req is None:
                    return
                model, rows, frame, ctx, parent = req
                try:
                    bucket = buckets.serve_bucket(max(1, rows))
                except ValueError:
                    bucket = buckets.max_batch_rows()
                t0 = time.perf_counter()
                response = fleet.route(
                    model, bucket, frame, self._forward, trace=ctx
                )
                if ctx is not None:
                    # the relay span: fleet admission (root when minted
                    # here) covering route + forward + response relay
                    TIMELINE.record_span(
                        "serve.relay", t0, time.perf_counter(),
                        model=model,
                        **tracectx.span_labels(ctx, parent=parent),
                    )
                self.wfile.write(response)
                self.wfile.flush()
        except (EOFError, BrokenPipeError, ConnectionResetError):
            pass
        except Exception:  # noqa: BLE001 - one bad conn must not kill the router
            logger.exception("fleet router connection failed")


class ServeFleet:
    """N supervised replica processes behind one consistent-hash router."""

    def __init__(
        self,
        models: dict[str, object],
        *,
        replicas: int | None = None,
        socket_dir: str | None = None,
        bucket_list: tuple[int, ...] = (),
        extra_env: dict | None = None,
    ):
        if replicas is None:
            raw = os.environ.get(SERVE_FLEET_REPLICAS_VAR, "")
            replicas = int(raw) if raw.strip() else int(
                knobs.SERVE_FLEET_REPLICAS.default
            )
        if replicas < 1:
            raise ValueError("a serve fleet needs at least 1 replica")
        self.replicas = replicas
        self.bucket_list = tuple(bucket_list)
        self._extra_env = dict(extra_env or {})
        socket_dir = socket_dir or os.environ.get(
            SERVE_FLEET_SOCKET_DIR_VAR, ""
        )
        if not socket_dir:
            socket_dir = tempfile.mkdtemp(prefix="tpu-ml-fleet-")
        self.socket_dir = socket_dir
        os.makedirs(socket_dir, exist_ok=True)
        self.spec_path = os.path.join(socket_dir, "fleet-spec.npz")
        self.param_bytes = write_spec(self.spec_path, models)
        self.placement = plan_placement(self.param_bytes, replicas)
        if not self.placement["fits"]:
            logger.warning(
                "fleet placement exceeds the HBM budget (%d bytes/replica "
                "vs %s) — replicas will page weights under pressure",
                self.placement["param_bytes_per_replica"],
                self.placement["budget_bytes"],
            )
        self.router_path = os.path.join(socket_dir, "router.sock")
        self.ring = HashRing(list(range(replicas)))
        self._supervisor = WorkerSupervisor(self._spawn, replicas)
        self._state_lock = threading.Lock()
        self._state_cond = threading.Condition(self._state_lock)
        self._draining: set[int] = set()
        self._in_flight: dict[int, int] = {i: 0 for i in range(replicas)}
        self._served: dict[int, int] = {i: 0 for i in range(replicas)}
        self._router: socketserver.ThreadingUnixStreamServer | None = None
        self._router_thread: threading.Thread | None = None
        # fleet observability plane: dead replicas' final registries and
        # flight-recorder fragments (harvested from telemetry trailers,
        # once per (slot, pid) incarnation) so the merged /metrics sum and
        # the stitched trace stream stay right through restarts
        self._agg_lock = threading.Lock()
        self._final_registry = MetricsRegistry()
        self._final_events: list[dict] = []
        self._harvested: set[tuple[int, int]] = set()
        self._clock_offsets: dict[int, int] = {}
        self._exporter: FleetExporter | None = None

    # -- lifecycle ----------------------------------------------------------

    def _spawn(self, extra_env: dict) -> ReplicaProcess:
        slot = int(extra_env.get(WORKER_SLOT_VAR, "0") or 0)
        env = dict(self._extra_env)
        env.update(extra_env)
        return ReplicaProcess(
            slot,
            self.spec_path,
            self.replica_socket(slot),
            self.bucket_list,
            extra_env=env,
        )

    def replica_socket(self, slot: int) -> str:
        return os.path.join(self.socket_dir, f"replica-{slot}.sock")

    def start(self, timeout: float = _SPAWN_TIMEOUT_S) -> "ServeFleet":
        """Spawn every replica, wait until all report READY, then open the
        router socket."""
        self._supervisor.begin_stage()
        for slot in range(self.replicas):
            worker = self._supervisor.checkout(slot)
            if worker is None or not worker.wait_ready(timeout):
                raise RuntimeError(
                    f"fleet replica {slot} failed to become ready"
                    + self._replica_stderr(worker)
                )
            self._supervisor.report_success(slot)
            with self._agg_lock:
                self._clock_offsets[slot] = worker.clock_offset_us
        if os.path.exists(self.router_path):
            os.unlink(self.router_path)
        self._router = socketserver.ThreadingUnixStreamServer(
            self.router_path, _RouterHandler
        )
        self._router.daemon_threads = True
        self._router.fleet = self
        self._router_thread = threading.Thread(
            target=self._router.serve_forever,
            kwargs={"poll_interval": 0.05},
            name="tpu-ml-fleet-router",
            daemon=True,
        )
        self._router_thread.start()
        REGISTRY.gauge_set("serve.fleet_replicas", self.live_replicas())
        return self

    @staticmethod
    def _replica_stderr(worker) -> str:
        if worker is None or worker.proc.stderr is None:
            return ""
        try:
            tail = worker.proc.stderr.read() or ""
        except (OSError, ValueError):
            return ""
        return ("\n--- replica stderr ---\n" + tail[-2000:]) if tail else ""

    def stop(self, timeout: float = 10.0) -> None:
        if self._exporter is not None:
            self._exporter.stop(timeout)
            self._exporter = None
        if self._router is not None:
            self._router.shutdown()
            self._router.server_close()
            self._router = None
        if self._router_thread is not None:
            self._router_thread.join(timeout)
            self._router_thread = None
        try:
            os.unlink(self.router_path)
        except OSError:
            pass
        self._supervisor.close()
        # every replica just flushed its teardown trailer; fold the final
        # fragments in so post-stop reads (bench, reports) see the fleet's
        # complete telemetry
        for slot in range(self.replicas):
            self._harvest_trailer(slot)
        REGISTRY.gauge_set("serve.fleet_replicas", 0)

    # -- routing ------------------------------------------------------------

    def live_replicas(self) -> int:
        n = 0
        for slot in range(self.replicas):
            lease = self._supervisor._slots[slot]
            w = lease.worker
            if w is not None and not w.dead:
                n += 1
        return n

    def _available(self, slot: int) -> bool:
        with self._state_lock:
            if slot in self._draining:
                return False
        lease = self._supervisor._slots[slot]
        w = lease.worker
        return w is not None and not w.dead

    def route(
        self, model: str, bucket: int, frame: bytes, forward, trace=None
    ) -> bytes:
        """Pick a replica for (model, bucket) and forward the frame.

        The home replica (first in the ring's preference order) gets the
        request unless it is draining, dead, or **saturated**: models are
        fully replicated (every replica AOT-warms every servable), so
        when the home replica's in-flight count runs ``_SPILL_IN_FLIGHT``
        past the least-loaded replica's, the request spills there —
        affinity is a cache-warmth preference, not a throughput ceiling.
        Anything that lands off-home books ``serve.route_misses``
        (fallback and spill alike; the hit-rate is the affinity measure).
        A transport failure marks the replica crashed with the supervisor
        and retries the (fully buffered) frame on the next preference — a
        mid-request replica death is a retry, not a client-visible
        failure."""
        last_err: Exception | None = None
        prefs = self.ring.preference(HashRing.key(model, bucket))
        order = [s for s in prefs if self._available(s)]
        if len(order) > 1:
            with self._state_lock:
                in_flight = {s: self._in_flight[s] for s in order}
            least = min(order, key=in_flight.get)
            if in_flight[order[0]] - in_flight[least] >= _SPILL_IN_FLIGHT:
                order.remove(least)
                order.insert(0, least)
        for slot in order:
            if not self._available(slot):
                continue
            with self._state_lock:
                # the draining re-check and the in-flight increment must
                # be one atomic step against drain(): once admitted here,
                # the slot's in-flight count holds the drain open until
                # the finally below releases it
                if slot in self._draining:
                    continue
                self._in_flight[slot] += 1
            try:
                response = forward(slot, frame)
            except (OSError, EOFError) as e:
                last_err = e
                worker = self._supervisor._slots[slot].worker
                if worker is not None and worker.dead:
                    self._supervisor.report_crash(slot, e)
                    # the dead replica's READY-time trailer is all that is
                    # left of its telemetry — fold it in now
                    self._harvest_trailer(slot)
                if trace is not None:
                    # the silent retry leaves a visible mark on the trace:
                    # an instant carrying the relay span's identity, so
                    # the stitched tree shows which hop re-routed
                    TIMELINE.record_instant(
                        "retry", slot=str(slot), model=model,
                        **tracectx.span_labels(trace),
                    )
                continue
            finally:
                with self._state_cond:
                    self._in_flight[slot] -= 1
                    self._state_cond.notify_all()
            with self._state_lock:
                self._served[slot] += 1
            if prefs and slot == prefs[0]:
                REGISTRY.counter_inc("serve.route_hits", model=model)
            else:
                REGISTRY.counter_inc("serve.route_misses", model=model)
            return response
        raise last_err or RuntimeError(
            f"no live replica for {model!r} (all draining or dead)"
        )

    # -- rolling drain / restart --------------------------------------------

    def drain(self, slot: int, timeout: float | None = None) -> bool:
        """Stop routing to ``slot`` and wait for its in-flight requests to
        finish; returns True when the replica drained fully inside the
        bound."""
        timeout = drain_timeout_s() if timeout is None else timeout
        with self._state_cond:
            self._draining.add(slot)
            REGISTRY.counter_inc("serve.drain_events", slot=str(slot))
            deadline = time.monotonic() + timeout
            while self._in_flight[slot] > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._state_cond.wait(left)
        return True

    def undrain(self, slot: int) -> None:
        with self._state_lock:
            self._draining.discard(slot)

    def restart_replica(
        self, slot: int, timeout: float = _SPAWN_TIMEOUT_S
    ) -> bool:
        """Rolling restart of one replica under live load: drain, respawn
        through the supervisor (lease/backoff/breaker), re-admit on READY.
        The shared AOT cache makes the respawn warm — zero fresh compiles,
        verified by test."""
        drained = self.drain(slot)
        if not drained:
            logger.warning(
                "replica %d drain timed out with requests in flight; "
                "restarting anyway", slot,
            )
        lease = self._supervisor._slots[slot]
        worker = lease.worker
        if worker is not None:
            worker.close()
            # the outgoing incarnation's graceful-teardown trailer is now
            # final — fold its registry + events into the fleet plane
            self._harvest_trailer(slot)
        replacement = self._supervisor.checkout(slot)
        ok = replacement is not None and replacement.wait_ready(timeout)
        if ok:
            self._supervisor.report_success(slot)
            with self._agg_lock:
                self._clock_offsets[slot] = replacement.clock_offset_us
            REGISTRY.counter_inc("serve.replica_restarts", slot=str(slot))
        else:
            self._supervisor.report_crash(
                slot, RuntimeError("replica respawn did not become ready")
            )
        self.undrain(slot)
        REGISTRY.gauge_set("serve.fleet_replicas", self.live_replicas())
        return ok

    # -- fleet-wide hot-swap propagation -------------------------------------

    def swap_models(
        self, models: dict[str, object], timeout: float = _SPAWN_TIMEOUT_S
    ) -> bool:
        """Propagate a hot-swap to every replica: merge ``models`` into the
        fleet spec, then rolling-restart each slot through the existing
        drain discipline — a draining slot finishes its in-flight requests
        on the old spec while the ring routes new admissions around it, so
        the fleet converges replica-by-replica to the new version with
        zero client-visible failures (the chaos matrix kills a replica in
        the middle of exactly this walk). Returns True when every replica
        came back READY on the new spec."""
        current = load_spec(self.spec_path)
        current.update(models)
        self.param_bytes = write_spec(self.spec_path, current)
        self.placement = plan_placement(self.param_bytes, self.replicas)
        ok = True
        for slot in range(self.replicas):
            ok = self.restart_replica(slot, timeout) and ok
        return ok

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        with self._state_lock:
            served = dict(self._served)
            in_flight = dict(self._in_flight)
            draining = sorted(self._draining)
        with self._agg_lock:
            offsets = dict(self._clock_offsets)
        return {
            "replicas": self.replicas,
            "live_replicas": self.live_replicas(),
            "router_socket": self.router_path,
            "served_per_replica": {str(k): v for k, v in served.items()},
            "in_flight": {str(k): v for k, v in in_flight.items()},
            "draining": draining,
            "clock_offsets_us": {str(k): v for k, v in offsets.items()},
            "placement": self.placement,
            "supervisor": self._supervisor.summary(),
        }

    # -- fleet observability plane -------------------------------------------

    def _harvest_trailer(self, slot: int) -> None:
        """Fold a dead/stopped replica incarnation's telemetry trailer into
        the fleet aggregation state — once per (slot, pid), so the READY
        trailer of a crashed incarnation and the teardown trailer of a
        graceful one are never double-counted."""
        trailer = read_trailer(self.replica_socket(slot))
        if not trailer:
            return
        pid = int(trailer.get("pid") or 0)
        with self._agg_lock:
            if (slot, pid) in self._harvested:
                return
            self._harvested.add((slot, pid))
            self._final_registry.merge_wire(
                trailer.get("registry") or {}, replica=str(slot)
            )
            for e in trailer.get("events") or []:
                if isinstance(e, dict):
                    self._final_events.append(
                        dict(
                            e,
                            args=dict(
                                e.get("args") or {}, replica=str(slot)
                            ),
                        )
                    )

    def scrape_stats(
        self, slot: int, since_seq: int = 0, timeout: float = 5.0
    ) -> dict | None:
        """Pull one live replica's registry + flight-recorder tail over the
        STATS frame on its serve socket; None when the replica is not
        scrapable. Plain stdlib json — the scrape surface stays off the
        counted serve.json_codec series on both sides."""
        if not self._available(slot):
            return None
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            s.settimeout(timeout)
            s.connect(self.replica_socket(slot))
            raw = json.dumps(
                {"kind": "stats", "since_seq": since_seq}
            ).encode()
            s.sendall(len(raw).to_bytes(4, "big") + raw)
            rfile = s.makefile("rb")
            head = rfile.read(4)
            if len(head) < 4:
                return None
            body = b""
            n = int.from_bytes(head, "big")
            while len(body) < n:
                chunk = rfile.read(n - len(body))
                if not chunk:
                    return None
                body += chunk
            stats = json.loads(body)
            return stats if isinstance(stats, dict) else None
        except (OSError, ValueError):
            return None
        finally:
            try:
                s.close()
            except OSError:
                pass

    def fleet_events(self) -> list[dict]:
        """The merged fleet-wide flight-recorder stream: the router
        process's own events (relay spans, retry instants), every live
        replica's scraped tail, and the harvested fragments of dead
        incarnations — deduplicated by (pid, seq) so a span seen both over
        a scrape and in a later trailer lands exactly once. Replica events
        are stamped ``replica=<slot>`` in args."""
        seen: set[tuple] = set()
        out: list[dict] = []

        def add(events: list, replica: str = "") -> None:
            for e in events:
                if not isinstance(e, dict):
                    continue
                k = (e.get("pid"), e.get("seq"))
                if k in seen:
                    continue
                seen.add(k)
                if replica:
                    e = dict(
                        e, args=dict(e.get("args") or {}, replica=replica)
                    )
                out.append(e)

        add(TIMELINE.events())
        for slot in range(self.replicas):
            stats = self.scrape_stats(slot)
            if stats:
                add(stats.get("events") or [], replica=str(slot))
        with self._agg_lock:
            final = list(self._final_events)
        add(final)
        return out

    def fleet_registry(self, include_router: bool = True) -> MetricsRegistry:
        """One merged registry for the whole fleet: live replicas scraped
        over STATS (``replica=<slot>``), dead incarnations' final trailers,
        and (by default) the router process's own registry
        (``replica=router``). Summing any family across the replica label
        reproduces the per-replica registries exactly — the contract the
        fleet /metrics test pins."""
        merged = MetricsRegistry()
        for slot in range(self.replicas):
            stats = self.scrape_stats(slot)
            if stats:
                merged.merge_wire(
                    stats.get("registry") or {}, replica=str(slot)
                )
        with self._agg_lock:
            merged.merge_wire(self._final_registry.snapshot().to_wire())
        if include_router:
            merged.merge_wire(
                REGISTRY.snapshot().to_wire(), replica="router"
            )
        return merged

    def healthz(self) -> dict:
        """Worst-of rollup across fleet components: any dead replica (or a
        closed router) makes the fleet ``down``, any draining replica
        ``degraded``, otherwise ``ok``."""
        components: dict[str, str] = {}
        with self._state_lock:
            draining = set(self._draining)
        for slot in range(self.replicas):
            w = self._supervisor._slots[slot].worker
            if w is None or w.dead:
                components[f"replica-{slot}"] = "down"
            elif slot in draining:
                components[f"replica-{slot}"] = "draining"
            else:
                components[f"replica-{slot}"] = "ok"
        components["router"] = "ok" if self._router is not None else "down"
        if any(s == "down" for s in components.values()):
            status = "down"
        elif any(s == "draining" for s in components.values()):
            status = "degraded"
        else:
            status = "ok"
        return {
            "status": status,
            "components": components,
            "live_replicas": self.live_replicas(),
            "replicas": self.replicas,
        }

    def trace_coverage(self) -> dict:
        """Stitching coverage over the merged fleet event stream — the
        ≥99%-complete / zero-orphan number bench gates on."""
        return tracectx.coverage(self.fleet_events())

    def start_exporter(self, port: int = 0) -> "FleetExporter":
        """Start (or return) the fleet-wide scrape surface."""
        if self._exporter is None:
            self._exporter = FleetExporter(self, port).start()
        return self._exporter


# -- fleet exporter ----------------------------------------------------------


class _FleetExporterHandler(http.server.BaseHTTPRequestHandler):
    """The unified observability plane over one port: merged fleet-wide
    Prometheus metrics, a worst-of health rollup, and stitched
    cross-process trace trees."""

    server_version = "tpu-ml-fleet-exporter/1.0"

    def log_message(self, format, *args):  # noqa: A002 - http.server naming
        logger.debug("fleet exporter: " + format, *args)

    def _send(self, code: int, body: bytes, ctype: str) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _json(self, code: int, payload: dict) -> None:
        self._send(
            code, json.dumps(payload).encode() + b"\n", "application/json"
        )

    def do_GET(self):  # noqa: N802 - http.server naming contract
        fleet: ServeFleet = self.server.fleet
        path = self.path.split("?", 1)[0].rstrip("/") or "/"
        if path == "/metrics":
            self._send(
                200,
                fleet.fleet_registry().to_prometheus().encode(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
            return
        if path == "/healthz":
            health = fleet.healthz()
            self._json(503 if health["status"] == "down" else 200, health)
            return
        if path == "/traces":
            self._json(200, fleet.trace_coverage())
            return
        if path.startswith("/traces/"):
            tid = path[len("/traces/"):]
            tree = tracectx.stitch(fleet.fleet_events(), tid)
            if tree is None:
                self._json(404, {"error": f"unknown trace {tid!r}"})
            else:
                self._json(200, tree)
            return
        self._json(404, {"error": f"no such endpoint: {path}"})


class FleetExporter:
    """HTTP scrape surface for a running fleet: ``/metrics`` (merged,
    replica-labeled), ``/healthz`` (worst-of rollup), ``/traces``
    (stitching coverage) and ``/traces/<id>`` (one stitched tree)."""

    def __init__(self, fleet: ServeFleet, port: int = 0):
        self._httpd = http.server.ThreadingHTTPServer(
            ("127.0.0.1", port), _FleetExporterHandler
        )
        self._httpd.daemon_threads = True
        self._httpd.fleet = fleet
        self._thread: threading.Thread | None = None

    @property
    def port(self) -> int:
        return self._httpd.server_address[1]

    def url(self, path: str = "/") -> str:
        return f"http://127.0.0.1:{self.port}{path}"

    def start(self) -> "FleetExporter":
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._httpd.serve_forever,
                kwargs={"poll_interval": 0.05},
                name="tpu-ml-fleet-exporter",
                daemon=True,
            )
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None


if __name__ == "__main__":
    if "--replica" in sys.argv:
        argv = [a for a in sys.argv[1:] if a != "--replica"]
        raise SystemExit(_replica_main(argv))
    raise SystemExit(
        "serving.fleet is a library (use ServeFleet) — only --replica "
        "runs standalone"
    )
