"""Warm-path serving runtime: AOT registry, shape buckets, micro-batching.

The fit path optimizes throughput; this package optimizes the *other* end
of the model lifecycle — low-latency scoring of already-fitted models:

- :mod:`.registry` — servable extraction + AOT compilation. Registering a
  fitted model lowers its pure ``kernel(params, x)`` transform for every
  rung of the serve bucket ladder up front (``jit(...).lower(...).compile()``)
  and persists the executables through the XLA compilation cache
  (``utils.config.enable_compilation_cache``), so a fresh process warms
  from disk instead of recompiling.
- :mod:`.buckets` — power-of-two row buckets with zero padding and
  valid-row slicing; the enumerable bucket ladder is what makes the
  zero-recompile regime a hard guarantee rather than a hope.
- :mod:`.batcher` — bounded-queue micro-batching: concurrent requests for
  the same ``(model, bucket)`` coalesce into one device dispatch inside a
  ``TPU_ML_SERVE_MAX_DELAY_US`` window.
- :mod:`.server` — ``/v1/models`` + ``/v1/models/<name>:predict`` HTTP
  front-end (JSON and the zero-copy ``application/x-tpu-ml-f32`` binary
  wire format) grafted onto the telemetry exporter, so ``serve.latency``
  lands in the same registry the SLO engine and ``/metrics`` read — plus
  the framing-free ``TPU_ML_SERVE_UDS_PATH`` Unix-socket listener.
- :mod:`.client` — the in-process transport: ``predict`` straight into the
  shared micro-batcher, zero framing, same telemetry.
- :mod:`.hbm` — the multi-model HBM fleet manager: resident param byte
  accounting against the live watermark, LRU weight paging
  (``serve.page_in``/``serve.page_out``), SLO-burn load shedding.
- :mod:`.fastlane` — the JSON-free dispatch lane: magic-framed binary
  wire straight from socket to batcher, pinned response-buffer pool, and
  the counted JSON codec that proves the hot path stays dict-free.
- :mod:`.fleet` — multi-process scale-out: N supervised replica servers
  with per-device affinity behind one consistent-hash router, rolling
  drain/restart with zero failed requests and zero warm-respawn compiles.

Submodules are loaded lazily: ``buckets`` is importable without jax, and
tooling that only wants the ladder math never pays the model-layer import.
"""

from __future__ import annotations

import importlib

_SUBMODULES = (
    "buckets", "registry", "batcher", "server", "client", "hbm",
    "fastlane", "fleet",
)

_LAZY_ATTRS = {
    # buckets
    "serve_bucket": "buckets",
    "bucket_ladder": "buckets",
    "pad_to_bucket": "buckets",
    # registry
    "ModelRegistry": "registry",
    "ServableEntry": "registry",
    "servable_from_model": "registry",
    "get_registry": "registry",
    "reset_for_tests": "registry",
    "validate_request": "registry",
    # batcher
    "MicroBatcher": "batcher",
    "ServeFuture": "batcher",
    # server
    "ServingHTTPServer": "server",
    "ServeUDSListener": "server",
    "start_serving": "server",
    "stop_serving": "server",
    "get_serving_server": "server",
    # client
    "ServeClient": "client",
    "get_client": "client",
    # hbm
    "HbmFleetManager": "hbm",
    "ServeShed": "hbm",
    "get_fleet": "hbm",
    # fastlane
    "FastlaneError": "fastlane",
    "ResponseBufferPool": "fastlane",
    "RESPONSE_POOL": "fastlane",
    # fleet
    "ServeFleet": "fleet",
    "HashRing": "fleet",
    "plan_placement": "fleet",
}

__all__ = list(_SUBMODULES) + sorted(_LAZY_ATTRS)


def __getattr__(name: str):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    target = _LAZY_ATTRS.get(name)
    if target is not None:
        module = importlib.import_module(f"{__name__}.{target}")
        return getattr(module, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def __dir__():
    return sorted(set(globals()) | set(__all__))
