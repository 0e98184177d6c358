"""Compile + device-memory observability.

XLA compiles are the TPU path's hidden multi-second cost (the reason
``utils.config.enable_compilation_cache`` exists); an un-attributed fit that
spends 8 s compiling and 0.3 s on the MXU looks like a 27× perf bug. JAX
already emits the needed signals through ``jax.monitoring`` — this module
subscribes once per process and folds them into the telemetry registry:

- ``/jax/core/compile/backend_compile_duration``  → ``compile.seconds``
  histogram: one event per compile REQUEST, i.e. per program built *or
  loaded from the persistent cache* (JAX times the cache lookup inside the
  same event). Where the cache is in use, ``compile.cache_misses`` is the
  count of programs actually compiled. The event's ``fun_name`` keyword
  also books ``compile.program_seconds{program}``, so a cold run says which
  program its minutes went to.
- ``/jax/core/compile/jaxpr_trace_duration`` and
  ``.../jaxpr_to_mlir_module_duration``           → ``compile.trace_seconds``
  / ``compile.lower_seconds`` histograms (Python-side tracing/lowering).
- ``/jax/compilation_cache/cache_hits|cache_misses`` → counters — whether
  the persistent XLA cache is actually saving the worker/driver processes
  the recompile.
- ``/jax/compilation_cache/cache_retrieval_time_sec`` →
  ``compile.cache_load_seconds`` histogram (what a cache hit cost).
- ``/jax/compilation_cache/compile_time_saved_sec`` → counter (seconds the
  cache provably saved). JAX reports compile time less load time, which is
  negative for a program that loads slower than it compiled; a counter
  never goes down, so ``max(0, saved)`` is booked and the load seconds
  above carry the rest.

Compile-ish durations this table does not name fall through to a generic
``compile.other_seconds`` histogram rather than being dropped.

Device memory has no event stream; :func:`sample_device_memory` polls
``Device.memory_stats()`` (PJRT exposes ``bytes_in_use`` /
``peak_bytes_in_use`` on TPU/GPU; CPU returns nothing) into per-device
gauges. The fit instrumentation samples at fit end, so ``FitReport`` carries
the peak HBM of that fit's process lifetime — the number an OOM post-mortem
needs first.
"""

from __future__ import annotations

import logging
import threading

from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

logger = logging.getLogger("spark_rapids_ml_tpu")

_install_lock = threading.Lock()
_installed = False

_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}

_DURATION_HISTS = {
    "/jax/core/compile/backend_compile_duration": "compile.seconds",
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_seconds",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_seconds",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_load_seconds",
}

_DURATION_COUNTERS = {
    "/jax/compilation_cache/compile_time_saved_sec": "compile.cache_time_saved_s",
}


def _on_event(event: str, **kwargs) -> None:
    name = _EVENT_COUNTERS.get(event)
    if name:
        REGISTRY.counter_inc(name)


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    name = _DURATION_HISTS.get(event)
    if name:
        REGISTRY.histogram_record(name, duration_secs)
        program = kwargs.get("fun_name")
        if program and name == "compile.seconds":
            REGISTRY.histogram_record(
                "compile.program_seconds", duration_secs, program=str(program)
            )
        return
    name = _DURATION_COUNTERS.get(event)
    if name:
        REGISTRY.counter_inc(name, max(0.0, duration_secs))
        return
    if "compile" in event:  # unnamed: keep the signal, generically
        REGISTRY.histogram_record("compile.other_seconds", duration_secs)


def install_monitoring() -> None:
    """Register the jax.monitoring listeners (idempotent, thread-safe)."""
    global _installed
    if _installed:
        return
    with _install_lock:
        if _installed:
            return
        import jax.monitoring as M

        M.register_event_listener(_on_event)
        M.register_event_duration_secs_listener(_on_duration)
        _installed = True


# memory_stats keys worth exporting (PJRT's full dict carries ~15 allocator
# internals; these are the capacity-planning triple)
_MEM_KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")


def sample_device_memory() -> dict[str, dict[str, int]]:
    """Poll per-device memory stats into gauges; returns the sampled map.

    ``{device: {bytes_in_use, peak_bytes_in_use, bytes_limit}}`` — empty on
    backends that expose no stats (CPU) and when JAX isn't initialized yet
    (sampling must never be the thing that first spins up a backend).
    """
    import jax

    out: dict[str, dict[str, int]] = {}
    try:
        devices = jax.local_devices()
    except Exception:  # backend init failed/wedged — never break the caller
        return out
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        dev = str(d)
        picked = {
            k: int(stats[k]) for k in _MEM_KEYS if stats.get(k) is not None
        }
        if not picked:
            continue
        out[dev] = picked
        for k, v in picked.items():
            REGISTRY.gauge_set(f"device.{k}", v, device=dev)
    return out
