"""Analytical kernel cost accounting — XLA's answer to "how fast *should*
this be?".

The reference platform's perf story leans on measured-vs-theoretical
throughput (its benchmark reports quote fractions of cuBLAS peak); the XLA
equivalent of those datasheet numbers is the AOT pipeline's own cost model:
``jitted.lower(*args).compile().cost_analysis()`` returns the analytical
FLOP and byte counts XLA assigned to the compiled executable, and
``memory_analysis()`` the static buffer footprint. :func:`capture` harvests
both for a named kernel at its call site, memoized per input signature
(shapes/dtypes) so steady-state dispatch pays one dict lookup and three
counter bumps.

Every capture books three registry counters labeled ``kernel=<name>`` —
``costmodel.calls`` / ``costmodel.flops`` / ``costmodel.bytes`` — so a
fit/transform capture window (a registry snapshot delta) can roll up the
analytical work it dispatched *even when the kernels ran in localspark
worker processes*: the counters ride the existing worker telemetry trailer;
the in-process ``_KERNELS`` table (richer: memory_analysis fields) augments
them when the kernel compiled in this process.

:func:`window_summary` turns a delta into the ``cost_model`` dict stamped
into FitReport v3 / TransformReport: per-kernel calls + per-call analytical
cost, window totals, and a roofline utilization estimate
``analytical_flops / (wall_seconds × peak_flops)`` with the peak looked up
by ``device_kind`` (``TPU_ML_PEAK_TFLOPS`` overrides); a device the table
does not know — the CPU included — gets no roofline figure.

Analysis is strictly best-effort: any lowering/compile failure is cached as
a no-op for that signature and never raises into the fit/transform path.
"""

from __future__ import annotations

import logging
import os
import threading

from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.utils import knobs

logger = logging.getLogger("spark_rapids_ml_tpu")

# Published bf16 peak per chip in TFLOP/s, keyed by jax ``device_kind``.
# v5e: Google Cloud documentation, "TPU v5e".
PEAK_TFLOPS_BY_DEVICE_KIND = {"TPU v5 lite": 197.0}

_LOCK = threading.Lock()
_KERNELS: dict[str, dict] = {}  # kernel name -> analytical entry (per call)
_ANALYZED: set = set()  # (kernel, signature) already analyzed OK
_FAILED: set = set()  # (kernel, signature) that failed to lower/compile

_MEMORY_FIELDS = (
    ("argument_bytes", "argument_size_in_bytes"),
    ("output_bytes", "output_size_in_bytes"),
    ("temp_bytes", "temp_size_in_bytes"),
)


def peak_flops() -> float | None:
    """Device peak FLOP/s for the roofline denominator: the explicit
    ``TPU_ML_PEAK_TFLOPS``, else the table entry for this process's
    ``device_kind``, else ``None`` (no roofline figure)."""
    raw = os.environ.get(knobs.PEAK_TFLOPS.name, "")
    if raw:
        try:
            return float(raw) * 1e12
        except ValueError:
            raise ValueError(
                f"{knobs.PEAK_TFLOPS.name}={raw!r} is not a number of TFLOP/s"
            ) from None
    import jax

    tflops = PEAK_TFLOPS_BY_DEVICE_KIND.get(jax.devices()[0].device_kind)
    return None if tflops is None else tflops * 1e12


def _sig(a) -> str:
    """Shape/dtype signature of one argument (abstract, never reads data)."""
    shape = getattr(a, "shape", None)
    if shape is not None:
        return f"{getattr(a, 'dtype', '?')}{tuple(shape)}"
    if isinstance(a, (tuple, list)):
        return "(" + ",".join(_sig(x) for x in a) + ")"
    return repr(a)[:48]


def _analyze(kernel: str, jitted_fn, args, kwargs) -> dict | None:
    """AOT-lower+compile the kernel and read XLA's analytical numbers."""
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis() or {}
        entry = {
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
        }
        try:
            mem = compiled.memory_analysis()
        except Exception:  # noqa: BLE001 — optional per backend
            mem = None
        if mem is not None:
            for field, attr in _MEMORY_FIELDS:
                v = getattr(mem, attr, None)
                if v is not None:
                    entry[field] = int(v)
        return entry
    except Exception:  # noqa: BLE001 — analysis must never break dispatch
        logger.debug("cost analysis failed for kernel %s", kernel,
                     exc_info=True)
        return None


def capture(kernel: str, jitted_fn, *args, **kwargs) -> dict | None:
    """Record one dispatch of ``kernel`` against the analytical cost model.

    Call at the kernel's dispatch site with the jitted callable and the
    exact arguments about to be passed (donated buffers are safe — lowering
    is abstract and does not consume them). Returns the per-call analytical
    entry, or ``None`` when the callable is not AOT-lowerable (e.g. a plain
    Python wrapper) — in which case the window simply has no cost model.
    """
    try:
        key = (kernel, tuple(_sig(a) for a in args),
               tuple((k, _sig(v)) for k, v in sorted(kwargs.items())))
    except Exception:  # noqa: BLE001
        return None
    with _LOCK:
        if key in _FAILED:
            return None
        fresh = key not in _ANALYZED
    if fresh:
        entry = _analyze(kernel, jitted_fn, args, kwargs)
        with _LOCK:
            if entry is None:
                _FAILED.add(key)
                return None
            _ANALYZED.add(key)
            # one entry per kernel name: keep the largest signature's
            # numbers as the representative per-call cost
            cur = _KERNELS.get(kernel)
            if cur is None or entry["flops"] >= cur["flops"]:
                _KERNELS[kernel] = dict(entry)
    with _LOCK:
        entry = _KERNELS.get(kernel)
    if entry is None:  # another signature of this kernel failed earlier
        return None
    REGISTRY.counter_inc("costmodel.calls", 1, kernel=kernel)
    if entry["flops"]:
        REGISTRY.counter_inc("costmodel.flops", entry["flops"], kernel=kernel)
    if entry["bytes_accessed"]:
        REGISTRY.counter_inc(
            "costmodel.bytes", entry["bytes_accessed"], kernel=kernel
        )
    return entry


def kernel_costs() -> dict[str, dict]:
    """Copy of the in-process analytical table (kernel -> per-call entry)."""
    with _LOCK:
        return {k: dict(v) for k, v in _KERNELS.items()}


def reset() -> None:
    """Drop all cached analyses (tests)."""
    with _LOCK:
        _KERNELS.clear()
        _ANALYZED.clear()
        _FAILED.clear()


def window_summary(delta, wall_seconds: float) -> dict:
    """Cost-model rollup of one capture window (a RegistrySnapshot delta).

    Counter-driven so it works across process boundaries: per-kernel call
    counts and analytical totals come from the ``costmodel.*`` counters in
    the delta (worker-side captures arrive via the telemetry trailer); the
    local ``_KERNELS`` table only adds memory_analysis detail when
    available. Returns ``{}`` when the window dispatched no captured
    kernels.
    """
    calls: dict[str, float] = {}
    flops: dict[str, float] = {}
    nbytes: dict[str, float] = {}
    by_name = {
        "costmodel.calls": calls,
        "costmodel.flops": flops,
        "costmodel.bytes": nbytes,
    }
    for (name, labels), v in delta.counters.items():
        dest = by_name.get(name)
        if dest is None:
            continue
        kernel = dict(labels).get("kernel", "")
        if kernel:
            dest[kernel] = dest.get(kernel, 0.0) + v
    if not calls:
        return {}
    local = kernel_costs()
    kernels: dict[str, dict] = {}
    for kernel, n in sorted(calls.items()):
        n = max(n, 1.0)
        entry = {
            "calls": int(n),
            "flops": flops.get(kernel, 0.0) / n,
            "bytes_accessed": nbytes.get(kernel, 0.0) / n,
        }
        for field, _ in _MEMORY_FIELDS:
            v = local.get(kernel, {}).get(field)
            if v is not None:
                entry[field] = v
        kernels[kernel] = entry
    total_flops = sum(flops.values())
    total_bytes = sum(nbytes.values())
    out = {
        "kernels": kernels,
        "analytical_flops": total_flops,
        "analytical_bytes": total_bytes,
    }
    peak = peak_flops()
    if peak:
        out["peak_flops"] = peak
    if wall_seconds > 0 and total_flops > 0:
        achieved = total_flops / wall_seconds
        out["achieved_flop_s"] = achieved
        if peak:
            out["roofline_utilization"] = achieved / peak
    return out
