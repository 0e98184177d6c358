"""From a profiler trace (``.xplane.pb``) to what the metrics need: the time
in which an operation ran on each device, the device time of each program,
the operations that took most of it, and the idle time by what the host was
doing meanwhile. Read with ``jax.profiler.ProfileData`` and nothing else.

What the planes of a TPU trace hold (looked at by hand, PERF.md section 6):
``/device:TPU:<i>`` has a line ``XLA Modules`` with one event for each run of
a compiled program (``jit__fold(<fingerprint>)``) and a line ``XLA Ops`` with
one for each operation inside it, named by its whole HLO text (a ``while`` and
the operations of its body overlap, so busy time is the union, not the sum);
``/host:CPU`` has a line for each thread, named after the thread (the main
one after the command: ``python``, ``python3``); there the program's spans
(``jax.profiler.TraceAnnotation``) stand under their own names among JAX's own
(``PjitFunction(..)``, ``shard_args``), and the runtime's threads show what
it does for a transfer (``Transpose::ExecuteChunk``, ``MapDmaBuffer``). All
planes share one clock.
"""

from __future__ import annotations

import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
MIN_HOST_SPAN_NS = 1e6  # a shorter host event holds no idle time worth a row


def _merge(starts: np.ndarray, ends: np.ndarray):
    """Union of intervals, as sorted disjoint (starts, ends)."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    starts, ends = starts[order], np.maximum.accumulate(ends[order])
    first = np.ones(len(starts), dtype=bool)
    first[1:] = starts[1:] > ends[:-1]
    return starts[first], np.append(ends[:-1][first[1:]], ends[-1])


def _covered(starts, ends, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the disjoint sorted intervals cover."""
    if hi <= lo or not len(starts):
        return 0.0
    return float(np.sum(np.clip(np.minimum(ends, hi) - np.maximum(starts, lo), 0, None)))


def _events(line):
    names, starts, durations = [], [], []
    for event in line.events:
        names.append(event.name)
        starts.append(event.start_ns)
        durations.append(event.duration_ns)
    return names, np.asarray(starts, dtype=np.float64), np.asarray(durations, dtype=np.float64)


def _program(name: str) -> str:
    return name.split("(", 1)[0]


def _op(name: str) -> str:
    """``%fusion.4 = f32[..] fusion(..)`` → ``fusion.4``."""
    return name.split(" = ", 1)[0].lstrip("%")


def reduce(path: str, span_names=None) -> dict | None:
    """Reduce one ``.xplane.pb``. Idle time is booked to the shortest host
    event of a millisecond or more, on any thread, that is open meanwhile
    (``span_names`` narrows them to the program's own spans). Returns None
    where the trace holds no device plane."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(path)
    devices, host_spans = [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE in lines:
                devices.append((plane.name, lines))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                names, starts, durations = _events(line)
                for name, start, duration in zip(names, starts, durations):
                    if duration < MIN_HOST_SPAN_NS:
                        continue
                    if span_names is not None and name not in span_names:
                        continue
                    host_spans.append((name, start, start + duration))
    if not devices:
        return None

    lo, hi = np.inf, -np.inf
    busy, ops, programs = [], {}, {}
    for _, lines in devices:
        names, starts, durations = _events(lines[OPS_LINE])
        if len(starts):
            lo, hi = min(lo, starts.min()), max(hi, (starts + durations).max())
        busy.append(_merge(starts, starts + durations))
        for name, duration in zip(names, durations):
            ops[_op(name)] = ops.get(_op(name), 0.0) + duration
        if MODULES_LINE in lines:
            names, starts, durations = _events(lines[MODULES_LINE])
            for name, duration in zip(names, durations):
                entry = programs.setdefault(_program(name), {"count": 0, "seconds": 0.0})
                entry["count"] += 1
                entry["seconds"] += float(duration) / 1e9
    for _, start, end in host_spans:
        lo, hi = min(lo, start), max(hi, end)
    if not np.isfinite(lo):
        return None
    ndev = len(devices)
    busy_s = sum(float(np.sum(e - s)) for s, e in busy) / ndev / 1e9
    for entry in programs.values():  # a program on every device: the mean of them
        entry["count"] /= ndev
        entry["seconds"] /= ndev

    # idle time by the innermost host span open meanwhile, on the first device
    starts, ends = busy[0]
    cuts = sorted({lo, hi, *[s for _, s, _ in host_spans], *[e for _, _, e in host_spans]})
    idle: dict[str, float] = {}
    for a, b in zip(cuts[:-1], cuts[1:]):
        mid = (a + b) / 2
        open_now = [(e - s, name) for name, s, e in host_spans if s <= mid < e]
        name = min(open_now)[1] if open_now else "(no span open)"
        gap = (b - a) - _covered(starts, ends, a, b)
        if gap > 0:
            idle[name] = idle.get(name, 0.0) + gap / 1e9
    return {
        "window_s": float(hi - lo) / 1e9,
        "busy_s": float(busy_s),
        "devices": ndev,
        "programs": programs,
        "device_ops": [
            [name, float(ns) / ndev / 1e9]
            for name, ns in sorted(ops.items(), key=lambda kv: -kv[1])
        ],
        "idle_gaps": [
            [name, float(s)] for name, s in sorted(idle.items(), key=lambda kv: -kv[1])
        ],
    }


def reduce_dir(directory, span_names=None) -> dict | None:
    """The newest trace under a ``jax.profiler.start_trace`` directory."""
    paths = sorted(glob.glob(os.path.join(str(directory), "plugins/profile/*/*.xplane.pb")))
    return reduce(paths[-1], span_names) if paths else None
