"""Flight recorder: a bounded ring buffer of raw span/instant events.

The registry (:mod:`.registry`) answers *how long* — per-phase latency
percentiles. It cannot answer *when*: whether chunk i+1's H2D staging
actually ran while chunk i's fold executed, whether a retry struck before
or after a checkpoint, which partition straggled. This module records the
raw events those questions need — the NVTX-timeline analog of the
reference's ``NvtxRange("compute cov", RED)`` ranges, but exportable
without an attached profiler session: the buffer serializes to Chrome
trace-event JSON that loads directly in Perfetto or ``chrome://tracing``.

Design constraints:

- **Bounded** — a multi-hour streamed fit emits an event per chunk; the
  recorder must never become the memory leak it is meant to diagnose. The
  buffer is a ``deque(maxlen=capacity)`` (``TPU_ML_TIMELINE_EVENTS``,
  default 4096): old events fall off, aggregate truth stays in the
  registry.
- **Thread-safe, cheap** — events are recorded from the ingest thread,
  the localspark task threads and worker processes concurrently; one lock
  around a deque append is far below the cost of anything being timed.
- **Cross-process alignable** — timestamps are ``time.perf_counter()``,
  which on Linux is CLOCK_MONOTONIC: a *system-wide* clock, so driver and
  localspark-worker events recorded in different processes share an epoch
  and interleave correctly on one Perfetto track set. Events carry their
  recording ``pid`` so each process renders as its own track group.
- **jax-free** — worker ingestion processes import this without pulling
  in jax (same constraint as :mod:`.registry`).

Events are wire-ready plain dicts (a subset of the Chrome trace-event
format plus a ``seq`` bookkeeping field stripped at export):

    {"name", "ph": "X"|"i", "ts": µs, "dur": µs (X only),
     "pid", "tid", "args": {labels...}, "seq"}

``seq`` is a monotone per-recorder counter: ``events(since_seq=...)``
extracts "everything since the snapshot" — how a worker ships only the
events of the task that just ran, and how a fit exports only its own
window.
"""

from __future__ import annotations

import collections
import os
import threading
import time

from spark_rapids_ml_tpu.utils import knobs

TIMELINE_CAPACITY_VAR = knobs.TIMELINE_EVENTS.name
DEFAULT_TIMELINE_CAPACITY = 4096


def timeline_capacity() -> int:
    """Ring capacity from ``TPU_ML_TIMELINE_EVENTS`` (0 disables)."""
    raw = os.environ.get(TIMELINE_CAPACITY_VAR, str(DEFAULT_TIMELINE_CAPACITY))
    try:
        cap = int(raw)
    except ValueError:
        raise ValueError(
            f"{TIMELINE_CAPACITY_VAR}={raw!r} is not an integer"
        ) from None
    if cap < 0:
        raise ValueError(f"{TIMELINE_CAPACITY_VAR}={cap} must be >= 0")
    return cap


# Who records: the process and the thread, asked of the kernel once each and
# not once an event. Both are system calls, and under a sandboxed kernel
# (gVisor, where the chip's hosts run) ``os.getpid()`` took 0.18-0.26 ms and
# ``threading.get_native_id()`` 0.03 ms: 275 events a streamed PCA fit made
# 60-80 ms of it, more than half of what ``compute cov`` could not name
# (PERF.md section 6, PR 37). A forked child asks again.
_pid = os.getpid()
_thread = threading.local()


def _forget_who() -> None:
    global _pid, _thread
    _pid, _thread = os.getpid(), threading.local()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_forget_who)


def _tid() -> int:
    try:
        return _thread.native_id
    except AttributeError:
        _thread.native_id = threading.get_native_id()
        return _thread.native_id


def _now_us() -> int:
    # CLOCK_MONOTONIC microseconds — the same clock trace_range spans use,
    # so span and instant timestamps interleave exactly
    return int(time.perf_counter() * 1e6)


class Timeline:
    """One process's bounded event recorder."""

    def __init__(self, capacity: int | None = None):
        self._capacity = timeline_capacity() if capacity is None else capacity
        self._lock = threading.Lock()
        self._events: collections.deque = collections.deque(
            maxlen=self._capacity or None
        )
        self._seq = 0
        self._enabled = self._capacity > 0

    @property
    def capacity(self) -> int:
        return self._capacity

    def seq(self) -> int:
        """Current sequence watermark — pair with ``events(since_seq=)``."""
        with self._lock:
            return self._seq

    def _append(self, event: dict) -> None:
        with self._lock:
            self._seq += 1
            event["seq"] = self._seq
            self._events.append(event)

    def record_span(
        self, name: str, t0_s: float, t1_s: float, **labels
    ) -> None:
        """One completed span: ``t0_s``/``t1_s`` are ``time.perf_counter()``
        readings (what ``trace_range`` already holds when it closes)."""
        if not self._enabled:
            return
        self._append(
            {
                "name": name,
                "ph": "X",
                "ts": int(t0_s * 1e6),
                "dur": max(0, int((t1_s - t0_s) * 1e6)),
                "pid": _pid,
                "tid": _tid(),
                "cat": "span",
                "args": {k: v for k, v in labels.items() if v},
            }
        )

    def record_instant(self, name: str, **labels) -> None:
        """A point event — retries, bisections, checkpoints, faults."""
        if not self._enabled:
            return
        self._append(
            {
                "name": name,
                "ph": "i",
                "ts": _now_us(),
                "pid": _pid,
                "tid": _tid(),
                "cat": "instant",
                "s": "t",  # thread-scoped instant (Perfetto render hint)
                "args": {k: v for k, v in labels.items() if v},
            }
        )

    def events(self, since_seq: int = 0) -> list[dict]:
        """Copied events with ``seq > since_seq``, in record order. Events
        that fell off the ring are gone — by design."""
        with self._lock:
            return [
                dict(e, args=dict(e["args"]))
                for e in self._events
                if e["seq"] > since_seq
            ]

    def merge(self, events: list[dict], **labels) -> None:
        """Adopt foreign events (a worker's trailer) into this recorder.

        The foreign ``pid``/``tid``/``ts`` are preserved — the system-wide
        monotonic clock makes them directly comparable — and ``labels``
        (e.g. ``partition="3"``) are stamped into each event's args so the
        driver-side timeline attributes them. Malformed entries are
        dropped rather than poisoning the buffer.
        """
        if not self._enabled:
            return
        extra = {k: v for k, v in labels.items() if v}
        for e in events:
            if not isinstance(e, dict) or "name" not in e or "ts" not in e:
                continue
            merged = dict(e)
            merged["args"] = {**(e.get("args") or {}), **extra}
            self._append(merged)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._events)


def chrome_trace(events: list[dict]) -> dict:
    """Events → a Chrome trace-event JSON object (Perfetto-loadable).

    Adds ``M``-phase process_name metadata per pid (driver vs workers read
    as named track groups) and strips the internal ``seq`` field.
    """
    pids = []
    out = []
    for e in events:
        e = {k: v for k, v in e.items() if k != "seq"}
        pid = e.get("pid", 0)
        if pid not in pids:
            pids.append(pid)
        out.append(e)
    meta = []
    for pid in pids:
        # a partition label on any of the pid's events names the track
        part = next(
            (
                e["args"]["partition"]
                for e in out
                if e.get("pid") == pid and (e.get("args") or {}).get("partition")
            ),
            None,
        )
        name = (
            f"worker partition {part}"
            if part is not None
            else f"driver (pid {pid})" if pid == os.getpid() else f"pid {pid}"
        )
        meta.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "tid": 0,
                "args": {"name": name},
            }
        )
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


# The ONE process-wide recorder, fed by spans.trace_range and the
# choke-point instant sites; tests construct private Timeline instances.
TIMELINE = Timeline()

record_instant = TIMELINE.record_instant
