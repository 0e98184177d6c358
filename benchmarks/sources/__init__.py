"""Readers of per-layer metrics, one module to a kind of source. Each has
``read(spec, ctx) -> float | None``: ``spec`` is the ``reader`` of the
metric's file under ``layer_metrics/``, ``ctx`` the :class:`Readings` of the
traced run. A reader that finds nothing to read returns None."""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any


@dataclass
class Readings:
    """What a traced window left behind for the readers."""

    config: dict            # the configuration's file
    chips: int
    peak: dict              # this device kind's row of peaks.json
    elapsed_s: float        # window start to the last completion
    completed: int          # operations (fits, requests) completed in it
    registry: Any           # the program's registry delta over the window
    trace: dict | None      # trace_reduce.reduce() of the traced part


def lookup(config: dict, path: str):
    """``"env.TPU_ML_STREAM_CHUNK_ROWS"`` → ``config["env"][...]`` as a number."""
    value: Any = config
    for key in path.split("."):
        value = value[key]
    return float(value)


def work(spec: dict, config: dict) -> dict[str, float]:
    """The operations and bytes of one unit of the work ``spec`` names."""
    from benchmarks import opcount

    args = {name: lookup(config, path) for name, path in spec["args"].items()}
    return getattr(opcount, spec["work"])(**args)
