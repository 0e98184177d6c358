"""The plain reference for a KMeans fit: Lloyd's iterations in float64 NumPy
from given initial centres, and the numbers that decide ``correct``. Imports
nothing of the program.

``lloyd`` follows Spark ML's KMeans as the program states it: every row goes
to its nearest centre by squared Euclidean distance (the lowest index on a
tie), a centre moves to the weighted mean of its rows, and a centre that no
row chose stays where it was. Departures from Spark ML's description, each of
them the program's own and kept here so that the two compute one thing:

- ``maxIter`` iterations are run whatever the centres do (the configuration
  states ``tol`` 0), but for an exact fixed point: once no centre moves at
  all the loop ends, as the program's ``shift > tol²`` does at ``tol`` 0.
  ``last_shift`` says whether that happened;
- the cost returned is the one the program calls ``trainingCost``: the sum of
  squared distances to the centres that *entered* the last iteration, not to
  the centres returned (Spark ML's summary evaluates the final ones);
- the seeding is not redone here. Its random draws are the program's, so the
  reference starts from the centres the program started from, and
  ``seeding`` holds them by what float64 can check without the draws.

The nearest centre is found as the largest x·c − ‖c‖²/2, which orders the
centres as ‖x − c‖² does and needs no [rows, k] temporary beyond the product;
the distance itself is ‖x‖² − 2·(that). A block that stands in the rows
several times is assigned once and weighed by its multiplicity: the same sums.
"""

from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.reference import BROKEN, split_bf16  # NumPy alone, as this file

BLOCK_ROWS = 4096
THREADS = os.cpu_count() or 1
COMPARED = ("cost_gap", "center_gap_med", "center_gap")


def _assign(x: np.ndarray, centres: np.ndarray, passes: int | None):
    """(label, squared distance) of each row of one block. ``passes`` = 1 is
    the control: the cross term as the chip takes it at ``Precision.DEFAULT``
    (one bfloat16 pass, a₁b₁), the norms left whole as the program leaves
    them."""
    half = 0.5 * np.sum(centres * centres, axis=1)
    if passes is None:
        score = x @ centres.T
    elif passes != 1:
        raise ValueError(f"passes={passes!r}: the control is one bfloat16 pass")
    else:
        score = (split_bf16(x, 1)[0].astype(np.float64)
                 @ split_bf16(centres, 1)[0].astype(np.float64).T)
    score -= half
    label = np.argmax(score, axis=1)
    best = score[np.arange(len(x)), label]
    d2 = np.clip(np.sum(x * x, axis=1) - 2.0 * best, 0.0, None)
    return label, d2


def _one_blas_thread():
    """The blocks of rows are spread over threads here, so each product runs
    on its caller's thread: a BLAS that spreads every product over its own
    pool lets one product in at a time, which on the chip's host tripled a
    pass's seconds. Without ``threadpoolctl`` the BLAS is left as it is."""
    try:
        from threadpoolctl import threadpool_limits
    except ImportError:
        return contextlib.nullcontext()
    return threadpool_limits(limits=1, user_api="blas")


def _pass(blocks: list, order: list[int], centres: np.ndarray, passes=None):
    """One pass over the rows: (sums [k, n], counts [k], cost)."""
    k, n = centres.shape
    jobs = [
        (order.count(kind), np.asarray(blocks[kind], dtype=np.float64)[lo : lo + BLOCK_ROWS])
        for kind in sorted(set(order))
        for lo in range(0, len(blocks[kind]), BLOCK_ROWS)
    ]

    def one(job):
        times, x = job
        label, d2 = _assign(x, centres, passes)
        by_label = np.argsort(label, kind="stable")
        counts = np.bincount(label, minlength=k)
        sums = np.zeros((k, n))
        filled = counts > 0
        starts = (np.cumsum(counts) - counts)[filled]
        sums[filled] = np.add.reduceat(x[by_label], starts, axis=0)
        return times * sums, times * counts.astype(np.float64), times * float(d2.sum())

    with _one_blas_thread(), ThreadPoolExecutor(max_workers=THREADS) as pool:
        parts = list(pool.map(one, jobs))
    return (
        np.sum([p[0] for p in parts], axis=0),
        np.sum([p[1] for p in parts], axis=0),
        float(np.sum([p[2] for p in parts])),
    )


def cost(blocks: list, order: list[int], centres) -> float:
    """Sum over the rows of the squared distance to the nearest centre."""
    return _pass(blocks, order, np.asarray(centres, dtype=np.float64))[2]


def lloyd(blocks: list, order: list[int], centres0, max_iter: int, passes=None) -> dict:
    """``max_iter`` Lloyd iterations from ``centres0``. Returns the centres,
    the cost as the program defines ``trainingCost``, the cost at ``centres0``
    (the first iteration's), the iterations run and the last iteration's
    largest squared movement of a centre."""
    centres = np.asarray(centres0, dtype=np.float64).copy()
    first_cost = last_cost = float("inf")
    done, shift = 0, float("inf")
    while done < max_iter and shift > 0.0:
        sums, counts, last_cost = _pass(blocks, order, centres, passes)
        if not done:
            first_cost = last_cost
        filled = counts > 0
        moved = centres.copy()
        moved[filled] = sums[filled] / counts[filled, None]
        shift = float(np.max(np.sum((moved - centres) ** 2, axis=1)))
        centres, done = moved, done + 1
    return {"centres": centres, "cost": last_cost, "first_cost": first_cost,
            "iterations": done, "last_shift": shift}


def compare(centres, cost_fit, ref: dict) -> dict[str, float]:
    """The numbers compared, for one fitted model against the reference.

    ``cost_gap``: the relative gap of ``trainingCost``. ``center_gap`` and
    ``center_gap_med``: the largest and the median, over the k centres index
    for index, of the distance between the fitted centre and the reference's
    over the root mean square norm of the reference's centres. A model of the
    wrong shape, or with a number that is not finite, reads ``BROKEN``."""
    bad = dict.fromkeys(COMPARED, BROKEN)
    centres = np.asarray(centres, dtype=np.float64)
    want = ref["centres"]
    if centres.shape != want.shape or not np.all(np.isfinite(centres)):
        return bad
    if not np.isfinite(cost_fit):
        return bad
    scale = float(np.sqrt(np.mean(np.sum(want * want, axis=1))))
    gaps = np.linalg.norm(centres - want, axis=1) / scale
    return {
        "cost_gap": abs(float(cost_fit) - ref["cost"]) / ref["cost"],
        "center_gap_med": float(np.median(gaps)),
        "center_gap": float(gaps.max()),
    }


def seeding(blocks: list, order: list[int], centres0, seed: int, cost0=None) -> dict[str, float]:
    """What holds the initial centres without the program's random draws.

    ``seed_rows_off``: how many of them are not, bit for bit, a row of the
    data in the dtype the centres came in, or are the same row as an earlier
    one. ``seed_cost_ratio``: the cost at them over the cost at as many rows
    drawn uniformly without replacement by NumPy from ``seed``: a k-means‖
    seeding reads well under 1, rows taken blindly read about 1. ``cost0`` is
    the cost at ``centres0`` where a Lloyd pass from them has computed it."""
    centres0 = np.ascontiguousarray(centres0)
    k = len(centres0)
    kinds = sorted(set(order))
    row_bytes = centres0.dtype.itemsize * centres0.shape[1]
    seen = set()
    for kind in kinds:
        as_put = np.ascontiguousarray(blocks[kind], dtype=centres0.dtype)
        seen.update(as_put.view(f"V{row_bytes}").ravel().tolist())
    mine = centres0.view(f"V{row_bytes}").ravel().tolist()
    off = sum(1 for row in mine if row not in seen) + (len(mine) - len(set(mine)))
    sizes = [len(blocks[kind]) for kind in kinds]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x5EED]))
    picks = np.sort(rng.choice(sum(sizes), size=k, replace=False))
    edges = np.cumsum([0] + sizes)
    blind = np.concatenate([
        np.asarray(blocks[kind], dtype=np.float64)[picks[(picks >= lo) & (picks < hi)] - lo]
        for kind, lo, hi in zip(kinds, edges[:-1], edges[1:])
    ])
    return {
        "seed_rows_off": float(off),
        "seed_cost_ratio": (cost(blocks, order, centres0) if cost0 is None else cost0)
        / cost(blocks, order, blind),
    }
