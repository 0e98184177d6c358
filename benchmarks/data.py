"""Rows from a seed, and the Arrow table a fit reads them from.

A fit's rows are ``len(order)`` blocks in a row; ``order[i]`` names which of a
few distinct seeded blocks stands at place ``i``. The table refers to each
distinct block's buffer as often as it is repeated and copies nothing, so
set-up makes a few hundred MiB of random numbers and the fit still streams
every byte of every row through decode, staging and H2D. The distinct blocks
together are far larger than the host's caches.

All blocks share one dense eigenbasis and differ in how strongly each
direction is excited, so that the whole has the decaying spectrum of
``chip_smoke.make_rows`` (leading k + 14 directions fall off by 5% each, the
rest sit a hundred times lower) while any part of it has another: a fit that
loses a chunk, or half of each, returns other components.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

COLUMN = "features"


def block_order(blocks: int, kinds: int) -> list[int]:
    """Equal runs of each kind, in turn: 0…0 1…1 2…2 3…3."""
    return [(i * kinds) // blocks for i in range(blocks)]


def make_blocks(seed: int, n: int, k: int, block_rows: int, kinds: int) -> list:
    """``kinds`` float64 blocks of ``[block_rows, n]``, each from its own
    stream of the seed, made side by side."""
    streams = np.random.SeedSequence(seed).spawn(kinds + 1)
    rng = np.random.default_rng(streams[0])
    lead = min(n, k + 14)
    scale = np.full(n, 0.95 ** lead / 100.0)
    scale[:lead] = 0.95 ** np.arange(lead)
    share = rng.uniform(0.5, 1.5, size=(kinds, n))
    weight = np.sqrt(kinds * share / share.sum(axis=0))  # Σ_kinds weight² = kinds
    v = rng.standard_normal(n)
    v /= np.linalg.norm(v)

    def one(kind: int):
        x = np.random.default_rng(streams[kind + 1]).standard_normal((block_rows, n))
        x *= scale * weight[kind]
        for lo in range(0, block_rows, 8192):  # Householder, no [rows, n] temporary
            rows = x[lo : lo + 8192]
            rows -= np.outer(rows @ (2.0 * v), v)
        return x

    with ThreadPoolExecutor(max_workers=kinds) as pool:
        return list(pool.map(one, range(kinds)))


def to_table(blocks: list, order: list[int]):
    """One ``array<double>`` column whose chunks are the blocks in ``order``."""
    import pyarrow as pa

    columns = {}
    for kind in set(order):
        x = blocks[kind]
        offsets = pa.array(np.arange(0, x.size + 1, x.shape[1], dtype=np.int32))
        columns[kind] = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
    chunked = pa.chunked_array([columns[kind] for kind in order])
    return pa.Table.from_arrays([chunked], names=[COLUMN])
