"""Rows for a clustering fit, from a seed: Gaussian blobs in ``kinds``
distinct float64 blocks, to stand in an Arrow table as ``data.to_table`` lays
blocks out (each kind's buffer referred to as often as ``order`` repeats it,
nothing copied).

``blobs`` centres are drawn N(0, ``spread``² I) and every row is its blob's
centre plus N(0, I) noise, so at ``spread`` 1 two centres lie about 16 noise
deviations apart: a seeding that misses a blob pays for it in the cost, which
is what lets the cost at the initial centres hold the seeding. A blob's share
of the rows falls off as 1/(rank + ``flatten``) (the largest some dozens of
times the smallest), so a fit with as many clusters as blobs splits the large
ones among several centres and lets small neighbours share one: inside a
split blob there is no structure to settle on, and Lloyd keeps moving its
centres for far more than 20 iterations. Every kind draws its rows from all
the blobs, so any part of the rows is a thinner sample of the same mixture.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def blob_shares(blobs: int, flatten: float) -> np.ndarray:
    share = 1.0 / (np.arange(blobs) + flatten)
    return share / share.sum()


def make_blocks(
    seed: int, n: int, blobs: int, block_rows: int, kinds: int,
    *, spread: float, flatten: float,
) -> list:
    """``kinds`` float64 blocks of ``[block_rows, n]``, each from its own
    stream of the seed, made side by side."""
    streams = np.random.SeedSequence(seed).spawn(kinds + 1)
    centres = np.random.default_rng(streams[0]).standard_normal((blobs, n)) * spread
    share = blob_shares(blobs, flatten)

    def one(kind: int):
        rng = np.random.default_rng(streams[kind + 1])
        x = rng.standard_normal((block_rows, n))
        x += centres[rng.choice(blobs, size=block_rows, p=share)]
        return x

    with ThreadPoolExecutor(max_workers=kinds) as pool:
        return list(pool.map(one, range(kinds)))
