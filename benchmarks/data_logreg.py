"""Rows and labels for a binary classification fit, from a seed: ``kinds``
distinct float64 blocks of features with a 0/1 label to each row, to stand in
a two-column Arrow table as ``data.to_table`` lays blocks out (each kind's
buffers referred to as often as ``order`` repeats it, nothing copied).

Features are independent N(0, 1). A planted weight vector of norm ``signal``
(its direction drawn from the seed) and a planted ``intercept`` give each row
the probability sigmoid(x·w + b), and the label is a Bernoulli draw of it: the
classes overlap, so they are **not separable** and the regularised optimum is
finite and a few Newton steps from zero. At ``signal`` 2 some 76% of the rows
lie on their own side of the planted plane. Every kind draws from the same
model, so any part of the rows is a thinner sample of it.

How this departs from the source's generator (spark-rapids-ml's
``gen_data.py classification`` wraps scikit-learn's ``make_classification``:
class centres on the vertices of a hypercube in an informative subspace,
redundant features as random combinations of the informative ones, a share of
labels flipped): here every feature is informative and none is redundant, so
the Hessian is well posed without the regulariser, and the labels' noise is
the model's own instead of flips. The widths and the label's type are the
source's.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

FEATURES, LABEL = "features", "label"


def planted(seed: int, n: int, signal: float, intercept: float):
    """The model the labels are drawn from: (w [n], b)."""
    w = np.random.default_rng(np.random.SeedSequence(seed).spawn(1)[0]).standard_normal(n)
    return w * (signal / np.linalg.norm(w)), float(intercept)


def make_blocks(
    seed: int, n: int, block_rows: int, kinds: int, *, signal: float, intercept: float
) -> list:
    """``kinds`` pairs ``(x [block_rows, n], y [block_rows])`` of float64,
    each from its own stream of the seed, made side by side."""
    streams = np.random.SeedSequence(seed).spawn(kinds + 1)  # the first is ``planted``'s
    w, b = planted(seed, n, signal, intercept)

    def one(kind: int):
        rng = np.random.default_rng(streams[kind + 1])
        x = rng.standard_normal((block_rows, n))
        p = 1.0 / (1.0 + np.exp(-(x @ w + b)))
        return x, (rng.random(block_rows) < p).astype(np.float64)

    with ThreadPoolExecutor(max_workers=kinds) as pool:
        return list(pool.map(one, range(kinds)))


def to_table(blocks: list, order: list[int]):
    """An ``array<double>`` column and a ``double`` column whose chunks are
    the blocks in ``order``, cut at the same rows."""
    import pyarrow as pa

    feats, labels = {}, {}
    for kind in set(order):
        x, y = blocks[kind]
        offsets = pa.array(np.arange(0, x.size + 1, x.shape[1], dtype=np.int32))
        feats[kind] = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
        labels[kind] = pa.array(y)
    return pa.Table.from_arrays(
        [pa.chunked_array([feats[kind] for kind in order]),
         pa.chunked_array([labels[kind] for kind in order])],
        names=[FEATURES, LABEL],
    )
