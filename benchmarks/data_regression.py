"""Rows and labels for a regression fit, from a seed: ``kinds`` distinct
float64 blocks of features with a continuous label to each row, to stand in
a two-column Arrow table as ``data_logreg.to_table`` lays blocks out (each
kind's buffers referred to as often as ``order`` repeats it, nothing copied).

The form of scikit-learn's ``make_regression``, which spark-rapids-ml's
``gen_data.py regression`` wraps: features independent N(0, 1); a planted
coefficient vector that is nonzero on ``n_informative`` of the features
(which ones drawn from the seed, as ``make_regression`` shuffles
its columns), each such coefficient 100 times a U(0, 1) draw; the label
``x·coef + bias``, plus N(0, ``noise``²) where ``noise`` is not 0. Every kind
draws from the same model, so any part of the rows is a thinner sample of it.

How this departs from ``make_regression``: its informative features are
drawn from a low-rank covariance where ``effective_rank`` is set (not set
here: independent columns, its default), and it draws every row from one
stream; here each kind of block draws from its own stream of the seed, so
the kinds are made side by side. The widths and the label's type are the
source's.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.data_logreg import FEATURES, LABEL, to_table  # noqa: F401  (the table's layout)


def planted(seed: int, n: int, n_informative: int) -> np.ndarray:
    """The coefficients the labels are drawn from: [n], nonzero on
    ``n_informative`` features, each 100 · U(0, 1)."""
    rng = np.random.default_rng(np.random.SeedSequence(seed).spawn(1)[0])
    coef = np.zeros(n)
    which = rng.choice(n, int(n_informative), replace=False)
    coef[which] = 100.0 * rng.random(len(which))
    return coef


def make_blocks(
    seed: int, n: int, block_rows: int, kinds: int, *, n_informative: int,
    bias: float, noise: float,
) -> list:
    """``kinds`` pairs ``(x [block_rows, n], y [block_rows])`` of float64,
    each from its own stream of the seed, made side by side."""
    streams = np.random.SeedSequence(seed).spawn(kinds + 1)  # the first is ``planted``'s
    coef = planted(seed, n, n_informative)

    def one(kind: int):
        rng = np.random.default_rng(streams[kind + 1])
        x = rng.standard_normal((block_rows, n))
        y = x @ coef + bias
        if noise:
            y = y + rng.normal(scale=noise, size=block_rows)
        return x, y

    with ThreadPoolExecutor(max_workers=kinds) as pool:
        return list(pool.map(one, range(kinds)))
