"""Operations and bytes that a binary logistic-regression fit by Newton's
method needs, from the configuration's true rows alone: the algorithm's
count, whatever implements it, so a later PR cannot move it. What an
implementation adds (rows of padding, lanes of padding, passes for precision,
the d³/3 of the solve, which is a thousandth of the Hessian here) is not
counted."""

from __future__ import annotations

from benchmarks import sources


def newton_iteration(rows: float, n: float, fit_intercept: float) -> dict[str, float]:
    """One Newton iteration over ``rows`` rows of d = n (+1) columns: XᵀWX
    (2·rows·d² FLOP), the margins x·w and the gradient Xᵀr (2·rows·d each),
    the rows read twice (once for the margins, once for the two products
    that need the margins)."""
    d = n + (1.0 if fit_intercept else 0.0)
    return {"flops": 2.0 * rows * d * d + 4.0 * rows * d, "bytes": 2.0 * 4.0 * rows * d}


def newton_fit(rows: float, n: float, fit_intercept: float, max_iter: float) -> dict[str, float]:
    """The ``max_iter`` iterations of one fit."""
    one = newton_iteration(rows, n, fit_intercept)
    return {key: max_iter * value for key, value in one.items()}


def work(spec: dict, config: dict) -> dict[str, float]:
    """The operations and bytes of one unit of the work ``spec`` names, as
    ``sources.work`` finds them for ``opcount``."""
    args = {name: sources.lookup(config, path) for name, path in spec["args"].items()}
    return globals()[spec["work"]](**args)
