"""Operations and bytes that a KMeans fit needs, from the configuration's
true rows alone: the algorithm's count, whatever implements it, so a later PR
cannot move it. What an implementation adds (a one-hot product for the sums,
rows of padding, passes for precision) is not counted."""

from __future__ import annotations

from benchmarks import sources


def lloyd_iteration(rows: float, k: float, n: float) -> dict[str, float]:
    """One Lloyd iteration: the distance of every row to every centre
    (2·rows·k·n FLOP), the rows read once."""
    return {"flops": 2.0 * rows * k * n, "bytes": 4.0 * rows * n}


def lloyd_fit(rows: float, k: float, n: float, max_iter: float) -> dict[str, float]:
    """The ``max_iter`` iterations of one fit. The seeding (a few passes of
    the same kind over 2·initSteps·k candidates) is left out, so a share of a
    whole fit reads a little low rather than high."""
    one = lloyd_iteration(rows, k, n)
    return {key: max_iter * value for key, value in one.items()}


def work(spec: dict, config: dict) -> dict[str, float]:
    """The operations and bytes of one unit of the work ``spec`` names, as
    ``sources.work`` finds them for ``opcount``."""
    args = {name: sources.lookup(config, path) for name, path in spec["args"].items()}
    return globals()[spec["work"]](**args)
