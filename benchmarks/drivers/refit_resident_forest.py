"""Traffic driver ``refit_resident_forest``: one caller that fits a random
forest classifier on a two-column table (an ``array<double>`` of features
and a ``double`` label) whose rows are held resident on the device, waits
for the trees on the host, and fits again (a closed loop of one).

Set-up first resolves everything it will ask of the program for the
reference (its bootstrap counts, node subsets, edge sample, edges and bins):
a program without them fails here, in its first seconds, and not after a
fit. Then it makes the rows and the labels from the seed
(``refit_resident_labeled``'s table), starts a localspark session and runs
one whole warm-up fit. The window calls ``Spark<Estimator>.fit(df)`` back to
back with the same params and seed; nothing starts after ``seconds`` and the
fit in flight finishes.

After the window the program is asked, outside the window and the trace,
for what it drew at random and for its edges and bins, and the float64
reference (``reference_forest``) judges every tree of the window's last fit
and the first tree of each other fit, node by node from the program's own
tree.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks import reference_forest
from benchmarks.drivers import refit_resident_labeled
from benchmarks.drivers.refit_stream import QUIET

# host spans whose seconds are printed for each fit, so that a slow fit says where it was slow
PHASES = ("mesh.ingest", "forest.bin", "forest build", "forest mesh-local fit")
TREES = ("forest.trees", {"path": "mesh-local"})
# the rows the program's binner is handed at a time after the window
BIN_ROWS = 8192


class Driver(refit_resident_labeled.Driver):
    def __init__(self, config: dict, traffic: dict, seed: int, chips: int):
        # not super().__init__: that one reads the Newton params
        self.config, self.traffic, self.seed, self.chips = config, traffic, seed, chips
        self.n = int(config["n_features"])
        self.rows = int(config["rows"])
        self.n_trees = int(config["params"]["numTrees"])
        self.answers: list[dict] = []   # one per fit started in the window
        self.started_at = self.elapsed_s = 0.0
        self.registry = None            # the program's registry delta over the window

    # -- set-up ---------------------------------------------------------------
    def setup(self) -> None:
        self.program = resolve_program()
        super().setup()

    # -- the window -----------------------------------------------------------
    def window(self, seconds: float) -> None:
        """Fits back to back; none starts after ``seconds``."""
        registry = self.telemetry.REGISTRY
        before = registry.snapshot()
        self.started_at = time.perf_counter()
        done = self.started_at
        while time.perf_counter() - self.started_at < seconds:
            snap = registry.snapshot()
            answer = {"trees": None, "thresholds": None, "error": None}
            self.answers.append(answer)
            t0 = time.perf_counter()
            try:
                model = self.estimator().fit(self.df)
                answer["trees"] = {
                    name: np.asarray(getattr(model.trees, name))
                    for name in ("feature", "split_bin", "is_leaf", "leaf_stats")
                }
                answer["thresholds"] = np.asarray(model.thresholds)
            except Exception as e:  # a failed fit is counted, and the loop goes on
                answer["error"] = f"{type(e).__name__}: {e}"
            done = time.perf_counter()
            answer["seconds"] = done - t0
            moved = registry.snapshot().delta(snap)
            answer["phases"] = {
                phase: round(moved.hist("span.seconds", phase=phase).total, 2)
                for phase in PHASES
            }
            noisy = {name: moved.counter(name) for name in QUIET if moved.counter(name)}
            if noisy and not answer["error"]:
                answer["error"] = f"the fit degraded or retried: {noisy}"
        self.elapsed_s = done - self.started_at
        self.registry = registry.snapshot().delta(before)

    # -- after the window -----------------------------------------------------
    def handed(self) -> dict:
        """What the program drew and made for this table, asked of it after
        the window: each tree's bootstrap counts over the table's rows, the
        edge sample's row ids, the edges it makes from them, and its bins of
        the distinct rows."""
        return program_inputs(self.program, self.blocks, self.order, self.config)

    def check(self) -> dict[str, dict]:
        """The trees of the window against the reference, each number beside
        its limit. The reference is run here, after the window."""
        limits = self.config["limits"]
        worst = dict.fromkeys(reference_forest.COMPARED, 0.0)
        fits = [a for a in self.answers if not a["error"]]
        if fits:
            t0 = time.perf_counter()
            given = self.handed()
            judged = judge_fits(given, fits, self.config, self.program)
            worst.update(judged)
            print(f"refit_resident_forest: {judged.pop('trees_judged'):.0f} trees judged "
                  f"in {time.perf_counter() - t0:.1f} s", flush=True)
            worst.pop("trees_judged", None)
        else:
            worst = dict.fromkeys(worst, reference_forest.BROKEN)
        compared = {
            name: {"value": float(value), "limit": float(limits[name])}
            for name, value in worst.items()
        }
        name, labels = TREES
        compared["trees_off_plan"] = {
            "value": float(abs(
                self.registry.counter(name, **labels) - self.attempted * self.n_trees
            )),
            "limit": 0.0,
        }
        compared["compiled_in_window"] = {
            "value": float(self.registry.counter("compile.cache_misses")),
            "limit": 0.0,
        }
        return compared


def resolve_program() -> dict:
    """The program's functions the reference is handed the results of."""
    from spark_rapids_ml_tpu.models import forest as MF
    from spark_rapids_ml_tpu.ops import forest as FO
    from spark_rapids_ml_tpu.parallel import forest as PF

    return {
        "bootstrap": FO.bootstrap_weights,
        "subsets": FO.node_subsets,
        "sample": MF.edge_sample_ids,
        "levels": MF.quantile_levels,
        "subset_size": MF.subset_size,
        "edges": PF.quantile_edges,
        "binner": PF.make_sharded_binner,
    }


def program_inputs(program: dict, blocks: list, order: list[int], config: dict) -> dict:
    """The program's draws, edges and bins for the table of ``blocks`` in
    ``order``, with the distinct rows they refer to."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.parallel import mesh as M

    params = config["params"]
    n_bins, seed = int(params["maxBins"]), int(params["seed"])
    # the dtype the device holds the rows in: float32 unless x64 is on
    dt = jax.dtypes.canonicalize_dtype(np.float64)
    block_rows = len(blocks[0][0])
    rows = block_rows * len(order)
    x = np.concatenate([b[0] for b in blocks])  # [distinct, F] float64
    y = np.concatenate([b[1] for b in blocks])
    distinct = reference_forest.distinct_of(order, block_rows)
    ids = np.asarray(program["sample"](seed, rows, n_bins))
    sample64 = x[distinct[ids]]
    qs = program["levels"](n_bins)
    edges = np.asarray(program["edges"](
        jnp.asarray(sample64.astype(dt)), jnp.ones((len(ids),), dt),
        jnp.asarray(qs, dt),
    ))
    x32 = x.astype(dt)
    padded = -(-len(x32) // BIN_ROWS) * BIN_ROWS
    mesh = M.create_mesh(data=1, devices=jax.devices()[:1])
    binner = program["binner"](mesh, n_bins)
    dev_bins = np.asarray(binner(
        jnp.asarray(np.pad(x32, ((0, padded - len(x32)), (0, 0)))), jnp.asarray(edges)
    ))[: len(x32)]
    weights = np.asarray(program["bootstrap"](
        seed, int(params["numTrees"]), rows,
        bootstrap=bool(params["bootstrap"]), rate=float(params["subsamplingRate"]),
    ), np.float64)
    return {
        "x32": x32, "y": y, "sample64": sample64, "edges": edges,
        "dev_bins": dev_bins, "distinct": distinct, "weights": weights,
    }


def judge_fits(given: dict, fits: list[dict], config: dict, program: dict) -> dict:
    """The worst of every compared number over the trees compared: every tree
    of the last fit and the first tree of each other fit (a tree equal to
    one already judged is judged once)."""
    import jax

    dt = jax.dtypes.canonicalize_dtype(np.float64)
    params = config["params"]
    n_bins, max_depth = int(params["maxBins"]), int(params["maxDepth"])
    n_trees, seed = int(params["numTrees"]), int(params["seed"])
    n = given["x32"].shape[1]
    k = program["subset_size"](params["featureSubsetStrategy"], n, classification=True)
    ref_bins = reference_forest.bin_rows(given["x32"], given["edges"])
    out = {
        "bins_off": float(np.sum(ref_bins != given["dev_bins"])),
        "edge_gap": reference_forest.edge_gap(
            given["edges"], reference_forest.quantile_edges(given["sample64"], n_bins)
        ),
        "thresholds_off": 0.0,
    }
    keys = jax.random.split(jax.random.PRNGKey(seed), n_trees)
    folded = reference_forest.fold(given["weights"], given["distinct"], len(given["x32"]))
    n_classes = int(given["y"].max()) + 1
    wanted = [(fits[-1], t) for t in range(n_trees)] + [(a, 0) for a in fits[:-1]]
    seen: dict[bytes, dict] = {}
    for answer, t in wanted:
        tree = {name: arr[t] for name, arr in answer["trees"].items()}
        out["thresholds_off"] = max(out["thresholds_off"], reference_forest.thresholds_off(
            tree["feature"], tree["split_bin"], answer["thresholds"][t], given["edges"]))
        mark = b"".join(np.ascontiguousarray(v).tobytes() for v in tree.values()) + bytes([t])
        if mark not in seen:
            subsets = [
                np.asarray(program["subsets"](keys[t], d, n, k, dt))
                for d in range(max_depth)
            ]
            seen[mark] = reference_forest.judge_tree(
                tree, ref_bins, given["y"], folded[t], subsets,
                max_depth=max_depth, n_bins=n_bins, n_classes=n_classes,
                min_instances=float(params["minInstancesPerNode"]),
                eps=float(np.finfo(dt).eps),
            )
        for name, value in seen[mark].items():
            out[name] = max(out.get(name, 0.0), value)
    out["trees_judged"] = float(len(seen))
    return out
