"""Checks of the benchmark that need no chip: ``python -m benchmarks.selfcheck``.

Every string of BENCHMARK.json against the driver's rules; every file a cell
names; ``opcount`` on shapes worked by hand; the trace reduction on a small
recorded TPU trace; and ``run.py``'s whole control flow at a toy size on the
CPU, as a rehearsal that is marked as one and whose numbers are never printed
as measurements. ``tests/test_selfcheck.py`` calls the same functions.
"""

from __future__ import annotations

import argparse
import contextlib
import gzip
import importlib
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as M  # noqa: E402
from benchmarks import opcount  # noqa: E402

RECORDED_TRACE = M.HERE / "testdata" / "tiny.xplane.pb.gz"

# the toy the rehearsal swaps in: 16,384 rows of 64 features in 8 blocks, two
# chunks a fit like the real cells, streamed because the cutover is lowered
TOY = {
    "config": {
        "n_features": 64,
        "rows": 16384,
        "params": {"k": 8, "distribution": "mesh-local", "precision": "highest",
                   "solver": "full", "meanCentering": False},
        "env": {"TPU_ML_STREAM_CHUNK_ROWS": "8192", "TPU_ML_AUTOTUNE": "off",
                "TPU_ML_STREAM_FIT_MAX_RESIDENT_BYTES": str(4096 * 64 * 8)},
    },
    "traffic": {"block_bytes": 2048 * 64 * 8},
}


def check_manifest() -> list[str]:
    return M.check(M.load())


def check_files() -> list[str]:
    """Every file and module that a cell or a metric names is there."""
    errors = []
    manifest = M.load()
    for cell in manifest["workloads"]:
        entry = M.config_entry(manifest, cell["config"])
        with open(ROOT / entry["file"], encoding="utf-8") as f:
            config = json.load(f)
        if config["name"] != entry["name"] or config["reduced"] != entry["reduced"]:
            errors.append(f"{entry['file']} and BENCHMARK.json disagree on name or reduced")
        for key in ("source", "params", "env", "assumed", "guarantee", "limits", "reference"):
            if key not in config:
                errors.append(f"{entry['file']} lacks {key!r}")
        traffic = M.load_json(f"traffic/{cell['traffic']}.json")
        driver = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
        if not hasattr(driver, "Driver"):
            errors.append(f"drivers/{traffic['driver']}.py has no Driver")
    for metric in manifest["per_layer"]:
        try:
            spec = M.load_json(f"layer_metrics/{metric['name']}.json")["reader"]
            reader = importlib.import_module(f"benchmarks.sources.{spec['kind']}")
            if not callable(getattr(reader, "read", None)):
                errors.append(f"sources/{spec['kind']}.py has no read()")
        except (OSError, KeyError, ImportError) as e:
            errors.append(f"per-layer metric {metric['name']}: {type(e).__name__}: {e}")
    peaks = M.load_json("peaks.json")
    for kind, row in peaks.items():
        for key in ("bf16_flops_per_s", "hbm_bytes_per_s", "hbm_bytes", "source"):
            if key not in row:
                errors.append(f"peaks.json {kind!r} lacks {key}")
    return errors


def check_opcount() -> list[str]:
    """Shapes worked by hand."""
    errors = []
    # 8 rows of 4 features: 2*8*4*4 FLOP; 4*8*4 bytes of chunk + 8*4*4 of carry
    if opcount.gram_fold(8, 4) != {"flops": 256.0, "bytes": 256.0}:
        errors.append(f"gram_fold(8, 4) = {opcount.gram_fold(8, 4)}")
    # a chunk of pca-2048-k50: 2*524288*2048^2 = 4.398 TFLOP, 4 GiB + 32 MiB
    work = opcount.gram_fold(524288, 2048)
    if work != {"flops": 2.0 * 524288 * 2048 ** 2, "bytes": 4.0 * 2 ** 30 + 8.0 * 2 ** 22}:
        errors.append(f"gram_fold(524288, 2048) = {work}")
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    seconds, bound = opcount.least_seconds(work, peak)
    if bound != "compute" or abs(seconds - work["flops"] / 197e12) > 1e-12:
        errors.append(f"least_seconds at n=2048: {seconds}, {bound}")
    # at n=128 a chunk's bytes take longer than its operations
    if opcount.least_seconds(opcount.gram_fold(1 << 20, 128), peak)[1] != "memory":
        errors.append("least_seconds at n=128 is not memory-bound")
    if opcount.pca_fit(100, 10) != {"flops": 20000.0, "bytes": 4000.0}:
        errors.append(f"pca_fit(100, 10) = {opcount.pca_fit(100, 10)}")
    return errors


def reduce_recorded_trace() -> dict:
    """The reduction of the recorded TPU trace (two toy fits, four chunks each)."""
    from benchmarks import trace_reduce

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "tiny.xplane.pb")
        with gzip.open(RECORDED_TRACE, "rb") as src, open(path, "wb") as dst:
            shutil.copyfileobj(src, dst)
        return trace_reduce.reduce(path)


def check_trace_reduce() -> list[str]:
    errors = []
    trace = reduce_recorded_trace()
    if trace is None:
        return ["the recorded trace reduces to nothing"]
    if not 0 < trace["busy_s"] < trace["window_s"]:
        errors.append(f"busy {trace['busy_s']} s of a window of {trace['window_s']} s")
    fold = trace["programs"].get("jit__fold")
    if not fold or fold["count"] != 8:
        errors.append(f"the recorded trace holds 8 folds, the reduction finds {fold}")
    if not trace["device_ops"] or not trace["idle_gaps"]:
        errors.append("no device operations or no idle gaps in the reduction")
    idle = sum(s for _, s in trace["idle_gaps"])
    if abs(idle + trace["busy_s"] - trace["window_s"]) > 1e-6 * trace["window_s"]:
        errors.append(f"idle {idle} + busy {trace['busy_s']} is not the window {trace['window_s']}")
    return errors


@contextlib.contextmanager
def kept_environment():
    before = dict(os.environ)
    try:
        yield
    finally:
        os.environ.clear()
        os.environ.update(before)


def rehearse(workload: str, trace: int, seed: int = 3_000_000_019, seconds: float = 1.0):
    """``run.py``'s control flow for one cell at the toy size, on the CPU."""
    from benchmarks import run

    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds, trace=trace)
    with kept_environment():
        return run.run(args, rehearsal=TOY)


def check_rehearsal() -> list[str]:
    errors = []
    manifest = M.load()
    for cell in manifest["workloads"]:
        for trace in (0, 1):
            result = rehearse(cell["name"], trace)
            what = f"rehearsal of {cell['name']} --trace {trace}"
            if result is None:
                errors.append(f"{what}: no result (is JAX on the CPU here?)")
                continue
            if not (result["correct"] and result["attempted"] and not result["failed"]):
                errors.append(f"{what}: {json.dumps(result)}")
            want = {
                m["name"] for m in M.metrics_for(
                    manifest, "per_layer" if trace else "end_to_end", cell["name"])
            }
            # no device plane and no table of peaks on the CPU: the readers
            # of the two shares rightly find nothing
            want -= {"gram_roofline", "fit_mfu"}
            if set(result["metrics"]) != want:
                errors.append(f"{what}: metrics {sorted(result['metrics'])}, want {sorted(want)}")
    return errors


CHECKS = {
    "manifest": check_manifest,
    "files": check_files,
    "opcount": check_opcount,
    "trace_reduce": check_trace_reduce,
    "rehearsal": check_rehearsal,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("checks", nargs="*", choices=[*CHECKS, []], help="default: all")
    args = ap.parse_args(argv)
    failed = 0
    for name in args.checks or CHECKS:
        errors = CHECKS[name]()
        failed += len(errors)
        print(f"{name}: {'ok' if not errors else 'FAILED'}")
        for e in errors:
            print(f"  {e}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
