#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. It finds the cell's
configuration (``configs/<config>.json``), its traffic (``traffic/<traffic>.json``)
and the traffic's driver (``drivers/<driver>.py``) by name; with ``--trace 1``
it finds each per-layer metric's reader (``layer_metrics/<metric>.json`` and
``sources/<kind>.py``) the same way. A new cell, configuration, mix or metric
is new files and new entries in BENCHMARK.json, and no edit here.

Without a TPU, or with fewer chips than the cell asks for, it exits with a
code other than 0 and prints no result. Human lines go first; the last line of
standard output is the result, and the last lines of standard error are the
numbers that decided ``correct``, each beside its limit.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is counted from here

import argparse
import importlib
import json
import resource
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import manifest as M  # noqa: E402
from benchmarks.sources import Readings  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


def say(msg: str) -> None:
    print(f"[bench {time.perf_counter() - T0:7.2f}s] {msg}", flush=True)


def parse(argv) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_facts(jax) -> dict:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def memory_peak(jax) -> int | None:
    """The peak on the fullest chip, where the backend reports one."""
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def start_trace(jax) -> None:
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0  # the program's own spans are TraceAnnotations
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)


def read_layer_metrics(manifest: dict, cell: str, ctx: Readings) -> dict:
    """Each per-layer metric of the cell through the reader its file names.
    A reader that finds nothing leaves its metric out of the line."""
    out = {}
    for metric in M.metrics_for(manifest, "per_layer", cell):
        spec = M.load_json(f"layer_metrics/{metric['name']}.json")["reader"]
        reader = importlib.import_module(f"benchmarks.sources.{spec['kind']}")
        value = reader.read(spec, ctx)
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
            note = getattr(reader, "binding", None)
            if note is not None:
                say(f"{metric['name']}: the {note(spec, ctx)} bound binds")
    return out


def run(args: argparse.Namespace, rehearsal: dict | None = None) -> dict | None:
    """The whole run. Returns the result, or None where no result may be
    printed. ``rehearsal`` (the self-check's) swaps in a toy configuration and
    lets the run go on without a TPU; its result is marked and never printed
    as a measurement."""
    manifest = M.load()
    cell, config, traffic = M.load_cell(args.workload)
    if rehearsal:
        config = {**config, **rehearsal["config"]}
        traffic = {**traffic, **rehearsal["traffic"]}
    M.apply_env(config)

    import jax

    device = device_facts(jax)
    want = "cpu" if rehearsal else "tpu"
    if device["platform"] != want or device["count"] < cell["chips"]:
        print(
            f"benchmarks/run.py: the cell needs {cell['chips']} {want} device(s), "
            f"JAX reports {device} - nothing was run", file=sys.stderr,
        )
        return None
    peaks = M.load_json("peaks.json")
    if not rehearsal and device["kind"] not in peaks:
        print(f"benchmarks/run.py: no peaks for {device['kind']!r}", file=sys.stderr)
        return None
    module = importlib.import_module(f"benchmarks.drivers.{traffic['driver']}")
    driver = module.Driver(config, traffic, args.seed, cell["chips"])
    say(f"cell {cell['name']} seed {args.seed} on {device}")
    driver.setup()
    if args.trace:
        start_trace(jax)
    setup_s = time.perf_counter() - T0
    say(f"set-up took {setup_s:.2f} s {getattr(driver, 'setup_marks', '')}; "
        f"the window opens for {args.seconds:g} s")

    driver.window(args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
    device["memory_peak_bytes"] = memory_peak(jax)
    say(
        f"window closed: {driver.completed} of {driver.attempted} completed in "
        f"{driver.elapsed_s:.2f} s; fits took "
        f"{[round(a['seconds'], 2) for a in driver.answers]}"
    )
    for answer in sorted(driver.answers, key=lambda a: -a["seconds"])[:4]:
        if answer.get("phases"):
            say(f"  a fit of {answer['seconds']:.2f} s spent {answer['phases']}")
    driver.release()
    say(f"host peak RSS {resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20:.1f} GiB; "
        f"device peak {device['memory_peak_bytes']} B")

    metrics = {}
    if args.trace:
        from benchmarks import trace_reduce

        trace = trace_reduce.reduce_dir(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if trace and trace["busy_s"]:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        ctx = Readings(
            config=config, chips=cell["chips"], peak=peaks.get(device["kind"], {}),
            elapsed_s=driver.elapsed_s, completed=driver.completed,
            registry=driver.registry, trace=trace,
        )
        metrics = read_layer_metrics(manifest, cell["name"], ctx)
    else:
        measured = {"setup_s": setup_s, **driver.end_to_end()}
        for metric in M.metrics_for(manifest, "end_to_end", cell["name"]):
            if metric["name"] in measured:
                metrics[metric["name"]] = {
                    "value": measured[metric["name"]], "unit": metric["unit"],
                }

    t0 = time.perf_counter()
    compared = driver.check()
    say(f"the reference and the comparison took {time.perf_counter() - t0:.2f} s")
    correct = driver.completed > 0 and all(
        c["value"] <= c["limit"] for c in compared.values()
    )
    result = {
        "correct": bool(correct),
        "attempted": driver.attempted,
        "failed": driver.failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace and trace:
        result["breakdown"] = {
            "device_ops": trace["device_ops"][:10],
            "idle_gaps": trace["idle_gaps"][:10],
        }
    if rehearsal:
        result["rehearsal"] = True
    errors = [a["error"] for a in driver.answers if a["error"]]
    if errors:
        result["errors"] = errors[:3]
    result["compared"] = compared  # last in the line
    return result


def report(result: dict) -> None:
    for name, c in result["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name}: {c['value']!r} limit {c['limit']!r} {verdict}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    result = run(parse(argv))
    if result is None:
        return 2
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
