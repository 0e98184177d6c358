"""BENCHMARK.json: loading, lookups, and the checks the driver makes before
any run, so that a bad string costs a CPU second and not a PR (PR 22)."""

from __future__ import annotations

import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
# a full check: 2 + 14 runs a cell, each run_seconds + 60, each cell 2 x 90
# more to compile, 1200 spare, inside 43200 s with the full 24 cells
MAX_CELLS, CHECK_SECONDS = 24, 43200


def load(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def load_json(rel: str) -> dict:
    with open(HERE / rel, encoding="utf-8") as f:
        return json.load(f)


def workload(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_entry(manifest: dict, name: str) -> dict:
    for cfg in manifest["configs"]:
        if cfg["name"] == name:
            return cfg
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def load_cell(name: str) -> tuple[dict, dict, dict]:
    """A cell's entry, its configuration's file and its traffic's file."""
    manifest = load()
    cell = workload(manifest, name)
    with open(ROOT / config_entry(manifest, cell["config"])["file"], encoding="utf-8") as f:
        config = json.load(f)
    return cell, config, load_json(f"traffic/{cell['traffic']}.json")


def apply_env(config: dict) -> None:
    """The configuration's environment and the compile cache's fixed place,
    set before the program is imported."""
    import os

    os.environ.update(config.get("env", {}))
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))


def metrics_for(manifest: dict, section: str, cell: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports:
    those that list it under ``workloads``, and those with no such key."""
    return [
        m for m in manifest[section]
        if "workloads" not in m or cell in m["workloads"]
    ]


def _line(text, what: str, errors: list[str]) -> None:
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def _keys(entry: dict, want: set, optional: set, what: str, errors) -> None:
    have = set(entry)
    if not (want <= have <= want | optional):
        errors.append(
            f"{what}: keys {sorted(have)} are not {sorted(want)}"
            + (f" (+ {sorted(optional)})" if optional else "")
        )


def check(manifest: dict, root: Path = ROOT) -> list[str]:
    """Every rule on BENCHMARK.json that can be checked without a run.
    Returns the faults found; an empty list is a pass."""
    errors: list[str] = []
    if set(manifest) != TOP_KEYS:
        errors.append(f"top-level keys {sorted(manifest)} are not {sorted(TOP_KEYS)}")
        return errors
    if len(json.dumps(manifest)) > 64 * 1024:
        errors.append("BENCHMARK.json is over 64 KiB")

    paths = manifest["paths"]
    if not 1 <= len(paths) <= 16:
        errors.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            errors.append(f"path {p!r}: relative, from letters, digits, _ . - /")
        elif not (root / p).is_dir():
            errors.append(f"path {p!r} is not a directory")
    command = manifest["command"]
    if not 1 <= len(command) <= 32:
        errors.append("command: 1 to 32 strings")
    for word in command:
        _line(word, f"command word {word!r}", errors)
        if word.startswith("/") or ".." in word.split("/"):
            errors.append(f"command word {word!r} leads out of the repo")
        elif (root / word).exists() and not any(
            word == p or word.startswith(p.rstrip("/") + "/") for p in paths
        ):
            errors.append(f"command names {word!r}, a file outside paths")

    seconds = manifest["run_seconds"]
    if not (isinstance(seconds, int) and 1 <= seconds <= 51):
        errors.append("run_seconds: a whole number from 1 to 51")
    elif (2 + 14 * MAX_CELLS) * (seconds + 60) + MAX_CELLS * 180 + 1200 > CHECK_SECONDS:
        errors.append(f"run_seconds {seconds}: a full check of 24 cells does not fit")

    def under_paths(rel: str) -> bool:
        return any(rel.startswith(p.rstrip("/") + "/") for p in paths)

    names: dict[str, set] = {k: set() for k in ("config", "cell", "metric")}

    def fresh(kind: str, name, errors) -> None:
        if not (isinstance(name, str) and NAME.match(name)):
            errors.append(f"{kind} name {name!r} is not a slug of 1 to 64 characters")
        if name in names[kind]:
            errors.append(f"{kind} name {name!r} appears twice")
        names[kind].add(name)

    if not 1 <= len(manifest["configs"]) <= 24:
        errors.append("configs: 1 to 24")
    files = set()
    for cfg in manifest["configs"]:
        what = f"config {cfg.get('name')!r}"
        _keys(cfg, {"name", "source", "file", "reduced", "why"}, set(), what, errors)
        fresh("config", cfg.get("name"), errors)
        _line(cfg.get("source"), f"{what} source", errors)
        _line(cfg.get("why"), f"{what} why", errors)
        rel = cfg.get("file", "")
        if not (PATH.match(rel) and under_paths(rel) and (root / rel).is_file()):
            errors.append(f"{what}: file {rel!r} is not a file under paths")
        if rel in files:
            errors.append(f"{what}: file {rel!r} is another configuration's")
        files.add(rel)
        reduced = cfg.get("reduced", [])
        if len(reduced) > 16:
            errors.append(f"{what}: reduced has over 16 keys")
        for key in reduced:
            if not NAME.match(key):
                errors.append(f"{what}: reduced key {key!r} is not a slug")
            if key.endswith(("_dim", "_rank")) or key in ("k", "n_features"):
                errors.append(f"{what}: reduced names a width, {key!r}")

    cells = manifest["workloads"]
    if not 1 <= len(cells) <= MAX_CELLS:
        errors.append("workloads: 1 to 24 cells")
    pairs = set()
    for cell in cells:
        what = f"cell {cell.get('name')!r}"
        _keys(cell, {"name", "config", "traffic", "chips", "why"}, set(), what, errors)
        fresh("cell", cell.get("name"), errors)
        _line(cell.get("why"), f"{what} why", errors)
        if cell.get("config") not in names["config"]:
            errors.append(f"{what}: unknown configuration {cell.get('config')!r}")
        if not NAME.match(str(cell.get("traffic"))):
            errors.append(f"{what}: traffic {cell.get('traffic')!r} is not a slug")
        if cell.get("chips") not in (1, 4):
            errors.append(f"{what}: chips is 1 or 4")
        pair = (cell.get("config"), cell.get("traffic"))
        if pair in pairs:
            errors.append(f"{what}: the pair {pair} appears twice")
        pairs.add(pair)
    four = sum(1 for c in cells if c.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        errors.append(f"{four} cells ask for 4 chips; at most {max(1, len(cells) // 4)} may")
    used = {c.get("config") for c in cells}
    for name in names["config"] - used:
        errors.append(f"config {name!r} is used by no cell")

    e2e = manifest["end_to_end"]
    if not 1 <= len(e2e) <= 16:
        errors.append("end_to_end: 1 to 16 metrics")
    if not any(m.get("name") == "setup_s" for m in e2e):
        errors.append("end_to_end lacks setup_s")
    for m in e2e:
        what = f"end_to_end metric {m.get('name')!r}"
        _keys(m, {"name", "unit", "better", "bound", "source"}, {"workloads"}, what, errors)
        fresh("metric", m.get("name"), errors)
        if m.get("source") not in ("host_clock", "device_trace"):
            errors.append(f"{what}: source is host_clock or device_trace")
        bound = m.get("bound")
        if not (isinstance(bound, (int, float)) and 0.01 <= bound <= 0.1):
            errors.append(f"{what}: bound {bound!r} is not within 0.01 to 0.1")
    e2e_cells = {
        m["name"]: set(m.get("workloads", names["cell"])) for m in e2e if "name" in m
    }
    if not 1 <= len(manifest["per_layer"]) <= 128:
        errors.append("per_layer: 1 to 128 metrics")
    for m in manifest["per_layer"]:
        what = f"per_layer metric {m.get('name')!r}"
        _keys(m, {"name", "unit", "better", "source", "layer", "moves"},
              {"workloads"}, what, errors)
        fresh("metric", m.get("name"), errors)
        if m.get("source") not in SOURCES:
            errors.append(f"{what}: source is one of {SOURCES}")
        # PR 22 was refused on this one string: a layer is a slug too
        if not NAME.match(str(m.get("layer"))):
            errors.append(f"{what}: layer {m.get('layer')!r} is not a slug")
        moved = e2e_cells.get(m.get("moves"))
        if moved is None:
            errors.append(f"{what}: moves {m.get('moves')!r}, no end-to-end metric")
        elif not set(m.get("workloads", names["cell"])) <= moved:
            errors.append(f"{what}: a cell it lists does not report {m['moves']}")
        name = str(m.get("name"))
        if "roofline" in name or "mfu" in re.split(r"[._\-]", name):
            if m.get("unit") != "%":
                errors.append(f"{what}: a share of a roofline or a peak has unit %")
    for m in e2e + manifest["per_layer"]:
        what = f"metric {m.get('name')!r}"
        if not UNIT.match(str(m.get("unit"))):
            errors.append(f"{what}: unit {m.get('unit')!r} is not 1 to 16 of letters, digits, _ / % . -")
        if m.get("better") not in ("lower", "higher"):
            errors.append(f"{what}: better is lower or higher")
        for cell in m.get("workloads", []):
            if cell not in names["cell"]:
                errors.append(f"{what}: unknown cell {cell!r}")
    for cell in names["cell"]:
        mine = [m["name"] for m in metrics_for(manifest, "end_to_end", cell)]
        if "setup_s" not in mine or len(mine) < 2:
            errors.append(f"cell {cell!r} reports {mine}: setup_s and one more are needed")
        if not metrics_for(manifest, "per_layer", cell):
            errors.append(f"cell {cell!r} reports no per-layer metric")
    return errors
