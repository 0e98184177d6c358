"""What the chip entry points do where there is no chip, and where the
compile cache lives — the parts of the bring-up a CPU can attest.

``chip_smoke.py`` itself passes only on a TPU (run it through the chip tool);
here it, ``bench.py`` and ``__graft_entry__.dryrun_multichip`` must refuse or
use what they are given, never pick a platform of their own.
"""

import inspect
import json
import os
import subprocess
import sys

import jax
import pytest

from spark_rapids_ml_tpu.utils import config, devicepolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env_overrides):
    """Run ``python *args`` from the repo root; an override of ``None``
    removes the variable."""
    env = devicepolicy.apply_overrides(os.environ, env_overrides)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )


def _json_lines(text):
    return [ln for ln in text.splitlines() if ln.startswith("{")]


class TestNoChipNoResult:
    def test_chip_smoke_refuses_the_cpu(self):
        proc = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
        assert proc.returncode != 0
        assert "nothing was run" in proc.stderr
        assert not proc.stdout.strip()  # no phase line, no result

    def test_chip_smoke_last_line_is_the_verdict_alone(self, capsys):
        """The driver reads the last line of stdout and wants exactly
        ``ok`` and ``device`` {platform, kind, count}; the detail goes on the
        line before. A rehearsal prints no verdict."""
        sys.path.insert(0, REPO)
        try:
            import chip_smoke
        finally:
            sys.path.remove(REPO)
        device = chip_smoke.device_facts()
        assert list(device) == ["platform", "kind", "count"]
        assert isinstance(device["kind"], str) and type(device["count"]) is int
        summary = {"device": device, "phases": {"fit_resident": {"wall_s": 1.0}}}

        chip_smoke.report(summary, rehearse=False)
        detail, verdict = capsys.readouterr().out.splitlines()
        assert json.loads(detail) == summary
        assert json.loads(verdict) == {"ok": True, "device": device}

        chip_smoke.report(summary, rehearse=True)
        (line,) = capsys.readouterr().out.splitlines()
        assert json.loads(line)["rehearsal"] is True
        assert "ok" not in json.loads(line)

    def test_bench_without_smoke_refuses_the_cpu(self):
        proc = _run(
            ["bench.py"], JAX_PLATFORMS="cpu", TPU_ML_PERF_LEDGER_PATH=""
        )
        assert proc.returncode != 0
        assert "nothing was run" in proc.stderr
        assert not _json_lines(proc.stdout)

    def test_dryrun_multichip_uses_the_devices_it_is_given(self):
        sys.path.insert(0, REPO)
        try:
            import __graft_entry__ as entry
        finally:
            sys.path.remove(REPO)
        source = inspect.getsource(entry.dryrun_multichip)
        assert "jax_platforms" not in source
        assert "jax_num_cpu_devices" not in source
        before = jax.config.jax_platforms
        # one more than this process has: the shortfall is raised, not
        # papered over by reconfiguring the backend
        with pytest.raises(RuntimeError, match="need 9 devices, have 8"):
            entry.dryrun_multichip(9)
        assert jax.config.jax_platforms == before


class TestCompileCacheRule:
    def test_a_directory_already_chosen_is_left_alone(self, monkeypatch):
        """``JAX_COMPILATION_CACHE_DIR`` reaches jax.config when JAX is
        imported; with a directory there, the rule sets none in code."""
        assert jax.config.jax_compilation_cache_dir  # conftest applied it
        calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda name, value: calls.append(name)
        )
        chosen = config.enable_compilation_cache()
        assert chosen == jax.config.jax_compilation_cache_dir
        assert "jax_compilation_cache_dir" not in calls

    def test_cache_and_ledger_names_in_gitignore(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            ignored = f.read().split()
        assert ".jax_cache/" in ignored
        assert "PERF_LEDGER.jsonl" not in ignored  # the driver's file

    def test_in_force_before_the_first_compile_of_a_plain_fit(self):
        """A core ``PCA().fit(ndarray)`` in a fresh process, nothing set
        outside: the cache is ``<repo root>/.jax_cache``, and every compile
        request either hit it or was written to it, the very first
        included."""
        program = """
import json, numpy as np, jax
from spark_rapids_ml_tpu import PCA
from spark_rapids_ml_tpu.telemetry import REGISTRY
x = np.linspace(0.0, 1.0, 96 * 5).reshape(96, 5)
PCA().setInputCol("f").setK(2).fit(x)
snap = REGISTRY.snapshot()
print(json.dumps({
    "dir": jax.config.jax_compilation_cache_dir,
    "floor_s": jax.config.jax_persistent_cache_min_compile_time_secs,
    "requests": snap.hist("compile.seconds").count,
    "hits": snap.counter("compile.cache_hits"),
    "misses": snap.counter("compile.cache_misses"),
}))
"""
        proc = _run(
            ["-c", program], JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=None
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        got = json.loads(_json_lines(proc.stdout)[-1])
        assert got["dir"] == os.path.join(REPO, ".jax_cache")
        assert got["floor_s"] == 0  # however short a compile, it is kept
        assert got["requests"] > 0
        assert got["hits"] + got["misses"] == got["requests"], got


def test_bench_history_is_not_the_drivers_ledger(monkeypatch):
    """A bare ``python bench.py --smoke`` appends to its own history file."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    monkeypatch.delenv("TPU_ML_PERF_LEDGER_PATH", raising=False)
    assert bench._ledger_path() == os.path.join(REPO, "bench_history.jsonl")
