"""tools/trace_report.py smoke: tiny fit with the JSONL sink enabled, then
the CLI renders it and the anomaly checks run (ISSUE-2 CI satellite)."""

from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

import numpy as np
import pytest

from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.utils.config import get_config, set_config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "trace_report.py")


def _load_cli_module():
    spec = importlib.util.spec_from_file_location("trace_report", CLI)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def sink(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    old = get_config().telemetry_path
    set_config(telemetry_path=path)
    yield path
    set_config(telemetry_path=old)


def test_cli_renders_a_real_fit(sink):
    x = np.random.default_rng(0).normal(size=(256, 6))
    PCA().setInputCol("f").setK(2).fit(x)
    assert os.path.exists(sink)
    proc = subprocess.run(
        [sys.executable, CLI, sink],
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    out = proc.stdout
    assert "PCA" in out
    assert "phase" in out  # the per-phase table header rendered
    # the anomaly checker ran (either clean or flagged)
    assert "anomaly checks: ok" in out or "!!" in out


def test_cli_in_process_main(sink):
    x = np.random.default_rng(1).normal(size=(128, 4))
    PCA().setInputCol("f").setK(2).fit(x)
    mod = _load_cli_module()
    assert mod.main([sink]) == 0
    assert mod.main([sink, "--last", "1"]) == 0


def test_cli_missing_file_fails_cleanly():
    mod = _load_cli_module()
    assert mod.main(["/nonexistent/t.jsonl"]) == 1


def test_overlap_anomaly_fires():
    mod = _load_cli_module()
    rec = {
        "type": "fit_report",
        "estimator": "X",
        "wall_seconds": 10.0,
        "rows_ingested": 100,
        "phases": {
            "fold.dispatch": {"count": 4, "sum": 1.0},
            "fold.wait": {"count": 1, "sum": 5.0},
        },
        "compile": {},
    }
    anomalies = mod.check_anomalies(rec)
    assert any("not overlapping" in a for a in anomalies)


def test_compile_dominated_anomaly_fires():
    mod = _load_cli_module()
    rec = {
        "type": "fit_report",
        "estimator": "X",
        "wall_seconds": 2.0,
        "rows_ingested": 100,
        "phases": {},
        "compile": {"count": 3, "seconds": 1.5},
    }
    anomalies = mod.check_anomalies(rec)
    assert any("compile-dominated" in a for a in anomalies)


@pytest.mark.parametrize(
    "compile_rec, fires",
    [
        # no persistent cache: every request is a compile
        ({"count": 3, "seconds": 1.5}, True),
        # the cache in use and every request a load (an eager eigh makes
        # sixty): nothing was compiled, however long the loads took
        ({"count": 60, "seconds": 1.5, "cache_hits": 60, "cache_misses": 0,
          "cache_load_seconds": 1.4}, False),
        # two real compiles among the loads, and they took the time
        ({"count": 60, "seconds": 1.8, "cache_hits": 58, "cache_misses": 2,
          "cache_load_seconds": 0.3}, True),
        # two real compiles, but the seconds were the loads'
        ({"count": 60, "seconds": 1.8, "cache_hits": 58, "cache_misses": 2,
          "cache_load_seconds": 1.5}, False),
    ],
)
def test_compile_dominated_counts_compiles_not_cache_loads(compile_rec, fires):
    mod = _load_cli_module()
    rec = {
        "type": "fit_report", "estimator": "X", "wall_seconds": 2.0,
        "rows_ingested": 100, "phases": {}, "compile": compile_rec,
    }
    got = any("compile-dominated" in a for a in mod.check_anomalies(rec))
    assert got is fires


@pytest.mark.parametrize(
    "compile_rec, fires",
    [
        ({"count": 60}, True),
        ({"count": 60, "cache_hits": 60, "cache_misses": 0}, False),
        ({"count": 60, "cache_hits": 56, "cache_misses": 4}, False),
        ({"count": 60, "cache_hits": 30, "cache_misses": 30}, True),
    ],
)
def test_recompile_storm_counts_cache_misses_where_the_cache_is_in_use(
    compile_rec, fires
):
    mod = _load_cli_module()
    rec = {
        "type": "fit_report", "estimator": "X", "wall_seconds": 100.0,
        "rows_ingested": 100, "phases": {}, "compile": compile_rec,
        "cost_model": {"kernels": {"gram": {}}},
    }
    got = any("recompile storm" in a for a in mod.check_anomalies(rec))
    assert got is fires


def test_phase_table_prints_self_seconds(capsys):
    mod = _load_cli_module()
    mod._print_phase_table(
        {"phases": {
            "compute cov": {"count": 1, "sum": 30.0, "self": 0.5},
            "ingest.stage": {"count": 66, "sum": 12.0},  # an older report
        }},
        sys.stdout,
    )
    out = capsys.readouterr().out
    assert out.splitlines()[0].split()[:4] == ["phase", "count", "total", "self"]
    rows = {line.split("  ")[0]: line.split() for line in out.splitlines()[2:]}
    assert rows["compute cov"][3:5] == ["30.000s", "500.00ms"]
    # a report from before spans knew their parent: the whole span is its own
    assert rows["ingest.stage"][2:4] == ["12.000s", "12.000s"]


def test_strict_exit_code(tmp_path):
    mod = _load_cli_module()
    import json

    rec = {
        "type": "fit_report",
        "estimator": "X",
        "wall_seconds": 10.0,
        "rows_ingested": 100,
        "phases": {
            "fold.dispatch": {"count": 4, "sum": 1.0},
            "fold.wait": {"count": 1, "sum": 5.0},
        },
        "compile": {},
    }
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps(rec) + "\n")
    assert mod.main([str(p)]) == 0
    assert mod.main([str(p), "--strict"]) == 2


def test_recovered_but_degraded_anomaly_fires():
    mod = _load_cli_module()
    rec = {
        "type": "fit_report",
        "estimator": "X",
        "wall_seconds": 1.0,
        "rows_ingested": 100,
        "phases": {},
        "compile": {},
        "counters": {
            "retry.attempts{site=ingest.chunk}": 2.0,
            "chunk.bisections{}": 1.0,
        },
    }
    anomalies = mod.check_anomalies(rec)
    assert any("recovered-but-degraded" in a for a in anomalies)


def test_newer_schema_skipped_with_note_not_keyerror(tmp_path, capsys):
    """Schema-tolerance satellite: a record from a future schema renders as
    a skip-note, and --strict turns skips into exit 2."""
    mod = _load_cli_module()
    import json

    future = {"type": "fit_report", "schema": 99, "estimator": "X"}
    ok = {
        "type": "fit_report",
        "estimator": "Y",
        "wall_seconds": 1.0,
        "rows_ingested": 10,
        "phases": {},
        "compile": {},
    }
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps(future) + "\n" + json.dumps(ok) + "\n")
    assert mod.main([str(p)]) == 0  # the good record still rendered
    captured = capsys.readouterr()
    assert "newer than this tool" in captured.err
    assert "Y" in captured.out
    assert mod.main([str(p), "--strict"]) == 2


def test_malformed_record_skipped_not_traceback(tmp_path, capsys):
    mod = _load_cli_module()
    import json

    # phases as a list breaks the renderer's .items(); must skip, not raise
    bad = {"type": "fit_report", "estimator": "X", "phases": [1, 2]}
    ok = {
        "type": "fit_report",
        "estimator": "Y",
        "wall_seconds": 1.0,
        "rows_ingested": 10,
        "phases": {},
        "compile": {},
    }
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps(bad) + "\n" + json.dumps(ok) + "\n")
    assert mod.main([str(p)]) == 0
    captured = capsys.readouterr()
    assert "skipping unrenderable record" in captured.err
    assert "Y" in captured.out


def test_overlap_fraction_and_fit_id_rendered():
    mod = _load_cli_module()
    import io

    rec = {
        "type": "fit_report",
        "estimator": "X",
        "fit_id": "abc123def456",
        "overlap_fraction": 0.75,
        "wall_seconds": 1.0,
        "rows_ingested": 10,
        "phases": {},
        "compile": {},
    }
    buf = io.StringIO()
    mod.render_record(rec, out=buf)
    out = buf.getvalue()
    assert "fit=abc123def456" in out
    assert "overlap: 0.75" in out


def test_fault_injection_anomaly_fires_and_strict_exits_2(tmp_path):
    mod = _load_cli_module()
    import json

    rec = {
        "type": "fit_report",
        "estimator": "X",
        "wall_seconds": 1.0,
        "rows_ingested": 100,
        "phases": {},
        "compile": {},
        "counters": {"fault.injected{site=fold.dispatch,kind=oom}": 3.0},
    }
    anomalies = mod.check_anomalies(rec)
    assert any("fault injection active" in a for a in anomalies)
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps(rec) + "\n")
    assert mod.main([str(p)]) == 0
    assert mod.main([str(p), "--strict"]) == 2


def test_slo_breach_anomaly_fires_and_strict_exits_2(tmp_path):
    """Schema-5 satellite: counted slo.breach during the fit window is the
    slo-breach-during-fit anomaly, and --strict gates on it."""
    mod = _load_cli_module()
    import json

    rec = {
        "type": "fit_report",
        "schema": 5,
        "estimator": "X",
        "wall_seconds": 1.0,
        "rows_ingested": 100,
        "phases": {},
        "compile": {},
        "counters": {"slo.breach{objective=fold.wait:p99}": 2.0},
    }
    anomalies = mod.check_anomalies(rec)
    assert any("slo-breach-during-fit" in a for a in anomalies)
    p = tmp_path / "t.jsonl"
    p.write_text(json.dumps(rec) + "\n")
    assert mod.main([str(p)]) == 0
    assert mod.main([str(p), "--strict"]) == 2


def test_health_summary_rendered_from_schema_5():
    mod = _load_cli_module()
    import io

    rec = {
        "type": "fit_report",
        "schema": 5,
        "estimator": "X",
        "wall_seconds": 1.0,
        "rows_ingested": 10,
        "phases": {},
        "compile": {},
        "health": {
            "state": "DEGRADED",
            "components": {
                "device": "OK",
                "transport": "DEGRADED",
                "stream": "OK",
                "workers": "OK",
                "resilience": "OK",
            },
            "polls": 7,
            "transitions": 2,
            "slo_breaches": 1,
        },
    }
    buf = io.StringIO()
    mod.render_record(rec, out=buf)
    out = buf.getvalue()
    assert "health: DEGRADED (transport=DEGRADED)" in out
    assert "7 poll(s)" in out
    assert "1 SLO breach(es)" in out


def test_health_summary_absent_prints_nothing():
    mod = _load_cli_module()
    import io

    rec = {
        "type": "fit_report",
        "schema": 5,
        "estimator": "X",
        "wall_seconds": 1.0,
        "rows_ingested": 10,
        "phases": {},
        "compile": {},
        "health": {},
    }
    buf = io.StringIO()
    mod.render_record(rec, out=buf)
    assert "health:" not in buf.getvalue()
