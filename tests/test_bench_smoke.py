"""Tier-1 guard for the bench harness: ``bench.py --smoke`` must keep
producing its JSON contract — including the ``streamed_fit_rows_per_s``
out-of-core metric — on the CPU backend, and appending a ``perf_ledger``
entry that the regression sentinel accepts (ISSUE 5).

Runs the bench as a subprocess (it owns platform/x64 setup) with the shared
compilation cache so repeat runs stay cheap.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_smoke_json_contract(tmp_path):
    ledger = str(tmp_path / "PERF_LEDGER.jsonl")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        TPU_ML_PERF_LEDGER_PATH=ledger,
        TPU_ML_PERF_SENTINEL="1",  # the bench gates itself on the sentinel
    )
    env.pop("TPU_ML_FAULT_PLAN", None)  # the zero-fault assertion below
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--smoke"],
        capture_output=True,
        text=True,
        timeout=420,
        env=env,
        cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]

    json_lines = [
        ln for ln in proc.stdout.splitlines() if ln.startswith("{")
    ]
    assert json_lines, f"no JSON line in bench output:\n{proc.stdout[-2000:]}"
    data = json.loads(json_lines[-1])

    assert data["value"] > 0
    assert data["unit"]
    assert "SMOKE" in data["metric"]

    extras = {m["metric"]: m for m in data["extra_metrics"]}
    assert "streamed_fit_rows_per_s" in extras, sorted(extras)
    sf = extras["streamed_fit_rows_per_s"]
    assert sf["unit"] == "rows/s"
    assert sf["value"] > 0
    # pipeline introspection must ride along so perf regressions in the
    # overlap machinery are visible in the bench record
    assert "overlapped_dispatches" in sf
    # flight-recorder evidence (ISSUE 4): the recorded H2D<->compute
    # overlap fraction of the timed streamed reps — structural only, no
    # absolute-time assertions (wall-clock is host-load-dependent)
    assert "overlap_fraction" in sf
    if sf["overlap_fraction"] is not None:
        assert 0.0 <= sf["overlap_fraction"] <= 1.0

    # the telemetry snapshot makes every BENCH_r* round phase-attributable
    # (ISSUE 2): full registry state keyed counters/gauges/spans/histograms
    tel = data["telemetry"]
    assert set(tel) == {"counters", "gauges", "spans", "histograms"}
    # the bench's streamed-fit stage ran through the instrumented pipeline,
    # so its spans must appear in the snapshot (reset_metrics in
    # _paired_slope clears earlier stages; the streamed-fit stage and the
    # DataFrame fit run after the last reset)
    assert any(
        phase.startswith(("fold.", "ingest.")) for phase in tel["spans"]
    ), sorted(tel["spans"])
    # no TPU_ML_FAULT_PLAN is set, so the resilience layer must be inert:
    # zero synthetic faults fired during the bench
    injected = [k for k in tel["counters"] if k.startswith("fault.injected")]
    assert injected == [], injected

    # the live-exporter stage (ISSUE 8): the bench scraped its own /healthz
    # (must be 200 on this healthy process) and /metrics (must contain the
    # streamed-fit counter families) over real HTTP on an ephemeral port —
    # a hard contract in --smoke, so rc=0 above already proves the scrape
    # succeeded; the evidence block records what it saw
    hl = data["health"]
    assert hl["healthz"] == 200
    assert hl["state"] == "OK"
    assert hl["components"].get("transport") == "OK"
    assert hl["components"].get("stream") == "OK"
    assert hl["port"] > 0
    assert hl["metrics_scrape_bytes"] > 0
    # the monitor's poll published its gauges into the same registry the
    # snapshot serialized
    assert "health.state{component=overall}" in data["telemetry"]["gauges"]

    # the run appended one perf-ledger entry holding every emitted metric
    # plus the analytical cost-model numbers (ISSUE 5)
    with open(ledger, encoding="utf-8") as f:
        entries = [json.loads(ln) for ln in f if ln.strip()]
    assert len(entries) == 1
    entry = entries[0]
    assert entry["type"] == "perf_ledger"
    assert entry["smoke"] is True
    assert data["metric"] in entry["metrics"]
    assert "streamed_fit_rows_per_s" in entry["metrics"]
    assert entry["metrics"]["streamed_fit_rows_per_s"]["unit"] == "rows/s"
    assert "analytical_flops" in entry["cost_model"]
    # the health verdict stamps the ledger so the sentinel's reader can
    # tell environment problems from genuine regressions (ISSUE 8)
    assert entry["health_state"] == "OK"
    # TPU_ML_PERF_SENTINEL=1 already ran the gate in-process (exit 0 above
    # proves a fresh ledger passes); the standalone CLI agrees
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(REPO, "tools", "perf_sentinel.py"),
            ledger,
            "--strict",
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
