"""Warm-path serving runtime: AOT registry, shape buckets, micro-batching.

Covers the ISSUE-10 acceptance list: after a 2-request warmup per bucket,
50 mixed-size concurrent requests across 2 models produce ZERO new compiles
(asserted via the telemetry compile counters) and every response is bitwise
equal to the eager ``transform()`` result on the unpadded rows; a fresh
process re-registering the same model warms from the persistent XLA cache
(``compile.cache_hits > 0``, no slow lowering); the bucket ladder rounds,
pads and rejects correctly; the micro-batcher coalesces concurrent
same-(model,bucket) requests into one device dispatch; and the HTTP
front-end serves ``/v1/models`` + ``:predict`` with the documented error
codes while keeping the exporter's ``/metrics`` surface alive.
"""

from __future__ import annotations

import importlib.util
import json
import os
import socket
import subprocess
import sys
import time
import types
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from spark_rapids_ml_tpu.serving import buckets
from spark_rapids_ml_tpu.serving import client as client_mod
from spark_rapids_ml_tpu.serving import fastlane
from spark_rapids_ml_tpu.serving import hbm as hbm_mod
from spark_rapids_ml_tpu.serving import registry as registry_mod
from spark_rapids_ml_tpu.serving import server as server_mod
from spark_rapids_ml_tpu.serving.batcher import MicroBatcher
from spark_rapids_ml_tpu.telemetry import tracectx
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

BUCKETS = (8, 16, 32, 64)


@pytest.fixture(autouse=True)
def serve_clean():
    yield
    client_mod.reset_client()
    server_mod.stop_serving(stop_monitor=False)
    registry_mod.reset_for_tests()


@pytest.fixture(scope="module")
def fitted_models():
    """One dataset and two fitted models (PCA + linear) shared across the
    serving tests; registration happens per-test against a fresh registry."""
    from spark_rapids_ml_tpu.models.linear import LinearRegression
    from spark_rapids_ml_tpu.models.pca import PCA

    rng = np.random.default_rng(5)
    x = rng.normal(size=(200, 6))
    y = x @ rng.normal(size=6) + 0.5
    pca = PCA().setInputCol("features").setK(3).fit(x)
    lin = LinearRegression().fit((x, y))
    return x, pca, lin


def _get(port: int, path: str):
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30
        ) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _post(port: int, path: str, payload) -> tuple[int, dict]:
    data = json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=data,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# -- bucket ladder ----------------------------------------------------------


class TestBuckets:
    @pytest.fixture(autouse=True)
    def _ladder_env(self, monkeypatch):
        monkeypatch.setenv("TPU_ML_SERVE_MIN_BUCKET", "8")
        monkeypatch.setenv("TPU_ML_SERVE_MAX_BATCH_ROWS", "64")

    def test_serve_bucket_rounds_up_to_power_of_two(self):
        assert buckets.serve_bucket(1) == 8
        assert buckets.serve_bucket(8) == 8
        assert buckets.serve_bucket(9) == 16
        assert buckets.serve_bucket(33) == 64
        assert buckets.serve_bucket(64) == 64

    def test_empty_and_oversized_requests_rejected(self):
        with pytest.raises(ValueError, match="at least one row"):
            buckets.serve_bucket(0)
        with pytest.raises(ValueError, match="ladder cap"):
            buckets.serve_bucket(65)

    def test_ladder_enumerates_every_rung(self):
        assert buckets.bucket_ladder() == (8, 16, 32, 64)

    def test_non_power_of_two_knobs_round_up(self, monkeypatch):
        monkeypatch.setenv("TPU_ML_SERVE_MIN_BUCKET", "6")
        monkeypatch.setenv("TPU_ML_SERVE_MAX_BATCH_ROWS", "100")
        assert buckets.min_bucket() == 8
        assert buckets.max_batch_rows() == 128
        assert buckets.bucket_ladder() == (8, 16, 32, 64, 128)

    def test_pad_to_bucket_zero_fills_and_reports_true_rows(self):
        x = np.arange(12.0).reshape(3, 4)
        padded, true_rows = buckets.pad_to_bucket(x)
        assert padded.shape == (8, 4)
        assert true_rows == 3
        assert np.array_equal(padded[:3], x)
        assert not padded[3:].any()
        # exact fit returns the block untouched
        full = np.ones((8, 4))
        same, rows = buckets.pad_to_bucket(full)
        assert same is full and rows == 8
        with pytest.raises(ValueError, match="do not fit"):
            buckets.pad_to_bucket(x, bucket=2)


# -- registry: kernel extraction + eager parity -----------------------------


class TestRegistryParity:
    SIZES = (1, 3, 8, 17, 40, 60)

    def _assert_parity(self, name, model, x):
        reg = registry_mod.get_registry()
        reg.register(name, model, bucket_list=BUCKETS)
        for n in self.SIZES:
            got = reg.predict(name, x[:n])
            expected = np.asarray(model.transform(x[:n]))
            assert got.shape == expected.shape, n
            assert np.array_equal(got, expected), (
                f"serve/eager mismatch for {name} at {n} rows"
            )

    def test_pca_bitwise_parity(self, fitted_models):
        x, pca, _ = fitted_models
        self._assert_parity("pca", pca, x)

    def test_linear_bitwise_parity(self, fitted_models):
        x, _, lin = fitted_models
        self._assert_parity("linear", lin, x)

    def test_scaler_bitwise_parity(self, rng):
        from spark_rapids_ml_tpu.models.scaler import StandardScaler

        x = rng.normal(loc=3.0, scale=2.0, size=(120, 5))
        scaler = (
            StandardScaler()
            .setInputCol("features")
            .setWithMean(True)
            .setWithStd(True)
            .fit(x)
        )
        self._assert_parity("scaler", scaler, x)

    def test_forest_bitwise_parity(self, rng):
        from spark_rapids_ml_tpu.models.forest import RandomForestClassifier

        x = rng.normal(size=(150, 4))
        yc = (x[:, 0] + x[:, 1] > 0).astype(np.int64)
        forest = (
            RandomForestClassifier().setNumTrees(5).setSeed(3).fit((x, yc))
        )
        self._assert_parity("forest", forest, x)

    def test_unservable_model_raises_type_error(self):
        with pytest.raises(TypeError, match="no serve contract"):
            registry_mod.get_registry().register("bad", object())

    def test_unknown_model_raises_key_error(self):
        with pytest.raises(KeyError, match="no servable model"):
            registry_mod.get_registry().predict("ghost", [[1.0]])

    def test_describe_reports_warm_buckets(self, fitted_models):
        _, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8, 16))
        (desc,) = reg.describe()
        assert desc["name"] == "p"
        assert desc["family"] == "pca"
        assert desc["n_features"] == 6
        assert desc["buckets"] == [8, 16]

    def test_unwarmed_bucket_books_cold_compile(self, fitted_models):
        """A bucket outside the registered list still serves — but books
        serve.cold_compiles, the steady-state anomaly the report flags."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        snap = REGISTRY.snapshot()
        got = reg.predict("p", x[:9])  # rounds to 16: never AOT-compiled
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.cold_compiles") == 1
        assert np.array_equal(got, np.asarray(pca.transform(x[:9])))
        # the miss is now warm: a second hit does not re-book
        snap = REGISTRY.snapshot()
        reg.predict("p", x[:9])
        assert REGISTRY.snapshot().delta(snap).counter("serve.cold_compiles") == 0


# -- micro-batcher ----------------------------------------------------------


class TestMicroBatcher:
    def test_concurrent_requests_share_one_dispatch(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8, 16))
        batcher = MicroBatcher(reg, max_delay_s=0.2).start()
        try:
            snap = REGISTRY.snapshot()
            futures = [batcher.submit("p", x[i : i + 1]) for i in range(8)]
            outs = [f.result(timeout=30.0) for f in futures]
        finally:
            batcher.stop()
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.batches") == 1  # 8 requests, 1 dispatch
        assert delta.counter("serve.rows") == 8
        assert delta.hist("serve.queue_delay_seconds").count == 8
        expected = np.asarray(pca.transform(x[:8]))
        for i, out in enumerate(outs):
            assert np.array_equal(np.asarray(out), expected[i : i + 1])

    def test_coalescing_never_exceeds_the_warm_bucket_set(self, fitted_models):
        """Requests that would combine past the model's largest AOT-warm
        bucket split into multiple warm dispatches instead of coalescing
        into an unwarmed (cold-compiling) one."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8, 16))
        batcher = MicroBatcher(reg, max_delay_s=0.2).start()
        try:
            snap = REGISTRY.snapshot()
            # 4 x 8 rows inside one window: 32 combined would round to an
            # unwarmed 32-bucket — must dispatch as 2 x 16 instead
            futures = [batcher.submit("p", x[8 * i : 8 * i + 8]) for i in range(4)]
            for f in futures:
                f.result(timeout=30.0)
        finally:
            batcher.stop()
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.cold_compiles") == 0
        assert delta.counter("serve.batches") == 2
        assert delta.counter("serve.rows") == 32

    def test_submit_validates_before_queueing(self, fitted_models, monkeypatch):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        batcher = MicroBatcher(reg)  # not started: all paths raise at submit
        with pytest.raises(KeyError):
            batcher.submit("ghost", x[:1])
        with pytest.raises(ValueError, match="expected"):
            batcher.submit("p", np.ones((2, 4)))
        monkeypatch.setenv("TPU_ML_SERVE_MAX_BATCH_ROWS", "16")
        with pytest.raises(ValueError, match="ladder cap"):
            batcher.submit("p", np.ones((17, 6)))

    def test_stop_fans_error_to_waiting_requests(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        batcher = MicroBatcher(reg, max_delay_s=60.0).start()
        future = batcher.submit("p", x[:1])
        batcher.stop()
        with pytest.raises(RuntimeError, match="stopped"):
            future.result(timeout=5.0)


# -- HTTP front-end ---------------------------------------------------------


class TestServeHTTP:
    def test_models_listing_and_predict(self, fitted_models):
        x, pca, _ = fitted_models
        registry_mod.get_registry().register("pca_http", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        code, raw = _get(srv.port, "/v1/models")
        assert code == 200
        (desc,) = json.loads(raw)["models"]
        assert desc["name"] == "pca_http" and desc["family"] == "pca"

        code, body = _post(
            srv.port, "/v1/models/pca_http:predict", {"instances": x[:3].tolist()}
        )
        assert code == 200
        assert body["model"] == "pca_http" and body["rows"] == 3
        assert body["latency_ms"] >= 0
        expected = np.asarray(pca.transform(x[:3]))
        assert np.array_equal(
            np.asarray(body["predictions"], dtype=expected.dtype), expected
        )

    def test_error_codes(self, fitted_models, monkeypatch):
        x, pca, _ = fitted_models
        registry_mod.get_registry().register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        # unknown model with a valid body -> 404
        code, body = _post(
            srv.port, "/v1/models/ghost:predict", {"instances": [[1.0] * 6]}
        )
        assert code == 404 and "ghost" in body["error"]
        # malformed body (no instances) -> 400
        code, body = _post(srv.port, "/v1/models/p:predict", {})
        assert code == 400
        # oversized request -> 413 at admission
        monkeypatch.setenv("TPU_ML_SERVE_MAX_BATCH_ROWS", "16")
        code, body = _post(
            srv.port,
            "/v1/models/p:predict",
            {"instances": np.ones((17, 6)).tolist()},
        )
        assert code == 413 and "ladder cap" in body["error"]
        # wrong endpoint -> 404
        code, _ = _post(srv.port, "/v1/nonsense", {"instances": []})
        assert code == 404

    def test_exporter_surface_still_served(self, fitted_models):
        """The serve front-end extends the telemetry exporter: /metrics on
        the SAME port carries the serve.* series the SLO engine watches."""
        x, pca, _ = fitted_models
        registry_mod.get_registry().register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        _post(srv.port, "/v1/models/p:predict", {"instances": x[:2].tolist()})
        code, raw = _get(srv.port, "/metrics")
        assert code == 200
        text = raw.decode()
        assert "tpu_ml_serve_requests" in text
        assert "tpu_ml_serve_latency" in text


# -- the acceptance test ----------------------------------------------------


class TestWarmPathAcceptance:
    def test_zero_recompiles_and_bitwise_parity_under_concurrency(
        self, fitted_models
    ):
        """2-request warmup per (model, bucket), then 50 mixed-size
        concurrent requests across 2 models: zero new compiles (telemetry
        compile counters) and every response bitwise-equal to the eager
        transform() on the unpadded rows."""
        x, pca, lin = fitted_models
        reg = registry_mod.get_registry()
        reg.register("pca_a", pca, bucket_list=BUCKETS)
        reg.register("lin_b", lin, bucket_list=BUCKETS)
        srv = server_mod.start_serving(0, with_monitor=False)

        for name in ("pca_a", "lin_b"):
            for bucket in BUCKETS:
                for _ in range(2):
                    code, _ = _post(
                        srv.port,
                        f"/v1/models/{name}:predict",
                        {"instances": x[:bucket].tolist()},
                    )
                    assert code == 200

        snap_warm = REGISTRY.snapshot()

        sizes = (1, 2, 3, 5, 8, 12, 17, 30, 40, 60)
        requests = []
        for i in range(50):
            n = sizes[i % len(sizes)]
            name, model = ("pca_a", pca) if i % 2 == 0 else ("lin_b", lin)
            start = (i * 3) % (len(x) - n)
            requests.append((name, model, x[start : start + n]))

        def call(req):
            name, model, xs = req
            code, body = _post(
                srv.port,
                f"/v1/models/{name}:predict",
                {"instances": xs.tolist()},
            )
            return code, body, model, xs

        with ThreadPoolExecutor(max_workers=8) as pool:
            results = list(pool.map(call, requests))

        window = REGISTRY.snapshot().delta(snap_warm)
        # the hard gate: nothing compiled after warmup
        assert window.hist("compile.seconds").count == 0
        assert window.counter("serve.cold_compiles") == 0
        assert window.counter("serve.requests") >= 50
        assert window.counter("serve.errors") == 0
        assert window.hist("serve.latency").count == 50
        # every response bitwise-equal to the eager transform (JSON carries
        # float64 exactly via repr round-trip)
        for code, body, model, xs in results:
            assert code == 200
            expected = np.asarray(model.transform(xs))
            got = np.asarray(body["predictions"], dtype=expected.dtype)
            assert got.shape == expected.shape
            assert np.array_equal(got, expected)
        # the evidence blob bench rides on the ledger renders from this window
        summary = server_mod.serve_summary(window)
        assert summary["requests"] >= 50
        assert summary["cold_compiles"] == 0
        assert summary["latency"]["count"] == 50
        assert sum(summary["bucket_hits"].values()) > 0


# -- persistent compile-cache warm start (subprocess) -----------------------


_WARM_SCRIPT = """
import json
import numpy as np
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.serving import registry as serve_registry
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY

x = np.linspace(0.0, 1.0, 64 * 6).reshape(64, 6)
model = PCA().setInputCol("features").setK(3).fit(x)
snap = REGISTRY.snapshot()
serve_registry.get_registry().register("warm_pca", model, bucket_list=(8, 16))
delta = REGISTRY.snapshot().delta(snap)
lower = delta.hist("compile.lower_seconds")
print(json.dumps({
    "cache_hits": delta.counter("compile.cache_hits"),
    "cache_misses": delta.counter("compile.cache_misses"),
    "lower_max_s": float(lower.vmax) if lower.count else 0.0,
    "aot_compiles": delta.counter("serve.aot_compiles"),
}))
"""


class TestCompileCacheWarmStart:
    def test_second_process_warms_from_disk(self, tmp_path):
        """Two fresh processes register the same model against the same
        JAX_COMPILATION_CACHE_DIR: the second reports cache hits, no miss
        and no slow lowering — the registration-time compiles were loads
        (the model's fit compiled first, so the cache was in force before
        the process's first compile)."""
        cache_dir = tmp_path / "serve_cache"
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)

        def run_once():
            proc = subprocess.run(
                [sys.executable, "-c", _WARM_SCRIPT],
                capture_output=True,
                text=True,
                env=env,
                cwd=REPO,
                timeout=300,
            )
            assert proc.returncode == 0, proc.stderr
            return json.loads(proc.stdout.strip().splitlines()[-1])

        first = run_once()
        assert first["aot_compiles"] == 2
        assert first["cache_misses"] > 0, first
        cached = [p for p in cache_dir.rglob("*") if p.is_file()]
        assert cached, "registration wrote nothing to the serve cache dir"

        second = run_once()
        assert second["aot_compiles"] == 2
        assert second["cache_hits"] > 0, second
        assert second["cache_misses"] == 0, second
        # a warm start never re-lowers slowly: the AOT .lower() still runs
        # (tracing is not cached) but stays far under a cold XLA compile
        assert second["lower_max_s"] < 2.0, second


# -- serve_report CLI -------------------------------------------------------


def _load_serve_report():
    spec = importlib.util.spec_from_file_location(
        "serve_report", os.path.join(REPO, "tools", "serve_report.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _summary_blob(**over):
    blob = {
        "type": "serve_summary",
        "coalesce_window_s": 0.002,
        "requests": 50.0,
        "errors": 0.0,
        "rows": 400.0,
        "batches": 30.0,
        "aot_compiles": 8.0,
        "cold_compiles": 0.0,
        "bucket_hits": {"8": 20.0, "16": 6.0, "32": 4.0},
        "latency": {
            "count": 50, "p50": 0.004, "p90": 0.006, "p99": 0.009,
            "max": 0.012,
        },
        "queue_delay": {
            "count": 50, "p50": 0.001, "p90": 0.0015, "p99": 0.002,
            "max": 0.004,
        },
        "batch_rows": {"count": 30, "p50": 8, "p90": 16, "p99": 32, "max": 32},
    }
    blob.update(over)
    return blob


class TestServeReport:
    def _write(self, tmp_path, records):
        path = tmp_path / "perf.jsonl"
        path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
        return str(path)

    def test_clean_ledger_entry_renders_and_passes_strict(self, tmp_path, capsys):
        sr = _load_serve_report()
        path = self._write(
            tmp_path,
            [
                {"bench": "smoke", "other": 1},  # no serving evidence: ignored
                {
                    "bench": "smoke",
                    "timestamp": "2026-08-05T00:00:00Z",
                    "serving": _summary_blob(),
                    "metrics": {
                        "serve_recompiles_after_warmup": {"value": 0}
                    },
                },
            ],
        )
        assert sr.main([path, "--strict"]) == 0
        out = capsys.readouterr().out
        assert "requests/dispatch" in out
        assert "anomaly checks: ok" in out
        assert "bucket" in out and "share" in out

    def test_cold_compile_anomaly_fails_strict(self, tmp_path, capsys):
        sr = _load_serve_report()
        path = self._write(
            tmp_path, [{"serving": _summary_blob(cold_compiles=2.0)}]
        )
        assert sr.main([path]) == 0  # render-only stays green
        assert sr.main([path, "--strict"]) == 2
        assert "cold-start-compile-in-steady-state" in capsys.readouterr().out

    def test_wrapper_recompile_metric_fails_strict(self, tmp_path):
        sr = _load_serve_report()
        path = self._write(
            tmp_path,
            [{
                "serving": _summary_blob(),
                "metrics": {"serve_recompiles_after_warmup": {"value": 1}},
            }],
        )
        assert sr.main([path, "--strict"]) == 2

    def test_queue_delay_and_error_anomalies(self, tmp_path, capsys):
        sr = _load_serve_report()
        blob = _summary_blob(
            errors=3.0,
            queue_delay={
                "count": 50, "p50": 0.01, "p90": 0.02, "p99": 0.05,
                "max": 0.06,
            },
        )
        path = self._write(tmp_path, [blob])  # bare blob, no wrapper
        assert sr.main([path, "--strict"]) == 2
        out = capsys.readouterr().out
        assert "serve-errors" in out
        assert "queue-delay-above-window" in out

    def test_no_evidence_is_an_error(self, tmp_path):
        sr = _load_serve_report()
        path = self._write(tmp_path, [{"bench": "smoke"}])
        assert sr.main([path]) == 1


# -- zero-copy ingest: dtype preservation + binary wire ----------------------


class TestZeroCopyIngest:
    def test_validate_request_preserves_dtype(self):
        f32 = registry_mod.validate_request(
            np.ones((2, 6), dtype=np.float32), 6, "m"
        )
        assert f32.dtype == np.float32
        f64 = registry_mod.validate_request(np.ones((2, 6)), 6, "m")
        assert f64.dtype == np.float64
        # JSON integers/bools widen to exact float64, like the eager path
        ints = registry_mod.validate_request(
            np.ones((2, 6), dtype=np.int64), 6, "m"
        )
        assert ints.dtype == np.float64

    def test_unsupported_dtype_names_accepted_set(self):
        with pytest.raises(ValueError) as ei:
            registry_mod.validate_request(
                np.ones((2, 6), dtype=np.float16), 6, "m"
            )
        msg = str(ei.value)
        assert "float16" in msg
        assert "float32" in msg and "float64" in msg

    def test_float32_never_round_trips_through_float64(self, fitted_models):
        """The batcher queues the request block in the device dtype: a f32
        payload must reach the staging block as f32, not as a f64 copy."""
        x, _, lin = fitted_models
        reg = registry_mod.get_registry()
        entry = reg.register("lin32", lin, bucket_list=(8,))
        x32 = np.asarray(x[:3], dtype=np.float32)
        prepared = entry.prepare(
            registry_mod.validate_request(x32, entry.n_features, "lin32")
        )
        assert prepared.dtype == np.float32

    def test_binary_http_round_trip_bitwise(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        x32 = np.ascontiguousarray(x[:5], dtype="<f4")
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/models/p:predict",
            data=x32.tobytes(),
            headers={
                "Content-Type": server_mod.BINARY_CONTENT_TYPE,
                server_mod.SHAPE_HEADER: "5,6",
                "Accept": server_mod.BINARY_CONTENT_TYPE,
            },
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"] == server_mod.BINARY_CONTENT_TYPE
            shape = tuple(
                int(d) for d in r.headers[server_mod.SHAPE_HEADER].split(",")
            )
            got = np.frombuffer(r.read(), dtype="<f4").reshape(shape)
        expected = np.asarray(
            reg.predict("p", x32), dtype="<f4"
        )
        assert np.array_equal(got, expected)

    def test_binary_request_json_response(self, fitted_models):
        """No binary Accept header: a binary request still answers JSON."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        x32 = np.ascontiguousarray(x[:2], dtype="<f4")
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/models/p:predict",
            data=x32.tobytes(),
            headers={
                "Content-Type": server_mod.BINARY_CONTENT_TYPE,
                server_mod.SHAPE_HEADER: "2,6",
            },
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            body = json.loads(r.read())
        assert body["rows"] == 2
        expected = reg.predict("p", x32)
        assert np.allclose(body["predictions"], expected)

    def test_binary_payload_validation_is_400(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)

        def binary_post(data, shape_header):
            headers = {"Content-Type": server_mod.BINARY_CONTENT_TYPE}
            if shape_header is not None:
                headers[server_mod.SHAPE_HEADER] = shape_header
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/models/p:predict",
                data=data,
                headers=headers,
            )
            try:
                with urllib.request.urlopen(req, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        x32 = np.ones((2, 6), dtype="<f4")
        # byte length does not match the declared shape
        code, body = binary_post(x32.tobytes()[:-4], "2,6")
        assert code == 400 and "expected" in body["error"]
        # missing shape header
        code, body = binary_post(x32.tobytes(), None)
        assert code == 400 and server_mod.SHAPE_HEADER in body["error"]

    def test_dtype_error_body_names_accepted_dtypes(self, fitted_models):
        x, pca, _ = fitted_models
        registry_mod.get_registry().register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        code, body = _post(
            srv.port,
            "/v1/models/p:predict",
            {"instances": [["not", "a", "number", "x", "y", "z"]]},
        )
        assert code == 400
        assert "accepted dtypes" in body["error"]
        assert "float32" in body["error"] and "float64" in body["error"]


# -- UDS transport -----------------------------------------------------------


def _uds_read_exact(rf, n: int) -> bytes:
    chunks = []
    while n > 0:
        chunk = rf.read(n)
        assert chunk, "peer closed mid-frame"
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def _uds_exchange(sock, header: dict, payload: bytes = b""):
    raw = json.dumps(header).encode()
    sock.sendall(len(raw).to_bytes(4, "big") + raw + payload)
    rf = sock.makefile("rb")
    n = int.from_bytes(_uds_read_exact(rf, 4), "big")
    resp = json.loads(_uds_read_exact(rf, n))
    body = (
        _uds_read_exact(rf, int(resp["payload_bytes"]))
        if resp.get("payload_bytes")
        else b""
    )
    return resp, body


class TestUDSTransport:
    def _serve(self, tmp_path, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        path = str(tmp_path / "serve.sock")
        server_mod.start_serving(0, with_monitor=False, uds_path=path)
        return x, reg, path

    def test_json_round_trip(self, tmp_path, fitted_models):
        x, reg, path = self._serve(tmp_path, fitted_models)
        snap = REGISTRY.snapshot()
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
            resp, _ = _uds_exchange(
                s, {"model": "p", "wire": "json", "instances": x[:3].tolist()}
            )
        assert resp["ok"] and resp["code"] == 200 and resp["rows"] == 3
        expected = reg.predict("p", x[:3])
        assert np.array_equal(np.asarray(resp["predictions"]), expected)
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter(
            "serve.transport", transport="uds", wire="json"
        ) == 1
        assert delta.hist("serve.latency").count == 1

    def test_binary_round_trip_bitwise(self, tmp_path, fitted_models):
        x, reg, path = self._serve(tmp_path, fitted_models)
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
            resp, body = _uds_exchange(
                s,
                {
                    "model": "p",
                    "wire": "binary",
                    "accept": "binary",
                    "shape": [4, 6],
                    "payload_bytes": x32.nbytes,
                },
                x32.tobytes(),
            )
        assert resp["ok"] and resp["wire"] == "binary"
        got = np.frombuffer(body, dtype="<f4").reshape(resp["shape"])
        expected = np.asarray(reg.predict("p", x32), dtype="<f4")
        assert np.array_equal(got, expected)

    def test_one_connection_many_requests_and_errors(
        self, tmp_path, fitted_models
    ):
        x, _, path = self._serve(tmp_path, fitted_models)
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
            # an error frame answers without killing the connection
            resp, _ = _uds_exchange(
                s,
                {"model": "ghost", "wire": "json",
                 "instances": x[:1].tolist()},
            )
            assert not resp["ok"] and resp["code"] == 404
            resp, _ = _uds_exchange(
                s, {"model": "p", "wire": "json", "instances": x[:2].tolist()}
            )
            assert resp["ok"] and resp["rows"] == 2

    def test_stop_serving_unlinks_socket(self, tmp_path, fitted_models):
        _, _, path = self._serve(tmp_path, fitted_models)
        assert os.path.exists(path)
        server_mod.stop_serving(stop_monitor=False)
        assert not os.path.exists(path)


# -- in-process client -------------------------------------------------------


class TestInprocClient:
    def test_client_shares_server_batcher(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        srv = server_mod.start_serving(0, with_monitor=False)
        snap = REGISTRY.snapshot()
        out = client_mod.predict("p", x[:3])
        assert np.array_equal(out, reg.predict("p", x[:3]))
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter(
            "serve.transport", transport="inproc", wire="array"
        ) == 1
        # bound to the front-end's batcher, not a private one
        assert client_mod.get_client()._batcher() is srv.batcher

    def test_client_without_server_starts_private_batcher(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        client = client_mod.ServeClient()
        try:
            out = client.predict("p", x[:2])
            assert np.array_equal(out, reg.predict("p", x[:2]))
        finally:
            client.close()

    def test_client_error_books_status_code(self, fitted_models):
        x, pca, _ = fitted_models
        registry_mod.get_registry().register("p", pca, bucket_list=(8,))
        client = client_mod.ServeClient()
        snap = REGISTRY.snapshot()
        try:
            with pytest.raises(KeyError):
                client.predict("ghost", x[:1])
        finally:
            client.close()
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.errors", model="ghost", code=404) == 1


# -- continuous batching -----------------------------------------------------


class TestContinuousBatching:
    def test_full_bucket_leaves_immediately(self, fitted_models):
        """The window is a ceiling, not a tax: a full min-bucket dispatches
        without waiting out a 60 s window."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        batcher = MicroBatcher(reg, max_delay_s=60.0).start()
        try:
            out = batcher.submit("p", x[:8]).result(timeout=10.0)
        finally:
            batcher.stop()
        assert np.array_equal(out, np.asarray(pca.transform(x[:8])))

    def test_late_request_joins_in_flight_dispatch(self, fitted_models):
        """A request arriving after the batch was taken but before the
        padded block is built rides the in-flight dispatch's pad slack —
        and its result is bitwise what a solo dispatch would produce."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        batcher = MicroBatcher(reg, max_delay_s=60.0, adaptive=False)
        # worker not started: drive the take/dispatch sequence by hand so
        # the "late" arrival is deterministic
        fut_a = batcher.submit("p", x[:1])
        key = ("p", 8)
        with batcher._cond:
            taken = batcher._groups.pop(key)
        fut_b = batcher.submit("p", x[1:3])  # arrives after the take
        snap = REGISTRY.snapshot()
        batcher._dispatch(key, taken, 0.0)
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.batches") == 1
        assert delta.counter("serve.joined_in_flight", model="p") == 1
        assert delta.hist("serve.queue_delay_seconds").count == 2
        out_a = fut_a.result(timeout=5.0)
        out_b = fut_b.result(timeout=5.0)
        assert np.array_equal(out_a, np.asarray(pca.transform(x[:1])))
        assert np.array_equal(out_b, np.asarray(pca.transform(x[1:3])))

    def test_late_join_never_overflows_the_bucket(self, fitted_models):
        """Riders only join up to the chosen bucket's pad slack; the rest
        stay queued for their own window."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8, 16))
        batcher = MicroBatcher(reg, max_delay_s=60.0, adaptive=False)
        batcher.submit("p", x[:6])
        key = ("p", 8)
        with batcher._cond:
            taken = batcher._groups.pop(key)
        fut_fits = batcher.submit("p", x[6:8])    # 6+2 = 8: fits
        fut_next = batcher.submit("p", x[8:16])   # would overflow: stays
        batcher._dispatch(key, taken, 0.0)
        assert fut_fits.result(timeout=5.0).shape[0] == 2
        with batcher._cond:
            assert sum(
                p.rows for g in batcher._groups.values() for p in g
            ) == 8
        # drain the leftover so no future leaks
        with batcher._cond:
            leftover = batcher._groups.pop(("p", 8))
        batcher._dispatch(("p", 8), leftover, 0.0)
        assert fut_next.result(timeout=5.0).shape[0] == 8

    def test_adaptive_window_tracks_device_time(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        fixed = MicroBatcher(reg, max_delay_s=0.2, adaptive=False)
        assert fixed.effective_window_s("p") == 0.2
        adaptive = MicroBatcher(reg, max_delay_s=0.2, adaptive=True).start()
        try:
            # no device observation yet: the ceiling is the window
            assert adaptive.effective_window_s("p") == 0.2
            adaptive.submit("p", x[:8]).result(timeout=30.0)
            # one dispatch seeded the EWMA: the window left the ceiling
            assert adaptive.effective_window_s("p") < 0.2
            assert adaptive.effective_window_s("p") >= 25e-6
        finally:
            adaptive.stop()

    def test_adaptive_window_cuts_queue_delay_under_burst(self, fitted_models):
        """The ISSUE acceptance: under a burst that does NOT fill the
        bucket, the adaptive window drains at ~device time while the fixed
        window idles out its full ceiling — queue-delay p99 drops by well
        over 3x."""
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8, 16))
        ceiling = 0.12

        def burst(batcher):
            snap = REGISTRY.snapshot()
            with ThreadPoolExecutor(max_workers=4) as pool:
                futs = list(
                    pool.map(
                        lambda i: batcher.submit("p", x[i : i + 1]), range(4)
                    )
                )
            outs = [f.result(timeout=30.0) for f in futs]
            for i, out in enumerate(outs):
                assert np.array_equal(
                    out, np.asarray(pca.transform(x[i : i + 1]))
                )
            delta = REGISTRY.snapshot().delta(snap)
            return delta.hist("serve.queue_delay_seconds").percentile(99)

        fixed = MicroBatcher(reg, max_delay_s=ceiling, adaptive=False).start()
        try:
            p99_fixed = burst(fixed)
        finally:
            fixed.stop()

        adaptive = MicroBatcher(
            reg, max_delay_s=ceiling, adaptive=True
        ).start()
        try:
            # seed the device EWMA with one full-bucket dispatch
            adaptive.submit("p", x[:8]).result(timeout=30.0)
            p99_adaptive = burst(adaptive)
        finally:
            adaptive.stop()

        assert p99_fixed >= 0.8 * ceiling
        assert p99_adaptive < p99_fixed / 3

    def test_every_dispatch_books_effective_window(self, fitted_models):
        x, pca, _ = fitted_models
        reg = registry_mod.get_registry()
        reg.register("p", pca, bucket_list=(8,))
        batcher = MicroBatcher(reg, max_delay_s=0.01).start()
        try:
            snap = REGISTRY.snapshot()
            batcher.submit("p", x[:8]).result(timeout=30.0)
            delta = REGISTRY.snapshot().delta(snap)
            assert delta.hist(
                "serve.window_effective_seconds", model="p"
            ).count == 1
        finally:
            batcher.stop()


# -- HBM fleet manager -------------------------------------------------------


class TestHbmFleet:
    def test_lru_paging_order_counters_and_repaged_parity(
        self, fitted_models, monkeypatch
    ):
        x, _, lin = fitted_models
        reg = registry_mod.get_registry()
        e1 = reg.register("m1", lin, bucket_list=(8,))
        per_model = hbm_mod.param_bytes(e1.params)
        assert per_model > 0
        # budget fits exactly two models
        monkeypatch.setenv(
            hbm_mod.SERVE_HBM_BUDGET_BYTES_VAR, str(2 * per_model)
        )
        reg.register("m2", lin, bucket_list=(8,))
        reg.predict("m1", x[:2])  # touch m1: m2 becomes LRU
        snap = REGISTRY.snapshot()
        reg.register("m3", lin, bucket_list=(8,))
        delta = REGISTRY.snapshot().delta(snap)
        # true LRU: the un-touched m2 was evicted, not the older m1
        assert delta.counter("serve.page_out", model="m2") == 1
        assert delta.counter("serve.page_out", model="m1") == 0
        fleet = hbm_mod.get_fleet()
        stats = fleet.stats()
        assert stats["budget_bytes"] == 2 * per_model
        assert stats["resident_bytes"] == 2 * per_model
        assert not stats["models"]["m2"]["resident"]
        assert stats["models"]["m1"]["resident"]
        assert stats["models"]["m3"]["resident"]

        # predicting the paged-out model repages it (evicting the new LRU,
        # m1) and its predictions are bitwise what they were when resident
        expected = np.asarray(lin.transform(x[:3]))
        snap = REGISTRY.snapshot()
        got = reg.predict("m2", x[:3])
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.page_in", model="m2") == 1
        assert delta.counter("serve.page_out", model="m1") == 1
        assert np.array_equal(got, expected)
        stats = fleet.stats()
        assert stats["models"]["m2"]["resident"]
        assert not stats["models"]["m1"]["resident"]

    def test_no_budget_means_no_paging(self, fitted_models, monkeypatch):
        """CPU backends expose no memory stats and set no override: every
        model stays resident and nothing pages."""
        monkeypatch.delenv(
            hbm_mod.SERVE_HBM_BUDGET_BYTES_VAR, raising=False
        )
        monkeypatch.setattr(hbm_mod, "budget_bytes", lambda: None)
        x, _, lin = fitted_models
        reg = registry_mod.get_registry()
        snap = REGISTRY.snapshot()
        for name in ("a", "b", "c"):
            reg.register(name, lin, bucket_list=(8,))
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.page_out") == 0
        assert all(
            r["resident"]
            for r in hbm_mod.get_fleet().stats()["models"].values()
        )

    def test_hbm_bytes_gauge_tracks_residency(self, fitted_models, monkeypatch):
        x, _, lin = fitted_models
        reg = registry_mod.get_registry()
        e1 = reg.register("g1", lin, bucket_list=(8,))
        per_model = hbm_mod.param_bytes(e1.params)
        monkeypatch.setenv(
            hbm_mod.SERVE_HBM_BUDGET_BYTES_VAR, str(per_model)
        )
        reg.register("g2", lin, bucket_list=(8,))
        snap = REGISTRY.snapshot()
        gauge = [
            v for (n, _), v in snap.gauges.items() if n == "serve.hbm_bytes"
        ]
        assert gauge == [per_model]

    def test_shed_on_slo_burn(self, monkeypatch):
        from spark_rapids_ml_tpu.telemetry import health

        breaches = [0]
        fake = types.SimpleNamespace(
            slo=types.SimpleNamespace(total_breaches=lambda: breaches[0])
        )
        monkeypatch.setattr(health, "get_monitor", lambda: fake)
        monkeypatch.setenv("TPU_ML_ADMISSION_POLICY", "refuse")
        fleet = hbm_mod.get_fleet()
        fleet.check_admission("m")  # no burn yet: admits
        breaches[0] = 2
        snap = REGISTRY.snapshot()
        with pytest.raises(hbm_mod.ServeShed):
            fleet.check_admission("m")
        # the shed surfaces as 503 at every transport
        assert server_mod.status_for_error(hbm_mod.ServeShed("x")) == 503
        # one shed per newly observed breach: the same burn does not
        # re-shed the next request
        fleet.check_admission("m")
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.shed", model="m", policy="refuse") == 1

    def test_degrade_policy_counts_but_admits(self, monkeypatch):
        from spark_rapids_ml_tpu.telemetry import health

        fake = types.SimpleNamespace(
            slo=types.SimpleNamespace(total_breaches=lambda: 1)
        )
        monkeypatch.setattr(health, "get_monitor", lambda: fake)
        monkeypatch.setenv("TPU_ML_ADMISSION_POLICY", "degrade")
        fleet = hbm_mod.get_fleet()
        snap = REGISTRY.snapshot()
        fleet.check_admission("m")  # burns, but admits
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.shed", model="m", policy="degrade") == 1

    def test_off_policy_disables_shedding(self, monkeypatch):
        from spark_rapids_ml_tpu.telemetry import health

        fake = types.SimpleNamespace(
            slo=types.SimpleNamespace(total_breaches=lambda: 99)
        )
        monkeypatch.setattr(health, "get_monitor", lambda: fake)
        monkeypatch.setenv("TPU_ML_ADMISSION_POLICY", "off")
        snap = REGISTRY.snapshot()
        hbm_mod.get_fleet().check_admission("m")
        assert REGISTRY.snapshot().delta(snap).counter("serve.shed") == 0


# -- serve_report: fast-path additions ---------------------------------------


class TestServeReportFastPath:
    def _write(self, tmp_path, records):
        path = tmp_path / "perf.jsonl"
        path.write_text("\n".join(json.dumps(r) for r in records) + "\n")
        return str(path)

    def test_transport_mix_paging_and_window_render(self, tmp_path, capsys):
        sr = _load_serve_report()
        blob = _summary_blob(
            transport_mix={
                "http/json": 20.0, "http/binary": 10.0,
                "uds/binary": 15.0, "inproc/array": 5.0,
            },
            joined_in_flight=7.0,
            page_in=1.0,
            page_out=2.0,
            hbm_bytes=4096.0,
            adaptive_window=True,
            window_effective={
                "count": 30, "p50": 0.0004, "p90": 0.001, "p99": 0.002,
                "max": 0.002,
            },
        )
        path = self._write(tmp_path, [blob])
        assert sr.main([path, "--strict"]) == 0
        out = capsys.readouterr().out
        assert "transport/wire" in out and "uds/binary" in out
        assert "7 rider(s) joined in-flight" in out
        assert "hbm paging: 1 page-in(s), 2 page-out(s)" in out
        assert "adaptive window" in out and "ceiling" in out

    def test_page_thrash_anomaly_fails_strict(self, tmp_path, capsys):
        sr = _load_serve_report()
        blob = _summary_blob(page_in=20.0, requests=50.0)
        path = self._write(tmp_path, [blob])
        assert sr.main([path, "--strict"]) == 2
        assert "page-thrash" in capsys.readouterr().out

    def test_window_never_adapts_anomaly(self, tmp_path, capsys):
        sr = _load_serve_report()
        blob = _summary_blob(
            adaptive_window=True,
            window_effective={
                "count": 12, "p50": 0.002, "p90": 0.002, "p99": 0.002,
                "max": 0.002,
            },
        )
        path = self._write(tmp_path, [blob])
        assert sr.main([path, "--strict"]) == 2
        assert "window-never-adapts" in capsys.readouterr().out

    def test_sparse_window_traffic_is_not_an_anomaly(self, tmp_path):
        """Too few dispatches to judge adaptation: no anomaly."""
        sr = _load_serve_report()
        blob = _summary_blob(
            adaptive_window=True,
            window_effective={
                "count": 4, "p50": 0.002, "p90": 0.002, "p99": 0.002,
                "max": 0.002,
            },
        )
        path = self._write(tmp_path, [blob])
        assert sr.main([path, "--strict"]) == 0


# -- fast lane: JSON-free dispatch -------------------------------------------


class TestFastlaneProtocol:
    def test_request_round_trip_zero_copy(self):
        x = np.arange(12, dtype="<f4").reshape(4, 3)
        frame = fastlane.pack_request("m", x)
        assert fastlane.is_fastlane_head(frame[:4])
        buf = memoryview(frame[4:])
        pos = [0]

        def read_exact(n):
            out = buf[pos[0]:pos[0] + n]
            pos[0] += n
            return out

        model, mat, is_query, trace = fastlane.read_request(read_exact)
        assert model == "m" and not is_query
        assert trace is None  # all-zero trace tail = untraced request
        assert np.array_equal(mat, x) and mat.dtype == np.dtype("<f4")

    def test_peek_matches_read(self):
        x = np.zeros((8, 5), dtype="<f4")
        frame = fastlane.pack_request("abc", x)
        struct_raw = frame[4:4 + fastlane.request_struct_size()]
        assert fastlane.peek_request(struct_raw) == (3, 8, 5)

    def test_trace_context_rides_the_struct(self):
        """v2 wire: a packed trace context round-trips through the binary
        request struct — no JSON anywhere on the path."""
        x = np.zeros((2, 3), dtype="<f4")
        ctx = tracectx.TraceContext(
            trace_id=0x1122334455667788, span_id=0x9ABCDEF0,
            origin_us=123456789,
        )
        frame = fastlane.pack_request("m", x, trace=ctx)
        buf, pos = memoryview(frame[4:]), [0]

        def read_exact(n):
            out = buf[pos[0]:pos[0] + n]
            pos[0] += n
            return out

        model, _mat, _q, got = fastlane.read_request(read_exact)
        assert model == "m" and got == ctx

    def test_peek_and_rewrite_trace_are_byte_surgery(self):
        """The router's relay path peeks the inbound context and rewrites
        its own child span id into the forwarded struct without touching
        name or payload bytes."""
        x = np.zeros((8, 5), dtype="<f4")
        parent = tracectx.TraceContext(
            trace_id=0xDEAD, span_id=0xBEEF, origin_us=42,
        )
        frame = fastlane.pack_request("abc", x, trace=parent)
        struct_raw = bytes(frame[4:4 + fastlane.request_struct_size()])
        assert fastlane.peek_trace(struct_raw) == parent
        # rows/cols/name_len untouched by the trace tail
        assert fastlane.peek_request(struct_raw) == (3, 8, 5)
        child = parent.child()
        rewritten = fastlane.rewrite_trace(struct_raw, child)
        assert len(rewritten) == len(struct_raw)
        assert fastlane.peek_trace(rewritten) == child
        assert fastlane.peek_request(rewritten) == (3, 8, 5)
        # untraced peek: all-zero tail reads back as None
        bare = bytes(fastlane.pack_request("abc", x)[
            4:4 + fastlane.request_struct_size()
        ])
        assert fastlane.peek_trace(bare) is None

    def test_error_frame_raises_with_status(self):
        frame = fastlane.pack_error_response(404, "model 'x' not found")
        buf, pos = memoryview(frame), [0]

        def read_exact(n):
            out = buf[pos[0]:pos[0] + n]
            pos[0] += n
            return bytes(out)

        with pytest.raises(fastlane.FastlaneError) as e:
            fastlane.read_response(read_exact)
        assert e.value.status == 404 and "not found" in e.value.message

    def test_magic_unreachable_as_json_header_length(self):
        # the discriminator rides in place of the 4-byte header length;
        # a real JSON header can never be ~4.1 GB long
        assert fastlane.FASTLANE_MAGIC > 2**31

    def test_response_pool_recycles_buffers(self):
        pool = fastlane.ResponseBufferPool()
        with pool.lease("m", 8, 64) as view:
            first = view.obj
            assert len(view) == 64
        with pool.lease("m", 8, 64) as view:
            assert view.obj is first  # recycled, not reallocated
        with pool.lease("m", 8, 32) as view:
            assert view.obj is first and len(view) == 32  # shrunk lease
        stats = pool.stats()
        assert stats == {"leases": 3, "allocations": 1, "keys": 1}

    def test_fill_f32_casts_into_leased_buffer(self):
        pool = fastlane.ResponseBufferPool()
        out = np.arange(6, dtype=np.float64).reshape(3, 2)
        with pool.lease("m", 8, out.size * 4) as view:
            rows, cols = fastlane.fill_f32(view, out)
            assert (rows, cols) == (3, 2)
            got = np.frombuffer(view, dtype="<f4").reshape(3, 2)
            assert np.array_equal(got, out.astype("<f4"))


class TestFastlaneE2E:
    def _serve(self, tmp_path, fitted_models):
        x, _, lin = fitted_models
        reg = registry_mod.get_registry()
        reg.register("lin", lin, bucket_list=(8,))
        path = str(tmp_path / "serve.sock")
        server_mod.start_serving(0, with_monitor=False, uds_path=path)
        return x, reg, path

    def test_zero_json_on_hot_path_and_bitwise_parity(
        self, tmp_path, fitted_models
    ):
        """The fast lane books ZERO serve.json_codec activity (the counted
        codec proves the no-dict-churn claim) and its f32 payload is
        bitwise identical to the JSON lane's predictions for the same
        f32-representable request (linear model: identity prepare, so
        both lanes run the exact same f32 kernel)."""
        x, reg, path = self._serve(tmp_path, fitted_models)
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
            rf = s.makefile("rb")
            snap = REGISTRY.snapshot()
            s.sendall(fastlane.pack_request("lin", x32))
            fast_out = fastlane.read_response(
                lambda n: _uds_read_exact(rf, n)
            )
            delta = REGISTRY.snapshot().delta(snap)
            assert delta.counter("serve.json_codec") == 0
            assert delta.counter(
                "serve.transport", transport="uds", wire="fast"
            ) == 1
            assert delta.hist(
                "serve.latency", transport="uds", wire="fast"
            ).count == 1

            # same request on the JSON lane of the same connection
            resp, _ = _uds_exchange(
                s,
                {"model": "lin", "wire": "json",
                 "instances": x32.tolist()},
            )
        assert resp["ok"]
        json_out = np.asarray(resp["predictions"], dtype="<f4")
        assert fast_out.tobytes() == json_out.reshape(fast_out.shape).tobytes()
        # ...and the JSON lane DID run the counted codec
        post = REGISTRY.snapshot().delta(snap)
        assert post.counter("serve.json_codec", op="decode") >= 1
        assert post.counter("serve.json_codec", op="encode") >= 1

    def test_fastlane_pooled_response_buffers_recycle(
        self, tmp_path, fitted_models
    ):
        x, _, path = self._serve(tmp_path, fitted_models)
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        before = fastlane.RESPONSE_POOL.stats()
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
            rf = s.makefile("rb")
            for _ in range(5):
                s.sendall(fastlane.pack_request("lin", x32))
                fastlane.read_response(lambda n: _uds_read_exact(rf, n))
        after = fastlane.RESPONSE_POOL.stats()
        assert after["leases"] - before["leases"] == 5
        # steady state allocates at most once for this (model, bucket)
        assert after["allocations"] - before["allocations"] <= 1

    def test_error_frame_keeps_connection_alive(
        self, tmp_path, fitted_models
    ):
        x, _, path = self._serve(tmp_path, fitted_models)
        x32 = np.ascontiguousarray(x[:2], dtype="<f4")
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(path)
            rf = s.makefile("rb")
            s.sendall(fastlane.pack_request("ghost", x32))
            with pytest.raises(fastlane.FastlaneError) as e:
                fastlane.read_response(lambda n: _uds_read_exact(rf, n))
            assert e.value.status == 404
            # the connection survives the error frame
            s.sendall(fastlane.pack_request("lin", x32))
            out = fastlane.read_response(lambda n: _uds_read_exact(rf, n))
        assert out.shape[0] == 2


# -- deterministic teardown (no leaked threads / sockets) --------------------


def _serve_threads() -> list[str]:
    import threading as _threading

    return sorted(
        t.name for t in _threading.enumerate()
        if t.name.startswith(("tpu-ml-serve", "tpu-ml-fleet"))
    )


class TestTeardownLeak:
    def test_repeated_start_stop_cycles_leak_nothing(
        self, tmp_path, fitted_models
    ):
        """stop_serving/reset_client must deterministically join every
        worker thread and unlink the UDS socket: after each of several
        start/serve/stop cycles the process has zero tpu-ml serve threads
        and no stray socket file."""
        x, _, lin = fitted_models
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        for cycle in range(3):
            reg = registry_mod.get_registry()
            if "lin" not in {d["name"] for d in reg.describe()}:
                reg.register("lin", lin, bucket_list=(8,))
            path = str(tmp_path / f"serve-{cycle}.sock")
            server_mod.start_serving(0, with_monitor=False, uds_path=path)
            with socket.socket(socket.AF_UNIX) as s:
                s.connect(path)
                rf = s.makefile("rb")
                s.sendall(fastlane.pack_request("lin", x32))
                fastlane.read_response(lambda n: _uds_read_exact(rf, n))
            client_mod.predict("lin", x32)
            server_mod.stop_serving(stop_monitor=False)
            client_mod.reset_client()
            assert _serve_threads() == [], (
                f"cycle {cycle} leaked threads: {_serve_threads()}"
            )
            assert not os.path.exists(path), (
                f"cycle {cycle} left the UDS socket behind"
            )

    def test_private_client_batcher_joins_on_reset(self, fitted_models):
        _, _, lin = fitted_models
        reg = registry_mod.get_registry()
        reg.register("lin", lin, bucket_list=(8,))
        # no server running: the client lazily starts a private batcher
        out = client_mod.predict("lin", np.zeros((2, 6), dtype="<f4"))
        assert out.shape[0] == 2
        assert "tpu-ml-serve-batcher" in _serve_threads()
        client_mod.reset_client()
        assert _serve_threads() == []


# -- tail-aware hedged dispatch ----------------------------------------------


class TestHedgedDispatch:
    def test_hedge_fires_past_threshold_and_first_result_wins(
        self, fitted_models, monkeypatch
    ):
        """A stalled primary dispatch past the hedge threshold re-issues
        the batch; the hedge's result answers the request and the
        telemetry books the hedge + the winner (the loser's device time
        never reaches the adaptive-window EWMA)."""
        _, _, lin = fitted_models
        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "1.5")
        monkeypatch.setenv("TPU_ML_SERVE_HEDGE_FLOOR_US", "1000")
        reg = registry_mod.get_registry()
        reg.register("lin", lin, bucket_list=(8,))
        mb = MicroBatcher(reg).start()
        try:
            x32 = np.ascontiguousarray(
                np.linspace(0.0, 1.0, 12).reshape(2, 6), dtype="<f4"
            )
            # seed the device-time EWMA (no hedging while it is unknown:
            # "never hedge blind")
            expected = mb.submit("lin", x32).result(timeout=30)

            real_dispatch = reg.dispatch_padded
            stalls = iter([0.4])

            def stalling_dispatch(entry, padded, bucket):
                delay = next(stalls, 0.0)
                if delay:
                    time.sleep(delay)
                return real_dispatch(entry, padded, bucket)

            monkeypatch.setattr(reg, "dispatch_padded", stalling_dispatch)
            snap = REGISTRY.snapshot()
            out = mb.submit("lin", x32).result(timeout=30)
            delta = REGISTRY.snapshot().delta(snap)
            assert np.array_equal(np.asarray(out), np.asarray(expected))
            assert delta.counter("serve.hedges", model="lin") == 1
            assert delta.counter(
                "serve.hedge_wins", model="lin", winner="hedge"
            ) == 1
        finally:
            mb.stop()

    def test_no_hedge_without_observed_device_time(
        self, fitted_models, monkeypatch
    ):
        from spark_rapids_ml_tpu.resilience import supervisor

        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "2.0")
        # observed == 0 -> never hedge blind
        assert supervisor.hedge_threshold_s(0.0, floor_s=0.001) is None
        # factor <= 0 -> hedging disabled outright
        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "0")
        assert supervisor.hedge_threshold_s(0.5, floor_s=0.001) is None

    def test_threshold_respects_serve_floor(self, monkeypatch):
        from spark_rapids_ml_tpu.resilience import supervisor
        from spark_rapids_ml_tpu.serving import batcher as batcher_mod

        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "2.0")
        monkeypatch.setenv("TPU_ML_SERVE_HEDGE_FLOOR_US", "5000")
        floor = batcher_mod.serve_hedge_floor_s()
        assert floor == pytest.approx(0.005)
        # tiny observed latency: the floor wins (no microsecond hedges)
        assert supervisor.hedge_threshold_s(
            1e-5, floor_s=floor
        ) == pytest.approx(0.005)
        # big observed latency: factor x observed wins
        assert supervisor.hedge_threshold_s(
            0.1, floor_s=floor
        ) == pytest.approx(0.2)


# -- hot-swap under concurrent load (ISSUE-18) -------------------------------


class TestSwapUnderConcurrentLoad:
    def test_every_response_is_bitwise_one_version(self, fitted_models):
        """Hammer the registry from worker threads while the main thread
        hot-swaps the model: zero errors, and every single response is
        bitwise-identical to exactly one version's eager ``transform()``
        — in-flight dispatches finish on the old kernel, new admissions
        land on the new one, nothing ever serves a torn mix."""
        from spark_rapids_ml_tpu.models.linear import LinearRegression

        x, _, _ = fitted_models
        rng = np.random.default_rng(13)
        y = x @ rng.normal(size=6) + 0.25
        old = LinearRegression().fit((x, y))
        new = LinearRegression().fit((x, -y))
        reg = registry_mod.get_registry()
        reg.register("hot", old, bucket_list=(8, 16))
        probe = x[:8]
        want_old = np.asarray(old.transform(probe))
        want_new = np.asarray(new.transform(probe))
        assert not np.array_equal(want_old, want_new)

        stop = False
        errors: list[Exception] = []
        outs: list[np.ndarray] = []

        def hammer():
            while not stop:
                try:
                    outs.append(reg.predict("hot", probe))
                except Exception as e:  # noqa: BLE001 — asserted empty
                    errors.append(e)
                    return

        import threading

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            time.sleep(0.1)  # guaranteed pre-swap traffic
            entry = reg.swap(
                "hot", new, shadow_sample=probe, tolerance=100.0
            )
            assert entry.version == 2
            time.sleep(0.1)  # guaranteed post-swap traffic
        finally:
            stop = True
            for t in threads:
                t.join(timeout=30)
        assert not errors, f"requests failed during swap: {errors[:3]}"
        n_old = sum(1 for o in outs if np.array_equal(o, want_old))
        n_new = sum(1 for o in outs if np.array_equal(o, want_new))
        assert n_old + n_new == len(outs), (
            "a response matched neither version bitwise — torn swap"
        )
        assert n_old > 0 and n_new > 0
        # post-swap steady state: the new version, bitwise, every time
        assert np.array_equal(reg.predict("hot", probe), want_new)
