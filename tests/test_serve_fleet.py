"""Multi-process serve fleet: replicas, consistent-hash router, rolling
restart.

Covers the scale-out half of the serve tail hunt: the model spec round-trips
fitted models into replica processes bitwise; the ``HashRing`` is
deterministic across processes and spreads keys over slots; ``ServeFleet``
spawns supervised replica servers behind one router socket that relays both
the JSON UDS wire and the fast lane verbatim; consistent routing pins a
``(model, bucket)`` key to its home replica (``serve.route_hits``) until
drain/death/saturation walks the ring; and a rolling drain/restart under
live load completes with ZERO failed requests while the respawned replica
re-AOTs entirely from the shared persistent compile cache
(``cache_misses == 0`` in its shutdown report).

The observability-plane tests pin the fleet aggregation contracts: the
STATS scrape frame, the merged registry whose replica-label partition
reproduces each replica's registry exactly, the exporter endpoints
(``/metrics``, ``/healthz``, ``/traces``, ``/traces/<id>``), and the
telemetry-trailer flush on supervised teardown.

Replica processes inherit ``JAX_PLATFORMS=cpu`` from the session env; the
fleet tests keep the bucket list minimal (one rung) so each replica's AOT
warmup is two executables, not the full ladder.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from spark_rapids_ml_tpu.serving import fastlane
from spark_rapids_ml_tpu.serving import fleet as fleet_mod
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def fitted_models():
    from spark_rapids_ml_tpu.models.linear import LinearRegression
    from spark_rapids_ml_tpu.models.pca import PCA

    rng = np.random.default_rng(11)
    x = rng.normal(size=(200, 6))
    y = x @ rng.normal(size=6) + 0.5
    pca = PCA().setInputCol("features").setK(3).fit(x)
    lin = LinearRegression().fit((x, y))
    return x, pca, lin


@pytest.fixture(scope="module")
def live_fleet(fitted_models, tmp_path_factory):
    """One 2-replica fleet shared by the e2e tests (replica spawn is the
    expensive part; every test gets its own connections)."""
    x, pca, lin = fitted_models
    cache_dir = str(tmp_path_factory.mktemp("fleet_cache"))
    fleet = fleet_mod.ServeFleet(
        {"pca": pca, "lin": lin},
        replicas=2,
        socket_dir=str(tmp_path_factory.mktemp("fleet_sock")),
        bucket_list=(8,),
        extra_env={"JAX_COMPILATION_CACHE_DIR": cache_dir},
    ).start()
    yield x, fleet
    fleet.stop()


def _read_exact(rf, n: int) -> bytes:
    chunks = []
    while n > 0:
        chunk = rf.read(n)
        assert chunk, "peer closed mid-frame"
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def _fast_call(sock, rf, model: str, x32: np.ndarray) -> np.ndarray:
    sock.sendall(fastlane.pack_request(model, x32))
    return fastlane.read_response(lambda n: _read_exact(rf, n))


def _json_call(sock, rf, model: str, rows: np.ndarray):
    header = json.dumps(
        {"model": model, "wire": "json", "instances": rows.tolist()}
    ).encode()
    sock.sendall(len(header).to_bytes(4, "big") + header)
    n = int.from_bytes(_read_exact(rf, 4), "big")
    resp = json.loads(_read_exact(rf, n))
    if resp.get("payload_bytes"):
        _read_exact(rf, int(resp["payload_bytes"]))
    return resp


# -- model spec --------------------------------------------------------------


class TestModelSpec:
    def test_round_trip_preserves_predictions(
        self, fitted_models, tmp_path
    ):
        x, pca, lin = fitted_models
        path = str(tmp_path / "spec.npz")
        param_bytes = fleet_mod.write_spec(path, {"p": pca, "l": lin})
        assert set(param_bytes) == {"p", "l"}
        assert all(v > 0 for v in param_bytes.values())
        loaded = fleet_mod.load_spec(path)
        assert np.array_equal(
            np.asarray(loaded["p"].transform(x[:16])),
            np.asarray(pca.transform(x[:16])),
        )
        assert np.array_equal(
            np.asarray(loaded["l"].transform(x[:16])),
            np.asarray(lin.transform(x[:16])),
        )

    def test_unservable_model_is_a_type_error(self, tmp_path):
        with pytest.raises(TypeError, match="no fleet spec"):
            fleet_mod.write_spec(str(tmp_path / "bad.npz"), {"x": object()})

    def test_plan_placement_checks_budget(self):
        plan = fleet_mod.plan_placement(
            {"a": 1000, "b": 2000}, 2, budget_bytes=4000
        )
        assert plan["fits"] and plan["param_bytes_per_replica"] == 3000
        over = fleet_mod.plan_placement(
            {"a": 3000, "b": 2000}, 2, budget_bytes=4000
        )
        assert not over["fits"]
        # no budget (CPU hosts): everything fits
        assert fleet_mod.plan_placement(
            {"a": 10**12}, 1, budget_bytes=None
        )["fits"]


# -- consistent-hash ring ----------------------------------------------------


class TestHashRing:
    def test_deterministic_across_instances(self):
        a = fleet_mod.HashRing([0, 1, 2])
        b = fleet_mod.HashRing([0, 1, 2])
        for model in ("m1", "m2", "m3"):
            for bucket in (8, 16, 32):
                key = fleet_mod.HashRing.key(model, bucket)
                assert a.preference(key) == b.preference(key)

    def test_preference_walks_every_slot_once(self):
        ring = fleet_mod.HashRing([0, 1, 2, 3])
        prefs = ring.preference("m/8")
        assert sorted(prefs) == [0, 1, 2, 3]

    def test_keys_spread_over_slots(self):
        ring = fleet_mod.HashRing([0, 1, 2, 3])
        homes = {
            ring.preference(fleet_mod.HashRing.key(f"model{i}", 8))[0]
            for i in range(64)
        }
        # 64 keys over 4 slots with 32 vnodes each: every slot is home
        # to at least one key
        assert homes == {0, 1, 2, 3}

    def test_removing_a_slot_only_moves_its_keys(self):
        full = fleet_mod.HashRing([0, 1, 2])
        keys = [fleet_mod.HashRing.key(f"m{i}", 8) for i in range(48)]
        homes_full = {k: full.preference(k)[0] for k in keys}
        reduced = fleet_mod.HashRing([0, 1])
        for k in keys:
            if homes_full[k] != 2:
                # keys not homed on the removed slot stay put — the
                # consistent-hash property that keeps replica caches warm
                # across fleet resizes
                assert reduced.preference(k)[0] == homes_full[k]


# -- fleet observability plane -----------------------------------------------


class TestFleetObservability:
    """The unified observability plane over a live fleet: per-replica
    STATS scrapes, the merged fleet registry whose replica-label
    partition reproduces each replica's registry exactly, the exporter's
    ``/metrics`` / ``/healthz`` / ``/traces`` endpoints, and the trailer
    flush that keeps a restarted incarnation's telemetry in the fleet
    totals."""

    @staticmethod
    def _drive(fleet, x, n_fast: int = 4, n_json: int = 2) -> None:
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(fleet.router_path)
            rf = s.makefile("rb")
            for _ in range(n_fast):
                _fast_call(s, rf, "pca", x32)
            for _ in range(n_json):
                assert _json_call(s, rf, "lin", x32)["ok"]

    @staticmethod
    def _scrape_snapshot(fleet, slot: int):
        st = fleet.scrape_stats(slot)
        assert st is not None, f"replica {slot} not scrapable"
        reg = MetricsRegistry()
        reg.merge_wire(st["registry"])
        return st, reg.snapshot()

    @staticmethod
    def _series_by_replica(snap, name: str) -> dict:
        out: dict = {}
        for (n, labels), v in snap.counters.items():
            if n == name:
                rep = dict(labels).get("replica", "")
                out[rep] = out.get(rep, 0) + v
        return out

    def test_stats_frame_scrapes_registry_and_events(self, live_fleet):
        x, fleet = live_fleet
        self._drive(fleet, x)
        total = 0.0
        for slot in (0, 1):
            st, snap = self._scrape_snapshot(fleet, slot)
            assert st["ok"] and st["kind"] == "stats"
            assert st["pid"] > 0 and st["seq"] >= 0 and st["mono_us"] > 0
            assert isinstance(st["events"], list)
            total += snap.counter("serve.requests")
        # between them the two replica registries cover the traffic
        assert total >= 6
        offsets = fleet.stats()["clock_offsets_us"]
        assert sorted(offsets) == ["0", "1"]
        assert all(isinstance(v, int) for v in offsets.values())

    def test_fleet_metrics_are_the_sum_of_replica_registries(
        self, live_fleet
    ):
        """The ``/metrics`` contract: the merged fleet registry's total
        for any serve family equals the sum of the per-replica registries
        (live scrapes plus harvested final fragments), and the replica
        label partitions the merged registry back into exactly those
        per-replica values."""
        x, fleet = live_fleet
        self._drive(fleet, x)
        per_slot = {
            str(slot): self._scrape_snapshot(fleet, slot)[1]
            for slot in (0, 1)
        }
        harvested = fleet._final_registry.snapshot()
        merged = fleet.fleet_registry(include_router=False).snapshot()
        for name in ("serve.requests", "serve.rows", "serve.batches"):
            assert merged.counter(name) == pytest.approx(
                sum(s.counter(name) for s in per_slot.values())
                + harvested.counter(name)
            ), f"fleet total for {name} is not the sum of its replicas"
        merged_by_rep = self._series_by_replica(merged, "serve.requests")
        harv_by_rep = self._series_by_replica(harvested, "serve.requests")
        for slot, snap in per_slot.items():
            assert merged_by_rep.get(slot, 0) == pytest.approx(
                snap.counter("serve.requests") + harv_by_rep.get(slot, 0)
            )
        # the router's own registry joins under replica="router"
        full = fleet.fleet_registry().snapshot()
        hits = self._series_by_replica(full, "serve.route_hits")
        assert hits.get("router", 0) > 0

    def test_exporter_unified_observability_plane(self, live_fleet):
        x, fleet = live_fleet
        self._drive(fleet, x, n_fast=3, n_json=1)
        ex = fleet.start_exporter()
        assert fleet.start_exporter() is ex  # idempotent
        body = urllib.request.urlopen(
            ex.url("/metrics"), timeout=10
        ).read().decode()
        assert "# TYPE tpu_ml_serve_requests counter" in body
        assert 'replica="0"' in body and 'replica="1"' in body
        assert 'replica="router"' in body
        health = json.loads(
            urllib.request.urlopen(ex.url("/healthz"), timeout=10).read()
        )
        assert health["status"] == "ok"
        assert health["components"]["router"] == "ok"
        assert health["components"]["replica-0"] == "ok"
        cov = json.loads(
            urllib.request.urlopen(ex.url("/traces"), timeout=10).read()
        )
        assert cov["traces"] >= 1 and "coverage" in cov
        # one stitched cross-process tree: the last relayed request
        relays = [
            e for e in fleet.fleet_events()
            if e.get("name") == "serve.relay"
        ]
        assert relays, "router recorded no relay spans"
        tid = (relays[-1].get("args") or {}).get("trace_id")
        assert tid
        tree = json.loads(
            urllib.request.urlopen(
                ex.url(f"/traces/{tid}"), timeout=10
            ).read()
        )
        assert tree["trace_id"] == tid and tree["complete"]
        assert len(tree["roots"]) == 1
        root = tree["roots"][0]
        assert root["name"] == "serve.relay"
        child_names = {c["name"] for c in root["children"]}
        assert "serve.request" in child_names
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                ex.url("/traces/ffffffffffffffff"), timeout=10
            )
        assert err.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(ex.url("/nope"), timeout=10)
        assert err.value.code == 404
        # worst-of rollup: one draining replica degrades the fleet
        assert fleet.drain(1)
        try:
            health = json.loads(
                urllib.request.urlopen(
                    ex.url("/healthz"), timeout=10
                ).read()
            )
            assert health["status"] == "degraded"
            assert health["components"]["replica-1"] == "draining"
        finally:
            fleet.undrain(1)

    def test_supervised_teardown_flushes_the_telemetry_trailer(
        self, live_fleet
    ):
        """A restarted replica's final registry + flight-recorder
        fragment must land in the fleet plane: the incarnation's
        telemetry survives the process."""
        x, fleet = live_fleet
        self._drive(fleet, x)
        # pick the slot that served the most requests this incarnation
        victim, served = 0, -1.0
        for slot in (0, 1):
            n = self._scrape_snapshot(fleet, slot)[1].counter(
                "serve.requests"
            )
            if n > served:
                victim, served = slot, n
        assert served > 0
        old_pid = fleet._supervisor._slots[victim].worker.proc.pid
        before = fleet._final_registry.snapshot().counter("serve.requests")
        assert fleet.restart_replica(victim), "respawn never became READY"
        assert (victim, old_pid) in fleet._harvested
        harvested = fleet._final_registry.snapshot()
        assert harvested.counter("serve.requests") - before >= served
        # the dead incarnation's events ride the merged stream,
        # replica-stamped for the fleet trace merge
        ev = [
            e for e in fleet.fleet_events() if e.get("pid") == old_pid
        ]
        assert ev and all(
            (e.get("args") or {}).get("replica") == str(victim)
            for e in ev
        )
        # and the merged fleet registry still covers it
        merged = fleet.fleet_registry(include_router=False).snapshot()
        assert merged.counter("serve.requests") >= served


# -- fleet end-to-end --------------------------------------------------------


class TestFleetE2E:
    def test_both_wires_relay_with_parity(self, live_fleet):
        """The router relays the fast lane and the JSON lane verbatim;
        both lanes answer bitwise-identically for the same request (the
        home replica serves both, so this also proves the relay does not
        corrupt frames)."""
        x, fleet = live_fleet
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(fleet.router_path)
            rf = s.makefile("rb")
            fast_out = _fast_call(s, rf, "lin", x32)
            resp = _json_call(s, rf, "lin", x32)
        assert resp["ok"] and resp["rows"] == 4
        json_out = np.asarray(resp["predictions"], dtype="<f4")
        assert fast_out.tobytes() == json_out.reshape(
            fast_out.shape
        ).tobytes()

    def test_consistent_routing_books_home_hits(self, live_fleet):
        """Sequential traffic for one (model, bucket) key always lands on
        its home replica: all hits, zero misses."""
        x, fleet = live_fleet
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        snap = REGISTRY.snapshot()
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(fleet.router_path)
            rf = s.makefile("rb")
            for _ in range(6):
                _fast_call(s, rf, "pca", x32)
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.route_hits", model="pca") == 6
        assert delta.counter("serve.route_misses", model="pca") == 0

    def test_error_relays_without_killing_connection(self, live_fleet):
        x, fleet = live_fleet
        x32 = np.ascontiguousarray(x[:2], dtype="<f4")
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(fleet.router_path)
            rf = s.makefile("rb")
            with pytest.raises(fastlane.FastlaneError) as e:
                _fast_call(s, rf, "ghost", x32)
            assert e.value.status == 404
            out = _fast_call(s, rf, "lin", x32)
        assert out.shape[0] == 2

    def test_stats_and_gauge(self, live_fleet):
        _, fleet = live_fleet
        stats = fleet.stats()
        assert stats["replicas"] == 2
        assert stats["live_replicas"] == 2
        assert stats["placement"]["fits"]
        assert sorted(stats["in_flight"]) == ["0", "1"]

    def test_rolling_restart_under_live_load_zero_failures(
        self, live_fleet
    ):
        """The headline operational contract: drain + respawn one replica
        while a client hammers the router — zero failed requests, and the
        respawned replica's shutdown report shows it re-AOT'd entirely
        from the shared persistent compile cache (cache_misses == 0)."""
        x, fleet = live_fleet
        x32 = np.ascontiguousarray(x[:4], dtype="<f4")
        stop = threading.Event()
        failures: list[Exception] = []
        completed = [0]

        def hammer():
            with socket.socket(socket.AF_UNIX) as s:
                s.connect(fleet.router_path)
                rf = s.makefile("rb")
                while not stop.is_set():
                    try:
                        _fast_call(s, rf, "lin", x32)
                        resp = _json_call(s, rf, "pca", x32)
                        assert resp["ok"]
                        completed[0] += 2
                    except Exception as e:  # noqa: BLE001 — collected
                        # and asserted empty below
                        failures.append(e)
                        return

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            snap = REGISTRY.snapshot()
            for slot in (0, 1):
                assert fleet.restart_replica(slot), (
                    f"replica {slot} respawn never became READY"
                )
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        assert not failures, f"requests failed during rolling restart: {failures[:3]}"
        assert completed[0] > 0
        delta = REGISTRY.snapshot().delta(snap)
        assert delta.counter("serve.drain_events") == 2
        assert delta.counter("serve.replica_restarts") == 2
        # both live replicas are now respawns; traffic still flows
        with socket.socket(socket.AF_UNIX) as s:
            s.connect(fleet.router_path)
            rf = s.makefile("rb")
            out = _fast_call(s, rf, "lin", x32)
        assert out.shape == (4, 1)
        # the warm-respawn proof: stop the fleet and read each replica's
        # shutdown report — every compile on the respawned replicas was a
        # persistent-cache load, zero fresh XLA compiles after restart
        workers = [fleet._supervisor._slots[s].worker for s in (0, 1)]
        fleet.stop()
        for w in workers:
            assert w is not None and w.cache_misses == 0, (
                f"respawned replica paid {w and w.cache_misses} fresh "
                "compile(s); expected a fully warm AOT-cache respawn"
            )
            assert w.cache_hits and w.cache_hits > 0
