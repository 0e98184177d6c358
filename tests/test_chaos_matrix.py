"""Chaos fault-matrix: every injected fault class against the streamed fit,
asserting BOTH recovery (parity with the clean result) and the telemetry
trail (injection + recovery counters). Deterministic — the TPU_ML_FAULT_PLAN
nth-occurrence grammar always fails the same call — so these run in tier-1,
not behind the slow marker.

Matrix: device OOM (chunk bisection), transient I/O (retry-in-place), hang
(bounded fold.wait + FoldHangTimeout diagnosis), preemption (durable
checkpoint + bitwise resume), non-finite rows (raise/skip policy),
collective blips (finalize retry), device-init failure (raises).
"""

import os

import jax
import numpy as np
import pytest

from spark_rapids_ml_tpu.localspark import LocalSparkSession
from spark_rapids_ml_tpu.localspark import types as LT
from spark_rapids_ml_tpu.models.linear import LinearRegression
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.resilience import faults
from spark_rapids_ml_tpu.resilience import retry as R
from spark_rapids_ml_tpu.spark import ingest
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.utils.checkpoint import TrainingCheckpointer
from spark_rapids_ml_tpu.utils.config import get_config, set_config

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def clean_faults(monkeypatch):
    """No plan leaks in (from the env) or out (to later tests)."""
    monkeypatch.delenv(faults.FAULT_PLAN_VAR, raising=False)
    faults.reset_faults()
    yield
    faults.reset_faults()


@pytest.fixture
def snap():
    """Telemetry delta for the test body: ``snap.delta()`` -> counters."""
    s0 = REGISTRY.snapshot()

    class _Snap:
        @staticmethod
        def delta():
            return REGISTRY.snapshot().delta(s0)

    return _Snap


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(23)
    x = np.asarray(rng.normal(size=(1100, 12)), np.float64)
    coef = rng.normal(size=12)
    y = x @ coef + 0.05 * rng.normal(size=1100)
    return x, y


def _gram_stream(x, plan=None, monkeypatch=None, **kw):
    if plan is not None:
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, plan)
    return ingest.stream_fold(
        iter(np.array_split(x, 4)),
        L.gram_fold_step(),
        n=x.shape[1],
        init=L.init_gram_carry(x.shape[1], x.dtype),
        rows=len(x),
        chunk_rows=128,
        **kw,
    )


def _assert_gram_equal(carry, x):
    import jax.numpy as jnp

    want = L.gram_stats(jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(carry.xtx), np.asarray(want.xtx), rtol=1e-12)
    np.testing.assert_allclose(
        np.asarray(carry.col_sum), np.asarray(want.col_sum), rtol=1e-12
    )
    assert float(carry.count) == float(len(x))


class TestOOMBisection:
    def test_oom_bisects_chunk_and_stays_exact(self, data, monkeypatch, snap):
        x, _ = data
        res = _gram_stream(x, "fold.dispatch:oom:3", monkeypatch)
        assert res.bisections >= 1
        assert res.rows == 1100
        _assert_gram_equal(res.carry, x)
        d = snap.delta()
        assert d.counter("fault.injected", site="fold.dispatch", kind="oom") == 1
        assert d.counter("chunk.bisections") == res.bisections

    def test_bisection_respects_floor(self, data, monkeypatch):
        """Every dispatch OOMs: the bisection floor turns an un-shrinkable
        OOM into the original error instead of an infinite loop."""
        x, _ = data
        plan = ",".join(f"fold.dispatch:oom:{i}" for i in range(1, 40))
        with pytest.raises(faults.InjectedResourceExhausted):
            _gram_stream(x, plan, monkeypatch, min_chunk_rows=64)


class TestTransientRetry:
    def test_ingest_io_retried(self, data, monkeypatch, snap):
        x, _ = data
        res = _gram_stream(x, "ingest.chunk:io:2", monkeypatch)
        _assert_gram_equal(res.carry, x)
        d = snap.delta()
        assert d.counter("fault.injected", site="ingest.chunk", kind="io") == 1
        assert d.counter("retry.attempts", site="ingest.chunk") == 1

    def test_dispatch_io_retried(self, data, monkeypatch, snap):
        x, _ = data
        res = _gram_stream(x, "fold.dispatch:io:4", monkeypatch)
        _assert_gram_equal(res.carry, x)
        assert snap.delta().counter("retry.attempts", site="fold.dispatch") == 1

    def test_transient_budget_exhaustion_raises(self, data, monkeypatch):
        plan = ",".join(f"ingest.chunk:io:{i}" for i in range(1, 30))
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        with pytest.raises(faults.InjectedTransientIOError):
            _gram_stream(x := data[0], plan, monkeypatch)


class TestHangBound:
    def test_hang_within_bound_completes(self, data, monkeypatch):
        x, _ = data
        res = _gram_stream(
            x, "fold.wait:hang:1:0.1", monkeypatch, fold_wait_timeout_s=30.0
        )
        _assert_gram_equal(res.carry, x)

    def test_hang_beyond_bound_diagnosed(self, data, monkeypatch):
        x, _ = data
        with pytest.raises(R.FoldHangTimeout, match="hung, not slow"):
            _gram_stream(
                x, "fold.wait:hang:1:3.0", monkeypatch, fold_wait_timeout_s=0.3
            )

    def test_hang_timeout_classified_poisoned(self):
        assert R.classify(R.FoldHangTimeout("x")) is R.ErrorClass.POISONED


class TestPreemptResume:
    def test_preempted_stream_resumes_bitwise(self, data, monkeypatch, tmp_path, snap):
        x, _ = data
        clean = _gram_stream(x)
        ckpt = TrainingCheckpointer(tmp_path / "ck")
        # chunks 1-5 fold; checkpoints land after chunks 2 and 4; the 6th
        # dispatch dies like a preempted process would
        with pytest.raises(faults.InjectedPreemption):
            _gram_stream(
                x, "fold.dispatch:preempt:6", monkeypatch,
                checkpointer=ckpt, checkpoint_every=2,
            )
        assert snap.delta().counter("stream.checkpoints") == 2
        monkeypatch.delenv(faults.FAULT_PLAN_VAR)
        res = _gram_stream(x, checkpointer=ckpt, checkpoint_every=2)
        assert res.resumed
        assert res.chunks == clean.chunks
        assert snap.delta().counter("stream.resumes") == 1
        # bitwise: the resumed accumulator path must reproduce the clean run
        np.testing.assert_array_equal(
            np.asarray(res.carry.xtx), np.asarray(clean.carry.xtx)
        )
        np.testing.assert_array_equal(
            np.asarray(res.carry.col_sum), np.asarray(clean.carry.col_sum)
        )
        assert float(res.carry.count) == float(clean.carry.count)

    def test_preemption_never_retried_in_process(self):
        calls = {"n": 0}

        def die():
            calls["n"] += 1
            raise faults.InjectedPreemption("gone")

        with pytest.raises(faults.InjectedPreemption):
            R.call_with_retry(die, policy=R.RetryPolicy(max_attempts=5))
        assert calls["n"] == 1


class TestNonFinitePolicy:
    def test_raise_policy_fails_loudly(self, data, monkeypatch):
        x = data[0].copy()
        x[7, 3] = np.nan
        with pytest.raises(ValueError, match="non-finite"):
            _gram_stream(x, nonfinite="raise")

    def test_skip_policy_drops_counts_and_matches(self, data, snap):
        x = data[0].copy()
        bad_rows = [7, 500, 1099]
        for i in bad_rows:
            x[i, i % 12] = np.inf if i % 2 else np.nan
        res = _gram_stream(x, nonfinite="skip")
        assert res.skipped_rows == len(bad_rows)
        assert res.rows == len(x) - len(bad_rows)
        _assert_gram_equal(res.carry, np.delete(x, bad_rows, axis=0))
        assert snap.delta().counter("rows.nonfinite_skipped") == len(bad_rows)

    def test_injected_corruption_skipped(self, data, monkeypatch, snap):
        x, _ = data
        res = _gram_stream(
            x, "ingest.chunk:nonfinite:1", monkeypatch, nonfinite="skip"
        )
        assert res.skipped_rows == 1
        _assert_gram_equal(res.carry, x[1:])  # first row of first pull corrupted
        d = snap.delta()
        assert d.counter("fault.injected", site="ingest.chunk", kind="nonfinite") == 1

    def test_allow_policy_skips_the_scan(self, data):
        x = data[0].copy()
        x[3, 3] = np.nan
        res = _gram_stream(x, nonfinite="allow")
        assert res.skipped_rows == 0
        assert not np.isfinite(np.asarray(res.carry.xtx)).all()


class TestCollectiveRetry:
    def test_finalize_retries_transient(self, data, monkeypatch, snap):
        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import mesh as M

        x, _ = data
        mesh = M.create_mesh()
        example = L.GramStats(
            xtx=jax.ShapeDtypeStruct((12, 12), np.float64),
            col_sum=jax.ShapeDtypeStruct((12,), np.float64),
            count=jax.ShapeDtypeStruct((), np.float64),
        )
        res = ingest.stream_fold(
            iter(np.array_split(x, 4)),
            lambda c, xd, wd: G.sharded_gram_fold(c, xd, wd, mesh),
            n=12,
            init=G.init_chunk_carry(example, mesh),
            chunk_rows=ingest.stream_chunk_rows_for_mesh(mesh),
            put_fn=G.ChunkPut(mesh),
        )
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "collective:io:1")
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        stats = G.finalize_chunk_fold(res.carry, mesh)
        _assert_gram_equal(stats, x)
        d = snap.delta()
        assert d.counter("fault.injected", site="collective", kind="io") == 1
        assert d.counter("retry.attempts", site="collective") == 1


class TestDeviceInit:
    @pytest.mark.parametrize(
        "kind,exc",
        [
            ("io", faults.InjectedTransientIOError),
            ("preempt", faults.InjectedPreemption),
        ],
    )
    def test_init_failure_raises_never_degrades(
        self, monkeypatch, snap, kind, exc
    ):
        """A mesh that cannot be created fails the fit, whatever the class
        of the error: nothing swaps the device for another one quietly."""
        from spark_rapids_ml_tpu.spark import estimators as E

        monkeypatch.setenv(faults.FAULT_PLAN_VAR, f"device.init:{kind}:1")
        with pytest.raises(exc):
            E._mesh_or_fallback()
        assert snap.delta().counter("degraded.cpu_fallback") == 0

    def test_healthy_init_returns_mesh(self):
        from spark_rapids_ml_tpu.spark import estimators as E

        assert E._mesh_or_fallback() is not None


@pytest.fixture
def force_streamed(monkeypatch):
    old = get_config().stream_fit_max_resident_bytes
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "128")
    set_config(stream_fit_max_resident_bytes=1)
    yield
    set_config(stream_fit_max_resident_bytes=old)


class TestEstimatorChaosParity:
    """Whole-fit chaos: streamed PCA / LinearRegression under injection
    complete with parity against the clean model, and the per-fit telemetry
    records the injection and the recovery."""

    def test_pca_streamed_fit_under_faults(self, data, monkeypatch, force_streamed, snap):
        x, _ = data
        est = PCA().setInputCol("f").setK(4)
        clean = est.fit(x, num_partitions=3)
        monkeypatch.setenv(
            faults.FAULT_PLAN_VAR, "ingest.chunk:io:1,fold.dispatch:oom:5"
        )
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        m = est.fit(x, num_partitions=3)
        cos = np.abs(np.sum(clean.pc * m.pc, axis=0))
        assert cos.min() >= 0.9999, cos
        d = snap.delta()
        assert d.counter("fault.injected") == 2
        assert d.counter("retry.attempts") >= 1
        assert d.counter("chunk.bisections") >= 1

    def test_linreg_streamed_fit_under_faults(self, data, monkeypatch, force_streamed, snap):
        x, y = data
        clean = LinearRegression().fit((x, y), num_partitions=3)
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "fold.dispatch:io:2")
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        m = LinearRegression().fit((x, y), num_partitions=3)
        np.testing.assert_allclose(m.coefficients, clean.coefficients, atol=1e-9)
        assert abs(m.intercept - clean.intercept) <= 1e-9
        d = snap.delta()
        assert d.counter("fault.injected", site="fold.dispatch", kind="io") == 1
        assert d.counter("retry.attempts", site="fold.dispatch") == 1

    def test_no_plan_means_zero_injections(self, data, force_streamed, snap):
        x, _ = data
        PCA().setInputCol("f").setK(3).fit(x, num_partitions=3)
        assert snap.delta().counter("fault.injected") == 0


# -- elastic stage scheduler: supervision, reassignment, hedging, barriers ----


def _ls_features_df(session, rows=36, dim=4, partitions=None, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(rows, dim))
    schema = LT.StructType(
        [
            LT.StructField("features", LT.ArrayType(LT.DoubleType())),
            LT.StructField("idx", LT.LongType()),
        ]
    )
    df = session.createDataFrame(
        [(row.tolist(), i) for i, row in enumerate(x)],
        schema,
        numPartitions=partitions,
    )
    return df, x


def _rows_key(rows):
    """Order-independent exact row content (the floats are bit-identical
    across runs: same source array, no arithmetic in the plan fn)."""
    return sorted((r.idx, tuple(r.features)) for r in rows)


def _local_ident():
    # defined per-call so cloudpickle ships it BY VALUE: a module-level
    # function would pickle by reference to this test module, which is not
    # importable inside a worker process
    def ident(batches):
        yield from batches

    return ident


class TestElasticScheduler:
    def test_worker_kill_mid_stage_reassigns(self, tmp_path, snap):
        """One worker SIGKILLs itself mid-stage: the supervisor respawns
        the slot, the dead attempt's partition migrates, and the output is
        identical to a clean run."""
        marker = str(tmp_path / "died_once")

        def die_once(batches):
            import os as wos

            data = list(batches)
            try:
                # O_EXCL: exactly one worker across the stage takes the hit
                wos.close(
                    wos.open(marker, wos.O_CREAT | wos.O_EXCL | wos.O_WRONLY)
                )
                wos.kill(wos.getpid(), 9)
            except FileExistsError:
                pass
            yield from data

        with LocalSparkSession(parallelism=6, num_workers=2) as s:
            df, _ = _ls_features_df(s, rows=36)
            clean = _rows_key(df.mapInArrow(_local_ident(), df.schema).collect())
            out = _rows_key(df.mapInArrow(die_once, df.schema).collect())
        assert out == clean
        d = snap.delta()
        assert d.counter("scheduler.reassign") >= 1
        assert d.counter("worker.respawn") >= 1
        assert d.counter("worker.quarantine") == 0

    def test_crash_loop_slot_quarantined_stage_completes(
        self, monkeypatch, snap
    ):
        """A slot whose every worker dies on arrival trips the circuit
        breaker; the stage finishes (degraded) on the surviving slot
        instead of respawning forever."""
        monkeypatch.setenv("TPU_ML_WORKER_BREAKER_THRESHOLD", "2")
        monkeypatch.setenv("TPU_ML_WORKER_RESPAWN_BACKOFF_S", "0.01")
        # on a loaded machine the warm slot hedges slot 0's cold start, and a
        # crash under a live hedge twin is not counted as a reassignment
        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "0")

        def die_on_slot0(batches):
            import os as wos

            data = list(batches)
            if wos.environ.get("TPU_ML_WORKER_SLOT") == "0":
                wos._exit(113)
            yield from data

        with LocalSparkSession(parallelism=6, num_workers=2) as s:
            df, _ = _ls_features_df(s, rows=36)
            out = _rows_key(df.mapInArrow(die_on_slot0, df.schema).collect())
            clean = _rows_key(df.mapInArrow(_local_ident(), df.schema).collect())
            assert out == clean
            assert s._supervisor.quarantined_slots() == [0]
            assert s._supervisor.summary()["leases"]["0"]["quarantined"]
        d = snap.delta()
        assert d.counter("worker.quarantine", slot="0") == 1
        assert d.counter("scheduler.reassign") >= 2

    def test_straggler_hedge_is_deterministic(self, monkeypatch, snap):
        """Each worker's 2nd task hangs 1s: with hedging on, an idle slot
        duplicates the straggler and the first result wins; results are
        bit-identical with hedging on, off, and with no fault at all."""
        # each worker process hangs on its 3rd task: occurrence 1 is the
        # warm-up below, 2 is the stage's seeded partition, 3 is the
        # straggler (primary on one worker, its hedge twin on the other)
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "worker.task:hang:3:1.0")
        monkeypatch.setenv("TPU_ML_HEDGE_FLOOR_S", "0.05")

        def run(factor):
            with LocalSparkSession(parallelism=3, num_workers=2) as s:
                # warm both workers first (hedging off, one seeded task
                # each) so the measured p50 reflects task time, not the
                # 1s worker spawn — the hedge threshold must see the hang
                # as a straggler, not as a normal first-task latency
                monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "0")
                warm, _ = _ls_features_df(s, rows=8, partitions=2)
                warm.mapInArrow(_local_ident(), warm.schema).collect()
                monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", factor)
                df, _ = _ls_features_df(s, rows=30)
                return _rows_key(
                    df.mapInArrow(_local_ident(), df.schema).collect()
                )

        hedged = run("2.0")
        assert snap.delta().counter("scheduler.hedge") >= 1

        s1 = REGISTRY.snapshot()
        unhedged = run("0")
        assert REGISTRY.snapshot().delta(s1).counter("scheduler.hedge") == 0

        monkeypatch.delenv(faults.FAULT_PLAN_VAR)
        clean = run("0")
        assert hedged == unhedged == clean

    def test_barrier_epoch_retry_after_rank_preemption(
        self, monkeypatch, snap
    ):
        """A preempted rank dooms the barrier epoch; the stage retries the
        WHOLE round with fresh workers and matches the clean result."""
        with LocalSparkSession(parallelism=3) as s:
            df, _ = _ls_features_df(s, rows=30, partitions=3)
            clean = _rows_key(
                df.mapInArrow(_local_ident(), df.schema, barrier=True).collect()
            )
            monkeypatch.setenv(faults.FAULT_PLAN_VAR, "scheduler.rank:preempt:2")
            retried = _rows_key(
                df.mapInArrow(_local_ident(), df.schema, barrier=True).collect()
            )
        assert retried == clean
        d = snap.delta()
        assert d.counter("scheduler.barrier_retry") == 1
        assert (
            d.counter("fault.injected", site="scheduler.rank", kind="preempt")
            == 1
        )

    def test_barrier_failure_leaves_no_workers_or_dirs(
        self, monkeypatch, tmp_path
    ):
        """Retries exhausted: the epoch's failure must still tear down every
        rank worker and remove the rendezvous scratch dir (try/finally —
        the old path leaked both on a failed rank)."""
        import tempfile

        # a temp dir of this test's own: another xdist worker's barrier
        # stage, live in the shared /tmp meanwhile, is not this one's leak
        monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

        def _barrier_dirs():
            return {
                n
                for n in os.listdir(tempfile.gettempdir())
                if n.startswith("localspark-barrier-")
            }

        def _live_children():
            me, kids = str(os.getpid()), set()
            for pid in os.listdir("/proc"):
                if not pid.isdigit():
                    continue
                try:
                    with open(f"/proc/{pid}/stat", "rb") as f:
                        raw = f.read()
                    # parse after the parenthesized comm (may hold spaces)
                    state, ppid = raw[raw.rindex(b")") + 2:].split()[:2]
                    if ppid == me.encode() and state != b"Z":
                        kids.add(int(pid))
                except (OSError, ValueError):
                    continue
            return kids

        monkeypatch.setenv("TPU_ML_BARRIER_RETRIES", "0")
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "scheduler.rank:preempt:1")
        dirs0, kids0 = _barrier_dirs(), _live_children()
        with LocalSparkSession(parallelism=3) as s:
            df, _ = _ls_features_df(s, rows=12, partitions=3)
            with pytest.raises(faults.InjectedPreemption):
                df.mapInArrow(_local_ident(), df.schema, barrier=True).collect()
        assert _barrier_dirs() == dirs0
        assert _live_children() - kids0 == set()


class TestAdmissionControl:
    """begin_fit consults the health monitor: a FAILING component refuses
    the fit under the default policy, or admits it CPU-degraded under
    ``TPU_ML_ADMISSION_POLICY=degrade`` — decision stamped on the report."""

    @pytest.fixture(autouse=True)
    def _monitor_lifecycle(self):
        from spark_rapids_ml_tpu.telemetry import health

        health.stop_monitor(timeout=10.0)
        yield
        health.stop_monitor(timeout=10.0)

    def _wedge_monitor(self):
        from spark_rapids_ml_tpu.telemetry import health

        health.start_monitor(
            interval_s=3600.0,
            probe_mode="inline",
            probe_fn=lambda: (False, "injected transport wedge"),
            failing_after=1,
        ).poll_once()

    def test_failing_health_refuses_fit_by_default(self, data, snap):
        from spark_rapids_ml_tpu.telemetry import health

        self._wedge_monitor()
        x, _ = data
        with pytest.raises(
            health.AdmissionRefused, match="refused by admission control"
        ):
            PCA().setInputCol("f").setK(3).fit(x)
        assert snap.delta().counter("scheduler.admission", action="refuse") == 1

    def test_degrade_policy_admits_and_stamps_report(
        self, data, monkeypatch, snap
    ):
        monkeypatch.setenv("TPU_ML_ADMISSION_POLICY", "degrade")
        self._wedge_monitor()
        x, _ = data
        model = PCA().setInputCol("f").setK(3).fit(x)
        rep = model.fit_report
        assert rep.admission["action"] == "degrade"
        assert rep.admission["health_state"] == "FAILING"
        assert "injected transport wedge" in rep.admission["reason"]
        assert snap.delta().counter("scheduler.admission", action="degrade") == 1

    def test_healthy_monitor_admits_plainly(self, data):
        from spark_rapids_ml_tpu.telemetry import health

        health.start_monitor(
            interval_s=3600.0,
            probe_mode="inline",
            probe_fn=lambda: (True, "ok"),
        ).poll_once()
        x, _ = data
        model = PCA().setInputCol("f").setK(3).fit(x)
        assert model.fit_report.admission["action"] == "admit"

    def test_no_monitor_means_no_gatekeeping(self, data):
        x, _ = data
        model = PCA().setInputCol("f").setK(3).fit(x)
        adm = model.fit_report.admission
        assert adm["action"] == "admit"
        assert "no health evidence" in adm["reason"]


# -- serving plane: hot-swap, refresh, rollback (ISSUE-18) -------------------
# invariant under every fault below: the registry ends on exactly ONE
# consistent serving version — never a torn slot, never a client-visible
# wrong answer


def _fit_lin_pair():
    """Live model + a genuinely different candidate (flipped target)."""
    rng = np.random.default_rng(41)
    x = rng.normal(size=(128, 6))
    y = x @ np.arange(1.0, 7.0)
    return (
        x,
        LinearRegression().fit((x, y)),
        LinearRegression().fit((x, -y)),
    )


class TestServingSwapChaos:
    @pytest.fixture(autouse=True)
    def serve_clean(self):
        yield
        from spark_rapids_ml_tpu.serving import client as client_mod
        from spark_rapids_ml_tpu.serving import registry as registry_mod
        from spark_rapids_ml_tpu.serving import server as server_mod

        client_mod.reset_client()
        server_mod.stop_serving(stop_monitor=False)
        registry_mod.reset_for_tests()

    def test_swap_barrier_fault_never_tears_the_slot(self, monkeypatch, snap):
        """An I/O fault at the serve.swap barrier lands strictly before
        the publish: the old version keeps serving bitwise, and the
        retried swap completes cleanly."""
        from spark_rapids_ml_tpu.serving import registry as registry_mod

        x, old, new = _fit_lin_pair()
        reg = registry_mod.get_registry()
        reg.register("lin", old, bucket_list=(8, 16))
        out_old = reg.predict("lin", x[:8])
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "serve.swap:io:1")
        faults.reset_faults()
        with pytest.raises(faults.InjectedTransientIOError):
            reg.swap("lin", new, tolerance=100.0)
        assert reg.current_version("lin") == 1
        assert np.array_equal(reg.predict("lin", x[:8]), out_old)
        d = snap.delta()
        assert d.counter("fault.injected", site="serve.swap", kind="io") == 1
        assert d.counter("serve.swaps") == 0
        assert d.hist("serve.swap_blackout_seconds").count == 0
        # the nth-occurrence plan is spent: the retry publishes v2
        entry = reg.swap("lin", new, tolerance=100.0)
        assert entry.version == 2
        d = snap.delta()
        assert d.counter("serve.swaps") == 1
        assert d.hist("serve.swap_blackout_seconds").count == 1

    def test_swap_hang_does_not_extend_the_blackout(self, monkeypatch, snap):
        """A hang at the barrier delays the swap, not the serving plane:
        the blackout (lock-hold) stays tiny because every slow step sits
        outside the atomic section."""
        from spark_rapids_ml_tpu.serving import registry as registry_mod

        x, old, new = _fit_lin_pair()
        reg = registry_mod.get_registry()
        reg.register("lin", old, bucket_list=(8,))
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "serve.swap:hang:1:0.3")
        faults.reset_faults()
        entry = reg.swap("lin", new, tolerance=100.0)
        assert entry.version == 2
        d = snap.delta()
        assert d.counter("fault.injected", site="serve.swap", kind="hang") == 1
        black = d.hist("serve.swap_blackout_seconds")
        assert black.count == 1
        # the 0.3s hang fired pre-publish; the publish itself stayed fast
        assert black.total < 0.25

    def test_dispatch_fault_is_one_request_not_a_torn_slot(
        self, monkeypatch, snap
    ):
        from spark_rapids_ml_tpu.serving import registry as registry_mod

        x, old, _ = _fit_lin_pair()
        reg = registry_mod.get_registry()
        reg.register("lin", old, bucket_list=(8,))
        out = reg.predict("lin", x[:8])
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "serve.dispatch:io:1")
        faults.reset_faults()
        with pytest.raises(faults.InjectedTransientIOError):
            reg.predict("lin", x[:8])
        # the very next request serves the same consistent version
        assert np.array_equal(reg.predict("lin", x[:8]), out)
        assert reg.current_version("lin") == 1
        d = snap.delta()
        assert d.counter("fault.injected", site="serve.dispatch", kind="io") == 1


class TestRefreshChaos:
    @pytest.fixture(autouse=True)
    def serve_clean(self):
        yield
        from spark_rapids_ml_tpu.serving import client as client_mod
        from spark_rapids_ml_tpu.serving import registry as registry_mod
        from spark_rapids_ml_tpu.serving import server as server_mod

        client_mod.reset_client()
        server_mod.stop_serving(stop_monitor=False)
        registry_mod.reset_for_tests()

    @staticmethod
    def _delta(n: int, seed: int, flip: float = 1.0):
        rng = np.random.default_rng(seed)
        x = rng.normal(size=(n, 6))
        return x, flip * (x @ np.arange(1.0, 7.0))

    def test_fold_fault_leaves_carry_retryable(self, monkeypatch, snap):
        """An injected fold failure consumes nothing: the carry and the
        pending-row count are untouched, and refolding the same delta
        finalizes bitwise with the never-faulted oracle."""
        from spark_rapids_ml_tpu.models.incremental import (
            IncrementalLinearRegression,
        )
        from spark_rapids_ml_tpu.refresh import RefreshDaemon

        d = RefreshDaemon(
            "lr", IncrementalLinearRegression(), min_rows=1, shadow_rows=0
        )
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "refresh.fold:io:1")
        faults.reset_faults()
        with pytest.raises(faults.InjectedTransientIOError):
            d.fold(self._delta(64, 0))
        assert d.rows_pending == 0
        d.fold(self._delta(64, 0))
        oracle = IncrementalLinearRegression().partial_fit(self._delta(64, 0))
        assert np.array_equal(
            np.asarray(d.estimator.finalize().coefficients),
            np.asarray(oracle.finalize().coefficients),
        )
        dlt = snap.delta()
        assert dlt.counter("fault.injected", site="refresh.fold", kind="io") == 1
        assert dlt.counter("refresh.folds") == 1

    def test_checkpoint_fault_keeps_previous_durable_step(
        self, monkeypatch, tmp_path, snap
    ):
        from spark_rapids_ml_tpu.models.incremental import (
            IncrementalLinearRegression,
        )
        from spark_rapids_ml_tpu.refresh import RefreshDaemon

        d = RefreshDaemon(
            "lr", IncrementalLinearRegression(),
            checkpoint_dir=str(tmp_path), min_rows=1, shadow_rows=0,
        )
        d.fold(self._delta(64, 0))
        assert d.checkpoint() == 1
        d.fold(self._delta(32, 1))
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "refresh.checkpoint:io:1")
        faults.reset_faults()
        with pytest.raises(faults.InjectedTransientIOError):
            d.checkpoint()
        # step 1 is still the durable truth, readable and complete
        step, arrays, state = d.checkpointer.latest()
        assert step == 1 and state["rows_pending"] == 64
        # and the spent plan lets the next checkpoint land as step 2
        assert d.checkpoint() == 2
        assert d.checkpointer.latest()[2]["rows_pending"] == 96
        assert snap.delta().counter(
            "fault.injected", site="refresh.checkpoint", kind="io"
        ) == 1

    def test_corrupt_checkpoint_refuses_swap_old_keeps_serving(
        self, tmp_path, snap
    ):
        """A truncated checkpoint must not produce a candidate: resume
        skips the unreadable step, the min-rows floor refuses the swap,
        and the registered version keeps serving untouched."""
        from spark_rapids_ml_tpu.models.incremental import (
            IncrementalLinearRegression,
        )
        from spark_rapids_ml_tpu.refresh import RefreshDaemon
        from spark_rapids_ml_tpu.serving import registry as registry_mod

        reg = registry_mod.get_registry()
        ckdir = str(tmp_path)
        d1 = RefreshDaemon(
            "lr", IncrementalLinearRegression(),
            checkpoint_dir=ckdir, min_rows=32, shadow_rows=0,
        )
        d1.fold(self._delta(64, 0))
        assert d1.try_swap()["status"] == "registered"
        x_probe = self._delta(8, 9)[0]
        out_v1 = reg.predict("lr", x_probe)
        # the delta folds and checkpoints... then the file is truncated
        d1.fold(self._delta(64, 1))
        step = d1.checkpoint()
        npz = os.path.join(
            ckdir, f"step-{step:09d}", "arrays.npz"
        )
        with open(npz, "r+b") as f:
            f.truncate(16)
        # the daemon restarts: nothing durable is readable, so it comes
        # back empty and the swap gate refuses on the min-rows floor
        d2 = RefreshDaemon(
            "lr", IncrementalLinearRegression(),
            checkpoint_dir=ckdir, min_rows=32, shadow_rows=0,
        )
        assert d2.resume() is False
        res = d2.try_swap()
        assert res["status"] == "waiting" and res["rows_pending"] == 0
        assert reg.current_version("lr") == 1
        assert np.array_equal(reg.predict("lr", x_probe), out_v1)
        dlt = snap.delta()
        assert dlt.counter("serve.swaps") == 0
        assert dlt.counter("refresh.resumes") == 0

    def test_post_swap_latency_burn_rolls_back(self, monkeypatch, snap):
        """The headline closed-loop contract: a latency burn on live
        post-swap traffic fires the probation SLO, the daemon rolls back
        to the HBM-retained prior, and serving resumes bitwise on the old
        version — all under load, no process restart."""
        from spark_rapids_ml_tpu.models.incremental import (
            IncrementalLinearRegression,
        )
        from spark_rapids_ml_tpu.refresh import RefreshDaemon
        from spark_rapids_ml_tpu.serving import client as client_mod
        from spark_rapids_ml_tpu.serving import registry as registry_mod

        reg = registry_mod.get_registry()
        d = RefreshDaemon(
            "lr", IncrementalLinearRegression(),
            min_rows=1, shadow_rows=0, tolerance=100.0,
            probation_s=3600.0, probation_burn=1,
            probation_slo="serve.latency:p99:0.05",
        )
        d.fold(self._delta(64, 0))
        assert d.try_swap()["status"] == "registered"
        x_probe = self._delta(8, 9)[0]
        out_v1 = reg.predict("lr", x_probe)
        d.fold(self._delta(64, 1, flip=-1.0))
        assert d.try_swap()["status"] == "swapped"
        assert reg.current_version("lr") == 2
        # live post-swap traffic through the in-process serve path, with
        # an injected hang on every dispatch: the p99 burns the 50ms SLO
        monkeypatch.setenv(
            faults.FAULT_PLAN_VAR,
            ",".join(f"serve.dispatch:hang:{i}:0.12" for i in range(1, 4)),
        )
        faults.reset_faults()
        for _ in range(3):
            client_mod.predict("lr", x_probe)
        res = d.probation_check()
        assert res["status"] == "rolled_back"
        assert res["from_version"] == 2 and res["version"] == 1
        assert reg.current_version("lr") == 1
        assert np.array_equal(reg.predict("lr", x_probe), out_v1)
        dlt = snap.delta()
        assert dlt.counter("serve.rollback") == 1
        assert dlt.counter(
            "fault.injected", site="serve.dispatch", kind="hang"
        ) == 3

    def test_healthy_probation_promotes_under_load(self, snap):
        """The control case for the burn test: identical swap, healthy
        latency, the deadline promotes and the prior is released."""
        from spark_rapids_ml_tpu.models.incremental import (
            IncrementalLinearRegression,
        )
        from spark_rapids_ml_tpu.refresh import RefreshDaemon
        from spark_rapids_ml_tpu.serving import client as client_mod
        from spark_rapids_ml_tpu.serving import registry as registry_mod

        reg = registry_mod.get_registry()
        d = RefreshDaemon(
            "lr", IncrementalLinearRegression(),
            min_rows=1, shadow_rows=0, tolerance=100.0,
            probation_s=0.0, probation_slo="serve.latency:p99:10",
        )
        d.fold(self._delta(64, 0))
        d.try_swap()
        d.fold(self._delta(64, 1))
        assert d.try_swap()["status"] == "swapped"
        x_probe = self._delta(8, 9)[0]
        for _ in range(3):
            client_mod.predict("lr", x_probe)
        assert d.probation_check()["status"] == "promoted"
        assert reg.current_version("lr") == 2
        assert reg.prior_entry("lr") is None
        assert snap.delta().counter("serve.rollback") == 0


class TestFleetSwapChaos:
    """Fleet-wide hot-swap propagation under a replica kill: the rolling
    walk converges every replica to the new version with ZERO failed
    client requests, and every response is attributable to exactly one
    version (old or new) — never a torn mix."""

    @staticmethod
    def _read_exact(rf, n: int) -> bytes:
        chunks = []
        while n > 0:
            chunk = rf.read(n)
            assert chunk, "peer closed mid-frame"
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def _fast_call(self, sock, rf, model, x32):
        from spark_rapids_ml_tpu.serving import fastlane

        sock.sendall(fastlane.pack_request(model, x32))
        return fastlane.read_response(lambda n: self._read_exact(rf, n))

    def test_replica_killed_mid_swap_zero_failed_requests(
        self, tmp_path, snap
    ):
        import socket
        import threading

        from spark_rapids_ml_tpu.serving import fleet as fleet_mod

        rng = np.random.default_rng(41)
        xf = rng.normal(size=(128, 6))
        yf = xf @ np.arange(1.0, 7.0)
        old = LinearRegression().fit((xf, yf))
        new = LinearRegression().fit((xf, -yf))
        x32 = np.ascontiguousarray(xf[:4], dtype="<f4")
        want_old = np.asarray(old.transform(x32)).ravel()
        want_new = np.asarray(new.transform(x32)).ravel()

        fleet = fleet_mod.ServeFleet(
            {"lin": old},
            replicas=3,
            socket_dir=str(tmp_path / "sock"),
            bucket_list=(8,),
            extra_env={
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")
            },
        ).start()
        stop = threading.Event()
        failures: list[Exception] = []
        responses: list[np.ndarray] = []

        def hammer():
            try:
                with socket.socket(socket.AF_UNIX) as s:
                    s.connect(fleet.router_path)
                    rf = s.makefile("rb")
                    while not stop.is_set():
                        responses.append(
                            self._fast_call(s, rf, "lin", x32)
                        )
            except Exception as e:  # noqa: BLE001 — collected + asserted
                failures.append(e)

        threads = [threading.Thread(target=hammer) for _ in range(2)]
        try:
            for t in threads:
                t.start()
            # SIGKILL the last-walked slot 0.15s into the rolling swap:
            # the walk is still respawning slot 0 (seconds), so the kill
            # lands squarely mid-swap on a not-yet-swapped replica
            victim = fleet._supervisor._slots[2].worker
            killer = threading.Timer(0.15, victim.proc.kill)
            killer.start()
            ok = fleet.swap_models({"lin": new})
            killer.join()
            assert ok, "a replica never came back READY on the new spec"
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        try:
            assert victim.proc.poll() is not None, "the kill never landed"
            assert not failures, (
                f"client requests failed during the killed swap: "
                f"{failures[:3]}"
            )
            assert len(responses) > 0
            # every response is exactly one version's answer — never torn
            n_old = n_new = 0
            for r in responses:
                flat = np.asarray(r, dtype=np.float64).ravel()
                if np.allclose(flat, want_old, rtol=1e-4, atol=1e-4):
                    n_old += 1
                elif np.allclose(flat, want_new, rtol=1e-4, atol=1e-4):
                    n_new += 1
                else:
                    raise AssertionError(
                        f"response matches neither version: {flat[:4]}"
                    )
            assert n_old > 0, "no pre-swap traffic observed"
            # after the walk every replica serves the NEW version only
            assert fleet.live_replicas() == 3
            with socket.socket(socket.AF_UNIX) as s:
                s.connect(fleet.router_path)
                rf = s.makefile("rb")
                for _ in range(6):
                    final = np.asarray(
                        self._fast_call(s, rf, "lin", x32), np.float64
                    ).ravel()
                    assert np.allclose(
                        final, want_new, rtol=1e-4, atol=1e-4
                    )
            d = snap.delta()
            assert d.counter("serve.replica_restarts") >= 3
            assert d.counter("serve.drain_events") >= 3
        finally:
            fleet.stop()


class TestFleetTraceChaos:
    """Trace stitching under fleet chaos: a replica SIGKILLed with
    requests in flight yields exactly one complete trace per retried
    request — carrying the router's silent-retry marker — and a rolling
    restart mid-window loses no spans: the merged fleet stream stitches
    with zero orphans."""

    @staticmethod
    def _read_exact(rf, n: int) -> bytes:
        chunks = []
        while n > 0:
            chunk = rf.read(n)
            assert chunk, "peer closed mid-frame"
            chunks.append(chunk)
            n -= len(chunk)
        return b"".join(chunks)

    def _fast_call(self, sock, rf, model, x32):
        from spark_rapids_ml_tpu.serving import fastlane

        sock.sendall(fastlane.pack_request(model, x32))
        return fastlane.read_response(lambda n: self._read_exact(rf, n))

    def _spawn_fleet(self, tmp_path, sample: str):
        from spark_rapids_ml_tpu.serving import fleet as fleet_mod

        rng = np.random.default_rng(43)
        xf = rng.normal(size=(96, 6))
        lin = LinearRegression().fit((xf, xf @ np.arange(1.0, 7.0)))
        fleet = fleet_mod.ServeFleet(
            {"lin": lin},
            replicas=2,
            socket_dir=str(tmp_path / "sock"),
            bucket_list=(8,),
            extra_env={
                "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
                "TPU_ML_TRACE_SAMPLE": sample,
            },
        ).start()
        x32 = np.ascontiguousarray(xf[:4], dtype="<f4")
        return fleet, x32

    def _hammer(self, fleet, x32, stop, failures, done):
        import socket

        try:
            with socket.socket(socket.AF_UNIX) as s:
                s.connect(fleet.router_path)
                rf = s.makefile("rb")
                while not stop.is_set():
                    self._fast_call(s, rf, "lin", x32)
                    done[0] += 1
        except Exception as e:  # noqa: BLE001 — collected + asserted
            failures.append(e)

    def test_replica_kill_mid_request_one_complete_trace_with_retry(
        self, tmp_path, monkeypatch
    ):
        import threading
        import time

        from spark_rapids_ml_tpu.serving import fleet as fleet_mod
        from spark_rapids_ml_tpu.telemetry import tracectx

        monkeypatch.setenv("TPU_ML_TRACE_SAMPLE", "1.0")
        fleet, x32 = self._spawn_fleet(tmp_path, "1.0")
        stop = threading.Event()
        failures: list[Exception] = []
        done = [0]
        threads = [
            threading.Thread(
                target=self._hammer, args=(fleet, x32, stop, failures, done)
            )
            for _ in range(3)
        ]
        try:
            for t in threads:
                t.start()
            # let traffic flow, then SIGKILL the home replica — the
            # hammer keeps requests in flight, so the kill lands
            # mid-request and the router's silent retry must re-route
            deadline = time.monotonic() + 10
            while done[0] < 20 and time.monotonic() < deadline:
                time.sleep(0.01)
            home = fleet.ring.preference(
                fleet_mod.HashRing.key("lin", 8)
            )[0]
            fleet._supervisor._slots[home].worker.proc.kill()
            want = done[0] + 50
            deadline = time.monotonic() + 10
            while done[0] < want and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=30)
        try:
            assert not failures, (
                f"clients saw failures across the kill: {failures[:3]}"
            )
            events = fleet.fleet_events()
            retries = [
                e for e in events
                if e.get("name") == "retry"
                and (e.get("args") or {}).get("trace_id")
            ]
            assert retries, (
                "the kill never exercised the router's silent retry"
            )
            traces = tracectx.stitch_all(events)
            for inst in retries:
                tid = inst["args"]["trace_id"]
                t = traces.get(tid)
                assert t is not None and t["complete"], (
                    f"retried trace {tid} did not stitch complete"
                )
                relays = [
                    s for s in t["spans"]
                    if s.get("name") == "serve.relay"
                ]
                reqs = [
                    s for s in t["spans"]
                    if s.get("name") == "serve.request"
                ]
                # exactly one client-visible relay — the retry re-routed
                # inside it, it did not fork a second trace
                assert len(relays) == 1
                assert reqs, (
                    "retried trace has no replica-side request span"
                )
                assert any(
                    i.get("name") == "retry" for i in t["instants"]
                )
            # the un-respawned victim leaves the fleet rollup down
            assert fleet.healthz()["status"] == "down"
        finally:
            fleet.stop()

    def test_rolling_restart_mid_window_stitches_zero_orphans(
        self, tmp_path, monkeypatch
    ):
        import threading

        from spark_rapids_ml_tpu.telemetry import tracectx
        from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE

        # sample down so a multi-thousand-request window cannot evict a
        # trace's parent spans from the bounded flight-recorder rings —
        # the same discipline the bench fleet stage uses
        monkeypatch.setenv("TPU_ML_TRACE_SAMPLE", "0.02")
        fleet, x32 = self._spawn_fleet(tmp_path, "0.02")
        seq0 = TIMELINE.seq()
        stop = threading.Event()
        failures: list[Exception] = []
        done = [0]
        threads = [
            threading.Thread(
                target=self._hammer, args=(fleet, x32, stop, failures, done)
            )
            for _ in range(3)
        ]
        try:
            for t in threads:
                t.start()
            try:
                for slot in (0, 1):
                    assert fleet.restart_replica(slot), (
                        f"replica {slot} respawn never became READY"
                    )
            finally:
                stop.set()
                for t in threads:
                    t.join(timeout=30)
            assert not failures, (
                f"requests failed during rolling restart: {failures[:3]}"
            )
            assert done[0] > 0
            # scope the router's bounded ring to this window; replica
            # processes (and their harvested trailers) are all fresh
            pid_self = os.getpid()
            events = [
                e for e in fleet.fleet_events()
                if e.get("pid") != pid_self or e.get("seq", 0) > seq0
            ]
            cov = tracectx.coverage(events)
            assert cov["traces"] > 0, "no sampled traces in the window"
            assert cov["orphan_spans"] == 0, (
                f"rolling restart orphaned spans: {cov}"
            )
            assert cov["coverage"] >= 0.99, (
                f"stitching coverage regressed across the restart: {cov}"
            )
        finally:
            fleet.stop()


class TestVerdictUnderFaults:
    """The chunk's verdict (asked of the chunk that was put, before its
    fold) under the faults the fold heals: a bad chunk never reaches the
    carry or a checkpoint, and the OOM bisection asks of each piece."""

    PUTS = {"device": None, "identity": staticmethod(lambda a: a)}

    @staticmethod
    def verdicts(d, where):
        return {
            clean: int(d.counter("ingest.verdicts", where=where, clean=clean))
            for clean in ("yes", "no")
        }

    @pytest.mark.parametrize("put", ["device", "identity"])
    def test_a_bad_chunk_after_a_saved_one_leaves_a_finite_checkpoint(
        self, data, tmp_path, snap, put
    ):
        x = data[0].copy()
        bad_rows = [300, 301, 700]  # chunks 3 and 6 of 128 rows
        x[bad_rows, 2] = np.nan
        put_fn = self.PUTS[put]
        clean = _gram_stream(x, nonfinite="skip", put_fn=put_fn)
        ckpt = TrainingCheckpointer(tmp_path / "ck")
        with pytest.raises(ValueError, match=r"^2 non-finite input row\(s\)"):
            _gram_stream(
                x, nonfinite="raise", put_fn=put_fn,
                checkpointer=ckpt, checkpoint_every=1,
            )
        # chunks 1 and 2 were saved; the third never reached the carry
        assert snap.delta().counter("stream.checkpoints") == 2
        step, arrays, state = ckpt.latest()
        assert state["chunks"] == 2 and state["rows_seen"] == 256
        assert all(np.isfinite(a).all() for a in arrays.values())
        res = _gram_stream(
            x, nonfinite="skip", put_fn=put_fn,
            checkpointer=ckpt, checkpoint_every=1,
        )
        assert res.resumed and res.chunks == clean.chunks
        assert res.skipped_rows == len(bad_rows)
        assert res.rows == len(x) - len(bad_rows)
        # bitwise: the same chunks, masked alike, in the same order
        for got, want in zip(res.carry, clean.carry):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        _assert_gram_equal(res.carry, np.delete(x, bad_rows, axis=0))
        # and a checkpoint taken after a masked chunk carries its count on
        step, arrays, state = ckpt.latest()
        assert state["rows_seen"] + state["skipped_rows"] == 1024
        assert state["skipped_rows"] == len(bad_rows)

    @pytest.mark.parametrize("put", ["device", "identity"])
    @pytest.mark.parametrize("nonfinite", ["raise", "skip"])
    def test_the_bisection_asks_of_each_piece(
        self, data, monkeypatch, snap, put, nonfinite
    ):
        x = data[0][:128].copy()
        x[100, 5] = np.inf  # the second piece of the one chunk
        where = "device" if put == "device" else "host"
        kw = dict(nonfinite=nonfinite, put_fn=self.PUTS[put], min_chunk_rows=64)
        if nonfinite == "raise":
            with pytest.raises(ValueError, match=r"^1 non-finite input row\(s\)"):
                _gram_stream(x, "fold.dispatch:oom:1", monkeypatch, **kw)
            # the first piece was clean and folded; the second said no
            assert self.verdicts(snap.delta(), where) == {"yes": 1, "no": 1}
            return
        res = _gram_stream(x, "fold.dispatch:oom:1", monkeypatch, **kw)
        assert res.bisections == 1 and res.chunks == 2
        assert res.skipped_rows == 1 and res.rows == 127
        assert self.verdicts(snap.delta(), where) == {"yes": 2, "no": 1}
        _assert_gram_equal(res.carry, np.delete(x, 100, axis=0))
