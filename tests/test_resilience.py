"""Unit tests for the resilience package: fault-plan parsing, error
classification, the shared retry policy, and the executor's migration onto
it (including the sleep-after-final-attempt fix)."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.parallel import executor
from spark_rapids_ml_tpu.resilience import faults
from spark_rapids_ml_tpu.resilience import retry as R
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY


@pytest.fixture(autouse=True)
def clean_faults(monkeypatch):
    monkeypatch.delenv(faults.FAULT_PLAN_VAR, raising=False)
    faults.reset_faults()
    yield
    faults.reset_faults()


class TestPlanParsing:
    def test_parses_entries_and_args(self):
        plan = faults.parse_plan("fold.dispatch:oom:3, ingest.chunk:io:1,fold.wait:hang:2:0.5")
        assert plan == (
            faults.FaultSpec("fold.dispatch", "oom", 3),
            faults.FaultSpec("ingest.chunk", "io", 1),
            faults.FaultSpec("fold.wait", "hang", 2, 0.5),
        )

    def test_empty_plan(self):
        assert faults.parse_plan("") == ()
        assert faults.parse_plan(" , ") == ()

    @pytest.mark.parametrize(
        "raw,msg",
        [
            ("fold.dispatch:oom", "site:kind:nth"),
            ("a:frobnicate:1", "not one of"),
            ("a:io:x", "not an int"),
            ("a:io:0", ">= 1"),
        ],
    )
    def test_rejects_malformed(self, raw, msg):
        with pytest.raises(ValueError, match=msg):
            faults.parse_plan(raw)

    def test_nth_occurrence_fires_once(self, monkeypatch):
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "s:io:2")
        faults.inject("s")  # occurrence 1: clean
        with pytest.raises(faults.InjectedTransientIOError):
            faults.inject("s")  # occurrence 2: fires
        faults.inject("s")  # occurrence 3: clean again (transient clears)

    def test_nonfinite_corrupts_data(self, monkeypatch):
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "s:nonfinite:1")
        x = np.ones((4, 3))
        out = faults.inject("s", x)
        assert np.isnan(out.reshape(-1)[0])
        assert np.isfinite(x).all(), "input must not be mutated in place"

    def test_no_plan_is_passthrough(self):
        x = np.ones(3)
        assert faults.inject("anything", x) is x


class TestClassify:
    @pytest.mark.parametrize(
        "exc,want",
        [
            (OSError("disk"), R.ErrorClass.TRANSIENT),
            (ConnectionResetError("peer"), R.ErrorClass.TRANSIENT),
            (TimeoutError(), R.ErrorClass.TRANSIENT),
            (EOFError(), R.ErrorClass.TRANSIENT),
            (MemoryError(), R.ErrorClass.RESOURCE_EXHAUSTED),
            (ValueError("shape"), R.ErrorClass.FATAL),
            (R.FoldHangTimeout("hung"), R.ErrorClass.POISONED),
            (faults.InjectedResourceExhausted("x"), R.ErrorClass.RESOURCE_EXHAUSTED),
            (faults.InjectedTransientIOError("x"), R.ErrorClass.TRANSIENT),
            (faults.InjectedPreemption("x"), R.ErrorClass.FATAL),
        ],
    )
    def test_basic(self, exc, want):
        assert R.classify(exc) is want

    @pytest.mark.parametrize(
        "msg,want",
        [
            ("RESOURCE_EXHAUSTED: out of memory allocating 2G", R.ErrorClass.RESOURCE_EXHAUSTED),
            ("Out of memory while trying to allocate", R.ErrorClass.RESOURCE_EXHAUSTED),
            ("UNAVAILABLE: connection reset by peer", R.ErrorClass.TRANSIENT),
            ("DEADLINE_EXCEEDED: collective timed out", R.ErrorClass.TRANSIENT),
            ("FAILED_PRECONDITION: PJRT client is dead", R.ErrorClass.POISONED),
            ("INVALID_ARGUMENT: mismatched shapes", R.ErrorClass.FATAL),
        ],
    )
    def test_xla_status_families(self, msg, want):
        from jax.errors import JaxRuntimeError

        assert R.classify(JaxRuntimeError(msg)) is want

    def test_a_real_device_oom_is_resource_exhausted(self):
        """The error the installed JAX actually raises, not a stand-in: an
        allocation no machine can satisfy, under jit."""
        import jax
        import jax.numpy as jnp
        from jax.errors import JaxRuntimeError

        with pytest.raises(JaxRuntimeError) as err:
            jax.jit(lambda: jnp.zeros((1 << 40,), jnp.float32))().block_until_ready()
        assert R.classify(err.value) is R.ErrorClass.RESOURCE_EXHAUSTED


class TestRetryPolicy:
    def test_backoff_deterministic_and_capped(self):
        pol = R.RetryPolicy(backoff_s=0.1, multiplier=2.0, max_backoff_s=0.3, jitter=0.1, seed=7)
        assert pol.sleep_s(1) == pol.sleep_s(1)  # deterministic per attempt
        for k in range(1, 8):
            assert pol.sleep_s(k) <= 0.3 * 1.1 + 1e-12
        nojit = R.RetryPolicy(backoff_s=0.1, multiplier=2.0, max_backoff_s=10.0, jitter=0.0)
        assert nojit.sleep_s(1) == pytest.approx(0.1)
        assert nojit.sleep_s(3) == pytest.approx(0.4)

    def test_from_config_reads_env_knobs(self, monkeypatch):
        from spark_rapids_ml_tpu.utils.config import set_config

        old_att, old_dl = None, None
        from spark_rapids_ml_tpu.utils.config import get_config

        cfg = get_config()
        old_att, old_dl = cfg.retry_max_attempts, cfg.retry_deadline_s
        try:
            set_config(retry_max_attempts=7, retry_deadline_s=0)
            pol = R.RetryPolicy.from_config()
            assert pol.max_attempts == 7
            assert pol.deadline_s is None  # 0 = unbounded
        finally:
            set_config(retry_max_attempts=old_att, retry_deadline_s=old_dl)

    def test_transient_clears_after_retries(self):
        calls = {"n": 0}
        sleeps = []

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("blip")
            return "ok"

        out = R.call_with_retry(
            flaky, site="t", policy=R.RetryPolicy(max_attempts=4, backoff_s=0.01),
            sleep=sleeps.append,
        )
        assert out == "ok"
        assert calls["n"] == 3
        assert len(sleeps) == 2

    def test_never_sleeps_after_final_attempt(self):
        sleeps = []

        def always():
            raise OSError("down")

        with pytest.raises(OSError):
            R.call_with_retry(
                always, site="t", policy=R.RetryPolicy(max_attempts=3, backoff_s=0.01),
                sleep=sleeps.append,
            )
        # 3 attempts -> 2 sleeps between them, NONE after the last failure
        assert len(sleeps) == 2

    def test_fatal_not_retried(self):
        calls = {"n": 0}

        def bad():
            calls["n"] += 1
            raise ValueError("shape")

        with pytest.raises(ValueError):
            R.call_with_retry(bad, policy=R.RetryPolicy(max_attempts=5))
        assert calls["n"] == 1

    def test_deadline_stops_retrying(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            raise OSError("blip")

        with pytest.raises(OSError):
            R.call_with_retry(
                flaky,
                policy=R.RetryPolicy(max_attempts=100, backoff_s=0.0, deadline_s=-1.0),
                sleep=lambda s: None,
            )
        assert calls["n"] == 1

    def test_retry_counted_in_telemetry(self):
        snap0 = REGISTRY.snapshot()
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 2:
                raise OSError("blip")
            return 1

        R.call_with_retry(
            flaky, site="unit.test", policy=R.RetryPolicy(max_attempts=3),
            sleep=lambda s: None,
        )
        delta = REGISTRY.snapshot().delta(snap0)
        assert delta.counter("retry.attempts", site="unit.test") == 1


class TestExecutorMigration:
    def test_succeeds_after_injected_transient(self, monkeypatch):
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "worker.task:io:1")
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        snap0 = REGISTRY.snapshot()
        out = executor.run_partition_tasks(
            lambda v: v * 2, [5], max_retries=2, retry_backoff_s=0.0
        )
        assert out == [10]
        delta = REGISTRY.snapshot().delta(snap0)
        assert delta.counter("fault.injected", site="worker.task", kind="io") == 1
        assert delta.counter("retry.attempts", site="worker.task") == 1

    def test_exhaustion_raises_without_trailing_sleep(self, monkeypatch):
        # the pre-migration loop slept retry_backoff_s * 2**att AFTER the
        # final failed attempt before raising; the shared policy must not
        monkeypatch.setenv(
            faults.FAULT_PLAN_VAR,
            "worker.task:io:1,worker.task:io:2,worker.task:io:3",
        )
        sleeps = []
        monkeypatch.setattr(R.time, "sleep", sleeps.append)
        with pytest.raises(executor.TaskFailedError, match="failed after 3 attempts"):
            executor.run_partition_tasks(
                lambda v: v, [1], max_retries=2, retry_backoff_s=0.01
            )
        assert len(sleeps) == 2, f"slept after the final attempt: {sleeps}"

    def test_log_format_preserved(self, monkeypatch, caplog):
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "worker.task:io:1")
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        with caplog.at_level("WARNING", logger="spark_rapids_ml_tpu"):
            executor.run_partition_tasks(
                lambda v: v, [1], max_retries=1, retry_backoff_s=0.0
            )
        assert any(
            "partition task 0 attempt 1/2 failed" in r.message for r in caplog.records
        )

    def test_results_stay_ordered_under_faults(self, monkeypatch):
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "worker.task:io:2,worker.task:io:5")
        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        out = executor.run_partition_tasks(
            lambda v: v, list(range(6)), max_retries=3, max_workers=1,
            retry_backoff_s=0.0,
        )
        assert out == list(range(6))


class TestExecutorHedging:
    """Speculative duplicates for stragglers: retry answers 'it failed',
    hedging answers 'it is taking too long' — a wedged attempt never fails,
    so only a duplicate can rescue the task's wall-clock."""

    def test_straggler_hedged_first_result_wins(self, monkeypatch):
        import threading
        import time

        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "2.0")
        monkeypatch.setenv("TPU_ML_HEDGE_FLOOR_S", "0.05")
        lock = threading.Lock()
        calls = {"slow": 0}

        def fn(v):
            if v == 2:
                with lock:
                    calls["slow"] += 1
                    wedged = calls["slow"] == 1
                if wedged:  # only the FIRST attempt of item 2 is stuck
                    time.sleep(1.0)
            return v * 10

        snap0 = REGISTRY.snapshot()
        out = executor.run_partition_tasks(
            fn, list(range(4)), max_workers=4, max_retries=0
        )
        assert out == [0, 10, 20, 30]
        d = REGISTRY.snapshot().delta(snap0)
        assert d.counter("scheduler.hedge", task="2") == 1
        assert calls["slow"] == 2  # the hedge twin really ran

    def test_factor_zero_disables_hedging(self, monkeypatch):
        import time

        monkeypatch.setenv("TPU_ML_HEDGE_FACTOR", "0")
        monkeypatch.setenv("TPU_ML_HEDGE_FLOOR_S", "0.0")

        def fn(v):
            if v == 1:
                time.sleep(0.2)
            return v

        snap0 = REGISTRY.snapshot()
        out = executor.run_partition_tasks(
            fn, list(range(3)), max_workers=3, max_retries=0
        )
        assert out == [0, 1, 2]
        assert REGISTRY.snapshot().delta(snap0).counter("scheduler.hedge") == 0
