"""Device-assignment policy (utils/devicepolicy.py).

The reference never had to solve this: Spark's GPU resource scheduling hands
every executor its own device before task code runs (JniRAPIDSML.java:27-58
then merely loads the library per-process). On a TPU host libtpu gives the
chips to the first process that initializes them, so the framework must own
the policy — keep worker children on the CPU backend and fail fast, never
hang, when a worker lands on the wrong platform.
"""

import os
import subprocess
import sys

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_ml_tpu.localspark import LocalSparkSession
from spark_rapids_ml_tpu.localspark import types as LT
from spark_rapids_ml_tpu.localspark.session import WorkerException
from spark_rapids_ml_tpu.utils import devicepolicy


def test_worker_env_scrubs_bootstrap_triggers():
    env = devicepolicy.worker_env("cpu")
    for var in devicepolicy.ACCELERATOR_BOOTSTRAP_VARS:
        assert env[var] is None  # None == remove from inherited env
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env[devicepolicy.PLATFORM_VAR] == "cpu"


def test_probe_armed_only_on_accelerator_hosts(monkeypatch):
    for var in devicepolicy.ACCELERATOR_BOOTSTRAP_VARS:
        monkeypatch.delenv(var, raising=False)
    assert devicepolicy.PROBE_VAR not in devicepolicy.worker_env("cpu")
    # presence of any bootstrap trigger in the PARENT env arms the probe
    monkeypatch.setenv(devicepolicy.ACCELERATOR_BOOTSTRAP_VARS[0], "x")
    assert devicepolicy.worker_env("cpu")[devicepolicy.PROBE_VAR] == "1"


def test_worker_env_none_platform_inherits_everything():
    assert devicepolicy.worker_env(None) == {}


def test_scrub_vars_extensible_via_env(monkeypatch):
    monkeypatch.setenv("TPU_ML_WORKER_SCRUB_VARS", "MY_PLUGIN_TRIGGER, OTHER")
    assert "MY_PLUGIN_TRIGGER" in devicepolicy.scrub_vars()
    assert "OTHER" in devicepolicy.scrub_vars()


def test_apply_overrides_deletes_on_none():
    base = {"KEEP": "1", "DROP": "2"}
    out = devicepolicy.apply_overrides(base, {"DROP": None, "NEW": "3"})
    assert out == {"KEEP": "1", "NEW": "3"}


def test_probe_platform_matches_cpu():
    # conftest forces the CPU backend in this process
    assert devicepolicy.probe_platform("cpu", timeout=30) == "cpu"


def test_probe_platform_mismatch_raises():
    with pytest.raises(devicepolicy.DevicePolicyError, match="assigned platform"):
        devicepolicy.probe_platform("tpu", timeout=30)


def _trivial_job(session):
    """One mapInArrow round trip through a real worker process."""
    df = session.createDataFrame(
        [([1.0, 2.0],)],
        LT.StructType([LT.StructField("x", LT.ArrayType(LT.DoubleType()))]),
        numPartitions=1,
    )

    def fn(batches):
        for b in batches:
            yield b

    return df.mapInArrow(
        fn, schema=LT.StructType([LT.StructField("x", LT.ArrayType(LT.DoubleType()))])
    ).collect()


def test_default_policy_runs_on_accelerator_host(monkeypatch):
    """The default session must complete a job when the parent env carries
    TPU topology variables: they are scrubbed, the probe is armed, and the
    worker proves it came up on the CPU backend."""
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    with LocalSparkSession(parallelism=1) as session:
        rows = _trivial_job(session)
    assert np.allclose(rows[0]["x"], [1.0, 2.0])


def test_wrong_platform_fails_fast_not_hang():
    """A worker assigned a platform it cannot get must error within the
    probe bound — the driver sees a WorkerException naming the policy."""
    session = LocalSparkSession(
        parallelism=1,
        worker_env={
            devicepolicy.PLATFORM_VAR: "tpu",  # expect tpu...
            "JAX_PLATFORMS": "cpu",            # ...but force cpu: mismatch
            devicepolicy.PROBE_VAR: "1",
            devicepolicy.PROBE_TIMEOUT_VAR: "30",
        },
    )
    try:
        with pytest.raises(WorkerException) as err:
            _trivial_job(session)
        assert "device-policy probe" in str(err.value)
        assert "device policy violation" in str(err.value)
    finally:
        session.stop()


def test_probe_timeout_fails_fast():
    """Even if JAX init blocks (simulated with a tiny timeout), the worker
    exits with a diagnosis instead of hanging the job."""
    session = LocalSparkSession(
        parallelism=1,
        worker_env={
            devicepolicy.PROBE_VAR: "1",
            devicepolicy.PROBE_TIMEOUT_VAR: "0.000001",
        },
    )
    try:
        with pytest.raises(WorkerException) as err:
            _trivial_job(session)
        assert "did not complete within" in str(err.value)
    finally:
        session.stop()


def test_probe_platform_none_accepts_any(monkeypatch):
    # expected=None must mean "any platform is fine" even when the worker
    # env contract var is present — an env var must not re-enable a check
    # the caller explicitly opted out of
    from spark_rapids_ml_tpu.utils import devicepolicy

    monkeypatch.setenv(devicepolicy.PLATFORM_VAR, "tpu")
    assert devicepolicy.probe_platform(expected=None, timeout=30) == "cpu"


def test_worker_starts_from_a_cwd_outside_the_repo(tmp_path):
    """A driver that found the package through a sys.path edit (not its cwd,
    not an install) still gets workers: the session hands the package root
    to the child, which is started by module name."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    program = f"""
import sys
sys.path.insert(0, {repo!r})
from spark_rapids_ml_tpu.localspark import LocalSparkSession
from spark_rapids_ml_tpu.localspark import types as LT
schema = LT.StructType([LT.StructField("x", LT.ArrayType(LT.DoubleType()))])
def fn(batches):
    yield from batches
with LocalSparkSession(parallelism=1) as session:
    df = session.createDataFrame([([1.0, 2.0],)], schema, numPartitions=1)
    print(df.mapInArrow(fn, schema=schema).collect()[0]["x"])
"""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "-c", program], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "[1.0, 2.0]" in proc.stdout
