"""Test harness configuration.

Mirrors the reference's test strategy (SURVEY.md §4) but fixes its biggest
gap: everything here runs WITHOUT accelerator hardware. We force the JAX CPU
backend with 8 virtual devices so the multi-chip sharding paths
(shard_map/psum over a Mesh) compile and execute in any environment —
the analog of the reference exercising "distributed" behavior with
2-partition local RDDs (PCASuite.scala:55-56).

This must run before the first ``import jax`` anywhere in the test session.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# barrier rendezvous races first-compile latency; on a loaded box (bench
# or a sibling suite sharing the host) the 120 s default can flake
os.environ.setdefault("TPU_ML_BARRIER_TIMEOUT_S", "300")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# f64 on the CPU backend so differential tests can hold tight tolerances
# against NumPy oracles; the framework code itself is dtype-agnostic. The
# chip runs with x64 OFF (f32 on device) — chip_smoke.py covers that side.
jax.config.update("jax_enable_x64", True)

# Persistent compilation cache so repeated test runs don't re-trace/compile:
# the package's one rule, applied before the first test compiles anything.
from spark_rapids_ml_tpu.utils.config import (  # noqa: E402
    enable_compilation_cache,
)

enable_compilation_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def transfers_booked():
    """``transfers_booked()`` returns once every host-to-device transfer
    issued so far is booked: the threads that wait for them
    (``spark.ingest._Transfers``) can lag a toy's fit, whose arrays are ready
    at once. ``transfers_booked(owner)`` asks another owner than the
    module's."""
    import time

    from spark_rapids_ml_tpu.spark import ingest

    def wait(transfers=None, timeout=30.0):
        transfers = transfers or ingest._transfers
        deadline = time.monotonic() + timeout
        while transfers.in_flight() and time.monotonic() < deadline:
            time.sleep(0.001)
        assert transfers.in_flight() == 0

    return wait
