"""Device-assignment policy: which process on a host owns the TPU.

The reference's executor topology gives every executor JVM its own GPU and a
one-singleton-per-process native loader (JniRAPIDSML.java:27-58) — device
ownership is decided by Spark's resource scheduling before any task code
runs. A TPU host is different: libtpu hands a chip to ONE process. The first
process that initializes the TPU backend holds every local chip until it
exits, and a second process that tries fails at start-up or waits. With
libtpu installed JAX selects the TPU by default, so the decision has to be
made for each child *before* it imports JAX. This module owns it:

1. The *parent* shapes the child environment (:func:`worker_env`):
   ``JAX_PLATFORMS=cpu`` keeps the child off libtpu altogether, and the
   ``TPU_*`` topology variables are removed so a child never acts on the
   parent's view of the chips.
2. The *child* can run a bounded-time :func:`probe_platform` that fails fast
   with a diagnosable error if it still ended up on the wrong platform or
   cannot initialize at all.

Default policy — **one device owner per host**: the driver process owns the
chips; worker subprocesses run the JAX CPU backend. Opt out by constructing
``LocalSparkSession(worker_platform=None)`` (workers inherit the parent
environment untouched — appropriate when each worker host has its own
accelerator, i.e. a real multi-host cluster).
"""

from __future__ import annotations

import os
from typing import Mapping

from spark_rapids_ml_tpu.utils import knobs

# Directory that holds the ``spark_rapids_ml_tpu`` package. Children are
# started with ``python -m spark_rapids_ml_tpu...`` and get it on PYTHONPATH.
PACKAGE_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# libtpu's per-process topology variables. Removed from worker environments
# under the "cpu" policy; their presence in the parent marks a TPU host.
# Extensible without a code change via TPU_ML_WORKER_SCRUB_VARS (comma-sep).
ACCELERATOR_BOOTSTRAP_VARS: tuple[str, ...] = (
    "TPU_WORKER_HOSTNAMES",
    "TPU_WORKER_ID",
    "TPU_VISIBLE_DEVICES",
)

# Env contract between the session (parent) and worker (child):
PLATFORM_VAR = knobs.WORKER_PLATFORM.name        # expected jax platform name
PROBE_VAR = knobs.WORKER_PROBE.name              # "1": probe at worker startup
PROBE_TIMEOUT_VAR = knobs.WORKER_PROBE_TIMEOUT.name  # seconds, float
DEFAULT_PROBE_TIMEOUT = 60.0

# Exit code a worker uses for a failed device probe; distinguishable in the
# driver's WorkerException from a plan-function crash.
PROBE_EXIT_CODE = 17


def scrub_vars() -> tuple[str, ...]:
    extra = tuple(
        v.strip()
        for v in os.environ.get(knobs.WORKER_SCRUB_VARS.name, "").split(",")
        if v.strip()
    )
    return ACCELERATOR_BOOTSTRAP_VARS + extra


def worker_env(platform: str | None = "cpu") -> dict[str, str | None]:
    """Environment overrides for a worker subprocess under ``platform``.

    A value of ``None`` means *remove the variable* from the inherited
    environment (the caller applies this — see LocalSparkSession._Worker).
    ``platform=None`` returns no overrides: the child inherits everything,
    including accelerator ownership.
    """
    if platform is None:
        return {}
    env: dict[str, str | None] = {v: None for v in scrub_vars()}
    env["JAX_PLATFORMS"] = platform
    env[PLATFORM_VAR] = platform
    # The startup probe initializes JAX inside the worker, which costs ~1s
    # and forecloses pre-init jax.config choices by plan functions — so it
    # is armed only on hosts whose environment carries TPU topology
    # variables. On clean CPU hosts workers keep their cold-interpreter
    # fidelity.
    if any(v in os.environ for v in scrub_vars()):
        env[PROBE_VAR] = "1"
    return env


def apply_overrides(
    base: Mapping[str, str], overrides: Mapping[str, str | None]
) -> dict[str, str]:
    """Merge ``overrides`` into a copy of ``base``; ``None`` deletes."""
    env = dict(base)
    for key, value in overrides.items():
        if value is None:
            env.pop(key, None)
        else:
            env[key] = value
    return env


class DevicePolicyError(RuntimeError):
    """This process could not honor its assigned device platform."""


# Default sentinel for probe_platform's ``expected``: resolve from the
# TPU_ML_WORKER_PLATFORM env contract. Pass ``expected=None`` to accept
# whatever platform initializes (bounded-time init check only) — an env
# var must not be able to re-enable the check the caller opted out of.
FROM_ENV = object()


def probe_platform(
    expected: object = FROM_ENV, timeout: float | None = None
) -> str:
    """Initialize JAX and verify the backend platform, in bounded time.

    Runs ``jax.devices()`` on a daemon thread and waits at most ``timeout``
    seconds. Three failure modes, all raising :class:`DevicePolicyError`:

    - the probe does not complete in time (libtpu is waiting for chips
      another process holds);
    - JAX initialization raised;
    - the initialized platform differs from ``expected``.

    Returns the platform name on success. ``expected`` defaults from the
    TPU_ML_WORKER_PLATFORM env var (:data:`FROM_ENV`); ``None`` means any
    platform is acceptable. ``timeout`` defaults from the env contract.
    """
    import threading

    if expected is FROM_ENV:
        expected = os.environ.get(PLATFORM_VAR) or None
    if timeout is None:
        raw = os.environ.get(PROBE_TIMEOUT_VAR, str(DEFAULT_PROBE_TIMEOUT))
        try:
            timeout = float(raw)
        except ValueError as e:
            raise DevicePolicyError(
                f"{PROBE_TIMEOUT_VAR}={raw!r} is not a number of seconds"
            ) from e
    result: dict[str, str] = {}

    def _probe() -> None:
        try:
            import jax

            result["platform"] = jax.devices()[0].platform
        except BaseException as e:  # noqa: BLE001 - reported to the parent
            result["error"] = f"{type(e).__name__}: {e}"

    t = threading.Thread(target=_probe, name="tpu-ml-device-probe", daemon=True)
    t.start()
    t.join(timeout)
    if t.is_alive():
        raise DevicePolicyError(
            f"device probe did not complete within {timeout}s: JAX backend "
            "initialization is blocked — most likely libtpu is waiting for "
            "chips that another process on this host holds. A worker "
            "process belongs on the CPU backend (JAX_PLATFORMS=cpu, see "
            "devicepolicy.worker_env); a driver process must be the only "
            "one on the host that initializes the TPU. To wait longer, pass "
            f"a larger timeout (workers: the {PROBE_TIMEOUT_VAR} env var)."
        )
    if "error" in result:
        raise DevicePolicyError(
            f"JAX failed to initialize in this process: {result['error']}"
        )
    platform = result.get("platform", "<unknown>")
    if expected is not None and platform != expected:
        raise DevicePolicyError(
            f"this process was assigned platform {expected!r} but JAX "
            f"initialized {platform!r}: JAX_PLATFORMS names another "
            "platform, or a backend was initialized before the assignment "
            "was made. In a worker under the one-device-owner-per-host "
            "policy, start it with devicepolicy.worker_env(), or run the "
            "session with worker_platform=None to hand workers the device."
        )
    return platform
