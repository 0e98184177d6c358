"""The health daemon CLI (tools/healthd.py): the --once exit-code contract
CI gates on."""

import importlib.util
import sys
from pathlib import Path

import pytest

_TOOLS = Path(__file__).resolve().parent.parent / "tools"


@pytest.fixture
def monitor():
    spec = importlib.util.spec_from_file_location(
        "healthd_under_test", _TOOLS / "healthd.py"
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


class TestExitCodes:
    """The --once/--strict CI-gate contract (healthd._exit_code)."""

    def test_ok_is_zero_even_strict(self, monitor):
        rollup = {"state": "OK", "slo": {"total_breaches": 0}}
        assert monitor._exit_code(rollup, strict=False) == 0
        assert monitor._exit_code(rollup, strict=True) == 0

    def test_failing_is_two_regardless(self, monitor):
        rollup = {"state": "FAILING", "slo": {}}
        assert monitor._exit_code(rollup, strict=False) == 2
        assert monitor._exit_code(rollup, strict=True) == 2

    def test_degraded_and_breaches_only_fail_strict(self, monitor):
        degraded = {"state": "DEGRADED", "slo": {}}
        assert monitor._exit_code(degraded, strict=False) == 0
        assert monitor._exit_code(degraded, strict=True) == 1
        breached = {"state": "OK", "slo": {"total_breaches": 2}}
        assert monitor._exit_code(breached, strict=False) == 0
        assert monitor._exit_code(breached, strict=True) == 1
