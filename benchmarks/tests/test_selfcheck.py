"""The self-checks of ``python -m benchmarks.selfcheck``, one case each, and
the manifest rules that have already cost a PR."""

import copy

import pytest

from benchmarks import manifest as M
from benchmarks import selfcheck


@pytest.mark.parametrize("name", ["manifest", "files", "opcount", "trace_reduce"])
def test_selfcheck(name):
    assert selfcheck.CHECKS[name]() == []


def test_rehearsal_on_the_cpu():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal is for the CPU")
    assert selfcheck.check_rehearsal() == []


def broken(change):
    manifest = copy.deepcopy(M.load())
    change(manifest)
    return M.check(manifest)


@pytest.mark.parametrize(
    "change, says",
    [
        # PR 22 was refused on a layer of "a few plain words"
        (lambda m: m["per_layer"][0].update(layer="arrow ingest"), "layer"),
        (lambda m: m["per_layer"][0].update(unit="ms per chunk"), "unit"),
        (lambda m: m["per_layer"][0].update(why="because"), "keys"),
        (lambda m: m["per_layer"][3].update(unit="pct"), "share of a roofline"),
        (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
        (lambda m: m["workloads"][0].update(chips=2), "chips"),
        (lambda m: m["workloads"][1].update(traffic=m["workloads"][0]["traffic"],
                                            config=m["workloads"][0]["config"]), "twice"),
        (lambda m: m["configs"][0].update(reduced=["n_features"]), "width"),
        (lambda m: m.update(run_seconds=52), "run_seconds"),
        (lambda m: m["end_to_end"].pop(1), "setup_s"),
    ],
)
def test_manifest_check_refuses(change, says):
    assert any(says in e for e in broken(change)), broken(change)
