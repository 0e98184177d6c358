"""A whole run with the timed path broken underneath must come out as not
correct: once for each fault a one-chip fit cell can have. The harness's look
for a chip is skipped (the self-check's rehearsal at a toy size on the CPU);
everything else is the run as the driver makes it, held to the limits of the
cell's own configuration file."""

import pytest

from benchmarks import manifest as M
from benchmarks import selfcheck

CELLS = [cell["name"] for cell in M.load()["workloads"]]


def fold_returns_its_state_unchanged(monkeypatch):
    from spark_rapids_ml_tpu.parallel import gram as G

    monkeypatch.setattr(G, "sharded_gram_fold", lambda carry, x, w, mesh, **kw: carry)


def half_of_each_chunk_left_out(monkeypatch):
    from spark_rapids_ml_tpu.parallel import gram as G

    fold = G.sharded_gram_fold

    def half(carry, x, w, mesh, **kw):
        return fold(carry, x, w.at[w.shape[0] // 2 :].set(0.0), mesh, **kw)

    monkeypatch.setattr(G, "sharded_gram_fold", half)


def second_chunk_left_out(monkeypatch):
    from spark_rapids_ml_tpu.parallel import gram as G

    fold, calls = G.sharded_gram_fold, [0]

    def odd_only(carry, x, w, mesh, **kw):
        calls[0] += 1
        return fold(carry, x, w, mesh, **kw) if calls[0] % 2 else carry

    monkeypatch.setattr(G, "sharded_gram_fold", odd_only)


def component_altered_where_it_is_produced(monkeypatch):
    from spark_rapids_ml_tpu.ops import linalg as L

    fit = L.pca_fit_from_cov

    def nudged(cov, k, **kw):
        pc, ev = fit(cov, k, **kw)
        # the last component turned by a hundredth of a radian towards the first
        return pc.at[:, k - 1].add(1e-2 * pc[:, 0]), ev

    monkeypatch.setattr(L, "pca_fit_from_cov", nudged)


FAULTS = [
    fold_returns_its_state_unchanged,
    half_of_each_chunk_left_out,
    second_chunk_left_out,
    component_altered_where_it_is_produced,
]


@pytest.fixture(autouse=True)
def on_the_cpu():
    import jax

    if jax.devices()[0].platform != "cpu":
        pytest.skip("the rehearsal is for the CPU")


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    result = selfcheck.rehearse(cell, trace=0, seed=2_147_483_659)
    assert result["correct"], result["compared"]


@pytest.mark.parametrize("fault", FAULTS, ids=lambda f: f.__name__)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    result = selfcheck.rehearse(cell, trace=0, seed=2_147_483_659)
    assert result["attempted"] and not result["correct"], result["compared"]
    over = [n for n, c in result["compared"].items() if c["value"] > c["limit"]]
    assert set(over) & {"pc_gap", "pc_gap_med", "ev_gap"}, result["compared"]
