"""The resident ``SparkLogisticRegression.fit(df)`` (``distribution=
'mesh-local'``) that the benchmark's ``logreg3000_fit_resident`` cell times:
the program against the plain float64 reference the cell is held by, the
counter and the program name its metrics read, the spans a fit's seconds are
split by, and the labelled ingest at a width off any round number."""

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import data_logreg, reference_logreg  # noqa: E402
from spark_rapids_ml_tpu.ops import linear as LIN  # noqa: E402
from spark_rapids_ml_tpu.parallel import linear as PL  # noqa: E402
from spark_rapids_ml_tpu.parallel import mesh as M  # noqa: E402
from spark_rapids_ml_tpu.spark import SparkLogisticRegression, ingest  # noqa: E402
from spark_rapids_ml_tpu.telemetry import REGISTRY, TIMELINE  # noqa: E402

N, MAX_ITER, REG = 61, 8, 1e-5


@pytest.fixture(scope="module")
def session():
    from spark_rapids_ml_tpu.localspark import LocalSparkSession

    s = LocalSparkSession(parallelism=2, num_workers=1)
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def empty_holder():
    ingest.release_staging()
    yield
    ingest.release_staging()


def blocks_of(rows: int, seed: int = 11):
    """Two distinct blocks of overlapping classes, each standing once."""
    blocks = data_logreg.make_blocks(seed, N, rows // 2, 2, signal=2.0, intercept=0.3)
    return blocks, [0, 1]


def on_devices(monkeypatch, ndev: int) -> None:
    """The mesh-local fit builds its mesh from every device there is: give
    it the first ``ndev`` of the eight virtual ones."""
    create = M.create_mesh
    monkeypatch.setattr(
        M, "create_mesh",
        lambda *a, **kw: create(*a, **{"devices": jax.devices()[:ndev], **kw}),
    )


def estimator(**params):
    base = dict(regParam=REG, elasticNetParam=0.0, fitIntercept=True,
                maxIter=MAX_ITER, tol=0.0, distribution="mesh-local")
    est = SparkLogisticRegression(**{**base, **params})
    return est.setFeaturesCol(data_logreg.FEATURES).setLabelCol(data_logreg.LABEL)


def iterations(moved) -> float:
    return moved.counter("logreg.iterations", path="mesh-local")


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("rows", [2048, 1500])
def test_fit_agrees_with_the_plain_reference(session, monkeypatch, rows, ndev):
    """61 features and an intercept, rows that are and are not a power of
    two (the pad rows change nothing), on one device and on a mesh of four:
    the weights against float64 IRLS from zero, by the numbers the cell
    compares, and the iteration counter."""
    on_devices(monkeypatch, ndev)
    blocks, order = blocks_of(rows)
    df = session.createDataFrame(data_logreg.to_table(blocks, order))
    before = REGISTRY.snapshot()
    model = estimator().fit(df)
    assert iterations(REGISTRY.snapshot().delta(before)) == MAX_ITER
    ref = reference_logreg.irls(blocks, order, MAX_ITER, REG)
    assert ref["last_step"] < 1e-9 * np.linalg.norm(ref["w"])
    read = reference_logreg.compare(
        blocks, order, model.coefficients, model.intercept, ref, REG
    )
    assert read["coef_gap"] < 1e-9 and read["grad_norm"] < 1e-9, read
    assert read["objective_gap"] < 1e-12, read


STATS = {"logistic": LIN.logistic_newton_stats, "squared_hinge": LIN.svc_newton_stats}


@pytest.mark.parametrize("loss", list(STATS))
def test_pad_rows_add_nothing(loss):
    """Zero rows of weight 0 behind the true rows, as the resident ingest
    pads a shard: every statistic is what the true rows alone give (to
    float64's rounding: the products are cut elsewhere)."""
    rng = np.random.default_rng(3)
    rows, pad = 1_500, 548
    x = rng.standard_normal((rows, N + 1))
    x[:, -1] = 1.0
    y = (rng.random(rows) < 0.5).astype(np.float64)
    w = 0.1 * rng.standard_normal(N + 1)
    true = STATS[loss](jnp.asarray(x), jnp.asarray(y), jnp.asarray(w), jnp.ones(rows))
    padded = STATS[loss](
        jnp.asarray(np.concatenate([x, np.zeros((pad, N + 1))])),
        jnp.asarray(np.concatenate([y, np.zeros(pad)])), jnp.asarray(w),
        jnp.asarray(np.concatenate([np.ones(rows), np.zeros(pad)])),
    )
    for name in LIN.NewtonStats._fields:
        a, b = np.asarray(getattr(true, name)), np.asarray(getattr(padded, name))
        assert np.abs(a - b).max() <= 1e-13 * np.abs(a).max(), name


@pytest.mark.parametrize(
    "tol, least, most", [(0.0, 3 * MAX_ITER, 3 * MAX_ITER), (1e-3, 3, 3 * (MAX_ITER - 1))],
    ids=["tol=0", "tol=1e-3"],
)
def test_the_counter_reads_the_iterations_the_program_ran(session, tol, least, most):
    """Three fits: fits x maxIter at ``tol`` 0, fewer where a tolerance is
    reached."""
    blocks, order = blocks_of(1024, seed=3)
    df = session.createDataFrame(data_logreg.to_table(blocks, order))
    before = REGISTRY.snapshot()
    for _ in range(3):
        estimator(tol=tol).fit(df)
    assert least <= iterations(REGISTRY.snapshot().delta(before)) <= most


def test_the_program_keeps_the_name_the_benchmark_reads():
    """benchmarks/layer_metrics/newton_roofline.json finds the Newton loop in
    the device trace by its module name, ``jit__newton``: a rename breaks
    this test on the CPU and not a metric on the chip."""
    mesh = M.create_mesh(devices=jax.devices()[:1])
    newton = PL.make_distributed_logreg_chunk(
        mesh, reg_param=REG, chunk_iters=3, tol=0.0
    ).lower(
        jax.ShapeDtypeStruct((64, N + 1), np.float32),
        jax.ShapeDtypeStruct((64,), np.float32),
        jax.ShapeDtypeStruct((64,), np.float32),
        jax.ShapeDtypeStruct((N + 1,), np.float32),
        jax.ShapeDtypeStruct((), np.int32),
    )
    assert "module @jit__newton" in newton.as_text()
    spec = json.loads((ROOT / "benchmarks/layer_metrics/newton_roofline.json").read_text())
    assert spec["reader"]["program"] == "jit__newton"


class TestSpans:
    """A fit's seconds are split among ``label scan``, ``mesh.ingest`` and
    ``logreg mesh-local fit``, in that order; the ingest's children carry the
    streamed fold's names."""

    PARTS = ("label scan", "mesh.ingest", "logreg mesh-local fit")

    def fit(self, session):
        blocks, order = blocks_of(1024)
        df = session.createDataFrame(data_logreg.to_table(blocks, order))
        estimator().fit(df)  # compiled before the fit that is read
        TIMELINE.clear()
        before = REGISTRY.snapshot()
        t0 = time.perf_counter()
        estimator().fit(df)
        wall = time.perf_counter() - t0
        return REGISTRY.snapshot().delta(before), wall

    def test_spans_nest_and_add_up(self, session):
        moved, wall = self.fit(session)
        seconds = {}
        for phase in self.PARTS:
            hist = moved.hist("span.seconds", phase=phase)
            assert hist.count == 1, phase
            seconds[phase] = hist.total
        assert sum(seconds.values()) <= wall
        starts = {e["name"]: e["ts"] for e in TIMELINE.events() if e["name"] in self.PARTS}
        assert sorted(starts, key=starts.get) == list(self.PARTS)
        children = ("ingest.chunk", "ingest.stage", "h2d.put", "stage.reclaim")
        covered = sum(moved.hist("span.seconds", phase=phase).total for phase in children)
        own = moved.hist("span.self_seconds", phase="mesh.ingest").total
        assert own + covered == pytest.approx(seconds["mesh.ingest"], abs=1e-6)
        parents = {
            e["name"]: e["args"].get("parent") for e in TIMELINE.events()
            if e["name"] in children
        }
        assert parents == dict.fromkeys(children, "mesh.ingest")

    def test_the_newton_span_covers_the_wait_for_the_result(self, session, monkeypatch):
        class Late:
            """An answer that costs its reader a wait, as a device array does."""

            def __init__(self, value):
                self.value = value

            def __array__(self, dtype=None, copy=None):
                time.sleep(0.05)
                return self.value

        def fit_fn(x, y, w):
            return Late(np.full(N + 1, 0.5)), np.int32(MAX_ITER), np.float32(1e-9)

        monkeypatch.setattr(PL, "make_distributed_logreg_fit", lambda *a, **kw: fit_fn)
        moved, _ = self.fit(session)
        assert moved.hist("span.seconds", phase="logreg mesh-local fit").total >= 0.05


class TestTheLabelledIngest:
    """``stream_to_mesh`` with a label column and the intercept's column at
    a staged width of 62: what lands is the rows, a 1.0 behind each, their
    labels, weight 1, and zeros behind the last true row."""

    ROWS = 1_300

    def ingested(self, session, ndev):
        blocks, order = blocks_of(self.ROWS)
        df = session.createDataFrame(data_logreg.to_table(blocks, order))
        mesh = M.create_mesh(devices=jax.devices()[:ndev])
        before = REGISTRY.snapshot()
        ing = ingest.stream_to_mesh(
            df, features_col=data_logreg.FEATURES, n=N, label_col=data_logreg.LABEL,
            with_weights=True, augment_intercept=True, mesh=mesh,
        )
        return ing, blocks, REGISTRY.snapshot().delta(before)

    @pytest.mark.parametrize("ndev", [1, 2])
    def test_rows_labels_weights_and_pads(self, session, ndev):
        ing, blocks, moved = self.ingested(session, ndev)
        rows, labels = (np.concatenate([b[i] for b in blocks]) for i in (0, 1))
        # a device's 650 rows lie in a shard of 656: the pads follow each shard's rows
        shard = ing.padded_rows // ndev
        assert moved.counter("mesh.pad_rows") == ing.padded_rows - self.ROWS
        got_x, got_y, got_w = (np.asarray(a) for a in (ing.xs, ing.ys, ing.ws))
        assert got_x.shape == (ing.padded_rows, N + 1)
        true = np.concatenate([
            np.arange(dev * shard, dev * shard + share)
            for dev, share in enumerate(self.shares(self.ROWS, shard, ndev))
        ])
        np.testing.assert_array_equal(got_x[true, :N], rows.astype(got_x.dtype))
        assert (got_x[true, N] == 1.0).all() and (got_w[true] == 1.0).all()
        np.testing.assert_array_equal(got_y[true], labels)
        pads = np.setdiff1d(np.arange(ing.padded_rows), true)
        assert not got_x[pads].any() and not got_y[pads].any() and not got_w[pads].any()

    @staticmethod
    def shares(rows, shard, ndev):
        """Shards fill in turn: each full but the last that holds rows."""
        return [max(0, min(shard, rows - dev * shard)) for dev in range(ndev)]
