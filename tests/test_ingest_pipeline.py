"""The one ingest pipeline (spark/ingest.py): the stager under both of its
consumers, the one owner of the streamed mesh fold's geometry under every
statistic that is folded through it, and a census of the spans and counters
the benchmark's per-layer metrics read, against a table taken from the tree
before the two staging loops became one."""

import collections
import sys
from pathlib import Path

import jax
import numpy as np
import pyarrow as pa
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import data as bench_data, data_blobs  # noqa: E402
from spark_rapids_ml_tpu.ann import IVFFlatIndex  # noqa: E402
from spark_rapids_ml_tpu.parallel import gram as G  # noqa: E402
from spark_rapids_ml_tpu.parallel import mesh as M  # noqa: E402
from spark_rapids_ml_tpu.resilience import faults  # noqa: E402
from spark_rapids_ml_tpu.spark import (  # noqa: E402
    SparkKMeans,
    SparkLinearRegression,
    SparkPCA,
    SparkStandardScaler,
    ingest,
)
from spark_rapids_ml_tpu.telemetry import (  # noqa: E402
    REGISTRY,
    TIMELINE,
    metrics,
    reset_metrics,
)
from spark_rapids_ml_tpu.utils import columnar  # noqa: E402
from spark_rapids_ml_tpu.utils.config import get_config, set_config  # noqa: E402


@pytest.fixture(scope="module")
def session():
    from spark_rapids_ml_tpu.localspark import LocalSparkSession

    s = LocalSparkSession(parallelism=2, num_workers=1)
    yield s
    s.stop()


@pytest.fixture(autouse=True)
def empty_holder(monkeypatch):
    monkeypatch.delenv(faults.FAULT_PLAN_VAR, raising=False)
    faults.reset_faults()
    ingest.release_staging()
    yield
    faults.reset_faults()
    ingest.release_staging()


@pytest.fixture
def copying_put(monkeypatch):
    """``jax.device_put`` as a chip's is: the array that comes back shares no
    memory with the host buffer and has landed. (The CPU backend's own put
    aliases an aligned ndarray, and which buffers are aligned is the
    allocator's business: the states below would then be its, too.)"""
    real = jax.device_put

    def put(a, *args, **kw):
        if isinstance(a, np.ndarray):
            a = np.array(a)
        return jax.block_until_ready(real(a, *args, **kw))

    monkeypatch.setattr(jax, "device_put", put)


def on_devices(monkeypatch, ndev: int) -> None:
    """Every mesh-local fit builds its mesh from all the devices there are:
    give it the first ``ndev`` of the eight virtual ones."""
    create = M.create_mesh
    monkeypatch.setattr(
        M, "create_mesh",
        lambda *a, **kw: create(*a, **{"devices": jax.devices()[:ndev], **kw}),
    )


def states(moved) -> dict:
    return {
        state: int(moved.counter("stage.buffers", state=state))
        for state in ("reused", "fresh", "aliased")
    }


# -- (a) one stager, two consumers -------------------------------------------

SET_ROWS = 128  # a fold chunk, and a device's shard of up to 256 rows on two
N = 5
# rows of each source batch
SPLITS = {
    "exact_multiple": [128, 128],
    "ragged_tail": [128, 72],
    "under_one_set": [50],
    "straddles_a_boundary": [100, 156],
}


def batches_of(split, extras: bool):
    rng = np.random.default_rng(sum(split))
    out = []
    for rows in split:
        x = rng.normal(size=(rows, N)) * 1e3
        y = rng.normal(size=rows) if extras else None
        w = rng.uniform(0.5, 2.0, size=rows) if extras else None
        out.append((x, y, w))
    return out


def expected_sets(batches, extras: bool, sets: int, dtype):
    """What each set must hold, built with NumPy alone: the rows in order,
    cast to the device's dtype, the intercept column of ones, weight 1 where
    none was given, and zeros from the last row to the end of the last set."""
    rows = sum(len(b[0]) for b in batches)
    x = np.zeros((sets * SET_ROWS, N + int(extras)), dtype)
    x[:rows, :N] = np.concatenate([b[0] for b in batches])
    w = np.zeros(sets * SET_ROWS, dtype)
    w[:rows] = np.concatenate([b[2] for b in batches]) if extras else 1.0
    y = None
    if extras:
        x[:rows, N] = 1.0
        y = np.zeros(sets * SET_ROWS, dtype)
        y[:rows] = np.concatenate([b[1] for b in batches])
    cut = lambda a: None if a is None else np.split(a, sets)  # noqa: E731
    return list(zip(cut(x), cut(y) or [None] * sets, cut(w)))


def fold_consumer(batches, extras: bool, fail_after=None):
    """The streamed fold: every chunk the fold step is handed."""
    handed = []

    def step(carry, *arrays):
        handed.append([np.asarray(a) for a in arrays])
        return carry

    def source():
        yield from batches[:fail_after]
        if fail_after is not None:
            raise RuntimeError("the source died")

    ingest.stream_fold(
        source(), step, n=N, init=0, chunk_rows=SET_ROWS,
        label_col="label" if extras else None, augment_intercept=extras,
    )
    return [tuple(h) if extras else (h[0], None, h[1]) for h in handed]


def resident_consumer(monkeypatch, batches, extras: bool, fail_after=None):
    """The resident ingest over a mesh of two: every device's shard."""

    def source(*a, **kw):
        yield from batches[:fail_after]
        if fail_after is not None:
            raise RuntimeError("the source died")

    monkeypatch.setattr(ingest, "_iter_chunks", source)
    mesh = M.create_mesh(devices=jax.devices()[:2])
    ing = ingest.stream_to_mesh(
        object(), features_col="f", n=N, mesh=mesh,
        rows=sum(len(b[0]) for b in batches),
        label_col="label" if extras else None,
        weight_col="w" if extras else None,
        with_weights=True, augment_intercept=extras,
    )
    # up to 256 rows on two devices: one step of the shard's rule a device
    assert ing.padded_rows == 2 * columnar.shard_rows(SET_ROWS) == 2 * SET_ROWS

    def shards(a):
        if a is None:
            return [None, None]
        by_start = sorted(a.addressable_shards, key=lambda s: s.index[0].start or 0)
        return [np.asarray(s.data) for s in by_start]

    return list(zip(shards(ing.xs), shards(ing.ys), shards(ing.ws)))


@pytest.mark.parametrize("extras", [False, True], ids=["features", "labelled"])
@pytest.mark.parametrize("split", list(SPLITS))
@pytest.mark.parametrize("consumer", ["fold", "resident"])
def test_the_stager_under_each_consumer(
    monkeypatch, copying_put, consumer, split, extras
):
    batches = batches_of(SPLITS[split], extras)
    rows = sum(SPLITS[split])
    if consumer == "fold":
        sets = -(-rows // SET_ROWS)
        run = lambda **kw: fold_consumer(batches, extras, **kw)  # noqa: E731
    else:
        sets = 2  # a shard a device, the empty tail shard too
        run = lambda **kw: resident_consumer(  # noqa: E731
            monkeypatch, batches, extras, **kw
        )
    dtype = np.dtype(jax.dtypes.canonicalize_dtype(ingest.wire_dtype()))
    want = expected_sets(batches, extras, sets, dtype)

    def check(got):
        assert len(got) == sets
        for (gx, gy, gw), (wx, wy, ww) in zip(got, want):
            assert gx.dtype == dtype and gx.tobytes() == wx.tobytes()
            assert gw.tobytes() == ww.tobytes()
            assert (gy is None) == (wy is None)
            if wy is not None:
                assert gy.tobytes() == wy.tobytes()
        # the pads: nothing past the last row, and no weight there
        tail_x, _, tail_w = got[-1]
        pad_from = rows - (sets - 1) * SET_ROWS
        assert not tail_x[max(pad_from, 0):].any()
        assert not tail_w[max(pad_from, 0):].any()

    before = REGISTRY.snapshot()
    check(run())
    cold = states(REGISTRY.snapshot().delta(before))
    assert cold == {"fresh": 1, "reused": sets - 1, "aliased": 0}
    (kept,) = ingest._kept_staging
    assert kept.key == (SET_ROWS, N + int(extras), dtype, extras)

    # a source that dies after its first batch: the set goes back all the same
    with pytest.raises(RuntimeError, match="the source died"):
        run(fail_after=1)
    assert ingest._kept_staging == [kept] and not kept.placed

    # and the next ingest rewrites it: stale rows never show
    before = REGISTRY.snapshot()
    check(run())
    warm = states(REGISTRY.snapshot().delta(before))
    assert warm == {"fresh": 0, "reused": sets, "aliased": 0}
    assert ingest._kept_staging == [kept]


def test_a_fold_leaves_no_cycle_that_holds_its_carry():
    """The stager holds its consumer; a consumer that named the stager would
    close a cycle, and the carry (an [n, n] device array a fit) would then
    wait for the collector: on the chip that read as 84 MB of peak memory."""
    import gc
    import weakref

    class Carry:
        pass

    carry = Carry()
    gone = weakref.ref(carry)
    x = np.ones((300, N))
    gc.collect()
    gc.disable()
    try:
        res = ingest.stream_fold(
            iter([x]), lambda c, xd, wd: c, n=N, init=carry, chunk_rows=SET_ROWS
        )
        assert res.carry is carry and res.chunks == 3
        del res, carry
        assert gone() is None
    finally:
        gc.enable()


# -- (b) one owner of the streamed mesh fold ----------------------------------

ROWS, WIDTH = 700, 6


@pytest.fixture(scope="module")
def table():
    rng = np.random.default_rng(23)
    x = rng.normal(size=(ROWS, WIDTH))
    y = x @ rng.normal(size=WIDTH) + 0.1 * rng.normal(size=ROWS)
    offsets = pa.array(np.arange(0, x.size + 1, WIDTH, dtype=np.int32))
    feats = pa.ListArray.from_arrays(offsets, pa.array(x.reshape(-1)))
    tab = pa.Table.from_arrays([feats, pa.array(y)], names=["features", "label"])
    return x, y, tab


@pytest.fixture
def force_streamed(monkeypatch):
    old = get_config().stream_fit_max_resident_bytes
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "128")
    set_config(stream_fit_max_resident_bytes=1)
    yield
    set_config(stream_fit_max_resident_bytes=old)


def gram_fit(session, x, y, tab):
    SparkPCA(k=2).setInputCol("features").setDistribution("mesh-local").fit(
        session.createDataFrame(tab.select(["features"]))
    )
    return {"xtx": x.T @ x, "col_sum": x.sum(0), "count": float(len(x))}


def linear_fit(session, x, y, tab):
    SparkLinearRegression().setDistribution("mesh-local").fit(
        session.createDataFrame(tab)
    )
    return {
        "xtx": x.T @ x, "xty": x.T @ y, "x_sum": x.sum(0),
        "y_sum": y.sum(), "y_sq": (y * y).sum(), "count": float(len(x)),
    }


def moment_fit(session, x, y, tab):
    SparkStandardScaler().setInputCol("features").setDistribution(
        "mesh-local"
    ).fit(session.createDataFrame(tab.select(["features"])))
    return {"count": float(len(x)), "total": x.sum(0), "total_sq": (x * x).sum(0)}


def lloyd_fit(session, x, y, tab):
    """The IVF build's streamed Lloyd passes; what the passes must add up to
    is the index a single device builds (below)."""
    return IVFFlatIndex(nlist=4, maxIter=2, seed=3).fit(
        [x[i : i + 250] for i in range(0, ROWS, 250)]
    )


STATISTICS = {
    "GramStats": gram_fit,
    "LinearStats": linear_fit,
    "MomentStats": moment_fit,
    "LloydCarry": lloyd_fit,
}


@pytest.mark.parametrize("ndev", [1, 3, 4])
@pytest.mark.parametrize("statistic", list(STATISTICS))
def test_the_mesh_fold_has_one_geometry(
    session, table, monkeypatch, force_streamed, statistic, ndev
):
    """Whatever is folded: chunks of a multiple of the data axis, an OOM that
    bisects to a multiple of it, one allreduce a pass, and the total a single
    device gets."""
    x, y, tab = table
    on_devices(monkeypatch, ndev)
    if statistic == "LloydCarry":
        monkeypatch.setattr(IVFFlatIndex, "_mesh_or_none", lambda self: None)
        single = lloyd_fit(session, x, y, tab)
        monkeypatch.undo()
        monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "128")
        on_devices(monkeypatch, ndev)

    folds, totals = [], []
    fold, finalize = ingest.stream_fold, G.finalize_chunk_fold

    def spy_fold(*a, **kw):
        folds.append(kw)
        return fold(*a, **kw)

    def spy_finalize(carry, mesh):
        totals.append(finalize(carry, mesh))
        return totals[-1]

    monkeypatch.setattr(ingest, "stream_fold", spy_fold)
    monkeypatch.setattr(G, "finalize_chunk_fold", spy_finalize)
    monkeypatch.setenv(faults.FAULT_PLAN_VAR, "fold.dispatch:oom:1")
    faults.reset_faults()
    reset_metrics()
    seq = TIMELINE.seq()
    before = REGISTRY.snapshot()
    result = STATISTICS[statistic](session, x, y, tab)

    assert folds and len(totals) == len(folds)
    assert metrics()["fold.finalize"]["count"] == len(folds)
    if statistic != "LloydCarry":
        assert len(folds) == 1
    for kw in folds:
        assert kw["chunk_rows"] == -(-128 // ndev) * ndev
        assert kw["min_chunk_rows"] == ndev
    assert REGISTRY.snapshot().delta(before).counter("chunk.bisections") >= 1
    halved = [
        e["args"] for e in TIMELINE.events(seq) if e["name"] == "chunk.bisection"
    ]
    assert halved and all(
        a["to_rows"] % ndev == 0 and 0 < a["to_rows"] < a["from_rows"]
        for a in halved
    )
    if statistic == "LloydCarry":
        np.testing.assert_allclose(
            result.centroids, single.centroids, rtol=1e-9, atol=1e-9
        )
    else:
        (total,) = totals
        assert set(total._fields) == set(result)
        for name, want in result.items():
            np.testing.assert_allclose(
                np.asarray(getattr(total, name)), want, rtol=1e-10, err_msg=name
            )


# -- (c) what the benchmark's per-layer metrics count --------------------------

# counters benchmarks/layer_metrics/*.json read (and the bytes they imply)
COUNTERS = (
    [("stage.buffers", {"state": s}) for s in ("fresh", "reused", "aliased")]
    + [("ingest.batches", {"path": p}) for p in ("pool", "inline")]
    + [
        ("ingest.verdicts", {"where": w, "clean": c})
        for w in ("device", "host") for c in ("yes", "no")
    ]
    + [
        ("fold.input_in_flight", {}),
        ("h2d.bytes", {"path": "stream"}),
        ("h2d.bytes", {"path": "mesh"}),
        ("h2d.shards", {"path": "stream"}),
        ("h2d.pieces", {"path": "stream"}),
        ("h2d.pieces", {"path": "mesh"}),
        ("h2d.transfer_bytes", {"path": "stream"}),
        ("h2d.transfer_bytes", {"path": "mesh"}),
        ("h2d.transfers_failed", {"path": "stream"}),
        ("h2d.transfers_failed", {"path": "mesh"}),
        ("kmeans.iterations", {"path": "mesh-local"}),
        # 0 here: the shards are float64, which has no exact bfloat16 parts
        ("kmeans.split_iterations", {"path": "mesh-local"}),
        ("ingest.rows", {}),
        ("ingest.bytes", {}),
    ]
)

# Taken from commit ea5ef6f (the tree before the staging loops became one):
# the second of two fits of 1,650 rows x 8 in three Arrow batches of 550 on a
# mesh of four. PCA streams chunks of 512 rows: 4 chunks, the last ragged.
# KMeans holds a shard of 512 rows a device: 4 shards, the last mostly pad.
# Since then: h2d.shards{path=stream}, 4 chunks x 4 devices; and the
# non-finite check asked once a chunk of the chunk that was put (fold.wait for
# its landing, then ingest.scan, both inside fold.dispatch), not once a batch.
CENSUS = {
    "pca_streamed": (
        {
            ("compute cov", None): 1,
            ("eigh", None): 1,
            ("model.to_host", None): 1,
            ("ingest.chunk", "compute cov"): 5,
            ("ingest.stage", "compute cov"): 8,
            ("stage.reclaim", "compute cov"): 4,
            ("fold.dispatch", "compute cov"): 4,
            # PR 36: a chunk goes by pieces, 16 a device's share of 128 rows:
            # 4 chunks x 4 devices x 16. A batch of 550 rows fills the first
            # chunk in one slice, so all of that chunk's go at its dispatch;
            # the pieces that a later slice leaves whole go at once, outside
            ("h2d.put", "fold.dispatch"): 194,
            ("h2d.put", "compute cov"): 62,
            # PR 37: a transfer a piece, booked by the thread that waits for it
            ("h2d.transfer", "h2d.put"): 256,
            ("fold.wait", "fold.dispatch"): 4,
            ("ingest.scan", "fold.dispatch"): 4,
            ("fold.enqueue", "fold.dispatch"): 4,
            ("fold.wait", "compute cov"): 1,
            ("fold.finalize", "compute cov"): 1,
        },
        {
            "stage.buffers{state=reused}": 4,
            "ingest.batches{path=inline}": 7,
            "h2d.bytes{path=stream}": 147456,
            "h2d.shards{path=stream}": 16,
            "h2d.pieces{path=stream}": 256,
            "h2d.transfer_bytes{path=stream}": 147456,
            "ingest.verdicts{where=device,clean=yes}": 4,
            "ingest.rows": 1650,
            "ingest.bytes": 105600,
        },
    ),
    "kmeans_resident": (
        {
            ("mesh.ingest", None): 1,
            ("kmeans mesh init", None): 1,
            ("kmeans mesh-local fit", None): 1,
            ("ingest.chunk", "mesh.ingest"): 5,
            ("ingest.stage", "mesh.ingest"): 8,
            ("stage.reclaim", "mesh.ingest"): 5,
            ("h2d.put", "mesh.ingest"): 4,
            # PR 37: a transfer a shard; the seeding's two halves
            ("h2d.transfer", "h2d.put"): 4,
            ("kmeans.seed.rounds", "kmeans mesh init"): 1,
            ("kmeans.seed.reduce", "kmeans mesh init"): 1,
        },
        {
            "stage.buffers{state=reused}": 4,
            "ingest.batches{path=inline}": 7,
            "h2d.bytes{path=mesh}": 147456,
            "h2d.transfer_bytes{path=mesh}": 147456,
            "kmeans.iterations{path=mesh-local}": 7,
            "ingest.rows": 1650,
            "ingest.bytes": 105600,
        },
    ),
}


@pytest.mark.parametrize("fit", list(CENSUS))
def test_census_of_spans_and_counters(
    session, monkeypatch, copying_put, transfers_booked, fit
):
    on_devices(monkeypatch, 4)
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "512")
    blocks = data_blobs.make_blocks(11, 8, 6, 550, 2, spread=1.5, flatten=2.0)
    df = session.createDataFrame(bench_data.to_table(blocks, [0, 1, 0]))
    old = get_config().stream_fit_max_resident_bytes
    if fit == "pca_streamed":
        set_config(stream_fit_max_resident_bytes=1)
        est = SparkPCA(k=3).setDistribution("mesh-local")
    else:
        est = SparkKMeans(
            k=6, maxIter=7, tol=0.0, initSteps=2, seed=5,
            initMode="k-means||", distribution="mesh-local",
        )
    est = est.setInputCol(bench_data.COLUMN)
    try:
        est.fit(df)  # compiled, and the staging set kept, before the fit read
        transfers_booked()
        seq = TIMELINE.seq()
        before = REGISTRY.snapshot()
        est.fit(df)
        transfers_booked()
    finally:
        set_config(stream_fit_max_resident_bytes=old)
    moved = REGISTRY.snapshot().delta(before)
    spans = collections.Counter(
        (e["name"], e["args"].get("parent"))
        for e in TIMELINE.events(seq) if e["cat"] == "span"
    )
    counters = {}
    for name, labels in COUNTERS:
        value = moved.counter(name, **labels)
        if value:
            tag = ",".join(f"{k}={v}" for k, v in labels.items())
            counters[name + (f"{{{tag}}}" if tag else "")] = value
    want_spans, want_counters = CENSUS[fit]
    assert dict(spans) == want_spans
    assert counters == want_counters
