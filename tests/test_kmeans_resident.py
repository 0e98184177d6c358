"""The resident ``SparkKMeans.fit(df)`` (``distribution='mesh-local'``) that
the benchmark's ``kmeans128_fit_resident`` cell times: the program against
the plain float64 reference the cell is held by, the counter and the program
names its metrics read, the spans a fit's seconds are split by, and the
staging set the resident ingest shares with the streamed fold."""

import json
import sys
import time
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import data, data_blobs, reference_kmeans  # noqa: E402
from spark_rapids_ml_tpu.parallel import kmeans as PK  # noqa: E402
from spark_rapids_ml_tpu.parallel import mesh as M  # noqa: E402
from spark_rapids_ml_tpu.spark import SparkKMeans, ingest  # noqa: E402
from spark_rapids_ml_tpu.telemetry import REGISTRY, TIMELINE  # noqa: E402

N, K, MAX_ITER = 8, 6, 7


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
    """Two distinct blocks of blobs, each standing once."""
    blocks = data_blobs.make_blocks(
        seed, N, K, rows // 2, 2, spread=1.5, flatten=2.0
    )
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
    base = dict(k=K, maxIter=MAX_ITER, tol=0.0, initSteps=2, seed=5,
                distribution="mesh-local")
    return SparkKMeans(**{**base, **params}).setInputCol(data.COLUMN)


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("rows", [2048, 1500])
@pytest.mark.parametrize("init", ["k-means||", "random"])
def test_fit_agrees_with_the_plain_reference(session, monkeypatch, init, rows, ndev):
    """Rows that are and are not a power of two (the pad rows change
    nothing), on one device and on a mesh of four: the centres, the cost as
    the program defines it and the iteration counter, against float64 Lloyd
    from where the program started (a fit of no iteration says where)."""
    on_devices(monkeypatch, ndev)
    blocks, order = blocks_of(rows)
    df = session.createDataFrame(data.to_table(blocks, order))
    before = REGISTRY.snapshot()
    model = estimator(initMode=init).fit(df)
    moved = REGISTRY.snapshot().delta(before)
    assert moved.counter("kmeans.iterations", path="mesh-local") == MAX_ITER
    centres0 = np.asarray(estimator(initMode=init, maxIter=0).fit(df).clusterCenters)
    held = reference_kmeans.seeding(blocks, order, centres0, seed=5)
    assert held["seed_rows_off"] == 0
    ref = reference_kmeans.lloyd(blocks, order, centres0, MAX_ITER)
    assert ref["iterations"] == MAX_ITER
    read = reference_kmeans.compare(model.clusterCenters, model.trainingCost, ref)
    assert read["center_gap"] < 1e-9 and read["cost_gap"] < 1e-9, read


class TestARaggedShard:
    """3,300 rows on a mesh of two: a device's 1,650 lie five eighths into
    their octave, so a shard is 1,664 rows where the power of two made it
    2,048 (dropped: zero rows of weight 0 and whole blocks of them)."""

    ROWS = 3_300

    def ingested(self, session, monkeypatch, rule=None):
        if rule is not None:
            monkeypatch.setattr(ingest.columnar, "shard_rows", rule)
        monkeypatch.setenv(ingest.WIRE_DTYPE_VAR, "float32")
        mesh = M.create_mesh(devices=jax.devices()[:2])
        blocks, order = blocks_of(self.ROWS)
        df = session.createDataFrame(data.to_table(blocks, order))
        ing = ingest.stream_to_mesh(
            df, features_col=data.COLUMN, n=N, mesh=mesh, with_weights=True
        )
        return ing, np.concatenate(blocks)

    def test_lloyd_from_the_same_centres_agrees_with_the_power_of_two_shard(
        self, session, monkeypatch
    ):
        """The same true rows in the same 256-row blocks, fewer zero blocks
        behind them: centres and cost equal to float32's rounding (the two
        shards cut the rows between the devices at other rows, so the
        ``psum`` adds the same terms in another order)."""
        ragged, rows = self.ingested(session, monkeypatch)
        padded, _ = self.ingested(session, monkeypatch, rule=ingest.columnar.bucket_rows)
        assert (ragged.padded_rows, padded.padded_rows) == (2 * 1_664, 2 * 2_048)
        assert ragged.xs.dtype == np.float32
        centres0 = rows[:: self.ROWS // K][:K].astype(np.float32)
        run = PK.make_distributed_kmeans_chunk(
            ragged.mesh, chunk_iters=MAX_ITER, tol=0.0, block_rows=256
        )
        got = {}
        for name, ing in (("ragged", ragged), ("padded", padded)):
            centres, cost, done, _ = run(
                ing.xs, ing.ws, jax.numpy.asarray(centres0), np.int32(MAX_ITER)
            )
            assert int(done) == MAX_ITER
            got[name] = np.asarray(centres), float(cost)
        np.testing.assert_allclose(got["ragged"][0], got["padded"][0], rtol=2e-6, atol=2e-6)
        np.testing.assert_allclose(got["ragged"][1], got["padded"][1], rtol=2e-6)

    def test_the_seeding_draws_true_rows_alone(self, session, monkeypatch):
        """``k-means||`` through ``SparkKMeans.fit(df)``: 2 x 48 candidates a
        round from shards that end in 14 and 28 pad rows, and every initial
        centre is a row of the data, none a pad row, none a row twice."""
        on_devices(monkeypatch, 2)
        blocks, order = blocks_of(self.ROWS)
        df = session.createDataFrame(data.to_table(blocks, order))
        before = REGISTRY.snapshot()
        model = estimator(k=24, maxIter=0).fit(df)
        moved = REGISTRY.snapshot().delta(before)
        assert moved.counter("mesh.pad_rows") == 2 * 1_664 - self.ROWS
        assert model.fit_report.counters["mesh.pad_rows"] == 2 * 1_664 - self.ROWS
        centres0 = np.asarray(model.clusterCenters)
        assert centres0.shape == (24, N) and np.abs(centres0).sum(axis=1).min() > 0
        held = reference_kmeans.seeding(blocks, order, centres0, seed=5)
        assert held["seed_rows_off"] == 0


def test_a_loop_that_reaches_its_tolerance_counts_fewer_iterations(session):
    blocks, order = blocks_of(512, seed=3)
    df = session.createDataFrame(data.to_table(blocks, order))
    before = REGISTRY.snapshot()
    estimator(initMode="random", maxIter=50, tol=0.5).fit(df)
    ran = REGISTRY.snapshot().delta(before).counter(
        "kmeans.iterations", path="mesh-local"
    )
    assert 1 <= ran < 50


@pytest.mark.parametrize("wire,split", [("float32", MAX_ITER), ("float64", 0)])
def test_the_counter_of_split_iterations_follows_the_shards_dtype(
    session, monkeypatch, wire, split
):
    """``kmeans.split_iterations``: the iterations whose sums took the three
    bfloat16 parts of float32 rows (``ops.kmeans.exact_bf16_parts``); a
    float64 shard takes the product as written and counts none. Both agree
    with float64 Lloyd from the same centres to their dtype's rounding."""
    monkeypatch.setenv(ingest.WIRE_DTYPE_VAR, wire)
    blocks, order = blocks_of(1500)
    df = session.createDataFrame(data.to_table(blocks, order))
    before = REGISTRY.snapshot()
    model = estimator(initMode="random").fit(df)
    moved = REGISTRY.snapshot().delta(before)
    assert moved.counter("kmeans.iterations", path="mesh-local") == MAX_ITER
    assert moved.counter("kmeans.split_iterations", path="mesh-local") == split
    centres0 = np.asarray(estimator(initMode="random", maxIter=0).fit(df).clusterCenters)
    ref = reference_kmeans.lloyd(blocks, order, centres0, MAX_ITER)
    read = reference_kmeans.compare(model.clusterCenters, model.trainingCost, ref)
    limit = 1e-5 if split else 1e-9
    assert read["center_gap"] < limit and read["cost_gap"] < limit, read


def test_the_programs_keep_the_names_the_benchmark_reads():
    """benchmarks/layer_metrics/lloyd_roofline.json finds the Lloyd loop in
    the device trace by its module name, ``jit__lloyd``, and the seeding must
    not share it: a rename breaks this test on the CPU and not a metric on
    the chip."""
    mesh = M.create_mesh(devices=jax.devices()[:1])
    x = jax.ShapeDtypeStruct((64, N), np.float32)
    w = jax.ShapeDtypeStruct((64,), np.float32)
    lloyd = PK.make_distributed_kmeans_chunk(mesh, chunk_iters=3, tol=0.0).lower(
        x, w, jax.ShapeDtypeStruct((K, N), np.float32),
        jax.ShapeDtypeStruct((), np.int32),
    )
    assert "module @jit__lloyd" in lloyd.as_text()
    seed = PK.make_distributed_kmeans_parallel_init(mesh, K).lower(
        x, w, jax.random.PRNGKey(0)
    )
    assert "module @jit__kmeans_seed" in seed.as_text()
    spec = json.loads(
        (ROOT / "benchmarks/layer_metrics/lloyd_roofline.json").read_text()
    )
    assert spec["reader"]["program"] == "jit__lloyd"


class TestSpans:
    """A fit's seconds are split among ``mesh.ingest``, ``kmeans mesh
    init`` and ``kmeans mesh-local fit``; the ingest's children carry the
    streamed fold's names."""

    def fit(self, session, **params):
        blocks, order = blocks_of(1024)
        df = session.createDataFrame(data.to_table(blocks, order))
        estimator(**params).fit(df)  # compiled before the fit that is read
        TIMELINE.clear()
        before = REGISTRY.snapshot()
        t0 = time.perf_counter()
        estimator(**params).fit(df)
        wall = time.perf_counter() - t0
        return REGISTRY.snapshot().delta(before), wall

    def test_spans_nest_and_add_up(self, session):
        moved, wall = self.fit(session, initMode="k-means||")
        parts = ("mesh.ingest", "kmeans mesh init", "kmeans mesh-local fit")
        seconds = {}
        for phase in parts:
            hist = moved.hist("span.seconds", phase=phase)
            assert hist.count == 1, phase
            seconds[phase] = hist.total
        assert sum(seconds.values()) <= wall
        # the in-program seeding is no longer inside a span that read the whole fit
        assert moved.hist("span.seconds", phase="kmeans init").count == 0
        children = ("ingest.chunk", "ingest.stage", "h2d.put", "stage.reclaim")
        covered = sum(
            moved.hist("span.seconds", phase=phase).total for phase in children
        )
        own = moved.hist("span.self_seconds", phase="mesh.ingest").total
        assert own + covered == pytest.approx(seconds["mesh.ingest"], abs=1e-6)
        parents = {
            e["name"]: e["args"].get("parent") for e in TIMELINE.events()
            if e["name"] in children
        }
        assert parents == dict.fromkeys(children, "mesh.ingest")

    def test_the_lloyd_span_covers_the_wait_for_the_result(self, session, monkeypatch):
        class Late:
            """An answer that costs its reader a wait, as a device array does."""

            def __init__(self, value):
                self.value = value

            def __array__(self, dtype=None, copy=None):
                time.sleep(0.05)
                return self.value

        def fit_fn(x, w, centres):
            return Late(np.asarray(centres)), np.float32(1.0), np.int32(MAX_ITER)

        monkeypatch.setattr(PK, "make_distributed_kmeans_fit", lambda *a, **kw: fit_fn)
        moved, _ = self.fit(session, initMode="random")
        assert moved.hist("span.seconds", phase="kmeans mesh-local fit").total >= 0.05


class TestResidentStaging:
    """``stream_to_mesh`` stages each shard in the set ``stream_fold`` keeps,
    under the same rule."""

    ROWS = 700

    @staticmethod
    def frame(rows, scale=1.0):
        mat = np.arange(rows * N, dtype=np.float64).reshape(rows, N) * scale
        table = data.to_table([mat], [0])

        class Frame:
            def count(self):
                return rows

            def _parts(self):
                yield table.to_batches(max_chunksize=256)

        return Frame(), mat

    @staticmethod
    def states(moved):
        return {
            state: int(moved.counter("stage.buffers", state=state))
            for state in ("reused", "fresh", "aliased")
        }

    def ingest(self, rows, mesh, scale=1.0, **kw):
        frame, mat = self.frame(rows, scale)
        before = REGISTRY.snapshot()
        ing = ingest.stream_to_mesh(
            frame, features_col=data.COLUMN, n=N, mesh=mesh, with_weights=True, **kw
        )
        return ing, mat, REGISTRY.snapshot().delta(before)

    def test_twice_in_a_row_takes_its_set_once_where_a_put_copies(self, monkeypatch):
        put = jax.device_put
        # a put whose result owns its bytes, as a TPU's does
        monkeypatch.setattr(
            jax, "device_put", lambda a, *rest, **kw: put(np.array(a), *rest, **kw)
        )
        mesh = M.create_mesh(devices=jax.devices()[:1])
        first, mat, moved = self.ingest(self.ROWS, mesh)
        assert self.states(moved) == {"reused": 0, "fresh": 1, "aliased": 0}
        (kept,) = ingest._kept_staging
        assert kept.x.dtype == first.xs.dtype and kept.dirty == self.ROWS
        # fewer rows of the same shard (641 to 768 rows share one), other
        # values: the set is rewritten, the stale tail zeroed
        second, mat2, moved = self.ingest(self.ROWS - 50, mesh, scale=-2.0)
        assert self.states(moved) == {"reused": 1, "fresh": 0, "aliased": 0}
        assert ingest._kept_staging[0] is kept
        np.testing.assert_array_equal(np.asarray(first.xs)[: self.ROWS], mat)
        got = np.asarray(second.xs)
        np.testing.assert_array_equal(got[: len(mat2)], mat2)
        assert not got[len(mat2) :].any()
        np.testing.assert_array_equal(
            np.asarray(second.ws), (np.arange(len(got)) < len(mat2)).astype(got.dtype)
        )

    def test_shards_are_staged_in_turn_on_a_mesh(self, monkeypatch):
        """Four devices, whatever the backend's put does with the buffer: a
        set that went with its array is not written again."""
        mesh = M.create_mesh(devices=jax.devices()[:4])
        for _ in range(2):
            ing, mat, moved = self.ingest(self.ROWS, mesh, augment_intercept=True)
            states = self.states(moved)
            assert sum(states.values()) == 4 and states["fresh"] <= 1
            got = np.asarray(ing.xs)
            np.testing.assert_array_equal(got[: self.ROWS, :N], mat)
            assert (got[: self.ROWS, N] == 1.0).all() and not got[self.ROWS :].any()
            assert float(np.asarray(ing.ws).sum()) == self.ROWS

    def test_the_set_is_shared_with_the_streamed_fold(self, monkeypatch):
        """One holder: a resident ingest and a streamed fold of the same
        shape and dtype rewrite the same set."""
        from spark_rapids_ml_tpu.ops import linalg as L

        put = jax.device_put
        monkeypatch.setattr(
            jax, "device_put", lambda a, *rest, **kw: put(np.array(a), *rest, **kw)
        )
        mesh = M.create_mesh(devices=jax.devices()[:1])
        ing, mat, _ = self.ingest(1024, mesh)
        (kept,) = ingest._kept_staging
        before = REGISTRY.snapshot()
        res = ingest.stream_fold(
            iter([mat]), L.gram_fold_step(), n=N,
            init=L.init_gram_carry(N, np.float64), chunk_rows=1024,
        )
        moved = REGISTRY.snapshot().delta(before)
        assert self.states(moved) == {"reused": 1, "fresh": 0, "aliased": 0}
        assert ingest._kept_staging[0] is kept
        np.testing.assert_allclose(res.carry.xtx, mat.T @ mat, rtol=1e-12)


class TestResidentHostPassPool:
    """``stream_to_mesh`` gets the host pass's pool through the one
    ``_StagingSet.write``: a shard staged by rows over threads is bit for bit
    the shard staged inline, and a batch books the path its copy took."""

    ROWS = 1500

    @staticmethod
    def frame(rows, labeled):
        import pyarrow as pa

        rng = np.random.default_rng(29)
        mat = rng.normal(size=(rows, N)) * 1e3
        table = data.to_table([mat], [0])
        if labeled:
            table = table.append_column("y", pa.array(rng.normal(size=rows)))
            table = table.append_column("w", pa.array(rng.uniform(0.5, 2.0, size=rows)))

        class Frame:
            def count(self):
                return rows

            def _parts(self):
                yield table.to_batches(max_chunksize=400)

        return Frame()

    def ingest(self, mesh, labeled):
        kw = dict(label_col="y", weight_col="w", augment_intercept=True) if labeled else {}
        before = REGISTRY.snapshot()
        seq = TIMELINE.seq()
        ing = ingest.stream_to_mesh(
            self.frame(self.ROWS, labeled), features_col=data.COLUMN, n=N, mesh=mesh,
            with_weights=True, **kw,
        )
        moved = REGISTRY.snapshot().delta(before)
        arrays = [np.asarray(a) for a in (ing.xs, ing.ys, ing.ws) if a is not None]
        stages = [
            e for e in TIMELINE.events(seq)
            if e["cat"] == "span" and e["name"] == "ingest.stage"
        ]
        return arrays, moved, stages

    @pytest.mark.parametrize("ndev", [1, 4])
    @pytest.mark.parametrize("labeled", [False, True])
    def test_a_pooled_ingest_is_bitwise_the_inline_one(self, monkeypatch, ndev, labeled):
        import threading

        put = jax.device_put
        # a put whose result owns its bytes, as a TPU's does: every shard
        # rewrites the one set, in both ingests alike
        monkeypatch.setattr(
            jax, "device_put", lambda a, *rest, **kw: put(np.array(a), *rest, **kw)
        )
        mesh = M.create_mesh(devices=jax.devices()[:ndev])
        inline, moved, inline_stages = self.ingest(mesh, labeled)
        assert int(moved.counter("ingest.batches", path="pool")) == 0
        assert int(moved.counter("ingest.batches", path="inline")) >= 4
        assert not ingest._pool
        # four workers whatever the host has, and blocks of 8 to 32 rows
        monkeypatch.setattr(ingest, "_pool_workers", lambda: 4)
        monkeypatch.setattr(ingest, "_POOL_MIN_BLOCK_BYTES", 8 * N * 8)
        monkeypatch.setattr(ingest, "_POOL_BLOCK_BYTES", 32 * N * 8)
        pooled, moved, pooled_stages = self.ingest(mesh, labeled)
        assert int(moved.counter("ingest.batches", path="pool")) >= 4
        assert len(ingest._pool) == 1
        assert len(pooled) == (3 if labeled else 2)
        for a, b in zip(inline, pooled):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
        # the spans a batch are what they were, on the caller's thread
        assert len(pooled_stages) == len(inline_stages)
        assert {e["tid"] for e in pooled_stages} == {threading.get_native_id()}
