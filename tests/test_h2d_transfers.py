"""A transfer has a start and an end (``spark.ingest._Transfers``).

The owner of "what is on its way to which device" against stubs whose wait
sleeps (two transfers that overlap book their union, not their sum; a wait
that raises is counted and the next is timed), and through the three real
paths that feed it: a piece of a streamed chunk, a resident shard, a whole
put. What it holds it lets go of, and ``release_staging()`` ends its threads.
"""

import gc
import json
import time
import weakref
from pathlib import Path

import jax
import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.parallel import gram as G
from spark_rapids_ml_tpu.parallel import mesh as M
from spark_rapids_ml_tpu.spark import ingest
from spark_rapids_ml_tpu.telemetry import (
    REGISTRY, TIMELINE, names, reset_current_fit_id, set_current_fit_id,
    trace_range,
)

N, CHUNK = 7, 128


@pytest.fixture(autouse=True)
def empty_holder():
    ingest.release_staging()
    yield
    ingest.release_staging()


def samples(moved, path, **labels):
    return moved.hist("h2d.transfer_seconds", path=path, **labels)


# -- the owner against stubs whose wait sleeps ---------------------------------


class Landing:
    """Stands for a transfer's arrays: ready ``seconds`` after it was made."""

    def __init__(self, seconds, fails=False):
        self.ready_at = time.perf_counter() + seconds
        self.fails = fails


def sleeping_wait(landing):
    time.sleep(max(0.0, landing.ready_at - time.perf_counter()))
    if landing.fails:
        raise RuntimeError("the device went away")


def issue(transfers, seconds, device, nbytes=1000, **kw):
    t0 = time.perf_counter()
    with trace_range("h2d.put"):
        transfers.issued(Landing(seconds, **kw), nbytes, (device,), "stream", t0)


@pytest.fixture
def stubbed():
    transfers = ingest._Transfers(wait=sleeping_wait)
    yield transfers
    threads = [t for _, t in transfers.waiters.values()]
    transfers.close()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)


@pytest.mark.parametrize("devices", [(0, 0), (0, 1)], ids=["one-link", "two-links"])
def test_two_transfers_that_overlap_book_their_union(stubbed, transfers_booked, devices):
    """Issued 10 ms apart, 50 ms each: two samples; on one device the link
    was busy for their union (60 ms), not their sum; on two devices each link
    for its own 50 ms, and any link for the union."""
    before = REGISTRY.snapshot()
    issue(stubbed, 0.05, devices[0])
    time.sleep(0.01)
    issue(stubbed, 0.05, devices[1])
    transfers_booked(stubbed)
    moved = REGISTRY.snapshot().delta(before)
    took = samples(moved, "stream")
    assert took.count == 2 and took.total >= 0.1
    union = moved.counter("h2d.any_link_busy_seconds", path="stream")
    assert 0.06 <= union <= took.total - 0.02
    busy = moved.counter("h2d.link_busy_seconds", path="stream")
    if devices == (0, 0):
        assert busy == pytest.approx(union, abs=1e-9)
        assert samples(moved, "stream", device="0").count == 2
    else:
        assert busy == pytest.approx(took.total, abs=5e-3) and busy > union + 0.02
        assert [samples(moved, "stream", device=d).count for d in "01"] == [1, 1]
    assert moved.counter("h2d.transfer_bytes", path="stream") == 2000
    assert moved.counter("h2d.transfers_failed", path="stream") == 0


def test_an_idle_link_books_nothing(stubbed, transfers_booked):
    """Two transfers with a hole between them: the hole is not busy time."""
    before = REGISTRY.snapshot()
    issue(stubbed, 0.02, 0)
    transfers_booked(stubbed)
    time.sleep(0.05)
    issue(stubbed, 0.02, 0)
    transfers_booked(stubbed)
    moved = REGISTRY.snapshot().delta(before)
    busy = moved.counter("h2d.link_busy_seconds", path="stream")
    assert 0.04 <= busy < 0.07


def test_a_wait_that_raises_is_counted_and_the_next_is_timed(stubbed, transfers_booked):
    before = REGISTRY.snapshot()
    issue(stubbed, 0.01, 0, fails=True)
    issue(stubbed, 0.03, 0, nbytes=77)
    transfers_booked(stubbed)
    moved = REGISTRY.snapshot().delta(before)
    assert moved.counter("h2d.transfers_failed", path="stream") == 1
    took = samples(moved, "stream")
    assert took.count == 1 and took.total >= 0.03
    assert moved.counter("h2d.transfer_bytes", path="stream") == 77
    # the link was busy from the first issue to the second's ready
    assert moved.counter("h2d.link_busy_seconds", path="stream") >= 0.03


def test_a_put_over_a_mesh_is_one_transfer_on_every_device(stubbed, transfers_booked):
    before = REGISTRY.snapshot()
    t0 = time.perf_counter()
    stubbed.issued(Landing(0.03), 4000, range(4), "stream", t0)
    transfers_booked(stubbed)
    moved = REGISTRY.snapshot().delta(before)
    assert samples(moved, "stream", device="*").count == samples(moved, "stream").count == 1
    busy = moved.counter("h2d.link_busy_seconds", path="stream")
    union = moved.counter("h2d.any_link_busy_seconds", path="stream")
    assert union >= 0.03 and busy == pytest.approx(4 * union, rel=1e-6)


# -- through the real paths ----------------------------------------------------


def rows(n_rows, seed=29):
    rng = np.random.default_rng(seed)
    return np.asarray(rng.normal(size=(n_rows, N)), np.float64)


@pytest.fixture
def fold(transfers_booked):
    """``fold(x, ...)``: one stream over ``x``, by pieces unless ``put_fn``
    says otherwise, and the registry's delta once its transfers are booked."""
    return lambda x, **kw: _fold(x, transfers_booked, **kw)


def _fold(x, booked, *, ndev=None, put_fn=None, fold_fn=None, **kw):
    if ndev is None:
        place, step = G.ChunkPut(None), L.gram_fold_step()
        init = L.init_gram_carry(N, np.float64)
    else:
        mesh = M.create_mesh(devices=jax.devices()[:ndev])
        place = G.ChunkPut(mesh)
        step = lambda c, xd, wd: G.sharded_gram_fold(c, xd, wd, mesh)  # noqa: E731
        init = G.init_chunk_carry(
            L.GramStats(
                xtx=jax.ShapeDtypeStruct((N, N), np.float64),
                col_sum=jax.ShapeDtypeStruct((N,), np.float64),
                count=jax.ShapeDtypeStruct((), np.float64),
            ),
            mesh,
        )
        kw.setdefault("min_chunk_rows", ndev)
    before = REGISTRY.snapshot()
    res = ingest.stream_fold(
        iter(np.array_split(x, 5)), fold_fn or step, n=N, init=init,
        chunk_rows=CHUNK, put_fn=put_fn(place) if put_fn else place, **kw,
    )
    booked()
    return res, REGISTRY.snapshot().delta(before)


@pytest.mark.parametrize("ndev", [None, 4])
def test_a_streamed_fit_books_a_transfer_a_piece(fold, ndev):
    x = rows(3 * CHUNK + 11)
    seq = TIMELINE.seq()
    token = set_current_fit_id("fit-37")
    try:
        res, moved = fold(x, ndev=ndev)
    finally:
        reset_current_fit_id(token)
    pieces = moved.counter("h2d.pieces", path="stream")
    assert pieces == res.chunks * ingest._PIECES * (ndev or 1)
    assert samples(moved, "stream").count == pieces
    assert moved.counter("h2d.transfer_bytes", path="stream") == moved.counter(
        "h2d.bytes", path="stream"
    )
    assert moved.counter("h2d.transfers_failed", path="stream") == 0
    for d in range(ndev or 1):
        assert samples(moved, "stream", device=str(d)).count == pieces / (ndev or 1)
    busy = moved.counter("h2d.link_busy_seconds", path="stream")
    assert busy >= moved.counter("h2d.any_link_busy_seconds", path="stream") > 0
    events = [e for e in TIMELINE.events(seq) if e["name"] == "h2d.transfer"]
    assert len(events) == pieces
    assert {e["args"]["parent"] for e in events} == {"h2d.put"}
    assert {e["args"]["fit_id"] for e in events} == {"fit-37"}
    assert {e["args"]["device"] for e in events} == {str(d) for d in range(ndev or 1)}
    assert sum(e["args"]["bytes"] for e in events) == moved.counter(
        "h2d.bytes", path="stream"
    )
    np.testing.assert_allclose(np.asarray(res.carry.xtx).reshape(-1, N, N).sum(0)
                               if ndev else res.carry.xtx, x.T @ x, rtol=1e-10)


@pytest.mark.parametrize(
    "ndev, put_fn, device",
    [
        (None, lambda place: (lambda a: a), "0"),        # an identity put_fn
        (None, lambda place: (lambda a: place(a)), "0"),  # the placement, whole
        (4, lambda place: (lambda a: place(a)), "*"),     # over a mesh
    ],
    ids=["identity", "whole", "whole-over-a-mesh"],
)
def test_a_whole_put_is_one_transfer_a_chunk(fold, ndev, put_fn, device):
    x = rows(2 * CHUNK + 5)
    res, moved = fold(x, ndev=ndev, put_fn=put_fn)
    assert moved.counter("h2d.pieces", path="stream") == 0
    assert samples(moved, "stream").count == res.chunks == 3
    assert samples(moved, "stream", device=device).count == res.chunks
    assert moved.counter("h2d.transfer_bytes", path="stream") == moved.counter(
        "h2d.bytes", path="stream"
    )
    if device == "*":
        assert moved.counter("h2d.link_busy_seconds", path="stream") == pytest.approx(
            4 * moved.counter("h2d.any_link_busy_seconds", path="stream"), rel=1e-6
        )


class Frame:
    """The least of a localspark DataFrame that ``stream_to_mesh`` asks for."""

    def __init__(self, x):
        self.x = x

    def count(self):
        return len(self.x)

    def _parts(self):
        for part in np.array_split(self.x, 4):
            flat = pa.array(part.reshape(-1))
            offsets = pa.array(np.arange(0, part.size + 1, part.shape[1], dtype=np.int32))
            yield [pa.RecordBatch.from_arrays(
                [pa.ListArray.from_arrays(offsets, flat)], names=["f"]
            )]


def test_a_resident_ingest_books_a_transfer_a_shard(transfers_booked):
    x = rows(500)
    seq = TIMELINE.seq()
    before = REGISTRY.snapshot()
    ing = ingest.stream_to_mesh(
        Frame(x), features_col="f", n=N, mesh=M.create_mesh(data=4), with_weights=True
    )
    transfers_booked()
    moved = REGISTRY.snapshot().delta(before)
    np.testing.assert_array_equal(np.asarray(ing.xs)[:500], x)
    assert samples(moved, "mesh").count == 4
    assert [samples(moved, "mesh", device=str(d)).count for d in range(4)] == [1] * 4
    assert moved.counter("h2d.transfer_bytes", path="mesh") == moved.counter(
        "h2d.bytes", path="mesh"
    ) > 0
    assert samples(moved, "stream").count == 0
    events = [e for e in TIMELINE.events(seq) if e["name"] == "h2d.transfer"]
    assert [e["args"]["parent"] for e in events] == ["h2d.put"] * 4
    assert {e["args"]["path"] for e in events} == {"mesh"}


@pytest.mark.parametrize("raises", [False, True], ids=["a-fit", "a-fit-that-raised"])
def test_the_owner_holds_no_array_once_it_is_ready(monkeypatch, fold, transfers_booked, raises):
    """Every array put, a piece's among them, is let go of by the owner once
    it is ready: after a fit, and after one that raised in mid-stream."""
    put, seen = jax.device_put, []

    def spy_put(a, *args, **kw):
        out = put(a, *args, **kw)
        seen.append(weakref.ref(out))
        return out

    step, folds = L.gram_fold_step(), []

    def fold_fn(c, xd, wd):
        folds.append(1)
        if raises and len(folds) == 2:
            raise KeyError("no such fold")
        return step(c, xd, wd)

    monkeypatch.setattr(jax, "device_put", spy_put)
    x = rows(3 * CHUNK)
    if raises:
        with pytest.raises(KeyError):
            fold(x, fold_fn=fold_fn)
    else:
        res, _ = fold(x, fold_fn=fold_fn)
        del res
    transfers_booked()
    ingest.release_staging()  # the kept set's placed arrays
    gc.collect()
    assert len(seen) >= 2 * ingest._PIECES and not any(r() is not None for r in seen)
    assert not ingest._transfers.flying or not any(ingest._transfers.flying.values())


def test_release_staging_ends_the_threads_and_the_next_ingest_starts_them_again(fold):
    x = rows(2 * CHUNK)
    fold(x, ndev=4)
    threads = [t for _, t in ingest._transfers.waiters.values()]
    assert sorted(t.name for t in threads) == [f"tpu-ml-h2d-wait-{d}" for d in range(4)]
    assert all(t.is_alive() and t.daemon for t in threads)
    ingest.release_staging()
    assert not ingest._transfers.waiters
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    _, moved = fold(x)
    (again,) = [t for _, t in ingest._transfers.waiters.values()]
    assert again.is_alive() and again not in threads
    assert samples(moved, "stream").count == 2 * ingest._PIECES


def test_the_names_are_declared_and_the_seeding_program_keeps_its_name():
    counters = {
        "h2d.link_busy_seconds", "h2d.any_link_busy_seconds", "h2d.transfer_bytes",
        "h2d.transfers_failed",
    }
    assert counters | {"h2d.transfer_seconds"} <= names.METRICS
    assert "h2d.transfer_seconds" in names.HISTOGRAMS
    assert not counters & (names.HISTOGRAMS | names.GAUGES)
    assert {
        "h2d.transfer", "kmeans.seed.rounds", "kmeans.seed.reduce",
    } <= names.SPAN_PHASES
    root = Path(__file__).resolve().parent.parent
    spec = json.loads(
        (root / "benchmarks/layer_metrics/kmeans.seed_device_s.json").read_text()
    )["reader"]
    # tests/test_kmeans_resident.py holds the program to this name
    assert spec["program"] == "jit__kmeans_seed"
    assert spec["per_span"] == "kmeans mesh init"
