"""Streamed-fit pipeline tests (spark.ingest.stream_fold + donated folds).

Three claims, each load-bearing for the out-of-core path:

1. PARITY — streamed fits equal resident fits on identical data (PCA
   per-component |cosine| >= 0.9999, linear coefficients atol <= 1e-5 —
   the ISSUE acceptance bars; in practice the {1,0} pad-mask convention
   makes the folds bit-for-bit so the margins are enormous), including
   weighted rows and a chunk size that does not divide the row count.
2. MEMORY — the full [rows, n] array is never materialized: the largest
   single host->device transfer stays O(chunk), and the carry is O(n**2).
3. OVERLAP — fold dispatch returns while the previous chunk's fold is
   still executing (double buffering via JAX async dispatch), observable
   via StreamFold.overlapped and the ingest.chunk/fold.dispatch/fold.wait
   trace spans.
"""

import itertools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.models.linear import LinearRegression
from spark_rapids_ml_tpu.models.pca import PCA
from spark_rapids_ml_tpu.models.scaler import StandardScaler
from spark_rapids_ml_tpu.ops import linalg as L
from spark_rapids_ml_tpu.spark import ingest
from spark_rapids_ml_tpu.utils.config import get_config, set_config
from spark_rapids_ml_tpu.telemetry import metrics, reset_metrics


@pytest.fixture
def force_streamed(monkeypatch):
    """Drop the cutover to 1 byte (every fit streams) and pin a chunk size
    that does NOT divide the test row counts; restore on exit."""
    old = get_config().stream_fit_max_resident_bytes
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", "128")
    set_config(stream_fit_max_resident_bytes=1)
    yield
    set_config(stream_fit_max_resident_bytes=old)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(17)
    # 1100 rows: not a multiple of the 128-row chunk (ragged tail rides
    # the w=0 pad mask), nor of the 3 partitions
    x = np.asarray(rng.normal(size=(1100, 12)), np.float64)
    coef = rng.normal(size=12)
    y = x @ coef + 0.05 * rng.normal(size=1100)
    w = rng.uniform(0.5, 3.0, size=1100)
    return x, y, w


class TestStreamedParity:
    def test_pca_streamed_matches_resident(self, data, force_streamed):
        x, _, _ = data
        est = PCA().setInputCol("f").setK(5)
        resident_bytes = get_config().stream_fit_max_resident_bytes
        set_config(stream_fit_max_resident_bytes=1 << 31)
        m_res = est.fit(x, num_partitions=3)
        set_config(stream_fit_max_resident_bytes=resident_bytes)
        m_str = est.fit(x, num_partitions=3)
        cos = np.abs(np.sum(m_res.pc * m_str.pc, axis=0))
        assert cos.min() >= 0.9999, cos
        np.testing.assert_allclose(
            m_str.explainedVariance, m_res.explainedVariance, atol=1e-9
        )

    def test_scaler_streamed_matches_resident(self, data, force_streamed):
        x, _, _ = data
        set_config(stream_fit_max_resident_bytes=1 << 31)
        m_res = StandardScaler().fit(x, num_partitions=3)
        set_config(stream_fit_max_resident_bytes=1)
        m_str = StandardScaler().fit(x, num_partitions=3)
        np.testing.assert_allclose(m_str.mean, m_res.mean, atol=1e-12)
        np.testing.assert_allclose(m_str.std, m_res.std, atol=1e-12)

    def test_linreg_streamed_matches_resident_weighted(
        self, data, force_streamed
    ):
        x, y, w = data
        set_config(stream_fit_max_resident_bytes=1 << 31)
        m_res = LinearRegression().fit((x, y, w), num_partitions=3)
        set_config(stream_fit_max_resident_bytes=1)
        m_str = LinearRegression().fit((x, y, w), num_partitions=3)
        np.testing.assert_allclose(
            m_str.coefficients, m_res.coefficients, atol=1e-5
        )
        assert abs(m_str.intercept - m_res.intercept) <= 1e-5

    def test_sharded_chunk_fold_matches_one_shot(self, data):
        """parallel.gram: stacked per-device partials + single finalize
        allreduce == the one-shot GramStats of the concatenated data."""
        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import mesh as M

        x, _, _ = data
        mesh = M.create_mesh()
        ndev = len(jax.devices())
        chunk = 128 // ndev * ndev or ndev
        dt = np.float64
        example = L.GramStats(
            xtx=jax.ShapeDtypeStruct((12, 12), dt),
            col_sum=jax.ShapeDtypeStruct((12,), dt),
            count=jax.ShapeDtypeStruct((), dt),
        )
        res = ingest.stream_fold(
            iter([x]),
            lambda c, xd, wd: G.sharded_gram_fold(c, xd, wd, mesh),
            n=12,
            init=G.init_chunk_carry(example, mesh),
            chunk_rows=chunk,
            put_fn=G.ChunkPut(mesh),
        )
        stats = G.finalize_chunk_fold(res.carry, mesh)
        want = L.gram_stats(jnp.asarray(x))
        np.testing.assert_allclose(stats.xtx, want.xtx, rtol=1e-12)
        np.testing.assert_allclose(stats.col_sum, want.col_sum, rtol=1e-12)
        assert float(stats.count) == 1100.0


class TestStreamedMemory:
    def test_peak_transfer_is_one_chunk_not_full_array(self, data):
        """O(chunk + n^2) evidence: the largest single device_put is one
        fixed-shape chunk (+ its weight vector), far below the [rows, n]
        resident array the old path shipped."""
        x, _, _ = data
        chunk = 128
        res = ingest.stream_fold(
            iter(np.array_split(x, 4)),
            L.gram_fold_step(),
            n=12,
            init=L.init_gram_carry(12, x.dtype),
            chunk_rows=chunk,
        )
        chunk_bytes = chunk * 12 * x.itemsize + chunk * x.itemsize
        assert res.max_put_bytes == chunk_bytes
        assert res.max_put_bytes < x.nbytes / 4
        assert res.rows == 1100
        # 1100 rows / 128-row chunks -> 8 full + 1 ragged = 9 dispatches
        assert res.chunks == 9
        # the carry itself is O(n^2), independent of rows
        assert res.carry.xtx.shape == (12, 12)

    def test_ragged_tail_and_count_exact(self, data):
        x, _, _ = data
        res = ingest.stream_fold(
            iter([x]),
            L.gram_fold_step(),
            n=12,
            init=L.init_gram_carry(12, x.dtype),
            chunk_rows=256,  # 1100 = 4*256 + 76: pad rows ride w=0
        )
        want = L.gram_stats(jnp.asarray(x))
        np.testing.assert_allclose(res.carry.xtx, want.xtx, rtol=1e-12)
        assert float(res.carry.count) == 1100.0


class TestStreamedOverlap:
    @staticmethod
    def heavy_fold(iterations):
        @partial(jax.jit, donate_argnums=0)
        def fold(carry, xc, wc):
            def body(_, c):
                return L.fold_gram_stats(c, xc, wc)

            return jax.lax.fori_loop(0, iterations, body, carry)

        return fold

    def test_dispatch_overlaps_previous_fold(self):
        """Double-buffering observable: with a fold heavy enough to still
        be executing when the host finishes staging the next chunk, at
        least one dispatch must find the carry not-ready. Of chunks put
        whole: a chunk put by pieces has its own observable, below."""
        rng = np.random.default_rng(5)
        x = np.asarray(rng.normal(size=(2048, 128)), np.float64)
        heavy_fold = self.heavy_fold(50)

        # the busy window is scheduler-dependent (CPU async dispatch may
        # finish a fold within the dispatch call itself), so sample a few
        # streams: a genuinely serialized pipeline yields 0 on every one
        for _ in range(8):
            res = ingest.stream_fold(
                iter(np.array_split(x, 8)),
                heavy_fold,
                n=128,
                init=L.init_gram_carry(128, x.dtype),
                chunk_rows=512,
                put_fn=jax.device_put,
            )
            assert res.chunks == 4
            if res.overlapped >= 1:
                break
        else:
            pytest.fail(
                "no fold dispatch observed the previous fold still executing "
                "in any of 8 streams — the pipeline is serialized"
            )

    def test_pieces_are_put_while_the_previous_fold_executes(self, monkeypatch):
        """A chunk put by pieces: the next chunk's first pieces are put, and
        their landings enqueued, while the last chunk's fold still runs (the
        host waits for neither); past ``_PIECES_IN_FLIGHT`` of them it waits
        for the oldest landing, which the fold is ahead of: ``overlapped``
        is not this path's observable."""
        rng = np.random.default_rng(5)
        x = np.asarray(rng.normal(size=(2048, 128)), np.float64)
        heavy, prog = self.heavy_fold(400), ingest._land_piece_prog()
        carries, under_a_fold = [], []

        def fold(carry, xc, wc):
            carries.append(heavy(carry, xc, wc))
            return carries[-1]

        def spy_land(share, piece, at):
            last = carries[-1].xtx if carries else None
            under_a_fold.append(
                last is not None and not last.is_deleted() and not last.is_ready()
            )
            return prog(share, piece, at)

        monkeypatch.setattr(ingest, "_land_piece_prog", lambda: spy_land)
        for _ in range(8):
            res = ingest.stream_fold(
                iter(np.array_split(x, 32)),
                fold,
                n=128,
                init=L.init_gram_carry(128, x.dtype),
                chunk_rows=512,
            )
            assert res.chunks == 4
            if any(under_a_fold):
                break
        else:
            pytest.fail("no piece was landed while a fold was still executing")

    def test_phase_spans_recorded(self, data):
        x, _, _ = data
        reset_metrics()
        res = ingest.stream_fold(
            iter(np.array_split(x, 3)),
            L.gram_fold_step(),
            n=12,
            init=L.init_gram_carry(12, x.dtype),
            chunk_rows=512,
        )
        m = metrics()
        assert m["fold.dispatch"]["count"] == res.chunks
        # each chunk's landing before its verdict, and the terminal wait
        assert m["fold.wait"]["count"] == res.chunks + 1
        # one span per source pull (3 partitions) + the exhausting pull
        assert m["ingest.chunk"]["count"] == 4

    def test_empty_and_mismatched_inputs_raise(self):
        with pytest.raises(ValueError, match="empty dataset"):
            ingest.stream_fold(
                iter([]),
                L.gram_fold_step(),
                n=4,
                init=L.init_gram_carry(4, np.float64),
                chunk_rows=128,
            )
        with pytest.raises(ValueError, match="feature dimension"):
            ingest.stream_fold(
                iter([np.zeros((8, 4)), np.zeros((8, 5))]),
                L.gram_fold_step(),
                n=4,
                init=L.init_gram_carry(4, np.float64),
                chunk_rows=128,
            )


class TestStreamedSpans:
    """The spans and the counter that name a streamed fit's host seconds:
    ingest.stage for every source batch, h2d.put, the verdict's ingest.scan
    and fold.enqueue once a chunk inside fold.dispatch, fold.finalize around
    the one collective (the benchmark's per-layer metrics read them)."""

    @staticmethod
    def fold(x, batches, **kw):
        from spark_rapids_ml_tpu.telemetry import REGISTRY, TIMELINE

        reset_metrics()
        seq = TIMELINE.seq()
        res = ingest.stream_fold(
            iter(np.array_split(x, batches)),
            L.gram_fold_step(),
            n=x.shape[1],
            init=L.init_gram_carry(x.shape[1], x.dtype),
            chunk_rows=512,
            **kw,
        )
        spans = [e for e in TIMELINE.events(seq) if e["cat"] == "span"]
        return res, metrics(), spans, REGISTRY.snapshot()

    @pytest.mark.parametrize("batches", [1, 3, 7])
    def test_scan_once_a_chunk_and_stage_for_every_batch(self, data, batches):
        x, _, _ = data
        res, m, _, _ = self.fold(x, batches)
        assert m["ingest.scan"]["count"] == res.chunks == 3
        # one slice a batch and one more for every chunk boundary inside a
        # batch; a rewritten set's ragged tail is zeroed under one more.
        # Taking a set books no span of its own
        assert m["ingest.stage"]["count"] >= max(batches, res.chunks)
        assert m["ingest.stage"]["count"] <= batches + res.chunks

    @pytest.mark.parametrize("pieces", [False, True])
    @pytest.mark.parametrize("nonfinite", ["raise", "allow"])
    def test_put_and_enqueue_once_a_chunk_inside_dispatch(
        self, data, nonfinite, pieces
    ):
        """A chunk put whole (any ``put_fn`` of the caller's own) has one
        ``h2d.put``, inside ``fold.dispatch``; a chunk put by pieces has
        one a piece, and whatever of them the dispatch is left with (the
        last piece at least, here the chunk's last slice staged) inside."""
        x, _, _ = data
        kw = {} if pieces else {"put_fn": lambda a: jax.device_put(np.array(a))}
        res, m, spans, snap = self.fold(x, 3, nonfinite=nonfinite, **kw)
        assert res.chunks == 3
        puts = [e for e in spans if e["name"] == "h2d.put"]
        inside = [e for e in puts if e["args"].get("parent") == "fold.dispatch"]
        for phase in ("fold.enqueue", "fold.dispatch"):
            assert m[phase]["count"] == res.chunks, phase
        if pieces:
            assert len(puts) == res.chunks * ingest._PIECES
            assert res.chunks <= len(inside) < len(puts)
        else:
            assert len(puts) == len(inside) == res.chunks
        for e in spans:
            if e["name"] in ("fold.enqueue", "ingest.scan"):
                assert e["args"]["parent"] == "fold.dispatch"
        if nonfinite != "allow":
            return
        # nothing asked: fold.dispatch's own seconds are what neither covers
        # (the timeline keeps a span's microseconds whole)
        own = snap.hist("span.self_seconds", phase="fold.dispatch").total
        assert own == pytest.approx(
            m["fold.dispatch"]["seconds"]
            - sum(e["dur"] for e in inside) / 1e6
            - m["fold.enqueue"]["seconds"],
            abs=1e-6 * (len(inside) + 1),
        )

    def test_stage_never_covers_a_dispatch(self, data):
        x, _, _ = data
        _, _, spans, _ = self.fold(x, 3)
        assert not [
            e for e in spans
            if e["name"] == "fold.dispatch"
            and e["args"].get("parent") == "ingest.stage"
        ]

    def test_no_scan_when_nonfinite_is_allowed(self, data):
        x, _, _ = data
        _, m, _, _ = self.fold(x, 3, nonfinite="allow")
        assert "ingest.scan" not in m
        assert m["ingest.stage"]["count"] >= 3

    def test_scan_is_once_a_chunk_and_once_more_after_a_mask(self, data):
        x, _, _ = data
        bad = x.copy()
        bad[5, 2] = np.nan
        res, m, _, _ = self.fold(bad, 3, nonfinite="skip")
        assert res.skipped_rows == 1 and res.rows == len(x) - 1
        # the first chunk is asked, masked, put again and asked again
        assert m["ingest.scan"]["count"] == res.chunks + 1 == 4
        # every piece of that chunk again, into the same device arrays
        assert m["h2d.put"]["count"] == (res.chunks + 1) * ingest._PIECES
        with pytest.raises(ValueError, match="non-finite"):
            self.fold(bad, 3, nonfinite="raise")
        # the verdict that raised booked its seconds, and no fold followed
        assert metrics()["ingest.scan"]["count"] == 1
        assert "fold.enqueue" not in metrics()

    def test_input_in_flight_is_declared_and_bounded(self, data):
        from spark_rapids_ml_tpu.telemetry import names

        assert "fold.input_in_flight" in names.METRICS
        assert "fold.input_in_flight" not in names.HISTOGRAMS | names.GAUGES
        x, _, _ = data
        res, _, _, snap = self.fold(x, 3)
        assert 0 <= snap.counter("fold.input_in_flight") <= res.chunks

    def test_new_span_names_are_declared(self):
        from spark_rapids_ml_tpu.telemetry import names

        assert {
            "ingest.scan", "ingest.stage", "h2d.put", "fold.enqueue",
            "fold.finalize", "model.to_host",
        } <= names.SPAN_PHASES
        assert {"span.self_seconds"} <= names.METRICS & names.HISTOGRAMS

    def test_finalize_records_its_span(self):
        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import mesh as M

        mesh = M.create_mesh()
        example = L.GramStats(
            xtx=jax.ShapeDtypeStruct((4, 4), np.float32),
            col_sum=jax.ShapeDtypeStruct((4,), np.float32),
            count=jax.ShapeDtypeStruct((), np.float32),
        )
        reset_metrics()
        stats = G.finalize_chunk_fold(G.init_chunk_carry(example, mesh), mesh)
        assert stats.xtx.shape == (4, 4)
        assert metrics()["fold.finalize"]["count"] == 1

    def test_stream_fold_does_not_touch_costmodel(self, data, monkeypatch):
        from spark_rapids_ml_tpu.telemetry import costmodel

        def refuse(*a, **kw):
            raise AssertionError("stream_fold called costmodel.capture")

        monkeypatch.setattr(costmodel, "capture", refuse)
        assert not hasattr(ingest, "costmodel")
        x, _, _ = data
        res, _, _, snap = self.fold(x, 3)
        assert res.chunks == 3
        assert snap.counter("costmodel.calls") == 0

    def test_the_fold_program_keeps_the_name_the_benchmark_reads(self):
        """benchmarks/layer_metrics/gram_roofline.json finds the fold in the
        device trace by its module name, ``jit__fold``: a rename breaks this
        test on the CPU and not a metric on the chip."""
        import json
        from pathlib import Path

        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import mesh as M

        mesh = M.create_mesh(devices=jax.devices()[:1])
        prog = G._gram_chunk_fold_prog(mesh, L.DEFAULT_PRECISION, "f32")
        carry = G.init_chunk_carry(
            L.GramStats(
                xtx=jax.ShapeDtypeStruct((8, 8), np.float32),
                col_sum=jax.ShapeDtypeStruct((8,), np.float32),
                count=jax.ShapeDtypeStruct((), np.float32),
            ),
            mesh,
        )
        lowered = prog.lower(
            carry,
            jax.ShapeDtypeStruct((16, 8), np.float32),
            jax.ShapeDtypeStruct((16,), np.float32),
        )
        assert "module @jit__fold" in lowered.as_text()
        spec = json.loads(
            (
                Path(__file__).resolve().parent.parent
                / "benchmarks/layer_metrics/gram_roofline.json"
            ).read_text()
        )
        assert spec["reader"]["program"] == "jit__fold"


class TestStagingSet:
    """The staging set is made in the dtype the device holds, kept, and
    written again only under the buffer rule stated in ``stream_fold``:
    after the arrays put from it are ready, and never where they share its
    memory; rows past a ragged tail are zeroed before the put."""

    N = 6
    CHUNK = 64

    @pytest.fixture(autouse=True)
    def empty_holder(self):
        ingest.release_staging()
        yield
        ingest.release_staging()

    @staticmethod
    def copying_put(seen=None):
        """A put_fn whose result owns its bytes, as a TPU's does."""

        def put(a):
            if seen is not None:
                seen.append(np.array(a))
            return jax.device_put(np.array(a))

        return put

    def fold(self, x, put_fn=None, *, dtype=np.float64, chunk=None, **kw):
        from spark_rapids_ml_tpu.telemetry import REGISTRY

        before = REGISTRY.snapshot()
        res = ingest.stream_fold(
            iter(np.array_split(x, 5)),
            L.gram_fold_step(),
            n=x.shape[1],
            init=L.init_gram_carry(x.shape[1], dtype),
            chunk_rows=chunk or self.CHUNK,
            put_fn=put_fn,
            **kw,
        )
        return res, REGISTRY.snapshot().delta(before)

    @staticmethod
    def states(moved):
        return {
            state: int(moved.counter("stage.buffers", state=state))
            for state in ("reused", "fresh", "aliased")
        }

    def rows(self, n_rows, scale=1.0, seed=3):
        rng = np.random.default_rng(seed)
        return np.asarray(rng.normal(size=(n_rows, self.N)), np.float64) * scale

    def test_ragged_tail_after_full_chunks_is_one_numpy_pass(self):
        # three full chunks and 23 rows more, all of large values
        x = self.rows(3 * self.CHUNK + 23, scale=1e150)
        seen = []
        res, moved = self.fold(x, self.copying_put(seen), nonfinite="allow")
        assert res.chunks == 4 and res.rows == len(x)
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)
        np.testing.assert_allclose(res.carry.col_sum, x.sum(0), rtol=1e-12)
        assert float(res.carry.count) == len(x)
        assert self.states(moved) == {"reused": 3, "fresh": 1, "aliased": 0}
        last_x, last_w = seen[-2], seen[-1]
        np.testing.assert_array_equal(last_x[:23], x[-23:])
        assert not last_x[23:].any() and not last_w[23:].any()
        assert (last_w[:23] == 1.0).all()

    def test_stale_rows_of_a_kept_set_never_reach_a_fold(self):
        """Under nonfinite="allow" a stale inf times w=0 is NaN: the tail of
        a second fold must not see what the first one left in the set."""
        poisoned = self.rows(2 * self.CHUNK)
        poisoned[:, 0] = np.inf
        self.fold(poisoned, self.copying_put(), nonfinite="allow")
        x = self.rows(self.CHUNK + 9, seed=4)
        res, moved = self.fold(x, self.copying_put(), nonfinite="allow")
        assert self.states(moved)["fresh"] == 0
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)
        assert float(res.carry.count) == len(x)

    def test_labels_and_intercept_follow_the_same_rule(self):
        from spark_rapids_ml_tpu.ops import linear as LIN

        x = self.rows(2 * self.CHUNK + 5)
        y = x @ np.arange(1.0, self.N + 1)
        seen = []
        put = self.copying_put(seen)
        for _ in range(2):  # the second fold rewrites the first one's set
            seen.clear()
            res = ingest.stream_fold(
                iter([(x[:70], y[:70]), (x[70:], y[70:])]),
                LIN.linear_fold_step(),
                n=self.N,
                init=LIN.init_linear_carry(self.N + 1, np.float64),
                label_col="y",
                augment_intercept=True,
                chunk_rows=self.CHUNK,
                put_fn=put,
                nonfinite="allow",
            )
            assert res.chunks == 3
            last_x, last_w, last_y = seen[-3:]
            assert (last_x[:5, self.N] == 1.0).all()
            assert not last_x[5:].any() and not last_w[5:].any()
            assert not last_y[5:].any()
            np.testing.assert_array_equal(last_y[:5], y[-5:])

    @pytest.mark.parametrize("x64", [False, True])
    def test_the_set_has_the_dtype_the_device_holds(self, x64):
        x = self.rows(3 * self.CHUNK)
        seen = []

        def put(a):
            seen.append(a)
            return jax.device_put(np.array(a))

        want = np.float64 if x64 else np.float32
        with jax.enable_x64(x64):
            res, moved = self.fold(x, put, dtype=want)
            assert res.chunks == 3
            assert all(
                isinstance(a, np.ndarray) and a.dtype == want for a in seen
            )
            assert res.carry.xtx.dtype == want
            # x of N columns and w; no label, no intercept
            itemsize = np.dtype(want).itemsize
            assert moved.counter("h2d.bytes", path="stream") == (
                res.chunks * self.CHUNK * (self.N + 1) * itemsize
            )
            assert res.max_put_bytes == self.CHUNK * (self.N + 1) * itemsize
            np.testing.assert_allclose(
                res.carry.xtx, x.T @ x, rtol=1e-12 if x64 else 1e-5
            )

    def test_a_put_that_shares_memory_takes_the_buffer_with_it(self):
        x = self.rows(4 * self.CHUNK + 7)
        want = x.T @ x
        # identity: the fold is handed the staging buffers themselves
        res, moved = self.fold(x, lambda a: a)
        assert res.chunks == 5
        assert self.states(moved) == {"reused": 0, "fresh": 1, "aliased": 4}
        np.testing.assert_allclose(res.carry.xtx, want, rtol=1e-12)
        assert not ingest._kept_staging  # and no such set is kept
        # the CPU backend's own device_put aliases an aligned ndarray and
        # copies any other: whichever it does, the answer is the same
        res, moved = self.fold(x)
        states = self.states(moved)
        assert states["fresh"] == 1
        assert states["reused"] + states["aliased"] == res.chunks - 1
        np.testing.assert_allclose(res.carry.xtx, want, rtol=1e-12)
        # a put that copies, as a TPU's does
        ingest.release_staging()
        res, moved = self.fold(x, self.copying_put())
        assert self.states(moved) == {"reused": 4, "fresh": 1, "aliased": 0}
        np.testing.assert_allclose(res.carry.xtx, want, rtol=1e-12)

    @pytest.mark.parametrize("offset", [0, 8])
    def test_shared_memory_is_read_off_the_array(self, offset):
        """``_shares_memory`` against what a write shows: the CPU backend
        aliases a 64-byte-aligned source and copies a misaligned one."""
        raw = np.zeros(4096 * 8 + 128, np.uint8)
        at = (-raw.ctypes.data) % 64 + offset
        buf = raw[at : at + 4096 * 8].view(np.float64)
        placed = jax.block_until_ready(jax.device_put(buf))
        buf[:] = 7.0
        aliased = bool(np.asarray(placed)[0] == 7.0)
        assert ingest._shares_memory(placed, buf) == aliased
        assert not ingest._shares_memory(placed, np.zeros(4096))
        assert ingest._shares_memory(buf[8:], buf)
        assert ingest._shares_memory(object(), buf)  # cannot say: shares

    def test_the_holder_keeps_one_set_between_folds(self):
        x = self.rows(2 * self.CHUNK + 3)
        put = self.copying_put()
        _, moved = self.fold(x, put)
        assert self.states(moved) == {"reused": 2, "fresh": 1, "aliased": 0}
        (kept,) = ingest._kept_staging
        # the same shape again: nothing new is taken
        _, moved = self.fold(x, put)
        assert self.states(moved) == {"reused": 3, "fresh": 0, "aliased": 0}
        assert ingest._kept_staging == [kept] and not kept.placed
        # another shape takes its own, and the holder keeps the newest
        _, moved = self.fold(x, put, chunk=2 * self.CHUNK)
        assert self.states(moved) == {"reused": 1, "fresh": 1, "aliased": 0}
        (newest,) = ingest._kept_staging
        assert newest is not kept and newest.key[0] == 2 * self.CHUNK
        ingest.release_staging()
        assert not ingest._kept_staging

    def test_the_wait_has_a_name_and_the_counter_is_declared(self):
        from spark_rapids_ml_tpu.telemetry import names

        assert "stage.reclaim" in names.SPAN_PHASES
        assert "stage.buffers" in names.METRICS
        assert "stage.buffers" not in names.HISTOGRAMS | names.GAUGES
        reset_metrics()
        res, _ = self.fold(self.rows(3 * self.CHUNK), self.copying_put())
        # one wait for every chunk that rewrites a set, none for a new one
        assert metrics()["stage.reclaim"]["count"] == res.chunks - 1

    def test_a_set_lent_out_is_not_lent_twice(self):
        x = self.rows(2 * self.CHUNK)
        put = self.copying_put()
        self.fold(x, put)
        (kept,) = ingest._kept_staging
        inner = {}

        def nested_put(a):
            if not inner:
                # a second fold of the same shape while the first holds the set
                inner["states"] = self.states(self.fold(x, put)[1])
            return put(a)

        res, _ = self.fold(x, nested_put)
        assert inner["states"] == {"reused": 1, "fresh": 1, "aliased": 0}
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)
        assert len(ingest._kept_staging) == 1

    def test_a_bisection_takes_a_set_of_the_new_shape(self, monkeypatch):
        from spark_rapids_ml_tpu.resilience import faults

        x = self.rows(3 * self.CHUNK)
        faults.reset_faults()
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, "fold.dispatch:oom:1")
        try:
            res, moved = self.fold(x, self.copying_put(), min_chunk_rows=8)
        finally:
            monkeypatch.delenv(faults.FAULT_PLAN_VAR)
            faults.reset_faults()
        assert res.bisections == 1
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)
        (kept,) = ingest._kept_staging
        assert kept.key[0] == self.CHUNK // 2
        assert self.states(moved)["fresh"] == 2


class TestHostPassPool:
    """A batch's host pass — the cast-copy into the staging set, and the
    non-finite check of a chunk where the host is the one to make it — is cut
    by rows over a kept pool of threads when there is enough to cut, and runs
    inline through the same helpers when there is not. Whatever the path: the
    same bytes in the set, the same answers from ``raise`` and ``skip``, the
    same spans."""

    N = 6
    ROW_BYTES = N * 8

    @pytest.fixture(autouse=True)
    def empty_holder(self):
        ingest.release_staging()
        yield
        ingest.release_staging()

    @classmethod
    def cut_small(cls, monkeypatch):
        """Four workers whatever the host has, and blocks of 8 to 32 rows:
        the toys below are cut as a 256 MiB batch is on the chip's host."""
        monkeypatch.setattr(ingest, "_pool_workers", lambda: 4)
        monkeypatch.setattr(ingest, "_POOL_MIN_BLOCK_BYTES", 8 * cls.ROW_BYTES)
        monkeypatch.setattr(ingest, "_POOL_BLOCK_BYTES", 32 * cls.ROW_BYTES)

    @pytest.fixture
    def small_blocks(self, monkeypatch):
        self.cut_small(monkeypatch)

    def rows(self, n_rows, seed=5):
        rng = np.random.default_rng(seed)
        return np.asarray(rng.normal(size=(n_rows, self.N)), np.float64)

    @staticmethod
    def paths(moved):
        return {
            path: int(moved.counter("ingest.batches", path=path))
            for path in ("pool", "inline")
        }

    def fold(self, batches, **kw):
        from spark_rapids_ml_tpu.telemetry import REGISTRY

        before = REGISTRY.snapshot()
        res = ingest.stream_fold(
            iter(batches),
            L.gram_fold_step(),
            n=self.N,
            init=L.init_gram_carry(self.N, np.float64),
            chunk_rows=kw.pop("chunk_rows", 512),
            put_fn=TestStagingSet.copying_put(),
            **kw,
        )
        return res, REGISTRY.snapshot().delta(before)

    @pytest.mark.parametrize(
        "rows, nbytes, workers, want",
        [
            (1000, 1000 * 48, 6, 1),            # a toy: one block, inline
            (65536, (8 << 20) - 1, 6, 1),       # under two 4 MiB blocks
            (65536, 8 << 20, 6, 2),             # two of 4 MiB
            (78125, 78125 * 1024, 6, 6),        # the KMeans cell's batch
            (16384, 256 << 20, 6, 18),          # the PCA cells' batch: 3 rounds
            (65536, 256 << 20, 8, 16),
            (16384, 256 << 20, 1, 1),           # one core to spare: inline
            (3, 256 << 20, 6, 3),               # never more blocks than rows
            (0, 0, 6, 1),
        ],
    )
    def test_blocks_cover_every_row_once(self, monkeypatch, rows, nbytes, workers, want):
        monkeypatch.setattr(ingest, "_pool_workers", lambda: workers)
        blocks = ingest._row_blocks(rows, nbytes)
        assert len(blocks) == want
        assert blocks[0][0] == 0 and blocks[-1][1] == rows
        assert all(a[1] == b[0] for a, b in zip(blocks, blocks[1:]))
        sizes = [stop - start for start, stop in blocks]
        assert max(sizes) - min(sizes) <= max(sizes) // 2 + 1  # even but for the last
        if want > 1:
            assert max(sizes) * (nbytes // rows) <= ingest._POOL_BLOCK_BYTES + nbytes // rows

    def test_the_worker_rule_follows_the_cores_the_process_may_run_on(self, monkeypatch):
        import os

        for cores, want in ((1, 1), (2, 1), (4, 2), (8, 4), (13, 6), (16, 8), (96, 8)):
            monkeypatch.setattr(
                os, "sched_getaffinity", lambda pid, c=cores: set(range(c)), raising=False
            )
            assert ingest._pool_workers() == want

    @pytest.mark.parametrize("rows", [257, 300, 511])
    @pytest.mark.parametrize("extras", [False, True])
    def test_pooled_write_is_bitwise_the_inline_write(
        self, monkeypatch, rows, extras
    ):
        """float64 rows into a float32 set at a ragged fill, with and without
        labels, weights and the intercept column."""
        from spark_rapids_ml_tpu.telemetry import REGISTRY

        x = self.rows(rows) * 1e3
        y = self.rows(rows, seed=6)[:, 0] if extras else None
        w = np.abs(self.rows(rows, seed=7)[:, 0]) if extras else None
        n_eff = self.N + 1 if extras else self.N
        key = (600, n_eff, np.dtype(np.float32), extras)
        sets = {}
        for path in ("inline", "pool"):
            if path == "pool":
                self.cut_small(monkeypatch)
            before = REGISTRY.snapshot()
            s = sets[path] = ingest._StagingSet(key)
            s.write(13, x, y, w, augment_intercept=extras)
            moved = self.paths(REGISTRY.snapshot().delta(before))
            assert moved == {"pool": int(path == "pool"), "inline": int(path == "inline")}
            assert s.dirty == 13 + rows
        for a, b in zip(sets["inline"].buffers(), sets["pool"].buffers()):
            assert a.tobytes() == b.tobytes()
        got = sets["pool"].x
        np.testing.assert_array_equal(got[13 : 13 + rows, : self.N], x.astype(np.float32))
        assert not got[:13].any() and not got[13 + rows :].any()
        if extras:
            assert (got[13 : 13 + rows, self.N] == 1.0).all()

    @pytest.mark.parametrize("value", [np.nan, np.inf, -np.inf])
    @pytest.mark.parametrize("where", ["first", "middle", "last"])
    def test_a_bad_value_in_any_block_is_found(self, small_blocks, value, where):
        x = self.rows(400)
        blocks = ingest._row_blocks(len(x), x.nbytes)
        assert len(blocks) >= 8
        start, stop = {"first": blocks[0], "middle": blocks[len(blocks) // 2],
                       "last": blocks[-1]}[where]
        bad = x.copy()
        bad[start, 1] = value
        bad[stop - 1, self.N - 1] = value
        assert ingest._all_finite(x) and not ingest._all_finite(bad)
        with pytest.raises(ValueError, match=r"^2 non-finite input row\(s\) in a streamed chunk; "
                           "set TPU_ML_NONFINITE_POLICY=skip"):
            self.fold([bad], nonfinite="raise")
        res, moved = self.fold([x[:100], bad, x[:50]], nonfinite="skip")
        assert res.skipped_rows == 2 and res.rows == 100 + 398 + 50
        assert int(moved.counter("rows.nonfinite_skipped")) == 2
        keep = np.ones(len(x), bool)
        keep[[start, stop - 1]] = False
        kept = np.concatenate([x[:100], x[keep], x[:50]])
        np.testing.assert_allclose(res.carry.xtx, kept.T @ kept, rtol=1e-12)

    def test_a_bad_label_or_weight_is_still_found_on_the_callers_thread(self, small_blocks):
        from spark_rapids_ml_tpu.ops import linear as LIN

        x = self.rows(400)
        y = x[:, 0].copy()
        y[399] = np.inf
        with pytest.raises(ValueError, match=r"^1 non-finite input row"):
            ingest.stream_fold(
                iter([(x, y)]), LIN.linear_fold_step(), n=self.N, label_col="y",
                init=LIN.init_linear_carry(self.N, np.float64), chunk_rows=512,
            )

    @pytest.mark.parametrize("rows", [0, 1, 7, 400])
    def test_all_finite_is_numpys_answer(self, small_blocks, rows):
        x = self.rows(rows)
        assert ingest._all_finite(x) is True
        if rows:
            x[rows // 2, 3] = np.nan
            assert ingest._all_finite(x) is False

    @pytest.mark.parametrize("nonfinite", ["raise", "allow"])
    def test_a_workers_error_reaches_the_caller_and_the_set_goes_back(
        self, small_blocks, nonfinite
    ):
        """An object column no cast can take: the copy's worker fails
        (ValueError) whatever the policy, before anything is asked."""
        x = self.rows(400)
        self.fold([x])
        (kept,) = ingest._kept_staging
        broken = x.astype(object)
        broken[399, 0] = "not a number"
        with pytest.raises(ValueError):
            self.fold([x[:64], broken], nonfinite=nonfinite)
        assert ingest._kept_staging == [kept] and not kept.placed
        # every other block was written before the error came back
        np.testing.assert_array_equal(kept.x[64 : 64 + 360], x[:360])
        # and the pool still serves
        res, moved = self.fold([x])
        assert self.paths(moved)["pool"] == 1
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)

    def test_a_batch_is_booked_by_the_path_its_copy_took(self, monkeypatch):
        """The real sizes: under two blocks of 4 MiB a batch runs inline."""
        from spark_rapids_ml_tpu.telemetry import REGISTRY, names

        assert "ingest.batches" in names.METRICS
        assert "ingest.batches" not in names.HISTOGRAMS | names.GAUGES
        monkeypatch.setattr(ingest, "_pool_workers", lambda: 4)
        rows = (8 << 20) // 8
        s = ingest._StagingSet((rows, 1, np.dtype(np.float32), False))
        for take, path in ((rows - 1, "inline"), (rows, "pool")):
            before = REGISTRY.snapshot()
            s.write(0, np.ones((take, 1)), None, None)
            moved = self.paths(REGISTRY.snapshot().delta(before))
            assert moved == {"pool": int(path == "pool"), "inline": int(path == "inline")}
        # a host with no core to spare never cuts
        monkeypatch.setattr(ingest, "_pool_workers", lambda: 1)
        before = REGISTRY.snapshot()
        s.write(0, np.ones((rows, 1)), None, None)
        assert self.paths(REGISTRY.snapshot().delta(before)) == {"pool": 0, "inline": 1}

    def test_every_toy_runs_inline_and_starts_no_thread(self, data):
        x, _, _ = data
        res, moved = self.fold(np.array_split(x[:, : self.N], 5))
        assert self.paths(moved) == {"pool": 0, "inline": 5 + res.chunks - 1}
        assert not ingest._pool

    @pytest.mark.parametrize("batches", [1, 3, 7])
    def test_spans_a_batch_are_what_they_were(self, small_blocks, batches):
        """One ``ingest.stage`` a batch and one ``ingest.scan`` a chunk, on
        the caller's thread; the workers (and the bounded wait's) open none.
        A transfer's own span is booked by the thread that waits for it."""
        import threading

        from spark_rapids_ml_tpu.telemetry import TIMELINE

        x = self.rows(1100)
        reset_metrics()
        seq = TIMELINE.seq()
        res, moved = self.fold(np.array_split(x, batches))
        m = metrics()
        assert self.paths(moved)["pool"] >= batches
        assert m["ingest.scan"]["count"] == res.chunks
        assert max(batches, res.chunks) <= m["ingest.stage"]["count"] <= batches + res.chunks
        spans = [
            e for e in TIMELINE.events(seq)
            if e["cat"] == "span" and e["name"] != "h2d.transfer"
        ]
        assert {e["tid"] for e in spans} == {threading.get_native_id()}
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)

    def test_folds_on_many_threads_share_one_pool_and_stay_exact(self, small_blocks):
        """More callers than workers, the interpreter switching threads
        every 10 us: a lost or crossed block would show in a Gram."""
        import sys
        import threading

        xs = [self.rows(700 + 13 * i, seed=20 + i) for i in range(8)]
        out = {}

        def caller(i):
            for _ in range(3):
                res = ingest.stream_fold(
                    iter(np.array_split(xs[i], 3)),
                    L.gram_fold_step(),
                    n=self.N,
                    init=L.init_gram_carry(self.N, np.float64),
                    chunk_rows=256,
                    put_fn=TestStagingSet.copying_put(),
                )
                out.setdefault(i, []).append(np.asarray(res.carry.xtx))

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=caller, args=(i,)) for i in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads)
        finally:
            sys.setswitchinterval(interval)
        (pool,) = ingest._pool
        assert len(pool.threads) == 4 and all(t.daemon for t in pool.threads)
        for i, x in enumerate(xs):
            assert len(out[i]) == 3
            for got in out[i]:
                np.testing.assert_allclose(got, x.T @ x, rtol=1e-12)

    def test_release_staging_drops_the_pool_and_the_next_batch_starts_another(
        self, small_blocks
    ):
        x = self.rows(400)
        self.fold([x])
        (pool,) = ingest._pool
        assert all(t.is_alive() and t.daemon for t in pool.threads)
        ingest.release_staging()
        assert not ingest._pool
        for t in pool.threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in pool.threads)
        res, moved = self.fold([x])
        assert self.paths(moved)["pool"] == 1 and ingest._pool[0] is not pool
        np.testing.assert_allclose(res.carry.xtx, x.T @ x, rtol=1e-12)


class TestChunkVerdict:
    """Whether a chunk is finite is asked once a chunk, of the chunk that was
    put and before its fold: on its device where ``put`` returned a
    ``jax.Array`` (program ``jit__chunk_finite``), on the host of the staged
    buffers where it handed back host arrays. ``raise``, ``skip`` and
    ``allow`` give what the per-batch host scan gave."""

    N = 5
    CHUNK = 64
    ROWS = 3 * CHUNK + 21  # three full chunks and a ragged fourth
    # bad rows in the first chunk (two), a middle one and the ragged last
    BAD = (3, 40, 2 * CHUNK + 7, 3 * CHUNK + 20)
    PUTS = {"device": None, "identity": staticmethod(lambda a: a)}

    @pytest.fixture(autouse=True)
    def empty_holder(self):
        ingest.release_staging()
        yield
        ingest.release_staging()

    def rows(self, seed=11):
        rng = np.random.default_rng(seed)
        x = np.asarray(rng.normal(size=(self.ROWS, self.N)), np.float64)
        y = x @ np.arange(1.0, self.N + 1) + 0.1 * rng.normal(size=self.ROWS)
        w = rng.uniform(0.5, 2.0, size=self.ROWS)
        return x, y, w

    @staticmethod
    def plant(x, y, w, fault, rows):
        x, y, w = x.copy(), y.copy(), w.copy()
        for i in rows:
            if fault == "label":
                y[i] = np.nan
            elif fault == "weight":
                w[i] = np.inf
            else:
                x[i, i % x.shape[1]] = {"nan": np.nan, "+inf": np.inf, "-inf": -np.inf}[fault]
        return x, y, w

    def fold(self, x, y, w, *, put="device", batches=3, fold_fn=None, **kw):
        from spark_rapids_ml_tpu.ops import linear as LIN
        from spark_rapids_ml_tpu.telemetry import REGISTRY, TIMELINE

        reset_metrics()
        seq = TIMELINE.seq()
        before = REGISTRY.snapshot()
        cuts = np.array_split(np.arange(len(x)), batches)
        res = ingest.stream_fold(
            iter([(x[c], y[c], w[c]) for c in cuts]),
            fold_fn or LIN.linear_fold_step(),
            n=self.N,
            init=LIN.init_linear_carry(self.N, np.float64),
            label_col="y",
            weight_col="w",
            chunk_rows=kw.pop("chunk_rows", self.CHUNK),
            put_fn=self.PUTS[put],
            **kw,
        )
        spans = [e for e in TIMELINE.events(seq) if e["cat"] == "span"]
        return res, REGISTRY.snapshot().delta(before), spans

    @staticmethod
    def verdicts(moved):
        return {
            (where, clean): int(moved.counter("ingest.verdicts", where=where, clean=clean))
            for where in ("device", "host") for clean in ("yes", "no")
            if moved.counter("ingest.verdicts", where=where, clean=clean)
        }

    @staticmethod
    def assert_stats_equal(got, want):
        for name in want._fields:
            np.testing.assert_allclose(
                np.asarray(getattr(got, name)), np.asarray(getattr(want, name)),
                rtol=1e-11, atol=1e-11, err_msg=name,
            )

    @pytest.mark.parametrize("fault", ["nan", "+inf", "-inf", "label", "weight"])
    @pytest.mark.parametrize("nonfinite", ["raise", "skip", "allow"])
    @pytest.mark.parametrize("put", ["device", "identity"])
    def test_the_three_policies_wherever_the_chunk_is_asked(self, put, nonfinite, fault):
        x, y, w = self.rows()
        bx, by, bw = self.plant(x, y, w, fault, self.BAD)
        where = "device" if put == "device" else "host"
        if nonfinite == "raise":
            # the first chunk holds two of the four bad rows: the chunk's count
            with pytest.raises(ValueError) as err:
                self.fold(bx, by, bw, put=put, nonfinite="raise")
            assert str(err.value) == (
                "2 non-finite input row(s) in a streamed chunk; set "
                "TPU_ML_NONFINITE_POLICY=skip to drop and count them instead"
            )
            assert metrics()["ingest.scan"]["count"] == 1
            assert "fold.enqueue" not in metrics()
            return
        res, moved, spans = self.fold(bx, by, bw, put=put, nonfinite=nonfinite)
        assert res.chunks == 4
        if nonfinite == "allow":
            assert res.rows == self.ROWS and res.skipped_rows == 0
            assert not self.verdicts(moved)
            assert not [e for e in spans if e["name"] == "ingest.scan"]
            assert moved.counter("rows.nonfinite_skipped") == 0
            # and the bad rows reached the carry
            assert not all(
                np.isfinite(np.asarray(leaf)).all() for leaf in res.carry
            )
            return
        keep = np.ones(self.ROWS, bool)
        keep[list(self.BAD)] = False
        assert res.skipped_rows == len(self.BAD)
        assert res.rows == self.ROWS - len(self.BAD)
        assert moved.counter("rows.nonfinite_skipped") == len(self.BAD)
        # chunks 1, 3 and 4 said no and were asked again; chunk 2 was clean
        assert self.verdicts(moved) == {(where, "yes"): 4, (where, "no"): 3}
        want, _, _ = self.fold(x[keep], y[keep], w[keep], put=put, nonfinite="allow")
        self.assert_stats_equal(res.carry, want.carry)
        assert float(res.carry.count) == pytest.approx(w[keep].sum(), rel=1e-12)

    @pytest.mark.parametrize("put", ["device", "identity"])
    def test_a_chunk_left_with_no_true_row_is_not_folded(self, put):
        x, y, w = self.rows()
        middle = range(self.CHUNK, 2 * self.CHUNK)
        bx, by, bw = self.plant(x, y, w, "nan", middle)
        res, moved, spans = self.fold(bx, by, bw, put=put, nonfinite="skip")
        assert res.chunks == 3 and res.skipped_rows == self.CHUNK
        assert res.rows == self.ROWS - self.CHUNK
        assert len([e for e in spans if e["name"] == "fold.enqueue"]) == 3
        # asked once: there was nothing left to put again
        assert sum(self.verdicts(moved).values()) == 4
        keep = np.ones(self.ROWS, bool)
        keep[list(middle)] = False
        want, _, _ = self.fold(x[keep], y[keep], w[keep], put=put, nonfinite="allow")
        self.assert_stats_equal(res.carry, want.carry)
        # every row bad: nothing is folded at all
        with pytest.raises(ValueError, match="empty dataset"):
            self.fold(*self.plant(x, y, w, "nan", range(self.ROWS)), put=put,
                      nonfinite="skip")

    @pytest.mark.parametrize("put", ["device", "identity"])
    @pytest.mark.parametrize("x64", [False, True])
    def test_the_verdict_reads_the_dtype_the_device_holds(self, put, x64):
        """THE ONE DIFFERENCE from the per-batch float64 scan: a finite
        float64 beyond float32's range is ``inf`` once staged for a float32
        device, and so a non-finite row; on a float64 wire it is not."""
        x = self.rows()[0]
        x[70, 2] = 1e300
        dtype = np.float64 if x64 else np.float32

        def fold(nonfinite):
            from spark_rapids_ml_tpu.telemetry import REGISTRY

            before = REGISTRY.snapshot()
            res = ingest.stream_fold(
                iter([x]), L.gram_fold_step(), n=self.N,
                init=L.init_gram_carry(self.N, dtype), chunk_rows=self.CHUNK,
                put_fn=self.PUTS[put], nonfinite=nonfinite,
            )
            return res, REGISTRY.snapshot().delta(before)

        with jax.enable_x64(x64), np.errstate(over="ignore"):
            if x64:
                res, moved = fold("raise")
                assert res.rows == self.ROWS and res.skipped_rows == 0
                assert {c for _, c in self.verdicts(moved)} == {"yes"}
                return
            with pytest.raises(ValueError, match=r"^1 non-finite input row\(s\)"):
                fold("raise")
            res, moved = fold("skip")
            assert res.rows == self.ROWS - 1 and res.skipped_rows == 1
            kept = np.delete(x, 70, axis=0).astype(np.float32).astype(np.float64)
            np.testing.assert_allclose(res.carry.xtx, kept.T @ kept, rtol=1e-4)
            assert np.isfinite(np.asarray(res.carry.xtx)).all()

    def test_the_verdict_is_read_before_its_chunk_is_enqueued(self):
        """The answer is on the host before ``fold.enqueue`` opens, and no
        wait (``fold.wait``, a transfer's) is a child of ``ingest.scan``: the
        span is the program's dispatch and the read of its answer alone."""
        from spark_rapids_ml_tpu.ops import linear as LIN
        from spark_rapids_ml_tpu.telemetry import REGISTRY

        x, y, w = self.rows()
        asked_at_fold = []
        step = LIN.linear_fold_step()

        def fold_fn(carry, xd, yd, wd):
            asked_at_fold.append(
                REGISTRY.snapshot().counter("ingest.verdicts", where="device", clean="yes")
            )
            return step(carry, xd, yd, wd)

        res, moved, spans = self.fold(x, y, w, fold_fn=fold_fn)  # resets the registry
        assert asked_at_fold == [1, 2, 3, 4]
        assert not [e for e in spans if e["args"].get("parent") == "ingest.scan"]
        inside = [e for e in spans if e["args"].get("parent") == "fold.dispatch"]
        # a chunk of CHUNK rows filled by batches of its rows, so the
        # dispatch is left with every piece of the last batch staged: the
        # puts, then the landing waited for, the verdict and the fold
        names = [e["name"] for e in inside]
        per_chunk = [
            list(g) for k, g in itertools.groupby(names, lambda n: n == "h2d.put")
            if not k
        ]
        assert per_chunk == [["fold.wait", "ingest.scan", "fold.enqueue"]] * res.chunks
        assert names[-3:] == ["fold.wait", "ingest.scan", "fold.enqueue"]
        assert res.chunks <= names.count("h2d.put") < res.chunks * ingest._PIECES
        others = [e for e in inside if e["name"] != "h2d.put"]
        for wait, scan, enqueue in zip(*[iter(others)] * 3):
            assert wait["ts"] + wait["dur"] <= scan["ts"]
            assert scan["ts"] + scan["dur"] <= enqueue["ts"]
        # the landings, and the terminal wait outside any dispatch
        waits = [e for e in spans if e["name"] == "fold.wait"]
        assert len(waits) == res.chunks + 1
        assert waits[-1]["args"].get("parent") != "fold.dispatch"

    def test_a_host_array_is_asked_on_the_host_and_waits_for_nothing(self):
        x, y, w = self.rows()
        res, moved, spans = self.fold(x, y, w, put="identity")
        assert self.verdicts(moved) == {("host", "yes"): res.chunks}
        assert len([e for e in spans if e["name"] == "fold.wait"]) == 1
        assert len([e for e in spans if e["name"] == "ingest.scan"]) == res.chunks

    def test_the_retry_of_a_transient_asks_again(self, monkeypatch):
        from spark_rapids_ml_tpu.ops import linear as LIN
        from spark_rapids_ml_tpu.resilience import faults
        from spark_rapids_ml_tpu.resilience import retry as R

        monkeypatch.setattr(R.time, "sleep", lambda s: None)
        x, y, w = self.rows()
        step = LIN.linear_fold_step()
        calls = []

        def flaky(carry, xd, yd, wd):
            calls.append(1)
            if len(calls) == 2:
                raise faults.InjectedTransientIOError("the second fold's enqueue")
            return step(carry, xd, yd, wd)

        res, moved, _ = self.fold(x, y, w, fold_fn=flaky)
        assert res.chunks == 4 and len(calls) == 5
        assert self.verdicts(moved) == {("device", "yes"): 5}
        want, _, _ = self.fold(x, y, w)
        self.assert_stats_equal(res.carry, want.carry)

    def test_the_counter_is_declared_and_the_program_keeps_its_name(self):
        """benchmarks/layer_metrics/ingest.chunk_verdicts.json reads the
        counter by its labels; the device trace shows the program under its
        module name, as ``jit__fold``'s is pinned above."""
        import json
        from pathlib import Path

        from spark_rapids_ml_tpu.telemetry import names

        assert "ingest.verdicts" in names.METRICS
        assert "ingest.verdicts" not in names.HISTOGRAMS | names.GAUGES
        lowered = ingest._chunk_finite_prog().lower(
            [jax.ShapeDtypeStruct((16, 8), np.float32),
             jax.ShapeDtypeStruct((16,), np.float32),
             jax.ShapeDtypeStruct((16,), np.float32)]
        )
        assert "module @jit__chunk_finite" in lowered.as_text()
        spec = json.loads(
            (
                Path(__file__).resolve().parent.parent
                / "benchmarks/layer_metrics/ingest.chunk_verdicts.json"
            ).read_text()
        )
        assert spec["reader"] == {
            "kind": "counter", "counter": "ingest.verdicts",
            "labels": {"where": "device", "clean": "yes"},
        }

    def test_over_a_mesh_the_shards_answers_meet_in_one(self):
        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import mesh as M

        mesh = M.create_mesh()
        put = G.ChunkPut(mesh)
        ndev = len(jax.devices())
        x = np.zeros((8 * ndev, 4))
        w = np.ones(8 * ndev)
        prog = ingest._chunk_finite_prog()
        assert bool(prog([put(x), put(w)])) is True
        for at in (0, 8 * ndev - 1):  # the first device's share, and the last's
            bad = x.copy()
            bad[at, 1] = np.inf
            assert bool(prog([put(bad), put(w)])) is False
        w[3] = np.nan
        assert bool(prog([put(x), put(w)])) is False

    def test_a_put_fn_that_changes_values_is_named(self):
        def poisoning_put(a):
            a = np.array(a)
            if a.ndim == 2:
                a[0, 0] = np.nan
            return jax.device_put(a)

        x, y, w = self.rows()
        for nonfinite in ("raise", "skip"):
            with pytest.raises(ValueError, match="put_fn may place a chunk"):
                ingest.stream_fold(
                    iter([x]), L.gram_fold_step(), n=self.N,
                    init=L.init_gram_carry(self.N, np.float64),
                    chunk_rows=self.CHUNK, put_fn=poisoning_put, nonfinite=nonfinite,
                )


class TestPiecedPut:
    """A chunk that goes where a ``ChunkPut`` says is put by pieces while it
    is staged, into the one chunk-sized set of device arrays the stream owns
    (``ingest._DeviceChunk``); any other ``put_fn`` is handed whole chunks,
    the parent's way, which is what the pieced stream is held against here:
    the same carry to the bit, the same answers from ``raise`` / ``skip`` /
    ``allow``, one device chunk, THE BUFFER RULE piece by piece, the counter,
    and the retries and the bisection ending in the exact Gram."""

    N = 7
    CHUNK = 128  # a piece is 8 rows on one device and 2 on each of four

    @pytest.fixture(autouse=True)
    def empty_holder(self):
        ingest.release_staging()
        yield
        ingest.release_staging()

    def rows(self, n_rows, seed=23):
        rng = np.random.default_rng(seed)
        return np.asarray(rng.normal(size=(n_rows, self.N)), np.float64)

    def fold(self, x, *, ndev=None, pieces=True, fold_fn=None, **kw):
        """One stream over ``x``: on the default device (``ndev`` None) or
        sharded over a mesh of ``ndev``; by pieces, or whole (the same
        placement behind a plain function)."""
        from spark_rapids_ml_tpu.parallel import gram as G
        from spark_rapids_ml_tpu.parallel import mesh as M
        from spark_rapids_ml_tpu.telemetry import REGISTRY

        if ndev is None:
            place = G.ChunkPut(None)
            step = fold_fn or L.gram_fold_step()
            init = L.init_gram_carry(self.N, np.float64)
        else:
            mesh = M.create_mesh(devices=jax.devices()[:ndev])
            place = G.ChunkPut(mesh)
            step = fold_fn or (lambda c, xd, wd: G.sharded_gram_fold(c, xd, wd, mesh))
            init = G.init_chunk_carry(
                L.GramStats(
                    xtx=jax.ShapeDtypeStruct((self.N, self.N), np.float64),
                    col_sum=jax.ShapeDtypeStruct((self.N,), np.float64),
                    count=jax.ShapeDtypeStruct((), np.float64),
                ),
                mesh,
            )
            kw.setdefault("min_chunk_rows", ndev)
        before = REGISTRY.snapshot()
        res = ingest.stream_fold(
            iter(np.array_split(x, 7)),
            step,
            n=self.N,
            init=init,
            chunk_rows=kw.pop("chunk_rows", self.CHUNK),
            put_fn=place if pieces else (lambda a: place(a)),
            **kw,
        )
        return res, REGISTRY.snapshot().delta(before)

    @staticmethod
    def assert_same_carry(got, want):
        for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("ndev", [None, 1, 4])
    @pytest.mark.parametrize(
        "n_rows", [CHUNK, 3 * CHUNK, 2 * CHUNK + 23], ids=["one", "several", "ragged"]
    )
    def test_the_carry_is_the_whole_puts_to_the_bit(self, n_rows, ndev):
        x = self.rows(n_rows)
        whole, moved = self.fold(x, ndev=ndev, pieces=False)
        assert moved.counter("h2d.pieces", path="stream") == 0
        pieced, moved = self.fold(x, ndev=ndev)
        assert pieced.chunks == whole.chunks == -(-n_rows // self.CHUNK)
        assert moved.counter("h2d.pieces", path="stream") == (
            pieced.chunks * ingest._PIECES * (ndev or 1)
        )
        self.assert_same_carry(pieced.carry, whole.carry)
        # over a mesh the carry is a slice a device
        total = jax.tree.map(
            lambda a: np.asarray(a).sum(0) if ndev else np.asarray(a), pieced.carry
        )
        np.testing.assert_allclose(total.xtx, x.T @ x, rtol=1e-12)
        assert float(total.count) == n_rows
        assert pieced.max_put_bytes == whole.max_put_bytes
        assert moved.counter("h2d.shards", path="stream") == pieced.chunks * (ndev or 1)

    # a value in the second chunk's seventh piece, and one in the ragged tail
    BAD = {"middle": CHUNK + 6 * (CHUNK // 16) + 5, "tail": 2 * CHUNK + 20}

    @pytest.mark.parametrize("ndev", [None, 4])
    @pytest.mark.parametrize("where", list(BAD))
    @pytest.mark.parametrize("nonfinite", ["raise", "skip", "allow"])
    def test_a_nonfinite_value_gets_the_whole_puts_answer(self, nonfinite, where, ndev):
        x = self.rows(2 * self.CHUNK + 23)
        x[self.BAD[where], 2] = np.inf
        if nonfinite == "raise":
            errors = []
            for pieces in (False, True):
                with pytest.raises(ValueError, match=r"^1 non-finite input row") as e:
                    self.fold(x, ndev=ndev, pieces=pieces, nonfinite=nonfinite)
                errors.append(str(e.value))
            assert errors[0] == errors[1]
            return
        whole, _ = self.fold(x, ndev=ndev, pieces=False, nonfinite=nonfinite)
        pieced, moved = self.fold(x, ndev=ndev, nonfinite=nonfinite)
        self.assert_same_carry(pieced.carry, whole.carry)
        assert (pieced.rows, pieced.skipped_rows) == (whole.rows, whole.skipped_rows)
        skipped = int(nonfinite == "skip")
        assert pieced.skipped_rows == skipped and pieced.rows == len(x) - skipped
        assert np.isfinite(np.asarray(pieced.carry.xtx)).all() == (nonfinite == "skip")
        # the masked chunk goes again, every piece of it, into the same arrays
        assert moved.counter("h2d.pieces", path="stream") == (
            (pieced.chunks + skipped) * ingest._PIECES * (ndev or 1)
        )

    def test_the_stream_never_holds_two_chunks_on_its_device(
        self, monkeypatch, transfers_booked
    ):
        """After every chunk's fold is enqueued, and while the next is
        staged, one array of the chunk's shape is alive, whatever a fold
        still reads: the landings write into it in place."""
        chunk = 112  # a shape no other test leaves behind
        shape = (chunk, self.N)
        piece = (chunk // ingest._PIECES, self.N)

        def alive(of):
            # by buffer: an array's ``addressable_shards`` are arrays too
            return len({
                a.unsafe_buffer_pointer() for a in jax.live_arrays() if a.shape == of
            })

        assert alive(shape) == 0
        seen, pieces_alive = [], []
        flush, write = ingest._Stager.flush, ingest._StagingSet.write

        def spy_flush(stager):
            flush(stager)
            seen.append(alive(shape))

        def spy_write(staged, fill, *a, **kw):
            write(staged, fill, *a, **kw)
            seen.append(alive(shape))
            # a piece's arrays are also held by the thread that waits for
            # its transfer, until they are ready: here they are at once, so
            # once that thread has had its turn
            transfers_booked()
            pieces_alive.append(alive(piece))

        monkeypatch.setattr(ingest._Stager, "flush", spy_flush)
        monkeypatch.setattr(ingest._StagingSet, "write", spy_write)
        res, _ = self.fold(
            self.rows(4 * chunk + 5), nonfinite="allow", chunk_rows=chunk
        )
        assert res.chunks == 5 and len(seen) > 2 * res.chunks
        assert set(seen[1:]) == {1} and seen[0] <= 1
        # and never more pieces beside it than may be in flight
        assert max(pieces_alive) <= ingest._PIECES_IN_FLIGHT
        del res
        assert alive(shape) == 0  # the stream's end lets go of it

    def test_a_device_holds_no_more_pieces_in_flight_than_the_bound(self, monkeypatch):
        """Landings that never end by themselves: before a put that would
        make one piece more than ``_PIECES_IN_FLIGHT`` not landed, the host
        waits for the oldest (span ``h2d.wait`` inside ``h2d.put``)."""
        from spark_rapids_ml_tpu.telemetry import TIMELINE

        class Landing:
            waited = False

            def is_ready(self):
                return self.waited

        prog, wait = ingest._land_piece_prog(), ingest._bounded_wait
        landings, in_flight = [], []

        def spy_land(share, piece, at):
            landings.append(Landing())
            in_flight.append(sum(not t.waited for t in landings))
            return prog(share, piece, at)[0], landings[-1]

        def spy_wait(a, timeout_s, **kw):
            if not isinstance(a, Landing):
                return wait(a, timeout_s, **kw)
            assert a is next(t for t in landings if not t.waited)  # the oldest
            a.waited = True

        monkeypatch.setattr(ingest, "_land_piece_prog", lambda: spy_land)
        monkeypatch.setattr(ingest, "_bounded_wait", spy_wait)
        seq = TIMELINE.seq()
        res, _ = self.fold(self.rows(2 * self.CHUNK))
        assert len(landings) == res.chunks * ingest._PIECES
        assert max(in_flight) == ingest._PIECES_IN_FLIGHT
        waits = [e for e in TIMELINE.events(seq) if e["name"] == "h2d.wait"]
        assert len(waits) == sum(t.waited for t in landings) > 0
        assert {e["args"]["parent"] for e in waits} == {"h2d.put"}

    def test_a_piece_is_rewritten_only_after_its_array_is_ready(self, monkeypatch):
        """THE BUFFER RULE by piece: no row of the staging set is written
        while an array put from it may still be read by its transfer; and
        what a set is reclaimed on is the device chunk, which is ready only
        once every piece has landed."""
        pieces = []  # (first row, rows, the array put from them)
        prog = ingest._land_piece_prog()
        write, reclaim = ingest._StagingSet.write, ingest._StagingSet.reclaim
        writes, reclaimed_on = [], []

        def spy_land(share, piece, at):
            pieces.append((int(at), len(piece[0]), piece[0]))
            return prog(share, piece, at)

        def spy_write(staged, fill, xc, *a, **kw):
            for at, rows, array in pieces:
                if at < fill + len(xc) and fill < at + rows:
                    assert array.is_ready()
            writes.append((fill, len(xc)))
            write(staged, fill, xc, *a, **kw)

        def spy_reclaim(staged, wait=True):
            reclaimed_on.append([(a.shape, a.is_deleted()) for a in staged.placed])
            return reclaim(staged, wait)

        monkeypatch.setattr(ingest, "_land_piece_prog", lambda: spy_land)
        monkeypatch.setattr(ingest._StagingSet, "write", spy_write)
        monkeypatch.setattr(ingest._StagingSet, "reclaim", spy_reclaim)
        res, moved = self.fold(self.rows(3 * self.CHUNK), nonfinite="allow")
        assert len(pieces) == res.chunks * ingest._PIECES
        assert len(writes) >= 7
        # each later chunk, and the holder at the end, asked the device chunk
        assert reclaimed_on == [[((self.CHUNK, self.N), False), ((self.CHUNK,), False)]] * 3
        states = TestStagingSet.states(moved)
        assert states["fresh"] == 1 and states["reused"] + states["aliased"] == 2

    def test_the_resident_ingest_puts_no_piece(self):
        import pyarrow as pa

        from spark_rapids_ml_tpu.parallel import mesh as M
        from spark_rapids_ml_tpu.telemetry import REGISTRY, names

        assert "h2d.pieces" in names.METRICS
        assert "h2d.pieces" not in names.HISTOGRAMS | names.GAUGES
        x = self.rows(500)

        class Frame:
            def count(self):
                return len(x)

            def _parts(self):
                for part in np.array_split(x, 4):
                    flat = pa.array(part.reshape(-1))
                    offsets = pa.array(
                        np.arange(0, part.size + 1, part.shape[1], dtype=np.int32)
                    )
                    yield [pa.RecordBatch.from_arrays(
                        [pa.ListArray.from_arrays(offsets, flat)], names=["f"]
                    )]

        before = REGISTRY.snapshot()
        ing = ingest.stream_to_mesh(
            Frame(), features_col="f", n=self.N, mesh=M.create_mesh(data=4)
        )
        moved = REGISTRY.snapshot().delta(before)
        np.testing.assert_array_equal(np.asarray(ing.xs)[:500], x)
        assert moved.counter("h2d.pieces") == 0
        assert moved.counter("h2d.bytes", path="mesh") > 0

    @pytest.mark.parametrize("ndev", [None, 4])
    @pytest.mark.parametrize(
        "plan, retries, bisections",
        [("fold.dispatch:io:2", 1, 0), ("fold.dispatch:oom:2", 0, 1)],
        ids=["retry", "bisection"],
    )
    def test_a_retry_and_a_bisection_end_in_the_exact_gram(
        self, monkeypatch, plan, retries, bisections, ndev
    ):
        from spark_rapids_ml_tpu.resilience import faults

        x = self.rows(3 * self.CHUNK + 11)
        clean, _ = self.fold(x, ndev=ndev)
        ingest.release_staging()
        faults.reset_faults()
        monkeypatch.setenv(faults.FAULT_PLAN_VAR, plan)
        monkeypatch.setenv("TPU_ML_RETRY_BACKOFF_S", "0")
        try:
            res, moved = self.fold(x, ndev=ndev, min_chunk_rows=8)
        finally:
            monkeypatch.delenv(faults.FAULT_PLAN_VAR)
            faults.reset_faults()
        assert res.bisections == bisections
        assert moved.counter("retry.attempts") == retries
        assert res.rows == len(x)
        if retries:
            # the pieces had landed: the retry puts none again
            self.assert_same_carry(res.carry, clean.carry)
            assert moved.counter("h2d.pieces", path="stream") == (
                res.chunks * ingest._PIECES * (ndev or 1)
            )
        total = np.asarray(res.carry.xtx)
        total = total.sum(0) if ndev else total
        np.testing.assert_allclose(total, x.T @ x, rtol=1e-12)
        # the halves went whole, and the rest of the stream by pieces of a
        # device chunk of the new shape
        if bisections:
            (kept,) = ingest._kept_staging
            assert kept.key[0] == self.CHUNK // 2
            assert moved.counter("h2d.pieces", path="stream") > ingest._PIECES

    @pytest.mark.parametrize("nth", [3, 16, 20])
    def test_a_landing_that_fails_is_made_up_for_at_the_dispatch(self, monkeypatch, nth):
        """A piece put ahead of its chunk is the dispatch's work begun early:
        a landing that raises there what the dispatch retries is left to it
        (nothing more of that chunk is put ahead, and a counter and a
        warning say so), and it puts every piece of the chunk again under its
        retries; one that raises at the dispatch (the 16th: a chunk's last)
        is retried like any transient."""
        from spark_rapids_ml_tpu.resilience import faults

        x = self.rows(2 * self.CHUNK + 9)
        clean, _ = self.fold(x)
        ingest.release_staging()
        prog, calls = ingest._land_piece_prog(), []

        def flaky_land(share, piece, at):
            calls.append(int(at))
            if len(calls) == nth:
                raise faults.InjectedTransientIOError("the landing failed")
            return prog(share, piece, at)

        monkeypatch.setattr(ingest, "_land_piece_prog", lambda: flaky_land)
        monkeypatch.setenv("TPU_ML_RETRY_BACKOFF_S", "0")
        res, moved = self.fold(x)
        self.assert_same_carry(res.carry, clean.carry)
        assert res.chunks == clean.chunks == 3 and res.rows == len(x)
        # the chunk it belonged to went again, whole
        assert len(calls) > res.chunks * ingest._PIECES
        assert moved.counter("retry.attempts") == int(nth == 16)
        assert moved.counter("h2d.put_ahead_abandoned", path="stream") == int(nth != 16)
        # the chunk after the abandoned one goes ahead of its dispatch again
        if nth == 3:
            assert calls[nth : nth + ingest._PIECES] == calls[nth + ingest._PIECES :][: ingest._PIECES]

    @pytest.mark.parametrize("kind", ["hang", "fatal"])
    def test_a_put_ahead_fails_the_stream_where_the_dispatch_would(
        self, monkeypatch, kind
    ):
        """What no retry and no bisection cures ends the stream at the piece
        that met it, once: a device that does not answer within the bound
        (``FoldHangTimeout`` from ``h2d.wait``) is not waited for again at
        every slice staged, and a fatal error is not kept for later."""
        from spark_rapids_ml_tpu.resilience.retry import FoldHangTimeout

        prog, wait = ingest._land_piece_prog(), ingest._bounded_wait
        raised = []

        class Stuck:
            def is_ready(self):
                return False

        def spy_land(share, piece, at):
            if kind == "fatal" and int(at) == 3 * (self.CHUNK // ingest._PIECES):
                raised.append(at)
                raise ValueError("not a device's fault")
            share, landed = prog(share, piece, at)
            return share, Stuck() if kind == "hang" else landed

        def spy_wait(a, timeout_s, **kw):
            if isinstance(a, Stuck):
                raised.append(a)
                raise FoldHangTimeout("h2d.wait did not complete")
            return wait(a, timeout_s, **kw)

        monkeypatch.setattr(ingest, "_bounded_wait", spy_wait)
        error = FoldHangTimeout if kind == "hang" else ValueError
        monkeypatch.setattr(ingest, "_land_piece_prog", lambda: spy_land)
        with pytest.raises(error, match="not a device's fault|h2d.wait did not"):
            self.fold(self.rows(2 * self.CHUNK))
        assert len(raised) == 1

    def test_a_share_of_the_device_chunk_is_made_on_its_own_device(self, monkeypatch):
        """``jnp.zeros(..., device=d)`` fills its shard on the default device
        and copies it to ``d`` (jax 0.9: ``lax.full`` over
        ``make_array_from_callback``), which on four chips left the first
        holding three more shares of a chunk at a stream's start. The shares
        are results of a program that runs on their device."""
        import jax._src.array as jax_array

        def refuse(*a, **kw):
            raise AssertionError("a share was filled elsewhere and copied over")

        devices = jax.devices()[:4]
        key = (((32, self.N), np.dtype(np.float32)), ((32,), np.dtype(np.float32)))
        monkeypatch.setattr(jax_array, "make_array_from_callback", refuse)
        for d in devices:
            x, w = ingest._new_share_prog(key, d)()
            assert x.devices() == w.devices() == {d}
            assert x.shape == (32, self.N) and not np.asarray(x).any()
        x, _ = ingest._new_share_prog(key, None)()
        assert x.devices() == {jax.devices()[0]}
        res, _ = self.fold(self.rows(2 * self.CHUNK + 5), ndev=4)
        assert res.chunks == 3

    def test_the_landing_program_has_a_name_of_its_own(self):
        lowered = ingest._land_piece_prog().lower(
            [jax.ShapeDtypeStruct((16, 4), np.float32)],
            [jax.ShapeDtypeStruct((2, 4), np.float32)],
            jax.ShapeDtypeStruct((), np.int32),
        )
        text = lowered.as_text()
        assert "module @jit__land_piece" in text
        # the share is donated: the update is in place
        assert "tf.aliasing_output = 0" in text or "jax.buffer_donor" in text
