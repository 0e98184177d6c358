"""The deployment ``pca-2048-k50-x4`` at a toy size on the CPU's virtual
devices: ``SparkPCA.fit(df)``, ``mesh-local``, streamed over a mesh of four.
Every chunk sharded by rows over the four, each device folding its quarter
into its own slice of a ``[4, n, n]`` carry, one all-reduce at the end, whose
wait span ``fold.finalize`` covers; against the benchmark's plain reference
(``benchmarks/reference.pca_gram_eigh``) and against the same fit on one
device, inside the limits the PCA cells hold ``correct`` to. And what the
cell's per-layer metrics (``benchmarks/layer_metrics/x4.*.json``) read is
there under the names they read it by."""

import collections
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import data as bench_data, opcount, reference  # noqa: E402
from benchmarks.sources import work  # noqa: E402
from spark_rapids_ml_tpu.parallel import backend as B  # noqa: E402
from spark_rapids_ml_tpu.parallel import gram as G  # noqa: E402
from spark_rapids_ml_tpu.parallel import mesh as M  # noqa: E402
from spark_rapids_ml_tpu.spark import SparkPCA, ingest  # noqa: E402
from spark_rapids_ml_tpu.telemetry import REGISTRY, TIMELINE, names  # noqa: E402
from spark_rapids_ml_tpu.utils.config import get_config, set_config  # noqa: E402

N, K, CHUNK, BLOCK = 64, 8, 4096, 2048
CONFIG = json.loads((ROOT / "benchmarks/configs/pca-2048-k50-x4.json").read_text())
LIMITS = CONFIG["limits"]
X4_METRICS = sorted((ROOT / "benchmarks/layer_metrics").glob("x4.*.json"))
# rows of a fit: whole chunks, a ragged tail, and a tail that four do not divide
ROWS = {"whole_chunks": 4 * CHUNK, "ragged_tail": 3 * CHUNK + 1024, "not_by_four": 2 * CHUNK + 1001}


@pytest.fixture(scope="module")
def session():
    from spark_rapids_ml_tpu.localspark import LocalSparkSession

    s = LocalSparkSession(parallelism=2, num_workers=1)
    yield s
    s.stop()


@pytest.fixture(scope="module")
def blocks():
    """Seeded rows as the cell makes them: 4 kinds that share an eigenbasis."""
    made = bench_data.make_blocks(32, N, K, BLOCK, 4)
    order = bench_data.block_order(4 * CHUNK // BLOCK, 4)
    return bench_data.to_table(made, order), np.concatenate([made[i] for i in order])


@pytest.fixture(autouse=True)
def streamed(monkeypatch):
    """The cell's geometry at the toy size: chunks of 4,096 rows, and the
    resident cutover lowered so that every fit here streams."""
    old = get_config().stream_fit_max_resident_bytes
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", str(CHUNK))
    set_config(stream_fit_max_resident_bytes=1)
    ingest.release_staging()
    yield
    set_config(stream_fit_max_resident_bytes=old)
    ingest.release_staging()


def on_devices(monkeypatch, ndev: int) -> None:
    """Every mesh-local fit builds its mesh from all the devices there are:
    give it the first ``ndev`` of the eight virtual ones."""
    create = M.create_mesh
    monkeypatch.setattr(
        M, "create_mesh",
        lambda *a, **kw: create(*a, **{"devices": jax.devices()[:ndev], **kw}),
    )


def fit(session, table, rows: int):
    est = SparkPCA(**CONFIG["params"]).setK(K).setInputCol(bench_data.COLUMN)
    return est.fit(session.createDataFrame(table.slice(0, rows)))


def inside_limits(read: dict) -> None:
    for name, value in read.items():
        assert value <= LIMITS[name], (name, value, LIMITS[name])


@pytest.mark.parametrize("case", list(ROWS))
def test_four_devices_against_the_plain_reference(session, blocks, monkeypatch, case):
    table, x = blocks
    rows = ROWS[case]
    on_devices(monkeypatch, 4)
    before = REGISTRY.snapshot()
    model = fit(session, table, rows)
    moved = REGISTRY.snapshot().delta(before)
    chunks = -(-rows // CHUNK)
    assert moved.hist("span.seconds", phase="fold.dispatch").count == chunks
    ref_pc, ref_ev = reference.pca_gram_eigh([x[:rows]], [0], K)
    inside_limits(reference.compare(model.pc, model.explainedVariance, ref_pc, ref_ev))


@pytest.mark.parametrize("case", list(ROWS))
def test_one_device_and_four_agree(session, blocks, monkeypatch, case):
    table, _ = blocks
    on_devices(monkeypatch, 1)
    one = fit(session, table, ROWS[case])
    monkeypatch.undo()
    monkeypatch.setenv("TPU_ML_STREAM_CHUNK_ROWS", str(CHUNK))
    on_devices(monkeypatch, 4)
    four = fit(session, table, ROWS[case])
    pc1 = np.asarray(one.pc, dtype=np.float64)
    inside_limits(reference.compare(
        four.pc, four.explainedVariance,
        pc1 / np.linalg.norm(pc1, axis=0), np.asarray(one.explainedVariance),
    ))


def test_each_device_holds_a_quarter_of_every_put_and_a_slice_of_the_carry(
    session, blocks, monkeypatch
):
    table, _ = blocks
    on_devices(monkeypatch, 4)
    puts, carries = [], []
    finalize = G.finalize_chunk_fold

    class SpyChunkPut(G.ChunkPut):
        """What the fold is handed: a chunk's arrays over the shares its
        pieces were landed in (looked at now: the next chunk's landings are
        donated them)."""

        def assemble(self, shape, parts):
            a = super().assemble(shape, parts)
            puts.append(
                (a.shape, [(s.data.shape, s.device) for s in a.addressable_shards])
            )
            return a

    def spy_finalize(carry, mesh):
        carries.append(carry)
        return finalize(carry, mesh)

    monkeypatch.setattr(G, "ChunkPut", SpyChunkPut)
    monkeypatch.setattr(G, "finalize_chunk_fold", spy_finalize)
    fit(session, table, ROWS["ragged_tail"])

    chunks = [shape for shape, _ in puts if len(shape) == 2]
    assert len(chunks) == 4 and len(puts) == 8  # a chunk and its weights, four times
    for shape, shards in puts:
        assert [s for s, _ in shards] == [(CHUNK // 4, *shape[1:])] * 4
        assert {d for _, d in shards} == set(jax.devices()[:4])
    (carry,) = carries
    assert carry.xtx.shape == (4, N, N) and carry.col_sum.shape == (4, N)
    for leaf in jax.tree_util.tree_leaves(carry):
        assert [s.data.shape[0] for s in leaf.addressable_shards] == [1] * 4
        assert len({s.device for s in leaf.addressable_shards}) == 4


@pytest.mark.parametrize("ndev", [1, 4])
def test_shards_put_and_allreduces_counted(session, blocks, monkeypatch, ndev):
    table, _ = blocks
    on_devices(monkeypatch, ndev)
    fit(session, table, ROWS["ragged_tail"])  # compiled, and the staging set kept
    before = REGISTRY.snapshot()
    fit(session, table, ROWS["ragged_tail"])
    moved = REGISTRY.snapshot().delta(before)
    assert moved.counter("h2d.shards", path="stream") == 4 * ndev
    assert moved.counter("h2d.shards") == 4 * ndev  # the resident ingest books none
    assert moved.counter("collective.count", kind="allreduce") == 3
    assert moved.counter("collective.bytes", kind="allreduce") > 0


def test_the_total_is_ready_when_fold_finalize_closes(session, blocks, monkeypatch):
    """One ``fold.finalize`` a fit, under ``compute cov``, and it covers the
    wait: an all-reduce that takes its time is paid for inside the span, not
    by whoever touches the total next."""
    table, _ = blocks
    on_devices(monkeypatch, 4)
    allreduce, trace_range = B.allreduce, G.trace_range
    totals, ready = [], []
    slow = jax.jit(lambda v, m: v + 0.0 * jnp.linalg.matrix_power(m, 64).sum())
    ballast = jnp.ones((768, 768)) / 768.0

    def slow_allreduce(v, mesh, axis):
        totals.append(slow(allreduce(v, mesh, axis), ballast))
        return totals[-1]

    class spy_range:
        def __init__(self, name):
            self.name, self.inner = name, trace_range(name)

        def __enter__(self):
            return self.inner.__enter__()

        def __exit__(self, *exc):
            if self.name == "fold.finalize":
                ready.append([t.is_ready() for t in totals])
            return self.inner.__exit__(*exc)

    monkeypatch.setattr(B, "allreduce", slow_allreduce)
    monkeypatch.setattr(G, "trace_range", spy_range)
    seq = TIMELINE.seq()
    fit(session, table, ROWS["whole_chunks"])
    assert ready == [[True, True, True]]
    spans = collections.Counter(
        (e["name"], e["args"].get("parent"))
        for e in TIMELINE.events(seq) if e["cat"] == "span"
    )
    assert spans[("fold.finalize", "compute cov")] == 1
    assert sum(n for (name, _), n in spans.items() if name == "fold.finalize") == 1


def test_the_allreduce_program_keeps_the_name_the_benchmark_reads():
    """``layer_metrics/x4.allreduce_ms.json`` finds the all-reduce in the
    device trace by its module name, ``jit__psum``: a rename breaks this test
    on the CPU and not a metric on the chip."""
    mesh = M.create_mesh(devices=jax.devices()[:4])
    lowered = B._allreduce_prog(mesh, M.DATA_AXIS).lower(
        jax.ShapeDtypeStruct((4, 8, 8), np.float32)
    )
    assert "module @jit__psum" in lowered.as_text()
    spec = json.loads((ROOT / "benchmarks/layer_metrics/x4.allreduce_ms.json").read_text())
    assert spec["reader"]["program"] == "jit__psum"


@pytest.mark.parametrize("path", X4_METRICS, ids=lambda p: p.stem)
def test_what_an_x4_metric_reads_is_declared(path):
    """Every span and counter a new per-layer metric reads is a name of
    ``telemetry/names.py``; every program, one a test pins; every count of
    work, a function of ``benchmarks/opcount.py`` fed from the configuration."""
    spec = json.loads(path.read_text())["reader"]
    kind = spec["kind"]
    if kind in ("span", "span_zero"):  # span_zero (PR 37): a wait that need not occur
        assert {spec["phase"], spec["per_span"]} <= names.SPAN_PHASES
    elif kind == "counter":
        assert spec["counter"] in names.METRICS
    elif kind == "counter_per_span_s":  # PR 37: the links' busy share
        assert spec["counter"] in names.METRICS and spec["phase"] in names.SPAN_PHASES
    elif kind == "counter_ratio":  # PR 37: a link's rate, the links at once
        assert {spec["counter"], spec["per_counter"]} <= names.METRICS - names.HISTOGRAMS
    elif kind == "trace_program_ms":
        assert spec["program"] == "jit__psum" and spec["per_span"] in names.SPAN_PHASES
    elif kind == "trace_program":
        assert spec["program"] == "jit__fold"
    else:
        assert kind == "step_share"
    if "work" in spec:  # one chip's quarter of a chunk; a whole fit's rows
        by_hand = {"gram_fold": (524288, 2048), "pca_fit": (4194304, 2048)}
        assert work(spec, CONFIG) == getattr(opcount, spec["work"])(*by_hand[spec["work"]])


def test_the_cell_has_its_twelve_metrics():
    # and since PR 37 four of the links: busy share, overlap, rate, the wait
    assert len(X4_METRICS) == 12 + 4
    assert CONFIG["per_chip"]["chunk_rows"] * 4 == int(CONFIG["env"]["TPU_ML_STREAM_CHUNK_ROWS"])
