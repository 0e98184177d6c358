"""Programs of the benchmark's cells compiled at their real shapes for a TPU
v5e that is described and not attached (the TPU's compiler is installed
here): what the compiler refuses, and what its memory analysis says, costs no
chip time. Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture and never at import: one process
at a time may load the TPU's library, and every xdist worker imports every
test file. Keep such compiles in this one file.
"""

import functools
import os
import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_ml_tpu.parallel import kmeans as PK
from spark_rapids_ml_tpu.parallel import linear as PL
from spark_rapids_ml_tpu.parallel import mesh as M


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    try:
        described = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever the plug-in raises where it cannot describe one
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile can be written to the persistent cache and never read back
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield described
    jax.config.update("jax_enable_compilation_cache", cached)
    compilation_cache.reset_cache()
    if had is None:
        os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture
def one_chip_mesh(topo):
    return Mesh(np.array(topo.devices[:1]).reshape(1, 1), (M.DATA_AXIS, M.FEAT_AXIS))


def on_mesh(mesh, shape, spec, dtype=np.float32):
    """A shape with its sharding: there is no device to hold an array."""
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def test_the_newton_program_holds_the_rows_once_at_the_cells_size(one_chip_mesh):
    """``logreg3000_fit_resident``: one chip's 524,288 padded rows of 3,001
    float32. Two findings of PR 34, held here at no chip time:

    - the runtime's default layout for a matrix this wide is column-major
      (3,001 pads to 3,008 sublanes, not to 3,072 lanes), so the rows are
      6.31 GB and not 6.44;
    - the statistics' ``x.T * w`` names a temporary the size of the shard,
      and the compiler never writes it: the scaling is fused into the
      product's operand, and what the whole loop holds beside its arguments
      is megabytes. (A walk of the rows in blocks of 8,192, tried in PR 34,
      made the compiler copy the column-major shard whole: 6.44 GB of
      temporaries. PERF.md section 6.)
    """
    rows, d = 524_288, 3_001
    newton = PL.make_distributed_logreg_chunk(
        one_chip_mesh, reg_param=1e-5, elastic_net_param=0.0, fit_intercept=True,
        chunk_iters=8, tol=0.0,
    )
    arg = functools.partial(on_mesh, one_chip_mesh)

    compiled = newton.lower(
        arg((rows, d), P(M.DATA_AXIS, None)), arg((rows,), P(M.DATA_AXIS)),
        arg((rows,), P(M.DATA_AXIS)), arg((d,), P()), arg((), P(), np.int32),
    ).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit__newton")
    assert f"f32[{rows},{d}]{{0,1:T(8,128)}}" in text.split("entry_computation_layout=", 1)[1][:200]
    memory = compiled.memory_analysis()
    assert 4 * rows * d <= memory.argument_size_in_bytes < 4 * rows * 3_072
    assert memory.temp_size_in_bytes < 1e9


def test_a_piece_lands_in_place_at_the_cells_size(topo):
    """``pca2048_fit_stream``: a piece of 32,768 rows of 2,048 float32 (a
    sixteenth of the chunk's 524,288) and of its weights written into the
    device chunk (``spark.ingest._land_piece_prog``, PR 36). The chunk is
    donated, so the program aliases it to its result and holds nothing the
    size of a chunk beside its arguments: one chunk on the device, not two."""
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.spark import ingest

    rows, n = 524_288, 2_048
    piece = rows // ingest._PIECES
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype=np.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = ingest._land_piece_prog().lower(
        [arg((rows, n)), arg((rows,))],
        [arg((piece, n)), arg((piece,))],
        arg((), np.int32),
    ).compile()
    assert compiled.as_text().startswith("HloModule jit__land_piece")
    memory = compiled.memory_analysis()
    chunk = 4 * rows * (n + 1)
    assert memory.alias_size_in_bytes == chunk
    assert memory.argument_size_in_bytes < chunk + 4 * piece * (n + 1) + 4096
    assert memory.temp_size_in_bytes < 4 * piece * (n + 1)


def test_a_share_of_the_device_chunk_is_made_where_it_stays(topo):
    """The zeroed share a stream's pieces land in is the result of a program
    compiled for its own chip (``spark.ingest._new_share_prog``), with no
    argument and no temporary: nothing is filled on chip 0 and copied over
    (what ``jnp.zeros(..., device=d)`` does, and what three four-chip runs
    of PR 36 paid 8.6 GB for on chip 0)."""
    from spark_rapids_ml_tpu.spark import ingest

    rows, n = 524_288, 2_048
    key = (((rows, n), np.dtype(np.float32)), ((rows,), np.dtype(np.float32)))
    for device in topo.devices[:2]:
        compiled = ingest._new_share_prog(key, device).lower().compile()
        assert {s.device_set.pop() for s in compiled.output_shardings} == {device}
        memory = compiled.memory_analysis()
        assert memory.argument_size_in_bytes == 0 and memory.temp_size_in_bytes == 0
        assert 4 * rows * (n + 1) <= memory.output_size_in_bytes < 4 * rows * (n + 1) + 4096


def test_the_lloyd_sums_take_bf16_passes_at_the_cells_size(one_chip_mesh):
    """``kmeans128_fit_resident``: one chip's 6,291,456 padded rows of 128
    float32, k = 1,000, the whole loop of 20 iterations (PR 38). The sums'
    product is the one-hot in bfloat16 against the three bfloat16 parts of
    the rows (``ops.kmeans.exact_bf16_parts``), so of the loop's two
    products only the distances' cross term keeps ``highest``; the parts
    are cut inside the fusion, block by block, and never written (cut
    before the scan they would be 4.83 GB of temporaries)."""
    rows, n, k = 6_291_456, 128, 1_000
    lloyd = PK.make_distributed_kmeans_chunk(one_chip_mesh, chunk_iters=20, tol=0.0)
    arg = functools.partial(on_mesh, one_chip_mesh)

    compiled = lloyd.lower(
        arg((rows, n), P(M.DATA_AXIS, None)), arg((rows,), P(M.DATA_AXIS)),
        arg((k, n), P()), arg((), P(), np.int32),
    ).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit__lloyd")
    products = [ln for ln in text.splitlines() if re.search(r"= \S+ convolution\(", ln)]
    at_highest = [ln for ln in products if "operand_precision={highest,highest}" in ln]
    assert len(at_highest) == 1 and f"f32[8192,{k}]" in at_highest[0]
    (sums,) = [ln for ln in products if ln not in at_highest]
    assert f"f32[{k},{3 * n}]" in sums  # the three parts side by side, one product
    operands = re.search(r"convolution\((%[\w.-]+), (%[\w.-]+)\)", sums).groups()
    dtypes = {re.search(rf"{re.escape(o)} = (\w+)\[", text).group(1) for o in operands}
    # the one-hot goes in as the comparison itself, narrower still
    assert "bf16" in dtypes and dtypes <= {"bf16", "pred"}
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 64e6
    shard = 4 * rows * (n + 1) + 4 * k * n
    assert shard <= memory.argument_size_in_bytes < shard + 4096


def test_the_forest_program_fits_beside_the_rows_at_the_cells_size(one_chip_mesh):
    """``rf3000_fit_resident``: one chip's 524,288 padded rows binned into
    3,000 bytes each, 128 bins, depth 13, 55 features a node, the cell's 5
    trees one at a time over the 317,440 rows a tree keeps of its bootstrap
    (``ops.forest.row_capacity`` of Poisson(1) counts over 500,000 rows). The
    whole forest is one program, ``jit__forest``, and what it holds beside
    its arguments fits the chip with the float32 rows still resident (the
    fit lets them go before the build; this holds it to the stricter
    budget)."""
    from spark_rapids_ml_tpu.parallel import forest as PF

    rows, n, n_bins, trees = 524_288, 3_000, 128, 5
    run = PF.make_sharded_forest(
        one_chip_mesh, max_depth=13, n_bins=n_bins, k_features=55,
        impurity="gini", group=1, capacity=317_440,
    )
    arg = functools.partial(on_mesh, one_chip_mesh)
    compiled = run.lower(
        arg((trees, 2), P(), np.uint32), arg((rows, n), P(M.DATA_AXIS, None), np.uint8),
        arg((rows, 2), P(M.DATA_AXIS, None)), arg((trees, rows), P(None, M.DATA_AXIS)),
        arg((), P()), arg((), P()),
    ).compile()
    assert compiled.as_text().startswith("HloModule jit__forest")
    memory = compiled.memory_analysis()
    bins = rows * n  # a byte a bin: 1.57 GB where int32 bins were 6.29
    assert bins <= memory.argument_size_in_bytes < bins + 32e6
    float_rows = 4 * rows * n
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + float_rows < 15.75 * 2**30
    # a level's selection by pieces holds a tree's rows as words and the
    # level's sorted copy of them beside the rows' bytes: 5,325,043,712 B of
    # temporaries, where the selection by compares held 3,990,602,752
    assert memory.temp_size_in_bytes < 5.5e9


def test_the_regressor_forest_walks_its_levels_in_blocks_beside_the_rows(one_chip_mesh):
    """``rfreg3000_fit_resident``: the classifier cell's rows and bins, a
    regressor's statistics [w, w·y, w·y²] (S = 3, each in three bfloat16
    parts) and its subset of a third of the features, 1,000 slots a node, at
    depth 13 over the 317,440 rows a tree keeps. Held whole, a level's
    histogram at depth 12 is [3, 4,096, 1,000, 128] float32, 6.3 GB, and its
    pieces' sums 30 GB (the root's alone 20.3 GB as the chip lays them out).
    Walked in blocks of slots that hold at most a sixth of the chip
    (``ops.forest.level_budget``), what the program holds beside its
    arguments fits the chip with the float32 rows still resident."""
    from spark_rapids_ml_tpu.ops import forest as FO
    from spark_rapids_ml_tpu.parallel import forest as PF

    rows, n, n_bins, trees, k = 524_288, 3_000, 128, 2, 1_000
    budget = int(15.75 * 2**30) // FO._BLOCK_SHARE
    assert FO.level_blocks(317_440, n, k, n_bins, 3, 13, budget) > 13
    run = PF.make_sharded_forest(
        one_chip_mesh, max_depth=13, n_bins=n_bins, k_features=k,
        impurity="variance", group=1, capacity=317_440, block_bytes=budget,
    )
    arg = functools.partial(on_mesh, one_chip_mesh)
    compiled = run.lower(
        arg((trees, 2), P(), np.uint32), arg((rows, n), P(M.DATA_AXIS, None), np.uint8),
        arg((rows, 3), P(M.DATA_AXIS, None)), arg((trees, rows), P(None, M.DATA_AXIS)),
        arg((), P()), arg((), P()),
    ).compile()
    assert compiled.as_text().startswith("HloModule jit__forest")
    memory = compiled.memory_analysis()
    bins = rows * n
    assert bins <= memory.argument_size_in_bytes < bins + 32e6
    float_rows = 4 * rows * n
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes + float_rows < 15.75 * 2**30
    # a block of slots at most, beside the rows as words, their sorted copy,
    # the kept bytes and the level's bins on every slot as bytes: 8.76 GB
    assert memory.temp_size_in_bytes < 9.0e9
