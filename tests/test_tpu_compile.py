"""Programs of the benchmark's cells compiled at their real shapes for a TPU
v5e that is described and not attached (the TPU's compiler is installed
here): what the compiler refuses, and what its memory analysis says, costs no
chip time. Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture and never at import: one process
at a time may load the TPU's library, and every xdist worker imports every
test file. Keep such compiles in this one file.
"""

import os

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

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


def test_the_newton_program_holds_the_rows_once_at_the_cells_size(topo):
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
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), (M.DATA_AXIS, M.FEAT_AXIS))
    newton = PL.make_distributed_logreg_chunk(
        mesh, reg_param=1e-5, elastic_net_param=0.0, fit_intercept=True,
        chunk_iters=8, tol=0.0,
    )

    def arg(shape, spec, dtype=np.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

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
