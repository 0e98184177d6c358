"""The rule that turns a resident shard's true rows into its padded rows
(``utils.columnar.shard_rows``: an eighth of the octave, not the next power
of two). What ``spark.ingest.stream_to_mesh`` does with it is in
``test_ingest.py``, what the KMeans programs do in ``test_kmeans_resident.py``."""

import numpy as np
import pytest

from spark_rapids_ml_tpu.spark import ingest
from spark_rapids_ml_tpu.utils import columnar
from spark_rapids_ml_tpu.utils.config import get_config

# the table's true rows: the floor, a toy, the first shard over the 8,192-row
# blocks' reach, the benchmark's 6,250,000 and its share on eight chips, and
# both sides of a power of two
ROWS = (1, 128, 1_000, 65_537, 781_250, 6_250_000, 2**23, 2**23 + 1)
BLOCK_ROWS = 8_192  # ops.kmeans.kmeans_stats, parallel.kmeans._blocked, ops.neighbors


@pytest.mark.parametrize("ndev", [1, 2, 3, 4])
@pytest.mark.parametrize("rows", ROWS)
def test_a_shard_holds_its_rows_under_an_eighth_of_padding(rows, ndev):
    floor = get_config().min_bucket
    r = -(-rows // ndev)
    shard = columnar.shard_rows(r)
    assert r <= shard < r * 1.125 + floor
    assert shard <= columnar.bucket_rows(r)  # never over the power of two
    assert shard % floor == 0
    if r > 65_536:
        assert shard % BLOCK_ROWS == 0  # no row-blocked program pads again


def test_the_benchmarks_shard():
    assert columnar.shard_rows(6_250_000) == 6_291_456 == 12 * 524_288
    assert 6_291_456 - 6_250_000 == 41_456


@pytest.mark.parametrize("octave", range(7, 25))
def test_an_octave_has_at_most_eight_shapes_in_rising_order(octave):
    """Every r of a small octave, 4,096 evenly spaced ones and both ends of a
    large one."""
    lo, hi = (1 << (octave - 1)) + 1, 1 << octave
    rs = np.unique(np.linspace(lo, hi, min(hi - lo + 1, 4_096)).astype(np.int64))
    shards = [columnar.shard_rows(int(r)) for r in rs]
    assert shards == sorted(shards)
    assert 1 <= len(set(shards)) <= 8
    assert shards[-1] == hi  # a power of two pads nothing
    assert all(r <= s for r, s in zip(rs, shards))


@pytest.mark.parametrize("floor", [8, 32, 256])
def test_the_floor_is_the_step_where_an_eighth_of_the_octave_is_under_it(floor):
    assert columnar.shard_rows(1, min_bucket=floor) == floor
    assert columnar.shard_rows(floor + 1, min_bucket=floor) == 2 * floor
    # from sixteen floors up the octave's eighth is the step
    r = 16 * floor + 1
    assert columnar.shard_rows(r, min_bucket=floor) == 16 * floor + 2 * floor


def test_bucket_rows_is_still_the_power_of_two():
    """Partition padding, the serve ladder and a fold chunk keep it."""
    assert columnar.bucket_rows(6_250_000) == 2**23
    assert ingest.stream_chunk_rows() == columnar.bucket_rows(ingest.stream_chunk_rows())
