"""The control of ``correct``: the plain reference put in the program's
place and computed below the configuration's precision has to come out as not
correct under the limits of the configuration's own file, at a size a test
run can hold. The control that is held is one bfloat16 pass
(``Precision.DEFAULT``). The step the contract names for float32 at
``highest``, three passes (``Precision.HIGH``), stays inside every limit here
as it does on the chip, where the float32 sums of the sound program already
read more than the dropped terms add (PERF.md, section 2): that is kept as a
test too, so that nobody takes it for a control that bites. Both were read at
the cells' own sizes on the chip through the estimator's ``precision``
param."""

import json

import numpy as np
import pytest

from benchmarks import data, reference
from benchmarks import manifest as M

CONFIGS = [(c["name"], c["file"]) for c in M.load()["configs"]]
SEEDS = [11, 2_147_483_659, 3_000_000_019]


def toy(seed: int, n: int = 256, k: int = 50, block_rows: int = 4096):
    blocks = data.make_blocks(seed, n, k, block_rows, kinds=4)
    return blocks, data.block_order(8, 4)


def limits_of(file: str) -> dict:
    with open(M.ROOT / file, encoding="utf-8") as f:
        return json.load(f)["limits"]


def control(seed: int, passes: int, k: int = 50) -> dict:
    blocks, order = toy(seed)
    ref_pc, ref_ev = reference.pca_gram_eigh(blocks, order, k)
    pc, ev = reference.pca_from_gram(reference.gram_bf16_passes(blocks, order, passes), k)
    return reference.compare(pc, ev, ref_pc, ref_ev)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name, file", CONFIGS)
def test_control_is_not_correct(name, file, seed):
    limits = limits_of(file)
    read = control(seed, passes=1)
    over = [name for name, limit in limits.items() if read[name] > limit]
    assert "pc_gap_med" in over, (read, limits)


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name, file", CONFIGS)
def test_three_passes_cannot_be_told_from_sound(name, file, seed):
    limits = limits_of(file)
    read = control(seed, passes=3)
    assert all(read[name] <= limit for name, limit in limits.items()), (read, limits)


@pytest.mark.parametrize("seed", SEEDS)
def test_float32_reference_is_correct(seed):
    """The other side: the reference's Gram with float32 operands and sums
    (what ``highest`` stands for) stays inside every limit."""
    blocks, order = toy(seed)
    n = blocks[0].shape[1]
    gram = np.zeros((n, n), dtype=np.float32)
    for kind in order:
        x = blocks[kind].astype(np.float32)
        gram += x.T @ x
    ref_pc, ref_ev = reference.pca_gram_eigh(blocks, order, 50)
    pc, ev = reference.pca_from_gram(gram.astype(np.float64), 50)
    read = reference.compare(pc, ev, ref_pc, ref_ev)
    for _, file in CONFIGS:
        limits = limits_of(file)
        assert all(read[name] <= limit for name, limit in limits.items()), (read, limits)
