"""The plain reference: PCA of the uncentred Gram in float64 NumPy, and the
numbers that decide ``correct``. Imports nothing of the program.

``pca_gram_eigh`` follows the published semantics the program states
(``RapidsRowMatrix``: XᵀX with no centring, eigenvectors in descending order,
explained variance as each singular value's share of all of them).
"""

from __future__ import annotations

import numpy as np

BLOCK_ROWS = 16_384
COMPARED = ("pc_gap", "pc_gap_med", "ev_gap", "cos_shortfall")
BROKEN = 1e300  # what a model that cannot be compared reads (JSON has no infinity)


def gram_f64(blocks: list, order: list[int]) -> np.ndarray:
    """XᵀX of the rows ``order`` describes, in blocks of rows. A block that
    stands in the rows several times is multiplied once and counted as often:
    the same sum."""
    n = blocks[0].shape[1]
    total = np.zeros((n, n))
    for kind in sorted(set(order)):
        x = np.asarray(blocks[kind], dtype=np.float64)
        part = np.zeros((n, n))
        for lo in range(0, len(x), BLOCK_ROWS):
            rows = x[lo : lo + BLOCK_ROWS]
            part += rows.T @ rows
        total += order.count(kind) * part
    return total


def pca_from_gram(gram: np.ndarray, k: int):
    """(components [n, k], explained variance [k]) of a Gram matrix."""
    evals, evecs = np.linalg.eigh(gram)
    evals, evecs = evals[::-1], evecs[:, ::-1]
    singular = np.sqrt(np.clip(evals, 0.0, None))
    return evecs[:, :k], (singular / singular.sum())[:k]


def pca_gram_eigh(blocks: list, order: list[int], k: int):
    return pca_from_gram(gram_f64(blocks, order), k)


def split_bf16(x: np.ndarray, pieces: int) -> list[np.ndarray]:
    """x as a sum of ``pieces`` bfloat16 numbers (held in float32), the way
    the chip's matrix unit takes a float32 operand apart."""
    rest = np.asarray(x, dtype=np.float32)
    out = []
    for _ in range(pieces):
        bits = rest.view(np.uint32)
        nearest = bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
        head = (nearest & np.uint32(0xFFFF0000)).view(np.float32)
        out.append(head)
        rest = rest - head
    return out


def gram_bf16_passes(blocks: list, order: list[int], passes: int) -> np.ndarray:
    """The control: the reference's Gram with each product taken as the chip
    takes it at a precision below the configuration's. ``passes`` = 3 is
    ``Precision.HIGH`` (a₁b₁ + a₁b₂ + a₂b₁), 1 is ``DEFAULT`` (a₁b₁); the
    configuration's ``highest`` is six. Sums are kept in float64, so what is
    left is the operands' rounding alone."""
    terms = {1: [(0, 0)], 3: [(0, 0), (0, 1), (1, 0)]}[passes]
    n = blocks[0].shape[1]
    total = np.zeros((n, n))
    for kind in sorted(set(order)):
        part = np.zeros((n, n))
        x = blocks[kind]
        for lo in range(0, len(x), BLOCK_ROWS):
            parts = [p.astype(np.float64) for p in split_bf16(x[lo : lo + BLOCK_ROWS], 2)]
            for i, j in terms:
                part += parts[i].T @ parts[j]
        total += order.count(kind) * part
    return total


def compare(pc, ev, ref_pc: np.ndarray, ref_ev: np.ndarray) -> dict[str, float]:
    """The numbers compared, for one fitted model against the reference.

    ``pc_gap``: the largest distance, over the k components, between the
    fitted unit vector and the reference's of the same place (up to sign); for
    small angles it is the angle; ``pc_gap_med`` is the median over the
    components, which swings less from seed to seed. ``ev_gap``: the largest relative gap of an
    explained-variance entry. ``cos_shortfall``: 1 − the least |cosine|, the
    guarantee ``BASELINE.md`` states. A model of the wrong shape, or with a
    number that is not finite, reads ``BROKEN`` in all."""
    pc = np.asarray(pc, dtype=np.float64)
    ev = np.asarray(ev, dtype=np.float64)
    bad = dict.fromkeys(COMPARED, BROKEN)
    if pc.shape != ref_pc.shape or ev.shape != ref_ev.shape:
        return bad
    if not (np.all(np.isfinite(pc)) and np.all(np.isfinite(ev))):
        return bad
    norms = np.linalg.norm(pc, axis=0)
    if not np.all(norms > 0):
        return bad
    unit = pc / norms
    dots = np.sum(unit * ref_pc, axis=0)
    sign = np.where(dots < 0, -1.0, 1.0)
    gaps = np.linalg.norm(unit - ref_pc * sign, axis=0)
    return {
        "pc_gap": float(gaps.max()),
        "pc_gap_med": float(np.median(gaps)),
        "ev_gap": float((np.abs(ev - ref_ev) / ref_ev).max()),
        "cos_shortfall": float(1.0 - np.abs(dots).min()),
    }
