"""The plain reference for a binary logistic-regression fit: IRLS (Newton's
method) in float64 NumPy, and the numbers that decide ``correct``. Imports
nothing of the program.

The objective is the one the program states (``ops.linear.newton_update``,
Spark ML's at ``elasticNetParam`` 0 and no standardization):

    f(w, b) = (1/m) · Σᵢ cᵢ · [log(1 + e^zᵢ) − yᵢ·zᵢ] + λ/2 · ‖w‖²,   zᵢ = xᵢ·w + b

with m = Σ cᵢ, labels 0/1 and the intercept b unpenalised. ``irls`` starts at
zero and takes exactly ``max_iter`` Newton steps, each the exact solve of
(XᵀWX/m + λP)·δ = −∇f with W = p(1−p) and P the identity but for the
intercept's 0. One departure from the program, stated: the program adds a
ridge of √eps(float32) · trace(H)/d (3.45e-4 of the mean diagonal) to the
Hessian it solves with, so that a separable fit stays solvable; the reference
does not. The ridge changes the Hessian and not the gradient, so it slows the
path and leaves the fixed point where it is: each step then leaves 3.45e-4 of
the distance where the exact step leaves its square, which after the steps a
cell runs is under float32's rounding of the weights (``last_step`` says how
far the reference itself still moved).

A block that stands in the rows several times is walked once and weighed by
its multiplicity: the same sums. Rows are walked in blocks of ``BLOCK_ROWS``
so that no temporary grows with them; XᵀWX is taken as AᵀA of the rows scaled
by √W, which NumPy hands to the BLAS as one symmetric rank-k update.
"""

from __future__ import annotations

import numpy as np

from benchmarks.reference import BROKEN, split_bf16  # NumPy alone, as this file

BLOCK_ROWS = 4096
COMPARED = ("coef_gap", "objective_gap", "grad_norm")


def _one_pass(a: np.ndarray) -> np.ndarray:
    """``a`` as the chip's matrix unit takes a float32 operand at
    ``Precision.DEFAULT``: its nearest bfloat16, in float64."""
    return split_bf16(a, 1)[0].astype(np.float64)


def _stats(blocks: list, order: list[int], w: np.ndarray, fit_intercept: bool,
           *, hessian: bool, passes: int | None = None):
    """(hess [d, d] | None, grad [d], loss, m) at ``w`` over all the rows:
    XᵀWX, Xᵀ(y − p) (the ascent direction of −loss), Σ log-loss and the
    rows' count, as the program's NewtonStats are. ``passes`` = 1 is the
    control: the operands of the three products (x·w, XᵀWX, Xᵀr) rounded to
    bfloat16 first, the sums kept in float64."""
    if passes not in (None, 1):
        raise ValueError(f"passes={passes!r}: the control is one bfloat16 pass")
    d = len(w)
    n = d - 1 if fit_intercept else d
    coef, b = w[:n], (w[n] if fit_intercept else 0.0)
    hess = np.zeros((d, d)) if hessian else None
    grad, loss, m = np.zeros(d), 0.0, 0.0
    for kind in sorted(set(order)):
        times = float(order.count(kind))
        xs, ys = blocks[kind]
        for lo in range(0, len(xs), BLOCK_ROWS):
            x = np.asarray(xs[lo : lo + BLOCK_ROWS], dtype=np.float64)
            y = np.asarray(ys[lo : lo + BLOCK_ROWS], dtype=np.float64)
            if passes:
                x = _one_pass(x)
                z = x @ _one_pass(coef) + b
            else:
                z = x @ coef + b
            p = 1.0 / (1.0 + np.exp(-z))
            resid = _one_pass(y - p) if passes else y - p
            g = np.concatenate([x.T @ resid, [resid.sum()]]) if fit_intercept else x.T @ resid
            grad += times * g
            loss += times * float(np.sum(np.logaddexp(0.0, z) - y * z))
            m += times * len(x)
            if hessian:
                a = np.empty((len(x), d))
                a[:, :n] = x
                if fit_intercept:
                    a[:, n] = 1.0
                if passes:  # the program's two operands: the scaled rows, and the rows
                    hess += times * (_one_pass(a * (p * (1.0 - p))[:, None]).T @ a)
                else:
                    a *= np.sqrt(p * (1.0 - p))[:, None]
                    hess += times * (a.T @ a)
    return hess, grad, loss, m


def _penalty(d: int, fit_intercept: bool) -> np.ndarray:
    pen = np.ones(d)
    if fit_intercept:
        pen[-1] = 0.0
    return pen


def evaluate(blocks: list, order: list[int], w, reg_param: float,
             fit_intercept: bool = True) -> tuple[float, np.ndarray]:
    """(f(w), ∇f(w)) in float64, ``w`` with the intercept last."""
    w = np.asarray(w, dtype=np.float64)
    pen = _penalty(len(w), fit_intercept)
    _, grad, loss, m = _stats(blocks, order, w, fit_intercept, hessian=False)
    return (
        loss / m + 0.5 * reg_param * float(np.sum(pen * w * w)),
        -grad / m + reg_param * pen * w,
    )


def irls(blocks: list, order: list[int], max_iter: int, reg_param: float,
         fit_intercept: bool = True, passes: int | None = None) -> dict:
    """``max_iter`` Newton steps from zero. Returns the weights (intercept
    last), the objective there and the gradient at zero (both in full
    float64, whatever ``passes``), and the last step's norm."""
    n = blocks[0][0].shape[1]
    d = n + 1 if fit_intercept else n
    pen = _penalty(d, fit_intercept)
    w, step = np.zeros(d), float("inf")
    for _ in range(max_iter):
        hess, grad, _, m = _stats(blocks, order, w, fit_intercept, hessian=True, passes=passes)
        lam = reg_param * m * pen
        delta = np.linalg.solve(hess + np.diag(lam), grad - lam * w)
        w, step = w + delta, float(np.linalg.norm(delta))
    objective, _ = evaluate(blocks, order, w, reg_param, fit_intercept)
    _, grad0 = evaluate(blocks, order, np.zeros(d), reg_param, fit_intercept)
    return {"w": w, "objective": objective, "last_step": step,
            "grad0_norm": float(np.linalg.norm(grad0)), "iterations": max_iter}


def compare(blocks: list, order: list[int], coef, intercept, ref: dict,
            reg_param: float, fit_intercept: bool = True) -> dict[str, float]:
    """The numbers compared, for one fitted model against the reference.

    ``coef_gap``: ‖w − w_ref‖₂ / ‖w_ref‖₂, the intercept included.
    ``objective_gap``: the float64 objective at the fitted weights against
    the reference's own, relative. ``grad_norm``: the float64 gradient at
    the fitted weights over the gradient at zero, which is the guarantee
    ("the optimum the source's solver reaches") whatever path led there. A
    model of the wrong shape, or with a number that is not finite, reads
    ``BROKEN`` in all."""
    bad = dict.fromkeys(COMPARED, BROKEN)
    want = ref["w"]
    try:
        coef = np.asarray(coef, dtype=np.float64).reshape(-1)
        w = np.concatenate([coef, [float(intercept)]]) if fit_intercept else coef
    except (TypeError, ValueError):
        return bad
    if w.shape != want.shape or not np.all(np.isfinite(w)):
        return bad
    objective, grad = evaluate(blocks, order, w, reg_param, fit_intercept)
    return {
        "coef_gap": float(np.linalg.norm(w - want) / np.linalg.norm(want)),
        "objective_gap": abs(objective - ref["objective"]) / ref["objective"],
        "grad_norm": float(np.linalg.norm(grad)) / ref["grad0_norm"],
    }
