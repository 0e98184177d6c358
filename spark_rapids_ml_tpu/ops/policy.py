"""Precision policies — the kernels' mixed-precision vocabulary.

A :class:`PrecisionPolicy` names how a kernel's matmuls treat operand and
accumulator dtypes. It is plain data: the numeric behavior lives in the ops
kernels, which accept ``policy=`` and branch on the policy string
(``ops.linalg.policy_matmul``, the distance cross terms of ``ops.kmeans``,
``ops.neighbors`` and ``ops.ivf``).

The invariant every policy must preserve: **accumulators stay in the carry
dtype** (f32/f64). ``bf16_f32acc`` casts only the matmul *operands* to
bfloat16 and forces the MXU to accumulate in f32 via
``preferred_element_type``; ``int8_dist`` quantizes only the distance cross
term of kmeans/knn candidate scoring. The donated-carry fold contract
(tpulint TPL001) and bitwise checkpoint/resume semantics therefore hold
under every policy — a checkpoint written under ``bf16_f32acc`` resumes
bitwise-identically because the carry never changes dtype.

Import-pure apart from :mod:`utils.knobs` (no jax) so the linter and
jax-free worker processes can load it.
"""

from __future__ import annotations

import enum
import os

from spark_rapids_ml_tpu.utils import knobs

PRECISION_POLICY_VAR = knobs.PRECISION_POLICY.name


class PrecisionPolicy(str, enum.Enum):
    """Named mixed-precision kernel policies.

    - ``F32`` — full-precision operands (the matmul ``precision`` knob still
      applies); the seed behavior and the default everywhere.
    - ``BF16_F32ACC`` — matmul operands cast to bfloat16, accumulation
      forced to f32 with ``preferred_element_type``; the result is upcast
      back into the carry dtype. Roughly halves MXU operand bytes (bf16
      tile (16, 128) vs f32 (8, 128)) at ~3 decimal digits of operand
      mantissa.
    - ``INT8_DIST`` — opt-in symmetric int8 quantization of the *distance
      cross term only* (kmeans / knn candidate scoring): int8×int8 matmul
      accumulated in int32, dequantized against f32 norms. Never used for
      Gram/linear accumulation.
    """

    F32 = "f32"
    BF16_F32ACC = "bf16_f32acc"
    INT8_DIST = "int8_dist"


POLICIES: tuple[str, ...] = tuple(p.value for p in PrecisionPolicy)

#: Policies meaningful for accumulation kernels (Gram/moment/linear folds);
#: ``int8_dist`` applies only to distance scoring and is rejected there.
FOLD_POLICIES: tuple[str, ...] = (
    PrecisionPolicy.F32.value,
    PrecisionPolicy.BF16_F32ACC.value,
)


def validate_policy(policy: str, *, allowed: tuple[str, ...] = POLICIES) -> str:
    """Canonicalize ``policy`` (str or :class:`PrecisionPolicy`) or raise."""
    value = policy.value if isinstance(policy, PrecisionPolicy) else policy
    if value not in allowed:
        raise ValueError(
            f"precision policy {value!r} must be one of {allowed}"
        )
    return value


def resolve_policy(policy: str | None,
                   *, allowed: tuple[str, ...] = POLICIES) -> str:
    """Resolve an explicit policy, or ``None`` → the process default from
    ``TPU_ML_PRECISION_POLICY`` (default ``f32``).

    Resolution happens *before* any ``lru_cache``'d program builder sees the
    value, so an env change between calls selects a different cached
    program instead of a stale one.
    """
    if policy is None:
        policy = os.environ.get(PRECISION_POLICY_VAR, PrecisionPolicy.F32.value)
    return validate_policy(policy, allowed=allowed)
