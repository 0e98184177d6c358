"""AOT-compiled model registry: the warm-path half of the serving runtime.

The fit path can afford ``jax.jit``'s lazy compile-on-first-call; a scoring
request cannot — a cold compile is tens of milliseconds to seconds, and the
DataFrame plan machinery around ``Model.transform`` adds host work that
dwarfs a single-row matmul. This module strips both away:

- **Pure kernel extraction.** Each servable model family exposes its
  transform as a pure ``kernel(params, x)`` function over device arrays
  (project, predict_linear, standardize, forest_apply) plus host-side
  ``prepare``/``finalize`` hooks for the parts that are host work in the
  eager path too (PCA's pre-pad standardization, the forest's per-tree
  vote normalization + argmax). The eager ``transform()`` and the serve
  path therefore run the *same* device computation — the serving test
  asserts bitwise equality.

- **AOT compilation at registration.** ``register()`` lowers and compiles
  the kernel for EVERY rung of the serve bucket ladder
  (``serving.buckets.bucket_ladder``) via
  ``jax.jit(kernel).lower(avals).compile()`` — so after registration,
  arbitrary request sizes hit a precompiled signature and steady-state
  serving is a zero-recompile regime (``serve_recompiles_after_warmup``
  is a hard perf-ledger gate). The build lives in an
  ``@functools.lru_cache`` factory keyed by (entry token, bucket), the
  TPL003-sanctioned shape for program construction.

- **Persistent warm start.** Compiles go through the XLA compilation
  cache (``utils.config.enable_compilation_cache``: the directory named by
  ``JAX_COMPILATION_CACHE_DIR``, else ``<repo root>/.jax_cache``), which
  keeps even the fastest kernels — a fresh process re-registering the same
  models warms from disk (``compile.cache_hits > 0``) instead of
  recompiling.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from spark_rapids_ml_tpu.resilience import faults, sites
from spark_rapids_ml_tpu.serving import buckets, hbm
from spark_rapids_ml_tpu.telemetry.registry import REGISTRY
from spark_rapids_ml_tpu.telemetry.timeline import TIMELINE
from spark_rapids_ml_tpu.utils import knobs
from spark_rapids_ml_tpu.utils.config import enable_compilation_cache

logger = logging.getLogger("spark_rapids_ml_tpu.serving")

SWAP_SHADOW_TOLERANCE_VAR = knobs.SWAP_SHADOW_TOLERANCE.name

FAMILIES = ("pca", "linear", "scaler", "forest", "ann")


class SwapRefused(RuntimeError):
    """A hot-swap candidate was refused before publish — shadow-scoring
    divergence past tolerance, or a structural mismatch with the live
    entry. The old version keeps serving; nothing was torn."""

#: Input dtypes a serve request may carry. Integer/bool payloads (JSON
#: numbers decode to them) are widened to float64 first; float16/bfloat16/
#: complex/object payloads are refused — silently widening them would
#: reintroduce the hidden float64 host copy the fast path removed.
ACCEPTED_DTYPES = ("float32", "float64")


def validate_request(x: Any, n_features: int, model: str) -> np.ndarray:
    """Dtype-preserving request validation: returns a ``[rows, n]`` float32
    or float64 matrix without ever forcing a float64 host copy. Raises
    ``ValueError`` (the transport layers' 400) for anything else, naming
    the accepted dtypes."""
    mat = np.asarray(x)
    if mat.dtype.kind in ("i", "u", "b"):
        # JSON integers and bools are exact in f64; widening them is the
        # eager path's behavior too
        mat = mat.astype(np.float64)
    if mat.dtype.name not in ACCEPTED_DTYPES:
        raise ValueError(
            f"unsupported input dtype {mat.dtype.name!r} for {model!r} — "
            f"accepted dtypes: {', '.join(ACCEPTED_DTYPES)} (and integers, "
            "widened to float64)"
        )
    if mat.ndim == 1:
        mat = mat[None, :]
    if mat.ndim != 2 or mat.shape[1] != n_features:
        raise ValueError(
            f"expected [rows, {n_features}] input for {model!r}, "
            f"got shape {mat.shape}"
        )
    return mat


# -- pure serve kernels (params, x) -> out ----------------------------------
# Module-scope so the AOT factory jits stable function objects; each mirrors
# the device computation of the family's eager transform exactly (bitwise
# parity is asserted in tests/test_serving.py).


def _pca_kernel(params, x):
    from spark_rapids_ml_tpu.ops import linalg as L

    (pc,) = params
    return L.project(x, pc)


def _linear_kernel(params, x):
    from spark_rapids_ml_tpu.ops import linear as LIN

    coef, intercept = params
    return LIN.predict_linear(x, coef, intercept)


def _scaler_kernel(params, x, *, with_mean, with_std):
    from spark_rapids_ml_tpu.ops import scaler as S

    mean, std = params
    return S.standardize(x, mean, std, with_mean=with_mean, with_std=with_std)


def _forest_kernel(params, x, *, max_depth):
    from spark_rapids_ml_tpu.ops import forest as FO

    trees, thresholds = params
    return FO.forest_apply(
        FO.TreeArrays(*trees), x, thresholds, max_depth=max_depth
    )


# -- servable entries -------------------------------------------------------

_TOKEN_LOCK = threading.Lock()
_TOKEN_SEQ = 0
_ENTRIES_BY_TOKEN: dict[int, "ServableEntry"] = {}


def _next_token(entry: "ServableEntry") -> int:
    global _TOKEN_SEQ
    with _TOKEN_LOCK:
        _TOKEN_SEQ += 1
        _ENTRIES_BY_TOKEN[_TOKEN_SEQ] = entry
        return _TOKEN_SEQ


@dataclass
class ServableEntry:
    """One registered model: its pure kernel, device params, host hooks,
    and the set of buckets already AOT-compiled (warm)."""

    name: str
    family: str
    model_cls: str
    n_features: int
    kernel: Callable
    params: Any                       # device-array pytree the kernel takes
    prepare: Callable                 # host pre-pad hook, np -> np
    finalize: Callable                # host post hook, (np, true_rows) -> np
    x_dtype: Any                      # device dtype of the padded block
    policy: str = "f32"
    row_axis: int = 0                 # rows axis of the raw kernel output
    token: int = 0
    version: int = 1                  # bumped by every hot-swap of the slot
    warm_buckets: set[int] = field(default_factory=set)
    model: Any = None

    def describe(self) -> dict:
        return {
            "name": self.name,
            "family": self.family,
            "model_class": self.model_cls,
            "n_features": self.n_features,
            "policy": self.policy,
            "version": self.version,
            "buckets": sorted(self.warm_buckets),
        }


@functools.lru_cache(maxsize=None)
def _compiled_for(token: int, bucket: int):
    """AOT build: lower + compile one (entry, bucket) signature. Cached, so
    the warmup loop and any steady-state miss share one executable; the
    compile itself goes through the persistent XLA cache enabled above."""
    import jax

    entry = _ENTRIES_BY_TOKEN[token]
    params_avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), entry.params
    )
    x_aval = jax.ShapeDtypeStruct((bucket, entry.n_features), entry.x_dtype)
    compiled = jax.jit(entry.kernel).lower(params_avals, x_aval).compile()
    REGISTRY.counter_inc(
        "serve.aot_compiles", model=entry.name, bucket=bucket
    )
    return compiled


@functools.lru_cache(maxsize=None)
def _hedge_compiled_for(token: int, bucket: int, device_index: int):
    """AOT build bound to a specific alternate device, for hedged
    dispatch: the straggler re-issue lands on its own executable (and its
    own copy of the params) so it never queues behind the stuck primary.
    Compiled only by an explicit ``warm_hedge`` — never on the request
    path."""
    import jax
    from jax.sharding import SingleDeviceSharding

    entry = _ENTRIES_BY_TOKEN[token]
    sharding = SingleDeviceSharding(jax.devices()[device_index])
    params_avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        entry.params,
    )
    x_aval = jax.ShapeDtypeStruct(
        (bucket, entry.n_features), entry.x_dtype, sharding=sharding
    )
    compiled = jax.jit(entry.kernel).lower(params_avals, x_aval).compile()
    REGISTRY.counter_inc(
        "serve.aot_compiles", model=entry.name, bucket=bucket, device="hedge"
    )
    return compiled


@functools.lru_cache(maxsize=None)
def _hedge_params(token: int, device_index: int):
    """The entry's params replicated onto the hedge device (one copy per
    (entry, device), reused by every hedged dispatch)."""
    import jax

    entry = _ENTRIES_BY_TOKEN[token]
    device = jax.devices()[device_index]
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, device), entry.params
    )


# -- kernel extraction per model family -------------------------------------


def _device_dtype() -> Any:
    """The dtype ``jnp.asarray`` gives a float64 host block — f32 unless
    x64 is enabled, matching every eager transform's conversion."""
    import jax.numpy as jnp

    return jnp.asarray(np.zeros((), np.float64)).dtype


def _identity_prepare(mat: np.ndarray) -> np.ndarray:
    return mat


def _identity_finalize(out: np.ndarray, true_rows: int) -> np.ndarray:
    return out[:true_rows]


def servable_from_model(name: str, model: Any) -> ServableEntry:
    """Extract the pure ``kernel(params, x)`` + host hooks from a fitted
    model. Raises ``TypeError`` for model families without a serve contract
    (see CONTRIBUTING: adding a servable model)."""
    import jax.numpy as jnp

    from spark_rapids_ml_tpu.models.linear import _GLMModel
    from spark_rapids_ml_tpu.models.pca import PCAModel
    from spark_rapids_ml_tpu.models.scaler import StandardScalerModel
    from spark_rapids_ml_tpu.utils import columnar

    x_dtype = _device_dtype()

    if isinstance(model, PCAModel):
        pc = jnp.asarray(model.pc, dtype=x_dtype)
        mean, std = model.mean, model.std

        def prepare(mat, _mean=mean, _std=std):
            # eager parity: standardization is host work applied BEFORE
            # padding so pad rows stay zero (models/pca.py)
            return columnar.standardize_host(mat, _mean, _std)

        return ServableEntry(
            name=name,
            family="pca",
            model_cls=type(model).__name__,
            n_features=int(model.pc.shape[0]),
            kernel=_pca_kernel,
            params=(pc,),
            prepare=prepare,
            finalize=_identity_finalize,
            x_dtype=x_dtype,
            model=model,
        )

    if isinstance(model, _GLMModel) and getattr(model, "coefficients", None) is not None:
        coef = np.asarray(model.coefficients)
        if coef.ndim != 1:
            raise TypeError(
                f"{type(model).__name__} is not single-output — the linear "
                "serve contract covers [n]-coefficient GLMs"
            )
        n = int(coef.shape[0])
        return ServableEntry(
            name=name,
            family="linear",
            model_cls=type(model).__name__,
            n_features=n,
            kernel=_linear_kernel,
            params=(
                jnp.asarray(coef, dtype=x_dtype),
                jnp.asarray(model.intercept, dtype=x_dtype),
            ),
            prepare=_identity_prepare,
            finalize=_identity_finalize,
            x_dtype=x_dtype,
            model=model,
        )

    if isinstance(model, StandardScalerModel):
        n = int(np.asarray(model.std).shape[0])
        return ServableEntry(
            name=name,
            family="scaler",
            model_cls=type(model).__name__,
            n_features=n,
            kernel=functools.partial(
                _scaler_kernel,
                with_mean=model.getWithMean(),
                with_std=model.getWithStd(),
            ),
            params=(jnp.asarray(model.mean), jnp.asarray(model.std)),
            prepare=_identity_prepare,
            finalize=_identity_finalize,
            x_dtype=x_dtype,
            model=model,
        )

    # forest classifier: device descent kernel + the host vote-normalization
    # / argmax decision rule (eager parity: proba_and_predictions)
    trees = getattr(model, "trees", None)
    if trees is not None and hasattr(model, "proba_and_predictions"):
        max_depth = int(np.log2(trees.feature.shape[1] + 1) - 1)
        n = int(model.numFeatures)
        num_trees = int(trees.feature.shape[0])

        def finalize(leaf, true_rows, _t=num_trees):
            leaf = leaf[:, :true_rows]
            tot = leaf.sum(-1, keepdims=True)
            per_tree = np.divide(
                leaf, np.where(tot > 0, tot, 1.0), dtype=leaf.dtype
            )
            proba = per_tree.sum(0) / _t
            return np.argmax(proba, axis=1).astype(np.float64)

        return ServableEntry(
            name=name,
            family="forest",
            model_cls=type(model).__name__,
            n_features=n,
            kernel=functools.partial(_forest_kernel, max_depth=max_depth),
            params=(
                tuple(jnp.asarray(a) for a in trees),
                jnp.asarray(model.thresholds),
            ),
            prepare=_identity_prepare,
            finalize=finalize,
            x_dtype=x_dtype,
            row_axis=1,
            model=model,
        )

    if (
        getattr(model, "bucketItems", None) is not None
        and getattr(model, "centroids", None) is not None
    ):
        # fitted IVF index (ApproximateNearestNeighborsModel or the
        # streamed IVFFlatIndexModel) — the ann subsystem owns the contract
        from spark_rapids_ml_tpu.ann import serving as ann_serving

        return ann_serving.servable_from_index(name, model)

    raise TypeError(
        f"{type(model).__name__} has no serve contract — servable families: "
        f"{', '.join(FAMILIES)} (see CONTRIBUTING, 'Adding a servable model')"
    )


# -- the registry -----------------------------------------------------------


class ModelRegistry:
    """Loads fitted models, AOT-compiles their kernels across the bucket
    ladder, and dispatches padded blocks to the compiled executables."""

    def __init__(self):
        self._entries: dict[str, ServableEntry] = {}
        # prior version of a hot-swapped slot, kept dispatchable (and
        # HBM-resident) until probation clears or rollback restores it
        self._prior: dict[str, ServableEntry] = {}
        self._lock = threading.RLock()
        # (token, device_index) pairs with warm hedge executables + params
        self._hedge_warm: set[tuple[int, int]] = set()

    def register(
        self,
        name: str,
        model: Any,
        *,
        bucket_list: tuple[int, ...] | None = None,
    ) -> ServableEntry:
        """Extract the model's pure kernel and AOT-compile it for every
        bucket in ``bucket_list`` (default: the whole serve ladder). After
        this returns, requests up to the ladder cap never compile."""
        enable_compilation_cache()
        from spark_rapids_ml_tpu.telemetry import compilemon

        compilemon.install_monitoring()
        entry = servable_from_model(name, model)
        entry.token = _next_token(entry)
        ladder = tuple(bucket_list) if bucket_list else buckets.bucket_ladder()
        for b in ladder:
            _compiled_for(entry.token, b)
            entry.warm_buckets.add(b)
        with self._lock:
            self._entries[name] = entry
            REGISTRY.gauge_set("serve.models", len(self._entries))
        REGISTRY.gauge_set("serve.model_version", entry.version, model=name)
        # book the params against the HBM fleet budget; registering past it
        # pages the least-recently-used cold models to host
        hbm.get_fleet().account(entry)
        logger.info(
            "registered servable %s (%s, n=%d, policy=%s, %d buckets)",
            name, entry.family, entry.n_features, entry.policy, len(ladder),
        )
        return entry

    def get(self, name: str) -> ServableEntry:
        with self._lock:
            try:
                return self._entries[name]
            except KeyError:
                raise KeyError(
                    f"no servable model {name!r} (registered: "
                    f"{sorted(self._entries) or 'none'})"
                ) from None

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._entries)

    def describe(self) -> list[dict]:
        with self._lock:
            return [e.describe() for _, e in sorted(self._entries.items())]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self._prior.clear()
            self._hedge_warm.clear()
            REGISTRY.gauge_set("serve.models", 0)

    # -- versioned hot-swap / rollback --------------------------------------

    @staticmethod
    def _prior_key(name: str) -> str:
        return f"{name}@prior"

    def _run_entry(self, entry: ServableEntry, mat: np.ndarray) -> np.ndarray:
        """Score a prepared-dtype host matrix through one specific entry —
        the shadow gate's scorer and ``predict``'s body, minus the name
        lookup (so a gate never races the slot it is gating)."""
        prepared = entry.prepare(mat)
        if prepared.dtype != entry.x_dtype:
            prepared = prepared.astype(entry.x_dtype)
        bucket = buckets.serve_bucket(prepared.shape[0])
        padded, true_rows = buckets.pad_to_bucket(prepared, bucket)
        raw = self.dispatch_padded(entry, padded, bucket)
        return entry.finalize(raw, true_rows)

    @staticmethod
    def _shadow_divergence(
        live_out: np.ndarray, cand_out: np.ndarray
    ) -> float:
        """Relative divergence of the candidate's shadow scores against the
        live model's: max absolute difference over the live output's max
        magnitude. Shape mismatches are infinite divergence."""
        a = np.asarray(live_out, dtype=np.float64)
        b = np.asarray(cand_out, dtype=np.float64)
        if a.shape != b.shape or not (
            np.all(np.isfinite(a)) and np.all(np.isfinite(b))
        ):
            return float("inf")
        scale = float(np.max(np.abs(a))) + 1e-12
        return float(np.max(np.abs(a - b))) / scale

    def shadow_tolerance(self) -> float:
        raw = os.environ.get(SWAP_SHADOW_TOLERANCE_VAR, "").strip()
        try:
            return float(raw) if raw else float(
                knobs.SWAP_SHADOW_TOLERANCE.default
            )
        except ValueError:
            return float(knobs.SWAP_SHADOW_TOLERANCE.default)

    def swap(
        self,
        name: str,
        model: Any,
        *,
        shadow_sample: np.ndarray | None = None,
        tolerance: float | None = None,
        bucket_list: tuple[int, ...] | None = None,
    ) -> ServableEntry:
        """Atomically hot-swap slot ``name`` to a freshly fitted ``model``.

        Everything expensive happens BEFORE the atomic section: the
        candidate's kernel is AOT-compiled across the live entry's warm
        bucket ladder (a swap never compiles on the request path — the
        zero-recompile contract survives the swap), and the shadow-scoring
        gate scores candidate vs live on ``shadow_sample``, raising
        :class:`SwapRefused` past ``tolerance`` (default
        ``TPU_ML_SWAP_SHADOW_TOLERANCE``). The publish itself is one dict
        store under the lock — in-flight dispatches hold their entry
        reference and finish on the old kernel while new admissions route
        to the new one; the lock-hold time is the swap blackout
        (``serve.swap_blackout_seconds``, stamped on the perf ledger as
        ``swap_blackout_ms``).

        The displaced version is retained (HBM-resident, booked under
        ``<name>@prior``) until :meth:`prune_prior` — the probation
        contract — or :meth:`rollback` restores it."""
        live = self.get(name)
        enable_compilation_cache()
        candidate = servable_from_model(name, model)
        if candidate.n_features != live.n_features:
            REGISTRY.counter_inc("serve.swap_refused", model=name,
                                 reason="shape")
            raise SwapRefused(
                f"swap of {name!r} refused: candidate n_features "
                f"{candidate.n_features} != live {live.n_features}"
            )
        candidate.token = _next_token(candidate)
        ladder = (
            tuple(bucket_list) if bucket_list
            else tuple(sorted(live.warm_buckets)) or buckets.bucket_ladder()
        )
        for b in ladder:
            _compiled_for(candidate.token, b)
            candidate.warm_buckets.add(b)
        if shadow_sample is not None and len(shadow_sample):
            sample = validate_request(
                shadow_sample, live.n_features, name
            )
            div = self._shadow_divergence(
                self._run_entry(live, sample),
                self._run_entry(candidate, sample),
            )
            tol = self.shadow_tolerance() if tolerance is None else tolerance
            if div > tol:
                REGISTRY.counter_inc("serve.swap_refused", model=name,
                                     reason="shadow")
                raise SwapRefused(
                    f"swap of {name!r} refused by the shadow gate: "
                    f"relative divergence {div:.3g} > tolerance {tol:.3g} "
                    f"on {len(sample)} held-back rows"
                )
        # the swap barrier: a chaos plan can hang or kill here — both land
        # strictly before the publish, so the old version keeps serving
        # consistently (never a torn slot)
        faults.inject(sites.SERVE_SWAP)
        t0 = time.perf_counter()
        with self._lock:
            prior = self._entries.get(name, live)
            candidate.version = prior.version + 1
            self._entries[name] = candidate
            self._prior[name] = prior
        blackout = time.perf_counter() - t0
        REGISTRY.histogram_record(
            "serve.swap_blackout_seconds", blackout, model=name
        )
        REGISTRY.counter_inc("serve.swaps", model=name)
        REGISTRY.gauge_set(
            "serve.model_version", candidate.version, model=name
        )
        TIMELINE.record_instant(
            "serve.swap", model=name, version=candidate.version
        )
        # the prior stays HBM-resident (rollback must not page) until
        # probation clears; the candidate books under the live key
        fleet = hbm.get_fleet()
        fleet.account(prior, key=self._prior_key(name))
        fleet.account(candidate)
        logger.info(
            "hot-swapped servable %s to version %d (blackout %.3f ms)",
            name, candidate.version, blackout * 1e3,
        )
        return candidate

    def rollback(self, name: str) -> ServableEntry:
        """Restore the retained prior version of ``name`` — the SLO-burn
        probation escape hatch. Atomic like the swap; the demoted candidate
        is dropped from the registry (in-flight dispatches on it still
        finish on their entry reference)."""
        with self._lock:
            prior = self._prior.pop(name, None)
            if prior is None:
                raise KeyError(
                    f"no prior version of {name!r} to roll back to"
                )
            self._entries[name] = prior
        REGISTRY.counter_inc("serve.rollback", model=name)
        REGISTRY.gauge_set("serve.model_version", prior.version, model=name)
        TIMELINE.record_instant(
            "serve.rollback", model=name, version=prior.version
        )
        fleet = hbm.get_fleet()
        fleet.account(prior)  # rebook under the live key, MRU again
        fleet.forget(self._prior_key(name))
        logger.warning(
            "rolled back servable %s to version %d", name, prior.version
        )
        return prior

    def prune_prior(self, name: str) -> bool:
        """Probation cleared: release the retained prior version (its HBM
        booking is forgotten; its executables age out of the AOT cache with
        the token)."""
        with self._lock:
            prior = self._prior.pop(name, None)
        if prior is None:
            return False
        hbm.get_fleet().forget(self._prior_key(name))
        logger.info(
            "pruned prior version %d of servable %s (probation cleared)",
            prior.version, name,
        )
        return True

    def prior_entry(self, name: str) -> ServableEntry | None:
        with self._lock:
            return self._prior.get(name)

    def current_version(self, name: str) -> int:
        return self.get(name).version

    # -- dispatch -----------------------------------------------------------

    def dispatch_padded(
        self, entry: ServableEntry, padded: np.ndarray, bucket: int
    ) -> np.ndarray:
        """Run one padded [bucket, n] block through the compiled executable;
        returns the RAW (still padded) kernel output as a host array. A
        bucket outside the warm set still works — it compiles on demand and
        books ``serve.cold_compiles``, the steady-state anomaly
        tools/serve_report.py flags."""
        import jax.numpy as jnp

        # chaos gate: counted per process, so a fleet plan can kill exactly
        # one replica mid-request (the router's buffered-frame retry is the
        # recovery under test). Before any state — a retry re-enters clean.
        faults.inject(sites.SERVE_DISPATCH)
        # repage the model's params if fleet pressure evicted them to host
        # (touches its LRU clock either way); the compiled executable is
        # shape-keyed and survives paging untouched
        hbm.get_fleet().ensure_resident(entry)
        cold = bucket not in entry.warm_buckets
        compiled = _compiled_for(entry.token, bucket)
        if cold:
            REGISTRY.counter_inc(
                "serve.cold_compiles", model=entry.name, bucket=bucket
            )
            entry.warm_buckets.add(bucket)
        xd = jnp.asarray(padded)  # same conversion the eager transform does
        return np.asarray(compiled(entry.params, xd))

    # -- hedged dispatch (second-device re-issue) ---------------------------

    def warm_hedge(
        self,
        name: str,
        *,
        bucket_list: tuple[int, ...] | None = None,
        device_index: int = 1,
    ) -> int:
        """Pre-compile a model's executables on an alternate device so a
        hedged re-issue runs there instead of queueing behind the primary.
        Returns the number of warmed buckets (0 when the host has a single
        device — hedging then re-issues on the primary executable, which
        still races the host-side tail)."""
        import jax

        if device_index >= len(jax.devices()):
            return 0
        entry = self.get(name)
        ladder = (
            tuple(bucket_list) if bucket_list
            else tuple(sorted(entry.warm_buckets))
        )
        warmed = 0
        for b in ladder:
            if b not in entry.warm_buckets:
                continue
            _hedge_compiled_for(entry.token, b, device_index)
            warmed += 1
        if warmed:
            _hedge_params(entry.token, device_index)
            with self._lock:
                self._hedge_warm.add((entry.token, device_index))
        return warmed

    def hedge_dispatch_padded(
        self, entry: ServableEntry, padded: np.ndarray, bucket: int
    ) -> np.ndarray:
        """The straggler re-issue: dispatch on the warm hedge device when
        one exists, else re-run the primary executable. The hedged tail is
        usually host-side (GIL, allocator, scheduler stall), so even the
        same-executable race wins back most of it; a warm second device
        additionally covers device-side stragglers."""
        key = (entry.token, 1)
        with self._lock:
            warm = key in self._hedge_warm and bucket in entry.warm_buckets
        if not warm:
            return self.dispatch_padded(entry, padded, bucket)
        import jax
        import jax.numpy as jnp

        compiled = _hedge_compiled_for(entry.token, bucket, 1)
        xd = jax.device_put(jnp.asarray(padded), jax.devices()[1])
        return np.asarray(compiled(_hedge_params(entry.token, 1), xd))

    def predict(self, name: str, x: Any) -> np.ndarray:
        """The direct (un-batched) serve path: prepare, pad, dispatch,
        finalize. The micro-batcher uses the same pieces but coalesces
        several requests into one dispatch."""
        entry = self.get(name)
        mat = validate_request(x, entry.n_features, name)
        prepared = entry.prepare(mat)
        if prepared.dtype != entry.x_dtype:
            # the one conversion to the device dtype (the rounding
            # jnp.asarray applied at dispatch before — bitwise-unchanged)
            prepared = prepared.astype(entry.x_dtype)
        bucket = buckets.serve_bucket(prepared.shape[0])
        REGISTRY.counter_inc("serve.bucket_hits", model=name, bucket=bucket)
        padded, true_rows = buckets.pad_to_bucket(prepared, bucket)
        raw = self.dispatch_padded(entry, padded, bucket)
        REGISTRY.counter_inc("serve.rows", true_rows, model=name)
        return entry.finalize(raw, true_rows)


_REGISTRY_LOCK = threading.Lock()
_MODEL_REGISTRY: ModelRegistry | None = None


def get_registry() -> ModelRegistry:
    """The process-wide registry the serve front-end publishes."""
    global _MODEL_REGISTRY
    with _REGISTRY_LOCK:
        if _MODEL_REGISTRY is None:
            _MODEL_REGISTRY = ModelRegistry()
        return _MODEL_REGISTRY


def reset_for_tests() -> None:
    """Drop the singleton registry and every cached executable (tests
    only — production processes register once and keep everything warm)."""
    global _MODEL_REGISTRY
    with _REGISTRY_LOCK:
        _MODEL_REGISTRY = None
    with _TOKEN_LOCK:
        _ENTRIES_BY_TOKEN.clear()
    _compiled_for.cache_clear()
    _hedge_compiled_for.cache_clear()
    _hedge_params.cache_clear()
    hbm.reset_fleet()
