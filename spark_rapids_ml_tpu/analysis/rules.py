"""The tpulint rules — the framework's JAX/TPU invariants, as code.

Each rule subclasses :class:`~.engine.Rule` and documents what it enforces
and why (CONTRIBUTING.md renders these docstrings). Rules are heuristic on
purpose: they resolve only module-local facts (imports, same-file function
defs) and skip what they cannot resolve — a linter that guesses produces
noise, and noise gets disabled. Anything a rule flags wrongly can be
silenced with ``# tpulint: disable=RULE`` at the site or blessed with a
justification in the baseline.
"""

from __future__ import annotations

import ast
from typing import Iterator

from spark_rapids_ml_tpu.analysis.engine import (
    Finding,
    LintedModule,
    Rule,
    dotted_name,
)

# Parameter names the framework uses for streamed-fold / chunked-fit
# carries. A jitted callable taking one of these re-ingests the
# accumulator every call; without donation XLA must keep input and output
# alive simultaneously — 2x accumulator HBM and a copy per chunk.
CARRY_PARAM_NAMES = frozenset(
    {"carry", "carry0", "acc", "accum", "state", "state0", "w0", "centers0"}
)

CACHE_DECORATORS = frozenset(
    {"lru_cache", "cache", "functools.lru_cache", "functools.cache"}
)

_SHAPE_ATTRS = frozenset({"shape", "ndim", "size", "dtype"})


def _is_jit_call(mod: LintedModule, node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and mod.call_is(node, "jax.jit")


def _jit_kwargs(call: ast.Call) -> dict[str, ast.expr]:
    return {kw.arg: kw.value for kw in call.keywords if kw.arg}


def _decorator_jit_kwargs(
    mod: LintedModule, fn: ast.FunctionDef
) -> dict[str, ast.expr] | None:
    """jit kwargs if ``fn`` is jit-decorated (@jax.jit or
    @partial(jax.jit, ...)); None when it is not."""
    for dec in fn.decorator_list:
        if mod.resolves_to(dec, "jax.jit"):
            return {}
        if isinstance(dec, ast.Call):
            if mod.call_is(dec, "jax.jit"):
                return _jit_kwargs(dec)
            if (
                mod.call_is(dec, "functools.partial")
                and dec.args
                and mod.resolves_to(dec.args[0], "jax.jit")
            ):
                return _jit_kwargs(dec)
    return None


def _const_int_set(node: ast.expr | None) -> set[int] | None:
    """{ints} from a Constant/Tuple-of-Constants node; None if unresolvable."""
    if node is None:
        return set()
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: set[int] = set()
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, int)):
                return None
            out.add(elt.value)
        return out
    return None


def _const_str_set(node: ast.expr | None) -> set[str] | None:
    if node is None:
        return set()
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out: set[str] = set()
        for elt in node.elts:
            if not (isinstance(elt, ast.Constant) and isinstance(elt.value, str)):
                return None
            out.add(elt.value)
        return out
    return None


def _param_names(fn: ast.FunctionDef | ast.Lambda) -> list[str]:
    a = fn.args
    return [p.arg for p in (*a.posonlyargs, *a.args)]


def _module_functions(mod: LintedModule) -> dict[str, ast.FunctionDef]:
    """Every (possibly nested) def in the file by name; later defs win."""
    return {
        n.name: n
        for n in ast.walk(mod.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }


def _jit_target(
    mod: LintedModule, call: ast.Call
) -> tuple[ast.FunctionDef | ast.Lambda | None, str]:
    """The callable a ``jax.jit(...)`` call wraps, resolved module-locally.

    Sees through ``partial(f, ...)``; returns (def-node-or-None, label).
    When several defs share the name (factory modules reuse ``run``), the
    one enclosed by the same function as the jit call wins — that is the
    def the name actually binds to at the call site."""
    if not call.args:
        return None, ""
    target = call.args[0]
    if isinstance(target, ast.Call) and mod.call_is(target, "functools.partial"):
        if not target.args:
            return None, ""
        target = target.args[0]
    if isinstance(target, ast.Lambda):
        return target, "<lambda>"
    name = dotted_name(target)
    candidates = [
        n for n in ast.walk(mod.tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        and n.name == name
    ]
    if not candidates:
        return None, name
    here = mod.enclosing_function(call)
    for fn in candidates:
        if mod.enclosing_function(fn) is here:
            return fn, name
    return candidates[-1], name


def _traced_functions(mod: LintedModule) -> dict[ast.AST, str]:
    """Function/lambda nodes whose bodies run under jax tracing:
    jit-decorated defs plus same-file callables passed to jax.jit."""
    out: dict[ast.AST, str] = {}
    for n in ast.walk(mod.tree):
        if isinstance(n, ast.FunctionDef):
            if _decorator_jit_kwargs(mod, n) is not None:
                out[n] = n.name
        if _is_jit_call(mod, n):
            fn, label = _jit_target(mod, n)
            if fn is not None:
                out[fn] = label or "<lambda>"
    return out


class DonatedCarryRule(Rule):
    id = "TPL001"
    name = "donated-carry"
    doc = (
        "Every jax.jit of a fold/step/chunk callable that re-ingests an "
        "accumulator (a parameter named carry/acc/state/w0/centers0/...) "
        "must donate that argument (donate_argnums/donate_argnames). "
        "Without donation the streamed fold holds two copies of the carry "
        "in HBM and pays a device copy per chunk — the exact regression "
        "PR 1's donated-carry design exists to prevent."
    )

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        # inline jax.jit(f, ...) calls
        for n in ast.walk(mod.tree):
            if _is_jit_call(mod, n):
                fn, label = _jit_target(mod, n)
                if fn is None:
                    continue
                yield from self._check_callable(mod, n, fn, label, _jit_kwargs(n))
            elif isinstance(n, ast.FunctionDef):
                kwargs = _decorator_jit_kwargs(mod, n)
                if kwargs is not None:
                    yield from self._check_callable(mod, n, n, n.name, kwargs)

    def _check_callable(self, mod, site, fn, label, kwargs):
        params = _param_names(fn)
        carry_idx = [i for i, p in enumerate(params) if p in CARRY_PARAM_NAMES]
        if not carry_idx:
            return
        donated_nums = _const_int_set(kwargs.get("donate_argnums"))
        donated_names = _const_str_set(kwargs.get("donate_argnames"))
        if donated_nums is None or donated_names is None:
            return  # dynamically built donation spec — trust it
        for i in carry_idx:
            if i not in donated_nums and params[i] not in donated_names:
                yield self.finding(
                    mod, site,
                    f"jit of {label or 'callable'}: carry parameter "
                    f"{params[i]!r} (arg {i}) is not donated — pass "
                    f"donate_argnums={i} so the fold reuses the "
                    "accumulator's buffer",
                )


class HostSyncRule(Rule):
    id = "TPL002"
    name = "host-sync-in-hot-path"
    doc = (
        "No float()/int()/bool()/np.asarray()/.item()/.tolist()/"
        ".block_until_ready() on traced values inside jit-traced functions "
        "— under tracing these either fail (ConcretizationTypeError) or, "
        "worse, silently force a device->host sync per call. ops/ kernel "
        "modules must additionally stay sync-free everywhere: they are the "
        "pure jittable compute layer and dispatch decides when to wait. "
        "serving/ holds the same whole-module bar — its kernels feed the "
        "AOT registry and a stray sync is per-request latency on the warm "
        "path. Shape/dtype reads (static under tracing) are exempt; "
        "telemetry/ is exempt (measurement is allowed to sync)."
    )

    SYNC_BUILTINS = frozenset({"float", "int", "bool"})
    SYNC_METHODS = frozenset({"item", "tolist", "block_until_ready"})
    NP_FUNCS = ("numpy.asarray", "numpy.array")
    # scopes held to the whole-module sync-method bar, not just traced fns
    SYNC_SCOPES = {
        "/ops/": "ops/ kernel module",
        "/serving/": "serving/ warm-path module",
    }

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        if "/telemetry/" in mod.relpath:
            return
        traced = _traced_functions(mod)
        for fn, label in traced.items():
            body = fn.body if isinstance(fn.body, list) else [fn.body]
            for stmt in body:
                for n in ast.walk(stmt):
                    # nested defs inside a traced fn still trace (closures)
                    yield from self._check_node(mod, n, f"traced {label}")
        scope_ctx = next(
            (c for s, c in self.SYNC_SCOPES.items() if s in mod.relpath),
            None,
        )
        if scope_ctx is not None:
            traced_nodes = {
                id(x) for fn in traced for x in ast.walk(fn)
            }
            for n in ast.walk(mod.tree):
                if id(n) in traced_nodes:
                    continue  # already reported with traced context
                yield from self._check_node(
                    mod, n, scope_ctx, methods_only=True
                )

    def _check_node(self, mod, n, ctx, methods_only=False):
        if not isinstance(n, ast.Call):
            return
        func = n.func
        if isinstance(func, ast.Attribute) and func.attr in self.SYNC_METHODS:
            yield self.finding(
                mod, n,
                f".{func.attr}() forces a device->host sync ({ctx})",
            )
            return
        if methods_only:
            return
        if (
            isinstance(func, ast.Name)
            and func.id in self.SYNC_BUILTINS
            and len(n.args) == 1
            and not self._static_arg(n.args[0])
        ):
            yield self.finding(
                mod, n,
                f"{func.id}() concretizes a traced value ({ctx})",
            )
            return
        if any(mod.resolves_to(func, f) for f in self.NP_FUNCS):
            yield self.finding(
                mod, n,
                f"{dotted_name(func)}() materializes a traced value on "
                f"host ({ctx}) — use jnp instead",
            )

    @staticmethod
    def _static_arg(arg: ast.expr) -> bool:
        """Constants and shape/dtype/len() reads are static under tracing."""
        if isinstance(arg, ast.Constant):
            return True
        for n in ast.walk(arg):
            if isinstance(n, ast.Attribute) and n.attr in _SHAPE_ATTRS:
                return True
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id == "len"
            ):
                return True
        return False


class RecompileHazardRule(Rule):
    id = "TPL003"
    name = "recompile-hazard"
    doc = (
        "A jax.jit(...) program object must be built once and reused: "
        "constructing one inside a loop, or inside an uncached function "
        "that runs per fit/chunk, discards XLA's in-process executable "
        "cache and retraces every call — the recompile storm the "
        "trace-report anomaly check flags at runtime. Build programs at "
        "module scope or in an @functools.lru_cache'd factory (the "
        "parallel/ convention). In serving/ the same discipline covers "
        "AOT lowering: a .lower(avals) call is a full trace+lower even "
        "when the executable would be cache-hit, so it must live in a "
        "cached factory (serving.registry._compiled_for), never per "
        "request or per loop iteration. Shape hazards are the runtime "
        "half of this rule: Python scalars that vary per call belong in "
        "static_argnums only if they are genuinely low-cardinality; "
        "varying data shapes belong in buckets (TPU_ML_MIN_BUCKET)."
    )

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        for n in ast.walk(mod.tree):
            if not (
                _is_jit_call(mod, n) or self._is_aot_lower(mod, n)
            ):
                continue
            what = (
                "AOT .lower() trace" if self._is_aot_lower(mod, n)
                else "jax.jit program"
            )
            in_loop = any(
                isinstance(a, (ast.For, ast.While, ast.AsyncFor))
                for a in mod.ancestors(n)
            )
            if in_loop:
                yield self.finding(
                    mod, n,
                    f"{what} constructed inside a loop — every "
                    "iteration retraces; hoist it out of the loop",
                )
                continue
            encl = mod.enclosing_function(n)
            if encl is None:
                continue  # module scope: built once at import
            chain = [encl, *(
                a for a in mod.ancestors(encl)
                if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef))
            )]
            if any(self._has_cache_decorator(mod, f) for f in chain):
                continue
            if any(f in _traced_functions(mod) for f in chain):
                continue  # jit-of-jit inside traced code is inlined, fine
            yield self.finding(
                mod, n,
                f"{what} built per call of {encl.name}() — cache "
                "the factory with @functools.lru_cache or hoist to module "
                "scope so repeat calls reuse the executable",
            )

    @staticmethod
    def _is_aot_lower(mod: LintedModule, n: ast.AST) -> bool:
        """A ``<jit-program>.lower(avals)`` AOT trace in serving/ — the
        argumentless form is str.lower() and stays exempt everywhere."""
        return (
            "/serving/" in mod.relpath
            and isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr == "lower"
            and bool(n.args or n.keywords)
        )

    @staticmethod
    def _has_cache_decorator(mod: LintedModule, fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            name = dotted_name(target)
            if name in CACHE_DECORATORS or any(
                mod.resolves_to(target, c) for c in CACHE_DECORATORS
            ):
                return True
        return False


class RetryDisciplineRule(Rule):
    id = "TPL004"
    name = "retry-discipline"
    doc = (
        "No hand-rolled time.sleep retry loops outside resilience/retry.py "
        "— the shared call_with_retry is the one backoff loop: it "
        "classifies errors, respects the attempt/deadline knobs, counts "
        "retry.attempts in telemetry, and never sleeps after the final "
        "attempt (the exact executor bug PR 3 fixed). A sleep inside an "
        "except handler, inside a loop that catches exceptions, or fed "
        "from a backoff variable is hand-rolled retry machinery."
    )

    BACKOFF_NAMES = ("backoff", "retry", "delay")

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        if mod.relpath.endswith("resilience/retry.py"):
            return
        for n in ast.walk(mod.tree):
            if not (isinstance(n, ast.Call) and mod.call_is(n, "time.sleep")):
                continue
            ancestors = list(mod.ancestors(n))
            in_except = any(isinstance(a, ast.ExceptHandler) for a in ancestors)
            loop = next(
                (a for a in ancestors if isinstance(a, (ast.For, ast.While))),
                None,
            )
            loop_catches = loop is not None and any(
                isinstance(x, ast.Try) for x in ast.walk(loop)
            )
            backoff_arg = bool(n.args) and any(
                isinstance(x, ast.Name)
                and any(b in x.id.lower() for b in self.BACKOFF_NAMES)
                for x in ast.walk(n.args[0])
            )
            if in_except or loop_catches or backoff_arg:
                yield self.finding(
                    mod, n,
                    "hand-rolled sleep-based retry — route this through "
                    "resilience.retry.call_with_retry (shared policy, "
                    "telemetry counters, no sleep-after-final-attempt)",
                )


class NameRegistryRule(Rule):
    id = "TPL005"
    name = "name-registry"
    doc = (
        "Metric, span, timeline-instant and fault-site string literals at "
        "call sites must resolve against the canonical registries "
        "(telemetry/names.py, resilience/sites.py). A typo'd name does "
        "not error — it mints a silent new metric family no dashboard or "
        "anomaly check reads, or a fault gate no chaos plan can hit. "
        "Adding a series means declaring it in the registry first."
    )

    METRIC_FNS = frozenset({"counter_inc", "gauge_set", "histogram_record"})

    def __init__(self, metrics=None, prefixes=None, spans=None,
                 instants=None, sites=None):
        if metrics is None:
            from spark_rapids_ml_tpu.resilience.sites import FAULT_SITES
            from spark_rapids_ml_tpu.telemetry.names import (
                INSTANTS, METRIC_PREFIXES, METRICS, SPAN_PHASES,
            )
            metrics, prefixes = METRICS, METRIC_PREFIXES
            spans, instants, sites = SPAN_PHASES, INSTANTS, FAULT_SITES
        self.metrics = metrics
        self.prefixes = tuple(prefixes or ())
        self.spans = spans or frozenset()
        self.instants = instants or frozenset()
        self.sites = sites or frozenset()

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        if mod.relpath.endswith(("telemetry/names.py", "resilience/sites.py")):
            return
        for n in ast.walk(mod.tree):
            if not (isinstance(n, ast.Call) and n.args):
                continue
            func = n.func
            attr = (
                func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else ""
            )
            lit = self._literal(n.args[0])
            if attr in self.METRIC_FNS:
                kind, registry = "metric", self.metrics
            elif attr == "trace_range" or attr == "record_span":
                kind, registry = "span phase", self.spans
            elif attr == "record_instant":
                kind, registry = "timeline instant", self.instants
            elif attr == "inject" and self._is_fault_inject(mod, func):
                kind, registry = "fault site", self.sites
            else:
                continue
            if lit is None:
                # f-string with a literal head: prefix-check metrics
                if kind == "metric":
                    head = self._fstring_head(n.args[0])
                    if head is not None and not any(
                        head.startswith(p) for p in self.prefixes
                    ):
                        yield self.finding(
                            mod, n,
                            f"dynamic metric name with unregistered prefix "
                            f"{head!r} — declare the prefix in "
                            "telemetry.names.METRIC_PREFIXES",
                        )
                continue
            ok = lit in registry or (
                kind == "metric"
                and any(lit.startswith(p) for p in self.prefixes)
            )
            if not ok:
                where = (
                    "telemetry.names" if kind != "fault site"
                    else "resilience.sites"
                )
                yield self.finding(
                    mod, n,
                    f"{kind} {lit!r} is not declared in the {where} "
                    "registry — a typo here silently mints a new family; "
                    "declare it (or fix the name)",
                )

    @staticmethod
    def _literal(node: ast.expr) -> str | None:
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        return None

    @staticmethod
    def _fstring_head(node: ast.expr) -> str | None:
        if isinstance(node, ast.JoinedStr) and node.values:
            first = node.values[0]
            if isinstance(first, ast.Constant) and isinstance(first.value, str):
                return first.value
        return None

    @staticmethod
    def _is_fault_inject(mod: LintedModule, func: ast.expr) -> bool:
        name = dotted_name(func)
        if name.endswith("faults.inject"):
            return True
        origin = mod.imports.get(name, "")
        return name == "inject" and origin.endswith("faults.inject")


class KnobInventoryRule(Rule):
    id = "TPL006"
    name = "knob-inventory"
    doc = (
        "Every TPU_ML_* environment knob must be declared in "
        "utils/knobs.py (name, type, default, doc, consumer) — the "
        "declaration is what --list-knobs renders and what keeps the "
        "README knob table honest (CI drift-checks them against each "
        "other). Any TPU_ML_* string literal outside the declaration "
        "module is either an undeclared knob or a typo'd read of a "
        "declared one; both ship silent misconfiguration."
    )

    def __init__(self, declared=None):
        if declared is None:
            from spark_rapids_ml_tpu.utils.knobs import KNOBS
            declared = frozenset(KNOBS)
        self.declared = declared

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        if mod.relpath.endswith("utils/knobs.py"):
            return
        for n in ast.walk(mod.tree):
            if not (isinstance(n, ast.Constant) and isinstance(n.value, str)):
                continue
            v = n.value
            if not (v.startswith("TPU_ML_") and len(v) > len("TPU_ML_")
                    and v.replace("_", "").isalnum() and v == v.upper()):
                continue
            parent = mod.parents.get(n)
            if isinstance(parent, ast.Expr):
                continue  # docstring / bare string statement
            if v not in self.declared:
                yield self.finding(
                    mod, n,
                    f"env knob {v!r} is not declared in utils.knobs.KNOBS "
                    "— declare it there (and prefer referencing "
                    "knobs.<NAME>.name over a fresh literal)",
                )


class TelemetryRaceRule(Rule):
    id = "TPL007"
    name = "telemetry-race"
    doc = (
        "Module-level mutable state in telemetry/ and resilience/ must "
        "only be mutated under a lock: these modules are written to from "
        "the partition executor's thread pool and from worker callbacks, "
        "and unlocked dict/list mutation corrupts counts exactly the way "
        "the PR 2 registry lock exists to prevent. A mutation (or a "
        "`global` rebind) with no enclosing `with <lock>:` is a finding."
    )

    SCOPES = ("/telemetry/", "/resilience/")
    MUTATORS = frozenset({
        "append", "add", "update", "clear", "pop", "popitem",
        "setdefault", "extend", "remove", "discard", "insert",
    })
    MUTABLE_CTORS = frozenset({
        "dict", "list", "set", "defaultdict", "deque", "OrderedDict",
        "Counter",
    })

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        if not any(s in mod.relpath for s in self.SCOPES):
            return
        mutable = self._module_mutables(mod)
        if not mutable:
            return
        for n in ast.walk(mod.tree):
            name = self._mutation_target(n, mutable, mod)
            if name and not self._under_lock(mod, n):
                yield self.finding(
                    mod, n,
                    f"module-level mutable {name!r} mutated outside a "
                    "lock — wrap in `with <lock>:` (or prove the path "
                    "single-threaded and bless with a note)",
                )

    def _module_mutables(self, mod: LintedModule) -> set[str]:
        out: set[str] = set()
        for stmt in mod.tree.body:
            targets: list[ast.expr] = []
            value: ast.expr | None = None
            if isinstance(stmt, ast.Assign):
                targets, value = stmt.targets, stmt.value
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                targets, value = [stmt.target], stmt.value
            if value is None:
                continue
            is_mutable = isinstance(
                value, (ast.Dict, ast.List, ast.Set, ast.DictComp,
                        ast.ListComp, ast.SetComp)
            ) or (
                isinstance(value, ast.Call)
                and dotted_name(value.func).split(".")[-1] in self.MUTABLE_CTORS
            )
            if is_mutable:
                out.update(
                    t.id for t in targets if isinstance(t, ast.Name)
                )
        return out

    def _mutation_target(self, n: ast.AST, mutable: set[str], mod) -> str | None:
        # x[k] = v / del x[k] / x[k] += v
        if isinstance(n, (ast.Assign, ast.AugAssign, ast.Delete)):
            targets = (
                n.targets if isinstance(n, ast.Assign)
                else [n.target] if isinstance(n, ast.AugAssign)
                else n.targets
            )
            for t in targets:
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)
                    and t.value.id in mutable
                ):
                    return t.value.id
            # global rebind: `global x` + assignment inside a function
            if isinstance(n, ast.Assign):
                fn = mod.enclosing_function(n)
                if fn is not None:
                    declared_global = {
                        g for s in ast.walk(fn)
                        if isinstance(s, ast.Global) for g in s.names
                    }
                    for t in targets:
                        if isinstance(t, ast.Name) and t.id in mutable \
                                and t.id in declared_global:
                            return t.id
        # x.append(...) etc.
        if (
            isinstance(n, ast.Call)
            and isinstance(n.func, ast.Attribute)
            and n.func.attr in self.MUTATORS
            and isinstance(n.func.value, ast.Name)
            and n.func.value.id in mutable
        ):
            return n.func.value.id
        return None

    @staticmethod
    def _under_lock(mod: LintedModule, n: ast.AST) -> bool:
        for a in mod.ancestors(n):
            if isinstance(a, ast.With):
                for item in a.items:
                    if "lock" in ast.unparse(item.context_expr).lower():
                        return True
        return False


class SwallowedExceptionRule(Rule):
    id = "TPL008"
    name = "swallowed-exception"
    doc = (
        "`except Exception: pass` (or a bare except: pass) with no "
        "explanation swallows every failure mode including the "
        "JaxRuntimeError families the retry classifier must see — PR 3 "
        "exists because exactly this pattern hid a retry bug. A broad "
        "swallow is allowed only with a same-line comment saying why "
        "(narrow handlers, or handlers that do something, are fine)."
    )

    BROAD = frozenset({"Exception", "BaseException"})

    def check(self, mod: LintedModule) -> Iterator[Finding]:
        for n in ast.walk(mod.tree):
            if not isinstance(n, ast.ExceptHandler):
                continue
            if not (len(n.body) == 1 and isinstance(n.body[0], ast.Pass)):
                continue
            if not self._is_broad(n.type):
                continue
            # intent may be documented on the except line or the pass line
            last = min(n.body[0].lineno, len(mod.lines))
            if any("#" in mod.lines[i - 1] for i in range(n.lineno, last + 1)):
                continue
            what = "bare except" if n.type is None else dotted_name(n.type)
            yield self.finding(
                mod, n,
                f"{what}: pass silently swallows every failure — narrow "
                "the type, handle it, or add a same-line comment saying "
                "why ignoring is correct",
            )

    def _is_broad(self, t: ast.expr | None) -> bool:
        if t is None:
            return True
        if isinstance(t, ast.Tuple):
            return any(self._is_broad(e) for e in t.elts)
        return dotted_name(t).split(".")[-1] in self.BROAD


def all_rules() -> list[Rule]:
    """Fresh instances of every rule, registry-backed defaults."""
    return [
        DonatedCarryRule(),
        HostSyncRule(),
        RecompileHazardRule(),
        RetryDisciplineRule(),
        NameRegistryRule(),
        KnobInventoryRule(),
        TelemetryRaceRule(),
        SwallowedExceptionRule(),
    ]


ALL_RULES = all_rules()
