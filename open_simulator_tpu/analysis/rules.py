"""The simonlint rule set: JAX/TPU hazards this codebase has been bitten by.

Rule ids (stable — they appear in suppression comments and CI output):

  host-sync-in-jit   device->host sync inside a traced function
  recompile-trigger  static-looking jit parameter not declared static
  dtype-drift        64-bit dtype on a TPU-targeted path
  carry-contract     lax.scan carry without (or violating) a NamedTuple contract
  contract-spec      malformed @shaped contract annotation
  metric-in-jit      metrics-registry mutation or wall-clock read under trace
  swallowed-exception  broad except that neither re-raises, returns, logs, nor counts
  naked-dispatch     device-computation call site bypassing the simonguard watchdog
  fetch-in-wave-loop device->host fetch inside a per-segment/epoch/round loop body
  unsharded-transfer shardingless device_put / jit dispatch in a mesh-aware hot path
  config-scope-across-thread  jax config scope entered in one thread, work
                     submitted to another inside it
  suppression-reason a `simonlint: ignore[...]` waiver without its `-- reason`
  per-pod-host-loop  O(pods) Python `for` over a pod batch in a module that
                     adopted the columnar PodStore
  collective-in-scan-body  cross-shard collective (psum/pmax/all_gather/...)
                     inside a scan/while/fori body — per-iteration latency
                     that should be batched to the loop boundary
  unattributed-dispatch  hot-kernel dispatch under guard.supervised with no
                     obs.record_dispatch in its attribution path — invisible
                     to the compile-cache census and the simonpulse ledger

Every rule is a pure function ModuleContext -> List[Finding]; file IO,
suppressions, and exit-code policy live in runner.py.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from ..ops.contracts import parse_spec
from .base import _REASON_RE, _SUPPRESS_RE, Finding, Severity, register
from .context import JIT_NAMES, PARTIAL_NAMES, ModuleContext

# ----------------------------------------------------------------- helpers ----


def _names_in(expr: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}


def _local_walk(fn: ast.FunctionDef):
    """Walk a function body without descending into nested defs/lambdas
    (those are separate traced contexts with their own taint sets)."""
    stack: List[ast.AST] = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _taint_set(fn: ast.FunctionDef, statics: Set[str]) -> Set[str]:
    """Names whose values derive from TRACED arguments: the non-static
    parameters, propagated through simple assignments / loop targets to a
    fixpoint. Conservative in the safe direction (a tainted name may in fact
    hold a static value; an untainted one never holds a traced one unless it
    came from a closure, which we don't track)."""
    a = fn.args
    params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    tainted: Set[str] = {p for p in params if p not in statics}
    for _ in range(10):
        grew = False
        for node in _local_walk(fn):
            targets: List[ast.expr] = []
            value: Optional[ast.AST] = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AugAssign):
                targets, value = [node.target], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            elif isinstance(node, ast.For):
                targets, value = [node.target], node.iter
            if value is None or not (_names_in(value) & tainted):
                continue
            for t in targets:
                for name in _names_in(t):
                    if name not in tainted:
                        tainted.add(name)
                        grew = True
        if not grew:
            break
    return tainted


# ---------------------------------------------------------- host-sync-in-jit --

_SYNC_ATTRS = {"item", "tolist", "block_until_ready"}
_SYNC_CALLS = {"numpy.asarray", "numpy.array", "jax.device_get"}
_SYNC_BUILTINS = {"float", "int", "bool", "print"}


@register(
    "host-sync-in-jit", Severity.ERROR,
    "Device->host synchronization (.item()/np.asarray/float()/print/...) on a "
    "traced value inside jit/pjit or a lax.scan|while_loop body. Under trace "
    "these either raise ConcretizationTypeError at runtime or, worse, silently "
    "pull the value at trace time and bake a stale constant into the compiled "
    "program.",
)
def rule_host_sync(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for fn, statics in ctx.traced_functions().items():
        tainted = _taint_set(fn, statics)
        for node in _local_walk(fn):
            if not isinstance(node, ast.Call):
                continue
            hazard: Optional[str] = None
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SYNC_ATTRS
                    and _names_in(node.func.value) & tainted):
                hazard = f".{node.func.attr}()"
            else:
                target = ctx.resolve(node.func)
                arg_names: Set[str] = set()
                for argn in list(node.args) + [k.value for k in node.keywords]:
                    arg_names |= _names_in(argn)
                if target in _SYNC_CALLS and arg_names & tainted:
                    hazard = target
                elif (isinstance(node.func, ast.Name)
                        and node.func.id in _SYNC_BUILTINS
                        and node.func.id not in ctx.aliases
                        and arg_names & tainted):
                    hazard = f"{node.func.id}()"
            if hazard:
                out.append(Finding(
                    "host-sync-in-jit", Severity.ERROR, ctx.path,
                    node.lineno, node.col_offset,
                    f"{hazard} on a value derived from traced arguments of "
                    f"'{fn.name}' — forces a host sync (or a stale trace-time "
                    f"constant) inside a compiled function",
                ))
    return out


# --------------------------------------------------------- recompile-trigger --

_STATICISH_ANNOTATIONS = {"int", "bool", "str", "tuple"}


def _annotation_is_staticish(ctx: ModuleContext, ann: Optional[ast.expr]) -> Optional[str]:
    if ann is None:
        return None
    base = ann.value if isinstance(ann, ast.Subscript) else ann
    r = ctx.resolve(base)
    if r in _STATICISH_ANNOTATIONS:
        return r
    if r in ("typing.Tuple", "typing.Literal"):
        return r.split(".")[-1]
    return None


@register(
    "recompile-trigger", Severity.WARNING,
    "A jit-compiled function takes a parameter that is plainly host-side "
    "configuration (int/bool/str/tuple annotation or scalar default) without "
    "declaring it in static_argnums/static_argnames. Used in Python control "
    "flow or shape arithmetic it aborts tracing; silently traced, every "
    "structurally distinct value risks a fresh compilation.",
)
def rule_recompile(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for fn, info in ctx.jit.items():
        a = fn.args
        params = list(a.posonlyargs + a.args)
        defaults = [None] * (len(params) - len(a.defaults)) + list(a.defaults)
        params_kw = list(a.kwonlyargs)
        defaults_kw = list(a.kw_defaults)
        for p, d in zip(params + params_kw, defaults + defaults_kw):
            if p.arg in info.static_names or p.arg in ("self", "cls"):
                continue
            why = _annotation_is_staticish(ctx, p.annotation)
            if why is None and isinstance(d, ast.Constant) and isinstance(
                    d.value, (int, bool, str)) and not isinstance(d.value, float):
                why = type(d.value).__name__
            if why is None and isinstance(d, ast.Tuple):
                why = "tuple"
            if why is not None:
                out.append(Finding(
                    "recompile-trigger", Severity.WARNING, ctx.path,
                    p.lineno, p.col_offset,
                    f"parameter '{p.arg}' of jit-compiled '{fn.name}' looks "
                    f"static ({why}) but is not in static_argnums/"
                    f"static_argnames — declare it static or pass a device "
                    f"array",
                ))
    return out


# -------------------------------------------------------------- dtype-drift --

_WIDE_DTYPES = {
    "numpy.float64", "numpy.int64", "numpy.uint64", "numpy.longdouble",
    "jax.numpy.float64", "jax.numpy.int64", "jax.numpy.uint64",
}
_WIDE_STRS = {"float64", "int64", "uint64"}
_ARRAY_FACTORIES = {
    "array", "asarray", "zeros", "ones", "full", "empty", "arange",
    "fromiter", "astype", "frombuffer", "linspace",
}


@register(
    "dtype-drift", Severity.WARNING,
    "64-bit dtype (float64/int64) referenced on a TPU-targeted module. JAX "
    "runs with x64 disabled: the value is silently downcast when it crosses "
    "the device boundary, so 64-bit staging is only sound host-side — "
    "whitelist intentional host buffers with "
    "`# simonlint: ignore[dtype-drift] -- <why>`.",
)
def rule_dtype_drift(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Attribute):
            r = ctx.resolve(node)
            if r in _WIDE_DTYPES:
                out.append(Finding(
                    "dtype-drift", Severity.WARNING, ctx.path,
                    node.lineno, node.col_offset,
                    f"{r.split('.')[-1]} staging ({r}): 64-bit values are "
                    f"downcast at the device boundary (JAX x64 is off) — keep "
                    f"host-side and whitelist, or use an explicit 32-bit dtype",
                ))
        elif isinstance(node, ast.Call):
            fname = (node.func.attr if isinstance(node.func, ast.Attribute)
                     else node.func.id if isinstance(node.func, ast.Name) else "")
            if fname not in _ARRAY_FACTORIES:
                continue
            for cand in list(node.args) + [k.value for k in node.keywords
                                           if k.arg in (None, "dtype")]:
                if isinstance(cand, ast.Constant) and cand.value in _WIDE_STRS:
                    out.append(Finding(
                        "dtype-drift", Severity.WARNING, ctx.path,
                        cand.lineno, cand.col_offset,
                        f'string dtype "{cand.value}" passed to {fname}(): '
                        f"64-bit values are downcast at the device boundary — "
                        f"use a 32-bit dtype or whitelist the host staging",
                    ))
    return out


# ------------------------------------------------------------ carry-contract --


def _carry_annotation(ctx: ModuleContext, body: ast.FunctionDef,
                      carry_index: int) -> Optional[ast.arg]:
    params = body.args.posonlyargs + body.args.args
    if carry_index >= len(params):
        return None
    return params[carry_index]


def _returned_carry_exprs(body: ast.FunctionDef) -> List[ast.expr]:
    """First tuple element of every `return (carry, y)` in the body (local
    scope only). A bare non-tuple return is itself taken as the carry."""
    out: List[ast.expr] = []
    for node in _local_walk(body):
        if isinstance(node, ast.Return) and node.value is not None:
            v = node.value
            out.append(v.elts[0] if isinstance(v, ast.Tuple) and v.elts else v)
    return out


@register(
    "carry-contract", Severity.ERROR,
    "Every lax.scan body must declare its carry with a NamedTuple contract "
    "(annotated carry parameter) and return that same contract from every "
    "branch: a carry whose pytree structure, leaf shapes, or dtypes shift "
    "between branches recompiles per step or fails deep inside XLA with no "
    "source location.",
)
def rule_carry_contract(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for site in ctx.scans:
        if site.kind != "scan":
            continue
        call_line, call_col = site.call.lineno, site.call.col_offset
        if site.body is None:
            out.append(Finding(
                "carry-contract", Severity.ERROR, ctx.path, call_line, call_col,
                "lax.scan body is not a statically resolvable function "
                "(lambda or imported name) — declare a local body function "
                "with a NamedTuple-annotated carry",
            ))
            continue
        body = site.body
        carry = _carry_annotation(ctx, body, site.carry_index)
        if carry is None or carry.annotation is None:
            out.append(Finding(
                "carry-contract", Severity.ERROR, ctx.path,
                body.lineno, body.col_offset,
                f"scan body '{body.name}' has no carry contract: annotate its "
                f"carry parameter with a NamedTuple type",
            ))
            continue
        ann = carry.annotation
        ann_name = ann.id if isinstance(ann, ast.Name) else None
        if ann_name is None:
            out.append(Finding(
                "carry-contract", Severity.ERROR, ctx.path,
                carry.lineno, carry.col_offset,
                f"carry of scan body '{body.name}' is annotated with a "
                f"non-NamedTuple type expression — use a NamedTuple class",
            ))
            continue
        fields = ctx.namedtuples.get(ann_name)  # None => imported; trusted

        # initial carry should be constructed with the same contract
        init = site.init
        if isinstance(init, ast.Tuple):
            out.append(Finding(
                "carry-contract", Severity.ERROR, ctx.path,
                init.lineno, init.col_offset,
                f"initial carry of lax.scan is a bare tuple but body "
                f"'{body.name}' declares contract {ann_name} — construct "
                f"{ann_name}(...) so the pytree structures match",
            ))

        # every return branch must yield the same contract
        aliases_ok: Set[str] = {carry.arg}
        for node in _local_walk(body):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name):
                v = node.value
                if _carry_expr_ok(ctx, v, ann_name, aliases_ok):
                    aliases_ok.add(node.targets[0].id)
        for rexpr in _returned_carry_exprs(body):
            if not _carry_expr_ok(ctx, rexpr, ann_name, aliases_ok):
                out.append(Finding(
                    "carry-contract", Severity.ERROR, ctx.path,
                    rexpr.lineno, rexpr.col_offset,
                    f"scan body '{body.name}' returns a carry that is not "
                    f"its declared contract {ann_name} on this branch",
                ))
            elif (isinstance(rexpr, ast.Call) and isinstance(rexpr.func, ast.Name)
                    and rexpr.func.id == ann_name and fields is not None
                    and rexpr.args and not rexpr.keywords
                    and len(rexpr.args) != len(fields)):
                out.append(Finding(
                    "carry-contract", Severity.ERROR, ctx.path,
                    rexpr.lineno, rexpr.col_offset,
                    f"{ann_name}(...) constructed with {len(rexpr.args)} "
                    f"positional leaves but the contract declares "
                    f"{len(fields)} fields",
                ))
    return out


def _carry_expr_ok(ctx: ModuleContext, expr: ast.expr, ann_name: str,
                   aliases_ok: Set[str]) -> bool:
    if isinstance(expr, ast.Name):
        return expr.id in aliases_ok
    if isinstance(expr, ast.Call):
        f = expr.func
        if isinstance(f, ast.Name):
            if f.id == ann_name:
                return True
            if f.id in ctx.namedtuples:
                return False  # a DIFFERENT contract constructor: the exact bug
            return True  # unknown callable — can't verify statically, trust it
        if isinstance(f, ast.Attribute) and f.attr == "_replace":
            return bool(_names_in(f.value) & aliases_ok) or isinstance(f.value, ast.Call)
    return False


# -------------------------------------------------------------- metric-in-jit --

# wall-clock reads: meaningless under trace (they'd run once at trace time and
# bake a constant timestamp into the compiled program)
_CLOCK_CALLS = {
    "time.time", "time.perf_counter", "time.monotonic", "time.process_time",
    "time.perf_counter_ns", "time.monotonic_ns", "time.time_ns",
}
# registry mutation methods. `.set(...)` is deliberately ABSENT: traced code
# is full of `arr.at[i].set(v)`, and a gauge .set under trace is caught by the
# factory/import half below whenever the metric came from obs.metrics.
_METRIC_MUTATORS = {"inc", "observe"}
# obs.metrics surface: constructing or fetching a metric under trace is as
# wrong as mutating one
_METRIC_FACTORIES = {
    "open_simulator_tpu.obs.metrics.counter",
    "open_simulator_tpu.obs.metrics.gauge",
    "open_simulator_tpu.obs.metrics.histogram",
}


@register(
    "metric-in-jit", Severity.ERROR,
    "Metrics-registry mutation (.inc()/.observe()/obs.metrics factories) or "
    "wall-clock read (time.perf_counter()/time.time()/...) inside jit/pjit or "
    "a lax.scan|while_loop body. Instrumentation must stay on the host side "
    "of the device boundary: under trace these run ONCE at trace time — the "
    "counter moves per compile instead of per dispatch and the timestamp is "
    "a baked constant — or force a host sync mid-kernel.",
)
def rule_metric_in_jit(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for fn in ctx.traced_functions():
        for node in _local_walk(fn):
            if not isinstance(node, ast.Call):
                continue
            hazard: Optional[str] = None
            target = ctx.resolve(node.func)
            if target in _CLOCK_CALLS:
                hazard = f"{target}()"
            elif target is not None and (
                    target in _METRIC_FACTORIES
                    or target.startswith("open_simulator_tpu.obs.")):
                hazard = f"{target}(...)"
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _METRIC_MUTATORS):
                hazard = f".{node.func.attr}()"
            if hazard:
                out.append(Finding(
                    "metric-in-jit", Severity.ERROR, ctx.path,
                    node.lineno, node.col_offset,
                    f"{hazard} inside traced '{fn.name}' — instrumentation "
                    f"must stay host-side of the device boundary (move the "
                    f"registry update / clock read to the dispatch site)",
                ))
    return out


# -------------------------------------------------------- swallowed-exception --

_LOG_METHODS = {"debug", "info", "warning", "warn", "error", "exception",
                "critical", "log", "log_message"}
_COUNT_METHODS = {"inc", "observe", "set", "labels"}
_REPORT_CALLS = {"print"}  # plus sys.exit / os._exit via resolve below
_EXIT_CALLS = {"sys.exit", "os._exit", "os.abort"}


def _walk_no_defs(stmts):
    """Walk statements without descending into nested defs/lambdas (a nested
    function that raises is a definition, not handling)."""
    stack: List[ast.AST] = list(stmts)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _handler_is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True  # bare except:
    elems = t.elts if isinstance(t, ast.Tuple) else [t]
    return any(isinstance(e, ast.Name) and e.id in ("Exception", "BaseException")
               for e in elems)


def _handler_handles(ctx: ModuleContext, handler: ast.ExceptHandler) -> bool:
    for node in _walk_no_defs(handler.body):
        if isinstance(node, (ast.Raise, ast.Return)):
            return True
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in _REPORT_CALLS:
            return True
        if isinstance(f, ast.Attribute) and (
                f.attr in _LOG_METHODS or f.attr in _COUNT_METHODS):
            return True
        if (ctx.resolve(f) or "") in _EXIT_CALLS:
            return True
    return False


@register(
    "swallowed-exception", Severity.WARNING,
    "A broad exception handler (bare except / except Exception/BaseException) "
    "that neither re-raises, returns, logs, nor moves a metric. Silent "
    "swallowing is how retryable failures, injected faults, and corrupted "
    "state disappear from every observability surface — handle narrowly, or "
    "whitelist deliberate best-effort blocks with "
    "`# simonlint: ignore[swallowed-exception] -- <why>`.",
)
def rule_swallowed_exception(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Try):
            continue
        for handler in node.handlers:
            if not _handler_is_broad(handler):
                continue
            if _handler_handles(ctx, handler):
                continue
            what = ("bare except:" if handler.type is None
                    else "except Exception" if not isinstance(handler.type, ast.Tuple)
                    else "broad except tuple")
            out.append(Finding(
                "swallowed-exception", Severity.WARNING, ctx.path,
                handler.lineno, handler.col_offset,
                f"{what} swallows the error: the handler neither re-raises, "
                f"returns, logs, nor counts — failures vanish silently "
                f"(narrow the type, or log/count and whitelist)",
            ))
    return out


# -------------------------------------------------------------- naked-dispatch --

# The compiled scheduling/probe kernels whose dispatch (or the fetch of whose
# results) can block forever on a wedged backend. Every call site in hot-path
# code must run under guard.supervised so the watchdog can contain it.
_DISPATCH_KERNELS = {
    "schedule_batch", "schedule_wave", "schedule_affinity_wave",
    "schedule_group_serial", "probe_serial_fanout",
    "probe_group_serial_fanout", "probe_wave_fanout",
    "probe_affinity_wave_fanout", "serve_whatif_fanout",
    "serve_wave_fanout", "sweep_wave_fanout", "sweep_whatif_fanout",
    "feasibility_jit", "explain_jit",
}


def _is_kernel_dispatch(ctx: ModuleContext, call: ast.Call) -> Optional[str]:
    """The kernel name when `call` invokes a dispatch kernel of the kernels
    module (attribute form `kernels.X(...)` via any alias, or a name imported
    absolutely from open_simulator_tpu.ops.kernels), else None."""
    r = ctx.resolve(call.func)
    if r is None:
        return None
    parts = r.split(".")
    if parts[-1] not in _DISPATCH_KERNELS:
        return None
    if "kernels" in parts[:-1]:
        return parts[-1]
    return None


def _supervised_functions(ctx: ModuleContext) -> Set[ast.AST]:
    """Function/lambda nodes whose BODY is executed under guard.supervised:
    the first argument of a supervised(...) call, resolved through a direct
    name, a functools.partial wrapper, or a method attribute."""
    out: Set[ast.AST] = set()

    def mark(expr: Optional[ast.expr]) -> None:
        if expr is None:
            return
        if isinstance(expr, ast.Lambda):
            out.add(expr)
            return
        fn = ctx.lookup_function(expr)
        if fn is not None:
            out.add(fn)
            return
        if isinstance(expr, ast.Call):
            r = ctx.resolve(expr.func) or ""
            if r in PARTIAL_NAMES or r.endswith(".partial"):
                mark(expr.args[0] if expr.args else None)
            return
        if isinstance(expr, ast.Attribute):
            # self._dispatch_round and friends: methods register by name
            for fn in ctx.functions.get(expr.attr, []):
                out.add(fn)

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        r = ctx.resolve(node.func) or ""
        if r == "supervised" or r.endswith(".supervised"):
            mark(node.args[0] if node.args else None)
    return out


@register(
    "naked-dispatch", Severity.WARNING,
    "A compiled scheduling/probe kernel is dispatched directly, outside "
    "guard.supervised (resilience/guard.py). An unsupervised dispatch on a "
    "wedged backend blocks the process forever — the exact failure mode the "
    "dispatch watchdog exists to contain. Route the call through "
    "guard.supervised (directly, via functools.partial, or by passing the "
    "enclosing function), or whitelist deliberate harness/offline code with "
    "`# simonlint: ignore[naked-dispatch] -- <why>`.",
)
def rule_naked_dispatch(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    guarded = _supervised_functions(ctx)
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        kernel = _is_kernel_dispatch(ctx, node)
        if kernel is None:
            continue
        covered = False
        cur: Optional[ast.AST] = node
        while cur is not None:
            if cur in guarded:
                covered = True
                break
            if isinstance(cur, ast.Call):
                r = ctx.resolve(cur.func) or ""
                if r == "supervised" or r.endswith(".supervised"):
                    covered = True
                    break
            cur = ctx.parents.get(cur)
        if not covered:
            out.append(Finding(
                "naked-dispatch", Severity.WARNING, ctx.path,
                node.lineno, node.col_offset,
                f"kernels.{kernel}(...) dispatched outside guard.supervised "
                f"— a wedged backend would hang here with no watchdog, "
                f"quarantine, or failover (wrap the dispatch, or whitelist "
                f"non-hot-path harness code)",
            ))
    return out


# ----------------------------------------------------- unattributed-dispatch --


def _wrapped_dispatch_targets(
        ctx: ModuleContext, call: ast.Call,
        encl: Optional[ast.AST]) -> tuple:
    """(function_nodes, kernel_name) for a specific supervised(...) call —
    the per-call-site companion of _supervised_functions. Resolves the first
    argument through a direct name, a functools.partial wrapper, a method
    attribute, or one level of local assignment in the enclosing function
    (`call = functools.partial(...); supervised(call, ...)`). kernel_name is
    set when the wrapped callable IS a dispatch kernel (partial-of-kernel,
    the engine's hottest form), independent of function_nodes."""
    fns: List[ast.AST] = []
    kernel: List[Optional[str]] = [None]

    def add(expr: Optional[ast.expr], depth: int = 0) -> None:
        if expr is None or depth > 4:
            return
        if isinstance(expr, ast.Lambda):
            fns.append(expr)
            return
        r = ctx.resolve(expr)
        if r is not None and r.split(".")[-1] in _DISPATCH_KERNELS:
            kernel[0] = r.split(".")[-1]
            return
        fn = ctx.lookup_function(expr)
        if fn is not None:
            fns.append(fn)
            return
        if isinstance(expr, ast.Call):
            cr = ctx.resolve(expr.func) or ""
            if cr in PARTIAL_NAMES or cr.endswith(".partial"):
                add(expr.args[0] if expr.args else None, depth + 1)
            return
        if isinstance(expr, ast.Name) and encl is not None:
            for node in ast.walk(encl):
                if (isinstance(node, ast.Assign)
                        and any(isinstance(t, ast.Name) and t.id == expr.id
                                for t in node.targets)):
                    add(node.value, depth + 1)
            return
        if isinstance(expr, ast.Attribute):
            fns.extend(ctx.functions.get(expr.attr, []))

    add(call.args[0] if call.args else None)
    return fns, kernel[0]


def _has_record_dispatch(ctx: ModuleContext,
                         scope: Optional[ast.AST]) -> bool:
    if scope is None:
        return False
    for n in ast.walk(scope):
        if isinstance(n, ast.Call):
            r = ctx.resolve(n.func) or ""
            if r == "record_dispatch" or r.endswith(".record_dispatch"):
                return True
    return False


@register(
    "unattributed-dispatch", Severity.WARNING,
    "A hot kernel is dispatched under guard.supervised with no "
    "obs.record_dispatch(...) in its attribution path. record_dispatch is "
    "the single definition of 'one dispatch happened': it keys the "
    "compile-cache hit/miss census AND parks the simonpulse ledger note "
    "that guard.supervised commits after the unit returns — without it the "
    "dispatch is invisible to both. Call obs.record_dispatch(kernel, "
    "**dims) at the supervised call site (engine pattern) or inside the "
    "wrapped function body (probe pattern), or whitelist deliberate "
    "harness/offline code with "
    "`# simonlint: ignore[unattributed-dispatch] -- <why>`.",
)
def rule_unattributed_dispatch(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        r = ctx.resolve(node.func) or ""
        if not (r == "supervised" or r.endswith(".supervised")):
            continue
        encl: Optional[ast.AST] = ctx.parents.get(node)
        while encl is not None and not isinstance(
                encl, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            encl = ctx.parents.get(encl)
        wrapped, kernel = _wrapped_dispatch_targets(ctx, node, encl)
        if kernel is None:
            for fn in wrapped:
                for sub in ast.walk(fn):
                    if isinstance(sub, ast.Call):
                        k = _is_kernel_dispatch(ctx, sub)
                        if k is not None:
                            kernel = k
                            break
                if kernel is not None:
                    break
        if kernel is None:
            continue  # supervised fetch/host work — not a kernel dispatch
        # attribution path 1 (probe pattern): record_dispatch runs inside
        # the wrapped body, so the note lands in the worker's context
        if any(_has_record_dispatch(ctx, fn) for fn in wrapped):
            continue
        # attribution path 2 (engine pattern): record_dispatch at the
        # supervised call site, before the unit is handed to the watchdog
        if _has_record_dispatch(ctx, encl if encl is not None else ctx.tree):
            continue
        out.append(Finding(
            "unattributed-dispatch", Severity.WARNING, ctx.path,
            node.lineno, node.col_offset,
            f"kernels.{kernel}(...) runs under guard.supervised with no "
            f"record_dispatch in its attribution path — the dispatch is "
            f"invisible to the compile-cache census and lands in the "
            f"simonpulse ledger with no kernel/bucket attribution (call "
            f"obs.record_dispatch at the call site or inside the wrapped "
            f"body, or whitelist offline harness code)",
        ))
    return out


# ---------------------------------------------------------- fetch-in-wave-loop --

# Loop-name fragments marking per-segment / per-epoch / per-round dispatch
# loops (the engine's `for seg in segs:` dispatch loop, the wave kernels'
# epoch machinery mirrored on the host, capacity-search rounds). A fetch
# inside such a body pays one full device round trip PER ITERATION — the
# exact latency hazard the PR 3 "fetch ONE concatenated result at the
# end" rewrite removed, and the one xray-style instrumentation most easily
# reintroduces.
_WAVE_LOOP_NAMES = ("seg", "epoch", "round", "wave")

# Resolved call targets that force a device→host sync when applied to a
# device value. jnp.* stays device-side and is deliberately absent.
_FETCH_CALLS = {
    "numpy.asarray", "numpy.array", "jax.device_get",
    "jax.block_until_ready",
}
_FETCH_ATTRS = {"block_until_ready", "device_get"}


def _loopish_names(node: ast.AST) -> Set[str]:
    """Lower-cased identifier names in a loop's target/iter (For) or test
    (While) — the signal for 'this iterates segments/epochs/rounds'."""
    if isinstance(node, ast.For):
        src: List[ast.AST] = [node.target, node.iter]
    elif isinstance(node, ast.While):
        src = [node.test]
    else:
        return set()
    out: Set[str] = set()
    for expr in src:
        out |= {n.lower() for n in _names_in(expr)}
    return out


@register(
    "fetch-in-wave-loop", Severity.WARNING,
    "A device->host fetch (np.asarray / jax.device_get / block_until_ready) "
    "sits inside a per-segment/per-epoch/per-round loop body. Each "
    "iteration then pays a full device round trip, which can turn "
    "milliseconds of device work into seconds of waiting (the engine's "
    "dispatch loop collects results and fetches ONE "
    "concatenated array after the loop for exactly this reason). Move the "
    "fetch to a post-loop spill point, or whitelist a deliberate blocking "
    "site with `# simonlint: ignore[fetch-in-wave-loop] -- <why>`.",
)
def rule_fetch_in_wave_loop(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    seen: Set[tuple] = set()  # nested wave-named loops report a site once
    for loop in ast.walk(ctx.tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        names = _loopish_names(loop)
        if not any(frag in name for name in names
                   for frag in _WAVE_LOOP_NAMES):
            continue
        for sub in ast.walk(loop):
            if sub is loop or not isinstance(sub, ast.Call):
                continue
            if (sub.lineno, sub.col_offset) in seen:
                continue
            r = ctx.resolve(sub.func) or ""
            leaf = r.split(".")[-1]
            if r not in _FETCH_CALLS and leaf not in _FETCH_ATTRS:
                continue
            seen.add((sub.lineno, sub.col_offset))
            out.append(Finding(
                "fetch-in-wave-loop", Severity.WARNING, ctx.path,
                sub.lineno, sub.col_offset,
                f"{r or leaf}(...) inside a "
                f"per-{'/'.join(sorted(names & set(_WAVE_LOOP_NAMES)) or ['segment'])} "
                f"loop body forces one device round trip per iteration — "
                f"collect device values and fetch once after the loop "
                f"(designated spill point)",
            ))
    return out


# -------------------------------------------------------------- contract-spec --


@register(
    "contract-spec", Severity.ERROR,
    "An @shaped(...) kernel contract names a parameter the function does not "
    "have, or a spec string that does not parse ('[DIMS] dtype', e.g. "
    "'[N, R] f32'). Broken contracts are worse than none: simonlint and "
    "readers both trust them.",
)
def rule_contract_spec(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for defs in ctx.functions.values():
        for fn in defs:
            for dec in fn.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                r = ctx.resolve(dec.func) or ""
                if not (r == "shaped" or r.endswith(".shaped")):
                    continue
                a = fn.args
                params = {p.arg for p in a.posonlyargs + a.args + a.kwonlyargs}
                for kw in dec.keywords:
                    if kw.arg is None:
                        continue
                    if kw.arg not in params and kw.arg not in ("ret", "returns"):
                        out.append(Finding(
                            "contract-spec", Severity.ERROR, ctx.path,
                            kw.value.lineno, kw.value.col_offset,
                            f"@shaped names '{kw.arg}' which is not a "
                            f"parameter of '{fn.name}'",
                        ))
                        continue
                    if isinstance(kw.value, ast.Constant) and isinstance(
                            kw.value.value, str):
                        try:
                            parse_spec(kw.value.value)
                        except ValueError as e:
                            out.append(Finding(
                                "contract-spec", Severity.ERROR, ctx.path,
                                kw.value.lineno, kw.value.col_offset,
                                f"@shaped spec for '{kw.arg}' does not parse: {e}",
                            ))
    return out


# ---------------------------------------------------------- unsharded-transfer --

# The sharded dispatch chain (parallel/mesh.py ShardedKernels) only stays
# reshard-free when every transfer and every jitted dispatch in a mesh-aware
# hot path declares its layout. A naked jax.device_put lands wherever the
# default device policy says (then the first sharded consumer pays a
# reshard); a jit over a dispatch kernel without in_shardings lets GSPMD
# re-infer per call.


def _module_is_mesh_aware(ctx: ModuleContext) -> bool:
    """True when the module imports the parallel (mesh/sharding) machinery —
    engine.py, probe.py, and parallel/ itself qualify via their (possibly
    function-local, possibly relative) `from ..parallel.mesh import ...`."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if "parallel" in mod.split("."):
                return True
        elif isinstance(node, ast.Import):
            if any("parallel" in a.name.split(".") for a in node.names):
                return True
    return False


@register(
    "unsharded-transfer", Severity.WARNING,
    "In a mesh-aware hot path (a module importing parallel/), a "
    "jax.device_put without an explicit sharding/device argument or a "
    "jax.jit over a dispatch kernel without in_shardings breaks the "
    "end-to-end sharding contract: the array lands in the default layout "
    "(or GSPMD re-infers one per call) and the next chained dispatch pays a "
    "reshard — the exact regression simon_reshard_bytes_total exists to "
    "catch at runtime. Pass the sharding explicitly (table_shardings / "
    "carry_shardings / fanout_shardings), route the dispatch through "
    "parallel.mesh.sharded_kernels, or whitelist a deliberate host-layout "
    "transfer with `# simonlint: ignore[unsharded-transfer] -- <why>`.",
)
def rule_unsharded_transfer(ctx: ModuleContext) -> List[Finding]:
    if not _module_is_mesh_aware(ctx):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        r = ctx.resolve(node.func) or ""
        if r == "jax.device_put":
            # only a TARGET placement counts: a `src=` keyword names where
            # the array comes from, committing no output layout at all
            has_target = len(node.args) >= 2 or any(
                kw.arg == "device" for kw in node.keywords)
            if not has_target:
                out.append(Finding(
                    "unsharded-transfer", Severity.WARNING, ctx.path,
                    node.lineno, node.col_offset,
                    "jax.device_put without an explicit sharding commits the "
                    "array to the default device layout; the first sharded "
                    "consumer then reshards it — pass the NamedSharding "
                    "(table_shardings/carry_shardings/fanout_shardings)",
                ))
        elif r in JIT_NAMES and node.args:
            target = ctx.resolve(node.args[0]) or ""
            if target.split(".")[-1] not in _DISPATCH_KERNELS:
                continue
            if not any(kw.arg == "in_shardings" for kw in node.keywords):
                out.append(Finding(
                    "unsharded-transfer", Severity.WARNING, ctx.path,
                    node.lineno, node.col_offset,
                    f"jax.jit({target.split('.')[-1]}, ...) in a mesh-aware "
                    f"module without in_shardings lets GSPMD re-infer the "
                    f"layout per call — declare in_shardings/out_shardings "
                    f"(or reuse parallel.mesh.sharded_kernels)",
                ))
    return out


# ------------------------------------------------ config-scope-across-thread --

# JAX config context managers whose effect is THREAD-LOCAL: entering one and
# then handing work to another thread silently drops the scope for that work
# (jax's config stack lives in a per-thread structure that copy_context()
# does not carry). This is the exact PR 5 failure class: a post-failover
# dispatch wrapped in `with jax.default_device(cpu)` kept landing on the
# quarantined backend because the dispatch ran in the watchdog's worker
# thread. The fix — re-entering the scope INSIDE the worker (guard.supervised
# does this) — leaves no `with` wrapping a cross-thread submission, so a
# clean tree has zero findings.
_JAX_SCOPE_CMS = {
    "jax.default_device", "jax.disable_jit", "jax.default_matmul_precision",
    "jax.transfer_guard", "jax.log_compiles", "jax.debug_nans",
    "jax.checking_leaks", "jax.enable_checks",
}
# a constructed Thread/Timer/Process runs its target on another thread even
# if .start() happens later; to_thread/run_in_executor submit directly
_THREAD_FACTORIES = {
    "threading.Thread", "threading.Timer", "multiprocessing.Process",
    "asyncio.to_thread",
}
_SUBMIT_ATTRS = {"submit", "run_in_executor", "apply_async", "map_async"}


@register(
    "config-scope-across-thread", Severity.ERROR,
    "A jax config context manager (jax.default_device / disable_jit / "
    "default_matmul_precision / ...) is entered in one thread while work is "
    "submitted to another inside the scope (executor.submit, "
    "threading.Thread/Timer targets, asyncio.to_thread). JAX config scopes "
    "are thread-local and are NOT carried by copy_context(): the submitted "
    "work runs with the scope silently absent — the post-failover "
    "wrong-backend dispatch bug. Re-enter the scope inside the worker "
    "(the guard.supervised pattern), or whitelist work that provably never "
    "touches jax with `# simonlint: ignore[config-scope-across-thread] -- "
    "<why>`.",
)
def rule_config_scope_across_thread(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        scope: Optional[str] = None
        for item in node.items:
            expr = item.context_expr
            target = expr.func if isinstance(expr, ast.Call) else expr
            r = ctx.resolve(target)
            if r in _JAX_SCOPE_CMS:
                scope = r
                break
        if scope is None:
            continue
        for sub in _walk_no_defs(node.body):
            if not isinstance(sub, ast.Call):
                continue
            r = ctx.resolve(sub.func) or ""
            hazard: Optional[str] = None
            if r in _THREAD_FACTORIES:
                hazard = f"{r}(...)"
            elif (isinstance(sub.func, ast.Attribute)
                    and sub.func.attr in _SUBMIT_ATTRS):
                hazard = f".{sub.func.attr}(...)"
            if hazard:
                out.append(Finding(
                    "config-scope-across-thread", Severity.ERROR, ctx.path,
                    sub.lineno, sub.col_offset,
                    f"{hazard} inside `with {scope}(...)`: jax config scopes "
                    f"are thread-local, so the submitted work runs with the "
                    f"scope silently dropped — re-enter the scope inside the "
                    f"worker (guard.supervised pattern)",
                ))
    return out


# ------------------------------------------------------- span-outside-guard --

# Span-like context managers whose wall-time measurement is the concern:
# utils/trace.Span and the simonscope live-span context managers.
_SPAN_ATTRS = {"span", "request_span"}


def _is_span_ctx(ctx: ModuleContext, expr: ast.expr) -> Optional[str]:
    """The span-context name when `expr` (a with-item context expression)
    opens a tracing span: utils/trace Span(...) via any import form, or a
    scope span method (`sc.span(...)` / `sc.request_span(...)`)."""
    if not isinstance(expr, ast.Call):
        return None
    r = ctx.resolve(expr.func)
    if r is not None and (r == "Span" or r.endswith(".Span")):
        return r
    f = expr.func
    if isinstance(f, ast.Attribute) and f.attr in _SPAN_ATTRS:
        return f".{f.attr}(...)"
    return None


@register(
    "span-outside-guard", Severity.WARNING,
    "A tracing Span (utils/trace.Span or a simonscope span) is opened around "
    "a kernel dispatch site that is not inside guard.supervised. The span "
    "then measures wall time the watchdog can abandon: on a wedged backend "
    "the unsupervised dispatch blocks forever INSIDE the span, so the trace "
    "never records the phase at all (and the process hangs with it). Wrap "
    "the dispatch in guard.supervised — the span may stay around the "
    "supervised call — or whitelist deliberate offline/harness timing with "
    "`# simonlint: ignore[span-outside-guard] -- <why>`.",
)
def rule_span_outside_guard(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    guarded = _supervised_functions(ctx)

    def covered(call: ast.Call) -> bool:
        cur: Optional[ast.AST] = call
        while cur is not None:
            if cur in guarded:
                return True
            if isinstance(cur, ast.Call):
                r = ctx.resolve(cur.func) or ""
                if r == "supervised" or r.endswith(".supervised"):
                    return True
            cur = ctx.parents.get(cur)
        return False

    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        span_name = None
        for item in node.items:
            span_name = _is_span_ctx(ctx, item.context_expr)
            if span_name is not None:
                break
        if span_name is None:
            continue
        for sub in _walk_no_defs(node.body):
            if not isinstance(sub, ast.Call):
                continue
            kernel = _is_kernel_dispatch(ctx, sub)
            if kernel is None or covered(sub):
                continue
            out.append(Finding(
                "span-outside-guard", Severity.WARNING, ctx.path,
                sub.lineno, sub.col_offset,
                f"kernels.{kernel}(...) dispatched inside `with "
                f"{span_name}` but outside guard.supervised — the span "
                f"records wall time the watchdog can abandon (a wedge "
                f"hangs inside the span and the phase is never traced); "
                f"supervise the dispatch",
            ))
    return out


# ---------------------------------------------------- collective-in-scan-body --

# Cross-shard collectives: one launch per loop ITERATION when called from a
# scan/while/fori body. Each costs a cross-device round trip, so a loop that
# reduces per round pays latency x rounds where a stacked operand reduced once
# per loop entry (or once per epoch) pays it once.
_COLLECTIVE_NAMES = {
    "jax.lax.psum", "jax.lax.pmax", "jax.lax.pmin", "jax.lax.pmean",
    "jax.lax.all_gather", "jax.lax.all_to_all", "jax.lax.ppermute",
    "jax.lax.psum_scatter", "jax.lax.pshuffle",
}


@register(
    "collective-in-scan-body", Severity.WARNING,
    "A cross-shard collective (psum / pmax / all_gather / ...) executes inside "
    "a lax.scan / while_loop / fori_loop body, directly or through a locally "
    "defined helper. The collective then launches once per ITERATION: its "
    "cross-device latency multiplies by the trip count, which is exactly the "
    "pattern that kept the sharded hard-predicate wave at 0.1x of serial. "
    "Stack the per-round operands and reduce ONCE per loop entry (max-space "
    "packing handles mins: -max(-x) == min(x) exactly in f32), or hoist the "
    "collective to the epoch boundary. A deliberate epoch-amortized collective "
    "— one reduction per outer-loop iteration over a stacked operand — is the "
    "fix, not a violation; waive it with "
    "`# simonlint: ignore[collective-in-scan-body] -- <why>`.",
)
def rule_collective_in_scan_body(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    seen_sites: Set[tuple] = set()

    for site in ctx.scans:
        if site.body is None:
            continue
        # Walk the body transitively through locally-called helpers: kernels
        # factor loop bodies into `front(...)` / `tail(...)` functions, and the
        # collective usually lives in the helper, not the body literal.
        visited = {site.body}
        frontier = [site.body]
        while frontier:
            fn = frontier.pop()
            for sub in _walk_no_defs(fn.body):
                if not isinstance(sub, ast.Call):
                    continue
                r = ctx.resolve(sub.func)
                if r in _COLLECTIVE_NAMES:
                    key = (sub.lineno, sub.col_offset)
                    if key in seen_sites:
                        continue
                    seen_sites.add(key)
                    out.append(Finding(
                        "collective-in-scan-body", Severity.WARNING, ctx.path,
                        sub.lineno, sub.col_offset,
                        f"{r}(...) runs inside a {site.kind} body (via "
                        f"`{site.body.name}`): one cross-shard launch per "
                        f"iteration — stack the operands and reduce once per "
                        f"loop entry, or hoist to the epoch boundary",
                    ))
                    continue
                callee = ctx.lookup_function(sub.func)
                if callee is not None and callee not in visited:
                    visited.add(callee)
                    frontier.append(callee)
    return out


# ---------------------------------------------------------- suppression-reason --


def _waiver_anchor(lines: List[str], lineno: int) -> int:
    """The code line a waiver at `lineno` binds to, mirroring
    base.suppressions_for: a trailing comment binds to its own line, a
    comment-only line carries forward to the first code line below. The
    finding anchors THERE so a reasoned ignore[suppression-reason] waiver
    covers it through the normal suppression mechanics."""
    if not lines[lineno - 1].lstrip().startswith("#"):
        return lineno
    for i in range(lineno + 1, len(lines) + 1):
        stripped = lines[i - 1].strip()
        if stripped and not stripped.startswith("#"):
            return i
    return lineno


@register(
    "suppression-reason", Severity.WARNING,
    "A `# simonlint: ignore[...]` waiver without its `-- reason` text. Every "
    "suppression is a claim that a hazard is deliberate; the reason is the "
    "evidence reviewers audit. Bare waivers rot: nobody can tell a sanctioned "
    "device boundary from a silenced bug. (This finding is itself only "
    "waivable by an explicit reasoned `ignore[suppression-reason]` — a bare "
    "`ignore[*]` does not cover it.)",
)
def rule_suppression_reason(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for lineno, raw in enumerate(ctx.lines, start=1):
        m = _SUPPRESS_RE.search(raw)
        if m is None:
            continue
        if _REASON_RE.match(raw[m.end():]):  # the same test base.py applies
            continue
        anchor = _waiver_anchor(ctx.lines, lineno)
        where = "" if anchor == lineno else f" (waiver at line {lineno})"
        out.append(Finding(
            "suppression-reason", Severity.WARNING, ctx.path,
            anchor, m.start(),
            f"waiver ignore[{m.group(1).strip()}] carries no `-- reason` "
            f"text{where} — state why the hazard is deliberate so reviewers "
            f"can audit it",
        ))
    return out


# --------------------------------------------------------- per-pod-host-loop --

# Modules that have adopted the columnar pod store (simulator/store.py) are
# held to its contract: batch-sized work is array ops over the store's
# columns, and a Python `for` over the pod batch is the O(pods) host loop the
# store exists to remove (the 1M-pod row spent ~60% of wall in exactly two
# such loops before the rewrite). Applicability is structural — the module
# imports `.store` / `..simulator.store` — so adopting the store opts a
# module into the fence, and fallback loops that must remain (dict batches,
# armed preemption, gpu/storage ledgers) carry reasoned waivers naming the
# columnar path that replaces them.
_POD_BATCH_NAMES = {"pods", "to_schedule", "batch", "request_pods"}


def _module_imports_store(ctx: ModuleContext) -> bool:
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.split(".")[-1] == "store" or any(
                    a.name == "store" for a in node.names):
                return True
        elif isinstance(node, ast.Import):
            if any(a.name.split(".")[-1] == "store" for a in node.names):
                return True
    return False


@register(
    "per-pod-host-loop", Severity.WARNING,
    "A Python `for` over a pod batch (pods / to_schedule / batch) in a "
    "module that has adopted the columnar PodStore. Each iteration is host "
    "work that scales with the batch — the O(pods) dict traversal the "
    "struct-of-arrays store exists to replace (encode is one gather per "
    "template, commit is one bulk array pass). Vectorize over the store's "
    "columns, or whitelist a deliberate fallback with "
    "`# simonlint: ignore[per-pod-host-loop] -- <why>` naming the columnar "
    "path that covers the hot case.",
)
def rule_per_pod_host_loop(ctx: ModuleContext) -> List[Finding]:
    if not _module_imports_store(ctx):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.For):
            continue
        hits = _names_in(node.iter) & _POD_BATCH_NAMES
        if not hits:
            continue
        out.append(Finding(
            "per-pod-host-loop", Severity.WARNING, ctx.path,
            node.lineno, node.col_offset,
            f"`for` over {'/'.join(sorted(hits))} runs O(pods) Python in a "
            f"store-adopted hot module — vectorize over the PodStore columns "
            f"(EncodedRows gather / bulk commit) or waive the deliberate "
            f"fallback with its reason",
        ))
    return out


# ------------------------------------------------------------ unbounded-queue --

# The serving tier's memory-safety discipline (simonha, serve/ha.py): every
# producer/consumer channel in a long-lived process is a memory hazard unless
# its depth is bounded — a stalled consumer turns an unbounded queue into an
# OOM kill with no 429 ever sent. stdlib spellings of "unbounded":
# queue.Queue/LifoQueue/PriorityQueue with no maxsize (or an explicit
# maxsize=0), SimpleQueue (never bounded), and collections.deque with no
# maxlen.
_QUEUE_CTORS = {"Queue", "LifoQueue", "PriorityQueue"}


def _is_zero(node: Optional[ast.AST]) -> bool:
    return (isinstance(node, ast.Constant) and isinstance(node.value, int)
            and not isinstance(node.value, bool) and node.value == 0)


@register(
    "unbounded-queue", Severity.WARNING,
    "A queue.Queue()/LifoQueue/PriorityQueue without a positive maxsize, a "
    "SimpleQueue (unboundable by construction), or a collections.deque() "
    "without maxlen. In a long-lived serving process an unbounded channel is "
    "deferred OOM: a stalled or slow consumer absorbs the backlog into heap "
    "instead of shedding it at admission (simonha's bounded-queue + 429 "
    "discipline). Pass maxsize=/maxlen=, or waive a deliberately unbounded "
    "channel with `# simonlint: ignore[unbounded-queue] -- <why it is "
    "bounded elsewhere>`.",
)
def rule_unbounded_queue(ctx: ModuleContext) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if isinstance(func, ast.Attribute):
            name = func.attr
        elif isinstance(func, ast.Name):
            name = func.id
        else:
            continue
        hazard: Optional[str] = None
        if name == "SimpleQueue":
            hazard = ("SimpleQueue has no maxsize at all — use "
                      "queue.Queue(maxsize=N)")
        elif name in _QUEUE_CTORS:
            maxsize = node.args[0] if node.args else next(
                (kw.value for kw in node.keywords if kw.arg == "maxsize"),
                None)
            if maxsize is None or _is_zero(maxsize):
                hazard = (f"{name}() without a positive maxsize accepts an "
                          f"unbounded backlog")
        elif name == "deque":
            # deque(iterable, maxlen): a second positional IS the bound
            maxlen = node.args[1] if len(node.args) > 1 else next(
                (kw.value for kw in node.keywords if kw.arg == "maxlen"),
                None)
            if maxlen is None:
                hazard = "deque() without maxlen grows with its producer"
        if hazard is None:
            continue
        out.append(Finding(
            "unbounded-queue", Severity.WARNING, ctx.path,
            node.lineno, node.col_offset,
            f"{hazard} — bound the channel and shed at admission, or waive "
            f"with the reason the depth is bounded elsewhere",
        ))
    return out


# ------------------------------------------ unclassified-network-error --

# The live tier's error taxonomy (simulator/live.py, live/sync.py): every
# network failure routes to exactly one of AuthError (fatal, never
# retried), TransientError (reconnect under the seeded RetryPolicy), or
# ProtocolError (bounded teardown; code=410 triggers relist
# reconciliation). A bare `except OSError: return None` in live code
# silently converts a dropped connection into wrong control flow — the
# retry/breaker/relist machinery never sees the failure, so the watch
# neither reconnects nor reconciles. Scope is structural: modules living
# in a `live` package directory or with a `live*` basename. Non-network
# uses of OSError in live modules (bookmark-file reads, best-effort
# close()) carry reasoned waivers.
_NETWORK_EXC = {
    "OSError", "IOError", "ConnectionError", "ConnectionResetError",
    "ConnectionRefusedError", "ConnectionAbortedError", "BrokenPipeError",
    "TimeoutError", "socket.error", "socket.timeout", "socket.gaierror",
    "socket.herror", "ssl.SSLError", "ssl.SSLEOFError",
    "urllib.error.URLError", "urllib.error.HTTPError",
    "http.client.HTTPException",
}
_NETWORK_EXC_PREFIXES = ("http.client.", "socket.")
_ERROR_TAXONOMY = {"AuthError", "TransientError", "ProtocolError"}


def _is_live_module(path: str) -> bool:
    parts = path.replace("\\", "/").split("/")
    return "live" in parts[:-1] or parts[-1].startswith("live")


def _caught_network_names(ctx: ModuleContext,
                          handler: ast.ExceptHandler) -> Set[str]:
    typ = handler.type
    if typ is None:
        return set()
    elts = typ.elts if isinstance(typ, ast.Tuple) else [typ]
    hits: Set[str] = set()
    for e in elts:
        name = ctx.resolve(e)
        if name is None:
            continue
        if name in _NETWORK_EXC or name.startswith(_NETWORK_EXC_PREFIXES):
            hits.add(name)
    return hits


def _routes_to_taxonomy(ctx: ModuleContext,
                        handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if not isinstance(node, ast.Raise):
            continue
        if node.exc is None:
            return True  # bare re-raise hands the error upward intact
        exc = node.exc
        if isinstance(exc, ast.Call):
            exc = exc.func
        name = ctx.resolve(exc)
        if name and name.split(".")[-1] in _ERROR_TAXONOMY:
            return True
    return False


@register(
    "unclassified-network-error", Severity.WARNING,
    "A network-error catch (OSError family, socket.*, urllib.error.*, "
    "http.client.*) in a live-cluster module whose handler neither raises "
    "one of the typed taxonomy errors (AuthError / TransientError / "
    "ProtocolError) nor bare-re-raises. Unrouted network failures bypass "
    "the retry/breaker/relist machinery entirely: the watch loop can't "
    "reconnect on what it never sees. Classify the failure, or waive a "
    "genuinely non-network OSError site with `# simonlint: "
    "ignore[unclassified-network-error] -- <why it is not a network "
    "path>`.",
)
def rule_unclassified_network_error(ctx: ModuleContext) -> List[Finding]:
    if not _is_live_module(ctx.path):
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        hits = _caught_network_names(ctx, node)
        if not hits or _routes_to_taxonomy(ctx, node):
            continue
        out.append(Finding(
            "unclassified-network-error", Severity.WARNING, ctx.path,
            node.lineno, node.col_offset,
            f"except {'/'.join(sorted(hits))} in live code swallows a "
            f"network failure the retry/breaker/relist machinery never "
            f"sees — raise AuthError/TransientError/ProtocolError (or "
            f"bare-re-raise), or waive with why this is not a network "
            f"path",
        ))
    return out
