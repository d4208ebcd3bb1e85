"""slate-lint rule set: ~10 codebase-specific AST rules.

Each rule is a checker registered in :data:`RULES` with an ID, severity, and
one-line title.  Checkers receive a ``ModuleCtx`` (see ``lint.py``) exposing
the parsed tree, parent links, qualnames, and a ``finding()`` factory; they
yield :class:`~slate_tpu.analysis.findings.Finding` objects.

The rules encode the JAX pitfalls that have cost this repo debugging rounds
(ISSUE 10): tracer hygiene inside jitted/vmapped/shard_mapped cores,
recompilation hazards, x64 scope leaks, leftover debug hooks, donation
misuse, taxonomy-swallowing ``except`` blocks, and missing ``@obs.instrument``
on public distributed drivers.

Suppression: any rule can be silenced at one site with a trailing or
preceding comment ``# slate-lint: disable=SLT501 -- reason`` (the reason is
mandatory by convention and checked in review, not by the parser).  Accepted
pre-existing findings live in ``analysis/baseline.json`` instead.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Set, Tuple

from .findings import Finding

# ---------------------------------------------------------------------------
# registry


@dataclasses.dataclass(frozen=True)
class Rule:
    """One registered lint rule."""

    id: str
    severity: str
    title: str
    doc: str
    checker: Callable


RULES: Dict[str, Rule] = {}


def rule(rule_id: str, severity: str, title: str):
    """Register a checker under ``rule_id`` (decorator)."""
    def deco(fn):
        RULES[rule_id] = Rule(rule_id, severity, title,
                              (fn.__doc__ or "").strip(), fn)
        return fn
    return deco


# ---------------------------------------------------------------------------
# shared AST helpers

#: attribute reads on a traced array that are static at trace time — Python
#: control flow on these is NOT a tracer leak
STATIC_SAFE_ATTRS = frozenset({"shape", "ndim", "dtype", "size", "sharding",
                               "itemsize"})

#: transforms whose function argument becomes a traced core
_TRACE_WRAPPERS = ("jit", "vmap", "pmap", "shard_map")


def dotted(node: ast.AST) -> str:
    """``a.b.c`` for an Attribute/Name chain, else ''."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _is_wrapper_name(name: str, kinds: Sequence[str] = _TRACE_WRAPPERS) -> bool:
    last = name.rsplit(".", 1)[-1]
    return last in kinds


def _partial_jit_target(call: ast.Call) -> Optional[ast.Call]:
    """``functools.partial(jax.jit, ...)`` -> the partial call, else None."""
    if not isinstance(call, ast.Call):
        return None
    if _is_wrapper_name(dotted(call.func), ("partial",)) and call.args:
        inner = dotted(call.args[0])
        if _is_wrapper_name(inner, ("jit", "vmap", "pmap")):
            return call
    return None


def _literal_int_tuple(node: ast.AST) -> Optional[Tuple[int, ...]]:
    """Literal int / tuple-or-list of ints -> values, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                    and not isinstance(e.value, bool):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


def _literal_str_tuple(node: ast.AST) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


def _param_names(fn: ast.AST) -> List[str]:
    a = fn.args
    names = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        names.append(a.vararg.arg)
    if a.kwarg:
        names.append(a.kwarg.arg)
    return names


def _static_params_from_kwargs(fn: ast.AST, kwargs: List[ast.keyword]
                               ) -> Set[str]:
    """static_argnums/static_argnames keywords -> static param name set."""
    params = _param_names(fn)
    static: Set[str] = set()
    for kw in kwargs:
        if kw.arg == "static_argnames":
            static.update(_literal_str_tuple(kw.value) or ())
        elif kw.arg == "static_argnums":
            for i in _literal_int_tuple(kw.value) or ():
                if 0 <= i < len(params):
                    static.add(params[i])
    return static


@dataclasses.dataclass
class TracedCore:
    """A function whose body traces: decorated with jit/vmap, or passed by
    name into jit/vmap/pmap/shard_map within the module."""

    fn: ast.AST                    # FunctionDef / AsyncFunctionDef
    how: str                       # "decorator" | "call:<wrapper>"
    static: Set[str]               # params that are static at trace time


def traced_cores(tree: ast.Module) -> List[TracedCore]:
    """Collect every function in the module whose body is traced."""
    cores: Dict[ast.AST, TracedCore] = {}
    fns_by_name: Dict[str, List[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fns_by_name.setdefault(node.name, []).append(node)
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call):
                    p = _partial_jit_target(dec)
                    if p is not None:
                        cores[node] = TracedCore(
                            node, "decorator",
                            _static_params_from_kwargs(node, p.keywords))
                    elif _is_wrapper_name(dotted(dec.func), ("jit", "vmap")):
                        cores[node] = TracedCore(
                            node, "decorator",
                            _static_params_from_kwargs(node, dec.keywords))
                elif _is_wrapper_name(dotted(dec), ("jit", "vmap")):
                    cores.setdefault(node, TracedCore(node, "decorator",
                                                      set()))
    # call form: jit(fn, ...) / shard_map(fn, ...) / vmap(fn) with fn a
    # module-or-locally defined function referenced by name
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted(node.func)
        if not _is_wrapper_name(name):
            continue
        wrapper = name.rsplit(".", 1)[-1]
        for arg in node.args[:1]:      # the traced callable is arg 0
            if isinstance(arg, ast.Name):
                for fn in fns_by_name.get(arg.id, ()):
                    if fn not in cores:
                        static = (_static_params_from_kwargs(fn, node.keywords)
                                  if wrapper == "jit" else set())
                        cores[fn] = TracedCore(fn, f"call:{wrapper}", static)
    return list(cores.values())


def _traced_param_uses(core: TracedCore, scope: ast.AST, ctx
                       ) -> Iterator[ast.Name]:
    """Bare loads of non-static core params within ``scope`` that are not in
    a static-safe position (``x.shape``, ``x is None``, ``len(x)``,
    ``isinstance(x, ...)``)."""
    traced = set(_param_names(core.fn)) - core.static
    for n in ast.walk(scope):
        if not (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                and n.id in traced):
            continue
        parent = ctx.parent(n)
        if isinstance(parent, ast.Attribute) \
                and parent.attr in STATIC_SAFE_ATTRS:
            continue
        if isinstance(parent, ast.Call) and parent.func is n:
            continue                       # the name is being *called*
        if isinstance(parent, ast.Call) \
                and dotted(parent.func) in ("len", "isinstance", "type",
                                            "repr", "str"):
            continue
        if isinstance(parent, ast.Compare) and all(
                isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops):
            continue                       # `x is None` identity checks
        yield n


# ---------------------------------------------------------------------------
# tracer hygiene


@rule("SLT101", "error", "Python control flow on a traced value")
def _tracer_branch(ctx):
    """`if`/`while`/ternary on a jitted core's traced parameter forces a
    concrete bool from a tracer — TracerBoolConversionError at trace time,
    or silent trace-time specialization.  Use `lax.cond`/`lax.select`, or
    mark the argument static."""
    for core in ctx.cores:
        for node in ast.walk(core.fn):
            tests = []
            if isinstance(node, (ast.If, ast.While, ast.IfExp)):
                tests.append(node.test)
            elif isinstance(node, ast.Assert):
                tests.append(node.test)
            for test in tests:
                for use in _traced_param_uses(core, test, ctx):
                    yield ctx.finding(
                        "SLT101", use,
                        f"Python control flow on traced value "
                        f"{use.id!r} inside traced core "
                        f"{core.fn.name!r} ({core.how})",
                        suggestion="use lax.cond/lax.select, or declare the "
                                   "argument in static_argnames")
                    break                  # one finding per test expression


@rule("SLT102", "error", "host materialization of a traced value")
def _host_materialize(ctx):
    """`float()`/`int()`/`bool()`/`.item()`/`.tolist()` on a traced value
    inside a jitted core forces a device sync + concretization — trace-time
    error under jit, silent host round-trip under eager fallback."""
    for core in ctx.cores:
        traced = set(_param_names(core.fn)) - core.static
        for node in ast.walk(core.fn):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            hit = None
            if fname in ("float", "int", "bool", "complex"):
                for a in node.args:
                    if isinstance(a, ast.Name) and a.id in traced:
                        hit = f"{fname}({a.id})"
                        break
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("item", "tolist"):
                names = {n.id for n in ast.walk(node.func.value)
                         if isinstance(n, ast.Name)}
                if names & traced:
                    hit = f".{node.func.attr}() on " \
                          f"{sorted(names & traced)[0]!r}"
            if hit:
                yield ctx.finding(
                    "SLT102", node,
                    f"host materialization {hit} of a traced value inside "
                    f"traced core {core.fn.name!r}",
                    suggestion="keep the value on device (jnp ops), or hoist "
                               "the concretization out of the jitted core")


@rule("SLT103", "error", "numpy call on a traced value in a jitted core")
def _numpy_in_core(ctx):
    """`np.*` calls on traced values inside a jitted core concretize the
    tracer (TracerArrayConversionError) or silently compute on host at trace
    time.  Use the `jnp` equivalent."""
    for core in ctx.cores:
        traced = set(_param_names(core.fn)) - core.static
        for node in ast.walk(core.fn):
            if not isinstance(node, ast.Call):
                continue
            fname = dotted(node.func)
            if not (fname.startswith("np.") or fname.startswith("numpy.")):
                continue
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(a, ast.Name) and a.id in traced:
                    yield ctx.finding(
                        "SLT103", node,
                        f"numpy call {fname}() on traced value {a.id!r} "
                        f"inside traced core {core.fn.name!r}",
                        suggestion=f"use jnp.{fname.split('.', 1)[1]} (or "
                                   "hoist the numpy work out of the core)")
                    break


# ---------------------------------------------------------------------------
# recompilation hazards


@rule("SLT201", "warning", "jit constructed inside a loop")
def _jit_in_loop(ctx):
    """`jax.jit(...)` inside a `for`/`while` body builds a fresh wrapper per
    iteration; cache hits still pay wrapper setup, and closure-captured
    values defeat the cache entirely.  Hoist the jit (or memoize the
    builder, as the package's `lru_cache`d program builders do)."""
    seen = set()                  # nested loops reach the same Call twice
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.For, ast.While, ast.AsyncFor)):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and _is_wrapper_name(dotted(sub.func), ("jit",)) \
                    and id(sub) not in seen:
                seen.add(id(sub))
                yield ctx.finding(
                    "SLT201", sub,
                    "jax.jit constructed inside a loop body "
                    "(per-iteration wrapper; recompilation hazard when "
                    "closures differ)",
                    suggestion="hoist the jit out of the loop or memoize "
                               "the builder with functools.lru_cache")


@rule("SLT202", "error", "unhashable default for a static argument")
def _unhashable_static(ctx):
    """A parameter named in `static_argnames`/`static_argnums` whose default
    is a list/dict/set literal raises `TypeError: unhashable type` on the
    first defaulted call — and a hashable-but-mutable stand-in recompiles on
    every new object.  Static args must be hashable values with stable
    equality (the package's Options carries `cache_key()` for this)."""
    for core in ctx.cores:
        if not core.static:
            continue
        a = core.fn.args
        params = a.posonlyargs + a.args
        defaults = [None] * (len(params) - len(a.defaults)) + list(a.defaults)
        pairs = list(zip(params, defaults)) + \
            list(zip(a.kwonlyargs, a.kw_defaults))
        for p, d in pairs:
            if p.arg in core.static and isinstance(
                    d, (ast.List, ast.Dict, ast.Set)):
                yield ctx.finding(
                    "SLT202", d,
                    f"static argument {p.arg!r} of traced core "
                    f"{core.fn.name!r} defaults to an unhashable "
                    f"{type(d).__name__.lower()} literal",
                    suggestion="use a tuple/frozenset/None default, or drop "
                               "the argument from static_argnames")


@rule("SLT203", "warning", "Options used as a cache key without cache_key()")
def _options_key(ctx):
    """On serve paths, an `Options` instance folded into an executable-cache
    key without `.cache_key()` keys the cache on object identity — every
    request misses and recompiles.  `serve/cache.py` documents the canonical
    key shape."""
    if not ctx.relpath.startswith("slate_tpu/serve/"):
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted(node.func)
        if fname not in ("Options", "Options.make"):
            continue
        parent = ctx.parent(node)
        if isinstance(parent, ast.Attribute) and parent.attr == "cache_key":
            continue
        if isinstance(parent, (ast.Tuple, ast.Dict, ast.Subscript)):
            yield ctx.finding(
                "SLT203", node,
                f"{fname}(...) folded into a key structure without "
                ".cache_key() — identity-keyed cache, every request misses",
                suggestion="call .cache_key() on the Options before keying")


# ---------------------------------------------------------------------------
# x64 + debug hygiene

#: files allowed to flip process-global x64 (the tester entrypoint owns the
#: process; everything else must use the scoped jax.enable_x64)
X64_ALLOWED = ("slate_tpu/testing/__main__.py",)


@rule("SLT301", "error", "process-global x64 toggle outside the entrypoint")
def _global_x64(ctx):
    """`jax.config.update("jax_enable_x64", ...)` flips precision for the
    whole process and leaks across sweep rows and library callers.  Use the
    scoped `jax.enable_x64(True)` context (testing/routines.py's
    gesv_mixed shows the pattern); only the tester entrypoint may set the
    global."""
    if ctx.relpath in X64_ALLOWED:
        return
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if not dotted(node.func).endswith("config.update"):
            continue
        if node.args and isinstance(node.args[0], ast.Constant) \
                and node.args[0].value == "jax_enable_x64":
            yield ctx.finding(
                "SLT301", node,
                "process-global jax_enable_x64 toggle outside the tester "
                "entrypoint (leaks x64 across sweep rows and callers)",
                suggestion="wrap the region in "
                           "`with jax.enable_x64(True):`")


@rule("SLT302", "warning", "leftover debug hook")
def _debug_left(ctx):
    """`jax.debug.print`/`jax.debug.breakpoint`/`pdb.set_trace`/
    `breakpoint()` left in library code: debug prints serialize the program
    at every call site and breakpoints hang non-interactive runs (CI,
    serving)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        fname = dotted(node.func)
        if fname in ("jax.debug.print", "jax.debug.breakpoint",
                     "pdb.set_trace", "breakpoint") \
                or fname.endswith(".debug.print") \
                or fname.endswith(".debug.breakpoint"):
            yield ctx.finding(
                "SLT302", node,
                f"leftover debug hook {fname}()",
                suggestion="remove it (or route through utils/debug.py, "
                           "which gates on an env switch)")


# ---------------------------------------------------------------------------
# donation


@rule("SLT401", "error", "donated argument is also static")
def _donate_static_overlap(ctx):
    """An argument index in both `donate_argnums` and `static_argnums`:
    static args are hashed into the cache key, not passed as buffers, so
    XLA rejects the donation (or silently ignores it) — the overlap is
    always a mistake."""
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and (
                _is_wrapper_name(dotted(node.func), ("jit",))
                or _partial_jit_target(node) is not None)):
            continue
        call = node
        donate = static = None
        for kw in call.keywords:
            if kw.arg == "donate_argnums":
                donate = _literal_int_tuple(kw.value)
            elif kw.arg == "static_argnums":
                static = _literal_int_tuple(kw.value)
        if donate and static:
            overlap = sorted(set(donate) & set(static))
            if overlap:
                yield ctx.finding(
                    "SLT401", call,
                    f"argument index(es) {overlap} appear in both "
                    "donate_argnums and static_argnums",
                    suggestion="drop the index from one of the two lists")


# ---------------------------------------------------------------------------
# exception taxonomy


@rule("SLT501", "error", "broad except can swallow the NumericalError taxonomy")
def _broad_except(ctx):
    """`except Exception:` / bare `except:` without a re-raise swallows
    `NumericalError`/`SingularMatrixError`/`ConvergenceError`, turning a
    diagnosable numerical failure into silent fallback behavior.  Narrow the
    handler, re-raise the taxonomy first, or mark the swallow intentional
    with `# slate-lint: disable=SLT501 -- reason`."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        broad = node.type is None
        if isinstance(node.type, ast.Name) \
                and node.type.id in ("Exception", "BaseException"):
            broad = True
        if isinstance(node.type, ast.Tuple) and any(
                isinstance(e, ast.Name)
                and e.id in ("Exception", "BaseException")
                for e in node.type.elts):
            broad = True
        if not broad:
            continue
        if any(isinstance(sub, ast.Raise) for sub in ast.walk(node)):
            continue                       # handler re-raises — not a swallow
        yield ctx.finding(
            "SLT501", node,
            "broad except without re-raise can swallow "
            "NumericalError/SingularMatrixError/ConvergenceError",
            suggestion="narrow the exception type, add `except "
                       "NumericalError: raise` above it, or suppress with "
                       "`# slate-lint: disable=SLT501 -- reason`")


# ---------------------------------------------------------------------------
# observability coverage

#: module-level function suffixes that mark a public distributed driver
#: (mirrors tests/test_obs.py's runtime meta-test, statically)
_DRIVER_SUFFIXES = ("_distributed", "_pipelined", "_sharded")


@rule("SLT601", "warning", "public distributed driver missing @obs.instrument")
def _missing_instrument(ctx):
    """Every public driver in `slate_tpu/parallel` wears `@instrument` so
    SCALING.md and metrics.json coverage stay complete (the PR-3 runtime
    meta-test, enforced statically with an autofix suggestion)."""
    if not ctx.relpath.startswith("slate_tpu/parallel/"):
        return
    for node in ctx.tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name.startswith("_") \
                or not node.name.endswith(_DRIVER_SUFFIXES):
            continue
        has = False
        for dec in node.decorator_list:
            base = dec.func if isinstance(dec, ast.Call) else dec
            if dotted(base).rsplit(".", 1)[-1] == "instrument":
                has = True
        if not has:
            yield ctx.finding(
                "SLT601", node,
                f"public distributed driver {node.name!r} is not "
                "@instrument-ed (invisible to spans/SCALING coverage)",
                suggestion="add `@instrument` (from ..obs import instrument) "
                           "above the def")


def rule_table() -> List[Tuple[str, str, str]]:
    """(id, severity, title) rows, sorted — the README/--rules table."""
    return [(r.id, r.severity, r.title)
            for r in sorted(RULES.values(), key=lambda r: r.id)]
