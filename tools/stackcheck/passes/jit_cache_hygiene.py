"""jit-cache-hygiene: jit wrappers that cannot reuse their trace cache.

``jax.jit`` (and ``pjit``/``shard_map``) attach the compilation cache to
the *wrapper object*. Construct the wrapper once — at module scope, in
``__init__``, under ``functools.cached_property``, or in a self-cached
memo — and every later call with a seen signature reuses the trace. Build
a fresh wrapper inside a method body and every call pays a full retrace
(+XLA compile): the PR 13 ``model_runner.export_blocks/import_blocks``
bug, where a per-call ``jax.jit(_gather)`` made KV tiering 3.6× *slower*
than recompute (~60 ms recompile per demotion) until the wrappers were
cached on ``self``. The ``vllm:unexpected_recompiles_total`` gauge
catches this class at runtime; this pass catches it at review time.

Four rules:

1. *Body-local wrapper construction* — a ``jax.jit``/``pjit``/
   ``shard_map`` call (or a ``@jax.jit``-decorated nested def) inside a
   function or method body, where the result is not cached: not assigned
   to a ``self`` attribute, not stored into a self-rooted memo dict/list,
   and the enclosing function is not ``__init__``/``__post_init__`` or a
   ``cached_property``/``lru_cache``. One-shot startup constructions are
   legitimate — suppress them with a rationale.
2. *Unhashable static value at a call site* — passing a list/dict/set
   literal in a ``static_argnums``/``static_argnames`` position of a
   known wrapper raises (or silently retraces) at dispatch.
3. *Per-call-varying static value* — a static-position argument computed
   from ``.shape`` / ``len(...)`` in the call expression retraces once
   per distinct value; bucket it (pad to a fixed set of sizes) first.
4. *Shape-dependent branching feeding a jitted call* — an ``if`` on
   ``.shape``/``len()`` of a parameter whose branch calls a known
   wrapper with an unbucketed dynamic slice of that parameter
   (``fn(x[:n])``): one compile signature per length.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from tools.stackcheck.core import Context, Finding, register
from tools.stackcheck.passes._astutil import (
    call_name,
    dotted,
    expr_calls,
    statements,
)

PASS = "jit-cache-hygiene"

_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit", "shard_map",
              "jax.shard_map", "jax.experimental.shard_map.shard_map"}
# constructors here run once per *instance* (or per cache key), not per
# call — construction inside them is the cached form, not the bug
_EXEMPT_FNS = {"__init__", "__post_init__"}
_CACHED_DECOS = {"functools.cached_property", "cached_property",
                 "functools.lru_cache", "lru_cache",
                 "functools.cache", "cache", "property.setter"}
# self.<container>.append(jax.jit(...)) and friends: caching via
# container mutation (a list of per-part step programs)
_CACHE_MUTATORS = {"append", "add", "insert", "setdefault", "update",
                   "extend"}


def _ctor_kind(call: ast.Call) -> Optional[str]:
    """'jax.jit' / 'shard_map' / ... if this Call constructs a wrapper."""
    name = call_name(call) or ""
    if name in _JIT_NAMES:
        return name
    if name in ("functools.partial", "partial") and call.args:
        inner = dotted(call.args[0]) or ""
        if inner in _JIT_NAMES:
            return inner
    return None


def _deco_ctor(deco: ast.AST) -> Optional[str]:
    """Wrapper kind when a decorator expression constructs one."""
    if isinstance(deco, ast.Call):
        return _ctor_kind(deco)
    name = dotted(deco) or ""
    return name if name in _JIT_NAMES else None


def _has_cached_deco(fn: ast.AST) -> bool:
    for deco in fn.decorator_list:
        name = dotted(deco if not isinstance(deco, ast.Call) else deco.func)
        if name in _CACHED_DECOS:
            return True
    return False


def _is_self_rooted(node: ast.AST, self_locals: Set[str]) -> bool:
    """Does this expression read through ``self`` (or a local bound from
    self / getattr(self, ...))?"""
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id == "self" or node.id in self_locals
    if isinstance(node, ast.Call):
        name = call_name(node) or ""
        if name == "getattr" and node.args:
            return _is_self_rooted(node.args[0], self_locals)
    return False


def _caching_target(stmt: ast.stmt, self_locals: Set[str]) -> bool:
    """Does this statement store its value somewhere instance-cached?"""
    if isinstance(stmt, ast.Assign):
        targets = stmt.targets
    elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
        targets = [stmt.target]
    elif isinstance(stmt, ast.Expr) and isinstance(stmt.value, ast.Call):
        func = stmt.value.func
        if (isinstance(func, ast.Attribute)
                and func.attr in _CACHE_MUTATORS
                and _is_self_rooted(func.value, self_locals)):
            return True
        return False
    else:
        return False
    for t in targets:
        if isinstance(t, ast.Attribute) and _is_self_rooted(t, self_locals):
            return True
        if isinstance(t, ast.Subscript) and _is_self_rooted(t, self_locals):
            return True
    return False


def _body_local_ctors(fn: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, kind) for every un-cached wrapper construction in this
    function's body."""
    out: List[Tuple[int, str]] = []
    self_locals: Set[str] = set()
    for stmt in statements(fn.body):
        # track locals bound from self-rooted values (memo dicts fetched
        # via ``cache = getattr(self, "_c", None)`` / ``cache = self._c``)
        if isinstance(stmt, ast.Assign) and _is_self_rooted(
                stmt.value, self_locals):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    self_locals.add(t.id)
        caches = _caching_target(stmt, self_locals)
        ctors: List[Tuple[int, str]] = []
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for deco in stmt.decorator_list:
                kind = _deco_ctor(deco)
                if kind is not None:
                    ctors.append((stmt.lineno, kind))
        for call in expr_calls(stmt):
            kind = _ctor_kind(call)
            if kind is not None:
                ctors.append((call.lineno, kind))
        if not caches:
            out.extend(ctors)
    return out


# -- known-wrapper registry (for the static-arg call-site rules) ------------

def _static_spec(jit_call: ast.Call) -> Tuple[Set[int], Set[str]]:
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in jit_call.keywords:
        if kw.arg == "static_argnums":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value,
                                                               int):
                    nums.add(el.value)
        elif kw.arg == "static_argnames":
            for el in ast.walk(kw.value):
                if isinstance(el, ast.Constant) and isinstance(el.value,
                                                               str):
                    names.add(el.value)
    return nums, names


def _wrapper_registry(tree: ast.AST) -> Dict[str, Tuple[Set[int], Set[str]]]:
    """dotted call-site name -> (static positions, static names) for
    wrappers whose construction is visible in this module: module-level
    ``name = jax.jit(f, ...)`` and ``self.attr = jax.jit(f, ...)``."""
    reg: Dict[str, Tuple[Set[int], Set[str]]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        call = node.value
        if not (isinstance(call, ast.Call) and _ctor_kind(call)):
            continue
        spec = _static_spec(call)
        if not (spec[0] or spec[1]):
            continue
        for t in node.targets:
            name = dotted(t)
            if name is not None:
                reg[name] = spec
    return reg


def _shape_derived(expr: ast.AST) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr == "shape":
            return True
        if isinstance(node, ast.Call) and (call_name(node) or "") == "len":
            return True
    return False


def _static_callsite_issues(tree: ast.AST) -> List[Tuple[int, str]]:
    reg = _wrapper_registry(tree)
    if not reg:
        return []
    out: List[Tuple[int, str]] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = call_name(node)
        if name not in reg:
            continue
        nums, names = reg[name]
        static_args: List[Tuple[ast.AST, str]] = []
        for i in nums:
            if i < len(node.args):
                static_args.append((node.args[i], f"position {i}"))
        for kw in node.keywords:
            if kw.arg in names:
                static_args.append((kw.value, f"{kw.arg!r}"))
        for arg, where in static_args:
            if isinstance(arg, (ast.List, ast.Dict, ast.Set)):
                out.append((node.lineno,
                            f"call to jitted {name} passes an unhashable "
                            f"{type(arg).__name__.lower()} literal in "
                            f"static arg {where} — jit hashes static "
                            f"args at dispatch; pass a tuple"))
            elif _shape_derived(arg):
                out.append((node.lineno,
                            f"call to jitted {name} passes a "
                            f"shape/len-derived value in static arg "
                            f"{where}: one retrace per distinct value — "
                            f"bucket it (pad to fixed sizes) or make it "
                            f"a traced argument"))
    return out


def _param_names(fn: ast.AST) -> Set[str]:
    args = fn.args
    return {a.arg for a in args.posonlyargs + args.args + args.kwonlyargs}


def _dynamic_slice_of(call: ast.Call, params: Set[str]) -> bool:
    for arg in call.args:
        if (isinstance(arg, ast.Subscript)
                and isinstance(arg.value, ast.Name)
                and arg.value.id in params
                and not isinstance(arg.slice, ast.Constant)):
            return True
    return False


def _shape_branch_issues(fn: ast.AST, reg: Dict) -> List[Tuple[int, str]]:
    if not reg:
        return []
    params = _param_names(fn)
    out: List[Tuple[int, str]] = []
    for stmt in statements(fn.body):
        if not isinstance(stmt, ast.If) or not _shape_derived(stmt.test):
            continue
        for sub in list(statements(stmt.body)) + list(
                statements(stmt.orelse)):
            for call in expr_calls(sub):
                name = call_name(call)
                if name in reg and _dynamic_slice_of(call, params):
                    out.append((
                        call.lineno,
                        f"shape-dependent branch feeds jitted {name} a "
                        f"dynamically-sliced operand: one compile "
                        f"signature per length — pad to bucketed shapes "
                        f"before dispatch"))
    return out


@register(PASS, "per-call jit/shard_map wrapper construction, unhashable "
                "or shape-varying static args, shape-branched dispatch")
def run(ctx: Context) -> List[Finding]:
    out: List[Finding] = []
    for path in ctx.py_files("production_stack_tpu"):
        tree = ctx.parse(path)
        if tree is None:
            continue
        rel = ctx.rel(path)
        reg = _wrapper_registry(tree)
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if node.name in _EXEMPT_FNS or _has_cached_deco(node):
                continue
            for lineno, kind in _body_local_ctors(node):
                out.append(Finding(
                    PASS, rel, lineno,
                    f"fresh {kind} wrapper constructed in {node.name}() "
                    f"body: a new wrapper has an empty trace cache, so "
                    f"every call recompiles (the PR 13 export/import "
                    f"bug class) — hoist to module scope, __init__, "
                    f"cached_property, or a self-cached memo"))
            out.extend(Finding(PASS, rel, lineno, msg)
                       for lineno, msg in _shape_branch_issues(node, reg))
        out.extend(Finding(PASS, rel, lineno, msg)
                   for lineno, msg in _static_callsite_issues(tree))
    return out
