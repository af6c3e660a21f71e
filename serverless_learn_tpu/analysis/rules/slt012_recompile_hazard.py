"""SLT012: compile-key cardinality hazards (the warm_shapes() discipline).

Every distinct (shape, dtype, static-arg) key a jitted function sees is
a fresh XLA compile — seconds of wall clock in the middle of a decode
step. The repo's answer is *deterministic bucketing*: call-site shapes
are quantized by declared bucket functions (``_bucket``, ``_wbucket``)
and ``warm_shapes()`` pre-compiles the closed set, so steady state
compiles exactly zero times. This rule machine-checks the discipline
project-wide (SCOPE="project": bucket declarations live in one module,
call sites in another):

* **traced-value branch** (error): ``if``/``while``/ternary/``range()``
  over a NON-static parameter inside a jit body — either a tracer leak
  (``TracerBoolConversionError``) or, with ``static_argnums``, a
  compile-key fork per distinct value. Tests on closures/``self`` state
  are fine (fixed at trace time).
* **unhashable static** (error): a list/dict/set literal passed at a
  declared ``static_argnums`` position — ``TypeError: unhashable`` at
  the first call.
* **jit-in-loop** (warning): ``jax.jit(...)`` created lexically inside
  a ``for``/``while`` body without being memoized into a subscript
  (``cache[key] = jax.jit(...)``) — a fresh jit object per iteration
  never hits the compile cache.
* **unbucketed shape key** (error): a call to a *bucketed jit factory*
  (a function that memoizes/returns ``jax.jit`` objects keyed by its
  int params, e.g. ``_paged_chunk_jit(nb, W)``) whose argument resolves to a
  raw ``len(...)``/arithmetic chain with NO bucket-function call in it
  — unbounded compile-key cardinality. Bucket functions are declared
  with ``@jitcheck.bucket`` (see ``analysis/jitcheck.py``); ``min``/
  ``max`` clamps over a bucketed value stay bucketed. Unresolvable
  chains (params, attributes) never findings.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from serverless_learn_tpu.analysis.engine import Finding, Project
from serverless_learn_tpu.analysis.rules import jitutil

RULE_ID = "SLT012"
TITLE = "recompile hazards and compile-key cardinality"
SCOPE = "project"


# -- bucket declarations (project-wide) ----------------------------------


def _is_bucket_decorator(dec: ast.AST) -> bool:
    """@jitcheck.bucket / @bucket / @jit_bucket (call or bare)."""
    if isinstance(dec, ast.Call):
        dec = dec.func
    recv, attr = jitutil.call_parts(dec) if isinstance(
        dec, (ast.Attribute, ast.Name)) else (None, None)
    if attr == "bucket" and recv is not None \
            and recv.split(".")[-1] == "jitcheck":
        return True
    return recv is None and attr in ("bucket", "jit_bucket")


def _declared_buckets(proj: Project) -> Set[str]:
    out: Set[str] = set()
    for sf in proj.files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if any(_is_bucket_decorator(d)
                       for d in node.decorator_list):
                    out.add(node.name)
    return out


# -- check 1: traced-value branches --------------------------------------


def _names_in(node: ast.AST) -> Set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _is_none_test(node: ast.AST) -> bool:
    """``x is None`` / ``x is not None`` (possibly under not/and/or):
    a pytree STRUCTURE test, resolved correctly at trace time — None is
    part of the compile key by structure, not a traced value."""
    if isinstance(node, ast.Compare):
        return (all(isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops)
                and all(isinstance(c, ast.Constant) and c.value is None
                        for c in node.comparators))
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
        return _is_none_test(node.operand)
    if isinstance(node, ast.BoolOp):
        return all(_is_none_test(v) for v in node.values)
    return False


def _check_traced_branches(sf, findings: List[Finding]):
    for jf in jitutil.jitted_functions(sf.tree):
        if jf.info.partial_knowledge:
            continue  # static set unknown: never guess
        params = set(jf.param_names())
        traced = params - jf.static_params()
        for node in jitutil.body_walk(jf.node):
            test = None
            kind = None
            if isinstance(node, (ast.If, ast.While)):
                test, kind = node.test, "branches"
            elif isinstance(node, ast.IfExp):
                test, kind = node.test, "branches"
            elif isinstance(node, ast.For):
                it = node.iter
                if isinstance(it, ast.Call):
                    recv, attr = jitutil.call_parts(it.func)
                    if recv is None and attr == "range":
                        test, kind = it, "loops a range"
            if test is None or (kind == "branches"
                                and _is_none_test(test)):
                continue
            hot = _names_in(test) & traced
            if not hot:
                continue
            names = ", ".join(sorted(hot))
            findings.append(Finding(
                RULE_ID, sf.path, node.lineno,
                f"jitted {jf.name} {kind} on traced parameter(s) "
                f"{names}: a tracer here raises at trace time, and "
                f"marking it static forks the compile key per distinct "
                f"value — use lax.cond/lax.select or hoist the branch "
                f"out of the jit"))


# -- check 2: unhashable static args -------------------------------------


def _jit_bindings(tree: ast.AST) -> Dict[str, jitutil.JitInfo]:
    """name -> JitInfo for jits with declared static positions."""
    out: Dict[str, jitutil.JitInfo] = {}

    def bind(name: Optional[str], info: jitutil.JitInfo):
        if name and (info.static_argnums or info.static_argnames):
            out[name.rsplit(".", 1)[-1]] = info

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if jitutil.is_jit_call(dec):
                    bind(node.name, jit_info := jitutil.jit_info(dec))
        if (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)
                and jitutil.is_jit_call(node.value)):
            info = jitutil.jit_info(node.value)
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    bind(tgt.id, info)
                elif isinstance(tgt, ast.Attribute):
                    bind(tgt.attr, info)
    return out


def _check_unhashable_static(sf, findings: List[Finding]):
    bindings = _jit_bindings(sf.tree)
    if not bindings:
        return
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        recv, attr = jitutil.call_parts(node.func)
        info = bindings.get(attr or "")
        if info is None or info.partial_knowledge:
            continue
        for i in info.static_argnums:
            if i < len(node.args) and isinstance(
                    node.args[i], (ast.List, ast.Dict, ast.Set)):
                lit = type(node.args[i]).__name__.lower()
                findings.append(Finding(
                    RULE_ID, sf.path, node.lineno,
                    f"{lit} literal passed at static_argnums position "
                    f"{i} of {attr}(): static args must be hashable — "
                    f"this raises TypeError at the first call; pass a "
                    f"tuple"))
        for kw in node.keywords:
            if kw.arg in info.static_argnames and isinstance(
                    kw.value, (ast.List, ast.Dict, ast.Set)):
                lit = type(kw.value).__name__.lower()
                findings.append(Finding(
                    RULE_ID, sf.path, node.lineno,
                    f"{lit} literal passed as static arg "
                    f"{kw.arg!r} of {attr}(): static args must be "
                    f"hashable — this raises TypeError at the first "
                    f"call; pass a tuple"))


# -- check 3: jit created inside a loop ----------------------------------


def _check_jit_in_loop(sf, findings: List[Finding]):
    for node in ast.walk(sf.tree):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        for sub in node.body:
            for inner in ast.walk(sub):
                if not (isinstance(inner, ast.Call)
                        and jitutil.is_jit_call(inner)):
                    continue
                recv, attr = jitutil.call_parts(inner.func)
                if attr == "partial":
                    continue
                # memoized into a subscript (cache[key] = jax.jit(...))
                # anywhere in the same loop statement tree is fine
                memoized = any(
                    isinstance(s, ast.Assign)
                    and s.value is inner
                    and any(isinstance(t, ast.Subscript)
                            for t in s.targets)
                    for s in ast.walk(node))
                if memoized:
                    continue
                findings.append(Finding(
                    RULE_ID, sf.path, inner.lineno,
                    "jax.jit created inside a loop body without "
                    "memoization: each iteration builds a fresh jit "
                    "object that never shares the compile cache — "
                    "hoist the jit or store it in a keyed dict",
                    severity="warning"))


# -- check 4: unbucketed shape keys into jit factories -------------------


def _jit_factories(tree: ast.AST) -> Dict[str, List[str]]:
    """name -> int-ish param names, for functions that memoize or
    return a jax.jit keyed by their parameters (the
    `_paged_chunk_jit(nb, W)` shape-factory idiom)."""
    out: Dict[str, List[str]] = {}
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        has_jit = any(isinstance(sub, ast.Call)
                      and jitutil.is_jit_call(sub)
                      for sub in ast.walk(node))
        if not has_jit:
            continue
        params = [a.arg for a in node.args.args if a.arg != "self"]
        if not params:
            continue
        # names derived from params (key = (nb, pb) one-hop closure)
        derived = set(params)
        for _ in range(2):
            for sub in ast.walk(node):
                if isinstance(sub, ast.Assign) \
                        and (_names_in(sub.value) & derived):
                    for tgt in sub.targets:
                        if isinstance(tgt, ast.Name):
                            derived.add(tgt.id)
        # keyed: a param-derived name flows into a subscript key
        keyed = any(isinstance(sub, ast.Subscript)
                    and (_names_in(sub.slice) & derived)
                    for sub in ast.walk(node))
        returns_jit = any(isinstance(sub, ast.Return)
                          and sub.value is not None
                          for sub in ast.walk(node))
        if keyed and returns_jit:
            out[node.name] = params
    return out


def _resolve_chain(fn: ast.AST, name: str,
                   depth: int = 4) -> Optional[ast.AST]:
    """Last single assignment to `name` in fn (linear approximation)."""
    found = None
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id == name:
            found = node.value
    return found


def _chain_verdict(fn: ast.AST, expr: ast.AST, buckets: Set[str],
                   depth: int = 4) -> str:
    """'bucketed' | 'raw' | 'unknown' for one factory argument."""
    if expr is None or depth <= 0:
        return "unknown"
    calls = [n for n in ast.walk(expr) if isinstance(n, ast.Call)]
    for call in calls:
        recv, attr = jitutil.call_parts(call.func)
        if attr in buckets:
            return "bucketed"
    if isinstance(expr, ast.Constant):
        return "bucketed"  # literal key: closed cardinality
    has_len = any(jitutil.call_parts(c.func)[1] == "len" for c in calls)
    # follow one name hop: W = min(_wbucket(...), cap) via temp names
    names = [n for n in ast.walk(expr) if isinstance(n, ast.Name)
             and isinstance(n.ctx, ast.Load)]
    sub_verdicts = []
    for n in names:
        prev = _resolve_chain(fn, n.id)
        if prev is not None and prev is not expr:
            sub_verdicts.append(
                _chain_verdict(fn, prev, buckets, depth - 1))
    if "bucketed" in sub_verdicts:
        return "bucketed"
    if has_len:
        return "raw"
    if "raw" in sub_verdicts:
        return "raw"
    return "unknown"


def _check_unbucketed(sf, buckets: Set[str], findings: List[Finding]):
    factories = _jit_factories(sf.tree)
    if not factories:
        return
    if not buckets:
        # no declared bucket fns anywhere: the discipline is absent,
        # not violated at one call site — stay quiet.
        return
    for fn in ast.walk(sf.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if fn.name in factories:
            continue  # the factory's own internals
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            recv, attr = jitutil.call_parts(node.func)
            params = factories.get(attr or "")
            if params is None:
                continue
            for i, arg in enumerate(node.args):
                verdict = _chain_verdict(fn, arg, buckets)
                if verdict == "raw":
                    pname = params[i] if i < len(params) else f"#{i}"
                    findings.append(Finding(
                        RULE_ID, sf.path, node.lineno,
                        f"{attr}() shape key {pname} derives from a "
                        f"raw len()/size chain with no declared bucket "
                        f"function (@jitcheck.bucket) in it: every "
                        f"distinct value is a fresh XLA compile — "
                        f"quantize with _bucket/_wbucket so "
                        f"warm_shapes() can close the key set"))


def run(proj: Project) -> List[Finding]:
    findings: List[Finding] = []
    buckets = _declared_buckets(proj)
    for sf in proj.files:
        if sf.tree is None:
            continue
        _check_traced_branches(sf, findings)
        _check_unhashable_static(sf, findings)
        _check_jit_in_loop(sf, findings)
        _check_unbucketed(sf, buckets, findings)
    return findings
