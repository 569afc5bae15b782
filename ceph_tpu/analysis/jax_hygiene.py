"""Rule family ``jax-hygiene``: tracer/host-sync discipline in jitted code.

PR 2's planar rewrite showed the costliest bugs here are structural:
a hidden host sync inside a device loop silently serializes dispatch
(and puts a host round-trip inside what the slope harness times — see
BENCH_NOTES.md), and Python control flow on a tracer either fails at
trace time or bakes one branch in forever.  No runtime assertion
catches these until a bench regresses; this pass finds them in the AST.

What counts as "traced code": functions decorated ``@jax.jit`` /
``@partial(jax.jit, ...)``, functions/lambdas wrapped ``jax.jit(f)``,
bodies handed to ``jax.lax.scan``, and the step/feedback callables
handed to the bench device-loop harness (``device_loop_slope`` /
``_bench_device_loop``) — the measured region of the timing contract.

Checks inside traced code:
- host materialization of a traced parameter: ``np.asarray``/``np.array``
  /``float``/``int``/``bool`` applied to a non-static parameter
  (static_argnums-named params are host values and exempt);
- ``.block_until_ready()`` / ``.item()`` anywhere;
- ``time.*`` wall-clock calls (they run at TRACE time, not step time);
- Python ``if``/``while`` branching on a bare non-static parameter
  (``.shape``/``.ndim``/``.dtype``/``len()``/``isinstance``/``is None``
  uses are static and exempt).

Module scope: any ``jnp.*(...)`` call in a top-level statement traces
and compiles at import — flagged (host-side ``np`` tables are fine).

Resolution is by direct parameter reference (no dataflow), following the
deviant-behavior school: high-precision, low-noise checks that hold as
a zero-findings tier-1 gate.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Sequence, Set, Tuple

from ceph_tpu.analysis.astutil import dotted, names_in, param_names, \
    walk_functions
from ceph_tpu.analysis.engine import Finding, LintContext

RULE = "jax-hygiene"

_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"functools.partial", "partial"}
_SCAN_NAMES = {"jax.lax.scan", "lax.scan"}
_DEVICE_LOOP_NAMES = {"device_loop_slope", "_bench_device_loop"}
_HOST_COERCE = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                "float", "int", "bool"}
_TIME_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
               "time.sleep", "time.process_time", "datetime.datetime.now"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}


def _static_argnums(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(static positions, static param names) from a jit/partial call —
    both keywords honored, int and str constants respectively."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg not in ("static_argnums", "static_argnames"):
            continue
        vals = list(kw.value.elts) \
            if isinstance(kw.value, (ast.Tuple, ast.List)) else [kw.value]
        for v in vals:
            if isinstance(v, ast.Constant):
                if isinstance(v.value, int):
                    nums.add(v.value)
                elif isinstance(v.value, str):
                    names.add(v.value)
    return nums, names


def _jit_decorator(fn) -> Optional[Tuple[Set[int], Set[str]]]:
    """(static_argnums, static_argnames) if ``fn`` is decorated jitted,
    else None."""
    for dec in fn.decorator_list:
        d = dotted(dec)
        if d in _JIT_NAMES:
            return set(), set()
        if isinstance(dec, ast.Call):
            dc = dotted(dec.func)
            if dc in _JIT_NAMES:
                return _static_argnums(dec)
            if dc in _PARTIAL_NAMES and dec.args \
                    and dotted(dec.args[0]) in _JIT_NAMES:
                return _static_argnums(dec)
    return None


def _collect_traced(module) -> List[Tuple[str, ast.AST, Set[str]]]:
    """(symbol, fn_node, static_param_names) for every traced function/
    lambda in the module."""
    # keep duplicates: bench_ec defines `step` once per workload branch,
    # and a dict keyed by qualified name would silently drop all but one
    fns = list(walk_functions(module.tree))
    by_name: dict = {}
    for sym, fn in fns:
        by_name.setdefault(fn.name, []).append((sym, fn))

    traced: dict = {}
    _NO_STATICS = (set(), set())

    def add(sym, fn, statics):
        if fn in traced:
            return
        nums, names = statics
        params = param_names(fn)
        static_names = {params[i] for i in nums if i < len(params)}
        static_names |= names & set(params)
        traced[fn] = (sym, static_names)

    for sym, fn in fns:
        statics = _jit_decorator(fn)
        if statics is not None:
            add(sym, fn, statics)

    def mark_by_ref(node: ast.AST, owner_sym: str, statics):
        if isinstance(node, ast.Lambda):
            add(f"{owner_sym}.<lambda>" if owner_sym else "<lambda>",
                node, statics)
        elif isinstance(node, ast.Name):
            for s, f in by_name.get(node.id, []):
                add(s, f, statics)

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        cn = dotted(node.func)
        sym = ""
        if cn in _JIT_NAMES and node.args:
            mark_by_ref(node.args[0], sym, _static_argnums(node))
        elif cn in _SCAN_NAMES and node.args:
            mark_by_ref(node.args[0], sym, _NO_STATICS)
        elif cn is not None and cn.split(".")[-1] in _DEVICE_LOOP_NAMES:
            for arg in node.args[:2]:
                mark_by_ref(arg, sym, _NO_STATICS)
    return [(sym, fn, statics) for fn, (sym, statics) in traced.items()]


def _bare_tracer_refs(test: ast.AST, tracers: Set[str]) -> Set[str]:
    """Non-static param names used 'bare' in a branch test — excluding
    static uses (.shape/.ndim/.dtype/.size, len(), isinstance(),
    ``is None`` checks)."""
    bare: Set[str] = set()

    def visit(node):
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return  # x.shape is static under trace
            visit(node.value)
            return
        if isinstance(node, ast.Call):
            cn = dotted(node.func)
            if cn in ("len", "isinstance", "getattr", "hasattr", "type"):
                return
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                visit(a)
            return
        if isinstance(node, ast.Compare):
            ops_static = all(isinstance(o, (ast.Is, ast.IsNot))
                             for o in node.ops)
            if ops_static:
                return  # `x is None` style identity checks are host-side
        if isinstance(node, ast.Name):
            if node.id in tracers:
                bare.add(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(test)
    return bare


def _check_traced_fn(module, sym: str, fn, static_names: Set[str],
                     findings: List[Finding]):
    params = set(param_names(fn))
    if params and param_names(fn)[0] in ("self", "cls"):
        params.discard(param_names(fn)[0])
    tracers = params - static_names

    def flag(node, msg):
        findings.append(Finding(
            rule=RULE, path=module.relpath, line=node.lineno,
            symbol=sym or "<lambda>", message=msg))

    body = fn.body if isinstance(fn.body, list) else [fn.body]
    for stmt in body:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                cn = dotted(node.func)
                if cn in _HOST_COERCE:
                    ref = tracers & set().union(
                        *(names_in(a) for a in node.args), set())
                    if ref:
                        flag(node,
                             f"host materialization {cn}() of traced "
                             f"value {sorted(ref)[0]!r} inside jitted/"
                             f"device-loop code (host sync)")
                elif cn in _TIME_CALLS:
                    flag(node,
                         f"wall-clock call {cn}() inside traced code "
                         f"runs at trace time, not per step")
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "block_until_ready":
                    flag(node,
                         "block_until_ready() inside traced code "
                         "(host sync in the measured region)")
                elif isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "item" and not node.args:
                    flag(node,
                         ".item() inside traced code forces a host "
                         "readback")
            elif isinstance(node, (ast.If, ast.While)):
                bare = _bare_tracer_refs(node.test, tracers)
                if bare:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    flag(node,
                         f"Python `{kind}` branches on traced value "
                         f"{sorted(bare)[0]!r}; use lax.cond/select or "
                         f"hoist the decision to host metadata")


def _module_scope_jnp(module, findings: List[Finding]):
    for stmt in module.tree.body:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef, ast.Import, ast.ImportFrom)):
            continue
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call):
                cn = dotted(node.func)
                if cn is not None and (cn.startswith("jnp.") or
                                       cn.startswith("jax.numpy.")):
                    findings.append(Finding(
                        rule=RULE, path=module.relpath, line=node.lineno,
                        symbol="",
                        message=f"module-scope {cn}() computes on device "
                                f"at import time; build host-side (np) "
                                f"and convert inside a function"))


def check(modules, ctx: LintContext) -> List[Finding]:
    findings: List[Finding] = []
    for m in modules:
        for sym, fn, static_names in _collect_traced(m):
            _check_traced_fn(m, sym, fn, static_names, findings)
        _module_scope_jnp(m, findings)
    return findings
