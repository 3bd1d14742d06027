"""graftlint rule implementations.

R1 is the deep one: a cross-file, interprocedural taint pass that starts
from every jit root's non-static parameters and follows values through
assignments, pytree field access and first-party call edges, flagging
the Python constructs whose *truthiness/host conversion* a tracer cannot
survive. The other rules are syntactic scans scoped by the same jit call
graph (R2) or by file class (R4/R5) — cheap by design so tier-1 can
afford to run the whole thing on every change.

Taint lattice: ``None < "pytree" < "maybe" < "array"``.

- ``"array"`` — definitely a traced array (jnp/jax result, field access
  on a traced bundle). Everything flags: truthiness, conversion,
  iteration, membership.
- ``"maybe"`` — unknown (unannotated parameter, element of a mixed
  container, opaque call result). Truthiness and conversions flag;
  iteration does not — iterating a NamedTuple of tracers
  (``DevicePods(*[f(x) for x in pods])``) is legal and common.
- ``"pytree"`` — definitely a container of traced leaves (dict/tuple
  literal, ``dict()``-family ctor). Containers have host truthiness, so
  only element access re-taints.

Parameter type annotations refine the entry kind: ``x: jnp.ndarray`` →
array, ``hoisted: Dict[...] | None`` → pytree, ``reverse: bool`` /
``name: str`` → host (annotated bools/strs are trace-time constants in
this codebase — jit would have to be told they're static anyway).
Comparisons against string constants are host metadata checks
(``kind == "full"``) and never taint.
"""

from __future__ import annotations

import ast
import re
import sys
from collections import deque
from typing import Dict, List, Optional, Sequence, Set, Tuple

from kubernetes_tpu.lint.engine import (
    RULE_IDS,
    FileInfo,
    Finding,
    FuncRecord,
    Project,
    dotted_name,
    register_rule,
    resolve_dotted,
)

# --- taint lattice ---------------------------------------------------------

_ORDER = {None: 0, "pytree": 1, "maybe": 2, "array": 3}

#: kinds whose truthiness / host conversion a tracer cannot survive
_HAZARD_KINDS = ("maybe", "array")


def _join(a: Optional[str], b: Optional[str]) -> Optional[str]:
    return a if _ORDER[a] >= _ORDER[b] else b


#: annotation leaf name -> entry taint kind. None means host value
#: (trusted untraced); absent leaves mean "maybe".
_ANNOTATION_KINDS = {
    "ndarray": "array", "array": "array", "jaxarray": "array",
    "arraylike": "array",
    "dict": "pytree", "mapping": "pytree", "defaultdict": "pytree",
    "list": "pytree", "tuple": "pytree", "sequence": "pytree",
    "set": "pytree", "frozenset": "pytree", "iterable": "pytree",
    "bool": None, "str": None, "bytes": None, "callable": None,
    "none": None, "nonetype": None,
}


def _annotation_kind(ann: Optional[ast.expr]) -> Tuple[Optional[str], bool]:
    """(entry kind, recognized?) for a parameter annotation. Optional[X]
    and ``X | None`` unwrap to X; unions join their parts."""
    if ann is None:
        return "maybe", False
    if isinstance(ann, ast.Constant):
        if ann.value is None:  # the `| None` / Optional member
            return None, True
        if isinstance(ann.value, str):  # string annotation
            leaf = ann.value.split("[")[0].split(".")[-1].strip().lower()
            if leaf in _ANNOTATION_KINDS:
                return _ANNOTATION_KINDS[leaf], True
        return "maybe", False
    if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
        k1, r1 = _annotation_kind(ann.left)
        k2, r2 = _annotation_kind(ann.right)
        if r1 and r2:
            return _join(k1, k2), True
        # `DevicePods | None`: an unrecognized union member means the
        # value can be anything — do not let the recognized side pin it
        return "maybe", False
    if isinstance(ann, ast.Subscript):
        base = dotted_name(ann.value)
        leaf = (base or "").split(".")[-1].lower()
        if leaf in ("optional", "union"):
            parts = (ann.slice.elts if isinstance(ann.slice, ast.Tuple)
                     else [ann.slice])
            kind: Optional[str] = None
            recognized = True
            for p in parts:
                k, r = _annotation_kind(p)
                recognized &= r
                kind = _join(kind, k)
            return (kind, True) if recognized else ("maybe", False)
        return _annotation_kind(ann.value)
    name = dotted_name(ann)
    if name is not None:
        leaf = name.split(".")[-1].lower()
        if leaf in _ANNOTATION_KINDS:
            return _ANNOTATION_KINDS[leaf], True
    return "maybe", False


def _param_pins(rec: FuncRecord) -> Dict[str, Tuple[Optional[str], bool]]:
    """Per-parameter (annotation kind, recognized) for a function."""
    a = rec.node.args
    return {p.arg: _annotation_kind(p.annotation)
            for p in a.posonlyargs + a.args + a.kwonlyargs}


#: attribute projections of a tracer that are plain host values (safe to
#: branch on): the static trace-time metadata
STATIC_ATTRS = {
    "shape", "dtype", "ndim", "size", "itemsize", "nbytes", "weak_type",
    "aval", "sharding", "dims",
}

#: builtins whose result is a host value even on traced input
_HOST_RESULT_CALLS = {
    "len", "range", "isinstance", "issubclass", "type", "id", "repr",
    "str", "callable", "print", "format", "hasattr",
}

#: conversions that force a concrete value out of a tracer (R1)
_CONVERSIONS = {"bool", "int", "float", "complex"}

#: method names that pull device values to host (R1 in jit, R2 in hot host)
_SYNC_METHODS = {"item", "tolist"}

#: call targets that read a whole device buffer back (R2)
_SYNC_CALLS = {
    "numpy.asarray", "numpy.array", "numpy.ascontiguousarray",
    "jax.device_get",
}

#: host functions that form the per-cycle solve loop (R2 hot scope) in
#: addition to every jit-context function. schedule_cycle itself is the
#: documented host boundary (results must come back to bind) and is
#: deliberately NOT in this set — see docs/lint.md.
HOT_FUNC_NAMES = {
    "Scheduler._run_tier", "Scheduler._solve_ladder", "Scheduler._exact_solve",
    "Scheduler._solve",
    "validate_solution", "greedy_assign", "batch_assign",
}

#: one-line rule summaries (lint_report / docs surface these)
RULE_SUMMARIES = {
    "R0": "suppression hygiene: every disable needs a justification",
    "R1": "tracer-unsafe Python in jit-compiled code",
    "R2": "host-device sync inside the per-cycle solve loop",
    "R3": "retrace hazards (jit-per-call, bogus static_argnames)",
    "R4": "non-determinism (global RNG, wall clock, argless now())",
    "R5": "dtype drift: float64 in device-math modules",
    "R6": "syntax gate: Py3.10 f-string backslash / parse errors",
    "R7": "d2h readback outside a declared obs.jax.readback boundary",
    "R8": "sharded-value gather in a mesh-aware (parallel-importing) module",
    "R9": "lock discipline: guarded state accessed off-lock",
    "R10": "blocking call (RPC/sleep/readback/event emit) under a held lock",
}

#: modules whose arrays must stay float32 (R5): the device-math layer
#: plus the host oracles that feed it
_DTYPE_SCOPE_MARKERS = ("/ops/", "/parallel/")
_DTYPE_SCOPE_FILES = ("native.py",)

_F64_ATTRS = {
    "numpy.float64", "numpy.double", "numpy.float128", "numpy.longdouble",
    "numpy.complex128", "jax.numpy.float64", "jax.numpy.complex128",
}


# ==========================================================================
# R1 — tracer safety (interprocedural taint)
# ==========================================================================

class _FnAnalysis:
    """Analyze one function under a parameter-taint assignment.

    Flow-sensitive single-environment walk. Loop bodies are walked
    twice so loop-carried taint settles (`a = x` at the bottom of the
    body reaches an `if a:` at the top on the second walk); the hazard
    dict is keyed by (line, col, message), so re-walks never duplicate
    findings. Nested defs/lambdas are walked inline with their
    parameters tainted "maybe" (annotation-refined) — inside a jit trace
    they are almost always scan/while/cond callbacks receiving tracers.
    """

    def __init__(self, rec: FuncRecord, param_taint: Dict[str, Optional[str]],
                 project: Project) -> None:
        self.rec = rec
        self.fi = rec.file
        self.project = project
        self.param_taint = {k: v for k, v in param_taint.items() if v}
        self.env: Dict[str, Optional[str]] = {}
        self.calls: Dict[str, Dict[str, str]] = {}  # callee qual -> param taint
        self.callee_recs: Dict[str, FuncRecord] = {}
        self.hazards: Dict[Tuple[int, int, str], Finding] = {}
        self.collect = False

    # -- driver --

    def run(self, collect: bool) -> None:
        self.collect = collect
        self.env = dict(self.param_taint)
        for stmt in self.rec.node.body:
            self.stmt(stmt)

    def findings(self) -> List[Finding]:
        return [self.hazards[k] for k in sorted(self.hazards)]

    def _flag(self, node: ast.AST, message: str) -> None:
        if not self.collect:
            return
        key = (node.lineno, node.col_offset, message)
        self.hazards[key] = self.fi.finding(
            node, "R1", f"{message} in jit-compiled `{self.rec.name}`"
        )

    # -- statements --

    def stmt(self, node: ast.stmt) -> None:
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = node.value
            kind = self.eval(value) if value is not None else None
            if isinstance(node, ast.AugAssign):
                kind = _join(kind, self.eval_target_as_expr(node.target))
            targets = node.targets if isinstance(node, ast.Assign) else [node.target]
            for t in targets:
                self.bind(t, kind)
        elif isinstance(node, (ast.Expr, ast.Return)):
            if node.value is not None:
                self.eval(node.value)
        elif isinstance(node, ast.If):
            self.truthiness(node.test, "`if` branch on traced value")
            for s in node.body + node.orelse:
                self.stmt(s)
        elif isinstance(node, ast.While):
            self.truthiness(node.test, "`while` condition on traced value")
            for _ in range(2):
                for s in node.body:
                    self.stmt(s)
                # the condition re-runs on loop-carried taint
                self.truthiness(node.test,
                                "`while` condition on traced value")
            for s in node.orelse:
                self.stmt(s)
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            k = self.eval(node.iter)
            if k == "array":
                self._flag(node.iter, "iteration over a traced array "
                                      "(use lax.scan / lax.fori_loop)")
            for _ in range(2):
                self.bind(node.target,
                          "array" if k == "array" else ("maybe" if k else None))
                for s in node.body:
                    self.stmt(s)
            for s in node.orelse:
                self.stmt(s)
        elif isinstance(node, ast.Match):
            k = self.eval(node.subject)
            if k in _HAZARD_KINDS:
                self._flag(node.subject,
                           "`match` on a traced value (pattern matching "
                           "concretizes the tracer — use lax.switch)")
            for case in node.cases:
                self._bind_pattern(case.pattern,
                                   "maybe" if k in _HAZARD_KINDS else None)
                if case.guard is not None:
                    self.truthiness(case.guard, "`case` guard on traced value")
                for s in case.body:
                    self.stmt(s)
        elif isinstance(node, ast.Assert):
            self.truthiness(node.test, "`assert` on traced value")
            if node.msg is not None:
                self.eval(node.msg)
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                k = self.eval(item.context_expr)
                if item.optional_vars is not None:
                    self.bind(item.optional_vars, k)
            for s in node.body:
                self.stmt(s)
        elif isinstance(node, ast.Try):
            for s in node.body + node.orelse + node.finalbody:
                self.stmt(s)
            for h in node.handlers:
                for s in h.body:
                    self.stmt(s)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = node.args
            for p in a.posonlyargs + a.args + a.kwonlyargs:
                kind, known = _annotation_kind(p.annotation)
                self.env[p.arg] = kind if known else "maybe"
            for s in node.body:
                self.stmt(s)
            self.env[node.name] = None
        elif isinstance(node, ast.ClassDef):
            for s in node.body:
                self.stmt(s)
        elif isinstance(node, (ast.Delete,)):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self.env[t.id] = None
        # Pass/Break/Continue/Import/Global/Nonlocal/Raise: nothing to do
        elif isinstance(node, ast.Raise):
            if node.exc is not None:
                self.eval(node.exc)

    def _bind_pattern(self, pat: ast.pattern, kind: Optional[str]) -> None:
        """Bind capture names of a match-case pattern; destructuring a
        traced subject yields traced pieces."""
        if isinstance(pat, ast.MatchAs):
            if pat.pattern is not None:
                self._bind_pattern(pat.pattern, kind)
            if pat.name:
                self.env[pat.name] = kind
        elif isinstance(pat, ast.MatchStar):
            if pat.name:
                self.env[pat.name] = kind
        elif isinstance(pat, ast.MatchMapping):
            for p in pat.patterns:
                self._bind_pattern(p, kind)
            if pat.rest:
                self.env[pat.rest] = kind
        elif isinstance(pat, (ast.MatchSequence, ast.MatchOr)):
            for p in pat.patterns:
                self._bind_pattern(p, kind)
        elif isinstance(pat, ast.MatchClass):
            for p in list(pat.patterns) + list(pat.kwd_patterns):
                self._bind_pattern(p, kind)
        elif isinstance(pat, ast.MatchValue):
            self.eval(pat.value)

    def bind(self, target: ast.AST, kind: Optional[str]) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = kind
        elif isinstance(target, (ast.Tuple, ast.List)):
            elt_kind = kind if kind is None else (
                "array" if kind == "array" else "maybe"
            )
            for e in target.elts:
                self.bind(e, elt_kind)
        elif isinstance(target, ast.Starred):
            self.bind(target.value, "maybe" if kind else None)
        # Attribute/Subscript targets mutate containers: no new name taint

    def eval_target_as_expr(self, target: ast.AST) -> Optional[str]:
        if isinstance(target, ast.Name):
            return self.env.get(target.id)
        return None

    # -- truthiness contexts --

    def truthiness(self, test: ast.expr, message: str) -> None:
        if isinstance(test, ast.Compare) and all(
            isinstance(o, (ast.Is, ast.IsNot)) for o in test.ops
        ):
            # `x is None` never calls __bool__ on a tracer — the blessed
            # Optional-arg branch form
            for v in [test.left] + test.comparators:
                self.eval(v)
            return
        if isinstance(test, ast.BoolOp):
            for v in test.values:
                self.truthiness(v, message)
            return
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            self.truthiness(test.operand, message)
            return
        k = self.eval(test)
        if k in _HAZARD_KINDS:
            self._flag(test, message + " (use jnp.where / lax.cond)")

    # -- expressions --

    def eval(self, node: ast.expr) -> Optional[str]:  # noqa: C901
        if isinstance(node, ast.Name):
            return self.env.get(node.id)
        if isinstance(node, ast.Constant):
            return None
        if isinstance(node, ast.Attribute):
            base = self.eval(node.value)
            if node.attr in STATIC_ATTRS:
                return None
            if base in _HAZARD_KINDS:
                return "array"
            return "maybe" if base else None
        if isinstance(node, ast.Subscript):
            base = self.eval(node.value)
            self.eval(node.slice)
            if base == "array":
                return "array"
            return "maybe" if base else None
        if isinstance(node, ast.Call):
            return self.eval_call(node)
        if isinstance(node, ast.BinOp):
            return _join(self.eval(node.left), self.eval(node.right))
        if isinstance(node, ast.UnaryOp):
            k = self.eval(node.operand)
            if isinstance(node.op, ast.Not):
                if k in _HAZARD_KINDS:
                    self._flag(node, "`not` on traced value")
                return None
            return k
        if isinstance(node, ast.BoolOp):
            # `a and b` outside an `if` still calls bool(a)
            out: Optional[str] = None
            for i, v in enumerate(node.values):
                k = self.eval(v)
                if k in _HAZARD_KINDS and i < len(node.values) - 1:
                    self._flag(v, "`and`/`or` short-circuit on traced value")
                out = _join(out, k)
            return out
        if isinstance(node, ast.Compare):
            operands = [node.left] + list(node.comparators)
            kinds = [self.eval(v) for v in operands]
            if all(isinstance(o, (ast.Is, ast.IsNot)) for o in node.ops):
                return None
            if any(isinstance(v, ast.Constant) and isinstance(v.value, str)
                   for v in operands):
                # comparing against a string constant is a host metadata
                # check (`kind == "full"`) — arrays don't compare to str
                return None
            if any(kinds) and len(node.ops) > 1:
                self._flag(node, "chained comparison on traced values "
                                 "(implicit `and` calls bool())")
            if "array" in kinds and any(isinstance(o, (ast.In, ast.NotIn))
                                        for o in node.ops):
                self._flag(node, "membership test on traced value")
            if "array" in kinds:
                return "array"
            return "maybe" if "maybe" in kinds else None
        if isinstance(node, ast.IfExp):
            self.truthiness(node.test, "conditional expression on traced value")
            return _join(self.eval(node.body), self.eval(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            kinds = [self.eval(e) for e in node.elts]
            return "pytree" if any(kinds) else None
        if isinstance(node, ast.Dict):
            kinds = [self.eval(v) for v in node.values if v is not None]
            kinds += [self.eval(k) for k in node.keys if k is not None]
            return "pytree" if any(kinds) else None
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            self._comp_generators(node.generators)
            k = self.eval(node.elt)
            return "pytree" if k else None
        if isinstance(node, ast.DictComp):
            self._comp_generators(node.generators)
            k = _join(self.eval(node.key), self.eval(node.value))
            return "pytree" if k else None
        if isinstance(node, ast.Lambda):
            a = node.args
            for p in a.posonlyargs + a.args + a.kwonlyargs:
                self.env[p.arg] = "maybe"
            self.eval(node.body)
            return None
        if isinstance(node, ast.Starred):
            return self.eval(node.value)
        if isinstance(node, ast.NamedExpr):
            k = self.eval(node.value)
            self.bind(node.target, k)
            return k
        if isinstance(node, ast.JoinedStr):
            for v in node.values:
                self.eval(v)
            return None
        if isinstance(node, ast.FormattedValue):
            self.eval(node.value)
            return None
        if isinstance(node, (ast.Await, ast.YieldFrom)):
            return self.eval(node.value)
        if isinstance(node, ast.Yield):
            return self.eval(node.value) if node.value else None
        if isinstance(node, ast.Slice):
            for part in (node.lower, node.upper, node.step):
                if part is not None:
                    self.eval(part)
            return None
        return None

    def _comp_generators(self, gens) -> None:
        for g in gens:
            k = self.eval(g.iter)
            if k == "array":
                self._flag(g.iter, "iteration over a traced array "
                                   "(use lax.scan / jnp ops)")
            self.bind(g.target,
                      "array" if k == "array" else ("maybe" if k else None))
            for cond in g.ifs:
                self.truthiness(cond, "comprehension filter on traced value")

    def eval_call(self, node: ast.Call) -> Optional[str]:
        arg_kinds = [self.eval(a) for a in node.args]
        kw_kinds = {kw.arg: self.eval(kw.value) for kw in node.keywords}
        all_kinds = arg_kinds + list(kw_kinds.values())
        any_taint = any(all_kinds)
        hazard_arg = any(k in _HAZARD_KINDS for k in all_kinds)
        name = dotted_name(node.func)
        full = resolve_dotted(name, self.fi.imports)

        # self.meth(...) / cls.meth(...): resolve within the enclosing
        # class and thread argument taint through like any first-party
        # call — without this, interprocedural R1/R2 stops dead at every
        # method boundary of class-structured jit code
        if (isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id in ("self", "cls")
                and "." in self.rec.name):
            cls_prefix = self.rec.name.rsplit(".", 1)[0] + "."
            meth = self.fi.functions.get(cls_prefix + node.func.attr)
            if meth is not None:
                recv = self.env.get(node.func.value.id)
                taints: Dict[str, str] = {}
                if recv and meth.params:
                    taints[meth.params[0]] = recv  # receiver slot
                for i, k in enumerate(arg_kinds):
                    if (k and i + 1 < len(meth.params)
                            and not any(isinstance(a, ast.Starred)
                                        for a in node.args[: i + 1])):
                        taints[meth.params[i + 1]] = k
                for kwname, k in kw_kinds.items():
                    if k and kwname and kwname in meth.params:
                        taints[kwname] = k
                if taints:
                    merged = self.calls.setdefault(meth.qual, {})
                    for p, k in taints.items():
                        merged[p] = _join(merged.get(p), k) or k
                    self.callee_recs[meth.qual] = meth
                return "maybe" if (any_taint or recv) else None

        # method-style: base.method(...)
        if isinstance(node.func, ast.Attribute):
            base = self.eval(node.func.value)
            if node.func.attr in _SYNC_METHODS and base in _HAZARD_KINDS:
                self._flag(node, f"`.{node.func.attr}()` forces a traced "
                                 "value to host")
                return None
            if base in _HAZARD_KINDS:
                return "array"
            if base == "pytree":
                # dict/tuple methods (.items(), .get(), .keys()) return
                # host iterables whose elements may be traced
                return "maybe"

        if full in _HOST_RESULT_CALLS:
            return None
        if full in _CONVERSIONS:
            if hazard_arg:
                self._flag(node, f"`{full}()` on a traced value")
            return None
        if full in ("dict", "list", "tuple", "set", "frozenset", "sorted",
                    "reversed", "zip", "enumerate"):
            return "pytree" if any_taint else None
        if full and (full.startswith("jax.") or full.startswith("numpy.")
                     or full == "jax"):
            # higher-order transforms (lax.scan/while_loop/cond, vmap, …)
            # trace their callbacks: a first-party function passed by name
            # into ANY jax call runs with traced parameters
            for a in node.args:
                if isinstance(a, (ast.Name, ast.Attribute)):
                    cb = self.project.resolve_name(dotted_name(a), self.fi)
                    if cb is not None and cb.params:
                        merged = self.calls.setdefault(cb.qual, {})
                        for p in cb.params:
                            merged.setdefault(p, "maybe")
                        self.callee_recs[cb.qual] = cb
            # numpy on tracers raises/constant-folds; R2 reports the sync
            # aspect, taint-wise the result is device-shaped either way
            return "array" if any_taint else None

        callee = self.project.resolve_call(node, self.fi)
        if callee is not None:
            taints: Dict[str, str] = {}
            for i, k in enumerate(arg_kinds):
                if k and not any(isinstance(a, ast.Starred)
                                 for a in node.args[: i + 1]):
                    if i < len(callee.params):
                        taints[callee.params[i]] = k
            for kwname, k in kw_kinds.items():
                if k and kwname and kwname in callee.params:
                    taints[kwname] = k
            if taints:
                merged = self.calls.setdefault(callee.qual, {})
                for p, k in taints.items():
                    merged[p] = _join(merged.get(p), k) or k
                self.callee_recs[callee.qual] = callee
        return "maybe" if any_taint else None


#: test hook: when set, overrides the computed fixpoint iteration budget
_FIXPOINT_LIMIT: Optional[int] = None


def _jit_taint_state(project: Project) -> Dict[str, Tuple[FuncRecord, Dict[str, str]]]:
    """Fixed-point interprocedural propagation from jit roots. Returns
    qual -> (record, param taints) for every function that runs in jit
    context. Cached on the project (R1 and R2 share it)."""
    cached = getattr(project, "_graftlint_jit_state", None)
    if cached is not None:
        return cached
    state: Dict[str, Tuple[FuncRecord, Dict[str, str]]] = {}
    work: deque = deque()
    for rec in project.jit_roots():
        pins = _param_pins(rec)
        taint = {}
        for p in rec.params:
            if p in rec.static_params:
                continue
            kind, known = pins.get(p, ("maybe", False))
            kind = kind if known else "maybe"
            if kind:
                taint[p] = kind
        state[rec.qual] = (rec, taint)
        work.append(rec.qual)
    # monotone 4-level lattice: each function re-enters the worklist at
    # most a few times, so pops are bounded by ~levels × call edges. The
    # guard only exists to catch an analysis bug — tripping it must be
    # LOUD, never a silent truncation of R1/R2 coverage that lets the
    # tier-1 gate pass with unanalyzed functions
    guard = 0
    guard_limit = _FIXPOINT_LIMIT or max(
        2000, 8 * sum(len(fi.functions) for fi in project.files)
    )
    while work:
        guard += 1
        if guard > guard_limit:
            raise RuntimeError(
                f"graftlint: interprocedural taint fixpoint exceeded "
                f"{guard_limit} iterations (still {len(work)} pending) — "
                "analysis bug or pathological call graph; refusing to "
                "report partial R1/R2 coverage as clean"
            )
        qual = work.popleft()
        rec, taint = state[qual]
        an = _FnAnalysis(rec, dict(taint), project)
        an.run(collect=False)
        for callee_qual, ptaints in an.calls.items():
            callee = an.callee_recs[callee_qual]
            pins = _param_pins(callee)
            if callee.jit_root:
                # statics of a root stay static even when inline-traced
                ptaints = {p: k for p, k in ptaints.items()
                           if p not in callee.static_params}
            prev = state.get(callee_qual)
            cur = dict(prev[1]) if prev else {}
            changed = prev is None
            for p, k in ptaints.items():
                kind, known = pins.get(p, ("maybe", False))
                if known:
                    # a recognized annotation pins the entry kind: the
                    # author's declared contract beats call-site guessing
                    k = kind
                    if not k:
                        continue
                nk = _join(cur.get(p), k)
                if nk != cur.get(p):
                    cur[p] = nk or k
                    changed = True
            if changed:
                state[callee_qual] = (callee, cur)
                work.append(callee_qual)
    project._graftlint_jit_state = state
    return state


@register_rule("R1")
def rule_r1_tracer_safety(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for qual, (rec, taint) in sorted(_jit_taint_state(project).items()):
        an = _FnAnalysis(rec, dict(taint), project)
        an.run(collect=True)
        findings.extend(an.findings())
    return findings


# ==========================================================================
# R2 — host↔device sync in hot paths
# ==========================================================================

@register_rule("R2")
def rule_r2_host_sync(project: Project) -> List[Finding]:
    jit_funcs = _jit_taint_state(project)
    findings: List[Finding] = []
    for fi in project.files:
        if fi.tree is None:
            continue
        for rec in fi.functions.values():
            hot = rec.qual in jit_funcs or rec.name in HOT_FUNC_NAMES \
                or rec.name.split(".")[-1] in HOT_FUNC_NAMES
            if not hot:
                continue
            where = ("jit-compiled" if rec.qual in jit_funcs
                     else "hot-path") + f" `{rec.name}`"
            for node in ast.walk(rec.node):
                if not isinstance(node, ast.Call):
                    continue
                full = resolve_dotted(dotted_name(node.func), fi.imports)
                if full in _SYNC_CALLS:
                    findings.append(fi.finding(
                        node, "R2",
                        f"`{full}` forces a host↔device sync inside {where} "
                        "(keep device values on device; move readback to "
                        "the cycle boundary)",
                    ))
                elif (isinstance(node.func, ast.Attribute)
                      and node.func.attr in _SYNC_METHODS
                      and rec.qual not in jit_funcs):
                    # in jit context R1 already reports tainted .item()
                    findings.append(fi.finding(
                        node, "R2",
                        f"`.{node.func.attr}()` is a per-element device "
                        f"sync inside {where}",
                    ))
    return findings


# ==========================================================================
# R3 — retrace hazards
# ==========================================================================

@register_rule("R3")
def rule_r3_retrace(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for fi in project.files:
        if fi.tree is None:
            continue
        findings.extend(_r3_jit_in_body(fi))
        for rec in fi.functions.values():
            if not rec.jit_root or not rec.static_params:
                continue
            a = rec.node.args
            has_kwargs = a.kwarg is not None
            missing = sorted(rec.static_params - set(rec.params))
            if missing and not has_kwargs:
                findings.append(fi.finding(
                    rec.node, "R3",
                    f"static_argnames {missing} name no parameter of "
                    f"`{rec.name}` — silent retrace/TypeError hazard",
                ))
    return findings


def _r3_jit_in_body(fi: FileInfo) -> List[Finding]:
    """``jax.jit(...)`` constructed inside a function or loop builds a
    fresh wrapper (empty compile cache) per call — the classic retrace
    storm. Decorators and module-scope wrappers are the blessed forms."""
    findings: List[Finding] = []

    def walk(node: ast.AST, in_def: bool, in_loop: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                walk(dec, in_def, in_loop)
            for s in node.body:
                walk(s, True, False)
            return
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            in_loop = True
        if isinstance(node, ast.Call):
            full = resolve_dotted(dotted_name(node.func), fi.imports)
            if full in ("jax.jit", "jax.api.jit") and (in_def or in_loop):
                site = "a loop" if in_loop else "a function body"
                findings.append(fi.finding(
                    node, "R3",
                    f"jax.jit constructed inside {site}: every call "
                    "builds a fresh wrapper with an empty compile "
                    "cache — hoist to module scope or memoize",
                ))
        for child in ast.iter_child_nodes(node):
            walk(child, in_def, in_loop)

    walk(fi.tree, False, False)
    return findings


# ==========================================================================
# R4 — determinism
# ==========================================================================

_RANDOM_OK = {"Random", "SystemRandom", "getstate", "setstate"}
_NP_RANDOM_OK = {
    "default_rng", "Generator", "RandomState", "SeedSequence", "PCG64",
    "Philox", "bit_generator",
}
_DATETIME_NOW = {
    "datetime.datetime.now", "datetime.datetime.utcnow",
    "datetime.date.today", "datetime.datetime.today",
}


@register_rule("R4")
def rule_r4_determinism(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for fi in project.files:
        if fi.tree is None:
            continue
        for node in ast.walk(fi.tree):
            if not isinstance(node, ast.Call):
                continue
            full = resolve_dotted(dotted_name(node.func), fi.imports)
            if not full:
                continue
            if full.startswith("random.") and full.count(".") == 1:
                leaf = full.split(".")[1]
                if leaf not in _RANDOM_OK:
                    findings.append(fi.finding(
                        node, "R4",
                        f"`{full}()` uses the global random state — seed a "
                        "`random.Random(seed)` instance and thread it "
                        "through (the sim/faults idiom)",
                    ))
            elif full.startswith("numpy.random."):
                leaf = full.split(".")[2]
                if leaf not in _NP_RANDOM_OK:
                    findings.append(fi.finding(
                        node, "R4",
                        f"`{full}()` uses numpy's global RNG — use "
                        "`np.random.default_rng(seed)`",
                    ))
            elif full == "time.time":
                findings.append(fi.finding(
                    node, "R4",
                    "`time.time()` is wall-clock — inject a clock "
                    "(`clock: Callable[[], float] = time.monotonic`) so "
                    "sim/chaos runs stay deterministic",
                ))
            elif full in _DATETIME_NOW and not node.args and not node.keywords:
                findings.append(fi.finding(
                    node, "R4",
                    f"argless `{full}()` — inject a clock or pass an "
                    "explicit timezone/timestamp",
                ))
    return findings


# ==========================================================================
# R5 — dtype drift in device-math modules
# ==========================================================================

def _in_dtype_scope(fi: FileInfo) -> bool:
    rel = "/" + fi.relpath
    return (any(m in rel for m in _DTYPE_SCOPE_MARKERS)
            or any(rel.endswith("/" + f) for f in _DTYPE_SCOPE_FILES))


@register_rule("R5")
def rule_r5_dtype(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for fi in project.files:
        if fi.tree is None or not _in_dtype_scope(fi):
            continue
        for node in ast.walk(fi.tree):
            if isinstance(node, ast.Attribute):
                full = resolve_dotted(dotted_name(node), fi.imports)
                if full in _F64_ATTRS:
                    findings.append(fi.finding(
                        node, "R5",
                        f"`{full}` in a device-math module — the solver "
                        "rides float32 end to end; widening silently "
                        "doubles memory traffic and splits jit caches",
                    ))
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        v = kw.value
                        if isinstance(v, ast.Name) and v.id == "float":
                            findings.append(fi.finding(
                                v, "R5",
                                "`dtype=float` is float64 — spell the "
                                "narrow dtype (np.float32) explicitly",
                            ))
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    if (isinstance(arg, ast.Constant)
                            and arg.value in ("float64", "complex128")):
                        findings.append(fi.finding(
                            arg, "R5",
                            f"dtype string '{arg.value}' in a device-math "
                            "module — use float32",
                        ))
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr == "astype" and node.args):
                    a0 = node.args[0]
                    if isinstance(a0, ast.Name) and a0.id == "float":
                        findings.append(fi.finding(
                            a0, "R5",
                            "`.astype(float)` is float64 — use np.float32",
                        ))
    return findings


# ==========================================================================
# R6 — syntax gate: Py3.10 f-string backslash (the seed breaker)
# ==========================================================================

@register_rule("R6")
def rule_r6_fstring_backslash(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for fi in project.files:
        if fi.parse_error is not None:
            line = getattr(fi.parse_error, "lineno", None) or 1
            if _looks_like_fstring_backslash(fi, line):
                findings.append(Finding(
                    fi.relpath, line, 0, "R6",
                    "f-string expression contains a backslash — a "
                    "SyntaxError on Python 3.10 (the class that broke the "
                    "seed's metrics.py); pull the escape into a variable",
                    fi.line_text(line),
                ))
            else:
                findings.append(Finding(
                    fi.relpath, line, 0, "R6",
                    f"file does not parse: {fi.parse_error}",
                    fi.line_text(line),
                ))
            continue
        # forward-compat: on interpreters where the construct parses
        # (3.12+, PEP 701), catch it from the AST so the repo stays
        # 3.10-loadable. Before 3.12 every FormattedValue in a joined
        # string shares the whole string's span (adjacent `\n` literals
        # would false-positive) — and the construct cannot parse there
        # anyway, so the parse_error path above is the real check.
        if sys.version_info < (3, 12):
            continue
        for node in ast.walk(fi.tree):
            if isinstance(node, ast.FormattedValue):
                seg = ast.get_source_segment(fi.source, node)
                if seg and "\\" in seg:
                    findings.append(fi.finding(
                        node, "R6",
                        "backslash inside an f-string expression — "
                        "SyntaxError on Python 3.10; pull the escape into "
                        "a variable",
                    ))
    return findings


def _looks_like_fstring_backslash(fi: FileInfo, around_line: int) -> bool:
    import re

    pat = re.compile(r"""[fF][rRbB]?(['"]).*{[^{}]*\\[^}]*}.*\1""")
    lo = max(0, around_line - 3)
    hi = min(len(fi.lines), around_line + 2)
    return any(pat.search(text) for text in fi.lines[lo:hi])


# ==========================================================================
# R7 — undeclared d2h readback sites
# ==========================================================================

#: modules implementing the declared boundary itself — their internal
#: numpy materialization IS the accounting path
_R7_BOUNDARY_MODULES = ("obs/jaxtel.py",)

#: argument AST nodes that cannot be device buffers (host literals and
#: comprehensions) — np.asarray over them is host-on-host bookkeeping
_R7_HOST_LITERALS = (ast.List, ast.Tuple, ast.Dict, ast.Set, ast.Constant,
                     ast.ListComp, ast.SetComp, ast.DictComp,
                     ast.GeneratorExp, ast.JoinedStr)


@register_rule("R7")
def rule_r7_undeclared_readback(project: Project) -> List[Finding]:
    """``np.asarray``/``jax.device_get`` on a potential device value
    outside the declared ``obs.jax.readback`` boundary. The PR-7 fused
    solve+validate work shrank the steady-state cycle's d2h traffic to
    one small accounted transfer; this rule is the ratchet that keeps
    new unaccounted readback sites from sneaking in silently. Scope:
    first-party modules that import jax (pure-numpy host modules can't
    hold device buffers); obvious host literals are exempt; remaining
    legitimate sites carry scope suppressions with justifications or
    live in the committed baseline — baseline-aware like R0-R6."""
    findings: List[Finding] = []
    for fi in project.files:
        if fi.tree is None:
            continue
        rel = fi.relpath.replace("\\", "/")
        if any(rel.endswith(m) for m in _R7_BOUNDARY_MODULES):
            continue
        if rel.split("/", 1)[0] in ("tests", "scripts"):
            # offline harnesses and parity oracles read device values by
            # design; the ratchet guards the serving/production modules
            continue
        if not any(v == "jax" or v.startswith("jax.")
                   for v in fi.imports.values()):
            continue
        for node in ast.walk(fi.tree):
            if not isinstance(node, ast.Call):
                continue
            full = resolve_dotted(dotted_name(node.func), fi.imports)
            if full not in _SYNC_CALLS:
                continue
            if node.args and isinstance(node.args[0], _R7_HOST_LITERALS):
                continue
            findings.append(fi.finding(
                node, "R7",
                f"`{full}` reads a (potential) device value back outside "
                "a declared boundary — route d2h syncs through "
                "obs.jax.readback so transfer accounting (and the "
                "readback-budget gate) sees them",
            ))
    return findings


# ==========================================================================
# R8 — sharded-value gather in mesh-aware modules
# ==========================================================================

#: gather-ish method calls on a (potentially sharded) device value: the
#: per-element syncs plus the per-shard buffer access that implies the
#: caller is about to assemble the full array on host
_R8_GATHER_METHODS = _SYNC_METHODS | {"addressable_data"}

#: argument forms np.asarray may legitimately take in mesh-aware modules
#: without touching a device buffer (host literals + comprehensions)
_R8_HOST_ONLY = _R7_HOST_LITERALS

_R8_SCOPE_PREFIX = "kubernetes_tpu.parallel"


def _imports_parallel(fi: FileInfo) -> bool:
    """Does this module import the mesh layer (any form, any level)?
    ``fi.imports`` alone is not enough: the engine maps a bare
    ``import a.b.c`` to its top-level name only, so the scope check
    walks the AST for Import/ImportFrom nodes too."""
    if any(v == _R8_SCOPE_PREFIX or v.startswith(_R8_SCOPE_PREFIX + ".")
           for v in fi.imports.values()):
        return True
    for node in ast.walk(fi.tree):
        if isinstance(node, ast.Import):
            if any(a.name == _R8_SCOPE_PREFIX
                   or a.name.startswith(_R8_SCOPE_PREFIX + ".")
                   for a in node.names):
                return True
        elif isinstance(node, ast.ImportFrom) and node.module:
            if (node.module == _R8_SCOPE_PREFIX
                    or node.module.startswith(_R8_SCOPE_PREFIX + ".")):
                return True
    return False


@register_rule("R8")
def rule_r8_mesh_gather(project: Project) -> List[Finding]:
    """``jax.device_get``/``np.asarray``/per-element sync on a potential
    device value inside a PRODUCTION module that imports
    ``kubernetes_tpu.parallel`` — i.e. a module whose values may be
    node-axis-sharded or (P, N)-shaped across the mesh. There, an
    undeclared materialization is not just an unaccounted d2h transfer
    (R7's concern): GSPMD inserts an ALL-GATHER to assemble the full
    array first, so one stray ``np.asarray`` silently moves a
    (P, N)-sized matrix across ICI and then over PCIe — the exact
    transfer the collective cost model (parallel/costmodel.py) claims
    never happens. This rule turns that falsifiable claim into a
    parse-time gate: every d2h in a mesh-aware module must ride the
    declared ``obs.jax.readback`` boundary (which gathers ONCE, with
    byte accounting) or carry a justified suppression. Scope mirrors
    R7 (tests/scripts/boundary modules exempt; host literals exempt);
    baseline-aware and tier-1-enforced like R0-R7."""
    findings: List[Finding] = []
    for fi in project.files:
        if fi.tree is None:
            continue
        rel = fi.relpath.replace("\\", "/")
        if any(rel.endswith(m) for m in _R7_BOUNDARY_MODULES):
            continue
        if rel.split("/", 1)[0] in ("tests", "scripts"):
            # parity oracles and offline harnesses gather by design;
            # the gate guards the production cycle
            continue
        if "/parallel/" in "/" + rel:
            # the placement layer itself (device_put, never a gather)
            continue
        if not _imports_parallel(fi):
            continue
        for node in ast.walk(fi.tree):
            if not isinstance(node, ast.Call):
                continue
            full = resolve_dotted(dotted_name(node.func), fi.imports)
            if full in _SYNC_CALLS:
                if node.args and isinstance(node.args[0], _R8_HOST_ONLY):
                    continue
                findings.append(fi.finding(
                    node, "R8",
                    f"`{full}` materializes a (potentially node-axis-"
                    "sharded) value on host in a mesh-aware module — "
                    "GSPMD all-gathers the full array first; route the "
                    "readback through obs.jax.readback so the gather is "
                    "deliberate, single, and byte-accounted",
                ))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr in _R8_GATHER_METHODS):
                findings.append(fi.finding(
                    node, "R8",
                    f"`.{node.func.attr}()` on a (potentially sharded) "
                    "device value in a mesh-aware module — a per-shard/"
                    "per-element gather outside the declared "
                    "obs.jax.readback boundary",
                ))
    return findings


# ==========================================================================
# R9 / R10 — lock discipline + blocking-under-lock
# ==========================================================================

#: lock constructors recognized on ``self.X = threading.Lock()`` — plus
#: any injectable factory whose name mentions "lock" (the sanitize.py
#: seam: ``self._lock = lock_factory("cache.snap")``)
_LOCK_CTOR_LEAVES = {
    "lock", "rlock", "condition", "semaphore", "boundedsemaphore",
}

#: ``# guarded-by: self._lock`` — the explicit declaration form; the
#: lock name normalizes through a leading ``self.``
_GUARDED_BY_RE = re.compile(r"#\s*guarded-by:\s*(?P<lock>[A-Za-z_][\w.]*)")

#: container mutations that count as WRITES for guard inference — in
#: this codebase shared state is mostly dicts/deques mutated in place,
#: not rebound
_MUTATOR_METHODS = {
    "append", "appendleft", "add", "insert", "extend", "update", "pop",
    "popleft", "popitem", "remove", "discard", "clear", "setdefault",
}

#: files R9/R10 never look at: test fakes and offline harnesses are
#: single-threaded by design, same scoping as R7/R8
_LOCK_EXEMPT_TOPDIRS = ("tests", "scripts")

#: directly-blocking operations for R10 — exactly the shapes that have
#: bitten this repo: hub RPC verbs, the declared d2h boundary, sleeps,
#: event-sink emission, and device syncs
_R10_BLOCKING_DOTTED = {"time.sleep"}
_R10_BLOCKING_METHODS = {"result", "block_until_ready", "readback"}
_R10_HUB_VERBS = {
    "bind", "bind_pod", "create_pod", "update_pod", "delete_pod",
    "patch_pod", "list_pods", "get_pod",
}
_R10_SINK_NAMES = {"event_sink"}
_R10_SINK_DESC = "event-sink emission"


def _r10_blocking_desc(node: ast.Call, imports: Dict[str, str]) -> Optional[str]:
    """Human description when this call is a known-blocking op, else
    None. Callers exclude intraclass ``self.meth()`` calls first —
    a class invoking its OWN ``delete_pod`` is in-process bookkeeping,
    not a stub RPC."""
    func = node.func
    name = dotted_name(func)
    full = resolve_dotted(name, imports)
    leaf = (name or "").split(".")[-1]
    if full in _R10_BLOCKING_DOTTED:
        return f"`{full}()`"
    if leaf == "block_until_ready" or (
            full and full.endswith(".block_until_ready")):
        return "`block_until_ready` (device sync)"
    if isinstance(func, ast.Attribute) and func.attr in _R10_BLOCKING_METHODS:
        return (f"`.{func.attr}()` "
                + ("(declared d2h readback)" if func.attr == "readback"
                   else "(device/future sync)"))
    if isinstance(func, ast.Attribute) and func.attr in _R10_HUB_VERBS:
        return f"hub RPC `.{func.attr}()`"
    if leaf in _R10_SINK_NAMES:
        return _R10_SINK_DESC
    return None


#: name tokens that mean "this is a lock" — token-wise so ``clock`` /
#: ``blocked`` never match
_LOCKISH_TOKENS = {"lock", "rlock", "mutex", "cond", "condition"}


def _lockish_name(leaf: str) -> bool:
    tokens = leaf.lower().strip("_").split("_")
    return any(t in _LOCKISH_TOKENS for t in tokens)


def _is_lock_ctor(call: ast.Call, imports: Dict[str, str]) -> bool:
    name = dotted_name(call.func)
    full = resolve_dotted(name, imports) or ""
    leaf = full.split(".")[-1].lower()
    if full.startswith("threading.") and leaf in _LOCK_CTOR_LEAVES:
        return True
    # injectable lock factories (kubernetes_tpu/sanitize.py seam)
    return _lockish_name((name or "").split(".")[-1])


def _lockish_expr(expr: ast.expr, locks: Set[str]) -> Optional[str]:
    """Dotted name of a with-item that acquires a lock, else None.
    ``self.X`` for a known class lock always counts; otherwise the last
    segment must look lock-like (lock / cond / mutex)."""
    name = dotted_name(expr)
    if name is None:
        return None
    parts = name.split(".")
    if len(parts) == 2 and parts[0] == "self" and parts[1] in locks:
        return name
    if _lockish_name(parts[-1]):
        return name
    return None


class _MethodLockScan:
    """One method's lock-relevant events: attribute accesses (with the
    self-locks held at that point), intraclass ``self.meth()`` call
    sites, and R10-relevant blocking calls (with every held lock expr,
    including non-self ones like ``loop.lock``)."""

    def __init__(self, cls: "_ClassLockInfo", meth_name: str,
                 node: ast.AST) -> None:
        self.cls = cls
        self.name = meth_name
        self.node = node
        #: (attr, is_write, frozenset(held self-locks), node)
        self.accesses: List[Tuple[str, bool, frozenset, ast.AST]] = []
        #: (callee method leaf name, frozenset(held self-locks),
        #:  tuple(held lock exprs), node)
        self.self_calls: List[Tuple[str, frozenset, Tuple[str, ...], ast.AST]] = []
        #: (description, tuple(held lock exprs), node) — direct blocking
        self.blocking: List[Tuple[str, Tuple[str, ...], ast.AST]] = []
        #: does this method directly call the event sink?
        self.emits = False

    # -- walk ---------------------------------------------------------------

    def run(self) -> None:
        for stmt in self.node.body:
            self._stmt(stmt, frozenset(), ())

    def _stmt(self, node: ast.stmt, held: frozenset,
              held_exprs: Tuple[str, ...]) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            # a nested def runs LATER, on whatever thread calls it —
            # never under the locks held at definition time
            for s in getattr(node, "body", ()):
                self._stmt(s, frozenset(), ())
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            new_held = set(held)
            new_exprs = list(held_exprs)
            for item in node.items:
                self._expr(item.context_expr, held, held_exprs)
                lk = _lockish_expr(item.context_expr, self.cls.locks)
                if lk is not None:
                    new_exprs.append(lk)
                    parts = lk.split(".")
                    if (len(parts) == 2 and parts[0] == "self"
                            and parts[1] in self.cls.locks):
                        new_held = new_held | {parts[1]}
            for s in node.body:
                self._stmt(s, frozenset(new_held), tuple(new_exprs))
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            if node.value is not None:
                self._expr(node.value, held, held_exprs)
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                self._target(t, held, held_exprs,
                             aug=isinstance(node, ast.AugAssign))
            return
        if isinstance(node, ast.Delete):
            for t in node.targets:
                self._target(t, held, held_exprs)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.stmt):
                self._stmt(child, held, held_exprs)
            elif isinstance(child, ast.expr):
                self._expr(child, held, held_exprs)
            elif isinstance(child, ast.excepthandler):
                for s in child.body:
                    self._stmt(s, held, held_exprs)

    def _self_attr(self, node: ast.AST) -> Optional[str]:
        if (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"):
            return node.attr
        return None

    def _target(self, node: ast.AST, held: frozenset,
                held_exprs: Tuple[str, ...], aug: bool = False) -> None:
        attr = self._self_attr(node)
        if attr is not None:
            self._record(attr, True, held, node)
            return
        if isinstance(node, ast.Subscript):
            base = self._self_attr(node.value)
            if base is not None:
                # self.A[k] = v mutates A in place
                self._record(base, True, held, node.value)
            else:
                self._expr(node.value, held, held_exprs)
            self._expr(node.slice, held, held_exprs)
            return
        if isinstance(node, (ast.Tuple, ast.List)):
            for e in node.elts:
                self._target(e, held, held_exprs)
            return
        if isinstance(node, ast.Starred):
            self._target(node.value, held, held_exprs)
            return
        if isinstance(node, ast.Attribute):
            self._expr(node.value, held, held_exprs)

    def _record(self, attr: str, is_write: bool, held: frozenset,
                node: ast.AST) -> None:
        if attr in self.cls.locks or attr in self.cls.method_names:
            return
        self.accesses.append((attr, is_write, held, node))

    def _expr(self, node: ast.expr, held: frozenset,
              held_exprs: Tuple[str, ...]) -> None:
        if isinstance(node, (ast.Lambda,)):
            # runs later, lock-free (same as nested defs)
            self._expr(node.body, frozenset(), ())
            return
        if isinstance(node, ast.Call):
            self._call(node, held, held_exprs)
            return
        attr = self._self_attr(node)
        if attr is not None:
            self._record(attr, False, held, node)
            self._expr(node.value, held, held_exprs)
            return
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.expr):
                self._expr(child, held, held_exprs)

    def _call(self, node: ast.Call, held: frozenset,
              held_exprs: Tuple[str, ...]) -> None:
        func = node.func
        meth = self._self_attr(func)
        intraclass = meth is not None and meth in self.cls.method_names
        desc = (None if intraclass
                else _r10_blocking_desc(node, self.cls.fi.imports))
        if desc is not None and desc == _R10_SINK_DESC:
            self.emits = True
        if desc is not None and held_exprs:
            self.blocking.append((desc, held_exprs, node))
        # intraclass call edge: self.meth(...) — an in-process call, not
        # a stub RPC, even when the method name is a hub verb; whatever
        # blocking IT does is reached through the entry/emitter closures
        if intraclass:
            self.self_calls.append((meth, held, held_exprs, node))
        # `self.A.append(x)` mutates A in place: a WRITE for guard
        # inference — the dominant shape for this codebase's shared
        # deques/dicts, which are mutated, not rebound
        if isinstance(func, ast.Attribute) and not intraclass:
            base = self._self_attr(func.value)
            if base is not None:
                self._record(base, func.attr in _MUTATOR_METHODS,
                             held, func.value)
            else:
                self._expr(func.value, held, held_exprs)
        for a in node.args:
            self._expr(a, held, held_exprs)
        for kw in node.keywords:
            self._expr(kw.value, held, held_exprs)


class _ClassLockInfo:
    """Per-class lock model: which attributes are locks, which state
    they guard (declared or inferred), and which methods are only ever
    entered with a lock already held."""

    def __init__(self, fi: FileInfo, node: ast.ClassDef) -> None:
        self.fi = fi
        self.node = node
        self.locks: Set[str] = set()
        self.declared: Dict[str, str] = {}  # attr -> lock attr
        self.method_names: Set[str] = set()
        self.scans: Dict[str, _MethodLockScan] = {}
        #: attr -> (lock, "declared"|"inferred", locked_writes, writes)
        self.guarded: Dict[str, Tuple[str, str, int, int]] = {}
        #: method leaf name -> self-locks guaranteed held on entry
        self.entry: Dict[str, frozenset] = {}
        #: methods that (transitively, intraclass) emit events
        self.emitters: Set[str] = set()

    # -- construction -------------------------------------------------------

    def build(self) -> None:
        methods = [n for n in self.node.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        self.method_names = {m.name for m in methods}
        for m in methods:
            self._find_locks_and_declarations(m)
        # class-level ``# guarded-by:`` annotations on assignments
        for n in self.node.body:
            if isinstance(n, (ast.Assign, ast.AnnAssign)):
                self._declare_from_line(n)
        if not self.locks:
            return
        for m in methods:
            if m.name in ("__init__", "__post_init__"):
                continue
            scan = _MethodLockScan(self, m.name, m)
            scan.run()
            self.scans[m.name] = scan
        self._infer_guards()
        self._entry_closure()
        self._emitter_closure()

    def _find_locks_and_declarations(self, meth: ast.AST) -> None:
        for node in ast.walk(meth):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"
                            and _is_lock_ctor(node.value, self.fi.imports)):
                        self.locks.add(t.attr)
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                self._declare_from_line(node)

    def _declare_from_line(self, node: ast.stmt) -> None:
        for ln in range(node.lineno, (node.end_lineno or node.lineno) + 1):
            if not 1 <= ln <= len(self.fi.lines):
                continue
            m = _GUARDED_BY_RE.search(self.fi.lines[ln - 1])
            if m is None:
                continue
            lock = m.group("lock")
            if lock.startswith("self."):
                lock = lock[len("self."):]
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                if (isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    self.declared[t.attr] = lock
                elif isinstance(t, ast.Name):
                    self.declared[t.id] = lock
            return

    # -- guard inference ----------------------------------------------------

    def _infer_guards(self) -> None:
        for attr, lock in self.declared.items():
            if lock in self.locks:
                self.guarded[attr] = (lock, "declared", 0, 0)
        writes: Dict[str, List[frozenset]] = {}
        for scan in self.scans.values():
            for attr, is_write, held, _node in scan.accesses:
                if is_write:
                    writes.setdefault(attr, []).append(held)
        for attr, helds in writes.items():
            if attr in self.guarded:
                continue
            total = len(helds)
            best_lock, best_k = None, 0
            for lock in self.locks:
                k = sum(1 for h in helds if lock in h)
                if k > best_k:
                    best_lock, best_k = lock, k
            if best_lock is not None and total and best_k / total >= 0.8:
                self.guarded[attr] = (best_lock, "inferred", best_k, total)

    # -- interprocedural closures (intraclass call graph) -------------------

    def _entry_closure(self) -> None:
        # *_locked is the codebase's declared caller-holds-the-lock
        # convention (cache._refresh_host_locked); everything else starts
        # lock-free and is promoted only when EVERY intraclass call site
        # provably holds the lock
        for name in self.scans:
            self.entry[name] = (frozenset(self.locks)
                                if name.endswith("_locked") else frozenset())
        sites: Dict[str, List[Tuple[str, frozenset]]] = {}
        for scan in self.scans.values():
            for callee, held, _exprs, _node in scan.self_calls:
                sites.setdefault(callee, []).append((scan.name, held))
        for _ in range(len(self.scans) + 2):
            changed = False
            for name, scan in self.scans.items():
                if name.endswith("_locked"):
                    continue
                calls = sites.get(name)
                if not calls:
                    continue
                new = frozenset.intersection(*[
                    held | self.entry.get(caller, frozenset())
                    for caller, held in calls
                ])
                if new != self.entry[name]:
                    self.entry[name] = new
                    changed = True
            if not changed:
                break

    def _emitter_closure(self) -> None:
        self.emitters = {n for n, s in self.scans.items() if s.emits}
        for _ in range(len(self.scans) + 2):
            grown = False
            for name, scan in self.scans.items():
                if name in self.emitters:
                    continue
                if any(callee in self.emitters
                       for callee, _h, _e, _n in scan.self_calls):
                    self.emitters.add(name)
                    grown = True
            if not grown:
                break


def _lock_state(project: Project) -> List[_ClassLockInfo]:
    """Per-class lock models for every production file; cached on the
    project (R9 and R10 share it, like the R1/R2 jit-taint cache)."""
    cached = getattr(project, "_graftlint_lock_state", None)
    if cached is not None:
        return cached
    out: List[_ClassLockInfo] = []
    for fi in project.files:
        if fi.tree is None:
            continue
        rel = fi.relpath.replace("\\", "/")
        if rel.split("/", 1)[0] in _LOCK_EXEMPT_TOPDIRS:
            continue
        for node in ast.walk(fi.tree):
            if isinstance(node, ast.ClassDef):
                info = _ClassLockInfo(fi, node)
                info.build()
                if info.locks:
                    out.append(info)
    project._graftlint_lock_state = out
    return out


@register_rule("R9")
def rule_r9_lock_discipline(project: Project) -> List[Finding]:
    """Guarded state accessed off-lock. An attribute is guarded by a
    lock when a ``# guarded-by: self._lock`` comment says so, or when
    >= 80% of its writes (rebinds AND in-place container mutations,
    ``__init__`` excluded — construction precedes sharing) happen under
    ``with self._lock``. Every other access — reads included, because
    unlocked snapshot reads were exactly the PR-8/PR-14 bug class —
    must hold that lock, either lexically or by being a method whose
    every intraclass call site holds it (``self._helper()`` under the
    lock, the ``*_locked`` naming convention)."""
    findings: List[Finding] = []
    for info in _lock_state(project):
        for scan in info.scans.values():
            entry = info.entry.get(scan.name, frozenset())
            for attr, is_write, held, node in scan.accesses:
                g = info.guarded.get(attr)
                if g is None:
                    continue
                lock, how, k, n = g
                if lock in held or lock in entry:
                    continue
                basis = ("declared guarded-by" if how == "declared"
                         else f"inferred from {k}/{n} locked writes")
                verb = "written" if is_write else "read"
                findings.append(info.fi.finding(
                    node, "R9",
                    f"`self.{attr}` is guarded by `self.{lock}` ({basis}) "
                    f"but {verb} here without holding it — a data race "
                    f"with the locked writers (torn reads / lost updates)",
                ))
    return findings


@register_rule("R10")
def rule_r10_blocking_under_lock(project: Project) -> List[Finding]:
    """Known-blocking operations while a lock is statically held — the
    exact shape of the PR-14 watchdog-events bug (events emitted inside
    the watchdog mutex, deadlocking any sink that calls back into the
    ledger). Blocking set: hub RPC verbs, ``obs.jax.readback``,
    ``time.sleep``, event-sink emission, ``.result()`` /
    ``block_until_ready``. Held means: inside ``with <lock>`` (any
    lock-named context manager, self or not), or in a method whose
    every intraclass call site holds one (incl. ``*_locked``).
    Collect what you need under the lock, drop it, THEN block."""
    findings: List[Finding] = []
    for info in _lock_state(project):
        for scan in info.scans.values():
            # blocking ops under a lexically held with-lock
            for desc, held_exprs, node in scan.blocking:
                locks = ", ".join(f"`{e}`" for e in held_exprs)
                findings.append(info.fi.finding(
                    node, "R10",
                    f"{desc} while holding {locks} — blocking under a "
                    "lock stalls every thread contending for it (and an "
                    "emission sink calling back in deadlocks); collect "
                    "under the lock, release, then block",
                ))
            # blocking ops in methods whose every intraclass call site
            # holds a lock (incl. *_locked), and emitter methods invoked
            # under a lexically held lock
            entry = info.entry.get(scan.name, frozenset())
            if entry:
                locks = ", ".join(f"`self.{l}`" for l in sorted(entry))
                for node in _r10_unlocked_blocking_nodes(scan):
                    findings.append(info.fi.finding(
                        node[1], "R10",
                        f"{node[0]} in `{scan.name}`, which is only ever "
                        f"called with {locks} held — blocking under a "
                        "caller-held lock; hoist the blocking work out "
                        "of the locked region",
                    ))
            for callee, held, held_exprs, node in scan.self_calls:
                if held_exprs and callee in info.emitters:
                    locks = ", ".join(f"`{e}`" for e in held_exprs)
                    findings.append(info.fi.finding(
                        node, "R10",
                        f"`self.{callee}()` emits events and is called "
                        f"while holding {locks} — the watchdog-events "
                        "bug shape; emit after the lock drops",
                    ))
    return findings


def _r10_unlocked_blocking_nodes(scan: _MethodLockScan):
    """Blocking calls in a scan that are NOT under a lexical with-lock
    (those already reported) — used for caller-held-lock methods."""
    out = []
    seen_lex = {id(n) for _d, _e, n in scan.blocking}

    class _V(ast.NodeVisitor):
        def visit_Call(self, node: ast.Call) -> None:
            if id(node) not in seen_lex:
                func = node.func
                intraclass = (isinstance(func, ast.Attribute)
                              and isinstance(func.value, ast.Name)
                              and func.value.id == "self"
                              and func.attr in scan.cls.method_names)
                if not intraclass:
                    desc = _r10_blocking_desc(node, scan.cls.fi.imports)
                    if desc is not None:
                        out.append((desc, node))
            self.generic_visit(node)

    _V().visit(scan.node)
    return out


# ==========================================================================
# R0 — suppression hygiene
# ==========================================================================

@register_rule("R0")
def rule_r0_suppression_hygiene(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for fi in project.files:
        for d in fi.suppressions.hygiene:
            if d.form == "malformed":
                msg = ("malformed graftlint directive — expected "
                       "`# graftlint: disable=R2 -- justification`")
            elif not d.why.strip():
                msg = (f"suppression of {','.join(d.rules) or '?'} has no "
                       "justification — add ` -- <why this is safe>`")
            elif any(r not in RULE_IDS for r in d.rules):
                msg = f"unknown rule id in suppression: {d.rules}"
            else:
                msg = ("disable-scope directive is not attached to a "
                       "def/class header")
            findings.append(Finding(fi.relpath, d.line, 0, "R0", msg,
                                    fi.line_text(d.line)))
    return findings


def ensure_registered() -> None:
    """Importing this module registers every rule; hook for the engine."""
