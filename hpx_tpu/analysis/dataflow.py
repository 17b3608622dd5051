"""hpxlint dataflow tier (tier 3): def-use chains and the rules on top.

The per-file tier (rules.py) is lexical; the project tier (project.py)
resolves symbols, locks and call edges but stays flow-insensitive.
This tier adds the missing axis: *which definitions reach which uses*.
It builds intraprocedural reaching-definitions/def-use chains per
function over the SAME parsed trees (no file is parsed twice), plus
one-level interprocedural summaries from the ProjectIndex call graph
(locks held by every caller at the call site; jit-donation positions
of factory returns).

Four rules run on it:

* HPX019 — infer a guarded-by lock per ``self.attr`` from the sites
  that mutate it with a lock held; flag mutations reachable bare,
* HPX020 — an array binding donated to a jitted call (donate_argnums)
  is used again afterwards,
* HPX021 — axis-name literals inside a ``shard_map`` body that the
  enclosing mesh/specs never declare,
* HPX022 — flow-sensitive HPX002: a value whose every reaching
  definition is device-origin flows into ``float()``/``int()``/
  ``bool()``/``np.array()`` in hot-path code.  (HPX002 keeps the
  token-level sinks and consults :func:`provably_host` to drop its
  historical false positives.)

Pure stdlib, like the rest of the linter.  The def-use core is a
may-analysis (unions over forks, loops walked twice for back edges);
the rules that need certainty (HPX022, the HPX002 prover) therefore
demand agreement of EVERY reaching definition before speaking up.
"""

from __future__ import annotations

import ast
from typing import (Callable, Dict, FrozenSet, Iterable, List, Optional,
                    Sequence, Set, Tuple)

from .engine import DataflowRule, FileContext, Finding, register
from .project import ProjectIndex, FunctionInfo

_FUNC_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)
_SCOPE_NODES = _FUNC_NODES  # lambdas handled by shadowing, not scoping


# ---------------------------------------------------------------------------
# Reaching definitions / def-use chains for one function body
# ---------------------------------------------------------------------------

class Def:
    """One binding of a local name: the statement that bound it, the
    bound value expression when there is one, and how it was bound."""

    __slots__ = ("name", "node", "value", "kind")

    def __init__(self, name: str, node: ast.AST,
                 value: Optional[ast.AST] = None,
                 kind: str = "assign") -> None:
        self.name = name
        self.node = node
        self.value = value
        self.kind = kind  # assign|aug|param|for|with|except|import|func|class|donated|unknown

    def __repr__(self) -> str:  # pragma: no cover — debugging aid
        return f"Def({self.name!r}, {self.kind}, line {getattr(self.node, 'lineno', '?')})"


class Use:
    """One Name load: the node and the definitions reaching it."""

    __slots__ = ("name", "node", "defs")

    def __init__(self, name: str, node: ast.AST,
                 defs: FrozenSet[Def]) -> None:
        self.name = name
        self.node = node
        self.defs = defs


Env = Dict[str, FrozenSet[Def]]
CallEffect = Callable[[ast.Call, Env], Optional[Dict[str, Def]]]


def _merge(*envs: Optional[Env]) -> Optional[Env]:
    """Union of reaching definitions over live branches (None = the
    branch cannot fall through)."""
    live = [e for e in envs if e is not None]
    if not live:
        return None
    if len(live) == 1:
        return dict(live[0])
    out: Env = {}
    for env in live:
        for name, defs in env.items():
            prev = out.get(name)
            out[name] = defs if prev is None else (prev | defs)
    return out


class DefUse:
    """Reaching-definitions walk of ONE function (or module) body.

    Statement-ordered abstract interpretation: `if` forks and merges,
    loops run twice so back-edge definitions reach first-iteration
    uses, `try` handlers start from every intermediate body state and
    `finally` sees both the normal and the escaping states (the HPX015
    walker's routing, rebuilt for environments instead of deltas).
    Nested ``def``/``lambda`` bodies are separate scopes — their loads
    are not recorded here (lambdas shadow their parameters).

    `call_effect` lets a rule rewrite the environment at call sites —
    HPX020 uses it to replace donated argument bindings with a
    ``donated`` definition that later loads then trip over.
    """

    def __init__(self, fn: ast.AST,
                 call_effect: Optional[CallEffect] = None) -> None:
        self.fn = fn
        self.call_effect = call_effect
        self.uses: List[Use] = []
        # id(Name node) -> reaching defs; loops record twice, the
        # second (superset, back edges included) wins
        self.use_at: Dict[int, FrozenSet[Def]] = {}
        env: Env = {}
        args = getattr(fn, "args", None)
        if args is not None:
            params = list(args.posonlyargs) + list(args.args) \
                + list(args.kwonlyargs)
            for a in params:
                env[a.arg] = frozenset({Def(a.arg, a, None, "param")})
            for va in (args.vararg, args.kwarg):
                if va is not None:
                    env[va.arg] = frozenset({Def(va.arg, va, None, "param")})
        self.exit_env = self._walk(getattr(fn, "body", []), env)

    # -- expression side ----------------------------------------------------

    def _use(self, node: ast.Name, env: Env,
             shadow: FrozenSet[str]) -> None:
        if node.id in shadow:
            return
        defs = env.get(node.id, frozenset())
        self.uses.append(Use(node.id, node, defs))
        self.use_at[id(node)] = defs

    def _expr(self, expr: Optional[ast.AST], env: Env,
              shadow: FrozenSet[str] = frozenset()) -> None:
        """Record loads and apply call effects, in evaluation-ish
        order (children before the call effect of their Call)."""
        if expr is None:
            return
        if isinstance(expr, ast.Name):
            if isinstance(expr.ctx, ast.Load):
                self._use(expr, env, shadow)
            return
        if isinstance(expr, ast.Lambda):
            for d in expr.args.defaults + [
                    d for d in expr.args.kw_defaults if d is not None]:
                self._expr(d, env, shadow)
            inner = shadow | {a.arg for a in (
                list(expr.args.posonlyargs) + list(expr.args.args)
                + list(expr.args.kwonlyargs)
                + [v for v in (expr.args.vararg, expr.args.kwarg) if v])}
            self._expr(expr.body, env, inner)
            return
        if isinstance(expr, (ast.ListComp, ast.SetComp, ast.DictComp,
                             ast.GeneratorExp)):
            inner = shadow
            for i, gen in enumerate(expr.generators):
                # first iterable evaluates in the enclosing scope
                self._expr(gen.iter, env, inner if i else shadow)
                inner = inner | {n.id for n in ast.walk(gen.target)
                                 if isinstance(n, ast.Name)}
                for cond in gen.ifs:
                    self._expr(cond, env, inner)
            if isinstance(expr, ast.DictComp):
                self._expr(expr.key, env, inner)
                self._expr(expr.value, env, inner)
            else:
                self._expr(expr.elt, env, inner)
            return
        if isinstance(expr, ast.Call):
            self._expr(expr.func, env, shadow)
            for a in expr.args:
                self._expr(a, env, shadow)
            for kw in expr.keywords:
                self._expr(kw.value, env, shadow)
            if self.call_effect is not None:
                eff = self.call_effect(expr, env)
                if eff:
                    for name, d in eff.items():
                        env[name] = frozenset({d})
            return
        for child in ast.iter_child_nodes(expr):
            if isinstance(child, ast.expr):
                self._expr(child, env, shadow)
            elif isinstance(child, (ast.comprehension, ast.keyword)):
                self._expr(getattr(child, "value", None) or
                           getattr(child, "iter", None), env, shadow)

    # -- binding ------------------------------------------------------------

    def _bind(self, target: ast.AST, env: Env, node: ast.AST,
              value: Optional[ast.AST], kind: str) -> None:
        """Record base-loads of complex targets, then (re)bind plain
        names.  ``x[i] = v`` / ``x.f = v`` mutate, not rebind — the
        base is a use and ``x`` keeps its definitions."""
        if isinstance(target, ast.Name):
            env[target.id] = frozenset(
                {Def(target.id, node, value, kind)})
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                # element-wise values are not tracked through unpacking
                self._bind(elt, env, node, None,
                           "unknown" if kind == "assign" else kind)
        elif isinstance(target, ast.Starred):
            self._bind(target.value, env, node, None, "unknown")
        else:
            self._expr(target, env)

    # -- statement side -----------------------------------------------------

    def _walk(self, stmts: Sequence[ast.stmt],
              env: Optional[Env]) -> Optional[Env]:
        for stmt in stmts:
            if env is None:
                return None
            env = self._stmt(stmt, env)
        return env

    def _stmt(self, stmt: ast.stmt, env: Env) -> Optional[Env]:
        if isinstance(stmt, _FUNC_NODES):
            for d in stmt.decorator_list:
                self._expr(d, env)
            for d in stmt.args.defaults + [
                    x for x in stmt.args.kw_defaults if x is not None]:
                self._expr(d, env)
            env[stmt.name] = frozenset(
                {Def(stmt.name, stmt, None, "func")})
            return env
        if isinstance(stmt, ast.ClassDef):
            for d in stmt.decorator_list + stmt.bases:
                self._expr(d, env)
            env[stmt.name] = frozenset(
                {Def(stmt.name, stmt, None, "class")})
            return env
        if isinstance(stmt, ast.Return):
            self._expr(stmt.value, env)
            return None
        if isinstance(stmt, ast.Raise):
            self._expr(stmt.exc, env)
            self._expr(stmt.cause, env)
            return None
        if isinstance(stmt, ast.Assign):
            self._expr(stmt.value, env)
            for t in stmt.targets:
                self._bind(t, env, stmt, stmt.value, "assign")
            return env
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                self._expr(stmt.value, env)
                self._bind(stmt.target, env, stmt, stmt.value, "assign")
            return env
        if isinstance(stmt, ast.AugAssign):
            if isinstance(stmt.target, ast.Name):
                # read-modify-write: the target is a use first
                self._use(stmt.target, env, frozenset())
            else:
                self._expr(stmt.target, env)
            self._expr(stmt.value, env)
            self._bind(stmt.target, env, stmt, None, "aug")
            return env
        if isinstance(stmt, ast.Delete):
            for t in stmt.targets:
                if isinstance(t, ast.Name):
                    env[t.id] = frozenset()
                else:
                    self._expr(t, env)
            return env
        if isinstance(stmt, ast.If):
            self._expr(stmt.test, env)
            taken = self._walk(stmt.body, dict(env))
            other = self._walk(stmt.orelse, dict(env)) \
                if stmt.orelse else dict(env)
            return _merge(taken, other)
        if isinstance(stmt, ast.While):
            self._expr(stmt.test, env)
            once = self._walk(stmt.body, dict(env))
            merged = _merge(env, once)
            twice = self._walk(stmt.body, dict(merged)) \
                if merged is not None else None
            out = _merge(env, once, twice)
            if out is not None and stmt.orelse:
                out = self._walk(stmt.orelse, out)
            return out
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._expr(stmt.iter, env)
            first = dict(env)
            self._bind(stmt.target, first, stmt, None, "for")
            once = self._walk(stmt.body, first)
            merged = _merge(first, once)
            twice = None
            if merged is not None:
                self._bind(stmt.target, merged, stmt, None, "for")
                twice = self._walk(stmt.body, merged)
            out = _merge(env, once, twice)
            if out is not None and stmt.orelse:
                out = self._walk(stmt.orelse, out)
            return out
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._expr(item.context_expr, env)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, env, stmt,
                               item.context_expr, "with")
            return self._walk(stmt.body, env)
        if isinstance(stmt, ast.Try) or (
                hasattr(ast, "TryStar")
                and isinstance(stmt, ast.TryStar)):
            snapshots: List[Env] = [dict(env)]
            cur: Optional[Env] = env
            for s in stmt.body:
                cur = self._stmt(s, cur)
                if cur is None:
                    break
                snapshots.append(dict(cur))
            handler_entry = _merge(*snapshots)
            handler_outs: List[Optional[Env]] = []
            for h in stmt.handlers:
                henv = dict(handler_entry or {})
                if h.type is not None:
                    self._expr(h.type, henv)
                if h.name:
                    henv[h.name] = frozenset(
                        {Def(h.name, h, None, "except")})
                handler_outs.append(self._walk(h.body, henv))
            if cur is not None and stmt.orelse:
                cur = self._walk(stmt.orelse, cur)
            merged_out = _merge(cur, *handler_outs)
            if stmt.finalbody:
                # the finally runs on normal flow, caught-and-handled
                # flow AND escaping flow — walk it from the union so
                # its uses see every state it can observe
                fin_in = _merge(merged_out, *snapshots, *handler_outs)
                fin_out = self._walk(stmt.finalbody, fin_in or {})
                return None if merged_out is None else fin_out
            return merged_out
        if isinstance(stmt, ast.Match):
            self._expr(stmt.subject, env)
            arms: List[Optional[Env]] = [dict(env)]  # no case may match
            for case in stmt.cases:
                cenv = dict(env)
                for n in ast.walk(case.pattern):
                    name = getattr(n, "name", None)
                    if isinstance(name, str):
                        cenv[name] = frozenset(
                            {Def(name, case.pattern, None, "unknown")})
                if case.guard is not None:
                    self._expr(case.guard, cenv)
                arms.append(self._walk(case.body, cenv))
            return _merge(*arms)
        if isinstance(stmt, (ast.Global, ast.Nonlocal)):
            for name in stmt.names:
                env[name] = frozenset()
            return env
        if isinstance(stmt, (ast.Import, ast.ImportFrom)):
            for a in stmt.names:
                if a.name == "*":
                    continue
                bound = a.asname or a.name.split(".")[0]
                env[bound] = frozenset({Def(bound, stmt, None, "import")})
            return env
        if isinstance(stmt, (ast.Break, ast.Continue, ast.Pass)):
            return env
        # Expr / Assert / anything simple: record every expression
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                self._expr(child, env)
        return env


# ---------------------------------------------------------------------------
# Per-file scope map + lazy DefUse cache
# ---------------------------------------------------------------------------

def own_nodes(scope: ast.AST) -> Iterable[ast.AST]:
    """Every node in `scope`'s body that belongs to its scope — stops
    at nested function definitions (their bodies are separate scopes;
    lambdas stay, they cannot contain statements)."""
    stack = list(getattr(scope, "body", []))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, _SCOPE_NODES):
            continue  # the def itself is visible, its body is not
        stack.extend(ast.iter_child_nodes(node))


class FileDataflow:
    """Scope discovery + lazily-built :class:`DefUse` per scope for
    one file.  Cached on the FileContext so the per-file tier (the
    HPX002 prover) and the dataflow tier share one instance."""

    def __init__(self, ctx: FileContext) -> None:
        self.ctx = ctx
        self.scopes: List[ast.AST] = [ctx.tree]
        self._scope_of: Dict[int, ast.AST] = {}
        self._du: Dict[int, DefUse] = {}

        def map_under(node: ast.AST, scope: ast.AST) -> None:
            for child in ast.iter_child_nodes(node):
                self._scope_of[id(child)] = scope
                if isinstance(child, _SCOPE_NODES):
                    self.scopes.append(child)
                    map_under(child, child)
                else:
                    map_under(child, scope)

        map_under(ctx.tree, ctx.tree)

    def scope_of(self, node: ast.AST) -> ast.AST:
        return self._scope_of.get(id(node), self.ctx.tree)

    def defuse(self, scope: ast.AST,
               call_effect: Optional[CallEffect] = None) -> DefUse:
        if call_effect is not None:  # rule-specific: never cached
            return DefUse(scope, call_effect)
        du = self._du.get(id(scope))
        if du is None:
            du = DefUse(scope)
            self._du[id(scope)] = du
        return du


def get_file_dataflow(ctx: FileContext) -> FileDataflow:
    fdf = getattr(ctx, "_hpxlint_dataflow", None)
    if fdf is None:
        fdf = FileDataflow(ctx)
        ctx._hpxlint_dataflow = fdf  # type: ignore[attr-defined]
    return fdf


# ---------------------------------------------------------------------------
# Origin classification: is this value provably host or device data?
# ---------------------------------------------------------------------------

_HOST_PREFIXES = ("numpy.", "math.", "time.", "os.", "collections.",
                  "itertools.", "statistics.", "random.")
_HOST_BUILTINS = {"len", "int", "float", "bool", "str", "min", "max",
                  "sum", "abs", "round", "range", "sorted", "list",
                  "tuple", "dict", "set", "enumerate", "zip", "divmod",
                  "ord", "repr", "hash", "format"}
_HOST_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize", "nbytes"}
_DEVICE_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.random.", "jax.nn.",
                    "jax.scipy.", "jax.ops.")
_DEVICE_CALLS = {"jax.device_put", "jax.tree_util.tree_map"}
_JIT_FUNCS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
_PROGRAM_FACTORIES = _JIT_FUNCS | {"jax.shard_map", "shard_map"}
# array methods that preserve the host/device-ness of their receiver
_ARRAY_METHODS = {"sum", "mean", "max", "min", "astype", "reshape",
                  "copy", "ravel", "any", "all", "dot", "transpose",
                  "squeeze", "flatten", "cumsum", "argmax", "argmin",
                  "block_until_ready", "clip", "round"}


def _is_getattr_shape(call: ast.Call, dotted: str) -> bool:
    return (dotted == "getattr" and len(call.args) >= 2
            and isinstance(call.args[1], ast.Constant)
            and call.args[1].value in _HOST_ATTRS)


def _join2(a: str, b: str) -> str:
    if a == "unknown" or b == "unknown":
        return "unknown"
    if a == b:
        return a
    return "device"  # jax wins numpy in mixed arithmetic


def classify_origin(expr: ast.AST, du: DefUse, ctx: FileContext,
                    _depth: int = 0,
                    _seen: Optional[Set[int]] = None) -> str:
    """'host' / 'device' / 'unknown' for the value of `expr`, chasing
    Name loads through their reaching definitions (all must agree)."""
    if _depth > 8 or expr is None:
        return "unknown"
    seen = _seen if _seen is not None else set()
    if isinstance(expr, ast.Constant):
        return "host"
    if isinstance(expr, ast.Name):
        defs = du.use_at.get(id(expr))
        if not defs:
            return "unknown"
        verdict = None
        for d in defs:
            if id(d) in seen:
                continue  # cycle through a loop back edge: ignore
            seen.add(id(d))
            if d.kind not in ("assign", "with"):
                return "unknown"
            got = classify_origin(d.value, du, ctx, _depth + 1, seen)
            if got == "unknown":
                return "unknown"
            if verdict is None:
                verdict = got
            elif verdict != got:
                return "unknown"
        return verdict or "unknown"
    if isinstance(expr, ast.Subscript):
        return classify_origin(expr.value, du, ctx, _depth + 1, seen)
    if isinstance(expr, ast.Attribute):
        if expr.attr in _HOST_ATTRS:
            return "host"
        return "unknown"
    if isinstance(expr, ast.Call):
        dotted = ctx.resolve_call(expr.func)
        if dotted:
            if dotted.startswith(_HOST_PREFIXES) \
                    or dotted in _HOST_BUILTINS \
                    or _is_getattr_shape(expr, dotted):
                return "host"
            if dotted.startswith(_DEVICE_PREFIXES) \
                    or dotted in _DEVICE_CALLS:
                return "device"
            if dotted in _PROGRAM_FACTORIES:
                return "unknown"  # a callable, not an array
        if isinstance(expr.func, ast.Call):
            inner = ctx.resolve_call(expr.func.func)
            if inner in _PROGRAM_FACTORIES:
                return "device"  # jax.jit(f, ...)(x)
        if isinstance(expr.func, ast.Name):
            defs = du.use_at.get(id(expr.func))
            if defs and all(
                    d.kind == "assign" and isinstance(d.value, ast.Call)
                    and ctx.resolve_call(d.value.func)
                    in _PROGRAM_FACTORIES for d in defs):
                return "device"  # prog = jax.jit(f); prog(x)
        if isinstance(expr.func, ast.Attribute) \
                and expr.func.attr in _ARRAY_METHODS:
            return classify_origin(expr.func.value, du, ctx,
                                   _depth + 1, seen)
        return "unknown"
    if isinstance(expr, ast.BinOp):
        return _join2(
            classify_origin(expr.left, du, ctx, _depth + 1, seen),
            classify_origin(expr.right, du, ctx, _depth + 1, seen))
    if isinstance(expr, ast.UnaryOp):
        return classify_origin(expr.operand, du, ctx, _depth + 1, seen)
    if isinstance(expr, (ast.BoolOp,)):
        got = [classify_origin(v, du, ctx, _depth + 1, seen)
               for v in expr.values]
        out = got[0]
        for g in got[1:]:
            out = out if out == g else "unknown"
        return out
    if isinstance(expr, ast.Compare):
        out = classify_origin(expr.left, du, ctx, _depth + 1, seen)
        for c in expr.comparators:
            out = _join2(out, classify_origin(c, du, ctx,
                                              _depth + 1, seen))
        return out
    if isinstance(expr, ast.IfExp):
        a = classify_origin(expr.body, du, ctx, _depth + 1, seen)
        b = classify_origin(expr.orelse, du, ctx, _depth + 1, seen)
        return a if a == b else "unknown"
    if isinstance(expr, (ast.Tuple, ast.List)):
        got = {classify_origin(e, du, ctx, _depth + 1, seen)
               for e in expr.elts}
        return "host" if got == {"host"} else "unknown"
    return "unknown"


def provably_host(expr: ast.AST, ctx: FileContext) -> bool:
    """True when every reaching definition of `expr` is host data —
    the HPX002 token rule calls this to drop sinks that can never
    touch the device (``int(np.flatnonzero(...)[0])`` and friends)."""
    fdf = get_file_dataflow(ctx)
    du = fdf.defuse(fdf.scope_of(expr))
    return classify_origin(expr, du, ctx) == "host"


# ---------------------------------------------------------------------------
# DataflowIndex: project-wide summaries shared by the tier-3 rules
# ---------------------------------------------------------------------------

def _call_desc(func: ast.AST) -> Optional[tuple]:
    """The ProjectIndex call descriptor for a call's func expression
    (same shapes _scan_exprs collects)."""
    if isinstance(func, ast.Attribute):
        base = func.value
        if isinstance(base, ast.Name):
            if base.id == "self":
                return ("self", func.attr)
            return ("dotted", base.id, func.attr)
        if isinstance(base, ast.Attribute) \
                and isinstance(base.value, ast.Name) \
                and base.value.id == "self":
            return ("selfattr", base.attr, func.attr)
        return None
    if isinstance(func, ast.Name):
        return ("name", func.id)
    return None


def _literal_ints(node: ast.AST) -> FrozenSet[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                    and not isinstance(e.value, bool):
                out.add(e.value)
        return frozenset(out)
    return frozenset()


def jit_donate_positions(call: ast.Call,
                         ctx: FileContext) -> FrozenSet[int]:
    """Donated argument positions of a ``jax.jit(f, donate_argnums=...)``
    call expression ('' when the callee is not a jit family member or
    the positions are not literal)."""
    if ctx.resolve_call(call.func) not in _JIT_FUNCS:
        return frozenset()
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            return _literal_ints(kw.value)
    return frozenset()


class DataflowIndex:
    """The ProjectIndex plus the one-level interprocedural summaries
    the tier-3 rules share: locks held at every resolved call site
    (→ entry-held sets, the HPX013 machinery reused one level deep)
    and jit-donation positions of program-factory returns."""

    def __init__(self, index: ProjectIndex) -> None:
        self.index = index
        self._entry_held: Optional[Dict[str, FrozenSet[str]]] = None
        self._donate_summary: Dict[str, FrozenSet[int]] = {}
        self._info_of_node: Dict[int, FunctionInfo] = {
            id(info.node): info for info in index.functions.values()}

    def file_dataflow(self, display_path: str) -> FileDataflow:
        return get_file_dataflow(self.index.contexts[display_path])

    def info_for(self, fn_node: ast.AST) -> Optional[FunctionInfo]:
        return self._info_of_node.get(id(fn_node))

    def entry_held(self, qname: str) -> FrozenSet[str]:
        """Locks held at EVERY resolved call site of `qname` (one
        level: the callers' lexical held sets, no propagation).
        Empty for functions without resolved in-edges."""
        if self._entry_held is None:
            eh: Dict[str, FrozenSet[str]] = {}
            for q in sorted(self.index.functions):
                info = self.index.functions[q]
                for desc, _node, held in info.calls:
                    for callee in self.index.resolve_call(info, desc):
                        s = frozenset(held)
                        eh[callee] = s if callee not in eh \
                            else (eh[callee] & s)
            self._entry_held = eh
        return self._entry_held.get(qname, frozenset())

    def jit_donate_summary(self, qname: str) -> FrozenSet[int]:
        """Donated positions when `qname` returns a jit-donate call
        (``def _jit_step(...): return jax.jit(step, donate_argnums=..)``)
        — the one-level summary HPX020 chases factory calls through."""
        if qname in self._donate_summary:
            return self._donate_summary[qname]
        out: FrozenSet[int] = frozenset()
        info = self.index.functions.get(qname)
        if info is not None and isinstance(info.node, _FUNC_NODES):
            ctx = self.index.contexts.get(info.path)
            if ctx is not None:
                for node in own_nodes(info.node):
                    if isinstance(node, ast.Return) \
                            and isinstance(node.value, ast.Call):
                        out = out | jit_donate_positions(node.value, ctx)
        self._donate_summary[qname] = out
        return out


# ---------------------------------------------------------------------------
# HPX019 — unguarded shared state (inferred guarded-by)
# ---------------------------------------------------------------------------

_HPX019_SUBPATHS = ("hpx_tpu/svc/", "hpx_tpu/models/", "hpx_tpu/cache/",
                    "hpx_tpu/dist/")
_INIT_METHODS = {"__init__", "__post_init__", "__new__",
                 "__init_subclass__"}


@register
class UnguardedSharedState(DataflowRule):
    """HPX019: an instance attribute is mutated under a lock at most
    sites but bare at others — the classic torn-update race that turns
    into corrupted state once ROADMAP item 1 splits the fleet into
    real localities.  The guard is INFERRED: when a strict majority of
    a ``self.attr``'s non-``__init__`` mutation sites (in ``svc/``,
    ``models/``, ``cache/``, ``dist/``) hold the same registered lock
    — lexically or via every caller (one-level entry-held sets) — the
    remaining bare sites are flagged.  Attributes touched by only one
    method (scratch) and ``__init__``-only attributes are exempt.
    Fix: widen the critical section to cover the bare site, or
    justify single-threaded access with an inline
    ``# hpxlint: disable=HPX019 — <why>``."""

    id = "HPX019"
    name = "unguarded-shared-state"
    severity = "error"

    def check_dataflow(self, dfx: DataflowIndex) -> Iterable[Finding]:
        index = dfx.index
        # (module, cls) -> attr -> [(kind, node, held_eff, info)]
        groups: Dict[Tuple[str, str],
                     Dict[str, List[tuple]]] = {}
        for q in sorted(index.functions):
            info = index.functions[q]
            if info.cls is None:
                continue
            if not any(s in info.path for s in _HPX019_SUBPATHS):
                continue
            eff = dfx.entry_held(q)
            for kind, attr, node, held in info.attr_ops:
                groups.setdefault((info.module, info.cls), {}) \
                    .setdefault(attr, []) \
                    .append((kind, node, frozenset(held) | eff, info))
        for mod_cls in sorted(groups):
            _mod, cls = mod_cls
            for attr in sorted(groups[mod_cls]):
                ops = groups[mod_cls][attr]
                if len({op[3].qname for op in ops}) <= 1:
                    continue  # single-method scratch attribute
                muts = [op for op in ops if op[0] == "write"
                        and op[3].node.name not in _INIT_METHODS]
                if not muts:
                    continue  # __init__-only (or read-only) attribute
                counts: Dict[str, int] = {}
                for _k, _n, held, _i in muts:
                    for lid in held:
                        counts[lid] = counts.get(lid, 0) + 1
                if not counts:
                    continue  # never guarded anywhere: no contract
                guard = max(sorted(counts), key=lambda L: counts[L])
                n_held, total = counts[guard], len(muts)
                if 2 * n_held <= total:
                    continue  # no majority: no inferable contract
                short = ".".join(guard.split(".")[-2:])
                for _k, node, held, info in muts:
                    if guard in held:
                        continue
                    yield self.finding_at(
                        info.path, node,
                        f"self.{attr} is mutated in "
                        f"{cls}.{info.node.name}() without holding "
                        f"{short} — {n_held} of {total} mutation sites "
                        "hold it (inferred guarded-by); widen the "
                        "critical section or justify the bare access")


# ---------------------------------------------------------------------------
# HPX020 — donation use-after-donate
# ---------------------------------------------------------------------------

@register
class DonationUseAfterDonate(DataflowRule):
    """HPX020: a binding passed at a donated position of a jitted call
    (``donate_argnums``) is used again afterwards — XLA aliases the
    donated buffer into the outputs, so the old array is dead and
    reads return garbage (or error under
    ``jax_debug_nans``-style guards).  Tracked through def-use
    chains: direct ``jax.jit(f, donate_argnums=..)(x)`` calls,
    programs bound to locals, and one level of factory indirection
    (``prog = self._jit_step(step)`` where the factory returns a
    jit-donate call).  Fix: rebind the result over the donated name
    (``x, s = prog(x, s)``) or stop donating that argument."""

    id = "HPX020"
    name = "donation-use-after-donate"
    severity = "error"

    def check_dataflow(self, dfx: DataflowIndex) -> Iterable[Finding]:
        index = dfx.index
        for path in sorted(index.contexts):
            ctx = index.contexts[path]
            if "donate_argnums" not in ctx.source:
                continue
            fdf = dfx.file_dataflow(path)
            for scope in fdf.scopes:
                if not isinstance(scope, _FUNC_NODES):
                    continue
                info = dfx.info_for(scope)

                def effect(call: ast.Call, env: Env,
                           _info=info) -> Optional[Dict[str, Def]]:
                    positions: Set[int] = set()
                    func = call.func
                    if isinstance(func, ast.Call):
                        positions |= jit_donate_positions(func, ctx)
                    elif isinstance(func, ast.Name):
                        for d in env.get(func.id, ()):
                            v = d.value
                            if not isinstance(v, ast.Call):
                                continue
                            positions |= jit_donate_positions(v, ctx)
                            desc = _call_desc(v.func)
                            if desc and _info is not None:
                                for callee in index.resolve_call(
                                        _info, desc):
                                    positions |= \
                                        dfx.jit_donate_summary(callee)
                    if not positions:
                        return None
                    out: Dict[str, Def] = {}
                    for p in sorted(positions):
                        if p < len(call.args) \
                                and isinstance(call.args[p], ast.Name):
                            name = call.args[p].id
                            out[name] = Def(name, call, None, "donated")
                    return out or None

                du = fdf.defuse(scope, call_effect=effect)
                seen_sites: Set[Tuple[int, int]] = set()
                for use in du.uses:
                    if not any(d.kind == "donated" for d in use.defs):
                        continue
                    site = (use.node.lineno, use.node.col_offset)
                    if site in seen_sites:
                        continue  # loops record uses twice
                    seen_sites.add(site)
                    yield self.finding_at(
                        path, use.node,
                        f"`{use.name}` is used after being donated to "
                        "a jitted call — XLA aliases donated buffers "
                        "into the outputs, so this read sees freed "
                        "memory; rebind the call's result over "
                        f"`{use.name}` or drop it from donate_argnums")


# ---------------------------------------------------------------------------
# HPX021 — mesh-axis consistency inside shard_map bodies
# ---------------------------------------------------------------------------

_SHARD_MAP_NAMES = {"shard_map"}
_PSPEC_NAMES = {"P", "PartitionSpec"}
_COLLECTIVE_AXIS_ARG = {"psum": 1, "pmean": 1, "pmax": 1, "pmin": 1,
                        "ppermute": 1, "all_gather": 1, "all_to_all": 1,
                        "psum_scatter": 1, "axis_index": 0, "pvary": 1}


def _axis_literals(node: ast.AST) -> FrozenSet[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return frozenset({node.value})
    if isinstance(node, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.add(e.value)
        return frozenset(out)
    return frozenset()


def _pspec_axes(expr: ast.AST, ctx: FileContext) -> FrozenSet[str]:
    """Axis-name string literals inside P(...)/PartitionSpec(...)
    fragments of a specs expression."""
    out: Set[str] = set()
    for node in ast.walk(expr):
        if isinstance(node, ast.Call):
            callee = ctx.resolve_call(node.func)
            if callee.split(".")[-1] in _PSPEC_NAMES:
                for a in node.args:
                    out |= _axis_literals(a)
    return frozenset(out)


def _specs_axes_complete(expr: ast.AST, du: DefUse, ctx: FileContext,
                         depth: int = 0) -> Optional[FrozenSet[str]]:
    """The FULL axis set of a specs expression, or None when any
    fragment is opaque (a call result, a variable P(axis), ...) — an
    incomplete declared set must skip the check, never flag against
    it.  Spec names are chased one def-use hop (``data_spec =
    P("dp", None)``)."""
    if depth > 4:
        return None
    if isinstance(expr, ast.Constant):
        # P(None) / spec=None placeholders declare nothing
        return frozenset() if expr.value is None else None
    if isinstance(expr, (ast.Tuple, ast.List)):
        out: Set[str] = set()
        for e in expr.elts:
            got = _specs_axes_complete(e, du, ctx, depth + 1)
            if got is None:
                return None
            out |= got
        return frozenset(out)
    if isinstance(expr, ast.Call):
        if ctx.resolve_call(expr.func).split(".")[-1] \
                not in _PSPEC_NAMES:
            return None
        out = set()
        for a in expr.args:
            if isinstance(a, ast.Constant) and a.value is None:
                continue
            lits = _axis_literals(a)
            if not lits:
                return None  # P(axis) with a variable: opaque
            out |= lits
        return frozenset(out)
    if isinstance(expr, ast.Name):
        defs = du.use_at.get(id(expr))
        if not defs:
            return None
        out = set()
        for d in defs:
            if d.value is None:
                return None
            got = _specs_axes_complete(d.value, du, ctx, depth + 1)
            if got is None:
                return None
            out |= got
        return frozenset(out)
    return None


def _mesh_axes_from_call(call: ast.Call,
                         ctx: FileContext) -> FrozenSet[str]:
    if ctx.resolve_call(call.func).split(".")[-1] not in (
            "Mesh", "AbstractMesh", "make_mesh"):
        return frozenset()
    axes: FrozenSet[str] = frozenset()
    if len(call.args) >= 2:
        axes = axes | _axis_literals(call.args[1])
    for kw in call.keywords:
        if kw.arg in ("axis_names", "axis_name"):
            axes = axes | _axis_literals(kw.value)
    return axes


@register
class MeshAxisConsistency(DataflowRule):
    """HPX021: a collective (``psum``/``ppermute``/``all_gather``/...)
    or ``PartitionSpec`` fragment inside a ``shard_map`` body names an
    axis the enclosing mesh/specs never declare — jax raises a
    NameError-like failure only when that branch first traces on a
    pod, long after the edit that renamed the axis.  Declared axes are
    collected from literal ``Mesh(..., ("dp","tp"))`` axis tuples
    (chased through def-use when ``mesh=`` is a local name) and from
    literal P()/PartitionSpec() fragments in ``in_specs``/
    ``out_specs``; bodies are resolved through local def-use (named
    inner functions, lambdas, ``functools.partial``) plus same-file
    helpers they call.  Sites whose axis set cannot be resolved
    statically are skipped, not guessed.  Fix: use the axis names the
    mesh declares, or thread the axis name in as a parameter."""

    id = "HPX021"
    name = "mesh-axis-consistency"
    severity = "error"

    def check_dataflow(self, dfx: DataflowIndex) -> Iterable[Finding]:
        index = dfx.index
        for path in sorted(index.contexts):
            ctx = index.contexts[path]
            if "shard_map" not in ctx.source:
                continue
            fdf = dfx.file_dataflow(path)
            module_defs = {
                s.name: s for s in ctx.tree.body
                if isinstance(s, _FUNC_NODES)}
            for scope in fdf.scopes:
                for node in own_nodes(scope):
                    if not isinstance(node, ast.Call):
                        continue
                    callee = ctx.resolve_call(node.func)
                    if callee.split(".")[-1] not in _SHARD_MAP_NAMES:
                        continue
                    yield from self._check_site(
                        node, scope, ctx, fdf, module_defs, path)

    def _check_site(self, sm: ast.Call, scope: ast.AST,
                    ctx: FileContext, fdf: FileDataflow,
                    module_defs: Dict[str, ast.AST],
                    path: str) -> Iterable[Finding]:
        du = fdf.defuse(scope)
        # the mesh declares the COMPLETE axis universe; specs only
        # reference it.  Resolve the mesh first (literal call, or a
        # local chased one def-use hop); only when the mesh is opaque
        # fall back to the specs — and then only if EVERY fragment
        # resolves, because flagging against a partial set invents
        # false positives
        declared: Set[str] = set()
        spec_exprs = []
        for kw in sm.keywords:
            if kw.arg == "mesh":
                if isinstance(kw.value, ast.Call):
                    declared |= _mesh_axes_from_call(kw.value, ctx)
                elif isinstance(kw.value, ast.Name):
                    for d in du.use_at.get(id(kw.value), ()):
                        if isinstance(d.value, ast.Call):
                            declared |= _mesh_axes_from_call(
                                d.value, ctx)
            elif kw.arg in ("in_specs", "out_specs"):
                spec_exprs.append(kw.value)
        if not declared:
            for expr in spec_exprs:
                got = _specs_axes_complete(expr, du, ctx)
                if got is None:
                    return  # opaque fragment: skip, don't guess
                declared |= got
        if not declared:
            return  # unresolvable statically: skip, don't guess

        body = self._resolve_body(
            sm.args[0] if sm.args else None, du, ctx, module_defs)
        if body is None:
            return
        decl = ", ".join(sorted(declared))
        seen_fns: Set[int] = set()
        queue: List[Tuple[str, ast.AST]] = [body]
        while queue:
            fname, fnode = queue.pop(0)
            if id(fnode) in seen_fns:
                continue
            seen_fns.add(id(fnode))
            nodes = own_nodes(fnode) if hasattr(fnode, "body") \
                else ast.walk(fnode)
            for node in nodes:
                if not isinstance(node, ast.Call):
                    continue
                callee = ctx.resolve_call(node.func)
                leaf = callee.split(".")[-1]
                if callee.startswith("jax.") \
                        and leaf in _COLLECTIVE_AXIS_ARG:
                    pos = _COLLECTIVE_AXIS_ARG[leaf]
                    axis_expr = None
                    if len(node.args) > pos:
                        axis_expr = node.args[pos]
                    for kw in node.keywords:
                        if kw.arg == "axis_name":
                            axis_expr = kw.value
                    if axis_expr is None:
                        continue
                    for ax in sorted(_axis_literals(axis_expr)):
                        if ax not in declared:
                            yield self.finding_at(
                                path, node,
                                f"{leaf}() over axis '{ax}' inside "
                                f"shard_map body `{fname}` — the "
                                "enclosing mesh/specs only declare "
                                f"({decl}); rename the axis or thread "
                                "it in as a parameter")
                elif leaf in _PSPEC_NAMES and callee != leaf:
                    for a in node.args:
                        for ax in sorted(_axis_literals(a)):
                            if ax not in declared:
                                yield self.finding_at(
                                    path, node,
                                    f"PartitionSpec axis '{ax}' inside "
                                    f"shard_map body `{fname}` — the "
                                    "enclosing mesh/specs only declare "
                                    f"({decl}); rename the axis or "
                                    "thread it in as a parameter")
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in module_defs:
                    queue.append((node.func.id,
                                  module_defs[node.func.id]))

    def _resolve_body(self, expr: Optional[ast.AST], du: DefUse,
                      ctx: FileContext,
                      module_defs: Dict[str, ast.AST]
                      ) -> Optional[Tuple[str, ast.AST]]:
        if expr is None:
            return None
        if isinstance(expr, ast.Lambda):
            return ("<lambda>", expr.body)
        if isinstance(expr, ast.Call):  # functools.partial(f, ...)
            if ctx.resolve_call(expr.func).split(".")[-1] == "partial" \
                    and expr.args:
                return self._resolve_body(expr.args[0], du, ctx,
                                          module_defs)
            return None
        if isinstance(expr, ast.Name):
            for d in du.use_at.get(id(expr), ()):
                if d.kind == "func":
                    return (expr.id, d.node)
            if expr.id in module_defs:
                return (expr.id, module_defs[expr.id])
        return None


# ---------------------------------------------------------------------------
# HPX022 — flow-sensitive host sync (HPX002 on dataflow)
# ---------------------------------------------------------------------------

_SYNC_BUILTINS = {"float", "int", "bool"}


@register
class FlowSensitiveHostSync(DataflowRule):
    """HPX022: a value that is device-origin on EVERY reaching
    definition (jax.numpy/jax.lax results, jitted-program outputs)
    flows into ``float()``/``int()``/``bool()``/``np.array()`` in
    hot-path code (``hpx_tpu/{futures,exec,algo,ops}``) — the same
    dispatch-pipeline stall HPX002 catches lexically, found through
    def-use chains on sinks the token rule cannot see (bare names
    instead of subscripts).  Sinks HPX002 already reports are skipped,
    so the two rules never double-report one site.  Fix: keep the
    value a jax.Array, or sync at the consumer boundary with an
    inline ``# hpxlint: disable=HPX022 — <why>``."""

    id = "HPX022"
    name = "flow-sensitive-host-sync"
    severity = "error"

    def check_dataflow(self, dfx: DataflowIndex) -> Iterable[Finding]:
        from .rules import HOT_SUBPATHS
        index = dfx.index
        for path in sorted(index.contexts):
            ctx = index.contexts[path]
            if not ctx.in_subpath(*HOT_SUBPATHS):
                continue
            fdf = dfx.file_dataflow(path)
            for scope in fdf.scopes:
                sinks: List[Tuple[ast.Call, str, ast.AST]] = []
                for node in own_nodes(scope):
                    if not isinstance(node, ast.Call):
                        continue
                    if isinstance(node.func, ast.Name) \
                            and node.func.id in _SYNC_BUILTINS \
                            and len(node.args) == 1 \
                            and isinstance(node.args[0], ast.Name):
                        # float(x[i]) is HPX002's token sink; float(x)
                        # on a bare name is ours
                        sinks.append((node, node.func.id,
                                      node.args[0]))
                    elif ctx.resolve_call(node.func) == "numpy.array" \
                            and node.args:
                        # np.asarray is HPX002's; np.array is not
                        sinks.append((node, "np.array", node.args[0]))
                if not sinks:
                    continue
                du = fdf.defuse(scope)
                seen: Set[Tuple[int, int]] = set()
                for call, label, arg in sinks:
                    site = (call.lineno, call.col_offset)
                    if site in seen:
                        continue
                    seen.add(site)
                    if classify_origin(arg, du, ctx) != "device":
                        continue
                    what = arg.id if isinstance(arg, ast.Name) \
                        else "its argument"
                    yield self.finding_at(
                        path, call,
                        f"{label}({what}) forces a device->host sync "
                        f"in hot-path code: `{what}` is device-origin "
                        "on every reaching definition — keep it a "
                        "jax.Array or sync at the consumer boundary")
