"""The hpxlint rule pack — this runtime's real hazard classes.

Each rule is a small `ast` walk over one file.  Rules are heuristic by
design: they trade a few suppressible false positives for catching the
failure modes that are silent at runtime (SURVEY.md §5.2 suspension
deadlocks, §7 host/device sync stalls).  Every rule's docstring states
the hazard and the fix — the CLI prints these for ``--list-rules``.
"""

from __future__ import annotations

import ast
import re
from typing import Iterable, List, Set, Tuple

from .engine import FileContext, Finding, Rule, register

# layers containing executor/continuation code where a hidden device
# sync stalls the dispatch pipeline (HPX002's scope)
HOT_SUBPATHS = ("hpx_tpu/futures", "hpx_tpu/exec",
                "hpx_tpu/algo", "hpx_tpu/ops")

# layers *above* hpx_tpu.synchronization where raw primitives are banned
# (HPX004's scope).  futures/, runtime/ and core/ sit BELOW it in the
# import graph (synchronization.py itself imports futures.future) and
# are the raw substrate; native/ is C++; analysis/ is host tooling.
RAW_PRIMITIVE_EXEMPT = (
    "hpx_tpu/synchronization.py", "hpx_tpu/runtime/", "hpx_tpu/core/",
    "hpx_tpu/futures/", "hpx_tpu/native/", "hpx_tpu/utils/",
    "hpx_tpu/testing.py", "hpx_tpu/analysis/",
)

_LOCK_TYPES = {"Mutex", "Spinlock", "SharedMutex"}


def _lock_symbols(tree: ast.Module) -> Tuple[Set[str], Set[str]]:
    """Names / self-attributes assigned from Mutex()/Spinlock()/
    SharedMutex() anywhere in the module."""
    names: Set[str] = set()
    attrs: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, (ast.Assign, ast.AnnAssign)):
            continue
        value = node.value
        if not (isinstance(value, ast.Call)
                and isinstance(value.func, (ast.Name, ast.Attribute))):
            continue
        callee = (value.func.id if isinstance(value.func, ast.Name)
                  else value.func.attr)
        if callee not in _LOCK_TYPES:
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        for t in targets:
            if isinstance(t, ast.Name):
                names.add(t.id)
            elif isinstance(t, ast.Attribute):
                attrs.add(t.attr)
    return names, attrs


def _is_lock_expr(expr: ast.AST, names: Set[str], attrs: Set[str]) -> str:
    """'' or the display name of a registered-lock `with` item."""
    # `with m.shared():` — SharedMutex read side registers too
    if isinstance(expr, ast.Call) and isinstance(expr.func, ast.Attribute) \
            and expr.func.attr == "shared":
        inner = _is_lock_expr(expr.func.value, names, attrs)
        return f"{inner}.shared()" if inner else ""
    if isinstance(expr, ast.Name) and expr.id in names:
        return expr.id
    if isinstance(expr, ast.Attribute) and expr.attr in attrs:
        base = expr.value
        prefix = f"{base.id}." if isinstance(base, ast.Name) else ""
        return f"{prefix}{expr.attr}"
    return ""


_WAIT_ATTRS = {"wait", "arrive_and_wait", "acquire", "result"}
_WAIT_NAMES = {"wait_all", "wait_any", "wait_some", "wait_each"}


@register
class LockHeldWaitRule(Rule):
    """HPX001: a blocking wait lexically inside a ``with`` block on a
    registered `hpx_tpu.synchronization` Mutex/Spinlock/SharedMutex.

    Suspending while holding a lock is the classic AMT deadlock the
    runtime's VERIFY_LOCKS mode aborts on — but only on executed paths;
    this catches it before any chip time is spent.  Fix: narrow the
    critical section so the wait happens after ``unlock()`` (snapshot
    state under the lock, wait outside), or restructure with a
    continuation (``future.then``) instead of a blocking ``get()``.
    """

    id = "HPX001"
    name = "lock-held-wait"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        names, attrs = _lock_symbols(ctx.tree)
        if not names and not attrs:
            return
        out: List[Finding] = []

        def scan_block(body: List[ast.stmt], lock_name: str) -> None:
            for stmt in body:
                for node in ast.walk(stmt):
                    if not isinstance(node, ast.Call):
                        continue
                    func = node.func
                    if isinstance(func, ast.Attribute):
                        attr = func.attr
                        blocking = attr in _WAIT_ATTRS or (
                            # zero-arg .get() is a future get; dict.get
                            # always takes at least the key
                            attr == "get" and not node.args
                            and not node.keywords)
                        if blocking:
                            out.append(self.finding(
                                ctx, node,
                                f".{attr}() reachable while registered "
                                f"lock `{lock_name}` is held — "
                                "suspension under a lock deadlocks the "
                                "scheduler (VERIFY_LOCKS aborts here at "
                                "runtime); wait after unlock or use a "
                                "continuation"))
                    elif isinstance(func, ast.Name) \
                            and func.id in _WAIT_NAMES:
                        out.append(self.finding(
                            ctx, node,
                            f"{func.id}() reachable while registered "
                            f"lock `{lock_name}` is held — suspension "
                            "under a lock deadlocks the scheduler"))

        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            for item in node.items:
                lock_name = _is_lock_expr(item.context_expr, names, attrs)
                if lock_name:
                    scan_block(node.body, lock_name)
                    break
        yield from out


@register
class HostSyncHotPathRule(Rule):
    """HPX002: host-device synchronization in executor/continuation
    code (``hpx_tpu/{futures,exec,algo,ops}``).

    ``np.asarray`` / ``jax.device_get`` / ``.block_until_ready()`` /
    ``.item()`` / ``float(x[i])`` all block the host until the device
    catches up, stalling every queued dispatch behind them — the "task
    granularity chasm" (SURVEY.md §7).  Fix: keep values as jax.Arrays
    (dispatch is already async), move the materialization to the
    consumer boundary, or route it through ``exec.tpu``'s watcher so a
    future completes off-thread.  Intentional boundary syncs get an
    inline ``# hpxlint: disable=HPX002 — <why>``.
    """

    id = "HPX002"
    name = "host-sync-hot-path"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath(*HOT_SUBPATHS):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = ctx.resolve_call(node.func)
            if dotted == "numpy.asarray":
                yield self.finding(
                    ctx, node, "np.asarray() forces a device->host "
                    "transfer in hot-path code — keep the value a "
                    "jax.Array or sync at the consumer boundary")
            elif dotted == "jax.device_get":
                yield self.finding(
                    ctx, node, "jax.device_get() blocks on the device "
                    "in hot-path code — sync at the consumer boundary")
            elif dotted == "jax.block_until_ready" or (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "block_until_ready"):
                yield self.finding(
                    ctx, node, "block_until_ready() stalls the dispatch "
                    "pipeline in hot-path code — route through the "
                    "exec.tpu watcher so a future completes off-thread")
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                yield self.finding(
                    ctx, node, ".item() materializes a device scalar on "
                    "the host in hot-path code — defer to the consumer "
                    "boundary")
            elif isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int") \
                    and len(node.args) == 1 \
                    and isinstance(node.args[0], ast.Subscript):
                # dataflow prover: int(np.flatnonzero(...)[0]) and
                # friends never touch the device — skip sinks whose
                # every reaching definition is host data
                from .dataflow import provably_host
                if provably_host(node.args[0], ctx):
                    continue
                yield self.finding(
                    ctx, node, f"{node.func.id}(x[...]) materializes a "
                    "device element on the host in hot-path code — "
                    "defer to the consumer boundary")


_FUTURE_FACTORIES = {"async_", "async_many", "dataflow"}


@register
class DroppedFutureRule(Rule):
    """HPX003: the future returned by ``async_()``, ``async_many()``,
    ``dataflow()`` or ``.then()`` discarded as an expression statement.

    A dropped future silently swallows the exception it may carry and
    severs the dependency graph (nothing can wait on the work).  Fix:
    keep the future (wait/compose it), or use ``post()`` /
    ``post_many()`` — the deliberate fire-and-forget API, which returns
    ``None`` and is therefore not flagged.
    """

    id = "HPX003"
    name = "dropped-future"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Call)):
                continue
            func = node.value.func
            label = ""
            if isinstance(func, ast.Name) and func.id in _FUTURE_FACTORIES:
                label = f"{func.id}()"
            elif isinstance(func, ast.Attribute):
                if func.attr in _FUTURE_FACTORIES:
                    label = f"{func.attr}()"
                elif func.attr == "then":
                    label = ".then()"
            if label:
                yield self.finding(
                    ctx, node,
                    f"result of {label} is discarded — the future (and "
                    "any exception it carries) is lost; keep it, or use "
                    "post() for fire-and-forget")


_RAW_PRIMITIVES = {
    "threading.Lock": "hpx_tpu.synchronization.Mutex",
    "threading.RLock": "hpx_tpu.synchronization.Mutex (non-reentrant: "
                       "restructure, or justify keeping RLock)",
    "time.sleep": "exec.execution_base yield/backoff helpers or a "
                  "Latch/Event wait with timeout",
    "queue.Queue": "lcos.local.Channel (futures-returning) or "
                   "runtime.threadpool work queues",
}


@register
class RawPrimitiveRule(Rule):
    """HPX004: raw ``threading.Lock``/``threading.RLock``/
    ``time.sleep``/``queue.Queue`` in runtime layers above
    ``hpx_tpu.synchronization``.

    Raw primitives bypass the VERIFY_LOCKS held-lock registration, so
    the dynamic deadlock guard cannot see them, and raw sleeps/queues
    block OS threads the work-helping scheduler could otherwise use.
    Fix: use the ``hpx_tpu.synchronization`` equivalents (Mutex,
    ConditionVariable, Latch, Event, semaphores) or the lcos channels.
    The substrate below synchronization.py (futures/, runtime/, core/)
    is exempt — it is what those primitives are built from.
    """

    id = "HPX004"
    name = "raw-sync-primitive"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if "hpx_tpu/" not in ctx.display_path \
                or ctx.in_subpath(*RAW_PRIMITIVE_EXEMPT):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = ctx.resolve_call(node.func)
            replacement = _RAW_PRIMITIVES.get(dotted)
            if replacement:
                yield self.finding(
                    ctx, node,
                    f"raw {dotted}() in a runtime module — invisible to "
                    f"VERIFY_LOCKS; use {replacement}")


@register
class JitInLoopRule(Rule):
    """HPX005: ``jax.jit`` constructed inside a loop body.

    Each ``jax.jit(f)`` call creates a fresh jitted callable with an
    empty trace cache, so a loop that rebuilds one recompiles every
    iteration (the recompile trap).  Fix: hoist the jit out of the
    loop, or memoize the built program on its static configuration
    (see ``models.transformer._cached_program``).
    """

    id = "HPX005"
    name = "jit-in-loop"
    severity = "warning"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        out: List[Finding] = []

        def is_jit(node: ast.AST) -> bool:
            return isinstance(node, (ast.Name, ast.Attribute)) and \
                ctx.resolve_call(node) in ("jax.jit", "jax.pjit")

        def walk(node: ast.AST, in_loop: bool) -> None:
            for child in ast.iter_child_nodes(node):
                child_in_loop = in_loop
                if isinstance(child, (ast.For, ast.While, ast.AsyncFor)):
                    child_in_loop = True
                elif isinstance(child, (ast.FunctionDef,
                                        ast.AsyncFunctionDef, ast.Lambda)):
                    # a def inside a loop still *runs* jit per iteration
                    # via its decorators; its body runs only when called
                    if in_loop and not isinstance(child, ast.Lambda):
                        for dec in child.decorator_list:
                            target = dec.func if isinstance(dec, ast.Call) \
                                else dec
                            if is_jit(target):
                                out.append(self._hit(ctx, dec))
                    child_in_loop = False
                if isinstance(child, ast.Call) and in_loop:
                    if is_jit(child.func):
                        out.append(self._hit(ctx, child))
                    elif ctx.resolve_call(child.func) == \
                            "functools.partial" and child.args \
                            and is_jit(child.args[0]):
                        out.append(self._hit(ctx, child))
                walk(child, child_in_loop)

        walk(ctx.tree, False)
        yield from out

    def _hit(self, ctx: FileContext, node: ast.AST) -> Finding:
        return self.finding(
            ctx, node, "jax.jit constructed inside a loop — a fresh "
            "jitted callable per iteration defeats the trace cache "
            "(recompile trap); hoist it or memoize on the static "
            "config (models.transformer._cached_program)")


@register
class BareExceptRule(Rule):
    """HPX006: bare ``except:``.

    A bare except catches ``BaseException`` — including
    ``KeyboardInterrupt``/``SystemExit`` and the runtime's own
    ``DeadlockError`` — so a failing continuation is silently swallowed
    instead of poisoning its future.  Fix: catch a concrete exception
    type, or ``except BaseException:`` + re-raise/``set_exception`` if
    the handler really must see everything (as the future completion
    paths do).
    """

    id = "HPX006"
    name = "bare-except"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ExceptHandler) and node.type is None:
                yield self.finding(
                    ctx, node, "bare except: swallows future exceptions "
                    "(and KeyboardInterrupt/DeadlockError) — catch a "
                    "concrete type or re-raise into the future")


_SPAN_FACTORIES = {"span", "annotate"}


@register
class SpanLeakRule(Rule):
    """HPX007: ``span(...)`` / ``annotate(...)`` called as a bare
    expression statement.

    Both return a context manager (``svc.tracing.span`` a B/E span,
    ``svc.profiling.annotate`` a jax TraceAnnotation); dropping the
    result records NOTHING — the begin never fires, so the region
    silently vanishes from every trace.  Worse, a tracer-level
    ``tracer.span(...)`` statement allocates a ``_Span`` that is never
    entered, leaking the annotation the author thought they added.
    Fix: ``with tracing.span("phase"): ...`` (or keep the object and
    enter it); for a point event use ``tracing.instant(...)``, which
    really is fire-and-forget.
    """

    id = "HPX007"
    name = "span-leak"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Expr)
                    and isinstance(node.value, ast.Call)):
                continue
            func = node.value.func
            if isinstance(func, ast.Name):
                name = func.id
            elif isinstance(func, ast.Attribute):
                name = func.attr
            else:
                continue
            if name in _SPAN_FACTORIES:
                yield self.finding(
                    ctx, node,
                    f"result of {name}() is discarded — it returns a "
                    "context manager, so no event is ever recorded; "
                    "wrap the region in `with ... :` or use "
                    "tracing.instant() for a point event")


_PROGRAM_CACHE_CALLEES = {"cached_program", "_cached_program",
                          "_program"}


def _walk_function(fn: ast.AST) -> Iterable[ast.AST]:
    """Yield the nodes of one function body WITHOUT descending into
    nested function definitions (each is analyzed as its own scope)."""
    stack: List[ast.AST] = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def _is_shape_read(value: ast.AST) -> bool:
    """`x.shape` or `x.shape[i]` — a raw array-extent read."""
    if isinstance(value, ast.Attribute) and value.attr == "shape":
        return True
    return (isinstance(value, ast.Subscript)
            and isinstance(value.value, ast.Attribute)
            and value.value.attr == "shape")


def _is_len_call(value: ast.AST) -> bool:
    return (isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id == "len")


@register
class UnbucketedProgramKeyRule(Rule):
    """HPX008: jit program cache keyed on a raw dynamic length.

    ``cached_program``-family memoization keyed on ``len(...)`` or a
    ``.shape`` extent compiles ONE program per distinct value — under
    mixed-length traffic (serving prompts, ragged batches) the cache
    becomes a compile storm and the trace cache an HBM leak.  Fix:
    round the extent to a bucket ladder and pad-then-mask inside the
    program (``models/serving.py``'s ``hpx.serving.prefill_buckets``
    discipline), so the cache is O(buckets).  A per-shape key is
    legitimate when the program truly cannot pad (whole-array FFTs,
    monolithic generate/scan bodies that bake trip counts) — keep
    those in the baseline with a justification.
    """

    id = "HPX008"
    name = "unbucketed-program-key"
    severity = "warning"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            yield from self._check_scope(ctx, fn)

    def _check_scope(self, ctx: FileContext,
                     fn: ast.AST) -> Iterable[Finding]:
        tainted: Set[str] = set()      # names holding len()/shape vals
        tuples: dict = {}              # local name -> ast.Tuple
        for node in _walk_function(fn):
            if isinstance(node, (ast.Assign, ast.AnnAssign)):
                value = node.value
                if value is None:
                    continue
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                dynamic = _is_len_call(value) or _is_shape_read(value)
                for t in targets:
                    names = ([t] if isinstance(t, ast.Name)
                             else list(t.elts)
                             if isinstance(t, ast.Tuple) else [])
                    for el in names:
                        if not isinstance(el, ast.Name):
                            continue
                        # `b, n = x.shape` taints every unpacked name
                        unpacked = (isinstance(t, ast.Tuple)
                                    and _is_shape_read(value))
                        if dynamic or unpacked:
                            tainted.add(el.id)
                        if isinstance(value, ast.Tuple) \
                                and isinstance(t, ast.Name):
                            tuples[el.id] = value
        seen: Set[Tuple[int, int]] = set()  # a key tuple built once
        # and passed to two call sites (mesh/no-mesh branches) is ONE
        # problem — report each offending element once per scope
        for node in _walk_function(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = ctx.resolve_call(node.func) or ""
            if callee.rsplit(".", 1)[-1] not in _PROGRAM_CACHE_CALLEES:
                continue
            for arg in node.args:
                key = arg
                if isinstance(key, ast.Name):
                    key = tuples.get(key.id)
                if not isinstance(key, ast.Tuple):
                    continue
                for elt in key.elts:
                    bad = (_is_len_call(elt) or _is_shape_read(elt)
                           or (isinstance(elt, ast.Name)
                               and elt.id in tainted))
                    if not bad:
                        continue
                    at = (elt.lineno, elt.col_offset)
                    if at in seen:
                        continue
                    seen.add(at)
                    desc = ast.unparse(elt)
                    fname = getattr(fn, "name", "<module>")
                    yield self.finding(
                        ctx, elt,
                        f"program cache key in {fname}() carries raw "
                        f"dynamic length {desc!r} — one compiled "
                        "program per distinct value; bucket it to a "
                        "ladder and pad-then-mask (serving's "
                        "hpx.serving.prefill_buckets discipline), or "
                        "baseline it with a justification")


# serving hot-loop functions whose device values must stay on device
# (HPX009's scope): the decode/speculation dispatch path in
# models/serving.py.  Admission/prefill code syncs legitimately (seed
# tokens need VALUES); these functions run once per decode step.
_SERVING_HOT_FUNCS = ("step", "run", "_step_inner", "_flush",
                      "_spec_step", "_draft_model_tokens",
                      "_prompt_drafts")


@register
class SpecHostSyncRule(Rule):
    """HPX009: host-device synchronization (``np.asarray`` /
    ``jax.device_get`` / ``.item()``) on draft/verify intermediates
    inside the serving hot loop (``models/serving.py``'s step, flush
    and speculation functions).

    The decode loop owes exactly ONE device->host read per step — the
    speculative path's packed targets+acceptance commit, or the
    non-speculative path's flush of buffered token vectors.  Syncing
    any other draft/verify intermediate (draft token columns, verify
    logits, acceptance counts read one at a time) serializes draft,
    verify and dispatch and turns the one-sync-per-window win back
    into one-sync-per-token.  The designed sync points stay in the
    baseline with a justification; anything new this rule flags is a
    regression.
    """

    id = "HPX009"
    name = "serving-hot-loop-host-sync"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath("hpx_tpu/models/serving"):
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            if fn.name not in _SERVING_HOT_FUNCS:
                continue
            for node in _walk_function(fn):
                if not isinstance(node, ast.Call):
                    continue
                dotted = ctx.resolve_call(node.func)
                if dotted == "numpy.asarray":
                    yield self.finding(
                        ctx, node,
                        f"np.asarray() in serving hot-loop "
                        f"{fn.name}() syncs the device — the decode "
                        "loop owes ONE host read per step; keep "
                        "draft/verify intermediates on device and "
                        "commit through the step's single packed read")
                elif dotted == "jax.device_get":
                    yield self.finding(
                        ctx, node,
                        f"jax.device_get() in serving hot-loop "
                        f"{fn.name}() syncs the device — commit "
                        "through the step's single packed read")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "item" and not node.args:
                    yield self.finding(
                        ctx, node,
                        f".item() in serving hot-loop {fn.name}() "
                        "materializes a device scalar per call — pack "
                        "scalars into the step's single device->host "
                        "read instead")


# modules on the paged-decode data path where a full-pool gather is a
# silent HBM-bandwidth regression (HPX010's scope); the gather oracle
# itself (ops/paged_attention.py) fires too and stays in the baseline.
# models/transformer is fenced since the (dp, tp) mesh work: shard_map
# bodies see per-shard pool slices there, and a pool gather inside one
# would ALSO be a cross-shard-correctness bug waiting to happen the
# moment the block axis stops being dp-replicated — keep every
# array-of-blocks read in the oracle module.
_PAGED_HOT_SUBPATHS = ("hpx_tpu/models/serving",
                       "hpx_tpu/models/transformer", "hpx_tpu/ops/",
                       "hpx_tpu/cache/")


@register
class FullPoolGatherRule(Rule):
    """HPX010: ``pool[table]``-shaped advanced indexing on a KV block
    pool in the paged serving hot path.

    Indexing a block pool with an int32 index array materializes the
    full mapped ``[B, max_blocks, block_size, n_kv, head_dim]`` view
    in HBM — the write-then-gather formulation whose bandwidth the
    fused Pallas kernel (``ops/attention_pallas.fused_paged_attention``)
    exists to eliminate: every byte the gather writes is immediately
    read back by the attention contraction that follows.  Fix: route
    decode attention through ``paged_decode_attention(..., fused=True)``
    so K/V stream table-directed through VMEM.  Array-of-blocks reads
    that must stay in XLA form belong in the designated oracle module
    (``ops/paged_attention.py``) — its sites are baselined with
    justification; anything new this rule flags is a regression.
    The fence covers mesh/shard_map code too (models/serving,
    models/transformer): inside a shard_map body the pool is a
    PER-SHARD slice whose block axis is dp-replicated — a gather there
    is the same bandwidth regression, plus a latent cross-shard bug if
    the replication invariant ever changes, so block tables stay
    per-shard int32 and gathers stay in the oracle.
    Detection is name-based (singular ``*pool*`` arrays are device
    block pools; plural ``pools`` is the host-side per-layer list) —
    a false positive takes an inline
    ``# hpxlint: disable=HPX010 — <why>``.
    """

    id = "HPX010"
    name = "full-pool-gather"
    severity = "error"

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath(*_PAGED_HOT_SUBPATHS):
            return
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Subscript)
                    and isinstance(node.ctx, ast.Load)):
                continue
            base = node.value
            name = (base.id if isinstance(base, ast.Name)
                    else base.attr if isinstance(base, ast.Attribute)
                    else "")
            # singular pool names are device block pools (`pool`,
            # `pool_q`, `k_pool`); plural `pools` is the per-layer
            # host list (Python-int indexed) and `.at[...]` chains
            # are scatters, not gathers — both stay out of scope
            if "pool" not in name or name.endswith("s"):
                continue
            # only array-valued (advanced) indexing gathers; constant
            # subscripts and slices read O(1) blocks
            if not isinstance(node.slice, (ast.Name, ast.Attribute)):
                continue
            yield self.finding(
                ctx, node,
                f"advanced indexing {ast.unparse(node)!r} gathers the "
                "full mapped pool view through HBM — route decode "
                "attention through paged_decode_attention(..., "
                "fused=True); XLA-oracle gathers live only in "
                "ops/paged_attention.py (baselined with justification)")


# resiliency-bearing layers where ad-hoc retry/except patterns hide
# real faults (HPX011's scope): the serving/model layer and the
# distributed layer — the two places `svc/resiliency` policies exist
# to replace hand-rolled loops.
_RESILIENCY_SUBPATHS = ("hpx_tpu/models/", "hpx_tpu/dist/")

# calls that make a retry loop polite: cooperative suspension between
# attempts (exec.execution_base.suspend / yield_while) or a policy
# helper that owns backoff itself
_BACKOFF_CALLEES = {"suspend", "sleep", "yield_while", "sync_replay"}


@register
class NakedRetryRule(Rule):
    """HPX011: hand-rolled retry loops without backoff, and
    broad-except swallowing, in the serving (``hpx_tpu/models``) and
    distributed (``hpx_tpu/dist``) layers.

    Two shapes of quiet fault-amplification:

    * a ``for``/``while`` loop whose body catches an exception and
      goes around again with NO suspension between attempts — under a
      persistent fault (allocator exhausted, locality gone) that loop
      is a busy-wait hammering the failed resource; every retry path
      owes a cooperative backoff (``exec.execution_base.suspend``,
      never raw ``time.sleep`` — HPX004) or should route through
      ``svc.resiliency.sync_replay``/``async_replay``, which own the
      policy;
    * ``except Exception:``/``except BaseException:``/bare ``except:``
      whose handler is only ``pass`` — a swallowed fault in these
      layers silently corrupts serving state the checkpoint/restore
      ladder exists to keep consistent.  Faults must be typed,
      counted, or re-raised.

    The deliberate sites (resiliency's own replay loops live in
    ``svc/`` and are out of scope; in-scope survivors carry a
    justification) stay in the baseline; anything new this rule flags
    is a regression.
    """

    id = "HPX011"
    name = "naked-retry"
    severity = "warning"

    def _loop_retries(self, loop: ast.AST) -> bool:
        """Does some Try directly in this loop catch-and-continue?"""
        for node in _walk_function(loop):
            if isinstance(node, (ast.For, ast.While)):
                continue          # nested loops report themselves
            if not isinstance(node, ast.Try):
                continue
            for h in node.handlers:
                body = h.body
                if body and isinstance(body[-1], ast.Continue):
                    return True
                if all(isinstance(s, ast.Pass) for s in body):
                    return True
                # the _replay_loop shape: handler records the
                # exception (assignment only) and falls through to
                # the next iteration
                if body and all(isinstance(s, (ast.Assign, ast.Pass))
                                for s in body):
                    return True
        return False

    def _loop_backs_off(self, loop: ast.AST) -> bool:
        for node in _walk_function(loop):
            if not isinstance(node, ast.Call):
                continue
            name = (node.func.attr
                    if isinstance(node.func, ast.Attribute)
                    else node.func.id
                    if isinstance(node.func, ast.Name) else "")
            if name in _BACKOFF_CALLEES:
                return True
        return False

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath(*_RESILIENCY_SUBPATHS):
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            fname = fn.name
            for node in _walk_function(fn):
                if isinstance(node, (ast.For, ast.While)):
                    # a RETRY loop iterates attempts (`while ...` or
                    # `for _ in range(n)`); a for over a data
                    # collection with a per-item try is error
                    # ISOLATION, not a retry of the same operation
                    if isinstance(node, ast.For) and not (
                            isinstance(node.iter, ast.Call)
                            and isinstance(node.iter.func, ast.Name)
                            and node.iter.func.id == "range"):
                        continue
                    if self._loop_retries(node) \
                            and not self._loop_backs_off(node):
                        yield self.finding(
                            ctx, node,
                            f"retry loop in {fname}() re-attempts "
                            "with no backoff — a persistent fault "
                            "turns this into a busy-wait; suspend "
                            "between attempts (exec.execution_base."
                            "suspend) or route through svc.resiliency."
                            "sync_replay, which owns the policy")
                elif isinstance(node, ast.ExceptHandler):
                    broad = (node.type is None
                             or (isinstance(node.type, ast.Name)
                                 and node.type.id in ("Exception",
                                                      "BaseException")))
                    if broad and all(isinstance(s, ast.Pass)
                                     for s in node.body):
                        yield self.finding(
                            ctx, node,
                            f"broad except swallowed in {fname}() — "
                            "a pass-only Exception handler hides the "
                            "faults the restore/shed ladder must see; "
                            "type it, count it, or re-raise")


# ---------------------------------------------------------------------------
# HPX012 — unbounded remote wait: a blocking get() on a remote action's
# future with no timeout is a hang waiting for a locality to die. The
# disaggregated serving work made every cross-locality edge carry a
# per-attempt timeout + bounded retry (dist.actions.resilient_action);
# this rule keeps new code from quietly regressing to unbounded waits.
# ---------------------------------------------------------------------------

_REMOTE_SENDERS = ("async_action", "send_action")


@register
class UnboundedRemoteWaitRule(Rule):
    """HPX012: ``.get()`` with no timeout on a remote action future in
    non-test runtime code.

    ``async_action``/``send_action`` parcels cross a process boundary:
    the peer can die mid-call, and without a failure detector ping in
    flight the future then NEVER resolves — a caller blocked in a bare
    ``get()`` hangs forever instead of seeing a typed
    ``LocalityLost``. Every remote wait must either pass
    ``get(timeout_s)`` or route the whole call through
    ``dist.actions.resilient_action`` (per-attempt timeout + bounded
    backoff retry + idempotent re-delivery), which owns the policy.

    Flagged shapes (same-function dataflow only):

    * ``async_action(...).get()`` / ``send_action(...).get()``
      chained directly with no argument;
    * ``f = async_action(...)`` … ``f.get()`` with no argument.

    Deliberate survivors (callers that own deadline handling a level
    up, or infrastructure that must wait out bootstrap) stay in the
    baseline with justification; suppress a single site with
    ``# hpxlint: disable=HPX012 — <why>``.
    """

    id = "HPX012"
    name = "unbounded-remote-wait"
    severity = "warning"

    @staticmethod
    def _is_remote_send(call: ast.AST) -> bool:
        if not isinstance(call, ast.Call):
            return False
        fn = call.func
        name = (fn.attr if isinstance(fn, ast.Attribute)
                else fn.id if isinstance(fn, ast.Name) else "")
        return name in _REMOTE_SENDERS

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.display_path.startswith("tests/") \
                or "/tests/" in ctx.display_path:
            return
        for fn in ast.walk(ctx.tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            # names bound to a remote-send result inside this function
            remote_names: Set[str] = set()
            for node in _walk_function(fn):
                if isinstance(node, ast.Assign) \
                        and self._is_remote_send(node.value):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            remote_names.add(tgt.id)
            for node in _walk_function(fn):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "get"
                        and not node.args and not node.keywords):
                    continue
                recv = node.func.value
                chained = self._is_remote_send(recv)
                via_name = (isinstance(recv, ast.Name)
                            and recv.id in remote_names)
                if chained or via_name:
                    yield self.finding(
                        ctx, node,
                        f"unbounded get() on a remote action future "
                        f"in {fn.name}() — a dead locality leaves "
                        "this blocked forever; pass get(timeout_s) "
                        "or route the call through dist.actions."
                        "resilient_action (timeout + bounded retry + "
                        "idempotent re-delivery)")


# the full counter-name grammar from svc/performance_counters._NAME_RE:
# /object{locality#N/instance}/counter  (N is a number or '*')
_COUNTER_NAME_RE = re.compile(
    r"^/[^{/]+\{locality#(\d+|\*)/[^}]+\}/[^{}]+$")

# registry entry points whose FIRST argument is a full counter name
_COUNTER_NAME_SINKS = {
    "register_counter", "unregister_counter", "query_counter",
    "query_counter_async", "parse_counter_name",
}

# helpers whose first two arguments are (object, counter) fragments
_COUNTER_FRAGMENT_SINKS = {"counter_name", "put"}


@register
class CounterNameDiscipline(Rule):
    """HPX016: counter names must parse against the registry grammar
    and histogram timers must not be silently dropped.  A counter
    name that fails ``/object{locality#N/instance}/counter`` raises
    only when the counter is first QUERIED — typically in a dashboard
    scrape long after the registering commit landed; and a bare
    ``h.record()`` statement mints a timing context manager and
    throws it away, recording nothing.  Fix: match the grammar
    (``performance_counters.counter_name`` builds it for you), and
    either pass ``record(value)`` or hold the timer in a ``with``."""

    id = "HPX016"
    name = "counter-name-discipline"
    severity = "error"

    @staticmethod
    def _literal_str(node: ast.AST) -> "str | None":
        if isinstance(node, ast.Constant) \
                and isinstance(node.value, str):
            return node.value
        return None

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.display_path.startswith("tests/") \
                or "/tests/" in ctx.display_path:
            return
        for node in ast.walk(ctx.tree):
            # dropped histogram timer: an expression STATEMENT whose
            # value is a no-arg .record() call
            if isinstance(node, ast.Expr) \
                    and isinstance(node.value, ast.Call) \
                    and isinstance(node.value.func, ast.Attribute) \
                    and node.value.func.attr == "record" \
                    and not node.value.args \
                    and not node.value.keywords:
                yield self.finding(
                    ctx, node,
                    "bare record() statement drops the timing "
                    "context manager without entering it — nothing "
                    "is recorded; pass record(value) or use "
                    "`with h.record():` around the timed region")
                continue
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            callee = (fn.attr if isinstance(fn, ast.Attribute)
                      else fn.id if isinstance(fn, ast.Name) else "")
            if callee in _COUNTER_NAME_SINKS and node.args:
                lit = self._literal_str(node.args[0])
                if lit is not None and lit.startswith("/") \
                        and not _COUNTER_NAME_RE.match(lit):
                    yield self.finding(
                        ctx, node,
                        f"counter name {lit!r} does not match "
                        "/object{locality#N/instance}/counter — it "
                        "registers silently and raises at first "
                        "query; build it with performance_counters."
                        "counter_name()")
            elif callee in _COUNTER_FRAGMENT_SINKS \
                    and len(node.args) >= 2:
                obj = self._literal_str(node.args[0])
                ctr = self._literal_str(node.args[1])
                if obj is not None and ctr is not None:
                    full = f"/{obj}{{locality#0/total}}/{ctr}"
                    if not _COUNTER_NAME_RE.match(full):
                        yield self.finding(
                            ctx, node,
                            f"counter fragments ({obj!r}, {ctr!r}) "
                            "assemble into a name that fails the "
                            "registry grammar /object{locality#N/"
                            "instance}/counter — it raises at first "
                            "query, not at registration")


@register
class ProgramCacheBypassRule(Rule):
    """HPX017: raw ``jax.jit`` in a models/ops hot path outside the
    profiled program-cache funnel.

    Every jit-program the serving stack builds flows through
    ``core.programs.cached_program`` (via a module's
    ``_cached_program`` / ``self._program`` wrapper) — the single
    funnel where the per-program profiler (``svc/progprof``)
    interposes to account compile wall time and the hold of each
    call.  A raw ``jax.jit(...)`` (or ``@jax.jit``
    decorator) in ``models/`` or ``ops/`` builds a program the
    profiler and the ``/programs{...}`` counters can never see — its
    compiles and calls vanish from the --metrics-out artifact and
    every flight bundle.  Fix: build the program inside a builder
    handed to ``cached_program()`` (or the module's wrapper); truly
    one-shot or demo programs get a baseline entry with justification.
    """

    id = "HPX017"
    name = "program-cache-bypass"
    severity = "warning"

    _SCOPE = ("hpx_tpu/models/", "hpx_tpu/ops/")
    _JITS = ("jax.jit", "jax.pjit")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath(*self._SCOPE):
            return

        # builders sanctioned by being handed to a program-cache
        # callee: lambdas passed directly in the argument list, plus
        # local functions referenced there by name
        sanctioned_lambdas: Set[int] = set()
        sanctioned_names: Set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            callee = (fn.attr if isinstance(fn, ast.Attribute)
                      else fn.id if isinstance(fn, ast.Name) else "")
            if callee not in _PROGRAM_CACHE_CALLEES:
                continue
            for arg in list(node.args) + \
                    [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    sanctioned_lambdas.add(id(arg))
                elif isinstance(arg, ast.Name):
                    sanctioned_names.add(arg.id)

        def is_jit(node: ast.AST) -> bool:
            return isinstance(node, (ast.Name, ast.Attribute)) and \
                ctx.resolve_call(node) in self._JITS

        out: List[Finding] = []

        def hit(node: ast.AST, scope: str) -> None:
            out.append(self.finding(
                ctx, node,
                f"raw jax.jit in {scope}() bypasses the profiled "
                "program cache — svc/progprof never sees its compile "
                "time or per-call cost; build it inside a "
                "core.programs.cached_program() builder, or baseline "
                "a genuinely one-shot program with a justification"))

        def walk(node: ast.AST, scope: str, ok: bool) -> None:
            for child in ast.iter_child_nodes(node):
                child_scope, child_ok = scope, ok
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    child_scope = child.name
                    child_ok = ok or child.name in sanctioned_names
                    for dec in child.decorator_list:
                        if not child_ok and is_jit(dec):
                            hit(dec, child.name)
                elif isinstance(child, ast.Lambda):
                    child_ok = ok or id(child) in sanctioned_lambdas
                if isinstance(child, ast.Call) and not child_ok \
                        and is_jit(child.func):
                    hit(child, child_scope)
                walk(child, child_scope, child_ok)

        walk(ctx.tree, "<module>", False)
        yield from out


# the instance attributes behind models/serving._RELOADABLE_KNOBS: the
# knobs a live server re-reads from the runtime config at its flush
# boundary.  Keyed attr -> backing config key so the finding names
# both.
_RELOADABLE_KNOB_ATTRS = {
    "prefill_chunk": "hpx.serving.prefill_chunk",
    "_max_async": "hpx.serving.max_async_steps",
    "_ckpt_every": "hpx.serving.ckpt_every",
    "_spec_k": "hpx.serving.spec.k",
    "_moe_capacity_pct": "hpx.serving.moe.capacity_factor",
    "budget_blocks": "hpx.cache.radix_budget_blocks",
    "budget_bytes": "hpx.cache.tier.host_budget_mb",
}

# the config actuation path: construction reads the config,
# _reload_knobs() applies operator config writes at the flush
# boundary.  Everything else must go through the runtime config.
_KNOB_SANCTIONED_FUNCS = {"__init__", "_reload_knobs"}


@register
class KnobMutationRule(Rule):
    """HPX018: direct mutation of a reloadable knob attribute outside
    the config actuation path.

    The serving knobs a live server re-reads (``prefill_chunk``,
    ``_max_async``, ``_ckpt_every``, ``_spec_k``,
    ``_moe_capacity_pct``, ``budget_blocks``, ``budget_bytes`` — the
    attributes behind ``models/serving._RELOADABLE_KNOBS``) change
    ONLY at the flush boundary, where no step is in flight:
    construction reads the config and ``_reload_knobs()`` applies
    operator config writes.  A write anywhere else can tear a
    dispatched program's geometry, and the live value no longer
    matches what the runtime config (and a flight bundle's config
    dump) says it is.  Fix: route the change through
    ``runtime_config().set(...)`` (picked up at the next flush).
    """

    id = "HPX018"
    name = "knob-mutation"
    severity = "warning"

    _SCOPE = ("hpx_tpu/models/", "hpx_tpu/svc/")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath(*self._SCOPE):
            return
        out: List[Finding] = []

        def walk(node: ast.AST, scope: str) -> None:
            for child in ast.iter_child_nodes(node):
                child_scope = scope
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    child_scope = child.name
                targets: List[ast.expr] = []
                if isinstance(child, ast.Assign):
                    targets = list(child.targets)
                elif isinstance(child, (ast.AugAssign, ast.AnnAssign)):
                    targets = [child.target]
                for t in targets:
                    if isinstance(t, ast.Attribute) \
                            and t.attr in _RELOADABLE_KNOB_ATTRS \
                            and child_scope not in _KNOB_SANCTIONED_FUNCS:
                        key = _RELOADABLE_KNOB_ATTRS[t.attr]
                        out.append(self.finding(
                            ctx, child,
                            f"direct write to reloadable knob "
                            f"attribute `{t.attr}` (backing {key}) in "
                            f"{child_scope}() bypasses the config "
                            "actuation path — it can land mid-step "
                            "and leaves the live value out of step "
                            "with the runtime config; route it "
                            "through runtime_config().set() (applied "
                            "by _reload_knobs at the next flush)"))
                walk(child, child_scope)

        walk(ctx.tree, "<module>")
        yield from out


# shape-ladder knobs with a resolver: explicit operator config, then
# the constant (for the block size: the measured table, then the
# constant).  Keyed param/kwarg name -> what a baked literal bypasses.
_SHAPE_KNOB_PARAMS = {
    "block_size": "hpx.cache.block_size, then "
                  "ops.attention_pallas.resolve_paged_block",
    "prefill_chunk": "hpx.serving.prefill_chunk",
    "prefill_buckets": "hpx.serving.prefill_buckets",
    "spec_k": "hpx.serving.spec.k",
    "page_size": "hpx.cache.block_size, then "
                 "ops.attention_pallas.resolve_paged_block",
}


def _is_shape_literal(node: ast.AST) -> bool:
    """A bare int literal, or a tuple/list of them (bucket ladders)."""
    if isinstance(node, ast.Constant):
        return type(node.value) is int
    if isinstance(node, (ast.Tuple, ast.List)) and node.elts:
        return all(isinstance(e, ast.Constant)
                   and type(e.value) is int for e in node.elts)
    return False


@register
class BakedShapeConstantRule(Rule):
    """HPX024: a shape-ladder knob (``block_size``, ``prefill_chunk``,
    ``prefill_buckets``, ``spec_k``, ``page_size``) baked to an int
    literal in a parameter default or call-site keyword inside
    ``models/``/``svc/``/``ops/``.

    These knobs are decided in ONE place each: explicit operator
    config (``hpx.serving.*``/``hpx.cache.*``), else the declared
    default (for the block size: ``resolve_paged_block``'s measured
    table, then 16).  A literal baked at a signature or call site
    silently pins the geometry for every caller: an operator's
    setting or a measured table entry never applies there, and two
    components can disagree about a shape they must share (a prefill
    worker emitting 16-row segments into a decode pool tuned to 32).
    Fix: default the parameter to ``None`` and resolve
    (``resolve_paged_block``, ``_resolve_buckets``), or thread
    the owning component's already-resolved value.  A deliberate bake
    (reference path, fixed-geometry kernel) carries ``# hpxlint:
    disable=HPX024 — <why>`` or a baseline entry with justification.
    """

    id = "HPX024"
    name = "baked-shape-constant"
    severity = "warning"

    _SCOPE = ("hpx_tpu/models/", "hpx_tpu/svc/", "hpx_tpu/ops/")

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if not ctx.in_subpath(*self._SCOPE):
            return
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                a = node.args
                pos = a.posonlyargs + a.args
                pairs = list(zip(pos[len(pos) - len(a.defaults):],
                                 a.defaults))
                pairs += [(p, d) for p, d in
                          zip(a.kwonlyargs, a.kw_defaults)
                          if d is not None]
                for param, default in pairs:
                    if param.arg in _SHAPE_KNOB_PARAMS \
                            and _is_shape_literal(default):
                        yield self.finding(
                            ctx, default,
                            f"parameter `{param.arg}` of "
                            f"{node.name}() bakes a shape constant "
                            "in its default — the resolver "
                            f"({_SHAPE_KNOB_PARAMS[param.arg]}) "
                            "never applies for callers that omit "
                            "it; default to None and resolve, or "
                            "thread the owner's resolved value")
            elif isinstance(node, ast.Call):
                for kw in node.keywords:
                    if kw.arg in _SHAPE_KNOB_PARAMS \
                            and _is_shape_literal(kw.value):
                        yield self.finding(
                            ctx, kw.value,
                            f"call-site keyword `{kw.arg}` bakes a "
                            "shape constant — it pins this "
                            "component's geometry against the "
                            "resolver "
                            f"({_SHAPE_KNOB_PARAMS[kw.arg]}); pass "
                            "the resolved value (or omit the "
                            "keyword and let the callee resolve)")
