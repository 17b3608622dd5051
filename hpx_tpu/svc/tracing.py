"""Causal task tracer — ring-buffered spans with parentage across futures.

Reference analog: APEX's task-dependency capture over the HPX external
timer hooks (libs/core/threading_base fires task create/start/stop into
`util::external_timer`; APEX reconstructs the task DAG and emits OTF2 /
Google-trace timelines). Here the same hook plumbing
(`svc/profiling.register_external_timer`) feeds a :class:`Tracer` that
records, into a bounded drop-oldest ring:

  * B/E duration spans for every pool task (named via profiling's
    ``_unwrap`` attribution), every ``.then()`` continuation, and every
    explicitly annotated region (:func:`span`);
  * the CAUSAL parent of each span — the span that was live on the
    submitting thread when the work was scheduled — threaded through
    ``runtime/threadpool.py`` (a fourth task-tuple slot) and
    ``futures/future.py`` (continuation wrapping), so ``post``/
    ``async_`` fan-outs, ``.then()`` chains and ``when_all`` joins form
    a reconstructable DAG;
  * flow events (the Chrome ``s``/``f`` arrow pair) for every
    submit→run and future→continuation edge;
  * periodic performance-counter samples (``/serving``, ``/cache``,
    ``/threads`` queue depth, …) interleaved on the same timeline.

`svc/trace_export.py` turns the ring into Chrome trace-event JSON that
loads directly in ``chrome://tracing`` / Perfetto.

Two sinks, one entry point. Every :func:`span` is ALSO a
``jax.profiler.TraceAnnotation`` (through ``svc/profiling.annotate``),
so in any live profiler session (``profile_trace()``, TensorBoard, a
benchmark's traced run) the program's spans lie in the host plane of
the same ``.xplane.pb`` as the device ops, on its clock; the ring below
is the second sink, for the Chrome-trace export, and is OFF by default.

Cost when nothing records: a span is one ``TraceAnnotation`` that C++
turns into a no-op without reading its arguments (measured on the CPU,
jax 0.9.0: 0.5 us enter+exit, 0.9 us with three arguments; the shared
null span it replaced: 0.3 us, 0.4 us). Call sites therefore pass only
arguments that cost nothing to BUILD. The other instrumented hot paths
(pool submit, ``Future.then``, radix match) still pay one
module-global load plus an ``is None`` test when no tracer is active —
no allocation, no lock, no call. The ring
itself is append-only under the GIL (no lock on the event path); the
drop counter is best-effort under concurrent appends.

The step's account (:class:`StepAccount`) is the same design for the
serving host loop with the profiler OFF: the three choke points that
write the spans (`ContinuousServer.step()`, the wrapper `_program()`
hands out, `_wait()`) also add up, on ``time.perf_counter_ns()``, where
a step's wall went — held in dispatch calls, waiting on reads, the
host's own work, and how much of that in the decode step's operands —
beside
what only the process knows (CPU time, collector pauses). One
fixed-size record a step in a ring of 4,096; a SLOW step writes the
instant ``serving.slow_step`` and one `svc/flight` bundle. No key turns
it on or off: it costs a handful of clock reads a step.

Config (``core/config.py`` DEFAULTS, all under ``hpx.trace.*``)::

    hpx.trace.enabled          0        start_if_configured() gate
    hpx.trace.buffer_events    65536    ring capacity (drop-oldest)
    hpx.trace.counter_interval 0.05     seconds between counter samples
    hpx.trace.counters         /serving*,/cache*,/threads*,/programs*
"""

from __future__ import annotations

import contextlib
import gc
import itertools
import os
import statistics
import threading
import time
from collections import deque
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from .profiling import annotate as _annotate

__all__ = [
    "Tracer", "TaskCtx", "active_tracer", "start_tracing",
    "stop_tracing", "start_if_configured", "trace", "span", "instant",
    "current_span_id", "flow_begin", "flow_end",
    "mark", "StepAccount", "StepRecord",
]

# Ring entries are flat 8-tuples — the cheapest thing CPython can
# append — decoded only at export time:
#   (ph, name, cat, ts, tid, id, parent, args)
# ph: "B"/"E" span begin/end (id = span id), "i" instant,
#     "s"/"f" flow start/finish (id = flow id), "C" counter sample
#     (args = value).
_Event = Tuple[str, str, str, float, int, Optional[int], Optional[int],
               Any]


class TaskCtx:
    """Causal context captured on the submitting thread: the parent
    span id plus a pre-allocated flow-arrow id (None when the submit
    happened outside any span — there is no slice to anchor the
    arrow)."""

    __slots__ = ("parent", "flow", "name")

    def __init__(self, parent: Optional[int], flow: Optional[int],
                 name: str) -> None:
        self.parent = parent
        self.flow = flow
        self.name = name


class _NullSpan:
    """The shared no-op of :func:`null_span`: for instrumentation that
    writes to a ring of its own and to no other sink."""

    __slots__ = ()
    id = None

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """Context manager recording one B/E pair in the ring and the same
    range on the profiler's clock; nesting via the tracer's per-thread
    span stack gives the parent id."""

    __slots__ = ("_tr", "name", "cat", "args", "id", "_ann")

    def __init__(self, tr: "Tracer", name: str, cat: str,
                 args: Optional[dict]) -> None:
        self._tr = tr
        self.name = name
        self.cat = cat
        self.args = args
        self.id: Optional[int] = None

    def __enter__(self) -> "_Span":
        self.id = self._tr._begin(self.name, self.cat, self.args)
        self._ann = _annotate(self.name, **(self.args or {}))
        self._ann.__enter__()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self._ann.__exit__(*exc)
        self._tr._end(self.name, self.cat, self.id)
        return False


def _qualname(fn: Any) -> str:
    return getattr(fn, "__qualname__", None) or repr(fn)


class Tracer:
    """Lock-cheap ring-buffered event tracer.

    One instance is active process-wide (module slot ``_active``);
    :meth:`start` installs it into the external-timer registry (pool
    task spans), the threadpool submit capture (causal parents + flow
    arrows) and the future continuation hook, and starts the counter
    sampler; :meth:`stop` removes every hook. Recording methods are
    safe to call from any thread.
    """

    def __init__(self, capacity: int = 65536,
                 counter_interval: float = 0.05,
                 counter_patterns: Optional[List[str]] = None,
                 sample_counters: bool = True) -> None:
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2, got {capacity}")
        self.capacity = int(capacity)
        self._buf: deque = deque(maxlen=self.capacity)
        self.dropped = 0           # best-effort under concurrent appends
        self._ids = itertools.count(1)     # span AND flow ids (shared)
        self._tls = threading.local()
        self._threads: Dict[int, str] = {}   # ident -> thread name
        self.t0 = time.perf_counter()
        # wall anchor taken at the same instant as t0: trace_export
        # merge_traces aligns rings born at different times by shifting
        # each doc's monotonic timestamps with the wall-anchor delta
        self.t0_wall = time.time()
        self.counter_interval = float(counter_interval)
        self.counter_patterns = list(counter_patterns or [])
        self._sample_counters = bool(sample_counters)
        self._sampler_stop: Optional[threading.Event] = None
        self._sampler: Optional[threading.Thread] = None
        self._started = False

    # -- event path (hot; no locks) -------------------------------------

    def _record(self, ev: _Event) -> None:
        buf = self._buf
        if len(buf) == self.capacity:
            self.dropped += 1      # deque(maxlen) drops the oldest
        buf.append(ev)

    def _tid(self) -> int:
        ident = threading.get_ident()
        if ident not in self._threads:
            self._threads[ident] = threading.current_thread().name
        return ident

    def _stack(self) -> list:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def _begin(self, name: str, cat: str, args: Optional[dict],
               parent: Optional[int] = None,
               flow: Optional[int] = None,
               flow_name: str = "") -> int:
        st = self._stack()
        if parent is None and st:
            parent = st[-1]
        sid = next(self._ids)
        tid = self._tid()
        ts = time.perf_counter()
        self._record(("B", name, cat, ts, tid, sid, parent, args))
        if flow is not None:
            # the arrow head binds to the slice just opened (same ts)
            self._record(("f", flow_name or name, "flow", ts, tid,
                          flow, None, None))
        st.append(sid)
        return sid

    def _end(self, name: str, cat: str, sid: Optional[int]) -> None:
        if sid is None:
            return
        st = self._stack()
        if st:
            if st[-1] == sid:
                st.pop()
            elif sid in st:        # misnested exit: drop it anyway
                st.remove(sid)
        self._record(("E", name, cat, time.perf_counter(), self._tid(),
                      sid, None, None))

    # -- public recording API -------------------------------------------

    def span(self, name: str, cat: str = "user", **args: Any) -> _Span:
        """``with tracer.span("phase"): ...`` — records a B/E pair;
        nested spans parent automatically."""
        return _Span(self, name, cat, args or None)

    def instant(self, name: str, cat: str = "user", **args: Any) -> None:
        """Point event, parented to the enclosing span (if any)."""
        st = self._stack()
        parent = st[-1] if st else None
        self._record(("i", name, cat, time.perf_counter(), self._tid(),
                      None, parent, args or None))

    def counter(self, name: str, value: float) -> None:
        """One counter sample on the shared timeline."""
        self._record(("C", name, "counter", time.perf_counter(), 0,
                      None, None, float(value)))

    def current_span_id(self) -> Optional[int]:
        st = self._stack()
        return st[-1] if st else None

    def flow_begin(self, name: str, cat: str = "flow") -> Optional[int]:
        """Emit the source half of a flow arrow anchored at the
        current slice; returns the flow id for :meth:`flow_end`.
        Returns None outside any span (no slice to anchor to) — the
        export janitor would drop a danging arrow anyway."""
        st = self._stack()
        if not st:
            return None
        fid = next(self._ids)
        self._record(("s", name, cat, time.perf_counter(), self._tid(),
                      fid, None, None))
        return fid

    def flow_end(self, fid: Optional[int], name: str,
                 cat: str = "flow") -> None:
        """Bind the arrow head of flow `fid` to the current slice.
        No-op for fid None (flow_begin outside a span) — callers can
        thread the id through unconditionally."""
        if fid is None or not self._stack():
            return
        self._record(("f", name, cat, time.perf_counter(), self._tid(),
                      fid, None, None))

    # -- causal capture (submit side) -----------------------------------

    def capture(self, fn: Any = None, args: tuple = ()) -> Optional[TaskCtx]:
        """Called on the SUBMITTING thread (threadpool submit hook /
        ``Future.then``): snapshot the current span as the causal
        parent and emit the flow-arrow tail inside it. Returns None
        when no span is live — nothing to parent to."""
        st = self._stack()
        if not st:
            return None
        parent = st[-1]
        from .profiling import _unwrap
        name = _qualname(_unwrap(fn, args)) if fn is not None else "task"
        fid = next(self._ids)
        self._record(("s", name, "flow", time.perf_counter(),
                      self._tid(), fid, None, None))
        return TaskCtx(parent, fid, name)

    # -- external-timer hook (pool task spans) --------------------------
    # profiling._emit calls these with the _unwrap'ed user function.

    def on_start(self, fn: Any) -> None:
        ctx = getattr(self._tls, "pending", None)
        if ctx is not None:
            self._tls.pending = None
        self._begin(_qualname(fn), "task", None,
                    parent=ctx.parent if ctx else None,
                    flow=ctx.flow if ctx else None,
                    flow_name=ctx.name if ctx else "")

    def on_stop(self, fn: Any, seconds: float) -> None:
        st = self._stack()
        if not st:
            return                 # started before the tracer attached
        self._end(_qualname(fn), "task", st[-1])

    def _set_pending(self, ctx: Optional[TaskCtx]) -> None:
        """Worker side of the handoff: the threadpool parks the task's
        captured ctx here just before the start event fires."""
        self._tls.pending = ctx

    # -- continuation wrapping (futures side) ---------------------------

    def wrap_continuation(self, run: Any, user_fn: Any) -> Any:
        """Wrap a ``Future.then`` continuation so its execution records
        a span parented to the ATTACHING context with a flow arrow from
        the attach site to the run site."""
        ctx = self.capture(user_fn)
        name = f"then:{_qualname(user_fn)}"

        def traced(st: Any) -> None:
            tr = _active
            if tr is not self:     # tracer stopped in the meantime
                run(st)
                return
            sid = self._begin(name, "continuation", None,
                              parent=ctx.parent if ctx else None,
                              flow=ctx.flow if ctx else None,
                              flow_name=ctx.name if ctx else "")
            try:
                run(st)
            finally:
                self._end(name, "continuation", sid)
        return traced

    # -- counter sampler -------------------------------------------------

    def _sample_once(self) -> None:
        from .performance_counters import query_counters
        for pattern in self.counter_patterns:
            try:
                for name, cv in query_counters(pattern).items():
                    self.counter(name, cv.value)
            except Exception:  # noqa: BLE001 — sampling must never die
                pass

    def _sampler_loop(self, stop: threading.Event) -> None:
        while not stop.wait(self.counter_interval):
            self._sample_once()

    # -- lifecycle -------------------------------------------------------

    def start(self) -> "Tracer":
        """Install every hook; idempotent."""
        if self._started:
            return self
        self._started = True
        from . import profiling
        from ..futures import future as _future
        from ..runtime import threadpool as _tp
        # spans for pool tasks ride the EXISTING external-timer
        # plumbing (this also flips pool instrumentation on)
        profiling.register_external_timer(self)
        # causal parents + flow arrows need the submit-side capture
        _tp.set_trace_hooks(self.capture, self._set_pending)
        _future.set_trace_continuation_hook(self.wrap_continuation)
        if self._sample_counters and self.counter_patterns \
                and self.counter_interval > 0:
            self._sampler_stop = threading.Event()
            self._sampler = threading.Thread(
                target=self._sampler_loop, args=(self._sampler_stop,),
                name="hpx-trace-sampler", daemon=True)
            self._sampler.start()
        return self

    def stop(self) -> "Tracer":
        """Remove every hook and stop the sampler; the buffer stays
        readable (snapshot/export after stop is the normal flow)."""
        if not self._started:
            return self
        self._started = False
        from . import profiling
        from ..futures import future as _future
        from ..runtime import threadpool as _tp
        profiling.unregister_external_timer(self)
        _tp.set_trace_hooks(None, None)
        _future.set_trace_continuation_hook(None)
        if self._sampler_stop is not None:
            self._sampler_stop.set()
            self._sampler.join(timeout=2.0)
            self._sampler_stop = None
            self._sampler = None
            self._sample_once()    # one final sample closes the tracks
        return self

    # -- inspection / export ---------------------------------------------

    def snapshot(self) -> List[_Event]:
        """Copy of the ring in record order. Safe after stop(); under
        live concurrent appends the copy retries (deque iteration
        raises if mutated mid-copy)."""
        for _ in range(8):
            try:
                return list(self._buf)
            except RuntimeError:   # mutated during iteration
                continue
        return list(self._buf)     # last try propagates if still racing

    def thread_names(self) -> Dict[int, str]:
        return dict(self._threads)

    def export(self, path: str) -> dict:
        """Write Chrome trace-event JSON; returns the document."""
        from .trace_export import write_chrome_trace
        return write_chrome_trace(path, self)


# ---------------------------------------------------------------------------
# module-level active tracer + convenience API
# ---------------------------------------------------------------------------

_active: Optional[Tracer] = None


def active_tracer() -> Optional[Tracer]:
    """The live tracer, or None — the ONE check every instrumentation
    point makes before doing any work."""
    return _active


def current_span_id() -> Optional[int]:
    tr = _active
    return tr.current_span_id() if tr is not None else None


def start_tracing(capacity: Optional[int] = None,
                  counter_interval: Optional[float] = None,
                  counter_patterns: Optional[List[str]] = None,
                  sample_counters: bool = True) -> Tracer:
    """Create, install and return the process tracer. Defaults come
    from the ``hpx.trace.*`` config keys. Raises if one is active."""
    global _active
    if _active is not None:
        raise RuntimeError("tracing already active; stop_tracing() first")
    from ..core.config import runtime_config
    rc = runtime_config()
    if capacity is None:
        capacity = rc.get_int("hpx.trace.buffer_events", 65536)
    if counter_interval is None:
        counter_interval = rc.get_float("hpx.trace.counter_interval",
                                        0.05)
    if counter_patterns is None:
        raw = rc.get("hpx.trace.counters",
                     "/serving*,/cache*,/threads*,/programs*") or ""
        counter_patterns = [p.strip() for p in raw.split(",")
                            if p.strip()]
    tr = Tracer(capacity=capacity, counter_interval=counter_interval,
                counter_patterns=counter_patterns,
                sample_counters=sample_counters)
    _active = tr
    tr.start()
    return tr


def stop_tracing() -> Optional[Tracer]:
    """Stop and detach the active tracer (returned for export)."""
    global _active
    tr = _active
    _active = None
    if tr is not None:
        tr.stop()
    return tr


def start_if_configured() -> Optional[Tracer]:
    """Start tracing iff ``hpx.trace.enabled`` is truthy and no tracer
    is active — the config-gated entry point bench harnesses use."""
    from ..core.config import runtime_config
    if _active is not None:
        return _active
    if not runtime_config().get_bool("hpx.trace.enabled", False):
        return None
    return start_tracing()


@contextlib.contextmanager
def trace(capacity: Optional[int] = None,
          counter_interval: Optional[float] = None,
          counter_patterns: Optional[List[str]] = None,
          sample_counters: bool = True):
    """Scoped tracing: ``with trace() as tr: ...; tr.export(path)``."""
    tr = start_tracing(capacity, counter_interval, counter_patterns,
                       sample_counters)
    try:
        yield tr
    finally:
        stop_tracing()


def span(name: str, cat: str = "user", **args: Any):
    """Module-level span, the instrumentation call sites' single entry
    point: a range on the profiler's clock always (a no-op in C++
    unless a `jax.profiler` session is live), and a ring span too
    under an active tracer. `cat` goes to the ring alone."""
    tr = _active
    if tr is None:
        return _annotate(name, **args)
    return tr.span(name, cat, **args)


def null_span() -> _NullSpan:
    """The shared no-op span, for instrumentation that keeps its OWN
    ring (disagg worker rings) and needs the do-nothing branch when
    process tracing is off."""
    return _NULL_SPAN


def instant(name: str, cat: str = "user", **args: Any) -> None:
    """A point event in the ring (under an active tracer)."""
    tr = _active
    if tr is not None:
        tr.instant(name, cat, **args)


def mark(name: str, cat: str = "user", **args: Any) -> None:
    """A point event in BOTH sinks: the ring's instant, and an empty
    range on the profiler's clock (a live session shows it where it
    happened). For rare events; a hot path keeps :func:`instant`."""
    with _annotate(name, **args):
        pass
    instant(name, cat, **args)


def flow_begin(name: str, cat: str = "flow") -> Optional[int]:
    """Module-level flow-arrow tail: links the current slice to a later
    one across steps/threads (serving uses it to tie an admit span to
    the chunked-prefill spans it scheduled). None when tracing is off
    or no span is live; feed the result to :func:`flow_end` as-is."""
    tr = _active
    return tr.flow_begin(name, cat) if tr is not None else None


def flow_end(fid: Optional[int], name: str, cat: str = "flow") -> None:
    """Module-level flow-arrow head; no-op when off or fid is None."""
    tr = _active
    if tr is not None:
        tr.flow_end(fid, name, cat)


# ---------------------------------------------------------------------------
# the step's account: where a serving step's wall went, profiler off
# ---------------------------------------------------------------------------

# The yardstick is a BLOCK of 32 steps, not a step: with
# `hpx.serving.max_async_steps` steps buffered the host runs ahead of
# the device, most steps take 2-3 ms and the step that next blocks (a
# read, or an eager op on the full queue) rightly waits for every step
# the host was ahead (Kimi-Linear on the chip: a median step of 3.3 ms,
# chunk steps of 330 ms at the same step numbers in every run). A
# step's PACE is a 32nd of the running median block (the last 8), or
# of the 32 steps before it, less their longest, where those took
# longer.
# A step is SLOW where it took over 250 ms and over 4 paces for every
# program it had to wait behind (at least one): the decode steps the
# host was ahead when it began, and the prefill chunks enqueued since
# the last blocking read, its own included (DeepSeek-V2 admits up to
# four questions in a step, a chunk of 60 ms each; a loader's document
# of 10k-24k tokens is 38-92 chunks with nothing to read between them,
# and the step that ends it drains 0.5-1.0 s of them; 1.7-3.2 s since
# PR 40, whose host runs ~38 chunks ahead where it ran ~13). A block
# is slow where the last 32 steps together, less their longest, took
# over 2.5 median blocks, and all of them at least 1 s more than one
# (all 32 crawl: 94 ms a step would pass the first rule; one step that
# drains what it had queued is the first rule's to judge; a closed
# loop's burst of admissions, 1.4 s where 0.77 s is the median on
# StarCoder2-3B, is no crawl). In code, not in the config: an
# admission step with a 512-row chunk is 2.5 times a decode step and
# must not fire, and nobody should have to tune that.
SLOW_X_PACE = 4.0
SLOW_FLOOR_NS = 250_000_000
BLOCK_STEPS = 32
BLOCK_X_MEDIAN = 2.5
BLOCK_FLOOR_NS = 1_000_000_000
BLOCKS_OVER = 8             # blocks the running median block looks back
ACCOUNTS_KEPT = 4096
BUNDLE_EVERY_NS = 5_000_000_000
BUNDLE_HISTORY = 64         # accounts before the slow one, in a bundle

_now_ns = time.perf_counter_ns


class StepRecord(NamedTuple):
    """One step's account. Times in ns on ``time.perf_counter_ns``;
    `wall_ns` = `work_ns` + `held_ns` + `waited_ns`. What the process
    alone knows (CPU time, the collector) covers `gap_ns` + `wall_ns`:
    from the end of the step before to the end of this one, so that
    the accounts tile the host's time."""

    n: int                  # step() calls so far (`serving.step`'s n)
    end_ns: int
    wall_ns: int
    work_ns: int            # the host's own Python and transfers
    eager_ns: int           # ... of it in the decode step's operands
                            # section (`serving.decode.operands`: the
                            # table rebuild, the host-to-device
                            # arrays). The one place where step() once
                            # enqueued eager, unnamed programs; none
                            # since the seed token's pick and the
                            # per-slot vectors moved into `cb_probe`
                            # (the one-row probe of the last prompt
                            # position, one layer deep since PR 44)
    held_ns: int            # inside dispatch calls of named programs
    waited_ns: int          # inside blocking device->host reads
    gap_ns: int             # the caller's, since the step before ended
                            # (less the server's own reads and
                            # dispatches there: a `flush()`)
    top_prog: str           # the program whose call held longest
    top_held_ns: int
    dispatches: int
    reads_draining: int     # reads with no step queued behind them
    reads_overlapped: int
    lead: int               # decode steps dispatched and not yet read
                            # when the step began: the host's lead
    owed: int               # prefill chunks enqueued since the last
                            # read, this step's included
    admits: int
    chunks: int
    live: int               # live slots when the step ended
    compiles: int           # program-cache misses inside the step
    latent_entries: int     # table entries the decode step's latent
                            # walks covered, a latent layer each, ...
    latent_coalesced: int   # ... of them copied with their group's
                            # neighbours in one descriptor
    cpu_thread_ns: int      # time.thread_time_ns
    cpu_process_ns: int     # time.process_time_ns: every thread's
    gc_ns: int              # collector pauses
    gc_full: int            # ... of which full (generation 2) runs
    slow: str               # "", "step" or "block"

    @property
    def latent_run_pct(self) -> float:
        """The share of the latent walks' table entries that lay in a
        coalesced copy (0 where the step walked none)."""
        return 100.0 * self.latent_coalesced / self.latent_entries \
            if self.latent_entries else 0.0

    def blame(self) -> str:
        """The part of a slow step to look at first: `held` (in
        `top_prog`'s call), `waited`, `caller` (the gap before the
        step), or the host's own work, told apart as `collector`
        (pauses make half of it), `eager` (half of it lies in the
        decode step's operands: a transfer that meets a full queue
        holds the host THERE as a named program's call does, and so
        would an eager op, should one come back), `off_cpu` (the
        thread's CPU time, gap and all, is under half of it: it
        slept, was switched out or faulted, or a transfer outside that
        section met a full queue) or `computing`."""
        part, ns = max((("held", self.held_ns), ("waited", self.waited_ns),
                        ("work", self.work_ns), ("caller", self.gap_ns)),
                       key=lambda kv: kv[1])
        if part != "work":
            return part
        if 2 * self.gc_ns >= ns:
            return "collector"
        if 2 * self.eager_ns >= ns:
            return "eager"
        return "off_cpu" if 2 * self.cpu_thread_ns < ns else "computing"


# what a slow block sums over its steps' records
_SUMMED = tuple(f for f in StepRecord._fields if f not in (
    "n", "end_ns", "top_prog", "top_held_ns", "lead", "owed", "live",
    "slow"))


class _GcClock:
    """The collector's pauses, summed by one ``gc.callbacks`` entry
    installed once a process (an account reads the sums' growth)."""

    def __init__(self) -> None:
        self.ns = self.full = self._t0 = 0
        self._installed = False

    def install(self) -> None:
        if not self._installed:
            self._installed = True
            gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t0 = _now_ns()
        else:
            self.ns += _now_ns() - self._t0
            self.full += info.get("generation") == 2


_GC = _GcClock()


class StepAccount:
    """Where each step's wall went, for ONE server: `begin()` and
    `end()` from `step()`, `dispatched()` after every named program's
    call, `waited_ns` grown by every blocking read and `eager_ns` by
    the decode step's operands section. The last `ACCOUNTS_KEPT`
    records stay in a ring (`records()`); a slow step (or one that
    ends a slow block of 32) is counted (`slow`, `slow_ns`), marked in
    both trace sinks, logged once and handed to the flight recorder
    (one bundle in 5 s). Reporting never raises into the step
    (`dropped` counts the reports that failed)."""

    def __init__(self) -> None:
        _GC.install()
        self._ring: deque = deque(maxlen=ACCOUNTS_KEPT)
        self._block: deque = deque(maxlen=BLOCK_STEPS)
        self._block_ns = 0
        self._blocks: deque = deque(maxlen=BLOCKS_OVER)
        self._median_block = self._steps = self._lead = self._owed = 0
        self._between = 0
        self._quiet = 0             # steps since the last slow verdict
        self._bundle_ns = -BUNDLE_EVERY_NS
        self.slow = 0               # /serving{...}/steps/slow
        self.slow_ns = 0            # ... /steps/slow-seconds
        self.dropped = 0
        self._t0 = 0
        self._end: Optional[int] = None
        self._seen: Tuple[int, ...] = ()
        self.held_ns = self.waited_ns = self.eager_ns = self.dispatches = 0
        self.top_prog, self.top_ns = "", 0

    # -- the choke points (hot: no allocation) --------------------------

    def begin(self, lead: int = 0) -> None:
        self._lead = lead
        if self._end is None:       # the first step: nothing before it
            self._seen = (time.thread_time_ns(), time.process_time_ns(),
                          _GC.ns, _GC.full, 0, 0, 0, 0, 0, 0, 0)
            self._end = _now_ns()
        # what the choke points saw since end(): the server's own reads
        # and dispatches BETWEEN steps (a caller's `flush()`), not the
        # caller's time
        self._between = self.held_ns + self.waited_ns
        self.held_ns = self.waited_ns = self.eager_ns = self.dispatches = 0
        self.top_prog, self.top_ns = "", 0
        self._t0 = _now_ns()

    def work_clock(self) -> int:
        """A clock of the host's own work: it stands still inside
        dispatch calls and reads (`eager_ns` is a difference of two)."""
        return _now_ns() - self.held_ns - self.waited_ns

    def dispatched(self, prog: str, ns: int) -> None:
        self.held_ns += ns
        self.dispatches += 1
        if ns > self.top_ns:
            self.top_prog, self.top_ns = prog, ns

    def end(self, n: int, live: int, admits: int = 0, chunks: int = 0,
            compiles: int = 0, draining: int = 0, overlapped: int = 0,
            latent: int = 0, coalesced: int = 0) -> StepRecord:
        """Close the step's record. `admits` .. `coalesced`: the
        server's running totals of admissions, chunks, program-cache
        misses, reads (draining, overlapped) and the latent walks'
        table entries (all, coalesced); like the process's
        clocks they are read once, here, and a record holds their
        growth since the record before."""
        now = _now_ns()
        wall, gap = now - self._t0, self._t0 - self._end - self._between
        cpu, cpu_all, gc_ns, gc_full, admits0, chunks0, compiles0, \
            draining0, overlapped0, latent0, coalesced0 = self._seen
        self._seen = seen = (
            time.thread_time_ns(), time.process_time_ns(), _GC.ns,
            _GC.full, admits, chunks, compiles, draining, overlapped,
            latent, coalesced)
        self._end = now
        chunks -= chunks0
        compiles -= compiles0
        draining -= draining0
        overlapped -= overlapped0
        # a read leaves nothing enqueued before it unfinished
        owed = self._owed + chunks
        self._owed = 0 if draining or overlapped else owed
        # a step that follows live slots owes its caller's gap too (the
        # operator's `decode_stall`); an idle server's gap is nobody's
        spent = wall + (gap if self._ring and self._ring[-1].live else 0)
        # a step that built a program is slow for a reason the
        # programs/cache-misses counter already gives: not judged, and
        # kept out of the median and the block
        slow = "" if compiles else self._verdict(spent, self._lead + owed)
        rec = StepRecord(
            n, now, wall, wall - self.held_ns - self.waited_ns,
            self.eager_ns, self.held_ns, self.waited_ns, gap,
            self.top_prog, self.top_ns, self.dispatches, draining,
            overlapped, self._lead, owed, admits - admits0, chunks, live,
            compiles, latent - latent0, coalesced - coalesced0,
            seen[0] - cpu, seen[1] - cpu_all, seen[2] - gc_ns,
            seen[3] - gc_full, slow)
        self._ring.append(rec)
        self.held_ns = self.waited_ns = 0
        if slow:
            try:
                self._report(rec, spent)
            except Exception:  # noqa: BLE001 — telemetry never raises
                self.dropped += 1       # into the serving loop
        return rec

    # -- the verdict ----------------------------------------------------

    def _verdict(self, spent: int, queued: int = 0) -> str:
        """`queued`: the decode steps the host was ahead plus the
        chunks enqueued since the last read: what the step may rightly
        wait behind."""
        block = self._block
        verdict, median = "", self._median_block
        held_to = median
        if spent > SLOW_FLOOR_NS and block:
            # ... or the 32 steps before this one, less their longest:
            # where the host runs deeper ahead than a block is long (a
            # loader's document: 38 chunks enqueued in 0.9 ms each,
            # then each held for one chunk's time), the median block
            # is all run-ahead and says nothing of the device's pace;
            # the steps just before the drain do
            held_to = max(median, self._block_ns - max(block))
        if len(block) == BLOCK_STEPS:
            self._block_ns -= block[0]
        block.append(spent)
        self._block_ns += spent
        self._steps += 1
        self._quiet += 1
        if not median:
            pass                    # no block to hold anything against
        elif spent > SLOW_FLOOR_NS and spent > (
                SLOW_X_PACE * max(1, queued) * held_to / BLOCK_STEPS):
            verdict = "step"
        elif self._quiet >= BLOCK_STEPS and len(self._blocks) > 1 \
                and self._block_ns - max(block) > BLOCK_X_MEDIAN * median \
                and self._block_ns - median >= BLOCK_FLOOR_NS:
            # less its longest step: ONE step that drains what it had
            # queued is the first rule's to judge, a block crawls
            verdict = "block"
        if verdict:
            self._quiet = 0
        if self._steps % BLOCK_STEPS == 0:
            # one more whole block; the median of the blocks BEFORE it
            # judged its steps
            self._blocks.append(self._block_ns)
            self._median_block = int(statistics.median(self._blocks))
        return verdict

    def _report(self, rec: StepRecord, spent: int) -> None:
        pace, ring = self._median_block // BLOCK_STEPS, list(self._ring)
        if rec.slow == "step":
            over, whole = spent - pace, rec
        else:
            # a slow block is judged, and blamed, as the sum of its steps
            over = self._block_ns - self._median_block
            last = ring[-BLOCK_STEPS:]
            top = max(last, key=lambda r: r.top_held_ns)
            whole = rec._replace(
                top_prog=top.top_prog, top_held_ns=top.top_held_ns,
                **{f: sum(getattr(r, f) for r in last) for f in _SUMMED})
        self.slow += 1
        self.slow_ns += over
        blame = whole.blame()
        mark("serving.slow_step", "serving", n=rec.n, kind=rec.slow,
             blame=blame, ms=spent // 1_000_000)
        if rec.end_ns - self._bundle_ns < BUNDLE_EVERY_NS:
            return
        self._bundle_ns = rec.end_ns
        from . import flight
        from .logging import get_logger
        extra = {
            "blame": blame, "kind": rec.slow, "fields": StepRecord._fields,
            "pace_ms": pace / 1e6, "over_ms": over / 1e6,
            "median_block_ms": self._median_block / 1e6,
            "block_ms": self._block_ns / 1e6,
            "slow": rec._asdict(), "block": whole._asdict(),
            "before": [list(r) for r in ring[-BUNDLE_HISTORY - 1:-1]],
            "cores": (len(os.sched_getaffinity(0))
                      if hasattr(os, "sched_getaffinity")
                      else os.cpu_count()),
        }
        path = flight.record_fault("slow_step", site="serving",
                                   extra=extra)
        get_logger("serving").warning(
            "slow step n=%d (%s): %.0f ms where a step's pace is %.1f ms "
            "behind %d queued program(s); look at %s first (held "
            "%.0f ms, longest in %r; waited %.0f; work %.0f, of it in "
            "the decode operands %.0f; caller %.0f; collector %.0f; "
            "thread on a core %.0f); bundle %s", rec.n, rec.slow,
            (spent if whole is rec else self._block_ns) / 1e6, pace / 1e6,
            rec.lead + rec.owed, blame, whole.held_ns / 1e6,
            whole.top_prog, whole.waited_ns / 1e6, whole.work_ns / 1e6,
            whole.eager_ns / 1e6, whole.gap_ns / 1e6, whole.gc_ns / 1e6,
            whole.cpu_thread_ns / 1e6, path)

    # -- reading ----------------------------------------------------------

    def records(self) -> List[StepRecord]:
        return list(self._ring)
