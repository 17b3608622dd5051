"""Profiler bridge — the APEX / ITT-notify analog (SURVEY.md §5.1).

Reference analog: libs/core/itt_notify (VTune task annotations around
scheduler events) and the APEX `util::external_timer` callbacks fired at
task create/start/stop in libs/core/threading_base.

TPU-first: two planes —
  * device plane: jax.profiler traces (Perfetto/XPlane) via
    `profile_trace(logdir)` and `annotate(name)` (TraceAnnotation), which
    stamp host-side named ranges into the trace alongside XLA ops;
  * host plane: an external-timer registry; when enabled, the task pool
    invokes the registered callbacks at task submit/start/stop so an
    APEX-style tool (or the bundled TaskTimer) can build task statistics.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from ..synchronization import Mutex

# ---------------------------------------------------------------------------
# external-timer registry (APEX hook analog)
# ---------------------------------------------------------------------------

_hooks_lock = Mutex()
_hooks: List[Any] = []      # objects with optional on_submit/on_start/on_stop

# Observer callbacks must never break tasks, so their exceptions are
# swallowed — but SILENT swallowing makes a broken hook (a TaskTimer
# whose on_stop raises, a tracer bug) invisible forever. Every swallow
# increments this counter, exported as the
# /runtime{...}/count/dropped-observer-callbacks performance counter.
_dropped_lock = Mutex()
_dropped_callbacks = 0


def note_observer_error() -> None:
    """Record one swallowed observer exception (also called by the
    threadpool's own observer guards)."""
    global _dropped_callbacks
    with _dropped_lock:
        _dropped_callbacks += 1


def dropped_callbacks() -> int:
    """Observer callbacks dropped (exception swallowed) so far."""
    return _dropped_callbacks


def reset_dropped_callbacks() -> None:
    global _dropped_callbacks
    with _dropped_lock:
        _dropped_callbacks = 0


def register_external_timer(hook: Any) -> None:
    """hook may define on_submit(fn), on_start(fn), on_stop(fn, seconds)."""
    # toggle under the same lock as the list mutation: otherwise a
    # concurrent register/last-unregister pair can interleave so the
    # observer ends disabled while _hooks is non-empty
    with _hooks_lock:
        if hook not in _hooks:
            _hooks.append(hook)
        _set_pool_instrumentation(bool(_hooks))


def unregister_external_timer(hook: Any) -> None:
    with _hooks_lock:
        if hook in _hooks:
            _hooks.remove(hook)
        _set_pool_instrumentation(bool(_hooks))


def _emit(event: str, *args: Any) -> None:
    with _hooks_lock:
        hooks = list(_hooks)
    for h in hooks:
        cb = getattr(h, f"on_{event}", None)
        if cb is not None:
            try:
                cb(*args)
            except Exception:  # noqa: BLE001 — observers must not break tasks
                note_observer_error()


def _set_pool_instrumentation(enable: bool) -> None:
    from ..runtime import threadpool
    threadpool.set_task_observer(_task_observer if enable else None)


def _unwrap(fn: Callable, args: tuple) -> Callable:
    """Attribute time to the user function, not scheduling shims.

    futures' async_ submits `_run_into(state, fn, args, kwargs)`; other
    wrappers are reported as-is."""
    name = getattr(fn, "__name__", "")
    if name == "_run_into" and len(args) >= 2 and callable(args[1]):
        return args[1]
    return fn


def _task_observer(event: str, fn: Callable, dt: Optional[float],
                   args: tuple = ()) -> None:
    target = _unwrap(fn, args)
    if event == "stop":
        _emit("stop", target, dt)
    else:
        _emit(event, target)


class TaskTimer:
    """Bundled external timer: per-function task counts + total seconds."""

    def __init__(self) -> None:
        self._lock = Mutex()
        self.stats: Dict[str, list] = {}   # name -> [count, total_s]

    @staticmethod
    def _name(fn: Callable) -> str:
        return getattr(fn, "__qualname__", repr(fn))

    def on_stop(self, fn: Callable, seconds: float) -> None:
        name = self._name(fn)
        with self._lock:
            st = self.stats.setdefault(name, [0, 0.0])
            st[0] += 1
            st[1] += seconds

    def top(self, k: int = 10) -> List[tuple]:
        with self._lock:
            rows = [(name, c, t) for name, (c, t) in self.stats.items()]
        return sorted(rows, key=lambda r: -r[2])[:k]


@contextlib.contextmanager
def task_timing():
    """Scoped TaskTimer: `with task_timing() as t: ...; t.top()`."""
    t = TaskTimer()
    register_external_timer(t)
    try:
        yield t
    finally:
        unregister_external_timer(t)


# ---------------------------------------------------------------------------
# device-plane bridges (jax.profiler)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def profile_trace(logdir: str):
    """Capture a jax.profiler trace (view in Perfetto/TensorBoard).
    Every `tracing.span()` opened meanwhile lies in its host plane."""
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


#: Named range on the profiler's clock (itt task annotation analog),
#: a context manager, `annotate(name, **args)`: in a live
#: `jax.profiler` session a host event of the `.xplane.pb` with `args`
#: as its statistics; with no session a no-op in C++ that never reads
#: `args`. `tracing.span()` opens one for every span, so it is the
#: class itself and not a wrapper (a wrapper doubles the cost of the
#: untraced path). This module is the one place that touches
#: `jax.profiler`.
annotate = jax.profiler.TraceAnnotation


def device_memory_stats(device_index: int = 0) -> Dict[str, Any]:
    try:
        return dict(jax.devices()[device_index].memory_stats() or {})
    except Exception:  # noqa: BLE001
        return {}
