"""TPU compute targets and the tpu_executor — the north-star device path.

Reference analog: libs/core/compute_local (hpx::compute::host::target,
block_executor) and libs/core/async_cuda (hpx::cuda::experimental::
cuda_executor whose async_execute launches a kernel and returns a future
completed by event polling integrated into the scheduler). Here the
"kernel launch" is an XLA program dispatch and the "event" is jax.Array
readiness.

Two completion models (hpx.tpu.eager_futures):

  eager (default): the returned future is READY immediately, holding the
    dispatched (possibly still-executing) jax.Array. JAX dispatch is
    asynchronous; downstream consumers that feed the array into further
    XLA programs get correct dataflow ordering from XLA itself, with zero
    host synchronization. This is the TPU-first answer to the task
    granularity chasm: the host races ahead, the device pipeline stays
    full. Materializing the value (np.asarray / block_until_ready) is the
    only synchronizing operation — exactly like .get() on an HPX future
    of GPU work.

  watched: the future completes only when the device result is actually
    ready (a watcher thread calls block_until_ready). Matches HPX
    semantics exactly (future ready == computation done) at the price of
    host round-trips; use for host-side control decisions on device data.

Error semantics (pinned by tests/test_executor_errors.py):
  * trace/compile failures -> exceptional future in BOTH modes
    (async_execute never leaks a raise).
  * device-side failures after a successful dispatch:
      watched — the watcher observes them; the future completes
      exceptionally and .get() raises (HPX contract).
      eager   — the future is already ready holding the in-flight
      array; the failure surfaces at the first MATERIALIZATION
      (np.asarray / block_until_ready / target.synchronize), NOT at
      .get(). This is the ONE deliberate divergence from HPX future
      semantics, the price of zero-sync dispatch — flip
      hpx.tpu.eager_futures=0 when exactness matters.
"""

from __future__ import annotations

import functools
import os
import queue as _queue
import threading
from typing import Any, Callable, List, Optional, Sequence

from ..core.config import runtime_config
from ..futures.future import (Future, SharedState, make_exceptional_future,
                              make_ready_future)
from .executors import BaseExecutor
from ..svc import tracing
from ..synchronization import Mutex


class Target:
    """A compute target = one addressable device (hpx::compute target).

    `synchronize()` is cuda::target::synchronize's analog.
    """

    def __init__(self, device: Any) -> None:
        self.device = device

    @property
    def platform(self) -> str:
        return self.device.platform

    @property
    def id(self) -> int:
        return self.device.id

    def synchronize(self) -> None:
        import jax
        # hpxlint: disable-next=HPX002 — synchronize() IS the
        # explicit fence API; blocking is its contract
        # Fence: a trivial computation placed on this device, blocked on.
        jax.block_until_ready(jax.device_put(0, self.device))

    def __repr__(self) -> str:
        return f"<Target {self.device}>"


@functools.lru_cache(maxsize=None)
def get_targets() -> tuple:
    """All device targets (hpx::compute::host::get_targets analog)."""
    import jax
    return tuple(Target(d) for d in jax.devices())


def default_target() -> Target:
    return get_targets()[0]


class _Watcher:
    """Completes futures when device values become ready.

    HPX integrates CUDA event polling into the scheduler loop; JAX has no
    public done-callback, so a small dedicated watcher pool calls
    block_until_ready off-thread (SURVEY.md §7 mitigation). Threads are
    started lazily and are daemons.
    """

    def __init__(self, num_threads: int) -> None:
        self._q: _queue.SimpleQueue = _queue.SimpleQueue()
        self._n = max(1, num_threads)
        self._started = False
        self._lock = Mutex()

    def _ensure_started(self) -> None:
        if self._started:
            return
        with self._lock:
            if self._started:
                return
            for i in range(self._n):
                threading.Thread(target=self._loop, daemon=True,
                                 name=f"hpx-tpu-watcher-{i}").start()
            self._started = True

    def _loop(self) -> None:
        import jax
        while True:
            state, value = self._q.get()
            try:
                # hpxlint: disable-next=HPX002 — the watcher thread
                # exists to absorb this block OFF the dispatch path (the
                # fix the rule suggests); this is that implementation
                jax.block_until_ready(value)
                state.set_value(value)
            except BaseException as e:  # noqa: BLE001 — device errors
                state.set_exception(e)

    def watch(self, value: Any) -> Future:
        self._ensure_started()
        state: SharedState = SharedState()
        self._q.put((state, value))
        return Future(state)


_watcher: Optional[_Watcher] = None
_watcher_lock = Mutex()


def _get_watcher() -> _Watcher:
    global _watcher
    if _watcher is None:
        with _watcher_lock:
            if _watcher is None:
                cfg = runtime_config()
                _watcher = _Watcher(cfg.get_int("hpx.tpu.watcher_threads", 2))
    return _watcher


def get_future(value: Any) -> Future:
    """Future tied to a dispatched jax value's completion
    (cuda_executor get_future(stream) analog)."""
    return _get_watcher().watch(value)


class TpuExecutor(BaseExecutor):
    """The device executor: async_execute dispatches a jitted XLA program.

    `par.on(TpuExecutor())` reroutes whole parallel algorithms onto the
    device (the executor/execution-policy plugin boundary is the only
    user-facing change — BASELINE.json north star).
    """

    import collections as _collections
    _jit_cache: "_collections.OrderedDict" = _collections.OrderedDict()
    _jit_cache_max = 4096
    _jit_lru: "_collections.OrderedDict" = _collections.OrderedDict()
    _jit_lru_max = 256
    # perf-counter feeds (class-level: all instances share the device
    # path). compile_count counts jit-wrapper cache misses — a proxy for
    # XLA compilations, which happen per (wrapper, shape) at first call.
    dispatch_count = 0
    compile_count = 0

    def __init__(self, target: Optional[Target] = None,
                 eager: Optional[bool] = None,
                 donate_argnums: tuple = ()) -> None:
        self.target = target if target is not None else default_target()
        if eager is None:
            eager = runtime_config().get_bool("hpx.tpu.eager_futures", True)
        self.eager = eager
        # donated positions alias into the outputs: callers must not
        # touch those bindings after dispatch (hpxlint HPX020 flags
        # use-after-donate through def-use chains)
        self._donate = donate_argnums

    # -- compilation --------------------------------------------------------
    def _compiled(self, fn: Callable[..., Any]) -> Callable[..., Any]:
        import jax
        from ..utils.fnkey import fn_cache_key
        # Structural key: algorithm call sites create fresh lambdas every
        # call; identity keying would re-jit (and re-compile the XLA
        # program) each time. Cache is class-level so short-lived executor
        # instances share compilations. Identity-keyed fallbacks (closures
        # capturing arrays etc.) go to a bounded LRU so they can't pin
        # captured data for the process lifetime.
        fkey = fn_cache_key(fn)
        key = (fkey, self._donate)
        if fkey is fn:  # identity fallback
            lru = TpuExecutor._jit_lru
            cached = lru.get(key)
            if cached is None:
                TpuExecutor.compile_count += 1
                cached = jax.jit(fn, donate_argnums=self._donate)
                lru[key] = cached
                if len(lru) > TpuExecutor._jit_lru_max:
                    lru.popitem(last=False)
            else:
                lru.move_to_end(key)
            return cached
        cache = TpuExecutor._jit_cache
        cached = cache.get(key)
        if cached is None:
            TpuExecutor.compile_count += 1
            cached = jax.jit(fn, donate_argnums=self._donate)
            cache[key] = cached
            # structural keys embed closure scalars, so loops over varying
            # captures (e.g. a learning-rate schedule) still create new
            # entries — bound this cache too
            if len(cache) > TpuExecutor._jit_cache_max:
                cache.pop(next(iter(cache)))
        else:
            cache.move_to_end(key)
        return cached

    # -- executor surface ----------------------------------------------------
    def post(self, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> None:
        # Raw call, NO jit: post is the generic fire-and-forget CPO that
        # async_/then/dataflow feed with arbitrary host callables (e.g.
        # _run_into closures) — jitting those is a type error. A jax fn
        # called raw still dispatches asynchronously. Use post_compiled
        # for an explicit compiled dispatch-and-forget.
        fn(*args, **kwargs)

    # `hpx.exec.dispatch` spans the compiled call alone: jit's own
    # overhead, or the host's wait on a full dispatch queue

    def post_compiled(self, fn: Callable[..., Any], *args: Any,
                      **kwargs: Any) -> None:
        TpuExecutor.dispatch_count += 1
        compiled = self._compiled(fn)
        with tracing.span("hpx.exec.dispatch", "hpx"):
            compiled(*args, **kwargs)

    def sync_execute(self, fn: Callable[..., Any], *args: Any,
                     **kwargs: Any) -> Any:
        import jax
        TpuExecutor.dispatch_count += 1
        compiled = self._compiled(fn)
        with tracing.span("hpx.exec.dispatch", "hpx"):
            value = compiled(*args, **kwargs)
        # hpxlint: disable-next=HPX002 — sync_execute()'s contract
        # is to block until the result is ready
        return jax.block_until_ready(value)

    def async_execute(self, fn: Callable[..., Any], *args: Any,
                      **kwargs: Any) -> Future:
        TpuExecutor.dispatch_count += 1
        try:
            compiled = self._compiled(fn)
            with tracing.span("hpx.exec.dispatch", "hpx"):
                value = compiled(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 — trace/compile errors
            return make_exceptional_future(e)
        if self.eager:
            return make_ready_future(value)
        return get_future(value)

    def async_execute_raw(self, fn: Callable[..., Any], *args: Any,
                          **kwargs: Any) -> Future:
        """Dispatch an already-compiled/arbitrary callable (no jit wrap)."""
        TpuExecutor.dispatch_count += 1
        try:
            with tracing.span("hpx.exec.dispatch", "hpx"):
                value = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001
            return make_exceptional_future(e)
        return make_ready_future(value) if self.eager else get_future(value)

    def then_execute(self, fn: Callable[..., Any], predecessor: Future,
                     *args: Any) -> Future:
        compiled = self._compiled(fn)
        if self.eager:
            return predecessor.then(lambda f: compiled(f.get(), *args))
        # watched mode: the continuation's future must complete only when
        # the device result is ready; then() unwraps the watcher future
        return predecessor.then(
            lambda f: get_future(compiled(f.get(), *args)))

    @property
    def num_workers(self) -> int:
        return 1  # one device; parallelism is inside the XLA program

    def __repr__(self) -> str:
        mode = "eager" if self.eager else "watched"
        return f"<TpuExecutor {self.target} {mode}>"
