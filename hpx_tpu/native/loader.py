"""ctypes binding for the native runtime core (libhpx_tpu_rt.so).

Builds the shared library on first use if g++ is available (no pybind11 in
this environment — plain C ABI + ctypes, per the project's binding policy).
Falls back cleanly: callers must handle native_lib() returning None and use
the pure-Python implementations.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Any, Callable, Dict, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_HERE, "libhpx_tpu_rt.so")

_lib: Optional[ctypes.CDLL] = None
_lib_tried = False
_lib_lock = threading.Lock()

# live NativePool instances, for the perf-counter registry (weak: a
# pool's lifetime is owned by its creator, not by observability).
# WeakSet is NOT thread-safe — all access under _pools_lock (counter
# threads snapshot while constructors add).
import weakref

_live_pools: "weakref.WeakSet" = weakref.WeakSet()
_pools_lock = threading.Lock()


def live_native_pools():
    """Snapshot of live NativePool instances (perf-counter discovery)."""
    with _pools_lock:
        pools = list(_live_pools)
    return [p for p in pools if not p._shut]


def _find_pool(name: str):
    with _pools_lock:
        pools = list(_live_pools)
    for p in pools:
        if p.name == name and not p._shut:
            return p
    return None


def native_pool_stat(name: str, key: str) -> float:
    """Counter feed, resolved by pool NAME at call time: a recreated
    same-name pool is picked up automatically, and a dead pool reads 0
    (no stale-instance weakrefs)."""
    p = _find_pool(name)
    if p is None:
        return 0.0
    return float(p.stats().get(key, 0))


def native_pool_queue_len(name: str, wid: int) -> int:
    """Per-worker queue depth by pool name (0 when absent/shut/out of
    range — a recreated pool may have fewer workers)."""
    p = _find_pool(name)
    return 0 if p is None else p.queue_length(wid)

_TASK_FN = ctypes.CFUNCTYPE(None, ctypes.c_size_t)


def _build() -> bool:
    try:
        subprocess.run(["make", "-C", _HERE], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_SO)
    except Exception:
        return False


def native_lib() -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native library; None if unavailable."""
    global _lib, _lib_tried
    if _lib is not None or _lib_tried:
        return _lib
    with _lib_lock:
        if _lib is not None or _lib_tried:
            return _lib
        _lib_tried = True
        # Always invoke make (it is incremental): a stale prebuilt .so —
        # the .so is gitignored, sources are not — would otherwise be
        # loaded and fail symbol binding after a source update. Where
        # make FAILS, whatever .so lies there was not built from these
        # sources: run the pure-Python scheduler instead of loading it.
        if not _build():
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None
        lib.hpxrt_pool_create.restype = ctypes.c_void_p
        lib.hpxrt_pool_create.argtypes = [ctypes.c_int]
        lib.hpxrt_pool_submit.argtypes = [ctypes.c_void_p, _TASK_FN,
                                          ctypes.c_size_t]
        if hasattr(lib, "hpxrt_pool_submit_many"):
            # probe, not hard bind: a stale prebuilt .so (copied between
            # checkouts) lacks the symbol; NativePool then falls back to
            # per-task submits
            lib.hpxrt_pool_submit_many.argtypes = [
                ctypes.c_void_p, _TASK_FN, ctypes.c_size_t, ctypes.c_int]
        lib.hpxrt_pool_help_one.restype = ctypes.c_int
        lib.hpxrt_pool_help_one.argtypes = [ctypes.c_void_p]
        lib.hpxrt_pool_in_worker.restype = ctypes.c_int
        lib.hpxrt_pool_in_worker.argtypes = [ctypes.c_void_p]
        lib.hpxrt_pool_shutdown.argtypes = [ctypes.c_void_p]
        lib.hpxrt_pool_executed.restype = ctypes.c_uint64
        lib.hpxrt_pool_executed.argtypes = [ctypes.c_void_p]
        lib.hpxrt_pool_stolen.restype = ctypes.c_uint64
        lib.hpxrt_pool_stolen.argtypes = [ctypes.c_void_p]
        lib.hpxrt_pool_pending.restype = ctypes.c_long
        lib.hpxrt_pool_pending.argtypes = [ctypes.c_void_p]
        if hasattr(lib, "hpxrt_pool_queue_len"):   # stale-.so tolerant
            lib.hpxrt_pool_queue_len.restype = ctypes.c_long
            lib.hpxrt_pool_queue_len.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_int]
        if hasattr(lib, "hpxrt_pool_idle"):
            lib.hpxrt_pool_idle.restype = ctypes.c_int
            lib.hpxrt_pool_idle.argtypes = [ctypes.c_void_p]
        lib.hpxrt_now_ns.restype = ctypes.c_uint64
        lib.hpxrt_counter_new.restype = ctypes.c_void_p
        lib.hpxrt_counter_add.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.hpxrt_counter_get.restype = ctypes.c_int64
        lib.hpxrt_counter_get.argtypes = [ctypes.c_void_p]
        lib.hpxrt_counter_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def now_ns() -> int:
    lib = native_lib()
    if lib is not None:
        return lib.hpxrt_now_ns()
    import time
    return time.monotonic_ns()


class NativePool:
    """Work-stealing pool backed by C++ threads.

    Python tasks are kept in an id-keyed registry; a single CFUNCTYPE
    trampoline (which re-acquires the GIL) dispatches by id. Conforms to
    the same interface as runtime.threadpool.WorkStealingPool so futures'
    work-helping treats both uniformly.
    """

    def __init__(self, num_threads: int, name: str = "native") -> None:
        lib = native_lib()
        if lib is None:
            raise RuntimeError("native runtime library unavailable")
        self._lib = lib
        self.name = name
        self._n = max(1, num_threads)
        self._handle = lib.hpxrt_pool_create(self._n)
        self._tasks: Dict[int, tuple] = {}
        self._tasks_lock = threading.Lock()
        self._next_id = 0
        self._shut = False
        self._shutdown_lock = threading.Lock()
        self._last_stats = {"executed": 0, "stolen": 0, "pending": 0,
                            "threads": self._n}

        # The trampoline must outlive every submitted task — bind it to the
        # instance so ctypes keeps the closure alive.
        def _tramp(arg: int) -> None:
            from ..runtime.threadpool import _worker_of
            if getattr(_worker_of, "pool", None) is None and \
                    self._lib.hpxrt_pool_in_worker(self._handle):
                _worker_of.pool = self  # register for future work-helping
            with self._tasks_lock:
                task = self._tasks.pop(arg, None)
            if task is None:
                return
            fn, args, kwargs = task
            from ..runtime import threadpool as _tp
            obs = _tp._task_observer
            if obs is not None:
                import time as _time
                try:  # observers must never break tasks or kill workers
                    obs("start", fn, None, args)
                except BaseException:  # noqa: BLE001
                    pass
                t0 = _time.monotonic()
            try:
                fn(*args, **kwargs)
            except BaseException:  # noqa: BLE001 — mirror Python pool
                import traceback
                traceback.print_exc()
            if obs is not None:
                try:
                    obs("stop", fn, _time.monotonic() - t0, args)
                except BaseException:  # noqa: BLE001
                    pass

        self._tramp = _TASK_FN(_tramp)
        with _pools_lock:
            _live_pools.add(self)

    @property
    def num_threads(self) -> int:
        return self._n

    def queue_length(self, wid: int) -> int:
        """ONE worker's queue depth (lock-free deque + staged inbox);
        0 after shutdown or out of range. Counter feed only — the C
        read is racy by design, and the shutdown lock pins the handle
        against the free in shutdown() (counters poll from arbitrary
        threads)."""
        with self._shutdown_lock:
            if self._shut or \
                    not hasattr(self._lib, "hpxrt_pool_queue_len"):
                return 0
            return max(0, int(self._lib.hpxrt_pool_queue_len(
                self._handle, wid)))

    def queue_lengths(self) -> list:
        return [self.queue_length(i) for i in range(self._n)]

    def submit(self, fn: Callable[..., Any], *args: Any, **kwargs: Any) -> None:
        if self._shut:  # the C++ pool was freed; a call would be UAF
            from ..core.errors import Error, HpxError
            raise HpxError(Error.invalid_status, "pool is shut down")
        from ..runtime.threadpool import notify_submit
        notify_submit([(fn, args)])
        with self._tasks_lock:
            tid = self._next_id
            self._next_id += 1
            self._tasks[tid] = (fn, args, kwargs)
        self._lib.hpxrt_pool_submit(self._handle, self._tramp, tid)

    def submit_many(self, tasks) -> None:
        """Batch fire-and-forget: `tasks` is a sequence of
        (fn, args, kwargs) triples, registered under contiguous ids with
        ONE lock acquisition and handed to the scheduler with ONE C
        call (hpxrt_pool_submit_many) — the fan-out path that amortizes
        the per-task interpreter/ABI overhead."""
        if self._shut:
            from ..core.errors import Error, HpxError
            raise HpxError(Error.invalid_status, "pool is shut down")
        tasks = list(tasks)
        if not tasks:
            return
        if not hasattr(self._lib, "hpxrt_pool_submit_many"):
            for fn, args, kwargs in tasks:       # stale .so fallback
                self.submit(fn, *args, **kwargs)
            return
        from ..runtime.threadpool import notify_submit
        notify_submit((fn, args) for fn, args, _ in tasks)
        with self._tasks_lock:
            start = self._next_id
            self._next_id += len(tasks)
            for i, t in enumerate(tasks):
                self._tasks[start + i] = t
        self._lib.hpxrt_pool_submit_many(self._handle, self._tramp,
                                         start, len(tasks))

    def help_one(self) -> bool:
        if self._shut:
            return False
        # depth-bounded like the Python pool: every nested help crosses
        # the C stack through the ctypes trampoline, so unbounded
        # nesting overflows long before Python's recursion limit
        from ..runtime.threadpool import enter_help, exit_help
        if not enter_help():
            return False
        try:
            return bool(self._lib.hpxrt_pool_help_one(self._handle))
        finally:
            exit_help()

    def in_worker(self) -> bool:
        if self._shut:
            return False
        return bool(self._lib.hpxrt_pool_in_worker(self._handle))

    def _stats_locked(self) -> dict:
        """Caller holds _shutdown_lock (or is shutdown() itself)."""
        if self._shut:
            return dict(self._last_stats, shutdown=True)
        self._last_stats = {
            "executed": int(self._lib.hpxrt_pool_executed(self._handle)),
            "stolen": int(self._lib.hpxrt_pool_stolen(self._handle)),
            "pending": int(self._lib.hpxrt_pool_pending(self._handle)),
            "threads": self._n,
        }
        if hasattr(self._lib, "hpxrt_pool_idle"):
            self._last_stats["idle"] = int(
                self._lib.hpxrt_pool_idle(self._handle))
        return self._last_stats

    def stats(self) -> dict:
        # under the shutdown lock: counter callbacks poll stats() from
        # arbitrary threads, and an unlocked read could dereference the
        # C++ pool mid-free (same hazard queue_length documents)
        with self._shutdown_lock:
            return self._stats_locked()

    def shutdown(self, wait: bool = True) -> None:
        # wait is accepted for interface parity with WorkStealingPool;
        # the native pool always joins its workers before freeing.
        if self._shut:
            return
        if self._handle is not None and self.in_worker():
            # a pool cannot join itself: pthread_join(self) aborts the
            # process. Hand the join to a fresh thread (continuations
            # commonly fire on the last worker that completed a future).
            import threading as _t
            _t.Thread(target=self.shutdown, name="pool-reaper",
                      daemon=True).start()
            return
        # the reaper hand-off means concurrent shutdown callers are
        # expected (reaper + atexit/__del__): serialize the
        # check-then-free so the native shutdown runs exactly once.
        # The lock covers ONLY the state flip — holding it across the
        # C++ join would deadlock any pool TASK that reads stats()
        # (worker blocks on the lock, join waits for the worker).
        with self._shutdown_lock:
            if self._shut:
                return
            self._stats_locked()  # snapshot final counters (lock held)
            self._shut = True
            handle, self._handle = self._handle, None
        # workers in _worker_of must not help a dead pool; stats/
        # queue_length callers now see _shut and never touch `handle`
        self._lib.hpxrt_pool_shutdown(handle)

    def __del__(self) -> None:  # best-effort; explicit shutdown preferred
        try:
            self.shutdown()
        except Exception:
            pass


# -- Chase-Lev lock-free deque binding --------------------------------------

def _bind_cldeque(lib: ctypes.CDLL) -> None:
    if getattr(lib, "_cld_bound", False):
        return
    for sym in ("hpxrt_cldeque_create", "hpxrt_cldeque_push",
                "hpxrt_cldeque_take", "hpxrt_cldeque_steal",
                "hpxrt_cldeque_size", "hpxrt_cldeque_destroy"):
        if not hasattr(lib, sym):
            raise RuntimeError(
                f"libhpx_tpu_rt.so is stale (missing symbol {sym}); "
                f"rebuild it: make -C {_HERE} clean && make -C {_HERE}")
    lib.hpxrt_cldeque_create.restype = ctypes.c_void_p
    lib.hpxrt_cldeque_push.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
    lib.hpxrt_cldeque_take.restype = ctypes.c_void_p
    lib.hpxrt_cldeque_take.argtypes = [ctypes.c_void_p]
    lib.hpxrt_cldeque_steal.restype = ctypes.c_void_p
    lib.hpxrt_cldeque_steal.argtypes = [ctypes.c_void_p]
    lib.hpxrt_cldeque_size.restype = ctypes.c_long
    lib.hpxrt_cldeque_size.argtypes = [ctypes.c_void_p]
    lib.hpxrt_cldeque_destroy.argtypes = [ctypes.c_void_p]
    lib._cld_bound = True


class ChaseLevDeque:
    """Lock-free work-stealing deque of nonzero ints (C Chase-Lev).

    push()/take() are OWNER-thread operations; steal() may be called
    from any thread (ctypes releases the GIL during the call, so Python
    threads genuinely race the lock-free C code). Items are opaque
    pointer-sized nonzero ints — 0 means empty.
    """

    def __init__(self) -> None:
        lib = native_lib()
        if lib is None:
            raise RuntimeError("native runtime library unavailable")
        _bind_cldeque(lib)
        self._lib = lib
        self._h = lib.hpxrt_cldeque_create()
        # close() must not free the C object under a thread that is
        # INSIDE a (GIL-released) deque call: ops register in-flight
        # around the call — the C calls themselves still race lock-free
        # — and close waits for quiescence before destroying.
        self._cv = threading.Condition()
        self._inflight = 0

    def _enter(self):
        with self._cv:
            if self._h is None:
                raise RuntimeError("deque is closed")
            self._inflight += 1
            return self._h

    def _exit(self) -> None:
        with self._cv:
            self._inflight -= 1
            if self._inflight == 0:
                self._cv.notify_all()

    def push(self, item: int) -> None:
        if item == 0:
            raise ValueError("0 is the empty sentinel")
        h = self._enter()
        try:
            self._lib.hpxrt_cldeque_push(h, item)
        finally:
            self._exit()

    def take(self) -> Optional[int]:
        h = self._enter()
        try:
            v = self._lib.hpxrt_cldeque_take(h)
        finally:
            self._exit()
        return None if not v else int(v)

    def steal(self) -> Optional[int]:
        h = self._enter()
        try:
            v = self._lib.hpxrt_cldeque_steal(h)
        finally:
            self._exit()
        return None if not v else int(v)

    def __len__(self) -> int:
        h = self._enter()
        try:
            return int(self._lib.hpxrt_cldeque_size(h))
        finally:
            self._exit()

    def close(self) -> None:
        with self._cv:
            if self._h is None:
                return
            self._cv.wait_for(lambda: self._inflight == 0)
            h, self._h = self._h, None
        self._lib.hpxrt_cldeque_destroy(h)

    def __del__(self) -> None:
        try:
            self.close()
        except Exception:
            pass


# -- TCP parcel transport binding -------------------------------------------

_NET_CB = ctypes.CFUNCTYPE(None, ctypes.c_void_p, ctypes.c_int,
                           ctypes.POINTER(ctypes.c_uint8), ctypes.c_uint64)


def _bind_net(lib: ctypes.CDLL) -> None:
    if getattr(lib, "_net_bound", False):
        return
    # symbol probe BEFORE binding: a stale prebuilt .so (built from older
    # sources, e.g. copied between checkouts — the Makefile's always-
    # remake only covers in-tree builds) would otherwise surface as a
    # bare AttributeError deep inside NetEndpoint.__init__
    for sym in ("hpxrt_net_create", "hpxrt_net_create2",
                "hpxrt_net_create3"):
        if not hasattr(lib, sym):
            raise RuntimeError(
                f"libhpx_tpu_rt.so is stale (missing symbol {sym}); "
                f"rebuild it: make -C {_HERE} clean && make -C {_HERE}")
    lib.hpxrt_net_create.restype = ctypes.c_void_p
    lib.hpxrt_net_create.argtypes = [ctypes.c_uint16]
    lib.hpxrt_net_create2.restype = ctypes.c_void_p
    lib.hpxrt_net_create2.argtypes = [ctypes.c_uint16, ctypes.c_int]
    lib.hpxrt_net_create3.restype = ctypes.c_void_p
    lib.hpxrt_net_create3.argtypes = [ctypes.c_uint16, ctypes.c_char_p]
    lib.hpxrt_net_port.restype = ctypes.c_uint16
    lib.hpxrt_net_port.argtypes = [ctypes.c_void_p]
    lib.hpxrt_net_set_callback.argtypes = [ctypes.c_void_p, _NET_CB,
                                           ctypes.c_void_p]
    lib.hpxrt_net_start.argtypes = [ctypes.c_void_p]
    lib.hpxrt_net_connect.restype = ctypes.c_int
    lib.hpxrt_net_connect.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_uint16]
    lib.hpxrt_net_send.restype = ctypes.c_int
    lib.hpxrt_net_send.argtypes = [ctypes.c_void_p, ctypes.c_int,
                                   ctypes.c_char_p, ctypes.c_uint64]
    lib.hpxrt_net_destroy.argtypes = [ctypes.c_void_p]
    lib._net_bound = True


class NetEndpoint:
    """Framed TCP endpoint over the native epoll transport.

    on_message(peer_id, bytes) is invoked on the IO thread (under the
    GIL); keep it cheap — the parcel layer enqueues to the task pool.
    """

    def __init__(self, port: int = 0,
                 on_message: Optional[Callable[[int, bytes], None]] = None,
                 bind: str = "127.0.0.1"):
        lib = native_lib()
        if lib is None:
            raise RuntimeError("native runtime library unavailable")
        _bind_net(lib)
        self._lib = lib
        # the native path takes IPv4 literals only; resolve names here
        import socket as _s
        try:
            _s.inet_pton(_s.AF_INET, bind)
        except OSError:
            bind = _s.getaddrinfo(bind, port, _s.AF_INET,
                                  _s.SOCK_STREAM)[0][4][0]
        self._h = lib.hpxrt_net_create3(port, bind.encode())
        if not self._h:
            raise OSError(f"cannot listen on {bind}:{port}")
        self.on_message = on_message
        # surface the epoll thread in the io_service registry (the
        # reference's "parcel" helper pool) for io_pool_names()/counters
        try:
            from ..runtime.io_service import register_external_pool
            register_external_pool("parcel", 1,
                                   "native/net.cpp epoll thread")
        except Exception:  # noqa: BLE001 — observability only
            pass

        def _cb(_user, peer_id, data, length):
            payload = ctypes.string_at(data, length)
            handler = self.on_message
            if handler is not None:
                handler(peer_id, payload)

        self._cb = _NET_CB(_cb)
        lib.hpxrt_net_set_callback(self._h, self._cb, None)
        lib.hpxrt_net_start(self._h)
        self._closed = False

    @property
    def port(self) -> int:
        if self._closed:
            raise OSError("endpoint closed")
        return int(self._lib.hpxrt_net_port(self._h))

    def connect(self, host: str, port: int) -> int:
        if self._closed:
            raise OSError("endpoint closed")
        # the native path takes IPv4 literals only (inet_pton); resolve
        # DNS names (multi-node: hpx.parcel.address=nodename) here
        import socket
        try:
            socket.inet_pton(socket.AF_INET, host)
        except OSError:
            host = socket.getaddrinfo(
                host, port, socket.AF_INET, socket.SOCK_STREAM)[0][4][0]
        pid = self._lib.hpxrt_net_connect(self._h, host.encode(), port)
        if pid < 0:
            raise OSError(f"connect to {host}:{port} failed")
        return pid

    def send(self, peer_id: int, data: bytes) -> None:
        if self._closed:
            raise OSError("endpoint closed")
        if self._lib.hpxrt_net_send(self._h, peer_id, data, len(data)) != 0:
            raise OSError(f"send to peer {peer_id} failed")

    def close(self) -> None:
        if not self._closed:
            self._closed = True
            self._lib.hpxrt_net_destroy(self._h)
