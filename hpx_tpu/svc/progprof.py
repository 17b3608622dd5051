"""Per-program continuous profiler over the jit-program cache.

Reference analog: APEX's per-task timers plus HPX's roofline-style
counters — the PAPERS.md adaptive-executor line ("A New Execution Model
and Executor for Adaptively Optimizing ... Using HPX") needs per-program
achieved-vs-peak data before any policy can act on it.

Every module that memoizes compiled programs funnels through
``core.programs.cached_program``; this module installs a build-time
hook there so each cache MISS is timed (compile wall time) and the
stored program is replaced by a thin callable proxy that records
the host's wall time around each call into a
:class:`metrics.HistogramCounter`. Cache HITS return the stored proxy
— the hot path pays one ``perf_counter`` pair per call and nothing
else.

What ``time/execute-s`` is NOT: an execution time. A jitted call is
asynchronous: it returns once the program is enqueued, so the wall
around it is the time the runtime HOLDS the host in the dispatch call
(long where the queue is full or a donated buffer is still in use,
microseconds where it is not) — the same quantity a serving step's
account sums as `held` (``svc/tracing.StepAccount``,
``serving.dispatch`` spans). A program's time on the device is read
from a profiler trace (``chipbench/trace_reduce.py``). The achieved
GFLOP/s and roofline fraction this module once derived by dividing
XLA's cost analysis by that wall are gone for that reason; the device
tables below stay for :func:`device_ridge`.

Exposure planes:

* ``/programs{locality#N/<tag>#i}/...`` performance counters —
  ``time/execute-s`` (the hold: histogram + derived pNN quantiles),
  ``count/calls``, ``time/compile-s`` — so Prometheus rows and
  Perfetto counter tracks (``hpx.trace.counters`` samples
  ``/programs*`` by default) come for free from the existing
  exposition paths.
* :func:`profile_table` — a JSON-safe fold serving_bench embeds in the
  ``--metrics-out`` artifact and the flight recorder persists in every
  bundle.
* an HBM/host high-water-mark sampler (:class:`MemoryWatermark`)
  riding ``profiling.device_memory_stats``.

Lifecycle mirrors tracing: :func:`start_profiling` /
:func:`stop_profiling` / :func:`active_profiler`, with
:func:`start_if_configured` gated on ``hpx.prof.programs``.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..core import programs as _programs
from ..synchronization import Mutex
from . import performance_counters as pc
from . import profiling as _profiling
from .metrics import HistogramCounter, register_histogram

__all__ = [
    "PROFILE_SCHEMA",
    "ProgramProfiler",
    "MemoryWatermark",
    "start_profiling",
    "stop_profiling",
    "active_profiler",
    "start_if_configured",
    "profile_table",
    "device_ridge",
]

PROFILE_SCHEMA = "hpx_tpu.progprof.v1"


def _cfg():
    from ..core.config import runtime_config
    return runtime_config()


# rough bf16 peak GFLOP/s per device kind (case-insensitive substring
# match on jax's device_kind; CPU and unknown kinds fall through to
# 0 = unknown)
_DEVICE_PEAK_GFLOPS: Tuple[Tuple[str, float], ...] = (
    ("v6e", 918_000.0),
    ("v5p", 459_000.0),
    ("v5e", 197_000.0),
    ("v5 lite", 197_000.0),
    ("v4", 275_000.0),
    ("v3", 123_000.0),
    ("v2", 45_000.0),
)

# HBM GB/s per device kind, matched as the table above: with it the
# RIDGE of a kind, the FLOPs a byte read from HBM has to feed before
# the matrix unit and not the read paces a program
_DEVICE_HBM_GBPS: Tuple[Tuple[str, float], ...] = (
    ("v6e", 1_640.0),
    ("v5p", 2_765.0),
    ("v5e", 819.0),
    ("v5 lite", 819.0),
    ("v4", 1_228.0),
    ("v3", 900.0),
    ("v2", 700.0),
)


def _by_kind(table: Tuple[Tuple[str, float], ...],
             kind: Optional[str] = None) -> float:
    """The table's entry for `kind` (None: the first device's); 0 for a
    kind it does not know, the CPU among them."""
    if kind is None:
        try:
            import jax
            kind = jax.devices()[0].device_kind
        except Exception:  # noqa: BLE001
            return 0.0
    kind = kind.lower()
    for frag, value in table:
        if frag in kind:
            return value
    return 0.0


def device_ridge(kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s over HBM bytes/s of a device kind (TPU v5 lite:
    197e12 / 819e9 = 240); 0 = unknown."""
    gbps = _by_kind(_DEVICE_HBM_GBPS, kind)
    return _by_kind(_DEVICE_PEAK_GFLOPS, kind) / gbps if gbps else 0.0


def _host_rss_bytes() -> int:
    try:
        import os
        with open("/proc/self/statm") as f:
            pages = int(f.read().split()[1])
        return pages * os.sysconf("SC_PAGE_SIZE")
    except Exception:  # noqa: BLE001 — non-procfs platforms report 0
        return 0


def _key_label(key: Any) -> str:
    """Compact, stable label for a program-cache key: the leading str
    tag every cache in the tree uses (("decode", cfg, ...) → "decode"),
    sanitized to counter-instance charset."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        raw = key[0]
    elif isinstance(key, str):
        raw = key
    else:
        raw = type(key).__name__
    out = "".join(ch if ch.isalnum() or ch in "-_." else "-"
                  for ch in raw)
    return out or "prog"


class ProgramRecord:
    """Accounting for ONE cached program key."""

    __slots__ = ("key", "label", "instance", "compiles", "compile_s",
                 "exec_hist", "counter_names")

    def __init__(self, key: Any, label: str, instance: str) -> None:
        self.key = key
        self.label = label
        self.instance = instance
        self.compiles = 0
        self.compile_s = 0.0
        self.exec_hist = HistogramCounter()    # the hold of each call
        self.counter_names: List[str] = []

    @property
    def calls(self) -> int:
        return self.exec_hist.count


class _ProfiledProgram:
    """Callable proxy stored in the program cache in place of the jit
    program: times the host's wall around each (asynchronous) call
    into the record's histogram; everything else (``lower``,
    ``clear_cache``, ...) passes through."""

    __slots__ = ("_prog", "_rec")

    def __init__(self, prog: Callable, rec: ProgramRecord) -> None:
        self._prog = prog
        self._rec = rec

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        t0 = time.perf_counter()
        out = self._prog(*args, **kwargs)
        self._rec.exec_hist.record(time.perf_counter() - t0)
        return out

    def __getattr__(self, name: str) -> Any:
        return getattr(self._prog, name)

    def __repr__(self) -> str:
        return f"_ProfiledProgram({self._rec.label!r})"


class MemoryWatermark:
    """HBM/host RSS high-water-mark sampler.  ``sample()`` is direct
    (tests call it synchronously); ``start()`` spins the periodic
    daemon thread.  Device peak comes from
    ``profiling.device_memory_stats`` (`peak_bytes_in_use`, falling
    back to `bytes_in_use` on backends without peak tracking)."""

    def __init__(self, interval_s: float = 0.05) -> None:
        self.interval_s = max(0.001, float(interval_s))
        self.hbm_peak_bytes = 0
        self.host_peak_bytes = 0
        self.samples = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def sample(self) -> None:
        st = _profiling.device_memory_stats()
        peak = st.get("peak_bytes_in_use", st.get("bytes_in_use", 0)) or 0
        if peak > self.hbm_peak_bytes:
            self.hbm_peak_bytes = int(peak)
        rss = _host_rss_bytes()
        if rss > self.host_peak_bytes:
            self.host_peak_bytes = rss
        self.samples += 1

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()

        def loop() -> None:
            while not self._stop.wait(self.interval_s):
                self.sample()

        self._thread = threading.Thread(
            target=loop, daemon=True, name="hpx-progprof-mem")
        self._thread.start()

    def stop(self) -> None:
        t = self._thread
        if t is None:
            return
        self._stop.set()
        t.join(timeout=2.0)
        self._thread = None

    def snapshot(self) -> Dict[str, int]:
        return {"hbm_peak_bytes": self.hbm_peak_bytes,
                "host_peak_bytes": self.host_peak_bytes,
                "samples": self.samples}


class ProgramProfiler:
    """Owns the program records, their registered counters, and the
    memory watermark.  Install via :func:`start_profiling` (or
    construct + ``install()`` directly in tests)."""

    def __init__(self, sample_memory: bool = True,
                 mem_interval_s: float = 0.05) -> None:
        self._lock = Mutex()
        self._records: Dict[Any, ProgramRecord] = {}
        self._names: List[str] = []
        self._sample_memory = sample_memory
        self.memory = MemoryWatermark(mem_interval_s)
        self._installed = False

    # -- the cached_program build hook --------------------------------

    def _build_hook(self, key: Any, build: Callable[[], Any]) -> Any:
        t0 = time.perf_counter()
        prog = build()
        dt = time.perf_counter() - t0
        if not callable(prog):
            return prog     # plans/tuples: nothing to time per-call
        rec = self._record_for(key)
        rec.compiles += 1
        rec.compile_s += dt
        return _ProfiledProgram(prog, rec)

    def _record_for(self, key: Any) -> ProgramRecord:
        with self._lock:
            rec = self._records.get(key)
            if rec is None:
                label = _key_label(key)
                instance = f"{label}#{len(self._records)}"
                rec = ProgramRecord(key, label, instance)
                self._records[key] = rec
                self._register_record(rec)
            return rec

    def _register_record(self, rec: ProgramRecord) -> None:
        names = register_histogram("programs", "time/execute-s",
                                   rec.exec_hist, rec.instance)

        def put(counter: str, fn: Callable[[], float]) -> None:
            name = pc.counter_name("programs", counter, rec.instance)
            pc.register_counter(name, pc.CallbackCounter(fn))
            names.append(name)

        put("count/calls", lambda r=rec: float(r.calls))
        put("time/compile-s", lambda r=rec: r.compile_s)
        rec.counter_names = names
        self._names.extend(names)

    # -- lifecycle ----------------------------------------------------

    def install(self) -> None:
        _programs.set_profile_hook(self._build_hook)
        self._installed = True
        if self._sample_memory:
            self.memory.start()
        with self._lock:
            if not any(n.endswith("memory/hbm-peak-bytes")
                       for n in self._names):
                for counter, fn in (
                        ("memory/hbm-peak-bytes",
                         lambda: float(self.memory.hbm_peak_bytes)),
                        ("memory/host-peak-bytes",
                         lambda: float(self.memory.host_peak_bytes))):
                    name = pc.counter_name("programs", counter)
                    pc.register_counter(name, pc.CallbackCounter(fn))
                    self._names.append(name)

    def close(self) -> None:
        if _programs.profile_hook() == self._build_hook:
            _programs.set_profile_hook(None)
        self._installed = False
        self.memory.stop()
        with self._lock:
            names, self._names = self._names, []
        for name in names:
            pc.unregister_counter(name)

    # -- reading ------------------------------------------------------

    def records(self) -> List[ProgramRecord]:
        with self._lock:
            return list(self._records.values())

    def profile_table(self) -> Dict[str, Any]:
        """JSON-safe fold of every record, the one that held the host
        longest in all (total seconds inside its calls) first — the section serving_bench embeds under
        ``"programs"`` in the metrics artifact and the flight recorder
        persists per bundle."""
        rows: List[Dict[str, Any]] = []
        for rec in sorted(self.records(),
                          key=lambda r: -r.exec_hist.sum):
            h = rec.exec_hist
            rows.append({
                "key": rec.label,
                "instance": rec.instance,
                "compiles": rec.compiles,
                "compile_s": rec.compile_s,
                "calls": h.count,
                "total_s": h.sum,
                "mean_s": h.mean(),
                "p50_s": h.quantile(0.5),
                "p99_s": h.quantile(0.99),
                "relative_error_bound": h.relative_error_bound(),
            })
        return {
            "schema": PROFILE_SCHEMA,
            "memory": self.memory.snapshot(),
            "programs": rows,
        }


# ---------------------------------------------------------------------------
# module lifecycle (tracing-style singleton)
# ---------------------------------------------------------------------------

_active: Optional[ProgramProfiler] = None


def start_profiling(sample_memory: bool = True,
                    mem_interval_s: float = 0.05) -> ProgramProfiler:
    """Create, install and return the process program profiler.
    Raises if one is active."""
    global _active
    if _active is not None:
        raise RuntimeError(
            "program profiler already active; stop_profiling() first")
    prof = ProgramProfiler(sample_memory=sample_memory,
                           mem_interval_s=mem_interval_s)
    _active = prof
    prof.install()
    return prof


def stop_profiling() -> Optional[ProgramProfiler]:
    """Stop and detach the active profiler (returned so callers can
    still fold its table into artifacts)."""
    global _active
    prof = _active
    _active = None
    if prof is not None:
        prof.close()
    return prof


def active_profiler() -> Optional[ProgramProfiler]:
    return _active


def start_if_configured() -> Optional[ProgramProfiler]:
    """Start profiling iff ``hpx.prof.programs`` is truthy and no
    profiler is active — the config-gated entry point bench harnesses
    use."""
    if _active is not None:
        return _active
    if not _cfg().get_bool("hpx.prof.programs", False):
        return None
    return start_profiling()


def profile_table() -> Optional[Dict[str, Any]]:
    """The active profiler's table, or None when profiling is off —
    flight bundles and metrics artifacts embed this verbatim."""
    prof = _active
    return prof.profile_table() if prof is not None else None
