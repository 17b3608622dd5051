"""From a profiler trace to numbers: the one place that knows how the
trace is laid out and how programs and kernels are found in it.

Two stages, so that the arithmetic can be tested without a chip:

  load_xplane(path)  reads an `.xplane.pb` with jax.profiler.ProfileData
                     into a plain dict (JSON-able; a trimmed one recorded
                     on the chip is kept beside the tests):
        {"devices": [{"name": str,
                      "ops":     [[name, start_ns, dur_ns], ...],
                      "modules": [[name, start_ns, dur_ns], ...]}, ...],
         "host":    [[name, start_ns, dur_ns], ...]}     # bench.* spans
  the reducers       take that dict.

Programs and kernels have no stable names yet (ROADMAP C12): a program
is found by the name XLA gives its module (`jit_<python function>`), a
Pallas kernel by the HLO op name of its custom call. `matches()` is
the one function that decides; the patterns live in the per-layer
metric files.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.trace_window"
NAME_CHARS = 160        # an HLO op's name is its whole instruction


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                for ev in line.events:
                    dev[key].append([ev.name[:NAME_CHARS],
                                     float(ev.start_ns),
                                     float(ev.duration_ns)])
            if dev["ops"] or dev["modules"]:
                devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host.append([ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def matches(name: str, pattern: str) -> bool:
    """Does a module or op name of the trace belong to `pattern`?
    A pattern is a regular expression searched in the name; module
    names look like `jit_step(1234567)`, op names like `fusion.12` or
    `%copy-done.3 = f32[...]`."""
    return re.search(pattern, name) is not None


def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events: Sequence[Sequence], t0: float, t1: float):
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            yield name, a, b


def window(trace: dict) -> Tuple[float, float]:
    """The traced window in ns: the harness's `bench.trace_window` span
    where the trace has one, else first device event to last."""
    for name, s, d in trace["host"]:
        if name == WINDOW_SPAN:
            return s, s + d
    starts = [e[1] for dev in trace["devices"] for e in dev["ops"]]
    ends = [e[1] + e[2] for dev in trace["devices"] for e in dev["ops"]]
    if not starts:
        raise ValueError("trace holds no device operation")
    return min(starts), max(ends)


def busy(trace: dict) -> Tuple[float, float]:
    """(busy_s, window_s): seconds in which an operation ran on the
    device, union of op intervals inside the window, mean over the
    device planes; and the window's length."""
    t0, t1 = window(trace)
    per = []
    for dev in trace["devices"]:
        u = _union((a, b) for _, a, b in _clip(dev["ops"], t0, t1))
        per.append(sum(b - a for a, b in u))
    if not per:
        raise ValueError("trace holds no device plane")
    return sum(per) / len(per) / 1e9, (t1 - t0) / 1e9


def idle_pct(trace: dict) -> float:
    b, w = busy(trace)
    return 100.0 * (1.0 - b / w)


def module_runs(trace: dict, pattern: str) -> List[Tuple[float, float]]:
    """(start_ns, end_ns) of every execution of a matching program
    inside the window, over all device planes."""
    t0, t1 = window(trace)
    return [(a, b) for dev in trace["devices"]
            for name, a, b in _clip(dev["modules"], t0, t1)
            if matches(name, pattern)]


def module_mean_ms(trace: dict, pattern: str) -> Optional[float]:
    runs = module_runs(trace, pattern)
    if not runs:
        return None
    return sum(b - a for a, b in runs) / len(runs) / 1e6


def op_seconds_in_modules(trace: dict, module_pattern: str,
                          op_pattern: str = "") -> Tuple[float, int]:
    """Summed device seconds (and count) of the ops that match
    `op_pattern` and run inside an execution of a matching program.
    Ops nest (a `while` covers its body): the union is summed, so
    nothing counts twice."""
    t0, t1 = window(trace)
    total, count = 0.0, 0
    for dev in trace["devices"]:
        runs = sorted((a, b) for name, a, b in _clip(dev["modules"], t0, t1)
                      if matches(name, module_pattern))
        if not runs:
            continue
        hits = []
        for name, a, b in _clip(dev["ops"], t0, t1):
            if op_pattern and not matches(name, op_pattern):
                continue
            mid = 0.5 * (a + b)
            if any(ra <= mid <= rb for ra, rb in runs):
                hits.append((a, b))
        count += len(hits)
        total += sum(b - a for a, b in _union(hits))
    return total / 1e9, count


def op_kind(name: str) -> str:
    """An HLO instruction without its instance numbers, so that the same
    op of 30 unrolled layers adds up under one name: `%step.34 = bf16[..]
    custom-call(s32[..] %copy-done.2, ...)` -> `%step = bf16[..]
    custom-call(s32[..] %copy-done, ...)`."""
    return re.sub(r"%([A-Za-z_\-]+)[\w.\-]*", r"%\1", name)


def top_device_ops(trace: dict, n: int = 10) -> List[List]:
    """Seconds inside the window by kind of op, most first (mean over
    the device planes)."""
    t0, t1 = window(trace)
    acc: Dict[str, float] = {}
    for dev in trace["devices"]:
        for name, a, b in _clip(dev["ops"], t0, t1):
            kind = op_kind(name)[:120]
            acc[kind] = acc.get(kind, 0.0) + (b - a) / 1e9
    k = max(1, len(trace["devices"]))
    return [[kind, sec / k] for kind, sec in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> List[List]:
    """The device's idle gaps inside the window, each named by the
    innermost harness span (`bench.*`) that covers its middle, summed
    by name, longest first."""
    t0, t1 = window(trace)
    acc: Dict[str, float] = {}
    spans = [(nm, s, s + d) for nm, s, d in trace["host"]
             if nm != WINDOW_SPAN]
    for dev in trace["devices"]:
        u = _union((a, b) for _, a, b in _clip(dev["ops"], t0, t1))
        edges = [t0] + [x for ab in u for x in ab] + [t1]
        for i in range(0, len(edges), 2):
            a, b = edges[i], edges[i + 1]
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            cover = [(e - s, nm) for nm, s, e in spans if s <= mid <= e]
            name = min(cover)[1] if cover else "outside_spans"
            acc[name] = acc.get(name, 0.0) + (b - a) / 1e9
    k = max(1, len(trace["devices"]))
    return [[name, sec / k] for name, sec in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def breakdown(trace: dict) -> dict:
    return {"device_ops": top_device_ops(trace),
            "idle_gaps": idle_gaps(trace)}
