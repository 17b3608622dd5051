"""The step's account out of a traced run: the program's
`serving.dispatch` spans (one around the call of every named program,
`prog` = its name) and `serving.decode.operands`, beside the
`serving.step` and `*.wait` spans span_reduce.py already reads.

  host_held_ms        time inside dispatch spans, a step: the runtime
                      HOLDING the host in a call (a full queue, a
                      donated buffer still in use), not an execution
  host_work_ms        a step's duration less its waits and less its
                      dispatch spans: the host's own Python and eager
                      ops; with host_held_ms it adds up to
                      span_reduce.host_self_ms
  dispatches_per_step named programs a step
  dev_programs_per_step  XLA module runs on the device a step; less
                      dispatches_per_step: eager, unnamed programs
  idle_pct_innermost  share of the window in which the device is idle
                      and the INNERMOST program span is the one named

Here an idle gap is SPLIT over the spans it overlaps (span_reduce gives
a gap whole to the span over its middle), by one sweep over the sorted
edges of gaps and spans, so a whole window reduces in the time it takes
to sort it. Every reader returns None where the run was not traced or
its trace holds no such span (a program without them).

    python -m chipbench.step_reduce <cell>

prints what the last traced run of that cell left: held ms and count a
step by `prog` (the span's argument, read from the event's stats), idle
seconds by innermost span, and the readers' values, as JSON (for
PERF.md; no check runs it).
"""

from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Tuple

from chipbench import span_reduce, trace_reduce
from chipbench.span_reduce import STEP, Span

DISPATCH = "serving.dispatch"
OPERANDS = "serving.decode.operands"
OUTSIDE = "outside_spans"


def _in_steps(spans: List[Span], name: str) -> List[Span]:
    """The spans called `name` inside a step span, outermost only."""
    return [sp for sp in spans if sp.name == name and STEP in sp.path
            and name not in sp.path[:-1]]


def _n_steps(spans: Optional[List[Span]]) -> int:
    return sum(sp.name == STEP for sp in spans or ())


def host_held_ms(spans: Optional[List[Span]]) -> Optional[float]:
    held, steps = _in_steps(spans or [], DISPATCH), _n_steps(spans)
    if not held or not steps:
        return None
    return sum(sp.end - sp.start for sp in held) / steps / 1e6


def host_work_ms(spans: Optional[List[Span]]) -> Optional[float]:
    held = host_held_ms(spans)
    return None if held is None else span_reduce.host_self_ms(spans) - held


def dispatches_per_step(spans: Optional[List[Span]]) -> Optional[float]:
    held, steps = _in_steps(spans or [], DISPATCH), _n_steps(spans)
    return len(held) / steps if held and steps else None


def dev_programs_per_step(trace: Optional[dict],
                          spans: Optional[List[Span]]) -> Optional[float]:
    steps = _n_steps(spans)
    if trace is None or not trace.get("devices") or not steps:
        return None
    runs = trace_reduce.module_runs(trace, "")
    return len(runs) / len(trace["devices"]) / steps


# -- the device's idle time, split over the innermost spans ---------------

def innermost(spans: List[Span]) -> List[Tuple[float, float, str]]:
    """(start, end, name) segments, in time order and disjoint: which
    span of the step loop's thread is innermost when. Spans of one
    thread nest, so one pass with a stack does it."""
    by_thread: Dict[int, int] = {}
    for sp in spans:
        by_thread[sp.thread] = by_thread.get(sp.thread, 0) + (
            sp.name == STEP)
    thread = max(by_thread, key=by_thread.get, default=None)
    rows = sorted((sp for sp in spans if sp.thread == thread),
                  key=lambda sp: (sp.start, -sp.end))
    segs: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, str]] = []     # (end, name), innermost last
    t = 0.0
    for sp in rows + [None]:
        upto = float("inf") if sp is None else sp.start
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > t:
                segs.append((t, end, name))
                t = end
        if sp is None:
            break
        if stack and upto > t:
            segs.append((t, upto, stack[-1][1]))
        t = upto
        stack.append((sp.end, sp.name))
    return segs


def idle_by_innermost(trace: dict, spans: List[Span]) -> Dict[str, float]:
    """name -> idle seconds of the window under that innermost span
    (`outside_spans`: under none), mean over the device planes."""
    t0, t1 = trace_reduce.window(trace)
    segs = innermost(spans)
    acc: Dict[str, float] = {}
    for dev in trace["devices"]:
        busy = trace_reduce._union(
            (a, b) for _, a, b in trace_reduce._clip(dev["ops"], t0, t1))
        edges = [t0] + [x for ab in busy for x in ab] + [t1]
        i = 0
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            named = 0.0
            while i < len(segs) and segs[i][1] <= a:
                i += 1
            j = i
            while j < len(segs) and segs[j][0] < b:
                s, e, name = segs[j]
                part = min(e, b) - max(s, a)
                acc[name] = acc.get(name, 0.0) + part
                named += part
                j += 1
            acc[OUTSIDE] = acc.get(OUTSIDE, 0.0) + (b - a) - named
    k = max(1, len(trace["devices"]))
    return {name: ns / k / 1e9 for name, ns in acc.items()}


def idle_pct_innermost(trace: Optional[dict], spans: Optional[List[Span]],
                       name: str) -> Optional[float]:
    if trace is None or not trace.get("devices") or not spans \
            or not any(sp.name == name for sp in spans):
        return None
    _, window_s = trace_reduce.busy(trace)
    return 100.0 * idle_by_innermost(trace, spans).get(name, 0.0) / window_s


# -- by program, for PERF.md --------------------------------------------

def held_by_prog(path: str) -> Dict[str, List[float]]:
    """prog -> [calls, seconds inside] of the window's dispatch spans."""
    from jax.profiler import ProfileData
    window, rows = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == trace_reduce.WINDOW_SPAN:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name == DISPATCH:
                    rows.append((ev.start_ns, ev.duration_ns,
                                 dict(ev.stats).get("prog", "?")))
    t0, t1 = window or (float("-inf"), float("inf"))
    acc: Dict[str, List[float]] = {}
    for start, dur, prog in rows:
        if start >= t0 and start + dur <= t1:
            row = acc.setdefault(str(prog), [0, 0.0])
            row[0] += 1
            row[1] += dur / 1e9
    return acc


def main(argv: List[str]) -> int:
    path = span_reduce.find_xplane(argv[0]) if argv else None
    if path is None:
        print("usage: python -m chipbench.step_reduce <cell>  (after a "
              "--trace 1 run of it)", file=sys.stderr)
        return 2
    spans = span_reduce.nest(span_reduce.load(path))
    trace = trace_reduce.load_xplane(path)
    steps = _n_steps(spans)
    out = {"steps": steps,
           "host_self_ms": span_reduce.host_self_ms(spans),
           "host_held_ms": host_held_ms(spans),
           "host_work_ms": host_work_ms(spans),
           "dispatches_per_step": dispatches_per_step(spans),
           "by_prog_a_step": {
               prog: {"calls": n / steps, "held_ms": 1e3 * sec / steps}
               for prog, (n, sec) in sorted(
                   held_by_prog(path).items(), key=lambda kv: -kv[1][1])
           } if steps else {}}
    if trace["devices"]:
        busy_s, window_s = trace_reduce.busy(trace)
        idle = idle_by_innermost(trace, spans)
        out.update(
            busy_s=busy_s, window_s=window_s,
            dev_programs_per_step=dev_programs_per_step(trace, spans),
            idle_s_by_innermost=dict(sorted(idle.items(),
                                            key=lambda kv: -kv[1])))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
