"""The program's own spans in a run's profiler trace: `serving.*` and
`hpx.*`, which `hpx_tpu.svc.tracing.span()` writes into the host plane
of the same `.xplane.pb` as the device ops, on its clock.

Two stages, as in trace_reduce.py, so that the arithmetic can be tested
without a chip:

  load(path)   reads the host planes into a plain dict (JSON-able):
        {"window": [start_ns, end_ns] or None,   # bench.trace_window
         "spans":  [[name, start_ns, dur_ns, thread], ...]}
  nest(raw)    keeps the spans that lie inside the window and gives each
               its ancestors on its own thread and its SELF time: its
               duration less what its child spans cover.
  the reducers take nest()'s list.

A reader gets both through `of_run(ctx)`, which finds the run's trace
under harness.OUT_DIR/trace-<cell>/ and reads it once. Where the trace
holds no such span (a program without them) every reader returns None.

The device's idle gaps are named by `trace_reduce.idle_gaps` itself,
handed the trace with these spans added to its `host` list, each under
the name of its whole path (`serving.step/serving.decode/serving.flush`),
so that "inside `serving.flush`" is read from where a span WAS and not
from what it is called.

    python -m chipbench.span_reduce <cell>

prints what the last traced run of that cell left: self time by span and
idle seconds by path, as JSON (for PERF.md; no check runs it).
"""

from __future__ import annotations

import functools
import glob
import json
import os
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from chipbench import harness, trace_reduce

PREFIXES = ("serving.", "hpx.")
STEP = "serving.step"
WAIT = ".wait"          # a span around a blocking device-to-host read
NODE, BODY, DISPATCH = ("hpx.dataflow.node", "hpx.dataflow.body",
                        "hpx.exec.dispatch")
SEP = "/"


class Span(NamedTuple):
    name: str
    start: float            # ns
    end: float
    thread: int
    path: Tuple[str, ...]   # ancestors' names, outermost first, then own
    self_ns: float


def load(path: str) -> dict:
    from jax.profiler import ProfileData
    window, spans, thread = None, [], 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            thread += 1         # a line is one thread; names repeat
            for ev in line.events:
                if ev.name == trace_reduce.WINDOW_SPAN:
                    window = [float(ev.start_ns),
                              float(ev.start_ns + ev.duration_ns)]
                elif ev.name.startswith(PREFIXES):
                    spans.append([ev.name, float(ev.start_ns),
                                  float(ev.duration_ns), thread])
    return {"window": window, "spans": spans}


def nest(raw: dict) -> List[Span]:
    """Spans wholly inside the window, in start order, each with its
    path and self time. A span's parent is the innermost span of its
    own thread that holds it."""
    t0, t1 = raw["window"] or (float("-inf"), float("inf"))
    rows = sorted(([s, s + d, th, nm] for nm, s, d, th in raw["spans"]
                   if s >= t0 and s + d <= t1),
                  key=lambda r: (r[2], r[0], -r[1]))
    children = [0.0] * len(rows)
    paths: List[Tuple[str, ...]] = []
    stack: List[int] = []
    for i, (s, e, th, nm) in enumerate(rows):
        while stack and not (rows[stack[-1]][2] == th
                             and rows[stack[-1]][1] >= e):
            stack.pop()
        if stack:
            children[stack[-1]] += e - s
            paths.append(paths[stack[-1]] + (nm,))
        else:
            paths.append((nm,))
        stack.append(i)
    out = [Span(nm, s, e, th, paths[i], (e - s) - children[i])
           for i, (s, e, th, nm) in enumerate(rows)]
    out.sort(key=lambda sp: sp.start)
    return out


def find_xplane(cell: str) -> Optional[str]:
    files = glob.glob(os.path.join(harness.OUT_DIR, "trace-" + cell,
                                   "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def of_run(ctx) -> Optional[List[Span]]:
    """The program's spans of this run's traced window, or None where
    the run was not traced or its trace holds none. Read once."""
    path = find_xplane(ctx.cell["name"]) if ctx.trace else None
    if path is None:
        return None
    return _nested(path, os.path.getmtime(path)) or None


@functools.lru_cache(maxsize=1)
def _nested(path: str, mtime: float) -> List[Span]:
    return nest(load(path))


# -- self time -----------------------------------------------------------

def by_name(spans: Iterable[Span]) -> Dict[str, List[float]]:
    """name -> [count, seconds inside, SELF seconds], every thread."""
    acc: Dict[str, List[float]] = {}
    for sp in spans:
        row = acc.setdefault(sp.name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (sp.end - sp.start) / 1e9
        row[2] += sp.self_ns / 1e9
    return acc


def _named(spans: Iterable[Span], name: str) -> List[Span]:
    return [sp for sp in spans if sp.name == name]


def _waits(spans: Iterable[Span]) -> List[Span]:
    return [sp for sp in spans if sp.name.endswith(WAIT)]


def host_self_ms(spans: List[Span]) -> Optional[float]:
    """Mean over the step spans of their duration less the time they
    spend inside `*.wait` spans: what the host itself costs a step,
    the blocking reads of the device taken out."""
    steps = _named(spans, STEP)
    if not steps:
        return None
    waited = sum(sp.end - sp.start for sp in _waits(spans)
                 if STEP in sp.path)
    inside = sum(sp.end - sp.start for sp in steps)
    return (inside - waited) / len(steps) / 1e6


def syncs_per_step(spans: List[Span]) -> Optional[float]:
    """`*.wait` spans over step spans: blocking device-to-host reads a
    step, each of which empties the dispatch queue."""
    steps = _named(spans, STEP)
    if not steps:
        return None
    return len(_waits(spans)) / len(steps)


def node_us(spans: List[Span]) -> Optional[Tuple[float, float, int]]:
    """(bookkeeping us a node, dispatch us a node, nodes): the time
    inside node spans that no body span covers, and the time inside
    the dispatch spans of those bodies, over the bodies run."""
    nodes = sum(1 for sp in spans if sp.name == BODY and NODE in sp.path)
    if not nodes:
        return None

    def outermost(name: str, inside: str) -> float:
        # a span nested in another of its name (a dependent node that
        # fires inside the node that readied it) holds no time of its own
        return sum(sp.end - sp.start for sp in _named(spans, name)
                   if inside in sp.path and name not in sp.path[:-1])
    sched = outermost(NODE, NODE) - outermost(BODY, NODE)
    return sched / nodes / 1e3, outermost(DISPATCH, BODY) / nodes / 1e3, nodes


# -- the device's idle gaps, by the host phase that caused them ----------

def idle_by_path(trace: dict, spans: List[Span]) -> List[List]:
    """[[path, idle seconds], ...], longest first, of the whole traced
    window: trace_reduce.idle_gaps with the program's spans among the
    harness's, so a gap is named by the innermost span of either."""
    host = list(trace["host"]) + [
        [SEP.join(sp.path), sp.start, sp.end - sp.start] for sp in spans]
    host.sort(key=lambda e: e[1])
    return trace_reduce.idle_gaps({**trace, "host": host}, n=len(host) + 1)


def idle_pct_inside(trace: Optional[dict], spans: Optional[List[Span]],
                    names: Iterable[str]) -> Optional[float]:
    """Share of the traced window, in percent, in which the device is
    idle and the innermost span is one of `names` or inside one."""
    if trace is None or not trace.get("devices") or not spans:
        return None
    names = set(names)
    _, window_s = trace_reduce.busy(trace)
    idle = sum(sec for path, sec in idle_by_path(trace, spans)
               if names & set(path.split(SEP)))
    return 100.0 * idle / window_s


def main(argv: List[str]) -> int:
    path = find_xplane(argv[0]) if argv else None
    if path is None:
        print("usage: python -m chipbench.span_reduce <cell>  (after a "
              "--trace 1 run of it)", file=sys.stderr)
        return 2
    spans = nest(load(path))
    trace = trace_reduce.load_xplane(path)
    out = {"xplane_bytes": os.path.getsize(path), "spans": len(spans),
           "by_name": by_name(spans)}
    if trace["devices"]:
        busy_s, window_s = trace_reduce.busy(trace)
        out["busy_s"], out["window_s"] = busy_s, window_s
        out["idle_by_path"] = idle_by_path(trace, spans)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
