"""Fault flight recorder: a bounded black-box for post-mortems.

When a typed fault fires — a shed, a worker failover, a retry
exhaustion, an autoscale drain — the counters that describe the fleet's
state are about to be overwritten by recovery.  This module persists a
schema-versioned JSON bundle at the moment of the fault: the last-N
trace spans, a full counter + histogram registry snapshot, the resolved
configuration, the program profile table, and the affected request's
timeline.  Wired through ``models/serving`` (``_shed_req``,
``_shed_everything``; a SLOW step's account, kind ``slow_step``, from
``svc/tracing.StepAccount``), ``models/disagg`` (worker failover, degrade),
``svc/fleet`` (autoscale drain) and ``svc/resiliency`` (replay
exhaustion).

Zero-cost discipline (same as tracing's ``active_tracer()`` None
check): the recorder allocates NOTHING until a capture fires —
``record_fault`` is the only entry point on fault paths, it is never
called per-step, and its disabled path is one config lookup.  Captures
never raise into the caller: a broken disk must not turn a shed into a
crash (failures count on :func:`dropped_count`).

Knobs (``hpx.flight.*``): ``enabled`` (default on), ``dir``
(``auto`` = ``<tmpdir>/hpx_tpu_flight``), ``max_bundles`` (oldest
pruned, the new bundle's own kind first), ``spans`` (last-N trace
spans per bundle).

One-shot live capture, and what a run left behind (newest first; a
``slow_step`` bundle's line says which part of the step to blame)::

    python -m hpx_tpu.svc.flight dump [--out PATH]
    python -m hpx_tpu.svc.flight --tail 5
"""

from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Any, Dict, List, Optional

__all__ = [
    "FLIGHT_SCHEMA",
    "record_fault",
    "build_bundle",
    "validate_bundle",
    "capture_count",
    "dropped_count",
    "reset_counts",
    "flight_dir",
    "bundle_index",
    "main",
]

FLIGHT_SCHEMA = "hpx_tpu.flight.v1"

# GIL-atomic capture accounting (Tracer.dropped discipline); the
# zero-cost-when-disarmed test asserts capture_count() stays 0 across a
# fault-free serving run.
_captures = 0
_dropped = 0
_seq = 0


def capture_count() -> int:
    return _captures


def dropped_count() -> int:
    return _dropped


def reset_counts() -> None:
    global _captures, _dropped
    _captures = 0
    _dropped = 0


def _cfg():
    from ..core.config import runtime_config
    return runtime_config()


def flight_dir() -> str:
    raw = _cfg().get("hpx.flight.dir", "auto") or "auto"
    if raw == "auto":
        return os.path.join(tempfile.gettempdir(), "hpx_tpu_flight")
    return raw


def _trace_spans(limit: int) -> List[Dict[str, Any]]:
    """Last-``limit`` events of the active tracer ring, decoded from
    the flat 8-tuples to JSON dicts ([] when tracing is off)."""
    from . import tracing
    tr = tracing.active_tracer()
    if tr is None:
        return []
    events = tr.snapshot()[-max(0, limit):]
    out: List[Dict[str, Any]] = []
    for ph, name, cat, ts, tid, id_, parent, args in events:
        ev: Dict[str, Any] = {"ph": ph, "name": name, "cat": cat,
                              "ts": ts, "tid": tid}
        if id_ is not None:
            ev["id"] = id_
        if parent is not None:
            ev["parent"] = parent
        if args is not None:
            # span args are dicts; "C" counter samples carry a bare
            # float in the same slot
            ev["args"] = dict(args) if isinstance(args, dict) else args
        out.append(ev)
    return out


def _config_dump() -> Dict[str, str]:
    cfg = _cfg()
    out: Dict[str, str] = {}
    for line in cfg.dump().splitlines():
        k, sep, v = line.partition(" = ")
        if sep:
            out[k] = v
    return out


def build_bundle(kind: str, site: Optional[str] = None,
                 rid: Any = None, error: Optional[BaseException] = None,
                 timeline: Any = None,
                 extra: Optional[Dict[str, Any]] = None
                 ) -> Dict[str, Any]:
    """Assemble (but do not persist) one flight bundle.  ``timeline``
    is an optional :class:`metrics.RequestTimeline`; with a ``rid`` its
    events for that request are captured."""
    from . import metrics, progprof
    cfg = _cfg()
    spans_n = cfg.get_int("hpx.flight.spans", 256)
    doc: Dict[str, Any] = {
        "schema": FLIGHT_SCHEMA,
        "wall_time": time.time(),
        "trigger": {
            "kind": kind,
            "site": site,
            "rid": rid if isinstance(rid, (int, str, type(None)))
            else repr(rid),
            "error_type": type(error).__name__
            if error is not None else None,
            "error": repr(error) if error is not None else None,
        },
        "spans": _trace_spans(spans_n),
        "counters": metrics.registry_snapshot("*"),
        "config": _config_dump(),
        "programs": progprof.profile_table(),
        "timeline": (timeline.events(rid)
                     if timeline is not None and rid is not None
                     else []),
    }
    # host-tier state: occupancy + demote/promote/drop totals across
    # every live tier, so a shed bundle answers "was the cold tier
    # absorbing evictions or thrashing when this request died". {}
    # when no tier is live (the key stays optional).
    from ..cache import tier as _tier
    doc["tier"] = _tier.flight_snapshot()
    if extra:
        doc["extra"] = dict(extra)
    return doc


def _persist(doc: Dict[str, Any]) -> str:
    global _seq
    d = flight_dir()
    os.makedirs(d, exist_ok=True)
    kind = str(doc.get("trigger", {}).get("kind", "fault"))
    kind = "".join(ch if ch.isalnum() or ch in "-_" else "-"
                   for ch in kind) or "fault"
    while True:
        _seq += 1
        path = os.path.join(
            d, f"flight-{os.getpid()}-{_seq:05d}-{kind}.json")
        if not os.path.exists(path):
            break
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, default=repr)
    os.replace(tmp, path)
    _prune(d, path, f"-{kind}.json")
    return path


def _prune(d: str, new: str, own: str) -> None:
    """Down to `hpx.flight.max_bundles`, the bundle just written
    (`new`) kept: the oldest of its own kind (file names ending in
    `own`) goes first, so that a kind that fires often, as `slow_step`
    may, evicts its own and not the one shed or failover bundle beside
    them."""
    keep = max(1, _cfg().get_int("hpx.flight.max_bundles", 8))
    try:
        bundles = sorted(
            (os.path.join(d, n) for n in os.listdir(d)
             if n.startswith("flight-") and n.endswith(".json")),
            key=os.path.getmtime)
    except OSError:
        return
    old = [p for p in bundles if p != new]
    while len(old) >= keep:
        path = next((p for p in old if p.endswith(own)), old[0])
        old.remove(path)
        try:
            os.remove(path)
        except OSError:
            pass


def record_fault(kind: str, site: Optional[str] = None, rid: Any = None,
                 error: Optional[BaseException] = None,
                 timeline: Any = None,
                 extra: Optional[Dict[str, Any]] = None) -> Optional[str]:
    """Capture and persist one bundle; returns its path, or None when
    disabled or the capture failed.  Never raises — this runs on fault
    paths where a second failure must not mask the first."""
    global _captures, _dropped
    try:
        if not _cfg().get_bool("hpx.flight.enabled", True):
            return None
        path = _persist(build_bundle(kind, site=site, rid=rid,
                                     error=error, timeline=timeline,
                                     extra=extra))
        _captures += 1
        return path
    except Exception:  # noqa: BLE001 — recorder must not break recovery
        _dropped += 1
        return None


# ---------------------------------------------------------------------------
# bundle index (the `list` CLI and the opsplane /flightz route share it)
# ---------------------------------------------------------------------------

def bundle_index(d: Optional[str] = None) -> List[Dict[str, Any]]:
    """Age-sorted (newest first) index of the on-disk bundles: name,
    age, trigger reason/site/rid, and schema version — enough for an
    operator to pick which bundle to fetch without opening each one.
    Unreadable files still index (an operator must see a truncated
    bundle exists), with ``error`` set."""
    d = flight_dir() if d is None else d
    try:
        names = [n for n in os.listdir(d)
                 if n.startswith("flight-") and n.endswith(".json")]
    except OSError:
        return []
    now = time.time()
    out: List[Dict[str, Any]] = []
    for name in names:
        path = os.path.join(d, name)
        entry: Dict[str, Any] = {"name": name, "path": path}
        try:
            entry["mtime"] = os.path.getmtime(path)
            entry["age_s"] = round(max(0.0, now - entry["mtime"]), 3)
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
            trig = doc.get("trigger") or {}
            entry["reason"] = trig.get("kind")
            entry["site"] = trig.get("site")
            entry["rid"] = trig.get("rid")
            entry["schema"] = doc.get("schema")
            blame = (doc.get("extra") or {}).get("blame")
            if blame is not None:       # a slow step's account
                entry["blame"] = blame
        except (OSError, ValueError) as e:
            entry["error"] = repr(e)
        out.append(entry)
    out.sort(key=lambda e: (-e.get("mtime", 0.0), e["name"]))
    return out


# ---------------------------------------------------------------------------
# schema validation (tests + CLI)
# ---------------------------------------------------------------------------

_REQUIRED_KEYS = ("schema", "wall_time", "trigger", "spans", "counters",
                  "config", "programs", "timeline")


def validate_bundle(doc: Dict[str, Any]) -> List[str]:
    """Structural check of one bundle; returns a list of problems
    (empty = valid)."""
    errs: List[str] = []
    if not isinstance(doc, dict):
        return ["bundle is not an object"]
    if doc.get("schema") != FLIGHT_SCHEMA:
        errs.append(f"schema {doc.get('schema')!r} != {FLIGHT_SCHEMA!r}")
    for k in _REQUIRED_KEYS:
        if k not in doc:
            errs.append(f"missing key {k!r}")
    trig = doc.get("trigger")
    if not isinstance(trig, dict) or "kind" not in trig:
        errs.append("trigger must be an object with a 'kind'")
    if not isinstance(doc.get("spans"), list):
        errs.append("spans must be a list")
    counters = doc.get("counters")
    if not (isinstance(counters, dict)
            and isinstance(counters.get("histograms"), dict)
            and isinstance(counters.get("counters"), dict)):
        errs.append("counters must hold 'histograms' and 'counters'")
    if not isinstance(doc.get("config"), dict):
        errs.append("config must be an object")
    progs = doc.get("programs")
    if progs is not None and not (
            isinstance(progs, dict)
            and isinstance(progs.get("programs"), list)):
        errs.append("programs must be null or a profile table")
    if not isinstance(doc.get("timeline"), list):
        errs.append("timeline must be a list")
    tier = doc.get("tier")
    if tier is not None and not isinstance(tier, dict):
        errs.append("tier must be absent or an object")
    return errs


# ---------------------------------------------------------------------------
# one-shot CLI:  python -m hpx_tpu.svc.flight dump [--out PATH]
#                python -m hpx_tpu.svc.flight --list | --tail N
# ---------------------------------------------------------------------------

def _print_index(tail: int) -> int:
    """The ``--list`` view: one line per bundle, newest first —
    exactly what the opsplane /flightz route serves as JSON."""
    entries = bundle_index()
    if tail > 0:
        entries = entries[:tail]
    for e in entries:
        if "error" in e:
            print(f"{e['name']}  error={e['error']}")
            continue
        blame = f"  blame={e['blame']}" if "blame" in e else ""
        print(f"{e['name']}  age={e['age_s']:.1f}s  "
              f"reason={e['reason']}  site={e['site']}  "
              f"rid={e['rid']}  schema={e['schema']}{blame}")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        prog="python -m hpx_tpu.svc.flight",
        description="fault flight recorder tools")
    ap.add_argument("--list", action="store_true", dest="list_",
                    help="print the age-sorted bundle index "
                         "(reason/rid/schema per line) and exit")
    ap.add_argument("--tail", type=int, default=0, metavar="N",
                    help="list only the newest N bundles")
    sub = ap.add_subparsers(dest="cmd", required=False)
    dump = sub.add_parser("dump", help="capture one bundle right now")
    dump.add_argument("--out", default=None,
                      help="write here instead of hpx.flight.dir")
    args = ap.parse_args(argv)
    if args.list_ or args.tail > 0:
        return _print_index(args.tail)
    if args.cmd is None:
        ap.print_usage()
        return 2
    if args.cmd == "dump":
        doc = build_bundle("manual", site="cli")
        if args.out:
            tmp = args.out + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, default=repr)
            os.replace(tmp, args.out)
            path = args.out
        else:
            path = _persist(doc)
        problems = validate_bundle(doc)
        print(path)
        for p in problems:
            print(f"warning: {p}")
        return 0 if not problems else 1
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
