#!/usr/bin/env python
"""Offline shape search: re-derive the serving ladders from the perfdb.

The online AdaptiveTuner (svc/autotune) walks baked ladders one
bounded step at a time; this tool re-derives the ladders themselves —
the geometric prefill-bucket geometry, the paged block-size table, the
spec-k bounds and the per-knob ``Tunable(lo,hi,step)`` ranges — from
the cost surface the persistent perf store (svc/perfdb) banked across
runs.  Compile-heavy exploration happens HERE, offline; a serving
process only ever reads the winning ladder at boot
(``hpx.perfdb.use_learned_ladders=1``).

Search objective (per store key, deterministic — no clocks, no RNG):
candidate bucket ladders are the subsets of the geometric doubling
ladder ``{8, 16, ..., chunk}`` that contain the chunk.  Each candidate
``L`` is scored as a serving-time rate: predicted warm padding cost
plus amortized compile cost, both dimensionless fractions of the
serving horizon::

    score(L) = frac_prefill * E_len[cost_L(len)] / chunk  # padded work
             + |L| * c_compile / amortize_s               # ladder mint

``cost_L(len) = max(rung_L(len), 32)`` — the per-chunk cost floor:
below ~32 rows a chunk dispatch is overhead-bound (fixed XLA dispatch
cost on CPU, the 8x128 minimum MXU tile on TPU), so padding a tiny
prompt up to a 32-wide bucket is free in wall-clock terms and the
search correctly prunes sub-floor rungs without predicting a warm
regression.  ``frac_prefill`` is the fraction of warm wall-clock the
store attributes to prefill (the ``prefill_frac`` metric serving
bench's ladder seed banks from a prefill-only probe drive; falls back
to the per-program ``exec_p50_s`` share of chunk-tagged programs,
then to 1.0 — the never-prune direction) — a coarser ladder only
pads THAT slice of the run, which keeps the search from collapsing
to the single-rung ladder on decode-dominated mixes.  ``c_compile``
is the banked mean compile seconds per program (``compile_s``;
serving_bench's ladder seed banks the honest cold-minus-warm
wall-clock estimate), the expectation over lengths uses the banked
per-rung ``chunk_demand`` histogram when present (the measured
workload, remainder chunks included) and falls back to uniform on
``[1, chunk]``, and ``amortize_s`` is the same horizon the online
tuner charges compile-minting moves (hpx.tune.compile_amortize_s
semantics).  Lowest score wins; ties break toward FEWER rungs, then
lexicographically — so the proposal is a pure function of the store
and byte-identical across runs (pinned by tests/test_perfdb.py).

Paged block sizes: keys carrying flash_tune's ``paged_step_us``
sweeps (program = ``bs<N>``) get their per-(head_dim, kv_dtype)
winner re-derived by argmin mean microseconds and banked into the
store's learned-blocks tier.

Provenance: a ladder derived from builder-session-only samples is
REFUSED (printed, not installed) unless ``--allow-session`` — the
same honesty discipline as bench.py's on-chip medians.  Offline
arbitration: pass ``--gate-base``/``--gate-cand`` metrics artifacts
and the install is additionally gated on benchmarks/slo_gate.py
finding no bounded-error quantile regression.

Usage::

    python benchmarks/ladder_search.py --db PATH
        [--key KEY]            # default: every key in the store
        [--chunk 128] [--min-samples 3] [--amortize-s 30]
        [--allow-session] [--dry-run]
        [--gate-base BASELINE.json --gate-cand CANDIDATE.json]

Exit status: 0 = at least one ladder installed (or --dry-run),
1 = nothing derivable, 2 = bad input, 3 = slo gate refused.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Optional, Tuple

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from hpx_tpu.svc.perfdb import (  # noqa: E402
    PERFDB_SCHEMA, PerfDB, PerfDBSchemaError)

DEFAULT_CHUNK = 128
DEFAULT_AMORTIZE_S = 30.0

# per-chunk dispatch cost floor, in padded rows: below this width a
# chunk program is overhead-bound (fixed dispatch cost on CPU, the
# 8x128 minimum MXU tile on TPU), so rungs under the floor cost the
# same wall-clock as a floor-width rung
DISPATCH_FLOOR_ROWS = 32


def _geometric_ladder(chunk: int) -> List[int]:
    out, w = [], 8
    while w < chunk:
        out.append(w)
        w *= 2
    out.append(chunk)
    return out


def _candidates(chunk: int) -> List[Tuple[int, ...]]:
    """Every subset of the doubling ladder that keeps the chunk rung
    (the ladder contract: every chunk has a bucket), deterministic
    order."""
    rungs = _geometric_ladder(chunk)
    lower, out = rungs[:-1], []
    for mask in range(1 << len(lower)):
        cand = tuple(sorted(
            [r for i, r in enumerate(lower) if mask >> i & 1]
            + [chunk]))
        out.append(cand)
    return sorted(set(out), key=lambda c: (len(c), c))


def _expected_rung(ladder: Tuple[int, ...], chunk: int) -> float:
    """E[rung(len)] for len uniform on [1, chunk]: each rung serves
    the lengths between its predecessor and itself."""
    total, prev = 0.0, 0
    for r in ladder:
        total += (r - prev) * r
        prev = r
    return total / chunk


def _expected_cost(ladder: Tuple[int, ...], chunk: int,
                   floor: int = DISPATCH_FLOOR_ROWS) -> float:
    """E[cost(len)] for len uniform on [1, chunk], where a rung's
    per-chunk cost is max(rung, floor) padded rows — the dispatch
    cost floor makes sub-floor rungs equally priced, so the search
    sees pruning them as free."""
    total, prev = 0.0, 0
    for r in ladder:
        total += (r - prev) * max(r, floor)
        prev = r
    return total / chunk


def _padded_ratio(ladder: Tuple[int, ...],
                  demand: Dict[int, float],
                  floor: int = DISPATCH_FLOOR_ROWS) -> float:
    """Predicted prefill cost of ``ladder`` relative to the ladder
    the demand histogram was measured under: each measured rung's
    demand rounds up to the candidate's smallest rung that covers it,
    priced at max(rung, floor) padded rows."""
    base = sum(d * max(r, floor) for r, d in demand.items())
    if base <= 0:
        return 1.0
    cand = 0.0
    for r, d in demand.items():
        up = min((b for b in ladder if b >= r), default=ladder[-1])
        cand += d * max(up, floor)
    return cand / base


def score_ladder(ladder: Tuple[int, ...], chunk: int,
                 frac_prefill: float, c_compile: float,
                 amortize_s: float,
                 demand: Optional[Dict[int, float]] = None) -> float:
    if demand:
        padded = frac_prefill * _padded_ratio(ladder, demand)
    else:
        padded = frac_prefill * _expected_cost(ladder, chunk) / chunk
    mint = len(ladder) * c_compile / max(amortize_s, 1e-9)
    return padded + mint


def derive_ladder(db: PerfDB, key: str, chunk: int = DEFAULT_CHUNK,
                  min_samples: int = 3,
                  amortize_s: float = DEFAULT_AMORTIZE_S
                  ) -> Optional[Dict[str, Any]]:
    """The deterministic per-key derivation: ladder proposal dict, or
    None when the store lacks a usable cost model for ``key``.  The
    returned dict is a pure function of (store contents, args) — NO
    timestamps, NO environment reads — so the same DB always yields a
    byte-identical proposal (the determinism test pins this)."""
    comp = db.model(key, "compile_s")
    execm = db.model(key, "exec_p50_s")
    if comp.get("n", 0) < min_samples or execm.get("n", 0) < 1:
        return None
    c_compile = comp["mean"]
    # padding only costs the prefill slice of the run.  Preferred
    # source: the wall-clock prefill_frac the ladder seed banks from
    # a prefill-only probe (stable — async dispatch hides compute
    # from per-call timers).  Fallbacks: the per-program exec share
    # of chunk-tagged programs, then 1.0 — charge the whole run,
    # the safe never-prune direction for a sparse store.
    fracm = db.model(key, "prefill_frac")
    if fracm.get("n", 0) >= 1:
        frac_prefill = min(1.0, max(0.0, fracm["mean"]))
    else:
        progs = db.program_models(key, "exec_p50_s")
        chunk_s = sum(m["n"] * m["mean"] for p, m in progs.items()
                      if "chunk" in p)
        total_s = sum(m["n"] * m["mean"] for m in progs.values())
        frac_prefill = chunk_s / total_s if total_s > 0 else 1.0
    # the banked per-rung chunk-demand histogram (mean count per run)
    # re-prices candidates against the measured workload; without it
    # the uniform-length expectation stands in
    demand = {int(p[1:]): m["mean"] for p, m in
              db.program_models(key, "chunk_demand").items()
              if p.startswith("r") and p[1:].isdigit()}
    best: Optional[Tuple[float, Tuple[int, ...]]] = None
    for cand in _candidates(chunk):
        s = score_ladder(cand, chunk, frac_prefill, c_compile,
                         amortize_s, demand=demand)
        if best is None or s < best[0]:
            best = (s, cand)
    assert best is not None
    score, ladder = best
    n = comp["n"] + execm["n"]
    onchip_n = comp.get("onchip_n", 0) + execm.get("onchip_n", 0)
    onchip = onchip_n == n and n > 0
    spec_hi = max(1, ladder[-1] - 1)
    # spec-k bounds ride the derived ladder (the verify window is a
    # bucket); best stays the declared default clamped into range —
    # acceptance-rate adaptation remains the ONLINE tuner's job
    spec_k = {"lo": 1, "hi": min(16, spec_hi),
              "best": min(4, spec_hi)}
    return {
        "prefill_buckets": list(ladder),
        "prefill_chunk": chunk,
        "spec_k": spec_k,
        "tunables": {
            "hpx.serving.prefill_chunk": {
                "lo": ladder[0], "hi": chunk, "step": 2},
            "hpx.serving.spec.k": {
                "lo": spec_k["lo"], "hi": spec_k["hi"], "step": 1},
        },
        "samples": n,
        "onchip": onchip,
        "provenance": "on-chip" if onchip else "builder-session",
        "objective": {
            "score": round(score, 9),
            "prefill_frac": round(frac_prefill, 9),
            "c_compile_s": round(c_compile, 9),
            "amortize_s": amortize_s,
            "expected_rung": round(_expected_rung(ladder, chunk), 6),
            "expected_cost": round(_expected_cost(ladder, chunk), 6),
            "padded_ratio": round(_padded_ratio(ladder, demand), 6)
            if demand else None,
            "demand": {str(r): round(demand[r], 3)
                       for r in sorted(demand)} or None,
            "candidates": len(_candidates(chunk)),
        },
    }


def derive_blocks(db: PerfDB, min_samples: int = 3
                  ) -> Dict[str, Dict[str, Any]]:
    """Re-derive the paged block-size table from banked
    ``paged_step_us`` sweeps (flash_tune --paged --perfdb): for each
    (head_dim, kv_dtype) seen, argmin mean microseconds over the
    ``bs<N>`` programs.  Deterministic: ties break toward the smaller
    block."""
    out: Dict[str, Dict[str, Any]] = {}
    sweeps: Dict[str, Dict[int, Dict[str, Any]]] = {}
    for key in db.keys():
        parts = key.split("|")
        if len(parts) != 5 or not parts[1].startswith("paged.hd"):
            continue
        hd = parts[1].split(".")[1][2:]        # paged.hd128.s2048
        bkey = f"hd{hd}x{parts[2]}"
        for row in db.observations:
            if row["key"] != key or row["metric"] != "paged_step_us" \
                    or not str(row.get("program", "")).startswith("bs"):
                continue
            bs = int(str(row["program"])[2:])
            cur = sweeps.setdefault(bkey, {}).setdefault(
                bs, {"sum": 0.0, "n": 0, "onchip_n": 0})
            cur["sum"] += float(row["value"])
            cur["n"] += 1
            cur["onchip_n"] += 1 if row.get("onchip") else 0
    for bkey in sorted(sweeps):
        table = sweeps[bkey]
        total = sum(c["n"] for c in table.values())
        if total < min_samples:
            continue
        best_bs = min(sorted(table),
                      key=lambda b: (table[b]["sum"] / table[b]["n"], b))
        onchip = all(c["onchip_n"] == c["n"] for c in table.values())
        out[bkey] = {
            "block_size": best_bs, "samples": total,
            "onchip": onchip,
            "provenance": "on-chip" if onchip else "builder-session",
        }
    return out


def _slo_gate(base: str, cand: str) -> List[Any]:
    """Offline candidate arbitration via benchmarks/slo_gate.py:
    regressions between two metrics artifacts (bounded-error quantile
    compare).  Empty list = candidate admissible."""
    from slo_gate import compare, load_artifact, regressions
    return regressions(compare(load_artifact(base),
                               load_artifact(cand)))


def _arg(name: str) -> Optional[str]:
    if name in sys.argv:
        return sys.argv[sys.argv.index(name) + 1]
    return None


def main() -> int:
    db_path = _arg("--db")
    if not db_path:
        print(json.dumps({"error": "--db PATH is required"}))
        return 2
    try:
        db = PerfDB(db_path)
    except PerfDBSchemaError as e:
        print(json.dumps({"error": str(e), "schema": PERFDB_SCHEMA}))
        return 2
    chunk = int(_arg("--chunk") or DEFAULT_CHUNK)
    min_samples = int(_arg("--min-samples") or 3)
    amortize_s = float(_arg("--amortize-s") or DEFAULT_AMORTIZE_S)
    allow_session = "--allow-session" in sys.argv
    dry = "--dry-run" in sys.argv
    only_key = _arg("--key")

    gate_base, gate_cand = _arg("--gate-base"), _arg("--gate-cand")
    if gate_base and gate_cand:
        regs = _slo_gate(gate_base, gate_cand)
        if regs:
            for r in regs:
                print(json.dumps({"slo_gate": "regressed",
                                  **r.to_dict()}), flush=True)
            print(json.dumps({"error": "slo gate refused the "
                              "candidate artifact; not installing"}))
            return 3
        print(json.dumps({"slo_gate": "ok", "base": gate_base,
                          "cand": gate_cand}), flush=True)

    keys = [only_key] if only_key else \
        [k for k in db.keys() if not k.split("|")[1].startswith("paged.")]
    installed = 0
    for key in keys:
        prop = derive_ladder(db, key, chunk=chunk,
                             min_samples=min_samples,
                             amortize_s=amortize_s)
        if prop is None:
            print(json.dumps({"key": key, "skipped":
                              "insufficient cost model "
                              f"(need >= {min_samples} compile "
                              "samples and >= 1 exec sample)"}),
                  flush=True)
            continue
        if not prop["onchip"] and not allow_session:
            # the honesty gate: costs never measured on a chip may not
            # mint a "learned" ladder a cold boot silently trusts
            print(json.dumps({"key": key, "refused":
                              "builder-session-only samples; pass "
                              "--allow-session to install anyway",
                              "provenance": prop["provenance"],
                              "samples": prop["samples"]}),
                  flush=True)
            continue
        print(json.dumps({"key": key, "ladder": prop,
                          "installed": not dry}), flush=True)
        if not dry:
            db.record_ladder(key, prop)
            installed += 1

    blocks = derive_blocks(db, min_samples=min_samples)
    for bkey in sorted(blocks):
        entry = blocks[bkey]
        if not entry["onchip"] and not allow_session:
            print(json.dumps({"block": bkey, "refused":
                              "builder-session-only samples"}),
                  flush=True)
            continue
        print(json.dumps({"block": bkey, **entry,
                          "installed": not dry}), flush=True)
        if not dry:
            db.record_block(bkey, entry)
            installed += 1

    if installed and not dry:
        db.save()
        print(json.dumps({"wrote": os.path.abspath(db_path),
                          "installed": installed}), flush=True)
    return 0 if (installed or dry) else 1


if __name__ == "__main__":
    sys.exit(main())
