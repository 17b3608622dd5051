"""Serving + cache performance counters, per ContinuousServer.

Registers into `svc/performance_counters.py`'s registry, following its
built-in discipline: counters OBSERVE through weakrefs and read 0 once
the server is gone — observability must never keep a retired server
(and its device pools) alive. A refresh hook (run before every
discovery/query, via `register_refresh_hook`) garbage-collects the
names of dead servers so `discover_counters` stays truthful.

Every server gets the serving counters::

    /serving{locality#L/server#i}/queue/depth       queued requests
    /serving{locality#L/server#i}/slots/occupancy   live slots / slots
    /serving{locality#L/server#i}/tokens/rate       decode tokens/sec
                                                    (windowed RateCounter)
    /serving{locality#L/server#i}/prefill/chunks    prefill chunk dispatches
    /serving{locality#L/server#i}/prefill/latent_groups  walks of the scratch
                                those chunks' latent layers made (over chunks x
                                latent layers: wide chunks walk in groups)
    /serving{locality#L/server#i}/prefill/pending   in-flight chunked prefills
    /serving{locality#L/server#i}/prefill/admit-wait-steps  step() calls between
                                a request's slot and its first token's
                                program, summed over the admissions
    /serving{locality#L/server#i}/prefill/chunk-width    rows of a full chunk
    /serving{locality#L/server#i}/prefill/chunk-derived  1: the width follows
                                the device's ridge; 0: an argument or the
                                config key stated it
    /serving{locality#L/server#i}/prefill/rows-per-chunk prompt tokens a
                                chunk dispatch carried (mean so far)
    /serving{locality#L/server#i}/programs/cache-hits    program-cache hits
    /serving{locality#L/server#i}/programs/cache-misses  program builds (compiles)
    /serving{locality#L/server#i}/reads/overlapped  blocking device->host reads
                                with a decode step queued behind the value
    /serving{locality#L/server#i}/reads/draining    ... with none: the read
                                empties the dispatch queue
    /serving{locality#L/server#i}/steps/slow        step() calls the step's
                                account called slow (svc/tracing.py: over
                                250 ms and 4 paces a program queued ahead
                                of it (decode steps unread, chunks since
                                the last read), or the end of a block of
                                32 over 2.5 median blocks and 1 s more)
    /serving{locality#L/server#i}/steps/slow-seconds  ... and the seconds they
                                took beyond the median

Speculative servers (``hpx.serving.spec.enable``) add::

    /serving{locality#L/server#i}/spec/drafted          draft tokens proposed
    /serving{locality#L/server#i}/spec/accepted         draft tokens accepted
    /serving{locality#L/server#i}/spec/acceptance-rate  accepted / drafted
    /serving{locality#L/server#i}/spec/tokens-per-step  emitted / spec steps

(the default ``hpx.trace.counters`` pattern ``/serving*`` matches
these, so the Chrome-trace counter sampler picks up an
acceptance-rate track with no extra config).

MoE servers (``cfg.n_experts > 0``) add the expert-routing feed::

    /serving{locality#L/server#i}/moe/tokens-routed   routing claims honored
    /serving{locality#L/server#i}/moe/tokens-dropped  claims over capacity
    /serving{locality#L/server#i}/moe/expert#e/occupancy  latest capacity
                                                          fraction, per expert
    /serving{locality#L/server#i}/moe/experts-hit     distinct experts hit a
                                                      decode step and sparse
                                                      layer (mean since start)
    /serving{locality#L/server#i}/moe/routed-here     under a group-limited
                                                      router: assignments to
                                                      the held experts
    /serving{locality#L/server#i}/moe/tokens-here     ... and tokens (a sparse
                                                      layer each) whose kept
                                                      groups include a held one

Every server also exports the counters of its cache::

    /cache{locality#L/server#i}/hit-rate                radix prefix hit rate
    /cache{locality#L/server#i}/blocks/in-use           pool blocks allocated
    /cache{locality#L/server#i}/blocks/free             pool blocks free
    /cache{locality#L/server#i}/blocks/shared           blocks with more than one
                                                        holder (a published prefix
                                                        and its readers)
    /cache{locality#L/server#i}/blocks/radix-held       blocks retained by the tree
    /cache{locality#L/server#i}/count/evictions         LRU chains dropped
    /cache{locality#L/server#i}/prefill-tokens/saved    prompt tokens NOT recomputed
    /cache{locality#L/server#i}/prefill-tokens/computed prompt tokens prefilled
    /cache{locality#L/server#i}/count/hbm-read-per-token  mapped blocks streamed
                                                          per decode token
    /cache{locality#L/server#i}/bytes/hbm-read-per-token  dtype-aware bytes of the
                                                          above (int8/fp8 scale
                                                          sidecars incl. — fp8 pools
                                                          report the ~0.25x ratio vs
                                                          an f32 compute dtype)
    /cache{locality#L/server#i}/count/walk-entries-per-slot  table entries the
                                                          bounded `fused` walk
                                                          visits per live slot
    /cache{locality#L/server#i}/walk-share              the same over the table's
                                                          width
    /cache{locality#L/server#i}/count/heads-per-copy    kv heads one copy of a
                                                          table entry carries (a
                                                          grid step's group; 0:
                                                          the grid walk)
    /cache{locality#L/server#i}/count/walk-copies-per-slot  DMA descriptors (K and
                                                          V) a slot, layer and
                                                          step
    /cache{locality#L/server#i}/count/walk-bank-sets    sets of banks the walk's
                                                          copies land in (2: a
                                                          grid step copies the
                                                          next one's entries; 0:
                                                          the grid walk)
    /cache{locality#L/server#i}/walk-steps-prefetched-share  grid steps of a
                                                          layer's call whose copies
                                                          the step before started

Models with recurrent ("kda", "lightning", "mamba") layers add their per-slot
state, models with latent-attention ("mla") layers their rows on the
full group, models with sparse layers their index and what the decode
steps' queries chose (from the positions: the device's choice is never
read)::

    /cache{locality#L/server#i}/index/rows              compressed-key entries of
                                                        the blocks held, a sparse
                                                        layer and kv head
    /serving{locality#L/server#i}/sparse/blocks-selected  blocks chosen, a sparse
                                                        layer and kv group, summed
                                                        over live slots and steps
    /serving{locality#L/server#i}/sparse/rows-walked    rows of those blocks a
                                                        query could see
    /serving{locality#L/server#i}/sparse/rows-live      rows the same queries had
                                                        behind them

    /cache{locality#L/server#i}/state/bytes             the state arrays, all slots
    /cache{locality#L/server#i}/state/slots-live        slots whose state is a request's
    /cache{locality#L/server#i}/state/resets            admissions that zeroed a state
    /cache{locality#L/server#i}/latent/blocks-in-use    blocks of latent rows held
    /cache{locality#L/server#i}/latent/rows-walked      rows a decode step's latent
                                                        walks read, a latent layer
    /cache{locality#L/server#i}/latent/run-pct          of the table entries those
                                                        walks cover, the share copied
                                                        with their group's neighbours
                                                        in one descriptor
    /cache{locality#L/server#i}/latent/entries-walked   the decode steps' so far, ...
    /cache{locality#L/server#i}/latent/entries-coalesced ... and those so copied
    /serving{locality#L/server#i}/state/prefix-refused  admissions whose prefix match
                                                        was refused (no state snapshot)
    /serving{locality#L/server#i}/state/reprefills      restores that recomputed a state

Models with "eva" layers add their two grains of rows (one table a
slot: its summary blocks, then its window's exact blocks)::

    /cache{locality#L/server#i}/eva/exact-rows          exact rows of the live
                                                        slots' windows under way
    /cache{locality#L/server#i}/eva/summary-rows        summary rows the live slots'
                                                        tables make visible
    /cache{locality#L/server#i}/eva/rolls               windows completed, in prefill
                                                        and in decode (cumulative)
    /cache{locality#L/server#i}/eva/blocks-freed        exact blocks the rolls gave
                                                        back, a window's at once

Models with window layers add their second block group::

    /cache{locality#L/server#i}/window/blocks-in-use    window-group blocks held
    /cache{locality#L/server#i}/window/blocks-freed     blocks released behind a
                                                        window (cumulative)
    /cache{locality#L/server#i}/window/prefix-refused   admissions whose prefix
                                                        match was refused

Tiered servers (``hpx.cache.tier.enable``) add the host-tier feed::

    /cache{locality#L/server#i}/tier/bytes-held         host bytes retained
    /cache{locality#L/server#i}/tier/entries            demoted blocks held
    /cache{locality#L/server#i}/tier/count/demoted      evictions the tier kept
    /cache{locality#L/server#i}/tier/count/promoted     blocks restored to device
    /cache{locality#L/server#i}/tier/count/dropped      LRU'd out of the tier
    /cache{locality#L/server#i}/tier/count/declined     gate chose re-prefill
    /cache{locality#L/server#i}/tier/hit-depth-blocks   cumulative promoted depth
    /cache{locality#L/server#i}/tier/promote-latency-s  promotion histogram
                                                        (+ derived pNN counters)
"""

from __future__ import annotations

import weakref
from typing import Dict, List, Tuple

from ..svc import performance_counters as pc
from ..synchronization import Mutex

__all__ = ["register_fleet", "register_server"]

_lock = Mutex()
_servers: Dict[int, Tuple["weakref.ref", List[str]]] = {}
_next_idx = 0
_fleets: Dict[int, Tuple["weakref.ref", List[str]]] = {}
_next_fleet_idx = 0


def _read(ref, fn):
    """Weakref-observing callback: a collected server reads 0.0."""
    def value() -> float:
        srv = ref()
        if srv is None:
            return 0.0
        return float(fn(srv))
    return value


def register_server(srv) -> str:
    """Register one server's counters; returns its instance name
    (``server#<i>``). Called from ContinuousServer.__init__."""
    global _next_idx
    with _lock:
        idx = _next_idx
        _next_idx += 1
    inst = f"server#{idx}"
    ref = weakref.ref(srv)
    names: List[str] = []

    def put(object_: str, counter: str, c: pc.Counter) -> None:
        name = pc.counter_name(object_, counter, inst)
        pc.register_counter(name, c)
        names.append(name)

    put("serving", "queue/depth",
        pc.CallbackCounter(_read(ref, lambda s: len(s._queue))))
    put("serving", "slots/occupancy",
        pc.CallbackCounter(_read(ref, lambda s: sum(
            r is not None for r in s._slot_req) / max(1, s.slots))))
    # the server's own windowed tokens/sec counter, registered as-is
    # (RateCounter IS a Counter); it holds no reference back
    put("serving", "tokens/rate", srv._rate)
    put("serving", "prefill/chunks",
        pc.CallbackCounter(_read(ref, lambda s: s._chunks)))
    put("serving", "prefill/latent_groups",
        pc.CallbackCounter(_read(ref, lambda s: s._latent_groups)))
    put("serving", "prefill/pending",
        pc.CallbackCounter(_read(ref, lambda s: len(s._pending))))
    put("serving", "prefill/admit-wait-steps",
        pc.CallbackCounter(_read(ref, lambda s: s._admit_wait_steps)))
    put("serving", "prefill/chunk-width",
        pc.CallbackCounter(_read(ref, lambda s: s.prefill_chunk)))
    put("serving", "prefill/chunk-derived",
        pc.CallbackCounter(_read(
            ref, lambda s: s._prefill_chunk_src == "ridge")))
    put("serving", "prefill/rows-per-chunk",
        pc.CallbackCounter(_read(
            ref, lambda s: s.prefill_stats()["prefill_rows_per_chunk"])))
    put("serving", "programs/cache-hits",
        pc.CallbackCounter(_read(ref, lambda s: s._prog_hits)))
    put("serving", "programs/cache-misses",
        pc.CallbackCounter(_read(ref, lambda s: s._prog_misses)))

    # blocking device->host reads, by whether a decode step was
    # queued behind the value read (ContinuousServer.read_stats)
    put("serving", "reads/overlapped",
        pc.CallbackCounter(_read(ref, lambda s: s._reads_overlapped)))
    put("serving", "reads/draining",
        pc.CallbackCounter(_read(ref, lambda s: s._reads_draining)))
    # the step's account (ContinuousServer.step_accounts)
    put("serving", "steps/slow",
        pc.CallbackCounter(_read(ref, lambda s: s._acct.slow)))
    put("serving", "steps/slow-seconds",
        pc.CallbackCounter(_read(ref, lambda s: s._acct.slow_ns / 1e9)))

    # fault/recovery ladder observability (svc/faultinject +
    # ContinuousServer.fault_stats): injected faults seen, step
    # retries, checkpoint restores, typed sheds, degradations
    put("serving", "faults/injected",
        pc.CallbackCounter(_read(ref, lambda s: s._flt_injected)))
    put("serving", "faults/retried",
        pc.CallbackCounter(_read(ref, lambda s: s._flt_retried)))
    put("serving", "faults/restored",
        pc.CallbackCounter(_read(ref, lambda s: s._flt_restored)))
    put("serving", "faults/shed",
        pc.CallbackCounter(_read(ref, lambda s: s._flt_shed)))
    put("serving", "faults/degraded",
        pc.CallbackCounter(_read(ref, lambda s: s._flt_degraded)))
    put("serving", "faults/restore-p99-s",
        pc.CallbackCounter(_read(ref, lambda s: s.fault_stats()
                           ["restore_p99_s"])))

    # SLO latency distributions: the server's live HistogramCounters
    # registered as-is (a histogram IS a Counter, value = mean, and
    # holds no reference back) plus derived pNN quantile counters —
    # /serving{...}/latency/ttft-s, .../ttft-s/p99, ...
    from ..svc.metrics import register_histogram
    _HIST_KEYS = (("ttft", "latency/ttft-s"),
                  ("queue_wait", "latency/queue-wait-s"),
                  ("transfer", "latency/transfer-s"),
                  ("decode_stall", "latency/decode-stall-s"),
                  ("e2e", "latency/e2e-s"))
    for attr, cname in _HIST_KEYS:
        names.extend(register_histogram("serving", cname,
                                        srv.hist[attr], inst))

    if getattr(srv, "_spec", False):
        put("serving", "spec/drafted",
            pc.CallbackCounter(_read(ref, lambda s: s._spec_drafted)))
        put("serving", "spec/accepted",
            pc.CallbackCounter(_read(ref, lambda s: s._spec_accepted)))
        put("serving", "spec/acceptance-rate",
            pc.CallbackCounter(_read(ref, lambda s: (
                s._spec_accepted / s._spec_drafted
                if s._spec_drafted else 0.0))))
        put("serving", "spec/tokens-per-step",
            pc.CallbackCounter(_read(ref, lambda s: (
                s._spec_emitted / s._spec_steps
                if s._spec_steps else 0.0))))

    if getattr(srv.cfg, "n_experts", 0) > 0:
        # expert-parallel MoE decode routing (models/moe): routing
        # claims routed vs dropped-over-capacity (capacity-factor
        # knob), plus each expert's latest occupancy fraction —
        # /serving{...}/moe/*. Fed from the per-step stats vector the
        # decode/verify programs return, drained at flush boundaries.
        put("serving", "moe/tokens-routed",
            pc.CallbackCounter(_read(ref, lambda s: s._moe_routed)))
        put("serving", "moe/tokens-dropped",
            pc.CallbackCounter(_read(ref, lambda s: s._moe_dropped)))
        for e in range(len(srv._moe_occ)):      # the experts held
            put("serving", f"moe/expert#{e}/occupancy",
                pc.CallbackCounter(_read(
                    ref, lambda s, e=e: s._moe_occ[e])))
        if getattr(srv.cfg, "moe_n_group", 1) > 1:
            # a group-limited router's share of the work that fell here
            put("serving", "moe/routed-here",
                pc.CallbackCounter(_read(ref, lambda s: s._moe_here)))
            put("serving", "moe/tokens-here",
                pc.CallbackCounter(_read(
                    ref, lambda s: s._moe_tokens_here)))
        put("serving", "moe/experts-hit",
            pc.CallbackCounter(_read(ref, lambda s: (
                s._moe_hit_sum / s._moe_steps if s._moe_steps else 0.0))))

    if getattr(srv, "_alerts", None) is not None:
        # SLO burn-rate alerting (svc/slo_alerts): evaluation and
        # transition totals — /serving{...}/alerts/*. `active` is the
        # number of rules currently in the alerting state, so a trace
        # or /varz scrape shows incident windows as a step function.
        put("serving", "alerts/evals",
            pc.CallbackCounter(_read(ref, lambda s: s._alerts.evals)))
        put("serving", "alerts/fired",
            pc.CallbackCounter(_read(ref, lambda s: s._alerts.fired)))
        put("serving", "alerts/cleared",
            pc.CallbackCounter(_read(ref, lambda s: s._alerts.cleared)))
        put("serving", "alerts/active",
            pc.CallbackCounter(_read(ref, lambda s: s._alerts.active())))

    put("cache", "hit-rate",
        pc.CallbackCounter(_read(ref, lambda s: s._radix.hit_rate())))
    put("cache", "blocks/in-use",
        pc.CallbackCounter(_read(ref, lambda s: s._alloc.in_use)))
    put("cache", "blocks/free",
        pc.CallbackCounter(_read(ref, lambda s: s._alloc.free_count)))
    put("cache", "blocks/shared",
        pc.CallbackCounter(_read(
            ref, lambda s: s._alloc.shared_count)))
    put("cache", "blocks/radix-held",
        pc.CallbackCounter(_read(ref, lambda s: s._radix.blocks_held)))
    put("cache", "count/evictions",
        pc.CallbackCounter(
            _read(ref, lambda s: s._radix.total_evictions)))
    put("cache", "prefill-tokens/saved",
        pc.CallbackCounter(_read(ref, lambda s: s._prefill_saved)))
    put("cache", "prefill-tokens/computed",
        pc.CallbackCounter(_read(ref, lambda s: s._prefill_computed)))
    # decode-attention HBM roofline feed: mapped blocks (and their
    # dtype-aware bytes, int8/fp8 scale sidecars included) streamed
    # per generated token — see ContinuousServer.hbm_read_stats
    put("cache", "count/hbm-read-per-token",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["hbm_read_blocks_per_token"])))
    put("cache", "bytes/hbm-read-per-token",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["hbm_read_bytes_per_token"])))
    # how far the bounded `fused` walk goes (entries a live slot,
    # and the share of the table's width)
    put("cache", "count/walk-entries-per-slot",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["walk_entries_per_slot"])))
    put("cache", "walk-share",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["walk_share"])))
    # the kv heads that share one copy of an entry, and the copies
    # a slot, layer and step then issues
    put("cache", "count/heads-per-copy",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["heads_per_copy"])))
    put("cache", "count/walk-copies-per-slot",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["walk_copies_per_slot"])))
    # the sets of banks those copies land in, and the grid steps
    # whose copies the step before them started
    put("cache", "count/walk-bank-sets",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["walk_bank_sets"])))
    put("cache", "walk-steps-prefetched-share",
        pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                           ["walk_steps_prefetched_share"])))
    if srv._win:
        # the window block group (serving._init_paged)
        put("cache", "window/blocks-in-use",
            pc.CallbackCounter(_read(
                ref, lambda s: s._walloc.in_use)))
        put("cache", "window/blocks-freed",
            pc.CallbackCounter(_read(ref, lambda s: s._win_freed)))
        put("cache", "window/prefix-refused",
            pc.CallbackCounter(_read(
                ref, lambda s: s._prefix_refused)))
    if srv._recurrent:
        # the per-slot recurrent state (serving._init_paged): no
        # blocks, reset at admission, recomputed at a restore
        put("cache", "state/bytes",
            pc.CallbackCounter(_read(ref, lambda s: s._state_bytes)))
        put("cache", "state/slots-live",
            pc.CallbackCounter(_read(ref, lambda s: sum(
                r is not None for r in s._slot_req)
                + len(s._pending))))
        put("cache", "state/resets",
            pc.CallbackCounter(_read(
                ref, lambda s: s._state_resets)))
        put("serving", "state/prefix-refused",
            pc.CallbackCounter(_read(
                ref, lambda s: s._prefix_refused)))
        put("serving", "state/reprefills",
            pc.CallbackCounter(_read(ref, lambda s: s._reprefills)))
    if "mla" in getattr(srv.cfg, "layer_mixer", ()):
        # latent rows live on the full group's blocks
        put("cache", "latent/blocks-in-use",
            pc.CallbackCounter(_read(ref, lambda s: s._alloc.in_use)))
        # rows a decode step's latent walks read, a latent layer
        put("cache", "latent/rows-walked",
            pc.CallbackCounter(_read(ref, lambda s: s.cache_stats()
                               ["latent_rows_walked_per_step"])))
        # of the table entries the next step's latent walks cover, the
        # share the kernel copies with their group's neighbours in one
        # descriptor; and the entries walked / so copied so far
        put("cache", "latent/run-pct",
            pc.CallbackCounter(_read(ref, lambda s: s.hbm_read_stats()
                               ["latent_run_pct"])))
        put("cache", "latent/entries-walked",
            pc.CallbackCounter(_read(
                ref, lambda s: s._latent_walked)))
        put("cache", "latent/entries-coalesced",
            pc.CallbackCounter(_read(
                ref, lambda s: s._latent_coalesced)))
    if "sparse" in getattr(srv.cfg, "layer_mixer", ()):
        # the index of compressed keys beside a sparse layer's K/V
        # pools, and what the decode steps' selections read
        put("cache", "index/rows",
            pc.CallbackCounter(_read(ref, lambda s: s.cache_stats()
                               ["index_rows"])))
        put("serving", "sparse/blocks-selected",
            pc.CallbackCounter(_read(
                ref, lambda s: s._sparse_blocks)))
        put("serving", "sparse/rows-walked",
            pc.CallbackCounter(_read(
                ref, lambda s: s._sparse_rows_walked)))
        put("serving", "sparse/rows-live",
            pc.CallbackCounter(_read(
                ref, lambda s: s._sparse_rows_live)))
    if "eva" in getattr(srv.cfg, "layer_mixer", ()):
        # rows at two grains in one run a slot, and the rolls' clocks
        for name, key in (("exact-rows", "eva_exact_rows"),
                          ("summary-rows", "eva_summary_rows"),
                          ("rolls", "eva_rolls"),
                          ("blocks-freed", "eva_blocks_freed")):
            put("cache", "eva/" + name, pc.CallbackCounter(_read(
                ref, lambda s, key=key: s.cache_stats()[key])))
    if srv._tier is not None:
        # host-RAM demotion tier (cache/tier.py): occupancy,
        # demote/promote/drop/decline totals, cumulative hit
        # depth, and the promotion-latency histogram (with its
        # derived pNN quantile counters) — /cache{...}/tier/*
        put("cache", "tier/bytes-held",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.stats()["tier_bytes_held"])))
        put("cache", "tier/entries",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.stats()["tier_entries"])))
        put("cache", "tier/count/demoted",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.total_demoted)))
        put("cache", "tier/count/promoted",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.total_promoted)))
        put("cache", "tier/count/dropped",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.total_dropped)))
        put("cache", "tier/count/declined",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.total_declined)))
        put("cache", "tier/hit-depth-blocks",
            pc.CallbackCounter(_read(
                ref, lambda s: s._tier.hit_depth_blocks)))
        names.extend(register_histogram(
            "cache", "tier/promote-latency-s", srv._tier_hist,
            inst))

    with _lock:
        _servers[idx] = (ref, names)
    return inst


def register_fleet(rt) -> str:
    """Register one FleetRouter's ``/serving{...}/fleet/*`` counters;
    returns its instance name (``fleet#<i>``). Called from
    svc/fleet.FleetRouter.__init__, same weakref discipline as
    :func:`register_server` — a collected router reads 0 and its
    names GC out of discovery.

    Per-worker queue-depth counters register up to the AUTOSCALE
    CEILING (``fleet/worker#k/queue-depth``): an index past the
    current pool reads 0, so scale-up/-down changes values, never the
    counter namespace (discovery stays stable across a wave)."""
    global _next_fleet_idx
    with _lock:
        idx = _next_fleet_idx
        _next_fleet_idx += 1
    inst = f"fleet#{idx}"
    ref = weakref.ref(rt)
    names: List[str] = []

    def put(counter: str, c: pc.Counter) -> None:
        name = pc.counter_name("serving", counter, inst)
        pc.register_counter(name, c)
        names.append(name)

    put("fleet/placed/prefix",
        pc.CallbackCounter(_read(ref, lambda r: r._placed_prefix)))
    put("fleet/placed/load",
        pc.CallbackCounter(_read(ref, lambda r: r._placed_load)))
    put("fleet/digest/staleness-s",
        pc.CallbackCounter(_read(ref,
                                 lambda r: r.digest_staleness_s())))
    put("fleet/autoscale/up",
        pc.CallbackCounter(_read(ref, lambda r: r._autoscale_up)))
    put("fleet/autoscale/down",
        pc.CallbackCounter(_read(ref, lambda r: r._autoscale_down)))
    put("fleet/prefill-tokens/saved",
        pc.CallbackCounter(_read(ref,
                                 lambda r: r.prefill_tokens_saved)))
    put("fleet/workers/decode",
        pc.CallbackCounter(_read(ref,
                                 lambda r: len(r._alive(r._decode)))))
    put("fleet/queue/depth",
        pc.CallbackCounter(_read(ref, lambda r: (len(r._qi)
                                                 + len(r._qb)))))
    for k in range(int(rt._pool_max)):
        put(f"fleet/worker#{k}/queue-depth",
            pc.CallbackCounter(_read(
                ref, lambda r, k=k: r.worker_queue_depth(k))))

    # fleet-wide SLO quantiles: merge() of every per-worker histogram,
    # computed at query time (so the value is BY CONSTRUCTION equal to
    # the merge of the per-worker distributions, the acceptance
    # contract serving_bench asserts) — /serving{locality#L/fleet#i}/
    # latency/ttft-s/p99 etc.
    from ..svc.metrics import (LATENCY_KEYS, configured_quantiles,
                               quantile_label)
    _CNAMES = {"ttft": "latency/ttft-s",
               "queue_wait": "latency/queue-wait-s",
               "transfer": "latency/transfer-s",
               "decode_stall": "latency/decode-stall-s",
               "e2e": "latency/e2e-s"}
    for key in LATENCY_KEYS:
        for q in configured_quantiles():
            put(f"{_CNAMES[key]}/{quantile_label(q)}",
                pc.CallbackCounter(_read(
                    ref, lambda r, k=key, q=q:
                    r.merged_hist()[k].quantile(q))))

    with _lock:
        _fleets[idx] = (ref, names)
    return inst


def _refresh() -> None:
    """Refresh hook: unregister the counters of collected servers (the
    reverse of the builtins' lazily-appearing pools — servers lazily
    DISAPPEAR)."""
    with _lock:
        dead = [(i, names) for i, (ref, names) in _servers.items()
                if ref() is None]
        for i, _ in dead:
            del _servers[i]
        dead_fleets = [(i, names) for i, (ref, names)
                       in _fleets.items() if ref() is None]
        for i, _ in dead_fleets:
            del _fleets[i]
    for _, names in dead + dead_fleets:
        for n in names:
            pc.unregister_counter(n)


pc.register_refresh_hook(_refresh)
