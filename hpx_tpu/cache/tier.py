"""Host-RAM KV tier: where radix evictions go instead of oblivion.

At scale the shared-prefix working set (system prompts, few-shot
templates, multi-turn sessions) dwarfs HBM but fits comfortably in
host RAM. `RadixCache` eviction used to be leaf-LRU to oblivion —
every budget-pressure evict turned a future prefix hit back into a
full re-prefill. This module adds the tier below: on eviction the
radix tree's demote hook hands the victim block here, and the tier
copies its RAW pool rows (quantized bytes for int8/fp8 pools, plus
the f32 scale sidecars — dequantize-free in both directions) into
pooled host buffers keyed by the chain's `prefix_digest` hash.

Budget and eviction mirror the hot tier one level down: a byte budget
(`hpx.cache.tier.host_budget_mb`), LRU-to-oblivion as the FINAL tier.
Buffers are pooled (free-listed by shape/dtype and recycled across
demotions) so steady-state demotion traffic allocates nothing — the
stand-in for pinned host memory.

Restoration is gated, not automatic: `RestoreGate` estimates restore
time (bytes over a measured host→device copy bandwidth, plus a fixed
splice overhead) against re-prefill time (tokens times the live
per-token prefill cost from `svc/progprof`'s cb_chunk records, config
fallback before any samples exist) and only promotes when copy-in
beats recompute by `hpx.cache.tier.min_speedup` — the cost-model-
arbitrated execution choice applied to cache restoration. The server
re-ships promoted rows through the `cache/transfer.py` KVSegment
framing (checksums, idempotent seq numbers) and splices the raw bytes
back at the promoted block ids, so a restored block dequantizes
bit-identically to the block that was demoted.

Consistency argument (why snapshots cannot go stale): published radix
blocks are immutable — decode writes COW-fork shared blocks and the
admit splice redirects matched-prefix entries to the trash block — so
the bytes demoted at eviction are the block's FINAL bytes. A tier hit
can therefore be spliced back without any validation beyond the chain
hash + token-chunk equality check.

Checkout discipline (hpxlint HPX015 covers this file): `checkout()`
removes an entry and marks its buffers in flight; every checkout must
reach exactly one of `checkin()` (promotion landed — recycle buffers)
or `putback()` (promotion aborted — reinsert the entry). In-flight
buffers at drain are LEAKS: `leaked_buffers()` is the host-side twin
of `BlockAllocator.leaked_blocks()`.
"""

from __future__ import annotations

import time
import weakref
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..synchronization import Mutex

__all__ = ["HostTier", "RestoreGate", "flight_snapshot"]

# live tiers, for svc/flight shed bundles (weak: a server dropping its
# tier must not be kept alive by observability)
_TIERS: "weakref.WeakSet[HostTier]" = weakref.WeakSet()


class _TierEntry:
    """One demoted block: raw pool rows + scale sidecars, host-side."""

    __slots__ = ("chain", "parent", "key", "rows", "scales", "nbytes",
                 "last_used")

    def __init__(self, chain: int, parent: int, key: Tuple[int, ...],
                 rows: np.ndarray, scales: Optional[np.ndarray],
                 nbytes: int) -> None:
        self.chain = chain          # 64-bit chain hash of the prefix
        self.parent = parent        # chain hash of the parent prefix
        self.key = key              # the block's token chunk
        self.rows = rows            # [n_layers, 2, bs, n_kv, head_dim]
        self.scales = scales        # [n_layers, 2, n_kv] f32 or None
        self.nbytes = nbytes
        self.last_used = 0


class HostTier:
    """Byte-budgeted host store of demoted KV blocks, LRU to oblivion.

    Thread-safe; the radix demote hook runs under the radix lock and
    the serving loop promotes concurrently with fleet digest pulls."""

    _POOL_SPARES = 8    # recycled buffers kept per (shape, dtype)

    def __init__(self, budget_bytes: int, block_size: int) -> None:
        self.budget_bytes = max(0, int(budget_bytes))
        self.block_size = int(block_size)
        self._lock = Mutex()
        self._entries: Dict[int, _TierEntry] = {}
        self._clock = 0
        self._bytes_held = 0
        self._inflight = 0          # checked-out entries not yet back
        self._pool: Dict[Tuple[Tuple[int, ...], str],
                         List[np.ndarray]] = {}
        # cumulative stats (cache/counters.py exports these)
        self.total_demoted = 0      # blocks accepted from eviction
        self.total_promoted = 0     # blocks restored to the device
        self.total_dropped = 0      # blocks LRU'd out / rejected
        self.total_declined = 0     # gate said re-prefill instead
        self.hit_depth_blocks = 0   # cumulative promoted chain depth
        _TIERS.add(self)

    # -- pooled host buffers ---------------------------------------------

    def _buf(self, like: np.ndarray) -> np.ndarray:
        key = (tuple(like.shape), like.dtype.str)
        free = self._pool.get(key)
        buf = free.pop() if free else np.empty(like.shape, like.dtype)
        np.copyto(buf, like, casting="no")
        return buf

    def _recycle(self, arr: Optional[np.ndarray]) -> None:
        if arr is None:
            return
        key = (tuple(arr.shape), arr.dtype.str)
        free = self._pool.setdefault(key, [])
        if len(free) < self._POOL_SPARES:
            free.append(arr)

    # -- demote / probe / checkout ---------------------------------------

    def demote(self, chain: int, parent: int, key: Sequence[int],
               rows: np.ndarray, scales: Optional[np.ndarray]) -> bool:
        """Accept one evicted block's raw rows. Returns True when the
        tier retained it (the radix eviction counts it as demoted,
        not dropped); False when the budget cannot hold it."""
        nbytes = rows.nbytes + (scales.nbytes if scales is not None
                                else 0)
        if nbytes > self.budget_bytes:
            with self._lock:
                self.total_dropped += 1
            return False
        with self._lock:
            old = self._entries.pop(chain, None)
            if old is not None:
                self._bytes_held -= old.nbytes
                self._recycle(old.rows)
                self._recycle(old.scales)
            e = _TierEntry(int(chain), int(parent),
                           tuple(int(t) for t in key),
                           self._buf(rows),
                           None if scales is None else self._buf(scales),
                           nbytes)
            self._clock += 1
            e.last_used = self._clock
            self._entries[chain] = e
            self._bytes_held += nbytes
            self.total_demoted += 1
            self._evict_locked()
        return True

    def _evict_locked(self) -> None:
        while self._bytes_held > self.budget_bytes and self._entries:
            victim = min(self._entries.values(),
                         key=lambda e: e.last_used)
            del self._entries[victim.chain]
            self._bytes_held -= victim.nbytes
            self._recycle(victim.rows)
            self._recycle(victim.scales)
            self.total_dropped += 1

    def probe(self, chain: int, key: Sequence[int]) -> Optional[int]:
        """Membership test for the two-tier match: the entry's nbytes
        when the tier holds `chain` AND its token chunk equals `key`
        (the collision guard), else None. Touches recency — a probed
        chain is about to matter."""
        want = tuple(int(t) for t in key)
        with self._lock:
            e = self._entries.get(int(chain))
            if e is None or e.key != want:
                return None
            self._clock += 1
            e.last_used = self._clock
            return e.nbytes

    def checkout(self, chain: int) -> Optional[_TierEntry]:
        """Remove and return the entry for `chain` (None when a
        concurrent demotion LRU'd it out). The entry's buffers are in
        flight until `checkin` (promoted) or `putback` (aborted)."""
        with self._lock:
            e = self._entries.pop(int(chain), None)
            if e is None:
                return None
            self._bytes_held -= e.nbytes
            self._inflight += 1
            return e

    def checkin(self, entry: _TierEntry) -> None:
        """Promotion landed: the radix tree holds the chain hot again
        (it will re-demote on the next eviction), so the tier's copy
        retires and its buffers recycle."""
        with self._lock:
            self._inflight -= 1
            self._recycle(entry.rows)
            self._recycle(entry.scales)
            self.total_promoted += 1
            self.hit_depth_blocks += 1

    def putback(self, entry: _TierEntry) -> None:
        """Promotion aborted (allocation failed mid-chain, corrupt
        frame): reinsert the entry so the data survives for the next
        hit."""
        with self._lock:
            self._inflight -= 1
            self._clock += 1
            entry.last_used = self._clock
            self._entries[entry.chain] = entry
            self._bytes_held += entry.nbytes
            self._evict_locked()

    def declined(self, nblocks: int) -> None:
        """The crossover gate chose re-prefill over restore."""
        with self._lock:
            self.total_declined += int(nblocks)

    # -- observability ----------------------------------------------------

    def digest(self, max_entries: int = 64) -> List[int]:
        """MRU-first chain hashes, the cold mirror of
        `RadixCache.prefix_digest` — what a fleet router scores with
        the discounted `w_tier` weight."""
        with self._lock:
            ranked = sorted(self._entries.values(),
                            key=lambda e: -e.last_used)
            return [e.chain for e in ranked[:max(0, int(max_entries))]]

    def leaked_buffers(self) -> int:
        """Checked-out entries that never came back — host buffers a
        drained server would strand. Must be 0 at drain."""
        with self._lock:
            return self._inflight

    def clear(self) -> None:
        with self._lock:
            for e in self._entries.values():
                self._bytes_held -= e.nbytes
                self._recycle(e.rows)
                self._recycle(e.scales)
            self._entries.clear()

    def stats(self) -> Dict[str, float]:
        with self._lock:
            return {
                "tier_entries": len(self._entries),
                "tier_bytes_held": self._bytes_held,
                "tier_budget_bytes": self.budget_bytes,
                "tier_demoted": self.total_demoted,
                "tier_promoted": self.total_promoted,
                "tier_dropped": self.total_dropped,
                "tier_declined": self.total_declined,
                "tier_hit_depth_blocks": self.hit_depth_blocks,
                "tier_inflight": self._inflight,
            }


class RestoreGate:
    """Restore-vs-recompute crossover estimator.

    Promote a tier hit only when the estimated restore time (bytes
    over measured host→device bandwidth plus a fixed splice overhead)
    beats the estimated re-prefill time (tokens times the live
    per-token cost from progprof's cb_chunk records) by at least
    `min_speedup`. The bandwidth probe is injectable so tests can pin
    both gate outcomes; the default probe times one real host→device
    transfer of `hpx.cache.tier.probe_mb` and is measured lazily
    once — construction must not touch the device."""

    def __init__(self, min_speedup: Optional[float] = None,
                 probe_mb: Optional[int] = None,
                 prefill_cost_us: Optional[float] = None,
                 overhead_us: Optional[float] = None,
                 probe_fn=None) -> None:
        from ..core.config import runtime_config
        rc = runtime_config()
        self.min_speedup = (rc.get_float("hpx.cache.tier.min_speedup",
                                         1.0)
                            if min_speedup is None else
                            float(min_speedup))
        self.probe_mb = (rc.get_int("hpx.cache.tier.probe_mb", 4)
                         if probe_mb is None else int(probe_mb))
        self.prefill_cost_us = (
            rc.get_float("hpx.cache.tier.prefill_cost_us", 50.0)
            if prefill_cost_us is None else float(prefill_cost_us))
        self.overhead_us = (
            rc.get_float("hpx.cache.tier.restore_overhead_us", 200.0)
            if overhead_us is None else float(overhead_us))
        self._probe_fn = probe_fn
        self._bandwidth: Optional[float] = None

    # -- inputs -----------------------------------------------------------

    def bandwidth(self) -> float:
        """Host→device copy bandwidth in bytes/s, measured once."""
        if self._bandwidth is None:
            nbytes = max(1, self.probe_mb) << 20
            if self._probe_fn is not None:
                self._bandwidth = max(1.0, float(self._probe_fn(nbytes)))
            else:
                self._bandwidth = max(1.0, _copy_probe(nbytes))
        return self._bandwidth

    def prefill_s_per_token(self) -> float:
        """Live per-token prefill cost from the profiler's cb_chunk
        records (exec seconds over chunk-width tokens, all buckets
        pooled), config fallback before any chunk has run or when
        profiling is off."""
        from ..svc import progprof
        prof = progprof.active_profiler()
        if prof is not None:
            sec = tok = 0.0
            for rec in prof.records():
                if rec.label != "cb_chunk":
                    continue
                key = rec.key
                width = (key[2] if isinstance(key, tuple)
                         and len(key) > 2
                         and isinstance(key[2], int) else 0)
                if width and rec.exec_hist.count:
                    sec += rec.exec_hist.sum
                    tok += rec.exec_hist.count * width
            if tok:
                return sec / tok
        return self.prefill_cost_us * 1e-6

    # -- the decision -----------------------------------------------------

    def should_promote(self, ntok: int,
                       nbytes: int) -> Tuple[bool, Dict[str, float]]:
        """(promote?, estimate) for restoring `nbytes` of tier rows
        that would otherwise re-prefill `ntok` tokens."""
        restore_s = (nbytes / self.bandwidth()
                     + self.overhead_us * 1e-6)
        prefill_s = ntok * self.prefill_s_per_token()
        est = {"restore_s": restore_s, "prefill_s": prefill_s,
               "bandwidth_bytes_s": self.bandwidth(),
               "min_speedup": self.min_speedup}
        return prefill_s >= restore_s * self.min_speedup, est


def _copy_probe(nbytes: int) -> float:
    """Default bandwidth probe: time one host→device put of `nbytes`
    and return bytes/s. jax imports lazily — the tier itself is
    numpy-only."""
    import jax
    import jax.numpy as jnp
    buf = np.empty(nbytes, np.uint8)
    t0 = time.perf_counter()
    jax.block_until_ready(jnp.asarray(buf))
    dt = max(1e-9, time.perf_counter() - t0)
    return nbytes / dt


def flight_snapshot() -> Dict[str, float]:
    """Aggregate tier state for svc/flight shed/failover bundles —
    the same shape whether one server or a fleet is live; {} when no
    tier exists (the flight doc key stays optional)."""
    tiers = list(_TIERS)
    if not tiers:
        return {}
    agg: Dict[str, float] = {"tiers": len(tiers)}
    for t in tiers:
        for k, v in t.stats().items():
            if k == "tier_budget_bytes":
                continue
            agg[k] = agg.get(k, 0) + v
    return agg
