"""Disaggregated prefill/decode serving over the dist/AGAS layer.

Reference analog: none in HPX proper — this is the ROADMAP's MPMD
prefill/decode split (PAPERS.md "Scaling Deep Learning Training with
MPMD Pipeline Parallelism"), built with RESILIENCY as the design
center: every cross-worker edge is retried/timed-out/idempotent, and
every worker death has a typed, deterministic failover.

Topology::

    DisaggRouter (front end, admits by SLO class)
        ├── PrefillWorker × N   (chunk programs, b=1 scratch)
        │       │  KVSegments (cache/transfer: framed, checksummed,
        │       ▼   idempotent)
        └── DecodeWorker × M    (ContinuousServer pools)

The prefill worker computes prompt KV rows with the SAME bucketed
chunk + probe programs a colocated server uses (the probe ONE layer
deep, from the hidden row the last chunk hands back) and ships raw
compute-dtype rows block-by-block as they finish (the final, partial
block ships post-probe — the probe rewrites the last layer's row
plen-1). The decode worker splices received rows through its own `_paged_splice_prog`
(`ContinuousServer.admit_prefilled`), so decode proceeds from KV
bytes a colocated prefill would have produced — which is what makes
failover REPLAY (not approximate): tokens are sha-identical to the
fault-free run.

Failure model (each detected via typed ``LocalityLost``/
``NetworkError`` from a worker call — real heartbeat promotion,
socket death, or the injected ``disagg.prefill``/``disagg.decode``
fault sites):

* **decode worker dies** — affected requests re-ship their
  router-retained segments to a surviving decode worker and re-admit;
  decode replays deterministically from the transferred KV. The last
  progress snapshot (``pump``'s live tokens) must be a prefix of the
  replayed output — checked, not assumed.
* **prefill worker dies** — a surviving prefill worker restarts from
  the already-shipped prefix (its scratch seeds from the router's
  retained rows); only the un-transferred suffix recomputes.
* **all workers of a role die** — the router degrades to a local
  colocated ``ContinuousServer`` and finishes every unfinished
  request there rather than erroring.

Config (``hpx.serving.disagg.*``)::

    hpx.serving.disagg.max_queue      router admission bound (64)
    hpx.serving.disagg.prefill_jobs   in-flight prefills per worker (slots)
    hpx.serving.disagg.pump_steps     decode steps per router tick (4)
    hpx.serving.disagg.xfer_retries   segment resend bound (4)
"""

from __future__ import annotations

import dataclasses
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cache.transfer import (KVSegment, TransferCorruptError,
                              TransferReceiver, make_segment)
from ..core.errors import (Error, FutureError, HpxError, LocalityLost,
                           NetworkError)
from ..svc import faultinject, flight, tracing
from ..svc import metrics as _metrics
from ..svc.resiliency import sync_replay
from .serving import (ContinuousServer, RequestShedError,
                      ServerClosedError, _normalize_key)
from .transformer import TransformerConfig

__all__ = [
    "DecodeWorker",
    "DisaggRouter",
    "InProcHandle",
    "PrefillWorker",
    "RemoteHandle",
    "register_worker",
]


# ---------------------------------------------------------------------------
# workers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _PrefillJob:
    prompt: List[int]
    caches: Any                    # b=1 [1, smax] scratch, per layer
    done: int                      # prompt rows computed so far
    emitted: int                   # rows already framed into segments
    temperature: float
    key: Any
    row: Any = None                # the newest chunk's hidden row, where
                                   # the probe starts


class _WorkerRing:
    """Per-worker span ring for cross-worker trace stitching.

    Workers live in their own event-loop turn (or their own process,
    behind a :class:`RemoteHandle`), so they cannot write into the
    router's tracer.  Instead each worker lazily mints a PRIVATE
    :class:`tracing.Tracer` the first time a span opens while the
    process tracer is active, and exposes the ring as a Chrome-trace
    doc via :meth:`trace_doc` — `trace_export.merge_traces` stitches
    those docs with the router's own export into one timeline.  When
    tracing is off the instrumentation is a shared no-op span."""

    _ring: Optional[tracing.Tracer] = None

    def _wspan(self, name: str, **args):
        if tracing.active_tracer() is None:
            return tracing.null_span()
        if self._ring is None:
            from ..core.config import runtime_config
            cap = runtime_config().get_int("hpx.trace.buffer_events",
                                           65536)
            self._ring = tracing.Tracer(capacity=cap,
                                        sample_counters=False)
        return self._ring.span(name, "serving", **args)

    def trace_doc(self) -> Optional[Dict[str, Any]]:
        """This worker's ring as a Chrome-trace doc (None if the ring
        never opened a span); carries the wall-clock anchor that
        merge_traces uses for clock alignment."""
        if self._ring is None:
            return None
        from ..svc.trace_export import to_chrome_trace
        return to_chrome_trace(self._ring.snapshot(),
                               self._ring.thread_names(),
                               self._ring.t0, self._ring.dropped,
                               t0_wall=self._ring.t0_wall)


class PrefillWorker(_WorkerRing):
    """Computes prompt KV on a b=1 contiguous scratch with the
    colocated server's OWN bucketed chunk/probe programs (an embedded
    one-slot ``ContinuousServer`` over the smallest pool it admits is
    the program cache), emitting block-aligned :class:`KVSegment`s as
    rows finish.

    Emission discipline: full blocks of ``[0, ((plen-1)//bs)*bs)`` may
    ship as soon as their rows are chunked (KV rows are append-only —
    functions of (token, position) alone); the FINAL segment ships
    only after the probe, which rewrites the last layer's row plen-1
    and picks the seed token. ``start`` with ``prefix_rows`` resumes a
    transfer whose original worker died: the scratch seeds from the
    already-shipped prefix and only the suffix recomputes.

    ``server_kwargs`` reach the embedded server (``prefill_chunk``,
    ``prefill_buckets``); its pool is never read here, so ``kv_dtype``
    and ``paged_kernel`` have no effect on a worker's segments."""

    def __init__(self, params, cfg: TransformerConfig, smax: int = 512,
                 block_size: Optional[int] = None,
                 **server_kwargs) -> None:
        if block_size is None:
            # the decode pool's geometry authority (env > seed table
            # > default) — emitted segments must match the pool the
            # router splices them into
            from ..ops.attention_pallas import resolve_paged_block
            block_size = resolve_paged_block(cfg.head_dim)[0]
        self.block_size = int(block_size)
        # the scratch `start` builds and the segments are K/V pairs
        cfg.kv_pairs_only("PrefillWorker", "models/disagg.py")
        self._eng = ContinuousServer(
            params, cfg, slots=1, smax=smax, block_size=self.block_size,
            num_blocks=smax // self.block_size + 1,
            async_dispatch=False, **server_kwargs)
        self._jobs: Dict[str, _PrefillJob] = {}

    def start(self, rid: str, prompt: List[int],
              temperature: float = 0.0, key=None,
              prefix_rows=None) -> int:
        """Open (or reopen) a prefill; returns the resume cursor."""
        with self._wspan("prefill.start", rid=rid, plen=len(prompt)):
            eng = self._eng
            prompt = [int(t) for t in prompt]
            nkv, hd = eng.cfg.kv_heads, eng.cfg.head_dim
            scratch = [(jnp.zeros((1, eng.smax, nkv, hd),
                                  eng.cfg.dtype),
                        jnp.zeros((1, eng.smax, nkv, hd),
                                  eng.cfg.dtype))
                       for _ in range(eng.cfg.n_layers)]
            done = 0
            if prefix_rows is not None:
                rows = np.asarray(prefix_rows)
                done = int(rows.shape[2])
                scratch = [
                    (k.at[0, :done].set(jnp.asarray(rows[li, 0],
                                                    eng.cfg.dtype)),
                     v.at[0, :done].set(jnp.asarray(rows[li, 1],
                                                    eng.cfg.dtype)))
                    for li, (k, v) in enumerate(scratch)]
            self._jobs[rid] = _PrefillJob(
                prompt=prompt, caches=scratch, done=done,
                emitted=done, temperature=float(temperature),
                key=_normalize_key(key) if key is not None else None)
            return done

    def step(self, rid: str) -> Dict[str, Any]:
        """Advance one bucketed chunk; returns ``{"segments", "seed",
        "done"}`` — newly completed block segments, plus the seeded
        first token when the prompt finished (probe ran)."""
        job = self._jobs[rid]
        eng, plen, bs = self._eng, len(job.prompt), self.block_size
        with self._wspan("prefill.step", rid=rid):
            if job.done < plen:
                n, width = eng._next_chunk(job.done, plen - job.done)
                job.caches, job.row = eng._run_chunk(
                    job.caches, job.prompt, job.done, n, width)
                job.done += n
            segs: List[KVSegment] = []
            # pre-probe emission cap: row plen-1 is rewritten by the
            # probe
            cap = ((plen - 1) // bs) * bs
            while job.emitted + bs <= min(job.done, cap):
                segs.append(self._emit(rid, job, job.emitted,
                                       job.emitted + bs, plen))
            seed: Optional[int] = None
            finished = job.done >= plen
            if finished:
                # the probe picks generate()'s tok0 (position plen-1,
                # row 0) inside its program; the engine's own lanes,
                # which it also returns, serve nothing here
                job.caches, *_, tok0 = eng._probe(
                    job.caches, job.row, plen - 1, 0,
                    job.temperature, job.key)
                seed = int(tok0)
                segs.append(self._emit(rid, job, job.emitted, plen,
                                       plen))
                del self._jobs[rid]
            return {"segments": segs, "seed": seed, "done": finished}

    def _emit(self, rid: str, job: _PrefillJob, a: int, b: int,
              plen: int) -> KVSegment:
        rows = np.stack([np.stack([np.asarray(k[0, a:b]),
                                   np.asarray(v[0, a:b])])
                         for (k, v) in job.caches])
        job.emitted = b
        # seq = start // block_size: stable across failover restarts,
        # so a re-emitted block dedups against its original delivery
        return make_segment(rid, a // self.block_size, a, plen, rows)

    def abort(self, rid: str) -> None:
        self._jobs.pop(rid, None)

    def jobs(self) -> int:
        return len(self._jobs)

    def ping(self) -> str:
        return "pong"

    def close(self) -> None:
        self._jobs.clear()
        self._eng.shutdown()


class DecodeWorker(_WorkerRing):
    """A ``ContinuousServer`` plus a :class:`TransferReceiver`:
    ingests segments (idempotently), admits completed transfers via
    ``admit_prefilled``, and pumps decode steps, translating between
    router-global request ids and local server rids."""

    def __init__(self, params, cfg: TransformerConfig, slots: int = 4,
                 smax: int = 512, mesh=None, **server_kwargs) -> None:
        # `mesh=` mirrors ContinuousServer(mesh=...) exactly: None is
        # the single-device server, a (dp, tp) Mesh runs decode
        # + verify under shard_map (PR 10's sharded paged serving;
        # axis names in those bodies are hpxlint-HPX021-checked) —
        # one constructor for both, so a fleet mixes them freely
        self.srv = ContinuousServer(params, cfg, slots=slots,
                                    smax=smax, mesh=mesh,
                                    **server_kwargs)
        self.recv = TransferReceiver()
        self._local_of: Dict[str, int] = {}
        self._global_of: Dict[int, str] = {}

    def block_size(self) -> int:
        return self.srv.block_size

    def prefix_digest(self, max_entries: int = 64) -> Dict[str, Any]:
        """Placement fingerprint for fleet routing: the radix tree's
        chain-hash digest (cache/radix.prefix_digest) plus the
        pressure signals the router folds into its score. Cheap by
        construction — O(entries) ints, no token lists, no leases."""
        srv = self.srv
        return {
            "hashes": srv._radix.prefix_digest(max_entries),
            # cold mirror: chains held only in the host tier — the
            # router scores these with the discounted w_tier weight
            "tier_hashes": (srv._tier.digest(max_entries)
                            if getattr(srv, "_tier", None) is not None
                            else []),
            "evictions": int(srv._radix.total_evictions),
            "blocks_held": int(srv._radix.blocks_held),
            "blocks_free": int(srv._alloc.free_count),
        }

    def fetch_prefix(self, prompt: List[int]) -> Dict[str, Any]:
        """Export this worker's longest cached whole-block prefix of
        `prompt` as raw host rows (ContinuousServer.
        export_prefix_rows) — the fleet router frames them as retained
        KV segments and seeds the prefill worker's scratch, so only
        the suffix recomputes."""
        matched, rows = self.srv.export_prefix_rows(prompt)
        return {"matched": matched, "rows": rows}

    def ingest(self, seg: KVSegment) -> Dict[str, Any]:
        with self._wspan("decode.ingest", rid=seg.rid, seq=seg.seq):
            return self.recv.ingest(seg)

    def admit(self, rid: str, prompt: List[int], seed: int,
              max_new: int, eos_id: Optional[int] = None,
              temperature: float = 0.0, key=None) -> int:
        with self._wspan("decode.admit", rid=rid, plen=len(prompt)):
            rows = self.recv.assemble(rid)
            local = self.srv.admit_prefilled(
                prompt, rows, seed, max_new, eos_id=eos_id,
                temperature=temperature, key=key)
            self._local_of[rid] = local
            self._global_of[local] = rid
            return local

    def pump(self, steps: int = 1) -> Dict[str, Any]:
        """Run up to `steps` server steps; returns ``{"done",
        "failed", "live", "busy"}`` keyed by router-global rid.
        ``live`` is each in-flight request's tokens so far — the
        router's progress checkpoint for post-failover replay
        verification."""
        busy = False
        with self._wspan("decode.pump", steps=steps):
            for _ in range(max(1, steps)):
                busy = self.srv.step()
                if not busy:
                    break
        done: Dict[str, List[int]] = {}
        for lrid in list(self.srv._done):
            grid = self._global_of.pop(lrid, None)
            if grid is None:
                continue
            done[grid] = self.srv._done.pop(lrid)
            self._local_of.pop(grid, None)
        failed: Dict[str, HpxError] = {}
        for lrid in list(self.srv.failed):
            grid = self._global_of.pop(lrid, None)
            if grid is None:
                continue
            failed[grid] = self.srv.failed.pop(lrid)
            self._local_of.pop(grid, None)
        live: Dict[str, List[int]] = {}
        for s in range(self.srv.slots):
            req = self.srv._slot_req[s]
            if req is not None and req.rid in self._global_of:
                live[self._global_of[req.rid]] = list(req.tokens)
        return {"done": done, "failed": failed, "live": live,
                "busy": busy}

    def stats(self) -> Dict[str, Any]:
        st = dict(self.srv._alloc.stats())
        st.update(self.recv.stats())
        return st

    def leaked_blocks(self) -> int:
        """Blocks still in use once the radix cache (a CACHE, not a
        reservation) is fully evicted — must be 0 after close().
        Excludes the server's one permanently resident trash block."""
        while sum(self.srv._radix.evict(1)):
            pass
        return int(self.srv._alloc.stats()["in_use"]) - 1

    def ping(self) -> str:
        return "pong"

    def close(self, drain: bool = False) -> None:
        """Stop intake; optionally drain in-flight decode, then abort
        pending transfers and release every slot/checkpoint block —
        zero allocator leak whether or not work was in flight."""
        if drain:
            self.srv.run()
        self.srv.shutdown()
        for rid in self.recv.pending():
            self.recv.abort(rid)
        self.srv._shed_everything(
            ServerClosedError("decode worker closed"))


# ---------------------------------------------------------------------------
# worker handles: one call surface for in-process and remote workers
# ---------------------------------------------------------------------------

class WorkerHandle:
    """Router-side proxy for one worker. ``call`` raises typed
    ``LocalityLost``/``NetworkError`` when the worker is gone —
    injected (``disagg.<role>`` fault sites) or real — and the router
    marks the handle dead permanently (a lost worker never
    resurrects mid-run; deterministic failover depends on that)."""

    role: str
    locality: int
    alive: bool
    # autoscale drain flag (svc/fleet): a draining worker finishes or
    # hands off what it owns but takes no NEW placements; the base
    # router only ever reads it (class default keeps plain disagg
    # topologies oblivious)
    draining: bool = False

    def call(self, method: str, *args: Any, **kwargs: Any) -> Any:
        raise NotImplementedError

    def kill(self) -> None:
        raise NotImplementedError

    def _check(self) -> None:
        if not self.alive:
            raise LocalityLost(
                self.locality,
                f"{self.role} worker at locality {self.locality} "
                f"is dead", "WorkerHandle.call")
        faultinject.check(f"disagg.{self.role}",
                          locality=self.locality)


class InProcHandle(WorkerHandle):
    """Same-process worker (tests, single-host serving, the chaos
    bench): direct method calls through the fault-site check."""

    def __init__(self, role: str, worker: Any,
                 locality: int = 0) -> None:
        self.role = role
        self.locality = locality
        self.alive = True
        self.worker = worker

    def call(self, method: str, *args: Any, **kwargs: Any) -> Any:
        self._check()
        return getattr(self.worker, method)(*args, **kwargs)

    def kill(self) -> None:
        self.alive = False


_workers: Dict[str, Any] = {}


def register_worker(worker_id: str, worker: Any) -> str:
    """Publish a worker under `worker_id` for `hpx.disagg.invoke`
    parcels arriving at THIS locality."""
    _workers[worker_id] = worker
    return worker_id


def _disagg_invoke(worker_id: str, method: str, args: tuple,
                   kwargs: dict) -> Any:
    w = _workers.get(worker_id)
    if w is None:
        raise HpxError(Error.bad_parameter,
                       f"no disagg worker {worker_id!r} registered "
                       f"at this locality")
    return getattr(w, method)(*args, **kwargs)


def _disagg_die() -> None:
    """Chaos harness: hard-kill this locality's process (no cleanup,
    no goodbye — the failure detector must notice the honest way)."""
    os._exit(0)


class RemoteHandle(WorkerHandle):
    """Worker on another locality, reached via `resilient_action`:
    per-attempt timeout, bounded backoff retry, idempotency keys (a
    retried parcel is deduplicated, never re-executed)."""

    def __init__(self, role: str, locality: int, worker_id: str,
                 timeout_s: float = 30.0, retries: int = 3) -> None:
        self.role = role
        self.locality = locality
        self.worker_id = worker_id
        self.timeout_s = timeout_s
        self.retries = retries
        self.alive = True

    def call(self, method: str, *args: Any, **kwargs: Any) -> Any:
        self._check()
        from ..dist.actions import resilient_action
        return resilient_action(
            "hpx.disagg.invoke", self.locality, self.worker_id,
            method, args, kwargs, timeout_s=self.timeout_s,
            retries=self.retries).get()

    def kill(self) -> None:
        from ..dist.actions import post_action
        try:
            post_action("hpx.disagg.die", self.locality)
        except (NetworkError, HpxError):
            pass               # already dead — which is the goal
        self.alive = False


class _WorkerDown(Exception):
    """Internal: a worker call failed with a connectivity-class error;
    carries WHICH handle so the router step loop can fail it over."""

    def __init__(self, handle: WorkerHandle, cause: BaseException):
        super().__init__(f"{handle.role}@{handle.locality}: {cause}")
        self.handle = handle
        self.cause = cause


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _RouterReq:
    rid: int
    prompt: List[int]
    max_new: int
    eos_id: Optional[int]
    temperature: float
    key: Any
    slo: str
    state: str = "queued"          # queued|prefill|decode|done|failed
    prefill_h: Optional[WorkerHandle] = None
    decode_h: Optional[WorkerHandle] = None
    segments: List[KVSegment] = dataclasses.field(default_factory=list)
    seed: Optional[int] = None
    progress: List[int] = dataclasses.field(default_factory=list)

    @property
    def grid(self) -> str:
        return f"r{self.rid}"


class DisaggRouter:
    """Front end of the disaggregated topology: admits by SLO class
    (bounded queue; ``batch`` sheds before ``interactive``),
    dispatches prefill, streams KV segments to the least-loaded
    decode worker, pumps decode, and runs the failover policy of the
    module docstring. `run()` returns ``{rid: tokens}`` exactly like
    ``ContinuousServer.run`` — shed/failed requests land typed in
    ``failed``."""

    def __init__(self, params, cfg: TransformerConfig,
                 prefill_workers: int = 1, decode_workers: int = 1, *,
                 slots: int = 4, smax: int = 512,
                 decode_mesh=None,
                 prefill_handles: Optional[List[WorkerHandle]] = None,
                 decode_handles: Optional[List[WorkerHandle]] = None,
                 server_kwargs: Optional[dict] = None) -> None:
        from ..core.config import runtime_config
        rc = runtime_config()
        self.params, self.cfg = params, cfg
        self.slots, self.smax = slots, smax
        self.decode_mesh = decode_mesh
        self._srv_kwargs = dict(server_kwargs or {})
        self.max_queue = rc.get_int("hpx.serving.disagg.max_queue", 64)
        self._pump_steps = max(1, rc.get_int(
            "hpx.serving.disagg.pump_steps", 4))
        self._prefill_jobs = max(1, rc.get_int(
            "hpx.serving.disagg.prefill_jobs", slots))
        self._xfer_retries = max(1, rc.get_int(
            "hpx.serving.disagg.xfer_retries", 4))
        if decode_handles is None:
            decode_handles = [
                InProcHandle("decode", self._make_decode_worker(),
                             locality=0)
                for _ in range(decode_workers)]
        self._decode = list(decode_handles)
        self.failovers = {"prefill": 0, "decode": 0}
        # prefill segments (and placement prefix hashes) must be
        # block-aligned to the DECODE pool's grid; a decode worker
        # already dead at construction just fails over to the next
        # for the query
        bs = None
        for h in self._decode:
            try:
                bs = int(h.call("block_size"))
                break
            except (NetworkError, FutureError):
                h.alive = False
                self.failovers["decode"] += 1
        if bs is None:
            bs = 16   # every decode worker dead: the first step
                      # degrades to colocated; bs is moot
        self._block_size = bs
        if prefill_handles is None:
            prefill_handles = [
                InProcHandle("prefill", PrefillWorker(
                    params, cfg, smax=smax, block_size=bs),
                    locality=0)
                for _ in range(prefill_workers)]
        self._prefill = list(prefill_handles)
        self._reqs: Dict[int, _RouterReq] = {}
        self._qi: deque = deque()      # interactive rids
        self._qb: deque = deque()      # batch rids
        self._next_rid = 0
        self._closed = False
        self.results: Dict[int, List[int]] = {}
        self.failed: Dict[int, HpxError] = {}
        self.shed = 0
        self._degraded = False
        self._local: Optional[ContinuousServer] = None
        self._local_map: Dict[int, int] = {}   # local rid -> router rid
        self.ttft: Dict[int, float] = {}
        self._t_submit: Dict[int, float] = {}
        # -- SLO metrics plane: per-decode-worker latency histograms
        # (keyed by creation-order index, stable across failover) plus
        # a rid-keyed lifecycle timeline.  merged_hist() folds the
        # per-worker histograms into the fleet-wide view.
        self._worker_idx: Dict[int, int] = {}
        self._next_widx = 0
        self.whist: Dict[int, Dict[str, _metrics.HistogramCounter]] = {}
        self.timeline = _metrics.RequestTimeline()
        self._last_pump_t: Dict[int, float] = {}
        # live ops plane: one weakref /statusz provider per router,
        # so the router port exposes the merged fleet view (workers
        # roll up through merged_hist / stats). None unless
        # hpx.obs.port enables the plane.
        from ..svc import opsplane as _opsplane
        if _opsplane.ensure_opsplane() is not None:
            _opsplane.register_provider(
                f"router/{id(self):x}", self, type(self)._statusz)

    # -- admission --------------------------------------------------------

    def submit(self, prompt, max_new: int,
               eos_id: Optional[int] = None,
               temperature: float = 0.0, key=None,
               slo: str = "interactive") -> int:
        if self._closed:
            raise ServerClosedError("router is closed")
        if slo not in ("interactive", "batch"):
            raise ValueError(
                f"slo must be 'interactive' or 'batch', got {slo!r}")
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("disagg serving needs a non-empty prompt")
        if len(prompt) + max_new > self.smax:
            raise ValueError(
                f"plen {len(prompt)} + max_new {max_new} exceeds "
                f"smax {self.smax}")
        rid = self._next_rid
        self._next_rid += 1
        req = _RouterReq(rid, prompt, max_new, eos_id,
                         float(temperature),
                         _normalize_key(key) if key is not None
                         else None, slo)
        self._reqs[rid] = req
        self._t_submit[rid] = time.monotonic()
        self.timeline.event(req.grid, "submit", slo=slo,
                            plen=len(prompt))
        # bounded admission: shed BATCH work first (newest first), an
        # overflowing batch submit sheds itself, and only a queue full
        # of interactive work sheds an interactive submit
        while len(self._qi) + len(self._qb) >= self.max_queue:
            if self._qb:
                self._shed(self._reqs[self._qb.pop()],
                           "admission queue full (batch shed first)")
                continue
            self._shed(req, "admission queue full of interactive work")
            return rid
        if self._degraded:
            self._submit_local(req)
            return rid
        (self._qi if slo == "interactive" else self._qb).append(rid)
        return rid

    def _shed(self, req: _RouterReq, reason: str) -> None:
        req.state = "failed"
        req.segments = []
        err = RequestShedError(req.rid, reason)
        self.failed[req.rid] = err
        self.shed += 1
        flight.record_fault("shed", site="disagg", rid=req.grid,
                            error=err, timeline=self.timeline)

    # -- the step loop ----------------------------------------------------

    def _call(self, h: WorkerHandle, method: str, *args: Any,
              **kwargs: Any) -> Any:
        try:
            return h.call(method, *args, **kwargs)
        except (NetworkError, FutureError) as e:
            raise _WorkerDown(h, e) from e

    def step(self) -> bool:
        """One router tick: admit → advance prefills (shipping
        segments) → pump decode. A worker death detected anywhere in
        the tick runs failover immediately; the tick's remaining work
        happens on later ticks (state only ever advances, so a
        half-finished tick is safe to abandon)."""
        if self._degraded:
            return self._local_step()
        try:
            self._dispatch_prefills()
            self._advance_prefills()
            self._pump_decodes()
        except _WorkerDown as wd:
            self._on_worker_failure(wd.handle, wd.cause)
        return self._unfinished() > 0

    def run(self) -> Dict[int, List[int]]:
        while self.step():
            pass
        out, self.results = self.results, {}
        return out

    def _unfinished(self) -> int:
        return sum(1 for r in self._reqs.values()
                   if r.state not in ("done", "failed"))

    def _alive(self, handles: List[WorkerHandle]) -> List[WorkerHandle]:
        return [h for h in handles if h.alive]

    def _make_decode_worker(self) -> DecodeWorker:
        """Mint one decode worker on this router's construction recipe
        — the default-handle path AND the fleet autoscaler both come
        through here, so scaled-up workers are indistinguishable from
        constructed ones (same mesh, same kwargs, same program-cache
        keys)."""
        return DecodeWorker(self.params, self.cfg, slots=self.slots,
                            smax=self.smax, mesh=self.decode_mesh,
                            **self._srv_kwargs)

    def _decode_load(self) -> Dict[int, int]:
        """In-flight requests per decode handle (by id) — the shared
        currency of every placement policy here and in svc/fleet."""
        load = {id(h): 0 for h in self._decode}
        for r in self._reqs.values():
            if (r.state in ("prefill", "decode")
                    and r.decode_h is not None
                    and id(r.decode_h) in load):
                load[id(r.decode_h)] += 1
        return load

    # -- SLO metrics plane ------------------------------------------------

    def _widx(self, h: Optional[WorkerHandle]) -> int:
        """Creation-order index of a decode handle — stable across
        failover and autoscale (-1 covers the degraded / no-worker
        path)."""
        if h is None:
            return -1
        key = id(h)
        if key not in self._worker_idx:
            self._worker_idx[key] = self._next_widx
            self._next_widx += 1
        return self._worker_idx[key]

    def _whist(self, h: Optional[WorkerHandle]
               ) -> Dict[str, _metrics.HistogramCounter]:
        """The latency histograms attributed to one decode worker,
        minted lazily on first touch."""
        idx = self._widx(h)
        hist = self.whist.get(idx)
        if hist is None:
            hist = self.whist[idx] = _metrics.latency_histograms()
            from ..svc import exemplars as _exemplars
            _exemplars.attach_from_config(hist)
        return hist

    def merged_hist(self) -> Dict[str, _metrics.HistogramCounter]:
        """The fleet-wide latency view: a fold of every per-worker
        histogram under :meth:`HistogramCounter.merge`, computed at
        query time — so the fleet-wide quantiles EQUAL the merge of
        the per-worker histograms by construction."""
        out = _metrics.latency_histograms()
        for per in self.whist.values():
            for k in _metrics.LATENCY_KEYS:
                out[k] = out[k].merge(per[k])
        return out

    def worker_trace_docs(self) -> List[Tuple[str, Dict[str, Any]]]:
        """Chrome-trace docs from every live worker's private span
        ring, labelled ``role#index`` — feed these together with the
        router's own export to ``trace_export.merge_traces`` for the
        single stitched fleet timeline."""
        docs: List[Tuple[str, Dict[str, Any]]] = []
        for role, pool in (("prefill", self._prefill),
                           ("decode", self._decode)):
            for i, h in enumerate(pool):
                if not h.alive:
                    continue
                try:
                    doc = self._call(h, "trace_doc")
                except _WorkerDown:
                    continue
                if doc is not None:
                    docs.append((f"{role}#{i}", doc))
        return docs

    def _placeable_decode(self) -> List[WorkerHandle]:
        """Candidates for NEW placements: alive and not draining. A
        fleet drain empties the pool's tail, never the whole pool, but
        failover must still find a home if it somehow does — fall back
        to anything alive rather than strand a request."""
        alive = self._alive(self._decode)
        return [h for h in alive if not h.draining] or alive

    def _least_loaded_decode(self) -> WorkerHandle:
        cands = self._placeable_decode()
        load = self._decode_load()
        return min(cands, key=lambda h: (load[id(h)],
                                         self._decode.index(h)))

    def _place_decode(self, req: _RouterReq) -> WorkerHandle:
        """Pick the decode worker for one request. The base policy is
        least-loaded; svc/fleet overrides this with prefix-cache-aware
        scoring. Called with the request still QUEUED (a worker death
        inside placement re-places on a later tick)."""
        return self._least_loaded_decode()

    def _start_prefill_job(self, req: _RouterReq,
                           h: WorkerHandle) -> None:
        """Open the prefill job on `h` — the one cross-worker send of
        dispatch. svc/fleet overrides this to seed the job with the
        placed decode worker's cached prefix rows first."""
        self._call(h, "start", req.grid, req.prompt,
                   req.temperature, req.key)

    def _dispatch_prefills(self) -> None:
        alive = self._alive(self._prefill)
        if not alive or not self._alive(self._decode):
            if self._unfinished():
                self._degrade()
            return
        jobs = {id(h): 0 for h in alive}
        for r in self._reqs.values():
            if r.state == "prefill" and id(r.prefill_h) in jobs:
                jobs[id(r.prefill_h)] += 1
        while self._qi or self._qb:
            h = min(alive, key=lambda w: (jobs[id(w)],
                                          self._prefill.index(w)))
            if jobs[id(h)] >= self._prefill_jobs:
                return
            q = self._qi if self._qi else self._qb
            # peek: a death during start must leave the rid queued
            # for re-dispatch
            req = self._reqs[q[0]]
            with tracing.span("serving.place", "serving",
                              rid=req.grid):
                req.prefill_h = h
                req.decode_h = self._place_decode(req)
                self._start_prefill_job(req, h)
            q.popleft()
            req.state = "prefill"
            jobs[id(h)] += 1
            now = time.monotonic()
            self._whist(req.decode_h)["queue_wait"].record(
                now - self._t_submit[req.rid], rid=req.grid)
            self.timeline.event(req.grid, "place", t=now,
                                worker=self._widx(req.decode_h))
            self.timeline.event(req.grid, "prefill_start", t=now)

    def _advance_prefills(self) -> None:
        for rid in sorted(r.rid for r in self._reqs.values()
                          if r.state == "prefill"):
            req = self._reqs[rid]
            out = self._call(req.prefill_h, "step", req.grid)
            req.segments.extend(out["segments"])  # retain BEFORE
            if out["done"]:                       # shipping: failover
                # prefill is over (the worker dropped the job) — from
                # here on a decode death re-ships + re-admits; it must
                # NOT re-step a prefill that no longer exists
                req.seed = int(out["seed"])
                req.state = "decode"
            for seg in out["segments"]:
                self._ship(req, seg)              # re-ships these
            if out["done"]:
                self._admit_decode(req)

    def _ship(self, req: _RouterReq, seg: KVSegment) -> None:
        """Deliver one segment, re-sending on checksum corruption
        (bounded, backed off); connectivity errors propagate to the
        failover path."""
        if seg.seq == 0:
            self.timeline.event(req.grid, "kv_transfer",
                                worker=self._widx(req.decode_h))
        with tracing.span("serving.transfer", "serving", rid=req.grid,
                          seq=seg.seq), \
                self._whist(req.decode_h)["transfer"].record():
            sync_replay(self._xfer_retries,
                        lambda: self._call(req.decode_h, "ingest",
                                           seg),
                        retry_on=(TransferCorruptError,),
                        backoff_s=0.005)

    def _admit_decode(self, req: _RouterReq) -> None:
        # transition BEFORE the call: prefill is finished (its job is
        # gone), so a decode death mid-admit must re-admit on the
        # survivor, not re-step a prefill that no longer exists
        req.state = "decode"
        self._call(req.decode_h, "admit", req.grid, req.prompt,
                   req.seed, req.max_new, req.eos_id,
                   req.temperature, req.key)

    def _pump_decodes(self) -> None:
        for h in self._alive(self._decode):
            widx = self._widx(h)
            assigned = any(r.decode_h is h and r.state == "decode"
                           for r in self._reqs.values())
            if not assigned:
                self._last_pump_t.pop(widx, None)
                continue
            # decode stall: the gap since this worker's previous pump
            # returned while it still held live work
            now = time.monotonic()
            last = self._last_pump_t.get(widx)
            if last is not None:
                # attribute the stall exemplar to the first live grid on
                # this worker (deterministic: lowest rid)
                stall_rid = next(
                    (self._reqs[r].grid for r in sorted(self._reqs)
                     if self._reqs[r].decode_h is h
                     and self._reqs[r].state == "decode"), None)
                self._whist(h)["decode_stall"].record(now - last,
                                                      rid=stall_rid)
            out = self._call(h, "pump", self._pump_steps)
            self._last_pump_t[widx] = time.monotonic()
            for grid, toks in sorted(out["done"].items()):
                self._finish(self._req_of(grid), toks)
            for grid, err in sorted(out["failed"].items()):
                req = self._req_of(grid)
                req.state = "failed"
                req.segments = []
                self.failed[req.rid] = err
            for grid, toks in out["live"].items():
                req = self._req_of(grid)
                req.progress = toks
                if req.rid not in self.ttft and toks:
                    ttft = time.monotonic() - self._t_submit[req.rid]
                    self.ttft[req.rid] = ttft
                    self._whist(req.decode_h)["ttft"].record(
                        ttft, rid=req.grid)
                    self.timeline.event(req.grid, "first_token",
                                        worker=widx)

    def _req_of(self, grid: str) -> _RouterReq:
        return self._reqs[int(grid[1:])]

    def _finish(self, req: _RouterReq, toks: List[int]) -> None:
        if req.progress and toks[:len(req.progress)] != req.progress:
            raise HpxError(
                Error.assertion_failure,
                f"request {req.rid}: post-failover replay diverged "
                f"from its last progress checkpoint",
                "DisaggRouter._finish")
        req.state = "done"
        req.segments = []
        self.results[req.rid] = toks
        now = time.monotonic()
        if req.rid not in self.ttft:
            ttft = now - self._t_submit[req.rid]
            self.ttft[req.rid] = ttft
            self._whist(req.decode_h)["ttft"].record(ttft,
                                                     rid=req.grid)
            self.timeline.event(req.grid, "first_token",
                                worker=self._widx(req.decode_h))
        self._whist(req.decode_h)["e2e"].record(
            now - self._t_submit[req.rid], rid=req.grid)
        self.timeline.event(req.grid, "retire", tokens=len(toks))

    # -- failover ---------------------------------------------------------

    def _on_worker_failure(self, h: WorkerHandle,
                           cause: BaseException) -> None:
        """A worker call surfaced a connectivity-class error: the
        worker is DEAD for the rest of this run. Re-route everything
        it owned; degrade to colocated when a role has no survivors."""
        if h.alive:
            h.alive = False
        self.failovers[h.role] += 1
        flight.record_fault("failover", site=h.role, error=cause)
        if not self._alive(self._prefill) \
                or not self._alive(self._decode):
            self._degrade()
            return
        if h.role == "prefill":
            # decoding requests no longer need their prefill worker
            affected = [r for r in self._reqs.values()
                        if r.state == "prefill" and r.prefill_h is h]
        else:
            # a decode death strands both decoding requests AND
            # mid-prefill requests whose segments streamed to it
            affected = [r for r in self._reqs.values()
                        if r.state in ("prefill", "decode")
                        and r.decode_h is h]
        affected.sort(key=lambda r: r.rid)
        try:
            for req in affected:
                if h.role == "decode":
                    self._failover_decode(req)
                else:
                    self._failover_prefill(req)
        except _WorkerDown as wd:
            # cascading loss: the failover target died too
            self._on_worker_failure(wd.handle, wd.cause)

    def _failover_decode(self, req: _RouterReq) -> None:
        """Re-ship the retained segments to a survivor; if decode was
        already running, re-admit — the survivor replays the whole
        decode from the transferred KV, deterministically emitting the
        tokens the dead worker lost."""
        req.decode_h = self._place_decode(req)
        for seg in req.segments:
            self._ship(req, seg)
        if req.state == "decode":
            self._admit_decode(req)

    def _failover_prefill(self, req: _RouterReq) -> None:
        """Restart ONLY the un-transferred suffix on a survivor: the
        replacement's scratch seeds from the rows already shipped (the
        router retains every segment until the request finishes)."""
        alive = self._alive(self._prefill)
        req.prefill_h = alive[0]
        prefix = None
        if req.segments:
            segs = sorted(req.segments, key=lambda s: s.start)
            prefix = np.concatenate([s.payload for s in segs], axis=2)
        self._call(req.prefill_h, "start", req.grid, req.prompt,
                   req.temperature, req.key, prefix)

    def _degrade(self) -> None:
        """A worker role has no survivors: colocated fallback. Every
        unfinished request restarts from its prompt on a LOCAL
        server — slower, but the tokens are identical (the same
        differential contract every path here rides)."""
        if self._degraded:
            return
        self._degraded = True
        flight.record_fault("degrade", site="disagg")
        self._local = ContinuousServer(
            self.params, self.cfg, slots=self.slots, smax=self.smax,
            **self._srv_kwargs)
        self._qi.clear()
        self._qb.clear()
        for rid in sorted(self._reqs):
            req = self._reqs[rid]
            if req.state in ("done", "failed"):
                continue
            self._submit_local(req)

    def _submit_local(self, req: _RouterReq) -> None:
        lrid = self._local.submit(
            req.prompt, req.max_new, eos_id=req.eos_id,
            temperature=req.temperature, key=req.key)
        self._local_map[lrid] = req.rid
        req.state = "decode"
        req.segments = []

    def _local_step(self) -> bool:
        busy = self._local.step()
        for lrid in list(self._local._done):
            rid = self._local_map.pop(lrid, None)
            if rid is None:
                continue
            self._finish(self._reqs[rid], self._local._done.pop(lrid))
        for lrid in list(self._local.failed):
            rid = self._local_map.pop(lrid, None)
            if rid is None:
                continue
            req = self._reqs[rid]
            req.state = "failed"
            self.failed[rid] = self._local.failed.pop(lrid)
        return busy or self._unfinished() > 0

    # -- lifecycle --------------------------------------------------------

    def _statusz(self) -> Dict[str, Any]:
        """This router's /statusz section (svc/opsplane provider):
        queue split, request-state census, per-worker liveness and
        per-worker SLO sample counts, plus the stats() roll-up —
        ONE port answers for the whole fleet.  Host-only reads; no
        worker calls (a scrape must not touch a dead worker)."""
        states: Dict[str, int] = {}
        for r in self._reqs.values():
            states[r.state] = states.get(r.state, 0) + 1
        return {
            "kind": "router",
            "queue": {"interactive": len(self._qi),
                      "batch": len(self._qb)},
            "requests": states,
            "workers": {
                "prefill": [
                    {"locality": getattr(h, "locality", 0),
                     "alive": h.alive} for h in self._prefill],
                "decode": [
                    {"widx": self._widx(h),
                     "locality": getattr(h, "locality", 0),
                     "alive": h.alive,
                     "samples": {k: v.count for k, v in sorted(
                         self.whist.get(self._widx(h), {}).items())}}
                    for h in self._decode],
            },
            "timeline_rids": len(self.timeline),
            "stats": self.stats(),
        }

    def stats(self) -> Dict[str, Any]:
        merged = self.merged_hist()
        return {
            "failovers": dict(self.failovers),
            "shed": self.shed,
            "degraded": self._degraded,
            "unfinished": self._unfinished(),
            "prefill_alive": len(self._alive(self._prefill)),
            "decode_alive": len(self._alive(self._decode)),
            # fleet-wide quantiles from LIVE histograms — the merge of
            # the per-worker views, not a post-hoc sort of raw samples
            "latency": {
                k: {_metrics.quantile_label(q): merged[k].quantile(q)
                    for q in _metrics.configured_quantiles()}
                for k in _metrics.LATENCY_KEYS},
        }

    def leaked_blocks(self) -> int:
        """Sum of post-eviction in-use blocks across every surviving
        decode worker (and the colocated fallback) — the chaos gate's
        zero-leak check."""
        total = 0
        for h in self._alive(self._decode):
            try:
                total += int(self._call(h, "leaked_blocks"))
            except _WorkerDown:
                continue
        if self._local is not None:
            while sum(self._local._radix.evict(1)):
                pass
            # minus the fallback server's resident trash block
            total += int(self._local._alloc.stats()["in_use"]) - 1
        return total

    def close(self, drain: bool = True) -> None:
        """Stop intake (later submit() raises ServerClosedError).
        ``drain=True`` finishes in-flight work first; ``drain=False``
        sheds it typed. Either way every worker's pending transfers
        abort and pinned blocks release — no allocator leak."""
        if self._closed:
            return
        self._closed = True
        if drain:
            while self.step():
                pass
        else:
            for rid in sorted(self._reqs):
                req = self._reqs[rid]
                if req.state not in ("done", "failed"):
                    self._shed(req, "router closed before completion")
        for h in self._alive(self._prefill):
            try:
                self._call(h, "close")
            except _WorkerDown:
                continue
        for h in self._alive(self._decode):
            try:
                self._call(h, "close", drain)
            except _WorkerDown:
                continue
        if self._local is not None:
            self._local.shutdown()
            self._local._shed_everything(
                ServerClosedError("router closed"))


from ..dist.actions import plain_action as _pa  # noqa: E402
_pa(_disagg_invoke, name="hpx.disagg.invoke")
_pa(_disagg_die, name="hpx.disagg.die")
