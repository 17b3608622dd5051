"""Continuous batching: slot-based serving with per-slot positions.

Reference analog: none (HPX ships no serving runtime); this is the
standard TPU serving-loop shape — a FIXED batch of decode slots, each
at its OWN sequence position, stepping together in one jitted program.
Requests admit into free slots between steps (their prompt prefills on
the side in BUCKETED CHUNKS, then SPLICES into the blocks the slot's
table maps) and retire on eos/max_new, so short requests never wait
for long ones and the chip never idles on a ragged batch. Static shapes
throughout:
the per-row cache write is a batched scatter at the slot's position
vector, the causal mask compares against per-row positions, and dead
slots simply compute masked work (the XLA way — uniform work, no
dynamic batch).

Three throughput disciplines shape the hot loop:

* BUCKETED prefill: prompts run through fixed-width chunk programs
  (widths from the ``hpx.serving.prefill_buckets`` ladder, padded then
  causally masked), so the program cache is O(buckets) instead of
  O(distinct prompt lengths) — mixed-length traffic compiles a handful
  of programs, ever.
* CHUNKED prefill interleaved with decode (Sarathi-style): a prompt
  longer than ``hpx.serving.prefill_chunk`` advances one chunk per
  step between decode dispatches, so an admit never stalls the live
  batch; pending prefills are served shortest-remaining-first, so a
  short prompt is never stuck behind a long one's tail chunks.
* ASYNC dispatch, DISPATCH BEFORE READ: the step loop feeds each
  step's sampled tokens back device-side, and every program a step
  enqueues (chunk, probe, splice, decode) is enqueued before that
  step's first blocking device->host read. The reads LAG by one step:
  a retirement by max_new (or ``hpx.serving.max_async_steps`` buffered
  steps) notes that a read is due, and the NEXT step() makes it after
  its own decode dispatch, taking every buffered step but the newest
  — each blocking read has a whole decode step queued behind it. An
  admission's seed token stays a device value (written into the
  feedback vector) until the step's decode is enqueued, then is read
  in the same step(). The order read-then-dispatch stays where the
  VALUE is needed before the next dispatch: a request with an
  ``eos_id``, ``max_new == 1``, a speculative server,
  ``async_dispatch=False`` (`read_stats()` counts both kinds).
* SPECULATIVE decode steps (``hpx.serving.spec.*``): each step drafts
  k tokens per slot — zero-model prompt-lookup over the slot's own
  history (plus the radix prefix tree), or a smaller draft checkpoint
  — and verifies the window with ONE forward, emitting 1..k+1 tokens
  per sync instead of one. Acceptance compares drafts against the
  EXACT token the sequential step would pick (same ``_pick_row``
  key-fold contract), so spec output stays byte-identical, greedy and
  sampled; a rejection rolls the window's blocks back
  (``PageTable.rollback``). Verify programs ride the prefill bucket
  ladder — still O(buckets) programs — and k adapts per slot on an
  acceptance EMA.

Differential contract (the test): every request's tokens are EXACTLY
what transformer.generate() emits for that prompt alone — continuous
batching changes THROUGHPUT, never content. Chunk padding preserves
this bit-for-bit: per-token hidden states and K/V rows are independent
of how the prompt is partitioned into windows (row-independent ops +
exact-zero causal masking of pad rows), and the first sampled token
comes from a one-row probe of the last prompt position that is ONE
layer deep: every chunk hands back the hidden row of its last real
column as it enters the last layer, and the probe takes the last
chunk's row through that layer and the head.

RESILIENCY (ROADMAP item 5): the step loop runs under a bounded
`svc.resiliency.sync_replay`. Every live slot keeps a host-side
`SlotCheckpoint` (tokens, position, feedback token, block pins)
captured at flush boundaries every ``hpx.serving.ckpt_every`` tokens,
at the frontier the HOST holds (the tokens landed; a step still in
flight is ahead of it and is replayed); a step-level fault — injected
via `svc/faultinject`, or a KV-pool OOM eviction couldn't clear —
flushes the completed suffix (the step in flight included), rewinds live
slots to their checkpoints and replays only the lost tail. The
differential contract is what makes this sha-provable: replayed steps
re-emit the SAME tokens, so a faulted run's outputs are byte-identical
to the fault-free run. A restore re-enters from still-resident
pinned blocks (no recompute); a recurrent model's re-prefills prompt ++
emitted[:-1] through the bucketed chunk programs. Retry exhaustion,
admission OOM that outlives ``hpx.serving.admit_retries``, and lapsed
submit() deadlines shed requests with typed errors into `failed`.

Build on the single-sequence machinery in models/transformer.py; the
per-row-position block lives here (the scalar-position `_block_decode`
stays the lean fast path for uniform decode).
"""

from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..cache.block_allocator import BlockAllocator, CacheOOM, block_bytes
from ..cache.ngram import propose as _ngram_propose
from ..cache.page_table import (PageTable, TwoGrainTable, WindowTable,
                                materialize,
                                occupancy)
from ..cache.radix import RadixCache
from ..core.errors import Error, HpxError
from ..svc import faultinject, flight, progprof, tracing
from ..svc.resiliency import sync_replay
from ..ops.attention_pallas import (latent_groups_coalesced,
                                     latent_walk_sizes,
                                     resolve_paged_block,
                                     walk_heads_per_copy)
from ..ops.kda import kda_mix
from ..ops.paged_attention import (
    block_rows,
    gather_block_kv,
    latent_takes_kernel,
    paged_decode_attention,
    paged_latent_attention,
    paged_window_attention,
    scatter_seq_blocks,
    scatter_seq_blocks_q,
)
from .moe import ROUTED_LEAVES
from .transformer import (
    _PREFILL_CHUNK,
    RECURRENT_KINDS,
    TransformerConfig,
    _cached_attention,
    _cached_program,
    _decode_window,
    _embed,
    _layer,
    _logits,
    _next_logits,
    _pick_row,
    _tree_key,
    _window_tail,
    latent_groups,
)

__all__ = ["ContinuousServer", "DeadlineExceededError",
           "RequestShedError", "ServerClosedError", "SlotCheckpoint"]

# the knob subset a LIVE server re-reads from the runtime config at
# flush boundaries (_reload_knobs). Only keys whose raw config value
# actually CHANGED since construction are applied — a constructor
# argument (e.g. a DecodeWorker's explicit prefill_chunk) must not be
# clobbered by an unrelated config write bumping the generation.
_RELOADABLE_KNOBS = (
    "hpx.serving.prefill_chunk",
    "hpx.serving.max_async_steps",
    "hpx.serving.ckpt_every",
    "hpx.serving.spec.k",
    "hpx.serving.moe.capacity_factor",
    "hpx.cache.radix_budget_blocks",
    "hpx.cache.tier.host_budget_mb",
)


class ServerClosedError(HpxError):
    """submit() after shutdown(). Typed (invalid_status) so a client
    can tell "server is draining" from a malformed request — before
    this error existed, post-shutdown submissions enqueued silently
    onto a server nobody was going to drive."""

    def __init__(self, message: str = ""):
        super().__init__(Error.invalid_status,
                         message or "server is shut down — submit() no "
                         "longer accepts requests (queued and in-flight "
                         "work still drains via run())",
                         "ContinuousServer.submit")


class RequestShedError(HpxError):
    """The server gave up on one request: step-retry exhaustion,
    admission OOM that outlived its deferral budget, or overload.
    Recorded per-rid in ``ContinuousServer.failed``; the code is
    service_unavailable — shed work is client-retryable, unlike a
    bad_parameter rejection."""

    def __init__(self, rid: int, reason: str):
        super().__init__(Error.service_unavailable,
                         f"request {rid} shed: {reason}",
                         "ContinuousServer")
        self.rid = rid
        self.reason = reason


class DeadlineExceededError(RequestShedError):
    """Shed because the submit()-time deadline lapsed while the
    request was still queued or prefilling — the overload fail-fast
    path (a starving queue sheds instead of aging out)."""

    def __init__(self, rid: int, deadline_s: Optional[float]):
        RequestShedError.__init__(
            self, rid,
            f"deadline of {deadline_s or 0.0:g}s lapsed before the "
            "request went live")
        self.deadline_s = deadline_s


def _raw_key_shape() -> Tuple[int, ...]:
    """Shape of a raw key of the default PRNG implementation (traced,
    never run: no program, no transfer)."""
    return jax.eval_shape(jax.random.PRNGKey, 0).shape


def _normalize_key(key) -> np.ndarray:
    """Bring a user PRNG key to host NumPy in the raw uint32 layout the
    batched sampler needs, once, at submit(): the per-slot keys are ONE
    `uint32[slots, 2]` host array whose rows an admission's program
    sets on the device, so a typed jax.random.key array is unwrapped
    via key_data (the one blocking read a key costs, outside step()),
    a raw uint32 array passes through, and anything else is rejected
    here instead of surfacing as a shape error deep in step()."""
    try:
        if isinstance(key, jax.Array) and jnp.issubdtype(
                key.dtype, jax.dtypes.prng_key):
            key = jax.random.key_data(key)
        arr = np.asarray(key)
    except (TypeError, ValueError) as e:
        raise ValueError(
            f"key is not a PRNG key (got {type(key).__name__}); pass "
            "jax.random.key(seed) or jax.random.PRNGKey(seed)") from e
    raw = _raw_key_shape()
    if arr.shape != raw or arr.dtype != np.uint32:
        raise ValueError(
            "key must be a typed jax.random.key(...) or a raw uint32 "
            f"jax.random.PRNGKey(...) of shape {raw}; got shape "
            f"{arr.shape} dtype {arr.dtype}")
    return arr


def _seed_lane(logits_row, cur, temp, keys, slot, temperature, key,
               pos):
    """An admission's part of the per-slot vectors, inside the program
    that made `logits_row`: the seed token is `_pick_row` at the last
    prompt position `pos` (the step's and the verify window's own
    pick: argmax at temperature 0, `_sample_row`'s draw at row 0
    otherwise, i.e. generate()'s tok0), and `slot`'s lane of the
    feedback tokens, the temperatures and the keys is set to the
    request's. Returns the three vectors and the token."""
    tok0 = _pick_row(logits_row, key, temperature, pos).astype(cur.dtype)
    return (cur.at[slot].set(tok0), temp.at[slot].set(temperature),
            keys.at[slot].set(key), tok0)


def _resolve_buckets(spec, chunk: int) -> Tuple[int, ...]:
    """The chunk-width ladder: ``auto`` doubles from 8 up to the chunk
    size; a csv spec is parsed, clamped to the chunk (a chunk program
    never sees a wider window), and always completed with the full
    chunk width so every chunk has a bucket."""
    if spec is None or str(spec).strip() in ("", "auto"):
        ladder, w = [], 8
        while w < chunk:
            ladder.append(w)
            w *= 2
        ladder.append(chunk)
        return tuple(sorted(set(ladder)))
    vals: List[int] = []
    for part in str(spec).split(","):
        part = part.strip()
        if not part:
            continue
        v = int(part)
        if v < 1:
            raise ValueError(
                f"hpx.serving.prefill_buckets entries must be >= 1, "
                f"got {v}")
        vals.append(min(v, chunk))
    if not vals:
        raise ValueError(
            f"hpx.serving.prefill_buckets parsed to nothing: {spec!r}")
    vals.append(chunk)
    return tuple(sorted(set(vals)))


# the widest chunk the ridge rule hands any model: `jit_chunk` at 512
# rows compiles for the chip beside the pools at every benchmark cell's
# real size (PERF.md section 6, PR 38: its temporaries grow with the
# rows); one number for every model
_CHUNK_CEILING = 512


def _ridge_chunk(params, cfg: TransformerConfig, ridge: float) -> int:
    """The chunk width at which a chunk's arithmetic takes as long as
    its weight read on a device of `ridge` FLOPs a byte: ridge x B /
    (2 N), B the bytes of the layers' parameter leaves (what a chunk
    without logits reads: every HELD expert of a sparse layer, neither
    embedding nor head) and N the parameters one row multiplies (the
    same leaves' elements, a routed expert's counted by the share of
    the ROUTER's experts a row goes to). Below it a wider chunk is
    nearly free, above it time grows with the rows. The power of two
    nearest in ratio, between today's 128 and `_CHUNK_CEILING`; 128
    where the device's ridge is unknown (0)."""
    if ridge <= 0.0:
        return _PREFILL_CHUNK
    share = cfg.moe_top_k / cfg.n_experts if cfg.n_experts else 1.0
    nbytes = mults = 0.0
    for lp in params["layers"]:
        moe = lp.get("moe", {})
        routed = sum(x.size for x in jax.tree.leaves(
            [moe[k] for k in ROUTED_LEAVES if k in moe]))
        leaves = jax.tree.leaves(lp)
        nbytes += sum(x.size * x.dtype.itemsize for x in leaves)
        mults += sum(x.size for x in leaves) - (1.0 - share) * routed
    rows = ridge * nbytes / (2.0 * mults)
    return min(max(2 ** round(math.log2(rows)), _PREFILL_CHUNK),
               _CHUNK_CEILING)


def _resolve_kv_dtype(kv_dtype, rc) -> str:
    """The pool dtype of a server: the constructor argument,
    else ``hpx.cache.kv_dtype``; validated."""
    if kv_dtype is None:
        kv_dtype = rc.get("hpx.cache.kv_dtype", "bf16")
    if kv_dtype not in ("bf16", "int8", "fp8"):
        raise ValueError(
            "hpx.cache.kv_dtype must be one of 'bf16' (pools in "
            "the model compute dtype), 'int8' (quantized blocks "
            "with absmax scale sidecars) or 'fp8' (e4m3 blocks "
            f"with the same sidecars), got {kv_dtype!r}")
    return kv_dtype


def _resolve_paged_kernel(paged_kernel, rc) -> str:
    """The paged attention formulation: the constructor argument,
    else ``hpx.serving.paged_kernel``; ``auto`` -> fused on TPU,
    gather elsewhere; validated."""
    if paged_kernel is None:
        paged_kernel = rc.get("hpx.serving.paged_kernel", "auto")
    if paged_kernel in (None, "", "auto"):
        # the fused Pallas table-walk kernel is native on TPU;
        # everywhere else the XLA gather formulation is the fast
        # path (interpret-mode Pallas is a test vehicle, not a
        # serving path)
        paged_kernel = ("fused" if jax.default_backend() == "tpu"
                        else "gather")
    if paged_kernel not in ("gather", "fused", "fused_online"):
        raise ValueError(
            "hpx.serving.paged_kernel must be one of 'auto', "
            "'gather', 'fused' (bitwise Pallas table walk) or "
            "'fused_online' (O(block)-scratch online softmax), "
            f"got {paged_kernel!r}")
    return paged_kernel


def _moe_rows(h, lp, cfg, moe_cf=None, moe_ep=None, moe_sink=None):
    """The sparse FFN of the serving bodies, over the token block h
    [B, W, D]. On one shard with no finite capacity set (`moe_cf`
    None or >= n_experts: the token-identity default) it is the
    drop-free `moe_ffn_serve`. `moe_ep` = (axis_name, axis_size)
    routes expert-parallel through `moe_ffn_decode` — only valid
    inside a shard_map body; that, and a finite `moe_cf`, take the
    GShard capacity dispatch. `moe_sink` (a list) collects the
    per-layer psum-complete stats vector."""
    from .moe import moe_ffn, moe_ffn_decode, moe_ffn_serve
    from .transformer import _moe_cfg
    b, w, d = h.shape
    h2 = h.reshape(b * w, d)
    cf = float(cfg.n_experts) if moe_cf is None else float(moe_cf)
    mcfg = dataclasses.replace(_moe_cfg(cfg), capacity_factor=cf)
    if moe_ep is not None:
        out, _aux, stats = moe_ffn_decode(h2, lp["moe"], mcfg,
                                          moe_ep[0], moe_ep[1])
    elif cf >= cfg.n_experts:
        out, stats = moe_ffn_serve(h2, lp["moe"], mcfg)
    else:
        out, _aux, stats = moe_ffn(h2, lp["moe"], mcfg,
                                   return_stats=True)
    if moe_sink is not None:
        moe_sink.append(stats)
    return out.reshape(b, w, d)


def _moe_fold(sink, cfg):
    """Fold the per-layer MoE stats vectors into ONE [2 + E] f32
    program output: routed / dropped-over-capacity claims SUM over
    layers, per-expert occupancy fractions AVERAGE over layers; what a
    group-limited router appends (`moe.STATS_HERE` counts) sums too.
    Returns None (an empty pytree — legal jit/shard_map output) for
    dense models, so every driver can return it unconditionally."""
    if not sink:
        return None
    from .moe import STATS_HERE
    s = jnp.sum(jnp.stack(sink), axis=0)
    end = s.shape[0] - (STATS_HERE if cfg.moe_n_group > 1 else 0)
    return jnp.concatenate([s[:2], s[2:end] / len(sink), s[end:]])


def _dp_rows(x, dp):
    """Every dp shard's slot rows, concatenated in slot order along
    axis 0 and TYPED replicated over dp (`dp` = (axis name, size), or
    None off the mesh: identity). Each shard places its rows in a zero
    buffer and a psum closes it — shard_map's replication check proves
    a psum's result replicated, not an all_gather's (collectives/
    device.py) — over the value's BITS, so the copy is exact down to
    the sign of zero."""
    if dp is None:
        return x
    name, size = dp
    n = x.shape[0]
    bits = x
    if not jnp.issubdtype(x.dtype, jnp.integer):
        bits = jax.lax.bitcast_convert_type(
            x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
    buf = jnp.zeros((size * n,) + x.shape[1:], bits.dtype)
    buf = jax.lax.dynamic_update_slice_in_dim(
        buf, bits, jax.lax.axis_index(name) * n, 0)
    out = jax.lax.psum(buf, name)
    return (out if out.dtype == x.dtype
            else jax.lax.bitcast_convert_type(out, x.dtype))


def _write_rows(k, v, write):
    """(k_new, v_new, paged_attention's `write=`) for one layer. Off
    the mesh (`write` None) a step writes the rows it attends. On it,
    `write` = (dp, all-slot table, all-slot positions): every dp shard
    writes EVERY slot's new rows into its copy of the pools — K and V
    closed by one psum over dp — which is what keeps the copies equal,
    the replication the pool spec declares."""
    if write is None:
        return k, v, None
    dp, table, pos = write
    kv = _dp_rows(jnp.stack([k, v], axis=1), dp)
    return kv[:, 0], kv[:, 1], (table, pos)


def _window_rows(x, lp, kv, pos0, cfg: TransformerConfig, li=0):
    """Layer `li` for a W-token window per slot at PER-SLOT positions
    over dense per-slot caches (the speculative DRAFT model's: the
    server's own cache is the block pools, `_paged_window_rows`):
    `_layer` with those caches as its mixer. x [B, W, D]; kv:
    (k_cache, v_cache) [B, Smax, Nkv, H]; pos0 [B] int32 — slot b's
    window row i lands at cache position pos0[b] + i and attends
    positions <= pos0[b] + i.

    Same projections, same einsum contractions over the same smax
    rows, same -inf mask and f32 softmax at every W — so window column
    i's output is byte-identical to what the i-th SEQUENTIAL step
    would compute (K/V rows are functions of (token, position) alone,
    and column i's horizon includes exactly the window rows < i it
    would have already written). Window columns past smax-1 (a dead
    slot's stale cursor, or batch-width padding beyond a short slot's
    budget) scatter with ``mode="drop"``: clamping would corrupt row
    smax-1, which can hold live K/V."""
    posw = pos0[:, None] + jnp.arange(x.shape[1])[None, :]   # [B, W]
    rows = jnp.arange(x.shape[0])[:, None]

    def attend(q, k, v):
        kc = kv[0].at[rows, posw].set(k, mode="drop")
        vc = kv[1].at[rows, posw].set(v, mode="drop")
        return _cached_attention(q, kc, vc, posw, cfg.window(li)), \
            (kc, vc)

    return _layer(x, lp, cfg, li, posw, attend, None,
                  lambda h: _moe_rows(h, lp, cfg))


def _decode_window_rows(params, caches, toks, pos0, cfg):
    """W tokens per slot through every layer at per-slot positions
    over dense caches; toks [B, W] int32, pos0 [B] int32. Returns
    (caches, f32 logits [B, W, V])."""
    x = _embed(params, toks, cfg)
    new_caches = []
    for li, (lp, kv) in enumerate(zip(params["layers"], caches)):
        x, kv = _window_rows(x, lp, kv, pos0, cfg, li)
        new_caches.append(kv)
    return new_caches, _logits(params, x, cfg).astype(jnp.float32)


def _decode_rows(params, caches, tok, pos, cfg):
    """One token per slot: the W == 1 case of `_decode_window_rows`
    (logits [B, V]), the draft model's step."""
    caches, logits = _decode_window_rows(
        params, caches, tok[:, None], pos, cfg)
    return caches, logits[:, 0, :]


def _paged_window_rows(x, lp, pools, scales, table, pos0,
                       cfg: TransformerConfig, li=0, fused=False,
                       tp_axis=None, moe_cf=None, moe_ep=None,
                       moe_sink=None, write=None):
    """Layer `li` for a W-token window per slot (W = 1: one decode
    token a slot; W > 1: the speculative verify window) with the K/V
    rows living in a shared BLOCK POOL: `_layer` with the pools as its
    mixer. x: [B, W, D]; pools: (k_pool, v_pool) each [num_blocks,
    Nkv, block_size, H]; scales: (k_scale, v_scale) [num_blocks, Nkv]
    f32 sidecars for quantized pools, or None; table: [B, max_blocks]
    int32 logical->physical block map OF THIS LAYER'S GROUP (a window
    layer's is its ring, `cfg.window(li)` names the width); pos0: [B]
    int32. Projections/rope/ffn are byte-identical to `generate()`'s
    dense caches; only the cache write (scatter through the table) and
    read (gather in logical order — same row values at the same
    logical indices, or the fused Pallas table walk) differ, which is
    what keeps the server token-exact to it, under speculation too
    (`ops.paged_attention` holds both, W == 1 as
    `paged_decode_attention`).

    Under shard_map on a (dp, tp) mesh, `tp_axis` names the
    tensor-parallel axis: every shard sees its LOCAL kv-head slice of
    the pools (block axis replicated over dp) and the partial attention
    / ffn outputs close with explicit psums; `write` as in
    `_write_rows`.

    A layer's MIXER kind names what `pools` holds: K/V pools (above); a
    "kda" layer's per-slot (state [B, H, d, d] float32, conv tail [B,
    K - 1, 3 H d]), no table, no positions; a "lightning" layer's
    per-slot (state,) and a "mamba" layer's (state [B, N, C] float32,
    conv tail [B, (K - 1) C]) alike; an "mla" layer's (latent pool [num_blocks,
    1, block_size, R],) on the full group's table; a "sparse" layer's
    (K pool, V pool, index pool [num_blocks, block_size / stride * Nkv,
    H] float32, the last step's chosen block ids [B, Nkv, K] and their
    count [B, Nkv]) on the full group's table
    (ops/sparse_attention.paged_sparse_decode); an "eva" layer's (K
    pool, V pool) whose table lists a slot's summary blocks, then its
    window's exact blocks: ONE gap-free run of rows, in which position
    p is row `ops/eva.eva_row(p)` and every row up to it is attended."""
    w = x.shape[1]
    posw = pos0[:, None] + jnp.arange(w)[None, :]
    kw = {"fused": fused, "window": cfg.window(li)}
    if scales is not None:
        kw.update(k_scale=scales[0], v_scale=scales[1])

    def attend(q, k, v):
        if w == 1:
            k, v, paged = k[:, 0], v[:, 0], paged_decode_attention
        else:
            paged = paged_window_attention
        kn, vn, wr = _write_rows(k, v, write)
        out = paged(q, kn, vn, *pools, table, pos0, write=wr, **kw)
        return out[0], (out[1:3], tuple(out[3:]) or None)

    if "kda" in lp:
        def attend(pre, g, beta):                           # noqa: F811
            o, carry = kda_mix(pre, g, beta, lp["kda"]["conv"], *pools)
            return o, (carry, None)
    elif "lightning" in lp:
        from ..ops.lightning import lightning_mix

        def attend(q, k, v):                                # noqa: F811
            o, carry = lightning_mix(q, k, v, cfg.lightning_decay(li),
                                     *pools)
            return o, (carry, None)
    elif "mamba" in lp:
        from ..ops.mamba import mamba_mix

        def attend(u):                                      # noqa: F811
            o, carry = mamba_mix(u, lp["mamba"], *pools,
                                 eps=cfg.norm_eps)
            return o, (carry, None)
    elif "eva" in lp:
        from ..ops.eva import eva_row

        def attend(q, k, v):                                # noqa: F811
            if w != 1:
                raise NotImplementedError(
                    "a W-token window over a two-grain table (a column "
                    "behind a window boundary needs summaries the step "
                    "has not pooled: ops/eva.py, models/serving.py "
                    "_eva_roll)")
            # the walk that serves K/V pairs, at the position's row in
            # the slot's one run [summaries | window]
            row = eva_row(pos0, cfg.eva_chunk, cfg.eva_window)
            out = paged_decode_attention(q, k[:, 0], v[:, 0], *pools,
                                         table, row, fused=fused)
            return out[0], (out[1:3], None)
    elif "sparse" in lp:
        from ..ops.sparse_attention import paged_sparse_decode

        def attend(q, k, v):                                # noqa: F811
            if w != 1:
                raise NotImplementedError(
                    "a W-token window over a sparse layer's paged pools "
                    "(ops/sparse_attention.paged_sparse_decode selects "
                    "and walks for one row a slot)")
            out = paged_sparse_decode(
                q, k[:, 0], v[:, 0], *pools[:3], table, pos0,
                cfg.sparse_spec, None if fused else "gather")
            return out[0], (tuple(out[1:]), None)
    elif "mla" in lp:
        def attend(q, row):                                 # noqa: F811
            if w != 1:
                raise NotImplementedError(
                    "a W-token window over a paged latent pool "
                    "(ops/paged_attention.paged_latent_attention "
                    "attends one row a slot)")
            o, pool = paged_latent_attention(
                q[:, 0], row[:, 0], pools[0], table, pos0,
                rank=cfg.mla_rank, fused=fused, scale=cfg.mla_scale)
            return o[:, None], ((pool,), None)

    x, (pools, scales) = _layer(
        x, lp, cfg, li, posw, attend, tp_axis,
        lambda h: _moe_rows(h, lp, cfg, moe_cf, moe_ep, moe_sink))
    return x, pools, scales


def _paged_decode_window_rows(params, pools, scales, toks, tables, pos0,
                              cfg, fused=False, tp_axis=None,
                              moe_cf=None, moe_ep=None, dp=None):
    """W tokens per slot over paged pools (W = 1: the decode step;
    else the speculative-verify forward); returns (pools, scales, f32
    logits [B, W, V], mstats) — mstats is the folded MoE stats vector
    (None for dense models). `tables`: one [B, max_blocks] map a
    block GROUP, (full,) or (full, window ring); a layer reads its
    group's. `scales` is the per-layer list of (k_scale, v_scale)
    sidecars for quantized pools, or None (passed through untouched)."""
    x = _embed(params, toks, cfg)
    new_pools, new_scales = [], []
    sink = []
    writes = [dp and (dp, _dp_rows(t, dp), _dp_rows(pos0, dp))
              for t in tables]
    for li, (lp, pl) in enumerate(zip(params["layers"], pools)):
        grp = 1 if cfg.window(li) else 0
        sc = None if scales is None else scales[li]
        x, pl, sc = _paged_window_rows(x, lp, pl, sc, tables[grp], pos0,
                                       cfg, li, fused, tp_axis, moe_cf,
                                       moe_ep, sink, writes[grp])
        new_pools.append(pl)
        new_scales.append(sc)
    return (new_pools, None if scales is None else new_scales,
            _logits(params, x, cfg).astype(jnp.float32),
            _moe_fold(sink, cfg))


def _paged_decode_rows(params, pools, scales, tok, tables, pos, cfg,
                       fused=False, tp_axis=None, moe_cf=None,
                       moe_ep=None, dp=None):
    """One token per slot over paged pools: the W == 1 case of
    `_paged_decode_window_rows` (logits [B, V])."""
    pools, scales, logits, ms = _paged_decode_window_rows(
        params, pools, scales, tok[:, None], tables, pos, cfg, fused,
        tp_axis, moe_cf, moe_ep, dp)
    return pools, scales, logits[:, 0, :], ms


def _scratch_entry(cfg: TransformerConfig, smax: int, i: int):
    """Layer i's part of an empty b=1 prefill scratch, by its mixer's
    kind: (k, v) [1, smax, n_kv, hd] (a "sparse" layer's too: its index
    is the means of k's rows); (latent rows [1, smax, 1, R],); or a
    recurrent layer's state of no tokens, zeros: (state [1, H, d, d]
    float32, conv tail [1, K - 1, 3 H d]), "lightning" (state,), or
    "mamba" (state [1, N, C] float32: the channels on the minor axis;
    conv tail [1, (K - 1) C]: its rows side by side; ops/mamba.py); or
    an "eva" layer's two grains apart (exact K, exact V [1, eva_window,
    H, hd]: ONE window's rows as a ring; summary K, summary V [1,
    summaries of the windows that can complete, H, hd]; ops/eva.py):
    (eva_window + smax / eva_chunk) rows a layer where a K/V scratch
    holds smax."""
    kind = cfg.mixer(i)
    if kind == "eva":
        from ..ops.eva import scratch_entry
        return scratch_entry(smax, cfg.n_heads, cfg.head_dim,
                             cfg.eva_chunk, cfg.eva_window, cfg.dtype)
    if kind == "mla":
        return (jnp.zeros((1, smax, 1, cfg.mla_row), cfg.dtype),)
    if kind == "kda":
        h, d = cfg.kda_heads, cfg.kda_head_dim
        return (jnp.zeros((1, h, d, d), jnp.float32),
                jnp.zeros((1, cfg.kda_conv - 1, 3 * h * d), cfg.dtype))
    if kind == "lightning":
        h, d = cfg.lightning_heads, cfg.lightning_head_dim
        return (jnp.zeros((1, h, d, d), jnp.float32),)
    if kind == "mamba":
        c = cfg.mamba_d_inner
        return (jnp.zeros((1, cfg.mamba_d_state, c), jnp.float32),
                jnp.zeros((1, (cfg.mamba_d_conv - 1) * c), cfg.dtype))
    shape = (1, smax, cfg.kv_heads, cfg.head_dim)
    return (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))


def _verify_tail(logits, toks, kvec, temp, keys, pos0, width):
    """Device-side tail of the verify program: pick the
    target token at every window position with the SAME `_pick_row`
    the sequential step uses, then count the longest prefix of drafts
    agreeing with them.

    Window column i holds draft d_i (column 0 the committed cur
    token); target t_i = pick(logits[i]) is the token the sequential
    decode would emit after consuming column i. Draft d_i is accepted
    iff d_i == t_{i-1} AND every earlier draft was (cumprod), capped
    by the slot's real draft count kvec. The committed emission is
    t_0..t_acc — acc+1 tokens, always >= 1 — so content NEVER depends
    on the drafts, only on the targets the step program would have
    produced (greedy argmax, or the deterministic (key, pos)
    categorical draw: acceptance-rejection against a deterministic
    sampler collapses to exact token match). Everything returns in ONE
    packed [B, width+1] int32 array (targets ‖ acc) = one host read
    per spec step."""
    offs = jnp.arange(width)
    tgt = jax.vmap(
        lambda rows, key, t, p0: jax.vmap(
            lambda row, p: _pick_row(row, key, t, p))(rows, p0 + offs)
    )(logits, keys, temp, pos0)
    match = jnp.logical_and(toks[:, 1:] == tgt[:, :-1],
                            offs[None, 1:] <= kvec[:, None])
    acc = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
    return jnp.concatenate(
        [tgt.astype(jnp.int32), acc[:, None]], axis=1)


@dataclasses.dataclass
class SlotCheckpoint:
    """Host-side restore point for one LIVE slot, captured at flush
    boundaries, at the frontier of the tokens the host holds (``pos =
    plen + len(tokens) - 1``, cache rows [0, pos) hold prompt ++
    tokens[:-1], and ``cur = tokens[-1]`` is the next feedback token;
    a step still in flight has written rows at and past pos, which the
    replay rewrites with the same bytes) every
    ``hpx.serving.ckpt_every`` emitted tokens.

    ``pins`` hold ONE extra allocator reference per FULL
    block below pos (rows [0, pos - pos % block_size)): the pin keeps
    eviction and slot-retire from recycling the block, and a full
    block is append-complete — this slot never writes it again, so
    the extra ref never provokes a `_cow_guard` fork (pinning the
    partial frontier block would: refcount >= 2 makes the very next
    token write fork+copy, one extra block per live slot — fatal in a
    barely-sized pool). The frontier block's rows [0, pos % bs) need
    no pin at all: KV rows are append-only (written exactly once, at
    their position) and a COW fork copies every row written so far,
    so the slot's CURRENT table always holds them byte-exact. Restore
    rebuilds the PageTable from pins ++ the live table's frontier
    block; the replayed decode suffix re-enters from still-resident
    KV. A recurrent model pins nothing and restores by re-prefilling
    prompt ++ tokens[:-1] (`_restore_recurrent`)."""

    rid: int
    tokens: List[int]              # emitted tokens at capture (copy)
    pos: int                       # next write position per invariant
    cur: int                       # feedback token (= tokens[-1])
    slot_k: int                    # spec adaptive-k at capture
    slot_acc: float                # spec acceptance EMA at capture
    pins: List[int] = dataclasses.field(default_factory=list)
    # window group: (base, every block live at capture, the frontier
    # too — nothing shares a window block, so no pin forks one)
    wpins: Optional[Tuple[int, List[int]]] = None


@dataclasses.dataclass
class _Request:
    rid: int
    prompt: Any                    # [plen] int32 host array
    max_new: int
    eos_id: Optional[int]
    temperature: float = 0.0       # 0: greedy; >0: sample with `key`
    key: Any = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    sent: int = 0                  # tokens DISPATCHED (>= len(tokens))
    t_submit: float = 0.0          # monotonic submit time (TTFT)
    deadline_s: Optional[float] = None   # submit()-time budget
    t_deadline: Optional[float] = None   # absolute monotonic deadline
    # disaggregated serving (admit_prefilled): prefill happened on a
    # REMOTE worker; admission splices these shipped KV rows instead
    # of computing a prefill. Host arrays only — no blocks are held
    # until the slot admits, so a shed queued transfer leaks nothing.
    xfer_rows: Any = None          # np [layers, 2, plen, n_kv, hd]
    xfer_seed: Optional[int] = None   # remote probe's seeded token


@dataclasses.dataclass
class _PendingPrefill:
    """One in-flight chunked prefill: owns a reserved slot and a b=1
    scratch cache; `done` is the absolute prompt cursor (starts at the
    radix-matched prefix length). The chunks run to the
    prompt's END on every model: a recurrent layer consumes the last
    token once, in its chunk."""
    req: _Request
    slot: int
    caches: Any                    # b=1 [1, smax] scratch, per layer
    done: int                      # prompt tokens already in scratch
    seq: int                       # admission order (FIFO tiebreak)
    pt: Optional[PageTable] = None  # blocks held for the request
    trow: Any = None               # host [maxb] table row
    wrow: Any = None               # splice WRITE rows, one a
                                   # block group (matched prefix
                                   # entries point at trash)
    wt: Optional[WindowTable] = None   # blocks held in the window group
    flow: Optional[int] = None     # tracing flow id chaining the chunks
    step0: int = 0                 # the step() call that gave the slot
    row: Any = None                # [1, 1, d_model], what the newest
                                   # chunk handed back: its last real
                                   # column ahead of the last layer,
                                   # where the probe starts

    @property
    def remaining(self) -> int:
        return len(self.req.prompt) - self.done


_now_ns = time.perf_counter_ns


class _Dispatch:
    """What `ContinuousServer._program()` hands out: the named
    program, its call inside a `serving.dispatch` span (`prog`, and
    `rid` where an admission is under way) and its nanoseconds on the
    step's account: the time the runtime HOLDS the host in the call,
    not the program's execution. Anything else (`lower`, ...) is the
    program's own."""

    __slots__ = ("_srv", "_name", "_prog")

    def __init__(self, srv: "ContinuousServer", name: str, prog) -> None:
        self._srv, self._name, self._prog = srv, name, prog

    def __call__(self, *args, **kwargs):
        srv, name = self._srv, self._name
        rid = srv._rid
        t0 = _now_ns()
        with (tracing.span("serving.dispatch", "serving", prog=name)
              if rid is None else
              tracing.span("serving.dispatch", "serving", prog=name,
                           rid=rid)):
            out = self._prog(*args, **kwargs)
        srv._acct.dispatched(name, _now_ns() - t0)
        return out

    def __getattr__(self, attr: str):
        return getattr(self._prog, attr)


class _Read:
    """The span around ONE blocking device->host read
    (`ContinuousServer._wait`), its nanoseconds on the step's account."""

    __slots__ = ("_acct", "_span", "_t0")

    def __init__(self, acct: tracing.StepAccount, span) -> None:
        self._acct, self._span = acct, span

    def __enter__(self) -> "_Read":
        self._t0 = _now_ns()
        self._span.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._span.__exit__(*exc)
        self._acct.waited_ns += _now_ns() - self._t0
        return False


class ContinuousServer:
    """Slot-based continuous batching, per-request greedy or sampled.

    ::

        srv = ContinuousServer(params, cfg, slots=4, smax=256)
        a = srv.submit([3, 1, 4], max_new=16)
        b = srv.submit([2, 7], max_new=8, eos_id=0)
        out = srv.run()            # {a: [tokens...], b: [tokens...]}

    The cache is ONE pool of fixed-size blocks a layer (`block_size`
    rows each, `num_blocks` of them, shared by every slot) and a
    per-slot TABLE that maps a request's positions onto the blocks it
    holds (`cache/page_table`, `cache/block_allocator`); a retired
    prompt's full blocks stay in a radix tree for the next request
    that starts with them (``prefix_reuse``). One jitted step decodes
    every live slot at its own position through its table; finished
    slots retire and queued requests admit between steps. Prompts
    prefill on a b=1 contiguous SCRATCH (the matched prefix gathered
    into it) in BUCKETED fixed-width chunks (pad-then-mask; widths from
    the ``hpx.serving.prefill_buckets`` ladder), then a one-row probe
    of the last prompt position, ONE layer deep (it starts from the
    hidden row the last chunk handed back), picks the seed token and
    the scratch splices into the request's blocks — so the program
    cache holds O(buckets) prefill programs regardless of the
    prompt-length mix. A prompt whose
    remaining tokens exceed ``hpx.serving.prefill_chunk`` becomes a
    PENDING prefill: it advances one chunk per step interleaved with
    live decode (shortest-remaining-first across pendings), so admits
    never stall the running batch. Dead slots compute masked no-op
    work (static shapes).

    With ``hpx.serving.async_dispatch`` (default on) the step loop
    keeps the sampled-token feedback on device and defers the
    device->host read until a token value is needed (eos check or a
    retirement) or ``hpx.serving.max_async_steps`` steps are buffered;
    results and retirement timing are unchanged — only the forced
    per-step sync goes away.

    PER-REQUEST decoding mode: greedy by default, or submit(...,
    temperature=t, key=k) to sample — the key folds follow generate()'s
    exactly (fold position, then row 0), so a sampled request emits the
    SAME tokens it would get from a solo generate(temperature=t, key=k)
    run. top_k truncation is not wired (it is a static shape choice;
    bucket by top_k if needed).

    ``spec=True`` turns each decode step speculative: per-slot drafts
    (``spec_draft='prompt'`` mines the slot's token history;
    ``'model'`` runs ``draft_params``/``draft_cfg``) are verified by
    one window forward and committed only where they match the
    sequential pick — same tokens, fewer host syncs per token. See
    ``spec_stats()`` and the ``/serving{...}/spec/*`` counters."""

    def __init__(self, params, cfg: TransformerConfig, slots: int = 4,
                 smax: int = 512, mesh=None, paged: bool = True,
                 block_size: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 radix_budget_blocks: Optional[int] = None,
                 prefix_reuse: Optional[bool] = None,
                 prefill_chunk: Optional[int] = None,
                 prefill_buckets: Optional[str] = None,
                 async_dispatch: Optional[bool] = None,
                 spec: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_draft: Optional[str] = None,
                 paged_kernel: Optional[str] = None,
                 kv_dtype: Optional[str] = None,
                 draft_params=None,
                 draft_cfg: Optional[TransformerConfig] = None):
        self.cfg = cfg
        self.slots = slots
        self.smax = smax
        self.mesh = mesh
        if not paged:
            # the keyword outlives its choice only for the callers that
            # still pass `paged=True`
            raise ValueError(
                "ContinuousServer(paged=False): the dense server mode "
                "is gone, the block pools are the one cache (drop the "
                "argument); transformer.generate() is the dense oracle")
        # the window layers' width: they are ONE block group beside the
        # full layers' (cache/page_table.WindowTable), so one width
        wins = {cfg.window(i) for i in range(cfg.n_layers)} - {0}
        if len(wins) > 1:
            raise NotImplementedError(
                f"window layers of widths {sorted(wins)}: the paged "
                "cache keeps one window block group (models/serving.py "
                "_init_paged, cache/page_table.WindowTable)")
        self._win = wins.pop() if wins else 0
        from ..core.config import runtime_config
        rc = runtime_config()
        # the mixer kinds beside softmax attention: a "kda", "lightning"
        # or "mamba" layer keeps a per-slot recurrent state, an "mla"
        # layer latent rows, a "sparse" layer an index beside its K/V
        # pools; all live in the cache pytree `_init_paged` builds
        self._recurrent = cfg.recurrent
        self._kinds = kinds = sorted(set(cfg.layer_mixer) - {"attn"})
        # an "eva" layer keeps K/V rows at two grains (ops/eva.py): one
        # table a slot lists its summary blocks, then its window's
        # (cache/page_table.TwoGrainTable)
        self._eva = "eva" in kinds
        # no snapshot can rewind these: a restore recomputes
        self._recomputes = self._recurrent or self._eva
        if kinds and mesh is not None:
            raise NotImplementedError(
                "a (dp, tp) mesh: the state, the latent pool, the index "
                "pool and a two-grain table's roll have no placement; a "
                f"model with {kinds} mixers runs on ContinuousServer on "
                "one device (models/serving.py _init_paged)")
        self._ep_axis, self._ep_size = None, 1
        if mesh is not None:
            # sharded serving: slots over dp, heads over tp, the decode
            # and verify steps under shard_map (`_paged_step_prog`)
            from .transformer import (_decode_ep, _decode_mesh_check,
                                      _decode_pspecs, _place)
            # the shared decode-mesh contract (axes, expert and
            # head/slot divisibility); slots play the batch role
            try:
                _decode_mesh_check(cfg, mesh, slots)
            except ValueError as e:
                raise ValueError(str(e).replace("batch", "slots")) \
                    from None
            self._ep_axis, self._ep_size = _decode_ep(cfg, mesh)
            params = _place(params, _decode_pspecs(params, cfg, mesh),
                            mesh)
        self.params = params
        # what the probe reads: the last layer, final ln and the head
        self._tail_params = {**params, "layers": params["layers"][-1:]}
        # MoE decode state: the capacity-factor knob is an int PERCENT
        # (100 = GShard cf 1.0); 0 = auto = drop-free (cf = n_experts),
        # the token-identity default. Routed/dropped counts and
        # per-expert occupancy come back as one small f32 vector per
        # step program and drain at flush boundaries (async-safe).
        pct = rc.get_int("hpx.serving.moe.capacity_factor", 0)
        self._moe_capacity_pct = (cfg.n_experts * 100 if pct <= 0
                                  else max(1, int(pct)))
        self._moe_routed = 0.0
        self._moe_dropped = 0.0
        self._moe_occ = [0.0] * max(0, cfg.experts_held)
        self._moe_hit_sum = 0.0     # sum over drained steps of the
        self._moe_steps = 0         # occupancy vector's total
        # a group-limited router's own: assignments that fell to the
        # held experts, and tokens whose kept groups include a held one
        # (summed over the sparse layers and the drained steps)
        self._moe_here = self._moe_tokens_here = 0.0
        self._moe_buf: deque = deque()

        # the chunk width: the argument, else the config key, else the
        # device's ridge over the weights a chunk reads (`_ridge_chunk`)
        self._prefill_chunk_src = "arg"
        if prefill_chunk is None:
            v = rc.get("hpx.serving.prefill_chunk", "auto")
            if v in (None, "", "auto"):
                prefill_chunk = _ridge_chunk(params, cfg,
                                             progprof.device_ridge())
                self._prefill_chunk_src = "ridge"
            else:
                prefill_chunk = int(v)
                self._prefill_chunk_src = "config"
        self.prefill_chunk = max(1, int(prefill_chunk))
        if prefill_buckets is None:
            prefill_buckets = rc.get("hpx.serving.prefill_buckets",
                                     "auto")
        self.prefill_buckets = _resolve_buckets(prefill_buckets,
                                                self.prefill_chunk)
        if async_dispatch is None:
            async_dispatch = rc.get_bool("hpx.serving.async_dispatch",
                                         True)
        self._async = bool(async_dispatch)
        self._max_async = max(1, rc.get_int(
            "hpx.serving.max_async_steps", 32))

        # speculative decoding (hpx.serving.spec.*): draft k tokens
        # per slot, verify the window in ONE forward. Spec steps sync
        # every step (the packed targets+acceptance read) — they
        # multiply tokens-per-host-sync instead of deferring the sync.
        if spec is None:
            spec = rc.get_bool("hpx.serving.spec.enable", False)
        self._spec = bool(spec)
        if self._spec and kinds:
            raise NotImplementedError(
                "speculative verify on a model with "
                f"{kinds} mixers: a rejected draft needs the "
                "recurrent state (and a sparse layer's index entry) "
                "rolled back, the latent and the sparse walk attend "
                "one row a slot, and a verify window over a two-grain "
                "table would need its exact rows and a summary published "
                "too early taken back (models/serving.py _spec_step, "
                "ops/kda.py, ops/lightning.py, ops/mamba.py, "
                "ops/sparse_attention.py, ops/eva.py, "
                "ops/paged_attention.paged_latent_attention)")
        if self._spec and self._win:
            raise NotImplementedError(
                "speculative verify on a model with window layers: a "
                "W-token window needs window + W rows, the window "
                "group's ring holds window + 2 blocks (models/serving.py "
                "_spec_step, ops/paged_attention.paged_window_attention)")
        if spec_draft is None:
            spec_draft = rc.get("hpx.serving.spec.draft", "prompt")
            if draft_params is not None:
                spec_draft = "model"  # a checkpoint implies the source
        if spec_draft not in ("prompt", "model"):
            raise ValueError(
                "hpx.serving.spec.draft must be 'prompt' or 'model', "
                f"got {spec_draft!r}")
        self._spec_source = spec_draft
        if spec_k is None:
            spec_k = rc.get_int("hpx.serving.spec.k", 4)
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        # the verify window (k drafts + the current token) rides the
        # prefill bucket ladder, so k is capped at the widest rung - 1
        self._spec_k = min(int(spec_k), self.prefill_buckets[-1] - 1)
        self._spec_ngram = max(1, rc.get_int(
            "hpx.serving.spec.ngram", 3))
        self._spec_min_accept = rc.get_float(
            "hpx.serving.spec.min_accept", 0.3)
        self._spec_adapt = rc.get_bool("hpx.serving.spec.adapt", True)
        self._slot_k = [self._spec_k] * slots   # per-slot adaptive k
        self._slot_acc = [1.0] * slots          # acceptance-rate EMA
        self._spec_drafted = 0                  # /serving/spec/* feed
        self._spec_accepted = 0
        self._spec_steps = 0
        self._spec_emitted = 0
        self._draft_params = None
        self._draft_cfg = None
        self._draft_caches = None
        if self._spec and self._spec_source == "model":
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "spec draft source 'model' needs draft_params and "
                    "draft_cfg (or use spec_draft='prompt' for "
                    "zero-model prompt-lookup drafting)")
            if draft_cfg.vocab != cfg.vocab:
                raise ValueError(
                    f"draft vocab {draft_cfg.vocab} != target vocab "
                    f"{cfg.vocab}")
            if mesh is not None:
                # the draft shares the serving mesh: same placement
                # contract, slots in the batch role
                from .transformer import (_decode_mesh_check,
                                          _decode_pspecs, _place)
                try:
                    _decode_mesh_check(draft_cfg, mesh, slots)
                except ValueError as e:
                    raise ValueError(
                        "draft model cannot share the serving mesh: "
                        + str(e).replace("batch", "slots")) from None
                draft_params = _place(
                    draft_params,
                    _decode_pspecs(draft_params, draft_cfg, mesh),
                    mesh)
            self._draft_params = draft_params
            self._draft_cfg = draft_cfg
            dn, dh = draft_cfg.kv_heads, draft_cfg.head_dim

            # the draft's dense per-slot rows, allocated in their
            # layout: on a mesh slots over dp and heads over tp
            dsh = None
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                dsh = NamedSharding(mesh, P("dp", None, "tp", None))

            def dzeros():
                return jnp.zeros((slots, smax, dn, dh), draft_cfg.dtype,
                                 device=dsh)
            self._draft_caches = [(dzeros(), dzeros())
                                  for _ in range(draft_cfg.n_layers)]

        self._init_paged(block_size, num_blocks, radix_budget_blocks,
                         prefix_reuse, paged_kernel, kv_dtype)
        # windowed decode throughput, read by the serving counters
        from ..svc.performance_counters import RateCounter
        self._rate = RateCounter(window_s=5.0)
        # host-side slot state
        self._slot_req: List[Optional[_Request]] = [None] * slots
        self._pos = [0] * slots         # next write position per slot
        self._cur = [0] * slots         # token to feed next, per slot
        # per-slot sampling state, host NumPy: temperatures, and raw
        # keys (zeros for a request without one: greedy never reads it)
        self._temp = np.zeros((slots,), np.float32)
        self._no_key = np.zeros(_raw_key_shape(), np.uint32)
        self._key = np.zeros((slots,) + self._no_key.shape, np.uint32)
        self._queue: deque = deque()
        self._done: Dict[int, List[int]] = {}
        self._next_rid = 0
        # chunked-prefill state: slot -> in-flight pending
        self._pending: Dict[int, _PendingPrefill] = {}
        self._pf_seq = 0
        # async-dispatch state: buffered (nxt, [(slot, req)]) steps
        # plus device-resident mirrors of the per-slot host vectors;
        # `_seeds`: (req, slot, device token) of admissions whose seed
        # token the host has not read yet; `_read_due`: the step just
        # dispatched asked for a read, which the next step() makes
        self._buf: deque = deque()
        self._seeds: deque = deque()
        self._read_due = False
        # blocking reads with / without a decode step queued behind
        self._reads_overlapped = 0
        self._reads_draining = 0
        # the step's account (svc/tracing.StepAccount): fed by step(),
        # by every program `_program` hands out and by `_wait`; `_rid`:
        # the request an admission's dispatches are for
        self._acct = tracing.StepAccount()
        self._rid: Optional[int] = None
        self._admits = 0                # requests `_admit` took up
        # device mirrors of the three per-slot vectors (`_feedback`,
        # `_lanes`): the
        # step's output feeds back as `_cur_dev`, an admission's probe
        # sets its slot's lane of all three; None: stale, rebuilt from
        # the host's by one transfer each
        self._cur_dev = None            # [slots] int32 token feedback
        self._temp_dev = None           # [slots] f32 (with _keys_dev)
        self._keys_dev = None           # [slots, 2] uint32
        # observability
        self._chunks = 0                # prefill chunk dispatches
        self._chunk_rows = 0            # prompt tokens they computed
        # walks of the scratch their latent layers made
        # (`transformer.latent_groups` a chunk's width and layer)
        self._latent_groups = 0
        # step() calls between a request's slot and its first token's
        # program, summed over the admissions
        self._admit_wait_steps = 0
        self._prog_hits = 0             # program-cache hits
        self._prog_misses = 0           # program-cache misses (compiles)
        self.ttft: Dict[int, float] = {}  # rid -> submit->seed seconds
        # resiliency: checkpoint cadence, step-retry policy, deadline
        # and shed accounting (ROADMAP item 5). `failed` is the typed
        # failure surface — run() keeps returning successes only.
        self._ckpt_every = max(1, rc.get_int(
            "hpx.serving.ckpt_every", 16))
        self._step_retries = max(1, rc.get_int(
            "hpx.serving.step_retries", 4))
        self._retry_backoff_s = max(0.0, rc.get_float(
            "hpx.serving.retry_backoff_s", 0.005))
        self._admit_retries = max(0, rc.get_int(
            "hpx.serving.admit_retries", 8))
        self._default_deadline_s = rc.get_float(
            "hpx.serving.default_deadline_s", 0.0)
        self._max_verify_faults = max(1, rc.get_int(
            "hpx.serving.spec.max_verify_faults", 2))
        self._ckpt: Dict[int, SlotCheckpoint] = {}
        self._closed = False
        self.failed: Dict[int, HpxError] = {}
        self._admit_defers: Dict[int, int] = {}  # rid -> OOM deferrals
        self._verify_faults = 0     # consecutive verify-site faults
        self._spec_degraded = False
        # /serving{...}/faults/* feed (see fault_stats)
        self._flt_injected = 0
        self._flt_retried = 0
        self._flt_restored = 0
        self._flt_shed = 0
        self._flt_degraded = 0
        # True while a bulk shed (retry exhaustion) records ONE
        # aggregate flight bundle instead of one per shed request
        self._flight_mute = False
        self._restored_by_site: Dict[str, int] = {}
        # SLO latency distributions (svc/metrics): live log-bucketed
        # histograms, one per family, registered (with derived pNN
        # counters) as /serving{...}/latency/* — plus the per-request
        # lifecycle timeline and checkpoint-restore timings (the
        # faults/restore-p99-s feed)
        from ..svc import metrics as _metrics
        self.hist: Dict[str, _metrics.HistogramCounter] = \
            _metrics.latency_histograms()
        self._restore_hist = _metrics.HistogramCounter()
        self.timeline = _metrics.RequestTimeline()
        self._last_step_t: Optional[float] = None
        self._stall_live = False
        self._step_n = 0               # step() calls: serving.step's `n`
        # operator config writes (runtime_config().set()) to the
        # _RELOADABLE_KNOBS land at flush boundaries only — between
        # two dispatches, so a knob write cannot tear a step's host
        # preparation — via _reload_knobs, keyed on the config
        # generation counter.
        self._cfg_gen = rc.generation()
        self._knob_raw = {k: rc.get(k) for k in _RELOADABLE_KNOBS}
        # live observability (svc/exemplars, svc/slo_alerts,
        # svc/opsplane): every piece is None/empty unless its
        # hpx.obs.* knob is on, so the record and flush fast paths
        # keep their pre-observability cost (the hpx.trace.*
        # discipline). Exemplar reservoirs ride the SLO histograms;
        # the burn-rate evaluator ticks in _flush (built BEFORE
        # register_server so the /serving{...}/alerts/* counters see
        # it); the ops plane gets a weakref /statusz provider.
        from ..svc import exemplars as _exemplars
        _exemplars.attach_from_config(self.hist)
        self._alerts = None
        if rc.get_bool("hpx.obs.alerts", False):
            from ..svc.slo_alerts import server_alerts
            self._alerts = server_alerts(self)
        from ..cache.counters import register_server
        self.counter_instance = register_server(self)
        if self._alerts is not None:
            self._alerts.name = f"serving/{self.counter_instance}"
        from ..svc import opsplane as _opsplane
        if _opsplane.ensure_opsplane() is not None:
            _opsplane.register_provider(
                f"serving/{self.counter_instance}", self,
                ContinuousServer._statusz)

    def _init_paged(self, block_size, num_blocks, radix_budget_blocks,
                    prefix_reuse, paged_kernel=None,
                    kv_dtype=None) -> None:
        """Resolve the hpx.cache.* knobs and build the paged state:
        one preallocated block pool per layer (plus the [num_blocks,
        n_kv] f32 scale sidecars when ``hpx.cache.kv_dtype`` is a
        quantized dtype — ``int8`` or ``fp8``), the free-list/
        ref-count allocator over it, and the radix prefix tree."""
        from ..core.config import runtime_config
        cfg, slots, smax = self.cfg, self.slots, self.smax
        rc = runtime_config()
        self._kv_dtype = _resolve_kv_dtype(kv_dtype, rc)
        self._paged_kernel = paged_kernel = _resolve_paged_kernel(
            paged_kernel, rc)
        # the `fused=` mode threaded down to ops.paged_attention:
        # False -> gather oracle, True -> bitwise kernel, "online" ->
        # the O(block) online-softmax kernel
        self._paged_fused = {"gather": False, "fused": True,
                             "fused_online": "online"}[paged_kernel]
        if block_size is None and "sparse" in self._kinds:
            # a sparse layer's pages ARE the blocks its queries choose
            block_size, self._block_size_src = cfg.sparse_block, "model"
        elif block_size is None:
            v = rc.get("hpx.cache.block_size", "auto")
            if v in (None, "", "auto"):
                # HPX_PAGED_BLOCK, then the seed table banked by
                # `benchmarks/flash_tune.py --paged`
                # (ops/paged_blocks.json), then 16
                block_size, self._block_size_src = resolve_paged_block(
                    cfg.head_dim, self._kv_dtype)
            else:
                block_size = int(v)
                self._block_size_src = "config"
        else:
            self._block_size_src = "arg"
        bs = int(block_size)
        if bs < 1:
            raise ValueError(f"block_size must be >= 1, got {bs}")
        if "sparse" in self._kinds and bs != cfg.sparse_block:
            raise NotImplementedError(
                f"block_size {bs} on a model with sparse layers: their "
                f"queries choose blocks of {cfg.sparse_block} rows, and "
                "a page is what the walk copies (models/serving.py "
                "_init_paged, ops/sparse_attention.paged_sparse_decode)")
        if smax % bs:
            raise ValueError(
                f"paged serving needs smax divisible by the block "
                f"size {bs}; got smax {smax} (use smax="
                f"{-(-smax // bs) * bs})")
        self.block_size = bs
        self._maxb = smax // bs     # table width: blocks per sequence
        if self._eva:
            # one run a slot: its summary blocks, then its window's
            chunk, win = cfg.eva_chunk, cfg.eva_window
            if win % chunk or (win // chunk) % bs \
                    or self.prefill_buckets[-1] > win - chunk:
                raise NotImplementedError(
                    f"eva layers of window {win} pooled every {chunk} "
                    f"under block_size {bs} and prefill chunks of up to "
                    f"{self.prefill_buckets[-1]} rows: a window's "
                    "summaries must fill whole blocks (one gap-free run "
                    "of rows, cache/page_table.TwoGrainTable) and a chunk "
                    "must not wrap the scratch's ring onto the rows it "
                    "pools (ops/eva.eva_window_attend)")
            self._maxb = TwoGrainTable.max_blocks(bs, win, chunk, smax)
        if num_blocks is None:
            v = rc.get("hpx.cache.num_blocks", "auto")
            num_blocks = None if v in (None, "", "auto") else int(v)
        if num_blocks is None:
            # worst-case live demand (every slot at smax) + the trash
            # block + equal headroom for radix retention, so prefix
            # chains persist before OOM-eviction starts recycling them
            # (none on a model that refuses prefix reuse: the headroom
            # would hold nothing)
            num_blocks = (1 if self._recomputes else 2) \
                * slots * self._maxb + 1
        if num_blocks < self._maxb + 1:
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold one max-length "
                f"request ({self._maxb} blocks) plus the reserved "
                "trash block")
        if radix_budget_blocks is None:
            v = rc.get("hpx.cache.radix_budget_blocks", "auto")
            radix_budget_blocks = (None if v in (None, "", "auto")
                                   else int(v))
        if prefix_reuse is None:
            prefix_reuse = rc.get_bool("hpx.cache.prefix_reuse", True)
        self._prefix_reuse = bool(prefix_reuse)
        self._alloc = BlockAllocator(num_blocks, bs,
                                     kv_dtype=self._kv_dtype)
        # the WINDOW block group: layers that see the last `window`
        # rows keep only those. A request's map there is a ring of
        # ceil(window / bs) + 2 columns whose blocks behind the window
        # go back to this group's own allocator a block at a time as
        # decode advances (a SLIDING window; an "eva" layer's ALIGNED
        # window gives all its blocks back at once, to the full group's
        # allocator: `_eva_roll`);
        # its pools hold slots x ring blocks, not slots x smax rows,
        # plus what a slot's checkpoint pins behind its window (the
        # tokens between two captures and in flight) and a trash block.
        self._ring = self._walloc = self._wtrash = None
        self._win_freed = self._prefix_refused = 0
        self._state_resets = self._reprefills = 0
        # a two-grain table's clocks, from the host's positions alone:
        # windows completed (in decode and in prefill), the exact
        # blocks they gave back, and what the decode steps' walks read
        # over what they had behind them (`_eva_account`)
        self._eva_rolls = self._eva_freed = self._eva_pooled = 0
        self._eva_attended = self._eva_behind = 0
        # the table entries the decode steps' latent walks covered, a
        # latent layer each, and those of them ONE copy carried with
        # its group's neighbours (`_latent_entries`); a slot's running
        # count of coalesced groups, by (table uid, version, run)
        self._latent_walked = self._latent_coalesced = 0
        self._run_cum: Dict[int, Tuple[Tuple[int, int, int],
                                       np.ndarray]] = {}
        for what, on in (("a quantized hpx.cache.kv_dtype (a quantized "
                          "latent row, a sparse layer's quantized page "
                          "or a summary pooled from quantized rows has "
                          "no write or kernel)",
                          self._kv_dtype != "bf16"),
                         ("the host tier (it demotes K/V pairs of a "
                          "prefix; a two-grain table shares none)",
                          rc.get_bool("hpx.cache.tier.enable", False))):
            if self._kinds and on:
                raise NotImplementedError(
                    f"{what} on a model with {self._kinds} mixers "
                    "(models/serving.py _init_paged, ops/paged_attention"
                    ".paged_latent_attention, ops/sparse_attention.py, "
                    "ops/eva.py)")
        if self._win:
            for what, on in (("a (dp, tp) mesh", self.mesh is not None),
                             ("a quantized hpx.cache.kv_dtype",
                              self._kv_dtype != "bf16"),
                             ("the host tier", rc.get_bool(
                                 "hpx.cache.tier.enable", False))):
                if on:
                    raise NotImplementedError(
                        f"{what} on a model with window layers: the "
                        "window block group (models/serving.py "
                        "_init_paged) is single-device bf16 pools")
            self._ring = -(-self._win // bs) + 2
            lag = rc.get_int("hpx.serving.ckpt_every", 16) + rc.get_int(
                "hpx.serving.max_async_steps", 32)
            self._walloc = BlockAllocator(
                slots * (self._ring + -(-lag // bs) + 1) + 1, bs,
                kv_dtype=self._kv_dtype)
            self._wtrash = self._walloc.alloc()
        # the trash block: dead slots' tables and table padding point
        # here, so masked decode lanes scatter into rows nothing reads
        self._trash = self._alloc.alloc()
        self._radix = RadixCache(self._alloc, radix_budget_blocks)
        # host-RAM demotion tier (cache/tier.py): radix evictions
        # demote raw block rows + scale sidecars into host buffers,
        # and the two-tier match promotes them back through the
        # KVSegment framing when the crossover gate says restore
        # beats re-prefill
        self._tier = None
        self._tier_gate = None
        self._tier_rx = None
        self._tier_hist = None
        if rc.get_bool("hpx.cache.tier.enable", False):
            from ..cache.tier import HostTier, RestoreGate
            from ..cache.transfer import TransferReceiver
            from ..svc import metrics as _metrics
            budget_mb = rc.get_int("hpx.cache.tier.host_budget_mb",
                                   256)
            self._tier = HostTier(budget_mb << 20, block_size=bs)
            self._tier_gate = RestoreGate()
            self._tier_rx = TransferReceiver()
            self._tier_hist = _metrics.HistogramCounter()
            self._radix.demote_hook = self._demote_block
        nkv, hd = cfg.kv_heads, cfg.head_dim

        # sharded paged serving: pools/scales shard their kv-head axis
        # over tp and REPLICATE the block axis over dp (the allocator's
        # pool_pspec rule) — one global allocator/radix/table space,
        # every block id resolvable on every dp shard, so per-shard
        # table gathers never cross shards. Tables shard their slot
        # rows over dp (knob-controlled; see cache.page_table.
        # device_table).
        self._pool_sh = self._scale_sh = None
        self._table_residency = "sharded"
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._pool_sh = NamedSharding(
                self.mesh, P(*self._alloc.pool_pspec("tp")))
            self._scale_sh = NamedSharding(
                self.mesh, P(*self._alloc.scale_pspec("tp")))
            self._table_residency = rc.get(
                "hpx.serving.mesh.table_residency", "sharded")
            if self._table_residency not in ("sharded", "replicated"):
                raise ValueError(
                    "hpx.serving.mesh.table_residency must be "
                    "'sharded' or 'replicated', got "
                    f"{self._table_residency!r}")

        def pzeros():
            # allocate directly in the sharded layout: a full pool on
            # one device followed by a redistribute would peak at the
            # unsharded size there, the very OOM sharding avoids
            dt = {"int8": jnp.int8,
                  "fp8": jnp.float8_e4m3fn}.get(self._kv_dtype,
                                                cfg.dtype)
            if self._pool_sh is not None:
                return jnp.zeros((num_blocks, nkv, bs, hd), dt,
                                 device=self._pool_sh)
            return jnp.zeros((num_blocks, nkv, bs, hd), dt)

        def wzeros():
            return jnp.zeros((self._walloc.num_blocks, nkv, bs, hd),
                             cfg.dtype)

        def entry(i):
            """Layer i's part of the cache pytree, by its mixer's kind:
            two K/V pools; ONE pool of latent rows on the full group's
            table; the per-slot recurrent state (and conv tail; no
            blocks, no positions: `_fresh_scratch` is a slot's row);
            two K/V pools, the INDEX pool of compressed keys on the
            same table (one float32 entry every `sparse_stride` rows
            and kv head) and the last step's selection; or an "eva"
            layer's two K/V pools whose blocks hold rows of EITHER
            grain (a window's exact rows, or summaries, never both in
            one block): which, the slot's TwoGrainTable says."""
            kind = cfg.mixer(i)
            if kind == "mla":
                return (jnp.zeros((num_blocks, 1, bs, cfg.mla_row),
                                  cfg.dtype),)
            if kind in RECURRENT_KINDS:
                return tuple(jnp.zeros((slots,) + a.shape[1:], a.dtype)
                             for a in _scratch_entry(cfg, smax, i))
            if kind == "sparse":
                per = bs // cfg.sparse_stride
                return (pzeros(), pzeros(),
                        jnp.zeros((num_blocks, nkv * per, hd),
                                  jnp.float32),
                        jnp.zeros((slots, nkv, cfg.sparse_spec.width),
                                  jnp.int32),
                        jnp.zeros((slots, nkv), jnp.int32))
            return (wzeros(), wzeros()) if cfg.window(i) \
                else (pzeros(), pzeros())
        self._pools = [entry(i) for i in range(cfg.n_layers)]
        # what a roll pools with: (phi, mu) of each "eva" layer
        self._eva_params = self._eva and [
            (lp["eva"]["phi"], lp["eva"]["mu"]) if "eva" in lp else None
            for lp in self.params["layers"]]
        self._state_bytes = sum(
            a.nbytes for i, e in enumerate(self._pools)
            if cfg.mixer(i) in RECURRENT_KINDS for a in e)
        # what the decode steps' sparse layers chose and walked, from
        # the positions alone (`_sparse_account`)
        self._sparse_steps = self._sparse_blocks = 0
        self._sparse_rows_walked = self._sparse_rows_live = 0
        if self._kv_dtype in ("int8", "fp8"):
            def sones():
                # scale 1.0 is quantize_blocks' zero-block convention:
                # fresh pools dequantize to exact zeros
                if self._scale_sh is not None:
                    return jnp.ones((num_blocks, nkv), jnp.float32,
                                    device=self._scale_sh)
                return jnp.ones((num_blocks, nkv), jnp.float32)
            self._scales = [(sones(), sones())
                            for _ in range(cfg.n_layers)]
        else:
            self._scales = None
        self._tables: List[Optional[PageTable]] = [None] * slots
        self._wtables: List[Optional[WindowTable]] = [None] * slots
        self._tables_sig = None     # (uid, version) per slot and group
        self._tables_arr = None     # cached device maps, one a group:
                                    # ([slots, maxb], [slots, ring]?)
        self._prefill_saved = 0
        self._prefill_computed = 0

    # -- jitted pieces (memoized on the baked constants) ----------------

    def _program(self, ck, build):
        """All program lookups go through here so the compile-cache
        hit/miss counters see every build (the /serving programs/*
        counters; the compile-count guard test reads them too).
        Builders that donate (donate_argnums) rely on callers
        rebinding the result over the donated binding — hpxlint
        HPX020 flags any other use after the donating call.
        What comes back is the program inside its `serving.dispatch`
        span and on the step's account (`_Dispatch`), under the name
        its key leads with."""
        from .transformer import _PROGRAMS
        if ck in _PROGRAMS:
            self._prog_hits += 1
        else:
            self._prog_misses += 1
        return _Dispatch(self, ck[0], _cached_program(ck, build))

    def _moe_cf(self):
        """Effective decode capacity factor from the int-percent knob
        (None for dense models, so dense bodies never see the knob)."""
        if self.cfg.n_experts <= 0:
            return None
        return self._moe_capacity_pct / 100.0

    def _dp(self):
        """(axis, size) of the data-parallel axis for the shard_map
        bodies (`_dp_rows`); None on a single device."""
        return None if self.mesh is None else ("dp",
                                               self.mesh.shape["dp"])

    def _moe_ep(self):
        """(axis, size) for expert-parallel routing inside the
        shard_map bodies; None on a single shard."""
        if self.cfg.n_experts <= 0 or self._ep_axis is None \
                or self._ep_size <= 1:
            return None
        return (self._ep_axis, self._ep_size)

    def _chunk_prog(self, width: int):
        """One bucketed prefill chunk: toks [1, width] (tail-padded
        with token 0) written into the b=1 scratch at absolute
        positions pos0..pos0+width-1. Keyed per LADDER WIDTH, not per
        prompt length — the whole point. Pad rows land past the real
        frontier; they are never attended (causal mask) and the next
        chunk or the decode steps overwrite them before their
        positions ever go live. `n`: how many of the columns are real
        (a recurrent layer's state consumes those alone). Returns the
        scratch and ONE hidden row [1, 1, d_model]: column `n - 1` as
        it enters the last layer (`_decode_window`), where the probe
        starts if the chunk was its prompt's last. Every chunk returns
        it: no chunk program carries a head or a pick."""
        cfg, smax = self.cfg, self.smax
        ck = ("cb_chunk", cfg, width, smax, self.mesh,
              _tree_key(self.params))

        def build():
            def chunk(params, caches, toks, pos0, n):
                return _decode_window(params, caches, toks, pos0, cfg,
                                      need_logits=False, valid=n)
            return jax.jit(chunk, donate_argnums=(1,))
        return self._program(ck, build)

    def _probe_prog(self):
        """Seed probe, ONE layer deep: the hidden row the prompt's last
        chunk handed back goes through the LAST layer at its own
        position (an idempotent rewrite of that layer's cache row —
        the same row to rounding) and through the head
        (`_window_tail`), and `_seed_lane` picks the seed token from
        its logits, which never leave the program: what comes back is
        the last layer's scratch entry, the three per-slot vectors with
        the slot's lane set, and the token. It reads one layer's
        weights and the head, whatever the model's depth. One program
        serves every prompt length, so no chunk program carries a head.
        Where the last layer is of a recurrent kind the row comes from
        BEHIND it (the layer consumed the token in its chunk) and the
        probe is final ln, head and pick alone."""
        cfg, smax = self.cfg, self.smax
        ck = ("cb_probe", cfg, smax, self.mesh, _tree_key(self.params))

        def build():
            lane_sh = self._lane_sh()

            def probe(params, row, kv, pos, cur, temp, keys, slot,
                      temperature, key):
                kv, lg = _window_tail(params, row, kv, pos, cfg)
                out = _seed_lane(_next_logits(lg[0], cfg), cur, temp,
                                 keys, slot, temperature, key, pos)
                if lane_sh is not None:
                    # the placement the step hands its vector back in
                    out = tuple(jax.lax.with_sharding_constraint(v, sh)
                                for v, sh in zip(out[:3], lane_sh)
                                ) + out[3:]
                return (kv,) + out
            return jax.jit(probe, donate_argnums=(2,))
        return self._program(ck, build)

    def _probe(self, caches, row, pos: int, slot: int = 0,
               temperature: float = 0.0, key=None):
        """Dispatch the probe on `row`, what the chunk that wrote row
        `pos` of the b=1 scratch handed back: (scratch, feedback
        tokens, temperatures, keys, seed token) with `slot`'s lanes set
        to the pick and the request's temperature and key. The last
        layer's entry of the scratch alone is an operand (donated);
        the others stay where they lie. Every operand is host NumPy or
        already on the device: no eager program beside the named one."""
        kv, *out = self._probe_prog()(
            self._tail_params, row, caches[-1], np.int32(pos),
            self._feedback(), *self._lanes(), np.int32(slot),
            np.float32(temperature),
            self._no_key if key is None else key)
        return [*caches[:-1], kv], *out

    def _lane_sh(self):
        """Placement of the per-slot vectors (1-d, and the keys' 2-d)
        under the mesh: slots over dp, as the step's shard_map
        takes and returns them. None on a single device."""
        if self.mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec as P
        one = NamedSharding(self.mesh, P("dp"))
        return one, one, NamedSharding(self.mesh, P("dp", None))

    def _feedback(self):
        """The feedback tokens on the device, one lane a slot: the
        newest step's output with the lanes admissions set since, or,
        where that mirror is stale (None: nothing dispatched yet, a
        recovery, a speculative commit, `admit_prefilled`), the host's
        `_cur` by ONE transfer and no program."""
        if self._cur_dev is None:
            self._cur_dev = jax.device_put(
                np.asarray(self._cur, np.int32),
                self.mesh and self._lane_sh()[0])
        return self._cur_dev

    def _lanes(self):
        """(temperatures, keys) on the device, one lane a slot, kept
        like `_feedback()`'s vector: an admission's probe sets its
        lane, a stale mirror is the host's array by one transfer."""
        if self._temp_dev is None:
            sh = self._lane_sh() or (None,) * 3
            # copies: the host's arrays change in place at admissions
            self._temp_dev = jax.device_put(self._temp.copy(), sh[1])
            self._keys_dev = jax.device_put(self._key.copy(), sh[2])
        return self._temp_dev, self._keys_dev

    # -- cache programs (rows live in pools; tables map positions) --------

    def _paged_step_prog(self):
        cfg, slots, smax = self.cfg, self.slots, self.smax
        nb, bs = self._alloc.num_blocks, self.block_size
        ck = ("pg_step", cfg, slots, smax, nb, bs, self._kv_dtype,
              self._paged_kernel, self._moe_capacity_pct, self.mesh,
              self._walloc and self._walloc.num_blocks,
              _tree_key(self.params))

        def build():
            fused = self._paged_fused
            tp_axis = None if self.mesh is None else "tp"
            dp = self._dp()
            moe_cf = self._moe_cf()
            moe_ep = self._moe_ep()

            def step(params, pools, scales, tok, pos, tables, temp,
                     keys):
                pools, scales, logits, ms = _paged_decode_rows(
                    params, pools, scales, tok, tables, pos, cfg,
                    fused, tp_axis, moe_cf, moe_ep, dp)
                nxt = jax.vmap(_pick_row)(_next_logits(logits, cfg), keys,
                                          temp, pos)
                if ms is not None and tp_axis is not None:
                    # fold the per-dp-group stats into one replicated
                    # vector: routed/dropped claims sum over groups,
                    # occupancy fractions average
                    ms = jnp.concatenate(
                        [jax.lax.psum(ms[:2], "dp"),
                         jax.lax.pmean(ms[2:], "dp")])
                return pools, scales, nxt, ms
            if self.mesh is None:
                return self._jit_step(step)
            # sharded decode runs under shard_map, NOT bare GSPMD: each
            # dp shard steps ITS slots against its LOCAL
            # pool replica (block tables are per-shard int32 into a
            # dp-replicated block axis — the gather can never cross
            # shards; every shard writes every slot's new rows, so the
            # replicas stay equal and the replication check, left ON,
            # can prove the pool spec), tp shards the kv-head axis with
            # explicit psums in _paged_block_rows, and MoE layers route
            # tokens over the expert axis via moe_ffn_decode's tiled
            # all_to_all. Per-slot sampling (keys fold per slot, row 0)
            # is shard-local, so emitted tokens match the single-device
            # server exactly.
            from jax.sharding import PartitionSpec as P
            pspecs, pool_sp, scale_sp = self._paged_shard_specs()
            return self._jit_step(jax.shard_map(
                step, mesh=self.mesh,
                in_specs=(pspecs, pool_sp, scale_sp, P("dp"),
                          P("dp"), (P("dp", None),), P("dp"),
                          P("dp", None)),
                out_specs=(pool_sp, scale_sp, P("dp"), P())))
        return self._program(ck, build)

    def _jit_step(self, step):
        # scales donate too: for bf16 pools the arg is None (an empty
        # pytree), which donation treats as a no-op
        return jax.jit(step, donate_argnums=(1, 2))

    def _paged_shard_specs(self):
        """Spec trees for the shard_map-wrapped programs:
        (param pspecs, pool spec, scale spec). Pools replicate the
        block axis over dp and shard kv-heads over tp (the allocator's
        pool_pspec rule); the scale spec degrades to P() for bf16
        pools, where the scales argument is an empty pytree."""
        from jax.sharding import PartitionSpec as P
        from .transformer import _decode_pspecs
        pool_sp = P(*self._alloc.pool_pspec("tp"))
        scale_sp = (P(*self._alloc.scale_pspec("tp"))
                    if self._scales is not None else P())
        return (_decode_pspecs(self.params, self.cfg, self.mesh),
                pool_sp, scale_sp)

    def _paged_gather_prog(self):
        """Materialize one request's (possibly prefix-matched) blocks
        into a contiguous b=1 scratch cache the shared chunk/probe
        programs run over — int8 pools dequantize here, so the scratch
        (and every chunk program over it) stays in the compute dtype.
        Rows at/past `valid` (the matched prefix length) zero out:
        they gather from not-yet-written blocks and table padding, and
        stale quantized garbage can dequantize to values large enough
        to defeat additive attention masking (an fp8 byte times a
        stale f32 scale is unbounded) — zeroing makes the scratch a
        pure function of the matched content instead of allocation
        history. Keyed once per server shape."""
        cfg = self.cfg
        nb, bs = self._alloc.num_blocks, self.block_size
        ck = ("pg_gather", cfg, self.smax, nb, bs, self._kv_dtype,
              self.mesh, _tree_key(self.params))

        def build():
            dt = cfg.dtype
            rows = self._maxb * bs

            def gather(pools, scales, trow, valid):
                keep = (jnp.arange(rows) < valid)[None, :, None, None]
                if scales is None:
                    return [tuple(jnp.where(
                        keep, gather_block_kv(p, trow[None]), 0)
                        for p in pl) for pl in pools]
                return [(jnp.where(keep,
                                   gather_block_kv(kp, trow[None], ks,
                                                   dt), 0),
                         jnp.where(keep,
                                   gather_block_kv(vp, trow[None], vs,
                                                   dt), 0))
                        for (kp, vp), (ks, vs) in zip(pools, scales)]
            return jax.jit(gather)
        return self._program(ck, build)

    def _paged_splice_prog(self):
        """Write the request's padded block row back from the b=1
        scratch (chunked-prefill splice). One program for every
        (matched, plen) combination: the WRITE row (`_start_prefill`'s
        `wrow`) redirects radix-matched prefix entries to the trash
        block, so shared prefix blocks are never rewritten — for bf16
        the skipped write was an identity copy of the bytes the gather
        read; for int8 it would be a dequant(bf16)->requant of a
        SHARED block (a ±1-quantum walk other readers would see), so
        skipping it is what keeps prefix reuse exact. The trash-padded
        tail (and the redirected prefix) is garbage-on-garbage (see
        scatter_seq_blocks); int8 splices quantize whole blocks here
        (scatter_seq_blocks_q). `slot`: where a recurrent layer's
        scratch state lands (it has no blocks). A sparse layer's index
        pool takes the means of the scratch's K rows by the same row.
        An "eva" layer's scratch keeps its two grains apart and `wrows`
        is (summary blocks, window blocks) of the slot's one table
        (`TwoGrainTable.write_rows`): the summaries of the complete
        windows and the ring's rows, each by its own row."""
        cfg = self.cfg
        nb, bs = self._alloc.num_blocks, self.block_size
        maxb = self._maxb
        ck = ("pg_splice", cfg, self.smax, nb, bs, self._kv_dtype,
              self.mesh, self._walloc and self._walloc.num_blocks,
              _tree_key(self.params))

        def build():
            pool_sh, scale_sh = self._pool_sh, self._scale_sh

            def splice(pools, scales, one, wrows, slot):
                outp, outs = [], []
                for i, (pl, sc) in enumerate(zip(pools, one)):
                    wrow = wrows[1 if cfg.window(i) else 0]
                    if cfg.mixer(i) == "eva":
                        (kp, vp), (ke, ve, ks, vs) = pl, sc
                        for row, (k1, v1) in zip(
                                wrows, ((ks, vs), (ke, ve))):
                            kp, vp = (scatter_seq_blocks(
                                p, row, c[0].reshape(-1, bs, *c.shape[2:]))
                                for p, c in ((kp, k1), (vp, v1)))
                        outp.append((kp, vp))
                    elif cfg.mixer(i) in RECURRENT_KINDS:
                        # the slot's row of the state (and the tail),
                        # whole: nothing of the last occupant survives
                        outp.append(tuple(
                            jax.lax.dynamic_update_index_in_dim(
                                p, c[0], slot, 0)
                            for p, c in zip(pl, sc)))
                    elif cfg.mixer(i) == "sparse":
                        # K and V as any pair; the index entries of the
                        # same blocks, from the scratch's K rows, by
                        # the same write row
                        from ..ops.sparse_attention import index_blocks
                        kp, vp = (
                            scatter_seq_blocks(p, wrow, c[0].reshape(
                                maxb, bs, *c.shape[2:]))
                            for p, c in zip(pl[:2], sc))
                        outp.append((kp, vp, pl[2].at[wrow].set(
                            index_blocks(sc[0][0], cfg.sparse_spec, bs)))
                            + tuple(pl[3:]))
                    elif scales is None:
                        outp.append(tuple(
                            scatter_seq_blocks(p, wrow, c[0].reshape(
                                maxb, bs, *c.shape[2:]))
                            for p, c in zip(pl, sc)))
                    else:
                        (kp, vp), (kc, vc) = pl, sc
                        kseg = kc[0].reshape(maxb, bs, *kc.shape[2:])
                        vseg = vc[0].reshape(maxb, bs, *vc.shape[2:])
                        ks, vs = scales[i]
                        kp, ks = scatter_seq_blocks_q(kp, ks, wrow,
                                                      kseg)
                        vp, vs = scatter_seq_blocks_q(vp, vs, wrow,
                                                      vseg)
                        outp.append((kp, vp))
                        outs.append((ks, vs))
                if pool_sh is not None:
                    # pin the sharded-pool layout: the scatter stays a
                    # per-device local write (block axis replicated
                    # over dp, kv-heads over tp) and donation reuses
                    # the input buffers in place — whole-block splice
                    # writes are therefore IDENTICAL on every dp
                    # replica, the coherence property radix prefix
                    # sharing on the mesh rests on
                    outp = jax.lax.with_sharding_constraint(
                        outp, pool_sh)
                    if outs:
                        outs = jax.lax.with_sharding_constraint(
                            outs, scale_sh)
                return outp, (None if scales is None else outs)
            return jax.jit(splice, donate_argnums=(0, 1))
        return self._program(ck, build)

    def _copy_block_prog(self):
        """Device side of allocator copy-on-write: duplicate one
        block's rows src->dst across every layer's pools (int8 pools
        copy the block's scale sidecar entries too — a forked block
        must dequantize identically to its source)."""
        nb, bs = self._alloc.num_blocks, self.block_size
        ck = ("pg_copy", self.cfg, self.smax, nb, bs, self._kv_dtype,
              self.mesh, _tree_key(self.params))

        def build():
            pool_sh, scale_sh = self._pool_sh, self._scale_sh

            def copy(pools, scales, src, dst):
                # full-group ids: a window layer's pools are another
                # group's (nothing shares its blocks, none is forked)
                # (a recurrent layer's state has no blocks at all)
                pools = [pl if self.cfg.window(i)
                         or self.cfg.mixer(i) in RECURRENT_KINDS
                         else tuple(p.at[dst].set(p[src])
                                    for p in pl[:3]) + tuple(pl[3:])
                         for i, pl in enumerate(pools)]
                if scales is not None:
                    scales = [(ks.at[dst].set(ks[src]),
                               vs.at[dst].set(vs[src]))
                              for ks, vs in scales]
                if pool_sh is not None:
                    # per-replica local copy: src's rows on each dp
                    # replica land in that replica's dst — exactly the
                    # COW semantics each owning shard needs
                    pools = jax.lax.with_sharding_constraint(
                        pools, pool_sh)
                    if scales is not None:
                        scales = jax.lax.with_sharding_constraint(
                            scales, scale_sh)
                return pools, scales
            return jax.jit(copy, donate_argnums=(0, 1))
        return self._program(ck, build)

    def _tier_restore_prog(self):
        """Host-tier promotion splice: write ONE restored block's RAW
        pool-dtype rows (and the f32 scale sidecars on quantized
        pools) at its promoted block id. Dequantize-free by
        construction — the bytes written are the bytes demoted, so a
        promoted block dequantizes bit-identically to the block the
        radix tree evicted (the sha-identity the crossover tests pin).
        One block per dispatch keeps the program shape fixed — a
        promotion chain costs N dispatches, never N compiles."""
        nb, bs = self._alloc.num_blocks, self.block_size
        ck = ("pg_tier_restore", self.cfg, self.smax, nb, bs,
              self._kv_dtype, self.mesh, _tree_key(self.params))

        def build():
            pool_sh, scale_sh = self._pool_sh, self._scale_sh

            def restore(pools, scales, bid, rows, scs):
                rows = block_rows(rows)     # token rows -> pool blocks
                pools = [(kp.at[bid].set(rows[li, 0].astype(kp.dtype)),
                          vp.at[bid].set(rows[li, 1].astype(vp.dtype)))
                         for li, (kp, vp) in enumerate(pools)]
                if scales is not None:
                    scales = [(ks.at[bid].set(scs[li, 0]),
                               vs.at[bid].set(scs[li, 1]))
                              for li, (ks, vs) in enumerate(scales)]
                if pool_sh is not None:
                    # dp-replicated block axis: the restored rows land
                    # on every dp replica, same as a colocated write
                    pools = jax.lax.with_sharding_constraint(
                        pools, pool_sh)
                    if scales is not None:
                        scales = jax.lax.with_sharding_constraint(
                            scales, scale_sh)
                return pools, scales
            return jax.jit(restore, donate_argnums=(0, 1))
        return self._program(ck, build)

    # -- speculative programs (verify windows + draft model) -------------

    def _paged_verify_prog(self, width: int):
        cfg, slots, smax = self.cfg, self.slots, self.smax
        nb, bs = self._alloc.num_blocks, self.block_size
        ck = ("pg_verify", cfg, slots, smax, width, nb, bs,
              self._kv_dtype, self._paged_kernel,
              self._moe_capacity_pct, self.mesh,
              _tree_key(self.params))

        def build():
            fused = self._paged_fused
            tp_axis = None if self.mesh is None else "tp"
            dp = self._dp()
            moe_cf = self._moe_cf()
            moe_ep = self._moe_ep()

            def verify(params, pools, scales, toks, pos0, tables,
                       kvec, temp, keys):
                pools, scales, logits, ms = _paged_decode_window_rows(
                    params, pools, scales, toks, tables, pos0, cfg,
                    fused, tp_axis, moe_cf, moe_ep, dp)
                if ms is not None and tp_axis is not None:
                    ms = jnp.concatenate(
                        [jax.lax.psum(ms[:2], "dp"),
                         jax.lax.pmean(ms[2:], "dp")])
                return pools, scales, _verify_tail(
                    logits, toks, kvec, temp, keys, pos0, width), ms
            if self.mesh is None:
                return jax.jit(verify, donate_argnums=(1, 2))
            # same shard_map layout as _paged_step_prog, stretched to
            # the verify window: toks/packed targets carry a width
            # column axis, everything else is the step's specs. The
            # _verify_tail pick is per-slot (shard-local) so spec
            # acceptance matches the single-device server exactly.
            from jax.sharding import PartitionSpec as P
            pspecs, pool_sp, scale_sp = self._paged_shard_specs()
            return jax.jit(jax.shard_map(
                verify, mesh=self.mesh,
                in_specs=(pspecs, pool_sp, scale_sp, P("dp", None),
                          P("dp"), (P("dp", None),), P("dp"), P("dp"),
                          P("dp", None)),
                out_specs=(pool_sp, scale_sp, P("dp", None), P())),
                donate_argnums=(1, 2))
        return self._program(ck, build)

    def _draft_step_prog(self):
        """One greedy draft-model step at per-slot positions. The
        draft ALWAYS proposes greedily — draft quality moves only the
        acceptance rate, never the emitted tokens."""
        dcfg, slots, smax = self._draft_cfg, self.slots, self.smax
        ck = ("cb_draft", dcfg, slots, smax, self.mesh,
              _tree_key(self._draft_params))

        def build():
            def step(params, caches, tok, pos):
                caches, logits = _decode_rows(params, caches, tok, pos,
                                              dcfg)
                return caches, jnp.argmax(logits, axis=-1) \
                                  .astype(jnp.int32)
            return jax.jit(step, donate_argnums=(1,))
        return self._program(ck, build)

    def _draft_chunk_prog(self, width: int):
        """One bucketed prefill chunk for ONE slot of the draft-model
        cache: slice the slot's b=1 rows, run the shared window
        forward, write them back. Same ladder widths as the target's
        chunks — O(buckets) draft programs."""
        dcfg, smax = self._draft_cfg, self.smax
        ck = ("cb_dchunk", dcfg, width, smax, self.slots, self.mesh,
              _tree_key(self._draft_params))

        def build():
            def chunk(params, caches, toks, pos0, slot):
                one = [(jax.lax.dynamic_slice_in_dim(kc, slot, 1, 0),
                        jax.lax.dynamic_slice_in_dim(vc, slot, 1, 0))
                       for kc, vc in caches]
                one, _ = _decode_window(params, one, toks, pos0, dcfg,
                                        need_logits=False)
                out = []
                for (kc, vc), (k1, v1) in zip(caches, one):
                    kc = jax.lax.dynamic_update_slice(
                        kc, k1.astype(kc.dtype), (slot, 0, 0, 0))
                    vc = jax.lax.dynamic_update_slice(
                        vc, v1.astype(vc.dtype), (slot, 0, 0, 0))
                    out.append((kc, vc))
                return out
            return jax.jit(chunk, donate_argnums=(1,))
        return self._program(ck, build)

    # -- host-side block bookkeeping -------------------------------------

    def _alloc_block(self) -> int:
        """allocator.alloc with the OOM→evict→retry discipline: a full
        pool first evicts the least-recently-used idle radix chain
        (retained prefixes are a cache, not a reservation). Injected
        OOM faults (`svc/faultinject`, site "alloc") walk the SAME
        ladder — counted, evicted against, retried — and escalate (to
        the step-level restore path or the admission defer/shed
        ladder) only when eviction has nothing left to give."""
        try:
            return self._alloc.alloc()
        except CacheOOM as e:
            injected = isinstance(e, faultinject.InjectedFault)
            if injected:
                self._flt_injected += 1
            if not sum(self._radix.evict(1)):
                raise
            if injected:
                self._flt_retried += 1
            return self._alloc.alloc()

    def _cow_guard(self, pt: PageTable, bi: int) -> None:
        """Make the block backing logical block `bi` exclusively ours
        before writing into it (copy-on-write fork + device copy)."""
        bid = pt.blocks[bi]
        if self._alloc.refcount(bid) > 1:
            new, copied = self._alloc.fork(bid)
            if copied:
                self._pools, self._scales = self._copy_block_prog()(
                    self._pools, self._scales, np.int32(bid),
                    np.int32(new))
                pt.replace_block(bi, new)

    def _ensure_block(self, slot: int, pos: int) -> None:
        """Before a decode write at `pos`: extend the slot's table to
        cover it, and make the target block exclusively ours (COW
        guard — unreachable under the publish-at-retire policy, since
        writes always land past the shared prefix, but correctness
        must not depend on the policy staying that way)."""
        pt = self._tables[slot]
        assert pt is not None
        if self._eva:
            # the write's row in the slot's one run; the step that
            # completes a window also holds the blocks its summaries
            # take (`_eva_roll`, once that step is dispatched). Nothing
            # is shared, so nothing to fork.
            while len(pt.blocks) < pt.blocks_at(pos):
                pt.append_block(self._alloc_block())
            return
        while pt.capacity <= pos:
            pt.append_block(self._alloc_block())
        self._cow_guard(pt, pos // self.block_size)
        wt = self._wtables[slot]
        if wt is not None:
            while wt.capacity <= pos:
                wt.append_block(self._walloc.alloc())
            # one step behind the write: the step in flight when a
            # checkpoint captures the host's frontier (pos - 1) must
            # not have given that frontier's oldest block away
            freed = wt.free_behind(pos - 1)
            if freed:
                with tracing.span("serving.window_free", "serving",
                                  rid=self._slot_req[slot].rid,
                                  blocks=len(freed)):
                    self._win_freed += len(freed)
                    for bid in freed:
                        self._walloc.decref(bid)

    def _eva_roll_prog(self):
        """A window's roll on the device: the exact rows the window
        left in one slot's blocks are pooled into its summaries, a
        layer at a time (`ops/eva.eva_roll_blocks`), which fill the
        blocks held for them. The decode path's pooling: ONE program,
        `jit_roll`, about every `eva_window` / live slots steps."""
        cfg = self.cfg
        ck = ("pg_roll", cfg, self._alloc.num_blocks, self.block_size,
              _tree_key(self.params))

        def build():
            from ..ops.eva import eva_roll_blocks

            def roll(pools, pv, exact, fresh):
                return [pl if m is None else eva_roll_blocks(
                    *pl, exact, fresh, *m, cfg.eva_chunk)
                    for pl, m in zip(pools, pv)]
            return jax.jit(roll, donate_argnums=(0,))
        return self._program(ck, build)

    def _eva_roll(self, slot: int) -> None:
        """The step just dispatched wrote the last row of `slot`'s
        window: pool the window into its summaries (enqueued behind
        that step), publish them at the head of the slot's run and
        give ALL the window's blocks back at once. Every block was
        held before the step went out (`_ensure_block`): nothing here
        can run out."""
        pt, req = self._tables[slot], self._slot_req[slot]
        win = self.cfg.eva_window
        with tracing.span("serving.window_roll", "serving", rid=req.rid,
                          slot=slot, window=self._pos[slot] // win - 1,
                          blocks=win // self.block_size,
                          rows=win // self.cfg.eva_chunk):
            exact = pt.blocks[pt.summary:-pt.per]
            self._pools = self._eva_roll_prog()(
                self._pools, self._eva_params,
                np.asarray(exact, np.int32),
                np.asarray(pt.blocks[-pt.per:], np.int32))
            for bid in pt.roll():
                self._alloc.decref(bid)
            self._eva_rolls += 1
            self._eva_freed += len(exact)
            self._eva_pooled += win // self.cfg.eva_chunk

    def _eva_account(self, pos: np.ndarray) -> None:
        """What one decode step's two-grain walks read, a layer, from
        the live slots' positions ALONE: a query at p attends the
        summaries of the windows behind its own and its window's rows
        up to itself, where plain attention reads p + 1 rows."""
        self._eva_attended += int((self._walk_row(pos) + 1).sum())
        self._eva_behind += int((pos + 1).sum())

    def _ensure_window(self, slot: int, pos0: int, last: int) -> None:
        """`_ensure_block` generalized to a speculative verify window:
        cover every write position in [pos0, last] and COW-guard each
        covered block — draft rows must never land in a radix-shared
        block. Window pad columns past `last` need no coverage: the
        table row pads with the trash block, so their scatters land in
        rows nothing ever reads."""
        last = min(last, self.smax - 1)
        pt = self._tables[slot]
        assert pt is not None
        while pt.capacity <= last:
            pt.append_block(self._alloc_block())
        for bi in range(pos0 // self.block_size,
                        last // self.block_size + 1):
            self._cow_guard(pt, bi)

    def _tables_dev(self):
        """The [slots, maxb] int32 device map for one decode step,
        rebuilt ONLY when some table mutated (PageTable.version) or a
        slot's table was swapped — steady-state decode re-uploads
        nothing. On a mesh the rows land per `hpx.serving.mesh.
        table_residency` (slot rows over dp by default) via
        cache.page_table.device_table; ids stay GLOBAL either way."""
        sig = tuple((pt.uid, pt.version) if pt is not None else None
                    for pt in self._tables + self._wtables)
        if sig != self._tables_sig or self._tables_arr is None:
            from ..cache.page_table import device_table
            self._tables_arr = (device_table(
                self._tables, self._maxb, self._trash, mesh=self.mesh,
                residency=self._table_residency),)
            if self._win:
                self._tables_arr += (jnp.asarray(materialize(
                    self._wtables, self._ring, self._wtrash)),)
            self._tables_sig = sig
        return self._tables_arr

    def _release_slot(self, slot: int, req: "_Request") -> None:
        """Retire: publish the request's FULL prompt blocks into
        the radix tree (prefix reuse for future admits), then drop the
        request's references — shared blocks survive under the tree's
        ref, private ones return to the free list."""
        pt = self._tables[slot]
        if pt is None:
            return
        self._free_window(self._wtables[slot])
        self._wtables[slot] = None
        if self._prefix_reuse and not (self._win or self._recomputes):
            nfull = len(req.prompt) // self.block_size
            if nfull:
                self._radix.insert(
                    req.prompt[:nfull * self.block_size],
                    pt.blocks[:nfull])
        for bid in pt.blocks:
            self._alloc.decref(bid)
        self._tables[slot] = None

    def _free_window(self, wt: Optional[WindowTable]) -> None:
        """Drop a request's references in the window group."""
        if wt is not None:
            for bid in wt.blocks:
                self._walloc.decref(bid)
            wt.blocks = []

    # -- host tier (cache/tier.py): demotion + gated promotion -----------

    def _demote_block(self, chain: int, parent: int, key, bid: int):
        """RadixCache demote hook: copy one evicted block's RAW pool
        rows (quantized bytes on int8/fp8 pools, plus the f32 scale
        sidecars) to the host tier. Runs under the radix lock BEFORE
        the tree reference drops, so the rows are stable; published
        blocks are immutable (COW + trash-redirected splices), so the
        snapshot is the block's final bytes. Returns the tier's
        verdict — False (budget refuses) counts the eviction as
        dropped, exactly the pre-tier behavior."""
        tier = self._tier
        if tier is None:
            return False
        layers = []
        scl = [] if self._scales is not None else None
        for li, (kp, vp) in enumerate(self._pools):
            layers.append(np.stack((np.asarray(block_rows(kp[bid])),
                                    np.asarray(block_rows(vp[bid])))))
            if scl is not None:
                ks, vs = self._scales[li]
                scl.append(np.stack((np.asarray(ks[bid]),
                                     np.asarray(vs[bid]))))
        rows = np.stack(layers)             # [L, 2, bs, n_kv, hd]
        scs = (np.stack(scl).astype(np.float32)
               if scl is not None else None)    # [L, 2, n_kv]
        return tier.demote(chain, parent, key, rows, scs)

    def _promote_tier(self, req: "_Request", matched: int,
                      mbids: List[int], ext) -> int:
        """Crossover-gated promotion of a host-tier hit: when restore
        beats re-prefill (RestoreGate), re-ship the tier entries'
        raw rows through the KVSegment framing (checksums, idempotent
        seq numbers — the disagg delivery discipline, exercised
        in-process), splice them dequantize-free at freshly allocated
        block ids, and republish the chain in the radix tree. Appends
        the promoted ids to `mbids` and returns the extra whole-block
        tokens restored (0 = gate declined or nothing could be held —
        the caller re-prefills, entries stay in the tier)."""
        from ..cache.transfer import make_segment
        bs = self.block_size
        promote, _est = self._tier_gate.should_promote(
            len(ext) * bs, sum(nb for _, _, nb in ext))
        if not promote:
            self._tier.declined(len(ext))
            return 0
        t0 = time.perf_counter()
        bids: List[int] = []
        try:
            for _ in ext:
                bids.append(self._alloc_block())
        except CacheOOM:
            pass        # a partial chain prefix is still a win
        if not bids:
            self._tier.declined(len(ext))
            return 0
        entries = []
        for h, _chunk, _nb in ext[:len(bids)]:
            e = self._tier.checkout(h)
            if e is None:
                break   # raced out by a concurrent demotion wave
            entries.append(e)
        n = len(entries)
        for bid in bids[n:]:
            self._alloc.decref(bid)
        bids = bids[:n]
        if not n:
            return 0
        rid = f"tier:{req.rid}:{self._pf_seq}"
        try:
            for i, e in enumerate(entries):
                self._tier_rx.ingest(make_segment(
                    rid, i, i * bs, n * bs, e.rows))
                if e.scales is not None:
                    self._tier_rx.ingest(make_segment(
                        "scale/" + rid, i, i, n,
                        e.scales[:, :, None, :]))
            rows = self._tier_rx.assemble(rid)
            scs = (self._tier_rx.assemble("scale/" + rid)
                   if entries[0].scales is not None else None)
        except HpxError:
            # corrupt/incomplete frame: keep the data (putback), free
            # the blocks, fall back to re-prefill — never a leak
            self._tier_rx.abort(rid)
            self._tier_rx.abort("scale/" + rid)
            for e in entries:
                self._tier.putback(e)
            for bid in bids:
                self._alloc.decref(bid)
            return 0
        for i, bid in enumerate(bids):
            blk = rows[:, :, i * bs:(i + 1) * bs]
            sblk = None if scs is None else scs[:, :, i]
            self._pools, self._scales = self._tier_restore_prog()(
                self._pools, self._scales, np.int32(bid), blk, sblk)
        # republish: the tree takes its reference on the promoted
        # blocks (refcount 2 = tree + our lease, same as a hot match)
        self._radix.insert(req.prompt[:matched + n * bs],
                           list(mbids) + bids)
        mbids.extend(bids)
        for e in entries:
            self._tier.checkin(e)
        if self._tier_hist is not None:
            jax.block_until_ready(self._pools)
            self._tier_hist.record(time.perf_counter() - t0)
        return n * bs

    def cache_stats(self) -> Dict[str, float]:
        """Cache observability snapshot (the same numbers the
        /cache{...} performance counters export)."""
        st: Dict[str, float] = dict(self._alloc.stats())
        st.update(self._radix.stats())
        if self._tier is not None:
            st.update(self._tier.stats())
        # prompt tokens the tree served / the chunks computed; blocks
        # more than one holder shares are the allocator's `shared`
        st["prefill_tokens_saved"] = self._prefill_saved
        st["prefill_tokens_computed"] = self._prefill_computed
        if self._win:
            # the window block group, beside the full group's fields
            st["window_num_blocks"] = self._walloc.num_blocks
            st["window_in_use"] = self._walloc.in_use
            st["window_blocks_freed"] = self._win_freed
            st["window_prefix_refused"] = self._prefix_refused
        if self._recurrent:
            # the second kind of cached state: per-slot, no blocks
            st["state_bytes"] = self._state_bytes
            st["state_slots_live"] = sum(
                1 for r in self._slot_req if r is not None) \
                + len(self._pending)
            st["state_resets"] = self._state_resets
            st["state_prefix_refused"] = self._prefix_refused
            st["state_reprefills"] = self._reprefills
        if "sparse" in self._kinds:
            # the fourth kind of cached entry: the index of compressed
            # keys, an entry every `sparse_stride` rows of a held block
            st["index_rows"] = self._alloc.in_use * (
                self.block_size // self.cfg.sparse_stride)
            st["sparse_prefix_refused"] = self._prefix_refused
            st["sparse_steps"] = self._sparse_steps
            st["sparse_blocks_selected"] = self._sparse_blocks
            st["sparse_rows_walked"] = self._sparse_rows_walked
            st["sparse_rows_live"] = self._sparse_rows_live
        if self._eva:
            # the fifth kind of cached entry: rows at two grains in
            # one run a slot; the live slots' rows, from their
            # positions and tables alone
            live = [s_ for s_ in range(self.slots)
                    if self._slot_req[s_] is not None]
            st["eva_summary_rows"] = self.block_size * sum(
                self._tables[s_].summary for s_ in live)
            st["eva_exact_rows"] = sum(
                self._pos[s_] % self.cfg.eva_window for s_ in live)
            st["eva_rolls"] = self._eva_rolls
            # summaries written, in prefill (a chunk as it fills) and
            # in decode (a window's at its roll), from positions alone
            st["eva_chunks_pooled"] = self._eva_pooled
            st["eva_blocks_freed"] = self._eva_freed
            st["eva_rows_attended"] = self._eva_attended
            st["eva_tokens_behind"] = self._eva_behind
            st["eva_prefix_refused"] = self._prefix_refused
            st["eva_reprefills"] = self._reprefills
        if "mla" in self._kinds:
            st["latent_blocks_in_use"] = self._alloc.in_use
            # what a step's latent walks read: live rows of the live
            # slots, a latent layer each (the kernel stops there)
            st["latent_rows_walked_per_step"] = sum(
                self._pos[s_] + 1 for s_ in range(self.slots)
                if self._slot_req[s_] is not None)
            # the table entries the decode steps' latent walks have
            # covered so far, a latent layer each, and those of them
            # that lay in a coalesced copy (`latent_run_pct` of a span
            # of steps: the growth of the second over the first's)
            st["latent_entries_walked"] = self._latent_walked
            st["latent_entries_coalesced"] = self._latent_coalesced
        st.update(self.hbm_read_stats())
        st.update(self.prefill_stats())
        if self.mesh is not None:
            # per-dp-shard slot accounting: slots map to dp shards by
            # index range (the P("dp") slot-axis sharding), so shard
            # d's decode reads exactly these slots' mapped blocks —
            # the skew between shards is the load-balance signal
            dp = self.mesh.shape["dp"]
            per = self.slots // dp
            for d in range(dp):
                st[f"occupancy_dp{d}"] = occupancy(
                    self._tables[d * per:(d + 1) * per])
        return st

    def _kv_acct_dtype(self) -> str:
        """block_bytes key for the POOLS AS ALLOCATED: kv_dtype=bf16
        stores the model compute dtype, which tier-1's CPU configs set
        to f32 — account what is actually resident, not the label.
        int8 and fp8 pools store 1 byte/elem regardless of the compute
        dtype, so their labels pass through."""
        if self._kv_dtype in ("int8", "fp8"):
            return self._kv_dtype
        return ("f32" if jnp.dtype(self.cfg.dtype).itemsize == 4
                else "bf16")

    def _walk_group(self) -> Tuple[int, int]:
        """(`hg`, n_kv) of the full group's decode call: the kv heads
        one grid step of the bounded walk owns, and one copy of a table
        entry carries (`attention_pallas.walk_heads_per_copy` of the
        call's shapes, W = 1), of the call's kv heads (the shard's
        under a mesh). `hg` is 0 where this server's calls keep the
        grid walk (another kernel, quantized pools, a head that is not
        whole 128-lane rows) or no layer attends over the full group's
        K/V pools."""
        cfg = self.cfg
        nkv = cfg.kv_heads // (
            self.mesh.shape["tp"] if self.mesh is not None else 1)
        full = [i for i in range(cfg.n_layers)
                if cfg.mixer(i) in ("attn", "eva") and not cfg.window(i)]
        if (self._paged_kernel != "fused" or self._kv_dtype != "bf16"
                or cfg.head_dim % 128 or not full):
            return 0, nkv
        item = jnp.dtype(cfg.dtype).itemsize
        return walk_heads_per_copy(
            nkv, self._maxb * self.block_size, cfg.head_dim,
            cfg.heads(full[0]) // cfg.kv_heads, item, item), nkv

    def _latent_entries(self, positions: Dict[int, int]
                        ) -> Tuple[int, int]:
        """(walked, coalesced): the table entries one latent layer's
        walk of a decode step at `positions` ({slot: position}) covers,
        and those of them `hpx_mla_paged` copies with their aligned
        group's neighbours in ONE descriptor (`attention_pallas.
        latent_groups_coalesced`, the kernel's own rule, over the
        slots' host tables: a slot's groups are read again only when
        its table has mutated). (0, 0) where no latent layer takes the
        kernel (none, the gather form, a rank that is no whole lanes);
        coalesced 0 where a fold is no whole number of groups. A slot's
        FIRST and LAST folds are single copies whatever its table
        holds."""
        if "mla" not in self._kinds or not latent_takes_kernel(
                self._paged_fused, self.cfg.mla_rank):
            return 0, 0
        fold, run = latent_walk_sizes(self._maxb)
        walked = coalesced = 0
        for s, p in positions.items():
            pt = self._tables[s]
            n = min(p // self.block_size + 1, self._maxb)
            walked += n
            key = (pt.uid, pt.version, run)
            hit = self._run_cum.get(s)
            if hit is None or hit[0] != key:
                hit = self._run_cum[s] = (key, np.concatenate((
                    [0], np.cumsum(latent_groups_coalesced(pt.blocks, run)))))
            cum = hit[1]
            # the groups of the folds between the slot's first and last
            ahead = min((-(-n // fold) - 1) * fold // run, len(cum) - 1)
            first = min(fold // run, ahead)
            coalesced += run * int(cum[ahead] - cum[first])
        return walked, coalesced

    def _walk_row(self, pos):
        """The row of the slot's table the walk of a step at `pos`
        ends at: `pos`, or its row in a two-grain run."""
        if not self._eva:
            return pos
        from ..ops.eva import eva_row
        return eva_row(pos, self.cfg.eva_chunk, self.cfg.eva_window)

    def hbm_read_stats(self) -> Dict[str, Any]:
        """Modeled decode-attention HBM read cost per generated token,
        fed from pool dtype + table occupancy (the
        /cache{...}/{count,bytes}/hbm-read-per-token counters and the
        serving-bench roofline columns).

        Each decode step emits one token per live slot and streams
        every MAPPED block of that slot once per layer, K and V pools
        both. How far a fused kernel WALKS a slot's table is another
        matter: the `fused` kernel over unquantized pools with a head
        of whole 128-lane rows stops at the block of the slot's
        position (`walk_entries_per_slot`, mean over the live slots of
        min(p // block_size + 1, max_blocks); `walk_share` is that over
        the table's width: what is left of a full-width walk) and
        copies an entry ONCE for the `heads_per_copy` kv heads a grid
        step owns (`walk_copies_per_slot` = `walk_entries_per_slot` x 2
        pools x n_kv / `heads_per_copy`: the DMA descriptors a slot,
        layer and step; both 0 where the calls keep the grid walk)
        into the set of banks the grid step before is not reading
        (`walk_bank_sets` 2, 0 on the grid walk;
        `walk_steps_prefetched_share` = (G - 1) / G, G = slots x n_kv /
        `heads_per_copy` of the call: the grid steps of a layer's call
        whose copies were in flight during the step before). The
        latent walk (`hpx_mla_paged`) copies an aligned group of
        `attention_pallas.LATENT_RUN` table entries that name
        neighbours in ONE descriptor: `latent_run_pct` is the share of
        the entries the next step's latent walks cover that lie in such
        a group (`_latent_entries`; what the SHAPE of the live tables
        gives the kernel: high for long prompts allocated in one loop
        on a fresh free list, a slot's first and last folds apart,
        which are never coalesced; less as slots grow side by side or
        a churned list hands out ids out of order; 0 where no latent
        layer takes the kernel). Every other fused call visits all max_blocks
        entries, whose tail aliases the single resident trash block —
        occupancy is the honest per-slot traffic either way.
        bytes/token uses
        `cache.block_allocator.block_bytes`, so the int8/fp8 sidecar
        scales are included: vs a bf16 compute dtype the quantized
        pools read ~0.5x, and vs tier-1's f32 compute dtype ~0.25x —
        the fp8 roofline ratio the acceptance gate pins at <= 0.30x."""
        live = sum(1 for pt in self._tables if pt is not None)
        blocks = occupancy(self._tables)
        per_tok = (blocks / live) if live else 0.0
        kinds = [self.cfg.mixer(i) for i in range(self.cfg.n_layers)]
        bb = block_bytes(self.block_size, self.cfg.kv_heads,
                         self.cfg.head_dim, self._kv_acct_dtype(),
                         layers=kinds.count("attn")
                         + kinds.count("sparse") + kinds.count("eva"))
        # a latent layer's block is one pool's rows (a recurrent
        # layer has none)
        bb += kinds.count("mla") * self.block_size * self.cfg.mla_row \
            * jnp.dtype(self.cfg.dtype).itemsize
        positions = self.live_positions()
        walks = [min(self._walk_row(p) // self.block_size + 1, self._maxb)
                 for p in positions.values()]
        walk = sum(walks) / len(walks) if walks else 0.0
        hg, nkv = self._walk_group()
        lat_walked, lat_coalesced = self._latent_entries(positions)
        # grid steps of one layer's call (the shard's under a mesh)
        dp = self.mesh.shape["dp"] if self.mesh is not None else 1
        grid = self.slots // dp * nkv // hg if hg else 0
        return {
            "hbm_read_blocks_per_token": per_tok,
            "hbm_read_bytes_per_token": per_tok * bb,
            # the bounded walk of the next decode step (the full
            # group's table; `attention_pallas._walk_entries` at W = 1)
            "walk_entries_per_slot": walk,
            "walk_share": walk / self._maxb,
            # how many kv heads share one copy of an entry, and the
            # copies (K and V) a slot, layer and step then issues
            "heads_per_copy": hg,
            "walk_copies_per_slot": walk * 2 * nkv / hg if hg else 0.0,
            # the sets of banks the walk lands in, and the share of a
            # call's grid steps whose copies the step before started
            "walk_bank_sets": 2 if hg else 0,
            "walk_steps_prefetched_share": (grid - 1) / grid if hg else 0.0,
            # of the table entries the next decode step's latent walks
            # cover, the share `hpx_mla_paged` copies with their
            # group's neighbours in one descriptor (0 where no latent
            # layer takes the kernel): what the tables' SHAPE gives it
            "latent_run_pct": 100.0 * lat_coalesced / lat_walked
            if lat_walked else 0.0,
            # where this server's block_size came from: arg | config |
            # env | seed (paged_blocks.json) | default
            "block_size_source": self._block_size_src,
            # what `auto` resolved to: gather | fused | fused_online
            "paged_kernel": self._paged_kernel,
        }

    def moe_stats(self) -> Dict[str, float]:
        """The sparse FFN's routing, summed over the decode steps whose
        statistics a flush has drained: claims routed and dropped, the
        steps, and `experts_hit_sum` (per step the distinct experts
        hit, mean over the sparse layers) — the feed of the
        /serving{...}/moe/* counters. Under a group-limited router
        also `routed_here` (the assignments that fell to the held
        experts) and `tokens_here` (the tokens, a sparse layer each,
        whose kept groups include a held one): `routed` counts every
        assignment, `routed / top_k` every token."""
        st = {"routed": self._moe_routed, "dropped": self._moe_dropped,
              "steps": self._moe_steps,
              "experts_hit_sum": self._moe_hit_sum}
        if self.cfg.moe_n_group > 1:
            st["routed_here"] = self._moe_here
            st["tokens_here"] = self._moe_tokens_here
        return st

    def prefill_stats(self) -> Dict[str, Any]:
        """The chunk width this server prefills at, where it came from
        (`arg` | `config` | `ridge`: derived from the device's ridge
        over the weights a chunk reads, `_ridge_chunk`) and the prompt
        tokens a chunk dispatch has carried so far; the chunked
        prefills under way (`_prefill_tick` gives ONE of them ONE chunk
        a step) and the step() calls that lay between a request's slot
        and its first token's program, summed over the admissions (0
        for a prompt that prefilled inline); `latent_groups`: the walks
        of the scratch the chunks' latent layers made (`transformer.
        latent_groups` of a chunk's width, a latent layer: over
        `prefill_chunks` x the latent layers where chunks are cut into
        groups of heads) — the /serving{...}/prefill/* counters."""
        return {"prefill_chunk": self.prefill_chunk,
                "prefill_chunk_source": self._prefill_chunk_src,
                "prefill_chunks": self._chunks,
                "latent_groups": self._latent_groups,
                "prefill_rows": self._chunk_rows,
                "prefill_pending": len(self._pending),
                "admit_wait_steps": self._admit_wait_steps,
                "prefill_rows_per_chunk":
                    self._chunk_rows / self._chunks if self._chunks
                    else 0.0}

    def read_stats(self) -> Dict[str, int]:
        """The blocking device->host reads so far (seed tokens, token
        vectors, MoE statistics), by whether a decode step was queued
        behind the value read — the /serving{...}/reads/* counters."""
        return {"reads_overlapped": self._reads_overlapped,
                "reads_draining": self._reads_draining}

    def step_accounts(self) -> List[tracing.StepRecord]:
        """The account of each of the last 4,096 step() calls, oldest
        first (`svc/tracing.StepRecord`: wall = work + held in dispatch
        calls + waited on reads, the program that held longest, the
        step's dispatches, reads, admissions and chunks, how much of
        the work lay in the decode step's operands, and the process's
        own view: CPU time, collector pauses; `slow` where the step, or
        the 32 steps it ends, stood out — the /serving{...}/steps/*
        counters count those, and each leaves a `svc/flight` bundle,
        one in 5 s)."""
        return self._acct.records()

    def spec_stats(self) -> Dict[str, float]:
        """Speculation observability snapshot (the same numbers the
        /serving{...}/spec/* performance counters export)."""
        drafted, steps = self._spec_drafted, self._spec_steps
        return {
            "drafted": float(drafted),
            "accepted": float(self._spec_accepted),
            "acceptance_rate": (self._spec_accepted / drafted)
                               if drafted else 0.0,
            "steps": float(steps),
            "emitted": float(self._spec_emitted),
            "tokens_per_step": (self._spec_emitted / steps)
                               if steps else 0.0,
        }

    def fault_stats(self) -> Dict[str, Any]:
        """Resiliency observability snapshot — the scalar fields feed
        the /serving{...}/faults/* performance counters; the chaos
        bench reads `restored_by_site` for its per-fault-class gate
        and `restore_p99_s` for the restore-latency column (a live
        HistogramCounter quantile — bounded relative error, O(buckets)
        memory — not a sorted sample list)."""
        p99 = self._restore_hist.quantile(0.99)
        return {
            "injected": self._flt_injected,
            "retried": self._flt_retried,
            "restored": self._flt_restored,
            "shed": self._flt_shed,
            "degraded": self._flt_degraded,
            "restore_p99_s": p99,
            "restored_by_site": dict(self._restored_by_site),
        }

    # -- public API ------------------------------------------------------

    def submit(self, prompt, max_new: int, eos_id: Optional[int] = None,
               temperature: float = 0.0, key=None,
               deadline_s: Optional[float] = None) -> int:
        if self._closed:
            raise ServerClosedError()
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("continuous batching needs a non-empty "
                             "prompt (unconditional generation: "
                             "transformer.generate)")
        if len(prompt) + max_new > self.smax:
            raise ValueError(
                f"plen {len(prompt)} + max_new {max_new} exceeds "
                f"smax {self.smax}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new} "
                             "(generate() handles max_new == 0)")
        if temperature > 0.0 and key is None:
            raise ValueError("temperature > 0 needs a PRNG key")
        if temperature <= 0.0 and key is not None:
            raise ValueError(
                "key has no effect at temperature=0 (greedy); pass "
                "temperature > 0 to sample")
        if key is not None:
            key = _normalize_key(key)
        if deadline_s is None:
            deadline_s = self._default_deadline_s or None
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(
                f"deadline_s must be > 0 (got {deadline_s}); omit it "
                "for no deadline")
        rid = self._next_rid
        self._next_rid += 1
        now = time.monotonic()
        self._queue.append(_Request(
            rid, prompt, max_new, eos_id, temperature, key,
            t_submit=now, deadline_s=deadline_s,
            t_deadline=(now + deadline_s) if deadline_s else None))
        self.timeline.event(rid, "submit", t=now, plen=len(prompt))
        return rid

    def admit_prefilled(self, prompt, kv_rows, seed_token: int,
                        max_new: int, eos_id: Optional[int] = None,
                        temperature: float = 0.0, key=None,
                        deadline_s: Optional[float] = None) -> int:
        """Submit a request whose prefill ALREADY HAPPENED on a remote
        prefill worker (disaggregated serving, `models/disagg`):
        `kv_rows` are the worker's raw compute-dtype scratch rows
        ([n_layers, 2, plen, n_kv, head_dim]) and `seed_token` is the
        token its probe seeded. Admission allocates blocks and splices
        the rows through the SAME `_paged_splice_prog` a colocated
        prefill uses, then decodes normally from pos=plen — emitted
        tokens match what a colocated submit() would produce. The rows
        stay a host array until a slot admits, so shedding a queued
        transfer can never leak pool blocks."""
        self._only_kv_pairs("admit_prefilled()")
        if self._win:
            raise NotImplementedError(
                "admit_prefilled() on a model with window layers: the "
                "transfer protocol (cache/transfer.KVSegment) ships one "
                "block map a request, the window group needs its own")
        if self._closed:
            raise ServerClosedError()
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("admit_prefilled needs a non-empty prompt")
        if len(prompt) + max_new > self.smax:
            raise ValueError(
                f"plen {len(prompt)} + max_new {max_new} exceeds "
                f"smax {self.smax}")
        if max_new < 1:
            raise ValueError(f"max_new must be >= 1, got {max_new}")
        if temperature > 0.0 and key is None:
            raise ValueError("temperature > 0 needs a PRNG key")
        if key is not None:
            key = _normalize_key(key)
        rows = np.asarray(kv_rows)
        nkv, hd = self.cfg.kv_heads, self.cfg.head_dim
        want = (self.cfg.n_layers, 2, len(prompt), nkv, hd)
        if tuple(rows.shape) != want:
            raise ValueError(
                f"kv_rows shape {tuple(rows.shape)} != expected {want}")
        if deadline_s is None:
            deadline_s = self._default_deadline_s or None
        rid = self._next_rid
        self._next_rid += 1
        now = time.monotonic()
        self._queue.append(_Request(
            rid, prompt, max_new, eos_id, temperature, key,
            t_submit=now, deadline_s=deadline_s,
            t_deadline=(now + deadline_s) if deadline_s else None,
            xfer_rows=rows, xfer_seed=int(seed_token)))
        return rid

    def export_prefix_rows(self, tokens):
        """The other direction of :meth:`admit_prefilled`: the longest
        radix-cached whole-block prefix of `tokens`, exported as raw
        compute-dtype host rows ``[n_layers, 2, matched, n_kv,
        head_dim]`` (the exact layout a prefill worker's scratch
        seeds from and a KV segment frames). Returns ``(matched,
        rows)`` — ``(0, None)`` on a cold tree.

        This is what lets a fleet router turn a placement HIT into a
        prefill SAVING: the rows a retired request published here get
        pulled once, shipped as ordinary retained segments, and the
        prefill worker computes only the suffix. Quantized pools
        dequantize through the same elementwise ops the fused kernels
        apply ((q * scale).astype(dtype)), so bf16/f32 pools roundtrip
        bit-exactly; int8/fp8 exports carry the pool's quantization —
        same contract as colocated prefix reuse on those pools. The
        match's block leases drop before returning (the caller gets
        BYTES, not references — nothing here can leak pool blocks)."""
        self._only_kv_pairs("export_prefix_rows()")
        matched, bids = self._radix.match(tokens)
        if not matched:
            return 0, None
        try:
            nkv, hd = self.cfg.kv_heads, self.cfg.head_dim
            idx = jnp.asarray(bids, jnp.int32)
            layers = []
            for li, (kp, vp) in enumerate(self._pools):
                sides = []
                for side, pool in enumerate((kp, vp)):
                    # hpxlint: disable-next=HPX010 — host-side export
                    # of a few matched blocks (once per fleet
                    # placement hit), not the decode attention loop
                    g = pool[idx]                 # [nblk, nkv, bs, hd]
                    if self._scales is not None:
                        sc = self._scales[li][side][idx]
                        g = (g.astype(jnp.float32)
                             * sc[:, :, None, None])
                    g = block_rows(g.astype(self.cfg.dtype))
                    sides.append(np.asarray(g).reshape(
                        matched, nkv, hd))
                layers.append(np.stack(sides))
            rows = np.stack(layers)
        finally:
            for bid in bids:
                self._alloc.decref(bid)
        return matched, rows

    def _only_kv_pairs(self, what: str) -> None:
        """Refuse a transfer of cache rows on a model some of whose
        layers cache no K/V pair."""
        if self._kinds:
            raise NotImplementedError(
                f"{what} on a model with {self._kinds} mixers: the "
                "transfer protocol ships K/V rows a layer, and a "
                "recurrent state or a latent row is neither "
                "(models/serving.py, models/disagg.py, cache/transfer.py)")

    def shutdown(self) -> None:
        """Close the intake: every later submit() raises
        ServerClosedError. Queued and in-flight requests are NOT
        cancelled — run()/step() still drain them (graceful drain);
        their results land in `run()`'s dict as usual."""
        self._closed = True

    # -- chunked prefill -------------------------------------------------

    def _bucket_width(self, n: int) -> int:
        """Smallest ladder width covering n chunk tokens."""
        for w in self.prefill_buckets:
            if w >= n:
                return w
        return self.prefill_buckets[-1]

    def _next_chunk(self, pos0: int, remaining: int) -> Tuple[int, int]:
        """(tokens, ladder width) of the chunk that starts at row
        `pos0` with `remaining` tokens to go. A tail chunk's pad rows
        reach pos0 + width, and a `dynamic_update_slice` whose window
        would pass the scratch's `smax` rows is CLAMPED and shifts the
        real rows: there the chunk is the widest bucket that fits and
        the rest a further chunk; where none fits, one row through the
        chunk program at width 1."""
        n = min(self.prefill_chunk, remaining)
        width = self._bucket_width(n)
        room = self.smax - pos0
        if width > room:
            width = max((w for w in self.prefill_buckets if w <= room),
                        default=1)
            n = min(n, width)
        return n, width

    def _run_chunk(self, caches, seq: List[int], done: int, n: int,
                   width: int):
        """Dispatch the chunk `_next_chunk` planned, seq[done:done + n],
        into the b=1 scratch `caches`: (scratch, the hidden row the
        probe starts from, `_chunk_prog`)."""
        toks = seq[done:done + n] + [0] * (width - n)
        return self._chunk_prog(width)(
            self.params, caches, np.asarray([toks], np.int32),
            np.int32(done), np.int32(n))

    def _fresh_scratch(self):
        """An empty b=1 prefill scratch, one entry a layer, made by ONE
        program: an admission enqueues one dispatch for it, not two
        tiny ones a layer."""
        cfg, smax = self.cfg, self.smax    # not `self`: the program
        ck = ("cb_scratch", cfg, smax)      # cache outlives the server
        return self._program(ck, lambda: jax.jit(lambda: [
            _scratch_entry(cfg, smax, i) for i in range(cfg.n_layers)]))()

    def _start_prefill(self, req: "_Request",
                       slot: int) -> _PendingPrefill:
        """Reserve `slot` and stand up the b=1 scratch cache: match
        the radix prefix, hold blocks for the whole prompt, and gather
        the matched ones into the scratch."""
        self._pf_seq += 1
        plen = len(req.prompt)
        matched, mbids, tier_ext = 0, [], []
        sparse = "sparse" in self._kinds
        if self._prefix_reuse and (self._win or self._recomputes
                                   or sparse):
            # a prefix hit would hand the full layers their rows and
            # leave the window layers without the matched prefix's last
            # window, a recurrent layer without its state at the
            # block's boundary, a sparse layer's scratch without the
            # rows its index is made of, an eva layer with blocks whose
            # grain depends on where the request's window ends: refused
            # (and counted) until the tree keeps them
            self._prefix_refused += 1
        elif self._prefix_reuse:
            # always leave >= 1 suffix token: admission needs the LAST
            # prompt token's logits to seed generation. A model whose
            # every layer caches rows addressed by position (K/V pairs
            # or latent rows, each a function of its own token and
            # position alone) takes the match.
            with tracing.span("serving.prefix_match", "serving",
                              rid=req.rid, plen=plen):
                if self._tier is not None:
                    matched, mbids, tier_ext = self._radix.match_tiered(
                        req.prompt[:-1], self._tier)
                else:
                    matched, mbids = self._radix.match(req.prompt[:-1])
        if tier_ext:
            # crossover-gated restore: a promoted chain extends the
            # hot match (mbids grows, matched covers the restored
            # blocks, the write row below trash-redirects them), a
            # declined one re-prefills with entries left in the tier
            matched += self._promote_tier(req, matched, mbids,
                                          tier_ext)
        pt = TwoGrainTable(self.block_size, self.cfg.eva_window,
                           self.cfg.eva_chunk) if self._eva \
            else PageTable(self.block_size)
        pt.extend_blocks(mbids)
        try:
            while len(pt.blocks) < pt.held(plen):
                pt.append_block(self._alloc_block())
        except CacheOOM:
            for bid in pt.blocks:
                self._alloc.decref(bid)
            raise
        pt.tokens = plen
        self._prefill_saved += matched
        self._prefill_computed += plen - matched
        trow = pt.as_row(self._maxb, self._trash)
        # the splice's WRITE row: radix-matched prefix blocks are
        # shared, so their entries redirect to the trash block — the
        # splice never rewrites them (see _paged_splice_prog)
        wnp = trow.copy()
        wnp[:matched // self.block_size] = self._trash
        wrow = (wnp,)
        if self._eva:
            pt.adopt(plen)
            wrow = self._eva_wrows(pt)
        # an empty scratch, but where the gather brings the match
        caches, wt = None, None
        if self._recurrent:
            # the request's state starts from zeros in its scratch and
            # the splice overwrites the slot's row whole: the reset
            n_rec = sum(self.cfg.mixer(i) in RECURRENT_KINDS
                        for i in range(self.cfg.n_layers))
            with tracing.span("serving.state_reset", "serving",
                              rid=req.rid, slot=slot, layers=n_rec):
                caches = self._fresh_scratch()
                self._state_resets += 1
        elif sparse or self._eva:
            pass        # the gather's program reads K/V pairs alone
        elif not self._win:
            # the matched rows, out of the shared blocks into the
            # request's scratch (the pools' kind: K/V or latent rows)
            with tracing.span("serving.prefix_gather", "serving",
                              rid=req.rid, matched=matched, plen=plen):
                caches = self._paged_gather_prog()(
                    self._pools, self._scales, trow, np.int32(matched))
        else:
            # window group: the blocks the FIRST decode step (at plen)
            # can still see; the splice writes the scratch's rows of
            # exactly those
            wt = WindowTable(self.block_size, self._win)
            wt.base = wt.first_needed(plen)
            try:
                while wt.capacity < plen:
                    wt.append_block(self._walloc.alloc())
            except CacheOOM:
                self._free_window(wt)
                for bid in pt.blocks:
                    self._alloc.decref(bid)
                raise
            wrow += (wt.as_linear_row(self._maxb, self._wtrash),)
        p = self._pending[slot] = _PendingPrefill(
            req=req, slot=slot,
            caches=self._fresh_scratch() if caches is None else caches,
            done=matched, seq=self._pf_seq, pt=pt, trow=trow, wrow=wrow,
            wt=wt, step0=self._step_n)
        self._admit_defers.pop(req.rid, None)   # admitted: ladder done
        return p

    def _eva_wrows(self, pt: TwoGrainTable):
        """The splice's write rows of a two-grain table: (summary
        blocks, window blocks), each padded with the trash block to its
        part of the scratch."""
        from ..ops.eva import summary_rows
        return pt.write_rows(
            summary_rows(self.smax, self.cfg.eva_chunk,
                         self.cfg.eva_window) // self.block_size,
            self._trash)

    def _advance_chunk(self, p: _PendingPrefill) -> None:
        """Run ONE bucketed chunk of p's prompt into its scratch.

        Fault site "prefill": the check fires BEFORE the chunk
        dispatch and before any host mutation, so a fault here leaves
        the pending internally consistent — recovery restarts it from
        the prompt (`_restart_pending`; a restart re-matches the
        radix prefix, so already-resident blocks are not recomputed).
        """
        faultinject.check("prefill")
        req = p.req
        n, width = self._next_chunk(p.done, p.remaining)
        with tracing.span("serving.prefill_chunk", "serving",
                          rid=req.rid, pos0=p.done, tokens=n,
                          width=width):
            if p.flow is not None:
                tracing.flow_end(p.flow, "serving.prefill_chunks")
                p.flow = None
            p.caches, p.row = self._run_chunk(p.caches, req.prompt,
                                              p.done, n, width)
            if self._eva and (p.done + n) // self.cfg.eva_window \
                    > p.done // self.cfg.eva_window:
                # the chunk completed a window in the scratch: its
                # summaries are pooled and seen by the rows behind the
                # boundary (no block is held yet: none to free)
                with tracing.span(
                        "serving.window_roll", "serving", rid=req.rid,
                        slot=p.slot, window=p.done // self.cfg.eva_window,
                        blocks=0, rows=self.cfg.eva_window
                        // self.cfg.eva_chunk):
                    self._eva_rolls += 1
            if self._eva:
                # the chunks of `eva_chunk` rows this one completed
                self._eva_pooled += (p.done + n) // self.cfg.eva_chunk \
                    - p.done // self.cfg.eva_chunk
            p.done += n
            self._chunks += 1
            self._chunk_rows += n
            self._latent_groups += self.cfg.layer_mixer.count("mla") \
                * latent_groups(1, width, self.cfg.n_heads)
            if p.remaining:
                p.flow = tracing.flow_begin("serving.prefill_chunks")

    def _finish_prefill(self, p: _PendingPrefill) -> None:
        """Prompt fully chunked: probe the last position from the row
        its last chunk handed back (one layer and the head), which
        picks the seed token and sets the slot's lane of the per-slot
        vectors on the device (`_probe_prog`), splice the scratch into
        the slot's blocks, go live. An admission
        enqueues its named programs and nothing else: every operand
        here is host NumPy. The host reads the seed token
        (`_land_seeds`) once this step's decode is enqueued — at once
        only where its VALUE decides what happens before that dispatch
        (an eos check, an instant retire, a speculative step's host-fed
        drafts, a synchronous server)."""
        req, slot = p.req, p.slot
        plen = len(req.prompt)
        self._admit_wait_steps += self._step_n - p.step0
        caches, self._cur_dev, self._temp_dev, self._keys_dev, tok0 = \
            self._probe(p.caches, p.row, plen - 1, slot,
                        req.temperature, req.key)
        if p.flow is not None:
            tracing.flow_end(p.flow, "serving.prefill_chunks")
            p.flow = None
        self._pools, self._scales = self._paged_splice_prog()(
            self._pools, self._scales, caches, p.wrow, np.int32(slot))
        self._tables[slot], self._wtables[slot] = p.pt, p.wt
        del self._pending[slot]
        req.sent = 1
        self._slot_req[slot] = req
        self._pos[slot] = plen
        self._set_lane(slot, req)
        if self._spec:
            self._slot_k[slot] = self._spec_k     # fresh adaptive k
            self._slot_acc[slot] = 1.0
            if self._draft_params is not None:
                self._draft_prefill(slot, req.prompt)
        self._seeds.append((req, slot, tok0))
        if (req.eos_id is not None or req.max_new == 1 or self._spec
                or not self._async):
            self._land_seeds(behind=0)

    def _set_lane(self, slot: int, req: "_Request") -> None:
        """The host's copy of `slot`'s sampling state (what `_lanes`
        sends the device after a recovery)."""
        self._temp[slot] = req.temperature
        self._key[slot] = self._no_key if req.key is None else req.key

    def _wait(self, name: str, behind: int, **args):
        """The span around ONE blocking device->host read. `behind`:
        decode steps dispatched after the program whose value it
        waits for — none, and the read empties the dispatch queue
        (`reads_draining`); else the device has a step to run while
        the host stands still and goes on (`reads_overlapped`)."""
        if behind > 0:
            self._reads_overlapped += 1
        else:
            self._reads_draining += 1
        return _Read(self._acct,
                     tracing.span(name, "serving", behind=behind, **args))

    def _land_seeds(self, behind: int) -> None:
        """Read the seed tokens `_finish_prefill` left on the device,
        in admission order: each reaches `req.tokens` before any
        decoded token (`_flush` comes here first)."""
        while self._seeds:
            req, slot, tok0 = self._seeds.popleft()
            with self._wait("serving.first_token.wait", behind,
                            rid=req.rid):
                tok0 = int(tok0)
            req.tokens.append(tok0)
            self._cur[slot] = tok0
            ttft = time.monotonic() - req.t_submit
            self.ttft[req.rid] = ttft
            self.hist["ttft"].record(ttft, rid=req.rid)
            self.timeline.event(req.rid, "first_token", slot=slot)
            if self._slot_req[slot] is req:
                # seed checkpoint: a fault before the first cadence
                # capture restores to the freshly-admitted state
                # instead of losing the slot. (Else max_new == 2: the
                # slot retired at the dispatch this read followed.)
                self._capture(slot)
                self._maybe_retire(slot)

    def _admit(self) -> None:
        """Fill free slots from the queue. A prompt whose remaining
        tokens fit one chunk prefills INLINE (admission latency = one
        chunk + the one-layer probe, and instant retires drain without
        decode steps); a longer prompt reserves the slot as a PENDING
        prefill and advances chunk-by-chunk in _prefill_tick, interleaved with
        decode.

        A request that retires DURING admission (max_new == 1, or an
        instant eos) frees its slot immediately — the inner loop
        re-scans the same slot within this pass, so a burst of
        one-token requests drains through one slot without burning a
        full decode step per request on an empty batch.

        Admission OOM (the pool is full and `_alloc_block`'s
        evict→retry already failed, or an injected alloc fault
        escalated) walks `_defer_admit`'s ladder: requeue at the front
        for up to hpx.serving.admit_retries passes — retirements
        between steps free blocks — then shed with a typed error."""
        for slot in range(self.slots):
            while (self._slot_req[slot] is None
                   and slot not in self._pending and self._queue):
                req = self._queue.popleft()
                plen = len(req.prompt)
                # queue wait = submit -> first admission attempt (an
                # OOM-deferred request re-dequeues but records once)
                if req.rid not in self._admit_defers:
                    self.hist["queue_wait"].record(
                        time.monotonic() - req.t_submit,
                        rid=req.rid)
                    self.timeline.event(req.rid, "prefill_start",
                                        slot=slot)
                self._admits += 1
                self._rid = req.rid
                try:
                    with tracing.span("serving.admit", "serving",
                                      rid=req.rid, slot=slot,
                                      plen=plen):
                        if req.xfer_rows is not None:
                            self._admit_transferred(req, slot)
                            continue
                        p = self._start_prefill(req, slot)
                        if p.remaining <= self.prefill_chunk:
                            with tracing.span("serving.prefill",
                                              "serving", rid=req.rid,
                                              plen=plen,
                                              matched=p.done,
                                              suffix=p.remaining):
                                while p.remaining:
                                    self._advance_chunk(p)
                                self._finish_prefill(p)
                        else:
                            p.flow = tracing.flow_begin(
                                "serving.prefill_chunks")
                except CacheOOM as e:
                    if slot in self._pending:
                        self._drop_pending(slot)
                    if not self._defer_admit(req, e):
                        return   # deferred: give retirements a step
                                 # to free blocks before re-admitting

    def _admit_transferred(self, req: "_Request", slot: int) -> None:
        """Admit a remotely-prefilled request: allocate its blocks,
        splice the shipped rows through the colocated splice program
        (identical quantization/padding semantics), seed the remote
        probe's token, go live at pos=plen. Mirrors `_finish_prefill`
        minus the compute — every downstream invariant (checkpoint
        capture, retire, COW discipline) sees a normal live slot."""
        plen = len(req.prompt)
        pt = PageTable(self.block_size)
        try:
            while pt.capacity < plen:
                pt.append_block(self._alloc_block())
        except CacheOOM:
            for bid in pt.blocks:
                self._alloc.decref(bid)
            raise
        pt.tokens = plen
        self._admit_defers.pop(req.rid, None)
        trow = (pt.as_row(self._maxb, self._trash),)
        nkv, hd = self.cfg.kv_heads, self.cfg.head_dim
        rows = req.xfer_rows
        scratch = []
        for li in range(self.cfg.n_layers):
            k = jnp.zeros((1, self.smax, nkv, hd), self.cfg.dtype)
            k = k.at[0, :plen].set(
                jnp.asarray(rows[li, 0], self.cfg.dtype))
            v = jnp.zeros((1, self.smax, nkv, hd), self.cfg.dtype)
            v = v.at[0, :plen].set(
                jnp.asarray(rows[li, 1], self.cfg.dtype))
            scratch.append((k, v))
        self._pools, self._scales = self._paged_splice_prog()(
            self._pools, self._scales, scratch, trow, np.int32(slot))
        self._tables[slot] = pt
        req.xfer_rows = None           # host copy no longer needed
        tok0 = int(req.xfer_seed)
        # the seed token is the host's: land what is in flight, so that
        # the host's feedback tokens are the newest, set the slot's
        # lanes there and let `_feedback` / `_lanes` send the arrays
        if self._buf or self._seeds:
            self._flush()
        req.tokens.append(tok0)
        req.sent = 1
        self._slot_req[slot] = req
        self._pos[slot] = plen
        self._cur[slot] = tok0
        self._set_lane(slot, req)
        self._cur_dev = self._temp_dev = None
        if self._spec:
            self._slot_k[slot] = self._spec_k
            self._slot_acc[slot] = 1.0
            if self._draft_params is not None:
                self._draft_prefill(slot, req.prompt)
        ttft = time.monotonic() - req.t_submit
        self.ttft[req.rid] = ttft
        self.hist["ttft"].record(ttft, rid=req.rid)
        self.timeline.event(req.rid, "transfer_admit", slot=slot,
                            plen=plen)
        self._prefill_saved += plen    # prefill compute happened remotely
        self._capture(slot)
        self._maybe_retire(slot)

    def _defer_admit(self, req: "_Request", exc: CacheOOM) -> bool:
        """Admission OOM ladder, entered after evict→retry failed:
        requeue the request at the FRONT (bounded by
        hpx.serving.admit_retries), then shed. Returns True when the
        request was shed (the admit pass may continue with the next
        request), False when deferred (the pass should stop)."""
        n = self._admit_defers.get(req.rid, 0) + 1
        if n > self._admit_retries:
            self._admit_defers.pop(req.rid, None)
            self._shed_req(req, RequestShedError(
                req.rid,
                f"admission OOM persisted through {n} attempts "
                f"({exc})"))
            return True
        self._admit_defers[req.rid] = n
        self._flt_retried += 1
        self._queue.appendleft(req)
        return False

    def _prefill_tick(self) -> None:
        """Advance chunked prefills: ONE chunk per step, given to the
        pending with the FEWEST remaining prompt tokens (ready-chunk
        ordering — a short prompt admitted behind a long one overtakes
        its tail chunks; FIFO breaks ties). The finishing pending
        splices and goes live the same step."""
        if not self._pending:
            return
        with tracing.span("serving.prefill_tick", "serving",
                          pending=len(self._pending)):
            p = min(self._pending.values(),
                    key=lambda q: (q.remaining, q.seq))
            self._rid = p.req.rid
            self._advance_chunk(p)
            if p.remaining == 0:
                with tracing.span("serving.prefill", "serving",
                                  rid=p.req.rid,
                                  plen=len(p.req.prompt), chunked=True):
                    self._finish_prefill(p)

    # -- speculative decode ----------------------------------------------

    def _draft_prefill(self, slot: int, prompt: List[int]) -> None:
        """Build the draft model's K/V rows 0..plen-1 for a freshly
        admitted slot: bucketed chunks over the whole prompt (same
        ladder as the target's prefill, so draft chunk programs are
        O(buckets) too)."""
        done, plen = 0, len(prompt)
        while done < plen:
            n, width = self._next_chunk(done, plen - done)
            toks = prompt[done:done + n] + [0] * (width - n)
            self._draft_caches = self._draft_chunk_prog(width)(
                self._draft_params, self._draft_caches,
                np.asarray([toks], np.int32), np.int32(done),
                np.int32(slot))
            done += n

    def _prompt_drafts(self, live: List[int],
                       kcap: Dict[int, int]) -> Dict[int, List[int]]:
        """Zero-model draft proposals per live slot: n-gram
        continuation mining over the slot's own history (prompt +
        generated so far), falling back to the radix tree's cached
        continuations when the history has no recurring suffix
        (prefix reuse keeps whole retired prompts around —
        `RadixCache.peek` reads them without taking leases)."""
        drafts: Dict[int, List[int]] = {}
        for s in live:
            req = self._slot_req[s]
            k = kcap[s]
            hist = req.prompt + req.tokens
            d = _ngram_propose(hist, k, self._spec_ngram) if k else []
            if not d and k and self._prefix_reuse:
                d = self._radix.peek(hist, k)
            drafts[s] = d[:k]
        return drafts

    def _draft_model_tokens(self, kbatch: int):
        """kbatch+1 chained greedy draft-model steps, entirely
        device-side. The extra (kbatch+1)-th feed lands the LAST draft
        token's K/V rows so the next round's draft attention never
        reads a never-written position (speculative_generate's KV-hole
        discipline); its proposal is discarded. Positions clamp at
        smax-1 for lanes whose window runs past the budget — those
        rows are rewritten by the real feed at that position before
        the causal mask can ever expose them. Returns [slots,
        1 + kbatch] int32 (column 0 = the committed cur tokens)."""
        prog = self._draft_step_prog()
        tok = self._feedback()
        pos = np.array(self._pos, np.int32)
        cols = [tok]
        for i in range(kbatch + 1):
            self._draft_caches, tok = prog(
                self._draft_params, self._draft_caches, tok,
                np.minimum(pos + i, self.smax - 1))
            if i < kbatch:
                cols.append(tok)
        return jnp.stack(cols, axis=1)

    def _spec_adapt_k(self, slot: int, accepted: int,
                      drafted: int) -> None:
        """Per-slot adaptive k: EMA the acceptance rate; back off when
        it sinks below hpx.serving.spec.min_accept (wasted draft+verify
        work), creep back toward the configured k when acceptance runs
        high. The EMA resets on change so one adjustment gets a fresh
        measurement window before the next."""
        if not drafted or not self._spec_adapt:
            return
        ema = 0.5 * self._slot_acc[slot] + 0.5 * (accepted / drafted)
        self._slot_acc[slot] = ema
        if ema < self._spec_min_accept and self._slot_k[slot] > 1:
            self._slot_k[slot] -= 1
            self._slot_acc[slot] = 1.0
        elif ema > 0.8 and self._slot_k[slot] < self._spec_k:
            self._slot_k[slot] += 1
            self._slot_acc[slot] = 1.0

    def _spec_step(self, live: List[int]) -> None:
        """One speculative decode step: draft up to k tokens per live
        slot, verify the whole batch with ONE window forward at
        per-slot positions, commit the longest target-agreeing prefix
        plus the bonus target token. Content is byte-identical to the
        sequential step loop (see `_verify_tail`); only the number of
        tokens per host sync changes. Rejection is cheap by
        construction: the tables just rewind their cursor
        (`PageTable.rollback`) and drop window-extension blocks."""
        self._flush()              # spec commits synchronously
        kcap: Dict[int, int] = {}
        for s in live:
            req = self._slot_req[s]
            remaining = req.max_new - len(req.tokens)
            kcap[s] = max(0, min(self._slot_k[s], remaining - 1))
        kbatch = max(kcap.values())
        width = self._bucket_width(1 + kbatch)
        kvec_host = [0] * self.slots
        f_draft = tracing.flow_begin("serving.spec")
        with tracing.span("serving.spec.draft", "serving",
                          source=self._spec_source, k=kbatch,
                          slots=len(live)):
            tracing.flow_end(f_draft, "serving.spec.draft")
            f_verify = tracing.flow_begin("serving.spec")
            if self._draft_params is not None:
                toks = self._draft_model_tokens(kbatch)
                if width > 1 + kbatch:
                    toks = jnp.pad(toks,
                                   ((0, 0), (0, width - 1 - kbatch)))
                for s in live:
                    kvec_host[s] = kcap[s]
            else:
                mat = np.zeros((self.slots, width), np.int32)
                mat[:, 0] = self._cur
                for s, d in self._prompt_drafts(live, kcap).items():
                    mat[s, 1:1 + len(d)] = d
                    kvec_host[s] = len(d)
                toks = mat
        drafted = sum(kvec_host[s] for s in live)
        with tracing.span("serving.spec.verify", "serving",
                          width=width, drafted=drafted,
                          slots=len(live)):
            tracing.flow_end(f_verify, "serving.spec.verify")
            # fault site "verify": before the window dispatch and
            # before any host commit — a fault here costs only the
            # (restorable) draft-cache advance; repeated ones walk the
            # degradation ladder in _recover and turn speculation off
            faultinject.check("verify")
            pos = np.array(self._pos, np.int32)
            kvec = np.array(kvec_host, np.int32)
            temp, keys = self._lanes()
            for s in live:
                self._ensure_window(s, self._pos[s],
                                    self._pos[s] + kvec_host[s])
            self._pools, self._scales, packed, ms = \
                self._paged_verify_prog(width)(
                    self.params, self._pools, self._scales, toks, pos,
                    self._tables_dev(), kvec, temp, keys)
            if ms is not None:
                self._moe_buf.append(ms)
            # the speculative step's single designed host sync: one
            # packed [slots, width+1] read carries every slot's target
            # tokens AND acceptance count together
            vals = np.asarray(packed)
        emitted_total = 0
        for s in live:
            req = self._slot_req[s]
            acc = int(vals[s, width])
            m = min(acc + 1, req.max_new - len(req.tokens))
            emis = [int(t) for t in vals[s, :m]]
            if req.eos_id is not None and req.eos_id in emis:
                emis = emis[:emis.index(req.eos_id) + 1]
            req.tokens.extend(emis)
            req.sent = len(req.tokens)
            self._pos[s] += len(emis)
            self._cur[s] = emis[-1]
            emitted_total += len(emis)
            self._spec_drafted += kvec_host[s]
            self._spec_accepted += min(acc, kvec_host[s])
            self._spec_adapt_k(s, min(acc, kvec_host[s]),
                               kvec_host[s])
            # rewind the table cursor past rejected draft rows;
            # _release_slot (below, on retire) must see the
            # post-rollback block list or it would double-release
            for bid in self._tables[s].rollback(self._pos[s]):
                self._alloc.decref(bid)
            self._maybe_retire(s)
        self._spec_steps += 1
        self._spec_emitted += emitted_total
        self._rate.mark(float(emitted_total))
        self._cur_dev = None
        self._verify_faults = 0    # a committed verify resets the
                                   # degradation ladder
        self._ckpt_sweep()         # spec commits are flush boundaries

    # -- checkpoint / restore / shed (ROADMAP item 5) --------------------

    def _capture(self, slot: int) -> None:
        """Snapshot one live slot's restore point at the frontier the
        host holds: the tokens landed, and the position and feedback
        token that follow from them — `_pos` is ahead of it by the
        steps in flight (``req.sent - len(req.tokens)``, at most one
        where a flush sweeps). The pins take one extra ref per FULL
        block below pos — never the partial frontier block, whose pin
        would force a COW fork on the next token write (see
        SlotCheckpoint)."""
        req = self._slot_req[slot]
        pos = len(req.prompt) + len(req.tokens) - 1
        pins: List[int] = []
        if not self._recomputes:
            pt = self._tables[slot]
            pins = list(pt.blocks[:pos // self.block_size])
            for bid in pins:
                self._alloc.incref(bid)
        wpins = None
        wt = self._wtables[slot]
        if wt is not None:
            # `_ensure_block` frees one step behind the write, so the
            # frontier's window is still mapped with a step in flight
            assert wt.base <= wt.first_needed(pos), (wt.base, pos)
            wpins = (wt.base, list(wt.blocks))
            for bid in wpins[1]:
                self._walloc.incref(bid)
        old = self._ckpt.pop(slot, None)
        self._ckpt[slot] = SlotCheckpoint(
            rid=req.rid, tokens=list(req.tokens), pos=pos,
            cur=req.tokens[-1], slot_k=self._slot_k[slot],
            slot_acc=self._slot_acc[slot], pins=pins, wpins=wpins)
        self._unpin(old)

    def _unpin(self, ck: Optional[SlotCheckpoint]) -> None:
        if ck is not None:
            for bid in ck.pins:
                self._alloc.decref(bid)
            for bid in (ck.wpins or (0, ()))[1]:
                self._walloc.decref(bid)

    def _drop_ckpt(self, slot: int) -> None:
        self._unpin(self._ckpt.pop(slot, None))

    def _ckpt_sweep(self) -> None:
        """Advance checkpoints at a flush boundary: every live slot
        whose landed tokens grew by >= hpx.serving.ckpt_every since
        its last capture (or whose checkpoint is missing/stale)
        captures now. Runs at the end of _flush (of a flush that left
        the newest step in flight too: `_capture` takes the frontier
        the host holds) and after spec commits."""
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None or not req.tokens:
                continue
            ck = self._ckpt.get(s)
            if (ck is None or ck.rid != req.rid
                    or len(req.tokens) - len(ck.tokens)
                    >= self._ckpt_every):
                self._capture(s)

    def _restore_slot(self, slot: int) -> None:
        """Rewind one live slot to its last checkpoint; the decode
        loop then replays ONLY the lost suffix. Rebuild the table
        from the pinned full blocks plus the live table's frontier
        block — its rows [0, pos % bs) are byte-exact because KV rows
        are append-only and COW forks copy every row written so far.
        Replayed tokens re-emit identically, so a restored run's
        outputs match the fault-free run."""
        ck = self._ckpt[slot]
        req = self._slot_req[slot]
        if self._recomputes:
            return self._restore_recurrent(slot, req)
        with tracing.span("serving.restore", "serving", rid=req.rid,
                          slot=slot, pos=ck.pos,
                          replayed=len(req.tokens) - len(ck.tokens)):
            req.tokens = list(ck.tokens)
            req.sent = len(req.tokens)
            self._pos[slot] = ck.pos
            self._cur[slot] = ck.cur
            self._slot_k[slot] = ck.slot_k
            self._slot_acc[slot] = ck.slot_acc
            pt = self._tables[slot]
            # pins cover the full blocks; the frontier block (if
            # ck.pos is not block-aligned) rides over from the
            # current table — it covered ck.pos at capture and
            # tables only grow, so it is still there
            keep = list(ck.pins)
            if pt is not None and ck.pos % self.block_size:
                keep.append(pt.blocks[ck.pos // self.block_size])
            npt = PageTable(self.block_size)
            for bid in keep:
                self._alloc.incref(bid)   # the new table's refs
            npt.extend_blocks(keep)
            npt.tokens = ck.pos
            if pt is not None:            # AFTER increfs: shared
                for bid in pt.blocks:     # bids must not hit 0
                    self._alloc.decref(bid)
            self._tables[slot] = npt
            if ck.wpins is not None:
                # the window group as it stood at capture: rows
                # past ck.pos in its blocks are rewritten by the
                # replay before any query sees them
                for bid in ck.wpins[1]:
                    self._walloc.incref(bid)
                self._free_window(self._wtables[slot])
                self._wtables[slot] = WindowTable(
                    self.block_size, self._win, *ck.wpins)
            if self._spec and self._draft_params is not None:
                self._draft_prefill(slot, req.prompt
                                    + req.tokens[:-1])
        self._flt_restored += 1

    def _restore_recurrent(self, slot: int, req: "_Request") -> None:
        """Restore of a slot whose layers keep a recurrent state: that
        state is a function of the tokens and cannot be rewound, so no
        snapshot of it is kept (no copy, no second buffer). The host
        holds every token the slot has landed (`_recover` flushed
        first): the state (a conv tail too where the kind has one),
        the latent or K/V rows and a sparse layer's index entries of
        prompt ++ tokens[:-1] are recomputed into a fresh scratch and
        spliced over the slot's row and blocks, and the slot goes on
        from the host's frontier with nothing to replay. One path for
        every recurrent kind (`transformer.RECURRENT_KINDS`) and for a
        two-grain table, whose rolls since cannot be taken back: its
        run is laid out anew for the host's frontier (more blocks than
        it holds now where the frontier lies ahead of a roll)."""
        seq = req.prompt + req.tokens[:-1]
        with tracing.span("serving.reprefill", "serving", rid=req.rid,
                          slot=slot, tokens=len(seq)):
            req.sent = len(req.tokens)
            self._pos[slot] = len(seq)
            self._cur[slot] = req.tokens[-1]
            pt = self._tables[slot]
            if self._eva:
                while len(pt.blocks) < pt.held(len(seq)):
                    pt.append_block(self._alloc_block())
                for bid in pt.rollback(pt.held(len(seq))
                                       * self.block_size):
                    self._alloc.decref(bid)
                pt.adopt(len(seq))
                wrow = self._eva_wrows(pt)
            else:
                wrow = (pt.as_row(self._maxb, self._trash),)
            self._pools, self._scales = self._paged_splice_prog()(
                self._pools, self._scales, self._reprefill(seq), wrow,
                np.int32(slot))
            self._reprefills += 1
        self._flt_restored += 1

    def _reprefill(self, seq: List[int]):
        """A recurrent model's restore recomputes: a fresh b=1 scratch
        with the cache state of `seq`, by re-running bucketed prefill over
        the known tokens (the caller splices it). No probe: the
        restore point already knows the feedback token."""
        scratch = self._fresh_scratch()
        done = 0
        while done < len(seq):
            n, width = self._next_chunk(done, len(seq) - done)
            scratch, _ = self._run_chunk(scratch, seq, done, n, width)
            done += n
        return scratch

    def _drop_pending(self, slot: int) -> _PendingPrefill:
        """Tear down one in-flight prefill (blocks decref'd, trace
        flow closed) and return it for requeue/restart."""
        p = self._pending.pop(slot)
        if p.flow is not None:
            tracing.flow_end(p.flow, "serving.prefill_chunks")
            p.flow = None
        if p.pt is not None:
            for bid in p.pt.blocks:
                self._alloc.decref(bid)
            p.pt = None
        self._free_window(p.wt)
        p.wt = None
        return p

    def _restart_pending(self, slot: int) -> None:
        """Faulted mid-chunked-prefill: drop the pending's scratch and
        blocks and start over from the prompt — `_start_prefill`
        re-matches the radix prefix, so the restart recomputes
        only what was never resident. OOM on the restart requeues the
        request instead of failing recovery."""
        p = self._drop_pending(slot)
        try:
            self._start_prefill(p.req, slot)
        except CacheOOM:
            self._queue.appendleft(p.req)

    def _recover(self, attempt: int, exc: BaseException) -> None:
        """sync_replay's on_retry hook: repair serving state after a
        step-level fault so the retry runs against a consistent world.
        Every injection site raises BEFORE its jit dispatch, so each
        BUFFERED step is a completed device op: flush first (those
        tokens are real), then rewind live slots to their checkpoints
        and restart in-flight prefills. Device-side mirrors of the
        per-slot host vectors reset and rebuild on the next dispatch.
        """
        t0 = time.monotonic()
        site = getattr(exc, "site", type(exc).__name__)
        if isinstance(exc, faultinject.InjectedFault) \
                and not isinstance(exc, faultinject.InjectedOOM):
            self._flt_injected += 1   # OOMs were counted at the ladder
        self._flt_retried += 1
        if site == "verify":
            self._verify_faults += 1
            if (self._spec and not self._spec_degraded
                    and self._verify_faults
                    >= self._max_verify_faults):
                # degradation ladder: repeated verify faults turn
                # speculation OFF — sequential steps emit the same
                # tokens (differential contract), only the
                # tokens-per-sync multiplier is lost
                self._spec = False
                self._spec_degraded = True
                self._flt_degraded += 1
                tracing.instant("serving.spec_degraded", "serving",
                                faults=self._verify_faults)
        self._flush()
        restored = 0
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None:
                continue
            ck = self._ckpt.get(s)
            if ck is not None and ck.rid == req.rid:
                self._restore_slot(s)
                restored += 1
            else:
                # unreachable while admission seeds a checkpoint, but
                # shedding beats decoding from corrupt state
                self._slot_req[s] = None
                self._drop_ckpt(s)
                self._release_slot(s, req)
                self._shed_req(req, RequestShedError(
                    req.rid, "no checkpoint to restore from"))
        for s in list(self._pending):
            self._restart_pending(s)
        self._cur_dev = None
        self._temp_dev = None
        self._keys_dev = None
        if restored:
            self._restored_by_site[site] = \
                self._restored_by_site.get(site, 0) + 1
            self._restore_hist.record(time.monotonic() - t0)

    def _shed_req(self, req: "_Request", err: HpxError) -> None:
        """Fail one request with a typed error, surfaced via `failed`
        (run() keeps returning successes only)."""
        with tracing.span("serving.shed", "serving", rid=req.rid,
                          reason=type(err).__name__):
            self.failed[req.rid] = err
            self._admit_defers.pop(req.rid, None)
            self._flt_shed += 1
        if not self._flight_mute:
            flight.record_fault("shed", site="serving",
                                rid=req.rid, error=err)

    def _shed_expired(self) -> None:
        """Deadline policy: a queued or still-prefilling request whose
        submit()-time deadline lapsed sheds NOW — overload fails fast
        with a typed error instead of starving the queue. Live decode
        slots are exempt: they already hold device state and their
        remaining tokens are the cheapest in the system."""
        now = time.monotonic()
        if any(r.t_deadline is not None for r in self._queue):
            keep: deque = deque()
            while self._queue:
                req = self._queue.popleft()
                if req.t_deadline is not None \
                        and now >= req.t_deadline:
                    self._shed_req(req, DeadlineExceededError(
                        req.rid, req.deadline_s))
                else:
                    keep.append(req)
            self._queue = keep
        for s, p in list(self._pending.items()):
            req = p.req
            if req.t_deadline is not None and now >= req.t_deadline:
                self._drop_pending(s)
                self._shed_req(req, DeadlineExceededError(
                    req.rid, req.deadline_s))

    def _shed_everything(self, exc: BaseException) -> None:
        """Step-retry budget exhausted: fail FAST and typed. Completed
        requests keep their results (the flush below finalizes any
        whose tokens were still buffered); every in-flight and queued
        request sheds into `failed` — run() terminates instead of
        spinning on a fault that recovery could not clear."""
        self._flush()
        reason = f"step retries exhausted ({exc})"
        # sync_replay already black-boxed this exhaustion (one
        # "retry-exhausted" bundle at the pre-unwind moment); mute the
        # per-request shed captures below so a bulk shed stays ONE
        # bundle, not one per request
        self._flight_mute = True
        try:
            for s in range(self.slots):
                req = self._slot_req[s]
                if req is None:
                    continue
                self._slot_req[s] = None
                self._drop_ckpt(s)
                self._release_slot(s, req)
                self._shed_req(req, RequestShedError(req.rid, reason))
            for s in list(self._pending):
                p = self._drop_pending(s)
                self._shed_req(p.req,
                               RequestShedError(p.req.rid, reason))
            while self._queue:
                q = self._queue.popleft()
                self._shed_req(q, RequestShedError(q.rid, reason))
        finally:
            self._flight_mute = False
        self._cur_dev = None
        self._temp_dev = None
        self._keys_dev = None

    # -- retirement ------------------------------------------------------

    def _maybe_retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        if req is None:
            return
        hit_eos = (req.eos_id is not None
                   and req.tokens[-1] == req.eos_id)
        if len(req.tokens) >= req.max_new or hit_eos:
            self._finalize(slot, req, hit_eos)

    def _finalize(self, slot: int, req: "_Request",
                  hit_eos: bool) -> None:
        """Retire one request: pad the eos tail exactly like
        generate()'s pinning, publish to _done, free the slot if it
        still holds this request (async max_new retires free it at
        dispatch time, before the token values arrive)."""
        if req.rid in self._done:
            return
        if hit_eos:
            # generate() keeps emitting pinned eos to max_new; the
            # slot retires early and pads the same tail
            req.tokens = req.tokens + [req.eos_id] * (
                req.max_new - len(req.tokens))
        with tracing.span("serving.retire", "serving",
                          rid=req.rid, slot=slot,
                          tokens=len(req.tokens), eos=hit_eos):
            self._done[req.rid] = req.tokens
            self.hist["e2e"].record(time.monotonic() - req.t_submit,
                                    rid=req.rid)
            self.timeline.event(req.rid, "retire",
                                tokens=len(req.tokens))
            if self._slot_req[slot] is req:
                self._slot_req[slot] = None
                self._drop_ckpt(slot)
                self._release_slot(slot, req)

    def _flush(self, keep: int = 0) -> None:
        """Materialize the buffered steps' token vectors, all but the
        newest `keep`, and replay the per-slot bookkeeping in dispatch
        order. With the seed-token reads (`_land_seeds`, one per
        admission, `serving.first_token.wait`) these are the ONLY
        device->host reads in the decode loop: one per buffered step
        (`serving.flush.wait`), oldest first, then one per step's MoE
        statistics (`serving.flush.moe_stats.wait`; the newest step's
        stay buffered with its tokens).

        `keep=1` is the decode loop's own read, made right after a
        step's dispatch for the retirement (or the full buffer) of the
        step BEFORE it: every read then has a decode step queued
        behind it and the device never waits for the host's next
        preparation. `keep=0` drains everything, the newest step last
        — the read that empties the dispatch queue: where a value is
        needed before the next dispatch (an eos check,
        async_dispatch=False, a speculative step), where nothing is
        live, in `_recover`, and for a caller (`flush()`, the
        benchmark's window edges).

        Also the knob actuation boundary: external config writes land
        (_reload_knobs) HERE, never mid-step."""
        self._land_seeds(behind=0)
        if not keep:
            self._read_due = False
        with tracing.span("serving.flush", "serving",
                          steps=len(self._buf)):
            while len(self._buf) > keep:
                nxt, lanes = self._buf.popleft()
                with self._wait("serving.flush.wait", len(self._buf)):
                    vals = np.asarray(nxt)
                for s, req in lanes:
                    t = int(vals[s])
                    req.tokens.append(t)
                    if self._slot_req[s] is req:    # else retired at
                        self._cur[s] = t            # dispatch: the slot
                                                    # may be another's
                    hit_eos = (req.eos_id is not None
                               and t == req.eos_id)
                    if hit_eos or len(req.tokens) >= req.max_new:
                        self._finalize(s, req, hit_eos)
            # MoE routing stats buffered by the step/verify programs:
            # one small [2+E] vector per dispatched step, read here so
            # the async window never gains an extra host sync
            while len(self._moe_buf) > keep:
                ms = self._moe_buf.popleft()
                with self._wait("serving.flush.moe_stats.wait",
                                len(self._moe_buf)):
                    ms = np.asarray(ms)
                self._moe_routed += float(ms[0])
                self._moe_dropped += float(ms[1])
                occ = ms[2:2 + len(self._moe_occ)]
                self._moe_occ = [float(v) for v in occ]
                # drop-free steps report 1.0 for an expert that was
                # hit (moe_ffn_serve), averaged over the sparse layers
                self._moe_hit_sum += float(occ.sum())
                self._moe_steps += 1
                if self.cfg.moe_n_group > 1:
                    self._moe_here += float(ms[-2])
                    self._moe_tokens_here += float(ms[-1])
            self._ckpt_sweep()
            self._reload_knobs()
            # SLO burn evaluation shares this boundary: the host's
            # state is consistent (at most the newest step in flight)
            if self._alerts is not None:
                self._alerts.maybe_tick()

    def _reload_knobs(self) -> None:
        """Propagate runtime config writes into the live server at
        the flush boundary. Cheap in the steady state: one generation
        read; the per-key compare only runs after a set() somewhere
        bumped the generation, and only keys whose raw value CHANGED
        are applied (constructor overrides survive unrelated writes).
        Values clamp to the baked ladders — the bucket ladder and
        smax are compile-time shape choices a live write cannot
        change."""
        from ..core.config import runtime_config
        rc = runtime_config()
        gen = rc.generation()
        if gen == self._cfg_gen:
            return
        self._cfg_gen = gen
        for key in _RELOADABLE_KNOBS:
            raw = rc.get(key)
            if raw == self._knob_raw[key]:
                continue
            self._knob_raw[key] = raw
            if raw is None or raw == "auto":
                continue
            if key == "hpx.serving.prefill_chunk":
                self.prefill_chunk = min(max(1, int(raw)),
                                         self.prefill_buckets[-1])
            elif key == "hpx.serving.max_async_steps":
                self._max_async = max(1, int(raw))
            elif key == "hpx.serving.ckpt_every":
                self._ckpt_every = max(1, int(raw))
            elif key == "hpx.serving.spec.k" and self._spec:
                self._spec_k = min(max(1, int(raw)),
                                   self.prefill_buckets[-1] - 1)
            elif key == "hpx.serving.moe.capacity_factor" \
                    and self.cfg.n_experts > 0:
                pct = int(raw)
                # 0 = auto = drop-free; the program cache re-keys on
                # the new percent (one compile per distinct value)
                self._moe_capacity_pct = (
                    self.cfg.n_experts * 100 if pct <= 0
                    else max(1, pct))
            elif key == "hpx.cache.radix_budget_blocks":
                self._radix.budget_blocks = max(1, int(raw))
            elif key == "hpx.cache.tier.host_budget_mb" \
                    and self._tier is not None:
                # shrink applies on the next demotion's LRU sweep
                self._tier.budget_bytes = max(1, int(raw)) << 20

    def _statusz(self) -> Dict[str, Any]:
        """This server's /statusz section (svc/opsplane provider):
        live queue/slot state, the SLO alert burn state and tier
        occupancy — host-only reads, no device sync
        (an ops scrape must never stall the decode loop)."""
        doc: Dict[str, Any] = {
            "kind": "server",
            "instance": self.counter_instance,
            "cache": {"free_blocks": self._alloc.free_count,
                      "num_blocks": self._alloc.num_blocks},
            "queue_depth": len(self._queue),
            "pending_prefills": len(self._pending),
            "live_slots": sum(1 for r in self._slot_req
                              if r is not None),
            "slots": self.slots,
            "done": len(self._done),
            "failed": len(self.failed),
            "tok_rate": float(self._rate.rate()),
            "timeline_rids": len(self.timeline),
        }
        if self._alerts is not None:
            doc["alerts"] = self._alerts.state()
        if self._tier is not None:
            doc["tier"] = self._tier.stats()
        return doc

    def step(self) -> bool:
        """Admit + one prefill chunk + one decode step for every live
        slot, wrapped in the recovery ladder. Returns True while any
        work remains (live slots, pending prefills, or queued
        requests).

        An injected/transient fault in the step body replays it up to
        ``hpx.serving.step_retries`` times through `sync_replay`;
        `_recover` runs before each retry (flush → restore slots from
        checkpoints → restart pendings), so the replay decodes the lost
        suffix against intact KV state and emits the SAME tokens the
        fault-free run would (differential contract). If the retry
        budget exhausts, every in-flight request sheds with a typed
        error into `failed` and the loop moves on.

        Every call closes one record of the step's account
        (`step_accounts()`): where its wall went, profiler on or off."""
        self._step_n += 1
        self._acct.begin(len(self._buf))
        try:
            return self._step_span()
        finally:
            self._acct.end(
                self._step_n, self.slots - self._slot_req.count(None),
                self._admits, self._chunks, self._prog_misses,
                self._reads_draining, self._reads_overlapped,
                self._latent_walked, self._latent_coalesced)

    def _step_span(self) -> bool:
        """step()'s body, inside its `serving.step` span."""
        with tracing.span("serving.step", "serving", n=self._step_n):
            self._shed_expired()
            # decode-stall feed: the gap between consecutive step()
            # entries while the PREVIOUS step left live slots — the
            # inter-token latency a streaming client would observe
            now = time.monotonic()
            if self._stall_live and self._last_step_t is not None:
                # the stall is shared by every live slot; attribute
                # the exemplar to the first live rid (deterministic
                # pick — any of them observed this inter-token gap)
                stall_rid = next((r.rid for r in self._slot_req
                                  if r is not None), None)
                self.hist["decode_stall"].record(
                    now - self._last_step_t, rid=stall_rid)
            self._last_step_t = now
            try:
                return sync_replay(
                    self._step_retries, self._step_inner,
                    retry_on=(faultinject.InjectedFault, CacheOOM),
                    on_retry=self._recover,
                    backoff_s=self._retry_backoff_s)
            except (faultinject.InjectedFault, CacheOOM) as e:
                self._shed_everything(e)
                return bool(self._queue or self._pending)
            finally:
                self._stall_live = any(r is not None
                                       for r in self._slot_req)

    def _step_inner(self) -> bool:
        try:
            self._admit()
            self._prefill_tick()
        finally:
            # what follows (a recovery's dispatches too) is every slot's
            self._rid = None
        live = [s for s in range(self.slots)
                if self._slot_req[s] is not None]
        if not live:
            self._flush()
            return bool(self._queue or self._pending)
        if self._spec:
            with tracing.span("serving.decode", "serving",
                              live=len(live), spec=True):
                self._spec_step(live)
            return True
        with tracing.span("serving.decode", "serving", live=len(live)):
            # fault site "decode": before the step dispatch and before
            # any host bookkeeping commits — at this point every
            # BUFFERED step already completed on device, so recovery's
            # flush-then-restore loses nothing
            faultinject.check("decode")
            t0 = self._acct.work_clock()
            with tracing.span("serving.decode.operands", "serving",
                              live=len(live)):
                # dead slots' tables are all-trash, so their writes
                # land in the reserved trash block instead of a
                # recycled live block. Dead slots' feedback tokens are
                # stale argmax/sample outputs — always valid ids.
                tok = self._feedback()
                temp, keys = self._lanes()
                pos = np.array(self._pos, np.int32)
                for s in live:
                    self._ensure_block(s, self._pos[s])
                tables = self._tables_dev()
            self._acct.eager_ns += self._acct.work_clock() - t0
            self._pools, self._scales, nxt, ms = \
                self._paged_step_prog()(
                    self.params, self._pools, self._scales, tok, pos,
                    tables, temp, keys)
            if ms is not None:
                self._moe_buf.append(ms)
            if "sparse" in self._kinds:
                self._sparse_account(pos[live])
            if self._eva:
                self._eva_account(pos[live])
            if "mla" in self._kinds:
                walked, coalesced = self._latent_entries(
                    self.live_positions())
                self._latent_walked += walked
                self._latent_coalesced += coalesced
            self._cur_dev = nxt
            self._rate.mark(float(len(live)))
            lanes = []
            need_sync, retired = not self._async, False
            for s in live:
                req = self._slot_req[s]
                assert req is not None
                lanes.append((s, req))
                self._pos[s] += 1
                req.sent += 1
                if req.eos_id is not None:
                    # the eos check needs this step's VALUE before the
                    # next dispatch — retire timing must not drift
                    need_sync = True
                elif req.sent >= req.max_new:
                    # bookkeeping retire at dispatch: the slot frees
                    # NOW (admissible next step); token values land at
                    # the read this asks for, in the next step()
                    self._slot_req[s] = None
                    self._drop_ckpt(s)
                    self._release_slot(s, req)
                    retired = True
            self._buf.append((nxt, lanes))
            if self._eva:
                for s in live:
                    if self._slot_req[s] is not None \
                            and self._pos[s] % self.cfg.eva_window == 0:
                        self._eva_roll(s)
            # every program of this step is enqueued: now the reads.
            # The seed tokens of this step's admissions first, then —
            # one step late, so that this step runs meanwhile — what
            # the step before asked for; all of it at once only where
            # a value decides the next dispatch
            self._land_seeds(behind=1)
            if need_sync:
                self._flush()
            else:
                if self._read_due:
                    self._flush(keep=1)
                self._read_due = (retired
                                  or len(self._buf) >= self._max_async)
        return True

    def _sparse_account(self, pos: np.ndarray) -> None:
        """What one decode step's sparse layers choose and walk, a
        layer and kv group, from the live slots' positions ALONE (the
        device's selection is never read): a query at p reads every
        block while p + 1 <= `sparse_dense_len`, else `sparse_topk` of
        them, all whole but the last, which holds p % block + 1 rows.
        Feeds `cache_stats()` `sparse_*` and the
        /serving{...}/sparse/* counters."""
        cfg = self.cfg
        bs = cfg.sparse_block
        blocks = pos // bs + 1
        chosen = np.where(pos + 1 <= cfg.sparse_dense_len, blocks,
                          np.minimum(blocks, cfg.sparse_topk))
        self._sparse_steps += 1
        self._sparse_blocks += int(chosen.sum())
        self._sparse_rows_walked += int(
            ((chosen - 1) * bs + pos % bs + 1).sum())
        self._sparse_rows_live += int((pos + 1).sum())

    def run(self) -> Dict[int, List[int]]:
        """Drive step() until every submitted request finishes; returns
        {request_id: tokens} (each exactly generate()'s output).
        Requests shed by deadline/overload/retry-exhaustion are NOT in
        the result — their typed errors are in `self.failed`."""
        while self.step():
            pass
        return self.poll_finished()

    # -- for a caller that drives step() itself --------------------------

    def flush(self) -> None:
        """Land every buffered step's tokens on the host now (one
        blocking read a step); step() does so by itself at each eos
        check, and one step after each retirement and every
        `hpx.serving.max_async_steps` steps (all but the step just
        dispatched)."""
        self._flush()

    def poll_finished(self) -> Dict[int, List[int]]:
        """Hand out {request_id: tokens} of the requests finished since
        the last call, and forget them (run() returns through here). A
        request's tokens reach the host at a flush, so a request whose
        last step is still buffered (the step() after its last
        dispatch reads it) is not in the result yet."""
        out, self._done = self._done, {}
        return out

    def recurrent_state(self, slot: int):
        """(tokens, state) of a live slot on a recurrent model: the
        token ids its recurrent state has consumed (prompt ++ every
        landed token but the last, which is fed next) and the float32
        state of the model's FIRST recurrent layer, read from the
        device: [H, d, d], or a "mamba" layer's in the PUBLISHED
        orientation [d_inner, d_state] (the cache keeps the channels on
        the minor axis). For a caller that checks the state against a
        recomputation; call `flush()` first, so that no step is in
        flight and the host's frontier is the device's."""
        req = self._slot_req[slot]
        if not self._recurrent or req is None or self._buf:
            raise ValueError("recurrent_state() needs a recurrent model, "
                             "a live slot and no step in flight "
                             "(flush() first)")
        li = next(i for i, k in enumerate(self.cfg.layer_mixer)
                  if k in RECURRENT_KINDS)
        state = np.asarray(self._pools[li][0][slot])
        return (req.prompt + req.tokens[:-1],
                state.T if self.cfg.mixer(li) == "mamba" else state)

    def sparse_selection(self, slot: int):
        """(tokens, ids, count) of a live slot on a model with sparse
        layers: the token ids the LAST decode step's query had behind
        it and was (prompt ++ every landed token but the last: the
        query is the last of these, at position len - 1), and the
        blocks the model's FIRST sparse layer chose for it, ids [n_kv,
        K] int32 ascending (`count` [n_kv] of them real), read from the
        device where the step left them. For a caller that checks the
        selection against a recomputation; `flush()` first."""
        req = self._slot_req[slot]
        if "sparse" not in self._kinds or req is None or self._buf \
                or len(req.tokens) < 2:
            raise ValueError("sparse_selection() needs a model with "
                             "sparse layers, a live slot that has decoded "
                             "and no step in flight (flush() first)")
        li = self.cfg.layer_mixer.index("sparse")
        return (req.prompt + req.tokens[:-1],
                np.asarray(self._pools[li][3][slot]),
                np.asarray(self._pools[li][4][slot]))

    def eva_summaries(self, slot: int):
        """(tokens, k~, v~) of a live slot on a model with "eva"
        layers: the token ids the slot has consumed (prompt ++ every
        landed token but the last, which is fed next) and the summary
        rows of the model's FIRST eva layer that the slot's table
        makes visible, [visible, H, hd] each in chunk order, read from
        the device (visible = eva_window / eva_chunk x the windows the
        tokens complete). For a caller that checks the summaries
        against a recomputation; `flush()` first."""
        req = self._slot_req[slot]
        if not self._eva or req is None or self._buf:
            raise ValueError("eva_summaries() needs a model with eva "
                             "layers, a live slot and no step in flight "
                             "(flush() first)")
        li = self.cfg.layer_mixer.index("eva")
        pt = self._tables[slot]
        bids = np.asarray([pt.blocks[:pt.summary]], np.int32)
        return (req.prompt + req.tokens[:-1],
                *(np.asarray(gather_block_kv(p, bids)[0])
                  for p in self._pools[li]))

    def live_positions(self) -> Dict[int, int]:
        """{slot: next write position} of every live slot: what the
        next decode step attends over (each slot reads its positions
        0..p-1 and writes p). Host-only, no device read."""
        return {s: self._pos[s] for s in range(self.slots)
                if self._slot_req[s] is not None}
