"""Gather-based paged decode attention over block tables.

The device side of the `hpx_tpu/cache` subsystem: K/V for every
request lives in one preallocated per-layer pool of fixed-size blocks
(`[num_blocks, n_kv, block_size, head_dim]` — heads ahead of rows, so
one head of one block is a contiguous (block_size, head_dim) tile, the
shape the fused kernels stream), and a per-step int32
block table (`cache/page_table.py`) maps each slot's logical positions
to physical blocks. The layout rule for every write into a pool: index
EVERY axis ahead of `head_dim` (block, kv head, row — the update window
is then one contiguous `head_dim` row), never a slice between two
indexed axes. The fused kernels pin their pool operands to the pool's
own `{3,2,1,0}` layout; `pool.at[bidx, :, row]` makes the chip's
compiler run the scatter in `{3,1,2,0}`, which costs two whole-pool
copies per pool, layer and step (`tests/test_chip_compile.py` guards
it). Whole-block writes (`pool.at[bids]`) index a prefix of the axes
and are fine.
This module is pure jit-safe array plumbing — no
host state, no syncs — so the serving layer can compose it with its
projections while the numerics stay in one place.

Numerical contract: `paged_decode_attention` is element-for-element the
attention core of `models/serving._block_decode_rows` — same einsum
contractions, same contraction lengths (`max_blocks * block_size` rows
gathered in logical order == the dense `smax` rows), same -inf mask and
f32 softmax. Rows past a slot's position are masked to exact-zero
probability, so the garbage content of pad/trash blocks contributes
exactly 0.0 — paged and dense servers emit byte-identical tokens.

The gather materializes a `[B, S, n_kv, head_dim]` view per layer —
the XLA-oracle formulation, and the DESIGNATED oracle module: hpxlint
HPX010 flags `pool[table]`-shaped gathers anywhere else in the serving
hot paths. The fused Pallas kernels that walk the block table in VMEM
(`ops/attention_pallas.fused_paged_attention` and its O(block)-scratch
online-softmax sibling `fused_paged_online_attention`) are the
production decode paths; `fused=True` / `fused="online"` on the two
attention entry points routes through them, and the gather formulation
here is what both are tested against (exact tokens; ulp-tight logits
for `fused`, tolerance-budgeted for `fused="online"` — see the
kernels' numerics contracts).

Quantized KV (`hpx.cache.kv_dtype=int8` or `fp8`): pools store
quantized blocks with per-(block, kv-head) symmetric-absmax scales in
a sibling `[num_blocks, n_kv]` f32 array (the scheme of
`models/quant.py`, applied per block instead of per output channel —
paged blocks make per-block mixed precision natural). int8 rounds onto
the 127-level integer ladder; fp8 (e4m3) scales the block absmax onto
±448 and lets the float8 cast round — both 1 byte/elem. The `*_q`
scatter variants pick the grid off the pool's dtype, so every code
path below serves both. Writes quantize at the frontier: the `*_q`
variants read-modify-write the touched block (dequantize with the old
scale, insert the new rows, recompute the block's absmax, requantize).
Requantization of UNTOUCHED rows is exact whenever the block absmax
didn't move (int8: max|q| == 127 by construction so the recomputed
scale is bit-identical; fp8: the e4m3 cast of an unchanged quotient
reproduces itself), and bounded by one rounding step when it did. The
gather side dequantizes with the same elementwise ops the kernels use
at their VMEM boundary ((q * scale).astype(compute)), so
gather-quantized and fused-quantized agree exactly like their bf16
twins.

Sharded serving (shard_map on a (dp, tp) mesh): every function here is
written against LOCAL shapes only — `n_kv` and `n_q` are read off the
arrays, GQA group size is `n_q // n_kv`, and block ids index the pool's
block axis directly — so the same code runs per-shard unchanged. The
serving layer shards pools/scales over tp on the kv-head axis and
REPLICATES the block axis over dp (`BlockAllocator.pool_pspec`), which
is exactly what keeps each shard's `pool[table]` gather shard-local:
tables carry global block ids, and every id resolves on every dp
shard. The replicas stay equal because every shard applies every
slot's write (the attention entry points' `write=`; the serving layer
gathers the rows). Nothing in this module may introduce a cross-shard
collective.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..models.quant import _quantize, _quantize_fp8
from .attention_pallas import (_live, _ring_kpos, fused_latent_attention,
                               fused_paged_attention,
                               fused_paged_online_attention)

__all__ = [
    "block_rows",
    "gather_block_kv",
    "latent_takes_kernel",
    "paged_decode_attention",
    "paged_latent_attention",
    "paged_window_attention",
    "quantize_blocks",
    "scatter_blocks",
    "scatter_blocks_q",
    "scatter_seq_blocks",
    "scatter_seq_blocks_q",
    "scatter_token",
    "scatter_token_q",
    "scatter_window",
    "scatter_window_q",
]


def block_rows(x: jax.Array) -> jax.Array:
    """Pool blocks `[..., n_kv, block_size, head_dim]` <-> token rows
    `[..., block_size, n_kv, head_dim]` (its own inverse). Everything
    outside the pools — scratch caches, KV segments, host-tier entries
    — keeps token-row order; this is the one crossing."""
    return jnp.swapaxes(x, -3, -2)


def gather_block_kv(pool: jax.Array, table: jax.Array,
                    scale: jax.Array = None,
                    out_dtype=None) -> jax.Array:
    """Materialize logical K or V rows from a block pool.

    pool: [num_blocks, n_kv, block_size, head_dim]; table: [B,
    max_blocks] int32. Returns [B, max_blocks * block_size, n_kv,
    head_dim] — slot b's logical row p at index p (pad blocks yield
    garbage rows the causal mask must exclude).

    For quantized (int8/fp8) pools pass `scale` ([num_blocks, n_kv]
    f32) and the compute `out_dtype`: blocks dequantize with the same
    elementwise ops the fused kernels apply at their VMEM boundary
    ((q * scale).astype(out_dtype)), keeping the quantized paths
    exactly comparable."""
    g = pool[table]                       # [B, maxb, nkv, bs, hd]
    b, m, n, s, h = g.shape
    if scale is not None:
        sc = scale[table]                 # [B, maxb, nkv]
        g = (g.astype(jnp.float32) * sc[:, :, :, None, None]).astype(
            out_dtype if out_dtype is not None else jnp.bfloat16)
    return block_rows(g).reshape(b, m * s, n, h)


def quantize_blocks(rows: jax.Array, dtype=jnp.int8):
    """Symmetric-absmax quantization per (block, kv-head): pool-layout
    blocks [..., n_kv, block_size, head_dim] -> (quantized blocks,
    scales [..., n_kv] f32). `dtype` picks the grid — jnp.int8
    (127-level integer ladder) or jnp.float8_e4m3fn (e4m3 float grid,
    block absmax mapped onto ±448); anything else is a loud error,
    never a silent fallback.
    Zero blocks get scale 1.0 (models/quant's convention), so fresh
    pools roundtrip exactly."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.int8):
        qt = _quantize(rows, axes=(-2, -1))
    elif dt == jnp.dtype(jnp.float8_e4m3fn):
        qt = _quantize_fp8(rows, axes=(-2, -1))
    else:
        raise ValueError(
            f"quantize_blocks: unsupported pool dtype {dt} (expected "
            "int8 or float8_e4m3fn)")
    return qt.q, jnp.squeeze(qt.s, axis=(-2, -1))


def scatter_token(pool: jax.Array, table: jax.Array, pos: jax.Array,
                  val: jax.Array, ring: bool = False) -> jax.Array:
    """Write one token row per slot into the pool.

    pool: [num_blocks, n_kv, block_size, head_dim]; table: [B,
    max_blocks]; pos: [B] int32 logical positions; val: [B, n_kv,
    head_dim]. Slot b's row lands at (table[b, pos[b]//bs], :,
    pos[b]%bs) — dead slots point their whole table at a reserved
    trash block, so their masked lanes scatter harmlessly. Block, kv
    head and row are indexed together (the module's layout rule).
    `ring`: the table is a window group's ring, logical block b in
    column b % max_blocks."""
    nkv, bs = pool.shape[1], pool.shape[2]
    rows = jnp.arange(table.shape[0])
    col = pos // bs
    bidx = table[rows, col % table.shape[1] if ring else col]
    return pool.at[bidx[:, None], jnp.arange(nkv)[None, :],
                   (pos % bs)[:, None]].set(val)


def scatter_window(pool: jax.Array, table: jax.Array, pos0: jax.Array,
                   vals: jax.Array) -> jax.Array:
    """Write a W-token window of rows per slot into the pool.

    pool: [num_blocks, n_kv, block_size, head_dim]; table: [B,
    max_blocks]; pos0: [B] int32 first logical position per slot; vals:
    [B, W, n_kv, head_dim]. Slot b's window row i lands at
    (table[b, (pos0[b]+i)//bs], :, (pos0[b]+i)%bs) — the speculative
    verify scatter, where the tail of a slot's window may run past its
    mapped (or even mappable) range.

    Out-of-range positions must DROP, never clamp: a clamped table
    gather (`min(p//bs, max_blocks-1)`) lands on the row's LAST column,
    which for a fully-mapped table is a REAL block — a clamped write
    would corrupt a live logical position ~block_size tokens back. So
    positions past the table's extent are routed to block index
    `num_blocks` (one past the pool) and the scatter uses
    ``mode="drop"``."""
    nb, nkv, bs = pool.shape[:3]
    b, w = vals.shape[0], vals.shape[1]
    rows = jnp.arange(b)[:, None]
    p = pos0[:, None] + jnp.arange(w)[None, :]          # [B, W]
    maxb = table.shape[1]
    bidx = table[rows, jnp.minimum(p // bs, maxb - 1)]
    bidx = jnp.where(p < maxb * bs, bidx, nb)           # OOB -> dropped
    return pool.at[bidx[:, :, None], jnp.arange(nkv)[None, None, :],
                   (p % bs)[:, :, None]].set(vals, mode="drop")


def scatter_token_q(pool_q: jax.Array, scales: jax.Array,
                    table: jax.Array, pos: jax.Array,
                    val: jax.Array):
    """`scatter_token` for quantized pools: read-modify-write the
    frontier block. pool_q int8/fp8 [num_blocks, n_kv, block_size,
    head_dim] (its dtype picks the requantization grid); scales f32
    [num_blocks, n_kv]; val [B, n_kv, head_dim] full-precision.
    Returns (pool_q, scales).

    Each slot's frontier block is gathered (B blocks, not the full
    table — bounded RMW traffic), dequantized with its old scale, the
    new row inserted, and the block requantized under its fresh absmax.
    Live slots own their frontier block exclusively (the COW guard
    forks shared blocks before the frontier reaches them), so the RMW
    never races a neighbour; dead slots all point at the trash block,
    whose duplicate writes are garbage-on-garbage.

    Out-of-range positions DROP, never clamp, for the same reason as
    `scatter_window`: both the block write and the scale write are
    routed to block index num_blocks and dropped, so an OOB row can
    neither corrupt a live block nor skew its scale."""
    nb, bs = pool_q.shape[0], pool_q.shape[2]
    maxb = table.shape[1]
    rows = jnp.arange(table.shape[0])
    bidx = table[rows, jnp.minimum(pos // bs, maxb - 1)]
    blk = pool_q[bidx]                    # [B, nkv, bs, hd] int8
    scl = scales[bidx]                    # [B, nkv]
    deq = blk.astype(jnp.float32) * scl[:, :, None, None]
    deq = deq.at[rows, :, pos % bs].set(val.astype(jnp.float32))
    q8, s_new = quantize_blocks(deq, pool_q.dtype)
    bidx = jnp.where(pos < maxb * bs, bidx, nb)         # OOB -> dropped
    pool_q = pool_q.at[bidx].set(q8, mode="drop")
    scales = scales.at[bidx].set(s_new, mode="drop")
    return pool_q, scales


def scatter_window_q(pool_q: jax.Array, scales: jax.Array,
                     table: jax.Array, pos0: jax.Array,
                     vals: jax.Array):
    """`scatter_window` for quantized pools: W sequential frontier
    RMWs.

    vals [B, W, n_kv, head_dim]. The window's rows land one at a time
    (a Python-unrolled W-step chain, W is static and small) because
    consecutive rows often share a block: parallel RMWs would each
    start from the ORIGINAL block and the last writer would erase its
    siblings' rows. Sequencing makes row i's RMW see rows < i — the
    quantized analog of `scatter_window`'s in-order semantics, with
    the same OOB-drop contract per row. Returns (pool_q, scales)."""
    for i in range(vals.shape[1]):
        pool_q, scales = scatter_token_q(pool_q, scales, table,
                                         pos0 + i, vals[:, i])
    return pool_q, scales


def scatter_blocks_q(pool_q: jax.Array, scales: jax.Array,
                     bids: jax.Array, rows: jax.Array):
    """`scatter_blocks` for quantized pools: whole blocks quantize in
    one shot (no RMW — the writes fully replace their targets).
    Returns (pool_q, scales)."""
    q8, s = quantize_blocks(block_rows(rows), pool_q.dtype)
    return pool_q.at[bids].set(q8), scales.at[bids].set(s)


def scatter_seq_blocks_q(pool_q: jax.Array, scales: jax.Array,
                         table_row: jax.Array, rows: jax.Array):
    """`scatter_seq_blocks` for quantized pools (the chunked-prefill
    splice): every block of one sequence quantizes whole. Trash-pad
    duplicates behave exactly as in the bf16 splice — garbage blocks
    get garbage scales, gathered only under exact-zero masks. Returns
    (pool_q, scales)."""
    q8, s = quantize_blocks(block_rows(rows), pool_q.dtype)
    return (pool_q.at[table_row].set(q8),
            scales.at[table_row].set(s))


def scatter_blocks(pool: jax.Array, bids: jax.Array,
                   rows: jax.Array) -> jax.Array:
    """Bulk-write whole blocks (prefill splice): bids [n] int32, rows
    [n, block_size, n_kv, head_dim] in token-row order."""
    return pool.at[bids].set(block_rows(rows).astype(pool.dtype))


def scatter_seq_blocks(pool: jax.Array, table_row: jax.Array,
                       rows: jax.Array) -> jax.Array:
    """Write ONE sequence's whole padded block row back into the pool
    (the chunked-prefill splice): table_row [max_blocks] int32 as
    produced by `PageTable.as_row`, rows [max_blocks, block_size,
    n_kv, head_dim] from its contiguous b=1 scratch cache.

    The row's tail entries are the server's trash-block pad, so the
    scatter carries DUPLICATE indices there; which garbage write wins
    is unspecified and irrelevant — trash rows are only ever gathered
    under an exact-zero mask. Real block ids are unique within a row
    (the allocator hands each out once), so live blocks get exactly
    their own scratch rows."""
    return pool.at[table_row].set(block_rows(rows).astype(pool.dtype))


def paged_decode_attention(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, table: jax.Array,
                           pos: jax.Array, k_scale: jax.Array = None,
                           v_scale: jax.Array = None,
                           fused=False, interpret=None, write=None,
                           window: int = 0):
    """One decode step of attention over paged K/V.

    q: [B, 1, n_q, head_dim] (post-rope); k_new/v_new: [B, n_kv,
    head_dim] this step's K/V rows (post-rope — pools store post-rope
    K exactly like the dense caches); table: [B, max_blocks] int32;
    pos: [B] int32 write/attend positions. Returns (att [B, 1, n_q,
    head_dim], k_pool, v_pool) with the new rows written — write
    precedes the attention so each slot attends its own fresh token
    (the mask is `<= pos`, inclusive).

    `fused=True` routes the attention through the bitwise Pallas
    block-table kernel instead of the gather formulation;
    `fused="online"` routes through the O(block)-scratch online-softmax
    variant (tolerance-budgeted — see its numerics contract). Same
    writes either way. Quantized (int8/fp8) pools pass k_scale/v_scale
    ([num_blocks, n_kv] f32): the new rows quantize at write time
    (frontier RMW, grid picked off the pool dtype) and the return grows
    to (att, k_pool, v_pool, k_scale, v_scale).

    `write=(table, pos)` names the WRITE side's table and positions
    where they are not the attend side's: sharded serving hands every
    dp shard the rows of ALL slots (k_new/v_new then carry that larger
    batch) so each replica of the pool takes every write and the
    replicas stay equal, while each shard attends its own slots.

    `window` > 0: a WINDOW layer over its group's ring table ([B,
    ring]; `attention_pallas._ring_kpos`): the new row lands in ring
    column (pos // bs) % ring, and only rows at positions > pos -
    window are attended; the fused call is `hpx_paged_fused_win`."""
    quant = k_scale is not None
    wtable, wpos = (table, pos) if write is None else write
    if quant:
        if window:
            raise NotImplementedError(
                "a quantized K/V pool under a window group's ring table "
                "(ops/paged_attention.scatter_token_q reads its block "
                "by logical column)")
        k_pool, k_scale = scatter_token_q(k_pool, k_scale, wtable,
                                          wpos, k_new)
        v_pool, v_scale = scatter_token_q(v_pool, v_scale, wtable,
                                          wpos, v_new)
    else:
        k_pool = scatter_token(k_pool, wtable, wpos, k_new, bool(window))
        v_pool = scatter_token(v_pool, wtable, wpos, v_new, bool(window))
    if fused:
        fpa = (fused_paged_online_attention if fused == "online"
               else fused_paged_attention)
        att = fpa(q, k_pool, v_pool, table, pos,
                  k_scale=k_scale, v_scale=v_scale,
                  interpret=interpret, window=window)
    else:
        kc = gather_block_kv(k_pool, table, k_scale, q.dtype)
        vc = gather_block_kv(v_pool, table, v_scale, q.dtype)
        b, _, nq, hd = q.shape
        nkv = kc.shape[2]
        g = nq // nkv
        qg = q.reshape(b, 1, nkv, g, hd)
        s = jnp.einsum("bqngh,bknh->bngqk", qg, kc) / math.sqrt(hd)
        kpos = jnp.arange(kc.shape[1])
        if window:
            bs = k_pool.shape[2]
            kpos = _ring_kpos(kpos[None, :] // bs, kpos[None, :] % bs,
                              pos[:, None], bs, table.shape[1])
            live = _live(kpos, pos[:, None], window)
        else:
            live = kpos[None, :] <= pos[:, None]        # [B, S]
        s = jnp.where(live[:, None, None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1
                           ).astype(q.dtype)
        att = jnp.einsum("bngqk,bknh->bqngh", p, vc).reshape(
            q.shape[0], 1, nq, hd)
    if quant:
        return att, k_pool, v_pool, k_scale, v_scale
    return att, k_pool, v_pool


def paged_window_attention(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, table: jax.Array,
                           pos0: jax.Array, k_scale: jax.Array = None,
                           v_scale: jax.Array = None,
                           fused=False, interpret=None, write=None,
                           window: int = 0):
    """W-token speculative-verify attention over paged K/V.

    q: [B, W, n_q, head_dim] (post-rope); k_new/v_new: [B, W, n_kv,
    head_dim] the window's K/V rows; table: [B, max_blocks]; pos0: [B]
    int32 first position per slot (window row i sits at pos0+i).
    Returns (att [B, W, n_q, head_dim], k_pool, v_pool) — plus the
    updated scales when k_scale/v_scale are given, exactly like
    `paged_decode_attention`; `fused=True` routes through the bitwise
    Pallas block-table kernel and `fused="online"` through the
    online-softmax variant (both share the per-window-row horizon
    mask).

    Per-query causal horizon: window row i attends positions
    `<= pos0 + i` — exactly the horizon W sequential `scatter_token` +
    `paged_decode_attention` steps would see, so the verify logits are
    byte-identical to the sequential decode the window replaces.
    Rejected draft rows stay in the pool as garbage, which is safe for
    the same write-precedes-gather reason as the dense scratch tail:
    a position is only ever attended once the frontier reaches it, and
    the frontier only advances past freshly (re)written rows. Under
    quantized pools that garbage ALSO sits under the block's absmax until
    rewritten — rejected rows can widen their block's scale, which
    costs the block's live rows at most one extra requantization
    rounding, identically on the gather and fused paths. `write=` as
    in `paged_decode_attention`."""
    if window:
        raise NotImplementedError(
            "a W-token verify window over a window group's ring table "
            "(ops/paged_attention.scatter_window writes by logical "
            "column, and a ring of ceil(window / block) + 2 blocks "
            "cannot hold window + W rows)")
    quant = k_scale is not None
    wtable, wpos0 = (table, pos0) if write is None else write
    if quant:
        k_pool, k_scale = scatter_window_q(k_pool, k_scale, wtable,
                                           wpos0, k_new)
        v_pool, v_scale = scatter_window_q(v_pool, v_scale, wtable,
                                           wpos0, v_new)
    else:
        k_pool = scatter_window(k_pool, wtable, wpos0, k_new)
        v_pool = scatter_window(v_pool, wtable, wpos0, v_new)
    if fused:
        fpa = (fused_paged_online_attention if fused == "online"
               else fused_paged_attention)
        att = fpa(q, k_pool, v_pool, table, pos0,
                  k_scale=k_scale, v_scale=v_scale,
                  interpret=interpret)
    else:
        kc = gather_block_kv(k_pool, table, k_scale, q.dtype)
        vc = gather_block_kv(v_pool, table, v_scale, q.dtype)
        b, w, nq, hd = q.shape
        nkv = kc.shape[2]
        g = nq // nkv
        qg = q.reshape(b, w, nkv, g, hd)
        s = jnp.einsum("bqngh,bknh->bngqk", qg, kc) / math.sqrt(hd)
        kpos = jnp.arange(kc.shape[1])
        posw = pos0[:, None] + jnp.arange(w)[None, :]   # [B, W]
        live = kpos[None, None, :] <= posw[:, :, None]  # [B, W, S]
        s = jnp.where(live[:, None, None, :, :], s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1
                           ).astype(q.dtype)
        att = jnp.einsum("bngqk,bknh->bqngh", p, vc).reshape(
            b, w, nq, hd)
    if quant:
        return att, k_pool, v_pool, k_scale, v_scale
    return att, k_pool, v_pool


def latent_takes_kernel(fused, rank: int) -> bool:
    """Whether `paged_latent_attention` takes `hpx_mla_paged`: asked
    for (`fused`: any of the fused kernels' names) and the value slice
    is whole lanes. Decided HERE, from the operands."""
    return bool(fused) and rank % 128 == 0


def paged_latent_attention(q: jax.Array, row_new: jax.Array,
                           pool: jax.Array, table: jax.Array,
                           pos: jax.Array, *, rank: int, scale: float,
                           fused=False, interpret=None):
    """One decode step of ABSORBED latent attention (MLA) over a paged
    pool of latent rows: one cached head, whose value is its own first
    `rank` columns, under every query head.

    q: [B, H, R] (W_uk^T q^C, the plain q^R dims, zeros up to R);
    row_new: [B, R] this step's row (c, r, zero pad); pool:
    [num_blocks, 1, block_size, R]; table: [B, max_blocks] (the FULL
    group's); pos: [B] int32. Returns (sum_j p_j c_j [B, H, rank],
    pool) with the new row written first (`scatter_token`: block, the
    one head and row indexed together, the module's layout rule), so a
    slot attends its own token.

    `fused` (any of the fused kernels' names) takes `hpx_mla_paged`,
    the table walk bounded by the slot's live length, where the value
    slice is whole lanes (`latent_takes_kernel`: rank % 128 == 0);
    every other call the gather form below, the kernel's
    oracle: the same float32 scores, mask, softmax and the
    probabilities rounded to the rows' type ahead of the value
    product."""
    pool = scatter_token(pool, table, pos, row_new[:, None, :])
    if latent_takes_kernel(fused, rank):
        return fused_latent_attention(q, pool, table, pos, rank=rank,
                                      scale=scale,
                                      interpret=interpret), pool
    lat = gather_block_kv(pool, table)[:, :, 0]          # [B, S, R]
    s = jnp.einsum("bhr,bkr->bhk", q, lat.astype(q.dtype),
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(lat.shape[1])[None, :] <= pos[:, None]
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, -jnp.inf), axis=-1)
    v = jnp.where(live[:, :, None], lat[..., :rank], 0)
    o = jnp.einsum("bhk,bkr->bhr", p.astype(q.dtype), v.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype), pool
