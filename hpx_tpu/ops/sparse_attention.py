"""Learned block-sparse attention (InfLLM-v2, arXiv:2506.07900): what a
query reads of its K/V cache is decided by the query.

Beside a layer's K and V there is an INDEX: the mean key of every
`stride` rows and kv head (`block_means`; in the paged cache a pool of
its own on the same block table, `[num_blocks, block / stride * n_kv,
head_dim]` float32: one 8 x 128 tile a block at 2 kv heads). A
compressed key is the mean of `kernel` = 2 x `stride` rows, i.e. of two
adjacent index entries, so nothing is re-read to build it. For a query
at position t and a kv group g (`select`):

    s_j = sum_{h in g} softmax_j(q_h . kbar_j / sqrt(d))   over the
          windows that END at or before t, float32
    a block of `block` rows scores the max of s_j over the windows
    that overlap it; block 0 (`init`) and the blocks that cover the
    last `local` rows are forced; the best others fill up to `topk`;
    while t + 1 <= `dense_len` every block is read.

`select` returns the chosen block ids SORTED ASCENDING and their count:
the last one is then the block that holds row t, the only one a causal
mask cuts.

Two readers:

  `chunk_attention`   a prefill chunk over the b=1 scratch's dense K/V:
                      every row selects for itself; the attention is
                      walked in blocks of rows under an online softmax,
                      bounded by the chunk's last position, each score
                      masked by its row's own selection. No [H, W, S]
                      array exists. (A masked dense walk: under random
                      weights a chunk's rows between them choose nearly
                      every block.)
  `paged_sparse_decode`  one token a slot over the paged pools: writes
                      the new K/V row and the index entry of its
                      `stride` rows, selects, and walks the chosen
                      pages: `hpx_paged_sparse` (grid over slot and kv
                      head; the pages of ONE (slot, kv head) copied
                      into a VMEM bank by table, all in flight at once,
                      then one softmax over the bank for the group's
                      query heads), `_walk_gather` its oracle and the
                      path off the TPU.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["SparseSpec", "block_means", "chunk_attention", "index_blocks",
           "paged_sparse_decode", "select", "sparse_walk"]

_HI = jax.lax.Precision.HIGHEST
CHUNK_ROWS_A_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class SparseSpec:
    """The numbers of a sparse layer (`sparse_config` of the model)."""
    kernel: int = 32        # rows a compressed key is the mean of
    stride: int = 16        # rows between two compressed keys
    block: int = 64         # rows of a block that is chosen whole
    topk: int = 64          # blocks a query and kv group read
    init: int = 1           # leading blocks always read
    local: int = 2048       # trailing rows whose blocks are always read
    dense_len: int = 8192   # up to this many rows every block is read

    def __post_init__(self):
        if self.kernel != 2 * self.stride or self.block % self.stride:
            raise NotImplementedError(
                "sparse selection (ops/sparse_attention.py) builds a "
                "compressed key from TWO adjacent index entries: kernel "
                f"{self.kernel} must be 2 x stride {self.stride}, and "
                f"block {self.block} a multiple of the stride")

    @property
    def width(self) -> int:
        """Entries of a walk's table: the chosen blocks, or every block
        of a context still read densely."""
        return max(self.topk, -(-self.dense_len // self.block))


def block_means(k_rows: jax.Array, stride: int) -> jax.Array:
    """[..., S, n_kv, hd] rows -> [..., S // stride, n_kv, hd] float32:
    the index entries of whole `stride`-row groups."""
    *lead, s, n, h = k_rows.shape
    x = k_rows.reshape(*lead, s // stride, stride, n, h)
    return jnp.mean(x.astype(jnp.float32), axis=-3)


def index_blocks(k_rows: jax.Array, spec: "SparseSpec",
                 bs: int) -> jax.Array:
    """One sequence's rows [S, n_kv, hd] -> its index entries in the
    pool's layout, a page each: [S // bs, bs / stride * n_kv, hd]
    float32 (entry group * n_kv + head of a page: the rows' own order,
    so a table's pages read back as [M, n_kv, hd] with no transpose)."""
    s, nkv, hd = k_rows.shape
    return block_means(k_rows, spec.stride).reshape(s // bs, -1, hd)


def _block_scores(q, means, qpos, spec: SparseSpec, n_blocks: int):
    """q [B, W, n_q, hd]; means [B, M, n_kv, hd] float32 index entries;
    qpos [B, W] -> [B, W, n_kv, n_blocks] float32: a block's score for
    each query row and kv group, +inf where forced, -inf where the row
    cannot see it or no complete window overlaps it."""
    b, w, nq, hd = q.shape
    nkv = means.shape[2]
    r = spec.block // spec.stride
    qg = q.astype(jnp.float32).reshape(b, w, nkv, nq // nkv, hd)
    # q . kbar_j = (q . m_j + q . m_{j+1}) / 2: the entries are scored
    # once, and no array of compressed keys is built
    t = jnp.einsum("bwngh,bmnh->bwngm", qg, means,
                   precision=_HI) / (2.0 * math.sqrt(hd))
    s = t[..., :-1] + t[..., 1:]                            # [.., M - 1]
    j = jnp.arange(s.shape[-1])
    done = (j * spec.stride + spec.kernel - 1)[None, None, :] \
        <= qpos[..., None]                                  # [B, W, J]
    s = jnp.where(done[:, :, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(done[:, :, None, None, :], p, 0.0)        # no window: nan
    sw = jnp.where(done[:, :, None, :], jnp.sum(p, axis=3), -jnp.inf)
    # window j overlaps block c for r c - 1 <= j <= r c + r - 1: shifted
    # by one, groups of r, and the first of the next group
    m = n_blocks * r
    sw = jnp.pad(sw, ((0, 0),) * 3 + ((1, max(0, m - sw.shape[-1])),),
                 constant_values=-jnp.inf)[..., :m + 1]
    grp = jnp.max(sw[..., :m].reshape(b, w, nkv, n_blocks, r), axis=-1)
    nxt = sw[..., r::r]                                     # [.., n_blocks]
    score = jnp.maximum(grp, nxt)
    c = jnp.arange(n_blocks)
    last = qpos // spec.block                               # [B, W]
    first_local = jnp.maximum(qpos - spec.local + 1, 0) // spec.block
    forced = jnp.logical_or(c < spec.init,
                            c >= first_local[..., None])    # [B, W, C]
    seen = c <= last[..., None]
    score = jnp.where(forced[:, :, None, :], jnp.inf, score)
    return jnp.where(seen[:, :, None, :], score, -jnp.inf)


def select(q, means, qpos, spec: SparseSpec, n_blocks: int):
    """The blocks each query row and kv group reads: (ids [B, W, n_kv,
    `spec.width`] int32 ascending, padded behind `count` with
    `n_blocks`; count [B, W, n_kv] int32). q [B, W, n_q, hd] (normed,
    unscaled); means [B, M, n_kv, hd] float32 (`block_means`, M >=
    n_blocks * block / stride); qpos [B, W]: the row at position t sees
    rows <= t."""
    width = spec.width
    score = _block_scores(q, means, qpos, spec, n_blocks)
    k = min(spec.topk, n_blocks)
    top, ids = jax.lax.top_k(score, k)
    ids = jnp.where(top > -jnp.inf, ids, n_blocks)
    ids = jnp.pad(ids, ((0, 0),) * 3 + ((0, width - k),),
                  constant_values=n_blocks)
    live = qpos // spec.block + 1                           # [B, W]
    dense = (qpos + 1 <= spec.dense_len)[..., None, None]
    every = jnp.arange(width)
    every = jnp.where(every[None, None, :] < live[..., None], every,
                      n_blocks)[:, :, None, :]
    ids = jnp.sort(jnp.where(dense, every, ids), axis=-1).astype(jnp.int32)
    return ids, jnp.sum(ids < n_blocks, axis=-1).astype(jnp.int32)


def chunk_attention(q, kc, vc, qpos, spec: SparseSpec,
                    rows_a_block: int = CHUNK_ROWS_A_BLOCK):
    """Sparse attention of a window of rows over a DENSE cache (this
    window's rows already written). q [B, W, n_q, hd] (normed); kc, vc
    [B, S, n_kv, hd]; qpos [W] or [B, W]. Returns (o [B, W, n_q, hd],
    ids, count) with `select`'s ids of every row."""
    b, w, nq, hd = q.shape
    s_len, nkv = kc.shape[1], kc.shape[2]
    g = nq // nkv
    n_blocks = s_len // spec.block
    qp = jnp.broadcast_to(qpos, (b, w))
    ids, count = select(q, block_means(kc, spec.stride), qp, spec,
                        n_blocks)
    # a row's choice as a mask over the blocks: every block it can see
    # while it reads densely, else its (at most topk, sorted first) ids
    c = jnp.arange(n_blocks)
    chosen = jnp.where(
        (qp + 1 <= spec.dense_len)[..., None, None],
        (c <= (qp // spec.block)[..., None])[:, :, None, :],
        jnp.any(ids[..., :spec.topk, None] == c, axis=-2))  # [B,W,nkv,C]
    blk = max(spec.block, min(rows_a_block, s_len)
              // spec.block * spec.block)
    per = blk // spec.block
    n_walk = jnp.minimum(jnp.max(qp) // blk + 1, -(-s_len // blk))
    qg = q.reshape(b, w, nkv, g, hd)
    scale = 1.0 / math.sqrt(hd)

    def body(j, carry):
        m, l, acc = carry
        start = jnp.minimum(j * blk, s_len - blk)
        kr = jax.lax.dynamic_slice_in_dim(kc, start, blk, axis=1)
        vr = jax.lax.dynamic_slice_in_dim(vc, start, blk, axis=1)
        kpos = start + jnp.arange(blk)
        sel = jax.lax.dynamic_slice_in_dim(
            chosen, start // spec.block, per, axis=3)
        sel = jnp.repeat(sel, spec.block, axis=3)           # [B,W,nkv,blk]
        live = jnp.logical_and(
            jnp.logical_and(kpos[None, None, :] <= qp[..., None],
                            kpos >= j * blk)[:, :, None, :], sel)
        s = jnp.einsum("bwngh,bknh->bngwk", qg, kr,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(jnp.moveaxis(live, 1, 2)[:, :, None], s, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        # a row with no live score yet (its blocks all lie ahead) keeps
        # its carry at zero: exp(-inf - 0), not exp(-inf + inf)
        safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - safe[..., None])
        fade = jnp.exp(m - safe)
        acc = acc * fade[..., None] + jnp.einsum(
            "bngwk,bknh->bngwh", p.astype(q.dtype), vr,
            preferred_element_type=jnp.float32)
        return m_new, l * fade + jnp.sum(p, axis=-1), acc

    m, l, acc = jax.lax.fori_loop(
        0, n_walk, body,
        (jnp.full((b, nkv, g, w), -jnp.inf, jnp.float32),
         jnp.zeros((b, nkv, g, w), jnp.float32),
         jnp.zeros((b, nkv, g, w, hd), jnp.float32)))
    o = jnp.moveaxis(acc / l[..., None], 3, 1)              # [B,W,nkv,g,hd]
    return o.reshape(b, w, nq, hd).astype(q.dtype), ids, count


# -- the decode walk ----------------------------------------------------

def _walk_gather(q, k_pool, v_pool, phys, count, pos):
    """The walk as XLA gathers: the oracle of `hpx_paged_sparse`. q [B,
    n_kv, g, hd]; pools [num_blocks, n_kv, bs, hd]; phys [B, n_kv, K]
    physical block ids in ascending LOGICAL order; count [B, n_kv]; pos
    [B]: entry count - 1 holds row `pos`, whose rows behind it are the
    only ones cut."""
    b, nkv, g, hd = q.shape
    bs, width = k_pool.shape[2], phys.shape[2]
    head = jnp.arange(nkv)[None, :, None]
    kr = k_pool[phys, head].reshape(b, nkv, width * bs, hd)
    vr = v_pool[phys, head].reshape(b, nkv, width * bs, hd)
    limit = (count - 1) * bs + (pos % bs)[:, None]          # [B, n_kv]
    live = jnp.arange(width * bs)[None, None, :] <= limit[..., None]
    s = jnp.einsum("bngh,bnkh->bngk", q, kr,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    s = jnp.where(live[:, :, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    vr = jnp.where(live[..., None], vr, 0)                  # 0 x NaN
    return jnp.einsum("bngk,bnkh->bngh", p, vr,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def _walk_kernel(phys_ref, cnt_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                 k_s, v_s, sem, *, nkv: int, bs: int, width: int):
    """One (slot b, kv head h) grid step. q_ref / o_ref: (g, hd) the
    group's query heads; k_hbm / v_hbm: the POOLS, left in HBM; k_s /
    v_s: (width * bs, hd) banks. The first `count` table entries are
    copied into the banks, all in flight at once on ONE DMA semaphore a
    pool: such a semaphore counts bytes landed from ANY copy that
    signals it, so NOTHING IS READ FROM A BANK BEFORE EVERY WAIT HAS
    RETURNED (`attention_pallas._paged_live_kernel`'s rule). Then one
    softmax over the bank: rows past the last entry's row `pos % bs`
    are masked (a select, so a NaN score goes too) and their V rows
    SELECTED to zero. A walk of `topk` entries or fewer reads the
    bank's first `topk` blocks alone."""
    b, h = pl.program_id(0), pl.program_id(1)
    r = b * nkv + h
    n = cnt_ref[r]
    limit = (n - 1) * bs + jax.lax.rem(pos_ref[b], bs)

    def copies(j):
        rows = pl.ds(pl.multiple_of(j * bs, bs), bs)
        blk = phys_ref[r, j]
        return (pltpu.make_async_copy(k_hbm.at[blk, h], k_s.at[rows, :],
                                      sem.at[0]),
                pltpu.make_async_copy(v_hbm.at[blk, h], v_s.at[rows, :],
                                      sem.at[1]))

    def start(j, carry):
        for c in copies(j):
            c.start()
        return carry

    def wait(j, carry):
        for c in copies(j):
            c.wait()
        return carry

    jax.lax.fori_loop(0, n, start, 0)
    jax.lax.fori_loop(0, n, wait, 0)

    def finish(rows: int):
        q = q_ref[...]
        s = jax.lax.dot_general(
            q, k_s[:rows].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
        live = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) <= limit
        p = jax.nn.softmax(jnp.where(live, s, -jnp.inf), axis=-1)
        vrow = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        v = jnp.where(vrow <= limit, v_s[:rows], 0)
        o_ref[...] = jax.lax.dot_general(
            p.astype(o_ref.dtype), v.astype(o_ref.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    half = min(width, max(1, width // 2))
    if half == width:
        finish(width * bs)
    else:
        pl.when(n <= half)(lambda: finish(half * bs))
        pl.when(n > half)(lambda: finish(width * bs))


def _walk_pallas(q, k_pool, v_pool, phys, count, pos, interpret: bool):
    b, nkv, g, hd = q.shape
    bs, width = k_pool.shape[2], phys.shape[2]
    item = jnp.dtype(k_pool.dtype).itemsize
    vmem = 2 * width * bs * hd * item + 6 * g * width * bs * 4 \
        + (8 << 20)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nkv),
        in_specs=[pl.BlockSpec((None, None, g, hd),
                               lambda i, j, *_: (i, j, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, None, g, hd),
                               lambda i, j, *_: (i, j, 0, 0)),
        scratch_shapes=[pltpu.VMEM((width * bs, hd), k_pool.dtype),
                        pltpu.VMEM((width * bs, hd), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))])
    return pl.pallas_call(
        functools.partial(_walk_kernel, nkv=nkv, bs=bs, width=width),
        name="hpx_paged_sparse",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(vmem)),
        interpret=interpret,
    )(phys.reshape(b * nkv, width).astype(jnp.int32),
      count.reshape(b * nkv).astype(jnp.int32), pos.astype(jnp.int32),
      q, k_pool, v_pool)


def sparse_walk(q, k_pool, v_pool, phys, count, pos,
                kernel: Optional[str] = None,
                interpret: Optional[bool] = None):
    """Attention of one query row a slot over the pages a table names:
    q [B, n_kv, g, hd]; phys [B, n_kv, K] physical block ids in
    ascending logical order; count [B, n_kv] >= 1 entries to read; pos
    [B] the row's position (it lies in entry count - 1). Decided HERE
    from the operands: unquantized pools with a head of whole 128-lane
    rows take `hpx_paged_sparse` (in interpret mode off the chip: the
    tests), every other call, and `kernel="gather"` (what a server
    whose `paged_kernel` is `gather` asks for), the gather oracle."""
    tiles = q.shape[-1] % 128 == 0 and k_pool.dtype == q.dtype
    if kernel is None:
        kernel = "pallas" if tiles else "gather"
    if kernel != "pallas":
        return _walk_gather(q, k_pool, v_pool, phys, count, pos)
    if not tiles:
        raise NotImplementedError(
            "hpx_paged_sparse (ops/sparse_attention.py) copies whole "
            f"128-lane rows of an unquantized pool; got a head of "
            f"{q.shape[-1]} and pools of {k_pool.dtype}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _walk_pallas(q, k_pool, v_pool, phys, count, pos, interpret)


def paged_sparse_decode(q, k_new, v_new, k_pool, v_pool, idx_pool, table,
                        pos, spec: SparseSpec,
                        kernel: Optional[str] = None):
    """One decode step of a sparse layer over the paged cache. q [B, 1,
    n_q, hd] (normed); k_new / v_new [B, n_kv, hd]; pools [num_blocks,
    n_kv, bs, hd] with bs == spec.block; idx_pool [num_blocks, bs /
    stride * n_kv, hd] float32 (`index_blocks`' layout); table [B, max_blocks]; pos [B]. Writes
    the row at `pos` into both pools and the mean of its `stride`-row
    group (the rows <= pos of it; final once the group is full, unread
    before) into the index, every axis ahead of `head_dim` indexed
    (`ops/paged_attention`'s layout rule). Returns (o [B, 1, n_q, hd],
    k_pool, v_pool, idx_pool, ids [B, n_kv, K] logical, count [B,
    n_kv])."""
    from .paged_attention import scatter_token
    b, _, nq, hd = q.shape
    nkv, bs = k_pool.shape[1], k_pool.shape[2]
    if bs != spec.block:
        raise NotImplementedError(
            f"a sparse layer's pages ARE its blocks: the pool's block "
            f"size {bs} must be the model's {spec.block} "
            "(ops/sparse_attention.py, models/serving.py _init_paged)")
    per, maxb = bs // spec.stride, table.shape[1]
    k_pool = scatter_token(k_pool, table, pos, k_new)
    v_pool = scatter_token(v_pool, table, pos, v_new)
    rows = jnp.arange(b)
    bidx = table[rows, pos // bs]                           # [B]
    sub = (pos % bs) // spec.stride
    heads = jnp.arange(nkv)
    grp = (sub * spec.stride)[:, None] + jnp.arange(spec.stride)
    kr = k_pool[bidx[:, None, None], heads[None, :, None],
                grp[:, None, :]]                            # [B,nkv,st,hd]
    real = (grp <= (pos % bs)[:, None])[:, None, :, None]
    mean = jnp.sum(jnp.where(real, kr.astype(jnp.float32), 0.0),
                   axis=2) / spec.stride
    idx_pool = idx_pool.at[bidx[:, None],
                           sub[:, None] * nkv + heads[None, :]].set(mean)
    # hpxlint: disable-next=HPX010 — the INDEX's pages, not K/V: one
    # float32 entry every `stride` rows, scored in XLA; a selection
    # kernel that walks them by table is ROADMAP B15 (1)
    means = idx_pool[table].reshape(b, maxb * per, nkv, hd)
    ids, count = select(q, means, pos[:, None], spec, maxb)
    ids, count = ids[:, 0], count[:, 0]
    phys = jnp.take_along_axis(
        table[:, None, :], jnp.minimum(ids, maxb - 1), axis=2)
    o = sparse_walk(q[:, 0].reshape(b, nkv, nq // nkv, hd), k_pool,
                    v_pool, phys, count, pos, kernel)
    return o.reshape(b, 1, nq, hd), k_pool, v_pool, idx_pool, ids, count
