"""EVA attention: softmax attention over K/V rows kept at TWO grains.

A layer caches, for each sequence, the EXACT K/V rows of one aligned
window of `window` positions (the window the newest position is in) and
ONE pooled K/V row, a chunk's SUMMARY, for every `chunk` positions of
every window behind it. With w = floor(t / window) the query at t
attends the exact rows {j : window w <= j <= t} and the summaries {c :
c < (window / chunk) w} under ONE softmax. The window is ALIGNED: it
empties all at once when t reaches a multiple of `window`, and
window / chunk summaries take its place. A summary is a function of its
own chunk's rows alone (keys already rotated), by two learned vectors a
head, phi and mu:

    a_j = (k_j . phi) / sqrt(d);  p = softmax_j(a) over the chunk's rows
    k~ = sum_j p_j k_j + mu;      v~ = sum_j p_j v_j        (float32)

What is here:

  `eva_pool`           the pooling, XLA: a softmax over `chunk` rows a
                       (chunk, head) and two weighted sums; float32
                       inside, rows out in the rows' type
  `eva_row`            where position p lies in a sequence's ONE run of
                       logical rows: the visible summaries first, then
                       the window's exact rows. Since window / chunk
                       summaries fill whole blocks, a page table that
                       lists a sequence's summary blocks and then its
                       window's blocks is gap-free, and a decode step is
                       `ops/paged_attention.paged_decode_attention` at
                       that row: the bounded walk `hpx_paged_fused`
                       serves both grains, no second kernel
  `eva_window_attend`  what a dense body's `attend` calls for a window
                       of new columns (a prefill chunk, the probe's one
                       row) over a b=1 scratch: ring write of the exact
                       rows, the summaries of the chunks these columns
                       complete, and the attention with both masks
  `eva_roll_blocks`    a completed window's exact blocks -> the blocks
                       of its summaries (the decode path's pooling: all
                       of a window's chunks at the roll)
  `scratch_entry`      an empty scratch of this layout

Every softmax and the pooling weights are float32; rows are stored in
the model's type.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .paged_attention import gather_block_kv

__all__ = ["eva_pool", "eva_roll_blocks", "eva_row", "eva_window_attend",
           "scratch_entry", "summary_rows"]


def eva_pool(k, v, phi, mu):
    """Summaries of whole chunks. k, v [..., C, H, d] (C a chunk's
    rows, keys rotated); phi, mu [H, d]. Returns (k~, v~) [..., H, d]
    in the rows' types; weights and sums float32."""
    f32 = jnp.float32
    kf, vf = k.astype(f32), v.astype(f32)
    a = jnp.einsum("...chd,hd->...ch", kf, phi.astype(f32)) \
        / math.sqrt(k.shape[-1])
    p = jax.nn.softmax(a, axis=-2)
    ks = jnp.einsum("...ch,...chd->...hd", p, kf) + mu.astype(f32)
    vs = jnp.einsum("...ch,...chd->...hd", p, vf)
    return ks.astype(k.dtype), vs.astype(v.dtype)


def summary_rows(smax: int, chunk: int, window: int) -> int:
    """Summary rows a sequence of up to `smax` positions can ever see:
    those of the windows that can complete."""
    return max(1, smax // window) * (window // chunk)


def eva_row(pos, chunk: int, window: int):
    """The logical row of position `pos` in a sequence's one run of
    rows [summaries of the complete windows | exact rows of the window
    under way]: every row up to it is what the query at `pos` attends.
    An int or an int array."""
    return pos // window * (window // chunk) + pos % window


def scratch_entry(smax: int, heads: int, head_dim: int, chunk: int,
                  window: int, dtype):
    """(exact K, exact V [1, window, H, d], summary K, summary V [1,
    summary_rows, H, d]) of no tokens."""
    ex = jnp.zeros((1, window, heads, head_dim), dtype)
    sm = jnp.zeros((1, summary_rows(smax, chunk, window), heads, head_dim),
                   dtype)
    return ex, ex, sm, sm


def eva_window_attend(q, k, v, entry, pos0, valid, phi, mu, chunk: int,
                      window: int):
    """A window of new columns over a dense two-grain scratch. q, k, v
    [B, Q, H, d] at positions pos0 .. pos0 + Q - 1 (pos0 a scalar),
    `valid` of them real (None: all); entry = (exact K, exact V [B,
    window, H, d], summary K, summary V [B, S, H, d]) holding every
    position below pos0: exact row r the newest position = r (mod
    window), summary row c chunk c. Returns (out [B, Q, H, d], the
    entry with the real columns written).

    The real columns' rows go into the ring (padding must not: it would
    wrap onto the live window's first rows). A chunk whose last row is
    among them is pooled from the ring as just written and its summary
    written; the others' are left as they are. A column at t attends:
    the ring AS IT CAME, a row counting where the position it held (the
    newest below pos0 in its residue) lies in t's window; this window's
    columns up to itself in t's window; and the summaries of the
    windows behind t's, the ones written here among them: a column
    behind a boundary sees its fresh summaries, one ahead of it the
    exact rows they were pooled from."""
    ke, ve, ks, vs = entry
    nq, d = q.shape[1], q.shape[-1]
    n_sum = ks.shape[1]
    col = jnp.arange(nq)
    t = pos0 + col
    real = col < (nq if valid is None else valid)
    ring = jnp.where(real, t % window, window)          # window: dropped
    ke_new = ke.at[:, ring].set(k.astype(ke.dtype), mode="drop")
    ve_new = ve.at[:, ring].set(v.astype(ve.dtype), mode="drop")
    # the chunks whose last row is a real column here
    c = pos0 // chunk + jnp.arange(nq // chunk + 1)
    done = (c + 1) * chunk <= pos0 + (nq if valid is None else valid)
    rows = (c[:, None] * chunk + jnp.arange(chunk)[None, :]) % window
    ksn, vsn = eva_pool(ke_new[:, rows], ve_new[:, rows], phi, mu)
    at = jnp.where(done, c, n_sum)                      # n_sum: dropped
    ks = ks.at[:, at].set(ksn, mode="drop")
    vs = vs.at[:, at].set(vsn, mode="drop")
    # the masks, by position arithmetic
    wq = t // window
    r = jnp.arange(window)
    held = pos0 - 1 - (pos0 - 1 - r) % window           # < 0: nothing yet
    old = held[None, :] // window == wq[:, None]
    new = (col[None, :] <= col[:, None]) & (wq[None, :] == wq[:, None])
    seen = jnp.arange(n_sum)[None, :] < (wq * (window // chunk))[:, None]
    live = jnp.concatenate([old, new, seen], axis=1)    # [Q, keys]
    keys = jnp.concatenate([ke, k.astype(ke.dtype), ks], axis=1)
    vals = jnp.concatenate([ve, v.astype(ve.dtype), vs], axis=1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, keys,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(live[None, None], s, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vals)
    return out, (ke_new, ve_new, ks, vs)


def eva_roll_blocks(k_pool, v_pool, exact, fresh, phi, mu, chunk: int):
    """A window's ROLL over one layer's pools [num_blocks, H,
    block_size, d]: the exact rows a completed window left in its
    blocks `exact` [window / block_size] int32 (in order) are pooled
    into window / chunk summaries, which fill the blocks `fresh`
    [window / chunk / block_size] whole. Returns (k_pool, v_pool)."""
    bs = k_pool.shape[2]

    def chunks_of(pool):            # [chunks, C, H, d] in position order
        rows = gather_block_kv(pool, exact[None])[0]    # [window, H, d]
        return rows.reshape((-1, chunk) + rows.shape[1:])

    def blocks_of(rows):            # [chunks, H, d] -> whole blocks
        return jnp.moveaxis(rows.reshape(-1, bs, *rows.shape[1:]), 1, 2)
    ks, vs = eva_pool(chunks_of(k_pool), chunks_of(v_pool), phi, mu)
    return (k_pool.at[fresh].set(blocks_of(ks)),
            v_pool.at[fresh].set(blocks_of(vs)))
