"""Lightning attention: decayed linear attention over a per-slot
recurrent state (Lightning Attention-2, arXiv:2401.04658).

The second update rule of the cache's per-slot state entry (`ops/kda.py`
holds the first): a layer and slot keep ONE float32 matrix a head, S in
R^{d x d}, a function of the tokens the slot has consumed. No conv tail,
no delta correction, and the decay is a CONSTANT of (head, layer), not
of the token:

    S_t = lam S_{t-1} + k_t^T v_t;   o_t = q_t S_t      (lam = exp(g), g < 0)

Three forms of the same recurrence, each the oracle of the next:

  `lightning_scan`   the token-by-token scan
  `lightning_chunk`  the chunkwise form a prefill chunk takes: blocks of
                     `block` tokens, the decay between two rows of a
                     block as exp of a difference of running sums
                     (<= 0 before the exp: nothing overflows), the
                     state carried from block to block
  `lightning_step`   one token a slot, state in place: the Pallas kernel
                     `hpx_lightning_step` (grid over slot and head
                     group; a head's 128 x 128 float32 tile is read
                     once and written once), `_step_xla` its oracle and
                     the path off the TPU and for other tile shapes

`lightning_mix` is what a forward body's `attend` calls. Everything
here is float32. The caller scales q (by d^-1/2) and rotates q and k.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["lightning_chunk", "lightning_log_decay", "lightning_mix",
           "lightning_scan", "lightning_step"]

_HI = jax.lax.Precision.HIGHEST
_HEADS_PER_STEP = 8     # heads one grid step of hpx_lightning_step updates


def lightning_log_decay(heads: int, layer: int, depth: int) -> np.ndarray:
    """g [heads] float32, the log of a head's decay on PUBLISHED layer
    `layer` of `depth`: -s_h f_l, s_h = 2^(-8 (h + 1) / heads), f_l =
    1 - l / (depth - 1) + 1e-5 (the slopes of Lightning Attention-2:
    fast heads forget in a few tokens, slow ones in thousands, and
    deeper layers remember longer)."""
    s = 2.0 ** (-8.0 * (np.arange(heads) + 1.0) / heads)
    f = 1.0 - layer / max(depth - 1, 1) + 1e-5
    return (-s * f).astype(np.float32)


def _step_xla(q, k, v, lam, state):
    """One token: q, k [B, H, dk], v [B, H, dv], lam [H], state [B, H,
    dk, dv], all float32 -> (o [B, H, dv], state)."""
    sn = state * lam[:, None, None] + k[..., None] * v[..., None, :]
    return jnp.sum(sn * q[..., None], axis=-2), sn


def lightning_scan(q, k, v, g, state):
    """The recurrence token by token. q, k [B, T, H, dk], v [B, T, H,
    dv], g [H] log decay, state [B, H, dk, dv] -> (o [B, T, H, dv],
    state)."""
    lam = jnp.exp(g)

    def body(s, x):
        o, s = _step_xla(x[0], x[1], x[2], lam, s)
        return s, o
    tm = lambda a: jnp.moveaxis(a, 1, 0)                   # noqa: E731
    state, o = jax.lax.scan(body, state, (tm(q), tm(k), tm(v)))
    return jnp.moveaxis(o, 0, 1), state


def lightning_chunk(q, k, v, g, state, valid=None, block: int = 128):
    """`lightning_scan`'s result in the chunkwise form. With r_i = 1
    for a real row and 0 for padding (rows at and past `valid`), G_i
    the running sum of g r inside a block and S_0 the state entering
    it:

        O = (Q exp G) S_0 + ((Q K^T) * D) V,  D[i, j] = exp(G_i - G_j)
                                                            (j <= i)
        S_C = exp(G_C) S_0 + (K exp(G_C - G))^T V

    Padding rows carry k = 0 and no decay: they leave the state as it
    is (their own outputs are garbage nobody reads)."""
    b, t, h, dk = q.shape
    c = min(block, t)
    pad = -t % c
    real = jnp.ones((t,), jnp.float32) if valid is None else \
        (jnp.arange(t) < valid).astype(jnp.float32)
    k = k * real[None, :, None, None]
    if pad:
        z = lambda a: jnp.pad(                             # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, real = z(q), z(k), z(v), jnp.pad(real, (0, pad))
    n = (t + pad) // c

    def blocks(a):      # [B, T, H, d] -> [N, B, H, C, d]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)
    row = jnp.arange(c)
    upto = row[:, None] >= row[None, :]
    ein = functools.partial(jnp.einsum, precision=_HI)

    def body(s, x):
        qb, kb, vb, rb = x              # [B, H, C, d] x 3, rb [C]
        gc = jnp.cumsum(g[:, None] * rb[None, :], axis=1)   # [H, C]
        diff = gc[:, :, None] - gc[:, None, :]
        d = jnp.where(upto, jnp.exp(jnp.where(upto, diff, 0.0)), 0.0)
        qk = ein("bhik,bhjk->bhij", qb, kb) * d
        o = ein("bhck,bhkv->bhcv", qb * jnp.exp(gc)[..., None], s) \
            + ein("bhij,bhjv->bhiv", qk, vb)
        last = gc[:, -1:]
        s = jnp.exp(last)[..., None] * s + ein(
            "bhck,bhcv->bhkv", kb * jnp.exp(last - gc)[..., None], vb)
        return s, o

    state, o = jax.lax.scan(body, state, (
        blocks(q), blocks(k), blocks(v), real.reshape(n, c)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)      # [B, N, C, H, dv]
    return o.reshape(b, t + pad, h, -1)[:, :t], state


def _step_kernel(x_ref, lam_ref, s_ref, o_ref, s_out, *, heads: int,
                 d: int):
    """One (slot, head group) grid step. x_ref (heads, 8, d): a head's
    rows q, k, v, 5 unused; lam_ref (heads, 1, d): the head's decay
    across the row; s_ref / s_out (heads, d, d): the state tiles, the
    same HBM buffer. q and k scale the state's ROWS, so they are turned
    into columns: the 8 rows padded to a d x d tile and transposed."""
    for i in range(heads):
        r = x_ref[i]
        cols = jnp.concatenate(
            [r, jnp.zeros((d - 8, d), jnp.float32)], axis=0).T
        qc, kc = cols[:, 0:1], cols[:, 1:2]
        sn = s_ref[i] * lam_ref[i] + kc * r[2:3]
        s_out[i] = sn
        o_ref[i] = jnp.sum(sn * qc, axis=0, keepdims=True)


def _step_pallas(q, k, v, lam, state, interpret: bool):
    b, h, d = q.shape
    hb = _HEADS_PER_STEP if h % _HEADS_PER_STEP == 0 else 1
    x = jnp.pad(jnp.stack([q, k, v], axis=2),
                ((0, 0), (0, 0), (0, 5), (0, 0)))           # [B, H, 8, d]
    lam = jnp.broadcast_to(lam[:, None, None], (h, 1, d))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, d=d),
        name="hpx_lightning_step",
        grid=(b, h // hb),
        in_specs=[pl.BlockSpec((None, hb, 8, d),
                               lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((hb, 1, d), lambda i, j: (j, 0, 0)),
                  pl.BlockSpec((None, hb, d, d),
                               lambda i, j: (i, j, 0, 0))],
        out_specs=[pl.BlockSpec((None, hb, 1, d),
                                lambda i, j: (i, j, 0, 0)),
                   pl.BlockSpec((None, hb, d, d),
                                lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, lam, state)
    return o[:, :, 0], state


def lightning_step(q, k, v, g, state, kernel: Optional[str] = None,
                   interpret: Optional[bool] = None):
    """One token a slot: q, k [B, H, dk], v [B, H, dv], g [H] log
    decay, state [B, H, dk, dv] float32 -> (o [B, H, dv], state).
    Decided HERE and nowhere else, from the operands: heads that are
    square tiles of whole 128-lane rows take `hpx_lightning_step` on a
    TPU (`kernel="pallas"` forces it, in interpret mode off the chip:
    the tests), every other call `_step_xla`."""
    dk, dv = state.shape[-2:]
    tiles = dk == dv and dk % 128 == 0
    if kernel is None:
        kernel = "pallas" if tiles and jax.default_backend() == "tpu" \
            else "xla"
    if kernel != "pallas":
        return _step_xla(q, k, v, jnp.exp(g), state)
    if not tiles:
        raise NotImplementedError(
            f"hpx_lightning_step (ops/lightning.py) updates square state "
            f"tiles of whole 128-lane rows; got {dk} x {dv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _step_pallas(q, k, v, jnp.exp(g), state, interpret)


def lightning_mix(q, k, v, g, state, valid=None):
    """A lightning layer's stateful core over a window of W tokens, as
    a forward body's `attend` runs it. q, k, v [B, W, H, d] float32 (q
    scaled, q and k rotated); g [H] float32 log decay; state [B, H, d,
    d] float32. `valid` (a scalar, or None for all W): the window's
    real rows; the rest is padding the state may not see. Returns (o
    [B, W, H, d] float32, (state,))."""
    g = jnp.asarray(g, jnp.float32)
    if q.shape[1] == 1:
        o, state = lightning_step(q[:, 0], k[:, 0], v[:, 0], g, state)
        return o[:, None], (state,)
    o, state = lightning_chunk(q, k, v, g, state, valid)
    return o, (state,)
