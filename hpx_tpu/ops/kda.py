"""Kimi Delta Attention (KDA): a gated delta rule over a per-slot
recurrent state, with a short causal convolution ahead of it.

The second KIND of cached state the serving path holds beside K/V
blocks: a layer and slot keep ONE float32 matrix a head, S in
R^{d_k x d_v}, and the last `K - 1` pre-activation rows of the layer's
three convolved streams (the conv tail). Neither has positions or
blocks; both are functions of the tokens the slot has consumed.

One token (per head; alpha = exp(g) in R^{d_k}, beta a scalar):

    S' = Diag(alpha) S;   S_t = S' + beta k (v - S'^T k)^T;   o = S_t^T q

Three forms of the same recurrence, each the oracle of the next:

  `kda_scan`   the token-by-token scan (`lax.scan` of `_step_xla`)
  `kda_chunk`  the chunkwise form a prefill chunk takes: blocks of
               `block` tokens, decays accumulated in LOG space inside a
               block (every exponent is a difference G_i - G_j <= 0
               taken before the exp, so nothing overflows whatever the
               decay), the block's deltas from one triangular solve,
               the state passed from block to block
  `kda_step`   one token a slot, state in place: the Pallas kernel
               `hpx_kda_step` (grid over slot and head group; a head's
               128 x 128 float32 tile in VMEM: decay, S'^T k, the rank-1
               update and S^T q in one read and one write of the state),
               `_step_xla` its oracle and the path off the TPU and for
               heads that are not 128 x 128 tiles

`kda_mix` is what a forward body's `attend` calls: the convolution over
its tail (`short_conv`), SiLU, the L2 norms of q and k, then the
recurrence in the form the window's width asks for. Everything here is
float32 but the tail, which keeps the streams' own type.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kda_chunk", "kda_mix", "kda_scan", "kda_step", "short_conv"]

_HI = jax.lax.Precision.HIGHEST
_HEADS_PER_STEP = 8     # heads one grid step of hpx_kda_step updates


def short_conv(pre: jax.Array, tail: jax.Array, w: jax.Array,
               valid=None, bias=None):
    """Depthwise causal convolution over time, K taps a channel, plus
    `bias` [C] where the layer has one. pre [B, W, C]: the window's
    pre-activation rows; tail [B, K - 1, C]: the rows before it; w [K,
    C] (w[K - 1] multiplies the current row). Returns (out [B, W, C]
    float32, the new tail: the K - 1 rows ending at the window's last
    VALID row; `valid` None = all W, else a scalar count, rows past it
    being padding)."""
    k = w.shape[0]
    n = pre.shape[1]
    full = jnp.concatenate([tail, pre.astype(tail.dtype)], axis=1)
    wf = w.astype(jnp.float32)
    out = sum(full[:, j:j + n].astype(jnp.float32) * wf[j]
              for j in range(k))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    at = n if valid is None else valid
    return out, jax.lax.dynamic_slice_in_dim(full, at, k - 1, axis=1)


def _step_xla(q, k, v, alpha, beta, state):
    """One token: q, k, alpha [B, H, dk], v [B, H, dv], beta [B, H],
    state [B, H, dk, dv], all float32 -> (o [B, H, dv], state)."""
    sd = state * alpha[..., None]
    u = jnp.sum(sd * k[..., None], axis=-2)                # S'^T k
    sn = sd + k[..., None] * (beta[..., None] * (v - u))[..., None, :]
    return jnp.sum(sn * q[..., None], axis=-2), sn


def kda_scan(q, k, v, g, beta, state):
    """The recurrence token by token. q, k, g [B, T, H, dk], v [B, T,
    H, dv], beta [B, T, H], state [B, H, dk, dv] -> (o [B, T, H, dv],
    state)."""
    def body(s, x):
        o, s = _step_xla(x[0], x[1], x[2], jnp.exp(x[3]), x[4], s)
        return s, o
    tm = lambda a: jnp.moveaxis(a, 1, 0)                   # noqa: E731
    state, o = jax.lax.scan(body, state, (tm(q), tm(k), tm(v), tm(g),
                                          tm(beta)))
    return jnp.moveaxis(o, 0, 1), state


def kda_chunk(q, k, v, g, beta, state, block: int = 32):
    """`kda_scan`'s result in the chunkwise form. With G_t the running
    sum of g inside a block and S_0 the state entering it:

        A[i, j] = beta_i sum_c k_i k_j exp(G_i - G_j)        (j < i)
        W = (I + A)^-1 beta (V - (K exp G) S_0)    the block's deltas
        O = (Q exp G) S_0 + B W,  B[i, j] = sum_c q_i k_j exp(G_i - G_j)
                                                            (j <= i)
        S_C = Diag(exp G_C) S_0 + (K exp(G_C - G))^T W

    Rows with beta = 0 and g = 0 (padding) leave the state as it is."""
    b, t, h, dk = q.shape
    c = min(block, t)
    pad = -t % c
    if pad:
        z = lambda a: jnp.pad(                             # noqa: E731
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        q, k, v, g, beta = z(q), z(k), z(v), z(g), z(beta)
    n = (t + pad) // c

    def blocks(a):      # [B, T, H, ...] -> [N, B, H, C, ...]
        a = a.reshape((b, n, c) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)
    row = jnp.arange(c)
    below = row[:, None] > row[None, :]
    upto = row[:, None] >= row[None, :]
    eye = jnp.eye(c, dtype=jnp.float32)
    ein = functools.partial(jnp.einsum, precision=_HI)

    def body(s, x):
        qb, kb, vb, gb, bb = x          # [B, H, C, dk] ..., bb [B, H, C]
        gc = jnp.cumsum(gb, axis=2)
        diff = gc[:, :, :, None, :] - gc[:, :, None, :, :]
        e = jnp.where(upto[..., None],
                      jnp.exp(jnp.where(upto[..., None], diff, 0.0)), 0.0)
        kk = jnp.sum(kb[:, :, :, None, :] * kb[:, :, None, :, :] * e, -1)
        qk = jnp.sum(qb[:, :, :, None, :] * kb[:, :, None, :, :] * e, -1)
        a = jnp.where(below, kk, 0.0) * bb[..., None]
        gam = jnp.exp(gc)
        rhs = bb[..., None] * (vb - ein("bhck,bhkv->bhcv", kb * gam, s))
        w = jax.scipy.linalg.solve_triangular(eye + a, rhs, lower=True)
        o = ein("bhck,bhkv->bhcv", qb * gam, s) \
            + ein("bhij,bhjv->bhiv", qk, w)
        last = gc[:, :, -1:, :]
        s = jnp.exp(last)[:, :, 0, :, None] * s \
            + ein("bhck,bhcv->bhkv", kb * jnp.exp(last - gc), w)
        return s, o

    state, o = jax.lax.scan(body, state, tuple(
        blocks(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)      # [B, N, C, H, dv]
    return o.reshape(b, t + pad, h, -1)[:, :t], state


def _step_kernel(x_ref, s_ref, o_ref, s_out, *, heads: int, d: int):
    """One (slot, head group) grid step. x_ref (heads, 8, d): a head's
    rows q, k, alpha, v, beta (across the row), 3 unused; s_ref / s_out
    (heads, d, d): the state tiles, the same HBM buffer. q, k and alpha
    scale the state's ROWS, so they are turned into columns: the 8 rows
    padded to a d x d tile and transposed (column j of it is row j)."""
    for i in range(heads):
        r = x_ref[i]
        cols = jnp.concatenate(
            [r, jnp.zeros((d - 8, d), jnp.float32)], axis=0).T
        qc, kc, ac = cols[:, 0:1], cols[:, 1:2], cols[:, 2:3]
        sd = s_ref[i] * ac
        u = jnp.sum(sd * kc, axis=0, keepdims=True)        # S'^T k
        sn = sd + kc * (r[4:5] * (r[3:4] - u))
        s_out[i] = sn
        o_ref[i] = jnp.sum(sn * qc, axis=0, keepdims=True)


def _step_pallas(q, k, v, alpha, beta, state, interpret: bool):
    b, h, d = q.shape
    hb = _HEADS_PER_STEP if h % _HEADS_PER_STEP == 0 else 1
    x = jnp.stack([q, k, alpha, v,
                   jnp.broadcast_to(beta[..., None], q.shape)], axis=2)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 3), (0, 0)))       # [B, H, 8, d]
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, heads=hb, d=d),
        name="hpx_kda_step",
        grid=(b, h // hb),
        in_specs=[pl.BlockSpec((None, hb, 8, d),
                               lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((None, hb, d, d),
                               lambda i, j: (i, j, 0, 0))],
        out_specs=[pl.BlockSpec((None, hb, 1, d),
                                lambda i, j: (i, j, 0, 0)),
                   pl.BlockSpec((None, hb, d, d),
                                lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, h, 1, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, state)
    return o[:, :, 0], state


def kda_step(q, k, v, g, beta, state, kernel: Optional[str] = None,
             interpret: Optional[bool] = None):
    """One token a slot: q, k, g [B, H, dk], v [B, H, dv], beta [B, H],
    state [B, H, dk, dv] float32 -> (o [B, H, dv], state). Decided HERE
    and nowhere else, from the operands: heads that are square tiles of
    whole 128-lane rows take `hpx_kda_step` on a TPU (`kernel="pallas"`
    forces it, in interpret mode off the chip: the tests), every other
    call `_step_xla`."""
    dk, dv = state.shape[-2:]
    tiles = dk == dv and dk % 128 == 0
    if kernel is None:
        kernel = "pallas" if tiles and jax.default_backend() == "tpu" \
            else "xla"
    if kernel != "pallas":
        return _step_xla(q, k, v, jnp.exp(g), beta, state)
    if not tiles:
        raise NotImplementedError(
            f"hpx_kda_step (ops/kda.py) updates square state tiles of "
            f"whole 128-lane rows; got {dk} x {dv}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _step_pallas(q, k, v, jnp.exp(g), beta, state, interpret)


def kda_mix(pre, g, beta, conv_w, state, tail, valid=None):
    """A KDA layer's stateful core over a window of W tokens, as a
    forward body's `attend` runs it. pre [B, W, 3, H, d]: the q, k, v
    streams ahead of the convolution; g [B, W, H, d] float32 log decay;
    beta [B, W, H] float32; conv_w [K, 3, H, d]; state [B, H, d, d]
    float32; tail [B, K - 1, 3 * H * d]. `valid` (a scalar, or None for
    all W): the window's real rows; the rest are padding that neither
    the state nor the tail may see. Returns (o [B, W, H, d] float32,
    (state, tail))."""
    b, w, _, h, d = pre.shape
    act, tail = short_conv(pre.reshape(b, w, -1), tail,
                           conv_w.reshape(conv_w.shape[0], -1), valid)
    q, k, v = jnp.moveaxis(
        jax.nn.silu(act).reshape(b, w, 3, h, d), 2, 0)
    norm = lambda a: a * jax.lax.rsqrt(                     # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k = norm(q) * d ** -0.5, norm(k)
    if w == 1:
        o, state = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                            beta[:, 0], state)
        return o[:, None], (state, tail)
    if valid is not None:
        real = jnp.arange(w) < valid
        g = jnp.where(real[None, :, None, None], g, 0.0)
        beta = jnp.where(real[None, :, None], beta, 0.0)
    o, state = kda_chunk(q, k, v, g, beta, state)
    return o, (state, tail)
