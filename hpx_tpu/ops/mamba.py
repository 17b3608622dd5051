"""Mamba-1 selective scan: a diagonal state-space recurrence over a
per-slot recurrent state, with a short causal convolution ahead of it
(Mamba, arXiv:2312.00752, as Jamba carries it: an RMSNorm on each of
dt, B and C).

The third update rule of the cache's per-slot state entry (`ops/kda.py`
and `ops/lightning.py` hold the other two). A layer and slot keep one
float32 state S in R^{N x C} (N = d_state on the SUBLANES, C = d_inner
channels on the LANES: the published [C, N] has a 16-wide minor
dimension, which the chip pads to 128 lanes, eight times the bytes) and
the last K - 1 pre-activation rows of the convolved stream (the conv
tail, kept FLAT as [K - 1 rows side by side]: [slots, (K - 1) C]; a
[slots, K - 1, C] array's 3-row minor tiles are padded on the chip and
re-laid around every step's convolution, a copy in and a copy out a
layer). One token (per channel c and state index n; A = -exp(A_log)):

    S[n, c] <- exp(dt[c] A[n, c]) S[n, c] + dt[c] B[n] u[c]
    y[c] = sum_n S[n, c] C[n]

The decay differs for every (channel, state index) and every token, so
there is NO chunkwise matmul form: a prefill chunk is a true scan over
its rows. Three forms of the same recurrence, each the oracle of the
next:

  `mamba_scan`   the token-by-token scan (`lax.scan` of `_step_xla`):
                 the definition, and the path off the TPU
  `mamba_chunk`  what a prefill chunk takes: the Pallas kernel
                 `hpx_mamba_scan` (grid over blocks of rows and of
                 channels; a channel block's [N, 640] state lives in
                 registers while a loop walks the block's rows; rows at
                 and past `valid` are not walked, so padding leaves the
                 state alone)
  `mamba_step`   one token a slot, state in place: the Pallas kernel
                 `hpx_mamba_step` (grid over channel block and group of
                 8 slots; a slot's [N, C] float32 state is read once
                 and written once, aliased), `_step_xla` its oracle and
                 the path off the TPU and for channel counts that are no
                 whole 128-lane rows

B and C scale the state's ROWS: both kernels take them as columns that
are already spread over 128 lanes ([.., 2 N, 128], B's N rows then
C's), which XLA writes for a few bytes a row and the kernel multiplies
with no transpose and no lane broadcast of its own.

`mamba_mix` is what a forward body's `attend` calls: the convolution
over its tail with the bias (`kda.short_conv`), SiLU, W_x, the three
RMSNorms, W_dt, softplus, then the recurrence in the form the window's
width asks for, and the skip D u. dt, exp(dt A), the norms and the state
are float32; the two projections' operands keep the stream's type.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kda import short_conv

__all__ = ["mamba_chunk", "mamba_mix", "mamba_scan", "mamba_step"]

_LANES = 128
_SCAN_ROWS = 128        # rows one grid step of hpx_mamba_scan walks
_SCAN_CHANNELS = 640    # its channels: [16, 640] state + A = 20 vregs
_STEP_CHANNELS = 5120   # channels one grid step of hpx_mamba_step owns
_STEP_SLOTS = 8         # its slots: [8, 16, 5120] float32 = 2.6 MB


def _step_xla(u, dt, bm, cm, a, state):
    """One token: u, dt [B, C], bm, cm [B, N], a [N, C], state [B, N,
    C], all float32 -> (y [B, C], state)."""
    sn = jnp.exp(dt[:, None, :] * a) * state \
        + (dt * u)[:, None, :] * bm[..., None]
    return jnp.sum(sn * cm[..., None], axis=1), sn


def mamba_scan(u, dt, bm, cm, a, state, valid=None):
    """The recurrence token by token. u, dt [B, T, C], bm, cm [B, T,
    N], a [N, C], state [B, N, C] -> (y [B, T, C], state). Rows at and
    past `valid` (a scalar; None = all T) leave the state as it is."""
    t = u.shape[1]
    real = jnp.ones((t,), bool) if valid is None else jnp.arange(t) < valid

    def body(s, x):
        y, sn = _step_xla(x[0], x[1], x[2], x[3], a, s)
        return jnp.where(x[4], sn, s), y
    tm = lambda v: jnp.moveaxis(v, 1, 0)                   # noqa: E731
    state, y = jax.lax.scan(body, state,
                            (tm(u), tm(dt), tm(bm), tm(cm), real))
    return jnp.moveaxis(y, 0, 1), state


def _block(c: int, most: int) -> int:
    """The widest block of whole 128-lane rows that divides c channels,
    at most `most` wide."""
    return max(w for w in range(_LANES, min(c, most) + 1, _LANES)
               if c % w == 0)


def _kernel_mode(kernel: Optional[str], interpret: Optional[bool],
                 channels: int, name: str) -> Optional[bool]:
    """Which form a call takes, decided from its operands: None = the
    XLA form, else the Pallas kernel `name` with this `interpret` flag.
    Channels that are whole 128-lane rows take the kernel on a TPU
    (`kernel="pallas"` forces it, in interpret mode off the chip: the
    tests), every other call the XLA form."""
    tiles = channels % _LANES == 0
    on_tpu = jax.default_backend() == "tpu"
    if kernel is None:
        kernel = "pallas" if tiles and on_tpu else "xla"
    if kernel != "pallas":
        return None
    if not tiles:
        raise NotImplementedError(
            f"{name} (ops/mamba.py) takes channels in whole 128-lane "
            f"rows; got {channels}")
    return not on_tpu if interpret is None else interpret


def _columns(bm, cm):
    """B and C as COLUMNS spread over the lanes: [..., 2 N, 128] float32
    (row n < N holds B[n] in every lane, row N + n holds C[n])."""
    bc = jnp.concatenate([bm, cm], axis=-1)
    return jnp.broadcast_to(bc[..., None], bc.shape + (_LANES,))


def _scan_kernel(valid_ref, u_ref, dt_ref, bc_ref, a_ref, s_ref, y_ref,
                 s_out, y8, *, n: int, rows: int, groups: int):
    """One (sequence, row block, channel block) grid step. u_ref /
    dt_ref / y_ref (rows, cb); bc_ref (rows, 2 n, 128): the rows' B and
    C columns; a_ref / s_ref / s_out (blocks, n, cb): A and the state
    of EVERY channel block, resident for the whole grid (s_ref and
    s_out are the same HBM buffer; s_out carries the state from one row
    block to the next); y8 (8, cb): the outputs of the tile of 8 rows
    under way. The state of this channel block is held as `groups` (n,
    128) values across the loop, which walks the rows a tile of 8 at a
    time (a row is loaded with its tile: the chip loads no single row
    at a dynamic index) up to the tile that holds the last real row. A
    row with dt = 0 leaves the state as it is: the caller zeroes the
    padding rows' dt."""
    r, j = pl.program_id(1), pl.program_id(2)

    @pl.when(r == 0)
    def _():
        s_out[j] = s_ref[j]
    todo = jnp.clip(valid_ref[0] - r * rows, 0, rows)
    lanes = [slice(g * _LANES, (g + 1) * _LANES) for g in range(groups)]
    a = a_ref[j]
    ag = [a[:, sl] for sl in lanes]
    y_ref[...] = jnp.zeros_like(y_ref)

    def body(i, sg):
        t0 = pl.multiple_of(i * 8, 8)
        dt8 = [dt_ref[pl.ds(t0, 8), sl] for sl in lanes]
        du8 = [d * u_ref[pl.ds(t0, 8), sl] for d, sl in zip(dt8, lanes)]
        sg = list(sg)
        for k in range(8):
            bc = bc_ref[t0 + k]
            bcol, ccol = bc[:n], bc[n:]
            for g, sl in enumerate(lanes):
                sg[g] = jnp.exp(dt8[g][k:k + 1] * ag[g]) * sg[g] \
                    + du8[g][k:k + 1] * bcol
                y8[k:k + 1, sl] = jnp.sum(sg[g] * ccol, axis=0,
                                          keepdims=True)
        y_ref[pl.ds(t0, 8), :] = y8[...]
        return tuple(sg)

    s0 = s_out[j]
    sg = jax.lax.fori_loop(0, (todo + 7) // 8, body,
                           tuple(s0[:, sl] for sl in lanes))
    s_out[j] = jnp.concatenate(sg, axis=1)


def _chunk_pallas(u, dt, bm, cm, a, state, valid, interpret: bool):
    b, t, c = u.shape
    n = a.shape[0]
    if valid is not None:
        dt = jnp.where((jnp.arange(t) < valid)[None, :, None], dt, 0.0)
    cb = _block(c, _SCAN_CHANNELS)
    rows = min(-(-t // 8) * 8, _SCAN_ROWS)
    pad = -t % rows
    if pad:
        z = lambda v: jnp.pad(v, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        u, dt, bm, cm = z(u), z(dt), z(bm), z(cm)
    nb = c // cb
    blocks = lambda v: jnp.moveaxis(                        # noqa: E731
        v.reshape(v.shape[:-1] + (nb, cb)), -2, -3)    # [.., nb, n, cb]
    row = pl.BlockSpec((None, rows, cb), lambda i, r, j, *_: (i, r, j))
    whole = pl.BlockSpec((None, nb, n, cb), lambda i, r, j, *_: (i, 0, 0, 0))
    y, st = pl.pallas_call(
        functools.partial(_scan_kernel, n=n, rows=rows,
                          groups=cb // _LANES),
        name="hpx_mamba_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, (t + pad) // rows, nb),
            in_specs=[row, row,
                      pl.BlockSpec((None, rows, 2 * n, _LANES),
                                   lambda i, r, j, *_: (i, r, 0, 0)),
                      pl.BlockSpec((nb, n, cb),
                                   lambda i, r, j, *_: (0, 0, 0)),
                      whole],
            out_specs=[row, whole],
            scratch_shapes=[pltpu.VMEM((8, cb), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32),
                   jax.ShapeDtypeStruct((b, nb, n, cb), jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(jnp.reshape(t if valid is None else valid, (1,)).astype(jnp.int32),
      u, dt, _columns(bm, cm), blocks(a), blocks(state))
    return y[:, :t], jnp.moveaxis(st, -3, -2).reshape(state.shape)


def mamba_chunk(u, dt, bm, cm, a, state, valid=None,
                kernel: Optional[str] = None,
                interpret: Optional[bool] = None):
    """`mamba_scan`'s result for a prefill chunk: u, dt [B, T, C], bm,
    cm [B, T, N], a [N, C], state [B, N, C] float32 -> (y [B, T, C],
    state); rows at and past `valid` leave the state alone (their
    outputs are zeros or garbage nobody reads). `_kernel_mode` decides
    between `hpx_mamba_scan` and `mamba_scan`."""
    mode = _kernel_mode(kernel, interpret, u.shape[-1], "hpx_mamba_scan")
    if mode is None:
        return mamba_scan(u, dt, bm, cm, a, state, valid)
    return _chunk_pallas(u, dt, bm, cm, a, state, valid, mode)


def _step_kernel(u_ref, dt_ref, bc_ref, a_ref, s_ref, y_ref, s_out, *,
                 n: int, slots: int, groups: int):
    """One (channel block, slot group) grid step. u_ref / dt_ref /
    y_ref (slots, cb); bc_ref (slots, 2 n, 128): the slots' B and C
    columns; a_ref (n, cb); s_ref / s_out (slots, n, cb): the slots'
    states, the same HBM buffer."""
    for i in range(slots):
        bc = bc_ref[i]
        bcol, ccol = bc[:n], bc[n:]
        for g in range(groups):
            sl = slice(g * _LANES, (g + 1) * _LANES)
            dt = dt_ref[i:i + 1, sl]
            s = jnp.exp(dt * a_ref[:, sl]) * s_ref[i, :, sl] \
                + (dt * u_ref[i:i + 1, sl]) * bcol
            s_out[i, :, sl] = s
            y_ref[i:i + 1, sl] = jnp.sum(s * ccol, axis=0, keepdims=True)


def _step_pallas(u, dt, bm, cm, a, state, interpret: bool):
    b, c = u.shape
    n = a.shape[0]
    cb = _block(c, _STEP_CHANNELS)
    # slots a grid step: a whole sublane tile of the [B, C] rows (split
    # off the leading axis for nothing), else one
    sb = _STEP_SLOTS if b % _STEP_SLOTS == 0 else 1
    split = lambda v: v.reshape((b // sb, sb) + v.shape[1:])  # noqa: E731
    row = pl.BlockSpec((None, sb, cb), lambda j, i: (i, 0, j))
    tile = pl.BlockSpec((None, sb, n, cb), lambda j, i: (i, 0, 0, j))
    y, st = pl.pallas_call(
        functools.partial(_step_kernel, n=n, slots=sb,
                          groups=cb // _LANES),
        name="hpx_mamba_step",
        grid=(c // cb, b // sb),
        in_specs=[row, row,
                  pl.BlockSpec((None, sb, 2 * n, _LANES),
                               lambda j, i: (i, 0, 0, 0)),
                  pl.BlockSpec((n, cb), lambda j, i: (0, j)),
                  tile],
        out_specs=[row, tile],
        out_shape=[jax.ShapeDtypeStruct((b // sb, sb, c), jnp.float32),
                   jax.ShapeDtypeStruct((b // sb, sb) + state.shape[1:],
                                        jnp.float32)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
    )(split(u), split(dt), split(_columns(bm, cm)), a, split(state))
    return y.reshape(b, c), st.reshape(state.shape)


def mamba_step(u, dt, bm, cm, a, state, kernel: Optional[str] = None,
               interpret: Optional[bool] = None):
    """One token a slot: u, dt [B, C], bm, cm [B, N], a [N, C], state
    [B, N, C] float32 -> (y [B, C], state). `_kernel_mode` decides
    between `hpx_mamba_step` and `_step_xla`."""
    mode = _kernel_mode(kernel, interpret, u.shape[-1], "hpx_mamba_step")
    if mode is None:
        return _step_xla(u, dt, bm, cm, a, state)
    return _step_pallas(u, dt, bm, cm, a, state, mode)


def _conv(pre, tail, w, bias, valid):
    """`kda.short_conv` over a FLAT tail [B, (K - 1) C] (its rows side
    by side). One token a slot takes and leaves the tail's rows as
    column blocks, whole 128-lane tiles that nothing re-lays; a window
    goes through `short_conv` itself."""
    b, n, c = pre.shape
    k = w.shape[0]
    if n > 1:
        out, tail = short_conv(pre, tail.reshape(b, k - 1, c), w, valid,
                               bias)
        return out, tail.reshape(b, (k - 1) * c)
    rows = [tail[:, j * c:(j + 1) * c] for j in range(k - 1)] \
        + [pre[:, 0].astype(tail.dtype)]
    wf = w.astype(jnp.float32)
    out = sum(r.astype(jnp.float32) * wf[j] for j, r in enumerate(rows))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out[:, None], jnp.concatenate(rows[1:], axis=1)


def mamba_mix(pre, m, state, tail, valid=None, eps: float = 1e-6):
    """A Mamba layer's stateful core over a window of W tokens, as a
    forward body's `attend` runs it. pre [B, W, C]: the u stream ahead
    of the convolution; m: the mixer's leaves ("conv" [K, C], "conv_b"
    [C] where the convolution has a bias, "wx" [C, R + 2 N], "dt_norm"
    [R], "b_norm" / "c_norm" [N], "wdt" [R, C], "dt_bias" [C], "A_log"
    [N, C], "D" [C]); state [B, N, C] float32; tail [B, (K - 1) C].
    `valid` (a scalar, or None for all W): the window's real rows; the
    rest are padding that neither the state nor the tail may see.
    Returns (y [B, W, C] float32, (state, tail)): the scan's output and
    the skip D u, ahead of the gate."""
    f32 = jnp.float32
    n, r = state.shape[1], m["wdt"].shape[0]
    act, tail = _conv(pre, tail, m["conv"], m.get("conv_b"), valid)
    u = jax.nn.silu(act).astype(pre.dtype)
    dbc = jnp.dot(u, m["wx"], preferred_element_type=f32)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + eps) * scale.astype(f32)
    dt_r = rms(dbc[..., :r], m["dt_norm"])
    bm = rms(dbc[..., r:r + n], m["b_norm"])
    cm = rms(dbc[..., r + n:], m["c_norm"])
    dt = jax.nn.softplus(
        jnp.dot(dt_r.astype(pre.dtype), m["wdt"],
                preferred_element_type=f32) + m["dt_bias"].astype(f32))
    a = -jnp.exp(m["A_log"].astype(f32))
    uf = u.astype(f32)
    if pre.shape[1] == 1:
        y, state = mamba_step(uf[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], a,
                              state)
        y = y[:, None]
    else:
        y, state = mamba_chunk(uf, dt, bm, cm, a, state, valid)
    return y + m["D"].astype(f32) * uf, (state, tail)
