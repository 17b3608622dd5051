"""Pallas flash-attention kernels (single chip): forward AND backward.

The MXU-resident inner loop for ops/attention.py: Q/K/V stream through
VMEM in (block_q × block_k) tiles over a sequential TPU grid; the
online-softmax state (acc, m, l) lives in VMEM scratch and carries
across the K dimension of the grid (TPU grids execute in order, so the
innermost axis is the flash loop). Causal blocks below the diagonal are
skipped entirely (`pl.when`), not just masked — ~2× fewer tiles.

Layout: [B, S, N, H] public shape; kernel works on [B*N, S, H] with the
(S, H) tiles as MXU operands (H = 64/128 hits the 128-lane layout).

`flash_attention` carries a `jax.custom_vjp`: the forward saves the
per-row logsumexp L = m + log(l) (lane-replicated, the same layout the
scratch uses), and the backward is the standard two-pass flash
backward — one kernel accumulates dQ (grid inner axis walks K blocks),
a second accumulates dK/dV (inner axis walks Q blocks), both
recomputing p = exp(s − L) tile-by-tile so nothing O(S²) is ever
materialized. Both backward kernels take the q/k global offset `d` as
a scalar-prefetch operand, so the SAME kernels serve the ring-attention
backward (ops/attention._ring_flash), where d is traced per ring step.

`flash_attention` falls back to interpret mode off-TPU so the same
kernels are testable on the CPU mesh (pallas interpret semantics).
"""

from __future__ import annotations

import functools
import json
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_chunk",
           "flash_attention_bwd", "fused_paged_attention",
           "fused_paged_online_attention",
           "paged_online_scratch_shapes",
           "resolve_blocks", "resolve_paged_block"]


# ---------------------------------------------------------------------------
# forward block-size selection
# ---------------------------------------------------------------------------
# Tile shape is THE forward-MFU lever at short S (causal diagonal tiles
# are half-masked: with 1024^2 blocks at S=4096 a fifth of the MXU work
# is wasted; smaller block_k trims the diagonal waste but adds per-tile
# loop overhead — the right point is measured, not derived). Resolution
# order: explicit arg > HPX_FLASH_BLOCK_Q/K env > measured table
# (benchmarks/flash_tune.py writes flash_blocks.json next to this file
# after sweeping on real hardware) > 1024x1024 default.

_BLOCKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "flash_blocks.json")
_blocks_table: Optional[dict] = None


def _load_blocks_table() -> dict:
    global _blocks_table
    if _blocks_table is None:
        try:
            with open(_BLOCKS_FILE) as f:
                _blocks_table = {k: tuple(v)
                                 for k, v in json.load(f).items()}
        except (OSError, ValueError):
            _blocks_table = {}
    return _blocks_table


def resolve_blocks(seq_q: int, seq_k: int,
                   causal: bool) -> Tuple[int, int]:
    """The (block_q, block_k) the forward kernel will use for this
    shape class when the caller doesn't pass blocks explicitly."""
    table = _load_blocks_table()
    bq, bk = table.get(f"{seq_q}x{seq_k}x{int(causal)}", (1024, 1024))
    # env overrides are PER-DIMENSION: the unset one keeps the
    # table/default value rather than snapping back to 1024
    env_q = os.environ.get("HPX_FLASH_BLOCK_Q")
    env_k = os.environ.get("HPX_FLASH_BLOCK_K")
    if env_q:
        bq = int(env_q)
    if env_k:
        bk = int(env_k)
    return bq, bk


def _sds(shape, dtype, *operands):
    """ShapeDtypeStruct whose varying-mesh-axes type is the union of the
    operands' — required when a pallas_call runs INSIDE a vma-checked
    shard_map (the kernel output varies over whatever its inputs do)."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)

_NEG_INF = -1e30     # large-negative instead of -inf: exp() stays exact,
                     # and (m_prev - m_new) never produces inf - inf


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest,
                  block_q: int, block_k: int, nk: int, causal: bool,
                  scale: float, seq_q: int, seq_k: int,
                  save_res: bool = False):
    if save_res:
        lse_ref, acc_ref, m_ref, l_ref = rest
    else:
        acc_ref, m_ref, l_ref = rest
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    # bottom-right causal alignment (matches reference_attention /
    # blockwise_attention): query qi attends keys kj <= qi + (sk - sq),
    # so a cross-attention suffix lines up with the END of the keys.
    off = seq_k - seq_q

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: the whole tile is masked iff its smallest k position
    # exceeds the largest (offset-adjusted) q position
    if causal:
        live = ik * block_k <= iq * block_q + block_q - 1 + off
    else:
        live = True

    @pl.when(live)
    def _compute():
        # keep q/k/v in their storage dtype for the dots: bf16 operands
        # run the MXU at full rate; preferred_element_type=f32 keeps the
        # ACCUMULATION in fp32 (the flash-attention numerics contract).
        # The scale is applied to the f32 scores, not the bf16 operands.
        q = q_ref[0]                               # (block_q, H)
        k = k_ref[0]                               # (block_k, H)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        # in-tile masks: sequence padding tail + causal diagonal
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, kpos <= qpos + off)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]                      # (block_q, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                # masked lanes: exact 0

        # delayed rescaling: the (corr = exp(m_prev - m_new)) multiply
        # of acc and l is an exact no-op on every tile where the running
        # max didn't move (corr == exp(0) == 1) — common once the max
        # stabilizes along the k walk. Rescale CONDITIONALLY (one scalar
        # reduction gates a (block_q, H) + (block_q, 128) VPU multiply),
        # then accumulate unconditionally.
        @pl.when(jnp.logical_not((m_new == m_prev).all()))
        def _rescale():
            corr = jnp.exp(m_prev - m_new)
            acc_ref[:] = acc_ref[:] * corr
            l_ref[:] = l_ref[:] * corr

        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = l_ref[:] + jnp.broadcast_to(
            p.sum(axis=1, keepdims=True), l_ref.shape)
        # second matmul in the storage dtype too (p cast bf16 when v is
        # bf16 — standard flash practice), still accumulated in fp32
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            p.astype(v.dtype) if v.dtype == jnp.bfloat16 else p, v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _finish():
        l = l_ref[:, :1]
        den = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[:] / den).astype(o_ref.dtype)
        if save_res:
            # logsumexp per row, lane-replicated; fully-masked rows
            # (l == 0: sequence padding, causal rows with no keys) get
            # L = 0 so the backward's exp(s - L) stays finite — their
            # contributions vanish through masks / zero cotangents.
            lf = l_ref[:]
            safe = jnp.where(lf > 0, lf, 1.0)
            lse_ref[0] = jnp.where(lf > 0, m_ref[:] + jnp.log(safe), 0.0)


def _kernel_layout(x: jax.Array) -> jax.Array:
    """[B, S, N, H] -> [B*N, S, H] (the MXU-operand layout)."""
    b, s, n, h = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * n, s, h)


def _pad_seq(x: jax.Array, pad: int) -> jax.Array:
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _kv_row_map(q_heads: int, kv_heads: int):
    """Grid-row remap for grouped-query attention: q row bn = bi*Nq + ni
    reads K/V row bi*Nkv + ni // (Nq/Nkv). Identity when heads match —
    GQA costs ONLY this index arithmetic, never a materialized repeat."""
    if q_heads == kv_heads:
        return lambda bn: bn
    group = q_heads // kv_heads
    return lambda bn: (bn // q_heads) * kv_heads + (bn % q_heads) // group


def _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                    save_res):
    b, sq, n, h = q.shape
    sk = k.shape[1]
    nkv = k.shape[2]
    if v.shape[2] != nkv:
        raise ValueError(f"k heads ({nkv}) != v heads ({v.shape[2]})")
    if n % nkv:
        raise ValueError(f"q heads ({n}) not a multiple of kv heads "
                         f"({nkv})")
    kv_of = _kv_row_map(n, nkv)

    block_q = min(block_q, max(sq, 8))
    block_k = min(block_k, max(sk, 8))
    pq = -sq % block_q
    pk = -sk % block_k

    qt = _pad_seq(_kernel_layout(q), pq)
    kt = _pad_seq(_kernel_layout(k), pk)
    vt = _pad_seq(_kernel_layout(v), pk)
    nq = qt.shape[1] // block_q
    nk = kt.shape[1] // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, nk=nk,
        causal=causal, scale=1.0 / math.sqrt(h), seq_q=sq, seq_k=sk,
        save_res=save_res)

    out_specs = [pl.BlockSpec((1, block_q, h),
                              lambda bn, iq, ik: (bn, iq, 0))]
    out_shape = [_sds((b * n, nq * block_q, h), q.dtype, q, k, v)]
    if save_res:
        out_specs.append(pl.BlockSpec((1, block_q, 128),
                                      lambda bn, iq, ik: (bn, iq, 0)))
        out_shape.append(
            _sds((b * n, nq * block_q, 128), jnp.float32, q, k, v))

    res = pl.pallas_call(
        kernel,
        name="hpx_flash_fwd",
        grid=(b * n, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, h), lambda bn, iq, ik: (bn, iq, 0)),
            pl.BlockSpec((1, block_k, h),
                         lambda bn, iq, ik: (kv_of(bn), ik, 0)),
            pl.BlockSpec((1, block_k, h),
                         lambda bn, iq, ik: (kv_of(bn), ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, h), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)

    out = res[0][:, :sq].reshape(b, n, sq, h)
    out = jnp.moveaxis(out, 1, 2)
    if save_res:
        return out, res[1][:, :sq]          # L in kernel layout
    return out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, block_q, block_k, interpret):
    return _flash_fwd_impl(q, k, v, causal, block_q, block_k, interpret,
                           save_res=False)


def _fa_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_q, block_k,
                               interpret, save_res=True)
    # keep ONE lane of the lane-replicated logsumexp as the residual
    # (128x smaller held fwd->bwd); _fa_bwd re-broadcasts
    return out, (q, k, v, out, lse[:, :, :1])


def _fa_bwd(causal, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    b, sq, n, h = q.shape
    sk = k.shape[1]
    nkv = k.shape[2]
    # backward tiles keep four (bq, bk) f32 intermediates live in VMEM
    # (s, p, dp, ds) — cap blocks at 512 so 512x512x4B x4 = 4 MB fits
    bq = min(block_q, 512, max(sq, 8))
    bk = min(block_k, 512, max(sk, 8))
    pq = -sq % bq
    pk = -sk % bk

    qt = _pad_seq(_kernel_layout(q), pq)
    dot_ = _pad_seq(_kernel_layout(g.astype(q.dtype)), pq)
    ot = _pad_seq(_kernel_layout(o), pq)
    kt = _pad_seq(_kernel_layout(k), pk)
    vt = _pad_seq(_kernel_layout(v), pk)
    lp = jnp.pad(lse, ((0, 0), (0, pq), (0, 0))) if pq else lse
    delta128, lse128 = bwd_prep(dot_, ot, lp)

    dq, dk, dv = flash_attention_bwd(
        qt, kt, vt, dot_, delta128, lse128, sk - sq, causal=causal,
        block_q=bq, block_k=bk, interpret=interpret, seq_k=sk,
        q_heads=n, kv_heads=nkv)

    def back(x, s, nh, dtype):
        return jnp.moveaxis(
            x[:, :s].reshape(b, nh, s, h), 1, 2).astype(dtype)

    return (back(dq, sq, n, q.dtype), back(dk, sk, nkv, k.dtype),
            back(dv, sk, nkv, v.dtype))


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """[B, S, N, H] flash attention as one pallas_call per device.

    block_q/block_k default to resolve_blocks' per-shape-class choice
    (env override / measured flash_tune table / 1024). S is padded to the
    block size internally; H should be a multiple of the 128-lane
    layout's tile for best MXU utilization (64/128).
    Differentiable: jax.custom_vjp routes reverse-mode through the
    pallas backward kernels (flash_attention_bwd).

    GQA/MQA: k/v may carry FEWER heads than q (N % Nkv == 0). K/V tiles
    are shared across each q-head group via BlockSpec index remapping —
    no materialized repeat, so the serving-standard grouped layouts get
    the full KV-bandwidth saving; backward group-sums dK/dV.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if block_q is None or block_k is None:
        rq, rk = resolve_blocks(q.shape[1], k.shape[1], causal)
        block_q = rq if block_q is None else block_q
        block_k = rk if block_k is None else block_k
    return _flash_attention(q, k, v, causal, block_q, block_k, interpret)


def bwd_prep(dot_, ot, lse1):
    """flash_attention_bwd's input contract, in one place: delta =
    rowsum(do * o) in one fused XLA pass (the kernels never touch o),
    and lse/delta broadcast to the [bn, sq, 128] lane-replicated f32
    layout the kernels' (1, block_q, 128) tiles expect. `lse1` is the
    single-lane [bn, sq, 1] residual the forward saves."""
    delta = (dot_.astype(jnp.float32) * ot.astype(jnp.float32)
             ).sum(axis=-1, keepdims=True)
    shape = (dot_.shape[0], dot_.shape[1], 128)
    return (jnp.broadcast_to(delta, shape),
            jnp.broadcast_to(lse1, shape))


# ---------------------------------------------------------------------------
# backward kernels — standard two-pass flash backward
# ---------------------------------------------------------------------------
#
# Math (s = scale * q k^T; p = softmax rows; o = p v; L = row logsumexp):
#   p     = exp(s - L)                      (recomputed per tile, stable:
#                                            s - L <= -log l <= 0)
#   delta = rowsum(do * o)                  (= p . dp per row)
#   ds    = p * (dp - delta) * scale,  dp = do v^T
#   dq    = ds k        dk = ds^T q        dv = p^T do
#
# Both kernels take the q/k global offset d (causal: kpos <= qpos + d)
# as scalar prefetch so the ring backward can trace it per step.

def _flash_bwd_dq_kernel(d_ref, q_ref, k_ref, v_ref, do_ref,
                         delta_ref, lse_ref, dq_ref, dq_s, *,
                         block_q: int, block_k: int, nk: int,
                         causal: bool, scale: float, seq_k: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    d = d_ref[0]

    @pl.when(ik == 0)
    def _init():
        dq_s[:] = jnp.zeros_like(dq_s)

    if causal:
        live = ik * block_k <= iq * block_q + block_q - 1 + d
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, kpos <= qpos + d)
        p = jnp.exp(s - lse_ref[0][:, :1])
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dq_s[:] = dq_s[:] + jax.lax.dot_general(
            ds.astype(k.dtype) if k.dtype == jnp.bfloat16 else ds, k,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _store():
        dq_ref[0] = dq_s[:]


def _flash_bwd_dkv_kernel(d_ref, q_ref, k_ref, v_ref, do_ref,
                          delta_ref, lse_ref, dk_ref, dv_ref, dk_s,
                          dv_s, *, block_q: int, block_k: int, nq: int,
                          causal: bool, scale: float, seq_k: int):
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    d = d_ref[0]

    @pl.when(iq == 0)
    def _init():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    if causal:
        live = ik * block_k <= iq * block_q + block_q - 1 + d
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = kpos < seq_k
        if causal:
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, kpos <= qpos + d)
        p = jnp.exp(s - lse_ref[0][:, :1])
        p = jnp.where(mask, p, 0.0)
        # dv += p^T do  (contract the q dimension)
        dv_s[:] = dv_s[:] + jax.lax.dot_general(
            p.astype(do.dtype) if do.dtype == jnp.bfloat16 else p, do,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0][:, :1]) * scale
        dk_s[:] = dk_s[:] + jax.lax.dot_general(
            ds.astype(q.dtype) if q.dtype == jnp.bfloat16 else ds, q,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _store():
        dk_ref[0] = dk_s[:]
        dv_ref[0] = dv_s[:]


def flash_attention_bwd(q, k, v, do, delta, lse, d,
                        causal: bool = False, block_q: int = 512,
                        block_k: int = 512,
                        interpret: Optional[bool] = None,
                        seq_k: Optional[int] = None,
                        q_heads: int = 1, kv_heads: int = 1):
    """Flash-attention backward in kernel-native layout.

    q/do: [bn, sq, h]; k/v: [bn_kv, sk, h]; delta/lse: [bn, sq, 128]
    f32, lane-replicated — lse is the forward's row logsumexp, delta is
    rowsum(do * o) precomputed once by the caller (one fused XLA pass;
    the kernels never touch o). d: int32 scalar (traced OK) =
    q_global_start - k_global_start, the causal offset. sq/sk must be
    multiples of the block sizes (callers pad; zero-padded do rows and
    k/v rows contribute exact zeros).

    GQA: with q_heads > kv_heads (q rows bn = b*q_heads, k/v rows
    bn_kv = b*kv_heads), K/V tiles are index-remapped per q row and the
    per-q-head dK/dV partials are group-summed before returning.

    Returns (dq [bn,sq,h], dk [bn_kv,sk,h], dv [bn_kv,sk,h]) — float32,
    so ring steps can accumulate partials without bf16 round-off.
    """
    bn, sq, h = q.shape
    sk = k.shape[1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if seq_k is None:
        seq_k = sk
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"bwd seq not block-aligned: sq={sq}/{block_q}, "
            f"sk={sk}/{block_k}")
    nq = sq // block_q
    nk = sk // block_k
    scale = 1.0 / math.sqrt(h)
    f32 = jnp.float32
    darr = jnp.asarray([d], jnp.int32).reshape(1)
    kv_of = _kv_row_map(q_heads, kv_heads)

    q_at_iq = pl.BlockSpec((1, block_q, h),
                           lambda bn_, iq, ik, *_: (bn_, iq, 0))
    k_at_ik = pl.BlockSpec((1, block_k, h),
                           lambda bn_, iq, ik, *_: (kv_of(bn_), ik, 0))
    l_at_iq = pl.BlockSpec((1, block_q, 128),
                           lambda bn_, iq, ik, *_: (bn_, iq, 0))

    dq = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k,
            nk=nk, causal=causal, scale=scale, seq_k=seq_k),
        name="hpx_flash_bwd_dq",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bn, nq, nk),
            in_specs=[q_at_iq, k_at_ik, k_at_ik, q_at_iq, l_at_iq,
                      l_at_iq],
            out_specs=[q_at_iq],
            scratch_shapes=[
                pltpu.VMEM((block_q, h), f32),
            ],
        ),
        out_shape=[_sds((bn, sq, h), f32, q, k, v, do, delta, lse)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(darr, q, k, v, do, delta, lse)[0]

    # dk/dv grid: k blocks on the parallel axis, q blocks innermost.
    # Outputs are PER Q ROW (bn) — with GQA several q rows share a K/V
    # row, and overlapping output maps across a parallel grid axis
    # would race; the group-sum below folds them to per-KV-row grads.
    q_at_iq2 = pl.BlockSpec((1, block_q, h),
                            lambda bn_, ik, iq, *_: (bn_, iq, 0))
    kin_at_ik2 = pl.BlockSpec((1, block_k, h),
                              lambda bn_, ik, iq, *_: (kv_of(bn_), ik, 0))
    kout_at_ik2 = pl.BlockSpec((1, block_k, h),
                               lambda bn_, ik, iq, *_: (bn_, ik, 0))
    l_at_iq2 = pl.BlockSpec((1, block_q, 128),
                            lambda bn_, ik, iq, *_: (bn_, iq, 0))

    dk, dv = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k,
            nq=nq, causal=causal, scale=scale, seq_k=seq_k),
        name="hpx_flash_bwd_dkv",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bn, nk, nq),
            in_specs=[q_at_iq2, kin_at_ik2, kin_at_ik2, q_at_iq2,
                      l_at_iq2, l_at_iq2],
            out_specs=[kout_at_ik2, kout_at_ik2],
            scratch_shapes=[
                pltpu.VMEM((block_k, h), f32),
                pltpu.VMEM((block_k, h), f32),
            ],
        ),
        out_shape=[_sds((bn, sk, h), f32, q, k, v, do, delta, lse),
                   _sds((bn, sk, h), f32, q, k, v, do, delta, lse)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(darr, q, k, v, do, delta, lse)

    if q_heads != kv_heads:
        group = q_heads // kv_heads
        b = bn // q_heads
        dk = dk.reshape(b, kv_heads, group, sk, h).sum(axis=2)
        dk = dk.reshape(b * kv_heads, sk, h)
        dv = dv.reshape(b, kv_heads, group, sk, h).sum(axis=2)
        dv = dv.reshape(b * kv_heads, sk, h)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# chunked variant with carry I/O — the ring-attention inner kernel
# ---------------------------------------------------------------------------

def _flash_chunk_kernel(d_ref, q_ref, k_ref, v_ref, acc_in, m_in, l_in,
                        acc_out, m_out, l_out, acc_s, m_s, l_s, *,
                        block_q: int, block_k: int, nk: int,
                        causal: bool, scale: float):
    """One K/V CHUNK folded into an online-softmax carry.

    Same tile loop as _flash_kernel, but the (acc, m, l) state arrives
    as inputs and leaves UNNORMALIZED as outputs, so a ring step
    (ops/attention.py ring_attention_sharded) can fold one rotating
    chunk per call. `d_ref` (SMEM) holds the TRACED relative offset
    d = q_global_start - k_global_start: causal masking inside the
    kernel is kpos <= qpos + d, which stays correct whichever ring step
    the chunk arrives on. m/l travel in a 128-lane replicated layout
    ([bn, s, 128]) to match the VMEM scratch tiling.
    """
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    d = d_ref[0]

    @pl.when(ik == 0)
    def _load_carry():
        acc_s[:] = acc_in[0]
        m_s[:] = m_in[0]
        l_s[:] = l_in[0]

    if causal:
        live = ik * block_k <= iq * block_q + block_q - 1 + d
    else:
        live = True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        if causal:
            kpos = ik * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            qpos = iq * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            mask = kpos <= qpos + d
            s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)

        # delayed rescaling, same as _flash_kernel: the corr multiply is
        # an exact no-op (corr == 1) whenever the running max held still
        @pl.when(jnp.logical_not((m_new == m_prev).all()))
        def _rescale():
            corr = jnp.exp(m_prev - m_new)
            acc_s[:] = acc_s[:] * corr
            l_s[:] = l_s[:] * corr

        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = l_s[:] + jnp.broadcast_to(
            p.sum(axis=1, keepdims=True), l_s.shape)
        acc_s[:] = acc_s[:] + jax.lax.dot_general(
            p.astype(v.dtype) if v.dtype == jnp.bfloat16 else p, v,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _store_carry():
        acc_out[0] = acc_s[:]
        m_out[0] = m_s[:]
        l_out[0] = l_s[:]


def flash_attention_chunk(q, k, v, acc, m, l, d,
                          causal: bool = False, block_q: int = 1024,
                          block_k: int = 512,
                          interpret: Optional[bool] = None,
                          q_heads: int = 1, kv_heads: int = 1):
    """Fold one K/V chunk into an online-softmax carry (pallas).

    Layouts (kernel-native, NO [B,S,N,H] public shape here — the ring
    transposes once outside its scan): q [bn, sq, h]; k/v [bn_kv, sk,
    h]; acc [bn, sq, h] f32; m/l [bn, sq, 128] f32 (lane-replicated).
    `d` is a traced int32 scalar: q_global_start - k_global_start.
    Returns updated (acc, m, l), unnormalized. Finalize with
    acc / max(l, eps) outside (ops/attention._finish agrees).

    GQA: q_heads > kv_heads reads shared K/V tiles via the same
    BlockSpec row remap plain flash uses (_kv_row_map) — grouped
    chunks stay grouped, which is what keeps the ring's ppermute
    volume at the kv-head size.

    sq and sk must be multiples of the (clamped) block sizes — ring
    chunks are equal by construction. 1024 x 1024 tiles need 19.4 MB
    of the chip's 16 MB scoped VMEM (the carry streams in AND out), so
    block_k defaults to 512.
    """
    import math as _math
    bn, sq, h = q.shape
    sk = k.shape[1]
    want = bn // q_heads * kv_heads
    if k.shape[0] != want:
        # loud in the equal-heads case too: grouped K/V passed with the
        # default params would otherwise be silently misread (pallas
        # clamps out-of-range block rows instead of raising)
        raise ValueError(
            f"chunk rows: k has {k.shape[0]}, expected {want} "
            f"(q rows {bn}, q_heads {q_heads}, kv_heads {kv_heads})")
    kv_of = _kv_row_map(q_heads, kv_heads)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    if sq % block_q or sk % block_k:
        raise ValueError(
            f"chunk sizes must divide blocks: sq={sq}/{block_q}, "
            f"sk={sk}/{block_k}")
    nq = sq // block_q
    nk = sk // block_k

    kernel = functools.partial(
        _flash_chunk_kernel, block_q=block_q, block_k=block_k, nk=nk,
        causal=causal, scale=1.0 / _math.sqrt(h))

    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bn, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, h), lambda bn_, iq, ik, *_: (bn_, iq, 0)),
            pl.BlockSpec((1, block_k, h),
                         lambda bn_, iq, ik, *_: (kv_of(bn_), ik, 0)),
            pl.BlockSpec((1, block_k, h),
                         lambda bn_, iq, ik, *_: (kv_of(bn_), ik, 0)),
            pl.BlockSpec((1, block_q, h), lambda bn_, iq, ik, *_: (bn_, iq, 0)),
            pl.BlockSpec((1, block_q, 128),
                         lambda bn_, iq, ik, *_: (bn_, iq, 0)),
            pl.BlockSpec((1, block_q, 128),
                         lambda bn_, iq, ik, *_: (bn_, iq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, h), lambda bn_, iq, ik, *_: (bn_, iq, 0)),
            pl.BlockSpec((1, block_q, 128),
                         lambda bn_, iq, ik, *_: (bn_, iq, 0)),
            pl.BlockSpec((1, block_q, 128),
                         lambda bn_, iq, ik, *_: (bn_, iq, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, h), f32),
            pltpu.VMEM((block_q, 128), f32),
            pltpu.VMEM((block_q, 128), f32),
        ],
    )

    acc2, m2, l2 = pl.pallas_call(
        kernel,
        name="hpx_flash_chunk",
        grid_spec=grid_spec,
        out_shape=[
            _sds((bn, sq, h), f32, q, k, v, acc, m, l),
            _sds((bn, sq, 128), f32, q, k, v, acc, m, l),
            _sds((bn, sq, 128), f32, q, k, v, acc, m, l),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.asarray([d], jnp.int32).reshape(1), q, k, v, acc, m, l)
    return acc2, m2, l2


# ---------------------------------------------------------------------------
# fused paged decode attention — the block-table kernels
# ---------------------------------------------------------------------------
#
# The serving decode hot loop: instead of materializing a
# [B, max_blocks*block_size, n_kv, head_dim] gather per layer per step
# (ops/paged_attention.gather_block_kv — the XLA oracle), the kernels
# walk the int32 block table DIRECTLY. Grid (slot, kv-head, block);
# the K/V BlockSpec index_map resolves logical block i of slot b to its
# physical pool block via the scalar-prefetched table
# (table_ref[b, i]), so each (block_size, head_dim) tile streams
# HBM -> VMEM exactly once and no logical view ever touches HBM.
#
# Pools are laid out [num_blocks, n_kv, block_size, head_dim] — heads
# AHEAD of rows — so the streamed tile's last two dimensions are the
# pool's own (block_size, head_dim): the TPU lowering takes a block
# only when its last two dimensions are multiples of (8, 128) or the
# array's full extents, and one head of a rows-ahead-of-heads pool is
# neither for n_kv > 1.
#
# Quantized (int8/fp8) pools dequantize AT THE VMEM BOUNDARY:
# per-(block, kv-head) absmax scales ride a sibling [num_blocks, n_kv]
# f32 array; the kernel streams the 8-row group holding the table's
# block (the same lowering rule: 8 rows x all heads) and selects its
# (block, head) entry by mask, and (q * scale).astype(q.dtype) happens
# on the freshly-landed tile — HBM moves 1 byte/elem instead of 2
# (bf16) or 4 (f32).
#
# TWO kernels share that walk, trading VMEM for exactness differently:
#
# `fused` (_paged_kernel) — the BITWISE reference. The fused path must
# be able to emit the SAME TOKENS as the gather oracle and the dense
# server with bitwise-equal scores and softmax (tests pin dense ==
# gather-paged == fused-paged greedy/sampled/speculative), so it
# spends VMEM on exactness: the dequantized K and V rows are banked
# into two (S, hd) scratches along the sequential block axis (row
# offsets are block multiples, which the sublane axis takes; a lane
# offset of one block would not lower), and the LAST block step applies
# the oracle's op order verbatim — one (W*g, hd) x (hd, S) score dot,
# mask to -inf, f32 softmax over the full row, cast to q.dtype, one
# (W*g, S) x (S, hd) dot. Scores and
# softmax are bitwise-equal to the oracle's; the final PV contraction
# is the same f32 math but XLA schedules a batched einsum's reduction
# differently from a 2-D dot, so logits agree to ~1 ulp rather than
# bit-for-bit — the same variation the repo already carries between
# its own programs (the oracle's eager and jitted logits differ by the
# same amount, as do its W=1 decode and W-window verify gemms), and
# the reason every serving equivalence contract here is pinned at
# exact TOKENS plus ulp-tight logits. VMEM cost is O(S * hd) per
# (slot, head) step plus the (W*g, S) score row at the last step, which
# is what CAPS the usable context: S rides the scratch, so smax can't
# outgrow VMEM.
#
# `fused_online` (_paged_online_kernel) — the O(block) roofline leg.
# The classic flash-attention move applied to the paged walk: the
# kernel carries only the (acc, m, l) online-softmax state —
# (W*g, hd) f32 accumulator plus two lane-replicated (W*g, 128)
# running max/denominator rows — and each K/V block tile is consumed
# the moment it lands (Pallas double-buffers the streamed BlockSpec
# tiles against compute, exactly like the flash kernels above). NO
# scratch has sequence extent, so VMEM no longer bounds smax and the
# HBM traffic is unchanged — pure roofline win at long context. The
# price is the numerics contract: a running-max softmax rescales
# partial accumulators (exp(m_prev - m_new) multiplies) and its
# reduction ORDER differs from the oracle's one-pass jax.nn.softmax,
# so results drift O(eps * nblk) from the oracle — a few ulp at
# serving shapes, NOT bitwise. The equivalence gate for fused_online
# is therefore tolerance-budgeted (logits allclose at a few-ulp rtol;
# greedy tokens identical across the dense/paged/spec sweep), with
# `fused` kept as the bitwise reference. Masking stays EXACT-zero
# (p = where(live, exp(s - m), 0)), so trash/pad blocks contribute
# exactly 0.0 probability mass in both kernels, and both share the
# per-window-row horizon `kpos <= pos0 + wrow` for the decode (W=1)
# and spec-verify window entry points.
#
# Pick `fused` when byte-identity with the dense/gather server is the
# contract (rollback-heavy speculation audits, A/B token equality);
# pick `fused_online` when context length presses VMEM — the knob is
# hpx.serving.paged_kernel = fused | fused_online | gather | auto.
#
# THE WALK'S BOUND (`_paged_live_kernel`). The grid walk above visits
# every table entry of every slot, and a grid step costs its ~0.1 us
# whether the entry is live or the trash block (PERF.md section 6, PR
# 31): on the serving cells' tables, a quarter to a third live, the
# kernel ran at 2-3% of its roofline. So a `fused` call over
# unquantized pools whose head_dim is a multiple of 128 (what
# `_fused_paged_call` can read off its operands; every call the
# benchmark's serving cells, the verify window and the mesh form make)
# takes a second launch path under the same names: grid (slot, group
# of kv heads) only, the pools LEFT IN HBM, and the kernel itself
# copies (`pltpu.make_async_copy`) physical block table[b, j] into
# rows j*block_size .. of the banks for j below
# `_walk_entries` = min((pos0 + W - 1) // block_size + 1, max_blocks):
# a trip count read from DATA, so one compiled program serves every
# length. The tail of the table is never fetched. The finish is the
# grid walk's, op for op, a head at a time over that head's whole
# (S, hd) bank (its fixed cost a (slot, kv head): ~0.2 ns a bank row).
#
# ONE COPY AN ENTRY FOR A GROUP OF HEADS (PR 35). Every kv head of a
# slot walks the SAME entries (the bound depends on pos0 alone), and the
# n_kv tiles of one block lie back to back in the pool ([num_blocks,
# n_kv, block_size, head_dim]). One (block_size, head_dim) tile a
# descriptor is 4 KB in bfloat16, and the copies, started and waited
# one by one in two scalar loops, were the larger part of a grid step
# (0.043 us a table entry, K + V: 190 GB/s of 819; PERF.md section 6).
# So a grid step owns `hg` kv heads and copies an entry ONCE for all of
# them: `pool.at[blk, h0 : h0 + hg]` -> `bank.at[:, rows, :]`, banks
# (hg, S, hd). The source is one contiguous run of hg tiles, the
# destination hg runs a bank apart, which the DMA engine strides over;
# the entry-major alternative (max_blocks, hg, block_size, head_dim)
# makes both sides contiguous but hands the finish a head's rows in
# max_blocks pieces, and measured no faster (PERF.md section 6), so the
# finish keeps reading a head's bank as one array, as the grid walk's
# does. `hg` is `walk_heads_per_copy`: the largest divisor of the
# call's n_kv whose banks fit `_WALK_VMEM_BUDGET`, read off the
# operands (StarCoder2-3B's 2 heads: 2; Laguna-XS.2's 8: 8 on the full
# tables and on the ring; 1 where a bank is too long to share VMEM,
# which is PR 31's kernel); the launch states its VMEM limit from the
# same bytes. Starts, waits and table reads fall by `hg`; the bytes
# stay; the semaphore rule stays word for word.
#
# TWO SETS OF BANKS (PR 50). A grid step ran its copies and then its
# finish, and because the kernel issues its own copies nothing of
# Pallas' pipeline overlapped them across grid steps either: on EvaByte
# (32 heads over 3,200 bank rows) 33 us of copies and 21 us of finish a
# step, one after the other, the kernel at 55 % of its bytes. So the
# banks are (2, hg, S, hd) a pool and grid step n, which owns set
# n % 2, STARTS THE COPIES OF STEP n + 1 INTO THE OTHER SET before it
# waits for its own and runs its finish: the next step's (slot, group)
# follows from n + 1, its `_walk_entries` and its table entries are
# scalar-prefetched, so they are readable a step early. The call's
# first step starts its own copies too; the last starts none (a trip
# count of 0, not a branch); a step's waits rebuild the descriptors the
# step before started. The semaphore rule holds PER SET: DMA semaphores
# are (set, pool), the next step's copies signal the OTHER set's, and
# nothing is read from a set's banks before every wait of that set's
# copies has returned. A set is written again only by the step AFTER
# the one that read it, which starts no copy before its own body
# begins. The chain needs the grid IN ORDER, so both axes are
# "arbitrary"; a v5e chip has one TensorCore and loses nothing, a
# two-core part would want the slot axis split by hand (no cell and no
# mesh test runs one). `walk_heads_per_copy` counts both sets under the
# same budget, so `hg` halves where one set filled it: EvaByte 32 -> 16
# (two sets of 16 heads' banks are the bytes one set of 32 was; a slot
# is two grid steps and an entry two 256 KB copies a pool, still paced
# by bytes); StarCoder2-3B's 2 and Laguna's 8 stay. A bank too long for
# two sets at one head (smax past 126,976 in bfloat16) is the
# compiler's to refuse, as one too long for one set (229,376) was. The
# finish is untouched: the same ops in the same order under the same
# masks, so the rows are bit for bit what one set gave.
#
# Every other call keeps the grid walk, for reasons
# that conflict with this path's: a one-byte pool would need a staging
# bank and a per-entry dequantization between landing and banking
# (which costs more than the copy it halves, and whose semaphore
# discipline is easy to get wrong); the chip copies out of an HBM array
# only whole 128-lane rows, so a 64-wide head cannot be sliced out of
# its pool at all; `fused_online` gains nothing at a full table.

_PAGED_BLOCKS_FILE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "paged_blocks.json")
_paged_blocks_table: Optional[dict] = None


def _load_paged_blocks() -> dict:
    global _paged_blocks_table
    if _paged_blocks_table is None:
        try:
            with open(_PAGED_BLOCKS_FILE) as f:
                _paged_blocks_table = dict(json.load(f))
        except (OSError, ValueError):
            _paged_blocks_table = {}
    return _paged_blocks_table


def resolve_paged_block(head_dim: int, kv_dtype: str = "bf16") -> tuple:
    """The cache block_size `hpx.cache.block_size=auto` resolves to,
    with its source: ``(value, 'env' | 'seed' | 'default')``.

    Resolution order: HPX_PAGED_BLOCK env > seed table
    (benchmarks/flash_tune.py --paged writes paged_blocks.json next to
    this file, keys ``hd<head_dim>x<kv_dtype>``) > 16.  The
    source lands in
    ``ContinuousServer.hbm_read_stats()['block_size_source']``."""
    env = os.environ.get("HPX_PAGED_BLOCK")
    if env:
        return int(env), "env"
    val = _load_paged_blocks().get(f"hd{head_dim}x{kv_dtype}")
    if val:
        return int(val), "seed"
    return 16, "default"


def _dequant_tile(x, sc_ref, blk, head, dtype):
    """Dequantize one freshly-landed (block_size, head_dim) tile:
    sc_ref holds the 8-block group of [num_blocks, n_kv] scales around
    physical block `blk`; its (blk % 8, head) entry is selected by mask
    (a dynamic scalar read from VMEM does not lower) — elementwise-
    identical to the oracle's (pool.astype(f32) * scale).astype(dtype)."""
    sc = sc_ref[...]                               # (8, n_kv) f32
    row = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    pick = jnp.logical_and(row == blk % 8, col == head)
    scale = jnp.sum(jnp.where(pick, sc, 0.0))
    return (x.astype(jnp.float32) * scale).astype(dtype)


def _ring_kpos(slot, row, qpos, block_size: int, nblk: int):
    """Absolute position of row `row` of ring slot `slot` as a query at
    `qpos` finds it: a window group's table is a RING over logical
    blocks (logical block b sits in slot b % nblk), so the slot holds
    the newest block <= qpos's own that is congruent to it. Slots the
    sequence has not reached yet come out negative."""
    cb = qpos // block_size
    blk = cb - jax.lax.rem(cb - slot + nblk, nblk)
    return blk * block_size + row


def _live(kpos, qpos, window: int):
    """The horizon: a query at qpos sees kpos <= qpos, and on a window
    layer only qpos - window < kpos (and no slot not yet reached)."""
    live = kpos <= qpos
    if window:
        live = jnp.logical_and(live, jnp.logical_and(
            kpos > qpos - window, kpos >= 0))
    return live


def _paged_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  block_size: int, nblk: int, group: int,
                  quantized: bool, window: int = 0):
    """One (slot b, kv-head h, logical block i) grid step.

    q_ref: (Wg, hd) the slot's query rows for this kv head (window row
    w, group lane j flattened as r = w*group + j); k_ref/v_ref:
    (block_size, hd) the PHYSICAL pool block the table maps logical
    block i to (the index_map did the gather); quantized adds
    ks_ref/vs_ref (8, n_kv) scale groups. k_s/v_s scratch bank the
    full logical K/V rows along the sequential i axis; the last step
    runs the oracle-order attention over them."""
    if quantized:
        ks_ref, vs_ref, o_ref, k_s, v_s = rest
    else:
        o_ref, k_s, v_s = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)

    k = k_ref[...]                                 # (bs, hd)
    v = v_ref[...]
    if quantized:
        blk = table_ref[b, i]
        k = _dequant_tile(k, ks_ref, blk, h, k_s.dtype)
        v = _dequant_tile(v, vs_ref, blk, h, v_s.dtype)
    rows = pl.ds(pl.multiple_of(i * block_size, block_size), block_size)
    k_s[rows, :] = k
    v_s[rows, :] = v

    @pl.when(i == nblk - 1)
    def _finish():
        pos0 = pos_ref[b]
        q = q_ref[...]                             # (Wg, hd)
        # the oracle's einsum: operands in q.dtype, result rounded to
        # q.dtype (the MXU accumulates in f32 either way; Mosaic wants
        # the accumulator spelled), scaled, then upcast for the softmax
        s = jax.lax.dot_general(
            q, k_s[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(q.dtype)
        sf = (s / math.sqrt(q.shape[-1])).astype(jnp.float32)
        kpos = jax.lax.broadcasted_iota(jnp.int32, sf.shape, 1)
        wrow = jax.lax.broadcasted_iota(jnp.int32, sf.shape, 0) // group
        if window:
            kpos = _ring_kpos(kpos // block_size, kpos % block_size,
                              pos0 + wrow, block_size, nblk)
        live = _live(kpos, pos0 + wrow, window)    # per-window-row horizon
        sf = jnp.where(live, sf, -jnp.inf)
        p = jax.nn.softmax(sf, axis=-1)            # oracle op order
        att = jax.lax.dot_general(
            p.astype(o_ref.dtype), v_s[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[...] = att.astype(o_ref.dtype)


def _walk_entries(pos0, w: int, block_size: int, nblk: int):
    """How many table entries the bounded walk of a slot visits: the
    entry of its LAST query row (pos0 + w - 1) and every one before it,
    at most the table's width. On a window group's ring those are the
    slots the sequence has reached (all of them once it has gone
    round). Every row `_live` lets through lies in one of them."""
    return jnp.minimum((pos0 + w - 1) // block_size + 1, nblk)


def _paged_live_kernel(table_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                       k_s, v_s, sem, *, block_size: int, nblk: int,
                       group: int, w: int, hg: int, window: int = 0):
    """One (slot b, group of `hg` kv heads) grid step: the slot's whole
    walk, bounded by its live length (`_paged_kernel`'s result for
    unquantized pools without its dead grid steps), each table entry
    copied ONCE for all the group's heads, and copied WHILE THE GRID
    STEP BEFORE ran its finish.

    q_ref / o_ref: (hg, Wg, hd), a head's rows as in `_paged_kernel`;
    k_hbm / v_hbm: the POOLS, left in HBM; k_s / v_s: (2, hg, S, hd),
    TWO SETS of a bank a head. The grid runs in order, and grid step n
    (= b * groups + g) owns set n % 2. The first `_walk_entries` table
    entries of a step are copied into its set (entry j, heads h0 .. h0
    + hg, which lie back to back in the pool, to rows j*bs .. of every
    head's bank: one descriptor a pool), all in flight at once on ONE
    DMA semaphore a set and pool: started by the step BEFORE (the
    call's first step starts its own), which then waits for its own
    and runs its finish while they land; the last step starts none.
    Such a semaphore counts bytes landed from ANY copy that signals it,
    so a wait that returns says nothing of ITS copy: NOTHING IS READ
    FROM A SET'S BANKS BEFORE EVERY WAIT OF THAT SET'S COPIES HAS
    RETURNED, and the next step's copies signal the OTHER set's
    semaphores. Then `_paged_kernel`'s finish, op for op, head by head
    over that head's (S, hd) bank. Rows past the walk hold whatever
    VMEM held (the rows of the step before last, NaN for all we know):
    their scores are masked to -inf as every dead row's are (a select,
    so a NaN score goes too), and V's are SELECTED to zero ahead of the
    product, because 0 x NaN is NaN."""
    b, g = pl.program_id(0), pl.program_id(1)
    nslot, ngrp = pl.num_programs(0), pl.num_programs(1)
    n = b * ngrp + g
    mine = n % 2

    def walk(slot):
        return _walk_entries(pos_ref[slot], w, block_size, nblk)

    def each(slot, grp, bank, count, what):
        """`what` (start or wait) of the copies of (slot, grp)'s first
        `count` entries into set `bank`: a wait rebuilds the descriptor
        its start was given."""
        heads = pl.ds(pl.multiple_of(grp * hg, hg), hg)

        def body(j, carry):
            rows = pl.ds(pl.multiple_of(j * block_size, block_size),
                         block_size)
            blk = table_ref[slot, j]
            what(pltpu.make_async_copy(k_hbm.at[blk, heads],
                                       k_s.at[bank, :, rows, :],
                                       sem.at[bank, 0]))
            what(pltpu.make_async_copy(v_hbm.at[blk, heads],
                                       v_s.at[bank, :, rows, :],
                                       sem.at[bank, 1]))
            return carry
        jax.lax.fori_loop(0, count, body, 0)

    def start(cp):
        cp.start()

    def wait(cp):
        cp.wait()

    pos0 = pos_ref[b]
    n_live = walk(b)
    # the step after this one: the slot's next group, else the next slot
    last = g == ngrp - 1
    b_next = jnp.minimum(jnp.where(last, b + 1, b), nslot - 1)
    g_next = jnp.where(last, 0, g + 1)
    each(b, g, mine, jnp.where(n == 0, n_live, 0), start)  # none before it
    each(b_next, g_next, 1 - mine,                 # into the OTHER set
         jnp.where(n == nslot * ngrp - 1, 0, walk(b_next)), start)
    each(b, g, mine, n_live, wait)

    # the horizon is the slot's, the same for every head of the group
    sshape = (q_ref.shape[1], k_s.shape[2])
    kpos = jax.lax.broadcasted_iota(jnp.int32, sshape, 1)
    wrow = jax.lax.broadcasted_iota(jnp.int32, sshape, 0) // group
    if window:
        kpos = _ring_kpos(kpos // block_size, kpos % block_size,
                          pos0 + wrow, block_size, nblk)
    live = _live(kpos, pos0 + wrow, window)        # per-window-row horizon
    vrow = jax.lax.broadcasted_iota(jnp.int32, k_s.shape[2:], 0)
    vlive = vrow < n_live * block_size

    for i in range(hg):                            # a head's finish
        q = q_ref[i]                               # (Wg, hd)
        s = jax.lax.dot_general(
            q, k_s[mine, i].astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(q.dtype)
        sf = (s / math.sqrt(q.shape[-1])).astype(jnp.float32)
        sf = jnp.where(live, sf, -jnp.inf)
        p = jax.nn.softmax(sf, axis=-1)            # oracle op order
        v = jnp.where(vlive, v_s[mine, i], 0)
        att = jax.lax.dot_general(
            p.astype(o_ref.dtype), v.astype(o_ref.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[i] = att.astype(o_ref.dtype)


def paged_online_scratch_shapes(wg_pad: int, head_dim: int) -> list:
    """The fused_online VMEM carry: (acc, m, l) — (W*g, hd) f32
    accumulator plus two lane-replicated (W*g, 128) running-max /
    denominator rows. O(block) BY CONSTRUCTION: the function does not
    even take a sequence length, so no scratch can carry S extent —
    the acceptance gate for the online kernel asserts exactly this."""
    return [
        pltpu.VMEM((wg_pad, head_dim), jnp.float32),   # acc
        pltpu.VMEM((wg_pad, 128), jnp.float32),        # m (running max)
        pltpu.VMEM((wg_pad, 128), jnp.float32),        # l (denominator)
    ]


def _paged_online_kernel(table_ref, pos_ref, q_ref, k_ref, v_ref,
                         *rest, block_size: int, nblk: int, group: int,
                         quantized: bool, window: int = 0):
    """One (slot b, kv-head h, logical block i) grid step of the
    online-softmax paged walk.

    Same operands and table indirection as `_paged_kernel`, but the
    carry is the flash (acc, m, l) state (`paged_online_scratch_shapes`)
    instead of the full score/V rows: each freshly-landed K/V tile is
    folded into the running softmax immediately (delayed rescaling —
    the corr multiply only fires when the running max moved, exactly
    the `_flash_kernel` idiom) and the last block step normalizes.
    Masked lanes get EXACT-zero probability (p is where()'d, not just
    exp()'d), so trash/pad blocks contribute 0.0 like the bitwise
    kernel's."""
    if quantized:
        ks_ref, vs_ref, o_ref, acc_s, m_s, l_s = rest
    else:
        o_ref, acc_s, m_s, l_s = rest
    b = pl.program_id(0)
    h = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_s[:] = jnp.zeros_like(acc_s)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    q = q_ref[...]                                 # (Wg, hd)
    k = k_ref[...]                                 # (bs, hd)
    v = v_ref[...]
    if quantized:
        blk = table_ref[b, i]
        k = _dequant_tile(k, ks_ref, blk, h, q.dtype)
        v = _dequant_tile(v, vs_ref, blk, h, q.dtype)

    # f32 score accumulation (the flash numerics contract) — this
    # kernel's gate is tolerance-budgeted, so MXU-rate operands with
    # f32 accumulation beat the bitwise kernel's oracle-order dots
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s / math.sqrt(q.shape[-1])                 # (Wg, bs) f32

    pos0 = pos_ref[b]
    krow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    wrow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    kpos = (_ring_kpos(i, krow, pos0 + wrow, block_size, nblk)
            if window else i * block_size + krow)
    live = _live(kpos, pos0 + wrow, window)        # per-window-row horizon
    s = jnp.where(live, s, _NEG_INF)

    m_prev = m_s[:, :1]                            # (Wg, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(live, p, 0.0)                    # masked lanes: exact 0

    # delayed rescaling: skip the corr multiply on every block where
    # the running max didn't move (corr == exp(0) == 1)
    @pl.when(jnp.logical_not((m_new == m_prev).all()))
    def _rescale():
        corr = jnp.exp(m_prev - m_new)
        acc_s[:] = acc_s[:] * corr
        l_s[:] = l_s[:] * corr

    m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[:] = l_s[:] + jnp.broadcast_to(
        p.sum(axis=1, keepdims=True), l_s.shape)
    acc_s[:] = acc_s[:] + jax.lax.dot_general(
        p.astype(v.dtype) if v.dtype == jnp.bfloat16 else p, v,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(i == nblk - 1)
    def _finish():
        # every real row has its own position live, so l > 0; the
        # guard covers only the 8-sublane pad rows (sliced off outside)
        l = l_s[:, :1]
        den = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc_s[:] / den).astype(o_ref.dtype)


def fused_paged_attention(q: jax.Array, k_pool: jax.Array,
                          v_pool: jax.Array, table: jax.Array,
                          pos0: jax.Array,
                          k_scale: Optional[jax.Array] = None,
                          v_scale: Optional[jax.Array] = None,
                          interpret: Optional[bool] = None,
                          window: int = 0) -> jax.Array:
    """Decode/verify attention that walks the block table in-kernel.

    q: [B, W, n_q, head_dim] post-rope queries (W = 1 for plain decode,
    W = window width for speculative verify); k_pool/v_pool:
    [num_blocks, n_kv, block_size, head_dim] with this step's rows
    ALREADY scattered (write precedes attention, exactly like the
    gather oracle); table: [B, max_blocks] int32; pos0: [B] int32 —
    window row w attends logical positions <= pos0 + w (W = 1: the
    inclusive `<= pos` decode mask). k_scale/v_scale: [num_blocks,
    n_kv] f32 per-(block, head) absmax scales for quantized (int8/fp8)
    pools (None for bf16/f32 pools). Returns att [B, W, n_q, head_dim]
    in q.dtype.

    Rows past pos0+w contribute exact-zero probability, matching
    `paged_decode_attention` element-for-element: bitwise-equal scores
    and softmax, logits within ~1 ulp (see the section comment), same
    tokens. GQA via the same grouped-query reshape, so n_q % n_kv == 0.

    How far the table is walked depends on the pools. Unquantized
    pools with head_dim % 128 == 0 (`_paged_live_kernel`): as far as
    the block of the slot's last query row, min((pos0 + W - 1) //
    block_size + 1, max_blocks) entries, and no further — the tail is
    never fetched (a dead slot at position 0 reads one block), one
    compiled program for every length. Quantized pools and narrower
    heads (`_paged_kernel`): every logical block, trash-padded tail
    included, is fetched and masked. Same result either way.

    Falls back to interpret mode off-TPU (CPU tier-1 stays green).

    Runs unchanged inside shard_map on the serving (dp, tp) mesh:
    n_q/n_kv here are then the PER-SHARD head
    counts (tp slices the kv-head axis, so the GQA group n_q // n_kv
    is unchanged), the block axis is dp-replicated so the
    scalar-prefetched table's global block ids index the local pool
    directly, and int8/fp8 scales arrive pre-sliced per (block, local
    head) — no kernel-visible difference from the single-device
    call.

    `window` > 0: a WINDOW layer. `table` is then the window group's
    RING ([B, ring]: logical block b in slot b % ring, `_ring_kpos`),
    the kernel walks those `ring` entries and no more (the bounded
    walk: the slots the sequence has reached), a row counts
    only where pos0 + w - window < its position, and the call is named
    `hpx_paged_fused_win` so that a trace tells the two apart."""
    return _fused_paged_call(q, k_pool, v_pool, table, pos0,
                             k_scale, v_scale, interpret, online=False,
                             window=window)


def fused_paged_online_attention(q: jax.Array, k_pool: jax.Array,
                                 v_pool: jax.Array, table: jax.Array,
                                 pos0: jax.Array,
                                 k_scale: Optional[jax.Array] = None,
                                 v_scale: Optional[jax.Array] = None,
                                 interpret: Optional[bool] = None,
                                 window: int = 0) -> jax.Array:
    """`fused_paged_attention` with an in-kernel online softmax —
    the O(block)-scratch variant (`hpx.serving.paged_kernel=
    fused_online`).

    Same operands, same scalar-prefetched table walk, same exact-zero
    masking and decode/spec-verify window semantics as the bitwise
    kernel — only the carry differs: instead of stashing (W*g, S)
    scores + (S, hd) V rows, the kernel streams each K/V block through
    the flash (acc, m, l) state (`paged_online_scratch_shapes` — no
    scratch carries sequence extent), so VMEM stops bounding smax.
    Pallas double-buffers the streamed tiles against compute along the
    sequential block axis.

    Numerics contract (tolerance-budgeted — NOT bitwise): the
    running-max rescales reorder the softmax reduction, so logits
    agree with the gather oracle to a few ulp (O(eps * num_blocks))
    rather than bit-for-bit; greedy tokens are identical across the
    dense/paged/spec test sweep. When byte-identity is the requirement,
    use `fused` — that kernel stays the bitwise reference."""
    return _fused_paged_call(q, k_pool, v_pool, table, pos0,
                             k_scale, v_scale, interpret, online=True,
                             window=window)


# VMEM the bounded walk may plan with: the two SETS of K and V banks of
# a grid step's heads and one head's finish. v5e has 128 MiB; half is
# left to the compiler (the q / o blocks it double-buffers, what it
# spills).
_WALK_VMEM_BUDGET = 64 << 20


def _walk_vmem_bytes(hg: int, seq: int, hd: int, wg: int,
                     pool_itemsize: int, q_itemsize: int) -> int:
    """What `_paged_live_kernel` holds in VMEM with `hg` heads a group:
    the K and V banks of all of them TWICE (this grid step's set and
    the one the next step's copies land in), and ONE head's finish (its
    K rows as loaded and cast, its V rows selected and cast, and the
    score rows, `wg` = W * group padded to 8 sublanes, through mask and
    softmax in float32)."""
    banks = 2 * 2 * hg * seq * hd * pool_itemsize
    finish = 2 * seq * hd * (pool_itemsize + q_itemsize) \
        + 6 * (wg + -wg % 8) * seq * 4
    return banks + finish


def walk_heads_per_copy(nkv: int, seq: int, hd: int, wg: int,
                        pool_itemsize: int, q_itemsize: int) -> int:
    """How many kv heads one grid step of the bounded walk owns, and
    with them one copy of a table entry carries: the largest divisor of
    `nkv` (the CALL's: under `shard_map` the shard's) whose
    `_walk_vmem_bytes` fit `_WALK_VMEM_BUDGET`; 1 where none does,
    whatever the bytes (a bank too long for VMEM is then the compiler's
    to refuse, as it always was)."""
    for hg in range(nkv, 1, -1):
        if nkv % hg == 0 and _walk_vmem_bytes(
                hg, seq, hd, wg, pool_itemsize,
                q_itemsize) <= _WALK_VMEM_BUDGET:
            return hg
    return 1


def _live_walk_call(qk, k_pool, v_pool, table, pos0, *, w: int,
                    group: int, window: int, interpret: bool) -> jax.Array:
    """Launch `_paged_live_kernel`: qk [B, n_kv, Wg_pad, hd] in, the
    same out. Grid (slot, n_kv // hg), run IN ORDER (a step starts the
    next step's copies: "arbitrary" on both axes), `hg` =
    `walk_heads_per_copy` of the operands' shapes; the pools stay in HBM
    for the kernel's own copies; table and pos0 scalar-prefetched (so a
    step reads the next slot's a step early); two sets of (hg, S, hd)
    banks a pool in the pools' dtype and a DMA semaphore a set and
    pool; the VMEM limit stated from those bytes."""
    b, nkv, wg_pad, hd = qk.shape
    bs = k_pool.shape[2]
    maxb = table.shape[1]
    sizes = (maxb * bs, hd, wg_pad, jnp.dtype(k_pool.dtype).itemsize,
             jnp.dtype(qk.dtype).itemsize)
    hg = walk_heads_per_copy(nkv, *sizes)
    q_spec = pl.BlockSpec((None, hg, wg_pad, hd),
                          lambda bb, gg, *_: (bb, gg, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_paged_live_kernel, block_size=bs, nblk=maxb,
                          group=group, w=w, hg=hg, window=window),
        name="hpx_paged_fused" + ("_win" if window else ""),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nkv // hg),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=[q_spec],
            scratch_shapes=[
                pltpu.VMEM((2, hg, maxb * bs, hd), k_pool.dtype),
                pltpu.VMEM((2, hg, maxb * bs, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2))],      # (set, pool)
        ),
        out_shape=[_sds((b, nkv, wg_pad, hd), qk.dtype, qk, k_pool,
                        v_pool)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(max(
                _walk_vmem_bytes(hg, *sizes) + (8 << 20), 32 << 20))),
        interpret=interpret,
    )(table.astype(jnp.int32), pos0.astype(jnp.int32), qk, k_pool,
      v_pool)[0]


# table entries one buffer of the latent walk holds: 32 blocks of 16
# rows = 512 rows a fold (a 640-wide bfloat16 buffer is 655 KB)
LATENT_WALK_ENTRIES = 32

# table entries ONE copy of the latent walk carries where the table
# names neighbours: a whole fold. An aligned group of 32 entries t,
# t + 1, .., t + 31 is 512 rows = 655 KB of contiguous pool, one
# descriptor where it was 32 of 20 KB. Swept on the chip (PERF.md PR
# 51, device clock, 64 slots x 128 heads at position 15,231): groups of
# 4 / 8 / 16 / 32 read 3,658 / 3,601 / 2,861 / 2,880 us a call over
# tables that are runs where a copy an entry reads 3,667: eight or four
# compare-and-branches a fold break the straight-line issue of its
# copies and cost what the descriptors they save cost; two or one
# pay. Kept: the fold itself. 1 = every entry its own copy
LATENT_RUN = 32


def latent_walk_sizes(maxb: int) -> Tuple[int, int]:
    """(fold, run) of `hpx_mla_paged` over a table `maxb` wide: the
    entries a buffer holds, and the entries a coalesced copy carries:
    `LATENT_RUN` where a fold is whole groups of it, else 1 (every
    entry its own copy). The one place the kernel's launch and the
    host's `latent_run_pct` ask."""
    fold = min(LATENT_WALK_ENTRIES, maxb)
    return fold, LATENT_RUN if fold % LATENT_RUN == 0 else 1


def latent_groups_coalesced(table, run: int):
    """Which aligned groups of `run` entries of each row of a `[..,
    maxb]` table `hpx_mla_paged` copies in ONE descriptor where their
    fold lies between a slot's first and last: those whose ids are t,
    t + 1, .., t + run - 1 (none at `run` 1: every entry is then its
    own copy). Host NumPy, bool `[.., maxb // run]`: the kernel's own
    rule over a table's groups, whatever their fold; the feed of
    `latent_run_pct`."""
    import numpy as np
    table = np.array(table)             # host ids, never a device array
    groups = table.shape[-1] // run
    ids = table[..., :groups * run].reshape(table.shape[:-1] + (groups, run))
    return (np.diff(ids, axis=-1) == 1).all(-1) & (run > 1)


def latent_entries_coalesced(table, n_live, fold: int, run: int):
    """Of the first `n_live` entries of each row of a table, how many
    lie in a coalesced copy: `run` for every group of
    `latent_groups_coalesced` in the folds BETWEEN the slot's first
    and its last (those two are single copies as they ever were,
    whatever the table holds)."""
    import numpy as np
    runs = latent_groups_coalesced(table, run)
    ahead = (-(-np.array(n_live) // fold) - 1) * fold
    at = np.arange(runs.shape[-1]) * run
    return run * (runs & (at >= fold) & (at < ahead[..., None])).sum(-1)


def _latent_kernel(table_ref, pos_ref, q_ref, pool_hbm, o_ref, bank,
                   acc_s, m_s, l_s, sem, *, block_size: int, nblk: int,
                   chunk: int, run: int, rank: int, scale: float):
    """One slot's absorbed latent attention (MLA decode): every query
    head over ONE cached head of latent rows whose value is its own
    first `rank` columns. q_ref (H, R), o_ref (H, rank); pool_hbm: the
    latent pool [num_blocks, 1, block_size, R], left in HBM.

    The slot's live table entries (`_walk_entries`: none past `pos`)
    are walked `chunk` at a time through TWO buffers, `bank` (2, chunk,
    block_size, R): while one buffer's rows are scored and folded into
    the running softmax (`acc_s` (H, rank), `m_s`, `l_s`, float32: the
    flash carry), the next `chunk` entries land in the other. VMEM
    holds two buffers and the carry whatever the table's width, and no
    row past the last live entry is copied, scored or weighed.

    The whole folds BETWEEN a slot's first and its last (the ones whose
    copies land while the fold before is scored) are taken in aligned
    GROUPS of `run`: a group whose entries are t, t + 1, .., t + run - 1
    (read off the scalar-prefetched table: `run` - 1 compares and one
    branch) is ONE copy of `run` consecutive pool blocks, any other
    group `run` copies of one block each; the same rows at the same
    buffer offsets either way, so the output does not depend on which
    was taken. The slot's FIRST fold (nothing runs beside its copies:
    they go out at once) and its LAST (a count of the slot's own) are
    an entry a copy, from a loop, as they ever were.

    A buffer's copies signal that buffer's OWN semaphore, and a buffer
    is read only after every wait of its copies has returned. A DMA
    semaphore counts bytes landed from any copy that signals it, so
    the waits do not ask how a group was started: one wait of a
    group's bytes a group, one of a block's a single entry. Only the
    LAST fold has rows past `pos` (the tail of the last live block,
    and what the buffer held before): there the scores are masked and
    the value rows selected to zero, because 0 x NaN is NaN."""
    b = pl.program_id(0)
    pos = pos_ref[b]
    n_live = _walk_entries(pos, 1, block_size, nblk)
    n_fold = (n_live + chunk - 1) // chunk
    tail = n_live - (n_fold - 1) * chunk        # the last fold's entries
    rows = chunk * block_size

    def single(buf, j, block=0):
        """Entry j of a buffer alone, from pool block `block`."""
        return pltpu.make_async_copy(
            pool_hbm.at[block, 0], bank.at[buf, j], sem.at[buf])

    def group(buf, g, block=0):
        """Group g of a buffer in one copy: `run` pool blocks from
        `block` on."""
        return pltpu.make_async_copy(
            pool_hbm.at[pl.ds(block, run), 0],
            bank.at[buf, pl.ds(g * run, run)], sem.at[buf])

    def start_group(c, buf, g):
        ids = [table_ref[b, c * chunk + g * run + k] for k in range(run)]
        if run == 1:
            single(buf, g, ids[0]).start()
            return
        neighbours = functools.reduce(
            jnp.logical_and, [ids[k] == ids[0] + k for k in range(1, run)])

        @pl.when(neighbours)
        def _():
            group(buf, g, ids[0]).start()

        @pl.when(jnp.logical_not(neighbours))
        def _():
            for k in range(run):
                single(buf, g * run + k, ids[k]).start()

    def each(c, buf, count, start: bool):
        """Start, or wait for, fold c's first `count` entries. A whole
        fold (a static count) in groups, straight-line code (on the
        chip 20% under the same descriptors issued from a loop,
        PERF.md PR 37); a count of the slot's own (its first fold's
        start, its last fold's start and wait) an entry a copy, from
        a loop."""
        if isinstance(count, int):
            assert count % run == 0
            for g in range(count // run):
                if start:
                    start_group(c, buf, g)
                else:
                    group(buf, g).wait()
            return

        def body(j, carry):
            if start:
                single(buf, j, table_ref[b, c * chunk + j]).start()
            else:
                single(buf, j).wait()
            return carry
        jax.lax.fori_loop(0, count, body, 0)

    acc_s[...] = jnp.zeros_like(acc_s)
    m_s[...] = jnp.full_like(m_s, _NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    q = q_ref[...]
    # nothing runs beside the slot's first fold: its copies go out at
    # once, an entry a copy, whatever the table holds
    each(0, 0, jnp.minimum(chunk, n_live), True)

    def fold(c, nxt, count, last: bool):
        """Fold c: start the `nxt` entries of fold c + 1 into the other
        buffer, wait for this fold's `count`, score and fold them in."""
        buf = c % 2
        if nxt is not None:
            each(c + 1, 1 - buf, nxt, True)
        each(c, buf, count, False)
        lat = bank[buf].reshape(rows, bank.shape[-1])
        s = jax.lax.dot_general(
            q, lat.astype(q.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (H, rows)
        v = lat[:, :rank]
        if last:
            kpos = c * rows + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(kpos <= pos, s, _NEG_INF)
            vrow = c * rows + jax.lax.broadcasted_iota(
                jnp.int32, v.shape, 0)
            v = jnp.where(vrow <= pos, v, 0)
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)              # a masked score: exactly 0
        fade = jnp.exp(m_prev - m_new)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = l_s[...] * fade + jnp.broadcast_to(
            p.sum(axis=1, keepdims=True), l_s.shape)
        acc_s[...] = acc_s[...] * fade + jax.lax.dot_general(
            p.astype(q.dtype), v.astype(q.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def whole(c, carry):            # a whole fold ahead of a whole fold
        fold(c, chunk, chunk, False)
        return carry

    jax.lax.fori_loop(0, n_fold - 2, whole, 0)

    @pl.when(n_fold >= 2)           # the whole fold ahead of the last
    def _():
        fold(n_fold - 2, tail, chunk, False)

    fold(n_fold - 1, None, tail, True)
    o_ref[...] = (acc_s[...] / l_s[:, :1]).astype(o_ref.dtype)


def fused_latent_attention(q: jax.Array, pool: jax.Array,
                           table: jax.Array, pos: jax.Array, *,
                           rank: int, scale: float,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Absorbed MLA decode attention that walks the block table
    in-kernel (`hpx_mla_paged`), in blocks of rows under an online
    softmax, bounded by each slot's live length.

    q: [B, H, R] absorbed queries (W_uk^T q^C, then the q^R dims, then
    zeros up to the pool's row width R); pool: [num_blocks, 1,
    block_size, R] latent rows (c, k^R, zero pad) with this step's row
    ALREADY written; table: [B, max_blocks] int32; pos: [B] int32, the
    slot attends rows <= pos. Returns sum_j p_j c_j, [B, H, rank]: the
    scores are q . row * scale in float32 (the pad columns are zero on
    both sides), the softmax float32, the value a row's first `rank`
    columns: K and V are the same bytes, read once.

    ONE pool of rows R = rank + rope dims rounded up to whole 128-lane
    rows, not a 512-wide and a 64-wide pool on one table: the chip pads
    a minor dim of 64 to 128 lanes in HBM anyway, so the split saves
    nothing, and one pool is one DMA a block, one buffer and one matmul
    for the scores (whose contraction the 128-wide unit would pad from
    576 to 640 itself). Needs R % 128 == 0 and rank % 128 == 0 (the
    value slice is then whole lanes); `ops/paged_attention.
    paged_latent_attention` decides and keeps the gather form for every
    other width. Grid (slot,), parallel. The VMEM need is two buffers
    of LATENT_WALK_ENTRIES blocks, the carry and a fold's scores: the
    same at every table width (`latent_vmem_bytes`)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, r = q.shape
    bs = pool.shape[2]
    maxb = table.shape[1]
    chunk, run = latent_walk_sizes(maxb)
    return pl.pallas_call(
        functools.partial(_latent_kernel, block_size=bs, nblk=maxb,
                          chunk=chunk, run=run, rank=rank, scale=scale),
        name="hpx_mla_paged",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[pl.BlockSpec((None, h, r), lambda bb, *_: (bb, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=[pl.BlockSpec((None, h, rank),
                                    lambda bb, *_: (bb, 0, 0))],
            scratch_shapes=[pltpu.VMEM((2, chunk, bs, r), pool.dtype),
                            pltpu.VMEM((h, rank), jnp.float32),
                            pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.VMEM((h, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=[_sds((b, h, rank), q.dtype, q, pool)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=latent_vmem_bytes(
                h, r, rank, chunk * bs, jnp.dtype(pool.dtype).itemsize)),
        interpret=interpret,
    )(table.astype(jnp.int32), pos.astype(jnp.int32), q, pool)[0]


def latent_vmem_bytes(h: int, r: int, rank: int, rows: int,
                      itemsize: int) -> int:
    """The scoped VMEM `hpx_mla_paged` asks for: the two buffers, a
    buffer's rows as loaded and as selected value rows, q and the
    output doubled by the pipeline, the float32 carry, a fold's scores
    and probabilities, and 4 MB for the compiler's own. No term holds
    the table's width."""
    buffers = (2 + 2) * rows * r * itemsize
    carry = h * (rank + 256) * 4
    scores = 3 * h * rows * 4
    io = 2 * h * (r + rank) * itemsize
    return int(max(buffers + carry + scores + io + (4 << 20), 16 << 20))


def _fused_paged_call(q, k_pool, v_pool, table, pos0, k_scale, v_scale,
                      interpret, online: bool,
                      window: int = 0) -> jax.Array:
    """Shared launch path for the paged kernels: the pad/slice layout
    of q, then the ONE place that decides which walk a call takes —
    the bounded one (`_live_walk_call`) for `fused` over unquantized
    pools with head_dim % 128 == 0, else the grid walk: identical grid,
    BlockSpec table indirection and quantized-scale plumbing for its
    two kernels, only the kernel body and its scratch differ."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, w, nq, hd = q.shape
    nkv = k_pool.shape[1]
    bs = k_pool.shape[2]
    maxb = table.shape[1]
    if nq % nkv:
        raise ValueError(f"q heads ({nq}) not a multiple of kv heads "
                         f"({nkv})")
    g = nq // nkv
    wg = w * g
    wg_pad = wg + (-wg % 8)          # 8-sublane f32 tile; pad rows are
    seq = maxb * bs                  # garbage, sliced off below

    # [B, W, nkv, g, hd] -> [B, nkv, W*g, hd]: row r = w*g + j
    qk = jnp.moveaxis(q.reshape(b, w, nkv, g, hd), 2, 1)
    qk = qk.reshape(b, nkv, wg, hd)
    if wg_pad != wg:
        qk = jnp.pad(qk, ((0, 0), (0, 0), (0, wg_pad - wg), (0, 0)))

    if not online and k_scale is None and hd % 128 == 0:
        # the bounded walk (section comment): decided HERE and nowhere
        # else, from the operands alone, the same on the CPU as on the
        # chip; every other call goes on below, as it always has
        out = _live_walk_call(qk, k_pool, v_pool, table, pos0, w=w,
                              group=g, window=window, interpret=interpret)
        return jnp.moveaxis(out[:, :, :wg].reshape(b, nkv, w, g, hd), 1, 2
                            ).reshape(b, w, nq, hd)

    quantized = k_scale is not None
    kernel = functools.partial(
        _paged_online_kernel if online else _paged_kernel,
        block_size=bs, nblk=maxb, group=g, quantized=quantized,
        window=window)
    if online:
        # the flash carry — O(block), no sequence extent anywhere
        scratch = paged_online_scratch_shapes(wg_pad, hd)
    else:
        # the bitwise kernel banks full K/V rows: O(S * hd)
        scratch = [pltpu.VMEM((seq, hd), q.dtype),
                   pltpu.VMEM((seq, hd), q.dtype)]

    q_spec = pl.BlockSpec((None, None, wg_pad, hd),
                          lambda bb, hh, ii, *_: (bb, hh, 0, 0))
    # THE fusion: logical block ii of slot bb reads physical pool
    # block table[bb, ii] straight from the scalar-prefetched table
    kv_spec = pl.BlockSpec(
        (None, None, bs, hd),
        lambda bb, hh, ii, tref, pref: (tref[bb, ii], hh, 0, 0))
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qk, k_pool, v_pool]
    if quantized:
        sc_spec = pl.BlockSpec(
            (8, nkv), lambda bb, hh, ii, tref, pref: (tref[bb, ii] // 8, 0))
        in_specs += [sc_spec, sc_spec]
        operands += [k_scale, v_scale]

    out = pl.pallas_call(
        kernel,
        name=("hpx_paged_fused_online" if online else "hpx_paged_fused")
        + ("_win" if window else ""),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, nkv, maxb),
            in_specs=in_specs,
            out_specs=[q_spec],
            scratch_shapes=scratch,
        ),
        out_shape=[_sds((b, nkv, wg_pad, hd), q.dtype, q, k_pool,
                        v_pool)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(table.astype(jnp.int32), pos0.astype(jnp.int32), *operands)[0]

    out = out[:, :, :wg]
    return jnp.moveaxis(out.reshape(b, nkv, w, g, hd), 1, 2
                        ).reshape(b, w, nq, hd)
