"""Attention ops: flash-style blockwise attention, ring attention
(sequence parallel over the ICI ring), and Ulysses (all_to_all head
parallel).

The reference (HPX) contains no attention — SURVEY.md §5.7 documents
that the nearest structural analogs it DOES have are the halo-exchange
ring (`lax.ppermute`, parallel/halo.py) and the `all_to_all` collective.
These ops are the long-context capability built ON that substrate, as
the driver mandates: ring attention is the stencil halo pattern with an
online-softmax accumulator; Ulysses is the segmented-algorithm pattern
with an all_to_all re-shard.

Shapes follow jax convention: [batch, seq, heads, head_dim] ("BSNH").
All math accumulates in float32 regardless of input dtype (bfloat16
inputs stay bf16 on the wire/MXU, f32 in the softmax accumulator).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = [
    "auto_attention", "reference_attention", "blockwise_attention",
    "ring_attention", "ring_attention_sharded", "ulysses_attention",
    "stripe_sequence", "unstripe_sequence", "ring_positions",
]


def auto_attention(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False) -> jax.Array:
    """Best-available single-device attention: the pallas flash kernel
    on TPU (bf16 MXU tiles with fp32 accumulation, VMEM-resident online
    softmax), XLA blockwise elsewhere.
    Differentiable on both paths (flash carries a custom_vjp)."""
    if jax.default_backend() == "tpu":
        from .attention_pallas import flash_attention
        return flash_attention(q, k, v, causal)
    return blockwise_attention(q, k, v, causal)


def _scale(q: jax.Array) -> jax.Array:
    return q * (1.0 / math.sqrt(q.shape[-1]))


def _pvary(x: jax.Array, axis) -> jax.Array:
    """Mark a constant as device-varying over shard_map axis/axes."""
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    return jax.lax.pcast(x, axes, to="varying")


# ---------------------------------------------------------------------------
# reference (materializes the full score matrix — test oracle only)
# ---------------------------------------------------------------------------

def _expand_kv(q: jax.Array, k: jax.Array, v: jax.Array):
    """GQA/MQA on the XLA paths: repeat K/V heads up to the q head
    count (the pallas kernels share tiles via BlockSpec index remaps
    instead — attention_pallas._kv_row_map — and never materialize the
    repeat; these XLA formulations are oracles/fallbacks, so the
    repeat's bandwidth cost is acceptable)."""
    nq, nkv = q.shape[2], k.shape[2]
    if nkv == nq:
        return k, v
    if nq % nkv:
        raise ValueError(f"q heads ({nq}) not a multiple of kv heads "
                         f"({nkv})")
    r = nq // nkv
    return (jnp.repeat(k, r, axis=2), jnp.repeat(v, r, axis=2))


def reference_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False) -> jax.Array:
    """O(S^2) memory oracle. [B,S,N,H] -> [B,S,N,H]; fewer K/V heads
    (GQA/MQA) broadcast per group."""
    k, v = _expand_kv(q, k, v)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bqnh,bknh->bnqk", _scale(qf), kf)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnqk,bknh->bqnh", p, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# blockwise (flash) attention — single device
# ---------------------------------------------------------------------------

def _online_block(q: jax.Array, k: jax.Array, v: jax.Array,
                  acc: jax.Array, m: jax.Array, l: jax.Array,
                  bias: Optional[jax.Array] = None):
    """One K/V block of online softmax.

    q:[B,Sq,N,H] k,v:[B,Sk,N,H]; acc:[B,Sq,N,H] f32; m,l:[B,Sq,N] f32.
    bias (optional): [Sq,Sk] additive mask (-inf for masked).
    Returns updated (acc, m, l).
    """
    s = jnp.einsum("bqnh,bknh->bqnk", _scale(q.astype(jnp.float32)),
                   k.astype(jnp.float32))
    if bias is not None:
        s = s + bias[None, :, None, :]
    m_new = jnp.maximum(m, s.max(axis=-1))
    # renormalize the old accumulator; -inf rows (nothing seen yet and
    # fully masked block) must contribute exp(0)=... guard NaNs:
    corr = jnp.exp(m - m_new)
    corr = jnp.where(jnp.isfinite(corr), corr, 0.0)
    p = jnp.exp(s - m_new[..., None])
    p = jnp.where(jnp.isfinite(p), p, 0.0)
    l_new = l * corr + p.sum(axis=-1)
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bqnk,bknh->bqnh", p, v.astype(jnp.float32))
    return acc_new, m_new, l_new


def _finish(acc: jax.Array, l: jax.Array, dtype) -> jax.Array:
    den = jnp.where(l > 0, l, 1.0)[..., None]
    return (acc / den).astype(dtype)


def blockwise_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        block_k: int = 512) -> jax.Array:
    """Flash-style attention: K/V consumed in blocks with an online
    softmax — O(S) memory. The inner loop is a lax.scan, so XLA sees a
    static program whatever the sequence length. Fewer K/V heads
    (GQA/MQA) broadcast per group."""
    k, v = _expand_kv(q, k, v)
    b, sq, n, h = q.shape
    sk = k.shape[1]
    nblk = -(-sk // block_k)
    pad = nblk * block_k - sk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        kp, vp = k, v
    kb = kp.reshape(b, nblk, block_k, n, h).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nblk, block_k, n, h).transpose(1, 0, 2, 3, 4)

    q_pos = jnp.arange(sq)
    # accumulators derive from q (not fresh constants) so that when this
    # runs INSIDE a shard_map (ulysses_attention) the scan carry has the
    # same varying-manual-axes type as its updated value; XLA folds the
    # multiply-by-zero
    zero_q = q.astype(jnp.float32) * 0.0
    acc0 = zero_q
    m0 = zero_q[..., 0] - jnp.inf
    l0 = zero_q[..., 0]

    def step(carry, inputs):
        acc, m, l = carry
        kblk, vblk, blk_idx = inputs
        k_pos = blk_idx * block_k + jnp.arange(block_k)
        bias = jnp.where(k_pos[None, :] < sk, 0.0, -jnp.inf)
        if causal:
            bias = bias + jnp.where(
                k_pos[None, :] <= q_pos[:, None] + (sk - sq), 0.0,
                -jnp.inf)
        else:
            bias = jnp.broadcast_to(bias, (sq, block_k))
        return _online_block(q, kblk, vblk, acc, m, l, bias), None

    (acc, _m, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (kb, vb, jnp.arange(nblk)))
    return _finish(acc, l, q.dtype)


# ---------------------------------------------------------------------------
# ring attention — sequence parallel over a mesh axis
# ---------------------------------------------------------------------------


def stripe_sequence(x: jax.Array, p: int, axis: int = 1) -> jax.Array:
    """Contiguous -> STRIPED token layout for a p-way causal ring:
    global token r + p*i moves to slot r*(S/p) + i, so the shard at
    ring position r holds every p-th token (Striped Attention). One
    reshape-transpose; applied to an array sharded over `axis` under
    jit, XLA lowers it to an all_to_all. Why: with contiguous chunks a
    causal ring idles rank r for (p-1-r) of its p steps (future
    chunks are fully masked) — wall clock ~p full chunk-folds. Striped,
    every chunk-pair is HALF-masked with plain local causal offset 0
    or -1, so all ranks work every step: ~p/2 fold-equivalents, ~2x
    on long causal sequences, same collectives."""
    n = x.shape[axis]
    if n % p:
        raise ValueError(f"stripe_sequence: length {n} not divisible "
                         f"by {p}")
    sq = n // p
    xm = jnp.moveaxis(x, axis, 0)
    y = xm.reshape(sq, p, *xm.shape[1:]).swapaxes(0, 1)
    return jnp.moveaxis(y.reshape(n, *xm.shape[1:]), 0, axis)


def unstripe_sequence(x: jax.Array, p: int, axis: int = 1) -> jax.Array:
    """Inverse of stripe_sequence (the same transpose with the factors
    swapped)."""
    n = x.shape[axis]
    if n % p:
        raise ValueError(f"unstripe_sequence: length {n} not divisible "
                         f"by {p}")
    return stripe_sequence(x, n // p, axis=axis)


def ring_positions(rank, nshards: int, sq: int, striped: bool):
    """GLOBAL token positions of ring shard `rank`: contiguous shards
    own [rank*sq, (rank+1)*sq); striped shards own rank, rank+p, ...
    THE one definition — the ring paths and RoPE all use it, so the
    layouts can never diverge."""
    if striped:
        return rank + nshards * jnp.arange(sq)
    return rank * sq + jnp.arange(sq)


def ring_offset(idx, src, sq: int, striped: bool):
    """The kernels' causal offset d for chunk (q-rank idx, k-rank src):
    contiguous d = q_global_start - k_global_start; striped layouts
    reduce to d = 0 (src <= idx) or -1 — see stripe_sequence."""
    if striped:
        return jnp.where(src <= idx, 0, -1).astype(jnp.int32)
    return (idx - src) * sq


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Any,
                   axis: str = "sp", causal: bool = False,
                   striped: bool = False) -> jax.Array:
    """Sequence-parallel attention: q/k/v sharded on `axis` along seq.

    Each device keeps its Q chunk resident and walks the WHOLE sequence
    by rotating K/V chunks around the ICI ring (`lax.ppermute` — the
    1d_stencil halo pattern, SURVEY.md §5.7), folding each arriving
    chunk into an online-softmax accumulator. Peak memory per chip is
    O(S/P); bandwidth is the ring's, which is exactly what the halos
    already ride.

    Causal masking is positional: chunk ownership gives each device its
    global offset, so masking stays correct whatever step the chunk
    arrives on (full-chunk skips still compute — uniform work per step
    keeps the ring in lockstep, the standard TPU tradeoff).

    striped=True (causal long-context): stripe the sequence over the
    ring first (one all_to_all each way), so every rank does balanced
    half-work each step instead of idling on future chunks — ~2x
    causal wall clock; see stripe_sequence.
    """
    nshards = mesh.shape[axis]
    spec = P(None, axis, None, None)

    def run(q, k, v):
        if striped:
            q, k, v = (stripe_sequence(x, nshards) for x in (q, k, v))

        def body(qc, kc, vc):
            return ring_attention_sharded(qc, kc, vc, axis, nshards,
                                          causal, use_flash=None,
                                          striped=striped)

        out = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                        out_specs=spec)(q, k, v)
        if striped:
            out = unstripe_sequence(out, nshards)
        return out

    return jax.jit(run)(q, k, v)


def ring_attention_sharded(qc: jax.Array, kc: jax.Array, vc: jax.Array,
                           axis: str, nshards: int,
                           causal: bool = False,
                           use_flash: Optional[bool] = None,
                           striped: bool = False) -> jax.Array:
    """The per-shard ring body, callable from INSIDE an enclosing
    shard_map (e.g. a sharded transformer step). The ring loop is a
    lax.scan, so reverse-mode AD works (scan transposes; the ppermute
    transpose is the inverse rotation) — training steps can
    differentiate straight through the ring.

    use_flash (default None = flash on TPU): fold each arriving chunk
    with the pallas chunk kernel (attention_pallas.flash_attention_chunk)
    instead of the XLA online block — 2-8x faster on TPU, and
    DIFFERENTIABLE: _ring_flash carries a custom_vjp whose backward
    replays the ring with the pallas flash-backward kernels
    (attention_pallas.flash_attention_bwd), rotating dK/dV partial
    accumulators around the ICI ring alongside the chunks.

    striped=True: chunks are in the stripe_sequence layout (shard r
    holds tokens r, r+p, ...). Causal masking then reduces to a plain
    local causal mask with offset 0 (k-rank <= q-rank) or -1 — EVERY
    ring step does balanced half-work instead of rank r idling for its
    future chunks, ~2x wall-clock on causal rings. Layout conversion
    (an all_to_all) is the caller's job: stripe once outside, run many
    layers striped, unstripe once.
    """
    if use_flash is None:
        use_flash = jax.default_backend() == "tpu"
    if use_flash:
        if nshards == 1:
            # degenerate ring: plain flash (custom_vjp) — skips the
            # scan/ppermute wrapping and the unnormalized f32 carry;
            # handles GQA natively (grouped K/V tiles)
            from .attention_pallas import flash_attention
            return flash_attention(qc, kc, vc, causal)
        # GQA rides the ring GROUPED: the chunk kernel reads shared
        # K/V tiles via the same BlockSpec row remap plain flash uses,
        # and the backward's dK/dV partials accumulate (group-summed)
        # in the kv-head layout — every ppermute hop moves only the
        # kv heads, the whole wire saving of GQA.
        return _ring_flash(qc, kc, vc, axis, nshards, causal, striped)
    b, sq, n, h = qc.shape
    idx = jax.lax.axis_index(axis)
    q_pos = ring_positions(idx, nshards, sq, striped)

    # accumulators derive from qc (already device-varying), so the scan
    # carry's varying manual axes match the updated values whatever
    # enclosing mesh axes exist
    zero_q = qc.astype(jnp.float32) * 0.0
    acc = zero_q
    m = zero_q[..., 0] - jnp.inf
    l = zero_q[..., 0]

    perm = [(i, (i + 1) % nshards) for i in range(nshards)]

    def step(carry, t):
        acc, m, l, kc, vc = carry
        # chunk arriving at step t started at ring position idx-t
        src = (idx - t) % nshards
        k_pos = ring_positions(src, nshards, sq, striped)
        if causal:
            bias = jnp.where(k_pos[None, :] <= q_pos[:, None],
                             0.0, -jnp.inf)
        else:
            bias = jnp.zeros((sq, sq), jnp.float32)
        # GQA: the ring circulates the GROUPED [B,S/P,Nkv,H] chunks —
        # every ppermute hop moves only the kv heads — and broadcasts
        # per group locally just for this step's fold (AD transposes
        # the repeat to a group-sum, so dK/dV stay grouped on the wire)
        ke, ve = _expand_kv(qc, kc, vc)
        acc, m, l = _online_block(qc, ke, ve, acc, m, l, bias)
        # rotate AFTER folding; ppermute rides the ICI ring
        kc = jax.lax.ppermute(kc, axis, perm)
        vc = jax.lax.ppermute(vc, axis, perm)
        return (acc, m, l, kc, vc), None

    (acc, m, l, _kc, _vc), _ = jax.lax.scan(
        step, (acc, m, l, kc, vc), jnp.arange(nshards))
    return _finish(acc, l, qc.dtype)


def _ring_blk(sq: int, cap: int) -> int:
    """Largest kernel block that divides the chunk length (the chunk
    and backward kernels have no padding path), sublane-aligned when
    possible."""
    blk = math.gcd(sq, cap)
    if blk % 8:
        blk = sq
    return blk


def _ring_flash_fwd_impl(qc, kc, vc, axis, nshards, causal,
                         striped=False):
    """Ring attention with the pallas chunk kernel as the inner fold.

    Layout transposes to kernel-native [B*N, S/P, H] happen ONCE
    outside the ring scan; each step folds the arriving K/V chunk via
    flash_attention_chunk with the traced global offset
    d = (idx - src) * sq, then rotates K/V with ppermute. Returns the
    public-layout output plus the residuals the backward needs
    (kernel-layout operands, normalized output, row logsumexp).
    """
    from .attention_pallas import _kernel_layout, flash_attention_chunk

    b, sq, n, h = qc.shape
    nkv = kc.shape[2]
    blk = _ring_blk(sq, 1024)
    idx = jax.lax.axis_index(axis)

    qt = _kernel_layout(qc)
    kt = _kernel_layout(kc)
    vt = _kernel_layout(vc)

    # accumulators derive from qt so the scan carry's varying manual
    # axes match inside whatever enclosing mesh axes exist
    zq = qt.astype(jnp.float32) * 0.0
    acc = zq
    m = zq[:, :, :1] - jnp.full((128,), 1e30, jnp.float32)
    l = zq[:, :, :1] + jnp.zeros((128,), jnp.float32)

    perm = [(i, (i + 1) % nshards) for i in range(nshards)]

    def step(carry, t):
        acc, m, l, kc_, vc_ = carry
        src = (idx - t) % nshards
        d = ring_offset(idx, src, sq, striped)
        acc, m, l = flash_attention_chunk(qt, kc_, vc_, acc, m, l, d,
                                          causal=causal, block_q=blk,
                                          block_k=_ring_blk(sq, 512),
                                          q_heads=n, kv_heads=nkv)
        kc_ = jax.lax.ppermute(kc_, axis, perm)
        vc_ = jax.lax.ppermute(vc_, axis, perm)
        return (acc, m, l, kc_, vc_), None

    # after nshards rotations the K/V chunks return home, so kt/vt are
    # valid residuals for the backward replay
    (acc, m, l, _kc, _vc), _ = jax.lax.scan(
        step, (acc, m, l, kt, vt), jnp.arange(nshards))

    l1 = l[:, :, :1]
    m1 = m[:, :, :1]
    den = jnp.where(l1 > 0, l1, 1.0)
    ot = (acc / den).astype(qc.dtype)              # [bn, sq, h]
    # one lane of the row logsumexp (the backward re-broadcasts)
    lse = jnp.where(l1 > 0, m1 + jnp.log(den), 0.0)
    out = jnp.moveaxis(ot.reshape(b, n, sq, h), 1, 2)
    return out, (qt, kt, vt, ot, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_flash(qc: jax.Array, kc: jax.Array, vc: jax.Array,
                axis: str, nshards: int, causal: bool,
                striped: bool = False) -> jax.Array:
    return _ring_flash_fwd_impl(qc, kc, vc, axis, nshards, causal,
                                striped)[0]


def _ring_flash_bwd(axis, nshards, causal, striped, res, g):
    """Ring-attention backward: replay the forward's chunk rotation;
    each step runs the pallas flash-backward kernels on the arriving
    chunk (attention_pallas.flash_attention_bwd with the traced offset
    d), accumulating dQ locally while dK/dV partial sums travel AROUND
    THE RING with their chunks — after nshards rotations each chunk's
    gradient arrives back at its owner, the same lockstep schedule the
    forward uses."""
    from .attention_pallas import (_kernel_layout, bwd_prep,
                                   flash_attention_bwd)

    qt, kt, vt, ot, lse = res
    b, sq, n, h = g.shape                      # public [B, S/P, N, H]
    nkv = kt.shape[0] // b                     # kv heads (grouped wire)
    blk = _ring_blk(sq, 512)
    idx = jax.lax.axis_index(axis)
    dot_ = _kernel_layout(g).astype(qt.dtype)
    delta128, lse128 = bwd_prep(dot_, ot, lse)

    perm = [(i, (i + 1) % nshards) for i in range(nshards)]
    zf = qt.astype(jnp.float32) * 0.0
    zkv = kt.astype(jnp.float32) * 0.0

    def step(carry, t):
        dq, dk, dv, kr, vr = carry
        src = (idx - t) % nshards
        d = ring_offset(idx, src, sq, striped)
        dq_p, dk_p, dv_p = flash_attention_bwd(
            qt, kr, vr, dot_, delta128, lse128, d, causal=causal,
            block_q=blk, block_k=blk, q_heads=n, kv_heads=nkv)
        dq = dq + dq_p
        dk = dk + dk_p
        dv = dv + dv_p
        kr = jax.lax.ppermute(kr, axis, perm)
        vr = jax.lax.ppermute(vr, axis, perm)
        dk = jax.lax.ppermute(dk, axis, perm)
        dv = jax.lax.ppermute(dv, axis, perm)
        return (dq, dk, dv, kr, vr), None

    (dq, dk, dv, _kr, _vr), _ = jax.lax.scan(
        step, (zf, zkv, zkv, kt, vt), jnp.arange(nshards))

    def back(x, heads, dtype):
        return jnp.moveaxis(x.reshape(b, heads, sq, h), 1,
                            2).astype(dtype)

    return (back(dq, n, qt.dtype), back(dk, nkv, kt.dtype),
            back(dv, nkv, vt.dtype))


_ring_flash.defvjp(_ring_flash_fwd_impl, _ring_flash_bwd)


# ---------------------------------------------------------------------------
# Ulysses — all_to_all head parallelism
# ---------------------------------------------------------------------------

def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Any,
                      axis: str = "sp", causal: bool = False,
                      use_flash: Optional[bool] = None) -> jax.Array:
    """DeepSpeed-Ulysses style sequence parallelism: inputs sharded on
    seq; one all_to_all re-shards to (full seq × heads/P), attention
    runs locally per head group, a second all_to_all restores the seq
    sharding. Requires num_heads % axis_size == 0.

    This is the `all_to_all` collective of the reference's collectives
    module (SURVEY.md §5.7) applied to the attention layout swap; on
    TPU both all_to_alls are single fused ICI ops.

    use_flash (default None = flash on TPU): the local attention uses
    the pallas flash kernel. Differentiable either way — flash carries
    a custom_vjp through the pallas backward kernels; blockwise
    differentiates through the XLA scan.
    """
    nshards = mesh.shape[axis]
    n = q.shape[2]
    if n % nshards:
        raise ValueError(f"heads ({n}) not divisible by mesh axis "
                         f"({nshards}) — use ring_attention")
    if k.shape[2] % nshards:
        # GQA with fewer kv heads than ring shards: broadcast up front
        # (the head all_to_all needs every axis to split evenly)
        k, v = _expand_kv(q, k, v)
    flash = (jax.default_backend() == "tpu" if use_flash is None
             else use_flash)
    spec = P(None, axis, None, None)

    def body(qc, kc, vc):
        def seq_to_heads(x):
            # [B, S/P, N, H] -> [B, S, N/P, H] (tiled all_to_all splits
            # the head axis across the ring and concatenates sequence)
            return jax.lax.all_to_all(x, axis, split_axis=2,
                                      concat_axis=1, tiled=True)

        def heads_to_seq(x):
            # [B, S, N/P, H] -> [B, S/P, N, H]
            return jax.lax.all_to_all(x, axis, split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = seq_to_heads(qc), seq_to_heads(kc), seq_to_heads(vc)
        # local attention sees the FULL sequence for its head group, so
        # the flash kernel drops straight in on TPU
        if flash:
            from .attention_pallas import flash_attention
            out = flash_attention(qh, kh, vh, causal=causal)
        else:
            out = blockwise_attention(qh, kh, vh, causal=causal)
        return heads_to_seq(out)

    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec))(q, k, v)
