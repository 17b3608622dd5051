"""Fused Pallas paged decode attention vs the XLA gather oracle.

`ops/attention_pallas.fused_paged_attention` walks the block table
in-kernel; `ops/paged_attention`'s gather formulation is the DESIGNATED
oracle it is pinned against. The numerics contract (see the kernel's
section comment): bitwise-equal scores and softmax, final logits within
~1 ulp (the PV contraction is the kernel's 2-D dot vs XLA's batched
einsum), and therefore EXACT tokens — which the server-level tests here
assert across generate()/gather/fused, greedy/sampled,
speculative/non-speculative, bf16 and int8 KV.

`fused_paged_online_attention` (paged_kernel="fused_online") carries a
WEAKER, tolerance-budgeted contract: the online-softmax recurrence
renormalizes per block, so logits drift O(eps * num_blocks) from the
oracle — a few f32 ulp at test extents — while greedy tokens stay
identical on the acceptance sweep. Its VMEM scratch is O(block): the
(acc, m, l) carry never allocates a sequence-extent array, which
`paged_online_scratch_shapes` makes checkable by construction.

fp8 (e4m3) KV pools reuse the int8 sidecar plumbing wholesale: same
per-(block, kv-head) absmax scales, same `*_q` scatter OOB-drop
semantics, same dequant-at-gather on both formulations — so fused
vs gather stays ulp-tight under fp8 even though fp8 vs full precision
is a lossy ~2^-4 relative grid. All kernel runs use interpret mode
off-TPU, so this file is CPU-CI green by construction.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import attention_pallas as ap
from hpx_tpu.ops.paged_attention import (
    paged_decode_attention,
    paged_window_attention,
    quantize_blocks,
    scatter_token,
    scatter_window,
    scatter_window_q,
)

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)

REQS = [dict(prompt=[3, 1, 4], max_new=9),
        dict(prompt=[2, 7], max_new=5),
        dict(prompt=[5, 6, 7, 8, 9], max_new=12),
        dict(prompt=[1], max_new=7),
        dict(prompt=[9, 9, 2, 1], max_new=3),
        dict(prompt=[4, 4], max_new=10)]

SAMPLED = [dict(prompt=[3, 1, 4], max_new=8, temperature=0.9,
                key=jax.random.PRNGKey(7)),
           dict(prompt=[2, 7, 9], max_new=8, temperature=0.7,
                key=jax.random.PRNGKey(8)),
           dict(prompt=[5, 5], max_new=6, temperature=1.3,
                key=jax.random.PRNGKey(9))]


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


# -- op level: fused vs gather ----------------------------------------------

def _paged_state(bs, maxb, B=3, nkv=2, nq=4, hd=8, w=1,
                 dtype=jnp.float32, seed=0):
    """Random pools + a shuffled table (logical != physical) + ragged
    positions, one slot pinned to the partial-first-block corner."""
    rng = np.random.default_rng(seed)
    nb = B * maxb + 2
    kp = jnp.asarray(rng.standard_normal((nb, nkv, bs, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((nb, nkv, bs, hd)), dtype)
    perm = rng.permutation(np.arange(1, nb))[:B * maxb]
    table = jnp.asarray(perm.reshape(B, maxb).astype(np.int32))
    pos = rng.integers(0, maxb * bs - w, size=B).astype(np.int32)
    pos[0] = 1                              # nearly-empty slot
    pos = jnp.asarray(pos)
    q = jnp.asarray(rng.standard_normal((B, w, nq, hd)), dtype)
    knew = rng.standard_normal((B, nkv, hd) if w == 1
                               else (B, w, nkv, hd))
    vnew = rng.standard_normal((B, nkv, hd) if w == 1
                               else (B, w, nkv, hd))
    return (kp, vp, table, pos, q,
            jnp.asarray(knew, dtype), jnp.asarray(vnew, dtype))


@pytest.mark.parametrize("bs", [8, 16, 32])
def test_fused_decode_matches_gather(bs):
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3, seed=bs)
    ag, kg, vg = paged_decode_attention(q, kn, vn, kp, vp, table, pos)
    af, kf, vf = paged_decode_attention(q, kn, vn, kp, vp, table, pos,
                                        fused=True, interpret=True)
    # identical writes (same scatter either way), ulp-tight attention
    assert (np.asarray(kg) == np.asarray(kf)).all()
    assert (np.asarray(vg) == np.asarray(vf)).all()
    np.testing.assert_allclose(np.asarray(ag), np.asarray(af),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("bs", [8, 16])
def test_fused_window_matches_gather(bs):
    # W=4 verify window, GQA (4 q heads over 2 kv heads), ragged pos0
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3, w=4,
                                                 seed=100 + bs)
    ag, _, _ = paged_window_attention(q, kn, vn, kp, vp, table, pos)
    af, _, _ = paged_window_attention(q, kn, vn, kp, vp, table, pos,
                                      fused=True, interpret=True)
    np.testing.assert_allclose(np.asarray(ag), np.asarray(af),
                               rtol=2e-6, atol=2e-6)


def test_fused_bf16_stays_within_one_ulp():
    kp, vp, table, pos, q, kn, vn = _paged_state(16, maxb=2, seed=5,
                                                 dtype=jnp.bfloat16)
    ag, _, _ = paged_decode_attention(q, kn, vn, kp, vp, table, pos)
    af, _, _ = paged_decode_attention(q, kn, vn, kp, vp, table, pos,
                                      fused=True, interpret=True)
    # scores+softmax are bitwise-equal; the final bf16 PV cast may
    # differ by one bf16 ulp where the f32 dots rounded apart
    np.testing.assert_allclose(np.asarray(ag, np.float32),
                               np.asarray(af, np.float32),
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("bs", [8, 16])
def test_fused_int8_matches_gather_int8(bs):
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3,
                                                 seed=200 + bs)
    kq, ks = quantize_blocks(kp)
    vq, vs = quantize_blocks(vp)
    ag, kg, vg, ksg, vsg = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs)
    af, kf, vf, ksf, vsf = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs,
        fused=True, interpret=True)
    # int8 pools and scales update identically; both paths dequantize
    # with the same elementwise ops, so attention stays ulp-tight
    assert (np.asarray(kg) == np.asarray(kf)).all()
    assert (np.asarray(ksg) == np.asarray(ksf)).all()
    assert (np.asarray(vsg) == np.asarray(vsf)).all()
    np.testing.assert_allclose(np.asarray(ag), np.asarray(af),
                               rtol=2e-6, atol=2e-6)


# -- op level: fused_online vs gather ---------------------------------------

@pytest.mark.parametrize("bs", [8, 16, 32])
def test_online_decode_matches_gather(bs):
    """The tolerance-budgeted contract: block-streamed online softmax
    drifts O(eps * num_blocks) from the oracle, a few f32 ulp here."""
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3,
                                                 seed=300 + bs)
    ag, kg, vg = paged_decode_attention(q, kn, vn, kp, vp, table, pos)
    ao, ko, vo = paged_decode_attention(q, kn, vn, kp, vp, table, pos,
                                        fused="online", interpret=True)
    assert (np.asarray(kg) == np.asarray(ko)).all()
    assert (np.asarray(vg) == np.asarray(vo)).all()
    np.testing.assert_allclose(np.asarray(ag), np.asarray(ao),
                               rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("bs", [8, 16])
def test_online_window_matches_gather(bs):
    # W=4 verify window, GQA (4 q heads over 2 kv heads), ragged pos0 —
    # the per-window-row horizon mask is shared with the bitwise kernel
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3, w=4,
                                                 seed=400 + bs)
    ag, _, _ = paged_window_attention(q, kn, vn, kp, vp, table, pos)
    ao, _, _ = paged_window_attention(q, kn, vn, kp, vp, table, pos,
                                      fused="online", interpret=True)
    np.testing.assert_allclose(np.asarray(ag), np.asarray(ao),
                               rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("bs", [8, 16])
def test_online_int8_matches_gather_int8(bs):
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3,
                                                 seed=500 + bs)
    kq, ks = quantize_blocks(kp)
    vq, vs = quantize_blocks(vp)
    ag, _, _, _, _ = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs)
    ao, _, _, _, _ = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs,
        fused="online", interpret=True)
    np.testing.assert_allclose(np.asarray(ag), np.asarray(ao),
                               rtol=5e-6, atol=5e-6)


def test_online_scratch_is_o_block():
    """The acceptance gate on the kernel's memory shape: the online
    kernel's VMEM scratch is the (acc, m, l) flash carry — a function
    of (padded q rows, head_dim) ONLY. No sequence extent reaches the
    allocation, by signature: a refactor that reintroduces an
    (S,)-shaped scratch has to change this function to get it."""
    import inspect
    sig = inspect.signature(ap.paged_online_scratch_shapes)
    assert list(sig.parameters) == ["wg_pad", "head_dim"]
    shapes = [tuple(s.shape)
              for s in ap.paged_online_scratch_shapes(8, 8)]
    assert shapes == [(8, 8), (8, 128), (8, 128)]
    # scratch does not grow with anything sequence-like
    assert shapes == [tuple(s.shape)
                      for s in ap.paged_online_scratch_shapes(8, 8)]
    big = [tuple(s.shape)
           for s in ap.paged_online_scratch_shapes(16, 128)]
    assert big == [(16, 128), (16, 128), (16, 128)]


# -- fp8 pools ---------------------------------------------------------------

def test_fp8_quantize_roundtrip():
    """e4m3 blocks under the per-(block, kv-head) absmax scale: the
    round-trip lands on the fp8 grid — relative error bounded by the
    format's 2^-4 mantissa step, never biased past one step."""
    rng = np.random.default_rng(9)
    rows = jnp.asarray(rng.standard_normal((4, 2, 16, 8)), jnp.float32)
    pq, sc = quantize_blocks(rows, jnp.float8_e4m3fn)
    assert pq.dtype == jnp.float8_e4m3fn
    assert sc.shape == (4, 2)                 # per-(block, kv-head)
    deq = (np.asarray(pq, np.float32)
           * np.asarray(sc)[:, :, None, None])
    orig = np.asarray(rows)
    err = np.abs(deq - orig)
    amax = np.abs(orig).max(axis=(2, 3), keepdims=True)
    assert (err <= np.abs(orig) * 2.0 ** -4 + amax * 2.0 ** -7).all()


def test_quantize_blocks_rejects_unknown_dtype():
    rows = jnp.zeros((1, 1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="unsupported pool dtype"):
        quantize_blocks(rows, jnp.float16)


@pytest.mark.parametrize("bs", [8, 16])
def test_fused_fp8_matches_gather_fp8(bs):
    """Both formulations see the SAME e4m3 pools and dequantize with
    the same elementwise ops — fused vs gather stays ulp-tight even
    though fp8 vs full precision is lossy."""
    kp, vp, table, pos, q, kn, vn = _paged_state(bs, maxb=3,
                                                 seed=600 + bs)
    kq, ks = quantize_blocks(kp, jnp.float8_e4m3fn)
    vq, vs = quantize_blocks(vp, jnp.float8_e4m3fn)
    assert kq.dtype == jnp.float8_e4m3fn
    ag, kg, vg, ksg, vsg = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs)
    assert kg.dtype == jnp.float8_e4m3fn      # frontier RMW kept fp8
    af, kf, vf, ksf, vsf = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs,
        fused=True, interpret=True)
    ao, _, _, _, _ = paged_decode_attention(
        q, kn, vn, kq, vq, table, pos, k_scale=ks, v_scale=vs,
        fused="online", interpret=True)
    assert (np.asarray(kg, np.float32)
            == np.asarray(kf, np.float32)).all()
    assert (np.asarray(ksg) == np.asarray(ksf)).all()
    assert (np.asarray(vsg) == np.asarray(vsf)).all()
    np.testing.assert_allclose(np.asarray(ag), np.asarray(af),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(ag), np.asarray(ao),
                               rtol=5e-6, atol=5e-6)


# -- the bounded walk: unquantized pools, head_dim % 128 == 0 -----------------
#
# `_fused_paged_call` sends a `fused` call over such pools to
# `_paged_live_kernel` (grid (slot, kv head); the kernel copies the
# entries below `_walk_entries` itself) and every other call to the
# grid walk (grid (slot, kv head, entry)), which the tests above pin.

_HD = 128


def _dead_tail_case(dtype, w, window, poison):
    """Pools, a table and positions whose DEAD entries (a slot's table
    tail; a ring's slots not reached yet) all point at block 1, which
    no live position maps to; `poison` fills it with NaN, else zeros."""
    bs, maxb, B, nkv, nq = 16, 8, 3, 2, 4
    rng = np.random.default_rng(31)
    nb = B * maxb + 2
    kp = rng.standard_normal((nb, nkv, bs, _HD)).astype(np.float32)
    vp = rng.standard_normal((nb, nkv, bs, _HD)).astype(np.float32)
    kp[1] = vp[1] = np.nan if poison else 0.0
    pos = np.array([0, 2 * bs - 1, 5 * bs + 3], np.int32)
    table = rng.permutation(np.arange(2, nb))[:B * maxb].reshape(B, maxb)
    entries = (pos + w - 1) // bs + 1
    table = np.where(np.arange(maxb)[None, :] < entries[:, None], table, 1)
    q = jnp.asarray(rng.standard_normal((B, w, nq, _HD)), dtype)
    return (q, jnp.asarray(kp, dtype), jnp.asarray(vp, dtype),
            jnp.asarray(table.astype(np.int32)), jnp.asarray(pos))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
def test_fused_never_reads_a_dead_entry(window, w, dtype):
    """Two pools that differ only in a block no live position maps to
    give the same bits: the walk stops at the slot's live length, and
    what the bank held past it never reaches the output (the grid walk
    fetched the block, and 0 x NaN is NaN in its p @ V)."""
    outs = []
    for poison in (True, False):
        q, kp, vp, table, pos = _dead_tail_case(dtype, w, window, poison)
        outs.append(np.asarray(ap.fused_paged_attention(
            q, kp, vp, table, pos, interpret=True, window=window),
            np.float32))
    assert np.isfinite(outs[0]).all()
    assert (outs[0] == outs[1]).all()


@pytest.mark.parametrize("head", [0, 2, 3])
@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
def test_a_dead_row_of_any_head_of_the_group_stays_out(window, w, head):
    """Four kv heads share a grid step and a copy (hg = 4). NaN sits in
    ONE head's rows of the block every dead entry points at, and in
    that head's rows of every block slot 0 walks (so that head's bank
    holds NaN past the walks of the slots after it): slot 0's queries
    of that head read NaN, as they must, and every other output equals,
    bit for bit, the run with zeros in the NaN's place."""
    bs, maxb, B, nkv, nq = 16, 8, 3, 4, 8
    rng = np.random.default_rng(37)
    nb = B * maxb + 2
    kp = rng.standard_normal((nb, nkv, bs, _HD)).astype(np.float32)
    vp = rng.standard_normal((nb, nkv, bs, _HD)).astype(np.float32)
    pos = np.array([maxb * bs - w, 3, 2 * bs + 1], np.int32)
    table = np.arange(2, nb, dtype=np.int32).reshape(B, maxb)
    entries = (pos + w - 1) // bs + 1
    table = np.where(np.arange(maxb)[None, :] < entries[:, None], table, 1)
    q = jnp.asarray(rng.standard_normal((B, w, nq, _HD)), jnp.bfloat16)
    assert ap.walk_heads_per_copy(*_walk_sizes(
        nkv, maxb, bs, _HD, w * nq // nkv, jnp.bfloat16)) == nkv
    outs = []
    for fill in (np.nan, 0.0):
        for pool in (kp, vp):
            pool[1, head] = fill
            pool[table[0], head] = fill
        outs.append(np.asarray(ap.fused_paged_attention(
            q, jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16),
            jnp.asarray(table), jnp.asarray(pos), interpret=True,
            window=window), np.float32))
    bad, good = outs
    g = nq // nkv
    mine = np.zeros(bad.shape, bool)
    mine[0, :, head * g:(head + 1) * g] = True     # slot 0, that head
    assert np.isnan(bad[mine]).all()
    assert np.isfinite(bad[~mine]).all()
    assert (bad[~mine] == good[~mine]).all()


def _assert_close_to_oracle(fused, gather, dtype):
    """f32: ulp-tight; bf16: one ulp of the final cast at |x| <= 2."""
    tol = 2e-6 if dtype == jnp.float32 else 1.6e-2
    np.testing.assert_allclose(np.asarray(gather, np.float32),
                               np.asarray(fused, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("corner,w", [
    ("first_row", 1), ("block_end", 1), ("block_start", 1),
    ("table_end", 1), ("first_row", 4), ("block_end", 4),
    ("crosses_edge", 4), ("table_end", 4), ("dead_slot", 1)])
def test_bounded_walk_corners_match_gather(corner, w, dtype):
    """The corners of n_live = min((pos0 + W - 1) // bs + 1, maxb)
    against the gather oracle: position 0, the last row of a block, the
    first of the next, the table's last row, a verify window whose rows
    cross a block edge, and a dead slot (all trash, position 0)."""
    bs, maxb = 8, 6
    kp, vp, table, pos, q, kn, vn = _paged_state(
        bs, maxb, B=2, hd=_HD, w=w, dtype=dtype, seed=41)
    p0 = {"first_row": 0, "block_end": bs - 1, "block_start": bs,
          "table_end": maxb * bs - w, "crosses_edge": 2 * bs - 2,
          "dead_slot": 0}[corner]
    pos = pos.at[1].set(p0)
    if corner == "dead_slot":
        table = table.at[1].set(0)
    attend = paged_decode_attention if w == 1 else paged_window_attention
    ag = attend(q, kn, vn, kp, vp, table, pos)[0]
    af = attend(q, kn, vn, kp, vp, table, pos, fused=True,
                interpret=True)[0]
    _assert_close_to_oracle(af, ag, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("lap", ["first_slot", "not_round", "ring_full",
                                 "gone_round", "round_twice"])
def test_bounded_walk_on_a_ring_matches_gather(lap, dtype):
    """A window group's ring (logical block b in slot b % ring): a
    sequence that has reached one slot, some, exactly all of them, and
    that has gone round (every slot live, the window's edge inside an
    overwritten one), against the gather oracle over the same ring."""
    bs, ring, window = 8, 5, 20
    kp, vp, table, pos, q, kn, vn = _paged_state(
        bs, ring, B=2, hd=_HD, dtype=dtype, seed=43)
    p0 = {"first_slot": 3, "not_round": 2 * bs + 5,
          "ring_full": ring * bs - 1, "gone_round": ring * bs + 2,
          "round_twice": 2 * ring * bs + bs + 1}[lap]
    pos = jnp.asarray([p0, ring * bs + bs + 4], jnp.int32)
    ag = paged_decode_attention(q, kn, vn, kp, vp, table, pos,
                                window=window)[0]
    af = paged_decode_attention(q, kn, vn, kp, vp, table, pos,
                                window=window, fused=True,
                                interpret=True)[0]
    _assert_close_to_oracle(af, ag, dtype)


def _paged_launch(fn, *args):
    """The one `pallas_call` in fn's jaxpr: (grid, the scratch refs'
    avals, the chip's compiler parameters)."""
    calls = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    par = calls[0].params
    gm = par["grid_mapping"]
    scratch = [v.aval for v in
               par["jaxpr"].invars[-gm.num_scratch_operands:]]
    return tuple(gm.grid), scratch, par["compiler_params"]["mosaic_tpu"]


def _paged_grid(fn, *args):
    """The grid of the one `pallas_call` in fn's jaxpr."""
    return _paged_launch(fn, *args)[0]


def _walk_sizes(nkv, maxb, bs, hd, wg, dtype):
    """`walk_heads_per_copy`'s arguments for a call's shapes."""
    item = jnp.dtype(dtype).itemsize
    return nkv, maxb * bs, hd, wg, item, item


@pytest.mark.parametrize("interpret", [True, False],
                         ids=["interpret", "compiled"])
@pytest.mark.parametrize("kernel,pool,hd,w,window,axes", [
    ("fused", "bf16", 128, 1, 0, 2), ("fused", "f32", 128, 1, 0, 2),
    ("fused", "bf16", 256, 1, 0, 2), ("fused", "bf16", 128, 4, 0, 2),
    ("fused", "bf16", 128, 1, 40, 2),
    ("fused", "int8", 128, 1, 0, 3), ("fused", "fp8", 128, 1, 0, 3),
    ("fused", "bf16", 64, 1, 0, 3), ("fused", "f32", 8, 1, 0, 3),
    ("fused", "bf16", 64, 1, 40, 3),
    ("fused_online", "bf16", 128, 1, 0, 3),
    ("fused_online", "int8", 128, 1, 0, 3)])
def test_which_calls_take_the_bounded_walk(kernel, pool, hd, w, window,
                                           axes, interpret):
    """The dispatch, read off the jaxpr: a `fused` call over
    unquantized pools with a head of whole 128-lane rows launches the
    two-axis grid (slot, n_kv // hg), a grid step a slot and GROUP of
    `hg` kv heads (here both heads: the banks are small); a quantized
    pool, a narrower head
    and `fused_online` still launch the grid walk's three axes (slot,
    kv head, table entry). The choice looks at the operands alone, so
    it is the same in interpret mode and compiled for the chip."""
    B, nkv, nq, bs, maxb = 2, 2, 4, 16, 3
    dtype = jnp.float32 if pool == "f32" else jnp.bfloat16
    kp, vp, table, pos, q, _, _ = _paged_state(
        bs, maxb, B=B, nkv=nkv, nq=nq, hd=hd, w=w, dtype=dtype, seed=47)
    ks = vs = None
    if pool in ("int8", "fp8"):
        qd = jnp.int8 if pool == "int8" else jnp.float8_e4m3fn
        kp, ks = quantize_blocks(kp, qd)
        vp, vs = quantize_blocks(vp, qd)
    fpa = (ap.fused_paged_online_attention if kernel == "fused_online"
           else ap.fused_paged_attention)
    grid = _paged_grid(
        lambda q, kp, vp: fpa(q, kp, vp, table, pos, k_scale=ks,
                              v_scale=vs, interpret=interpret,
                              window=window), q, kp, vp)
    hg = ap.walk_heads_per_copy(
        *_walk_sizes(nkv, maxb, bs, hd, w * nq // nkv, dtype))
    assert hg == nkv
    assert grid == ((B, nkv // hg) if axes == 2 else (B, nkv, maxb))


# the cells' calls and the corners of the rule: (n_kv, table entries,
# W * group, pool dtype, block) -> heads a copy, TWO sets of banks
# counted (PR 50). Head 128.
_HG_CASES = [
    ((2, 128, 12, jnp.bfloat16, 16), 2),  # StarCoder2-3B: 2 x 2 MB of banks
    ((8, 304, 6, jnp.bfloat16, 16), 8),   # Laguna's full layers: 2 x 20 MB
    ((8, 34, 8, jnp.bfloat16, 16), 8),    # Laguna's ring: 2 x 2.2 MB
    ((1, 128, 24, jnp.bfloat16, 16), 1),  # one kv head (tp = n_kv)
    ((4, 304, 6, jnp.bfloat16, 16), 4),   # Laguna's shard on tp = 2
    ((8, 304, 6, jnp.float32, 16), 4),    # float32 pools: 2 x 40 MB is over
    ((8, 608, 6, jnp.float32, 16), 2),    # and twice the rows
    ((8, 1024, 6, jnp.bfloat16, 16), 2),  # smax 16,384
    ((6, 1024, 8, jnp.bfloat16, 16), 2),  # a divisor, not the largest fit
    ((2, 1792, 12, jnp.bfloat16, 16), 1),     # smax 28,672: one head fits
    ((8, 1792, 6, jnp.bfloat16, 16), 1),
    ((8, 4096, 6, jnp.bfloat16, 16), 1),  # nothing fits: still 1
    # EvaByte: 32 kv heads over 50 entries of 64 rows; one set of 32
    # heads' banks is 52 MB, so two sets hold 16 and a slot is two steps
    ((32, 50, 1, jnp.bfloat16, 64), 16),
    ((32, 50, 4, jnp.bfloat16, 64), 16),  # its verify window alike
]


@pytest.mark.parametrize("shape,want", _HG_CASES, ids=[
    f"nkv{c[0][0]}-maxb{c[0][1]}-wg{c[0][2]}-{jnp.dtype(c[0][3]).name}"
    for c in _HG_CASES])
def test_heads_per_copy_follows_the_banks(shape, want):
    """`hg` is the largest divisor of the call's n_kv whose two sets of
    banks and one head's finish fit the stated VMEM budget, and 1 where
    none does; the grid the launch pins is (B, n_kv // hg)."""
    nkv, maxb, wg, dtype, bs = shape
    sizes = _walk_sizes(nkv, maxb, bs, _HD, wg, dtype)
    hg = ap.walk_heads_per_copy(*sizes)
    assert hg == want and nkv % hg == 0
    assert hg == 1 or ap._walk_vmem_bytes(hg, *sizes[1:]) \
        <= ap._WALK_VMEM_BUDGET
    for more in range(hg + 1, nkv + 1):
        assert nkv % more or ap._walk_vmem_bytes(more, *sizes[1:]) \
            > ap._WALK_VMEM_BUDGET
    if maxb <= 304:
        q = jax.ShapeDtypeStruct((2, 1, nkv * wg, _HD), dtype)
        pool = jax.ShapeDtypeStruct((5, nkv, bs, _HD), dtype)
        grid = _paged_grid(
            lambda q, kp, vp: ap.fused_paged_attention(
                q, kp, vp, jnp.zeros((2, maxb), jnp.int32),
                jnp.zeros((2,), jnp.int32), interpret=True), q, pool, pool)
        assert grid == (2, nkv // hg)


def test_the_banks_are_counted_twice():
    """`_walk_vmem_bytes` holds two sets of K and V banks and one
    head's finish: a head more costs four banks."""
    one, two = (ap._walk_vmem_bytes(hg, 3200, _HD, 8, 2, 2)
                for hg in (1, 2))
    assert two - one == 2 * 2 * 3200 * _HD * 2


@pytest.mark.parametrize("nkv,maxb,bs,window,hg", [
    (2, 8, 16, 0, 2), (8, 34, 16, 512, 8), (32, 50, 64, 0, 16)],
    ids=["two-heads", "ring", "evabyte"])
def test_the_launch_pins_two_sets_and_a_semaphore_pair_a_set(
        nkv, maxb, bs, window, hg):
    """What `_live_walk_call` hands the compiler: the grid (B, n_kv //
    hg) as before, run IN ORDER (a step starts the next step's copies),
    two sets of (hg, S, hd) banks a pool, DMA semaphores (set, pool):
    no set shares a semaphore with the other, and the stated VMEM limit
    covers both sets."""
    B = 3
    q = jax.ShapeDtypeStruct((B, 1, nkv, _HD), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((5, nkv, bs, _HD), jnp.bfloat16)
    grid, scratch, params = _paged_launch(
        lambda q, kp, vp: ap.fused_paged_attention(
            q, kp, vp, jnp.zeros((B, maxb), jnp.int32),
            jnp.zeros((B,), jnp.int32), interpret=True, window=window),
        q, pool, pool)
    assert grid == (B, nkv // hg)
    k_s, v_s, sem = scratch
    assert k_s.shape == v_s.shape == (2, hg, maxb * bs, _HD)
    assert k_s.dtype == v_s.dtype == jnp.bfloat16
    assert sem.shape == (2, 2) and "dma_sem" in str(sem.dtype)
    assert "semaphore" in str(sem.memory_space)
    assert tuple(params.dimension_semantics) == ("arbitrary", "arbitrary")
    banks = k_s.size * 2 + v_s.size * 2
    assert banks < params.vmem_limit_bytes < 128 << 20


def _forced(monkeypatch, hg):
    """Make every bounded-walk launch group `hg` heads (`None`: what the
    shapes give). The rule is the ONE place a launch asks."""
    if hg is None:
        monkeypatch.undo()
    else:
        monkeypatch.setattr(ap, "walk_heads_per_copy", lambda *a: hg)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("nkv", [1, 2, 8])
def test_a_group_of_heads_gives_one_heads_bits(nkv, w, window, dtype,
                                               monkeypatch):
    """Every group size a call's n_kv admits (n_kv 1 / 2 / 8: hg 1, 2,
    and 2 / 4 / 8) gives, bit for bit, what one head a grid step gives
    (PR 31's kernel), and that agrees with the gather oracle as it
    always has: a head's finish runs over its own bank whatever shares
    the copy."""
    bs, maxb, B = 16, 6, 3
    kp, vp, table, pos, q, kn, vn = _paged_state(
        bs, maxb, B=B, nkv=nkv, nq=2 * nkv, hd=_HD, w=w, dtype=dtype,
        seed=53 + nkv)
    pos = jnp.asarray([0, 2 * bs + 3, maxb * bs - w], jnp.int32)

    def run():
        return np.asarray(ap.fused_paged_attention(
            q, kp, vp, table, pos, interpret=True, window=window),
            np.float32)
    assert ap.walk_heads_per_copy(*_walk_sizes(
        nkv, maxb, bs, _HD, 2 * w, dtype)) == nkv
    _forced(monkeypatch, 1)
    one = run()
    for hg in [h for h in (2, 4, 8) if nkv % h == 0] + [None]:
        _forced(monkeypatch, hg)
        assert (run() == one).all(), hg
    if w > 1 and window:
        return      # no oracle: a verify window over a ring is refused
    attend = paged_decode_attention if w == 1 else paged_window_attention
    ag = attend(q, kn, vn, kp, vp, table, pos, window=window)[0]
    af = attend(q, kn, vn, kp, vp, table, pos, window=window, fused=True,
                interpret=True)[0]
    _assert_close_to_oracle(af, ag, dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_a_bank_too_long_for_a_group_walks_one_head(dtype, monkeypatch):
    """An smax at which only hg = 1 fits (the budget shrunk to this
    toy's banks, as 28,672 rows do to the real one): the launch falls
    back to one head a grid step and gives the grouped kernel's bits."""
    bs, maxb, B, nkv = 16, 6, 2, 4
    kp, vp, table, pos, q, _, _ = _paged_state(
        bs, maxb, B=B, nkv=nkv, nq=8, hd=_HD, dtype=dtype, seed=59)
    sizes = _walk_sizes(nkv, maxb, bs, _HD, 2, dtype)

    def run():
        fn = lambda q, kp, vp: ap.fused_paged_attention(  # noqa: E731
            q, kp, vp, table, pos, interpret=True)
        return _paged_grid(fn, q, kp, vp), np.asarray(fn(q, kp, vp),
                                                      np.float32)
    grid, grouped = run()
    assert grid == (B, 1)
    monkeypatch.setattr(ap, "_WALK_VMEM_BUDGET",
                        ap._walk_vmem_bytes(2, *sizes[1:]) - 1)
    assert ap.walk_heads_per_copy(*sizes) == 1
    grid, single = run()
    assert grid == (B, nkv)
    assert (single == grouped).all()


# -- two sets of banks (PR 50): a grid step starts the NEXT step's copies ------
#
# Grid step n lands in set n % 2 and was started by step n - 1. A call
# of ONE slot with every kv head in one group is one grid step: it
# starts its own copies, waits, finishes, which is the kernel as it was
# before the second set, op for op. So "the slot alone" is the bitwise
# oracle here, and the gather form stays the ulp-tight one it always
# was (this file's docstring: the final contraction differs).

def _neighbours_case(dtype, w, nkv=4):
    """Five slots whose walks differ as far as they can: position 0
    beside a full table beside a one-entry slot, a ragged one, and a
    second slot at position 0 at the call's end."""
    bs, maxb, B = 16, 6, 5
    kp, vp, table, _, q, kn, vn = _paged_state(
        bs, maxb, B=B, nkv=nkv, nq=2 * nkv, hd=_HD, w=w, dtype=dtype,
        seed=61)
    pos = jnp.asarray([0, maxb * bs - w, bs - w, 2 * bs + 3, 0], jnp.int32)
    return q, kp, vp, table, pos, kn, vn


def _walk(q, kp, vp, table, pos, window):
    return np.asarray(ap.fused_paged_attention(
        q, kp, vp, table, pos, interpret=True, window=window), np.float32)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
@pytest.mark.parametrize("w", [1, 4])
@pytest.mark.parametrize("hg", [1, 2, 4], ids=["hg1", "hg2", "hg-nkv"])
def test_neighbours_that_differ_most_give_the_slot_alone(hg, w, window,
                                                         dtype, monkeypatch):
    """Every slot of the batch, at one head a grid step (four steps a
    slot, each copied during the head before), two, and all four (one
    step a slot, copied during the slot before): bit for bit the call
    of that slot ALONE in one grid step, which starts, waits and
    finishes its own copies as the kernel did before the second set.
    A copy that landed in the set being read, or rows taken from the
    set of the step before, would show in the short slot beside the
    full one. And the batch agrees with the gather form as it always
    has."""
    q, kp, vp, table, pos, kn, vn = _neighbours_case(dtype, w)
    _forced(monkeypatch, hg)
    batch = _walk(q, kp, vp, table, pos, window)
    assert np.isfinite(batch).all()
    _forced(monkeypatch, 4)
    for b in range(q.shape[0]):
        alone = _walk(q[b:b + 1], kp, vp, table[b:b + 1], pos[b:b + 1],
                      window)
        assert (alone[0] == batch[b]).all(), b
    if w > 1 and window:
        return      # no oracle: a verify window over a ring is refused
    attend = paged_decode_attention if w == 1 else paged_window_attention
    _forced(monkeypatch, hg)
    ag = attend(q, kn, vn, kp, vp, table, pos, window=window)[0]
    af = attend(q, kn, vn, kp, vp, table, pos, window=window, fused=True,
                interpret=True)[0]
    _assert_close_to_oracle(af, ag, dtype)


@pytest.mark.parametrize("order", [[4, 3, 2, 1, 0], [1, 0, 2, 4, 3],
                                   [2, 4, 0, 3, 1]],
                         ids=["reversed", "swapped", "shuffled"])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
@pytest.mark.parametrize("hg", [1, 4, None], ids=["hg1", "hg-nkv", "rule"])
def test_a_slots_rows_do_not_move_with_its_neighbours(hg, window, order,
                                                      monkeypatch):
    """The same five slots in another order: every slot's rows are the
    same bits whichever slot ran before it (whose set it does not read)
    and after it (whose copies it starts into the other set)."""
    q, kp, vp, table, pos, _, _ = _neighbours_case(jnp.bfloat16, 1)
    _forced(monkeypatch, hg)
    base = _walk(q, kp, vp, table, pos, window)
    o = np.asarray(order)
    moved = _walk(q[o], kp, vp, table[o], pos[o], window)
    assert (moved == base[o]).all()


def test_a_call_of_one_grid_step_prefetches_nothing(monkeypatch):
    """One slot, one group: the first step of a call is also its last,
    so it starts its own copies and no other (the next step's trip
    count is 0: no read past the table or the positions)."""
    q, kp, vp, table, pos, _, _ = _neighbours_case(jnp.float32, 1)
    _forced(monkeypatch, 4)
    grid = _paged_grid(
        lambda q, kp, vp: ap.fused_paged_attention(
            q, kp, vp, table[1:2], pos[1:2], interpret=True),
        q[1:2], kp, vp)
    assert grid == (1, 1)
    alone = _walk(q[1:2], kp, vp, table[1:2], pos[1:2], 0)
    assert (alone[0] == _walk(q, kp, vp, table, pos, 0)[1]).all()


def test_server_walk_share_follows_the_positions(params):
    """`hbm_read_stats()` says how far the next step's bounded walk
    goes: per live slot p // block + 1 entries of the table's width."""
    from hpx_tpu.svc import performance_counters as pc
    srv = ContinuousServer(params, CFG, slots=3, smax=64, paged=True,
                           paged_kernel="fused", block_size=8)
    st = srv.hbm_read_stats()
    assert st["walk_share"] == 0.0 == st["walk_entries_per_slot"]
    # a head of 8 keeps the grid walk: no set of banks, nothing ahead
    assert st["walk_bank_sets"] == 0
    assert st["walk_steps_prefetched_share"] == 0.0
    for r in REQS[:3]:
        srv.submit(**r)
    seen = 0
    while srv.step():
        live = srv.live_positions()
        if not live:
            continue
        want = np.mean([p // 8 + 1 for p in live.values()])
        st = srv.hbm_read_stats()
        assert st["walk_entries_per_slot"] == pytest.approx(want)
        assert st["walk_share"] == pytest.approx(want / (64 // 8))
        assert st["walk_share"] == srv.cache_stats()["walk_share"]
        # the registry carries the same two numbers
        for name, key in (("count/walk-entries-per-slot",
                           "walk_entries_per_slot"),
                          ("walk-share", "walk_share")):
            assert pc.query_counter(pc.counter_name(
                "cache", name, srv.counter_instance)).value == st[key]
        seen += 1
    assert seen > 3


@pytest.mark.parametrize("kernel,kv_dtype,hd,nkv,want", [
    ("fused", "bf16", _HD, 2, 2), ("fused", "bf16", _HD, 4, 4),
    ("fused", "bf16", _HD, 1, 1),
    ("fused", "int8", _HD, 2, 0), ("fused_online", "bf16", _HD, 2, 0),
    ("gather", "bf16", _HD, 2, 0), ("fused", "bf16", 8, 2, 0)])
def test_server_counts_the_heads_a_copy_carries(kernel, kv_dtype, hd, nkv,
                                                want):
    """`hbm_read_stats()` carries the group of the full group's decode
    call and the copies a slot, layer and step issues (entries x 2
    pools x n_kv / hg), 0 where the server's calls keep the grid walk;
    the sets of banks those copies land in (2, or 0) and the share of a
    call's G = slots x n_kv / hg grid steps whose copies the step
    before started, (G - 1) / G; the registry carries all four."""
    from hpx_tpu.svc import performance_counters as pc
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                n_kv_heads=nkv, head_dim=hd, n_layers=2,
                                d_ff=64)
    srv = ContinuousServer(tfm.init_params(cfg, jax.random.PRNGKey(0)),
                           cfg, slots=3, smax=64, paged=True, block_size=8,
                           paged_kernel=kernel, kv_dtype=kv_dtype)
    st = srv.hbm_read_stats()
    assert st["heads_per_copy"] == want
    assert st["walk_copies_per_slot"] == 0.0
    steps = 3 * nkv // want if want else 0     # G of a layer's call
    ahead = (steps - 1) / steps if want else 0.0
    for r in REQS[:3]:
        srv.submit(**r)
    seen = 0
    while srv.step():
        st = srv.hbm_read_stats()
        if not srv.live_positions():
            continue
        assert st["heads_per_copy"] == want
        assert st["walk_copies_per_slot"] == pytest.approx(
            st["walk_entries_per_slot"] * 2 * nkv / want if want else 0.0)
        assert st["walk_bank_sets"] == (2 if want else 0)
        assert st["walk_steps_prefetched_share"] == pytest.approx(ahead)
        for name, key in (("count/heads-per-copy", "heads_per_copy"),
                          ("count/walk-copies-per-slot",
                           "walk_copies_per_slot"),
                          ("count/walk-bank-sets", "walk_bank_sets"),
                          ("walk-steps-prefetched-share",
                           "walk_steps_prefetched_share")):
            assert pc.query_counter(pc.counter_name(
                "cache", name, srv.counter_instance)).value == st[key]
        seen += 1
    assert seen > 3
    if want:
        # what the launch itself groups, read off the step's jaxpr
        q = jnp.zeros((3, 1, 4, hd), cfg.dtype)
        pool = jnp.zeros((4, nkv, 8, hd), cfg.dtype)
        grid = _paged_grid(
            lambda q, kp, vp: ap.fused_paged_attention(
                q, kp, vp, jnp.zeros((3, 8), jnp.int32),
                jnp.zeros((3,), jnp.int32), interpret=True), q, pool, pool)
        assert grid == (3, nkv // want)


@pytest.mark.parametrize("kern", ["gather", "fused", "fused_online"])
@pytest.mark.parametrize("kvd", ["bf16", "int8", "fp8"])
def test_flash_tune_paged_step_follows_pool_layout(kvd, kern):
    """The block-size sweep (benchmarks/flash_tune.py --paged) builds
    its own pools: every kernel it times must accept them and agree
    with the gather formulation over the same pools, so a pool layout
    move cannot skip the tool that banks ops/paged_blocks.json."""
    from benchmarks import flash_tune
    f, q, hbm = flash_tune.paged_step(jax, jnp, 32, 16, kvd, kern)
    out = np.asarray(f(q), np.float32)
    assert out.shape == q.shape and np.isfinite(out).all()
    assert hbm > 0
    g, qg, _ = flash_tune.paged_step(jax, jnp, 32, 16, kvd, "gather")
    # bf16 outputs: one ulp at |x| <= 1
    np.testing.assert_allclose(out, np.asarray(g(qg), np.float32),
                               atol=8e-3)


@pytest.mark.parametrize("pos", [0, 20, 63], ids=lambda p: f"pos{p}")
def test_flash_tune_paged_step_at_a_position(pos):
    """`--positions`: every slot at one position, the table's tail on
    the one trash block as the server lays it out, and the bytes one
    call has to read counted over the entries reached."""
    from benchmarks import flash_tune
    heads = (4, 4, 2)
    f, q, hbm = flash_tune.paged_step(jax, jnp, 64, 16, "bf16", "fused",
                                      pos=pos, heads=heads)
    g, qg, _ = flash_tune.paged_step(jax, jnp, 64, 16, "bf16", "gather",
                                     pos=pos, heads=heads)
    assert q.shape == (4, 1, 4, 128)
    assert hbm == 2 * 4 * (pos // 16 + 1) * 16 * 2 * 128 * 2
    np.testing.assert_allclose(np.asarray(f(q), np.float32),
                               np.asarray(g(qg), np.float32), atol=8e-3)


@pytest.mark.parametrize("kern", ["gather", "fused"])
def test_flash_tune_paged_measure_times_one_dispatch(kern, monkeypatch):
    """`paged_measure` chains its calls inside ONE jitted loop (the trip
    count is data: one compile) and reads the clock around one dispatch
    a chain, so the host's dispatch latency is not in the slope."""
    from benchmarks import flash_tune
    chains = []
    real = flash_tune.slope_time

    def spy(run_chain, k1, k2, repeats=3):
        chains.append((k1, k2))
        return real(run_chain, k1, k2, repeats=1)
    monkeypatch.setattr(flash_tune, "slope_time", spy)
    gbs, us, spread = flash_tune.paged_measure(
        jax, jnp, 64, 16, "bf16", kern, samples=1, pos=20, heads=(2, 4, 2))
    assert us > 0 and gbs > 0 and spread == 0.0
    (k1, k2), = chains
    assert k1 == 8 and k2 - k1 >= 64       # the slope's two trip counts


# -- row writes: scatter_token / scatter_window vs a NumPy row loop ---------

_TRASH = 0


def _write_case(kind, nkv, dtype, w, seed):
    """(pool, table, pos, vals [B, W, nkv, hd]) of one traffic shape:
    shuffled tables, ragged positions, random rows."""
    bs, maxb, hd = 4, 3, 8
    rng = np.random.default_rng(seed)
    b = {"live": 3, "dead": 4, "past": 2, "mesh": 6}[kind]
    nb = b * maxb + 1
    pool = jnp.asarray(rng.standard_normal((nb, nkv, bs, hd)), dtype)
    table = rng.permutation(np.arange(1, nb)).reshape(b, maxb)
    pos = rng.integers(0, maxb * bs - w + 1, size=b)
    if kind == "dead":          # slots 1 and 3: all-trash tables,
        table[[1, 3]] = _TRASH  # both on the same trash row
        pos[3] = pos[1]
    if kind == "past":          # slot 0 starts on the table's last row
        pos[0] = maxb * bs - 1
    vals = jnp.asarray(rng.standard_normal((b, w, nkv, hd)), dtype)
    return (pool, jnp.asarray(table.astype(np.int32)),
            jnp.asarray(pos.astype(np.int32)), vals)


def _reference_rows(pool, table, pos, vals):
    """The write, row by row in NumPy (a later duplicate overwrites an
    earlier one; rows past the table's extent are dropped)."""
    out = np.array(pool)
    table, pos, vals = map(np.asarray, (table, pos, vals))
    bs, maxb = out.shape[2], table.shape[1]
    for b in range(vals.shape[0]):
        for i in range(vals.shape[1]):
            p = pos[b] + i
            if p < maxb * bs:
                out[table[b, p // bs], :, p % bs] = vals[b, i]
    return out


def _assert_pool_bits(got, pool, table, pos, vals):
    """Bitwise the row loop's pool outside the trash block; inside it
    a row some slot wrote holds ONE of the rows written there (which
    duplicate wins is unspecified) and every other row is untouched."""
    got, old = np.asarray(got), np.asarray(pool)
    want = _reference_rows(pool, table, pos, vals)
    assert got.dtype == want.dtype and got.shape == want.shape
    assert (got[_TRASH + 1:] == want[_TRASH + 1:]).all()
    table, pos, vals = map(np.asarray, (table, pos, vals))
    bs = old.shape[2]
    for r in range(bs):
        cands = [vals[b, i] for b in range(vals.shape[0])
                 for i in range(vals.shape[1])
                 if (pos[b] + i) < table.shape[1] * bs
                 and table[b, (pos[b] + i) // bs] == _TRASH
                 and (pos[b] + i) % bs == r] or [old[_TRASH, :, r]]
        for h in range(old.shape[1]):
            assert any((got[_TRASH, h, r] == c[h]).all() for c in cands)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("nkv", [1, 2, 4])
@pytest.mark.parametrize("kind", ["live", "dead"])
def test_scatter_token_matches_row_loop(kind, nkv, dtype):
    pool, table, pos, vals = _write_case(kind, nkv, dtype, 1, 7 * nkv)
    got = jax.jit(scatter_token)(pool, table, pos, vals[:, 0])
    _assert_pool_bits(got, pool, table, pos, vals)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("nkv", [1, 2, 4])
@pytest.mark.parametrize("kind", ["live", "dead", "past"])
def test_scatter_window_matches_row_loop(kind, nkv, dtype):
    pool, table, pos, vals = _write_case(kind, nkv, dtype, 3, 11 * nkv)
    got = jax.jit(scatter_window)(pool, table, pos, vals)
    _assert_pool_bits(got, pool, table, pos, vals)
    if kind == "past":
        # slot 0's window starts on the table's last row: one row
        # lands, two drop, and the last real block keeps every other
        # row (a clamped write would have hit its rows 0 and 1)
        last = int(table[0, -1])
        assert (np.asarray(got)[last, :, :3]
                == np.asarray(pool)[last, :, :3]).all()
        assert (np.asarray(got)[last, :, 3]
                == np.asarray(vals)[0, 0]).all()


@pytest.mark.parametrize("nkv", [1, 2, 4])
@pytest.mark.parametrize("fused", [False, True], ids=["gather", "fused"])
def test_write_rows_outnumber_attended_slots(fused, nkv):
    """The mesh form: `write=` carries ALL slots' rows (6) while this
    shard attends its own 3; every row lands, the attention is the
    plain call's over the same pools."""
    kp, table, pos, vals = _write_case("mesh", nkv, jnp.float32, 1, 5)
    vp = kp * 0.5
    kn, vn = vals[:, 0], vals[:, 0] + 1.0
    q = jnp.asarray(np.random.default_rng(9).standard_normal(
        (3, 1, 2 * nkv, 8)), jnp.float32)
    att, kg, vg = paged_decode_attention(
        q, kn, vn, kp, vp, table[:3], pos[:3], fused=fused,
        interpret=True, write=(table, pos))
    assert (np.asarray(kg)
            == _reference_rows(kp, table, pos, kn[:, None])).all()
    assert (np.asarray(vg)
            == _reference_rows(vp, table, pos, vn[:, None])).all()
    # slots 3..5 written beforehand, then the plain call: same answer
    k0 = scatter_token(kp, table[3:], pos[3:], kn[3:])
    v0 = scatter_token(vp, table[3:], pos[3:], vn[3:])
    want, _, _ = paged_decode_attention(q, kn[:3], vn[:3], k0, v0,
                                        table[:3], pos[:3], fused=fused,
                                        interpret=True)
    assert (np.asarray(att) == np.asarray(want)).all()


# -- quantized scatter: OOB drop regression ---------------------------------

def test_scatter_window_q_oob_drops_rows_and_scales():
    """A window running past the table's extent must corrupt NOTHING:
    not the frontier block's content via a clamped write, and not any
    block's scale via the sidecar's own scatter."""
    bs, maxb, nkv, hd = 4, 2, 2, 8
    rng = np.random.default_rng(3)
    base = jnp.asarray(rng.standard_normal((3, nkv, bs, hd)),
                       jnp.float32)
    pq, sc = quantize_blocks(base)
    table = jnp.asarray([[0, 1]], jnp.int32)
    # pos0=6: rows 6,7 land in block 1; rows 8,9 are PAST the table
    vals = jnp.asarray(rng.standard_normal((1, 4, nkv, hd)), jnp.float32)
    npq, nsc = scatter_window_q(pq, sc, table, jnp.asarray([6]), vals)
    # unmapped/untouched blocks are bit-identical, scales included —
    # a clamped OOB write would have hit block 1's rows 0/1 instead
    assert (np.asarray(npq[0]) == np.asarray(pq[0])).all()
    assert (np.asarray(npq[2]) == np.asarray(pq[2])).all()
    assert (np.asarray(nsc[0]) == np.asarray(sc[0])).all()
    assert (np.asarray(nsc[2]) == np.asarray(sc[2])).all()
    deq = (np.asarray(npq[1], np.float32)
           * np.asarray(nsc[1])[:, None, None])
    orig = np.asarray(base[1])
    amax = np.abs(np.asarray(vals)).max() + np.abs(orig).max()
    tol = amax / 127 + 1e-6                 # one quantization step
    # the two in-range rows hold the window's first two values; the
    # block's pre-existing rows survive the RMW requantization
    np.testing.assert_allclose(deq[:, 2], np.asarray(vals[0, 0]),
                               atol=tol)
    np.testing.assert_allclose(deq[:, 3], np.asarray(vals[0, 1]),
                               atol=tol)
    np.testing.assert_allclose(deq[:, :2], orig[:, :2], atol=tol)


def test_scatter_window_q_oob_drops_fp8_rows_and_scales():
    """The same OOB-drop regression under fp8 pools: the sidecar
    plumbing is shared with int8, so a clamped write corrupting the
    frontier block (or its scale) would be a DTYPE-DISPATCH bug, not a
    new scatter bug — pin it anyway."""
    bs, maxb, nkv, hd = 4, 2, 2, 8
    rng = np.random.default_rng(13)
    base = jnp.asarray(rng.standard_normal((3, nkv, bs, hd)),
                       jnp.float32)
    pq, sc = quantize_blocks(base, jnp.float8_e4m3fn)
    table = jnp.asarray([[0, 1]], jnp.int32)
    vals = jnp.asarray(rng.standard_normal((1, 4, nkv, hd)),
                       jnp.float32)
    npq, nsc = scatter_window_q(pq, sc, table, jnp.asarray([6]), vals)
    assert npq.dtype == jnp.float8_e4m3fn
    assert (np.asarray(npq[0], np.float32)
            == np.asarray(pq[0], np.float32)).all()
    assert (np.asarray(npq[2], np.float32)
            == np.asarray(pq[2], np.float32)).all()
    assert (np.asarray(nsc[0]) == np.asarray(sc[0])).all()
    assert (np.asarray(nsc[2]) == np.asarray(sc[2])).all()
    deq = (np.asarray(npq[1], np.float32)
           * np.asarray(nsc[1])[:, None, None])
    orig = np.asarray(base[1])
    amax = np.abs(np.asarray(vals)).max() + np.abs(orig).max()
    tol = amax * 2.0 ** -4 + 1e-6           # one e4m3 grid step
    np.testing.assert_allclose(deq[:, 2], np.asarray(vals[0, 0]),
                               atol=tol)
    np.testing.assert_allclose(deq[:, 3], np.asarray(vals[0, 1]),
                               atol=tol)
    np.testing.assert_allclose(deq[:, :2], orig[:, :2], atol=tol)


# -- block-size resolution ---------------------------------------------------

def test_resolve_paged_block_order(monkeypatch):
    monkeypatch.setattr(ap, "_paged_blocks_table", {"hd8xint8": 32})
    monkeypatch.delenv("HPX_PAGED_BLOCK", raising=False)
    assert ap.resolve_paged_block(8, "int8") == (32, "seed")
    assert ap.resolve_paged_block(8, "bf16") == (16, "default")
    monkeypatch.setenv("HPX_PAGED_BLOCK", "64")
    assert ap.resolve_paged_block(8, "int8") == (64, "env")


def test_server_auto_block_size_honors_env(params, monkeypatch):
    monkeypatch.setenv("HPX_PAGED_BLOCK", "8")
    srv = ContinuousServer(params, CFG, slots=2, smax=64, paged=True)
    assert srv.block_size == 8


# -- server level: generate() == gather == fused ------------------------------

def _serve(params, reqs, **kw):
    srv = ContinuousServer(params, CFG, slots=3, smax=64, **kw)
    for r in reqs:
        srv.submit(**r)
    return srv.run(), srv


def _generated(params, cfg, reqs):
    """{rid: tokens} of each request alone through `generate()`: the
    witness that shares no pool, table or paged kernel with a server."""
    out = {}
    for rid, r in enumerate(reqs):
        r = dict(r)
        toks = tfm.generate(params, cfg,
                            jnp.asarray([r.pop("prompt")], jnp.int32),
                            **r)
        out[rid] = [int(t) for t in np.asarray(toks)[0]]
    return out


@pytest.mark.parametrize("reqs", [REQS, SAMPLED],
                         ids=["greedy", "sampled"])
def test_server_fused_matches_generate_and_gather(params, reqs):
    gather, _ = _serve(params, reqs, paged=True, paged_kernel="gather")
    fused, srv = _serve(params, reqs, paged=True, paged_kernel="fused")
    assert srv._paged_kernel == "fused"
    assert fused == gather == _generated(params, CFG, reqs)


@pytest.mark.parametrize("mode", ["greedy", "sampled", "spec1", "spec2"])
def test_server_bounded_walk_matches_generate_and_gather(mode):
    """A model whose head is 128 wide serves `fused` through the
    bounded walk (decode W = 1; the speculative verify window W = k +
    1): the same tokens as the gather server (without speculation) and
    as `generate()`."""
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=2,
                                head_dim=_HD, n_layers=2, d_ff=64)
    p128 = tfm.init_params(cfg, jax.random.PRNGKey(0))
    reqs = SAMPLED if mode == "sampled" else REQS[:4]
    kw = (dict(spec=True, spec_k=int(mode[-1]))
          if mode.startswith("spec") else {})

    def serve(**kw):
        srv = ContinuousServer(p128, cfg, slots=3, smax=64, **kw)
        for r in reqs:
            srv.submit(**r)
        return srv.run()
    fused = serve(paged=True, paged_kernel="fused", **kw)
    assert (fused == serve(paged=True, paged_kernel="gather")
            == _generated(p128, cfg, reqs))


@pytest.mark.parametrize("k", [1, 2])
def test_server_fused_spec_matches_nonspec(params, k):
    base, _ = _serve(params, REQS)
    spec, srv = _serve(params, REQS, paged=True, paged_kernel="fused",
                       spec=True, spec_k=k)
    assert spec == base
    assert srv.spec_stats()["emitted"] > 0


@pytest.mark.parametrize("reqs", [REQS, SAMPLED],
                         ids=["greedy", "sampled"])
def test_server_fused_online_matches_generate_and_gather(params, reqs):
    """The acceptance sweep's token gate: the online kernel's few-ulp
    logit drift never flips a token on this workload — greedy AND
    sampled, against BOTH `generate()` and the gather server."""
    gather, _ = _serve(params, reqs, paged=True, paged_kernel="gather")
    online, srv = _serve(params, reqs, paged=True,
                         paged_kernel="fused_online")
    assert srv._paged_kernel == "fused_online"
    assert srv._paged_fused == "online"
    assert online == gather == _generated(params, CFG, reqs)


@pytest.mark.parametrize("k", [1, 2])
def test_server_fused_online_spec_matches_nonspec(params, k):
    # spec-verify routes through the window entry point: the shared
    # per-window-row horizon mask must hold under the online carry too
    base, _ = _serve(params, REQS)
    spec, srv = _serve(params, REQS, paged=True,
                       paged_kernel="fused_online", spec=True, spec_k=k)
    assert spec == base
    assert srv.spec_stats()["emitted"] > 0


def test_server_int8_fused_matches_int8_gather_exactly(params):
    # the int8 hard contract: both formulations see the SAME quantized
    # pools and dequantize identically, so tokens are identical —
    # greedy AND sampled, speculative included
    for reqs in (REQS, SAMPLED):
        g, _ = _serve(params, reqs, paged=True, paged_kernel="gather",
                      kv_dtype="int8")
        f, _ = _serve(params, reqs, paged=True, paged_kernel="fused",
                      kv_dtype="int8")
        assert f == g
    gs, _ = _serve(params, REQS, paged=True, paged_kernel="gather",
                   kv_dtype="int8", spec=True, spec_k=2)
    fs, _ = _serve(params, REQS, paged=True, paged_kernel="fused",
                   kv_dtype="int8", spec=True, spec_k=2)
    assert fs == gs


def test_server_int8_greedy_matches_bf16(params):
    """Greedy token match under KV quantization on the fixed test
    workload — the ISSUE's acceptance workload. (Not a general
    guarantee: quantization MAY flip near-ties on other inputs; here
    the margins dominate one quantization step.)"""
    int8, srv = _serve(params, REQS, paged=True, kv_dtype="int8")
    assert srv._kv_dtype == "int8"
    assert int8 == _generated(params, CFG, REQS)


def test_server_int8_halves_hbm_read_bytes(params):
    """The tentpole's bandwidth claim at the accounting boundary:
    int8 blocks cost ~half of bf16 blocks (scale sidecars keep the
    ratio just above exactly 0.5), and the live hbm_read_stats()
    counters report exactly block_bytes() x mid-run occupancy for the
    pool dtype actually in use (f32 pools on CPU account as f32)."""
    from hpx_tpu.cache.block_allocator import block_bytes

    nkv, hd, nl = CFG.kv_heads, CFG.head_dim, CFG.n_layers
    stats = {}
    for kvd in ("bf16", "int8"):
        srv = ContinuousServer(params, CFG, slots=2, smax=64,
                               paged=True, kv_dtype=kvd)
        for r in REQS[:2]:
            srv.submit(**r)
        while srv.step():
            st = srv.hbm_read_stats()
            if st["hbm_read_bytes_per_token"]:
                stats.setdefault(kvd, (st, srv.block_size,
                                       srv._kv_acct_dtype()))
    for kvd in ("bf16", "int8"):
        st, bs, acct = stats[kvd]
        assert st["hbm_read_blocks_per_token"] > 0
        assert st["hbm_read_bytes_per_token"] == pytest.approx(
            st["hbm_read_blocks_per_token"]
            * block_bytes(bs, nkv, hd, acct, layers=nl))
    bs = stats["int8"][1]
    ratio = (block_bytes(bs, nkv, hd, "int8", layers=nl)
             / block_bytes(bs, nkv, hd, "bf16", layers=nl))
    assert 0.5 < ratio < 0.6


def test_server_fp8_kernels_agree_and_quarter_hbm_read_bytes(params):
    """The fp8 acceptance gates. Tokens: both kernels over the same
    e4m3 pools emit IDENTICAL tokens (fp8-vs-dense is lossy and makes
    no token claim — kernel-vs-kernel over shared pools is exact).
    Bytes: the live hbm_read_stats() counters account fp8 blocks at
    1 byte/elem + f32 sidecars; against this CPU run's f32 compute
    pools that is the tentpole's <= 0.30x bytes/token (on a bf16
    compute dtype the same pools sit at ~0.52x, like int8)."""
    from hpx_tpu.cache.block_allocator import block_bytes

    g, _ = _serve(params, REQS, paged=True, paged_kernel="gather",
                  kv_dtype="fp8")
    o, srv = _serve(params, REQS, paged=True,
                    paged_kernel="fused_online", kv_dtype="fp8")
    assert srv._kv_dtype == "fp8"
    assert o == g
    gs, _ = _serve(params, REQS, paged=True, paged_kernel="gather",
                   kv_dtype="fp8", spec=True, spec_k=2)
    os_, _ = _serve(params, REQS, paged=True,
                    paged_kernel="fused_online", kv_dtype="fp8",
                    spec=True, spec_k=2)
    assert os_ == gs

    nkv, hd, nl = CFG.kv_heads, CFG.head_dim, CFG.n_layers
    stats = {}
    for kvd in ("bf16", "fp8"):
        srv = ContinuousServer(params, CFG, slots=2, smax=64,
                               paged=True, kv_dtype=kvd)
        for r in REQS[:2]:
            srv.submit(**r)
        while srv.step():
            st = srv.hbm_read_stats()
            if st["hbm_read_bytes_per_token"]:
                stats.setdefault(kvd, (st, srv.block_size,
                                       srv._kv_acct_dtype()))
    for kvd in ("bf16", "fp8"):
        st, bs, acct = stats[kvd]
        assert st["hbm_read_blocks_per_token"] > 0
        assert st["hbm_read_bytes_per_token"] == pytest.approx(
            st["hbm_read_blocks_per_token"]
            * block_bytes(bs, nkv, hd, acct, layers=nl))
    assert stats["fp8"][2] == "fp8"
    bs, base_acct = stats["fp8"][1], stats["bf16"][2]
    ratio = (block_bytes(bs, nkv, hd, "fp8", layers=nl)
             / block_bytes(bs, nkv, hd, base_acct, layers=nl))
    if base_acct == "f32":                  # CPU CI: the 0.25x leg
        assert ratio <= 0.30
    else:                                   # bf16 pools: same as int8
        assert 0.5 < ratio < 0.6


def test_paged_kernel_knob_validation(params):
    with pytest.raises(ValueError, match="paged_kernel"):
        ContinuousServer(params, CFG, slots=2, smax=64, paged=True,
                         paged_kernel="nope")
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousServer(params, CFG, slots=2, smax=64, paged=True,
                         kv_dtype="fp4")
    # near-miss dtype strings fail loudly, never silently serve bf16
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousServer(params, CFG, slots=2, smax=64, paged=True,
                         kv_dtype="fp8_e5m2")
    # the knobs are every server's: there is one cache
    srv = ContinuousServer(params, CFG, slots=2, smax=64,
                           paged_kernel="fused", kv_dtype="int8")
    assert srv.hbm_read_stats()["paged_kernel"] == "fused"
    assert srv.cache_stats()["kv_dtype"] == "int8"
