"""Gradients through the pallas flash-attention kernels.

The round-2/3 verdicts' #1 item: training must be able to differentiate
through the flash path. flash_attention carries a jax.custom_vjp whose
backward runs the two-pass pallas kernels
(ops/attention_pallas.flash_attention_bwd); the ring path
(ops/attention._ring_flash) carries its own custom_vjp that replays the
ring, rotating dK/dV partials around with their chunks.

Oracle: the O(S^2) softmax written NaN-safely (stop-gradient row max,
zero rows with no visible keys) — reference_attention's plain softmax
NaNs on fully-masked rows and poisons every gradient, and
blockwise_attention's scan transpose does the same, so neither can
serve as a grad oracle for causal sq > sk.

All pallas runs here are interpret mode on the CPU mesh (same kernel
code the TPU compiles). The ring shard_map uses check_vma=False:
pallas interpret mode cannot run inside a vma-checked shard_map on CPU
(its interpreter loop mixes varying/unvarying dynamic_slices); the
vma-checked wiring is exercised on real TPU via `pytest -m tpu`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.ops.attention import (_ring_flash, reference_attention,
                                   ulysses_attention)
from hpx_tpu.ops.attention_pallas import flash_attention
from hpx_tpu.parallel import make_mesh


def grad_oracle(q, k, v, causal):
    """NaN-safe O(S^2) attention for gradient comparison. Rows with no
    visible keys output 0 and carry zero gradient (the flash kernels'
    convention)."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    h = q.shape[-1]
    s = jnp.einsum("bqnh,bknh->bnqk", qf, kf) / np.sqrt(h)
    sq, sk = s.shape[-2], s.shape[-1]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask = jnp.tril(mask, k=sk - sq)
    s = jnp.where(mask, s, -jnp.inf)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(jnp.where(mask, s - m, -jnp.inf)) * mask
    den = p.sum(-1, keepdims=True)
    out = jnp.einsum("bnqk,bknh->bqnh", p / jnp.where(den > 0, den, 1.0),
                     vf)
    return out.astype(q.dtype)


def _rand(shape, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape, np.float32), dtype)


def _grads(fn, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(
        jnp.float32) * w), argnums=(0, 1, 2))(q, k, v)


def _cmp(got, want, tol):
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol, err_msg=f"d{name}")


class TestFlashGrad:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("sq,sk", [(64, 64), (37, 53), (48, 16),
                                       (16, 48)])
    def test_matches_oracle(self, causal, sq, sk):
        B, N, H = 2, 2, 32
        q = _rand((B, sq, N, H), 0)
        k = _rand((B, sk, N, H), 1)
        v = _rand((B, sk, N, H), 2)
        w = _rand((B, sq, N, H), 3)
        want = _grads(lambda q, k, v: grad_oracle(q, k, v, causal),
                      q, k, v, w)
        got = _grads(
            lambda q, k, v: flash_attention(q, k, v, causal,
                                            block_q=16, block_k=16),
            q, k, v, w)
        _cmp(got, want, 3e-4)

    def test_bfloat16(self):
        B, S, N, H = 2, 64, 2, 32
        q, k, v, w = (_rand((B, S, N, H), i, jnp.bfloat16)
                      for i in range(4))
        wf = w.astype(jnp.float32)
        want = _grads(lambda q, k, v: grad_oracle(q, k, v, True),
                      q, k, v, wf)
        got = _grads(
            lambda q, k, v: flash_attention(q, k, v, True,
                                            block_q=16, block_k=16),
            q, k, v, wf)
        assert got[0].dtype == jnp.bfloat16
        _cmp(got, want, 5e-2)

    def test_value_and_grad_under_jit(self):
        B, S, N, H = 1, 32, 2, 16
        q, k, v = (_rand((B, S, N, H), i) for i in range(3))

        @jax.jit
        def f(q, k, v):
            return jax.value_and_grad(
                lambda q: jnp.sum(flash_attention(q, k, v, True,
                                                  block_q=8,
                                                  block_k=8)))(q)

        val, g = f(q, k, v)
        assert np.isfinite(float(val))
        assert g.shape == q.shape


class TestRingFlashGrad:
    """_ring_flash's custom_vjp: replayed ring with rotating dK/dV
    accumulators, against the oracle through real ppermute plumbing."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_oracle(self, causal, devices):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        B, S, N, H = 2, 64, 2, 32
        q, k, v, w = (_rand((B, S, N, H), i + 10) for i in range(4))
        spec = P(None, "sp", None, None)

        def loss(q, k, v):
            def body(qc, kc, vc, wc):
                o = _ring_flash(qc, kc, vc, "sp", 4, causal)
                return jax.lax.psum(jnp.sum(o * wc), "sp")

            return jax.jit(shard_map(
                body, mesh=mesh, in_specs=(spec,) * 4, out_specs=P(),
                check_vma=False))(q, k, v, w)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = _grads(lambda q, k, v: grad_oracle(q, k, v, causal),
                      q, k, v, w)
        _cmp(got, want, 3e-4)

    def test_forward_value_matches(self, devices):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        B, S, N, H = 2, 64, 2, 32
        q, k, v = (_rand((B, S, N, H), i + 20) for i in range(3))
        spec = P(None, "sp", None, None)
        out = jax.jit(shard_map(
            lambda qc, kc, vc: _ring_flash(qc, kc, vc, "sp", 4, True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(reference_attention(q, k, v,
                                                            True)),
            rtol=3e-4, atol=3e-4)


class TestUlyssesGrad:
    """Ulysses differentiates through the blockwise path on CPU (flash
    defaults on only for TPU, where its custom_vjp takes over)."""

    def test_matches_oracle(self):
        mesh = make_mesh((4,), ("sp",), jax.devices()[:4])
        B, S, N, H = 2, 64, 4, 16
        q, k, v, w = (_rand((B, S, N, H), i + 30) for i in range(4))
        got = _grads(
            lambda q, k, v: ulysses_attention(q, k, v, mesh, "sp", True),
            q, k, v, w)
        want = _grads(lambda q, k, v: grad_oracle(q, k, v, True),
                      q, k, v, w)
        _cmp(got, want, 3e-4)


class TestGQA:
    """Grouped-query attention: k/v carry fewer heads than q, shared
    per group via index remapping (no materialized repeat). Oracle:
    repeat kv heads and run the dense-head path; dK/dV oracle grads
    group-sum over the repeated heads."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("nq,nkv", [(4, 2), (4, 1), (8, 4)])
    def test_forward_matches_repeat_oracle(self, causal, nq, nkv):
        B, S, H = 2, 64, 16
        q = _rand((B, S, nq, H), 40)
        k = _rand((B, S, nkv, H), 41)
        v = _rand((B, S, nkv, H), 42)
        rep = nq // nkv
        got = flash_attention(q, k, v, causal, block_q=16, block_k=16)
        want = flash_attention(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2), causal,
                               block_q=16, block_k=16)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_repeat_oracle(self, causal):
        B, S, H, nq, nkv = 2, 64, 16, 4, 2
        rep = nq // nkv
        q = _rand((B, S, nq, H), 43)
        k = _rand((B, S, nkv, H), 44)
        v = _rand((B, S, nkv, H), 45)
        w = _rand((B, S, nq, H), 46)

        got = _grads(
            lambda q, k, v: flash_attention(q, k, v, causal,
                                            block_q=16, block_k=16),
            q, k, v, w)

        def oracle(q, k, v):
            return grad_oracle(q, jnp.repeat(k, rep, axis=2),
                               jnp.repeat(v, rep, axis=2), causal)

        # jnp.repeat lives INSIDE the oracle fn, so AD already
        # group-sums its transpose: oracle grads come back in
        # [B, S, nkv, H] directly comparable to the kernel's
        want = _grads(oracle, q, k, v, w)
        _cmp(got, want, 3e-4)

    def test_indivisible_heads_raises(self):
        q = _rand((1, 16, 3, 8), 47)
        k = _rand((1, 16, 2, 8), 48)
        with pytest.raises(ValueError, match="heads"):
            flash_attention(q, k, k)


class TestStripedRingGrad:
    """Striped causal ring (offsets in {0,-1}) must produce the
    reference gradients — both the custom_vjp flash path and AD
    through the XLA scan."""

    def _striped(self, q, k, v, w, devices, use_flash):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from hpx_tpu.ops.attention import (
            ring_attention_sharded, stripe_sequence)
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        spec = P(None, "sp", None, None)

        def loss(q, k, v):
            qs, ks, vs, ws = (stripe_sequence(x, 4)
                              for x in (q, k, v, w))

            def body(qc, kc, vc, wc):
                o = ring_attention_sharded(qc, kc, vc, "sp", 4,
                                           causal=True,
                                           use_flash=use_flash,
                                           striped=True)
                return jax.lax.psum(jnp.sum(o * wc), "sp")

            return jax.jit(shard_map(
                body, mesh=mesh, in_specs=(spec,) * 4, out_specs=P(),
                check_vma=False))(qs, ks, vs, ws)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    @pytest.mark.parametrize("use_flash", [False, True])
    def test_matches_oracle(self, use_flash, devices):
        B, S, N, H = 2, 64, 2, 32
        q, k, v, w = (_rand((B, S, N, H), i + 30) for i in range(4))
        got = self._striped(q, k, v, w, devices, use_flash)
        want = _grads(lambda q, k, v: grad_oracle(q, k, v, True),
                      q, k, v, w)
        _cmp(got, want, 3e-4)


class TestRingFlashGQAGrad:
    """GQA through the flash ring with GROUPED chunks on the wire: the
    backward's dK/dV partials rotate in the kv-head layout. Grads must
    match the repeat-K/V oracle — contiguous AND striped layouts (the
    two features interact inside one _ring_flash fwd/bwd)."""

    @pytest.mark.parametrize("striped", [False, True])
    def test_matches_repeat_oracle(self, striped, devices):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from hpx_tpu.ops.attention import _ring_flash, stripe_sequence
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        B, S, NQ, NKV, H = 2, 64, 4, 2, 32
        q = _rand((B, S, NQ, H), 40)
        k = _rand((B, S, NKV, H), 41)
        v = _rand((B, S, NKV, H), 42)
        w = _rand((B, S, NQ, H), 43)
        qs = P(None, "sp", None, None)

        def loss(q, k, v):
            if striped:
                q, k, v, wl = (stripe_sequence(x, 4)
                               for x in (q, k, v, w))
            else:
                wl = w

            def body(qc, kc, vc, wc):
                o = _ring_flash(qc, kc, vc, "sp", 4, True, striped)
                return jax.lax.psum(jnp.sum(o * wc), "sp")

            return jax.jit(shard_map(
                body, mesh=mesh, in_specs=(qs, qs, qs, qs),
                out_specs=P(), check_vma=False))(q, k, v, wl)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        kr = jnp.repeat(k, NQ // NKV, axis=2)
        vr = jnp.repeat(v, NQ // NKV, axis=2)

        def oref(q, kr, vr):
            return grad_oracle(q, kr, vr, True)

        wantq, wantkr, wantvr = _grads(oref, q, kr, vr, w)
        # repeat transposes to a group-sum on the kv side
        g = NQ // NKV
        wantk = wantkr.reshape(B, S, NKV, g, H).sum(axis=3)
        wantv = wantvr.reshape(B, S, NKV, g, H).sum(axis=3)
        _cmp(got, (wantq, wantk, wantv), 3e-4)

    def test_grouped_chunks_on_the_wire(self, devices):
        """The compiled program must ppermute KV-sized buffers, never
        q-head-expanded ones — the whole point of grouped GQA rings."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from hpx_tpu.ops.attention import ring_attention_sharded
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        B, S, NQ, NKV, H = 2, 64, 4, 1, 32
        q = _rand((B, S, NQ, H), 44)
        k = _rand((B, S, NKV, H), 45)
        v = _rand((B, S, NKV, H), 46)
        spec = P(None, "sp", None, None)

        def body(qc, kc, vc):
            return ring_attention_sharded(qc, kc, vc, "sp", 4,
                                          causal=True, use_flash=True)

        fn = shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=spec, check_vma=False)
        jaxpr = str(jax.make_jaxpr(fn)(q, k, v))
        sq = S // 4
        kv_shape = f"[{B * NKV},{sq},{H}]"        # kernel-layout rows
        exp_shape = f"[{B * NQ},{sq},{H}]"
        perm_lines = [ln for ln in jaxpr.splitlines()
                      if "ppermute" in ln]
        assert perm_lines, "no ppermute in the ring program?"
        assert any(kv_shape in ln for ln in perm_lines), \
            (kv_shape, perm_lines[:4])
        assert not any(exp_shape in ln for ln in perm_lines), \
            (exp_shape, perm_lines[:4])
