"""Attention ops tests: blockwise == reference; ring and Ulysses
sequence-parallel forms == reference on the 8-device mesh.

The reference (HPX) has no attention; these validate the long-context
capability built on the halo/all_to_all substrate (SURVEY.md §5.7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.ops.attention import (blockwise_attention, reference_attention,
                                   ring_attention, ulysses_attention)
from hpx_tpu.parallel import make_mesh

B, S, N, H = 2, 64, 4, 16


def _qkv(seed=0, dtype=jnp.float32, s=S):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, s, N, H), np.float32), dtype)
    return mk(), mk(), mk()


def _close(a, b, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(a, np.float32), np.asarray(b, np.float32),
        rtol=tol, atol=tol)


class TestBlockwise:
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("block_k", [16, 23, 64, 512])
    def test_matches_reference(self, causal, block_k):
        q, k, v = _qkv()
        want = reference_attention(q, k, v, causal)
        got = blockwise_attention(q, k, v, causal, block_k=block_k)
        _close(got, want, jnp.float32)

    def test_bfloat16(self):
        q, k, v = _qkv(dtype=jnp.bfloat16)
        want = reference_attention(q, k, v, True)
        got = blockwise_attention(q, k, v, True, block_k=32)
        assert got.dtype == jnp.bfloat16
        _close(got, want, jnp.bfloat16)

    def test_long_seq_memory_shape(self):
        q, k, v = _qkv(s=256)
        out = blockwise_attention(q, k, v, block_k=64)
        assert out.shape == (B, 256, N, H)


class TestPallasFlash:
    """The pallas kernel runs in interpret mode on the CPU mesh — same
    kernel code the TPU compiles, validated here block-by-block."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("shape", [(2, 70, 2, 64), (1, 128, 4, 32)])
    def test_matches_reference(self, causal, shape):
        from hpx_tpu.ops.attention_pallas import flash_attention
        b, s, n, h = shape
        rng = np.random.default_rng(5)
        q, k, v = (jnp.asarray(
            rng.standard_normal((b, s, n, h), np.float32))
            for _ in range(3))
        want = reference_attention(q, k, v, causal)
        got = flash_attention(q, k, v, causal, block_q=32, block_k=16)
        _close(got, want, jnp.float32)

    def test_ragged_seq_padding(self):
        from hpx_tpu.ops.attention_pallas import flash_attention
        q, k, v = _qkv(seed=9, s=37)      # not a block multiple
        want = reference_attention(q, k, v, True)
        got = flash_attention(q, k, v, True, block_q=16, block_k=16)
        _close(got, want, jnp.float32)

    @pytest.mark.parametrize("sq,sk", [(16, 48), (48, 16), (37, 53)])
    def test_causal_cross_lengths(self, sq, sk):
        """causal with sq != sk must use bottom-right alignment
        (kj <= qi + (sk - sq)), matching reference/blockwise — the
        round-1 kernel used top-left and diverged. For sq > sk the
        leading rows see no keys; flash and blockwise both define those
        rows as 0 (reference's full softmax NaNs there), so that case
        compares flash against blockwise."""
        from hpx_tpu.ops.attention_pallas import flash_attention
        rng = np.random.default_rng(11)
        q = jnp.asarray(rng.standard_normal((B, sq, N, H), np.float32))
        k = jnp.asarray(rng.standard_normal((B, sk, N, H), np.float32))
        v = jnp.asarray(rng.standard_normal((B, sk, N, H), np.float32))
        want = (reference_attention(q, k, v, True) if sq <= sk else
                blockwise_attention(q, k, v, True, block_k=16))
        got = flash_attention(q, k, v, True, block_q=16, block_k=16)
        _close(got, want, jnp.float32)
        if sq <= sk:
            _close(blockwise_attention(q, k, v, True, block_k=16), want,
                   jnp.float32)

    def test_front_door_dispatch(self):
        from hpx_tpu.ops.attention import auto_attention
        q, k, v = _qkv(seed=10)
        _close(auto_attention(q, k, v, True),
               reference_attention(q, k, v, True), jnp.float32)


class TestRing:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal, mesh1d):
        mesh = make_mesh((8,), ("sp",))
        q, k, v = _qkv(seed=1)
        want = reference_attention(q, k, v, causal)
        got = ring_attention(q, k, v, mesh, "sp", causal)
        _close(got, want, jnp.float32)

    @pytest.mark.parametrize("causal", [False, True])
    def test_flash_chunk_ring_matches_reference(self, causal):
        """The pallas chunk kernel behind the TPU flash-ring path
        (ops/attention._ring_flash), validated by simulating the ring on
        the host: fold every rotating chunk with the traced global
        offset d, exactly as the device scan does. (pallas interpret
        mode cannot run INSIDE a vma-checked shard_map on CPU — the
        in-shard_map wiring is exercised on real TPU.)"""
        from hpx_tpu.ops.attention_pallas import flash_attention_chunk
        q, k, v = _qkv(seed=6)
        want = reference_attention(q, k, v, causal)
        nsh, sq = 4, S // 4
        outs = []
        for i in range(nsh):
            qc = jnp.moveaxis(q[:, i * sq:(i + 1) * sq], 2, 1
                              ).reshape(B * N, sq, H)
            acc = jnp.zeros((B * N, sq, H), jnp.float32)
            m = jnp.full((B * N, sq, 128), -1e30, jnp.float32)
            l = jnp.zeros((B * N, sq, 128), jnp.float32)
            for j in range(nsh):
                kc = jnp.moveaxis(k[:, j * sq:(j + 1) * sq], 2, 1
                                  ).reshape(B * N, sq, H)
                vc = jnp.moveaxis(v[:, j * sq:(j + 1) * sq], 2, 1
                                  ).reshape(B * N, sq, H)
                acc, m, l = flash_attention_chunk(
                    qc, kc, vc, acc, m, l, jnp.int32(i * sq - j * sq),
                    causal=causal, block_q=8, block_k=8)
            den = jnp.where(l[:, :, :1] > 0, l[:, :, :1], 1.0)
            o = (acc / den).reshape(B, N, sq, H)
            outs.append(jnp.moveaxis(o, 1, 2))
        got = jnp.concatenate(outs, axis=1).astype(q.dtype)
        _close(got, want, jnp.float32)

    def test_output_stays_sharded(self):
        mesh = make_mesh((8,), ("sp",))
        q, k, v = _qkv(seed=2)
        out = ring_attention(q, k, v, mesh, "sp")
        assert len(out.sharding.device_set) == 8

    def test_2d_mesh_dp_x_sp(self):
        # batch over dp, sequence over sp — the combined layout a
        # training step uses
        mesh = make_mesh((2, 4), ("dp", "sp"))
        q, k, v = _qkv(seed=3)
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P("dp", "sp", None, None))
        q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
        want = reference_attention(q, k, v, True)

        from jax import shard_map
        import hpx_tpu.ops.attention as att

        def body(qc, kc, vc):
            # inside dp shard: ring over sp
            nshards = 4
            idx = jax.lax.axis_index("sp")
            b, sq, n, h = qc.shape
            q_pos = idx * sq + jnp.arange(sq)
            axes = ("dp", "sp")
            acc = att._pvary(jnp.zeros((b, sq, n, h), jnp.float32), axes)
            m = att._pvary(jnp.full((b, sq, n), -jnp.inf, jnp.float32),
                           axes)
            l = att._pvary(jnp.zeros((b, sq, n), jnp.float32), axes)

            def step(t, carry):
                acc, m, l, kc, vc = carry
                src = (idx - t) % nshards
                k_pos = src * sq + jnp.arange(sq)
                bias = jnp.where(k_pos[None, :] <= q_pos[:, None],
                                 0.0, -jnp.inf)
                acc, m, l = att._online_block(qc, kc, vc, acc, m, l, bias)
                perm = [(i, (i + 1) % nshards) for i in range(nshards)]
                kc = jax.lax.ppermute(kc, "sp", perm)
                vc = jax.lax.ppermute(vc, "sp", perm)
                return acc, m, l, kc, vc

            acc, m, l, _, _ = jax.lax.fori_loop(0, nshards, step,
                                                (acc, m, l, kc, vc))
            return att._finish(acc, l, qc.dtype)

        got = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P("dp", "sp"), P("dp", "sp"), P("dp", "sp")),
            out_specs=P("dp", "sp")))(q, k, v)
        _close(got, want, jnp.float32)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_reference(self, causal):
        import jax as _j
        mesh = make_mesh((4,), ("sp",), _j.devices()[:4])
        q, k, v = _qkv(seed=4)
        want = reference_attention(q, k, v, causal)
        got = ulysses_attention(q, k, v, mesh, "sp", causal)
        _close(got, want, jnp.float32)

    def test_indivisible_heads_raises(self):
        import jax as _j
        mesh = make_mesh((8,), ("sp",), _j.devices())
        q, k, v = _qkv()          # N=4 heads < 8 shards
        with pytest.raises(ValueError):
            ulysses_attention(q, k, v, mesh, "sp")


class TestGqaXlaPaths:
    """GQA/MQA on the XLA formulations (oracle/fallback paths): fewer
    K/V heads broadcast per group (_expand_kv). The pallas kernels
    handle GQA natively (tests/test_attention_grad.py::TestGQA); these
    pin the non-TPU paths to the repeat-heads oracle."""

    def _gqa(self, nkv, seed=0):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.standard_normal((2, 64, 8, 16)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, 64, nkv, 16)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, 64, nkv, 16)), jnp.float32)
        return q, k, v

    @pytest.mark.parametrize("nkv", [1, 2, 4])
    def test_blockwise_matches_repeat_oracle(self, nkv):
        from hpx_tpu.ops.attention import (blockwise_attention,
                                           reference_attention)
        q, k, v = self._gqa(nkv)
        got = blockwise_attention(q, k, v, causal=True)
        kr = jnp.repeat(k, 8 // nkv, axis=2)
        vr = jnp.repeat(v, 8 // nkv, axis=2)
        want = reference_attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)

    def test_rejects_non_divisible(self):
        from hpx_tpu.ops.attention import blockwise_attention
        q, k, v = self._gqa(3)
        with pytest.raises(ValueError, match="multiple"):
            blockwise_attention(q, k, v)

    def test_ring_sharded_gqa(self, devices):
        """GQA through the XLA ring path under a 4-shard sp mesh."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from jax import shard_map
        from hpx_tpu.ops.attention import (reference_attention,
                                           ring_attention_sharded)
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        q, k, v = self._gqa(2, seed=1)
        spec = P(None, "sp", None, None)

        def body(qc, kc, vc):
            return ring_attention_sharded(qc, kc, vc, "sp", 4,
                                          causal=True, use_flash=False)

        got = jax.jit(shard_map(body, mesh=mesh,
                                in_specs=(spec, spec, spec),
                                out_specs=spec))(q, k, v)
        kr = jnp.repeat(k, 4, axis=2)
        vr = jnp.repeat(v, 4, axis=2)
        want = reference_attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_ulysses_gqa_non_divisible_kv(self, devices):
        """kv heads (2) < shards (4): ulysses broadcasts KV up front."""
        from jax.sharding import Mesh
        from hpx_tpu.ops.attention import (reference_attention,
                                           ulysses_attention)
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        q, k, v = self._gqa(2, seed=2)
        got = ulysses_attention(q, k, v, mesh, "sp", causal=True,
                                use_flash=False)
        kr = jnp.repeat(k, 4, axis=2)
        vr = jnp.repeat(v, 4, axis=2)
        want = reference_attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-5, atol=3e-5)

    def test_ring_flash_gqa(self, devices):
        """GQA through the FLASH ring path (interpret on CPU): the
        library broadcasts grouped K/V before the chunk kernel —
        regression for the nshards>1 flash branch."""
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from hpx_tpu.ops.attention import (reference_attention,
                                           ring_attention_sharded)
        mesh = Mesh(np.array(devices[:4]), ("sp",))
        q, k, v = self._gqa(2, seed=3)
        spec = P(None, "sp", None, None)

        def body(qc, kc, vc):
            return ring_attention_sharded(qc, kc, vc, "sp", 4,
                                          causal=True, use_flash=True)

        # check_vma=False: pallas interpret can't thread vma through
        # the chunk kernel (same caveat as tests/test_attention_grad);
        # the vma-checked wiring runs on real TPU via pytest -m tpu
        got = jax.jit(shard_map(body, mesh=mesh,
                                in_specs=(spec, spec, spec),
                                out_specs=spec,
                                check_vma=False))(q, k, v)
        kr = jnp.repeat(k, 4, axis=2)
        vr = jnp.repeat(v, 4, axis=2)
        want = reference_attention(q, kr, vr, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3)


class TestBlockResolution:
    def test_default_blocks(self, monkeypatch):
        from hpx_tpu.ops import attention_pallas as ap
        monkeypatch.setattr(ap, "_blocks_table", {})   # no tuned table
        monkeypatch.delenv("HPX_FLASH_BLOCK_Q", raising=False)
        monkeypatch.delenv("HPX_FLASH_BLOCK_K", raising=False)
        assert ap.resolve_blocks(4096, 4096, True) == (1024, 1024)
        monkeypatch.setattr(ap, "_blocks_table", None)

    def test_env_override(self, monkeypatch):
        from hpx_tpu.ops import attention_pallas as ap
        monkeypatch.setenv("HPX_FLASH_BLOCK_Q", "256")
        monkeypatch.setenv("HPX_FLASH_BLOCK_K", "512")
        assert ap.resolve_blocks(4096, 4096, True) == (256, 512)

    def test_table_override(self, tmp_path, monkeypatch):
        import json
        from hpx_tpu.ops import attention_pallas as ap
        p = tmp_path / "flash_blocks.json"
        p.write_text(json.dumps({"4096x4096x1": [512, 1024]}))
        monkeypatch.setattr(ap, "_BLOCKS_FILE", str(p))
        monkeypatch.setattr(ap, "_blocks_table", None)   # drop cache
        assert ap.resolve_blocks(4096, 4096, True) == (512, 1024)
        assert ap.resolve_blocks(2048, 2048, True) == (1024, 1024)
        monkeypatch.setattr(ap, "_blocks_table", None)

    def test_explicit_blocks_still_honored(self):
        import numpy as np
        import jax.numpy as jnp
        from hpx_tpu.ops.attention_pallas import flash_attention
        from hpx_tpu.ops.attention import reference_attention
        rng = np.random.default_rng(7)
        q = jnp.asarray(rng.standard_normal((1, 64, 2, 8)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((1, 64, 2, 8)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((1, 64, 2, 8)), jnp.float32)
        out = flash_attention(q, k, v, causal=True, block_q=16,
                              block_k=32)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-3)

    def test_partial_env_override_keeps_table_value(self, tmp_path,
                                                    monkeypatch):
        import json
        from hpx_tpu.ops import attention_pallas as ap
        p = tmp_path / "flash_blocks.json"
        p.write_text(json.dumps({"4096x4096x1": [512, 512]}))
        monkeypatch.setattr(ap, "_BLOCKS_FILE", str(p))
        monkeypatch.setattr(ap, "_blocks_table", None)
        monkeypatch.setenv("HPX_FLASH_BLOCK_Q", "256")
        monkeypatch.delenv("HPX_FLASH_BLOCK_K", raising=False)
        # q from env, k from the tuned table — not a hardcoded 1024
        assert ap.resolve_blocks(4096, 4096, True) == (256, 512)
        monkeypatch.setattr(ap, "_blocks_table", None)


class TestStripedRing:
    """Striped Attention: stripe_sequence layout + per-step offsets in
    {0, -1} balance causal ring work. Results must match the
    contiguous ring / reference exactly (same math, reordered)."""

    def test_stripe_roundtrip_and_layout(self):
        from hpx_tpu.ops.attention import (stripe_sequence,
                                           unstripe_sequence)
        x = jnp.arange(24).reshape(1, 24)
        y = stripe_sequence(x, 4)
        # shard r of 4 holds tokens r, r+4, ...
        np.testing.assert_array_equal(
            np.asarray(y)[0, :6], [0, 4, 8, 12, 16, 20])
        np.testing.assert_array_equal(np.asarray(
            unstripe_sequence(y, 4)), np.asarray(x))
        with pytest.raises(ValueError, match="divisible"):
            stripe_sequence(x, 5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_striped_ring_matches_reference(self, causal, mesh1d):
        from hpx_tpu.ops.attention import ring_attention
        mesh = make_mesh((8,), ("sp",))
        q, k, v = _qkv(seed=11)
        want = reference_attention(q, k, v, causal)
        got = ring_attention(q, k, v, mesh, "sp", causal, striped=True)
        _close(got, want, jnp.float32)

    def test_striped_flash_chunk_offsets(self):
        """The flash path's striped offsets, simulated on the host the
        same way test_flash_chunk_ring_matches_reference does: chunk
        (i, j) folds with d = 0 (j <= i) or -1 — the result, after
        unstriping, is the reference."""
        from hpx_tpu.ops.attention import (stripe_sequence,
                                           unstripe_sequence)
        from hpx_tpu.ops.attention_pallas import flash_attention_chunk
        q, k, v = _qkv(seed=12)
        want = reference_attention(q, k, v, True)
        nsh, sq = 4, S // 4
        qs = stripe_sequence(q, nsh)
        ks = stripe_sequence(k, nsh)
        vs = stripe_sequence(v, nsh)
        outs = []
        for i in range(nsh):
            qc = jnp.moveaxis(qs[:, i * sq:(i + 1) * sq], 2, 1
                              ).reshape(B * N, sq, H)
            acc = jnp.zeros((B * N, sq, H), jnp.float32)
            m = jnp.full((B * N, sq, 128), -1e30, jnp.float32)
            l = jnp.zeros((B * N, sq, 128), jnp.float32)
            for j in range(nsh):
                kc = jnp.moveaxis(ks[:, j * sq:(j + 1) * sq], 2, 1
                                  ).reshape(B * N, sq, H)
                vc = jnp.moveaxis(vs[:, j * sq:(j + 1) * sq], 2, 1
                                  ).reshape(B * N, sq, H)
                acc, m, l = flash_attention_chunk(
                    qc, kc, vc, acc, m, l,
                    jnp.int32(0 if j <= i else -1),
                    causal=True, block_q=8, block_k=8)
            den = jnp.where(l[:, :, :1] > 0, l[:, :, :1], 1.0)
            o = (acc / den).reshape(B, N, sq, H)
            outs.append(jnp.moveaxis(o, 1, 2))
        got = unstripe_sequence(
            jnp.concatenate(outs, axis=1), nsh).astype(q.dtype)
        _close(got, want, jnp.float32)
