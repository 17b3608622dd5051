"""Compile-and-check every pallas kernel on the real chip.

Numerics oracles are the XLA formulations (blockwise attention, roll
stencil) computed ON THE SAME CHIP, so assertions isolate kernel bugs
from backend-numerics differences. bf16 tolerances follow
tests/test_attention.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _qkv(b, s, n, h, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(
        rng.standard_normal((b, s, n, h), np.float32), dtype)
        for _ in range(3))


def _close(a, b, tol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=tol, atol=tol)


class TestFlashForward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_blockwise(self, causal):
        from hpx_tpu.ops.attention import blockwise_attention
        from hpx_tpu.ops.attention_pallas import flash_attention
        q, k, v = _qkv(2, 1024, 4, 64)
        got = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal)
                      )(q, k, v)
        want = jax.jit(lambda q, k, v: blockwise_attention(q, k, v,
                                                           causal)
                       )(q, k, v)
        _close(got, want, 3e-2)

    def test_f32_tighter(self, float32_products):
        from hpx_tpu.ops.attention import blockwise_attention
        from hpx_tpu.ops.attention_pallas import flash_attention
        q, k, v = _qkv(1, 512, 2, 128, dtype=jnp.float32)
        got = flash_attention(q, k, v, True)
        want = blockwise_attention(q, k, v, True)
        _close(got, want, 2e-4)


class TestFlashBackward:
    @pytest.mark.parametrize("causal", [False, True])
    def test_grads_match_blockwise(self, causal):
        from hpx_tpu.ops.attention import blockwise_attention
        from hpx_tpu.ops.attention_pallas import flash_attention
        q, k, v = _qkv(2, 1024, 4, 64)
        w = _qkv(2, 1024, 4, 64, seed=9)[0].astype(jnp.float32)

        def loss(fn):
            return lambda q, k, v: jnp.sum(
                fn(q, k, v, causal).astype(jnp.float32) * w)

        gf = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2))
                     )(q, k, v)
        gb = jax.jit(jax.grad(loss(blockwise_attention),
                              argnums=(0, 1, 2)))(q, k, v)
        for name, a, b in zip("qkv", gf, gb):
            _close(a, b, 5e-2)


class TestChunkKernel:
    def test_host_simulated_ring(self, float32_products):
        """flash_attention_chunk (scalar-prefetch d) compiled by Mosaic:
        fold all chunks of a 4-way ring on-chip, compare to the
        reference O(S^2) oracle."""
        from hpx_tpu.ops.attention import reference_attention
        from hpx_tpu.ops.attention_pallas import flash_attention_chunk
        B, S, N, H = 1, 512, 2, 64
        q, k, v = _qkv(B, S, N, H, dtype=jnp.float32, seed=3)
        want = reference_attention(q, k, v, True)
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
                    qc, kc, vc, acc, m, l,
                    jnp.int32(i * sq - j * sq), causal=True,
                    block_q=128, block_k=128)
            den = jnp.where(l[:, :, :1] > 0, l[:, :, :1], 1.0)
            o = (acc / den).reshape(B, N, sq, H)
            outs.append(jnp.moveaxis(o, 1, 2))
        got = jnp.concatenate(outs, axis=1).astype(q.dtype)
        _close(got, want, 3e-4)


class TestRingInShardMap:
    def test_vma_checked_shard_map_single_chip(self, float32_products):
        """The exact wiring the training step uses — _ring_flash inside
        a vma-checked shard_map (degenerate 1-device mesh on one chip;
        multi-chip runs the same code over real ICI)."""
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from hpx_tpu.ops.attention import (_ring_flash,
                                           blockwise_attention)
        mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
        q, k, v = _qkv(1, 256, 2, 64, dtype=jnp.float32, seed=5)
        spec = P(None, "sp", None, None)
        out = jax.jit(shard_map(
            lambda qc, kc, vc: _ring_flash(qc, kc, vc, "sp", 1, True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))(q, k, v)
        _close(out, blockwise_attention(q, k, v, True), 3e-4)

    def test_grad_through_shard_map(self, float32_products):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        from hpx_tpu.ops.attention import (_ring_flash,
                                           blockwise_attention)
        mesh = Mesh(np.array(jax.devices()[:1]), ("sp",))
        q, k, v = _qkv(1, 256, 2, 64, dtype=jnp.float32, seed=6)
        spec = P(None, "sp", None, None)

        def loss(q, k, v):
            def body(qc, kc, vc):
                o = _ring_flash(qc, kc, vc, "sp", 1, True)
                return jax.lax.psum(jnp.sum(o), "sp")
            return jax.jit(shard_map(body, mesh=mesh,
                                     in_specs=(spec,) * 3,
                                     out_specs=P()))(q, k, v)

        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(lambda q, k, v: jnp.sum(
            blockwise_attention(q, k, v, True)), argnums=(0, 1, 2)
            )(q, k, v)
        for a, b in zip(got, want):
            _close(a, b, 3e-4)


class TestStencilKernels:
    def test_blocked_step_with_seams(self):
        from hpx_tpu.ops.stencil import heat_step, pallas_heat_step
        n = 1 << 21
        u = jnp.asarray(np.random.default_rng(0).random(n, np.float32))
        _close(pallas_heat_step(u, jnp.float32(0.25)),
               heat_step(u, jnp.float32(0.25)), 1e-6)

    def test_fused_multistep(self):
        from hpx_tpu.ops.stencil import pallas_multistep, xla_multistep
        n = 1 << 16
        u = jnp.asarray(np.random.default_rng(1).random(n, np.float32))
        _close(pallas_multistep(u, jnp.float32(0.25), 32),
               xla_multistep(u, jnp.float32(0.25), 32), 1e-4)


class TestTrainStepOnChip:
    def test_flash_vs_blockwise_trajectories(self):
        """Two full train steps through each attention path must agree —
        the end-to-end guard for the custom_vjp wiring."""
        import hpx_tpu.ops.attention as att
        from hpx_tpu.models import transformer as tfm

        def run(use_flash):
            orig = att.ring_attention_sharded

            def patched(qc, kc, vc, axis, nshards, causal=False, **kw):
                return orig(qc, kc, vc, axis, nshards, causal,
                            use_flash=use_flash, **kw)

            att.ring_attention_sharded = patched
            tfm.ring_attention_sharded = patched
            try:
                cfg = tfm.TransformerConfig(
                    vocab=128, d_model=64, n_heads=2, head_dim=32,
                    n_layers=2, d_ff=128, lr=0.05, dtype=jnp.bfloat16)
                mesh = tfm.make_mesh_3d(1)
                params = tfm.shard_params(
                    tfm.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                    mesh)
                step = tfm.make_train_step(cfg, mesh)
                toks, tgts = tfm.sample_batch(
                    cfg, batch=2, seq=128, key=jax.random.PRNGKey(1))
                toks, tgts = tfm.shard_batch(toks, tgts, mesh)
                losses = []
                for _ in range(3):
                    params, loss = step(params, toks, tgts)
                    losses.append(float(loss))
                return losses
            finally:
                att.ring_attention_sharded = orig
                tfm.ring_attention_sharded = orig

        lf, lb = run(True), run(False)
        np.testing.assert_allclose(lf, lb, rtol=2e-3, atol=2e-3)


class TestGQAOnChip:
    @pytest.mark.parametrize("causal", [False, True])
    def test_forward_and_grads_match_repeat(self, causal):
        """GQA via index-remapped K/V tiles, Mosaic-compiled: must equal
        the dense path on repeated heads, values and grads."""
        from hpx_tpu.ops.attention_pallas import flash_attention
        B, S, H, nq, nkv = 2, 512, 64, 8, 2
        rep = nq // nkv
        q = _qkv(B, S, nq, H, seed=21)[0]
        k, v = _qkv(B, S, nkv, H, seed=22)[:2]
        w = _qkv(B, S, nq, H, seed=23)[0].astype(jnp.float32)

        def loss_gqa(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal).astype(
                jnp.float32) * w)

        def loss_rep(q, k, v):
            return jnp.sum(flash_attention(
                q, jnp.repeat(k, rep, axis=2),
                jnp.repeat(v, rep, axis=2), causal).astype(
                    jnp.float32) * w)

        got = jax.jit(jax.value_and_grad(loss_gqa, argnums=(0, 1, 2))
                      )(q, k, v)
        want = jax.jit(jax.value_and_grad(loss_rep, argnums=(0, 1, 2))
                       )(q, k, v)
        _close(got[0], want[0], 2e-2)
        for a, b in zip(got[1], want[1]):
            _close(a, b, 5e-2)


class TestTransformerShapeOnChip:
    def test_flash_head_dim_64(self):
        """The bench transformer's attention shape (H=64 heads): flash
        kernels must stay numerically tight at the narrow head dim the
        train step actually uses."""
        from hpx_tpu.ops.attention import blockwise_attention
        from hpx_tpu.ops.attention_pallas import flash_attention
        q, k, v = _qkv(4, 1024, 8, 64, seed=3)
        got = flash_attention(q, k, v, causal=True)
        want = blockwise_attention(q, k, v, causal=True)
        _close(got, want, 2e-2)

    def test_flash_head_dim_64_grads(self):
        from hpx_tpu.ops.attention import blockwise_attention
        from hpx_tpu.ops.attention_pallas import flash_attention
        q, k, v = _qkv(2, 512, 4, 64, seed=4)

        def loss(f):
            return lambda a, b, c: jnp.sum(
                f(a, b, c, True).astype(jnp.float32) ** 2)
        g1 = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(blockwise_attention),
                      argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            _close(a, b, 6e-2)


class TestFftOnChip:
    def test_local_fft_matches_numpy(self):
        """XLA's TPU fft lowering (algo/fft's local transforms) against
        numpy — guards the distributed FFT on real hardware."""
        rng = np.random.default_rng(5)
        a = (rng.standard_normal((64, 256)) +
             1j * rng.standard_normal((64, 256))).astype(np.complex64)
        got = jax.jit(lambda x: jnp.fft.fft(x, axis=1))(jnp.asarray(a))
        ref = np.fft.fft(a.astype(np.complex128), axis=1)
        rel = (np.linalg.norm(np.asarray(got) - ref)
               / np.linalg.norm(ref))
        assert rel < 1e-4, rel

    def test_fft_sharded_single_chip(self):
        """fft_sharded on a 1-device mesh (degenerate all_to_all) —
        compiles the whole four-step program through the TPU backend."""
        from jax.sharding import Mesh
        from hpx_tpu.algo import fft as dfft
        mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
        rng = np.random.default_rng(6)
        v = (rng.standard_normal(4096) +
             1j * rng.standard_normal(4096)).astype(np.complex64)
        got = dfft.fft_sharded(jnp.asarray(v), mesh)
        ref = np.fft.fft(v.astype(np.complex128))
        rel = (np.linalg.norm(np.asarray(got) - ref)
               / np.linalg.norm(ref))
        assert rel < 1e-4, rel


class TestServingOnChip:
    def test_quantized_decode_matches_dense(self):
        """int8 weight-only decode on the real chip: XLA must fuse the
        dequant into the matmul and tokens should match dense for a
        small model."""
        import hpx_tpu.models.transformer as tfm
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=128, d_model=128, n_heads=8,
                                    head_dim=16, n_layers=2, d_ff=256,
                                    dtype=jnp.bfloat16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        prompt = jnp.array([[5, 9, 2, 7]], jnp.int32)
        dense = np.asarray(tfm.generate(params, cfg, prompt, max_new=8))
        q = np.asarray(tfm.generate(quant.quantize_params(params), cfg,
                                    prompt, max_new=8))
        assert (dense == q).mean() >= 0.75, (dense, q)

    def test_beam_search_compiles_on_chip(self):
        import hpx_tpu.models.transformer as tfm
        cfg = tfm.TransformerConfig(vocab=64, d_model=64, n_heads=4,
                                    head_dim=16, n_layers=2, d_ff=128,
                                    dtype=jnp.bfloat16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(1))
        out = tfm.beam_search(params, cfg,
                              jnp.array([[1, 2, 3]], jnp.int32),
                              max_new=6, beam_width=4)
        assert out.shape == (1, 6)


class TestTunedBlocks:
    """Whatever block sizes resolve_blocks picks (tuned table, env, or
    default) must Mosaic-compile and agree with the XLA oracle — run
    after benchmarks/flash_tune.py writes a table to catch a tuned
    shape that compiles differently than it benched."""

    def test_resolved_blocks_compile_and_match(self):
        import functools
        import numpy as np
        import jax
        import jax.numpy as jnp
        from hpx_tpu.ops.attention import blockwise_attention
        from hpx_tpu.ops.attention_pallas import (flash_attention,
                                                  resolve_blocks)
        B, S, N, H = 1, 2048, 4, 128
        bq, bk = resolve_blocks(S, S, True)
        rng = np.random.default_rng(0)
        mk = lambda: jnp.asarray(  # noqa: E731
            rng.standard_normal((B, S, N, H), np.float32), jnp.bfloat16)
        q, k, v = mk(), mk(), mk()
        got = jax.jit(functools.partial(flash_attention, causal=True))(
            q, k, v)
        want = blockwise_attention(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2)
        assert bq >= 8 and bk >= 8


class TestStripedAndGQAChunks:
    """Round-5 ring upgrades through Mosaic on the real chip: striped
    offsets (d in {0,-1}) and GQA row-remapped K/V tiles in
    flash_attention_chunk."""

    def test_striped_chunk_fold(self, float32_products):
        from hpx_tpu.ops.attention import (reference_attention,
                                           stripe_sequence,
                                           unstripe_sequence)
        from hpx_tpu.ops.attention_pallas import flash_attention_chunk
        B, S, N, H = 1, 512, 2, 64
        q, k, v = _qkv(B, S, N, H, dtype=jnp.float32, seed=9)
        want = reference_attention(q, k, v, True)
        nsh, sq = 4, S // 4
        qs, ks, vs = (stripe_sequence(x, nsh) for x in (q, k, v))
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
                    jnp.int32(0 if j <= i else -1), causal=True,
                    block_q=128, block_k=128)
            den = jnp.where(l[:, :, :1] > 0, l[:, :, :1], 1.0)
            o = (acc / den).reshape(B, N, sq, H)
            outs.append(jnp.moveaxis(o, 1, 2))
        got = unstripe_sequence(jnp.concatenate(outs, axis=1),
                                nsh).astype(q.dtype)
        _close(got, want, 3e-4)

    def test_gqa_grouped_chunk_fold(self, float32_products):
        """Grouped K/V rows through the chunk kernel's BlockSpec remap
        (the grouped-wire ring path) vs the repeat oracle."""
        from hpx_tpu.ops.attention import reference_attention
        from hpx_tpu.ops.attention_pallas import flash_attention_chunk
        B, S, NQ, NKV, H = 1, 512, 4, 2, 64
        q, _, _ = _qkv(B, S, NQ, H, dtype=jnp.float32, seed=10)
        _, k, v = _qkv(B, S, NKV, H, dtype=jnp.float32, seed=11)
        want = reference_attention(
            q, jnp.repeat(k, NQ // NKV, 2), jnp.repeat(v, NQ // NKV, 2),
            True)
        nsh, sq = 4, S // 4
        outs = []
        for i in range(nsh):
            qc = jnp.moveaxis(q[:, i * sq:(i + 1) * sq], 2, 1
                              ).reshape(B * NQ, sq, H)
            acc = jnp.zeros((B * NQ, sq, H), jnp.float32)
            m = jnp.full((B * NQ, sq, 128), -1e30, jnp.float32)
            l = jnp.zeros((B * NQ, sq, 128), jnp.float32)
            for j in range(nsh):
                kc = jnp.moveaxis(k[:, j * sq:(j + 1) * sq], 2, 1
                                  ).reshape(B * NKV, sq, H)
                vc = jnp.moveaxis(v[:, j * sq:(j + 1) * sq], 2, 1
                                  ).reshape(B * NKV, sq, H)
                acc, m, l = flash_attention_chunk(
                    qc, kc, vc, acc, m, l,
                    jnp.int32(i * sq - j * sq), causal=True,
                    block_q=128, block_k=128, q_heads=NQ,
                    kv_heads=NKV)
            den = jnp.where(l[:, :, :1] > 0, l[:, :, :1], 1.0)
            o = (acc / den).reshape(B, NQ, sq, H)
            outs.append(jnp.moveaxis(o, 1, 2))
        got = jnp.concatenate(outs, axis=1).astype(q.dtype)
        _close(got, want, 3e-4)
