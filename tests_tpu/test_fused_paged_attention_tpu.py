"""Real-chip leg of the fused paged-attention contract (ROADMAP item
3): the Pallas block-table kernels compiled by Mosaic must match the
XLA gather-oracle formulation ON THE SAME TPU — decode and verify
windows; bf16, int8 and fp8 (e4m3) pools; the bitwise `fused` kernel
AND the O(block)-scratch `fused_online` online-softmax kernel. tests/
covers interpret mode on CPU; this is the only place the actual
Mosaic lowering (incl. the double-buffered online carry) is checked,
so a regression fails a test instead of silently showing up as a
serving numerics drift. Skipped only under JAX_PLATFORMS=cpu (see
conftest). Head shapes: the small 4q/2kv x 64 one and the smoke's
16q/4kv x 128."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


HEADS = pytest.mark.parametrize(
    "nkv,nq,hd", [(2, 4, 64), (4, 16, 128)], ids=["4q2kv_h64",
                                                  "16q4kv_h128"])


def _pools(nb, bs, nkv, hd, dtype=jnp.bfloat16, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(
        rng.standard_normal((nb, nkv, bs, hd), np.float32), dtype)
        for _ in range(2))


def _table(b, maxb, nb, seed=1):
    rng = np.random.default_rng(seed)
    ids = rng.permutation(nb)[:b * maxb].reshape(b, maxb)
    return jnp.asarray(ids, jnp.int32)


def _close(a, b, tol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=tol, atol=tol)


class TestFusedPagedDecode:
    @HEADS
    def test_matches_gather_bf16(self, nkv, nq, hd):
        from hpx_tpu.ops.paged_attention import paged_decode_attention
        B, nb, bs, maxb = 2, 16, 16, 4
        kp, vp = _pools(nb, bs, nkv, hd)
        table = _table(B, maxb, nb)
        pos = jnp.asarray([37, 22], jnp.int32)
        rng = np.random.default_rng(2)
        q = jnp.asarray(rng.standard_normal((B, 1, nq, hd), np.float32),
                        jnp.bfloat16)
        kn, vn = (jnp.asarray(
            rng.standard_normal((B, nkv, hd), np.float32), jnp.bfloat16)
            for _ in range(2))

        def run(fused):
            att, *_ = jax.jit(
                lambda q, kn, vn, kp, vp: paged_decode_attention(
                    q, kn, vn, kp, vp, table, pos, fused=fused)
            )(q, kn, vn, kp, vp)
            return att
        _close(run(True), run(False), 3e-2)
        # the online kernel's tolerance budget is O(eps * num_blocks)
        # past the bitwise kernel's — identical bf16 tolerance here
        _close(run("online"), run(False), 3e-2)

    @HEADS
    def test_matches_gather_int8(self, nkv, nq, hd):
        """int8 pools + absmax scale sidecars: both paths dequantize
        the SAME stored bytes, so they agree to bf16 tolerance."""
        from hpx_tpu.ops.paged_attention import (paged_decode_attention,
                                                 quantize_blocks)
        B, nb, bs, maxb = 2, 16, 32, 2
        kf, vf = _pools(nb, bs, nkv, hd, seed=3)
        kp, ks = quantize_blocks(kf)
        vp, vs = quantize_blocks(vf)
        table = _table(B, maxb, nb, seed=4)
        pos = jnp.asarray([51, 9], jnp.int32)
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.standard_normal((B, 1, nq, hd), np.float32),
                        jnp.bfloat16)
        kn, vn = (jnp.asarray(
            rng.standard_normal((B, nkv, hd), np.float32), jnp.bfloat16)
            for _ in range(2))

        def run(fused):
            att, *_ = jax.jit(
                lambda q, kn, vn, kp, vp, ks, vs: paged_decode_attention(
                    q, kn, vn, kp, vp, table, pos, k_scale=ks,
                    v_scale=vs, fused=fused)
            )(q, kn, vn, kp, vp, ks, vs)
            return att
        _close(run(True), run(False), 3e-2)
        _close(run("online"), run(False), 3e-2)

    @HEADS
    def test_matches_gather_fp8(self, nkv, nq, hd):
        """fp8 (e4m3) pools + the same f32 scale sidecars: the Mosaic
        lowering of the in-kernel float8 dequant must agree with the
        gather formulation over the same stored bytes — both fused
        kernels."""
        from hpx_tpu.ops.paged_attention import (paged_decode_attention,
                                                 quantize_blocks)
        B, nb, bs, maxb = 2, 16, 16, 4     # the server's default block
        kf, vf = _pools(nb, bs, nkv, hd, seed=9)
        kp, ks = quantize_blocks(kf, jnp.float8_e4m3fn)
        vp, vs = quantize_blocks(vf, jnp.float8_e4m3fn)
        table = _table(B, maxb, nb, seed=10)
        pos = jnp.asarray([44, 17], jnp.int32)
        rng = np.random.default_rng(11)
        q = jnp.asarray(rng.standard_normal((B, 1, nq, hd), np.float32),
                        jnp.bfloat16)
        kn, vn = (jnp.asarray(
            rng.standard_normal((B, nkv, hd), np.float32), jnp.bfloat16)
            for _ in range(2))

        def run(fused):
            att, *_ = jax.jit(
                lambda q, kn, vn, kp, vp, ks, vs: paged_decode_attention(
                    q, kn, vn, kp, vp, table, pos, k_scale=ks,
                    v_scale=vs, fused=fused)
            )(q, kn, vn, kp, vp, ks, vs)
            return att
        _close(run(True), run(False), 3e-2)
        _close(run("online"), run(False), 3e-2)


class TestFusedPagedWindow:
    @HEADS
    def test_matches_gather_bf16(self, nkv, nq, hd):
        """The verify-window horizon (row i attends <= pos0+i) must
        agree between the kernel's per-row mask and the gather mask."""
        from hpx_tpu.ops.paged_attention import paged_window_attention
        B, W, nb, bs, maxb = 2, 4, 16, 16, 4
        kp, vp = _pools(nb, bs, nkv, hd, seed=6)
        table = _table(B, maxb, nb, seed=7)
        pos0 = jnp.asarray([29, 12], jnp.int32)
        rng = np.random.default_rng(8)
        q = jnp.asarray(rng.standard_normal((B, W, nq, hd), np.float32),
                        jnp.bfloat16)
        kn, vn = (jnp.asarray(
            rng.standard_normal((B, W, nkv, hd), np.float32),
            jnp.bfloat16) for _ in range(2))

        def run(fused):
            att, *_ = jax.jit(
                lambda q, kn, vn, kp, vp: paged_window_attention(
                    q, kn, vn, kp, vp, table, pos0, fused=fused)
            )(q, kn, vn, kp, vp)
            return att
        _close(run(True), run(False), 3e-2)
        # per-window-row horizon under the online (acc, m, l) carry
        _close(run("online"), run(False), 3e-2)
