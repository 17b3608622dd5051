"""Real-chip leg of the fused paged-attention contract (ROADMAP item
3): the Pallas block-table kernels compiled by Mosaic must match the
XLA gather-oracle formulation ON THE SAME TPU — decode and verify
windows; bf16, int8 and fp8 (e4m3) pools; the bitwise `fused` kernel
AND the O(block)-scratch `fused_online` online-softmax kernel. tests/
covers interpret mode on CPU; this is the only place the actual
Mosaic lowering (incl. the double-buffered online carry) is checked,
so a regression fails a test instead of silently showing up as a
serving numerics drift. Skipped only under JAX_PLATFORMS=cpu (see
conftest). Head shapes: the small 4q/2kv x 64 one (the grid walk: a
head narrower than 128 lanes) and the smoke's 16q/4kv x 128, which
over bf16 pools takes the walk bounded by each slot's live length
(`_paged_live_kernel`, PR 31). Of that walk only the chip can show two
things: that hundreds of copies in flight on one semaphore have ALL
landed before the banks are read, and that the rows of the VMEM banks
the PREVIOUS grid step left behind a short walk stay out of the
result. Since PR 35 a grid step owns a GROUP of kv heads and copies a
table entry once for all of them (`TestHeadGroups`: every group size
bit for bit one head's result, at both serving cells' shapes). Since
PR 50 a grid step starts the NEXT step's copies into a second set of
banks before it waits for its own (`TestTwoSets`: only the chip runs
the copies beside the finish, so only here can a copy that lands in
the set being read, or a wait on the other set's semaphore, show)."""

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


def _gather_oracle(q, kp, vp, table, pos, nkv):
    """Attention over the gathered rows of the same pools (nothing
    written this step): q [B, W, n_q, hd], window row w sees rows <=
    pos + w."""
    from hpx_tpu.ops.paged_attention import gather_block_kv
    b, w, nq, hd = q.shape
    kc = gather_block_kv(kp, table, None, q.dtype)
    vc = gather_block_kv(vp, table, None, q.dtype)
    qg = q.reshape(b, w, nkv, nq // nkv, hd)
    s = (jnp.einsum("bqngh,bknh->bngqk", qg, kc)
         / np.sqrt(hd)).astype(jnp.float32)
    live = (jnp.arange(kc.shape[1])[None, None, :]
            <= pos[:, None, None] + jnp.arange(w)[None, :, None])
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bngqk,bknh->bqngh", p, vc).reshape(q.shape)


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


class TestBoundedWalk:
    """What only the chip can show of the walk bounded by `pos0`."""

    @pytest.mark.parametrize("nkv,nq,maxb", [(2, 24, 128), (8, 48, 304)],
                             ids=["sc2-3b", "laguna-full"])
    def test_ragged_long_tables_match_gather(self, nkv, nq, maxb):
        """The cells' table widths and head counts, ragged slots from
        one block to the whole table: up to 2 x 304 copies in flight at
        once on two semaphores, every one landed before the finish."""
        from hpx_tpu.ops.paged_attention import paged_decode_attention
        B, bs, hd = 8, 16, 128
        nb = B * maxb + 1
        kp, vp = _pools(nb, bs, nkv, hd, seed=20)
        table = _table(B, maxb, nb, seed=21)
        top = maxb * bs - 1
        pos = jnp.asarray([0, 15, 16, 527, top // 3, top // 2, top - 16,
                           top], jnp.int32)
        rng = np.random.default_rng(22)
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
            return np.asarray(att, np.float32)
        want, got = run(False), run(True)
        _close(got, want, 3e-2)
        for _ in range(3):      # a race would not show every time
            assert (run(True) == got).all()

    @pytest.mark.parametrize("w", [1, 4])
    @pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
    def test_rows_the_last_grid_step_left_stay_out(self, window, w):
        """Slot 0 walks its whole table over NaN blocks; the ragged
        slots after it walk a few entries of sound ones: their rows of
        the banks past the walk still hold slot 0's NaN, and their
        outputs equal, bit for bit, the run in which slot 0's blocks
        hold zeros."""
        from hpx_tpu.ops.attention_pallas import fused_paged_attention
        B, bs, maxb, nkv, nq, hd = 4, 16, 16, 2, 8, 128
        nb = B * maxb + 1
        kf, vf = _pools(nb, bs, nkv, hd, seed=30)
        table = np.arange(1, nb, dtype=np.int32).reshape(B, maxb)
        pos = np.asarray([maxb * bs - w, 3, 2 * bs, 5 * bs - 1], np.int32)
        live = np.arange(maxb)[None, :] <= ((pos + w - 1) // bs)[:, None]
        table = jnp.asarray(np.where(live, table, 0))    # tail: trash
        rng = np.random.default_rng(31)
        q = jnp.asarray(rng.standard_normal((B, w, nq, hd), np.float32),
                        jnp.bfloat16)
        first = np.arange(1, maxb + 1)                   # slot 0's blocks

        def run(fill):
            kp, vp = kf.at[first].set(fill), vf.at[first].set(fill)
            return np.asarray(jax.jit(
                lambda q, kp, vp: fused_paged_attention(
                    q, kp, vp, table, jnp.asarray(pos), window=window)
            )(q, kp, vp), np.float32)
        bad, good = run(np.nan), run(0.0)
        assert np.isnan(bad[0]).all()        # the poison was in the bank
        assert np.isfinite(bad[1:]).all()
        assert (bad[1:] == good[1:]).all()


class TestHeadGroups:
    """What only the chip can show of a grid step that owns a GROUP of
    kv heads (PR 35): one descriptor moves `hg` heads' tiles into `hg`
    banks (a strided destination), and the heads' finishes run one
    after another over banks that all landed on the same semaphores."""

    @staticmethod
    def _case(nkv, nq, maxb, w, dtype, seed):
        B, bs, hd = 8, 16, 128
        nb = B * maxb + 1
        kp, vp = _pools(nb, bs, nkv, hd, dtype, seed=seed)
        table = _table(B, maxb, nb, seed=seed + 1)
        top = maxb * bs - w
        pos = jnp.asarray([0, 15, 16, min(527, top), top // 3, top // 2,
                           max(top - 16, 0), top], jnp.int32)
        rng = np.random.default_rng(seed + 2)
        q = jnp.asarray(rng.standard_normal((B, w, nq, hd), np.float32),
                        dtype)
        return q, kp, vp, table, pos

    @pytest.mark.parametrize("nkv,nq,maxb,window,w,dtype,hg", [
        (2, 24, 128, 0, 1, jnp.bfloat16, 2),       # sc2-3b.gen-closed
        (8, 48, 304, 0, 1, jnp.bfloat16, 8),       # Laguna's full layers
        (8, 64, 34, 512, 1, jnp.bfloat16, 8),      # Laguna's ring
        (8, 48, 304, 0, 4, jnp.bfloat16, 8),       # a verify window
        (8, 16, 304, 0, 1, jnp.float32, 4),        # 8 KB a head and entry
        (6, 12, 64, 0, 1, jnp.bfloat16, 6),        # no power of two
    ], ids=["sc2-3b", "laguna-full", "laguna-window", "laguna-full-w4",
            "f32", "nkv6"])
    def test_every_group_gives_one_heads_bits(self, monkeypatch, nkv, nq,
                                              maxb, window, w, dtype, hg):
        """At the cells' shapes the group the rule picks, and every
        smaller divisor, give bit for bit what one head a grid step
        gives (PR 31's kernel), which stays as close to the gather
        oracle as it was."""
        from hpx_tpu.ops import attention_pallas as ap
        q, kp, vp, table, pos = self._case(nkv, nq, maxb, w, dtype, 40)
        item = jnp.dtype(dtype).itemsize
        assert ap.walk_heads_per_copy(nkv, maxb * 16, 128, w * nq // nkv,
                                      item, item) == hg

        def run(force):
            if force:
                monkeypatch.setattr(ap, "walk_heads_per_copy",
                                    lambda *a: force)
            else:
                monkeypatch.undo()
            return np.asarray(jax.jit(
                lambda q, kp, vp: ap.fused_paged_attention(
                    q, kp, vp, table, pos, window=window)
            )(q, kp, vp), np.float32)
        one = run(1)
        assert np.isfinite(one).all()
        for force in [d for d in range(2, nkv + 1) if nkv % d == 0] + [0]:
            for _ in range(2):      # a race would not show every time
                assert (run(force) == one).all(), force
        if not window:
            # float32 too: at the default precision the chip multiplies
            # the oracle's einsums in bfloat16 passes
            _close(one, _gather_oracle(q, kp, vp, table, pos, nkv), 3e-2)

    @pytest.mark.parametrize("head", [0, 5, 7])
    @pytest.mark.parametrize("window", [0, 40], ids=["full", "ring"])
    def test_a_dead_row_of_any_head_of_the_group_stays_out(self, window,
                                                           head):
        """Eight heads share a grid step. NaN in ONE head's rows of the
        blocks slot 0 walks (its whole table) and of the trash block:
        that head's bank holds NaN behind the short walks of the slots
        after it. Only slot 0's queries of that head read NaN; all else
        equals, bit for bit, the run with zeros in its place."""
        from hpx_tpu.ops.attention_pallas import fused_paged_attention
        B, bs, maxb, nkv, nq, hd = 4, 16, 16, 8, 16, 128
        nb = B * maxb + 1
        kf, vf = _pools(nb, bs, nkv, hd, seed=50)
        table = np.arange(1, nb, dtype=np.int32).reshape(B, maxb)
        pos = np.asarray([maxb * bs - 1, 3, 2 * bs, 5 * bs - 1], np.int32)
        live = np.arange(maxb)[None, :] <= (pos // bs)[:, None]
        table = jnp.asarray(np.where(live, table, 0))    # tail: trash
        rng = np.random.default_rng(51)
        q = jnp.asarray(rng.standard_normal((B, 1, nq, hd), np.float32),
                        jnp.bfloat16)
        bad_blocks = np.arange(0, maxb + 1)      # trash + slot 0's

        def run(fill):
            kp = kf.at[bad_blocks, head].set(fill)
            vp = vf.at[bad_blocks, head].set(fill)
            return np.asarray(jax.jit(
                lambda q, kp, vp: fused_paged_attention(
                    q, kp, vp, table, jnp.asarray(pos), window=window)
            )(q, kp, vp), np.float32)
        bad, good = run(np.nan), run(0.0)
        g = nq // nkv
        mine = np.zeros(bad.shape, bool)
        mine[0, :, head * g:(head + 1) * g] = True
        assert np.isnan(bad[mine]).all()     # the poison was in the bank
        assert np.isfinite(bad[~mine]).all()
        assert (bad[~mine] == good[~mine]).all()


class TestTwoSets:
    """What only the chip can show of the two sets of banks (PR 50):
    the next grid step's copies land WHILE this step's finish reads its
    own set, each set on its own pair of semaphores."""

    @pytest.mark.parametrize("nkv,nq,maxb,bs,window,hg", [
        (32, 32, 50, 64, 0, 16),       # evabyte.doc-closed: 2 steps a slot
        (8, 48, 304, 16, 0, 8),        # Laguna's full layers: 1 a slot
        (8, 64, 34, 16, 512, 8),       # Laguna's ring
        (2, 24, 128, 16, 0, 2),        # sc2-3b.gen-closed
    ], ids=["evabyte", "laguna-full", "laguna-window", "sc2-3b"])
    def test_neighbours_do_not_move_a_slots_rows(self, nkv, nq, maxb, bs,
                                                 window, hg):
        """Slots whose walks differ as far as they can (position 0, a
        full table, one entry, ragged ones between) in three orders:
        a slot's rows are bit for bit the same whatever ran before and
        after it, run after run, and stay as close to the gather oracle
        as they were."""
        from hpx_tpu.ops import attention_pallas as ap
        B, hd = 6, 128
        nb = B * maxb + 1
        kp, vp = _pools(nb, bs, nkv, hd, seed=60)
        table = _table(B, maxb, nb, seed=61)
        top = maxb * bs - 1
        pos = jnp.asarray([0, top, bs - 1, top // 2, bs, top - bs],
                          jnp.int32)
        rng = np.random.default_rng(62)
        q = jnp.asarray(rng.standard_normal((B, 1, nq, hd), np.float32),
                        jnp.bfloat16)
        assert ap.walk_heads_per_copy(nkv, maxb * bs, hd, nq // nkv,
                                      2, 2) == hg
        f = jax.jit(lambda q, kp, vp, table, pos: ap.fused_paged_attention(
            q, kp, vp, table, pos, window=window))
        one = np.asarray(f(q, kp, vp, table, pos), np.float32)
        assert np.isfinite(one).all()
        for order in ([5, 4, 3, 2, 1, 0], [1, 0, 2, 5, 3, 4],
                      [0, 1, 2, 3, 4, 5]):
            o = np.asarray(order)
            for _ in range(2):      # a race would not show every time
                got = np.asarray(f(q[o], kp, vp, table[o], pos[o]),
                                 np.float32)
                assert (got == one[o]).all(), order
        if not window:
            _close(one, _gather_oracle(q, kp, vp, table, pos, nkv), 3e-2)
