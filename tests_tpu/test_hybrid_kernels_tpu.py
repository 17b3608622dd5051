"""The two kernels of the recurrent / latent mixers on the chip, each
against its XLA oracle at the shapes the cell `kimi-linear.reason-closed`
serves: `hpx_kda_step` (48 slots x 32 heads of 128 x 128 float32 state)
against `ops/kda._step_xla`, `hpx_mla_paged` (32 query heads of 640 over
a pool of 12,673 blocks of 16 latent rows, table 264 wide) against the
gather form of `ops/paged_attention.paged_latent_attention`; and the
chunkwise form of the recurrence against the token scan, on the chip's
own float32 products."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.ops import kda
from hpx_tpu.ops import paged_attention as pa

pytestmark = pytest.mark.tpu


def _kda_inputs(key, b, t, h, d):
    ks = jax.random.split(key, 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (b, t, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (b, t, h, d)))
    v = jax.random.normal(ks[2], (b, t, h, d))
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, d), minval=-7.0,
                                    maxval=0.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d)) * 0.1
    return q, k, v, g, beta, s0


def test_kda_step_kernel_equals_its_oracle_at_the_cells_shape():
    q, k, v, g, beta, s0 = _kda_inputs(jax.random.PRNGKey(0), 48, 1, 32,
                                       128)
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    o_x, s_x = jax.jit(lambda *a: kda.kda_step(*a, kernel="xla"))(
        *args, s0)
    o_p, s_p = jax.jit(lambda *a: kda.kda_step(*a, kernel="pallas"),
                       donate_argnums=(5,))(*args, s0 + 0.0)
    # the same float32 multiplies and adds; the sums over 128 rows are
    # taken in another order
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                               rtol=1e-4, atol=1e-6)


def test_kda_chunk_equals_the_scan_on_the_chip():
    q, k, v, g, beta, s0 = _kda_inputs(jax.random.PRNGKey(1), 1, 128, 32,
                                       128)
    o_s, s_s = jax.jit(kda.kda_scan)(q, k, v, g, beta, s0)
    o_c, s_c = jax.jit(kda.kda_chunk)(q, k, v, g, beta, s0)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_s),
                               rtol=2e-3, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_s),
                               rtol=2e-3, atol=2e-5)


def test_mla_paged_kernel_equals_the_gather_form_at_the_cells_shape():
    b, h, r, rank, bs, maxb = 48, 32, 640, 512, 16, 264
    nb = b * maxb + 1
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    pool = jax.random.normal(ks[0], (nb, 1, bs, r), jnp.bfloat16)
    q = (jax.random.normal(ks[1], (b, h, r)) * 0.3).astype(jnp.bfloat16)
    row = jax.random.normal(ks[2], (b, r), jnp.bfloat16)
    table = (1 + jnp.arange(b * maxb, dtype=jnp.int32)).reshape(b, maxb)
    pos = jax.random.randint(ks[3], (b,), 0, maxb * bs).at[0].set(0) \
        .at[1].set(maxb * bs - 1)
    call = lambda fused: jax.jit(                          # noqa: E731
        lambda *a: pa.paged_latent_attention(
            *a, rank=rank, scale=192 ** -0.5, fused=fused)[0])(
        q, row, pool, table, pos)
    got, want = np.asarray(call(True), np.float32), \
        np.asarray(call(False), np.float32)
    # bfloat16 outputs of float32 sums taken in another order
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert np.abs(got - want).mean() < 2e-3


class TestRunCopies:
    """What only the chip can show of the latent walk's coalesced
    copies (PR 51): a fold of 32 neighbours lands as ONE 655 KB
    descriptor on the buffer's own semaphore, and the waits count a
    group's bytes whichever way its copies were started."""

    @pytest.mark.parametrize("kind", ["run", "permuted", "broken@1",
                                      "broken@2", "broken@3", "mixed"])
    @pytest.mark.parametrize("b,h,maxb,nb", [
        (64, 128, 1576, 20000), (48, 32, 264, 12673)],
        ids=["deepseek-v2", "kimi"])
    def test_grouped_copies_give_the_single_copy_forms_bits(
            self, b, h, maxb, nb, kind, monkeypatch):
        """At the two cells' shapes, over tables that are one run a
        slot, a permutation with no two neighbours, runs broken at every
        offset of a group of four, and a mix of all of them fold by
        fold: the kernel in groups of `LATENT_RUN` is the kernel a copy
        an entry TO THE BIT, run after run, at positions from 0 to the
        whole table."""
        from hpx_tpu.ops import attention_pallas as ap
        r, rank, bs = 640, 512, 16
        fold, run = ap.latent_walk_sizes(maxb)
        assert run == ap.LATENT_RUN > 1
        rng = np.random.default_rng(70)
        ks = jax.random.split(jax.random.PRNGKey(71), 2)
        pool = jax.random.normal(ks[0], (nb, 1, bs, r), jnp.bfloat16)
        q = (jax.random.normal(ks[1], (b, h, r)) * 0.3).astype(jnp.bfloat16)
        # a slot's region of the pool: `maxb` ids from its base on (the
        # regions of DeepSeek-V2's 64 slots overlap: 20,000 blocks)
        base = rng.integers(1, nb - maxb, b)
        ids = base[:, None] + np.arange(maxb)[None]

        def permuted(x):                # no entry's successor its id's
            half = maxb // 2
            return np.stack([x[:, half:2 * half], x[:, :half]], -1).reshape(
                b, -1)[:, ::-1]

        def broken(x, k):               # every group of 4 jumps at k
            g = x.reshape(b, -1, 4).copy()
            g[:, :, k:] = g[:, ::-1, k:]
            return g.reshape(b, -1)
        forms = {"run": ids, "permuted": permuted(ids),
                 **{f"broken@{k}": broken(ids, k) for k in (1, 2, 3)}}
        if kind == "mixed":             # a slot's folds each their own
            table = ids.copy()
            whole = maxb // run * run
            pick = rng.integers(0, len(forms), (b, whole // run))
            for i, form in enumerate(forms.values()):
                here = np.repeat(pick == i, run, axis=1)
                table[:, :whole][here] = form[:, :whole][here]
        else:
            table = forms[kind]
        top = maxb * bs - 1
        pos = rng.integers(0, top + 1, b)
        pos[:6] = [0, top, bs - 1, run * bs - 1, run * bs, top - bs]
        n_live = pos // bs + 1
        share = ap.latent_entries_coalesced(table, n_live, fold,
                                            run).sum() / n_live.sum()
        assert {"run": share > 0.6, "mixed": 0.05 < share < 0.5}.get(
            kind, share == 0.0), share
        table, pos = jnp.asarray(table, jnp.int32), jnp.asarray(pos,
                                                                jnp.int32)

        def form():                     # traced under today's constant
            return jax.jit(lambda q, pool, table, pos:
                           ap.fused_latent_attention(
                               q, pool, table, pos, rank=rank,
                               scale=192 ** -0.5))
        grouped = form()
        got = np.asarray(grouped(q, pool, table, pos), np.float32)
        monkeypatch.setattr(ap, "LATENT_RUN", 1)
        one = np.asarray(form()(q, pool, table, pos), np.float32)
        assert np.isfinite(one).all()
        assert (got == one).all()
        for _ in range(3):              # a race would not show every time
            assert (np.asarray(grouped(q, pool, table, pos),
                               np.float32) == one).all()
