"""The two kernels of the selective scan on the chip, each against its
XLA oracle at the shapes the cell `jamba2-3b.chat-closed` serves:
`hpx_mamba_step` (256 slots x [16, 5120] float32 state, eight slots a
grid step) against `ops/mamba._step_xla`, and `hpx_mamba_scan` (a chunk
of 512 rows, 300 of them real, over 5,120 channels) against the token
scan `ops/mamba.mamba_scan`. The same float32 multiplies and adds in
the same order but for the sum over the 16 state rows and the chip's
own exp."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.ops import mamba

pytestmark = pytest.mark.tpu

C, N = 5120, 16


def _inputs(key, b, t):
    ks = jax.random.split(key, 5)
    u = jax.random.normal(ks[0], (b, t, C))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, C)) - 3.0)
    bm = jax.random.normal(ks[2], (b, t, N))
    cm = jax.random.normal(ks[3], (b, t, N))
    a = -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(
        1, N + 1, dtype=jnp.float32))[:, None], (N, C)))
    return u, dt, bm, cm, a, jax.random.normal(ks[4], (b, N, C))


def test_mamba_step_kernel_equals_its_oracle_at_the_cells_shape():
    u, dt, bm, cm, a, s0 = _inputs(jax.random.PRNGKey(0), 256, 1)
    args = (u[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], a)
    y_x, s_x = jax.jit(lambda *v: mamba.mamba_step(*v, kernel="xla"))(
        *args, s0)
    y_p, s_p = jax.jit(lambda *v: mamba.mamba_step(*v, kernel="pallas"),
                       donate_argnums=(5,))(*args, s0 + 0.0)
    np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y_p), np.asarray(y_x),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("rows,valid", [(512, 300), (8, 8), (256, 0)])
def test_mamba_scan_kernel_equals_the_token_scan_on_the_chip(rows, valid):
    u, dt, bm, cm, a, s0 = _inputs(jax.random.PRNGKey(rows), 1, rows)
    v = jnp.int32(valid)
    y_s, s_s = jax.jit(mamba.mamba_scan)(u, dt, bm, cm, a, s0, v)
    y_k, s_k = jax.jit(lambda *x: mamba.mamba_chunk(*x, kernel="pallas"),
                       donate_argnums=(5,))(u, dt, bm, cm, a, s0 + 0.0, v)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_s),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(y_k)[:, :valid],
                               np.asarray(y_s)[:, :valid],
                               rtol=1e-4, atol=1e-4)
