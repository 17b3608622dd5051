"""Schedule algebra of parallel/pipeline_spmd across (P, V, M) shapes,
with synthetic stages — cheap enough to sweep combinations the
transformer parity tests can't afford.

Stage s applies y = x * 2 + s, so a microbatch x that has traversed
stages 0..S-1 in order carries a unique closed-form value:
    f_S(x) = x * 2^S + sum_{s<S} s * 2^(S-1-s)
Any routing error (wrong chunk, wrong order, dropped/duplicated
microbatch) lands on a different value.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from hpx_tpu.ops.attention import _pvary
from hpx_tpu.parallel.pipeline_spmd import (pipeline_run,
                                            pipeline_run_interleaved)


def _expected(xs, n_stages):
    val = np.asarray(xs, np.float64)
    for s in range(n_stages):
        val = val * 2 + s
    return val


def _run(devices, p, v, m):
    mesh = Mesh(np.array(devices[:p]), ("pp",))
    mbs = jnp.arange(1.0, m + 1.0)          # microbatch payloads

    def body(_dummy):
        def collect(buf, y, t_out, valid):
            upd = jax.lax.dynamic_update_index_in_dim(buf, y, t_out, 0)
            return jnp.where(valid, upd, buf)

        def feed(t):
            return mbs[t]

        acc0 = _pvary(jnp.zeros((m,)), ("pp",))
        x0s = _pvary(jnp.zeros(() if v == 1 else (v,)), ("pp",))
        idx = jax.lax.axis_index("pp")
        if v == 1:
            def stage_fn(x):
                return x * 2 + idx
            buf = pipeline_run("pp", p, m, stage_fn, feed, collect,
                               acc0, x0s)
        else:
            def stage_fn(chunk, x):
                return x * 2 + (chunk * p + idx)     # stage id
            buf = pipeline_run_interleaved("pp", p, v, m, stage_fn,
                                           feed, collect, acc0, x0s)
        # results live on the last device only; replicate for P() out
        return jax.lax.psum(buf, "pp")

    dummy = jax.device_put(
        jnp.zeros((p,)), jax.sharding.NamedSharding(mesh, P("pp")))
    out = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("pp"),),
                            out_specs=P()))(dummy)
    return np.asarray(out)


@pytest.mark.parametrize("p,m", [(2, 1), (2, 4), (4, 4), (8, 8), (3, 5)])
def test_plain_schedule(devices, p, m):
    got = _run(devices, p, 1, m)
    want = _expected(np.arange(1.0, m + 1.0), p)
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("p,v,m", [
    (2, 2, 2), (2, 2, 4), (2, 3, 4), (2, 4, 8),
    (4, 2, 4), (4, 2, 8), (4, 3, 4), (8, 2, 8), (3, 2, 3),
])
def test_interleaved_schedule(devices, p, v, m):
    got = _run(devices, p, v, m)
    want = _expected(np.arange(1.0, m + 1.0), p * v)
    np.testing.assert_allclose(got, want)


def test_interleaved_requires_m_divisible(devices):
    with pytest.raises(ValueError, match="divisible"):
        _run(devices, 4, 2, 6)
