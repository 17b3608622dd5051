"""M8 tests: 2-D Jacobi ladder (config #5) + 2-D halo exchange +
BlockExecutor. All variants must agree with a numpy reference sweep."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models.jacobi2d import (JacobiParams, gather_blocks, init_grid,
                                     jacobi_dataflow, jacobi_serial,
                                     jacobi_sharded)
from hpx_tpu.testing import HPX_TEST, HPX_TEST_EQ


def numpy_jacobi(u0: np.ndarray, iterations: int) -> np.ndarray:
    u = u0.copy()
    for _ in range(iterations):
        new = u.copy()
        new[1:-1, 1:-1] = 0.25 * (u[:-2, 1:-1] + u[2:, 1:-1] +
                                  u[1:-1, :-2] + u[1:-1, 2:])
        u = new
    return u


@pytest.fixture(scope="module")
def params():
    return JacobiParams(nx=32, ny=24, nb=4, iterations=20)


@pytest.fixture(scope="module")
def expected(params):
    return numpy_jacobi(np.asarray(init_grid(params)), params.iterations)


def test_serial_matches_numpy(params, expected):
    got = np.asarray(jacobi_serial(params))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


def test_dataflow_matches_numpy(params, expected):
    got = np.asarray(gather_blocks(jacobi_dataflow(params)))
    np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-6)


def test_sharded_matches_numpy(params, expected, mesh2d):
    u, res = jacobi_sharded(params, mesh2d)
    np.testing.assert_allclose(np.asarray(u), expected, rtol=1e-5, atol=1e-6)
    HPX_TEST(float(np.asarray(res).reshape(-1)[0]) >= 0.0)
    # stays sharded over all 8 devices for the whole run
    HPX_TEST_EQ(len(u.sharding.device_set), 8)


def test_sharded_multiple_dispatches(params, expected, mesh2d):
    # 20 iterations in dispatches of 8 => 8+8+4 (remainder program)
    u, _ = jacobi_sharded(params, mesh2d, steps_per_dispatch=8)
    np.testing.assert_allclose(np.asarray(u), expected, rtol=1e-5, atol=1e-6)


def test_dataflow_single_block():
    # regression: nb=1 must keep BOTH Dirichlet rows fixed
    p = JacobiParams(nx=8, ny=8, nb=1, iterations=3)
    got = np.asarray(gather_blocks(jacobi_dataflow(p)))
    want = numpy_jacobi(np.asarray(init_grid(p)), p.iterations)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_residual_decreases(mesh2d):
    p = JacobiParams(nx=32, ny=24, iterations=1)
    from hpx_tpu.parallel.halo2d import sharded_jacobi_step
    from jax.sharding import NamedSharding, PartitionSpec as P
    u = jax.device_put(init_grid(p), NamedSharding(mesh2d, P("x", "y")))
    step = sharded_jacobi_step(mesh2d, p.grid)
    _, r1 = step(u)
    for _ in range(30):
        u, r = step(u)
    # Jacobi converges on Laplace: late residual < first residual
    HPX_TEST(float(np.asarray(r).reshape(-1)[0]) <
             float(np.asarray(r1).reshape(-1)[0]))


def test_edge_shift_zero_fills(mesh1d):
    """Non-periodic shift: boundary shard receives zeros (Dirichlet)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from hpx_tpu.parallel.halo2d import edge_shift

    x = jnp.arange(8, dtype=jnp.float32)

    def body(s):
        return edge_shift(s, "x", +1), edge_shift(s, "x", -1)

    fwd, bwd = jax.jit(shard_map(body, mesh=mesh1d, in_specs=P("x"),
                                 out_specs=(P("x"), P("x"))))(x)
    np.testing.assert_allclose(np.asarray(fwd), [0, 0, 1, 2, 3, 4, 5, 6])
    np.testing.assert_allclose(np.asarray(bwd), [1, 2, 3, 4, 5, 6, 7, 0])


class TestBlockExecutor:
    def test_round_robin_placement(self, devices):
        from hpx_tpu.exec.block import BlockExecutor
        from hpx_tpu.exec.tpu import Target
        ex = BlockExecutor([Target(d) for d in devices])
        HPX_TEST_EQ(ex.num_workers, 8)
        futs = ex.bulk_async_execute(lambda i: jnp.float32(i) * 2.0,
                                     list(range(16)))
        vals = [float(f.get()) for f in futs]
        HPX_TEST_EQ(vals, [2.0 * i for i in range(16)])

    def test_place_blocks(self, devices):
        from hpx_tpu.exec.block import place_blocks
        from hpx_tpu.exec.tpu import Target
        tgts = [Target(d) for d in devices[:4]]
        arrs = place_blocks([jnp.ones(4) * i for i in range(8)], tgts)
        for i, a in enumerate(arrs):
            assert next(iter(a.devices())) == devices[i % 4]

    def test_sync_and_async(self):
        from hpx_tpu.exec.block import BlockExecutor
        ex = BlockExecutor()
        HPX_TEST_EQ(float(ex.sync_execute(lambda: jnp.float32(7.0))), 7.0)
        HPX_TEST_EQ(float(ex.async_execute(
            lambda x: x + 1, jnp.float32(1.0)).get()), 2.0)
