"""1d_stencil workload tests (BASELINE config #2 parity).

Reference analog: examples/1d_stencil — correctness is cross-checked
between the serial, dataflow, fused-XLA, fused-pallas, and sharded-mesh
variants (all must agree bitwise-ish on the same physics), mirroring how
the reference's ladder validates against 1d_stencil_1.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import hpx_tpu as hpx
from hpx_tpu.models.stencil1d import (
    StencilParams, gather_dataflow_result, heat_part, init_domain,
    stencil_dataflow, stencil_fused, stencil_serial,
)
from hpx_tpu.ops.stencil import (
    heat_step, pallas_multistep, takes_kernel, xla_multistep,
)
from hpx_tpu.parallel import (
    make_mesh, shard_1d, sharded_heat_step, sharded_multistep,
)


def numpy_reference(p: StencilParams) -> np.ndarray:
    u = np.arange(p.total, dtype=np.float64)
    for _ in range(p.nt):
        u = u + p.coef * (np.roll(u, 1) - 2 * u + np.roll(u, -1))
    return u


def test_serial_matches_numpy():
    p = StencilParams(nx=64, np_=4, nt=20, k=0.25)
    got = np.asarray(stencil_serial(p), dtype=np.float64)
    np.testing.assert_allclose(got, numpy_reference(p), rtol=1e-4)


def test_dataflow_matches_serial():
    p = StencilParams(nx=32, np_=8, nt=15, k=0.25)
    u = stencil_dataflow(p)
    got = np.asarray(gather_dataflow_result(u))
    want = np.asarray(stencil_serial(p))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_fused_xla_matches_serial():
    p = StencilParams(nx=128, np_=4, nt=40, k=0.25)
    got = np.asarray(stencil_fused(p, steps_per_dispatch=10,
                                   use_pallas=False))
    want = np.asarray(stencil_serial(p))
    np.testing.assert_allclose(got, want, rtol=1e-5)


def test_pallas_multistep_matches_xla():
    # pallas path needs length % 128 == 0; runs in interpreter-compatible
    # mode on CPU backend
    n, steps, coef = 512, 8, jnp.float32(0.25)
    u = jnp.arange(n, dtype=jnp.float32)
    try:
        got = pallas_multistep(u, coef, steps)
    except Exception as e:  # pallas-on-CPU unavailable in this jax build
        pytest.skip(f"pallas unavailable on CPU backend: {e}")
    want = xla_multistep(u, coef, steps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_sharded_heat_step_matches_serial(mesh1d):
    n = 8 * 32
    u = jnp.arange(n, dtype=jnp.float32)
    us = shard_1d(u, mesh1d)
    step = sharded_heat_step(mesh1d, "x")
    coef = jnp.float32(0.25)
    got = us
    for _ in range(5):
        got = step(got, coef)
    want = u
    for _ in range(5):
        want = heat_step(want, coef)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_sharded_multistep_single_program(mesh1d):
    n = 8 * 64
    u = jnp.arange(n, dtype=jnp.float32)
    us = shard_1d(u, mesh1d)
    coef = jnp.float32(0.3)
    fn = sharded_multistep(mesh1d, "x", steps=12, halo_steps=3)
    got = fn(us, coef)
    want = u
    for _ in range(12):
        want = heat_step(want, coef)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4)
    # sharding preserved (no implicit gather)
    assert len(got.sharding.device_set) == 8


def test_sharded_wide_halo_equivalence(mesh1d):
    # halo_steps=4 (communication-avoiding) must equal halo_steps=1
    n = 8 * 64
    u = jnp.arange(n, dtype=jnp.float32)
    us = shard_1d(u, mesh1d)
    coef = jnp.float32(0.25)
    a = sharded_multistep(mesh1d, "x", steps=8, halo_steps=1)(us, coef)
    b = sharded_multistep(mesh1d, "x", steps=8, halo_steps=4)(us, coef)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


def test_conservation():
    # periodic heat equation conserves the sum
    p = StencilParams(nx=64, np_=4, nt=50, k=0.4)
    u = stencil_fused(p, use_pallas=False)
    np.testing.assert_allclose(float(jnp.sum(u)),
                               float(jnp.sum(init_domain(p))), rtol=1e-3)


@pytest.fixture
def small_slabs(monkeypatch):
    """Slabs of 8 rows, so a few thousand points take several grid
    steps and every kind of seam (inside, both partition ends) is hit."""
    from hpx_tpu.ops import stencil as st
    monkeypatch.setattr(st, "_BLOCK_ROWS", 8)
    jitted = (st.heat_step_halo, st.pallas_heat_step)
    for f in jitted:        # a trace made at another slab height is stale
        f.clear_cache()
    yield st
    for f in jitted:
        f.clear_cache()


@pytest.mark.parametrize("slabs", [1, 2, 5])
@pytest.mark.parametrize("seed", [7, 2**31 + 5])
def test_halo_kernel_is_heat_part_bit_for_bit(small_slabs, slabs, seed):
    """The blocked kernel with the two halo points as scalars against
    the XLA `heat_part` (what the CPU backend takes): the same float32
    arithmetic in the same order, so equal bitwise, at every slab seam
    and at both partition ends. The halos are NOT the ring's own ends,
    so a kernel that wrapped the partition onto itself would differ."""
    st = small_slabs
    n, coef = 8 * 128 * slabs, jnp.float32(0.3)
    assert st._slab_rows(n) == 8
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.random(n, np.float32))
    left = jnp.asarray(rng.random(1, np.float32) + 2.0)
    right = jnp.asarray(rng.random(1, np.float32) - 3.0)
    got = np.asarray(st.heat_step_halo(left, u, right, coef, interpret=True))
    want = np.asarray(jax.jit(heat_part)(left, u, right, coef))
    np.testing.assert_array_equal(got, want)
    ring = np.asarray(st.pallas_heat_step(u, coef, interpret=True))
    assert got[0] != ring[0] and got[-1] != ring[-1]
    np.testing.assert_array_equal(got[1:-1], ring[1:-1])


def test_pallas_heat_step_is_the_halo_form_fed_from_the_ring(small_slabs):
    """`pallas_heat_step` is `heat_step_halo` with the ring's own ends
    as halos: every slab-boundary element gets its true global-periodic
    neighbours, and the result is `heat_step`'s."""
    st = small_slabs
    n, coef = 8 * 128 * 4, jnp.float32(0.3)
    u = jnp.asarray(np.random.default_rng(7).random(n, np.float32))
    got = st.pallas_heat_step(u, coef, interpret=True)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jax.jit(heat_step)(u, coef)))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("nx,tiles", [
    (64, False),                # under one (8, 128) tile
    (1024, True),               # one tile, one slab
    ((1 << 20) + 128, False),   # whole rows, not whole tiles
    (1 << 27, True),            # the benchmark's partition
])
def test_takes_kernel(nx, tiles, backend):
    """Which path a partition takes is a pure function of what the code
    can observe: the platform, the dtype, the length."""
    assert takes_kernel(nx, jnp.float32, backend) == \
        (tiles and backend == "tpu")
    assert not takes_kernel(nx, jnp.bfloat16, backend)
