"""Stencil kernels: 3-point heat update, single- and multi-step.

Reference analog: the `heat_part` inner loop of examples/1d_stencil/
1d_stencil_4.cpp (u'[i] = u[i] + k*dt/dx^2 * (u[i-1] - 2u[i] + u[i+1]),
periodic neighbors) — the Mcells/s hot loop of BASELINE config #2.

A single heat step is HBM-bandwidth-bound (read u, write u'):
  * heat_step: the XLA roll expression, any shape, any platform.
  * heat_step_halo: ONE blocked Pallas kernel, slabs of (rows, 128)
    streamed through a 1-D grid with each slab's two outside neighbours
    as SMEM scalars: one stream in, one out. `stencil1d.heat_part` (the
    dataflow node's body) and `pallas_heat_step` (the periodic ring,
    the halo form fed from itself) both call it; `takes_kernel` says
    for which platform, dtype and length.
Fusing T steps per dispatch takes the traffic away instead:
  * pallas_multistep: whole array resident in VMEM, T updates without
    touching HBM in between — compute-bound instead of HBM-bound for
    arrays that fit VMEM (~<=2M f32).
  * xla_multistep: lax.fori_loop of the fused roll-expression under jit —
    works at any size, one HBM round-trip per step.
All are shape-static, branch-free, and VPU-friendly (8x128 lanes; arrays
are laid out 2D (rows, 128)).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128


def heat_step(u: jax.Array, coef: float) -> jax.Array:
    """One periodic 3-point heat update on a 1-D array (XLA-fused)."""
    left = jnp.roll(u, 1)
    right = jnp.roll(u, -1)
    return u + coef * (left - 2.0 * u + right)


@functools.partial(jax.jit, static_argnames=("steps",))
def xla_multistep(u: jax.Array, coef: jax.Array, steps: int) -> jax.Array:
    """T fused steps via fori_loop in ONE compiled program."""
    def body(_i, s):
        return heat_step(s, coef)
    return jax.lax.fori_loop(0, steps, body, u)


def _pallas_kernel(u_ref, coef_ref, out_ref, *, steps: int):
    """Whole-array-in-VMEM multi-step kernel.

    Layout: (rows, 128). Periodic 1-D neighbor access on the flattened
    view maps to lane/row shifts: left neighbor = roll(+1), which in 2-D
    is a lane roll with row-carry; implemented with jnp.roll on the 2-D
    block (cheap VPU shuffles) after adjusting the carry column.
    """
    u0 = u_ref[:]
    coef = coef_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, u0.shape, 1)
    first_col = col == 0
    last_col = col == LANES - 1

    from jax.experimental.pallas import tpu as pltpu

    def one(_i, u):
        # flattened roll(+1): shift lanes right by one; column 0 takes the
        # previous row's lane 127 (row 0 wraps to the last row). Column
        # patch via iota-mask where (a scatter would not lower on TPU);
        # shifts use pltpu.roll — Mosaic's native circular shift.
        lane_r = pltpu.roll(u, 1, axis=1)
        carry_r = pltpu.roll(u[:, LANES - 1:], 1, axis=0)  # prev row's last
        left = jnp.where(first_col, carry_r, lane_r)
        # flattened roll(-1): shift lanes left; last lane takes next row's
        # lane 0.
        # pltpu.roll requires non-negative shifts: roll by size-1
        lane_l = pltpu.roll(u, LANES - 1, axis=1)
        carry_l = pltpu.roll(u[:, :1], u.shape[0] - 1, axis=0)  # next row's first
        right = jnp.where(last_col, carry_l, lane_l)
        return u + coef * (left - 2.0 * u + right)

    # fori_loop (not Python unroll): bounds VMEM liveness to one
    # iteration's temporaries regardless of `steps`
    out_ref[:] = jax.lax.fori_loop(0, steps, one, u0)


@functools.partial(jax.jit, static_argnames=("steps",))
def pallas_multistep(u: jax.Array, coef, steps: int) -> jax.Array:
    """T steps with the state held in VMEM throughout (zero intermediate
    HBM traffic). Requires len(u) % 128 == 0 and the array to fit VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = u.shape[0]
    assert n % LANES == 0, "pallas stencil requires length % 128 == 0"
    u2 = u.reshape(n // LANES, LANES)
    coef_arr = jnp.asarray([coef], dtype=u.dtype)

    out = pl.pallas_call(
        functools.partial(_pallas_kernel, steps=steps),
        name="hpx_stencil_multistep",
        out_shape=jax.ShapeDtypeStruct(u2.shape, u2.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
    )(u2, coef_arr)
    return out.reshape(n)


# Working set in the kernel is ~5 arrays (state + roll/where temporaries);
# 512K f32 = 2 MB each keeps us ~10 MB, under the 16 MB scoped-VMEM limit.
_VMEM_F32_LIMIT = 1 << 19


def _pallas_blocked_kernel(u_ref, edges_ref, coef_ref, out_ref):
    """ONE heat step on a (R, 128) slab streamed from HBM.

    Flattened-order neighbours in the (rows, 128) layout are lane shifts
    with a row carry, computed with the SLAB-periodic wrap (the slab's
    first and last elements borrow from its own far edge). The two
    elements a slab that wrap wrongly are patched in the kernel from
    `edges_ref` (SMEM, [2 * grid]: the true left neighbour of each
    slab's first element, then the true right neighbour of each slab's
    last), so one program streams one input and one output: 8 bytes a
    cell, what the HBM roofline assumes. The sum is associated as
    `stencil1d.heat_part` and the plain float32 recurrence associate
    it, (left - 2u) + right, so the kernel's result is theirs bit for
    bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i = pl.program_id(0)
    u = u_ref[:]
    coef = coef_ref[0]
    col = jax.lax.broadcasted_iota(jnp.int32, u.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, u.shape, 0)

    lane_r = pltpu.roll(u, 1, axis=1)
    carry_r = pltpu.roll(u[:, LANES - 1:], 1, axis=0)
    left = jnp.where(col == 0, carry_r, lane_r)
    first_cell = jnp.logical_and(row == 0, col == 0)
    left = jnp.where(first_cell, edges_ref[i], left)

    lane_l = pltpu.roll(u, LANES - 1, axis=1)
    carry_l = pltpu.roll(u[:, :1], u.shape[0] - 1, axis=0)
    right = jnp.where(col == LANES - 1, carry_l, lane_l)
    last_cell = jnp.logical_and(row == u.shape[0] - 1, col == LANES - 1)
    right = jnp.where(last_cell, edges_ref[pl.num_programs(0) + i], right)

    out_ref[:] = u + coef * ((left - 2.0 * u) + right)


# Rows of 128 points a slab (a grid step): 2 MB in, 2 MB out, 14 MB of the
# 16 MB of scoped VMEM with the pipeline's second buffers and the body's
# temporaries. Measured inside jit(heat_part) at 2^27 points on a v5e
# (PERF.md, PR 46): 1,024 rows 1.913 ms, 2,048 1.675, 4,096 1.650, 8,192
# 1.655 (and 28 MB); a copy through the same BlockSpecs 1.643 at 4,096.
_BLOCK_ROWS = 4096


def _slab_rows(n: int) -> int:
    """Rows a slab of an n-point array takes; 0 where the blocked kernel
    cannot tile it (whole (8, 128) tiles, slabs that divide the rows)."""
    if n <= 0 or n % (8 * LANES):
        return 0
    rows = n // LANES
    r = min(_BLOCK_ROWS, rows)
    return r if rows % r == 0 else 0


def takes_kernel(nx: int, dtype, backend: str) -> bool:
    """Whether a 1-D array of `nx` points of `dtype` on `backend` takes
    the blocked kernel (`heat_step_halo`) or the XLA expression. The
    kernel is Mosaic's: TPU only, float32, whole slabs."""
    return (backend == "tpu" and jnp.dtype(dtype) == jnp.float32
            and _slab_rows(nx) > 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def heat_step_halo(left: jax.Array, u: jax.Array, right: jax.Array, coef,
                   interpret: bool = False) -> jax.Array:
    """One heat step of `u` whose outer neighbours are `left[-1]` and
    `right[0]`: slabs of `_BLOCK_ROWS` rows stream through a 1-D grid,
    each slab's two outside neighbours handed in as SMEM scalars, read
    from `u`'s own slab seams and, at the two ends, from the halos.
    `takes_kernel` says which shapes it accepts."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = u.shape[0]
    r = _slab_rows(n)
    if not r:
        raise ValueError(f"{n} points do not tile into slabs of "
                         f"{_BLOCK_ROWS} rows of {LANES}")
    rows = n // LANES
    grid = rows // r
    # edges[i], edges[grid + i]: the left neighbour of slab i's first
    # point and the right neighbour of its last, one gather at the seams
    seams = np.arange(1, grid, dtype=np.int32) * (r * LANES)
    edges = jnp.concatenate(
        [left[-1:], u[np.concatenate([seams - 1, seams])], right[:1]])

    return pl.pallas_call(
        _pallas_blocked_kernel,
        name="hpx_stencil_blocked",
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((r, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((r, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), u.dtype),
        interpret=interpret,
    )(u.reshape(rows, LANES), edges,
      jnp.asarray([coef], dtype=u.dtype)).reshape(n)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_heat_step(u: jax.Array, coef,
                     interpret: bool = False) -> jax.Array:
    """One periodic heat step by the blocked kernel: the halo form fed
    from the ring's own ends."""
    return heat_step_halo(u[-1:], u, u[:1], coef, interpret=interpret)


def heat_step_best(u: jax.Array, coef) -> jax.Array:
    """Best-available single step: the blocked pallas kernel on TPU
    when shapes allow, the XLA roll formulation otherwise."""
    if u.ndim == 1 and takes_kernel(u.shape[0], u.dtype,
                                    jax.default_backend()):
        return pallas_heat_step(u, coef)
    return heat_step(u, coef)


@functools.partial(jax.jit, static_argnames=("steps", "use_pallas"))
def multistep(u: jax.Array, coef: jax.Array, steps: int,
              use_pallas: Optional[bool] = None) -> jax.Array:
    """Best-available T-step stencil: pallas when the array fits VMEM.

    Auto mode only picks pallas on a real TPU backend — the mosaic
    kernel runs neither on the CPU test platform nor on GPU."""
    if use_pallas is None:
        use_pallas = (jax.default_backend() == "tpu" and
                      u.shape[0] % LANES == 0 and
                      u.shape[0] <= _VMEM_F32_LIMIT)
    if use_pallas:
        return pallas_multistep(u, coef, steps)
    return xla_multistep(u, coef, steps)
