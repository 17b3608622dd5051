"""Stencil kernels: 3-point heat update, single- and multi-step.

Reference analog: the `heat_part` inner loop of examples/1d_stencil/
1d_stencil_4.cpp (u'[i] = u[i] + k*dt/dx^2 * (u[i-1] - 2u[i] + u[i+1]),
periodic neighbors) — the Mcells/s hot loop of BASELINE config #2.

TPU-first design: a single heat step is HBM-bandwidth-bound (read u, write
u'). The win is fusing T steps per dispatch:
  * pallas_multistep: whole array resident in VMEM, T updates without
    touching HBM in between — compute-bound instead of HBM-bound for
    arrays that fit VMEM (~<=2M f32).
  * xla_multistep: lax.fori_loop of the fused roll-expression under jit —
    works at any size, one HBM round-trip per step.
Both are shape-static, branch-free, and VPU-friendly (8x128 lanes; arrays
are laid out 2D (rows, 128)).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

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

    Flattened-order neighbors in the (rows, 128) layout are lane shifts
    with a row carry, computed with the SLAB-periodic wrap (the slab's
    first/last elements borrow from its own far edge). The 2 elements
    per slab that wrap wrongly are patched IN-KERNEL from `edges_ref`
    (SMEM: [grid, 2] true global neighbors, 8 bytes per slab gathered
    once in XLA) — so ONE program streams one input + one output
    (8 B/cell, the HBM roofline's assumption). The round-1..3 variant
    patched them with a host-side scatter instead, which forced a
    second full pass over `out` and capped the bench at ~61% of roof.
    Separate halo-block INPUTS (vs these SMEM scalars) were measured to
    stall the DMA pipeline (~15 points of roof); XLA's roll/concat
    lowering of the same step materializes shifted copies (~4x
    traffic)."""
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
    left = jnp.where(first_cell, edges_ref[i, 0], left)

    lane_l = pltpu.roll(u, LANES - 1, axis=1)
    carry_l = pltpu.roll(u[:, :1], u.shape[0] - 1, axis=0)
    right = jnp.where(col == LANES - 1, carry_l, lane_l)
    last_cell = jnp.logical_and(row == u.shape[0] - 1, col == LANES - 1)
    right = jnp.where(last_cell, edges_ref[i, 1], right)

    out_ref[:] = u + coef * ((left + right) - 2.0 * u)


_BLOCK_ROWS = 2048           # 1 MB/slab: deep DMA pipeline; 8192 looked
                             # ~5% faster in the r4 sweep but OOMs the
                             # 16 MB scoped VMEM under some jit wrappings
                             # (5 live slab temporaries x 4 MB)


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_heat_step(u: jax.Array, coef,
                     interpret: bool = False) -> jax.Array:
    """Single periodic heat step for arrays too big for VMEM: slabs
    stream through a 1-D grid with the global-periodic seam neighbors
    fed as per-slab SMEM scalars. Requires len(u) % 128 == 0 and
    rows % block == 0 (the benchmark shapes; use heat_step_best for
    automatic fallback)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = u.shape[0]
    rows = n // LANES
    r = min(_BLOCK_ROWS, rows)
    assert n % LANES == 0 and rows % r == 0 and r % 8 == 0, (n, rows, r)
    u2 = u.reshape(rows, LANES)
    grid = rows // r

    # true global neighbors of each slab's first/last element — a tiny
    # fused gather (2 scalars per slab)
    import numpy as _np
    starts = jnp.asarray(_np.arange(grid) * r * LANES, jnp.int32)
    edges = jnp.stack([u[(starts - 1) % n],
                       u[(starts + r * LANES) % n]], axis=1)

    out = pl.pallas_call(
        _pallas_blocked_kernel,
        name="hpx_stencil_blocked",
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((r, LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((r, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(u2.shape, u2.dtype),
        interpret=interpret,
    )(u2, edges, jnp.asarray([coef], dtype=u.dtype)).reshape(n)
    return out


def heat_step_best(u: jax.Array, coef) -> jax.Array:
    """Best-available single step: the blocked pallas kernel on TPU
    when shapes allow, the XLA roll formulation otherwise."""
    n = u.shape[0]
    rows = n // LANES if n % LANES == 0 else 0
    r = min(_BLOCK_ROWS, rows) if rows else 0
    # == "tpu", not "not cpu": the kernel is Mosaic-only — a GPU backend
    # must take the XLA path, not crash in pallas lowering (advisor r2)
    if (jax.default_backend() == "tpu" and rows
            and rows % r == 0 and r % 8 == 0):
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
