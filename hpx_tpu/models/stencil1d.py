"""1-D heat-equation workloads — the reference's flagship example ladder.

Reference analog: examples/1d_stencil/1d_stencil_{1,4}.cpp (BASELINE
config #2). The ladder is kept so the programming models can be compared
on identical physics:

  stencil_serial    1d_stencil_1: whole-domain update loop (here: one
                    fused XLA program per step batch — the honest TPU
                    "serial" baseline).
  stencil_dataflow  1d_stencil_4: the domain is split into np partitions,
                    each timestep builds hpx.dataflow(unwrapping(heat_part),
                    left, mid, right) — the future DAG throttled only by
                    dependencies. Partition updates are device dispatches;
                    halos are 1-element array slices; the host never
                    blocks inside the loop. A node's body (heat_part) is
                    the blocked one-pass kernel of ops/stencil where
                    `ops.stencil.takes_kernel` says the platform and the
                    partition allow it, one XLA expression elsewhere.
  stencil_fused     TPU-first production path: T steps fused per dispatch
                    (ops/stencil.multistep — pallas in-VMEM when it fits).

All use periodic boundaries and u0[i] = i (the reference's init), so
results are directly comparable across variants and to the reference.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import jax.numpy as jnp

from ..exec.tpu import TpuExecutor
from ..futures.async_ import Launch
from ..futures.dataflow import dataflow, unwrapping
from ..futures.future import Future, make_ready_future
from ..ops.stencil import heat_step, heat_step_halo, multistep, takes_kernel


@dataclasses.dataclass
class StencilParams:
    nx: int = 1024          # points per partition
    np_: int = 16           # number of partitions
    nt: int = 100           # timesteps
    k: float = 0.5          # heat transfer coefficient
    dt: float = 1.0
    dx: float = 1.0

    @property
    def coef(self) -> float:
        return self.k * self.dt / (self.dx * self.dx)

    @property
    def total(self) -> int:
        return self.nx * self.np_


def init_domain(p: StencilParams) -> jax.Array:
    return jnp.arange(p.total, dtype=jnp.float32)


# -- serial (1d_stencil_1 analog) -------------------------------------------

def stencil_serial(p: StencilParams, u0: Optional[jax.Array] = None) -> jax.Array:
    u = init_domain(p) if u0 is None else u0
    coef = jnp.float32(p.coef)
    step = jax.jit(heat_step)
    for _ in range(p.nt):
        u = step(u, coef)
    return u


# -- dataflow over partitions (1d_stencil_4 analog) -------------------------

def heat_part(left: jax.Array, middle: jax.Array,
              right: jax.Array, coef) -> jax.Array:
    """Update one partition given 1-element neighbor boundary arrays.

    Reference: heat_part in examples/1d_stencil/1d_stencil_4.cpp — there
    left/right are whole neighbor partitions; shipping only the boundary
    element is the same optimization 1d_stencil_8 makes for the
    distributed case, and the right call for device memory traffic.

    On a TPU a float32 partition of whole slabs goes through the blocked
    kernel, which reads `middle` once and writes the result once; the
    XLA expression below reads it three times. Both give the same
    float32 result bit for bit.
    """
    if middle.ndim == 1 and takes_kernel(middle.shape[0], middle.dtype,
                                         jax.default_backend()):
        return heat_step_halo(left, middle, right, coef)
    um = jnp.concatenate([left, middle, right])
    return um[1:-1] + coef * (um[:-2] - 2.0 * um[1:-1] + um[2:])


def stencil_dataflow(p: StencilParams,
                     executor: Optional[TpuExecutor] = None,
                     u0: Optional[jax.Array] = None) -> List[Future]:
    """The 1d_stencil_4 DAG: U[t+1][i] = dataflow(heat_part, U[t][i-1],
    U[t][i], U[t][i+1]). Returns the final vector of partition futures."""
    ex = executor or TpuExecutor()
    coef = jnp.float32(p.coef)
    full = init_domain(p) if u0 is None else u0
    parts = [full[i * p.nx:(i + 1) * p.nx] for i in range(p.np_)]
    u: List[Future] = [make_ready_future(x) for x in parts]

    compiled = jax.jit(heat_part)

    def node(lf: Future, mf: Future, rf: Future) -> Future:
        # device dispatch; future is eager — the DAG drives XLA's async
        # queue, dependencies are enforced by the arrays themselves
        return ex.async_execute_raw(
            compiled, lf.get()[-1:], mf.get(), rf.get()[:1], coef)

    for _t in range(p.nt):
        # node returns a Future; dataflow's shared state unwraps it, so
        # u stays a flat vector of futures of partition arrays. sync
        # policy: the "task body" is just an async device dispatch, no
        # host pool hop needed.
        u = [
            dataflow(node, u[(i - 1) % p.np_], u[i], u[(i + 1) % p.np_],
                     policy=Launch.sync)
            for i in range(p.np_)
        ]
    return u


def gather_dataflow_result(u: List[Future]) -> jax.Array:
    return jnp.concatenate([f.get() for f in u])


# -- fused (TPU-first) ------------------------------------------------------

def stencil_fused(p: StencilParams, u0: Optional[jax.Array] = None,
                  steps_per_dispatch: int = 50,
                  use_pallas: Optional[bool] = None) -> jax.Array:
    u = init_domain(p) if u0 is None else u0
    coef = jnp.float32(p.coef)
    done = 0
    while done < p.nt:
        s = min(steps_per_dispatch, p.nt - done)
        u = multistep(u, coef, s, use_pallas)
        done += s
    return u


# -- reporting (print_time_results analog) ----------------------------------

def print_time_results(variant: str, elapsed_s: float, p: StencilParams,
                       file=None) -> float:
    """Prints the reference-style results row; returns Mcells/s."""
    import sys
    cells = p.total * p.nt
    mcps = cells / elapsed_s / 1e6
    print(f"{variant:>18s}: {p.np_:>6d} partitions, {p.nx:>8d} points each, "
          f"{p.nt:>6d} steps, {elapsed_s:8.4f} s, {mcps:12.1f} Mcells/s",
          file=file or sys.stdout)
    return mcps
