"""Halo exchange over the device mesh — the neighbor-ring substrate.

Reference analog: the distributed stencil halo exchange of
examples/1d_stencil/1d_stencil_8.cpp (channels between neighboring
localities) and hpx::lcos::local::receive_buffer. TPU-first: the ring is
lax.ppermute over ICI inside shard_map — compiled, deadlock-free, and the
same primitive ring attention / context parallelism rides (SURVEY.md
§5.7); ring_attention (M10) builds on exactly this exchange.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def ring_shift(x: jax.Array, axis_name: str, shift: int = 1) -> jax.Array:
    """Send x to the neighbor `shift` steps up the ring (periodic).

    Inside shard_map only. shift=+1: each shard receives its LEFT
    neighbor's payload (data moves right).
    """
    n = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_exchange_1d(u_local: jax.Array, axis_name: str):
    """Return (left_ghost, right_ghost) 1-element arrays for a 1-D shard.

    left_ghost = left neighbor's last element, right_ghost = right
    neighbor's first element (periodic ring over the mesh axis).
    """
    left_ghost = ring_shift(u_local[-1:], axis_name, +1)
    right_ghost = ring_shift(u_local[:1], axis_name, -1)
    return left_ghost, right_ghost


def sharded_heat_step(mesh: Mesh, axis: str = "x",
                      halo_steps: int = 1) -> Callable:
    """Build a jitted SPMD heat step: shard_map body does `halo_steps`
    local updates per exchange (ghost width = halo_steps — the classic
    communication-avoiding trapezoid).

    The returned fn(u_sharded, coef) keeps u sharded over `axis`;
    ICI traffic is 2 * halo_steps elements per shard per call.
    """
    from jax import shard_map

    w = halo_steps

    def body(u, coef):
        lg = ring_shift(u[-w:], axis, +1)   # left neighbor's tail
        rg = ring_shift(u[:w], axis, -1)    # right neighbor's head
        ext = jnp.concatenate([lg, u, rg])
        for _ in range(w):
            ext = ext[1:-1] + coef * (ext[:-2] - 2.0 * ext[1:-1] + ext[2:])
        return ext

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis), P()),
                   out_specs=P(axis))
    return jax.jit(fn)


def sharded_multistep(mesh: Mesh, axis: str, steps: int,
                      halo_steps: int = 1) -> Callable:
    """T-step sharded stencil: fori_loop of exchange+update inside ONE
    jitted program — the whole time loop is a single XLA computation with
    ICI collectives compiled in (no host round-trips)."""
    from jax import shard_map

    w = halo_steps
    outer = steps // w
    assert steps % w == 0, "steps must be a multiple of halo_steps"

    def body(u, coef):
        def one(_i, s):
            lg = ring_shift(s[-w:], axis, +1)
            rg = ring_shift(s[:w], axis, -1)
            ext = jnp.concatenate([lg, s, rg])
            for _ in range(w):
                ext = ext[1:-1] + coef * (
                    ext[:-2] - 2.0 * ext[1:-1] + ext[2:])
            return ext
        return jax.lax.fori_loop(0, outer, one, u)

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                   out_specs=P(axis))
    return jax.jit(fn)
