"""2-D halo exchange over a 2-D device mesh — config #5's substrate.

Reference analog: the ghost-zone exchange of examples/jacobi/ and
examples/jacobi_smp/ (row-block dataflow dependencies), generalized to a
2-D decomposition. TPU-first: both halo directions are lax.ppermute over
ICI inside one shard_map body; the whole Jacobi sweep — exchange, 5-point
update, boundary masking, residual psum — compiles to a single XLA
program per dispatch. Non-periodic edges fall out of ppermute semantics:
a shard with no source in the permutation receives zeros, which is
exactly the zero-Dirichlet ghost value; interior masking keeps true
boundary cells fixed.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def edge_shift(x: jax.Array, axis_name: str, shift: int) -> jax.Array:
    """Non-periodic neighbor shift along a mesh axis (inside shard_map).

    shift=+1: each shard receives the payload of the neighbor BELOW it in
    index order (data moves toward higher mesh index); the shard at the
    low edge receives zeros. shift=-1 is the mirror.
    """
    n = jax.lax.axis_size(axis_name)
    if shift >= 0:
        perm = [(i, i + shift) for i in range(n - shift)]
    else:
        perm = [(i, i + shift) for i in range(-shift, n)]
    return jax.lax.ppermute(x, axis_name, perm)


def halo_exchange_2d(u: jax.Array, ax: str, ay: str
                     ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Exchange 1-cell ghost edges of a (h, w) local block.

    Returns (north, south, west, east) ghost strips: north = the last row
    of the neighbor at mesh index-1 along `ax` (zeros at the boundary),
    etc. Corners are not exchanged (5-point stencils don't need them).
    """
    north = edge_shift(u[-1:, :], ax, +1)
    south = edge_shift(u[:1, :], ax, -1)
    west = edge_shift(u[:, -1:], ay, +1)
    east = edge_shift(u[:, :1], ay, -1)
    return north, south, west, east


def _interior_mask(local_shape: Tuple[int, int], grid: Tuple[int, int],
                   ax: str, ay: str) -> jax.Array:
    """Boolean (h, w) mask of cells that are interior in GLOBAL coords."""
    h, w = local_shape
    nx, ny = grid
    gr = jax.lax.axis_index(ax) * h + jnp.arange(h)
    gc = jax.lax.axis_index(ay) * w + jnp.arange(w)
    rows = (gr > 0) & (gr < nx - 1)
    cols = (gc > 0) & (gc < ny - 1)
    return rows[:, None] & cols[None, :]


def jacobi_local_sweep(u: jax.Array, mask: jax.Array,
                       ax: str, ay: str) -> jax.Array:
    """One 5-point Jacobi sweep of a local block with halo exchange.

    u_new = mean of 4 neighbors on interior cells; boundary cells are
    carried through unchanged (Dirichlet).
    """
    north, south, west, east = halo_exchange_2d(u, ax, ay)
    vert = jnp.concatenate([north, u, south], axis=0)
    horz = jnp.concatenate([west, u, east], axis=1)
    new = 0.25 * (vert[:-2, :] + vert[2:, :] + horz[:, :-2] + horz[:, 2:])
    return jnp.where(mask, new, u)


def sharded_jacobi_step(mesh: Mesh, grid: Tuple[int, int],
                        ax: str = "x", ay: str = "y") -> Callable:
    """Jitted SPMD Jacobi step over a 2-D mesh: fn(u) -> (u_new, residual).

    residual = global sum of squared cell updates (psum over both axes) —
    the convergence diagnostic, computed on-device so the host never syncs
    unless it reads it.
    """
    from jax import shard_map

    nx, ny = grid
    npx, npy = mesh.shape[ax], mesh.shape[ay]
    assert nx % npx == 0 and ny % npy == 0, (grid, dict(mesh.shape))
    local = (nx // npx, ny // npy)

    def body(u):
        mask = _interior_mask(local, grid, ax, ay)
        new = jacobi_local_sweep(u, mask, ax, ay)
        res = jax.lax.psum(jnp.sum((new - u) ** 2), (ax, ay))
        return new, res

    fn = shard_map(body, mesh=mesh, in_specs=P(ax, ay),
                   out_specs=(P(ax, ay), P()))
    return jax.jit(fn)


def sharded_jacobi_multistep(mesh: Mesh, grid: Tuple[int, int], steps: int,
                             ax: str = "x", ay: str = "y") -> Callable:
    """`steps` Jacobi sweeps fused into ONE XLA program (fori_loop inside
    shard_map): per-sweep halo exchange rides ICI with no host round-trip.
    fn(u) -> (u_new, last_residual).
    """
    from jax import shard_map

    nx, ny = grid
    npx, npy = mesh.shape[ax], mesh.shape[ay]
    assert nx % npx == 0 and ny % npy == 0, (grid, dict(mesh.shape))
    local = (nx // npx, ny // npy)

    def body(u):
        mask = _interior_mask(local, grid, ax, ay)

        def one(_i, carry):
            s, _ = carry
            new = jacobi_local_sweep(s, mask, ax, ay)
            res = jax.lax.psum(jnp.sum((new - s) ** 2), (ax, ay))
            return new, res

        return jax.lax.fori_loop(0, steps, one,
                                 (u, jnp.zeros((), u.dtype)))

    fn = shard_map(body, mesh=mesh, in_specs=P(ax, ay),
                   out_specs=(P(ax, ay), P()))
    return jax.jit(fn)
