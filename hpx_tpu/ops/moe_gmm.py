"""Grouped expert FFN as one Pallas kernel: `hpx_moe_gmm`.

The device side of `models/moe.moe_ffn_serve`, the drop-free sparse
FFN of the serving path. The T * k routing assignments arrive SORTED BY
EXPERT and padded per expert to the kernel's row tile, so every row
tile belongs to exactly one expert:

    x_pad   [n_tiles * tm, D]   token rows, grouped by expert
    tile_e  [n_tiles] int32     the expert of each row tile
    n_used  [1] int32           tiles that hold any row

Grid (n_tiles,): the weight BlockSpecs resolve tile i to expert
`tile_e[i]` through the scalar-prefetched map, so one expert's three
matrices stream HBM -> VMEM once per run of its tiles (Pallas skips the
copy when consecutive steps name the same block) and an expert no token
chose is never read. Tiles past `n_used` name the last used expert
(no new copy) and skip the arithmetic. Per tile, in VMEM:

    y = (silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]

with f32 accumulation and the gated product rounded to the weights'
dtype before the second matmul, exactly as the XLA formulation next to
the caller (`lax.ragged_dot`, the kernel's oracle) computes it.

Memory-bound by design: a decode step of 32 tokens x top-8 hits ~160 of
256 experts with 1.6 rows each; each hit costs the expert's 3 * D * F
weights (6 MB at 2048 x 512 bf16, 7.7 us at 819 GB/s) against 0.1
GFLOP.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["grouped_swiglu", "row_tile"]


def row_tile(dtype) -> int:
    """Rows of one tile: the sublane count of the dtype's native tile
    (16 for 2-byte types, 8 for 4-byte)."""
    return 16 if jnp.dtype(dtype).itemsize == 2 else 8


def _gmm_kernel(tile_e_ref, n_used_ref, x_ref, w1_ref, w3_ref, w2_ref,
                o_ref):
    @pl.when(pl.program_id(0) < n_used_ref[0])
    def _tile():
        x = x_ref[...]                                   # (tm, D)
        f32 = jnp.float32
        gate = jnp.dot(x, w1_ref[...], preferred_element_type=f32)
        up = jnp.dot(x, w3_ref[...], preferred_element_type=f32)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)     # (tm, F)
        o_ref[...] = jnp.dot(h, w2_ref[...],
                             preferred_element_type=f32
                             ).astype(o_ref.dtype)


def grouped_swiglu(x_pad: jax.Array, tile_e: jax.Array,
                   n_used: jax.Array, w1: jax.Array, w3: jax.Array,
                   w2: jax.Array,
                   interpret: Optional[bool] = None) -> jax.Array:
    """SiLU-gated expert MLPs over expert-grouped row tiles.

    x_pad: [n_tiles * tm, D] (tm = `row_tile(x_pad.dtype)`); tile_e:
    [n_tiles] int32; n_used: [1] int32; w1, w3: [E, D, F]; w2:
    [E, F, D]. Returns [n_tiles * tm, D]; rows of tiles past `n_used`
    are never written (nothing may read them)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    tm = row_tile(x_pad.dtype)
    m, d = x_pad.shape
    f = w1.shape[2]
    n_tiles = m // tm
    if m % tm or tile_e.shape != (n_tiles,):
        raise ValueError(f"x_pad rows {m} / tile map {tile_e.shape} do "
                         f"not make whole tiles of {tm} rows")
    row = pl.BlockSpec((tm, d), lambda i, te, nu: (i, 0))
    item = jnp.dtype(w1.dtype).itemsize
    # three weight blocks, double-buffered, plus the row tiles
    vmem = 2 * 3 * d * f * item + 8 * tm * max(d, f) * 4 + (4 << 20)
    return pl.pallas_call(
        _gmm_kernel,
        name="hpx_moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_tiles,),
            in_specs=[
                row,
                pl.BlockSpec((None, d, f), lambda i, te, nu: (te[i], 0, 0)),
                pl.BlockSpec((None, d, f), lambda i, te, nu: (te[i], 0, 0)),
                pl.BlockSpec((None, f, d), lambda i, te, nu: (te[i], 0, 0)),
            ],
            out_specs=row,
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), x_pad.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(max(vmem, 16 << 20))),
        interpret=interpret,
    )(tile_e.astype(jnp.int32), n_used.astype(jnp.int32), x_pad, w1, w3,
      w2)
