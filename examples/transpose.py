"""Distributed matrix transpose — phase-based collectives demo.

Reference analog: examples/transpose/transpose_block.cpp (block
transpose where every locality exchanges tiles with every other —
the all_to_all communication pattern).

TPU-first: the matrix is row-sharded over the mesh; the transpose is
ONE sharded XLA program — `lax.all_to_all` inside shard_map exchanges
tiles over ICI, then each shard transposes its received tiles locally.
Compare with the reference's N² explicit parcels.

Usage: python examples/transpose.py [n]
"""

import sys

sys.path.insert(0, ".")
from examples._common import setup_platform  # noqa: E402

argv = setup_platform()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import shard_map  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

import hpx_tpu as hpx  # noqa: E402
from hpx_tpu.parallel import make_mesh, shard_1d  # noqa: E402


def main() -> int:
    import jax
    ndev = len(jax.devices())
    n = int(argv[0]) if argv else 1024
    n -= n % ndev                     # divisible rows/cols
    mesh = make_mesh((ndev,), ("x",))

    a = jnp.asarray(np.random.default_rng(0).random((n, n), np.float32))
    a = jax.device_put(a, jax.sharding.NamedSharding(mesh, P("x", None)))

    def body(blk):                    # blk: (n/ndev, n) local rows
        # split my rows into ndev column-tiles, trade tile j to device j
        tiles = blk.reshape(blk.shape[0], ndev, n // ndev)
        tiles = jnp.moveaxis(tiles, 1, 0)           # (ndev, rows, cols)
        recv = jax.lax.all_to_all(tiles, "x", 0, 0, tiled=False)
        # recv[j] = tile from device j: my columns of their rows
        return jnp.concatenate(
            [r.T for r in recv], axis=1)            # (n/ndev, n)

    tr = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                           out_specs=P("x", None)))

    t = hpx.HighResolutionTimer()
    at = tr(a)
    at.block_until_ready()
    dt = t.elapsed()

    np.testing.assert_allclose(np.asarray(at), np.asarray(a).T, rtol=1e-6)
    gbs = 2 * n * n * 4 / dt / 1e9
    print(f"transpose {n}x{n} over {ndev} devices: "
          f"{dt * 1e3:.2f} ms ({gbs:.1f} GB/s effective)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
