"""Distributed FFT over a sharded axis: pencil decomposition on ICI.

Reference analog: HPX ships no FFT in-tree, but the distributed FFT
built from `hpx::collectives::all_to_all` over `partitioned_vector`
data is its published flagship collectives workload (SURVEY.md §6,
PAPERS.md arXiv:2504.03657 — scaling HPX collectives vs MPI for FFT).
The TPU-native form: the transpose steps are `lax.all_to_all` inside
one `shard_map`-jitted program, so XLA schedules the exchange over ICI
and fuses the twiddle multiply into the surrounding FFTs; the local
1-D transforms are XLA's native `fft` batched over the non-transformed
dimension (MXU/VPU friendly, no tag-matched messaging anywhere).

Two surfaces, matching collectives/device.py:
  * whole-array helpers (`fft2_sharded`, `fft_sharded`, and inverses):
    take a jax.Array sharded over a mesh axis, run ONE jitted program,
    return the result sharded the same way in natural order;
  * `fft2_body` / `fft1d_body` for user shard_map SPMD code.

1-D algorithm (Bailey four-step), derived for a row-major matrix view
A[n1, n2] = v[n1*N2 + n2] with N = N1*N2 and the vector sharded into
contiguous chunks (= whole rows of A):

    X[k2*N1 + k1] = FFT_axis1( FFT_axis0(A)[k1, n2] * w(k1, n2) )[k1, k2]
    with twiddle w(k1, n2) = exp(-2*pi*i * k1 * n2 / N)

so the schedule is: all_to_all (rows -> full columns), column FFTs,
twiddle, all_to_all back, row FFTs, and one final all_to_all + local
transpose to deliver natural-order output (skippable — see
`natural_order` — exactly like classic distributed FFTs that leave the
result bit-transposed for a later inverse to undo).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Optional, Tuple

__all__ = ["fft", "ifft", "fft2_sharded", "ifft2_sharded", "fft_sharded",
           "ifft_sharded", "fft2_sharded_2d", "ifft2_sharded_2d",
           "fft2_body", "fft1d_body"]


# ---------------------------------------------------------------------------
# in-body pieces (run inside an enclosing shard_map over `axis`)
# ---------------------------------------------------------------------------

def _a2a(x, axis: str, split: int, concat: int):
    from jax import lax
    return lax.all_to_all(x, axis, split_axis=split, concat_axis=concat,
                          tiled=True)


def fft2_body(a, axis: str, inverse: bool = False,
              natural_order: bool = True):
    """2-D FFT of a matrix row-sharded over `axis`; local shard
    [N0/P, N1]. Returns the row-sharded result (or column-sharded
    [N0, N1/P] when natural_order=False, saving one all_to_all)."""
    import jax.numpy as jnp
    f = jnp.fft.ifft if inverse else jnp.fft.fft
    a = f(a, axis=1)                       # rows are local: N1 FFTs
    a = _a2a(a, axis, split=1, concat=0)   # -> [N0, N1/P]
    a = f(a, axis=0)                       # full columns now local
    if natural_order:
        a = _a2a(a, axis, split=0, concat=1)   # -> [N0/P, N1]
    return a


def fft1d_body(a, axis: str, n_shards: int, n: int,
               inverse: bool = False, natural_order: bool = True):
    """Four-step 1-D FFT; `a` is the [N1/P, N2] row-major matrix view
    of this device's contiguous vector chunk. Returns the [N/P]-shaped
    natural-order chunk (or the [N1/P, N2] D-matrix when
    natural_order=False; undo with the matching inverse)."""
    import jax
    import jax.numpy as jnp

    f = jnp.fft.ifft if inverse else jnp.fft.fft
    n1 = a.shape[0] * n_shards
    n2 = a.shape[1]
    t = _a2a(a, axis, split=1, concat=0)       # [N1, N2/P]
    b = f(t, axis=0)
    idx = jax.lax.axis_index(axis)
    n2_loc = n2 // n_shards
    k1 = jnp.arange(n1)[:, None]
    n2g = idx * n2_loc + jnp.arange(n2_loc)[None, :]
    sign = 2.0 if inverse else -2.0
    # k1*n2g < N1*N2 = N: cast BEFORE the product — an int32 multiply
    # silently wraps for N >= 2^31 and would corrupt the spectrum, while
    # the float product merely loses ulps (f32 exact to N ~ 16M; f64
    # when x64 is on)
    ftype = jnp.float64 if b.dtype == jnp.complex128 else jnp.float32
    tw = jnp.exp((sign * jnp.pi / n) * 1j
                 * (k1.astype(ftype) * n2g.astype(ftype))).astype(b.dtype)
    c = b * tw
    d = f(_a2a(c, axis, split=0, concat=1), axis=1)   # [N1/P, N2]
    # ifft normalizes each local transform by its length; the composed
    # 1-D inverse needs exactly 1/N total: patch N1*N2 -> N (they are
    # equal, so nothing to patch — kept explicit for readers)
    if not natural_order:
        return d
    e = _a2a(d, axis, split=1, concat=0)       # [N1, N2/P]
    return jnp.swapaxes(e, 0, 1).reshape(-1)   # X[k2*N1+k1] chunk


# ---------------------------------------------------------------------------
# whole-array helpers (one cached jitted program per shape/mesh)
# ---------------------------------------------------------------------------

from ..core.programs import cached_program

_PROGRAMS: dict = {}


def _program(key, build):
    return cached_program(_PROGRAMS, key, build)


def _shard_prog(mesh, spec, body):
    import jax
    from jax import shard_map
    if isinstance(spec, str):
        from jax.sharding import PartitionSpec as P
        spec = P(spec)
    return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                             out_specs=spec))


def fft2_sharded(x: Any, mesh, axis: str = "x", inverse: bool = False):
    """2-D FFT of a [N0, N1] array sharded over rows (dim 0 on mesh
    axis `axis`); both dims' per-device extents must divide evenly.
    One jitted program: local row FFTs, all_to_all transpose, column
    FFTs, all_to_all back."""
    p = mesh.shape[axis]
    n0, n1 = x.shape
    if n0 % p or n1 % p:
        raise ValueError(f"shape {x.shape} not tileable over {p} shards")

    def build():
        return _shard_prog(mesh, axis,
                           lambda a: fft2_body(a, axis, inverse=inverse))

    return _program(("fft2", mesh, axis, x.shape, x.dtype.name, inverse),
                    build)(x)


def ifft2_sharded(x: Any, mesh, axis: str = "x"):
    return fft2_sharded(x, mesh, axis, inverse=True)


def fft2_sharded_2d(x: Any, mesh, axes: Tuple[str, str] = ("x", "y"),
                    inverse: bool = False):
    """2-D FFT of an [N0, N1] array sharded over BOTH dims on a 2-D
    mesh (dim 0 over axes[0], dim 1 over axes[1]) — the layout real
    pods use (2-D ICI torus). Pencil schedule, one jitted program:

        a2a over axes[1] (rows whole)  -> row FFTs   -> a2a back
        a2a over axes[0] (cols whole)  -> column FFTs -> a2a back

    Each transpose stays INSIDE one mesh axis, so every exchange rides
    that axis's ICI ring; the other axis's sharding is untouched.
    Per-device extents must tile: Px*Py | N0/Px-side splits, i.e.
    N0 % (Px*Py) == 0 and N1 % (Px*Py) == 0.
    """
    ax0, ax1 = axes
    px, py = mesh.shape[ax0], mesh.shape[ax1]
    n0, n1 = x.shape
    if n0 % (px * py) or n1 % (px * py):
        raise ValueError(
            f"shape {x.shape} not tileable by Px*Py = {px}*{py} on both "
            f"dims (the intra-axis transposes re-split each dim)")

    def build():
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        def body(a):                      # [N0/Px, N1/Py]
            f = jnp.fft.ifft if inverse else jnp.fft.fft
            # rows whole: redistribute dim 0 over the y axis too
            t = _a2a(a, ax1, split=0, concat=1)   # [N0/(PxPy), N1]
            t = f(t, axis=1)
            a = _a2a(t, ax1, split=1, concat=0)   # [N0/Px, N1/Py]
            # columns whole: redistribute dim 1 over the x axis
            t = _a2a(a, ax0, split=1, concat=0)   # [N0, N1/(PxPy)]
            t = f(t, axis=0)
            return _a2a(t, ax0, split=0, concat=1)

        return _shard_prog(mesh, P(ax0, ax1), body)

    return _program(("fft2_2d", mesh, axes, x.shape, x.dtype.name,
                     inverse), build)(x)


def ifft2_sharded_2d(x: Any, mesh, axes: Tuple[str, str] = ("x", "y")):
    return fft2_sharded_2d(x, mesh, axes, inverse=True)


def _split_n(n: int, p: int) -> Tuple[int, int]:
    """Factor n = n1*n2 with p | n1 and p | n2, n1 as near sqrt(n) as
    possible (balanced pencils minimize all_to_all volume skew)."""
    best = None
    d = p
    while d * d <= n * p:        # n1 candidates: multiples of p
        if n % d == 0 and (n // d) % p == 0:
            if best is None or abs(d - math.isqrt(n)) < abs(
                    best - math.isqrt(n)):
                best = d
        d += p
    if best is None:
        raise ValueError(
            f"cannot factor n={n} as n1*n2 with {p} | n1 and {p} | n2")
    return best, n // best


def fft_sharded(v: Any, mesh, axis: str = "x", inverse: bool = False):
    """1-D FFT of a length-N vector sharded in contiguous chunks over
    mesh axis `axis` (Bailey four-step; three all_to_alls; output in
    natural order, sharded the same way)."""
    p = mesh.shape[axis]
    (n,) = v.shape
    n1, n2 = _split_n(n, p)

    def build():
        def body(chunk):
            a = chunk.reshape(n1 // p, n2)
            return fft1d_body(a, axis, p, n, inverse=inverse)
        return _shard_prog(mesh, axis, body)

    return _program(("fft1", mesh, axis, n, v.dtype.name, inverse),
                    build)(v)


def ifft_sharded(v: Any, mesh, axis: str = "x"):
    return fft_sharded(v, mesh, axis, inverse=True)


def fft(v: Any, mesh=None, axis: str = "x", inverse: bool = False):
    """Front door: a sharded jax.Array (pass mesh) or a
    PartitionedVector (its layout carries mesh + axis) — the segmented-
    algorithm pattern (algo/__init__) applied to the FFT."""
    from ..containers.partitioned_vector import PartitionedVector
    if isinstance(v, PartitionedVector):
        if mesh is not None and mesh is not v.mesh:
            raise ValueError(
                "fft(pv, mesh=...): the layout's mesh governs; drop the "
                "mesh argument or pass the plain sharded array")
        if v.data.shape[0] != v.size:
            raise ValueError(
                f"fft over a padded partitioned_vector (size {v.size}, "
                f"padded {v.data.shape[0]}): resize so the axis divides "
                f"the length")
        out = fft_sharded(v.data, v.mesh, v.layout.axis, inverse)
        return PartitionedVector.from_array(out, layout=v.layout)
    if mesh is None:
        raise ValueError("pass mesh= for a plain sharded array")
    return fft_sharded(v, mesh, axis, inverse)


def ifft(v: Any, mesh=None, axis: str = "x"):
    return fft(v, mesh, axis, inverse=True)
