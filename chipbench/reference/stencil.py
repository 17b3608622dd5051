"""The plain reference of hpx-1d-stencil: the serial float32 recurrence
in NumPy, on the dependency cone of each sampled point. Shares no code
with hpx_tpu.

u[t+1][i] = u[t][i] + coef * (u[t][i-1] - 2 u[t][i] + u[t][i+1]),
periodic. The value of point i after nt steps depends on u0[i-nt ..
i+nt] alone, so the reference runs the recurrence on windows of
2 nt + 1 points, which shrink by one point a side and step.

`dtype=bfloat16` (ml_dtypes) is the CONTROL: the same recurrence with
every operation rounded to bfloat16, the nearest precision below the
float32 the configuration states.
"""

from __future__ import annotations

import numpy as np


def sample_points(total: int, nx: int, n_random: int, seed: int):
    """Where the gathered field is compared: the two points on each
    side of every partition seam (the halo exchange is what a dataflow
    fault breaks first) and n_random seeded points."""
    seams = np.arange(0, total, nx, dtype=np.int64)
    near = (seams[:, None] + np.arange(-2, 2)[None, :]).ravel() % total
    rng = np.random.default_rng(seed)
    rand = rng.integers(0, total, n_random, dtype=np.int64)
    return np.unique(np.concatenate([near, rand]))


def window_index(points, nt: int, total: int):
    """[n, 2 nt + 1] indices of each point's dependency cone."""
    return (np.asarray(points)[:, None]
            + np.arange(-nt, nt + 1)[None, :]) % total


def recurrence(windows, coef: float, nt: int, dtype=np.float32):
    """windows [n, 2 nt + 1] of the initial field -> [n] values of the
    centre points after nt steps, every operation in `dtype`."""
    w = np.asarray(windows).astype(dtype)
    c = np.asarray(coef).astype(dtype)
    two = np.asarray(2.0).astype(dtype)
    if w.shape[1] != 2 * nt + 1:
        raise ValueError("window width must be 2 nt + 1")
    for _ in range(nt):
        mid = w[:, 1:-1]
        w = (mid + c * (w[:, :-2] - two * mid + w[:, 2:])).astype(dtype)
    return w[:, 0].astype(np.float32)


def bfloat16():
    import ml_dtypes
    return ml_dtypes.bfloat16
