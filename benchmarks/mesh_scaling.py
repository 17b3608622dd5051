"""Mesh-scaling measurements for BASELINE configs #3/#4/#5.

Reference analog: the distributed benchmarks HPX runs per-locality-count
(partitioned_vector STREAM triad, collectives all_reduce, distributed
Jacobi — SURVEY.md §6 configs #3/#4/#5). Here a locality = a mesh
device; the harness meshes over however many devices jax exposes and
fails when they are too few. Under JAX_PLATFORMS=cpu the launcher
gives the host platform N virtual devices for development, where the
numbers measure SCALING SHAPE (collective/halo overhead vs device
count), not absolute GB/s. Every line carries the platform it ran on.

One command:  python -m hpx_tpu.run --bench-mesh 8
prints one JSON line per (config, device-count):
  pv_triad        — partitioned_vector a+s*b via the segmented algo
                    layer (config #3), elements/s
  all_reduce_1m   — 1M-float all_reduce over the mesh (config #4),
                    ops/s and algorithm bandwidth
  jacobi2d        — sharded 2-D Jacobi, halo exchange both axes
                    (config #5), Mcells/s
"""

from __future__ import annotations

import json
import time


def _emit(**kv) -> None:
    import jax
    print(json.dumps({**kv, "platform": jax.devices()[0].platform}),
          flush=True)


def _time_loop(fn, iters: int, warm: int = 2) -> float:
    """Wall-seconds per iteration (mean of `iters` after warmup)."""
    import jax
    for _ in range(warm):
        out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_pv_triad(ndev: int, devices) -> None:
    """Config #3: STREAM triad over a PartitionedVector via the
    segmented-algorithm dispatch (one sharded XLA program)."""
    import jax.numpy as jnp
    import numpy as np

    from hpx_tpu.algo import transform
    from hpx_tpu.containers.partitioned_vector import PartitionedVector
    from hpx_tpu.dist.distribution_policies import ContainerLayout
    from hpx_tpu.exec.policies import par
    from hpx_tpu.parallel import make_mesh

    mesh = make_mesh((ndev,), ("x",), devices[:ndev])
    layout = ContainerLayout(mesh=mesh)
    n = ndev * (1 << 20)                      # weak scaling: 1M/device
    rng = np.random.default_rng(0)
    a = PartitionedVector.from_array(
        jnp.asarray(rng.random(n, np.float32)), layout=layout)
    b = PartitionedVector.from_array(
        jnp.asarray(rng.random(n, np.float32)), layout=layout)
    s = jnp.float32(1e-7)

    def run():
        return transform(par, a, lambda x, y: x + s * y, b).data

    per = _time_loop(run, iters=10)
    _emit(metric="pv_triad", n_devices=ndev, elements=n,
          meps=round(n / per / 1e6, 1),
          gbs=round(3 * n * 4 / per / 1e9, 2),
          us_per_op=round(per * 1e6, 1))


def bench_all_reduce(ndev: int, devices) -> None:
    """Config #4: 1M-float all_reduce over the mesh (XLA psum over
    ICI on hardware). Algorithm bandwidth uses the ring-allreduce
    convention 2(P-1)/P * bytes."""
    import jax.numpy as jnp
    import numpy as np

    from hpx_tpu.collectives.device import all_reduce
    from hpx_tpu.parallel import make_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    import jax

    mesh = make_mesh((ndev,), ("x",), devices[:ndev])
    n = 1 << 20
    x = jax.device_put(
        jnp.asarray(np.random.default_rng(1).random(n, np.float32)),
        NamedSharding(mesh, P("x")))

    def run():
        return all_reduce(x, mesh, "x")

    per = _time_loop(run, iters=10)
    bw = 2 * (ndev - 1) / max(ndev, 1) * n * 4 / per / 1e9 if ndev > 1 \
        else 0.0
    _emit(metric="all_reduce_1m", n_devices=ndev, elements=n,
          us_per_op=round(per * 1e6, 1), algo_gbs=round(bw, 2))


def bench_jacobi(ndev: int, devices) -> None:
    """Config #5: sharded 2-D Jacobi, halos via ppermute on both mesh
    axes, all sweeps fused per dispatch."""
    import math

    from hpx_tpu.models.jacobi2d import JacobiParams, jacobi_sharded
    from hpx_tpu.parallel import make_mesh

    ax = 2 ** (int(math.log2(ndev)) // 2) if ndev > 1 else 1
    ay = ndev // ax
    mesh = make_mesh((ax, ay), ("x", "y"), devices[:ndev])
    n = 1024
    iters = 50
    p = JacobiParams(nx=n, ny=n, nb=1, iterations=iters)

    def run():
        u, res = jacobi_sharded(p, mesh)
        return res

    per = _time_loop(run, iters=5)
    cells = n * n * iters / per
    _emit(metric="jacobi2d", n_devices=ndev, grid=f"{n}x{n}",
          mesh=f"{ax}x{ay}", iterations=iters,
          mcells=round(cells / 1e6, 1))


def bench_fft(ndev: int, devices) -> None:
    """Distributed 1-D FFT (four-step, three all_to_alls) — the
    collectives workload HPX's published FFT study measures; weak
    scaling at 2^18 points/device."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hpx_tpu.algo import fft as dfft
    from hpx_tpu.parallel import make_mesh

    mesh = make_mesh((ndev,), ("x",), devices[:ndev])
    # n = P^2 * m always satisfies the four-step factorability (P | n1
    # and P | n2); m sized for ~2^18 points per device
    n = ndev * ndev * max(1, (1 << 18) // ndev)
    rng = np.random.default_rng(1)
    v = jax.device_put(
        jnp.asarray((rng.standard_normal(n) + 1j * rng.standard_normal(n)
                     ).astype(np.complex64)),
        NamedSharding(mesh, P("x")))

    def run():
        return dfft.fft_sharded(v, mesh)

    per = _time_loop(run, iters=5)
    gflops = 5 * n * math.log2(n) / per / 1e9
    _emit(metric="fft_1d", n_devices=ndev, n=n,
          gflops=round(gflops, 2), ms=round(per * 1e3, 3))


def bench_sort(ndev: int, devices) -> None:
    """Distributed PSRS sample sort (weak scaling at 2^17 elems/device):
    collective-step count is constant in mesh size, so per-op time
    should stay flat as devices grow — the curve this table exists to
    show. The sample path is FORCED at every ndev (not the p<=4
    odd-even default) so the measured program is the pod-scale one."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from hpx_tpu.algo.sorting import sort_sharded
    from hpx_tpu.parallel import make_mesh

    mesh = make_mesh((ndev,), ("x",), devices[:ndev])
    n = ndev * (1 << 17)
    rng = np.random.default_rng(2)
    v = jax.device_put(
        jnp.asarray(rng.standard_normal(n).astype(np.float32)),
        NamedSharding(mesh, P("x")))
    if ndev > 1:
        run = lambda: sort_sharded(v, mesh, method="sample")  # noqa: E731
        method = "sample"
    else:
        run = lambda: jnp.sort(v)  # noqa: E731 — 1-dev reference program
        method = "jnp.sort"

    per = _time_loop(run, iters=5)
    _emit(metric="sort_sample", n_devices=ndev, elements=n,
          method=method,                     # self-describing: the
          melem_s=round(n / per / 1e6, 2),   # 1-dev row is a DIFFERENT
          ms=round(per * 1e3, 3))            # program (local reference)


def bench_paged_serving(ndev: int, devices) -> None:
    """Sharded paged serving: greedy continuous-batching decode over a
    (dp, tp) mesh — KV block pool sharded over tp on kv heads, slots
    and device block tables over dp. Weak in neither sense: the mix is
    FIXED, so the curve shows how decode latency absorbs devices (tp
    splits the attention/MLP math, dp splits the slots). The 1-device
    row runs the plain single-device paged server (a DIFFERENT
    program — the reference, like sort's jnp.sort row)."""
    import math

    import jax
    import numpy as np

    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.parallel import make_mesh

    cfg = tfm.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                                head_dim=16, n_layers=2, d_ff=128)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(1, 200, 16).tolist(), 24) for _ in range(6)]
    total = sum(m for _, m in reqs)

    if ndev == 1:
        mesh, dp, tp = None, 1, 1
    else:
        dp = 2 ** (int(math.log2(ndev)) // 2)
        tp = ndev // dp
        if cfg.n_heads % tp:            # tp must divide kv heads
            tp = math.gcd(tp, cfg.n_heads)
            dp = ndev // tp
        mesh = make_mesh((dp, tp), ("dp", "tp"), devices[:ndev])
    slots = max(4, dp)                  # dp | slots

    def run():
        srv = ContinuousServer(params, cfg, slots=slots, smax=64,
                               paged=True, mesh=mesh)
        for p, m in reqs:
            srv.submit(p, max_new=m)
        t0 = time.perf_counter()
        srv.run()
        return time.perf_counter() - t0

    run()                               # compile
    per = run()
    _emit(metric="paged_serving", n_devices=ndev, mesh=f"{dp}x{tp}",
          slots=slots, tokens=total,
          tokens_per_s=round(total / per, 1),
          ms_per_token=round(per * 1e3 / total, 3))


def sweep(max_devices: int) -> None:
    import jax
    devs = jax.devices()
    if len(devs) < max_devices:
        raise RuntimeError(
            f"need {max_devices} devices, jax exposes {len(devs)} on "
            f"platform {devs[0].platform!r}")
    _emit(metric="mesh_info", device_kind=devs[0].device_kind,
          n_available=len(devs))
    counts = []
    k = 1
    while k <= max_devices:
        counts.append(k)
        k *= 2
    if counts[-1] != max_devices:       # non-power-of-two request: the
        counts.append(max_devices)      # asked-for scale must be measured
    for k in counts:
        bench_pv_triad(k, devs)
        bench_all_reduce(k, devs)
        bench_jacobi(k, devs)
        bench_fft(k, devs)
        bench_sort(k, devs)
        bench_paged_serving(k, devs)


def main(max_devices: int) -> None:
    """The entry point (`__main__`, `hpx_tpu.run --bench-mesh`)."""
    from hpx_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    sweep(max_devices)


if __name__ == "__main__":
    import argparse
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    main(args.devices)
