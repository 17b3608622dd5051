#!/usr/bin/env python
"""Benchmarks on the real TPU chip — one JSON line per metric.

Metrics (each with a DEFENSIBLE roofline as its vs_baseline):
  * stream_triad_gbs      — dispatch-level a+s*b (2 reads + 1 write per
                            element, buffers HBM-resident, output buffer
                            donated). Roof: 819 GB/s v5e HBM bandwidth.
  * 1d_stencil_unfused    — ONE heat step per dispatch (BASELINE config
                            #2's per-step shape): 8 bytes/cell-update.
                            Roof: HBM => 102.4 Gcells/s.
  * flash_attention_mfu   — pallas kernel, bf16 B2/S4096/N8/H128 causal.
                            Roof: 197 bf16 TFLOP/s (v5e MXU peak);
                            value = TFLOP/s, vs_baseline = MFU.
  * fft_1d_gflops         — 1-D complex64 FFT (2^22 pts) through
                            algo/fft's four-step program (the
                            distributed code path on a 1-chip mesh).
                            vs_baseline: HBM traffic model (~6 passes
                            of 8 B/pt) over measured time.
  * transformer_step_ms   — single-chip fwd+bwd+sgd on a 4-layer
                            d512/S1024 model; vs_baseline = achieved
                            model FLOP/s over MXU peak (MFU).
  * 1d_stencil_cell_updates (HEADLINE, printed last) — the fused
    1024-step in-VMEM path. Its honest roof is NOT the unfused HBM
    bound (it barely touches HBM): per-step work is ~3 VPU flops/cell,
    so the compute roof is vpu_flops/3. vs_baseline reports against
    that compute roof; the unfused-HBM ratio the round-1 bench used is
    reported alongside as `x_vs_unfused_hbm_roof` for continuity.

Timing: every number uses the SLOPE method — time chains of K
dependent dispatches ending in a scalar materialization for two K
values and divide the deltas, which cancels the fixed host<->device
round trip. Chained inputs evolve, so no dispatch can be deduplicated.
Every metric repeats its whole slope measurement SAMPLES times and
reports the MEDIAN, with `spread` = (max-min)/median alongside — a
metric whose spread rivals its delta hasn't moved.

One process measures, on the chip: every line is stamped with the
device it ran on (`platform`, `device_kind`, `device_count`), and a run
that finds no TPU prints no metric line and exits non-zero.
"""

import functools
import json
import os
import sys
import time

import numpy as np

HBM_PEAK_GBS = 819.0      # TPU v5e HBM bandwidth
MXU_PEAK_BF16 = 197e12    # TPU v5e bf16 FLOP/s
# (the fused-stencil compute roof is MEASURED — see bench_vpu_rate —
# rather than derived from an unpublished VPU spec)


def slope_time(run_chain, k1: int, k2: int, repeats: int = 3):
    """Slope timing with min-of-N endpoints. The fixed dispatch and
    readback cost fluctuates, so the k2 chain must put well over
    100 ms of real device work above it — callers pick (k1, k2) so
    (k2-k1)*per_iter >> jitter."""
    run_chain(k1)                        # warm: pages, donation, caches
    t1 = min(run_chain(k1) for _ in range(repeats))
    t2 = min(run_chain(k2) for _ in range(repeats))
    return max(t2 - t1, 1e-9) / (k2 - k1)


SAMPLES = 3


def robust(per_fn, samples: int = 0):
    """Repeat a whole slope measurement; (median, (max-min)/median)."""
    samples = samples or SAMPLES
    ps = sorted(per_fn() for _ in range(samples))
    med = ps[samples // 2]
    return med, (ps[-1] - ps[0]) / med


# emission order (headline LAST)
_METRIC_ORDER = [
    "stream_triad_gbs", "copy_stream_elems",
    "1d_stencil_unfused_cell_updates", "flash_attention_tflops",
    "flash_attention_bwd_tflops", "transformer_step_ms", "fft_1d_gflops",
    "1d_stencil_cell_updates",
]


_DEVICE = {}           # platform / device_kind / device_count stamp


def emit(metric, value, unit, vs_baseline, **extra):
    line = {"metric": metric, "value": round(value, 3), "unit": unit,
            "vs_baseline": round(vs_baseline, 3)}
    line.update(extra)
    line.update(_DEVICE)
    print(json.dumps(line), flush=True)


def bench_triad(jax, jnp):
    """Dispatch-level STREAM triad: b <- x + s*b, output donated."""
    m = 1 << 24

    @functools.partial(jax.jit, donate_argnums=(1,))
    def f(a, b):
        return a + jnp.float32(1e-7) * b

    x = jnp.asarray(np.random.default_rng(1).random(m, np.float32))
    b = jnp.asarray(np.random.default_rng(2).random(m, np.float32))
    b = f(x, b)
    _ = float(b[0])

    state = [b]

    def chain(k):
        bb = state[0]
        t0 = time.perf_counter()
        for _ in range(k):
            bb = f(x, bb)
        _ = float(bb[0])
        state[0] = bb
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 64, 640, repeats=5))
    gbs = 3 * m * 4 / per / 1e9
    emit("stream_triad_gbs", gbs, "GB/s", gbs / HBM_PEAK_GBS,
         spread=round(spread, 3))
    return gbs


def bench_stencil_unfused(jax, jnp, heat_step_best, copy_rate=None):
    """One heat step per dispatch: the HBM-bound per-step number (the
    blocked pallas kernel — ops/stencil.pallas_heat_step — which
    streams 8 B/cell where XLA's roll lowering moves ~4x that).
    `copy_rate` (elems/s of bench_copy_stream) adds the same-session
    normalized copy_ratio."""
    n = 1 << 24
    coef = jnp.float32(0.25)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(u):
        return heat_step_best(u, coef)

    u = jnp.asarray(np.random.default_rng(0).random(n, np.float32))
    u = step(u)
    _ = float(u[0])
    state = [u]

    def chain(k):
        uu = state[0]
        t0 = time.perf_counter()
        for _ in range(k):
            uu = step(uu)
        _ = float(uu[0])
        state[0] = uu
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 64, 640, repeats=5))
    cells = n / per
    roof = HBM_PEAK_GBS * 1e9 / 8.0          # read 4B + write 4B per cell
    extra = {}
    if copy_rate:
        # ratio vs the same-session copy stream: the drift-immune bar
        extra["copy_ratio"] = round(cells / copy_rate, 3)
    emit("1d_stencil_unfused_cell_updates", cells / 1e6, "Mcells/s",
         cells / roof, spread=round(spread, 3), **extra)
    return cells


def bench_vpu_rate(jax, jnp):
    """Empirical VPU elementwise-op rate: an in-VMEM FMA chain with the
    same shape/loop structure as the fused stencil kernel but ONE vector
    op per element per iteration. This measured rate is the compute roof
    the fused stencil is judged against."""
    import functools as ft

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = 1 << 17        # whole array + 8 temporaries must fit scoped VMEM
    steps = 1024

    def kernel(u_ref, c_ref, o_ref):
        c = c_ref[0]

        def one(_i, u):
            # 8 independent FMAs + a 7-add reduction tree: enough ILP
            # that the VPU pipelines stay full (a single serial FMA
            # chain measures instruction LATENCY, not throughput).
            # Coefficients differ by ~1e-9 so nothing CSEs, while the
            # iteration map stays u' ~ 0.9999*u + 1 (bounded).
            ys = [u * (c + j * 1e-9) + (c + j * 1e-9) for j in range(8)]
            s1 = (ys[0] + ys[1]) + (ys[2] + ys[3])
            s2 = (ys[4] + ys[5]) + (ys[6] + ys[7])
            return (s1 + s2) * jnp.float32(0.125 * 0.9999)
        o_ref[:] = jax.lax.fori_loop(0, steps, one, u_ref[:])

    @jax.jit
    def run(u):
        u2 = u.reshape(n // 128, 128)
        out = pl.pallas_call(
            kernel,
            out_shape=jax.ShapeDtypeStruct(u2.shape, u2.dtype),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        )(u2, jnp.asarray([0.9999999], jnp.float32))
        return out.reshape(n)

    u0 = jnp.asarray(np.random.default_rng(0).random(n, np.float32))
    u0 = run(u0)
    _ = float(u0[0])

    def chain(k):
        u = u0
        t0 = time.perf_counter()
        for _ in range(k):
            u = run(u)
        _ = float(u[0])
        return time.perf_counter() - t0

    per, _ = robust(lambda: slope_time(chain, 8, 72))
    return n * steps * 16 / per          # vector ops / s (8 FMA + 7 add
                                         # + 1 scale per element-iter)


# vector ops per cell-update in the fused pallas stencil kernel
# (ops/stencil._pallas_kernel): 2 lane rolls + 2 masked selects + 5
# arithmetic ops (mul, sub, add, mul, add)
_STENCIL_OPS_PER_CELL = 9.0


def bench_stencil_fused(jax, jnp, multistep):
    n = 1 << 19               # 512K cells: pallas in-VMEM path
    spd = 1024
    coef = jnp.float32(0.25)
    u0 = jnp.asarray(np.random.default_rng(0).random(n, np.float32))
    u0 = multistep(u0, coef, spd)
    _ = float(u0[0])

    def chain(k):
        u = u0
        t0 = time.perf_counter()
        for _ in range(k):
            u = multistep(u, coef, spd)
        _ = float(u[0])
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 8, 72))
    cells_per_s = n * spd / per
    hbm_roof = HBM_PEAK_GBS * 1e9 / 8.0
    return cells_per_s, hbm_roof, spread


def bench_attention(jax, jnp):
    from hpx_tpu.ops.attention_pallas import flash_attention
    B, S, N, H = 2, 4096, 8, 128
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, S, N, H), np.float32), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    f = jax.jit(functools.partial(flash_attention, causal=True))
    out = f(q, k, v)
    jax.block_until_ready(out)

    def chain(kk):
        qq = q
        t0 = time.perf_counter()
        for _ in range(kk):
            qq = f(qq, k, v)
        _ = float(qq[0, 0, 0, 0])
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 8, 48))
    flops = 4 * B * N * S * S * H * 0.5          # causal halves the work
    tf = flops / per / 1e12
    from hpx_tpu.ops.attention_pallas import resolve_blocks
    bq, bk = resolve_blocks(S, S, True)
    emit("flash_attention_tflops", tf, "TFLOP/s", tf * 1e12 / MXU_PEAK_BF16,
         shape=f"B{B} S{S} N{N} H{H} bf16 causal", spread=round(spread, 3),
         blocks=f"{bq}x{bk}")
    return tf


def bench_attention_bwd(jax, jnp):
    """Backward flash kernels (custom_vjp): time grad of sum(flash)
    w.r.t. (q, k, v). FLOP model: fwd 2 matmuls + bwd 5 matmuls per
    tile pair => total 3.5x the forward's 2; causal halves everything.
    Reported TFLOP/s covers the whole fwd+bwd step, which is what
    training sees; vs_baseline = that rate over MXU peak."""
    from hpx_tpu.ops.attention_pallas import flash_attention
    B, S, N, H = 2, 4096, 8, 128
    rng = np.random.default_rng(1)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, S, N, H), np.float32), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    dq, dk, dv = g(q, k, v)
    jax.block_until_ready((dq, dk, dv))

    def chain(kk):
        qq = q
        t0 = time.perf_counter()
        for _ in range(kk):
            dq, _dk, _dv = g(qq, k, v)
            qq = dq.astype(jnp.bfloat16)        # chain dependency
        _ = float(qq[0, 0, 0, 0])
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 4, 24))
    flops = 3.5 * 4 * B * N * S * S * H * 0.5
    tf = flops / per / 1e12
    emit("flash_attention_bwd_tflops", tf, "TFLOP/s",
         tf * 1e12 / MXU_PEAK_BF16,
         shape=f"B{B} S{S} N{N} H{H} bf16 causal fwd+bwd",
         spread=round(spread, 3))
    return tf


def bench_copy_stream(jax, jnp):
    """Pure HBM copy stream (read 4B + write 4B per element — the same
    traffic shape as one unfused stencil step). Its measured rate is the
    SAME-SESSION normalizer for the stencil: chip-to-chip drift hits
    both equally, so stencil/copy_ratio stays meaningful when absolute
    numbers swing (BASELINE.md)."""
    n = 1 << 24

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(u):
        # *c with c != 1: a real read->write pass XLA cannot alias away
        return u * jnp.float32(1.0000001)

    u = jnp.asarray(np.random.default_rng(3).random(n, np.float32))
    u = step(u)
    _ = float(u[0])
    state = [u]

    def chain(k):
        uu = state[0]
        t0 = time.perf_counter()
        for _ in range(k):
            uu = step(uu)
        _ = float(uu[0])
        state[0] = uu
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 64, 640, repeats=5))
    elems = n / per
    roof = HBM_PEAK_GBS * 1e9 / 8.0
    emit("copy_stream_elems", elems / 1e6, "Melem/s", elems / roof,
         spread=round(spread, 3))
    return elems


def bench_transformer(jax, jnp):
    from hpx_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab=32768, d_model=512, n_heads=8,
                                head_dim=64, n_layers=4, d_ff=2048,
                                lr=0.01, dtype=jnp.bfloat16)
    mesh1 = tfm.make_mesh_3d(1)
    params = tfm.shard_params(tfm.init_params(cfg, jax.random.PRNGKey(0)),
                              cfg, mesh1)
    step = tfm.make_train_step(cfg, mesh1)
    B, S = 8, 1024
    toks, tgts = tfm.sample_batch(cfg, batch=B, seq=S,
                                  key=jax.random.PRNGKey(1))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh1)
    params, l0 = step(params, toks, tgts)
    _ = float(l0)

    state = [params]

    def chain(k):
        p = state[0]
        t0 = time.perf_counter()
        loss = None
        for _ in range(k):
            p, loss = step(p, toks, tgts)
        _ = float(loss)
        state[0] = p
        return time.perf_counter() - t0

    per, spread = robust(lambda: slope_time(chain, 2, 10))
    # model flops: 6 * params * tokens (fwd+bwd) + attention term
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    attn_flops = 4 * B * cfg.n_heads * S * S * cfg.head_dim * \
        cfg.n_layers * 3 * 0.5            # qk^T+pv, fwd+2bwd, causal
    flops = 6 * n_params * B * S + attn_flops
    mfu = flops / per / MXU_PEAK_BF16
    emit("transformer_step_ms", per * 1e3, "ms", mfu,
         shape=f"L{cfg.n_layers} d{cfg.d_model} B{B} S{S} bf16",
         params=n_params, spread=round(spread, 3))
    return per


def bench_fft(jax, jnp):
    """Single-chip 1-D FFT through algo/fft's four-step program (the
    degenerate 1-device mesh exercises the same code path the
    distributed transform compiles). FLOP model: 5*n*log2(n). The
    vs_baseline roof is an HBM traffic model — the transform is
    bandwidth-bound at this size: ~3 read+write passes of 8 B/point
    (stage FFTs + twiddle fold; the on-device transpose copies are
    layout changes XLA mostly fuses)."""
    import math as _m

    from jax.sharding import Mesh
    from hpx_tpu.algo import fft as dfft

    n = 1 << 22                     # 32 MiB complex64
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    rng = np.random.default_rng(0)
    v = jnp.asarray((rng.standard_normal(n) + 1j * rng.standard_normal(n)
                     ).astype(np.complex64))

    norm = jax.jit(lambda x: jnp.float32(
        jnp.sum(jnp.abs(x).astype(jnp.float32))))
    y = dfft.fft_sharded(v, mesh)
    _ = float(norm(y))
    state = [y]

    def chain(k):
        x = state[0]
        t0 = time.perf_counter()
        for _ in range(k):
            # alternate directions so chained dispatches stay dependent
            # without the values blowing up
            x = dfft.ifft_sharded(dfft.fft_sharded(x, mesh), mesh)
        _ = float(norm(x))
        state[0] = x
        return time.perf_counter() - t0

    per2, spread = robust(lambda: slope_time(chain, 8, 40))
    per = per2 / 2.0                 # one transform
    gflops = 5 * n * _m.log2(n) / per / 1e9
    roof_time = 6 * n * 8 / (HBM_PEAK_GBS * 1e9)
    emit("fft_1d_gflops", gflops, "GFLOP/s", roof_time / per,
         n=n, spread=round(spread, 3))
    return gflops


def _bench_main(only, trace_out=None) -> None:
    """The measurements, in this process, on the chip or not at all."""
    import jax
    import jax.numpy as jnp

    from hpx_tpu.ops.stencil import heat_step_best, multistep
    from hpx_tpu.utils.compile_cache import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench.py measures on a TPU; jax found platform "
              f"{dev.platform!r} ({dev.device_kind}) — no metric "
              "measured", file=sys.stderr)
        sys.exit(1)
    enable_compile_cache()
    _DEVICE.update(platform=dev.platform, device_kind=dev.device_kind,
                   device_count=len(jax.devices()))

    # --trace-out: run everything under the causal tracer and write
    # Chrome trace JSON next to the bench result at the end.
    tracer = None
    if trace_out:
        from hpx_tpu.core.config import runtime_config
        from hpx_tpu.svc import tracing
        runtime_config().set("hpx.trace.enabled", "1")
        tracer = tracing.start_if_configured()

    def want(name):
        return not only or name in only

    if want("stream_triad_gbs"):
        bench_triad(jax, jnp)
    copy_rate = None
    if want("copy_stream_elems") or \
            want("1d_stencil_unfused_cell_updates"):
        # the copy stream is the unfused stencil's same-session
        # normalizer, so it rides along with it
        copy_rate = bench_copy_stream(jax, jnp)
    if want("1d_stencil_unfused_cell_updates"):
        bench_stencil_unfused(jax, jnp, heat_step_best,
                              copy_rate=copy_rate)
    if want("flash_attention_tflops"):
        bench_attention(jax, jnp)
    if want("flash_attention_bwd_tflops"):
        bench_attention_bwd(jax, jnp)
    if want("transformer_step_ms"):
        bench_transformer(jax, jnp)
    if want("fft_1d_gflops"):
        bench_fft(jax, jnp)

    if want("1d_stencil_cell_updates"):
        vpu_rate = bench_vpu_rate(jax, jnp)
        cells_per_s, hbm_roof, spread = bench_stencil_fused(jax, jnp,
                                                            multistep)
        # headline LAST. The honest roof for the VMEM-resident kernel
        # is COMPUTE: the empirically measured VPU op rate divided by
        # the kernel's 9 vector ops per cell-update. The unfused-HBM
        # ratio is kept for continuity.
        emit("1d_stencil_cell_updates", cells_per_s / 1e6, "Mcells/s",
             cells_per_s * _STENCIL_OPS_PER_CELL / vpu_rate,
             x_vs_unfused_hbm_roof=round(cells_per_s / hbm_roof, 3),
             vpu_rate_gops=round(vpu_rate / 1e9, 1),
             spread=round(spread, 3))

    if tracer is not None:
        from hpx_tpu.svc import tracing
        tracing.stop_tracing()
        doc = tracer.export(trace_out)
        print(f"# trace written: {trace_out} "
              f"({len(doc['traceEvents'])} events, "
              f"{doc['otherData']['dropped_events']} dropped)",
              file=sys.stderr)

    # --metrics-out: dump the registered-counter plane (histograms as
    # mergeable snapshots) as a hpx_tpu.metrics.v1 artifact at the end.
    metrics_out = os.environ.get(_METRICS_ENV)
    if metrics_out:
        from hpx_tpu.svc import metrics as svc_metrics
        reg = svc_metrics.registry_snapshot("*")
        doc = {"schema": "hpx_tpu.metrics.v1",
               "histograms": {n: {"snapshot": s}
                              for n, s in reg["histograms"].items()},
               "counters": reg["counters"]}
        tmp = f"{metrics_out}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, metrics_out)
        print(f"# metrics written: {metrics_out} "
              f"({len(doc['counters'])} counters, "
              f"{len(doc['histograms'])} histograms)",
              file=sys.stderr)


# --metrics-out rides an env var: _run_slo_gate reads the artifact's
# path from it (tests drive the gate that way)
_METRICS_ENV = "_HPX_BENCH_METRICS_OUT"


def _run_slo_gate(baseline: str) -> None:
    """--baseline: gate this round's --metrics-out artifact against a
    previous round's with benchmarks/slo_gate.py (bounded-error
    quantile comparison). Verdicts go to stderr — stdout stays a pure
    metric stream — and a regression exits 1."""
    cand = os.environ.get(_METRICS_ENV)
    if not cand or not os.path.exists(cand):
        print("# --baseline given but no --metrics-out artifact to "
              "gate; skipped", file=sys.stderr)
        return
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "benchmarks"))
    import slo_gate
    try:
        verdicts = slo_gate.compare(slo_gate.load_artifact(baseline),
                                    slo_gate.load_artifact(cand))
    except (OSError, ValueError) as e:
        print(f"# slo gate unreadable input: {e}", file=sys.stderr)
        return
    print(slo_gate.render_text(verdicts), file=sys.stderr)
    if slo_gate.regressions(verdicts):
        sys.exit(1)


def main() -> None:
    trace_out = os.path.abspath(
        sys.argv[sys.argv.index("--trace-out") + 1]) \
        if "--trace-out" in sys.argv else None
    if "--metrics-out" in sys.argv:
        os.environ[_METRICS_ENV] = os.path.abspath(
            sys.argv[sys.argv.index("--metrics-out") + 1])
    baseline = os.path.abspath(
        sys.argv[sys.argv.index("--baseline") + 1]) \
        if "--baseline" in sys.argv else None

    # HPX_BENCH_ONLY=m1,m2 measures just those metrics (chip time is
    # budgeted; a typo must not read as "nothing to measure")
    only = {m.strip() for m in
            os.environ.get("HPX_BENCH_ONLY", "").split(",") if m.strip()}
    unknown = only - set(_METRIC_ORDER)
    if unknown:
        print(f"HPX_BENCH_ONLY names unknown metrics {sorted(unknown)}; "
              f"known: {_METRIC_ORDER}", file=sys.stderr)
        sys.exit(2)

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    _bench_main(only, trace_out)
    if baseline:
        _run_slo_gate(baseline)


if __name__ == "__main__":
    main()
