"""Driver of the HPX dataflow cells: `stencil1d.stencil_dataflow` and
`gather_dataflow_result`, unchanged, DAG after DAG.

Set-up: the initial field made on the device from --seed, `warm_dags`
DAGs (they compile heat_part, the halo slices, the gather). The window
closes at the end of the DAG that crosses --seconds. Every DAG's
gathered field is sampled on the device (partition seams and seeded
points); once the window has closed the samples are compared with the
NumPy recurrence of chipbench/reference/stencil.py.
"""

from __future__ import annotations

import numpy as np

from chipbench import opcount
from chipbench.harness import seed_key


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from hpx_tpu.exec.tpu import TpuExecutor
    from hpx_tpu.models import stencil1d
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    g = ctx.generator()
    ref = ctx.reference()
    p = stencil1d.StencilParams(nx=g.nx, np_=g.np_, nt=g.nt, k=conf["k"],
                                dt=conf["dt"], dx=conf["dx"])
    total = g.total
    points = ref.sample_points(total, g.nx, g.sample_points, ctx.seed)
    ex = TpuExecutor()

    def one_dag():
        t = ctx.clock()
        with ctx.span("bench.dag_build"):
            futs = stencil1d.stencil_dataflow(p, ex, u0)
        build_s = ctx.clock() - t
        with ctx.span("bench.gather"):
            out = stencil1d.gather_dataflow_result(futs)
            sample = take(out, idx)
            ready = all(f.is_ready() and not f.has_exception()
                        for f in futs)
            out.block_until_ready()
        return sample, ready, build_s

    with count_compiles() as setup_c:
        u0 = jax.jit(lambda k: jax.random.uniform(
            k, (total,), jnp.float32))(seed_key(ctx.seed))
        idx = jnp.asarray(points.astype(np.int32))
        take = jax.jit(lambda u, i: u[i])
        for _ in range(g.warm_dags):
            one_dag()
        t_open = ctx.clock()
    setup_s = ctx.setup_seconds(t_open)
    ctx.say(phase="setup", setup_s=setup_s,
            devices_ready_s=ctx.devices_ready_s, fresh_compiles=int(setup_c),
            cache_hits=setup_c.hits, nx=g.nx, np=g.np_, nt=g.nt)

    t_from = int(traffic.get("trace_after_dags", 1))
    t_n = int(traffic.get("trace_dags", 4))
    samples, not_ready, build_s, traced_dags, dag_ends = [], 0, 0.0, 0, []
    with count_compiles() as win_c:
        while True:
            n = len(samples)
            if ctx.trace and n == t_from:
                ctx.trace_start()
            sample, ready, b = one_dag()
            samples.append(sample)
            not_ready += 0 if ready else 1
            build_s += b
            if ctx.trace and t_from <= n < t_from + t_n:
                traced_dags += 1
                if n == t_from + t_n - 1:
                    ctx.trace_stop()
            t_close = ctx.clock()
            dag_ends.append(t_close)
            if t_close - t_open >= ctx.seconds and \
                    not (ctx.trace and n < t_from + t_n - 1):
                break
    window_s = t_close - t_open
    dags = len(samples)
    cells = opcount.stencil_dag_cells(g.nx, g.np_, g.nt)
    ctx.say(phase="window", window_s=window_s, dags=dags,
            nodes=dags * g.np_ * g.nt, window_compiles=int(win_c),
            dispatches=TpuExecutor.dispatch_count,
            **ctx.stalls(dag_ends, t_open))
    end_to_end = {"setup_s": setup_s,
                  "mcells_s": cells * dags / window_s / 1e6}
    counters = {
        "node_host_us": 1e6 * build_s / (dags * g.np_ * g.nt),
        "traced_dags": traced_dags,
        "traced_bytes": traced_dags * opcount.stencil_dag_bytes(
            g.nx, g.np_, g.nt),
    }

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    widx = ref.window_index(points, g.nt, total)
    windows = np.asarray(jax.device_get(
        take(u0, jnp.asarray(widx.astype(np.int32)))))
    got = np.stack([np.asarray(s) for s in jax.device_get(samples)])
    del u0
    want = ref.recurrence(windows, p.coef, g.nt)
    err = float(np.abs(got - want[None, :]).max())
    moved = float(np.abs(want - windows[:, g.nt]).max())
    ctx.say(phase="reference", dags_compared=dags, points=int(points.size),
            field_err_max=err, field_moved_max=moved)
    checks = [("window_compiles", int(win_c), 0),
              ("dags_not_ready", not_ready, 0),
              ("field_err_max", err, conf["tolerance_abs"])]
    return {"end_to_end": end_to_end, "counters": counters, "checks": checks,
            "attempted": dags, "failed": not_ready,
            "memory_peak_bytes": memory_peak,
            "control_inputs": (windows, want, p.coef, g.nt)}


def control(ctx, outcome) -> dict:
    """The CONTROL's reading: the recurrence with every operation
    rounded to bfloat16, in the program's place."""
    windows, want, coef, nt = outcome["control_inputs"]
    ref = ctx.reference()
    got = ref.recurrence(windows, coef, nt, dtype=ref.bfloat16())
    return {"checks": {"field_err_max": float(np.abs(got - want).max())}}
