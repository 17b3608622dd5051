import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
REHEARSE = {"sc2-3b.gen-closed": os.path.join(HERE, "rehearse_serve.json"),
            "hpx-stencil.dataflow-coarse":
                os.path.join(HERE, "rehearse_hpx.json")}
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def run_cell(workload, *, seed=5, seconds=1.0, trace=0, rehearse=None,
             root=ROOT, timeout=600):
    """The benchmark's own command, as the driver runs it."""
    cmd = [sys.executable] + bench(root)["command"][1:] + [
        "--workload", workload, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", str(trace)]
    if rehearse:
        cmd += ["--rehearse", rehearse]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=timeout)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def in_process_ctx(workload, rehearse, *, seed=5, seconds=0.5, trace=False):
    """A run's context without the harness's look for a chip."""
    import time
    import jax
    from chipbench import harness
    ctx = harness.Context(workload, seed, seconds, trace,
                          time.perf_counter(), rehearse=rehearse)
    ctx.devices, ctx.peaks = jax.devices(), None
    return ctx
