"""Per-program continuous profiler (svc/progprof): the cached_program
build hook, the callable proxy's per-call histogram (the hold of each
call), the /programs{...} counter namespace, the profile_table fold,
the memory watermark, and the <2% overhead contract asserted by
call-count accounting (the proxy adds exactly one perf_counter pair
and one histogram record per call — never an extra compile or an
extra execution).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.core import programs as core_programs
from hpx_tpu.core.config import runtime_config
from hpx_tpu.svc import performance_counters as pc
from hpx_tpu.svc import progprof
from hpx_tpu.utils.compilemon import count_compiles


@pytest.fixture()
def profiler():
    """An installed profiler (no memory thread — tests sample
    directly), torn down even on failure so the module hook never
    leaks into other tests."""
    prof = progprof.start_profiling(sample_memory=False)
    try:
        yield prof
    finally:
        progprof.stop_profiling()


def _demo_cache_and_build(tag="demo"):
    cache = {}
    key = (tag, 8)

    def build():
        return jax.jit(lambda x: (x * 2.0 + 1.0).sum())

    return cache, key, build


# ---------------------------------------------------------------------------
# hook mechanics
# ---------------------------------------------------------------------------


def test_miss_wraps_hit_returns_same_proxy(profiler):
    cache, key, build = _demo_cache_and_build()
    p1 = core_programs.cached_program(cache, key, build)
    p2 = core_programs.cached_program(cache, key, build)
    assert p1 is p2                      # hit returns the stored proxy
    assert isinstance(p1, progprof._ProfiledProgram)
    (rec,) = profiler.records()
    assert rec.compiles == 1 and rec.compile_s > 0.0
    # passthrough: jit attributes still reachable through the proxy
    assert callable(p1.lower)


def test_no_profiler_no_wrapping():
    assert progprof.active_profiler() is None
    cache, key, build = _demo_cache_and_build()
    p = core_programs.cached_program(cache, key, build)
    assert not isinstance(p, progprof._ProfiledProgram)
    assert float(p(jnp.ones((8,)))) == pytest.approx(24.0)


def test_non_callable_build_product_passes_through(profiler):
    cache = {}
    plan = ("plan", 1, 2)
    out = core_programs.cached_program(cache, ("k",), lambda: plan)
    assert out is plan
    assert profiler.records() == []      # nothing to time per-call


# ---------------------------------------------------------------------------
# per-call accounting + overhead contract
# ---------------------------------------------------------------------------


def test_call_count_accounting_zero_extra_compiles(profiler):
    """The <2% overhead claim reduces to an exact accounting claim:
    N warm calls through the proxy cost N histogram records and ZERO
    additional compiles or executions — the proxy never re-traces,
    re-lowers, or double-calls the underlying program."""
    cache, key, build = _demo_cache_and_build()
    x = jnp.ones((8,))
    prog = core_programs.cached_program(cache, key, build)
    prog(x)                              # cold: compile
    (rec,) = profiler.records()
    warm0 = rec.calls
    n = 25
    with count_compiles() as c:
        for _ in range(n):
            prog(x)
    assert c.count == 0                  # zero extra compiles warm
    assert rec.calls == warm0 + n        # exactly one record per call
    assert rec.compiles == 1             # one build, ever
    assert rec.exec_hist.count == rec.calls
    assert rec.exec_hist.sum > 0.0


def test_results_identical_through_proxy(profiler):
    cache, key, build = _demo_cache_and_build()
    x = jnp.arange(8, dtype=jnp.float32)
    prog = core_programs.cached_program(cache, key, build)
    want = float(jax.jit(lambda x: (x * 2.0 + 1.0).sum())(x))
    assert float(prog(x)) == pytest.approx(want)


# ---------------------------------------------------------------------------
# counter namespace
# ---------------------------------------------------------------------------


def test_programs_counter_namespace(profiler):
    cache, key, build = _demo_cache_and_build()
    prog = core_programs.cached_program(cache, key, build)
    for _ in range(4):
        prog(jnp.ones((8,)))
    names = pc.discover_counters("/programs{locality#*/*}/*")
    # per-program planes + process-wide memory watermarks
    assert any(n.endswith("/time/execute-s") for n in names)
    assert any("/time/execute-s/p99" in n for n in names)
    assert any(n.endswith("/memory/hbm-peak-bytes") for n in names)
    calls = pc.query_counter(
        "/programs{locality#0/demo#0}/count/calls").value
    assert calls == 4.0
    compile_s = pc.query_counter(
        "/programs{locality#0/demo#0}/time/compile-s").value
    assert compile_s > 0.0


def test_counters_unregistered_on_stop():
    prof = progprof.start_profiling(sample_memory=False)
    cache, key, build = _demo_cache_and_build()
    core_programs.cached_program(cache, key, build)(jnp.ones((8,)))
    assert pc.discover_counters("/programs{locality#*/*}/*")
    progprof.stop_profiling()
    assert pc.discover_counters("/programs{locality#*/*}/*") == []
    assert core_programs.profile_hook() is None
    assert prof.records()                # table still readable after


# ---------------------------------------------------------------------------
# profile_table fold
# ---------------------------------------------------------------------------


def test_profile_table_shape_and_order(profiler):
    import json
    cache = {}
    fast = core_programs.cached_program(
        cache, ("fast", 1), lambda: jax.jit(lambda x: x + 1.0))
    slow = core_programs.cached_program(
        cache, ("slow", 1),
        lambda: jax.jit(lambda x: jnp.sort(x * 2.0)))
    x = jnp.ones((64,))
    fast(x)
    for _ in range(10):
        slow(x)
    table = profiler.profile_table()
    assert table["schema"] == progprof.PROFILE_SCHEMA
    assert set(table["memory"]) == {"hbm_peak_bytes",
                                    "host_peak_bytes", "samples"}
    rows = table["programs"]
    totals = [r["total_s"] for r in rows]
    assert totals == sorted(totals, reverse=True)   # busiest first
    by_key = {r["key"]: r for r in rows}
    assert by_key["slow"]["calls"] == 10
    assert by_key["fast"]["calls"] == 1
    for r in rows:
        assert r["p99_s"] >= r["p50_s"] >= 0.0
        assert 0.0 < r["relative_error_bound"] < 0.1
        assert r["mean_s"] * r["calls"] == pytest.approx(r["total_s"])
    json.dumps(table)                    # JSON-safe, whole fold
    # module-level accessor answers the same fold while active
    assert progprof.profile_table()["schema"] == \
        progprof.PROFILE_SCHEMA


def test_module_profile_table_none_when_inactive():
    assert progprof.active_profiler() is None
    assert progprof.profile_table() is None


# ---------------------------------------------------------------------------
# lifecycle + config gate
# ---------------------------------------------------------------------------


def test_double_start_raises(profiler):
    with pytest.raises(RuntimeError):
        progprof.start_profiling()


def test_start_if_configured_gate():
    cfg = runtime_config()
    assert not cfg.get_bool("hpx.prof.programs", False)
    assert progprof.start_if_configured() is None
    cfg.set("hpx.prof.programs", "1")
    try:
        prof = progprof.start_if_configured()
        assert prof is not None
        assert progprof.start_if_configured() is prof   # idempotent
    finally:
        progprof.stop_profiling()
        cfg.set("hpx.prof.programs", "0")


# ---------------------------------------------------------------------------
# memory watermark
# ---------------------------------------------------------------------------


def test_memory_watermark_direct_sample():
    wm = progprof.MemoryWatermark()
    wm.sample()
    snap = wm.snapshot()
    assert snap["samples"] == 1
    assert snap["host_peak_bytes"] > 0           # procfs RSS
    assert snap["hbm_peak_bytes"] >= 0
    # high-water-mark: a second sample never lowers the peaks
    wm.sample()
    assert wm.host_peak_bytes >= snap["host_peak_bytes"]


def test_memory_watermark_thread_lifecycle():
    wm = progprof.MemoryWatermark(interval_s=0.002)
    wm.start()
    import time
    deadline = time.time() + 2.0
    while wm.samples == 0 and time.time() < deadline:
        time.sleep(0.005)
    wm.stop()
    assert wm.samples > 0
    assert wm._thread is None
    wm.stop()                                    # idempotent


# ---------------------------------------------------------------------------
# end-to-end: the real serving stack funnels through the hook
# ---------------------------------------------------------------------------


def test_serving_programs_profiled(profiler):
    """ContinuousServer's programs all flow through cached_program, so
    a fresh config's compiles land in the profiler (fresh d_ff keeps
    the shared transformer cache cold for this test)."""
    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                head_dim=8, n_layers=2, d_ff=44)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    srv = ContinuousServer(params, cfg, slots=2, smax=64)
    srv.submit([3, 1, 4, 1, 5], max_new=6)
    srv.submit([2, 7], max_new=4)
    out = srv.run()
    assert len(out) == 2
    rows = profiler.profile_table()["programs"]
    assert rows, "serving compiled no profiled programs"
    assert all(r["calls"] >= 1 for r in rows)
    labels = {r["key"] for r in rows}
    assert labels, labels
