"""The program's spans, reduced: on a synthetic trace whose self times,
sync count and idle shares are known by construction, and on real
profiler traces made on the CPU with and without such spans."""

import glob
import os

import pytest

from chipbench import harness, span_reduce as sr
from chipbench.tests import helpers as h

MS = 1e6
SERVE, HPX = "sc2-3b.gen-closed", "hpx-stencil.dataflow-coarse"
NEW = {SERVE: ["host_self_ms.serve", "host_syncs_per_step",
               "idle_flush_pct.serve", "idle_admit_pct.serve"],
       HPX: ["node_sched_us", "node_dispatch_us"]}


def serving_raw():
    """A 100 ms window, thread 1: step A 0..40 = admit 2..12 (first
    token read 6..10) + decode 14..38 (flush 20..36 = wait 22..32 +
    retire 33..34); step B 50..90 = prefill tick 52..58 (chunk 53..57) +
    decode 60..70; the harness's own flush 92..98 (wait 93..97).
    Thread 2, meanwhile: one dataflow node, which belongs to no step."""
    t1 = [("serving.step", 0, 40), ("serving.admit", 2, 10),
          ("serving.first_token.wait", 6, 4), ("serving.decode", 14, 24),
          ("serving.flush", 20, 16), ("serving.flush.wait", 22, 10),
          ("serving.retire", 33, 1),
          ("serving.step", 50, 40), ("serving.prefill_tick", 52, 6),
          ("serving.prefill_chunk", 53, 4), ("serving.decode", 60, 10),
          ("serving.flush", 92, 6), ("serving.flush.wait", 93, 4)]
    t2 = [("hpx.dataflow.node", 10, 10), ("hpx.dataflow.body", 12, 6),
          ("hpx.exec.dispatch", 13, 4)]
    spans = [[n, s * MS, d * MS, 1] for n, s, d in t1] + \
            [[n, s * MS, d * MS, 2] for n, s, d in t2]
    # outside the window: never counted
    spans.append(["serving.step", 101 * MS, 5 * MS, 1])
    return {"window": [0.0, 100 * MS], "spans": spans}


def device_trace():
    """The device idles 7..9 (under the first-token read), 24..30 (under
    the flush's wait), 42..44 (under bench.step alone), 46..48 (under no
    span), 54..56 (under the prefill chunk), 62..66 (under decode's own
    code) and 99..100: 19 ms of 100."""
    busy = [(0, 7), (9, 24), (30, 42), (44, 46), (48, 54), (56, 62),
            (66, 99)]
    ops = [["fusion.1", a * MS, (b - a) * MS] for a, b in busy]
    host = [["bench.trace_window", 0.0, 100 * MS],
            ["bench.step", 0.0, 45 * MS], ["bench.step", 50 * MS, 41 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": []}], "host": host}


def test_self_time_is_duration_less_children_on_the_same_thread():
    spans = sr.nest(serving_raw())
    assert len(spans) == 16                 # the one past the window is out
    rows = sr.by_name(spans)
    # step A 40 - (10 + 24), step B 40 - (6 + 10)
    assert rows["serving.step"] == [2, pytest.approx(0.080),
                                    pytest.approx(0.006 + 0.024)]
    assert rows["serving.admit"][2] == pytest.approx(0.006)
    # decode A 24 - 16, decode B 10 - 0
    assert rows["serving.decode"][2] == pytest.approx(0.008 + 0.010)
    # flush 16 - (10 + 1), and the harness's 6 - 4
    assert rows["serving.flush"][2] == pytest.approx(0.005 + 0.002)
    # thread 2 runs meanwhile and nests under nothing of thread 1
    node = [sp for sp in spans if sp.name == "hpx.dataflow.node"][0]
    assert node.path == ("hpx.dataflow.node",)
    wait = [sp for sp in spans if sp.name == "serving.flush.wait"][0]
    assert wait.path == ("serving.step", "serving.decode", "serving.flush",
                         "serving.flush.wait")
    assert sum(sp.self_ns for sp in spans if sp.thread == 1) == \
        pytest.approx((40 + 40 + 6) * MS)


def test_host_self_and_syncs_a_step():
    spans = sr.nest(serving_raw())
    # two steps of 40 ms, 4 + 10 ms of them inside blocking reads
    assert sr.host_self_ms(spans) == pytest.approx(33.0)
    # three blocking reads (one outside any step) over two steps
    assert sr.syncs_per_step(spans) == pytest.approx(1.5)
    no_step = [sp for sp in spans if sp.name != sr.STEP]
    assert sr.host_self_ms(no_step) is None
    assert sr.syncs_per_step(no_step) is None


def test_idle_gaps_are_named_by_the_program_span_they_lie_under():
    trace, spans = device_trace(), sr.nest(serving_raw())
    gaps = dict(sr.idle_by_path(trace, spans))
    flush = "serving.step/serving.decode/serving.flush/serving.flush.wait"
    assert gaps[flush] == pytest.approx(0.006)
    assert gaps["serving.step/serving.admit/serving.first_token.wait"] == \
        pytest.approx(0.002)
    assert gaps["serving.step/serving.prefill_tick/serving.prefill_chunk"] \
        == pytest.approx(0.002)
    assert gaps["serving.step/serving.decode"] == pytest.approx(0.004)
    assert gaps["bench.step"] == pytest.approx(0.002)
    assert gaps["outside_spans"] == pytest.approx(0.003)
    assert sum(gaps.values()) == pytest.approx(0.019)
    flush_pct = sr.idle_pct_inside(trace, spans, ("serving.flush",))
    admit_pct = sr.idle_pct_inside(
        trace, spans, ("serving.admit", "serving.prefill_tick"))
    assert flush_pct == pytest.approx(6.0)
    assert admit_pct == pytest.approx(4.0)
    from chipbench import trace_reduce
    assert flush_pct + admit_pct <= trace_reduce.idle_pct(trace)
    # nothing to read -> nothing, never 0
    assert sr.idle_pct_inside(None, spans, ("serving.flush",)) is None
    assert sr.idle_pct_inside(trace, None, ("serving.flush",)) is None
    assert sr.idle_pct_inside(trace, [], ("serving.flush",)) is None


def test_node_time_splits_into_bookkeeping_and_dispatch():
    """Node 0..10 (body 2..8, dispatch 3..7); node 20..34 whose body
    22..26 (dispatch 23..25) readies a dependent that fires at once
    inside it: node 27..33 (body 28..32, dispatch 29..31); one dispatch
    of no node (a gather)."""
    names = (sr.NODE, sr.BODY, sr.DISPATCH)
    rows = [(0, 0, 10), (1, 2, 6), (2, 3, 4), (0, 20, 14), (1, 22, 4),
            (2, 23, 2), (0, 27, 6), (1, 28, 4), (2, 29, 2), (2, 40, 5)]
    raw = {"window": None,
           "spans": [[names[k], s * MS, d * MS, 7] for k, s, d in rows]}
    sched, sent, nodes = sr.node_us(sr.nest(raw))
    assert nodes == 3
    assert sched == pytest.approx((24 - 14) * 1e3 / 3)
    assert sent == pytest.approx((4 + 2 + 2) * 1e3 / 3)
    serving_only = [sp for sp in sr.nest(serving_raw()) if sp.thread == 1]
    assert sr.node_us(serving_only) is None


class Ctx:
    trace, peaks = True, None

    def __init__(self, cell):
        self.cell = {"name": cell}


def _profile(tmp_path, monkeypatch, cell, body):
    """A real `.xplane.pb` where the harness would have left it."""
    import jax
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    jax.profiler.start_trace(os.path.join(str(tmp_path), "trace-" + cell))
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            body()
    finally:
        jax.profiler.stop_trace()
    assert glob.glob(os.path.join(str(tmp_path), "trace-" + cell, "plugins",
                                  "profile", "*", "*.xplane.pb"))


@pytest.mark.parametrize("cell", [SERVE, HPX])
def test_every_new_reader_reads_nothing_from_a_trace_without_spans(
        cell, tmp_path, monkeypatch):
    import jax.numpy as jnp
    _profile(tmp_path, monkeypatch, cell,
             lambda: jnp.ones(8).block_until_ready())
    assert sr.of_run(Ctx(cell)) is None
    for name in NEW[cell]:
        reader = harness.load_by_path(f"chipbench/layers/{name}.py")
        assert reader.read(device_trace(), {}, Ctx(cell)) is None
        assert reader.read(None, {}, Ctx(cell)) is None


def test_readers_read_the_programs_spans_from_a_real_trace(
        tmp_path, monkeypatch):
    from hpx_tpu.svc import tracing

    def body():
        for n in (1, 2):
            with tracing.span("serving.step", "serving", n=n):
                with tracing.span("serving.flush", "serving"):
                    with tracing.span("serving.flush.wait", "serving"):
                        pass
    _profile(tmp_path, monkeypatch, SERVE, body)
    spans = sr.of_run(Ctx(SERVE))
    assert [sp.name for sp in spans].count("serving.step") == 2
    read = {name: harness.load_by_path(f"chipbench/layers/{name}.py").read(
        None, {}, Ctx(SERVE)) for name in NEW[SERVE]}
    assert read["host_syncs_per_step"] == 1.0
    assert read["host_self_ms.serve"] > 0
    # the idle shares need the device's plane, which a CPU has not
    assert read["idle_flush_pct.serve"] is None
    assert read["idle_admit_pct.serve"] is None
    untraced = Ctx(SERVE)
    untraced.trace = False
    assert sr.of_run(untraced) is None


def test_the_six_entries_name_their_cells():
    per = {m["name"]: m for m in h.bench()["per_layer"]}
    for cell, names in NEW.items():
        for name in names:
            assert per[name]["workloads"] == [cell]
            assert per[name]["source"] == "program_span"
