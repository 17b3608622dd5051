"""The step's account out of a trace: the six readers on a hand-built
span list and device ops whose held, work and idle shares are known by
construction; and on a real profiler trace made on the CPU."""

import json
import os

import pytest

from chipbench import harness, span_reduce as sr, step_reduce as st
from chipbench import trace_reduce

MS = 1e6
SERVE = "sc2-3b.gen-closed"
NEW = ["host_held_ms.serve", "host_work_ms.serve", "dispatches_per_step",
       "dev_programs_per_step", "idle_dispatch_pct.serve",
       "idle_operands_pct.serve"]
CELLS = ["sc2-3b.gen-closed", "laguna-xs2.mixed-closed",
         "kimi-linear.reason-closed", "deepseek-v2.docqa-closed"]


def raw():
    """A 100 ms window, thread 1. Step A 0..40: admit 2..12 holding a
    chunk's dispatch 3..6 and a probe's 7..9; decode 14..38 = operands
    15..19, the step's dispatch 19..24, a retirement 24..26, flush
    27..37 with its wait 28..36. Step B 50..90: decode 52..80 =
    operands 53..61, dispatch 61..63, a retirement 63..64, first-token
    wait 65..70. A dispatch of no step at 94..96 (the harness's own
    call), and on thread 2 a span that is nobody's innermost here."""
    t1 = [("serving.step", 0, 40), ("serving.admit", 2, 10),
          ("serving.dispatch", 3, 3), ("serving.dispatch", 7, 2),
          ("serving.decode", 14, 24), ("serving.decode.operands", 15, 4),
          ("serving.dispatch", 19, 5), ("serving.retire", 24, 2),
          ("serving.flush", 27, 10), ("serving.flush.wait", 28, 8),
          ("serving.step", 50, 40), ("serving.decode", 52, 28),
          ("serving.decode.operands", 53, 8), ("serving.dispatch", 61, 2),
          ("serving.retire", 63, 1),
          ("serving.first_token.wait", 65, 5),
          ("serving.dispatch", 94, 2)]
    spans = [[n, s * MS, d * MS, 1] for n, s, d in t1]
    spans.append(["hpx.exec.dispatch", 0.0, 100 * MS, 2])
    return {"window": [0.0, 100 * MS], "spans": spans}


def device():
    """Busy but for: 4..5 (inside the chunk's dispatch), 16..21 (16..19
    = 3 under operands, 19..21 = 2 under the step's dispatch), 25..30
    (1 under the retirement, 1 under decode, 1 under flush, 2 under its
    wait), 44..46 (no program span), 58..62 (3 under operands, 1 under
    dispatch), 95..97 (1 under the stray dispatch, 1 under none): 19 ms
    of 100. Eleven module runs, one of them half outside the window."""
    busy = [(0, 4), (5, 16), (21, 25), (30, 44), (46, 58), (62, 95),
            (97, 100)]
    ops = [["fusion.1", a * MS, (b - a) * MS] for a, b in busy]
    modules = [["jit_step(1)", 10 * k * MS, 5 * MS] for k in range(10)] + \
              [["jit_chunk(2)", 98 * MS, 5 * MS]]
    host = [["bench.trace_window", 0.0, 100 * MS],
            ["bench.step", 0.0, 45 * MS], ["bench.step", 50 * MS, 41 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "host": host}


def test_held_and_work_add_up_to_host_self():
    spans = sr.nest(raw())
    # dispatch inside steps: 3 + 2 + 5 + 2 over two steps; the stray
    # one belongs to no step
    assert st.host_held_ms(spans) == pytest.approx(6.0)
    assert st.dispatches_per_step(spans) == pytest.approx(2.0)
    # two steps of 40 ms, 8 + 5 ms of them waiting: 33.5 a step
    assert sr.host_self_ms(spans) == pytest.approx(33.5)
    assert st.host_work_ms(spans) == pytest.approx(27.5)
    assert st.host_work_ms(spans) + st.host_held_ms(spans) == \
        pytest.approx(sr.host_self_ms(spans))


def test_module_runs_a_step():
    spans = sr.nest(raw())
    assert st.dev_programs_per_step(device(), spans) == pytest.approx(5.5)
    two = device()
    two["devices"].append(dict(two["devices"][0], name="/device:TPU:1"))
    assert st.dev_programs_per_step(two, spans) == pytest.approx(5.5)


def test_the_innermost_span_is_found_by_one_sweep():
    segs = st.innermost(sr.nest(raw()))
    assert [(a / MS, b / MS, n) for a, b, n in segs][:9] == [
        (0, 2, "serving.step"), (2, 3, "serving.admit"),
        (3, 6, "serving.dispatch"), (6, 7, "serving.admit"),
        (7, 9, "serving.dispatch"), (9, 12, "serving.admit"),
        (12, 14, "serving.step"), (14, 15, "serving.decode"),
        (15, 19, "serving.decode.operands")]
    # disjoint, in order, and covering exactly what thread 1's outermost
    # spans cover: 40 + 40 + 2 ms; thread 2's span is nobody's here
    assert all(a < b for a, b, _ in segs)
    assert all(x[1] <= y[0] for x, y in zip(segs, segs[1:]))
    assert sum(b - a for a, b, _ in segs) == pytest.approx(82 * MS)
    assert "hpx.exec.dispatch" not in {n for _, _, n in segs}


def test_an_idle_gap_is_split_over_the_spans_it_overlaps():
    trace, spans = device(), sr.nest(raw())
    idle = st.idle_by_innermost(trace, spans)
    want = {"serving.dispatch": 1 + 2 + 1 + 1,
            "serving.decode.operands": 3 + 3,
            "serving.retire": 1, "serving.decode": 1,
            "serving.flush": 1, "serving.flush.wait": 2,
            "outside_spans": 2 + 1}
    assert {k: v for k, v in idle.items() if v} == {
        k: pytest.approx(v / 1e3) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(0.019)
    assert sum(idle.values()) == pytest.approx(
        trace_reduce.idle_pct(trace) / 100 * 0.1)
    d = st.idle_pct_innermost(trace, spans, st.DISPATCH)
    o = st.idle_pct_innermost(trace, spans, st.OPERANDS)
    assert (d, o) == (pytest.approx(5.0), pytest.approx(6.0))
    assert d + o <= trace_reduce.idle_pct(trace)
    # the middle-of-gap rule gives 16..21 whole to operands and 58..62
    # to operands too: the split is what tells launch latency apart
    mid = dict(sr.idle_by_path(trace, spans))
    assert mid["serving.step/serving.decode/serving.decode.operands"] == \
        pytest.approx(0.009)
    # a traced window of many steps reduces in one pass
    many = raw()
    many["spans"] = [[n, s + k * 100 * MS, d, th] for k in range(2000)
                     for n, s, d, th in many["spans"]]
    many["window"] = [0.0, 2000 * 100 * MS]
    assert len(st.innermost(sr.nest(many))) == 2000 * len(
        st.innermost(spans))


def test_nothing_to_read_is_none_never_zero():
    spans = sr.nest(raw())
    old = [sp for sp in spans if sp.name not in (st.DISPATCH, st.OPERANDS)]
    trace = device()
    for s in (None, [], old):
        assert st.host_held_ms(s) is None
        assert st.host_work_ms(s) is None
        assert st.dispatches_per_step(s) is None
        assert st.idle_pct_innermost(trace, s, st.DISPATCH) is None
        assert st.idle_pct_innermost(trace, s, st.OPERANDS) is None
    # a program without the new spans still has steps and modules
    assert st.dev_programs_per_step(trace, old) == pytest.approx(5.5)
    assert st.dev_programs_per_step(None, spans) is None
    assert st.dev_programs_per_step(trace, None) is None
    assert st.dev_programs_per_step({"devices": [], "host": []},
                                    spans) is None
    assert st.idle_pct_innermost(None, spans, st.DISPATCH) is None
    no_step = [sp for sp in spans if sp.name != sr.STEP]
    assert st.host_held_ms(no_step) is None


def test_the_six_entries_name_the_serving_cells_and_their_files():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == NEW
    for name in NEW:
        m = entries[name]
        assert m["workloads"] == CELLS
        assert (m["layer"], m["moves"]) == ("serving host loop",
                                            "out_tok_s")
        assert m["source"] == ("device_trace"
                               if name == "dev_programs_per_step"
                               else "program_span")
        assert os.path.exists(os.path.join(
            harness.ROOT, "chipbench", "layers", name + ".py"))


class Ctx:
    trace, peaks = True, None
    cell = {"name": SERVE}


def test_the_readers_read_a_real_servers_trace(tmp_path, monkeypatch):
    """The real profiler around a toy paged server on the CPU: the
    three span readers report and add up, the three that need the
    device's ops have nothing to read; the held time splits by `prog`,
    the span's argument."""
    import jax
    import jax.numpy as jnp
    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                head_dim=8, n_layers=2, d_ff=64)
    srv = ContinuousServer(tfm.init_params(cfg, jax.random.PRNGKey(0)),
                           cfg, slots=2, smax=64, paged=True, block_size=8)
    srv.submit([5, 4, 3, 2, 1], max_new=4)
    srv.run()                                   # compiled
    before = len(srv.step_accounts())
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    jax.profiler.start_trace(os.path.join(str(tmp_path), "trace-" + SERVE))
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            for k in range(3):
                srv.submit([1 + k, 2, 3, 4, 5, 6], max_new=5)
            srv.run()
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    read = {name: harness.load_by_path(f"chipbench/layers/{name}.py").read(
        None, {}, Ctx()) for name in NEW}
    assert all(read[name] > 0 for name in NEW[:3])
    assert all(read[name] is None for name in NEW[3:])
    spans = sr.of_run(Ctx())
    assert read["host_work_ms.serve"] + read["host_held_ms.serve"] == \
        pytest.approx(sr.host_self_ms(spans), rel=1e-9)
    steps = sum(sp.name == sr.STEP for sp in spans)
    assert steps == len(srv.step_accounts()) - before > 0
    by = st.held_by_prog(sr.find_xplane(SERVE))
    assert {"pg_step", "cb_chunk", "cb_probe", "pg_splice"} <= set(by)
    assert sum(n for n, _ in by.values()) == pytest.approx(
        read["dispatches_per_step"] * steps)
    # the profiler's clock and the account's agree on the held time
    recs = srv.step_accounts()[-steps:]
    assert sum(r.dispatches for r in recs) == sum(n for n, _ in by.values())
    assert sum(sec for _, sec in by.values()) == pytest.approx(
        sum(r.held_ns for r in recs) / 1e9, rel=0.2)
    assert st.main([SERVE]) == 0
