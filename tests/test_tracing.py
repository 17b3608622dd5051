"""Causal task tracer (svc/tracing + svc/trace_export).

Contracts under test: with no tracer there are no hooks and no ring,
and a span is a profiler annotation that reads no argument unless a
`jax.profiler` session is live; in such a session the serving loop's
and the dataflow layer's spans lie in the trace's host plane, nested
as the code nests; spans nest and record causal parents; parents and flow arrows propagate across async_ /
.then() / when_all joins; the ring drops oldest at capacity; exported
Chrome-trace JSON always validates (matched B/E, resolving flows,
monotonic ts); counter samples interleave on the same timeline; and the
ContinuousServer emits the admit -> prefill / decode -> retire causal
chain end to end (the CI smoke).
"""

import glob
import json
import os
import time

import jax
import pytest

import hpx_tpu as hpx
from hpx_tpu.futures import future as future_mod
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.runtime import threadpool
from hpx_tpu.svc import profiling, tracing
from hpx_tpu.svc.performance_counters import query_counter
from hpx_tpu.svc.trace_export import (
    load_chrome_trace,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)

# snapshot tuples: (ph, name, cat, ts, tid, id, parent, args)
PH, NAME, CAT, TS, TID, ID, PARENT, ARGS = range(8)

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test must leave the process untraced."""
    yield
    assert tracing.active_tracer() is None, "test leaked an active tracer"
    tracing.stop_tracing()          # defensive cleanup anyway


def spans_named(events, name):
    return [e for e in events if e[PH] == "B" and e[NAME] == name]


def _wait_for(cond, timeout=5.0):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.001)
    return True


# ---------------------------------------------------------------------------
# disabled path: structurally zero work
# ---------------------------------------------------------------------------

class TestDisabled:
    def test_no_tracer_no_hooks(self):
        assert tracing.active_tracer() is None
        assert tracing.current_span_id() is None
        assert threadpool._trace_submit is None
        assert threadpool._trace_pending is None
        assert future_mod._trace_continuation is None

    def test_span_off_records_nothing_and_reads_no_argument(self):
        # with no tracer a span is a profiler annotation and nothing
        # else: no ring comes to be, and with no profiler session
        # either its arguments are never read
        class Heavy:
            read = 0

            def __repr__(self):
                Heavy.read += 1
                return "heavy"
            __str__ = __repr__

        with tracing.span("x", "user", heavy=Heavy()) as a:
            assert isinstance(a, profiling.annotate)
            with tracing.span("y"):
                assert tracing.current_span_id() is None
        assert Heavy.read == 0
        assert tracing.active_tracer() is None
        # instrumentation with a ring of its own keeps the shared no-op
        assert tracing.null_span() is tracing.null_span()
        with tracing.null_span() as n:
            assert n.id is None

    def test_instant_is_noop(self):
        tracing.instant("nothing", "user", k=1)   # must not raise

    def test_hooks_detached_after_stop(self):
        with tracing.trace(sample_counters=False):
            assert threadpool._trace_submit is not None
            assert future_mod._trace_continuation is not None
        assert threadpool._trace_submit is None
        assert threadpool._trace_pending is None
        assert future_mod._trace_continuation is None

    def test_double_start_raises(self):
        with tracing.trace(sample_counters=False):
            with pytest.raises(RuntimeError):
                tracing.start_tracing()


# ---------------------------------------------------------------------------
# span recording + nesting
# ---------------------------------------------------------------------------

class TestSpans:
    def test_nesting_parents(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("outer", "user", k=1) as outer:
                with tracing.span("inner") as inner:
                    assert tracing.current_span_id() == inner.id
                assert tracing.current_span_id() == outer.id
            assert tracing.current_span_id() is None
        ev = tr.snapshot()
        (ob,) = spans_named(ev, "outer")
        (ib,) = spans_named(ev, "inner")
        assert ob[PARENT] is None
        assert ib[PARENT] == ob[ID]
        assert ob[ARGS] == {"k": 1}
        ends = [e for e in ev if e[PH] == "E"]
        assert {e[ID] for e in ends} == {ob[ID], ib[ID]}

    def test_instant_parented(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("phase") as sp:
                tracing.instant("tick", "user", n=3)
        (i,) = [e for e in tr.snapshot() if e[PH] == "i"]
        assert i[PARENT] == sp.id and i[ARGS] == {"n": 3}

    def test_module_span_is_real_when_active(self):
        with tracing.trace(sample_counters=False) as tr:
            s = tracing.span("live")
            assert s is not tracing._NULL_SPAN
            with s:
                pass
        assert spans_named(tr.snapshot(), "live")


# ---------------------------------------------------------------------------
# causal propagation across futures
# ---------------------------------------------------------------------------

class TestCausality:
    def test_async_task_parented_to_submit_site(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("submit-site") as site:
                hpx.async_(lambda: 42).get(timeout=5.0)
            ev = tr.snapshot()
        tasks = [e for e in ev if e[PH] == "B" and e[CAT] == "task"]
        assert tasks, "pool task recorded no span"
        assert any(e[PARENT] == site.id for e in tasks)

    def test_async_flow_arrow_resolves(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("root"):
                hpx.async_(lambda: 1).get(timeout=5.0)
            ev = tr.snapshot()
        s_ids = {e[ID] for e in ev if e[PH] == "s"}
        f_ids = {e[ID] for e in ev if e[PH] == "f"}
        assert s_ids and s_ids & f_ids, (s_ids, f_ids)

    def test_submit_outside_span_has_no_parent(self):
        with tracing.trace(sample_counters=False) as tr:
            hpx.async_(lambda: 1).get(timeout=5.0)
            ev = tr.snapshot()
        tasks = [e for e in ev if e[PH] == "B" and e[CAT] == "task"]
        assert tasks and all(e[PARENT] is None for e in tasks)

    def test_then_chain_parented_to_attach_site(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("attach-site") as site:
                f = hpx.async_(lambda: 2)
                g = f.then(lambda fut: fut.get() * 3)
            assert g.get(timeout=5.0) == 6
            assert _wait_for(lambda: any(
                e[PH] == "B" and e[CAT] == "continuation"
                for e in tr.snapshot()))
            ev = tr.snapshot()
        conts = [e for e in ev
                 if e[PH] == "B" and e[CAT] == "continuation"]
        assert any(e[PARENT] == site.id for e in conts)
        assert all(e[NAME].startswith("then:") for e in conts)

    def test_when_all_join_parented(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("join-site") as site:
                fs = [hpx.async_(lambda i=i: i) for i in range(3)]
                g = hpx.when_all(*fs).then(
                    lambda fut: sum(f.get() for f in fut.get()))
            assert g.get(timeout=5.0) == 3
            assert _wait_for(lambda: any(
                e[PH] == "B" and e[CAT] == "continuation"
                and e[PARENT] == site.id for e in tr.snapshot()))

    def test_tracer_stop_leaves_pending_continuations_runnable(self):
        # a continuation attached while tracing may run after stop()
        with tracing.trace(sample_counters=False):
            f = hpx.async_(lambda: time.sleep(0.05) or 5)
            g = f.then(lambda fut: fut.get() + 1)
        assert g.get(timeout=5.0) == 6


# ---------------------------------------------------------------------------
# ring buffer
# ---------------------------------------------------------------------------

class TestRing:
    def test_overflow_drops_oldest(self):
        tr = tracing.Tracer(capacity=8, sample_counters=False)
        for i in range(20):
            tr.instant(f"i{i}")
        ev = tr.snapshot()
        assert len(ev) == 8
        assert tr.dropped == 12
        assert [e[NAME] for e in ev] == [f"i{i}" for i in range(12, 20)]

    def test_capacity_floor(self):
        with pytest.raises(ValueError):
            tracing.Tracer(capacity=1)


# ---------------------------------------------------------------------------
# export schema
# ---------------------------------------------------------------------------

class TestExport:
    def test_artifact_validates_and_loads(self, tmp_path):
        path = str(tmp_path / "trace.json")
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("work", "user", step=1):
                hpx.async_(lambda: 1).get(timeout=5.0)
                tracing.instant("mark")
            tr.counter("/custom/depth", 2.0)
        doc = tr.export(path)
        assert validate_chrome_trace(doc) == []
        loaded = load_chrome_trace(path)
        assert loaded == json.loads(json.dumps(doc))
        names = {e["name"] for e in loaded["traceEvents"]}
        assert {"process_name", "work", "mark", "/custom/depth"} <= names
        assert loaded["otherData"]["format"] == "hpx_tpu.svc.tracing"

    def test_open_spans_closed_at_export(self):
        tr = tracing.Tracer(sample_counters=False)
        outer = tr._begin("outer", "user", None)
        tr._begin("inner", "user", None)
        doc = to_chrome_trace(tr.snapshot(), tr.thread_names(), tr.t0,
                              tr.dropped)
        assert validate_chrome_trace(doc) == []
        ends = [e for e in doc["traceEvents"] if e["ph"] == "E"]
        # innermost closes first so the synthetic E's nest correctly
        assert [e["name"] for e in ends] == ["inner", "outer"]
        del outer

    def test_orphan_halves_are_dropped(self):
        # an E whose B was evicted and a dangling s must not survive
        tr = tracing.Tracer(sample_counters=False)
        tr._record(("E", "ghost", "task", tr.t0 + 1.0, 7, 99, None,
                    None))
        tr._record(("s", "queued", "flow", tr.t0 + 2.0, 7, 42, None,
                    None))
        doc = to_chrome_trace(tr.snapshot(), {}, tr.t0, tr.dropped)
        assert validate_chrome_trace(doc) == []
        assert [e for e in doc["traceEvents"] if e["ph"] != "M"] == []

    def test_thread_metadata_rows(self):
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("here"):
                pass
        doc = to_chrome_trace(tr.snapshot(), tr.thread_names(), tr.t0)
        rows = [e for e in doc["traceEvents"]
                if e["ph"] == "M" and e["name"] == "thread_name"]
        assert rows and all(e["args"]["name"] for e in rows)

    def test_write_is_atomic(self, tmp_path):
        path = tmp_path / "out.json"
        with tracing.trace(sample_counters=False) as tr:
            with tracing.span("x"):
                pass
        write_chrome_trace(str(path), tr)
        assert path.exists() and not (tmp_path / "out.json.tmp").exists()

    def test_validator_catches_breakage(self):
        bad = {"traceEvents": [
            {"ph": "B", "pid": 1, "tid": 1, "ts": 2.0, "name": "a",
             "cat": "u"},
            {"ph": "E", "pid": 1, "tid": 1, "ts": 1.0, "name": "a"},
            {"ph": "s", "pid": 1, "tid": 1, "ts": 3.0, "name": "q",
             "cat": "flow", "id": 9},
        ]}
        problems = validate_chrome_trace(bad)
        assert any("not monotonically ordered" in p for p in problems)
        assert any("flow id 9" in p for p in problems)


# ---------------------------------------------------------------------------
# counter sampling
# ---------------------------------------------------------------------------

class TestCounters:
    def test_samples_interleave(self):
        with tracing.trace(counter_interval=0.01,
                           counter_patterns=["/runtime*"]) as tr:
            with tracing.span("while-sampling"):
                time.sleep(0.05)
        # stop() takes one final sample, so >=1 even on a loaded host
        cs = [e for e in tr.snapshot() if e[PH] == "C"]
        assert cs and all(e[NAME].startswith("/runtime") for e in cs)
        assert all(isinstance(e[ARGS], float) for e in cs)

    def test_config_defaults_flow_into_tracer(self):
        from hpx_tpu.core.config import runtime_config
        rc = runtime_config()
        old = rc.get("hpx.trace.buffer_events")
        rc.set("hpx.trace.buffer_events", "128")
        try:
            tr = tracing.start_tracing(sample_counters=False)
            assert tr.capacity == 128
            assert tr.counter_patterns == ["/serving*", "/cache*",
                                           "/threads*", "/programs*"]
        finally:
            tracing.stop_tracing()
            rc.set("hpx.trace.buffer_events", old)

    def test_start_if_configured_respects_gate(self):
        from hpx_tpu.core.config import runtime_config
        rc = runtime_config()
        assert tracing.start_if_configured() is None   # off by default
        rc.set("hpx.trace.enabled", "1")
        try:
            tr = tracing.start_if_configured()
            assert tr is not None and tracing.active_tracer() is tr
            assert tracing.start_if_configured() is tr  # idempotent
        finally:
            rc.set("hpx.trace.enabled", "0")
            tracing.stop_tracing()


# ---------------------------------------------------------------------------
# profiling: swallowed observer exceptions are counted
# ---------------------------------------------------------------------------

class TestDroppedCallbacks:
    def test_broken_hook_is_counted_not_fatal(self):
        class Bad:
            def on_stop(self, fn, seconds):
                raise RuntimeError("boom")

        profiling.reset_dropped_callbacks()
        bad = Bad()
        profiling.register_external_timer(bad)
        try:
            assert hpx.async_(lambda: 7).get(timeout=5.0) == 7
            assert _wait_for(lambda: profiling.dropped_callbacks() >= 1)
        finally:
            profiling.unregister_external_timer(bad)
        cv = query_counter("/runtime{locality#0/total}/count/"
                           "dropped-observer-callbacks")
        assert cv.value >= 1
        profiling.reset_dropped_callbacks()
        assert profiling.dropped_callbacks() == 0


# ---------------------------------------------------------------------------
# CI smoke: a traced ContinuousServer run emits the causal chain
# ---------------------------------------------------------------------------

class TestServingSmoke:
    def test_admit_prefill_decode_retire_chain(self, params):
        with tracing.trace(sample_counters=False) as tr:
            srv = ContinuousServer(params, CFG, slots=2, smax=32)
            # prefill yields token 1, so max_new=3 -> two decode steps
            a = srv.submit([3, 1, 4], max_new=3)
            b = srv.submit([2, 7], max_new=3)
            out = srv.run()
            ev = tr.snapshot()
        assert set(out) == {a, b}

        admits = spans_named(ev, "serving.admit")
        prefills = spans_named(ev, "serving.prefill")
        decodes = spans_named(ev, "serving.decode")
        retires = spans_named(ev, "serving.retire")
        assert len(admits) == 2 and len(prefills) == 2
        assert len(decodes) >= 2          # two decode steps minimum
        assert len(retires) == 2

        # causal edges: prefill nests under its admit, retire under a
        # flush — the one of the step() AFTER the request's last
        # dispatch (reads lag by one step), which here finds nothing
        # live and so flushes outside any decode span
        admit_ids = {e[ID] for e in admits}
        step_ids = {e[ID] for e in spans_named(ev, "serving.step")}
        flushes = {e[ID]: e for e in spans_named(ev, "serving.flush")}
        assert all(e[PARENT] in admit_ids for e in prefills)
        assert all(flushes[e[PARENT]][PARENT] in step_ids
                   for e in retires)
        # rid args connect admit to its retire
        rids = {e[ARGS]["rid"] for e in admits}
        assert rids == {a, b}
        assert {e[ARGS]["rid"] for e in retires} == rids

        # the whole artifact still validates
        doc = to_chrome_trace(ev, tr.thread_names(), tr.t0, tr.dropped)
        assert validate_chrome_trace(doc) == []

    def test_paged_serving_records_cache_instants(self, params):
        with tracing.trace(sample_counters=False) as tr:
            srv = ContinuousServer(params, CFG, slots=1, smax=48,
                                   paged=True)
            shared = list(range(1, 17))    # one full 16-token block
            r1 = srv.submit(shared + [21, 22], max_new=2)
            r2 = srv.submit(shared + [31, 32], max_new=2)
            out = srv.run()
            ev = tr.snapshot()
        assert set(out) == {r1, r2}
        matches = [e for e in ev
                   if e[PH] == "i" and e[NAME] == "cache.match"]
        assert len(matches) == 2
        # slots=1 serializes the requests, so the second admission
        # matches the prefix the first one published at retire
        assert matches[-1][ARGS]["matched"] >= 16

    def test_untraced_serving_output_identical(self, params, tmp_path):
        def serve():
            srv = ContinuousServer(params, CFG, slots=2, smax=32)
            r = srv.submit([3, 1, 4], max_new=2)
            return srv.run()[r]
        base = serve()
        with tracing.trace(sample_counters=False):
            traced = serve()
        assert traced == base
        # a profiler session alone: the spans go to ITS trace, no
        # tracer comes to be, and the tokens are the same
        with profiling.profile_trace(str(tmp_path)):
            profiled = serve()
            assert tracing.active_tracer() is None
        assert profiled == base
        assert "serving.step" in {sp.name for sp in host_spans(tmp_path)}


# ---------------------------------------------------------------------------
# the same spans on the profiler's clock: a jax.profiler session's host plane
# ---------------------------------------------------------------------------

class HostSpan:
    """One `serving.*` / `hpx.*` event of a trace's host plane."""

    def __init__(self, ev, thread):
        self.name, self.thread = ev.name, thread
        self.start, self.end = ev.start_ns, ev.start_ns + ev.duration_ns
        self.args = dict(ev.stats)

    def holds(self, other) -> bool:
        return (self.thread == other.thread and self is not other
                and self.start <= other.start and other.end <= self.end)


def host_spans(logdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(str(logdir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [HostSpan(ev, (plane.name, i)) for ev in line.events
                    if ev.name.startswith(("serving.", "hpx."))]
    return sorted(out, key=lambda sp: sp.start)


def named(spans, name):
    return [sp for sp in spans if sp.name == name]


class TestProfilerPlane:
    def test_serving_spans_nest_in_the_host_plane(self, params, tmp_path):
        """Two slots. A (3 tokens) and B (5) are admitted in step 1, C
        (2) waits for A's slot: A's last step is dispatched in step 2,
        C's (admitted in step 3) in step 3, B's in step 4; step 5 finds
        nothing live. By hand: one blocking read of a first token an
        admission (3), after that step's decode dispatch, and one of
        each decode step's tokens (4), at the flush of the step AFTER
        one in which a request is dispatched its last token (steps 3,
        4: each leaves the step just dispatched in the buffer; step 3
        reads two steps) and of step 5, which drains."""
        srv = ContinuousServer(params, CFG, slots=2, smax=48, paged=True)
        with profiling.profile_trace(str(tmp_path)):
            rids = [srv.submit(p, max_new=m) for p, m in
                    (([3, 1, 4], 3), ([2, 7], 5), ([5, 6, 7], 2))]
            calls = 1
            while srv.step():
                calls += 1
            out = srv.poll_finished()
        assert set(out) == set(rids) and calls == 5
        spans = host_spans(tmp_path)

        steps = named(spans, "serving.step")
        assert [sp.args["n"] for sp in steps] == [1, 2, 3, 4, 5]
        admits = named(spans, "serving.admit")
        assert [sp.args["rid"] for sp in admits] == rids
        reads = named(spans, "serving.first_token.wait")
        waits = named(spans, "serving.flush.wait")
        flushes = named(spans, "serving.flush")
        decodes = named(spans, "serving.decode")
        assert len(reads) == 3 and len(waits) == 4 and len(decodes) == 4
        assert [sp.args["steps"] for sp in flushes] == [3, 2, 1]
        assert [sum(f.holds(w) for w in waits) for f in flushes] == \
            [2, 1, 1]
        # every read but the draining one has a step queued behind it
        assert [sp.args["behind"] for sp in reads] == [1, 1, 1]
        assert [sp.args["behind"] for sp in waits] == [2, 1, 1, 0]
        # a first token is read inside the decode span of the step
        # that admitted it, after the admission
        for read, admit in zip(reads, admits):
            (decode,) = [d for d in decodes if d.holds(read)]
            (step,) = [st for st in steps if st.holds(decode)]
            assert step.holds(admit) and admit.end <= read.start
            assert read.args["rid"] == admit.args["rid"]
        # a flush lies inside a decode span, but for the draining one
        assert [sum(d.holds(f) for d in decodes) for f in flushes] == \
            [1, 1, 0]
        for sp in spans:
            if sp.name != "serving.step":
                assert sum(st.holds(sp) for st in steps) == 1, sp.name
        # blocking device-to-host reads a step: what the benchmark's
        # host_syncs_per_step reads from the same spans
        blocking = [sp for sp in spans if sp.name.endswith(".wait")]
        assert len(blocking) / len(steps) == (3 + 4) / 5

    def test_chunked_prefill_ticks_inside_a_step(self, params, tmp_path):
        srv = ContinuousServer(params, CFG, slots=1, smax=48,
                               prefill_chunk=4)
        with profiling.profile_trace(str(tmp_path)):
            srv.submit(list(range(1, 12)), max_new=2)
            srv.run()
        spans = host_spans(tmp_path)
        ticks = named(spans, "serving.prefill_tick")
        chunks = named(spans, "serving.prefill_chunk")
        assert ticks and len(chunks) == len(ticks)
        for tick in ticks:
            assert sum(tick.holds(c) for c in chunks) == 1
        # the first token is read once the step's decode is enqueued:
        # after the last tick, inside the same step's decode span
        (read,) = named(spans, "serving.first_token.wait")
        (decode,) = [d for d in named(spans, "serving.decode")
                     if d.holds(read)]
        (step,) = [st for st in named(spans, "serving.step")
                   if st.holds(decode)]
        assert step.holds(ticks[-1]) and ticks[-1].end <= read.start

    def test_dataflow_nodes_hold_body_and_dispatch(self, tmp_path):
        from hpx_tpu.exec.tpu import TpuExecutor
        from hpx_tpu.models import stencil1d
        p = stencil1d.StencilParams(nx=64, np_=4, nt=3)
        ex = TpuExecutor()
        stencil1d.gather_dataflow_result(
            stencil1d.stencil_dataflow(p, ex)).block_until_ready()
        with profiling.profile_trace(str(tmp_path)):
            out = stencil1d.gather_dataflow_result(
                stencil1d.stencil_dataflow(p, ex))
            out.block_until_ready()
        spans = host_spans(tmp_path)
        nodes = named(spans, "hpx.dataflow.node")
        bodies = named(spans, "hpx.dataflow.body")
        sent = named(spans, "hpx.exec.dispatch")
        assert len(nodes) == len(bodies) == len(sent) == p.np_ * p.nt
        for node in nodes:
            (body,) = [b for b in bodies if node.holds(b)]
            (call,) = [d for d in sent if node.holds(d)]
            assert body.holds(call)

    def test_a_deferred_node_opens_a_span_of_its_own(self, tmp_path):
        from hpx_tpu.futures.dataflow import dataflow
        from hpx_tpu.futures.future import Future, SharedState
        dep = SharedState()
        with profiling.profile_trace(str(tmp_path)):
            f = dataflow(lambda d: d.get() + 1, Future(dep),
                         policy=hpx.Launch.sync)
            assert not f.is_ready()
            dep.set_value(41)           # fires the node from a callback
            assert f.get() == 42
        spans = host_spans(tmp_path)
        built, fired = named(spans, "hpx.dataflow.node")
        (body,) = named(spans, "hpx.dataflow.body")
        assert fired.holds(body) and not built.holds(body)
