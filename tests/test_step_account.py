"""The step's account (`svc/tracing.StepAccount`, fed by
`ContinuousServer.step()`, by what `_program()` hands out and by
`_wait()`): every named program's call lies in exactly one
`serving.dispatch` span under its name; a step's wall is its work, its
time held in dispatch calls and its time waiting on reads; a step made
slow by hand is counted, marked and leaves ONE readable bundle that
blames the right part; an admission-heavy run raises none — each still
token for token `generate()`'s output (the plain reference's on the
recurrent toy), over the paged server on a dense toy, on window layers
with experts (the Laguna toy) and on recurrent and latent-attention
layers (the Kimi-Linear toy).
"""

import gc
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import serving_hybrid as hybrid_drv
from chipbench.drivers import serving_mixed as drv
from chipbench.reference import kimi_linear as hybrid_ref
from hpx_tpu.core.config import runtime_config
from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.svc import faultinject, flight, tracing
from hpx_tpu.svc import performance_counters as pc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)
PH, NAME, ID, PARENT, ARGS = 0, 1, 5, 6, 7
DISPATCH = "serving.dispatch"
MODES = ["paged", "mixed", "hybrid"]
NAP = 0.3           # seconds: over the 250 ms floor of a slow step


def _toy(conf_file, rehearse_file, build):
    with open(os.path.join(ROOT, "chipbench/configs", conf_file)) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "chipbench/tests", rehearse_file)) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    return conf, build(conf)


@pytest.fixture(scope="module")
def models():
    _, toy = _toy("laguna-xs2.json", "rehearse_mixed.json", drv.build_cfg)
    hconf, htoy = _toy("kimi-linear-48b.json", "rehearse_hybrid.json",
                       hybrid_drv.build_cfg)
    return {"paged": (CFG, tfm.init_params(CFG, jax.random.PRNGKey(0))),
            "mixed": (toy, drv.make_params(toy, 11)),
            "hybrid": (htoy, hybrid_drv.make_params(htoy, 11)),
            "hybrid_conf": hconf}


@pytest.fixture()
def ring():
    """The span ring, switched on the way a deployment does."""
    rc = runtime_config()
    rc.set("hpx.trace.enabled", "1")
    tr = tracing.start_if_configured()
    try:
        yield tr
    finally:
        tracing.stop_tracing()
        rc.set("hpx.trace.enabled", "0")


@pytest.fixture()
def bundles(tmp_path):
    """`hpx.flight.dir` in a directory of the test's own."""
    rc = runtime_config()
    old = rc.get("hpx.flight.dir", "auto")
    rc.set("hpx.flight.dir", str(tmp_path))
    try:
        yield tmp_path
    finally:
        rc.set("hpx.flight.dir", old)


def _server(models, mode, **kw):
    cfg, params = models[mode]
    base = {"paged": dict(smax=64, block_size=8),
            "mixed": dict(smax=128, block_size=4, prefill_chunk=8),
            "hybrid": dict(smax=64, block_size=4, prefill_chunk=8)}[mode]
    return ContinuousServer(params, cfg,
                            **{"paged": True, "slots": 2, **base, **kw})


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 60, n)]


def _generate(models, mode, prompt, max_new):
    if mode == "hybrid":
        # the reference's greedy continuation in one padded frame
        _, params = models["hybrid"]
        seq, out = list(prompt), []
        while len(out) < max_new:
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            lg = hybrid_ref.logits(params, models["hybrid_conf"], toks)
            out.append(int(np.asarray(lg)[0, len(seq) - 1].argmax()))
            seq.append(out[-1])
        return out
    cfg, params = models[mode]
    out = tfm.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new)
    return [int(t) for t in np.asarray(out)[0]]


def _identity(rec):
    assert rec.work_ns + rec.held_ns + rec.waited_ns == rec.wall_ns
    assert min(rec.work_ns, rec.held_ns, rec.waited_ns, rec.gap_ns) >= 0
    assert 0 <= rec.eager_ns <= rec.work_ns
    assert rec.owed >= rec.chunks
    assert rec.top_held_ns <= rec.held_ns
    assert (rec.dispatches == 0) == (rec.top_prog == "")


# -- a span around every named program's call ----------------------------

@pytest.mark.parametrize("mode", MODES)
def test_every_program_call_lies_in_one_dispatch_span(models, ring, mode,
                                                      monkeypatch):
    calls = []

    class Spy:
        """A cached program that leaves a ring instant where it runs."""

        def __init__(self, prog, name):
            self.prog, self.name = prog, name

        def __call__(self, *a, **kw):
            tracing.instant("test.program_call", "test", prog=self.name)
            calls.append(self.name)
            return self.prog(*a, **kw)

    cached = serving._cached_program
    monkeypatch.setattr(
        serving, "_cached_program",
        lambda ck, build: Spy(cached(ck, build), ck[0]))
    srv = _server(models, mode)
    reqs = [(_prompt(5, 1), 3), (_prompt(19, 2), 7), (_prompt(6, 3), 4)]
    rids = [srv.submit(p, max_new=m) for p, m in reqs]
    out = srv.run()
    assert out == {r: _generate(models, mode, p, m)
                   for r, (p, m) in zip(rids, reqs)}

    ev = ring.snapshot()
    spans = {e[ID]: e for e in ev if e[PH] == "B" and e[NAME] == DISPATCH}
    inside = [e for e in ev if e[NAME] == "test.program_call"]
    assert len(inside) == len(calls) == len(spans) > 10
    # each call in a span of its own, named as the program is
    assert sorted(e[PARENT] for e in inside) == sorted(spans)
    for e in inside:
        assert spans[e[PARENT]][ARGS]["prog"] == e[ARGS]["prog"]
    # step, chunk, probe, splice, scratch or gather: every kind ran
    progs = {n.split("_", 1)[1] for n in calls}
    assert {"step", "chunk", "probe", "splice"} <= progs
    assert progs & {"scratch", "gather"}
    # an admission's dispatches carry its request, the step's none
    by_prog = {}
    for sp in spans.values():
        by_prog.setdefault(sp[ARGS]["prog"], []).append(sp[ARGS].get("rid"))
    assert set(by_prog["pg_step"]) == {None}
    assert set(by_prog["pg_splice"]) == set(rids)
    # every span is a child of the span that held its code before
    names = {e[ID]: e[NAME] for e in ev if e[PH] == "B"}
    kids = {"serving.decode.operands": "serving.decode"}
    for e in ev:
        if e[PH] == "B" and e[NAME] in kids:
            assert names[e[PARENT]] == kids.pop(e[NAME])
    assert not kids
    for sp in spans.values():
        if sp[ARGS]["prog"] == "pg_step":
            assert names[sp[PARENT]] == "serving.decode"

    # the account counts what the ring saw
    recs = srv.step_accounts()
    assert len(recs) == srv._step_n and [r.n for r in recs] == list(
        range(1, len(recs) + 1))
    for rec in recs:
        _identity(rec)
    assert sum(r.dispatches for r in recs) == len(calls)
    st = srv.read_stats()
    assert sum(r.reads_draining for r in recs) == st["reads_draining"]
    assert sum(r.reads_overlapped for r in recs) == st["reads_overlapped"]
    assert sum(r.admits for r in recs) == len(reqs)
    assert sum(r.chunks for r in recs) == srv._chunks > 0
    assert not any(r.slow for r in recs)
    # the operands of every decode step are on the account as the
    # `eager_ns` part of the work, and nothing else is: an admission's
    # pick is inside `cb_probe`, a step without a dispatch has none
    assert all(r.eager_ns > 0 for r in recs if r.live)
    assert all(r.eager_ns == 0 for r in recs if not r.dispatches)
    # a read leaves no chunk owed
    assert all(r.owed == r.chunks for prev, r in zip(recs, recs[1:])
               if prev.reads_draining or prev.reads_overlapped)


# -- a slow step, made by hand -------------------------------------------

def _warm(srv, steps=40):
    """A long request decoding alone: enough steps for a median."""
    rid = srv.submit(_prompt(4, 9), max_new=steps + 30)
    for _ in range(steps):
        assert srv.step()
    assert not any(r.slow for r in srv.step_accounts())
    return rid


def _slow_program(srv, monkeypatch):
    cached = serving._cached_program
    armed = [True]

    def napping(ck, build):
        prog = cached(ck, build)
        if ck[0] != "pg_step" or not armed[0]:
            return prog
        armed[0] = False

        def call(*a, **kw):
            time.sleep(NAP)
            return prog(*a, **kw)
        return call
    monkeypatch.setattr(serving, "_cached_program", napping)


def _slow_read(srv, monkeypatch):
    class Numpy:
        """numpy, its first `asarray` of a device value a slow read
        (the operands are `np.asarray` of host lists)."""
        naps = 1

        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, x, *a, **kw):
            if self.naps and isinstance(x, jax.Array):
                self.naps -= 1
                time.sleep(NAP)
            return np.asarray(x, *a, **kw)
    srv._read_due = True            # the next step reads
    monkeypatch.setattr(serving, "np", Numpy())


def _slow_host(srv, monkeypatch, what=lambda: time.sleep(NAP),
               where="_shed_expired"):
    inner, armed = getattr(srv, where), [True]

    def slow(*a):
        if armed[0]:
            armed[0] = False
            what()
        return inner(*a)
    monkeypatch.setattr(srv, where, slow)


def _slow_eager(srv, monkeypatch, what=lambda: time.sleep(NAP)):
    # inside `serving.decode.operands`: where a transfer (once an
    # eager op) that meets a full queue holds the host
    _slow_host(srv, monkeypatch, what, "_ensure_block")


def _slow_collector(srv, monkeypatch):
    def pause(phase, info):
        # after the account's own callback: inside the pause it times
        if phase == "start" and info["generation"] == 2:
            time.sleep(NAP)
    gc.callbacks.append(pause)
    try:
        _slow_eager(srv, monkeypatch, gc.collect)
        srv.step()
    finally:
        gc.callbacks.remove(pause)


def _slow_caller(srv, monkeypatch):
    time.sleep(NAP)


SLOW = {"held": _slow_program, "waited": _slow_read,
        "off_cpu": _slow_host, "eager": _slow_eager,
        "collector": _slow_collector, "caller": _slow_caller}


@pytest.mark.parametrize("blame", sorted(SLOW))
def test_a_slow_step_leaves_one_bundle_that_blames_the_right_part(
        models, ring, bundles, monkeypatch, capsys, blame):
    srv = _server(models, "paged", slots=1, smax=128)
    rid = _warm(srv)
    before = len(srv.step_accounts())
    SLOW[blame](srv, monkeypatch)
    srv.step()
    recs = srv.step_accounts()
    slow = [r for r in recs if r.slow]
    assert [r.slow for r in slow] == ["step"] and slow[0].n > before
    rec = slow[0]
    _identity(rec)
    assert rec.blame() == blame
    part = {"held": rec.held_ns, "waited": rec.waited_ns,
            "off_cpu": rec.work_ns, "eager": rec.eager_ns,
            "collector": rec.gc_ns, "caller": rec.gap_ns}[blame]
    assert part >= NAP * 1e9 > 4 * max(1, rec.lead + rec.owed) * (
        srv._acct._median_block / 32)
    assert (rec.eager_ns >= NAP * 1e9) == (blame in ("eager", "collector"))
    if blame == "held":
        assert rec.top_prog == "pg_step" and rec.top_held_ns == part
    if blame == "collector":
        assert rec.gc_full >= 1
    if blame in ("off_cpu", "eager", "caller"):
        assert rec.cpu_thread_ns < NAP * 1e9 / 2       # it slept

    # ONE bundle, readable, that says so
    files = sorted(os.listdir(bundles))
    assert len(files) == 1 and files[0].endswith("-slow_step.json")
    with open(os.path.join(bundles, files[0])) as f:
        doc = json.load(f)
    assert flight.validate_bundle(doc) == []
    assert doc["trigger"]["kind"] == "slow_step"
    assert doc["trigger"]["site"] == "serving"
    extra = doc["extra"]
    assert extra["blame"] == blame and extra["kind"] == "step"
    assert extra["slow"] == json.loads(json.dumps(rec._asdict()))
    assert extra["fields"] == list(tracing.StepRecord._fields)
    assert len(extra["before"]) == min(64, rec.n - 1)
    assert extra["before"][-1][0] == rec.n - 1
    assert extra["cores"] >= 1
    assert any(s["name"] == "serving.step" for s in doc["spans"])
    # ... as the recorder's own listing does, in one line
    capsys.readouterr()
    assert flight.main(["--tail", "1"]) == 0
    (line,) = capsys.readouterr().out.strip().splitlines()
    assert "reason=slow_step" in line and line.endswith(f"blame={blame}")

    # a second slow step inside 5 s: counted and marked, no new bundle
    time.sleep(NAP)
    srv.step()
    recs = srv.step_accounts()
    assert [r.slow for r in recs if r.slow] == ["step", "step"]
    assert recs[-1].blame() == "caller"
    assert len(os.listdir(bundles)) == 1
    marks = [e[ARGS] for e in ring.snapshot()
             if e[PH] == "i" and e[NAME] == "serving.slow_step"]
    assert [(m["n"], m["blame"]) for m in marks] == [
        (rec.n, blame), (recs[-1].n, "caller")]

    # the counters equal the ring
    inst = srv.counter_instance
    got = {n.rsplit("/", 1)[1]: pc.query_counter(n).value for n in
           pc.discover_counters(f"/serving{{locality#*/{inst}}}/steps/*")}
    assert got["slow"] == 2 == srv._acct.slow
    assert got["slow-seconds"] == pytest.approx(srv._acct.slow_ns / 1e9)
    assert got["slow-seconds"] > 2 * NAP * 0.9

    # and the tokens are generate()'s all the same
    out = srv.run()
    assert out == {rid: _generate(models, "paged", _prompt(4, 9), 70)}


def test_a_crawling_block_is_slow_where_no_step_is(bundles, monkeypatch):
    """All 32 steps at 94 ms beside a median of 30 (blocks of 0.96 s):
    no step passes the first rule, the block does, once a block, and
    is blamed as the sum of its steps."""
    acct = tracing.StepAccount()
    ms = 1_000_000

    def step(n, wall, held=0):
        acct.begin()
        acct._t0 = acct._end            # no gap
        if held:
            acct.dispatched("pg_step", held)
        end = tracing._now_ns
        monkeypatch.setattr(tracing, "_now_ns", lambda: acct._t0 + wall)
        try:
            return acct.end(n, 1)
        finally:
            monkeypatch.setattr(tracing, "_now_ns", end)
    for n in range(1, 257):
        assert step(n, 30 * ms).slow == ""
    verdicts = [step(256 + k, 94 * ms, held=70 * ms).slow
                for k in range(1, 65)]
    # the last 32 steps, less their longest, against a median block of
    # 0.96 s: over 2.5 times, and 1 s more, once 24 of them crawl
    # (24 x 94 + 8 x 30 = 2.50 s, 2.40 without one of 94), and again a
    # whole block later
    assert [k for k, v in enumerate(verdicts, 1) if v] == [24, 56]
    assert set(verdicts) == {"", "block"} and acct.slow == 2
    assert acct.slow_ns == (2496 - 960) * ms + (32 * 94 - 960) * ms
    # 3 s apart: one bundle; its `block` is the 32 steps' sum
    (name,) = os.listdir(bundles)
    with open(os.path.join(bundles, name)) as f:
        extra = json.load(f)["extra"]
    assert (extra["kind"], extra["blame"]) == ("block", "held")
    assert extra["slow"]["n"] == 280 and extra["slow"]["wall_ns"] == 94 * ms
    assert extra["block"]["wall_ns"] == 2496 * ms == extra["block_ms"] * ms
    assert extra["block"]["held_ns"] == 24 * 70 * ms
    assert extra["block"]["dispatches"] == 24
    assert extra["median_block_ms"] == 960


class _Fed:
    """A StepAccount on a clock of the test's own (ms a step)."""

    def __init__(self, monkeypatch):
        self.acct, self.now, self.n = tracing.StepAccount(), 10 ** 9, 0
        self.chunks = self.reads = 0
        monkeypatch.setattr(tracing, "_now_ns", lambda: self.now)

    def step(self, wall, chunks=0, reads=0, waited=0, lead=0, live=1,
             gap=0, flush=0):
        self.n += 1
        self.chunks += chunks
        self.reads += reads
        # the caller's own time, and its `flush()`, between two steps
        self.acct.waited_ns += flush * 1_000_000
        self.now += (gap + flush) * 1_000_000
        self.acct.begin(lead)
        self.acct.waited_ns += waited * 1_000_000
        self.now += wall * 1_000_000
        return self.acct.end(self.n, live, 0, self.chunks, 0, self.reads)


def test_a_loaders_document_drains_behind_its_chunks(bundles, monkeypatch):
    """DeepSeek-V2's ramp (its accounts on the chip, PR 39): a loader's
    document of 14k tokens alone in the server, 54 chunk steps of 58 ms
    with nothing to read, then the step that ends it and drains what
    the queue still held: 608 ms, 347 of them waiting. Not slow: it
    waits behind the chunks enqueued since the last read. The same
    608 ms after that read, behind one chunk, is."""
    fed = _Fed(monkeypatch)
    for doc in range(6):
        for _ in range(54):
            assert fed.step(58, chunks=1, live=0).slow == ""
        rec = fed.step(608, chunks=1, reads=1, waited=347, live=0)
        assert (rec.slow, rec.owed, rec.chunks) == ("", 55, 1)
    assert fed.acct._median_block > 0 and os.listdir(bundles) == []
    rec = fed.step(608, chunks=1, reads=1, waited=347, live=0)
    assert (rec.slow, rec.owed, rec.blame()) == ("step", 1, "waited")
    assert len(os.listdir(bundles)) == 1


def test_a_flush_between_steps_is_not_the_callers_gap(bundles, monkeypatch):
    """The harness opens its window with a `flush()` (DeepSeek-V2 on
    the chip: 137 ms between two steps, an admission step of 220 ms
    behind it): the drain is the server's own read, not a stall of the
    caller's; the same 137 ms spent by the caller is."""
    fed = _Fed(monkeypatch)
    for _ in range(128):
        assert fed.step(60).slow == ""
    rec = fed.step(220, chunks=1, reads=3, flush=137, gap=2)
    assert (rec.slow, rec.gap_ns, rec.wall_ns) == ("", 2_000_000, 220_000_000)
    rec = fed.step(220, chunks=1, reads=1, gap=137)
    assert (rec.slow, rec.gap_ns) == ("step", 137_000_000)
    assert len(os.listdir(bundles)) == 1


def test_a_report_that_fails_does_not_raise_into_the_step(
        models, bundles, monkeypatch):
    srv = _server(models, "paged", slots=1, smax=128)
    rid = _warm(srv)

    def broken(*a, **kw):
        raise OSError("no clock today")
    monkeypatch.setattr(tracing, "mark", broken)
    _slow_host(srv, monkeypatch)
    assert srv.step()
    rec = srv.step_accounts()[-1]
    assert rec.slow == "step" and rec.blame() == "off_cpu"
    assert srv._acct.dropped == 1 and os.listdir(bundles) == []
    assert srv.run() == {rid: _generate(models, "paged", _prompt(4, 9), 70)}


def test_a_recovery_dispatches_under_no_request(models, ring):
    """A fault inside an admission's chunk: the restores that
    recovery dispatches are every slot's, not the admission's."""
    srv = _server(models, "paged")
    rid = srv.submit(_prompt(5, 1), max_new=12)
    for _ in range(4):
        srv.step()
    seen, recover = [], srv._recover
    srv._recover = lambda *a: (seen.append(srv._rid), recover(*a))[1]
    fi = faultinject.install(faultinject.FaultInjector(
        schedule={"prefill": [1]}))
    try:
        late = srv.submit(_prompt(19, 2), max_new=5)
        out = srv.run()
    finally:
        faultinject.uninstall()
    assert fi.total_injected == 1 and seen == [None]
    assert out == {rid: _generate(models, "paged", _prompt(5, 1), 12),
                   late: _generate(models, "paged", _prompt(19, 2), 5)}


def test_a_slow_step_bundle_evicts_its_own_kind_first(bundles):
    rc = runtime_config()
    rc.set("hpx.flight.max_bundles", "3")
    try:
        shed = flight.record_fault("shed", site="t")
        slow = [flight.record_fault("slow_step", site="serving")
                for _ in range(5)]
        assert sorted(os.listdir(bundles)) == sorted(
            os.path.basename(p) for p in [shed] + slow[-2:])
        # another kind, and no older one of it: the oldest of all goes
        failover = flight.record_fault("failover", site="t")
        assert sorted(os.listdir(bundles)) == sorted(
            os.path.basename(p) for p in slow[-2:] + [failover])
    finally:
        rc.set("hpx.flight.max_bundles", "8")


def _blocks(acct, walls, lead=0):
    """Feed whole blocks of walls (ms); the verdicts that were not ""."""
    ms = 1_000_000
    return [v for w in walls
            if (v := acct._verdict(int(w * ms), lead))]


def test_admission_steps_of_a_wide_chunk_are_not_slow():
    """DeepSeek-V2's shape: decode steps of 60 ms, every fourth an
    admission of 150 ms (2.5 times), one of 240 ms under the floor and
    one of 330 ms over it but under four paces: none fires; 4 paces
    and 250 ms do."""
    acct, ms = tracing.StepAccount(), 1_000_000
    walls = [150 if n % 4 == 0 else 60 for n in range(1024)]
    walls[500], walls[700] = 240, 330
    assert _blocks(acct, walls) == []
    assert acct._median_block == (8 * 150 + 24 * 60) * ms   # pace 82.5
    assert acct._verdict(329 * ms) == "" and acct._verdict(331 * ms) == "step"


def test_a_burst_of_admissions_is_not_a_slow_block():
    """StarCoder2-3B's shape (its accounts on the chip, PR 39): decode
    steps of 11 ms, blocks of 0.77 s, and every seventh block a burst
    of admissions, 1.39 s: four times 32 median steps, under twice the
    median block."""
    acct, ms = tracing.StepAccount(), 1_000_000
    walls = [43.5 if b % 7 == 6 else 63 if i % 4 == 0 else 11
             for b in range(40) for i in range(32)]
    assert _blocks(acct, walls) == []
    assert acct._median_block == (8 * 63 + 24 * 11) * ms


def test_a_step_rightly_waits_for_every_step_the_host_was_ahead():
    """The host runs `max_async_steps` ahead (Kimi-Linear's shape): 31
    steps of 2 ms, then one that blocks 650 ms for the 31 steps queued,
    in a read or in an eager op alike; the same 650 ms with ONE step
    queued is a stall."""
    acct, ms = tracing.StepAccount(), 1_000_000
    for n in range(640):
        w, lead = (652, 31) if n % 32 == 31 else (2, n % 32)
        assert acct._verdict(w * ms, lead) == ""
    assert acct._median_block == 714 * ms                   # pace 22.3
    assert acct._verdict(652 * ms, 8) == ""                 # 8 x 89 ms
    assert acct._verdict(652 * ms, 1) == "step"


# -- a run of admissions raises none -------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_an_admission_heavy_run_raises_no_slow_step(models, bundles, mode):
    reqs = [(_prompt(3 + k % 17, 100 + k), 2 + k % 3) for k in range(320)]
    # every program compiled by a first server, so that none of the
    # second's steps builds one
    warm = _server(models, mode)
    for p, m in reqs[:20]:
        warm.submit(p, max_new=m)
    warm.run()
    srv = _server(models, mode)
    rids = [srv.submit(p, max_new=m) for p, m in reqs]
    out = srv.run()
    recs = srv.step_accounts()
    assert len(recs) == srv._step_n >= 300 > 0
    assert sum(r.admits for r in recs) == len(reqs)
    assert sum(r.compiles for r in recs) == 0
    for rec in recs:
        _identity(rec)
    # nothing the server does is slow; a loaded test machine can still
    # pause the process, and the account then says that it did
    assert all(r.blame() in ("off_cpu", "eager", "collector", "caller")
               for r in recs if r.slow)
    assert len(os.listdir(bundles)) == sum(1 for r in recs if r.slow)
    for rid, (p, m) in list(zip(rids, reqs))[::32]:
        assert out[rid] == _generate(models, mode, p, m)
