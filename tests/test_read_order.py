"""Dispatch before read in `ContinuousServer.step()`: every program a
step enqueues is enqueued before the step's first blocking
device->host read, and a read never takes the newest dispatched step —
read from the span ring (`hpx.trace.enabled`), event by event (a
dispatch: the begin of the `serving.dispatch` span around the call of
the step's program).

  * a step that admits reads the first token AFTER its decode dispatch
  * the read that lands a max_new retirement of step t begins after
    step t + 1's dispatch and leaves that step in `_buf`
  * a step with nothing live, `flush()`, `run()`'s end and `_recover`
    drain everything
  * a request with an `eos_id`, `max_new == 1`, a speculative server
    and `async_dispatch=False` keep the order read-then-dispatch

each still token for token `generate()`'s output, over blocks of 8 rows
and of 4, and with a window block group and experts (the Laguna toy of
tests/test_laguna_serving.py); and, against the plain reference's own
greedy continuation, over the server of a model with recurrent and
latent-attention layers (the Kimi-Linear toy of
tests/test_kimi_serving.py: `generate()` has no path for it).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import serving_hybrid as hybrid_drv
from chipbench.drivers import serving_mixed as drv
from chipbench.reference import kimi_linear as hybrid_ref
from hpx_tpu.core.config import runtime_config
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.svc import faultinject, tracing
from hpx_tpu.svc import performance_counters as pc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)
PH, NAME, ARGS = 0, 1, 7
READS = ("serving.first_token.wait", "serving.flush.wait",
         "serving.flush.moe_stats.wait")
DISPATCH = "serving.dispatch"
STEP_PROG = "pg_step"                   # the decode step's
# the K/V toy over blocks of 8 rows and of 4 (a request's decode crosses
# a seam, and `_ensure_block` extends its table, every fourth step),
# the mixed and the hybrid toys over their own geometry
MODES = ["paged", "block4", "mixed", "hybrid"]


@pytest.fixture(scope="module")
def models():
    with open(os.path.join(ROOT, "chipbench/configs/laguna-xs2.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "chipbench/tests/rehearse_mixed.json")) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    toy = drv.build_cfg(conf)
    with open(os.path.join(ROOT,
                           "chipbench/configs/kimi-linear-48b.json")) as f:
        hconf = json.load(f)
    with open(os.path.join(ROOT,
                           "chipbench/tests/rehearse_hybrid.json")) as f:
        hconf = harness._merge(hconf, json.load(f)["config"])
    htoy = hybrid_drv.build_cfg(hconf)
    return {"kv": (CFG, tfm.init_params(CFG, jax.random.PRNGKey(0))),
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


def _server(models, mode, **kw):
    cfg, params = models[mode if mode in ("mixed", "hybrid") else "kv"]
    base = {"paged": dict(smax=64, block_size=8),
            "block4": dict(smax=64, block_size=4),
            "mixed": dict(paged=True, smax=128, block_size=4,
                          prefill_chunk=8),
            "hybrid": dict(paged=True, smax=64, block_size=4,
                           prefill_chunk=8)}[mode]
    return ContinuousServer(params, cfg, **{"slots": 2, **base, **kw})


def _prompt(n, seed):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 60, n)]


def _generate(models, mode, prompt, max_new, eos_id=None):
    if mode == "hybrid":
        # the reference's greedy continuation in one padded frame, the
        # tail pinned to eos as generate() pins it
        _, params = models["hybrid"]
        seq, out = list(prompt), []
        while len(out) < max_new:
            if out and out[-1] == eos_id:
                out.append(eos_id)
                continue
            toks = np.zeros((1, 64), np.int32)
            toks[0, :len(seq)] = seq
            lg = hybrid_ref.logits(params, models["hybrid_conf"], toks)
            out.append(int(np.asarray(lg)[0, len(seq) - 1].argmax()))
            seq.append(out[-1])
        return out
    cfg, params = models["mixed" if mode == "mixed" else "kv"]
    out = tfm.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, eos_id=eos_id)
    return [int(t) for t in np.asarray(out)[0]]


def _step(srv, tr):
    """One step(); the ring's events it wrote, as (name, args) of every
    span begin and instant, in order."""
    n0 = len(tr.snapshot())
    more = srv.step()
    return more, [(e[NAME], e[ARGS] or {}) for e in tr.snapshot()[n0:]
                  if e[PH] in "Bi"]


def _names(events):
    """The reads, and the begin of the `serving.dispatch` span whose
    `prog` is the decode step's (every other program's span: an
    admission's chunks, probe and splice, is left out)."""
    return [n for n, a in events if n in READS
            or (n == DISPATCH and a.get("prog") == STEP_PROG)]


def _behind(events, name):
    return [a["behind"] for n, a in events if n == name]


# -- the lagged order ----------------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_reads_follow_the_dispatch_and_lag_one_step(models, ring, mode):
    srv = _server(models, mode)
    experts = srv.cfg.n_experts > 0
    reqs = [(_prompt(5, 1), 3), (_prompt(3, 2), 7), (_prompt(6, 3), 4)]
    a, b, c = (srv.submit(p, max_new=m) for p, m in reqs)

    # step 1 admits A and B: both first tokens are read after the
    # step's dispatch, with that step queued behind them
    _, ev = _step(srv, ring)
    assert _names(ev) == [DISPATCH] + 2 * ["serving.first_token.wait"]
    assert _behind(ev, "serving.first_token.wait") == [1, 1]
    assert srv.live_positions() == {0: 5 + 1, 1: 3 + 1}
    assert len(srv._buf) == 1 and not srv._seeds
    assert [len(r.tokens) for r in srv._slot_req] == [1, 1]

    # step 2 dispatches A's last token: the slot frees, nothing is read
    _, ev = _step(srv, ring)
    assert _names(ev) == [DISPATCH]
    assert srv._slot_req[0] is None and len(srv._buf) == 2
    assert a not in srv._done

    # step 3 admits C and dispatches; THEN C's first token, then the
    # read step 2 asked for: steps 1 and 2, step 3 stays buffered
    _, ev = _step(srv, ring)
    want = [DISPATCH, "serving.first_token.wait"] + 2 * ["serving.flush.wait"]
    if experts:
        want += 2 * ["serving.flush.moe_stats.wait"]
    assert _names(ev) == want
    assert _behind(ev, "serving.flush.wait") == [2, 1]
    assert len(srv._buf) == 1 and len(srv._moe_buf) == int(experts)
    assert srv._done[a] == _generate(models, mode, *reqs[0])
    assert len(srv._slot_req[1].tokens) == 3        # seed + steps 1, 2

    # flush() drains: the newest step's read has nothing behind it
    n0 = len(ring.snapshot())
    srv.flush()
    ev = [(e[NAME], e[ARGS] or {}) for e in ring.snapshot()[n0:]
          if e[PH] == "B"]
    assert _behind(ev, "serving.flush.wait") == [0]
    assert not srv._buf and not srv._moe_buf

    # run()'s end drains too, and every request is generate()'s
    out = srv.run()
    assert not srv._buf and not srv._moe_buf and not srv._seeds
    assert out == {rid: _generate(models, mode, p, m)
                   for rid, (p, m) in zip((a, b, c), reqs)}

    # the counter counts what the ring saw
    waits = [e[ARGS]["behind"] for e in ring.snapshot()
             if e[PH] == "B" and e[NAME] in READS]
    st = srv.read_stats()
    assert st == {"reads_overlapped": sum(b > 0 for b in waits),
                  "reads_draining": sum(b == 0 for b in waits)}
    assert st["reads_overlapped"] > st["reads_draining"] > 0
    inst = srv.counter_instance
    got = {n.rsplit("/", 1)[1]: pc.query_counter(n).value for n in
           pc.discover_counters(f"/serving{{locality#*/{inst}}}/reads/*")}
    assert got == {"overlapped": st["reads_overlapped"],
                   "draining": st["reads_draining"]}


@pytest.mark.parametrize("mode", MODES)
def test_a_full_buffer_is_read_one_step_late_too(models, ring, mode):
    """`max_async_steps` buffered: the read is due, the NEXT step makes
    it after its dispatch and keeps its own step."""
    srv = _server(models, mode, slots=1)
    srv._max_async = 3
    p = _prompt(4, 5)
    rid = srv.submit(p, max_new=12)
    sizes = []
    while srv.step():
        sizes.append(len(srv._buf))
        assert len(srv._moe_buf) in (0, len(srv._buf))
    # 1, 2, 3 (full: due), then 4 -> 1 after the read, 2, 3, ...
    assert sizes[:7] == [1, 2, 3, 1, 2, 3, 1]
    assert srv.poll_finished() == {rid: _generate(models, mode, p, 12)}
    behind = [e[ARGS]["behind"] for e in ring.snapshot()
              if e[PH] == "B" and e[NAME] == "serving.flush.wait"]
    assert behind[:3] == [3, 2, 1] and behind.count(0) == 1


# -- where everything is drained -------------------------------------------

@pytest.mark.parametrize("mode", MODES)
def test_a_step_with_nothing_live_drains(models, mode):
    srv = _server(models, mode)
    p = _prompt(4, 7)
    rid = srv.submit(p, max_new=2)
    assert srv.step()               # admitted, dispatched its last token
    assert len(srv._buf) == 1 and srv.live_positions() == {}
    assert rid not in srv._done and len(srv._queue) == 0
    assert not srv.step()           # nothing live: everything lands
    assert not srv._buf and not srv._moe_buf
    assert srv.poll_finished() == {rid: _generate(models, mode, p, 2)}


@pytest.mark.parametrize("site", ["decode", "prefill"])
@pytest.mark.parametrize("mode", MODES)
def test_recover_drains_with_a_step_in_flight(models, mode, site):
    srv = _server(models, mode, prefill_chunk=2)
    reqs = [(_prompt(5, 11), 9), (_prompt(7, 12), 6), (_prompt(3, 13), 5)]
    rids = [srv.submit(p, max_new=m) for p, m in reqs]
    seen = []
    recover = srv._recover

    def spy(attempt, exc):
        before = len(srv._buf), len(srv._seeds)
        recover(attempt, exc)
        seen.append((before, len(srv._buf), len(srv._moe_buf),
                     len(srv._seeds)))
    srv._recover = spy
    fi = faultinject.install(faultinject.FaultInjector(
        schedule={site: {4, 7}}))
    try:
        out = srv.run()
    finally:
        faultinject.uninstall()
    assert fi.total_injected == 2 and len(seen) == 2
    assert any(before[0] >= 1 for before, *_ in seen)   # a step in flight
    assert all(after == [0, 0, 0] for _, *after in seen)
    assert out == {rid: _generate(models, mode, p, m)
                   for rid, (p, m) in zip(rids, reqs)}
    assert srv.failed == {}


# -- the kept order: read, then dispatch ---------------------------------

KEPT = {
    "eos_id": (dict(), dict(eos_id=63)),
    "max_new_1": (dict(), dict(max_new=1)),
    "spec": (dict(spec=True), dict()),
    "sync": (dict(async_dispatch=False), dict()),
}


@pytest.mark.parametrize("kind", sorted(KEPT))
@pytest.mark.parametrize("mode", MODES)
def test_inputs_that_need_the_value_keep_todays_order(models, ring, mode,
                                                      kind):
    srv_kw, req_kw = KEPT[kind]
    if kind == "spec" and mode in ("mixed", "hybrid"):
        with pytest.raises(NotImplementedError,
                           match="speculative verify"):
            _server(models, mode, **srv_kw)
        return
    srv = _server(models, mode, **srv_kw)
    p, max_new = _prompt(5, 21), req_kw.get("max_new", 6)
    rid = srv.submit(p, max_new=max_new, eos_id=req_kw.get("eos_id"))
    more, ev = _step(srv, ring)
    names = _names(ev)
    # the first token is read BEFORE anything else of the step is
    # dispatched, with nothing behind it ...
    assert names[0] == "serving.first_token.wait"
    assert _behind(ev, "serving.first_token.wait") == [0]
    if kind == "max_new_1":
        assert not more and names == names[:1]      # retired in admission
    elif kind == "spec":
        assert DISPATCH not in names                # _spec_step's own read
    else:
        # ... and the step's tokens right after its own dispatch
        assert names[1:3] == [DISPATCH, "serving.flush.wait"]
        assert _behind(ev, "serving.flush.wait") == [0]
    while more:
        assert not srv._buf and not srv._moe_buf    # nothing ever lags
        more, ev = _step(srv, ring)
        assert all(a["behind"] == 0 for n, a in ev if n in READS)
    assert srv.poll_finished() == {
        rid: _generate(models, mode, p, max_new, req_kw.get("eos_id"))}
    assert srv.read_stats()["reads_overlapped"] == 0


def test_an_eos_request_beside_a_plain_one_drains_both(models, ring):
    """One request that needs its values makes every read of the batch
    immediate; once it is gone the other's reads lag again."""
    srv = _server(models, "paged")
    p1, p2 = _prompt(4, 31), _prompt(6, 32)
    a = srv.submit(p1, max_new=3, eos_id=63)
    b = srv.submit(p2, max_new=9)
    lag = []
    while srv.step():
        lag.append(len(srv._buf))
    assert lag[:2] == [0, 0] and max(lag) >= 1
    assert srv.poll_finished() == {
        a: _generate(models, "paged", p1, 3, 63),
        b: _generate(models, "paged", p2, 9)}
