"""Fleet serving (svc/fleet.py): prefix-cache-aware placement over
N prefill x M decode workers must stay BYTE-IDENTICAL to single-server
``tfm.generate`` — through mesh-sharded decode pools, prefix-seeded
prefills (the placement hit that SKIPS prompt compute), seeded
per-role worker kills, and autoscale up/down cycles — with zero KV
blocks leaked anywhere, including by workers the autoscaler retired.

The placement policy itself (digest pull, longest-match scoring,
eviction-rate pressure) is pinned by asserting a shared-prefix warm
wave lands digest-matched (``placed_prefix``) and actually saves
prefill tokens; ``placement=load`` degrades to the base least-loaded
router and must save nothing.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.core.config import runtime_config
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.disagg import DecodeWorker
from hpx_tpu.svc import faultinject
from hpx_tpu.svc import performance_counters as pc
from hpx_tpu.svc import tracing
from hpx_tpu.svc.fleet import FleetRouter

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))


@pytest.fixture()
def fresh_digests(monkeypatch):
    """Digest freshness window 0: every placement re-pulls, so the
    tests see the workers' REAL trees, not a stale mirror."""
    monkeypatch.setitem(runtime_config()._data,
                        "hpx.serving.fleet.digest_refresh_s", "0")


def _ref(params, prompt, max_new, temperature=0.0, key=None):
    out = tfm.generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=temperature,
                       key=key)
    return [int(t) for t in np.asarray(out)[0]]


def _mix(n=6, seed=7, prefix=()):
    """Mixed greedy/sampled requests; a shared `prefix` models the
    Zipf head (system prompt) the placement policy routes on."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        tail = [int(t) for t in
                rng.integers(1, 64, int(rng.integers(3, 12)))]
        temp = 0.8 if i % 2 else 0.0
        key = jax.random.PRNGKey(100 + i) if temp else None
        reqs.append((list(prefix) + tail, 5 + i, temp, key))
    return reqs


def _submit_all(r, reqs):
    return [r.submit(p, mn, temperature=t, key=k)
            for (p, mn, t, k) in reqs]


def _check(out, rids, reqs, params):
    for rid, (p, mn, t, k) in zip(rids, reqs):
        assert out[rid] == _ref(params, p, mn, temperature=t, key=k)


# ---------------------------------------------------------------------------
# fault-free N x M identity: one-slot prefill -> mesh-sharded decode
# ---------------------------------------------------------------------------

def test_fleet_mesh_decode_matches_generate(params, mesh,
                                            fresh_digests):
    reqs = _mix(6)
    r = FleetRouter(params, CFG, prefill_workers=2, decode_workers=2,
                    slots=4, smax=64, decode_mesh=mesh)
    rids = _submit_all(r, reqs)
    out = r.run()
    _check(out, rids, reqs, params)
    st = r.stats()
    assert st["failovers"] == {"prefill": 0, "decode": 0}
    assert st["decode_pool"] == 2
    r.close()
    assert r.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# the headline: shared-prefix traffic routes to its cached blocks and
# skips prefill compute — tokens unchanged
# ---------------------------------------------------------------------------

def test_warm_prefix_wave_places_by_digest_and_saves(params,
                                                     fresh_digests):
    shared = [7, 3, 1, 9, 2, 8, 4, 6, 5, 1, 2, 3, 9, 8, 7, 6, 5, 4,
              3, 2]
    r = FleetRouter(params, CFG, prefill_workers=2, decode_workers=2,
                    slots=4, smax=64)
    cold = _mix(4, seed=11, prefix=shared)
    rids = _submit_all(r, cold)
    _check(r.run(), rids, cold, params)
    st0 = r.stats()

    warm = _mix(4, seed=23, prefix=shared)
    rids = _submit_all(r, warm)
    _check(r.run(), rids, warm, params)
    st1 = r.stats()

    # every warm request shares >= 1 full cached block: digest-matched
    # placement, and the matched rows seeded the prefill
    assert st1["placed_prefix"] - st0["placed_prefix"] >= 3
    assert st1["prefill_tokens_saved"] > st0["prefill_tokens_saved"]
    r.close()
    assert r.leaked_blocks() == 0


def test_load_placement_mode_saves_nothing(params, monkeypatch,
                                           fresh_digests):
    monkeypatch.setitem(runtime_config()._data,
                        "hpx.serving.fleet.placement", "load")
    shared = [5, 5, 4, 4, 3, 3, 2, 2, 1, 1, 9, 9, 8, 8, 7, 7, 6, 6]
    r = FleetRouter(params, CFG, prefill_workers=2, decode_workers=2,
                    slots=4, smax=64)
    for wave_seed in (11, 23):
        reqs = _mix(4, seed=wave_seed, prefix=shared)
        rids = _submit_all(r, reqs)
        _check(r.run(), rids, reqs, params)
    st = r.stats()
    assert st["placed_prefix"] == 0
    assert st["prefill_tokens_saved"] == 0
    assert st["placed_load"] == 8
    r.close()
    assert r.leaked_blocks() == 0


def test_bad_placement_knob_rejected(params, monkeypatch):
    monkeypatch.setitem(runtime_config()._data,
                        "hpx.serving.fleet.placement", "random")
    with pytest.raises(ValueError):
        FleetRouter(params, CFG, prefill_workers=1, decode_workers=1,
                    slots=2, smax=64)


# ---------------------------------------------------------------------------
# failover: one seeded kill per role -> identical tokens, no leak
# ---------------------------------------------------------------------------

def _run_fleet(params, reqs, schedule=None, **fleet_kw):
    inj = None
    if schedule is not None:
        inj = faultinject.install(
            faultinject.FaultInjector(schedule=schedule))
    try:
        r = FleetRouter(params, CFG, prefill_workers=2,
                        decode_workers=2, slots=3, smax=64, **fleet_kw)
        rids = _submit_all(r, reqs)
        out = r.run()
        stats = r.stats()
        r.close()
        leak = r.leaked_blocks()
    finally:
        if inj is not None:
            faultinject.uninstall()
    return [out[rid] for rid in rids], stats, leak


def test_fleet_decode_worker_death_replays_identically(params,
                                                       fresh_digests):
    reqs = _mix(6)
    base, _, _ = _run_fleet(params, reqs)
    out, stats, leak = _run_fleet(
        params, reqs, schedule={"disagg.decode": {12}})
    assert out == base
    assert stats["failovers"]["decode"] >= 1
    assert not stats["degraded"]
    assert leak == 0


def test_fleet_prefill_worker_death_restarts_identically(params,
                                                         fresh_digests):
    reqs = _mix(6)
    base, _, _ = _run_fleet(params, reqs)
    out, stats, leak = _run_fleet(
        params, reqs, schedule={"disagg.prefill": {6}})
    assert out == base
    assert stats["failovers"]["prefill"] >= 1
    assert not stats["degraded"]
    assert leak == 0


# ---------------------------------------------------------------------------
# autoscaling: queue-depth up, idle-streak drain down — zero leaks
# either way, including blocks owned by RETIRED workers
# ---------------------------------------------------------------------------

def test_autoscale_up_on_queue_depth(params, monkeypatch,
                                     fresh_digests):
    for k, v in (("scale_high", "3"), ("decode_pool_max", "3")):
        monkeypatch.setitem(runtime_config()._data,
                            f"hpx.serving.fleet.{k}", v)
    reqs = _mix(6)
    r = FleetRouter(params, CFG, prefill_workers=2, decode_workers=2,
                    slots=3, smax=64)
    rids = _submit_all(r, reqs)
    out = r.run()
    _check(out, rids, reqs, params)
    st = r.stats()
    assert st["autoscale_up"] >= 1
    assert st["decode_pool"] == 3
    r.close()
    assert r.leaked_blocks() == 0


def test_autoscale_down_drains_idle_worker(params, monkeypatch,
                                           fresh_digests):
    monkeypatch.setitem(runtime_config()._data,
                        "hpx.serving.fleet.idle_ticks", "3")
    reqs = _mix(4)
    r = FleetRouter(params, CFG, prefill_workers=2, decode_workers=2,
                    slots=3, smax=64)
    rids = _submit_all(r, reqs)
    _check(r.run(), rids, reqs, params)
    assert r.stats()["decode_pool"] == 2
    # idle ticks accumulate only while the router steps; a few empty
    # ticks past the streak threshold drain the newest worker down to
    # the pool floor (decode_pool_min=1) and no further
    for _ in range(6):
        r.step()
    st = r.stats()
    assert st["autoscale_down"] == 1
    assert st["decode_pool"] == 1
    # the survivor still serves, and the retired worker's blocks are
    # in the ledger, not leaked
    more = _mix(2, seed=31)
    rids = _submit_all(r, more)
    _check(r.run(), rids, more, params)
    r.close()
    assert r.leaked_blocks() == 0


def test_drain_with_inflight_work_redispatches(params, fresh_digests):
    """The PR 8 rule on the autoscale drain path: _retire re-dispatches
    everything the draining worker owns through _failover_decode
    (router state commits before the risky send), so a drain with
    work in flight is just a failover with a planned death."""
    reqs = _mix(5)
    base, _, _ = _run_fleet(params, reqs)
    r = FleetRouter(params, CFG, prefill_workers=2, decode_workers=2,
                    slots=3, smax=64)
    rids = _submit_all(r, reqs)
    victim = None
    while victim is None:
        r.step()
        owned = [q for q in r._reqs.values()
                 if q.state in ("prefill", "decode")
                 and q.decode_h is not None]
        if owned:
            victim = owned[0].decode_h
    n_owned = len(owned)
    victim.draining = True
    r._retire(victim)
    # every request the victim owned re-homed onto the survivor (a
    # planned drain is not a failure, so `failovers` stays clean)
    assert victim not in r._decode
    rehomed = [q for q in r._reqs.values()
               if q.state in ("prefill", "decode")
               and q.decode_h is not None]
    assert len(rehomed) >= n_owned
    assert all(q.decode_h is not victim for q in rehomed)
    out = r.run()
    assert [out[rid] for rid in rids] == base
    st = r.stats()
    assert st["failovers"] == {"prefill": 0, "decode": 0}
    assert st["autoscale_down"] == 1
    assert st["decode_pool"] == 1
    r.close()
    assert r.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# observability: /serving fleet counters + placement spans/flows
# ---------------------------------------------------------------------------

def test_fleet_counters_registered_and_stable(params, fresh_digests):
    r = FleetRouter(params, CFG, prefill_workers=1, decode_workers=2,
                    slots=2, smax=64)
    inst = r.counter_instance
    names = pc.discover_counters(f"/serving{{*{inst}}}*")
    short = {n.split("}/", 1)[1] for n in names}
    assert {"fleet/placed/prefix", "fleet/placed/load",
            "fleet/digest/staleness-s", "fleet/autoscale/up",
            "fleet/autoscale/down", "fleet/prefill-tokens/saved",
            "fleet/workers/decode",
            "fleet/queue/depth"} <= short
    # per-worker depth registers to the autoscale CEILING: indexes
    # past the live pool read 0 rather than vanishing from discovery
    depth_names = sorted(n for n in names if "worker#" in n)
    assert len(depth_names) == r._pool_max
    assert pc.query_counter(depth_names[-1]).value == 0.0
    rid = r.submit([1, 2, 3, 4, 5], 4)
    out = r.run()
    assert out[rid] == _ref(params, [1, 2, 3, 4, 5], 4)
    workers = [n for n in names if n.endswith("fleet/workers/decode")]
    assert pc.query_counter(workers[0]).value == 2.0
    r.close()


def test_placement_spans_and_flow_arrows(params, fresh_digests):
    shared = [9, 1, 8, 2, 7, 3, 6, 4, 5, 5, 4, 6, 3, 7, 2, 8, 1, 9]
    r = FleetRouter(params, CFG, prefill_workers=1, decode_workers=2,
                    slots=3, smax=64)
    cold = _mix(3, seed=5, prefix=shared)
    rids = _submit_all(r, cold)
    _check(r.run(), rids, cold, params)
    tr = tracing.start_tracing(sample_counters=False)
    try:
        warm = _mix(3, seed=6, prefix=shared)
        rids = _submit_all(r, warm)
        _check(r.run(), rids, warm, params)
    finally:
        tracing.stop_tracing()
    ev = tr.snapshot()
    names = [(e[0], e[1]) for e in ev]
    assert ("B", "serving.fleet.place") in names
    assert ("B", "serving.fleet.admit") in names
    placed = [e for e in ev
              if e[0] == "i" and e[1] == "serving.fleet.placed"]
    assert any(e[7]["by"] == "prefix" for e in placed)
    # the placement -> admit flow arrow: tail (s) in the place span,
    # head (f) bound at admit, same id
    tails = {e[5] for e in ev
             if e[0] == "s" and e[1] == "serving.fleet.place"}
    heads = {e[5] for e in ev
             if e[0] == "f" and e[1] == "serving.fleet.place"}
    assert tails and tails & heads
    r.close()
    assert r.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# unified construction: DecodeWorker(mesh=) is ContinuousServer(mesh=)
# ---------------------------------------------------------------------------

def test_decode_worker_mesh_passthrough(params, mesh):
    solo = DecodeWorker(params, CFG, slots=2, smax=64)
    assert solo.srv.mesh is None
    assert solo.srv.block_size == solo.block_size()
    sharded = DecodeWorker(params, CFG, slots=2, smax=64, mesh=mesh)
    assert sharded.srv.mesh is mesh
    solo.close()
    sharded.close()
