"""Disaggregated prefill/decode serving (models/disagg.py +
cache/transfer.py): the router's tokens must be BYTE-IDENTICAL to
single-server ``tfm.generate`` references in every topology state —
fault-free, after a decode-worker death (replay from transferred KV on
a survivor), after a prefill-worker death (suffix-only restart from
retained segments), and fully degraded to colocated — with zero KV
blocks leaked by any path, including close() with work in flight.

The transfer protocol itself (framing, checksums, idempotent
re-delivery) is tested at the KVSegment/TransferReceiver level, and
the dist-layer robustness additions (Runtime.finalize failing pending
parcels typed, resilient_action retry/timeout) ride along here.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.core.errors import LocalityLost, NetworkError
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.disagg import (DecodeWorker, DisaggRouter,
                                   InProcHandle, PrefillWorker)
from hpx_tpu.models.serving import RequestShedError, ServerClosedError
from hpx_tpu.svc import faultinject

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _ref(params, prompt, max_new, temperature=0.0, key=None,
         eos_id=None):
    out = tfm.generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, temperature=temperature,
                       key=key, eos_id=eos_id)
    return [int(t) for t in np.asarray(out)[0]]


def _mix(n=5, seed=7):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        prompt = [int(t) for t in
                  rng.integers(1, 64, int(rng.integers(3, 24)))]
        temp = 0.8 if i % 2 else 0.0
        key = jax.random.PRNGKey(100 + i) if temp else None
        reqs.append((prompt, 6 + i, temp, key))
    return reqs


def _run_router(params, reqs, schedule=None, **router_kw):
    inj = None
    if schedule is not None:
        inj = faultinject.install(
            faultinject.FaultInjector(schedule=schedule))
    try:
        r = DisaggRouter(params, CFG, prefill_workers=2,
                         decode_workers=2, slots=3, smax=64,
                         **router_kw)
        for (p, mn, t, k) in reqs:
            r.submit(p, mn, temperature=t, key=k)
        out = r.run()
        stats = r.stats()
        r.close()
        leak = r.leaked_blocks()
    finally:
        if inj is not None:
            faultinject.uninstall()
    return out, stats, leak


# ---------------------------------------------------------------------------
# fault-free: disagg == generate, greedy and sampled
# ---------------------------------------------------------------------------

def test_disagg_matches_generate(params):
    reqs = _mix()
    out, stats, leak = _run_router(params, reqs)
    for rid, (p, mn, t, k) in enumerate(reqs):
        assert out[rid] == _ref(params, p, mn, temperature=t, key=k)
    assert stats["failovers"] == {"prefill": 0, "decode": 0}
    assert leak == 0


def test_disagg_single_workers_and_eos(params):
    # 1 prefill + 1 decode worker; eos early-exit must survive the
    # admit_prefilled path (seed token counts toward eos)
    r = DisaggRouter(params, CFG, prefill_workers=1, decode_workers=1,
                     slots=2, smax=64)
    prompt = [5, 9, 13, 21, 2]
    want = _ref(params, prompt, 12, eos_id=3)
    rid = r.submit(prompt, 12, eos_id=3)
    out = r.run()
    assert out[rid] == want
    r.close()
    assert r.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# failover: one seeded kill per role -> identical tokens, no leak
# ---------------------------------------------------------------------------

def test_decode_worker_death_replays_identically(params):
    reqs = _mix()
    base, _, _ = _run_router(params, reqs)
    out, stats, leak = _run_router(
        params, reqs, schedule={"disagg.decode": {5}})
    assert out == base
    assert stats["failovers"]["decode"] >= 1
    assert not stats["degraded"]
    assert leak == 0


def test_prefill_worker_death_restarts_suffix_only(params):
    reqs = _mix()
    base, _, _ = _run_router(params, reqs)
    out, stats, leak = _run_router(
        params, reqs, schedule={"disagg.prefill": {7}})
    assert out == base
    assert stats["failovers"]["prefill"] >= 1
    assert not stats["degraded"]
    assert leak == 0


def test_both_roles_die_same_run(params):
    reqs = _mix()
    base, _, _ = _run_router(params, reqs)
    out, stats, leak = _run_router(
        params, reqs,
        schedule={"disagg.prefill": {3}, "disagg.decode": {9}})
    assert out == base
    assert stats["failovers"]["prefill"] >= 1
    assert stats["failovers"]["decode"] >= 1
    assert leak == 0


def test_total_role_loss_degrades_to_colocated(params):
    reqs = _mix()
    base, _, _ = _run_router(params, reqs)
    for schedule in ({"disagg.prefill": {2, 5}},
                     {"disagg.decode": {1, 3}}):
        out, stats, leak = _run_router(params, reqs,
                                       schedule=schedule)
        assert out == base, schedule
        assert stats["degraded"]
        assert leak == 0


# ---------------------------------------------------------------------------
# admission: SLO classes, bounded queue, typed shedding
# ---------------------------------------------------------------------------

def test_batch_sheds_before_interactive(params, monkeypatch):
    from hpx_tpu.core.config import runtime_config
    monkeypatch.setitem(runtime_config()._data,
                        "hpx.serving.disagg.max_queue", "2")
    r = DisaggRouter(params, CFG, prefill_workers=1, decode_workers=1,
                     slots=2, smax=64)
    r0 = r.submit([1, 2, 3], 4, slo="interactive")
    rb = r.submit([4, 5, 6], 4, slo="batch")
    # queue full: the BATCH request sheds to admit interactive work
    r2 = r.submit([7, 8, 9], 4, slo="interactive")
    assert isinstance(r.failed[rb], RequestShedError)
    # full of interactive work: the incoming interactive sheds itself
    r3 = r.submit([2, 4, 6], 4, slo="interactive")
    assert isinstance(r.failed[r3], RequestShedError)
    out = r.run()
    assert set(out) == {r0, r2}
    for rid, prompt in ((r0, [1, 2, 3]), (r2, [7, 8, 9])):
        assert out[rid] == _ref(params, prompt, 4)
    r.close()
    assert r.leaked_blocks() == 0


def test_submit_after_close_raises_typed(params):
    r = DisaggRouter(params, CFG, prefill_workers=1, decode_workers=1,
                     slots=2, smax=64)
    r.submit([1, 2, 3], 3)
    r.close()               # drains the in-flight request first
    with pytest.raises(ServerClosedError):
        r.submit([4, 5, 6], 3)
    assert r.leaked_blocks() == 0


def test_close_without_drain_sheds_typed_and_releases(params):
    r = DisaggRouter(params, CFG, prefill_workers=1, decode_workers=1,
                     slots=2, smax=64)
    rids = [r.submit([i + 1, i + 2, i + 3], 8) for i in range(4)]
    r.step()                # some prefills/transfers now in flight
    r.close(drain=False)
    for rid in rids:
        assert rid in r.results or isinstance(r.failed.get(rid),
                                              RequestShedError)
    assert r.leaked_blocks() == 0
    with pytest.raises(ServerClosedError):
        r.submit([9], 2)


def test_bad_slo_rejected(params):
    r = DisaggRouter(params, CFG, prefill_workers=1, decode_workers=1,
                     slots=2, smax=64)
    with pytest.raises(ValueError):
        r.submit([1, 2], 4, slo="best-effort")
    r.close()


# ---------------------------------------------------------------------------
# the transfer protocol: framing, checksums, idempotent re-delivery
# ---------------------------------------------------------------------------

def test_segment_checksum_and_idempotent_redelivery():
    from hpx_tpu.cache.transfer import (TransferCorruptError,
                                        TransferReceiver, make_segment)
    rows = np.arange(2 * 2 * 8 * 2 * 4, dtype=np.float32).reshape(
        2, 2, 8, 2, 4)
    recv = TransferReceiver()
    a = make_segment("r1", 0, 0, 12, rows)
    b = make_segment("r1", 1, 8, 12, rows[:, :, :4])
    assert recv.ingest(a)["dup"] is False
    # duplicate delivery (lost ACK): re-acked, not re-applied
    assert recv.ingest(a)["dup"] is True
    assert recv.stats()["dups"] == 1
    assert not recv.complete("r1")
    assert recv.ingest(b)["dup"] is False
    assert recv.complete("r1")
    got = recv.assemble("r1")
    assert got.shape == (2, 2, 12, 2, 4)
    np.testing.assert_array_equal(got[:, :, :8], rows)
    # corruption: a tampered payload fails verification loudly
    import dataclasses
    bad = dataclasses.replace(a, payload=rows + 1.0)
    with pytest.raises(TransferCorruptError):
        recv.ingest(bad)
    assert recv.stats()["corrupt"] == 1


def test_receiver_abort_drops_segments():
    from hpx_tpu.cache.transfer import TransferReceiver, make_segment
    rows = np.zeros((1, 2, 4, 2, 4), np.float32)
    recv = TransferReceiver()
    recv.ingest(make_segment("r9", 0, 0, 8, rows))
    recv.abort("r9")
    assert recv.pending() == []
    # late duplicate for an aborted rid: acked and dropped
    assert recv.ingest(make_segment("r9", 0, 0, 8, rows))["dup"] is True


def test_wire_faults_between_router_and_decode(params):
    # parcel.drop/dup-shaped trouble on the segment path: drops raise
    # through the resilient send (router re-ships), dups dedup — the
    # decode output stays byte-identical either way
    reqs = _mix(3)
    base, _, _ = _run_router(params, reqs)

    class FlakyHandle(InProcHandle):
        """Delivers every segment twice (duplicate ACK lost on the
        'wire'), and drops the first delivery of segment seq 1."""

        def __init__(self, worker):
            super().__init__("decode", worker)
            self.dropped = False

        def call(self, method, *args, **kwargs):
            if method == "ingest":
                seg = args[0]
                if seg.seq == 1 and not self.dropped:
                    self.dropped = True
                    raise LocalityLost(
                        0, "injected parcel drop", "FlakyHandle")
                out = super().call(method, *args, **kwargs)
                super().call(method, *args, **kwargs)   # duplicate
                return out
            return super().call(method, *args, **kwargs)

    # a dropped segment surfaces as a connectivity error -> the router
    # fails the handle over; with a second (clean) worker the run
    # completes identically
    flaky = FlakyHandle(DecodeWorker(params, CFG, slots=3, smax=64))
    clean = InProcHandle("decode",
                         DecodeWorker(params, CFG, slots=3, smax=64))
    bs = clean.call("block_size")
    r = DisaggRouter(
        params, CFG, prefill_workers=1, slots=3, smax=64,
        decode_handles=[flaky, clean])
    for (p, mn, t, k) in reqs:
        r.submit(p, mn, temperature=t, key=k)
    out = r.run()
    assert out == base
    assert r.stats()["failovers"]["decode"] >= 1
    r.close()
    assert r.leaked_blocks() == 0
    # the double-deliveries before the drop hit the flaky worker's
    # receiver and were deduplicated there, not re-applied
    assert flaky.worker.recv.stats()["dups"] >= 1


def test_prefill_segments_block_aligned(params):
    w = PrefillWorker(params, CFG, smax=64, block_size=4)
    prompt = list(range(1, 12))          # plen 11: cap = 8, final 8..11
    w.start("j", prompt)
    segs, seed = [], None
    while True:
        out = w.step("j")
        segs.extend(out["segments"])
        if out["done"]:
            seed = out["seed"]
            break
    assert [(s.start, s.ntok) for s in segs] == [(0, 4), (4, 4), (8, 3)]
    assert all(s.total == 11 for s in segs)
    assert [s.seq for s in segs] == [0, 1, 2]
    assert seed == _ref(params, prompt, 1)[0]
    for s in segs:
        s.verify()


# ---------------------------------------------------------------------------
# fault-site plumbing: deterministic streams for the chaos harness
# ---------------------------------------------------------------------------

def test_disagg_fault_sites_registered_and_deterministic():
    assert "disagg.prefill" in faultinject.SITES
    assert "disagg.decode" in faultinject.SITES
    for site in ("parcel.drop", "parcel.dup", "parcel.delay",
                 "net.partition"):
        assert site in faultinject.SITES

    def draws(seed):
        fi = faultinject.FaultInjector(seed=seed, rate=0.3,
                                       sites=["parcel.drop"])
        return [fi.fires("parcel.drop") for _ in range(40)]

    assert draws(1) == draws(1)          # same seed -> same stream
    assert draws(1) != draws(2)
    # injected losses are the REAL typed error (failover code paths
    # cannot tell injected from organic)
    fi = faultinject.install(faultinject.FaultInjector(
        schedule={"disagg.decode": {1}}))
    try:
        with pytest.raises(LocalityLost) as ei:
            faultinject.check("disagg.decode", locality=4)
        assert isinstance(ei.value, NetworkError)
        assert ei.value.locality == 4
    finally:
        faultinject.uninstall()


# ---------------------------------------------------------------------------
# dist-layer rides-along: finalize fails pending parcels typed
# ---------------------------------------------------------------------------

def test_finalize_fails_pending_parcels_typed():
    from hpx_tpu.dist.runtime import Runtime
    from hpx_tpu.futures.future import SharedState
    rt = Runtime.__new__(Runtime)      # no bootstrap: single-process
    import threading
    rt.locality = 0
    rt.num_localities = 1              # skips the barrier/drain path
    rt._stopped = False
    rt._hb_thread = None
    rt._hb_stop = threading.Event()
    rt._coalescer = None
    rt._endpoint = None
    rt._pending_lock = threading.Lock()
    st = SharedState()
    rt._pending = {7: st}
    rt._pending_dst = {7: 3}
    rt.finalize()
    with pytest.raises(LocalityLost) as ei:
        from hpx_tpu.futures.future import Future
        Future(st).get(timeout=1.0)
    assert ei.value.locality == 3


# ---------------------------------------------------------------------------
# export_prefix_rows / fetch_prefix round-trip on quantized pools
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvd", ["fp8", "int8"])
def test_export_fetch_prefix_roundtrip_quantized(params, kvd):
    """A quantized pool's exported prefix rows must (a) be exactly the
    dequantized pool bytes (scale-sidecar path) and (b) survive the
    full wire round-trip: fetch_prefix → KVSegment framing → ingest →
    admit_prefilled, decoding the SAME tokens the publisher emitted."""
    from hpx_tpu.cache.transfer import make_segment
    from hpx_tpu.models.serving import ContinuousServer

    rng = np.random.default_rng(11)
    prompt = [int(t) for t in rng.integers(1, 64, 32)]

    src = DecodeWorker(params, CFG, slots=2, smax=64, kv_dtype=kvd,
                       block_size=8)
    srv = src.srv
    rid = srv.submit(prompt, max_new=6)
    base = srv.run()[rid]

    got = src.fetch_prefix(prompt)
    matched, rows = got["matched"], got["rows"]
    assert matched == len(prompt)
    assert rows.shape == (CFG.n_layers, 2, matched, CFG.kv_heads,
                          CFG.head_dim)

    # (a) rows == dequantized pool contents, bit-exact — same
    # elementwise ops the fused kernels apply
    assert srv._scales is not None           # fp8/int8 carry sidecars
    m2, bids = srv._radix.match(prompt)
    assert m2 == matched
    try:
        for li in range(CFG.n_layers):
            kp, vp = srv._pools[li]
            for side, pool in enumerate((kp, vp)):
                g = np.asarray(pool)[np.asarray(bids)]
                sc = np.asarray(srv._scales[li][side])[
                    np.asarray(bids)]
                # pools are [blocks, kv heads, rows, hd]; rows export
                # in token order
                ref = (g.astype(np.float32)
                       * sc[:, :, None, None]).swapaxes(1, 2).reshape(
                           matched, CFG.kv_heads, CFG.head_dim)
                np.testing.assert_array_equal(
                    rows[li, side], ref.astype(rows.dtype))
    finally:
        for b in bids:
            srv._alloc.decref(b)

    # (b) ship through the segment framing into a fresh worker with
    # the same pool dtype: identical tokens out
    dst = DecodeWorker(params, CFG, slots=2, smax=64, kv_dtype=kvd,
                       block_size=8)
    dst.ingest(make_segment("rt:0", 0, 0, matched, rows))
    dst.admit("rt:0", prompt, base[0], 6)
    done = {}
    for _ in range(200):
        res = dst.pump(4)
        done.update(res["done"])
        if not res["busy"] and not res["live"]:
            break
    assert done["rt:0"] == base
    assert src.leaked_blocks() == 0
    assert dst.leaked_blocks() == 0


# ---------------------------------------------------------------------------
# multi-process: real localities, real deaths (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_disagg_multiprocess_kill_one_worker_per_role():
    from hpx_tpu.run import launch
    rc = launch(os.path.join(REPO, "tests", "mp_scripts",
                             "disagg_smoke.py"),
                [], localities=5, timeout=540.0)
    assert rc == 0
