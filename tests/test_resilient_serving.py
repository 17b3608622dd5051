"""Fault-injected serving (svc/faultinject + ContinuousServer's
checkpoint/restore/shed ladder): a run with injected decode, chunked-
prefill, spec-verify and allocator-OOM faults must emit BYTE-IDENTICAL
tokens to the fault-free run (the differential contract makes restore
provable), leak zero KV blocks, and fail unrecoverable requests with
TYPED errors in `ContinuousServer.failed` instead of exceptions."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import (
    ContinuousServer,
    DeadlineExceededError,
    RequestShedError,
    ServerClosedError,
)
from hpx_tpu.svc import faultinject

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _ref(params, cfg, prompt, max_new, eos_id=None):
    out = tfm.generate(params, cfg,
                       jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, eos_id=eos_id)
    return [int(t) for t in np.asarray(out)[0]]


@contextlib.contextmanager
def _inject(**kw):
    fi = faultinject.install(faultinject.FaultInjector(**kw))
    try:
        yield fi
    finally:
        faultinject.uninstall()


REQS = [dict(prompt=[3, 1, 4, 1, 5], max_new=10),
        dict(prompt=[2, 7, 1], max_new=8),
        dict(prompt=[9, 9, 8, 2, 6, 5, 3], max_new=12),
        dict(prompt=[4, 4], max_new=6, temperature=0.9,
             key=jax.random.PRNGKey(7))]


def _serve(params, reqs=REQS, fi_kw=None, **srv_kw):
    srv = ContinuousServer(params, CFG, slots=2, smax=64, **srv_kw)
    for r in reqs:
        srv.submit(**r)
    if fi_kw is None:
        out = srv.run()
    else:
        with _inject(**fi_kw):
            out = srv.run()
    return out, srv


# -- kill-mid-decode ---------------------------------------------------------

def test_kill_mid_decode_default_block_identical(params):
    base, _ = _serve(params)
    got, srv = _serve(params, fi_kw=dict(
        schedule={"decode": {2, 5, 9}}))
    assert got == base
    st = srv.fault_stats()
    assert st["injected"] == 3 and st["restored"] >= 3
    assert st["shed"] == 0
    assert srv.failed == {}


def test_kill_mid_decode_paged_identical_no_leak(params):
    kw = dict(paged=True, block_size=8, num_blocks=64)
    base, srv0 = _serve(params, **kw)
    free0 = srv0._alloc.stats()["free"]
    got, srv = _serve(params, fi_kw=dict(
        schedule={"decode": {3, 7}}), **kw)
    assert got == base
    assert srv._alloc.stats()["free"] == free0
    assert srv.fault_stats()["restored_by_site"].get("decode", 0) >= 1


# -- kill-mid-chunked-prefill ------------------------------------------------

def test_kill_mid_chunked_prefill_identical(params):
    # prefill_chunk=2 over a 7-token prompt: a chunk check faults
    # while the prefill is PENDING and another slot decodes live —
    # recovery restarts the pending from the prompt AND restores the
    # live slot; the final tokens must not change
    base, _ = _serve(params, prefill_chunk=2)
    got, srv = _serve(params, prefill_chunk=2, fi_kw=dict(
        schedule={"prefill": {3}}))
    assert got == base
    assert srv.fault_stats()["restored_by_site"].get("prefill", 0) >= 1


def test_kill_mid_chunked_prefill_paged_no_leak(params):
    kw = dict(paged=True, block_size=8, num_blocks=64, prefill_chunk=2)
    base, srv0 = _serve(params, **kw)
    free0 = srv0._alloc.stats()["free"]
    got, srv = _serve(params, fi_kw=dict(
        schedule={"prefill": {2, 4}}), **kw)
    assert got == base
    assert srv._alloc.stats()["free"] == free0


# -- kill-mid-spec-verify ----------------------------------------------------

def test_kill_mid_spec_verify_identical(params):
    base, _ = _serve(params, spec=True)
    got, srv = _serve(params, spec=True, fi_kw=dict(
        schedule={"verify": {2}}))
    assert got == base
    assert srv.fault_stats()["restored_by_site"].get("verify", 0) >= 1
    assert not srv._spec_degraded        # one fault: below the ladder


def test_repeated_verify_faults_degrade_spec_identically(params):
    # hpx.serving.spec.max_verify_faults (default 2) consecutive
    # verify faults turn speculation OFF; the sequential path emits
    # the same tokens, so output is unchanged while fault_stats
    # records the degradation
    base, _ = _serve(params, spec=True)
    got, srv = _serve(params, spec=True, fi_kw=dict(
        schedule={"verify": {1, 2}}))
    assert got == base
    assert srv._spec_degraded and not srv._spec
    assert srv.fault_stats()["degraded"] == 1


# -- OOM during admission ----------------------------------------------------

def test_oom_during_admit_defers_then_identical(params):
    # prefix_reuse off -> the radix holds nothing to evict, so the
    # injected admission OOM escalates to the defer ladder; the
    # deferred request admits on a later step and ends identical
    kw = dict(paged=True, block_size=8, num_blocks=64,
              prefix_reuse=False)
    base, _ = _serve(params, **kw)
    got, srv = _serve(params, fi_kw=dict(
        schedule={"alloc": {1}}), **kw)
    assert got == base
    assert srv.failed == {}
    st = srv.fault_stats()
    assert st["injected"] >= 1 and st["retried"] >= 1


def test_admit_oom_persisting_sheds_typed(params):
    # every alloc check faults and nothing is evictable: the
    # admission ladder exhausts hpx.serving.admit_retries and sheds
    # with a typed RequestShedError instead of raising
    kw = dict(paged=True, block_size=8, num_blocks=64,
              prefix_reuse=False)
    srv = ContinuousServer(params, CFG, slots=2, smax=64, **kw)
    rid = srv.submit([3, 1, 4], max_new=4)
    with _inject(rate=1.0, sites=["alloc"], seed=1):
        out = srv.run()
    assert out == {}
    assert isinstance(srv.failed[rid], RequestShedError)
    assert srv.failed[rid].rid == rid
    assert srv.fault_stats()["shed"] == 1
    # no block leaked by the repeatedly-failed admissions
    assert srv._alloc.stats()["in_use"] == 1   # the trash block only


# -- checkpoint refcount accounting ------------------------------------------

def test_checkpoint_pins_release_on_retire(params):
    # while a request is live its checkpoint pins blocks (extra
    # refs); after run() every pin must be gone — the free count
    # matches a fault-free server's and nothing is left pinned
    kw = dict(paged=True, block_size=4, num_blocks=64)
    base, srv0 = _serve(params, **kw)
    free0 = srv0._alloc.stats()["free"]
    got, srv = _serve(params, fi_kw=dict(
        schedule={"decode": {4}, "prefill": {1}}), **kw)
    assert got == base
    assert srv._ckpt == {}
    assert srv._alloc.stats()["free"] == free0


def test_mixed_sites_identical(params):
    # all four fault classes in one seeded run, spec + paged
    kw = dict(paged=True, block_size=8, num_blocks=64, spec=True,
              prefill_chunk=2)
    base, _ = _serve(params, **kw)
    got, srv = _serve(params, fi_kw=dict(
        schedule={"verify": {2}, "prefill": {2}, "alloc": {6}}), **kw)
    assert got == base
    assert srv.failed == {}


# -- recovery with a step in flight (reads lag one step) ---------------------

# blocks of 4 rows and of 2: a checkpoint's pins are the full blocks
# below the host's frontier and its restore takes the frontier block
# over from the live table, so at 2 rows every other capture lands ON a
# seam (no frontier block to take over) and a restore spans many pins
BLOCKS = pytest.mark.parametrize("block_size", [4, 2],
                                 ids=["block4", "block2"])


@BLOCKS
@pytest.mark.parametrize("site", ["decode", "prefill"])
def test_fault_while_a_step_is_buffered_identical(params, monkeypatch, site,
                                                  block_size):
    """The decode loop reads one step behind its newest dispatch, so a
    fault finds a step (and, right after an admission, a seed token)
    that the host has not read: `_recover` lands them all, restores
    from checkpoints taken at the host's frontier, and the replay
    emits the fault-free tokens. prefill_chunk=2 keeps a chunked
    prefill pending beside a live decode."""
    kw = dict(prefill_chunk=2, block_size=block_size,
              num_blocks=256 // block_size)
    base, srv0 = _serve(params, **kw)
    buffered = []
    orig = ContinuousServer._recover

    def spy(self, attempt, exc):
        buffered.append(len(self._buf))
        orig(self, attempt, exc)
        assert not self._buf and not self._seeds
        assert all(r is None or r.sent == len(r.tokens)
                   for r in self._slot_req)
    monkeypatch.setattr(ContinuousServer, "_recover", spy)
    got, srv = _serve(params, fi_kw=dict(schedule={site: {3, 6, 8}}), **kw)
    assert got == base
    assert len(buffered) == 3 and max(buffered) >= 1
    assert srv.fault_stats()["restored_by_site"].get(site, 0) >= 1
    assert srv.failed == {} and srv._ckpt == {}
    assert srv._alloc.stats()["free"] == srv0._alloc.stats()["free"]


@BLOCKS
def test_checkpoints_advance_under_steady_decode(params, block_size):
    """One request, no retirement: the only reads are the lagged ones
    of a full buffer, each of which leaves a step in flight. The
    checkpoint still advances every ckpt_every tokens, at the frontier
    the host holds, and a fault restores from the newest."""
    kw = dict(block_size=block_size, num_blocks=256 // block_size)
    prompt, max_new, every = [3, 1, 4, 1, 5], 30, 4
    want = _ref(params, CFG, prompt, max_new)
    srv = ContinuousServer(params, CFG, slots=1, smax=64, **kw)
    srv._ckpt_every, srv._max_async = every, 2
    rid = srv.submit(prompt, max_new=max_new)
    seen = []                   # (tokens in the checkpoint, steps in flight)
    with _inject(schedule={"decode": {20}}):
        while srv.step():
            ck = srv._ckpt.get(0)
            if ck is None:
                continue
            n = len(ck.tokens)
            assert ck.tokens == want[:n] and ck.cur == want[n - 1]
            assert ck.pos == len(prompt) + n - 1
            if not seen or seen[-1][0] != n:
                seen.append((n, len(srv._buf)))
    sizes = [n for n, _ in seen]
    assert sizes[0] == 1                            # the seed checkpoint
    assert len(sizes) >= 5
    assert all(every <= b - a < every + 2 for a, b in zip(sizes, sizes[1:]))
    assert all(inflight == 1 for _, inflight in seen)   # no full flush
    assert srv.poll_finished() == {rid: want}
    st = srv.fault_stats()
    assert st["injected"] == 1 and st["restored"] == 1
    # every read but the fault's drain and the last had a step behind it
    rs = srv.read_stats()
    assert rs["reads_draining"] <= 3 < rs["reads_overlapped"]


# -- typed errors: shutdown, deadlines, retry exhaustion ---------------------

def test_submit_after_shutdown_raises_typed(params):
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    a = srv.submit([3, 1, 4], max_new=4)
    srv.shutdown()
    with pytest.raises(ServerClosedError):
        srv.submit([2, 7], max_new=4)
    # graceful drain: the pre-shutdown request still completes
    out = srv.run()
    assert out[a] == _ref(params, CFG, [3, 1, 4], 4)


def test_submit_validation(params):
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    with pytest.raises(ValueError):
        srv.submit([3, 1], max_new=0)
    with pytest.raises(ValueError):
        srv.submit([3, 1], max_new=4, deadline_s=0.0)
    with pytest.raises(ValueError):
        srv.submit([3, 1], max_new=4, deadline_s=-1.0)


def test_deadline_sheds_queued_request(params):
    srv = ContinuousServer(params, CFG, slots=1, smax=64)
    a = srv.submit([3, 1, 4], max_new=8)
    b = srv.submit([2, 7], max_new=8, deadline_s=1e-6)
    out = srv.run()
    assert out[a] == _ref(params, CFG, [3, 1, 4], 8)
    assert b not in out
    err = srv.failed[b]
    assert isinstance(err, DeadlineExceededError)
    assert isinstance(err, RequestShedError)   # one except clause
    assert err.rid == b and err.deadline_s == 1e-6


def test_step_retry_exhaustion_sheds_everything_typed(params):
    # every decode check faults: the sync_replay budget
    # (hpx.serving.step_retries) exhausts and ALL in-flight/queued
    # requests shed typed — run() terminates instead of spinning
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    rids = [srv.submit(r["prompt"], max_new=r["max_new"])
            for r in REQS[:3]]
    with _inject(rate=1.0, sites=["decode"], seed=3):
        out = srv.run()
    assert out == {}
    for rid in rids:
        assert isinstance(srv.failed[rid], RequestShedError)
    assert srv.fault_stats()["shed"] == len(rids)


def test_no_injector_zero_overhead_path(params):
    # sanity: with nothing installed check() is a no-op and stats are
    # all zero — the hot loop pays one global read
    out, srv = _serve(params)
    st = srv.fault_stats()
    assert st["injected"] == 0 and st["restored"] == 0
    assert st["shed"] == 0 and st["restore_p99_s"] == 0.0
    for rid, r in enumerate(REQS):
        if r.get("temperature", 0.0) == 0.0:
            assert out[rid] == _ref(params, CFG, r["prompt"],
                                    r["max_new"])


# -- injector unit behavior --------------------------------------------------

def test_injector_deterministic_and_capped():
    fi = faultinject.FaultInjector(seed=42, rate=0.5, max_faults=3)
    hits = []
    for i in range(50):
        try:
            fi.check("decode")
        except faultinject.InjectedFault as e:
            hits.append((i, e.nth))
    assert fi.total_injected == 3 and len(hits) == 3
    # same seed -> same schedule
    fi2 = faultinject.FaultInjector(seed=42, rate=0.5, max_faults=3)
    hits2 = []
    for i in range(50):
        try:
            fi2.check("decode")
        except faultinject.InjectedFault as e:
            hits2.append((i, e.nth))
    assert hits2 == hits


def test_injector_typed_by_site():
    from hpx_tpu.cache.block_allocator import CacheOOM
    from hpx_tpu.core.errors import NetworkError
    fi = faultinject.FaultInjector(schedule={"alloc": {1},
                                             "locality": {1}})
    with pytest.raises(CacheOOM) as ei:
        fi.check("alloc")
    assert isinstance(ei.value, faultinject.InjectedFault)
    with pytest.raises(NetworkError) as ei:
        fi.check("locality", locality=2)
    assert ei.value.locality == 2
    stats = fi.stats()
    assert stats["alloc"]["injected"] == 1
    assert stats["locality"]["injected"] == 1
