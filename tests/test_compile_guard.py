"""Compile-count guard for the serving hot path.

The bucketed-prefill contract measured at the REAL boundary: jax's
``/jax/core/compile/backend_compile_duration`` monitoring event fires
per XLA backend compilation, so these tests pin the number of
compiles a mixed-length serving workload may trigger.  The bound is
O(buckets) + a constant (step/probe/splice programs) — NOT O(distinct
prompt lengths): pre-bucketing, 12 distinct lengths meant 12 prefill +
12 splice programs.  And the workload compiles NOTHING beside its named
programs (PR 40: `step()` enqueues no eager op), so the count of
backend compiles over the workload IS the count of program builds; the
server is built ahead of the counted region (its pools' zero fills are
allocation, not serving).

A dedicated config (d_ff=48) keeps these counts isolated from other
test modules warming the shared program cache in the same process."""

import jax
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.utils.compilemon import count_compiles

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=48)

PLENS = [3, 5, 9, 12, 17, 23, 4, 8, 16, 21, 6, 14]   # 12 mixed lengths


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(1))


def _workload(srv, plens, seed):
    r = np.random.RandomState(seed)
    for plen in plens:
        srv.submit([int(t) for t in r.randint(1, CFG.vocab, plen)],
                   max_new=5)
    return srv.run()


def test_mixed_length_workload_compiles_o_buckets(params):
    srv = ContinuousServer(params, CFG, slots=4, smax=64,
                           prefill_chunk=8, prefill_buckets="4,8")
    with count_compiles() as c:
        out = _workload(srv, PLENS, seed=0)
    assert len(out) == len(PLENS)
    buckets = len(srv.prefill_buckets)
    # program builds: one chunk program per bucket + probe + splice +
    # step + the empty scratch, NOT one per prompt length
    assert srv._prog_misses <= buckets + 4
    # total backend compiles: the program builds and nothing else (no
    # first-touch eager argmax / sampling / scatter / stack)
    assert int(c) == srv._prog_misses


def test_fused_paged_workload_compiles_o_buckets(params):
    """The fused-kernel paged server rides the same bucket ladder: the
    paged step/gather/splice programs are keyed on (kv_dtype,
    paged_kernel) — constants for a given server — so mixed-length
    traffic still compiles O(buckets), and flipping the pool dtype
    re-keys only the pool-dtype programs, never the bucket ladder."""
    srv = ContinuousServer(params, CFG, slots=4, smax=64,
                           prefill_chunk=8, prefill_buckets="4,8",
                           paged=True, paged_kernel="fused")
    with count_compiles() as c:
        out = _workload(srv, PLENS, seed=3)
    assert len(out) == len(PLENS)
    buckets = len(srv.prefill_buckets)
    # chunk program per bucket + probe + step + gather + splice
    assert srv._prog_misses <= buckets + 5
    assert int(c) == srv._prog_misses
    # a fresh fused server, NEW prompt lengths: total reuse
    srv2 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            paged=True, paged_kernel="fused")
    with count_compiles() as c2:
        _workload(srv2, [7, 11, 19, 22], seed=4)
    assert srv2._prog_misses == 0 and srv2._prog_hits > 0
    assert int(c2) == 0
    # int8 pools: only the kv_dtype-keyed programs rebuild (step,
    # gather, splice); the bucket-ladder chunk programs are reused
    srv3 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            paged=True, paged_kernel="fused",
                            kv_dtype="int8")
    with count_compiles() as c3:
        out3 = _workload(srv3, PLENS, seed=5)
    assert len(out3) == len(PLENS)
    assert srv3._prog_misses <= 5
    assert int(c3) == srv3._prog_misses
    # fp8 pools ride the SAME kv_dtype re-key budget — a new dtype
    # value, not a new keying dimension
    srv4 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            paged=True, paged_kernel="fused",
                            kv_dtype="fp8")
    with count_compiles() as c4:
        out4 = _workload(srv4, PLENS, seed=5)
    assert len(out4) == len(PLENS)
    assert srv4._prog_misses <= 5
    assert int(c4) == srv4._prog_misses
    # fused_online: paged_kernel is already a key component, so the
    # online kernel re-keys the same <= 5 programs and rides the
    # bucket ladder untouched
    srv5 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            paged=True,
                            paged_kernel="fused_online")
    with count_compiles() as c5:
        out5 = _workload(srv5, PLENS, seed=5)
    assert len(out5) == len(PLENS)
    assert srv5._prog_misses <= 5
    assert int(c5) == srv5._prog_misses


def test_sharded_paged_workload_compiles_o_buckets(params):
    """Sharded paged serving rides the SAME bucket ladder: the
    shard_map-wrapped step/verify and the mesh-keyed gather/splice
    programs are keyed on (kv_dtype, paged_kernel, mesh) — constants
    for a given server — so mixed-length traffic on a 2x2 (dp, tp)
    mesh still compiles O(buckets), fresh servers on the same mesh
    reuse everything, and flipping the pool dtype re-keys <= 5
    programs (the single-device budget carries over)."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    srv = ContinuousServer(params, CFG, slots=4, smax=64,
                           prefill_chunk=8, prefill_buckets="4,8",
                           paged=True, mesh=mesh)
    with count_compiles() as c:
        out = _workload(srv, PLENS, seed=6)
    assert len(out) == len(PLENS)
    buckets = len(srv.prefill_buckets)
    # chunk program per bucket + probe + step + gather + splice
    assert srv._prog_misses <= buckets + 5
    assert int(c) == srv._prog_misses
    # a fresh sharded server, NEW prompt lengths: total reuse
    srv2 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            paged=True, mesh=mesh)
    with count_compiles() as c2:
        _workload(srv2, [7, 11, 19, 22], seed=7)
    assert srv2._prog_misses == 0 and srv2._prog_hits > 0
    assert int(c2) == 0
    # int8 pools on the mesh: only the kv_dtype-keyed programs rebuild
    srv3 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            paged=True, mesh=mesh,
                            kv_dtype="int8")
    with count_compiles() as c3:
        out3 = _workload(srv3, PLENS, seed=8)
    assert len(out3) == len(PLENS)
    assert srv3._prog_misses <= 5
    assert int(c3) == srv3._prog_misses


def test_new_lengths_reuse_everything(params, recwarn):
    # warm wave (may share compiles with the test above when it ran
    # first — irrelevant, we only pin the SECOND wave)
    srv = ContinuousServer(params, CFG, slots=4, smax=64,
                           prefill_chunk=8, prefill_buckets="4,8")
    _workload(srv, PLENS, seed=1)
    # fresh server, prompt lengths NOT seen above: zero new programs,
    # and zero backend compiles
    srv2 = ContinuousServer(params, CFG, slots=4, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8")
    with count_compiles() as c:
        out = _workload(srv2, [7, 11, 19, 22], seed=2)
    assert len(out) == 4
    assert srv2._prog_misses == 0
    assert srv2._prog_hits > 0
    assert int(c) == 0
