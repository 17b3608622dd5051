"""The server's cache (block pools + radix prefix reuse): whatever the
block geometry, the pool's size, its dtype or the prefixes requests
share, the tokens must be BYTE-IDENTICAL to `generate()`'s, which has
no pool, no table and no tree — same tokens for every request, greedy
and sampled — while actually reusing cached prefix blocks (nonzero hit
rate, prefill tokens saved)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)
GQA_ROPE = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                 head_dim=8, n_layers=2, d_ff=64,
                                 n_kv_heads=2, rope=True)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _ref(params, cfg, prompt, max_new, eos_id=None, temperature=0.0,
         key=None):
    out = tfm.generate(params, cfg,
                       jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, eos_id=eos_id,
                       temperature=temperature, key=key)
    return [int(t) for t in np.asarray(out)[0]]


def _run_both(params, cfg, reqs, smax=64, slots=3, **server_kw):
    """Each request alone through `generate()` (the oracle, which has no
    pool, no table and no radix tree), and the same mix through one
    server built with `server_kw`; returns ({rid: tokens} generate's,
    {rid: tokens} the server's, the server). rids are the submission
    order."""
    srv = ContinuousServer(params, cfg, slots=slots, smax=smax,
                           **server_kw)
    for r in reqs:
        srv.submit(**r)
    ref = {rid: _ref(params, cfg, **r) for rid, r in enumerate(reqs)}
    return ref, srv.run(), srv


# -- equivalence -------------------------------------------------------------

def test_greedy_matches_generate(params):
    reqs = [dict(prompt=[3, 1, 4], max_new=9),
            dict(prompt=[2, 7], max_new=5),
            dict(prompt=[5, 6, 7, 8, 9], max_new=12),
            dict(prompt=[1], max_new=7),
            dict(prompt=[9, 9, 2, 1], max_new=3),
            dict(prompt=[4, 4], max_new=10)]
    ref, out, _ = _run_both(params, CFG, reqs)
    assert out == ref


def test_sampled_matches_generate(params):
    """temperature > 0: the per-(position, row) fold_in sampling
    contract holds over the pool bit-for-bit."""
    reqs = [dict(prompt=[3, 1, 4], max_new=8, temperature=0.9,
                 key=jax.random.PRNGKey(7)),
            dict(prompt=[2, 7, 9], max_new=8, temperature=0.7,
                 key=jax.random.PRNGKey(8)),
            dict(prompt=[5, 5], max_new=6, temperature=1.3,
                 key=jax.random.PRNGKey(9))]
    ref, out, _ = _run_both(params, CFG, reqs, slots=2)
    assert out == ref


def test_gqa_rope_matches_generate():
    params = tfm.init_params(GQA_ROPE, jax.random.PRNGKey(5))
    reqs = [dict(prompt=[3, 1, 4, 1, 5], max_new=7),
            dict(prompt=[2, 7], max_new=5),
            dict(prompt=[1, 2, 3], max_new=6)]
    ref, out, _ = _run_both(params, GQA_ROPE, reqs, smax=48, slots=2)
    assert out == ref


def test_eos_matches_generate(params):
    probe = _ref(params, CFG, [3, 1, 4], 9)
    eos = probe[3]
    reqs = [dict(prompt=[3, 1, 4], max_new=9, eos_id=eos),
            dict(prompt=[2, 7], max_new=5)]
    ref, out, _ = _run_both(params, CFG, reqs, slots=2)
    assert out == ref


# -- prefix reuse ------------------------------------------------------------

def test_shared_prefix_hits_and_stays_identical(params):
    """Requests sharing a 2-block prefix: later admissions must match
    the published chain (saved prefill tokens) and still emit exactly
    `generate()`'s tokens."""
    pre = list(range(1, 33))                    # 32 = 2 blocks of 16
    reqs = [dict(prompt=pre + [40, 41], max_new=6),
            dict(prompt=pre + [50], max_new=6),
            dict(prompt=pre + [60, 61, 62], max_new=6)]
    ref, out, srv = _run_both(params, CFG, reqs, slots=2)
    assert out == ref
    st = srv.cache_stats()
    assert st["tokens_matched"] >= 32           # later reqs reused pre
    assert st["hit_rate"] > 0
    assert st["prefill_tokens_saved"] >= 32
    # conservation: every prompt position was either reused or computed
    total_prompt = sum(len(r["prompt"]) for r in reqs)
    assert (st["prefill_tokens_saved"]
            + st["prefill_tokens_computed"]) == total_prompt


def test_disjoint_prefixes_no_false_sharing(params):
    """Unrelated prompts must never match each other's chains — zero
    matched tokens, identical output."""
    reqs = [dict(prompt=[10 + i] * 20, max_new=5) for i in range(4)]
    ref, out, srv = _run_both(params, CFG, reqs, slots=2)
    assert out == ref
    assert srv.cache_stats()["tokens_matched"] == 0


def test_prefix_reuse_off_is_still_identical(params):
    pre = list(range(1, 33))
    reqs = [dict(prompt=pre + [40], max_new=5),
            dict(prompt=pre + [50], max_new=5)]
    ref, out, srv = _run_both(params, CFG, reqs, slots=2,
                                prefix_reuse=False)
    assert out == ref
    assert srv.cache_stats()["tokens_matched"] == 0
    assert srv.cache_stats()["prefill_tokens_saved"] == 0


def test_oom_evicts_and_recovers(params):
    """A pool with barely more than live demand: retained radix chains
    must be evicted on OOM and serving must complete correctly."""
    # smax=32 -> 2 blocks/seq; 2 slots live demand = 4 blocks; +trash.
    # 6 blocks leaves one spare for radix retention -> guaranteed OOM
    # churn across 6 sequential requests.
    reqs = [dict(prompt=[10 + i] * 20, max_new=5) for i in range(6)]
    ref, out, srv = _run_both(params, CFG, reqs, smax=32, slots=2,
                                num_blocks=6)
    assert out == ref
    st = srv.cache_stats()
    assert st["total_evictions"] > 0            # the retry path ran
    assert st["in_use"] <= 6


# -- construction contracts --------------------------------------------------

def test_paged_rejects_misaligned_smax(params):
    with pytest.raises(ValueError, match="divisible"):
        ContinuousServer(params, CFG, slots=2, smax=50, paged=True,
                         block_size=16)


def test_paged_rejects_undersized_pool(params):
    # smax=64/bs=16 -> 4 blocks/seq; 4 (one request) + trash = 5 min
    with pytest.raises(ValueError, match="num_blocks"):
        ContinuousServer(params, CFG, slots=2, smax=64, paged=True,
                         num_blocks=4)


# -- instant retirement (admission re-scan) ----------------------------------

def test_one_token_burst_drains_without_decode_steps(params):
    """max_new == 1 requests retire during admission; the re-scan
    drains a whole burst through the slots in a single step() call
    with no decode dispatch at all."""
    srv = ContinuousServer(params, CFG, slots=2, smax=64, paged=True)
    reqs = {srv.submit([3 + i, 1, 4], max_new=1): [3 + i, 1, 4]
            for i in range(5)}
    steps = 0
    while srv.step():
        steps += 1
    assert steps == 0                 # first call admits+retires all
    out, srv._done = srv._done, {}
    for rid, p in reqs.items():
        assert out[rid] == _ref(params, CFG, p, 1)


def test_counters_registered_and_queryable(params):
    from hpx_tpu.svc import performance_counters as pc
    srv = ContinuousServer(params, CFG, slots=2, smax=64, paged=True)
    inst = srv.counter_instance
    srv.submit([3, 1, 4], max_new=4)
    srv.run()
    hit = pc.query_counter(
        pc.counter_name("cache", "hit-rate", inst)).value
    assert hit == srv._radix.hit_rate()
    used = pc.query_counter(
        pc.counter_name("cache", "blocks/in-use", inst)).value
    assert used == srv._alloc.in_use
    rate = pc.query_counter(
        pc.counter_name("serving", "tokens/rate", inst)).value
    assert rate > 0                   # 3 decode tokens inside the window
    # a collected server reads 0 and its names vanish on refresh
    name = pc.counter_name("cache", "blocks/in-use", inst)
    del srv
    import gc
    gc.collect()
    assert name not in pc.discover_counters("/cache{locality#*/*}/*")
