"""Bucketed + chunked prefill (models/serving.ContinuousServer).

The contract: chunking a prompt into fixed-width padded windows and
splicing the scratch cache changes WHICH programs run, never the
bytes — every request still equals its solo transformer.generate()
run, for prompt lengths straddling every bucket boundary, over blocks of
16 rows and of 4, greedy and sampled, async dispatch on and off.  Plus the
scheduling guarantees: the program cache stays O(buckets), and a
short prompt admitted behind a long prompt's chunked prefill
overtakes its tail chunks (ready-chunk ordering)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import (_CHUNK_CEILING, ContinuousServer,
                                    _resolve_buckets)
from hpx_tpu.svc import progprof

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)

# ladder (4, 8): plens straddle every boundary (b-1, b, b+1) of both
# buckets AND the chunk boundary at 8 (9 and 15/16/17 need 2-3 chunks)
LADDER = "4,8"
CHUNK = 8
PLENS = [3, 4, 5, 7, 8, 9, 15, 16, 17]


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


def _prompt(plen, seed):
    r = np.random.RandomState(seed)
    return [int(t) for t in r.randint(1, CFG.vocab, size=plen)]


def _solo(params, prompt, m, t=0.0, key=None, eos_id=None):
    out = tfm.generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                       max_new=m, temperature=t, key=key, eos_id=eos_id)
    return [int(x) for x in np.asarray(out)[0]]


# a DERIVED chunk width: no argument, no config key, and a device whose
# ridge a test states. The float32 toy reads 4 bytes a parameter, so
# its ridge width is twice the device's ridge: 120 -> 256, 240 -> the
# ceiling (tests/test_server_geometry.py has the rule's own cases)
_RIDGE_OF = {256: 120.0, _CHUNK_CEILING: 240.0}
_SMAX_WIDE = 640


def _derived_server(monkeypatch, params, width, **kw):
    monkeypatch.setattr(progprof, "device_ridge",
                        lambda: _RIDGE_OF[width])
    srv = ContinuousServer(params, CFG, slots=3, smax=_SMAX_WIDE, **kw)
    assert srv.prefill_chunk == width == srv.prefill_buckets[-1]
    assert srv.prefill_stats()["prefill_chunk_source"] == "ridge"
    return srv


def test_resolve_buckets():
    assert _resolve_buckets("auto", 128) == (8, 16, 32, 64, 128)
    assert _resolve_buckets("auto", 8) == (8,)
    assert _resolve_buckets("auto", 3) == (3,)
    # csv: clamped to the chunk, deduped, chunk width always present
    assert _resolve_buckets("64,16", 32) == (16, 32)
    assert _resolve_buckets("4, 8", 8) == (4, 8)
    with pytest.raises(ValueError, match=">= 1"):
        _resolve_buckets("0,4", 8)
    with pytest.raises(ValueError, match="nothing"):
        _resolve_buckets(" , ", 8)


@pytest.mark.parametrize("block_size", [None, 4], ids=["paged", "block4"])
@pytest.mark.parametrize("async_dispatch", [True, False],
                         ids=["async", "sync"])
@pytest.mark.parametrize("width", [CHUNK, 256, _CHUNK_CEILING],
                         ids=["stated-8", "derived-256", "ceiling"])
def test_boundary_plens_match_generate(params, monkeypatch, block_size,
                                       async_dispatch, width):
    """Every bucket-boundary prompt length, greedy AND sampled mixed in
    one batch, byte-identical to the solo run; at a derived width, one
    under, at and one over that width and its half. Over the default
    block of 16 rows, and over blocks of 4: a bucket's padded tail, the
    chunk boundary and the splice's last block then fall on either
    side of a block seam at every length."""
    if width == CHUNK:
        srv = ContinuousServer(params, CFG, slots=3, smax=64,
                               block_size=block_size,
                               prefill_chunk=CHUNK, prefill_buckets=LADDER,
                               async_dispatch=async_dispatch)
        plens = PLENS
    else:
        srv = _derived_server(monkeypatch, params, width,
                              block_size=block_size,
                              async_dispatch=async_dispatch)
        plens = [w + d for w in (width // 2, width) for d in (-1, 0, 1)]
    want = {}
    for i, plen in enumerate(plens):
        p = _prompt(plen, seed=100 + plen)
        if i % 2:
            k = jax.random.PRNGKey(7 * i)
            rid = srv.submit(p, max_new=6, temperature=0.9, key=k)
            want[rid] = _solo(params, p, 6, t=0.9, key=k)
        else:
            rid = srv.submit(p, max_new=6)
            want[rid] = _solo(params, p, 6)
    out = srv.run()
    assert out == want


@pytest.mark.parametrize("ladder", ["stated", "derived"])
def test_program_cache_is_o_buckets(params, monkeypatch, ladder):
    """After a mixed-length workload, the module program cache holds at
    most one chunk program PER LADDER WIDTH for this server shape —
    not one per prompt length; the longer ladder of a derived width
    (8 doubling to the ceiling) adds rungs, nothing else."""
    smax = {"stated": 64, "derived": _SMAX_WIDE}[ladder]

    def mine(k):
        return k[0] == "cb_chunk" and k[1] == CFG and k[3] == smax

    # the cache is process-wide: drop what earlier tests of this shape
    # (this file or another in the same xdist worker) left, so the
    # count below is this workload's alone
    for k in [k for k in tfm._PROGRAMS if mine(k)]:
        del tfm._PROGRAMS[k]
    if ladder == "stated":
        srv = ContinuousServer(params, CFG, slots=3, smax=64,
                               prefill_chunk=CHUNK, prefill_buckets=LADDER)
        plens = PLENS
    else:
        srv = _derived_server(monkeypatch, params, _CHUNK_CEILING)
        assert srv.prefill_buckets == (8, 16, 32, 64, 128, 256, 512)
        plens = PLENS + [30, 33, 100, 129, 200, 300, 513, 600]
    for plen in plens:
        srv.submit(_prompt(plen, seed=200 + plen), max_new=4)
    srv.run()
    chunk_keys = [k for k in tfm._PROGRAMS if mine(k)]
    assert 0 < len(chunk_keys) <= len(srv.prefill_buckets)
    widths = sorted(k[2] for k in chunk_keys)
    assert set(widths) <= set(srv.prefill_buckets)
    if ladder == "derived":
        assert widths == list(srv.prefill_buckets)
        # 600 = one full chunk + 88 in the 128 bucket: two dispatches
        assert srv.prefill_stats()["prefill_rows_per_chunk"] == \
            sum(plens) / srv._chunks
        assert srv._chunks == len(plens) + 2


def test_second_server_reuses_programs(params):
    """Same shapes on a fresh server: zero program builds (the cache
    key carries no per-request state)."""
    srv = ContinuousServer(params, CFG, slots=3, smax=64,
                           prefill_chunk=CHUNK, prefill_buckets=LADDER)
    for plen in PLENS:
        srv.submit(_prompt(plen, seed=300 + plen), max_new=4)
    srv.run()
    srv2 = ContinuousServer(params, CFG, slots=3, smax=64,
                            prefill_chunk=CHUNK, prefill_buckets=LADDER)
    # NEW lengths, same buckets
    for plen in [6, 10, 13]:
        srv2.submit(_prompt(plen, seed=400 + plen), max_new=4)
    out = srv2.run()
    assert srv2._prog_misses == 0
    assert srv2._prog_hits > 0
    for rid, plen in zip(sorted(out), [6, 10, 13]):
        assert out[rid] == _solo(params, _prompt(plen, 400 + plen), 4)


def test_short_prompt_overtakes_long_prefill(params):
    """Satellite: fairness. A long prompt's chunked prefill must not
    starve a short prompt admitted behind it — ready-chunk ordering
    advances the pending with the fewest remaining tokens first, so
    the short request SEEDS (ttft) before the long one."""
    srv = ContinuousServer(params, CFG, slots=2, smax=64,
                           prefill_chunk=4, prefill_buckets="4")
    long_p = _prompt(40, seed=1)     # 10 chunks of 4
    short_p = _prompt(6, seed=2)     # 2 chunks — but admitted second
    a = srv.submit(long_p, max_new=4)
    b = srv.submit(short_p, max_new=4)
    out = srv.run()
    # ttft insertion order == seeding order
    assert list(srv.ttft) == [b, a]
    assert out[a] == _solo(params, long_p, 4)
    assert out[b] == _solo(params, short_p, 4)


def test_inline_admit_bypasses_pending_queue(params):
    """A prompt that fits one chunk prefills inline at admission even
    while a long pending occupies another slot."""
    srv = ContinuousServer(params, CFG, slots=2, smax=64,
                           prefill_chunk=4, prefill_buckets="4")
    a = srv.submit(_prompt(30, seed=3), max_new=4)   # deferred
    b = srv.submit(_prompt(3, seed=4), max_new=4)    # inline
    srv.step()
    assert b in srv.ttft and a not in srv.ttft
    out = srv.run()
    assert out[a] == _solo(params, _prompt(30, 3), 4)
    assert out[b] == _solo(params, _prompt(3, 4), 4)


def test_equal_remaining_is_fifo(params):
    """Ready-chunk ties break by admission order."""
    srv = ContinuousServer(params, CFG, slots=2, smax=64,
                           prefill_chunk=4, prefill_buckets="4")
    a = srv.submit(_prompt(20, seed=5), max_new=3)
    b = srv.submit(_prompt(20, seed=6), max_new=3)
    out = srv.run()
    assert list(srv.ttft) == [a, b]
    assert out[a] == _solo(params, _prompt(20, 5), 3)
    assert out[b] == _solo(params, _prompt(20, 6), 3)


def test_chunked_prefill_with_eos(params):
    """eos retirement timing is unchanged by chunked prefill and async
    dispatch."""
    p = _prompt(19, seed=8)
    probe = _solo(params, p, 8)
    eos = probe[3]
    srv = ContinuousServer(params, CFG, slots=2, smax=64,
                           prefill_chunk=4, prefill_buckets="4")
    a = srv.submit(p, max_new=8, eos_id=eos)
    b = srv.submit(_prompt(2, seed=9), max_new=5)
    out = srv.run()
    assert out[a] == _solo(params, p, 8, eos_id=eos)
    assert out[b] == _solo(params, _prompt(2, 9), 5)


def test_paged_prefix_reuse_skips_chunks(params):
    """Paged + radix: the second request's matched prefix starts its
    chunk cursor past the shared blocks — fewer chunks, same bytes."""
    shared = _prompt(32, seed=10)
    p1 = shared + _prompt(4, seed=11)
    p2 = shared + _prompt(4, seed=12)
    srv = ContinuousServer(params, CFG, slots=1, smax=64, paged=True,
                           block_size=16, prefill_chunk=8,
                           prefill_buckets="8")
    a = srv.submit(p1, max_new=4)
    out1 = srv.run()
    chunks_first = srv._chunks
    b = srv.submit(p2, max_new=4)
    out2 = srv.run()
    assert srv._chunks - chunks_first < chunks_first  # prefix skipped
    assert srv.cache_stats()["prefill_tokens_saved"] >= 32
    assert out1[a] == _solo(params, p1, 4)
    assert out2[b] == _solo(params, p2, 4)


@pytest.mark.parametrize("matched", [0, 20], ids=["cold", "radix-20"])
def test_a_prompt_that_ends_near_smax_matches_generate(params, matched):
    """A tail chunk's pad rows reach pos0 + width: where that passes
    the scratch's smax rows the write would be CLAMPED and shift the
    real rows. The host takes the widest bucket that fits and sends the
    rest as a further chunk (within the narrowest bucket of the end:
    a row at a time through the chunk program at width 1). After a
    radix match of 20 tokens (blocks of 4) the chunks start off every
    chunk boundary: 20, 36, 52 (room 12: the 8 bucket), 60 (room 4: rows)."""
    shared = _prompt(20, seed=20)
    srv = ContinuousServer(params, CFG, slots=1, smax=64, paged=True,
                           block_size=4, prefill_chunk=16,
                           prefill_buckets="8,16")
    if matched:
        first = shared + _prompt(4, seed=21)
        a = srv.submit(first, max_new=3)
        assert srv.run()[a] == _solo(params, first, 3)
    p = shared + _prompt(41, seed=22)          # 61 + 3 = smax
    before = srv._chunks
    b = srv.submit(p, max_new=3)
    out = srv.run()
    assert srv.cache_stats()["prefill_tokens_saved"] == matched
    # cold: 16 16 16 and 13 in the 16 bucket, which ends at 64 exactly
    assert srv._chunks - before == 4
    assert srv._next_chunk(48, 13) == (13, 16)
    assert srv._next_chunk(52, 9) == (8, 8)
    assert srv._next_chunk(60, 1) == (1, 1)
    assert out[b] == _solo(params, p, 3)


def test_async_buffer_caps_and_flushes(params):
    """max_async_steps bounds the buffer; results are unaffected."""
    srv = ContinuousServer(params, CFG, slots=1, smax=64,
                           async_dispatch=True)
    srv._max_async = 3
    p = _prompt(5, seed=13)
    a = srv.submit(p, max_new=20)
    out = srv.run()
    assert out[a] == _solo(params, p, 20)
    assert not srv._buf
