"""Continuous batching (models/serving.ContinuousServer): slot-based
serving with per-slot positions. The contract under test: every
request's tokens are EXACTLY transformer.generate()'s output for that
prompt alone — batching changes throughput, never content."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.core.config import runtime_config
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


def _ref(params, cfg, prompt, max_new, eos_id=None):
    out = tfm.generate(params, cfg,
                       jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, eos_id=eos_id)
    return [int(t) for t in np.asarray(out)[0]]


def test_mixed_lengths_match_generate(params):
    """More requests than slots, heterogeneous prompt lengths and
    max_new — every result equals the solo generate() run."""
    reqs = [([3, 1, 4], 9), ([2, 7], 5), ([5, 6, 7, 8, 9], 12),
            ([1], 7), ([9, 9, 2, 1], 3), ([4, 4], 10)]
    srv = ContinuousServer(params, CFG, slots=3, smax=64)
    rids = {srv.submit(p, max_new=m): (p, m) for p, m in reqs}
    out = srv.run()
    assert set(out) == set(rids)
    for rid, (p, m) in rids.items():
        assert out[rid] == _ref(params, CFG, p, m), (rid, p, m)


def test_eos_retires_early_and_matches(params):
    probe = _ref(params, CFG, [3, 1, 4], 9)
    eos = probe[3]                    # a token greedy actually emits
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    a = srv.submit([3, 1, 4], max_new=9, eos_id=eos)
    b = srv.submit([2, 7], max_new=5)
    out = srv.run()
    assert out[a] == _ref(params, CFG, [3, 1, 4], 9, eos_id=eos)
    assert out[b] == _ref(params, CFG, [2, 7], 5)


def test_gqa_rope_model():
    params = tfm.init_params(GQA_ROPE, jax.random.PRNGKey(5))
    srv = ContinuousServer(params, GQA_ROPE, slots=2, smax=48)
    rids = {srv.submit(p, max_new=m): (p, m)
            for p, m in [([3, 1, 4, 1], 8), ([2], 6), ([7, 7, 7], 5)]}
    out = srv.run()
    for rid, (p, m) in rids.items():
        assert out[rid] == _ref(params, GQA_ROPE, p, m), (rid, p)


def test_slot_reuse_is_clean(params):
    """A slot freed by a short request must not leak stale cache rows
    into the next request admitted there."""
    srv = ContinuousServer(params, CFG, slots=1, smax=64)
    a = srv.submit([9, 8, 7, 6, 5, 4], max_new=4)   # long prompt first
    b = srv.submit([2, 7], max_new=5)               # then short
    out = srv.run()
    assert out[a] == _ref(params, CFG, [9, 8, 7, 6, 5, 4], 4)
    assert out[b] == _ref(params, CFG, [2, 7], 5)


def test_rejects_bad_submits(params):
    srv = ContinuousServer(params, CFG, slots=1, smax=16)
    with pytest.raises(ValueError, match="non-empty"):
        srv.submit([], max_new=4)
    with pytest.raises(ValueError, match="smax"):
        srv.submit([1, 2, 3], max_new=14)


KEYS = {"raw": jax.random.PRNGKey,              # uint32[2] on the device
        "typed": jax.random.key,                # a typed key array
        "host": lambda s: np.asarray(jax.random.PRNGKey(s))}


@pytest.mark.parametrize("staggered", [False, True])
@pytest.mark.parametrize("block_size", [None, 2], ids=["paged", "block2"])
@pytest.mark.parametrize("kind", sorted(KEYS))
def test_per_request_sampling_matches_solo(params, kind, block_size,
                                           staggered):
    """Sampled requests reproduce their SOLO generate(temperature, key)
    tokens exactly, the seed token the probe's program picks included
    (the key folds match), mixed in one batch with greedy requests —
    with a key of each kind, admitted in one step or in different
    steps beside slots that are already decoding, and over the default
    block (a request lives in one) or blocks of 2 rows (every prompt,
    the probe's rewrite of row plen - 1 and every other decoded token
    cross a block seam)."""
    k1, k2 = KEYS[kind](11), KEYS[kind](22)
    srv = ContinuousServer(params, CFG, slots=3, smax=64,
                           block_size=block_size)
    asks = [([3, 1, 4], dict(max_new=8, temperature=0.8, key=k1)),
            ([2, 7], dict(max_new=6)),                      # greedy
            ([5, 6, 7, 8], dict(max_new=7, temperature=1.3, key=k2)),
            ([9, 2, 6], dict(max_new=5, temperature=0.6, key=k1))]
    rids = []
    for prompt, ask in asks:
        rids.append(srv.submit(prompt, **ask))
        if staggered:
            srv.step()
            srv.step()
    out = srv.run()

    def solo(prompt, max_new, temperature=0.0, key=None):
        o = tfm.generate(params, CFG, jnp.asarray([prompt], jnp.int32),
                         max_new=max_new, temperature=temperature,
                         key=None if key is None else jnp.asarray(key)
                         if kind == "host" else key)
        return [int(x) for x in np.asarray(o)[0]]

    for rid, (prompt, ask) in zip(rids, asks):
        assert out[rid] == solo(prompt, **ask), (rid, prompt)


def test_sampling_requires_key(params):
    srv = ContinuousServer(params, CFG, slots=1, smax=32)
    with pytest.raises(ValueError, match="PRNG key"):
        srv.submit([1, 2], max_new=4, temperature=0.5)


def test_submit_arg_validation(params):
    srv = ContinuousServer(params, CFG, slots=1, smax=32)
    with pytest.raises(ValueError, match="max_new"):
        srv.submit([1, 2], max_new=0)
    with pytest.raises(ValueError, match="no effect"):
        srv.submit([1, 2], max_new=4, key=jax.random.PRNGKey(0))


def test_quantized_params_serve(params):
    """int8 weights through the slot server == int8 solo generate (the
    per-row block dequantizes at use like the scalar-position one)."""
    from hpx_tpu.models import quant
    qp = quant.quantize_params(params)
    srv = ContinuousServer(qp, CFG, slots=2, smax=48)
    rids = {srv.submit(p, max_new=m): (p, m)
            for p, m in [([3, 1, 4], 7), ([2, 7], 5), ([9, 9], 6)]}
    out = srv.run()
    for rid, (p, m) in rids.items():
        assert out[rid] == _ref(qp, CFG, p, m), (rid, p)


def test_sharded_server_matches_single_device(params):
    """GSPMD sharded serving (slots over dp, heads over tp): placement
    alone — identical step program — must reproduce the single-device
    server token for token."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    reqs = [([3, 1, 4], 7), ([2, 7], 5), ([5, 6, 7, 8], 9), ([1], 4),
            ([9, 2], 6)]

    def serve(mesh_arg):
        srv = ContinuousServer(params, CFG, slots=4, smax=64,
                               mesh=mesh_arg)
        rids = {srv.submit(p, max_new=m): i
                for i, (p, m) in enumerate(reqs)}
        out = srv.run()
        return {rids[r]: out[r] for r in out}

    single = serve(None)
    sharded = serve(mesh)
    assert sharded == single


def test_sharded_server_validates(params):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="slots"):
        ContinuousServer(params, CFG, slots=3, smax=32, mesh=mesh)
    # MoE decodes expert-parallel now; the only MoE refusal left is
    # expert-count divisibility over the expert axis, and it names
    # the counts and the remedy
    import dataclasses
    moe_cfg = dataclasses.replace(CFG, n_experts=3)
    moe_params = tfm.init_params(moe_cfg, jax.random.PRNGKey(8))
    with pytest.raises(ValueError, match=r"n_experts \(3\).*tp=2"):
        ContinuousServer(moe_params, moe_cfg, slots=4, smax=32,
                         mesh=mesh)


def test_one_token_burst_drains_in_admission(params):
    """Requests that retire instantly (max_new == 1) free their slot
    mid-admission; the same-pass re-scan pushes the next queued
    request through WITHOUT spending a decode step per request —
    the whole burst drains before the first (and only) step() call
    dispatches nothing."""
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    reqs = {srv.submit([3 + i, 1, 4], max_new=1): [3 + i, 1, 4]
            for i in range(5)}
    steps = 0
    while srv.step():
        steps += 1
    assert steps == 0
    out, srv._done = srv._done, {}
    for rid, p in reqs.items():
        assert out[rid] == _ref(params, CFG, p, 1)


def test_instant_eos_frees_slot_same_pass(params):
    """A request whose FIRST token is its eos retires during admission
    too; the re-scan lets a trailing request take the slot in the same
    pass and everything still matches generate()."""
    tok0 = _ref(params, CFG, [3, 1, 4], 1)[0]
    srv = ContinuousServer(params, CFG, slots=1, smax=64)
    a = srv.submit([3, 1, 4], max_new=5, eos_id=tok0)   # instant eos
    b = srv.submit([2, 7], max_new=4)
    out = srv.run()
    assert out[a] == _ref(params, CFG, [3, 1, 4], 5, eos_id=tok0)
    assert out[b] == _ref(params, CFG, [2, 7], 4)


# -- the public names for a caller that drives step() itself -------------

def test_flush_lands_buffered_tokens_on_the_host(params):
    """Two decode steps stay on the device until something needs their
    values; flush() reads them now."""
    srv = ContinuousServer(params, CFG, slots=1, smax=32)
    srv.submit([3, 1, 4], max_new=6)
    srv.step()
    srv.step()
    req = srv._slot_req[0]
    assert len(req.tokens) == 1 and req.sent == 3   # the seed token only
    srv.flush()
    assert req.tokens == _ref(params, CFG, [3, 1, 4], 3)


def test_poll_finished_hands_out_and_forgets(params):
    srv = ContinuousServer(params, CFG, slots=2, smax=32)
    a = srv.submit([3, 1, 4], max_new=2)
    b = srv.submit([2, 7], max_new=6)
    got = {}
    while srv.step():
        fresh = srv.poll_finished()
        assert not set(fresh) & set(got)
        got.update(fresh)
    got.update(srv.poll_finished())
    assert got == {a: _ref(params, CFG, [3, 1, 4], 2),
                   b: _ref(params, CFG, [2, 7], 6)}
    assert srv.poll_finished() == {} and srv.run() == {}


def test_live_positions_follow_the_decode_steps(params):
    srv = ContinuousServer(params, CFG, slots=2, smax=32)
    assert srv.live_positions() == {}
    srv.submit([3, 1, 4], max_new=4)
    srv.submit([2, 7], max_new=2)
    srv.step()              # both admitted and decoded once
    # slot 1's request was dispatched its last token: the slot is free
    assert srv.live_positions() == {0: 4}
    srv.step()
    assert srv.live_positions() == {0: 5}
    srv.run()
    assert srv.live_positions() == {}


def test_reload_knobs_applies_config_writes_at_flush(params):
    """The operator path: a runtime_config().set() of a reloadable key
    is picked up by _reload_knobs (generation-gated), clamped to the
    server's ladders; constructor overrides survive unrelated
    writes."""
    rc = runtime_config()
    srv = ContinuousServer(params, CFG, slots=2, smax=64,
                           prefill_chunk=8)
    assert srv.prefill_chunk == 8
    saved = rc.get("hpx.serving.ckpt_every")
    try:
        # unrelated write: bumps the generation, must NOT clobber the
        # prefill_chunk=8 constructor override back to the default
        rc.set("hpx.serving.ckpt_every", "128")
        srv._reload_knobs()
        assert srv.prefill_chunk == 8
        assert srv._ckpt_every == 128
        # a write to the key itself IS applied, clamped to the ladder
        saved_pc = rc.get("hpx.serving.prefill_chunk")
        try:
            rc.set("hpx.serving.prefill_chunk", "1000000")
            srv._reload_knobs()
            assert srv.prefill_chunk == srv.prefill_buckets[-1]
        finally:
            rc.set("hpx.serving.prefill_chunk",
                   saved_pc if saved_pc is not None else "auto")
    finally:
        rc.set("hpx.serving.ckpt_every", saved if saved is not None
               else "16")
