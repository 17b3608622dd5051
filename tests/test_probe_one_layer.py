"""The seed probe is ONE layer deep (PR 44): every prefill chunk hands
back the hidden row of its last real column as it enters the last
layer, and `cb_probe` takes the last chunk's row through that layer and
the head. So the server's first token is the pick of the last prompt
position's logits, the chunks run to the prompt's END on every model (a
recurrent layer consumes the last token once, in its chunk), and a
model whose LAST layer is recurrent hands back the row behind it.

Float32, token for token: the oracle is `generate()`'s tok0 where
`generate()` serves the model (K/V pairs, windows) and everywhere a
ONE-SHOT prefill of the whole prompt through `_decode_window` with the
same `_pick_row` (argmax at temperature 0, the keyed draw otherwise).
Lengths straddle the chunk width W: 1, W-1, W, W+1, 2W+3.

A config of its own (d_ff=52) keeps other modules' program caches out
of the counts."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer

W = 8
SMAX = 48
LENGTHS = [1, W - 1, W, W + 1, 2 * W + 3]
_BASE = dict(vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=52)
_MLA = dict(mla_rank=16, mla_nope_dim=8, mla_rope_dim=4, mla_v_dim=8)
_SALA = dict(
    head_dim=8, n_kv_heads=2, norm="rmsnorm", mlp="swiglu", tied=False,
    sparse_kernel=4, sparse_stride=2, sparse_block=8, sparse_topk=2,
    sparse_local=8, sparse_dense_len=16, lightning_heads=4,
    lightning_head_dim=8, qk_norm=True, emb_scale=12.0,
    residual_scale=0.25, logit_scale=0.5)
CFGS = {
    "kv": tfm.TransformerConfig(head_dim=8, **_BASE),
    "window": tfm.TransformerConfig(head_dim=8, rope=True,
                                    layer_window=(0, 6), **_BASE),
    "latent": tfm.TransformerConfig(
        norm="rmsnorm", mlp="swiglu", tied=False,
        layer_mixer=("mla", "mla"),
        layer_rope=(tfm.RopeSpec(10000.0),) * 2, **_MLA, **_BASE),
    "kda": tfm.TransformerConfig(
        norm="rmsnorm", mlp="swiglu", tied=False,
        layer_mixer=("kda", "mla"), kda_heads=2, kda_head_dim=8,
        kda_rank=8, **_MLA, **_BASE),
    # lightning ahead of sparse, as the model has them at its end: the
    # probe reruns the sparse layer's one row
    "sala": tfm.TransformerConfig(
        layer_mixer=("lightning", "sparse"),
        layer_rope=(tfm.RopeSpec(10000.0), None), **_SALA, **_BASE),
    # the LAST layer recurrent: the chunk hands back the row behind it
    "sala_rec": tfm.TransformerConfig(
        layer_mixer=("sparse", "lightning"),
        layer_rope=(None, tfm.RopeSpec(10000.0)), **_SALA, **_BASE),
}
# (kind, block): the default block of 16 rows, and for the models whose
# rows are K/V pairs addressed by position blocks of 4, so that the
# chunk's padded tail (W = 8 over two blocks), the probe's rewrite of
# row plen - 1 and the window group's ring all cross block seams (a
# sparse layer's page is the model's own block)
MODES = [("kv", None), ("kv", 4), ("window", None), ("window", 4),
         ("latent", None), ("kda", None), ("sala", None),
         ("sala_rec", None)]


@pytest.fixture(scope="module")
def models():
    return {k: tfm.init_params(c, jax.random.PRNGKey(3 + i))
            for i, (k, c) in enumerate(CFGS.items())}


def _prompt(n, seed=0):
    r = np.random.RandomState(100 + seed)
    return [int(t) for t in r.randint(1, 64, n)]


def _server(models, kind, block=None, **kw):
    return ContinuousServer(models[kind], CFGS[kind], slots=2, smax=SMAX,
                            block_size=block, prefill_chunk=W,
                            prefill_buckets="4,8", **kw)


def _scratch(kind):
    cfg = CFGS[kind]
    return [serving._scratch_entry(cfg, SMAX, i)
            for i in range(cfg.n_layers)]


def _oneshot(models, kind, prompt, temperature=0.0, key=None):
    """(caches, tok0) of the whole prompt in ONE window over an empty
    scratch: every token consumed once, the pick at its last position."""
    n = len(prompt)
    caches, lg = tfm._decode_window(models[kind], _scratch(kind),
                                    jnp.asarray([prompt]), 0, CFGS[kind],
                                    valid=jnp.int32(n))
    key = np.zeros((2,), np.uint32) if key is None else \
        serving._normalize_key(key)
    tok0 = tfm._pick_row(lg[0, -1], key, jnp.float32(temperature),
                         jnp.int32(n - 1))
    return caches, int(tok0)


def _admit(srv, prompt, **ask):
    """Admit `prompt` alone; (slot, first token) once it is live and its
    seed has landed, before any decode step ran."""
    rid = srv.submit(prompt, max_new=4, **ask)
    while not any(r is not None and r.rid == rid for r in srv._slot_req):
        srv._admit()
        srv._prefill_tick()
    srv.flush()
    slot = next(s for s, r in enumerate(srv._slot_req)
                if r is not None and r.rid == rid)
    assert srv._slot_req[slot].tokens == [srv._cur[slot]]
    return slot, srv._cur[slot]


@pytest.mark.parametrize("plen", LENGTHS)
@pytest.mark.parametrize("kind,block", MODES)
def test_first_token_is_the_last_positions_pick(models, kind, block, plen):
    prompt = _prompt(plen, seed=plen)
    srv = _server(models, kind, block)
    slot, tok0 = _admit(srv, prompt)
    caches, want = _oneshot(models, kind, prompt)
    assert tok0 == want
    if kind in ("kv", "window"):
        solo = tfm.generate(models[kind], CFGS[kind],
                            jnp.asarray([prompt]), max_new=1)
        assert tok0 == int(solo[0, plen])
    # chunks of W rows to the prompt's END (none holds a token back for
    # the probe), then one probe
    assert srv._chunks == -(-plen // W)
    assert srv.prefill_stats()["prefill_rows_per_chunk"] == \
        plen / srv._chunks
    if CFGS[kind].recurrent:
        # the state after the splice is a one-shot prefill's: the last
        # token was consumed once, by its chunk (a probe that reran it
        # through every layer would have fed it twice)
        li = next(i for i, k in enumerate(CFGS[kind].layer_mixer)
                  if k in tfm.RECURRENT_KINDS)
        toks, state = srv.recurrent_state(slot)
        assert toks == prompt
        np.testing.assert_allclose(state, np.asarray(caches[li][0][0]),
                                   atol=2e-5, rtol=0)
    # and the request decodes on from it
    out = srv.run()
    assert len(out) == 1 and list(out.values())[0][0] == tok0


@pytest.mark.parametrize("plen", [1, W, 2 * W + 3])
@pytest.mark.parametrize("kind,block", [("kv", None), ("kv", 4),
                                        ("kda", None), ("sala", None),
                                        ("sala_rec", None)])
def test_a_sampled_first_token_is_the_same_draw(models, kind, block, plen):
    prompt = _prompt(plen, seed=40 + plen)
    key = jax.random.PRNGKey(11 + plen)
    srv = _server(models, kind, block)
    _, tok0 = _admit(srv, prompt, temperature=0.9, key=key)
    assert tok0 == _oneshot(models, kind, prompt, 0.9, key)[1]
    if kind == "kv":
        solo = tfm.generate(models[kind], CFGS[kind], jnp.asarray([prompt]),
                            max_new=1, temperature=0.9, key=key)
        assert tok0 == int(solo[0, plen])


@pytest.mark.parametrize("doc_len", [W, 2 * W])
def test_a_match_that_leaves_one_token_probes_a_one_row_chunk(
        models, doc_len):
    """Latent rows under the radix tree: a loader publishes the
    document's blocks, and a request of document ++ ONE token is
    matched up to that token (`match(prompt[:-1])`): its only chunk has
    one real column, whose row the probe takes on."""
    doc = _prompt(doc_len, seed=7)
    srv = _server(models, "latent", 4)
    srv.submit(doc + [9], max_new=1)
    srv.run()
    before, saved = srv._chunks, srv.cache_stats()["prefill_tokens_saved"]
    prompt = doc + [13]
    _, tok0 = _admit(srv, prompt)
    st = srv.cache_stats()
    assert st["prefill_tokens_saved"] - saved == doc_len
    assert srv._chunks - before == 1
    assert tok0 == _oneshot(models, "latent", prompt)[1]
    alone = _server(models, "latent", 4, prefix_reuse=False)
    assert _admit(alone, prompt)[1] == tok0


@pytest.mark.parametrize("kind", sorted(CFGS))
def test_the_row_and_its_tail_are_the_windows_own_column(models, kind):
    """A cache-only `_decode_window` and `_window_tail` over the row it
    hands back, against the whole window's logits: the same row of
    logits, and the same cache state of the real columns, whether the
    row left ahead of the last layer (rows addressed by position: the
    tail reruns that layer on one row) or behind it (the "sala_rec"
    toy's last layer is recurrent: the tail is ln and head alone)."""
    cfg, params = CFGS[kind], models[kind]
    n, width, pos0 = 5, W, 2
    toks = jnp.asarray([_prompt(n, seed=3) + [0] * (width - n)])

    def window(**kw):
        return tfm._decode_window(params, _scratch(kind), toks, pos0, cfg,
                                  valid=jnp.int32(n), **kw)
    caches, whole = window()
    got_caches, row = window(need_logits=False)
    assert row.shape == (1, 1, cfg.d_model)
    tail = {**params, "layers": params["layers"][-1:]}
    kv, got = tfm._window_tail(tail, row, got_caches[-1],
                               jnp.int32(pos0 + n - 1), cfg)
    assert got.shape == (1, cfg.vocab)
    np.testing.assert_allclose(got[0], whole[0, n - 1], atol=2e-5, rtol=0)
    for a, b in zip(jax.tree.leaves([*got_caches[:-1], kv]),
                    jax.tree.leaves(caches)):
        rows = a.shape[1] == SMAX           # rows by position, or a state
        np.testing.assert_allclose(a[:, pos0:pos0 + n] if rows else a,
                                   b[:, pos0:pos0 + n] if rows else b,
                                   atol=2e-5, rtol=0)


def test_a_row_past_the_last_bucket_goes_through_a_width_one_chunk(models):
    """A prompt that ends at the scratch's last row, past what any
    bucket of the ladder fits: `_next_chunk` plans one row at width 1
    (the chunk program, not the probe's), and the first token is still
    the one-shot pick."""
    srv = _server(models, "kv")
    assert srv._next_chunk(SMAX - 1, 1) == (1, 1)
    assert srv._next_chunk(SMAX - 5, 5) == (4, 4)
    prompt = _prompt(SMAX - 1, seed=5)
    rid = srv.submit(prompt, max_new=1)
    out = srv.run()
    assert out[rid] == [_oneshot(models, "kv", prompt)[1]]


def test_one_chunk_program_a_width_and_one_probe(models, monkeypatch):
    """No seeded variant: a server's chunk programs are keyed by the
    ladder width alone and return (scratch, one hidden row); the probe
    is one program, whose operands are the row and the LAST layer's
    entry of the scratch; `_PendingPrefill` holds no token back."""
    srv = _server(models, "kv")
    seen = []
    real = serving._cached_program
    monkeypatch.setattr(serving, "_cached_program",
                        lambda ck, build: seen.append(ck) or real(ck, build))
    for plen in (3, W, 2 * W + 3):
        _admit(srv, _prompt(plen, seed=60 + plen))
        srv.run()
    chunks = {ck for ck in seen if ck[0] == "cb_chunk"}
    assert sorted(ck[2] for ck in chunks) == [4, 8]
    assert len({ck for ck in seen if ck[0] == "cb_probe"}) == 1
    assert "hold" not in {f.name for f in dataclasses.fields(
        serving._PendingPrefill)}
    caches, row = srv._run_chunk(srv._fresh_scratch(), [5, 6, 7], 0, 3, 4)
    assert row.shape == (1, 1, CFGS["kv"].d_model)
    assert len(caches) == CFGS["kv"].n_layers
