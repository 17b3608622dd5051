"""DeepSeek-V2 on the paged path: latent attention WITH a rotary part
under a low-rank query in every layer, a device-limited (group-limited)
router over one chip's group of the experts, a leading dense layer, a
sliced vocabulary, and prefix reuse over the latent pool. A CPU toy
with every mechanism against the plain reference
`chipbench/reference/deepseek_v2.py`, on LOGITS."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.drivers import serving_latent as drv
from chipbench.reference import deepseek_v2 as ref
from hpx_tpu.models import moe, serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import attention_pallas as ap
from hpx_tpu.ops import paged_attention as pa
from hpx_tpu.svc import performance_counters as pc
from hpx_tpu.svc import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 5e-4
CHUNK = 8


def _conf(**over):
    with open(os.path.join(ROOT, "chipbench/configs/deepseek-v2.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT,
                           "chipbench/tests/rehearse_latent.json")) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    return harness._merge(conf, over)


def _make(conf, seed):
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.balance_router(drv.make_params(cfg, seed), conf,
                                         seed)


@pytest.fixture(scope="module")
def toy():
    return _make(_conf(), 11)


@pytest.fixture(scope="module")
def toy128():
    """The toy with a latent rank of whole 128-lane rows: the width at
    which the Pallas kernel `hpx_mla_paged` is taken."""
    return _make(_conf(kv_lora_rank=128), 12)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


def _ref_logits(conf, params, seq):
    return np.asarray(ref.logits(params, conf,
                                 np.asarray([seq], np.int32)))[0]


def test_the_toy_has_every_mechanism(toy):
    conf, cfg, params = toy
    assert cfg.layer_mixer == ("mla",) * 3 and cfg.mla_q_rank == 24
    assert cfg.layer_sparse == (False, True, True)
    assert (cfg.n_experts, cfg.moe_held, cfg.moe_top_k) == (32, (0, 4), 6)
    assert (cfg.moe_n_group, cfg.moe_topk_group) == (8, 3)
    assert cfg.moe_scale == 16.0 and not cfg.moe_renorm
    assert cfg.moe_shared_d_ff == 2 * cfg.moe_d_ff and not cfg.tied
    rope = cfg.rope_of(0)
    assert rope.factor == 40.0 and rope.attention_factor == 1.0
    m = 0.1 * 0.707 * np.log(40.0) + 1.0
    assert cfg.mla_scale == pytest.approx((16 + 8) ** -0.5 * m * m)
    assert ref.softmax_scale(conf) == pytest.approx(cfg.mla_scale)
    lp = params["layers"][1]
    assert set(lp["mla"]) == {"wdq", "qnorm", "wuq", "wdkv", "kvnorm",
                              "wuk", "wuv", "wo"}
    assert lp["moe"]["wg"].shape == (64, 32)
    assert lp["moe"]["w1"].shape == (4, 64, 32) and "bias" not in lp["moe"]
    assert "w3" in params["layers"][0] and params["head"].shape == (256, 64)
    # init_params builds the same leaves
    own = jax.eval_shape(lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert jax.tree.map(lambda x: x.shape, own) == jax.tree.map(
        lambda x: x.shape, params)
    # the published config's numbers survive, but for the cut's three
    with open(os.path.join(ROOT, "chipbench/configs/deepseek-v2.json")) as f:
        full = json.load(f)
    assert full["reduced"] == ["num_hidden_layers", "n_routed_experts",
                               "vocab_size"]
    assert (full["hidden_size"], full["num_attention_heads"],
            full["q_lora_rank"], full["kv_lora_rank"], full["n_group"],
            full["topk_group"], full["router_experts"]) == (
                5120, 128, 1536, 512, 8, 3, 160)


def test_the_yarn_frequencies_are_the_references():
    conf = _conf()
    cfg = drv.build_cfg(conf)
    cos, sin = ref.rotary_tables(conf, 200)
    inv = np.asarray(cfg.rope_of(0).inv_freq(8), np.float64)
    ang = np.arange(200)[:, None] * inv[None, :]
    np.testing.assert_allclose(np.asarray(cos), np.cos(ang), atol=2e-5)
    np.testing.assert_allclose(np.asarray(sin), np.sin(ang), atol=2e-5)
    # factor 40 over 32 positions: the slow dims are stretched
    plain = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    assert inv[0] == pytest.approx(plain[0]) and inv[-1] < plain[-1] / 30


# -- the router: group-limited choice -------------------------------------

MCFG = moe.MoeConfig(n_experts=32, top_k=6, d_model=16, d_ff=8,
                     mlp="swiglu", scale=16.0, shared_d_ff=16)


@pytest.mark.parametrize("router,bias", [("softmax", False),
                                         ("sigmoid", True)])
def test_route_with_one_group_is_the_flat_top_k(router, bias):
    """n_group 1 / topk_group 1 = the route every other model takes,
    bit for bit; with all the groups kept a grouped route agrees too."""
    cfg = dataclasses.replace(MCFG, router=router, renorm=bias)
    x = jax.random.normal(jax.random.PRNGKey(0), (40, 16))
    wg = jax.random.normal(jax.random.PRNGKey(1), (16, 32))
    b = jax.random.normal(jax.random.PRNGKey(2), (32,)) if bias else None
    idx, w = moe.route(x, wg, cfg, b)
    logits = np.asarray(x, np.float32) @ np.asarray(wg, np.float32)
    sc = (1 / (1 + np.exp(-logits)) if router == "sigmoid"
          else np.asarray(jax.nn.softmax(logits, -1)))
    want = np.argsort(-(sc + (0 if b is None else np.asarray(b))),
                      -1, kind="stable")[:, :6]
    np.testing.assert_array_equal(np.asarray(idx), want)
    allg = dataclasses.replace(cfg, n_group=8, topk_group=8)
    idx8, w8, kept = moe.route(x, wg, allg, b, groups=True)
    np.testing.assert_array_equal(np.asarray(idx8), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(w8), np.asarray(w), rtol=1e-6)
    assert np.asarray(kept).all()


def test_group_limited_route_follows_the_equations():
    cfg = dataclasses.replace(MCFG, n_group=8, topk_group=3)
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 16))
    wg = jax.random.normal(jax.random.PRNGKey(4), (16, 32))
    idx, w, kept = (np.asarray(a) for a in moe.route(x, wg, cfg,
                                                     groups=True))
    g = np.asarray(jax.nn.softmax(
        np.asarray(x, np.float32) @ np.asarray(wg, np.float32), -1))
    for t in range(64):
        best = g[t].reshape(8, 4).max(-1)
        groups = np.argsort(-best, kind="stable")[:3]
        assert set(np.flatnonzero(kept[t])) == set(groups)
        masked = np.where(np.isin(np.arange(32) // 4, groups), g[t], 0.0)
        want = np.argsort(-masked, kind="stable")[:6]
        np.testing.assert_array_equal(idx[t], want)
        np.testing.assert_allclose(w[t], 16.0 * g[t][want], rtol=1e-5)
    assert not np.array_equal(
        idx, np.asarray(moe.route(x, wg, MCFG)[0]))     # the limit bites
    ri, rw = ref.choose(jnp.asarray(g), top_k=6, n_group=8, topk_group=3)
    np.testing.assert_array_equal(np.asarray(ri), idx)


def test_the_eight_groups_shares_add_up_to_the_uncut_reference(toy):
    """The share test: the 8 routing groups of a sparse layer, one a
    chip, the router at its full width and all its groups in each, the
    shared expert counted ONCE, add up to what the uncut reference
    gives for the whole layer; the statistics vector says how much of
    the routing fell to each."""
    conf, cfg, _ = toy
    d, f, e = 64, 32, 32
    mcfg = dataclasses.replace(tfm._moe_cfg(cfg), held=())
    p = moe.init_moe_params(mcfg, jax.random.PRNGKey(1))
    assert "bias" not in p and p["shared"]["w1"].shape == (d, 2 * f)
    u = jax.random.normal(jax.random.PRNGKey(2), (48, d))
    # the uncut reference: every expert held, lo = 0. `_sparse_ffn`
    # returns x + FFN(RMSNorm_2(x)): rows of unit RMS under a scale of
    # ones pass the norm unchanged
    lp = {"ln2": jnp.ones((d,)), "moe": p}
    un = u / jnp.sqrt(jnp.mean(u * u, -1, keepdims=True))
    whole = np.asarray(ref._sparse_ffn(
        un[None], lp, eps=0.0, quant=None, top_k=6, n_group=8,
        topk_group=3, scale=16.0, lo=0, leave_out=()))[0] - np.asarray(un)
    total, here, tokens = 0, [], []
    for g in range(8):
        lo, hi = 4 * g, 4 * g + 4
        share = {"wg": p["wg"], **{k: p[k][lo:hi]
                                   for k in ("w1", "w3", "w2")}}
        if g == 5:
            share["shared"] = p["shared"]
        held = dataclasses.replace(mcfg, held=(lo, hi))
        out, stats = moe.moe_ffn_serve(un, share, held)
        assert stats.shape == (2 + 4 + moe.STATS_HERE,)
        assert stats[0] == 48 * 6 and stats[1] == 0
        here.append(float(stats[-2]))
        tokens.append(float(stats[-1]))
        total = total + out
    np.testing.assert_allclose(np.asarray(total), whole, atol=2e-4, rtol=0)
    assert sum(here) == 48 * 6          # every assignment is some group's
    assert sum(tokens) == 48 * 3        # every token keeps three groups
    assert e == mcfg.n_experts


# -- latent attention: absorbed == expanded; kernel == gather -------------

def _latent_case(b, h, rank, dr, bs, maxb, pos, key=5):
    row_w = -(-(rank + dr) // 128) * 128
    ks = jax.random.split(jax.random.PRNGKey(key), 4)
    table = (1 + jnp.arange(b * maxb, dtype=jnp.int32)).reshape(b, maxb)
    lat = jax.random.normal(ks[0], (b, maxb * bs, rank + dr))
    pool = jnp.zeros((b * maxb + 1, 1, bs, row_w)).at[table].set(jnp.pad(
        lat, ((0, 0), (0, 0), (0, row_w - rank - dr))).reshape(
            b, maxb, 1, bs, row_w))
    q = jnp.pad(jax.random.normal(ks[1], (b, h, rank + dr)) * 0.3,
                ((0, 0), (0, 0), (0, row_w - rank - dr)))
    new = jnp.pad(jax.random.normal(ks[2], (b, rank + dr)),
                  ((0, 0), (0, row_w - rank - dr)))
    return q, new, pool, table, jnp.asarray(pos, jnp.int32)


@pytest.mark.parametrize("shape", ["deepseek-v2", "kimi"])
def test_the_blocked_kernel_equals_its_gather_oracle(shape, monkeypatch):
    """`hpx_mla_paged` (interpret mode) against the gather form at 128
    heads and at Kimi's 32, at a live length of 1, one block, mid-block,
    a whole number of folds, mid-fold and the whole table; rows past a
    slot's length hold NaN in the pool and must not be read."""
    h = {"deepseek-v2": 128, "kimi": 32}[shape]
    monkeypatch.setattr(ap, "LATENT_WALK_ENTRIES", 4)   # folds of 64 rows
    bs, maxb = 16, 11
    pos = [0, 15, 40, 63, 64, 100, maxb * bs - 1]
    q, new, pool, table, pos = _latent_case(len(pos), h, 128, 64, bs, maxb,
                                            pos)
    rows = jnp.arange(maxb * bs)[None, :] > pos[:, None]    # past the length
    dead = rows.reshape(len(pos), maxb, 1, bs, 1)
    pool = pool.at[table].set(jnp.where(dead, jnp.nan, pool[table]))
    got = {f: np.asarray(pa.paged_latent_attention(
        q, new, pool, table, pos, rank=128, scale=0.1, fused=f)[0])
        for f in (False, True)}
    assert np.isfinite(got[True]).all()
    np.testing.assert_allclose(got[True], got[False], atol=2e-5, rtol=0)


def test_the_kernels_vmem_does_not_grow_with_the_table():
    need = ap.latent_vmem_bytes(128, 640, 512, ap.LATENT_WALK_ENTRIES * 16, 2)
    assert need <= 16 << 20                 # under the chip's default scope
    # the whole-bank kernel this replaces asked 4 banks + 8 MB at smax
    assert need < (4 * 25216 * 640 * 2 + (8 << 20)) / 8


def test_mla_with_a_rotary_part_absorbed_equals_expanded(toy):
    """`_mla_mixer` (absorbed, rows rotated before they are cached)
    against the expanded form of the reference, one layer, positions
    that differ a slot."""
    conf, cfg, params = toy
    lp = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 21, 64))
    want = np.asarray(ref._mla(
        x, lp, *ref.rotary_tables(conf, 21), eps=cfg.norm_eps, quant=None,
        rank=32, nope=16, scale=ref.softmax_scale(conf), leave_out=())) \
        - np.asarray(x)
    h = tfm._norm(x, lp["ln1"], cfg)
    pos = jnp.arange(21)

    def attend(q, row):
        return tfm._latent_attention(q, row, pos, cfg.mla_rank,
                                     cfg.mla_scale), None
    got, _ = tfm._mla_mixer(h, lp["mla"], cfg, attend, pos, cfg.rope_of(0))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    # NoPE (Kimi's call: no rope) is another model
    plain, _ = tfm._mla_mixer(h, lp["mla"], cfg, attend)
    assert np.abs(np.asarray(plain) - want).max() > 1e-2


# pairs a group over q [1, 8, 4, .]: None = the default (one group),
# then two even groups of 2 heads, a ragged last group (3 + 1 heads),
# one head a group; a group of fewer pairs than a head has is one head
@pytest.mark.parametrize("pairs", [None, 16, 24, 8, 1])
@pytest.mark.parametrize("rows,block", [(40, 512), (100, 16), (100, 48)])
def test_a_chunks_latent_attention_is_blocked_and_bounded(rows, block, pairs,
                                                          monkeypatch):
    """`_latent_attention` walks the scratch in blocks up to the last
    query's position: equal to the whole-scratch softmax, whatever the
    rows past the chunk hold (a scratch's are finite: zeros, or what an
    earlier bucket's padding left), also where the scratch is no whole
    number of blocks. Walked a GROUP of heads at a time it gives the
    one walk's result TO THE BIT, for positions a chunk `[Q]` and a
    row `[B, Q]`: held on quarters (every score's sum is then exact,
    whatever order the backend's product takes it in: the CPU's sums a
    score of 32 (head, query) rows in another order than one of 16 or
    8, and the two differ by an ulp on arbitrary float32 data)."""
    monkeypatch.setattr(tfm, "LATENT_ROWS_A_BLOCK", block)
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    q = jax.random.normal(ks[0], (1, 8, 4, 48))
    lat = jax.random.normal(ks[1], (1, rows, 48))
    groups = {None: 1, 16: 2, 24: 2, 8: 4, 1: 4}[pairs]

    def program():
        # traced at its first call, under the constants of that moment
        return jax.jit(lambda q, lat, qpos: tfm._latent_attention(
            q, lat, qpos, 32, 0.2))

    def quarters(x):
        return jnp.round(x * 4) / 4
    cases = [(pos0 + jnp.arange(8), lat.at[:, pos0 + 8:].set(1e4))
             for pos0 in (0, 5, rows - 8)]
    one_walk = program()
    ones = [np.asarray(one_walk(quarters(q), quarters(seen), qpos))
            for qpos, seen in cases]
    if pairs is not None:
        monkeypatch.setattr(tfm, "LATENT_PAIRS_A_GROUP", pairs)
    assert tfm.latent_groups(1, 8, 4) == groups
    grouped = program()
    for (qpos, seen), one in zip(cases, ones):
        for at in (qpos, qpos[None]):
            np.testing.assert_array_equal(np.asarray(
                grouped(quarters(q), quarters(seen), at)), one)
        got = np.asarray(grouped(q, seen, qpos))
        s = np.einsum("bqhr,bkr->bhqk", q, lat) * 0.2
        s = np.where(np.arange(rows)[None, :] <= np.asarray(qpos)[:, None],
                     s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("bhqk,bkr->bqhr", p / p.sum(-1, keepdims=True),
                         lat[..., :32])
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def _whiles(jaxpr):
    """The `while` equations of a jaxpr, nested ones too."""
    found = []
    for eqn in jaxpr.eqns:
        found += [eqn] * (eqn.primitive.name == "while")
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _whiles(sub)
    return found


@pytest.mark.parametrize("width,heads,loops", [
    (128, 128, 1), (256, 128, 2), (512, 32, 1), (64, 128, 1)],
    ids=["docqa128", "docqa256", "kimi512", "docqa64"])
def test_the_groups_follow_from_the_querys_shape_alone(width, heads, loops):
    """At the default constant a chunk of DeepSeek-V2's 128 heads walks
    in two groups only at width 256, Kimi-Linear's widest (512 rows of
    32 heads) in one: a `while` a group, each over the accumulator of
    its heads, and with one group no slice and no concatenate."""
    assert tfm.LATENT_PAIRS_A_GROUP == 16384
    jaxpr = jax.make_jaxpr(
        lambda q, lat, qpos: tfm._latent_attention(q, lat, qpos, 512, 0.1))(
            jax.ShapeDtypeStruct((1, width, heads, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 4224, 640), jnp.bfloat16),
            jax.ShapeDtypeStruct((width,), jnp.int32)).jaxpr
    found = _whiles(jaxpr)
    assert len(found) == loops == tfm.latent_groups(1, width, heads)
    assert all(e.outvars[-1].aval.shape == (1, heads // loops, width, 512)
               for e in found)
    joins = [e for e in jaxpr.eqns if e.primitive.name == "concatenate"]
    assert len(joins) == (loops > 1)


# -- the whole model: chunked prefill, then decode, on LOGITS -------------

@pytest.mark.parametrize("which,kernel", [("toy", "gather"),
                                          ("toy128", "fused")])
def test_prefill_then_paged_decode_logits_equal_the_reference(
        request, which, kernel):
    conf, cfg, params = request.getfixturevalue(which)
    plen, steps = 29, 12
    prompt = _prompt(plen)
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=64,
                           prefill_chunk=CHUNK, paged_kernel=kernel)
    caches, got = srv._fresh_scratch(), []
    for s in range(0, plen, CHUNK):
        n = min(CHUNK, plen - s)
        toks = prompt[s:s + n] + [0] * (CHUNK - n)
        caches, lg = tfm._decode_window(params, caches,
                                        jnp.asarray([toks]), s, cfg,
                                        valid=jnp.int32(n))
        got.append(np.asarray(lg[0, :n]))
    want = _ref_logits(conf, params, prompt)
    np.testing.assert_allclose(np.concatenate(got), want, atol=TOL, rtol=0)
    srv.submit(prompt, max_new=steps + 1)
    while srv._slot_req[0] is None:
        srv._admit()
        srv._prefill_tick()
    srv.flush()
    assert srv._cur[0] == int(want[-1].argmax())
    seq = prompt + [srv._cur[0]]
    for _ in range(steps):
        pos = srv._pos[0]
        srv._ensure_block(0, pos)
        srv._pools, _, lg, _ = serving._paged_decode_rows(
            srv.params, srv._pools, None,
            jnp.asarray(srv._cur, jnp.int32), srv._tables_dev(),
            jnp.asarray(srv._pos, jnp.int32), cfg, srv._paged_fused)
        np.testing.assert_allclose(
            np.asarray(lg[0]), _ref_logits(conf, params, seq)[-1],
            atol=TOL, rtol=0)
        srv._cur[0] = int(np.asarray(lg[0]).argmax())
        srv._pos[0] += 1
        seq.append(srv._cur[0])


def _admission_logits(srv, prompt):
    """The seed logits of `prompt`'s admission and the decode logits of
    the steps after it, through the server's own programs."""
    srv.submit(prompt, max_new=6)
    probe = srv._probe_prog()
    seen = []
    # the probe's logits never leave its program (it returns the seed
    # token): the same tail (last layer's weights, the last chunk's
    # hidden row, the last layer's scratch entry, the position) over
    # the same entry, before the probe donates it
    logits = jax.jit(lambda params, row, kv, pos: tfm._window_tail(
        params, row, kv, pos, srv.cfg)[1][0])

    def spy(*a):
        seen.append(np.asarray(logits(*a[:4])))
        return probe(*a)
    srv._probe_prog = lambda: spy
    try:
        while srv._slot_req[0] is None:
            srv._admit()
            srv._prefill_tick()
    finally:
        del srv._probe_prog
    srv.flush()
    out = [seen[-1]]
    for _ in range(4):
        pos = srv._pos[0]
        srv._ensure_block(0, pos)
        srv._pools, _, lg, _ = serving._paged_decode_rows(
            srv.params, srv._pools, None,
            jnp.asarray(srv._cur, jnp.int32), srv._tables_dev(),
            jnp.asarray(srv._pos, jnp.int32), srv.cfg, srv._paged_fused)
        out.append(np.asarray(lg[0]))
        srv._cur[0] = int(out[-1].argmax())
        srv._pos[0] += 1
    return np.stack(out)


@pytest.mark.parametrize("which,kernel", [("toy", "gather"),
                                          ("toy128", "fused")])
def test_a_document_served_from_the_tree_gives_the_same_logits(
        request, which, kernel):
    """Prefix reuse over the latent pool, every layer "mla": a request
    whose document's rows come out of shared blocks (matched, gathered
    into the scratch, spliced past them) has bit for bit the float32
    logits of the same request on a server that never saw the
    document, and both are the reference's."""
    conf, cfg, params = request.getfixturevalue(which)
    doc, question = _prompt(32, 3), _prompt(9, 4)

    def server(**kw):
        return ContinuousServer(params, cfg, paged=True, slots=1, smax=64,
                                block_size=16, prefill_chunk=CHUNK,
                                paged_kernel=kernel, **kw)
    # the loader publishes the document's two blocks at its retirement.
    # One token more than the document, so that the probe's one-row
    # pass of the last layer (the last prompt token again, the same row
    # to rounding but through a matmul of another shape) lands behind
    # the blocks that are shared: both servers' document rows then come
    # out of chunks
    shared = server()
    shared.submit(doc + [7], max_new=1)
    shared.run()
    assert shared.cache_stats()["blocks_held"] == 2
    got = _admission_logits(shared, doc + question)
    st = shared.cache_stats()
    assert st["prefill_tokens_saved"] == 32
    assert st["prefill_tokens_computed"] == 33 + 9
    assert st["shared"] == 2                # the document's two blocks
    alone = _admission_logits(server(prefix_reuse=False), doc + question)
    np.testing.assert_array_equal(got, alone)
    want = _ref_logits(conf, params, doc + question)[-1]
    np.testing.assert_allclose(got[0], want, atol=TOL, rtol=0)


def test_requests_over_shared_documents_decode_the_references_tokens(toy):
    conf, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                           block_size=16, prefill_chunk=CHUNK)
    docs = [_prompt(48, 1), _prompt(32, 2)]
    for d in docs:
        srv.submit(d, max_new=1)
    srv.run()
    reqs = [(docs[i % 2] + _prompt(5 + i, 10 + i), 6) for i in range(5)]
    rids = [srv.submit(p, max_new=m) for p, m in reqs]
    out = srv.run()
    for rid, (p, m) in zip(rids, reqs):
        seq = list(p)
        for t in out[rid]:
            lg = _ref_logits(conf, params, seq)[-1]
            assert lg.max() - lg[t] < 10 * TOL      # the best, or a tie
            seq.append(t)
    st = srv.cache_stats()
    assert st["prefill_tokens_saved"] == 3 * 48 + 2 * 32
    assert st["in_use"] == 1 + st["blocks_held"] and st["shared"] == 0


@pytest.mark.parametrize("leave_out", ["rotation", "mscale", "q_norm",
                                       "kv_norm", "group_limit", "scaling",
                                       "shared"])
def test_a_reference_with_a_piece_left_out_disagrees(toy, leave_out):
    conf, cfg, params = toy
    toks = np.asarray([_prompt(40, 9)], np.int32)
    whole = np.asarray(ref.logits(params, conf, toks))
    cut = np.asarray(ref.logits(params, conf, toks,
                                leave_out=(leave_out,)))
    assert np.abs(whole - cut).max() > 100 * TOL


def test_the_routers_weights_are_balanced_from_the_seed_alone(toy):
    """`balance_router` takes out of W_g what the seeded tokens' mean
    router input gives every column: on FRESH tokens the held group
    gets about its eighth of the assignments, where the drawn weights
    send a seed's tokens one way; the same seed gives the same
    weights."""
    conf, cfg, params = toy
    drawn = drv.make_params(cfg, 11)
    again = drv.balance_router(drv.make_params(cfg, 11), conf, 11)
    fresh = np.random.default_rng(3).integers(1, cfg.vocab, (16, 96))

    def shares(p):
        out = []

        def visit(lp, u):
            g = jax.nn.softmax(jnp.tensordot(
                u, lp["moe"]["wg"].astype(jnp.float32), axes=1), -1)
            idx, _ = ref.choose(g, top_k=6, n_group=8, topk_group=3)
            out.append(np.bincount(np.asarray(idx).ravel() // 4,
                                   minlength=8) / idx.size)
            return lp
        ref.forward(p, conf, fresh, visit=visit)
        return np.asarray(out)
    even, skewed = shares(params), shares(drawn)
    assert np.abs(even - 0.125).max() < np.abs(skewed - 0.125).max()
    assert np.abs(even - 0.125).max() < 0.06
    for lp, lq in zip(params["layers"], again["layers"]):
        if "moe" in lp:
            np.testing.assert_array_equal(lp["moe"]["wg"], lq["moe"]["wg"])


# -- counters and spans ---------------------------------------------------

def test_prefix_and_routing_counters_and_spans(toy):
    _, cfg, params = toy
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.trace.enabled", "1")
    tr = tracing.start_if_configured()
    try:
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                               block_size=16, prefill_chunk=CHUNK)
        doc = _prompt(32, 5)
        srv.submit(doc, max_new=1)
        srv.run()
        rid = srv.submit(doc + _prompt(7, 6), max_new=9)
        srv.submit(doc + _prompt(5, 7), max_new=9)
        for _ in range(4):
            srv.step()
        mid = srv.cache_stats()
        srv.run()
        events = [(e[1], e[7] or {}) for e in tr.snapshot() if e[0] == "B"]
    finally:
        tracing.stop_tracing()
        rc.set("hpx.trace.enabled", "0")
    matches = [a for n, a in events if n == "serving.prefix_match"]
    assert {"rid": rid, "plen": 39} in matches and len(matches) == 3
    gathers = [a for n, a in events if n == "serving.prefix_gather"]
    assert {"rid": rid, "matched": 32, "plen": 39} in gathers
    # two readers and the tree hold the document's two blocks
    assert mid["shared"] == 2 and mid["latent_rows_walked_per_step"] > 64
    inst = srv.counter_instance

    def counter(obj, name):
        return pc.query_counter(pc.counter_name(obj, name, inst)).value
    st = srv.cache_stats()
    assert st["prefill_tokens_saved"] == 64
    assert counter("cache", "prefill-tokens/saved") == 64
    assert counter("cache", "prefill-tokens/computed") == 32 + 7 + 5
    assert counter("cache", "blocks/shared") == st["shared"] == 0
    assert counter("cache", "latent/rows-walked") == 0      # nothing live
    ms = srv.moe_stats()
    assert ms["dropped"] == 0 and len(srv._moe_occ) == 4
    assert ms["routed"] == ms["steps"] * 2 * 2 * 6  # slots x layers x k
    assert 0 < ms["routed_here"] < ms["routed"]
    assert ms["routed_here"] <= 6 * ms["tokens_here"] <= ms["routed"] * 3
    assert counter("serving", "moe/routed-here") == ms["routed_here"]
    assert counter("serving", "moe/tokens-here") == ms["tokens_here"]
    # a model without groups reports neither
    assert "routed_here" not in ContinuousServer(
        *_plain_moe(), paged=True, slots=1, smax=32).moe_stats()


def test_a_chunk_past_the_constant_walks_in_groups_and_is_counted(
        toy, monkeypatch):
    """A 16-wide chunk of the toy's 4 heads is 64 pairs: under a
    constant of 32 each of its three latent layers walks the scratch
    twice (2 heads a group), an 8-wide chunk once; `prefill_stats()["latent_groups"]`
    and the /serving{...}/prefill/latent_groups counter follow the
    widths dispatched (no device read), the programs built under the
    constant hold a `while` a group, and the tokens are the
    reference's."""
    conf, cfg, params = toy
    monkeypatch.setattr(tfm, "LATENT_PAIRS_A_GROUP", 32)
    had = set(tfm._PROGRAMS)
    try:
        # an smax no other test's programs were built for
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=80,
                               block_size=16, prefill_chunk=16,
                               prefix_reuse=False)
        assert srv.prefill_buckets == (8, 16)
        prompt = _prompt(21, 3)             # a 16-wide chunk, an 8-wide
        rid = srv.submit(prompt, max_new=4)
        out = srv.run()
        st = srv.prefill_stats()
        assert (st["prefill_chunks"], st["latent_groups"]) == (2, 3 * (2 + 1))
        assert pc.query_counter(pc.counter_name(
            "serving", "prefill/latent_groups",
            srv.counter_instance)).value == 9
        scratch = jax.eval_shape(srv._fresh_scratch)
        for width, loops in ((8, 3), (16, 6)):
            jaxpr = jax.make_jaxpr(srv._chunk_prog(width))(
                params, scratch, np.zeros((1, width), np.int32),
                np.int32(0), np.int32(width)).jaxpr
            assert len(_whiles(jaxpr)) == loops
        srv.submit(_prompt(7, 4), max_new=2)    # one 8-wide chunk more
        srv.run()
        assert srv.prefill_stats()["latent_groups"] == 9 + 3
    finally:
        for key in set(tfm._PROGRAMS) - had:
            del tfm._PROGRAMS[key]
    seq = list(prompt)
    for t in out[rid]:
        lg = _ref_logits(conf, params, seq)[-1]
        assert lg.max() - lg[t] < 10 * TOL
        seq.append(t)
    # under the default constant every chunk of the toy is one group
    monkeypatch.undo()
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=64,
                           block_size=16, prefill_chunk=16,
                           prefix_reuse=False)
    srv.submit(prompt, max_new=1)
    srv.run()
    assert srv.prefill_stats()["latent_groups"] == 3 * 2


def _plain_moe():
    cfg = tfm.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                head_dim=8, n_layers=1, d_ff=32,
                                n_experts=4)
    return tfm.init_params(cfg, jax.random.PRNGKey(0)), cfg


def test_the_tree_trims_its_oldest_leaves_in_one_walk():
    """`RadixCache._evict_locked`: one walk a call, the order a fresh
    search a block would give (least recently used idle leaf first, a
    parent once its last child went), chains a request reads left."""
    from hpx_tpu.cache.block_allocator import BlockAllocator
    from hpx_tpu.cache.radix import RadixCache
    alloc = BlockAllocator(32, 2)
    tree = RadixCache(alloc, None)

    def publish(tokens):
        bids = [alloc.alloc() for _ in range(len(tokens) // 2)]
        tree.insert(tokens, bids)
        for b in bids:
            alloc.decref(b)
        return bids
    doc = publish([1, 2, 3, 4])
    old = publish([1, 2, 3, 4, 5, 6])[2:]
    new = publish([1, 2, 3, 4, 7, 8])[2:]
    held = publish([9, 9])
    _, lease = tree.match([9, 9])               # a request reads it
    assert lease == held[:1] and tree.match([1, 2, 3, 4])[0] == 4
    for b in doc:
        alloc.decref(b)
    assert alloc.stats()["shared"] == 1
    assert sum(tree.evict(1)) == 1 and alloc.refcount(old[0]) == 0
    assert alloc.refcount(new[0]) == 1
    assert sum(tree.evict(10)) == 3             # new's leaf, then the doc
    assert tree.stats()["blocks_held"] == 1 and alloc.refcount(held[0]) == 2


# -- what cannot run such a model says so, by mechanism and module --------

def _refusals(cfg, params):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    paged = dict(paged=True, slots=2, smax=64)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    srv = lambda **kw: ContinuousServer(params, cfg, **{**paged, **kw})  # noqa
    return {
        "mesh": (r"a \(dp, tp\) mesh.*mixers", lambda: srv(mesh=mesh)),
        "dense": (r"dense server mode is gone.*generate\(\)",
                  lambda: srv(paged=False)),
        "spec": (r"speculative verify", lambda: srv(spec=True)),
        "quantized": (r"quantized latent row", lambda: srv(kv_dtype="int8")),
        "generate": (r"generate: the dense K/V caches.*layer_mixer",
                     lambda: tfm.generate(params, cfg, prompt)),
        "beam_search": (r"beam_search.*K/V pairs",
                        lambda: tfm.beam_search(params, cfg, prompt)),
        "param_specs": (r"param_specs.*models/transformer.py",
                        lambda: tfm.param_specs(cfg)),
        "capacity_moe": (r"moe_ffn_serve", lambda: moe.moe_ffn(
            jnp.zeros((4, 64)), params["layers"][1]["moe"],
            dataclasses.replace(tfm._moe_cfg(cfg), mlp="gelu", scale=1.0,
                                shared_d_ff=0, held=()))),
        "only_groups": (
            r"`moe_n_group` = 8 has no path there",
            lambda: dataclasses.replace(
                tfm.TransformerConfig(), moe_n_group=8).only(
                    "a body", "a/module.py")),
    }


@pytest.mark.parametrize("what", sorted(_refusals(None, None)))
def test_bodies_without_a_path_refuse_by_mechanism_and_module(toy, what):
    _, cfg, params = toy
    match, call = _refusals(cfg, params)[what]
    # the one value `paged` has left is refused by name, not by mixer
    with pytest.raises(ValueError if what == "dense"
                       else NotImplementedError, match=match):
        call()
