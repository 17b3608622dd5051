"""A model whose MIXERS differ, through the one layer definition and the
paged server, against the plain reference of chipbench/reference/
kimi_linear.py: Kimi-Delta-Attention layers (a per-slot float32 state
and a conv tail, no positions) beside latent-attention layers (MLA,
NoPE: one cached row a token), a dense SiLU-gated layer 1 and sparse
layers that hold a SHARE of the experts under a full-width router with
a selection bias and a shared expert, an untied head. A 5-layer toy of
Kimi-Linear-48B-A3B's shape at sizes a CPU holds, seeded random weights
made by the benchmark's own driver (chipbench/drivers/serving_hybrid.py),
float32.

Tolerances, and why. Program and reference are both float32 on the CPU
and differ in the ORDER of their sums: the chunkwise form of the delta
rule against the token scan (a triangular solve a block where the scan
substitutes token by token), the absorbed form of MLA against the
expanded one, a grouped expert product against a dense loop, a paged
gather against a full matrix. Logits of order 1 then agree to 5e-4
absolute (`TOL`; found: under 1e-4); the recurrence's forms among
themselves to 2e-5 on outputs of order 0.05 (`KDA_TOL`). A dropped
decay, beta, convolution, norm, gate, bias or shared expert moves
logits by tenths.
"""

import contextlib
import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.drivers import serving_hybrid as drv
from chipbench.reference import kimi_linear as ref
from hpx_tpu.models import moe, serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import kda
from hpx_tpu.ops import paged_attention as pa
from hpx_tpu.svc import faultinject, tracing
from hpx_tpu.svc import performance_counters as pc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, KDA_TOL = 5e-4, 2e-5
CHUNK = 8


def _conf(**over):
    with open(os.path.join(ROOT,
                           "chipbench/configs/kimi-linear-48b.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT,
                           "chipbench/tests/rehearse_hybrid.json")) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    return harness._merge(conf, over)


@pytest.fixture(scope="module")
def toy():
    conf = _conf()
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 11)


@pytest.fixture(scope="module")
def toy128():
    """The toy with heads and a latent rank of whole 128-lane rows: the
    widths at which the two Pallas kernels are taken."""
    conf = _conf(kv_lora_rank=128, linear_attn_config={"head_dim": 128})
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 12)


def _loads(conf, params, tokens):
    """Each sparse layer's share of the choices an expert takes, over
    the mean share (1.0 everywhere = even loads), on `tokens`."""
    k, out = conf["num_experts_per_token"], []

    def visit(lp, s):
        sel = np.asarray(s + lp["moe"]["bias"]).reshape(-1, s.shape[-1])
        idx = np.argsort(-sel, -1)[:, :k]
        out.append(np.bincount(idx.ravel(), minlength=sel.shape[1])
                   * sel.shape[1] / idx.size)
        return lp
    ref.forward(params, conf, tokens, visit=visit)
    return np.asarray(out)


def test_the_selection_bias_is_balanced_from_the_seed_alone(toy, monkeypatch):
    """`make_params` balances every sparse layer's selection bias over
    seeded tokens (random hidden states share a direction, so a random
    router favours the same experts for every token, which ones by the
    seed): on FRESH tokens the loads are far more even than the 0.01
    normal draw leaves them, and the same seed gives the same bias."""
    conf, cfg, params = toy
    again = drv.make_params(cfg, 11)
    monkeypatch.setattr(drv, "balance_router", lambda p, c, s: p)
    drawn = drv.make_params(cfg, 11)
    fresh = np.random.default_rng(3).integers(1, cfg.vocab, (16, 96))
    even, skewed = _loads(conf, params, fresh), _loads(conf, drawn, fresh)
    assert even.shape == skewed.shape == (4, 16)
    assert np.abs(skewed - 1).max() > 2 * np.abs(even - 1).max()
    assert even.std() < 0.5 * skewed.std() and even.min() > 0.5
    for lp, lq, lr in zip(params["layers"], again["layers"],
                          drawn["layers"]):
        if "moe" in lp:
            np.testing.assert_array_equal(lp["moe"]["bias"],
                                          lq["moe"]["bias"])
            assert np.std(lp["moe"]["bias"]) > 2 * np.std(lr["moe"]["bias"])
            np.testing.assert_array_equal(lp["moe"]["wg"], lr["moe"]["wg"])


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


def _ref_logits(conf, params, seq):
    return np.asarray(ref.logits(params, conf,
                                 np.asarray([seq], np.int32)))[0]


def _ref_greedy(conf, params, prompt, max_new, frame=144):
    """The reference's own greedy continuation, in one padded frame."""
    seq, out = list(prompt), []
    for _ in range(max_new):
        toks = np.zeros((1, frame), np.int32)
        toks[0, :len(seq)] = seq
        lg = np.asarray(ref.logits(params, conf, toks))[0, len(seq) - 1]
        out.append(int(lg.argmax()))
        seq.append(out[-1])
    return out


def test_the_toy_has_every_mechanism(toy):
    conf, cfg, params = toy
    assert cfg.layer_mixer == ("kda", "kda", "mla", "kda", "mla")
    assert [cfg.sparse(i) for i in range(5)] == [False] + [True] * 4
    assert cfg.recurrent and cfg.n_experts == 16 and cfg.moe_held == (4, 8)
    assert cfg.experts_held == 4 and cfg.mla_row == 128
    m = params["layers"][1]["moe"]
    assert m["wg"].shape == (64, 16) and m["w1"].shape[0] == 4
    assert m["bias"].shape == (16,) and "shared" in m
    assert "kda" in params["layers"][0] and "mla" in params["layers"][2]
    assert "head" in params
    # the published pattern, 1-indexed, at full size
    full = drv.build_cfg(json.load(open(os.path.join(
        ROOT, "chipbench/configs/kimi-linear-48b.json"))))
    assert [i + 1 for i in range(27) if full.mixer(i) == "mla"] == \
        [4, 8, 12, 16, 20, 24, 27]
    assert full.experts_held == 16 and full.n_experts == 256
    init = tfm.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), init) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), params)


# -- the recurrence: step == scan == chunkwise form -----------------------

def _kda_inputs(t, h=2, d=16, b=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    pre = jax.random.normal(ks[0], (b, t, 3, h, d))
    g = -jnp.exp(jax.random.uniform(ks[1], (b, t, h, d), minval=-6.0,
                                    maxval=1.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[2], (b, t, h)))
    conv = jax.random.normal(ks[3], (4, 3, h, d)) * 0.5
    state = jax.random.normal(ks[4], (b, h, d, d)) * 0.1
    tail = jax.random.normal(ks[5], (b, 3, 3 * h * d))
    return pre, g, beta, conv, state, tail


@pytest.mark.parametrize("width", [1, 16, 128, 37])
def test_kda_step_scan_and_chunkwise_forms_agree(width):
    """A window of W tokens in ONE pass (the chunkwise form; W = 1 the
    step) equals W single steps, outputs, state and conv tail; 37 is a
    ragged tail: one block of 32 and five rows of the next."""
    pre, g, beta, conv, state, tail = _kda_inputs(width)
    o_all, (s_all, t_all) = kda.kda_mix(pre, g, beta, conv, state, tail)
    s, tl, outs = state, tail, []
    for i in range(width):
        o, (s, tl) = kda.kda_mix(pre[:, i:i + 1], g[:, i:i + 1],
                                 beta[:, i:i + 1], conv, s, tl)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(o_all),
                               np.asarray(jnp.concatenate(outs, 1)),
                               atol=KDA_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(s_all), np.asarray(s),
                               atol=KDA_TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(t_all), np.asarray(tl))


@pytest.mark.parametrize("block", [1, 16, 64])
def test_kda_chunk_equals_the_token_scan_whatever_the_block(block):
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    b, t, h, d = 2, 70, 2, 32
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q, k = (unit(jax.random.normal(ks[i], (b, t, h, d))) for i in (0, 1))
    v = jax.random.normal(ks[2], (b, t, h, d))
    # decays from none to e^-20 a token: nothing overflows in a block
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, d), minval=-8.0,
                                    maxval=3.0))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, d, d)) * 0.1
    o_s, s_s = kda.kda_scan(q, k, v, g, beta, s0)
    o_c, s_c = kda.kda_chunk(q, k, v, g, beta, s0, block=block)
    assert np.isfinite(np.asarray(o_c)).all()
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_s),
                               atol=KDA_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_s),
                               atol=KDA_TOL, rtol=0)


def test_padding_columns_leave_state_and_tail_alone():
    """A bucketed chunk: 11 real columns of 16. State and tail are those
    of the 11 alone, whatever the padding holds."""
    pre, g, beta, conv, state, tail = _kda_inputs(16, seed=4)
    want_o, (want_s, want_t) = kda.kda_mix(
        pre[:, :11], g[:, :11], beta[:, :11], conv, state, tail)
    o, (s, tl) = kda.kda_mix(pre, g, beta, conv, state, tail,
                             valid=jnp.int32(11))
    np.testing.assert_allclose(np.asarray(o[:, :11]), np.asarray(want_o),
                               atol=KDA_TOL, rtol=0)
    np.testing.assert_allclose(np.asarray(s), np.asarray(want_s),
                               atol=KDA_TOL, rtol=0)
    np.testing.assert_array_equal(np.asarray(tl), np.asarray(want_t))


def test_kda_step_kernel_equals_its_xla_oracle():
    """`hpx_kda_step` in interpret mode: 16 heads (two grid steps of 8)
    and 3 (one head a step) of 128 x 128."""
    for h in (16, 3):
        ks = jax.random.split(jax.random.PRNGKey(h), 6)
        q, k, v, g = (jax.random.normal(ks[i], (3, h, 128))
                      for i in range(4))
        beta = jax.nn.sigmoid(jax.random.normal(ks[4], (3, h)))
        s0 = jax.random.normal(ks[5], (3, h, 128, 128)) * 0.1
        g = -jnp.abs(g)
        o_x, s_x = kda.kda_step(q, k, v, g, beta, s0, kernel="xla")
        o_p, s_p = kda.kda_step(q, k, v, g, beta, s0, kernel="pallas")
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_x),
                                   atol=1e-5, rtol=0)
        np.testing.assert_allclose(np.asarray(s_p), np.asarray(s_x),
                                   atol=1e-6, rtol=0)
    with pytest.raises(NotImplementedError, match="square state tiles"):
        kda.kda_step(q[..., :16], k[..., :16], v[..., :16], g[..., :16],
                     beta, s0[..., :16, :16], kernel="pallas")


# -- latent attention: absorbed == expanded; kernel == gather -------------

def test_mla_absorbed_equals_expanded_and_the_kernel_its_oracle():
    b, h, rank, dr, dn, dv, bs, maxb = 3, 4, 128, 8, 16, 16, 16, 5
    row_w = 256
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    pool = jnp.zeros((b * maxb + 1, 1, bs, row_w))
    table = (1 + jnp.arange(b * maxb, dtype=jnp.int32)).reshape(b, maxb)
    pos = jnp.array([0, 17, 79], jnp.int32)
    lat = jax.random.normal(ks[0], (b, maxb * bs, rank + dr))
    pool = pool.at[table].set(jnp.pad(
        lat, ((0, 0), (0, 0), (0, row_w - rank - dr))).reshape(
            b, maxb, 1, bs, row_w))
    wuk = jax.random.normal(ks[1], (rank, h, dn)) * rank ** -0.5
    wuv = jax.random.normal(ks[2], (rank, h, dv)) * rank ** -0.5
    q = jax.random.normal(ks[3], (b, h, dn + dr))
    new = jax.random.normal(ks[4], (b, rank + dr))
    scale = (dn + dr) ** -0.5
    pad = lambda a: jnp.pad(a, ((0, 0),) * (a.ndim - 1)              # noqa
                            + ((0, row_w - rank - dr),))
    qa = jnp.concatenate(
        [jnp.einsum("bhn,rhn->bhr", q[..., :dn], wuk), q[..., dn:]], -1)
    got = {f: pa.paged_latent_attention(
        pad(qa), pad(new), pool, table, pos, rank=rank, scale=scale,
        fused=f)[0] for f in (False, True)}
    np.testing.assert_allclose(np.asarray(got[True]),
                               np.asarray(got[False]), atol=1e-5, rtol=0)
    # the expanded form, one slot and head at a time, in NumPy
    lat = np.asarray(lat.at[jnp.arange(b), pos].set(new), np.float64)
    for i in range(b):
        n = int(pos[i]) + 1
        c, r = lat[i, :n, :rank], lat[i, :n, rank:]
        for j in range(h):
            kk = np.concatenate([c @ np.asarray(wuk[:, j]), r], -1)
            s = kk @ np.asarray(q[i, j], np.float64) * scale
            p = np.exp(s - s.max())
            p /= p.sum()
            want = p @ (c @ np.asarray(wuv[:, j]))
            have = np.asarray(got[False][i, j]) @ np.asarray(wuv[:, j])
            np.testing.assert_allclose(have, want, atol=1e-4, rtol=0)


# -- the whole model: chunked prefill, then decode, on LOGITS -------------

@pytest.mark.parametrize("which,kernel", [("toy", "gather"),
                                          ("toy128", "fused")])
def test_prefill_then_paged_decode_logits_equal_the_reference(
        request, which, kernel):
    conf, cfg, params = request.getfixturevalue(which)
    plen, steps = 29, 20
    prompt = _prompt(plen)
    # prefill chunk by chunk over the b=1 scratch, with logits: the
    # last chunk ragged (5 real columns of 8)
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
    # the same prompt through the server's admission, then its decode
    # program's forward, one step at a time
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


@pytest.mark.parametrize("leave_out", ["decay", "beta", "conv", "l2norm",
                                       "out_gate", "rope_dims", "bias",
                                       "shared"])
def test_a_reference_with_a_piece_left_out_disagrees(toy, leave_out):
    conf, cfg, params = toy
    toks = np.asarray([_prompt(40, 9)], np.int32)
    whole = np.asarray(ref.logits(params, conf, toks))
    cut = np.asarray(ref.logits(params, conf, toks,
                                leave_out=(leave_out,)))
    assert np.abs(whole - cut).max() > 200 * TOL


# -- the server: slots, resets, restores ----------------------------------

def test_two_requests_share_a_slot_one_after_the_other(toy):
    """One slot: the second request's state starts from zeros (the
    reset), nothing of the first survives; a one-token prompt is one
    chunk of one real column, then the probe."""
    conf, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=1, smax=144,
                           prefill_chunk=CHUNK)
    reqs = [(_prompt(21, 1), 9), (_prompt(1, 2), 7), (_prompt(8, 3), 5)]
    rids = [srv.submit(p, max_new=m) for p, m in reqs]
    out = srv.run()
    for rid, (p, m) in zip(rids, reqs):
        assert out[rid] == _ref_greedy(conf, params, p, m)
    st = srv.cache_stats()
    assert st["state_resets"] == 3 and st["state_slots_live"] == 0
    assert st["state_prefix_refused"] == 3 and st["tokens_matched"] == 0
    assert st["latent_blocks_in_use"] == 1          # the trash block
    h, d = cfg.kda_heads, cfg.kda_head_dim
    assert st["state_bytes"] == 3 * (h * d * d * 4 + 3 * 3 * h * d * 4)


@contextlib.contextmanager
def _inject(**kw):
    faultinject.install(faultinject.FaultInjector(**kw))
    try:
        yield
    finally:
        faultinject.uninstall()


@pytest.mark.parametrize("site", ["decode", "prefill"])
def test_a_faulted_step_gives_the_fault_free_tokens(toy, site):
    """No snapshot of a recurrent state is kept: a restore re-prefills
    prompt ++ the tokens the host holds and goes on from there."""
    _, cfg, params = toy
    reqs = [(_prompt(30, 4), 40), (_prompt(6, 5), 50), (_prompt(22, 6), 12)]

    def serve(fi=None):
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=144,
                               block_size=4, prefill_chunk=CHUNK)
        rids = [srv.submit(p, max_new=m) for p, m in reqs]
        with (_inject(**fi) if fi else contextlib.nullcontext()):
            out = srv.run()
        return [out[r] for r in rids], srv
    base, _ = serve()
    got, srv = serve({"schedule": {site: {2, 9, 23}}})
    assert got == base and srv.failed == {}
    assert srv.fault_stats()["restored_by_site"].get(site, 0) >= 1
    st = srv.cache_stats()
    assert st["state_reprefills"] >= 1 and st["in_use"] == 1
    assert not srv._ckpt or all(not c.pins for c in srv._ckpt.values())


def test_state_counters_and_spans(toy):
    _, cfg, params = toy
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.trace.enabled", "1")
    tr = tracing.start_if_configured()
    try:
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=144,
                               prefill_chunk=CHUNK)
        rid = srv.submit(_prompt(12), max_new=10)
        with _inject(schedule={"decode": {3}}):
            srv.run()
        events = [(e[1], e[7] or {}) for e in tr.snapshot() if e[0] == "B"]
    finally:
        tracing.stop_tracing()
        rc.set("hpx.trace.enabled", "0")
    resets = [a for n, a in events if n == "serving.state_reset"]
    assert resets == [{"rid": rid, "slot": 0, "layers": 3}]
    (again,) = [a for n, a in events if n == "serving.reprefill"]
    assert again["rid"] == rid and again["slot"] == 0 and \
        12 <= again["tokens"] < 22
    inst = srv.counter_instance

    def counter(obj, name):
        return pc.query_counter(pc.counter_name(obj, name, inst)).value
    st = srv.cache_stats()
    assert counter("cache", "state/bytes") == st["state_bytes"] > 0
    assert counter("cache", "state/slots-live") == 0
    assert counter("cache", "state/resets") == 1
    assert counter("cache", "latent/blocks-in-use") == 1
    assert counter("serving", "state/prefix-refused") == 1
    assert counter("serving", "state/reprefills") == 1
    # the statistics vector counts over the experts HELD
    ms = srv.moe_stats()
    assert ms["dropped"] == 0 and len(srv._moe_occ) == 4
    assert 0 < ms["experts_hit_sum"] / ms["steps"] <= 4
    # (hit or not, averaged over the four sparse layers)
    assert 0.0 <= counter("serving", "moe/expert#3/occupancy") <= 1.0


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
        "spec": (r"speculative verify.*rolled back",
                 lambda: srv(spec=True)),
        "quantized": (r"quantized latent row", lambda: srv(kv_dtype="int8")),
        "admit_prefilled": (
            r"admit_prefilled\(\).*mixers",
            lambda: srv().admit_prefilled([1, 2], None, 3, 4)),
        "export_prefix_rows": (
            r"export_prefix_rows\(\).*mixers",
            lambda: srv().export_prefix_rows([1, 2, 3])),
        "generate": (r"generate: the dense K/V caches.*layer_mixer",
                     lambda: tfm.generate(params, cfg, prompt)),
        "beam_search": (r"beam_search.*K/V pairs",
                        lambda: tfm.beam_search(params, cfg, prompt)),
        "speculative_generate": (
            r"speculative_generate.*K/V pairs",
            lambda: tfm.speculative_generate(params, cfg, params, cfg,
                                             prompt)),
        "train": (r"make_train_step.*models/transformer.py",
                  lambda: tfm.make_train_step(cfg, tfm.make_mesh_3d(1))),
        "pipeline": (r"make_pipelined_train_step",
                     lambda: tfm.make_pipelined_train_step(
                         cfg, tfm.make_mesh_3d(1), 2)),
        "capacity_moe": (r"moe_ffn_serve", lambda: moe.moe_ffn(
            jnp.zeros((4, 64)), {}, tfm._moe_cfg(cfg))),
        "prefill_worker": (
            r"PrefillWorker \(models/disagg.py\).*K/V pairs",
            lambda: __import__("hpx_tpu.models.disagg", fromlist=["x"])
            .PrefillWorker(params, cfg, smax=64, block_size=16)),
    }


@pytest.mark.parametrize("case", [
    "mesh", "dense", "spec", "quantized", "admit_prefilled",
    "export_prefix_rows", "generate", "beam_search",
    "speculative_generate", "train", "pipeline", "capacity_moe",
    "prefill_worker"])
def test_bodies_without_a_path_refuse_by_mechanism_and_module(toy, case):
    _, cfg, params = toy
    match, call = _refusals(cfg, params)[case]
    # the one value `paged` has left is refused by name, not by mixer
    with pytest.raises(ValueError if case == "dense"
                       else NotImplementedError, match=match):
        call()


def test_the_host_tier_is_refused(toy):
    _, cfg, params = toy
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.cache.tier.enable", "1")
    try:
        with pytest.raises(NotImplementedError, match="host tier"):
            ContinuousServer(params, cfg, paged=True, slots=2, smax=64)
    finally:
        rc.set("hpx.cache.tier.enable", "0")


# -- the share of the experts, at the model's level ------------------------

def test_the_16_shares_of_a_sparse_layer_add_up_to_the_uncut_reference():
    """One sparse layer of the model's form (256-wide sigmoid router
    with a selection bias, top-8 renormalised x 2.446, a shared expert)
    under the 16 shares of 16 experts the deployment places on its 16
    chips: the bias in every share, the shared expert counted once,
    against the UNCUT reference layer."""
    d, f = 32, 16
    cfg = moe.MoeConfig(n_experts=256, top_k=8, d_model=d, d_ff=f,
                        mlp="swiglu", router="sigmoid", renorm=True,
                        scale=2.446, shared_d_ff=f, bias=True)
    p = moe.init_moe_params(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 24, d))
    conf = {"rms_norm_eps": 1e-5}
    lp = {"ln2": jnp.ones((d,)), "moe": p}
    kw = dict(eps=1e-5, quant=None, top_k=8, scale=2.446, lo=0,
              leave_out=())
    whole = np.asarray(ref._sparse_ffn(x, lp, **kw) - x)[0]
    no_bias = np.asarray(ref._sparse_ffn(
        x, lp, **{**kw, "leave_out": ("bias",)}) - x)[0]
    assert np.abs(whole - no_bias).max() > 0.05     # the bias decides
    u = ref._rms(x, lp["ln2"], conf["rms_norm_eps"])[0]
    total = 0
    for i in range(16):
        lo, hi = 16 * i, 16 * i + 16
        share = {"wg": p["wg"], "bias": p["bias"],
                 **{k: p[k][lo:hi] for k in ("w1", "w3", "w2")}}
        if i == 5:
            share["shared"] = p["shared"]
        held = dataclasses.replace(cfg, held=(lo, hi))
        assert moe.init_moe_params(held, jax.random.PRNGKey(1))[
            "w1"].shape == (16, d, f)
        out, stats = moe.moe_ffn_serve(u, share, held)
        assert stats.shape == (2 + 16,)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), whole, atol=2e-5, rtol=0)


def test_served_at_the_ceiling_width_gives_the_tokens_of_128(toy,
                                                             monkeypatch):
    """A model with recurrent and latent layers served with no stated chunk width on a
    device whose ridge puts it at the ceiling: the chunks of 512 rows
    (and the tail that smax splits: 512 + 256 > 720, so 128 then 64)
    leave the tokens that chunks of 128 leave."""
    from hpx_tpu.svc import progprof
    _, cfg, params = toy
    reqs = [(_prompt(700, 5), 12), (_prompt(513, 6), 8),
            (_prompt(90, 7), 10)]

    def serve(**kw):
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=720,
                               **kw)
        rids = [srv.submit(p, max_new=m) for p, m in reqs]
        out = srv.run()
        return [out[r] for r in rids], srv
    base, at128 = serve(prefill_chunk=128)
    monkeypatch.setattr(progprof, "device_ridge", lambda: 240.0)
    got, srv = serve()
    st = srv.cache_stats()
    assert (st["prefill_chunk"], st["prefill_chunk_source"]) == (
        serving._CHUNK_CEILING, "ridge")
    assert got == base and srv.failed == {}
    # the chunks run to the prompt's end: 700: 512 128 64; 513: 512 8;
    # 90: 128
    assert (at128._chunks, srv._chunks) == (6 + 5 + 1, 3 + 2 + 1)
