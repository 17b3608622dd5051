"""A model whose attention layers CHOOSE what they read, beside layers
that keep a decayed linear state, through the one layer definition and
the paged server, against the plain reference of chipbench/reference/
minicpm_sala.py: learned block-sparse attention (InfLLM-v2: an index
of compressed keys beside the K/V pools, the best blocks a query and kv
group, walked by table; NoPE, QK-norm, an elementwise output gate) and
lightning attention (a per-slot float32 state under a constant decay a
head and PUBLISHED layer; RoPE, QK-norm, an output norm, the gate),
with the MiniCPM family's three scales. A 4-layer toy of MiniCPM-SALA's
shape at sizes a CPU holds (blocks of 8 rows, 4 of them a query, dense
up to 32 rows), seeded random weights made by the benchmark's own
driver (chipbench/drivers/serving_sparse.py), float32.

Tolerances, and why. Program and reference are both float32 on the CPU
and differ in the ORDER of their sums: the chunkwise form of the linear
recurrence against the token scan, an online softmax over blocks of
rows (prefill) or one softmax over a bank of chosen pages (decode)
against a full masked matrix, index entries scored once against
compressed keys built first. Logits of order 1 then agree to 5e-4
absolute (`TOL`; found: under 1e-5); the recurrence's forms among
themselves to 2e-5 (`LIN_TOL`). A piece of the mathematics left out
moves logits by hundredths to tenths: each such case is held to 50 x
`TOL`.
"""

import contextlib
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.drivers import serving_sparse as drv
from chipbench.reference import minicpm_sala as ref
from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import lightning as lt
from hpx_tpu.ops import sparse_attention as sa
from hpx_tpu.svc import faultinject, tracing
from hpx_tpu.svc import performance_counters as pc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, LIN_TOL = 5e-4, 2e-5
CHUNK = 16


def _conf(**over):
    with open(os.path.join(ROOT,
                           "chipbench/configs/minicpm-sala.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT,
                           "chipbench/tests/rehearse_sparse.json")) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    return harness._merge(conf, over)


@pytest.fixture(scope="module")
def toy():
    conf = _conf()
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 11)


@pytest.fixture(scope="module")
def toy128():
    """The toy with heads of whole 128-lane rows: the width at which the
    two Pallas kernels are taken."""
    conf = _conf(head_dim=128, lightning_head_dim=128)
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 12)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


def _ref_logits(conf, params, seq, frame=128):
    toks = np.zeros((1, frame), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(ref.logits(params, conf, toks))[0, :len(seq)]


@contextlib.contextmanager
def _inject(**kw):
    faultinject.install(faultinject.FaultInjector(**kw))
    try:
        yield
    finally:
        faultinject.uninstall()


def test_the_toy_has_every_mechanism(toy):
    conf, cfg, params = toy
    assert cfg.layer_mixer == ("sparse", "lightning", "lightning", "sparse")
    assert cfg.recurrent and cfg.layer_published == (9, 10, 15, 16)
    assert cfg.published_layers == 32 and cfg.qk_norm
    assert cfg.emb_scale == 12 and cfg.logit_scale == 0.5
    assert cfg.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert [r is None for r in cfg.layer_rope] == [True, False, False, True]
    sp = cfg.sparse_spec
    assert (sp.kernel, sp.stride, sp.block, sp.topk, sp.init, sp.local,
            sp.dense_len, sp.width) == (4, 2, 8, 4, 1, 8, 32, 4)
    assert set(params["layers"][0]["sparse"]) == {
        "wq", "wkv", "qnorm", "knorm", "wg", "wo"}
    assert set(params["layers"][1]["lightning"]) == {
        "wq", "wk", "wv", "qnorm", "knorm", "onorm", "wg", "wo"}


def test_the_cells_configuration_is_the_published_one_cut_in_depth():
    with open(os.path.join(ROOT,
                           "chipbench/configs/minicpm-sala.json")) as f:
        conf = json.load(f)
    cfg = drv.build_cfg(conf)
    assert cfg.layer_mixer == ("sparse",) + ("lightning",) * 6 + ("sparse",)
    assert cfg.layer_published == tuple(range(9, 17))
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab) == (4096, 32, 2, 128, 16384, 73448)
    assert cfg.sparse_spec.width == 128 and not cfg.tied
    shapes = jax.eval_shape(lambda: drv.make_params(cfg, 1))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 2820.5e6) < 0.1e6          # 5.64 GB in bfloat16


# -- the linear recurrence's three forms ----------------------------------

def _lin_inputs(b, t, h, d, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k, v = (jax.random.normal(kk, (b, t, h, d), jnp.float32)
               for kk in ks[:3])
    s0 = jax.random.normal(ks[3], (b, h, d, d), jnp.float32)
    return q * d ** -0.5, k, v, s0


@pytest.mark.parametrize("width", [1, 16, 128, 37])
def test_lightning_step_scan_and_chunkwise_forms_agree(width):
    """With the PUBLISHED decays of layer 10 of 32: heads that forget
    in two tokens beside heads that remember hundreds."""
    q, k, v, s0 = _lin_inputs(2, width, 4, 16, width)
    g = jnp.asarray(lt.lightning_log_decay(4, 10, 32))
    want, s_want = lt.lightning_scan(q, k, v, g, s0)
    got, (s_got,) = lt.lightning_mix(q, k, v, g, s0)
    np.testing.assert_allclose(got, want, atol=LIN_TOL * 10, rtol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=LIN_TOL * 10, rtol=1e-5)
    outs, s = [], s0
    for i in range(width):
        o, s = lt.lightning_step(q[:, i], k[:, i], v[:, i], g, s)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want, atol=LIN_TOL * 10,
                               rtol=1e-5)


@pytest.mark.parametrize("block", [1, 16, 64])
def test_lightning_chunk_equals_the_token_scan_whatever_the_block(block):
    q, k, v, s0 = _lin_inputs(1, 50, 4, 16, block)
    g = jnp.asarray(lt.lightning_log_decay(4, 15, 32))
    want, s_want = lt.lightning_scan(q, k, v, g, s0)
    got, s_got = lt.lightning_chunk(q, k, v, g, s0, block=block)
    np.testing.assert_allclose(got, want, atol=LIN_TOL * 10, rtol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=LIN_TOL * 10, rtol=1e-5)


def test_the_decay_is_the_published_layers():
    g = lt.lightning_log_decay(32, 9, 32)
    h = np.arange(32)
    np.testing.assert_allclose(
        g, -(2.0 ** (-8 * (h + 1) / 32)) * (1 - 9 / 31 + 1e-5), rtol=1e-6)
    assert g[0] < g[-1] < 0 and lt.lightning_log_decay(32, 16, 32)[3] > g[3]
    np.testing.assert_allclose(g, ref.log_decay(32, 9, 32), rtol=1e-6)


def test_padding_columns_leave_the_state_alone():
    q, k, v, s0 = _lin_inputs(2, 16, 4, 16, 3)
    g = jnp.asarray(lt.lightning_log_decay(4, 10, 32))
    want, s_want = lt.lightning_scan(q[:, :11], k[:, :11], v[:, :11], g, s0)
    got, (s_got,) = lt.lightning_mix(q, k, v, g, s0, valid=jnp.int32(11))
    np.testing.assert_allclose(got[:, :11], want, atol=LIN_TOL * 10,
                               rtol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=LIN_TOL * 10, rtol=1e-5)


def test_lightning_step_kernel_equals_its_xla_oracle():
    q, k, v, s0 = _lin_inputs(3, 1, 8, 128, 5)
    g = jnp.asarray(lt.lightning_log_decay(8, 12, 32))
    want, s_want = lt.lightning_step(q[:, 0], k[:, 0], v[:, 0], g, s0,
                                     kernel="xla")
    got, s_got = lt.lightning_step(q[:, 0], k[:, 0], v[:, 0], g, s0,
                                   kernel="pallas")
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(s_got, s_want, atol=1e-5, rtol=1e-6)
    with pytest.raises(NotImplementedError, match="hpx_lightning_step"):
        lt.lightning_step(q[:, 0, :, :16], k[:, 0, :, :16], v[:, 0, :, :16],
                          g, s0[..., :16, :16], kernel="pallas")


# -- the selection and the walk ---------------------------------------------

SPEC = sa.SparseSpec(kernel=4, stride=2, block=8, topk=4, init=1, local=8,
                     dense_len=32)


def _paged_case(pos, hd, seed, nan=True):
    """B slots of a paged sparse layer at positions `pos`: logical rows,
    their pools on a shuffled table, NaN in every row behind a slot's
    position (the rest of its last page and every page past it) and in
    every index entry of a group not yet begun."""
    b, nkv, g, bs, maxb = len(pos), 2, 2, SPEC.block, 12
    rng = np.random.default_rng(seed)
    rows_k = rng.standard_normal((b, maxb * bs, nkv, hd)).astype(np.float32)
    rows_v = rng.standard_normal((b, maxb * bs, nkv, hd)).astype(np.float32)
    q = rng.standard_normal((b, 1, nkv * g, hd)).astype(np.float32)
    table = 1 + rng.permutation(b * maxb).reshape(b, maxb).astype(np.int32)
    nb = b * maxb + 1
    per = bs // SPEC.stride
    kp = np.full((nb, nkv, bs, hd), np.nan, np.float32)
    vp = np.full((nb, nkv, bs, hd), np.nan, np.float32)
    ip = np.full((nb, per * nkv, hd), np.nan, np.float32)
    for i, p in enumerate(pos):
        full = np.asarray(sa.index_blocks(jnp.asarray(rows_k[i]), SPEC, bs))
        for c in range(maxb):
            blk = slice(c * bs, (c + 1) * bs)
            kp[table[i, c]] = np.swapaxes(rows_k[i, blk], 0, 1)
            vp[table[i, c]] = np.swapaxes(rows_v[i, blk], 0, 1)
            ip[table[i, c]] = full[c]
        if nan:     # rows >= p are not written yet (p's own comes now)
            for r in range(p, maxb * bs):
                kp[table[i, r // bs], :, r % bs] = np.nan
                vp[table[i, r // bs], :, r % bs] = np.nan
            first = p // SPEC.stride        # the group p is in, and later
            for e in range(first, maxb * per):
                ip[table[i, e // per],
                   (e % per) * nkv:(e % per + 1) * nkv] = np.nan
    return (q, rows_k, rows_v, jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(ip), jnp.asarray(table),
            jnp.asarray(pos, jnp.int32))


def _dense_oracle(q, rows_k, rows_v, pos, ids, count):
    """NumPy: attention of each slot's query over the rows <= pos of the
    blocks `ids[:count]` names."""
    b, _, nq, hd = q.shape
    nkv = rows_k.shape[2]
    out = np.zeros((b, 1, nq, hd), np.float32)
    for i in range(b):
        for h in range(nq):
            n = h // (nq // nkv)
            rows = np.concatenate([np.arange(c * SPEC.block,
                                             (c + 1) * SPEC.block)
                                   for c in ids[i, n, :count[i, n]]])
            rows = rows[rows <= pos[i]]
            s = rows_k[i, rows, n] @ q[i, 0, h] / np.sqrt(hd)
            p = np.exp(s - s.max())
            out[i, 0, h] = (p / p.sum()) @ rows_v[i, rows, n]
    return out


# positions below, at and past dense_len (32), with a partial last block
POSITIONS = [[5, 30, 31], [32, 33, 47], [63, 70, 95]]


@pytest.mark.parametrize("kernel,hd", [("gather", 16), ("pallas", 128)])
@pytest.mark.parametrize("pos", POSITIONS, ids=["below", "at", "past"])
def test_the_walk_equals_a_gather_oracle(pos, kernel, hd):
    q, rows_k, rows_v, kp, vp, ip, table, p = _paged_case(pos, hd, 7)
    b = len(pos)
    k_new = jnp.asarray(rows_k[np.arange(b), pos])
    v_new = jnp.asarray(rows_v[np.arange(b), pos])
    o, kp2, vp2, ip2, ids, count = sa.paged_sparse_decode(
        jnp.asarray(q), k_new, v_new, kp, vp, ip, table, p, SPEC, kernel)
    ids, count = np.asarray(ids), np.asarray(count)
    assert not np.isnan(np.asarray(o)).any()
    np.testing.assert_allclose(
        o, _dense_oracle(q, rows_k, rows_v, pos, ids, count),
        atol=2e-5, rtol=1e-5)
    for i, t in enumerate(pos):
        live = t // SPEC.block + 1
        for n in range(2):
            got = ids[i, n, :count[i, n]]
            assert list(got) == sorted(set(got)) and got[-1] == live - 1
            if t + 1 <= SPEC.dense_len:
                assert list(got) == list(range(live))
            else:       # block 0, the last 8 rows' blocks, 4 in all
                assert count[i, n] == 4 and got[0] == 0
                assert (t - 7) // SPEC.block in got
        # the row and its group's mean are in the pools
        page = int(table[i, t // SPEC.block])
        np.testing.assert_array_equal(kp2[page, :, t % SPEC.block],
                                      rows_k[i, t])
        grp = t // SPEC.stride * SPEC.stride
        entry = (t % SPEC.block) // SPEC.stride * 2
        np.testing.assert_allclose(
            ip2[page, entry:entry + 2],
            rows_k[i, grp:t + 1].sum(0) / SPEC.stride, atol=1e-6)


@pytest.mark.parametrize("pos", POSITIONS, ids=["below", "at", "past"])
def test_the_programs_selection_is_the_references(pos):
    """`select` (the index entries scored once, a top-k, a sort) against
    the reference's masks (compressed keys built first, ranks): the same
    blocks, up to near-ties of the last chosen score (none at float32 on
    these seeds)."""
    q, rows_k, _, _, _, _, _, _ = _paged_case(pos, 16, 9, nan=False)
    means = sa.block_means(jnp.asarray(rows_k), SPEC.stride)
    ids, count = sa.select(jnp.asarray(q), means,
                           jnp.asarray(pos)[:, None], SPEC, 12)
    sparse = (4, 2, 8, 4, 1, 8, 32)
    for i, t in enumerate(pos):
        for n in range(2):
            m = np.asarray(means[i, :, n])
            kbar = jnp.asarray(0.5 * (m[:-1] + m[1:]))
            score = ref.block_scores(
                jnp.asarray(q[i, 0, 2 * n:2 * n + 2])[:, None], kbar,
                jnp.asarray([t]), sparse, 12)
            want = np.flatnonzero(np.asarray(
                ref.choose(score, jnp.asarray([t]), sparse))[0])
            assert list(ids[i, 0, n, :count[i, 0, n]]) == list(want)


def test_a_chunks_rows_each_select_for_themselves():
    """`chunk_attention` over a dense cache == the oracle row by row,
    with rows on both sides of dense_len in one chunk."""
    q1, rows_k, rows_v, *_ = _paged_case([40], 16, 3, nan=False)
    rng = np.random.default_rng(4)
    w, pos0 = 16, 24                    # rows 24..39: dense up to 31
    q = rng.standard_normal((1, w, 4, 16)).astype(np.float32)
    qpos = pos0 + np.arange(w)
    o, ids, count = sa.chunk_attention(
        jnp.asarray(q), jnp.asarray(rows_k), jnp.asarray(rows_v),
        jnp.asarray(qpos), SPEC, rows_a_block=32)
    for i, t in enumerate(qpos):
        want = _dense_oracle(q[:, i:i + 1], rows_k, rows_v, [t],
                             np.asarray(ids[:, i]), np.asarray(count[:, i]))
        np.testing.assert_allclose(o[:, i:i + 1], want, atol=2e-5, rtol=1e-5)
    assert int(count[0, 7, 0]) == 4 and int(count[0, 8, 0]) == 4


def test_the_walk_kernel_refuses_what_it_cannot_copy():
    q, _, _, kp, vp, _, table, p = _paged_case([40], 16, 1, nan=False)
    with pytest.raises(NotImplementedError, match="hpx_paged_sparse"):
        sa.sparse_walk(jnp.asarray(q[:, 0]).reshape(1, 2, 2, 16), kp, vp,
                       table[:, None, :4].repeat(2, 1),
                       jnp.ones((1, 2), jnp.int32), p, kernel="pallas")
    with pytest.raises(NotImplementedError, match="2 x stride"):
        sa.SparseSpec(kernel=32, stride=8)


# -- program == reference ----------------------------------------------------

@pytest.mark.parametrize("which,kernel", [("toy", "gather"),
                                          ("toy128", "fused")])
def test_prefill_then_paged_decode_logits_equal_the_reference(
        request, which, kernel):
    conf, cfg, params = request.getfixturevalue(which)
    plen, steps = 45, 24                # crosses dense_len 32 in chunk 2
    prompt = _prompt(plen)
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                           prefill_chunk=CHUNK, paged_kernel=kernel)
    assert srv.block_size == 8 and srv._block_size_src == "model"
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


@pytest.mark.parametrize("leave_out", [
    "decay", "qk_norm", "sparse_gate", "lightning_gate", "out_norm",
    "sparse_rope", "lightning_rope", "init_block", "local", "topk_half",
    "scale_emb", "scale_depth", "scale_logit"])
def test_a_reference_with_a_piece_left_out_disagrees(toy, leave_out):
    conf, cfg, params = toy
    toks = np.zeros((1, 128), np.int32)
    toks[0, :100] = _prompt(100, 9)
    whole = np.asarray(ref.logits(params, conf, toks))[0, :100]
    cut = np.asarray(ref.logits(params, conf, toks,
                                leave_out=(leave_out,)))[0, :100]
    assert np.abs(whole - cut).max() > 50 * TOL


def test_state_and_selection_of_a_live_slot_equal_the_references(toy):
    conf, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                           prefill_chunk=CHUNK)
    srv.submit(_prompt(50, 2), max_new=30)
    srv.submit(_prompt(9, 3), max_new=30)
    for _ in range(18):
        srv.step()
    with pytest.raises(ValueError, match="flush"):
        srv.sparse_selection(0)
    srv.flush()
    states = [srv.recurrent_state(s) for s in (0, 1)]
    picks = [srv.sparse_selection(s) for s in (0, 1)]
    assert states[0][1].shape == (4, 16, 16)
    assert [len(t) for t, _ in states] == [len(t) for t, _, _ in picks]
    assert ref.state_errors(params, conf, states).max() < 1e-5
    sel = ref.selection_numbers(params, conf, picks)
    assert sel == {"selection_missed": 0.0, "selection_score_gap": 0.0}
    # the three controls read otherwise, each by the number it is held by
    assert ref.state_errors(params, conf, states,
                            quant="state_bf16").min() > 1e-3
    assert ref.selection_numbers(params, conf, picks, quant="window_only")[
        "selection_missed"] >= 0.25
    # a choice of blocks made for no reason lies far below the last score
    t, ids, count = picks[0]
    wrong = ids.copy()
    wrong[:, 1] = [c for c in range(1, 5) if c not in ids[0]][0]
    bad = ref.selection_numbers(params, conf, [(t, wrong, count)])
    assert bad["selection_missed"] > 0
    assert bad["selection_score_gap"] > 0.02        # found: 0.48


# -- the server: slots, resets, restores ------------------------------------

@pytest.mark.parametrize("site", ["decode", "prefill"])
def test_a_faulted_step_gives_the_fault_free_tokens(toy, site):
    """No snapshot of a linear state is kept: a restore re-prefills
    prompt ++ the tokens the host holds (index and state recomputed)
    and goes on from there."""
    _, cfg, params = toy
    reqs = [(_prompt(44, 4), 30), (_prompt(6, 5), 40), (_prompt(22, 6), 12)]

    def serve(fi=None):
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                               prefill_chunk=CHUNK)
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


def test_counters_and_spans(toy):
    _, cfg, params = toy
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.trace.enabled", "1")
    tr = tracing.start_if_configured()
    try:
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                               prefill_chunk=CHUNK)
        rid = srv.submit(_prompt(40), max_new=10)
        srv.run()
        events = [(e[1], e[7] or {}) for e in tr.snapshot() if e[0] == "B"]
    finally:
        tracing.stop_tracing()
        rc.set("hpx.trace.enabled", "0")
    names = {n for n, _ in events}
    # the host builds nothing of a selection: no span of its own
    assert "serving.sparse.select" not in names
    assert {"serving.step", "serving.prefill_chunk", "serving.dispatch",
            "serving.state_reset"} <= names
    assert [a for n, a in events if n == "serving.state_reset"] == [
        {"rid": rid, "slot": 0, "layers": 2}]
    inst = srv.counter_instance

    def counter(obj, name):
        return pc.query_counter(pc.counter_name(obj, name, inst)).value
    st = srv.cache_stats()
    # 2 lightning layers x 4 heads x 16 x 16 float32, 2 slots
    assert st["state_bytes"] == counter("cache", "state/bytes") == 16384
    assert counter("cache", "index/rows") == st["index_rows"] == 4
    assert counter("serving", "state/prefix-refused") == 1
    # 9 decode steps at positions 40..48: 4 blocks of 8 a query
    assert st["sparse_steps"] == 9
    assert counter("serving", "sparse/blocks-selected") == 36
    walked = sum(3 * 8 + p % 8 + 1 for p in range(40, 49))
    assert counter("serving", "sparse/rows-walked") == walked
    assert counter("serving", "sparse/rows-live") == sum(range(41, 50))


# -- what cannot run such a model says so, by mechanism and module ----------

def _refusals(cfg, params):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    paged = dict(paged=True, slots=2, smax=64)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    srv = lambda **kw: ContinuousServer(params, cfg, **{**paged, **kw})  # noqa
    return {
        "mesh": (r"a \(dp, tp\) mesh.*index pool", lambda: srv(mesh=mesh)),
        "dense": (r"dense server mode is gone.*generate\(\)",
                  lambda: srv(paged=False)),
        "spec": (r"speculative verify.*index entry.*rolled back",
                 lambda: srv(spec=True)),
        "quantized": (r"sparse layer's quantized page",
                      lambda: srv(kv_dtype="int8")),
        "block_size": (r"block_size 16 on a model with sparse layers",
                       lambda: srv(block_size=16)),
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
        "prefill_worker": (
            r"PrefillWorker \(models/disagg.py\).*K/V pairs",
            lambda: __import__("hpx_tpu.models.disagg", fromlist=["x"])
            .PrefillWorker(params, cfg, smax=64, block_size=8)),
    }


@pytest.mark.parametrize("case", [
    "mesh", "dense", "spec", "quantized", "block_size", "admit_prefilled",
    "export_prefix_rows", "generate", "beam_search",
    "speculative_generate", "train", "pipeline", "prefill_worker"])
def test_bodies_without_a_path_refuse_by_mechanism_and_module(toy, case):
    _, cfg, params = toy
    match, call = _refusals(cfg, params)[case]
    # the one value `paged` has left is refused by name, not by mixer
    with pytest.raises(ValueError if case == "dense"
                       else NotImplementedError, match=match):
        call()


@pytest.mark.parametrize("field", ["qk_norm", "emb_scale", "residual_scale",
                                   "logit_scale"])
def test_a_scale_or_norm_no_body_computes_is_refused_there(field):
    """The three scales and the q/k norm on a model of plain attention
    layers: the bodies that take `_layer`, `_embed` and `_logits`
    compute them; the training bodies say they do not."""
    import dataclasses
    cfg = dataclasses.replace(
        tfm.TransformerConfig(),
        **{field: True if field == "qk_norm" else 0.5})
    with pytest.raises(NotImplementedError, match=field):
        tfm.make_train_step(cfg, tfm.make_mesh_3d(1))


def test_the_three_scales_reach_a_model_of_plain_attention_layers():
    """`emb_scale`, `residual_scale` and `logit_scale` through
    `generate()`'s body: logits move as the equations say."""
    import dataclasses
    base = tfm.TransformerConfig(norm="rmsnorm", mlp="swiglu")
    params = tfm.init_params(base, jax.random.PRNGKey(0))
    toks = jnp.asarray([_prompt(12, 1)]) % base.vocab

    def logits(cfg):
        caches = [(jnp.zeros((1, 16, cfg.kv_heads, cfg.head_dim)),) * 2
                  for _ in range(cfg.n_layers)]
        return tfm._decode_window(params, caches, toks, 0, cfg)[1]
    plain = logits(base)
    np.testing.assert_allclose(
        logits(dataclasses.replace(base, logit_scale=0.25)), 0.25 * plain,
        atol=1e-5)
    # RMSNorm forgets a scale of the embedding only where no branch adds
    assert np.abs(logits(dataclasses.replace(base, emb_scale=12.0))
                  - plain).max() > 0.01
    assert np.abs(logits(dataclasses.replace(base, residual_scale=0.25))
                  - plain).max() > 0.01


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


def test_prefix_reuse_is_refused_and_counted(toy):
    _, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                           prefill_chunk=CHUNK)
    p = _prompt(40, 8)
    a = srv.submit(p, max_new=4)
    out = srv.run()
    b = srv.submit(p, max_new=4)
    assert srv.run()[b] == out[a]
    st = srv.cache_stats()
    assert st["state_prefix_refused"] == st["sparse_prefix_refused"] == 2
    assert st["prefill_tokens_saved"] == 0
