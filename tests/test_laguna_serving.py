"""A model whose layers DIFFER, through the one layer definition and the
paged server, against the plain reference of chipbench/reference/
laguna.py: full and window attention with their own head counts and
RoPEs (YaRN on half the head / plain), a per-head gate, RMSNorm, a
dense SiLU-gated layer 0 and sparse layers of many small experts plus a
shared one, an untied head. A 5-layer toy of Laguna-XS.2's shape at
sizes a CPU holds, seeded random weights made by the benchmark's own
driver (chipbench/drivers/serving_mixed.py), float32.

Tolerance of the logit comparisons: 2e-4 absolute on logits of order 1.
Program and reference are both float32 on the CPU and differ in the
order of their sums (a grouped product against a loop over experts, a
paged gather against a full matrix); a dropped gate, scale, shared
expert or a window off by one row moves logits by tenths.
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
from chipbench.drivers import serving_mixed as drv
from chipbench.reference import laguna as ref
from hpx_tpu.models import moe, serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.svc import faultinject

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-4
WINDOW, CHUNK = 16, 8


def _conf():
    with open(os.path.join(ROOT, "chipbench/configs/laguna-xs2.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT, "chipbench/tests/rehearse_mixed.json")) as f:
        return harness._merge(conf, json.load(f)["config"])


@pytest.fixture(scope="module")
def toy():
    conf = _conf()
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 11)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


def _ref_logits(conf, params, seq):
    x = ref.forward(params, conf, np.asarray([seq], np.int32))
    with jax.default_matmul_precision("highest"):
        x = ref._rms(x, params["ln_f"], float(conf["rms_norm_eps"]))
        return np.asarray(x[0] @ params["head"].astype(jnp.float32).T)


def _generate(params, cfg, prompt, max_new):
    out = tfm.generate(params, cfg, jnp.asarray([prompt], jnp.int32),
                       max_new=max_new)
    return [int(t) for t in np.asarray(out)[0]]


def test_the_toy_has_every_mechanism(toy):
    conf, cfg, params = toy
    assert [cfg.window(i) for i in range(5)] == [0, 16, 16, 16, 0]
    assert [cfg.heads(i) for i in range(5)] == [4, 8, 8, 8, 4]
    assert [cfg.sparse(i) for i in range(5)] == [False] + [True] * 4
    full, slide = cfg.rope_of(0), cfg.rope_of(1)
    assert full.factor == 64 and full.rotary_dim == 8 and \
        full.attention_factor > 1.4
    assert slide.factor == 1.0 and slide.rotary_dim == 0
    assert "head" in params and "wgate" in params["layers"][0]
    assert "shared" in params["layers"][1]["moe"]
    # YaRN's inverse frequencies: the program's and the reference's,
    # written apart, agree
    np.testing.assert_allclose(
        np.asarray(full.inv_freq(8)),
        ref.inv_freq(conf["rope_parameters"]["full_attention"], 16),
        rtol=1e-6)


# -- (a) chunked prefill, then paged decode past the window ---------------

@pytest.mark.parametrize("kernel", ["gather", "fused", "fused_online"])
def test_prefill_then_paged_decode_logits_equal_the_reference(toy, kernel):
    conf, cfg, params = toy
    plen, steps = WINDOW + CHUNK + 17, 30       # 41 > window + chunk
    prompt = _prompt(plen)
    # prefill, chunk by chunk over the dense scratch, with logits
    caches = [tuple(jnp.zeros((1, 128, cfg.kv_heads, cfg.head_dim))
                    for _ in "kv") for _ in range(cfg.n_layers)]
    got = []
    for s in range(0, plen, CHUNK):
        caches, lg = tfm._decode_window(
            params, caches, jnp.asarray([prompt[s:s + CHUNK]]), s, cfg)
        got.append(np.asarray(lg[0]))
    # the same prompt through the server's admission, then its decode
    # program's forward, one step at a time, past the window
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=128,
                           prefill_chunk=CHUNK, paged_kernel=kernel)
    srv.submit(prompt, max_new=steps + 1)
    while srv._slot_req[0] is None:
        srv._admit()
        srv._prefill_tick()
    srv.flush()             # the seed token is a device value until read
    seq = prompt + [srv._cur[0]]
    for _ in range(steps):
        pos = srv._pos[0]
        srv._ensure_block(0, pos)
        srv._pools, _, lg, ms = serving._paged_decode_rows(
            srv.params, srv._pools, None,
            jnp.asarray([seq[-1], 0], jnp.int32), srv._tables_dev(),
            jnp.asarray([pos, 0], jnp.int32), cfg, srv._paged_fused)
        got.append(np.asarray(lg[:1]))
        srv._pos[0] += 1
        seq.append(int(np.argmax(got[-1][0])))
    assert srv._pos[0] > 3 * WINDOW and ms[1] == 0
    want = _ref_logits(conf, params, seq[:-1])
    got = np.concatenate(got)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    # and greedy decoding agrees token for token
    assert seq[plen:] == [int(t) for t in want[plen - 1:].argmax(-1)]


@pytest.mark.parametrize("leave_out", ["gate", "scale", "window_edge",
                                       "shared"])
def test_a_reference_with_a_piece_left_out_disagrees(toy, leave_out):
    conf, cfg, params = toy
    prompt = _prompt(40, 3)
    served = _generate(params, cfg, prompt, 24)
    sound = ref.served_gaps(params, conf, [(prompt, served)], 64, 24)
    broken = ref.served_gaps(params, conf, [(prompt, served)], 64, 24,
                             leave_out=(leave_out,))
    assert sound.max() < 1e-3 < 0.1 < broken.max()


# -- (b) the window block group ----------------------------------------

def test_window_blocks_are_freed_and_reused_while_the_owner_decodes(toy):
    _, cfg, params = toy
    long_p, short_p = _prompt(20, 1), _prompt(9, 2)
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=128,
                           block_size=4, prefill_chunk=CHUNK)
    assert srv._ring == WINDOW // 4 + 2
    a = srv.submit(long_p, max_new=70)
    ever, reused, b = set(), set(), None
    while srv.step():
        wt = srv._wtables[0]
        if wt is not None:
            assert len(wt.blocks) <= srv._ring - 1
            ever |= set(wt.blocks)
        if b is None and srv.cache_stats()["window_blocks_freed"] >= 6:
            b = srv.submit(short_p, max_new=30)    # while `a` decodes
        if wt is not None and srv._wtables[1] is not None:
            reused |= (ever - set(wt.blocks)) & set(srv._wtables[1].blocks)
    assert reused, "no freed window block went to the second request"
    out = srv.poll_finished()
    assert out[a] == _generate(params, cfg, long_p, 70)
    assert out[b] == _generate(params, cfg, short_p, 30)
    st = srv.cache_stats()
    assert st["window_in_use"] == 1 and st["in_use"] == 1   # trash blocks
    assert st["window_blocks_freed"] > 0
    assert st["window_prefix_refused"] == 2 and st["hit_rate"] == 0.0


def test_window_pools_are_sized_for_the_ring_not_the_context(toy):
    _, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=4, smax=256)
    full, win = srv._pools[0][0].shape[0], srv._pools[1][0].shape[0]
    assert full == 2 * 4 * (256 // 16) + 1
    assert win == srv._walloc.num_blocks == 4 * (srv._ring + 4) + 1 < full
    assert [p[0].shape[0] for p in srv._pools] == [full, win, win, win,
                                                   full]


@contextlib.contextmanager
def _inject(**kw):
    faultinject.install(faultinject.FaultInjector(**kw))
    try:
        yield
    finally:
        faultinject.uninstall()


@pytest.mark.parametrize("site", ["decode", "prefill"])
def test_a_faulted_step_restores_both_block_groups(toy, site):
    _, cfg, params = toy
    reqs = [(_prompt(30, 4), 40), (_prompt(6, 5), 50), (_prompt(22, 6), 12)]

    def serve(fi=None):
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=128,
                               block_size=4, prefill_chunk=CHUNK)
        rids = [srv.submit(p, max_new=m) for p, m in reqs]
        with (_inject(**fi) if fi else contextlib.nullcontext()):
            out = srv.run()
        return [out[r] for r in rids], srv
    base, _ = serve()
    got, srv = serve({"schedule": {site: {2, 9, 23}}})
    assert got == base
    assert srv.fault_stats()["restored_by_site"].get(site, 0) >= 1
    st = srv.cache_stats()
    assert st["window_in_use"] == 1 and st["in_use"] == 1


# -- (c), (d) the drop-free sparse FFN ------------------------------------

MCFG = moe.MoeConfig(n_experts=16, top_k=4, d_model=32, d_ff=24,
                     mlp="swiglu", router="sigmoid", renorm=True,
                     scale=2.5, shared_d_ff=24)


def _loop_over_experts(x, p, cfg):
    idx, w = (np.asarray(v) for v in moe.route(x, p["wg"], cfg))
    out = np.zeros(x.shape, np.float32)
    for e in range(cfg.n_experts):
        rows, choice = np.nonzero(idx == e)
        if rows.size:
            xe = x[rows]
            y = (jax.nn.silu(xe @ p["w1"][e]) * (xe @ p["w3"][e])) \
                @ p["w2"][e]
            out[rows] += np.asarray(y) * w[rows, choice][:, None]
    sp = p["shared"]
    return out + np.asarray(
        (jax.nn.silu(x @ sp["w1"]) * (x @ sp["w3"])) @ sp["w2"]), idx


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("tokens", [3, 40])
def test_sparse_ffn_equals_a_loop_over_experts_under_uneven_routing(
        kernel, tokens):
    p = moe.init_moe_params(MCFG, jax.random.PRNGKey(0))
    # one expert takes most tokens, four take none
    p["wg"] = p["wg"].at[:, 5].add(0.3).at[:, 12:].add(-0.3)
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, 32)) + 2.0
    want, idx = _loop_over_experts(x, p, MCFG)
    got, stats = moe.moe_ffn_serve(x, p, MCFG, kernel=kernel)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5, rtol=0)
    sizes = np.bincount(idx.reshape(-1), minlength=16)
    assert sizes[5] >= 0.9 * tokens and (sizes[12:] == 0).all()
    assert stats[0] == tokens * 4 and stats[1] == 0
    np.testing.assert_array_equal(np.asarray(stats[2:]), sizes > 0)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_the_shares_of_the_experts_add_up_to_the_whole_layer(kernel):
    """The share test: 256 experts in 8 disjoint shares of 32, the
    router at its full width in each, the shared expert counted once."""
    cfg = dataclasses.replace(MCFG, n_experts=256, top_k=8, d_ff=16)
    p = moe.init_moe_params(cfg, jax.random.PRNGKey(1))
    x = jax.random.normal(jax.random.PRNGKey(2), (32, 32))
    whole, _ = moe.moe_ffn_serve(x, p, cfg, kernel=kernel)
    np.testing.assert_allclose(np.asarray(whole),
                               _loop_over_experts(x, p, cfg)[0],
                               atol=1e-5, rtol=0)
    total = 0
    for i in range(8):
        lo, hi = 32 * i, 32 * i + 32
        share = {"wg": p["wg"], **{k: p[k][lo:hi]
                                   for k in ("w1", "w3", "w2")}}
        if i == 3:
            share["shared"] = p["shared"]
        out, stats = moe.moe_ffn_serve(x, share, cfg, kernel=kernel,
                                       held=(lo, hi))
        assert stats.shape == (2 + 32,)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=1e-5, rtol=0)


def test_moe_counters_of_a_drop_free_server(toy):
    _, cfg, params = toy
    from hpx_tpu.svc import performance_counters as pc
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=128)
    srv.submit(_prompt(12), max_new=10)
    srv.run()
    st = srv.moe_stats()
    # claims a step: 2 slots (dead ones too: static shapes) x top-4 x
    # the 4 sparse layers
    assert st["dropped"] == 0 and st["routed"] == st["steps"] * 2 * 4 * 4
    inst = srv.counter_instance

    def counter(obj, name):
        return pc.query_counter(pc.counter_name(obj, name, inst)).value
    assert counter("serving", "moe/tokens-dropped") == 0
    hit = counter("serving", "moe/experts-hit")
    # 2 slots x top-4: at most 8 distinct experts a step and layer
    assert hit == st["experts_hit_sum"] / st["steps"] and 4 <= hit <= 8
    assert counter("cache", "window/blocks-in-use") == 1
    assert counter("cache", "window/prefix-refused") == 1


# -- bodies that cannot compute such a model say so ----------------------

def test_bodies_without_a_path_refuse_by_mechanism_and_module(toy):
    _, cfg, params = toy
    from jax.sharding import Mesh
    with pytest.raises(NotImplementedError,
                       match=r"make_train_step.*models/transformer.py"):
        tfm.make_train_step(cfg, tfm.make_mesh_3d(1))
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "tp"))
    with pytest.raises(NotImplementedError, match="sharded decode"):
        ContinuousServer(params, cfg, paged=True, slots=2, smax=64,
                         mesh=mesh)
    with pytest.raises(NotImplementedError, match="speculative verify"):
        ContinuousServer(params, cfg, paged=True, slots=2, smax=64,
                         spec=True)
    with pytest.raises(NotImplementedError, match="quantized"):
        ContinuousServer(params, cfg, paged=True, slots=2, smax=64,
                         kv_dtype="int8")
    with pytest.raises(NotImplementedError, match="moe_ffn_serve"):
        moe.moe_ffn(jnp.zeros((4, 32)), {}, MCFG)
    two = dataclasses.replace(cfg, layer_window=(0, 16, 8, 16, 0))
    with pytest.raises(NotImplementedError, match="one window block"):
        ContinuousServer(params, two, paged=True, slots=2, smax=64)


def test_the_server_and_generate_compute_the_same_model(toy):
    """The dense body takes the same definition: generate() serves the
    toy token for token like the server, over the default block (the
    window is one block of 16 rows) and over blocks of 8 (it is two)."""
    _, cfg, params = toy
    prompts = [_prompt(26, 7), _prompt(5, 8)]
    outs = []
    for block in (None, 8):
        srv = ContinuousServer(params, cfg, block_size=block, slots=2,
                               smax=128, prefill_chunk=CHUNK)
        rids = [srv.submit(p, max_new=30) for p in prompts]
        out = srv.run()
        outs.append([out[r] for r in rids])
    assert outs[0] == outs[1] == [_generate(params, cfg, p, 30)
                                  for p in prompts]


def test_served_at_the_ceiling_width_gives_the_tokens_of_128(toy,
                                                             monkeypatch):
    """A model with window layers and sparse layers served with no stated chunk width on a
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
    # 700: 512 128 64; 513: 512 and 1 in the 8 bucket; 90: 128
    assert (at128._chunks, srv._chunks) == (6 + 5 + 1, 3 + 2 + 1)
