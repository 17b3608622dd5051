"""A state-space hybrid through the one layer definition and the paged
server, against the plain reference of chipbench/reference/jamba.py:
Mamba-1 selective-scan layers (a per-slot float32 state [d_state,
d_inner] behind a short convolution WITH a bias, an RMSNorm on each of
dt, B and C, a skip D u and a SiLU gate) beside softmax attention over
ONE K/V head without rotation, a tied head, and a LAST layer that is
recurrent. A 4-layer toy of AI21-Jamba2-3B's shape at sizes a CPU holds
(d_state 16, d_inner 128 = one 128-lane row, so the two Pallas kernels
are taken in interpret mode), seeded random weights made by the
benchmark's own driver (chipbench/drivers/serving_ssm.py), float32.

Tolerances, and why. Program and reference are both float32 on the CPU
and compute the SAME recurrence in the same order (there is no
chunkwise form to differ by); they differ in the order of the sums of
their matmuls and of the softmax (an online walk over pages against a
full masked matrix). Logits of order 1 then agree to 5e-4 absolute
(`TOL`; found: under 2e-5); the recurrence's forms among themselves to
1e-5 (`SCAN_TOL`; found: 0 to 2e-6). A piece of the mathematics left
out moves logits by hundredths to tenths: each such case is held to 20
x `TOL`.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.drivers import serving_ssm as drv
from chipbench.reference import jamba as ref
from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import kda
from hpx_tpu.ops import mamba as mb
from hpx_tpu.svc import performance_counters as pc
from hpx_tpu.svc import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, SCAN_TOL = 5e-4, 1e-5
CHUNK = 16


def _conf(**over):
    with open(os.path.join(ROOT, "chipbench/configs/jamba2-3b.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT,
                           "chipbench/tests/rehearse_ssm.json")) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    return harness._merge(conf, over)


@pytest.fixture(scope="module")
def toy():
    conf = _conf()
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 11)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, 256, n)]


def _ref_logits(conf, params, seq, frame=128):
    toks = np.zeros((1, frame), np.int32)
    toks[0, :len(seq)] = seq
    return np.asarray(ref.logits(params, conf, toks))[0, :len(seq)]


def test_the_toy_has_every_mechanism(toy):
    conf, cfg, params = toy
    assert cfg.layer_mixer == ("mamba", "attn", "mamba", "mamba")
    assert cfg.recurrent and cfg.mixer(cfg.n_layers - 1) == "mamba"
    assert (cfg.kv_heads, cfg.n_heads, cfg.head_dim) == (1, 4, 16)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
            cfg.mamba_dt_rank, cfg.mamba_conv_bias) == (128, 16, 4, 8, True)
    assert cfg.tied and not cfg.rope and "head" not in params
    assert set(params["layers"][0]["mamba"]) == {
        "win", "conv", "conv_b", "wx", "dt_norm", "b_norm", "c_norm",
        "wdt", "dt_bias", "A_log", "D", "wo"}
    assert params["layers"][0]["mamba"]["A_log"].shape == (16, 128)
    assert set(params["layers"][1]) >= {"wq", "wkv", "wo"}
    # the program's own initialisation builds the same leaves
    mine = tfm.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), params)


def test_the_cells_configuration_is_the_published_one_uncut():
    with open(os.path.join(ROOT, "chipbench/configs/jamba2-3b.json")) as f:
        conf = json.load(f)
    cfg = drv.build_cfg(conf)
    assert conf["reduced"] == []
    assert [i for i, k in enumerate(cfg.layer_mixer) if k == "attn"] == \
        [7, 21] and cfg.layer_mixer.count("mamba") == 26
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab, cfg.n_layers) == (2560, 20, 1, 128, 8192, 65536, 28)
    assert (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank) == \
        (5120, 16, 160) and cfg.tied
    shapes = jax.eval_shape(lambda: drv.make_params(cfg, 1))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 3029.3e6) < 0.1e6          # 6.06 GB in bfloat16
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(ln) for ln in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["name"] == "AI21-Jamba2-3B":
            assert conf["source"] == row["source_url"]
            assert all(conf[k] == v for k, v in row["config"].items())


# -- the recurrence's forms -------------------------------------------------

def _scan_inputs(b, t, c, seed, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (b, t, c), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 2.0)
    bm = jax.random.normal(ks[2], (b, t, n), jnp.float32)
    cm = jax.random.normal(ks[3], (b, t, n), jnp.float32)
    a = -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(
        1, n + 1, dtype=jnp.float32))[:, None], (n, c)))
    return u, dt, bm, cm, a, jax.random.normal(ks[4], (b, n, c), jnp.float32)


W = 16


@pytest.mark.parametrize("width,valid", [
    (1, None), (W - 1, None), (W, None), (W + 1, None), (2 * W + 3, None),
    (W, 5), (300, 131), (W, 0)],
    ids=["1", "W-1", "W", "W+1", "2W+3", "padded", "two-row-blocks",
         "all-padding"])
def test_scan_chunk_kernel_and_chained_steps_agree(width, valid):
    """`mamba_scan` = `mamba_chunk` = `hpx_mamba_scan` in interpret mode
    = chained `mamba_step`s (XLA and `hpx_mamba_step`), outputs of the
    real rows and the state; padding rows leave the state alone."""
    u, dt, bm, cm, a, s0 = _scan_inputs(2, width, 256, width)
    v = None if valid is None else jnp.int32(valid)
    real = width if valid is None else valid
    want, s_want = mb.mamba_scan(u, dt, bm, cm, a, s0, v)
    for kernel in ("xla", "pallas"):
        got, s_got = mb.mamba_chunk(u, dt, bm, cm, a, s0, v, kernel=kernel)
        np.testing.assert_allclose(got[:, :real], want[:, :real],
                                   atol=SCAN_TOL, rtol=1e-5)
        np.testing.assert_allclose(s_got, s_want, atol=SCAN_TOL, rtol=1e-5)
        step = jax.jit(lambda *x, k=kernel: mb.mamba_step(*x, kernel=k))
        outs, s = [], s0
        for i in range(min(real, 20)):
            o, s = step(u[:, i], dt[:, i], bm[:, i], cm[:, i], a, s)
            outs.append(o)
        if outs:
            np.testing.assert_allclose(
                jnp.stack(outs, 1), want[:, :len(outs)], atol=SCAN_TOL,
                rtol=1e-5)
        if real <= 20:
            np.testing.assert_allclose(s, s_want, atol=SCAN_TOL, rtol=1e-5)


def test_the_step_kernel_takes_eight_slots_a_grid_step_or_one():
    for b in (3, 16):
        u, dt, bm, cm, a, s0 = _scan_inputs(b, 1, 384, b)
        want, s_want = mb.mamba_step(u[:, 0], dt[:, 0], bm[:, 0], cm[:, 0],
                                     a, s0, kernel="xla")
        got, s_got = mb.mamba_step(u[:, 0], dt[:, 0], bm[:, 0], cm[:, 0],
                                   a, s0, kernel="pallas")
        np.testing.assert_allclose(got, want, atol=SCAN_TOL, rtol=1e-5)
        np.testing.assert_allclose(s_got, s_want, atol=SCAN_TOL, rtol=1e-5)


@pytest.mark.parametrize("form", ["step", "chunk"])
def test_the_kernels_refuse_channels_that_are_no_whole_lane_rows(form):
    u, dt, bm, cm, a, s0 = _scan_inputs(1, 8, 96, 1)
    with pytest.raises(NotImplementedError, match={"step": "hpx_mamba_step", "chunk": "hpx_mamba_scan"}[form]):
        if form == "step":
            mb.mamba_step(u[:, 0], dt[:, 0], bm[:, 0], cm[:, 0], a, s0,
                          kernel="pallas")
        else:
            mb.mamba_chunk(u, dt, bm, cm, a, s0, kernel="pallas")
    # and take the XLA form by themselves
    mb.mamba_chunk(u, dt, bm, cm, a, s0)


@pytest.mark.parametrize("bias", [False, True])
def test_short_conv_with_and_without_a_bias(bias):
    """`kda.short_conv` gained an optional bias: without one it is the
    function it was; `_conv` over a FLAT tail is the same convolution."""
    rng = np.random.default_rng(3)
    pre = jnp.asarray(rng.standard_normal((2, 9, 128)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((2, 3, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 128)), jnp.float32)
    b = jnp.asarray(rng.standard_normal(128), jnp.float32) if bias else None
    out, new = kda.short_conv(pre, tail, w, None, b)
    full = np.concatenate([tail, pre], 1)
    want = sum(full[:, j:j + 9] * np.asarray(w)[j] for j in range(4)) \
        + (np.asarray(b) if bias else 0.0)
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_array_equal(new, full[:, -3:])
    flat, flat_new = mb._conv(pre, tail.reshape(2, -1), w, b, None)
    np.testing.assert_allclose(flat, out, atol=1e-6)
    np.testing.assert_array_equal(flat_new.reshape(2, 3, 128), new)
    # one row a slot: the tail's rows as column blocks
    one, one_new = mb._conv(pre[:, :1], tail.reshape(2, -1), w, b, None)
    np.testing.assert_allclose(one, out[:, :1], atol=1e-5)
    np.testing.assert_array_equal(one_new.reshape(2, 3, 128),
                                  full[:, 1:4])
    # padding rows stay out of the tail
    _, cut = kda.short_conv(pre, tail, w, jnp.int32(4), b)
    np.testing.assert_array_equal(cut, full[:, 4:7])


# -- prefill then decode through the paged cache ----------------------------

@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_prefill_then_paged_decode_logits_equal_the_reference(
        toy, kernel, monkeypatch):
    """LOGITS, not tokens: every prompt position's from the chunk
    windows over the b=1 scratch, then every decode step's from the
    step program's forward over the paged pools and the per-slot state,
    against the reference's full forward of the same tokens. With
    `kernel` = pallas both Mamba kernels run (interpret mode)."""
    conf, cfg, params = toy
    if kernel == "pallas":
        for name in ("mamba_step", "mamba_chunk"):
            monkeypatch.setattr(mb, name, _forced(getattr(mb, name)))
    plen, steps = 45, 20
    prompt = _prompt(plen)
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                           prefill_chunk=CHUNK)
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


def _forced(fn):
    def call(*args, **kw):
        return fn(*args, **{**kw, "kernel": "pallas"})
    return call


@pytest.mark.parametrize("leave_out", [
    "conv_bias", "conv", "D", "dt_norm", "b_norm", "c_norm", "dt_bias",
    "softplus", "gate"])
def test_a_reference_with_a_piece_left_out_disagrees(toy, leave_out):
    conf, cfg, params = toy
    toks = np.zeros((1, 128), np.int32)
    toks[0, :100] = _prompt(100, 9)
    whole = np.asarray(ref.logits(params, conf, toks))[0, :100]
    cut = np.asarray(ref.logits(params, conf, toks,
                                leave_out=(leave_out,)))[0, :100]
    assert not np.abs(whole - cut).max() <= 20 * TOL


@pytest.mark.parametrize("plen", [1, CHUNK - 1, CHUNK, CHUNK + 1,
                                  2 * CHUNK + 3])
def test_the_state_after_the_splice_is_a_one_shot_prefills(toy, plen):
    """The slot's state and tail once the admission's chunks (bucketed,
    tail-padded) are spliced == the reference's state of the prompt,
    and the seed token is the reference's."""
    conf, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                           prefill_chunk=CHUNK)
    prompt = _prompt(plen, plen)
    srv.submit(prompt, max_new=8)
    while srv._slot_req[0] is None:
        srv.step()
    srv.flush()
    toks, state = srv.recurrent_state(0)
    assert state.shape == (128, 16)             # the published [C, N]
    assert toks[:plen] == prompt
    assert ref.state_errors(params, conf, [(toks, state)]).max() < 1e-5
    want = _ref_logits(conf, params, toks + [0])
    assert srv._slot_req[0].tokens[0] == int(want[plen - 1].argmax())


def test_served_tokens_and_states_pass_and_both_controls_fail(toy):
    """The comparison that decides `correct`, as the driver makes it:
    the served tokens' gaps and a live slot's state against the float32
    reference pass the toy's limits; the int8 control fails by
    `gap_mean`, the bfloat16-state control by `state_rel_err`."""
    conf, cfg, params = toy
    srv = ContinuousServer(params, cfg, paged=True, slots=3, smax=96,
                           prefill_chunk=CHUNK)
    reqs = [(_prompt(50, 2), 30), (_prompt(9, 3), 40), (_prompt(33, 4), 12)]
    rids = [srv.submit(p, max_new=m) for p, m in reqs]
    for _ in range(20):
        srv.step()
    with pytest.raises(ValueError, match="flush"):
        srv.recurrent_state(0)
    srv.flush()
    states = [srv.recurrent_state(s) for s in sorted(srv.live_positions())]
    out = srv.run()
    served = [(p, out[r]) for (p, _), r in zip(reqs, rids)]
    limits = conf["correct"]["limits"]
    gaps = ref.served_gaps(params, conf, served, 96, 40)
    assert gaps.mean() <= limits["gap_mean"]
    assert ref.state_errors(params, conf, states).max() \
        <= limits["state_rel_err"]
    q8 = ref.served_gaps(params, conf, served, 96, 40, quant="int8")
    assert q8.mean() > limits["gap_mean"]
    assert ref.state_errors(params, conf, states,
                            quant="state_bf16").min() \
        > limits["state_rel_err"]


# -- the server: counters, refusals -----------------------------------------

def test_counters_and_spans(toy):
    _, cfg, params = toy
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.trace.enabled", "1")
    tr = tracing.start_if_configured()
    try:
        srv = ContinuousServer(params, cfg, paged=True, slots=2, smax=96,
                               prefill_chunk=CHUNK)
        rids = [srv.submit(_prompt(n, n), max_new=6) for n in (40, 5, 12)]
        out = srv.run()
        events = [(e[1], e[7] or {}) for e in tr.snapshot() if e[0] == "B"]
    finally:
        tracing.stop_tracing()
        rc.set("hpx.trace.enabled", "0")
    assert sorted(out) == sorted(rids)
    names = {n for n, _ in events}
    assert {"serving.step", "serving.prefill_chunk", "serving.dispatch",
            "serving.state_reset", "serving.prefill_tick"} <= names
    assert [a["layers"] for n, a in events
            if n == "serving.state_reset"] == [3, 3, 3]
    # the queue's depth a step, in a trace: the tick's own argument
    assert {a["pending"] for n, a in events
            if n == "serving.prefill_tick"} == {1}
    inst = srv.counter_instance

    def counter(obj, name):
        return pc.query_counter(pc.counter_name(obj, name, inst)).value
    st = srv.cache_stats()
    # 3 Mamba layers x (16 x 128 float32 state + 3 x 128 float32 tail
    # rows) x 2 slots
    assert st["state_bytes"] == counter("cache", "state/bytes") == \
        3 * (16 * 128 + 3 * 128) * 4 * 2
    assert st["state_resets"] == counter("cache", "state/resets") == 3
    assert counter("serving", "state/prefix-refused") == 3
    assert st["state_reprefills"] == 0
    assert st["prefill_pending"] == counter("serving",
                                            "prefill/pending") == 0
    assert st["prefill_chunks"] >= 5
    # the 40-token prompt (three chunks) waited for its chunks, a step
    # each; the two short ones prefilled inline
    assert st["admit_wait_steps"] == counter(
        "serving", "prefill/admit-wait-steps") == 2


def _refusals(cfg, params):
    import dataclasses
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    return {
        "mesh": ("mamba", lambda: ContinuousServer(
            params, cfg, paged=True, slots=4, smax=64, mesh=mesh)),
        "dense": (r"dense server mode is gone.*generate\(\)",
                  lambda: ContinuousServer(params, cfg, paged=False,
                                           slots=2, smax=64)),
        "spec": ("mamba", lambda: ContinuousServer(
            params, cfg, paged=True, slots=2, smax=64, spec=True)),
        "generate": ("layer_mixer", lambda: tfm.generate(
            params, cfg, jnp.asarray([[1, 2, 3]]), 4)),
        "train": ("layer_mixer", lambda: tfm.make_train_step(
            dataclasses.replace(tfm.TransformerConfig(),
                                layer_mixer=("mamba", "mamba")),
            tfm.make_mesh_3d(1))),
    }


@pytest.mark.parametrize("case", ["mesh", "dense", "spec", "generate",
                                  "train"])
def test_bodies_without_a_path_refuse_by_the_kind(toy, case):
    _, cfg, params = toy
    match, call = _refusals(cfg, params)[case]
    # the one value `paged` has left is refused by name, not by mixer
    with pytest.raises(ValueError if case == "dense"
                       else NotImplementedError, match=match):
        call()


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
    assert st["state_prefix_refused"] == 2
    assert st["prefill_tokens_saved"] == 0
