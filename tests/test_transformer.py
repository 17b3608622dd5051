"""Transformer model family tests: the dp×sp×tp-sharded training step
compiles, runs, agrees with a single-device replica, and learns.
"""

import dataclasses
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm


CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64, lr=0.05)


@pytest.fixture(scope="module")
def mesh3d():
    return tfm.make_mesh_3d(8)


def test_mesh_factoring():
    m = tfm.make_mesh_3d(8)
    assert dict(m.shape) == {"dp": 2, "sp": 2, "tp": 2}
    m4 = tfm.make_mesh_3d(4)
    assert m4.shape["sp"] * m4.shape["tp"] * m4.shape["dp"] == 4


def test_train_step_runs_and_learns(mesh3d):
    key = jax.random.PRNGKey(0)
    params = tfm.init_params(CFG, key)
    params = tfm.shard_params(params, CFG, mesh3d)
    step = tfm.make_train_step(CFG, mesh3d)

    # one fixed tiny batch -> loss must drop when memorizing it
    toks, tgts = tfm.sample_batch(CFG, batch=4, seq=32,
                                  key=jax.random.PRNGKey(1))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)

    losses = []
    for _ in range(10):
        params, loss = step(params, toks, tgts)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.8, losses
    assert np.isfinite(losses).all()


def test_matches_single_device(mesh3d):
    """The sharded step must compute the SAME loss and updates as an
    unsharded replica of the math."""
    key = jax.random.PRNGKey(2)
    params = tfm.init_params(CFG, key)
    toks, tgts = tfm.sample_batch(CFG, batch=4, seq=16,
                                  key=jax.random.PRNGKey(3))

    # single-device oracle: same math, mesh of 1x1x1
    mesh1 = tfm.make_mesh_3d(1)
    p1 = tfm.shard_params(jax.tree.map(jnp.copy, params), CFG, mesh1)
    step1 = tfm.make_train_step(CFG, mesh1)
    t1, g1 = tfm.shard_batch(toks, tgts, mesh1)
    p1, loss1 = step1(p1, t1, g1)

    p8 = tfm.shard_params(jax.tree.map(jnp.copy, params), CFG, mesh3d)
    step8 = tfm.make_train_step(CFG, mesh3d)
    t8, g8 = tfm.shard_batch(toks, tgts, mesh3d)
    p8, loss8 = step8(p8, t8, g8)

    np.testing.assert_allclose(float(loss1), float(loss8), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-5)


def test_optax_train_step(mesh3d):
    import optax
    opt = optax.adam(1e-2)
    params = tfm.shard_params(tfm.init_params(CFG, jax.random.PRNGKey(4)),
                              CFG, mesh3d)
    opt_state = tfm.make_opt_state(params, CFG, mesh3d, opt)
    step = tfm.make_train_step(CFG, mesh3d, optimizer=opt)
    toks, tgts = tfm.sample_batch(CFG, batch=4, seq=32,
                                  key=jax.random.PRNGKey(5))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)
    losses = []
    for _ in range(8):
        params, opt_state, loss = step(params, opt_state, toks, tgts)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses
    # adam moments follow the params' tp sharding
    mu_w1 = opt_state[0].mu["layers"][0]["w1"]
    shard_shapes = {s.data.shape for s in mu_w1.addressable_shards}
    assert shard_shapes == {(CFG.d_model, CFG.d_ff // 2)}


def test_generate_greedy_decode():
    params = tfm.init_params(CFG, jax.random.PRNGKey(6))
    prompt = jnp.array([[1, 2, 3], [4, 5, 6]], dtype=jnp.int32)
    out = tfm.generate(params, CFG, prompt, max_new=5)
    assert out.shape == (2, 5)
    assert ((out >= 0) & (out < CFG.vocab)).all()
    # deterministic
    out2 = tfm.generate(params, CFG, prompt, max_new=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_generate_consistent_with_forward():
    """The first generated token must equal the argmax of the full
    forward pass at the last prompt position (KV-cache correctness)."""
    params = tfm.init_params(CFG, jax.random.PRNGKey(7))
    prompt = jnp.array([[3, 1, 4, 1, 5, 9, 2, 6]], dtype=jnp.int32)
    out = tfm.generate(params, CFG, prompt, max_new=1)

    # full forward (mesh of 1): logits at the last position
    mesh1 = tfm.make_mesh_3d(1)
    sp = 1
    from hpx_tpu.models.transformer import _ln, _block
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def fwd(p, toks):
        x = p["emb"][toks]
        for lp in p["layers"]:
            x, _aux = _block(x, lp, CFG, sp, 1)
        x = _ln(x, p["ln_f"])
        return jnp.einsum("bsd,vd->bsv", x, p["emb"])

    p1 = tfm.shard_params(params, CFG, mesh1)
    logits = jax.jit(shard_map(
        fwd, mesh=mesh1,
        in_specs=(tfm.param_specs(CFG), P("dp", "sp")),
        out_specs=P("dp", "sp")))(p1, prompt)
    want = int(jnp.argmax(logits[0, -1]))
    assert int(out[0, 0]) == want


def test_generate_matches_full_forward_oracle():
    """Greedy decode must equal token-by-token decoding with the full
    (uncached) forward pass, on a TRAINED model whose argmax varies by
    position. An untrained model's argmax is effectively constant, which
    masked a round-1 off-by-one (generate() emitted the step's own
    prediction, dropping the first generated token)."""
    mesh1 = tfm.make_mesh_3d(1)
    params = tfm.shard_params(tfm.init_params(CFG, jax.random.PRNGKey(8)),
                              CFG, mesh1)
    step = tfm.make_train_step(CFG, mesh1)
    toks, tgts = tfm.sample_batch(CFG, batch=4, seq=16,
                                  key=jax.random.PRNGKey(9))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh1)
    for _ in range(30):
        params, _ = step(params, toks, tgts)

    prompt = jnp.array([[3, 1, 4, 1], [2, 7, 1, 8]], dtype=jnp.int32)
    max_new = 6
    out = tfm.generate(params, CFG, prompt, max_new=max_new)

    # oracle: grow the sequence one token at a time through the full
    # forward pass (same shard_map-on-mesh1 path the other tests use)
    from hpx_tpu.models.transformer import _ln, _block
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def fwd(p, toks):
        x = p["emb"][toks]
        for lp in p["layers"]:
            x, _aux = _block(x, lp, CFG, 1, 1)
        x = _ln(x, p["ln_f"])
        return jnp.einsum("bsd,vd->bsv", x, p["emb"])

    run = jax.jit(shard_map(
        fwd, mesh=mesh1,
        in_specs=(tfm.param_specs(CFG), P("dp", "sp")),
        out_specs=P("dp", "sp")))

    seq = prompt
    want = []
    for _ in range(max_new):
        logits = run(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        want.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    want = jnp.stack(want, axis=1)

    # the test is only meaningful if decode is non-constant
    flat = np.asarray(want).reshape(-1).tolist()
    assert len(set(flat)) > 1, f"oracle decode degenerate: {flat}"
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_params_actually_sharded(mesh3d):
    params = tfm.shard_params(tfm.init_params(CFG, jax.random.PRNGKey(0)),
                              CFG, mesh3d)
    w1 = params["layers"][0]["w1"]
    # tp axis of the mesh has 2 shards; w1's column dim is split
    assert len(w1.sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in w1.addressable_shards}
    assert shard_shapes == {(CFG.d_model, CFG.d_ff // 2)}


MOE_CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                head_dim=8, n_layers=2, d_ff=64, lr=0.05,
                                n_experts=4, moe_top_k=2,
                                moe_capacity=4.0)


def test_moe_train_step_runs_and_learns(mesh3d):
    """dp x sp x tp x EP: experts shard over the dp axis (GShard
    layout — tokens batch-sharded there exchange via all_to_all);
    the step must compile, run, and learn."""
    params = tfm.shard_params(tfm.init_params(MOE_CFG,
                                              jax.random.PRNGKey(11)),
                              MOE_CFG, mesh3d)
    # experts really are sharded 2-ways over dp
    w1 = params["layers"][0]["moe"]["w1"]
    shard_shapes = {s.data.shape for s in w1.addressable_shards}
    assert shard_shapes == {(MOE_CFG.n_experts // 2, MOE_CFG.d_model,
                             MOE_CFG.d_ff // 2)}   # dp- AND tp-sharded
    step = tfm.make_train_step(MOE_CFG, mesh3d)
    toks, tgts = tfm.sample_batch(MOE_CFG, batch=4, seq=32,
                                  key=jax.random.PRNGKey(12))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)
    losses = []
    for _ in range(10):
        params, loss = step(params, toks, tgts)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * 0.85, losses


def test_moe_sharded_matches_single_device(mesh3d):
    """The ep-sharded MoE step computes the same loss as mesh(1,1,1)."""
    params = tfm.init_params(MOE_CFG, jax.random.PRNGKey(13))
    toks, tgts = tfm.sample_batch(MOE_CFG, batch=4, seq=16,
                                  key=jax.random.PRNGKey(14))
    mesh1 = tfm.make_mesh_3d(1)
    p1 = tfm.shard_params(jax.tree.map(jnp.copy, params), MOE_CFG, mesh1)
    _, loss1 = tfm.make_train_step(MOE_CFG, mesh1)(
        p1, *tfm.shard_batch(toks, tgts, mesh1))
    p8 = tfm.shard_params(jax.tree.map(jnp.copy, params), MOE_CFG, mesh3d)
    _, loss8 = tfm.make_train_step(MOE_CFG, mesh3d)(
        p8, *tfm.shard_batch(toks, tgts, mesh3d))
    np.testing.assert_allclose(float(loss1), float(loss8), rtol=2e-4)


def test_moe_generate():
    params = tfm.init_params(MOE_CFG, jax.random.PRNGKey(15))
    prompt = jnp.array([[1, 2, 3]], dtype=jnp.int32)
    out = tfm.generate(params, MOE_CFG, prompt, max_new=4)
    assert out.shape == (1, 4)
    assert ((out >= 0) & (out < MOE_CFG.vocab)).all()


def test_moe_generate_batch_independent():
    """Serving is drop-free (decode capacity = every claim fits), so a
    prompt's continuation must not depend on the rest of the batch."""
    params = tfm.init_params(MOE_CFG, jax.random.PRNGKey(16))
    p1 = jnp.array([[1, 2, 3]], dtype=jnp.int32)
    batch = jnp.array([[1, 2, 3], [9, 9, 9], [4, 5, 6], [7, 7, 7]],
                      dtype=jnp.int32)
    alone = tfm.generate(params, MOE_CFG, p1, max_new=5)
    together = tfm.generate(params, MOE_CFG, batch, max_new=5)
    np.testing.assert_array_equal(np.asarray(alone[0]),
                                  np.asarray(together[0]))


def test_train_checkpoint_resume(mesh3d, tmp_path):
    """Mid-training save/restore through svc/checkpoint reproduces the
    uninterrupted trajectory exactly (sharded params round-trip through
    the host serializer and come back with the same values; resharding
    is the caller's shard_params)."""
    import hpx_tpu as hpx

    params = tfm.shard_params(tfm.init_params(CFG, jax.random.PRNGKey(7)),
                              CFG, mesh3d)
    step = tfm.make_train_step(CFG, mesh3d)
    toks, tgts = tfm.sample_batch(CFG, batch=4, seq=32,
                                  key=jax.random.PRNGKey(8))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)

    for _ in range(3):
        params, _ = step(params, toks, tgts)

    path = tmp_path / "train.cp"
    hpx.save_checkpoint_to_file(path, {"step": 3},
                                jax.device_get(params)).get(timeout=60.0)

    # uninterrupted continuation
    p_cont, ref_losses = params, []
    for _ in range(3):
        p_cont, l = step(p_cont, toks, tgts)
        ref_losses.append(float(l))

    # resume from the file
    meta, host_params = hpx.restore_checkpoint_from_file(path)
    assert meta["step"] == 3
    p_res = tfm.shard_params(host_params, CFG, mesh3d)
    got_losses = []
    for _ in range(3):
        p_res, l = step(p_res, toks, tgts)
        got_losses.append(float(l))

    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-6)


def test_generate_sharded_matches_single_device(devices):
    """Megatron decode (heads/ffn/KV cache over tp, batch over dp) must
    emit the same greedy tokens as the single-device path."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
    params = tfm.init_params(CFG, jax.random.PRNGKey(20))
    prompt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [3, 1, 2]],
                       dtype=jnp.int32)
    ref = tfm.generate(params, CFG, prompt, max_new=8)
    sharded_params = tfm.shard_params(params, CFG, mesh)
    got = tfm.generate(sharded_params, CFG, prompt, max_new=8, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_generate_sharded_rejects_bad(devices):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
    params = tfm.init_params(CFG, jax.random.PRNGKey(21))
    bad_batch = jnp.ones((3, 4), jnp.int32)       # 3 % dp=2 != 0
    with pytest.raises(ValueError, match="divisible"):
        tfm.generate(params, CFG, bad_batch, max_new=2, mesh=mesh)
    # MoE decodes expert-parallel now; the remaining MoE refusal is
    # expert divisibility over the expert axis, with the remedy named
    import dataclasses
    odd = dataclasses.replace(MOE_CFG, n_experts=3, moe_top_k=2)
    with pytest.raises(ValueError, match=r"n_experts \(3\).*tp=2"):
        tfm.generate(tfm.init_params(odd, jax.random.PRNGKey(2)),
                     odd, jnp.ones((2, 4), jnp.int32), max_new=2,
                     mesh=mesh)


GQA_CFG = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=4,
                                head_dim=8, n_layers=2, d_ff=32,
                                n_kv_heads=2, lr=0.05)


def test_gqa_train_step_learns(mesh3d):
    params = tfm.shard_params(tfm.init_params(GQA_CFG, jax.random.PRNGKey(0)),
                              GQA_CFG, mesh3d)
    step = tfm.make_train_step(GQA_CFG, mesh3d)
    toks, tgts = tfm.sample_batch(GQA_CFG, batch=4, seq=32,
                                  key=jax.random.PRNGKey(1))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)
    losses = []
    for _ in range(8):
        params, loss = step(params, toks, tgts)
        losses.append(float(loss))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    # the kv projection really is smaller
    wkv = jax.tree.leaves({"w": params["layers"][0]["wkv"]})[0]
    assert wkv.shape == (2, 16, 2, 8)


def test_gqa_decode_cache_is_grouped():
    """KV caches hold n_kv_heads — the serving memory saving — and
    decode is batch-independent as before."""
    params = tfm.init_params(GQA_CFG, jax.random.PRNGKey(2))
    prompt = jnp.array([[1, 2, 3], [4, 5, 6]], dtype=jnp.int32)
    out = tfm.generate(params, GQA_CFG, prompt, max_new=6)
    assert out.shape == (2, 6)
    alone = tfm.generate(params, GQA_CFG, prompt[:1], max_new=6)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(alone[0]))


def test_gqa_sharded_decode_matches(devices):
    from jax.sharding import Mesh
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
    params = tfm.init_params(GQA_CFG, jax.random.PRNGKey(3))
    prompt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [2, 2, 2]],
                       dtype=jnp.int32)
    ref = tfm.generate(params, GQA_CFG, prompt, max_new=6)
    got = tfm.generate(tfm.shard_params(params, GQA_CFG, mesh), GQA_CFG,
                       prompt, max_new=6, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


def test_gqa_pipelined_train(devices):
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=4,
                                head_dim=8, n_layers=4, d_ff=32,
                                n_kv_heads=2, lr=0.05)
    mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "pp"))
    stacked = tfm.shard_pipeline_params(
        tfm.stack_pipeline_params(tfm.init_params(cfg, jax.random.PRNGKey(4))),
        mesh)
    step = tfm.make_pipelined_train_step(cfg, mesh, 2)
    toks, tgts = tfm.sample_batch(cfg, batch=4, seq=8,
                                  key=jax.random.PRNGKey(5))
    sh = NamedSharding(mesh, P("dp", None))
    t, g = jax.device_put(toks, sh), jax.device_put(tgts, sh)
    _, l0 = step(stacked, t, g)
    stacked, _ = step(stacked, t, g)
    for _ in range(3):
        stacked, l1 = step(stacked, t, g)
    assert float(l1) < float(l0)


def test_remat_matches_non_remat(mesh3d):
    """cfg.remat changes memory, not math: losses and updated params
    must match the non-remat step."""
    base = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                 head_dim=8, n_layers=2, d_ff=32, lr=0.05)
    rem = dataclasses.replace(base, remat=True)
    toks, tgts = tfm.sample_batch(base, batch=4, seq=32,
                                  key=jax.random.PRNGKey(6))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)
    outs = []
    for cfg in (base, rem):
        params = tfm.shard_params(
            tfm.init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh3d)
        step = tfm.make_train_step(cfg, mesh3d)
        params, loss = step(params, toks, tgts)
        outs.append((jax.device_get(params), float(loss)))
    (p1, l1), (p2, l2) = outs
    assert l1 == pytest.approx(l2, abs=1e-6)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)


ROPE_CFG = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                 head_dim=8, n_layers=2, d_ff=32,
                                 rope=True, lr=0.05)


def test_rope_positions_matter():
    """With RoPE, permuting prompt tokens changes the logits even in a
    fresh model — the position-free baseline can't tell (same-token
    prompts aside)."""
    params = tfm.init_params(ROPE_CFG, jax.random.PRNGKey(0))
    from hpx_tpu.models.transformer import _ln, _block
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    mesh1 = tfm.make_mesh_3d(1)
    sp = tfm.shard_params(params, ROPE_CFG, mesh1)

    def fwd(p, toks):
        x = p["emb"][toks]
        for lp in p["layers"]:
            x, _ = _block(x, lp, ROPE_CFG, 1, 1)
        return _ln(x, p["ln_f"])

    run = jax.jit(shard_map(fwd, mesh=mesh1,
                            in_specs=(tfm.param_specs(ROPE_CFG),
                                      P("dp", "sp")),
                            out_specs=P("dp", "sp")))
    a = run(sp, jnp.array([[5, 5, 5, 7]], jnp.int32))
    b = run(sp, jnp.array([[5, 5, 7, 5]], jnp.int32))
    # final-position outputs must differ: token 7 sat at different pos
    assert not np.allclose(np.asarray(a)[0, -1], np.asarray(b)[0, -1],
                           atol=1e-5)


def test_rope_sharded_matches_single_device(mesh3d):
    """RoPE under the sp ring (global positions per shard) computes the
    same loss as the 1-device mesh."""
    mesh1 = tfm.make_mesh_3d(1)
    toks, tgts = tfm.sample_batch(ROPE_CFG, batch=4, seq=32,
                                  key=jax.random.PRNGKey(1))
    losses = []
    for mesh in (mesh1, mesh3d):
        params = tfm.shard_params(
            tfm.init_params(ROPE_CFG, jax.random.PRNGKey(0)), ROPE_CFG,
            mesh)
        step = tfm.make_train_step(ROPE_CFG, mesh)
        t, g = tfm.shard_batch(toks, tgts, mesh)
        _p, loss = step(params, t, g)
        losses.append(float(loss))
    assert losses[0] == pytest.approx(losses[1], abs=2e-5)


def test_rope_generate_matches_forward_oracle():
    """Decode-path rotation (scalar write position, post-rope cache)
    agrees with the training-path rotation (vector positions)."""
    mesh1 = tfm.make_mesh_3d(1)
    params = tfm.shard_params(tfm.init_params(ROPE_CFG,
                                              jax.random.PRNGKey(2)),
                              ROPE_CFG, mesh1)
    step = tfm.make_train_step(ROPE_CFG, mesh1)
    toks, tgts = tfm.sample_batch(ROPE_CFG, batch=4, seq=16,
                                  key=jax.random.PRNGKey(3))
    toks, tgts = tfm.shard_batch(toks, tgts, mesh1)
    for _ in range(30):
        params, _ = step(params, toks, tgts)

    prompt = jnp.array([[3, 1, 4, 1], [2, 7, 1, 8]], dtype=jnp.int32)
    out = tfm.generate(params, ROPE_CFG, prompt, max_new=6)

    from hpx_tpu.models.transformer import _ln, _block
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def fwd(p, toks):
        x = p["emb"][toks]
        for lp in p["layers"]:
            x, _ = _block(x, lp, ROPE_CFG, 1, 1)
        x = _ln(x, p["ln_f"])
        return jnp.einsum("bsd,vd->bsv", x, p["emb"])

    run = jax.jit(shard_map(fwd, mesh=mesh1,
                            in_specs=(tfm.param_specs(ROPE_CFG),
                                      P("dp", "sp")),
                            out_specs=P("dp", "sp")))
    seq = prompt
    want = []
    for _ in range(6):
        logits = run(params, seq)
        nxt = jnp.argmax(logits[:, -1, :], axis=-1)
        want.append(np.asarray(nxt))
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.stack(want, 1))


def test_rope_rejects_odd_head_dim(mesh3d):
    bad = dataclasses.replace(ROPE_CFG, head_dim=7)
    params = tfm.init_params(bad, jax.random.PRNGKey(0))
    step = tfm.make_train_step(bad, tfm.make_mesh_3d(1))
    toks, tgts = tfm.sample_batch(bad, batch=2, seq=8,
                                  key=jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="even head_dim"):
        step(tfm.shard_params(params, bad, tfm.make_mesh_3d(1)),
             *tfm.shard_batch(toks, tgts, tfm.make_mesh_3d(1)))


class TestSamplingDecode:
    def test_temperature_zero_is_greedy(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(30))
        prompt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        a = tfm.generate(params, CFG, prompt, max_new=6)
        b = tfm.generate(params, CFG, prompt, max_new=6, temperature=0.0)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_sampling_deterministic_and_key_sensitive(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(31))
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
        a = tfm.generate(params, CFG, prompt, max_new=8, temperature=1.0,
                         key=k1)
        b = tfm.generate(params, CFG, prompt, max_new=8, temperature=1.0,
                         key=k1)
        c = tfm.generate(params, CFG, prompt, max_new=8, temperature=1.0,
                         key=k2)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_sampled_sharded_matches_single_device(self, devices):
        """Global-row key folding: the sharded sampler draws the same
        tokens as the single-device one."""
        from jax.sharding import Mesh
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        params = tfm.init_params(CFG, jax.random.PRNGKey(32))
        prompt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [2, 1, 2]],
                           jnp.int32)
        k = jax.random.PRNGKey(7)
        ref = tfm.generate(params, CFG, prompt, max_new=6,
                           temperature=0.8, top_k=8, key=k)
        got = tfm.generate(tfm.shard_params(params, CFG, mesh), CFG,
                           prompt, max_new=6, temperature=0.8, top_k=8,
                           key=k, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_top_k_one_is_greedy(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(33))
        prompt = jnp.array([[3, 1, 4]], jnp.int32)
        greedy = tfm.generate(params, CFG, prompt, max_new=6)
        tk1 = tfm.generate(params, CFG, prompt, max_new=6,
                           temperature=0.5, top_k=1,
                           key=jax.random.PRNGKey(0))
        np.testing.assert_array_equal(np.asarray(greedy), np.asarray(tk1))

    def test_eos_pins_rows(self):
        """Force eos to be the argmax continuation by picking eos_id
        from a greedy run, then check everything after stays eos."""
        params = tfm.init_params(CFG, jax.random.PRNGKey(34))
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        free = np.asarray(tfm.generate(params, CFG, prompt, max_new=8))
        eos = int(free[0, 2])               # whatever it emits 3rd
        out = np.asarray(tfm.generate(params, CFG, prompt, max_new=8,
                                      eos_id=eos))
        hits = np.where(out[0] == eos)[0]
        assert hits.size
        first = hits[0]
        assert (out[0, first:] == eos).all()

    def test_requires_key_for_sampling(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(35))
        with pytest.raises(ValueError, match="PRNG key"):
            tfm.generate(params, CFG, jnp.ones((1, 3), jnp.int32),
                         max_new=2, temperature=1.0)


class TestQuantizedServing:
    def test_quantized_decode_runs_and_logits_close(self):
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=64, d_model=64, n_heads=4,
                                    head_dim=16, n_layers=2, d_ff=128)
        params = tfm.init_params(cfg, jax.random.PRNGKey(40))
        qp = quant.quantize_params(params)
        prompt = jnp.array([[1, 2, 3, 4]], jnp.int32)
        dense = tfm.generate(params, cfg, prompt, max_new=6)
        q = tfm.generate(qp, cfg, prompt, max_new=6)
        assert q.shape == dense.shape
        assert (np.asarray(q) >= 0).all() and \
            (np.asarray(q) < cfg.vocab).all()
        # real closeness check: full-sequence logits through the two
        # weight sets (a wrong scale axis would blow this up)
        from hpx_tpu.models.transformer import _ln, _qkv_proj, _dq
        from hpx_tpu.ops.attention import blockwise_attention

        def fwd(p, toks):
            x = p["emb"][toks]
            for lp in p["layers"]:
                h = _ln(x, lp["ln1"])
                qh, kh, vh = _qkv_proj(h, lp)
                att = blockwise_attention(qh, kh, vh, causal=True)
                x = x + jnp.einsum("bsnh,nhd->bsd", att,
                                   _dq(lp["wo"], att))
                h = _ln(x, lp["ln2"])
                x = x + jax.nn.gelu(h @ _dq(lp["w1"], h) + lp["b1"]) \
                    @ _dq(lp["w2"], h)
            return jnp.einsum("bsd,vd->bsv", _ln(x, p["ln_f"]), p["emb"])

        ld = np.asarray(fwd(params, prompt), np.float32)
        lq = np.asarray(fwd(qp, prompt), np.float32)
        rel = np.linalg.norm(ld - lq) / np.linalg.norm(ld)
        assert rel < 0.02, rel

    def test_quantization_error_bounded(self):
        """Per-channel int8 roundtrip error on each weight < 1%."""
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=32, d_model=64, n_heads=4,
                                    head_dim=16, n_layers=1, d_ff=128)
        params = tfm.init_params(cfg, jax.random.PRNGKey(41))
        qp = quant.quantize_params(params)
        for name in ("wqkv", "wo", "w1", "w2"):
            w = np.asarray(params["layers"][0][name], np.float32)
            wq = np.asarray(quant.dequant(qp["layers"][0][name],
                                          jnp.float32))
            rel = np.linalg.norm(w - wq) / np.linalg.norm(w)
            assert rel < 0.01, (name, rel)

    def test_memory_shrinks_4x(self):
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=32, d_model=128, n_heads=4,
                                    head_dim=32, n_layers=2, d_ff=512)
        params = tfm.init_params(cfg, jax.random.PRNGKey(42))
        dense_bytes = quant.quantized_bytes(params["layers"])
        q_bytes = quant.quantized_bytes(
            quant.quantize_params(params)["layers"])
        assert q_bytes < dense_bytes * 0.3       # f32 -> int8 + scales

    def test_gqa_quantized(self):
        from hpx_tpu.models import quant
        qp = quant.quantize_params(
            tfm.init_params(GQA_CFG, jax.random.PRNGKey(43)))
        out = tfm.generate(qp, GQA_CFG, jnp.array([[1, 2]], jnp.int32),
                           max_new=4)
        assert out.shape == (1, 4)

    # sharded quantized decode is now supported —
    # see TestQuantizedShardedDecode below for the bit-identity coverage


class TestBeamSearch:
    def _trained(self, seed=50):
        mesh1 = tfm.make_mesh_3d(1)
        params = tfm.shard_params(
            tfm.init_params(CFG, jax.random.PRNGKey(seed)), CFG, mesh1)
        step = tfm.make_train_step(CFG, mesh1)
        toks, tgts = tfm.sample_batch(CFG, batch=4, seq=16,
                                      key=jax.random.PRNGKey(seed + 1))
        toks, tgts = tfm.shard_batch(toks, tgts, mesh1)
        for _ in range(25):
            params, _ = step(params, toks, tgts)
        return jax.device_get(params)

    def test_beam_one_equals_greedy(self):
        params = self._trained()
        prompt = jnp.array([[3, 1, 4], [2, 7, 1]], jnp.int32)
        greedy = tfm.generate(params, CFG, prompt, max_new=8)
        beam1 = tfm.beam_search(params, CFG, prompt, max_new=8,
                                beam_width=1)
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.asarray(beam1))

    def test_beam_score_at_least_greedy(self):
        """The best beam's total logprob must be >= the greedy
        sequence's (greedy is in the search space of width >= 1)."""
        params = self._trained(seed=60)
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        max_new = 8
        greedy = np.asarray(tfm.generate(params, CFG, prompt,
                                         max_new=max_new))
        beams, scores = tfm.beam_search(params, CFG, prompt,
                                        max_new=max_new, beam_width=4,
                                        return_all=True)

        def seq_logprob(tokens):
            # teacher-force through THE decoder's own per-token forward
            from hpx_tpu.models.transformer import _decode_forward
            caches = [(jnp.zeros((1, 3 + max_new, CFG.kv_heads,
                                  CFG.head_dim), CFG.dtype),) * 2
                      for _ in range(CFG.n_layers)]
            total, seq = 0.0, [1, 2, 3] + list(tokens)
            for pos in range(len(seq) - 1):
                caches, logits = _decode_forward(
                    params, caches, jnp.array([seq[pos]]), pos, CFG)
                lp_ = jax.nn.log_softmax(logits[0])
                if pos >= 2:            # predictions beyond the prompt
                    total += float(lp_[seq[pos + 1]])
            return total

        g = seq_logprob(greedy[0].tolist())
        b = seq_logprob(np.asarray(beams)[0, 0].tolist())
        assert b >= g - 1e-4
        assert float(scores[0, 0]) == pytest.approx(b, abs=1e-3)

    def test_beam_shapes_and_sorted(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(70))
        prompt = jnp.array([[1, 2], [3, 4], [5, 6]], jnp.int32)
        beams, scores = tfm.beam_search(params, CFG, prompt, max_new=5,
                                        beam_width=3, return_all=True)
        assert beams.shape == (3, 3, 5) and scores.shape == (3, 3)
        s = np.asarray(scores)
        assert (s[:, :-1] >= s[:, 1:] - 1e-6).all()

    def test_beam_bf16_model(self):
        """Regression: the logits scan carry must stay f32 whatever the
        model dtype (bf16 once crashed the carry-type check)."""
        cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
        params = tfm.init_params(cfg, jax.random.PRNGKey(80))
        out = tfm.beam_search(params, cfg,
                              jnp.array([[1, 2, 3]], jnp.int32),
                              max_new=4, beam_width=3)
        assert out.shape == (1, 4)


class TestQuantizedShardedDecode:
    """int8 serving under dp x tp: scales shard with their channels
    (quant.quantized_param_specs); output must be bit-identical to the
    single-device quantized decode."""

    def test_quantized_tp_decode_bit_identical(self, devices):
        from jax.sharding import Mesh
        from hpx_tpu.models import quant
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                    head_dim=8, n_layers=2, d_ff=64)
        qp = quant.quantize_params(
            tfm.init_params(cfg, jax.random.PRNGKey(50)))
        prompt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [3, 1, 2]],
                           jnp.int32)
        ref = tfm.generate(qp, cfg, prompt, max_new=8)
        sharded = quant.shard_quantized(qp, cfg, mesh)
        got = tfm.generate(sharded, cfg, prompt, max_new=8, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_quantized_gqa_tp_decode_bit_identical(self, devices):
        from jax.sharding import Mesh
        from hpx_tpu.models import quant
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        qp = quant.quantize_params(
            tfm.init_params(GQA_CFG, jax.random.PRNGKey(51)))
        prompt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [2, 2, 2]],
                           jnp.int32)
        ref = tfm.generate(qp, GQA_CFG, prompt, max_new=6)
        got = tfm.generate(quant.shard_quantized(qp, GQA_CFG, mesh),
                           GQA_CFG, prompt, max_new=6, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_scales_actually_sharded_with_channels(self, devices):
        from jax.sharding import Mesh
        from hpx_tpu.models import quant
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                    head_dim=8, n_layers=1, d_ff=64)
        sharded = quant.shard_quantized(
            quant.quantize_params(tfm.init_params(
                cfg, jax.random.PRNGKey(52))), cfg, mesh)
        lp = sharded["layers"][0]
        # wqkv q and its scales both split their head axis over tp
        q_sh = lp["wqkv"].q.sharding.spec
        s_sh = lp["wqkv"].s.sharding.spec
        assert "tp" in tuple(q_sh) and "tp" in tuple(s_sh), (q_sh, s_sh)
        # w2's contracted f axis is tp-sharded, its scales replicated
        assert tuple(lp["w2"].q.sharding.spec)[0] == "tp"
        assert all(a is None for a in tuple(lp["w2"].s.sharding.spec))


class TestQuantizedMoE:
    """int8 expert weights for MoE serving: w1/w2 quantized per
    (expert, output channel); router and biases stay dense."""

    MOE_Q_CFG = tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, head_dim=8, n_layers=2,
        d_ff=64, n_experts=4, moe_top_k=2, moe_capacity=4.0)

    def test_quantized_moe_decode_matches_dense(self):
        from hpx_tpu.models import quant
        params = tfm.init_params(self.MOE_Q_CFG, jax.random.PRNGKey(60))
        qp = quant.quantize_params(params)
        lp = qp["layers"][0]["moe"]
        assert isinstance(lp["w1"], quant.QTensor)
        assert isinstance(lp["w2"], quant.QTensor)
        assert not isinstance(lp["wg"], quant.QTensor)   # router dense
        prompt = jnp.array([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
        dense = tfm.generate(params, self.MOE_Q_CFG, prompt, max_new=6)
        q = tfm.generate(qp, self.MOE_Q_CFG, prompt, max_new=6)
        # int8 rounding can flip a rare near-tie; anything below high
        # agreement means the scales are wrong
        agree = float((np.asarray(q) == np.asarray(dense)).mean())
        assert agree >= 0.9, agree
        assert q.shape == dense.shape

    def test_expert_weight_roundtrip_error_bounded(self):
        from hpx_tpu.models import quant
        params = tfm.init_params(self.MOE_Q_CFG, jax.random.PRNGKey(61))
        qp = quant.quantize_params(params)
        for name in ("w1", "w2"):
            w = np.asarray(params["layers"][0]["moe"][name], np.float32)
            wq = np.asarray(quant.dequant(
                qp["layers"][0]["moe"][name], jnp.float32))
            rel = np.linalg.norm(w - wq) / np.linalg.norm(w)
            assert rel < 0.01, (name, rel)

    def test_quantized_moe_specs_tree_matches(self):
        from jax.sharding import PartitionSpec
        from hpx_tpu.models import quant
        params = tfm.init_params(self.MOE_Q_CFG, jax.random.PRNGKey(62))
        qp = quant.quantize_params(params)
        specs = quant.quantized_param_specs(self.MOE_Q_CFG)
        # STRUCTURE equality (tree.map alone flattens specs only up to
        # qp's structure and would accept nested garbage), and every
        # spec leaf is an actual PartitionSpec — catches the
        # shared-moe-dict double-wrap regression
        assert (jax.tree.structure(qp)
                == jax.tree.structure(specs)), "tree mismatch"
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, PartitionSpec), leaf


# -- speculative decoding ----------------------------------------------------

class TestSpeculativeDecoding:
    """speculative_generate must emit generate(temperature=0)'s tokens
    — the draft changes throughput, never content. (Exact equality
    holds when no position's top-2 target logits are within the window
    vs sequential forward's ~1e-4 reassociation gap; these f32 models
    at fixed seeds have no such ties.)"""

    DRAFT = tfm.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                  head_dim=8, n_layers=1, d_ff=32)

    def test_window_forward_matches_sequential(self):
        """_decode_window == a scan of _decode_forward on the same
        tokens (validates the multi-token mask/rope generalization of
        _block_decode directly)."""
        params = tfm.init_params(CFG, jax.random.PRNGKey(3))
        toks = jnp.array([[5, 9, 11, 2], [7, 1, 3, 8]], jnp.int32)
        b, w = toks.shape
        smax = 16

        def fresh():
            return [(jnp.zeros((b, smax, CFG.kv_heads, CFG.head_dim),
                               CFG.dtype),
                     jnp.zeros((b, smax, CFG.kv_heads, CFG.head_dim),
                               CFG.dtype))
                    for _ in range(CFG.n_layers)]

        _, win_logits = tfm._decode_window(params, fresh(), toks, 0, CFG)
        caches = fresh()
        seq_logits = []
        for i in range(w):
            caches, lg = tfm._decode_forward(params, caches, toks[:, i],
                                             i, CFG)
            seq_logits.append(lg)
        np.testing.assert_allclose(np.asarray(win_logits),
                                   np.stack(seq_logits, axis=1),
                                   rtol=2e-4, atol=2e-4)

    def test_draft_equals_target_all_accepted(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(6))
        prompt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
        ref = tfm.generate(params, CFG, prompt, max_new=8)
        out = tfm.speculative_generate(params, CFG, params, CFG, prompt,
                                       max_new=8, k=3)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("k", [1, 2, 4, 7])
    def test_small_draft_matches_greedy(self, k):
        params = tfm.init_params(CFG, jax.random.PRNGKey(6))
        draft = tfm.init_params(self.DRAFT, jax.random.PRNGKey(7))
        prompt = jnp.array([[1, 2, 3, 4], [9, 8, 7, 6],
                            [0, 0, 0, 0]], jnp.int32)
        ref = tfm.generate(params, CFG, prompt, max_new=11)
        out = tfm.speculative_generate(params, CFG, draft, self.DRAFT,
                                       prompt, max_new=11, k=k)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_rejects_bad_args(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(6))
        draft = tfm.init_params(self.DRAFT, jax.random.PRNGKey(7))
        prompt = jnp.array([[1, 2]], jnp.int32)
        with pytest.raises(ValueError, match="k must be"):
            tfm.speculative_generate(params, CFG, draft, self.DRAFT,
                                     prompt, max_new=4, k=0)
        bad = dataclasses.replace(self.DRAFT, vocab=32)
        with pytest.raises(ValueError, match="vocab"):
            tfm.speculative_generate(params, CFG, draft, bad, prompt,
                                     max_new=4)

    def test_full_acceptance_rounds_near_minimal(self):
        """Self-draft must accept ~k+1 tokens per round for the WHOLE
        run. Regression: a draft-cache KV hole after a fully-accepted
        round silently collapses later acceptance (outputs stay
        correct — only the round count shows it)."""
        import math as _math
        params = tfm.init_params(CFG, jax.random.PRNGKey(6))
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        max_new, k = 20, 3
        out, rounds = tfm.speculative_generate(
            params, CFG, params, CFG, prompt, max_new=max_new, k=k,
            return_stats=True)
        ref = tfm.generate(params, CFG, prompt, max_new=max_new)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
        # 19 tokens after tok0 at k+1=4 per round -> 5 rounds minimum;
        # allow +1 slack for a float argmax tie, never the collapse
        assert int(rounds) <= _math.ceil((max_new - 1) / (k + 1)) + 1, \
            f"acceptance collapsed: {int(rounds)} rounds"

    def test_sharded_matches_single_device(self, devices):
        """dp2/tp2 speculative decode emits the same tokens as the
        single-device run (per-dp-shard loops may diverge in trip
        count; content must not)."""
        from jax.sharding import Mesh
        cfg = dataclasses.replace(CFG, n_kv_heads=2, rope=True)
        params = tfm.init_params(cfg, jax.random.PRNGKey(6))
        draft = tfm.init_params(self.DRAFT, jax.random.PRNGKey(7))
        prompt = jnp.array([[1, 2, 3, 4], [9, 8, 7, 6],
                            [5, 5, 5, 5], [2, 4, 6, 8]], jnp.int32)
        single = tfm.speculative_generate(params, cfg, draft,
                                          self.DRAFT, prompt,
                                          max_new=9, k=3)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    ("dp", "tp"))
        sharded, rounds = tfm.speculative_generate(
            tfm.shard_params(params, cfg, mesh), cfg, draft,
            self.DRAFT, prompt, max_new=9, k=3, mesh=mesh,
            return_stats=True)
        np.testing.assert_array_equal(np.asarray(sharded),
                                      np.asarray(single))
        assert rounds.shape == (4,) and (np.asarray(rounds) >= 1).all()


class TestInt4Quantization:
    def test_pack_unpack_roundtrip(self):
        from hpx_tpu.models import quant
        rng = np.random.default_rng(0)
        for shape, axis in [((8, 6), 0), ((3, 8, 4), 1), ((2, 4, 6), 2)]:
            q = jnp.asarray(rng.integers(-7, 8, shape), jnp.int8)
            packed = quant._pack4(q, axis)
            assert packed.shape[axis] == shape[axis] // 2
            np.testing.assert_array_equal(
                np.asarray(quant._unpack4(packed, axis)), np.asarray(q))
        with pytest.raises(ValueError, match="even"):
            quant._pack4(jnp.zeros((3, 4), jnp.int8), 0)

    def test_int4_error_bounded_and_4x_smaller(self):
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=64, d_model=64, n_heads=4,
                                    head_dim=16, n_layers=2, d_ff=128)
        params = tfm.init_params(cfg, jax.random.PRNGKey(40))
        q4 = quant.quantize_params(params, bits=4)
        assert quant.quantized_bits(q4) == 4
        # per-element roundtrip error <= s/2 (15-level symmetric grid)
        w = params["layers"][0]["w1"]
        t4 = q4["layers"][0]["w1"]
        back = np.asarray(quant.dequant(t4, jnp.float32))
        err = np.abs(back - np.asarray(w, np.float32))
        assert (err <= np.asarray(t4.s) / 2 + 1e-6).all()
        # storage: ~4x smaller than f32 weights (scales add a little)
        dense_b = quant.quantized_bytes(params["layers"])
        q4_b = quant.quantized_bytes(q4["layers"])
        assert dense_b / q4_b > 3.0, (dense_b, q4_b)
        q8_b = quant.quantized_bytes(
            quant.quantize_params(params)["layers"])
        assert q8_b / q4_b > 1.6, (q8_b, q4_b)

    def test_int4_decode_runs_and_logits_close(self):
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=64, d_model=64, n_heads=4,
                                    head_dim=16, n_layers=2, d_ff=128)
        params = tfm.init_params(cfg, jax.random.PRNGKey(40))
        q4 = quant.quantize_params(params, bits=4)
        prompt = jnp.array([[1, 2, 3, 4]], jnp.int32)
        out = tfm.generate(q4, cfg, prompt, max_new=6)
        assert out.shape == (1, 6)
        assert (np.asarray(out) >= 0).all() and \
            (np.asarray(out) < cfg.vocab).all()

    def test_int4_tp_decode_bit_identical(self, devices):
        from jax.sharding import Mesh
        from hpx_tpu.models import quant
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                    head_dim=8, n_layers=2, d_ff=64)
        q4 = quant.quantize_params(
            tfm.init_params(cfg, jax.random.PRNGKey(50)), bits=4)
        prompt = jnp.array([[1, 2, 3], [4, 5, 6], [7, 8, 9], [3, 1, 2]],
                           jnp.int32)
        ref = tfm.generate(q4, cfg, prompt, max_new=8)
        sharded = quant.shard_quantized(q4, cfg, mesh)
        got = tfm.generate(sharded, cfg, prompt, max_new=8, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_int4_moe_decode_runs(self):
        from hpx_tpu.models import quant
        cfg = tfm.TransformerConfig(vocab=32, d_model=32, n_heads=4,
                                    head_dim=8, n_layers=1, d_ff=64,
                                    n_experts=4)
        params = tfm.init_params(cfg, jax.random.PRNGKey(9))
        q4 = quant.quantize_params(params, bits=4)
        out = tfm.generate(q4, cfg,
                           jnp.array([[1, 2]], jnp.int32), max_new=4)
        assert out.shape == (1, 4)

    def test_int4_odd_local_heads_pack_unsharded_axis(self, devices):
        """wo packs head_dim, not the tp-sharded heads axis: n_heads=6
        with tp=2 (odd local head count) must shard + decode fine."""
        from jax.sharding import Mesh
        from hpx_tpu.models import quant
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = tfm.TransformerConfig(vocab=64, d_model=24, n_heads=6,
                                    head_dim=8, n_layers=1, d_ff=64)
        q4 = quant.quantize_params(
            tfm.init_params(cfg, jax.random.PRNGKey(51)), bits=4)
        prompt = jnp.array([[1, 2], [3, 4], [5, 6], [7, 8]], jnp.int32)
        ref = tfm.generate(q4, cfg, prompt, max_new=5)
        got = tfm.generate(quant.shard_quantized(q4, cfg, mesh), cfg,
                           prompt, max_new=5, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_int4_sharded_pack_axis_validated(self, devices):
        """d_ff not a multiple of 2*tp: clear error, not a device_put
        shape failure."""
        from jax.sharding import Mesh
        from hpx_tpu.models import quant
        mesh = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "tp"))
        cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                    head_dim=8, n_layers=1, d_ff=66)
        q4 = quant.quantize_params(
            tfm.init_params(cfg, jax.random.PRNGKey(52)), bits=4)
        with pytest.raises(ValueError, match="nibble pairs"):
            quant.shard_quantized(q4, cfg, mesh)


class TestSpeculativeSampling:
    """speculative_sample: the exact acceptance-rejection algorithm.
    Emitted tokens must be distributed as target-only sampling."""

    SMALL = tfm.TransformerConfig(vocab=8, d_model=16, n_heads=2,
                                  head_dim=8, n_layers=1, d_ff=32)
    SDRAFT = tfm.TransformerConfig(vocab=8, d_model=8, n_heads=1,
                                   head_dim=8, n_layers=1, d_ff=16)

    def test_valid_and_deterministic(self):
        params = tfm.init_params(CFG, jax.random.PRNGKey(6))
        draft = tfm.init_params(
            TestSpeculativeDecoding.DRAFT, jax.random.PRNGKey(7))
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        out = tfm.speculative_sample(params, CFG, draft,
                                     TestSpeculativeDecoding.DRAFT,
                                     prompt, max_new=9, k=3,
                                     key=jax.random.PRNGKey(11))
        assert out.shape == (1, 9)
        assert (np.asarray(out) >= 0).all() and \
            (np.asarray(out) < CFG.vocab).all()
        out2 = tfm.speculative_sample(params, CFG, draft,
                                      TestSpeculativeDecoding.DRAFT,
                                      prompt, max_new=9, k=3,
                                      key=jax.random.PRNGKey(11))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))

    def test_self_draft_accepts_nearly_everything(self):
        import math as _math
        params = tfm.init_params(CFG, jax.random.PRNGKey(6))
        prompt = jnp.array([[1, 2, 3]], jnp.int32)
        max_new, k = 20, 3
        _, rounds = tfm.speculative_sample(
            params, CFG, params, CFG, prompt, max_new=max_new, k=k,
            key=jax.random.PRNGKey(4), return_stats=True)
        # p == q (up to window/sequential reassociation), so the
        # acceptance probability is ~1 at every step
        assert int(rounds) <= _math.ceil((max_new - 1) / (k + 1)) + 2, \
            int(rounds)

    def test_rejects_bad_args(self):
        params = tfm.init_params(self.SMALL, jax.random.PRNGKey(0))
        draft = tfm.init_params(self.SDRAFT, jax.random.PRNGKey(1))
        two = jnp.array([[1, 2], [3, 4]], jnp.int32)
        one = jnp.array([[1, 2]], jnp.int32)
        with pytest.raises(ValueError, match="single-stream"):
            tfm.speculative_sample(params, self.SMALL, draft,
                                   self.SDRAFT, two, max_new=4,
                                   key=jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="PRNG key"):
            tfm.speculative_sample(params, self.SMALL, draft,
                                   self.SDRAFT, one, max_new=4)
        with pytest.raises(ValueError, match="temperature"):
            tfm.speculative_sample(params, self.SMALL, draft,
                                   self.SDRAFT, one, max_new=4,
                                   temperature=0.0,
                                   key=jax.random.PRNGKey(0))

    @pytest.mark.slow
    def test_distribution_matches_target_sampling(self):
        """Two-sample check: the SECOND emitted token (the first that
        exercises draft/accept/resample) must match target-only
        sampling's marginal. TV noise at n=1200, V=8 is ~0.08; the
        0.15 gate catches a wrong acceptance rule (which shifts mass
        by O(d_TV(p, q)) — large for this mismatched draft) while
        staying flake-free."""
        params = tfm.init_params(self.SMALL, jax.random.PRNGKey(0))
        draft = tfm.init_params(self.SDRAFT, jax.random.PRNGKey(1))
        prompt = jnp.array([[1, 2]], jnp.int32)
        n = 1200
        spec = np.zeros(8)
        ref = np.zeros(8)
        for i in range(n):
            o = tfm.speculative_sample(params, self.SMALL, draft,
                                       self.SDRAFT, prompt, max_new=2,
                                       k=2, key=jax.random.PRNGKey(i))
            spec[int(np.asarray(o)[0, 1])] += 1
            r = tfm.generate(params, self.SMALL, prompt, max_new=2,
                             temperature=1.0,
                             key=jax.random.PRNGKey(10_000 + i))
            ref[int(np.asarray(r)[0, 1])] += 1
        tv = 0.5 * np.abs(spec / n - ref / n).sum()
        assert tv < 0.15, (tv, spec, ref)


def test_speculative_eos_matches_generate():
    """eos pinning through speculative decode matches generate's
    done-row pinning exactly (greedy)."""
    params = tfm.init_params(CFG, jax.random.PRNGKey(6))
    draft = tfm.init_params(TestSpeculativeDecoding.DRAFT,
                            jax.random.PRNGKey(7))
    prompt = jnp.array([[1, 2, 3], [4, 5, 6]], jnp.int32)
    plain = np.asarray(tfm.generate(params, CFG, prompt, max_new=10))
    eos = int(plain[0, 2])            # a token greedy actually emits
    ref = tfm.generate(params, CFG, prompt, max_new=10, eos_id=eos)
    out = tfm.speculative_generate(params, CFG, draft,
                                   TestSpeculativeDecoding.DRAFT,
                                   prompt, max_new=10, k=3, eos_id=eos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # sampled path: tail after first eos is pinned
    o2 = np.asarray(tfm.speculative_sample(
        params, CFG, draft, TestSpeculativeDecoding.DRAFT, prompt[:1],
        max_new=10, k=3, key=jax.random.PRNGKey(3), eos_id=eos))
    hits = np.where(o2[0] == eos)[0]
    if hits.size:
        assert (o2[0, hits[0]:] == eos).all()


class TestStripedRingTraining:
    """cfg.striped_ring: the train step stripes the batch itself and
    runs the balanced causal ring — losses must match the contiguous
    run (same per-token terms, reordered) and training must learn."""

    def test_losses_match_contiguous(self, mesh3d):
        cfg_c = dataclasses.replace(CFG, rope=True)
        cfg_s = dataclasses.replace(CFG, rope=True, striped_ring=True)
        key = jax.random.PRNGKey(0)
        toks, tgts = tfm.sample_batch(cfg_c, batch=4, seq=32,
                                      key=jax.random.PRNGKey(1))
        toks, tgts = tfm.shard_batch(toks, tgts, mesh3d)
        losses = {}
        for name, cfg in (("contig", cfg_c), ("striped", cfg_s)):
            params = tfm.shard_params(tfm.init_params(cfg, key), cfg,
                                      mesh3d)
            step = tfm.make_train_step(cfg, mesh3d)
            ls = []
            for _ in range(3):
                params, lo = step(params, toks, tgts)
                ls.append(float(lo))
            losses[name] = ls
        np.testing.assert_allclose(losses["striped"], losses["contig"],
                                   rtol=2e-4)
        assert losses["striped"][-1] < losses["striped"][0]

    def test_pipelined_rejects_striped(self, mesh3d):
        cfg = dataclasses.replace(CFG, striped_ring=True)
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 2),
                    ("dp", "pp", "tp"))
        with pytest.raises(NotImplementedError, match="striped"):
            tfm.make_pipelined_train_step(cfg, mesh, n_microbatches=2)


def test_speculative_with_quantized_target():
    """int8 target through speculative decode == int8 greedy decode
    (the draft never changes which weights produce tokens). Same
    fixed-seed tie caveat as TestSpeculativeDecoding."""
    from hpx_tpu.models import quant
    qp = quant.quantize_params(tfm.init_params(CFG, jax.random.PRNGKey(2)))
    draft = tfm.init_params(TestSpeculativeDecoding.DRAFT,
                            jax.random.PRNGKey(3))
    prompt = jnp.array([[5, 6, 7]], jnp.int32)
    ref = tfm.generate(qp, CFG, prompt, max_new=8)
    out = tfm.speculative_generate(qp, CFG, draft,
                                   TestSpeculativeDecoding.DRAFT,
                                   prompt, max_new=8, k=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
