"""StarCoder2's block through the ONE layer definition
(`transformer._layer`) against the block as it was written out before
that definition existed: bit-identical logits, caches and pools on the
CPU. `_parent_*` below are that block's bodies, kept verbatim (PR 28's
`serving._paged_block_rows` / `_paged_decode_rows` and
`transformer._block_decode` / `_decode_window`) as the oracle: a
default `TransformerConfig` must keep building exactly them.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.ops.paged_attention import paged_decode_attention


def _parent_ln(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * scale


def _parent_rope_win(x, posw, cfg):
    half = x.shape[-1] // 2
    freq = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                              / half)
    ang = posw.astype(jnp.float32)[..., None] * freq
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _parent_rope(x, pos, cfg):
    half = x.shape[-1] // 2
    freq = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32)
                              / half)
    ang = pos.astype(jnp.float32)[:, None] * freq[None, :]
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1)


def _parent_paged_decode_rows(params, pools, tok, table, pos, cfg, fused):
    x = params["emb"][tok][:, None, :]
    new_pools = []
    for lp, (kp, vp) in zip(params["layers"], pools):
        h = _parent_ln(x, lp["ln1"])
        q, k, v = tfm._qkv_proj(h, lp)
        if cfg.rope:
            q = _parent_rope_win(q, pos[:, None], cfg)
            k = _parent_rope_win(k, pos[:, None], cfg)
        att, kp, vp = paged_decode_attention(q, k[:, 0], v[:, 0], kp, vp,
                                             table, pos, fused=fused)
        x = x + jnp.einsum("bsnh,nhd->bsd", att, lp["wo"])
        h = _parent_ln(x, lp["ln2"])
        x = x + jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
        new_pools.append((kp, vp))
    x = _parent_ln(x, params["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["emb"])
    return new_pools, logits[:, 0, :].astype(jnp.float32)


def _parent_decode_window(params, caches, toks, pos0, cfg):
    x = params["emb"][toks]
    new_caches = []
    for lp, (kc, vc) in zip(params["layers"], caches):
        h = _parent_ln(x, lp["ln1"])
        q, k, v = tfm._qkv_proj(h, lp)
        sq = x.shape[1]
        if cfg.rope:
            pos = jnp.asarray(pos0) + jnp.arange(sq)
            q, k = _parent_rope(q, pos, cfg), _parent_rope(k, pos, cfg)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos0, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos0, axis=1)
        b, sq, nq, hd = q.shape
        nkv = kc.shape[2]
        qg = q.reshape(b, sq, nkv, nq // nkv, hd)
        s = jnp.einsum("bqngh,bknh->bngqk", qg, kc) / math.sqrt(hd)
        kpos = jnp.arange(kc.shape[1])
        qpos = jnp.asarray(pos0) + jnp.arange(sq)
        s = jnp.where(kpos[None, None, None, None, :]
                      <= qpos[None, None, None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(x.dtype)
        att = jnp.einsum("bngqk,bknh->bqngh", p, vc).reshape(b, sq, nq, hd)
        x = x + jnp.einsum("bsnh,nhd->bsd", att, lp["wo"])
        h = _parent_ln(x, lp["ln2"])
        x = x + jax.nn.gelu(h @ lp["w1"] + lp["b1"]) @ lp["w2"]
        new_caches.append((kc, vc))
    x = _parent_ln(x, params["ln_f"])
    logits = jnp.einsum("bsd,vd->bsv", x, params["emb"])
    return new_caches, logits.astype(jnp.float32)


def _model(dtype, nkv, rope):
    """StarCoder2-3B's shape cut to a CPU's size: GQA, RoPE, LayerNorm,
    GELU with the first bias, tied head (the defaults)."""
    cfg = tfm.TransformerConfig(vocab=128, d_model=64, n_heads=4,
                                head_dim=16, n_layers=3, d_ff=128,
                                n_kv_heads=nkv, rope=rope,
                                rope_theta=999999.4, dtype=dtype)
    params = tfm.init_params(cfg, jax.random.PRNGKey(3))
    for i, lp in enumerate(params["layers"]):    # scales and bias off 1/0
        k = jax.random.split(jax.random.PRNGKey(i), 3)
        lp["ln1"] = (1 + 0.1 * jax.random.normal(k[0], (64,))).astype(dtype)
        lp["ln2"] = (1 + 0.1 * jax.random.normal(k[1], (64,))).astype(dtype)
        lp["b1"] = (0.1 * jax.random.normal(k[2], (128,))).astype(dtype)
    return cfg, params


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.float32)),
                                      np.asarray(y.astype(jnp.float32)))


CASES = [(jnp.float32, 2, True), (jnp.bfloat16, 2, True),
         (jnp.float32, 0, False), (jnp.bfloat16, 1, True)]
IDS = ["f32-gqa", "bf16-gqa", "f32-mha-norope", "bf16-mqa"]


@pytest.mark.parametrize("fused", [False, True], ids=["gather", "fused"])
@pytest.mark.parametrize("dtype,nkv,rope", CASES, ids=IDS)
def test_paged_decode_step_is_bit_identical_to_the_parents_block(
        dtype, nkv, rope, fused):
    cfg, params = _model(dtype, nkv, rope)
    slots, bs, maxb = 3, 4, 8
    rng = np.random.default_rng(0)
    pools = [tuple(jnp.asarray(rng.normal(size=(slots * maxb + 1,
                                                cfg.kv_heads, bs, 16)),
                               dtype) for _ in "kv")
             for _ in range(cfg.n_layers)]
    table = jnp.asarray(1 + np.arange(slots * maxb).reshape(slots, maxb),
                        jnp.int32)
    tok = jnp.asarray([5, 17, 99], jnp.int32)
    pos = jnp.asarray([0, 13, 31], jnp.int32)
    want = jax.jit(_parent_paged_decode_rows, static_argnums=(5, 6))(
        params, pools, tok, table, pos, cfg, fused)
    got = jax.jit(serving._paged_decode_rows, static_argnums=(6, 7))(
        params, pools, None, tok, (table,), pos, cfg, fused)
    _same(got[0], want[0])              # the pools, every layer
    _same(got[2], want[1])              # the logits
    assert got[1] is None and got[3] is None


@pytest.mark.parametrize("width", [1, 8])
@pytest.mark.parametrize("dtype,nkv,rope", CASES, ids=IDS)
def test_prefill_chunk_is_bit_identical_to_the_parents_block(
        dtype, nkv, rope, width):
    cfg, params = _model(dtype, nkv, rope)
    rng = np.random.default_rng(1)
    caches = [tuple(jnp.asarray(rng.normal(size=(2, 32, cfg.kv_heads, 16)),
                                dtype) for _ in "kv")
              for _ in range(cfg.n_layers)]
    toks = jnp.asarray(rng.integers(0, 128, (2, width)), jnp.int32)
    want = jax.jit(_parent_decode_window, static_argnums=(4,))(
        params, caches, toks, jnp.int32(9), cfg)
    got = jax.jit(tfm._decode_window, static_argnums=(4,))(
        params, caches, toks, jnp.int32(9), cfg)
    _same(got, want)


def test_a_default_config_describes_the_old_block():
    cfg = tfm.TransformerConfig(n_layers=3, rope=True)
    assert [cfg.heads(i) for i in range(3)] == [cfg.n_heads] * 3
    assert [cfg.window(i) for i in range(3)] == [0] * 3
    assert cfg.rope_of(2) == tfm.RopeSpec(cfg.rope_theta)
    assert not cfg.sparse(0) and cfg.tied and cfg.norm == "layernorm"
    cfg.only("any body", "any module")      # nothing to refuse
    lp = tfm.init_params(cfg, jax.random.PRNGKey(0))["layers"][0]
    assert sorted(lp) == ["b1", "ln1", "ln2", "w1", "w2", "wo", "wqkv"]


# -- what a layer's cache entry is, by its mixer's kind -----------------------

_KIND = dict(vocab=64, d_model=32, n_heads=4, head_dim=8, n_layers=1,
             d_ff=64, n_kv_heads=2)
# kind -> (the fields that size its entry, the shapes of a b=1 scratch
# entry at smax 64, what the dense-cache bodies' refusal must name)
_KINDS = {
    "attn": ({}, [(1, 64, 2, 8)] * 2, None),
    "sparse": ({}, [(1, 64, 2, 8)] * 2, "sparse layer's index"),
    "mla": (dict(mla_rank=24, mla_rope_dim=8), [(1, 64, 1, 128)],
            "latent"),
    "kda": (dict(kda_heads=2, kda_head_dim=8), [(1, 2, 8, 8), (1, 3, 48)],
            "recurrent state"),
    "lightning": (dict(lightning_heads=2, lightning_head_dim=8),
                  [(1, 2, 8, 8)], "recurrent state"),
    "mamba": (dict(mamba_d_inner=16, mamba_d_state=4),
              [(1, 4, 16), (1, 48)], "recurrent state"),
    # one window's exact rows as a ring, and one summary every chunk of
    # the windows that can complete (64 // 16 = 4 of them, 16 // 4 each)
    "eva": (dict(n_kv_heads=0, eva_chunk=4, eva_window=16),
            [(1, 16, 4, 8)] * 2 + [(1, 16, 4, 8)] * 2, "two\\s+grains"),
}


@pytest.mark.parametrize("kind", sorted(_KINDS))
def test_a_scratch_entry_has_its_kinds_shapes(kind):
    fields, shapes, _ = _KINDS[kind]
    cfg = tfm.TransformerConfig(**{**_KIND, **fields},
                                layer_mixer=(kind,))
    entry = jax.eval_shape(lambda: serving._scratch_entry(cfg, 64, 0))
    assert [a.shape for a in entry] == shapes
    if kind == "eva":       # (window + smax / chunk) rows where K/V has smax
        entry = jax.eval_shape(lambda: serving._scratch_entry(
            tfm.TransformerConfig(n_heads=32, head_dim=128, eva_chunk=16,
                                  eva_window=2048, layer_mixer=("eva",)),
            18688, 0))
        assert [a.shape[1] for a in entry] == [2048, 2048, 1152, 1152]


@pytest.mark.parametrize("kind", sorted(k for k in _KINDS if k != "attn"))
def test_the_dense_cache_bodies_refuse_each_kind_by_mechanism(kind):
    fields, _, names = _KINDS[kind]
    cfg = tfm.TransformerConfig(**{**_KIND, **fields},
                                layer_mixer=(kind,))
    with pytest.raises(NotImplementedError) as e:
        cfg.kv_pairs_only("generate: the dense K/V caches",
                          "models/transformer.py")
    text = str(e.value)
    assert "models/transformer.py" in text and kind in text
    assert "models/serving.py _init_paged" in text
    import re
    assert re.search(names, text)
