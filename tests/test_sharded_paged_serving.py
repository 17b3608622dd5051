"""Sharded paged serving: ContinuousServer(paged=True, mesh=(dp, tp))
must emit BYTE-IDENTICAL tokens to the single-device paged server —
greedy and sampled, with and without speculation, bf16 and int8 pools —
while the block pool shards kv-heads over tp, replicates the block axis
over dp, and the slot/page-table rows shard over dp (the shard_map
step: block tables stay per-shard int32, no cross-shard gathers).

Single-device server == generate() is already pinned by
test_paged_serving / test_spec_serving (each request against its solo
`generate()` run, greedy and sampled), so equality against the solo
server chains all the way back to the solo-generate() contract.
"""

import jax
import numpy as np
import pytest

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


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))


def _run_both(params, cfg, mesh, reqs, smax=64, slots=4, **kw):
    """The same mix through a single-device and a sharded paged
    server; rids align because submission order is identical."""
    solo = ContinuousServer(params, cfg, slots=slots, smax=smax,
                            paged=True, **kw)
    shard = ContinuousServer(params, cfg, slots=slots, smax=smax,
                             paged=True, mesh=mesh, **kw)
    for srv in (solo, shard):
        for r in reqs:
            srv.submit(**r)
    return solo.run(), shard.run(), shard


GREEDY = [dict(prompt=[3, 1, 4], max_new=9),
          dict(prompt=[2, 7], max_new=5),
          dict(prompt=[5, 6, 7, 8, 9], max_new=12),
          dict(prompt=[1], max_new=7),
          dict(prompt=[9, 9, 2, 1], max_new=3),
          dict(prompt=[4, 4], max_new=10)]


# -- equivalence -------------------------------------------------------------

def test_greedy_matches_single_device(params, mesh):
    outs, outm, _ = _run_both(params, CFG, mesh, GREEDY)
    assert outs == outm


def test_sampled_matches_single_device(params, mesh):
    """Per-slot sampling folds the request key, not the shard — the
    (key, pos, row=0) categorical draw must survive shard_map."""
    reqs = [dict(prompt=[3, 1, 4], max_new=8, temperature=0.9,
                 key=jax.random.PRNGKey(7)),
            dict(prompt=[2, 7, 9], max_new=8, temperature=0.7,
                 key=jax.random.PRNGKey(8)),
            dict(prompt=[5, 5], max_new=6, temperature=1.3,
                 key=jax.random.PRNGKey(9)),
            dict(prompt=[6, 1], max_new=6)]
    outs, outm, _ = _run_both(params, CFG, mesh, reqs)
    assert outs == outm


def test_gqa_rope_matches_single_device(mesh):
    """n_kv_heads=2 over tp=2: ONE kv head per shard — the sharpest
    per-shard head-slicing case the fused/gather kernels must get
    right."""
    p = tfm.init_params(GQA_ROPE, jax.random.PRNGKey(5))
    reqs = [dict(prompt=[3, 1, 4, 1, 5], max_new=7),
            dict(prompt=[2, 7], max_new=5),
            dict(prompt=[1, 2, 3], max_new=6)]
    outs, outm, _ = _run_both(p, GQA_ROPE, mesh, reqs, smax=48)
    assert outs == outm


def test_int8_matches_single_device(params, mesh):
    """int8 pools: the [num_blocks, nkv] scale sidecars shard over tp
    with their heads; per-head absmax quantization is shard-local, so
    quantized values are identical to the single-device pools."""
    outs, outm, _ = _run_both(params, CFG, mesh, GREEDY,
                              kv_dtype="int8")
    assert outs == outm


def test_spec_matches_single_device(params, mesh):
    """Speculative decode on the mesh: the shard_map verify window and
    per-shard rollback must accept exactly the drafts the solo server
    accepts (greedy + sampled mix)."""
    reqs = GREEDY[:4] + [dict(prompt=[3, 1, 4], max_new=8,
                              temperature=0.9,
                              key=jax.random.PRNGKey(7))]
    outs, outm, srv = _run_both(params, CFG, mesh, reqs,
                                spec=True, spec_k=3)
    assert outs == outm
    assert srv.spec_stats()["steps"] > 0


def test_spec_draft_model_matches_single_device(params, mesh):
    """Draft-model speculation: the draft shares the serving mesh
    (its dense rows: slots over dp, heads over tp) while the target runs the shard_map
    paged path."""
    dcfg = tfm.TransformerConfig(vocab=64, d_model=16, n_heads=2,
                                 head_dim=8, n_layers=1, d_ff=32)
    dparams = tfm.init_params(dcfg, jax.random.PRNGKey(3))
    reqs = GREEDY[:3]
    outs, outm, _ = _run_both(params, CFG, mesh, reqs, spec=True,
                              spec_k=3, spec_draft="model",
                              draft_params=dparams, draft_cfg=dcfg)
    assert outs == outm


def test_prefix_reuse_across_dp_shards(params, mesh):
    """Requests sharing a prefix land on BOTH dp shards (4 slots over
    dp=2): the radix chain published by one shard's request must be
    reusable by slots on the other shard — the dp-replicated block
    axis (whole-block splice writes are identical on every replica) is
    what makes that sound."""
    pre = list(range(1, 33))                    # 2 blocks of 16
    # 8 requests over 4 slots: the first wave publishes the prefix
    # chain on retire, the second wave (admitting into slots on BOTH
    # dp shards) must match it
    reqs = [dict(prompt=pre + [40 + i], max_new=6) for i in range(8)]
    outs, outm, srv = _run_both(params, CFG, mesh, reqs)
    assert outs == outm
    st = srv.cache_stats()
    assert st["tokens_matched"] >= 32
    assert st["prefill_tokens_saved"] >= 32


def test_table_residency_replicated_matches(params, mesh):
    """hpx.serving.mesh.table_residency=replicated: same tokens, the
    device table is just placed replicated instead of row-sharded."""
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.serving.mesh.table_residency", "replicated")
    try:
        outs, outm, srv = _run_both(params, CFG, mesh, GREEDY[:3])
        assert outs == outm
        assert srv._table_residency == "replicated"
    finally:
        rc.set("hpx.serving.mesh.table_residency", "sharded")


# -- validation / accounting -------------------------------------------------

def test_sharded_paged_validates(params, mesh):
    # slots must divide over dp (the shared decode-mesh contract,
    # reworded for slots)
    with pytest.raises(ValueError, match="slots"):
        ContinuousServer(params, CFG, slots=3, smax=64, paged=True,
                         mesh=mesh)
    # MoE decodes expert-parallel now; the remaining refusal is
    # expert-count divisibility over the expert axis
    moe = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                head_dim=8, n_layers=2, d_ff=64,
                                n_experts=3)
    mp = tfm.init_params(moe, jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match=r"n_experts \(3\).*tp=2"):
        ContinuousServer(mp, moe, slots=4, smax=64, paged=True,
                         mesh=mesh)
    # bogus residency knob
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.serving.mesh.table_residency", "bogus")
    try:
        with pytest.raises(ValueError, match="table_residency"):
            ContinuousServer(params, CFG, slots=4, smax=64, paged=True,
                             mesh=mesh)
    finally:
        rc.set("hpx.serving.mesh.table_residency", "sharded")


def test_per_dp_shard_occupancy(params, mesh):
    """cache_stats() breaks occupancy down by dp shard (slots map to
    shards by index range); totals reconcile with the global mapped
    count while requests are live."""
    srv = ContinuousServer(params, CFG, slots=4, smax=64, paged=True,
                           mesh=mesh)
    for i in range(4):
        srv.submit([10 + i] * 20, max_new=4)
    ticks = 0
    while srv.step():
        ticks += 1
        st = srv.cache_stats()
        from hpx_tpu.cache.page_table import occupancy
        assert (st["occupancy_dp0"] + st["occupancy_dp1"]
                == occupancy(srv._tables))
    assert ticks > 0
    st = srv.cache_stats()
    assert "occupancy_dp0" in st and "occupancy_dp1" in st
