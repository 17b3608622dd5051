"""Expert-parallel MoE decode on the mesh: ContinuousServer with an
MoE model and mesh=(dp, tp) must emit BYTE-IDENTICAL tokens to the
single-device MoE server — greedy and sampled, over blocks of 16 rows
and of 4, spec on and off.  Experts shard over the "tp" axis (no
dedicated "ep" axis in the default serving mesh); decode routing rides
moe_ffn's tiled all_to_all with the drop-free auto capacity (cf =
n_experts), so token identity is exact, not approximate.

Also pinned here: the /serving{...}/moe/* counters advance from real
decode stats, the capacity-factor knob re-keys at most the decode
step/verify programs (compile guard), and the declared
hpx.serving.moe.capacity_factor tunable accepts a probe and replays
deterministically from its flight state.
"""

import jax
import numpy as np
import pytest

from hpx_tpu.core.config import runtime_config
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer

MOE = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64, n_experts=4,
                            moe_top_k=2, moe_capacity=4.0)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(MOE, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def mesh():
    return jax.sharding.Mesh(
        np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))


GREEDY = [dict(prompt=[3, 1, 4], max_new=9),
          dict(prompt=[2, 7], max_new=5),
          dict(prompt=[5, 6, 7, 8, 9], max_new=12),
          dict(prompt=[1], max_new=7)]

SAMPLED = [dict(prompt=[3, 1, 4], max_new=8, temperature=0.9,
                key=jax.random.PRNGKey(7)),
           dict(prompt=[2, 7, 9], max_new=8, temperature=0.7,
                key=jax.random.PRNGKey(8)),
           dict(prompt=[6, 1], max_new=6)]


def _run_both(params, mesh, reqs, **kw):
    solo = ContinuousServer(params, MOE, slots=4, smax=64, **kw)
    shard = ContinuousServer(params, MOE, slots=4, smax=64, mesh=mesh,
                             **kw)
    for srv in (solo, shard):
        for r in reqs:
            srv.submit(**r)
    return solo.run(), shard.run(), shard


# -- token identity ----------------------------------------------------------

# the default block of 16 rows, and blocks of 4: every dp shard then
# writes rows of EVERY slot across block seams into its copy of the
# pools (`_write_rows`), and a verify window spans blocks
BLOCKS = pytest.mark.parametrize("block_size", [None, 4],
                                 ids=["paged", "block4"])


@BLOCKS
def test_greedy_matches_single_device(params, mesh, block_size):
    kw = dict(block_size=block_size)
    outs, outm, srv = _run_both(params, mesh, GREEDY, **kw)
    assert outs == outm
    assert srv._ep_axis == "tp" and srv._ep_size == 2


@BLOCKS
def test_sampled_matches_single_device(params, mesh, block_size):
    kw = dict(block_size=block_size)
    outs, outm, _ = _run_both(params, mesh, SAMPLED, **kw)
    assert outs == outm


@BLOCKS
def test_spec_matches_single_device(params, mesh, block_size):
    """Speculative decode over expert-parallel MoE: the verify window
    routes every draft position through the same drop-free exchange,
    so accepts match the solo server exactly."""
    kw = dict(block_size=block_size)
    reqs = GREEDY[:3] + SAMPLED[:1]
    outs, outm, srv = _run_both(params, mesh, reqs, spec=True,
                                spec_k=3, **kw)
    assert outs == outm
    assert srv.spec_stats()["steps"] > 0


# -- counters ----------------------------------------------------------------

def test_moe_counters_advance(params, mesh):
    from hpx_tpu.svc import performance_counters as pc
    _, _, srv = _run_both(params, mesh, GREEDY)
    inst = srv.counter_instance
    names = pc.discover_counters(f"/serving{{locality#*/{inst}}}/moe/*")
    leaves = {n.split("/moe/", 1)[1] for n in names}
    assert {"tokens-routed", "tokens-dropped"} <= leaves
    assert {f"expert#{e}/occupancy" for e in range(MOE.n_experts)} \
        <= leaves
    got = {n.split("/moe/", 1)[1]: pc.query_counter(n).value
           for n in names}
    # every decoded token claims top_k expert slots; auto capacity
    # (cf = n_experts) is drop-free
    assert got["tokens-routed"] > 0
    assert got["tokens-dropped"] == 0
    assert any(got[f"expert#{e}/occupancy"] > 0
               for e in range(MOE.n_experts))
    assert all(got[f"expert#{e}/occupancy"] <= 1.0 + 1e-6
               for e in range(MOE.n_experts))


# -- compile guard -----------------------------------------------------------

def test_capacity_pct_rekeys_bounded_programs(params, mesh):
    """Reloading hpx.serving.moe.capacity_factor re-keys ONLY the
    decode step program family (step/verify; chunk/probe/splice are
    knob-independent): a warm server picks up the knob at the flush
    boundary and mints at most 5 new programs."""
    rc = runtime_config()
    srv = ContinuousServer(params, MOE, slots=4, smax=64, mesh=mesh)
    for r in GREEDY:
        srv.submit(**r)
    base_out = srv.run()
    warm = srv._prog_misses
    rc.set("hpx.serving.moe.capacity_factor", "200")
    try:
        for r in GREEDY:
            srv.submit(**r)
        out2 = srv.run()
        assert srv._moe_capacity_pct == 200
        assert srv._prog_misses - warm <= 5
        # cf 2.0 with T=slots tokens per step never overflows here,
        # so tokens stay byte-identical to the drop-free run
        assert list(out2.values()) == list(base_out.values())
    finally:
        rc.set("hpx.serving.moe.capacity_factor", "0")
