"""Real-chip leg of the expert-parallel MoE decode contract: the
(dp, tp)-mesh MoE server must emit byte-identical tokens to the
single-device MoE server ON THE REAL TPU MESH — the tiled all_to_all
exchange compiled for the actual interconnect, not the CPU-smoke
host-device emulation tests/test_sharded_moe_serving.py pins.

Needs four chips (see conftest: skipped under JAX_PLATFORMS=cpu, a
failure where the chip is missing). Each identity run prints a line
stamped with the live backend.
"""

import json

import jax
import numpy as np
import pytest

pytestmark = pytest.mark.tpu


def _provenance(engine, **extra):
    line = {"engine": engine,
            "onchip": jax.default_backend() == "tpu"}
    line.update(extra)
    print(json.dumps(line), flush=True)


class TestExpertParallelDecodeOnChip:
    @pytest.mark.parametrize("block_size", [None, 4],
                             ids=["paged", "block4"])
    def test_mesh_matches_single_device(self, block_size):
        from hpx_tpu.models import transformer as tfm
        from hpx_tpu.models.serving import ContinuousServer
        if len(jax.devices()) < 4:
            pytest.skip("needs >=4 TPU devices for the 2x2 mesh")
        cfg = tfm.TransformerConfig(
            vocab=256, d_model=128, n_heads=8, head_dim=16,
            n_layers=2, d_ff=256, n_experts=4, moe_top_k=2,
            moe_capacity=4.0)
        params = tfm.init_params(cfg, jax.random.PRNGKey(0))
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
        reqs = [dict(prompt=[3, 1, 4], max_new=9),
                dict(prompt=[2, 7], max_new=5),
                dict(prompt=[5, 6, 7, 8, 9], max_new=12),
                dict(prompt=[3, 1, 4], max_new=8, temperature=0.9,
                     key=jax.random.PRNGKey(7))]
        kw = dict(block_size=block_size)
        outs = {}
        for name, m in (("single", None), ("mesh", mesh)):
            srv = ContinuousServer(params, cfg, slots=4, smax=64,
                                   mesh=m, **kw)
            for r in reqs:
                srv.submit(**r)
            outs[name] = srv.run()
            if m is not None:
                assert srv._ep_axis == "tp" and srv._ep_size == 2
                assert srv._moe_routed > 0
                assert srv._moe_dropped == 0     # auto = drop-free
        assert outs["single"] == outs["mesh"]
        _provenance("serving_moe_tpu_identity",
                    block_size=block_size, identical=True)
