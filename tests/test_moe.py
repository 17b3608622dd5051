"""MoE FFN with expert parallelism (models/moe.py).

Oracles: with IDENTICAL expert weights and no capacity drops, top-1 MoE
must equal gate_prob * dense_ffn(x) exactly (Switch's output scaling),
and the ep-sharded run must equal the single-shard run bit-for-bit in
f32 (the all_to_all round trip is a permutation).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models.moe import (MoeConfig, init_moe_params, moe_ffn,
                                moe_param_specs)

T, D, F, E = 32, 16, 24, 4


def _params(cfg, identical=False, seed=0):
    p = init_moe_params(cfg, jax.random.PRNGKey(seed))
    if identical:
        for k in ("w1", "b1", "w2"):
            p[k] = jnp.broadcast_to(p[k][:1], p[k].shape)
    return p


def _dense(x, p):
    h = jax.nn.gelu(x @ p["w1"][0] + p["b1"][0])
    return h @ p["w2"][0]


def _x(seed=1):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (T, D), np.float32))


class TestSingleShard:
    def test_top1_identical_experts_equals_scaled_dense(self):
        cfg = MoeConfig(n_experts=E, top_k=1, capacity_factor=8.0,
                        d_model=D, d_ff=F)
        p = _params(cfg, identical=True)
        x = _x()
        out, aux = moe_ffn(x, p, cfg)
        gates = jax.nn.softmax(x @ p["wg"], axis=-1)
        want = jnp.max(gates, axis=-1, keepdims=True) * _dense(x, p)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        assert np.isfinite(float(aux))

    def test_top2_identical_experts(self):
        cfg = MoeConfig(n_experts=E, top_k=2, capacity_factor=8.0,
                        d_model=D, d_ff=F)
        p = _params(cfg, identical=True)
        x = _x(2)
        out, _ = moe_ffn(x, p, cfg)
        gates = jax.nn.softmax(x @ p["wg"], axis=-1)
        top2 = jnp.sort(gates, axis=-1)[:, -2:].sum(-1, keepdims=True)
        want = top2 * _dense(x, p)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)

    def test_capacity_drops_are_finite_and_smaller(self):
        cfg_big = MoeConfig(n_experts=E, top_k=1, capacity_factor=8.0,
                            d_model=D, d_ff=F)
        cfg_tiny = MoeConfig(n_experts=E, top_k=1, capacity_factor=0.25,
                             d_model=D, d_ff=F)
        p = _params(cfg_big)
        x = _x(3)
        full, _ = moe_ffn(x, p, cfg_big)
        cut, _ = moe_ffn(x, p, cfg_tiny)
        assert np.isfinite(np.asarray(cut)).all()
        assert float(jnp.linalg.norm(cut)) < float(jnp.linalg.norm(full))

    def test_grads_reach_every_weight(self):
        cfg = MoeConfig(n_experts=E, top_k=2, capacity_factor=8.0,
                        d_model=D, d_ff=F)
        p = _params(cfg)
        x = _x(4)

        def loss(p):
            out, aux = moe_ffn(x, p, cfg)
            return jnp.sum(out ** 2) + 0.01 * aux

        g = jax.grad(loss)(p)
        for k in ("wg", "w1", "b1", "w2"):
            assert np.isfinite(np.asarray(g[k])).all(), k
            assert float(jnp.abs(g[k]).max()) > 0, k


class TestExpertParallel:
    @pytest.mark.parametrize("top_k", [1, 2])
    def test_sharded_matches_single_shard(self, top_k, devices):
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        ep = 4
        mesh = Mesh(np.array(devices[:ep]), ("ep",))
        cfg = MoeConfig(n_experts=E, top_k=top_k, capacity_factor=8.0,
                        d_model=D, d_ff=F)
        p = _params(cfg, seed=7)
        xs = jnp.asarray(np.random.default_rng(8).standard_normal(
            (ep * T, D), np.float32))        # tokens sharded over ep

        # single-shard oracle: per token block (capacity is per-device,
        # so the oracle processes each device's block independently)
        outs, auxs = [], []
        for i in range(ep):
            o, a = moe_ffn(xs[i * T:(i + 1) * T], p, cfg)
            outs.append(o)
            auxs.append(a)
        want = jnp.concatenate(outs)

        specs = moe_param_specs("ep")
        ps = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in p.items()}
        xsh = jax.device_put(xs, NamedSharding(mesh, P("ep")))

        def body(xc, pc):
            out, aux = moe_ffn(xc, pc, cfg, axis="ep", axis_size=ep)
            return out, jax.lax.pmean(aux, "ep")

        got, aux = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P("ep"), specs),
            out_specs=(P("ep"), P())))(xsh, ps)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(float(aux), float(np.mean(auxs)),
                                   rtol=1e-5)

    def test_sharded_grads_match(self, devices):
        from jax import shard_map
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        ep = 2
        mesh = Mesh(np.array(devices[:ep]), ("ep",))
        cfg = MoeConfig(n_experts=E, top_k=2, capacity_factor=8.0,
                        d_model=D, d_ff=F)
        p = _params(cfg, seed=9)
        xs = jnp.asarray(np.random.default_rng(10).standard_normal(
            (ep * T, D), np.float32))

        def loss_single(p):
            tot = 0.0
            for i in range(ep):
                o, _ = moe_ffn(xs[i * T:(i + 1) * T], p, cfg)
                tot = tot + jnp.sum(o ** 2)
            return tot

        want = jax.grad(loss_single)(p)

        specs = moe_param_specs("ep")
        ps = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
              for k, v in p.items()}
        xsh = jax.device_put(xs, NamedSharding(mesh, P("ep")))

        def loss_sharded(ps, xc):
            o, _ = moe_ffn(xc, ps, cfg, axis="ep", axis_size=ep)
            return jax.lax.psum(jnp.sum(o ** 2), "ep")

        got = jax.jit(shard_map(
            jax.grad(loss_sharded), mesh=mesh,
            in_specs=(specs, P("ep")),
            out_specs=specs))(ps, xsh)
        for k in ("wg", "w1", "b1", "w2"):
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(want[k]),
                rtol=3e-4, atol=3e-4, err_msg=k)

    def test_indivisible_experts_raises(self):
        cfg = MoeConfig(n_experts=3, d_model=D, d_ff=F)
        with pytest.raises(ValueError):
            moe_ffn(_x(), init_moe_params(cfg, jax.random.PRNGKey(0)),
                    cfg, axis="ep", axis_size=2)


class TestCapacityOverflow:
    def test_overflow_drops_deterministic_and_exact_zero(self):
        """Overflow routing is pure argmax over f32 gates — no RNG, no
        nondeterministic reduction — so two runs drop THE SAME tokens,
        and a dropped token (all its claims through the trash row)
        contributes exact-zero output, not merely small."""
        import math
        from hpx_tpu.models.moe import _top_k_dispatch
        cfg = MoeConfig(n_experts=E, top_k=1, capacity_factor=0.25,
                        d_model=D, d_ff=F)
        p = _params(cfg, seed=5)
        x = _x(6)
        out1, _, st1 = moe_ffn(x, p, cfg, return_stats=True)
        out2, _, st2 = moe_ffn(x, p, cfg, return_stats=True)
        np.testing.assert_array_equal(np.asarray(out1),
                                      np.asarray(out2))
        np.testing.assert_array_equal(np.asarray(st1),
                                      np.asarray(st2))
        routed, dropped = float(st1[0]), float(st1[1])
        assert dropped > 0            # the fixture actually overflows
        assert routed + dropped == T * cfg.top_k
        assert float(jnp.max(st1[2:])) <= 1.0 + 1e-6   # occupancy caps
        cap = max(1, math.ceil(T * cfg.top_k
                               * cfg.capacity_factor / E))
        gates = jax.nn.softmax(x @ p["wg"], axis=-1)
        disp, _, _ = _top_k_dispatch(gates, cfg.top_k, cap)
        lost = np.asarray(jnp.sum(disp, axis=(1, 2)) == 0)
        assert lost.any()
        assert (np.asarray(out1)[lost] == 0.0).all()

    def test_bf16_gating_agrees_with_f32(self):
        """Gating always runs in f32 (the xf upcast), so a bf16 expert
        compute makes the SAME routing and drop decisions as f32 —
        stats identical, outputs within bf16 rounding."""
        cfg32 = MoeConfig(n_experts=E, top_k=2, capacity_factor=1.0,
                          d_model=D, d_ff=F, dtype=jnp.float32)
        cfg16 = MoeConfig(n_experts=E, top_k=2, capacity_factor=1.0,
                          d_model=D, d_ff=F, dtype=jnp.bfloat16)
        p = _params(cfg32, seed=11)
        x = _x(12)
        out32, _, st32 = moe_ffn(x, p, cfg32, return_stats=True)
        out16, _, st16 = moe_ffn(x, p, cfg16, return_stats=True)
        np.testing.assert_array_equal(np.asarray(st32),
                                      np.asarray(st16))
        np.testing.assert_allclose(
            np.asarray(out16, np.float32), np.asarray(out32),
            rtol=0.1, atol=0.1)


def test_top_k_exceeding_experts_raises():
    cfg = MoeConfig(n_experts=2, top_k=3, d_model=D, d_ff=F)
    with pytest.raises(ValueError, match="top_k"):
        moe_ffn(_x(), init_moe_params(cfg, jax.random.PRNGKey(0)), cfg)
