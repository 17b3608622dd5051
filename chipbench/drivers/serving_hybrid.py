"""Driver of the served cells whose model mixes RECURRENT layers (Kimi
Delta Attention: a per-slot float32 state and a conv tail, no
positions) with latent-attention layers (MLA, NoPE: one cached row a
token) and holds one chip's SHARE of the experts:
`ContinuousServer.submit()` and `.step()` under a mix of
chipbench/traffic_gen/requests.py, through the same loop as
drivers/serving.py (`Loop`, the gap numbers and the sample are its).

Its own: `build_cfg` (a Hugging Face `kimi_linear` config.json, with
the configuration's `experts_held` / `router_experts`, to the program's
`TransformerConfig`), `make_params` (the weights on the device from
--seed, in the program's layout; `balance_router`: every selection bias
balanced over seeded tokens, so that each seed routes the same work)
and the counters of the mechanisms: the state's bytes a slot
(`cache_stats()`), the distinct experts of the held share a decode
step hits (`moe_stats()`), and the bytes the traced steps' state
updates, latent reads and experts had to move
(chipbench/opcount_hybrid.py). `correct` also holds the recurrent state
itself to the float32 the configuration states: a few live slots'
states (`ContinuousServer.recurrent_state()`) against the reference's
state of the same tokens (`state_rel_err`). `control`: the two controls
of `correct`, read together.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

from chipbench import opcount_hybrid
from chipbench.adapters import serving_adapter as adapter
from chipbench.drivers.serving import (Loop, _p90, _sample, gap_checks,
                                       gap_numbers)
from chipbench.harness import seed_key


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import TransformerConfig
    n = conf["num_hidden_layers"]
    lin = conf["linear_attn_config"]
    kda = {i - 1 for i in lin["kda_layers"]}            # 1-indexed
    full = {i - 1 for i in lin["full_attn_layers"]}
    if kda | full != set(range(n)) or kda & full:
        raise ValueError("kda_layers and full_attn_layers must split "
                         f"the {n} layers")
    held = tuple(conf["experts_held"])
    if held[1] - held[0] != conf["num_experts"]:
        raise ValueError("experts_held does not hold num_experts experts")
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], n_layers=n,
        d_ff=conf["intermediate_size"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            conf["dtype"]],
        norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        mlp="swiglu", tied=bool(conf["tie_word_embeddings"]),
        layer_mixer=tuple("kda" if i in kda else "mla" for i in range(n)),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_rank=lin["head_dim"],
        mla_rank=conf["kv_lora_rank"],
        mla_nope_dim=conf["qk_nope_head_dim"],
        mla_rope_dim=conf["qk_rope_head_dim"],
        mla_v_dim=conf["v_head_dim"],
        layer_sparse=tuple(i >= conf["first_k_dense_replace"]
                           for i in range(n)),
        n_experts=conf["router_experts"], moe_held=held,
        moe_top_k=conf["num_experts_per_token"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_shared_d_ff=conf["num_shared_experts"]
        * conf["moe_intermediate_size"],
        moe_router=conf["moe_router_activation_func"],
        moe_renorm=bool(conf["moe_renormalize"]), moe_bias=True,
        moe_scale=float(conf["routed_scaling_factor"]))


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type, one jitted program a kind of layer. Normal /
    sqrt(fan_in); norm scales 1 + 0.02 normal, so that a path that
    drops one shows; A_log, dt_bias, b_g and the selection bias in
    float32 (A_log = log U(1, 16), dt_bias the inverse softplus of a
    log-uniform dt in [0.001, 0.1]; the selection bias 0.01 normal and
    then BALANCED, `balance_router`)."""
    import functools
    import jax
    import jax.numpy as jnp
    d, dt = cfg.d_model, cfg.dtype
    s = 1.0 / math.sqrt(d)
    f32 = jnp.float32

    def nrm(k, shape, scale, shift=0.0, dtype=dt):
        return (jax.random.normal(k, shape, f32) * scale
                + shift).astype(dtype)

    def mlp(ks, f, lead=()):
        return {"w1": nrm(ks[0], lead + (d, f), s),
                "w3": nrm(ks[1], lead + (d, f), s),
                "w2": nrm(ks[2], lead + (f, d), 1.0 / math.sqrt(f))}

    def kda(ks):
        h, hd, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_rank
        step = jnp.exp(jax.random.uniform(
            ks[5], (h, hd), f32, math.log(0.001), math.log(0.1)))
        return {"wqkv": nrm(ks[0], (d, 3, h, hd), s),
                "conv": nrm(ks[1], (cfg.kda_conv, 3, h, hd),
                            1.0 / math.sqrt(cfg.kda_conv)),
                "wf1": nrm(ks[2], (d, r), s),
                "wf2": nrm(ks[3], (r, h, hd), 1.0 / math.sqrt(r)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[4], (h,), f32, 1.0, 16.0)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "wb": nrm(ks[6], (d, h), s),
                "wg1": nrm(ks[7], (d, r), s),
                "wg2": nrm(ks[8], (r, h, hd), 1.0 / math.sqrt(r)),
                "bg": nrm(ks[9], (h, hd), 0.1, dtype=f32),
                "onorm": nrm(ks[10], (hd,), 0.02, 1.0),
                "wo": nrm(ks[11], (h, hd, d), 1.0 / math.sqrt(h * hd))}

    def mla(ks):
        h, r = cfg.n_heads, cfg.mla_rank
        return {"wq": nrm(ks[0], (d, h, cfg.mla_nope_dim
                                  + cfg.mla_rope_dim), s),
                "wdkv": nrm(ks[1], (d, r + cfg.mla_rope_dim), s),
                "kvnorm": nrm(ks[2], (r,), 0.02, 1.0),
                "wuk": nrm(ks[3], (r, h, cfg.mla_nope_dim),
                           1.0 / math.sqrt(r)),
                "wuv": nrm(ks[4], (r, h, cfg.mla_v_dim),
                           1.0 / math.sqrt(r)),
                "wo": nrm(ks[5], (h, cfg.mla_v_dim, d),
                          1.0 / math.sqrt(h * cfg.mla_v_dim))}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def layer(k, kind, sparse):
        ks = jax.random.split(k, 32)
        out = {"ln1": nrm(ks[0], (d,), 0.02, 1.0),
               kind: (kda if kind == "kda" else mla)(ks[1:13]),
               "ln2": nrm(ks[13], (d,), 0.02, 1.0)}
        if not sparse:
            return dict(out, **mlp(ks[14:17], cfg.d_ff))
        moe = dict(mlp(ks[14:17], cfg.moe_d_ff, (cfg.experts_held,)),
                   wg=nrm(ks[17], (d, cfg.n_experts), s),
                   bias=nrm(ks[18], (cfg.n_experts,), 0.01, dtype=f32))
        if cfg.moe_shared_d_ff:
            moe["shared"] = mlp(ks[19:22], cfg.moe_shared_d_ff)
        return dict(out, moe=moe)

    @jax.jit
    def outer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (nrm(k1, (cfg.vocab, d), s), nrm(k2, (cfg.vocab, d), s),
                nrm(k3, (d,), 0.02, 1.0))

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, head, ln_f = outer(keys[0])
    return balance_router(
        {"emb": emb, "head": head, "ln_f": ln_f,
         "layers": [layer(keys[1 + i], cfg.mixer(i), cfg.sparse(i))
                    for i in range(cfg.n_layers)]}, cfg, seed)


# the tokens the selection bias is balanced on, and the bias's steps
BALANCE = {"sequences": 48, "tokens": 128, "iterations": 100,
           "step": (0.05, 0.002)}


def balance_router(params, cfg, seed: int):
    """The weights with every sparse layer's selection bias balanced,
    as the balancing rule it was trained under leaves it (a bias falls
    by a step while its expert is chosen more often than the mean, and
    rises while less; the step shrinks from the first of `step` to the
    second): over `sequences` x `tokens` seeded random tokens, through
    the float32 reference layer by layer, each layer balanced before
    the next sees its output. Hidden states under random weights share
    a direction, so a random router favours the same few experts for
    every token, WHICH ones by the seed: unbalanced, the held share's
    experts hit a step (the expert bytes a step reads) differed from
    seed to seed by more than the bound on `out_tok_s`. A function of
    the seed alone."""
    import jax
    import jax.numpy as jnp
    from chipbench.reference import kimi_linear as reference
    first, last = BALANCE["step"]
    iters, top_k = BALANCE["iterations"], cfg.moe_top_k

    @jax.jit
    def balanced(s, bias):
        n, e = s.shape

        def body(i, b):
            step = first * (last / first) ** (i / (iters - 1.0))
            sel = s + b
            kth = jax.lax.top_k(sel, top_k)[0][:, -1:]
            load = jnp.sum(sel >= kth, 0) * (e / (top_k * n))
            return b - step * jnp.sign(load - 1.0)
        return jax.lax.fori_loop(0, iters, body, bias)

    def visit(lp, s):
        bias = balanced(s.reshape(-1, s.shape[-1]), lp["moe"]["bias"])
        sparse.append(dict(lp, moe=dict(lp["moe"], bias=bias)))
        return sparse[-1]

    sparse = []
    tokens = jax.random.randint(
        jax.random.fold_in(seed_key(seed), 1),
        (BALANCE["sequences"], BALANCE["tokens"]), 1, cfg.vocab)
    reference.forward(
        params, {"rms_norm_eps": cfg.norm_eps, "kv_lora_rank": cfg.mla_rank,
                 "qk_nope_head_dim": cfg.mla_nope_dim,
                 "num_experts_per_token": top_k,
                 "routed_scaling_factor": cfg.moe_scale,
                 "experts_held": list(cfg.moe_held)},
        tokens, visit=visit)
    done = iter(sparse)
    return dict(params, layers=[next(done) if "moe" in lp else lp
                                for lp in params["layers"]])


def _moe_delta(server, since: dict) -> dict:
    now = server.moe_stats()
    return {k: now[k] - since[k] for k in now}


def run(ctx) -> dict:
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    cfg = build_cfg(conf)
    n_kda = sum(cfg.mixer(i) == "kda" for i in range(cfg.n_layers))
    n_mla = cfg.n_layers - n_kda
    n_sparse = sum(cfg.sparse(i) for i in range(cfg.n_layers))
    item = np.dtype(cfg.dtype).itemsize
    with count_compiles() as setup_c:
        params = make_params(cfg, ctx.seed)
        server = ContinuousServer(params, cfg, **conf["server"])
        gen = ctx.generator(vocab=cfg.vocab)
        loop = Loop(ctx, server, gen)
        t_built = ctx.clock()
        loop.warm()
        t_warm = ctx.clock()
        loop.ramp()
        t_open = loop.flush()
    setup_s = ctx.setup_seconds(t_open)
    stats_open = server.cache_stats()
    ctx.say(phase="setup", setup_s=setup_s,
            devices_ready_s=ctx.devices_ready_s,
            built_s=t_built - ctx.t_start, warmed_s=t_warm - ctx.t_start,
            fresh_compiles=int(setup_c), cache_hits=setup_c.hits,
            ramp_steps=loop.steps,
            paged_kernel=server.hbm_read_stats().get("paged_kernel"),
            block_size=server.block_size,
            state_bytes=stats_open.get("state_bytes"),
            latent_num_blocks=stats_open.get("num_blocks"))

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
    loop.occ_sum, loop.occ_n = 0.0, 0
    moe_open = server.moe_stats()
    t_after = float(traffic.get("trace_after_s", 2.0))
    t_len = float(traffic.get("trace_seconds", 3.0))
    traced, positions, moe_tr = "no", [], None
    with count_compiles() as win_c:
        while True:
            loop.step()
            el = ctx.clock() - t_open
            if ctx.trace and traced == "no" and el >= t_after:
                loop.flush()
                moe_tr = server.moe_stats()
                ctx.trace_start()
                loop.traced_positions = []
                traced, t_tr = "on", ctx.clock()
            elif traced == "on" and ctx.clock() - t_tr >= t_len:
                loop.flush()
                ctx.trace_stop()
                moe_tr = _moe_delta(server, moe_tr)
                positions, loop.traced_positions = loop.traced_positions, None
                traced = "done"
            if el >= ctx.seconds and traced != "on":
                break
        t_close = loop.flush()
    window_s = t_close - t_open
    tokens = loop.received() - tok_open
    moe_win = _moe_delta(server, moe_open)
    stats_close = server.cache_stats()
    in_win = [t for t in loop.finished[n_fin_open:] if not t.failed]
    firsts = [t for t in loop.finished + list(loop.active.values())
              if t.t_first is not None and t_open <= t.t_first <= t_close]
    tpots = [1e3 * (t.t_last - t.t_first) / (len(t.tokens) - 1)
             for t in in_win if len(t.tokens) > 1]
    ttfts = [1e3 * (t.t_first - t.t_submit) for t in firsts]
    failed_win = sum(1 for t in loop.finished[n_fin_open:] if t.failed)
    short = sum(1 for t in in_win if len(t.tokens) != t.max_new)
    ctx.say(phase="window", window_s=window_s, steps=loop.steps - steps_open,
            tokens=tokens, requests_finished=len(in_win),
            first_tokens=len(firsts), requests_failed=failed_win,
            window_compiles=int(win_c), flushes=2,
            ttft_p50_ms=statistics.median(ttfts) if ttfts else None,
            tpot_p50_ms=statistics.median(tpots) if tpots else None,
            moe_steps=moe_win["steps"], moe_routed=moe_win["routed"],
            moe_dropped=moe_win["dropped"],
            state_resets=stats_close.get("state_resets", 0)
            - stats_open.get("state_resets", 0),
            state_prefix_refused=stats_close.get("state_prefix_refused"),
            state_reprefills=stats_close.get("state_reprefills"),
            **ctx.stalls(loop.step_ends[steps_open:], t_open, block=32))
    end_to_end = {"setup_s": setup_s, "out_tok_s": tokens / window_s}
    if tpots:
        end_to_end["tpot_p90_ms"] = _p90(tpots)
    if ttfts:
        end_to_end["ttft_p90_ms"] = _p90(ttfts)
    state_bytes = stats_close.get("state_bytes")
    counters = {
        "batch_occupancy": loop.occ_sum / max(1, loop.occ_n),
        "kv_blocks_used": (loop.kv_used_sum / loop.kv_used_n
                           if loop.kv_used_n else None),
        "experts_hit": (moe_win["experts_hit_sum"] / moe_win["steps"]
                        if moe_win["steps"] else None),
        "n_experts": cfg.experts_held,
        "state_mb_per_slot": (state_bytes / server.slots / 1e6
                              if state_bytes else None),
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        counters["traced_steps"] = len(positions)
        counters["traced_state_bytes"] = sum(
            opcount_hybrid.kda_state_bytes(
                len(p), n_kda, cfg.kda_heads, cfg.kda_head_dim)
            for p in positions)
        counters["traced_latent_bytes"] = sum(
            opcount_hybrid.latent_row_bytes(
                p, n_mla, cfg.mla_rank, cfg.mla_rope_dim, item)
            for p in positions)
        counters["traced_moe_steps"] = moe_tr["steps"]
        counters["traced_gmm_bytes"] = opcount_hybrid.routed_expert_bytes(
            moe_tr["experts_hit_sum"], n_sparse, cfg.d_model,
            cfg.moe_d_ff, item)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 12)), ctx.seed)
    # the recurrent state of a few live slots, and the tokens it holds
    live = sorted(server.live_positions())
    pick = np.random.default_rng([ctx.seed, 78]).permutation(len(live))
    states = [server.recurrent_state(live[i])
              for i in sorted(pick[:int(traffic.get("check_states", 4))])]
    adapter.release(server)
    del server, loop
    ref = ctx.reference()
    length, out_max = gen.frame()
    checks = [("window_compiles", int(win_c), 0),
              ("requests_short", short, 0),
              ("requests_failed", failed_win, 0),
              ("moe_tokens_dropped", moe_win["dropped"], 0)]
    raw = None
    if sample:
        t_ref = ctx.clock()
        gaps = ref.served_gaps(
            params, conf, [(t.prompt, t.tokens) for t in sample],
            length, out_max)
        errs = ref.state_errors(params, conf, states)
        numbers = _numbers(gaps, errs)
        ctx.say(phase="reference", requests=len(sample),
                tokens_compared=int(gaps.size), states_compared=len(states),
                state_tokens=[len(t) for t, _ in states],
                seconds=ctx.clock() - t_ref, **numbers)
        checks += gap_checks(numbers, conf)
        raw = {"gap": gaps, "state_err": errs}
    else:
        checks.append(("requests_compared_missing", 1, 0))
    return {"end_to_end": end_to_end, "counters": counters, "checks": checks,
            "attempted": len(in_win) + failed_win, "failed": failed_win,
            "memory_peak_bytes": memory_peak, "raw": raw,
            "control_inputs": (params, [(t.prompt, t.tokens) for t in sample],
                               length, out_max, states)}


def _numbers(gaps, state_errs) -> dict:
    """`gap_numbers`, and `state_rel_err`: the farthest a sampled
    slot's recurrent state (first layer) lies from the float32
    reference's state of the same tokens, |S - S_ref| / |S_ref|."""
    return dict(gap_numbers(gaps), state_rel_err=float(np.max(state_errs)))


def control(ctx, outcome) -> dict:
    """The CONTROLS' reading of the numbers `run` compared, the
    reference in each precision of `control_precision` in the program's
    place (see drivers/serving.py `control`): "int8", the nearest below
    the bfloat16 the configuration serves in, and "state_bf16", the
    nearest below the float32 it states for the KDA state. Each has to
    come out not correct; what goes into the program's place is, for
    each number, the SMALLER of the two readings, so that a limit this
    passes over is passed over by both controls. `numbers` keeps each
    control's own."""
    params, requests, length, out_max, states = outcome["control_inputs"]
    ref, readings, raw = ctx.reference(), {}, {}
    for quant in ctx.config["control_precision"]:
        gaps = ref.served_gaps(
            params, ctx.config, requests, length, out_max, quant=quant)
        errs = ref.state_errors(params, ctx.config, states, quant=quant)
        readings[quant] = _numbers(gaps, errs)
        raw["gap_" + quant], raw["state_err_" + quant] = gaps, errs
    names = [n for n, _, _ in gap_checks(next(iter(readings.values())),
                                         ctx.config)]
    return {"checks": {n: min(r[n] for r in readings.values())
                       for n in names},
            "numbers": readings, "raw": raw}
