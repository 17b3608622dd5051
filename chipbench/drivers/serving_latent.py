"""Driver of the served cells whose model caches LATENT rows with a
rotary part in every layer (DeepSeek-V2's MLA: a low-rank query, one
rotated key shared by 128 heads) and holds one routing GROUP of a
device-limited router's experts: `ContinuousServer.submit()` and
`.step()` under a mix of chipbench/traffic_gen/shared_docs.py (pinned
documents served from the prefix tree, a fresh question each), through
the same loop as drivers/serving.py (`Loop`, the gap numbers and the
sample are its; the window's shape is drivers/serving_hybrid.py's).

Its own: `build_cfg` (a Hugging Face `deepseek_v2` config.json, with
the configuration's `experts_held` / `router_experts`, to the program's
`TransformerConfig`), `make_params` (the weights on the device from
--seed, in the program's layout; `balance_router`: what the seeded
tokens' mean hidden state gives every router column removed, so that
each seed routes the same work) and the counters of the mechanisms:
prompt tokens the tree served over prompt tokens admitted
(`cache_stats()`), the assignments that fell to the held group
(`moe_stats()`), the rows and operations the traced steps' latent
walks needed (chipbench/opcount_latent.py), and `doc_rows_recomputed`:
the document rows of the window's requests that the tree did NOT
serve (limit 0: with a document recomputed the cell is another cell).
`control`: the two controls of `correct`, read together.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

from chipbench import opcount_hybrid, opcount_latent
from chipbench.adapters import serving_adapter as adapter
from chipbench.drivers.serving import (Loop, _p90, _sample, gap_checks,
                                       gap_numbers)
from chipbench.drivers.serving_hybrid import _moe_delta
from chipbench.harness import seed_key


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import RopeSpec, TransformerConfig
    n = conf["num_hidden_layers"]
    held = tuple(conf["experts_held"])
    if held[1] - held[0] != conf["n_routed_experts"]:
        raise ValueError("experts_held does not hold n_routed_experts")
    if conf["topk_method"] != "group_limited_greedy" \
            or conf["scoring_func"] != "softmax":
        raise ValueError("this driver builds a softmax router with a "
                         "group-limited greedy choice")
    sc = conf["rope_scaling"]
    m_all = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    m_one = 0.1 * sc["mscale"] * math.log(sc["factor"]) + 1.0
    rope = RopeSpec(theta=float(conf["rope_theta"]),
                    factor=float(sc["factor"]),
                    original_max=int(sc["original_max_position_embeddings"]),
                    beta_fast=float(sc["beta_fast"]),
                    beta_slow=float(sc["beta_slow"]),
                    attention_factor=m_one / m_all)
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], n_layers=n,
        d_ff=conf["intermediate_size"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            conf["dtype"]],
        norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        mlp="swiglu", tied=bool(conf["tie_word_embeddings"]),
        layer_mixer=("mla",) * n, layer_rope=(rope,) * n,
        mla_rank=conf["kv_lora_rank"], mla_q_rank=conf["q_lora_rank"],
        mla_nope_dim=conf["qk_nope_head_dim"],
        mla_rope_dim=conf["qk_rope_head_dim"],
        mla_v_dim=conf["v_head_dim"], mla_mscale=m_all,
        layer_sparse=tuple(i >= conf["first_k_dense_replace"]
                           for i in range(n)),
        n_experts=conf["router_experts"], moe_held=held,
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_shared_d_ff=conf["n_shared_experts"]
        * conf["moe_intermediate_size"],
        moe_router="softmax", moe_renorm=bool(conf["norm_topk_prob"]),
        moe_scale=float(conf["routed_scaling_factor"]),
        moe_n_group=conf["n_group"], moe_topk_group=conf["topk_group"])


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type, one jitted program a kind of layer. Normal /
    sqrt(fan_in); norm scales 1 + 0.02 normal, so that a path that
    drops one shows; the router's weights then BALANCED
    (`balance_router`)."""
    import functools
    import jax
    import jax.numpy as jnp
    d, dt = cfg.d_model, cfg.dtype
    s = 1.0 / math.sqrt(d)
    f32 = jnp.float32

    def nrm(k, shape, scale, shift=0.0):
        return (jax.random.normal(k, shape, f32) * scale
                + shift).astype(dt)

    def mlp(ks, f, lead=()):
        return {"w1": nrm(ks[0], lead + (d, f), s),
                "w3": nrm(ks[1], lead + (d, f), s),
                "w2": nrm(ks[2], lead + (f, d), 1.0 / math.sqrt(f))}

    def mla(ks):
        h, r, rq = cfg.n_heads, cfg.mla_rank, cfg.mla_q_rank
        dq = cfg.mla_nope_dim + cfg.mla_rope_dim
        return {"wdq": nrm(ks[0], (d, rq), s),
                "qnorm": nrm(ks[1], (rq,), 0.02, 1.0),
                "wuq": nrm(ks[2], (rq, h, dq), 1.0 / math.sqrt(rq)),
                "wdkv": nrm(ks[3], (d, r + cfg.mla_rope_dim), s),
                "kvnorm": nrm(ks[4], (r,), 0.02, 1.0),
                "wuk": nrm(ks[5], (r, h, cfg.mla_nope_dim),
                           1.0 / math.sqrt(r)),
                "wuv": nrm(ks[6], (r, h, cfg.mla_v_dim),
                           1.0 / math.sqrt(r)),
                "wo": nrm(ks[7], (h, cfg.mla_v_dim, d),
                          1.0 / math.sqrt(h * cfg.mla_v_dim))}

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(k, sparse):
        ks = jax.random.split(k, 24)
        out = {"ln1": nrm(ks[0], (d,), 0.02, 1.0), "mla": mla(ks[1:9]),
               "ln2": nrm(ks[9], (d,), 0.02, 1.0)}
        if not sparse:
            return dict(out, **mlp(ks[10:13], cfg.d_ff))
        moe = dict(mlp(ks[10:13], cfg.moe_d_ff, (cfg.experts_held,)),
                   wg=nrm(ks[13], (d, cfg.n_experts), s))
        if cfg.moe_shared_d_ff:
            moe["shared"] = mlp(ks[14:17], cfg.moe_shared_d_ff)
        return dict(out, moe=moe)

    @jax.jit
    def outer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (nrm(k1, (cfg.vocab, d), s), nrm(k2, (cfg.vocab, d), s),
                nrm(k3, (d,), 0.02, 1.0))

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, head, ln_f = outer(keys[0])
    return {"emb": emb, "head": head, "ln_f": ln_f,
            "layers": [layer(keys[1 + i], cfg.sparse(i))
                       for i in range(cfg.n_layers)]}


# the tokens the router's weights are balanced on
BALANCE = {"sequences": 48, "tokens": 128}


def balance_router(params, conf: dict, seed: int):
    """The weights with every sparse layer's router BALANCED: from each
    column of W_g what the seeded tokens' MEAN router input gives it is
    removed (W_g <- W_g - m (m . W_g) / |m|^2, m the mean of RMSNorm_2's
    output over `sequences` x `tokens` seeded random tokens), through
    the float32 reference layer by layer, each layer balanced before
    the next sees its output. Hidden states under random weights share
    a direction, so an unbalanced random router sends every token the
    same way, WHICH way by the seed: this chip's group would lie in
    nearly every token's three groups or in none. This model has no
    selection bias to balance, so the weights are; the tokens' own
    parts still choose, and choose alike over the groups. A function of
    the seed alone."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def balanced(u, wg):
        m = jnp.mean(u.reshape(-1, u.shape[-1]), axis=0)
        w = wg.astype(jnp.float32)
        return (w - jnp.outer(m, m @ w) / jnp.sum(m * m)).astype(wg.dtype)

    def visit(lp, u):
        sparse.append(dict(lp, moe=dict(
            lp["moe"], wg=balanced(u, lp["moe"]["wg"]))))
        return sparse[-1]

    sparse = []
    tokens = jax.random.randint(
        jax.random.fold_in(seed_key(seed), 1),
        (BALANCE["sequences"], BALANCE["tokens"]), 1, conf["vocab_size"])
    _reference().forward(params, conf, tokens, visit=visit)
    done = iter(sparse)
    return dict(params, layers=[next(done) if "moe" in lp else lp
                                for lp in params["layers"]])


def _reference():
    """chipbench/reference/deepseek_v2.py, the file the configuration
    names, as ONE module: `run`, `control` and `balance_router` share
    its compiled layers and the hidden rows it keeps for a control."""
    from chipbench.reference import deepseek_v2
    return deepseek_v2


def _prefix_delta(now: dict, since: dict) -> dict:
    return {k: now[k] - since[k]
            for k in ("prefill_tokens_saved", "prefill_tokens_computed")}


def run(ctx) -> dict:
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    cfg = build_cfg(conf)
    n_sparse = sum(cfg.sparse(i) for i in range(cfg.n_layers))
    item = np.dtype(cfg.dtype).itemsize
    with count_compiles() as setup_c:
        params = balance_router(make_params(cfg, ctx.seed), conf, ctx.seed)
        t_made = ctx.clock()
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
            weights_s=t_made - ctx.t_start,
            built_s=t_built - ctx.t_start, warmed_s=t_warm - ctx.t_start,
            ramp_s=t_open - t_warm, fresh_compiles=int(setup_c),
            cache_hits=setup_c.hits, ramp_steps=loop.steps,
            paged_kernel=server.hbm_read_stats().get("paged_kernel"),
            block_size=server.block_size,
            prefill_chunk=server.prefill_chunk,
            latent_num_blocks=stats_open.get("num_blocks"),
            blocks_in_use=stats_open.get("in_use"),
            shared_blocks=stats_open.get("shared"),
            radix_blocks=stats_open.get("blocks_held"),
            ramp_tokens_computed=stats_open["prefill_tokens_computed"],
            ramp_tokens_saved=stats_open["prefill_tokens_saved"])

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
    issued_open = gen.issued
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
    prefix = _prefix_delta(stats_close, stats_open)
    # every request submitted in the window was admitted in it (64
    # callers, 64 slots: a caller's next request takes the slot its
    # last one left), so the tree should have served their documents
    doc_tokens = sum(gen.document_tokens(k)
                     for k in range(issued_open, gen.issued))
    recomputed = doc_tokens - prefix["prefill_tokens_saved"]
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
            requests_submitted=gen.issued - issued_open,
            first_tokens=len(firsts), requests_failed=failed_win,
            window_compiles=int(win_c), flushes=2,
            ttft_p50_ms=statistics.median(ttfts) if ttfts else None,
            tpot_p50_ms=statistics.median(tpots) if tpots else None,
            moe_steps=moe_win["steps"], moe_routed=moe_win["routed"],
            moe_routed_here=moe_win["routed_here"],
            moe_tokens_here=moe_win["tokens_here"],
            moe_dropped=moe_win["dropped"],
            document_tokens=doc_tokens, **prefix,
            shared_blocks=stats_close.get("shared"),
            blocks_in_use=stats_close.get("in_use"),
            radix_blocks=stats_close.get("blocks_held"),
            evictions=stats_close.get("total_evictions"),
            **ctx.stalls(loop.step_ends[steps_open:], t_open, block=32))
    end_to_end = {"setup_s": setup_s, "out_tok_s": tokens / window_s}
    if tpots:
        end_to_end["tpot_p90_ms"] = _p90(tpots)
    if ttfts:
        end_to_end["ttft_p90_ms"] = _p90(ttfts)
    admitted = sum(prefix.values())
    counters = {
        "batch_occupancy": loop.occ_sum / max(1, loop.occ_n),
        "kv_blocks_used": (loop.kv_used_sum / loop.kv_used_n
                           if loop.kv_used_n else None),
        "experts_hit": (moe_win["experts_hit_sum"] / moe_win["steps"]
                        if moe_win["steps"] else None),
        "n_experts": cfg.experts_held,
        "prompt_tokens_matched": prefix["prefill_tokens_saved"],
        "prompt_tokens_admitted": admitted,
        "moe_routed": moe_win["routed"],
        "moe_routed_here": moe_win["routed_here"],
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        counters["traced_steps"] = len(positions)
        counters["traced_latent_bytes"] = sum(
            opcount_latent.latent_walk_bytes(
                p, cfg.n_layers, cfg.mla_rank, cfg.mla_rope_dim, item)
            for p in positions)
        counters["traced_latent_flops"] = sum(
            opcount_latent.latent_walk_flops(
                p, cfg.n_layers, cfg.n_heads, cfg.mla_rank,
                cfg.mla_rope_dim) for p in positions)
        counters["traced_moe_steps"] = moe_tr["steps"]
        counters["traced_gmm_bytes"] = opcount_hybrid.routed_expert_bytes(
            moe_tr["experts_hit_sum"], n_sparse, cfg.d_model,
            cfg.moe_d_ff, item)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 6)), ctx.seed)
    adapter.release(server)
    del server, loop
    ref = _reference()
    length, out_max = gen.frame()
    checks = [("window_compiles", int(win_c), 0),
              ("requests_short", short, 0),
              ("requests_failed", failed_win, 0),
              ("moe_tokens_dropped", moe_win["dropped"], 0),
              ("doc_rows_recomputed", recomputed, 0)]
    raw = None
    if sample:
        t_ref = ctx.clock()
        gaps = ref.served_gaps(
            params, conf, [(t.prompt, t.tokens) for t in sample],
            length, out_max)
        numbers = gap_numbers(gaps)
        ctx.say(phase="reference", requests=len(sample),
                tokens_compared=int(gaps.size),
                request_tokens=[len(t.prompt) + len(t.tokens)
                                for t in sample],
                seconds=ctx.clock() - t_ref, **numbers)
        checks += gap_checks(numbers, conf)
        raw = {"gap": gaps}
    else:
        checks.append(("requests_compared_missing", 1, 0))
    return {"end_to_end": end_to_end, "counters": counters, "checks": checks,
            "attempted": len(in_win) + failed_win, "failed": failed_win,
            "memory_peak_bytes": memory_peak, "raw": raw,
            "control_inputs": (params, [(t.prompt, t.tokens) for t in sample],
                               length, out_max)}


def control(ctx, outcome) -> dict:
    """The CONTROLS' reading of the numbers `run` compared, the
    reference under each name of `control_precision` in the program's
    place (see drivers/serving.py `control`): "int8", the nearest
    precision below the bfloat16 the configuration serves in, and
    "nope", the float32 forward with the rotary dims left unrotated (a
    program that skipped the rotation). Each has to come out not
    correct; what goes into the program's place is, for each number,
    the SMALLER of the two readings, so that a limit this passes over
    is passed over by both controls. `numbers` keeps each control's
    own."""
    params, requests, length, out_max = outcome["control_inputs"]
    ref, readings, raw = _reference(), {}, {}
    for quant in ctx.config["control_precision"]:
        gaps = ref.served_gaps(params, ctx.config, requests, length,
                               out_max, quant=quant)
        readings[quant] = gap_numbers(gaps)
        raw["gap_" + quant] = gaps
    names = [n for n, _, _ in gap_checks(next(iter(readings.values())),
                                         ctx.config)]
    return {"checks": {n: min(r[n] for r in readings.values())
                       for n in names},
            "numbers": readings, "raw": raw}
