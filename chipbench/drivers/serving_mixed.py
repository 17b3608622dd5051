"""Driver of the served cells whose model has layers that DIFFER (full
and window attention with their own head counts and RoPEs, dense and
sparse FFNs): `ContinuousServer.submit()` and `.step()` under a mix of
chipbench/traffic_gen/requests.py, through the same loop as
drivers/serving.py (`Loop`, the gap numbers and the sample are its).

Its own: `build_cfg` (a Hugging Face `laguna` config.json to the
program's `TransformerConfig`), `make_params` (the weights on the
device from --seed, in the program's layout) and the counters of the
two mechanisms: the window block group's occupancy (`cache_stats()`),
the distinct experts a decode step hits (`moe_stats()`), and the bytes
the traced steps' attention and experts had to read
(chipbench/opcount_mixed.py).
"""

from __future__ import annotations

import math
import statistics

import numpy as np

from chipbench import opcount_mixed
from chipbench.adapters import serving_adapter as adapter
from chipbench.drivers.serving import (Loop, _p90, _sample, gap_checks,
                                       gap_numbers)
from chipbench.harness import seed_key


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import RopeSpec, TransformerConfig
    hd, n = conf["head_dim"], conf["num_hidden_layers"]

    def rope(kind):
        r = conf["rope_parameters"][kind]
        part = float(r.get("partial_rotary_factor", 1))
        yarn = r.get("rope_type") == "yarn"
        return RopeSpec(
            theta=float(r["rope_theta"]),
            rotary_dim=0 if part == 1 else int(round(part * hd)),
            factor=float(r["factor"]) if yarn else 1.0,
            original_max=int(r.get("original_max_position_embeddings", 0)),
            beta_fast=float(r.get("beta_fast", 32)),
            beta_slow=float(r.get("beta_slow", 1)),
            attention_factor=float(r.get("attention_factor", 1.0)))
    kinds = conf["layer_types"][:n]
    ropes = {k: rope(k) for k in set(kinds)}
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], head_dim=hd, n_layers=n,
        d_ff=conf["intermediate_size"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            conf["dtype"]],
        n_kv_heads=conf["num_key_value_heads"], rope=True,
        norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        mlp="swiglu", tied=bool(conf["tie_word_embeddings"]),
        attn_gate=bool(conf["gating"]),
        layer_heads=tuple(conf["num_attention_heads_per_layer"][:n]),
        layer_window=tuple(conf["sliding_window"]
                           if k == "sliding_attention" else 0
                           for k in kinds),
        layer_rope=tuple(ropes[k] for k in kinds),
        layer_sparse=tuple(k == "sparse"
                           for k in conf["mlp_layer_types"][:n]),
        n_experts=conf["num_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_shared_d_ff=conf["shared_expert_intermediate_size"],
        moe_router="sigmoid", moe_renorm=True,
        moe_scale=float(conf["moe_routed_scaling_factor"]))


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type, one jitted program a kind of layer. Normal /
    sqrt(fan_in); norm scales 1 + 0.02 normal, so that a path that
    drops one shows."""
    import functools
    import jax
    import jax.numpy as jnp
    d, hd, nkv, dt = cfg.d_model, cfg.head_dim, cfg.kv_heads, cfg.dtype
    s = 1.0 / math.sqrt(d)

    def nrm(k, shape, scale, shift=0.0):
        return (jax.random.normal(k, shape, jnp.float32) * scale
                + shift).astype(dt)

    def mlp(ks, f, lead=()):
        return {"w1": nrm(ks[0], lead + (d, f), s),
                "w3": nrm(ks[1], lead + (d, f), s),
                "w2": nrm(ks[2], lead + (f, d), 1.0 / math.sqrt(f))}

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def layer(k, nh, sparse):
        ks = jax.random.split(k, 16)
        out = {"ln1": nrm(ks[0], (d,), 0.02, 1.0),
               "wq": nrm(ks[1], (d, nh, hd), s),
               "wkv": nrm(ks[2], (2, d, nkv, hd), s),
               "wo": nrm(ks[3], (nh, hd, d), 1.0 / math.sqrt(nh * hd)),
               "ln2": nrm(ks[4], (d,), 0.02, 1.0)}
        if cfg.attn_gate:
            out["wgate"] = nrm(ks[5], (d, nh), s)
        if not sparse:
            return dict(out, **mlp(ks[6:9], cfg.d_ff))
        moe = dict(mlp(ks[6:9], cfg.moe_d_ff, (cfg.n_experts,)),
                   wg=nrm(ks[9], (d, cfg.n_experts), s))
        if cfg.moe_shared_d_ff:
            moe["shared"] = mlp(ks[10:13], cfg.moe_shared_d_ff)
        return dict(out, moe=moe)

    @jax.jit
    def outer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (nrm(k1, (cfg.vocab, d), s), nrm(k2, (cfg.vocab, d), s),
                nrm(k3, (d,), 0.02, 1.0))

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, head, ln_f = outer(keys[0])
    params = {"emb": emb, "ln_f": ln_f,
              "layers": [layer(keys[1 + i], cfg.heads(i), cfg.sparse(i))
                         for i in range(cfg.n_layers)]}
    if not cfg.tied:
        params["head"] = head
    return params


class MixedLoop(Loop):
    """`Loop`, which in a traced run also notes the window block
    group's occupancy at every step."""

    def __init__(self, ctx, server, gen):
        super().__init__(ctx, server, gen)
        self.win_used_sum, self.win_used_n = 0.0, 0

    def step(self) -> None:
        super().step()
        if self.ctx.trace:      # costs host time: the traced run only
            st = self.server.cache_stats()
            if st.get("window_num_blocks"):
                self.win_used_sum += (st["window_in_use"]
                                      / st["window_num_blocks"])
                self.win_used_n += 1


def _moe_delta(server, since: dict) -> dict:
    now = server.moe_stats()
    return {k: now[k] - since[k] for k in now}


def run(ctx) -> dict:
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    cfg = build_cfg(conf)
    windows = [cfg.window(i) for i in range(cfg.n_layers)]
    n_sparse = sum(cfg.sparse(i) for i in range(cfg.n_layers))
    item = np.dtype(cfg.dtype).itemsize
    with count_compiles() as setup_c:
        params = make_params(cfg, ctx.seed)
        server = ContinuousServer(params, cfg, **conf["server"])
        gen = ctx.generator(vocab=cfg.vocab)
        loop = MixedLoop(ctx, server, gen)
        t_built = ctx.clock()
        loop.warm()
        t_warm = ctx.clock()
        loop.ramp()
        t_open = loop.flush()
    setup_s = ctx.setup_seconds(t_open)
    ctx.say(phase="setup", setup_s=setup_s,
            devices_ready_s=ctx.devices_ready_s,
            built_s=t_built - ctx.t_start, warmed_s=t_warm - ctx.t_start,
            fresh_compiles=int(setup_c), cache_hits=setup_c.hits,
            ramp_steps=loop.steps,
            paged_kernel=server.hbm_read_stats().get("paged_kernel"),
            block_size=server.block_size)

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
    loop.occ_sum, loop.occ_n = 0.0, 0
    moe_open = server.moe_stats()
    freed_open = server.cache_stats().get("window_blocks_freed", 0)
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
            window_blocks_freed=stats_close.get("window_blocks_freed", 0)
            - freed_open,
            window_prefix_refused=stats_close.get("window_prefix_refused"),
            **ctx.stalls(loop.step_ends[steps_open:], t_open, block=32))
    end_to_end = {"setup_s": setup_s, "out_tok_s": tokens / window_s}
    if tpots:
        end_to_end["tpot_p90_ms"] = _p90(tpots)
    if ttfts:
        end_to_end["ttft_p90_ms"] = _p90(ttfts)
    counters = {
        "batch_occupancy": loop.occ_sum / max(1, loop.occ_n),
        "kv_blocks_used": (loop.kv_used_sum / loop.kv_used_n
                           if loop.kv_used_n else None),
        "kv_window_blocks_used": (loop.win_used_sum / loop.win_used_n
                                  if loop.win_used_n else None),
        "experts_hit": (moe_win["experts_hit_sum"] / moe_win["steps"]
                        if moe_win["steps"] else None),
        "n_experts": cfg.n_experts,
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        counters["traced_steps"] = len(positions)
        counters["traced_kv_bytes"] = sum(
            opcount_mixed.paged_decode_attention_bytes(
                p, windows, cfg.kv_heads, cfg.head_dim, item)
            for p in positions)
        counters["traced_moe_steps"] = moe_tr["steps"]
        counters["traced_gmm_bytes"] = opcount_mixed.routed_expert_bytes(
            moe_tr["experts_hit_sum"], n_sparse, cfg.d_model,
            cfg.moe_d_ff, item)
        counters["traced_expert_bytes"] = opcount_mixed.expert_bytes(
            moe_tr["experts_hit_sum"], int(moe_tr["steps"]), n_sparse,
            cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.moe_shared_d_ff,
            item)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 16)), ctx.seed)
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
        numbers = gap_numbers(gaps)
        ctx.say(phase="reference", requests=len(sample),
                tokens_compared=int(gaps.size), seconds=ctx.clock() - t_ref,
                **numbers)
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
    """The CONTROL's reading of the numbers `run` compared: the
    reference in the nearest precision below the configuration's, in
    the program's place (see drivers/serving.py `control`)."""
    params, requests, length, out_max = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(
        params, ctx.config, requests, length, out_max,
        quant=ctx.config["control_precision"])
    numbers = gap_numbers(gaps)
    return {"checks": {n: v for n, v, _ in gap_checks(numbers, ctx.config)},
            "numbers": numbers, "raw": {"gap": gaps}}
