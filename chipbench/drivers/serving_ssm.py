"""Driver of the served cells whose model is a STATE-SPACE hybrid:
Mamba-1 selective-scan layers (a per-slot float32 state [d_inner,
d_state] and a conv tail, no positions) beside a few softmax-attention
layers without rotation (K/V pools): `ContinuousServer.submit()` and
`.step()` under a mix of chipbench/traffic_gen/requests.py, through the
same loop as drivers/serving.py (`Loop`, the gap numbers and the sample
are its; `_numbers` is drivers/serving_hybrid.py's, `_worst_block`
drivers/serving_sparse.py's).

Its own: `build_cfg` (a Hugging Face `jamba` config.json to the
program's `TransformerConfig`; the layer order by the family's rule),
`make_params` (the weights on the device from --seed, in the program's
layout) and the counters of the mechanisms: the state's bytes a slot,
the chunks and their real rows, the step() calls an admission waited
(`cache_stats()`), and the bytes the traced steps' state updates and
the traced chunks' scans had to move (chipbench/opcount_ssm.py).
`correct` holds the served tokens to the float32 reference (`gap_mean`)
and the Mamba state itself to the float32 the configuration states
(`state_rel_err`: `recurrent_state()` of the live slots that have
consumed the most tokens against the reference's state of the same
tokens, over the long memories: the quarter of the channels with the
smallest dt bias). `control`: the two controls of `correct`, each
judged on its own numbers.
"""

from __future__ import annotations

import math
import statistics

from chipbench import opcount_ssm
from chipbench.adapters import serving_adapter as adapter
from chipbench.drivers.serving import Loop, _p90, _sample, gap_checks
from chipbench.drivers.serving_hybrid import _numbers
from chipbench.drivers.serving_sparse import _worst_block
from chipbench.harness import seed_key


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import TransformerConfig
    n, d = conf["num_hidden_layers"], conf["hidden_size"]
    heads = conf["num_attention_heads"]
    if conf["num_experts"] != 1 or conf["sliding_window"] is not None \
            or conf["mamba_proj_bias"] or d % heads:
        raise ValueError("one expert (a plain MLP), no window, no "
                         "projection bias: no other form is built here")
    period, offset = conf["attn_layer_period"], conf["attn_layer_offset"]
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=d, n_heads=heads,
        head_dim=d // heads, n_kv_heads=conf["num_key_value_heads"],
        n_layers=n, d_ff=conf["intermediate_size"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            conf["dtype"]],
        norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        mlp="swiglu", tied=bool(conf["tie_word_embeddings"]), rope=False,
        layer_mixer=tuple("attn" if i % period == offset else "mamba"
                          for i in range(n)),
        mamba_d_inner=conf["mamba_expand"] * d,
        mamba_d_state=conf["mamba_d_state"],
        mamba_d_conv=conf["mamba_d_conv"],
        mamba_dt_rank=conf["mamba_dt_rank"],
        mamba_conv_bias=bool(conf["mamba_conv_bias"]))


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type, one jitted program a kind of layer. Normal /
    sqrt(fan_in); norm scales 1 + 0.02 normal and the conv bias 0.1
    normal, so that a path that drops one shows; A_log = log(1..N) a
    channel, dt_bias the inverse softplus of a log-uniform dt in
    [0.001, 0.1], D 1 + 0.02 normal, all three float32."""
    import functools
    import jax
    import jax.numpy as jnp
    d, dt, f = cfg.d_model, cfg.dtype, cfg.d_ff
    s = 1.0 / math.sqrt(d)
    f32 = jnp.float32

    def nrm(k, shape, scale, shift=0.0, dtype=dt):
        return (jax.random.normal(k, shape, f32) * scale
                + shift).astype(dtype)

    def mamba(ks):
        c, n = cfg.mamba_d_inner, cfg.mamba_d_state
        r, taps = cfg.mamba_dt_rank, cfg.mamba_d_conv
        step = jnp.exp(jax.random.uniform(
            ks[5], (c,), f32, math.log(0.001), math.log(0.1)))
        out = {"win": nrm(ks[0], (d, 2 * c), s),
               "conv": nrm(ks[1], (taps, c), 1.0 / math.sqrt(taps)),
               "wx": nrm(ks[2], (c, r + 2 * n), 1.0 / math.sqrt(c)),
               "dt_norm": nrm(ks[6], (r,), 0.02, 1.0),
               "b_norm": nrm(ks[7], (n,), 0.02, 1.0),
               "c_norm": nrm(ks[8], (n,), 0.02, 1.0),
               "wdt": nrm(ks[3], (r, c), 1.0 / math.sqrt(r)),
               "dt_bias": step + jnp.log(-jnp.expm1(-step)),
               "A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                   1, n + 1, dtype=f32))[:, None], (n, c)),
               "D": nrm(ks[9], (c,), 0.02, 1.0, dtype=f32),
               "wo": nrm(ks[4], (c, d), 1.0 / math.sqrt(c))}
        if cfg.mamba_conv_bias:
            out["conv_b"] = nrm(ks[10], (c,), 0.1)
        return {"mamba": out}

    def attn(ks):
        nh, nkv, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        return {"wq": nrm(ks[0], (d, nh, hd), s),
                "wkv": nrm(ks[1], (2, d, nkv, hd), s),
                "wo": nrm(ks[2], (nh, hd, d), 1.0 / math.sqrt(nh * hd))}

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(k, kind):
        ks = jax.random.split(k, 20)
        return {"ln1": nrm(ks[0], (d,), 0.02, 1.0),
                **(mamba if kind == "mamba" else attn)(ks[1:13]),
                "ln2": nrm(ks[13], (d,), 0.02, 1.0),
                "w1": nrm(ks[14], (d, f), s), "w3": nrm(ks[15], (d, f), s),
                "w2": nrm(ks[16], (f, d), 1.0 / math.sqrt(f))}

    @jax.jit
    def outer(k):
        k1, k2 = jax.random.split(k)
        return nrm(k1, (cfg.vocab, d), s), nrm(k2, (d,), 0.02, 1.0)

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, ln_f = outer(keys[0])
    return {"emb": emb, "ln_f": ln_f,
            "layers": [layer(keys[1 + i], cfg.mixer(i))
                       for i in range(cfg.n_layers)]}


WINDOW_KEYS = ("state_resets", "admit_wait_steps", "prefill_chunks",
               "prefill_rows")


def _delta(now: dict, since: dict) -> dict:
    return {k: now.get(k, 0) - since.get(k, 0) for k in WINDOW_KEYS}


def run(ctx) -> dict:
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    cfg = build_cfg(conf)
    n_mamba = sum(cfg.mixer(i) == "mamba" for i in range(cfg.n_layers))
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
            prefill_chunk=stats_open.get("prefill_chunk"),
            state_bytes=stats_open.get("state_bytes"),
            num_blocks=stats_open.get("num_blocks"))

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
    acct_open = server.step_accounts()[-1].n
    loop.occ_sum, loop.occ_n = 0.0, 0
    t_after = float(traffic.get("trace_after_s", 2.0))
    t_len = float(traffic.get("trace_seconds", 3.0))
    traced, positions, chunks_tr = "no", [], None
    with count_compiles() as win_c:
        while True:
            loop.step()
            el = ctx.clock() - t_open
            if ctx.trace and traced == "no" and el >= t_after:
                loop.flush()
                chunks_tr = server.cache_stats()
                ctx.trace_start()
                loop.traced_positions = []
                traced, t_tr = "on", ctx.clock()
            elif traced == "on" and ctx.clock() - t_tr >= t_len:
                loop.flush()
                ctx.trace_stop()
                chunks_tr = _delta(server.cache_stats(), chunks_tr)
                positions, loop.traced_positions = loop.traced_positions, None
                traced = "done"
            if el >= ctx.seconds and traced != "on":
                break
        t_close = loop.flush()
    window_s = t_close - t_open
    tokens = loop.received() - tok_open
    steps = loop.steps - steps_open
    stats_close = server.cache_stats()
    win = _delta(stats_close, stats_open)
    in_win = [t for t in loop.finished[n_fin_open:] if not t.failed]
    firsts = [t for t in loop.finished + list(loop.active.values())
              if t.t_first is not None and t_open <= t.t_first <= t_close]
    tpots = [1e3 * (t.t_last - t.t_first) / (len(t.tokens) - 1)
             for t in in_win if len(t.tokens) > 1]
    ttfts = [1e3 * (t.t_first - t.t_submit) for t in firsts]
    failed_win = sum(1 for t in loop.finished[n_fin_open:] if t.failed)
    short = sum(1 for t in in_win if len(t.tokens) != t.max_new)
    ctx.say(phase="window", window_s=window_s, steps=steps,
            tokens=tokens, requests_finished=len(in_win),
            first_tokens=len(firsts), requests_failed=failed_win,
            window_compiles=int(win_c), flushes=2,
            ttft_p50_ms=statistics.median(ttfts) if ttfts else None,
            tpot_p50_ms=statistics.median(tpots) if tpots else None,
            blocks_in_use=stats_close.get("in_use"),
            state_prefix_refused=stats_close.get("state_prefix_refused"),
            state_reprefills=stats_close.get("state_reprefills"),
            **win, worst_block=_worst_block(server, acct_open),
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
        "state_mb_per_slot": (state_bytes / server.slots / 1e6
                              if state_bytes else None),
        "admit_wait_steps": (win["admit_wait_steps"] / win["state_resets"]
                             if win["state_resets"] else None),
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        c, n = cfg.mamba_d_inner, cfg.mamba_d_state
        counters["traced_steps"] = len(positions)
        counters["traced_state_bytes"] = sum(
            opcount_ssm.mamba_state_bytes(len(p), n_mamba, c, n)
            for p in positions)
        counters["traced_chunks"] = chunks_tr["prefill_chunks"]
        counters["traced_scan_bytes"] = opcount_ssm.mamba_scan_bytes(
            chunks_tr["prefill_rows"], chunks_tr["prefill_chunks"],
            n_mamba, c, n)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 16)), ctx.seed)
    # the Mamba state of the live slots that have consumed the MOST
    # tokens, and the tokens it holds: a state carried below float32
    # strays farthest where it has accumulated longest
    live = server.live_positions()
    longest = sorted(live, key=live.get)[
        -int(traffic.get("check_states", 4)):]
    states = [server.recurrent_state(s) for s in sorted(longest)]
    adapter.release(server)
    del server, loop
    ref = ctx.reference()
    length, out_max = gen.frame()
    checks = [("window_compiles", int(win_c), 0),
              ("requests_short", short, 0),
              ("requests_failed", failed_win, 0)]
    raw = None
    requests = [(t.prompt, t.tokens) for t in sample]
    if sample and states:
        t_ref = ctx.clock()
        gaps = ref.served_gaps(params, conf, requests, length, out_max)
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
            "control_inputs": (params, requests, length, out_max, states)}


def control(ctx, outcome) -> dict:
    """The CONTROLS' reading of the numbers `run` compared, the
    reference in each precision of `control_precision` in the program's
    place: "int8", the nearest below the bfloat16 the configuration
    serves in, and "state_bf16", the nearest below the float32 it
    states for the Mamba state. EACH has to come out not correct on its
    own numbers. What goes into the program's place: the readings of a
    control that passes every limit, if there is one (the harness then
    reads `correct` and fails); else, for each number, the reading of
    the control that number exists to catch (`held_by`). `numbers`
    keeps each control's own, with its verdict."""
    params, requests, length, out_max, states = outcome["control_inputs"]
    ref, conf = ctx.reference(), ctx.config
    readings, raw = {}, {}
    for quant in conf["control_precision"]:
        gaps = ref.served_gaps(params, conf, requests, length, out_max,
                               quant=quant)
        errs = ref.state_errors(params, conf, states, quant=quant)
        readings[quant] = _numbers(gaps, errs)
        raw["gap_" + quant], raw["state_err_" + quant] = gaps, errs
    limits = {n: lim for n, _, lim in gap_checks(
        next(iter(readings.values())), conf)}
    for r in readings.values():
        r["correct"] = all(r[n] <= lim for n, lim in limits.items())
    passing = [q for q, r in readings.items() if r["correct"]]
    held = conf["correct"]["held_by"]
    checks = {n: readings[passing[0] if passing else held[n]][n]
              for n in limits}
    return {"checks": checks, "numbers": readings, "raw": raw}
