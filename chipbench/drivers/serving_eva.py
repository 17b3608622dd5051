"""Driver of the served cells whose model keeps its K/V rows at TWO
grains (EVA attention: the exact rows of one aligned window a slot and
one pooled summary row for every chunk behind it, under one softmax)
and predicts several bytes a position: `ContinuousServer.submit()` and
`.step()` under a mix of chipbench/traffic_gen/requests.py, through the
same loop as drivers/serving.py (`Loop`, the sample and `gap_checks`
are its; `_worst_block` drivers/serving_sparse.py's).

Its own: `build_cfg` (a Hugging Face `evabyte` config.json to the
program's `TransformerConfig`), `make_params` (the weights on the
device from --seed, in the program's layout), one more warm-up request
that decodes across a window boundary (the roll's program), and the
counters of the mechanism: the rows the decode steps' walks read over
the positions they had behind them, the rolls (`cache_stats()`), and
the bytes the traced steps' walks had to read
(chipbench/opcount_eva.py). `correct` holds the served bytes to the
float32 reference (`gap_mean`) and the SUMMARY rows themselves to it
(`summary_rel_err`: `eva_summaries()` of the live slots that have
consumed the most bytes against the reference's pooling of the same
bytes; `summary_rows_miscounted`: slots whose count of visible rows is
not the reference's). `control`: the controls of `correct`, each judged
on its own numbers.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

from chipbench import opcount_eva
from chipbench.adapters import serving_adapter as adapter
from chipbench.drivers.serving import Loop, _p90, _sample, gap_checks
from chipbench.drivers.serving_sparse import _worst_block
from chipbench.harness import seed_key


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import TransformerConfig
    d, heads = conf["hidden_size"], conf["num_attention_heads"]
    if conf["attention_class"] != "eva" or conf["attention_bias"] \
            or conf["rope_scaling"] is not None or d % heads \
            or conf["num_key_value_heads"] != heads \
            or conf["tie_word_embeddings"]:
        raise ValueError("plain multi-head EVA attention, no bias, no "
                         "rope scaling, an untied head: no other form "
                         "is built here")
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=d, n_heads=heads,
        head_dim=d // heads, n_layers=conf["num_hidden_layers"],
        d_ff=conf["intermediate_size"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            conf["dtype"]],
        norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        norm_unit_offset=bool(conf["norm_add_unit_offset"]),
        mlp="swiglu", tied=False, rope=True,
        rope_theta=float(conf["rope_theta"]),
        layer_mixer=("eva",) * conf["num_hidden_layers"],
        eva_chunk=conf["chunk_size"], eva_window=conf["window_size"],
        pred_heads=conf["num_pred_heads"],
        logits_f32=bool(conf["fp32_logits"]))


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type, one jitted program a layer. Normal / sqrt(fan_in);
    the norms' parameters g (the scale is 1 + g) 0.1 normal, so that a
    dropped offset or scale shows; phi and mu unit normal, float32, so
    that the pooling weights are uneven and mu moves the summaries'
    scores (the published initialisation, 0.01275 clipped to one sigma,
    pools almost evenly)."""
    import jax
    import jax.numpy as jnp
    d, dt, f = cfg.d_model, cfg.dtype, cfg.d_ff
    h, hd = cfg.n_heads, cfg.head_dim
    s = 1.0 / math.sqrt(d)
    f32 = jnp.float32

    def nrm(k, shape, scale, dtype=dt):
        return (jax.random.normal(k, shape, f32) * scale).astype(dtype)

    @jax.jit
    def layer(k):
        ks = jax.random.split(k, 12)
        return {"ln1": nrm(ks[0], (d,), 0.1),
                "eva": {"wq": nrm(ks[1], (d, h * hd), s),
                        "wk": nrm(ks[2], (d, h * hd), s),
                        "wv": nrm(ks[3], (d, h * hd), s),
                        "phi": nrm(ks[4], (h, hd), 1.0, f32),
                        "mu": nrm(ks[5], (h, hd), 1.0, f32),
                        "wo": nrm(ks[6], (h * hd, d),
                                  1.0 / math.sqrt(h * hd))},
                "ln2": nrm(ks[7], (d,), 0.1),
                "w1": nrm(ks[8], (d, f), s), "w3": nrm(ks[9], (d, f), s),
                "w2": nrm(ks[10], (f, d), 1.0 / math.sqrt(f))}

    @jax.jit
    def outer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (nrm(k1, (cfg.vocab, d), s), nrm(k2, (d,), 0.1),
                nrm(k3, (cfg.pred_heads * cfg.vocab, d), s))

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, ln_f, head = outer(keys[0])
    return {"emb": emb, "ln_f": ln_f, "head": head,
            "layers": [layer(keys[1 + i]) for i in range(cfg.n_layers)]}


WINDOW_KEYS = ("eva_rolls", "eva_blocks_freed", "eva_rows_attended",
               "eva_tokens_behind", "prefill_chunks", "prefill_rows")


def _delta(now: dict, since: dict) -> dict:
    return {k: now.get(k, 0) - since.get(k, 0) for k in WINDOW_KEYS}


def _numbers(gaps, errs, miscounted) -> dict:
    parted = gaps[gaps > 0]
    return {"gap_mean": float(gaps.mean()), "gap_max": float(gaps.max()),
            "parted_tokens": int(parted.size),
            "summary_rel_err": float(errs.max()) if errs.size else 0.0,
            "summary_rows_miscounted": int(miscounted)}


def _warm_roll(server, cfg) -> None:
    """One request that DECODES across a window boundary: the roll's
    program, which no short warm-up prompt reaches."""
    rng = np.random.default_rng(1)
    server.submit([int(t) for t in rng.integers(
        1, cfg.vocab, cfg.eva_window - 2)], max_new=4)
    while server.step():
        pass
    adapter.flush(server)
    adapter.drain(server)


def run(ctx) -> dict:
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    cfg = build_cfg(conf)
    with count_compiles() as setup_c:
        params = make_params(cfg, ctx.seed)
        server = ContinuousServer(params, cfg, **conf["server"])
        gen = ctx.generator(vocab=cfg.vocab)
        loop = Loop(ctx, server, gen)
        t_built = ctx.clock()
        loop.warm()
        _warm_roll(server, cfg)
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
            heads_per_copy=stats_open.get("heads_per_copy"),
            block_size=server.block_size,
            prefill_chunk=stats_open.get("prefill_chunk"),
            num_blocks=stats_open.get("num_blocks"))

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
    acct_open = server.step_accounts()[-1].n
    loop.occ_sum, loop.occ_n = 0.0, 0
    t_after = float(traffic.get("trace_after_s", 2.0))
    t_len = float(traffic.get("trace_seconds", 3.0))
    traced, positions, rolls_tr = "no", [], None
    with count_compiles() as win_c:
        while True:
            loop.step()
            el = ctx.clock() - t_open
            if ctx.trace and traced == "no" and el >= t_after:
                loop.flush()
                rolls_tr = server.cache_stats()
                ctx.trace_start()
                loop.traced_positions = []
                traced, t_tr = "on", ctx.clock()
            elif traced == "on" and ctx.clock() - t_tr >= t_len:
                loop.flush()
                ctx.trace_stop()
                rolls_tr = _delta(server.cache_stats(), rolls_tr)
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
            eva_summary_rows=stats_close.get("eva_summary_rows"),
            eva_exact_rows=stats_close.get("eva_exact_rows"),
            eva_prefix_refused=stats_close.get("eva_prefix_refused"),
            eva_reprefills=stats_close.get("eva_reprefills"),
            **win, worst_block=_worst_block(server, acct_open),
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
        "eva_rows_attended": win["eva_rows_attended"],
        "eva_tokens_behind": win["eva_tokens_behind"],
        "eva_rolls": win["eva_rolls"],
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        counters["traced_steps"] = len(positions)
        counters["traced_rolls"] = rolls_tr["eva_rolls"]
        counters["traced_eva_bytes"] = sum(
            opcount_eva.walk_bytes(
                p, cfg.n_layers, cfg.n_heads, cfg.head_dim,
                np.dtype(cfg.dtype).itemsize, cfg.eva_chunk,
                cfg.eva_window) for p in positions)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 8)), ctx.seed)
    # the summary rows of the live slots that have consumed the MOST
    # bytes (the most rows to compare), and the bytes they pool
    live = server.live_positions()
    longest = sorted(live, key=live.get)[
        -int(traffic.get("check_summaries", 4)):]
    states = []
    for s in sorted(longest):
        toks, ks, vs = server.eva_summaries(s)
        states.append((toks, ks.shape[0], ks, vs))
    adapter.release(server)
    del server, loop
    ref = ctx.reference()
    checks = [("window_compiles", int(win_c), 0),
              ("requests_short", short, 0),
              ("requests_failed", failed_win, 0)]
    raw = None
    requests = [(t.prompt, t.tokens) for t in sample]
    if sample and states:
        t_ref = ctx.clock()
        gaps = ref.served_gaps(params, conf, requests)
        errs, miscounted = ref.summary_errors(params, conf, states)
        numbers = _numbers(gaps, errs, miscounted)
        ctx.say(phase="reference", requests=len(sample),
                tokens_compared=int(gaps.size),
                summaries_compared=[int(n) for _, n, _, _ in states],
                summary_tokens=[len(t) for t, _, _, _ in states],
                seconds=ctx.clock() - t_ref, **numbers)
        checks += gap_checks(numbers, conf)
        raw = {"gap": gaps, "summary_err": errs}
    else:
        checks.append(("requests_compared_missing", 1, 0))
    return {"end_to_end": end_to_end, "counters": counters, "checks": checks,
            "attempted": len(in_win) + failed_win, "failed": failed_win,
            "memory_peak_bytes": memory_peak, "raw": raw,
            "control_inputs": (params, requests, states)}


def control(ctx, outcome) -> dict:
    """The CONTROLS' reading of the numbers `run` compared, the
    reference in each form of `control_precision` in the program's
    place: "int8", the nearest precision below the bfloat16 the
    configuration serves in, and "window_only", the forward in which no
    summary is attended (the mechanism left out). EACH has to come out
    not correct on its own numbers. What goes into the program's place:
    the readings of a control that passes every limit, if there is one
    (the harness then reads `correct` and fails); else, for each
    number, the reading of the control that number exists to catch
    (`held_by`). `numbers` keeps each control's own, with its
    verdict."""
    params, requests, states = outcome["control_inputs"]
    ref, conf = ctx.reference(), ctx.config
    readings, raw = {}, {}
    for quant in conf["control_precision"]:
        gaps = ref.served_gaps(params, conf, requests, quant=quant)
        errs, miscounted = ref.summary_errors(params, conf, states,
                                              quant=quant)
        readings[quant] = _numbers(gaps, errs, miscounted)
        raw["gap_" + quant], raw["summary_err_" + quant] = gaps, errs
    limits = {n: lim for n, _, lim in gap_checks(
        next(iter(readings.values())), conf)}
    for r in readings.values():
        r["correct"] = all(r[n] <= lim for n, lim in limits.items())
    passing = [q for q, r in readings.items() if r["correct"]]
    held = conf["correct"]["held_by"]
    checks = {n: readings[passing[0] if passing else held[n]][n]
              for n in limits}
    return {"checks": checks, "numbers": readings, "raw": raw}
