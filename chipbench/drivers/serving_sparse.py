"""Driver of the served cells whose model mixes LEARNED-SPARSE attention
layers (InfLLM-v2: K/V pools, an index of compressed keys beside them,
the blocks a query reads chosen by the query) with decayed
linear-attention layers (lightning: a per-slot float32 state, a
constant decay a head and layer): `ContinuousServer.submit()` and
`.step()` under a mix of chipbench/traffic_gen/requests.py, through the
same loop as drivers/serving.py (`Loop`, the gap numbers and the sample
are its).

Its own: `build_cfg` (a Hugging Face `minicpm_sala` config.json with
the configuration's `sparse_config` and `published_layers`, to the
program's `TransformerConfig`), `make_params` (the weights on the
device from --seed, in the program's layout) and the counters of the
mechanisms: the state's bytes a slot and the rows the decode steps'
sparse layers walked of those they had (`cache_stats()`), and the bytes
the traced steps' walks and state updates had to move
(chipbench/opcount_sparse.py). `correct` holds the served tokens to
the float32 reference (`gap_mean`), the linear state itself to the
float32 the configuration states (`state_rel_err`: a few live slots'
`recurrent_state()` against the reference's state of the same tokens),
and the SELECTION as a selection (`selection_missed`,
`selection_score_gap`: the blocks a few live slots' last decode step
chose in the first sparse layer, `sparse_selection()`, against the
reference's own float32 scores of the same query). `control`: the
three controls of `correct`, each judged on its own numbers.
"""

from __future__ import annotations

import math
import statistics

import numpy as np

from chipbench import opcount_sparse
from chipbench.adapters import serving_adapter as adapter
from chipbench.drivers.serving import (Loop, _p90, _sample, gap_checks,
                                       gap_numbers)
from chipbench.harness import seed_key

KINDS = {"minicpm4": "sparse", "lightning-attn": "lightning"}


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import RopeSpec, TransformerConfig
    mix = tuple(KINDS[m] for m in conf["mixer_types"])
    n = conf["num_hidden_layers"]
    pub = tuple(conf["published_layers"])
    src = conf["source_values"]
    if len(mix) != n or len(pub) != n or [
            src["mixer_types"][i] for i in pub] != conf["mixer_types"]:
        raise ValueError("mixer_types / published_layers do not name "
                         f"{n} layers of the source's mixer_types")
    if conf["attn_use_rope"] or not conf["lightning_use_rope"]:
        raise ValueError("the sparse layers are NoPE and the linear "
                         "ones rotated: no other form is built here")
    sc = conf["sparse_config"]
    rope = RopeSpec(float(conf["rope_theta"]))
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], head_dim=conf["head_dim"],
        n_kv_heads=conf["num_key_value_heads"], n_layers=n,
        d_ff=conf["intermediate_size"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            conf["dtype"]],
        norm="rmsnorm", norm_eps=float(conf["rms_norm_eps"]),
        mlp="swiglu", tied=bool(conf["tie_word_embeddings"]),
        layer_mixer=mix,
        layer_rope=tuple(rope if m == "lightning" else None for m in mix),
        sparse_kernel=sc["kernel_size"], sparse_stride=sc["kernel_stride"],
        sparse_block=sc["block_size"], sparse_topk=sc["topk"],
        sparse_init=sc["init_blocks"], sparse_local=sc["window_size"],
        sparse_dense_len=sc["dense_len"],
        lightning_heads=conf["lightning_nh"],
        lightning_head_dim=conf["lightning_head_dim"],
        qk_norm=bool(conf["qk_norm"]),
        emb_scale=float(conf["scale_emb"]),
        residual_scale=float(conf["scale_depth"])
        / math.sqrt(src["num_hidden_layers"]),
        logit_scale=conf["dim_model_base"] / conf["hidden_size"],
        layer_published=pub, published_layers=src["num_hidden_layers"])


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type, one jitted program a kind of layer. Normal /
    sqrt(fan_in); every norm scale 1 + 0.02 normal, so that a path that
    drops one shows."""
    import functools
    import jax
    import jax.numpy as jnp
    d, f, dt = cfg.d_model, cfg.d_ff, cfg.dtype
    s = 1.0 / math.sqrt(d)

    def nrm(k, shape, scale, shift=0.0):
        return (jax.random.normal(k, shape, jnp.float32) * scale
                + shift).astype(dt)

    def mixer(ks, kind):
        if kind == "sparse":
            h, hd = cfg.n_heads, cfg.head_dim
            out = {"wq": nrm(ks[0], (d, h * hd), s),
                   "wkv": nrm(ks[1], (d, 2 * cfg.kv_heads * hd), s)}
        else:
            h, hd = cfg.lightning_heads, cfg.lightning_head_dim
            out = {"wq": nrm(ks[0], (d, h * hd), s),
                   "wk": nrm(ks[6], (d, h * hd), s),
                   "wv": nrm(ks[7], (d, h * hd), s),
                   "onorm": nrm(ks[1], (h * hd,), 0.02, 1.0)}
        return dict(out, qnorm=nrm(ks[2], (hd,), 0.02, 1.0),
                    knorm=nrm(ks[3], (hd,), 0.02, 1.0),
                    wg=nrm(ks[4], (d, h * hd), s),
                    wo=nrm(ks[5], (h * hd, d), 1.0 / math.sqrt(h * hd)))

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(k, kind):
        ks = jax.random.split(k, 12)
        return {"ln1": nrm(ks[0], (d,), 0.02, 1.0),
                kind: mixer(ks[1:9], kind),
                "ln2": nrm(ks[11], (d,), 0.02, 1.0),
                "w1": nrm(ks[8], (d, f), s), "w3": nrm(ks[9], (d, f), s),
                "w2": nrm(ks[10], (f, d), 1.0 / math.sqrt(f))}

    @jax.jit
    def outer(k):
        k1, k2, k3 = jax.random.split(k, 3)
        return (nrm(k1, (cfg.vocab, d), s), nrm(k2, (cfg.vocab, d), s),
                nrm(k3, (d,), 0.02, 1.0))

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, head, ln_f = outer(keys[0])
    return {"emb": emb, "head": head, "ln_f": ln_f,
            "layers": [layer(keys[1 + i], cfg.mixer(i))
                       for i in range(cfg.n_layers)]}


def _delta(now: dict, since: dict, keys) -> dict:
    return {k: now[k] - since[k] for k in keys}


SPARSE_KEYS = ("sparse_steps", "sparse_blocks_selected",
               "sparse_rows_walked", "sparse_rows_live")


def run(ctx) -> dict:
    import jax
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles

    conf, traffic = ctx.config, ctx.traffic
    cfg = build_cfg(conf)
    kinds = [cfg.mixer(i) for i in range(cfg.n_layers)]
    n_sparse, n_lin = kinds.count("sparse"), kinds.count("lightning")
    item = np.dtype(cfg.dtype).itemsize
    with count_compiles() as setup_c:
        params = make_params(cfg, ctx.seed)
        n_params = sum(int(np.prod(a.shape))
                       for a in jax.tree.leaves(params))
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
            ramp_steps=loop.steps, parameters=n_params,
            paged_kernel=server.hbm_read_stats().get("paged_kernel"),
            block_size=server.block_size,
            prefill_chunk=server.prefill_chunk,
            state_bytes=stats_open.get("state_bytes"),
            num_blocks=stats_open.get("num_blocks"),
            blocks_in_use=stats_open.get("in_use"),
            index_rows=stats_open.get("index_rows"))

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
    acct_open = server.step_accounts()[-1].n
    loop.occ_sum, loop.occ_n = 0.0, 0
    t_after = float(traffic.get("trace_after_s", 2.0))
    t_len = float(traffic.get("trace_seconds", 3.0))
    traced, positions = "no", []
    with count_compiles() as win_c:
        while True:
            loop.step()
            el = ctx.clock() - t_open
            if ctx.trace and traced == "no" and el >= t_after:
                loop.flush()
                ctx.trace_start()
                loop.traced_positions = []
                traced, t_tr = "on", ctx.clock()
            elif traced == "on" and ctx.clock() - t_tr >= t_len:
                loop.flush()
                ctx.trace_stop()
                positions, loop.traced_positions = loop.traced_positions, None
                traced = "done"
            if el >= ctx.seconds and traced != "on":
                break
        t_close = loop.flush()
    window_s = t_close - t_open
    tokens = loop.received() - tok_open
    stats_close = server.cache_stats()
    sparse_win = _delta(stats_close, stats_open, SPARSE_KEYS)
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
            blocks_in_use=stats_close.get("in_use"),
            prefill_rows_per_chunk=stats_close.get("prefill_rows_per_chunk"),
            state_resets=stats_close.get("state_resets", 0)
            - stats_open.get("state_resets", 0),
            state_prefix_refused=stats_close.get("state_prefix_refused"),
            state_reprefills=stats_close.get("state_reprefills"),
            **sparse_win, worst_block=_worst_block(server, acct_open),
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
        "sparse_rows_walked": sparse_win["sparse_rows_walked"],
        "sparse_rows_live": sparse_win["sparse_rows_live"],
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        sc = conf["sparse_config"]
        counters["traced_steps"] = len(positions)
        counters["traced_sparse_bytes"] = sum(
            opcount_sparse.selected_row_bytes(
                p, n_sparse, cfg.kv_heads, cfg.head_dim, item,
                sc["block_size"], sc["topk"], sc["dense_len"])
            for p in positions)
        counters["traced_state_bytes"] = sum(
            opcount_sparse.lightning_state_bytes(
                len(p), n_lin, cfg.lightning_heads, cfg.lightning_head_dim)
            for p in positions)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 4)), ctx.seed)
    # a few live slots: the linear state, and the last step's selection
    # (a slot that has not decoded yet has no selection: passed over)
    live = sorted(server.live_positions())
    states, picks = [], []
    for i in np.random.default_rng([ctx.seed, 78]).permutation(len(live)):
        if len(states) == int(traffic.get("check_states", 2)):
            break
        try:
            picks.append(server.sparse_selection(live[i]))
        except ValueError:
            continue
        states.append(server.recurrent_state(live[i]))
    adapter.release(server)
    del server, loop
    ref = ctx.reference()
    checks = [("window_compiles", int(win_c), 0),
              ("requests_short", short, 0),
              ("requests_failed", failed_win, 0)]
    raw = None
    requests = [(t.prompt, t.tokens) for t in sample]
    frame = int(traffic.get("check_frame", 4096))
    if sample and states:
        t_ref = ctx.clock()
        gaps = served_gaps(ref, params, conf, requests, frame)
        numbers = _numbers(gaps, ref.state_errors(params, conf, states),
                           ref.selection_numbers(params, conf, picks))
        ctx.say(phase="reference", requests=len(sample),
                tokens_compared=int(gaps.size), states_compared=len(states),
                state_tokens=[len(t) for t, _ in states],
                seconds=ctx.clock() - t_ref, **numbers)
        checks += gap_checks(numbers, conf)
        raw = {"gap": gaps}
    else:
        checks.append(("requests_compared_missing", 1, 0))
    return {"end_to_end": end_to_end, "counters": counters, "checks": checks,
            "attempted": len(in_win) + failed_win, "failed": failed_win,
            "memory_peak_bytes": memory_peak, "raw": raw,
            "control_inputs": (params, requests, states, picks, frame)}


def _worst_block(server, since: int, block: int = 32) -> dict:
    """The window's slowest block of `block` steps by the program's own
    account (`step_accounts()`, profiler off): where its milliseconds
    went (a step's wall = work + held + waited; `gap` is the caller's,
    between two steps), and its slowest step with what the account
    blames. A run that lost time says here whether the host waited on
    the device, was held in a call, worked, or was off its core."""
    recs = [r for r in server.step_accounts() if r.n > since]
    blocks = [recs[i:i + block] for i in range(0, len(recs), block)]
    if not blocks:
        return {}
    worst = max(blocks, key=lambda b: sum(r.wall_ns + r.gap_ns for r in b))
    top = max(worst, key=lambda r: r.wall_ns + r.gap_ns)
    ms = lambda f: round(sum(getattr(r, f) for r in worst) / 1e6, 1)  # noqa
    return {"first_n": worst[0].n - since, "wall_ms": ms("wall_ns"),
            "gap_ms": ms("gap_ns"), "work_ms": ms("work_ns"),
            "held_ms": ms("held_ns"), "waited_ms": ms("waited_ns"),
            "gc_ms": ms("gc_ns"), "cpu_thread_ms": ms("cpu_thread_ns"),
            "chunks": sum(r.chunks for r in worst),
            "admits": sum(r.admits for r in worst),
            "top_step": [top.n - since, round(top.wall_ns / 1e6, 1),
                         round(top.gap_ns / 1e6, 1), top.blame(),
                         top.top_prog, top.lead, top.owed]}


def served_gaps(ref, params, conf, requests, frame: int, quant=None):
    """`ref.served_gaps` with each request ALONE in a frame of its own
    length rounded up to `frame` rows (the mix's `check_frame`: a
    sparse layer's cost grows with the square of the frame), the
    frames' gaps joined."""
    out = []
    for prompt, served in requests:
        n = len(prompt) + len(served)
        out.append(ref.served_gaps(params, conf, [(prompt, served)],
                                   n + -n % frame, len(served), quant=quant))
    return np.concatenate(out)


def _numbers(gaps, state_errs, selection: dict) -> dict:
    """`gap_numbers`, `state_rel_err` (the farthest a sampled slot's
    linear state, first linear layer, lies from the float32 reference's
    state of the same tokens, |S - S_ref| / |S_ref|) and the two
    numbers of the selection (`reference.selection_numbers`)."""
    return dict(gap_numbers(gaps), state_rel_err=float(np.max(state_errs)),
                **selection)


def control(ctx, outcome) -> dict:
    """The CONTROLS' reading of the numbers `run` compared, the
    reference in each mode of `control_precision` in the program's
    place: "int8", the nearest precision below the bfloat16 the
    configuration serves in; "state_bf16", the nearest below the
    float32 it states for the linear state; "window_only", the sparse
    layers reading their forced blocks alone. EACH has to come out not
    correct on its own numbers. What goes into the program's place: the
    readings of a control that passes every limit, if there is one (the
    harness then reads `correct` and fails); else, for each number, the
    reading of the control that number exists to catch (`held_by`).
    `numbers` keeps each control's own, with its verdict."""
    params, requests, states, picks, frame = outcome["control_inputs"]
    ref, conf = ctx.reference(), ctx.config
    readings, raw = {}, {}
    for quant in conf["control_precision"]:
        gaps = served_gaps(ref, params, conf, requests, frame, quant)
        readings[quant] = _numbers(
            gaps, ref.state_errors(params, conf, states, quant=quant),
            ref.selection_numbers(params, conf, picks, quant=quant))
        raw["gap_" + quant] = gaps
    limits = {n: lim for n, _, lim in gap_checks(
        next(iter(readings.values())), conf)}
    for quant, r in readings.items():
        r["correct"] = all(r[n] <= lim for n, lim in limits.items())
    passing = [q for q, r in readings.items() if r["correct"]]
    held = conf["correct"]["held_by"]
    if passing:
        checks = {n: readings[passing[0]][n] for n in limits}
    else:
        checks = {n: readings[held[n]][n] for n in limits}
    return {"checks": checks, "numbers": readings, "raw": raw}
