"""Driver of the served-decoder cells: `ContinuousServer.submit()` and
`.step()` under a traffic mix of chipbench/traffic_gen/requests.py.

Set-up: weights made on the device from --seed, the server built with
the configuration's `server` arguments and every other at its default,
every prefill bucket warmed, then the mix's ramp. The window opens and
closes on a flush. Once it has closed: the peak memory is read, the
server is freed, and the float32 reference runs over prompt ++ served
tokens of a seeded sample of the requests the window finished.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Optional

import numpy as np

from chipbench import opcount
from chipbench.adapters import serving_adapter as adapter
from chipbench.harness import seed_key


def build_cfg(conf: dict):
    import jax.numpy as jnp
    from hpx_tpu.models.transformer import TransformerConfig
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["torch_dtype"]]
    return TransformerConfig(
        vocab=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"], head_dim=conf["head_dim"],
        n_layers=conf["num_hidden_layers"], d_ff=conf["intermediate_size"],
        dtype=dtype, n_kv_heads=conf["num_key_value_heads"],
        rope=bool(conf.get("rope_theta")),
        rope_theta=float(conf.get("rope_theta") or 10000.0))


def make_params(cfg, seed: int):
    """The weight pytree in the program's layout, made on the device in
    the served type: one jitted program a layer (the same for all), one
    for the embedding. Scales and the bias are random too, so that a
    path that drops them shows."""
    import jax
    import jax.numpy as jnp
    d, nh, hd, f = cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff
    nkv, dt = cfg.kv_heads, cfg.dtype
    s = 1.0 / math.sqrt(d)

    def nrm(k, shape, scale, shift=0.0):
        return (jax.random.normal(k, shape, jnp.float32) * scale
                + shift).astype(dt)

    @jax.jit
    def layer(k):
        ks = jax.random.split(k, 8)
        if nkv == nh:
            qkv = {"wqkv": nrm(ks[0], (3, d, nh, hd), s)}
        else:
            qkv = {"wq": nrm(ks[0], (d, nh, hd), s),
                   "wkv": nrm(ks[1], (2, d, nkv, hd), s)}
        return {"ln1": nrm(ks[2], (d,), 0.02, 1.0), **qkv,
                "wo": nrm(ks[3], (nh, hd, d), s),
                "ln2": nrm(ks[4], (d,), 0.02, 1.0),
                "w1": nrm(ks[5], (d, f), s),
                "b1": nrm(ks[6], (f,), 0.02),
                "w2": nrm(ks[7], (f, d), 1.0 / math.sqrt(f))}

    @jax.jit
    def outer(k):
        k1, k2 = jax.random.split(k)
        return nrm(k1, (cfg.vocab, d), s), nrm(k2, (d,), 0.02, 1.0)

    keys = jax.random.split(seed_key(seed), cfg.n_layers + 1)
    emb, ln_f = outer(keys[0])
    return {"emb": emb, "ln_f": ln_f,
            "layers": [layer(keys[1 + i]) for i in range(cfg.n_layers)]}


class Track:
    __slots__ = ("rid", "k", "prompt", "max_new", "t_submit", "t_first",
                 "t_last", "req", "tokens", "failed")

    def __init__(self, rid, spec, t_submit, req):
        self.rid, self.k = rid, spec["k"]
        self.prompt, self.max_new = spec["prompt"], spec["max_new"]
        self.t_submit, self.t_first, self.t_last = t_submit, None, None
        self.req, self.tokens, self.failed = req, None, False


class Loop:
    """The harness's side of the serving loop: submits what the
    generator says is due, steps the server, and notes on its own clock
    when each request's first token and its last reached the host."""

    def __init__(self, ctx, server, gen):
        self.ctx, self.server, self.gen = ctx, server, gen
        self.active: Dict[int, Track] = {}
        self.finished: List[Track] = []
        self.n_failed = 0
        self.finished_tokens = 0
        self.steps = 0
        self.t0 = ctx.clock()
        self.occ_sum, self.occ_n = 0.0, 0
        self.kv_used_sum, self.kv_used_n = 0.0, 0
        self.traced_positions: Optional[List[List[int]]] = None
        self.step_ends: List[float] = []

    def received(self) -> int:
        return self.finished_tokens + sum(
            len(t.req.tokens) for t in self.active.values())

    def submit_due(self) -> None:
        now = self.ctx.clock()
        for spec in self.gen.poll(self.steps, now - self.t0):
            with self.ctx.span("bench.submit"):
                t = self.ctx.clock()
                rid = self.server.submit(spec["prompt"], spec["max_new"])
                req = adapter.request_of(self.server, rid)
            due = spec["due_s"]
            self.active[rid] = Track(
                rid, spec, t if due is None else self.t0 + due, req)

    def poll(self, now: float) -> None:
        for rid, tr in list(self.active.items()):
            if tr.t_first is None and tr.req.tokens:
                tr.t_first = now
            toks = adapter.done(self.server, rid)
            if toks is not None:
                tr.t_last, tr.tokens = now, list(toks)
            elif rid in self.server.failed:
                tr.t_last, tr.failed = now, True
                self.n_failed += 1
            else:
                continue
            del self.active[rid]
            self.finished.append(tr)
            self.finished_tokens += len(tr.tokens or ())
            self.gen.finished()

    def step(self) -> None:
        self.submit_due()
        before = adapter.live(self.server)
        with self.ctx.span("bench.step"):
            self.server.step()
        self.steps += 1
        now = self.ctx.clock()
        self.step_ends.append(now)
        after = adapter.live(self.server)
        # positions this step decoded: every slot live before it, and
        # every slot that went live inside it (admitted, then decoded)
        decoded = list(before.values()) + [
            p - 1 for s, p in after.items() if s not in before]
        self.occ_sum += len(decoded) / self.server.slots
        self.occ_n += 1
        if self.traced_positions is not None:
            self.traced_positions.append(decoded)
        if self.ctx.trace:      # costs host time: the traced run only
            st = self.server.cache_stats()
            self.kv_used_sum += st["in_use"] / st["num_blocks"]
            self.kv_used_n += 1
        self.poll(now)

    def flush(self) -> float:
        adapter.flush(self.server)
        now = self.ctx.clock()
        self.poll(now)
        return now

    def warm(self) -> None:
        """One tiny request a prefill bucket, and one prompt long enough
        to go through the pending (chunk-a-step) path: every program of
        the cell, none of another."""
        widths = list(self.server.prefill_buckets)
        lens = widths + [self.server.prefill_chunk + widths[0] + 1]
        rng = np.random.default_rng(0)
        for n in lens:
            self.server.submit(
                [int(t) for t in rng.integers(1, self.server.cfg.vocab, n)],
                max_new=3)
        while self.server.step():
            pass
        adapter.flush(self.server)
        adapter.drain(self.server)

    def ramp(self) -> None:
        self.t0 = self.ctx.clock()
        while not self.gen.ramp_done(self.steps, self.ctx.clock() - self.t0):
            self.step()


def _p90(values: List[float]) -> float:
    return float(np.percentile(np.asarray(values, np.float64), 90))


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
        t_warm = ctx.clock()
        loop.ramp()
        t_open = loop.flush()
    setup_s = ctx.setup_seconds(t_open)
    ctx.say(phase="setup", setup_s=setup_s,
            devices_ready_s=ctx.devices_ready_s,
            built_s=t_built - ctx.t_start, warmed_s=t_warm - ctx.t_start, fresh_compiles=int(setup_c),
            cache_hits=setup_c.hits, ramp_steps=loop.steps,
            paged_kernel=server.hbm_read_stats().get("paged_kernel"),
            block_size=server.block_size)

    # -- the measured window -------------------------------------------
    tok_open, steps_open = loop.received(), loop.steps
    n_fin_open = len(loop.finished)
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
        "ttft_p90_ms": end_to_end.get("ttft_p90_ms"),
    }
    if ctx.trace and traced == "done":
        counters["traced_steps"] = len(positions)
        counters["traced_kv_bytes"] = sum(
            opcount.paged_decode_attention_bytes(
                p, cfg.n_layers, cfg.kv_heads, cfg.head_dim,
                np.dtype(cfg.dtype).itemsize) for p in positions)

    # -- the window has closed: memory, then the reference ---------------
    memory_peak = ctx.memory_peak()
    sample = _sample(in_win, int(traffic.get("check_requests", 64)), ctx.seed)
    adapter.release(server)
    del server, loop
    ref = ctx.reference()
    length, out_max = gen.frame()
    checks = [("window_compiles", int(win_c), 0),
              ("requests_short", short, 0),
              ("requests_failed", failed_win, 0)]
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


def gap_numbers(gaps) -> dict:
    """The numbers read from the served tokens' gaps under the float32
    reference (gap: how far the served token's logit lies below the
    reference's best at its position; 0 where it IS the best).
    `gap_max`: the widest gap, which one grossly wrong token sets.
    `parted_gap_sq_mean`: over the tokens that part from the
    reference's best, the mean squared gap: the power of the served
    path's rounding noise, whatever the share of near-ties the seed's
    weights happen to give. `gap_mean`, `parted_tokens`: printed only."""
    parted = gaps[gaps > 0]
    return {"gap_max": float(gaps.max()),
            "parted_gap_sq_mean": float((parted.astype(np.float64) ** 2)
                                        .mean()) if parted.size else 0.0,
            "gap_mean": float(gaps.mean()),
            "parted_tokens": int(parted.size)}


def gap_checks(numbers: dict, conf: dict) -> list:
    """Each number the configuration gives a limit, beside that limit."""
    return [(name, numbers[name], limit)
            for name, limit in conf["correct"]["limits"].items()]


def control(ctx, outcome) -> dict:
    """The CONTROL's reading of the numbers `run` compared: the
    reference in the nearest precision below the configuration's, in
    the program's place. It need not decode: at each position of the
    same prompts and tokens, the gap of the token it puts first."""
    params, requests, length, out_max = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(
        params, ctx.config, requests, length, out_max,
        quant=ctx.config["control_precision"])
    numbers = gap_numbers(gaps)
    return {"checks": {n: v for n, v, _ in gap_checks(numbers, ctx.config)},
            "numbers": numbers, "raw": {"gap": gaps}}


def _sample(finished: List[Track], n: int, seed: int) -> List[Track]:
    """A seeded sample of the requests the window finished, the longest
    (prompt ++ served) always in it."""
    if not finished:
        return []
    order = sorted(finished, key=lambda t: (len(t.prompt) + len(t.tokens),
                                            t.k))
    longest = order[-1]
    rest = order[:-1]
    rng = np.random.default_rng([int(seed), 77])
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]
