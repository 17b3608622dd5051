"""Serving throughput harness: tokens/s for the three decode engines.

Measures, on whatever backend jax resolves (the real TPU on the bench
host; CPU for smoke runs with --cpu):

  1. generate            — batched uniform greedy decode
  2. ContinuousServer    — slot-based continuous batching over a ragged
                           request mix (the steady-state serving shape),
                           plus a mixed-UNBUCKETED-length wave reporting
                           cold-start compiles, TTFT, and decode-stall
                           p99 (the bucketed chunked-prefill case)
  3. speculative_generate — draft-assisted greedy (reports rounds too:
                           tokens per target window forward is the
                           speedup lever)
  4. paged_prefix_reuse  — ContinuousServer(paged=True) over a
                           prefix-heavy mix (many requests sharing one
                           long system prompt); reports radix cache hit
                           rate and the fraction of prefill tokens the
                           prefix cache eliminated
  5. serving_spec        — the speculation wave: the SAME mixed
                           repetitive + non-repetitive request mix
                           through a spec-off and a spec-on server
                           (prompt-lookup drafts, per-slot adaptive k);
                           reports acceptance rate, tokens per decode
                           step, warm tokens/s for both runs, and the
                           sha256 of every request's output — the
                           hashes MUST match, speculation only changes
                           how fast identical tokens appear
  6. paged_decode        — the decode-attention roofline wave: one
                           greedy mix through the paged server in each
                           (paged_kernel, kv_dtype) mode over
                           kv_dtype {bf16, int8, fp8} and kernel
                           {gather, fused, fused_online} (fused
                           kernels on TPU only — interpret-mode Pallas
                           is a test vehicle, not a serving path).
                           Reports warm tokens/s, decode-attention HBM
                           bytes/token (sampled at peak occupancy from
                           the /cache hbm-read-per-token feed, so the
                           int8/fp8 byte reductions are MEASURED, not
                           modeled) and the effective attention
                           GFLOP/s, plus a per-cell oracle-match gate:
                           bf16 cells (any kernel, incl. the
                           tolerance-budgeted fused_online) must match
                           the gather/bf16 oracle exactly; quantized
                           cells report their greedy match fraction

Prints one JSON line per engine. This is an operator harness, not part
of bench.py's driver metrics — serving throughput depends on the
request mix, so the mix is printed with the number.

With --trace-out PATH the whole run executes under the causal task
tracer (hpx_tpu.svc.tracing) and a Chrome trace-event JSON — serving
spans, flow arrows, /serving + /cache counter tracks — is written to
PATH, loadable directly in chrome://tracing or https://ui.perfetto.dev.

  7. serving_chaos       — the fault-injection wave (--chaos): the
                           SAME mixed paged+spec request mix through a
                           fault-free server and one with a seeded
                           deterministic fault schedule (decode,
                           chunked-prefill, spec-verify and
                           allocator-OOM faults; spec degrades to
                           sequential after repeated verify faults).
                           Reports goodput for both runs, restores per
                           fault class, restore p99, shed/degraded
                           counts, and the sha256 of every request's
                           output — the hashes MUST match: recovery
                           replays from slot checkpoints over
                           still-resident KV, so a faulted run emits
                           byte-identical tokens, just later. A second
                           overload sub-run (100% decode fault rate)
                           demonstrates typed shedding: the retry
                           budget exhausts and every request fails
                           into `srv.failed` instead of hanging.

  8. serving_disagg      — the disaggregated wave (--disagg): one
                           Poisson-arrival mix (Zipf-shared prefixes,
                           70/30 interactive/batch SLO classes)
                           through a colocated paged server and a
                           DisaggRouter (2 prefill + 2 decode
                           workers). Reports TTFT p50/p95/p99, decode
                           stall p50/p99 (inter-step gap while slots
                           are live) and goodput for BOTH topologies.
                           With --chaos as well, a sub-run kills one
                           worker of each role mid-flight (seeded
                           disagg.prefill/disagg.decode schedule) and
                           GATES on: sha-identical tokens to the
                           fault-free disagg run, >=1 failover per
                           role, zero leaked KV blocks.

  9. paged_mesh          — the sharded serving wave (--mesh): the
                           SAME greedy mix through the single-device
                           paged server and ContinuousServer(
                           paged=True, mesh=(dp, tp)) — KV block pool
                           sharded over tp on kv heads, slots and
                           device block tables over dp. Reports warm
                           tokens/s and decode-stall p50/p99 for BOTH
                           topologies plus the sha256 of every
                           request's output — the hashes MUST match:
                           sharding moves the same program onto more
                           chips, so a misplaced psum shows up here
                           as a sha mismatch, not a vibe. Needs >=4
                           devices (CPU smoke: XLA_FLAGS=
                           --xla_force_host_platform_device_count=8);
                           emits a skipped line otherwise.

  9b. serving_moe        — the expert-parallel MoE wave (--moe): one
                           greedy mix through a single-device MoE
                           paged server and the (dp, tp)-mesh one,
                           experts sharded over tp and decode routing
                           through moe_ffn's tiled all_to_all at the
                           drop-free auto capacity. Reports warm
                           tokens/s, decode-stall p50/p99 and the
                           overflow-drop rate from the /serving moe
                           counters (banked into --metrics-out), and
                           GATES on sha-identical tokens. Rows carry
                           an explicit onchip stamp; needs >=4
                           devices, emits a skipped line otherwise.

 10. serving_fleet      — the fleet wave (--fleet): the SAME warm
                           Zipf-shared-prefix Poisson mix through a
                           FleetRouter in placement=load (pure
                           least-loaded, the baseline) and
                           placement=prefix (digest-scored routing +
                           prefix-seeded prefills). Reports TTFT
                           p50/p99, decode-stall p50/p99, placement
                           counts by policy, and the prefill tokens
                           each mode ACTUALLY skipped on the measured
                           wave. GATES on: sha-identical tokens
                           between the two modes (placement moves
                           work, never changes it), the prefix mode
                           saving strictly more prefill tokens than
                           least-loaded, and zero leaked KV blocks.

 11. serving_tier       — the tiered-KV wave (--tier): one
                           prefix-heavy greedy+sampled mix, radix
                           budget deliberately smaller than the shared
                           chain so the tail demotes to the host-RAM
                           tier and later admissions promote it back
                           (crossover-gated restore). Runs tier-off
                           and tier-on and GATES on: sha-identical
                           outputs, tier-on saving strictly more
                           prefill tokens, zero leaked device blocks
                           and zero leaked host buffers at drain.

Usage: python benchmarks/serving_bench.py [--cpu] [--scale N]
                                          [--prefix-only] [--spec-only]
                                          [--paged-decode-only] [--mesh]
                                          [--moe] [--chaos] [--disagg]
                                          [--fleet]
                                          [--tier] [--alerts]
                                          [--trace-out PATH]
                                          [--metrics-out PATH]

With --metrics-out PATH the waves' live HistogramCounters (TTFT,
queue wait, KV transfer, decode stall, E2E — merged across workers
for disagg/fleet) are written as a hpx_tpu.metrics.v1 JSON artifact:
full mergeable snapshots plus derived p50/p95/p99.  When --trace-out
and --fleet combine, the router tracer and every worker's private
span ring are stitched by trace_export.merge_traces into ONE Perfetto
trace — per-worker pid rows, clock-aligned, with rid flow arrows
place → prefill → transfer → decode across processes.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

# --metrics-out artifact schema; tests/test_metrics.py smoke-checks it
METRICS_SCHEMA = "hpx_tpu.metrics.v1"


def metrics_artifact(histograms, counters=None,
                     quantiles=(0.5, 0.95, 0.99)):
    """JSON-safe SLO artifact from LIVE HistogramCounters: each
    histogram's full mergeable snapshot plus its derived quantiles
    (bounded-relative-error estimates, not a post-hoc sort of raw
    samples)."""
    hists = {}
    for name in sorted(histograms):
        h = histograms[name]
        hists[name] = {
            "snapshot": h.snapshot(),
            "quantiles": {f"p{round(q * 100.0, 4):g}": h.quantile(q)
                          for q in quantiles},
            "relative_error_bound": h.relative_error_bound(),
        }
    return {"schema": METRICS_SCHEMA, "histograms": hists,
            "counters": dict(counters or {})}


def write_metrics_artifact(path, doc):
    """Atomic write (tmp + rename) so a watcher never reads a torn
    artifact."""
    tmp = f"{path}.tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return doc


def main() -> int:
    if "--cpu" in sys.argv:
        os.environ["JAX_PLATFORMS"] = "cpu"     # before jax is imported
    import jax
    dev = jax.devices()[0]
    print(f"# device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", file=sys.stderr, flush=True)
    if dev.platform != "tpu" and "--cpu" not in sys.argv:
        print("serving_bench measures on a TPU; --cpu is the only way "
              "onto the CPU", file=sys.stderr)
        return 1
    # no persistent compile cache here, even where the environment
    # places one: cold compiles are a MEASURED quantity of this bench
    # (compile counts, cold TTFT), and
    # `_PROGRAMS.clear()` is a true cold boot only while no earlier
    # run's programs come back from disk
    jax.config.update("jax_enable_compilation_cache", False)
    import jax.numpy as jnp
    import numpy as np
    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer

    scale = int(sys.argv[sys.argv.index("--scale") + 1]) \
        if "--scale" in sys.argv else (4 if "--cpu" in sys.argv else 16)
    on_tpu = jax.default_backend() == "tpu"

    trace_out = sys.argv[sys.argv.index("--trace-out") + 1] \
        if "--trace-out" in sys.argv else None
    tracer = None
    if trace_out:
        from hpx_tpu.core.config import runtime_config
        from hpx_tpu.svc import tracing
        runtime_config().set("hpx.trace.enabled", "1")
        tracer = tracing.start_if_configured()

    metrics_out = sys.argv[sys.argv.index("--metrics-out") + 1] \
        if "--metrics-out" in sys.argv else None
    # --metrics-out implies the per-program profiler: the artifact's
    # "programs" section is the roofline/compile-time table ROADMAP
    # items 3/4 consume
    profiler = None
    if metrics_out:
        from hpx_tpu.svc import progprof
        profiler = progprof.start_profiling()
    # live HistogramCounters the waves hand to finish() for the
    # --metrics-out artifact, keyed "<bench>/<metric>"
    collected_hists = {}
    # scalar counters the waves bank for the artifact's "counters"
    # section (merged over the live registry snapshot), keyed
    # "<bench>/<name>" — e.g. the MoE wave's overflow-drop rate
    collected_counters = {}
    # per-wave cold/warm compile counts (utils/compilemon), keyed
    # "<bench>[/<leg>]" -> {"cold": n, "warm": n}; finish() embeds
    # the dict as the artifact's "compiles" section
    collected_compiles = {}
    # (label, chrome-doc) pairs from the fleet wave's worker rings —
    # finish() stitches them with the router tracer into ONE trace
    fleet_trace_docs = []

    d = 64 * scale
    cfg = tfm.TransformerConfig(
        vocab=1024, d_model=d, n_heads=8, head_dim=d // 8,
        n_layers=4, d_ff=4 * d,
        dtype=jnp.bfloat16 if on_tpu else jnp.float32)
    draft_cfg = tfm.TransformerConfig(
        vocab=1024, d_model=d // 4, n_heads=2, head_dim=d // 8,
        n_layers=1, d_ff=d, dtype=cfg.dtype)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    draft = tfm.init_params(draft_cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)

    def emit(name, toks, secs, **extra):
        line = {"engine": name, "tokens": toks,
                "seconds": round(secs, 4),
                "tokens_per_s": round(toks / secs, 1)}
        line.update(extra)
        print(json.dumps(line), flush=True)

    # 4. paged KV cache with radix prefix reuse: 12 requests sharing a
    # 64-token system prompt with short unique tails — the agentic /
    # chat-assistant shape where prefix caching pays. The first request
    # through prefills the shared prefix; later admissions splice its
    # blocks straight from the radix tree.
    def paged_prefix_bench():
        shared = rng.integers(1, 1000, 64).tolist()
        preqs = [(shared + rng.integers(1, 1000, 8).tolist(),
                  int(rng.integers(16, 33))) for _ in range(12)]
        ptotal = sum(m for _, m in preqs)

        def run_paged():
            srv = ContinuousServer(params, cfg, slots=4, smax=160,
                                   paged=True)
            for p, m in preqs:
                srv.submit(p, max_new=m)
            t0 = time.perf_counter()
            srv.run()
            return srv, time.perf_counter() - t0

        run_paged()                                    # compile
        srv, secs = run_paged()
        st = srv.cache_stats()
        computed = st["prefill_tokens_computed"]
        saved = st["prefill_tokens_saved"]
        emit("paged_prefix_reuse", ptotal, secs,
             mix="12 reqs 64-tok shared prefix + 8-tok tail over 4 slots",
             cache_hit_rate=round(st["hit_rate"], 3),
             prefill_tokens_saved=saved,
             prefill_tokens_computed=computed,
             prefill_saved_frac=round(saved / (saved + computed), 3))

    # 4b. the tiered-KV wave (--tier): two 96-token shared prefixes
    # ALTERNATE over one slot under a 4-block radix budget — each
    # retire's budget sweep evicts the other (reader-free) chain
    # wholesale, so the next admission of that prefix is restorable
    # ONLY from the host tier. Tier-off this mix saves zero prefill
    # tokens (every chain dies before its reuse); tier-on the
    # crossover gate promotes the full prefix back each time.
    # Identity is gated against a HOT-RETENTION ORACLE (tier off,
    # UNBOUNDED radix budget: every reuse is a plain hot radix match):
    # a promoted block must be byte-for-byte what hot retention would
    # have served, so sha(tier-on) == sha(oracle) exactly. The
    # budget-constrained tier-off wave is NOT the identity baseline —
    # it never matches, and at fp8 a matched admission reads
    # dequantized (quantizer-roundtripped) prefix rows while a
    # recomputed one reads full-precision rows, a pre-existing
    # prefix-reuse asymmetry independent of the tier (bf16/int8 are
    # unaffected). That wave instead gates the strict-increase clause:
    # tier-on must save STRICTLY more prefill tokens than tier-off
    # with promotions actually observed, and zero leaked device blocks
    # AND zero leaked host buffers once the radix drains — in all
    # three waves.
    def tier_bench() -> None:
        import hashlib
        from hpx_tpu.core.config import runtime_config
        rc = runtime_config()
        prefixes = [rng.integers(1, 1000, 96).tolist(),
                    rng.integers(1, 1000, 96).tolist()]
        treqs = [(prefixes[i % 2] + rng.integers(1, 1000, 8).tolist(),
                  int(rng.integers(12, 25))) for i in range(8)]
        ttotal = sum(m for _, m in treqs)

        def run_wave(tier_on, budget=4):
            rc.set("hpx.cache.tier.enable", "1" if tier_on else "0")
            try:
                srv = ContinuousServer(params, cfg, slots=1, smax=160,
                                       paged=True, block_size=16,
                                       kv_dtype="fp8",
                                       radix_budget_blocks=budget)
                free0 = srv._alloc.stats()["free"]
                for i, (p, m) in enumerate(treqs):
                    if i % 3 == 2:
                        # sampled rows reuse per-index keys across the
                        # two runs — identity must hold beyond greedy
                        srv.submit(p, max_new=m, temperature=0.8,
                                   key=jax.random.PRNGKey(1000 + i))
                    else:
                        srv.submit(p, max_new=m)
                t0 = time.perf_counter()
                out = srv.run()
                secs = time.perf_counter() - t0
                st = srv.cache_stats()
                while sum(srv._radix.evict(1)):
                    pass                        # drain the tree
                dev_leak = free0 - srv._alloc.stats()["free"]
                host_leak = (srv._tier.leaked_buffers()
                             if srv._tier is not None else 0)
                sha = hashlib.sha256(json.dumps(
                    [out[r] for r in sorted(out)]).encode()).hexdigest()
                return secs, st, sha, dev_leak, host_leak
            finally:
                rc.set("hpx.cache.tier.enable", "0")

        run_wave(False)                        # compile
        run_wave(True)                         # compile (restore prog)
        off_secs, off_st, off_sha, off_dev, off_host = run_wave(False)
        (_, hot_st, hot_sha,
         hot_dev, hot_host) = run_wave(False, budget=None)  # oracle
        secs, st, sha, dev_leak, host_leak = run_wave(True)
        emit("serving_tier", ttotal, secs,
             mix="8 reqs alternating two 96-tok shared prefixes + "
                 "8-tok tails over 1 slot, radix budget 4 blocks, "
                 "fp8 KV",
             prefill_tokens_saved={
                 "off": off_st["prefill_tokens_saved"],
                 "on": st["prefill_tokens_saved"]},
             tier_demoted=st.get("tier_demoted", 0),
             tier_promoted=st.get("tier_promoted", 0),
             tier_declined=st.get("tier_declined", 0),
             baseline_tokens_per_s=round(ttotal / off_secs, 1),
             kv_blocks_leaked={"off": off_dev, "hot": hot_dev,
                               "on": dev_leak},
             host_buffers_leaked=host_leak + off_host + hot_host,
             output_sha=sha[:16],
             output_identical_to_hot_oracle=(sha == hot_sha))
        if (sha != hot_sha
                or st["prefill_tokens_saved"]
                <= off_st["prefill_tokens_saved"]
                or st["prefill_tokens_saved"]
                != hot_st["prefill_tokens_saved"]
                or not st.get("tier_promoted")
                or dev_leak or off_dev or hot_dev
                or host_leak or off_host or hot_host):
            print(json.dumps({
                "error": "tier gate failed",
                "hot_oracle_sha": hot_sha[:16], "on_sha": sha[:16],
                "prefill_tokens_saved": {
                    "off": off_st["prefill_tokens_saved"],
                    "hot": hot_st["prefill_tokens_saved"],
                    "on": st["prefill_tokens_saved"]},
                "kv_blocks_leaked": {"off": off_dev, "hot": hot_dev,
                                     "on": dev_leak},
                "host_buffers_leaked": (host_leak + off_host
                                        + hot_host)}),
                flush=True)
            raise SystemExit(2)

    # 5. the speculation wave: half the mix is repetitive (periodic
    # prompts whose continuations prompt-lookup nails), half is random
    # (drafts mostly rejected — the floor case). Byte-identity is
    # CHECKED here, not assumed: both servers' outputs are hashed.
    def spec_wave_bench():
        import hashlib
        rep = [(([11, 23, 7, 42] * 12)[:40], 48) for _ in range(4)]
        rnd = [(rng.integers(1, 1000, 24).tolist(),
                int(rng.integers(24, 49))) for _ in range(4)]
        sreqs = rep + rnd
        stotal = sum(m for _, m in sreqs)

        def run_wave(spec):
            srv = ContinuousServer(params, cfg, slots=4, smax=128,
                                   spec=spec, spec_k=4)
            for p, m in sreqs:
                srv.submit(p, max_new=m)
            srv.run()                                  # compile
            srv = ContinuousServer(params, cfg, slots=4, smax=128,
                                   spec=spec, spec_k=4)
            for p, m in sreqs:
                srv.submit(p, max_new=m)
            t0 = time.perf_counter()
            out = srv.run()
            secs = time.perf_counter() - t0
            sha = hashlib.sha256(json.dumps(
                [out[r] for r in sorted(out)]).encode()).hexdigest()
            return srv, secs, sha

        base_srv, base_secs, base_sha = run_wave(False)
        srv, secs, sha = run_wave(True)
        st = srv.spec_stats()
        emit("serving_spec", stotal, secs,
             mix="4 periodic + 4 random reqs new24-48 over 4 slots",
             draft="prompt", spec_k=4,
             acceptance_rate=round(st["acceptance_rate"], 3),
             tokens_per_step=round(st["tokens_per_step"], 2),
             baseline_tokens_per_s=round(stotal / base_secs, 1),
             output_sha=sha[:16],
             output_identical=(sha == base_sha))
        if sha != base_sha:
            print(json.dumps({"error": "spec output diverged",
                              "baseline_sha": base_sha[:16],
                              "spec_sha": sha[:16]}), flush=True)
            raise SystemExit(2)

    # 6. decode-attention roofline wave: the same greedy mix through
    # each (paged_kernel, kv_dtype) mode. bytes/token samples the
    # hbm_read_stats feed at PEAK table occupancy (mid-run max, not
    # the post-run zero), so the int8 ~2x / fp8 ~4x-vs-f32 reductions
    # are measured numbers; effective GFLOP/s models decode attention
    # as its two matmuls (QK^T + PV: 4 * S * n_heads * head_dim flops
    # per token per layer over the occupancy-derived S).
    def paged_decode_bench():
        dreqs = [(rng.integers(1, 1000, 24).tolist(), 48)
                 for _ in range(8)]
        dtotal = sum(m for _, m in dreqs)
        dtypes = ("bf16", "int8", "fp8")
        modes = [("gather", kvd) for kvd in dtypes]
        if on_tpu:
            modes += [(kern, kvd) for kern in ("fused", "fused_online")
                      for kvd in dtypes]

        def run_mode(kern, kvd):
            def run_once():
                srv = ContinuousServer(params, cfg, slots=4, smax=128,
                                       paged=True, paged_kernel=kern,
                                       kv_dtype=kvd,
                                       prefix_reuse=False)
                for p, m in dreqs:
                    srv.submit(p, max_new=m)
                t0 = time.perf_counter()
                peak = {"hbm_read_blocks_per_token": 0.0,
                        "hbm_read_bytes_per_token": 0.0}
                while srv.step():
                    st = srv.hbm_read_stats()
                    if (st["hbm_read_bytes_per_token"]
                            > peak["hbm_read_bytes_per_token"]):
                        peak = st
                secs = time.perf_counter() - t0
                out, srv._done = srv._done, {}
                return (secs, peak, [out[r] for r in sorted(out)],
                        srv.block_size)

            run_once()                                 # compile
            return run_once()

        results = {}
        for kern, kvd in modes:
            results[(kern, kvd)] = run_mode(kern, kvd)
        oracle_toks = results[("gather", "bf16")][2]
        bf16_bytes = results[("gather", "bf16")][1][
            "hbm_read_bytes_per_token"]
        for (kern, kvd), (secs, peak, toks, bs) in results.items():
            tps = dtotal / secs
            # occupancy-derived attended length: blocks/token * bs
            s_eff = peak["hbm_read_blocks_per_token"] * bs
            flops_tok = (4 * s_eff * cfg.n_heads * cfg.head_dim
                         * cfg.n_layers)
            match = sum(a == b for a, b in zip(toks, oracle_toks))
            emit(f"paged_decode_{kern}_{kvd}", dtotal, secs,
                 mix="8 reqs plen24 new48 over 4 slots",
                 hbm_blocks_per_token=round(
                     peak["hbm_read_blocks_per_token"], 2),
                 hbm_bytes_per_token=int(
                     peak["hbm_read_bytes_per_token"]),
                 bytes_vs_bf16=round(
                     peak["hbm_read_bytes_per_token"]
                     / bf16_bytes, 3) if bf16_bytes else None,
                 attn_gflops_per_s=round(flops_tok * tps / 1e9, 2),
                 outputs_match_bf16_oracle=f"{match}/{len(toks)}")
            if kvd == "bf16" and toks != oracle_toks:
                print(json.dumps({"error": "bf16 paged modes "
                                  "diverged", "mode": kern}),
                      flush=True)
                raise SystemExit(2)

    # 9. the sharded serving wave: the same greedy mix through the
    # single-device paged server and the (dp, tp)-mesh paged server
    # (pool over tp kv heads, slots + device block tables over dp).
    # Identity is CHECKED: sharding is a placement change, not an
    # algorithm change, so tokens must be byte-identical.
    def mesh_paged_bench():
        import hashlib
        ndev = len(jax.devices())
        if ndev < 4:
            print(json.dumps({
                "engine": "paged_mesh", "skipped": True,
                "reason": f"needs >=4 devices, have {ndev} (CPU smoke:"
                          " XLA_FLAGS=--xla_force_host_platform"
                          "_device_count=8)"}), flush=True)
            return
        tp = 4 if (ndev >= 8 and cfg.n_heads % 4 == 0) else 2
        dp = 2
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:dp * tp]).reshape(dp, tp),
            ("dp", "tp"))
        wreqs = [(rng.integers(1, 1000, 24).tolist(), 48)
                 for _ in range(8)]
        wtotal = sum(m for _, m in wreqs)

        def run_once(m):
            srv = ContinuousServer(params, cfg, slots=4, smax=128,
                                   paged=True, mesh=m)
            for p, mx in wreqs:
                srv.submit(p, max_new=mx)
            t0 = time.perf_counter()
            stalls = []
            alive = True
            while alive:
                s0 = time.perf_counter()
                alive = srv.step()
                stalls.append(time.perf_counter() - s0)
            secs = time.perf_counter() - t0
            out, srv._done = srv._done, {}
            sha = hashlib.sha256(json.dumps(
                [out[r] for r in sorted(out)]).encode()).hexdigest()
            return secs, stalls, sha

        waves = [("paged_single_device", None),
                 (f"paged_mesh_dp{dp}_tp{tp}", mesh)]
        results = {}
        for name, m in waves:
            run_once(m)                                # compile
            results[name] = run_once(m)
        base_sha = results["paged_single_device"][2]
        for name, (secs, stalls, sha) in results.items():
            emit(name, wtotal, secs,
                 mix="8 reqs plen24 new48 over 4 slots, greedy",
                 decode_stall_p50_ms=round(
                     1e3 * float(np.percentile(stalls, 50)), 2),
                 decode_stall_p99_ms=round(
                     1e3 * float(np.percentile(stalls, 99)), 2),
                 output_sha=sha[:16],
                 output_identical=(sha == base_sha))
        if any(sha != base_sha for _, _, sha in results.values()):
            print(json.dumps({"error": "sharded paged output "
                              "diverged from single-device"}),
                  flush=True)
            raise SystemExit(2)

    # 9b. the expert-parallel MoE wave (--moe): the SAME greedy mix
    # through a single-device MoE paged server and the (dp, tp)-mesh
    # one — experts sharded over tp, decode routing through moe_ffn's
    # tiled all_to_all with the drop-free auto capacity. Identity is
    # CHECKED (sha gate): expert parallelism moves the exchange onto
    # more chips, never changes tokens. Reports warm tokens/s and
    # decode-stall p50/p99 for both topologies plus the overflow-drop
    # rate from the /serving moe counters (banked into --metrics-out);
    # rows carry an explicit onchip stamp so CPU-smoke numbers can
    # never masquerade as chip measurements. Needs >=4 devices;
    # emits a skipped line otherwise.
    def moe_bench():
        import hashlib
        ndev = len(jax.devices())
        if ndev < 4:
            print(json.dumps({
                "engine": "serving_moe", "skipped": True,
                "reason": f"needs >=4 devices, have {ndev} (CPU smoke:"
                          " XLA_FLAGS=--xla_force_host_platform"
                          "_device_count=8)"}), flush=True)
            return
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
        mcfg = tfm.TransformerConfig(
            vocab=1024, d_model=d, n_heads=8, head_dim=d // 8,
            n_layers=2, d_ff=2 * d, n_experts=4, moe_top_k=2,
            moe_capacity=4.0, dtype=cfg.dtype)
        mparams = tfm.init_params(mcfg, jax.random.PRNGKey(4))
        wreqs = [(rng.integers(1, 1000, 24).tolist(), 48)
                 for _ in range(8)]
        wtotal = sum(m for _, m in wreqs)

        def run_once(m):
            srv = ContinuousServer(mparams, mcfg, slots=4, smax=128,
                                   paged=True, mesh=m)
            for p, mx in wreqs:
                srv.submit(p, max_new=mx)
            t0 = time.perf_counter()
            stalls = []
            alive = True
            while alive:
                s0 = time.perf_counter()
                alive = srv.step()
                stalls.append(time.perf_counter() - s0)
            secs = time.perf_counter() - t0
            out, srv._done = srv._done, {}
            sha = hashlib.sha256(json.dumps(
                [out[r] for r in sorted(out)]).encode()).hexdigest()
            routed, dropped = srv._moe_routed, srv._moe_dropped
            drop_rate = dropped / max(routed + dropped, 1.0)
            return secs, stalls, sha, routed, dropped, drop_rate

        waves = [("serving_moe_single_device", None),
                 ("serving_moe_mesh_dp2_tp2", mesh)]
        results = {}
        for name, m in waves:
            run_once(m)                                # compile
            results[name] = run_once(m)
        base_sha = results["serving_moe_single_device"][2]
        for name, (secs, stalls, sha, routed, dropped,
                   drop_rate) in results.items():
            emit(name, wtotal, secs,
                 mix="8 reqs plen24 new48 over 4 slots, greedy, "
                     "4 experts top-2, auto capacity",
                 decode_stall_p50_ms=round(
                     1e3 * float(np.percentile(stalls, 50)), 2),
                 decode_stall_p99_ms=round(
                     1e3 * float(np.percentile(stalls, 99)), 2),
                 moe_tokens_routed=int(routed),
                 moe_tokens_dropped=int(dropped),
                 moe_overflow_drop_rate=round(drop_rate, 4),
                 onchip=on_tpu,
                 output_sha=sha[:16],
                 output_identical=(sha == base_sha))
            collected_counters[f"{name}/moe_tokens_routed"] = \
                int(routed)
            collected_counters[f"{name}/moe_tokens_dropped"] = \
                int(dropped)
            collected_counters[f"{name}/moe_overflow_drop_rate"] = \
                round(drop_rate, 6)
        if any(sha != base_sha
               for _, _, sha, _, _, _ in results.values()):
            print(json.dumps({"error": "expert-parallel MoE output "
                              "diverged from single-device"}),
                  flush=True)
            raise SystemExit(2)

    # 7. the chaos wave: fault-free vs seeded-fault-schedule runs of
    # one mixed paged+spec mix. The schedule is chosen so every fault
    # CLASS recovers at least once: two verify faults walk the spec
    # degradation ladder (speculation off, sequential decode takes
    # over — which is what lets the later decode faults fire), a
    # prefill fault restarts a pending chunked prefill while live
    # slots restore, and an alloc fault with nothing evictable
    # (prefix_reuse off) escalates to the step-level restore path.
    # Identity is CHECKED: both runs' outputs are hashed.
    def chaos_bench():
        import hashlib
        from hpx_tpu.svc import faultinject
        crng = np.random.default_rng(7)
        creqs = [(crng.integers(1, 1000,
                                int(crng.integers(6, 40))).tolist(),
                  int(crng.integers(16, 33))) for _ in range(10)]
        ctotal = sum(m for _, m in creqs)
        SCHEDULE = {"verify": {1, 2}, "prefill": {6},
                    "decode": {3, 11}, "alloc": {50}}

        def run_wave(fi=None):
            srv = ContinuousServer(params, cfg, slots=4, smax=128,
                                   paged=True, block_size=8,
                                   prefix_reuse=False, spec=True,
                                   prefill_chunk=8)
            for p, m in creqs:
                srv.submit(p, max_new=m)
            if fi is not None:
                faultinject.install(fi)
            t0 = time.perf_counter()
            try:
                out = srv.run()
            finally:
                faultinject.uninstall()
            secs = time.perf_counter() - t0
            sha = hashlib.sha256(json.dumps(
                [out[r] for r in sorted(out)]).encode()).hexdigest()
            return srv, out, secs, sha

        run_wave()                                     # compile
        base_srv, base_out, base_secs, base_sha = run_wave()
        free0 = base_srv._alloc.stats()["free"]
        srv, out, secs, sha = run_wave(
            faultinject.FaultInjector(seed=0, schedule=SCHEDULE))
        st = srv.fault_stats()
        goodput = sum(len(t) for t in out.values())
        emit("serving_chaos", goodput, secs,
             mix="10 reqs plen6-39 new16-32, paged+spec over 4 slots",
             fault_schedule={k: sorted(v)
                             for k, v in SCHEDULE.items()},
             faultfree_tokens_per_s=round(ctotal / base_secs, 1),
             injected=st["injected"], recovered=st["restored"],
             restored_by_site=st["restored_by_site"],
             restore_p99_ms=round(1e3 * st["restore_p99_s"], 3),
             shed=st["shed"], degraded=st["degraded"],
             kv_blocks_leaked=free0 - srv._alloc.stats()["free"],
             output_sha=sha[:16],
             output_identical=(sha == base_sha))
        missing = [s for s in ("decode", "prefill", "verify", "alloc")
                   if not st["restored_by_site"].get(s)]
        if sha != base_sha or missing:
            print(json.dumps({
                "error": "chaos gate failed",
                "baseline_sha": base_sha[:16], "chaos_sha": sha[:16],
                "classes_without_restore": missing}), flush=True)
            raise SystemExit(2)

        # overload sub-run: every decode dispatch faults, recovery
        # can never complete a step — the retry budget exhausts and
        # every request sheds TYPED instead of looping forever
        srv = ContinuousServer(params, cfg, slots=4, smax=128)
        for p, m in creqs[:4]:
            srv.submit(p, max_new=m)
        faultinject.install(faultinject.FaultInjector(
            seed=0, rate=1.0, sites=["decode"]))
        try:
            shed_out = srv.run()
        finally:
            faultinject.uninstall()
        print(json.dumps({
            "engine": "serving_chaos_overload",
            "completed": len(shed_out),
            "shed_typed": len(srv.failed),
            "errors": sorted({type(e).__name__
                              for e in srv.failed.values()}),
        }), flush=True)

    # 7b. the observability wave: exemplars + burn-rate alerting over
    # two sub-runs with identical request streams. The healthy run
    # must collect >=1 exemplar per recorded SLO histogram, each rid
    # resolving to a complete (submit..retire) timeline; the seeded
    # regression run (decode faults -> retry backoff inflates decode
    # stalls past the rule threshold) must fire EXACTLY one
    # flight-bundle-capturing alert and clear on recovery.
    def alerts_bench() -> None:
        import glob
        import tempfile
        from hpx_tpu.core.config import runtime_config
        from hpx_tpu.svc import faultinject
        rc = runtime_config()
        arng = np.random.default_rng(11)
        areqs = [(arng.integers(1, 1000,
                                int(arng.integers(6, 24))).tolist(),
                  int(arng.integers(16, 33))) for _ in range(8)]
        atotal = sum(m for _, m in areqs)
        fdir = tempfile.mkdtemp(prefix="hpx-alerts-")
        knobs = {
            "hpx.obs.exemplars": "1",
            "hpx.obs.exemplar_quantile": "0.9",
            "hpx.obs.alert_interval_s": "0.02",
            "hpx.flight.dir": fdir,
        }
        defaults = {
            "hpx.obs.exemplars": "0",
            "hpx.obs.exemplar_quantile": "0.95",
            "hpx.obs.alerts": "0",
            "hpx.obs.alert_rules": "",
            "hpx.obs.alert_fast_s": "300",
            "hpx.obs.alert_slow_s": "3600",
            "hpx.obs.alert_burn_fast": "14.4",
            "hpx.obs.alert_burn_slow": "6",
            "hpx.obs.alert_interval_s": "1.0",
            "hpx.flight.dir": "auto",
            "hpx.serving.retry_backoff_s": "0.005",
        }
        for k, v in knobs.items():
            rc.set(k, v)

        def run_wave(fi=None):
            srv = ContinuousServer(params, cfg, slots=4, smax=128)
            for p, m in areqs:
                srv.submit(p, max_new=m)
            if fi is not None:
                faultinject.install(fi)
            t0 = time.perf_counter()
            try:
                out = srv.run()
            finally:
                faultinject.uninstall()
            return srv, out, time.perf_counter() - t0

        try:
            # compile run doubles as cadence calibration: decode_stall
            # IS the inter-step gap, so the SLO threshold must sit
            # between this host's healthy step time and the injected
            # fault-retry stall (step + backoff) — absolute numbers
            # would fire spuriously on a loaded box and never fire on
            # a fast one.  The fast burn window spans several fault
            # periods so alternating good/bad steps can't flap the FSM
            # (a clear fires whenever the fast window drains).
            csrv, _, _ = run_wave()
            from hpx_tpu.svc.metrics import HistogramCounter as _HC
            cal = _HC.from_snapshot(
                csrv.hist["decode_stall"].snapshot())
            p50 = cal.quantile(0.5) if cal.count else 0.005
            thr = min(max(0.05, 3.0 * p50), 2.0)
            backoff = min(max(0.2, 3.0 * thr), 4.0)
            fast_s = 3.0 * (p50 + backoff)
            for k, v in {
                "hpx.obs.alerts": "1",
                "hpx.obs.alert_rules": f"decode_stall:{thr:.3f}:0.9",
                "hpx.obs.alert_fast_s": f"{fast_s:.3f}",
                "hpx.obs.alert_slow_s": f"{3.0 * fast_s:.3f}",
                "hpx.obs.alert_burn_fast": "3",
                "hpx.obs.alert_burn_slow": "1.5",
                "hpx.serving.retry_backoff_s": f"{backoff:.3f}",
            }.items():
                rc.set(k, v)
            srv, out, secs = run_wave()                 # healthy
            bad_exemplars = []
            exemplar_counts = {}
            for key in ("ttft", "queue_wait", "decode_stall", "e2e"):
                h = srv.hist[key]
                if not h.count:
                    continue
                collected_hists[f"alerts/{key}"] = h
                exs = h.snapshot().get("exemplars", [])
                resolved = 0
                for e in exs:
                    evs = srv.timeline.events(e["rid"]) \
                        if e["rid"] is not None else []
                    names = {ev["name"] for ev in evs}
                    if "submit" in names and "retire" in names:
                        resolved += 1
                if not resolved:
                    bad_exemplars.append(key)
                exemplar_counts[key] = [len(exs), resolved]
            healthy_fired = srv._alerts.fired

            pre_bundles = set(glob.glob(
                os.path.join(fdir, "flight-*-slo_alert.json")))

            # regression: a burst of decode faults, each retried with
            # the elevated backoff — every faulted step's inter-step
            # gap sits at >= backoff >= 3x the calibrated rule
            # threshold until the schedule runs dry
            fi = faultinject.FaultInjector(
                seed=0, schedule={"decode": set(range(2, 40, 2))})
            rsrv, rout, rsecs = run_wave(fi)
            fired, cleared = rsrv._alerts.fired, rsrv._alerts.cleared
            bundles = sorted(set(glob.glob(
                os.path.join(fdir, "flight-*-slo_alert.json")))
                - pre_bundles)
            emit("serving_alerts", atotal, secs,
                 mix="8 reqs plen6-23 new16-32 over 4 slots, "
                     "healthy + seeded decode regression",
                 exemplars={k: v[0] for k, v in
                            exemplar_counts.items()},
                 exemplars_resolved={k: v[1] for k, v in
                                     exemplar_counts.items()},
                 healthy_fired=healthy_fired,
                 calibration={"stall_p50_s": round(p50, 4),
                              "threshold_s": round(thr, 3),
                              "retry_backoff_s": round(backoff, 3),
                              "fast_window_s": round(fast_s, 3)},
                 regression_secs=round(rsecs, 4),
                 regression_fired=fired,
                 regression_cleared=cleared,
                 alert_bundles=len(bundles),
                 alert_state=rsrv._alerts.state()["rules"])
            if (bad_exemplars or healthy_fired
                    or fired != 1 or len(bundles) != 1):
                print(json.dumps({
                    "error": "alerts gate failed",
                    "hists_without_resolved_exemplar": bad_exemplars,
                    "healthy_fired": healthy_fired,
                    "regression_fired": fired,
                    "alert_bundles": [os.path.basename(b)
                                      for b in bundles],
                }), flush=True)
                raise SystemExit(2)
        finally:
            for k, v in defaults.items():
                rc.set(k, v)

    # 8. the disaggregated wave: Poisson arrivals over Zipf-shared
    # prefixes with a 70/30 interactive/batch SLO mix, measured twice —
    # colocated paged server vs DisaggRouter — with identical request
    # streams. Percentiles are wall-clock (TTFT = submit->first token;
    # decode stall = inter-step gap while any request is live), so this
    # wave is a latency-shape comparison, not a correctness gate —
    # except under --chaos, where a seeded kill of one worker per role
    # must leave tokens sha-identical and leak zero KV blocks.
    def disagg_bench(chaos: bool) -> None:
        import hashlib
        from hpx_tpu.models.disagg import DisaggRouter
        from hpx_tpu.svc import faultinject

        drng = np.random.default_rng(11)
        npfx = 6
        prefixes = [drng.integers(1, 1000, 32).tolist()
                    for _ in range(npfx)]
        # Zipf over the prefix pool: rank r drawn with weight 1/r
        zw = np.array([1.0 / (r + 1) for r in range(npfx)])
        zw /= zw.sum()
        nreq = 12
        arrivals = np.cumsum(drng.exponential(0.05, nreq))  # Poisson
        wave = []
        for i in range(nreq):
            pfx = prefixes[int(drng.choice(npfx, p=zw))]
            tail = drng.integers(1, 1000,
                                 int(drng.integers(4, 12))).tolist()
            slo = "interactive" if drng.random() < 0.7 else "batch"
            wave.append((pfx + tail, int(drng.integers(12, 25)),
                         slo, float(arrivals[i])))
        wtotal = sum(m for _, m, _, _ in wave)

        def pctl(xs, q):
            return round(float(np.percentile(xs, q)) * 1e3, 2) \
                if xs else None

        def drive(submit, step, ttft_of):
            """Poisson-paced open loop: submit at arrival offsets,
            step in between; returns (outputs, secs, stalls)."""
            t0 = time.perf_counter()
            pending = list(enumerate(wave))
            stalls, live, last = [], False, t0
            out = None
            while pending or out is None or out:
                now = time.perf_counter() - t0
                while pending and pending[0][1][3] <= now:
                    _, (p, m, slo, _) = pending.pop(0)
                    submit(p, m, slo)
                out = step()
                t = time.perf_counter()
                if live:
                    stalls.append(t - last)
                live, last = bool(out), t
            return time.perf_counter() - t0, stalls

        def run_colocated():
            srv = ContinuousServer(params, cfg, slots=4, smax=96,
                                   paged=True)
            secs, stalls = drive(
                lambda p, m, slo: srv.submit(p, max_new=m),
                srv.step, None)
            out = dict(srv._done)
            return out, dict(srv.ttft), secs, stalls, srv.hist

        def run_disagg(fi=None):
            if fi is not None:
                faultinject.install(fi)
            try:
                r = DisaggRouter(params, cfg, prefill_workers=2,
                                 decode_workers=2, slots=4, smax=96)
                secs, stalls = drive(
                    lambda p, m, slo: r.submit(p, m, slo=slo),
                    r.step, None)
                out = dict(r.results)
                st = r.stats()
                hists = r.merged_hist()
                r.close()
                leak = r.leaked_blocks()
            finally:
                if fi is not None:
                    faultinject.uninstall()
            return out, dict(r.ttft), secs, stalls, st, leak, hists

        def sha(out):
            return hashlib.sha256(json.dumps(
                [out[r] for r in sorted(out)]).encode()).hexdigest()

        def hq(h, q):
            return round(h.quantile(q) * 1e3, 2)

        run_colocated()                                # compile
        run_disagg()                                   # compile
        co_out, co_ttft, co_secs, co_stalls, co_hist = run_colocated()
        dg_out, dg_ttft, dg_secs, dg_stalls, dg_st, dg_leak, \
            dg_hist = run_disagg()
        for name, out, ttft, secs, stalls, hists, extra in (
                ("serving_colocated", co_out, co_ttft, co_secs,
                 co_stalls, co_hist, {}),
                ("serving_disagg", dg_out, dg_ttft, dg_secs,
                 dg_stalls, dg_hist,
                 {"workers": "2 prefill + 2 decode",
                  "failovers": dg_st["failovers"],
                  "kv_blocks_leaked": dg_leak})):
            goodput = sum(len(t) for t in out.values())
            ts = sorted(ttft.values())
            line = {"mix": f"{nreq} reqs, {npfx} Zipf prefixes, "
                           "70/30 interactive/batch, Poisson 50ms",
                    "ttft_p50_ms": pctl(ts, 50),
                    "ttft_p95_ms": pctl(ts, 95),
                    "ttft_p99_ms": pctl(ts, 99),
                    "decode_stall_p50_ms": pctl(stalls, 50),
                    "decode_stall_p99_ms": pctl(stalls, 99),
                    # live-histogram view (svc/metrics, merged across
                    # workers for disagg) of the same SLOs
                    "slo_hist_ms": {
                        k: {"p50": hq(hists[k], 0.5),
                            "p95": hq(hists[k], 0.95),
                            "p99": hq(hists[k], 0.99)}
                        for k in ("ttft", "queue_wait",
                                  "decode_stall")}}
            line.update(extra)
            emit(name, goodput, secs, **line)
            for k, h in hists.items():
                collected_hists[f"{name}/{k}"] = h
        if co_out != {r: t for r, t in dg_out.items()}:
            print(json.dumps({"error": "disagg diverged from "
                              "colocated"}), flush=True)
            raise SystemExit(2)
        if not chaos:
            return

        # chaos sub-run: one seeded kill per role mid-flight; gated
        base_sha = sha(dg_out)
        ch_out, _, ch_secs, _, ch_st, ch_leak = run_disagg(
            faultinject.FaultInjector(schedule={
                "disagg.prefill": {9}, "disagg.decode": {30}}))
        ch_sha = sha(ch_out)
        emit("serving_disagg_chaos",
             sum(len(t) for t in ch_out.values()), ch_secs,
             fault_schedule={"disagg.prefill": [9],
                             "disagg.decode": [30]},
             failovers=ch_st["failovers"],
             degraded=ch_st["degraded"],
             kv_blocks_leaked=ch_leak,
             output_sha=ch_sha[:16],
             output_identical=(ch_sha == base_sha))
        if (ch_sha != base_sha or ch_leak != 0
                or not ch_st["failovers"]["prefill"]
                or not ch_st["failovers"]["decode"]):
            print(json.dumps({
                "error": "disagg chaos gate failed",
                "baseline_sha": base_sha[:16],
                "chaos_sha": ch_sha[:16],
                "failovers": ch_st["failovers"],
                "kv_blocks_leaked": ch_leak}), flush=True)
            raise SystemExit(2)

    def fleet_bench() -> None:
        import hashlib
        from hpx_tpu.core.config import runtime_config
        from hpx_tpu.svc import metrics as svc_metrics
        from hpx_tpu.svc.fleet import FleetRouter

        frng = np.random.default_rng(17)
        npfx = 4
        prefixes = [frng.integers(1, 1000, 40).tolist()
                    for _ in range(npfx)]
        zw = np.array([1.0 / (r + 1) for r in range(npfx)])
        zw /= zw.sum()
        nreq = 12
        arrivals = np.cumsum(frng.exponential(0.05, nreq))
        wave = []
        for i in range(nreq):
            pfx = prefixes[int(frng.choice(npfx, p=zw))]
            tail = frng.integers(1, 1000,
                                 int(frng.integers(4, 12))).tolist()
            wave.append((pfx + tail, int(frng.integers(10, 20)),
                         float(arrivals[i])))

        def pctl(xs, q):
            return round(float(np.percentile(xs, q)) * 1e3, 2) \
                if xs else None

        def drive(r):
            t0 = time.perf_counter()
            pending = list(wave)
            stalls, live, last = [], False, t0
            busy = None
            while pending or busy is None or busy:
                now = time.perf_counter() - t0
                while pending and pending[0][2] <= now:
                    p, m, _ = pending.pop(0)
                    r.submit(p, m)
                busy = r.step()
                t = time.perf_counter()
                if live:
                    stalls.append(t - last)
                live, last = bool(busy), t
            return time.perf_counter() - t0, stalls

        def run_mode(mode):
            rc = runtime_config()
            old = {k: rc.get(k) for k in
                   ("hpx.serving.fleet.placement",
                    "hpx.serving.fleet.digest_refresh_s")}
            rc.set("hpx.serving.fleet.placement", mode)
            rc.set("hpx.serving.fleet.digest_refresh_s", "0.01")
            try:
                r = FleetRouter(params, cfg, prefill_workers=2,
                                decode_workers=2, slots=4, smax=96)
                # two cold passes (same mix, unpaced): the first
                # warms the decode workers' radix trees, the second
                # takes placement hits and compiles the SEEDED
                # prefill programs — so the measured wave is the
                # steady Zipf state placement is for
                for _ in range(2):
                    for p, m, _ in wave:
                        r.submit(p, m)
                    r.run()
                warm_stats = r.stats()
                secs, stalls = drive(r)
                out = dict(r.results)
                st = r.stats()
                merged = r.merged_hist()
                wsnaps = [{k: h.snapshot() for k, h in per.items()}
                          for per in r.whist.values()]
                if tracer is not None and mode == "prefix":
                    # harvest the worker rings BEFORE close() tears
                    # the handles down; finish() stitches them
                    fleet_trace_docs[:] = r.worker_trace_docs()
                ttft = {rid: r.ttft[rid] for rid in out
                        if rid in r.ttft}
                r.close()
                leak = r.leaked_blocks()
            finally:
                for k, v in old.items():
                    if v is None:
                        rc._data.pop(k, None)
                    else:
                        rc.set(k, v)
            saved = (st["prefill_tokens_saved"]
                     - warm_stats["prefill_tokens_saved"])
            placed = {"prefix": st["placed_prefix"]
                      - warm_stats["placed_prefix"],
                      "load": st["placed_load"]
                      - warm_stats["placed_load"]}
            return (out, ttft, secs, stalls, placed, saved, leak,
                    merged, wsnaps)

        def sha(out):
            return hashlib.sha256(json.dumps(
                [out[r] for r in sorted(out)]).encode()).hexdigest()

        def hq(h, q):
            return round(h.quantile(q) * 1e3, 2)

        results = {}
        for mode in ("load", "prefix"):
            out, ttft, secs, stalls, placed, saved, leak, merged, \
                wsnaps = run_mode(mode)
            results[mode] = (out, saved, leak)
            # fleet-wide == merge() of the per-worker histograms:
            # re-fold the per-worker SNAPSHOTS independently and
            # compare against the router's merged view
            refold = svc_metrics.latency_histograms()
            for snap in wsnaps:
                for k in refold:
                    refold[k] = refold[k].merge(
                        svc_metrics.HistogramCounter.from_snapshot(
                            snap[k]))
            merge_identity = all(
                refold[k].snapshot()["counts"]
                == merged[k].snapshot()["counts"]
                and refold[k].snapshot()["count"]
                == merged[k].snapshot()["count"]
                for k in refold)
            ts = sorted(ttft.values())
            emit(f"serving_fleet_{mode}",
                 sum(len(t) for t in out.values()), secs,
                 mix=f"{nreq} reqs, {npfx} Zipf prefixes, "
                     "Poisson 50ms, warm caches",
                 workers="2 prefill + 2 decode",
                 placement=placed,
                 prefill_tokens_saved=saved,
                 ttft_p50_ms=pctl(ts, 50),
                 ttft_p99_ms=pctl(ts, 99),
                 decode_stall_p50_ms=pctl(stalls, 50),
                 decode_stall_p99_ms=pctl(stalls, 99),
                 slo_hist_ms={
                     k: {"p50": hq(merged[k], 0.5),
                         "p95": hq(merged[k], 0.95),
                         "p99": hq(merged[k], 0.99)}
                     for k in ("ttft", "queue_wait", "decode_stall")},
                 hist_merge_identity=merge_identity,
                 kv_blocks_leaked=leak,
                 output_sha=sha(out)[:16])
            for k, h in merged.items():
                collected_hists[f"serving_fleet_{mode}/{k}"] = h
            if not merge_identity:
                print(json.dumps({
                    "error": "fleet-wide histograms != merge() of "
                             "per-worker histograms"}), flush=True)
                raise SystemExit(2)
        (lo, lo_saved, lo_leak) = results["load"]
        (pf, pf_saved, pf_leak) = results["prefix"]
        if (sha(lo) != sha(pf) or pf_saved <= lo_saved
                or lo_leak != 0 or pf_leak != 0):
            print(json.dumps({
                "error": "fleet gate failed",
                "load_sha": sha(lo)[:16],
                "prefix_sha": sha(pf)[:16],
                "prefill_tokens_saved": {"load": lo_saved,
                                         "prefix": pf_saved},
                "kv_blocks_leaked": {"load": lo_leak,
                                     "prefix": pf_leak}}),
                flush=True)
            raise SystemExit(2)

    def finish() -> int:
        if tracer is not None:
            from hpx_tpu.svc import tracing
            tracing.stop_tracing()
            if fleet_trace_docs:
                # stitch router + every worker ring into ONE trace:
                # per-worker pid rows, clock-aligned, rid flow arrows
                from hpx_tpu.svc.trace_export import (
                    merge_traces, to_chrome_trace, write_trace_doc)
                router_doc = to_chrome_trace(
                    tracer.snapshot(), tracer.thread_names(),
                    tracer.t0, tracer.dropped,
                    t0_wall=tracer.t0_wall)
                doc = merge_traces([("router", router_doc)]
                                   + fleet_trace_docs)
                write_trace_doc(trace_out, doc)
                print(json.dumps({
                    "trace": os.path.abspath(trace_out),
                    "trace_events": len(doc["traceEvents"]),
                    "dropped_events":
                        doc["otherData"]["dropped_events"],
                    "stitched_processes":
                        doc["otherData"]["processes"],
                    "stitched_rids": doc["otherData"]["stitched_rids"],
                    "rid_flow_arrows":
                        doc["otherData"]["rid_flow_arrows"],
                }), flush=True)
            else:
                doc = tracer.export(trace_out)
                print(json.dumps({
                    "trace": os.path.abspath(trace_out),
                    "trace_events": len(doc["traceEvents"]),
                    "dropped_events":
                        doc["otherData"]["dropped_events"],
                }), flush=True)
        if metrics_out:
            from hpx_tpu.svc import metrics as svc_metrics
            reg = svc_metrics.registry_snapshot("*")
            doc = metrics_artifact(
                collected_hists,
                counters={**reg["counters"], **collected_counters})
            doc["compiles"] = dict(collected_compiles)
            if profiler is not None:
                from hpx_tpu.svc import progprof
                doc["programs"] = profiler.profile_table()
                progprof.stop_profiling()
            write_metrics_artifact(metrics_out, doc)
            print(json.dumps({
                "metrics": os.path.abspath(metrics_out),
                "schema": doc["schema"],
                "histograms": len(doc["histograms"]),
                "programs": len(doc.get("programs", {})
                                .get("programs", []))
                if profiler is not None else 0,
            }), flush=True)
        return 0

    if "--prefix-only" in sys.argv:
        paged_prefix_bench()
        return finish()

    if "--tier" in sys.argv:
        tier_bench()
        return finish()

    if "--spec-only" in sys.argv:
        spec_wave_bench()
        return finish()

    if "--paged-decode-only" in sys.argv:
        paged_decode_bench()
        return finish()

    if "--mesh" in sys.argv:
        mesh_paged_bench()
        return finish()

    if "--moe" in sys.argv:
        moe_bench()
        return finish()

    if "--disagg" in sys.argv:
        disagg_bench("--chaos" in sys.argv)
        return finish()

    if "--fleet" in sys.argv:
        fleet_bench()
        return finish()

    if "--alerts" in sys.argv:
        alerts_bench()
        return finish()

    if "--chaos" in sys.argv:
        chaos_bench()
        return finish()

    # 1. uniform batched greedy
    B, plen, max_new = 8, 32, 64
    prompt = jnp.asarray(rng.integers(1, 1000, (B, plen)), jnp.int32)
    tfm.generate(params, cfg, prompt, max_new=4)       # compile
    t0 = time.perf_counter()
    out = tfm.generate(params, cfg, prompt, max_new=max_new)
    jax.block_until_ready(out)
    emit("generate", B * max_new, time.perf_counter() - t0,
         mix=f"B{B} plen{plen} new{max_new}")

    # 2. continuous batching over a ragged mix (pre-bucketed plens:
    # the legacy-friendly shape; the mixed_length wave below is the
    # hard case)
    reqs = [(rng.integers(1, 1000, 8 * int(rng.integers(1, 7))).tolist(),
             int(rng.integers(16, 96))) for _ in range(12)]
    total_new = sum(m for _, m in reqs)
    srv = ContinuousServer(params, cfg, slots=4, smax=160)
    for p, m in reqs[:1]:
        srv.submit(p, max_new=m)
    srv.run()                                          # compile slots
    srv = ContinuousServer(params, cfg, slots=4, smax=160)
    for p, m in reqs:
        srv.submit(p, max_new=m)
    t0 = time.perf_counter()
    srv.run()
    emit("continuous_batching", total_new, time.perf_counter() - t0,
         mix="12 reqs plen8-48(x8 buckets) new16-96 over 4 slots")

    # 2b. mixed UNBUCKETED prompt lengths — the compile-storm shape the
    # bucketed chunked prefill exists for. A manual step loop times
    # every step (decode-stall p99: a prefill blocking the batch shows
    # up here), TTFT comes straight from srv.ttft, and compile counts
    # from jax.monitoring — reported for the COLD server; throughput
    # and stalls for the warm one.
    def mixed_length_bench():
        from hpx_tpu.utils.compilemon import count_compiles
        mreqs = [(rng.integers(
                      1, 1000, int(rng.integers(5, 150))).tolist(),
                  int(rng.integers(16, 96))) for _ in range(12)]
        mtotal = sum(m for _, m in mreqs)

        def run_mixed():
            with count_compiles() as c:
                srv = ContinuousServer(params, cfg, slots=4, smax=256)
                for p, m in mreqs:
                    srv.submit(p, max_new=m)
                t0 = time.perf_counter()
                stalls = []
                alive = True
                while alive:
                    s0 = time.perf_counter()
                    alive = srv.step()
                    stalls.append(time.perf_counter() - s0)
                secs = time.perf_counter() - t0
            srv._done.clear()
            return srv, secs, stalls, int(c)

        cold_srv, _, _, cold_compiles = run_mixed()
        srv, secs, stalls, warm_compiles = run_mixed()
        collected_compiles["continuous_batching_mixed"] = {
            "cold": cold_compiles, "warm": warm_compiles}
        ttfts = list(srv.ttft.values())
        emit("continuous_batching_mixed", mtotal, secs,
             mix="12 reqs plen5-149 (unbucketed) new16-96 over 4 slots",
             compiles_cold=cold_compiles,
             programs_built=cold_srv._prog_misses,
             prefill_chunks=srv._chunks,
             ttft_mean_ms=round(1e3 * sum(ttfts) / len(ttfts), 2),
             ttft_max_ms=round(1e3 * max(ttfts), 2),
             decode_stall_p99_ms=round(
                 1e3 * float(np.percentile(stalls, 99)), 2))

    mixed_length_bench()
    spec_wave_bench()

    # 3. speculative greedy (single stream: the latency case)
    sp = jnp.asarray(rng.integers(1, 1000, (1, plen)), jnp.int32)
    tfm.speculative_generate(params, cfg, draft, draft_cfg, sp,
                             max_new=4, k=4)           # compile
    t0 = time.perf_counter()
    out, rounds = tfm.speculative_generate(
        params, cfg, draft, draft_cfg, sp, max_new=max_new, k=4,
        return_stats=True)
    jax.block_until_ready(out)
    emit("speculative", max_new, time.perf_counter() - t0,
         rounds=int(rounds),
         tokens_per_target_forward=round(max_new / int(rounds), 2))
    t0 = time.perf_counter()
    out = tfm.generate(params, cfg, sp, max_new=max_new)
    jax.block_until_ready(out)
    emit("generate_single_stream", max_new, time.perf_counter() - t0)

    paged_prefix_bench()
    paged_decode_bench()
    return finish()


if __name__ == "__main__":
    sys.exit(main())
