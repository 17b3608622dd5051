"""Central declaration of every ``hpx.*`` configuration key.

Reference analog: HPX's generated ini default groups in
runtime_configuration.cpp — every knob the runtime understands is
declared in one place with its type and default, so a typo'd key is a
startup error instead of a silently-ignored setting.

Each key the tree reads through ``Configuration.get*`` must be declared
here with its value type, compiled-in default (``None`` when the read
site carries its own inline default), and a one-line doc string.
``hpxlint`` rule HPX014 cross-checks this registry against every
``cfg.get*("hpx....")`` call in the tree: undeclared reads, declared
keys nothing reads, and getter/type mismatches all fail the lint gate.
``Configuration(strict=True)`` enforces the same contract at runtime.

Keys marked ``reserved=True`` exist for HPX interface parity (accepted
on the command line / ini so reference invocations keep working) but
have no reader yet; HPX014 skips them in its dead-key check.

Adding a config knob: declare it here FIRST (key, type, default, doc),
then read it via ``runtime_config().get_<type>(...)`` — in that order,
or HPX014 flags the read as undeclared and tier-1 fails.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

_VALID_TYPES = ("str", "int", "bool", "float")


@dataclasses.dataclass(frozen=True)
class ConfigKey:
    """One declared configuration knob."""

    key: str
    type: str                 # "str" | "int" | "bool" | "float"
    default: Optional[str]    # None = no compiled-in default
    doc: str
    reserved: bool = False    # HPX-parity: declared but not read (yet)
    # closed value set for enumerated str knobs (None = free-form).
    # ``Configuration(strict=True)`` rejects a set() outside it with
    # the valid set in the error — a typo'd kv_dtype=fp8_e5m2 fails at
    # the set, not as a silently-ignored setting downstream.
    choices: Optional[Tuple[str, ...]] = None


_SCHEMA: Dict[str, ConfigKey] = {}


def declare(key: str, type: str, default: Optional[str], doc: str,
            reserved: bool = False,
            choices: Optional[Tuple[str, ...]] = None) -> ConfigKey:
    """Register one knob; duplicate keys and unknown types are errors.
    ``choices`` declares a closed value set for enumerated str knobs
    (the declared default must be a member)."""
    if type not in _VALID_TYPES:
        raise ValueError(f"config key {key!r}: bad type {type!r} "
                         f"(expected one of {_VALID_TYPES})")
    if key in _SCHEMA:
        raise ValueError(f"config key {key!r} declared twice")
    if choices is not None:
        choices = tuple(choices)
        if type != "str":
            raise ValueError(f"config key {key!r}: choices= is only "
                             "meaningful for str knobs")
        if default is not None and default not in choices:
            raise ValueError(f"config key {key!r}: default {default!r} "
                             f"not in choices {choices}")
    entry = ConfigKey(key, type, default, doc, reserved, choices)
    _SCHEMA[key] = entry
    return entry


def is_declared(key: str) -> bool:
    return key in _SCHEMA


def lookup(key: str) -> Optional[ConfigKey]:
    return _SCHEMA.get(key)


def all_keys() -> Dict[str, ConfigKey]:
    """Copy of the full registry (key -> ConfigKey)."""
    return dict(_SCHEMA)


def defaults() -> Dict[str, str]:
    """The compiled-in defaults map consumed by ``config.DEFAULTS`` —
    exactly the declared keys that carry a non-None default."""
    return {k: e.default for k, e in _SCHEMA.items()
            if e.default is not None}


# ---------------------------------------------------------------------------
# Declarations. Order matches the historical config.DEFAULTS layout so
# the defaults() dict is drop-in identical; default-less keys (read
# sites carry their own inline defaults) follow, grouped by section.
# ---------------------------------------------------------------------------

# -- core / scheduling ------------------------------------------------------
declare("hpx.os_threads", "str", "auto", "host worker threads (auto = cores, floor 4)")
declare("hpx.localities", "int", "1", "number of localities in the launch")
declare("hpx.locality", "int", "0", "this process's locality id")
declare("hpx.queuing", "str", "local-priority-fifo",
        "scheduler choice", reserved=True)
declare("hpx.scheduler.native", "bool", "1",
        "use the C++ scheduler when available")
declare("hpx.stacks.small_size", "int", "0",
        "stackful-coroutine stack size (no stackful coroutines on host)",
        reserved=True)

# -- parcel layer -----------------------------------------------------------
declare("hpx.parcel.enable", "bool", "1",
        "parcel transport master switch", reserved=True)
declare("hpx.parcel.port", "int", "7910", "TCP port for the parcelport")
declare("hpx.startup_timeout", "float", "120",
        "seconds to wait for all localities at startup")
declare("hpx.parcel.address", "str", "127.0.0.1", "parcelport bind address")
declare("hpx.parcel.bootstrap", "str", "tcp",
        "bootstrap parcelport kind", reserved=True)
declare("hpx.parcel.max_message_size", "int", str(1 << 30),
        "largest admissible parcel in bytes", reserved=True)
declare("hpx.parcel.secret", "str", None,
        "shared HMAC secret for parcel authentication ('' = off)")
declare("hpx.parcel.allow_insecure", "bool", None,
        "permit unauthenticated parcels when no secret is set")
declare("hpx.parcel.bind_any", "bool", None,
        "bind the listening socket to 0.0.0.0 instead of the address")
declare("hpx.parcel.compression", "str", None,
        "wire compression codec ('' = off)")
declare("hpx.parcel.compression_min_bytes", "int", None,
        "compress only parcels at least this large")
declare("hpx.parcel.coalescing", "bool", None,
        "batch small parcels into one wire message")
declare("hpx.parcel.coalescing_count", "int", None,
        "max parcels folded into one coalesced message")
declare("hpx.parcel.coalescing_bytes", "int", None,
        "max coalesced payload bytes before an eager flush")
declare("hpx.parcel.coalescing_interval", "float", None,
        "seconds a parcel may wait in the coalescing buffer")
declare("hpx.parcel.endpoint", "str", None,
        "--hpx:hpx CLI sugar target (endpoint of locality 0)",
        reserved=True)

# -- AGAS / distributed control ---------------------------------------------
declare("hpx.agas.service_mode", "str", "bootstrap",
        "locality 0 hosts the registry", reserved=True)
declare("hpx.agas.max_pending_refcnt_requests", "int", "4096",
        "AGAS refcount request queue bound", reserved=True)
declare("hpx.agas.endpoint", "str", None,
        "--hpx:agas CLI sugar target (AGAS endpoint)", reserved=True)
declare("hpx.connect", "bool", None,
        "late-join this process to a running cluster")
declare("hpx.route_timeout", "float", None,
        "seconds an AGAS-routed parcel may wait for resolution")
declare("hpx.barrier_timeout", "float", None,
        "seconds a distributed barrier waits before failing")
declare("hpx.shutdown_timeout", "float", None,
        "seconds finalize waits for remote localities")
declare("hpx.ignore_batch_env", "bool", None,
        "--hpx:ignore-batch-env CLI sugar (consumed at config init)",
        reserved=True)
declare("hpx.dist.heartbeat_interval", "float", None,
        "seconds between liveness heartbeats (0 = off)")
declare("hpx.dist.heartbeat_suspect", "float", None,
        "missed-heartbeat seconds before a locality is suspect")
declare("hpx.dist.heartbeat_dead", "float", None,
        "missed-heartbeat seconds before a locality is declared dead")
declare("hpx.dist.idem_table_max", "int", None,
        "bounded idempotency table size for resilient actions")

# -- logging / diagnostics --------------------------------------------------
declare("hpx.logging.level", "str", "warning", "minimum logged severity")
declare("hpx.logging.destination", "str", "stderr", "log sink")
declare("hpx.diagnostics.dump_config", "bool", "0",
        "print the resolved configuration to stderr at runtime init")

# -- TPU backend ------------------------------------------------------------
declare("hpx.tpu.platform", "str", "auto", "auto | tpu | cpu", reserved=True)
declare("hpx.tpu.default_dtype", "str", "float32",
        "default device array dtype", reserved=True)
declare("hpx.tpu.donate_buffers", "bool", "1",
        "donate input buffers to XLA where safe", reserved=True)
declare("hpx.tpu.watcher_threads", "int", "2",
        "future-completion watcher pool width")
declare("hpx.tpu.eager_futures", "bool", "1",
        "device futures ready at dispatch")

# -- performance counters ---------------------------------------------------
declare("hpx.counters.enable", "bool", "1",
        "performance-counter registry master switch", reserved=True)
declare("hpx.counters.print", "str", None,
        "csv counter name patterns printed at finalize "
        "(--hpx:print-counter)")
declare("hpx.counters.print_interval", "float", None,
        "seconds between periodic counter prints (0 = finalize only)")

# -- KV cache ---------------------------------------------------------------
declare("hpx.cache.block_size", "str", "auto",
        "KV tokens per paged block (auto: HPX_PAGED_BLOCK env, then "
        "ops/paged_blocks.json as written by benchmarks/flash_tune.py "
        "--paged, then 16: ops.attention_pallas.resolve_paged_block)")
declare("hpx.cache.num_blocks", "str", "auto",
        "pool size (auto: 2x worst case)")
declare("hpx.cache.radix_budget_blocks", "str", "auto",
        "prefix-tree HBM budget")
declare("hpx.cache.prefix_reuse", "bool", "1",
        "radix prefix matching on admit")
declare("hpx.cache.kv_dtype", "str", "bf16",
        "paged pool storage: bf16 (compute dtype) | int8 (absmax-scaled "
        "integer blocks) | fp8 (e4m3 blocks, same f32 scale sidecars — "
        "~0.25x decode bytes/token vs an f32 compute dtype)",
        choices=("bf16", "int8", "fp8"))
declare("hpx.cache.tier.enable", "bool", "0",
        "host-RAM KV tier: radix evictions demote block rows (raw "
        "quantized bytes + scale sidecars) to pooled host buffers "
        "instead of dropping them")
declare("hpx.cache.tier.host_budget_mb", "int", "256",
        "host tier byte budget; LRU-to-oblivion past it")
declare("hpx.cache.tier.min_speedup", "float", "1.0",
        "promote only when estimated re-prefill time exceeds restore "
        "time by this factor")
declare("hpx.cache.tier.probe_mb", "int", "4",
        "host->device bandwidth probe transfer size")
declare("hpx.cache.tier.prefill_cost_us", "float", "50.0",
        "fallback per-token prefill cost when progprof has no live "
        "pg_chunk/cb_chunk samples yet")
declare("hpx.cache.tier.restore_overhead_us", "float", "200.0",
        "fixed per-promotion overhead added to the copy-time estimate "
        "(framing, checksum, splice dispatch)")

# -- serving ----------------------------------------------------------------
declare("hpx.serving.paged_kernel", "str", "auto",
        "decode-attention formulation: auto (fused on TPU, gather "
        "elsewhere) | gather (XLA oracle) | fused (bitwise Pallas "
        "block-table walk, O(S) VMEM scratch) | fused_online "
        "(flash-style online softmax, O(block) scratch — "
        "tolerance-budgeted vs the oracle, VMEM no longer bounds smax)",
        choices=("auto", "gather", "fused", "fused_online"))
declare("hpx.serving.prefill_chunk", "str", "auto",
        "prompt tokens per prefill chunk (int|auto: the width at which "
        "a chunk's arithmetic takes as long as its weight read on this "
        "device, 128 where the device is unknown)")
declare("hpx.serving.prefill_buckets", "str", "auto",
        "chunk-width ladder (csv|auto)")
declare("hpx.serving.async_dispatch", "bool", "1",
        "decode without per-step sync")
declare("hpx.serving.max_async_steps", "int", "32",
        "buffered steps before a sync")
declare("hpx.serving.spec.enable", "bool", "0",
        "speculative decode in serving")
declare("hpx.serving.spec.k", "int", "4", "draft tokens per slot per step")
declare("hpx.serving.spec.draft", "str", "prompt",
        "draft source: prompt | model")
declare("hpx.serving.spec.ngram", "int", "3",
        "max n-gram for prompt lookup")
declare("hpx.serving.spec.min_accept", "float", "0.3",
        "adaptive-k backoff threshold")
declare("hpx.serving.spec.adapt", "bool", "1",
        "per-slot adaptive k on/off")
declare("hpx.serving.spec.max_verify_faults", "int", "2",
        "verify faults before speculation self-disables")
declare("hpx.serving.ckpt_every", "int", "16",
        "tokens between slot checkpoints")
declare("hpx.serving.step_retries", "int", "4",
        "step attempts before shedding")
declare("hpx.serving.retry_backoff_s", "float", "0.005",
        "base step-retry backoff")
declare("hpx.serving.admit_retries", "int", "8",
        "admit OOM deferrals before shed")
declare("hpx.serving.default_deadline_s", "float", "0",
        "per-request deadline (0=none)")
declare("hpx.serving.disagg.max_queue", "int", None,
        "disaggregated router: bound on queued prefill jobs")
declare("hpx.serving.disagg.pump_steps", "int", None,
        "decode steps per disagg pump iteration")
declare("hpx.serving.disagg.prefill_jobs", "int", None,
        "concurrent prefill jobs per prefill worker")
declare("hpx.serving.disagg.xfer_retries", "int", None,
        "KV transfer attempts before failing over")
declare("hpx.serving.moe.capacity_factor", "int", "0",
        "MoE decode expert capacity factor as an integer PERCENT "
        "(100 = GShard cf 1.0; C = ceil(T*k*pct/100 / E)); 0 = auto = "
        "drop-free (cf = n_experts), the token-identity default. "
        "Lower trades overflow drops for smaller expert exchanges")
declare("hpx.serving.mesh.table_residency", "str", "sharded",
        "device block-table placement on mesh: sharded | replicated")
declare("hpx.serving.fleet.prefill_workers", "int", "2",
        "fleet: prefill workers stood up by default")
declare("hpx.serving.fleet.decode_workers", "int", "2",
        "fleet: decode workers stood up at construction")
declare("hpx.serving.fleet.decode_pool_min", "int", "1",
        "fleet: autoscale floor on decode workers")
declare("hpx.serving.fleet.decode_pool_max", "int", "4",
        "fleet: autoscale ceiling on decode workers")
declare("hpx.serving.fleet.digest_entries", "int", "64",
        "fleet: prefix-digest entries pulled per decode worker")
declare("hpx.serving.fleet.digest_refresh_s", "float", "0.25",
        "fleet: seconds a pulled prefix digest stays fresh")
declare("hpx.serving.fleet.placement", "str", "prefix",
        "fleet decode placement policy", choices=("prefix", "load"))
declare("hpx.serving.fleet.w_prefix", "float", "1.0",
        "fleet placement: score weight per digest-matched block")
declare("hpx.serving.fleet.w_pressure", "float", "0.05",
        "fleet placement: score penalty per eviction/s of pressure")
declare("hpx.serving.fleet.w_tier", "float", "0.25",
        "fleet placement: discount on w_prefix for blocks a worker "
        "holds only in its host tier (cold but restorable)")
declare("hpx.serving.fleet.scale_high", "int", "8",
        "fleet autoscale: queue depth that spins a decode worker up")
declare("hpx.serving.fleet.scale_low", "int", "0",
        "fleet autoscale: queue depth that drains a decode worker")
declare("hpx.serving.fleet.idle_ticks", "int", "16",
        "fleet autoscale: consecutive idle router ticks before an "
        "idle decode worker drains")

# -- fault injection --------------------------------------------------------
declare("hpx.fault.enable", "bool", "0", "svc/faultinject master switch")
declare("hpx.fault.seed", "int", "0", "rate-mode RNG seed")
declare("hpx.fault.rate", "float", "0.0", "per-check fault probability")
declare("hpx.fault.sites", "str", "", "csv armed sites ('' = all)")
declare("hpx.fault.max", "int", "0", "total fault cap (0 = unlimited)")
declare("hpx.fault.schedule", "str", "", "csv 'site:nth' exact schedule")
declare("hpx.fault.parcel_delay_s", "float", None,
        "injected parcel delivery delay for chaos runs")

# -- tracing ----------------------------------------------------------------
declare("hpx.trace.enabled", "bool", "0", "svc/tracing off by default")
declare("hpx.trace.buffer_events", "int", "65536",
        "ring capacity (drop-oldest)")
declare("hpx.trace.counter_interval", "float", "0.05",
        "s between counter samples")
declare("hpx.trace.counters", "str", "/serving*,/cache*,/threads*,/programs*",
        "csv counter patterns sampled into the trace")

# -- metrics (svc/metrics histograms + timelines) ---------------------------
declare("hpx.metrics.hist_lo", "float", "1e-6",
        "latency histogram lowest bucket bound, seconds (values below "
        "land in the underflow bucket)")
declare("hpx.metrics.hist_hi", "float", "1e4",
        "latency histogram highest bucket bound, seconds")
declare("hpx.metrics.hist_subbuckets", "int", "8",
        "histogram buckets per octave (gamma = 2**(1/n); 8 bounds "
        "quantile relative error at ~4.4%)")
declare("hpx.metrics.quantiles", "str", "0.5,0.95,0.99",
        "csv quantiles derived as .../pNN counters per histogram")
declare("hpx.metrics.timeline_capacity", "int", "1024",
        "rids retained per RequestTimeline (drop-oldest)")

# -- program profiler (svc/progprof) ----------------------------------------
declare("hpx.prof.programs", "bool", "0",
        "per-program continuous profiler: wrap every cached_program() "
        "build in a proxy that times its compile and the host's wall "
        "around each (asynchronous) call")

# -- flight recorder (svc/flight) -------------------------------------------
declare("hpx.flight.enabled", "bool", "1",
        "fault flight recorder master switch (lazy: allocates nothing "
        "until a fault capture fires)")
declare("hpx.flight.dir", "str", "auto",
        "directory for flight bundles (auto = <tmpdir>/hpx_tpu_flight)")
declare("hpx.flight.max_bundles", "int", "8",
        "bundles retained on disk (oldest pruned first)")
declare("hpx.flight.spans", "int", "256",
        "last-N trace spans captured into each bundle")

# -- live observability (svc/exemplars, svc/slo_alerts, svc/opsplane) ------
declare("hpx.obs.port", "int", "-1",
        "ops-plane HTTP port (/varz /statusz /tracez /flightz /healthz); "
        "-1 = off, 0 = ephemeral OS-assigned, >0 = fixed")
declare("hpx.obs.host", "str", "127.0.0.1",
        "ops-plane bind address (loopback by default: the endpoint is "
        "an operator surface, not a public one)")
declare("hpx.obs.exemplars", "bool", "0",
        "capture tail-bucket exemplars (rid, value, wall ts, span ref) "
        "on the SLO latency histograms")
declare("hpx.obs.exemplars_per_bucket", "int", "4",
        "exemplar reservoir slots per histogram bucket (deterministic "
        "ring replacement: slot = offers-to-bucket mod capacity)")
declare("hpx.obs.exemplar_quantile", "float", "0.95",
        "only records landing at/above this quantile's bucket capture "
        "an exemplar (the tail is what needs attribution)")
declare("hpx.obs.exemplar_refresh", "int", "64",
        "offers between threshold-bucket recomputes (amortizes the "
        "O(buckets) cumulative scan off the record path)")
declare("hpx.obs.alerts", "bool", "0",
        "SLO burn-rate alert evaluation at the serving flush boundary "
        "(off by default: zero-overhead is-None fast path)")
declare("hpx.obs.alert_rules", "str", "",
        "csv 'hist:threshold_s:target' SLO rules ('' = built-in "
        "defaults, see svc/slo_alerts.DEFAULT_RULES)")
declare("hpx.obs.alert_fast_s", "float", "300",
        "fast burn-rate window, seconds (SRE 5m page window)")
declare("hpx.obs.alert_slow_s", "float", "3600",
        "slow burn-rate window, seconds (gates flapping: both windows "
        "must burn before an alert fires)")
declare("hpx.obs.alert_burn_fast", "float", "14.4",
        "burn-rate factor the fast window must exceed (14.4 = a 30d "
        "budget gone in 2d)")
declare("hpx.obs.alert_burn_slow", "float", "6",
        "burn-rate factor the slow window must exceed")
declare("hpx.obs.alert_interval_s", "float", "1.0",
        "minimum wall seconds between alert evaluations (the flush "
        "boundary can tick far faster than SLO state moves)")
declare("hpx.obs.alert_trace_dump", "bool", "0",
        "dump the live trace ring next to the flight bundle when an "
        "alert fires")

# -- checkpoint / resiliency / exec -----------------------------------------
declare("hpx.checkpoint.dir", "str", "./checkpoints",
        "base directory for checkpoint_path() relative names")
declare("hpx.resiliency.replay_default_n", "int", "3",
        "replay attempts when callers pass n=None")
declare("hpx.exec.default_chunk", "str", "auto",
        "default chunker: auto | static[:N] | dynamic[:N] | guided | N")
declare("hpx.exec.min_chunk_size", "int", "1",
        "floor on per-chunk iterations for auto/guided chunking")
