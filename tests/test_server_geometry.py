"""One place decides each construction-time shape of a
ContinuousServer: the constructor argument, else its config key, else
the constant (for the block size: HPX_PAGED_BLOCK, the measured table
`ops/paged_blocks.json`, then 16; for the chunk width: the device's
ridge over the weights a chunk reads, 128 on a device of unknown
ridge). And a live server takes a config write to one of its
reloadable knobs at its next flush, never mid-step. And there is ONE
kind of server: the block pools are its cache, `paged` has one value
left, and nothing builds a dense server's programs."""

import dataclasses

import jax
import numpy as np
import pytest

from hpx_tpu.core import config_schema
from hpx_tpu.core.config import Configuration, runtime_config
from hpx_tpu.core.errors import UndeclaredConfigKey
from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import attention_pallas as ap
from hpx_tpu.svc import progprof

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)
MOE = dataclasses.replace(CFG, n_experts=4, moe_top_k=2)


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def moe_params():
    return tfm.init_params(MOE, jax.random.PRNGKey(0))


@pytest.fixture()
def knobs():
    """Set config keys for one test; each goes back to its declared
    default afterwards."""
    rc = runtime_config()
    touched = []

    def set_(key, value):
        touched.append(key)
        rc.set(key, value)

    yield set_
    for key in touched:
        rc.set(key, config_schema.lookup(key).default)


@pytest.fixture(autouse=True)
def _no_env_no_table(monkeypatch):
    monkeypatch.delenv("HPX_PAGED_BLOCK", raising=False)
    monkeypatch.setattr(ap, "_paged_blocks_table", {})


# knob -> (what the server resolved, the constant, config key, the
# value written to it and what that resolves to, constructor argument
# and what that resolves to)
_KNOBS = {
    "block_size": (lambda s: s.block_size, 16,
                   "hpx.cache.block_size", "8", 8, 32, 32),
    "prefill_chunk": (lambda s: s.prefill_chunk, 128,
                      "hpx.serving.prefill_chunk", "64", 64, 32, 32),
    "prefill_buckets": (lambda s: s.prefill_buckets,
                        (8, 16, 32, 64, 128),
                        "hpx.serving.prefill_buckets", "4,16",
                        (4, 16, 128), "32", (32, 128)),
    "spec_k": (lambda s: s._spec_k, 4,
               "hpx.serving.spec.k", "6", 6, 2, 2),
    # off the chip `auto` is the XLA gather formulation
    "paged_kernel": (lambda s: s._paged_kernel, "gather",
                     "hpx.serving.paged_kernel", "fused", "fused",
                     "fused_online", "fused_online"),
    "kv_dtype": (lambda s: s._kv_dtype, "bf16",
                 "hpx.cache.kv_dtype", "int8", "int8", "fp8", "fp8"),
}


@pytest.mark.parametrize("source", ["constant", "config", "arg"])
@pytest.mark.parametrize("knob", sorted(_KNOBS))
def test_resolution(params, knobs, knob, source):
    got, const, key, written, from_config, arg, from_arg = _KNOBS[knob]
    kwargs = {}
    if source != "constant":
        knobs(key, written)
    if source == "arg":
        kwargs[knob] = arg
    srv = ContinuousServer(params, CFG, slots=2, smax=64, paged=True,
                           **kwargs)
    want = {"constant": const, "config": from_config,
            "arg": from_arg}[source]
    assert got(srv) == want
    if knob == "block_size":
        assert srv.hbm_read_stats()["block_size_source"] == {
            "constant": "default"}.get(source, source)
    if knob == "prefill_chunk":
        # the third tier is derived: on a device of unknown ridge (the
        # CPU) it comes out as the old constant
        assert srv.cache_stats()["prefill_chunk_source"] == {
            "constant": "ridge"}.get(source, source)
        assert srv.cache_stats()["prefill_chunk"] == want


# a dense model in bfloat16 reads 2 bytes a parameter and multiplies
# each once a row: its ridge width is the device's ridge itself. A row
# of SPARSE goes to 1 of 32 experts and the chunk reads all 32.
DENSE16 = dataclasses.replace(CFG, dtype=jax.numpy.bfloat16)
SPARSE = dataclasses.replace(CFG, n_experts=32, moe_top_k=1)
# a share of the experts held under the full-width router: a row's
# choices fall to the held ones by top_k / n_experts of the ROUTER's
HELD = dataclasses.replace(CFG, n_experts=32, moe_top_k=4, moe_held=(0, 4),
                           mlp="swiglu")

_RIDGE_CASES = {
    # case: (model, the device kind's ridge, config key, argument,
    #        the width, its source)
    "unknown-kind": (DENSE16, 0.0, None, None, 128, "ridge"),
    "dense-at-240": (DENSE16, 240.0, None, None, 256, "ridge"),
    "dense-f32-at-240": (CFG, 240.0, None, None, 512, "ridge"),
    "dense-at-60": (DENSE16, 60.0, None, None, 128, "ridge"),
    "dense-at-560": (DENSE16, 560.0, None, None, 512, "ridge"),
    "sparse-at-240": (SPARSE, 240.0, None, None,
                      serving._CHUNK_CEILING, "ridge"),
    # all 4 held experts' rows counted a row would read 120 -> 128
    "held-share-at-60": (HELD, 60.0, None, None, 512, "ridge"),
    "config-wins": (SPARSE, 240.0, "64", None, 64, "config"),
    "arg-wins": (SPARSE, 240.0, "64", 32, 32, "arg"),
}


@pytest.mark.parametrize("case", sorted(_RIDGE_CASES))
def test_prefill_chunk_follows_the_ridge(knobs, monkeypatch, case):
    """Where neither the argument nor the config key states a width,
    the chunk is as wide as the model's ridge on this device: the
    power of two nearest ridge x bytes read / (2 x parameters a row
    multiplies), between 128 and the one ceiling; the ladder follows."""
    cfg, ridge, written, arg, want, source = _RIDGE_CASES[case]
    monkeypatch.setattr(progprof, "device_ridge", lambda: ridge)
    if written is not None:
        knobs("hpx.serving.prefill_chunk", written)
    p = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    assert serving._ridge_chunk(p, cfg, ridge) == (
        want if source == "ridge" else serving._CHUNK_CEILING)
    srv = ContinuousServer(tfm.init_params(cfg, jax.random.PRNGKey(0)),
                           cfg, slots=2, smax=64, paged=True,
                           **({} if arg is None else
                              {"prefill_chunk": arg}))
    st = srv.cache_stats()
    assert (srv.prefill_chunk, st["prefill_chunk_source"]) == (want, source)
    assert st["prefill_chunk"] == want
    assert st["prefill_rows_per_chunk"] == 0.0
    assert srv.prefill_buckets[-1] == want
    assert srv.prefill_buckets[0] == min(8, want)
    srv.submit([3, 1, 4, 1, 5], max_new=2)
    srv.run()
    assert srv.cache_stats()["prefill_rows_per_chunk"] == 5.0


def test_the_ridge_of_a_device_kind():
    """One table by `device_kind`, beside the peak FLOP/s: the chip the
    benchmark runs on, and a kind nobody entered."""
    assert int(progprof.device_ridge("TPU v5 lite")) == 240
    assert progprof.device_ridge("TPU v5e") == \
        progprof.device_ridge("TPU v5 lite")
    assert progprof.device_ridge("cpu") == 0.0
    assert progprof.device_ridge() == 0.0       # the tier-1 tests' CPU


@pytest.mark.parametrize("source", ["seed", "env"])
def test_block_size_source(params, monkeypatch, source):
    """Below the config key: the environment beats the measured table,
    the table beats 16, and the server says which it took."""
    monkeypatch.setattr(ap, "_paged_blocks_table", {"hd8xbf16": 32})
    if source == "env":
        monkeypatch.setenv("HPX_PAGED_BLOCK", "8")
    srv = ContinuousServer(params, CFG, slots=2, smax=64, paged=True)
    assert srv.block_size == {"seed": 32, "env": 8}[source]
    assert srv.hbm_read_stats()["block_size_source"] == source


# the benchmark's two serving configurations construct their server
# with these arguments and nothing else (their files' "server" entry)
_CELLS = {
    "starcoder2-3b": dict(paged=True, slots=32, smax=2048),
    "laguna-xs2": dict(paged=True, slots=32, smax=4864),
}


@pytest.mark.parametrize("config", sorted(_CELLS))
def test_default_geometry_of_the_cells(params, config):
    srv = ContinuousServer(params, CFG, **_CELLS[config])
    stats = srv.hbm_read_stats()
    assert (srv.block_size, stats["block_size_source"]) == (16, "default")
    assert srv.prefill_chunk == 128
    assert srv.prefill_buckets == (8, 16, 32, 64, 128)
    assert srv._max_async == 32
    assert srv._spec_k == 4 and not srv._spec
    assert (srv._kv_dtype, stats["paged_kernel"]) == ("bf16", "gather")
    assert srv._maxb == _CELLS[config]["smax"] // 16


# key -> (the server that has the knob, what it resolved, a value in
# range and what it lands as, a value out of range and its clamp)
_RELOADS = {
    "hpx.serving.prefill_chunk": (
        {}, lambda s: s.prefill_chunk, "16", 16, "1000000", 128),
    "hpx.serving.max_async_steps": (
        {}, lambda s: s._max_async, "8", 8, "0", 1),
    "hpx.serving.ckpt_every": (
        {}, lambda s: s._ckpt_every, "128", 128, "0", 1),
    "hpx.serving.spec.k": (
        {"spec": True}, lambda s: s._spec_k, "2", 2, "1000", 127),
    "hpx.serving.moe.capacity_factor": (
        {"moe": True}, lambda s: s._moe_capacity_pct, "200", 200,
        "-3", 400),
    "hpx.cache.radix_budget_blocks": (
        {}, lambda s: s._radix.budget_blocks, "7", 7, "0", 1),
    "hpx.cache.tier.host_budget_mb": (
        {"tier": True}, lambda s: s._tier.budget_bytes, "3", 3 << 20,
        "0", 1 << 20),
}


def test_the_reload_cases_are_the_reloadable_knobs():
    assert sorted(_RELOADS) == sorted(serving._RELOADABLE_KNOBS)


@pytest.mark.parametrize("key", sorted(_RELOADS))
def test_reload_knobs_each_key(params, moe_params, knobs, key):
    """A value written between two steps is unseen until the next
    flush, then applied, clamped to what the built server can take."""
    kind, got, legal, applied, wild, clamped = _RELOADS[key]
    if kind.get("tier"):
        knobs("hpx.cache.tier.enable", "1")
    cfg, p = (MOE, moe_params) if kind.get("moe") else (CFG, params)
    srv = ContinuousServer(p, cfg, slots=2, smax=64, paged=True,
                           spec=bool(kind.get("spec")))
    before = got(srv)
    assert before != applied != clamped     # each landing shows
    srv.submit([3, 1, 4, 1, 5], max_new=24)
    srv.step()
    srv.step()
    knobs(key, legal)
    assert got(srv) == before               # the write alone: unseen
    if not srv._spec:                       # spec steps flush each time
        srv.step()
        assert srv._buf, "the step after the write must not have flushed"
        assert got(srv) == before           # nor does a step apply it
    srv.flush()
    assert got(srv) == applied
    knobs(key, wild)
    assert got(srv) == applied
    srv.flush()
    assert got(srv) == clamped


# -- one cache: `paged` is no choice ---------------------------------------

def test_paged_false_is_refused_and_names_the_oracle(params):
    """The keyword is accepted for the callers that still pass
    `paged=True`; its other value names what took the dense server's
    place as the reference."""
    with pytest.raises(ValueError, match=r"dense server mode is gone"
                       r".*generate\(\)"):
        ContinuousServer(params, CFG, slots=2, smax=64, paged=False)
    assert ContinuousServer(params, CFG, slots=2, smax=64,
                            paged=True).block_size == 16


def test_a_server_built_with_no_paged_argument_answers_cache_stats(params):
    from hpx_tpu.svc import performance_counters as pc
    srv = ContinuousServer(params, CFG, slots=2, smax=64)
    rid = srv.submit([3, 1, 4, 1, 5], max_new=4)
    assert len(srv.run()[rid]) == 4
    st = srv.cache_stats()
    assert st["num_blocks"] == 2 * 2 * (64 // 16) + 1
    assert st["prefill_tokens_computed"] == 5
    assert srv.hbm_read_stats()["block_size_source"] == "default"
    # and its /cache{...} counters are registered like any server's
    names = pc.discover_counters(
        f"/cache{{locality#*/{srv.counter_instance}}}/*")
    assert any(n.endswith("/blocks/in-use") for n in names)


_RUNS = {
    "plain": dict(),
    "speculative": dict(spec=True, spec_k=3),
    "mesh": dict(mesh=(1, 2)),
}


@pytest.mark.parametrize("run", sorted(_RUNS))
def test_no_run_builds_a_dense_servers_program(params, run):
    """The step, the splice and the verify of a plain, a speculative and
    a two-device-mesh run are the pools' programs: no key of the
    program cache names a dense server's."""
    kw = dict(_RUNS[run])
    if "mesh" in kw:
        kw["mesh"] = jax.sharding.Mesh(
            np.array(jax.devices()[:2]).reshape(kw["mesh"]), ("dp", "tp"))
    srv = ContinuousServer(params, CFG, slots=2, smax=64, **kw)
    rids = [srv.submit(p, max_new=6) for p in ([3, 1, 4], [2, 7, 1, 8, 2])]
    out = srv.run()
    assert sorted(out) == rids
    names = {k[0] for k in tfm._PROGRAMS if isinstance(k, tuple)}
    assert not {n for n in names
                if n.startswith(("cb_step", "cb_splice", "cb_verify"))}
    assert {"pg_step", "pg_splice"} <= names
    assert ("pg_verify" in names) or run != "speculative"


def test_the_mesh_hatch_is_no_config_key():
    """`hpx.serving.mesh.paged` chose between two sharding rules; with
    one left it is undeclared, and a strict configuration refuses it."""
    assert config_schema.lookup("hpx.serving.mesh.paged") is None
    assert config_schema.lookup(
        "hpx.serving.mesh.table_residency") is not None
    with pytest.raises(UndeclaredConfigKey, match="mesh.paged"):
        Configuration(environ={}, strict=True).set(
            "hpx.serving.mesh.paged", "0")


# -- a two-grain table's block counts, reckoned without a device -------------

def _eva_cell():
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "chipbench/configs/evabyte-6.5b.json")) as f:
        return json.load(f)


# position of a write -> (summary blocks behind it, its window's exact
# blocks up to it, the blocks held for the summaries it completes) at
# the cell's geometry: windows of 2,048, chunks of 16, blocks of 64
_EVA_BLOCKS = {
    0: (0, 1, 0), 63: (0, 1, 0), 64: (0, 2, 0), 2046: (0, 32, 0),
    2047: (0, 32, 2), 2048: (2, 1, 0), 4095: (2, 32, 2),
    16384: (16, 1, 0), 18431: (16, 32, 2), 18432: (18, 1, 0),
    18687: (18, 4, 0),
}


@pytest.mark.parametrize("pos", sorted(_EVA_BLOCKS))
def test_two_grain_block_counts_at_the_cells_geometry(pos):
    from hpx_tpu.cache.page_table import TwoGrainTable
    conf = _eva_cell()
    srv = conf["server"]
    t = TwoGrainTable(srv["block_size"], conf["window_size"],
                      conf["chunk_size"])
    assert t.per == 2
    summary, exact, fresh = _EVA_BLOCKS[pos]
    assert t.blocks_at(pos) == summary + exact + fresh
    # once the step is out and its window rolled: what the slot holds
    assert t.held(pos + 1) == (pos + 1) // 2048 * 2 \
        + -(-((pos + 1) % 2048) // 64)
    assert t.blocks_at(pos) <= TwoGrainTable.max_blocks(
        64, 2048, 16, srv["smax"]) == 50
    assert srv["num_blocks"] == srv["slots"] * 50 + 1


def test_a_two_grain_server_sizes_its_table_and_pool_from_the_run():
    cfg = tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, head_dim=8, n_layers=2, d_ff=64,
        norm="rmsnorm", mlp="swiglu", tied=False, rope=True,
        layer_mixer=("eva", "eva"), eva_chunk=4, eva_window=16)
    srv = ContinuousServer(tfm.init_params(cfg, jax.random.PRNGKey(0)),
                           cfg, slots=3, smax=96, block_size=4,
                           prefill_chunk=8)
    # 5 windows behind the last + its 4 blocks + the block a roll holds
    assert srv._maxb == 10
    # nothing is shared (prefix reuse is refused): no radix headroom
    assert srv._alloc.num_blocks == 3 * 10 + 1
    assert [tuple(a.shape for a in e) for e in srv._pools] == \
        [((31, 4, 4, 8),) * 2] * 2
    assert srv.hbm_read_stats()["walk_entries_per_slot"] == 0.0
