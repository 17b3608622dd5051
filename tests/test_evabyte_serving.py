"""EVA attention through the one layer definition and the paged server,
against the plain reference of chipbench/reference/evabyte.py: exact
K/V rows inside an ALIGNED window, one learned-pooled K/V row for every
chunk behind it, one softmax over both; RMSNorm with a unit offset; a
head of several prediction heads. A 2-layer toy of EvaByte's shape at
sizes a CPU holds (4 heads x 16, chunks of 4, windows of 16, vocabulary
40, 3 prediction heads; blocks of 4 rows, so a window's 4 summaries
fill one block), seeded random weights made by the benchmark's own
driver (chipbench/drivers/serving_eva.py), float32.

Tolerances, and why. Program and reference are both float32 on the CPU
and compute the same sums; they differ in the ORDER of the sums of
their matmuls and of the softmax (a walk over pages, or [ring | chunk |
summaries], against one masked matrix over the whole sequence). Logits
of order 1 then agree to 5e-4 absolute (`TOL`; found: under 3e-5); the
pooling's forms among themselves to 1e-5 (`POOL_TOL`; found: under
1e-6). A piece of the mathematics left out moves logits by hundredths
to ones: each such case is held to 20 x `TOL`.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.drivers import serving_eva as drv
from chipbench.reference import evabyte as ref
from hpx_tpu.cache.page_table import TwoGrainTable
from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.ops import eva
from hpx_tpu.svc import performance_counters as pc
from hpx_tpu.svc import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL, POOL_TOL = 5e-4, 1e-5
C, W, V, P = 4, 16, 40, 3


def _conf(**over):
    with open(os.path.join(ROOT, "chipbench/configs/evabyte-6.5b.json")) as f:
        conf = json.load(f)
    with open(os.path.join(ROOT,
                           "chipbench/tests/rehearse_eva.json")) as f:
        conf = harness._merge(conf, json.load(f)["config"])
    return harness._merge(conf, over)


@pytest.fixture(scope="module")
def toy():
    conf = _conf()
    cfg = drv.build_cfg(conf)
    return conf, cfg, drv.make_params(cfg, 11)


def _prompt(n, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(1, V, n)]


def _server(toy, **kw):
    _, cfg, params = toy
    return ContinuousServer(params, cfg, **{
        "slots": 3, "smax": 96, "block_size": 4, "prefill_chunk": 8, **kw})


def test_the_toy_has_every_mechanism(toy):
    conf, cfg, params = toy
    assert cfg.layer_mixer == ("eva", "eva") and not cfg.recurrent
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 4, 16)
    assert (cfg.eva_chunk, cfg.eva_window, cfg.vocab, cfg.pred_heads) == \
        (C, W, V, P)
    assert cfg.norm_unit_offset and cfg.logits_f32 and not cfg.tied
    assert cfg.rope and cfg.rope_theta == 100000.0
    assert set(params["layers"][0]["eva"]) == {
        "wq", "wk", "wv", "phi", "mu", "wo"}
    assert params["head"].shape == (P * V, cfg.d_model)
    assert params["layers"][0]["eva"]["phi"].dtype == jnp.float32
    # the program's own initialisation builds the same leaves, the
    # norms' parameters at the scale 1 they start from: zeros
    mine = tfm.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), mine) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), params)
    assert not np.asarray(mine["ln_f"]).any()


def test_the_cells_configuration_carries_every_published_width():
    with open(os.path.join(ROOT, "chipbench/configs/evabyte-6.5b.json")) as f:
        conf = json.load(f)
    cfg = drv.build_cfg(conf)
    assert conf["reduced"] == ["num_hidden_layers"]
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff,
            cfg.vocab, cfg.n_layers, cfg.pred_heads) == \
        (4096, 32, 32, 128, 11008, 320, 8, 8)
    assert (cfg.eva_chunk, cfg.eva_window, cfg.rope_theta) == \
        (16, 2048, 100000.0) and cfg.layer_mixer == ("eva",) * 8
    shapes = jax.eval_shape(lambda: drv.make_params(cfg, 1))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    assert abs(n - 1630.9e6) < 0.2e6          # 3.26 GB in bfloat16
    srv = conf["server"]
    # every slot at the mix's longest fits: 18 summary + 32 window blocks
    assert TwoGrainTable.max_blocks(
        srv["block_size"], 2048, 16, srv["smax"]) == 50
    assert srv["num_blocks"] == srv["slots"] * 50 + 1
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(ln) for ln in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["name"] == "EvaByte":
            assert conf["source"] == row["source_url"]
            assert all(conf[k] == v for k, v in row["config"].items()
                       if k not in conf["reduced"])


# -- the pooling's forms ----------------------------------------------------

def test_the_pooling_is_the_references(toy):
    _, cfg, params = toy
    m = params["layers"][0]["eva"]
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    k, v = (jax.random.normal(kk, (6 * C, 4, 16), jnp.float32) for kk in ks)
    want = ref._summaries(k, v, k, m["phi"], m["mu"], C, None, ())
    got = eva.eva_pool(k.reshape(6, C, 4, 16), v.reshape(6, C, 4, 16),
                       m["phi"], m["mu"])
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=POOL_TOL)


def test_a_roll_pools_a_windows_blocks_into_its_summaries(toy):
    """`eva_roll_blocks` over pools whose blocks lie in no order: the
    window's rows are read in the table's order and the summaries fill
    the fresh blocks whole, other blocks untouched."""
    _, cfg, params = toy
    m = params["layers"][0]["eva"]
    bs, nb = 2, 14
    ks = jax.random.split(jax.random.PRNGKey(4), 2)
    kp, vp = (jax.random.normal(kk, (nb, 4, bs, 16), jnp.float32)
              for kk in ks)
    exact = np.asarray([9, 2, 11, 5, 0, 7, 13, 4], np.int32)   # 16 rows
    fresh = np.asarray([6, 1], np.int32)                      # 4 rows
    rows = lambda p: np.moveaxis(np.asarray(p)[exact], 1, 2).reshape(  # noqa: E731
        W // C, C, 4, 16)
    want = eva.eva_pool(rows(kp), rows(vp), m["phi"], m["mu"])
    k2, v2 = eva.eva_roll_blocks(kp, vp, exact, fresh, m["phi"], m["mu"], C)
    for new, old, w in ((k2, kp, want[0]), (v2, vp, want[1])):
        got = np.moveaxis(np.asarray(new)[fresh], 1, 2).reshape(-1, 4, 16)
        np.testing.assert_allclose(got, w, atol=POOL_TOL)
        rest = [i for i in range(nb) if i not in fresh]
        np.testing.assert_array_equal(np.asarray(new)[rest],
                                      np.asarray(old)[rest])


def test_a_positions_row_in_the_one_run():
    pos = np.asarray([0, 15, 16, 17, 31, 32, 47])
    row = eva.eva_row(pos, C, W)
    assert list(row) == [0, 15, 4, 5, 19, 8, 23]
    assert list(row - pos % W) == [0, 0, 4, 4, 4, 8, 8]    # summaries
    assert eva.summary_rows(96, C, W) == 24 and eva.summary_rows(
        18688, 16, 2048) == 1152


# -- prefill: windows of columns over the two-grain scratch ------------------

def _prefill_logits(toy, seq, width):
    """Every position's logits [T, P, V] through `_decode_window` over
    a fresh scratch, `width` columns at a time (the last chunk padded
    to the width, `valid` saying how many are real: the server's
    chunk)."""
    _, cfg, params = toy
    scratch = [serving._scratch_entry(cfg, 96, i)
               for i in range(cfg.n_layers)]
    out = []
    for s in range(0, len(seq), width):
        n = min(width, len(seq) - s)
        toks = np.asarray([seq[s:s + n] + [0] * (width - n)], np.int32)
        scratch, lg = tfm._decode_window(params, scratch, toks, s, cfg,
                                         valid=n)
        out.append(np.asarray(lg)[0, :n])
    return np.concatenate(out).reshape(len(seq), P, V), scratch


@pytest.mark.parametrize("plen,width", [
    (13, 8),        # ends before a boundary
    (16, 8),        # ends ON one
    (37, 8),        # two behind it; chunks aligned to the chunk of 4
    (37, 12),       # a chunk [12, 24) STRADDLES 16, another 32
    (41, 5),        # chunks that are no whole number of pooled chunks
    (33, 1),        # a column at a time
])
def test_prefill_matches_the_reference_at_every_position_and_head(
        toy, plen, width):
    conf, cfg, params = toy
    seq = _prompt(plen, seed=plen)
    got, scratch = _prefill_logits(toy, seq, width)
    want, (ks, vs) = ref.logits(params, conf, seq, summaries_of=0)
    np.testing.assert_allclose(got, np.asarray(want), atol=TOL)
    # the scratch's summaries of layer 0: every whole chunk's
    whole = plen // C
    np.testing.assert_allclose(np.asarray(scratch[0][2])[0, :whole],
                               np.asarray(ks), atol=POOL_TOL * 10)
    np.testing.assert_allclose(np.asarray(scratch[0][3])[0, :whole],
                               np.asarray(vs), atol=POOL_TOL * 10)


def test_a_padded_chunk_does_not_wrap_onto_the_live_window(toy):
    """A last chunk whose padding reaches past the window's end: the
    pad columns' rows must not land on the ring's first rows, which
    hold the window's live rows."""
    _, cfg, params = toy
    seq = _prompt(14, seed=5)
    _, padded = _prefill_logits(toy, seq, 12)      # [12, 24): 2 real
    _, tight = _prefill_logits(toy, seq, 7)        # no padding at all
    for a, b in zip(padded[0][:2], tight[0][:2]):
        np.testing.assert_allclose(np.asarray(a)[0, :14],
                                   np.asarray(b)[0, :14], atol=1e-6)


LEFT_OUT = ("mu", "phi_scale", "pool_v", "rope_before_pool", "aligned",
            "gate", "unit_offset")


@pytest.mark.parametrize("piece", LEFT_OUT)
def test_a_reference_with_a_piece_left_out_fails_the_limit(toy, piece):
    conf, cfg, params = toy
    seq = _prompt(45, seed=2)
    got, _ = _prefill_logits(toy, seq, 8)
    want = np.asarray(ref.logits(params, conf, seq, leave_out=(piece,)))
    assert np.abs(got - want).max() > 20 * TOL
    assert np.abs(got - np.asarray(ref.logits(params, conf, seq))).max() \
        < TOL


@pytest.mark.parametrize("quant", ["int8", "window_only"])
def test_the_controls_part_from_the_reference(toy, quant):
    conf, cfg, params = toy
    seq = _prompt(45, seed=2)
    want = np.asarray(ref.logits(params, conf, seq))
    got = np.asarray(ref.logits(params, conf, seq, quant=quant))
    assert np.abs(got - want).max() > 20 * TOL
    if quant == "window_only":      # nothing differs inside window 0
        np.testing.assert_allclose(got[:W], want[:W], atol=1e-6)


# -- decode through the pools ------------------------------------------------

def _step_logits(srv, toy):
    """All prediction heads' logits [slots, P, V] of the NEXT decode
    step, through the server's pools and tables as that step will see
    them (the pools are not donated here: nothing of the server
    moves)."""
    _, cfg, params = toy
    for s, p in srv.live_positions().items():
        srv._ensure_block(s, p)
    lg = serving._paged_decode_rows(
        params, srv._pools, None, jnp.asarray(srv._cur, jnp.int32),
        srv._tables_dev(), jnp.asarray(srv._pos, jnp.int32), cfg)[2]
    return np.asarray(lg).reshape(srv.slots, P, V)


def test_decode_matches_the_reference_every_head_across_two_boundaries(toy):
    """Three slots at different phases of their windows in one batch
    (prompts that end before, ON and after a boundary), each decoding
    across two more: before every step, every live slot's logits of
    all prediction heads against the reference's full forward of the
    same bytes."""
    conf, cfg, params = toy
    srv = _server(toy, async_dispatch=False)
    prompts = {srv.submit(_prompt(n, seed=n), max_new=38): _prompt(n, seed=n)
               for n in (13, 16, 21)}
    reqs = {}
    checked = 0
    while True:
        live = {s: srv._slot_req[s] for s in srv.live_positions()}
        if live and all(len(r.tokens) for r in live.values()):
            got = _step_logits(srv, toy)
            for s, r in live.items():
                reqs[r.rid] = r
                seq = r.prompt + r.tokens
                want = np.asarray(ref.logits(params, conf, seq))[-1]
                np.testing.assert_allclose(got[s], want, atol=TOL)
                checked += 1
        if not srv.step():
            break
    assert checked > 100
    out = srv.poll_finished()
    for rid, prompt in prompts.items():
        seq = prompt + out[rid]
        best = np.asarray(ref.logits(params, conf, seq[:-1]))[
            len(prompt) - 1:, 0].argmax(-1)
        assert list(best) == out[rid]
    st = srv.cache_stats()
    assert st["in_use"] == 1 and st["eva_rolls"] >= 6   # the trash block


def test_a_slot_taken_again_keeps_nothing_of_its_last_request(toy):
    """One slot, three requests one after another, the later ones
    shorter than what the slot held: tokens are the reference's greedy
    bytes, so no summary and no exact row of the old request is seen;
    every block returns to the allocator."""
    conf, cfg, params = toy
    srv = _server(toy, slots=1)
    prompts = {srv.submit(_prompt(n, seed=100 + n), max_new=m):
               _prompt(n, seed=100 + n)
               for n, m in ((50, 30), (7, 12), (18, 20))}
    out = srv.run()
    for rid, prompt in prompts.items():
        seq = prompt + out[rid]
        best = np.asarray(ref.logits(params, conf, seq[:-1]))[
            len(prompt) - 1:, 0].argmax(-1)
        assert list(best) == out[rid]
    assert srv._alloc.in_use == 1
    assert srv.cache_stats()["eva_prefix_refused"] == 3


def test_the_pick_never_leaves_the_next_bytes_head(toy):
    """Heads 1.. of the output matrix made to shout: the step and the
    probe still pick from head 0's `vocab` columns."""
    conf, cfg, params = toy
    loud = dict(params, head=params["head"].at[V:].multiply(100.0))
    srv = ContinuousServer(loud, cfg, slots=2, smax=96, block_size=4,
                           prefill_chunk=8)
    prompt = _prompt(19, seed=9)
    rid = srv.submit(prompt, max_new=6)
    out = srv.run()[rid]
    assert all(0 <= t < V for t in out)
    best = np.asarray(ref.logits(loud, conf, prompt + out[:-1]))[
        len(prompt) - 1:, 0].argmax(-1)
    assert list(best) == out


# -- the cache manager --------------------------------------------------------

def test_the_two_grain_table_is_one_gap_free_run():
    t = TwoGrainTable(4, 16, 4)
    assert t.per == 1
    assert [t.blocks_at(p) for p in (0, 3, 4, 14, 15, 16, 20, 31, 32)] == \
        [1, 1, 2, 4, 5, 2, 3, 6, 3]
    t.adopt(0)
    bids = iter(range(100, 200))
    freed_all = []
    for pos in range(40):
        while len(t.blocks) < t.blocks_at(pos):
            t.append_block(next(bids))
        assert t.row_of(pos) // 4 < len(t.blocks)
        if (pos + 1) % 16 == 0:
            exact = t.blocks[t.summary:-t.per]
            assert t.roll() == exact and len(exact) == 4
            freed_all += exact
        assert t.summary == (pos + 1) // 16 and t.held(pos + 1) == \
            len(t.blocks)
    assert len(set(freed_all)) == 8
    srow, erow = t.write_rows(6, pad=0)
    assert list(srow) == t.blocks[:2] + [0] * 4
    assert list(erow) == t.blocks[2:] + [0] * 2
    with pytest.raises(ValueError, match="no whole number of blocks"):
        TwoGrainTable(8, 16, 4)
    assert TwoGrainTable.max_blocks(4, 16, 4, 96) == 5 + 4 + 1
    assert TwoGrainTable.max_blocks(64, 2048, 16, 18688) == 50


def test_rolls_are_spanned_counted_and_leave_the_summaries_visible(toy):
    """`serving.window_roll` spans where a window completes, in prefill
    and in decode; `eva_rolls` counts them; a live slot's visible rows
    are (window / chunk) x floor(pos / window) at every check, and
    `eva_summaries()` hands back the reference's rows."""
    conf, cfg, params = toy
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.trace.enabled", "1")
    tr = tracing.start_if_configured()
    try:
        srv = _server(toy, slots=2)
        for n in (37, 9):
            srv.submit(_prompt(n, seed=n), max_new=30)
        for _ in range(28):
            srv.step()
            srv.flush()
            st = srv.cache_stats()
            live = srv.live_positions()
            assert st["eva_summary_rows"] == sum(
                W // C * (p // W) for p in live.values())
            assert st["eva_exact_rows"] == sum(p % W for p in live.values())
        for s in live:
            toks, ks, vs = srv.eva_summaries(s)
            assert len(toks) == live[s]
            want = ref.first_summaries(params, conf, toks)
            n = ref.visible_rows(len(toks), conf)
            assert ks.shape[0] == n
            np.testing.assert_allclose(ks, want[0][:n], atol=POOL_TOL * 10)
            np.testing.assert_allclose(vs, want[1][:n], atol=POOL_TOL * 10)
        errs, miscounted = ref.summary_errors(
            params, conf, [(t, k.shape[0], k, v) for t, k, v in (
                srv.eva_summaries(s) for s in live)])
        assert miscounted == 0 and errs.max() < 1e-5
        rolls = [e[7] for e in tr.snapshot()
                 if e[0] == "B" and e[1] == "serving.window_roll"]
        st = srv.cache_stats()
        assert len(rolls) == st["eva_rolls"] >= 4
        in_decode = [a for a in rolls if a["blocks"]]
        assert in_decode and all(
            a["blocks"] == W // 4 and a["rows"] == W // C
            for a in in_decode)
        assert st["eva_blocks_freed"] == len(in_decode) * (W // 4)
        assert 0 < st["eva_rows_attended"] < st["eva_tokens_behind"]
        # prompts of 37 and 9 pool 9 + 2 chunks as they fill; a roll in
        # decode pools its window's 4 again from the blocks
        assert st["eva_chunks_pooled"] == 11 + len(in_decode) * (W // C)
        inst = srv.counter_instance
        for name, key in (("exact-rows", "eva_exact_rows"),
                          ("summary-rows", "eva_summary_rows"),
                          ("rolls", "eva_rolls"),
                          ("blocks-freed", "eva_blocks_freed")):
            assert pc.query_counter(pc.counter_name(
                "cache", "eva/" + name, inst)).value == st[key]
    finally:
        tracing.stop_tracing()
        rc.set("hpx.trace.enabled", "0")


def test_a_restore_recomputes_and_goes_on_with_the_same_bytes(toy):
    """No snapshot can take a roll back: a restore lays the slot's run
    out anew from prompt ++ landed bytes (`serving.reprefill`) and the
    bytes that follow are the undisturbed run's."""
    conf, cfg, params = toy
    prompt = _prompt(27, seed=4)
    calm = _server(toy, slots=1)
    rid = calm.submit(prompt, max_new=30)
    want = calm.run()[rid]
    srv = _server(toy, slots=1, async_dispatch=False)
    rid = srv.submit(prompt, max_new=30)
    for _ in range(12):
        srv.step()
    srv.flush()
    req = srv._slot_req[0]
    srv._restore_recurrent(0, req)
    out = srv.run()[rid]
    assert out == want
    st = srv.cache_stats()
    assert st["eva_reprefills"] == 1 and st["in_use"] == 1


@pytest.mark.parametrize("kernel", ["gather", "fused"])
def test_the_bounded_walk_serves_both_grains(kernel):
    """A head of 128 (whole lane rows: `hpx_paged_fused`'s bounded walk
    in interpret mode) against the gather form, through the server:
    the kernel that serves K/V pairs walks a two-grain run unchanged."""
    cfg = tfm.TransformerConfig(
        vocab=V, d_model=32, n_heads=2, head_dim=128, n_layers=1, d_ff=48,
        dtype=jnp.float32, norm="rmsnorm", mlp="swiglu", tied=False,
        rope=True, rope_theta=100000.0, layer_mixer=("eva",), eva_chunk=C,
        eva_window=W, pred_heads=2, logits_f32=True, norm_unit_offset=True)
    params = drv.make_params(cfg, 5)
    srv = ContinuousServer(params, cfg, slots=2, smax=64, block_size=4,
                           prefill_chunk=8, paged_kernel=kernel)
    # what the rule gives for the toy's shapes: both heads in one grid
    # step, so two steps a layer's call, the second copied during the
    # first; the gather form has no bank to copy into
    st = srv.hbm_read_stats()
    fused = kernel == "fused"
    assert st["heads_per_copy"] == (2 if fused else 0)
    assert st["walk_bank_sets"] == (2 if fused else 0)
    assert st["walk_steps_prefetched_share"] == (0.5 if fused else 0.0)
    prompt = _prompt(14, seed=1)
    rid = srv.submit(prompt, max_new=22)
    while not srv.live_positions():
        srv.step()
    st = srv.hbm_read_stats()
    assert st["walk_copies_per_slot"] == (
        st["walk_entries_per_slot"] * 2 if fused else 0.0)
    out = srv.run()[rid]
    conf = _conf(num_pred_heads=2)
    best = np.asarray(ref.logits(params, conf, prompt + out[:-1]))[
        len(prompt) - 1:, 0].argmax(-1)
    assert list(best) == out


# -- what is refused -----------------------------------------------------------

def test_what_the_new_kind_refuses_names_mechanism_and_module(toy):
    conf, cfg, params = toy
    kw = dict(slots=2, smax=96, block_size=4, prefill_chunk=8)
    with pytest.raises(NotImplementedError, match="ops/eva.py"):
        ContinuousServer(params, cfg, spec=True, **kw)
    with pytest.raises(NotImplementedError, match="ops/eva.py"):
        ContinuousServer(params, cfg, kv_dtype="int8", **kw)
    with pytest.raises(NotImplementedError, match="TwoGrainTable"):
        ContinuousServer(params, cfg, **{**kw, "block_size": 8})
    with pytest.raises(NotImplementedError, match="eva_window_attend"):
        ContinuousServer(params, cfg, **{**kw, "prefill_chunk": 16})
    srv = ContinuousServer(params, cfg, **kw)
    with pytest.raises(NotImplementedError, match="eva"):
        srv.admit_prefilled(_prompt(5), None, 1, 4)
    with pytest.raises(NotImplementedError, match="eva"):
        srv.export_prefix_rows(_prompt(8))
    with pytest.raises(NotImplementedError, match="two\\s+grains"):
        tfm.generate(params, cfg, jnp.asarray([_prompt(5)], jnp.int32), 4)
    with pytest.raises(ValueError, match="eva_summaries"):
        srv.eva_summaries(0)
