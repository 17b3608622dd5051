"""The cell `deepseek-v2.docqa-closed` at a tiny size on the CPU: end to
end through the benchmark's own command (documents loaded once, then
served from the prefix tree), the contract of the generator
`traffic_gen/shared_docs.py`, its two controls NOT correct through
`Context.result` (together, as the driver's `control` reads them, and
each alone), a reference with one piece of the mathematics left out not
correct either, and the three readers this cell brought on counters and
a trace whose numbers are known by construction. The readings at the
cell's own size are in PERF.md.
"""

import json
import os

import numpy as np
import pytest

from chipbench.tests import helpers as h

CELL = "deepseek-v2.docqa-closed"
REHEARSE = os.path.join(h.HERE, "rehearse_latent.json")
MINE = {"mla_attn_roofline.docqa", "prefix_hit_pct", "routed_here_pct"}
FED = {"batch_occupancy_pct", "kv_blocks_used_pct", "decode_step_dev_ms",
       "prefill_chunk_dev_ms", "device_idle_pct.serve",
       "host_self_ms.serve", "host_syncs_per_step", "idle_flush_pct.serve",
       "idle_admit_pct.serve", "moe_gmm_roofline", "moe_step_share_pct",
       "experts_hit_pct", "mixer_step_share_pct"}
MS = 1e6


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_line(trace):
    p, lines = h.run_cell(CELL, trace=trace, seed=2**31 + 4243,
                          rehearse=REHEARSE)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(lines[-1])
    assert set(line) - {"rehearsal", "checks", "breakdown"} == h.RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    for name in ("moe_tokens_dropped", "doc_rows_recomputed",
                 "window_compiles"):
        assert line["checks"][name] == {"value": 0.0, "limit": 0.0}
    if trace:       # the counters read on a CPU; the trace's do not
        assert {"experts_hit_pct", "prefix_hit_pct", "routed_here_pct"} <= \
            set(line["metrics"]) <= MINE | FED
        assert 80 < line["metrics"]["prefix_hit_pct"]["value"] < 100
        assert 3 < line["metrics"]["routed_here_pct"]["value"] < 30
    else:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p90_ms",
                                        "setup_s"}
    setup = [json.loads(ln) for ln in lines if '"phase": "setup"' in ln][0]
    window = [json.loads(ln) for ln in lines if '"phase": "window"' in ln][0]
    # the ramp prefilled each document once; the window none of them
    assert setup["ramp_tokens_computed"] >= 3 * 64
    assert window["prefill_tokens_saved"] == window["document_tokens"] > 0
    assert window["prefill_tokens_computed"] <= 16 * window[
        "requests_submitted"]
    assert window["moe_dropped"] == 0 and window["shared_blocks"] > 0


def test_the_benchmark_gained_entries_and_lost_none():
    b = h.bench()
    assert [w["name"] for w in b["workloads"]] == [
        "sc2-3b.gen-closed", "hpx-stencil.dataflow-coarse",
        "laguna-xs2.mixed-closed", "kimi-linear.reason-closed", CELL]
    cell = b["workloads"][-1]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2", "closed-64-docqa", 1)
    conf = b["configs"][-1]
    assert conf["name"] == "deepseek-v2" and conf["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    by = {m["name"]: m for m in b["per_layer"]}
    for name in MINE:
        assert by[name]["workloads"] == [CELL]
        assert os.path.exists(os.path.join(
            h.ROOT, "chipbench", "layers", name + ".py"))
    assert [m["name"] for m in b["per_layer"]][-3:] == [
        "mla_attn_roofline.docqa", "prefix_hit_pct", "routed_here_pct"]
    assert {n for n, m in by.items()
            if CELL in m.get("workloads", [])} == MINE | FED
    assert CELL not in by["mla_attn_roofline"]["workloads"]
    for m in b["end_to_end"]:
        if m["name"] in ("out_tok_s", "tpot_p90_ms"):
            assert m["workloads"][-1] == CELL
    assert not any(w["chips"] != 1 for w in b["workloads"])


def test_the_configuration_holds_every_published_width():
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/deepseek-v2.json")))
    assert (conf["num_hidden_layers"], conf["n_routed_experts"],
            conf["vocab_size"]) == (6, 20, 12800)
    assert conf["source_values"] == {"num_hidden_layers": 60,
                                     "n_routed_experts": 160,
                                     "vocab_size": 102400}
    assert conf["experts_held"] == [0, 20] and conf["router_experts"] == 160
    assert len(conf["assumed"]) >= 6 and "8 TPU v5e chips" in \
        conf["deployment"] and "3,814,568,960" in conf["deployment"]
    assert conf["server"]["slots"] == 64 and conf["server"]["smax"] == 25216
    assert conf["control_precision"] == ["int8", "nope"]
    assert conf["correct"]["limits"] == {"gap_mean": 0.055}
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(ln) for ln in open(catalog)] \
        if os.path.exists(catalog) else []
    for row in rows:
        if row["name"] == "DeepSeek-V2":
            assert conf["source"] == row["source_url"]
            assert {k for k, v in row["config"].items()
                    if conf.get(k) != v} == set(conf["reduced"])


# -- the generator's contract ----------------------------------------------

def _mix(**over):
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-64-docqa.json")))
    return dict(mix, **over)


def test_documents_and_order_are_the_same_for_every_seed():
    from chipbench.traffic_gen import shared_docs
    a = shared_docs.make(_mix(), 1, vocab=12800)
    b = shared_docs.make(_mix(), 2**31 + 77, vocab=12800)
    assert a.doc_lens == b.doc_lens and len(a.doc_lens) == 16
    assert all(8192 <= n <= 24576 and n % 16 == 0 for n in a.doc_lens)
    assert sum(a.doc_lens) == 236464
    assert [a.lengths(k) for k in range(600)] == \
        [b.lengths(k) for k in range(600)]
    assert a.docs[3] != b.docs[3]                   # the ids are the seed's
    for g in (a, b):
        assert all(1 <= t < 12800 for d in g.docs for t in d)
    r = a.request(16 + 5)
    d, nq, out = a.lengths(16 + 5)
    assert r["prompt"][:a.doc_lens[d]] == a.docs[d] and r["doc"] == d
    assert len(r["prompt"]) == a.doc_lens[d] + nq and r["max_new"] == out
    assert 64 <= nq <= 256 and 128 <= out <= 384 and r["due_s"] is None
    assert all(1 <= t < 12800 for t in r["prompt"])
    assert a.document_tokens(3) == 0                # a loader matches nothing
    assert a.document_tokens(16 + 5) == a.doc_lens[d]
    assert a.frame() == (max(a.doc_lens) + 256 + 384, 384)
    asked = np.bincount([a.lengths(16 + i)[0] for i in range(480)],
                        minlength=16)
    assert asked.min() >= 15                        # every document, often


def test_the_ramp_ends_only_after_every_document_is_published():
    from chipbench.traffic_gen import shared_docs
    g = shared_docs.make(_mix(callers=4, stagger_steps=2, life_steps=10,
                              documents={"count": 3, "round_to": 16,
                                         "tokens": {"dist": "uniform",
                                                    "min": 32, "max": 64}}),
                         7, vocab=300)
    step, seen = 0, []
    # the loaders, one at a time: nothing else is offered, the ramp is
    # not done however long it lasts
    for _ in range(3):
        got = g.poll(step, 0.0)
        assert len(got) == 1 and got[0]["max_new"] == 1
        assert got[0]["prompt"] == g.docs[got[0]["k"]]
        seen.append(got[0]["k"])
        for _ in range(5):
            step += 1
            assert g.poll(step, 0.0) == [] and not g.ramp_done(step, 0.0)
        g.finished()
    assert seen == [0, 1, 2] and not g.ramp_done(step + 1000, 0.0)
    # the callers, `stagger_steps` apart from the step the last loader
    # was seen finished
    start, out = step, 0
    while out < 4:
        out += len(g.poll(step, 0.0))
        assert out == min(4, (step - start) // 2 + 1)
        assert not g.ramp_done(step, 0.0)
        step += 1
    last = start + 3 * 2
    assert not g.ramp_done(last + 9, 0.0) and g.ramp_done(last + 10, 0.0)
    g.finished()                                    # a caller comes back
    (nxt,) = g.poll(step, 0.0)
    assert nxt["k"] == 3 + 4 and nxt["question"] >= 1


# -- correct, its controls, and a reference with a piece left out ---------

def _fresh_programs():
    from hpx_tpu.models import transformer
    transformer._PROGRAMS.clear()


@pytest.fixture(scope="module")
def sound_run():
    _fresh_programs()
    ctx = h.in_process_ctx(CELL, REHEARSE)
    driver = ctx.driver()
    return ctx, driver, driver.run(ctx)


def test_sound_run_is_correct_and_both_controls_are_not(sound_run):
    from chipbench import control
    ctx, driver, outcome = sound_run
    program = ctx.result(outcome)
    assert program["correct"] is True, program["checks"]
    read = driver.control(ctx, outcome)
    assert set(read["numbers"]) == {"int8", "nope"}
    line = ctx.result(control.swapped(outcome, read["checks"]))
    assert line["correct"] is False, line["checks"]
    for name, numbers in read["numbers"].items():
        alone = {n: numbers[n] for n in read["checks"]}
        line = ctx.result(control.swapped(outcome, alone))
        assert line["correct"] is False, (name, line["checks"])
        assert all(read["checks"][n] <= alone[n] for n in alone)


@pytest.mark.parametrize("piece", ["rotation", "mscale", "q_norm",
                                   "kv_norm", "group_limit", "scaling",
                                   "shared"])
def test_a_reference_with_a_piece_left_out_fails_correct(sound_run, piece):
    from chipbench import control
    from chipbench.drivers import serving as base
    ctx, driver, outcome = sound_run
    params, requests, length, out_max = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(params, ctx.config, requests,
                                       length, out_max, leave_out=(piece,))
    numbers = base.gap_numbers(gaps)
    broken = {n: numbers[n] for n in ctx.config["correct"]["limits"]
              if n in numbers}
    line = ctx.result(control.swapped(outcome, broken))
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["gap_max"]["value"] > 0.05


def test_a_recomputed_document_fails_correct(sound_run):
    from chipbench import control
    ctx, _, outcome = sound_run
    line = ctx.result(control.swapped(outcome, {"doc_rows_recomputed": 64}))
    assert line["correct"] is False


def test_control_command_exits_0_only_if_the_controls_fail(capsys):
    from chipbench import control
    _fresh_programs()
    argv = ["--workload", CELL, "--seeds", "5,2147483659",
            "--seconds", "0.5", "--rehearse", REHEARSE]
    assert control.main(argv) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rows[-1]["every_program_correct_and_every_control_not"] is True
    seeds = [r for r in rows if "seed" in r]
    assert len(seeds) == 2 and all(
        set(r["control_numbers"]) == {"int8", "nope"} for r in seeds)


# -- what the algorithm needs, and the three readers -----------------------

def test_bytes_and_operations_the_latent_walk_needs():
    from chipbench import opcount_latent as oc
    # a slot at position 1000 reads 1001 rows of 576 values, once a layer
    assert oc.latent_walk_bytes([1000], 6, 512, 64) == 1001 * 6 * 1152
    assert oc.latent_walk_bytes([0, 99], 6, 512, 64) == 101 * 6 * 1152
    # ... and 128 heads score 576 and weigh 512 values of each
    assert oc.latent_walk_flops([1000], 6, 128, 512, 64) == \
        1001 * 6 * 128 * 2 * (576 + 512)
    assert oc.latent_walk_flops([], 6, 128, 512, 64) == 0
    # 242 operations a byte: the v5e's ridge is 240
    assert oc.latent_walk_flops([9], 1, 128, 512, 64) / \
        oc.latent_walk_bytes([9], 1, 512, 64) == pytest.approx(241.8, abs=.1)


def synthetic():
    """A 100 ms window; two runs of `jit_step`, each holding a 4 ms
    `hpx_mla_paged`; one `jit_chunk` with a kernel call of its own,
    which no reader of the step may count."""
    ops, mods = [], []
    for t0 in (10, 50):
        mods.append(["jit_step(123)", t0 * MS, 20 * MS])
        ops.append(["%hpx_mla_paged = bf16[64,128,512] custom-call(s32[64] "
                    "%t)", t0 * MS, 4 * MS])
        ops.append(["fusion.3", (t0 + 10) * MS, 10 * MS])
    mods.append(["jit_chunk(9)", 80 * MS, 10 * MS])
    ops.append(["%hpx_mla_paged = bf16[1,128,512] custom-call(s32[1] %t)",
                80 * MS, 10 * MS])
    host = [["bench.trace_window", 0.0, 100 * MS],
            ["bench.step", 0.0, 100 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}], "host": host}


def test_the_three_readers_on_numbers_known_by_construction():
    from chipbench import harness

    class Ctx:
        peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}

    def reader(name):
        return harness.load_by_path(f"chipbench/layers/{name}.py").read
    t = synthetic()
    roof = reader("mla_attn_roofline.docqa")
    # 8 ms of kernel; bytes need 2 ms, operations 1: the bytes bound, 25%
    assert roof(t, {"traced_latent_bytes": 2 * 819e6,
                    "traced_latent_flops": 197e9}, Ctx) == pytest.approx(25.0)
    # ... operations 4 ms: they bound, 50%
    assert roof(t, {"traced_latent_bytes": 2 * 819e6,
                    "traced_latent_flops": 4 * 197e9}, Ctx) == \
        pytest.approx(50.0)
    # never clamped: a count that is too high reads over 100
    assert roof(t, {"traced_latent_bytes": 1,
                    "traced_latent_flops": 10 * 197e9}, Ctx) == \
        pytest.approx(125.0)
    assert reader("prefix_hit_pct")(
        None, {"prompt_tokens_matched": 14900,
               "prompt_tokens_admitted": 15060}, Ctx) == \
        pytest.approx(100 * 14900 / 15060)
    assert reader("routed_here_pct")(
        None, {"moe_routed_here": 240.0, "moe_routed": 1920.0}, Ctx) == 12.5
    # nothing to read -> nothing, never 0 (a program with no such
    # kernel or counter: the parent commit)
    bare = synthetic()
    bare["devices"][0]["ops"] = [o for o in bare["devices"][0]["ops"]
                                 if "hpx_mla" not in o[0]]
    for name in sorted(MINE):
        assert reader(name)(bare, {}, Ctx) is None
        assert reader(name)(None, {}, Ctx) is None
    assert roof(t, {"traced_latent_bytes": 1}, Ctx) is None
    assert roof(t, {"traced_latent_bytes": 1, "traced_latent_flops": 1},
                type("C", (), {"peaks": None})) is None
    assert reader("prefix_hit_pct")(
        None, {"prompt_tokens_matched": 0, "prompt_tokens_admitted": 0},
        Ctx) is None
    assert reader("routed_here_pct")(None, {"moe_routed": 10.0}, Ctx) is None
