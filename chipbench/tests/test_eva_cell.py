"""The cell `evabyte.doc-closed` at a tiny size on the CPU: end to end
through the benchmark's own command, its two controls NOT correct
through `Context.result` (each on its own numbers, and as the driver's
`control` hands them over; `window_only`, the mechanism left out, among
them), a reference with one piece of the mathematics left out not
correct either, the bytes the walk needs by hand at three positions
(before, on and after a window boundary), and the three readers this
cell brought on a trace whose numbers are known by construction.
Entries of BENCHMARK.json are found BY NAME. The readings at the cell's
own size are in PERF.md.
"""

import json
import os

import pytest

from chipbench.tests import helpers as h

CELL = "evabyte.doc-closed"
REHEARSE = os.path.join(h.HERE, "rehearse_eva.json")
MINE = {"eva_attn_roofline": "tpot_p90_ms",
        "mixer_step_share_pct.doc": "tpot_p90_ms",
        "eva_rows_read_pct": "tpot_p90_ms"}
MS = 1e6


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_line(trace):
    p, lines = h.run_cell(CELL, trace=trace, seed=2**31 + 4949,
                          rehearse=REHEARSE)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(lines[-1])
    assert set(line) - {"rehearsal", "checks", "breakdown"} == h.RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert set(line["checks"]) == {
        "window_compiles", "requests_short", "requests_failed", "gap_mean",
        "summary_rel_err", "summary_rows_miscounted"}
    if trace:       # the counters read on a CPU; the trace's do not
        assert {"eva_rows_read_pct", "batch_occupancy_pct",
                "kv_blocks_used_pct"} <= set(line["metrics"]) <= {
                m["name"] for m in h.bench()["per_layer"]
                if CELL in m.get("workloads", [])}
        # windows of 16 behind prompts of 20-60: well under plain
        # attention's 100, well over nothing
        assert 20 < line["metrics"]["eva_rows_read_pct"]["value"] < 80
    else:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p90_ms",
                                        "setup_s"}
    window = [json.loads(ln) for ln in lines if '"phase": "window"' in ln][0]
    assert window["eva_rolls"] > 0 and window["eva_reprefills"] == 0
    assert window["eva_blocks_freed"] > 0
    assert 0 < window["eva_rows_attended"] < window["eva_tokens_behind"]
    assert window["window_compiles"] == 0


def test_the_benchmark_gained_entries_and_lost_none():
    b = h.bench()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "evabyte-6.5b", "closed-24-bytes", 1)
    conf = {c["name"]: c for c in b["configs"]}["evabyte-6.5b"]
    assert conf["reduced"] == ["num_hidden_layers"] and conf["file"] == \
        "chipbench/configs/evabyte-6.5b.json"
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, moves in MINE.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == moves
        assert os.path.exists(os.path.join(
            h.ROOT, "chipbench", "layers", name + ".py"))
    for m in b["end_to_end"]:
        if m["name"] in ("out_tok_s", "tpot_p90_ms"):
            assert m["workloads"][-1] == CELL
    # every per-layer metric the six older serving cells all report
    older = [w["name"] for w in b["workloads"]
             if w["name"] not in (CELL, "hpx-stencil.dataflow-coarse")]
    assert len(older) == 6
    for m in b["per_layer"]:
        if set(older) <= set(m.get("workloads", [])):
            assert m["workloads"][-1] == CELL, m["name"]
    # the exact rows live in the full group's pools: no window group
    assert CELL not in by_name["kv_window_blocks_used_pct"]["workloads"]
    # 8 cells of 24, none on four chips
    assert len(b["workloads"]) == 8
    assert all(w["chips"] == 1 for w in b["workloads"])
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-24-bytes.json")))
    assert (mix["callers"], mix["stagger_steps"], mix["ramp_steps"],
            mix["check_requests"], mix["check_summaries"]) == (
                24, 32, 1536, 8, 4)
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "min": 4096,
                                    "max": 16384}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 768,
                                    "max": 2304}
    assert mix["generator"] == "chipbench/traffic_gen/requests.py"


def test_the_mix_fits_the_configurations_smax():
    from chipbench import harness
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/evabyte-6.5b.json")))
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-24-bytes.json")))
    gen = harness.load_by_path(mix["generator"]).make(mix, 1, vocab=320)
    lens = [gen.lengths(k) for k in range(mix["length_cycle"])]
    server = conf["server"]
    assert max(p + o for p, o in lens) <= server["smax"] == 18688 \
        == 16384 + 2304
    assert 8000 < sum(p for p, _ in lens) / len(lens) < 9800
    assert 1450 < sum(o for _, o in lens) / len(lens) < 1620
    assert server == {"paged": True, "slots": 24, "smax": 18688,
                      "block_size": 64, "num_blocks": 24 * 50 + 1,
                      "prefill_chunk": 512}
    assert mix["callers"] == server["slots"]


def test_the_configuration_is_the_catalogs_row_cut_in_depth_alone():
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/evabyte-6.5b.json")))
    assert conf["reduced"] == ["num_hidden_layers"]
    assert len(conf["assumed"]) >= 8
    assert conf["control_precision"] == ["int8", "window_only"]
    assert set(conf["correct"]["limits"]) == set(conf["correct"]["held_by"])
    assert (conf["hidden_size"], conf["num_attention_heads"],
            conf["num_key_value_heads"], conf["intermediate_size"],
            conf["chunk_size"], conf["window_size"], conf["vocab_size"],
            conf["num_pred_heads"], conf["rope_theta"],
            conf["num_hidden_layers"]) == (
                4096, 32, 32, 11008, 16, 2048, 320, 8, 100000, 8)
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(ln) for ln in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["name"] == "EvaByte":
            assert conf["source"] == row["source_url"]
            assert {k for k, v in row["config"].items()
                    if conf.get(k, "absent") != v} == {"num_hidden_layers"}


def _fresh_programs():
    from hpx_tpu.models import transformer
    transformer._PROGRAMS.clear()


@pytest.fixture(scope="module")
def sound_run():
    _fresh_programs()
    ctx = h.in_process_ctx(CELL, REHEARSE)
    driver = ctx.driver()
    return ctx, driver, driver.run(ctx)


def test_sound_run_is_correct_and_every_control_is_not(sound_run):
    from chipbench import control
    ctx, driver, outcome = sound_run
    program = ctx.result(outcome)
    assert program["correct"] is True, program["checks"]
    read = driver.control(ctx, outcome)
    assert set(read["numbers"]) == {"int8", "window_only"}
    line = ctx.result(control.swapped(outcome, read["checks"]))
    assert line["correct"] is False, line["checks"]
    for name, numbers in read["numbers"].items():
        assert numbers["correct"] is False
        alone = {n: numbers[n] for n in read["checks"]}
        line = ctx.result(control.swapped(outcome, alone))
        assert line["correct"] is False, (name, line["checks"])
    # the mechanism left out shows in the served bytes, not in the
    # summaries themselves (they are pooled all the same)
    only = read["numbers"]["window_only"]
    assert only["gap_mean"] > 100 * ctx.config["correct"]["limits"][
        "gap_mean"]
    assert only["summary_rel_err"] < 1e-5
    assert read["numbers"]["int8"]["summary_rel_err"] > 1e-3
    assert program["checks"]["summary_rel_err"]["value"] < 1e-5
    assert program["checks"]["summary_rows_miscounted"]["value"] == 0


@pytest.mark.parametrize("piece", [
    "mu", "phi_scale", "pool_v", "rope_before_pool", "aligned", "gate",
    "unit_offset"])
def test_a_reference_with_a_piece_left_out_fails_correct(sound_run, piece):
    """The comparison that decides `correct`, with one piece of the
    reference's mathematics left out: the served bytes then lie below
    what that reference puts first."""
    from chipbench import control
    ctx, driver, outcome = sound_run
    params, requests, _ = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(
        params, ctx.config, requests, leave_out=(piece,))
    line = ctx.result(control.swapped(
        outcome, {"gap_mean": float(gaps.mean())}))
    assert line["correct"] is False, line["checks"]


def test_a_miscounted_slot_fails_correct(sound_run):
    """A slot that shows a window's summaries too early (or too few)
    is not correct whatever its rows hold."""
    from chipbench import control
    ctx, driver, outcome = sound_run
    params, _, states = outcome["control_inputs"]
    toks, n, ks, vs = states[-1]
    errs, miscounted = ctx.reference().summary_errors(
        params, ctx.config, [(toks, n - 4, ks[:-4], vs[:-4])])
    assert miscounted == 1
    line = ctx.result(control.swapped(
        outcome, {"summary_rows_miscounted": miscounted}))
    assert line["correct"] is False


def test_control_command_exits_0_only_if_the_controls_fail(capsys):
    from chipbench import control
    _fresh_programs()
    argv = ["--workload", CELL, "--seeds", "5", "--seconds", "0.5",
            "--rehearse", REHEARSE]
    assert control.main(argv) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rows[-1]["every_program_correct_and_every_control_not"] is True
    (seed,) = [r for r in rows if "seed" in r]
    assert set(seed["control_numbers"]) == {"int8", "window_only"}


@pytest.mark.parametrize("pos,rows", [
    (2047, 2048),           # the window's last row: all exact, no summary
    (2048, 128 + 1),        # ON the boundary: one exact row, 128 summaries
    (2049, 128 + 2),        # after it
    (18687, 9 * 128 + 256),  # the mix's last position
])
def test_bytes_the_walk_needs_by_hand(pos, rows):
    from chipbench import opcount_eva as oc
    assert oc.rows_attended(pos, 16, 2048) == rows
    # a K and a V row of 32 x 128 bfloat16 = 16,384 B, 8 layers
    assert oc.walk_bytes([pos], 8, 32, 128, 2, 16, 2048) == \
        rows * 16384 * 8
    assert oc.walk_bytes([pos, pos], 8, 32, 128, 2, 16, 2048) == \
        2 * rows * 16384 * 8
    assert oc.walk_bytes([], 8, 32, 128, 2, 16, 2048) == 0


def synthetic():
    """A 100 ms window; two runs of `jit_step` (10..30, 50..70 ms), each
    holding an 8 ms `hpx_paged_fused` and a 12 ms fusion; one `jit_roll`
    of 1 ms and one `jit_chunk` of 10 ms holding a walk's name of its
    own, which no reader of the step may count."""
    ops, mods = [], []
    for t0 in (10, 50):
        mods.append(["jit_step(123)", t0 * MS, 20 * MS])
        ops.append(["%hpx_paged_fused = bf16[24,1,32,128] custom-call("
                    "s32[24,50] %t)", t0 * MS, 8 * MS])
        ops.append(["fusion.3", (t0 + 8) * MS, 12 * MS])
    mods.append(["jit_roll(7)", 32 * MS, 1 * MS])
    ops.append(["fusion.9", 32 * MS, 1 * MS])
    mods.append(["jit_chunk(9)", 80 * MS, 10 * MS])
    ops.append(["%hpx_paged_fused = bf16[1] custom-call(s32[8] %e)",
                83 * MS, 5 * MS])
    host = [["bench.trace_window", 0.0, 100 * MS],
            ["bench.step", 0.0, 100 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}], "host": host}


def test_the_three_readers_on_a_trace_of_known_numbers():
    from chipbench import harness

    class Ctx:
        peaks = {"hbm_bytes_per_s": 819e9}

    def reader(name):
        return harness.load_by_path(f"chipbench/layers/{name}.py").read
    t = synthetic()
    # 6,552e6 bytes need 8 ms at the peak; the walks took 16 ms: 50%
    assert reader("eva_attn_roofline")(
        t, {"traced_eva_bytes": 6552e6}, Ctx) == pytest.approx(50.0)
    # 16 ms of the steps' 40
    assert reader("mixer_step_share_pct.doc")(t, {}, Ctx) == \
        pytest.approx(40.0)
    assert reader("eva_rows_read_pct")(
        None, {"eva_rows_attended": 157, "eva_tokens_behind": 1000},
        Ctx) == pytest.approx(15.7)
    # nothing to read -> nothing, never 0 (a program with no such
    # kernel or counter: the parent commit)
    bare = synthetic()
    bare["devices"][0]["ops"] = [o for o in bare["devices"][0]["ops"]
                                 if "hpx_" not in o[0]]
    for name in sorted(MINE):
        assert reader(name)(bare, {}, Ctx) is None
        assert reader(name)(None, {}, Ctx) is None
    assert reader("eva_attn_roofline")(t, {}, Ctx) is None
    assert reader("eva_attn_roofline")(
        t, {"traced_eva_bytes": 1}, type("C", (), {"peaks": None})) is None
    assert reader("eva_rows_read_pct")(
        None, {"eva_rows_attended": 5, "eva_tokens_behind": 0}, Ctx) is None
