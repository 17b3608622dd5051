"""The cell `jamba2-3b.chat-closed` at a tiny size on the CPU: end to
end through the benchmark's own command, its two controls NOT correct
through `Context.result` (each on its own numbers, and as the driver's
`control` hands them over), a reference with one piece of the
mathematics left out not correct either, the bytes the two kernels
need by hand, and the four readers this cell brought on a trace whose
numbers are known by construction. Entries of BENCHMARK.json are found
BY NAME. The readings at the cell's own size are in PERF.md.
"""

import json
import os

import pytest

from chipbench.tests import helpers as h

CELL = "jamba2-3b.chat-closed"
REHEARSE = os.path.join(h.HERE, "rehearse_ssm.json")
MINE = {"mamba_step_roofline": "tpot_p90_ms",
        "mamba_scan_roofline": "out_tok_s",
        "mixer_step_share_pct.chat": "tpot_p90_ms",
        "admit_wait_steps": "out_tok_s"}
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
    assert set(line["checks"]) == {
        "window_compiles", "requests_short", "requests_failed", "gap_mean",
        "state_rel_err"}
    if trace:       # the counters read on a CPU; the trace's do not
        assert {"admit_wait_steps", "state_mb_per_slot",
                "batch_occupancy_pct"} <= set(line["metrics"]) <= {
                m["name"] for m in h.bench()["per_layer"]
                if CELL in m.get("workloads", [])}
        # 3 Mamba layers x (16 x 128 float32 state + 3 x 128 tail rows)
        assert line["metrics"]["state_mb_per_slot"]["value"] == \
            pytest.approx(3 * (16 * 128 + 3 * 128) * 4 / 1e6)
        assert 0 <= line["metrics"]["admit_wait_steps"]["value"] < 4
    else:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p90_ms",
                                        "setup_s"}
    window = [json.loads(ln) for ln in lines if '"phase": "window"' in ln][0]
    assert window["state_resets"] > 0 and window["state_reprefills"] == 0
    assert window["prefill_chunks"] >= window["state_resets"]
    assert window["prefill_rows"] > 8 * window["state_resets"]


def test_the_benchmark_gained_entries_and_lost_none():
    b = h.bench()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "closed-256-chat", 1)
    conf = {c["name"]: c for c in b["configs"]}["jamba2-3b"]
    assert conf["reduced"] == [] and conf["file"] == \
        "chipbench/configs/jamba2-3b.json"
    by_name = {m["name"]: m for m in b["per_layer"]}
    for name, moves in MINE.items():
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == moves
        assert os.path.exists(os.path.join(
            h.ROOT, "chipbench", "layers", name + ".py"))
    for m in b["end_to_end"]:
        if m["name"] in ("out_tok_s", "tpot_p90_ms"):
            assert CELL in m["workloads"]
    assert CELL in by_name["state_mb_per_slot"]["workloads"]
    # every per-layer metric the five older serving cells all report
    older = [w["name"] for w in b["workloads"]
             if w["name"] not in (CELL, "hpx-stencil.dataflow-coarse")]
    assert len(older) == 5
    for m in b["per_layer"]:
        if set(older) <= set(m.get("workloads", [])):
            assert CELL in m["workloads"], m["name"]
    # nothing that was there went
    assert {"sc2-3b.gen-closed", "hpx-stencil.dataflow-coarse",
            "laguna-xs2.mixed-closed", "kimi-linear.reason-closed",
            "deepseek-v2.docqa-closed", "minicpm-sala.longctx-closed"} <= \
        {w["name"] for w in b["workloads"]}
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-256-chat.json")))
    assert (mix["callers"], mix["stagger_steps"], mix["ramp_steps"],
            mix["length_cycle"], mix["check_requests"],
            mix["check_states"]) == (256, 2, 1024, 2048, 16, 4)
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "min": 64,
                                    "max": 1024}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 128,
                                    "max": 768}
    assert mix["generator"] == "chipbench/traffic_gen/requests.py"


def test_the_mix_fits_the_configurations_smax():
    from chipbench import harness
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/jamba2-3b.json")))
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-256-chat.json")))
    gen = harness.load_by_path(mix["generator"]).make(mix, 1, vocab=100)
    longest = max(sum(gen.lengths(k)) for k in range(mix["length_cycle"]))
    server = conf["server"]
    assert longest == 1762 <= server["smax"] == 1792 == 1024 + 768
    assert gen.frame() == (1792, 768)
    assert server == {"paged": True, "slots": 256, "smax": 1792,
                      "block_size": 64, "num_blocks": 256 * 28 + 1,
                      "prefill_chunk": 512}
    assert mix["callers"] == server["slots"]


def test_the_configuration_is_the_catalogs_row_uncut():
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/jamba2-3b.json")))
    assert conf["reduced"] == [] and len(conf["assumed"]) >= 8
    assert conf["control_precision"] == ["int8", "state_bf16"]
    assert conf["correct"]["held_by"] == {"gap_mean": "int8",
                                          "state_rel_err": "state_bf16"}
    assert set(conf["correct"]["limits"]) == set(conf["correct"]["held_by"])
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(ln) for ln in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["name"] == "AI21-Jamba2-3B":
            assert conf["source"] == row["source_url"]
            assert {k for k, v in row["config"].items()
                    if conf.get(k, "absent") != v} == set()


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
    assert set(read["numbers"]) == {"int8", "state_bf16"}
    line = ctx.result(control.swapped(outcome, read["checks"]))
    assert line["correct"] is False, line["checks"]
    held = ctx.config["correct"]["held_by"]
    for name, numbers in read["numbers"].items():
        assert numbers["correct"] is False
        alone = {n: numbers[n] for n in read["checks"]}
        line = ctx.result(control.swapped(outcome, alone))
        assert line["correct"] is False, (name, line["checks"])
        mine = [n for n, c in held.items() if c == name]
        assert any(line["checks"][n]["value"] > line["checks"][n]["limit"]
                   for n in mine), (name, line["checks"])
    assert program["checks"]["state_rel_err"]["value"] < 1e-5
    assert read["numbers"]["state_bf16"]["state_rel_err"] > 1e-3


def test_a_control_that_passes_every_limit_is_what_the_driver_hands_over(
        sound_run, monkeypatch):
    from chipbench import control
    ctx, driver, outcome = sound_run
    loose = dict(ctx.config["correct"], limits={
        n: 1e9 for n in ctx.config["correct"]["limits"]})
    monkeypatch.setitem(ctx.config, "correct", loose)
    read = driver.control(ctx, outcome)
    assert all(r["correct"] for r in read["numbers"].values())
    line = ctx.result(control.swapped(
        dict(outcome, checks=[(n, v, 1e9 if n in loose["limits"] else lim)
                              for n, v, lim in outcome["checks"]]),
        read["checks"]))
    assert line["correct"] is True


@pytest.mark.parametrize("piece", [
    "conv_bias", "D", "dt_norm", "b_norm", "c_norm", "dt_bias", "softplus",
    "gate"])
def test_a_reference_with_a_piece_left_out_fails_correct(sound_run, piece):
    """The comparison that decides `correct`, with one piece of the
    reference's mathematics left out: the served tokens then lie below
    what that reference puts first (or it overflows: not correct
    either)."""
    from chipbench import control
    from chipbench.drivers import serving as base
    ctx, driver, outcome = sound_run
    params, requests, length, out_max, _ = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(
        params, ctx.config, requests, length, out_max, leave_out=(piece,))
    numbers = base.gap_numbers(gaps)
    line = ctx.result(control.swapped(
        outcome, {"gap_mean": numbers["gap_mean"]}))
    assert line["correct"] is False, line["checks"]


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
    assert set(seed["control_numbers"]) == {"int8", "state_bf16"}


def test_bytes_the_two_kernels_need():
    from chipbench import opcount_ssm as oc
    # 256 live slots x 26 layers x 5120 x 16 float32, in and out
    assert oc.mamba_state_bytes(256, 26, 5120, 16) == \
        256 * 26 * 2 * 327680 == 4362076160
    assert oc.mamba_state_bytes(0, 26, 5120, 16) == 0
    # one chunk of 300 real rows, a layer: u, dt, y 3 x 5120 and B, C 2 x
    # 16 float32 a row, the state once in and once out
    assert oc.mamba_scan_bytes(300, 1, 1, 5120, 16) == \
        300 * (15360 + 32) * 4 + 2 * 327680
    # two chunks, 26 layers: rows add, a state in and out a chunk
    assert oc.mamba_scan_bytes(812, 2, 26, 5120, 16) == 26 * (
        812 * 15392 * 4 + 2 * 2 * 327680)
    assert oc.mamba_scan_bytes(0, 0, 26, 5120, 16) == 0


def synthetic():
    """A 100 ms window; two runs of `jit_step` (10..30, 50..70 ms), each
    holding a 4 ms `hpx_mamba_step`, a 1 ms `hpx_paged_fused` and a 15
    ms fusion; one `jit_chunk` of 10 ms holding a 2 ms `hpx_mamba_scan`
    and a step kernel's name of its own, which no reader of the step
    may count."""
    ops, mods = [], []
    for t0 in (10, 50):
        mods.append(["jit_step(123)", t0 * MS, 20 * MS])
        ops.append(["%hpx_mamba_step = (f32[32,8,5120], f32[32,8,16,5120]) "
                    "custom-call(f32[32,8,5120] %u)", t0 * MS, 4 * MS])
        ops.append(["%hpx_paged_fused = bf16[256,1,20,128] custom-call("
                    "s32[256,28] %t)", (t0 + 4) * MS, 1 * MS])
        ops.append(["fusion.3", (t0 + 5) * MS, 15 * MS])
    mods.append(["jit_chunk(9)", 80 * MS, 10 * MS])
    ops.append(["%hpx_mamba_scan = (f32[1,512,5120], f32[1,8,16,640]) "
                "custom-call(s32[1] %v)", 80 * MS, 2 * MS])
    ops.append(["%hpx_mamba_step = f32[1] custom-call(s32[8] %e)",
                83 * MS, 5 * MS])
    host = [["bench.trace_window", 0.0, 100 * MS],
            ["bench.step", 0.0, 100 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}], "host": host}


def test_the_four_readers_on_a_trace_of_known_numbers():
    from chipbench import harness

    class Ctx:
        peaks = {"hbm_bytes_per_s": 819e9}

    def reader(name):
        return harness.load_by_path(f"chipbench/layers/{name}.py").read
    t = synthetic()
    # 819e6 bytes need 1 ms at the peak; the kernel took 8 ms: 12.5%
    assert reader("mamba_step_roofline")(
        t, {"traced_state_bytes": 819e6}, Ctx) == pytest.approx(12.5)
    # ... and the scan 2 ms: 50%
    assert reader("mamba_scan_roofline")(
        t, {"traced_scan_bytes": 819e6}, Ctx) == pytest.approx(50.0)
    # (4 + 1) ms of the step's 20
    assert reader("mixer_step_share_pct.chat")(t, {}, Ctx) == \
        pytest.approx(25.0)
    assert reader("admit_wait_steps")(
        None, {"admit_wait_steps": 0.4}, Ctx) == pytest.approx(0.4)
    # nothing to read -> nothing, never 0 (a program with no such
    # kernel or counter: the parent commit)
    bare = synthetic()
    bare["devices"][0]["ops"] = [o for o in bare["devices"][0]["ops"]
                                 if "hpx_" not in o[0]]
    for name in sorted(MINE):
        assert reader(name)(bare, {}, Ctx) is None
        assert reader(name)(None, {}, Ctx) is None
    assert reader("mamba_step_roofline")(t, {}, Ctx) is None
    assert reader("mamba_scan_roofline")(t, {}, Ctx) is None
    assert reader("mamba_step_roofline")(
        t, {"traced_state_bytes": 1}, type("C", (), {"peaks": None})) \
        is None
