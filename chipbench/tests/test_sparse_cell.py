"""The cell `minicpm-sala.longctx-closed` at a tiny size on the CPU: end
to end through the benchmark's own command, its three controls NOT
correct through `Context.result` (each on its own numbers, and as the
driver's `control` hands them over), a reference with one piece of the
mathematics left out not correct either, and the four readers this cell
brought on a trace whose numbers are known by construction. The
readings at the cell's own size are in PERF.md.
"""

import json
import os

import pytest

from chipbench.tests import helpers as h

CELL = "minicpm-sala.longctx-closed"
REHEARSE = os.path.join(h.HERE, "rehearse_sparse.json")
MINE = {"sparse_attn_roofline", "lightning_step_roofline",
        "sparse_rows_read_pct", "mixer_step_share_pct.longctx"}
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
        "state_rel_err", "selection_missed", "selection_score_gap"}
    if trace:       # the counters read on a CPU; the trace's do not
        assert {"sparse_rows_read_pct", "state_mb_per_slot"} <= \
            set(line["metrics"]) <= {
                m["name"] for m in h.bench()["per_layer"]
                if CELL in m.get("workloads", [])}
        assert 20 < line["metrics"]["sparse_rows_read_pct"]["value"] < 100
        # 2 lightning layers x 4 heads x 16 x 16 float32
        assert line["metrics"]["state_mb_per_slot"]["value"] == \
            pytest.approx(2 * 4 * 256 * 4 / 1e6)
    else:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p90_ms",
                                        "setup_s"}
    window = [json.loads(ln) for ln in lines if '"phase": "window"' in ln][0]
    assert window["state_resets"] > 0 and window["sparse_steps"] > 0
    assert window["state_reprefills"] == 0
    assert 0 < window["sparse_rows_walked"] < window["sparse_rows_live"]


def test_the_benchmark_gained_entries_and_lost_none():
    b = h.bench()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala", "closed-64-longctx", 1)
    conf = {c["name"]: c for c in b["configs"]}["minicpm-sala"]
    assert conf["reduced"] == ["num_hidden_layers", "mixer_types"]
    for m in b["per_layer"]:
        if m["name"] in MINE:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_p90_ms"
            assert os.path.exists(os.path.join(
                h.ROOT, "chipbench", "layers", m["name"] + ".py"))
    assert MINE <= {m["name"] for m in b["per_layer"]}
    assert [w["name"] for w in b["workloads"]][:5] == [
        "sc2-3b.gen-closed", "hpx-stencil.dataflow-coarse",
        "laguna-xs2.mixed-closed", "kimi-linear.reason-closed",
        "deepseek-v2.docqa-closed"]
    assert cell["chips"] == 1
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-64-longctx.json")))
    assert (mix["callers"], mix["stagger_steps"], mix["ramp_steps"],
            mix["length_cycle"], mix["check_requests"]) == (
        64, 48, 3328, 512, 4)
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "min": 12288,
                                    "max": 32768}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 2048,
                                    "max": 4096}


def test_the_mix_fits_the_configurations_smax():
    from chipbench import harness
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/minicpm-sala.json")))
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-64-longctx.json")))
    gen = harness.load_by_path(mix["generator"]).make(mix, 1, vocab=100)
    longest = max(sum(gen.lengths(k)) for k in range(mix["length_cycle"]))
    assert longest == 35764 <= conf["server"]["smax"] == 35840 == 70 * 512


def test_the_configuration_holds_every_published_width():
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/minicpm-sala.json")))
    want = {"hidden_size": 4096, "intermediate_size": 16384,
            "num_attention_heads": 32, "num_key_value_heads": 2,
            "head_dim": 128, "lightning_nh": 32, "lightning_head_dim": 128,
            "vocab_size": 73448, "rms_norm_eps": 1e-06, "scale_emb": 12,
            "scale_depth": 1.4, "dim_model_base": 256, "rope_theta": 10000,
            "attn_use_rope": False, "lightning_use_rope": True,
            "qk_norm": True, "tie_word_embeddings": False}
    assert {k: conf[k] for k in want} == want
    assert conf["num_hidden_layers"] == 8
    assert conf["mixer_types"] == ["minicpm4"] + ["lightning-attn"] * 6 + [
        "minicpm4"]
    assert conf["published_layers"] == list(range(9, 17))
    assert conf["reduced"] == ["num_hidden_layers", "mixer_types"]
    src = conf["source_values"]
    assert src["num_hidden_layers"] == 32 and len(src["mixer_types"]) == 32
    assert src["mixer_types"][9:17] == conf["mixer_types"]
    assert src["mixer_types"].count("minicpm4") == 8
    assert conf["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    assert len(conf["assumed"]) >= 8
    assert conf["server"] == {"paged": True, "slots": 64, "smax": 35840,
                              "num_blocks": 24576, "prefill_chunk": 512}
    assert conf["control_precision"] == ["int8", "state_bf16",
                                         "window_only"]
    assert set(conf["correct"]["held_by"]) == set(conf["correct"]["limits"])
    # the catalog's row, number for number, but for the cut in depth
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    rows = [json.loads(ln) for ln in open(path)] \
        if os.path.exists(path) else []
    for row in rows:
        if row["name"] == "MiniCPM-SALA":
            assert conf["source"] == row["source_url"]
            assert {k for k, v in row["config"].items()
                    if conf.get(k) != v} == set(conf["reduced"])


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
    assert set(read["numbers"]) == {"int8", "state_bf16", "window_only"}
    # as handed over (each number from the control it is held by) ...
    line = ctx.result(control.swapped(outcome, read["checks"]))
    assert line["correct"] is False, line["checks"]
    held = ctx.config["correct"]["held_by"]
    # ... and each alone, on all its own numbers and by its own limit
    for name, numbers in read["numbers"].items():
        assert numbers["correct"] is False
        alone = {n: numbers[n] for n in read["checks"]}
        line = ctx.result(control.swapped(outcome, alone))
        assert line["correct"] is False, (name, line["checks"])
        # (at this float32 toy the int8 control's gaps are a few 1e-5
        # and move with the requests the window happens to finish: it
        # is held here by the state it feeds, as on the chip by both)
        mine = [n for n, c in held.items() if c == name] + (
            ["state_rel_err"] if name == "int8" else [])
        assert any(line["checks"][n]["value"] > line["checks"][n]["limit"]
                   for n in mine), (name, line["checks"])
    assert program["checks"]["state_rel_err"]["value"] < 1e-5
    assert read["numbers"]["state_bf16"]["state_rel_err"] > 1e-3
    assert program["checks"]["selection_missed"]["value"] == 0.0
    assert read["numbers"]["window_only"]["selection_missed"] >= 0.25


def test_a_control_that_passes_every_limit_is_what_the_driver_hands_over(
        sound_run, monkeypatch):
    """The verdict cannot hide a control that reads correct behind the
    others' failures: its own numbers go into the program's place."""
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
    "decay", "qk_norm", "sparse_gate", "lightning_gate", "out_norm",
    "sparse_rope", "lightning_rope", "scale_emb", "scale_depth"])
def test_a_reference_with_a_piece_left_out_fails_correct(sound_run, piece):
    """The comparison that decides `correct`, with one piece of the
    reference's mathematics left out: the served tokens then lie below
    what that reference puts first. (The logit scale moves no token's
    rank: tests/test_minicpm_sala_serving.py holds it by the logits.)"""
    from chipbench import control
    from chipbench.drivers import serving as base
    ctx, driver, outcome = sound_run
    params, requests, _, _, _ = outcome["control_inputs"]
    ref = ctx.reference()
    gaps = []
    for prompt, served in requests:
        n = len(prompt) + len(served)
        gaps.append(ref.served_gaps(params, ctx.config, [(prompt, served)],
                                    n + -n % 128, len(served),
                                    leave_out=(piece,)))
    import numpy as np
    numbers = base.gap_numbers(np.concatenate(gaps))
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
    assert set(seed["control_numbers"]) == {"int8", "state_bf16",
                                            "window_only"}


def test_bytes_the_two_mechanisms_need():
    from chipbench import opcount_sparse as oc
    # below the dense length every row; past it 63 whole blocks and the
    # rows <= p of p's own
    assert oc.selected_rows(100, 64, 64, 8192) == 101
    assert oc.selected_rows(8191, 64, 64, 8192) == 8192
    assert oc.selected_rows(8192, 64, 64, 8192) == 63 * 64 + 1
    assert oc.selected_rows(20000, 64, 64, 8192) == 63 * 64 + 20000 % 64 + 1
    # K and V, 2 layers x 2 kv heads x 128 x 2 B a row
    assert oc.selected_row_bytes([8192, 20000], 2, 2, 128, 2, 64, 64,
                                 8192) == 2048 * (4033 + 4065)
    # 64 live slots x 6 layers x 32 heads x 128 x 128 float32, in and out
    assert oc.lightning_state_bytes(64, 6, 32, 128) == 64 * 6 * 2 * 2097152
    assert oc.lightning_state_bytes(0, 6, 32, 128) == 0


def synthetic():
    """A 100 ms window; two runs of `jit_step` (10..30, 50..70 ms), each
    holding a 4 ms `hpx_lightning_step`, a 1 ms `hpx_paged_sparse` and a
    15 ms fusion; one `jit_chunk` with a custom call of its own, which
    no reader of the step may count."""
    ops, mods = [], []
    for t0 in (10, 50):
        mods.append(["jit_step(123)", t0 * MS, 20 * MS])
        ops.append(["%hpx_lightning_step = (f32[4,2,1,128], "
                    "f32[4,2,128,128]) custom-call(f32[4,2,8,128] %x)",
                    t0 * MS, 4 * MS])
        ops.append(["%hpx_paged_sparse = bf16[4,2,16,128] custom-call("
                    "s32[8,128] %t)", (t0 + 4) * MS, 1 * MS])
        ops.append(["fusion.3", (t0 + 5) * MS, 15 * MS])
    mods.append(["jit_chunk(9)", 80 * MS, 10 * MS])
    ops.append(["%hpx_lightning_step = f32[1] custom-call(s32[8] %e)",
                80 * MS, 10 * MS])
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
    assert reader("lightning_step_roofline")(
        t, {"traced_state_bytes": 819e6}, Ctx) == pytest.approx(12.5)
    # ... and 2 ms: 50%
    assert reader("sparse_attn_roofline")(
        t, {"traced_sparse_bytes": 819e6}, Ctx) == pytest.approx(50.0)
    # (4 + 1) ms of the step's 20
    assert reader("mixer_step_share_pct.longctx")(t, {}, Ctx) == \
        pytest.approx(25.0)
    assert reader("sparse_rows_read_pct")(
        None, {"sparse_rows_walked": 18, "sparse_rows_live": 100},
        Ctx) == pytest.approx(18.0)
    # nothing to read -> nothing, never 0 (a program with no such
    # kernel or counter: the parent commit)
    bare = synthetic()
    bare["devices"][0]["ops"] = [o for o in bare["devices"][0]["ops"]
                                 if "hpx_" not in o[0]]
    for name in sorted(MINE):
        assert reader(name)(bare, {}, Ctx) is None
        assert reader(name)(None, {}, Ctx) is None
    assert reader("sparse_attn_roofline")(t, {}, Ctx) is None
    assert reader("lightning_step_roofline")(
        t, {"traced_state_bytes": 1}, type("C", (), {"peaks": None})) \
        is None
