"""The cell `laguna-xs2.mixed-closed` at a tiny size on the CPU: end to
end through the benchmark's own command, its control NOT correct through
`Context.result`, and a reference with one piece of the mathematics left
out not correct either. The readings at the cell's own size are in
PERF.md.
"""

import json
import os

import pytest

from chipbench.tests import helpers as h

CELL = "laguna-xs2.mixed-closed"
REHEARSE = os.path.join(h.HERE, "rehearse_mixed.json")
MINE = {"moe_gmm_roofline", "paged_attn_roofline.mixed",
        "moe_step_share_pct", "experts_hit_pct",
        "kv_window_blocks_used_pct"}


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_line(trace):
    p, lines = h.run_cell(CELL, trace=trace, seed=2**31 + 4242,
                          rehearse=REHEARSE)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(lines[-1])
    assert set(line) - {"rehearsal", "checks", "breakdown"} == h.RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearsal"] is True
    assert line["checks"]["moe_tokens_dropped"] == {"value": 0.0,
                                                    "limit": 0.0}
    if trace:       # the counters read on a CPU; the trace's do not
        assert {"experts_hit_pct", "kv_window_blocks_used_pct"} <= \
            set(line["metrics"]) <= MINE | {
                m["name"] for m in h.bench()["per_layer"]
                if CELL in m.get("workloads", [])}
        assert 0 < line["metrics"]["experts_hit_pct"]["value"] <= 100
        assert 0 < line["metrics"]["kv_window_blocks_used_pct"]["value"] < 100
    else:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p90_ms",
                                        "setup_s"}
    window = [json.loads(ln) for ln in lines if '"phase": "window"' in ln][0]
    assert window["window_blocks_freed"] > 0 and window["moe_dropped"] == 0


def test_the_benchmark_gained_entries_and_lost_none():
    b = h.bench()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna-xs2", "closed-32-long", 1)
    conf = {c["name"]: c for c in b["configs"]}["laguna-xs2"]
    assert sorted(conf["reduced"]) == sorted([
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer"])
    for m in b["per_layer"]:
        if m["name"] in MINE:
            assert m["workloads"] == [CELL]
            assert os.path.exists(os.path.join(
                h.ROOT, "chipbench", "layers", m["name"] + ".py"))
    assert MINE <= {m["name"] for m in b["per_layer"]}


def test_the_configuration_holds_every_published_width():
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/laguna-xs2.json")))
    want = {"hidden_size": 2048, "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 8192, "num_experts": 256,
            "num_experts_per_tok": 8, "moe_intermediate_size": 512,
            "shared_expert_intermediate_size": 512, "sliding_window": 512,
            "vocab_size": 100352, "num_attention_heads": 48,
            "moe_routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06}
    assert {k: conf[k] for k in want} == want
    assert conf["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert conf["layer_types"] == ["full_attention"] + \
        ["sliding_attention"] * 3 + ["full_attention"]
    assert conf["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    full = conf["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["rope_theta"],
            full["partial_rotary_factor"]) == ("yarn", 64, 500000, 0.5)
    assert conf["source_values"]["num_hidden_layers"] == 40
    assert len(conf["assumed"]) == 5
    assert conf["server"] == {"paged": True, "slots": 32, "smax": 4864}


def _fresh_programs():
    from hpx_tpu.models import transformer
    transformer._PROGRAMS.clear()


@pytest.fixture(scope="module")
def sound_run():
    _fresh_programs()
    ctx = h.in_process_ctx(CELL, REHEARSE)
    driver = ctx.driver()
    return ctx, driver, driver.run(ctx)


def test_sound_run_is_correct_and_the_int8_control_is_not(sound_run):
    from chipbench import control
    ctx, driver, outcome = sound_run
    program = ctx.result(outcome)
    assert program["correct"] is True, program["checks"]
    read = driver.control(ctx, outcome)
    line = ctx.result(control.swapped(outcome, read["checks"]))
    assert line["correct"] is False, line["checks"]
    assert any(line["checks"][n]["value"] > line["checks"][n]["limit"]
               for n in read["checks"])


@pytest.mark.parametrize("piece", ["gate", "scale", "window_edge",
                                   "shared"])
def test_a_reference_with_a_piece_left_out_fails_correct(sound_run, piece):
    """The comparison that decides `correct`, with the reference's
    gate, its 2.5, its window edge or its shared expert left out: the
    served tokens then lie far below what that reference puts first."""
    from chipbench import control
    ctx, driver, outcome = sound_run
    params, requests, length, out_max = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(params, ctx.config, requests,
                                       length, out_max, leave_out=(piece,))
    broken = {n: v for n, v, _ in driver.gap_checks(
        driver.gap_numbers(gaps), ctx.config)}
    line = ctx.result(control.swapped(outcome, broken))
    assert line["correct"] is False, line["checks"]
    assert line["checks"]["gap_max"]["value"] > 0.1


def test_control_command_exits_0_only_if_the_control_fails(capsys):
    from chipbench import control
    _fresh_programs()
    argv = ["--workload", CELL, "--seeds", "5,2147483659",
            "--seconds", "0.5", "--rehearse", REHEARSE]
    assert control.main(argv) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rows[-1]["every_program_correct_and_every_control_not"] is True


def test_bytes_the_two_mechanisms_need():
    from chipbench import opcount_mixed as oc
    # one slot at position 1000: a full layer reads 1001 rows, a window
    # layer its last 512; one at 99 reads 100 in both
    one = 2 * 8 * 128 * 2
    assert oc.paged_decode_attention_bytes([1000], [0, 512], 8, 128) == \
        one * (1001 + 512)
    assert oc.paged_decode_attention_bytes([99, 1000], [0, 512, 512], 8,
                                           128) == one * (
        100 * 3 + 1001 + 2 * 512)
    per = 3 * 2048 * 512 * 2
    assert oc.routed_expert_bytes(161.5, 4, 2048, 512) == int(
        4 * 161.5 * per)
    assert oc.expert_bytes(161.5, 1, 4, 2048, 512, 256, 512) == int(
        4 * (161.5 * per + (2048 * 256 + 3 * 2048 * 512) * 2))
