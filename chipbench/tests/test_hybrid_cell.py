"""The cell `kimi-linear.reason-closed` at a tiny size on the CPU: end to
end through the benchmark's own command, its two controls NOT correct
through `Context.result` (together, as the driver's `control` reads
them, and each alone), a reference with one piece of the mathematics
left out not correct either, and the four readers this cell brought on
a trace whose numbers are known by construction. The readings at the
cell's own size are in PERF.md.
"""

import json
import os

import pytest

from chipbench.tests import helpers as h

CELL = "kimi-linear.reason-closed"
REHEARSE = os.path.join(h.HERE, "rehearse_hybrid.json")
MINE = {"kda_step_roofline", "mla_attn_roofline", "mixer_step_share_pct",
        "state_mb_per_slot"}
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
    assert line["checks"]["moe_tokens_dropped"] == {"value": 0.0,
                                                    "limit": 0.0}
    if trace:       # the counters read on a CPU; the trace's do not
        assert {"experts_hit_pct", "state_mb_per_slot"} <= \
            set(line["metrics"]) <= {
                m["name"] for m in h.bench()["per_layer"]
                if CELL in m.get("workloads", [])}
        assert 0 < line["metrics"]["experts_hit_pct"]["value"] <= 100
        # 3 KDA layers x 2 heads x (16 x 16 state + 3 x 3 x 16 tail) x 4 B
        assert line["metrics"]["state_mb_per_slot"]["value"] == \
            pytest.approx(3 * 2 * (256 + 144) * 4 / 1e6)
    else:
        assert set(line["metrics"]) == {"out_tok_s", "tpot_p90_ms",
                                        "setup_s"}
    window = [json.loads(ln) for ln in lines if '"phase": "window"' in ln][0]
    assert window["state_resets"] > 0 and window["moe_dropped"] == 0
    assert window["state_reprefills"] == 0


def test_the_benchmark_gained_entries_and_lost_none():
    b = h.bench()
    cell = {w["name"]: w for w in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b", "closed-48-reason", 1)
    conf = {c["name"]: c for c in b["configs"]}["kimi-linear-48b"]
    assert conf["reduced"] == ["num_experts", "vocab_size"]
    for m in b["per_layer"]:
        if m["name"] in MINE:
            assert m["workloads"] == [CELL]
            assert os.path.exists(os.path.join(
                h.ROOT, "chipbench", "layers", m["name"] + ".py"))
    assert MINE <= {m["name"] for m in b["per_layer"]}
    assert [w["name"] for w in b["workloads"]][:3] == [
        "sc2-3b.gen-closed", "hpx-stencil.dataflow-coarse",
        "laguna-xs2.mixed-closed"]
    mix = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-48-reason.json")))
    assert (mix["callers"], mix["stagger_steps"], mix["ramp_steps"]) == (
        48, 5, 960)
    assert mix["prompt_tokens"] == {"dist": "log_uniform", "min": 256,
                                    "max": 3072}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384,
                                    "max": 1152}


def test_the_configuration_holds_every_published_width():
    conf = json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/kimi-linear-48b.json")))
    want = {"hidden_size": 2304, "num_hidden_layers": 27,
            "num_attention_heads": 32, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "moe_intermediate_size": 1024,
            "intermediate_size": 9216, "num_experts_per_token": 8,
            "routed_scaling_factor": 2.446, "num_shared_experts": 1,
            "first_k_dense_replace": 1, "rms_norm_eps": 1e-05,
            "q_lora_rank": None, "mla_use_nope": True,
            "tie_word_embeddings": False, "router_experts": 256}
    assert {k: conf[k] for k in want} == want
    lin = conf["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert sorted(lin["kda_layers"] + lin["full_attn_layers"]) == \
        list(range(1, 28))
    assert conf["reduced"] == ["num_experts", "vocab_size"]
    assert (conf["num_experts"], conf["vocab_size"]) == (16, 20480)
    assert conf["source_values"] == {"num_experts": 256,
                                     "vocab_size": 163840}
    assert conf["experts_held"] == [0, 16] and len(conf["assumed"]) >= 8
    assert conf["server"] == {"paged": True, "slots": 48, "smax": 4224}
    assert conf["control_precision"] == ["int8", "state_bf16"]
    assert "16 TPU v5e chips" in conf["deployment"]
    # the catalog's row, number for number, but for the two cuts
    rows = [json.loads(ln) for ln in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    for row in rows:
        if row["name"] == "Kimi-Linear-48B-A3B-Instruct":
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


def test_sound_run_is_correct_and_both_controls_are_not(sound_run):
    from chipbench import control
    ctx, driver, outcome = sound_run
    program = ctx.result(outcome)
    assert program["correct"] is True, program["checks"]
    read = driver.control(ctx, outcome)
    assert set(read["numbers"]) == {"int8", "state_bf16"}
    # together (each number the smaller of the two readings) ...
    line = ctx.result(control.swapped(outcome, read["checks"]))
    assert line["correct"] is False, line["checks"]
    # the state itself is held to float32: a bfloat16 state parts from
    # the reference's where the program's does not
    assert program["checks"]["state_rel_err"]["value"] < 1e-5
    assert read["numbers"]["state_bf16"]["state_rel_err"] > 1e-3
    # ... and each alone
    for name, numbers in read["numbers"].items():
        alone = {n: numbers[n] for n in read["checks"]}
        line = ctx.result(control.swapped(outcome, alone))
        assert line["correct"] is False, (name, line["checks"])
        assert all(read["checks"][n] <= alone[n] for n in alone)


@pytest.mark.parametrize("piece", ["decay", "beta", "conv", "l2norm",
                                   "out_gate", "rope_dims", "bias",
                                   "shared"])
def test_a_reference_with_a_piece_left_out_fails_correct(sound_run, piece):
    """The comparison that decides `correct`, with one piece of the
    reference's mathematics left out: the served tokens then lie far
    below what that reference puts first."""
    from chipbench import control
    from chipbench.drivers import serving as base
    ctx, driver, outcome = sound_run
    params, requests, length, out_max, _ = outcome["control_inputs"]
    gaps = ctx.reference().served_gaps(params, ctx.config, requests,
                                       length, out_max, leave_out=(piece,))
    numbers = base.gap_numbers(gaps)
    broken = {n: numbers[n] for n in ctx.config["correct"]["limits"]
              if n in numbers}
    line = ctx.result(control.swapped(outcome, broken))
    assert line["correct"] is False, line["checks"]
    # (no L2 norm: the state grows without bound and the gaps are NaN)
    assert not line["checks"]["gap_max"]["value"] <= 0.1


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
        set(r["control_numbers"]) == {"int8", "state_bf16"} for r in seeds)


def test_bytes_the_two_mechanisms_need():
    from chipbench import opcount_hybrid as oc
    # 48 live slots x 20 layers x 32 heads x 128 x 128 float32, in and out
    assert oc.kda_state_bytes(48, 20, 32, 128) == 48 * 20 * 2 * 2097152
    assert oc.kda_state_bytes(0, 20, 32, 128) == 0
    # a slot at position 1000 reads 1001 rows of 576 values, once
    assert oc.latent_row_bytes([1000], 7, 512, 64) == 1001 * 7 * 1152
    assert oc.latent_row_bytes([0, 99], 7, 512, 64) == 101 * 7 * 1152
    assert oc.routed_expert_bytes(12.5, 26, 2304, 1024) == int(
        26 * 12.5 * 3 * 2304 * 1024 * 2)


def synthetic():
    """A 100 ms window; two runs of `jit_step` (10..30, 50..70 ms), each
    holding a 4 ms `hpx_kda_step`, a 1 ms `hpx_mla_paged`, a 5 ms
    `hpx_moe_gmm` and a 10 ms fusion; one `jit_chunk` with a custom call
    of its own, which no reader of the step may count."""
    ops, mods = [], []
    for t0 in (10, 50):
        mods.append(["jit_step(123)", t0 * MS, 20 * MS])
        ops.append(["%hpx_kda_step = (f32[4,2,1,128], f32[4,2,128,128]) "
                    "custom-call(f32[4,2,8,128] %x)", t0 * MS, 4 * MS])
        ops.append(["%hpx_mla_paged = bf16[4,4,128] custom-call(s32[4,9] "
                    "%t)", (t0 + 4) * MS, 1 * MS])
        ops.append(["%hpx_moe_gmm = bf16[64,64] custom-call(s32[4] %e)",
                    (t0 + 5) * MS, 5 * MS])
        ops.append(["fusion.3", (t0 + 10) * MS, 10 * MS])
    mods.append(["jit_chunk(9)", 80 * MS, 10 * MS])
    ops.append(["%hpx_moe_gmm = bf16[128,64] custom-call(s32[8] %e)",
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
    assert reader("kda_step_roofline")(
        t, {"traced_state_bytes": 819e6}, Ctx) == pytest.approx(12.5)
    # ... and 2 ms: 50%
    assert reader("mla_attn_roofline")(
        t, {"traced_latent_bytes": 819e6}, Ctx) == pytest.approx(50.0)
    # (4 + 1) ms of the step's 20
    assert reader("mixer_step_share_pct")(t, {}, Ctx) == \
        pytest.approx(25.0)
    assert reader("state_mb_per_slot")(
        None, {"state_mb_per_slot": 43.4176}, Ctx) == 43.4176
    # nothing to read -> nothing, never 0 (a program with no such
    # kernel or counter: the parent commit)
    bare = synthetic()
    bare["devices"][0]["ops"] = [o for o in bare["devices"][0]["ops"]
                                 if "hpx_kda" not in o[0]
                                 and "hpx_mla" not in o[0]]
    for name in sorted(MINE):
        assert reader(name)(bare, {}, Ctx) is None
        assert reader(name)(None, {}, Ctx) is None
    assert reader("kda_step_roofline")(t, {}, Ctx) is None
    assert reader("mla_attn_roofline")(
        t, {"traced_latent_bytes": 1}, type("C", (), {"peaks": None})) \
        is None
