"""The reduction from a trace to numbers, on a trace whose numbers are
known by construction and on a small one recorded on the chip."""

import json
import os

import pytest

from chipbench import trace_reduce as tr
from chipbench.tests import helpers as h

MS = 1e6


def synthetic():
    """A 100 ms window; two runs of `jit_step` (10..30, 50..70 ms), each
    holding a 5 ms `custom-call` and a 15 ms fusion; one `jit_chunk`
    (80..90). Busy 50 ms -> idle 50%."""
    ops, mods = [], []
    for t0 in (10, 50):
        mods.append(["jit_step(123)", t0 * MS, 20 * MS])
        ops.append(["%step.7 = bf16[32,2,16,128] custom-call(s32[32,128] %c)",
                    t0 * MS, 5 * MS])
        ops.append(["fusion.3", (t0 + 5) * MS, 15 * MS])
    mods.append(["jit_chunk(9)", 80 * MS, 10 * MS])
    ops.append(["%chunk.1 = bf16[1,128] custom-call(bf16[1,128] %q)",
                80 * MS, 10 * MS])
    host = [["bench.trace_window", 0.0, 100 * MS],
            ["bench.step", 0.0, 45 * MS], ["bench.submit", 30 * MS, 20 * MS],
            ["bench.step", 70 * MS, 30 * MS]]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": mods}], "host": host}


def test_known_idle_share_and_times():
    t = synthetic()
    busy_s, window_s = tr.busy(t)
    assert busy_s == pytest.approx(0.050) and window_s == pytest.approx(0.1)
    assert tr.idle_pct(t) == pytest.approx(50.0)
    assert tr.module_mean_ms(t, r"^jit_step\b") == pytest.approx(20.0)
    assert tr.module_mean_ms(t, r"^jit_chunk\b") == pytest.approx(10.0)
    assert tr.module_mean_ms(t, r"^jit_nothing\b") is None
    sec, n = tr.op_seconds_in_modules(t, r"^jit_step\b", r"custom-call\(")
    assert (sec, n) == (pytest.approx(0.010), 2)
    sec, n = tr.op_seconds_in_modules(t, r"^jit_step\b")
    assert (sec, n) == (pytest.approx(0.040), 4)


def test_known_roofline_share():
    from chipbench import harness

    class Ctx:
        peaks = {"hbm_bytes_per_s": 819e9}
    reader = harness.load_by_path("chipbench/layers/paged_attn_roofline.py")
    # 819e6 bytes need 1 ms at the peak; the kernels took 10 ms: 10%
    got = reader.read(synthetic(), {"traced_kv_bytes": 819e6}, Ctx)
    assert got == pytest.approx(10.0)
    # nothing to read -> nothing, never 0
    assert reader.read(synthetic(), {}, Ctx) is None
    assert reader.read(None, {"traced_kv_bytes": 1}, Ctx) is None


def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = dict(tr.idle_gaps(synthetic()))
    # 0..10 bench.step; 30..50 bench.submit (inside no step);
    # 70..80 and 90..100 the second bench.step
    assert gaps["bench.submit"] == pytest.approx(0.020)
    assert gaps["bench.step"] == pytest.approx(0.030)
    top = tr.top_device_ops(synthetic())
    assert top[0][0] == "fusion.3" and top[0][1] == pytest.approx(0.030)
    assert len(tr.breakdown(synthetic())["device_ops"]) <= 10
    # the same op of many unrolled layers adds up under one name
    assert tr.op_kind("%step.34 = bf16[32,2] custom-call(s32[32] %copy-done.2)") \
        == tr.op_kind("%step.59 = bf16[32,2] custom-call(s32[32] %copy-done.7)")


def test_nested_ops_count_once():
    t = synthetic()
    t["devices"][0]["ops"].append(["while.1", 10 * MS, 20 * MS])
    sec, _ = tr.op_seconds_in_modules(t, r"^jit_step\b")
    assert sec == pytest.approx(0.040)
    assert tr.busy(t)[0] == pytest.approx(0.050)


RECORDED = os.path.join(h.HERE, "recorded_trace_hpx.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    t, want = rec["trace"], rec["expected"]
    assert tr.idle_pct(t) == pytest.approx(want["idle_pct"], rel=1e-9)
    sec, n = tr.op_seconds_in_modules(t, r"^jit_heat_part\b")
    assert n == want["heat_part_ops"]
    assert sec == pytest.approx(want["heat_part_seconds"], rel=1e-9)
    assert tr.module_mean_ms(t, r"^jit_heat_part\b") == pytest.approx(
        want["heat_part_mean_ms"], rel=1e-9)
