"""`correct` has been shown to fail: with the timed path broken
underneath, and for the control (the reference in the nearest precision
below the configuration's, in the program's place). Both at sizes a
test run can hold; the readings at the cells' own sizes are in PERF.md.
"""

import json

import pytest

from chipbench.tests import helpers as h

SERVE, HPX = "sc2-3b.gen-closed", "hpx-stencil.dataflow-coarse"


def _program_and_control(workload):
    """One run's line, and the line of the same outcome with the
    control's numbers in the program's place: both by the harness's
    own comparison, `Context.result`."""
    from chipbench import control
    ctx = h.in_process_ctx(workload, h.REHEARSE[workload])
    driver = ctx.driver()
    outcome = driver.run(ctx)
    read = driver.control(ctx, outcome)
    return ctx.result(outcome), ctx.result(
        control.swapped(outcome, read["checks"])), read


def test_hpx_sound_run_is_correct_and_the_control_is_not():
    program, control, read = _program_and_control(HPX)
    assert program["correct"] is True
    assert control["correct"] is False
    failed = [n for n, c in control["checks"].items()
              if c["value"] > c["limit"]]
    assert failed == ["field_err_max"] == list(read["checks"])


def test_hpx_node_that_returns_its_state_unchanged(monkeypatch):
    from hpx_tpu.models import stencil1d
    monkeypatch.setattr(stencil1d, "heat_part",
                        lambda left, middle, right, coef: middle + 0.0)
    ctx = h.in_process_ctx(HPX, h.REHEARSE[HPX])
    line = ctx.result(ctx.driver().run(ctx))
    assert line["correct"] is False
    assert line["checks"]["field_err_max"]["value"] > 0.1


def test_hpx_halo_left_out(monkeypatch):
    """The exchange between partitions dropped: each node sees its own
    edge in place of its neighbour's."""
    from hpx_tpu.models import stencil1d
    real = stencil1d.heat_part
    monkeypatch.setattr(
        stencil1d, "heat_part",
        lambda left, middle, right, coef: real(middle[:1], middle,
                                               middle[-1:], coef))
    ctx = h.in_process_ctx(HPX, h.REHEARSE[HPX])
    line = ctx.result(ctx.driver().run(ctx))
    assert line["correct"] is False


def _fresh_programs():
    from hpx_tpu.models import transformer
    transformer._PROGRAMS.clear()


def test_serving_sound_run_is_correct():
    _fresh_programs()
    ctx = h.in_process_ctx(SERVE, h.REHEARSE[SERVE])
    line = ctx.result(ctx.driver().run(ctx))
    assert line["correct"] is True, line["checks"]


def test_serving_token_altered_where_it_is_produced(monkeypatch):
    """The decode step's pick shifted by one token id: every decoded
    token is wrong, the first token of each request (the probe's) is
    not."""
    from hpx_tpu.models import serving
    real = serving._pick_row
    vocab = json.load(open(h.REHEARSE[SERVE]))["config"]["vocab_size"]
    monkeypatch.setattr(serving, "_pick_row",
                        lambda *a, **k: (real(*a, **k) + 1) % vocab)
    _fresh_programs()
    try:
        ctx = h.in_process_ctx(SERVE, h.REHEARSE[SERVE])
        line = ctx.result(ctx.driver().run(ctx))
    finally:
        _fresh_programs()
    assert line["correct"] is False
    for name in ("gap_max", "parted_gap_sq_mean"):
        assert line["checks"][name]["value"] > line["checks"][name]["limit"]


def test_serving_window_that_compiles_is_not_correct(monkeypatch):
    ctx = h.in_process_ctx(SERVE, h.REHEARSE[SERVE])
    outcome = ctx.driver().run(ctx)
    outcome["checks"] = [(n, 1 if n == "window_compiles" else v, lim)
                         for n, v, lim in outcome["checks"]]
    assert ctx.result(outcome)["correct"] is False


def test_serving_control_int8_is_not_correct():
    """The reference itself as a bfloat16 model served in int8 (W8A8,
    int8 K and V), put in the program's place at a size a test can
    hold: `correct` comes out false through `Context.result`, by the
    mean squared gap of the tokens that part from the reference's best. (At the cell's own size: PERF.md section 2.)"""
    _fresh_programs()
    program, control, read = _program_and_control(SERVE)
    assert program["correct"] is True, program["checks"]
    assert control["correct"] is False, control["checks"]
    assert control["checks"]["parted_gap_sq_mean"]["value"] > \
        control["checks"]["parted_gap_sq_mean"]["limit"]
    assert set(read["checks"]) == set(read["numbers"]) & set(
        control["checks"])


@pytest.mark.parametrize("workload", [HPX, SERVE])
def test_control_command_exits_0_only_if_the_control_fails(workload, capsys,
                                                           monkeypatch):
    from chipbench import control
    _fresh_programs()
    argv = ["--workload", workload, "--seeds", "5,2147483659",
            "--seconds", "0.5", "--rehearse", h.REHEARSE[workload]]
    assert control.main(argv) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]
    assert rows[-1]["every_program_correct_and_every_control_not"] is True
    # a control that the comparison lets through: exit code 1
    monkeypatch.setattr(control, "swapped", lambda outcome, checks: outcome)
    _fresh_programs()
    assert control.main(argv) == 1
