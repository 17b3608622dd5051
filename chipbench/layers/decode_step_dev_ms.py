"""Device time of one execution of the paged decode-step program, mean
over the traced window (device_trace). Layer: server programs. Moves
tpot_p90_ms. The program is found by its XLA module name: the jitted
python function is `step` (serving._paged_step_prog)."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"


def read(trace, counters, ctx):
    if trace is None:
        return None
    return trace_reduce.module_mean_ms(trace, PROGRAM)
