"""Share of the traced window in which the device is idle and the
innermost span over the gap's middle is the program's `serving.flush` or
inside it: token replay, the checkpoint sweep, knob reload, and the
blocking read itself (program_span over the device trace; the gaps are
trace_reduce.idle_gaps' own). Part of device_idle_pct.serve. Layer:
serving host loop. Moves out_tok_s."""

from chipbench import span_reduce

INSIDE = ("serving.flush",)


def read(trace, counters, ctx):
    return span_reduce.idle_pct_inside(trace, span_reduce.of_run(ctx),
                                       INSIDE)
