"""Share of the traced window in which the device is idle and the
innermost span over the gap's middle is the program's `serving.admit` or
`serving.prefill_tick` or inside one: radix match, block allocation,
the chunk's dispatch, and the blocking read of the first token
(program_span over the device trace; the gaps are
trace_reduce.idle_gaps' own). Part of device_idle_pct.serve. Layer:
serving host loop. Moves out_tok_s."""

from chipbench import span_reduce

INSIDE = ("serving.admit", "serving.prefill_tick")


def read(trace, counters, ctx):
    return span_reduce.idle_pct_inside(trace, span_reduce.of_run(ctx),
                                       INSIDE)
