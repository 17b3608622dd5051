"""Share of the traced window in which the device is idle and the
INNERMOST program span is `serving.dispatch`: the device waits while
the host is inside a dispatch call, which is launch latency (a full
queue cannot idle the device). A gap is split over the spans it
overlaps (program_span over the device trace; step_reduce.py). Part of
device_idle_pct.serve. Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce, step_reduce


def read(trace, counters, ctx):
    return step_reduce.idle_pct_innermost(trace, span_reduce.of_run(ctx),
                                          step_reduce.DISPATCH)
