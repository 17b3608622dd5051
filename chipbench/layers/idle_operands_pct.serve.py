"""Share of the traced window in which the device is idle and the
INNERMOST program span is `serving.decode.operands`: block bookkeeping,
the device tables and the host-to-device arrays of a decode step, up to
its dispatch. A gap is split over the spans it overlaps (program_span
over the device trace; step_reduce.py). Part of device_idle_pct.serve.
Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce, step_reduce


def read(trace, counters, ctx):
    return step_reduce.idle_pct_innermost(trace, span_reduce.of_run(ctx),
                                          step_reduce.OPERANDS)
