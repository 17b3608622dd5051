"""Named programs a serving step enqueues: the program's
`serving.dispatch` spans over its `serving.step` spans in the traced
window (program_span). Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce, step_reduce


def read(trace, counters, ctx):
    return step_reduce.dispatches_per_step(span_reduce.of_run(ctx))
