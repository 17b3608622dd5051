"""What the host itself does in a serving step: mean over the
`serving.step` spans of the traced window of their duration less the
`*.wait` spans (blocking reads) and less the `serving.dispatch` spans
(held by the runtime) inside them: Python and eager, unnamed device ops
(program_span). `host_work_ms.serve` + `host_held_ms.serve` =
`host_self_ms.serve`. Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce, step_reduce


def read(trace, counters, ctx):
    return step_reduce.host_work_ms(span_reduce.of_run(ctx))
