"""Programs the device runs a serving step: executions of any XLA
module in the traced window (device_trace) over the program's
`serving.step` spans there. Less `dispatches_per_step` it is the eager,
unnamed programs a step (`jnp.asarray`, `.at[].set`, an argmax), each a
dispatch of its own. Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce, step_reduce


def read(trace, counters, ctx):
    return step_reduce.dev_programs_per_step(trace, span_reduce.of_run(ctx))
