"""What the host itself costs a serving step: mean over the program's
`serving.step` spans of the traced window of their duration less the
time inside `serving.flush.wait` and `serving.first_token.wait`, the
two blocking reads of the device (program_span, on the profiler's
clock). Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce


def read(trace, counters, ctx):
    spans = span_reduce.of_run(ctx)
    return None if spans is None else span_reduce.host_self_ms(spans)
