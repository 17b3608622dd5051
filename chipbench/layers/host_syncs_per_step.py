"""Blocking device-to-host reads a serving step: the program's `*.wait`
spans (`serving.flush.wait`, one a buffered step at its flush;
`serving.first_token.wait`, one an admission) over its `serving.step`
spans in the traced window (program_span). The last read of a flush
and every first-token read empty the dispatch queue, so the device
idles through the host's next preparation. Layer: serving host loop.
Moves out_tok_s."""

from chipbench import span_reduce


def read(trace, counters, ctx):
    spans = span_reduce.of_run(ctx)
    return None if spans is None else span_reduce.syncs_per_step(spans)
