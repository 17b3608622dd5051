"""The futures layer alone: time inside the program's
`hpx.dataflow.node` spans that no `hpx.dataflow.body` covers (pack
traversal, shared state, callbacks, scheduling), over the nodes of the
traced DAGs (program_span). Layer: HPX model. Moves mcells_s."""

from chipbench import span_reduce


def read(trace, counters, ctx):
    spans = span_reduce.of_run(ctx)
    us = spans and span_reduce.node_us(spans)
    return us[0] if us else None
