"""The compiled call: time inside the program's `hpx.exec.dispatch`
spans, over the nodes of the traced DAGs whose bodies made them
(program_span). At a grain the device paces this is the host's wait on
a full dispatch queue; at a fine grain, jit's call overhead. Layer: HPX
model. Moves mcells_s."""

from chipbench import span_reduce


def read(trace, counters, ctx):
    spans = span_reduce.of_run(ctx)
    us = spans and span_reduce.node_us(spans)
    return us[1] if us else None
