"""Time a serving step spends inside the program's `serving.dispatch`
spans, one around the call of every named program (step, chunk, probe,
splice, gather, scratch): mean over the `serving.step` spans of the
traced window (program_span, on the profiler's clock). The time the
runtime HOLDS the host in a dispatch call, which `host_self_ms.serve`
counts as the host's own; `python -m chipbench.step_reduce <cell>` splits
it by program. Layer: serving host loop. Moves out_tok_s."""

from chipbench import span_reduce, step_reduce


def read(trace, counters, ctx):
    return step_reduce.host_held_ms(span_reduce.of_run(ctx))
