"""Device time of one execution of a prefill chunk program, mean over
the traced window (device_trace). Layer: server programs. Moves
out_tok_s (`ttft_p90_ms.gen-closed`, which it moves first, is per-layer
too). Found by its XLA module name: the jitted python function is
`chunk` (serving._chunk_prog)."""

from chipbench import trace_reduce

PROGRAM = r"^jit_chunk\b"


def read(trace, counters, ctx):
    if trace is None:
        return None
    return trace_reduce.module_mean_ms(trace, PROGRAM)
