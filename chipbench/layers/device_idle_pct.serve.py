"""1 - union of device-op intervals over the traced window
(device_trace), serving cells. Layer: device. Moves out_tok_s."""

from chipbench import trace_reduce


def read(trace, counters, ctx):
    return None if trace is None else trace_reduce.idle_pct(trace)
