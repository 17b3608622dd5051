"""1 - union of device-op intervals over the traced window
(device_trace), HPX cells. Layer: device. Moves mcells_s."""

from chipbench import trace_reduce


def read(trace, counters, ctx):
    return None if trace is None else trace_reduce.idle_pct(trace)
