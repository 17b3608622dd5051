"""The sparse FFN's share of the decode step (device_trace): device
time of the grouped expert product `hpx_moe_gmm` inside the `jit_step`
programs over the device time of those programs. Only the kernel can be
told from the step's other ops by name; the router, the sort and the
shared expert are XLA fusions like the attention's and are left in the
divisor alone. Layer: server programs. Moves tpot_p90_ms."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_moe_gmm"


def read(trace, counters, ctx):
    if trace is None:
        return None
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    whole = sum(b - a for a, b in trace_reduce.module_runs(trace, PROGRAM))
    if not n or whole <= 0:
        return None
    return 100.0 * spent / (whole / 1e9)
