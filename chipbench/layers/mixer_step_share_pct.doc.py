"""The mixer's kernel's share of the decode step (device_trace): device
time of `hpx_paged_fused`, the walk over a slot's summaries and its
window's exact rows, inside the `jit_step` programs over the device
time of those programs. The pooling is NOT inside the step: a window's
summaries are pooled by the program `jit_roll` when the window
completes (its own line of the breakdown). The projections, the
rotation and the row's write stay in the divisor alone: no name tells
them from the step's other ops. Layer: server programs. Moves
tpot_p90_ms."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_paged_fused"


def read(trace, counters, ctx):
    if trace is None:
        return None
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    whole = sum(b - a for a, b in trace_reduce.module_runs(trace, PROGRAM))
    if not n or whole <= 0:
        return None
    return 100.0 * spent / (whole / 1e9)
