"""The mixers' kernels' share of the decode step (device_trace): device
time of `hpx_mamba_step` and `hpx_paged_fused` inside the `jit_step`
programs over the device time of those programs. Only the kernels can
be told from the step's other ops by name: the convolution, the
projections, norms, softplus and gates stay in the divisor alone.
Layer: server programs. Moves tpot_p90_ms."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNELS = (r"%hpx_mamba_step", r"%hpx_paged_fused")


def read(trace, counters, ctx):
    if trace is None:
        return None
    found = [trace_reduce.op_seconds_in_modules(trace, PROGRAM, k)
             for k in KERNELS]
    whole = sum(b - a for a, b in trace_reduce.module_runs(trace, PROGRAM))
    if not any(n for _, n in found) or whole <= 0:
        return None
    return 100.0 * sum(s for s, _ in found) / (whole / 1e9)
