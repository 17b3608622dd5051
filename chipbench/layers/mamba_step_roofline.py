"""The selective scan's decode step's share of its roofline
(device_trace). Memory-bound (about 7 vector operations and one exp for
8 bytes moved): least time = the state bytes the traced decode steps
had to move (chipbench/opcount_ssm.py `mamba_state_bytes`: every live
slot's float32 state of every Mamba layer, read once and written once)
over the table's HBM bandwidth; divided by the summed device time of
the Pallas kernel `hpx_mamba_step` (ops/mamba.py) inside those steps'
programs. Layer: kernels. Moves tpot_p90_ms. Returns nothing where the
program has no such kernel or counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_mamba_step"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_state_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
