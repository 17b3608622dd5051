"""The fused paged decode attention kernel's share of its roofline
(device_trace). Memory-bound: least time = the live K and V bytes the
traced decode steps had to read (chipbench/opcount.py, from the slots'
positions at each traced step) over the table's HBM bandwidth; divided
by the summed device time of the Pallas custom calls inside those
steps' programs. Layer: kernels. Moves tpot_p90_ms."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"custom-call\("


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_kv_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
