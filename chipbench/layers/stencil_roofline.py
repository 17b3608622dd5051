"""heat_part's share of its roofline (device_trace). Memory-bound:
least time = 8 bytes a cell update (one float32 read, one written;
chipbench/opcount.py) over the table's HBM bandwidth; divided by the
summed device time of EVERY op of the heat_part program in the traced
window, its copies included. Layer: kernels. Moves mcells_s."""

from chipbench import trace_reduce

PROGRAM = r"^jit_heat_part\b"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
