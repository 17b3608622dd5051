"""The fused paged decode attention kernels' share of their roofline in
a model with full and window layers (device_trace). Memory-bound: least
time = the live K and V bytes the traced decode steps had to read
(chipbench/opcount_mixed.py: rows 0..p in a full layer, the last
min(p + 1, window) in a window layer) over the table's HBM bandwidth;
divided by the summed device time of the kernels `hpx_paged_fused` (full
layers) and `hpx_paged_fused_win` (window layers) inside those steps'
programs, found by name, so that no other custom call lands in the
divisor. Layer: kernels. Moves tpot_p90_ms."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_paged_fused"        # and hpx_paged_fused_win


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_kv_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
