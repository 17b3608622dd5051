"""The grouped expert matrix product's share of its roofline
(device_trace). Memory-bound: least time = the bytes of the routed
experts' matrices the traced decode steps had to read
(chipbench/opcount_mixed.py `routed_expert_bytes`: distinct experts hit
a step and sparse layer, from the program's statistics vector, x 3 x
d_model x expert width x itemsize) over the table's HBM bandwidth;
divided by the summed device time of the Pallas kernel `hpx_moe_gmm`
(ops/moe_gmm.py) inside those steps' programs. Layer: kernels. Moves
tpot_p90_ms. Returns nothing where the program has no such kernel or
counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_moe_gmm"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_gmm_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
