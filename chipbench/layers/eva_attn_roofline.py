"""The two-grain walk's share of its roofline (device_trace).
Memory-bound (one query row a (slot, head): 2 FLOP a byte): least time
= the K and V bytes the traced decode steps' walks had to read
(chipbench/opcount_eva.py `walk_bytes`: for every live slot one summary
row for every chunk of every complete window behind its own and its
window's exact rows up to itself, every EVA layer) over the table's HBM
bandwidth; divided by the summed device time of the Pallas kernel that
walks a slot's one run of rows, `hpx_paged_fused` (ops/attention_pallas.py;
ops/eva.py says why one kernel serves both grains), inside those steps'
programs. Layer: kernels. Moves tpot_p90_ms. Returns nothing where the
program has no such kernel or counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_paged_fused"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_eva_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
