"""The sparse walk's share of its roofline (device_trace). Memory-bound:
least time = the K and V bytes of the rows the SELECTION names
(chipbench/opcount_sparse.py `selected_row_bytes`, from the traced
decode steps' positions alone: the rows <= p of 64 blocks of 64, the
last one partial, past `dense_len`; p + 1 rows below it; a sparse layer
and kv head) over the table's HBM bandwidth; divided by the summed
device time of the Pallas kernel `hpx_paged_sparse`
(ops/sparse_attention.py) inside those steps' programs. The index read
and the selection are not in it: they are XLA fusions no name tells
from the step's other ops. Layer: kernels. Moves tpot_p90_ms. Returns
nothing where the program has no such kernel or counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_paged_sparse"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_sparse_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
