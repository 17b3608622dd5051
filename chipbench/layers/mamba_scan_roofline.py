"""The selective scan's prefill kernel's share of its MEMORY roofline
(device_trace): least time = the bytes the traced chunks' scans had to
move (chipbench/opcount_ssm.py `mamba_scan_bytes`: u, dt, B, C and y of
the chunks' real rows, a chunk's state once in and once out) over the
table's HBM bandwidth; divided by the summed device time of the Pallas
kernel `hpx_mamba_scan` (ops/mamba.py) inside the `jit_chunk`
programs. It reads LOW by construction: the recurrence is serial in
time and bound by the vector unit (about 17 vector operations a row
and 128 channels), for which chipbench/peaks.json has no row; never
clamped. Layer: kernels. Moves out_tok_s. Returns nothing where the
program has no such kernel or counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_chunk\b"
KERNEL = r"%hpx_mamba_scan"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_scan_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
