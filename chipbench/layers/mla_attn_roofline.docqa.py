"""The absorbed latent decode attention's share of its roofline under
MANY query heads (device_trace), where the kernel sits on the ridge
and bytes alone would flatter it. Least time = the larger of the live
latent bytes the traced decode steps had to read over the table's HBM
bandwidth and the operations they had to spend over its bfloat16 peak
(chipbench/opcount_latent.py: what the algorithm needs, no pad
columns, no dead rows); divided by the summed device time of the
Pallas kernel `hpx_mla_paged` (ops/attention_pallas.py) inside those
steps' programs. Never clamped: a reading over 100 is a wrong count.
Layer: kernels. Moves tpot_p90_ms. Returns nothing where the program
has no such kernel or counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_mla_paged"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    nbytes = counters.get("traced_latent_bytes")
    flops = counters.get("traced_latent_flops")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not nbytes or not flops or not n or spent <= 0:
        return None
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / spent
