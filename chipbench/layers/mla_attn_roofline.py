"""The absorbed latent decode attention's share of its roofline
(device_trace). Memory-bound: least time = the live latent bytes the
traced decode steps had to read (chipbench/opcount_hybrid.py
`latent_row_bytes`: rows 0..p of kv_lora_rank + qk_rope_head_dim values
in every MLA layer, once: keys and values are the same bytes) over the
table's HBM bandwidth; divided by the summed device time of the Pallas
kernel `hpx_mla_paged` (ops/attention_pallas.py) inside those steps'
programs. Layer: kernels. Moves tpot_p90_ms. Returns nothing where the
program has no such kernel or counter."""

from chipbench import trace_reduce

PROGRAM = r"^jit_step\b"
KERNEL = r"%hpx_mla_paged"


def read(trace, counters, ctx):
    if trace is None or ctx.peaks is None:
        return None
    need = counters.get("traced_latent_bytes")
    spent, n = trace_reduce.op_seconds_in_modules(trace, PROGRAM, KERNEL)
    if not need or not n or spent <= 0:
        return None
    return 100.0 * (need / ctx.peaks["hbm_bytes_per_s"]) / spent
