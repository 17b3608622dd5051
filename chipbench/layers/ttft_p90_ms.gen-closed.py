"""submit() to first token on the harness's clock, 90th percentile
over every request whose first token arrived inside the window
(host_clock). Layer: serving host loop. Moves out_tok_s.

A per-layer metric by the issue's fallback, not an end-to-end one: one
host stall of a third of a second moves this tail by 5% and such
stalls come in about one run of six, so no bound held in both pairs of
proof sets (PERF.md section 2)."""


def read(trace, counters, ctx):
    return counters.get("ttft_p90_ms")
