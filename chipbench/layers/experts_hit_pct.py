"""Distinct experts hit by a decode step over the experts the model
has, mean over the sparse layers and the window's steps, from the
statistics vector the step program returns
(`ContinuousServer.moe_stats()`; program_counter). Layer: router. Moves
tpot_p90_ms: the experts a step hits are the expert weights it reads."""


def read(trace, counters, ctx):
    hit, n = counters.get("experts_hit"), counters.get("n_experts")
    return None if hit is None or not n else 100.0 * hit / n
