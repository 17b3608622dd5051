"""Assignments to the experts this chip HOLDS over all assignments of
the window's decode steps (program_counter: the statistics vector the
step program returns, `ContinuousServer.moe_stats()` `routed_here` /
`routed`). 100 / n_group = 12.5 where a device-limited router spreads
its tokens evenly over the groups: the seed's hand in the expert work,
read in every run. Layer: router. Moves tpot_p90_ms. Returns nothing
where the program counts no such assignments."""


def read(trace, counters, ctx):
    here, routed = counters.get("moe_routed_here"), counters.get("moe_routed")
    return None if here is None or not routed else 100.0 * here / routed
