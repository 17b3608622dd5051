"""Rows the decode steps' two-grain walks attended over the positions
they had behind them, summed over live slots and the window's steps,
from `ContinuousServer.cache_stats()` `eva_rows_attended` /
`eva_tokens_behind` (program_counter; the program counts from the
positions alone: one summary for every chunk of every complete window
behind the query's own, and its window's exact rows). 100 would be
plain attention. Layer: cache manager. Moves tpot_p90_ms: what a step
reads of its contexts is what the walk costs. Returns nothing where the
program has no such counter."""


def read(trace, counters, ctx):
    read_, behind = (counters.get("eva_rows_attended"),
                     counters.get("eva_tokens_behind"))
    return None if read_ is None or not behind else 100.0 * read_ / behind
