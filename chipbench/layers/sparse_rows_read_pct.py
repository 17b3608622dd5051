"""Rows the decode steps' sparse layers walked over the rows they had
behind them, summed over live slots and the window's steps, from
`ContinuousServer.cache_stats()` `sparse_rows_walked` /
`sparse_rows_live` (program_counter; the program counts from the
positions what its selection reads: 64 blocks of 64 past the dense
length). Layer: cache manager. Moves tpot_p90_ms: what a step reads of
its contexts is what the walk costs. Returns nothing where the program
has no such counter."""


def read(trace, counters, ctx):
    walked, live = (counters.get("sparse_rows_walked"),
                    counters.get("sparse_rows_live"))
    return None if walked is None or not live else 100.0 * walked / live
