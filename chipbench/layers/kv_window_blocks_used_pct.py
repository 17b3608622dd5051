"""Blocks in use over blocks in the pool of the WINDOW block group
(the layers that keep only the last `sliding_window` rows), mean over
the window's steps, from ContinuousServer.cache_stats()
`window_in_use` / `window_num_blocks` (program_counter). Layer: cache
manager. Moves out_tok_s."""


def read(trace, counters, ctx):
    v = counters.get("kv_window_blocks_used")
    return None if v is None else 100.0 * v
