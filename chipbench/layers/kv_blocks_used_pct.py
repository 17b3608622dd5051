"""KV blocks in use over blocks in the pool, mean over the window's
steps, from ContinuousServer.cache_stats() (program_counter). Layer:
cache manager. Moves out_tok_s."""


def read(trace, counters, ctx):
    v = counters.get("kv_blocks_used")
    return None if v is None else 100.0 * v
