"""Prompt tokens served from the prefix tree over prompt tokens
admitted in the window (program_counter: `ContinuousServer.
cache_stats()` `prefill_tokens_saved` / saved + computed, the window's
difference). Layer: cache manager. Moves out_tok_s: a matched row is a
row no chunk recomputes. Returns nothing where the driver counted no
admission."""


def read(trace, counters, ctx):
    hit = counters.get("prompt_tokens_matched")
    admitted = counters.get("prompt_tokens_admitted")
    return None if hit is None or not admitted else 100.0 * hit / admitted
