"""Bytes of per-slot recurrent state (every KDA layer's float32 state
and conv tail) a slot, in MB, from `ContinuousServer.cache_stats()`
["state_bytes"] over the slots (program_counter). Layer: cache manager.
Moves out_tok_s: what a slot costs beside its latent rows sets how many
slots fit, and a decode step moves all of it whatever the length.
Returns nothing where the program keeps no such state."""


def read(trace, counters, ctx):
    return counters.get("state_mb_per_slot")
