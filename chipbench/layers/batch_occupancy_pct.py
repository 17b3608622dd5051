"""Live slots over slots, mean over the step() calls of the window.
Source: the harness's counter (program_counter). Layer: serving host
loop. Moves out_tok_s."""


def read(trace, counters, ctx):
    v = counters.get("batch_occupancy")
    return None if v is None else 100.0 * v
