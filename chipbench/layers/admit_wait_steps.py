"""The step() calls between a request's slot and its first token's
program, mean over the window's admissions (program_counter:
`ContinuousServer.cache_stats()`["admit_wait_steps"], its difference
over the window, over the difference of ["state_resets"], one an
admission). 0 for a prompt that prefills inline; a prompt over the
chunk width waits for ONE chunk of ONE pending prompt a step. Layer:
serving host loop. Moves out_tok_s. Returns nothing where the program
has no such counter."""


def read(trace, counters, ctx):
    return counters.get("admit_wait_steps")
