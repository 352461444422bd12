"""Device time per step of the EF exchange's ``obs.compress`` phase: ops
under that scope, own or inherited, averaged over the chips. Nothing to
read in a cell without the exchange. Layer: EF exchange."""

import program_trace


def read(trace, cell, steps):
    return program_trace.scope_ms(trace, steps, "obs.compress")
