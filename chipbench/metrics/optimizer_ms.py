"""Device time per step of the local optimizer: ops under ``obs.optimizer``
(the momentum update; in a ``dense`` cell also the apply), own or inherited
scope, averaged over the chips. Layer: model step."""

import program_trace


def read(trace, cell, steps):
    return program_trace.scope_ms(trace, steps, "obs.optimizer")
