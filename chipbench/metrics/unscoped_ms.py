"""Device time per step of the ops that no ``obs.*`` scope covers, neither
their own nor (where the trace carries it) an inherited one: what the
per-scope split leaves unexplained. Averaged over the chips.
Layer: device (v5e)."""

import program_trace
import tracing


def read(trace, cell, steps):
    return tracing.op_ns(trace, lambda o: not program_trace.scope(o)) / steps / 1e6
