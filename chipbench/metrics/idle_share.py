"""Device idle share: the part of the traced window in which no device op
ran, averaged over the cell's chips. Layer: device (v5e)."""

import tracing


def read(trace, cell, steps):
    lo, hi = trace["window"]
    busy = tracing.busy_ns(trace)
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
