"""Device time per step of the EF exchange: ops whose ``op_name`` lies under
``obs.bucketize``, ``obs.compress``, ``obs.collective.*``, ``obs.decode`` or
``obs.apply``. Nothing to read (no such op) in a cell without the exchange.
Layer: EF exchange."""

import tracing


def read(trace, cell, steps):
    if not any(tracing.is_exchange(o) for ops in trace["devices"] for o in ops):
        return None
    return tracing.op_ns(trace, tracing.is_exchange) / steps / 1e6
