"""Device time per step of the model: every device op outside the EF
exchange's ``obs.*`` scopes (``tracing.EXCHANGE_SCOPES``): forward,
backward, loss and the local optimizer. In a ``dense`` cell it is the whole
step. Averaged over the chips. Layer: model step."""

import tracing


def read(trace, cell, steps):
    return tracing.op_ns(trace, lambda o: not tracing.is_exchange(o)) / steps / 1e6
