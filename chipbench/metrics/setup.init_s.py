"""Seconds of set-up in the program's ``init_train_state``: its
``obs.setup.init`` span (``train/loop.py`` ``prepare_training``), which
closes once the state is ready on the device. Nothing to read where the
program records no such span. Layer: set-up."""

import program_trace


def read(trace, cell, steps):
    found = program_trace.program_spans(trace)
    if found is None:
        return None
    spans = [s for s in found[0] if s[0] == "obs.setup.init"]
    return sum(s[2] - s[1] for s in spans) / 1e9 if spans else None
