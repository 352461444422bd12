"""Seconds of set-up spent compiling (or loading from the persistent
cache) outside ``init_train_state``: the program's ``obs.compile`` spans
before the window, less those inside ``obs.setup.init``, which
``setup.init_s`` already holds; so the two add up to part of ``setup_s``.
Where the spans are the live recorder's (no anchor on the trace), every
compile before the readout counts, the window's among them. Nothing to
read where the program records no set-up spans. Layer: set-up."""

import math

import program_trace


def read(trace, cell, steps):
    found = program_trace.program_spans(trace)
    if found is None:
        return None
    spans, on_trace = found
    if not any(s[0] == "obs.setup.init" for s in spans):
        return None
    lo = trace["window"][0] if on_trace else math.inf
    return sum(s[2] - s[1] for s in spans
               if s[0] == "obs.compile" and s[3] != "obs.setup.init" and s[2] <= lo) / 1e9
