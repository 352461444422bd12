"""Model FLOP/s utilisation of the whole step: the FLOPs the step requires
(``counts.train_flops_per_step``, the benchmark's own count) times the
steps per second of the traced window, over the cell's chips times the
chip's bf16 peak (``peaks.json``). Layer: device (v5e)."""

import json

import counts
import tracing


def read(trace, cell, steps):
    kind = trace["device_kind"]
    peaks = json.loads((tracing.HERE / "peaks.json").read_text())
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    lo, hi = trace["window"]
    flops = counts.train_flops_per_step(cell.config, cell.tokens_per_step, cell.seq)
    return 100.0 * flops * steps / ((hi - lo) / 1e9) / (cell.chips * peaks[kind]["bf16_flops"])
