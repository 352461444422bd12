"""HBM roofline share of the EF bucket kernels (``kernels/ef_sign.py``):
the least time their bytes take at the chip's HBM bandwidth, summed over
their calls, over their summed device time. Bytes come from the bucket
stack's shapes (``counts``). The kernels are found by the label the trace
reduction gives a Pallas call, ``<jitted wrapper>-><result type>``:

* ``ef_sign_bucket_step->(f32[nb,1], f32[nb,1])``: per-bucket L1/L2 of g + e;
* ``ef_sign_bucket_step->(u32[nb,bs/32], f32[nb,bs])``: sign, pack, residual;
* ``bucket_decompress_mean->f32[nb,bs]``: the mean of the W payloads.

Layer: kernels."""

import json
import re

import counts
import tracing

KERNELS = [
    (re.compile(r"^ef_sign_bucket_step->\(f32\[\d+,1\], f32\[\d+,1\]\)$"),
     lambda nb, bs, w: counts.stats_bytes(nb, bs)),
    (re.compile(r"^ef_sign_bucket_step->\(u32\[\d+,\d+\], f32\[\d+,\d+\]\)$"),
     lambda nb, bs, w: counts.compress_bytes(nb, bs)),
    (re.compile(r"^bucket_decompress_mean->f32\[\d+,\d+\]$"),
     lambda nb, bs, w: counts.decompress_mean_bytes(nb, bs, w)),
]


def read(trace, cell, steps):
    peaks = json.loads((tracing.HERE / "peaks.json").read_text())
    bw = peaks[trace["device_kind"]]["hbm_bytes_per_s"]
    bs = cell.traffic["bucket_size"]
    nb = counts.n_buckets(cell.config, bs)
    least = spent = 0.0
    for ops in trace["devices"]:
        for o in tracing.in_window(trace, ops):
            for pattern, nbytes in KERNELS:
                if pattern.match(o[4]):
                    least += nbytes(nb, bs, cell.workers) / bw
                    spent += (o[2] - o[1]) / 1e9
    return 100.0 * least / spent if spent else None
