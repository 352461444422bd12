"""From a profiler trace to per-layer metrics.

``start``/``stop`` take a JAX profiler trace of the traced window into a
temporary directory and reduce it at once to a small JSON-able record (the
*reduced trace*), then delete the files:

* ``window``: [start, end] ns of the traced window on the host clock: from
  the first ``chipbench.dispatch`` span to the end of the last
  ``chipbench.wait`` span;
* ``devices``: for each chip, its device operations (the innermost ones:
  a ``while`` op's event spans its body's) as ``[name, start, end,
  scope, kernel]``, where ``scope`` is the first ``obs.*`` name in the op's
  ``op_name`` metadata (``""`` where it has none) and ``kernel`` a Pallas
  kernel's label (``""`` otherwise). Trace events name an op by its HLO
  text and carry no metadata, so both come from the compiled step's HLO,
  joined by instruction name (``hlo_metadata``);
* ``host``: the benchmark's own host spans, ``[name, start, end]``.

Every metric reader in ``metrics/`` takes the reduced trace and the cell.
The functions below are what they share: interval unions, time per scope
and idle gaps.
"""

from __future__ import annotations

import glob
import importlib.util
import os
import re
import shutil
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
OBS = re.compile(r"\bobs\.[a-z_]+(?:\.[a-z_]+)?")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
_HLO_NAME = re.compile(r"\s*%?([^\s=]+)")
_LAYOUT = re.compile(r"\{[^{}]*\}")
EXCHANGE_SCOPES = ("obs.bucketize", "obs.compress", "obs.collective", "obs.decode", "obs.apply")


def start() -> str:
    import jax

    path = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(path)
    return path


def stop(path: str, hlo_text: str) -> dict:
    import jax

    jax.profiler.stop_trace()
    try:
        files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {path}")
        return reduce_xspace(files[0], hlo_text)
    finally:
        shutil.rmtree(path, ignore_errors=True)


# ---------------------------------------------------------------------------
# xplane -> reduced trace
# ---------------------------------------------------------------------------


def hlo_metadata(hlo_text: str) -> dict[str, tuple[str, str]]:
    """HLO instruction name -> (op_name metadata, kernel label). A Pallas
    kernel (a ``tpu_custom_call``) is labelled ``<name stem>-><result type>``,
    e.g. ``bucket_decompress_mean->f32[5672,65536]``: the stem is the jitted
    wrapper it was called from, the result type tells apart kernels that
    share one wrapper."""
    out = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\([^)]*\)|\S+)", _LAYOUT.sub("", line))
        if not m:
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        kernel = ""
        if 'custom_call_target="tpu_custom_call"' in line:
            kernel = f"{re.sub(r'[.]\d+$', '', m.group(1))}->{m.group(2)}"
        out[m.group(1)] = (op.group(1) if op else "", kernel)
    return out


def _leaves(ops):
    """Ops that contain no other op: a ``while`` or ``call`` event spans the
    events of the ops it runs, which are on the same line."""
    ops = sorted(ops, key=lambda o: (o[1], -o[2]))
    return [o for o, nxt in zip(ops, ops[1:] + [None]) if nxt is None or nxt[1] >= o[2]]


def reduce_xspace(path: str, hlo_text: str) -> dict:
    from jax.profiler import ProfileData

    meta = hlo_metadata(hlo_text)
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        dm = DEVICE_PLANE.match(plane.name)
        if dm:
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    # an op event is named by its HLO text: "%fusion.12 = f32[...] fusion(...)"
                    name = _HLO_NAME.match(ev.name).group(1)
                    op_name, kernel = meta.get(name, ("", ""))
                    scope = OBS.search(op_name)
                    ops.append([name, ev.start_ns, ev.end_ns, scope.group(0) if scope else "", kernel])
            devices[int(dm.group(1))] = _leaves(ops)
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("chipbench."):
                        host.append([ev.name, ev.start_ns, ev.end_ns])
    host.sort(key=lambda h: h[1])
    dispatch = [h for h in host if h[0] == "chipbench.dispatch"]
    waits = [h for h in host if h[0] == "chipbench.wait"]
    if not dispatch or not waits:
        raise RuntimeError("the trace holds none of the benchmark's host spans")
    window = [dispatch[0][1], waits[-1][2]]
    return {"window": window, "devices": [devices[k] for k in sorted(devices)], "host": host}


# ---------------------------------------------------------------------------
# shared reductions
# ---------------------------------------------------------------------------


def union(intervals) -> list[tuple[int, int]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the union ``a`` that the union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def busy_ns(trace: dict) -> list[int]:
    """Per chip: ns of the window in which some device op ran."""
    lo, hi = trace["window"]
    return [length(clip(union((o[1], o[2]) for o in ops), lo, hi)) for ops in trace["devices"]]


def in_window(trace: dict, ops):
    lo, hi = trace["window"]
    return [o for o in ops if o[2] > lo and o[1] < hi]


def op_ns(trace: dict, keep) -> float:
    """Device ns of the ops ``keep(op)`` selects, summed over each chip's
    window and averaged over the chips."""
    per_chip = [sum(o[2] - o[1] for o in in_window(trace, ops) if keep(o)) for ops in trace["devices"]]
    return sum(per_chip) / len(per_chip)


def is_exchange(op) -> bool:
    return op[3].startswith(EXCHANGE_SCOPES)


def breakdown(trace: dict, top: int = 10) -> dict:
    """Chip 0's longest device ops (by summed seconds per name) and longest
    idle gaps, each gap named by the benchmark host span open at its middle."""
    lo, hi = trace["window"]
    ops = in_window(trace, trace["devices"][0])
    by_name = {}
    for o in ops:
        label = o[4] or o[0]
        by_name[label] = by_name.get(label, 0) + (min(o[2], hi) - max(o[1], lo))
    busy = clip(union((o[1], o[2]) for o in ops), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    named = []
    for s, e in gaps:
        mid = (s + e) // 2
        open_spans = [h[0] for h in trace["host"] if h[1] <= mid < h[2]]
        named.append([open_spans[-1] if open_spans else "none", (e - s) / 1e9])
    named.sort(key=lambda g: -g[1])
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[k, v / 1e9] for k, v in ranked],
        "idle_gaps": named[:top],
    }


# ---------------------------------------------------------------------------
# metric readers
# ---------------------------------------------------------------------------


def load_reader(name: str):
    """The ``read(trace, cell, steps)`` function of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer_metrics(trace: dict, cell, steps: int, bench: dict) -> tuple[dict, dict, dict]:
    """The cell's per-layer metrics (those whose reader finds something to
    read), the breakdown, and the device's busy and window seconds."""
    out = {}
    for entry in bench["per_layer"]:
        if "workloads" in entry and cell.name not in entry["workloads"]:
            continue
        value = load_reader(entry["name"]).read(trace, cell, steps)
        if value is not None:
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    lo, hi = trace["window"]
    busy = busy_ns(trace)
    device = {"busy_s": sum(busy) / len(busy) / 1e9, "window_s": (hi - lo) / 1e9}
    return out, breakdown(trace), device
