"""What the program tells the reduced trace beyond ``tracing.py``'s fields,
and what the metric readers read of it.

``tracing.reduce_xspace`` keys each device op by the ``obs.*`` scope in its
own ``op_name`` metadata and keeps the benchmark's own host spans. Three
things are added here, by :func:`extend`, to a reduced trace taken while the
program's recorder (``repro.obs.trace.RECORDER``) was on and anchored:

* a sixth field on each device op: the scope it *inherits*. Copy insertion
  and layout assignment add copies and relayouts with no metadata at all,
  which no scope in the program can label; such an op inherits the scope of
  the nearest operand producer in the same computation that has one, else
  that of its nearest user (:func:`inherited_scopes`). ``""`` where the op
  has its own scope, or ``op_name`` metadata without a scope, or nothing to
  inherit;
* ``modules``: each chip's per-execution module events (the ``XLA Modules``
  line of the device plane), ``[name, start, end]``: where one step's
  program starts and ends on the device;
* ``program``: the recorder's spans, ``[name, start, end, parent,
  fun_name]``, moved onto the trace's clock by the ``obs.clock`` anchor:
  ``offset = the anchor's start on the trace − its wall time``.

Where a trace lacks them, :func:`scope` reads the own scope alone, and
:func:`program_spans` reads the live program's recorder (on the wall
clock); the program before the recorder existed has none, and the readers
that need it then read nothing.
"""

from __future__ import annotations

import re
import sys
from collections import deque

import tracing

MODULES_LINE = "XLA Modules"
CLOCK_SPAN = "obs.clock"
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPERAND = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")


def _skip_balanced(text: str, i: int) -> int:
    """Index just past the parenthesised group that opens at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _parse(line: str):
    """(name, operand names, op_name) of an HLO instruction line, or None."""
    m = _INSTRUCTION.match(line)
    if not m:
        return None
    i = m.end()
    # the result type: a tuple "(...)" or one token
    i = _skip_balanced(line, i) if line[i:i + 1] == "(" else line.find(" ", i)
    op = _OPCODE.match(line, i) if i >= 0 else None
    if not op:
        return None
    start = op.end() - 1
    operands = _OPERAND.findall(line[start:_skip_balanced(line, start)])
    meta = re.search(r'op_name="([^"]*)"', line)
    return m.group(1), operands, meta.group(1) if meta else None


def inherited_scopes(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> the scope it inherits, for every instruction
    with no ``op_name`` metadata that can inherit one: the scope of the
    nearest producer of its operands, breadth first through unscoped
    producers, within its computation; else that of its nearest user."""
    out = {}
    computation: list[tuple[str, list[str], str | None]] = []

    def close():
        own = {n: _own_scope(meta) for n, _, meta in computation}
        operands = {n: ops for n, ops, _ in computation}
        users: dict[str, list[str]] = {}
        for n, ops, _ in computation:
            for o in ops:
                users.setdefault(o, []).append(n)
        for n, _, meta in computation:
            if meta is not None:
                continue
            for edges in (operands, users):
                found = _nearest(n, edges, own)
                if found:
                    out[n] = found
                    break
        computation.clear()

    for line in hlo_text.splitlines():
        if _COMPUTATION.match(line):
            computation.clear()
        elif line.startswith("}"):
            close()
        else:
            parsed = _parse(line)
            if parsed:
                computation.append(parsed)
    close()
    return out


def _own_scope(op_name: str | None) -> str:
    found = tracing.OBS.search(op_name or "")
    return found.group(0) if found else ""


def _nearest(name: str, edges: dict[str, list[str]], own: dict[str, str]) -> str:
    seen, queue = {name}, deque(edges.get(name, ()))
    while queue:
        n = queue.popleft()
        if n in seen or n not in own:
            continue
        seen.add(n)
        if own[n]:
            return own[n]
        queue.extend(edges.get(n, ()))
    return ""


def module_events(data) -> dict[int, list[list]]:
    """Chip -> its module events ``[name, start, end]`` from a
    ``jax.profiler.ProfileData``."""
    out = {}
    for plane in data.planes:
        m = tracing.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        out[int(m.group(1))] = sorted(
            ([ev.name, ev.start_ns, ev.end_ns] for line in plane.lines if line.name == MODULES_LINE
             for ev in line.events), key=lambda e: e[1])
    return out


def anchor_start(data) -> float | None:
    """Trace start of the last ``obs.clock`` annotation on a host plane."""
    starts = [ev.start_ns for plane in data.planes if plane.name.startswith("/host")
              for line in plane.lines for ev in line.events if ev.name == CLOCK_SPAN]
    return max(starts) if starts else None


def on_trace_clock(spans, anchor_wall_ns: int, anchor_trace_ns: float) -> list[list]:
    """Recorded spans ``(name, start, end, parent, fun_name)`` on the wall
    clock -> the same on the trace's clock."""
    offset = anchor_trace_ns - anchor_wall_ns
    return [[s[0], s[1] + offset, s[2] + offset, s[3], s[4]] for s in spans]


def extend(trace: dict, data, hlo_text: str, spans, anchor_wall_ns: int | None) -> dict:
    """Add the inherited scope, ``modules`` and ``program`` (where the
    profile holds the recorder's anchor) to a reduced trace of ``data``."""
    inherited = inherited_scopes(hlo_text)
    for ops in trace["devices"]:
        for o in ops:
            o.append("" if o[3] else inherited.get(o[0], ""))
    modules = module_events(data)
    trace["modules"] = [modules.get(k, []) for k in sorted(modules)]
    anchor = anchor_start(data)
    if anchor is not None and anchor_wall_ns is not None:
        trace["program"] = on_trace_clock(spans, anchor_wall_ns, anchor)
    return trace


# ---------------------------------------------------------------------------
# what the readers share
# ---------------------------------------------------------------------------


def scope(op) -> str:
    """The op's own scope, else the one it inherits (where the trace says)."""
    return op[3] or (op[5] if len(op) > 5 else "")


def scope_ms(trace: dict, steps: int, prefix: str) -> float | None:
    """Device ms a step (averaged over chips) of the ops whose scope, own or
    inherited, starts with ``prefix``; None where no op has it."""
    if not any(scope(o).startswith(prefix) for ops in trace["devices"] for o in ops):
        return None
    return tracing.op_ns(trace, lambda o: scope(o).startswith(prefix)) / steps / 1e6


def program_spans(trace: dict) -> tuple[list, bool] | None:
    """(the program's recorded spans, whether they are on the trace's clock):
    the trace's ``program`` where it has one, else the live program
    recorder's on the wall clock; None where the program keeps none."""
    if "program" in trace:
        return trace["program"], True
    recorder = getattr(sys.modules.get("repro.obs.trace"), "RECORDER", None)
    if recorder is None:
        return None
    return [list(s) for s in recorder.spans], False


def idle_between_steps_ns(trace: dict) -> float | None:
    """Device idle ns inside the window between one module event and the
    next on the same chip, averaged over the chips; None without module
    events."""
    modules = trace.get("modules")
    if not modules or not any(modules):
        return None
    lo, hi = trace["window"]
    per_chip = []
    for ops, mods in zip(trace["devices"], modules):
        between = tracing.clip([(a[2], b[1]) for a, b in zip(mods, mods[1:]) if b[1] > a[2]], lo, hi)
        busy = tracing.union((o[1], o[2]) for o in ops)
        per_chip.append(tracing.length(tracing.subtract(tracing.union(between), busy)))
    return sum(per_chip) / len(per_chip)
