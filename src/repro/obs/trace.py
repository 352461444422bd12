"""Trace spans: name the step's phases for XProf/perfetto, record host spans.

Two kinds of region markers, matching the two kinds of time in a step:

* :func:`span` — in-graph. ``jax.named_scope`` attaches the span name to the
  op metadata of everything traced under it, so compiled-HLO ops (and the
  XProf timeline rows XLA derives from them) segment by phase:
  ``obs.backward`` → ``obs.optimizer`` → ``obs.bucketize`` →
  ``obs.compress`` → ``obs.collective.<backend>`` → ``obs.decode`` →
  ``obs.apply``. Metadata only — applied unconditionally because it cannot
  change numerics (the bitwise tests run with it on).
* :func:`host_span` / :class:`WallTimers` — host-side. Wraps non-jit regions
  (set-up, dispatch, blocking on results, checkpoint writes) in
  ``jax.profiler.TraceAnnotation`` so they land on the profiler timeline too,
  and accumulates wall seconds for the JSONL run records.

:data:`RECORDER` keeps host spans and counters in memory on the wall clock
(``time.time_ns()``, the clock ``jax.monitoring`` stamps compile events
with); :meth:`Recorder.anchor` ties that clock to a running profiler's.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import NamedTuple

import jax
import jax.monitoring
import jax.profiler

#: canonical span names, in step order — tests and the README table key on
#: these exact strings appearing in compiled HLO ``op_name`` metadata
SPAN_BACKWARD = "obs.backward"
SPAN_OPTIMIZER = "obs.optimizer"  # the local optimizer (and, dense, the apply)
SPAN_BUCKETIZE = "obs.bucketize"
SPAN_COMPRESS = "obs.compress"
SPAN_COLLECTIVE = "obs.collective"  # suffixed ".<backend>" per transport
SPAN_DECODE = "obs.decode"
SPAN_APPLY = "obs.apply"

SPAN_NAMES = (
    SPAN_BACKWARD,
    SPAN_OPTIMIZER,
    SPAN_BUCKETIZE,
    SPAN_COMPRESS,
    SPAN_COLLECTIVE,
    SPAN_DECODE,
    SPAN_APPLY,
)

#: host spans of set-up (:func:`repro.train.loop.prepare_training`)
SPAN_SETUP_INIT = "obs.setup.init"  # init_train_state, until the state is ready
SPAN_SETUP_BUILD = "obs.setup.build"  # make_train_step
SPAN_SETUP_PLACE = "obs.setup.place"  # device_put of the state
#: one span per backend compile (or persistent-cache load), from jax.monitoring
SPAN_COMPILE = "obs.compile"
#: the annotation that ties the recorder's clock to a profiler trace
SPAN_CLOCK = "obs.clock"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: jax.monitoring event -> recorder counter
COUNTED_EVENTS = {
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
COUNTERS = ("compiles", "cache_hits", "cache_misses")
#: counts of the EF step as built, per dtype group (``<name>.<dtype>``), set
#: by ``train.steps``: bucket rows moved straight from and to one leaf, and
#: boundary rows assembled from pieces (``comm.bucketize.BucketGroup``)
ROWS_ONE_LEAF = "bucketize.rows_one_leaf"
ROWS_SHARED = "bucketize.rows_shared"


class Span(NamedTuple):
    """A recorded host span on the wall clock: ``parent`` is the recorded
    span open around it (``""`` at top level); ``fun_name`` is the compiled
    function's name on an ``obs.compile`` span."""

    name: str
    start_ns: int
    end_ns: int
    parent: str
    fun_name: str = ""


class Recorder:
    """Host spans and counters kept in memory, stamped with ``time.time_ns()``.

    Off by default. While on (:meth:`start`), every :func:`host_span` also
    appends a :class:`Span`; while off, a ``host_span`` costs what a bare
    ``TraceAnnotation`` costs. Once listening (:meth:`listen`, which
    ``start`` and set-up call), each backend compile becomes an
    ``obs.compile`` span and compiles and persistent-cache hits and misses
    are counted, on or off: they come a bounded number of times a process
    and not at all in a steady window. So do set-up's spans
    (``host_span(..., keep=True)``).
    """

    def __init__(self):
        self.on = False
        self.spans: list[Span] = []
        self.counters = dict.fromkeys(COUNTERS, 0)
        self.anchor_ns: int | None = None
        self._open: list[str] = []
        self._lock = threading.Lock()
        self._listening = False

    def listen(self) -> None:
        """Register the compile and cache listeners (once)."""
        with self._lock:
            if self._listening:
                return
            self._listening = True
        jax.monitoring.register_event_time_span_listener(self._on_time_span)
        jax.monitoring.register_event_listener(self._on_event)

    def close(self) -> None:
        """Unregister the listeners (a recorder that is thrown away)."""
        with self._lock:
            if not self._listening:
                return
            self._listening = False
        jax.monitoring.unregister_event_time_span_listener(self._on_time_span)
        jax.monitoring.unregister_event_listener(self._on_event)

    def start(self) -> None:
        self.listen()
        self.on = True

    def stop(self) -> None:
        self.on = False

    def anchor(self) -> int:
        """Put an ``obs.clock`` annotation on the running profiler's timeline
        and return the wall time (ns) at which it opened. A reduction maps
        a recorded span onto the trace by ``offset = the annotation's start
        on the trace − anchor_ns``."""
        with jax.profiler.TraceAnnotation(SPAN_CLOCK):
            self.anchor_ns = time.time_ns()
        return self.anchor_ns

    def set_count(self, name: str, value: int) -> None:
        """Set counter ``name``: a count of a program as built, not of
        events, so building it again does not add to it."""
        with self._lock:
            self.counters[name] = value

    def counts(self) -> dict[str, int]:
        with self._lock:
            return dict(self.counters)

    def drain(self) -> list[Span]:
        """Return the recorded spans and forget them (counters keep counting)."""
        with self._lock:
            out, self.spans = self.spans, []
        return out

    def _parent(self) -> str:
        return self._open[-1] if self._open else ""

    @contextmanager
    def _recording(self, name: str):
        """Record the region as a :class:`Span` under the one open around it."""
        parent = self._parent()
        self._open.append(name)
        start = time.time_ns()
        try:
            yield
        finally:
            end = time.time_ns()
            self._open.pop()
            with self._lock:
                self.spans.append(Span(name, start, end, parent))

    def _on_time_span(self, event: str, start: float, end: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        span = Span(SPAN_COMPILE, int(start * 1e9), int(end * 1e9), self._parent(),
                    str(kwargs.get("fun_name", "")))
        with self._lock:
            self.counters["compiles"] += 1
            self.spans.append(span)

    def _on_event(self, event: str, **kwargs) -> None:
        name = COUNTED_EVENTS.get(event)
        if name is not None:
            with self._lock:
                self.counters[name] += 1


#: the process's recorder, which :func:`host_span` appends to
RECORDER = Recorder()


def span(name: str):
    """In-graph span: a ``jax.named_scope`` carrying an ``obs.`` name.

    ``name`` may be a bare phase (``"compress"``) or already qualified
    (``"collective.ring"``); either way the scope is ``obs.``-prefixed so
    profiler rows from this subsystem sort together.
    """
    if not name.startswith("obs."):
        name = f"obs.{name}"
    return jax.named_scope(name)


@contextmanager
def host_span(name: str, *, keep: bool = False):
    """Host-side region on the profiler timeline (non-jit work), recorded in
    :data:`RECORDER` while it is on, or always with ``keep`` (set-up)."""
    if not name.startswith("obs."):
        name = f"obs.{name}"
    if not (RECORDER.on or keep):
        with jax.profiler.TraceAnnotation(name):
            yield
        return
    with RECORDER._recording(name), jax.profiler.TraceAnnotation(name):
        yield


def step_span(step: int):
    """Whole-step marker; XProf's step-time view groups by these."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


class WallTimers:
    """Named wall-clock accumulators for the host side of a step.

    ``with timers.region("step"): ...`` both annotates the profiler timeline
    and adds the elapsed seconds to ``timers.seconds["step"]``; ``drain()``
    returns and resets the totals, which is what the train loop folds into
    each JSONL record as ``wall_<name>_s``.
    """

    def __init__(self):
        self.seconds: dict[str, float] = {}

    @contextmanager
    def region(self, name: str):
        t0 = time.perf_counter()
        with host_span(name):
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) + (time.perf_counter() - t0)

    def drain(self) -> dict[str, float]:
        out, self.seconds = self.seconds, {}
        return out
