"""Flight-recorder tracing with Chrome/Perfetto export (DESIGN.md §13).

A single process-wide :class:`Tracer` records three Chrome-trace event
kinds into a bounded ring buffer:

* ``span(name, **args)`` — a nestable context manager emitting one
  complete ("ph": "X") event on exit, covering the region's wall time.
  Nesting is implicit: Perfetto reconstructs the stack from ts/dur
  containment per thread, so spans survive exceptions — ``__exit__``
  always runs and stamps the error type into ``args``.
* ``instant(name, **args)`` — a point event ("ph": "i"), used for
  degradation-rung transitions and deadline trips.
* ``counter(name, **values)`` — a counter track ("ph": "C"), used for
  span-less overload accounting (shed/queued requests).

The disabled fast path is a single attribute check returning a shared
no-op span object — no allocation, no clock read — so production code
can leave instrumentation inline; its cost is measured on the chip, by
the benchmark's untraced runs against its traced ones (PERF.md §6).  The
ring buffer (default 64k events) makes the tracer a flight recorder:
always safe to leave on, oldest events are dropped and counted in
:attr:`Tracer.dropped`.

Timestamps are microseconds on ``time.monotonic`` relative to tracer
creation, which is exactly what the Chrome trace-event format expects.

**Stage spans.** ``stage(name, counter)`` times one step inside a served
launch (``engine.prepare`` / ``engine.wait`` / ``engine.fetch`` /
``engine.finish``) and has three sinks:

* always: its ``time.perf_counter`` seconds are added to the named
  process counter (``prepare_s``, ...), as :func:`add` adds byte counts
  (``h2d_bytes``, ``d2h_bytes``) and ``schedule_stagings``; the façade
  folds them into the tenant's ``AccessStats`` (:func:`drain_counters`),
  so they are operator metrics that ``SpatialIndex.metrics()`` exports;
* while the tracer is enabled: an "X" event in the ring buffer, as
  ``span()`` records;
* while a JAX profiler session collects: a ``jax.profiler.TraceAnnotation``
  of the same name, so the stage sits on the profiler's host line, on
  the device trace's clock, beside the device ops it waits for.

Only stages reach the profiler.  A trace reduction names an idle device
gap by the host annotation that overlaps it most; a layer span
(``serve.launch``, ``index.*``, ``backend.*``) encloses every stage of
its launch and would always win, hiding the step beneath it.  With both
sinks off a stage is a shared object per name: two clock reads and an
add, no span object and no annotation.  A stage does not nest inside
another stage, so the stage seconds of a launch add up to at most its
wall time.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional


class _NullSpan:
    """Shared do-nothing span returned while tracing is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, et, ev, tb) -> bool:
        return False

    def annotate(self, **args: Any) -> None:
        pass

    def event(self, name: str, **args: Any) -> None:
        pass


NULL_SPAN = _NullSpan()


class Span:
    """A live span; created only when the tracer is enabled."""

    __slots__ = ("_tracer", "name", "args", "_t0")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0

    def __enter__(self) -> "Span":
        self._t0 = self._tracer._now_us()
        return self

    def annotate(self, **args: Any) -> None:
        """Attach extra args discovered mid-span (e.g. result sizes)."""
        self.args.update(args)

    def event(self, name: str, **args: Any) -> None:
        """An instant event stamped inside this span's thread track."""
        self._tracer.instant(name, **args)

    def __exit__(self, et, ev, tb) -> bool:
        t1 = self._tracer._now_us()
        args = self.args
        if et is not None:
            # spans close under exceptions (incl. BaseException kills);
            # record what tore through so the trace shows the failure.
            args = dict(args)
            args["error"] = et.__name__
        self._tracer._append(
            {
                "name": self.name,
                "ph": "X",
                "ts": self._t0,
                "dur": max(t1 - self._t0, 0.0),
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            }
        )
        return False


class Tracer:
    """Bounded in-memory trace recorder with Chrome-trace export."""

    def __init__(self, capacity: int = 65536, clock=time.monotonic):
        self.enabled = False
        self.clock = clock
        self.dropped = 0
        self._t0 = clock()
        self._events: deque = deque(maxlen=int(capacity))
        self._lock = threading.Lock()

    # -- recording ------------------------------------------------------
    def _now_us(self) -> float:
        return (self.clock() - self._t0) * 1e6

    def _append(self, ev: Dict[str, Any]) -> None:
        with self._lock:
            if len(self._events) == self._events.maxlen:
                self.dropped += 1
            self._events.append(ev)

    def span(self, name: str, **args: Any):
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, args)

    def instant(self, name: str, **args: Any) -> None:
        if not self.enabled:
            return
        self._append(
            {
                "name": name,
                "ph": "i",
                "s": "t",
                "ts": self._now_us(),
                "pid": os.getpid(),
                "tid": threading.get_ident(),
                "args": args,
            }
        )

    def counter(self, name: str, **values: Any) -> None:
        if not self.enabled:
            return
        self._append(
            {
                "name": name,
                "ph": "C",
                "ts": self._now_us(),
                "pid": os.getpid(),
                "args": {k: float(v) for k, v in values.items()},
            }
        )

    # -- inspection / export --------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self.dropped = 0

    def export_chrome_trace(self, path) -> str:
        """Write the ring buffer as a Perfetto-loadable ``trace.json``."""
        doc = {
            "traceEvents": self.events(),
            "displayTimeUnit": "ms",
            "metadata": {
                "recorder": "repro.obs.trace",
                "dropped_events": self.dropped,
            },
        }
        path = os.fspath(path)
        with open(path, "w") as fh:
            json.dump(doc, fh)
        return path


# -- process-wide tracer ------------------------------------------------
_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the process tracer (tests install a fresh one); returns it."""
    global _TRACER
    _TRACER = tracer
    return tracer


def enable(capacity: Optional[int] = None) -> Tracer:
    if capacity is not None and capacity != _TRACER._events.maxlen:
        set_tracer(Tracer(capacity=capacity))
    _TRACER.enabled = True
    return _TRACER


def disable() -> None:
    _TRACER.enabled = False


def span(name: str, **args: Any):
    """Module-level span helper; the disabled path is one attr check."""
    t = _TRACER
    if not t.enabled:
        return NULL_SPAN
    return Span(t, name, args)


def instant(name: str, **args: Any) -> None:
    _TRACER.instant(name, **args)


def counter(name: str, **values: Any) -> None:
    _TRACER.counter(name, **values)


# -- stage spans: always-on counters, ring buffer, profiler --------------
# Process counters the stages feed until the façade drains them.
_COUNTERS: Dict[str, float] = {}
# TraceMe.is_enabled and TraceAnnotation, resolved once jax is imported.
_collecting: Optional[Callable[[], bool]] = None
_Annotation = None


def add(counter: str, value: float) -> None:
    """Add ``value`` to a stage counter (e.g. ``h2d_bytes``)."""
    _COUNTERS[counter] = _COUNTERS.get(counter, 0) + value


def drain_counters() -> Dict[str, float]:
    """The stage counters accumulated since the last drain; resets them."""
    global _COUNTERS
    out, _COUNTERS = _COUNTERS, {}
    return out


def profiler_collecting() -> bool:
    """True while a JAX profiler session collects.  Without jax imported
    no session can exist, and this imports none."""
    global _collecting, _Annotation
    if _collecting is None:
        if "jax" not in sys.modules:
            return False
        try:
            from jax._src.lib import _profiler
            from jax.profiler import TraceAnnotation
        except ImportError:
            _collecting = lambda: False
        else:
            _collecting = _profiler.TraceMe.is_enabled
            _Annotation = TraceAnnotation
    return _collecting()


class Stage:
    """A stage with only the counter sink: shared per name while off."""

    __slots__ = ("name", "counter", "_t0")

    def __init__(self, name: str, counter: str):
        self.name = name
        self.counter = counter
        self._t0 = None

    def __enter__(self) -> "Stage":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb) -> bool:
        add(self.counter, time.perf_counter() - self._t0)
        self._t0 = None
        return False


class _LiveStage(Stage):
    """A stage while the tracer is on or the profiler collects."""

    __slots__ = ("_span", "_ann")

    def __enter__(self) -> "_LiveStage":
        self._span = span(self.name)
        self._span.__enter__()
        self._ann = _Annotation(self.name) if profiler_collecting() else None
        if self._ann is not None:
            self._ann.__enter__()
        return super().__enter__()

    def __exit__(self, et, ev, tb) -> bool:
        super().__exit__(et, ev, tb)
        if self._ann is not None:
            self._ann.__exit__(et, ev, tb)
        self._span.__exit__(et, ev, tb)
        return False


_STAGES: Dict[str, Stage] = {}


def stage(name: str, counter: str) -> Stage:
    """A stage span adding its seconds to ``counter``; see the module
    docstring.  A name feeds one counter, the one its first call gave."""
    if _TRACER.enabled or profiler_collecting():
        return _LiveStage(name, counter)
    st = _STAGES.get(name)
    if st is None:
        st = _STAGES[name] = Stage(name, counter)
    elif st._t0 is not None:  # open already: another thread holds it
        return Stage(name, counter)
    return st


if os.environ.get("REPRO_TRACE") == "1":  # opt-in via env for CLIs
    enable()
