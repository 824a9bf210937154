"""Race-level trace spans over the event log (DESIGN.md §8.3).

A *span* is one timed phase of one trace (``ph="X"`` in the Chrome trace
event model); an *instant* is a point event (``ph="i"``). Every serving
ticket gets a trace id at submit (``p<plane>.t<ticket>``) that is
propagated through its whole lifecycle — submit → queue → admit → each
race epoch → terminal — so ``tools/trace_view.py`` can reconstruct exactly
where any individual query's pulls, epochs and wall-time went. Race
sessions record under their own ``s<N>`` trace id; the ticket's ``admit``
event carries ``session=<sid>`` as the join key.

Spans are recorded *at end* (one event each, into the bounded ring), so an
abandoned span costs nothing. All timing is ``time.perf_counter()`` on one
clock; exporters convert to microseconds.

A span opened and closed in one frame (``Tracer.span``, or ``annotate``
around a phase later recorded by ``complete``) is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so a profiler
capture names each idle gap of the device by the host phase that caused
it. Spans that cross calls (``Tracer.start``, e.g. ``plane.queue``) stay in
the event log only: an annotation left open across the caller's waits
would claim them.
"""
from __future__ import annotations

import contextlib
import itertools
import time
from typing import Optional

from jax.profiler import TraceAnnotation

from repro.obs.registry import EventLog

_NO_ANNOTATION = contextlib.nullcontext()

_ids = itertools.count()


def new_trace_id(prefix: str) -> str:
    """Process-unique trace id: ``<prefix>-<N>``."""
    return f"{prefix}-{next(_ids)}"


class Span:
    """An open span; ``end()`` records it. Usable as a context manager.
    With ``annotate`` it is also open as a profiler annotation until
    ``end()``."""

    __slots__ = ("_tracer", "name", "trace", "t0", "attrs", "_open", "_ann")

    def __init__(self, tracer: "Tracer", name: str, trace: Optional[str],
                 attrs: dict, annotate: bool = False):
        self._tracer = tracer
        self.name = name
        self.trace = trace
        self.attrs = attrs
        self._ann = None
        if annotate:
            self._ann = TraceAnnotation(f"repro.{name}")
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        self._open = True

    def end(self, **attrs) -> None:
        if not self._open:          # idempotent: double-end records once
            return
        self._open = False
        dur = time.perf_counter() - self.t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        if attrs:
            self.attrs.update(attrs)
        self._tracer.complete(self.name, self.t0, dur,
                              trace=self.trace, **self.attrs)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _NullSpan:
    """No-op span handed out by a disabled tracer."""

    __slots__ = ()
    name = trace = None
    t0 = 0.0
    attrs: dict = {}

    def end(self, **attrs) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


class Tracer:
    """Records spans/instants into an ``EventLog``. Disabled ⇒ every call
    is a cheap no-op (the ≤2% overhead budget's off switch, §8.5)."""

    def __init__(self, log: EventLog, enabled: bool = True):
        self.log = log
        self.enabled = enabled

    def start(self, name: str, trace: Optional[str] = None, **attrs):
        """Open a span whose end is at a different call site (e.g. the
        queue span: opened at submit, ended at admit)."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, trace, attrs)

    def span(self, name: str, trace: Optional[str] = None, **attrs):
        """Context-manager form for lexically scoped phases; also the
        profiler annotation ``repro.<name>`` while open."""
        if not self.enabled:
            return NULL_SPAN
        return Span(self, name, trace, attrs, annotate=True)

    def annotate(self, name: str):
        """The profiler annotation ``repro.<name>`` alone, for a phase the
        caller times and records itself with ``complete``."""
        if not self.enabled:
            return _NO_ANNOTATION
        return TraceAnnotation(f"repro.{name}")

    def complete(self, name: str, t0: float, dur: float,
                 trace: Optional[str] = None, **attrs) -> None:
        """Record an already-timed span (explicit t0/duration, seconds)."""
        if not self.enabled:
            return
        self.log.append({"ph": "X", "name": name, "trace": trace,
                         "ts": t0, "dur": dur, "attrs": attrs})

    def instant(self, name: str, trace: Optional[str] = None,
                **attrs) -> None:
        if not self.enabled:
            return
        self.log.append({"ph": "i", "name": name, "trace": trace,
                         "ts": time.perf_counter(), "dur": 0.0,
                         "attrs": attrs})
