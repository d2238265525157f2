"""Tracer: nested spans with monotonic-clock durations.

A :class:`Span` is a context manager; entering pushes it on the tracer's
stack (so spans opened inside it become its children), exiting records a
finished-span event ``{id, parent, name, ts_ns, dur_ns, attrs}`` with
timestamps from ``time.perf_counter_ns`` (monotonic — wall-clock steps
never produce negative durations) relative to the tracer's epoch,
``tracer.epoch_ns`` (an absolute ``perf_counter_ns``): a reader with one
anchor on another clock puts any event on that clock.

Every span also opens a profiler ``TraceMe`` of the same name
(``jax.profiler.TraceAnnotation``, imported only here, on the enabled
path).  Inside a profiler session the program's spans then sit on the
host plane of the ``.xplane.pb``, beside the device operations and on
their clock, so Perfetto or TensorBoard lays them against the device's
idle gaps; outside one a ``TraceMe`` records nothing.

Structured attributes ride on the span: pass them at creation
(``tracer.span("archive.seal", stripes=4, codec="rans")``) or attach
mid-span with ``span.set(launches=2)`` for values only known after the
work ran (e.g. the Pallas launch count a batched seal actually used).

The disabled fast path lives one level up (``repro.obs.Telemetry.span``
returns a shared no-op span without touching this module), so a call site
pays one branch when telemetry is off.  Events accumulate in
``tracer.events`` bounded by ``max_events`` (drops are counted, never
silent) and export via ``repro.obs.export`` (JSONL / Chrome trace_event).
"""

from __future__ import annotations

import time
from typing import Dict, List

__all__ = ["Span", "NullSpan", "NULL_SPAN", "Tracer"]


class NullSpan:
    """Shared no-op span: the single-branch disabled path returns this."""

    __slots__ = ()

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> "NullSpan":
        return self


NULL_SPAN = NullSpan()


class Span:
    __slots__ = ("_tracer", "name", "attrs", "span_id", "parent_id", "_t0",
                 "_trace_me")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict):
        self._tracer = tracer
        self.name = name
        self.attrs = attrs
        self.span_id = 0
        self.parent_id = 0
        self._t0 = 0
        self._trace_me = None

    def set(self, **attrs) -> "Span":
        """Attach attributes discovered mid-span (launch counts, sizes)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self) -> "Span":
        tr = self._tracer
        self.span_id = tr._next_id
        tr._next_id += 1
        self.parent_id = tr._stack[-1] if tr._stack else 0
        tr._stack.append(self.span_id)
        from jax.profiler import TraceAnnotation

        self._trace_me = TraceAnnotation(self.name)
        self._trace_me.__enter__()
        self._t0 = tr._clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tracer
        t1 = tr._clock()
        self._trace_me.__exit__(*exc)
        if tr._stack and tr._stack[-1] == self.span_id:
            tr._stack.pop()
        tr._finish(self, self._t0, t1)
        return False


class Tracer:
    """Collects finished spans as plain dict events (export-ready)."""

    def __init__(self, clock=time.perf_counter_ns, max_events: int = 100_000):
        self._clock = clock
        self.max_events = max_events
        self.events: List[Dict] = []
        self.dropped = 0
        self._stack: List[int] = []
        self._next_id = 1
        self.epoch_ns = clock()

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, attrs)

    def _finish(self, span: Span, t0: int, t1: int) -> None:
        if len(self.events) >= self.max_events:
            self.dropped += 1
            return
        self.events.append(
            {
                "id": span.span_id,
                "parent": span.parent_id,
                "name": span.name,
                "ts_ns": t0 - self.epoch_ns,
                "dur_ns": t1 - t0,
                "attrs": span.attrs,
            }
        )

    def clear(self) -> None:
        self.events = []
        self.dropped = 0
        self._stack = []
        self._next_id = 1
        self.epoch_ns = self._clock()
