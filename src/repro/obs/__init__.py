"""repro.obs — archive telemetry tier: spans, metrics, byte-flow ledger.

One process-global :class:`Telemetry` bundle (``OBS``) that the whole
stripe lifecycle reports into:

* ``OBS.span("archive.seal", stripes=4)`` — nested spans with monotonic
  durations and structured attrs (stripe ids, shard counts, codec names,
  Pallas launch counts).  Exports as JSONL or a Chrome/Perfetto trace,
  and each span also lands in the JAX profiler's trace as a ``TraceMe``
  of the same name, on the device operations' clock; the tracer's public
  ``epoch_ns`` maps its events onto any clock a reader has an anchor for.
* ``OBS.metrics`` — counters / gauges / fixed-bucket histograms (p50/p95/
  p99 without stored samples).  Canonical names in :mod:`repro.obs.names`.
* ``OBS.ledger`` — every byte crossing a lifecycle boundary attributed to
  a labeled edge (:mod:`repro.obs.ledger`); ``OBS.ledger.report()`` is the
  paper's data-movement table in one call.

Zero overhead when disabled — the contract every hot path relies on:
``OBS`` starts disabled; ``span()`` then returns the shared ``NULL_SPAN``
and ``count``/``flow``/``observe``/``gauge`` return after a single
attribute test.  No event, no allocation beyond the argument tuple, no
timestamps, no ``TraceMe``.  The ``obs_overhead`` bench gates the enabled
cost at <= 3% of ``seal_payload_stripe``; disabled cost is one branch.

Instrumented call sites follow one pattern::

    from repro import obs

    with obs.OBS.span("archive.seal", stripes=len(stripes)) as sp:
        ...
        sp.set(launches=n_launches)
    obs.OBS.flow(obs.EDGE_DEVICE_TO_JOURNAL, body_nbytes)

Tests use the ``enabled()`` context manager for a fresh, isolated capture::

    with obs.enabled() as t:
        seal_payload_stripe(...)
    assert t.ledger.bytes(obs.EDGE_SHARD_TO_PARITY) == expected
"""

from __future__ import annotations

from contextlib import contextmanager
from typing import Dict

from .ledger import (  # noqa: F401  (re-exported surface)
    ByteLedger,
    EDGE_CROSS_CHIP,
    EDGE_DEVICE_TO_JOURNAL,
    EDGE_ENTROPY_COMP,
    EDGE_ENTROPY_RAW,
    EDGE_HOST_TO_DEVICE,
    EDGE_INGEST_SHED,
    EDGE_REBUILD_READ,
    EDGE_REBUILD_WRITE,
    EDGE_REPLAY_FULL_BASELINE,
    EDGE_REPLAY_PARITY,
    EDGE_REPLAY_PLANNED,
    EDGE_REPLAY_READ,
    EDGE_SCRUB_READ,
    EDGE_SCRUB_SYNDROME,
    EDGE_SHARD_TO_PARITY,
)
from .metrics import Counter, Gauge, Histogram, Metrics  # noqa: F401
from .trace import NULL_SPAN, NullSpan, Span, Tracer  # noqa: F401
from . import names  # noqa: F401

__all__ = [
    "Telemetry", "OBS", "enable", "disable", "reset", "enabled",
    "Metrics", "Counter", "Gauge", "Histogram",
    "Tracer", "Span", "NullSpan", "NULL_SPAN",
    "ByteLedger", "names",
    "EDGE_HOST_TO_DEVICE", "EDGE_ENTROPY_RAW", "EDGE_ENTROPY_COMP",
    "EDGE_DEVICE_TO_JOURNAL", "EDGE_SHARD_TO_PARITY", "EDGE_CROSS_CHIP",
    "EDGE_INGEST_SHED",
    "EDGE_REPLAY_PLANNED", "EDGE_REPLAY_FULL_BASELINE",
    "EDGE_REPLAY_READ", "EDGE_REPLAY_PARITY",
    "EDGE_SCRUB_READ", "EDGE_SCRUB_SYNDROME",
    "EDGE_REBUILD_READ", "EDGE_REBUILD_WRITE",
]


class Telemetry:
    """Tracer + metrics + ledger behind one enable flag.

    Every recording entry point tests ``self.enabled`` exactly once and
    returns immediately when off — that single branch is the entire
    disabled-mode cost at a call site.
    """

    __slots__ = ("enabled", "tracer", "metrics", "ledger")

    def __init__(self) -> None:
        self.enabled = False
        self.tracer = Tracer()
        self.metrics = Metrics()
        self.ledger = ByteLedger()

    # ------------------------------------------------------------ recording
    def span(self, name: str, **attrs):
        if not self.enabled:
            return NULL_SPAN
        return self.tracer.span(name, **attrs)

    def count(self, name: str, n: int = 1) -> None:
        if self.enabled:
            self.metrics.add(name, n)

    def gauge(self, name: str, v: float) -> None:
        if self.enabled:
            self.metrics.set_gauge(name, v)

    def observe(self, name: str, v: float) -> None:
        if self.enabled:
            self.metrics.observe(name, v)

    def flow(self, edge: str, nbytes: int, events: int = 1) -> None:
        """Bill bytes to a ledger edge (no-op when disabled)."""
        if self.enabled:
            self.ledger.add(edge, nbytes, events)

    # ------------------------------------------------------------- querying
    def snapshot(self, reset: bool = False) -> Dict[str, object]:
        """Metrics snapshot plus the ledger report (ledger never resets
        here — it is a conservation ledger, not a rate window)."""
        out = self.metrics.snapshot(reset=reset)
        out["ledger"] = self.ledger.report()
        return out

    def reset(self) -> None:
        self.tracer.clear()
        self.metrics.clear()
        self.ledger.reset()


#: The process-global telemetry bundle every instrumented seam reports to.
OBS = Telemetry()


def enable(reset: bool = False) -> Telemetry:
    if reset:
        OBS.reset()
    OBS.enabled = True
    return OBS


def disable() -> Telemetry:
    OBS.enabled = False
    return OBS


def reset() -> Telemetry:
    OBS.reset()
    return OBS


@contextmanager
def enabled(fresh: bool = True):
    """Enable OBS for a block, restoring the prior state after.  With
    ``fresh=True`` (the default) the capture starts empty AND is cleared
    on exit, so tests never leak events into each other."""
    prior = OBS.enabled
    if fresh:
        OBS.reset()
    OBS.enabled = True
    try:
        yield OBS
    finally:
        # The capture stays readable after the block; only the flag reverts.
        OBS.enabled = prior
