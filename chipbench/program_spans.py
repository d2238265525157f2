"""The program's own spans (``repro.obs``) on the profiler trace's clock.

The program's tracer stamps each span on ``time.perf_counter_ns``,
relative to its public ``tracer.epoch_ns``; the profiler stamps the device
operations on a clock of its own.  The harness stamps ``window_ns`` on
``perf_counter_ns`` just before it opens the ``window`` annotation, so

    offset = trace_summary.window[0] - stamps["window_ns"][0]

puts every span on the trace's clock.  (Each span also lands in the
``.xplane.pb`` as a ``TraceMe`` of the same name, but the harness deletes
the trace before the readers run; ``tests/test_program_spans.py`` checks
the mapped spans against those copies in a recorded trace.)

From these: chip 0's idle time inside the window, split by the innermost
program span open during it, the rest being ``host:other``.  Nothing is
found here, and every reader returns ``None``, where the trace holds no
device operation (a CPU run), where telemetry was off, or where the
program's tracer has no public epoch.
"""

from __future__ import annotations

import heapq
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from trace_reduce import Op, TraceSummary, _merge

__all__ = ["on_chip", "events", "ms_per_stripe", "idle_gaps",
           "idle_by_span", "OTHER"]

OTHER = "host:other"


def on_chip(run) -> bool:
    """Whether a traced run has device operations and program telemetry."""
    tr = run.trace_summary
    return (tr is not None and any(tr.device_ops)
            and run.telemetry is not None)


def events(run) -> List[Op]:
    """The program's spans of a traced run, on the trace's clock."""
    epoch = getattr(getattr(run.telemetry, "tracer", None), "epoch_ns", None)
    if not on_chip(run) or epoch is None or "window_ns" not in run.stamps:
        return []
    offset = run.trace_summary.window[0] - run.stamps["window_ns"][0] + epoch
    return [Op(e["name"], e["ts_ns"] + offset, e["dur_ns"])
            for e in run.telemetry.tracer.events]


def ms_per_stripe(run, name: str) -> Optional[float]:
    """Host milliseconds in the program's ``name`` spans over the traced
    run (window and drain), per stripe of their ``stripes`` attributes."""
    if not on_chip(run):
        return None
    spans = [e for e in run.telemetry.tracer.events if e["name"] == name]
    stripes = sum(int(e["attrs"].get("stripes", 0)) for e in spans)
    if not stripes:
        return None
    return sum(e["dur_ns"] for e in spans) / stripes / 1e6


def idle_gaps(summary: TraceSummary) -> List[Tuple[int, int]]:
    """Chip 0's idle intervals inside the window, in order."""
    lo, hi = summary.window
    ops = summary.device_ops[0] if summary.device_ops else []
    gaps, t = [], lo
    for a, b in _merge((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def _overlap(pieces: Iterable[Tuple[int, int, str]],
             gaps: Sequence[Tuple[int, int]]) -> Dict[str, int]:
    """Length of each label's pieces inside the gaps; both in order,
    each disjoint."""
    out: Dict[str, int] = {}
    i = 0
    for a, b, label in pieces:
        while i < len(gaps) and gaps[i][1] <= a:
            i += 1
        j = i
        while j < len(gaps) and gaps[j][0] < b:
            c = min(b, gaps[j][1]) - max(a, gaps[j][0])
            if c > 0:
                out[label] = out.get(label, 0) + c
            j += 1
    return out


def _innermost(spans: Sequence[Op], lo: int, hi: int
               ) -> List[Tuple[int, int, str]]:
    """[lo, hi) cut at every span's ends, each piece labelled with the
    innermost span open over it (the one opened last; of two opened at
    once, the shorter); pieces under no span are left out."""
    cuts = sorted({lo, hi} | {min(hi, max(lo, x)) for s in spans
                              for x in (s.start_ns, s.end_ns)})
    order = sorted(spans, key=lambda s: s.start_ns)
    open_: List[Tuple[int, int, int, str]] = []
    out: List[Tuple[int, int, str]] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(order) and order[i].start_ns <= a:
            s = order[i]
            heapq.heappush(open_, (-s.start_ns, s.end_ns, i, s.name))
            i += 1
        while open_ and open_[0][1] <= a:
            heapq.heappop(open_)
        if open_:
            out.append((a, b, open_[0][3]))
    return out


def idle_by_span(summary: TraceSummary, spans: Sequence[Op]
                 ) -> Dict[str, int]:
    """Chip 0's idle ns inside the window by the innermost program span
    open during it; idle time under none is ``host:other``."""
    lo, hi = summary.window
    gaps = idle_gaps(summary)
    out = _overlap(_innermost(spans, lo, hi), gaps)
    other = sum(b - a for a, b in gaps) - sum(out.values())
    if other > 0:
        out[OTHER] = other
    return out
