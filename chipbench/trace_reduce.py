"""Reduce a JAX profiler trace to the numbers the per-layer readers take.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A TPU's plane is named
``/device:TPU:<n>``; its ``XLA Ops`` line holds one event per device
operation (named by its HLO instruction, ``%rans_decode.1 = ...``) and its
``XLA Modules`` line one per program execution (``jit__fused_core(<hash>)``),
each with a start and a duration in nanoseconds on the same clock as the
host planes.  The host's ``TraceAnnotation`` spans (the loops' ``window``, ``offer``,
``pump`` and ``drain``) are events on the host plane's thread lines.

From these:

* the traced window is the ``window`` span;
* busy time is the union of the device operations' intervals inside the
  window, averaged over the chips used; idle share is 1 minus busy over
  the window;
* a kernel's time is the sum of the durations of the program executions
  whose name contains one of its names, over the whole trace: the
  program's XLA glue inside it (``rans_tables``, ``rans_pack``) carries
  no kernel name of its own;
* chip 0's idle time inside the window is put down to the host spans
  (other than ``window``) open during it, and the rest to ``host:other``.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

__all__ = ["Op", "TraceSummary", "reduce_trace", "reduce_events",
           "union_length", "HOST_SPANS"]

# the loops' host spans around calls into the program
HOST_SPANS = ("offer", "pump", "drain")
WINDOW_SPAN = "window"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Op(NamedTuple):
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


def _merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def union_length(intervals: Iterable[Tuple[int, int]], lo: int, hi: int) -> int:
    """Length of the union of intervals, clipped to [lo, hi)."""
    return sum(b - a for a, b in _merge(
        (max(a, lo), min(b, hi)) for a, b in intervals))


def op_label(name: str) -> str:
    """``%fusion.6 = s32[...] fusion(...)`` -> ``fusion.6``;
    ``jit__fused_core(123)`` -> ``jit__fused_core``."""
    return name.split(" = ")[0].lstrip("%").split("(")[0]


class TraceSummary(NamedTuple):
    window: Tuple[int, int]          # ns, the ``window`` span
    device_ops: List[List[Op]]       # per chip, every operation
    host_spans: List[Op]             # the loops' spans
    modules: List[List[Op]]          # per chip, every program execution

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        lo, hi = self.window
        busy = [union_length(((o.start_ns, o.end_ns) for o in ops), lo, hi)
                for ops in self.device_ops]
        return sum(busy) / len(busy) / 1e9 if busy else 0.0

    def idle_share(self) -> Optional[float]:
        if self.window_s <= 0 or not any(self.device_ops):
            return None
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, names: Sequence[str]) -> float:
        """Device seconds of every program execution whose name contains
        one of ``names``, on all chips, over the whole trace."""
        return sum(m.dur_ns for mods in self.modules for m in mods
                   if any(n in m.name for n in names)) / 1e9

    def idle_by_host(self) -> Dict[str, int]:
        """Chip 0's idle time inside the window, in ns, by the host span
        that was open during it (the loop's spans do not overlap); idle
        time under none of them is ``host:other``."""
        lo, hi = self.window
        ops = self.device_ops[0] if self.device_ops else []
        busy = _merge((max(o.start_ns, lo), min(o.end_ns, hi)) for o in ops)
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < hi:
            gaps.append((t, hi))
        out: Dict[str, int] = {}
        for a, b in gaps:
            left = b - a
            for s in self.host_spans:
                c = min(b, s.end_ns) - max(a, s.start_ns)
                if c > 0:
                    out[s.name] = out.get(s.name, 0) + c
                    left -= c
            if left > 0:
                out["host:other"] = out.get("host:other", 0) + left
        return out

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        """The device operations that took most time (as
        ``program:instruction``), and the idle time by what the host was
        doing, each at most ``top`` entries."""
        by_op: Dict[str, int] = {}
        for ops, mods in zip(self.device_ops, self.modules):
            starts = [m.start_ns for m in mods]
            for o in ops:
                i = bisect.bisect_right(starts, o.start_ns) - 1
                prog = (op_label(mods[i].name) if i >= 0
                        and o.start_ns < mods[i].end_ns else "?")
                key = f"{prog}:{op_label(o.name)}"
                by_op[key] = by_op.get(key, 0) + o.dur_ns
        by_gap = self.idle_by_host()
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v / 1e9] for k, v in rank(by_op)],
                "idle_gaps": [[k, v / 1e9] for k, v in rank(by_gap)]}


def reduce_events(device_ops: List[List[Op]], host: List[Op],
                  modules: Optional[List[List[Op]]] = None) -> TraceSummary:
    """The summary from already-extracted events (the recorded-trace test
    and ``reduce_trace`` share this)."""
    windows = [s for s in host if s.name == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no 'window' span")
    w = max(windows, key=lambda s: s.dur_ns)
    spans = [s for s in host if s.name in HOST_SPANS]
    if modules is None:
        modules = [[] for _ in device_ops]
    modules = [sorted(m, key=lambda o: o.start_ns) for m in modules]
    return TraceSummary((w.start_ns, w.end_ns), device_ops, spans, modules)


def read_xplane(path: str, chips: int
                ) -> Tuple[List[List[Op]], List[Op], List[List[Op]]]:
    """(per-chip device operations, host spans, per-chip program
    executions) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[int, List[Op]] = {}
    modules: Dict[int, List[Op]] = {}
    host: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            chip = int(plane.name[len(DEVICE_PLANE):].split()[0])
            if chip >= chips:
                continue
            for line in plane.lines:
                dest = {OPS_LINE: device, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest.setdefault(chip, []).extend(
                        Op(e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    Op(e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                    if e.name in HOST_SPANS or e.name == WINDOW_SPAN)
    return ([device.get(c, []) for c in range(chips)], host,
            [modules.get(c, []) for c in range(chips)])


def reduce_trace(logdir: str, chips: int = 1) -> TraceSummary:
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {logdir}, "
                         f"found {len(paths)}")
    return reduce_events(*read_xplane(paths[0], chips))
