"""The mesh write program's collectives on the device trace of a run on
several chips.

The write program is the one ``kernels.json`` names (``jit__fused_core``,
which ``jit__fused_core_mesh`` contains, so one entry finds the program on
one chip and on a mesh); its executions, summed over the chips, are what
``seal_device_ms.backlog`` and ``seal_roofline.backlog`` read there too.
``mesh_programs.json`` names the HLO collectives that move bytes between
chips.  From the reduced trace (``trace_reduce.TraceSummary``):

* each chip's collective time: the union of the intervals of the
  collective operations that ran inside the write program's executions on
  it;
* the bytes the parity reduce must bring to each chip: every stripe's P
  and Q partials of the other chips, each as long as the coder's capacity
  of its launch (T rows of 128 bytes: the sealed rows are sized to hold a
  raw shard), which the manifests record.

``ici_peaks.json`` holds each chip's published interconnect bandwidth.
Nothing is found, and every reader returns ``None``, on a trace without a
collective inside the write program (a CPU run, or one chip).
"""

from __future__ import annotations

import bisect
import json
import os
from typing import Dict, Iterable, List

import kernels
from trace_reduce import TraceSummary, op_label, union_length

__all__ = ["names", "collective_s", "parity_bytes_per_chip", "ici_peak",
           "stripes"]

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE = os.path.join(HERE, "mesh_programs.json")
PEAKS = os.path.join(HERE, "ici_peaks.json")


def names() -> Dict[str, List[str]]:
    with open(TABLE) as f:
        return json.load(f)


def stripes(run) -> List:
    """The stripes the traced run sealed: the window's and the drain's."""
    return run.stamps.get("committed", []) + run.stamps.get("drained", [])


def collective_s(tr: TraceSummary) -> List[float]:
    """Per chip, seconds in which a collective operation of the write
    program ran on it."""
    program = kernels.names("write")
    collectives = names()["collectives"]
    out = []
    for ops, mods in zip(tr.device_ops, tr.modules):
        mine = [m for m in mods if any(n in m.name for n in program)]
        starts = [m.start_ns for m in mine]
        spans = []
        for o in ops:
            if not any(c in op_label(o.name) for c in collectives):
                continue
            i = bisect.bisect_right(starts, o.start_ns) - 1
            if i >= 0 and o.start_ns < mine[i].end_ns:
                spans.append((o.start_ns, o.end_ns))
        out.append(union_length(spans, -2**62, 2**62) / 1e9)
    return out


def parity_bytes_per_chip(sealed: Iterable, chips: int, parity: str) -> int:
    """Bytes of the other chips' parity partials each chip receives for
    ``sealed`` stripes: (chips - 1) partials of each strip of each stripe,
    T * 128 bytes each, T the coder rows its manifests record."""
    strips = {"raid6": 2, "raid5": 1, "none": 0}[parity]
    rows = (max(int(b.manifest["entropy"]["rows"]) for b in st.blocks)
            for st in sealed)
    return sum(strips * (chips - 1) * r * 128 for r in rows)


def ici_peak(device_kind: str) -> float:
    """Published interconnect bandwidth of one chip, bytes/s.  A device
    the table does not hold is an error, not a default."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table["devices"]:
        raise KeyError(f"no ICI peak for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return float(table["devices"][device_kind]["ici_bytes_per_s"])
