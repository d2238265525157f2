"""Backlog upload: a node that was offline reconnects and uploads its
cameras' recorded GOPs as fast as the archive takes them.

Closed loop with backpressure: the next GOP in capture order is offered
only while it fits under the frontend's queue budget, else the loop
pumps, so admission never sheds.  The write path does the work: coalescer,
host staging and KEM, the fused seal, the fetch, and the journal.

End-to-end: ``ingest_mb_per_s``, the codec payload bytes of GOPs committed
in the window over the window.  An attempt is a GOP offered in the window;
it fails if it is shed, is not committed after the drain that follows the
window, is not durable when acknowledged, or does not read back, through
the reference or the program, as what was offered.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import archive_ops  # noqa: E402
from harness import Check  # noqa: E402


def setup(run) -> None:
    t = time.perf_counter()
    n = archive_ops.warm_write_shapes(run)
    run.log(f"warm-up: {n} seal launches over every write shape in "
            f"{time.perf_counter() - t:.3f} s")
    front, jdir = run.make_frontend("journal")
    run.state.update(front=front, jdir=jdir, acknowledged=[], ack_ns=[], g=0)
    run.stamps["journal_dir"] = jdir


def window(run, seconds: float) -> dict:
    front = run.state["front"]
    budget = int(run.cfg["frontend"]["queue_budget_bytes"])
    g0 = g = run.state["g"]
    committed, ack_ns = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        g, c = archive_ops.offer_or_pump(run, front, g, budget)
        if c:
            committed += c
            ack_ns += [time.perf_counter_ns()] * len(c)
    window_s = time.perf_counter() - t0
    run.state["g"] = g
    run.state["acknowledged"] += committed
    run.state["ack_ns"] += ack_ns
    payload = sum(int(b.manifest["n_i8"]) for st in committed
                  for b in st.blocks)
    run.stamps.update(window_s=window_s, offered=(g0, g),
                      committed=list(committed))
    return {"attempted": g - g0,
            "metrics": {"ingest_mb_per_s": payload / window_s / 1e6}}


def finish(run) -> None:
    with run.span("drain"):
        tail = run.state["front"].drain()
    run.state["acknowledged"] += tail
    run.state["ack_ns"] += [time.perf_counter_ns()] * len(tail)
    run.stamps["drained"] = list(tail)


def check(run):
    front = run.state["front"]
    ack = run.state["acknowledged"]
    g0, g1 = run.stamps["offered"]
    biggest = [max(ack, key=lambda st: max(int(b.manifest["n_i8"])
                                           for b in st.blocks))] if ack else []
    sample = archive_ops.draw_sample(
        run, ack, int(run.traffic["check_stripes"]), must=biggest)
    counts, failed = archive_ops.check_acknowledged(
        run, front.ingest, run.state["jdir"], ack, run.state["ack_ns"],
        range(g1), sample)
    shed = len(front.shed_log)
    limits = {"stored_pct": float(run.cfg["max_stored_pct"])}
    checks = [Check("gops_shed", shed, 0)]
    checks += [Check(k, v, limits.get(k, 0)) for k, v in counts.items()]
    return min(g1 - g0, shed + failed), checks
