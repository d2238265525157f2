"""Milliseconds a GOP waits from its offer to its stripe's seal dispatch,
in the frontend's queue and the coalescer's bucket, from the program's
counters ``ingest.dispatch_wait_us`` / ``ingest.dispatched_gops`` over
the traced run."""

import program_spans


def read(run):
    if not program_spans.on_chip(run):
        return None
    gops = run.telemetry.metrics.get("ingest.dispatched_gops")
    wait_us = run.telemetry.metrics.get("ingest.dispatch_wait_us")
    return wait_us / gops / 1e3 if gops else None
