"""Megabytes of sealed rows and parity the fused write launches sent to
the host per stripe they sealed, from the program's counters
``kernels.rows_to_host_bytes`` / ``kernels.fused_stripes`` over the traced
run (the window and its drain).  Nothing on a run without device
operations (a CPU run), nor from a program without the counter."""

import program_spans


def read(run):
    if not program_spans.on_chip(run):
        return None
    moved = run.telemetry.metrics.get("kernels.rows_to_host_bytes")
    stripes = run.telemetry.metrics.get("kernels.fused_stripes")
    return moved / stripes / 1e6 if stripes and moved else None
