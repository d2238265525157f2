"""Megabytes the mesh write launches moved between chips per stripe they
sealed, from the program's counters ``mesh.cross_chip_bytes`` /
``kernels.fused_stripes`` over the traced run (the window and its drain).
Nothing on a run without device operations (a CPU run), nor from a
program without the counter."""

import program_spans


def read(run):
    if not program_spans.on_chip(run):
        return None
    moved = run.telemetry.metrics.get("mesh.cross_chip_bytes")
    stripes = run.telemetry.metrics.get("kernels.fused_stripes")
    return moved / stripes / 1e6 if stripes and moved else None
