"""Share of the window in which chip 0 is idle while the host is in the
seal dispatch's KEM or launch staging (the program's ``ingest.kem`` and
``kernels.stage`` spans, put on the trace's clock)."""

import program_spans

SPANS = ("ingest.kem", "kernels.stage")


def read(run):
    spans = [s for s in program_spans.events(run) if s.name in SPANS]
    if not spans:
        return None
    tr = run.trace_summary
    idle = program_spans.idle_by_span(tr, spans)
    under = sum(ns for name, ns in idle.items() if name in SPANS)
    return 100.0 * under / (tr.window[1] - tr.window[0])
