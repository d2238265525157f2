"""Milliseconds per sealed stripe of the mesh write program's collective
operations (the parity partials' all-gather) on the busiest chip, from the
profiler trace of the window and its drain."""

import mesh_trace


def read(run):
    tr = run.trace_summary
    stripes = mesh_trace.stripes(run)
    if tr is None or not stripes:
        return None
    busiest = max(mesh_trace.collective_s(tr), default=0.0)
    return busiest / len(stripes) * 1e3 if busiest > 0 else None
