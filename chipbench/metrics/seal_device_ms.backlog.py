"""Device milliseconds of the write kernels per sealed stripe, from the
profiler trace of the window and its drain."""

import kernels


def read(run):
    tr = run.trace_summary
    stripes = run.stamps.get("committed", []) + run.stamps.get("drained", [])
    if tr is None or not stripes:
        return None
    s = tr.kernel_s(kernels.names("write"))
    return s / len(stripes) * 1e3 if s > 0 else None
