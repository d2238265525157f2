"""Stripes per fused write launch, from the program's counters
``kernels.fused_stripes`` / ``kernels.fused_launches`` over the traced
run (the window and its drain)."""


def read(run):
    if run.telemetry is None:
        return None
    launches = run.telemetry.metrics.get("kernels.fused_launches")
    stripes = run.telemetry.metrics.get("kernels.fused_stripes")
    return stripes / launches if launches else None
