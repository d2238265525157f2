"""Share of the window in which no operation ran on the device."""


def read(run):
    tr = run.trace_summary
    idle = tr.idle_share() if tr is not None else None
    return None if idle is None else 100.0 * idle
