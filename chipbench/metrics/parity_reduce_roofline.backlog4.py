"""The parity reduce's share of the interconnect roofline: the bytes of
the other chips' P/Q partials each chip must receive for the traced run's
stripes (``mesh_trace.parity_bytes_per_chip``) over one chip's published
ICI bandwidth, against the busiest chip's collective time."""

import mesh_trace


def read(run):
    tr = run.trace_summary
    stripes = mesh_trace.stripes(run)
    if tr is None or not stripes:
        return None
    busiest = max(mesh_trace.collective_s(tr), default=0.0)
    if busiest <= 0:
        return None
    nbytes = mesh_trace.parity_bytes_per_chip(stripes, run.cell.chips,
                                              run.parity)
    return 100.0 * nbytes / mesh_trace.ici_peak(run.device_kind) / busiest
