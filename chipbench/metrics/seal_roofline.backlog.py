"""Write kernels' share of the HBM roofline: the bytes sealing the traced
run's stripes must move (``hbm.seal_bytes``, unpadded) over the chip's
HBM peak, against the kernels' device time.  Integer coder work has no
published peak, so bytes bound it."""

import hbm
import kernels


def read(run):
    tr = run.trace_summary
    stripes = run.stamps.get("committed", []) + run.stamps.get("drained", [])
    if tr is None or not stripes:
        return None
    s = tr.kernel_s(kernels.names("write"))
    if s <= 0:
        return None
    nbytes = hbm.seal_bytes(
        ([kernels.shard_sizes(b) for b in st.blocks] for st in stripes),
        parity_strips={"raid6": 2, "raid5": 1}[run.parity])
    return 100.0 * nbytes / hbm.hbm_peak(run.device_kind) / s
