"""bitplane_roofline.repair: the RS bitplane kernel's share of its
roofline.  The least time the traced recoveries need on this chip (the
larger of int8 operations over the int8 peak and bytes over HBM
bandwidth, harness/kernels.py) over the device time of the
`_matmul_gf_bitplane` XLA modules in the trace."""

from harness.kernels import bitplane_cost, roofline_floor
from harness.trace import module_seconds


def read(run):
    if run.trace is None or not run.trace_work.get("segments"):
        return None
    secs, runs = module_seconds(run.trace, "_matmul_gf_bitplane")
    if not runs or secs <= 0:
        return None
    ops, nbytes = bitplane_cost(run.trace_work["segments"],
                                run.extra["data_fragments"],
                                run.extra["fragment_bytes"])
    floor, _ = roofline_floor(ops, nbytes, run.peaks, "int8_ops_per_s")
    return 100.0 * floor / secs
