"""Share of its roofline that the all-pairs tile kernel reaches.

Least time of the solves in the window (operations and bytes from the
problem's shape, bench/lib/work.py, on the chip's peaks) over the device
time of the kernel's events, summed over every device of the cell."""

from bench.lib import kernels, trace


def read(rec):
    if rec.view is None or rec.peaks is None or not rec.solves:
        return None
    ns = trace.matching_ns(rec.view, kernels.PCC_TILES)
    if ns == 0:
        return None
    least = rec.solves * rec.solve_work.least_seconds(rec.peaks,
                                                      rec.operand_dtype)
    return 100.0 * least / (ns * 1e-9)
