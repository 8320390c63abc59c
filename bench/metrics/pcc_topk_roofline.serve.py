"""Share of its roofline that the device top-k kernel reaches.

Least time of the window's launches (2*l*n operations per real probe row,
probes and corpus read once, k partners written per row; not the
bucket-padded rows) over the device time of the kernel's events."""

from bench.lib import kernels, trace


def read(rec):
    if rec.view is None or rec.peaks is None or not rec.launches:
        return None
    ns = trace.matching_ns(rec.view, kernels.PCC_TOPK)
    if ns == 0:
        return None
    least = sum(w * work.least_seconds(rec.peaks, rec.operand_dtype)
                for work, w in rec.launches)
    return 100.0 * least / (ns * 1e-9)
