"""Device busy time outside the Pallas kernels, per device and solve:
the operand transform and the dense sink's scatter and symmetrize (and,
on a mesh, the collectives that feed them)."""

from bench.lib import kernels, trace


def read(rec):
    if rec.view is None or not rec.view.devices or not rec.solves:
        return None
    devs = list(rec.view.devices)
    outside = sum(trace.busy_ns(rec.view, d)
                  - trace.matching_ns(rec.view, kernels.ANY_KERNEL, d)
                  for d in devs)
    return outside * 1e-6 / len(devs) / rec.solves
