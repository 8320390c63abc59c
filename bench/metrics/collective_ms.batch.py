"""Device time of collective operations, per device and solve."""

from bench.lib import trace


def read(rec):
    if rec.view is None or not rec.view.devices or not rec.solves:
        return None
    ns = trace.collective_ns(rec.view)
    if ns == 0:
        return None
    return ns * 1e-6 / len(rec.view.devices) / rec.solves
