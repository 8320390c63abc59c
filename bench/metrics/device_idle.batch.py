"""Share of the traced window in which the devices ran nothing."""

from bench.lib import trace


def read(rec):
    if rec.view is None or rec.kind != "solves":
        return None
    share = trace.idle_share(rec.view)
    return None if share is None else 100.0 * share
