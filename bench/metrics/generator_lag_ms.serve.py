"""95th percentile of how late the load generator sent a query after its
due time: a starved generator must not read as a fast server."""

from bench.lib.traffic import nearest_rank


def read(rec):
    if not rec.lags_s:
        return None
    return 1e3 * nearest_rank(rec.lags_s, 95)
