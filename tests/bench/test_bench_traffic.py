"""The open-loop load generator and the latency statistics."""

import json
import math
from pathlib import Path

import numpy as np
import pytest

import _bench_path  # noqa: F401
from bench.lib import traffic

TRAFFIC = json.loads((_bench_path.ROOT / "bench" / "traffic" /
                      "seek_top50.json").read_text())
BIG_SEED = 2**33 + 12345


def _sched(seed, seconds=10.0, tr=TRAFFIC):
    return traffic.search_schedule(tr, 17555, seed, seconds)


def test_same_seed_same_schedule_and_gene_sets():
    a, b = _sched(BIG_SEED), _sched(BIG_SEED)
    assert [q.due_s for q in a] == [q.due_s for q in b]
    assert all(np.array_equal(p.genes, q.genes) for p, q in zip(a, b))


def test_seeds_share_the_schedule_and_draw_other_genes():
    a, b = _sched(1), _sched(BIG_SEED)
    assert len(a) == len(b) == round(TRAFFIC["rate_qps"] * 10.0)
    assert [q.due_s for q in a] == [q.due_s for q in b]
    assert [len(q.genes) for q in a] == [len(q.genes) for q in b]
    assert sum(np.array_equal(p.genes, q.genes) for p, q in zip(a, b)) \
        < len(a) // 10


def test_schedule_seed_moves_the_arrivals_not_the_work():
    other = dict(TRAFFIC, schedule_seed=TRAFFIC["schedule_seed"] + 1)
    a, b = _sched(1), _sched(1, tr=other)
    assert not np.array_equal([q.due_s for q in a], [q.due_s for q in b])
    assert sorted(len(q.genes) for q in a) == sorted(len(q.genes) for q in b)
    # the same n gaps in another order: the n - 1 between the dues leave
    # out one each, so their sorted values agree up to that one gap
    ga = np.sort(np.diff([q.due_s for q in a]))
    gb = np.sort(np.diff([q.due_s for q in b]))
    assert np.mean(np.isclose(ga, gb, rtol=1e-9, atol=1e-12)) > 0.5
    assert abs(ga.sum() - gb.sum()) < 2 * max(ga.max(), gb.max())


def test_schedule_fits_the_window_at_the_offered_rate():
    s = _sched(7, seconds=20.0)
    due = np.array([q.due_s for q in s])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 20.0
    assert len(s) / 20.0 == TRAFFIC["rate_qps"]
    lo, hi = TRAFFIC["set_size"]
    sizes = [len(q.genes) for q in s]
    assert min(sizes) == lo and max(sizes) == hi
    assert all(len(np.unique(q.genes)) == len(q.genes) for q in s)


def test_popular_genes_are_asked_for_most():
    s = _sched(3, seconds=50.0)
    counts = np.bincount(np.concatenate([q.genes for q in s]),
                         minlength=17555)
    top = np.sort(counts)[::-1]
    assert top[0] > 20 * max(1, np.median(counts))


def test_percentiles_are_over_all_queries_with_misses_in_the_tail():
    lat = list(np.arange(1, 101, dtype=float))
    assert traffic.nearest_rank(lat, 50) == 50.0
    assert traffic.nearest_rank(lat, 95) == 95.0
    lat[-6:] = [math.inf] * 6
    assert traffic.nearest_rank(lat, 95) == math.inf
    # not a mean of per-chunk percentiles
    chunks = [sorted(lat[i:i + 10]) for i in range(0, 100, 10)]
    assert traffic.nearest_rank(lat, 50) != np.mean([c[4] for c in chunks])


class _SlowServer:
    """Answers every query `service_s` after it is dispatched, one at a
    time, like a server with one dispatcher thread."""

    def __init__(self, x, service_s=0.02, **kw):
        import threading
        from concurrent.futures import ThreadPoolExecutor
        self.pool = ThreadPoolExecutor(1)
        self.service_s = service_s
        self.lock = threading.Lock()

    def submit(self, probes, k):
        import time

        def serve():
            time.sleep(self.service_s)
            return _Answer(probes, k)
        return self.pool.submit(serve)

    def stats(self):
        return {"requests": 0, "batches": 0, "rows": 0}

    def close(self):
        self.pool.shutdown()


class _Answer:
    def __init__(self, probes, k):
        self.value = None
        self.stats = {"batch_rows": len(probes), "batch_requests": 1,
                      "batch_occupancy": 1.0}


def test_latency_runs_from_the_due_time(monkeypatch):
    """A server slower than the offered rate: the backlog grows, so later
    queries wait longer; measured from due time, their latency counts that
    wait, and it exceeds the bare service time by far."""
    import dataclasses

    import jax

    from bench.lib import search
    cell = _bench_path.cell("seek_gpl570", "seek_top50", ["query_p95_ms"],
                            n_genes=64, n_samples=16, programs=4)
    cell = dataclasses.replace(
        cell, traffic=dict(cell.traffic, rate_qps=100.0, set_size=[1, 3]))
    d = search.SearchDriver(cell, 11, jax.devices()[:1], 1.0,
                            server=_SlowServer)
    d._stats0 = d.srv.stats()
    d.window(1.0)
    lat = d.latencies_ms()
    assert len(lat) == 100 and all(np.isfinite(lat))
    # one at a time at 20 ms each: the last of 100 due within 1 s is
    # answered after ~2 s, so its latency from due is about 1 s
    assert lat[-1] > 500.0 and lat[0] < 200.0
    assert traffic.nearest_rank(lat, 95) > 10 * 20.0
    assert d.end_to_end()["served_qps"] == pytest.approx(50.0, rel=0.25)
