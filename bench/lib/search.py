"""Driver of the search traffic: SEEK-style gene-set queries through
`CorrServer.submit`, offered open-loop at the rate the traffic file fixes.

Each query is the expression rows of a gene set, sent from the host as a
client would send them, asking for the k strongest partners of each gene.
Its latency runs from its due time in the schedule to the moment its
answer is on the host.  Once the window has closed, a seeded sample of the
answers (the largest set among them) is compared with the float64
reference; an answer that never comes is a failed query.

Traffic parameters (bench/traffic/<name>.json, "kind": "search"):
    rate_qps         offered queries per second, Poisson arrivals
    schedule_seed    draws the due times and set sizes, the same in every run
    set_size         [lo, hi] genes per query, spread evenly
    zipf_s           gene popularity exponent over a seeded permutation
    k                partners per gene
    max_wait_s, max_batch_rows   the server's coalescing settings
    check_queries    answers compared with the reference per run
    limits           {number: limit} for the comparison
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from typing import Callable, Dict, List, Optional

import numpy as np

from bench.lib import reference, traffic
from bench.lib.data import make_compendium
from bench.lib.record import Record
from bench.lib.spec import Cell
from bench.lib.trace import span
from bench.lib.work import rect_topk_work

# an answer due in the window may come this long after the close
LATE_S = 60.0


class SearchDriver:
    def __init__(self, cell: Cell, seed: int, devices: List,
                 seconds: float, server: Optional[Callable] = None):
        import jax
        import jax.numpy as jnp
        if server is None:
            from repro.serving.server import CorrServer as server
        self.cell, self.seed, self.devices = cell, seed, devices
        cfg, tr = cell.config, cell.traffic
        self.n, self.l = int(cfg["n_genes"]), int(cfg["n_samples"])
        self.k = int(tr["k"])
        self.x = make_compendium(seed, self.n, self.l, int(cfg["programs"]))
        self.schedule = traffic.search_schedule(tr, self.n, seed, seconds)
        self.warm_sets = traffic.warm_sets(tr, self.n, seed)
        # every probe slab the run sends, gathered in one call and brought
        # to the host once: clients send host arrays
        sets = [q.genes for q in self.schedule] + self.warm_sets
        flat = np.asarray(jax.jit(lambda x, i: x[i])(
            self.x, jnp.asarray(np.concatenate(sets), jnp.int32)))
        cuts = np.cumsum([len(g) for g in sets])[:-1]
        slabs = np.split(flat, cuts)
        self.probes = slabs[:len(self.schedule)]
        self.warm_probes = slabs[len(self.schedule):]
        self.srv = server(self.x, max_wait_s=float(tr["max_wait_s"]),
                          max_batch_rows=int(tr["max_batch_rows"]))
        self.done_at: Dict[int, float] = {}
        self.lags: List[float] = []
        self._lock = threading.Lock()

    def warm(self) -> None:
        """One query of every set size, one after another: the probe
        shapes the window sends, and the kernel bucket they launch."""
        for p in self.warm_probes:
            self.srv.submit(p, k=self.k).result(timeout=LATE_S)

    def _mark(self, i: int):
        def done(_f):
            t = time.perf_counter()
            with self._lock:
                self.done_at[i] = t
        return done

    def window(self, seconds: float) -> None:
        """Send every query at its due time; wait for every answer."""
        futures: List[Optional[Future]] = []
        lags = []
        t0 = time.perf_counter()
        self.t0 = t0
        with span("window"):
            for i, q in enumerate(self.schedule):
                wait = t0 + q.due_s - time.perf_counter()
                if wait > 0:
                    with span("wait"):
                        time.sleep(wait)
                t_send = time.perf_counter()
                lags.append(t_send - (t0 + q.due_s))
                with span("submit"):
                    try:
                        f = self.srv.submit(self.probes[i], k=self.k)
                    except Exception:   # noqa: BLE001 — a refused query
                        f = None
                if f is not None:
                    f.add_done_callback(self._mark(i))
                futures.append(f)
            with span("result"):
                close = t0 + seconds
                for f in futures:
                    if f is None:
                        continue
                    left = close + LATE_S - time.perf_counter()
                    try:
                        f.result(timeout=max(left, 0.0))
                    except Exception:   # noqa: BLE001 — counted as failed
                        pass
        self.lags = lags
        # a Future wakes its waiters before it runs its callbacks
        settle = time.perf_counter() + 1.0
        while time.perf_counter() < settle and any(
                f is not None and f.done() and i not in self.done_at
                for i, f in enumerate(futures)):
            time.sleep(0.001)
        self.ok = [f is not None and f.done() and not f.cancelled()
                   and f.exception() is None and i in self.done_at
                   for i, f in enumerate(futures)]
        self.served = {i: futures[i].result() for i, ok in
                       enumerate(self.ok) if ok}
        self.srv.close()

    def latencies_ms(self) -> List[float]:
        """Due time to answer, for every query of the window; a query that
        failed or never came reads infinity."""
        return [1e3 * (self.done_at[i] - (self.t0 + q.due_s))
                if self.ok[i] else float("inf")
                for i, q in enumerate(self.schedule)]

    def end_to_end(self) -> Dict[str, float]:
        lat = self.latencies_ms()
        ok = sorted(self.served)
        span_s = (max(self.done_at[i] for i in ok) - self.t0) if ok else 0.0
        return {"query_p50_ms": traffic.nearest_rank(lat, 50),
                "query_p95_ms": traffic.nearest_rank(lat, 95),
                "served_qps": len(ok) / span_s if span_s > 0 else 0.0}

    def record(self, rec: Record) -> Record:
        rec.lags_s = list(self.lags)
        # each answered query names the launch that served it; a launch
        # of c queries is counted once, as c shares of 1/c
        for st in (r.stats for r in self.served.values()):
            share = 1.0 / int(st["batch_requests"])
            rec.launches.append((rect_topk_work(
                int(st["batch_rows"]), self.n, self.l, self.k), share))
            rec.occupancy.append((float(st["batch_occupancy"]), share))
        return rec

    def free(self):
        """The sampled answers on the host; every device array dropped."""
        answered = sorted(self.served)
        rng = traffic.rng_for(self.seed, 4)
        count = min(int(self.cell.traffic["check_queries"]), len(answered))
        sample = set(rng.choice(answered, count, replace=False).tolist()
                     if count else [])
        if answered:
            sample.add(max(answered,
                           key=lambda i: len(self.schedule[i].genes)))
        self.sample = sorted(sample)
        answers = {i: self.served[i].value for i in self.sample}
        self.served = {i: None for i in self.served}
        self._x_host = np.asarray(self.x)
        self.x = None
        return answers

    def _ref_rows(self, zn: np.ndarray, i: int) -> np.ndarray:
        return reference.rows_of(zn, self.schedule[i].genes)

    def _zn(self) -> np.ndarray:
        if getattr(self, "_zn_host", None) is None:
            self._zn_host = reference.unit_rows(self._x_host, "pearson")
        return self._zn_host

    def _gaps(self, answers) -> Dict[str, float]:
        """The widest gaps over the sampled answers, and how many of them
        broke a limit."""
        limits = self.cell.traffic["limits"]
        worst = {"value_gap": 0.0, "rank_gap": 0.0}
        bad = 0
        for i in self.sample:
            g = reference.topk_gaps(answers[i]["indices"],
                                    answers[i]["values"],
                                    self._ref_rows(self._zn(), i), self.k)
            bad += not reference.within(g, limits)
            worst = {k: max(worst[k], g[k]) for k in worst}
        return {**worst, "bad_answers": bad}

    def readings(self, answers) -> Dict[str, float]:
        return self._gaps(answers)

    def control_readings(self) -> Dict[str, float]:
        """The control in the program's place: the same sampled queries
        answered from rows computed one precision step down."""
        import jax.numpy as jnp
        u = reference.control_unit_rows(jnp.asarray(self._x_host), "pearson")
        answers = {}
        for i in self.sample:
            rows = reference.control_rows(u, self.schedule[i].genes)
            idx = reference.topk_order(rows, self.k)
            answers[i] = {"indices": idx,
                          "values": np.take_along_axis(rows, idx, axis=1)}
        return self._gaps(answers)

    def counts(self, readings: Dict[str, float]) -> Dict[str, int]:
        missing = len(self.schedule) - len(self.served)
        return {"attempted": len(self.schedule),
                "failed": missing + int(readings["bad_answers"])}
