"""Streaming-corpus benchmarks: what incremental ingest buys (ISSUE 9).

Three structural A/Bs over the live-corpus subsystem (serving/live.py),
small enough for the CPU-interpret CI smoke but shaped like the
production win:

  append O(delta) vs O(n)   appending d rows to a live corpus maintains
                            the prepared operand incrementally (transform
                            d rows, launch d-vs-n grid + d-vs-d triangle)
                            vs the cold path: re-transform all n+d rows
                            and recompute the full (n+d) triangle.
  delta tile count          the structural ratio behind the time: delta
                            tiles vs full-rebuild tiles (kernel-spy
                            counted, not estimated).
  watch revalidation        latency of revalidating a standing top-k
                            query against an append delta (probes vs d
                            new rows, canonical re-merge) vs re-running
                            the full probes-vs-corpus query.

Steady-state measurement: every timed step appends the same d rows to a
fresh same-shaped corpus through one shared PlanCache, so the first
(warm-up) append pays plan build + kernel trace and the timed ones
measure the serving-loop cost — the same discipline benchmarks/serving.py
uses for its hit path.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

import repro.core.allpairs as allpairs
from benchmarks.common import emit, timeit_host
from repro.core.api import corr
from repro.core.mapping import TriangularWorkload
from repro.core.plan import prepare_operand_raw
from repro.core import measures
from repro.serving import CorpusHandle, CorrServer, LiveIndex, PlanCache

T, LBLK = 16, 32
N0, L, D = 64, 32, 8
STEPS = 3


def run() -> None:
    rng = np.random.default_rng(11)
    x0 = rng.standard_normal((N0, L)).astype(np.float32)
    d = rng.standard_normal((D, L)).astype(np.float32)

    # -- append: incremental maintain + delta plans vs cold rebuild ---------
    cache = PlanCache()
    handles = [CorpusHandle(x0, t=T, l_blk=LBLK) for _ in range(STEPS + 1)]
    indexes = [LiveIndex(h, measure="pearson", plan_cache=cache)
               for h in handles]
    tiles = {"n": 0}
    orig = allpairs.launch_tiles

    def spy(plan, u, j0, launch, v=None, grid_cols=None):
        tiles["n"] += plan.workload.job_count
        return orig(plan, u, j0, launch, v=v, grid_cols=grid_cols)

    allpairs.launch_tiles = spy
    try:
        handles[0].append(d)        # warm-up: traces the delta plans
    finally:
        allpairs.launch_tiles = orig
    delta_tiles = tiles["n"]
    t_inc = timeit_host(lambda: [h.append(d) for h in handles[1:]]) / STEPS

    meas = measures.get("pearson")
    full = np.concatenate([x0, d])
    n1 = full.shape[0]

    def cold_rebuild():
        u = prepare_operand_raw(jnp.asarray(full), meas, None, T, LBLK)
        jnp.asarray(u).block_until_ready()
        np.asarray(corr(full, t=T, l_blk=LBLK))

    cold_rebuild()                  # warm-up: same discipline
    t_cold = timeit_host(cold_rebuild, iters=STEPS)
    full_tiles = TriangularWorkload(-(-n1 // T)).job_count
    emit("streaming/append_incremental", t_inc * 1e6,
         f"n={N0};d={D};delta_tiles={delta_tiles}")
    emit("streaming/append_cold_rebuild", t_cold * 1e6,
         f"n={n1};full_tiles={full_tiles};"
         f"speedup={t_cold / max(t_inc, 1e-9):.1f}x;"
         f"tile_ratio={full_tiles / max(delta_tiles, 1):.1f}x")
    assert delta_tiles < full_tiles, \
        "delta plans must launch fewer tiles than a full rebuild"
    for li in indexes:
        li.close()

    # -- standing-query revalidation latency --------------------------------
    probes = rng.standard_normal((4, L)).astype(np.float32)
    wcache = PlanCache()
    servers = [CorrServer(x0, t=T, l_blk=LBLK, max_wait_s=0.0,
                          plan_cache=wcache)
               for _ in range(STEPS + 1)]
    try:
        watches = [srv.watch(probes, 5) for srv in servers]
        servers[0].corpus.append(d)     # warm-up revalidation
        t_reval = timeit_host(
            lambda: [srv.corpus.append(d) for srv in servers[1:]]) / STEPS

        def full_requery():
            np.asarray(corr(probes, full, t=T, l_blk=LBLK))

        full_requery()
        t_full = timeit_host(full_requery, iters=STEPS)
        emit("streaming/watch_revalidate_delta", t_reval * 1e6,
             f"probes=4;k=5;d={D};generation={watches[1].generation}")
        emit("streaming/watch_full_requery", t_full * 1e6,
             f"probes=4;n={n1};"
             f"speedup={t_full / max(t_reval, 1e-9):.1f}x")
        assert all(w.generation == s.corpus.generation
                   for w, s in zip(watches, servers)), \
            "watches must track their corpus generation"
    finally:
        for srv in servers:
            srv.close()


if __name__ == "__main__":
    run()
