"""Serving-layer benchmarks: what the plan cache and request batching buy.

Three structural A/Bs over the serving subsystem (src/repro/serving/),
small enough for the CPU-interpret CI smoke but shaped like the production
win:

  plan_cache miss vs hit   first query of a shape bucket pays plan build +
                           kernel trace; every later query in the bucket
                           reuses the frozen plan and compiled kernel
                           (tracking pcc_tiles' jit-cache size proves no
                           re-trace on the hit path).
  batched vs serial        N single-probe queries served one-by-one launch
                           N padded tile grids; coalesced through the
                           QueryBatcher they launch ONE grid whose row
                           bucket holds all probes — tile count drops from
                           N * ceil(n/t) to ceil(N/t) * ceil(n/t).
  transform cache          repeat corr() over the same corpus array skips
                           the O(n*l) row transform (the CorpusHandle /
                           corr() shared seam).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from benchmarks.common import emit, timeit_host
from repro.core import api
from repro.core.api import corr
from repro.serving import CorpusHandle, PlanCache, Query, QueryBatcher

T, LBLK = 16, 32
N_CORPUS, L = 64, 32
N_SERIAL = 8


def _kernel_cache_size() -> int:
    from repro.kernels.pcc_tile import pcc_tiles
    try:
        return pcc_tiles._cache_size()
    except AttributeError:  # jit cache introspection moved; fail soft
        return -1


def run() -> None:
    rng = np.random.default_rng(7)
    corpus = jnp.asarray(
        rng.standard_normal((N_CORPUS, L)).astype(np.float32))
    handle = CorpusHandle(corpus, t=T, l_blk=LBLK)
    cache = PlanCache()
    bat = QueryBatcher(handle, t=T, l_blk=LBLK, plan_cache=cache)
    probes = [jnp.asarray(rng.standard_normal((m, L)).astype(np.float32))
              for m in (5, 7, 3)]

    # -- plan-cache miss vs hit --------------------------------------------
    traces0 = _kernel_cache_size()
    t_miss = timeit_host(lambda: bat.execute([Query(probes[0])]))
    traces_miss = _kernel_cache_size()
    t_hit = timeit_host(lambda: bat.execute([Query(probes[1])]))
    traces_hit = _kernel_cache_size()
    emit("serving/plan_cache_miss", t_miss * 1e6,
         f"m=5;bucket={T};kernel_traces={traces_miss - traces0}")
    emit("serving/plan_cache_hit", t_hit * 1e6,
         f"m=7;bucket={T};kernel_traces={traces_hit - traces_miss};"
         f"speedup={t_miss / max(t_hit, 1e-9):.1f}x;"
         f"cache={cache.stats()['hits']}h/{cache.stats()['misses']}m")
    assert cache.stats()["hits"] >= 1, "same bucket must hit the plan cache"
    if traces_hit >= 0:
        assert traces_hit == traces_miss, \
            "a plan-cache hit must not re-trace the kernel"

    # -- batched vs serial probe queries ------------------------------------
    singles = [jnp.asarray(rng.standard_normal((1, L)).astype(np.float32))
               for _ in range(N_SERIAL)]
    queries = [Query(p) for p in singles]

    def serial():
        for p in singles:
            np.asarray(corr(p, corpus, t=T, l_blk=LBLK))

    def batched():
        bat.execute(queries)

    # steady-state serving comparison: warm both paths (tracing + transform
    # caches), then take the median — the launch-count difference is the
    # signal, not one-time compilation
    serial()
    batched()
    t_serial = timeit_host(serial, iters=3)
    t_batched = timeit_host(batched, iters=3)
    m_col = -(-N_CORPUS // T)
    tiles_serial = N_SERIAL * m_col
    tiles_batched = -(-N_SERIAL // T) * m_col
    emit("serving/probe_queries_serial", t_serial * 1e6,
         f"requests={N_SERIAL};m=1;grid_tiles={tiles_serial}")
    emit("serving/probe_queries_batched", t_batched * 1e6,
         f"requests={N_SERIAL};m=1;grid_tiles={tiles_batched};"
         f"speedup={t_serial / max(t_batched, 1e-9):.1f}x;"
         f"occupancy={N_SERIAL / (-(-N_SERIAL // T) * T):.2f}")

    # -- transform cache: repeat corr() over one corpus ---------------------
    api.clear_prepared_cache()
    xs = jnp.asarray(rng.standard_normal((48, L)).astype(np.float32))
    t_cold = timeit_host(lambda: np.asarray(
        corr(xs, t=T, l_blk=LBLK)))
    t_warm = timeit_host(lambda: np.asarray(
        corr(xs, t=T, l_blk=LBLK)))
    st = api.prepared_cache_stats()
    emit("serving/corr_repeat_cold", t_cold * 1e6,
         f"n=48;l={L};transforms={st['misses']}")
    emit("serving/corr_repeat_warm", t_warm * 1e6,
         f"n=48;l={L};transform_cache_hits={st['hits']};"
         f"speedup={t_cold / max(t_warm, 1e-9):.1f}x")
    assert st["misses"] == 1, "one transform per corpus"


if __name__ == "__main__":
    run()
