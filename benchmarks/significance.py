"""Significance-workload benchmarks: the replica axis vs the legacy path.

The paper's SSIV motivation — >= 1000 permutation iterations per dataset
— is the engine's heaviest workload, so how replicas execute matters:

  engine replica-axis      corr(x, pvalues=...) — one kernel launch per
                           pass covers a whole replica chunk as a leading
                           grid axis; exceedance counts reduce on device.
  legacy dense batched     the pre-engine formulation: per chunk, a
                           vmapped dense GEMM over stacked permuted
                           operands, full (R, n, n) replica matrices
                           materialised and compared on device.
  serving null state       CorrServer.significance cold (builds the
                           replica stacks) vs warm (corpus null-state
                           cache hit) — what repeat edge-significance
                           queries pay.

Small CPU-interpret shapes for the CI smoke; the derived column carries
replicas/s so points stay comparable as shapes change.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, timeit_host
from repro.core import measures
from repro.core.api import corr
from repro.core.significance import PermutationSpec, iteration_keys

T, LBLK = 16, 32
N, L = 48, 32
B, CHUNK = 64, 16


def _legacy_dense_batched(x, spec):
    """The legacy batched-GEMM formulation (key derivation fixed): chunked
    vmap over permuted U, full (R, n, n) replica matrices on device."""
    u = measures.PEARSON.transform(x, dtype=jnp.float32)
    r = jnp.clip(jnp.dot(u, u.T, preferred_element_type=jnp.float32),
                 -1.0, 1.0)
    abs_r = jnp.abs(r)
    keys = iteration_keys(spec)

    @jax.jit
    def chunk_counts(ks):
        def one(k):
            idx = jax.random.permutation(k, u.shape[1])
            rep = jnp.dot(u, u[:, idx].T,
                          preferred_element_type=jnp.float32)
            return (jnp.abs(rep) >= abs_r).astype(jnp.int32)
        return jnp.sum(jax.vmap(one)(ks), axis=0)

    counts = jnp.zeros(r.shape, jnp.int32)
    for lo in range(0, spec.iterations, CHUNK):
        counts = counts + chunk_counts(keys[lo:lo + CHUNK])
    return r, (1.0 + counts) / (1.0 + spec.iterations)


def run() -> None:
    rng = np.random.default_rng(17)
    x = jnp.asarray(rng.standard_normal((N, L)).astype(np.float32))
    spec = PermutationSpec(iterations=B, key=jax.random.PRNGKey(5),
                           chunk=CHUNK)
    kw = dict(t=T, l_blk=LBLK)

    def engine():
        r, p = corr(x, pvalues=spec, **kw)
        jax.block_until_ready(p)

    def legacy():
        r, p = _legacy_dense_batched(x, spec)
        jax.block_until_ready(p)

    engine()   # warm traces
    legacy()
    t_eng = timeit_host(engine, iters=3)
    t_leg = timeit_host(legacy, iters=3)
    emit("significance/engine_replica_axis", t_eng * 1e6,
         f"n={N};l={L};B={B};chunk={CHUNK};"
         f"replicas_per_s={B / max(t_eng, 1e-9):.0f}")
    emit("significance/legacy_dense_batched", t_leg * 1e6,
         f"n={N};l={L};B={B};chunk={CHUNK};"
         f"replicas_per_s={B / max(t_leg, 1e-9):.0f};"
         f"engine_speedup={t_leg / max(t_eng, 1e-9):.2f}x")

    # parity guard: a benchmark that drifts from the oracle measures nothing
    _, p_eng = corr(x, pvalues=spec, **kw)
    _, p_leg = _legacy_dense_batched(x, spec)
    iu = np.triu_indices(N)
    np.testing.assert_array_equal(np.asarray(p_eng)[iu],
                                  np.asarray(p_leg)[iu])

    # -- serving null-state cache: cold vs warm edge-significance queries ----
    from repro.serving import CorpusHandle, CorrServer
    handle = CorpusHandle(x, t=T, l_blk=LBLK)
    probes = jnp.asarray(rng.standard_normal((4, L)).astype(np.float32))
    with CorrServer(handle, t=T, l_blk=LBLK) as srv:
        t_cold = timeit_host(
            lambda: srv.significance(probes, pvalues=spec))
        res = srv.significance(probes, pvalues=spec)
        assert res.stats["null_state_hit"], "repeat spec must hit null state"
        t_warm = timeit_host(
            lambda: srv.significance(probes, pvalues=spec), iters=3)
    emit("significance/serving_null_cold", t_cold * 1e6,
         f"m=4;n={N};B={B};null_chunks={handle.stats()['null_chunks']}")
    emit("significance/serving_null_warm", t_warm * 1e6,
         f"m=4;n={N};B={B};"
         f"speedup={t_cold / max(t_warm, 1e-9):.1f}x")


if __name__ == "__main__":
    run()
