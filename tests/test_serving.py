"""Serving layer (ISSUE 5): plan-cache keying/LRU, transform-cache
identity (one transform per corpus — including the corr() bugfix),
batcher coalescing oracle (bit-identical to per-request corr(), dense and
top-k, ragged tile-straddling slabs), and CorrServer end-to-end with
concurrent submission and per-request stats.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import api, measures
from repro.core.api import corr
from repro.core.plan import ExecutionPlan
from repro.core.sinks import RowBlockSink, TopKSink
from repro.serving import (CorpusHandle, CorrServer, PlanCache, ProblemSpec,
                           Query, QueryBatcher, bucket_rows)

T, LBLK = 8, 8


def _x(n, l, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((n, l)).astype(np.float32))


@pytest.fixture
def corpus():
    return CorpusHandle(_x(40, 12, seed=100), t=T, l_blk=LBLK)


@pytest.fixture(autouse=True)
def _fresh_prepared_cache():
    api.clear_prepared_cache()
    yield
    api.clear_prepared_cache()


# ---------------------------------------------------------------------------
# PlanCache keying
# ---------------------------------------------------------------------------


def _spec(rows=5, cols=40, l=12, **kw):
    kw.setdefault("t", T)
    kw.setdefault("l_blk", LBLK)
    return ProblemSpec.for_query(rows, cols, l, **kw)


def test_plan_cache_hit_on_equal_spec():
    pc = PlanCache()
    p1, hit1 = pc.get(_spec())
    p2, hit2 = pc.get(_spec())
    assert (hit1, hit2) == (False, True)
    assert p1 is p2  # same frozen plan object -> jit cache sees same statics
    assert pc.stats() == {"hits": 1, "misses": 1, "size": 1, "capacity": 32}


def test_plan_cache_bucketing_shares_plans_within_a_tile():
    pc = PlanCache()
    # 1..t probes land in one bucket; t+1 starts the next
    p1, _ = pc.get(_spec(rows=1))
    p2, hit = pc.get(_spec(rows=T))
    assert hit and p1 is p2
    _, hit3 = pc.get(_spec(rows=T + 1))
    assert not hit3
    assert bucket_rows(1, T) == T and bucket_rows(T + 1, T) == 2 * T
    with pytest.raises(ValueError, match="positive"):
        bucket_rows(0, T)


@pytest.mark.parametrize("delta", [
    dict(measure="cosine"),               # measure change
    dict(compute_dtype=jnp.bfloat16),     # dtype change
    dict(rows=T + 1),                     # shape-bucket change
    dict(cols=41),                        # corpus-size change
    dict(l=13),                           # sample-count change
    dict(max_tiles_per_pass=2),           # pass-partition change
])
def test_plan_cache_misses_on_spec_change(delta):
    pc = PlanCache()
    pc.get(_spec())
    _, hit = pc.get(_spec(**delta))
    assert not hit
    assert pc.stats()["misses"] == 2


def test_plan_cache_misses_on_mesh_change():
    pc = PlanCache()
    pc.get(_spec())
    mesh = jax.make_mesh((1,), ("d",))
    plan, hit = pc.get(_spec(mesh=mesh))
    assert not hit and plan.p == 1
    _, hit2 = pc.get(_spec(mesh=mesh))
    assert hit2


def test_plan_cache_bounded_lru_eviction():
    pc = PlanCache(capacity=2)
    s1, s2, s3 = _spec(rows=1), _spec(rows=T + 1), _spec(rows=2 * T + 1)
    pc.get(s1)
    pc.get(s2)
    pc.get(s1)          # refresh s1 -> s2 becomes LRU
    pc.get(s3)          # evicts s2
    assert len(pc) == 2 and s2 not in pc and s1 in pc and s3 in pc
    _, hit = pc.get(s2)  # rebuilt, not a hit
    assert not hit
    with pytest.raises(ValueError, match="positive"):
        PlanCache(capacity=0)


def test_transform_cache_evicts_inside_its_own_locked_section():
    """A cached array's death callback can run while its thread holds the
    cache lock (a garbage collection triggered by an allocation there);
    the eviction must not deadlock on that lock."""
    cache = api.TransformCache(capacity=4)
    held = [jnp.ones((8, 8), jnp.float32)]
    cache.prepared(held[0], measures.PEARSON, None, T, LBLK,
                   build=lambda: held[0] + 1)
    assert len(cache) == 1

    def drop_under_lock():
        with cache._lock:
            held.pop()      # last reference: the callback evicts right here

    worker = threading.Thread(target=drop_under_lock, daemon=True)
    worker.start()
    worker.join(timeout=10)
    assert not worker.is_alive(), "eviction deadlocked on the cache lock"
    assert len(cache) == 0


def test_plan_cache_serves_unregistered_custom_measures():
    """corr() accepts bare Measure objects; serving must too — the spec
    carries the resolved object, so an unregistered measure builds fine
    and a custom measure shadowing a registry name stays distinct."""
    custom = measures.Measure("my_dot", measures.identity_transform, None,
                              None)
    handle = CorpusHandle(_x(24, 12, seed=9), t=T, l_blk=LBLK)
    bat = QueryBatcher(handle, t=T, l_blk=LBLK, measure=custom)
    p = _x(3, 12, seed=10)
    results, _ = bat.execute([Query(p)])
    ref = np.asarray(corr(p, handle.x, t=T, l_blk=LBLK, measure=custom))
    np.testing.assert_array_equal(results[0], ref)
    # a shadowing instance (same name as a registered measure, different
    # semantics) must not collide with the registry singleton in the cache
    shadow = measures.Measure("pearson", measures.identity_transform, None,
                              None)
    pc = bat.plan_cache
    n0 = pc.stats()["misses"]
    bat2 = QueryBatcher(handle, t=T, l_blk=LBLK, measure=shadow,
                        plan_cache=pc)
    res_shadow, _ = bat2.execute([Query(p)])
    assert pc.stats()["misses"] == n0 + 1  # distinct spec, no false hit
    ref_shadow = np.asarray(corr(p, handle.x, t=T, l_blk=LBLK,
                                 measure=shadow))
    np.testing.assert_array_equal(res_shadow[0], ref_shadow)
    # and it really is the raw-dot semantics, not registry pearson
    assert not np.array_equal(
        res_shadow[0], np.asarray(corr(p, handle.x, t=T, l_blk=LBLK)))
    # shadow + registry singleton in ONE batch: grouped by identity, each
    # served with its own semantics
    mixed, infos = bat2.execute([Query(p, measure=shadow),
                                 Query(p, measure="pearson")])
    np.testing.assert_array_equal(mixed[0], ref_shadow)
    np.testing.assert_array_equal(
        mixed[1], np.asarray(corr(p, handle.x, t=T, l_blk=LBLK)))
    assert infos[0] is not infos[1]  # two launches, not one


def test_spec_key_matches_spec_dict_identity():
    plan = ExecutionPlan.create(16, 12, n_cols=40, t=T, l_blk=LBLK)
    same = ExecutionPlan.create(16, 12, n_cols=40, t=T, l_blk=LBLK)
    other = ExecutionPlan.create(16, 12, n_cols=40, t=T, l_blk=LBLK,
                                 measure="cosine")
    assert plan.spec_key() == same.spec_key()
    assert hash(plan.spec_key()) == hash(same.spec_key())
    assert plan.spec_key() != other.spec_key()
    assert dict(plan.spec_key()) == plan.spec_dict()


# ---------------------------------------------------------------------------
# Transform cache: one transform per corpus (incl. the corr() bugfix)
# ---------------------------------------------------------------------------


def _count_prepares(monkeypatch):
    calls = []
    real = ExecutionPlan._prepare_one

    def spy(self, x):
        calls.append(x.shape)
        return real(self, x)

    monkeypatch.setattr(ExecutionPlan, "_prepare_one", spy)
    return calls


def test_corr_symmetric_transforms_once_per_corpus(monkeypatch):
    """The satellite bugfix: repeat corr(x) over the same device array runs
    the O(n·l) row transform exactly once."""
    calls = _count_prepares(monkeypatch)
    x = _x(33, 12, seed=1)
    r1 = np.asarray(corr(x, t=T, l_blk=LBLK))
    r2 = np.asarray(corr(x, t=T, l_blk=LBLK))
    assert len(calls) == 1
    np.testing.assert_array_equal(r1, r2)
    # a different measure is a different prepared operand
    corr(x, t=T, l_blk=LBLK, measure="cosine")
    assert len(calls) == 2
    # host numpy input converts to a fresh device array per call, so the
    # transform re-runs (no stable identity to key on)
    xh = np.asarray(x)
    corr(xh, t=T, l_blk=LBLK)
    corr(xh, t=T, l_blk=LBLK)
    assert len(calls) == 4


def test_corr_rectangular_reuses_cached_corpus_transform(monkeypatch):
    calls = _count_prepares(monkeypatch)
    x, y = _x(5, 12, seed=2), _x(40, 12, seed=3)
    corr(x, y, t=T, l_blk=LBLK)
    assert len(calls) == 2          # both operands prepared once
    x2 = _x(7, 12, seed=4)
    corr(x2, y, t=T, l_blk=LBLK)
    assert len(calls) == 3          # y served from cache across calls


def test_corpus_handle_one_transform_per_measure(monkeypatch):
    x = _x(40, 12, seed=5)
    handle = CorpusHandle(x, t=T, l_blk=LBLK)
    calls = []
    real = CorpusHandle._prepare
    monkeypatch.setattr(
        CorpusHandle, "_prepare",
        lambda self, meas, cd: (calls.append(meas.name),
                                real(self, meas, cd))[1])
    for _ in range(3):
        handle.operand("pearson")
    handle.operand("cosine")
    handle.operand("cosine")
    assert calls == ["pearson", "cosine"]
    assert handle.stats()["misses"] == 2 and handle.stats()["hits"] == 3
    # norms: pearson-transformed rows are unit-norm (non-degenerate corpus)
    norms = np.asarray(handle.row_norms("pearson"))
    np.testing.assert_allclose(norms, 1.0, atol=1e-5)


def test_transform_cache_lru_and_identity_guard():
    cache = api.TransformCache(capacity=2)
    meas = measures.get("pearson")
    xs = [_x(8, 8, seed=s) for s in range(3)]
    for x in xs:
        cache.prepared(x, meas, None, T, LBLK,
                       build=lambda x=x: jnp.zeros((8, 8)))
    assert len(cache) == 2 and cache.misses == 3
    # oldest evicted: re-preparing it is a miss again
    cache.prepared(xs[0], meas, None, T, LBLK,
                   build=lambda: jnp.zeros((8, 8)))
    assert cache.misses == 4
    # numpy operands bypass the cache entirely
    cache.prepared(np.zeros((8, 8), np.float32), meas, None, T, LBLK,
                   build=lambda: jnp.zeros((8, 8)))
    assert cache.stats()["size"] == 2 and cache.misses == 4


def test_transform_cache_entries_die_with_their_operand():
    """The cache must never extend an operand's lifetime: dropping the
    corpus array evicts its entry (weakref death callback), freeing both
    the array and the cached prepared operand."""
    import gc
    x = _x(16, 10, seed=8)
    corr(x, t=T, l_blk=LBLK)
    assert api.prepared_cache_stats()["size"] == 1
    del x
    gc.collect()
    assert api.prepared_cache_stats()["size"] == 0


def test_corr_numpy_inputs_do_not_pollute_cache():
    """A host numpy operand converts to a fresh device array per call —
    caching it would pin dead buffers and evict live entries without ever
    hitting, so corr() bypasses the cache for it entirely."""
    xh = np.asarray(_x(12, 10, seed=6))
    corr(xh, t=T, l_blk=LBLK)
    corr(xh, t=T, l_blk=LBLK)
    assert api.prepared_cache_stats()["size"] == 0
    yh = np.asarray(_x(9, 10, seed=7))
    corr(xh, yh, t=T, l_blk=LBLK)
    assert api.prepared_cache_stats() == {
        "hits": 0, "misses": 0, "size": 0, "capacity": 8}


# ---------------------------------------------------------------------------
# QueryBatcher: coalesced == per-request, bit for bit
# ---------------------------------------------------------------------------


def _ref_dense(probes, corpus, measure="pearson"):
    return np.asarray(corr(probes, corpus.x, t=T, l_blk=LBLK,
                           measure=measure))


def _ref_topk(probes, corpus, k, measure="pearson"):
    return corr(probes, corpus.x, t=T, l_blk=LBLK, measure=measure,
                sink=TopKSink(k))


def test_batched_dense_bit_identical_to_per_request(corpus):
    """Ragged probe counts straddling tile boundaries (5 + 7 + 9 rows with
    t=8: every slab crosses a tile edge in the stacked batch)."""
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK)
    probes = [_x(m, 12, seed=10 + m) for m in (5, 7, 9)]
    results, infos = bat.execute([Query(p) for p in probes])
    for p, got in zip(probes, results):
        np.testing.assert_array_equal(got, _ref_dense(p, corpus))
    assert infos[0].requests == 3 and infos[0].rows == 21
    assert infos[0].rows_bucket == bucket_rows(21, T)
    assert infos[0] is infos[1] is infos[2]  # one coalesced launch


def test_batched_single_probe_rows(corpus):
    """m=1 queries — the extreme serving shape — coalesce and stay exact."""
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK)
    probes = [_x(1, 12, seed=20 + i) for i in range(5)]
    results, infos = bat.execute([Query(p) for p in probes])
    for p, got in zip(probes, results):
        np.testing.assert_array_equal(got, _ref_dense(p, corpus))
    assert infos[0].rows == 5 and infos[0].rows_bucket == T


def test_batched_topk_bit_identical_including_mixed_k(corpus):
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK)
    pa, pb = _x(5, 12, seed=30), _x(11, 12, seed=31)
    results, _ = bat.execute([Query(pa, k=3), Query(pb, k=7)])
    for p, k, got in [(pa, 3, results[0]), (pb, 7, results[1])]:
        ref = _ref_topk(p, corpus, k)
        np.testing.assert_array_equal(got["indices"], ref["indices"])
        np.testing.assert_array_equal(got["values"], ref["values"])


def test_batched_mixed_kinds_and_measures(corpus):
    """Dense + top-k + a second measure in one execute(): grouped into
    three launches, every answer exact."""
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK)
    pa, pb, pc_, pd = (_x(m, 12, seed=40 + m) for m in (3, 6, 4, 2))
    results, infos = bat.execute([
        Query(pa), Query(pb, k=4), Query(pc_, measure="cosine"), Query(pd)])
    np.testing.assert_array_equal(results[0], _ref_dense(pa, corpus))
    ref_b = _ref_topk(pb, corpus, 4)
    np.testing.assert_array_equal(results[1]["indices"], ref_b["indices"])
    np.testing.assert_array_equal(
        results[2], _ref_dense(pc_, corpus, measure="cosine"))
    np.testing.assert_array_equal(results[3], _ref_dense(pd, corpus))
    # pa and pd share the pearson-dense launch; others ran separately
    assert infos[0] is infos[3] and infos[0].requests == 2
    assert infos[1].requests == 1 and infos[2].requests == 1


def test_batched_topk_bit_identical_under_ties_and_multipass():
    """Exact |r| ties (duplicated corpus rows -> tied 1.0s; and tied
    intermediate values) must not break the bit-identity contract: the
    top-k order is canonical (|value| desc, column asc), so the sliced
    TopKSink(k_max) batch run equals per-request TopKSink(k) runs even
    across different pass partitionings."""
    base = np.asarray(_x(10, 12, seed=33))
    dup = np.concatenate([base, base, base[:4]])  # 24 rows, many exact ties
    handle = CorpusHandle(jnp.asarray(dup), t=T, l_blk=LBLK)
    bat = QueryBatcher(handle, t=T, l_blk=LBLK, max_tiles_per_pass=1)
    pa = jnp.asarray(base[:3])   # probes duplicate corpus rows -> |r| = 1 ties
    pb = jnp.asarray(base[4:9])
    results, _ = bat.execute([Query(pa, k=5), Query(pb, k=8)])
    for p, k, got in [(pa, 5, results[0]), (pb, 8, results[1])]:
        for mtp in (None, 2):  # per-request runs under other partitionings
            ref = corr(p, handle.x, t=T, l_blk=LBLK,
                       max_tiles_per_pass=mtp, sink=TopKSink(k))
            np.testing.assert_array_equal(got["indices"], ref["indices"])
            np.testing.assert_array_equal(got["values"], ref["values"])


def test_batcher_plan_cache_hits_across_batches(corpus):
    pc = PlanCache()
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK, plan_cache=pc)
    bat.execute([Query(_x(5, 12, seed=50))])
    assert pc.stats() == {"hits": 0, "misses": 1, "size": 1, "capacity": 32}
    # different m, same tile bucket -> hit
    _, infos = bat.execute([Query(_x(3, 12, seed=51))])
    assert infos[0].plan_cache_hit and pc.stats()["hits"] == 1


def test_batcher_multi_pass_launches_match(corpus):
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK, max_tiles_per_pass=2)
    probes = [_x(m, 12, seed=60 + m) for m in (7, 9)]
    results, infos = bat.execute([Query(p) for p in probes])
    assert infos[0].passes > 1
    for p, got in zip(probes, results):
        np.testing.assert_array_equal(got, _ref_dense(p, corpus))


def test_batcher_rejections(corpus):
    bat = QueryBatcher(corpus, t=T, l_blk=LBLK)
    with pytest.raises(ValueError, match="samples"):
        bat.execute([Query(_x(3, 11, seed=70))])
    with pytest.raises(ValueError, match="positive"):
        Query(_x(3, 12), k=0)
    with pytest.raises(ValueError, match="probes"):
        Query(jnp.zeros((0, 12)))
    with pytest.raises(ValueError, match="alignment"):
        QueryBatcher(corpus, t=16, l_blk=LBLK)


def test_row_block_sink_contract():
    plan = ExecutionPlan.create(16, 12, n_cols=20, t=T, l_blk=LBLK)
    with pytest.raises(ValueError, match="exceeds"):
        RowBlockSink([(0, 17)]).open(plan)
    with pytest.raises(ValueError, match="bad row range"):
        RowBlockSink([(4, 2)])
    sym = ExecutionPlan.create(16, 12, t=T, l_blk=LBLK)
    with pytest.raises(ValueError, match="grid"):
        RowBlockSink([(0, 4)]).open(sym)


def test_prepare_rows_seam():
    plan = ExecutionPlan.create(16, 12, n_cols=40, t=T, l_blk=LBLK)
    u = plan.prepare_rows(_x(5, 12, seed=80))
    assert u.shape[0] == plan.n_pad == 16
    np.testing.assert_array_equal(np.asarray(u[5:]), 0.0)
    with pytest.raises(ValueError, match="rows"):
        plan.prepare_rows(_x(17, 12, seed=81))
    with pytest.raises(ValueError, match="sample count"):
        plan.prepare_rows(_x(5, 13, seed=82))


# ---------------------------------------------------------------------------
# CorrServer end to end
# ---------------------------------------------------------------------------


def test_server_concurrent_submissions_bit_identical(corpus):
    """Many caller threads, one dispatcher: every future resolves to the
    standalone corr() answer and carries the serving stats."""
    probes = [_x(m, 12, seed=90 + i) for i, m in
              enumerate([1, 5, 7, 3, 9, 2, 4, 6])]
    refs = [_ref_dense(p, corpus) for p in probes]
    with CorrServer(corpus, t=T, l_blk=LBLK, max_wait_s=0.2) as srv:
        futs = [None] * len(probes)

        def submit(i):
            futs[i] = srv.submit(probes[i])

        threads = [threading.Thread(target=submit, args=(i,))
                   for i in range(len(probes))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        results = [f.result(timeout=60) for f in futs]
        stats = srv.stats()
    for ref, res in zip(refs, results):
        np.testing.assert_array_equal(res.value, ref)
        assert res.stats["queue_s"] >= 0
        assert 0 < res.stats["batch_occupancy"] <= 1.0
        assert res.stats["batch_requests"] >= 1
    assert stats["requests"] == len(probes)
    # coalescing happened: strictly fewer launches than requests
    assert stats["batches"] < len(probes)


def test_server_sync_query_and_topk(corpus):
    with CorrServer(corpus, t=T, l_blk=LBLK, max_wait_s=0.0) as srv:
        p = _x(6, 12, seed=200)
        res = srv.query(p, k=5)
        ref = _ref_topk(p, corpus, 5)
        np.testing.assert_array_equal(res.value["indices"], ref["indices"])
        np.testing.assert_array_equal(res.value["values"], ref["values"])
        dense = srv.query(p)
        np.testing.assert_array_equal(dense.value, _ref_dense(p, corpus))
        assert dense.stats["plan_cache_hit"]  # same shape bucket as topk


def test_server_batch_error_fails_futures_not_server(corpus):
    with CorrServer(corpus, t=T, l_blk=LBLK, max_wait_s=0.0) as srv:
        bad = srv.submit(_x(3, 11, seed=201))  # wrong sample count
        with pytest.raises(ValueError, match="samples"):
            bad.result(timeout=60)
        good = srv.query(_x(3, 12, seed=202))
        np.testing.assert_array_equal(
            good.value, _ref_dense(_x(3, 12, seed=202), corpus))


def test_server_close_drains_and_rejects_new(corpus):
    srv = CorrServer(corpus, t=T, l_blk=LBLK, max_wait_s=5.0)
    p = _x(4, 12, seed=203)
    fut = srv.submit(p)
    srv.close()  # must not strand the queued request despite the long wait
    np.testing.assert_array_equal(fut.result(timeout=60).value,
                                  _ref_dense(p, corpus))
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(p)
    srv.close()  # idempotent


def test_server_survives_future_cancellation(corpus):
    """A client cancelling its future must not kill the dispatcher:
    futures transition to RUNNING before resolution, so a cancel either
    lands before dispatch (request dropped uncomputed) or returns False."""
    with CorrServer(corpus, t=T, l_blk=LBLK, max_wait_s=0.2) as srv:
        fut = srv.submit(_x(3, 12, seed=220))
        cancelled = fut.cancel()  # usually lands within the batching window
        p = _x(4, 12, seed=221)
        res = srv.query(p)  # dispatcher must still be alive either way
        np.testing.assert_array_equal(res.value, _ref_dense(p, corpus))
        if cancelled:
            assert fut.cancelled()
        else:
            fut.result(timeout=60)  # raced past the window: served normally


def test_server_max_batch_rows_splits_batches(corpus):
    with CorrServer(corpus, t=T, l_blk=LBLK, max_wait_s=0.05,
                    max_batch_rows=8) as srv:
        probes = [_x(5, 12, seed=210 + i) for i in range(3)]
        futs = [srv.submit(p) for p in probes]
        results = [f.result(timeout=60) for f in futs]
        for p, res in zip(probes, results):
            np.testing.assert_array_equal(res.value, _ref_dense(p, corpus))
        # 15 rows at a cap of 8 -> at least two launches, none above cap
        assert srv.stats()["batches"] >= 2
        for res in results:
            assert res.stats["batch_rows"] <= 8
