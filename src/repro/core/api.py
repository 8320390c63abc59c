"""`corr()`: one problem-centric facade over every pairwise workload.

The paper's bijective job<->coordinate framework (SSIII-B) was derived for
*symmetric* all-pairs, and the historical drivers hardwired that shape:
one operand, n x n output, upper triangle mirrored.  The dominant
production query shapes are wider (cf. CoMet, arXiv:1705.08213 /
arXiv:1705.08210):

  * rectangular — "correlate these m query profiles against the corpus":
    X (n_rows, l) vs Y (n_cols, l), full (n_rows, n_cols) output, no
    mirror;
  * masked — "correlate despite missing samples": per-entry validity
    masks, pairwise-complete statistics over each pair's common support.

This module closes the gap without a second engine.  A frozen
:class:`PairwiseProblem` captures *what* is being asked (operands,
workload, measure, mask policy); :func:`corr` resolves it onto the
existing plan/executor/sink core:

    corr(x)                      symmetric all-pairs — bit-identical to the
                                 historical allpairs(x) for every measure
    corr(x, y)                   rectangular X-vs-Y over the grid bijection
                                 (mapping.GridWorkload, second-operand
                                 kernel block specs)
    corr(x, where="nan")         pairwise-complete masked similarity: the
                                 masked measure's component GEMMs (values,
                                 ones/counts, cross sums — core/measures.py)
                                 each ride the engine as a plain workload
                                 and combine elementwise per pass
    corr(x, sink=HostSink(path=p))           out-of-core assembly with
    corr(x, resume_from=p)                   durable per-pass checkpoints

Execution knobs (sink=, mesh=, shard_u=, t=, max_tiles_per_pass=,
interpret=, compute_dtype=, ...) are orthogonal to the problem and keep
their plan/executor semantics.  The legacy drivers (allpairs_pcc*,
allpairs_pcc_sharded*) are deprecated wrappers over this facade.
"""

from __future__ import annotations

import dataclasses
import weakref
from typing import Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core import measures
from repro.core.allpairs import (_stream, current_call, execute_plan,
                                 executor_stats, run_sink, traced_call)
from repro.core.lru import LruStatsCache
from repro.core.plan import ExecutionPlan, pad_operands
from repro.core.significance import PermutationSpec, run_significance
from repro.core.sinks import HostSink, TileSink
from repro.kernels.pcc_tile import DEFAULT_LBLK, DEFAULT_TILE
from repro.runtime.tracing import span

Array = jax.Array
MaskLike = Union[None, str, np.ndarray, Array, Tuple]


# ---------------------------------------------------------------------------
# Cached operand preparation: the serving seam
# ---------------------------------------------------------------------------


class TransformCache(LruStatsCache):
    """Memoises prepared operands (row transform + dtype narrowing + pad)
    per corpus array.

    The measure row transform is the only per-operand device work of a run
    (epilogues fuse into the kernel), and it is O(n·l) — re-running it for
    an operand the process has already prepared is pure waste.  This cache
    is the one seam both consumers share: ``corr()`` routes every unmasked
    operand through the process-wide default instance, and a serving
    :class:`~repro.serving.corpus.CorpusHandle` owns a private instance for
    its registered corpus, so probe queries skip the corpus transform
    entirely.

    Keying is by *object identity* of the operand (plus the transform's
    parameters: measure, compute dtype, tile alignment).  Only operands
    the *caller* handed over as ``jax.Array`` are cached, and an entry
    holds only a *weak* reference to its operand: the moment the caller
    drops the array, the entry evicts itself (weakref death callback), so
    the cache never extends an operand's lifetime — a loop over many
    distinct corpora peaks at the same device memory it did before the
    cache existed, and a recycled ``id()`` can never alias a dead entry
    (the callback removed it at collection time; an identity check on
    lookup guards the race).  The prepared operand is pinned exactly as
    long as its source array is alive — "while you hold the corpus, its
    transform stays warm".  Host numpy inputs are mutable and convert to
    a fresh device array per call, so ``corr()`` bypasses the cache for
    them (``prepared_operand(cacheable=False)``) — they re-transform as
    they always did, and never pin memory or evict reusable entries.

    Bounded LRU; thread-safe (the serving layer prepares operands from a
    dispatcher thread while user threads call ``corr()``).
    """

    def __init__(self, capacity: int = 8):
        super().__init__(capacity)

    @staticmethod
    def _key(x: Array, measure: measures.Measure, compute_dtype,
             t: int, l_blk: int) -> tuple:
        cd = None if compute_dtype is None else jnp.dtype(compute_dtype).name
        return (id(x), id(measure), cd, int(t), int(l_blk))

    def prepared(self, x: Array, measure: measures.Measure, compute_dtype,
                 t: int, l_blk: int, build: Callable[[], Array]) -> Array:
        """The prepared operand for (x, measure, compute_dtype, t, l_blk),
        built via `build()` on a miss.  Non-jax.Array operands are built
        uncached (mutable host arrays have no stable identity)."""
        if not isinstance(x, jax.Array):
            return build()
        key = self._key(x, measure, compute_dtype, t, l_blk)
        entry = self._lookup(key)
        if entry is not None and entry[0]() is x and entry[1] is measure:
            return entry[2]
        # build outside the lock: transforms may dispatch device work
        u_pad = build()
        try:
            ref = weakref.ref(x, lambda _, k=key: self._evict(k))
        except TypeError:
            # non-weakref-able array type: serve uncached rather than pin
            return u_pad
        self._insert(key, (ref, measure, u_pad))
        return u_pad


_PREPARED = TransformCache()


def prepared_operand(plan: ExecutionPlan, x: Array, *,
                     cache: Optional[TransformCache] = None,
                     expect_rows: Optional[int] = None,
                     cacheable: bool = True) -> Array:
    """``plan.prepare(x)`` through a transform cache (default: the
    process-wide one ``corr()`` uses).  expect_rows overrides the row-count
    check for rectangular column operands (plan.prepare validates against
    n_rows; the prepared output itself only depends on measure, dtype and
    alignment, so cached entries are shared across workload shapes).
    cacheable=False skips the cache outright — ``corr()`` passes it for
    operands the caller supplied as host numpy, whose jnp.asarray
    conversion is a fresh device array every call (caching those would pin
    dead buffers and evict live entries without ever hitting)."""
    rows = plan.n_rows if expect_rows is None else expect_rows
    if tuple(x.shape) != (rows, plan.l):
        raise ValueError(
            f"operand shape {tuple(x.shape)} does not match plan "
            f"(rows={rows}, l={plan.l})")
    with span("prepare", call=current_call()):
        if not cacheable:
            return plan._prepare_one(x)
        c = cache if cache is not None else _PREPARED
        return c.prepared(x, plan.measure, plan.compute_dtype, plan.t,
                          plan.l_blk, build=lambda: plan._prepare_one(x))


def clear_prepared_cache() -> None:
    """Drop every cached prepared operand (tests; memory pressure)."""
    _PREPARED.clear()


def prepared_cache_stats() -> dict:
    return _PREPARED.stats()


def _as_mask(mask, data: Array, side: str) -> Array:
    m = jnp.asarray(mask)
    if m.shape != tuple(data.shape):
        raise ValueError(
            f"where mask for {side} has shape {m.shape}, expected "
            f"{tuple(data.shape)}")
    return m.astype(bool)


@dataclasses.dataclass(frozen=True, eq=False)
class PairwiseProblem:
    """What is being asked, independent of how it executes.

    operands:    x (n_rows, l) and optional y (n_cols, l) — y=None is the
                 symmetric all-pairs workload over x alone.
    measure:     resolved Measure; masked runs additionally resolve the
                 pairwise-complete MaskedMeasure of the same name.
    mask policy: mask_x / mask_y are boolean validity masks (True = sample
                 present), or None for fully observed.  Built by `create`
                 from ``where=``: None (unmasked), "nan" (infer validity
                 from NaNs), a boolean array for x, or an (x_mask, y_mask)
                 tuple for rectangular problems.
    """

    x: Array
    y: Optional[Array]
    measure: measures.Measure
    mask_x: Optional[Array] = None
    mask_y: Optional[Array] = None

    @property
    def symmetric(self) -> bool:
        return self.y is None

    @property
    def masked(self) -> bool:
        return self.mask_x is not None

    @property
    def n_rows(self) -> int:
        return self.x.shape[0]

    @property
    def n_cols(self) -> int:
        return (self.x if self.y is None else self.y).shape[0]

    @property
    def l(self) -> int:
        return self.x.shape[1]

    @classmethod
    def create(cls, x: Array, y: Optional[Array] = None, *,
               measure: measures.MeasureLike = "pearson",
               where: MaskLike = None) -> "PairwiseProblem":
        x = jnp.asarray(x)
        if x.ndim != 2:
            raise ValueError(f"x must be (n, l), got shape {x.shape}")
        if y is not None:
            y = jnp.asarray(y)
            if y.ndim != 2 or y.shape[1] != x.shape[1]:
                raise ValueError(
                    f"y must be (n_cols, l={x.shape[1]}), got shape "
                    f"{None if y is None else y.shape}")
        meas = measures.get(measure)
        mask_x = mask_y = None
        if where is not None:
            # resolving the masked variant up front fails fast for
            # measures with no pairwise-complete form (rank measures)
            measures.get_masked(meas)
            if isinstance(where, str):
                if where != "nan":
                    raise ValueError(
                        f"where={where!r} not understood; pass a boolean "
                        f"mask, an (x_mask, y_mask) tuple, or 'nan'")
                mask_x = ~jnp.isnan(x)
                mask_y = None if y is None else ~jnp.isnan(y)
            elif isinstance(where, tuple):
                wx, wy = where
                mask_x = (~jnp.isnan(x) if wx is None
                          else _as_mask(wx, x, "x"))
                if y is None:
                    if wy is not None:
                        raise ValueError(
                            "symmetric problem (y=None) takes a single "
                            "mask, not an (x_mask, y_mask) tuple")
                    mask_y = None
                else:
                    mask_y = (~jnp.isnan(y) if wy is None
                              else _as_mask(wy, y, "y"))
            else:
                if y is not None:
                    raise ValueError(
                        "rectangular masked problems need masks for both "
                        "sides: pass where=(x_mask, y_mask) (either may be "
                        "None to infer from NaNs)")
                mask_x = _as_mask(where, x, "x")
        return cls(x=x, y=y, measure=meas, mask_x=mask_x, mask_y=mask_y)


@traced_call
def corr(
    x: Array,
    y: Optional[Array] = None,
    *,
    measure: measures.MeasureLike = "pearson",
    where: MaskLike = None,
    sink: Optional[TileSink] = None,
    mesh: Optional[Mesh] = None,
    shard_u: bool = False,
    t: int = DEFAULT_TILE,
    l_blk: int = DEFAULT_LBLK,
    max_tiles_per_pass: Optional[int] = None,
    interpret: Optional[bool] = None,
    clip: bool = True,
    fuse_epilogue: bool = True,
    compute_dtype=None,
    resume_from: Optional[str] = None,
    pvalues: Optional[PermutationSpec] = None,
    recovery=None,
):
    """Pairwise similarity for any workload shape: plan -> executor -> sink.

    x:       (n_rows, l) variables.
    y:       optional (n_cols, l) second operand — rectangular X-vs-Y
             cross-correlation over the full tile grid (row-major
             bijection; nothing mirrored).  y=None is the symmetric
             all-pairs workload (upper-triangle bijection + mirror),
             bit-identical to the historical ``allpairs(x)``.
    measure: any registered measure name or Measure (core/measures.py).
    where:   mask policy for pairwise-complete (missing-data) similarity:
             "nan" infers per-entry validity from NaNs; a boolean array
             masks x (symmetric problems); an (x_mask, y_mask) tuple masks
             both sides of a rectangular problem (either entry None =
             infer from NaNs).  Each pair is scored over its *common*
             valid samples via the masked measure's component GEMMs —
             effective sample counts come from a parallel ones-GEMM.
             Pairs with fewer than 2 common samples (or degenerate
             common-support variance) score 0.  Supported for measures
             with a registered pairwise-complete variant
             (pearson/cosine/covariance).
    sink:    output handling (core/sinks.py) — default DenseSink returns
             the dense device matrix; HostSink assembles out-of-core to
             host/memmap (with durable per-pass checkpoints when given a
             path); TopKSink keeps the k strongest |r| per row;
             ReductionSink/EdgeCountSink stream-reduce.
    mesh:    a jax Mesh to shard over (paper SSIII-D); shard_u row-shards
             the (symmetric) operand instead of replicating it.
    resume_from: path of a checkpointed HostSink memmap from an
             interrupted run — completed passes are skipped (the persisted
             plan spec must match this call).  Implies
             ``sink=HostSink(path=resume_from, resume=True)`` when no sink
             is given.
    pvalues: a :class:`~repro.core.significance.PermutationSpec` makes the
             run a significance workload (paper SSIV): B permuted (or
             bootstrapped) replicas of the column operand ride every pass
             as a replica grid axis, null exceedance counts reduce on
             device (never a (B, n, n) array), and the call returns
             ``(r, p)`` — the usual sink result plus p-values under the
             add-one estimator.  ``pvalues.sink`` routes the p-value tiles
             (dense by default); not supported with ``where=`` (the masked
             component GEMMs have no single observed statistic to permute).
    recovery: a :class:`~repro.runtime.faults.RetryPolicy` arms the
             self-healing executor (docs/robustness.md): transient
             failures retry in place with exponential backoff, OOM halves
             the per-pass footprint, device loss shrinks onto the
             surviving mesh and continues — results stay bit-identical to
             an uninterrupted run.  Supported for plain (non-masked,
             non-pvalues) runs, symmetric and rectangular alike: the
             coverage bitmap indexes global tile ids, so X-vs-Y grids —
             including the streaming delta passes of
             :mod:`repro.serving.live` — resume exactly like triangles.
    t / l_blk / max_tiles_per_pass / interpret / clip / fuse_epilogue /
    compute_dtype keep their ExecutionPlan semantics.
    """
    problem = PairwiseProblem.create(x, y, measure=measure, where=where)

    if resume_from is not None:
        if sink is None:
            sink = HostSink(path=resume_from, resume=True)
        elif isinstance(sink, HostSink) and sink._path == resume_from:
            sink._resume = True
        else:
            raise ValueError(
                "resume_from requires the default HostSink or a HostSink "
                "whose path matches resume_from")

    p = 1 if mesh is None else int(np.prod(mesh.devices.shape))
    replicas = 0 if pvalues is None else pvalues.iterations
    replica_chunk = None if pvalues is None else pvalues.chunk
    if recovery is not None and (problem.masked or pvalues is not None):
        raise ValueError(
            "recovery= is supported for plain runs only (masked and "
            "pvalues workloads drive their own multi-stream pass loops); "
            "run those under a FaultPlan with resume_from= restart "
            "recovery instead")
    if problem.masked:
        if pvalues is not None:
            raise ValueError(
                "pvalues= is not supported with where=: a masked run has "
                "no single observed GEMM to permute (each pair's statistic "
                "combines several component GEMMs over its common support)")
        if compute_dtype is not None:
            raise ValueError(
                "compute_dtype narrowing is not supported with where= "
                "(component GEMMs accumulate counts and sums that must "
                "stay exact f32)")
        if shard_u:
            raise ValueError("shard_u is not supported with where= (the "
                             "component GEMMs are rectangular workloads)")
        return _run_masked(problem, sink=sink, mesh=mesh, p=p, t=t,
                           l_blk=l_blk, max_tiles_per_pass=max_tiles_per_pass,
                           interpret=interpret, clip=clip)

    if problem.symmetric:
        plan = ExecutionPlan.create(
            problem.n_rows, problem.l, t=t, l_blk=l_blk,
            measure=problem.measure, p=p,
            max_tiles_per_pass=max_tiles_per_pass, interpret=interpret,
            clip=clip, fuse_epilogue=fuse_epilogue,
            compute_dtype=compute_dtype,
            replicas=replicas, replica_chunk=replica_chunk)
        # the cached-transform seam: repeat calls over the same corpus
        # array run the O(n·l) row transform exactly once (the same seam
        # serving's CorpusHandle uses — see TransformCache).  problem.x is
        # the caller's object only when they passed a jax.Array; a numpy
        # input converts to a fresh array per call and must not be cached.
        u_pad = prepared_operand(plan, problem.x, cacheable=problem.x is x)
        if pvalues is not None:
            return run_significance(plan, pvalues, u_pad, columns=problem.x,
                                    sink=sink, mesh=mesh, shard_u=shard_u)
        return execute_plan(plan, u_pad, sink=sink, mesh=mesh,
                            shard_u=shard_u, recovery=recovery)

    plan = ExecutionPlan.create(
        problem.n_rows, problem.l, n_cols=problem.n_cols, t=t, l_blk=l_blk,
        measure=problem.measure, p=p,
        max_tiles_per_pass=max_tiles_per_pass, interpret=interpret,
        clip=clip, fuse_epilogue=fuse_epilogue, compute_dtype=compute_dtype,
        replicas=replicas, replica_chunk=replica_chunk)
    u_pad = prepared_operand(plan, problem.x, cacheable=problem.x is x)
    v_pad = prepared_operand(plan, problem.y, expect_rows=problem.n_cols,
                             cacheable=problem.y is y)
    if pvalues is not None:
        return run_significance(plan, pvalues, u_pad, columns=problem.y,
                                v_pad=v_pad, sink=sink, mesh=mesh,
                                shard_u=shard_u)
    return execute_plan(plan, u_pad, v_pad, sink=sink, mesh=mesh,
                        shard_u=shard_u, recovery=recovery)


def _run_masked(problem: PairwiseProblem, *, sink, mesh, p, t, l_blk,
                max_tiles_per_pass, interpret, clip):
    """Masked execution: one engine run per component GEMM, combined
    elementwise pass-by-pass.

    Rectangular problems run every component over the full grid.
    Symmetric problems ride the *triangular* bijection for all six
    components: the cross terms are non-symmetric as matrices
    (sx = A·Mᵀ ≠ its transpose), but they come in transpose *pairs*
    (sy(i,j) = sx(j,i), qy(i,j) = qx(j,i); n and sxy are symmetric), and
    every combine formula touches them only through commutative products
    (sx·sy, qx·qy) — so the combined tile at (x_t, y_t) is exactly the
    transpose of the tile at (y_t, x_t), bit for bit, and the sink's
    standard mirror reconstructs the lower half.  That halves the GEMM
    work of every symmetric masked run (the ROADMAP's residual promised
    2x on two of six components; the triangle delivers it on all six).

    The component streams share one plan (same geometry, raw-dot measure),
    so their pass boundaries, tile ids, and clamped-slot selections line
    up exactly; zip-ing them keeps device memory at #components pass
    buffers and lets the combined tiles flow into any TileSink (run_sink:
    checkpointing included).
    """
    mm = measures.get_masked(problem.measure)
    ops_x = measures.masked_operands(problem.x, problem.mask_x)
    ops_y = (ops_x if problem.symmetric
             else measures.masked_operands(problem.y, problem.mask_y))

    plan = ExecutionPlan.create(
        problem.n_rows, problem.l,
        n_cols=None if problem.symmetric else problem.n_cols,
        t=t, l_blk=l_blk,
        measure="dot", p=p, max_tiles_per_pass=max_tiles_per_pass,
        interpret=interpret, clip=False)
    pad_x = {k: pad_operands(v, t, l_blk) for k, v in ops_x.items()}
    pad_y = (pad_x if ops_y is ops_x
             else {k: pad_operands(v, t, l_blk) for k, v in ops_y.items()})

    # The sink sees the *masked* measure's identity (name + clip), so
    # checkpoint specs distinguish masked runs, bounded results clip iff
    # requested (fused=False: combine leaves values unclipped, the sink
    # applies the clip like any unfused run), and pair-semantic sinks
    # (TopKSink/EdgeCountSink) see self-pair semantics — natively on the
    # triangular workload for symmetric problems, via symmetric_grid on
    # rectangular-shaped ones (unreachable today, kept for custom plans).
    sink_measure = measures.Measure(mm.name, measures.identity_transform,
                                    None, mm.clip)
    sink_plan = dataclasses.replace(
        plan, measure=sink_measure, fused=False, clip=clip,
        symmetric_grid=problem.symmetric and not plan.symmetric)

    def make_stream(k0, skip):
        streams = [
            _stream(plan, pad_x[MASKED_ROW[c]],
                    # identical row/col operands (sxy, n) take the
                    # single-operand path — bit-identical to the plain
                    # symmetric kernel; transpose-pair components ride the
                    # triangle as a same-shape second operand
                    v_pad=(None if pad_y is pad_x
                           and MASKED_ROW[c] == MASKED_COL[c]
                           else pad_y[MASKED_COL[c]]),
                    mesh=mesh, start_pass=k0, skip=skip)
            for c in mm.components
        ]
        for items in zip(*streams):
            k, ids, _, sel, padded = items[0]
            parts = {c: buf
                     for c, (_, _, buf, _, _) in zip(mm.components, items)}
            yield k, ids, mm.combine(parts), sel, padded

    return run_sink(sink_plan, sink, make_stream)


MASKED_ROW = {c: rk for c, (rk, _) in
              measures.MASKED_COMPONENT_OPERANDS.items()}
MASKED_COL = {c: ck for c, (_, ck) in
              measures.MASKED_COMPONENT_OPERANDS.items()}


__all__ = [
    "PairwiseProblem",
    "corr",
    "TransformCache",
    "prepared_operand",
    "prepared_cache_stats",
    "executor_stats",
    "clear_prepared_cache",
]
