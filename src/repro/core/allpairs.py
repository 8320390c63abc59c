"""Unified all-pairs executor: one plan-driven loop for every driver.

Architecture (see docs/architecture.md):

    ExecutionPlan (core/plan.py)   what to run — measure resolution,
        |                          padding, fusion, precision, pass
        v                          partitioning, per-device tile ranges;
    executor (this module)         computed once, host-side.
        |
        |  allpairs() / stream_tiles(): iterate passes with double
        |  buffering — pass k+1 is dispatched before anything blocks on
        |  pass k (paper Alg. 2's signal/wait overlap, via JAX async
        |  dispatch) — on a single device or a shard_map mesh.
        v
    TileSink (core/sinks.py)       what becomes of the tiles — dense
                                   device matrix, host/memmap assembly,
                                   or a streaming reduction.  Device
                                   memory for the output path is bounded
                                   by max_tiles_per_pass * t * t per
                                   device regardless of n.

The measure pipeline (core/measures.py) is unchanged: row_transform ->
shared triangular-grid Pallas kernel (kernels/pcc_tile.py, runtime J_start
scalar prefetch) -> elementwise epilogue fused into the kernel's final
k-step.  Every pass launches a kernel sized to the tiles it actually
covers (the final pass launches the remainder, not the padded maximum), so
at most two kernel variants compile per plan and no launch computes dummy
tiles beyond the cross-device ceil remainder of uniform shard_map ranges.

The four historical drivers — allpairs_pcc, allpairs_pcc_streamed,
allpairs_pcc_sharded, allpairs_pcc_sharded_u (core/distributed.py) — are
kept as thin wrappers over allpairs()/stream_tiles() and remain
bit-identical to their pre-refactor outputs (regression-tested in
tests/test_plan_executor.py and tests/test_distributed.py).  New code
should call allpairs() directly.
"""

from __future__ import annotations

import contextvars
import dataclasses
import functools
import threading
import warnings
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import mapping, measures, tiling
from repro.core.plan import (ExecutionPlan, pad_operands, resolve_interpret,
                             tiles_per_device)
from repro.core.quantize import Operand, operand_parts
from repro.core.sinks import (DenseSink, TileSink, place_tiles_host,
                              scatter_tiles, symmetrize)
from repro.kernels.pcc_tile import (DEFAULT_LBLK, DEFAULT_TILE, pcc_tiles,
                                    pcc_topk_tiles)
from repro.runtime import faults
from repro.runtime.tracing import span

Array = jax.Array

# Compat alias: pad_u predates the plan module; pad_operands is the same op.
pad_u = pad_operands


def prepare(x: Array, *, t: int = DEFAULT_TILE, l_blk: int = DEFAULT_LBLK,
            dtype=None,
            measure: measures.MeasureLike = "pearson",
            compute_dtype=None,
            ) -> Tuple[Array, tiling.TilePlan]:
    """Row-transform (Eq. 4 analogue for the measure) + pad.

    Compat shim over ExecutionPlan.prepare — returns (u_pad, tile_plan) as
    the historical drivers did; plan.l records the *original* sample count,
    which the measure epilogue needs (e.g. covariance's 1/(l-1)) even when
    the transform widens the sample axis (Kendall's pair expansion).

    compute_dtype narrows the *stored operands* after the transform has run
    at full (>= f32) precision — the kernel still accumulates in f32:
      - jnp.bfloat16 halves operand HBM traffic/VMEM at ~3 decimal digits
        of operand precision (tolerance-tested against the f32 oracle);
      - jnp.int8 on measures whose transform output is exactly
        integer-valued (measure.exact_int8, e.g. Kendall's +/-1 pair
        signs) is *lossless*: int8 operands accumulate exactly on the MXU
        (int32 per block), quartering operand traffic;
      - jnp.int8 / fp8 on the other measures takes the quantized path
        (core/quantize.py): per-row absmax scales travel with the operand
        as an Operand container and the kernel dequantizes finished tiles
        in VMEM (error budgets in tests/test_quantized.py).
    """
    n, l = x.shape
    eplan = ExecutionPlan.create(n, l, t=t, l_blk=l_blk, measure=measure,
                                 compute_dtype=compute_dtype)
    if dtype is not None:
        u = eplan.measure.transform(x, dtype=dtype)
        if eplan.compute_dtype is not None:
            u = u.astype(eplan.compute_dtype)
        return pad_operands(u, t, l_blk), eplan.tile
    return eplan.prepare(x), eplan.tile


# ---------------------------------------------------------------------------
# Executor counters, and the call id the trace spans of one corr() share
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {"calls": 0, "passes": 0, "mesh_programs_built": 0}
# the corr() call this thread is inside (0 outside any): per thread, since
# serving dispatches from its own thread while user threads call corr()
_CALL = contextvars.ContextVar("repro_call", default=0)


def _count(key: str) -> int:
    with _STATS_LOCK:
        _STATS[key] += 1
        return _STATS[key]


def executor_stats() -> dict:
    """Process-wide executor counts: ``calls`` (corr() calls),
    ``passes`` (pass launches dispatched) and ``mesh_programs_built``
    (shard_map pass programs the mesh executor built)."""
    with _STATS_LOCK:
        return dict(_STATS)


def current_call() -> int:
    """The id of the corr() call this thread is inside, else 0."""
    return _CALL.get()


def traced_call(fn):
    """Count each call of `fn` in ``executor_stats()["calls"]`` and run it
    under a ``repro.corr`` span whose ``call`` id every span inside it
    carries."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        call = _count("calls")
        token = _CALL.set(call)
        try:
            with span("corr", call=call):
                return fn(*args, **kwargs)
        finally:
            _CALL.reset(token)
    return run


# ---------------------------------------------------------------------------
# The executor
# ---------------------------------------------------------------------------


def launch_tiles(plan: ExecutionPlan, u, j0, launch: int, v=None,
                 grid_cols: Optional[int] = None) -> Array:
    """THE kernel-launch seam: route one pass launch to the plan's tile
    kernel.

    Unwraps quantized :class:`Operand` containers (core/quantize.py) and
    threads their per-row scales to the Pallas GEMM kernel; measures with a
    custom ``tile_kernel`` (merge-sort Kendall) dispatch to it instead,
    with the true sample count ``plan.l`` appended to the shared launch
    signature.  Every launch site — local passes, in-shard_map mesh passes
    — calls this, so kernel choice lives in exactly one place."""
    u_data, u_scale = operand_parts(u)
    v_data, v_scale = operand_parts(v) if v is not None else (None, None)
    if plan.measure.tile_kernel is not None:
        return plan.measure.tile_kernel(
            u_data, j0, t=plan.t, l_blk=plan.l_blk, pass_tiles=launch,
            interpret=plan.interpret, epilogue=plan.epilogue_spec,
            v_pad=v_data, grid_cols=grid_cols, l=plan.l)
    row_scale = col_scale = None
    if u_scale is not None:
        row_scale = u_scale
        col_scale = u_scale if v is None else v_scale
        if col_scale is None:
            raise ValueError("quantized row operand paired with an "
                             "unquantized column operand — both sides must "
                             "be prepared by the same plan")
    return pcc_tiles(u_data, j0, t=plan.t, l_blk=plan.l_blk,
                     pass_tiles=launch, interpret=plan.interpret,
                     epilogue=plan.epilogue_spec,
                     v_pad=v_data, grid_cols=grid_cols,
                     row_scale=row_scale, col_scale=col_scale)


def launch_topk_tiles(plan: ExecutionPlan, u, j0, dev_hi, launch: int,
                      kk: int, v=None, grid_cols: Optional[int] = None):
    """Launch seam of the device-side top-k epilogue
    (kernels/pcc_tile.pcc_topk_tiles): one pass's tiles are computed and
    folded into per-row top-k state entirely in VMEM, so only O(n * kk)
    state (plus, on the triangle, O(kk * t) per tile slot) crosses to the
    host.  j0 is the *raw* (unclamped) device-local global start and dev_hi
    the device's exclusive bound — the kernel's validity guard, which
    replaces the executor's clamped-slot filtering.
    """
    u_data, u_scale = operand_parts(u)
    v_data, _ = operand_parts(v) if v is not None else (None, None)
    if u_scale is not None or plan.measure.tile_kernel is not None:
        raise ValueError(
            "device top-k epilogue supports the plain GEMM kernel only "
            "(no quantized scales, no custom tile kernels) — "
            "DeviceTopKSink.open validates this")
    return pcc_topk_tiles(u_data, j0, dev_hi, t=plan.t, l_blk=plan.l_blk,
                          pass_tiles=launch, kk=kk,
                          n_cols_valid=plan.n_cols,
                          symmetric_problem=plan.symmetric_problem,
                          interpret=plan.interpret,
                          epilogue=plan.epilogue_spec,
                          v_pad=v_data, grid_cols=grid_cols)


def _with_slot_ids(state, slot_ids: np.ndarray):
    """Append the clamped tile id of every slot to a triangular top-k state
    tuple: the per-slot column-side states (kernels/pcc_tile.pcc_topk_tiles)
    name no rows of their own, and the sink places them by these ids."""
    return state if len(state) == 2 else (*state, slot_ids)


def _local_launches(plan: ExecutionPlan, u_pad: Array,
                    v_pad: Optional[Array] = None, start_pass: int = 0,
                    skip=frozenset(), state_k: Optional[int] = None):
    """Single-device pass launches: consecutive spans of the workload's
    tile-id range, each kernel sized to its actual tile count.  start_pass
    skips already-completed passes without computing them (checkpoint
    resume); `skip` drops individual later passes (coverage resume after
    an elastic repartition, where completed work is no longer a prefix).
    state_k switches the launch to the device top-k epilogue: the buffer
    becomes the kernel's per-row state tuple instead of tiles."""
    grid_cols = plan.workload.grid_cols
    sizes = plan.launch_sizes
    for k, launch in list(enumerate(sizes))[start_pass:]:
        if k in skip:
            continue
        faults.check("pass_launch")
        lo = plan.pass_offset(k)
        _count("passes")
        if state_k is not None:
            with span("launch", call=current_call(), **{"pass": k}):
                buf = launch_topk_tiles(plan, u_pad, lo, plan.total_tiles,
                                        launch, state_k, v=v_pad,
                                        grid_cols=grid_cols)
            ids = np.arange(lo, lo + launch, dtype=np.int64)
            yield k, ids, _with_slot_ids(buf, ids), None, None
            continue
        with span("launch", call=current_call(), **{"pass": k}):
            buf = launch_tiles(plan, u_pad, lo, launch, v=v_pad,
                               grid_cols=grid_cols)
        if not plan.fused and plan.measure.epilogue is not None:
            buf = plan.measure.epilogue(buf, plan.l)
        # local launches are exact-sized: every slot is valid
        yield k, np.arange(lo, lo + launch, dtype=np.int64), buf, None, None


def _mesh_launches(plan: ExecutionPlan, u_pad: Array, mesh: Mesh,
                   shard_u: bool, v_pad: Optional[Array] = None,
                   start_pass: int = 0, skip=frozenset(),
                   state_k: Optional[int] = None):
    """shard_map pass launches (paper SSIII-D): all mesh axes flatten into
    one logical PE-rank axis; device `rank` owns the contiguous tile range
    [rank*per_dev, (rank+1)*per_dev) and each pass covers at most
    max_tiles_per_pass of it — the (p*per_dev, t, t) global array is never
    materialised; each pass's sharded output is handed to the caller and
    the next pass reuses the buffers.

    With shard_u=True, U is row-sharded over the flat rank axis and
    all-gathered inside shard_map (for U too large to replicate from host;
    the gather re-runs per pass, so multi-pass shard_u trades gather
    traffic for output memory).

    Rectangular workloads (v_pad given) replicate the second operand V
    across the mesh per pass — V's tile blocks broadcast to whichever
    device owns a job in their column, exactly as U does for rows.
    shard_u stays a symmetric-workload option.
    """
    axes = tuple(mesh.axis_names)
    grid_cols = plan.workload.grid_cols
    if state_k is not None and shard_u:
        raise ValueError(
            "device top-k state does not compose with shard_u: the in-shard "
            "all_gather would re-run per pass against state-shaped outputs")
    u_data, u_scale = operand_parts(u_pad)
    v_data, v_scale = (operand_parts(v_pad) if v_pad is not None
                       else (None, None))
    if shard_u:
        if v_pad is not None:
            raise ValueError("shard_u supports the symmetric workload only "
                             "(one operand to shard); rectangular runs "
                             "replicate both operands")
        rows = u_data.shape[0]
        rows_pad = -(-rows // plan.p) * plan.p
        if rows_pad != rows:
            u_data = jnp.pad(u_data, ((0, rows_pad - rows), (0, 0)))
        in_spec = P(axes, None)
    else:
        in_spec = P(*([None] * u_data.ndim))
    u_in = jax.device_put(u_data, NamedSharding(mesh, in_spec))
    rep_spec = P(None, None)
    v_in = (None if v_data is None
            else jax.device_put(v_data, NamedSharding(mesh, rep_spec)))
    # Quantized operands: the per-row dequantization scales are tiny
    # ((n_pad,) f32), so they replicate across the mesh even under shard_u
    # — no gather needed in-shard.  Symmetric runs reuse the row scales for
    # the columns, exactly like the operand itself.
    has_s = u_scale is not None
    s_row_in = s_col_in = None
    if has_s:
        srep = NamedSharding(mesh, P(None))
        s_row_in = jax.device_put(jnp.asarray(u_scale, jnp.float32), srep)
        cs = u_scale if v_pad is None else v_scale
        if cs is None:
            raise ValueError("quantized row operand paired with an "
                             "unquantized column operand — both sides must "
                             "be prepared by the same plan")
        s_col_in = jax.device_put(jnp.asarray(cs, jnp.float32), srep)

    fns = {}

    def pass_fn(launch: int):
        if launch in fns:
            return fns[launch]

        def compute(u, v, su, sv, off: Array) -> Array:
            u_rep = u
            if shard_u:
                # Gather minor axis first so the row order reassembles
                # major-to-minor (P(("a","b")) shards rows a-major, b-minor).
                for ax in reversed(axes):
                    u_rep = jax.lax.all_gather(u_rep, ax, axis=0, tiled=True)
                u_rep = u_rep[: plan.n_pad]
            # flat rank from the (possibly multi-axis) mesh position
            rank = jnp.int32(0)
            for ax in axes:
                rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
            uu = u_rep if su is None else Operand(u_rep, su)
            vv = (None if v is None
                  else (v if sv is None else Operand(v, sv)))
            if state_k is not None:
                # the raw start and the device bound go to the kernel's
                # validity guard: clamped remainder slots compute duplicate
                # tiles (as always) but contribute no candidates, keeping
                # per-(device, pass) states disjoint
                raw = rank * plan.per_dev + off[0]
                dev_hi = jnp.minimum((rank + 1) * plan.per_dev,
                                     plan.total_tiles)
                return launch_topk_tiles(plan, uu, raw, dev_hi, launch,
                                         state_k, v=vv, grid_cols=grid_cols)
            j0 = jnp.minimum(rank * plan.per_dev + off[0],
                             plan.total_tiles - 1)
            # symmetric quantized runs: launch_tiles reuses su for the
            # columns when v is None, so sv only matters for grids
            return launch_tiles(plan, uu, j0, launch, v=vv,
                                grid_cols=grid_cols)

        def device_fn(*args) -> Array:
            it = iter(args)
            u = next(it)
            v = next(it) if v_in is not None else None
            su = next(it) if has_s else None
            sv = next(it) if has_s else None
            off = next(it)
            return compute(u, v, su, sv, off)

        specs = ((in_spec,)
                 + ((rep_spec,) if v_in is not None else ())
                 + ((P(None), P(None)) if has_s else ())
                 + (P(None),))
        if state_k is not None:
            # 2 state stacks for grids, 4 (row + per-slot col) for triangles
            n_out = 4 if grid_cols is None else 2
            out_spec = tuple(P(axes) for _ in range(n_out))
        else:
            out_spec = P(axes)
        fns[launch] = shard_map(device_fn, mesh=mesh, in_specs=specs,
                                out_specs=out_spec, check_vma=False)
        _count("mesh_programs_built")
        return fns[launch]

    for k, launch in list(enumerate(plan.launch_sizes))[start_pass:]:
        if k in skip:
            continue
        faults.check("pass_launch")
        off = jnp.full((1,), plan.pass_offset(k), jnp.int32)
        args = ((u_in,)
                + ((v_in,) if v_in is not None else ())
                + ((s_row_in, s_col_in) if has_s else ())
                + (off,))
        _count("passes")
        # spans the shard_map's trace, lowering and cache load too
        with span("launch", call=current_call(), **{"pass": k}):
            buf = pass_fn(launch)(*args)
        if state_k is not None:
            # state stacks carry their own validity guard: no clamped-slot
            # selection to resolve, and ids are the pass's true tile set
            yield (k, plan.pass_selection(k)[0],
                   _with_slot_ids(buf, plan.pass_padded_ids(k)), None, None)
            continue
        if not plan.fused and plan.measure.epilogue is not None:
            buf = plan.measure.epilogue(buf, plan.l)
        # The raw sharded buffer is handed on as-is: clamped tail-device
        # slots (sel is not None) are resolved by the sink — either a
        # clamped-id scatter or a host-side filter, never a device gather
        # (which would undo the per-device pass-memory bound).
        ids, sel = plan.pass_selection(k)
        padded = plan.pass_padded_ids(k) if sel is not None else None
        yield k, ids, buf, sel, padded


def _stream(plan: ExecutionPlan, u_pad: Array, *, mesh: Optional[Mesh] = None,
            shard_u: bool = False, v_pad: Optional[Array] = None,
            start_pass: int = 0, skip=frozenset(),
            state_k: Optional[int] = None):
    """Double-buffered pass stream of (k, ids, raw_buffer, sel, padded_ids):
    pulls (and thus async-dispatches) pass k+1 before yielding pass k, so a
    sink that blocks on host transfer overlaps the device's next pass
    (paper Alg. 2 signal/wait).  sel/padded_ids are None except on mesh
    passes with clamped tail-device slots (see TileSink.consume_clamped).
    v_pad supplies the second operand of rectangular workloads; start_pass
    resumes mid-run and `skip` drops individual later passes (coverage
    resume) — neither is ever dispatched."""
    launches = (_local_launches(plan, u_pad, v_pad, start_pass, skip,
                                state_k)
                if mesh is None
                else _mesh_launches(plan, u_pad, mesh, shard_u, v_pad,
                                    start_pass, skip, state_k))
    pending = None
    for item in launches:
        if pending is not None:
            yield pending
        pending = item
    if pending is not None:
        yield pending


def run_sink(plan: ExecutionPlan, sink: Optional[TileSink], make_stream):
    """The one sink-driving loop behind every entry point: open the sink,
    recover its resume schedule, drain the (k, ids, buf, sel, padded)
    stream that `make_stream(start_pass, skip)` builds, committing each
    pass.

    Sinks that persist progress (HostSink with a memmap path) report a
    resume point via ``resume_pass()`` plus a ``skip_passes()`` set —
    completed passes are never dispatched — and ``pass_complete(k)``
    commits each pass as it lands.  getattr-with-default keeps duck-typed
    sinks written against the PR-3 contract (open/consume/result only)
    working unchanged."""
    snk = sink if sink is not None else DenseSink()
    snk.open(plan)
    k0 = getattr(snk, "resume_pass", lambda: 0)()
    skip = getattr(snk, "skip_passes", set)()
    pass_complete = getattr(snk, "pass_complete", lambda k: None)
    for k, ids, buf, sel, padded in make_stream(k0, frozenset(skip)):
        if sel is None:
            snk.consume(ids, buf)
        else:
            snk.consume_clamped(padded, sel, ids, buf)
        pass_complete(k)
    return snk.result()


def execute_plan(plan: ExecutionPlan, u_pad: Array,
                 v_pad: Optional[Array] = None, *,
                 sink: Optional[TileSink] = None,
                 mesh: Optional[Mesh] = None,
                 shard_u: bool = False,
                 recovery: Optional[faults.RetryPolicy] = None):
    """Run a prepared plan end to end: stream every remaining pass into
    the sink and finalise (see run_sink for the resume/commit protocol).

    recovery=RetryPolicy() arms the self-healing loop: transient failures
    retry in place with exponential backoff, OOM halves the per-pass
    footprint, and device loss shrinks onto the surviving mesh and
    continues — resuming from the tiles already consumed/checkpointed,
    bit-identical to an uninterrupted run (see _execute_recovering)."""
    if recovery is not None:
        return _execute_recovering(plan, u_pad, v_pad, sink=sink, mesh=mesh,
                                   shard_u=shard_u, policy=recovery)
    state_k = _sink_state_k(sink)
    return run_sink(
        plan, sink,
        lambda k0, skip: _stream(plan, u_pad, v_pad=v_pad, mesh=mesh,
                                 shard_u=shard_u, start_pass=k0, skip=skip,
                                 state_k=state_k))


def _sink_state_k(sink: Optional[TileSink]) -> Optional[int]:
    """State capacity for sinks that want the device top-k stream
    (core/sinks.DeviceTopKSink), else None (the tile stream)."""
    if sink is not None and getattr(sink, "wants_device_state", False):
        return int(sink.k)
    return None


def _default_shrink(mesh: Optional[Mesh], plan: ExecutionPlan,
                    exc: BaseException):
    """Default device-loss resolution: drop one device, flatten the
    survivors into a 1-D mesh, repartition the plan (runtime/elastic)."""
    from repro.runtime import elastic  # lazy: elastic imports core.plan

    if mesh is None:
        raise exc  # local run: no mesh to shrink
    new_mesh = elastic.shrink_mesh(mesh)
    new_p = 1 if new_mesh is None else int(np.prod(new_mesh.devices.shape))
    return new_mesh, elastic.replan_execution(plan, new_p)


def _execute_recovering(plan: ExecutionPlan, u_pad: Array,
                        v_pad: Optional[Array], *, sink: Optional[TileSink],
                        mesh: Optional[Mesh], shard_u: bool,
                        policy: faults.RetryPolicy):
    """The self-healing executor loop.

    Progress is tracked as a host-side coverage bitmap over *global tile
    ids* — not pass indices — seeded from the sink's recovered coverage.
    Each attempt re-derives the pass schedule from coverage
    (plan.coverage_schedule), streams the remaining passes, and filters
    already-covered ids out of consume() host-side: sinks whose merge is
    not idempotent under duplicates (TopKSink candidates, EdgeCountSink
    tallies) stay correct even when a retried or repartitioned pass
    overlaps tiles that already landed.

    Failure handling per classify_failure:
      transient    retry in place; exponential backoff; the retry budget
                   refills whenever a pass lands (forward progress)
      oom          halve max_tiles_per_pass (>= 1) and retry
      device_loss  policy.on_device_loss (default: drop one device via
                   runtime/elastic, repartition) then continue on the
                   surviving mesh; the sink rebinds so durable sidecars
                   immediately carry the new spec
      crash/fatal  propagate — simulated process death is recovered by
                   restart + resume_from, never in-process
    """
    snk = sink if sink is not None else DenseSink()
    snk.open(plan)
    covered = getattr(snk, "covered", lambda: None)()
    if covered is None or np.shape(covered) != (plan.total_tiles,):
        covered = np.zeros(plan.total_tiles, bool)
    else:
        covered = np.asarray(covered, bool).copy()
    pass_complete = getattr(snk, "pass_complete", lambda k: None)
    state_k = _sink_state_k(snk)
    merge_dedups = getattr(snk, "merge_dedups", False)
    failures = 0
    while not covered.all():
        k0, skip = plan.coverage_schedule(covered)
        if k0 >= plan.n_pass:
            break
        try:
            stream = _stream(plan, u_pad, v_pad=v_pad, mesh=mesh,
                             shard_u=shard_u, start_pass=k0,
                             skip=frozenset(skip), state_k=state_k)
            for k, ids, buf, sel, padded in stream:
                ids = np.asarray(ids)
                fresh = ~covered[ids]
                if merge_dedups:
                    # state-shaped buffers cannot be subset by tile id; the
                    # sink's canonical merge drops the exact duplicates a
                    # retried pass re-delivers (topk_merge_rows dedup=True)
                    if fresh.any():
                        snk.consume(ids, buf)
                elif sel is None:
                    if fresh.all():
                        snk.consume(ids, buf)
                    elif fresh.any():
                        snk.consume(ids[fresh], np.asarray(buf)[fresh])
                else:
                    if fresh.all():
                        snk.consume_clamped(padded, sel, ids, buf)
                    elif fresh.any():
                        # host-side filter down to the missing tiles — the
                        # same memory-bound resolution consume_clamped uses
                        snk.consume(ids[fresh],
                                    np.asarray(buf)[np.asarray(sel)[fresh]])
                covered[ids] = True
                pass_complete(k)
                failures = 0  # forward progress refills the retry budget
        except BaseException as exc:
            kind = faults.classify_failure(exc)
            if kind == "transient":
                failures += 1
                if failures > policy.max_retries:
                    policy.log.append({"kind": kind, "action": "give_up",
                                       "attempt": failures})
                    raise
                policy.log.append({"kind": kind, "action": "retry",
                                   "attempt": failures, "error": str(exc)})
                policy.sleep(policy.backoff(failures - 1))
                continue
            if kind == "oom" and policy.shrink_pass_on_oom:
                if plan.max_tiles_per_pass <= 1:
                    policy.log.append({"kind": kind, "action": "give_up",
                                       "max_tiles_per_pass": 1})
                    raise
                plan = dataclasses.replace(
                    plan,
                    max_tiles_per_pass=max(1, plan.max_tiles_per_pass // 2))
                policy.log.append(
                    {"kind": kind, "action": "shrink_pass",
                     "max_tiles_per_pass": plan.max_tiles_per_pass})
                getattr(snk, "rebind", lambda _p: None)(plan)
                continue
            if kind == "device_loss" and policy.shrink_on_device_loss:
                resolver = policy.on_device_loss or _default_shrink
                mesh, plan = resolver(mesh, plan, exc)
                new_p = (1 if mesh is None
                         else int(np.prod(mesh.devices.shape)))
                policy.log.append({"kind": kind, "action": "shrink_mesh",
                                   "p": new_p, "error": str(exc)})
                getattr(snk, "rebind", lambda _p: None)(plan)
                continue
            policy.log.append({"kind": kind, "action": "raise",
                               "error": str(exc)})
            raise
    return snk.result()


def stream_tiles(
    x: Array,
    *,
    t: int = DEFAULT_TILE,
    l_blk: int = DEFAULT_LBLK,
    measure: measures.MeasureLike = "pearson",
    mesh: Optional[Mesh] = None,
    shard_u: bool = False,
    max_tiles_per_pass: Optional[int] = None,
    interpret: Optional[bool] = None,
    clip: bool = True,
    fuse_epilogue: bool = True,
    compute_dtype=None,
    plan: Optional[ExecutionPlan] = None,
) -> Iterator[Tuple[np.ndarray, Array]]:
    """Yield (tile_ids, tiles) per pass as (host ids, device buffer) —
    the raw executor stream that every sink (and the legacy streamed
    driver) consumes.  Tiles carry the measure epilogue (fused in-kernel by
    default); ids are unique, valid, and in pass order.  On mesh passes
    with clamped tail-device slots the valid tiles are filtered host-side
    (numpy) to preserve the per-device memory bound — otherwise the buffer
    is the kernel's device output.  Pass `plan=` to reuse a prebuilt
    ExecutionPlan (its geometry must match x)."""
    p = 1 if mesh is None else int(np.prod(mesh.devices.shape))
    if plan is None:
        plan = ExecutionPlan.create(
            x.shape[0], x.shape[1], t=t, l_blk=l_blk, measure=measure, p=p,
            max_tiles_per_pass=max_tiles_per_pass, interpret=interpret,
            clip=clip, fuse_epilogue=fuse_epilogue,
            compute_dtype=compute_dtype)
    else:
        # An explicit plan wins over the per-call kwargs; refuse obviously
        # conflicting ones rather than silently computing with the plan's
        # settings (default-valued kwargs cannot be told apart from unset,
        # so only non-default conflicts are detectable).
        if plan.p != p:
            raise ValueError(f"plan.p={plan.p} does not match mesh size {p}")
        if t != DEFAULT_TILE and t != plan.t:
            raise ValueError(f"t={t} conflicts with plan.t={plan.t}")
        if l_blk != DEFAULT_LBLK and l_blk != plan.l_blk:
            raise ValueError(
                f"l_blk={l_blk} conflicts with plan.l_blk={plan.l_blk}")
        req = measures.get(measure)
        resolved = measures.resolve_tile_kernel(
            req, l=plan.l, compute_dtype=plan.compute_dtype,
            replicas=plan.replicas)
        if (measure != "pearson" and req is not plan.measure
                and resolved is not plan.measure):
            raise ValueError(
                f"measure={req.name!r} conflicts with "
                f"plan.measure={plan.measure.name!r}")
    for _k, ids, buf, sel, _padded in _stream(plan, plan.prepare(x),
                                              mesh=mesh, shard_u=shard_u):
        yield ids, (buf if sel is None else np.asarray(buf)[sel])


def allpairs(
    x: Array,
    *,
    measure: measures.MeasureLike = "pearson",
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
):
    """All-pairs similarity: plan -> executor -> sink, on one device or a
    mesh.  Since the workload facade (core/api.py) this is the *symmetric
    spelling* of ``corr(x, ...)`` — bit-identical delegation; new code
    should call ``corr`` directly (it also serves rectangular X-vs-Y and
    masked workloads).

    measure: any registered measure name or Measure instance.
    sink:    output handling (core/sinks.py) — default DenseSink returns
             the (n, n) device matrix; HostSink assembles out-of-core to
             host/memmap; ReductionSink/EdgeCountSink stream-reduce without
             materialising the matrix.  Device output memory is bounded by
             max_tiles_per_pass * t * t per device for every sink.
    mesh:    a jax Mesh to shard over (paper SSIII-D).  All axes flatten
             into one logical rank axis; device i owns the contiguous tile
             range [i*ceil(T/p), (i+1)*ceil(T/p)).
    shard_u: row-shard U over the mesh and all-gather it in-kernel instead
             of replicating (for U beyond a single device's memory).
    max_tiles_per_pass: per-device pass bound (C4); None = one pass.
    interpret: None infers from the backend (compiled Pallas on TPU,
             interpret elsewhere); fuse_epilogue / compute_dtype as in
             prepare().
    """
    from repro.core.api import corr  # lazy: api builds on this module
    return corr(x, measure=measure, sink=sink, mesh=mesh, shard_u=shard_u,
                t=t, l_blk=l_blk, max_tiles_per_pass=max_tiles_per_pass,
                interpret=interpret, clip=clip, fuse_epilogue=fuse_epilogue,
                compute_dtype=compute_dtype)


# ---------------------------------------------------------------------------
# Legacy drivers: thin wrappers, kept bit-identical (deprecated entry points)
# ---------------------------------------------------------------------------


def warn_deprecated_driver(name: str, replacement: str) -> None:
    """One DeprecationWarning per legacy-driver call, naming corr().

    stacklevel=3 points at the *user's* call site (user -> wrapper ->
    here).  Shared by the tiled/streamed wrappers and the sharded drivers
    (core/distributed.py) so the wording, category, and count (exactly one
    per call — the wrapped corr()/stream_tiles() path never warns again)
    stay uniform and testable."""
    warnings.warn(
        f"{name} is deprecated; use repro.core.api.corr({replacement}) — "
        f"outputs are bit-identical through the unified executor",
        DeprecationWarning, stacklevel=3)


def allpairs_pcc(
    x: Array,
    *,
    t: int = DEFAULT_TILE,
    l_blk: int = DEFAULT_LBLK,
    max_tiles_per_pass: Optional[int] = None,
    interpret: Optional[bool] = None,
    clip: bool = True,
    measure: measures.MeasureLike = "pearson",
    fuse_epilogue: bool = True,
    compute_dtype=None,
) -> Array:
    """All-pairs similarity via the triangular-grid Pallas kernel.
    Returns the (n, n) similarity matrix (R for the default Pearson).

    Deprecated spelling of ``corr(x, ...)`` (kept for history/paper
    fidelity; bit-identical through the unified executor).
    """
    warn_deprecated_driver("allpairs_pcc", "x, measure=...")
    return allpairs(x, measure=measure, t=t, l_blk=l_blk,
                    max_tiles_per_pass=max_tiles_per_pass,
                    interpret=interpret, clip=clip,
                    fuse_epilogue=fuse_epilogue, compute_dtype=compute_dtype)


def allpairs_pcc_streamed(
    x: Array,
    *,
    t: int = DEFAULT_TILE,
    l_blk: int = DEFAULT_LBLK,
    max_tiles_per_pass: int = 1024,
    interpret: Optional[bool] = None,
    measure: measures.MeasureLike = "pearson",
    fuse_epilogue: bool = True,
    compute_dtype=None,
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    """Memory-bounded streaming variant (paper Alg. 2 with double buffering).

    Deprecated spelling of ``stream_tiles(x, ...)`` with host conversion:
    yields (tile_ids, tiles) per pass as *host* numpy arrays, while the
    next pass is already dispatched on device (async dispatch =
    signal/wait).  The caller assembles (or reduces) the stream — new code
    should pass a TileSink to ``corr`` instead.
    """
    warn_deprecated_driver("allpairs_pcc_streamed", "x, sink=HostSink(...)")
    for ids, buf in stream_tiles(
            x, t=t, l_blk=l_blk, measure=measure,
            max_tiles_per_pass=max_tiles_per_pass, interpret=interpret,
            fuse_epilogue=fuse_epilogue, compute_dtype=compute_dtype):
        yield ids, np.asarray(buf)  # blocks on this pass; next is in flight


def assemble_from_stream(n: int, t: int, m: int,
                         stream: Iterator[Tuple[np.ndarray, np.ndarray]],
                         out: Optional[np.ndarray] = None,
                         measure: measures.MeasureLike = "pearson",
                         ) -> np.ndarray:
    """Assemble a streamed tile sequence into a full symmetric host matrix.

    The stream's tiles already carry the measure epilogue; assembly only
    mirrors and (for bounded measures) clips.  Each chunk's tile-id batch is
    inverted to coordinates in one vectorised call (job_coord_batch) and
    placed with one fancy-index scatter — no per-tile Python loop.  (The
    sink-based spelling is ``allpairs(x, sink=HostSink(...))``, which fuses
    streaming and assembly.)

    CAUTION: `measure` must match the one the stream was produced with —
    the stream itself is just arrays and cannot be checked.  The default
    assumes Pearson; assembling a non-Pearson stream without repeating
    `measure=` applies Pearson's [-1, 1] clip, silently truncating
    unbounded measures such as covariance.
    """
    meas = measures.get(measure)
    n_pad = m * t
    r = out if out is not None else np.zeros((n_pad, n_pad), np.float32)
    for ids, tiles in stream:
        ys, xs = mapping.job_coord_batch(m, np.asarray(ids))
        place_tiles_host(r, np.asarray(tiles), ys, xs, t)
    r = r[:n, :n]
    if meas.clip is not None:
        np.clip(r, meas.clip[0], meas.clip[1], out=r)
    return r


# Measure-agnostic aliases: the `_pcc` names are kept for history/paper
# fidelity, but the drivers serve every registered measure.
allpairs_similarity = allpairs_pcc
allpairs_similarity_streamed = allpairs_pcc_streamed

__all__ = [
    "allpairs",
    "execute_plan",
    "executor_stats",
    "launch_tiles",
    "launch_topk_tiles",
    "run_sink",
    "stream_tiles",
    "prepare",
    "pad_u",
    "pad_operands",
    "resolve_interpret",
    "tiles_per_device",
    "scatter_tiles",
    "place_tiles_host",
    "symmetrize",
    "allpairs_pcc",
    "allpairs_pcc_streamed",
    "allpairs_similarity",
    "allpairs_similarity_streamed",
    "assemble_from_stream",
]
