"""Permutation/bootstrap significance as a first-class engine workload.

The paper motivates LightPCC with permutation testing (SSIV: >= 1000
iterations per dataset) — all-pairs correlation is usually computed *to
ask which pairs are real*.  This module runs that question through the
plan/executor/sink core instead of the legacy dense batched-GEMM path
(core/permutation.py, now a thin wrapper over this module):

    r, p = corr(x, pvalues=PermutationSpec(iterations=1000, key=0))

Replica axis.  Iteration b applies a random sample reordering pi_b to the
*column* operand; R_b = U @ pi_b(V)^T is then a plain all-pairs workload
over the same row operand.  Rather than one launch per iteration, the
stacked (R, cols_pad, l_pad) replica operand rides the existing Pallas
tile kernel as a leading grid axis (kernels/pcc_tile.py `replica` mode):
one launch per pass covers a whole replica chunk, for both bijection
families (triangle and rectangular grid) and on a shard_map mesh, where
replicas ride the per-pass device ranges unchanged.

Replica operands.  Measures whose row transform commutes with sample
permutation (Measure.permute_gather — mean/norm/ranks are permutation-
invariant) build replicas by *gathering columns of the already-prepared
operand*: no per-replica re-transform, and bit-identical to the legacy
path, which permuted U.  Everything else — bootstrap resampling always,
and transforms that widen the sample axis (Kendall's pair expansion) —
routes through the always-correct re-transform of the permuted raw data.

Exceedance semantics.  p(i, j) = (1 + #{b : |R_b| >= |R|}) / (1 + B), the
add-one estimator.  Both sides of the comparison are *finalised* values
(epilogue + the bounded-measure clip), which for every built-in measure
matches the legacy comparison bit-for-bit: the epilogue is a shared
positive scale, and clipping both sides of `>=` at the same bound cannot
change the outcome.  Counts accumulate *on device* per pass — an int32
buffer of O(pass tiles), sharded across the mesh, never a (B, n, n)
array — and stream through an ExceedanceSink (core/sinks.py) into any
inner TileSink (dense, host/memmap checkpointed, top-k).

Memory model.  Peak device memory beyond the operands is one pass's
observed tiles + counts (max_tiles_per_pass * t * t) plus one replica
chunk's stacked operand and output (replica_chunk * (operand + pass
tiles)).  `PermutationSpec.chunk` is a pure memory knob: one key is
derived per *iteration* up front (jax.random.split(key, B)) and chunks
slice that sequence, so p-values are invariant to chunk — and to the
pass split — by construction.  Multi-pass runs rebuild each chunk's
replica stack per pass (gathers are cheap; the serving layer caches the
stacks as corpus null state instead — serving/corpus.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import measures, quantize
from repro.core.plan import ExecutionPlan, needs_row_scales
from repro.core.quantize import Operand, operand_parts
from repro.core.sinks import DenseSink, ExceedanceSink, TileSink
from repro.kernels.pcc_tile import pcc_tiles
from repro.runtime import faults

Array = jax.Array
KeyLike = Union[int, Array]

METHODS = ("permute", "bootstrap")


def canonical_key(key: KeyLike) -> Array:
    """Accept an int seed or a PRNG key array; return a PRNG key."""
    if isinstance(key, (int, np.integer)):
        return jax.random.PRNGKey(int(key))
    return key


def key_fingerprint(key: KeyLike) -> str:
    """Short stable digest of a PRNG key — embedded in the p-value plan's
    pseudo-measure name so checkpoint specs (HostSink sidecars) and serving
    null-state caches distinguish different null distributions."""
    k = canonical_key(key)
    try:
        data = np.asarray(jax.random.key_data(k))
    except (AttributeError, TypeError):
        data = np.asarray(k)
    return hashlib.sha1(data.tobytes()).hexdigest()[:12]


@dataclasses.dataclass(frozen=True, eq=False)
class PermutationSpec:
    """What null distribution to test against (corr(pvalues=...)).

    iterations: number of null replicas B (paper SSIV: >= 1000 for real
                inference; the add-one estimator floors p at 1/(B+1)).
    key:        PRNG key or int seed — REQUIRED.  The legacy API's silent
                PRNGKey(0) default meant repeated "independent" runs drew
                identical permutations; here independence is explicit.
    method:     "permute" draws a sample permutation per iteration (exact
                null: samples exchangeable under H0); "bootstrap" draws a
                with-replacement resample (bootstrap null; always routes
                through the re-transform path, since resampling changes
                per-row statistics).
    chunk:      replicas per kernel launch — a pure device-memory knob
                (default plan.DEFAULT_REPLICA_CHUNK).  P-values are
                invariant to it: one key per iteration is derived up
                front and chunks slice the sequence.
    sink:       optional inner TileSink receiving the finished p-value
                tiles (wrapped in an ExceedanceSink) — HostSink for
                out-of-core/checkpointed p-values, TopKSink, etc.
                Default assembles a dense device matrix.
    """

    iterations: int
    key: Optional[KeyLike] = None
    method: str = "permute"
    chunk: Optional[int] = None
    sink: Optional[TileSink] = None

    def __post_init__(self):
        if self.iterations <= 0:
            raise ValueError(
                f"iterations must be positive, got {self.iterations}")
        if self.key is None:
            raise ValueError(
                "PermutationSpec requires an explicit key: the legacy "
                "default silently reused the fixed seed PRNGKey(0), making "
                "repeated 'independent' runs draw identical null "
                "permutations.  Pass key=<int seed> or a jax PRNG key.")
        if self.method not in METHODS:
            raise ValueError(
                f"method must be one of {METHODS}, got {self.method!r}")
        if self.chunk is not None and self.chunk <= 0:
            raise ValueError(f"chunk must be positive, got {self.chunk}")


def iteration_keys(spec: PermutationSpec) -> Array:
    """One PRNG key per iteration, independent of chunking — THE fix for
    the legacy chunk-dependence bug (keys were split per chunk-step, so
    the same seed yielded different permutations under a different chunk
    size).  Chunks slice this sequence."""
    return jax.random.split(canonical_key(spec.key), spec.iterations)


def pvalue_measure(plan: ExecutionPlan, spec: PermutationSpec) -> measures.Measure:
    """Identity pseudo-measure naming the p-value output's full identity
    (base measure, method, B, key) — the p-plan's `measure`, so HostSink
    checkpoint specs can never confuse a p-value memmap with an r memmap
    or two different null distributions with each other."""
    name = (f"{plan.measure.name}:pvalues:{spec.method}:"
            f"B{spec.iterations}:{key_fingerprint(spec.key)}")
    return measures.Measure(name, measures.identity_transform, None, None)


def replica_operand(plan: ExecutionPlan, keys: Array, *, method: str,
                    columns: Array, cols_prepared) -> Array:
    """Stacked column-operand variants for one replica chunk:
    (len(keys), cols_pad, l_pad) — an Operand carrying (len(keys),
    cols_pad) per-row scales when the plan quantizes its operands.

    Gather path (method == "permute" and measure.permute_gather): each
    replica gathers sample-columns of the already-prepared operand —
    transform(x[:, pi]) == transform(x)[:, pi] for these measures, so this
    skips the per-replica transform and bit-matches the legacy path (which
    permuted U).  Padding columns stay in place, so zero padding is
    preserved.  For quantized operands the per-row absmax is permutation-
    invariant, so the gather permutes the *quantized codes* and broadcasts
    the one prepared scale vector across the replica axis — every replica
    dequantizes bit-identically to the observed operand.  Everything else
    re-transforms the reordered raw data (`columns`), which is correct for
    any measure; quantized plans re-quantize each replica after its
    transform (bootstrap resamples change per-row absmax).
    """
    l = plan.l
    cols_data, cols_scale = operand_parts(cols_prepared)
    cols_pad, l_pad = cols_data.shape
    if method == "permute" and plan.measure.permute_gather:
        tail = jnp.arange(l, l_pad, dtype=jnp.int32)

        def one(k):
            idx = jax.random.permutation(k, l)
            if l_pad > l:
                idx = jnp.concatenate([idx.astype(jnp.int32), tail])
            return jnp.take(cols_data, idx, axis=1)

        stack = jax.vmap(one)(keys)
        if cols_scale is None:
            return stack
        scales = jnp.broadcast_to(cols_scale[None], (keys.shape[0], cols_pad))
        return Operand(stack, scales)

    quantized = needs_row_scales(plan.measure, plan.compute_dtype)

    def one(k):
        if method == "bootstrap":
            idx = jax.random.randint(k, (l,), 0, l)
        else:
            idx = jax.random.permutation(k, l)
        ub = plan.measure.transform(jnp.take(columns, idx, axis=1),
                                    dtype=jnp.float32)
        if quantized:
            return quantize.quantize_rows(ub, plan.compute_dtype)
        if plan.compute_dtype is not None:
            ub = ub.astype(plan.compute_dtype)
        return ub, None

    stack, scales = jax.vmap(one)(keys)
    pad_r = cols_pad - stack.shape[1]
    pad_l = l_pad - stack.shape[2]
    if pad_r or pad_l:
        stack = jnp.pad(stack, ((0, 0), (0, pad_r), (0, pad_l)))
    if not quantized:
        return stack
    if pad_r:
        scales = jnp.pad(scales, ((0, 0), (0, pad_r)))
    return Operand(stack, scales)


def _cmp_vals(plan: ExecutionPlan, raw):
    """|finalised| values for the exceedance comparison: epilogue + the
    bounded-measure clip applied to the raw accumulator.  Clipping *both*
    sides of >= at the same bound never changes the outcome, which keeps
    the count bit-identical to the legacy raw-replica-vs-clipped-observed
    comparison for Pearson."""
    return jnp.abs(plan.measure.finalize(raw, plan.l, clip=plan.clip))


def _obs_tiles(plan: ExecutionPlan, raw):
    """Reconstruct the executor stream's observed-tile buffer from the raw
    accumulator — bit-identical to what _local/_mesh_launches yield: the
    fused kernel applies EpilogueSpec.apply to the same VMEM accumulator
    the raw launch writes to HBM, and the unfused stream applies the
    measure epilogue on the pass buffer (clip deferred to the sink)."""
    if plan.fused:
        if plan.epilogue_spec is None or plan.epilogue_spec.is_identity():
            return raw
        return plan.epilogue_spec.apply(raw)
    if plan.measure.epilogue is not None:
        return plan.measure.epilogue(raw, plan.l)
    return raw


def run_significance(
    plan: ExecutionPlan,
    spec: PermutationSpec,
    u_pad: Array,
    *,
    columns: Array,
    v_pad: Optional[Array] = None,
    sink: Optional[TileSink] = None,
    mesh: Optional[Mesh] = None,
    shard_u: bool = False,
    replica_source: Optional[Callable[[int, Array], Array]] = None,
):
    """Execute a significance plan end to end; returns (r, p) results.

    plan must carry the replica axis (ExecutionPlan.create(replicas=B,
    replica_chunk=...)); u_pad is the prepared row operand, v_pad the
    prepared column operand of rectangular workloads (None = symmetric:
    replicas permute U itself).  `columns` is the *raw* column-side data,
    needed by the re-transform replica path.  `sink` receives the observed
    r tiles (default DenseSink); spec.sink receives the p-value tiles
    through an ExceedanceSink.  replica_source overrides chunk-stack
    construction — the serving layer's null-state cache seam: called as
    replica_source(chunk_index, keys_slice), must return what
    replica_operand would.

    Both output legs resume independently (HostSink checkpoints): passes
    below a sink's resume point are recomputed only if the *other* sink
    still needs them, and each leg's pass_complete commits separately.
    """
    if plan.replicas != spec.iterations:
        raise ValueError(
            f"plan.replicas={plan.replicas} does not match "
            f"spec.iterations={spec.iterations} — build the plan with "
            f"ExecutionPlan.create(replicas=spec.iterations, ...)")
    keys = iteration_keys(spec)
    cols_prepared = u_pad if v_pad is None else v_pad
    u_data, u_scale = operand_parts(u_pad)
    v_data, v_scale = (operand_parts(v_pad) if v_pad is not None
                       else (None, None))
    cs_obs = u_scale if v_pad is None else v_scale
    if (u_scale is None) != (cs_obs is None):
        raise ValueError("quantized row operand paired with an unquantized "
                         "column operand — both sides must be prepared by "
                         "the same plan")

    def rep_parts(reps):
        rep_data, rep_scale = operand_parts(reps)
        if (u_scale is None) != (rep_scale is None):
            raise ValueError(
                "replica stack quantization does not match the row operand "
                "— a replica_source override must return an Operand with "
                "(R, cols_pad) scales exactly when the plan quantizes its "
                "operands (plan.compute_dtype="
                f"{plan.compute_dtype}), got scales="
                f"{'present' if rep_scale is not None else 'absent'}")
        return rep_data, rep_scale

    grid_cols = plan.workload.grid_cols
    rchunks = plan.replica_chunk_sizes

    if replica_source is None:
        def replica_source(ci: int, keys_c: Array) -> Array:
            del ci
            return replica_operand(plan, keys_c, method=spec.method,
                                   columns=columns,
                                   cols_prepared=cols_prepared)

    def chunk_slices():
        lo = 0
        for ci, rc in enumerate(rchunks):
            yield ci, rc, keys[lo:lo + rc]
            lo += rc

    r_sink = sink if sink is not None else DenseSink()
    r_sink.open(plan)
    p_plan = dataclasses.replace(plan, measure=pvalue_measure(plan, spec),
                                 fused=False, clip=False, epilogue_spec=None)
    p_sink = ExceedanceSink(inner=spec.sink)
    p_sink.open(p_plan)
    k0_r = getattr(r_sink, "resume_pass", lambda: 0)()
    k0_p = getattr(p_sink, "resume_pass", lambda: 0)()
    skip_r = getattr(r_sink, "skip_passes", set)()
    skip_p = getattr(p_sink, "skip_passes", set)()
    k0 = min(k0_r, k0_p)
    r_done = getattr(r_sink, "pass_complete", lambda k: None)
    p_done = getattr(p_sink, "pass_complete", lambda k: None)

    def need_r(k: int) -> bool:
        return k >= k0_r and k not in skip_r

    def need_p(k: int) -> bool:
        return k >= k0_p and k not in skip_p

    if mesh is None:
        for k in range(k0, plan.n_pass):
            if not (need_r(k) or need_p(k)):
                continue
            faults.check("pass_launch")
            launch = plan.launch_sizes[k]
            j0 = plan.pass_offset(k)
            raw = pcc_tiles(u_data, j0, t=plan.t, l_blk=plan.l_blk,
                            pass_tiles=launch, interpret=plan.interpret,
                            epilogue=None, v_pad=v_data, grid_cols=grid_cols,
                            row_scale=u_scale, col_scale=cs_obs)
            ids = np.arange(j0, j0 + launch, dtype=np.int64)
            if need_r(k):
                r_sink.consume(ids, _obs_tiles(plan, raw))
                r_done(k)
            if need_p(k):
                abs_obs = _cmp_vals(plan, raw)
                counts = jnp.zeros(raw.shape, jnp.int32)
                for ci, rc, keys_c in chunk_slices():
                    rep_data, rep_scale = rep_parts(replica_source(ci, keys_c))
                    rep_raw = pcc_tiles(u_data, j0, t=plan.t, l_blk=plan.l_blk,
                                        pass_tiles=launch,
                                        interpret=plan.interpret,
                                        epilogue=None, v_pad=rep_data,
                                        grid_cols=grid_cols,
                                        row_scale=u_scale,
                                        col_scale=rep_scale)
                    hits = _cmp_vals(plan, rep_raw) >= abs_obs[None]
                    counts = counts + jnp.sum(hits.astype(jnp.int32), axis=0)
                p_sink.consume(ids, counts)
                p_done(k)
        return r_sink.result(), p_sink.result()

    # -- mesh execution: replicas ride the per-pass shard_map unchanged ------
    axes = tuple(mesh.axis_names)
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
        in_spec = P(None, None)
    u_in = jax.device_put(u_data, NamedSharding(mesh, in_spec))
    rep_spec = P(None, None, None)
    rep_shard = NamedSharding(mesh, rep_spec)
    v_in = (None if v_data is None
            else jax.device_put(v_data, NamedSharding(mesh, P(None, None))))
    # Quantized operands: the dequantization scales are tiny f32 vectors
    # ((n_pad,) per side, (R, cols_pad) per replica chunk), so they
    # replicate across the mesh even under shard_u — no gather in-shard.
    has_s = u_scale is not None
    s_row_in = s_col_in = None
    if has_s:
        srep = NamedSharding(mesh, P(None))
        s_row_in = jax.device_put(jnp.asarray(u_scale, jnp.float32), srep)
        s_col_in = jax.device_put(jnp.asarray(cs_obs, jnp.float32), srep)
    rep_scale_shard = NamedSharding(mesh, P(None, None))

    def gathered(u: Array) -> Array:
        u_rep = u
        for ax in reversed(axes):
            u_rep = jax.lax.all_gather(u_rep, ax, axis=0, tiled=True)
        return u_rep[: plan.n_pad]

    def rank_j0(off: Array) -> Array:
        rank = jnp.int32(0)
        for ax in axes:
            rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
        return jnp.minimum(rank * plan.per_dev + off[0],
                           plan.total_tiles - 1)

    obs_fns, cnt_fns = {}, {}

    def obs_fn(launch: int):
        if launch not in obs_fns:
            def compute(*args):
                it = iter(args)
                u = next(it)
                v = next(it) if v_in is not None else None
                su = next(it) if has_s else None
                sv = next(it) if has_s else None
                off = next(it)
                u_rep = gathered(u) if shard_u else u
                return pcc_tiles(u_rep, rank_j0(off), t=plan.t,
                                 l_blk=plan.l_blk, pass_tiles=launch,
                                 interpret=plan.interpret, epilogue=None,
                                 v_pad=v, grid_cols=grid_cols,
                                 row_scale=su, col_scale=sv)

            specs = (in_spec,)
            if v_in is not None:
                specs += (P(None, None),)
            if has_s:
                specs += (P(None), P(None))
            specs += (P(None),)
            obs_fns[launch] = shard_map(
                compute, mesh=mesh, in_specs=specs, out_specs=P(axes),
                check_vma=False)
        return obs_fns[launch]

    def cnt_fn(launch: int, rc: int):
        # keyed by (launch, replicas): at most two launch sizes and two
        # chunk sizes occur per plan, so at most four traced variants
        if (launch, rc) not in cnt_fns:
            def compute(*args):
                it = iter(args)
                u, reps = next(it), next(it)
                su = next(it) if has_s else None
                srep_c = next(it) if has_s else None
                abs_obs, off = next(it), next(it)
                u_rep = gathered(u) if shard_u else u
                buf = pcc_tiles(u_rep, rank_j0(off), t=plan.t,
                                l_blk=plan.l_blk, pass_tiles=launch,
                                interpret=plan.interpret, epilogue=None,
                                v_pad=reps, grid_cols=grid_cols,
                                row_scale=su, col_scale=srep_c)
                hits = _cmp_vals(plan, buf) >= abs_obs[None]
                return jnp.sum(hits.astype(jnp.int32), axis=0)

            specs = (in_spec, rep_spec)
            if has_s:
                specs += (P(None), P(None, None))
            specs += (P(axes, None, None), P(None))
            cnt_fns[(launch, rc)] = shard_map(
                compute, mesh=mesh, in_specs=specs, out_specs=P(axes),
                check_vma=False)
        return cnt_fns[(launch, rc)]

    for k in range(k0, plan.n_pass):
        if not (need_r(k) or need_p(k)):
            continue
        faults.check("pass_launch")
        launch = plan.launch_sizes[k]
        off = jnp.full((1,), plan.pass_offset(k), jnp.int32)
        args = (u_in,) + (() if v_in is None else (v_in,))
        if has_s:
            args += (s_row_in, s_col_in)
        raw = obs_fn(launch)(*args, off)
        ids, sel = plan.pass_selection(k)
        padded = plan.pass_padded_ids(k) if sel is not None else None
        if need_r(k):
            r_buf = _obs_tiles(plan, raw)
            if sel is None:
                r_sink.consume(ids, r_buf)
            else:
                r_sink.consume_clamped(padded, sel, ids, r_buf)
            r_done(k)
        if need_p(k):
            abs_obs = _cmp_vals(plan, raw)
            counts = None
            for ci, rc, keys_c in chunk_slices():
                rep_data, rep_scale = rep_parts(replica_source(ci, keys_c))
                reps = jax.device_put(rep_data, rep_shard)
                cargs = (u_in, reps)
                if has_s:
                    cargs += (s_row_in,
                              jax.device_put(
                                  jnp.asarray(rep_scale, jnp.float32),
                                  rep_scale_shard))
                c = cnt_fn(launch, rc)(*cargs, abs_obs, off)
                counts = c if counts is None else counts + c
            if sel is None:
                p_sink.consume(ids, counts)
            else:
                p_sink.consume_clamped(padded, sel, ids, counts)
            p_done(k)
    return r_sink.result(), p_sink.result()


def dense_significance_reference(
    x: Array,
    y: Optional[Array] = None,
    *,
    measure: measures.MeasureLike = "pearson",
    spec: PermutationSpec,
    clip: bool = True,
):
    """Dense (jnp.dot) oracle for the engine's (r, p): same key derivation,
    same per-replica operand semantics (gather vs re-transform), same
    finalised-value comparison, same canonical symmetric output (upper
    triangle mirrored elementwise).  Doubles as the benchmark baseline for
    the legacy batched-GEMM formulation."""
    meas = measures.get(measure)
    x = jnp.asarray(x)
    src = x if y is None else jnp.asarray(y)
    l = x.shape[1]
    u = meas.transform(x, dtype=jnp.float32)
    v = u if y is None else meas.transform(src, dtype=jnp.float32)
    raw = jnp.dot(u, v.T, preferred_element_type=jnp.float32)
    r = meas.finalize(raw, l, clip=clip)
    abs_obs = jnp.abs(r)
    counts = jnp.zeros(raw.shape, jnp.int32)
    for k in iteration_keys(spec):
        if spec.method == "bootstrap":
            idx = jax.random.randint(k, (l,), 0, l)
        else:
            idx = jax.random.permutation(k, l)
        if spec.method == "permute" and meas.permute_gather:
            vb = jnp.take(v, idx, axis=1)
        else:
            vb = meas.transform(jnp.take(src, idx, axis=1),
                                dtype=jnp.float32)
        rep = jnp.dot(u, vb.T, preferred_element_type=jnp.float32)
        fin = jnp.abs(meas.finalize(rep, l, clip=clip))
        counts = counts + (fin >= abs_obs).astype(jnp.int32)
    p = (1.0 + counts.astype(jnp.float32)) / np.float32(1.0 + spec.iterations)
    if y is None:
        idxs = jnp.arange(p.shape[0])
        upper = idxs[:, None] <= idxs[None, :]
        p = jnp.where(upper, p, p.T)
    return r, p


__all__ = [
    "PermutationSpec",
    "canonical_key",
    "key_fingerprint",
    "iteration_keys",
    "pvalue_measure",
    "replica_operand",
    "run_significance",
    "dense_significance_reference",
]
