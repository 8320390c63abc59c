"""Pallas TPU kernel: triangular-grid all-pairs correlation tiles.

This is the MXU adaptation of the paper's Algorithm 1 (mtPearsonR):

* Paper: a thread group picks tile id J_t, inverts it to (y_t, x_t) with the
  closed-form bijection, and 4 threads/core each compute one column of the
  t x t tile with 512-bit SIMD FMAs over the sample axis l.
* Here: a 1-D Pallas grid runs over tile ids [J_start, J_end).  The BlockSpec
  index_map *is* the bijection — it inverts the tile id to (y_t, x_t) and
  pulls the two (t, l_blk) operand blocks of U into VMEM.  The innermost
  SIMD loop becomes one MXU matmul (t, l_blk) x (l_blk, t) accumulated in
  f32 over a second grid axis that blocks the sample dimension l.

Like the paper's kernel, J_start is a *runtime* argument (scalar prefetch),
so the multi-pass driver (core/allpairs.py, Alg. 2 analogue) reuses one
compiled kernel for every pass and every device-local tile range.

Grid layout: (num_tiles_per_pass, l_blocks) — the l axis iterates fastest,
so each output tile's accumulator stays resident in VMEM across its k-steps
(revisited-block accumulation).

Fused epilogue: the measure's elementwise finalisation (divide by a static
denominator, clip to a bounded range — see core/measures.py) is applied *in
VMEM at the final k-step*, so finished similarity tiles are the only thing
ever written to HBM.  Without fusion the driver re-reads and re-writes the
whole (pass_tiles, t, t) output once more just to scale/clip it — a full
extra HBM round-trip per pass.  The fused ops replicate the unfused jnp ops
exactly (same division, same clip), so results are bit-identical.

Mixed-precision operands: U may be stored in bf16 (or int8 for exactly
integer-valued transforms such as Kendall's +/-1 pair signs), halving or
quartering operand HBM traffic and VMEM footprint; accumulation stays f32
(int8 operands accumulate exactly in int32 per k-block, then convert —
exact because each block's dot is bounded by l_blk).

VMEM budget at the default t=256, l_blk=512, f32:
  2 operand blocks (256*512*4 = 512 KiB each) + 1 accumulator
  (256*256*4 = 256 KiB) ~= 1.3 MiB  << 16 MiB/core.
bf16 operands halve the operand blocks (512 KiB total), int8 quarters them.

Out-of-range grid steps clamp to the last valid tile; the executor discards
those tiles.  Since the plan/executor refactor the drivers size every
launch to the tiles it actually covers (the final pass launches the
remainder, not the padded maximum — see ExecutionPlan.launch_sizes), so
clamped dummy steps only arise from the cross-device ceil remainder of
uniform shard_map tile ranges, never from pass padding.

Diagonal tiles compute their full t x t block although only t(t+1)/2 jobs are
needed: on the MXU a partial tile costs the same as a full one, so unlike the
paper's scalar `if (y <= x)` guard we keep the redundant half-tile — a
fraction ~1/m of the total work (documented in DESIGN.md SS2).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.mapping import job_coord_f32

DEFAULT_TILE = 256
DEFAULT_LBLK = 512


@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """Kernel-inlinable elementwise epilogue: v -> clip(v * (1/div), lo, hi).

    Hashable (static jit argument) so one compiled kernel serves each
    (div, clip) pair.  `div` is the measure's static denominator (e.g.
    covariance's l-1, Kendall's C(l,2)) or None for identity; `clip` is the
    bounded-measure output range or None.

    The division is canonically a multiply by the f32-rounded reciprocal —
    not an IEEE divide — because XLA rewrites in-jit divides by constants to
    reciprocal multiplies anyway, and pinning one form keeps the fused
    (in-kernel, jitted) and unfused (eager Measure.finalize) paths
    bit-identical.  `apply` is that single canonical implementation; both
    the kernel's final k-step and the unfused epilogues call it.
    """

    div: Optional[float] = None
    clip: Optional[Tuple[float, float]] = None

    def is_identity(self) -> bool:
        return self.div is None and self.clip is None

    def apply(self, vals):
        if self.div is not None:
            vals = vals * (np.float32(1.0) / np.float32(self.div))
        if self.clip is not None:
            vals = jnp.clip(vals, self.clip[0], self.clip[1])
        return vals


def _block_dot(a, b):
    """(t, l_blk) . (t, l_blk)^T on the MXU, as an f32 partial tile.

    Integer operands (Kendall pair signs, or absmax-quantized rows)
    accumulate exactly in int32 per block, then widen (exact: each block dot
    is bounded by l_blk * 127^2).  f32 operands ask for f32 contract
    precision: Mosaic's default rounds them to bf16, which on a v5e left
    Pearson at GPL570 width 3e-4 from float64.  fp8 operands have no MXU
    path and widen to f32 first; like bf16 they are exact in the default
    bf16 pass."""
    if jnp.issubdtype(a.dtype, jnp.integer):
        return jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32).astype(jnp.float32)
    precision = None
    if a.dtype == jnp.float32:
        precision = jax.lax.Precision.HIGHEST
    elif a.dtype != jnp.bfloat16:
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _kernel(jstart_ref, urow_ref, ucol_ref, *rest, l_blocks: int,
            epilogue: Optional[EpilogueSpec], replica: bool = False,
            scaled: bool = False):
    """Body: accumulate one (t, t) tile over the l (sample) axis, applying
    the fused epilogue at the last k-step (finished tiles only hit HBM).

    replica=True is the significance workload (core/significance.py): the
    grid gains a leading replica axis and the column operand is a stacked
    (R, cols_pad, l_pad) array of permuted/resampled operand variants — the
    column block then carries a leading singleton replica dim to strip, and
    the l axis moves to grid position 2.

    scaled=True is the quantized-operand path (core/quantize.py): two extra
    per-row dequantization scale refs ride between the operands and the
    output; the finished tile is multiplied by their outer product *before*
    the epilogue at the final k-step, so dequantization is fused and never
    costs a second HBM pass.  Applied whenever scales are present — also on
    raw (epilogue=None) significance launches."""
    if scaled:
        srow_ref, scol_ref, out_ref = rest
    else:
        (out_ref,) = rest
    k = pl.program_id(2 if replica else 1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ucol = ucol_ref[0] if replica else ucol_ref[...]
    out_ref[...] += _block_dot(urow_ref[...], ucol)

    # Dequantization and epilogue share ONE final-k block so their order is
    # structural (scales first, then div/clip) — never two racing pl.when's.
    needs_fin = scaled or (epilogue is not None and not epilogue.is_identity())
    if needs_fin:
        @pl.when(k == l_blocks - 1)
        def _finalize():
            acc = out_ref[...]
            if scaled:
                srow = srow_ref[0]                                # (t, 1)
                scol = scol_ref[0, 0] if replica else scol_ref[0]  # (1, t)
                acc = acc * (srow * scol)
            if epilogue is not None and not epilogue.is_identity():
                acc = epilogue.apply(acc)
            out_ref[...] = acc


def _row_map(i, k, jstart_ref, *, m: int, total: int):
    """BlockSpec index_map for the row operand: tile id -> y_t (Eq. 18)."""
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    y_t, _ = job_coord_f32(m, jt)
    return y_t, k


def _col_map(i, k, jstart_ref, *, m: int, total: int):
    """BlockSpec index_map for the column operand: tile id -> x_t (Eq. 19)."""
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    _, x_t = job_coord_f32(m, jt)
    return x_t, k


def _grid_row_map(i, k, jstart_ref, *, mc: int, total: int):
    """Rectangular-grid row index_map: tile id -> y_t = jt // m_cols.
    Pure int32 division — no sqrt inversion needed for the grid family."""
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt // mc, k


def _grid_col_map(i, k, jstart_ref, *, mc: int, total: int):
    """Rectangular-grid column index_map: tile id -> x_t = jt % m_cols,
    indexing the *second* operand V."""
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt - (jt // mc) * mc, k


def _out_map(i, k, jstart_ref, *, m: int, total: int):
    del k, jstart_ref
    return i, 0, 0


# Scale index maps (quantized operands): the per-row scales are laid out as
# an (m, t, 1) column for the rows and an (mc, 1, t) row for the columns, so
# each tile pulls a (t, 1) and a (1, t) block whose last two dims equal the
# array's (the TPU block rule) and whose product is the tile's outer
# product.  They follow the same tile-id bijection as their operand, but
# ignore the k axis.


def _scale_row_map(i, k, jstart_ref, *, m: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    y_t, _ = job_coord_f32(m, jt)
    return y_t, 0, 0


def _scale_col_map(i, k, jstart_ref, *, m: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    _, x_t = job_coord_f32(m, jt)
    return x_t, 0, 0


def _scale_grid_row_map(i, k, jstart_ref, *, mc: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt // mc, 0, 0


def _scale_grid_col_map(i, k, jstart_ref, *, mc: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt - (jt // mc) * mc, 0, 0


# Replica-axis index maps (significance workload): the grid is
# (replicas, pass_tiles, l_blocks).  The row operand stays 2-D (the observed
# transform — every replica reads the same row blocks); the column operand is
# the 3-D (R, cols_pad, l_pad) replica stack, so its map prepends the replica
# grid index.  The tile-id bijections are unchanged.


def _rep_row_map(r, i, k, jstart_ref, *, m: int, total: int):
    del r
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    y_t, _ = job_coord_f32(m, jt)
    return y_t, k


def _rep_col_map(r, i, k, jstart_ref, *, m: int, total: int):
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    _, x_t = job_coord_f32(m, jt)
    return r, x_t, k


def _rep_grid_row_map(r, i, k, jstart_ref, *, mc: int, total: int):
    del r
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt // mc, k


def _rep_grid_col_map(r, i, k, jstart_ref, *, mc: int, total: int):
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return r, jt - (jt // mc) * mc, k


def _rep_out_map(r, i, k, jstart_ref, *, m: int, total: int):
    del k, jstart_ref
    return r, i, 0, 0


def _rep_scale_row_map(r, i, k, jstart_ref, *, m: int, total: int):
    del r, k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    y_t, _ = job_coord_f32(m, jt)
    return y_t, 0, 0


def _rep_scale_col_map(r, i, k, jstart_ref, *, m: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    _, x_t = job_coord_f32(m, jt)
    return r, x_t, 0, 0


def _rep_scale_grid_row_map(r, i, k, jstart_ref, *, mc: int, total: int):
    del r, k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt // mc, 0, 0


def _rep_scale_grid_col_map(r, i, k, jstart_ref, *, mc: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return r, jt - (jt // mc) * mc, 0, 0


@functools.partial(
    jax.jit,
    static_argnames=("t", "l_blk", "pass_tiles", "interpret", "epilogue",
                     "grid_cols"),
)
def pcc_tiles(
    u_pad: jax.Array,
    j_start: jax.Array,
    *,
    t: int = DEFAULT_TILE,
    l_blk: int = DEFAULT_LBLK,
    pass_tiles: int,
    interpret: bool = False,
    epilogue: Optional[EpilogueSpec] = None,
    v_pad: Optional[jax.Array] = None,
    grid_cols: Optional[int] = None,
    row_scale: Optional[jax.Array] = None,
    col_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Compute `pass_tiles` consecutive tiles starting at tile id `j_start`
    (runtime scalar), following paper Alg. 1.

    u_pad: (n_pad, l_pad) pre-transformed variables (Eq. 4), zero-padded so
           n_pad % t == 0 and l_pad % l_blk == 0.  May be f32, bf16, or (for
           integer-valued transforms) int8 — accumulation is always f32.
    j_start: int32 scalar — first tile id of this pass (J_start in Alg. 1).
    epilogue: optional static EpilogueSpec fused into the final k-step so
           tiles leave VMEM already finalised (no second HBM pass).
    v_pad: optional second operand (n_cols_pad, l_pad) for rectangular
           X-vs-Y workloads — the column BlockSpec pulls its blocks from V
           instead of U.  Requires grid_cols.  None reuses U (symmetric).
           A 3-D (replicas, cols_pad, l_pad) stack selects the *replica*
           grid: one launch computes every replica's tiles over a leading
           grid axis (the significance workload, core/significance.py),
           returning (replicas, pass_tiles, t, t).  Replica stacks compose
           with both bijection families: grid_cols=None runs the triangle
           against stacked permutations of U itself (cols_pad == n_pad).
    grid_cols: None runs the triangular bijection over U against itself
           (tile ids number the upper triangle, Eq. 9/14 — the paper's
           symmetric workload, bit-identical to the historical kernel).  An
           int selects the rectangular grid family: tile ids number an
           (m_rows x grid_cols) grid row-major, y = jt // grid_cols indexes
           U and x = jt % grid_cols indexes V.  A 2-D v_pad of u_pad's
           exact shape may also ride the triangle (grid_cols=None): the
           masked-symmetric composite's cross-component GEMMs
           (values . mask^T etc.) are symmetric tile-by-tile under the
           needs_symmetrize mirror, so they too need only the upper half.
    row_scale / col_scale: optional (n_pad,)-shaped f32 per-row
           dequantization scales (col_scale (R, cols_pad) for replica
           stacks) — present iff the operands were absmax-quantized
           (core/quantize.py).  The kernel multiplies each finished tile by
           the scale outer product before the epilogue.  Must be given
           together (pass the same array twice for symmetric runs).
    Returns (pass_tiles, t, t) f32 tile results (R' in Alg. 1).
    """
    n_pad, l_pad = u_pad.shape
    if n_pad % t or l_pad % l_blk:
        raise ValueError(f"u_pad {u_pad.shape} not aligned to t={t}, l_blk={l_blk}")
    if pass_tiles <= 0:
        raise ValueError(f"pass_tiles must be positive, got {pass_tiles} "
                         f"(remainder launches must be sized, not empty)")
    replicas = None
    if v_pad is not None and v_pad.ndim == 3:
        replicas = v_pad.shape[0]
        if replicas <= 0:
            raise ValueError(f"replica stack {v_pad.shape} is empty")
    elif v_pad is not None and grid_cols is None:
        if v_pad.shape != u_pad.shape:
            raise ValueError(
                f"a 2-D second operand may ride the triangular bijection "
                f"only when it matches u_pad exactly (symmetric composite "
                f"GEMMs), got v_pad {v_pad.shape} vs u_pad {u_pad.shape}")
    v = u_pad if v_pad is None else v_pad
    if (row_scale is None) != (col_scale is None):
        raise ValueError("row_scale and col_scale must be given together "
                         "(pass the same scales twice for symmetric runs)")
    scaled = row_scale is not None
    m = n_pad // t
    if grid_cols is None:
        total = m * (m + 1) // 2
        if replicas is None:
            row_map = functools.partial(_row_map, m=m, total=total)
            col_map = functools.partial(_col_map, m=m, total=total)
            smaps = (functools.partial(_scale_row_map, m=m, total=total),
                     functools.partial(_scale_col_map, m=m, total=total))
        else:
            if v.shape[1:] != (n_pad, l_pad):
                raise ValueError(
                    f"triangular replica stack {v.shape} must stack "
                    f"({n_pad}, {l_pad}) operand variants")
            row_map = functools.partial(_rep_row_map, m=m, total=total)
            col_map = functools.partial(_rep_col_map, m=m, total=total)
            smaps = (functools.partial(_rep_scale_row_map, m=m, total=total),
                     functools.partial(_rep_scale_col_map, m=m, total=total))
    else:
        if v.shape[-1] != l_pad or v.shape[-2] != grid_cols * t:
            raise ValueError(
                f"column operand {v.shape} does not match grid_cols="
                f"{grid_cols} tiles of t={t} over l_pad={l_pad}")
        total = m * grid_cols
        if replicas is None:
            row_map = functools.partial(_grid_row_map, mc=grid_cols,
                                        total=total)
            col_map = functools.partial(_grid_col_map, mc=grid_cols,
                                        total=total)
            smaps = (functools.partial(_scale_grid_row_map, mc=grid_cols,
                                       total=total),
                     functools.partial(_scale_grid_col_map, mc=grid_cols,
                                       total=total))
        else:
            row_map = functools.partial(_rep_grid_row_map, mc=grid_cols,
                                        total=total)
            col_map = functools.partial(_rep_grid_col_map, mc=grid_cols,
                                        total=total)
            smaps = (functools.partial(_rep_scale_grid_row_map, mc=grid_cols,
                                       total=total),
                     functools.partial(_rep_scale_grid_col_map, mc=grid_cols,
                                       total=total))
    l_blocks = l_pad // l_blk

    kernel = functools.partial(_kernel, l_blocks=l_blocks, epilogue=epilogue,
                               replica=replicas is not None, scaled=scaled)
    if replicas is None:
        grid = (pass_tiles, l_blocks)
        in_specs = [
            pl.BlockSpec((t, l_blk), row_map),
            pl.BlockSpec((t, l_blk), col_map),
        ]
        scale_specs = [pl.BlockSpec((1, t, 1), smaps[0]),
                       pl.BlockSpec((1, 1, t), smaps[1])]
        out_specs = pl.BlockSpec(
            (1, t, t), functools.partial(_out_map, m=m, total=total))
        out_shape = (pass_tiles, t, t)
    else:
        # replica axis slowest, l fastest: each (r, i) accumulator stays
        # resident in VMEM across its k-steps, exactly as without replicas
        grid = (replicas, pass_tiles, l_blocks)
        in_specs = [
            pl.BlockSpec((t, l_blk), row_map),
            pl.BlockSpec((1, t, l_blk), col_map),
        ]
        scale_specs = [pl.BlockSpec((1, t, 1), smaps[0]),
                       pl.BlockSpec((1, 1, 1, t), smaps[1])]
        out_specs = pl.BlockSpec(
            (1, 1, t, t), functools.partial(_rep_out_map, m=m, total=total))
        out_shape = (replicas, pass_tiles, t, t)

    operands = [jnp.asarray(j_start, jnp.int32).reshape(1), u_pad, v]
    if scaled:
        # scales arrive per padded row (n_pad,) — or (R, cols_pad) for a
        # replica-stacked column operand — and are reshaped so each tile's
        # scale blocks are one (t, 1) column and one (.., 1, t) row
        in_specs = in_specs + scale_specs
        srow = jnp.asarray(row_scale, jnp.float32).reshape(m, t, 1)
        cs = jnp.asarray(col_scale, jnp.float32)
        if replicas is None:
            scol = cs.reshape(v.shape[0] // t, 1, t)
        else:
            scol = cs.reshape(replicas, v.shape[1] // t, 1, t)
        operands += [srow, scol]

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )(*operands)
    return out


# -- device-side per-row top-k epilogue (multi-host scale-out) ---------------
#
# pcc_topk_tiles computes the same tiles as pcc_tiles but never writes them
# to HBM: each (t, t) tile lives only in a VMEM scratch accumulator, and at
# its final k-step it is folded into running per-row (value, column) top-k
# state blocks — so a pass's device->host traffic is O(n * k), not
# O(pass_tiles * t^2), and a multi-host launch ships partial top-k states
# instead of n^2/hosts of tiles (the CoMet trick, arXiv:1705.08213).
#
# The in-kernel selection replicates core/sinks.topk_merge_rows' canonical
# order *exactly* — |value| descending, ties by ascending column — so
# per-host partial states merge into results bit-identical to a single-host
# TopKSink.  It is sort-free (the TPU compiler lowers no sort): kk rounds of
# max-extraction, each a handful of lane reductions.
#
# Row state blocks are revisited across grid steps, and that is safe on a
# compiled pipeline only because the row block y(jt) is non-decreasing
# within a pass (row-major tile order): a block's visits are consecutive, so
# it stays resident in VMEM between them and is written back once.  Its
# first visit loads the carried-in state explicitly (an output block is not
# read from HBM).  The mirrored column side of triangular runs has no such
# order — x(jt) revisits a block once per row above it — so it is not kept
# as revisited state at all: every tile slot writes its own (t, kk)
# column-side top-k, and the host merge (core/sinks.DeviceTopKSink) folds
# the slots into their rows.


def _tk_row_state_map(i, k, jstart_ref, *, m: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    y_t, _ = job_coord_f32(m, jt)
    return y_t, 0, 0


def _tk_grid_row_state_map(i, k, jstart_ref, *, mc: int, total: int):
    del k
    jt = jnp.minimum(jstart_ref[0] + i, total - 1)
    return jt // mc, 0, 0


def _tk_slot_map(i, k, jstart_ref):
    del k, jstart_ref
    return i, 0, 0


def _topk_select(parts, kk: int):
    """Top-kk per row of the union of (values, columns) candidate arrays,
    each (t, w), under the canonical order.  Masked candidates carry column
    -1 and empty output slots are (0, -1), exactly like empty state slots;
    both are dropped again host-side.

    Each round takes the largest |value|, then the smallest column holding
    it; candidate columns are unique per row, so that names one candidate,
    which is then retired.  Once only masked candidates remain, every later
    slot is empty.  A NaN value ranks below every empty slot in the
    canonical order, so it never enters a top-k: it is masked here too."""
    keys = [jnp.where((c < 0) | jnp.isnan(v), -jnp.inf, jnp.abs(v))
            for v, c in parts]
    rows = parts[0][0].shape[0]
    slot = jax.lax.broadcasted_iota(jnp.int32, (rows, kk), 1)
    big = jnp.iinfo(jnp.int32).max

    def round_(r, carry):
        keys, out_v, out_c = carry
        kmax = functools.reduce(jnp.maximum, [
            jnp.max(key, axis=1, keepdims=True) for key in keys])
        cmin = functools.reduce(jnp.minimum, [
            jnp.min(jnp.where(key == kmax, c, big), axis=1, keepdims=True)
            for key, (_, c) in zip(keys, parts)])
        hits = [(key == kmax) & (c == cmin)
                for key, (_, c) in zip(keys, parts)]
        val = functools.reduce(jnp.maximum, [
            jnp.max(jnp.where(h, v, -jnp.inf), axis=1, keepdims=True)
            for h, (v, _) in zip(hits, parts)])
        live = kmax > -jnp.inf
        here = slot == r
        out_v = jnp.where(here, jnp.where(live, val, 0.0), out_v)
        out_c = jnp.where(here, jnp.where(live, cmin, -1), out_c)
        keys = [jnp.where(h, -jnp.inf, key) for h, key in zip(hits, keys)]
        return keys, out_v, out_c

    _, out_v, out_c = jax.lax.fori_loop(
        0, kk, round_, (keys, jnp.zeros((rows, kk), jnp.float32),
                        jnp.full((rows, kk), -1, jnp.int32)))
    return out_v, out_c


def _topk_kernel(jstart_ref, urow_ref, ucol_ref, rv_in, rc_in, *rest,
                 l_blocks: int, epilogue: Optional[EpilogueSpec], kk: int,
                 t: int, n_cols: int, symmetric: bool, mirror: bool, m: int,
                 grid_cols: Optional[int], total: int):
    """pcc_tiles' accumulation (bit-identical f32 adds into a VMEM scratch)
    plus a final-k-step merge of the finished tile into per-row top-k state.

    jstart_ref holds three scalars: [clamped j_start (the index maps' view,
    as in pcc_tiles), the *raw* device start, and the device's exclusive
    tile bound] — the latter two gate the merge so clamped duplicate slots
    never contribute candidates and per-(device, pass) states stay disjoint.
    """
    if mirror:
        rv_out, rc_out, cv_out, cc_out, acc = rest
    else:
        rv_out, rc_out, acc = rest
    i = pl.program_id(0)
    k = pl.program_id(1)

    def coords(jt):
        if grid_cols is None:
            return job_coord_f32(m, jt)
        y = jt // grid_cols
        return y, jt - y * grid_cols

    jt_raw = jstart_ref[1] + i
    valid = jt_raw < jstart_ref[2]
    y_t, x_t = coords(jnp.minimum(jt_raw, total - 1))
    y_prev, _ = coords(jnp.clip(jt_raw - 1, 0, total - 1))

    @pl.when(k == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    @pl.when((k == 0) & ((i == 0) | (y_prev != y_t)))
    def _load_row_state():
        # first visit of this row block in the launch: its consecutive
        # visits keep it resident, so only this one reads the state in
        rv_out[...] = rv_in[...]
        rc_out[...] = rc_in[...]

    acc[...] += _block_dot(urow_ref[...], ucol_ref[...])

    def _final_tile():
        r = acc[...]
        if epilogue is not None and not epilogue.is_identity():
            r = epilogue.apply(r)
        return r

    rows_io = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    cols_io = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    last = k == l_blocks - 1

    @pl.when(last & valid)
    def _merge_rows():
        r = _final_tile()
        cols_g = x_t * t + cols_io
        bad = cols_g >= n_cols
        if symmetric:
            bad = bad | (y_t * t + rows_io == cols_g)
        nv, nc = _topk_select(
            [(rv_out[0], rc_out[0]), (r, jnp.where(bad, -1, cols_g))], kk)
        rv_out[0] = nv
        rc_out[0] = nc

    if mirror:
        # off-diagonal tiles also rank row i as a neighbour of row j via the
        # transposed tile (diagonal tiles already carry both orders); every
        # slot writes its own column-side state, empty when it adds nothing
        off_diag = valid & (y_t != x_t)

        @pl.when(last & off_diag)
        def _merge_cols():
            cols_g = y_t * t + cols_io
            nv, nc = _topk_select(
                [(_final_tile().T, jnp.where(cols_g >= n_cols, -1, cols_g))],
                kk)
            cv_out[0] = nv
            cc_out[0] = nc

        @pl.when(last & ~off_diag)
        def _empty_cols():
            cv_out[...] = jnp.zeros_like(cv_out)
            cc_out[...] = jnp.full(cc_out.shape, -1, jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("t", "l_blk", "pass_tiles", "kk", "interpret",
                     "epilogue", "grid_cols", "n_cols_valid",
                     "symmetric_problem"),
)
def pcc_topk_tiles(
    u_pad: jax.Array,
    j_start: jax.Array,
    dev_hi: jax.Array,
    *,
    t: int = DEFAULT_TILE,
    l_blk: int = DEFAULT_LBLK,
    pass_tiles: int,
    kk: int,
    n_cols_valid: int,
    symmetric_problem: bool = True,
    interpret: bool = False,
    epilogue: Optional[EpilogueSpec] = None,
    v_pad: Optional[jax.Array] = None,
    grid_cols: Optional[int] = None,
):
    """pcc_tiles with the top-k epilogue: compute `pass_tiles` tiles from
    raw device-local start `j_start`, returning per-row-block top-k state
    instead of the tiles themselves.

    j_start here is the *unclamped* device start (rank * per_dev + offset);
    dev_hi is the device's exclusive global tile bound — slots at or past
    it (the cross-device ceil remainder) compute clamped duplicates exactly
    as pcc_tiles does, but are excluded from the merge.

    kk: state capacity per row (>= the requested k); n_cols_valid masks
    padding columns; symmetric_problem additionally masks self-pairs.

    Returns (row_vals, row_cols), each (m, t, kk), for grid workloads.
    Triangular runs (grid_cols=None) also return (col_vals, col_cols), each
    (pass_tiles, t, kk): slot i's column-side top-k — the neighbours its
    tile gives the rows of its *column* block, read off the transposed tile
    — so no transpose is ever materialised.  Value 0 / column -1 mark empty
    slots.  Replica stacks and quantized scaled operands are not supported
    (core/sinks.DeviceTopKSink gates on this).
    """
    n_pad, l_pad = u_pad.shape
    if n_pad % t or l_pad % l_blk:
        raise ValueError(
            f"u_pad {u_pad.shape} not aligned to t={t}, l_blk={l_blk}")
    if pass_tiles <= 0:
        raise ValueError(f"pass_tiles must be positive, got {pass_tiles}")
    if kk <= 0:
        raise ValueError(f"kk must be positive, got {kk}")
    if v_pad is not None and v_pad.ndim != 2:
        raise ValueError(
            "pcc_topk_tiles does not support replica stacks — top-k of a "
            "null distribution is not a defined workload")
    v = u_pad if v_pad is None else v_pad
    mirror = grid_cols is None
    m = n_pad // t
    if grid_cols is None:
        total = m * (m + 1) // 2
        if v.shape != u_pad.shape:
            raise ValueError(
                f"triangular top-k needs v_pad == u_pad shape, got "
                f"{v.shape} vs {u_pad.shape}")
        row_map = functools.partial(_row_map, m=m, total=total)
        col_map = functools.partial(_col_map, m=m, total=total)
        rs_map = functools.partial(_tk_row_state_map, m=m, total=total)
    else:
        if v.shape[-1] != l_pad or v.shape[-2] != grid_cols * t:
            raise ValueError(
                f"column operand {v.shape} does not match grid_cols="
                f"{grid_cols} tiles of t={t} over l_pad={l_pad}")
        total = m * grid_cols
        row_map = functools.partial(_grid_row_map, mc=grid_cols, total=total)
        col_map = functools.partial(_grid_col_map, mc=grid_cols, total=total)
        rs_map = functools.partial(_tk_grid_row_state_map, mc=grid_cols,
                                   total=total)
    l_blocks = l_pad // l_blk

    j0 = jnp.asarray(j_start, jnp.int32).reshape(())
    hi = jnp.asarray(dev_hi, jnp.int32).reshape(())
    starts = jnp.stack([jnp.minimum(j0, total - 1), j0, hi])

    kernel = functools.partial(
        _topk_kernel, l_blocks=l_blocks, epilogue=epilogue, kk=kk, t=t,
        n_cols=n_cols_valid, symmetric=symmetric_problem, mirror=mirror,
        m=m, grid_cols=grid_cols, total=total)

    state_spec = pl.BlockSpec((1, t, kk), rs_map)
    in_specs = [pl.BlockSpec((t, l_blk), row_map),
                pl.BlockSpec((t, l_blk), col_map), state_spec, state_spec]
    out_specs = [state_spec, state_spec]
    out_shape = [jax.ShapeDtypeStruct((m, t, kk), jnp.float32),
                 jax.ShapeDtypeStruct((m, t, kk), jnp.int32)]
    if mirror:
        out_specs += [pl.BlockSpec((1, t, kk), _tk_slot_map)] * 2
        out_shape += [jax.ShapeDtypeStruct((pass_tiles, t, kk), jnp.float32),
                      jax.ShapeDtypeStruct((pass_tiles, t, kk), jnp.int32)]
    operands = [starts, u_pad, v, jnp.zeros((m, t, kk), jnp.float32),
                jnp.full((m, t, kk), -1, jnp.int32)]

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pass_tiles, l_blocks),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=[pltpu.VMEM((t, t), jnp.float32)],
        ),
        out_shape=tuple(out_shape),
        interpret=interpret,
        # the empty state inputs initialise the row-state outputs, so row
        # blocks no tile of the launch visits come back empty; indices
        # count the scalar-prefetch operand (starts = 0)
        input_output_aliases={3: 0, 4: 1},
    )(*operands)


__all__ = ["pcc_tiles", "pcc_topk_tiles", "EpilogueSpec", "DEFAULT_TILE",
           "DEFAULT_LBLK"]
