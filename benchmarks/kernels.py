"""Kernel microbenchmarks (paper SSIII-C): tile sizes, dtypes, grid savings.

interpret-mode Pallas is a correctness vehicle, not a speed path, so we
report (i) the XLA oracle timing across tile sizes (the CPU-executable
proxy), (ii) interpret-kernel validation timing, and (iii) the structural
metrics that determine TPU throughput: triangular-grid step savings, VMEM
working-set per BlockSpec across operand dtypes (f32 / bf16 / int8), and
the HBM traffic a fused vs. unfused epilogue implies per pass.
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

import jax

from benchmarks.common import emit, timeit
from repro.core import measures
from repro.core.allpairs import allpairs, prepare
from repro.core.api import corr
from repro.core.plan import ExecutionPlan, resolve_interpret
from repro.core.quantize import fp8_dtype, quantize_rows
from repro.core.sinks import EdgeCountSink, HostSink, TopKSink
from repro.kernels.flash_attention import grid_savings
from repro.kernels.kendall_merge import KENDALL_MERGE_CROSSOVER_L
from repro.kernels.pcc_tile import DEFAULT_LBLK, DEFAULT_TILE, pcc_tiles
from repro.kernels.ref import pcc_tiles_ref
from repro.core.mapping import tri_count

# the "production" bench rows describe the shipped kernel geometry — alias
# the kernel defaults so they can never drift apart silently
PROD_T = DEFAULT_TILE
PROD_LBLK = DEFAULT_LBLK
PROD_PASS_TILES = 1024


def vmem_bytes(t: int, l_blk: int, op_itemsize: int = 4,
               acc_itemsize: int = 4) -> int:
    """VMEM working set of one grid step: two (t, l_blk) operand blocks at
    the operand dtype's width plus one (t, t) accumulator (f32 unless the
    operands are int8, whose per-block accumulator is int32 — same width)."""
    return 2 * t * l_blk * op_itemsize + t * t * acc_itemsize


def epilogue_hbm_bytes(pass_tiles: int, t: int, fused: bool,
                       itemsize: int = 4) -> int:
    """HBM bytes the epilogue costs per pass: fused tiles are written once,
    finished; an unfused epilogue re-reads and re-writes the whole
    (pass_tiles, t, t) output as a separate elementwise op (3x traffic)."""
    tile_bytes = pass_tiles * t * t * itemsize
    return tile_bytes if fused else 3 * tile_bytes


def run() -> None:
    interpret = resolve_interpret(None)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 128)).astype(np.float32))

    for t, lblk in [(32, 32), (64, 32), (64, 64), (128, 64)]:
        u, plan = prepare(x, t=t, l_blk=lblk)
        total = plan.total_tiles
        t_ref = timeit(lambda u=u, t=t, total=total:
                       pcc_tiles_ref(u, 0, t=t, pass_tiles=total))
        emit(f"kernels/pcc_ref_t{t}_l{lblk}", t_ref * 1e6,
             f"tiles={total};vmem_kib={vmem_bytes(t, lblk) // 1024}")

    # interpret-mode validation cost (documented, not a perf claim)
    u, plan = prepare(x[:64, :64], t=16, l_blk=32)
    t_int = timeit(lambda: pcc_tiles(u, 0, t=16, l_blk=32,
                                     pass_tiles=plan.total_tiles,
                                     interpret=interpret), warmup=1, iters=1)
    emit("kernels/pcc_interpret_t16", t_int * 1e6,
         f"tiles={plan.total_tiles}")

    # production BlockSpec working set across operand dtypes: bf16 halves,
    # int8 quarters the operand blocks (the accumulator stays 4 bytes/elt)
    for dname, isz in [("f32", 4), ("bf16", 2), ("int8", 1)]:
        emit(f"kernels/pcc_vmem_production_{dname}", 0.0,
             f"t={PROD_T};l_blk={PROD_LBLK};op_itemsize={isz};"
             f"vmem_kib={vmem_bytes(PROD_T, PROD_LBLK, isz) // 1024}")

    # fused vs. unfused epilogue: interpret timing (1 iter, correctness
    # vehicle) + the structural HBM traffic per production pass — the fused
    # kernel writes finished tiles once, the unfused path round-trips the
    # whole output a second time for the elementwise finalisation.
    xe = x[:64, :]
    for fused in (True, False):
        t_e = timeit(lambda fused=fused: corr(
            xe, t=16, l_blk=32, measure="covariance", fuse_epilogue=fused),
            warmup=1, iters=1)
        label = "fused" if fused else "unfused"
        emit(f"kernels/pcc_epilogue_{label}", t_e * 1e6,
             f"hbm_bytes_per_pass="
             f"{epilogue_hbm_bytes(PROD_PASS_TILES, PROD_T, fused)}")

    # operand-dtype A/B on the interpret kernel; int8 rides the Kendall
    # pair-sign path (the only exactly-int8 transform)
    u32, plan32 = prepare(x[:64], t=16, l_blk=32)
    for dname, ud in [("f32", u32), ("bf16", u32.astype(jnp.bfloat16))]:
        t_d = timeit(lambda ud=ud: pcc_tiles(ud, 0, t=16, l_blk=32,
                                             pass_tiles=plan32.total_tiles,
                                             interpret=interpret),
                     warmup=1, iters=1)
        emit(f"kernels/pcc_interpret_dtype_{dname}", t_d * 1e6,
             f"operand_bytes={ud.size * ud.dtype.itemsize}")
    u8, plan8 = prepare(x[:64, :24], t=16, l_blk=32, measure="kendall",
                        compute_dtype=jnp.int8)
    t_8 = timeit(lambda: pcc_tiles(u8, 0, t=16, l_blk=32,
                                   pass_tiles=plan8.total_tiles,
                                   interpret=interpret), warmup=1, iters=1)
    emit("kernels/pcc_interpret_dtype_int8_kendall", t_8 * 1e6,
         f"operand_bytes={u8.size * u8.dtype.itemsize};"
         f"pairs={24 * 23 // 2}")

    # per-measure row-transform cost feeding the same tiled kernel: the
    # transform is the only measure-specific device work (epilogues are
    # fused into the kernel), so this is the whole marginal cost of measure
    # diversity.
    for name in ("pearson", "spearman", "cosine", "covariance"):
        meas = measures.get(name)
        t_tr = timeit(lambda meas=meas:
                      meas.transform(x, dtype=jnp.float32))
        emit(f"kernels/transform_{name}", t_tr * 1e6, "n=256;l=128")
    # Kendall widens l -> l(l-1)/2; benchmarked at small l (see docs).
    xk = x[:, :48]
    t_tr = timeit(lambda: measures.KENDALL.transform(xk, dtype=jnp.float32))
    emit("kernels/transform_kendall", t_tr * 1e6,
         f"n=256;l=48;pairs={48 * 47 // 2}")

    # final-pass launch sizing: the executor's last kernel launch covers
    # exactly the remaining tiles — assert no dummy-tile compute at the
    # production geometry (the pre-refactor driver padded the final pass to
    # max_tiles_per_pass, wasting up to mtp-1 tiles of MXU work per run).
    plan = ExecutionPlan.create(65536, 4096, t=PROD_T, l_blk=PROD_LBLK,
                                max_tiles_per_pass=PROD_PASS_TILES)
    sizes = plan.launch_sizes
    assert sum(sizes) == plan.total_tiles, "launches must cover the triangle"
    assert all(s == PROD_PASS_TILES for s in sizes[:-1])
    assert sizes[-1] == plan.total_tiles % PROD_PASS_TILES or \
        sizes[-1] == PROD_PASS_TILES
    dummy = len(sizes) * PROD_PASS_TILES - plan.total_tiles
    saved = dummy * PROD_T * PROD_T * 4
    emit("kernels/final_pass_launch", 0.0,
         f"total_tiles={plan.total_tiles};passes={len(sizes)};"
         f"final_launch={sizes[-1]};dummy_tiles_avoided={dummy};"
         f"hbm_bytes_saved_per_run={saved}")

    # executor + sink structural A/B (interpret timing, correctness
    # vehicle): dense device assembly vs out-of-core host assembly vs an
    # O(n)-state streaming reduction — all three through the one executor.
    xs = x[:64, :64]
    for label, mk in [("dense", lambda: None),
                      ("host", lambda: HostSink()),
                      ("edgecount", lambda: EdgeCountSink(0.2))]:
        t_s = timeit(lambda mk=mk: allpairs(xs, t=16, l_blk=32,
                                            max_tiles_per_pass=4,
                                            sink=mk()),
                     warmup=1, iters=1)
        emit(f"kernels/executor_sink_{label}", t_s * 1e6,
             "n=64;l=64;t=16;mtp=4")

    # rectangular (grid-workload) path: X-vs-Y cross-correlation through
    # the second-operand block specs.  Structural payoff vs the symmetric
    # workaround (embedding X and Y in one (n_r+n_c)^2 triangle): the grid
    # computes exactly m_r*m_c tiles.
    xq, yq = x[:48, :64], x[64:192, :64]
    t_rect = timeit(lambda: corr(xq, yq, t=16, l_blk=32),
                    warmup=1, iters=1)
    mr, mc = 48 // 16, 128 // 16
    embed = (mr + mc) * (mr + mc + 1) // 2
    emit("kernels/rect_corr_interpret", t_rect * 1e6,
         f"n_rows=48;n_cols=128;grid_tiles={mr * mc};"
         f"symmetric_embed_tiles={embed};"
         f"tile_savings={1 - mr * mc / embed:.3f}")

    # masked (pairwise-complete) path: component GEMMs + elementwise
    # combine.  Structural cost = #components kernel passes over the full
    # grid (the cross terms are non-symmetric even for y == x).
    xn = np.asarray(x[:48, :64]).copy()
    xn[np.random.default_rng(5).random(xn.shape) < 0.3] = np.nan
    xnj = jnp.asarray(xn)
    for name, ncomp in [("pearson", 6), ("cosine", 3)]:
        t_m = timeit(lambda name=name: corr(xnj, where="nan", measure=name,
                                            t=16, l_blk=32),
                     warmup=1, iters=1)
        emit(f"kernels/masked_{name}_interpret", t_m * 1e6,
             f"n=48;l=64;nan_frac=0.3;component_gemms={ncomp};"
             f"grid_tiles={(48 // 16) ** 2}")

    # top-k sink: O(n*k) streaming state vs the dense matrix
    t_k = timeit(lambda: corr(x[:64, :64], t=16, l_blk=32,
                              max_tiles_per_pass=4, sink=TopKSink(8)),
                 warmup=1, iters=1)
    emit("kernels/executor_sink_topk", t_k * 1e6,
         f"n=64;k=8;state_bytes={64 * 8 * (4 + 8)}")

    # triangular/banded grid savings (the C1 payoff)
    for s, blk, w in [(4096, 128, None), (32768, 128, None),
                      (32768, 128, 4096), (524288, 128, 1024)]:
        emit(f"kernels/grid_savings_s{s}_w{w}", 0.0,
             f"savings={grid_savings(s, blk, w):.4f};"
             f"steps={tri_count(-(-s // blk)) if w is None else '-'}")

    # Kendall sign-GEMM vs merge-sort crossover (ISSUE 8 tentpole): end-to-
    # end corr() on both forced paths, the user-observable the dispatch
    # bound (KENDALL_MERGE_CROSSOVER_L) was measured from.  The sign path's
    # pair operand grows as l^2, the merge path's stays O(l); above the
    # bound merge must win, and the gap must grow with l.
    ck_prev = None
    for l in (64, 96, 160, 256):
        xk = jnp.asarray(rng.standard_normal((32, l)).astype(np.float32))
        t_sign = timeit(lambda xk=xk: corr(xk, measure="kendall_sign_gemm",
                                           t=16, l_blk=32),
                        warmup=1, iters=1)
        t_merge = timeit(lambda xk=xk: corr(xk, measure="kendall_merge",
                                            t=16, l_blk=32),
                         warmup=1, iters=1)
        ratio = t_sign / t_merge
        emit(f"kernels/kendall_crossover_l{l}_sign", t_sign * 1e6,
             f"n=32;pairs={l * (l - 1) // 2}")
        emit(f"kernels/kendall_crossover_l{l}_merge", t_merge * 1e6,
             f"n=32;operand_l={l};speedup_vs_sign={ratio:.2f}")
        if l >= KENDALL_MERGE_CROSSOVER_L:
            assert ratio > 1.0, \
                f"merge must beat sign above the crossover (l={l})"
            if ck_prev is not None:
                assert ratio > ck_prev, "the merge gap must grow with l"
            ck_prev = ratio
    emit("kernels/kendall_crossover_dispatch", 0.0,
         f"crossover_l={KENDALL_MERGE_CROSSOVER_L};"
         f"auto_dispatch=resolve_tile_kernel")

    # Quantized operand sweep (ISSUE 8 tentpole b): f32/bf16/int8 (+fp8
    # when the backend's matmul supports it — probed, never assumed; a
    # skip row records absence in the bench JSON).  Two observables per
    # dtype x {small,large} l: the compiled XLA GEMM proxy (honest CPU
    # timing; XLA CPU has no int8 GEMM fast path, so int8 *compute* loses
    # here — on MXU hardware it wins) and a pure operand-streaming pass,
    # which is what an HBM-bound shape is bound by: time tracks operand
    # bytes, so int8/fp8 beat bf16 ~2x and f32 ~4x.
    f8 = fp8_dtype()
    dts = [("f32", jnp.float32), ("bf16", jnp.bfloat16),
           ("int8", jnp.int8)]
    if f8 is not None:
        dts.append(("fp8", f8))
    else:
        emit("kernels/quantized_fp8_skipped", 0.0,
             "fp8_matmul_unsupported_on_backend;probe=quantize.fp8_supported")

    def quant_gemm(dname, dt, u):
        if dname == "f32":
            return jax.jit(lambda q: jnp.dot(
                q, q.T, preferred_element_type=jnp.float32)), u, None
        if dname == "bf16":
            ub = u.astype(jnp.bfloat16)
            return jax.jit(lambda q: jnp.dot(
                q, q.T, preferred_element_type=jnp.float32)), ub, None
        q, s = quantize_rows(u, dt)
        if dname == "int8":
            fn = jax.jit(lambda q, s: jnp.dot(
                q, q.T, preferred_element_type=jnp.int32
            ).astype(jnp.float32) * (s[:, None] * s[None, :]))
        else:
            fn = jax.jit(lambda q, s: jnp.dot(
                q.astype(jnp.float32), q.astype(jnp.float32).T)
                * (s[:, None] * s[None, :]))
        return fn, q, s

    stream = jax.jit(lambda q: q + q.dtype.type(0))
    for lname, lq in (("small", 256), ("large", 16384)):
        xq = jnp.asarray(
            rng.standard_normal((256, lq)).astype(np.float32))
        uq = measures.PEARSON.transform(xq, dtype=jnp.float32)
        ref = jnp.dot(uq, uq.T, preferred_element_type=jnp.float32)
        base_stream = None
        for dname, dt in dts:
            fn, op, s = quant_gemm(dname, dt, uq)
            args = (op,) if s is None else (op, s)
            t_g = timeit(lambda: fn(*args), warmup=1, iters=3)
            err = float(jnp.max(jnp.abs(
                jnp.clip(fn(*args), -1, 1) - jnp.clip(ref, -1, 1))))
            emit(f"kernels/quantized_gemm_{dname}_l_{lname}", t_g * 1e6,
                 f"n=256;l={lq};operand_bytes={op.nbytes};"
                 f"err_pearson={err:.1e}")
            t_s = timeit(lambda: stream(op), warmup=1, iters=3)
            emit(f"kernels/quantized_stream_{dname}_l_{lname}", t_s * 1e6,
                 f"operand_bytes={op.nbytes}")
            if dname == "bf16":
                base_stream = t_s
            if dname == "int8" and lname == "large":
                # the HBM-bound acceptance: int8 moves half bf16's bytes
                assert t_s < base_stream, \
                    "int8 streaming must beat bf16 on the HBM-bound shape"


if __name__ == "__main__":
    run()
