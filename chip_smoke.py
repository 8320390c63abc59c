#!/usr/bin/env python3
"""Chip smoke test: the engine's main path on a TPU, at the width of the
paper's real dataset (SEEK GPL570, Table II: n = 17,555 genes x l = 5,072
samples), through the entry points a user calls.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # the mesh phase only, on 4 chips
    python chip_smoke.py --rehearse [--chips 4]
        # the same phases at a tiny size on whatever JAX finds (CPU,
        # interpret-mode kernels; XLA_FLAGS=
        # --xla_force_host_platform_device_count=4 gives the CPU 4 devices)

One chip, one process, in this order:
  1. require the chip: a TPU backend, compiled (not interpreted) kernels;
  2. corr(x) with Pearson and Spearman (DenseSink) against a float64
     numpy/scipy reference on REF_ROWS seeded rows x all columns;
  3. CorrServer over the corpus: batches of 1-50-probe gene-set queries,
     dense and top-k, each bit-identical to standalone corr();
  4. symmetric corr(x, sink=DeviceTopKSink(k)) bit-identical to TopKSink(k);
  5. compute_dtype=int8 Pearson within the pinned int8 error budget.
With --chips 4: corr(x, mesh=) replicated and shard_u, and DeviceTopKSink
on the mesh, each bit-identical to the same call on one device.

The data is a co-expression compendium drawn from --seed: every gene
follows one of PROGRAMS latent expression programs with a random signed
loading, plus its own noise — modules of correlated genes, as in a real
compendium, so top-k neighbours are a real ranking.

Lines starting with "[chip_smoke]" are information, not metrics.  The last
line of standard output is one JSON object naming the device; it is printed
only when every check passed.  Any failure exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import scipy.stats  # noqa: E402

from repro.configs.lightpcc import REAL_SEEK  # noqa: E402
from repro.core.api import corr  # noqa: E402
from repro.core.plan import resolve_interpret  # noqa: E402
from repro.core.sinks import DeviceTopKSink, TopKSink  # noqa: E402
from repro.kernels.pcc_tile import pcc_tiles  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving.server import CorrServer  # noqa: E402

REF_ROWS = 512      # reference rows compared against every column
TOP_K = 50          # served and symmetric top-k depth
PROGRAMS = 64       # latent expression programs of the synthetic compendium
MAX_PROBES = 50     # SEEK query gene sets hold 1-50 genes
# f32 path vs float64: the row transform and the kernel's dot both round in
# f32; a unit-norm dot over l = 5,072 samples accumulates about
# sqrt(l) * 2^-24 ~ 4e-6 of rounding, the transform adds a few ulps per
# entry.  5e-5 leaves >10x headroom yet fails a kernel that rounds its
# operands to bf16 (2^-9 per operand, ~1e-4 and up at this l).
PEARSON_TOL = 5e-5
# Spearman is Pearson on ranks (exact in f32 up to 2^24): same bound.
SPEARMAN_TOL = 5e-5
# the int8 Pearson budget pinned in tests/test_quantized.py (BUDGETS),
# measured against the f32 result, as there
INT8_BUDGET = 8e-3


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Checks:
    """Every check is logged; main() exits non-zero if any failed."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> None:
        log(f"{'PASS' if ok else 'FAIL'} {name} {detail}".rstrip())
        if not ok:
            self.failed.append(name)


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported"
    return str(stats["peak_bytes_in_use"])


def timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def require_chip(args) -> None:
    devices = jax.devices()
    d = devices[0]
    log(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devices)}")
    if not args.rehearse:
        if d.platform != "tpu":
            sys.exit(f"chip_smoke: no TPU — JAX found {d.platform} devices "
                     f"(a TPU backend that fails to start falls back to CPU)")
        if resolve_interpret(None):
            sys.exit("chip_smoke: the plan would interpret the kernels")
        lowered = pcc_tiles.lower(
            jax.ShapeDtypeStruct((256, 512), jnp.float32),
            jax.ShapeDtypeStruct((), jnp.int32), pass_tiles=1)
        if "tpu_custom_call" not in lowered.as_text():
            sys.exit("chip_smoke: pcc_tiles did not lower to a TPU kernel")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devices)}")


def make_compendium(seed: int, n: int, l: int) -> jax.Array:
    """(n, l) expression drawn on the device: gene i = a_i * f_prog(i) +
    sqrt(1 - a_i^2) * noise_i with a signed loading |a_i| in [0.3, 0.95]."""
    @jax.jit
    def draw(key):
        kp, ka, ks, kf, ke = jax.random.split(key, 5)
        prog = jax.random.randint(kp, (n,), 0, PROGRAMS)
        sign = jnp.where(jax.random.bernoulli(ks, 0.5, (n,)), 1.0, -1.0)
        load = sign * jax.random.uniform(ka, (n,), minval=0.3, maxval=0.95)
        factors = jax.random.normal(kf, (PROGRAMS, l))
        noise = jax.random.normal(ke, (n, l))
        return (load[:, None] * factors[prog]
                + jnp.sqrt(1.0 - load * load)[:, None] * noise)
    return jax.block_until_ready(draw(jax.random.PRNGKey(seed)))


def f64_rows(z: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Pearson of rows `rows` against every row of z, in float64."""
    zc = z - z.mean(axis=1, keepdims=True)
    zn = zc / np.linalg.norm(zc, axis=1, keepdims=True)
    return zn[rows] @ zn.T


def phase_dense(x, rows, check) -> np.ndarray:
    """corr(x) for Pearson and Spearman against float64; returns the f32
    Pearson rows for the int8 phase."""
    x64 = np.asarray(x, np.float64)
    refs = {"pearson": (lambda: f64_rows(x64, rows), PEARSON_TOL),
            "spearman": (lambda: f64_rows(
                scipy.stats.rankdata(x64, axis=1), rows), SPEARMAN_TOL)}
    pearson_rows = None
    for measure, (ref_fn, tol) in refs.items():
        r, cold = timed(lambda: corr(x, measure=measure))
        got = np.asarray(r[rows])
        del r
        r, warm = timed(lambda: corr(x, measure=measure))
        del r
        t0 = time.perf_counter()
        ref = ref_fn()
        t_ref = time.perf_counter() - t0
        err = float(np.abs(got - ref).max())
        log(f"info {measure}: max|dr| vs float64 = {err!r} over "
            f"{len(rows)} rows x {ref.shape[1]} cols; first call "
            f"{cold:.3f} s, second call {warm:.3f} s, compile and transform "
            f"~{cold - warm:.3f} s; reference {t_ref:.1f} s; peak bytes "
            f"{peak_bytes()}")
        check(f"{measure} within {tol:g} of float64", err <= tol,
              f"(max|dr| {err!r})")
        if measure == "pearson":
            pearson_rows = got
    return pearson_rows


def phase_server(x, rng, check, batches: int = 3, per_batch: int = 8):
    n = x.shape[0]
    served = {"dense": 0, "topk": 0}
    t0 = time.perf_counter()
    with CorrServer(x, max_wait_s=0.05) as srv:
        for _ in range(batches):
            pending = []
            for q in range(per_batch):
                genes = rng.choice(n, int(rng.integers(1, MAX_PROBES + 1)),
                                   replace=False)
                probes = x[np.sort(genes)]
                k = TOP_K if q % 2 else None
                pending.append((probes, k, srv.submit(probes, k=k)))
            for probes, k, fut in pending:
                got = fut.result().value
                if k is None:
                    want = np.asarray(corr(probes, x))
                    ok = np.array_equal(np.asarray(got), want)
                    served["dense"] += 1
                else:
                    want = corr(probes, x, sink=TopKSink(k))
                    ok = (np.array_equal(got["indices"], want["indices"])
                          and np.array_equal(got["values"], want["values"]))
                    served["topk"] += 1
                check(f"served {'dense' if k is None else f'top-{k}'} "
                      f"{probes.shape[0]} probes == corr()", ok)
        stats = srv.stats()
    log(f"info server: {served['dense']} dense + {served['topk']} top-k "
        f"queries in {stats.get('batches')} batches, "
        f"{time.perf_counter() - t0:.1f} s with references; peak bytes "
        f"{peak_bytes()}")


def phase_device_topk(x, check) -> None:
    got, t_dev = timed(lambda: corr(x, sink=DeviceTopKSink(TOP_K)))
    t0 = time.perf_counter()
    # the host reference in passes of 512 tiles bounds its host memory;
    # the canonical merge makes the result independent of the pass split
    want = corr(x, sink=TopKSink(TOP_K), max_tiles_per_pass=512)
    t_host = time.perf_counter() - t0
    log(f"info symmetric top-{TOP_K}: DeviceTopKSink {t_dev:.1f} s, "
        f"TopKSink {t_host:.1f} s; peak bytes {peak_bytes()}")
    check(f"symmetric DeviceTopKSink({TOP_K}) == TopKSink({TOP_K})",
          np.array_equal(got["indices"], want["indices"])
          and np.array_equal(got["values"], want["values"]))


def phase_int8(x, rows, pearson_rows, check) -> None:
    r8, t8 = timed(lambda: corr(x, compute_dtype=jnp.int8))
    got = np.asarray(r8[rows])
    del r8
    err = float(np.abs(got - pearson_rows).max())
    log(f"info int8 pearson: max|dr| vs f32 = {err!r}, first call "
        f"{t8:.3f} s; peak bytes {peak_bytes()}")
    check(f"int8 pearson within {INT8_BUDGET:g} of f32", err <= INT8_BUDGET,
          f"(max|dr| {err!r})")


def phase_mesh(x, chips: int) -> None:
    """corr(mesh=) on `chips` devices, bit-identical to one device."""
    mesh = jax.make_mesh((chips,), ("d",), devices=jax.devices()[:chips])
    local, t_local = timed(lambda: corr(x))
    local = np.asarray(local)
    for shard_u in (False, True):
        got, t_mesh = timed(lambda: corr(x, mesh=mesh, shard_u=shard_u))
        log(f"info mesh shard_u={shard_u}: {t_mesh:.3f} s (one device "
            f"{t_local:.3f} s); output {got.sharding}")
        np.testing.assert_array_equal(np.asarray(got), local)
        log(f"PASS corr(mesh={chips} chips, shard_u={shard_u}) == one chip")
        del got
    want = corr(x, sink=DeviceTopKSink(TOP_K))
    got = corr(x, mesh=mesh, sink=DeviceTopKSink(TOP_K))
    np.testing.assert_array_equal(got["indices"], want["indices"])
    np.testing.assert_array_equal(got["values"], want["values"])
    log(f"PASS DeviceTopKSink({TOP_K}) on {chips} chips == one chip")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the mesh phase, across 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any backend (no TPU required)")
    args = ap.parse_args()

    enable_compile_cache()
    require_chip(args)
    n, l = (600, 300) if args.rehearse else (REAL_SEEK.n, REAL_SEEK.l)
    t0 = time.perf_counter()
    x = make_compendium(args.seed, n, l)
    log(f"info compendium {n} x {l} drawn in "
        f"{time.perf_counter() - t0:.2f} s (seed {args.seed})")

    if args.chips > 1:
        phase_mesh(x, args.chips)
    else:
        check = Checks()
        rng = np.random.default_rng(args.seed)
        rows = np.sort(rng.choice(n, min(REF_ROWS, n), replace=False))
        pearson_rows = phase_dense(x, rows, check)
        phase_server(x, rng, check)
        phase_device_topk(x, check)
        phase_int8(x, rows, pearson_rows, check)
        if check.failed:
            sys.exit(f"chip_smoke: {len(check.failed)} check(s) failed: "
                     f"{check.failed}")

    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
