"""Paper Fig. 2: parallel scalability vs number of accelerators.

Two components:
  (a) measured multi-device run: shard_map PCC over 1, 2, 4, ... of the
      devices this process sees (the chips of a TPU host, or CPU devices
      forced with XLA_FLAGS=--xla_force_host_platform_device_count=8) —
      one process, since a chip belongs to the process that opened it;
      rows verify correctness and report per-device tile counts;
  (b) the load-balance model: with T tiles and p devices the bound on
      speedup is T / (p * ceil(T/p)) * p; at paper scale the contiguous
      partition (C5) keeps this >= 99.9%, which is what underwrites the
      paper's measured 11.3-12.4x on 16 Phis.
"""

from __future__ import annotations

import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.configs import lightpcc
from repro.core import tiling
from repro.core.allpairs import execute_plan
from repro.core.api import corr
from repro.core.mapping import tri_count
from repro.core.pcc import pearson_gemm
from repro.core.plan import ExecutionPlan, tiles_per_device
from repro.core.sinks import (DeviceTopKSink, ShardedHostSink, TopKSink,
                              assemble)


def _balance(total: int, p: int) -> float:
    per = -(-total // p)
    return total / (p * per)


def run(mesh_part: bool = True) -> None:
    # (b) load-balance bound at paper scale
    for cfg in lightpcc.TABLES["table1"] + lightpcc.TABLES["table2"]:
        m = -(-cfg.n // cfg.t)
        total = tri_count(m)
        for p in (1, 2, 4, 8, 16):
            eff = _balance(total, p)
            emit(f"fig2/balance_{cfg.name}_p{p}", 0.0,
                 f"tiles={total};efficiency={eff:.4f};"
                 f"ideal_speedup={p * eff:.2f}")

    # (a) correctness + distribution across the devices present
    if not mesh_part:
        return
    devices = jax.devices()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((128, 64)).astype(np.float32))
    ref = pearson_gemm(x)
    plan = tiling.TilePlan.create(128, 64, 16)
    p = 1
    while p <= len(devices):
        mesh = jax.make_mesh((p,), ("d",), devices=devices[:p])
        t0 = time.perf_counter()
        r = corr(x, mesh=mesh, t=16, l_blk=32)
        jax.block_until_ready(r)
        dt = time.perf_counter() - t0
        err = float(jnp.max(jnp.abs(r - ref)))
        emit(f"fig2/measured_p{p}", dt * 1e6,
             f"tiles_per_dev={tiles_per_device(plan.total_tiles, p)};"
             f"maxerr={err:.1e}")
        p *= 2

    # multi-host scale-out: the mesh's devices split over 2 hosts write
    # disjoint shard files; the device-side top-k epilogue crosses O(n*k)
    # to hosts instead of O(n^2 / hosts).  (docs/scaling.md)
    p = len(devices)
    hosts = 2 if p % 2 == 0 else 1
    mesh = jax.make_mesh((p,), ("d",))
    ep = ExecutionPlan.create(128, 64, t=16, l_blk=32, p=p,
                              max_tiles_per_pass=4)
    u = ep.prepare(x)
    d = tempfile.mkdtemp()
    t0 = time.perf_counter()
    for h in range(hosts):
        r = execute_plan(ep, u, sink=ShardedHostSink(
            d, host=h, n_hosts=hosts), mesh=mesh)
        assert r["complete"], h
    dt = time.perf_counter() - t0
    err = float(np.max(np.abs(assemble(d) - np.asarray(ref))))
    host_bytes = ep.total_tiles * ep.t * ep.t * 4 // hosts
    emit(f"fig2/multihost_sharded_h{hosts}", dt * 1e6,
         f"hosts={hosts};devices={p};tiles={ep.total_tiles};"
         f"bytes_per_host={host_bytes};maxerr={err:.1e}")
    k = 8
    t0 = time.perf_counter()
    dtk = execute_plan(ep, u, sink=DeviceTopKSink(k), mesh=mesh)
    dt = time.perf_counter() - t0
    ep1 = ExecutionPlan.create(128, 64, t=16, l_blk=32, max_tiles_per_pass=4)
    tk = execute_plan(ep1, ep1.prepare(x), sink=TopKSink(k))
    same = (np.array_equal(dtk["indices"], tk["indices"])
            and np.array_equal(dtk["values"], tk["values"]))
    topk_bytes = 128 * k * 8
    emit("fig2/multihost_topk_device", dt * 1e6,
         f"k={k};bit_identical={int(same)};"
         f"bytes_to_host={topk_bytes};"
         f"dense_bytes_per_host={host_bytes};"
         f"crossing_ratio={host_bytes / topk_bytes:.1f}")


if __name__ == "__main__":
    run()
