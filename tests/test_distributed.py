"""Distributed drivers on 8 simulated devices.

These run in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
(4 where stated) so the main pytest process keeps seeing 1 device (per the
project rule that only dryrun.py forces a device count).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_ENV = dict(os.environ,
            XLA_FLAGS="--xla_force_host_platform_device_count=8",
            PYTHONPATH=os.path.join(os.path.dirname(__file__), "..", "src"))


def _run(body: str, devices: int = 8):
    code = textwrap.dedent(body)
    env = dict(_ENV, XLA_FLAGS=f"--xla_force_host_platform_device_count="
                               f"{devices}")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, f"stdout:\n{res.stdout}\nstderr:\n{res.stderr}"
    return res.stdout


def test_sharded_pcc_matches_single_device():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import (allpairs_pcc_sharded,
                                            allpairs_pcc_sharded_u)
        from repro.core.pcc import pearson_gemm
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((50, 37)).astype(np.float32))
        ref = pearson_gemm(x)
        for mesh_shape, axes in [((8,), ("d",)), ((4, 2), ("a", "b"))]:
            mesh = jax.make_mesh(mesh_shape, axes)
            r = allpairs_pcc_sharded(x, mesh, t=8, l_blk=16)
            assert float(jnp.max(jnp.abs(r - ref))) < 3e-6, mesh_shape
            r2 = allpairs_pcc_sharded_u(x, mesh, t=8, l_blk=16)
            assert float(jnp.max(jnp.abs(r2 - ref))) < 3e-6, mesh_shape
        print("OK")
    """)


def test_sharded_pcc_multipass():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import allpairs_pcc_sharded
        from repro.core.pcc import pearson_gemm
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((64, 20)).astype(np.float32))
        mesh = jax.make_mesh((8,), ("d",))
        r = allpairs_pcc_sharded(x, mesh, t=8, l_blk=8, max_tiles_per_pass=2)
        assert float(jnp.max(jnp.abs(r - pearson_gemm(x)))) < 3e-6
        print("OK")
    """)


def test_sharded_measures_match_dense_oracle():
    """Path parity for every registered measure: both sharded drivers agree
    with the dense transform+GEMM oracle (one subprocess amortises startup)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.distributed import (allpairs_pcc_sharded,
                                            allpairs_pcc_sharded_u)
        from repro.core.measures import available, dense_reference
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.standard_normal((30, 17)).astype(np.float32))
        mesh = jax.make_mesh((8,), ("d",))
        for name in available():
            ref = dense_reference(x, name)
            r = allpairs_pcc_sharded(x, mesh, t=8, l_blk=8, measure=name)
            err = float(jnp.max(jnp.abs(r - ref)))
            assert err < 1e-5, (name, err)
            r2 = allpairs_pcc_sharded_u(x, mesh, t=8, l_blk=8, measure=name)
            err2 = float(jnp.max(jnp.abs(r2 - ref)))
            assert err2 < 1e-5, (name, err2)
        print("OK")
    """)


def test_sharded_streaming_bit_identical_to_materializing_path():
    """Both sharded drivers, through the streaming executor, are
    bit-identical to the pre-refactor materializing pipeline (inlined here:
    one shard_map producing the full (p*per_dev, t, t) global array, then a
    single clamped-id scatter), on 1-D and 2-D meshes."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from jax import shard_map
        from repro.core import measures
        from repro.core.allpairs import prepare, scatter_tiles, symmetrize
        from repro.core.distributed import (allpairs_pcc_sharded,
                                            allpairs_pcc_sharded_u,
                                            tiles_per_device)
        from repro.kernels.pcc_tile import pcc_tiles

        def legacy_sharded(x, mesh, t, l_blk, max_tiles_per_pass=None):
            n = x.shape[0]
            axes = tuple(mesh.axis_names)
            p = int(np.prod(mesh.devices.shape))
            u_pad, plan = prepare(x, t=t, l_blk=l_blk)
            spec, _ = measures.resolve_fusion(measures.PEARSON, True, plan.l)
            total = plan.total_tiles
            per_dev = tiles_per_device(total, p)
            pass_tiles = min(per_dev, max_tiles_per_pass or per_dev)
            n_pass = -(-per_dev // pass_tiles)
            def device_fn(u_rep):
                rank = jnp.int32(0)
                for ax in axes:
                    rank = rank * mesh.shape[ax] + jax.lax.axis_index(ax)
                outs = []
                for k in range(n_pass):
                    j0 = jnp.minimum(rank * per_dev + k * pass_tiles,
                                     total - 1)
                    outs.append(pcc_tiles(u_rep, j0, t=t, l_blk=l_blk,
                                          pass_tiles=pass_tiles,
                                          interpret=True, epilogue=spec))
                return jnp.concatenate(outs, axis=0)[:per_dev]
            spec_rep = P(*([None] * u_pad.ndim))
            fn = shard_map(device_fn, mesh=mesh, in_specs=(spec_rep,),
                           out_specs=P(axes), check_vma=False)
            u_rep = jax.device_put(u_pad, NamedSharding(mesh, spec_rep))
            tiles = fn(u_rep)  # the (p*per_dev, t, t) global array
            ids = np.minimum(np.arange(p * per_dev), total - 1)
            r_pad = jnp.zeros((plan.n_pad, plan.n_pad), jnp.float32)
            r_pad = scatter_tiles(r_pad, tiles, ids, t, plan.m)
            return symmetrize(r_pad, n)

        rng = np.random.default_rng(21)
        x = jnp.asarray(rng.standard_normal((50, 37)).astype(np.float32))
        for mesh_shape, axes in [((8,), ("d",)), ((4, 2), ("a", "b"))]:
            mesh = jax.make_mesh(mesh_shape, axes)
            for mtp in (None, 2):
                want = np.asarray(legacy_sharded(x, mesh, 8, 16,
                                                 max_tiles_per_pass=mtp))
                got = np.asarray(allpairs_pcc_sharded(
                    x, mesh, t=8, l_blk=16, max_tiles_per_pass=mtp))
                np.testing.assert_array_equal(got, want), (mesh_shape, mtp)
            got_u = np.asarray(allpairs_pcc_sharded_u(x, mesh, t=8, l_blk=16))
            want_u = np.asarray(legacy_sharded(x, mesh, 8, 16))
            np.testing.assert_array_equal(got_u, want_u)
        print("OK")
    """)


def test_sharded_output_memory_bounded_by_pass():
    """The executor never materialises the (p*per_dev, t, t) global array:
    every per-pass buffer is bounded by max_tiles_per_pass tiles *per
    device* (inspected via addressable_shards), on a 4- and 8-device mesh."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.allpairs import allpairs
        from repro.core.plan import ExecutionPlan
        from repro.core.sinks import DenseSink, HostSink
        from repro.core.pcc import pearson_gemm

        class Probe:
            '''Wrap a sink; assert every device buffer it is handed obeys
            the per-device pass bound (mtp tiles of t*t f32).'''
            def __init__(self, inner, p, mtp, t, per_dev):
                self.inner, self.p, self.mtp = inner, p, mtp
                self.t, self.per_dev = t, per_dev
                self.passes = 0
            def open(self, plan):
                self.inner.open(plan)
            def _check(self, tiles):
                assert tiles.shape[0] <= self.p * self.mtp, tiles.shape
                assert tiles.shape[0] < self.p * self.per_dev
                for shard in tiles.addressable_shards:
                    assert shard.data.size <= self.mtp * self.t * self.t, \
                        shard.data.shape
                self.passes += 1
            def consume(self, ids, tiles):
                self._check(tiles)
                self.inner.consume(ids, tiles)
            def consume_clamped(self, padded, sel, ids, tiles):
                self._check(tiles)
                self.inner.consume_clamped(padded, sel, ids, tiles)
            def result(self):
                return self.inner.result()

        rng = np.random.default_rng(22)
        x = jnp.asarray(rng.standard_normal((96, 24)).astype(np.float32))
        ref = np.asarray(pearson_gemm(x))
        t, mtp = 8, 3
        for p in (4, 8):
            mesh = jax.make_mesh((p,), ("d",))
            plan = ExecutionPlan.create(96, 24, t=t, l_blk=8, p=p,
                                        max_tiles_per_pass=mtp)
            assert plan.n_pass > 1, "bound not exercised"
            for inner in (DenseSink(), HostSink()):
                probe = Probe(inner, p, mtp, t, plan.per_dev)
                r = np.asarray(allpairs(x, mesh=mesh, t=t, l_blk=8,
                                        max_tiles_per_pass=mtp, sink=probe))
                assert probe.passes == plan.n_pass
                assert np.abs(r - ref).max() < 3e-6
        print("OK")
    """)


def test_sharded_sink_streaming_reduction():
    """A streaming EdgeCountSink on the mesh path agrees with the dense
    adjacency — no n x n array on any device or host."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.allpairs import allpairs
        from repro.core.sinks import EdgeCountSink
        from repro.core.pcc import pearson_gemm
        rng = np.random.default_rng(23)
        n = 60
        x = jnp.asarray(rng.standard_normal((n, 20)).astype(np.float32))
        mesh = jax.make_mesh((8,), ("d",))
        thr = 0.3
        got = allpairs(x, mesh=mesh, t=8, l_blk=8, max_tiles_per_pass=2,
                       sink=EdgeCountSink(thr))
        ref = np.asarray(pearson_gemm(x))
        adj = (np.abs(ref) >= thr) & ~np.eye(n, dtype=bool)
        assert got["edges"] == int(adj.sum()) // 2
        np.testing.assert_array_equal(got["degrees"], adj.sum(1))
        print("OK")
    """)


def test_sharded_corr_facade_all_workloads():
    """corr() on a mesh: symmetric runs are bit-identical to the local
    facade for every measure; rectangular and masked runs match their
    dense oracles — one subprocess, 8 devices, 1- and 2-axis meshes."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.api import corr
        from repro.core import measures
        rng = np.random.default_rng(31)
        x = jnp.asarray(rng.standard_normal((50, 20)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((26, 20)).astype(np.float32))
        mesh = jax.make_mesh((8,), ("d",))
        for name in measures.available():
            local = np.asarray(corr(x, t=8, l_blk=8, measure=name))
            shard = np.asarray(corr(x, t=8, l_blk=8, measure=name,
                                    mesh=mesh, max_tiles_per_pass=2))
            np.testing.assert_array_equal(shard, local, err_msg=name)
        ref = np.asarray(measures.dense_reference_pair(x, y))
        for mesh_k in (mesh, jax.make_mesh((4, 2), ("a", "b"))):
            rect = np.asarray(corr(x, y, t=8, l_blk=8, mesh=mesh_k,
                                   max_tiles_per_pass=3))
            assert np.abs(rect - ref).max() < 1e-5
        xm = np.asarray(x).copy()
        xm[rng.random(xm.shape) < 0.3] = np.nan
        xmj = jnp.asarray(xm)
        mref = np.asarray(measures.masked_dense_reference(
            xmj, ~jnp.isnan(xmj)))
        got = np.asarray(corr(xmj, where="nan", t=8, l_blk=8, mesh=mesh,
                              max_tiles_per_pass=4))
        assert np.abs(got - mref).max() < 1e-5
        print("OK")
    """)


@pytest.mark.parametrize("workload", ["symmetric", "shard_u", "rectangular",
                                      "device_topk"])
def test_corr_mesh_explicit_and_auto_axes(workload):
    """corr(mesh=) takes the Explicit-axis meshes jax.make_mesh builds by
    default and Auto-axis ones alike, bit-identical to one device, on 4
    devices; a dense result lives replicated on the mesh, not on one
    device."""
    _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType
        from repro.core.api import corr
        from repro.core.sinks import DeviceTopKSink
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.standard_normal((44, 20)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((27, 20)).astype(np.float32))
        kw = dict(t=8, l_blk=8)
        args, extra = {{
            "symmetric": ((x,), {{}}),
            "shard_u": ((x,), dict(shard_u=True)),
            "rectangular": ((x, y), {{}}),
            "device_topk": ((x,), dict(sink=DeviceTopKSink(5))),
        }}["{workload}"]
        local = corr(*args, **kw, **{{k: v for k, v in extra.items()
                                      if k != "shard_u"}})
        for axis_type in (AxisType.Explicit, AxisType.Auto):
            mesh = jax.make_mesh((4,), ("d",), axis_types=(axis_type,))
            got = corr(*args, **kw, **extra, mesh=mesh,
                       max_tiles_per_pass=3)
            if isinstance(got, dict):
                for key in ("indices", "values"):
                    np.testing.assert_array_equal(got[key], local[key])
                continue
            assert got.sharding.is_fully_replicated, got.sharding
            assert got.sharding.device_set == set(mesh.devices.flat)
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(local))
        print("OK")
    """, devices=4)


@pytest.mark.parametrize("axis_type", ["Explicit", "Auto"])
@pytest.mark.parametrize("workload", ["symmetric", "rectangular"])
def test_mesh_dense_sink_gathers_on_device(workload, axis_type):
    """A dense corr(mesh=) moves each pass's mesh-sharded tiles to the
    replicated result inside the compiled scatter: no array is fetched to
    the host in DenseSink._scatter (full and clamped passes alike), the
    scatter program holds an all-gather, and an equal, newly built mesh
    reuses that program."""
    _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax._src.array import ArrayImpl
        from jax.sharding import AxisType
        import repro.core.sinks as sinks
        from repro.core.api import corr

        fetched = {{"inside": False, "n": 0}}
        value = ArrayImpl._value
        def counted(self):
            fetched["n"] += fetched["inside"]
            return value.fget(self)
        ArrayImpl._value = property(counted)
        scatter = sinks.DenseSink._scatter
        def watched(self, ids, tiles):
            fetched["inside"] = True
            try:
                scatter(self, ids, tiles)
            finally:
                fetched["inside"] = False
        sinks.DenseSink._scatter = watched
        program = sinks._scatter_tiles_device
        seen = []
        def recorded(*args, **kw):
            seen.append((args, kw))
            return program(*args, **kw)
        sinks._scatter_tiles_device = recorded

        rng = np.random.default_rng(14)
        x = jnp.asarray(rng.standard_normal((44, 20)).astype(np.float32))
        y = jnp.asarray(rng.standard_normal((27, 20)).astype(np.float32))
        args = {{"symmetric": (x,), "rectangular": (x, y)}}["{workload}"]
        axis_types = (AxisType.{axis_type},)
        def solve():
            mesh = jax.make_mesh((4,), ("d",), axis_types=axis_types)
            return corr(*args, t=8, l_blk=8, mesh=mesh,
                        max_tiles_per_pass=3)
        got = solve()
        assert got.sharding.is_fully_replicated, got.sharding
        assert len(seen) > 1, len(seen)  # a full and a clamped pass
        assert fetched["n"] == 0, fetched
        for args_, kw in seen:
            assert kw["placement"] is not None
            text = program.lower(*args_, **kw).compile().as_text()
            assert "all-gather" in text
        built = program._cache_size()
        again = solve()
        assert program._cache_size() == built
        assert fetched["n"] == 0, fetched
        np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
        print("OK")
    """, devices=4)


@pytest.mark.slow
def test_pjit_train_matches_single_device_loss():
    """The sharded train step computes the same loss as unsharded."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models.config import ModelConfig
        from repro.models.registry import build_model
        from repro.models import steps
        from repro.models.sharding import make_policy
        from repro.optim import adamw
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = ModelConfig(arch="t", n_layers=2, d_model=64, n_heads=4,
                          n_kv_heads=2, d_ff=128, vocab=256, dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        opt_cfg = adamw.AdamWConfig(total_steps=10)
        opt = adamw.init(opt_cfg, params)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0, 256)
        labs = jax.random.randint(jax.random.PRNGKey(2), (8, 64), 0, 256)

        _, _, m0 = jax.jit(steps.make_train_step(cfg, opt_cfg))(
            params, opt, tokens=toks, labels=labs)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        policy = make_policy(cfg, mesh)
        shardings = policy.params_shardings(cfg, model.init_shapes())
        params_s = jax.device_put(params, shardings)
        opt_s = adamw.init(opt_cfg, params_s)
        bsh = NamedSharding(mesh, P(("data",), None))
        step = jax.jit(steps.make_train_step(cfg, opt_cfg, policy=policy))
        _, _, m1 = step(params_s, opt_s,
                        tokens=jax.device_put(toks, bsh),
                        labels=jax.device_put(labs, bsh))
        d = abs(float(m0["loss"]) - float(m1["loss"]))
        assert d < 1e-4, d
        print("OK", d)
    """)


def test_elastic_remesh_pcc_renumbering():
    """After dropping devices, the PCC re-partition covers all tiles."""
    _run("""
        import jax
        from repro.runtime import elastic
        from repro.core import tiling
        from repro.core.plan import ExecutionPlan
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        plan = elastic.elastic_pcc_plan(mesh, n_failed=2, total_tiles=1000)
        assert plan.new_shape == (3, 2)
        ranges = plan.new_tile_ranges
        assert len(ranges) == 6
        covered = sum(hi - lo for lo, hi in ranges)
        assert covered == 1000
        sizes = [hi - lo for lo, hi in ranges]
        assert max(sizes) - min(sizes) <= 1

        # with an ExecutionPlan, recovery is a pure plan re-slice
        ep = ExecutionPlan.create(352, 16, t=8, p=8, max_tiles_per_pass=64)
        assert ep.total_tiles == 990  # m=44 -> 44*45/2
        plan2 = elastic.elastic_pcc_plan(mesh, n_failed=2, total_tiles=990,
                                         exec_plan=ep)
        ep2 = plan2.new_exec_plan
        assert ep2.p == 6 and ep2.measure is ep.measure
        assert ep2.tile == ep.tile
        assert sum(hi - lo for lo, hi in ep2.device_ranges) == 990
        print("OK")
    """)


def test_multihost_sharded_sink_and_topk_bit_identical():
    """The multi-host story end to end on the 8-device mesh: per-host
    shard files are disjoint, assemble == single-host DenseSink, the
    device-side top-k epilogue == single-host TopKSink bit-for-bit — and
    both survive an injected device loss (mesh shrink mid-run) plus a
    crash + resume without changing a bit."""
    _run("""
        import json, os, tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.plan import ExecutionPlan
        from repro.core.allpairs import execute_plan
        from repro.core.sinks import (DenseSink, DeviceTopKSink,
                                      ShardedHostSink, TopKSink, assemble)
        from repro.runtime.faults import CrashFault, FaultPlan, RetryPolicy

        mesh = jax.make_mesh((8,), ("d",))
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(40, 16)).astype(np.float32))
        plan = ExecutionPlan.create(40, 16, t=8, l_blk=8, p=8,
                                    max_tiles_per_pass=1)
        u = plan.prepare(x)
        plan1 = ExecutionPlan.create(40, 16, t=8, l_blk=8,
                                     max_tiles_per_pass=4)
        u1 = plan1.prepare(x)
        ref = np.asarray(execute_plan(plan1, u1, sink=DenseSink()))
        tk = execute_plan(plan1, u1, sink=TopKSink(5))

        # 2 hosts x 4 devices: disjoint files, assemble == dense
        d = tempfile.mkdtemp()
        for h in range(2):
            r = execute_plan(plan, u, sink=ShardedHostSink(
                d, host=h, n_hosts=2), mesh=mesh)
            assert r["complete"], h
        files = [set(c["file"] for c in json.load(
                     open(os.path.join(d, f"manifest.h{h}.json")))["chunks"])
                 for h in range(2)]
        assert files[0] and files[1] and not (files[0] & files[1])
        np.testing.assert_array_equal(assemble(d), ref)

        # merged device-side top-k == single-host TopKSink, bit for bit
        dtk = execute_plan(plan, u, sink=DeviceTopKSink(5), mesh=mesh)
        np.testing.assert_array_equal(dtk["indices"], tk["indices"])
        np.testing.assert_array_equal(dtk["values"], tk["values"])

        # device loss mid-run (8 -> 7 shrink): still bit-identical
        pol = RetryPolicy(sleep=lambda s: None)
        with FaultPlan.single("pass_launch", "device_loss", at=2).armed():
            dtk2 = execute_plan(plan, u, sink=DeviceTopKSink(5), mesh=mesh,
                                recovery=pol)
        assert [e["action"] for e in pol.log] == ["shrink_mesh"]
        np.testing.assert_array_equal(dtk2["indices"], tk["indices"])
        np.testing.assert_array_equal(dtk2["values"], tk["values"])

        # device loss on one host's sharded write, crash + resume on the
        # other: assemble still == dense
        d2 = tempfile.mkdtemp()
        pol = RetryPolicy(sleep=lambda s: None)
        with FaultPlan.single("pass_launch", "device_loss", at=2).armed():
            r = execute_plan(plan, u, sink=ShardedHostSink(
                d2, host=0, n_hosts=2), mesh=mesh, recovery=pol)
        assert r["complete"]
        try:
            with FaultPlan.single("sink_commit", "crash", at=2).armed():
                execute_plan(plan, u, sink=ShardedHostSink(
                    d2, host=1, n_hosts=2), mesh=mesh)
            raise SystemExit("crash fault did not fire")
        except CrashFault:
            pass
        r = execute_plan(plan, u, sink=ShardedHostSink(
            d2, host=1, n_hosts=2, resume=True), mesh=mesh)
        assert r["complete"]
        np.testing.assert_array_equal(assemble(d2), ref)
        print("OK")
    """)


def test_dense_sink_survives_device_loss():
    """A dense run on a mesh that loses a device after its first passes
    landed (8 -> 7 shrink): the replicated result moves onto the
    survivors' mesh and the output is bit-identical to one device."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        import repro.core.sinks as sinks
        from repro.core.plan import ExecutionPlan
        from repro.core.allpairs import execute_plan
        from repro.runtime.faults import FaultPlan, RetryPolicy

        held = []  # devices holding the result after each scatter
        scatter = sinks.DenseSink._scatter
        def watched(self, ids, tiles):
            scatter(self, ids, tiles)
            held.append(len(self.r_pad.sharding.device_set))
        sinks.DenseSink._scatter = watched

        mesh = jax.make_mesh((8,), ("d",))
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.normal(size=(96, 16)).astype(np.float32))
        plan1 = ExecutionPlan.create(96, 16, t=8, l_blk=8,
                                     max_tiles_per_pass=4)
        ref = np.asarray(execute_plan(plan1, plan1.prepare(x),
                                      sink=sinks.DenseSink()))
        plan = ExecutionPlan.create(96, 16, t=8, l_blk=8, p=8,
                                    max_tiles_per_pass=1)
        held.clear()
        pol = RetryPolicy(sleep=lambda s: None)
        with FaultPlan.single("pass_launch", "device_loss", at=3).armed():
            got = execute_plan(plan, plan.prepare(x), sink=sinks.DenseSink(),
                               mesh=mesh, recovery=pol)
        assert [e["action"] for e in pol.log] == ["shrink_mesh"]
        assert held[0] == 8 and held[-1] == 7, held
        assert len(got.sharding.device_set) == 7 and \\
            got.sharding.is_fully_replicated, got.sharding
        np.testing.assert_array_equal(np.asarray(got), ref)
        print("OK")
    """)


def test_mesh_backed_server_identity_and_host_occupancy():
    """CorrServer over an 8-device mesh: one multi-host launch per
    coalesced batch (the top-k path rides the device-side epilogue),
    results bit-identical to local corr(), and stats() reports per-host
    occupancy of the mesh launches."""
    _run("""
        import jax, numpy as np
        from repro.core.api import corr
        from repro.core.sinks import TopKSink
        from repro.serving.server import CorrServer

        rng = np.random.default_rng(9)
        corpus = rng.normal(size=(48, 16)).astype(np.float32)
        probes = rng.normal(size=(5, 16)).astype(np.float32)
        mesh = jax.make_mesh((8,), ("d",))
        with CorrServer(corpus, t=8, l_blk=8, max_wait_s=0.0,
                        mesh=mesh) as srv:
            dense = srv.query(probes)
            topk = srv.query(probes, k=4)
            st = srv.stats()
        np.testing.assert_array_equal(
            np.asarray(dense.value),
            np.asarray(corr(probes, corpus, t=8, l_blk=8)))
        cold = corr(probes, corpus, t=8, l_blk=8, sink=TopKSink(4))
        np.testing.assert_array_equal(topk.value["indices"],
                                      np.asarray(cold["indices"]))
        np.testing.assert_array_equal(topk.value["values"],
                                      np.asarray(cold["values"]))
        ho = st["host_occupancy"]
        assert ho is not None and len(ho) == 8
        assert 0.0 <= min(ho) and max(ho) <= 1.0 and sum(ho) > 0
        print("OK")
    """)


def test_compressed_psum_shard_map():
    """int8 error-feedback all-reduce: mean error bounded, feedback works."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from jax import shard_map
        from repro.optim.compression import compressed_psum
        mesh = jax.make_mesh((8,), ("d",), axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(0)
        g_all = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))

        def f(g, e):
            avg, e2 = compressed_psum(g[0], "d", e[0])
            return avg[None], e2[None]
        fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("d"), P("d")),
                               out_specs=(P("d"), P("d")),
                               check_vma=False))
        err = jnp.zeros((8, 64), jnp.float32)
        avg, err = fn(g_all, err)
        true_avg = g_all.mean(0)
        # every rank ends with (approximately) the true average
        for i in range(8):
            q_err = float(jnp.max(jnp.abs(avg[i] - true_avg)))
            assert q_err < 0.1, q_err
        # error feedback state holds the residual
        assert float(jnp.max(jnp.abs(err))) > 0
        print("OK")
    """)
