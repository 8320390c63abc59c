"""Compile rehearsals: the main path's kernels, at the width of the paper's
real dataset (SEEK GPL570, n = 17,555 genes x l = 5,072 samples), compiled
for a described TPU v5e chip — no chip attached, nothing run.

Interpret-mode tests cannot see what the TPU compiler refuses (a block
shape off the 8 x 128 tiling, a primitive Mosaic does not lower, a program
past the device's memory); these compiles can, at no chip time.  Each
kernel goes through the executor's own launch seam with the plan the
engine would build, so what compiles here is what ``corr()`` and
``CorrServer`` launch on the chip.

The topology is described inside a fixture, never at import: only one
process may load the TPU compiler's library, so a test worker that is not
given this file must not touch it.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.lightpcc import REAL_SEEK
from repro.core.allpairs import launch_tiles, launch_topk_tiles
from repro.core.plan import ExecutionPlan
from repro.core.quantize import Operand
from repro.serving.plan_cache import bucket_rows

TOP_K = 50          # the served top-k depth chip_smoke.py uses
PROBES = 50         # the largest SEEK query gene set


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a described chip's executables cannot be read back without the chip:
    # keep them out of any persistent compilation cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _chip_plan(**kw) -> ExecutionPlan:
    # the plan corr() builds on a TPU backend: compiled Pallas, one pass
    plan = ExecutionPlan.create(kw.pop("n", REAL_SEEK.n), REAL_SEEK.l, **kw)
    return dataclasses.replace(plan, interpret=False)


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _operand(one_chip, plan, rows):
    l_pad = -(-plan.l // plan.l_blk) * plan.l_blk
    dtype = jnp.float32 if plan.compute_dtype is None else plan.compute_dtype
    return _spec(one_chip, (rows, l_pad), dtype)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("compute_dtype", [None, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_triangle_compiles(one_chip, compute_dtype):
    """corr(x): the whole GPL570 triangle in one pass — 2,415 tiles."""
    plan = _chip_plan(compute_dtype=compute_dtype)
    launch = plan.launch_sizes[0]
    compiled = _compile(lambda u, j0: launch_tiles(plan, u, j0, launch),
                        _operand(one_chip, plan, plan.n_pad),
                        _spec(one_chip, (), jnp.int32))
    out = compiled.memory_analysis().output_size_in_bytes
    assert out == launch * plan.t * plan.t * 4


def test_int8_row_scales_compile(one_chip):
    """compute_dtype=int8 Pearson: absmax-quantized rows whose per-row
    scales ride the kernel as (t, 1) and (1, t) blocks."""
    plan = _chip_plan(compute_dtype=jnp.int8)
    launch = plan.launch_sizes[0]

    def run(q, scale, j0):
        return launch_tiles(plan, Operand(q, scale), j0, launch)

    _compile(run, _operand(one_chip, plan, plan.n_pad),
             _spec(one_chip, (plan.n_pad,), jnp.float32),
             _spec(one_chip, (), jnp.int32))


def _serving_plan():
    # one CorrServer batch: a probe slab bucketed to one tile row against
    # the whole corpus, over the rectangular grid
    return _chip_plan(n=bucket_rows(PROBES, REAL_SEEK.t), n_cols=REAL_SEEK.n)


def test_serving_grid_compiles(one_chip):
    plan = _serving_plan()
    launch = plan.launch_sizes[0]

    def run(u, v, j0):
        return launch_tiles(plan, u, j0, launch, v=v,
                            grid_cols=plan.workload.grid_cols)

    _compile(run, _operand(one_chip, plan, plan.n_pad),
             _operand(one_chip, plan, plan.col_pad),
             _spec(one_chip, (), jnp.int32))


@pytest.mark.parametrize("workload", ["grid", "triangle"])
def test_device_topk_compiles(one_chip, workload):
    """The device top-k epilogue: served top-k (grid) and symmetric
    corr(x, sink=DeviceTopKSink(k)) (triangle, per-slot column states)."""
    plan = _serving_plan() if workload == "grid" else _chip_plan()
    launch = plan.launch_sizes[0]
    grid_cols = plan.workload.grid_cols
    j = _spec(one_chip, (), jnp.int32)
    if grid_cols is None:
        def run(u, j0, hi):
            return launch_topk_tiles(plan, u, j0, hi, launch, TOP_K)
        specs = (_operand(one_chip, plan, plan.n_pad), j, j)
    else:
        def run(u, v, j0, hi):
            return launch_topk_tiles(plan, u, j0, hi, launch, TOP_K, v=v,
                                     grid_cols=grid_cols)
        specs = (_operand(one_chip, plan, plan.n_pad),
                 _operand(one_chip, plan, plan.col_pad), j, j)
    _compile(run, *specs)
