"""`correct` comes out true for the program and false for the control and
for each fault a cell can have, with the rest of a run driven as on the
chip: set-up, window, peak memory, free, comparison.  Small sizes on the
CPU (interpret-mode kernels); the chip check of the harness is skipped.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _bench_path  # noqa: F401
from bench import run as bench_run
from bench.lib import search
from repro.core.api import corr
from repro.serving.server import CorrServer

ROOT = _bench_path.ROOT
SEED = 2**33 + 77
N, L = 600, 5072         # three 256-row tiles, the published samples


SOLVES = ["solve_s", "setup_s"]
QUERIES = ["query_p50_ms", "query_p95_ms", "served_qps", "setup_s"]


def _small(traffic, metrics=SOLVES, **overrides):
    """A cell of `traffic` over the GPL570 configuration at a small size."""
    cell = _bench_path.cell("seek_gpl570", traffic, metrics, n_genes=N,
                            n_samples=L, programs=8)
    return dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  **overrides))


def _run(cell, seconds=1.0, **program):
    return bench_run.run_cell(cell, SEED, seconds, False, jax.devices()[:1],
                              time.perf_counter(), program=program)


# -- solves ------------------------------------------------------------------


def _stale(x, **kw):
    """A solve that returns its state unchanged: the zero matrix it
    starts from."""
    r = corr(x, **kw)
    return jnp.zeros_like(r)


def _half(x, **kw):
    """Half of the variables left out of the solve."""
    r = corr(x, **kw)
    keep = jnp.arange(r.shape[0]) < r.shape[0] // 2
    return jnp.where(keep[:, None] & keep[None, :], r, 0.0)


def _altered(x, **kw):
    """One off-diagonal tile altered where it is produced (and its
    mirror)."""
    r = corr(x, **kw)
    return r.at[:256, 256:512].add(1e-3).at[256:512, :256].add(1e-3)


@pytest.mark.parametrize("measure", ["pearson", "spearman"])
def test_solves_program_is_correct(measure):
    res = _run(_small(f"{measure}_solves"))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert set(res["metrics"]) == {"solve_s", "setup_s"}
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("fault", [_stale, _half, _altered],
                         ids=["state-unchanged", "half-left-out",
                              "answer-altered"])
def test_solves_fault_is_not_correct(fault):
    res = _run(_small("pearson_solves"), corr=fault)
    assert not res["correct"]
    assert res["failed"] > 0


@pytest.mark.parametrize("measure", ["pearson", "spearman"])
def test_solves_control_is_not_correct(measure):
    """The reference one precision step down (float32 transform, three
    bf16 passes) in the program's place breaks a limit."""
    from bench.lib.solves import SolvesDriver
    cell = _small(f"{measure}_solves")
    d = SolvesDriver(cell, SEED, jax.devices()[:1])
    d.warm()
    d.window(0.5)
    got = d.free()
    limits = cell.traffic["limits"]
    program = d.readings(got)
    control = d.control_readings()
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control


MESH_SCRIPT = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src",
                sys.argv[1] + "/tests/bench"]
import jax, jax.numpy as jnp
import _bench_path
from bench import run as bench_run
import repro.core.sinks as sinks
cell = _bench_path.cell("seek_gpl570_x4", "pearson_solves",
                        ["solve_s", "setup_s"], chips=4,
                        n_genes=int(sys.argv[2]), n_samples=int(sys.argv[3]),
                        programs=8)
devs = jax.devices()[:4]
sound = bench_run.run_cell(cell, int(sys.argv[4]), 1.0, False, devs,
                           time.perf_counter())
scatter = sinks.scatter_tiles_at

def local_only(r_pad, tiles, ys, xs, t):
    # the exchange between chips left out: only the first chip's share of
    # the pass's tiles reaches the result
    own = tiles.shape[0] // 4
    keep = (jnp.arange(tiles.shape[0]) < own)[:, None, None]
    return scatter(r_pad, jnp.where(keep, tiles, 0.0), ys, xs, t)

sinks.scatter_tiles_at = local_only
broken = bench_run.run_cell(cell, int(sys.argv[4]), 1.0, False, devs,
                            time.perf_counter())
print(json.dumps({"sound": sound, "broken": broken}))
"""


def test_mesh_program_is_correct_and_a_missing_exchange_is_not():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT, str(ROOT), str(N), str(L),
         str(SEED)], env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"]["correct"], out["sound"]["checks"]
    assert out["sound"]["device"]["count"] == 4
    assert not out["broken"]["correct"]


# -- search ------------------------------------------------------------------


SEARCH = dict(rate_qps=20.0, set_size=[1, 4], check_queries=12)


def _server(alter):
    """A CorrServer whose batcher's answers pass through `alter`."""
    def make(x, **kw):
        srv = CorrServer(x, **kw)
        execute = srv.batcher.execute
        srv.batcher.execute = lambda qs: alter(qs, *execute(qs))
        return srv
    return make


def _swap_partner(qs, results, infos):
    for r in results:
        r["indices"] = r["indices"].copy()
        r["indices"][0, -1] = (r["indices"][0, -1] + 1) % N
    return results, infos


def _first_answer_forever():
    first = []

    def alter(qs, results, infos):
        if not first:
            first.append(results[0])
        return [{"indices": np.resize(first[0]["indices"],
                                      r["indices"].shape),
                 "values": np.resize(first[0]["values"], r["values"].shape)}
                for r in results], infos
    return alter


def _drop_every_other(warm):
    """Half of the queries left out: every other request of the window is
    never answered (the `warm` set-up queries are)."""
    seen = [-warm]

    def make(x, **kw):
        srv = CorrServer(x, **kw)
        serve = srv._serve

        def half(batch):
            keep = []
            for p in batch:
                seen[0] += 1
                if seen[0] <= 0 or seen[0] % 2:
                    keep.append(p)
            if keep:
                serve(keep)
        srv._serve = half
        return srv
    return make


def test_search_program_is_correct():
    res = _run(_small("seek_top50", QUERIES, **SEARCH), seconds=1.5)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 30
    assert set(res["metrics"]) == {"query_p50_ms", "query_p95_ms",
                                   "served_qps", "setup_s"}


@pytest.mark.parametrize("fault", ["answer-altered", "state-unchanged",
                                   "half-left-out"])
def test_search_fault_is_not_correct(fault, monkeypatch):
    # long enough for a first query that compiles, short for a lost one
    monkeypatch.setattr(search, "LATE_S", 10.0)
    server = {"answer-altered": lambda: _server(_swap_partner),
              "state-unchanged": lambda: _server(_first_answer_forever()),
              "half-left-out": lambda: _drop_every_other(4)}[fault]()
    res = _run(_small("seek_top50", QUERIES, **SEARCH), seconds=1.5,
               server=server)
    assert not res["correct"]
    assert res["failed"] > 0


def test_search_control_is_not_correct():
    cell = _small("seek_top50", QUERIES, **SEARCH)
    d = search.SearchDriver(cell, SEED, jax.devices()[:1], 1.0)
    d.warm()
    d.window(1.0)
    answers = d.free()
    limits = cell.traffic["limits"]
    program = d.readings(answers)
    control = d.control_readings()
    assert all(program[k] <= limits[k] for k in limits), program
    assert any(control[k] > limits[k] for k in limits), control
