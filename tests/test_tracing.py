"""Program spans and executor counters: what a jax.profiler trace of
corr() shows of its layers, on one CPU device and on a 4-device CPU mesh
(in a subprocess, like tests/test_distributed.py)."""

import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
from jax.profiler import ProfileData

from repro.core.api import corr, executor_stats
from repro.core.plan import ExecutionPlan

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _program_spans(trace_dir):
    """Every repro.* event of the trace as (line, name, start, end, ids)."""
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append(((plane.name, i), e.name, e.start_ns,
                                e.end_ns, dict(e.stats)))
    return out


def _check_one_call(spans, call, n_pass):
    """The spans of one corr() call nest in `repro.corr`, share its call
    id, come once per pass where they belong to a pass, and no pass launch
    overlaps a scatter."""
    names = [s[1] for s in spans]
    assert names.count("repro.corr") == 1
    assert names.count("repro.prepare") == 1
    assert names.count("repro.sink.symmetrize") == 1
    launches = [s for s in spans if s[1] == "repro.launch"]
    scatters = [s for s in spans if s[1] == "repro.sink.scatter"]
    assert sorted(s[4]["pass"] for s in launches) == list(range(n_pass))
    assert sorted(s[4]["pass"] for s in scatters) == list(range(n_pass))
    assert {s[4]["call"] for s in spans} == {call}
    assert len({s[0] for s in spans}) == 1          # one host thread
    _, _, lo, hi, _ = next(s for s in spans if s[1] == "repro.corr")
    assert all(lo <= s[2] <= s[3] <= hi for s in spans)
    for _, _, ls, le, _ in launches:
        for _, _, ss, se, _ in scatters:
            assert le <= ss or se <= ls


def test_corr_spans_nest_share_the_call_and_come_once_a_pass(tmp_path):
    x = np.random.default_rng(0).standard_normal((300, 40)).astype(
        np.float32)
    plan = ExecutionPlan.create(300, 40, max_tiles_per_pass=2)
    assert plan.n_pass == 2
    corr(x, max_tiles_per_pass=2)                  # compiled outside
    before = executor_stats()
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(corr(x, max_tiles_per_pass=2))
    after = executor_stats()
    assert after["calls"] == before["calls"] + 1
    assert after["passes"] == before["passes"] + plan.n_pass
    assert after["mesh_programs_built"] == before["mesh_programs_built"]
    _check_one_call(_program_spans(str(tmp_path)), after["calls"],
                    plan.n_pass)


def test_corr_outside_a_profiler_session_runs_and_counts_its_call():
    """No session: the spans record nothing, corr() returns what it did
    and counts its call."""
    x = np.random.default_rng(1).standard_normal((40, 12)).astype(np.float32)
    before = executor_stats()["calls"]
    np.testing.assert_allclose(np.asarray(corr(x)), np.corrcoef(x),
                               atol=1e-5)
    assert executor_stats()["calls"] == before + 1


def test_mesh_spans_and_programs_built_per_call(tmp_path):
    """On a 4-device mesh the same spans come out, and the mesh executor
    builds one shard_map program per distinct launch size (here two passes
    of one size) on every call: its programs live in a dict local to the
    call.  The mesh-program cache that the mesh path lacks today would
    take this to 0 on the second call."""
    code = textwrap.dedent(f"""
        import json
        import jax, numpy as np
        from repro.core.api import corr, executor_stats
        from repro.core.plan import ExecutionPlan
        x = np.random.default_rng(2).standard_normal((44, 20)).astype(
            np.float32)
        mesh = jax.make_mesh((4,), ("d",))
        kw = dict(t=8, l_blk=8, max_tiles_per_pass=3)
        plan = ExecutionPlan.create(44, 20, p=4, **kw)
        built = [executor_stats()["mesh_programs_built"]]
        r = corr(x, mesh=mesh, **kw)
        np.testing.assert_allclose(np.asarray(r), np.corrcoef(x), atol=1e-5)
        built.append(executor_stats()["mesh_programs_built"])
        with jax.profiler.trace({str(tmp_path)!r}):
            jax.block_until_ready(corr(x, mesh=mesh, **kw))
        built.append(executor_stats()["mesh_programs_built"])
        print(json.dumps(dict(built=built, sizes=plan.launch_sizes,
                              stats=executor_stats())))
    """)
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr
    got = json.loads(res.stdout.strip().splitlines()[-1])
    n_pass, sizes = len(got["sizes"]), set(got["sizes"])
    assert n_pass == 2 and len(sizes) == 1
    assert np.diff(got["built"]).tolist() == [len(sizes), len(sizes)]
    assert got["stats"] == {"calls": 2, "passes": 2 * n_pass,
                            "mesh_programs_built": 2 * len(sizes)}
    _check_one_call(_program_spans(str(tmp_path)), 2, n_pass)


def test_calls_count_once_and_see_their_own_id_across_threads():
    """Calls from many threads at once each count once, and the spans of
    each see that call's own id, never another thread's."""
    import threading

    from repro.core.allpairs import current_call, traced_call

    ids = []
    lock = threading.Lock()

    @traced_call
    def call():
        got = current_call()
        with lock:
            ids.append(got)
        return got

    def worker():
        for _ in range(200):
            call()

    before = executor_stats()["calls"]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert executor_stats()["calls"] == before + 16 * 200
    assert sorted(ids) == list(range(before + 1, before + 16 * 200 + 1))
    assert current_call() == 0
