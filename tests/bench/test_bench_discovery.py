"""The harness finds cells, configurations, traffic mixes and per-layer
metrics by name, and runs nowhere but on a chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import _bench_path  # noqa: F401
from bench.lib import spec
from bench.lib.record import Record

ROOT = _bench_path.ROOT


def _copy_benchmark(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_every_cell_resolves_to_its_files():
    bm = spec.load_benchmark(ROOT)
    for wl in bm["workloads"]:
        cell = spec.resolve(wl["name"], ROOT)
        assert cell.chips == wl["chips"]
        assert cell.traffic["kind"] in ("solves", "search")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in names
            assert callable(cell.reader(m))


def test_added_files_are_found_by_name_with_no_edit(tmp_path):
    """A new configuration, traffic mix and per-layer metric are new files
    plus new entries in BENCHMARK.json; no existing file changes."""
    root = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/seek_gpl570.json").read_text())
    cfg.update(name="tiny", n_genes=128)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    tr = json.loads((root / "bench/traffic/seek_top50.json").read_text())
    tr["rate_qps"] = 3
    (root / "bench/traffic/slow_search.json").write_text(json.dumps(tr))
    (root / "bench/metrics/queries_sent.serve.py").write_text(
        "def read(rec):\n"
        "    return len(rec.lags_s) or None\n")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny", "source": "https://example.org",
                          "file": "bench/configs/tiny.json", "reduced": [],
                          "why": "test"})
    bm["workloads"].append({"name": "tiny-slow", "config": "tiny",
                            "traffic": "slow_search", "chips": 1,
                            "why": "test"})
    bm["end_to_end"].append({"name": "query_p95_ms", "unit": "ms",
                             "better": "lower", "bound": 0.25,
                             "source": "host_clock",
                             "workloads": ["tiny-slow"]})
    bm["per_layer"].append({
        "name": "queries_sent.serve", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "serving",
        "moves": "query_p95_ms", "workloads": ["tiny-slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = spec.resolve("tiny-slow", root)
    assert cell.config["n_genes"] == 128
    assert cell.traffic["rate_qps"] == 3
    assert [m["name"] for m in cell.per_layer] == ["queries_sent.serve"]
    assert {m["name"] for m in cell.end_to_end} == {"query_p95_ms",
                                                    "setup_s"}
    rec = Record(kind="search", view=None, peaks=None,
                 operand_dtype="float32", lags_s=[0.001] * 7)
    assert spec.read_per_layer(cell, rec) == {
        "queries_sent.serve": {"value": 7.0, "unit": "count"}}
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    cell = spec.resolve("gpl570-pearson-dense", ROOT)
    rec = Record(kind="solves", view=None, peaks=None,
                 operand_dtype="float32", solves=3)
    assert "pcc_tiles_roofline.batch" not in spec.read_per_layer(cell, rec)


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="not in BENCHMARK.json"):
        spec.resolve("no-such-cell", ROOT)


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_without_a_tpu_it_exits_non_zero_and_prints_no_metric():
    p = _run(["--workload", "gpl570-pearson-dense", "--seed", "1",
              "--seconds", "1", "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_with_only_the_benchmark_files_it_exits_non_zero(tmp_path):
    root = _copy_benchmark(tmp_path)
    p = _run(["--workload", "gpl570-pearson-dense", "--seed", "1",
              "--seconds", "1", "--trace", "0"], root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_compiles_and_cache_loads_are_read_apart():
    cell = spec.resolve("gpl570-pearson-dense-x4", ROOT)
    rec = Record(kind="solves", view=None, peaks=None,
                 operand_dtype="float32", solves=7,
                 compiles={"compiles": 0, "loads": 119, "seconds": 0.4})
    got = spec.read_per_layer(cell, rec)
    assert got["compiles_in_window.batch"]["value"] == 0
    assert got["cache_loads_in_window.batch"] == {"value": 119.0,
                                                  "unit": "count"}
