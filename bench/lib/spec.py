"""Resolve a cell of BENCHMARK.json to its files, by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own:

    BENCHMARK.json           the cells, their metrics and bounds
    <config "file">          sizes of a configuration (bench/configs/*.json)
    bench/traffic/<name>.json    parameters of a traffic mix
    bench/metrics/<name>.py      the reader of a per-layer metric

so a cell, a mix or a metric is added by adding files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, List, Optional

ROOT = Path(__file__).resolve().parents[2]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]      # the end-to-end metrics this cell reports
    per_layer: List[dict]       # the per-layer metrics this cell reports
    root: Path

    def reader(self, metric: dict) -> Callable:
        return load_reader(metric["name"], self.root)


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _one(entries: List[dict], name: str, what: str) -> dict:
    hits = [e for e in entries if e["name"] == name]
    if len(hits) != 1:
        raise KeyError(f"{what} {name!r} is not in BENCHMARK.json "
                       f"(known: {sorted(e['name'] for e in entries)})")
    return hits[0]


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named `workload`, with its configuration, traffic and the
    metrics it reports, read from the files that BENCHMARK.json names."""
    bm = load_benchmark(root)
    wl = _one(bm["workloads"], workload, "workload")
    cfg_entry = _one(bm["configs"], wl["config"], "config")
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{wl['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name=workload, chips=int(wl["chips"]),
                config_name=wl["config"], config=config,
                traffic_name=wl["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, root=root)


def load_reader(name: str, root: Path = ROOT) -> Callable:
    """`read(record)` of bench/metrics/<name>.py: returns the metric's
    value, or None where the run holds nothing for it to read."""
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_name = "bench_metric_" + "".join(
        c if c.isalnum() else "_" for c in name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, record) -> dict:
    """Every per-layer metric of the cell that finds something to read in
    `record`, as {name: {"value", "unit"}}."""
    out = {}
    for m in cell.per_layer:
        value: Optional[float] = cell.reader(m)(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
