"""Puts the checkout root and the program's src/ on sys.path, so that the
benchmark's tests import `bench.lib` as bench/run.py does."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)


def cell(config: str, traffic: str, end_to_end, chips: int = 1, **sizes):
    """A cell built from a configuration file and a traffic file alone,
    whatever BENCHMARK.json lists, with the configuration's sizes
    overridden by `sizes`."""
    import json

    from bench.lib.spec import Cell
    cfg = json.loads((ROOT / "bench" / "configs" / f"{config}.json")
                     .read_text())
    tr = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                    .read_text())
    return Cell(name=f"{config}.{traffic}", chips=chips, config_name=config,
                config=dict(cfg, **sizes), traffic_name=traffic, traffic=tr,
                end_to_end=[{"name": m, "unit": "-"} for m in end_to_end],
                per_layer=[], root=ROOT)
