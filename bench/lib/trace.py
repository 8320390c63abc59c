"""Reduction of a profiler trace to device busy time, idle share, kernel
and collective time, and idle gaps labelled by the benchmark's host spans.

The JAX profiler writes one `.xplane.pb` per traced window.  Its device
planes (`/device:TPU:<i>`) carry the operations the chip ran on the
"XLA Ops" line; the host plane carries the benchmark's own spans, which
are `jax.profiler.TraceAnnotation`s named `bench.<label>` (see `span`).
Both sit on one clock in the file, so a device gap can be put beside the
host span that was open while the chip waited.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
WINDOW = "window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all")

Interval = Tuple[int, int]


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start: int      # ns
    end: int        # ns

    @property
    def dur(self) -> int:
        return self.end - self.start

    def matches(self, pattern: "re.Pattern") -> bool:
        return bool(pattern.search(self.name))


@dataclasses.dataclass
class TraceView:
    """What the reduction reads: device ops per device, the host spans,
    and the traced window (the `bench.window` span)."""

    devices: Dict[int, List[Op]]
    spans: List[Tuple[str, int, int]]
    window: Interval

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self, device: int) -> List[Op]:
        lo, hi = self.window
        return [o for o in self.devices[device] if o.end > lo and o.start < hi]


def span(label: str):
    """A host span of the benchmark's own, readable from the trace."""
    import jax
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + label)


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {files}")
    return files[0]


def load(path: str) -> TraceView:
    """Read an .xplane.pb into a TraceView."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, List[Op]] = {}
    spans: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    Op(e.name, int(e.start_ns), int(e.end_ns))
                    for e in line.events]
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      int(e.start_ns), int(e.end_ns)))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if windows:
        window = (min(s for s, _ in windows), max(e for _, e in windows))
    else:
        starts = [o.start for ops in devices.values() for o in ops]
        ends = [o.end for ops in devices.values() for o in ops]
        window = (min(starts), max(ends)) if starts else (0, 0)
    spans = [s for s in spans if s[0] != WINDOW]
    return TraceView(devices=devices, spans=spans, window=window)


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Union of half-open intervals, sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(view: TraceView, device: int) -> int:
    """Union of the device's operation intervals inside the window."""
    iv = merge(_clip(((o.start, o.end) for o in view.devices[device]),
                     view.window))
    return sum(e - s for s, e in iv)


def busy_s(view: TraceView) -> float:
    """Busy seconds averaged over the traced devices."""
    if not view.devices:
        return 0.0
    return sum(busy_ns(view, d) for d in view.devices) * 1e-9 / len(
        view.devices)


def idle_share(view: TraceView) -> Optional[float]:
    """1 - busy / window, averaged over devices; None without a device."""
    if not view.devices or view.window_s <= 0:
        return None
    return 1.0 - busy_s(view) / view.window_s


def matching_ns(view: TraceView, pattern: "re.Pattern",
                device: Optional[int] = None) -> int:
    """Device time of the ops whose name matches, summed over the devices
    (or on one device), clipped to the window."""
    devs = view.devices if device is None else [device]
    total = 0
    for d in devs:
        iv = merge(_clip(((o.start, o.end) for o in view.devices[d]
                          if o.matches(pattern)), view.window))
        total += sum(e - s for s, e in iv)
    return total


def count_matching(view: TraceView, pattern: "re.Pattern") -> int:
    return sum(1 for d in view.devices for o in view.ops(d)
               if o.matches(pattern))


def collective_ns(view: TraceView) -> int:
    """Device time of collective operations, summed over the devices."""
    return matching_ns(view, COLLECTIVE)


def short_name(op_name: str) -> str:
    """`%pcc_tiles.1 = f32[...] custom-call(...)` -> `pcc_tiles.1`."""
    return op_name.split(" = ", 1)[0].lstrip("%")


def self_ns(ops: List[Op]) -> List[Tuple[Op, int]]:
    """Each op with its self time: its duration less the ops nested in it
    (the trace lists a while loop and the ops of its body alike)."""
    ops = sorted(ops, key=lambda o: (o.start, -o.end))
    selfs = [o.dur for o in ops]
    stack: List[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end <= o.start:
            stack.pop()
        if stack and o.end <= ops[stack[-1]].end:
            selfs[stack[-1]] -= o.dur
        stack.append(i)
    return list(zip(ops, selfs))


def top_ops(view: TraceView, n: int = 10) -> List[List]:
    """The device operations that took most self time, summed over the
    devices, as [[name, seconds], ...]."""
    agg: Dict[str, int] = defaultdict(int)
    for d in view.devices:
        for o, ns in self_ns(view.ops(d)):
            agg[short_name(o.name)] += ns
    top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in top]


def _label(view: TraceView, s: int, e: int) -> str:
    """The host span that overlaps [s, e) the most, else 'none'."""
    best, label = 0, "none"
    for name, hs, he in view.spans:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, label = ov, name
    return label


def idle_gaps(view: TraceView, n: int = 10) -> List[List]:
    """The longest idle gaps of the devices inside the window, each as
    [host span open during it, seconds]."""
    gaps = []
    lo, hi = view.window
    for d in view.devices:
        busy = merge(_clip(((o.start, o.end) for o in view.devices[d]),
                           view.window))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    return [[_label(view, s, e), g * 1e-9] for g, s, e in gaps[:n]]


def breakdown(view: TraceView) -> dict:
    return {"device_ops": top_ops(view), "idle_gaps": idle_gaps(view)}
