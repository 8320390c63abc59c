"""Device work attributed to the host span that dispatched it, by the
trace's own flow ids.

`trace.load` keeps a trace's device ops, the benchmark's `bench.*` spans
and the window.  This module reads, from the same `.xplane.pb`, what ties
device work to the host:

  * each device's "XLA Modules" events (one per program run), with the
    flow each consumes;
  * the host events that carry flows: produced (`_p`, of type `_pt`) and
    consumed (`_c`, of type `_ct`).  An id is unique only within its type
    (in a full-size window a fifth of the ids are shared across types), so
    a flow is the pair (type, id);
  * the spans with their host line and ids: the program's `repro.*`
    (src/repro/runtime/tracing.py) and the benchmark's `bench.*`.

A module's `_c` is produced by a `DoEnqueueProgram` on some host thread;
an event that encloses it there consumes a flow produced on another
thread, and so on, until a `PJRT_LoadedExecutable_Execute linkage` event
on a Python thread's line, where the spans are recorded.  The innermost
span open there at that instant dispatched the module.  Time overlap
would not do: work runs after its span has closed (dispatch is
asynchronous), and the host and device clocks agree only to microseconds.
"""

from __future__ import annotations

import bisect
import dataclasses
from collections import defaultdict
from typing import Dict, List, Optional, Tuple, Union

from bench.lib import kernels, trace
from bench.lib.trace import Interval, TraceView

Flow = Tuple[Optional[int], int]    # (type, id)

PROGRAM_PREFIX = "repro."
PREFIXES = (PROGRAM_PREFIX, trace.SPAN_PREFIX)
MODULES_LINE = "XLA Modules"
# recorded on the calling thread: it marks a Python thread's line, which
# is named after the executable ("python", "python3", ...)
LINKAGE = "PJRT_LoadedExecutable_Execute linkage"
UNLINKED = "unlinked"       # the flow chain breaks before a Python thread
NO_SPAN = "none"            # it reaches one, but no span was open there


@dataclasses.dataclass(frozen=True)
class Span:
    name: str               # "repro.launch", "bench.solve", ...
    line: int               # index of its line in the host plane
    start: int              # ns
    end: int                # ns
    ids: Tuple[Tuple[str, int], ...] = ()   # e.g. (("call", 3), ("pass", 0))

    @property
    def short(self) -> str:
        """The name without its prefix: "launch", "solve"."""
        return self.name.split(".", 1)[1]


@dataclasses.dataclass(frozen=True)
class HostEvent:
    line: int
    start: int
    end: int
    produces: Optional[Flow] = None
    consumes: Optional[Flow] = None


@dataclasses.dataclass(frozen=True)
class Module:
    name: str
    start: int
    end: int
    flow: Optional[Flow] = None


@dataclasses.dataclass
class FlowView:
    """A TraceView with what links its device programs to the host."""

    view: TraceView
    spans: List[Span] = dataclasses.field(default_factory=list)
    host: List[HostEvent] = dataclasses.field(default_factory=list)
    python_lines: frozenset = frozenset()
    modules: Dict[int, List[Module]] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        self.spans = sorted(self.spans, key=lambda s: (s.start, -s.end))
        self._producer = {e.produces: e for e in self.host
                          if e.produces is not None}
        self._parent = _parents(self.host)
        self._by_flow: Dict[Optional[Flow], Union[Span, str]] = {}


def _flow(stats: dict, end: str) -> Optional[Flow]:
    """The flow an event produces (`end` "p") or consumes ("c")."""
    if f"_{end}" not in stats:
        return None
    return stats.get(f"_{end}t"), stats[f"_{end}"]


def _ids(stats) -> Tuple[Tuple[str, int], ...]:
    """A span's ids: the profiler records a TraceAnnotation's keyword
    arguments as the event's stats."""
    return tuple(sorted((k, int(v)) for k, v in stats))


def load(path: str) -> FlowView:
    """Read an .xplane.pb into a FlowView."""
    from jax.profiler import ProfileData
    spans: List[Span] = []
    host: List[HostEvent] = []
    python_lines = set()
    modules: Dict[int, List[Module]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for i, line in enumerate(plane.lines):
            if m and line.name == MODULES_LINE:
                modules[int(m.group(1))] = [
                    Module(e.name, int(e.start_ns), int(e.end_ns),
                           _flow(dict(e.stats), "c")) for e in line.events]
            elif not m and plane.name.startswith("/host"):
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append(Span(e.name, i, int(e.start_ns),
                                          int(e.end_ns), _ids(e.stats)))
                        continue
                    if e.name == LINKAGE:
                        python_lines.add(i)
                    st = dict(e.stats)
                    if "_p" in st or "_c" in st:
                        host.append(HostEvent(i, int(e.start_ns),
                                              int(e.end_ns), _flow(st, "p"),
                                              _flow(st, "c")))
    return FlowView(view=trace.load(path), spans=spans, host=host,
                    python_lines=frozenset(python_lines), modules=modules)


def _parents(events: List[HostEvent]) -> Dict[HostEvent, HostEvent]:
    """The innermost event that encloses each, on its own line (events of
    one thread nest)."""
    parent: Dict[HostEvent, HostEvent] = {}
    by_line: Dict[int, List[HostEvent]] = defaultdict(list)
    for e in events:
        by_line[e.line].append(e)
    for evs in by_line.values():
        stack: List[HostEvent] = []
        for e in sorted(evs, key=lambda e: (e.start, -e.end)):
            while stack and stack[-1].end < e.end:
                stack.pop()
            if stack:
                parent[e] = stack[-1]
            stack.append(e)
    return parent


def _python_instant(fv: FlowView, flow: Optional[Flow]
                    ) -> Optional[Tuple[int, int]]:
    """(line, ns) on a Python thread where the flow `flow` began, or
    None where the chain breaks."""
    seen = set()
    ev = fv._producer.get(flow)
    while ev is not None and ev not in seen:
        if ev.line in fv.python_lines:
            return ev.line, ev.start
        seen.add(ev)
        nxt, up = None, ev
        while up is not None and nxt is None:
            if up.consumes is not None:
                nxt = fv._producer.get(up.consumes)
            up = fv._parent.get(up)
        ev = nxt
    return None


def innermost(fv: FlowView, line: int, at: int,
              prefix: Optional[str] = None) -> Optional[Span]:
    """The innermost span (of `prefix`, if given) open on `line` at
    instant `at`."""
    best = None
    for s in fv.spans:
        if s.start > at:
            break
        if (s.line == line and s.end >= at
                and (prefix is None or s.name.startswith(prefix))):
            best = s      # sorted by start: a later one nests deeper
    return best


def _dispatcher(fv: FlowView, flow: Optional[Flow]) -> Union[Span, str]:
    """The span that dispatched the program run consuming `flow`, else
    UNLINKED or NO_SPAN (memoised per flow)."""
    if flow not in fv._by_flow:
        at = _python_instant(fv, flow)
        fv._by_flow[flow] = (UNLINKED if at is None
                             else innermost(fv, *at) or NO_SPAN)
    return fv._by_flow[flow]


def dispatching_span(fv: FlowView, module: Module) -> Optional[Span]:
    """The span open on the Python thread that dispatched `module`, or
    None where a link is missing or no span was open."""
    found = _dispatcher(fv, module.flow)
    return found if isinstance(found, Span) else None


def _label(fv: FlowView, module: Module) -> str:
    found = _dispatcher(fv, module.flow)
    return found.name if isinstance(found, Span) else found


def _length(iv: List[Interval]) -> int:
    return sum(e - s for s, e in iv)


def _overlap(a: List[Interval], b: List[Interval]) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def attribute(fv: FlowView, device: int) -> Dict[str, int]:
    """The device's busy time in the window (ns) by what put it there:
    "kernel" for the Pallas kernels wherever dispatched; for every other
    op, the name of the span that dispatched its module, else UNLINKED
    (no module, or a broken chain) or NO_SPAN.  Ops of one module never
    overlap another module's, so the parts add up to `trace.busy_ns`."""
    ops = fv.view.devices.get(device, [])
    mods = sorted(fv.modules.get(device, []), key=lambda m: m.start)
    starts = [m.start for m in mods]
    kernel = trace.merge(trace._clip(
        ((o.start, o.end) for o in ops if o.matches(kernels.ANY_KERNEL)),
        fv.view.window))
    parts: Dict[str, List[Interval]] = defaultdict(list)
    for o in ops:
        if o.matches(kernels.ANY_KERNEL):
            continue
        i = bisect.bisect_right(starts, o.start) - 1
        # by start: an op may end a rounded nanosecond after its module
        label = (_label(fv, mods[i]) if i >= 0 and o.start < mods[i].end
                 else UNLINKED)
        parts[label].append((o.start, o.end))
    out = {"kernel": _length(kernel)}
    for label, iv in parts.items():
        iv = trace.merge(trace._clip(iv, fv.view.window))
        ns = _length(iv) - _overlap(iv, kernel)
        if ns:
            out[label] = ns
    return out


def span_device_ns(fv: FlowView, name: str) -> int:
    """Device time of the programs `name` dispatched, kernels apart,
    summed over the devices."""
    return sum(attribute(fv, d).get(name, 0) for d in fv.view.devices)


def span_host_ns(fv: FlowView, name: str) -> int:
    """Host wall time inside the spans called `name`, clipped to the
    window."""
    lo, hi = fv.view.window
    return sum(max(0, min(s.end, hi) - max(s.start, lo))
               for s in fv.spans if s.name == name)


def module_links(fv: FlowView) -> Tuple[int, int]:
    """(modules in the window that reach a Python thread, all modules in
    the window), over every device."""
    lo, hi = fv.view.window
    linked = total = 0
    for d, mods in fv.modules.items():
        for m in mods:
            if m.end > lo and m.start < hi:
                total += 1
                linked += _python_instant(fv, m.flow) is not None
    return linked, total


def _gap_label(fv: FlowView, s: int, e: int) -> str:
    """The innermost `repro.*` span open on a Python thread in the middle
    of [s, e), else the innermost `bench.*` one, else NO_SPAN."""
    mid = (s + e) // 2
    for prefix in PREFIXES:
        found = [innermost(fv, line, mid, prefix)
                 for line in sorted(fv.python_lines)]
        found = [f for f in found if f is not None]
        if found:
            return max(found, key=lambda f: f.start).short
    return NO_SPAN


def program_idle_gaps(fv: FlowView, n: int = 10) -> List[List]:
    """The same longest idle gaps as `trace.idle_gaps`, each as [the
    program span open during it, seconds]."""
    gaps = []
    lo, hi = fv.view.window
    for d in fv.view.devices:
        busy = trace.merge(trace._clip(
            ((o.start, o.end) for o in fv.view.devices[d]), fv.view.window))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    gaps.sort(reverse=True)
    return [[_gap_label(fv, s, e), g * 1e-9] for g, s, e in gaps[:n]]
