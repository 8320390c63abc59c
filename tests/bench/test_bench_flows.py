"""Device programs linked to the host span that dispatched them, by the
trace's flow ids (bench/lib/flows.py): on a hand-built trace, and on
traces recorded on a v5e chip."""

import json
from pathlib import Path

import pytest

import _bench_path  # noqa: F401
from bench.lib import flows, spec, trace
from bench.lib.flows import FlowView, HostEvent, Module, Span
from bench.lib.record import Record
from bench.lib.trace import Op, TraceView

DATA = Path(__file__).resolve().parent / "data"
PY, EXEC, ASYNC = 0, 1, 2          # host lines: Python, execute, enqueue
LINK, ENQ, SEQ = 14, 12, 7         # flow types, as the profiler numbers them


def _fv():
    """One solve on two devices over an 800 ns window.

    The Python line holds the spans and three linkage events; each links
    (`_p`) to a `PJRT_LoadedExecutable_Execute` on the execute line, which
    encloses the `DoEnqueueProgram`s that produce the modules' flows.  The
    scatter's enqueue sits one hop further, on an async thread, behind an
    event of its own that consumes a flow of the execute line.  Flow ids
    repeat across types, so a decoy shares the prepare's id.  Device 0:
    the transform (prepare), a kernel and a copy (launch), the scatter (a
    while loop round its body) and one module no host event produced.
    Device 1: the launch's kernel and an op outside any module."""
    spans = [Span("bench.solve", PY, 50, 660),
             Span("repro.corr", PY, 60, 640, (("call", 1),)),
             Span("repro.prepare", PY, 90, 110, (("call", 1),)),
             Span("repro.launch", PY, 160, 330, (("call", 1), ("pass", 0))),
             Span("repro.sink.scatter", PY, 490, 510,
                  (("call", 1), ("pass", 0)))]
    host = [HostEvent(PY, 100, 101, produces=(LINK, 11)),
            HostEvent(PY, 320, 321, produces=(LINK, 12)),
            HostEvent(PY, 500, 501, produces=(LINK, 13)),
            HostEvent(EXEC, 100, 120, consumes=(LINK, 11)),
            HostEvent(EXEC, 105, 110, produces=(ENQ, 101)),
            HostEvent(EXEC, 320, 340, consumes=(LINK, 12)),
            HostEvent(EXEC, 325, 330, produces=(ENQ, 102)),
            HostEvent(EXEC, 331, 335, produces=(ENQ, 103)),
            HostEvent(EXEC, 500, 530, consumes=(LINK, 13)),
            HostEvent(EXEC, 502, 504, produces=(SEQ, 201)),
            HostEvent(ASYNC, 510, 525, consumes=(SEQ, 201)),
            HostEvent(ASYNC, 512, 515, produces=(ENQ, 104)),
            # the same id as the prepare's enqueue, in a flow of another
            # type, on the Python line while no program span is open
            HostEvent(PY, 620, 621, produces=(SEQ, 101))]
    modules = {0: [Module("jit_prepare", 110, 150, (ENQ, 101)),
                   Module("jit_pcc", 330, 400, (ENQ, 102)),
                   Module("jit_scatter", 540, 600, (ENQ, 104)),
                   Module("jit_orphan", 700, 730, (ENQ, 999))],
               1: [Module("jit_pcc", 330, 390, (ENQ, 103))]}
    kernel = "%pcc_tiles.1 = f32[6,256,256] custom-call(...)"
    devices = {0: [Op("%mul.1 = f32[..] multiply(...)", 110, 150),
                   Op(kernel, 330, 380),
                   Op("%copy.3 = f32[..] copy(...)", 380, 400),
                   Op("%while.1 = (f32[]) while(...)", 540, 600),
                   Op("%dynamic-update-slice.2 = f32[8,8] ...", 545, 590),
                   Op("%copy.9 = f32[..] copy(...)", 700, 730)],
               1: [Op(kernel, 330, 390),
                   Op("%fusion = f32[..] fusion(...)", 395, 399)]}
    view = TraceView(devices=devices, spans=[("solve", 50, 660)],
                     window=(0, 800))
    return FlowView(view=view, spans=spans, host=host,
                    python_lines=frozenset({PY}), modules=modules)


def test_a_module_links_back_to_the_span_open_at_its_dispatch():
    fv = _fv()
    got = {m.name: flows.dispatching_span(fv, m)
           for d in (0, 1) for m in fv.modules[d]}
    assert got["jit_prepare"].name == "repro.prepare"   # not the decoy
    assert got["jit_pcc"].name == "repro.launch"     # both devices
    assert dict(got["jit_pcc"].ids) == {"call": 1, "pass": 0}
    assert got["jit_scatter"].name == "repro.sink.scatter"   # two hops
    assert got["jit_orphan"] is None
    assert flows.module_links(fv) == (4, 5)


def test_device_time_adds_up_to_busy_with_the_unlinked_rest_apart():
    fv = _fv()
    a0, a1 = flows.attribute(fv, 0), flows.attribute(fv, 1)
    assert a0 == {"kernel": 50, "repro.prepare": 40, "repro.launch": 20,
                  "repro.sink.scatter": 60, flows.UNLINKED: 30}
    assert a1 == {"kernel": 60, flows.UNLINKED: 4}
    for d, a in ((0, a0), (1, a1)):
        assert sum(a.values()) == trace.busy_ns(fv.view, d)
    assert flows.span_device_ns(fv, "repro.sink.scatter") == 60
    assert flows.span_host_ns(fv, "repro.launch") == 170


def test_a_span_that_dispatched_nothing_reads_no_device_time():
    fv = _fv()
    assert flows.span_device_ns(fv, "repro.sink.symmetrize") == 0
    assert flows.span_device_ns(fv, "bench.solve") == 0


def test_program_idle_gaps_name_the_innermost_program_span():
    """The same gaps as idle_gaps, each named by the innermost repro.*
    span open in its middle, else the bench.* one, else none."""
    fv = _fv()
    gaps = flows.program_idle_gaps(fv)
    assert [g for _, g in gaps] == [g for _, g in trace.idle_gaps(fv.view)]
    assert gaps == [["corr", pytest.approx(401e-9)],       # dev 1, 399..800
                    ["launch", pytest.approx(330e-9)],     # dev 1, 0..330
                    ["launch", pytest.approx(180e-9)],     # dev 0, 150..330
                    ["corr", pytest.approx(140e-9)],       # dev 0, 400..540
                    ["solve", pytest.approx(110e-9)],      # dev 0, 0..110
                    ["solve", pytest.approx(100e-9)],      # dev 0, 600..700
                    [flows.NO_SPAN, pytest.approx(70e-9)],
                    ["corr", pytest.approx(5e-9)]]


def test_mesh_programs_built_reads_programs_per_call():
    from repro.core.api import executor_stats
    read = spec.load_reader("mesh_programs_built.batch")
    stats = executor_stats()
    rec = Record(kind="solves", view=None, peaks=None,
                 operand_dtype="float32", solves=3)
    want = (None if not stats["calls"]
            else stats["mesh_programs_built"] / stats["calls"])
    assert read(rec) == want
    assert read(Record(kind="search", view=None, peaks=None,
                       operand_dtype="float32")) is None


CHIP_TRACES = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", CHIP_TRACES, ids=lambda p: p.stem)
def test_every_device_program_of_a_chip_trace_links_to_python(path):
    fv = flows.load(str(path))
    linked, total = flows.module_links(fv)
    assert total > 0 and linked == total
    for d in fv.view.devices:
        a = flows.attribute(fv, d)
        assert sum(a.values()) == trace.busy_ns(fv.view, d)
        assert flows.UNLINKED not in a


def test_recorded_spans_read_what_was_recorded():
    """Two 1024 x 640 solves on one v5e chip, with the program's spans:
    one `call` id a solve, and the prepare, scatter and symmetrize device
    times pinned at what this reduction read from it when it was
    recorded."""
    path = DATA / "v5e_spans.xplane.pb"
    want = json.loads(path.with_name("v5e_spans.json").read_text())
    fv = flows.load(str(path))
    calls = {dict(s.ids)["call"] for s in fv.spans
             if s.name.startswith(flows.PROGRAM_PREFIX)}
    assert len(calls) == want["solves"]
    for name, ns in want["span_device_ns"].items():
        assert flows.span_device_ns(fv, name) == ns
    kept = {s.name for s in fv.spans}
    assert kept >= {"repro.corr", "repro.prepare", "repro.launch",
                    "repro.sink.scatter", "repro.sink.symmetrize",
                    "bench.copy", "bench.solve", "bench.gather"}
