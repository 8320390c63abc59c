"""The reduction from a profiler trace to busy time, idle share, kernel
and collective time, and idle gaps labelled by the benchmark's spans."""

import json
import re
from pathlib import Path

import pytest

import _bench_path  # noqa: F401
from bench.lib import kernels, trace
from bench.lib.trace import Op, TraceView

DATA = Path(__file__).resolve().parent / "data"


def _view():
    """Two devices over a 100 ns window; device 0 runs a kernel nested in
    nothing, a while loop with two body ops, and an all-gather."""
    dev0 = [Op("%pcc_tiles.1 = f32[6,256,256] custom-call(...)", 10, 40),
            Op("%while.1 = (f32[]) while(...)", 50, 70),
            Op("%dynamic-update-slice.2 = f32[8,8] ...", 52, 60),
            Op("%dynamic-update-slice.2 = f32[8,8] ...", 61, 69),
            Op("%all-gather.3 = f32[24,256,256] all-gather(...)", 80, 90)]
    dev1 = [Op("%pcc_tiles.1 = f32[6,256,256] custom-call(...)", 5, 45),
            Op("%pcc_tiles.1 = f32[6,256,256] custom-call(...)", 45, 60)]
    spans = [("copy", 0, 8), ("solve", 8, 75), ("gather", 75, 100)]
    return TraceView(devices={0: dev0, 1: dev1}, spans=spans,
                     window=(0, 100))


def test_merge_unions_overlapping_intervals():
    assert trace.merge([(5, 10), (0, 3), (2, 6), (20, 20), (12, 15)]) == [
        (0, 10), (12, 15)]


def test_busy_is_the_union_of_ops_inside_the_window():
    v = _view()
    assert trace.busy_ns(v, 0) == 30 + 20 + 10
    assert trace.busy_ns(v, 1) == 55
    assert trace.busy_s(v) == pytest.approx((60 + 55) / 2 * 1e-9)
    assert trace.idle_share(v) == pytest.approx(1 - 57.5 / 100)


def test_window_clips_ops_that_straddle_it():
    v = _view()
    v.window = (20, 55)
    assert trace.busy_ns(v, 1) == 35
    assert trace.matching_ns(v, kernels.PCC_TILES) == 20 + 35


def test_kernel_and_collective_time_by_name():
    v = _view()
    assert trace.matching_ns(v, kernels.PCC_TILES) == 30 + 55
    assert trace.matching_ns(v, kernels.PCC_TILES, device=1) == 55
    assert trace.matching_ns(v, kernels.PCC_TOPK) == 0
    assert trace.collective_ns(v) == 10


def test_top_ops_count_self_time_once():
    top = dict(trace.top_ops(_view()))
    assert top["pcc_tiles.1"] == pytest.approx(85e-9)
    assert top["while.1"] == pytest.approx(4e-9)       # 20 less 16 nested
    assert top["dynamic-update-slice.2"] == pytest.approx(16e-9)


def test_idle_gaps_carry_the_host_span_open_during_them():
    gaps = trace.idle_gaps(_view())
    assert gaps[0] == ["gather", pytest.approx(40e-9)]   # dev 1, 60..100
    labels = {g[0] for g in gaps}
    assert labels <= {"copy", "solve", "gather"}
    assert ["copy", pytest.approx(10e-9)] in gaps        # dev 0, 0..10


def test_no_device_reads_nothing():
    v = TraceView(devices={}, spans=[], window=(0, 10))
    assert trace.idle_share(v) is None
    assert trace.busy_s(v) == 0.0


CHIP_TRACES = sorted(DATA.glob("*.xplane.pb"))


@pytest.mark.parametrize("path", CHIP_TRACES, ids=lambda p: p.stem)
def test_chip_trace_reduces_to_what_was_run(path):
    """A small trace recorded on a v5e chip, committed beside the
    expectations of the run that recorded it: one kernel event per solve
    or query, busy time inside the window, the kernels found by name."""
    want = json.loads(path.with_name(
        path.name.replace(".xplane.pb", ".json")).read_text())
    v = trace.load(str(path))
    assert list(v.devices) == [0]
    for name, count in want["kernel_events"].items():
        assert trace.count_matching(v, getattr(kernels, name)) == count
    assert trace.busy_s(v) == pytest.approx(want["busy_s"])
    assert v.window_s == pytest.approx(want["window_s"])
    assert 0 < trace.busy_s(v) < v.window_s
    assert 0.0 < trace.idle_share(v) < 1.0
    assert trace.matching_ns(v, kernels.ANY_KERNEL) <= trace.busy_ns(v, 0)
    b = trace.breakdown(v)
    assert b["device_ops"][0][0] == want["top_op"]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    gaps = [g for _, g in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= v.window_s - trace.busy_s(v) + 1e-12
    assert {s[0] for s in v.spans} >= set(want["spans"])
    if want["spans"]:
        assert {g[0] for g in b["idle_gaps"]} <= set(want["spans"]) | {
            "none"}
