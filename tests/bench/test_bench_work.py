"""Operations, bytes and peaks of the benchmark's roofline shares."""

import pytest

import _bench_path  # noqa: F401
from bench.lib import peaks, work
from repro.core.plan import ExecutionPlan


def test_allpairs_counts_come_from_the_shape_alone():
    n, l = 17555, 5072
    w = work.allpairs_work(n, l)
    assert w.flops == 2 * l * n * (n + 1) // 2
    assert w.bytes == n * l * 4 + n * (n + 1) // 2 * 4


@pytest.mark.parametrize("shapes", [((256, 512), (128, 256)),
                                    ((256, 512), (512, 1024))])
def test_two_tile_shapes_give_the_same_count(shapes):
    """The plan's padded tile work changes with the tile shape; the
    benchmark's count of the problem does not."""
    n, l = 1000, 700
    padded = []
    for t, l_blk in shapes:
        plan = ExecutionPlan.create(n, l, t=t, l_blk=l_blk, interpret=True)
        padded.append(plan.total_tiles * t * t * (-(-l // l_blk) * l_blk))
    assert padded[0] != padded[1]
    assert work.allpairs_work(n, l) == work.allpairs_work(n, l)
    assert work.allpairs_work(n, l).flops < 2 * min(padded)


def test_topk_counts_real_rows_not_buckets():
    one = work.rect_topk_work(1, 17555, 5072, 50)
    many = work.rect_topk_work(25, 17555, 5072, 50)
    assert many.flops == 25 * one.flops == 25 * 2 * 5072 * 17555
    # the corpus is read once per launch whatever the rows
    assert many.bytes - one.bytes == 24 * (5072 * 4 + 50 * 8)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("TPU v99")


def test_float32_is_held_against_the_bf16_peak():
    p = peaks.peaks("TPU v5 lite")
    assert peaks.compute_peak(p, "float32") == p.bf16_flops == 197e12
    assert peaks.compute_peak(p, "int8") == p.int8_ops == 393e12
    assert p.hbm_bytes_per_s == 819e9


def test_least_time_is_the_larger_roofline():
    p = peaks.peaks("TPU v5 lite")
    solve = work.allpairs_work(17555, 5072)
    assert solve.bound(p) == "compute"
    assert solve.least_seconds(p) == pytest.approx(solve.flops / 197e12)
    one_row = work.rect_topk_work(1, 17555, 5072, 50)
    assert one_row.bound(p) == "memory"
    assert one_row.least_seconds(p) == pytest.approx(one_row.bytes / 819e9)
