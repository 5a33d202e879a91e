"""The trace reduction against values counted by hand from a trace
recorded on a TPU v5e (``fixtures/trace_1chip.xplane.pb``: two SVRG
calls of 8 steps on 4,096 rows × 4,096 columns, q = 4, recorded by
``bench/record_fixture.py``).

Counted from the fixture: the ``bench_window`` host span runs from
55,014,495 to 70,078,783 ns.  The ``XLA Ops`` line of ``/device:TPU:0``
holds 526 events; two are ``%while.3``, which enclose the loop bodies.
Marking every other event's nanoseconds inside the window on a boolean
grid gives 2,758,321 busy ns.  The 36 ``tpu_custom_call`` events take
2,018,756 ns: per SVRG call, the full-gradient pass's forward (output
f32[4,4096,1], 2 calls, 798,804 ns in all) and backward (f32[4,1024,1],
2 calls, 902,863 ns), and per step the forward (f32[4,64,2], 16 calls,
113,620 ns) and backward (f32[4,1024,2], 16 calls, 203,469 ns).
"""
import math
from pathlib import Path

import pytest

from bench.harness import cost, device, trace

FIXTURES = Path(__file__).parent / "fixtures"
WINDOW_NS = 70_078_783 - 55_014_495
BUSY_NS = 2_758_321
KERNEL_NS = 2_018_756
V5E = device.peaks("TPU v5 lite")


def _least(floats_moved, flops):
    """max(bytes / peak bandwidth, ops / peak FLOP/s) of one call."""
    return max(4 * floats_moved / V5E["hbm_bytes_per_s"],
               flops / V5E["flops_bf16_per_s"])


# (calls, floats read and written, multiply-add ops × 2) by kind
KINDS = [
    (2, 4 * 4096 * 1024 + 4 * 1024 + 4 * 4096, 2 * 4096 * 1024 * 4),
    (2, 4 * 4096 * 1024 + 4096 + 4 * 1024, 2 * 4096 * 1024 * 4),
    (16, 4 * 64 * 1024 + 4 * 1024 * 2 + 4 * 64 * 2, 2 * 64 * 1024 * 2 * 4),
    (16, 4 * 64 * 1024 + 64 * 2 + 4 * 1024 * 2, 2 * 64 * 1024 * 2 * 4),
]


@pytest.fixture(scope="module")
def one_chip():
    return trace.reduce_file(str(FIXTURES / "trace_1chip.xplane.pb"))


def test_window_and_idle_share(one_chip):
    assert one_chip.window_s == pytest.approx(WINDOW_NS * 1e-9, rel=1e-12)
    assert one_chip.busy_s == pytest.approx(BUSY_NS * 1e-9, rel=1e-12)
    assert one_chip.idle_share == pytest.approx(1 - BUSY_NS / WINDOW_NS,
                                                rel=1e-12)


def test_kernel_time_and_roofline(one_chip):
    calls = one_chip.mosaic_calls()
    assert len(calls) == 36
    assert sum(c.dur for c in calls) == KERNEL_NS
    least = sum(n * _least(moved, flops) for n, moved, flops in KINDS)
    share = one_chip.roofline_share(calls, V5E, (64, 4096), [1024] * 4)
    assert share == pytest.approx(100 * least / (KERNEL_NS * 1e-9),
                                  rel=1e-9)
    assert 0 < share <= 100


def test_kernel_cost_leaves_out_padding():
    """The D4 cell's step calls: four parties of 63, 64, 63 and 64
    columns padded to 128 lanes; the full-gradient pass's 350,000 rows
    padded to a row tile.  Only the logical extents count."""
    widths, rows = [63, 64, 63, 64], (64, 350_000)
    fwd = cost.kernel_cost([((4, 64, 128), 4), ((4, 128, 2), 4)],
                           [((4, 64, 2), 4)], rows, widths)
    assert fwd == (2 * 64 * 254 * 2,
                   4 * (64 * 254 + 254 * 2 + 4 * 64 * 2))
    bwd = cost.kernel_cost([((4, 64, 128), 4), ((64, 2), 4)],
                           [((4, 128, 2), 4)], rows, widths)
    assert bwd == (2 * 64 * 254 * 2, 4 * (64 * 254 + 64 * 2 + 254 * 2))
    full = cost.kernel_cost([((4, 350_208, 128), 4), ((4, 128, 1), 4)],
                            [((4, 350_208, 1), 4)], rows, widths)
    assert full == (2 * 350_000 * 254,
                    4 * (350_000 * 254 + 254 + 4 * 350_000))
    with pytest.raises(ValueError):
        cost.kernel_cost([((2, 64, 128), 4)], [((2, 64, 1), 4)], rows,
                         widths)


def test_no_collective_on_one_chip(one_chip):
    assert one_chip.collective_exposed_share is None


def test_breakdown(one_chip):
    bd = one_chip.breakdown()
    ops = dict(bd["device_ops"])
    assert ops["tpu_custom_call"] == pytest.approx(KERNEL_NS * 1e-9)
    assert "while" not in ops
    idle = sum(s for _, s in bd["idle_gaps"])
    assert 0 < idle <= (WINDOW_NS - BUSY_NS) * 1e-9 * (1 + 1e-12)
    assert {name for name, _ in bd["idle_gaps"]} <= set(trace.HOST_SPANS) \
        | {"outside benchmark spans"}


def test_hlo_shapes():
    text = ('%closed_call.13 = f32[4,1024,2]{2,1,0:T(8,128)S(1)} custom-call('
            'f32[4,64,1024]{2,1,0:T(8,128)S(1)} %copy_bitcast_fusion.2, '
            'f32[64,2]{1,0:T(8,128)S(1)} %pad_maximum_fusion.5), '
            'custom_call_target="tpu_custom_call", operand_layout_constraints'
            '={f32[4,64,1024]{2,1,0}, f32[64,2]{1,0}}')
    outs, operands = trace.hlo_shapes(text)
    assert outs == [((4, 1024, 2), 4)]
    assert operands == [((4, 64, 1024), 4), ((64, 2), 4)]
    tup = ('%f = (f32[64,2]{1,0:T(8,128)}, bf16[8]{0}) fusion(f32[4,64,2]'
           '{2,1,0} %a, u32[4]{0} %b), kind=kLoop')
    assert trace.hlo_shapes(tup) == ([((64, 2), 4), ((8,), 2)],
                                     [((4, 64, 2), 4), ((4,), 4)])


def test_exposed_collective_by_hand():
    """Two chips, window 0..100 ns.  Chip a: all-reduce 10..40, compute
    30..50 -> exposed 10..30 (20 ns).  Chip b: all-reduce 60..70 alone ->
    10 ns.  Mean exposed share (0.2 + 0.1) / 2."""
    op = trace.Op
    red = trace.Reduction(
        (0, 100),
        {"a": [op("%all-reduce.1 = f32[64] all-reduce(...)", 10, 40),
               op("%fusion.2 = f32[64] fusion(...)", 30, 50)],
         "b": [op("%all-reduce.1 = f32[64] all-reduce(...)", 60, 70)]},
        [])
    assert red.collective_exposed_share == pytest.approx(0.15)
    assert red.busy_s == pytest.approx((40 + 10) / 2 * 1e-9)
    assert math.isclose(red.idle_share, 1 - 25 / 100)
