"""The reduction from a profiler trace to busy and idle time, time by
program and by operation, and gaps by host span: on made-up intervals, and
on one small trace recorded on the chip (`data/record_trace.py`)."""

import os

import pytest

from lib import xplane

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")


def test_union_counts_overlap_once_and_lists_the_gaps():
    busy, gaps = xplane.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8), (8, 9)])
    assert busy == pytest.approx(3 + 1 + 1)
    assert gaps == [(3, 5), (6, 8)]


def test_an_enclosing_operation_is_counted_without_its_children():
    events = [(0.0, 10.0, "p/while"), (1.0, 4.0, "p/fusion"), (4.0, 6.0, "p/copy"),
              (20.0, 21.0, "p/fusion")]
    assert xplane.self_times(events) == pytest.approx({"p/while": 5.0, "p/fusion": 4.0, "p/copy": 2.0})


def test_names():
    hlo = "%convert_reduce_fusion.12 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop"
    assert xplane.op_kind(hlo) == "convert_reduce_fusion"
    assert xplane.op_kind("%copy-done.3.1 = bf16[2] copy-done(...)") == "copy-done"
    assert xplane.program_name("jit__paged_decode_impl(4226678822324716436)") == "jit__paged_decode_impl"


def test_gaps_go_to_the_innermost_span_that_covers_them():
    trace = xplane.DeviceTrace(window_s=10.0, chips=[xplane.ChipTrace(
        busy_s=4.0, gaps=[(1.0, 3.0), (5.0, 5.00001), (6.0, 9.0)])], to_monotonic_s=100.0)
    spans = [("fit", 100.0, 110.0), ("ps/push", 101.5, 102.5), ("sched_step", 106.0, 109.5)]
    assert trace.gaps_by_span(spans) == [["sched_step", pytest.approx(3.0)],
                                         ["ps/push", pytest.approx(2.0)],
                                         [xplane.SHORT_GAP, pytest.approx(1e-5)]]


@pytest.mark.skipif(not os.path.exists(SMALL), reason="no recorded trace")
def test_recorded_trace():
    trace = xplane.reduce_trace(SMALL, {xplane.MARK_OPEN: 0.0})
    assert len(trace.chips) == 1 and trace.to_monotonic_s is not None
    chip = trace.chips[0]
    assert {"jit__chunk_prefill_impl", "jit__paged_decode_impl"} <= set(chip.program_calls)
    assert 0 < trace.busy_s < trace.window_s
    gaps = sum(b - a for a, b in chip.gaps)
    assert trace.busy_s + gaps == pytest.approx(trace.window_s, rel=1e-6)
    in_programs = sum(sum(v) for v in chip.program_calls.values())
    assert trace.busy_s <= in_programs * 1.001  # operations run inside program calls
    assert sum(chip.op_seconds.values()) == pytest.approx(trace.busy_s, rel=0.02)
    top = trace.top_ops(10)
    assert len(top) == 10 and all(name.startswith("jit_") and "/" in name for name, _ in top)
