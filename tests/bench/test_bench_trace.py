"""The reduction from a profiler trace to device metrics, on a small
trace with known answers."""
from __future__ import annotations

import bench_testkit as K
import pytest

from bench.lib import trace as TR


def _trace():
    ms = 1_000_000
    tr = TR.Trace()
    # window 0..100 ms; device 0 busy 10-30 (two ops back to back) and
    # 50-60 (the kernel), device 1 busy 0-100
    tr.devices[0] = sorted([
        (10 * ms, 25 * ms, "%fusion.1 = bf16[8] fusion(%x)"),
        (25 * ms, 30 * ms, "%fusion.2 = bf16[8] fusion(%paged_decode.3)"),
        (50 * ms, 60 * ms, "%paged_decode.3 = (f32[8]) custom-call(%y)"),
        (95 * ms, 120 * ms, "%fusion.1 = bf16[8] fusion(%x)")])
    # device 1: a loop whose body holds one kernel call
    tr.devices[1] = [(-5 * ms, 100 * ms, "%while.9 = (s32[]) while(%t)"),
                     (40 * ms, 70 * ms, "%paged_decode.4 = (f32[8]) custom-call")]
    tr.host = sorted([(0, 100 * ms, TR.WINDOW),
                      (0, 40 * ms, "bench.step"),
                      (40 * ms, 45 * ms, "bench.account"),
                      (45 * ms, 90 * ms, "bench.sleep"),
                      (90 * ms, 100 * ms, "bench.step")])
    return tr


def test_window_is_the_harness_span():
    assert _trace().window() == (0, 100_000_000)


def test_busy_is_the_union_inside_the_window():
    tr = _trace()
    lo, hi = tr.window()
    assert TR.busy_ns(tr.devices[0], lo, hi) == 35_000_000   # 20 + 10 + 5
    assert TR.busy_ns(tr.devices[1], lo, hi) == 100_000_000
    idle = 1 - (35 + 100) / 2 / 100
    assert idle == pytest.approx(0.325)


def test_kernel_time_sums_its_events():
    tr = _trace()
    lo, hi = tr.window()
    assert TR.kernel_ns(tr.devices[0], "paged_decode", lo, hi) == 10_000_000
    assert TR.kernel_count(tr.devices[0], "paged_decode", lo, hi) == 1
    assert TR.kernel_ns(tr.devices[1], "paged_decode", lo, hi) == 30_000_000
    assert TR.op("%paged_decode.3 = (f32[8]) custom-call(%y)") == "paged_decode.3"


def test_idle_gaps_named_by_the_host_span_in_their_middle():
    tr = _trace()
    lo, hi = tr.window()
    gaps = TR.idle_gaps(tr, lo, hi)
    assert gaps[0] == ["bench.sleep", pytest.approx(0.035)]    # 60-95
    assert gaps[1] == ["bench.account", pytest.approx(0.020)]  # 30-50: mid 40
    assert gaps[2] == ["bench.step", pytest.approx(0.010)]     # 0-10
    assert len(gaps) == 3


def test_self_time_leaves_out_nested_events():
    ms = 1_000_000
    ev = [(0, 100 * ms, "%while.1 = x"), (10 * ms, 20 * ms, "%a.1 = y"),
          (30 * ms, 60 * ms, "%b.2 = z"), (40 * ms, 50 * ms, "%c.3 = w")]
    assert dict(TR.self_ns(ev)) == {"while.1": 60 * ms, "a.1": 10 * ms,
                                    "b.2": 20 * ms, "c.3": 10 * ms}


def test_top_ops_average_over_devices():
    tr = _trace()
    lo, hi = tr.window()
    top = dict((n, s) for n, s in TR.top_ops(tr, lo, hi))
    assert top["while.9"] == pytest.approx(0.035)        # (100 - 30) / 2
    assert top["paged_decode.4"] == pytest.approx(0.015)
    assert top["fusion.1"] == pytest.approx(0.010)       # (15 + 5) / 2


def test_recorded_trace_busy_idle_and_kernel_time():
    tr = K.recorded_trace()
    lo, hi = tr.window()
    ev = tr.devices[0]
    busy = TR.busy_ns(ev, lo, hi)
    assert 0 < busy < hi - lo
    # one line of properly nested ops: self times add up to the busy time
    assert sum(t for _, t in TR.self_ns(TR._clip(ev, lo, hi))) == busy
    gaps = TR.idle_gaps(tr, lo, hi, n=10 ** 6)
    assert sum(s for _, s in gaps) == pytest.approx((hi - lo - busy) / 1e9)
    kernel = [e - s for s, e, n in TR._clip(ev, lo, hi)
              if n.startswith("paged_decode")]
    assert len(kernel) > 0
    assert TR.kernel_ns(ev, "paged_decode", lo, hi) == sum(kernel)
    assert TR.kernel_count(ev, "paged_decode", lo, hi) == len(kernel)
    assert gaps[0][0] == "bench.sleep"
