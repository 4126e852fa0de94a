"""The reduction from a trace to busy time, idle share, kernel time and
host time per call, on a small synthetic trace whose answers are counted
by hand."""

import numpy as np
import pytest

from chipbench import reduce
from chipbench.reduce import Event, Trace

MS = 1e6  # ns


def _trace():
    # window 0..100 ms; two calls 10..40 and 50..90 ms
    host = [Event(reduce.WINDOW, 0, 100 * MS, "main"),
            Event(reduce.CALL, 10 * MS, 40 * MS, "main"),
            Event(reduce.CALL, 50 * MS, 90 * MS, "main"),
            Event("weights_of", 10 * MS, 22 * MS, "main"),
            Event(reduce.WAIT, 40 * MS, 50 * MS, "main"),
            Event("Allocate", 9 * MS, 11 * MS, "worker")]  # other thread
    ops = [Event("fusion.1", 20 * MS, 30 * MS),
           Event("%vmap_jit_dsc_block__.3", 25 * MS, 35 * MS),   # overlaps
           Event("%vmap_jit_dsc_block__.3", 60 * MS, 70 * MS),
           Event("copy.2", 95 * MS, 110 * MS)]                 # past window
    return Trace({"/device:TPU:0": ops}, host)


def test_busy_union_counts_overlap_once_and_clips_to_window():
    t = _trace()
    lo, hi = t.window()
    merged = reduce.merge(t.device_ops["/device:TPU:0"], lo, hi)
    assert merged == [(20 * MS, 35 * MS), (60 * MS, 70 * MS),
                      (95 * MS, 100 * MS)]
    assert reduce.busy_share(t) == pytest.approx(30 / 100)


def test_idle_gaps_and_coverage():
    t = _trace()
    merged = reduce.merge(t.device_ops["/device:TPU:0"], *t.window())
    assert reduce.gaps(merged, 0, 100 * MS) == [
        (0, 20 * MS), (35 * MS, 60 * MS), (70 * MS, 95 * MS)]
    got = reduce.covered(merged, np.array([0, 30 * MS]),
                         np.array([100 * MS, 65 * MS]))
    np.testing.assert_allclose(got, [30 * MS, 10 * MS])
    assert float(reduce.covered([], 0, 5)) == 0.0


def test_kernel_event_sum_and_host_time_per_call():
    t = _trace()
    ev = reduce.kernel_events(t, "jit_dsc_block")
    assert sum(e.dur for e in ev) == 20 * MS
    # call 1: 30 ms span, busy 20..35 inside -> 15; call 2: 40 ms, busy 10
    assert reduce.host_ms_per_call(t) == pytest.approx(((30 - 15) +
                                                       (40 - 10)) / 2)


def test_breakdown_names_gaps_by_the_innermost_host_event():
    b = reduce.breakdown(_trace())
    ops = dict(b["device_ops"])
    assert ops["%vmap_jit_dsc_block__.3"] == pytest.approx(0.020)
    assert ops["copy.2"] == pytest.approx(0.005)      # clipped at 100 ms
    gaps = dict(b["idle_gaps"])
    # 0..20 ms: middle at 10 ms lies in the first call and weights_of,
    # the shorter wins (the other thread's event is not the benchmark's); 35..60: middle 47.5 ms in the wait; 70..95: call 2
    assert gaps == pytest.approx({"weights_of": 0.020,
                                  reduce.WAIT: 0.025, reduce.CALL: 0.025})


def test_window_must_be_unique():
    with pytest.raises(ValueError):
        Trace({}, []).window()


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))
    assert reduce.percentile(v, 95) == 95
    assert reduce.percentile(v, 50) == 50
    assert reduce.percentile([7.0], 95) == 7.0
