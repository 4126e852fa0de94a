"""Host time per call of each phase of the fast path's entry, from its
spans, on small synthetic traces whose answers are counted by hand."""

import types

import pytest

from chipbench import harness, reduce, spans
from chipbench.reduce import Event, Trace

MS = 1e6  # ns
PHASES = ("weights", "put_input", "launch", "readback")

# window 0..100 ms; two calls, 10..40 and 50..90 ms, each tiled by the
# four phases (start, end in ms)
TILES = {
    "weights": ((10, 14), (50, 55)),
    "put_input": ((14, 22), (55, 65)),
    "launch": ((22, 27), (65, 72)),
    "readback": ((27, 40), (72, 90)),
}
# device busy: 20..24 straddles put_input and launch of call 1,
# 28..36 lies in its readback, 74..80 and 78..86 overlap in call 2's
# readback (12 ms once merged), 95..110 lies outside every call
OPS = ((20, 24), (28, 36), (74, 80), (78, 86), (95, 110))
# span minus busy inside it, per call, then the mean
WANT_MS = {
    "weights": (4 + 5) / 2,
    "put_input": ((8 - 2) + 10) / 2,
    "launch": ((5 - 2) + 7) / 2,
    "readback": ((13 - 8) + (18 - 12)) / 2,
}


def _trace(phases=True, strays=False):
    host = [Event(reduce.WINDOW, 0, 100 * MS, "main"),
            Event(reduce.CALL, 10 * MS, 40 * MS, "main"),
            Event(reduce.CALL, 50 * MS, 90 * MS, "main")]
    if phases:
        host += [Event(f"fastpath.{p}", s * MS, e * MS, "main")
                 for p, tiles in TILES.items() for s, e in tiles]
    if strays:
        host += [
            # inside call 1's time, on another thread
            Event("fastpath.launch", 30 * MS, 31 * MS, "worker"),
            Event("fastpath.weights", 11 * MS, 13 * MS, "worker"),
            # inside the window, outside every call
            Event("fastpath.weights", 92 * MS, 96 * MS, "main"),
            # a call past the window's end, with its phases
            Event(reduce.CALL, 101 * MS, 105 * MS, "main"),
            Event("fastpath.readback", 101 * MS, 104 * MS, "main"),
        ]
    ops = [Event("fusion", s * MS, e * MS) for s, e in OPS]
    return Trace({"/device:TPU:0": ops}, host)


def _read(phase, trace):
    return harness.metric_reader(f"{phase}_ms_per_call.batch")(
        types.SimpleNamespace(trace=trace))


@pytest.mark.parametrize("phase", PHASES)
def test_phase_host_time_subtracts_device_busy(phase):
    assert _read(phase, _trace()) == pytest.approx(WANT_MS[phase])


@pytest.mark.parametrize("phase", PHASES)
def test_spans_off_the_window_or_thread_are_ignored(phase):
    assert _read(phase, _trace(strays=True)) == pytest.approx(
        WANT_MS[phase])


@pytest.mark.parametrize("phase", PHASES)
def test_no_span_reads_nothing(phase):
    assert _read(phase, _trace(phases=False)) is None
    assert _read(phase, None) is None
    no_device = _trace()
    no_device.device_ops.clear()
    assert _read(phase, no_device) is None


def test_phases_that_tile_a_call_sum_to_its_host_time():
    t = _trace()
    total = sum(spans.phase_ms_per_call(t, f"fastpath.{p}") for p in PHASES)
    assert reduce.host_ms_per_call(t) == pytest.approx(23.0)
    assert total == pytest.approx(reduce.host_ms_per_call(t))


def test_a_call_without_the_span_counts_as_zero():
    t = _trace()
    t.host.append(Event("fastpath.compile", 26 * MS, 26.5 * MS, "main"))
    assert spans.phase_ms_per_call(t, "fastpath.compile") == \
        pytest.approx(0.5 / 2)
