"""Host time per call of the entry: each call's span on the profiler clock
minus the device busy time inside it, averaged over the calls (ms)."""

from chipbench import reduce


def read(run):
    return reduce.host_ms_per_call(run.trace) if run.trace else None
