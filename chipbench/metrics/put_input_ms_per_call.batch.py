"""Host time per call in the fast path's ``fastpath.put_input`` span, minus
the device busy time inside it, averaged over the window's calls (ms)."""

from chipbench import spans


def read(run):
    return (spans.phase_ms_per_call(run.trace, "fastpath.put_input")
            if run.trace else None)
