"""Host time of one phase of the program's entry, from the program's own
spans.

The fast path wraps each phase of a call in a ``jax.profiler`` annotation
on the profiler's clock (``fastpath.weights``, ``fastpath.put_input``,
``fastpath.launch``, ``fastpath.readback``). A phase's host time in one
call is the duration of its spans that lie inside that call's
``chipbench.call``, on the same host thread, minus the device busy time
inside them: the definition ``reduce.host_ms_per_call`` applies to the
whole call, so phases that tile a call sum to it. A call without the span
counts as zero; a program without the spans reads nothing.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench import reduce


def phase_ms_per_call(trace: reduce.Trace, span: str) -> Optional[float]:
    """Mean over the window's calls of the host time in ``span`` (ms);
    None when no call holds the span, or the trace has no device."""
    if not trace.device_ops:
        return None
    lo, hi = trace.window()
    calls = [c for c in trace.spans(reduce.CALL)
             if c.start >= lo and c.end <= hi]
    if not calls:
        return None
    by_line = {}                       # host thread -> its calls, by start
    for i, c in enumerate(calls):
        by_line.setdefault(c.line, []).append(i)
    starts = {k: np.array([calls[i].start for i in v])
              for k, v in by_line.items()}
    inside, dur = [], []
    for e in trace.spans(span):
        if e.line not in by_line:
            continue
        j = int(np.searchsorted(starts[e.line], e.start, side="right")) - 1
        i = by_line[e.line][j] if j >= 0 else -1
        if i >= 0 and e.end <= calls[i].end:
            inside.append(i)
            dur.append((e.start, e.end))
    if not inside:
        return None
    s, e = np.array(dur).T
    busy = np.mean([reduce.covered(reduce.merge(ops, lo, hi), s, e)
                    for ops in trace.device_ops.values()], axis=0)
    host = np.bincount(inside, weights=(e - s) - busy,
                       minlength=len(calls))
    return float(np.mean(host)) / 1e6
