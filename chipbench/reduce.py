"""From a profiler trace and host-clock records to numbers.

A trace is the ``.xplane.pb`` file that ``jax.profiler`` writes. Only two
things are read from it:

* device operations: the events of each TPU device plane's ``XLA Ops``
  line (start and duration in nanoseconds, on the profiler's clock), each
  named by its HLO instruction and result shape, e.g.
  ``%vmap_jit_dsc_block__.14 = s8[256,40,40,8]``;
* host events: every event of the host plane, among them the benchmark's
  own annotations (``chipbench.window`` around the measured window,
  ``chipbench.call`` around each call of the entry, ``chipbench.wait``
  while the generator waits for a request to fall due).

Busy time is the union of device-operation intervals, so operations that
overlap are counted once; idle share is one minus busy over the window.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

WINDOW, CALL, WAIT = "chipbench.window", "chipbench.call", "chipbench.wait"
OPS_LINE = "XLA Ops"
N_LABELLED_GAPS = 500   # the longest idle gaps that are named and summed


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float       # ns, profiler clock
    end: float
    line: str = ""

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    device_ops: Dict[str, List[Event]]   # device plane name -> ops
    host: List[Event]

    def window(self) -> Tuple[float, float]:
        w = [e for e in self.host if e.name == WINDOW]
        if len(w) != 1:
            raise ValueError(f"trace holds {len(w)} {WINDOW} spans, not 1")
        return w[0].start, w[0].end

    def spans(self, name: str) -> List[Event]:
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start)


def load(log_dir: str) -> Trace:
    """Read the one ``.xplane.pb`` under ``log_dir``."""
    import jax
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{len(paths)} xplane files under {log_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    dev: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev.setdefault(plane.name, []).extend(
                        Event(_op_name(e.name), e.start_ns,
                              e.start_ns + e.duration_ns, line.name)
                        for e in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, line.name)
                            for e in line.events)
    return Trace(dev, host)


def _op_name(hlo: str) -> str:
    """An HLO instruction's text cut to its name and result shape."""
    return hlo.split("{", 1)[0].strip()


def merge(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of event intervals, clipped to [lo, hi], sorted."""
    iv = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                if e.end > lo and e.start < hi)
    out: List[List[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], lo, hi):
    """Length of [lo, hi] that the merged intervals cover; ``lo`` and
    ``hi`` may be arrays of the same shape."""
    if not merged:
        return np.zeros_like(np.asarray(lo, np.float64))
    s, e = np.asarray(merged, np.float64).T
    before = np.concatenate([[0.0], np.cumsum(e - s)])

    def upto(t):                      # busy time before t
        t = np.asarray(t, np.float64)
        i = np.searchsorted(s, t, side="right") - 1
        part = np.clip(t - s[np.maximum(i, 0)], 0,
                       (e - s)[np.maximum(i, 0)])
        return np.where(i >= 0, before[np.maximum(i, 0)] + part, 0.0)
    return upto(hi) - upto(lo)


def busy_share(trace: Trace) -> Optional[float]:
    """Device busy time over the window, averaged over the devices."""
    lo, hi = trace.window()
    if not trace.device_ops or hi <= lo:
        return None
    busy = [float(covered(merge(ops, lo, hi), lo, hi))
            for ops in trace.device_ops.values()]
    return float(np.mean(busy)) / (hi - lo)


def gaps(merged: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi] between the merged busy ones."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_ms_per_call(trace: Trace) -> Optional[float]:
    """Mean over calls of the call's span minus the device busy time
    inside it, in milliseconds."""
    calls = trace.spans(CALL)
    if not calls or not trace.device_ops:
        return None
    lo, hi = trace.window()
    starts = np.array([c.start for c in calls])
    ends = np.array([c.end for c in calls])
    busy = np.mean([covered(merge(ops, lo, hi), starts, ends)
                    for ops in trace.device_ops.values()], axis=0)
    host = (ends - starts) - busy
    return float(np.mean(host)) / 1e6


def kernel_events(trace: Trace, pattern: str) -> List[Event]:
    lo, hi = trace.window()
    return [e for ops in trace.device_ops.values() for e in ops
            if pattern in e.name and e.start >= lo and e.end <= hi]


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the idle time of the
    longest gaps, summed by the innermost event around each gap's middle
    on the benchmark's own thread: what it was doing while the device
    waited (``DevicePut``: an upload; ``np.asarray(jax.Array)``: a copy back
    to the host; ``chipbench.call`` alone: Python work inside the call)."""
    lo, hi = trace.window()
    per_op: Dict[str, float] = {}
    idle: List[Tuple[float, float]] = []
    for ops in trace.device_ops.values():
        for e in ops:
            if e.end > lo and e.start < hi:
                per_op[e.name] = per_op.get(e.name, 0.0) + (
                    min(e.end, hi) - max(e.start, lo))
        idle.extend(gaps(merge(ops, lo, hi), lo, hi))
    own = {e.line for e in trace.host if e.name == WINDOW}
    host = [e for e in trace.host
            if e.line in own and e.name != WINDOW and e.dur > 0]
    h_start = np.array([h.start for h in host])
    h_end = np.array([h.end for h in host])
    h_dur = h_end - h_start
    named: Dict[str, float] = {}
    longest = sorted(idle, key=lambda g: g[1] - g[0], reverse=True)
    for s, e in longest[:N_LABELLED_GAPS]:
        mid = (s + e) / 2
        around = np.flatnonzero((h_start <= mid) & (h_end >= mid))
        name = (host[around[np.argmin(h_dur[around])]].name if around.size
                else "no host event")
        named[name] = named.get(name, 0.0) + (e - s)
    ops_top = sorted(per_op.items(), key=lambda kv: kv[1], reverse=True)
    gaps_top = sorted(named.items(), key=lambda kv: kv[1], reverse=True)
    return {"device_ops": [[k, v / 1e9] for k, v in ops_top[:top]],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps_top[:top]]}


def percentile(values, pct: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``pct``
    percent of the values at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    k = max(1, math.ceil(pct / 100.0 * len(v)))
    return float(v[k - 1])
