"""The whole step's share of the chip's int8 peak (%): images answered in
the traced window over its length, times the algorithm's operations per
image (two per multiply-accumulate), over the peak."""

from chipbench import reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    calls = len(run.trace.spans(reduce.CALL))
    if not calls or hi <= lo:
        return None
    ops = 2 * sum(c["macs"] for c in run.ref.stage_costs(run.cfg))
    rate = calls * run.plan.batch / ((hi - lo) / 1e9)
    return 100.0 * rate * ops / run.peaks["int8_ops_per_s"]
