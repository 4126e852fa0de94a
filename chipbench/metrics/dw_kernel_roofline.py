"""The kernel of the blocks without expansion (t=1): its share of its
roofline (%).

As ``dsc_kernel_roofline``, over the stages of kind ``"dw"``: for each such
block and call, the least time the chip needs for its operations (two per
multiply-accumulate) and its minimum HBM bytes, summed over the traced
window's calls and divided by the summed device time of the kernel's
events (``jit_dw_block``). Nothing to read where no such kernel ran.
"""

import importlib.util
import pathlib

from chipbench import reduce

KERNEL = "jit_dw_block"   # in the trace's name of the kernel's custom call


def _least_seconds():
    path = pathlib.Path(__file__).with_name("dsc_kernel_roofline.py")
    spec = importlib.util.spec_from_file_location("dsc_kernel_roofline_",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.least_seconds


def read(run):
    if run.trace is None:
        return None
    events = reduce.kernel_events(run.trace, KERNEL)
    calls = len(run.trace.spans(reduce.CALL))
    costs = [c for c in run.ref.stage_costs(run.cfg) if c["kind"] == "dw"]
    if not events or not calls or not costs:
        return None
    t_min, _ = _least_seconds()(costs, run.plan.batch, run.peaks)
    t_dev = sum(e.dur for e in events) / 1e9
    return 100.0 * t_min * calls / t_dev
