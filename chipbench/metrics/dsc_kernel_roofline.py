"""The fused DSC kernel's share of its roofline (%).

For each DSC block and call: the least time the chip needs for the block's
algorithmic operations (two per multiply-accumulate) and its minimum HBM
bytes (input and output maps, int8 weights, int32 biases, float32
multipliers), the larger of operations over the int8 peak and bytes over
HBM bandwidth. Summed over the blocks and the traced window's calls, and
divided by the summed device time of the kernel's events in the trace.
Lane padding, halo recompute and the residual add (outside the kernel) are
not counted.
"""

from chipbench import reduce

KERNEL = "jit_dsc_block"   # in the trace's name of the kernel's custom call


def least_seconds(costs, batch, peaks):
    """Least time of one call of every DSC block, and the bound that set
    each block's time ("compute" or "memory")."""
    total, bounds = 0.0, []
    for c in costs:
        t_ops = 2 * c["macs"] * batch / peaks["int8_ops_per_s"]
        t_mem = (c["map_bytes"] * batch + c["param_bytes"]) / \
            peaks["hbm_bytes_per_s"]
        total += max(t_ops, t_mem)
        bounds.append("compute" if t_ops >= t_mem else "memory")
    return total, bounds


def read(run):
    if run.trace is None:
        return None
    events = reduce.kernel_events(run.trace, KERNEL)
    calls = len(run.trace.spans(reduce.CALL))
    if not events or not calls:
        return None
    costs = [c for c in run.ref.stage_costs(run.cfg) if c["kind"] == "dsc"]
    t_min, _ = least_seconds(costs, run.plan.batch, run.peaks)
    t_dev = sum(e.dur for e in events) / 1e9
    return 100.0 * t_min * calls / t_dev
