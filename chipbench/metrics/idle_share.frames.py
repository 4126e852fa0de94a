"""Share of the traced window with no operation on the device (%)."""

from chipbench import reduce


def read(run):
    busy = reduce.busy_share(run.trace) if run.trace else None
    return None if busy is None else 100.0 * (1.0 - busy)
