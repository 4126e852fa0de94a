"""Run one benchmark cell on the chip this process finds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``check``); the last lines
of standard error are the numbers the correctness check compared, each with
its limit. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
