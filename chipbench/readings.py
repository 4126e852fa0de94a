"""Readings that set the correctness limits, on the chip at a cell's size.

    python3 chipbench/readings.py --workload <name> --seconds <s> \
        --seeds 1,2,... [--control-seeds ...] [--fault-seeds ...]

In one process, runs the cell (a short window at the cell's own load) for
each seed as it is (the lower reading), with the 4-bit control in the
program's place (the upper reading), and with each fault of
``chipbench/faults.py`` planted in the program. Prints one JSON line per
run with the numbers the check compared; the benchmark's own runs never
run this.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import faults, harness  # noqa: E402


def _seeds(text):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    manifest = harness.load_manifest()
    cell = harness.cell_of(manifest, args.workload)
    cfg, _ = harness.load_config(manifest, cell["config"])
    n_stages = len(cfg["blocks"]) + 3
    runs = [("program", s, contextlib.nullcontext) for s in args.seeds]
    runs += [("control", s, faults.control) for s in args.control_seeds]
    for s in args.fault_seeds:
        runs += [(f"stage{i}", s, lambda i=i: faults.stage_fault(i))
                 for i in range(n_stages)]
        runs += [("half_batch", s, faults.half_batch),
                 ("altered_answer", s, faults.altered_answer)]
    for kind, seed, ctx in runs:
        lines = []
        t0 = time.perf_counter()
        with ctx():
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 t0, log=lines.append)
        print(json.dumps({"workload": args.workload, "kind": kind,
                          "seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          "check": r["check"], "log": lines,
                          "run_s": time.perf_counter() - t0}), flush=True)
    print(f"# readings: {len(runs)} runs in "
          f"{time.perf_counter() - T_START:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
