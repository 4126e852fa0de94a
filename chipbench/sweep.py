"""The rate sweep that fixes an open-loop cell's arrival rate.

    python3 chipbench/sweep.py --workload <open-loop cell> --seconds <s> \
        --seed <n> --rates 100,200,...

The workload is an open-loop cell of ``BENCHMARK.json``. In one process:
first the cell's single frames back to back (a closed loop, which gives
the service rate), then the open loop at each rate.
Prints one JSON line per run: latency percentiles, the deepest queue, and
how long after the last request fell due the last answer came (the backlog
left at the end of the window). A rate is sustained when that backlog stays
near one service time. The benchmark's own runs never run this.
"""

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import generator, harness  # noqa: E402


def _rates(text):
    return [float(r) for r in text.split(",") if r]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", type=_rates, required=True)
    args = ap.parse_args(argv)
    harness.enable_compile_cache()
    manifest = harness.load_manifest()
    mix = generator.load(harness.cell_of(manifest, args.workload)["traffic"])
    closed = {"loop": "closed", "batch": 1, "pool_batches": mix["pool_images"]}
    load = generator.load
    for rate in [None] + args.rates:
        generator.load = (lambda *_a, **_k: closed) if rate is None else \
            (lambda *_a, r=rate, **_k: dict(mix, rate_per_s=r))
        lines = []
        t0 = time.perf_counter()
        r = harness.run_cell(args.workload, args.seed, args.seconds, False,
                             t0, log=lines.append)
        generator.load = load
        m = {k: v["value"] for k, v in r["metrics"].items()}
        out = {"rate_per_s": rate, "correct": r["correct"],
               "attempted": r["attempted"], "metrics": m}
        out["log"] = lines
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
