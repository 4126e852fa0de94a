"""The one traffic generator: turns a traffic mix's parameters into a plan.

A traffic mix is a JSON file, ``chipbench/traffic/<traffic>.json``:

* ``{"loop": "closed", "batch": B, "pool_batches": P}``: back-to-back
  calls of B distinct images each, cycling over P batches made in set-up;
* ``{"loop": "open", "batch": 1, "arrivals": "poisson", "rate_per_s": R,
  "pool_images": P}``: single-frame requests due at Poisson arrival times of
  mean rate R, served one at a time in arrival order, images cycling over
  a pool of P.

Every seed gets the same set of inter-arrival gaps (the exponential
distribution's quantiles), in an order drawn from the seed, so that seeds
change which burst comes when and not how much work a run holds.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent.parent


@dataclasses.dataclass(frozen=True)
class Plan:
    loop: str                 # "closed" | "open"
    batch: int                # images per call
    pool: int                 # distinct calls' inputs made in set-up
    arrivals_s: np.ndarray    # open loop: due times from the window start
    order: np.ndarray         # pool index of each call, in call order


def load(traffic: str, root: pathlib.Path = ROOT) -> dict:
    path = root / "chipbench" / "traffic" / f"{traffic}.json"
    return json.loads(path.read_text())


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def plan(mix: dict, seed: int, seconds: float) -> Plan:
    batch = int(mix["batch"])
    if mix["loop"] == "closed":
        pool = int(mix["pool_batches"])
        # a closed loop runs as long as the window; the order repeats
        order = np.arange(pool)
        return Plan("closed", batch, pool, np.zeros(0), order)
    if mix["loop"] != "open" or mix["arrivals"] != "poisson":
        raise ValueError(f"unknown traffic mix {mix}")
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    gaps = _rng(seed, 1).permutation(gaps)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    pool = int(mix["pool_images"])
    order = _rng(seed, 2).permutation(np.arange(n) % pool)
    return Plan("open", batch, pool, arrivals, order)

