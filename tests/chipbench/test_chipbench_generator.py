"""The traffic generator: the same seed gives the same plan, and every seed
gets the same work in another order."""

import numpy as np

from chipbench import generator

OPEN = {"loop": "open", "batch": 1, "arrivals": "poisson",
        "rate_per_s": 400.0, "pool_images": 256}
BIG = 2**31 + 977


def test_same_seed_same_plan():
    a, b = generator.plan(OPEN, BIG, 10), generator.plan(OPEN, BIG, 10)
    np.testing.assert_array_equal(a.arrivals_s, b.arrivals_s)
    np.testing.assert_array_equal(a.order, b.order)


def test_seeds_share_the_gaps_in_another_order():
    a = generator.plan(OPEN, BIG, 10)
    b = generator.plan(OPEN, 2**40 + 3, 10)
    assert len(a.arrivals_s) == len(b.arrivals_s) == 4000
    assert not np.array_equal(a.arrivals_s, b.arrivals_s)
    ga, gb = np.diff(a.arrivals_s), np.diff(b.arrivals_s)
    # the same quantile gaps, one left out of each by the first arrival
    assert abs(ga.sum() - gb.sum()) < ga.max() + gb.max()
    assert np.bincount(a.order).tolist() == np.bincount(b.order).tolist()


def test_open_loop_rate_and_window():
    p = generator.plan(OPEN, 5, 10)
    assert p.arrivals_s[0] == 0.0
    assert np.all(np.diff(p.arrivals_s) > 0)
    assert 9.0 < p.arrivals_s[-1] < 10.0
    assert p.pool == 256 and p.batch == 1


def test_closed_loop_cycles_the_pool():
    p = generator.plan({"loop": "closed", "batch": 256, "pool_batches": 4},
                       BIG, 10)
    assert p.loop == "closed" and p.batch == 256 and p.pool == 4
    assert p.order.tolist() == [0, 1, 2, 3]


def test_traffic_files_load():
    assert generator.load("batch256")["loop"] == "closed"
    assert generator.load("frames")["loop"] == "open"
