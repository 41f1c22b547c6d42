"""The benchmark's traffic generator: the same seed gives the same
arrivals, every seed the same amount of work, and a rating survives the
request body exactly."""

import json

import numpy as np
import pytest

from benchmark import cells, traffic

SEARCH = {"loop": "open", "arrivals": "poisson", "rate": 500.0,
          "ratings": {"model": "iid", "mean": 1500.0, "sd": 300.0}}
PAIRED = dict(SEARCH, ratings=dict(SEARCH["ratings"], model="paired"))


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3, -5])
def test_same_seed_same_arrivals(seed):
    a = traffic.window_arrivals(SEARCH, seed, 2.0)
    b = traffic.window_arrivals(SEARCH, seed, 2.0)
    assert a.ids == b.ids
    np.testing.assert_array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.rating, b.rating)


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.window_arrivals(SEARCH, 1, 3.0)
    b = traffic.window_arrivals(SEARCH, 2, 3.0)
    assert len(a) == len(b) == 1500
    assert not np.array_equal(a.due, b.due)
    for arr in (a, b):
        assert np.all(np.diff(arr.due) >= 0)
        assert arr.due[0] >= 0.0 and arr.due[-1] < 3.0


def test_streams_of_one_seed_are_independent():
    cfg = cells.find_cell("elo_1v1.search").config
    pool = traffic.waiting_pool(cfg, 3)
    window = traffic.window_arrivals(SEARCH, 3, 1.0)
    assert len(pool) == cfg["waiting"]["count"]
    assert not np.array_equal(pool.rating[:len(window)], window.rating)
    np.testing.assert_array_equal(
        pool.rating, traffic.waiting_pool(cfg, 3).rating)


def test_paired_arrivals_come_in_equal_twos():
    arr = traffic.window_arrivals(PAIRED, 5, 1.0)
    r = arr.rating
    np.testing.assert_array_equal(r[0::2][:len(r[1::2])], r[1::2])
    assert len(set(r.tolist())) > len(r) // 2 - 5


def test_a_rating_survives_the_request_body():
    arr = traffic.window_arrivals(SEARCH, 9, 1.0)
    for body, pid, r in zip(arr.bodies(), arr.ids, arr.rating.tolist()):
        d = json.loads(body)
        assert d["id"] == pid and d["rating"] == r


def test_warmup_lands_in_every_bucket():
    cfg = cells.find_cell("elo_1v1.search").config
    buckets = cfg["service"]["engine"]["batch_buckets"]
    sizes = [len(b) for b in traffic.warmup_bursts(cfg, SEARCH, 1)]
    for b, n in zip(buckets[:-1], sizes):
        assert min(x for x in buckets if x >= n) == b
    assert sizes[-1] >= 2 * buckets[-1]


def test_only_open_loop_poisson_is_generated():
    with pytest.raises(ValueError):
        traffic.window_arrivals(dict(SEARCH, loop="closed"), 1, 1.0)
