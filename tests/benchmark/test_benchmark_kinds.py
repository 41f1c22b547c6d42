"""Queue kinds in the harness, on the CPU at a size a test holds.

A configuration without a kind sends and restores exactly what the 1v1
harness always did: the digests below are of the request bodies and pool
columns it produced before kinds existed, for one fixed seed. A
configuration that names a kind takes its request fields and its
comparison from the kind's file: here a 5v5 team queue with one region
and game mode pinned stays on the device and is judged by the test's
kind, and the same queue with them left out hands itself to the host
oracle and fails the run.
"""

import copy
import dataclasses
import hashlib
import os
import shutil
import time

import numpy as np
import pytest

from benchmark import cells, serve, traffic
from benchmark.run import result_line, run_cell

SEED = 2**33 + 17
KIND_FILE = os.path.join(os.path.dirname(__file__), "kind_team_probe.py")

#: sha256 of each part, for ``SEED``: a window of 2 s, the warm-up bursts,
#: and the restored pool's columns of ``elo_1v1``.
DIGESTS = {
    "elo_1v1.search.window":
        "918f014fcfec1492921e8402af77f87c1072e8847c2cbc5139f2f796f9f645a6",
    "elo_1v1.search.warmup":
        "d1274e2d457381ea5cf1ec82e3cc1a9f5578b0e7bf27b90cc8a332eab7a13159",
    "elo_1v1.paired.window":
        "1cec2dd9339ae5fffc9abf807c186b7cd5001998473b4a1855646eec8d122907",
    "elo_1v1.paired.warmup":
        "d1274e2d457381ea5cf1ec82e3cc1a9f5578b0e7bf27b90cc8a332eab7a13159",
    "elo_1v1.pool":
        "e1525e0fa05795a36ce19559aaeb93a00d06daa3cdc2f9fa5093c842e9a61c9e",
}


def _digest(parts) -> str:
    d = hashlib.sha256()
    for p in parts:
        d.update(p if isinstance(p, bytes)
                 else np.ascontiguousarray(p).tobytes())
        d.update(b"|")
    return d.hexdigest()


def _part(name: str) -> str:
    cell_name, part = name.rsplit(".", 1)
    if part == "pool":
        cell = cells.find_cell("elo_1v1.search")
        pool, = traffic.add_fields(
            [traffic.waiting_pool(cell.config, SEED)], cell.kind,
            cell.config, SEED, traffic.STREAM_POOL_FIELDS)
        c = serve._pool_columns(pool, 1000.0, None)
        cols = (c.rating, c.rd, c.region, c.mode, c.threshold, c.enqueued_at)
        return _digest(["\n".join(c.ids.tolist()).encode(), *cols,
                        str([str(a.dtype) for a in cols]).encode()])
    cell = cells.find_cell(cell_name)
    if part == "window":
        phase = [traffic.window_arrivals(cell.traffic, SEED, 2.0)]
        stream = traffic.STREAM_WINDOW_FIELDS
    else:
        phase = traffic.warmup_bursts(cell.config, cell.traffic, SEED)
        stream = traffic.STREAM_WARMUP_FIELDS
    phase = traffic.add_fields(phase, cell.kind, cell.config, SEED, stream)
    return _digest([b for a in phase for b in a.bodies()])


@pytest.mark.parametrize("name", sorted(DIGESTS))
def test_a_configuration_without_a_kind_sends_what_it_always_sent(name):
    assert _part(name) == DIGESTS[name]


def test_a_kind_s_fields_ride_in_the_body_and_the_pool_columns():
    """Region and mode enter the pool through the engine's interners."""
    arr = traffic.Arrivals(["a", "b"], np.array([1500.0, 1612.25]),
                           np.zeros(2), {"region": np.array(["eu", "na"],
                                                            object),
                                         "rating_deviation":
                                             np.array([80.0, 350.0])})
    assert arr.bodies() == [
        b'{"id":"a","rating":1500.00,"region":"eu","rating_deviation":80.0}',
        b'{"id":"b","rating":1612.25,"region":"na",'
        b'"rating_deviation":350.0}']

    class Interners:
        def intern_columns(self, regions, modes):
            codes = {"*": 0, "eu": 1, "na": 2}
            return (np.array([codes[r] for r in regions], np.int32),
                    np.array([codes[m] for m in modes], np.int32))

    cols = serve._pool_columns(arr, 5.0, Interners())
    assert cols.region.tolist() == [1, 2] and cols.mode.tolist() == [0, 0]
    assert cols.rd.tolist() == [80.0, 350.0]
    with pytest.raises(ValueError):
        serve._pool_columns(dataclasses.replace(
            arr, fields={"roles": np.array(["tank", "dps"], object)}),
            5.0, Interners())


def team_cell(tmp_path, pinned: bool):
    """A tiny 5v5 team queue of the test's kind, in a checkout of its own:
    ``elo_1v1``'s queue with ``team_size`` 5, 700 waiting, two buckets."""
    root = tmp_path / "checkout"
    (root / "benchmark/kinds").mkdir(parents=True)
    shutil.copy(KIND_FILE, root / "benchmark/kinds/team_probe.py")
    cell = cells.find_cell("elo_1v1.search")
    cfg = copy.deepcopy(cell.config)
    cfg.update(name="team_5v5_probe", kind="team_probe")
    if pinned:
        cfg["pinned"] = {"region": "eu", "game_mode": "ranked"}
    cfg["waiting"]["count"] = 700
    cfg["service"]["queues"][0]["team_size"] = 5
    cfg["service"]["engine"].update(pool_capacity=2048, pool_block=512,
                                    batch_buckets=[16, 128])
    return dataclasses.replace(cell, name="team_5v5_probe.search",
                               config=cfg, root=str(root),
                               traffic=dict(cell.traffic, rate=300.0))


def _line(cell, run, numbers):
    return result_line(cell, run, numbers, False,
                       {"platform": "cpu", "kind": "cpu", "count": 1},
                       cells.metric_units(cells.benchmark_spec()))


def test_a_team_queue_of_a_kind_stays_on_the_device(tmp_path):
    cell = team_cell(tmp_path, pinned=True)
    run, numbers = run_cell(cell, SEED, 0.5, False,
                            t_process=time.perf_counter())
    assert numbers["off_device"] == 0
    assert not cell.kind.failures(numbers), numbers
    assert set(run.data.sent_fields["region"].tolist()) == {"eu"}
    matched = [r for r in run.data.replies if r.status == "matched"]
    assert matched
    for rep in matched:
        assert len(set(rep.players)) == 10
        assert [len(t) for t in rep.teams] == [5, 5]
        assert {p for t in rep.teams for p in t} == set(rep.players)
    line = _line(cell, run, numbers)
    assert line["correct"] is True
    assert list(line["checks"]) == list(cell.kind.LIMITS)


def test_a_team_queue_of_wildcards_leaves_the_device_and_fails(tmp_path):
    cell = team_cell(tmp_path, pinned=False)
    run, numbers = run_cell(cell, SEED, 0.5, False,
                            t_process=time.perf_counter())
    assert numbers["off_device"] >= 1
    assert cell.kind.failures(numbers)
    assert _line(cell, run, numbers)["correct"] is False
