"""The trace reduction and the formation window's byte count, on events
and shapes small enough to check by hand."""

import pytest

from benchmark import peaks, work
from benchmark.trace import (
    NO_HOST_EVENT,
    Event,
    busy_seconds,
    idle_gaps,
    is_collective,
    reduce_trace,
    self_times,
)


def test_busy_is_the_union_of_overlapping_ops():
    ops = [Event("a", 0.0, 2.0), Event("b", 1.0, 3.0), Event("c", 5.0, 6.0)]
    assert busy_seconds(ops) == pytest.approx(4.0)
    assert idle_gaps(ops) == [(3.0, 5.0)]


def test_idle_share_and_per_chip_average():
    devices = {"/device:TPU:0": [Event("%fusion.1 = f", 0.0, 1.0)],
               "/device:TPU:1": [Event("%fusion.1 = f", 0.0, 3.0)]}
    t = reduce_trace(devices, [], window_s=10.0)
    assert t["busy_s"] == pytest.approx(2.0)
    assert t["idle_share"] == pytest.approx(0.8)


def test_nested_ops_count_their_own_time_once():
    ops = [Event("%while.4 = (s32[]) while(%t)", 0.0, 10.0),
           Event("%fusion.2 = f32[8] fusion(%a)", 1.0, 4.0),
           Event("%fusion.3 = f32[8] fusion(%b)", 5.0, 6.0),
           Event("%copy.1 = f32[8] copy(%c)", 12.0, 13.0)]
    own = dict(self_times(ops))
    assert own == {"%while.4": 6.0, "%fusion.2": 3.0, "%fusion.3": 1.0,
                   "%copy.1": 1.0}
    t = reduce_trace({"d": ops}, [], window_s=20.0)
    assert sum(v for _, v in t["device_ops"]) == pytest.approx(
        t["busy_s"])


def test_collectives_by_their_own_op_kind():
    assert is_collective("%all-gather-start.1 = (f32[4,8]) all-gather-start(%x)")
    assert is_collective("%collective-permute-done = f32[8] "
                         "collective-permute-done(%y)")
    # An op that only reads a collective's result is not one.
    assert not is_collective("%fusion.9 = f32[8] fusion(%all-gather.1)")
    devices = {f"/device:TPU:{i}": [
        Event("%all-gather.1 = f32[2,8] all-gather(%v)", 0.0, 0.5),
        Event("%fusion.9 = f32[8] fusion(%all-gather.1)", 0.5, 2.0)]
        for i in range(4)}
    t = reduce_trace(devices, [], window_s=4.0)
    assert t["collective_s"] == pytest.approx(0.5)


def test_idle_time_by_what_the_host_was_doing():
    ops = [Event("a", 0.0, 1.0), Event("b", 5.0, 6.0), Event("c", 8.0, 9.0)]
    host = [Event("PjitFunction(step)", 1.5, 2.5),
            Event("np.asarray(jax.Array)", 2.0, 3.0),
            Event("PjitFunction(step)", 6.5, 7.0)]
    t = reduce_trace({"d": ops}, host, window_s=9.0)
    gaps = dict(t["idle_gaps"])
    assert gaps["PjitFunction(step)"] == pytest.approx(1.5)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(1.0)
    # 4 s + 2 s of gaps, 2.0 s of them covered by some host event.
    assert gaps[NO_HOST_EVENT] == pytest.approx(4.0)


def test_formation_bytes_by_hand():
    # 131,072 slots: 25 bytes read and 25 written back per slot, and the
    # active byte read and written again by the eviction; a bucket of
    # 1,024 lanes: 9 float32 rows in, 3 out.
    assert work.window_bytes(131_072, 1024) == (
        131_072 * (25 + 25 + 2) + 1024 * 4 * (9 + 3))
    assert work.window_bytes(131_072, 1024) == 6_864_896
    assert work.window_ops(131_072, 16) == 131_072 * 16 * 8


def test_least_time_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.least_seconds(131_072, 1024, p["hbm_bytes_per_s"],
                                  p["bf16_flops_per_s"])
    assert bound == "hbm"
    assert t == pytest.approx(6_864_896 / 819e9)


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
