"""A window's traces settled as columns (``FlightRecorder.complete_window``)
record exactly what one ``complete`` per trace records: the stage
histograms, the attribution's categories, statuses, SLO and tier counts,
and the recent and slow rings, in the same order. Also the batched
observations underneath (``Histogram.observe_many``,
``LatencyRecorder.record_many``) and one window through the served path.
"""

import asyncio
import bisect
import math
import random
import time

import numpy as np
import pytest

from matchmaking_tpu.config import Config
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.attribution import Attribution
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.utils.metrics import (
    DEFAULT_STAGE_BUCKETS,
    Histogram,
    LatencyRecorder,
    Metrics,
)
from matchmaking_tpu.utils.trace import FlightRecorder, TraceContext

REL = 1e-9

_ONE_CHUNK = ("consume", "middleware", "batch", "flush", "dispatch", "h2d",
              "device_step", "readback_seal", "collect", "encode",
              "respond")
_TWO_CHUNKS = ("consume", "middleware", "batch", "flush", "dispatch", "h2d",
               "device_step", "h2d", "device_step", "readback_seal",
               "collect", "encode", "respond")
_REDELIVERED = ("consume", "chaos_drop", "consume", "middleware", "batch",
                "flush", "dispatch", "h2d", "device_step", "readback_seal",
                "collect", "encode", "respond")
_NO_RESPOND = ("consume", "middleware", "batch", "flush", "dispatch", "h2d",
               "device_step", "readback_seal", "collect", "encode")


def _traces(seed: int, other_queue: bool = True) -> list[TraceContext]:
    """A window's worth of traces of mixed mark sequences, statuses and
    tiers, in a shuffled order; a few have a mark out of time order. With
    ``other_queue`` a few belong to a second queue."""
    rng = random.Random(seed)
    kinds = ([("q", _ONE_CHUNK)] * 60 + [("q", _TWO_CHUNKS)] * 14
             + [("q", _REDELIVERED)] * 3 + [("q", _NO_RESPOND)] * 11
             + [("q2", _ONE_CHUNK)] * (9 if other_queue else 0))
    rng.shuffle(kinds)
    out = []
    for i, (queue, names) in enumerate(kinds):
        t = 1000.0 + rng.random()
        tr = TraceContext(queue, f"c{i}", t=t)
        for name in names:
            t += rng.expovariate(1 / 0.004)
            tr.mark(name, t)
        if i % 17 == 5:
            name, t_mark = tr.marks[4]
            tr.marks[4] = (name, t_mark - 0.05)  # out of order: clipped
        tr.mark("publish", t + rng.random() * 1e-3)
        tr.status = rng.choice(["matched", "matched", "queued", "rejected",
                                ""])
        tr.tier = rng.choice([0, 1, 2, 5])
        tr.player_id = f"p{i}"
        out.append(tr)
    return out


def _recorder(tiers: int, slo_s: float) -> FlightRecorder:
    rec = FlightRecorder(Metrics(), ring=32, slow_ring=8,
                         slow_threshold_s=0.05)
    rec.attribution = Attribution(slo_target_s=slo_s, tiers=tiers)
    return rec


def _same_hist(a: Histogram, b: Histogram, where) -> None:
    assert a.counts == b.counts, where
    assert a.overflow == b.overflow, where
    assert a.count == b.count, where
    assert math.isclose(a.sum, b.sum, rel_tol=REL, abs_tol=1e-15), where


def _close(a: float, b: float) -> bool:
    return math.isclose(a, b, rel_tol=REL, abs_tol=1e-15)


@pytest.mark.parametrize("tiers", [1, 3])
@pytest.mark.parametrize("slo", [False, True], ids=["slo_off", "slo_on"])
@pytest.mark.parametrize("other_queue", [False, True],
                         ids=["one_queue", "two_queues"])
def test_window_settle_records_what_a_settle_per_trace_records(
        tiers, slo, other_queue):
    traces = _traces(seed=24 + tiers, other_queue=other_queue)
    # The SLO target is one served trace's span exactly: that trace meets it.
    served = sorted(t.total_s for t in traces if t.status == "matched")
    slo_s = served[len(served) // 2] if slo else 0.0
    one, window = _recorder(tiers, slo_s), _recorder(tiers, slo_s)
    for tr in traces:
        one.complete(tr)
    window.complete_window(traces)

    # Stage histograms.
    assert one._metrics.stages.keys() == window._metrics.stages.keys()
    for q, per_q in one._metrics.stages.items():
        assert per_q.keys() == window._metrics.stages[q].keys()
        for stage, hist in per_q.items():
            _same_hist(hist, window._metrics.stages[q][stage], (q, stage))

    # Attribution.
    qa1, qa2 = one.attribution._queues, window.attribution._queues
    assert qa1.keys() == qa2.keys()
    for q in qa1:
        a, b = qa1[q], qa2[q]
        assert a.categories.keys() == b.categories.keys()
        for name, cat in a.categories.items():
            other = b.categories[name]
            assert (cat.kind, cat.gaps, cat.traces) == \
                (other.kind, other.gaps, other.traces), (q, name)
            assert _close(cat.total_s, other.total_s), (q, name)
            _same_hist(cat.hist, other.hist, (q, name))
        assert a.spans == b.spans
        assert _close(a.work_s, b.work_s) and _close(a.wait_s, b.wait_s)
        _same_hist(a.total_hist, b.total_hist, (q, "total"))
        assert a.statuses == b.statuses
        assert (a.slo_good, a.slo_total) == (b.slo_good, b.slo_total)
        assert a.tiers.keys() == b.tiers.keys()
        for t, ts in a.tiers.items():
            other = b.tiers[t]
            assert (ts.spans, ts.statuses, ts.slo_good, ts.slo_total) == \
                (other.spans, other.statuses, other.slo_good,
                 other.slo_total), (q, t)
            assert _close(ts.work_s, other.work_s)
            assert _close(ts.wait_s, other.wait_s)
            _same_hist(ts.total_hist, other.total_hist, (q, t))
    assert one.attribution.snapshot()["queues"].keys() == \
        window.attribution.snapshot()["queues"].keys()
    if tiers > 1:
        assert len(qa1["q"].tiers) == tiers
    if slo:
        assert 0 < qa1["q"].slo_good < qa1["q"].slo_total

    # The rings: the same traces, in the same order.
    for rings in ("_recent", "_slow"):
        r1, r2 = getattr(one, rings), getattr(window, rings)
        assert r1.keys() == r2.keys()
        for q in r1:
            assert [t.trace_id for t in r1[q]] == \
                [t.trace_id for t in r2[q]], (rings, q)
    assert len(one._slow["q"]) == 8 and len(one._recent["q"]) == 32
    assert ("q2" in one._recent) == other_queue

    # The counters say which way each trace settled.
    c1, c2 = one._metrics.counters, window._metrics.counters
    assert c1.get("settled_single") == len(traces)
    assert c1.get("settled_columnar") == 0
    # The 3 redelivered traces are too few for a column of their own.
    assert c2.get("settled_single") == 3
    assert c2.get("settled_columnar") == len(traces) - 3


def test_window_settle_of_a_few_traces_goes_one_at_a_time():
    traces = _traces(seed=5)[:4]
    rec = _recorder(1, 0.0)
    rec.complete_window(traces)
    assert rec._metrics.counters.get("settled_single") == 4
    assert rec._metrics.counters.get("settled_columnar") == 0
    rec.complete_window([])
    assert rec._metrics.counters.get("settled_single") == 4


def test_window_settle_without_metrics_or_attribution():
    traces = _traces(seed=6)
    rec = FlightRecorder(None, ring=512)
    rec.complete_window(traces)
    assert sum(len(r) for r in rec._recent.values()) == len(traces)


@pytest.mark.parametrize("values", [
    [],
    [0.0],
    list(DEFAULT_STAGE_BUCKETS),
    [DEFAULT_STAGE_BUCKETS[-1] * 2, 1e9, DEFAULT_STAGE_BUCKETS[-1]],
    [np.nextafter(e, 0.0) for e in DEFAULT_STAGE_BUCKETS[:5]]
    + [np.nextafter(e, 1.0) for e in DEFAULT_STAGE_BUCKETS[:5]],
    [-1.0, 0.0, 3e-5, 1e-4, 0.3, 0.3, 7.0],
], ids=["empty", "zero", "on_edges", "overflow", "beside_edges", "mixed"])
def test_histogram_observe_many_matches_an_observe_loop(values):
    loop, many = Histogram(), Histogram()
    for v in values:
        loop.observe(float(v))
    many.observe_many(np.asarray(values, dtype=np.float64))
    _same_hist(loop, many, values)
    # Each value on an edge lands in that edge's bucket (bisect_left).
    for v in values:
        i = bisect.bisect_left(many.buckets, v)
        if 0.0 < v < many.buckets[-1] and i < len(many.buckets):
            assert many.counts[i] > 0


def test_histogram_observe_many_with_custom_buckets():
    loop, many = Histogram((0.5, 0.1, 1.0)), Histogram((0.5, 0.1, 1.0))
    values = np.array([0.1, 0.1, 0.2, 0.5, 0.9, 1.0, 1.5])
    for v in values.tolist():
        loop.observe(v)
    many.observe_many(values)
    many.observe_many(values[:0])
    _same_hist(loop, many, "custom")
    assert many.counts == [2, 2, 2] and many.overflow == 1


def test_latency_recorder_record_many_matches_a_record_loop():
    loop, many = LatencyRecorder(window=5), LatencyRecorder(window=5)
    values = np.array([0.3, 0.1, 0.9, 0.2, 0.4, 0.05, 0.6, 0.7])
    for v in values.tolist():
        loop.record(v)
    many.record_many(values[:3])
    many.record_many(values[3:])
    many.record_many(values[:0])
    assert len(many) == len(loop) == 8
    assert list(many._samples) == list(loop._samples) == values[3:].tolist()
    assert many._max == loop._max == 0.9
    assert many.summary_ms() == loop.summary_ms()


QUEUE = "mm.settle"
REPLIES = "mm.settle.replies"


async def _one_window(n: int):
    """Warm the served path, then publish ``n`` searchers at once into a
    window of exactly ``n`` (the batcher's max). Returns the app's counters
    and attribution before and after that window, and its traces."""
    app = MatchmakingApp(Config.from_dict({
        "queues": [{"name": QUEUE, "rating_threshold": 100.0}],
        "engine": {"backend": "tpu", "pool_capacity": 1024,
                   "pool_block": 256, "batch_buckets": [16, n]},
        "batcher": {"max_batch": n, "max_wait_ms": 2000.0},
    }))
    await app.start()
    answered: set[str] = set()
    try:
        rt = app.runtime(QUEUE)
        assert rt._pipelined and rt._columnar
        app.broker.declare_queue(REPLIES)

        async def on_reply(delivery):
            answered.add(delivery.properties.correlation_id)
            app.broker.ack(tag, delivery.delivery_tag)

        tag = app.broker.basic_consume(REPLIES, on_reply, prefetch=1 << 20)

        async def burst(prefix: str, rating: float) -> list[str]:
            ids = [f"{prefix}{i}" for i in range(n)]
            for i, pid in enumerate(ids):
                app.broker.publish(
                    QUEUE,
                    f'{{"id":"{pid}","rating":{rating + i // 2:.1f}}}'
                    .encode(),
                    Properties(reply_to=REPLIES, correlation_id=pid))
            deadline = time.perf_counter() + 120.0
            while not set(ids) <= answered:
                assert time.perf_counter() < deadline, "no replies"
                await asyncio.sleep(0.005)
            return ids

        def state():
            c = app.metrics.counters
            cats = app.attribution.snapshot(QUEUE)["queues"][QUEUE][
                "categories"]
            return (c.get("settled_columnar"), c.get("settled_single"),
                    c.get("windows"), cats["batcher_hold"]["traces"])

        await burst("w", 1500.0)
        before = state()
        ids = await burst("p", 2500.0)
        await asyncio.sleep(0.05)
        after = state()
        traces = [t for t in app.recorder._recent[QUEUE]
                  if t.correlation_id in set(ids)]
    finally:
        await app.stop()
    return before, after, traces


def test_a_served_window_settles_one_trace_per_delivery_as_columns():
    n = 64
    before, after, traces = asyncio.run(_one_window(n))
    columnar, single, windows, held = (b - a for a, b in zip(before, after))
    assert windows == 1
    assert columnar == n and single == 0
    assert held == n
    assert len(traces) == n
    assert len({t.trace_id for t in traces}) == n
    for tr in traces:
        names = [name for name, _ in tr.marks]
        assert names.count("publish") == 1 and names[-1] == "publish"
        assert tr.status in ("matched", "queued")
    assert sum(t.status == "matched" for t in traces) == n
