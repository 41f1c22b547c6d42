"""The one drain (testing/drain.py) against a late egress.

A window collected by the collector task (the usual path on a chip) counts
its matches, awaits its journal commit in a worker thread, and only then
publishes its replies and acks its deliveries. During that await nothing
is left in the broker, the batcher or the device, so a drain that watches
only those reads "settled" with the replies unsent. Here every window
lands only after its flush has looked, and the commit is slow, so the race
shows on every run instead of only on a loaded host.
"""

import asyncio
import json
import threading
import time

from matchmaking_tpu.config import (BatcherConfig, Config, DurabilityConfig,
                                    EngineConfig, QueueConfig)
from matchmaking_tpu.engine.tpu import TpuEngine
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.service.loadgen import offered_load
from matchmaking_tpu.testing.drain import fully_drained, settle
from matchmaking_tpu.utils import journal as jr

Q = "matchmaking.search"


def _late_egress_app(monkeypatch, jdir, threshold, **batcher):
    """An app whose windows land on the second look (the dispatching
    flush looks once) and whose off-loop journal commits sleep 0.5 s."""
    ready, commit = TpuEngine._is_ready, jr.PoolJournal.commit
    looked: set[int] = set()

    def is_ready(self, pending):
        first = pending.token not in looked
        looked.add(pending.token)
        return not first and ready(self, pending)

    def slow_commit(self, *a, **kw):
        if threading.current_thread() is not threading.main_thread():
            time.sleep(0.5)
        return commit(self, *a, **kw)

    monkeypatch.setattr(TpuEngine, "_is_ready", is_ready)
    monkeypatch.setattr(jr.PoolJournal, "commit", slow_commit)
    return MatchmakingApp(Config(
        queues=(QueueConfig(rating_threshold=threshold),),
        engine=EngineConfig(backend="tpu", pool_capacity=256, pool_block=64,
                            batch_buckets=(8, 64), top_k=4),
        batcher=BatcherConfig(**batcher),
        durability=DurabilityConfig(journal_dir=str(jdir), fsync="window")))


async def test_settle_waits_for_replies_behind_a_late_egress(tmp_path,
                                                             monkeypatch):
    app = _late_egress_app(monkeypatch, tmp_path / "j", 50.0,
                           max_batch=8, max_wait_ms=5.0)
    await app.start()
    rt = app.runtime(Q)
    reply_q, replies = "drain.replies", []

    async def on_reply(delivery):
        replies.append(json.loads(delivery.body))

    app.broker.declare_queue(reply_q)
    app.broker.basic_consume(reply_q, on_reply, prefetch=1_000_000)

    def old_conjunction() -> bool:
        # What every hand-copied drain checked: fully_drained without its
        # unsettled-delivery clause.
        with monkeypatch.context() as m:
            m.setattr(app.broker, "unsettled", lambda queue: 0)
            return fully_drained(app, rt, Q, 4)

    async def old_settles():
        while not old_conjunction():
            await asyncio.sleep(0.005)

    try:
        for pid, rating in (("p0", 1500.0), ("p1", 1501.0),
                            ("p2", 2000.0), ("p3", 2001.0),
                            ("s0", 4000.0)):
            app.broker.publish(
                Q, json.dumps({"id": pid, "rating": rating}).encode(),
                Properties(reply_to=reply_q, correlation_id=pid))
        await asyncio.wait_for(old_settles(), 30.0)
        assert app.broker.unsettled(Q) > 0
        assert not [r for r in replies if r.get("status") == "matched"]
        assert not fully_drained(app, rt, Q, 4, reply_queues=(reply_q,))

        assert await settle(app, rt, Q, matched_at_least=4,
                            reply_queues=(reply_q,))
        assert {r["player_id"] for r in replies
                if r.get("status") == "matched"} == {"p0", "p1", "p2", "p3"}
        assert app.broker.unsettled(Q) == 0
    finally:
        await app.stop()


async def test_offered_load_counts_every_reply_behind_a_late_egress(
        tmp_path, monkeypatch):
    # Nothing matches (the threshold is far below the seeded ratings'
    # spacing), so each request gets exactly one reply: its queued ack,
    # which rides its window's publish behind the slow commit.
    app = _late_egress_app(monkeypatch, tmp_path / "j", 1e-3,
                           max_batch=64, max_wait_ms=200.0)
    await app.start()
    try:
        r = await offered_load(app, Q, rate=100.0, duration=0.5, seed=1,
                               rating_sigma=300.0)
    finally:
        await app.stop()
    assert r["sent"] > 0 and r["players_matched"] == 0
    assert r["replies"] == r["sent"]
