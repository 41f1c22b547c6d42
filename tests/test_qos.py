"""Tiered-QoS suite (`qos` marker — ISSUE 7): priority classes, EDF window
cutting, pool-resident deadline expiry.

The acceptance soak is deterministic BY CONSTRUCTION, the same way the
ISSUE 5 overload soak is: the burst is published before the app starts,
every request's tier is a fixed function of its index (stamped ``x-tier``
header), chaos faults are scripted per publish seq, and admission/eviction
decisions are pure functions of per-tier counts at the decision point — so
the admit/shed/expire transcript of two runs compares equal byte for byte.
"""

import asyncio
import json
import time

import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    ChaosConfig,
    Config,
    EngineConfig,
    ObservabilityConfig,
    OverloadConfig,
    QueueConfig,
)
from matchmaking_tpu.service.app import MatchmakingApp, _QueueRuntime
from matchmaking_tpu.service.batcher import Batcher
from matchmaking_tpu.service.broker import Delivery, Properties
from matchmaking_tpu.service.overload import (
    ADMIT,
    SHED,
    AdmissionController,
    stamp_deadline,
    stamp_tier,
    tier_of,
)
from matchmaking_tpu.testing.drain import settle

pytestmark = [pytest.mark.qos, pytest.mark.overload]


async def _drain_replies(app, reply: str) -> list[dict]:
    out = []
    while True:
        d = await app.broker.get(reply, timeout=0.05)
        if d is None:
            return out
        out.append(json.loads(d.body))


# ---- tier header parsing ----------------------------------------------------

def test_tier_header_roundtrip_and_clamping():
    headers: dict = {}
    stamp_tier(headers, 2)
    assert tier_of(headers, default=0, n_tiers=3) == 2
    # First stamp wins (a redelivery must not change class).
    stamp_tier(headers, 0)
    assert tier_of(headers, default=0, n_tiers=3) == 2
    # Out-of-range clamps into the ladder; garbage reads as the default.
    assert tier_of({"x-tier": "99"}, default=0, n_tiers=3) == 2
    assert tier_of({"x-tier": "-4"}, default=1, n_tiers=3) == 0
    assert tier_of({"x-tier": "junk"}, default=1, n_tiers=3) == 1
    assert tier_of({}, default=2, n_tiers=3) == 2
    assert tier_of({}, default=9, n_tiers=3) == 2


# ---- admission partitions (pure controller) ---------------------------------

class _FakeDelivery:
    def __init__(self, tag=1, headers=None, tier=None):
        class P:
            pass

        self.delivery_tag = tag
        self.tier = 0
        self.properties = P()
        self.properties.headers = headers if headers is not None else {}
        if tier is not None:
            self.properties.headers["x-tier"] = str(tier)


def test_tier0_never_shed_while_lower_tier_credits_remain():
    """Regression (ISSUE 7 satellite): the inflight partition counts only
    SAME-OR-HIGHER-priority credits against a tier, so tier-0 is never
    shed while tier-2 credits remain — tier-0 sheds only once its OWN
    usage fills the whole cap."""
    cfg = OverloadConfig(max_inflight=10, tiers=3)
    ac = AdmissionController(cfg, "q")
    # Fill tier-2's slice (10 * 1/3 -> 3): the 4th tier-2 sheds.
    for tag in range(3):
        assert ac.decide(_FakeDelivery(tag, tier=2), 0.0, 0) == ADMIT
        ac.admit(tag, 2)
    assert ac.decide(_FakeDelivery(90, tier=2), 0.0, 0) == SHED
    # Tier-1's slice (10 * 2/3 -> 6) counts tier-1 credits only (the
    # tier-2 holdings are LOWER priority): 6 admit, the 7th sheds.
    for tag in range(10, 16):
        assert ac.decide(_FakeDelivery(tag, tier=1), 0.0, 0) == ADMIT
        ac.admit(tag, 1)
    assert ac.decide(_FakeDelivery(91, tier=1), 0.0, 0) == SHED
    # Tier-0 ignores every lower-tier holding: it admits until ITS prefix
    # (tier-0 alone) reaches the full cap — never shed while tier-2
    # credits remain un-drained.
    for tag in range(20, 30):
        assert ac.decide(_FakeDelivery(tag, tier=0), 0.0, 0) == ADMIT
        ac.admit(tag, 0)
    assert ac.snapshot()["tiers"]["2"]["held"] == 3  # still held
    assert ac.decide(_FakeDelivery(92, tier=0), 0.0, 0) == SHED
    assert ac.shed_by_tier[0] == 0  # record_shed was never called for t0


def test_tiered_waiting_partition_and_oldest_preemption():
    """max_waiting partitions: a tier's slice counts same-or-higher-
    priority pool occupancy; under shed_policy="oldest" an over-cap
    arrival admits ONLY when a same-or-lower-priority victim exists."""
    cfg = OverloadConfig(max_waiting=12, tiers=3, shed_policy="oldest")
    ac = AdmissionController(cfg, "q")
    # Pool full of tier-0/tier-1: a tier-2 arrival has no victim -> SHED.
    assert ac.decide(_FakeDelivery(1, tier=2), 0.0, 12,
                     pool_tiers=[8, 4, 0]) == SHED
    # A tier-2 victim exists -> ADMIT (evicts lowest tier at the flush).
    assert ac.decide(_FakeDelivery(2, tier=2), 0.0, 12,
                     pool_tiers=[8, 3, 1]) == ADMIT
    # Tier-0 over the global cap with ANY pool occupancy admits (evicts
    # the lowest-priority waiter).
    assert ac.decide(_FakeDelivery(3, tier=0), 0.0, 12,
                     pool_tiers=[4, 4, 4]) == ADMIT
    # Under "reject" there is no preemption, but the ladder still holds:
    # tier-0 counts only its OWN occupancy against the full cap (lower
    # tiers can never crowd it out — bounded transient overshoot is the
    # documented trade), so it sheds only once tier-0 usage fills the cap.
    cfg2 = OverloadConfig(max_waiting=12, tiers=3, shed_policy="reject")
    ac2 = AdmissionController(cfg2, "q")
    assert ac2.decide(_FakeDelivery(4, tier=0), 0.0, 12,
                      pool_tiers=[4, 4, 4]) == ADMIT
    assert ac2.decide(_FakeDelivery(5, tier=0), 0.0, 12,
                      pool_tiers=[12, 0, 0]) == SHED
    # A lower tier under "reject" sheds at its slice with no victim check.
    assert ac2.decide(_FakeDelivery(6, tier=2), 0.0, 12,
                      pool_tiers=[4, 0, 0]) == SHED


def test_untiered_controller_behavior_unchanged():
    """tiers=1 keeps the exact pre-tier semantics (the overload suite
    pins the full behavior; this pins the partition arithmetic edge)."""
    cfg = OverloadConfig(max_inflight=2, max_waiting=3)
    ac = AdmissionController(cfg, "q")
    assert ac.tiers == 1
    assert ac.decide(_FakeDelivery(1), 100.0, 0) == ADMIT
    ac.admit(1)
    ac.admit(1)  # idempotent: double-admit must not double-count
    assert ac.inflight() == 1
    assert ac.decide(_FakeDelivery(2), 100.0, 2) == SHED  # pool+credits
    ac.release(1)
    ac.release(1)  # idempotent release
    assert ac.inflight() == 0


# ---- EDF window cutting -----------------------------------------------------

def _delivery(tag: int, tier: int, deadline: float | None) -> Delivery:
    headers: dict = {"x-tier": str(tier)}
    if deadline is not None:
        headers["x-deadline"] = repr(deadline)
    d = Delivery(body=b"{}", properties=Properties(headers=headers),
                 queue="q", delivery_tag=tag)
    d.tier = tier
    return d


async def _edf_property_run(seed: int) -> None:
    import random

    rng = random.Random(seed)
    items = []
    for i in range(30):
        tier = rng.randrange(3)
        deadline = (None if rng.random() < 0.2
                    else 100.0 + rng.random() * 50.0)
        items.append((None, _delivery(i, tier, deadline)))
    windows: list[list] = []

    async def flush(window):
        windows.append(window)

    b = Batcher(BatcherConfig(max_batch=8, max_wait_ms=1.0), flush,
                sort_key=_QueueRuntime._edf_key)
    # All submissions land before the batcher task runs a single cut (no
    # awaits between submits), so every cut slices the globally-best
    # prefix of what remains.
    for it in items:
        b.submit(it)
    await b.close()

    flat = [d for w in windows for _, d in w]
    assert len(flat) == len(items)
    keys = [_QueueRuntime._edf_key((None, d)) for d in flat]
    # THE property: no window ever contains a later-deadline request
    # while an earlier-deadline admitted request waits — i.e. the cut
    # sequence is globally (tier, deadline)-sorted...
    assert keys == sorted(keys)
    # ...and stable: equal keys keep arrival (delivery_tag) order.
    for a, b2 in zip(flat, flat[1:]):
        ka, kb = (_QueueRuntime._edf_key((None, a)),
                  _QueueRuntime._edf_key((None, b2)))
        if ka == kb:
            assert a.delivery_tag < b2.delivery_tag


def test_edf_window_cut_property(sanitizer):
    for seed in (1, 7, 23):
        asyncio.run(_edf_property_run(seed))


def test_edf_key_orders_tier_before_deadline():
    k0 = _QueueRuntime._edf_key((None, _delivery(1, 0, None)))
    k1 = _QueueRuntime._edf_key((None, _delivery(2, 1, 100.0)))
    k2 = _QueueRuntime._edf_key((None, _delivery(3, 1, 200.0)))
    assert k0 < k1 < k2  # tier dominates; no-deadline sorts last in tier


# ---- the acceptance soak ----------------------------------------------------

_W = 64     # occupancy cap
_OVER = 4   # offered multiple

#: Fixed 20/50/30 tier pattern by request index: pure function of i, so
#: both runs offer the identical per-class load.
_TIER_PATTERN = (0, 0, 1, 1, 1, 1, 1, 2, 2, 2)


def _tier_for(i: int) -> int:
    return _TIER_PATTERN[i % 10]


def _qos_soak_cfg() -> tuple[QueueConfig, Config]:
    q = QueueConfig(name="mm.qos", rating_threshold=50.0,
                    send_queued_ack=True)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="cpu", pool_capacity=1024),
        batcher=BatcherConfig(max_batch=32, max_wait_ms=2.0),
        overload=OverloadConfig(max_waiting=_W, tiers=3,
                                shed_policy="oldest", edf=True,
                                retry_after_ms=250.0),
        # Scripted chaos: a first-attempt drop inside the burst and a
        # redelivery storm — the tiered transcript must still replay.
        chaos=ChaosConfig(seed=99, queues=(q.name,), drop_seqs=(3,),
                          dup_seqs=((100, 1),)),
        observability=ObservabilityConfig(trace_ring=2048,
                                          slo_target_ms=2000.0,
                                          snapshot_interval_s=0.0),
        debug_invariants=True,
    )
    return q, cfg


async def _qos_soak_run() -> dict:
    """One 4x-capacity tiered burst (20/50/30). Returns the transcript of
    every deterministic accounting fact."""
    q, cfg = _qos_soak_cfg()
    app = MatchmakingApp(cfg)
    reply = "qos.replies"
    app.broker.declare_queue(q.name)
    app.broker.declare_queue(reply)
    n = _OVER * _W
    # Unmatchable by construction (unique ratings, gap 300 >> threshold
    # 50): the pool only grows, so the admit/shed boundary cannot depend
    # on event-loop interleaving.
    for i in range(n):
        headers: dict = {}
        stamp_tier(headers, _tier_for(i))
        app.broker.publish(
            q.name, f'{{"id":"p{i}","rating":{1000 + i * 300}}}'.encode(),
            Properties(reply_to=reply, correlation_id=f"c{i}",
                       headers=headers))
    await app.start()
    rt = app.runtime(q.name)
    try:
        await settle(app, rt, q.name,
                     until=lambda: rt.engine.pool_size() >= _W)
        replies = await _drain_replies(app, reply)
        ac = rt.admission
        assert ac is not None
        shed_replies = [r for r in replies if r["status"] == "shed"]
        # Shed responses are honest AND classed: retry hint + tier.
        assert shed_replies
        assert all(r["retry_after_ms"] == 250.0 for r in shed_replies)
        assert all("tier" in r for r in shed_replies)
        # The respond mark landed on settled queued-ack traces (the
        # publish_lag/respond split — PR 6 carry-over).
        snap = app.recorder.snapshot(queue=q.name, limit=2048)
        queued_traces = [t for t in snap["queues"][q.name]["recent"]
                        if t["status"] == "queued"]
        assert queued_traces
        assert any("respond" in [m[0] for m in t["marks"]]
                   for t in queued_traces)
        transcript = {
            "statuses": sorted(r["status"] for r in replies),
            "n_replies": len(replies),
            "pool_end": rt.engine.pool_size(),
            "pool_tiers": rt.engine.pool_tier_counts(3),
            "waiting": sorted(r.id for r in rt.engine.waiting()),
            "shed_by_tier": list(ac.shed_by_tier),
            "expired_by_tier": list(ac.expired_by_tier),
            "shed_counter": int(app.metrics.counters.get("shed_requests")),
            "shed_t0": int(app.metrics.counters.get("shed_requests_t0")),
            "shed_t1": int(app.metrics.counters.get("shed_requests_t1")),
            "shed_t2": int(app.metrics.counters.get("shed_requests_t2")),
            "shed_names": sorted(r["player_id"] for r in shed_replies
                                 if r["player_id"]),
            "acked": app.broker.stats["acked"],
            "dead_lettered": app.broker.stats["dead_lettered"],
            "dropped": app.broker.stats["dropped"],
            "duplicated": app.broker.stats["duplicated"],
        }
        # Per-tier SLO attainment (attribution split): tier 0 holds.
        app.sample_telemetry()
        attr = app.attribution.snapshot()["queues"][q.name]
        transcript["t0_slo"] = (attr["tiers"]["0"]["slo_good"],
                                attr["tiers"]["0"]["slo_total"])
        transcript["t0_statuses"] = attr["tiers"]["0"]["statuses"]
        return transcript
    finally:
        await app.stop()


def test_qos_soak_4x_tier0_holds_tier2_absorbs(sanitizer):
    """THE ISSUE 7 acceptance: 4x offered load with a 20/50/30 tier mix —
    tier-0 sheds ZERO requests and holds its SLO while the lower tiers
    absorb all shedding, and the admit/shed/expire transcript replays
    bit-identically across two runs."""
    first = asyncio.run(_qos_soak_run())
    second = asyncio.run(_qos_soak_run())
    assert first == second  # bit-identical tiered accounting

    n = _OVER * _W
    n_t0 = sum(1 for i in range(n) if _tier_for(i) == 0)
    # Tier-0: fully admitted, never shed, all still waiting (unmatchable).
    assert first["shed_by_tier"][0] == 0
    assert first["shed_t0"] == 0
    assert first["pool_tiers"][0] == n_t0
    assert not any(name for name in first["shed_names"]
                   if _tier_for(int(name[1:])) == 0)
    # Tier-0 SLO: every tier-0 request reached a served outcome within
    # the target (attainment 1.0 on the per-tier split).
    good, total = first["t0_slo"]
    assert total >= n_t0 and good == total
    assert set(first["t0_statuses"]) == {"queued"}
    # The pool ends at the cap and the shed volume is the overflow: the
    # lower tiers absorbed every shed.
    assert first["pool_end"] == _W
    assert first["shed_counter"] == (
        first["shed_by_tier"][1] + first["shed_by_tier"][2])
    assert first["shed_by_tier"][2] > first["shed_by_tier"][1] // 2
    # Ordered degradation: the surviving non-tier-0 slots are held by the
    # HIGHEST-priority remainder — no tier-2 waiter outranks a shed
    # tier-1 (eviction consumed tier-2 first).
    assert first["pool_tiers"][2] == 0 or first["shed_by_tier"][1] == 0
    assert first["dead_lettered"] == 0
    assert first["dropped"] == 1 and first["duplicated"] == 1


# ---- priority-aware eviction ------------------------------------------------

def test_oldest_eviction_takes_lowest_tier_first(sanitizer):
    """shed_policy="oldest" under tiers: a tier-0 arrival over the cap
    evicts the OLDEST LOWEST-TIER pool player — by name — never a
    higher-priority one."""
    async def run():
        q = QueueConfig(name="mm.evict", rating_threshold=50.0,
                        send_queued_ack=True)
        cfg = Config(
            queues=(q,),
            engine=EngineConfig(backend="cpu"),
            batcher=BatcherConfig(max_batch=8, max_wait_ms=2.0),
            # tier_shares sized so BOTH tier-2 waiters fit their slice
            # (default ladder would cap tiers<=2 occupancy at 4/3 -> 1).
            overload=OverloadConfig(max_waiting=4, tiers=3,
                                    tier_shares=(1.0, 0.75, 0.5),
                                    shed_policy="oldest",
                                    retry_after_ms=500.0),
            debug_invariants=True,
        )
        app = MatchmakingApp(cfg)
        reply = "evict.replies"
        app.broker.declare_queue(q.name)
        app.broker.declare_queue(reply)
        await app.start()
        rt = app.runtime(q.name)
        try:
            # Fill the pool: oldest-first publish order o0(t2) o1(t2)
            # o2(t1) o3(t0) — unmatchable ratings.
            tiers = (2, 2, 1, 0)
            for i, t in enumerate(tiers):
                headers: dict = {}
                stamp_tier(headers, t)
                app.broker.publish(
                    q.name,
                    f'{{"id":"o{i}","rating":{1000 + i * 300}}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"c{i}",
                               headers=headers))
            for _ in range(200):
                await asyncio.sleep(0.05)
                if rt.engine.pool_size() == 4:
                    break
            assert rt.engine.pool_size() == 4
            # Two tier-0 arrivals over the cap: each evicts the oldest
            # LOWEST-tier waiter (o0 then o1 — both tier-2), never o3.
            for i in (4, 5):
                headers = {}
                stamp_tier(headers, 0)
                app.broker.publish(
                    q.name,
                    f'{{"id":"o{i}","rating":{1000 + i * 300}}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"c{i}",
                               headers=headers))
            for _ in range(200):
                await asyncio.sleep(0.05)
                if app.metrics.counters.get("shed_requests") >= 2:
                    break
            replies = await _drain_replies(app, reply)
            shed = [r for r in replies if r["status"] == "shed"]
            assert sorted(r["player_id"] for r in shed) == ["o0", "o1"]
            assert all(r["tier"] == 2 for r in shed)
            waiting = sorted(r.id for r in rt.engine.waiting())
            assert waiting == ["o2", "o3", "o4", "o5"]
            assert rt.engine.pool_tier_counts(3) == [3, 1, 0]
        finally:
            await app.stop()

    asyncio.run(run())


# ---- pool-resident deadline expiry ------------------------------------------

def test_pool_deadline_sweep_cancels_exactly(sanitizer):
    """Acceptance: pool WAITERS whose ``x-deadline`` passes are cancelled
    by the per-slot sweep — explicit timeout response honoring the exact
    deadline (not ``request_timeout_s`` granularity: it is unset), an
    ``expired`` trace with NO dispatch mark, zero matching work — while
    deadline-less waiters stay untouched."""
    async def run():
        q = QueueConfig(name="mm.sweep", rating_threshold=50.0,
                        send_queued_ack=False, request_timeout_s=None)
        cfg = Config(
            queues=(q,),
            engine=EngineConfig(backend="tpu", pool_capacity=64,
                                pool_block=32, batch_buckets=(16,),
                                pipeline_depth=2),
            batcher=BatcherConfig(max_batch=16, max_wait_ms=2.0),
            overload=OverloadConfig(max_inflight=100,
                                    deadline_sweep_ms=20.0),
        )
        app = MatchmakingApp(cfg)
        reply = "sweep.replies"
        app.broker.declare_queue(q.name)
        app.broker.declare_queue(reply)
        await app.start()
        rt = app.runtime(q.name)
        try:
            t_pub = time.time()
            budget = 0.3
            for i in range(3):  # deadline-stamped, unmatchable ratings
                headers: dict = {}
                stamp_deadline(headers, t_pub, budget)
                app.broker.publish(
                    q.name,
                    f'{{"id":"d{i}","rating":{1000 + i * 300}}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"c{i}",
                               headers=headers))
            for i in range(3, 5):  # no deadline: must keep waiting
                app.broker.publish(
                    q.name,
                    f'{{"id":"d{i}","rating":{1000 + i * 300}}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"c{i}"))
            for _ in range(200):
                await asyncio.sleep(0.05)
                if rt.engine.pool_size() == 5:
                    break
            assert rt.engine.pool_size() == 5
            # The mirror's deadline column is populated per slot.
            pool = rt.engine.pool
            slots = pool.waiting_slots()
            stamped = pool.m_deadline[slots]
            assert (stamped > 0).sum() == 3
            assert ((stamped > 0) & (abs(stamped - (t_pub + budget)) < 1.0)).sum() == 3
            # Wait for the sweep (20 ms cadence) to fire at the deadline.
            for _ in range(200):
                await asyncio.sleep(0.05)
                if app.metrics.counters.get("expired_requests") >= 3:
                    break
            assert app.metrics.counters.get("expired_requests") == 3
            assert rt.engine.pool_size() == 2  # deadline-less players stay
            assert sorted(r.id for r in rt.engine.waiting()) == ["d3", "d4"]
            replies = await _drain_replies(app, reply)
            timeouts = [r for r in replies if r["status"] == "timeout"]
            assert sorted(r["player_id"] for r in timeouts) == [
                "d0", "d1", "d2"]
            for r in timeouts:
                # Exact to the deadline: the cancel happened AFTER the
                # stamped budget elapsed (never early), and the sweep —
                # not the coarse timeout sweeper — did it
                # (request_timeout_s is None).
                assert r["latency_ms"] >= budget * 1e3 - 1.0
                tr = app.recorder.get(r["trace_id"])
                assert tr is not None and tr.status == "expired"
                names = [name for name, _ in tr.marks]
                assert "expired" in names
                assert "dispatch" not in names  # no device matching work
            # Every pool expiry is on the event timeline.
            expired_events = [e for e in app.events.snapshot()
                              if e["kind"] == "expired"]
            assert len(expired_events) == 3
        finally:
            await app.stop()

    asyncio.run(run())


# ---- tier-aware rescan selection --------------------------------------------

def test_rescan_selects_lowest_tier_deadline_first():
    """ISSUE 9 satellite (PR 7 follow-up): when a rescan tick cannot cover
    the whole pool, it picks the lowest-(tier, deadline) slots first — the
    EDF sort key over the QoS mirror columns — instead of oldest-first; an
    untiered deadline-less pool keeps the old oldest-first order."""
    import numpy as np

    from matchmaking_tpu.config import EngineConfig
    from matchmaking_tpu.engine.interface import make_engine
    from matchmaking_tpu.service.contract import RequestColumns

    def cols(ids, tiers, deadlines, enqueued):
        n = len(ids)
        return RequestColumns(
            ids=np.asarray(ids, object),
            rating=np.asarray([1000.0 + 300.0 * i for i in range(n)],
                              np.float32),  # unmatchable: gaps >> threshold
            rd=np.zeros(n, np.float32),
            region=np.zeros(n, np.int32),
            mode=np.zeros(n, np.int32),
            threshold=np.full(n, 10.0, np.float32),
            enqueued_at=np.asarray(enqueued, np.float64),
            reply_to=np.asarray([""] * n, object),
            correlation_id=np.asarray([""] * n, object),
            tier=np.asarray(tiers, np.int32),
            deadline=np.asarray(deadlines, np.float64),
        )

    cfg = Config(engine=EngineConfig(backend="tpu", pool_capacity=64,
                                     pool_block=32, batch_buckets=(16,)))
    q = QueueConfig(name="mm.resel", rating_threshold=10.0)
    engine = make_engine(cfg, q)
    try:
        now = 1000.0
        # Arrival order p0..p3 (p0 oldest): oldest-first would pick p0,p1.
        engine.search_columns_async(
            cols(["p0", "p1", "p2", "p3"],
                 tiers=[2, 1, 0, 1],
                 deadlines=[0.0, now + 50.0, 0.0, now + 20.0],
                 enqueued=[now, now + 1, now + 2, now + 3]), now)
        engine.flush()
        assert engine.pool_size() == 4
        tok = engine.rescan_async(2, now + 5)
        assert tok is not None
        pending = engine._pending[-1]
        chosen = sorted(pending.chunks[0][0][0].ids.tolist())
        # Lowest (tier, deadline) first: p2 (tier 0), then p3 (tier 1,
        # earlier deadline than p1). Never p0 (tier 2) despite being
        # oldest.
        assert chosen == ["p2", "p3"]
        engine.flush()
    finally:
        engine.close()

    # Untiered pin: zero tiers + zero deadlines reduce to oldest-first
    # (fresh engine — the tiered pool above must not interfere).
    engine = make_engine(cfg, q)
    try:
        now = 1000.0
        engine.search_columns_async(
            cols(["o0", "o1", "o2"], tiers=[0, 0, 0],
                 deadlines=[0.0, 0.0, 0.0],
                 enqueued=[now + 2, now, now + 1]), now)
        engine.flush()
        assert engine.pool_size() == 3
        tok = engine.rescan_async(2, now + 5)
        assert tok is not None
        pending = engine._pending[-1]
        chosen = sorted(pending.chunks[0][0][0].ids.tolist())
        assert chosen == ["o1", "o2"]  # the two oldest
        engine.flush()
    finally:
        engine.close()


# ---- loadgen tier mix -------------------------------------------------------

def test_loadgen_tier_mix_accounting(sanitizer):
    """--tier-mix: seeded per-class offered load with per-tier response
    accounting (offered sums to sent; statuses split per tier)."""
    from matchmaking_tpu.service.loadgen import offered_load, parse_tier_mix

    mix = parse_tier_mix("0:0.2,1:0.5,2:0.3")
    assert mix is not None and abs(sum(mix.values()) - 1.0) < 1e-9

    async def run():
        q = QueueConfig(name="mm.lg", rating_threshold=100.0,
                        send_queued_ack=True)
        cfg = Config(
            queues=(q,),
            engine=EngineConfig(backend="cpu"),
            batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
            overload=OverloadConfig(tiers=3, edf=True),
        )
        app = MatchmakingApp(cfg)
        await app.start()
        try:
            result = await offered_load(app, q.name, rate=400.0,
                                        duration=0.5, seed=11,
                                        tier_mix=mix)
            assert "tiers" in result
            rows = result["tiers"]
            assert set(rows) == {"0", "1", "2"}
            assert sum(r["offered"] for r in rows.values()) == result["sent"]
            # Near-equal consecutive ratings pair off: matches happened
            # and were attributed to tiers.
            assert sum(r["matched"] for r in rows.values()) == (
                result["players_matched"])
            for r in rows.values():
                assert r["shed_requests"] == 0
        finally:
            await app.stop()

    asyncio.run(run())


# ---- attribution: respond split + rescan bucket -----------------------------

def test_respond_mark_splits_publish_lag():
    from matchmaking_tpu.service.attribution import (
        WAIT,
        WORK,
        classify,
        decompose_marks,
    )

    assert classify("collect", "respond") == ("publish_lag", WAIT)
    assert classify("respond", "publish") == ("respond", WORK)
    # Traces WITHOUT the mark keep the lumped pre-split semantics.
    assert classify("collect", "publish") == ("publish_lag", WAIT)
    # Telescoping identity holds across the new mark.
    marks = [("enqueue", 0.0), ("consume", 0.01), ("batch", 0.02),
             ("flush", 0.03), ("dispatch", 0.04), ("collect", 0.06),
             ("respond", 0.08), ("publish", 0.085)]
    gaps, work_s, wait_s = decompose_marks(marks)
    assert abs((work_s + wait_s) - 0.085) < 1e-12
    respond_gaps = [g for g in gaps if g["category"] == "respond"]
    assert len(respond_gaps) == 1 and respond_gaps[0]["kind"] == WORK


def test_rescan_attribution_bucket():
    from matchmaking_tpu.service.attribution import Attribution

    a = Attribution()
    a.observe_rescan("q", [("dispatch", 10.0), ("h2d", 10.002),
                           ("device_step", 10.005), ("collect", 10.010)])
    a.observe_rescan("q", [("dispatch", 11.0), ("device_step", 11.004),
                           ("collect", 11.006)])
    snap = a.snapshot()["queues"]["q"]
    assert snap["rescan"]["windows"] == 2
    assert abs(snap["rescan"]["total_s"] - 0.016) < 1e-9
    assert abs(snap["rescan"]["device_step_s"] - 0.007) < 1e-9
    # Rescan time stays OUT of the trace work/wait sums (telescoping).
    assert snap["work_s"] == 0.0 and snap["wait_s"] == 0.0


def test_rescan_windows_feed_attribution_bucket(sanitizer):
    """End to end: an overlapped device rescan tick's window marks land in
    the per-queue rescan bucket instead of vanishing."""
    async def run():
        q = QueueConfig(name="mm.rescan", rating_threshold=10.0,
                        widen_per_sec=200.0, max_threshold=2000.0,
                        rescan_interval_s=0.05, send_queued_ack=False)
        cfg = Config(
            queues=(q,),
            engine=EngineConfig(backend="tpu", pool_capacity=64,
                                pool_block=32, batch_buckets=(16,),
                                pipeline_depth=2),
            batcher=BatcherConfig(max_batch=16, max_wait_ms=2.0),
        )
        app = MatchmakingApp(cfg)
        reply = "rescan.replies"
        app.broker.declare_queue(q.name)
        app.broker.declare_queue(reply)
        await app.start()
        rt = app.runtime(q.name)
        try:
            # Two players too far apart to match now; widening (200/s on a
            # 380 gap) resolves within ~2 s via the rescan tick.
            for i, rating in enumerate((1000, 1380)):
                app.broker.publish(
                    q.name, f'{{"id":"r{i}","rating":{rating}}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"c{i}"))
            for _ in range(400):
                await asyncio.sleep(0.05)
                snap = app.attribution.snapshot()["queues"].get(q.name, {})
                if snap.get("rescan", {}).get("windows", 0) > 0:
                    break
            snap = app.attribution.snapshot()["queues"][q.name]
            assert snap["rescan"]["windows"] > 0
            assert snap["rescan"]["total_s"] > 0.0
        finally:
            await app.stop()

    asyncio.run(run())
