"""Consume-batch / sharded-ingress equivalence (ISSUE 12; ``ingress``
marker).

The acceptance bar for the columnar consume_batch seam and the in-process
ingress shard workers is EQUIVALENCE: the batched/sharded configurations
must produce the same outcomes as the per-delivery path — same match
pairings, same per-player terminal responses (normalized for the
wall-clock-valued fields: latency_ms/waited_ms are measured times and
match/trace ids are process-global counters), and the same settlement
accounting (every delivery acked exactly once, nothing shed or lost).

Burst-by-burst submission with a drain between bursts pins the window
composition (max_batch == burst size, generous max_wait), so the seeded
soak is deterministic across configs and runs.

Plus unit coverage for the broker seam itself: whole-burst callbacks,
crash → nack-requeue, the per-delivery fallback while consume faults are
armed, and the AMQP loop-bridge coalescing.
"""

import asyncio
import json

import numpy as np
import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    BrokerConfig,
    ChaosConfig,
    Config,
    EngineConfig,
    QueueConfig,
)
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.broker import InProcBroker, Properties
from matchmaking_tpu.service.ingress import ShardedRecent, shard_of
from matchmaking_tpu.testing.drain import settle

pytestmark = pytest.mark.ingress

QUEUE = "matchmaking.search"
REPLY = "soak.replies"

#: Deliveries per soak burst == batcher max_batch, so window == burst.
BURST = 64
BURSTS = 4


def _soak_cfg(consume_batch: bool, shards: int = 1) -> Config:
    return Config(
        queues=(QueueConfig(rating_threshold=200.0, send_queued_ack=True),),
        engine=EngineConfig(backend="tpu", pool_capacity=512, pool_block=128,
                            batch_buckets=(16, BURST), top_k=4),
        # max_wait far above the submit gap: a burst always cuts by SIZE,
        # never by the clock — window composition is deterministic.
        batcher=BatcherConfig(max_batch=BURST, max_wait_ms=250.0),
        broker=BrokerConfig(consume_batch=consume_batch,
                            ingress_shards=shards),
        debug_invariants=True,
    )


def _soak_bodies(seed: int = 11) -> list[bytes]:
    """Seeded request corpus: plain hot-path rows, NEEDS_PYTHON rows
    (escaped/unicode ids — the shard workers' contract fallback), and
    malformed rows (decode rejects)."""
    rng = np.random.default_rng(seed)
    bodies: list[bytes] = []
    for i in range(BURST * BURSTS):
        r = float(rng.normal(1500.0, 150.0))
        if i % 23 == 7:
            # NEEDS_PYTHON: escaped quote in the id.
            bodies.append(json.dumps({"id": f'e"sc{i}', "rating": r}
                                     ).encode())
        elif i % 23 == 15:
            bodies.append(f'{{"id":"uni-é{i}","rating":{r:.2f}}}'
                          .encode())
        elif i % 31 == 19:
            bodies.append(b'{"id":"broken" "rating":1}')  # malformed
        else:
            bodies.append(f'{{"id":"p{i}","rating":{r:.2f}}}'.encode())
    return bodies


def _normalize(body: bytes) -> dict:
    """A response body minus its wall-clock-valued fields (measured
    latencies) and process-global ids (match/trace counters) — everything
    the engine DECIDED, nothing the clock stamped. Match identity is kept
    as the partner set, which pins the pairing exactly."""
    d = json.loads(body)
    d.pop("latency_ms", None)
    d.pop("waited_ms", None)
    d.pop("trace_id", None)
    match = d.get("match")
    if match:
        match.pop("match_id", None)
        match["quality"] = round(float(match.get("quality", 0.0)), 4)
    return d


async def _run_soak(cfg: Config) -> tuple[dict, dict]:
    """Drive the seeded corpus burst-by-burst with a drain between bursts;
    returns ({corr: [normalized responses]}, settlement counters)."""
    app = MatchmakingApp(cfg)
    await app.start()
    rt = app.runtime(QUEUE)
    app.broker.declare_queue(REPLY)
    replies: dict[str, list[dict]] = {}

    async def on_reply(delivery) -> None:
        corr = delivery.properties.correlation_id
        replies.setdefault(corr, []).append(_normalize(delivery.body))

    app.broker.basic_consume(REPLY, on_reply, prefetch=1_000_000)

    try:
        bodies = _soak_bodies()
        for b in range(BURSTS):
            for i in range(b * BURST, (b + 1) * BURST):
                app.broker.publish(
                    QUEUE, bodies[i],
                    Properties(reply_to=REPLY, correlation_id=f"c{i}"))
            assert await settle(app, rt, QUEUE, reply_queues=(REPLY,)), (
                f"burst {b} did not drain")
        counters = {
            name: int(app.metrics.counters.get(name))
            for name in ("players_matched", "rejected_by_middleware",
                         "rejected_by_engine", "deduped_replays",
                         "shed_requests", "expired_requests")
        }
        counters["acked"] = app.broker.stats["acked"]
        counters["dead_lettered"] = app.broker.stats["dead_lettered"]
        counters["consumer_errors"] = app.broker.stats["consumer_errors"]
        counters["pool_end"] = rt.engine.pool_size()
        # Exactly-once settlement: every request-queue delivery acked.
        assert counters["acked"] >= BURST * BURSTS
        return replies, counters
    finally:
        await app.stop()


def _assert_equivalent(a, b, label: str) -> None:
    ra, ca = a
    rb, cb = b
    assert ca == cb, f"{label}: settlement counters diverge: {ca} vs {cb}"
    assert set(ra) == set(rb), f"{label}: responded correlation ids diverge"
    for corr in ra:
        # Sort each side's responses canonically (the queued ack and the
        # terminal response may interleave differently between drains).
        sa = sorted(ra[corr], key=lambda d: json.dumps(d, sort_keys=True))
        sb = sorted(rb[corr], key=lambda d: json.dumps(d, sort_keys=True))
        assert sa == sb, f"{label}: responses for {corr} diverge:\n{sa}\n{sb}"


def test_consume_batch_on_off_equivalence():
    """consume_batch=True must reproduce the per-delivery path's outcomes:
    identical pairings, per-player responses, and settlement counters."""
    async def run():
        on = await _run_soak(_soak_cfg(consume_batch=True))
        off = await _run_soak(_soak_cfg(consume_batch=False))
        _assert_equivalent(on, off, "consume_batch on vs off")
        # The corpus exercised the interesting paths on both sides.
        assert on[1]["rejected_by_middleware"] > 0
        assert on[1]["players_matched"] > 0

    asyncio.run(run())


def test_ingress_shards_1_vs_4_equivalence():
    """ingress_shards=4 (per-shard fallback decode + per-shard dedup
    store) must match N=1 exactly — the consistent hash only changes WHO
    does the work, never the outcome."""
    async def run():
        one = await _run_soak(_soak_cfg(consume_batch=True, shards=1))
        four = await _run_soak(_soak_cfg(consume_batch=True, shards=4))
        _assert_equivalent(one, four, "ingress_shards 1 vs 4")

    asyncio.run(run())


@pytest.mark.chaos
def test_consume_batch_chaos_redelivery_soak():
    """Seeded chaos drops/dups with consume_batch on: the broker falls
    back to the per-delivery fault gate (chaos identity preserved), the
    invariant checker stays quiet, and every player reaches a terminal or
    queued state — the PR 1 soak guarantee, under the new ingress."""
    async def run():
        q = QueueConfig(rating_threshold=120.0, dedup_ttl_s=30.0)
        cfg = Config(
            queues=(q,),
            engine=EngineConfig(backend="tpu", pool_capacity=512,
                                pool_block=128, batch_buckets=(16, 64),
                                top_k=4),
            broker=BrokerConfig(max_redelivery=30, consume_batch=True,
                                ingress_shards=2),
            chaos=ChaosConfig(seed=5, queues=(q.name,),
                              drop_prob=0.08, dup_prob=0.12),
            batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
            debug_invariants=True,
        )
        app = MatchmakingApp(cfg)
        await app.start()
        rng = np.random.default_rng(5)
        app.broker.declare_queue(REPLY)
        statuses: dict[str, set] = {}

        async def on_reply(delivery) -> None:
            d = json.loads(delivery.body)
            statuses.setdefault(d.get("player_id", ""), set()).add(
                d["status"])

        app.broker.basic_consume(REPLY, on_reply, prefetch=1_000_000)
        try:
            n = 200
            for i in range(n):
                body = (f'{{"id":"p{i}","rating":'
                        f'{float(rng.normal(1500, 100)):.2f}}}').encode()
                app.broker.publish(q.name, body,
                                   Properties(reply_to=REPLY,
                                              correlation_id=f"c{i}"))
                if i % 40 == 39:
                    await asyncio.sleep(0.05)
            rt = app.runtime(q.name)
            await settle(app, rt, q.name, reply_queues=(REPLY,))
            matched = sum("matched" in s for s in statuses.values())
            waiting = rt.engine.pool_size()
            assert matched + waiting >= n - 2, (matched, waiting)
        finally:
            await app.stop()

    asyncio.run(run())


# ---- broker seam units ----------------------------------------------------


@pytest.fixture
def broker():
    return InProcBroker(BrokerConfig())


async def test_burst_callback_receives_whole_burst(broker):
    broker.declare_queue("q")
    for i in range(5):
        broker.publish("q", f"m{i}".encode())
    bursts: list[list[bytes]] = []

    async def on_batch(batch):
        bursts.append([d.body for d in batch])
        for d in batch:
            broker.ack(tag, d.delivery_tag)

    async def never(_d):  # pragma: no cover - batch path must win
        raise AssertionError("per-delivery callback on a fault-free broker")

    tag = broker.basic_consume("q", never, batch_callback=on_batch)
    for _ in range(100):
        await asyncio.sleep(0.005)
        if sum(len(b) for b in bursts) == 5:
            break
    assert sum(len(b) for b in bursts) == 5
    # The already-buffered backlog drains as ONE burst (after the first
    # get() returns, the drain loop sweeps the rest).
    assert len(bursts) <= 2
    assert broker.stats["acked"] == 5


async def test_burst_callback_crash_nacks_unsettled(broker):
    broker.declare_queue("q")
    for i in range(3):
        broker.publish("q", f"m{i}".encode())
    seen: list[bytes] = []
    crashed = False

    async def on_batch(batch):
        nonlocal crashed
        if not crashed:
            crashed = True
            raise RuntimeError("boom")
        for d in batch:
            seen.append(d.body)
            broker.ack(tag, d.delivery_tag)

    tag = broker.basic_consume("q", lambda d: None,
                               batch_callback=on_batch)
    for _ in range(200):
        await asyncio.sleep(0.005)
        if len(seen) == 3:
            break
    assert sorted(seen) == [b"m0", b"m1", b"m2"]
    assert broker.stats["consumer_errors"] == 1
    assert broker.stats["acked"] == 3


async def test_consume_faults_fall_back_to_per_delivery():
    """A broker with consume-side chaos armed must keep the per-delivery
    handler (fault identity is per delivery) — the batch callback is not
    invoked at all."""
    from matchmaking_tpu.utils.chaos import ChaosState

    chaos = ChaosState(ChaosConfig(seed=1, queues=("q",), drop_prob=0.5))
    broker = InProcBroker(BrokerConfig(max_redelivery=30), chaos=chaos)
    broker.declare_queue("q")
    for i in range(4):
        broker.publish("q", f"m{i}".encode())
    got: list[bytes] = []

    async def per_delivery(d):
        got.append(d.body)
        broker.ack(tag, d.delivery_tag)

    async def on_batch(batch):  # pragma: no cover - must not run
        raise AssertionError("batch path with consume faults armed")

    tag = broker.basic_consume("q", per_delivery, batch_callback=on_batch)
    for _ in range(200):
        await asyncio.sleep(0.005)
        if len(got) == 4:
            break
    assert sorted(got) == [b"m0", b"m1", b"m2", b"m3"]
    broker.close()


async def test_amqp_bridge_coalesces_bursts():
    """AMQP transport: deliveries bridged from the pika thread coalesce
    into one loop-side burst callback (fake_pika harness)."""
    import uuid

    from matchmaking_tpu.service.amqp_transport import AmqpBroker
    from matchmaking_tpu.testing import fake_pika

    url = f"amqp://fake-{uuid.uuid4().hex[:8]}"
    broker = AmqpBroker(url, pika_module=fake_pika,
                        reconnect_base_s=0.01, reconnect_max_s=0.05)
    broker.declare_queue("q")
    bursts: list[int] = []
    bodies: list[bytes] = []

    async def on_batch(batch):
        bursts.append(len(batch))
        for d in batch:
            bodies.append(d.body)
            broker.ack(tag, d.delivery_tag)

    tag = broker.basic_consume("q", lambda d: None,
                               batch_callback=on_batch)
    for i in range(6):
        broker.publish("q", f"m{i}".encode())
    for _ in range(400):
        await asyncio.sleep(0.005)
        if len(bodies) == 6:
            break
    assert sorted(bodies) == [f"m{i}".encode() for i in range(6)]
    assert sum(bursts) == 6
    broker.close()


async def test_amqp_burst_crash_nacks_only_unsettled():
    """AMQP _run_batch crash guard: deliveries the app settled before the
    crash are NOT nacked again (a basic_nack on an acked tag is a 406
    channel kill on real RabbitMQ); the unsettled remainder redelivers."""
    import uuid

    from matchmaking_tpu.service.amqp_transport import AmqpBroker
    from matchmaking_tpu.testing import fake_pika

    url = f"amqp://fake-{uuid.uuid4().hex[:8]}"
    broker = AmqpBroker(url, pika_module=fake_pika,
                        reconnect_base_s=0.01, reconnect_max_s=0.05)
    broker.declare_queue("q")
    settled: list[bytes] = []
    crashed = False

    async def on_batch(batch):
        nonlocal crashed
        if not crashed and len(batch) > 1:
            # Settle the first delivery, then crash: the handler must
            # nack only the rest.
            crashed = True
            settled.append(batch[0].body)
            broker.ack(tag, batch[0].delivery_tag)
            raise RuntimeError("boom")
        for d in batch:
            settled.append(d.body)
            broker.ack(tag, d.delivery_tag)

    tag = broker.basic_consume("q", lambda d: None,
                               batch_callback=on_batch)
    for i in range(4):
        broker.publish("q", f"m{i}".encode())
    for _ in range(400):
        await asyncio.sleep(0.005)
        if len(settled) >= 4 and crashed:
            break
    # Every delivery settled exactly once overall: the crashed burst's
    # first member was acked pre-crash and never reprocessed.
    assert sorted(settled) == [b"m0", b"m1", b"m2", b"m3"], settled
    assert broker.stats["consumer_errors"] >= 1
    broker.close()


# ---- sharded state units --------------------------------------------------


def test_shard_hash_is_deterministic_and_balanced():
    assert shard_of("player-1", 1) == 0
    ids = [f"p{i}" for i in range(4096)]
    counts = [0] * 8
    for pid in ids:
        s = shard_of(pid, 8)
        assert s == shard_of(pid, 8)  # stable
        counts[s] += 1
    assert min(counts) > 4096 // 8 // 2  # roughly balanced


def test_sharded_recent_routes_and_prunes():
    r = ShardedRecent(4)
    for i in range(100):
        r.set(f"p{i}", (b"body", 10.0 if i % 2 else 1.0))
    assert len(r) == 100
    assert r.get("p3") == (b"body", 10.0)
    r.pop("p3")
    assert r.get("p3") is None
    r.prune(5.0)  # drops the expiry-1.0 half
    assert len(r) == 49
    # Single-shard degenerate case: same API, one dict.
    one = ShardedRecent(1)
    one.set("x", (b"b", 2.0))
    assert len(one) == 1 and one.get("x") == (b"b", 2.0)
