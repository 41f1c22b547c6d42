"""Service-layer tests: middleware pipeline, batcher windows, and the full
end-to-end path request → broker → middleware → batcher → engine → response
(SURVEY.md §4 "integration-test request→response through the full
middleware+batcher+kernel path")."""

import asyncio
import json
import time

import pytest

from matchmaking_tpu.config import (
    AuthConfig,
    BatcherConfig,
    BrokerConfig,
    Config,
    EngineConfig,
    QueueConfig,
)
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.batcher import Batcher
from matchmaking_tpu.service.broker import Delivery, InProcBroker, Properties
from matchmaking_tpu.service.client import MatchmakingClient
from matchmaking_tpu.testing.drain import settle
from matchmaking_tpu.service.middleware import (
    AuthMiddleware,
    DecodeMiddleware,
    MessageContext,
    MiddlewareReject,
    Pipeline,
)


def tiny_cfg(backend="tpu", queues=None, **kw):
    return Config(
        queues=queues or (QueueConfig(rating_threshold=100.0),),
        engine=EngineConfig(backend=backend, pool_capacity=256, pool_block=64,
                            batch_buckets=(8, 32), top_k=4),
        batcher=BatcherConfig(max_batch=8, max_wait_ms=10.0),
        **kw,
    )


def _delivery(body: bytes, headers=None, reply_to="r.q", corr="c1"):
    return Delivery(body=body, properties=Properties(reply_to=reply_to,
                    correlation_id=corr, headers=headers or {}),
                    queue="q", delivery_tag=1)


# ---- middleware -----------------------------------------------------------


async def test_decode_middleware_sets_request():
    ctx = MessageContext(_delivery(b'{"id":"p","rating":1500}'), queue="q")
    await Pipeline([DecodeMiddleware()]).run(ctx)
    assert ctx.request is not None and ctx.request.id == "p"
    assert ctx.request.reply_to == "r.q" and ctx.request.queue == "q"
    assert ctx.request.enqueued_at == pytest.approx(ctx.received_at)


async def test_decode_middleware_rejects_bad_payload():
    ctx = MessageContext(_delivery(b"garbage"), queue="q")
    with pytest.raises(MiddlewareReject) as ei:
        await Pipeline([DecodeMiddleware()]).run(ctx)
    assert ei.value.code == "bad_json"


async def test_auth_middleware_static():
    mw = AuthMiddleware(AuthConfig(mode="static", static_secret="sekrit"))
    ok = MessageContext(_delivery(b"{}", headers={"authorization": "sekrit-abc"}), queue="q")
    ran = []

    async def nxt():
        ran.append(1)

    await mw.call(ok, nxt)
    assert ran == [1]
    bad = MessageContext(_delivery(b"{}", headers={"authorization": "wrong"}), queue="q")
    with pytest.raises(MiddlewareReject) as ei:
        await mw.call(bad, nxt)
    assert ei.value.code == "unauthorized"


async def test_auth_middleware_rpc_roundtrip():
    broker = InProcBroker(BrokerConfig())

    async def auth_service(d):
        verdict = b"ok" if d.body == b"good" else b"denied"
        broker.publish(d.properties.reply_to, verdict,
                       Properties(correlation_id=d.properties.correlation_id))
        broker.ack(tag, d.delivery_tag)

    tag = broker.basic_consume("auth.token.verify", auth_service)
    mw = AuthMiddleware(AuthConfig(mode="rpc"), broker)

    async def nxt():
        pass

    await mw.call(MessageContext(_delivery(b"{}", headers={"authorization": "good"}), queue="q"), nxt)
    with pytest.raises(MiddlewareReject):
        await mw.call(MessageContext(_delivery(b"{}", headers={"authorization": "evil"}), queue="q"), nxt)
    broker.close()


# ---- batcher --------------------------------------------------------------


async def test_batcher_size_trigger():
    windows = []

    async def flush(w):
        windows.append(list(w))

    b = Batcher(BatcherConfig(max_batch=4, max_wait_ms=10_000.0), flush)
    for i in range(4):
        b.submit(i)
    await asyncio.sleep(0.05)
    assert windows == [[0, 1, 2, 3]]  # size fired despite huge wait
    await b.close()


async def test_batcher_time_trigger():
    windows = []

    async def flush(w):
        windows.append(list(w))

    b = Batcher(BatcherConfig(max_batch=1000, max_wait_ms=20.0), flush)
    b.submit("only")
    t0 = time.perf_counter()
    while not windows:
        assert time.perf_counter() - t0 < 1.0
        await asyncio.sleep(0.005)
    assert windows == [["only"]]
    assert time.perf_counter() - t0 < 0.5
    await b.close()


async def test_batcher_serializes_windows():
    active = [0]
    overlap = []

    async def flush(w):
        active[0] += 1
        overlap.append(active[0])
        await asyncio.sleep(0.02)
        active[0] -= 1

    b = Batcher(BatcherConfig(max_batch=2, max_wait_ms=5.0), flush)
    for i in range(10):
        b.submit(i)
    await asyncio.sleep(0.3)
    assert max(overlap) == 1  # windows never overlap (atomicity)
    await b.close()


# ---- end-to-end -----------------------------------------------------------


async def test_e2e_two_players_match():
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    a, b = (client.submit({"id": "alice", "rating": 1500}),
            client.submit({"id": "bob", "rating": 1540}))
    ra = await client.next_response(a, timeout=15.0)
    rb = await client.next_response(b, timeout=15.0)
    # Both arrive in one window → immediate match (no queued ack first).
    assert {ra.status, rb.status} == {"matched"}
    assert ra.match.match_id == rb.match.match_id
    assert set(ra.match.players) == {"alice", "bob"}
    await app.stop()


async def test_e2e_queued_then_matched_later():
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    a = client.submit({"id": "alice", "rating": 1500})
    ra = await client.next_response(a, timeout=15.0)
    assert ra.status == "queued"
    await asyncio.sleep(0.05)  # next window
    b = client.submit({"id": "bob", "rating": 1520})
    ra2 = await client.next_response(a, timeout=15.0)
    rb = await client.next_response(b, timeout=15.0)
    assert ra2.status == "matched" and rb.status == "matched"
    assert ra2.match.match_id == rb.match.match_id
    await app.stop()


async def test_e2e_malformed_payload_gets_error_response():
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    import uuid

    reply = f"amq.gen-{uuid.uuid4().hex}"
    app.broker.publish("matchmaking.search", b"not json",
                       Properties(reply_to=reply, correlation_id="x"))
    d = await app.broker.get(reply, timeout=15.0)
    resp = json.loads(d.body)
    assert resp["status"] == "error" and resp["error"]["code"] == "bad_json"
    await app.stop()


async def test_e2e_party_rejected_on_1v1_queue():
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    r = client.submit({"id": "lead", "rating": 1500,
                       "party": [{"id": "m2", "rating": 1510}]})
    resp = await client.next_response(r, timeout=15.0)
    assert resp.status == "error" and resp.error_code == "party_not_supported"
    await app.stop()


async def test_e2e_auth_static_rejects_without_token():
    cfg = tiny_cfg(auth=AuthConfig(mode="static", static_secret="tok"))
    app = MatchmakingApp(cfg)
    await app.start()
    good = MatchmakingClient(app.broker, "matchmaking.search", auth_token="tok-1")
    bad = MatchmakingClient(app.broker, "matchmaking.search")
    rb = bad.submit({"id": "evil", "rating": 1500})
    resp = await bad.next_response(rb, timeout=15.0)
    assert resp.status == "error" and resp.error_code == "unauthorized"
    rg = good.submit({"id": "nice", "rating": 1500})
    resp = await good.next_response(rg, timeout=15.0)
    assert resp.status == "queued"
    await app.stop()


async def test_e2e_multi_queue_partitioning():
    # BASELINE config #2: separate queues per game mode.
    queues = (QueueConfig(name="mm.ranked", game_mode="ranked", rating_threshold=100),
              QueueConfig(name="mm.casual", game_mode="casual", rating_threshold=100))
    app = MatchmakingApp(tiny_cfg(queues=queues))
    await app.start()
    client = MatchmakingClient(app.broker, "mm.ranked")
    r1 = client.submit({"id": "a", "rating": 1500}, queue="mm.ranked")
    r2 = client.submit({"id": "b", "rating": 1510}, queue="mm.casual")
    ra = await client.next_response(r1, timeout=15.0)
    rb = await client.next_response(r2, timeout=15.0)
    # Different queues must NOT match each other.
    assert ra.status == "queued" and rb.status == "queued"
    r3 = client.submit({"id": "c", "rating": 1505}, queue="mm.ranked")
    rc = await client.next_response(r3, timeout=15.0)
    ra2 = await client.next_response(r1, timeout=15.0)
    assert rc.status == "matched" and ra2.status == "matched"
    assert set(rc.match.players) == {"a", "c"}
    await app.stop()


async def test_e2e_request_timeout_response():
    queues = (QueueConfig(rating_threshold=10.0, request_timeout_s=0.2),)
    app = MatchmakingApp(tiny_cfg(queues=queues))
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    r = client.submit({"id": "lonely", "rating": 1500})
    resp = await client.next_response(r, timeout=15.0)
    assert resp.status == "queued"
    resp = await client.next_response(r, timeout=15.0)
    assert resp.status == "timeout"
    assert app.runtime("matchmaking.search").engine.pool_size() == 0
    await app.stop()


async def test_e2e_engine_crash_recovers_from_mirror(monkeypatch):
    # SURVEY.md §5 failure recovery: engine dies mid-window → window is
    # nacked/redelivered, engine is revived from the host mirror, and the
    # waiting player is still matchable afterwards.
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    a = client.submit({"id": "alice", "rating": 1500})
    ra = await client.next_response(a, timeout=15.0)
    assert ra.status == "queued"

    rt = app.runtime("matchmaking.search")
    # The columnar flush enters through search_columns_async; crash there.
    # Revive replaces the engine object, so only the first call explodes.

    def exploding(cols, now):
        raise RuntimeError("injected engine crash")

    monkeypatch.setattr(rt.engine, "search_columns_async", exploding)
    b = client.submit({"id": "bob", "rating": 1520})
    rb = await client.next_response(b, timeout=3.0)
    ra2 = await client.next_response(a, timeout=3.0)
    assert rb.status == "matched" and ra2.status == "matched"
    assert set(rb.match.players) == {"alice", "bob"}
    assert app.metrics.counters.get("engine_crashes") == 1
    await app.stop()


async def test_e2e_under_broker_faults():
    # Drop/dup injection: at-least-once + idempotent enqueue must still
    # produce exactly-once match results.
    cfg = tiny_cfg(broker=BrokerConfig(drop_prob=0.2, dup_prob=0.2, max_redelivery=20))
    app = MatchmakingApp(cfg)
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    n = 16
    replies = [client.submit({"id": f"p{i}", "rating": 1500 + (i % 4)}) for i in range(n)]
    results = await asyncio.gather(*[_await_terminal(client, r) for r in replies])
    matched = [r for r in results if r and r.status == "matched"]
    assert len(matched) == n
    # No player may appear in two different matches.
    seen = {}
    for r in matched:
        for pid in r.match.players:
            assert seen.setdefault(pid, r.match.match_id) == r.match.match_id
    await app.stop()


async def _await_terminal(client, reply_to, timeout=5.0):
    deadline = asyncio.get_event_loop().time() + timeout
    last = None
    while asyncio.get_event_loop().time() < deadline:
        resp = await client.next_response(reply_to, timeout=0.5)
        if resp is not None:
            last = resp
            if resp.status != "queued":
                return resp
    return last


async def test_e2e_duplicate_delivery_never_double_matches():
    # dup_prob=1.0: EVERY request is delivered twice. Reading every response
    # on every reply queue, each player must see exactly one match_id.
    cfg = tiny_cfg(broker=BrokerConfig(dup_prob=1.0))
    app = MatchmakingApp(cfg)
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    n = 8
    replies = {f"p{i}": client.submit({"id": f"p{i}", "rating": 1500 + i}) for i in range(n)}
    # The replies are read only once every duplicate has been consumed
    # and its replay published.
    rt = app.runtime("matchmaking.search")
    await settle(app, rt, "matchmaking.search", matched_at_least=n)
    match_ids = {}
    for pid, reply_to in replies.items():
        while True:
            resp = await client.next_response(reply_to, timeout=0.2)
            if resp is None:
                break
            if resp.status == "matched":
                match_ids.setdefault(pid, set()).add(resp.match.match_id)
    for pid, ids in match_ids.items():
        assert len(ids) == 1, f"{pid} saw {len(ids)} distinct matches"
    assert len(match_ids) == n
    assert app.metrics.counters.get("players_matched") == n  # engine saw each once
    await app.stop()


async def test_app_stop_with_pending_window_is_clean():
    # Items still sitting in the batcher at stop(): shutdown must not crash
    # and must flush or requeue them.
    cfg = tiny_cfg()
    cfg = Config(queues=cfg.queues, engine=cfg.engine,
                 batcher=BatcherConfig(max_batch=64, max_wait_ms=10_000.0))
    app = MatchmakingApp(cfg)
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    a = client.submit({"id": "alice", "rating": 1500})
    b = client.submit({"id": "bob", "rating": 1510})
    await asyncio.sleep(0.05)  # delivered into the batcher; window still open
    await app.stop()  # must not raise; close() flushes the pending window
    ra = await client.next_response(a, timeout=1.0)
    rb = await client.next_response(b, timeout=1.0)
    assert ra is not None and rb is not None
    assert {ra.status, rb.status} == {"matched"}


async def test_reply_queues_do_not_leak():
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    client = MatchmakingClient(app.broker, "matchmaking.search")
    base = len(app.broker._queues)
    for i in range(0, 20, 2):
        r1 = await client.search_until_matched({"id": f"a{i}", "rating": 1500}, timeout=15.0)
        assert r1.status in ("matched", "queued", "timeout")
    # search_until_matched deletes its reply queue; only the odd leftovers
    # from pairing (none here: players match in pairs a{i}/a{i+1}? actually
    # sequential singles pile up) — just assert no growth beyond the waiting
    # players still being matched.
    assert len(app.broker._queues) <= base + 1
    await app.stop()


async def test_redelivery_preserves_wait_clock(monkeypatch):
    # A crashed window's redelivered request must keep its original
    # enqueued_at (timeout sweeper / widening restart otherwise).
    app = MatchmakingApp(tiny_cfg())
    await app.start()
    rt = app.runtime("matchmaking.search")
    seen_enqueued = []

    def crashing(cols, now):
        # Record the wait clock the engine would have seen, then crash
        # (revive replaces the engine object, so only this call explodes).
        seen_enqueued.extend(cols.enqueued_at.tolist())
        raise RuntimeError("crash before matching")

    monkeypatch.setattr(rt.engine, "search_columns_async", crashing)
    client = MatchmakingClient(app.broker, "matchmaking.search")
    r = client.submit({"id": "alice", "rating": 1500})
    resp = await client.next_response(r, timeout=3.0)
    assert resp is not None and resp.status == "queued"
    # The crash revived the engine (new object, real search), so the
    # redelivered copy lives in the NEW engine's pool: its enqueued_at must
    # equal the original receive time, not the redelivery time.
    assert len(seen_enqueued) == 1
    waiting = rt.engine.waiting()
    assert len(waiting) == 1
    assert waiting[0].enqueued_at == pytest.approx(seen_enqueued[0], abs=1e-6)
    await app.stop()
