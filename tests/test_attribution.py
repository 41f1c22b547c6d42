"""ISSUE 6 acceptance surface: critical-path attribution, device idle
accounting, continuous telemetry, and SLO burn monitoring.

- Smoke (scripts/check.sh runs it by name): on a seeded 400-player soak,
  every settled trace's wait + work decomposition sums to its
  enqueue→publish span (telescoping identity), and the attribution-side
  p99 agrees with the exact recorder p99 within one log-bucket width.
- /debug/attribution over HTTP decomposes the e2e span into named work
  stages and wait gaps, reports the per-queue device idle fraction, and
  quotes a p99 exemplar whose gaps sum to its span exactly.
- Device utilization counters are monotone and expose busy/idle +
  batch-fill-weighted effective occupancy.
- The telemetry ring answers delta/rate queries; SLO monitors flip
  burning on sustained budget burn and emit slo_burn events.
- Replay stability: two runs of the seeded chaos soak produce
  bit-identical attribution counts (statuses, per-category trace counts,
  SLO good/total).
- Drain-time broker-backlog handoff: unconsumed deliveries ride the drain
  checkpoint and are re-published on restore.
"""

import asyncio
import json
import os
import time

import numpy as np
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
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.attribution import (
    WAIT,
    WORK,
    Attribution,
    classify,
    decompose,
)
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.testing.drain import settle
from matchmaking_tpu.utils.timeseries import SloMonitor, TelemetryRing


async def _wait_for(cond, tries: int = 400, dt: float = 0.05):
    for _ in range(tries):
        if cond():
            return
        await asyncio.sleep(dt)
    assert cond(), "condition not reached in time"


async def _http_json(url: str):
    import aiohttp

    async with aiohttp.ClientSession() as s:
        async with s.get(url) as r:
            return r.status, json.loads(await r.text())


# ---------------------------------------------------------------------------
# classification + decomposition units


def test_classify_total_and_classes():
    """Every pair classifies somewhere (the telescoping identity needs a
    total function), and the classes pins the load-bearing gaps."""
    assert classify("enqueue", "consume") == ("broker_dwell", WAIT)
    assert classify("chaos_drop", "consume") == ("redelivery_wait", WAIT)
    assert classify("batch", "flush") == ("batcher_hold", WAIT)
    assert classify("flush", "dispatch") == ("pipeline_slot_wait", WAIT)
    assert classify("dispatch", "h2d") == ("pack_h2d", WORK)
    assert classify("h2d", "device_step") == ("device_step", WORK)
    assert classify("device_step", "readback_seal")[1] == WAIT
    assert classify("collect", "publish") == ("publish_lag", WAIT)
    # synchronous engines bracket the step with dispatch→collect
    assert classify("dispatch", "collect") == ("engine_step", WORK)
    # unknown marks still land in a kind
    cat, kind = classify("made", "up")
    assert kind in (WORK, WAIT)


def test_decompose_telescopes_exactly():
    from matchmaking_tpu.utils.trace import TraceContext

    tr = TraceContext("q", t=100.0)
    for i, name in enumerate(("consume", "middleware", "batch", "flush",
                              "dispatch", "h2d", "device_step", "collect",
                              "publish")):
        tr.mark(name, 100.0 + (i + 1) * 0.01)
    tr.status = "matched"
    d = decompose(tr)
    assert d["work_ms"] + d["wait_ms"] == pytest.approx(d["total_ms"],
                                                        abs=1e-6)
    assert {g["category"] for g in d["gaps"]} >= {
        "broker_dwell", "batcher_hold", "pipeline_slot_wait", "device_step"}


# ---------------------------------------------------------------------------
# the check.sh smoke: seeded 400-player soak


async def _soak_400(q: QueueConfig, cfg: Config) -> MatchmakingApp:
    app = MatchmakingApp(cfg)
    reply = "attr.replies"
    app.broker.declare_queue(reply)
    await app.start()
    rng = np.random.default_rng(42)
    waits = np.exp(rng.uniform(np.log(5e-3), np.log(20.0), size=400))
    now = time.time()
    for i, w in enumerate(waits.tolist()):
        app.broker.publish(
            q.name,
            f'{{"id":"a{i}","rating":{1500 + (i % 2)}}}'.encode(),
            Properties(reply_to=reply, correlation_id=f"c{i}",
                       headers={"x-first-received": f"{now - w:.6f}"}))
    # Wait on the ATTRIBUTION span count, not the matched counter: the
    # counter increments a hair before the window's traces settle.
    await _wait_for(
        lambda: app.attribution.snapshot(queue=q.name)["queues"]
        .get(q.name, {}).get("spans", 0) >= 400)
    return app


async def test_attribution_smoke():
    """check.sh gate: wait + work sums to the e2e span for every settled
    trace, the per-queue totals agree with the per-trace sums, and the
    attribution p99 sits within one log bucket of the recorder's exact
    p99 (factor-2 buckets → exact in (upper/2, upper])."""
    q = QueueConfig(name="mm.attr", rating_threshold=100.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="cpu"),
        batcher=BatcherConfig(max_batch=1024, max_wait_ms=2.0),
        observability=ObservabilityConfig(slow_trace_ms=1e9, trace_ring=1024,
                                          snapshot_interval_s=0.0),
        debug_invariants=True,
    )
    app = await _soak_400(q, cfg)
    try:
        snap = app.recorder.snapshot(queue=q.name, limit=1024)
        traces = snap["queues"][q.name]["recent"]
        assert len(traces) >= 400
        work_sum = wait_sum = total_sum = 0.0
        for tr_dict in traces:
            # re-decompose from the raw marks: the identity must hold per
            # trace, not just in aggregate
            marks = tr_dict["marks"]
            total = marks[-1][1] - marks[0][1]
            w = s = 0.0
            prev_name, prev_t = marks[0]
            for name, t in marks[1:]:
                _, kind = classify(prev_name, name)
                if kind == WORK:
                    w += max(0.0, t - prev_t)
                else:
                    s += max(0.0, t - prev_t)
                prev_name, prev_t = name, t
            assert w + s == pytest.approx(total, abs=1e-6), tr_dict
            work_sum += w
            wait_sum += s
            total_sum += total
        entry = app.attribution.snapshot(queue=q.name)["queues"][q.name]
        assert entry["spans"] >= 400
        # aggregate identity: per-queue work/wait totals equal the sum of
        # the per-trace decompositions (the same settled traces feed both)
        assert entry["work_s"] == pytest.approx(work_sum, rel=1e-6, abs=1e-4)
        assert entry["wait_s"] == pytest.approx(wait_sum, rel=1e-6, abs=1e-4)
        assert entry["work_s"] + entry["wait_s"] == pytest.approx(
            total_sum, rel=1e-6, abs=1e-4)
        # attribution p99 (bucket upper edge) within one log bucket of the
        # EXACT p99 over the same settled spans (nearest rank).
        import math

        totals = sorted(t["total_ms"] / 1e3 for t in traces)
        exact = totals[min(len(totals) - 1,
                           max(0, math.ceil(0.99 * len(totals)) - 1))]
        upper = entry["p99_total_ms"] / 1e3
        assert exact <= upper * 1.0000001, (exact, upper)
        assert exact > upper / 2.0, (
            f"p99 off by more than one bucket: exact={exact} upper={upper}")
        assert 0.0 < entry["wait_fraction"] < 1.0
        for expected in ("broker_dwell", "batcher_hold", "engine_step",
                         "publish_lag"):
            assert expected in entry["categories"], entry["categories"]
    finally:
        await app.stop()


# ---------------------------------------------------------------------------
# device utilization counters (engine-level)


def test_device_util_counters_monotone_and_occupancy():
    from matchmaking_tpu.engine.interface import make_engine

    cfg = Config(
        queues=(QueueConfig(rating_threshold=100.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=256, pool_block=64,
                            batch_buckets=(16, 64), pipeline_depth=2),
    )
    engine = make_engine(cfg, cfg.queues[0])
    u0 = engine.util_report()
    assert u0["device_busy_s"] == 0.0
    assert u0["lanes_valid"] == 0
    time.sleep(0.02)
    u1 = engine.util_report()
    # idle accrues read-only while nothing is dispatched
    assert u1["device_idle_s"] > u0["device_idle_s"]
    assert u1["idle_fraction"] > 0.99

    from matchmaking_tpu.service.contract import RequestColumns

    def cols(n, start):
        return RequestColumns(
            ids=np.asarray([f"p{start + i}" for i in range(n)], object),
            rating=np.full(n, 1500.0, np.float32),
            rd=np.zeros(n, np.float32),
            region=np.zeros(n, np.int32),
            mode=np.zeros(n, np.int32),
            threshold=np.full(n, np.nan, np.float32),
            enqueued_at=np.zeros(n, np.float64),
        )

    engine.search_columns_async(cols(10, 0), 0.0)
    engine.search_columns_async(cols(20, 100), 0.0)
    engine.flush()
    u2 = engine.util_report()
    assert u2["device_busy_s"] > 0.0
    assert u2["windows"] == 2
    # batch-fill-weighted effective occupancy: 10→bucket 16, 20→bucket 64
    assert u2["lanes_valid"] == 30
    assert u2["lanes_padded"] == 16 + 64
    assert u2["effective_occupancy"] == pytest.approx(30 / 80)
    # counters are monotone: a later scrape never goes backwards
    u3 = engine.util_report()
    for key in ("device_busy_s", "device_idle_s", "readback_s"):
        assert u3[key] >= u2[key]


# ---------------------------------------------------------------------------
# HTTP surfaces: /debug/attribution, /debug/telemetry, /healthz slo


async def test_debug_attribution_endpoint_device_path():
    port = 19271
    q = QueueConfig(name="mm.attr.dev", rating_threshold=100.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="tpu", pool_capacity=64, pool_block=32,
                            batch_buckets=(16,), pipeline_depth=2),
        batcher=BatcherConfig(max_batch=16, max_wait_ms=2.0),
        observability=ObservabilityConfig(
            slow_trace_ms=0.0, snapshot_interval_s=0.05,
            slo_target_ms=60_000.0, slo_fast_window_s=0.2,
            slo_slow_window_s=0.5),
        debug_invariants=True,
        metrics_port=port,
    )
    app = MatchmakingApp(cfg)
    reply = "attr.dev.replies"
    app.broker.declare_queue(q.name)
    app.broker.declare_queue(reply)
    await app.start()
    try:
        for i in range(4):
            app.broker.publish(
                q.name, f'{{"id":"d{i}","rating":1500}}'.encode(),
                Properties(reply_to=reply, correlation_id=f"c{i}"))
        # All 4 traces settle at window collection (matched or queued);
        # identical ratings may leave a tie queued, which is fine — the
        # endpoint needs settled device-path spans, not a match count.
        await _wait_for(
            lambda: app.attribution.snapshot(queue=q.name)["queues"]
            .get(q.name, {}).get("spans", 0) >= 4
            and app.metrics.counters.get("players_matched") >= 2)
        status, body = await _http_json(
            f"http://127.0.0.1:{port}/debug/attribution")
        assert status == 200
        entry = body["queues"][q.name]
        cats = entry["categories"]
        # named work stages AND wait gaps, from the device path
        assert cats["device_step"]["kind"] == "work"
        assert cats["pack_h2d"]["kind"] == "work"
        assert cats["batcher_hold"]["kind"] == "wait"
        assert cats["broker_dwell"]["kind"] == "wait"
        assert cats["publish_lag"]["kind"] == "wait"
        assert 0.0 <= entry["wait_fraction"] <= 1.0
        # per-queue device idle fraction is a number in [0, 1]
        util = entry["device_util"]
        assert 0.0 <= util["idle_fraction"] <= 1.0
        assert util["device_busy_s"] > 0.0
        # the p99 exemplar's gaps sum to its span exactly
        ex = entry["p99_exemplar"]
        assert ex["work_ms"] + ex["wait_ms"] == pytest.approx(
            ex["total_ms"], abs=1e-2)
        assert sum(g["ms"] for g in ex["gaps"]) == pytest.approx(
            ex["total_ms"], abs=1e-2)
        # SLO entry present (target generous → not burning)
        assert entry["slo"]["target_ms"] == 60_000.0
        assert entry["slo"]["burning"] is False

        # telemetry ring over HTTP, filtered to the idle series
        await _wait_for(lambda: len(app.telemetry) >= 2, tries=100, dt=0.05)
        status, tele = await _http_json(
            f"http://127.0.0.1:{port}/debug/telemetry?key=idle_frac&n=8")
        assert status == 200 and tele["snapshots"]
        assert any(f"idle_frac[{q.name}]" in snap["values"]
                   for snap in tele["snapshots"])

        # /healthz surfaces the SLO monitor
        status, health = await _http_json(
            f"http://127.0.0.1:{port}/healthz")
        assert status == 200
        assert health["queues"][q.name]["slo"]["burning"] is False
        assert health["slo_burning_queues"] == []
    finally:
        await app.stop()


# ---------------------------------------------------------------------------
# telemetry ring + SLO monitor units


def test_telemetry_ring_delta_rate_and_filtering():
    ring = TelemetryRing(4)
    for i in range(6):
        ring.append(float(i), {"slo_good[q]": 10.0 * i,
                               "slo_total[q]": 10.0 * i,
                               "other": 1.0})
    assert len(ring) == 4  # bounded
    d = ring.delta("slo_good[q]", 2.0, now=5.0)
    # Delta is a (value, span_s, reset) NamedTuple — the old positional
    # contract holds at [0]/[1], with the reset flag riding along.
    assert (d.value, d.span_s, d.reset) == (20.0, 2.0, False)
    assert (d[0], d[1]) == (20.0, 2.0)
    assert ring.rate("slo_good[q]", 2.0, now=5.0) == pytest.approx(10.0)
    # window longer than the ring falls back to the oldest retained
    d = ring.delta("slo_good[q]", 100.0, now=5.0)
    assert (d.value, d.span_s) == (30.0, 3.0)
    assert ring.delta("missing", 2.0) is None
    rows = ring.snapshot(limit=2, prefixes=("slo_good",))
    assert len(rows) == 2
    assert set(rows[-1]["values"]) == {"slo_good[q]"}


def test_telemetry_ring_counter_reset_clamps_and_flags():
    """ISSUE 13 satellite: an engine revive/breaker swap restarts the
    monotone device counters at 0 — delta/rate must never go negative.
    The reset-corrected increase sums positive increments, counting each
    post-reset sample from 0 (Prometheus increase() semantics), and the
    ``reset`` flag marks the window as spanning two engines."""
    from matchmaking_tpu.utils.timeseries import Delta

    ring = TelemetryRing(16)
    # 10 → 30 busy-seconds, revive (restart at 2), then 2 → 8.
    for t, v in [(0, 10.0), (1, 20.0), (2, 30.0), (3, 2.0), (4, 8.0)]:
        ring.append(float(t), {"device_busy_s[q]": v})
    d = ring.delta("device_busy_s[q]", 100.0, now=4.0)
    assert isinstance(d, Delta)
    assert d.reset is True
    assert d.value == pytest.approx(28.0)  # 20 pre-revive + 8 post
    assert d.value >= 0 and ring.rate("device_busy_s[q]", 100.0,
                                      now=4.0) >= 0
    # A reset hidden INSIDE an endpoint-increasing window is still caught
    # (naive endpoint difference would undercount, not just go negative).
    ring2 = TelemetryRing(16)
    for t, v in [(0, 10.0), (1, 1.0), (2, 12.0)]:
        ring2.append(float(t), {"c": v})
    d2 = ring2.delta("c", 100.0, now=2.0)
    assert d2.reset is True and d2.value == pytest.approx(12.0)
    # Reset-free windows keep the exact endpoint difference.
    ring3 = TelemetryRing(16)
    for t, v in [(0, 5.0), (1, 6.0), (2, 9.0)]:
        ring3.append(float(t), {"c": v})
    d3 = ring3.delta("c", 100.0, now=2.0)
    assert d3.reset is False and d3.value == 4.0


async def test_telemetry_reset_survives_engine_revive_mid_soak(rng):
    """Regression pin for the revive-mid-soak shape: a scripted chaos
    step fault crashes the device engine mid-traffic, the revive installs
    a fresh engine (busy/idle counters restart at 0), and every delta the
    ring serves across that boundary stays non-negative with the reset
    flag raised — the burn monitors and the autotuner read these."""
    q = QueueConfig(name="mm.reset", rating_threshold=1.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="tpu", pool_capacity=512,
                            pool_block=256, batch_buckets=(16, 64),
                            pipeline_depth=1),
        batcher=BatcherConfig(max_batch=32, max_wait_ms=2.0),
        chaos=ChaosConfig(seed=7, queues=(q.name,), fail_steps=(2,)),
        observability=ObservabilityConfig(snapshot_interval_s=0.0),
    )
    app = MatchmakingApp(cfg)
    await app.start()
    rt = app.runtime(q.name)
    try:
        crash_seen = False
        for wave in range(6):
            # Unmatchable ratings (unique, gaps >> threshold): every wave
            # dispatches at least one real window and the pool only grows
            # — so the scripted step-2 fault fires mid-soak, not at the
            # teardown flush.
            for j in range(8):
                rating = 1000 + (wave * 8 + j) * 300
                app.broker.publish(
                    q.name,
                    f'{{"id":"p{wave}_{j}","rating":{rating}}}'.encode(),
                    Properties(reply_to="reset.replies",
                               correlation_id=f"c{wave}_{j}"))
            await settle(app, rt, q.name)
            app.sample_telemetry(now=float(wave + 1))
            crash_seen = crash_seen or any(
                e["kind"] == "engine_crash"
                for e in app.events.snapshot())
        assert crash_seen, "the scripted step fault never fired"
        for name in (f"device_busy_s[{q.name}]",
                     f"device_idle_s[{q.name}]"):
            d = app.telemetry.delta(name, 100.0, now=6.0)
            assert d is not None
            assert d.value >= 0.0, (name, d)
        # At least one of the device-counter series must have seen the
        # restart (the revive rebuilt the engine).
        flags = [app.telemetry.delta(f"device_busy_s[{q.name}]",
                                     100.0, now=6.0).reset,
                 app.telemetry.delta(f"device_idle_s[{q.name}]",
                                     100.0, now=6.0).reset]
        assert any(flags), flags
    finally:
        await app.stop()


def test_slo_monitor_burn_transitions_emit_events():
    from matchmaking_tpu.utils.metrics import Metrics
    from matchmaking_tpu.utils.trace import EventLog

    events = EventLog()
    metrics = Metrics()
    ring = TelemetryRing(64)
    mon = SloMonitor("q", target_ms=100.0, objective=0.9,
                     fast_window_s=2.0, slow_window_s=5.0,
                     burn_threshold=1.0, events=events, metrics=metrics)
    # healthy phase: everything good
    for i in range(6):
        ring.append(float(i), {"slo_good[q]": 10.0 * i,
                               "slo_total[q]": 10.0 * i})
        mon.evaluate(ring, float(i))
    assert mon.burning is False
    # burn phase: half the requests miss → error rate 0.5, budget 0.1 →
    # burn 5x in both windows
    good = 50.0
    for i in range(6, 12):
        good += 5.0
        ring.append(float(i), {"slo_good[q]": good,
                               "slo_total[q]": 10.0 * i})
        mon.evaluate(ring, float(i))
    assert mon.burning is True
    assert mon.burn_fast == pytest.approx(5.0, rel=0.2)
    kinds = [e["kind"] for e in events.snapshot()]
    assert "slo_burn" in kinds
    assert metrics.gauges["slo_burning[q]"] == 1.0
    # recovery: all good again long enough to clear both windows
    total = 110.0
    for i in range(12, 24):
        good += 10.0
        total += 10.0
        ring.append(float(i), {"slo_good[q]": good, "slo_total[q]": total})
        mon.evaluate(ring, float(i))
    assert mon.burning is False
    assert "slo_burn_clear" in [e["kind"] for e in events.snapshot()]


# ---------------------------------------------------------------------------
# replay stability: seeded chaos soak, bit-identical counts


async def _chaos_soak_transcript() -> dict:
    """Seeded 4x-overload chaos burst (the test_overload shape): the
    attribution counts that are pure functions of the seeded lifecycle —
    statuses, per-category TRACE counts, SLO good/total — must replay
    bit-identically."""
    q = QueueConfig(name="mm.attr.chaos", rating_threshold=50.0,
                    send_queued_ack=True)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="cpu", pool_capacity=1024),
        batcher=BatcherConfig(max_batch=32, max_wait_ms=2.0),
        overload=OverloadConfig(max_waiting=64, retry_after_ms=250.0),
        chaos=ChaosConfig(seed=99, queues=(q.name,), drop_seqs=(3,),
                          dup_seqs=((100, 1),)),
        observability=ObservabilityConfig(
            trace_ring=1024, snapshot_interval_s=0.0,
            # A huge target makes GOOD = "reached a served outcome" —
            # deterministic under the seeded schedule, unlike wall-clock
            # latency.
            slo_target_ms=1e9),
        debug_invariants=True,
    )
    app = MatchmakingApp(cfg)
    reply = "attr.chaos.replies"
    app.broker.declare_queue(q.name)
    app.broker.declare_queue(reply)
    n = 4 * 64
    for i in range(n):
        app.broker.publish(
            q.name, f'{{"id":"p{i}","rating":{1000 + i * 300}}}'.encode(),
            Properties(reply_to=reply, correlation_id=f"c{i}"))
    await app.start()
    try:
        # Every delivery settles exactly one trace: 256 publishes + the
        # scripted storm copy. Wait on the span count so the read cannot
        # race the final settle.
        for _ in range(400):
            await asyncio.sleep(0.05)
            snap = app.attribution.snapshot(queue=q.name)["queues"]
            if snap.get(q.name, {}).get("spans", 0) >= n + 1:
                break
        entry = app.attribution.snapshot(queue=q.name)["queues"][q.name]
        return {
            "spans": entry["spans"],
            "statuses": entry["statuses"],
            "category_traces": {
                name: cat["traces"]
                for name, cat in entry["categories"].items()
            },
            "slo_good": entry["slo_good"],
            "slo_total": entry["slo_total"],
        }
    finally:
        await app.stop()


@pytest.mark.chaos
def test_attribution_replay_stable_across_chaos_soaks(sanitizer):
    first = asyncio.run(_chaos_soak_transcript())
    second = asyncio.run(_chaos_soak_transcript())
    assert first == second  # bit-identical attribution accounting
    # sanity on the shape: the cap admits 64, the rest shed (+1 storm copy)
    assert first["statuses"]["queued"] == 64
    assert first["statuses"]["shed"] == 4 * 64 - 64 + 1
    assert first["slo_total"] == first["spans"]
    # served outcomes are exactly the queued set under the huge target
    assert first["slo_good"] == first["statuses"]["queued"]
    # the scripted drop leaves a redelivery_wait trace in both runs
    assert first["category_traces"]["redelivery_wait"] == 1


# ---------------------------------------------------------------------------
# satellite: concurrent Prometheus scrape


async def test_concurrent_prom_scrape_valid_and_monotone():
    """/metrics?format=prom scraped WHILE the seeded soak is mid-flight:
    every scrape parses spec-valid, and per-series cumulative histogram
    bucket counts are monotone non-decreasing across consecutive scrapes."""
    import aiohttp

    from test_observability import parse_prom

    port = 19272
    q = QueueConfig(name="mm.scrape", rating_threshold=100.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="cpu"),
        batcher=BatcherConfig(max_batch=64, max_wait_ms=1.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.05),
        debug_invariants=True,
        metrics_port=port,
    )
    app = MatchmakingApp(cfg)
    reply = "scrape.replies"
    app.broker.declare_queue(q.name)
    app.broker.declare_queue(reply)
    await app.start()
    try:
        for i in range(400):
            app.broker.publish(
                q.name, f'{{"id":"s{i}","rating":{1500 + (i % 2)}}}'.encode(),
                Properties(reply_to=reply, correlation_id=f"c{i}"))
        scrapes = []
        async with aiohttp.ClientSession() as s:
            while (app.metrics.counters.get("players_matched") < 400
                   and len(scrapes) < 40):
                async with s.get(
                        f"http://127.0.0.1:{port}/metrics?format=prom") as r:
                    assert r.status == 200
                    scrapes.append(await r.text())
                await asyncio.sleep(0.01)
            # one final scrape after the soak settles
            async with s.get(
                    f"http://127.0.0.1:{port}/metrics?format=prom") as r:
                scrapes.append(await r.text())
        assert len(scrapes) >= 2, "soak finished before any mid-flight scrape"
        prev: dict = {}
        for text in scrapes:
            types, samples = parse_prom(text)  # spec-valid mid-flight
            assert types.get("matchmaking_stage_seconds") == "histogram"
            cur = {
                (name, labels): float(value)
                for name, labels, value in samples
                if name.startswith(("matchmaking_stage_seconds",
                                    "matchmaking_attributed_",
                                    "matchmaking_attribution_seconds",
                                    "matchmaking_device_busy_seconds",
                                    "matchmaking_device_idle_seconds"))
            }
            for key, val in prev.items():
                if key in cur:
                    assert cur[key] >= val - 1e-9, (
                        f"series {key} went backwards: {val} -> {cur[key]}")
            prev = cur
    finally:
        await app.stop()


# ---------------------------------------------------------------------------
# satellite: drain-time broker-backlog handoff


async def _run_backlog_drain(tmp_path) -> None:
    q = QueueConfig(name="mm.backlog", rating_threshold=100.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="cpu"),
        batcher=BatcherConfig(max_batch=8, max_wait_ms=1.0),
        # Partition from the FIRST publish, never scripted-resumed: the
        # consumer stays paused, so every delivery is still buffered on
        # the queue when drain() runs — the exact backlog the old drain
        # dropped on the floor.
        chaos=ChaosConfig(seed=5, queues=(q.name,),
                          partitions=((0, 10_000),), partition_max_s=60.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.0),
    )
    app = MatchmakingApp(cfg)
    reply = "backlog.replies"
    app.broker.declare_queue(q.name)
    app.broker.declare_queue(reply)
    for i in range(6):
        app.broker.publish(
            q.name, f'{{"id":"b{i}","rating":1500}}'.encode(),
            Properties(reply_to=reply, correlation_id=f"c{i}",
                       headers={"x-first-received": "123.456"}))
    await app.start()
    counts = await app.drain(str(tmp_path))
    assert counts[q.name] == 0  # nothing reached the pool
    assert os.path.exists(tmp_path / "_backlog.json")
    kinds = [e["kind"] for e in app.events.snapshot()]
    assert "backlog_checkpointed" in kinds

    # Successor: fresh app + broker, no partition. Restore re-publishes
    # the backlog; the consumers work it off into real matches.
    cfg2 = Config(
        queues=(q,),
        engine=EngineConfig(backend="cpu"),
        batcher=BatcherConfig(max_batch=8, max_wait_ms=1.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.0),
    )
    app2 = MatchmakingApp(cfg2)
    app2.broker.declare_queue(reply)
    await app2.start()
    try:
        await app2.restore_checkpoint(str(tmp_path))
        kinds2 = [e["kind"] for e in app2.events.snapshot()]
        assert "backlog_restored" in kinds2
        await _wait_for(
            lambda: app2.metrics.counters.get("players_matched") >= 6)
        # headers survived the handoff: enqueued_at honors the original
        # x-first-received stamp, so match latency is measured from it
        replies = []
        while True:
            d = await app2.broker.get(reply, timeout=0.05)
            if d is None:
                break
            replies.append(json.loads(d.body))
        matched = [r for r in replies if r["status"] == "matched"]
        assert len(matched) == 6
        assert all(r["latency_ms"] > 1e6 for r in matched), (
            "x-first-received header did not survive the backlog handoff")
    finally:
        await app2.stop()


def test_drain_backlog_handoff_roundtrip(tmp_path, sanitizer):
    asyncio.run(_run_backlog_drain(tmp_path))
