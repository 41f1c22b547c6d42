"""Match-quality & fairness observatory (ISSUE 8).

The acceptance gate: under a seeded soak, the DEVICE-accumulated quality /
wait-at-match histograms reconcile against an exact host recomputation from
the settled responses (counts exact per rating bucket, percentiles within
one histogram bucket), the disparity metric detects a planted per-bucket
bias, the quality SLO burns like a latency SLO, the surfaces
(/debug/quality + the prom families) serve mid-soak, and the quality
counters replay bit-identically across two seeded runs.
"""

import asyncio
import json
import math
import time

import numpy as np
import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    ChaosConfig,
    Config,
    EngineConfig,
    ObservabilityConfig,
    QueueConfig,
)
from matchmaking_tpu.engine.interface import make_engine
from matchmaking_tpu.engine.quality import (
    HostQualityAccum,
    QualitySpec,
    build_report,
    disparity,
)
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.service.contract import RequestColumns
from matchmaking_tpu.testing.drain import settle

pytestmark = pytest.mark.quality


async def _wait_for(cond, tries: int = 400, dt: float = 0.05):
    for _ in range(tries):
        if cond():
            return
        await asyncio.sleep(dt)
    assert cond(), "condition not reached in time"


def _columns(ids, ratings, thresholds, enqueued):
    n = len(ids)
    return RequestColumns(
        ids=np.asarray(ids, object),
        rating=np.asarray(ratings, np.float32),
        rd=np.zeros(n, np.float32),
        region=np.zeros(n, np.int32),
        mode=np.zeros(n, np.int32),
        threshold=np.asarray(thresholds, np.float32),
        enqueued_at=np.asarray(enqueued, np.float64),
        reply_to=np.asarray([""] * n, object),
        correlation_id=np.asarray([""] * n, object),
    )


# ---------------------------------------------------------------------------
# device-vs-host reconciliation


def _engine_cfg(capacity=1024, buckets=(16, 64, 256), **obs):
    return Config(
        queues=(QueueConfig(rating_threshold=100.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=capacity,
                            pool_block=min(256, capacity),
                            batch_buckets=buckets, pipeline_depth=2),
        observability=ObservabilityConfig(**obs),
    )


def test_device_accum_reconciles_with_outcome_recompute(rng):
    """Engine-level exactness: the device-resident accumulator's counts
    equal an exact host recomputation from the very ColumnarOutcome the
    engine returned (counts exact; per-rating-bucket counts exact — the
    rating passes through both sides as the same f32; quality/wait
    percentiles within one histogram bucket)."""
    cfg = _engine_cfg()
    engine = make_engine(cfg, cfg.queues[0])
    assert engine._quality is not None, "plain 1v1 kernels accumulate on device"
    spec = engine._q_spec
    host = HostQualityAccum(spec)
    n_rounds, per = 6, 64
    base = 100.0
    all_m = 0
    #: Driver-side id → rating truth, across rounds — a player queued in
    #: round k can match a round k+3 arrival.
    rating_of: dict[str, float] = {}
    for k in range(n_rounds):
        now = base + 0.25 * k
        ratings = rng.normal(1500.0, 220.0, per).astype(np.float32)
        rating_of.update({f"p{k}_{i}": float(ratings[i])
                          for i in range(per)})
        enq = now - rng.uniform(0.05, 8.0, per)
        engine.search_columns_async(
            _columns([f"p{k}_{i}" for i in range(per)], ratings,
                     np.full(per, np.nan, np.float32), enq), now)
        for _tok, out in engine.flush():
            if not hasattr(out, "m_quality"):
                continue
            all_m += out.n_matches
            # The host recomputation: quality/wait from the outcome the
            # engine returned, ratings from the driver-side truth.
            host.observe(
                rating=np.asarray(
                    [rating_of[i] for i in out.m_id_a.tolist()]
                    + [rating_of[i] for i in out.m_id_b.tolist()],
                    np.float32),
                quality=np.concatenate([out.m_quality, out.m_quality]),
                wait_s=np.concatenate([out.m_wait_a, out.m_wait_b]),
                spread=np.concatenate([out.m_dist, out.m_dist]))
    assert all_m > 30, "soak formed too few matches to reconcile"
    dev = build_report({k: v for k, v in _dev_arrays(engine).items()}, spec)
    ref = build_report(host.arrays, spec)
    # totals + per-rating-bucket counts: EXACT
    assert dev["samples"] == ref["samples"] == 2 * all_m
    assert ([b["count"] for b in dev["buckets"]]
            == [b["count"] for b in ref["buckets"]])
    # means: f32 device accumulation vs f64 host — tight but not bitwise
    assert dev["quality_mean"] == pytest.approx(ref["quality_mean"],
                                                abs=2e-3)
    assert dev["wait_mean_s"] == pytest.approx(ref["wait_mean_s"], rel=2e-3)
    assert dev["spread_mean"] == pytest.approx(ref["spread_mean"], rel=2e-3)
    # percentiles: within one histogram bucket (log buckets factor 2 /
    # linear quality buckets 1/20)
    for key in ("wait_p50_s", "wait_p90_s", "wait_p99_s"):
        assert _within_one_log_bucket(dev[key], ref[key]), (key, dev, ref)
    for key in ("quality_p10", "quality_p50"):
        assert abs(dev[key] - ref[key]) <= 1.0 / spec.n_quality + 1e-9


def _ratings_of(ids, ratings, k):
    return np.asarray([float(ratings[int(str(i).split("_", 1)[1])])
                       for i in ids], np.float32)


def _dev_arrays(engine):
    """Force a fresh device-state readback and return the numpy arrays."""
    engine._quality_force_sync()
    return engine._q_host


def _within_one_log_bucket(a, b):
    if a is None or b is None:
        return a == b
    lo, hi = min(a, b), max(a, b)
    return hi <= lo * 2.0 + 1e-12


def test_quality_report_merges_host_fallback_paths():
    """Team-queue (host fallback) matches land in quality_report too —
    same bucket scheme, merged with the (absent) device state."""
    cfg = Config(
        queues=(QueueConfig(team_size=2, rating_threshold=200.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=256, pool_block=64,
                            batch_buckets=(16,), pipeline_depth=2),
    )
    engine = make_engine(cfg, cfg.queues[0])
    assert engine._quality is None, "team kernels use the host fallback"
    from matchmaking_tpu.service.contract import SearchRequest

    now = 50.0
    reqs = [SearchRequest(id=f"t{i}", rating=1500.0 + i,
                          enqueued_at=now - 1.0) for i in range(4)]
    engine.search_async(reqs, now)
    outs = engine.flush()
    matches = sum(len(o.matches) for _, o in outs if hasattr(o, "matches"))
    assert matches == 1
    rep = engine.quality_report()
    assert rep["samples"] == 4  # one sample per member
    assert rep["quality_mean"] is not None
    assert rep["wait_mean_s"] == pytest.approx(1.0, abs=0.05)


def test_cpu_engine_quality_accum_matches_outcomes():
    cfg = Config(queues=(QueueConfig(rating_threshold=100.0),))
    engine = make_engine(cfg, cfg.queues[0])
    from matchmaking_tpu.service.contract import SearchRequest

    now = 10.0
    out = engine.search(
        [SearchRequest(id="a", rating=1500.0, enqueued_at=now - 2.0),
         SearchRequest(id="b", rating=1530.0, enqueued_at=now - 4.0)], now)
    assert len(out.matches) == 1
    rep = engine.quality_report()
    assert rep["samples"] == 2
    assert rep["quality_mean"] == pytest.approx(out.matches[0].quality,
                                                abs=1e-6)
    assert rep["wait_mean_s"] == pytest.approx(3.0, abs=1e-6)
    assert rep["spread_mean"] == pytest.approx(30.0, abs=1e-4)


# ---------------------------------------------------------------------------
# fairness disparity


def test_disparity_detects_planted_bias(rng):
    """A planted bias — one rating bucket forced onto narrow thresholds
    and long waits — must move the disparity gaps; the unbiased control
    must not."""
    spec = QualitySpec()

    def run(biased: bool):
        cfg = _engine_cfg()
        engine = make_engine(cfg, cfg.queues[0])
        now = 200.0
        # Cohort LOW: ratings laddered 2.8 apart in bucket "-1150";
        # cohort HIGH: near-identical at 1700 ("1675-1800").
        low_r = 1062.0 + 2.8 * np.arange(24)
        high_r = rng.normal(1700.0, 2.0, 24)
        if biased:
            # Thresholds barely above the ladder spacing: formed matches
            # eat most of their limit (quality ≈ 1 - 2.8/3.5), and stale
            # enqueues make their wait-at-match long — the planted
            # "low-rated players get worse, slower matches" bias.
            low_thr = np.full(24, 3.5, np.float32)
            low_enq = np.full(24, now - 20.0)
        else:
            low_thr = np.full(24, 200.0, np.float32)
            low_enq = np.full(24, now - 0.4)
        engine.search_columns_async(
            _columns([f"l{i}" for i in range(24)], low_r, low_thr,
                     low_enq), now)
        engine.search_columns_async(
            _columns([f"h{i}" for i in range(24)], high_r,
                     np.full(24, 200.0, np.float32),
                     np.full(24, now - 0.4)), now)
        engine.flush()
        return engine.quality_report()

    biased = run(True)
    control = run(False)
    assert biased["samples"] >= 24 and control["samples"] >= 24
    d_b, d_c = biased["disparity"], control["disparity"]
    cohorts = {"-1150", "1675-1800"}
    assert d_b["quality_gap"] > 0.15, d_b
    assert d_b["quality_gap_bucket"] in cohorts
    # NB the named bucket is the one FARTHEST from the global mean/p90 —
    # with the biased cohort holding most samples, that can be either side
    # of the gap; the magnitude is the detection signal.
    assert d_b["wait_p90_gap_s"] > 5.0, d_b
    assert d_b["wait_gap_bucket"] in cohorts
    assert d_c["quality_gap"] < 0.1, d_c
    assert d_c["wait_p90_gap_s"] < 1.0, d_c


def test_disparity_ignores_underpopulated_buckets():
    spec = QualitySpec()
    acc = HostQualityAccum(spec)
    # 100 good samples mid-distribution, 2 terrible outliers low-bucket:
    # below min_count the outliers must not dominate the gap.
    acc.observe(np.full(100, 1500.0), np.full(100, 0.9),
                np.full(100, 0.2), np.full(100, 10.0))
    acc.observe(np.full(2, 1000.0), np.full(2, 0.0),
                np.full(2, 500.0), np.full(2, 400.0))
    d = disparity(acc.arrays, spec, min_count=8)
    assert d["quality_gap"] < 0.05
    d_all = disparity(acc.arrays, spec, min_count=1)
    assert d_all["quality_gap"] > 0.5


# ---------------------------------------------------------------------------
# quality SLO burn


def test_quality_slo_monitor_burn_transitions():
    """The quality monitors reuse SloMonitor verbatim — GOOD = matched
    with quality >= target; a run of low-quality matches burns, recovery
    clears."""
    from matchmaking_tpu.engine.quality import QualitySpec
    from matchmaking_tpu.service.quality import QualityLedger
    from matchmaking_tpu.utils.timeseries import SloMonitor, TelemetryRing
    from matchmaking_tpu.utils.trace import EventLog

    ledger = QualityLedger(QualitySpec(), quality_target=0.7)
    ring = TelemetryRing(64)
    events = EventLog(64)
    mon = SloMonitor("q#quality", target_ms=0.7, objective=0.9,
                     fast_window_s=10.0, slow_window_s=30.0,
                     burn_threshold=1.0, events=events,
                     good_key="quality_good[q]",
                     total_key="quality_total[q]", kind="quality")

    def sample(t):
        g, tot = ledger.slo_counts("q")
        ring.append(t, {"quality_good[q]": float(g),
                        "quality_total[q]": float(tot)})
        return mon.evaluate(ring, t)

    t = 1000.0
    sample(t)
    # healthy: quality 0.9 >= target
    for k in range(5):
        ledger.observe("q", np.full(10, 0.9), np.full(10, 0.1))
        t += 1.0
        snap = sample(t)
    assert not mon.burning
    # regression: all matches land below the target
    for k in range(8):
        ledger.observe("q", np.full(10, 0.2), np.full(10, 0.1))
        t += 1.0
        snap = sample(t)
    assert mon.burning
    assert snap["kind"] == "quality"
    assert any(e["kind"] == "slo_burn" for e in events.snapshot())
    # recovery — the windows age the bad samples out
    for k in range(40):
        ledger.observe("q", np.full(10, 0.95), np.full(10, 0.1))
        t += 1.0
        sample(t)
    assert not mon.burning
    assert any(e["kind"] == "slo_burn_clear" for e in events.snapshot())


# ---------------------------------------------------------------------------
# the service soak: wire contract + HTTP surfaces + prom families


def _soak_cfg(q, port=0, **obs_extra):
    return Config(
        queues=(q,),
        engine=EngineConfig(backend="tpu", pool_capacity=1024,
                            pool_block=256, batch_buckets=(16, 64, 256),
                            pipeline_depth=2),
        batcher=BatcherConfig(max_batch=256, max_wait_ms=2.0),
        observability=ObservabilityConfig(
            snapshot_interval_s=0.0, trace_ring=1024,
            quality_report_every=2, quality_slo_target=0.5,
            **obs_extra),
        metrics_port=port,
        debug_invariants=True,
    )


async def _publish_soak(app, q, reply, n=400, seed=77, sigma=200.0):
    rng = np.random.default_rng(seed)
    ratings = rng.normal(1500.0, sigma, n)
    waits = np.exp(rng.uniform(np.log(5e-3), np.log(10.0), size=n))
    now = time.time()
    for i in range(n):
        app.broker.publish(
            q.name,
            f'{{"id":"s{i}","rating":{ratings[i]:.2f}}}'.encode(),
            Properties(reply_to=reply, correlation_id=f"c{i}",
                       headers={"x-first-received":
                                f"{now - waits[i]:.6f}"}))
    return {f"s{i}": float(np.float32(round(ratings[i], 2)))
            for i in range(n)}


@pytest.mark.asyncio
async def test_service_soak_waited_ms_and_device_host_reconciliation(
        sanitizer):
    """The acceptance soak, service-level: a seeded 400-player soak on the
    device path; every matched response carries quality + waited_ms (and
    waited <= latency); the device-accumulated histograms reconcile with
    the host recomputation built from those settled responses (counts
    exact per rating bucket; percentiles within one bucket); settled
    matched TRACES carry the same quality/waited stamps."""
    q = QueueConfig(name="mm.qual", rating_threshold=150.0,
                    send_queued_ack=False)
    app = MatchmakingApp(_soak_cfg(q))
    reply = "qual.replies"
    app.broker.declare_queue(reply)
    matched: list[dict] = []

    async def on_reply(d):
        body = json.loads(d.body)
        if body.get("status") == "matched":
            matched.append(body)

    app.broker.basic_consume(reply, on_reply, prefetch=10_000)
    await app.start()
    ratings = await _publish_soak(app, q, reply)
    rt = app.runtime(q.name)
    assert await settle(app, rt, q.name, reply_queues=(reply,))
    try:
        assert len(matched) >= 100, "soak formed too few matches"
        # Wire contract: waited_ms on every matched body, <= latency_ms.
        for body in matched:
            assert "waited_ms" in body, body
            assert body["waited_ms"] <= body["latency_ms"] + 1e-6, body
        # Host recomputation from the settled responses (each matched
        # player's reply carries the pair quality + its own engine wait).
        spec = rt.engine._q_spec
        host = HostQualityAccum(spec)
        host.observe(
            rating=[ratings[b["player_id"]] for b in matched],
            quality=[b["match"]["quality"] for b in matched],
            wait_s=[b["waited_ms"] / 1e3 for b in matched],
            spread=0.0)
        async with rt._engine_lock:
            await asyncio.to_thread(rt.engine.flush)
        dev = rt.engine.quality_report()
        ref = build_report(host.arrays, spec)
        assert dev["samples"] == ref["samples"] == len(matched)
        assert ([b["count"] for b in dev["buckets"]]
                == [b["count"] for b in ref["buckets"]]), (dev, ref)
        assert dev["quality_mean"] == pytest.approx(ref["quality_mean"],
                                                    abs=2e-3)
        assert _within_one_log_bucket(dev["wait_p50_s"], ref["wait_p50_s"])
        assert _within_one_log_bucket(dev["wait_p99_s"], ref["wait_p99_s"])
        assert abs(dev["quality_p50"] - ref["quality_p50"]) \
            <= 1.0 / spec.n_quality + 1e-9
        # Settled matched traces carry the same stamps.
        snap = app.recorder.snapshot(queue=q.name, limit=1024)
        stamped = [t for t in snap["queues"][q.name]["recent"]
                   if t["status"] == "matched" and "quality" in t]
        assert stamped, "matched traces must carry quality/waited_ms"
        by_id = {b["player_id"]: b for b in matched}
        for t in stamped:
            body = by_id.get(t["player_id"])
            if body is None:
                continue
            assert t["quality"] == pytest.approx(body["match"]["quality"],
                                                 abs=1e-5)
            assert t["waited_ms"] == pytest.approx(body["waited_ms"],
                                                   abs=0.01)
        # Service ledger saw every matched player.
        ledger = app.quality.snapshot(queue=q.name)["queues"][q.name]
        assert ledger["matched_players"] == len(matched)
    finally:
        await app.stop()


@pytest.mark.asyncio
async def test_debug_quality_and_prom_families_over_http(sanitizer):
    """/debug/quality + the prom families serve mid-soak: the quality
    histogram families are present and spec-valid (one TYPE per family),
    /healthz carries the quality-SLO block, and the engine block exposes
    per-rating-bucket rows + disparity."""
    import aiohttp

    port = 19361
    q = QueueConfig(name="mm.qhttp", rating_threshold=150.0,
                    send_queued_ack=False)
    app = MatchmakingApp(_soak_cfg(q, port=port))
    reply = "qhttp.replies"
    app.broker.declare_queue(reply)
    n_matched = [0]

    async def on_reply(d):
        if b'"status":"matched"' in bytes(d.body):
            n_matched[0] += 1

    app.broker.basic_consume(reply, on_reply, prefetch=10_000)
    await app.start()
    await _publish_soak(app, q, reply, n=300, seed=5)
    await _wait_for(lambda: n_matched[0] >= 50)
    rt = app.runtime(q.name)
    try:
        app.sample_telemetry()
        async with aiohttp.ClientSession() as s:
            async with s.get(f"http://127.0.0.1:{port}/debug/quality") as r:
                assert r.status == 200
                body = json.loads(await r.text())
            async with s.get(
                    f"http://127.0.0.1:{port}/metrics?format=prom") as r:
                assert r.status == 200
                prom = await r.text()
            async with s.get(f"http://127.0.0.1:{port}/healthz") as r:
                hz = json.loads(await r.text())
        entry = body["queues"][q.name]
        assert entry["service"]["matched_players"] >= 50
        assert "tiers" in entry["service"]
        assert "disparity" in entry
        assert entry["slo_quality"]["kind"] == "quality"
        # engine block may lag by the readback cadence but must be shaped
        assert "engine" in entry and "buckets" in entry["engine"]
        # ledger-side families serve mid-soak, one TYPE line each
        for family in ("matchmaking_match_quality",
                       "matchmaking_quality_disparity"):
            type_lines = [ln for ln in prom.splitlines()
                          if ln.startswith(f"# TYPE {family} ")]
            assert len(type_lines) == 1, family
        assert 'matchmaking_match_quality_bucket{queue="mm.qhttp"' in prom
        # engine-side families appear once the device snapshot has been
        # read back — force it (flush) and re-scrape.
        async with rt._engine_lock:
            await asyncio.to_thread(rt.engine.flush)
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{port}/metrics?format=prom") as r:
                prom2 = await r.text()
        assert "matchmaking_wait_at_match_seconds_bucket" in prom2
        assert "# TYPE matchmaking_quality_mean gauge" in prom2
        assert "slo_quality" in hz["queues"][q.name]
    finally:
        await app.stop()


# ---------------------------------------------------------------------------
# replay stability


async def _chaos_quality_run() -> tuple[dict, dict]:
    q = QueueConfig(name="mm.qrep", rating_threshold=150.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        engine=EngineConfig(backend="tpu", pool_capacity=1024,
                            pool_block=256, batch_buckets=(16, 64, 256),
                            pipeline_depth=2),
        batcher=BatcherConfig(max_batch=256, max_wait_ms=2.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.0,
                                          quality_report_every=4),
        chaos=ChaosConfig(seed=9, queues=(q.name,),
                          drop_seqs=(3, 17), dup_seqs=((5, 2), (40, 1))),
        debug_invariants=True,
    )
    app = MatchmakingApp(cfg)
    reply = "qrep.replies"
    app.broker.declare_queue(reply)
    matched = [0]

    async def on_reply(d):
        if b'"status":"matched"' in bytes(d.body):
            matched[0] += 1

    app.broker.basic_consume(reply, on_reply, prefetch=10_000)
    await app.start()
    rng = np.random.default_rng(31)
    ratings = rng.normal(1500.0, 200.0, 300)
    now = time.time()
    for i in range(300):
        app.broker.publish(
            q.name,
            f'{{"id":"r{i}","rating":{ratings[i]:.2f}}}'.encode(),
            Properties(reply_to=reply, correlation_id=f"c{i}",
                       headers={"x-first-received": f"{now - 1.0:.6f}"}))
    rt = app.runtime(q.name)
    assert await settle(app, rt, q.name, reply_queues=(reply,))
    async with rt._engine_lock:
        await asyncio.to_thread(rt.engine.flush)
    rep = rt.engine.quality_report()
    ledger = app.quality.snapshot(queue=q.name)
    await app.stop()
    return rep, ledger


@pytest.mark.chaos
def test_quality_counters_replay_stable_across_chaos_runs(sanitizer):
    """Two identical seeded-chaos runs produce bit-identical quality
    COUNTERS: total samples, the per-rating-bucket counts, and the full
    quality histogram (quality is a pure function of pairing + thresholds
    with widening off — wall-clock-shaped wait durations are excluded on
    purpose)."""
    rep1, led1 = asyncio.run(_chaos_quality_run())
    rep2, led2 = asyncio.run(_chaos_quality_run())
    assert rep1["samples"] == rep2["samples"] > 0
    assert ([b["count"] for b in rep1["buckets"]]
            == [b["count"] for b in rep2["buckets"]])
    assert rep1["quality_mean"] == pytest.approx(rep2["quality_mean"],
                                                 abs=1e-6)
    assert rep1["quality_p50"] == rep2["quality_p50"]
    q1 = led1["queues"]["mm.qrep"]
    q2 = led2["queues"]["mm.qrep"]
    assert q1["matched_players"] == q2["matched_players"]
    assert (q1["tiers"]["0"]["quality_hist"]
            == q2["tiers"]["0"]["quality_hist"])


# ---------------------------------------------------------------------------
# loadgen + bench_diff satellites


@pytest.mark.asyncio
async def test_loadgen_quality_accounting(sanitizer):
    from matchmaking_tpu.service.loadgen import offered_load

    q = QueueConfig(name="mm.qload", rating_threshold=200.0,
                    send_queued_ack=False)
    cfg = Config(
        queues=(q,),
        # warm_start: the measured second must not be eaten by a cold
        # first-window compile (the drain poll can exit before replies).
        engine=EngineConfig(backend="tpu", pool_capacity=512,
                            pool_block=128, batch_buckets=(16, 64),
                            pipeline_depth=2, warm_start=True),
        batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.0),
    )
    app = MatchmakingApp(cfg)
    await app.start()
    try:
        res = await offered_load(app, q.name, rate=200.0, duration=1.0,
                                 seed=3, quality_stats=True,
                                 rating_sigma=120.0)
        qs = res["quality"]
        assert qs["matched"] > 0
        assert 0.0 <= qs["quality_mean"] <= 1.0
        assert qs["waited_ms_p99"] <= qs["latency_ms_p99"] + 1e-6
        assert qs["wait_gap_ms_mean"] >= 0.0
    finally:
        await app.stop()


def test_bench_diff_detects_regressions(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(os.path.dirname(__file__), "..",
                                   "scripts", "bench_diff.py"))
    bd = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bd)

    base = {"value": 1000.0, "e2e_p99_ms": 100.0,
            "e2e_frontier": [{"threshold": 50.0, "quality_mean": 0.8,
                              "wait_at_match_ms_p99": 200.0,
                              "quality_disparity": 0.1}]}
    same = json.loads(json.dumps(base))
    rows = bd.diff(base, same, threshold=0.10)
    assert rows and not any(r["regressed"] for r in rows)
    # throughput down 20% → regression; p99 down (improvement) → not
    worse = dict(base, value=800.0, e2e_p99_ms=50.0)
    rows = bd.diff(base, worse, threshold=0.10)
    flagged = {r["metric"] for r in rows if r["regressed"]}
    assert flagged == {"value"}
    # frontier quality regression caught by threshold-matched row
    worse_f = json.loads(json.dumps(base))
    worse_f["e2e_frontier"][0]["quality_mean"] = 0.6
    rows = bd.diff(base, worse_f, threshold=0.10)
    assert any(r["regressed"] and "quality_mean" in r["metric"]
               for r in rows)
    # zero-baseline disparity (a perfectly fair committed round) must
    # still gate an absolute worsening — skipping would disable the
    # fairness gate from a clean baseline.
    fair = json.loads(json.dumps(base))
    fair["e2e_frontier"][0]["quality_disparity"] = 0.0
    unfair = json.loads(json.dumps(fair))
    unfair["e2e_frontier"][0]["quality_disparity"] = 0.5
    rows = bd.diff(fair, unfair, threshold=0.10)
    assert any(r["regressed"] and "quality_disparity" in r["metric"]
               for r in rows)
    assert not any(r["regressed"]
                   for r in bd.diff(fair, json.loads(json.dumps(fair)),
                                    threshold=0.10))
    # missing metrics on either side are skipped, not failed
    rows = bd.diff({"value": 10.0}, {"e2e_p99_ms": 5.0}, threshold=0.1)
    assert rows == []
    # file loading: driver artifact shape ({"parsed": {...}})
    p = tmp_path / "wrapped.json"
    p.write_text(json.dumps({"parsed": base, "tail": "..."}))
    assert bd.load_result(str(p))["value"] == 1000.0


def test_waited_ms_wire_roundtrip():
    from matchmaking_tpu.service.contract import (
        MatchResult,
        SearchResponse,
        decode_response,
        encode_response,
    )

    resp = SearchResponse(
        status="matched", player_id="p1",
        match=MatchResult("m1", ("p1", "p2"), (("p1",), ("p2",)),
                          quality=0.75),
        latency_ms=120.0, waited_ms=80.5)
    body = encode_response(resp)
    back = decode_response(body)
    assert back.waited_ms == pytest.approx(80.5, abs=1e-3)
    assert back.match.quality == pytest.approx(0.75)
    # Native batch-encoded bodies carry waited_ms directly (ISSUE 9: the
    # PR 8 post-encode splice helpers are gone — the C encoder emits the
    # byte-identical contract body).
    from matchmaking_tpu.native import codec

    if codec.available():
        bodies = codec.encode_matched_batch(
            ["p1"], ["p2"], ["m1"], np.array([120.0]), np.array([120.0]),
            np.array([0.75]), np.array([42.125]), np.array([42.125]))
        assert bodies is not None
        native = decode_response(bodies[0])
        assert native.waited_ms == pytest.approx(42.125, abs=1e-3)
    # non-matched responses don't carry the key
    shed = encode_response(SearchResponse(status="shed", player_id=""))
    assert b"waited_ms" not in shed


def test_quality_counters_survive_chaos_crash_revive(sanitizer):
    """ISSUE 9 satellite (PR 8 follow-up): engine quality accumulators
    survive a crash revive — a scripted chaos device-step fault nacks its
    window and rebuilds the engine from the mirror, and /debug/quality's
    sample counters keep COUNTING UP across the swap instead of resetting
    (checkpointed via Engine.quality_checkpoint/quality_restore)."""
    async def run():
        q = QueueConfig(name="mm.qrev", rating_threshold=100.0,
                        send_queued_ack=False)
        cfg = Config(
            queues=(q,),
            engine=EngineConfig(backend="tpu", pool_capacity=64,
                                pool_block=32, batch_buckets=(16,),
                                pipeline_depth=2, breaker_threshold=0),
            batcher=BatcherConfig(max_batch=16, max_wait_ms=2.0),
            # Step 0: the first pair's window — matches cleanly. Step 1:
            # the second pair's window — scripted device fault, nack +
            # revive; the redelivery matches on the fresh engine (step 2).
            chaos=ChaosConfig(seed=3, queues=(q.name,), fail_steps=(1,)),
        )
        app = MatchmakingApp(cfg)
        reply = "qrev.replies"
        app.broker.declare_queue(q.name)
        app.broker.declare_queue(reply)
        await app.start()
        rt = app.runtime(q.name)
        try:
            for i in range(2):
                app.broker.publish(
                    q.name, f'{{"id":"a{i}","rating":1500}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"a{i}"))
            await _wait_for(lambda: app.metrics.counters.get(
                "players_matched") >= 2)

            async def samples() -> int:
                # The device-side accumulator snapshot is async (refreshed
                # every quality_report_every windows) — force the readback
                # under the engine lock so the report shows exact totals.
                async with rt._engine_lock:
                    await asyncio.to_thread(rt.engine._quality_force_sync)
                return rt.engine.quality_report()["samples"]

            assert await samples() == 2
            # Second pair: its window hits the scripted step fault.
            for i in range(2):
                app.broker.publish(
                    q.name, f'{{"id":"b{i}","rating":1520}}'.encode(),
                    Properties(reply_to=reply, correlation_id=f"b{i}"))
            await _wait_for(lambda: app.metrics.counters.get(
                "players_matched") >= 4)
            assert app.metrics.counters.get("engine_crashes") >= 1
            revives = [e for e in app.events.snapshot()
                       if e["kind"] == "engine_revive"]
            assert revives, "scripted fault must have revived the engine"
            # THE regression: monotone across the revive — the fresh
            # engine reports the dead engine's samples plus its own.
            assert await samples() == 4
        finally:
            await app.stop()

    asyncio.run(run())


def test_quality_checkpoint_restore_units():
    """Engine.quality_checkpoint/quality_restore: the CPU engine round-
    trips its accumulator arrays, and restore MERGES (adds) rather than
    replaces."""
    cfg = Config(engine=EngineConfig(backend="cpu"))
    q = QueueConfig(name="u", rating_threshold=50.0)
    e1 = make_engine(cfg, q)
    e1.quality_accum.observe([1500.0, 1520.0], 0.9, [1.0, 2.0], 20.0)
    snap = e1.quality_checkpoint()
    assert snap is not None and int(snap["count"].sum()) == 2
    e2 = make_engine(cfg, q)
    e2.quality_accum.observe([1400.0], 0.5, [0.5], 10.0)
    e2.quality_restore(snap)
    assert e2.quality_report()["samples"] == 3
    # Mutating the checkpoint after the fact must not alias e1's arrays.
    snap["count"][:] = 99
    assert e1.quality_report()["samples"] == 2
    e2.quality_restore(None)  # tolerated no-op
    assert e2.quality_report()["samples"] == 3


@pytest.mark.bucketed
def test_disparity_no_regression_under_hierarchical_formation():
    """ISSUE 14 fairness gate: hierarchical (bucketed) formation must not
    move the per-rating-bucket quality/wait accounting — the bucketed
    engine's matches are bit-exact vs flat, so its quality report
    (conditional means, disparity gaps, per-bucket counts) must be
    IDENTICAL, not merely within an envelope."""
    from matchmaking_tpu.service.contract import SearchRequest

    def run(bucketed: bool) -> dict:
        ec = EngineConfig(backend="tpu", pool_capacity=4096, pool_block=256,
                          batch_buckets=(16, 64, 256),
                          band_spec="gaussian:1500:300",
                          bucketed=bucketed,
                          prune_window_blocks=8 if bucketed else 0)
        cfg = Config(engine=ec,
                     queues=(QueueConfig(rating_threshold=100.0,
                                         widen_per_sec=2.0,
                                         max_threshold=200.0),))
        engine = make_engine(cfg, cfg.queues[0])
        local = np.random.default_rng(21)
        for w in range(5):
            reqs = [SearchRequest(id=f"w{w}_{i}",
                                  rating=float(local.normal(1500, 300)),
                                  enqueued_at=100.0 + w)
                    for i in range(150)]
            engine.search(reqs, now=100.0 + w)
        return engine.quality_report()

    flat, hier = run(False), run(True)
    assert hier["samples"] == flat["samples"] > 100
    assert hier["disparity"] == flat["disparity"]
    assert hier["buckets"] == flat["buckets"]
