"""Incident-forensics suite (ISSUE 18).

- **EventSpine**: one process-wide monotone seq under concurrent
  worker-thread emitters (ring order IS seq order), the deterministic
  ``transcript()`` projection (clock fields and timing-valued refs
  dropped, counter refs kept) with a stable digest, and observers
  running OUTSIDE the spine lock (a slow capture must not block other
  threads' emissions).
- **EventLog**: appends are spine-stamped (seq + mono_ns + component)
  and ``snapshot()`` orders by seq, not wall clock — two events in the
  same millisecond render in causal order.
- **IncidentRecorder**: trigger rules fire a bundle, the per-class rate
  limiter and the reentrancy guard COUNT their drops, and
  ``validate_bundle`` accepts every captured bundle while rejecting the
  schema mutations an operator could plausibly produce by hand.
- **HTTP surfaces mid-failover**: after a real crash → lease-expiry
  takeover → successor adoption, concurrent ``/debug/incidents`` +
  ``/metrics?format=prom`` scrapes stay spec-valid (parse_prom) while
  the listing's trigger seqs stay monotone and the per-id fetch returns
  a bundle that validates.
- **Drain non-interference**: a capture fired mid-load neither blocks
  the drain (``fully_drained`` settles) nor leaks a settlement credit
  (``debug_invariants`` twin active, every published player matches).
- **Offline analyzer**: ``scripts/postmortem.py`` reconstructs the
  takeover root chain (lease expiry → epoch bump → replay window →
  takeover → burn → burn clear) from a synthetic bundle alone, and
  ``scripts/journal_dump.py --lsn-range`` slices exactly the WAL window
  a bundle's journal watermark names.
"""

import asyncio
import importlib.util
import io
import json
import os
import threading
import time

import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    Config,
    DurabilityConfig,
    EngineConfig,
    ForensicsConfig,
    QueueConfig,
    ReplicationConfig,
)
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.service.replication import ReplicationHub
from matchmaking_tpu.testing.drain import settle
from matchmaking_tpu.utils.forensics import (
    DETERMINISTIC_KINDS,
    INCIDENT_SCHEMA,
    EventSpine,
    component_of,
    validate_bundle,
)
from matchmaking_tpu.utils.trace import EventLog

pytestmark = pytest.mark.forensics

Q = "matchmaking.search"
_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")


def _load_script(name: str):
    spec = importlib.util.spec_from_file_location(
        f"mm_script_{name}", os.path.join(_SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(**kw) -> Config:
    base = dict(
        queues=(QueueConfig(rating_threshold=50.0, dedup_ttl_s=600.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=256, pool_block=64,
                            batch_buckets=(8, 32), top_k=4),
        batcher=BatcherConfig(max_batch=8, max_wait_ms=5.0),
    )
    base.update(kw)
    return Config(**base)


def _publish(app, pid, rating, reply_q):
    app.broker.publish(
        Q, json.dumps({"id": pid, "rating": rating}).encode(),
        Properties(reply_to=reply_q, correlation_id=pid))


# ---- event spine ------------------------------------------------------------


def test_spine_seq_monotone_under_concurrent_threads():
    """Four worker threads stamping concurrently: every seq is unique,
    the ring's iteration order is seq order (the draw + append happen as
    one step under the lock), and the window() slice stays sorted."""
    spine = EventSpine(ring=4096)
    n_threads, per_thread = 4, 200
    start = threading.Barrier(n_threads)

    def emit(tid: int) -> None:
        start.wait()
        for i in range(per_thread):
            spine.stamp("engine_crash", queue=f"q{tid}", detail=str(i))

    threads = [threading.Thread(target=emit, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = spine.window()
    assert len(rows) == n_threads * per_thread
    seqs = [ev["seq"] for ev in rows]
    assert seqs == sorted(seqs)
    assert len(set(seqs)) == len(seqs)
    assert seqs[0] == 1 and seqs[-1] == n_threads * per_thread
    # Ring order IS seq order even before window() sorts.
    raw = [ev["seq"] for ev in spine._ring]
    assert raw == sorted(raw)


def test_spine_transcript_drops_clocks_and_keeps_counter_refs():
    """The deterministic projection: clock fields (mono_ns, wall) and
    timing-valued refs (rto_ms) never reach the transcript; counter refs
    (epoch, players) do. Two spines stamped with the same script but
    different wall clocks digest identically."""
    def script(spine: EventSpine) -> None:
        spine.stamp("lease_expired", Q, refs={"epoch": 1})
        spine.stamp("epoch_bump", Q, refs={"epoch": 2, "prev_epoch": 1})
        spine.stamp("replay_window", Q, refs={"epoch": 2, "players": 6,
                                              "records": 96})
        spine.stamp("failover_takeover", Q,
                    refs={"epoch": 2, "players": 6, "rto_ms": 7.31})
        # Non-deterministic kind: on the spine, out of the transcript.
        spine.stamp("slo_burn", Q, refs={"burn_fast": 100.0})

    a, b = EventSpine(), EventSpine()
    script(a)
    time.sleep(0.01)  # different wall/mono values on b, same script
    script(b)
    ta = a.transcript()
    assert [r["kind"] for r in ta] == ["lease_expired", "epoch_bump",
                                      "replay_window", "failover_takeover"]
    takeover = ta[-1]
    assert takeover["refs"] == {"epoch": 2, "players": 6}  # rto_ms dropped
    assert all("mono_ns" not in r and "wall" not in r and "seq" not in r
               for r in ta)
    assert a.digest() == b.digest()
    assert set(DETERMINISTIC_KINDS) >= {r["kind"] for r in ta}


def test_spine_observers_run_outside_the_lock():
    """A slow observer (a capture in flight) must not hold the spine
    lock: another thread's stamp during the observer's sleep returns
    promptly instead of queueing behind the capture."""
    spine = EventSpine()
    in_observer = threading.Event()
    release = threading.Event()

    def slow_observer(ev):
        if ev["kind"] == "breaker_trip":
            in_observer.set()
            release.wait(timeout=5.0)

    spine.subscribe(slow_observer)
    t = threading.Thread(target=spine.stamp, args=("breaker_trip", Q))
    t.start()
    assert in_observer.wait(timeout=5.0)
    t0 = time.monotonic()
    spine.stamp("engine_revive", Q)  # must not block on the observer
    elapsed = time.monotonic() - t0
    release.set()
    t.join()
    assert elapsed < 1.0, (
        f"stamp blocked {elapsed:.3f}s behind a slow observer — the "
        f"capture is holding the spine lock")
    assert [ev["seq"] for ev in spine.window()] == [1, 2]


def test_event_log_snapshot_orders_by_seq_not_wall_clock():
    """Rows appended with DESCENDING wall stamps still snapshot in seq
    (causal) order, and every row carries seq/mono_ns/component."""
    log = EventLog(64)
    now = time.time()
    # Stamp with explicit wall going backwards (clock step / NTP skew).
    log.spine.stamp("breaker_trip", Q, wall=now + 5.0)
    log._events.append(log.spine._ring[-1])
    log.append("engine_revive", Q)
    rows = log.spine.window()
    assert [r["seq"] for r in rows] == [1, 2]
    assert rows[0]["wall"] > rows[1]["wall"]  # wall order is inverted...
    snap = EventLog(64)
    a = snap.append("breaker_trip", Q)
    b = snap.append("engine_revive", Q, component="engine")
    listed = snap.snapshot()
    assert [r["seq"] for r in listed] == [a["seq"], b["seq"]]
    assert listed[0]["component"] == "service"  # component_of fallback
    assert listed[1]["component"] == "engine"   # explicit wins
    assert all("mono_ns" in r for r in listed)


def test_component_table_routes_known_kinds():
    assert component_of("journal_compacted") == "durability"
    assert component_of("failover_takeover") == "replication"
    assert component_of("autotune_applied") == "control"
    assert component_of("slo_burn") == "slo"
    assert component_of("breaker_trip") == "service"
    assert component_of("spec_invalidate") == "engine"
    assert component_of("totally_unknown") == "service"


# ---- recorder: triggers, rate limit, reentrancy -----------------------------


async def test_recorder_trigger_rate_limit_and_reentrancy():
    """One breaker_trip fires a capture; a second within min_interval_s
    is dropped AND counted; a trigger observed while a capture is in
    flight (self-amplification) is dropped AND counted; every captured
    bundle validates clean."""
    app = MatchmakingApp(_cfg(
        forensics=ForensicsConfig(min_interval_s=60.0)))
    await app.start()
    try:
        ev = app.events.append("breaker_trip", Q, "fixture trip",
                               refs={"crashes": 2})
        assert app.incidents.captured == 1
        assert app.incidents.by_class == {"breaker_trip": 1}
        assert app.incidents.dropped == 0
        bundle = app.incidents.get("inc-000001")
        assert bundle is not None
        assert bundle["trigger"]["seq"] == ev["seq"]
        assert bundle["schema"] == INCIDENT_SCHEMA
        assert validate_bundle(bundle) == []
        assert app.metrics.counters.get("incidents_captured") == 1

        # Rate limit: same class inside min_interval_s → counted drop.
        app.events.append("breaker_trip", Q, "storm repeat")
        assert app.incidents.captured == 1
        assert app.incidents.dropped == 1
        assert app.metrics.counters.get("incidents_dropped") == 1

        # Reentrancy: a trigger while a capture is in flight is the
        # self-amplification case — dropped and counted, never recursed.
        app.incidents._capturing = True
        try:
            app.events.append("crash_recovered", Q, "mid-capture")
        finally:
            app.incidents._capturing = False
        assert app.incidents.captured == 1
        assert app.incidents.dropped == 2

        # A different class is NOT rate-limited by breaker_trip's stamp.
        app.events.append("crash_recovered", Q, "other class")
        assert app.incidents.by_class.get("crash_recovery") == 1
        assert app.incidents.dropped == 2
    finally:
        await app.stop()


async def test_capture_persist_retention_and_snapshot(tmp_path):
    """Bundles persist under incident_dir with the retention cap pruning
    oldest-first; snapshot() reports counters + capture p99."""
    inc_dir = str(tmp_path / "incidents")
    app = MatchmakingApp(_cfg(
        forensics=ForensicsConfig(incident_dir=inc_dir, min_interval_s=0.0,
                                  retention_files=2)))
    await app.start()
    try:
        for i in range(3):
            app.events.append("breaker_trip", Q, f"trip {i}")
        files = sorted(os.listdir(inc_dir))
        assert files == ["incident_inc-000002_breaker_trip.json",
                         "incident_inc-000003_breaker_trip.json"]
        with open(os.path.join(inc_dir, files[-1]), encoding="utf-8") as f:
            assert validate_bundle(json.load(f)) == []
        snap = app.incidents.snapshot()
        assert snap["captured"] == 3 and snap["dropped"] == 0
        assert snap["by_class"] == {"breaker_trip": 3}
        assert snap["capture_ms_p99"] is not None
        assert [b["id"] for b in snap["incidents"]] == [
            "inc-000001", "inc-000002", "inc-000003"]
    finally:
        await app.stop()


def test_validate_bundle_rejects_schema_mutations():
    ok = {
        "schema": INCIDENT_SCHEMA, "id": "inc-000001",
        "trigger": {"class": "failover", "seq": 5, "kind":
                    "failover_takeover", "queue": Q, "detail": "", "refs": {}},
        "captured_wall": 1.0, "capture_ms": 0.5,
        "spine": [{"seq": 1, "mono_ns": 10, "wall": 1.0,
                   "component": "replication", "queue": Q,
                   "kind": "lease_expired", "refs": {}},
                  {"seq": 5, "mono_ns": 20, "wall": 1.0,
                   "component": "replication", "queue": Q,
                   "kind": "failover_takeover", "refs": {}}],
        "spine_digest": "x", "telemetry": {}, "replication": {},
        "journal": {}, "counters": {},
    }
    assert validate_bundle(ok) == []
    assert validate_bundle([]) != []
    assert any("schema" in p for p in validate_bundle(
        {**ok, "schema": "mm.incident/999"}))
    missing = dict(ok)
    del missing["spine_digest"]
    assert any("spine_digest" in p for p in validate_bundle(missing))
    assert any("trigger class" in p for p in validate_bundle(
        {**ok, "trigger": {**ok["trigger"], "class": "nope"}}))
    broken = {**ok, "spine": list(reversed(ok["spine"]))}
    assert any("strictly increasing" in p for p in validate_bundle(broken))
    assert any("capture_ms" in p for p in validate_bundle(
        {**ok, "capture_ms": "fast"}))


# ---- HTTP surfaces mid-failover ---------------------------------------------


async def test_debug_incidents_and_prom_concurrent_after_failover(tmp_path):
    """Crash → lease-expiry takeover → successor adoption, then
    CONCURRENT /debug/incidents + /metrics?format=prom scrapes while
    load flows: prom stays spec-valid with the incident families
    present, the incident listing's trigger seqs are monotone, and the
    per-id fetch returns a bundle that validates."""
    import aiohttp

    from test_observability import parse_prom

    port = 19271
    hub = ReplicationHub(lease_s=0.4)
    app = MatchmakingApp(_cfg(
        durability=DurabilityConfig(journal_dir=str(tmp_path / "h0"),
                                    fsync="window"),
        replication=ReplicationConfig(role="primary", owner="hostA")),
        replication_hub=hub)
    reply = "forensics.replies"
    app.broker.declare_queue(reply)
    app.broker.basic_consume(reply, lambda d: None, prefetch=1_000_000)
    await app.start()
    rt = app.runtime(Q)
    standby = hub.standby(Q, owner="hostB")
    for i in range(4):
        _publish(app, f"fp{i}", 1500.0 + (i // 2) * 400.0, reply)
    assert await settle(app, rt, Q, matched_at_least=4, standby=standby)
    await app.crash()
    standby.takeover(time.monotonic() + 0.4 + 0.05)

    app2 = MatchmakingApp(_cfg(
        durability=DurabilityConfig(journal_dir=str(tmp_path / "h1"),
                                    fsync="window"),
        replication=ReplicationConfig(role="primary", owner="hostB"),
        metrics_port=port),
        replication_hub=hub)
    app2.broker.declare_queue(reply)
    app2.broker.basic_consume(reply, lambda d: None, prefetch=1_000_000)
    await app2.start()
    try:
        assert app2.incidents.by_class.get("failover") == 1
        for i in range(6):
            _publish(app2, f"fq{i}", 2500.0 + (i // 2) * 400.0, reply)

        async def scrape(session, path):
            async with session.get(
                    f"http://127.0.0.1:{port}{path}") as r:
                return r.status, await r.text()

        async with aiohttp.ClientSession() as s:
            results = await asyncio.gather(*(
                scrape(s, p) for p in
                ("/debug/incidents", "/metrics?format=prom") * 4))
            for (inc_status, inc_text), (prom_status, prom_text) in zip(
                    results[0::2], results[1::2]):
                assert inc_status == 200 and prom_status == 200
                body = json.loads(inc_text)
                seqs = [b["seq"] for b in body["incidents"]]
                assert seqs == sorted(seqs)
                assert any(b["class"] == "failover"
                           for b in body["incidents"])
                types, _ = parse_prom(prom_text)
                assert "matchmaking_incidents_captured" in types
                assert "matchmaking_incidents_by_class" in types
                assert "matchmaking_incident_capture_p99_ms" in types
            inc_id = json.loads(results[0][1])["incidents"][0]["id"]
            status, text = await scrape(s, f"/debug/incidents?id={inc_id}")
            assert status == 200
            assert validate_bundle(json.loads(text)) == []
            status, _ = await scrape(s, "/debug/incidents?id=inc-999999")
            assert status == 404
        assert await settle(app2, rt2 := app2.runtime(Q), Q,
                            matched_at_least=6, replication=False)
        spine_rows = app2.spine.window()
        assert [r["seq"] for r in spine_rows] == sorted(
            r["seq"] for r in spine_rows)
        del rt2
    finally:
        await app2.stop()


async def test_capture_during_drain_blocks_nothing_leaks_nothing(tmp_path):
    """A capture fired while windows are in flight must not stall the
    drain or leak a settlement credit: the invariant twin runs
    (debug_invariants), every published pair still matches, and the
    drain predicate settles with the capture counted."""
    app = MatchmakingApp(_cfg(
        forensics=ForensicsConfig(min_interval_s=0.0),
        debug_invariants=True))
    reply = "forensics.drain.replies"
    app.broker.declare_queue(reply)
    app.broker.basic_consume(reply, lambda d: None, prefetch=1_000_000)
    await app.start()
    rt = app.runtime(Q)
    try:
        for i in range(8):
            _publish(app, f"dp{i}", 1000.0 + (i // 2) * 300.0, reply)
        # Fire mid-load, from a worker thread (the spine's cross-thread
        # path): the observer capture runs outside the spine lock.
        t = threading.Thread(target=app.events.append,
                             args=("breaker_trip", Q, "mid-drain fixture"))
        t.start()
        t.join()
        assert app.incidents.by_class.get("breaker_trip") == 1
        assert await settle(app, rt, Q, matched_at_least=8)
        assert app.metrics.counters.get("players_matched") == 8
        assert app.incidents.dropped == 0
    finally:
        await app.stop()


# ---- offline analyzer -------------------------------------------------------


def _synthetic_takeover_bundle() -> dict:
    rows = [
        (1, "replication", "lease_expired", {"epoch": 1}),
        (2, "replication", "epoch_bump", {"epoch": 2, "prev_epoch": 1}),
        (3, "durability", "journal_compacted", {"anchor": 0, "count": 6}),
        (4, "replication", "replay_window", {"epoch": 2, "players": 6}),
        (5, "replication", "failover_takeover", {"epoch": 2, "players": 6}),
        (7, "slo", "slo_burn", {"burn_fast": 100.0, "burn_slow": 100.0}),
        (9, "slo", "slo_burn_clear", {"slo_kind": "latency"}),
    ]
    spine = [{"seq": seq, "mono_ns": seq * 1_000_000, "wall": 100.0 + seq,
              "component": comp, "queue": Q, "kind": kind, "detail": "",
              "refs": refs} for seq, comp, kind, refs in rows]
    return {
        "schema": INCIDENT_SCHEMA, "id": "inc-000042",
        "trigger": {"class": "slo_burn_clear", "seq": 9,
                    "kind": "slo_burn_clear", "queue": Q,
                    "detail": "burn back under threshold", "refs": {},
                    "mono_ns": 9_000_000, "wall": 109.0},
        "captured_wall": 110.0, "capture_ms": 0.8,
        "spine": spine, "spine_digest": "d" * 64,
        "telemetry": {}, "replication": {},
        "journal": {Q: {"seq": 96, "synced_seq": 96, "segment_records": 60,
                        "lsn_range": [36, 96], "tail_digest": "t" * 64}},
        "counters": {},
    }


def test_postmortem_reconstructs_takeover_root_chain_offline():
    """The acceptance chain, from the bundle alone — no live service:
    lease expiry → epoch bump → replay window → takeover → burn →
    burn clear, epoch-matched across components."""
    pm = _load_script("postmortem")
    bundle = _synthetic_takeover_bundle()
    analysis = pm.analyze(bundle)
    assert analysis["problems"] == []
    assert analysis["root_chain_kinds"] == [
        "lease_expired", "epoch_bump", "replay_window",
        "failover_takeover", "slo_burn", "slo_burn_clear"]
    # journal_compacted (seq 3) sits INSIDE the chain's seq span but is
    # not a link — ref resolution, not seq adjacency.
    assert all(ev["kind"] != "journal_compacted"
               for ev in analysis["root_chain"])
    out = io.StringIO()
    pm.render(bundle, out=out)
    text = out.getvalue()
    assert "root chain (6 link(s), cause first)" in text
    assert "journal_dump.py" in text and "--lsn-range 36,96" in text
    # A trigger that rotated out of the spine window still anchors.
    rotated = dict(bundle)
    rotated["spine"] = [r for r in bundle["spine"] if r["seq"] != 9]
    chain = pm.root_chain(rotated)
    assert chain[-1]["kind"] == "slo_burn_clear"
    assert chain[0]["kind"] == "lease_expired"


def test_postmortem_main_exits_2_on_schema_problems(tmp_path, capsys):
    pm = _load_script("postmortem")
    good = tmp_path / "good.json"
    good.write_text(json.dumps(_synthetic_takeover_bundle()))
    assert pm.main([str(good)]) == 0
    assert "root chain" in capsys.readouterr().out
    bad = tmp_path / "bad.json"
    broken = _synthetic_takeover_bundle()
    del broken["spine_digest"]
    bad.write_text(json.dumps(broken))
    assert pm.main([str(bad)]) == 2
    assert "spine_digest" in capsys.readouterr().err


async def test_journal_dump_lsn_range_slices_bundle_window(tmp_path):
    """End to end: run a journaled app, capture a bundle, then slice the
    exact LSN window the bundle's journal watermark names — the records
    come back seq-ordered inside [lo, hi] with admit/terminal types."""
    jdir = str(tmp_path / "journal")
    app = MatchmakingApp(_cfg(
        durability=DurabilityConfig(journal_dir=jdir, fsync="window"),
        forensics=ForensicsConfig(min_interval_s=0.0)))
    reply = "forensics.lsn.replies"
    app.broker.declare_queue(reply)
    app.broker.basic_consume(reply, lambda d: None, prefetch=1_000_000)
    await app.start()
    try:
        for i in range(4):
            _publish(app, f"jp{i}", 1200.0 + (i // 2) * 300.0, reply)
        assert await settle(app, app.runtime(Q), Q, matched_at_least=4)
        bundle = app.incidents.capture(
            "breaker_trip",
            app.events.append("engine_revive", Q, "fixture anchor"))
        lo, hi = bundle["journal"][Q]["lsn_range"]
        assert hi == app.runtime(Q).journal.seq and lo <= hi
    finally:
        await app.stop()
    jd = _load_script("journal_dump")
    sliced = jd.slice_lsn_range(jdir, Q, lo, hi)
    assert "error" not in sliced
    seqs = [r["seq"] for r in sliced["records"]]
    assert seqs and seqs == sorted(seqs)
    assert all(lo <= s <= hi for s in seqs)
    types = {r["type"] for r in sliced["records"]}
    assert "admit" in types and types & {"terminal", "terminals"}
    # CLI shape: --lsn-range requires --queue; a bad range exits early.
    assert jd.main([jdir, "--queue", Q,
                    "--lsn-range", f"{lo},{hi}", "--json"]) == 0
    missing = jd.slice_lsn_range(jdir, "no.such.queue", 0, 10)
    assert "error" in missing
