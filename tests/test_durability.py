"""Crash durability (ISSUE 15): the write-ahead pool journal, hard-crash
recovery, and device-loss failover.

Layers under test:

- **journal framing/replay** (utils/journal.py): CRC-framed records, torn
  tails parse as "stop here", seq-filtered replay, clean-marker detection.
- **corruption fixtures**: byte-level corruption of checkpoint sidecars is
  DETECTED (CRC), a truncated newest snapshot FALLS BACK to the previous
  good generation, and a crash at every compaction point keeps the old
  state authoritative.
- **service round trip**: an app hard-crashed (``MatchmakingApp.crash()``:
  no drain, no clean marker) recovers its waiting pool, dedup cache, and
  admission state on the next boot — zero lost waiting players, and
  broker redeliveries of already-matched players REPLAY the same match
  (zero double matches). RTO is recorded (``crash_rto_ms``).
- **determinism**: the recovery transcript is bit-identical across two
  runs of the same seeded script (incl. scripted chaos).
- **device-loss failover**: a scripted ``device_lost`` fault demotes a
  D=2 sharded queue to its surviving device, audited with a measured
  blackout; traffic keeps matching on D=1.
- **sanitizer journal twin** (testing/sanitizer.py): double-append,
  append-after-clean-marker, and ack-before-commit are findings.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    ChaosConfig,
    Config,
    DurabilityConfig,
    EngineConfig,
    QueueConfig,
)
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.service.contract import SearchRequest
from matchmaking_tpu.testing.drain import settle
from matchmaking_tpu.utils import journal as jr

pytestmark = pytest.mark.durability

Q = "matchmaking.search"


def _row(pid: str, rating: float = 1500.0) -> list:
    return [pid, rating, 0.0, "", "", None, 1.0, "r.q", pid, 0, 0.0]


def _cpu_engine(requests=()):
    from matchmaking_tpu.engine.cpu import CpuEngine

    cfg = Config(queues=(QueueConfig(rating_threshold=100.0),))
    eng = CpuEngine(cfg, cfg.queues[0])
    if requests:
        eng.restore(list(requests), 1.0)
    return eng


def durable_cfg(jdir, *, chaos=None, mesh=1, bucketed=False,
                compact_interval=0.0, threshold=50.0):
    eng = dict(backend="tpu", pool_capacity=256, pool_block=64,
               batch_buckets=(8, 32), top_k=4)
    if mesh > 1:
        eng["mesh_pool_axis"] = mesh
    if bucketed:
        eng.update(bucketed=True, band_spec="gaussian:1500:300",
                   prune_window_blocks=2, prune_chunk=8)
    return Config(
        queues=(QueueConfig(rating_threshold=threshold,
                            dedup_ttl_s=600.0),),
        engine=EngineConfig(**eng),
        batcher=BatcherConfig(max_batch=8, max_wait_ms=5.0),
        durability=DurabilityConfig(journal_dir=str(jdir), fsync="window",
                                    compact_interval_s=compact_interval),
        chaos=chaos if chaos is not None else ChaosConfig(),
    )


def _publish(app, pid, rating, reply_q):
    app.broker.publish(
        Q, json.dumps({"id": pid, "rating": rating}).encode(),
        Properties(reply_to=reply_q, correlation_id=pid,
                   headers={"x-first-received": "1.0"}))


def _collect_responses(app, reply_q, sink):
    async def on_reply(delivery):
        sink.append(json.loads(delivery.body))

    app.broker.declare_queue(reply_q)
    app.broker.basic_consume(reply_q, on_reply, prefetch=1_000_000)


# ---- journal framing / replay ---------------------------------------------


def test_journal_roundtrip_and_torn_tail(tmp_path):
    j = jr.PoolJournal(str(tmp_path), "q", fsync="window")
    j.append_admits([_row("a"), _row("b")])
    j.append_terminal("a", b"matched-body", 99.0)
    j.commit()
    j.abandon()
    # Torn tail: a partial frame (crash mid-write) must parse as "stop
    # here", never as garbage records — and must void nothing before it.
    with open(jr.journal_path(str(tmp_path), "q"), "ab") as f:
        f.write(b"\x01\x02garbage-partial-frame")
    j2 = jr.PoolJournal(str(tmp_path), "q")
    rec = j2.recovered
    assert rec is not None and not rec.clean
    assert sorted(rec.waiting) == ["b"]
    assert rec.removed == {"a"}
    assert rec.recent["a"] == (b"matched-body", 99.0)
    assert any("torn tail" in note for note in rec.corrupt)
    # The re-attached writer truncated the torn tail and continues the
    # numbering past the newest intact record.
    assert j2.seq == rec.last_seq
    j2.abandon()


def test_journal_clean_marker_skips_recovery(tmp_path):
    j = jr.PoolJournal(str(tmp_path), "q")
    j.append_admits([_row("a")])
    j.commit()
    j.mark_clean()
    j.close()
    j2 = jr.PoolJournal(str(tmp_path), "q")
    assert j2.recovered is not None and j2.recovered.clean
    # A mutation after re-attach reopens the journal: the NEXT attach
    # must see an unclean shutdown again.
    j2.append_admits([_row("b")])
    j2.commit()
    j2.abandon()
    j3 = jr.PoolJournal(str(tmp_path), "q")
    assert j3.recovered is not None and not j3.recovered.clean
    assert "b" in j3.recovered.waiting
    j3.abandon()


def test_journal_append_after_close_raises(tmp_path):
    j = jr.PoolJournal(str(tmp_path), "q")
    j.mark_clean()
    j.close()
    with pytest.raises(RuntimeError):
        j.append_terminal("p", b"x", 1.0)


def test_crash_mid_window_players_recover_as_waiting(tmp_path):
    # The window's ADMIT committed at dispatch, its terminals never did
    # (crash before collection): recovery yields the players WAITING, not
    # matched — and the uncommitted buffer is lost exactly like kill -9.
    j = jr.PoolJournal(str(tmp_path), "q", fsync="window")
    j.append_admits([_row("a"), _row("b")])
    j.commit()
    j.append_terminal("a", b"never-committed", 99.0)  # buffered only
    j.abandon()  # drops the buffer — crash fidelity
    j2 = jr.PoolJournal(str(tmp_path), "q")
    rec = j2.recovered
    assert rec is not None and not rec.clean
    assert sorted(rec.waiting) == ["a", "b"]
    assert not rec.removed and not rec.recent
    j2.abandon()


# ---- corruption fixtures ---------------------------------------------------


def test_corrupt_newest_snapshot_falls_back_to_previous(tmp_path):
    from matchmaking_tpu.utils.checkpoint import save_pool

    j = jr.PoolJournal(str(tmp_path), "q", keep_snapshots=2)
    j.append_admits([_row("a"), _row("b")])
    j.commit()
    # Compaction 1: snapshot {a, b}.
    anchor1, snap1 = j.compact_begin()
    save_pool(_cpu_engine([jr.row_to_request(_row("a")),
                           jr.row_to_request(_row("b"))]), snap1)
    j.compact_finish(anchor1, snap1)
    # A later admit, then compaction 2: snapshot {a, b, c}.
    j.append_admits([_row("c")])
    j.commit()
    anchor2, snap2 = j.compact_begin()
    save_pool(_cpu_engine([jr.row_to_request(_row(p))
                           for p in ("a", "b", "c")]), snap2)
    j.compact_finish(anchor2, snap2)
    j.abandon()
    # Byte-level truncation of the NEWEST snapshot: recovery must fall
    # back to the previous good generation with a speakable note — and
    # replay the retained segments' tail over it, losslessly.
    blob = open(snap2, "rb").read()
    with open(snap2, "wb") as f:
        f.write(blob[:len(blob) // 2])
    j2 = jr.PoolJournal(str(tmp_path), "q")
    rec = j2.recovered
    assert rec is not None
    assert rec.snapshot == snap1 and rec.fallback
    assert any("failed verification" in note for note in rec.corrupt)
    assert sorted(rec.waiting) == ["c"]  # the post-anchor1 tail
    j2.abandon()


def test_crash_during_compaction_old_state_wins(tmp_path):
    from matchmaking_tpu.utils.checkpoint import save_pool

    # Crash point 1: the compaction snapshot never finished writing (a
    # garbage file at the target path). compact_finish REFUSES to rotate
    # and the old segment keeps covering the pool.
    j = jr.PoolJournal(str(tmp_path), "q")
    j.append_admits([_row("a"), _row("b")])
    j.commit()
    anchor, snap = j.compact_begin()
    with open(snap, "wb") as f:
        f.write(b"not an npz")
    with pytest.raises(ValueError):
        j.compact_finish(anchor, snap)
    j.abandon()
    rec = jr.PoolJournal(str(tmp_path), "q").recovered
    assert rec is not None and sorted(rec.waiting) == ["a", "b"]
    assert rec.snapshot == ""  # garbage snapshot failed verification
    os.unlink(snap)

    # Crash point 2: snapshot written and verified, but the process died
    # BEFORE the segment rotation (no compact_finish). The new snapshot
    # wins, seq filtering makes the un-truncated segment harmless.
    j = jr.PoolJournal(str(tmp_path / "p2"), "q")
    j.append_admits([_row("a"), _row("b")])
    j.append_terminal("x", b"old-terminal", 99.0)
    j.commit()
    anchor, snap = j.compact_begin()
    save_pool(_cpu_engine([jr.row_to_request(_row("a")),
                           jr.row_to_request(_row("b"))]), snap)
    j.abandon()  # crash between snapshot write and rotation
    rec = jr.PoolJournal(str(tmp_path / "p2"), "q").recovered
    assert rec is not None
    assert rec.snapshot == snap and not rec.fallback
    assert not rec.waiting  # pool state comes from the snapshot
    # Pre-anchor terminals still rebuild the dedup horizon (the
    # seq-unfiltered TERMINAL replay — compaction-crash losslessness).
    assert rec.recent["x"] == (b"old-terminal", 99.0)


def test_sidecar_crc_detects_byte_corruption(tmp_path):
    from matchmaking_tpu.service.broker import Delivery
    from matchmaking_tpu.utils.checkpoint import (
        load_admission,
        load_backlog,
        save_admission,
        save_backlog,
    )

    d = Delivery(body=b'{"id":"p"}',
                 properties=Properties(reply_to="r", correlation_id="c",
                                       headers={"x-tier": "1"}),
                 queue="q", delivery_tag=7)
    bpath = str(tmp_path / "_backlog.json")
    save_backlog(bpath, {"q": [d]})
    assert load_backlog(bpath)["q"][0]["body"] == b'{"id":"p"}'
    text = open(bpath).read()
    corrupted = text.replace('"redelivered": false', '"redelivered": true')
    assert corrupted != text
    with open(bpath, "w") as f:
        f.write(corrupted)
    with pytest.raises(ValueError, match="CRC mismatch"):
        load_backlog(bpath)

    apath = str(tmp_path / "_admission.json")
    save_admission(apath, {"q": {"credit_fraction": 0.5}})
    assert load_admission(apath)["q"]["credit_fraction"] == 0.5
    text = open(apath).read()
    with open(apath, "w") as f:
        f.write(text.replace("0.5", "0.9"))
    with pytest.raises(ValueError, match="CRC mismatch"):
        load_admission(apath)


# ---- service round trip ----------------------------------------------------


async def _run_crash_cycle(jdir, *, chaos=None):
    """One scripted load + hard crash: two pairs that match + one single
    that waits. Returns (pre-crash waiting ids, pid → match_id)."""
    from matchmaking_tpu.service.app import MatchmakingApp

    app = MatchmakingApp(durable_cfg(jdir, chaos=chaos))
    await app.start()
    rt = app.runtime(Q)
    replies: list[dict] = []
    _collect_responses(app, "dur.replies", replies)
    # Designed pairs (adjacent ratings, within threshold) + a far single:
    # the matched SET is deterministic whatever the window composition.
    for pid, rating in (("p0", 1500.0), ("p1", 1501.0),
                        ("p2", 2000.0), ("p3", 2001.0),
                        ("s0", 4000.0)):
        _publish(app, pid, rating, "dur.replies")
    assert await settle(app, rt, Q, matched_at_least=4,
                        reply_queues=("dur.replies",))
    waiting = {r.id for r in rt.engine.waiting()}
    matches = {r["player_id"]: r["match"]["match_id"]
               for r in replies if r.get("status") == "matched"}
    await app.crash()
    return waiting, matches


async def test_crash_recovery_service_roundtrip(tmp_path):
    from matchmaking_tpu.service.app import MatchmakingApp

    jdir = tmp_path / "j"
    pre_waiting, matches = await _run_crash_cycle(jdir)
    assert pre_waiting == {"s0"}
    assert set(matches) == {"p0", "p1", "p2", "p3"}

    # Successor boot: recovery replays snapshot + journal tail — zero
    # lost waiting players, the dedup cache restored, RTO measured.
    app2 = MatchmakingApp(durable_cfg(jdir))
    await app2.start()
    rt2 = app2.runtime(Q)
    try:
        assert {r.id for r in rt2.engine.waiting()} == pre_waiting
        assert app2.metrics.counters.get("crash_recoveries") == 1
        rto = app2.metrics.gauges.get(f"crash_rto_ms[{Q}]")
        assert rto is not None and rto > 0.0
        rec = rt2.last_recovery
        assert rec is not None and rec["transcript"]["waiting"] == ["s0"]
        assert not rec["fallback"]
        assert any(e["kind"] == "crash_recovered"
                   for e in app2.events.snapshot())

        # At-least-once reconciliation: the broker redelivers EVERY
        # pre-crash request. Matched players must replay the SAME match
        # (zero double matches), the waiting player re-enters as a
        # duplicate-enqueue no-op (zero duplicate pool entries).
        replays: list[dict] = []
        _collect_responses(app2, "dur.replays", replays)
        for pid, rating in (("p0", 1500.0), ("p1", 1501.0),
                            ("p2", 2000.0), ("p3", 2001.0),
                            ("s0", 4000.0)):
            _publish(app2, pid, rating, "dur.replays")
        assert await settle(app2, rt2, Q, reply_queues=("dur.replays",))
        replayed = {r["player_id"]: r["match"]["match_id"]
                    for r in replays if r.get("status") == "matched"}
        assert replayed == matches  # byte-for-byte the cached truth
        assert {r.id for r in rt2.engine.waiting()} == {"s0"}
        assert app2.metrics.counters.get("deduped_replays") >= 4
    finally:
        await app2.stop()


async def test_clean_shutdown_skips_recovery(tmp_path):
    from matchmaking_tpu.service.app import MatchmakingApp

    jdir = tmp_path / "j"
    app = MatchmakingApp(durable_cfg(jdir))
    await app.start()
    rt = app.runtime(Q)
    _publish(app, "s0", 4000.0, "")
    assert await settle(app, rt, Q)
    await app.stop()  # graceful: clean marker written
    app2 = MatchmakingApp(durable_cfg(jdir))
    await app2.start()
    try:
        assert app2.metrics.counters.get("crash_recoveries") == 0
        assert app2.runtime(Q).last_recovery is None
    finally:
        await app2.stop()


async def test_two_run_recovery_transcripts_bit_identical(tmp_path):
    from matchmaking_tpu.service.app import MatchmakingApp

    # Seeded chaos (one scripted window fault mid-load) on both runs: the
    # fault pattern, the redeliveries, and therefore the recovered state
    # must replay bit-identically.
    async def one(run: int) -> dict:
        jdir = tmp_path / f"run{run}"
        chaos = ChaosConfig(seed=7, queues=(Q,), fail_steps=(1,))
        await _run_crash_cycle(jdir, chaos=chaos)
        app = MatchmakingApp(durable_cfg(jdir, chaos=chaos))
        await app.start()
        rec = app.runtime(Q).last_recovery
        await app.stop()
        assert rec is not None
        return rec["transcript"]

    t0 = await one(0)
    t1 = await one(1)
    assert json.dumps(t0, sort_keys=True) == json.dumps(t1, sort_keys=True)


async def test_bucketed_index_exact_after_replay(tmp_path):
    import jax.numpy as jnp

    from matchmaking_tpu.service.app import MatchmakingApp

    jdir = tmp_path / "j"
    app = MatchmakingApp(durable_cfg(jdir, bucketed=True))
    await app.start()
    rt = app.runtime(Q)
    # Far-apart singles across the rating range: they populate several
    # buckets and never match.
    for i, rating in enumerate((800.0, 1500.0, 2200.0, 4000.0)):
        _publish(app, f"s{i}", rating, "")
    assert await settle(app, rt, Q)
    pre = {r.id for r in rt.engine.waiting()}
    assert len(pre) == 4
    await app.crash()

    app2 = MatchmakingApp(durable_cfg(jdir, bucketed=True))
    await app2.start()
    try:
        eng = app2.runtime(Q).engine
        assert {r.id for r in eng.waiting()} == pre
        # index_rebuild vs the incrementally-maintained index: recovery
        # ran the rebuild (heartbeat seam), so the device index must be
        # EXACTLY the from-scratch one, array for array.
        index_keys = list(eng.kernels.init_index_arrays())
        assert index_keys
        pool_copy = {k: jnp.array(np.asarray(v))
                     for k, v in eng._dev_pool.items()}
        rebuilt = eng.kernels.index_rebuild(pool_copy)
        for k in index_keys:
            assert np.array_equal(np.asarray(eng._dev_pool[k]),
                                  np.asarray(rebuilt[k])), k
    finally:
        await app2.stop()


async def test_compaction_timer_armed_only_after_recovery(tmp_path,
                                                          monkeypatch):
    from matchmaking_tpu.service.app import MatchmakingApp, _QueueRuntime

    # Review-pinned ordering: a re-attached segment can already exceed
    # the compaction budget, and a timer armed before recovery could
    # snapshot the NOT-YET-RECOVERED (empty) pool anchored at the
    # recovered seq — GC'ing the snapshot recovery is about to load.
    jdir = tmp_path / "j"
    await _run_crash_cycle(jdir)
    orig = _QueueRuntime.recover_from_journal
    timer_state: dict = {}

    async def spy(self):
        timer_state["armed_before_recovery"] = self._durability is not None
        return await orig(self)

    monkeypatch.setattr(_QueueRuntime, "recover_from_journal", spy)
    app = MatchmakingApp(durable_cfg(jdir, compact_interval=0.05))
    await app.start()
    try:
        assert timer_state["armed_before_recovery"] is False
        rt = app.runtime(Q)
        assert rt._durability is not None  # armed after recovery applied
        assert {r.id for r in rt.engine.waiting()} == {"s0"}
    finally:
        await app.stop()


# ---- device-loss failover --------------------------------------------------


async def test_device_lost_failover_demotes_to_surviving_devices(tmp_path):
    from matchmaking_tpu.service.app import MatchmakingApp

    chaos = ChaosConfig(seed=3, queues=(Q,), device_lost_steps=(0,))
    app = MatchmakingApp(durable_cfg(tmp_path / "j", chaos=chaos, mesh=2))
    await app.start()
    rt = app.runtime(Q)
    try:
        replies: list[dict] = []
        _collect_responses(app, "fo.replies", replies)
        _publish(app, "a0", 1500.0, "fo.replies")
        _publish(app, "a1", 1501.0, "fo.replies")
        # The first device step raises ChaosDeviceLostError: the window
        # nacks, the queue demotes D=2 -> D=1 onto the surviving device,
        # and the redelivered pair matches on the demoted engine.
        assert await settle(app, rt, Q, matched_at_least=2,
                            reply_queues=("fo.replies",))
        assert rt.placement == (0,)
        assert app.metrics.counters.get("device_failovers") == 1
        assert len(rt.failover_log) == 1
        entry = rt.failover_log[0]
        assert entry["from_devices"] == [0, 1]
        assert entry["to_devices"] == [0]
        assert entry["blackout_ms"] > 0.0
        assert any(e["kind"] == "device_failover"
                   for e in app.events.snapshot())
        matched = [r for r in replies if r.get("status") == "matched"]
        assert {m["player_id"] for m in matched} == {"a0", "a1"}
        # Traffic keeps flowing on the demoted binding.
        _publish(app, "b0", 1600.0, "fo.replies")
        _publish(app, "b1", 1601.0, "fo.replies")
        assert await settle(app, rt, Q, matched_at_least=4,
                            reply_queues=("fo.replies",))
    finally:
        await app.stop()


# ---- sanitizer journal twin ------------------------------------------------


def test_sanitizer_flags_journal_double_append(tmp_path):
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    san = AsyncSanitizer()
    with san.installed():
        j = jr.PoolJournal(str(tmp_path), "q")
        j.append_terminal("p", b"body", 9.0)
        j.append_terminal("p", b"body", 9.0)  # identical record twice
        j.abandon()
    assert any(f.kind == "journal-double-append" for f in san.findings)
    assert "twice in one segment" in str(
        [f for f in san.findings if f.kind == "journal-double-append"][0])


def test_sanitizer_flags_append_after_clean_marker(tmp_path):
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    san = AsyncSanitizer()
    with san.installed():
        j = jr.PoolJournal(str(tmp_path), "q")
        j.mark_clean()
        # Replay semantics self-correct (a later mutation voids the
        # marker at the next attach), so this is not a crash-safety hole
        # — but it IS the discipline violation the twin exists to name.
        j.append_terminal("p", b"x", 1.0)
        j.close()
    assert any(f.kind == "journal-append-after-clean" for f in san.findings)


def test_sanitizer_flags_ack_before_journal_commit(tmp_path):
    from matchmaking_tpu.service.broker import InProcBroker
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    # Break the write-ahead discipline on purpose at the twin's own seam:
    # a BUFFERED terminal record (the object-path shape — the columnar
    # hot path writes out inside the append, so a process crash cannot
    # lose it) is still pending when its queue's delivery acks. In the
    # real app every settle path runs _journal_commit first; here we
    # simply never commit — the twin must catch the dirty-buffer ack
    # (this is exactly the bug class it exists for).
    san = AsyncSanitizer()
    with san.installed():
        async def run():
            broker = InProcBroker()
            broker.declare_queue("q")
            deliveries: list = []
            got = asyncio.Event()

            async def handler(d):
                deliveries.append(d)
                got.set()

            tag = broker.basic_consume("q", handler, prefetch=10)
            broker.publish("q", b'{"id":"p"}',
                           Properties(reply_to="", correlation_id=""))
            await got.wait()
            j = jr.PoolJournal(str(tmp_path), "q", fsync="window")
            j.append_terminal("p", b"body", 9.0)  # buffered, uncommitted
            broker.ack(tag, deliveries[0].delivery_tag)
            j.abandon()
            broker.close()

        asyncio.run(run())
    finding = [f for f in san.findings
               if f.kind == "journal-unflushed-settle"]
    assert finding, san.findings
    assert "write-ahead discipline" in str(finding[0])
