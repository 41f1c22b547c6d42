"""Hot-standby journal replication with fenced cross-host failover
(ISSUE 17): the acceptance surface.

Layers under test:

- **lease/epoch authority** (service/replication.LeaseAuthority): acquire
  / renew / expiry / takeover / release semantics, the epoch bump on
  every ownership change, LeaseHeldError on a live lease, and the
  scripted renewal faults (ChaosConfig.repl_fail_renewals).
- **the at-least-once link** (InProcReplicationLink): scripted drop /
  dup / delay / partition faults all converge once the sender's
  stall-retransmission replays the unacked tail — faults fire on a seq's
  FIRST transmission only.
- **the standby applier** (StandbyApplier): strict-order apply with a
  gap buffer, idempotent duplicates, and the RT_REPL_SNAPSHOT baseline
  that re-bases the watermark (attach-mid-life).
- **fencing** (the acceptance regression): a superseded ex-primary
  provably cannot append (PoolJournal.fence raises FencedError) or
  publish (_publish_body/_publish_batch refuse + count), whether the
  process is dead (failover e2e) or still running (live lease lapse).
- **service stream round trip**: the standby's shadow mirrors the
  primary's waiting pool + dedup cache record for record; a graceful
  stop streams CLEAN and releases the lease; the drain predicate's
  replication-quiescence clause holds the soak open until the ack
  watermark catches the appended seq.
- **failover e2e**: crash → takeover → successor adoption with the RTO
  gauge/counter/event, ``last_recovery`` sourced from the replica, and a
  redelivered already-matched player replaying the SAME match from the
  replicated dedup cache.
- **sanitizer replication twin** (testing/sanitizer.py):
  publish-after-fence, apply-out-of-order, and ack-beyond-received are
  findings — negative-tested by breaking each seam on purpose, positive-
  tested by a clean streamed flow under the installed twin.
- **offline journal inspector** (scripts/journal_dump.py): record/seq
  reports on an intact WAL, the torn-tail diagnosis, snapshot
  verification, and the intact-vs-not exit status.
"""

import asyncio
import json
import time

import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    ChaosConfig,
    Config,
    DurabilityConfig,
    EngineConfig,
    QueueConfig,
    ReplicationConfig,
)
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.service.replication import (
    RT_REPL_SNAPSHOT,
    InProcReplicationLink,
    LeaseAuthority,
    LeaseHeldError,
    QueueReplication,
    ReplicationHub,
    StandbyApplier,
    baseline_payload,
)
from matchmaking_tpu.testing.drain import fully_drained, settle
from matchmaking_tpu.utils import journal as jr
from matchmaking_tpu.utils.journal import FencedError

pytestmark = pytest.mark.replication

Q = "matchmaking.search"


def _row(pid: str, rating: float = 1500.0) -> list:
    return [pid, rating, 0.0, "", "", None, 1.0, "r.q", pid, 0, 0.0]


def _admit(*pids: str) -> bytes:
    return json.dumps({"rows": [_row(p) for p in pids]}).encode()


def repl_cfg(jdir, *, owner="primary", chaos=None, metrics_port=0):
    return Config(
        queues=(QueueConfig(rating_threshold=50.0, dedup_ttl_s=600.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=256, pool_block=64,
                            batch_buckets=(8, 32), top_k=4),
        batcher=BatcherConfig(max_batch=8, max_wait_ms=5.0),
        durability=DurabilityConfig(journal_dir=str(jdir), fsync="window"),
        replication=ReplicationConfig(role="primary", owner=owner),
        chaos=chaos if chaos is not None else ChaosConfig(),
        metrics_port=metrics_port,
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


# ---- lease / epoch authority ------------------------------------------------


def test_lease_acquire_renew_expire_epoch_bump():
    auth = LeaseAuthority(lease_s=0.5)
    assert auth.acquire(Q, "p", 100.0) == 1
    # Same-owner re-acquire renews IN PLACE: no epoch bump.
    assert auth.acquire(Q, "p", 100.2) == 1
    assert auth.renew(Q, "p", 1, 100.4) is True
    assert auth.is_current(Q, "p", 1)
    assert not auth.expired(Q, 100.8)
    # A different owner cannot acquire over a live lease.
    with pytest.raises(LeaseHeldError):
        auth.acquire(Q, "s", 100.8)
    # ... but CAN over an expired one, and that bumps the epoch,
    # fencing the previous holder's (owner, epoch) pair.
    assert auth.expired(Q, 100.9)
    assert auth.acquire(Q, "s", 100.9) == 2
    assert not auth.is_current(Q, "p", 1)
    assert auth.is_current(Q, "s", 2)
    assert auth.renew(Q, "p", 1, 101.0) is False
    assert auth.epoch_of(Q) == 2


def test_lease_takeover_and_release():
    auth = LeaseAuthority(lease_s=0.5)
    auth.acquire(Q, "p", 10.0)
    # Takeover before expiry is refused (split-brain guard) ...
    with pytest.raises(LeaseHeldError):
        auth.takeover(Q, "s", 10.1)
    # ... unless forced (the operator drill), and after expiry it is the
    # normal failover step — both bump the epoch.
    assert auth.takeover(Q, "s", 10.1, force=True) == 2
    assert auth.takeover(Q, "t", 10.6 + 0.5) == 3
    # Graceful release expires the lease NOW: an immediate successor
    # takeover needs no expiry wait.
    auth.release(Q, "t", 3, 20.0)
    assert auth.expired(Q, 20.0)
    assert auth.takeover(Q, "u", 20.0) == 4


def test_lease_scripted_renewal_faults():
    auth = LeaseAuthority(lease_s=0.5, fail_renewals=(0,))
    auth.acquire(Q, "p", 1.0)
    # The scripted fault refuses the renewal WITHOUT changing ownership:
    # the lease simply lapses on the authority's clock — fencing happens
    # only when someone takes over the expired lease.
    assert auth.renew(Q, "p", 1, 1.1) is False
    assert auth.is_current(Q, "p", 1)
    assert auth.renew(Q, "p", 1, 1.2) is True


# ---- the at-least-once link under scripted faults ---------------------------


def _sender(chaos=None, lease_s=60.0):
    auth = LeaseAuthority(lease_s=lease_s)
    epoch = auth.acquire(Q, "p", 0.0)
    link = InProcReplicationLink(Q, chaos=chaos)
    repl = QueueReplication(Q, "p", epoch, auth, link)
    applier = StandbyApplier(Q, link, auth, owner="s")
    return auth, link, repl, applier


def test_link_drop_heals_via_stall_retransmit():
    _auth, link, repl, applier = _sender(
        chaos=ChaosConfig(repl_drop_seqs=(2,)))
    for seq, pid in ((1, "a"), (2, "b"), (3, "c")):
        repl.on_record(seq, jr.RT_ADMIT, _admit(pid))
    applier.pump()
    # Seq 2's first transmission dropped: 1 applies, 3 buffers ahead.
    assert applier.applied_seq == 1
    assert link.counters["dropped"] == 1
    assert applier.counters["buffered"] == 1
    assert not repl.quiescent
    repl.pump(1.0)   # collects ack=1 (progress)
    repl.pump(2.0)   # stalled x1
    repl.pump(3.0)   # stalled x2 -> retransmits the unacked tail {2, 3}
    assert link.counters["retransmits"] >= 2
    applier.pump()
    assert applier.applied_seq == 3
    assert sorted(applier.shadow.waiting) == ["a", "b", "c"]
    repl.pump(4.0)
    assert repl.quiescent
    assert repl.lag() == 0


def test_link_dup_and_delay_reorder_absorbed():
    _auth, link, repl, applier = _sender(
        chaos=ChaosConfig(repl_dup_seqs=(1,), repl_delay_seqs=((2, 1),)))
    repl.on_record(1, jr.RT_ADMIT, _admit("a"))   # duplicated on the wire
    repl.on_record(2, jr.RT_ADMIT, _admit("b"))   # held one transmission
    repl.on_record(3, jr.RT_ADMIT, _admit("c"))   # releases 2 LATE (reorder)
    assert link.counters["dup"] == 1
    assert link.counters["delayed"] == 1
    applier.pump()
    # The duplicate drops idempotently; the late release lands in order.
    assert applier.applied_seq == 3
    assert applier.counters["dups"] >= 1
    assert sorted(applier.shadow.waiting) == ["a", "b", "c"]


def test_link_runtime_partition_holds_and_resumes():
    _auth, link, repl, applier = _sender()
    link.partition(2, resume=4)
    for seq, pid in ((1, "a"), (2, "b"), (3, "c")):
        repl.on_record(seq, jr.RT_ADMIT, _admit(pid))
    applier.pump()
    assert applier.applied_seq == 1          # 2 and 3 held on the far side
    assert link.counters["partitions"] == 1
    repl.on_record(4, jr.RT_ADMIT, _admit("d"))   # reaches resume: heals
    applier.pump()
    assert applier.applied_seq == 4
    assert sorted(applier.shadow.waiting) == ["a", "b", "c", "d"]
    # Default resume is NEVER — the bench's kill-under-lag cut: the held
    # tail is exactly the lag the kill loses, and it never self-heals.
    link.partition(5)
    repl.on_record(5, jr.RT_ADMIT, _admit("e"))
    repl.on_record(6, jr.RT_ADMIT, _admit("f"))
    applier.pump()
    assert applier.applied_seq == 4
    repl.pump(1.0)
    assert not repl.quiescent
    assert repl.unacked_admit_players() == 2


def test_applier_baseline_rebase_and_stale_baseline_dropped():
    link = InProcReplicationLink(Q)
    applier = StandbyApplier(Q, link)
    # Attach mid-life: the baseline REPLACES the shadow and re-bases the
    # watermark at the journal seq it summarizes.
    link.send(10, RT_REPL_SNAPSHOT,
              baseline_payload([_row("a"), _row("b")],
                               [("z", b"z-body", 9e9)], {"k": 1}))
    applier.pump()
    assert applier.applied_seq == 10
    assert sorted(applier.shadow.waiting) == ["a", "b"]
    assert applier.shadow.recent["z"] == (b"z-body", 9e9)
    assert applier.shadow.admission == {"k": 1}
    assert link.acked == 10
    # Later records apply on top of the re-based watermark.
    link.send(11, jr.RT_ADMIT, _admit("c"))
    applier.pump()
    assert applier.applied_seq == 11
    assert "c" in applier.shadow.waiting
    # A stale (retransmitted) baseline below the watermark is a duplicate
    # of state already held — dropped, never a rollback.
    link.send(5, RT_REPL_SNAPSHOT, baseline_payload([_row("x")], [], None))
    applier.pump()
    assert applier.applied_seq == 11
    assert "x" not in applier.shadow.waiting


def test_applier_terminal_and_clean_semantics():
    import base64

    link = InProcReplicationLink(Q)
    applier = StandbyApplier(Q, link)
    link.send(1, jr.RT_ADMIT, _admit("a", "b"))
    b64 = base64.b64encode(b"matched-body").decode("ascii")
    link.send(2, jr.RT_TERMINAL,
              json.dumps({"id": "a", "body": b64, "exp": 9e9}).encode())
    link.send(3, jr.RT_CLEAN, b"")
    applier.pump()
    # Terminal moves the player waiting -> removed + dedup cache; CLEAN
    # marks the stream clean (a later mutation would reopen it).
    assert sorted(applier.shadow.waiting) == ["b"]
    assert applier.shadow.recent["a"] == (b"matched-body", 9e9)
    assert "a" in applier.shadow.removed
    assert applier.shadow.clean
    link.send(4, jr.RT_ADMIT, _admit("c"))
    applier.pump()
    assert not applier.shadow.clean


# ---- fencing: the ex-primary regression (unit, live process) ---------------


def test_fenced_live_primary_cannot_append_or_publish(tmp_path):
    """The acceptance regression at the journal seam: a LIVE ex-primary
    whose lease lapsed (here: epoch superseded by a standby takeover)
    must fail its next append with FencedError and refuse publishes —
    aliveness is irrelevant, the AUTHORITY's epoch decides."""
    auth = LeaseAuthority(lease_s=0.5)
    epoch = auth.acquire(Q, "p", 100.0)
    link = InProcReplicationLink(Q)
    repl = QueueReplication(Q, "p", epoch, auth, link)
    j = jr.PoolJournal(str(tmp_path), Q, fsync="window")
    j.tap = repl.on_record
    j.fence = repl.may_write
    try:
        j.append_admits([_row("a")])
        assert repl.sent_seq == j.seq and repl.role == "primary"
        # Standby takes over AFTER lease expiry (deadline = 100.5).
        assert auth.takeover(Q, "s", 101.0) == epoch + 1
        assert repl.superseded()
        with pytest.raises(FencedError):
            j.append_admits([_row("b")])
        assert repl.role == "fenced"
        assert repl.may_publish() is False
        assert repl.snapshot()["role"] == "fenced"
        # The fenced sender ships nothing more (no split-brain stream).
        sent_before = link.counters["sent"]
        repl.on_record(99, jr.RT_ADMIT, _admit("x"))
        assert link.counters["sent"] == sent_before
    finally:
        j.abandon()


def test_unacked_admit_players_is_the_loss_bound():
    _auth, link, repl, applier = _sender()
    repl.on_record(1, jr.RT_ADMIT, _admit("a", "b"))
    repl.on_record(2, jr.RT_TERMINAL,
                   json.dumps({"id": "a", "body": "eA==",
                               "exp": 9e9}).encode())
    # Two players sit in unacked ADMIT records: exactly what a kill right
    # now could lose across failover (terminals don't count — a lost
    # terminal replays the match, it doesn't lose a player).
    assert repl.unacked_admit_players() == 2
    applier.pump()
    repl.pump(1.0)
    assert repl.unacked_admit_players() == 0


# ---- service stream round trip ---------------------------------------------


async def test_replication_service_roundtrip_and_clean_handoff(tmp_path):
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.observability import build_report

    hub = ReplicationHub(lease_s=0.5)
    app = MatchmakingApp(repl_cfg(tmp_path / "j1"), replication_hub=hub)
    await app.start()
    rt = app.runtime(Q)
    standby = hub.standby(Q)
    stopped = False
    try:
        assert rt.replication is not None
        assert any(e["kind"] == "replication_attached"
                   for e in app.events.snapshot())
        replies: list[dict] = []
        _collect_responses(app, "repl.replies", replies)
        for pid, rating in (("p0", 1500.0), ("p1", 1501.0),
                            ("p2", 2000.0), ("p3", 2001.0),
                            ("s0", 4000.0)):
            _publish(app, pid, rating, "repl.replies")
        assert await settle(app, rt, Q, matched_at_least=4,
                            reply_queues=("repl.replies",), standby=standby)
        standby.pump()
        # The shadow mirrors the primary: the lone unmatched player
        # waiting, every matched player in the dedup cache, the apply
        # watermark at the journal's appended seq.
        assert sorted(standby.shadow.waiting) == ["s0"]
        assert {"p0", "p1", "p2", "p3"} <= set(standby.shadow.recent)
        assert standby.applied_seq == rt.journal.seq
        assert rt.replication.quiescent
        rep = build_report(app)
        blk = rep["replication"][Q]
        assert blk["role"] == "primary" and blk["lag"] == 0
        assert blk["acked_seq"] == blk["sent_seq"] == rt.journal.seq
        assert app.metrics.gauges.get(f"replication_lag[{Q}]") == 0
        assert app.metrics.gauges.get(f"replication_epoch[{Q}]") == 1
        # Graceful stop: CLEAN streams to the standby and the lease is
        # released — a successor could promote with no expiry wait.
        await app.stop()
        stopped = True
        standby.pump()
        assert standby.shadow.clean
        assert hub.authority.expired(Q, time.monotonic())
    finally:
        if not stopped:
            await app.stop()


async def test_drain_holds_until_replication_quiesces(tmp_path):
    """The fully_drained replication clause (satellite): with the
    standby never pumped, the engine-side drain settles but the full
    predicate must NOT — the unacked tail is exactly the lag a kill
    would lose, so a soak settling early would mismeasure it. Pumping
    the standby (apply + ack) releases the clause."""
    from matchmaking_tpu.service.app import MatchmakingApp

    hub = ReplicationHub(lease_s=5.0)
    app = MatchmakingApp(repl_cfg(tmp_path / "j1"), replication_hub=hub)
    await app.start()
    rt = app.runtime(Q)
    standby = hub.standby(Q)
    try:
        replies: list[dict] = []
        _collect_responses(app, "drain.replies", replies)
        _publish(app, "a0", 1500.0, "drain.replies")
        _publish(app, "a1", 1501.0, "drain.replies")
        assert await settle(app, rt, Q, matched_at_least=2,
                            replication=False)
        assert not fully_drained(app, rt, Q, 2)          # unacked tail
        assert fully_drained(app, rt, Q, 2, replication=False)
        assert await settle(app, rt, Q, matched_at_least=2,
                            standby=standby)             # clause settles
        assert rt.replication.quiescent
    finally:
        await app.stop()


# ---- failover e2e -----------------------------------------------------------


async def test_failover_crash_takeover_successor_adopts(tmp_path):
    """The acceptance e2e: primary crashes mid-life, the standby takes
    over after lease expiry (epoch 2), the fenced ex-primary can neither
    append nor publish, and the successor app adopts the shadow — the
    waiting player survives, the RTO is recorded, and a redelivered
    already-matched player replays the SAME match from the replicated
    dedup cache (zero double matches)."""
    from matchmaking_tpu.service.app import MatchmakingApp

    hub = ReplicationHub(lease_s=0.5)
    app = MatchmakingApp(repl_cfg(tmp_path / "j1", owner="hostA"),
                         replication_hub=hub)
    await app.start()
    rt = app.runtime(Q)
    standby = hub.standby(Q, owner="hostB")
    replies: list[dict] = []
    _collect_responses(app, "fo.replies", replies)
    for pid, rating in (("p0", 1500.0), ("p1", 1501.0), ("s0", 4000.0)):
        _publish(app, pid, rating, "fo.replies")
    assert await settle(app, rt, Q, matched_at_least=2,
                        reply_queues=("fo.replies",), standby=standby)

    await app.crash()
    # Lease expiry is scriptable: the authority's clock is the caller's.
    epoch = standby.takeover(time.monotonic() + 0.5 + 0.05)
    assert epoch == 2
    assert Q in hub.adopted

    # Fenced ex-primary: the journal refuses the append, the publish
    # seam refuses (and counts) the response.
    assert rt.replication.superseded()
    with pytest.raises(FencedError):
        rt.journal.append_admits([_row("zz")])
    before = app.metrics.counters.get("fenced_publish_refused")
    rt._publish_body("fo.replies", "zz", b"{}")
    assert app.metrics.counters.get("fenced_publish_refused") == before + 1
    assert rt.replication.role == "fenced"

    # Successor boots AS the takeover owner and adopts the shadow.
    app2 = MatchmakingApp(repl_cfg(tmp_path / "j2", owner="hostB"),
                          replication_hub=hub)
    await app2.start()
    rt2 = app2.runtime(Q)
    try:
        assert sorted(r.id for r in rt2.engine.waiting()) == ["s0"]
        rto = app2.metrics.gauges.get(f"failover_rto_ms[{Q}]")
        assert rto is not None and rto > 0
        assert app2.metrics.counters.get("failover_takeovers") == 1
        assert any(e["kind"] == "failover_takeover"
                   for e in app2.events.snapshot())
        rec = rt2.last_recovery
        assert rec["source"] == "replica" and rec["epoch"] == 2
        assert rec["tail_players"] == 1
        # Redelivery of an already-matched player replays the SAME
        # terminal response — the dedup cache crossed hosts.
        replies.clear()
        _collect_responses(app2, "fo.replies", replies)
        _publish(app2, "p0", 1500.0, "fo.replies")
        assert await settle(app2, rt2, Q, reply_queues=("fo.replies",),
                            replication=False)
        replayed = [r for r in replies if r.get("player_id") == "p0"]
        assert replayed and replayed[0]["status"] == "matched"
    finally:
        await app2.stop()


# ---- sanitizer replication twin ---------------------------------------------


def test_sanitizer_replication_clean_stream_no_findings():
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    san = AsyncSanitizer()
    with san.installed():
        auth = LeaseAuthority(lease_s=60.0)
        epoch = auth.acquire(Q, "p", 0.0)
        link = InProcReplicationLink(Q)
        repl = QueueReplication(Q, "p", epoch, auth, link)
        applier = StandbyApplier(Q, link, auth, owner="s")
        for seq, pid in enumerate(("a", "b", "c"), start=1):
            repl.on_record(seq, jr.RT_ADMIT, _admit(pid))
        applier.pump()
        repl.pump(1.0)
        applier.takeover(100.0, force=True)
    assert not [f for f in san.findings if f.kind.startswith("replication-")]


def test_sanitizer_flags_apply_out_of_order():
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    san = AsyncSanitizer()
    with san.installed():
        link = InProcReplicationLink(Q)
        applier = StandbyApplier(Q, link)
        link.send(1, jr.RT_ADMIT, _admit("a"))
        applier.pump()
        # Break the ordering seam on purpose: apply a gapped seq
        # DIRECTLY, bypassing pump()'s gap buffer.
        applier._apply(5, jr.RT_ADMIT, _admit("x"))
    finding = [f for f in san.findings
               if f.kind == "replication-apply-out-of-order"]
    assert finding, san.findings
    assert "corrupts the shadow" in str(finding[0])


def test_sanitizer_flags_ack_beyond_received():
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    san = AsyncSanitizer()
    with san.installed():
        link = InProcReplicationLink(Q)
        link.send(1, jr.RT_ADMIT, _admit("a"))
        link.recv()
        # Break the watermark seam on purpose: ack past the delivered
        # horizon — the primary would drop records the standby never saw.
        link.ack(link.max_delivered + 7)
    finding = [f for f in san.findings
               if f.kind == "replication-ack-beyond-received"]
    assert finding, san.findings
    assert "silent loss" in str(finding[0])


async def test_sanitizer_flags_publish_after_fence_and_healthz_degraded(
        tmp_path):
    """Two acceptance points on one fenced LIVE primary: /healthz turns
    ``degraded`` naming the fenced queue (a load balancer must stop
    routing here), and — with the publish fence broken ON PURPOSE — a
    response reaching the broker after the epoch was superseded is a
    sanitizer finding (the split-brain double match fencing kills)."""
    import aiohttp

    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    port = 19281
    san = AsyncSanitizer()
    with san.installed():
        hub = ReplicationHub(lease_s=0.4)
        app = MatchmakingApp(
            repl_cfg(tmp_path / "j1", owner="hostA", metrics_port=port),
            replication_hub=hub)
        await app.start()
        rt = app.runtime(Q)
        standby = hub.standby(Q, owner="hostB")
        try:
            replies: list[dict] = []
            _collect_responses(app, "fence.replies", replies)
            _publish(app, "a0", 1500.0, "fence.replies")
            _publish(app, "a1", 1501.0, "fence.replies")
            assert await settle(app, rt, Q, matched_at_least=2,
                                standby=standby)
            assert not [f for f in san.findings
                        if f.kind.startswith("replication-")]
            standby.takeover(time.monotonic() + 0.4 + 0.05)
            # The pump loop's next lease renewal discovers the
            # superseded epoch and flips the role to fenced.
            for _ in range(400):
                await asyncio.sleep(0.01)
                if rt.replication.role == "fenced":
                    break
            assert rt.replication.role == "fenced"
            async with aiohttp.ClientSession() as s:
                async with s.get(
                        f"http://127.0.0.1:{port}/healthz") as resp:
                    assert resp.status == 200
                    health = await resp.json()
            assert health["status"] == "degraded"
            assert Q in health["degraded_queues"]
            assert health["queues"][Q]["replication"]["role"] == "fenced"
            # The intact seam refuses: no broker publish, no finding.
            before = app.broker.stats.get("published", 0)
            rt._publish_body("fence.replies", "a0", b"{}")
            assert app.broker.stats.get("published", 0) == before
            assert not [f for f in san.findings
                        if f.kind == "replication-publish-after-fence"]
            # Break the seam on purpose: the response becomes visible at
            # the broker after the fence — the twin must catch it.
            rt.replication.may_publish = lambda: True
            rt._publish_body("fence.replies", "a0", b"{}")
        finally:
            await app.crash()
    finding = [f for f in san.findings
               if f.kind == "replication-publish-after-fence"]
    assert finding, san.findings
    assert "split-brain" in str(finding[0])


# ---- offline journal inspector (scripts/journal_dump.py) --------------------


def _load_journal_dump():
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "journal_dump.py")
    spec = importlib.util.spec_from_file_location("journal_dump", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_journal_dump_intact_segment_report(tmp_path, capsys):
    jd = _load_journal_dump()
    j = jr.PoolJournal(str(tmp_path), "q", fsync="window")
    j.append_admits([_row("a"), _row("b")])
    j.append_terminal("a", b"matched", 99.0)
    j.commit(force_sync=True)
    j.mark_clean()
    j.close()
    rep = jd.inspect_queue(str(tmp_path), "q")
    seg = rep["segment"]
    assert rep["intact"] and not seg["torn"]
    assert seg["counts"]["admit"] == 1 and seg["counts"]["terminal"] == 1
    assert seg["clean_tail"] and seg["seq_gaps"] == []
    assert seg["seq_min"] == 1 and seg["seq_max"] == seg["records"] == 3
    assert jd.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "intact: True" in out and "clean tail: True" in out


def test_journal_dump_torn_tail_diagnosis(tmp_path, capsys):
    jd = _load_journal_dump()
    j = jr.PoolJournal(str(tmp_path), "q", fsync="window")
    j.append_admits([_row("a")])
    j.commit(force_sync=True)
    j.abandon()
    with open(jr.journal_path(str(tmp_path), "q"), "ab") as f:
        f.write(b"\x07\x07torn-partial-frame")
    rep = jd.inspect_queue(str(tmp_path), "q")
    seg = rep["segment"]
    assert seg["torn"] and not rep["intact"]
    assert seg["torn_bytes"] > 0
    assert "truncates here" in seg["diagnosis"]
    # The CLI doubles as a health probe: torn -> exit 1, and --json emits
    # the same dict machine-readably.
    assert jd.main([str(tmp_path)]) == 1
    capsys.readouterr()
    assert jd.main([str(tmp_path), "--queue", "q", "--json"]) == 1
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["q"]["segment"]["torn"] is True


def _cpu_engine(requests=()):
    from matchmaking_tpu.engine.cpu import CpuEngine

    cfg = Config(queues=(QueueConfig(rating_threshold=100.0),))
    eng = CpuEngine(cfg, cfg.queues[0])
    if requests:
        eng.restore(list(requests), 1.0)
    return eng


def test_journal_dump_snapshot_verification(tmp_path):
    from matchmaking_tpu.utils.checkpoint import save_pool

    jd = _load_journal_dump()
    j = jr.PoolJournal(str(tmp_path), "q", fsync="window")
    j.append_admits([_row("a"), _row("b")])
    j.commit(force_sync=True)
    anchor, snap_path = j.compact_begin()
    save_pool(_cpu_engine([jr.row_to_request(_row("a")),
                           jr.row_to_request(_row("b"))]),
              snap_path, queue_name="q")
    j.compact_finish(anchor, snap_path)
    j.close()
    rep = jd.inspect_queue(str(tmp_path), "q")
    assert rep["snapshots"] and rep["snapshots"][0]["verified"]
    assert rep["snapshots"][0]["anchor_seq"] == anchor
    assert rep["intact"]
    # Corrupt the snapshot payload: verification fails, intact goes
    # False — the CLI would point the operator at the bad generation.
    path = rep["snapshots"][0]["path"]
    with open(path, "r+b") as f:
        f.seek(-8, 2)
        f.write(b"\xff" * 8)
    rep2 = jd.inspect_queue(str(tmp_path), "q")
    assert not rep2["snapshots"][0]["verified"]
    assert not rep2["intact"]
