"""Child-process runner for the cross-process failover soak (ISSUE 20,
``bench.py --failover-soak --transport=socket``).

The driver (bench.py) spawns one ``lease`` child (the shared
LeaseService — the part of the deployment that outlives every host) and
a chain of ``host`` children. Each host child boots, attaches as the
WARM STANDBY of the current primary (real socket stream + real lease
RPCs), and on command takes over — waiting out the REAL lease expiry —
and boots a MatchmakingApp adopting its shadow. The driver then SIGKILLs
the old primary mid-load; invariants are gated on what crossed the wire,
not on shared memory.

Protocol: JSON lines — commands on stdin, events on stdout (stdout
carries ONLY protocol lines; logging goes to stderr). Every command gets
exactly one reply event carrying the command's ``id``.

Host commands: ``standby`` (attach + pump thread), ``takeover`` (retry
until the lease actually expires), ``serve`` (boot the app streaming to
``target``), ``publish`` (designed load into the local broker, replies
accumulate ``match_of``), ``quiesce`` (``testing.drain.settle``),
``deafen`` (arm an asymmetric partition on the local nemesis),
``probe`` (drive both fencing seams and report refusals), ``report``
(replication watermarks / waiting set / match_of / counters), ``stop``.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import threading
import time
from typing import Any

_Q_RATE_BURST = 4  # publish pacing: sleep every 4th row, like the bench


def _emit(ev: "dict[str, Any]") -> None:
    sys.stdout.write(json.dumps(ev, sort_keys=True) + "\n")
    sys.stdout.flush()


def _chaos_from_json(blob: str):
    from matchmaking_tpu.config import ChaosConfig

    d = json.loads(blob) if blob else {}

    def tt(v):  # JSON lists back to the tuple-of-tuples ChaosConfig shape
        return tuple(tuple(e) if isinstance(e, list) else e for e in (v or ()))

    return ChaosConfig(
        seed=int(d.get("seed", 0)), queues=tuple(d.get("queues", ())),
        net_drop_frames=tt(d.get("net_drop_frames")),
        net_dup_frames=tt(d.get("net_dup_frames")),
        net_delay_frames=tt(d.get("net_delay_frames")),
        net_reset_frames=tt(d.get("net_reset_frames")),
        net_partitions=tt(d.get("net_partitions")),
        net_deaf_flows=tuple(d.get("net_deaf_flows", ())),
        net_drop_prob=float(d.get("net_drop_prob", 0.0)),
        net_bandwidth_caps=tt(d.get("net_bandwidth_caps")))


async def _run_lease(args) -> None:
    from matchmaking_tpu.config import NetConfig
    from matchmaking_tpu.net.lease import LeaseService

    svc = LeaseService(args.lease_addr, lease_s=float(args.lease_s),
                       net=NetConfig(transport="socket"))
    svc.start()
    _emit({"ev": "ready", "role": "lease", "addr": args.lease_addr})
    loop = asyncio.get_running_loop()
    stop = asyncio.Event()

    def _reader() -> None:
        for line in sys.stdin:
            if json.loads(line).get("cmd") == "stop":
                loop.call_soon_threadsafe(stop.set)
                return
        loop.call_soon_threadsafe(stop.set)

    threading.Thread(target=_reader, daemon=True).start()
    await stop.wait()
    svc.close()
    _emit({"ev": "stopped", "role": "lease"})


class _HostChild:
    """One host generation: standby → (takeover) → primary → killed."""

    def __init__(self, args):
        from matchmaking_tpu.config import NetConfig
        from matchmaking_tpu.net.link import SocketReplicationHub

        self.q = args.queue
        self.name = args.name
        self.seed = int(args.seed)
        self.lease_s = float(args.lease_s)
        self.chaos = _chaos_from_json(args.chaos)
        self.net = NetConfig(
            transport="socket", lease_addr=args.lease_addr,
            heartbeat_timeout_s=float(args.heartbeat_timeout_s))
        self.hub = SocketReplicationHub(
            net=self.net, chaos=self.chaos, seed=self.seed, owner=self.name)
        self.app = None
        self.rt = None
        self.sap = None
        self._pump = True
        self._pump_thread: "threading.Thread | None" = None
        self.match_of: "dict[str, list[str]]" = {}
        self.reply_q = f"failover.replies.{self.name}"

    # -- commands ------------------------------------------------------------

    def cmd_standby(self, msg) -> dict:
        self.sap = self.hub.standby(self.q, owner=self.name,
                                    listen=msg["listen"])

        def pump_loop() -> None:
            while self._pump:
                try:
                    self.sap.pump()
                except Exception:
                    import logging

                    logging.getLogger(__name__).exception("standby pump")
                time.sleep(0.005)

        self._pump_thread = threading.Thread(target=pump_loop, daemon=True)
        self._pump_thread.start()
        return {"ev": "standby_up"}

    def cmd_takeover(self, msg) -> dict:
        from matchmaking_tpu.service.replication import LeaseHeldError

        deadline = time.monotonic() + float(msg.get("timeout_s", 30.0))
        self._pump = False
        if self._pump_thread is not None:
            self._pump_thread.join(timeout=5.0)
        # REAL expiry: no scriptable clock across processes — retry until
        # the authority stops seeing a live holder. (``force`` stays
        # False: promoting past a live lease is exactly split-brain.)
        while True:
            try:
                epoch = self.sap.takeover(time.monotonic())
                return {"ev": "took_over", "epoch": epoch,
                        "applied_seq": self.sap.applied_seq}
            except LeaseHeldError:
                if time.monotonic() >= deadline:
                    return {"ev": "error", "error": "takeover timeout: "
                            "lease never expired"}
                time.sleep(0.02)

    async def cmd_serve(self, msg) -> dict:
        from matchmaking_tpu.config import (
            BatcherConfig,
            Config,
            DurabilityConfig,
            EngineConfig,
            QueueConfig,
            ReplicationConfig,
        )
        from matchmaking_tpu.service.app import MatchmakingApp

        self.hub.set_target(self.q, msg["target"])
        cfg = Config(
            queues=(QueueConfig(name=self.q, rating_threshold=50.0,
                                dedup_ttl_s=3600.0,
                                send_queued_ack=False),),
            engine=EngineConfig(backend="tpu", pool_capacity=4096,
                                pool_block=512, batch_buckets=(16, 64),
                                top_k=8, warm_start=True),
            batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
            durability=DurabilityConfig(journal_dir=msg["jdir"],
                                        fsync="window"),
            replication=ReplicationConfig(role="primary", owner=self.name),
            chaos=self.chaos)
        self.app = MatchmakingApp(cfg, replication_hub=self.hub)
        await self.app.start()
        self.rt = self.app.runtime(self.q)
        self.app.broker.declare_queue(self.reply_q)

        async def on_reply(delivery) -> None:
            d = json.loads(delivery.body)
            if d.get("status") == "matched":
                pid = str(d.get("player_id", ""))
                mid = (d.get("match") or {}).get("match_id")
                if pid and mid:
                    ids = self.match_of.setdefault(pid, [])
                    if mid not in ids:
                        ids.append(mid)

        self.app.broker.basic_consume(self.reply_q, on_reply,
                                      prefetch=1_000_000)
        rto = self.app.metrics.gauges.get(f"failover_rto_ms[{self.q}]")
        rec = self.rt.last_recovery
        return {"ev": "serving",
                "recovered": sorted(r.id for r in self.rt.engine.waiting()),
                "rto_ms": rto,
                "transcript": rec["transcript"] if rec else None}

    async def cmd_publish(self, msg) -> dict:
        from matchmaking_tpu.service.broker import Properties

        gap = 1.0 / max(1.0, float(msg.get("rate", 500.0)))
        for k, (pid, rating) in enumerate(msg["rows"]):
            self.app.broker.publish(
                self.q, f'{{"id":"{pid}","rating":{rating}}}'.encode(),
                Properties(reply_to=self.reply_q, correlation_id=pid))
            if k % _Q_RATE_BURST == _Q_RATE_BURST - 1:
                await asyncio.sleep(gap * _Q_RATE_BURST)
        return {"ev": "published", "n": len(msg["rows"])}

    async def cmd_quiesce(self, msg) -> dict:
        from matchmaking_tpu.testing.drain import settle

        try:
            ok = await asyncio.wait_for(
                settle(self.app, self.rt, self.q,
                       matched_at_least=int(msg.get("matched_at_least", 0)),
                       reply_queues=(self.reply_q,),
                       replication=bool(msg.get("replication", True))),
                float(msg.get("timeout_s", 30.0)))
        except asyncio.TimeoutError:
            ok = False
        return {"ev": "quiesced", "ok": ok}

    def cmd_deafen(self, msg) -> dict:
        self.hub.nemesis.deafen(msg["pattern"])
        return {"ev": "deafened", "pattern": msg["pattern"]}

    async def cmd_probe(self, msg) -> dict:
        """Drive BOTH fencing seams on this (presumed superseded)
        primary and report what they did. Waits for the role flip first:
        remote fencing is asynchronous (a budgeted lease deadline has to
        lapse), unlike the in-proc authority's instant epoch check."""
        from matchmaking_tpu.utils.journal import FencedError

        deadline = time.monotonic() + float(msg.get("timeout_s", 10.0))
        repl = self.rt.replication
        while (repl.role != "fenced" and not repl.superseded()
               and time.monotonic() < deadline):
            await asyncio.sleep(0.01)
        before = self.app.metrics.counters.get("fenced_publish_refused")
        pubs_before = self.app.broker.stats.get("published", 0)
        self.rt._publish_body(self.reply_q, "fence-probe", b"{}")
        refused = (self.app.metrics.counters.get("fenced_publish_refused")
                   > before)
        pubs_after = self.app.broker.stats.get("published", 0)
        append_fenced = False
        try:
            self.rt.journal.append_terminal("fence-probe", b"{}",
                                            time.time() + 60.0)
        except FencedError:
            append_fenced = True
        return {"ev": "probe", "role": repl.role,
                "publish_refused": bool(refused),
                "publish_leaked": pubs_after > pubs_before,
                "append_fenced": append_fenced}

    def cmd_report(self, msg) -> dict:
        out: "dict[str, Any]" = {"ev": "report", "name": self.name}
        if self.sap is not None:
            out["applied_seq"] = self.sap.applied_seq
        if self.rt is not None:
            repl = self.rt.replication
            link = self.hub._links.get(self.q)
            out.update({
                "role": repl.role, "epoch": repl.epoch,
                "sent_seq": repl.sent_seq, "acked_seq": repl.acked_seq,
                "kill_bound": repl.unacked_admit_players(),
                "waiting": sorted(r.id for r in self.rt.engine.waiting()),
                "matched": self.app.metrics.counters.get("players_matched"),
                "link": dict(link.counters) if link is not None else {},
            })
        out["match_of"] = self.match_of
        if self.sap is not None:
            out["standby_link"] = dict(self.sap.link.counters)
        return out

    async def cmd_stop(self, msg) -> dict:
        self._pump = False
        if self.app is not None:
            await self.app.stop()
        self.hub.close()
        return {"ev": "stopped", "name": self.name}


async def _run_host(args) -> None:
    child = _HostChild(args)
    _emit({"ev": "ready", "role": "host", "name": args.name})
    loop = asyncio.get_running_loop()
    inbox: "asyncio.Queue[dict | None]" = asyncio.Queue()

    def _reader() -> None:
        for line in sys.stdin:
            line = line.strip()
            if line:
                msg = json.loads(line)
                loop.call_soon_threadsafe(inbox.put_nowait, msg)
        loop.call_soon_threadsafe(inbox.put_nowait, None)

    threading.Thread(target=_reader, daemon=True).start()
    while True:
        msg = await inbox.get()
        if msg is None:
            return
        cmd = msg.get("cmd", "")
        try:
            handler = getattr(child, f"cmd_{cmd}")
            reply = handler(msg)
            if asyncio.iscoroutine(reply):
                reply = await reply
        except Exception as exc:  # surface, don't die: the driver gates
            import logging

            logging.getLogger(__name__).exception("cmd %r failed", cmd)
            reply = {"ev": "error", "cmd": cmd, "error": repr(exc)}
        reply["id"] = msg.get("id")
        _emit(reply)
        if reply.get("ev") == "stopped":
            return


def main(argv: "list[str] | None" = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("role", choices=("lease", "host"))
    p.add_argument("--name", default="host")
    p.add_argument("--queue", default="failover.soak")
    p.add_argument("--lease-addr", default="")
    p.add_argument("--lease-s", default="2.0")
    p.add_argument("--heartbeat-timeout-s", default="0.6")
    p.add_argument("--seed", default="0")
    p.add_argument("--chaos", default="",
                   help="JSON ChaosConfig subset (net_* script)")
    args = p.parse_args(argv)
    if args.role == "lease":
        asyncio.run(_run_lease(args))
    else:
        asyncio.run(_run_host(args))


if __name__ == "__main__":
    main()
