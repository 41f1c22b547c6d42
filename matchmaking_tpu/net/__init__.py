"""Real-transport DCN seams (ISSUE 20).

The ROADMAP's phase-3 item names ``InProcReplicationLink`` and
``LeaseAuthority`` as the explicit DCN seams — this package is the real
network layer under them:

- :mod:`~matchmaking_tpu.net.transport` — length-prefixed CRC-framed
  messages over TCP/UDS via asyncio (one shared IO thread per process),
  with connect/request timeouts, seeded exponential-backoff-with-jitter
  reconnect, application heartbeats with a deadline-based peer-liveness
  verdict, and bounded send buffers. A torn frame kills the connection,
  never corrupts the stream — resume is by cumulative ack, reusing the
  WAL seq watermark.
- :mod:`~matchmaking_tpu.net.nemesis` — deterministic network fault
  engine riding the ChaosConfig ``net_*`` vocabulary: scripted
  drop/delay/reorder/duplicate/reset/bandwidth-cap plus ASYMMETRIC
  partitions, all pure functions of (seed, connection id, frame seq).
- :mod:`~matchmaking_tpu.net.link` — ``SocketReplicationLink`` /
  ``SocketStandbyLink`` implementing the in-proc link's
  send/recv/ack/acked surface over the wire, and the
  ``SocketReplicationHub`` fabric (same surface as ``ReplicationHub``,
  so ``MatchmakingApp`` / ``QueueReplication`` / ``StandbyApplier`` run
  unchanged).
- :mod:`~matchmaking_tpu.net.lease` — ``LeaseService`` server +
  ``RemoteLeaseAuthority`` client speaking acquire/renew/takeover over
  the same transport, with renewal deadlines that budget for RTT (a
  renewal in flight when the lease expires must NOT count — fencing
  safety over liveness).
"""

from matchmaking_tpu.net.lease import LeaseService, RemoteLeaseAuthority
from matchmaking_tpu.net.link import (
    SocketReplicationHub,
    SocketReplicationLink,
    SocketStandbyLink,
)
from matchmaking_tpu.net.transport import FrameDecoder, FrameError

__all__ = [
    "FrameDecoder", "FrameError", "LeaseService", "RemoteLeaseAuthority",
    "SocketReplicationHub", "SocketReplicationLink", "SocketStandbyLink",
]
