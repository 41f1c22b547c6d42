"""The one drain of the repo: a settle predicate and the wait around it.

Instead of a wall-clock sleep racing the pipeline, tests, soaks and
benches ``settle``: they wait until every published request has FULLY
settled — the match-count target reached, nothing buffered at any stage
between the broker and the device, and no delivery of the queue held
unsettled (handed to the runtime's consumer, not yet acked or nacked).
A window's deliveries are acked only after its journal commit and its
response publish, so that clause covers egress: a window whose replies
wait on its commit has nothing left in any buffer. The conjunction must
name every buffering stage the runtime has; when a stage is added,
extend it HERE.
"""

from __future__ import annotations

import asyncio
from typing import Any, Callable

__all__ = ["fully_drained", "settle"]

#: ``settle``'s poll period (fine, so as not to quantise a bench span that
#: ends at the drain) and its hang guard (not a timing assertion: a run
#: that settles returns at the first poll that sees it).
POLL_S = 0.005
DEADLINE_S = 120.0


def fully_drained(app: Any, rt: Any, queue: str,
                  matched_at_least: int, *,
                  reply_queues: "tuple[str, ...]" = (),
                  replication: bool = True) -> bool:
    """True once ``matched_at_least`` players have matched AND the whole
    request path is empty: broker queue drained, delivery handlers idle,
    no delivery unsettled, batcher backlog cut, no flush in progress, no
    windows in flight on the device, every named reply queue consumed,
    and — with replication attached — the standby's acked watermark has
    caught the appended/sent seq (a soak that settles with an unacked
    tail would measure replication lag as "lost players").

    The replication clause is transport-agnostic by construction
    (ISSUE 20): ``repl.quiescent`` compares the sender's OWN acked/sent
    watermarks, so over the socket link it settles only once real ack
    frames have crossed the wire — reconnect gaps, scripted nemesis
    faults, and retransmissions all have to converge before a socket
    soak's quiesce returns, exactly as the in-proc wire deque does.

    ``replication=False`` drops the quiescence clause — the knob for
    soaks that DELIBERATELY hold the stream open (a scripted link
    partition never acks, so the full conjunction would never settle;
    the lag at the kill point is exactly what such a soak measures)."""
    broker = app.broker
    repl = getattr(rt, "replication", None)
    return (app.metrics.counters.get("players_matched") >= matched_at_least
            and broker.queue_depth(queue) == 0
            and broker.handlers_idle()
            and broker.unsettled(queue) == 0
            and all(broker.queue_depth(r) == 0 for r in reply_queues)
            and rt.batcher.depth == 0
            and rt._flushing == 0
            and (not hasattr(rt.engine, "inflight")
                 or rt.engine.inflight() == 0)
            and (not replication or repl is None or repl.quiescent))


async def settle(app: Any, rt: Any, queue: str, *,
                 matched_at_least: int = 0,
                 reply_queues: "tuple[str, ...]" = (),
                 standby: Any = None, replication: bool = True,
                 until: "Callable[[], bool] | None" = None) -> bool:
    """Poll ``fully_drained`` (and ``until``, a caller's extra condition)
    until both hold; True as soon as they do, False only after
    ``DEADLINE_S``. ``standby`` is pumped each poll: the replication
    clause settles only once the standby applies and acks."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + DEADLINE_S
    while loop.time() < deadline:
        await asyncio.sleep(POLL_S)
        if standby is not None:
            standby.pump()
        if (fully_drained(app, rt, queue, matched_at_least,
                          reply_queues=reply_queues,
                          replication=replication)
                and (until is None or until())):
            return True
    return False
