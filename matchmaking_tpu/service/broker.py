"""In-process message broker with AMQP 0-9-1 work-queue semantics.

The reference's only transport is RabbitMQ: requests arrive on a named queue,
responses go to the per-request ``reply_to`` queue with the request's
``correlation_id``, deliveries are acked after processing, and unacked
deliveries are redelivered (at-least-once) (SURVEY.md §1 L5, §2 C2–C4).
No RabbitMQ/pika exists in this environment (SURVEY.md §7 [ENV]), so this
module implements those semantics in-process behind an interface a real AMQP
client could also satisfy; it doubles as the test fake and carries the
fault-injection hooks (drop/dup/delay — SURVEY.md §5 "Failure detection").

Semantics implemented:

- named queues, auto-declared on first use;
- competing consumers with per-consumer prefetch (basic.qos);
- ack / nack(requeue) by delivery tag; consumer cancellation requeues its
  unacked deliveries (like an AMQP channel close);
- redelivery cap with dead-lettering (counted, not silently dropped);
- RPC helper (ephemeral reply queue + correlation id) — the pattern the
  reference's auth middleware uses against ``microservice-auth`` (§2 C5).
"""

from __future__ import annotations

import asyncio
import itertools
import random
import uuid
from dataclasses import dataclass, field
from typing import Any, Awaitable, Callable

from matchmaking_tpu.config import BrokerConfig
from matchmaking_tpu.utils.trace import TraceContext


@dataclass(frozen=True, slots=True)
class Properties:
    """AMQP basic.properties subset the contract uses."""

    reply_to: str = ""
    correlation_id: str = ""
    headers: dict[str, Any] = field(default_factory=dict)


@dataclass(slots=True)
class Delivery:
    body: bytes
    properties: Properties
    queue: str
    delivery_tag: int
    redelivered: bool = False
    redelivery_count: int = 0
    #: Per-queue publish sequence number (chaos identity: fault decisions
    #: are pure functions of it, so runs replay deterministically). -1 when
    #: no chaos schedule covers the queue — nothing is counted.
    seq: int = -1
    #: Request-lifecycle trace context (utils/trace.TraceContext), stamped
    #: at publish by the in-proc broker; None from other transports until
    #: the app's ingress lazily creates one. Requeues reuse the SAME
    #: Delivery object, so stage marks survive redelivery by construction.
    trace: Any = None
    #: QoS priority tier (service/overload.py): parsed from the
    #: ``x-tier`` header at admission and cached here so the batcher's EDF
    #: sort key and the flush paths never re-parse headers. 0 = the most
    #: latency-critical tier AND the untiered default.
    tier: int = 0
    #: Cached parse of the ``x-deadline`` header (same rationale: the EDF
    #: key touches every pending delivery per cut). -1.0 = not parsed
    #: yet; 0.0 = parsed, no deadline; > 0 = absolute wall-clock deadline.
    #: Safe to cache: the header is stamped once (setdefault) and survives
    #: redelivery on the same object.
    deadline: float = -1.0
    #: Cached parse of the ``x-first-received`` header (stamped setdefault-
    #: once by the ingress middleware, which fills this cache) — the
    #: columnar flush reads it per lane, and a header parse per lane is
    #: exactly the per-delivery hot-path work ISSUE 9 removes (matchlint's
    #: perf rule now flags it). -1.0 = not cached.
    first_received: float = -1.0
    #: Batcher-submit sequence (per queue runtime): the batched admission
    #: pass decides a cut window in ARRIVAL order even after the EDF sort
    #: reordered it — batching must not reorder admission decisions.
    #: Re-stamped on every submit, so redeliveries order by re-consume
    #: time exactly as per-delivery admission did.
    arrival: int = -1
    #: Consume-time decoded row (ISSUE 12, consume_batch ingress): a
    #: ``(DecodedBurst, index)`` reference into the burst's preparsed
    #: columns, set by the ingress shard workers so the window flush
    #: assembles columns by vectorized gather instead of re-decoding.
    #: None = not burst-decoded (per-delivery path, or a redelivery whose
    #: burst is gone — the flush's contract-path fallback decodes it).
    row: Any = None


class _Queue:
    def __init__(self, name: str):
        self.name = name
        self.messages: asyncio.Queue[Delivery] = asyncio.Queue()
        self.consumers: list["_Consumer"] = []
        #: Partition gate (chaos): set = consumers flow; cleared = paused.
        self.gate = asyncio.Event()
        self.gate.set()
        #: Failsafe auto-resume timer for the CURRENT partition (cancelled
        #: on scripted resume so it cannot fire into a LATER partition).
        self.gate_timer: asyncio.TimerHandle | None = None


class _BatchState:
    """One handler task's deliveries + progress, registered BEFORE the task
    is created so ``cancel()`` can requeue them even if asyncio cancels the
    task before its first step (a never-started coroutine's try/finally
    never runs — relying on the task body alone LOSES the whole batch)."""

    __slots__ = ("batch", "i", "current")

    def __init__(self, batch: list[Delivery]):
        self.batch = batch
        self.i = 0                       # next index to start
        self.current: Delivery | None = None  # in-flight delivery, if any


class _Consumer:
    def __init__(self, broker: "InProcBroker", queue: _Queue,
                 callback: Callable[[Delivery], Awaitable[None]], prefetch: int,
                 batch_hint: bool = False,
                 batch_callback: "Callable[[list[Delivery]], Awaitable[None]] | None" = None):
        self.broker = broker
        self.queue = queue
        self.callback = callback
        self.prefetch = max(1, prefetch)
        self.unacked: dict[int, Delivery] = {}
        self.cancelled = False
        self.tag = f"ctag-{uuid.uuid4().hex[:8]}"
        #: Non-blocking-callback consumers opt in: deliveries already
        #: buffered in the queue drain into ONE handler task per sweep
        #: (sequential within the sweep) instead of one task each —
        #: measured ~2x ingress on the 1-core host. Blocking callbacks
        #: (auth-RPC middleware) keep the per-delivery task so they run
        #: CONCURRENTLY up to prefetch — the reference's Search.Worker
        #: GenServer-pool parallelism (SURVEY.md §2).
        self.batch_hint = batch_hint
        #: Columnar consume_batch seam (ISSUE 12): when set, a drained
        #: burst is handed to the app as ONE ``batch_callback(batch)``
        #: call — no per-delivery handler invocation or bookkeeping at
        #: all. Falls back to the per-delivery ``callback`` whenever the
        #: broker has consume-side fault injection armed (delay/chaos
        #: drops are per-delivery decisions whose replay identity must
        #: not change with batching).
        self.batch_callback = batch_callback
        self._burst_max = max(1, broker.cfg.consume_batch_max)
        self._cancel_requeued: set[int] = set()
        self._batch_states: set[_BatchState] = set()
        self._free = self.prefetch
        self._free_ev = asyncio.Event()
        self._handlers: set[asyncio.Task] = set()
        self._task = asyncio.create_task(self._run())

    async def _acquire(self) -> None:
        while self._free <= 0:
            self._free_ev.clear()
            await self._free_ev.wait()
        self._free -= 1

    def _release(self) -> None:
        self._free += 1
        self._free_ev.set()

    def _try_acquire(self) -> bool:
        if self._free > 0:
            self._free -= 1
            return True
        return False

    async def _run(self) -> None:
        # Deliveries are handled CONCURRENTLY up to ``prefetch`` — this is
        # the rebuild's request-level data parallelism (the reference's
        # Search.Worker GenServer pool; SURVEY.md §2 "Parallelism
        # strategies"): N in-flight handlers per consumer. batch_hint
        # consumers trade that for one task per drained burst (see above).
        while not self.cancelled:
            if not self.queue.gate.is_set():
                # Chaos partition: the queue's consumers pause here until
                # the scripted resume publish (or the failsafe timer) opens
                # the gate. Messages buffer; at-least-once holds.
                await self.queue.gate.wait()
            await self._acquire()
            try:
                delivery = await self.queue.messages.get()
            except asyncio.CancelledError:
                self._release()
                raise
            if self.cancelled:
                # Requeue and bail (channel closed mid-delivery).
                self.queue.messages.put_nowait(delivery)
                self._release()
                return
            batch = [delivery]
            if self.batch_hint or self.batch_callback is not None:
                while (len(batch) < self._burst_max
                       and not self.queue.messages.empty()
                       and self._try_acquire()):
                    batch.append(self.queue.messages.get_nowait())
            # Register BEFORE create_task: cancel() must see these
            # deliveries even if the task is cancelled before it ever runs.
            state = _BatchState(batch)
            self._batch_states.add(state)
            handler = (self._handle_burst
                       if (self.batch_callback is not None
                           and not self.broker.consume_faults_enabled)
                       else self._handle_batch)
            task = asyncio.create_task(handler(state))
            self._handlers.add(task)
            task.add_done_callback(self._handlers.discard)

    def _requeue_batch_rest(self, state: _BatchState) -> None:
        """Requeue a batch's unfinished deliveries exactly once
        (at-least-once on cancellation). Called from the task's finally OR
        from cancel() — whichever runs first empties the state so the other
        is a no-op. The in-flight delivery is requeued by the unacked sweep
        when it got that far; the _cancel_requeued/unacked checks cover the
        not-yet-registered window."""
        start = state.i
        current, state.current = state.current, None
        if current is not None:
            if (current.delivery_tag not in self.unacked
                    and current.delivery_tag not in self._cancel_requeued):
                self._release()
                self.broker._requeue(self.queue, current)
            start += 1
        for j in range(start, len(state.batch)):
            self._release()
            self.broker._requeue(self.queue, state.batch[j])
        state.i = len(state.batch)
        self._batch_states.discard(state)

    async def _handle_batch(self, state: _BatchState) -> None:
        try:
            while state.i < len(state.batch):
                state.current = state.batch[state.i]
                await self._handle(state.current)
                state.current = None
                state.i += 1
        finally:
            self._requeue_batch_rest(state)

    async def _handle_burst(self, state: _BatchState) -> None:
        """Columnar consume_batch handler (ISSUE 12): ONE app callback for
        the whole drained burst. Every delivery registers in ``unacked``
        BEFORE the callback — the at-least-once contract moves wholesale:
        cancel()'s unacked sweep requeues them, acks/nacks settle them one
        by one as the app's windows finish, and a crashing batch callback
        nack-requeues whatever it had not settled yet (exactly the
        per-delivery crash semantics, amortized)."""
        batch = state.batch
        for delivery in batch:
            self.unacked[delivery.delivery_tag] = delivery
        # The burst is owned by unacked now: the pre-start cancel sweep
        # (_requeue_batch_rest) must not requeue it a second time.
        state.i = len(batch)
        state.current = None
        self._batch_states.discard(state)
        try:
            await self.batch_callback(batch)
        except Exception:
            # A crashing burst callback must not lose deliveries: requeue
            # every one it had not settled (OTP-style let-it-crash +
            # redeliver — the per-delivery _handle contract, batched).
            self.broker.stats["consumer_errors"] += 1
            for delivery in batch:
                if delivery.delivery_tag in self.unacked:
                    self.nack(delivery.delivery_tag, requeue=True)

    async def _handle(self, delivery: Delivery) -> None:
        broker = self.broker
        if broker.consume_faults_enabled:
            # The ONE consume-side fault gate: delay, seeded/scripted chaos
            # drops, and probabilistic drops all live behind it, so a
            # fault-free broker pays zero per-delivery overhead here.
            if broker.cfg.delay_ms > 0:
                broker.stats["delayed"] += 1
                await asyncio.sleep(broker.cfg.delay_ms / 1000.0)
            chaos = broker.chaos
            if ((chaos is not None
                 and chaos.should_drop(delivery.queue, delivery.seq,
                                       delivery.redelivery_count))
                    or broker._should_drop()):
                # Fault injection: consumer "crashed" before processing —
                # the delivery is requeued as AMQP would on channel close.
                broker.stats["dropped"] += 1
                if delivery.trace is not None:
                    # The drop is part of the request's biography: the trace
                    # shows the crash point and the redelivery gap behind it.
                    delivery.trace.mark("chaos_drop")
                if broker.events is not None:
                    broker.events.append(
                        "chaos_drop", delivery.queue,
                        f"seq {delivery.seq} attempt "
                        f"{delivery.redelivery_count}")
                self._release()
                broker._requeue(self.queue, delivery)
                return
        self.unacked[delivery.delivery_tag] = delivery
        try:
            await self.callback(delivery)
        except Exception:
            # A crashing consumer callback must not lose the delivery:
            # requeue it (OTP-style let-it-crash + redeliver, §3 Entry 4).
            self.broker.stats["consumer_errors"] += 1
            self.nack(delivery.delivery_tag, requeue=True)

    def ack(self, delivery_tag: int) -> None:
        if self.unacked.pop(delivery_tag, None) is not None:
            self.broker.stats["acked"] += 1
            self._release()

    def nack(self, delivery_tag: int, requeue: bool = True) -> None:
        delivery = self.unacked.pop(delivery_tag, None)
        if delivery is None:
            return
        self._release()
        if requeue:
            self.broker._requeue(self.queue, delivery)
        else:
            self.broker.stats["dead_lettered"] += 1

    def cancel(self) -> None:
        self.cancelled = True
        self._task.cancel()
        for task in list(self._handlers):
            task.cancel()
        self._cancel_requeued = set(self.unacked)
        for delivery in list(self.unacked.values()):
            self.broker._requeue(self.queue, delivery)
        self.unacked.clear()
        # Handler tasks cancelled before their first step never run their
        # finally — sweep their registered batches here (each state empties
        # on first sweep, so a later-running finally is a no-op).
        for state in list(self._batch_states):
            self._requeue_batch_rest(state)


class InProcBroker:
    """The broker. All methods are called from one event loop."""

    def __init__(self, cfg: BrokerConfig | None = None, seed: int = 0,
                 chaos: "Any | None" = None):
        self.cfg = cfg or BrokerConfig()
        #: Deterministic chaos schedule (utils/chaos.py ChaosState), or
        #: None. Owned by the app (shared with the engine hooks) so broker
        #: and engine faults replay from one script.
        self.chaos = chaos
        #: Any consume-side fault injection configured? The hot path skips
        #: the whole per-delivery fault block when False — future consume
        #: fault kinds must extend THIS flag, not get gated out by a
        #: field-specific check inside the block.
        self.consume_faults_enabled = (
            self.cfg.delay_ms > 0 or self.cfg.drop_prob > 0
            or (chaos is not None and chaos.consume_faults())
        )
        #: Publish-side twin: dup copies and chaos storms/partitions.
        self.publish_faults_enabled = (
            self.cfg.dup_prob > 0
            or (chaos is not None and chaos.publish_faults())
        )
        #: Lifecycle event log (utils/trace.EventLog), attached by the app —
        #: chaos drops/dups, partitions and dead-letters land here so
        #: /debug/events shows broker faults on the same timeline as
        #: breaker trips and engine revives. None = not recorded.
        self.events: Any = None
        #: Trace-context stamping at publish (the flight recorder's
        #: "enqueue" mark). The app may disable it via config.
        self.trace_enabled = True
        #: Stamp every Nth request publish (ObservabilityConfig.
        #: trace_sample_n, set by the app). 1 = every publish.
        self.trace_sample_n = 1
        self._trace_count = 0
        self._queues: dict[str, _Queue] = {}
        self._tags = itertools.count(1)
        self._consumers: dict[str, _Consumer] = {}
        self._rng = random.Random(seed)
        #: Per-queue publish sequence counters (chaos identity; only
        #: advanced for queues a chaos schedule covers).
        self._pub_seq: dict[str, int] = {}
        self.stats = {
            "published": 0, "acked": 0, "dropped": 0, "duplicated": 0,
            "delayed": 0, "dead_lettered": 0, "consumer_errors": 0,
            "unroutable": 0, "partitions": 0,
        }

    # ---- queue ops --------------------------------------------------------

    def declare_queue(self, name: str) -> None:
        self._queues.setdefault(name, _Queue(name))

    def delete_queue(self, name: str) -> None:
        """Drop a queue and its buffered messages (AMQP queue.delete — used
        for ephemeral reply queues, which would otherwise leak one map entry
        per request)."""
        q = self._queues.pop(name, None)
        if q is not None:
            for consumer in list(q.consumers):
                self.basic_cancel(consumer.tag)

    def queue_depth(self, name: str) -> int:
        q = self._queues.get(name)
        return q.messages.qsize() if q else 0

    def drain_backlog(self, name: str) -> list[Delivery]:
        """Pop every delivery still buffered on ``name`` (drain handoff:
        after the queue's consumers are cancelled, these messages would die
        with the process — the app checkpoints them instead and a successor
        re-publishes them). Call only after basic_cancel'ing the queue's
        consumers, or live consumers race the pop."""
        q = self._queues.get(name)
        out: list[Delivery] = []
        if q is None:
            return out
        while not q.messages.empty():
            out.append(q.messages.get_nowait())
        return out

    def handlers_idle(self) -> bool:
        """True when no consumer has a handler task outstanding — i.e. no
        delivery is inside a created-(possibly-unstarted)-handler, which
        ``queue_depth`` cannot see. Drain/quiesce checks combine this with
        queue depths."""
        return all(not c._handlers for c in self._consumers.values())

    def unsettled(self, name: str) -> int:
        """Deliveries of ``name`` handed to a consumer and not yet acked or
        nacked. A read-only probe for drain checks
        (``testing.drain.fully_drained``); the served path never calls it."""
        q = self._queues.get(name)
        return sum(len(c.unacked) for c in q.consumers) if q else 0

    def publish(self, queue: str, body: bytes,
                properties: Properties | None = None) -> None:
        # AMQP default-exchange semantics: publishing to a queue that does
        # not exist drops the message as unroutable (it does NOT declare —
        # otherwise deleted reply queues would resurrect and leak).
        q = self._queues.get(queue)
        if q is None:
            self.stats["unroutable"] += 1
            return
        chaos = self.chaos
        seq = -1
        if chaos is not None and chaos.applies(queue):
            seq = self._pub_seq.get(queue, 0)
            self._pub_seq[queue] = seq + 1
        props = properties or Properties()
        # Stamp a trace only on publishes that expect a response (reply_to
        # set — i.e. requests): response publishes to reply queues are
        # consumed by clients, never settled by a runtime, and at north-star
        # match rates they would allocate as many dead contexts as live
        # ones. Requests published without reply_to still get a trace
        # lazily at ingress (the enqueue stage then reads 0).
        stamp = self.trace_enabled and bool(props.reply_to)
        if stamp and self.trace_sample_n > 1:
            # Sample-N tracing (ROADMAP PR 3 follow-up): only every Nth
            # request publish allocates a context; the counter advances
            # per stampable publish so the sample is uniform over requests,
            # not over mixed request/response traffic.
            self._trace_count += 1
            stamp = self._trace_count % self.trace_sample_n == 1
        delivery = Delivery(
            body=bytes(body), properties=props,
            queue=queue, delivery_tag=next(self._tags), seq=seq,
            trace=(TraceContext(queue, props.correlation_id)
                   if stamp else None),
        )
        self.stats["published"] += 1
        q.messages.put_nowait(delivery)
        if not self.publish_faults_enabled:
            return
        if self.cfg.dup_prob > 0 and self._rng.random() < self.cfg.dup_prob:
            # Fault injection: duplicate delivery (at-least-once world).
            self.stats["duplicated"] += 1
            dup = Delivery(body=bytes(body), properties=delivery.properties,
                           queue=queue, delivery_tag=next(self._tags),
                           redelivered=True,
                           trace=(TraceContext(queue, props.correlation_id,
                                               redelivered=True)
                                  if stamp else None))
            q.messages.put_nowait(dup)
        if chaos is None or seq < 0:
            return
        # Chaos storms: extra copies get their OWN publish seqs (they are
        # distinct deliveries for drop accounting) but are never themselves
        # re-evaluated for duplication — a storm cannot cascade. Each copy
        # also gets its own trace context (stamped at this same publish), so
        # a duplicated redelivery's lifecycle is separately attributable.
        n_copies = chaos.dup_copies(queue, seq)
        if n_copies and self.events is not None:
            self.events.append("chaos_dup", queue,
                               f"seq {seq} +{n_copies} copies")
        for _ in range(n_copies):
            cseq = self._pub_seq[queue]
            self._pub_seq[queue] = cseq + 1
            self.stats["duplicated"] += 1
            q.messages.put_nowait(Delivery(
                body=bytes(body), properties=delivery.properties,
                queue=queue, delivery_tag=next(self._tags),
                redelivered=True, seq=cseq,
                trace=(TraceContext(queue, props.correlation_id,
                                    redelivered=True)
                       if stamp else None)))
        action = chaos.partition_action(queue, seq)
        if action == "pause":
            self._pause(q)
        elif action == "resume":
            self._resume(q)

    def publish_batch(self, items) -> None:
        """Publish a whole window of RESPONSE messages in one call — the
        window-granular egress seam (ISSUE 9): per-response publish()
        bookkeeping (trace sampling, chaos seq accounting, fault rolls)
        collapses to one loop of queue pushes. Items that DO need the
        per-message machinery — a reply_to set (request publishes stamp
        traces), a chaos schedule covering the queue (seq counters must
        advance), or any publish-side fault injection armed — take the
        full publish() path, so batching never changes semantics, only
        per-call overhead. ``items``: (queue, body, Properties|None)."""
        for queue, body, props in items:
            props = props or Properties()
            if (self.publish_faults_enabled
                    or (self.chaos is not None and self.chaos.applies(queue))
                    or (self.trace_enabled and props.reply_to)):
                self.publish(queue, body, props)
                continue
            q = self._queues.get(queue)
            if q is None:
                self.stats["unroutable"] += 1
                continue
            self.stats["published"] += 1
            q.messages.put_nowait(Delivery(
                body=bytes(body), properties=props, queue=queue,
                delivery_tag=next(self._tags)))

    def basic_consume(self, queue: str,
                      callback: Callable[[Delivery], Awaitable[None]],
                      prefetch: int | None = None,
                      batch_hint: bool = False,
                      batch_callback: "Callable[[list[Delivery]], Awaitable[None]] | None" = None) -> str:
        self.declare_queue(queue)
        consumer = _Consumer(self, self._queues[queue], callback,
                             prefetch or self.cfg.prefetch,
                             batch_hint=batch_hint,
                             batch_callback=batch_callback)
        self._queues[queue].consumers.append(consumer)
        self._consumers[consumer.tag] = consumer
        return consumer.tag

    def basic_cancel(self, consumer_tag: str) -> None:
        consumer = self._consumers.pop(consumer_tag, None)
        if consumer is not None:
            consumer.cancel()
            consumer.queue.consumers.remove(consumer)

    def ack(self, consumer_tag: str, delivery_tag: int) -> None:
        # A late ack after basic_cancel is a no-op: the cancel already
        # requeued the delivery (at-least-once; dedup absorbs the replay).
        consumer = self._consumers.get(consumer_tag)
        if consumer is not None:
            consumer.ack(delivery_tag)

    def nack(self, consumer_tag: str, delivery_tag: int, requeue: bool = True) -> None:
        consumer = self._consumers.get(consumer_tag)
        if consumer is not None:
            consumer.nack(delivery_tag, requeue)

    async def get(self, queue: str, timeout: float | None = None) -> Delivery | None:
        """basic.get analog for clients awaiting replies (no consumer)."""
        self.declare_queue(queue)
        q = self._queues[queue]
        try:
            if timeout is None:
                return await q.messages.get()
            return await asyncio.wait_for(q.messages.get(), timeout)
        except asyncio.TimeoutError:
            return None

    async def rpc(self, queue: str, body: bytes, timeout: float) -> bytes | None:
        """Publish with an ephemeral reply queue; await the correlated reply."""
        reply_queue = f"amq.gen-{uuid.uuid4().hex}"
        corr = uuid.uuid4().hex
        self.declare_queue(reply_queue)  # before publish: replies must route
        self.publish(queue, body, Properties(reply_to=reply_queue, correlation_id=corr))
        deadline = asyncio.get_event_loop().time() + timeout
        try:
            while True:
                remaining = deadline - asyncio.get_event_loop().time()
                if remaining <= 0:
                    return None
                reply = await self.get(reply_queue, timeout=remaining)
                if reply is None:
                    return None
                if reply.properties.correlation_id == corr:
                    return reply.body
        finally:
            self.delete_queue(reply_queue)  # exclusive reply queues auto-delete

    def close(self) -> None:
        for tag in list(self._consumers):
            self.basic_cancel(tag)

    # ---- fault injection --------------------------------------------------

    def _should_drop(self) -> bool:
        return self.cfg.drop_prob > 0 and self._rng.random() < self.cfg.drop_prob

    def _pause(self, q: _Queue) -> None:
        """Chaos partition: pause the queue's consumers. The scripted
        resume publish re-opens the gate; a wall-clock failsafe
        (ChaosConfig.partition_max_s) guards against schedules whose
        resume seq never arrives — a chaos script must not wedge a drain."""
        if not q.gate.is_set():
            return
        q.gate.clear()
        self.stats["partitions"] += 1
        if self.events is not None:
            self.events.append("partition_pause", q.name)
        max_s = self.chaos.cfg.partition_max_s if self.chaos else 0.0
        if max_s > 0:
            try:
                q.gate_timer = asyncio.get_running_loop().call_later(
                    max_s, lambda: self._resume(q))
            except RuntimeError:  # pragma: no cover - no running loop
                pass

    def _resume(self, q: _Queue) -> None:
        if q.gate_timer is not None:
            q.gate_timer.cancel()
            q.gate_timer = None
        if not q.gate.is_set():
            q.gate.set()
            if self.events is not None:
                self.events.append("partition_resume", q.name)

    def _requeue(self, queue: _Queue, delivery: Delivery) -> None:
        if delivery.redelivery_count >= self.cfg.max_redelivery:
            self.stats["dead_lettered"] += 1
            if self.events is not None:
                self.events.append("dead_letter", queue.name,
                                   f"tag {delivery.delivery_tag}")
            return
        delivery.redelivered = True
        delivery.redelivery_count += 1
        queue.messages.put_nowait(delivery)
