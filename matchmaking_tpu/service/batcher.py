"""Request batcher: collects a time/size window per queue, dispatches once.

This is the structural pivot of the rebuild (BASELINE north_star: "the AMQP
consumer batches a window of incoming search requests and hands them to a
JAX sidecar"): instead of one engine call per delivery, deliveries accumulate
until ``max_batch`` or ``max_wait_ms``, whichever first, then flush as one
window. Windows per queue are serialized — the next window is not dispatched
until the previous one's flush callback returns — which is the atomicity
guarantee (a matched player is out of the pool before anyone else can see
them; SURVEY.md §7 "Hard parts").

A window's deadline is the submit time of the oldest request still pending
plus ``max_wait_ms``, on the monotonic clock: a request is held at most
``max_wait_ms``, or until the flush that is running when its deadline
falls has returned — then the next window is cut at once. The deadline
does not restart when a flush returns, so a slow flush does not add a
fresh ``max_wait_ms`` to the requests that arrived during it.

Concurrency contract: all state (``_pending``/``_submitted``/the events)
is event-loop-confined — ``submit()`` must be called from the loop, never
from a worker thread (use ``loop.call_soon_threadsafe`` to cross). There
is deliberately no lock here for matchlint's guarded-by rule to check:
the ``_run`` task and submitters interleave only at awaits, and ``_cut``
is await-free, so a window slice is atomic by construction.
"""

from __future__ import annotations

import asyncio
import time
from typing import Awaitable, Callable, Generic, TypeVar

from matchmaking_tpu.config import BatcherConfig
from matchmaking_tpu.utils.metrics import Counter

T = TypeVar("T")

#: What closed a window, each counted as ``batcher_cuts_<trigger>``:
#: ``size`` — the size event (a full window, or ``flush_hint``/``close``);
#: ``deadline`` — the oldest request's deadline fell while waiting;
#: ``overdue`` — the deadline had already passed when the previous flush
#: returned, so the window was cut with no wait.
CUT_TRIGGERS = ("size", "deadline", "overdue")


class Batcher(Generic[T]):
    def __init__(self, cfg: BatcherConfig,
                 flush: Callable[[list[T]], Awaitable[None]],
                 observe_window: Callable[[int, float], None] | None = None,
                 sort_key: "Callable[[T], object] | None" = None,
                 counters: Counter | None = None):
        self.cfg = cfg
        self._flush = flush
        #: Earliest-deadline-first window cutting (OverloadConfig.edf): when
        #: set, each cut re-orders the pending backlog by this key — the
        #: runtime keys on (tier, absolute x-deadline) — so a full window
        #: is exactly the best ``max_batch`` candidates, never an
        #: arrival-order prefix that strands a near-deadline tier-0
        #: request behind backlog. The sort is stable (FIFO within equal
        #: keys) and the key must be a pure function of the item (no clock
        #: reads — matchlint's determinism rule owns that), so cut
        #: composition replays bit-identically. PUBLIC like the
        #: max_batch/max_wait_ms live knobs below: the online autotuner's
        #: EDF toggle (_QueueRuntime.set_edf) swaps it at tick time, and
        #: the next _cut picks the change up.
        self.sort_key = sort_key
        #: Observability hook, called once per cut window with
        #: ``(window_size, open_age_seconds)`` — batch fill and batcher
        #: wait are BASELINE headline metrics (utils/metrics docstring) and
        #: the first suspect in any p99 investigation, so the batcher
        #: reports them itself instead of making callers reverse-engineer
        #: the window boundaries from item timestamps.
        self._observe = observe_window
        #: Live window knobs, initialized from the (frozen) config. The
        #: online autotuner (control/autotune.py, ISSUE 13) adjusts
        #: ``max_wait_ms`` within its declared safe range at tick time;
        #: ``_run`` re-reads it every window so a change takes effect on
        #: the NEXT cut, never mid-window. Event-loop-confined like the
        #: rest of the batcher state.
        self.max_batch = cfg.max_batch
        self.max_wait_ms = cfg.max_wait_ms
        #: Cuts by trigger (``CUT_TRIGGERS``), counted into the app's
        #: counters; created at 0 so an operator sees all three at once.
        self._counters = counters
        if counters is not None:
            for trigger in CUT_TRIGGERS:
                counters.inc(f"batcher_cuts_{trigger}", 0.0)
        self._pending: list[T] = []
        #: Per-item submit times, parallel to _pending. The oldest sets the
        #: next window's deadline, and the cut reports the OLDEST remaining
        #: item's true wait, so carried-over backlog (items sliced into a
        #: later window under saturation) is not under-reported exactly
        #: when queueing is the p99 cause.
        self._submitted: list[float] = []
        self._first = asyncio.Event()   # first item of a window arrived
        self._full = asyncio.Event()    # size trigger
        self._closed = False
        self._task = asyncio.create_task(self._run())

    def submit(self, item: T) -> None:
        if self._closed:
            raise RuntimeError("batcher closed")
        self._pending.append(item)
        self._submitted.append(time.monotonic())
        self._first.set()
        if len(self._pending) >= self.max_batch:
            self._full.set()

    def submit_many(self, items: "list[T]") -> None:
        """Burst submit (ISSUE 12, the consume_batch ingress): one extend,
        one clock read, and one trigger check for a whole consume burst —
        the per-item bookkeeping of N ``submit`` calls, amortized. The
        shared submit timestamp is the burst's arrival instant, which is
        when every member actually became pending."""
        if self._closed:
            raise RuntimeError("batcher closed")
        if not items:
            return
        self._pending.extend(items)
        self._submitted.extend([time.monotonic()] * len(items))
        self._first.set()
        if len(self._pending) >= self.max_batch:
            self._full.set()

    def _cut(self) -> list[T]:
        """Slice the next window off the pending list and report it."""
        if self.sort_key is not None and len(self._pending) > 1:
            # EDF: stable-sort the WHOLE backlog, then slice — the window
            # is the min-key prefix, and the carried-over remainder stays
            # ordered for the next cut. O(n log n) on the backlog; the
            # backlog is bounded by admission (and by prefetch without it).
            key = self.sort_key
            order = sorted(range(len(self._pending)),
                           key=lambda i: key(self._pending[i]))
            self._pending = [self._pending[i] for i in order]
            self._submitted = [self._submitted[i] for i in order]
        window = self._pending[: self.max_batch]
        self._pending = self._pending[self.max_batch:]
        if self._observe is not None and window:
            # Oldest item still PENDING at the cut (window + remainder):
            # under FIFO that is index 0, the pre-EDF behavior exactly;
            # under EDF a starved low-tier item rides the remainder across
            # many cuts, and restricting the age to the window would
            # under-report batcher wait precisely while EDF is starving
            # someone — the signal the adaptive limiter feeds on.
            age = time.monotonic() - min(self._submitted)
            self._observe(len(window), max(0.0, age))
        self._submitted = self._submitted[len(window):]
        return window

    async def _run(self) -> None:
        while not self._closed:
            if not self._pending:
                # Idle: wake immediately on the window's first item.
                self._first.clear()
                try:
                    await asyncio.wait_for(self._first.wait(), timeout=0.5)
                except asyncio.TimeoutError:
                    continue
                if not self._pending:
                    continue
            # Window open: close at the oldest pending request's deadline
            # unless the size trigger fires first. Re-read per window: the
            # autotuner may retune the wait knob. Under EDF the minimum
            # spans the carried-over remainder too, as _cut's age does.
            self._full.clear()
            trigger = "size"
            if len(self._pending) < self.max_batch:
                deadline = min(self._submitted) + self.max_wait_ms / 1000.0
                delay = deadline - time.monotonic()
                if delay <= 0.0:
                    trigger = "overdue"
                else:
                    try:
                        await asyncio.wait_for(self._full.wait(),
                                               timeout=delay)
                    except asyncio.TimeoutError:
                        trigger = "deadline"
            if not self._pending:
                continue
            if self._counters is not None:
                self._counters.inc(f"batcher_cuts_{trigger}")
            window = self._cut()
            try:
                await self._flush(window)
            except Exception:
                # The flush owner handles its own errors; a crash here must
                # not kill the batching loop (supervision logs it).
                import logging

                logging.getLogger(__name__).exception("batch flush crashed")

    def flush_hint(self) -> None:
        """Close the current window early (e.g. at shutdown)."""
        self._full.set()

    @property
    def depth(self) -> int:
        return len(self._pending)

    async def close(self) -> None:
        """Graceful close: let any in-flight flush finish (cancelling it
        would drop a window already sliced out of ``_pending``), then flush
        the remainder."""
        self._closed = True
        self._first.set()
        self._full.set()
        try:
            await asyncio.wait_for(self._task, timeout=5.0)
        except (asyncio.TimeoutError, asyncio.CancelledError):
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
        while self._pending:
            await self._flush(self._cut())
