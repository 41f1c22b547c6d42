"""Request-lifecycle flight recorder (SURVEY.md §5 per-stage tracing).

The BASELINE north star asserts a p99; this module is what *explains* one.
Three pieces, all bounded-memory:

- ``TraceContext`` — a lightweight per-delivery trace: an id plus an
  append-only list of ``(stage, wall_clock)`` marks. Stamped at broker
  publish (the "enqueue" mark) and carried on the ``Delivery`` through
  middleware → dedup → batcher → engine window dispatch → settle/publish.
  Marks survive redelivery (the same Delivery object is requeued), and a
  chaos duplicate gets its OWN context stamped at the same publish — a
  trace is the biography of one delivery attempt stream, monotone by
  construction (append order is time order).
- ``FlightRecorder`` — per-queue bounded ring of completed traces plus a
  separate ring of *slow exemplars*: any trace whose enqueue→publish span
  exceeds the configured threshold keeps its full stage breakdown. On
  completion, every adjacent mark pair feeds the shared per-stage latency
  histograms (utils/metrics.py) — the true-histogram replacement for the
  averages-only ``span_report``. A window's traces settle together, as
  columns (``complete_window``).
- ``EventLog`` — one bounded ring of lifecycle events (breaker trips,
  probes, delegations, re-promotions, revives, chaos faults, partitions,
  dead-letters) that were previously only visible as scattered counters.
  The ``/debug/events`` surface.

Stage vocabulary (each stage's duration = its mark minus the previous
mark): enqueue → consume → middleware → batch → flush → dispatch → h2d →
device_step → readback_seal → collect → publish, with off-nominal marks
interleaved where they happen (chaos_drop, dedup_replay, oracle_step,
reject). Window-level marks (dispatch..collect) are recorded once per
engine window and merged into every member trace at settle time, so
histogram counts for those stages are per-request attributions.

Two more pieces put the host stages into a ``jax.profiler`` capture:

- ``stage`` — a span named ``mm.<name>`` on the profiler's host timeline
  (one ``TraceAnnotation``), plus its ``perf_counter`` duration added to a
  counter. Spans are opened per burst, window or tick, never per request,
  and never held across an ``await``.
- ``ProcessHooks`` — the event loop's selector wait (``mm.loop_idle``,
  counter ``loop_idle_s``) and the garbage collector's pauses (``mm.gc``,
  counters ``gc_s`` / ``gc_pauses`` and per generation), installed once
  per process while an app runs.

One clock: the profiler stamps host events on the wall clock that
``time.time()`` reads (a capture stores them relative to its
``profile_start_time``), so the recorder's marks and the spans line up
without conversion.
"""

from __future__ import annotations

import gc
import itertools
import logging
import sys
import threading
import time
from collections import deque
from typing import Any, Iterable

import numpy as np

log = logging.getLogger(__name__)


class _NoAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation`` in a process that
    has not imported JAX (no profiler can be recording there)."""

    __slots__ = ()

    def __init__(self, name: str, **args: Any) -> None:
        pass

    @staticmethod
    def is_enabled() -> bool:
        return False

    def __enter__(self) -> "_NoAnnotation":
        return self

    def __exit__(self, *exc: Any) -> None:
        return None


_annotation: Any = None


def _annotation_type() -> Any:
    """``jax.profiler.TraceAnnotation`` once JAX is loaded, else a no-op.
    Never imports JAX itself: a host-only deployment does not pay for it."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return _NoAnnotation
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


class stage:
    """``with stage(name, sink, key, token=...):`` — one span ``mm.<name>``
    on the profiler's host timeline, its ``args`` attached (the window's
    ``token`` joins a window's spans across threads). With a ``sink``, the
    span's ``perf_counter`` duration is added to ``sink[key]`` (``key``
    defaults to ``name + "_s"``). With the profiler off no annotation is
    made, so the cost is one enabled check and the counter update."""

    __slots__ = ("_name", "_args", "_ann", "_sink", "_key", "_t")

    def __init__(self, name: str, sink: "dict | None" = None,
                 key: "str | None" = None, **args: Any) -> None:
        self._name = name
        self._args = args
        self._sink = sink
        self._key = key

    def __enter__(self) -> "stage":
        annotation = _annotation_type()
        if annotation.is_enabled():
            self._ann = annotation("mm." + self._name, **self._args)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        if self._sink is not None:
            key = self._key if self._key is not None else self._name + "_s"
            self._sink[key] += time.perf_counter() - self._t
        if self._ann is not None:
            self._ann.__exit__(*exc)


class ProcessHooks:
    """The process-wide stall hooks, shared by every running app.

    - ``loop_idle``: the event loop's selector ``select`` call is wrapped,
      so the time the loop waits for I/O or a timer becomes the counter
      ``loop_idle_s`` (a loop near 0 idle is saturated) and an
      ``mm.loop_idle`` span.
    - ``gc``: a ``gc.callbacks`` entry times each collection into
      ``gc_s`` / ``gc_pauses`` and their ``_gen<n>`` splits, with an
      ``mm.gc`` span.

    ``attach(counters, loop)`` registers an app's counters (anything with
    ``inc(name, value)``); ``detach`` removes them, and the hooks come out
    when the last app on a loop (selector) or in the process (gc) leaves.
    A loop with no selector to wrap (a non-selector event loop) is logged
    once and counted without ``loop_idle``."""

    _lock = threading.Lock()
    #: loop → (its wrapped selector or None, the counters attached there)
    _loops: dict = {}
    #: every attached counters object, for the collector's pauses
    _sinks: list = []
    _gc_t = 0.0
    _gc_ann: Any = None
    _warned = False

    @classmethod
    def attach(cls, counters: Any, loop: Any) -> None:
        with cls._lock:
            if not cls._sinks:
                gc.callbacks.append(cls._on_gc)
            cls._sinks.append(counters)
            entry = cls._loops.get(loop)
            if entry is None:
                selector = getattr(loop, "_selector", None)
                select = getattr(selector, "select", None)
                if select is None:
                    if not cls._warned:
                        cls._warned = True
                        log.info("event loop %s has no selector to wrap; "
                                 "loop_idle_s is not counted",
                                 type(loop).__name__)
                    entry = (None, [])
                else:
                    entry = (selector, [])
                    selector.select = cls._wrap(select, entry[1])
                cls._loops[loop] = entry
            entry[1].append(counters)

    @classmethod
    def detach(cls, counters: Any, loop: Any) -> None:
        with cls._lock:
            if counters in cls._sinks:
                cls._sinks.remove(counters)
                if not cls._sinks and cls._on_gc in gc.callbacks:
                    gc.callbacks.remove(cls._on_gc)
            entry = cls._loops.get(loop)
            if entry is None or counters not in entry[1]:
                return
            entry[1].remove(counters)
            if not entry[1]:
                del cls._loops[loop]
                if entry[0] is not None:
                    # The wrapper lives on the instance; removing it
                    # uncovers the selector class's own method again.
                    del entry[0].select

    @classmethod
    def installed(cls, loop: Any = None) -> bool:
        """Whether the gc hook (and, given ``loop``, its selector wrapper)
        is in place."""
        if loop is not None:
            entry = cls._loops.get(loop)
            if entry is None or entry[0] is None:
                return False
            if "select" not in vars(entry[0]):
                return False
        return cls._on_gc in gc.callbacks

    @staticmethod
    def _wrap(select: Any, sinks: list) -> Any:
        perf = time.perf_counter

        def wrapped(timeout: "float | None" = None) -> Any:
            annotation = _annotation_type()
            ann = (annotation("mm.loop_idle") if annotation.is_enabled()
                   else None)
            if ann is not None:
                ann.__enter__()
            t = perf()
            try:
                return select(timeout)
            finally:
                waited = perf() - t
                for counters in sinks:
                    counters.inc("loop_idle_s", waited)
                if ann is not None:
                    ann.__exit__(None, None, None)

        return wrapped

    @classmethod
    def _on_gc(cls, phase: str, info: dict) -> None:
        # Collections never nest and hold the interpreter lock throughout,
        # so one slot carries a collection from its start to its stop.
        if phase == "start":
            annotation = _annotation_type()
            if annotation.is_enabled():
                cls._gc_ann = annotation("mm.gc",
                                         generation=info["generation"])
                cls._gc_ann.__enter__()
            cls._gc_t = time.perf_counter()
            return
        paused = time.perf_counter() - cls._gc_t
        if cls._gc_ann is not None:
            cls._gc_ann.__exit__(None, None, None)
            cls._gc_ann = None
        gen = info["generation"]
        for counters in tuple(cls._sinks):
            counters.inc("gc_s", paused)
            counters.inc("gc_pauses")
            counters.inc(f"gc_s_gen{gen}", paused)
            counters.inc(f"gc_pauses_gen{gen}")

#: Marks recorded once per engine window and merged into member traces.
WINDOW_STAGES = ("dispatch", "h2d", "device_step", "readback_seal",
                 "collect", "oracle_step")

_trace_seq = itertools.count(1)


class TraceContext:
    """One delivery's lifecycle marks. Cheap by design (``__slots__``, one
    list) — it is allocated on EVERY broker publish."""

    __slots__ = ("trace_id", "queue", "correlation_id", "player_id",
                 "redelivered", "status", "tier", "marks", "quality",
                 "waited_s")

    def __init__(self, queue: str, correlation_id: str = "",
                 redelivered: bool = False, t: float | None = None):
        self.trace_id = f"{queue}#{next(_trace_seq)}"
        self.queue = queue
        self.correlation_id = correlation_id
        self.player_id = ""
        self.redelivered = redelivered
        self.status = ""  # set at settle: matched/queued/rejected/...
        #: QoS priority tier (service/overload.py; 0 = untiered default),
        #: stamped at admission so attribution can split per tier.
        self.tier = 0
        #: Outcome values stamped at publish for MATCHED traces (ISSUE 8):
        #: the match's quality scalar and the engine-observed wait-at-match
        #: (dispatch − enqueue, seconds). -1.0 = not matched / not stamped
        #: — lets the quality reconciliation soak recompute histograms
        #: from settled traces.
        self.quality = -1.0
        self.waited_s = -1.0
        self.marks: list[tuple[str, float]] = [
            ("enqueue", time.time() if t is None else t)]

    def mark(self, stage: str, t: float | None = None) -> None:
        self.marks.append((stage, time.time() if t is None else t))

    def extend(self, marks: Iterable[tuple[str, float]]) -> None:
        self.marks.extend(marks)

    @property
    def total_s(self) -> float:
        return self.marks[-1][1] - self.marks[0][1]

    def to_dict(self) -> dict[str, Any]:
        t0 = self.marks[0][1]
        return {
            "trace_id": self.trace_id,
            "queue": self.queue,
            "player_id": self.player_id,
            "correlation_id": self.correlation_id,
            "redelivered": self.redelivered,
            "status": self.status,
            "tier": self.tier,
            **({"quality": round(self.quality, 6),
                "waited_ms": round(self.waited_s * 1e3, 3)}
               if self.quality >= 0.0 else {}),
            "enqueue_t": t0,
            "total_ms": round(self.total_s * 1e3, 3),
            #: absolute wall-clock marks (monotone non-decreasing)
            "marks": [(name, t) for name, t in self.marks],
            #: per-stage breakdown: duration attributed to the LATER mark
            "stages_ms": {
                f"{i}:{name}": round((t - self.marks[i - 1][1]) * 1e3, 3)
                for i, (name, t) in enumerate(self.marks) if i
            },
        }


class EventLog:
    """Bounded ring of lifecycle events — the single place trips, probes,
    delegations, re-promotions, revives and chaos faults become a readable
    timeline instead of counter deltas. Appended from the event loop AND
    engine worker threads (delegation events fire inside to_thread).

    Since ISSUE 18 every append is stamped onto the app's causal
    EventSpine (utils/forensics.py): rows carry the process-wide monotone
    ``seq`` + ``mono_ns`` pair and a ``component`` tag, plus optional
    ``refs`` linking causal neighbors (epoch, decision id, WAL range).
    ``snapshot()`` orders by SEQ, not wall clock — two events in the same
    millisecond can no longer render out of causal order, and a worker
    thread that drew its seq but lost the append race to the ring no
    longer appears late."""

    def __init__(self, maxlen: int = 512, spine=None):
        self._events: deque[dict[str, Any]] = deque(maxlen=max(1, maxlen))
        if spine is None:
            # Standalone EventLog (tests, subsystems constructed without
            # an app): own a private spine so rows are shaped identically.
            from matchmaking_tpu.utils.forensics import EventSpine

            spine = EventSpine(ring=max(1, maxlen))
        self.spine = spine

    def append(self, kind: str, queue: str = "", detail: str = "",
               component: str = "",
               refs: "dict[str, Any] | None" = None) -> dict[str, Any]:
        ev = self.spine.stamp(kind, queue, detail, component=component,
                              refs=refs)
        self._events.append(ev)
        return ev

    def __len__(self) -> int:
        return len(self._events)

    def snapshot(self, queue: str | None = None,
                 limit: int = 0) -> list[dict[str, Any]]:
        # tuple() first: worker threads append concurrently, and iterating
        # a live deque across their mutations raises RuntimeError.
        rows = [
            {"seq": ev["seq"], "t": ev["wall"], "mono_ns": ev["mono_ns"],
             "component": ev["component"], "kind": ev["kind"],
             "queue": ev["queue"], "detail": ev["detail"],
             "refs": ev["refs"]}
            for ev in tuple(self._events)
            if queue is None or ev["queue"] == queue
        ]
        # Causal order is the SEQ order: ring append order can diverge
        # when a worker thread is preempted between its seq draw and the
        # stamp landing (the old wall-clock-only sort had the same hole
        # one level up).
        rows.sort(key=lambda r: r["seq"])
        return rows[-limit:] if limit else rows


#: Below this many traces of one mark signature, ``complete_window``
#: settles them one at a time: numpy's fixed cost per call outweighs what a
#: column saves on a handful of rows.
_COLUMN_MIN_TRACES = 8


class FlightRecorder:
    """Per-queue rings of completed traces + slow exemplars; feeds the
    per-stage histograms on every completion.

    ``complete`` settles one trace; ``complete_window`` settles a window's
    traces together, as columns, and records exactly what a loop of
    ``complete`` would. The counters ``settled_single`` and
    ``settled_columnar`` count the traces settled each way."""

    def __init__(self, metrics, ring: int = 256, slow_ring: int = 64,
                 slow_threshold_s: float = 0.25):
        self._metrics = metrics
        self._ring = max(1, ring)
        self._slow_ring = max(1, slow_ring)
        self.slow_threshold_s = slow_threshold_s
        self._recent: dict[str, deque[TraceContext]] = {}
        self._slow: dict[str, deque[TraceContext]] = {}
        #: Critical-path attribution sink (service/attribution.Attribution),
        #: attached by the app: every settled trace is decomposed into
        #: work-vs-wait categories alongside the stage histograms. None =
        #: attribution off.
        self.attribution = None

    def complete(self, trace: TraceContext) -> None:
        """Settle one trace: derive per-stage durations from adjacent mark
        pairs into the shared histograms, record it in the recent ring, and
        keep it as a slow exemplar when the enqueue→publish span exceeds
        the threshold."""
        self._observe(trace)
        if self._metrics is not None:
            self._metrics.counters.inc("settled_single")
        self._keep(trace.queue, [trace])

    def complete_window(self, traces: list[TraceContext]) -> None:
        """Settle a window's traces at once: what ``complete`` does for
        each, in the same order. Traces are grouped by queue and mark
        names; each group of at least ``_COLUMN_MIN_TRACES`` becomes one
        (traces × marks) array whose clipped differences feed each stage
        histogram and the attribution a column at a time. Smaller groups
        settle one trace at a time."""
        groups: dict[tuple, list] = {}
        for tr in traces:
            names, times = zip(*tr.marks)
            key = (tr.queue, names)
            group = groups.get(key)
            if group is None:
                groups[key] = group = ([], [])
            group[0].append(tr)
            group[1].append(times)
        counters = (self._metrics.counters if self._metrics is not None
                    else None)
        for (q, names), (members, times) in groups.items():
            n = len(members)
            if n < _COLUMN_MIN_TRACES or len(names) < 2:
                for tr in members:
                    self._observe(tr)
                if counters is not None:
                    counters.inc("settled_single", n)
                continue
            marks = np.fromiter(itertools.chain.from_iterable(times),
                                np.float64, n * len(names)).reshape(n, -1)
            gaps = np.maximum(np.diff(marks, axis=1), 0.0)
            totals = marks[:, -1] - marks[:, 0]
            if self._metrics is not None:
                observe = self._metrics.observe_stage_many
                for j, name in enumerate(names[1:]):
                    observe(q, name, gaps[:, j])
                observe(q, "total", np.maximum(totals, 0.0))
                counters.inc("settled_columnar", n)
            if self.attribution is not None:
                self.attribution.observe_window(
                    q, names, gaps, totals,
                    [tr.status or "unknown" for tr in members],
                    np.fromiter((tr.tier for tr in members), np.int64, n))
        queues = {q for q, _names in groups}
        for q in queues:
            self._keep(q, traces if len(queues) == 1
                       else [tr for tr in traces if tr.queue == q])

    def _observe(self, trace: TraceContext) -> None:
        """One trace's stage histograms and attribution."""
        q = trace.queue
        marks = trace.marks
        if self._metrics is not None:
            observe = self._metrics.observe_stage
            prev_t = marks[0][1]
            for name, t in marks[1:]:
                observe(q, name, max(0.0, t - prev_t))
                prev_t = t
            observe(q, "total", max(0.0, marks[-1][1] - marks[0][1]))
        if self.attribution is not None:
            self.attribution.observe(trace)

    def _keep(self, queue: str, traces: "list[TraceContext]") -> None:
        """Record settled traces of ``queue`` in its recent ring, in order,
        and in its slow ring those whose span reaches the threshold."""
        ring = self._recent.get(queue)
        if ring is None:
            ring = self._recent[queue] = deque(maxlen=self._ring)
        ring.extend(traces)
        threshold = self.slow_threshold_s
        slow_ones = [tr for tr in traces if tr.total_s >= threshold]
        if slow_ones:
            slow = self._slow.get(queue)
            if slow is None:
                slow = self._slow[queue] = deque(maxlen=self._slow_ring)
            slow.extend(slow_ones)

    def percentile_exemplar(self, queue: str,
                            p: float = 99.0) -> TraceContext | None:
        """The settled trace sitting at the p-th percentile of total span
        among the RECENT ring (nearest rank) — the exemplar whose
        decomposition /debug/attribution quotes: unlike a histogram-side
        p99, its per-gap durations sum to its span exactly."""
        ring = self._recent.get(queue)
        if not ring:
            return None
        by_total = sorted(ring, key=lambda t: t.total_s)
        import math

        k = min(len(by_total) - 1,
                max(0, math.ceil(p / 100.0 * len(by_total)) - 1))
        return by_total[k]

    def get(self, trace_id: str) -> TraceContext | None:
        for rings in (self._slow, self._recent):
            for ring in rings.values():
                for tr in ring:
                    if tr.trace_id == trace_id:
                        return tr
        return None

    def snapshot(self, queue: str | None = None,
                 limit: int = 32) -> dict[str, Any]:
        queues = ([queue] if queue is not None
                  else sorted(set(self._recent) | set(self._slow)))
        out: dict[str, Any] = {}
        for q in queues:
            recent = list(self._recent.get(q, ()))[-limit:]
            slow = list(self._slow.get(q, ()))[-limit:]
            out[q] = {
                "recent": [t.to_dict() for t in recent],
                "slow": [t.to_dict() for t in slow],
            }
        return {"slow_threshold_ms": self.slow_threshold_s * 1e3,
                "queues": out}
