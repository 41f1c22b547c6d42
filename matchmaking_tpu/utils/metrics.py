"""Metrics/observability: counters, latency percentiles, stage spans.

The reference leans on Elixir ``Logger`` and BEAM introspection; the rebuild
makes the BASELINE headline numbers (matches/sec, p50/p99 end-to-end latency,
pool occupancy, batch fill, recompile count) first-class (SURVEY.md §5
"Metrics/logging/observability"). Stdlib plus numpy, for the batched
observations a window settles at once.
"""

from __future__ import annotations

import bisect
import json
import math
import threading
import time
from collections import defaultdict, deque
from dataclasses import dataclass, field

import numpy as np


class Counter:
    def __init__(self) -> None:
        self._values: dict[str, float] = defaultdict(float)

    def inc(self, name: str, value: float = 1.0) -> None:
        self._values[name] += value

    def get(self, name: str) -> float:
        return self._values[name]

    def snapshot(self) -> dict[str, float]:
        return dict(self._values)


class LatencyRecorder:
    """Sliding-window latency recorder: keeps the most recent ``window``
    samples (bounded memory for a long-lived service; one sample lands here
    per matched player) plus lifetime count/max; percentiles are over the
    window."""

    def __init__(self, window: int = 65_536) -> None:
        self._samples: deque[float] = deque(maxlen=window)
        self._count = 0
        self._max = 0.0

    def record(self, seconds: float) -> None:
        self._samples.append(seconds)
        self._count += 1
        if seconds > self._max:
            self._max = seconds

    def record_many(self, seconds: np.ndarray) -> None:
        """What a loop of ``record`` over ``seconds`` would do, in order."""
        if not len(seconds):
            return
        self._samples.extend(seconds.tolist())
        self._count += len(seconds)
        top = float(seconds.max())
        if top > self._max:
            self._max = top

    def __len__(self) -> int:
        return self._count

    @staticmethod
    def _pct(sorted_samples: list[float], p: float) -> float:
        """Nearest-rank percentile over an ALREADY-sorted sample list — the
        one percentile definition, shared by ``percentile`` and
        ``summary_ms`` so the two can never drift apart."""
        n = len(sorted_samples)
        k = min(n - 1, max(0, math.ceil(p / 100.0 * n) - 1))
        return sorted_samples[k]

    def percentile(self, p: float) -> float:
        if not self._samples:
            return math.nan
        return self._pct(sorted(self._samples), p)

    def summary_ms(self) -> dict[str, float]:
        if not self._samples:
            return {"count": 0}
        # ONE sorted pass per scrape; every percentile reads from it.
        s = sorted(self._samples)
        return {
            "count": self._count,
            "p50_ms": round(self._pct(s, 50) * 1e3, 3),
            "p90_ms": round(self._pct(s, 90) * 1e3, 3),
            "p99_ms": round(self._pct(s, 99) * 1e3, 3),
            "max_ms": round(self._max * 1e3, 3),
            "mean_ms": round(sum(s) / len(s) * 1e3, 3),
        }


#: Default per-stage latency buckets: log-spaced (factor 2) upper bounds
#: from 100 µs to ~14 min. Wide enough that one histogram scheme covers
#: sub-millisecond host stages (pack/H2D) AND long low-traffic match waits
#: (the e2e stage must not saturate into +Inf while the LatencyRecorder
#: still resolves, or the p99 cross-check diverges); factor 2 bounds the
#: p99-from-buckets error at one octave.
DEFAULT_STAGE_BUCKETS: tuple[float, ...] = tuple(
    1e-4 * 2.0 ** k for k in range(24))


class Histogram:
    """Fixed-bucket latency histogram (Prometheus-style cumulative ``le``
    semantics at export; stored as per-bucket counts here). Replaces the
    averages-only span reporting in the /metrics path: an average cannot
    show the bimodal batcher-wait or H2D-stall signatures that explain a
    p99 outlier."""

    __slots__ = ("buckets", "counts", "overflow", "count", "sum", "_edges")

    def __init__(self, buckets: tuple[float, ...] = DEFAULT_STAGE_BUCKETS):
        # Sorted is a bisect precondition AND a prom-exposition requirement
        # (le labels must ascend) — user-supplied stage_buckets get no
        # ordering promise, so enforce it here.
        self.buckets = tuple(sorted(buckets))
        self.counts = [0] * len(self.buckets)
        self.overflow = 0  # observations above the last bucket (+Inf)
        self.count = 0
        self.sum = 0.0
        self._edges: np.ndarray | None = None

    def observe(self, seconds: float) -> None:
        self.count += 1
        self.sum += seconds
        i = bisect.bisect_left(self.buckets, seconds)
        if i < len(self.buckets):
            self.counts[i] += 1
        else:
            self.overflow += 1

    def observe_many(self, seconds: np.ndarray) -> None:
        """What a loop of ``observe`` over ``seconds`` would add: the same
        bucket for each value (``searchsorted(side="left")`` is
        ``bisect_left``), ``count`` by its length and ``sum`` by its sum."""
        n = len(seconds)
        if not n:
            return
        if self._edges is None:
            self._edges = np.asarray(self.buckets, dtype=np.float64)
        nb = len(self.buckets)
        per = np.bincount(np.searchsorted(self._edges, seconds, side="left"),
                          minlength=nb + 1).tolist()
        counts = self.counts
        for i in range(nb):
            if per[i]:
                counts[i] += per[i]
        self.overflow += per[nb]
        self.count += n
        self.sum += float(seconds.sum())

    def percentile(self, p: float) -> float:
        """Upper edge of the bucket holding the p-th percentile (NaN when
        empty; the last finite edge when it lands in +Inf) — accurate to
        one bucket width by construction."""
        if self.count == 0:
            return math.nan
        rank = max(1, math.ceil(p / 100.0 * self.count))
        cum = 0
        for edge, c in zip(self.buckets, self.counts):
            cum += c
            if cum >= rank:
                return edge
        return self.buckets[-1] if self.buckets else math.nan

    def to_dict(self) -> dict:
        """JSON-ready: cumulative bucket counts keyed by stringified upper
        bound (prom ``le`` semantics), plus count/sum."""
        cum = 0
        le: dict[str, int] = {}
        for edge, c in zip(self.buckets, self.counts):
            cum += c
            le[format(edge, ".6g")] = cum
        le["+Inf"] = cum + self.overflow
        return {"le": le, "count": self.count, "sum_s": round(self.sum, 6)}


@dataclass
class Span:
    """Wall-clock span for per-stage latency accounting (batcher wait, H2D,
    kernel, D2H, publish — SURVEY.md §5 tracing plan)."""

    name: str
    start: float = field(default_factory=time.perf_counter)
    elapsed: float = 0.0

    def stop(self) -> float:
        self.elapsed = time.perf_counter() - self.start
        return self.elapsed


class CompileCounter:
    """Process-wide XLA compilation counter (SURVEY.md §5 names "recompile
    count" explicitly). The whole p99 story rests on bucketed static shapes —
    a config typo that un-buckets one queue would silently add multi-hundred-
    ms compiles to the hot path; this makes that visible in /metrics and
    assertable in tests (soak asserts zero after warmup).

    Counts ``/jax/core/compile/backend_compile_duration`` events via
    jax.monitoring — one per actual XLA backend compile (cache hits don't
    fire it). Process-wide by nature (the monitoring hook is global), which
    matches the hazard: ANY unexpected compile in the serving process is a
    latency cliff."""

    _registered = False
    _count = 0
    _seconds = 0.0
    # The monitoring listener fires on whichever thread runs the compile
    # (dispatch happens from service worker threads via to_thread), and
    # count+seconds must move together — guard the read-modify-write.
    _lock = threading.Lock()

    @classmethod
    def install(cls) -> None:
        if cls._registered:
            return
        try:
            import jax.monitoring as mon
        except Exception:  # pragma: no cover - jax always present in practice
            return

        def on_event(name: str, duration: float, **kw) -> None:
            if name == "/jax/core/compile/backend_compile_duration":
                with cls._lock:
                    cls._count += 1
                    cls._seconds += duration

        mon.register_event_duration_secs_listener(on_event)
        cls._registered = True

    @classmethod
    def count(cls) -> int:
        return cls._count

    @classmethod
    def seconds(cls) -> float:
        """Total backend-compile wall time — a recompile COUNT says the
        cliff exists; the duration says how much p99 budget it burned."""
        return cls._seconds


class Metrics:
    def __init__(self, stage_buckets: tuple[float, ...] | None = None) -> None:
        self.counters = Counter()
        self.latency: dict[str, LatencyRecorder] = defaultdict(LatencyRecorder)
        #: Point-in-time gauges (set, not accumulated): circuit-breaker
        #: state per queue (0=closed 1=half_open 2=open), time degraded,
        #: current probe backoff — anything whose CURRENT value matters
        #: more than its history.
        self.gauges: dict[str, float] = {}
        #: True per-stage latency histograms, fed by the flight recorder
        #: (utils/trace.py) on every settled trace: queue → stage →
        #: Histogram. Exported as ONE Prometheus histogram family,
        #: ``matchmaking_stage_seconds{queue=...,stage=...}``.
        self.stage_buckets = tuple(stage_buckets or DEFAULT_STAGE_BUCKETS)
        self.stages: dict[str, dict[str, Histogram]] = {}
        # No CompileCounter.install() here: installing imports jax, which a
        # pure-CPU deployment (CpuEngine = numpy oracle) otherwise never
        # pays for. TpuEngine.__init__ installs it — exactly the processes
        # where a compile can happen; count() reads 0 elsewhere.

    def record_latency(self, name: str, seconds: float) -> None:
        self.latency[name].record(seconds)

    def _stage_histogram(self, queue: str, stage: str) -> Histogram:
        per_q = self.stages.get(queue)
        if per_q is None:
            per_q = self.stages[queue] = {}
        hist = per_q.get(stage)
        if hist is None:
            hist = per_q[stage] = Histogram(self.stage_buckets)
        return hist

    def observe_stage(self, queue: str, stage: str, seconds: float) -> None:
        self._stage_histogram(queue, stage).observe(seconds)

    def observe_stage_many(self, queue: str, stage: str,
                           seconds: np.ndarray) -> None:
        """``observe_stage`` for a column of samples at once."""
        self._stage_histogram(queue, stage).observe_many(seconds)

    def set_gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def report(self) -> dict:
        counters = self.counters.snapshot()
        counters["xla_compiles"] = float(CompileCounter.count())
        counters["xla_compile_seconds"] = round(CompileCounter.seconds(), 6)
        return {
            "counters": counters,
            "gauges": dict(self.gauges),
            "latency": {k: v.summary_ms() for k, v in self.latency.items()},
            "stage_seconds": {
                q: {s: h.to_dict() for s, h in per_q.items()}
                for q, per_q in self.stages.items()
            },
        }

    def report_json(self) -> str:
        return json.dumps(self.report(), sort_keys=True)
