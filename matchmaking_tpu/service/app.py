"""The matchmaking application: boot, wiring, supervision.

``Matchmaking.Application`` + the supervision tree, rebuilt (SURVEY.md §2 C1,
§3 Entry 1/4). Boot wires, per configured queue:

    broker consumer → middleware pipeline → batcher → engine → responses

Supervision semantics (the OTP analog, SURVEY.md §5 "Failure detection"):

- a crashing consumer callback requeues its delivery (broker-level);
- a crashing engine step nacks the whole window (redelivered, idempotent via
  duplicate-enqueue no-ops) and **revives the engine from the authoritative
  host mirror** — the "sidecar death → resubmit pool" recovery path;
- deliveries are acked only after their window's responses are published
  (at-least-once end to end).

Run a self-contained demo with ``python -m matchmaking_tpu.service.app --demo``.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time

from matchmaking_tpu.config import Config, QueueConfig
from matchmaking_tpu.engine.interface import Engine, SearchOutcome, make_engine
from matchmaking_tpu.service.batcher import Batcher
from matchmaking_tpu.service.broker import Delivery, InProcBroker, Properties
from matchmaking_tpu.service.contract import (
    SearchRequest,
    SearchResponse,
    encode_response,
)
from matchmaking_tpu.service.breaker import CLOSED, STATE_CODE, CircuitBreaker
from matchmaking_tpu.service.middleware import (
    MessageContext,
    MiddlewareReject,
    Pipeline,
    columnar_pipeline,
    default_pipeline,
)
from matchmaking_tpu.service.overload import (
    ADMIT,
    EXPIRED,
    AdmissionController,
    deadline_of,
)
from matchmaking_tpu.service.attribution import WAIT, Attribution
from matchmaking_tpu.service.ingress import (
    IngressShards,
    ShardedRecent,
    gather_rows,
)
from matchmaking_tpu.service.quality import QualityLedger
from matchmaking_tpu.engine.quality import QualitySpec
from matchmaking_tpu.utils.chaos import ChaosState
from matchmaking_tpu.utils.metrics import Metrics
from matchmaking_tpu.utils.timeseries import SloMonitor, TelemetryRing
from matchmaking_tpu.utils.trace import (
    EventLog,
    FlightRecorder,
    ProcessHooks,
    TraceContext,
    stage,
)

log = logging.getLogger(__name__)

#: Minimum consume-burst size worth decoding at the consume seam
#: (ISSUE 12): below this, the per-burst fixed cost (column allocation +
#: one native call) exceeds what it saves, and the flush's WINDOW-batched
#: decode — which aggregates many small bursts into one call — is already
#: the cheaper shape. Bursts this size and up decode at consume, so under
#: load (where the broker drains full bursts) the flush skips decode
#: entirely and assembles by gather.
_MIN_DECODE_BURST = 16


async def _shielded_to_thread(task: "asyncio.Task"):
    """Await an already-launched ``asyncio.to_thread`` task, shielded from
    caller cancellation: the worker THREAD cannot be interrupted anyway,
    so a cancelled caller lets it finish in the background (the caller
    attaches a done-callback to dispose the result).  Named so the
    runtime async sanitizer recognizes it as the same sanctioned off-loop
    seam as a bare ``await asyncio.to_thread(...)`` — the work is off the
    event loop either way (testing/sanitizer.py
    ``_SANCTIONED_CODE_NAMES``)."""
    return await asyncio.shield(task)


class _QueueRuntime:
    """Everything one matchmaking queue owns (consumer, batcher, engine)."""

    def __init__(self, app: "MatchmakingApp", queue_cfg: QueueConfig,
                 placement: "tuple[int, ...] | None" = None):
        self.app = app
        self.queue_cfg = queue_cfg
        #: Elastic placement binding (ISSUE 11): logical device ids this
        #: queue's engine runs on (shard degree = len). None = the static
        #: pre-placement default.  EVERY engine rebuild (crash revive,
        #: breaker demote/re-promote, migration) goes through
        #: _make_engine/_probe_build, which apply this — a revive must not
        #: silently undo a migration.
        self.placement: tuple[int, ...] | None = (
            tuple(placement) if placement else None)
        #: Chaos fault hook for this queue's engines (None = no chaos). The
        #: hook's step counters live in the APP's ChaosState, not the
        #: engine, so a scripted schedule keeps advancing across revives.
        self._chaos_hook = (
            app.chaos.engine_hook(queue_cfg.name)
            if app.chaos is not None and app.chaos.applies(queue_cfg.name)
            else None)
        #: Device-engine circuit breaker. Host-backend queues have no lower
        #: tier to demote to, so they run without one. Created even when
        #: breaker_threshold=0 (disabled) so /healthz always reports state.
        self.breaker: CircuitBreaker | None = (
            CircuitBreaker(app.cfg.engine)
            if app.cfg.engine.backend == "tpu" else None)
        self._publish_breaker_gauges()
        self.batcher: Batcher = Batcher(
            app.cfg.batcher, self._flush,
            observe_window=self._observe_window,
            # EDF window cutting (OverloadConfig.edf): windows are cut by
            # (tier, absolute deadline) instead of arrival order, so a
            # near-deadline tier-0 request dispatches in the next device
            # window. The key is a pure function of the delivery's cached
            # tier + stamped header — no clock reads (determinism rule).
            sort_key=self._edf_key if app.cfg.overload.edf else None,
            counters=app.metrics.counters)
        #: Live in-flight window cap (the backpressure gate in
        #: _dispatch_pipelined), initialized from the frozen engine
        #: config. The online autotuner (control/autotune.py, ISSUE 13)
        #: steps it within [1, cfg.pipeline_depth] — the pipelined/sync
        #: path CHOICE stays the boot-time config's (depth 1 here only
        #: gates in-flight windows, it does not de-pipeline the flush).
        self.pipeline_depth = app.cfg.engine.pipeline_depth
        # Serializes ALL engine access (window flushes vs the timeout
        # sweeper): engines are single-writer objects with no internal locks.
        # Attributes below marked ``guarded-by: _engine_lock`` are checked
        # by matchlint (analysis/locks.py): every mutation site must be
        # dominated by this lock (or live in a *_locked / holds-lock
        # method).
        self._engine_lock = asyncio.Lock()
        # Pipelined columnar windows: token → (by_id, deliveries) for every
        # dispatched-but-uncollected window. Outcomes are handled (publish +
        # ack) at COLLECTION time, so up to ``engine.pipeline_depth`` windows
        # overlap on device — the discipline the bench measures, now in
        # production (round-3 verdict ask #3).
        # guarded-by: _engine_lock
        self._inflight_meta: dict[int, tuple[dict[str, Delivery], list[Delivery]]] = {}
        self._collector: asyncio.Task | None = None
        #: A collected window failed on device; revive once in-flight drains.
        # guarded-by: _engine_lock
        self._needs_revive = False
        #: Windows currently inside a flush (decode → dispatch → [inline
        #: handling]); engine.inflight() only counts DISPATCHED windows, so
        #: during a long first-window compile both it and batcher.depth read
        #: 0 — drain/quiesce checks must consult this too.
        self._flushing = 0
        #: Overload admission control (service/overload.py): credit
        #: limiter + deadline gate + adaptive shedding. None when no
        #: OverloadConfig knob is set — the ingress path then pays nothing.
        #: Created BEFORE the engine binds: _bind_engine derives the
        #: inline-ingress fast path from the admission mode.
        self.admission: AdmissionController | None = (
            AdmissionController(app.cfg.overload, queue_cfg.name,
                                app.metrics, app.events,
                                default_tier=queue_cfg.default_tier)
            if app.cfg.overload.enabled() else None)
        #: Window-granular admission (ISSUE 9, OverloadConfig.
        #: batch_admission): per-delivery ingress keeps only pre_decide's
        #: pre-checks; the credit/occupancy ladder runs ONCE per cut
        #: window at the top of the flush (_admission_cut), in arrival
        #: order, with batched shed responses.
        self._batch_admission = (self.admission is not None
                                 and app.cfg.overload.batch_admission)
        #: Arrival stamp for batcher submits (Delivery.arrival): the
        #: admission pass orders the EDF-sorted window back into consume
        #: order with it.
        self._arrival_seq = 0
        #: Window-granular egress (BrokerConfig.batch_publish): one
        #: publish_batch broker call per window of responses.
        self._batch_publish = (app.cfg.broker.batch_publish
                               and hasattr(app.broker, "publish_batch"))
        #: Write-ahead pool journal (ISSUE 15, utils/journal.py; None =
        #: durability off). Construction ATTACHES to whatever segments a
        #: crashed predecessor left — the app's recovery step reads
        #: ``journal.recovered`` before any consumer runs.
        self.journal = None
        dur = app.cfg.durability
        if dur.enabled():
            from matchmaking_tpu.utils.journal import PoolJournal

            self.journal = PoolJournal(
                dur.journal_dir, queue_cfg.name, fsync=dur.fsync,
                fsync_interval_s=dur.fsync_interval_s,
                compact_records=dur.compact_records,
                compact_bytes=dur.compact_bytes,
                keep_snapshots=dur.keep_snapshots)
        #: Hot-standby replication (ISSUE 17, service/replication.py;
        #: None = replication off — zero hot-path cost: no journal tap,
        #: no fence checks, no pump task). Built by app.start() via
        #: ``start_replication`` AFTER journal recovery, so the baseline
        #: the standby receives is the recovered truth. Owns the
        #: primary→fenced role bit; the publish seams below consult it.
        self.replication = None
        self._repl_task: asyncio.Task | None = None
        #: Device-loss failover (ISSUE 15): the logical device a
        #: ChaosDeviceLostError (or a real XLA device-loss) named, consumed
        #: by the next ``_revive_engine`` to demote a sharded queue to its
        #: surviving devices; plus the bounded audit of past demotions
        #: (served at /debug/placement next to the controller's ring).
        self._lost_device: int | None = None
        self.failover_log: list[dict] = []
        #: The last hard-crash recovery this runtime applied (None = clean
        #: boot): rto_ms + the journal's deterministic transcript — what
        #: test_durability.py pins bit-identical across two runs.
        self.last_recovery: "dict | None" = None
        self._bind_engine(self._make_engine())
        # At-least-once dedup: player id → (encoded terminal response BODY,
        # expiry). Bytes, not SearchResponse: the body is built exactly once
        # (possibly by the native batch encoder) and replays publish it
        # verbatim — a player always sees a self-consistent response.
        # Split into per-shard dicts by the consistent request-id hash
        # (ISSUE 12): at ingress_shards=1 a single dict, byte for byte.
        self._recent = ShardedRecent(app.cfg.broker.ingress_shards)
        self._next_prune = 0.0
        #: In-process ingress shard workers (ISSUE 12): the consume-burst
        #: decode + NEEDS_PYTHON fallback plane, consistent-hashed by
        #: request id. N=1 runs inline — today's path.
        self._shards = IngressShards(app.cfg.broker.ingress_shards)
        #: Previous "total"-stage histogram snapshot (counts, overflow,
        #: count) for the adaptive limiter's per-window DELTA p99 — the
        #: lifetime-cumulative histogram would tighten on stale history
        #: (a startup compile spike) and take half of forever to relax.
        self._stage_total_prev: tuple[list[int], int, int] | None = None
        # batch_hint: _on_delivery is non-blocking for auth modes other
        # than "rpc" (decode defers to the batched codec; static/none auth
        # never awaits), so the broker may drain bursts into one handler
        # task. RPC auth keeps per-delivery tasks — its round trips must
        # overlap up to prefetch (the GenServer-pool parallelism analog).
        # With admission control on, prefetch must keep headroom ABOVE the
        # credit cap: admitted deliveries hold a prefetch slot until their
        # window settles, and if the two bounds were equal the excess load
        # would rot unacked in the broker instead of flowing through
        # admission to be shed with an explicit response.
        prefetch = app.cfg.broker.prefetch
        if app.cfg.overload.max_inflight > 0:
            prefetch = max(prefetch, 2 * app.cfg.overload.max_inflight)
        # Columnar consume_batch ingress (ISSUE 12): ONE app callback per
        # drained broker burst instead of one handler invocation per
        # delivery. Same eligibility as batch_hint (RPC auth keeps
        # per-delivery tasks so its round trips overlap); the broker
        # additionally falls back per-delivery while consume-side fault
        # injection is armed, so chaos identity never changes with
        # batching. consume_batch=False = the per-delivery path verbatim.
        self._consume_batch = (app.cfg.broker.consume_batch
                               and app.cfg.auth.mode != "rpc")
        self.consumer_tag = app.broker.basic_consume(
            queue_cfg.name, self._on_delivery,
            prefetch=prefetch,
            batch_hint=app.cfg.auth.mode != "rpc",
            batch_callback=(self._on_delivery_batch if self._consume_batch
                            else None),
        )
        self._sweeper: asyncio.Task | None = None
        if (queue_cfg.request_timeout_s is not None
                or (self.admission is not None
                    and app.cfg.overload.deadline_sweep_ms > 0)):
            # One sweep loop serves both evictions: the coarse
            # request_timeout_s timeout AND the per-slot x-deadline expiry
            # (OverloadConfig.deadline_sweep_ms) — they share the drain +
            # engine-lock discipline, so two timers would double the lock
            # contention for nothing.
            self._sweeper = asyncio.create_task(self._sweep_loop())
        self._rescanner: asyncio.Task | None = None
        if queue_cfg.rescan_interval_s > 0:
            # 1v1 queues AND device team queues support rescan (team window
            # formation is pool-wide, so an all-invalid batch re-forms with
            # widened thresholds); host-oracle team paths return None from
            # rescan_async and the tick is a no-op.
            self._rescanner = asyncio.create_task(self._rescan_loop())
        #: Dedicated low-frequency health timer: drives breaker half-open
        #: probes AND the idle re-promotion heartbeat for wildcard-delegated
        #: team/role queues — independent of ``_rescan_loop``, so a
        #: delegated queue with ``rescan_interval_s=0`` still re-promotes
        #: once its wildcards drain (ADVICE round-5 #3, closed for real).
        self._health: asyncio.Task | None = None
        if app.cfg.engine.health_interval_s > 0 and self.breaker is not None:
            # Device-backend queues only: host-backend queues have no
            # breaker to probe and no delegate to re-promote, so the timer
            # would just contend on the engine lock every tick for nothing.
            self._health = asyncio.create_task(self._health_loop())
        #: Speculative formation driver (ISSUE 16): fills idle window gaps
        #: with precomputed no-admission pairing steps over the resident
        #: pool; the cut (traffic dispatch / rescan tick / next spec tick)
        #: validates the speculation against the mutation clock and commits
        #: it in O(1) or discards it. Pipelined 1v1 device queues only —
        #: the commit path rides the pipelined collector, and team windows
        #: delegate formation where no speculative twin exists.
        self._spec_task: asyncio.Task | None = None
        if (app.cfg.engine.spec_formation
                and app.cfg.engine.spec_interval_ms > 0
                and self._pipelined
                and queue_cfg.team_size == 1
                and not queue_cfg.role_slots):
            self._spec_task = asyncio.create_task(self._spec_loop())
        #: Journal compaction timer (ISSUE 15): checks wants_compact() on
        #: its cadence and runs snapshot + segment rotation off the hot
        #: path, under the engine lock with the pipeline drained. NOT
        #: started here: app.start() arms it via
        #: ``start_durability_timer`` only AFTER recover_from_journal has
        #: applied the predecessor's state — a re-attached segment can
        #: already exceed the compaction budget, and compacting the
        #: not-yet-recovered (empty) engine would anchor an empty
        #: snapshot at the recovered seq and GC the one recovery needs.
        self._durability: asyncio.Task | None = None
        # Online invariant checking (SURVEY.md §5 "Race detection").
        self._invariants = None
        if app.cfg.debug_invariants:
            from matchmaking_tpu.utils.invariants import InvariantChecker

            self._invariants = InvariantChecker(queue_cfg.team_size)

    # ---- engine lifecycle (revive / breaker demotion / re-promotion) ------

    def elastic_shardable(self) -> bool:
        """Elastic sharding (D=1↔D>1 promotion) is available for this
        queue: the device 1v1 path only — team/role kernel sets take no
        device binding for their meshes, so the controller moves them
        whole-device or not at all."""
        return (self.queue_cfg.team_size == 1
                and not self.queue_cfg.role_slots
                and self.app.cfg.engine.backend == "tpu")

    def _engine_cfg(self) -> Config:
        """The engine's effective config under the CURRENT placement:
        for elastic-shardable queues the mesh axis follows the binding's
        device count (promote D=1→2 rebuilds onto the sharded kernel set;
        demote comes back), everything else passes through unchanged."""
        cfg = self.app.cfg
        if (self.placement is not None and self.elastic_shardable()
                and len(self.placement) != cfg.engine.mesh_pool_axis):
            cfg = dataclasses.replace(
                cfg, engine=dataclasses.replace(
                    cfg.engine, mesh_pool_axis=len(self.placement)))
        return cfg

    def _make_engine(self) -> Engine:
        """Build this queue's engine for the CURRENT breaker state: the
        configured (device) engine while the breaker is closed, the
        host-oracle fallback while it is open/half-open — graceful
        degradation: matches keep flowing at oracle throughput instead of
        revive-looping a persistently failing device path at traffic rate."""
        if self.breaker is not None and self.breaker.state != CLOSED:
            from matchmaking_tpu.engine.cpu import CpuEngine

            self.app.metrics.counters.inc("breaker_degraded_revives")
            self.app.events.append("degraded_revive", self.queue_cfg.name,
                                   f"breaker {self.breaker.state}")
            log.warning(
                "queue %r: breaker %s — running DEGRADED on the host oracle",
                self.queue_cfg.name, self.breaker.state)
            return CpuEngine(self.app.cfg, self.queue_cfg)
        engine = make_engine(self._engine_cfg(), self.queue_cfg,
                             devices=self.placement)
        if self._chaos_hook is not None and hasattr(engine, "chaos_hook"):
            engine.chaos_hook = self._chaos_hook
        return engine

    # holds-lock: _engine_lock
    def _bind_engine(self, engine: Engine) -> None:
        """Install ``engine`` and recompute every engine-shape-dependent
        seam. The single place engine swaps land — boot, crash revive,
        breaker demotion, and probe re-promotion all come through here,
        because the device engine and the host oracle differ in ingress
        shape (columnar vs object decode) and dispatch discipline
        (pipelined vs synchronous)."""
        # guarded-by: _engine_lock
        self.engine = engine
        # Lifecycle event timeline: engine-internal transitions (wildcard
        # delegation, re-promotion) report through the shared log.
        engine.events = self.app.events
        # Columnar ingress (1v1 queues on a columnar-capable engine): decode
        # is deferred to the batched native codec at flush time. A degraded
        # (host-oracle) engine has no columnar API — deliveries decode per
        # object in the flush instead.
        self._columnar = (
            self.queue_cfg.team_size == 1 and not self.queue_cfg.role_slots
            and hasattr(engine, "search_columns_async")
        )
        self.pipeline: Pipeline = (
            columnar_pipeline(self.app.cfg.auth, self.app.broker)
            if self._columnar
            else default_pipeline(self.app.cfg.auth, self.app.broker)
        )
        # Inline ingress (ISSUE 9): with no auth configured the columnar
        # pipeline is just the first-received stamp — running it as a
        # middleware chain costs a MessageContext + 3 coroutine frames +
        # nested closures PER DELIVERY. Inline the stamp in _on_delivery
        # instead (same headers, same trace marks); any real middleware
        # (auth rpc/static) keeps the full chain. Legacy per-delivery
        # admission also keeps the chain — that path stays byte-identical.
        self._inline_ingress = (
            self._columnar and self.app.cfg.auth.mode == "none"
            and (self.admission is None or self._batch_admission))
        # Pipelining applies to BOTH ingress shapes: the columnar 1v1 fast
        # path and the object path (device team queues, config #3) — any
        # engine with the pipelined window API (search_async/collect_ready;
        # the CPU oracle has neither and stays synchronous).
        self._pipelined = (
            hasattr(engine, "collect_ready")
            and hasattr(engine, "search_async")
            and self.app.cfg.engine.pipeline_depth > 1
        )
        # The collector task follows the pipelined flag: a degraded engine
        # has no inflight()/collect_ready(), so its collector would only
        # spin on AttributeError noise.
        if self._pipelined and (self._collector is None
                                or self._collector.done()):
            self._collector = asyncio.create_task(self._collector_loop())
        elif not self._pipelined and self._collector is not None:
            self._collector.cancel()
            self._collector = None

    def _record_engine_crash(self, now: float) -> None:
        """Count one engine crash and feed the circuit breaker. When this
        crash trips the breaker, the NEXT engine rebuild (_make_engine —
        every crash path ends in one) demotes the queue to the host oracle;
        half-open probes on the health timer re-promote it later."""
        self.app.metrics.counters.inc("engine_crashes")
        self.app.events.append("engine_crash", self.queue_cfg.name)
        if self.breaker is not None and self.breaker.record_crash(now):
            self.app.metrics.counters.inc("breaker_trips")
            self.app.events.append(
                "breaker_trip", self.queue_cfg.name,
                f"{self.breaker.threshold} crashes in "
                f"{self.breaker.window_s:.1f}s",
                component="service",
                refs={"crashes": self.breaker.threshold})
            self._publish_breaker_gauges()
            log.error(
                "queue %r: circuit breaker TRIPPED (%d engine crashes "
                "within %.1fs) — demoting to the host oracle; first "
                "half-open probe in %.2fs",
                self.queue_cfg.name, self.breaker.threshold,
                self.breaker.window_s, self.breaker.probe_delay_s)

    def _publish_breaker_gauges(self) -> None:
        """Mirror breaker state into the shared metrics gauges — /metrics
        readers see state without the observability server having to reach
        into runtimes. Called on every state transition (cheap: three dict
        writes), so the gauge is never staler than the last transition."""
        if self.breaker is None:
            return
        snap = self.breaker.snapshot(time.time())
        q = self.queue_cfg.name
        m = self.app.metrics
        m.set_gauge(f"breaker_state[{q}]", STATE_CODE[snap["state"]])
        m.set_gauge(f"breaker_probe_delay_s[{q}]", snap["probe_delay_s"])
        m.set_gauge(f"breaker_time_degraded_s[{q}]", snap["time_degraded_s"])

    # ---- flight recorder (utils/trace.py) ---------------------------------

    def _observe_window(self, size: int, age_s: float) -> None:
        """Batcher window-cut hook: batch fill + batcher wait, per queue."""
        m = self.app.metrics
        q = self.queue_cfg.name
        m.observe_stage(q, "batch_window", age_s)
        fill = size / max(1, self.batcher.max_batch)
        m.set_gauge(f"batch_fill[{q}]", fill)
        if self.admission is not None and self.app.cfg.overload.adaptive:
            # Adaptive shedding feeds on the signals the service already
            # exports: batch fill (this hook), pipeline occupancy, and the
            # per-queue stage p99 from the PR 3 histograms — the limiter
            # tightens BEFORE the circuit breaker trips. Once per cut
            # window, a deterministic point in the ingress sequence. The
            # p99 is over the DELTA since the previous window (the
            # histogram is lifetime-cumulative; tightening on all-time
            # history would hold the limiter down long after recovery).
            depth = self.pipeline_depth
            pipeline_frac = (self.engine.inflight() / depth
                             if depth > 0 and hasattr(self.engine, "inflight")
                             else 0.0)
            hist = m.stages.get(q, {}).get("total")
            self.admission.observe_window(fill, pipeline_frac,
                                          self._delta_p99(hist))

    def _delta_p99(self, hist) -> float | None:
        """p99 (bucket upper edge) of the "total"-stage observations that
        settled SINCE the previous window cut — a sliding signal built by
        differencing cumulative histogram snapshots. None when no trace
        settled in the interval (the limiter then judges on occupancy
        signals alone)."""
        if hist is None:
            return None
        prev = self._stage_total_prev
        cur = (list(hist.counts), hist.overflow, hist.count)
        self._stage_total_prev = cur
        if prev is None:
            prev = ([0] * len(cur[0]), 0, 0)
        n = cur[2] - prev[2]
        if n <= 0:
            return None
        import math

        rank = max(1, math.ceil(0.99 * n))
        cum = 0
        for edge, c0, c1 in zip(hist.buckets, prev[0], cur[0]):
            cum += c1 - c0
            if cum >= rank:
                return edge
        return hist.buckets[-1] if hist.buckets else None

    def _trace(self, delivery: Delivery) -> "TraceContext | None":
        """The delivery's trace, created lazily for transports that don't
        stamp at publish (the enqueue stage then reads 0). None when
        tracing is off — or when sample-N tracing is on: with N > 1 an
        unstamped delivery means the broker SAMPLED IT OUT, and creating a
        context here would resurrect every one of them."""
        tr = delivery.trace
        if (tr is None and self.app.trace_enabled
                and self.app.trace_sample_n <= 1):
            tr = delivery.trace = TraceContext(
                self.queue_cfg.name, delivery.properties.correlation_id,
                redelivered=delivery.redelivered)
        return tr

    def _settle_trace(self, delivery: Delivery, status: str,
                      t: float | None = None) -> None:
        """Final trace mark ("publish") + hand-off to the flight recorder.
        Called wherever a delivery reaches a terminal settle (response
        published + acked); nacked deliveries keep their trace open — the
        redelivery appends to the same mark list."""
        tr = delivery.trace
        if tr is None:
            return
        tr.status = status
        tr.mark("publish", t)
        self.app.recorder.complete(tr)

    def _settle_outcome_traces(self, out: SearchOutcome,
                               deliveries: list[Delivery],
                               t: float | None = None) -> None:
        """Settle every delivery's trace with the status its player reached
        in this OBJECT outcome (trace.player_id was stamped at ingress/
        flush, so duplicate deliveries of one player settle too)."""
        if all(d.trace is None for d in deliveries):
            return  # tracing off: skip the id-set builds entirely
        matched = {r.id for m in out.matches for r in m.requests()}
        rejected = {r.id for r, _ in out.rejected}
        timed = {r.id for r in out.timed_out}
        for d in deliveries:
            tr = d.trace
            if tr is None:
                continue
            pid = tr.player_id
            status = ("matched" if pid in matched else
                      "rejected" if pid in rejected else
                      "timeout" if pid in timed else "queued")
            self._settle_trace(d, status, t)

    def _merge_window_marks(self, tok: int,
                            deliveries: list[Delivery]) -> None:
        """Fold one finalized window's engine-side stage marks (dispatch /
        h2d / device_step / readback_seal / collect) into every member
        delivery's trace. Pops the engine's entry either way so the
        hand-off dict cannot grow unbounded."""
        wm = getattr(self.engine, "window_marks", None)
        if wm is None:
            return
        marks = wm.pop(tok, None)
        if not marks:
            return
        for d in deliveries:
            if d.trace is not None:
                d.trace.extend(marks)

    # ---- settle + admission (overload control) ----------------------------

    # ---- write-ahead journal (ISSUE 15, utils/journal.py) -----------------

    def _journal_commit(self) -> None:
        """Flush buffered journal records before an externally visible
        effect (response publish / delivery ack) — the write-ahead points.
        One buffered os.write per window; fsync per the configured policy.
        No-op (one attr read + one bool) with durability off or a clean
        buffer."""
        j = self.journal
        if j is not None and j.needs_commit:
            j.commit()

    # holds-lock: _engine_lock
    def _journal_admit_cols(self, cols) -> None:
        """ADMIT record for one dispatched columnar window: called inside
        the dispatch closures, under the engine lock, AFTER the stale/
        expired/debt drops — the journal records exactly what entered the
        pool, so recovery can never resurrect a terminal-replayed player
        as waiting. One buffered append per window, not per player.
        Region/mode by NAME (interner codes are process-local, the
        utils/checkpoint portability rule)."""
        j = self.journal
        if j is None or not len(cols):
            return
        rname = self.engine.pool.regions.name
        mname = self.engine.pool.modes.name
        k = len(cols)
        tiers = (cols.tier.tolist() if cols.tier is not None else [0] * k)
        dls = (cols.deadline.tolist() if cols.deadline is not None
               else [0.0] * k)
        rows = [
            [pid, float(rating), float(rd), rname(int(rc)), mname(int(mc)),
             (None if thr != thr else float(thr)), float(enq), rep, corr,
             int(tier), float(dl)]
            for pid, rating, rd, rc, mc, thr, enq, rep, corr, tier, dl
            in zip(cols.ids.tolist(), cols.rating.tolist(), cols.rd.tolist(),
                   cols.region.tolist(), cols.mode.tolist(),
                   cols.threshold.tolist(), cols.enqueued_at.tolist(),
                   cols.reply_to.tolist(), cols.correlation_id.tolist(),
                   tiers, dls)
        ]
        j.append_admits(rows)
        # Write out at dispatch (one os.write, NO fsync — a process crash
        # cannot lose written bytes): a crash mid-window then recovers the
        # window's players as WAITING from the journal alone — not
        # matched, never lost. The policy fsync runs at the response/ack
        # commit points, once per window.
        j.flush_buffer()

    # holds-lock: _engine_lock
    def _journal_admit_reqs(self, requests: "list[SearchRequest]") -> None:
        """Object-path twin of ``_journal_admit_cols`` (device team queues
        and the demoted-oracle flush)."""
        j = self.journal
        if j is None or not requests:
            return
        j.append_admits([
            [r.id, float(r.rating), float(r.rating_deviation), r.region,
             r.game_mode,
             (None if r.rating_threshold is None
              else float(r.rating_threshold)),
             float(r.enqueued_at), r.reply_to, r.correlation_id,
             int(r.tier), float(r.deadline_at)]
            for r in requests
        ])
        j.flush_buffer()

    def start_durability_timer(self) -> None:
        """Arm the compaction timer — called by app.start() AFTER
        ``recover_from_journal`` so the first compaction can only ever
        snapshot a recovered (or genuinely fresh) pool."""
        if (self.journal is not None and self._durability is None
                and self.app.cfg.durability.compact_interval_s > 0):
            self._durability = asyncio.create_task(self._durability_loop())

    async def _durability_loop(self) -> None:
        """Compaction timer: snapshot + segment rotation once the live
        segment crosses its record/byte budget. Supervised like the
        collector — one failed compaction (disk full, transient device
        error in the drain) must not end durability for the process."""
        interval = self.app.cfg.durability.compact_interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                j = self.journal
                if j is None or not j.wants_compact():
                    continue
                await self.compact_journal()
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("journal compaction failed; retrying")
                self.app.metrics.counters.inc("journal_compact_errors")

    async def compact_journal(self) -> "dict[str, Any]":
        """One compaction: under the engine lock with the pipeline
        drained, capture the anchor seq, snapshot the pool (utils/
        checkpoint format, atomic), rotate the segment, and carry the
        live dedup entries + admission checkpoint into the successor —
        the snapshot is exactly consistent with the journal sequence it
        anchors because nothing can mutate the pool between the capture
        and the write."""
        from matchmaking_tpu.utils.checkpoint import save_pool

        j = self.journal
        assert j is not None
        async with self._engine_lock:
            now = time.time()
            await self._drain_engine(now)
            anchor, snap_path = j.compact_begin()

            def rotate() -> int:
                n = save_pool(self.engine, snap_path,
                              queue_name=self.queue_cfg.name)
                carry = [(pid, body, exp)
                         for pid, (body, exp) in self._recent.items()
                         if exp > now]
                adm = (self.admission.checkpoint()
                       if self.admission is not None else None)
                j.compact_finish(anchor, snap_path, carry, adm)
                return n

            # shield + ensure_future (the migrate() pattern): the rotate
            # THREAD cannot be interrupted and mutates on-disk journal
            # state (snapshot write, segment rotation, carry records). If
            # the durability task is cancelled mid-compaction — close()
            # cancels it without awaiting — a bare await would release
            # the engine lock while the thread keeps running, letting
            # shutdown's drain → mark_clean() → journal.close() race
            # compact_finish: the rotation would strand the CLEAN marker
            # in the retired segment and append carry records PAST it, so
            # the next boot would "recover" from a clean shutdown. Hold
            # the lock until the thread actually finishes, then let the
            # cancellation propagate.
            rotate_task = asyncio.ensure_future(asyncio.to_thread(rotate))
            try:
                count = await _shielded_to_thread(rotate_task)
            except asyncio.CancelledError:
                while not rotate_task.done():
                    try:
                        await _shielded_to_thread(rotate_task)
                    except asyncio.CancelledError:
                        continue
                    except Exception:
                        break
                raise
        self.app.metrics.counters.inc("journal_compactions")
        self.app.events.append(
            "journal_compacted", self.queue_cfg.name,
            f"anchor seq {anchor}, {count} waiting players snapshotted",
            component="durability", refs={"anchor": anchor, "count": count})
        return {"anchor": anchor, "snapshot": snap_path, "count": count}

    async def recover_from_journal(self) -> "dict | None":
        """Hard-crash recovery (app.start() calls this before traffic):
        apply the journal's recovered state — newest-valid snapshot +
        journal tail into the engine (index_rebuild via the heartbeat
        seam so the bucketed index is exact), the ``_recent`` dedup/replay
        cache so broker redeliveries of already-terminal players replay
        instead of re-entering, and the admission decision checkpoint.
        The whole span is the measured RTO (``crash_rto_ms`` gauge +
        ``crash_recovered`` EventLog event). Returns the recovery record
        (also kept as ``self.last_recovery``), or None on a clean boot."""
        from matchmaking_tpu.utils.checkpoint import load_pool
        from matchmaking_tpu.utils.journal import row_to_request

        j = self.journal
        if j is None or j.recovered is None:
            return None
        rec = j.recovered
        q = self.queue_cfg.name
        for note in rec.corrupt:
            # Speakable, non-fatal: a truncated newest snapshot fell back
            # to the previous good generation instead of crashing the boot.
            self.app.events.append("journal_corrupt", q, note)
            log.warning("queue %r: %s", q, note)
        if rec.clean:
            return None
        t0 = time.perf_counter()
        now = time.time()
        async with self._engine_lock:
            # Journal replay is an invalidation path in the speculation
            # contract (ISSUE 16): recovery rebuilds the pool from the
            # WAL, so any speculation is against a pool that never was.
            self._spec_invalidate_audited("journal replay")

            def apply() -> tuple[int, int]:
                n_snap = 0
                if rec.snapshot:
                    n_snap = load_pool(self.engine, rec.snapshot, now)
                for pid in sorted(rec.removed):
                    # Terminal after the snapshot anchor: the player is no
                    # longer waiting (remove is a no-op when absent).
                    self.engine.remove(pid)
                tail = [row_to_request(rec.waiting[pid])
                        for pid in sorted(rec.waiting)]
                if tail:
                    self.engine.restore(tail, now)
                if hasattr(self.engine, "heartbeat"):
                    # Bucketed engines re-tighten the device index with a
                    # full index_rebuild here: incremental admits during
                    # restore only WIDEN bounds, and recovery must hand
                    # traffic an index as exact as the pre-crash one.
                    self.engine.heartbeat(now)
                return n_snap, len(tail)

            n_snap, n_tail = await asyncio.to_thread(apply)
            for pid, (body, exp) in rec.recent.items():
                if exp > now:
                    self._recent.set(pid, (body, exp))
            if rec.admission is not None and self.admission is not None:
                self.admission.restore_state(rec.admission)
        # Anchor a fresh snapshot immediately: the recovered tail must not
        # replay again on the next crash, and the successor segment starts
        # from the exact recovered state.
        await self.compact_journal()
        rto_ms = (time.perf_counter() - t0) * 1e3
        self.app.metrics.set_gauge(f"crash_rto_ms[{q}]", round(rto_ms, 3))
        self.app.metrics.counters.inc("crash_recoveries")
        self.app.events.append(
            "crash_recovered", q,
            f"unclean shutdown: {n_snap} snapshot + {n_tail} journal-tail "
            f"players restored, {len(rec.recent)} dedup entries, "
            f"rto {rto_ms:.1f} ms"
            + (" (snapshot fallback)" if rec.fallback else ""),
            component="durability",
            refs={"snapshot_players": n_snap, "players": n_tail,
                  "rto_ms": round(rto_ms, 3)})
        log.warning(
            "queue %r: recovered from unclean shutdown — %d snapshot + %d "
            "journal-tail players, %d dedup entries, rto %.1f ms",
            q, n_snap, n_tail, len(rec.recent), rto_ms)
        self.last_recovery = {
            "rto_ms": round(rto_ms, 3),
            "snapshot_players": n_snap,
            "tail_players": n_tail,
            "dedup_entries": len(rec.recent),
            "fallback": rec.fallback,
            "corrupt": list(rec.corrupt),
            "transcript": rec.transcript(),
        }
        return self.last_recovery

    # ---- hot-standby replication (ISSUE 17, service/replication.py) -------

    async def start_replication(self) -> None:
        """Attach this queue to the replication fabric as the PRIMARY:
        adopt a takeover handoff if one is registered (the failover
        successor path), acquire/renew the lease, wire the journal's tap
        + fence seams, ship the full-state baseline, and start the pump.
        Called by app.start() AFTER recover_from_journal — the baseline
        must be the recovered truth, not the pre-crash one."""
        hub = self.app.replication_hub
        rcfg = self.app.cfg.replication
        if hub is None or not rcfg.enabled():
            return
        j = self.journal
        if j is None:
            raise ValueError(
                "replication requires durability (journal_dir): the WAL "
                "is the replication stream source")
        from matchmaking_tpu.service.replication import QueueReplication

        q = self.queue_cfg.name
        adopted = hub.adopted.pop(q, None)
        if adopted is not None:
            await self.recover_from_replica(adopted)
        owner = rcfg.owner or "primary"
        # Raises LeaseHeldError when another owner's lease is live — the
        # boot-time split-brain guard: two primaries cannot coexist.
        epoch = hub.authority.acquire(q, owner, time.monotonic())
        repl = QueueReplication(q, owner, epoch, hub.authority, hub.link(q),
                                metrics=self.app.metrics,
                                events=self.app.events)
        async with self._engine_lock:
            # Tap + baseline under the engine lock on the event loop: no
            # dispatch (lock) and no settle (loop) can append between
            # the seam install and the baseline capture, so the stream
            # the standby sees is gapless from its baseline seq.
            self.replication = repl
            j.tap = repl.on_record
            j.fence = repl.may_write
            repl.send_baseline(j.seq, self._baseline_payload(time.time()))
        self._repl_task = asyncio.create_task(self._replication_loop())
        self.app.events.append(
            "replication_attached", q,
            f"owner {owner!r} epoch {epoch}, baseline seq {j.seq}",
            component="replication",
            refs={"epoch": epoch, "records": j.seq})

    # holds-lock: _engine_lock
    def _baseline_payload(self, now: float) -> bytes:
        """Full-state baseline for a freshly attached standby: the live
        waiting pool as admit-shaped rows (region/mode by NAME — the
        journal's portability rule), the unexpired dedup entries, and
        the admission checkpoint."""
        from matchmaking_tpu.service.replication import baseline_payload

        try:
            reqs = self.engine.waiting()
        except Exception:
            reqs = []
        rows = [
            [r.id, float(r.rating), float(r.rating_deviation), r.region,
             r.game_mode,
             (None if r.rating_threshold is None
              else float(r.rating_threshold)),
             float(r.enqueued_at), r.reply_to, r.correlation_id,
             int(r.tier), float(r.deadline_at)]
            for r in reqs
        ]
        recent = [(pid, body, exp)
                  for pid, (body, exp) in self._recent.items() if exp > now]
        adm = (self.admission.checkpoint()
               if self.admission is not None else None)
        return baseline_payload(rows, recent, adm)

    async def recover_from_replica(self, adopted: "dict[str, Any]") -> dict:
        """Cross-host failover adoption: apply the standby's shadow state
        (waiting pool + dedup cache + admission checkpoint — everything
        the replication stream delivered before the takeover cut) into
        this fresh runtime. The whole span is the measured failover RTO
        (``failover_rto_ms`` gauge + ``failover_takeover`` event) —
        bounded by replication lag, never by journal size, because the
        shadow already holds everything the old primary streamed."""
        from matchmaking_tpu.utils.journal import row_to_request

        rec = adopted["state"]
        q = self.queue_cfg.name
        t0 = time.perf_counter()
        now = time.time()
        # The takeover's causal chain onto the event spine (ISSUE 18),
        # in cause order with epoch refs linking the links: the analyzer
        # (scripts/postmortem.py) reconstructs lease expiry → epoch bump
        # → replay window → takeover from the bundle alone.
        epoch = int(adopted["epoch"])
        self.app.events.append(
            "lease_expired", q,
            f"predecessor's lease lapsed; standby {adopted['owner']!r} "
            f"claimed the queue", component="replication",
            refs={"epoch": epoch - 1})
        self.app.events.append(
            "epoch_bump", q,
            f"takeover fenced epoch {epoch - 1} -> {epoch}",
            component="replication",
            refs={"epoch": epoch, "prev_epoch": epoch - 1})
        async with self._engine_lock:
            # Same contract as journal replay: the adopted pool
            # invalidates any speculation against the empty boot pool.
            self._spec_invalidate_audited("replica adoption")

            def apply() -> int:
                tail = [row_to_request(rec.waiting[pid])
                        for pid in sorted(rec.waiting)]
                if tail:
                    self.engine.restore(tail, now)
                if hasattr(self.engine, "heartbeat"):
                    self.engine.heartbeat(now)
                return len(tail)

            n_tail = await asyncio.to_thread(apply)
            for pid, (body, exp) in rec.recent.items():
                if exp > now:
                    self._recent.set(pid, (body, exp))
            if rec.admission is not None and self.admission is not None:
                self.admission.restore_state(rec.admission)
        if self.journal is not None:
            # Anchor the adopted pool in THIS host's journal immediately:
            # a crash right after takeover must recover from local disk
            # without needing the (dead) predecessor's stream again.
            await self.compact_journal()
        rto_ms = (time.perf_counter() - t0) * 1e3
        self.app.events.append(
            "replay_window", q,
            f"standby shadow applied: {n_tail} waiting players, "
            f"{len(rec.recent)} dedup entries (applied seq "
            f"{adopted.get('applied_seq', 0)})", component="replication",
            refs={"epoch": epoch, "players": n_tail,
                  "records": int(adopted.get("applied_seq", 0))})
        self.app.metrics.set_gauge(f"failover_rto_ms[{q}]", round(rto_ms, 3))
        self.app.metrics.counters.inc("failover_takeovers")
        self.app.events.append(
            "failover_takeover", q,
            f"epoch {adopted['epoch']}: {n_tail} waiting players adopted, "
            f"{len(rec.recent)} dedup entries, rto {rto_ms:.1f} ms",
            component="replication",
            refs={"epoch": epoch, "players": n_tail,
                  "rto_ms": round(rto_ms, 3)})
        log.warning(
            "queue %r: failover takeover (epoch %s) — %d waiting players "
            "adopted, %d dedup entries, rto %.1f ms",
            q, adopted["epoch"], n_tail, len(rec.recent), rto_ms)
        self.last_recovery = {
            "rto_ms": round(rto_ms, 3),
            "snapshot_players": 0,
            "tail_players": n_tail,
            "dedup_entries": len(rec.recent),
            "fallback": False,
            "corrupt": [],
            "transcript": rec.transcript(),
            "source": "replica",
            "epoch": adopted["epoch"],
        }
        return self.last_recovery

    async def _replication_loop(self) -> None:
        """Sender pump: ack collection, stall retransmission, lease
        renewal, lag gauges. Supervised like the other timers — one
        failed pump must not end replication for the process."""
        interval = self.app.cfg.replication.pump_interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                r = self.replication
                if r is None:
                    continue
                r.pump(time.monotonic())
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("replication pump failed; retrying")
                self.app.metrics.counters.inc("replication_pump_errors")

    def _note_failure(self, err: BaseException) -> None:
        """Classify an engine failure before the revive: a device-LOSS
        error (chaos-scripted or a real XLA device loss) names the dead
        mesh participant — the next ``_revive_engine`` demotes an
        elastic-shardable queue to its surviving devices instead of
        revive-looping an engine bound to the dead chip."""
        from matchmaking_tpu.utils.chaos import ChaosDeviceLostError

        if isinstance(err, ChaosDeviceLostError):
            self._lost_device = err.device
            self.app.events.append(
                "device_lost", self.queue_cfg.name,
                f"logical device {err.device}")

    # settles: delivery
    def _ack(self, delivery: Delivery) -> None:
        """Ack + release the delivery's admission credit. EVERY runtime
        settle path comes through here (or _nack): the credit limiter's
        inflight count is exactly the deliveries admitted but unsettled,
        and a leaked credit would tighten admission forever. Journal
        commit FIRST (write-ahead): with fsync="window" an acked delivery
        implies its window's journaled mutations are durable."""
        self._journal_commit()
        self.app.broker.ack(self.consumer_tag, delivery.delivery_tag)
        if self.admission is not None:
            self.admission.release(delivery.delivery_tag)

    # settles: delivery
    def _nack(self, delivery: Delivery, requeue: bool = True) -> None:
        """Nack twin of _ack. The credit is released even on requeue: the
        redelivery re-enters through admission and takes a fresh credit
        (or a shed/expired response, if the queue tightened meanwhile)."""
        self._journal_commit()
        self.app.broker.nack(self.consumer_tag, delivery.delivery_tag,
                             requeue=requeue)
        if self.admission is not None:
            self.admission.release(delivery.delivery_tag)

    # settles: delivery
    def _shed_delivery(self, delivery: Delivery) -> None:
        """Explicit rejection under overload: a ``shed`` response with a
        retry-after hint, acked — never silent rot in an unbounded queue.
        Runs BEFORE decode (nothing is spent on a request we won't serve),
        so player_id is unknown; clients correlate by correlation_id."""
        assert self.admission is not None
        tr = self._trace(delivery)
        if tr is not None:
            tr.tier = delivery.tier
            tr.mark("shed")
        self.admission.record_shed(
            f"inflight={self.admission.inflight()} "
            f"pool={self.engine.pool_size()}", tier=delivery.tier)
        tiered = self.admission.tiers > 1
        self._respond_raw(
            delivery.properties.reply_to, delivery.properties.correlation_id,
            SearchResponse(
                status="shed", player_id="",
                retry_after_ms=self.app.cfg.overload.retry_after_ms,
                trace_id=tr.trace_id if tr is not None else "",
                tier=delivery.tier if tiered else None),
            trace=tr)
        self._ack(delivery)
        if tr is not None:
            self._settle_trace(delivery, "shed")

    # settles: delivery
    def _expire_delivery(self, delivery: Delivery, now: float,
                         player_id: str = "") -> None:
        """Deadline-expired: cancel without dispatch. The ``expired`` trace
        mark with NO ``dispatch`` mark after it is the auditable proof no
        device work was spent on a client that already gave up."""
        tr = self._trace(delivery)
        if tr is not None:
            if player_id:
                tr.player_id = player_id
            tr.tier = delivery.tier
            tr.mark("expired", now)
        tiered = self.admission is not None and self.admission.tiers > 1
        if self.admission is not None:
            self.admission.record_expired(
                f"player={player_id or '?'} tag={delivery.delivery_tag}",
                tier=delivery.tier)
        self._respond_raw(
            delivery.properties.reply_to, delivery.properties.correlation_id,
            SearchResponse(status="timeout", player_id=player_id,
                           trace_id=tr.trace_id if tr is not None else "",
                           tier=delivery.tier if tiered else None),
            trace=tr)
        self._ack(delivery)
        if tr is not None:
            self._settle_trace(delivery, "expired")

    def _deadline_expired(self, delivery: Delivery, now: float) -> bool:
        """Has this delivery's propagated deadline passed? Gated on the
        admission controller so a service without overload control pays
        zero header lookups per delivery."""
        if self.admission is None:
            return False
        deadline = self._delivery_deadline(delivery)
        return deadline > 0.0 and now >= deadline

    @staticmethod
    def _delivery_deadline(delivery: Delivery) -> float:
        """The delivery's absolute deadline (0.0 = none), from the cache
        admission filled — parsed from the stamped header at most once
        per delivery (lazy fallback for paths that bypass admission)."""
        dl = delivery.deadline
        if dl < 0.0:
            dl = deadline_of(delivery.properties.headers) or 0.0
            delivery.deadline = dl
        return dl

    @staticmethod
    def _edf_key(item: "tuple[SearchRequest | None, Delivery]"):
        """Window-cut ordering key (OverloadConfig.edf): (tier, absolute
        x-deadline, no-deadline-last). Pure function of the delivery —
        tier and deadline were cached at admission — so two identical
        ingress sequences cut identical windows. Stable sort keeps FIFO
        within equal keys."""
        _, delivery = item
        deadline = _QueueRuntime._delivery_deadline(delivery)
        return (delivery.tier, deadline if deadline else float("inf"))

    @property
    def edf_on(self) -> bool:
        return self.batcher.sort_key is not None

    def set_edf(self, on: bool) -> None:
        """Toggle EDF window cutting at runtime (the autotuner's knob,
        control/autotune.py). The key reads only the tier/deadline caches
        admission stamps, so flipping it mid-traffic is safe — the next
        cut simply sorts (or stops sorting) the backlog. Callers gate on
        ``admission is not None`` (without admission every key is
        (0, inf) and the sort is a paid no-op)."""
        self.batcher.sort_key = self._edf_key if on else None

    # ---- window-granular admission (ISSUE 9) ------------------------------

    # settles-some: deliveries
    def _admission_cut(self, deliveries: list[Delivery],
                       now: float) -> "set[int] | None":
        """The batched admission ladder over one cut window: ONE
        pool_tier_counts/pool_size read + one decide_batch pass in ARRIVAL
        order (the EDF sort reordered the window for dispatch, never for
        admission), sheds settled with batch-encoded responses and one
        batch publish. Returns the delivery TAGS to drop from the flush
        (None = keep all). Runs before decode, so a shed request still
        costs no decode work — the per-delivery semantics, window-granular."""
        ac = self.admission
        if ac is None or not self._batch_admission:
            return None
        ordered = sorted(deliveries, key=lambda d: d.arrival)
        pool_tiers = (self.engine.pool_tier_counts(ac.tiers)
                      if ac.tiers > 1 else None)
        decisions = ac.decide_batch(ordered, now, self.engine.pool_size(),
                                    pool_tiers)
        shed = [d for d, dec in zip(ordered, decisions) if dec is not ADMIT]
        if not shed:
            return None
        self._shed_deliveries(shed)
        return {d.delivery_tag for d in shed}

    # settles: *deliveries
    def _shed_deliveries(self, deliveries: list[Delivery]) -> None:
        """Batched twin of ``_shed_delivery`` for a window's shed rows:
        identical per-row accounting (one record_shed EVENT per row — the
        soaks count them), but bodies come from the native batch encoder
        and the responses leave in one publish_batch call."""
        import numpy as np

        from matchmaking_tpu.native import codec

        ac = self.admission
        assert ac is not None
        tiered = ac.tiers > 1
        retry = self.app.cfg.overload.retry_after_ms
        metas: list[tuple[Delivery, Any]] = []
        for d in deliveries:
            tr = self._trace(d)
            if tr is not None:
                tr.tier = d.tier
                tr.mark("shed")
            ac.record_shed(f"window cut tag={d.delivery_tag}", tier=d.tier)
            metas.append((d, tr))
        n = len(metas)
        bodies = None
        if codec.available():
            bodies = codec.encode_simple_batch(
                np.full(n, codec.KIND_SHED, np.int32), [""] * n,
                np.zeros(n, np.float64), np.full(n, retry, np.float64),
                [tr.trace_id if tr is not None else "" for _, tr in metas],
                np.fromiter((d.tier if tiered else -1 for d, _ in metas),
                            np.int32, n))
        rows: list[tuple[str, str, bytes, Any]] = []
        for j, (d, tr) in enumerate(metas):
            body = bodies[j] if bodies is not None else None
            if body is None:  # codec off or NEEDS_PYTHON row: exact contract
                body = encode_response(SearchResponse(
                    status="shed", player_id="", retry_after_ms=retry,
                    trace_id=tr.trace_id if tr is not None else "",
                    tier=d.tier if tiered else None))
            if tr is not None:
                tr.mark("encode")
            rows.append((d.properties.reply_to,
                         d.properties.correlation_id, body, tr))
        self._publish_batch(rows)
        for d, tr in metas:
            self._ack(d)
            if tr is not None:
                self._settle_trace(d, "shed")

    # ---- ingress ----------------------------------------------------------

    async def _on_delivery(self, delivery: Delivery) -> None:
        received_at = time.time()
        tr = self._trace(delivery)
        if tr is not None:
            tr.mark("consume", received_at)
        if self._batch_admission:
            # Window-granular admission (ISSUE 9): only the pre-checks run
            # per delivery — default-deadline stamp, tier/deadline caching
            # (the EDF cut key reads them), already-expired-at-receive,
            # drain-mode shed. The credit/occupancy ladder runs once per
            # cut window inside the flush (_admission_cut).
            assert self.admission is not None
            decision = self.admission.pre_decide(delivery, received_at)
            if tr is not None:
                tr.tier = delivery.tier
            if decision is EXPIRED:
                self._expire_delivery(delivery, received_at)
                return
            if decision is not ADMIT:  # draining
                self._shed_delivery(delivery)
                return
        elif self.admission is not None:
            # Per-delivery admission (batch_admission=False — the PR 5/7
            # path, byte for byte). Admission runs FIRST — before decode
            # and before any auth RPC round trip: an overloaded queue must
            # not spend middleware work on a request it is about to shed.
            # Tiered queues also hand the per-tier pool composition in, so
            # the nested-ladder partition check can count only
            # same-or-higher-priority occupancy (and oldest-policy
            # preemption knows whether a lower-priority victim exists).
            pool_tiers = (self.engine.pool_tier_counts(self.admission.tiers)
                          if self.admission.tiers > 1 else None)
            decision = self.admission.decide(delivery, received_at,
                                             self.engine.pool_size(),
                                             pool_tiers)
            if tr is not None:
                tr.tier = delivery.tier
            if decision is EXPIRED and delivery.redelivered:
                # A REDELIVERED expired copy may belong to a player who
                # already reached a terminal state (its matched response
                # lost in flight) — admission can't consult the dedup
                # cache pre-decode, so let it through: the flush checks
                # terminal-replay BEFORE deadline and either replays the
                # cached truth or expires it there.
                decision = ADMIT
            if decision is not ADMIT:
                if decision is EXPIRED:
                    self._expire_delivery(delivery, received_at)
                else:
                    self._shed_delivery(delivery)
                return
            self.admission.admit(delivery.delivery_tag, delivery.tier)
            try:
                await self._ingress_submit(delivery, received_at, tr)
            except BaseException:
                # Any crash between the admit above and the batcher
                # hand-off is settled by the BROKER layer (the consumer's
                # crash handler nacks without coming through _nack), which
                # would strand this delivery's admission credit: over AMQP
                # every redelivery carries a fresh tag, so leaked credits
                # accumulate until the queue sheds 100% of traffic.  ONE
                # wrapper owns the whole post-admit region — the
                # settlement rule (analysis/lifecycle.py) proved the old
                # per-call guards left the MessageContext build and the
                # inter-try trace marks on unprotected exception edges.
                self.admission.release(delivery.delivery_tag)
                raise
            return
        await self._ingress_submit(delivery, received_at, tr)

    # settles: delivery
    async def _ingress_submit(self, delivery: Delivery, received_at: float,
                              tr: "TraceContext | None") -> None:
        """Post-admission ingress: middleware (or the inline stamp) + the
        batcher hand-off.  On a normal return the delivery is either
        settled (middleware reject) or owned by the batcher; on an
        exception the CALLER settles (credit release in the per-delivery
        admission wrapper, broker-level nack above that)."""
        if self._inline_ingress:
            # Columnar + auth "none" (ISSUE 9): the whole middleware chain
            # is the first-received stamp — run it inline instead of
            # paying a MessageContext + nested coroutine frames per
            # delivery. Same headers, same marks (middleware/batch), same
            # deferred decode; auth-configured services keep the chain.
            headers = delivery.properties.headers
            first = headers.setdefault("x-first-received", received_at)
            try:
                delivery.first_received = float(first)
            except (TypeError, ValueError):
                delivery.first_received = received_at
            if tr is not None:
                tr.mark("middleware")
                tr.mark("batch")
            delivery.arrival = self._arrival_seq
            self._arrival_seq += 1
            self.batcher.submit((None, delivery))
            # Ingest accounting (ISSUE 12): the per-delivery consume cost,
            # measured where it is spent — the batched twin records one
            # span per burst; this records one per delivery, so the
            # consume-share comparison across the two configs is honest.
            self.app.attribution.observe_site(
                self.queue_cfg.name, "consume",
                time.time() - received_at, 1)
            return
        ctx = MessageContext(delivery=delivery, queue=self.queue_cfg.name,
                             received_at=received_at)
        try:
            await self.pipeline.run(ctx)
        except MiddlewareReject as e:
            self.app.metrics.counters.inc("rejected_by_middleware")
            self._respond_error(delivery, e.code, e.reason)
            self._ack(delivery)
            if tr is not None:
                tr.mark("reject")
                self._settle_trace(delivery, "rejected")
            return
        if tr is not None:
            tr.mark("batch")
        # Arrival stamp: the batched admission pass re-orders the (possibly
        # EDF-sorted) cut window back into consume order with it, so
        # batching cannot reorder admission decisions. Re-stamped per
        # submit — a redelivery takes its re-consume position, exactly as
        # per-delivery admission decided it.
        delivery.arrival = self._arrival_seq
        self._arrival_seq += 1
        if ctx.request is None:
            # Columnar ingress: the pipeline left decoding to the
            # batched native codec (1v1 queues) — middleware only ran
            # auth/validity checks that need headers.
            self.batcher.submit((None, delivery))
            return
        if tr is not None:
            tr.player_id = ctx.request.id
        self.batcher.submit((ctx.request, delivery))

    # ---- batched ingress: one callback per consume burst (ISSUE 12) ------

    # settles-some: deliveries
    async def _on_delivery_batch(self, deliveries: list[Delivery]) -> None:
        """The consume_batch ingress: ONE invocation per drained broker
        burst. The fast path (columnar + inline ingress) runs the
        admission pre-checks, the first-received stamp, arrival stamping,
        the native burst decode (shard workers), and the batcher hand-off
        in one pass — one clock read and one decode call per burst where
        the per-delivery path paid them per delivery. Queues that need
        per-delivery semantics (middleware chains, legacy per-delivery
        admission, non-columnar engines) loop the per-delivery handler —
        identical behavior, minus the per-delivery handler TASK."""
        if not self._inline_ingress:
            for delivery in deliveries:
                await self._on_delivery(delivery)
            return
        received_at = time.time()
        t_burst = time.perf_counter()  # monotonic twin: the ingest spans
        # Spans never cover an await: the pre-checks here, the hand-off
        # after the decode below (whose native call has its own span).
        with stage("ingest", rows=len(deliveries)):
            ac = self.admission
            # Window-granular admission's pre-checks, ONE pass
            # over the burst (_inline_ingress guarantees batch_admission
            # here): per-row pre_decide logic in burst order, amortized to
            # one call.
            decisions = (ac.pre_decide_batch(deliveries, received_at)
                         if ac is not None else None)
            live: list[Delivery] = []
            for idx, delivery in enumerate(deliveries):
                tr = self._trace(delivery)
                if tr is not None:
                    tr.mark("consume", received_at)
                if decisions is not None:
                    decision = decisions[idx]
                    if tr is not None:
                        tr.tier = delivery.tier
                    if decision is EXPIRED:
                        self._expire_delivery(delivery, received_at)
                        continue
                    if decision is not ADMIT:  # draining
                        self._shed_delivery(delivery)
                        continue
                headers = delivery.properties.headers
                first = headers.setdefault("x-first-received", received_at)
                try:
                    delivery.first_received = float(first)
                except (TypeError, ValueError):
                    delivery.first_received = received_at
                if tr is not None:
                    # Same mark vocabulary as the per-delivery inline path
                    # so the trace classes are stable across configs; the
                    # burst handler's real cost is measured ONCE per burst
                    # into the `consume`/`decode` site categories instead
                    # of being smeared N× across member traces.
                    tr.mark("middleware", received_at)
                    tr.mark("batch", received_at)
                delivery.arrival = self._arrival_seq
                self._arrival_seq += 1
                live.append(delivery)
        if not live:
            return
        from matchmaking_tpu.native import codec

        decode_s = 0.0
        if len(live) >= _MIN_DECODE_BURST and codec.available():
            # The decode side of PR 9's batch encoder: one native call
            # over the burst's concatenated bodies + offsets; NEEDS_PYTHON
            # rows fall back through the contract path on the shard
            # workers; malformed rows settle here (reject + ack) exactly
            # as the flush's decode would have.
            t_dec = time.perf_counter()
            live, rejects = await self._shards.decode_burst(live)
            decode_s = time.perf_counter() - t_dec
            for delivery, counter, code, reason in rejects:
                self._reject_delivery(delivery, counter, code, reason)
            self.app.attribution.observe_site(
                self.queue_cfg.name, "decode", decode_s,
                len(live) + len(rejects))
        with stage("ingest", rows=len(live)):
            self.batcher.submit_many([(None, d) for d in live])
        # Monotonic throughout (perf_counter — a wall-clock step must not
        # produce a negative span the observe guard would silently drop);
        # at ingress_shards>1 the decode await can suspend, so decode_s
        # may include other tasks' loop time — noise, bounded by the
        # burst cadence, and identical across the A/B configs.
        self.app.attribution.observe_site(
            self.queue_cfg.name, "consume",
            max(0.0, (time.perf_counter() - t_burst) - decode_s),
            len(deliveries))

    # ---- the window flush: THE seam into Engine.search --------------------

    # settles: *window
    async def _flush(self, window: list[tuple[SearchRequest, Delivery]]) -> None:
        self._flushing += 1
        try:
            await self._flush_inner(window)
        except Exception:
            # A breaker demotion/re-promotion can swap the engine while a
            # flush that already chose the columnar/pipelined branch is
            # parked on the engine lock. Whatever went wrong, the window's
            # deliveries must be SETTLED — stranding them unacked eats
            # broker prefetch slots until the queue stops consuming.
            # Nack-requeue is the at-least-once answer (redeliveries are
            # deduped against the pool / _recent).
            log.exception("window flush failed; nacking its deliveries")
            self.app.metrics.counters.inc("flush_errors")
            for _, delivery in window:
                self._nack(delivery)
        finally:
            self._flushing -= 1

    # settles: *window
    async def _flush_inner(self, window: list[tuple[SearchRequest, Delivery]]) -> None:
        if self._columnar:
            await self._flush_columnar([d for _, d in window])
            return
        now = time.time()
        if self._batch_admission:
            # Window-granular admission (ISSUE 9) — before the straggler
            # decode below, so a shed request costs no decode work.
            dropped = self._admission_cut([d for _, d in window], now)
            if dropped:
                window = [(r, d) for r, d in window
                          if d.delivery_tag not in dropped]
                if not window:
                    return
        if any(req is None for req, _ in window):
            # Transition stragglers: these deliveries entered through the
            # columnar ingress (decode deferred to the batched codec), but
            # the engine has since been demoted to the host oracle — decode
            # them per object here; the shapes may be mixed in one window.
            window = self._decode_deferred(window)
        # At-least-once dedup: a redelivered copy of a request whose player
        # already reached a terminal state must not re-enter the pool (the
        # player could end up in two matches); replay the cached response.
        self._prune_recent(now)
        # QoS metadata rides the frozen request object from here on: the
        # pool mirror (tier column for priority-aware eviction, deadline
        # column for the per-slot expiry sweep) is populated from request
        # fields, and headers are gone once the delivery settles.
        stamp_qos = self.admission is not None
        fresh: list[tuple[SearchRequest, Delivery]] = []
        for req, delivery in window:
            if stamp_qos:
                deadline = self._delivery_deadline(delivery)
                if delivery.tier or deadline:
                    req = dataclasses.replace(
                        req, tier=delivery.tier, deadline_at=deadline)
            tr = delivery.trace
            if tr is not None:
                tr.player_id = req.id
                tr.mark("flush", now)
            cached = self._recent.get(req.id)
            if cached is not None and cached[1] <= now:
                self._recent.pop(req.id)  # expired: a genuine re-queue
                cached = None
            if cached is not None:
                # Terminal replay BEFORE the deadline check (same order as
                # the pipelined pre-dispatch sweep): a redelivered copy of
                # an already-matched player must replay "matched", not
                # contradict it with a post-deadline "timeout".
                self.app.metrics.counters.inc("deduped_replays")
                self._publish_body(req.reply_to, req.correlation_id,
                                   cached[0], trace=tr)
                self._ack(delivery)
                if tr is not None:
                    tr.mark("dedup_replay")
                    self._settle_trace(delivery, "deduped")
            elif self._deadline_expired(delivery, now):
                # Deadline check #2 (batch formation): the request was live
                # at admission but its deadline passed while it waited in
                # the batcher — cancel before any engine work.
                self._expire_delivery(delivery, now, player_id=req.id)
            else:
                fresh.append((req, delivery))
        window = fresh
        if not window:
            return
        requests = [r for r, _ in window]
        deliveries_in = [d for _, d in window]

        if self._pipelined:
            # Object-path pipelining (device team queues + 1v1 object
            # ingress): the full SearchOutcome (incl. dispatch-time
            # rejections) arrives under the window's token at collection.
            def dispatch(drop: set[str]):
                reqs = ([r for r in requests if r.id not in drop]
                        if drop else requests)
                # matchlint: ignore[guarded-by] closure runs under _engine_lock inside _dispatch_pipelined (via to_thread)
                tok, _ = self.engine.search_async(reqs, now)
                self._journal_admit_reqs(reqs)  # matchlint: ignore[guarded-by] same lock-held closure
                return tok

            await self._dispatch_pipelined(
                dispatch, [(r.id, d) for r, d in window], now)
            return

        try:
            # Engine.search blocks (host work + device step); keep the event
            # loop responsive for other queues. The lock serializes against
            # the timeout sweeper.
            async with self._engine_lock:
                if self.admission is not None:
                    # shed_policy="oldest" debt from actual occupancy
                    # (synchronous engines have no windows in flight, so
                    # eviction is legal here). Tiered queues settle the
                    # debt across pool ∪ window, lowest priority first.
                    debt = self.admission.eviction_debt(
                        len(requests), self.engine.pool_size())
                    drop = await self._pay_debt_locked(
                        [(r.id, r.tier, r.enqueued_at, d)
                         for r, d in window], debt, now)
                    if drop:
                        window = [(r, d) for r, d in window
                                  if r.id not in drop]
                        if not window:
                            return
                        requests = [r for r, _ in window]
                        deliveries_in = [d for _, d in window]
                # Dispatch mark AFTER debt settlement: a window entrant
                # shed as a debt victim must not carry a dispatch mark —
                # the mark is the audit convention for "engine work was
                # spent" (the pipelined/columnar twins order it the same
                # way). Lock wait lands in flush→dispatch, which is the
                # pipeline_slot_wait category's definition.
                t_disp = time.time()
                for delivery in deliveries_in:
                    if delivery.trace is not None:
                        delivery.trace.mark("dispatch", t_disp)
                # Arbiter slot (ISSUE 11): (tier, deadline) turn against
                # co-located queues — inside the engine lock (see
                # _dispatch_pipelined), spanning the synchronous step
                # (dispatch == device step for engines without the
                # pipelined API; the device serializes them anyway).
                async with self._arbiter_slot(deliveries_in):

                    def run_search():
                        # Journal the admits at dispatch (write-ahead): the
                        # sync step admits AND matches in one call, so the
                        # replay order is admit-then-terminal either way.
                        # (Lexically inside the lock body — matchlint sees
                        # the dominance directly, no ignores needed.)
                        self._journal_admit_reqs(requests)
                        return self.engine.search(requests, now)

                    outcome = await asyncio.to_thread(run_search)
        except Exception as e:
            log.exception("engine step crashed; reviving engine from mirror")
            self._note_failure(e)
            self._record_engine_crash(now)
            # Sync crash path: the raise released the lock, and no await
            # separates detection from rebuild, so nothing can interleave.
            # matchlint: ignore[guarded-by] revive sequence is await-free; the lock guards cross-await atomicity only
            self._revive_engine(now)
            for delivery in deliveries_in:
                self._nack(delivery)
            return
        t_col = time.time()
        for delivery in deliveries_in:
            if delivery.trace is not None:
                delivery.trace.mark("collect", t_col)
        self._publish_outcome(outcome, now,
                              trace_ids=self._trace_id_map(deliveries_in),
                              traces=self._trace_map(deliveries_in))
        for delivery in deliveries_in:
            self._ack(delivery)
        self._settle_outcome_traces(outcome, deliveries_in)
        self.app.metrics.counters.inc("windows")
        self.app.metrics.counters.inc("requests_batched", len(window))

    def _first_received(self, delivery: Delivery, now: float) -> float:
        """Client-settable ``x-first-received`` stamp, from the cache the
        ingress middleware filled (Delivery.first_received) — the columnar
        flush reads this per lane, and a header parse per lane is exactly
        the per-delivery hot-path work ISSUE 9 removed (matchlint's perf
        rule now flags it). Lazy header fallback for paths that bypass the
        middleware; a non-numeric value must not crash the whole window
        flush (it would strand every delivery in it)."""
        cached = delivery.first_received
        if cached >= 0.0:
            return cached
        try:
            first = float(delivery.properties.headers.get(
                "x-first-received", now))
        except (TypeError, ValueError):
            first = now
        delivery.first_received = first
        return first

    # settles: delivery
    def _reject_delivery(self, delivery: Delivery, counter: str,
                         code: str, reason: str) -> None:
        """THE reject settle — counter + error response + ack + trace
        settle. Every decode/party reject (per-delivery fallback, flush
        row resolution, consume-burst rejects) funnels here so the paths
        cannot drift: the equivalence soaks pin them to each other."""
        self.app.metrics.counters.inc(counter)
        self._respond_error(delivery, code, reason)
        self._ack(delivery)
        if delivery.trace is not None:
            delivery.trace.mark("reject")
            self._settle_trace(delivery, "rejected")

    # settles-some: delivery
    def _decode_or_reject(self, delivery: Delivery,
                          now: float) -> SearchRequest | None:
        """Decode one delivery through the semantic codec; a ContractError
        is rejected + acked here and returns None. The ONE slow-path
        decode, shared by the columnar flush's Python fallback and the
        demoted-queue straggler path — reject handling must not diverge
        between them."""
        from matchmaking_tpu.service.contract import ContractError, decode_request

        try:
            return decode_request(
                delivery.body,
                reply_to=delivery.properties.reply_to,
                correlation_id=delivery.properties.correlation_id,
                queue=self.queue_cfg.name,
                enqueued_at=self._first_received(delivery, now),
            )
        except ContractError as e:
            self._reject_delivery(delivery, "rejected_by_middleware",
                                  e.code, e.reason)
            return None

    def _decode_deferred(
        self, window: list[tuple[SearchRequest | None, Delivery]]
    ) -> list[tuple[SearchRequest, Delivery]]:
        """Decode deliveries whose request is still None (columnar ingress
        deferred decoding to the flush, then the engine lost its columnar
        API to a breaker demotion). Malformed payloads are rejected+acked
        here, exactly as the columnar flush would have."""
        now = time.time()
        out: list[tuple[SearchRequest, Delivery]] = []
        for req, delivery in window:
            if req is None:
                req = self._decode_or_reject(delivery, now)
                if req is None:
                    continue
            out.append((req, delivery))
        return out

    # settles: *deliveries
    async def _flush_columnar(self, deliveries: list[Delivery]) -> None:
        """Columnar window flush, window-granular end to end (ISSUE 9):
        batched admission pass → batched native decode → batch dedup probe
        → vectorized column assembly → pipelined columnar engine step →
        batch-encoded responses in one publish call.

        Per-delivery Python is reduced to the dedup probe's dict lookups
        and the rows the native codec flags NEEDS_PYTHON (parties/escapes),
        which re-decode through contract.decode_request — the semantic
        truth."""
        import numpy as np

        from matchmaking_tpu.native import codec
        from matchmaking_tpu.service.contract import RequestColumns

        now = time.time()
        # The cut to the hand-off: everything up to the first await.
        with stage("flush", rows=len(deliveries)):
            self._prune_recent(now)
            if self._batch_admission:
                # Admission ladder once per window, before decode — a shed
                # request costs no decode work, exactly like the per-delivery
                # flow (which also shed pre-decode).
                dropped = self._admission_cut(deliveries, now)
                if dropped:
                    deliveries = [d for d in deliveries
                                  if d.delivery_tag not in dropped]
                    if not deliveries:
                        return
            # Consume-time decoded windows: when EVERY lane carries
            # a burst-decoded row reference (Delivery.row, set by the ingress
            # shard workers), the flush decode is skipped entirely and the
            # column assembly below gathers from the burst columns. A MIXED
            # window (redeliveries consumed through the per-delivery fault
            # path have no row) re-decodes wholesale — rare, and correct by
            # construction (the body is unchanged).
            pre = all(d.row is not None for d in deliveries)
            native = None
            t_dec = time.perf_counter()
            if not pre:
                bodies = [bytes(d.body) for d in deliveries]
                native = (codec.decode_batch(bodies) if codec.available()
                          else None)

            traced = any(d.trace is not None for d in deliveries)
            if traced:
                for d in deliveries:
                    if d.trace is not None:
                        d.trace.mark("flush", now)

            # Row resolution: native-OK rows stay columnar end to end; only
            # NEEDS_PYTHON rows materialize a SearchRequest, and only
            # malformed rows pay a response here. ``rows``: (source index,
            # player id, fallback request or None).
            if native is not None:
                (ids_n, rating_n, rd_n, thr_n, regions_n, modes_n,
                 status_n) = native
                status_l = status_n.tolist()
            rows: list[tuple[int, str, SearchRequest | None]] = []
            if pre:
                # Burst-decoded: every row is valid (malformed rows settled at
                # consume); the pid column reads straight out of the burst.
                for i, delivery in enumerate(deliveries):
                    burst, j = delivery.row
                    rows.append((i, burst.ids[j], None))
            else:
                for i, delivery in enumerate(deliveries):
                    st = (int(status_l[i]) if native is not None
                          else codec.NEEDS_PYTHON)
                    if st == codec.OK:
                        rows.append((i, ids_n[i], None))
                        continue
                    if st != codec.NEEDS_PYTHON:
                        self._reject_delivery(
                            delivery, "rejected_by_middleware",
                            codec.error_code(st), "malformed payload")
                        continue
                    # Python fallback (codec unavailable or NEEDS_PYTHON row).
                    req = self._decode_or_reject(delivery, now)
                    if req is None:
                        continue
                    if req.party_size > 1:
                        # 1v1 queue: parties are unservable (oracle semantics).
                        self._reject_delivery(
                            delivery, "rejected_by_engine",
                            "party_not_supported",
                            "engine rejected request: party_not_supported")
                        continue
                    rows.append((i, req.id, req))
                # Flush-time decode accounting (the consume_batch=off twin of
                # the burst-decode observation — same category, same meaning).
                self.app.attribution.observe_site(
                    self.queue_cfg.name, "decode",
                    time.perf_counter() - t_dec, len(deliveries))
            if traced:
                for src, pid, _req in rows:
                    tr = deliveries[src].trace
                    if tr is not None:
                        tr.player_id = pid
                        tr.tier = deliveries[src].tier

            # Batch dedup probe (at-least-once terminal replay) + deadline
            # check #2 (batch formation). Terminal replay BEFORE the deadline
            # check — see the object-path twin: "matched" must never be
            # followed by a contradictory post-deadline "timeout".
            recent = self._recent
            check_deadline = self.admission is not None
            keep: list[tuple[int, str, SearchRequest | None]] = []
            for src, pid, req in rows:
                delivery = deliveries[src]
                cached = recent.get(pid)
                if cached is not None and cached[1] <= now:
                    recent.pop(pid)  # expired: a genuine re-queue
                    cached = None
                if cached is not None:
                    self.app.metrics.counters.inc("deduped_replays")
                    self._publish_body(delivery.properties.reply_to,
                                       delivery.properties.correlation_id,
                                       cached[0], trace=delivery.trace)
                    self._ack(delivery)
                    if delivery.trace is not None:
                        delivery.trace.mark("dedup_replay")
                        self._settle_trace(delivery, "deduped")
                elif check_deadline and self._deadline_expired(delivery, now):
                    # Columnar twin of deadline check #2 — after decode, so
                    # the timeout quotes the player id.
                    self._expire_delivery(delivery, now, player_id=pid)
                else:
                    keep.append((src, pid, req))
            if not keep:
                return

            # QoS columns from the per-delivery caches (tier/deadline were
            # parsed at most once, at admission) — mirrored into the pool for
            # priority-aware eviction + the per-slot deadline sweep; None when
            # overload control is off so the pool stores plain zeros.
            stamp_qos = self.admission is not None
            k = len(keep)
            tier_col = (np.fromiter((deliveries[s].tier for s, _, _ in keep),
                                    np.int32, k) if stamp_qos else None)
            dl_col = (np.fromiter(
                (self._delivery_deadline(deliveries[s]) for s, _, _ in keep),
                np.float64, k) if stamp_qos else None)
            if self.app.cfg.overload.edf and stamp_qos and k > 1:
                # EDF, flush side: the batcher already cut by (tier, deadline),
                # but dedup/expiry/reject filtering just rewrote the lane set —
                # re-establish the order so when this window splits into bucket
                # CHUNKS, the near-deadline tier-0 lanes ride the first chunk.
                # Stable (arange tiebreak): FIFO within equal keys. Gated on
                # stamp_qos: edf without any admission knob leaves the QoS
                # columns None, and every key is (0, inf) then anyway: the
                # sort is a no-op there.
                dl_eff = np.where(dl_col > 0.0, dl_col, np.inf)
                order = np.lexsort((np.arange(k), dl_eff, tier_col))
                keep = [keep[j] for j in order.tolist()]
                tier_col = tier_col[order]
                dl_col = dl_col[order]

            # Column assembly: pure numpy takes of the native decode arrays in
            # the common all-native case; element-wise only for the rare
            # fallback rows.
            interner_r = self.engine.pool.regions.code
            interner_m = self.engine.pool.modes.code
            enq_col = np.fromiter(
                (self._first_received(deliveries[s], now) for s, _, _ in keep),
                np.float64, k)
            reply_col = np.fromiter(
                (deliveries[s].properties.reply_to for s, _, _ in keep),
                object, k)
            corr_col = np.fromiter(
                (deliveries[s].properties.correlation_id for s, _, _ in keep),
                object, k)
            if pre:
                # Merge shard/burst columns at the EDF cut: one
                # vectorized take per (burst, column) in final window order.
                # Region/mode are interned HERE — codes belong to the current
                # engine incarnation (a revive between consume and flush
                # rebuilds the interners).
                g_ids, g_rating, g_rd, g_thr, g_reg, g_mode = gather_rows(
                    [deliveries[s].row for s, _, _ in keep])
                cols = RequestColumns(
                    ids=g_ids,
                    rating=g_rating,
                    rd=g_rd,
                    region=np.fromiter(
                        (0 if r == "" else interner_r(r)
                         for r in g_reg.tolist()), np.int32, k),
                    mode=np.fromiter(
                        (0 if m == "" else interner_m(m)
                         for m in g_mode.tolist()), np.int32, k),
                    threshold=g_thr,
                    enqueued_at=enq_col, reply_to=reply_col,
                    correlation_id=corr_col, tier=tier_col, deadline=dl_col,
                )
            elif native is not None and all(req is None for _, _, req in keep):
                sel = np.fromiter((s for s, _, _ in keep), np.int64, k)
                cols = RequestColumns(
                    ids=ids_n[sel],
                    rating=rating_n[sel],
                    rd=rd_n[sel],
                    region=np.fromiter(
                        (0 if r == "" else interner_r(r)
                         for r in regions_n[sel].tolist()), np.int32, k),
                    mode=np.fromiter(
                        (0 if m == "" else interner_m(m)
                         for m in modes_n[sel].tolist()), np.int32, k),
                    threshold=thr_n[sel],
                    enqueued_at=enq_col, reply_to=reply_col,
                    correlation_id=corr_col, tier=tier_col, deadline=dl_col,
                )
            else:
                rating_a = np.empty(k, np.float32)
                rd_a = np.empty(k, np.float32)
                thr_a = np.empty(k, np.float32)
                reg_a = np.empty(k, np.int32)
                mode_a = np.empty(k, np.int32)
                for j, (s, _pid, req) in enumerate(keep):
                    if req is None:
                        rating_a[j] = rating_n[s]
                        rd_a[j] = rd_n[s]
                        thr_a[j] = thr_n[s]
                        r, m = regions_n[s], modes_n[s]
                    else:
                        rating_a[j] = req.rating
                        rd_a[j] = req.rating_deviation
                        thr_a[j] = (np.nan if req.rating_threshold is None
                                    else req.rating_threshold)
                        r = "" if req.region == "*" else req.region
                        m = "" if req.game_mode == "*" else req.game_mode
                    reg_a[j] = 0 if r == "" else interner_r(r)
                    mode_a[j] = 0 if m == "" else interner_m(m)
                cols = RequestColumns(
                    ids=np.fromiter((pid for _, pid, _ in keep), object, k),
                    rating=rating_a, rd=rd_a, region=reg_a, mode=mode_a,
                    threshold=thr_a, enqueued_at=enq_col, reply_to=reply_col,
                    correlation_id=corr_col, tier=tier_col, deadline=dl_col,
                )
            by_id = {pid: deliveries[s] for s, pid, _ in keep}

        if not self._pipelined:
            deliveries_in = [deliveries[s] for s, _, _ in keep]

            def run_engine():
                # Dispatch + flush OFF the event loop: first-window jit
                # compilation and per-window pack/H2D host work would
                # otherwise freeze every other queue's consumers, sweepers,
                # and auth RPC deadlines.
                # matchlint: ignore[guarded-by] closure runs under _engine_lock below (via to_thread)
                self.engine.search_columns_async(cols, now)
                self._journal_admit_cols(cols)  # matchlint: ignore[guarded-by] same lock-held closure
                return self.engine.flush()

            try:
                async with self._engine_lock:
                    if self.admission is not None:
                        # shed_policy="oldest" debt, depth-1 twin — debt
                        # from occupancy read UNDER the lock (a sweeper
                        # parked ahead of us may have just freed slots;
                        # a pre-lock read would over-evict) and paid
                        # before the dispatch opens a window (remove()
                        # requires _open == 0).
                        evict_debt = self.admission.eviction_debt(
                            k, self.engine.pool_size())
                        drop = await self._pay_debt_locked(
                            [(pid, d.tier, enq, d) for (_s, pid, _), d, enq
                             in zip(keep, deliveries_in,
                                    cols.enqueued_at.tolist())],
                            evict_debt, now)
                        if drop:
                            mask = np.fromiter(
                                (pid not in drop
                                 for pid in cols.ids.tolist()),
                                bool, len(cols))
                            cols = cols.take(mask)
                            deliveries_in = [
                                deliveries[s] for s, pid, _ in keep
                                if pid not in drop]
                            if not len(cols):
                                return
                    # Arbiter slot (ISSUE 11) — inside the engine lock,
                    # around the dispatch+flush only (see
                    # _dispatch_pipelined for the discipline).
                    async with self._arbiter_slot(deliveries_in):
                        outs = await asyncio.to_thread(run_engine)
                    # Error check + failed-token bookkeeping stay INSIDE
                    # the lock: a breaker demotion parked on it must not
                    # swap the engine between the flush and this read.
                    if self.engine.device_error is not None:
                        err, self.engine.device_error = (
                            self.engine.device_error, None)
                        raise err
                    for tok, _out in outs:
                        self.engine.failed_tokens.discard(tok)
            except Exception as e:
                log.exception("engine step crashed; reviving engine from mirror")
                self._note_failure(e)
                self._record_engine_crash(now)
                # Sync crash path — see the object-path twin above.
                # matchlint: ignore[guarded-by] revive sequence is await-free; the lock guards cross-await atomicity only
                self._revive_engine(now)
                for d in deliveries_in:
                    self._nack(d)
                return
            # Depth-1/never-empty by the flush() return contract: the
            # closure dispatched exactly one window under the lock, so
            # this loop's body runs exactly once — matchlint's settlement
            # rule now PROVES that shape (the flush-return value-flow
            # refinement), retiring the two inline ignores that sat here.
            for tok, out in outs:
                self._merge_window_marks(tok, deliveries_in)
                await self._handle_columnar_out(out, by_id, deliveries_in,
                                                now, tok)
            return

        # Pipelined path: dispatch without waiting; outcomes (publish + ack)
        # happen at collection — on later flushes or the collector tick.
        def dispatch(drop: set[str]):
            c = cols
            if drop:
                mask = np.fromiter((i not in drop for i in c.ids.tolist()),
                                   bool, len(c))
                c = c.take(mask)
            # matchlint: ignore[guarded-by] closure runs under _engine_lock inside _dispatch_pipelined (via to_thread)
            tok = self.engine.search_columns_async(c, now)
            self._journal_admit_cols(c)  # matchlint: ignore[guarded-by] same lock-held closure
            return tok

        await self._dispatch_pipelined(
            dispatch, [(pid, deliveries[s]) for s, pid, _ in keep], now)

    # ---- pipelined collection ---------------------------------------------

    # settles-some: pairs
    def _settle_terminal_locked(self, pairs: list[tuple[str, Delivery]],
                                now: float) -> set[str]:
        """Second dedup-cache check, run under the engine lock immediately
        before dispatch. The flush-time ``_recent`` check races pipelined
        collection: a redelivered copy of player p can pass it while p's
        first copy sits in an in-flight window; if that window collects
        (evicting p from the pool and writing ``_recent``) before this
        dispatch acquires the lock, the engine's pool-membership dedupe no
        longer sees p and would admit it into a SECOND match. Delegated-
        oracle windows widen the race to the whole dispatch→collection gap
        (the oracle matches and evicts at dispatch; ``_remember`` runs at
        collection) — hence the caller collects landed windows first.
        Replays + acks stale rows; returns their ids for the dispatch to
        drop."""
        stale: set[str] = set()
        for pid, delivery in pairs:
            cached = self._recent.get(pid)
            if cached is None or cached[1] <= now:
                continue  # absent or expired (a genuine re-queue)
            stale.add(pid)
            self.app.metrics.counters.inc("deduped_replays")
            self._publish_body(delivery.properties.reply_to,
                               delivery.properties.correlation_id, cached[0],
                               trace=delivery.trace)
            self._ack(delivery)
            if delivery.trace is not None:
                delivery.trace.mark("dedup_replay")
                self._settle_trace(delivery, "deduped")
        return stale

    # holds-lock: _engine_lock
    # settles-some: pairs
    def _settle_expired_locked(self, pairs: list[tuple[str, Delivery]],
                               now: float) -> set[str]:
        """Deadline check #3 (pre-dispatch), run under the engine lock
        immediately before the window dispatches: the batch-formation check
        raced the batcher wait and pipeline backpressure — a request can
        expire between the two. Cancelled here it costs zero device work
        (the acceptance proof: an ``expired`` trace mark with no
        ``dispatch`` mark after it). Returns the expired ids for the
        dispatch to drop."""
        if self.admission is None:
            return set()
        expired: set[str] = set()
        for pid, delivery in pairs:
            if self._deadline_expired(delivery, now):
                expired.add(pid)
                self._expire_delivery(delivery, now, player_id=pid)
        return expired

    # holds-lock: _engine_lock
    def _evict_oldest(self, k: int, now: float) -> list[SearchRequest]:
        """shed_policy="oldest": evict the k longest-waiting pool players,
        LOWEST-PRIORITY TIER FIRST (oldest within a tier) — under tiered
        QoS the eviction order is what makes degradation ordered: tier-2
        waiters absorb every eviction and a tier-0 waiter is touched only
        once no lower tier remains. Untiered pools (all tier 0) keep the
        plain oldest-first semantics. Runs in a worker thread with the
        engine lock held and no windows in flight (remove() requires it).
        O(pool) object materialization — acceptable: it only runs while
        the queue is at its occupancy cap, which the cap keeps small."""
        waiting = sorted(self.engine.waiting(),
                         key=lambda r: (-r.tier, r.enqueued_at))
        out: list[SearchRequest] = []
        for req in waiting[:k]:
            removed = self.engine.remove(req.id)
            if removed is not None:
                out.append(removed)
        return out

    # holds-lock: _engine_lock
    def _remove_ids(self, ids: list[str]) -> list[SearchRequest]:
        """Evict the named pool players (worker thread, lock held, no
        windows in flight — remove() requires it)."""
        out: list[SearchRequest] = []
        for pid in ids:
            removed = self.engine.remove(pid)
            if removed is not None:
                out.append(removed)
        return out

    # holds-lock: _engine_lock
    # settles-some: entering
    async def _pay_debt_locked(self, entering: "list[tuple[str, int, float, Delivery]]",
                               debt: int, now: float) -> set[str]:
        """Settle the occupancy debt for one dispatching window. Untiered:
        evict the ``debt`` longest-waiting pool players (the pre-tier
        semantics, byte for byte). Tiered: pick the ``debt`` LOWEST-
        PRIORITY candidates across pool ∪ window (tier descending, oldest
        first within a tier — stable on consume order, so replays are
        bit-identical): pool victims are evicted with shed-by-name
        responses, WINDOW victims are shed before dispatch — a tier-1
        entrant must absorb the shed itself, never displace a tier-0 pool
        member the admission ladder already protected. Returns the window
        pids to drop from the dispatch (settled here: shed response, ack,
        trace)."""
        if debt <= 0:
            return set()
        ac = self.admission
        if ac is None or ac.tiers <= 1:
            evicted = await asyncio.to_thread(self._evict_oldest, debt, now)
            self._publish_shed_evictions(evicted, now)
            return set()
        waiting = await asyncio.to_thread(self.engine.waiting)
        # kind 0 = pool, 1 = entering; construction order (pool in mirror
        # order, entrants in window order) is the stable tiebreak for
        # equal (tier, enqueued_at) — both are deterministic sequences.
        cands: list[tuple[int, float, int, str, Delivery | None]] = [
            (-r.tier, r.enqueued_at, 0, r.id, None) for r in waiting]
        cands.extend((-t, enq, 1, pid, d) for pid, t, enq, d in entering)
        cands.sort(key=lambda c: (c[0], c[1]))
        victims = cands[:debt]
        pool_ids = [pid for _, _, kind, pid, _ in victims if kind == 0]
        if pool_ids:
            evicted = await asyncio.to_thread(self._remove_ids, pool_ids)
            self._publish_shed_evictions(evicted, now)
        drop: set[str] = set()
        for _, _, kind, pid, delivery in victims:
            if kind != 1:
                continue
            assert delivery is not None
            drop.add(pid)
            tr = delivery.trace
            if tr is not None:
                tr.mark("shed")
            ac.record_shed(f"window debt {pid}", tier=delivery.tier)
            self._respond_raw(
                delivery.properties.reply_to,
                delivery.properties.correlation_id,
                SearchResponse(
                    status="shed", player_id=pid,
                    retry_after_ms=self.app.cfg.overload.retry_after_ms,
                    trace_id=tr.trace_id if tr is not None else "",
                    tier=delivery.tier),
                trace=tr)
            self._ack(delivery)
            if tr is not None:
                self._settle_trace(delivery, "shed")
        return drop

    def _publish_shed_evictions(self, evicted: list[SearchRequest],
                                now: float) -> None:
        """Shed responses for pool players evicted under the "oldest"
        policy. Remembered in the dedup cache: a redelivered copy of an
        evicted player must replay the shed, not silently re-enter."""
        tiered = self.admission is not None and self.admission.tiers > 1
        for req in evicted:
            if self.admission is not None:
                self.admission.record_shed(f"evicted oldest {req.id}",
                                           tier=req.tier)
            body = encode_response(SearchResponse(
                status="shed", player_id=req.id,
                retry_after_ms=self.app.cfg.overload.retry_after_ms,
                latency_ms=((now - req.enqueued_at) * 1e3
                            if req.enqueued_at else 0.0),
                tier=req.tier if tiered else None))
            self._remember(req.id, body, now)
            self._publish_body(req.reply_to, req.correlation_id, body)

    # settles: *pairs
    async def _dispatch_pipelined(self, dispatch,
                                  pairs: list[tuple[str, Delivery]],
                                  now: float) -> None:
        """Shared pipelined dispatch (columnar AND object windows):
        ``dispatch(drop)`` runs off the event loop with the ids the
        terminal re-check settled (excluded from the window) and returns
        the window token. Crash recovery and backpressure live HERE, once."""
        recorded = False
        deliveries_in = [d for _, d in pairs]
        try:
            async with self._engine_lock:
                # Reap landed windows BEFORE the terminal re-check: a
                # delegated-oracle window's outcome is already complete at
                # dispatch, and collecting it here moves its matched players
                # into _recent where _settle_terminal_locked can see them.
                await self._collect_ready_locked(time.time())
                if self._needs_revive:
                    # A collected window failed on device: the device pool
                    # diverged from the mirror (its step may have matched
                    # players the mirror still holds). Dispatching into the
                    # diverged pool would strand them — drain + revive FIRST
                    # (under sustained traffic the collector's inflight()==0
                    # revive may otherwise never fire).
                    await self._drain_engine(now)
                stale = self._settle_terminal_locked(pairs, now)
                # Only still-live pairs reach the expired sweep: a delivery
                # that was just terminal-replayed is SETTLED — expiring it
                # too would double-respond and double-settle its trace.
                stale |= self._settle_expired_locked(
                    [p for p in pairs if p[0] not in stale], now)
                if stale:
                    pairs = [(p, d) for p, d in pairs if p not in stale]
                    deliveries_in = [d for _, d in pairs]
                    if not pairs:
                        return  # every row replayed/expired + acked
                if self.admission is not None:
                    # shed_policy="oldest": evict the longest-waiting pool
                    # players so this window's (fresher) arrivals fit under
                    # the cap — debt computed from ACTUAL occupancy at this
                    # dispatch point. remove() requires no windows in
                    # flight, so paying costs a pipeline drain; at a
                    # sustained cap that would collapse pipeline_depth to 1
                    # on every window. Pay when the pipeline is already
                    # empty (free) or once the debt exceeds one batch
                    # (bounded occupancy overshoot); otherwise the next
                    # flush recomputes from occupancy and settles then.
                    debt = self.admission.eviction_debt(
                        len(pairs), self.engine.pool_size())
                    if debt:
                        busy = (hasattr(self.engine, "inflight")
                                and self.engine.inflight() > 0)
                        if not busy or debt >= self.app.cfg.batcher.max_batch:
                            await self._drain_engine(now)
                            drop = await self._pay_debt_locked(
                                [(pid, d.tier,
                                  self._first_received(d, now), d)
                                 for pid, d in pairs], debt, now)
                            if drop:
                                stale |= drop
                                pairs = [(p, d) for p, d in pairs
                                         if p not in drop]
                                deliveries_in = [d for _, d in pairs]
                                if not pairs:
                                    return
                # Speculative cut (ISSUE 16): commit-or-discard the gap's
                # precomputed pairing window BEFORE the traffic step
                # donates the pool. Validation is an O(1) mutation-clock
                # compare; on a hit the precomputed matches enter the
                # pipelined stream as a rescan-family window (the shared
                # collector publishes them), and [commit S; step W] is
                # bit-equal to [rescan at t_spec; step W]. On a miss the
                # traffic step below IS the full-step fallback — nothing
                # to recompute, only idle-gap work was discarded.
                self._spec_cut_locked(now)
                # Cross-queue EDF arbitration (ISSUE 11): while the
                # placement controller co-locates queues on this device,
                # the dispatch call waits its (tier, deadline) turn
                # against the other tenants' concurrently-waiting
                # windows.  Acquired INSIDE the engine lock and held only
                # across the host-side dispatch itself, so a migration
                # blackout (engine lock held for the whole rebuild) can
                # never stall a co-located queue through the slot.
                async with self._arbiter_slot(deliveries_in):
                    tok = await asyncio.to_thread(dispatch, stale)
                self._inflight_meta[tok] = (dict(pairs), deliveries_in)
                recorded = True
                await self._collect_ready_locked(time.time())
        except Exception as e:
            log.exception("engine dispatch crashed; reviving engine from mirror")
            self._note_failure(e)
            self._record_engine_crash(now)
            # Once meta is recorded the revive path settles this window
            # exactly once (salvage-ack or stale-meta nack) — passing
            # extra_nack too would double-settle the same delivery tags.
            # The settlement rule now PROVES this shape (guard-flag
            # refinement: `recorded`'s only True-assignment immediately
            # follows the meta hand-off), so the PR 10 inline ignore that
            # sat here is retired.
            await self._revive_pipelined(
                now, extra_nack=None if recorded else deliveries_in)
            return
        # Backpressure: hold THIS queue's batcher until a pipeline slot
        # frees (windows keep arriving from other queues; the collector
        # task keeps collecting even when no flush is running). The
        # hasattr re-check matters: a breaker demotion can swap in the
        # host oracle (no inflight()) while this loop is parked on the
        # sleep — the swap already nacked our window's meta, so there is
        # nothing left to wait for.
        depth = self.pipeline_depth
        while (hasattr(self.engine, "inflight")
               and self.engine.inflight() >= depth):
            await asyncio.sleep(0.001)
            async with self._engine_lock:
                await self._collect_ready_locked(time.time())

    async def _collect_ready_locked(self, now: float) -> None:
        """Collect + handle every landed window. Caller holds _engine_lock
        (held across the awaits — the async settle's journal commit relies
        on that to exclude concurrent appends). Cheap on the event loop:
        results were D2H-copied asynchronously at dispatch, so this is
        numpy slicing + publish/ack bookkeeping, plus the off-loop policy
        fsync when durability is on."""
        if not hasattr(self.engine, "collect_ready"):
            return
        for tok, out in self.engine.collect_ready():
            await self._finish_token(tok, out, now)

    # holds-lock: _engine_lock
    async def _finish_token(self, tok: int, out, now: float) -> None:
        meta = self._inflight_meta.pop(tok, None)
        if meta is None:
            # Not a delivery-backed window (rescan tick / already-settled):
            # pop its window marks — and when it IS a rescan tick, feed
            # them to the per-queue rescan attribution bucket (PR 6
            # carry-over: rescan device time was counted in busy/idle but
            # merged into no trace, a blind spot in the work/wait story).
            wm = getattr(self.engine, "window_marks", None)
            marks = wm.pop(tok, None) if wm is not None else None
            if marks and tok in getattr(self.engine, "rescan_tokens", ()):
                self.app.attribution.observe_rescan(self.queue_cfg.name,
                                                    marks)
            # Rescan ticks flow through the shared collector now that they
            # overlap the pipeline.
            if tok in getattr(self.engine, "rescan_tokens", ()):
                self.engine.rescan_tokens.discard(tok)
                if tok in self.engine.failed_tokens:
                    self.engine.failed_tokens.discard(tok)
                    log.error("rescan window %d failed on device; revive "
                              "scheduled", tok)
                    self._record_engine_crash(now)
                    # The device pool diverged at the failed step — flag the
                    # deferred revive exactly like a failed delivery window,
                    # or traffic keeps dispatching into the diverged pool
                    # until the next rescan tick notices device_error.
                    self._needs_revive = True
                    return
                self._publish_rescan_outcome(out, now)
            return
        by_id, deliveries = meta  # owns: deliveries
        self._merge_window_marks(tok, deliveries)
        if tok in self.engine.failed_tokens:
            self.engine.failed_tokens.discard(tok)
            log.error("window %d failed on device; nack + revive scheduled", tok)
            self._record_engine_crash(now)
            self.app.events.append("window_failed", self.queue_cfg.name,
                                   f"token {tok}, {len(deliveries)} nacked")
            for d in deliveries:
                self._nack(d)
            self._needs_revive = True
            return
        try:
            if hasattr(out, "m_id_a"):
                await self._handle_columnar_out(out, by_id, deliveries, now,
                                                tok)
            else:
                self._handle_object_out(out, deliveries, now)
        except Exception:
            # A publish failure mid-handling must still settle the window's
            # deliveries — leaving them unacked consumes broker prefetch
            # slots until the queue stops consuming entirely. Nack-requeue
            # is the at-least-once answer (redeliveries are deduped against
            # the pool / _recent; a match whose response raised before its
            # _remember ran can, rarely, be re-queued — accepted dup risk).
            log.exception("window %d outcome handling failed; nacking", tok)
            self.app.metrics.counters.inc("outcome_errors")
            for d in deliveries:
                self._nack(d)

    def _trace_id_map(self, deliveries: list[Delivery]) -> dict[str, str]:
        """player id → flight-recorder trace id for this window's TRACED
        deliveries — responses quote the id a client can hand to
        ``/debug/traces?id=``. Only same-window deliveries are attributable:
        a pool member matched windows later settled its trace as "queued"
        back when it was admitted."""
        return {d.trace.player_id: d.trace.trace_id for d in deliveries
                if d.trace is not None and d.trace.player_id}

    def _trace_map(self, deliveries: list[Delivery]) -> "dict[str, Any]":
        """player id → live TraceContext for this window's traced
        deliveries — the publish paths mark "respond" on these at the
        moment the response publish starts (attribution's publish_lag /
        respond split)."""
        return {d.trace.player_id: d.trace for d in deliveries
                if d.trace is not None and d.trace.player_id}

    # settles: *deliveries
    async def _handle_columnar_out(self, out, by_id: dict[str, Delivery],
                                   deliveries: list[Delivery],
                                   now: float, token: int = -1) -> None:
        """Publish one collected window's outcome and ack its deliveries.

        Async settle (ISSUE 15): the rows are BUILT first — terminal
        memory and the window's journal records land with them — then the
        journal's policy commit runs in a worker thread, so the fsync
        overlaps device compute on already-dispatched windows instead of
        stalling the event loop (measured at ~2 ms/window of pure loop
        overhead when it ran inline), and only then do the window's
        responses and acks go out. Write-ahead is preserved: the commit
        covers every record the publishes below make visible. The
        pipelined callers hold _engine_lock across the await, so no new
        records interleave; the non-pipelined fallback settles post-lock
        — a concurrent append between the commit and the publish only
        makes the publish-time commit non-empty, never unsafe (it covers
        strictly MORE records than write-ahead requires)."""
        m = self.app.metrics
        # Egress spans: one per window on each side of the journal
        # commit's await; their sum is the window's `egress` site time.
        egress = {"egress_s": 0.0}
        with stage("egress", egress, token=token), \
                stage("encode", token=token):
            trace_ids = self._trace_id_map(deliveries)
            traces = self._trace_map(deliveries)
            rows = self._build_columnar_rows(
                out, now, trace_ids=trace_ids, traces=traces)
            if self.queue_cfg.send_queued_ack and len(out.q_ids):
                # Queued acks ride the batch path too: one native
                # encode per window instead of an encode_response + publish
                # per newly pooled player — and they share the matches'
                # publish_batch call below.
                import numpy as np

                from matchmaking_tpu.native import codec

                metas = [(pid, by_id[pid]) for pid in out.q_ids.tolist()
                         if pid in by_id]
                if metas:
                    nq = len(metas)
                    bodies_q = None
                    if codec.available():
                        bodies_q = codec.encode_simple_batch(
                            np.full(nq, codec.KIND_QUEUED, np.int32),
                            [pid for pid, _ in metas],
                            np.zeros(nq, np.float64), None,
                            [trace_ids.get(pid, "") for pid, _ in metas],
                            None)
                    for j, (pid, d) in enumerate(metas):
                        body = (bodies_q[j] if bodies_q is not None
                                else None)
                        if body is None:  # codec off or NEEDS_PYTHON row
                            body = encode_response(SearchResponse(  # matchlint: ignore[perf] per-ROW fallback: codec off or NEEDS_PYTHON rows only
                                status="queued", player_id=pid,
                                trace_id=trace_ids.get(pid, "")))
                        if d.trace is not None:
                            d.trace.mark("encode")
                        rows.append((d.properties.reply_to,
                                     d.properties.correlation_id, body,
                                     d.trace))
        jnl = self.journal
        if jnl is not None and jnl.needs_commit:
            await asyncio.to_thread(jnl.commit)
        with stage("egress", egress, token=token):
            if rows:
                with stage("publish", token=token):
                    self._publish_batch(rows)
            for pid, code in out.rejected:
                m.counters.inc("rejected_by_engine")
                d = by_id.get(pid)
                if d is not None:
                    self._respond_error(d, code,
                                        f"engine rejected request: {code}")
            for d in deliveries:
                self._ack(d)
            if any(d.trace is not None for d in deliveries):
                # The window's traces settle together, as columns: one
                # status and "publish" mark each, then one recorder call.
                with stage("settle", token=token):
                    matched_ids = set(out.m_id_a.tolist()) | set(out.m_id_b.tolist())  # matchlint: ignore[perf] O(window matches) OUTCOME columns, traced windows only — not a pool scan
                    rejected_ids = {pid for pid, _ in out.rejected}
                    t_settle = time.time()
                    settled = []
                    for d in deliveries:
                        tr = d.trace
                        if tr is None:
                            continue
                        tr.status = ("matched" if tr.player_id in matched_ids
                                     else "rejected"
                                     if tr.player_id in rejected_ids
                                     else "queued")
                        tr.mark("publish", t_settle)
                        settled.append(tr)
                    self.app.recorder.complete_window(settled)
            m.counters.inc("windows")
            m.counters.inc("requests_batched", len(deliveries))
        self.app.attribution.observe_site(
            self.queue_cfg.name, "egress", egress["egress_s"],
            len(rows))

    # settles: *deliveries
    def _handle_object_out(self, out, deliveries: list[Delivery],
                           now: float) -> None:
        """Publish one collected OBJECT window's outcome (device team
        queues) and ack its deliveries — _publish_outcome covers matches,
        queued acks, rejections, and timeouts."""
        self._publish_outcome(out, now,
                              trace_ids=self._trace_id_map(deliveries),
                              traces=self._trace_map(deliveries))
        for d in deliveries:
            self._ack(d)
        self._settle_outcome_traces(out, deliveries)
        self.app.metrics.counters.inc("windows")
        self.app.metrics.counters.inc("requests_batched", len(deliveries))

    def _spec_invalidate_audited(self, reason: str) -> None:
        """Discard any pending speculation AND stamp the invalidation
        onto the event spine (ISSUE 18) — invalidations were counter-only
        before, invisible to the incident timeline. The event fires only
        when a speculation was actually pending: the drain/checkpoint
        chokepoints call this unconditionally, and an empty invalidation
        is not a causal fact worth a timeline row."""
        eng = self.engine
        if not hasattr(eng, "spec_invalidate"):
            return
        had = getattr(eng, "_spec", None) is not None
        eng.spec_invalidate(reason)
        if had:
            self.app.events.append("spec_invalidate", self.queue_cfg.name,
                                   reason, component="engine")

    # holds-lock: _engine_lock
    async def _drain_engine(self, now: float) -> None:
        """Flush every in-flight window and handle its outcome. Caller holds
        _engine_lock. Restores the ``_open == 0`` invariant rescan/expire/
        remove/checkpoint require."""
        # Speculation dies at every drain chokepoint (ISSUE 16): the
        # callers are about to mutate, checkpoint, migrate, or revive —
        # a speculative pool committed after a checkpoint walk would
        # double-match players the snapshot still holds as waiting.
        self._spec_invalidate_audited("drain")
        if not self._pipelined:
            return
        if self.engine.inflight() > 0:
            outs = await asyncio.to_thread(self.engine.flush)
            for tok, out in outs:
                await self._finish_token(tok, out, now)
        if self._needs_revive:
            self._revive_locked(now)

    def _revive_locked(self, now: float) -> None:
        """Complete a deferred revive (caller holds _engine_lock): clear the
        failure flags, then rebuild from the mirror. The single place the
        revive-completion sequence lives — three paths (drain, dispatch
        crash, collector tick) all come through here."""
        # The mirror rebuild replaces the device pool a pending
        # speculation was computed against — device-loss demotion is one
        # of the invalidation paths the speculation contract names.
        self._spec_invalidate_audited("revive")
        self._needs_revive = False
        self.engine.device_error = None
        self._revive_engine(now)

    # settles: *extra_nack
    async def _revive_pipelined(self, now: float,
                                extra_nack: list[Delivery] | None = None) -> None:
        """Dispatch-path crash with windows possibly in flight: salvage what
        landed, nack the rest, rebuild the engine from the mirror."""
        async with self._engine_lock:
            try:
                outs = await asyncio.to_thread(self.engine.flush)
            except Exception:
                log.exception("flush during revive failed; all in-flight nacked")
                outs = []
            for tok, out in outs:
                await self._finish_token(tok, out, now)
            for d in extra_nack or ():
                self._nack(d)
            # _revive_engine nacks + clears whatever meta the salvage flush
            # could not finish.
            self._revive_locked(now)

    async def _collector_loop(self) -> None:
        """Collect landed windows even when no new flush is running (traffic
        stops → in-flight windows must still complete promptly). Supervised:
        a publish/revive failure on one tick must not kill the task — a dead
        collector means windows dispatched just before a traffic pause are
        NEVER collected (matches unpublished, deliveries unacked).

        Each tick records how late its sleep woke beyond the time asked
        for (site category ``loop_lag``): the time a ready callback waited
        for the event loop."""
        observe = self.app.attribution.observe_site
        queue = self.queue_cfg.name
        while True:
            try:
                if self.engine.inflight() > 0 or self._needs_revive:
                    now = time.time()
                    async with self._engine_lock:
                        await self._collect_ready_locked(now)
                        if self._needs_revive and self.engine.inflight() == 0:
                            self._revive_locked(now)
                    delay = 0.001
                else:
                    delay = 0.01
                t = time.perf_counter()
                await asyncio.sleep(delay)
                observe(queue, "loop_lag",
                        max(0.0, time.perf_counter() - t - delay), 1, WAIT)
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("collector tick failed; retrying")
                self.app.metrics.counters.inc("collector_errors")
                await asyncio.sleep(0.05)

    def _publish_columnar_matches(self, out, now: float,
                                  trace_ids: dict[str, str] | None = None,
                                  traces: "dict[str, Any] | None" = None,
                                  ) -> None:
        """Matched responses for one ColumnarOutcome — build + publish in
        one sync call (rescan outcomes and other non-deferring callers;
        the async window settle uses ``_build_columnar_rows`` directly so
        the journal's policy commit can run off the event loop between
        building and publishing)."""
        rows = self._build_columnar_rows(out, now, trace_ids=trace_ids,
                                         traces=traces)
        if rows:
            self._publish_batch(rows)

    def _build_columnar_rows(self, out, now: float,
                             trace_ids: dict[str, str] | None = None,
                             traces: "dict[str, Any] | None" = None,
                             ) -> "list[tuple[str, str, bytes, Any]]":
        """Row-building half of the columnar match publish (window flush
        AND rescan both come through here). Bodies are built by the native
        batch encoder when available — one C call per window with
        trace_id/waited_ms INCLUDED, byte-identical to
        contract.encode_response (pinned by tests/test_codec_fuzz.py; the
        PR 8 splice helpers are gone) — and the whole window leaves in ONE
        publish_batch call, so publish_lag collapses from O(matches)
        publish callbacks to O(windows). The Python path is the fallback
        and the semantic source of truth; rows the C encoder flags
        NEEDS_PYTHON (non-ASCII ids, non-finite floats) re-encode through
        it individually. Terminal memory (dedup cache + journal records)
        lands HERE, with the rows — callers publish the returned rows
        only after the journal's write-ahead commit. Returns [] when the
        codec-off per-player fallback already published."""
        import numpy as np

        from matchmaking_tpu.native import codec
        from matchmaking_tpu.service.contract import MatchResult

        if self._invariants is not None:
            self._invariants.observe_outcome(out)
        n = out.n_matches
        if n == 0:
            return []
        # Quality ledger (ISSUE 8): one vectorized observe per window —
        # both sides' quality/wait/tier samples, regardless of which
        # encoder builds the bodies below.
        have_wait = len(out.m_wait_a) == n
        if have_wait:
            self.app.quality.observe(
                self.queue_cfg.name,
                np.concatenate([out.m_quality, out.m_quality]),
                np.concatenate([out.m_wait_a, out.m_wait_b]),
                (np.concatenate([out.m_tier_a, out.m_tier_b])
                 if len(out.m_tier_a) == n else None))
        trace_ids = trace_ids or {}
        traces = traces or {}
        if not codec.available():
            # Codec off: the per-request Python path, byte-identical to
            # the pre-batch behavior.
            for j in range(n):
                id_a, id_b = out.m_id_a[j], out.m_id_b[j]
                result = MatchResult(
                    match_id=out.m_match_id[j], players=(id_a, id_b),
                    teams=((id_a,), (id_b,)),
                    quality=float(out.m_quality[j]),
                )
                self._publish_matched(id_a, out.m_reply_a[j],
                                      out.m_corr_a[j],
                                      float(out.m_enq_a[j]), result, now,
                                      trace_id=trace_ids.get(id_a, ""),
                                      trace=traces.get(id_a),
                                      waited_ms=(float(out.m_wait_a[j]) * 1e3
                                                 if have_wait else None),
                                      record_quality=not have_wait)
                self._publish_matched(id_b, out.m_reply_b[j],
                                      out.m_corr_b[j],
                                      float(out.m_enq_b[j]), result, now,
                                      trace_id=trace_ids.get(id_b, ""),
                                      trace=traces.get(id_b),
                                      waited_ms=(float(out.m_wait_b[j]) * 1e3
                                                 if have_wait else None),
                                      record_quality=not have_wait)
            return []
        lat_a = np.where(out.m_enq_a != 0.0, (now - out.m_enq_a) * 1e3, 0.0)
        lat_b = np.where(out.m_enq_b != 0.0, (now - out.m_enq_b) * 1e3, 0.0)
        # waited_ms parity with the Python encoder: the engine-observed
        # wait when the outcome carries one, publish-time latency
        # otherwise (what _publish_matched reports in that case).
        wa_ms = out.m_wait_a * 1e3 if have_wait else lat_a
        wb_ms = out.m_wait_b * 1e3 if have_wait else lat_b
        ids_a, ids_b = out.m_id_a.tolist(), out.m_id_b.tolist()
        mids = out.m_match_id.tolist()
        qual = out.m_quality.astype(np.float64)
        tr_a = ([trace_ids.get(p, "") for p in ids_a] if trace_ids else None)
        tr_b = ([trace_ids.get(p, "") for p in ids_b] if trace_ids else None)
        bodies = codec.encode_matched_batch(
            ids_a, ids_b, mids, lat_a, lat_b, qual, wa_ms, wb_ms, tr_a, tr_b)
        if bodies is None:  # library load raced away: full Python fallback
            bodies = [None] * (2 * n)
        m = self.app.metrics
        m.counters.inc("players_matched", 2 * n)
        waits = np.concatenate([now - enq[enq != 0.0]
                                for enq in (out.m_enq_a, out.m_enq_b)])
        m.latency["match_wait"].record_many(waits)
        # The same samples feed the bucketed histogram, so its
        # p99-from-buckets is checkable against the recorder.
        m.observe_stage_many(self.queue_cfg.name, "e2e", waits)
        reply_a, reply_b = out.m_reply_a.tolist(), out.m_reply_b.tolist()
        corr_a, corr_b = out.m_corr_a.tolist(), out.m_corr_b.tolist()
        wa_l, wb_l = wa_ms.tolist(), wb_ms.tolist()
        lat_al, lat_bl = lat_a.tolist(), lat_b.tolist()
        qual_l = qual.tolist()
        rows: list[tuple[str, str, bytes, Any]] = []
        terminals: list[tuple[str, bytes]] = []
        for j in range(n):
            body_a, body_b = bodies[2 * j], bodies[2 * j + 1]
            if body_a is None or body_b is None:
                # NEEDS_PYTHON row: exact contract via the Python encoder.
                result = MatchResult(
                    match_id=mids[j], players=(ids_a[j], ids_b[j]),
                    teams=((ids_a[j],), (ids_b[j],)), quality=qual_l[j])
                if body_a is None:
                    body_a = encode_response(SearchResponse(
                        status="matched", player_id=ids_a[j], match=result,
                        latency_ms=lat_al[j], waited_ms=wa_l[j],
                        trace_id=tr_a[j] if tr_a else ""))
                if body_b is None:
                    body_b = encode_response(SearchResponse(
                        status="matched", player_id=ids_b[j], match=result,
                        latency_ms=lat_bl[j], waited_ms=wb_l[j],
                        trace_id=tr_b[j] if tr_b else ""))
            tr_ja = traces.get(ids_a[j]) if traces else None
            tr_jb = traces.get(ids_b[j]) if traces else None
            if tr_ja is not None:
                tr_ja.quality = qual_l[j]
                tr_ja.waited_s = wa_l[j] / 1e3
                tr_ja.mark("encode")
            if tr_jb is not None:
                tr_jb.quality = qual_l[j]
                tr_jb.waited_s = wb_l[j] / 1e3
                tr_jb.mark("encode")
            terminals.append((ids_a[j], body_a))
            terminals.append((ids_b[j], body_b))
            rows.append((reply_a[j], corr_a[j], body_a, tr_ja))
            rows.append((reply_b[j], corr_b[j], body_b, tr_jb))
        self._remember_window(terminals, now)
        return rows

    def _publish_matched(self, pid: str, reply_to: str, correlation_id: str,
                         enqueued_at: float, result, now: float,
                         trace_id: str = "", trace=None,
                         waited_ms: float | None = None, tier: int = 0,
                         record_quality: bool = True) -> None:
        """One matched player's response + metrics + dedup memory — the
        slow-path builder (object flush; the columnar flush uses the native
        batch encoder when available and only falls back here).

        ``waited_ms`` is the engine-observed wait-at-match when the caller
        has one (columnar outcomes carry it); the object path falls back to
        publish-time latency — it has no separate dispatch stamp here.
        ``record_quality=False`` when the caller already fed the quality
        ledger vectorized (the columnar publish did, for the whole window)."""
        m = self.app.metrics
        m.counters.inc("players_matched")
        if enqueued_at:
            m.record_latency("match_wait", now - enqueued_at)
            m.observe_stage(self.queue_cfg.name, "e2e", now - enqueued_at)
        waited = (waited_ms if waited_ms is not None
                  else ((now - enqueued_at) * 1e3 if enqueued_at else 0.0))
        body = encode_response(SearchResponse(
            status="matched", player_id=pid, match=result,
            latency_ms=(now - enqueued_at) * 1e3 if enqueued_at else 0.0,
            waited_ms=waited,
            trace_id=trace_id))
        if record_quality:
            self.app.quality.observe(self.queue_cfg.name, result.quality,
                                     waited / 1e3, tier)
        if trace is not None:
            trace.quality = result.quality
            trace.waited_s = waited / 1e3
        self._remember(pid, body, now)
        self._publish_body(reply_to, correlation_id, body, trace=trace)

    def _respond_raw(self, reply_to: str, correlation_id: str,
                     resp: SearchResponse, trace=None) -> None:
        if not reply_to:
            return  # before encode: replyless requests pay nothing
        self._publish_body(reply_to, correlation_id, encode_response(resp),
                           trace=trace)

    # protocol-effect: response_publish requires-fence may_publish
    def _publish_body(self, reply_to: str, correlation_id: str,
                      body: bytes, trace=None) -> None:
        """THE response-publish seam (every respond helper funnels here).
        ``trace`` gets the "respond" mark at the moment the actual broker
        publish starts — splitting publish_lag (outcome-handling queueing
        on the loop, collect→respond) from the publish itself
        (respond→publish) in the attribution classes (PR 6 carry-over)."""
        if not reply_to:
            return
        r = self.replication
        if r is not None and not r.may_publish():
            # Epoch fencing (ISSUE 17): a superseded ex-primary must not
            # make ANY response visible — the standby's successor owns
            # these players now, and a fenced publish is exactly the
            # split-brain double match the lease/epoch machinery exists
            # to kill. Refused and counted, never silent.
            self.app.metrics.counters.inc("fenced_publish_refused")
            return
        # Write-ahead: a terminal response must never be visible before
        # its journal record is durable (fsync per policy) — the invariant
        # that makes recovery yield zero double matches.
        self._journal_commit()
        if trace is not None:
            trace.mark("respond")
        self.app.broker.publish(reply_to, body,
                                Properties(correlation_id=correlation_id))

    # protocol-effect: response_publish requires-fence may_publish
    def _publish_batch(self, rows: "list[tuple[str, str, bytes, Any]]") -> None:
        """Window-granular twin of ``_publish_body`` (ISSUE 9): one broker
        ``publish_batch`` call for a whole window of responses (rows:
        reply_to, correlation_id, body, trace). Each traced row gets its
        "respond" mark as the batch publish starts — publish_lag keeps its
        queueing semantics (…→respond WAIT) and the publish itself is the
        respond→publish WORK gap, now amortized over the window."""
        r = self.replication
        if r is not None and not r.may_publish():
            # Epoch-fencing twin of the _publish_body check: the whole
            # window of responses is refused at once.
            self.app.metrics.counters.inc(
                "fenced_publish_refused",
                sum(1 for reply_to, _c, _b, _t in rows if reply_to))
            return
        # Write-ahead twin of _publish_body: ONE commit (and fsync, per
        # policy) covers the whole window's terminal records before any
        # of its responses become visible.
        self._journal_commit()
        items = []
        for reply_to, corr, body, trace in rows:
            if not reply_to:
                continue  # replyless requests pay nothing
            if trace is not None:
                trace.mark("respond")
            items.append((reply_to, body, Properties(correlation_id=corr)))
        if not items:
            return
        if self._batch_publish:
            self.app.broker.publish_batch(items)
        else:
            for reply_to, body, props in items:
                self.app.broker.publish(reply_to, body, props)

    # holds-lock: _engine_lock
    def _revive_engine(self, now: float) -> None:
        """Elastic recovery: rebuild the engine and resubmit the pool from
        the authoritative host mirror (SURVEY.md §5).

        Any window meta still tracked is nacked HERE, whichever path led to
        the revive (flush, sweeper drain, rescan drain, collector): the old
        engine's windows are gone, and the fresh engine reissues tokens from
        0 — stale entries would strand their deliveries unacked AND collide
        with the new engine's token numbering.

        Device-loss failover (ISSUE 15): when the crash named a dead mesh
        participant (``_note_failure`` set ``_lost_device``), an
        elastic-shardable D>=2 queue DEMOTES to its surviving devices
        before the rebuild — a plain revive would bind the same dead chip
        and revive-loop at traffic rate. The whole lock-held rebuild is
        the measured blackout, audited in ``failover_log``
        (/debug/placement)."""
        t0 = time.perf_counter()
        lost, self._lost_device = self._lost_device, None
        demoted: "tuple[tuple[int, ...], tuple[int, ...], int] | None" = None
        if lost is not None:
            binding = (self.placement if self.placement is not None
                       else tuple(range(self.app.cfg.engine.mesh_pool_axis)))
            if self.elastic_shardable() and len(binding) > 1:
                idx = lost if 0 <= lost < len(binding) else len(binding) - 1
                survivors = tuple(d for i, d in enumerate(binding)
                                  if i != idx)
                # The binding sticks for EVERY later rebuild (probe,
                # migration, further revives) — _engine_cfg follows it, so
                # the mesh axis shrinks to the survivor count (D -> D-1).
                self.placement = survivors
                demoted = (binding, survivors, idx)
            else:
                log.error(
                    "queue %r: device %d lost but no demotion possible "
                    "(D=1 or non-elastic) — plain revive; a persistent "
                    "loss trips the breaker into the host oracle",
                    self.queue_cfg.name, lost)
        for tok, (_by_id, deliveries) in list(self._inflight_meta.items()):
            for d in deliveries:
                self._nack(d)
            del self._inflight_meta[tok]
        try:
            snapshot = self.engine.waiting()
        except Exception:
            snapshot = []
            log.exception("mirror unreadable; pool lost (broker will redeliver)")
        # Quality accounting survives the rebuild (ISSUE 9 satellite):
        # /debug/quality counters are monotone across a crash revive or
        # breaker demotion — the fresh engine starts from the dead one's
        # accumulated histograms instead of zero.
        try:
            q_snapshot = self.engine.quality_checkpoint()
        except Exception:
            q_snapshot = None
            log.exception("quality checkpoint unreadable; counters reset")
        try:
            self.engine.close()
        except Exception:
            log.exception("old engine close failed")
        self._bind_engine(self._make_engine())
        self.engine.restore(snapshot, now)
        self.engine.quality_restore(q_snapshot)
        self.app.events.append("engine_revive", self.queue_cfg.name,
                               f"{len(snapshot)} players restored from mirror")
        if demoted is not None:
            was, survivors, idx = demoted
            blackout_ms = round((time.perf_counter() - t0) * 1e3, 3)
            entry = {
                "queue": self.queue_cfg.name,
                "at": now,
                "from_devices": list(was),
                "to_devices": list(survivors),
                "lost_device": idx,
                "blackout_ms": blackout_ms,
                "restored": len(snapshot),
            }
            self.failover_log.append(entry)
            del self.failover_log[:-64]  # bounded audit ring
            self.app.metrics.counters.inc("device_failovers")
            self.app.metrics.set_gauge(
                f"failover_blackout_ms[{self.queue_cfg.name}]", blackout_ms)
            self.app.events.append(
                "device_failover", self.queue_cfg.name,
                f"D={len(was)} -> D={len(survivors)} after losing device "
                f"{idx}: {len(snapshot)} players, {blackout_ms:.1f} ms "
                f"blackout")
            log.error(
                "queue %r: DEVICE-LOSS FAILOVER — demoted %s -> %s "
                "(lost logical device %d), %d players restored, %.1f ms "
                "blackout", self.queue_cfg.name, list(was), list(survivors),
                idx, len(snapshot), blackout_ms)

    # ---- egress -----------------------------------------------------------

    def _publish_outcome(self, outcome: SearchOutcome, now: float,
                         trace_ids: dict[str, str] | None = None,
                         traces: "dict[str, Any] | None" = None) -> None:
        m = self.app.metrics
        tids = trace_ids or {}
        trs = traces or {}
        if self._invariants is not None:
            self._invariants.observe_outcome(outcome)
        for match in outcome.matches:
            result = match.result()
            for req in match.requests():
                self._publish_matched(req.id, req.reply_to, req.correlation_id,
                                      req.enqueued_at, result, now,
                                      trace_id=tids.get(req.id, ""),
                                      trace=trs.get(req.id),
                                      tier=req.tier)
        if self.queue_cfg.send_queued_ack:
            for req in outcome.queued:
                self._respond(req, SearchResponse(
                    status="queued", player_id=req.id,
                    trace_id=tids.get(req.id, "")),
                    trace=trs.get(req.id))
        for req, code in outcome.rejected:
            m.counters.inc("rejected_by_engine")
            self._respond(req, SearchResponse(
                status="error", player_id=req.id, error_code=code,
                error_reason=f"engine rejected request: {code}",
                trace_id=tids.get(req.id, ""),
            ), trace=trs.get(req.id))
        for req in outcome.timed_out:
            body = encode_response(SearchResponse(
                status="timeout", player_id=req.id,
                trace_id=tids.get(req.id, "")))
            self._remember(req.id, body, now)
            self._publish_body(req.reply_to, req.correlation_id, body,
                               trace=trs.get(req.id))

    def _remember(self, player_id: str, body: bytes, now: float) -> None:
        """THE terminal-memory seam: every terminal state (matched /
        timeout / shed-evicted / pool expiry) comes through here or
        through ``_remember_window``, so the journal's TERMINAL record
        rides the same call — exactly what the ``_recent`` replay cache
        holds, which is what recovery rebuilds."""
        expiry = now + self.queue_cfg.dedup_ttl_s
        self._recent.set(player_id, (body, expiry))
        if self.journal is not None:
            self.journal.append_terminal(player_id, body, expiry)

    def _remember_window(self, pairs: "list[tuple[str, bytes]]",
                         now: float) -> None:
        """Windowed twin of ``_remember`` (the columnar settle hot path):
        the whole window's terminals land in the dedup cache AND as ONE
        journal record — per-player appends cost json+crc+lock each, and
        on the event loop that was a measurable slice of the journal's
        steady-state overhead."""
        if not pairs:
            return
        expiry = now + self.queue_cfg.dedup_ttl_s
        for pid, body in pairs:
            self._recent.set(pid, (body, expiry))
        if self.journal is not None:
            self.journal.append_terminals(
                [(pid, body, expiry) for pid, body in pairs])

    def dedup_cache_size(self) -> int:
        """Public dedup-cache occupancy for observability (/metrics reads
        this instead of reaching into the private ``_recent`` dict, so a
        cache rename/restructure breaks loudly here instead of silently
        dropping the metric)."""
        return len(self._recent)

    def _prune_recent(self, now: float) -> None:
        # Time-throttled: a full-dict rebuild on every window would be O(n)
        # hot-path overhead under sustained load; expiry only moves at TTL
        # granularity anyway.
        if len(self._recent) > 4096 and now >= self._next_prune:
            self._recent.prune(now)
            self._next_prune = now + self.queue_cfg.dedup_ttl_s / 2.0

    def _respond(self, req: SearchRequest, resp: SearchResponse,
                 trace=None) -> None:
        self._respond_raw(req.reply_to, req.correlation_id, resp,
                          trace=trace)

    def _respond_error(self, delivery: Delivery, code: str, reason: str) -> None:
        # Routed through the _publish_body funnel so error responses obey
        # the same epoch fence and write-ahead commit as every other
        # publish — a fenced ex-primary must not answer AT ALL, not even
        # with errors (the protocol rule's undeclared-effect sweep pins
        # this: no direct broker.publish outside the annotated funnels).
        if not delivery.properties.reply_to:
            return
        tr = delivery.trace
        self._publish_body(
            delivery.properties.reply_to,
            delivery.properties.correlation_id,
            encode_response(SearchResponse(
                status="error", player_id="", error_code=code,
                error_reason=reason,
                trace_id=tr.trace_id if tr is not None else "",
            )))

    # ---- periodic rescan (threshold widening between pool members) --------

    async def _rescan_loop(self) -> None:
        interval = self.queue_cfg.rescan_interval_s
        window = (self.queue_cfg.rescan_window
                  or self.app.cfg.batcher.max_batch)
        #: Token of the previous tick's rescan, if it never collected
        #: within the deadline. A stalled device (or a tick longer than
        #: rescan_interval_s) must not stack another full-pool rescan per
        #: interval, unbounded and unlogged (ADVICE round-5 #2).
        outstanding: int | None = None
        while True:
            await asyncio.sleep(interval)
            now = time.time()
            tok: int | None = None
            if (outstanding is not None
                    and outstanding in getattr(self.engine,
                                               "rescan_tokens", ())):
                log.warning(
                    "queue %r: previous rescan (token %d) still "
                    "outstanding — skipping this tick",
                    self.queue_cfg.name, outstanding)
                self.app.metrics.counters.inc("rescan_skipped_outstanding")
                continue
            outstanding = None
            try:
                async with self._engine_lock:
                    if hasattr(self.engine, "rescan_async"):
                        # Overlap-capable engines dispatch the rescan INTO
                        # the pipelined stream (no-admission step — see
                        # kernels._rescan_step); the round-4 full pipeline
                        # drain per tick is gone. Engines without the
                        # variant keep the drained single-chunk contract.
                        if not getattr(self.engine, "rescan_overlap", False):
                            await self._drain_engine(now)
                        # A rescan tick is a cut too (ISSUE 16): commit a
                        # still-valid speculation instead of letting the
                        # rescan's donation discard it as wasted — the
                        # rescan below then widens over the POST-commit
                        # pool, exactly as if the spec had been a tick.
                        self._spec_cut_locked(now)
                        tok = await asyncio.to_thread(
                            self.engine.rescan_async, window, now)
                    elif hasattr(self.engine, "rescan"):
                        out = await asyncio.to_thread(
                            self.engine.rescan, window, now)
                        self._publish_rescan_outcome(out, now)
                        continue
            except Exception as e:
                log.exception("rescan failed; reviving engine from mirror")
                self._note_failure(e)
                self._record_engine_crash(now)
                async with self._engine_lock:
                    # _revive_locked, not a bare _revive_engine: the failure
                    # may have set _needs_revive (failed delivery window
                    # collected on this path) — clearing the flags here
                    # prevents a second spurious revive of the fresh engine.
                    self._revive_locked(now)
                continue
            if tok is None:
                continue
            # Wait for the tick's results WITHOUT draining: poll the shared
            # collector (which routes rescan tokens to
            # _publish_rescan_outcome via _finish_token). In-order FIFO
            # finalization means the token lands once the windows dispatched
            # before it have landed — traffic keeps flowing the whole time.
            deadline = time.monotonic() + 30.0
            done = False
            try:
                while time.monotonic() < deadline:
                    async with self._engine_lock:
                        await self._collect_ready_locked(time.time())
                        done = tok not in self.engine.rescan_tokens
                        if self.engine.device_error is not None:
                            err = self.engine.device_error
                            self.engine.device_error = None
                            raise err
                    if done:
                        break
                    await asyncio.sleep(0.01)
                if not done:
                    # Deadline exceeded: the token stays routable (the
                    # shared collector publishes it whenever it lands);
                    # remember it so the next tick skips instead of
                    # silently stacking another full-pool rescan.
                    outstanding = tok
                    log.warning(
                        "queue %r: rescan (token %d) exceeded its 30 s "
                        "collection deadline; next tick will skip while "
                        "it is outstanding", self.queue_cfg.name, tok)
                    self.app.metrics.counters.inc("rescan_deadline_overruns")
                    self.app.events.append("rescan_overrun",
                                           self.queue_cfg.name,
                                           f"token {tok}")
            except Exception as e:
                log.exception("rescan failed; reviving engine from mirror")
                self._note_failure(e)
                self._record_engine_crash(now)
                async with self._engine_lock:
                    self._revive_locked(now)

    # ---- speculative formation (ISSUE 16) ---------------------------------

    # holds-lock: _engine_lock
    def _spec_cut_locked(self, now: float) -> bool:
        """Commit-or-discard the pending speculation at a cut point.
        Caller holds _engine_lock. Validation is O(1) (mutation-clock
        compare + staleness bound); a hit submits the precomputed window
        into the pipelined stream as a rescan-family token — the shared
        collector publishes its matches — and returns True. A miss (or no
        pending speculation) returns False and the caller's own full step
        is the bit-exact fallback. spec_validate → spec_commit runs with
        no pool mutation in between, the exact ordering the sanitizer and
        the matchlint rule pin."""
        eng = self.engine
        if not hasattr(eng, "spec_validate"):
            return False  # breaker-demoted host oracle: no speculation
        try:
            tok = eng.spec_validate(
                now, max_age_s=self.app.cfg.engine.spec_staleness_ms / 1e3)
            if tok is None:
                return False
            eng.spec_commit(tok, now)
            self.app.metrics.counters.inc("spec_commits")
            return True
        except Exception:
            # A commit failure must not take the cut down with it: the
            # traffic/rescan step that follows is the full-step fallback.
            log.exception("speculative commit failed; falling back to a "
                          "full step")
            self.app.metrics.counters.inc("spec_errors")
            if hasattr(eng, "spec_invalidate"):
                had = getattr(eng, "_spec", None) is not None
                eng.spec_invalidate("cut-commit failure")
                if had:
                    self.app.events.append(
                        "spec_invalidate", self.queue_cfg.name,
                        "cut-commit failure", component="engine")
            return False

    async def _spec_loop(self) -> None:
        """Speculative-formation driver (ISSUE 16): on its cadence
        (EngineConfig.spec_interval_ms), when the pipeline is idle — the
        window gap r04 attribution shows the device spending mostly idle —
        commit the previous tick's speculation (the tick is a cut: if the
        mutation clock hasn't moved, the precomputed pairings are the
        pairings a rescan would form NOW) and precompute the next one.
        Traffic arriving mid-gap commits the pending speculation at its
        own cut (_dispatch_pipelined) before dispatching, so gap work is
        wasted only when a pool mutation (admit/expiry/dedup/removal/
        recovery) actually invalidated it. Supervised like the collector:
        one bad tick discards the speculation, never the task."""
        interval = max(0.001, self.app.cfg.engine.spec_interval_ms / 1e3)
        while True:
            await asyncio.sleep(interval)
            try:
                eng = self.engine
                if not hasattr(eng, "speculate"):
                    continue  # breaker demotion swapped in the host oracle
                if self._needs_revive or self._flushing > 0:
                    continue  # not a gap: revive pending / flush running
                if hasattr(eng, "inflight") and eng.inflight() > 0:
                    continue  # pipeline busy: the gap has not opened
                now = time.time()
                async with self._engine_lock:
                    eng = self.engine  # re-read: swaps happen under lock
                    if not hasattr(eng, "speculate"):
                        continue
                    self._spec_cut_locked(now)
                    # Off-thread: the speculative step is real device math
                    # (the non-donated rescan twin over the packed pool).
                    await asyncio.to_thread(eng.speculate, now)
                    # Collect promptly: a commit above submitted a window;
                    # under zero traffic the collector task is the only
                    # other reaper and it polls at 10 ms.
                    await self._collect_ready_locked(time.time())
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("speculation tick failed; discarding")
                self.app.metrics.counters.inc("spec_errors")
                try:
                    async with self._engine_lock:
                        self._spec_invalidate_audited("tick failure")
                except Exception:
                    log.exception("speculation discard failed")
                await asyncio.sleep(0.05)

    def _publish_rescan_outcome(self, out, now: float) -> None:
        """Publish one rescan outcome's matches. q_ids / queued are
        unmatched RESCANS, not newly queued players — never re-acked."""
        matched = 0
        if hasattr(out, "m_id_a"):  # ColumnarOutcome
            matched += out.n_matches
            self._publish_columnar_matches(out, now)
        else:  # object outcome (CPU oracle / team queues)
            matched += len(out.matches)
            if self._invariants is not None:
                self._invariants.observe_outcome(out)
            for match in out.matches:
                result = match.result()
                for req in match.requests():
                    self._publish_matched(
                        req.id, req.reply_to, req.correlation_id,
                        req.enqueued_at, result, now, tier=req.tier)
        if matched:
            self.app.metrics.counters.inc("rescan_matches", matched)

    # ---- health timer: breaker probes + idle re-promotion heartbeat -------

    async def _health_loop(self) -> None:
        """Dedicated low-frequency health timer (EngineConfig.
        health_interval_s). Two jobs, both of which nothing else covers
        under zero traffic:

        - ``engine.heartbeat``: idle re-promotion for wildcard-delegated
          team/role queues (ADVICE round-5 #3 — previously rode rescan
          ticks, which default to OFF for team/role queues, so an idle
          delegated queue stayed on the O(n) host oracle until the next
          arrival);
        - half-open circuit-breaker probes with exponential backoff
          (_probe_device).

        Supervised like the collector: one bad tick must not kill the
        timer — a dead health loop would strand a demoted queue degraded
        forever."""
        interval = self.app.cfg.engine.health_interval_s
        while True:
            await asyncio.sleep(interval)
            now = time.time()
            try:
                changed = False
                # Skip the lock + thread hop unless the tick can actually do
                # something: heartbeat() acts on a delegated queue (idle
                # re-promotion — real device work that must run off the
                # event loop) or an engine declaring idle housekeeping
                # (ISSUE 14: the bucketed index re-tighten).
                if (getattr(self.engine, "_team_delegate", None) is not None
                        or getattr(self.engine, "heartbeat_housekeeping",
                                   False)):
                    async with self._engine_lock:
                        changed = await asyncio.to_thread(
                            self.engine.heartbeat, now)
                if changed:
                    self.app.metrics.counters.inc("health_repromotions")
                if self.breaker is not None and self.breaker.probe_due(now):
                    await self._probe_device(now)
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("health tick failed; retrying")
                self.app.metrics.counters.inc("health_tick_errors")

    def _probe_build(self) -> Engine:
        """Build a fresh device engine and run its half-open probe (one
        no-op device step, blocked until ready). Runs OFF the event loop —
        probe failure must cost the degraded queue nothing but this thread's
        time. Returns the proven engine; closes it and re-raises on probe
        failure."""
        engine = make_engine(self._engine_cfg(), self.queue_cfg,
                             devices=self.placement)
        if self._chaos_hook is not None and hasattr(engine, "chaos_hook"):
            engine.chaos_hook = self._chaos_hook
        try:
            engine.probe()
        except BaseException:
            try:
                engine.close()
            except Exception:
                log.exception("probe engine close failed")
            raise
        return engine

    async def _probe_device(self, now: float) -> None:
        """Half-open probe: try the device path with a FRESH engine while
        the degraded host engine keeps serving traffic. Success swaps the
        pool back onto the device engine (breaker closes); failure doubles
        the probe backoff and stays degraded."""
        assert self.breaker is not None
        self.breaker.begin_probe(now)
        self.app.metrics.counters.inc("breaker_probes")
        self.app.events.append("breaker_probe", self.queue_cfg.name)
        self._publish_breaker_gauges()
        try:
            candidate = await asyncio.to_thread(self._probe_build)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self.breaker.probe_failed(time.time())
            self.app.metrics.counters.inc("breaker_probe_failures")
            self.app.events.append("probe_failed", self.queue_cfg.name,
                                   str(e))
            self._publish_breaker_gauges()
            log.warning(
                "queue %r: half-open device probe failed (%s); next probe "
                "in %.2fs", self.queue_cfg.name, e,
                self.breaker.probe_delay_s)
            return
        async with self._engine_lock:
            swap_now = time.time()
            # Degraded engines are synchronous (no pipeline), so the drain
            # is a no-op today — kept for when a future degraded tier isn't.
            await self._drain_engine(swap_now)
            old = self.engine

            def swap() -> int:
                snapshot = old.waiting()
                # Restore BEFORE closing the degraded engine: a transfer
                # failure (the same flaky device the breaker exists for)
                # must leave the old engine intact and serving.
                candidate.restore(snapshot, swap_now)
                # Degraded-period matches ride along: /debug/quality stays
                # monotone across the re-promotion (ISSUE 9 satellite).
                candidate.quality_restore(old.quality_checkpoint())
                try:
                    old.close()
                except Exception:
                    log.exception("degraded engine close failed")
                return len(snapshot)

            try:
                transferred = await asyncio.to_thread(swap)
            except Exception as e:
                # The no-op probe passed but the real pool transfer did
                # not. Count it as a probe failure (back off, stay OPEN) —
                # otherwise the breaker is stranded HALF_OPEN forever and
                # probe_due() never fires again.
                self.breaker.probe_failed(time.time())
                self.app.metrics.counters.inc("breaker_probe_failures")
                self.app.events.append("probe_failed", self.queue_cfg.name,
                                       f"pool transfer: {e}")
                self._publish_breaker_gauges()
                try:
                    candidate.close()
                except Exception:
                    log.exception("probe engine close failed")
                log.warning(
                    "queue %r: pool transfer to the probed device engine "
                    "failed (%s); staying degraded, next probe in %.2fs",
                    self.queue_cfg.name, e, self.breaker.probe_delay_s)
                return
            self._bind_engine(candidate)
            self.breaker.probe_succeeded(time.time())
        self.app.metrics.counters.inc("breaker_closes")
        self.app.events.append("breaker_closed", self.queue_cfg.name,
                               f"{transferred} waiting players transferred")
        self._publish_breaker_gauges()
        log.info(
            "queue %r: half-open probe succeeded — breaker CLOSED, device "
            "engine restored (%d waiting players transferred)",
            self.queue_cfg.name, transferred)

    # ---- elastic placement: live queue→device migration (ISSUE 11) --------

    async def migrate(self, devices: "tuple[int, ...]") -> "dict[str, Any]":
        """Live-migrate this queue's engine onto ``devices`` (shard degree
        = len) using the drain/checkpoint/restore primitive: under the
        engine lock, collect every in-flight window (their outcomes
        publish + ack through the normal settle paths), snapshot the
        waiting pool and the quality accumulators, rebuild the engine
        bound to the target devices, and restore.  Nothing else is
        settled here: admission credits and EDF deadline caches live in
        THIS runtime and survive by construction; deliveries parked in
        the batcher or on the engine lock simply dispatch to the
        successor engine once the lock frees.

        The lock-held span is the migration BLACKOUT — measured and
        returned (the controller audits it in /debug/placement).  On any
        build/restore failure the old engine keeps serving and the old
        binding is restored (same order of operations as the breaker's
        probe swap)."""
        from matchmaking_tpu.control.executor import rebuild_engine

        if self.breaker is not None and self.breaker.state != CLOSED:
            raise RuntimeError(
                f"queue {self.queue_cfg.name!r} is degraded (breaker "
                f"{self.breaker.state}) — the host oracle serves it, so a "
                f"device re-binding would migrate nothing")
        devices = tuple(int(d) for d in devices)
        async with self._engine_lock:
            t0 = time.perf_counter()
            now = time.time()
            await self._drain_engine(now)
            old = self.engine
            prev = self.placement
            self.placement = devices

            def swap():
                return rebuild_engine(
                    old,
                    lambda: self._make_engine(),
                    now=now)

            # shield + ensure_future: a cancelled migrate (drain/stop
            # tearing the controller tick down) cannot interrupt the swap
            # THREAD anyway — let it finish in the background and dispose
            # whatever engine it built, instead of leaking a bound-to-
            # nothing device pool.
            swap_task = asyncio.ensure_future(asyncio.to_thread(swap))
            try:
                candidate, stats = await _shielded_to_thread(swap_task)
            except BaseException:
                # Build/restore failed or the await was cancelled: the
                # old engine never stopped serving — revert the binding
                # so later rebuilds (revive, probe) stay where the pool
                # actually is, and close the candidate when/if the swap
                # thread completes.
                self.placement = prev

                def _dispose(t: "asyncio.Task") -> None:
                    if t.cancelled() or t.exception() is not None:
                        return
                    eng, _stats = t.result()
                    try:
                        eng.close()
                    except Exception:
                        log.exception("orphaned candidate engine close "
                                      "failed")

                swap_task.add_done_callback(_dispose)
                raise
            self._bind_engine(candidate)
            try:
                old.close()
            except Exception:
                log.exception("migrated-away engine close failed")
            blackout_s = time.perf_counter() - t0
        self.app.metrics.counters.inc("queue_migrations")
        self.app.events.append(
            "queue_migrated", self.queue_cfg.name,
            f"{list(prev) if prev else 'default'} -> {list(devices)}: "
            f"{stats['transferred']} players, "
            f"{blackout_s * 1e3:.1f} ms blackout")
        return {"blackout_s": blackout_s,
                "transferred": stats["transferred"],
                "devices": devices}

    def _arbiter_slot(self, deliveries: "list[Delivery]"):
        """The cross-queue (tier, deadline) dispatch gate (ISSUE 11): a
        no-op context unless the placement controller is live, the
        arbiter is enabled, and this queue currently SHARES its primary
        device with another queue — the unshared layout pays one attr
        read and one set probe per window."""
        from matchmaking_tpu.control.arbiter import NOOP_SLOT, window_key

        ctrl = self.app.placement
        if ctrl is None or not self.app.cfg.placement.arbiter:
            return NOOP_SLOT
        dev = self.placement[0] if self.placement else None
        if not ctrl.arbiter.engaged(dev):
            return NOOP_SLOT
        return ctrl.arbiter.slot(dev, window_key(deliveries))

    # ---- timeout + deadline sweeper ---------------------------------------

    async def _sweep_loop(self) -> None:
        """One loop, two evictions: the coarse ``request_timeout_s``
        timeout sweep (engine.expire) and the pool-resident per-slot
        ``x-deadline`` expiry (engine.expire_deadlines, gated on
        OverloadConfig.deadline_sweep_ms) — a waiting player whose client
        stamped a deadline is cancelled EXACTLY at it, not at the next
        multiple of the queue timeout. Both run under the engine lock on a
        drained pipeline; deadline expiry costs zero device matching work
        (a host-mirror column scan + the eviction scatter)."""
        timeout = self.queue_cfg.request_timeout_s
        sweep_ms = self.app.cfg.overload.deadline_sweep_ms
        deadline_sweep = self.admission is not None and sweep_ms > 0
        timeout_interval = (max(0.05, timeout / 4.0)
                            if timeout is not None else None)
        dl_interval = max(0.01, sweep_ms / 1e3) if deadline_sweep else None
        interval = min(x for x in (timeout_interval, dl_interval) if x)
        # Independent cadences (monotonic — wall clocks step): the
        # deadline sweep may tick at 10 ms without dragging the O(pool)
        # timeout expire along at the same rate.
        next_timeout = (time.monotonic() + timeout_interval
                        if timeout_interval else None)
        while True:
            await asyncio.sleep(interval)
            now = time.time()
            run_timeout = (next_timeout is not None
                           and time.monotonic() >= next_timeout)
            if run_timeout:
                next_timeout = time.monotonic() + timeout_interval
            # O(1) gate: a tick with no deadline-carrying waiter (and no
            # timeout sweep due) must not take the engine lock or drain
            # the pipeline — deadline-less traffic pays nothing for the
            # sweep being configured. deadline_count() is a lock-free
            # point read; -1 (unknown engine) always sweeps.
            run_dl = deadline_sweep and self.engine.deadline_count() != 0
            if not run_timeout and not run_dl:
                continue
            # The lock keeps evictions from racing an in-flight window's
            # engine.search (engines have no internal locking). expire() is
            # O(expired) on the columnar mirror (TpuEngine) and runs off
            # the event loop; only the responses happen here. Device work
            # can fail transiently — the sweeper must survive (a dead
            # sweeper means no request in this queue ever times out again),
            # so failures revive the engine like the flush/rescan paths.
            try:
                async with self._engine_lock:
                    # expire()/expire_deadlines() require _open == 0 (same
                    # re-admission hazard as rescan) — collect in-flight
                    # windows first.
                    await self._drain_engine(now)
                    expired = (await asyncio.to_thread(
                        self.engine.expire, now, timeout)
                        if run_timeout else [])
                    dl_expired = (await asyncio.to_thread(
                        self.engine.expire_deadlines, now)
                        if run_dl else [])
            except Exception:
                log.exception("timeout sweep failed; reviving engine from mirror")
                self._record_engine_crash(now)
                # Sync crash path — see _flush_inner.
                # matchlint: ignore[guarded-by] revive sequence is await-free; the lock guards cross-await atomicity only
                self._revive_engine(now)
                continue
            for removed in expired:
                self.app.metrics.counters.inc("timeouts")
                body = encode_response(SearchResponse(
                    status="timeout", player_id=removed.id,
                    latency_ms=(now - removed.enqueued_at) * 1e3,
                ))
                self._remember(removed.id, body, now)
                self._publish_body(removed.reply_to, removed.correlation_id,
                                   body)
            for removed in dl_expired:
                self._publish_pool_expiry(removed, now)

    def _publish_pool_expiry(self, removed: SearchRequest,
                             now: float) -> None:
        """Settle one pool waiter the deadline sweep cancelled: explicit
        ``timeout`` response (remembered — a redelivered copy replays it
        instead of re-entering), expired/tier accounting, and a fresh
        settled trace whose marks are enqueue → expired → publish with NO
        dispatch mark — the auditable proof the expiry itself spent no
        device matching work. (The player's ORIGINAL trace settled as
        "queued" when its admit window collected; expiry is a new
        lifecycle event, so it gets its own trace.)"""
        tiered = self.admission is not None and self.admission.tiers > 1
        if self.admission is not None:
            self.admission.record_expired(
                f"pool waiter {removed.id} deadline", tier=removed.tier)
        tr = None
        if self.app.trace_enabled:
            tr = TraceContext(self.queue_cfg.name, removed.correlation_id,
                              t=removed.enqueued_at or now)
            tr.player_id = removed.id
            tr.tier = removed.tier
            tr.mark("expired", now)
        body = encode_response(SearchResponse(
            status="timeout", player_id=removed.id,
            latency_ms=((now - removed.enqueued_at) * 1e3
                        if removed.enqueued_at else 0.0),
            trace_id=tr.trace_id if tr is not None else "",
            tier=removed.tier if tiered else None))
        self._remember(removed.id, body, now)
        self._publish_body(removed.reply_to, removed.correlation_id, body,
                           trace=tr)
        if tr is not None:
            tr.status = "expired"
            tr.mark("publish")
            self.app.recorder.complete(tr)

    async def close(self) -> None:
        if self._sweeper is not None:
            self._sweeper.cancel()
        if self._rescanner is not None:
            self._rescanner.cancel()
        if self._health is not None:
            self._health.cancel()
        if self._spec_task is not None:
            # Before the batcher drain: a speculation tick racing the
            # final flush would only be discarded at its cut anyway.
            self._spec_task.cancel()
        if self._durability is not None:
            self._durability.cancel()
        if self._repl_task is not None:
            self._repl_task.cancel()
        # Drain the batcher BEFORE cancelling the consumer so the final
        # windows can still ack their deliveries; then collect any windows
        # the final flush left in flight.
        await self.batcher.close()
        if self._collector is not None:
            self._collector.cancel()
        async with self._engine_lock:
            await self._drain_engine(time.time())
        self.app.broker.basic_cancel(self.consumer_tag)
        if self.journal is not None:
            # Clean-shutdown marker, durable: the next boot sees it and
            # skips crash recovery (its ABSENCE is the crash detector).
            self.journal.mark_clean()
            self.journal.close()
        if self.replication is not None:
            # mark_clean just streamed the CLEAN record through the tap;
            # the final pump sweeps acks and releases the lease so a
            # standby may promote without waiting out the expiry (and
            # knows from CLEAN that no failover is NEEDED).
            self.replication.shutdown(time.monotonic())

    def abandon(self) -> None:
        """Crash-fidelity teardown (durability tests): cancel the timers
        and drop the journal WITHOUT a clean
        marker, drain, or final commit — the on-disk journal state is
        exactly what a ``kill -9`` would leave. The engine is still
        closed (device buffers are process resources a soak would
        otherwise leak across cycles); a real crash frees them with the
        process."""
        for task in (self._sweeper, self._rescanner, self._health,
                     self._spec_task, self._durability, self._repl_task,
                     self._collector, self.batcher._task):
            if task is not None:
                task.cancel()
        if self.journal is not None:
            self.journal.abandon()
        try:
            self.engine.close()  # matchlint: ignore[guarded-by] simulated kill -9: every consumer/timer task was just cancelled, nothing else drives this engine again
        except Exception:
            log.exception("engine close during simulated crash failed")


class MatchmakingApp:
    """Boot/own the whole service (SURVEY.md §3 Entry 1)."""

    def __init__(self, cfg: Config | None = None,
                 broker: InProcBroker | None = None,
                 replication_hub=None):
        self.cfg = cfg or Config()
        #: Replication fabric (ISSUE 17, service/replication.ReplicationHub;
        #: None = no fabric). Injected like a foreign broker: the hub is
        #: SHARED between a primary app, its standby appliers, and a
        #: failover successor — in-process here, per-host over DCN later.
        self.replication_hub = replication_hub
        #: True when start() auto-built a SocketReplicationHub from
        #: cfg.net (owned: closed on stop/crash/drain). An injected hub
        #: is never closed here — it outlives hosts by design.
        self._owns_net_hub = False
        obs = self.cfg.observability
        #: Causal event spine (ISSUE 18, utils/forensics.py): ONE
        #: process-wide monotone sequence every lifecycle emission is
        #: stamped onto — EventLog appends, knob/placement decisions,
        #: replication epoch transitions, journal compaction/replay,
        #: breaker trips, SLO burns, speculation invalidations — so a
        #: single seq-ordered timeline spans engine→service→control→
        #: replication. Per-app, not module-global: two seeded runs must
        #: each start at seq 1 for the incident-soak's transcript pin.
        from matchmaking_tpu.utils.forensics import EventSpine

        self.spine = EventSpine(ring=self.cfg.forensics.spine_ring)
        #: Lifecycle event timeline (/debug/events): breaker trips, probes,
        #: delegations, revives, chaos faults — one bounded ring, appended
        #: to by the app, the broker, the engines, and the chaos hooks.
        #: Every append routes through the spine above.
        self.events = EventLog(obs.event_ring, spine=self.spine)
        #: Trace stamping master switch (flight recorder).
        self.trace_enabled = obs.trace
        #: Trace every Nth request publish (1 = all; PR 3 follow-up for
        #: very high ingress — see ObservabilityConfig.trace_sample_n).
        self.trace_sample_n = max(1, obs.trace_sample_n)
        self.metrics = Metrics(stage_buckets=obs.stage_buckets or None)
        #: Request-lifecycle flight recorder (/debug/traces): per-queue
        #: rings of settled traces + slow exemplars; feeds the per-stage
        #: histograms on every completion.
        self.recorder = FlightRecorder(
            self.metrics, ring=obs.trace_ring, slow_ring=obs.slow_trace_ring,
            slow_threshold_s=obs.slow_trace_ms / 1e3)
        #: Critical-path attribution (service/attribution.py): every
        #: settled trace's adjacent mark pairs are classified work-vs-wait
        #: into per-queue category histograms — the numbers behind
        #: /debug/attribution and the SLO good/total counters.
        self.attribution = Attribution(
            buckets=obs.stage_buckets or None,
            slo_target_s=obs.slo_target_ms / 1e3,
            tiers=max(1, self.cfg.overload.tiers))
        self.recorder.attribution = self.attribution
        #: Match-quality ledger (service/quality.py, ISSUE 8): per-queue/
        #: per-tier quality + wait-at-match histograms fed at response
        #: publish, plus the quality-SLO good/total counters the
        #: ``<queue>#quality`` burn monitors difference.
        self.quality = QualityLedger(
            QualitySpec.from_config(obs),
            quality_target=obs.quality_slo_target)
        #: Continuous telemetry ring (utils/timeseries.py): periodic
        #: snapshots of per-queue load/SLO/idle signals with delta/rate
        #: queries; sampled by _telemetry_loop every
        #: ObservabilityConfig.snapshot_interval_s.
        self.telemetry = TelemetryRing(obs.telemetry_ring)
        self._slo_monitors: dict[str, SloMonitor] = {}
        self._telemetry_task: "asyncio.Task | None" = None
        #: Deterministic chaos runtime (None when no schedule configured):
        #: one shared state so broker faults and per-queue engine fault
        #: hooks replay from a single script (utils/chaos.py).
        self.chaos: ChaosState | None = (
            ChaosState(self.cfg.chaos) if self.cfg.chaos.enabled() else None)
        if self.chaos is not None:
            # Before any engine hook exists: hooks copy the ref at creation.
            self.chaos.events = self.events
        self.broker = broker or InProcBroker(self.cfg.broker, self.cfg.seed,
                                             chaos=self.chaos)
        # Wire the broker into the shared observability plane (the in-proc
        # broker has both attrs; foreign transports may have neither).
        if hasattr(self.broker, "events"):
            self.broker.events = self.events
        # Chaos schedule for injected transports (AmqpBroker carries the
        # same drop/dup/partition hooks as the in-proc broker — PR 2
        # follow-up closed): the in-proc default got it at construction.
        if (self.chaos is not None and hasattr(self.broker, "chaos")
                and self.broker.chaos is None):
            self.broker.chaos = self.chaos
        if hasattr(self.broker, "trace_enabled"):
            self.broker.trace_enabled = self.trace_enabled
        if hasattr(self.broker, "trace_sample_n"):
            self.broker.trace_sample_n = self.trace_sample_n
        self._runtimes: dict[str, _QueueRuntime] = {}
        #: Black-box incident capture (ISSUE 18): subscribes to the spine
        #: and freezes bounded ring snapshots into schema-versioned
        #: bundles on trigger rules (/debug/incidents). Built after
        #: _runtimes exists — a capture racing construction reads {}.
        from matchmaking_tpu.utils.forensics import IncidentRecorder

        self.incidents = IncidentRecorder(self, self.cfg.forensics)
        self._started = False
        #: The loop this app's counters are attached to the process-wide
        #: stall hooks on (utils/trace.ProcessHooks) while it runs.
        self._hooks_loop: "asyncio.AbstractEventLoop | None" = None
        self._observability = None
        #: Elastic placement control plane (ISSUE 11; None = disabled).
        #: Built at start(): the controller needs the runtimes to bind
        #: boot placements and the telemetry ring to steer.
        self.placement = None
        #: Online autotuner (control/autotune.py, ISSUE 13; None =
        #: disabled). Built at start() like the placement controller.
        self.autotune = None

    async def start(self) -> None:
        assert not self._started
        if self.cfg.placement.enabled():
            from matchmaking_tpu.control import PlacementController

            self.placement = PlacementController(self, self.cfg.placement)
        for i, queue_cfg in enumerate(self.cfg.queues):
            self.broker.declare_queue(queue_cfg.name)
            rt = _QueueRuntime(self, queue_cfg,
                               placement=self._boot_placement(i, queue_cfg))
            self._runtimes[queue_cfg.name] = rt
            if self.cfg.engine.warm_start:
                rt.engine.warmup()
        if self.cfg.durability.enabled():
            # Hard-crash recovery (ISSUE 15), BEFORE any control plane or
            # traffic: an unclean predecessor's snapshot + journal tail
            # replays into each engine, the dedup/replay cache is
            # restored so broker redeliveries reconcile instead of
            # double-matching, and the span is recorded as crash_rto_ms.
            for rt in self._runtimes.values():
                await rt.recover_from_journal()
                rt.start_durability_timer()
        if self.cfg.replication.enabled():
            # Role state machine (ISSUE 17): this app boots as PRIMARY for
            # every queue — adopt a registered takeover handoff, acquire
            # the lease (LeaseHeldError = a live primary already owns it:
            # the boot-time split-brain guard), stream from the WAL tap.
            # Runs AFTER journal recovery so the standby's baseline is
            # the recovered truth, BEFORE any control plane or traffic.
            if self.replication_hub is None and self.cfg.net.enabled():
                # Real-transport fabric (ISSUE 20): cfg.net names a lease
                # service + replication target, so this app builds (and
                # owns) its SocketReplicationHub — the cross-process
                # deployment shape, where no in-process hub can be shared.
                from matchmaking_tpu.net.link import SocketReplicationHub

                self.replication_hub = SocketReplicationHub(
                    net=self.cfg.net, chaos=self.cfg.chaos,
                    seed=self.cfg.chaos.seed,
                    owner=self.cfg.replication.owner or "primary")
                self._owns_net_hub = True
            if self.replication_hub is None:
                raise ValueError(
                    "cfg.replication.role is set but no ReplicationHub was "
                    "passed to MatchmakingApp(replication_hub=...) — the "
                    "hub is the shared fabric (links + lease authority) a "
                    "standby attaches through")
            if not self.cfg.durability.enabled():
                raise ValueError(
                    "replication requires durability (journal_dir): the "
                    "WAL is the replication stream source")
            for rt in self._runtimes.values():
                await rt.start_replication()
        if self.placement is not None:
            self.placement.bind_boot_placements()
            self.placement.start()
        if self.cfg.autotune.enabled():
            from matchmaking_tpu.control.autotune import AutoTuner

            self.autotune = AutoTuner(self, self.cfg.autotune)
            self.autotune.start()
        obs = self.cfg.observability
        if obs.slo_target_ms > 0:
            def _monitor(key: str) -> SloMonitor:
                return SloMonitor(
                    key, target_ms=obs.slo_target_ms,
                    objective=obs.slo_objective,
                    fast_window_s=obs.slo_fast_window_s,
                    slow_window_s=obs.slo_slow_window_s,
                    burn_threshold=obs.slo_burn_threshold,
                    events=self.events, metrics=self.metrics)

            for name in self._runtimes:
                self._slo_monitors[name] = _monitor(name)
                # Tiered QoS: one burn monitor PER TIER on top of the
                # aggregate — "tier-0 holds its SLO while tier-2 burns" is
                # the whole point of ordered degradation, and an aggregate
                # monitor would average the two into a lie. Keyed
                # "queue@tN" (the telemetry ring's slo_good[queue@tN]
                # series); /healthz surfaces which tier is burning.
                if self.cfg.overload.tiers > 1:
                    for t in range(self.cfg.overload.tiers):
                        key = f"{name}@t{t}"
                        self._slo_monitors[key] = _monitor(key)
        if obs.quality_slo_target > 0:
            # Quality-SLO burn monitors (ISSUE 8): GOOD = matched with
            # quality >= target. Same SloMonitor machinery, pointed at the
            # ledger's quality_good/quality_total counter pair — a quality
            # regression burns on /healthz exactly like a latency SLO.
            for name in self._runtimes:
                self._slo_monitors[f"{name}#quality"] = SloMonitor(
                    f"{name}#quality",
                    target_ms=obs.quality_slo_target,
                    objective=obs.quality_slo_objective,
                    fast_window_s=obs.slo_fast_window_s,
                    slow_window_s=obs.slo_slow_window_s,
                    burn_threshold=obs.slo_burn_threshold,
                    events=self.events, metrics=self.metrics,
                    good_key=f"quality_good[{name}]",
                    total_key=f"quality_total[{name}]",
                    kind="quality")
        if obs.snapshot_interval_s > 0:
            self._telemetry_task = asyncio.create_task(self._telemetry_loop())
        elif self._slo_monitors:
            # The burn monitors only evaluate on telemetry ticks — with the
            # sampler off they would sit inert while a queue misses its SLO.
            log.warning(
                "slo_target_ms is set but snapshot_interval_s=0 disables "
                "the telemetry sampler — SLO burn monitors will never "
                "evaluate (call sample_telemetry() manually, or set an "
                "interval)")
        if self.cfg.metrics_port:
            from matchmaking_tpu.service.observability import ObservabilityServer

            self._observability = ObservabilityServer(
                self, host=self.cfg.metrics_host,
                port=self.cfg.metrics_port)
            await self._observability.start()
        self._hooks_loop = asyncio.get_running_loop()
        ProcessHooks.attach(self.metrics.counters, self._hooks_loop)
        self._started = True

    def _detach_hooks(self) -> None:
        """Leave the process-wide stall hooks (the last app out removes
        them)."""
        if self._hooks_loop is not None:
            ProcessHooks.detach(self.metrics.counters, self._hooks_loop)
            self._hooks_loop = None

    def _boot_placement(self, index: int,
                        queue_cfg: QueueConfig) -> "tuple[int, ...] | None":
        """The queue's boot-time device binding under the control plane:
        mesh-sharded queues keep the default leading-device span (their
        kernel sets build the mesh), single-device queues pack round-robin
        over the inventory — the static layout, now explicit so the
        controller's first tick starts from the truth. None when the
        control plane is off (the pre-placement default everywhere)."""
        if self.placement is None:
            return None
        n = self.placement.state.n_devices
        axis = self.cfg.engine.mesh_pool_axis
        if axis > 1:
            # The mesh spans the leading devices; an inventory smaller
            # than the axis is a config error PlacementState reports.
            return tuple(range(axis))
        return (index % n,)

    def _close_owned_net_hub(self) -> None:
        """Tear down an auto-built socket replication fabric (sockets +
        IO tasks die with the host). Injected hubs are left alone."""
        hub = self.replication_hub
        if self._owns_net_hub and hub is not None:
            try:
                hub.close()
            except Exception:
                log.exception("socket replication hub close failed")
            self.replication_hub = None
            self._owns_net_hub = False

    async def crash(self) -> None:
        """Simulated HARD crash (durability tests):
        tear the process state down with NO drain, NO checkpoints, and NO
        clean-shutdown journal markers — in-flight windows are dropped,
        uncommitted journal buffers are lost, consumers die with the
        broker. What remains on disk is exactly what ``kill -9`` leaves;
        a successor app pointed at the same journal_dir must recover it."""
        if not self._started:
            return
        if self.placement is not None:
            await self.placement.stop()
        if self.autotune is not None:
            await self.autotune.stop()
        self._stop_telemetry()
        if self._observability is not None:
            await self._observability.stop()
            self._observability = None
        for rt in self._runtimes.values():
            rt.abandon()
        self.broker.close()
        self._close_owned_net_hub()
        self._detach_hooks()
        self._started = False

    async def stop(self) -> None:
        if not self._started:
            return  # drain() already shut everything down
        if self.placement is not None:
            await self.placement.stop()
        if self.autotune is not None:
            await self.autotune.stop()
        self._stop_telemetry()
        if self._observability is not None:
            await self._observability.stop()
        for rt in self._runtimes.values():
            await rt.close()
        self.broker.close()
        self._close_owned_net_hub()
        self._detach_hooks()
        self._started = False

    async def drain(self, checkpoint_dir: str | None = None) -> dict[str, int]:
        """Graceful drain/handoff (SIGTERM path — see ``serve``): stop
        admission (late arrivals get ``shed`` + retry-after, not silence),
        drain every in-flight window so earned matches still publish,
        checkpoint each queue's waiting pool (utils/checkpoint.py), then
        stop. A restarted app pointed at the same directory restores the
        pools via ``restore_checkpoint`` — zero waiting players lost, and
        restore-side dedup means zero duplicate matches when the broker
        redelivers the same requests (at-least-once world).

        Returns per-queue checkpointed player counts ({} when no directory
        is configured)."""
        directory = (checkpoint_dir if checkpoint_dir is not None
                     else self.cfg.overload.drain_checkpoint_dir)
        if self.placement is not None:
            # Placement actions stop FIRST (cancel + AWAIT the tick, so
            # no migration is mid-flight): a migration racing the drain
            # would rebuild an engine the checkpoint walk below is about
            # to read.
            await self.placement.stop()
        if self.autotune is not None:
            # Knob writes stop before the per-queue close: a window-wait
            # retune racing a draining batcher is harmless but noisy.
            await self.autotune.stop()
        self._stop_telemetry()
        self.events.append("drain_begin", "",
                           f"checkpoint={'on' if directory else 'off'}")
        # Admission off FIRST, across all queues: deliveries that race the
        # per-queue close below are shed with an explicit response instead
        # of being half-processed into a pool we are about to freeze.
        for rt in self._runtimes.values():
            if rt.admission is not None:
                rt.admission.begin_drain()
        # Per-queue close: stops the timers, drains the batcher (final
        # windows still publish + ack), collects in-flight device windows,
        # cancels the consumer. Engines stay bound — the checkpoint below
        # reads their quiesced pools.
        for rt in self._runtimes.values():
            await rt.close()
        counts: dict[str, int] = {}
        if directory:
            counts = await self.save_checkpoint(directory)
            # Admission-state sidecar (ISSUE 11 satellite): the adaptive
            # credit fraction is DECISION state — without it a restored
            # queue admits a burst the predecessor had tightened against.
            # Saved after begin_drain flipped the controllers, so the
            # checkpoint method excludes drain mode by construction.
            adm = {name: rt.admission.checkpoint()
                   for name, rt in self._runtimes.items()
                   if rt.admission is not None}
            if adm:
                import os

                from matchmaking_tpu.utils.checkpoint import save_admission

                save_admission(os.path.join(directory, "_admission.json"),
                               adm)
            # Broker-backlog handoff (ROADMAP carry-over): the consumers
            # above are cancelled, so any delivery still buffered on a
            # request queue would die with this process on the in-proc
            # transport. Include them in the drain checkpoint; the
            # successor re-publishes them at restore (at-least-once —
            # restore-side dedup absorbs any overlap with redeliveries).
            if hasattr(self.broker, "drain_backlog"):
                import os

                from matchmaking_tpu.utils.checkpoint import save_backlog

                backlog = {
                    name: self.broker.drain_backlog(name)
                    for name in self._runtimes
                }
                backlog = {k: v for k, v in backlog.items() if v}
                n_backlog = save_backlog(
                    os.path.join(directory, "_backlog.json"), backlog)
                if n_backlog:
                    self.events.append(
                        "backlog_checkpointed", "",
                        f"{n_backlog} unconsumed deliveries across "
                        f"{len(backlog)} queue(s)")
        self.events.append(
            "drain_complete", "",
            f"{sum(counts.values())} waiting players checkpointed"
            if directory else "no checkpoint directory")
        if self._observability is not None:
            await self._observability.stop()
            self._observability = None
        self.broker.close()
        self._close_owned_net_hub()
        self._detach_hooks()
        self._started = False
        return counts

    def runtime(self, queue_name: str) -> _QueueRuntime:
        return self._runtimes[queue_name]

    # ---- continuous telemetry (utils/timeseries.py) ------------------------

    def sample_telemetry(self, now: float | None = None) -> dict[str, float]:
        """Take one telemetry snapshot into the ring and run the SLO burn
        monitors. Called by _telemetry_loop on its interval; also public so
        bench/tests can force a final point before reading trajectories.
        Read-only against runtimes (pool_size / gauges / monotone counters
        — the same unguarded surface /metrics already scrapes)."""
        now = time.time() if now is None else now
        prev = self.telemetry.latest()
        prev_vals = prev["values"] if prev is not None else {}
        vals: dict[str, float] = {
            "players_matched": self.metrics.counters.get("players_matched"),
        }
        gauges = self.metrics.gauges
        for name, rt in self._runtimes.items():
            vals[f"pool_size[{name}]"] = float(rt.engine.pool_size())
            for gauge in ("batch_fill", "breaker_state"):
                g = gauges.get(f"{gauge}[{name}]")
                if g is not None:
                    vals[f"{gauge}[{name}]"] = g
            if rt.admission is not None:
                vals[f"shed_total[{name}]"] = float(rt.admission.shed_total)
                vals[f"expired_total[{name}]"] = float(
                    rt.admission.expired_total)
                if rt.admission.tiers > 1:
                    for t in range(rt.admission.tiers):
                        vals[f"shed_total[{name}@t{t}]"] = float(
                            rt.admission.shed_by_tier[t])
                        vals[f"expired_total[{name}@t{t}]"] = float(
                            rt.admission.expired_by_tier[t])
            hist = self.metrics.stages.get(name, {}).get("total")
            if hist is not None and hist.count:
                vals[f"stage_total_p99_ms[{name}]"] = round(
                    hist.percentile(99) * 1e3, 3)
            totals = self.attribution.queue_totals(name)
            vals[f"work_s[{name}]"] = round(totals["work_s"], 6)
            vals[f"wait_s[{name}]"] = round(totals["wait_s"], 6)
            good, total = self.attribution.slo_counts(name)
            vals[f"slo_good[{name}]"] = float(good)
            vals[f"slo_total[{name}]"] = float(total)
            if self.cfg.observability.quality_slo_target > 0:
                qg, qt = self.quality.slo_counts(name)
                vals[f"quality_good[{name}]"] = float(qg)
                vals[f"quality_total[{name}]"] = float(qt)
            if self.cfg.overload.tiers > 1:
                # Per-tier SLO series (slo_good[queue@tN]) — what the
                # per-tier burn monitors difference: tier-0 attainment must
                # be readable while tier-2 burns its budget on purpose.
                for t in range(self.cfg.overload.tiers):
                    tg, tt = self.attribution.slo_counts_tier(name, t)
                    vals[f"slo_good[{name}@t{t}]"] = float(tg)
                    vals[f"slo_total[{name}@t{t}]"] = float(tt)
            if hasattr(rt.engine, "util_report"):
                u = rt.engine.util_report()
                vals[f"device_busy_s[{name}]"] = u["device_busy_s"]
                vals[f"device_idle_s[{name}]"] = u["device_idle_s"]
                vals[f"effective_occupancy[{name}]"] = (
                    u["effective_occupancy"])
                # Idle fraction over the SNAPSHOT interval (the trajectory
                # signal), not lifetime: delta of the monotone counters vs
                # the previous ring entry; lifetime fraction on the first.
                # A NEGATIVE delta means the counters reset under us (crash
                # revive / breaker swap installed a fresh engine) — the
                # interval spans two engines, so fall back to the new
                # engine's lifetime fraction instead of a corrupt ratio.
                db = u["device_busy_s"] - prev_vals.get(
                    f"device_busy_s[{name}]", 0.0)
                di = u["device_idle_s"] - prev_vals.get(
                    f"device_idle_s[{name}]", 0.0)
                vals[f"idle_frac[{name}]"] = (
                    round(di / (db + di), 6)
                    if db >= 0.0 and di >= 0.0 and db + di > 0
                    else u["idle_fraction"])
                vals[f"spec_commit_share[{name}]"] = u.get(
                    "spec_commit_share", 0.0)
            if hasattr(rt.engine, "spec_report"):
                sr = rt.engine.spec_report()
                if sr is not None:
                    # The speculation scoreboard (ISSUE 16): hit/miss/
                    # wasted trajectories are what the A-B bench and the
                    # frontier sweep read off the telemetry ring.
                    for k in ("spec_hit", "spec_miss", "spec_wasted"):
                        vals[f"{k}[{name}]"] = float(sr[k])
                    vals[f"spec_hit_rate[{name}]"] = sr["spec_hit_rate"]
            if hasattr(rt.engine, "frontier_snapshot"):
                # Adaptive frontier-K (ISSUE 14) into the ring (ISSUE 18
                # satellite): incident bundles and TuneView read the
                # active rung + monotone move count as trajectories.
                fs = rt.engine.frontier_snapshot()
                if fs is not None:
                    vals[f"frontier_k[{name}]"] = float(fs["frontier_k"])
                    vals[f"frontier_k_moves[{name}]"] = float(
                        fs["frontier_k_moves"])
        self.telemetry.append(now, vals)
        for mon in self._slo_monitors.values():
            mon.evaluate(self.telemetry, now)
        return vals

    async def _telemetry_loop(self) -> None:
        """Periodic sampler. Supervised like the collector: one bad tick
        must not end the trajectory."""
        interval = self.cfg.observability.snapshot_interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                self.sample_telemetry()
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("telemetry snapshot failed; retrying")
                self.metrics.counters.inc("telemetry_errors")

    def _stop_telemetry(self) -> None:
        if self._telemetry_task is not None:
            self._telemetry_task.cancel()
            self._telemetry_task = None

    # ---- checkpoint / resume (SURVEY.md §5) --------------------------------

    async def save_checkpoint(self, directory: str) -> dict[str, int]:
        """Serialize every queue's waiting pool to ``directory`` (one file
        per queue). Holds each engine lock so no window is mid-flight."""
        import os

        from matchmaking_tpu.utils.checkpoint import save_pool

        os.makedirs(directory, exist_ok=True)
        counts: dict[str, int] = {}
        for name, rt in self._runtimes.items():
            async with rt._engine_lock:
                # In-flight windows may still match (and release) mirror
                # entries; collect them so the snapshot is post-match.
                await rt._drain_engine(time.time())
                counts[name] = save_pool(
                    rt.engine, os.path.join(directory, f"{name}.npz"),
                    queue_name=name)
        return counts

    async def restore_checkpoint(self, directory: str,
                                 now: float | None = None) -> dict[str, int]:
        """Re-admit saved pools (no matching). Missing files are skipped."""
        import os

        from matchmaking_tpu.utils.checkpoint import load_pool

        counts: dict[str, int] = {}
        for name, rt in self._runtimes.items():
            path = os.path.join(directory, f"{name}.npz")
            if not os.path.exists(path):
                continue
            async with rt._engine_lock:
                await rt._drain_engine(now if now is not None else time.time())
                try:
                    counts[name] = load_pool(rt.engine, path, now)
                except Exception as e:
                    # A truncated/corrupt pool checkpoint must not crash
                    # the boot: the queue starts empty (the broker's
                    # at-least-once redelivery is the backstop) and the
                    # corruption is speakable in the event timeline.
                    self.events.append(
                        "checkpoint_corrupt", name,
                        f"{os.path.basename(path)}: {e} — starting empty")
                    log.warning("pool checkpoint %s unreadable (%s); "
                                "queue %r starts empty", path, e, name)
        # Admission-state sidecar (ISSUE 11 satellite): restore the
        # adaptive credit fraction + shed/expired accounting so the
        # successor's first admission ladder walk is IDENTICAL to what
        # the predecessor's next walk would have been (the regression
        # test in tests/test_overload.py diffs exactly that).
        adm_path = os.path.join(directory, "_admission.json")
        if os.path.exists(adm_path):
            from matchmaking_tpu.utils.checkpoint import load_admission

            try:
                restored_adm = load_admission(adm_path)
            except Exception as e:
                # CRC/version mismatch (ISSUE 15 satellite): a truncated
                # sidecar loses only the adaptive admission state, never
                # the boot.
                restored_adm = {}
                self.events.append("checkpoint_corrupt", "",
                                   f"_admission.json: {e} — skipped")
                log.warning("admission sidecar %s unreadable: %s",
                            adm_path, e)
            for qname, state in restored_adm.items():
                rt = self._runtimes.get(qname)
                if rt is not None and rt.admission is not None:
                    rt.admission.restore_state(state)
        # Re-publish the predecessor's unconsumed broker backlog (see
        # drain()): each entry flows through the normal publish path —
        # fresh delivery tags and trace contexts, original headers
        # (x-first-received / x-deadline budgets survive the handoff).
        backlog_path = os.path.join(directory, "_backlog.json")
        if os.path.exists(backlog_path):
            from matchmaking_tpu.utils.checkpoint import load_backlog

            try:
                per_queue = load_backlog(backlog_path)
            except Exception as e:
                per_queue = {}
                self.events.append("checkpoint_corrupt", "",
                                   f"_backlog.json: {e} — skipped")
                log.warning("backlog sidecar %s unreadable: %s",
                            backlog_path, e)
            republished = 0
            for qname, rows in per_queue.items():
                for row in rows:
                    self.broker.publish(
                        qname, row["body"],
                        Properties(reply_to=row["reply_to"],
                                   correlation_id=row["correlation_id"],
                                   headers=dict(row["headers"])))
                    republished += 1
            if republished:
                self.events.append(
                    "backlog_restored", "",
                    f"{republished} unconsumed deliveries re-published "
                    f"from drain checkpoint")
                log.info("restored %d unconsumed broker deliveries from %s",
                         republished, backlog_path)
        return counts


async def _demo() -> None:
    """Self-contained end-to-end demo: spin the app, submit players, print
    responses (the project verify recipe drives this)."""
    from matchmaking_tpu.config import EngineConfig
    from matchmaking_tpu.service.client import MatchmakingClient

    cfg = Config(engine=EngineConfig(backend="tpu", pool_capacity=1024,
                                     pool_block=256, batch_buckets=(16, 64)))
    app = MatchmakingApp(cfg)
    await app.start()
    client = MatchmakingClient(app.broker, cfg.broker.request_queue)
    players = [{"id": f"p{i}", "rating": 1500 + (i % 7) * 12} for i in range(10)]
    results = await asyncio.gather(*[
        client.search_until_matched(p, timeout=5.0) for p in players
    ])
    for resp in results:
        # Show the id TAIL: the head is the shared per-process prefix
        # (contract.new_match_id), identical for every match in this run.
        match_id = resp.match.match_id[-8:] if resp.match else "-"
        print(f"{resp.player_id}: {resp.status} match={match_id}")
    print("metrics:", app.metrics.report_json())
    await app.stop()


async def serve(stop: "asyncio.Event | None" = None,
                pika_module=None) -> None:
    """Production entrypoint: 12-factor config from ``MM_*`` env vars
    (Config.from_env), real AMQP transport when ``MM_BROKER_URL`` points at
    a RabbitMQ (``amqp://``/``amqps://``), in-process broker otherwise.
    Runs until SIGTERM/SIGINT (or ``stop`` is set — the test seam, which
    also injects ``pika_module``) — the Docker CMD. With
    ``MM_OVERLOAD_DRAIN_CHECKPOINT_DIR`` set, shutdown is a graceful drain
    (admission stops, in-flight windows finish, waiting pools checkpoint)
    and the next boot restores the pools — zero lost waiting players."""
    import os
    import signal

    # Multi-host (DCN): when MM_DCN_* names a topology, join the jax
    # multi-host runtime BEFORE any backend touch so jax.devices() is the
    # global list and mesh_pool_axis can span hosts (engine/distributed.py;
    # 2-process path exercised by tests/test_dcn.py).
    from matchmaking_tpu.engine.distributed import dcn_configured, init_distributed

    if dcn_configured():
        rank, nprocs = init_distributed()
        logging.getLogger(__name__).info(
            "joined DCN topology: process %d of %d", rank, nprocs)

    cfg = Config.from_env()
    if cfg.engine.backend == "tpu":
        # Before the first compile: a restart finds its executables again.
        from matchmaking_tpu.utils.compile_cache import enable_compile_cache

        logging.getLogger(__name__).info(
            "compile cache at %s", enable_compile_cache())
    broker = None
    url = cfg.broker.url
    if url.startswith(("amqp://", "amqps://")):
        from matchmaking_tpu.service.amqp_transport import AmqpBroker

        broker = AmqpBroker(url, prefetch=cfg.broker.prefetch,
                            pika_module=pika_module,
                            consume_batch_max=cfg.broker.consume_batch_max)
        logging.getLogger(__name__).info("serving against AMQP broker %s", url)
    else:
        logging.getLogger(__name__).info(
            "MM_BROKER_URL %r is not amqp:// — using the in-process broker "
            "(demo/test semantics; clients must run in this process)", url)
    app = MatchmakingApp(cfg, broker=broker)
    await app.start()
    # Graceful handoff (OverloadConfig.drain_checkpoint_dir): restore the
    # waiting pools a predecessor checkpointed at its SIGTERM — zero lost
    # waiting players across a restart. Restore re-admits WITHOUT matching,
    # and pool-membership dedup absorbs the broker's redeliveries of the
    # same requests, so no player can land in two matches.
    drain_dir = cfg.overload.drain_checkpoint_dir
    if drain_dir and os.path.isdir(drain_dir):
        restored = await app.restore_checkpoint(drain_dir)
        if restored:
            logging.getLogger(__name__).info(
                "restored %d waiting players from drain checkpoint %s",
                sum(restored.values()), drain_dir)
    if stop is None:
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:  # pragma: no cover - non-unix
                pass
    try:
        await stop.wait()
    finally:
        if drain_dir:
            # SIGTERM = drain, not drop: admission stops, in-flight windows
            # finish, the waiting pools checkpoint for the successor.
            await app.drain(drain_dir)
        else:
            await app.stop()


if __name__ == "__main__":
    import sys

    logging.basicConfig(level=logging.INFO)
    if "--demo" in sys.argv:
        asyncio.run(_demo())
    elif "serve" in sys.argv or "--serve" in sys.argv:
        asyncio.run(serve())
    else:
        print("usage: python -m matchmaking_tpu.service.app [serve|--demo]")
