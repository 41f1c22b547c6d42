"""Typed configuration for the whole framework.

Replaces the reference's Mix config (``config/*.exs`` + env vars: broker URL,
queue names, pool size, default ``rating_threshold`` — SURVEY.md §2 C10, §5
"Config/flag system"). One frozen dataclass tree, loadable from JSON or
environment variables, passed explicitly (no global mutable config).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Mapping, Sequence


@dataclass(frozen=True)
class QueueConfig:
    """Per-matchmaking-queue knobs (the reference partitions work across AMQP
    queues per game-mode/region — SURVEY.md §2 "Queue sharding")."""

    #: AMQP queue this engine consumes (must equal what clients publish to —
    #: BrokerConfig.request_queue points at the default one).
    name: str = "matchmaking.search"
    #: Game mode this queue serves. ``None`` → mode taken from each request.
    game_mode: str | None = None
    #: Players per team. 1 → 1v1; 5 → 5v5 team-balanced (BASELINE config #3).
    team_size: int = 1
    #: Default max |rating_a - rating_b| for a valid match (reference knob
    #: ``rating_threshold`` — BASELINE.json north_star).
    rating_threshold: float = 100.0
    #: Threshold widening: effective threshold grows by this many rating
    #: points per second waited (0 disables; SURVEY.md §2 C9 notes widening is
    #: typical but unverified in the reference, so it is config-gated).
    widen_per_sec: float = 0.0
    #: Cap on the widened threshold.
    max_threshold: float = 400.0
    #: Use Glicko-2 rating-deviation-weighted scoring (BASELINE config #4).
    #: Applies to 1v1 distance; team queues use plain rating spread.
    glicko2: bool = False
    #: Require role coverage for team formation (BASELINE config #5).
    role_slots: tuple[str, ...] = ()
    #: Evict waiting players after this many seconds and answer ``timeout``
    #: (None → wait forever, durability delegated to the broker like the
    #: reference's volatile ETS pool — SURVEY.md §5 checkpoint/resume).
    request_timeout_s: float | None = None
    #: Publish an immediate ``queued`` ack when a request enters the pool
    #: (the matched response follows on the same reply queue when found).
    send_queued_ack: bool = True
    #: At-least-once dedup horizon: a redelivered/duplicated request whose
    #: player reached a terminal state (matched/timeout) within this many
    #: seconds is answered with the cached response instead of re-entering
    #: the pool (prevents one player landing in two matches).
    dedup_ttl_s: float = 30.0
    #: Default QoS priority tier for requests arriving WITHOUT an
    #: ``x-tier`` header on this queue (service/overload.py: tier 0 is the
    #: most latency-critical; higher numbers shed first). Only meaningful
    #: when ``OverloadConfig.tiers > 1`` — a ranked queue defaults to 0, a
    #: bot-fill queue to the lowest configured tier.
    default_tier: int = 0
    #: Periodic rescan of the longest-waiting players (seconds; 0 = off).
    #: Matching is otherwise arrival-triggered (reference semantics), so two
    #: waiting players whose thresholds WIDENED into compatibility would
    #: never match under zero traffic; the rescan re-submits the oldest
    #: waiting window so widening can resolve. Only meaningful with
    #: ``widen_per_sec > 0`` on 1v1 queues.
    rescan_interval_s: float = 0.0
    #: Players covered per rescan tick (0 → the batcher's max_batch).
    #: Device 1v1 queues rescan through a no-admission step that is safe to
    #: overlap in-flight windows AND to split into multiple device chunks
    #: (kernels._rescan_step), so this may exceed one batch bucket. A tick
    #: dispatches at most ``EngineConfig.pipeline_depth`` chunks (largest
    #: bucket each) so a pool-sized window cannot queue tens of device
    #: steps ahead of traffic; the oldest-first pick rolls the remainder
    #: into later ticks — size pipeline_depth × largest bucket ≳ pool to
    #: resolve widening pool-wide in a single tick.
    rescan_window: int = 0


@dataclass(frozen=True)
class EngineConfig:
    """Engine selection + device-pool geometry."""

    #: ``"cpu"`` → NumPy oracle with the reference's sequential-scan
    #: semantics; ``"tpu"`` → batched JAX engine. The seam mirrors the
    #: reference's ``Matchmaking.Engine`` behaviour (SURVEY.md §2 C6).
    backend: str = "cpu"
    #: Fixed device-pool capacity P (static shape; slots are recycled).
    pool_capacity: int = 131_072
    #: Candidates kept per request before conflict resolution.
    top_k: int = 8
    #: Request-window batch buckets (padded to the smallest bucket ≥ batch) —
    #: static shapes keep XLA from recompiling in the hot path (p99 killer,
    #: SURVEY.md §7 "Hard parts").
    batch_buckets: tuple[int, ...] = (16, 64, 256, 1024)
    #: Pool-shard mesh axis size (1 → single device). Multi-chip: pool slots
    #: are sharded over axis ``"pool"`` and merged with XLA collectives.
    mesh_pool_axis: int = 1
    #: Use ring (ppermute) top-k merge instead of all_gather when sharded.
    ring_merge: bool = False
    #: Score tile size over the pool dimension (blockwise scoring keeps the
    #: B×P score matrix out of HBM at P=100k; SURVEY.md §7 "Hard parts").
    pool_block: int = 8192
    #: Proposal rounds in the parallel greedy pairing kernel. Each round
    #: resolves all non-conflicting best edges at once; leftovers (rare —
    #: they need ≥``pair_rounds`` collisions on their top-k list) stay in
    #: the pool for the next window.
    pair_rounds: int = 8
    #: Team queues (device path): max matches extracted per step and
    #: parallel-greedy window-selection rounds (engine/teams.py).
    team_max_matches: int = 1024
    team_rounds: int = 16
    #: Ring-scaled sharded team/role window formation (mesh_pool_axis > 1).
    #: 0 = replicated fallback only: every step all_gathers the full pool
    #: columns — O(P) ICI bytes and O(P) per-device window math regardless
    #: of shard count. N > 0 = per-shard top-N candidate frontier: each
    #: shard compacts its (group, rating)-sorted slice to N rows and the
    #: frontiers travel the ICI ring via ppermute (D−1 neighbor hops) —
    #: O(P/D + N·D) per device, bit-identical to the fallback while pool
    #: occupancy stays <= N (the host checks per window and silently falls
    #: back above it, counted in engine_counters team_ring_fallback). Size
    #: N at the expected concurrent WAITING population, not capacity.
    team_ring_k: int = 0
    #: Max dispatched-but-uncollected windows the SERVICE keeps in flight on
    #: the pipelined columnar path (1 = the old dispatch-then-block flush).
    #: Pipelining hides the host↔device round trip: depth 2 keeps a
    #: readback in flight while window N+1 computes. Readback latency on
    #: the chip is not measured yet (PERF.md), so neither is the depth
    #: past which more only queues latency.
    pipeline_depth: int = 2
    #: Device-side readback grouping: stack this many result arrays (one
    #: per dispatched window chunk — a window larger than the top batch
    #: bucket contributes one per chunk) ON DEVICE and transfer them to
    #: host as ONE array. What is amortized is TRANSFERS: where each
    #: device→host transfer pays a fixed latency, one transfer per k
    #: windows multiplies result throughput by ~k at the cost of up to
    #: (k-1) device-step times of extra latency for the group's first
    #: window. 1 = off (one transfer per window). Groups seal early when a
    #: caller collects (collect_ready/flush), so idle traffic is not held
    #: back a full group.
    readback_group: int = 1
    #: Age (ms) after which a partially-filled readback group is sealed and
    #: transferred anyway (checked on every collect_ready poll) — bounds
    #: the extra latency grouping can add when traffic pauses mid-group.
    readback_group_wait_ms: float = 8.0
    #: Compile every (batch bucket × step variant) executable at app start
    #: (Engine.warmup) instead of lazily on first use. The engine ships TWO
    #: compiled 1v1 step variants (full and all-ANY-window, see
    #: kernels.KernelSet); without warmup the first window that needs the
    #: OTHER variant stalls on an XLA compile inline on the serving path —
    #: the recompile cliff the bucketing exists to prevent. Off by default
    #: (tests build many small engines; deployment configs turn it on).
    warm_start: bool = False
    #: Rating-banded candidate pruning (single-device 1v1 path). 0 = dense
    #: scoring of every pool block. N > 0: each rating-sorted window chunk
    #: scores only an N-block contiguous span of the pool chosen from live
    #: per-block rating bounds — BIT-EXACT vs dense (a whole-window dense
    #: fallback cond covers spans that don't fit; kernels.py
    #: ``_search_step_pruned``). Effective only with ``band_spec`` set so
    #: the allocator keeps blocks rating-coherent. Size so that
    #: N·(capacity/n_blocks) slots cover ~2·max effective threshold of
    #: rating mass (Glicko-2: /g(max rd)) for the mid-distribution chunks.
    prune_window_blocks: int = 0
    #: Sorted-window chunk size for pruning: smaller chunks → tighter rating
    #: intervals → narrower spans, but more scan iterations per window.
    prune_chunk: int = 128
    #: Rating-band layout for the HOST slot allocator (core/pool.py
    #: ``band_edges_from_spec``): "" (off), "uniform:LO:HI", or
    #: "gaussian:MEAN:STD" (equal-mass bands — keeps band occupancy even
    #: under a normal rating distribution). One band per pool block.
    band_spec: str = ""
    #: Hierarchical rating-bucketed formation (ISSUE 14). Single-device 1v1
    #: queues: the pool dict carries a device-resident bucket index (per-
    #: block occupancy + conservative rating bounds, maintained
    #: incrementally by every admit/match/evict; kernels.INDEX_FIELDS) and
    #: window formation cuts candidate spans from the INDEX instead of
    #: re-deriving block bounds with an O(P) per-window scan — sub-O(P)
    #: formation, bit-exact vs the flat step (dense-fallback cond above
    #: span overflow), with the touched-slot fraction reported per window.
    #: Most effective with ``band_spec`` set (rating-coherent blocks);
    #: without it the step stays correct but mostly falls back to dense.
    #: Sharded 1v1 queues additionally need ``bucket_frontier_k``.
    bucketed: bool = False
    #: Per-bucket top-K frontier exchange for SHARDED 1v1 queues
    #: (mesh_pool_axis > 1; engine/sharded.py ``bucket_step``): each shard
    #: compacts every local pool block into its top-K active rows and only
    #: those frontiers cross the shard boundary (ppermute ring) — ICI
    #: traffic and formation work become occupancy-shaped (O(nb·K))
    #: instead of capacity-shaped (O(P)). This value is the LADDER
    #: CEILING: the engine sizes the actual K per window from the
    #: mirror's observed per-bucket occupancy (powers of two up to here,
    #: compiled lazily per K, moves audited in /debug/placement) and
    #: falls back to the dense sharded step when any bucket overflows —
    #: which is the bit-exactness gate. 0 = off.
    bucket_frontier_k: int = 0
    #: Consumer merge for ring-gathered team/role frontiers
    #: (``teams.merge_frontiers``): "linear" concatenates all D·K rows in
    #: canonical shard order (the PR 1 path); "tournament" merges the D
    #: already-sorted K-row frontiers up a pairwise tree keeping top-K —
    #: the formation buffer shrinks from O(K·D) to O(K) (working set
    #: O(K·log D)), bit-exact under the ring path's existing occupancy
    #: gate.
    frontier_merge: str = "linear"
    #: Device-engine circuit breaker (service/breaker.py): after this many
    #: engine crashes within ``breaker_window_s`` the queue's breaker trips
    #: OPEN and the queue is demoted to the host-oracle engine — matches
    #: keep flowing at oracle throughput instead of revive-looping a
    #: persistently failing device path at full traffic rate. 0 disables
    #: (every crash revives the device engine immediately, the pre-breaker
    #: behavior). Device (``backend="tpu"``) queues only.
    breaker_threshold: int = 0
    #: Sliding crash-count window for the trip decision (seconds).
    breaker_window_s: float = 30.0
    #: Half-open probe schedule while the breaker is open: the first probe
    #: runs ``breaker_probe_initial_s`` after the trip; each FAILED probe
    #: multiplies the delay by ``breaker_probe_backoff`` up to
    #: ``breaker_probe_max_s`` (exponential backoff — a dead device is not
    #: hammered). A probe builds a fresh device engine and runs one no-op
    #: step end to end; success re-promotes the queue (pool transferred
    #: back, breaker CLOSED).
    breaker_probe_initial_s: float = 1.0
    breaker_probe_backoff: float = 2.0
    breaker_probe_max_s: float = 60.0
    #: Dedicated low-frequency health timer (seconds; 0 disables). Drives
    #: the half-open breaker probes AND the idle re-promotion heartbeat for
    #: wildcard-delegated team/role queues — independent of ``_rescan_loop``,
    #: so a delegated queue with ``rescan_interval_s=0`` still re-promotes
    #: once its wildcards drain (ADVICE round-5 #3).
    health_interval_s: float = 1.0
    #: Speculative formation (ISSUE 16): spend idle window-gap device
    #: cycles precomputing pool-resident pairings (an ahead-of-time rescan
    #: tick over the resident pool), then commit the precomputed window in
    #: O(delta) at the next cut — or discard and fall back bit-exactly to
    #: the full step when any pool mutation invalidated the basis. Off by
    #: default: it trades wasted speculative steps (free on an idle device)
    #: for turnaround latency, which only pays on gappy traffic.
    spec_formation: bool = False
    #: Max chained speculative steps per gap (each runs on the previous
    #: speculative pool; matched-slot re-selection is a device-side no-op).
    spec_max_steps: int = 2
    #: Staleness bound (ms): a speculation older than this at commit time
    #: is discarded even if no mutation invalidated it — with widening on,
    #: a committed window is "the rescan evaluated at speculation time",
    #: and this caps how far in the past that evaluation may sit.
    spec_staleness_ms: float = 500.0
    #: Gap-poll cadence for the service speculation loop (ms; 0 disables
    #: the loop even with spec_formation on — cut-path commit still runs).
    spec_interval_ms: float = 10.0


@dataclass(frozen=True)
class BrokerConfig:
    """In-process AMQP-semantic broker knobs (SURVEY.md §2 "Distributed
    communication backend": real RabbitMQ is not available in this
    environment, so an in-process broker implements identical semantics
    behind the same interface)."""

    url: str = "inproc://matchmaking"
    request_queue: str = "matchmaking.search"
    #: Per-consumer unacked-message cap (AMQP basic.qos prefetch).
    prefetch: int = 2048
    #: Redelivery attempts for nacked/dropped deliveries (at-least-once).
    max_redelivery: int = 3
    #: Window-granular egress (ISSUE 9): the service publishes a whole
    #: window's responses through one ``publish_batch`` broker call instead
    #: of one ``publish`` per response — publish_lag collapses from
    #: O(matches) callbacks to O(windows). Per-message semantics (trace
    #: stamping, chaos seq accounting, dup faults) are preserved: items
    #: needing them take the full publish() path inside the batch. False =
    #: the per-response path, byte for byte.
    batch_publish: bool = True
    #: Columnar consume_batch ingress (ISSUE 12 — the decode side of the
    #: batch response encoder): the broker drains whole bursts of buffered
    #: deliveries and hands the app ONE callback per burst instead of one
    #: handler invocation + bookkeeping per delivery; the app then runs
    #: admission pre-checks, the native batch request decode (one C call
    #: over the burst's concatenated bodies + offsets), and the batcher
    #: hand-off burst-granular. Per-delivery semantics are preserved: a
    #: broker with consume-side fault injection armed (chaos drops, delay)
    #: keeps the per-delivery handler path so fault identity replays
    #: bit-identically, and auth-RPC services keep per-delivery tasks (the
    #: round trips must overlap). False = the per-delivery PR 9 path, byte
    #: for byte.
    consume_batch: bool = True
    #: Deliveries per consume burst (the batch callback's max rows; also
    #: the AMQP loop-bridge coalescing cap).
    consume_batch_max: int = 256
    #: In-process ingress shard workers per queue (ISSUE 12): a burst's
    #: contract-fallback rows are consistent-hashed (crc32 of the
    #: correlation id — the request identity available pre-decode) into N
    #: worker slices, and the shard columns merge at the EDF cut feeding
    #: the single device engine. The terminal-replay dedup cache is
    #: independently split into per-shard dicts by player id; shard
    #: workers never touch it (the probe runs at the cut, on the event
    #: loop), and the remaining ingress state (admission credits,
    #: batcher) stays event-loop-confined and is proven
    #: settle-exactly-once by matchlint's settlement typestate — which is
    #: what keeps the whole split lock-free. 1 = today's single-worker
    #: path, byte for byte. N > 1 runs shard slices on worker threads
    #: (the native decode and numpy assembly release the GIL, so
    #: multi-core hosts parallelize ingress).
    ingress_shards: int = 1
    # Fault-injection hooks (SURVEY.md §5 "Failure detection").
    drop_prob: float = 0.0
    dup_prob: float = 0.0
    delay_ms: float = 0.0


@dataclass(frozen=True)
class ChaosConfig:
    """Deterministic, scriptable fault schedule (SURVEY.md §5 "Failure
    detection") — the replay-exact successor to the probabilistic
    ``BrokerConfig.drop_prob``/``dup_prob`` hooks. Two fault families:

    - **Scripted** faults fire at exact sequence indices: per-queue publish
      sequence numbers for broker faults (``drop_seqs``/``dup_seqs``/
      ``partitions``), per-queue device SEARCH-step indices for engine
      faults (``fail_steps``/``fail_step_ranges`` — admits, evicts and
      restores are exempt so crash recovery itself cannot be failed).
    - **Seeded** faults are decided by hashing ``(seed, stream, queue,
      index[, attempt])`` — a pure function of each message's identity, so
      two runs with the same seed inject bit-identical faults regardless of
      event-loop interleaving. (``BrokerConfig.drop_prob`` draws from one
      shared RNG whose call ORDER depends on scheduling — soak accounting
      under it is irreproducible by construction.)

    Engine step counters live in the app runtime (utils/chaos.py
    ``ChaosState``), not the engine, so indices keep advancing across
    engine revives — a schedule failing steps 0-2 trips the circuit breaker
    instead of re-failing step 0 on every fresh engine forever.
    """

    seed: int = 0
    #: Queues the broker faults apply to; () = every queue including reply
    #: queues. Name the request queues to keep reply traffic fault-free
    #: (response publishes interleave nondeterministically with requests,
    #: so scripting them by index is rarely what a test wants).
    queues: tuple[str, ...] = ()
    # ---- seeded broker faults (pure function of (queue, seq, attempt)) ----
    #: Consume-side drop probability: the delivery is "crashed" before
    #: processing and requeued, exactly like BrokerConfig.drop_prob but
    #: decided by hash(seed, queue, seq, attempt).
    drop_prob: float = 0.0
    #: Publish-side duplicate-delivery probability, hash-decided per seq.
    dup_prob: float = 0.0
    # ---- scripted broker faults (per-queue publish sequence indices) ------
    #: Publish seqs whose FIRST delivery attempt is dropped.
    drop_seqs: tuple[int, ...] = ()
    #: Redelivery storms: (seq, extra_copies) — that publish is delivered
    #: 1 + extra_copies times (dedup/idempotence must absorb the storm).
    dup_seqs: tuple[tuple[int, int], ...] = ()
    #: Broker partitions: [pause_seq, resume_seq) — consumers of the queue
    #: pause when publish seq ``pause_seq`` is enqueued and resume when
    #: ``resume_seq`` is (messages buffer meanwhile; at-least-once holds).
    partitions: tuple[tuple[int, int], ...] = ()
    #: Failsafe: a paused queue auto-resumes after this many seconds even if
    #: the resume-seq publish never arrives (a mis-scripted schedule must
    #: not wedge a drain forever; fault ACCOUNTING stays seq-deterministic).
    partition_max_s: float = 5.0
    # ---- scripted engine faults (per-queue device search-step indices) ----
    #: Device search-step indices that raise ChaosInjectedError at dispatch.
    fail_steps: tuple[int, ...] = ()
    #: Same, as [start, stop) ranges — "raise on k consecutive windows".
    fail_step_ranges: tuple[tuple[int, int], ...] = ()
    #: The first N half-open breaker probes fail (a separate stream from
    #: fail_steps, so probe outcomes are scriptable independently of how
    #: many traffic steps the storm consumed).
    fail_probes: int = 0
    #: Device-loss fault (ISSUE 15): device SEARCH-step indices at which
    #: the engine raises ``ChaosDeviceLostError`` — modeling a mesh
    #: participant dying mid-serve (the XLA "device lost / transfer
    #: failed" error class, which a revive-from-mirror cannot fix because
    #: the rebuilt engine would bind the same dead chip). Shares the
    #: per-queue step counter with ``fail_steps``. The queue runtime
    #: routes it through the breaker's crash accounting into the failover
    #: path: an elastic-shardable sharded queue demotes to its SURVIVING
    #: devices (D → D-1, journal/mirror as the pool source) instead of
    #: revive-looping the dead mesh; the demotion is audited at
    #: /debug/placement with the measured blackout.
    device_lost_steps: tuple[int, ...] = ()
    #: Which logical device of the queue's binding "died" (-1 = the last
    #: device — the default models losing the highest shard).
    device_lost_device: int = -1
    # ---- scripted replication-link faults (per-queue STREAM record seqs,
    # ---- ISSUE 17; consumed by service/replication.InProcReplicationLink.
    # ---- Scripted faults fire on a record's FIRST transmission only —
    # ---- retransmission of the unacked tail is how the stream heals) ----
    #: Stream record seqs whose first transmission is dropped.
    repl_drop_seqs: tuple[int, ...] = ()
    #: Stream record seqs delivered twice (the applier's dedup absorbs).
    repl_dup_seqs: tuple[int, ...] = ()
    #: Reordering: (seq, hold_n) — the record is held until ``hold_n``
    #: further first transmissions pass, then delivered LATE (the
    #: applier's gap buffer must absorb the out-of-order arrival).
    repl_delay_seqs: tuple[tuple[int, int], ...] = ()
    #: Link partitions: [pause_seq, resume_seq) — the stream buffers from
    #: the pause record's first transmission until ANY transmission
    #: reaches the resume seq (replication lag grows; the failover-soak's
    #: lag-bounded-loss gate exercises exactly this window).
    repl_partitions: tuple[tuple[int, int], ...] = ()
    #: Seeded stream drop probability, hash-decided per
    #: (seed, "repl", queue, seq) — reproducible like every seeded fault.
    repl_drop_prob: float = 0.0
    #: Scripted lease-expiry faults: global renewal-call indices the
    #: LeaseAuthority refuses — the deterministic way to make a LIVE
    #: primary's lease lapse so a standby can legally take over (the
    #: split-brain fencing regression rides this).
    repl_fail_renewals: tuple[int, ...] = ()
    # ---- scripted NETWORK faults (ISSUE 20; consumed by net/nemesis.py
    # ---- at the socket transport's send/recv seams. Every entry names a
    # ---- FLOW by substring match against the connection's flow id
    # ---- ("repl:<queue>:fwd", "repl:<queue>:ack", "lease:<owner>") and a
    # ---- data-frame seq — record seq on replication flows, a per-flow
    # ---- frame counter elsewhere — so every decision is a pure function
    # ---- of (seed, connection id, frame seq). Scripted faults fire on a
    # ---- frame's FIRST transmission only, like the repl_* family:
    # ---- retransmission by cumulative ack is how the stream heals) ----
    #: (flow substring, frame seq): first transmission is dropped.
    net_drop_frames: tuple[tuple[str, int], ...] = ()
    #: (flow substring, frame seq): first transmission is sent twice.
    net_dup_frames: tuple[tuple[str, int], ...] = ()
    #: (flow, seq, hold_n): frame held until ``hold_n`` further first
    #: transmissions pass, then sent LATE (reordering over the wire).
    net_delay_frames: tuple[tuple[str, int, int], ...] = ()
    #: (flow, seq): instead of sending the frame, the sender abruptly
    #: closes the connection mid-stream (the torn-stream case — resume is
    #: reconnect + cumulative-ack retransmission).
    net_reset_frames: tuple[tuple[str, int], ...] = ()
    #: (flow, pause_seq, resume_seq): sender-side partition window
    #: [pause, resume) — frames buffer at the sender until any
    #: transmission reaches the resume seq.
    net_partitions: tuple[tuple[str, int, int], ...] = ()
    #: Flows whose INBOUND frames this process drops from the start — the
    #: scripted ASYMMETRIC partition (a primary that can send but cannot
    #: hear acks or lease-renewal responses lists its ack + lease flows
    #: here; heartbeats are dropped too, so the liveness verdict sees it).
    net_deaf_flows: tuple[str, ...] = ()
    #: Seeded frame-drop probability, hash-decided per
    #: (seed, "net", flow, seq) — reproducible like every seeded fault.
    net_drop_prob: float = 0.0
    #: (flow, bytes_per_s): sender-side bandwidth cap — frames over the
    #: budget wait (delivery delay, never corruption).
    net_bandwidth_caps: tuple[tuple[str, int], ...] = ()

    def enabled(self) -> bool:
        return bool(
            self.drop_prob > 0 or self.dup_prob > 0 or self.drop_seqs
            or self.dup_seqs or self.partitions or self.fail_steps
            or self.fail_step_ranges or self.fail_probes
            or self.device_lost_steps
        )

    def consume_faults(self) -> bool:
        """Any consume-side broker fault configured? (broker hot-path gate)"""
        return bool(self.drop_prob > 0 or self.drop_seqs)

    def publish_faults(self) -> bool:
        """Any publish-side broker fault configured? (broker hot-path gate)"""
        return bool(self.dup_prob > 0 or self.dup_seqs or self.partitions)

    def replication_faults(self) -> bool:
        """Any replication-link fault configured? (read by the hub when
        building links — the broker/engine gates above are untouched)."""
        return bool(
            self.repl_drop_seqs or self.repl_dup_seqs or self.repl_delay_seqs
            or self.repl_partitions or self.repl_drop_prob > 0
            or self.repl_fail_renewals
        )

    def net_faults(self) -> bool:
        """Any socket-transport fault configured? (read by net/nemesis.py
        when building per-flow fault scripts — the broker/engine/repl
        gates above are untouched)."""
        return bool(
            self.net_drop_frames or self.net_dup_frames
            or self.net_delay_frames or self.net_reset_frames
            or self.net_partitions or self.net_deaf_flows
            or self.net_drop_prob > 0 or self.net_bandwidth_caps
        )


@dataclass(frozen=True)
class OverloadConfig:
    """End-to-end overload control (service/overload.py): per-queue
    admission control, deadline propagation, adaptive load shedding, and
    graceful drain/handoff. The reference's survival story under load is
    RabbitMQ buffering — queues grow without bound and clients that gave
    up keep consuming engine windows; this subsystem bounds the queue in
    front of the matcher and is honest about rejection (explicit ``shed``
    responses with retry-after hints, never silent rot).

    Every knob is deterministic: admission decisions are pure functions of
    the controller's credit/pool counts at the decision point, so a chaos
    soak with burst ingress replays bit-identically (tests/test_overload).
    """

    #: Token/credit limiter: max admitted-but-unsettled deliveries per
    #: queue (a credit is held from admission until the delivery's ack or
    #: nack). 0 = unlimited. Also bounds the broker consumer's prefetch.
    max_inflight: int = 0
    #: Max waiting-pool occupancy counted at admission (live pool size +
    #: admitted credits on their way into it). 0 = unlimited.
    max_waiting: int = 0
    #: What to shed when the waiting cap is hit: ``"reject"`` sheds the
    #: INCOMING request (cheapest — nothing decoded, nothing dispatched);
    #: ``"oldest"`` admits it and sheds the longest-waiting pool player
    #: instead (freshness-biased queues, e.g. quick-play).
    shed_policy: str = "reject"
    #: Retry-after hint (ms) carried on shed responses — clients back off
    #: instead of hammering an overloaded queue.
    retry_after_ms: float = 1000.0
    #: Deadline propagation: requests arriving WITHOUT an ``x-deadline``
    #: header get one stamped at admission, first-received + this budget
    #: (0 = don't stamp; client-stamped deadlines are always honored).
    #: Deadlines are checked at admission, batch formation, and
    #: pre-dispatch — an expired request is cancelled (``timeout``
    #: response, ``expired`` trace mark) before any device work is spent.
    #: Transport caveat (same as ``x-first-received``): consumer-side
    #: stamps survive redelivery on the in-proc broker (the Delivery
    #: object is reused) but NOT over real AMQP, where a nack-requeue
    #: redelivers the originally PUBLISHED headers — a crash-looping
    #: request then gets a fresh budget per attempt. Clients that need a
    #: hard end-to-end deadline over AMQP must stamp it themselves at
    #: publish (``MatchmakingClient.submit(deadline_s=...)``), which is
    #: immune: publish-time headers do survive the wire and redelivery.
    default_deadline_ms: float = 0.0
    #: QoS priority classes (Nitsum admission tiers): requests carry an
    #: ``x-tier`` header (0 = most latency-critical; missing header → the
    #: queue's ``default_tier``), and admission partitions every cap into a
    #: nested ladder so graceful degradation is ORDERED — the lowest tier
    #: absorbs shedding and queueing first, and tier 0 is untouched until
    #: every lower tier is exhausted. 1 = untiered (exactly the pre-tier
    #: behavior; zero per-delivery overhead beyond one header default).
    tiers: int = 1
    #: Fraction of each cap tier ``t`` may reach counting only SAME-OR-
    #: HIGHER-priority usage (tiers ``<= t``): tier t is shed once
    #: occupancy(tiers 0..t) >= cap * tier_shares[t]. Element 0 is forced
    #: to 1.0 (tier 0 may use the whole cap); () → the equal ladder
    #: ((tiers-t)/tiers). Monotone non-increasing by construction of the
    #: check: a LOWER tier stops admitting strictly earlier, which is what
    #: makes adaptive tightening consume tier-2 first — every cap scales
    #: by the credit fraction and the smallest slice binds first.
    tier_shares: tuple[float, ...] = ()
    #: Earliest-deadline-first window cutting: the batcher and the columnar
    #: flush order window candidates by (tier, absolute x-deadline) instead
    #: of arrival order, so a near-deadline tier-0 request dispatches in
    #: the next device window instead of behind the backlog. Stable within
    #: equal keys (FIFO preserved for untiered/undeadlined traffic).
    edf: bool = False
    #: Pool-resident deadline expiry: sweep the per-slot ``x-deadline``
    #: column of every waiting pool this often (ms; 0 = off) and cancel
    #: expired waiters EXACTLY at their deadline — ``timeout`` response,
    #: ``expired`` trace mark, no dispatch — instead of the coarse
    #: ``request_timeout_s`` sweeper granularity.
    deadline_sweep_ms: float = 0.0
    #: Adaptive shedding: tighten the credit limit from live signals
    #: (pipeline occupancy, batch fill, per-stage p99) so the limiter
    #: reacts BEFORE the circuit breaker trips.
    adaptive: bool = False
    #: Adaptive target: when the queue's end-to-end stage p99 exceeds this,
    #: the effective credit limit is multiplied by ``tighten_step``; when
    #: p99 falls below half the target and the pipeline has headroom it is
    #: relaxed by ``relax_step`` (clamped to [min_credit_fraction, 1.0]).
    target_p99_ms: float = 250.0
    min_credit_fraction: float = 0.25
    tighten_step: float = 0.5
    relax_step: float = 1.25
    #: Graceful drain/handoff: SIGTERM (service.app.serve) stops admission,
    #: drains in-flight windows, and checkpoints every queue's waiting pool
    #: into this directory (utils/checkpoint.py); a restarted app restores
    #: it — zero waiting players lost. "" = drain without checkpointing.
    drain_checkpoint_dir: str = ""
    #: Window-granular admission (ISSUE 9): run the credit/occupancy ladder
    #: ONCE per cut window inside the flush (arrival-order pass over the
    #: window's cached tier/deadline columns) instead of per delivery at
    #: ingress. The per-delivery ingress keeps only the pre-checks that
    #: cannot wait for a cut (already-expired-at-receive, drain-mode shed,
    #: tier/deadline header caching for the EDF cut key). Ladder semantics
    #: are identical over the same count sequence — batching never reorders
    #: decisions within the stream. False = the per-delivery PR 5/7 path,
    #: byte for byte.
    batch_admission: bool = True

    def enabled(self) -> bool:
        """Any admission/deadline/drain machinery configured? The ingress
        hot path pays zero per-delivery overhead when False.
        ``drain_checkpoint_dir`` alone counts: the drain sequence needs a
        controller to flip into shed-everything mode (and /healthz needs
        it to report ``draining``) even when no cap is set. ``tiers > 1``
        and ``deadline_sweep_ms`` count too: tier parsing/accounting and
        the per-slot deadline sweep ride the controller."""
        return bool(self.max_inflight > 0 or self.max_waiting > 0
                    or self.default_deadline_ms > 0 or self.adaptive
                    or self.drain_checkpoint_dir or self.tiers > 1
                    or self.deadline_sweep_ms > 0)


@dataclass(frozen=True)
class DurabilityConfig:
    """Crash durability (ISSUE 15; utils/journal.py): a per-queue
    write-ahead pool journal + periodic compaction snapshots, so a HARD
    crash (OOM, host loss, ``kill -9``) recovers the waiting pool, the
    at-least-once dedup/replay cache and the admission decision state —
    the graceful drain→checkpoint→restore round trip (OverloadConfig.
    drain_checkpoint_dir) only fires on SIGTERM.

    Mechanics: admit/match/evict/expire mutations append as CRC-framed,
    version-stamped records, batched per cut window (the hot columnar
    path pays ONE buffered append per window, not per player) and
    committed before the corresponding response/ack leaves (write-ahead:
    a matched response is never visible before its terminal record is).
    The live segment periodically compacts into a pool snapshot
    (utils/checkpoint format) + a fresh segment; boot detects an unclean
    shutdown (no clean-shutdown marker) and replays newest-valid
    snapshot + journal tail into the engine — recovery time recorded as
    the ``crash_rto_ms`` gauge and a ``crash_recovered`` EventLog event.
    """

    #: Directory for per-queue journal segments + compaction snapshots
    #: ("" = durability off: zero hot-path work, no files).
    journal_dir: str = ""
    #: Commit durability: ``"none"`` buffers through the OS page cache
    #: (cheapest; a HOST loss can drop the tail, a process crash cannot),
    #: ``"interval"`` fsyncs at most every ``fsync_interval_s`` seconds,
    #: ``"window"`` fsyncs every commit (= every cut window — the
    #: bounded-loss setting the crash-soak acceptance measures).
    fsync: str = "none"
    #: Max seconds between fsyncs under the ``"interval"`` policy.
    fsync_interval_s: float = 0.05
    #: Compact (snapshot + segment rotation) once the live segment holds
    #: this many records…
    compact_records: int = 50_000
    #: …or this many bytes, whichever first. Compaction runs off the hot
    #: path (the app's durability timer), under the engine lock with the
    #: pipeline drained, so the snapshot is exactly consistent with the
    #: journal sequence it anchors.
    compact_bytes: int = 8 << 20
    #: Compaction-check cadence for the durability timer (seconds).
    compact_interval_s: float = 1.0
    #: Snapshot generations retained per queue (newest + fallbacks): a
    #: truncated/corrupt newest snapshot falls back to the previous good
    #: one at recovery instead of crashing the boot.
    keep_snapshots: int = 2

    def enabled(self) -> bool:
        return bool(self.journal_dir)


@dataclass(frozen=True)
class ReplicationConfig:
    """Hot-standby journal replication + fenced cross-host failover
    (ISSUE 17, service/replication.py). The primary streams every sealed
    WAL record per queue over a pluggable link to a warm standby that
    applies them into a shadow pool/dedup/admission state and acks a
    replication watermark; failover is lease/epoch-fenced (the standby
    takes over only after lease expiry, bumps the epoch, and the
    ex-primary's appends and publishes are refused at the journal-append
    and response-publish seams). Requires durability (the WAL is the
    stream source) and a :class:`~matchmaking_tpu.service.replication.
    ReplicationHub` passed to ``MatchmakingApp(replication_hub=...)`` —
    the hub is the in-process stand-in for the cross-host fabric (links
    + lease service), so config alone cannot conjure a standby."""

    #: ``""`` = replication off (zero hot-path work: no journal tap, no
    #: fence checks, no pump task). ``"primary"`` = this app streams and
    #: serves. (The standby side is not a full app — it is the hub's
    #: StandbyApplier, promoted via takeover + successor adoption.)
    role: str = ""
    #: This host's lease identity. A failover successor must boot with
    #: the TAKEOVER owner (the standby identity that bumped the epoch) —
    #: acquire() by the current lease holder renews; by anyone else over
    #: an unexpired lease it refuses (split-brain guard at boot).
    owner: str = "primary"
    #: Sender pump cadence (seconds): ack collection, stall retransmit,
    #: lease renewal, lag gauges.
    pump_interval_s: float = 0.02

    def enabled(self) -> bool:
        if self.role and self.role != "primary":
            raise ValueError(
                f"unknown replication role {self.role!r} (\"\" or "
                f"\"primary\"; the standby is a hub-side StandbyApplier, "
                f"not an app role)")
        return bool(self.role)


@dataclass(frozen=True)
class NetConfig:
    """Real-transport DCN seams (ISSUE 20, matchmaking_tpu/net/): the
    framed socket transport under the replication link and the lease
    service. ``transport="socket"`` makes the replication fabric run over
    TCP/UDS — length-prefixed CRC-framed messages, application
    heartbeats with a deadline-based peer-liveness verdict, seeded
    exponential-backoff-with-jitter reconnect, and bounded send buffers
    that surface backpressure (a dropped frame is healed by the
    cumulative-ack retransmission the in-proc link already relies on).

    Addresses are ``"unix:/path.sock"`` or ``"tcp:host:port"``. The
    fencing-over-RTT rule lives here too: ``lease_rtt_budget_s`` is
    subtracted from every lease grant the :class:`~matchmaking_tpu.net.
    lease.RemoteLeaseAuthority` caches, so a renewal still in flight when
    the budgeted deadline passes does NOT count — safety over liveness."""

    #: "inproc" (default — the PR 17 in-process fabric, zero sockets) or
    #: "socket" (the real transport; an app with replication enabled and
    #: no hub passed builds a SocketReplicationHub from the addrs below).
    transport: str = "inproc"
    #: Lease service address (required for transport="socket").
    lease_addr: str = ""
    #: Where this primary streams replication records (the standby's
    #: listen address). One queue per address; "" = stream to nowhere
    #: (frames drop until a target is set on the hub).
    repl_target: str = ""
    #: Dial timeout per connect attempt (seconds).
    connect_timeout_s: float = 1.0
    #: Blocking lease-RPC timeout (acquire/takeover/expired/release and
    #: the expired-validity renew re-confirm).
    request_timeout_s: float = 1.0
    #: Application heartbeat cadence per connection.
    heartbeat_interval_s: float = 0.1
    #: Peer-liveness deadline: no inbound frame for this long → the peer
    #: is declared dead (counted; the connection closes and reconnects).
    heartbeat_timeout_s: float = 0.6
    #: Reconnect backoff: min(cap, base * 2^attempt) scaled by seeded
    #: jitter in [0.5, 1.0] — hash01(seed, "backoff", conn, attempt).
    reconnect_base_s: float = 0.02
    reconnect_cap_s: float = 1.0
    #: Hostile-length guard: a frame header announcing more than this is
    #: a FrameError (connection dies; stream resumes by ack).
    max_frame_bytes: int = 1 << 20
    #: Bounded send buffer per link: once this many bytes are queued or
    #: in the transport buffer, further sends DROP and count
    #: (backpressure_dropped) instead of buffering unboundedly.
    send_buffer_bytes: int = 4 << 20
    #: Subtracted from every cached lease grant: the client treats a
    #: lease granted at send-time t as valid until t + lease_s - budget,
    #: under-approximating the authority's own deadline by the RTT the
    #: request may have spent in flight.
    lease_rtt_budget_s: float = 0.05

    def enabled(self) -> bool:
        if self.transport not in ("", "inproc", "socket"):
            raise ValueError(
                f"unknown net transport {self.transport!r} "
                f"(\"inproc\" or \"socket\")")
        return self.transport == "socket"


@dataclass(frozen=True)
class PlacementConfig:
    """Elastic queue→device placement control plane (matchmaking_tpu/
    control/): a controller that watches the telemetry ring (per-queue SLO
    burn, device idle fraction, effective occupancy, stage p99) and
    live-migrates queues across device engines using the drain/checkpoint/
    restore primitive — plus Nitsum-style elastic sharding (promote a hot
    1v1 queue from single-chip to D>1 and back as load recedes).  The
    greedy burn-to-idle policy ships first; the policy seam
    (control/policy.PlacementPolicy) is where a MIPS-style search planner
    drops in later.

    Every decision is a pure function of the controller's signal view at
    the tick (no RNG, no clock reads inside the policy — ``now`` is data),
    so the seeded simulation mode (control/simulate.py) replays decision
    traces bit-identically without devices."""

    #: Controller tick interval (seconds; 0 disables the control plane
    #: entirely — no task, no arbiter, zero hot-path overhead).
    interval_s: float = 0.0
    #: Logical device inventory the controller places queues onto. 0 =
    #: discover from the live backend (``jax.devices()``); N > 0 = a fixed
    #: logical inventory — what the host-oracle backend and the seeded
    #: simulation use (CpuEngine carries placement as metadata only).
    devices: int = 0
    #: A queue is HOT (migration source) when its SLO is burning or its
    #: device idle fraction over the last telemetry window falls below
    #: this bound.
    hot_idle_below: float = 0.15
    #: A device is a migration TARGET only when its idle fraction exceeds
    #: this bound (and it hosts no hot queue).
    cold_idle_above: float = 0.5
    #: Minimum idle-fraction gap between target and source devices before
    #: a migration is worth its blackout.
    min_idle_gain: float = 0.2
    #: Per-queue cooldown between placement actions (seconds) — bounds
    #: migrate/promote thrash; measured against the tick's ``now``.
    cooldown_s: float = 10.0
    #: Elastic sharding cap for 1v1 device queues (Nitsum adaptive
    #: parallelism): a hot queue alone on its device may be promoted to up
    #: to this many chips (D>1, engine/sharded.py) and is demoted back as
    #: load recedes. 1 = no elastic sharding.
    max_shard: int = 1
    #: Promote only while effective occupancy (valid/padded lanes) exceeds
    #: this — an idle-but-burning queue gains nothing from more chips.
    promote_occupancy: float = 0.5
    #: Demote a sharded queue once its idle fraction exceeds this.
    demote_idle_above: float = 0.8
    #: Placement decisions kept in the audit ring (/debug/placement).
    decision_ring: int = 256
    #: Cross-queue (tier, deadline) dispatch arbitration for queues the
    #: controller co-locates on one device: EDF ordering holds ACROSS
    #: co-located queues' concurrently-waiting windows, not just within
    #: one batcher.  Only engaged while >= 2 queues share a device — an
    #: unshared device's dispatches bypass the arbiter entirely.
    arbiter: bool = True

    def enabled(self) -> bool:
        return self.interval_s > 0


@dataclass(frozen=True)
class AutotuneConfig:
    """Telemetry-driven online autotuner (matchmaking_tpu/control/
    autotune.py, ISSUE 13): a supervised tick loop — the same audited
    decision shape as the placement controller — that reads the telemetry
    ring (stage p99, batch fill, idle fraction, shed deltas) and the SLO
    burn monitors, and moves ONE serving knob per tick within the declared
    safe ranges below:

    - ``max_wait_ms`` (the batcher window wait) — tightened multiplicatively
      while the queue's p99 exceeds the target; NEVER widened back by the
      tuner (a one-way ratchet: widening trades latency for batch fill,
      a tradeoff the frontier bench owns, not an online controller).
    - ``edf`` — earliest-deadline-first window cutting switched ON for a
      burning queue whose deliveries carry deadlines (also a ratchet).
    - ``pipeline_depth`` — in-flight window cap stepped down when latency
      stays high after the window floor, stepped back up once calm.
    - ``credit_fraction`` — the admission credit scale stepped down so an
      overloaded queue sheds earlier with honest responses; stepped back
      toward 1.0 once calm. Skipped when ``OverloadConfig.adaptive`` is on
      (that controller owns the fraction — two writers would fight).

    Safety model: every move is clamped to the range knobs below, applied
    one per tick so each effect is observable before the next decision,
    and recorded — trigger signals, from→to, observed effect one tick
    later — in a bounded audit ring served at ``/debug/autotune``. The
    plan step is a pure function of the signal view (no RNG, no clock
    reads), so a deterministic signal trajectory replays a bit-identical
    decision trace (tests/test_autotune.py pins it)."""

    #: Tick interval (seconds; 0 disables — no task, no knob writes).
    interval_s: float = 0.0
    #: The latency target the tuner steers to: tighten while the queue's
    #: rolling stage-total p99 exceeds this, relax when it falls below
    #: half of it. 0 → inherit ``ObservabilityConfig.slo_target_ms``.
    target_p99_ms: float = 0.0
    #: Safe range for the batcher window wait.
    max_wait_ms_min: float = 0.5
    max_wait_ms_max: float = 50.0
    #: Safe range for the pipeline depth (upper bound additionally clamped
    #: to the engine's configured ``pipeline_depth``).
    pipeline_depth_min: int = 1
    #: Floor for the admission credit fraction (the controller's own
    #: ``min_credit_fraction`` still applies; the tighter bound wins).
    credit_fraction_min: float = 0.25
    #: Multiplicative steps (tighten < 1 < relax).
    wait_step: float = 0.5
    fraction_step: float = 0.8
    #: Ticks a queue must stay calm (p99 < target/2, not burning) before a
    #: relax move, and the minimum ticks between ANY two moves on one
    #: queue — each move's effect must land in the telemetry ring before
    #: the next decision reads it.
    settle_ticks: int = 2
    #: Decisions kept in the audit ring (/debug/autotune).
    decision_ring: int = 256

    def enabled(self) -> bool:
        return self.interval_s > 0


@dataclass(frozen=True)
class ObservabilityConfig:
    """Request-lifecycle flight recorder + debug surfaces (utils/trace.py,
    service/observability.py). The BASELINE north star asserts a p99;
    these knobs size the machinery that explains one: trace contexts are
    stamped at broker publish, carried through every stage, and settled
    into per-queue rings + true per-stage histograms."""

    #: Trace-context stamping + flight recording. On by default: the cost
    #: is one small object per publish and O(marks) appends per delivery —
    #: measured noise next to decode/publish work.
    trace: bool = True
    #: Trace every Nth request publish (1 = every publish, the default).
    #: At the measured service knee one context per publish is noise, but
    #: a 500k+/s ingress allocates half a million dead objects a second
    #: for rings that keep 256 — sample instead: stage histograms stay
    #: statistically true, exemplars stay available, and untraced
    #: deliveries skip every mark. Applies to broker-side stamping (in-proc
    #: AND the AMQP header stamp); the lazy ingress fallback only runs at
    #: N == 1 so sampled-out deliveries aren't resurrected downstream.
    trace_sample_n: int = 1
    #: Completed traces kept per queue (newest wins; bounded memory).
    trace_ring: int = 256
    #: Slow-trace exemplars kept per queue.
    slow_trace_ring: int = 64
    #: A settled trace whose enqueue→publish span exceeds this keeps its
    #: full stage breakdown in the slow ring (/debug/traces "slow").
    slow_trace_ms: float = 250.0
    #: Lifecycle event-log ring size (/debug/events): breaker trips,
    #: probes, delegations, re-promotions, revives, chaos faults.
    event_ring: int = 512
    #: Per-stage histogram bucket upper bounds in SECONDS; () → the
    #: default log-spaced scheme (utils/metrics.DEFAULT_STAGE_BUCKETS:
    #: 100 µs · 2^k, 24 buckets + overflow, topping out ~14 min).
    stage_buckets: tuple[float, ...] = ()
    #: Where /debug/profile?secs=N writes its jax.profiler capture;
    #: "" → a fresh temp directory per capture.
    profile_dir: str = ""
    #: Continuous telemetry (utils/timeseries.TelemetryRing): the app
    #: samples a snapshot of per-queue signals (pool size, batch fill,
    #: breaker state, shed/expired totals, device busy/idle counters,
    #: stage p99, SLO good/total) every this many seconds into a bounded
    #: in-proc ring with delta/rate queries — the load signal the elastic
    #: placement controller (ROADMAP) consumes. 0 disables the sampler.
    snapshot_interval_s: float = 1.0
    #: Snapshots kept in the telemetry ring (newest wins).
    telemetry_ring: int = 512
    #: Per-queue SLO monitoring (utils/timeseries.SloMonitor): a settled
    #: request is GOOD when it reached a served outcome (matched / queued /
    #: deduped — shed and expired burn the budget on purpose) within this
    #: many milliseconds end to end (enqueue→publish). 0 disables SLO
    #: accounting and the burn monitors entirely.
    slo_target_ms: float = 0.0
    #: Attainment objective: the fraction of requests that must be GOOD
    #: (0.99 = "99% of requests served within the target").
    slo_objective: float = 0.99
    #: Multi-window burn-rate evaluation: the FAST window detects a budget
    #: bleed quickly, the SLOW window de-flaps; the queue is declared
    #: burning (``slo_burn`` EventLog event, ``slo_burning`` gauge,
    #: /healthz ``slo``) only when BOTH windows' burn rates exceed
    #: ``slo_burn_threshold``.
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 300.0
    slo_burn_threshold: float = 1.0
    #: Match-quality & fairness observatory (ISSUE 8; engine/quality.py).
    #: Rating-bucket edges for the conditional quality/wait accounting —
    #: () → engine/quality.DEFAULT_RATING_EDGES (8 buckets around a
    #: N(1500, 300) rating distribution). The fairness axis: per-bucket
    #: conditional means + the disparity gaps are computed over these.
    quality_rating_edges: tuple[float, ...] = ()
    #: Linear quality-histogram buckets over [0, 1].
    quality_buckets: int = 20
    #: Wait-at-match histogram bucket upper bounds (seconds); () → the
    #: default log-spaced scheme (1 ms · 2^k, 22 buckets + overflow).
    quality_wait_buckets: tuple[float, ...] = ()
    #: Device-accumulator readback cadence, in WINDOWS: the engine
    #: snapshots its device-resident quality state with an async D2H every
    #: N finalized windows and materializes it at a later finalize — the
    #: quality report is at most N windows stale and the hot path never
    #: pays a synchronous readback. flush() forces a fresh snapshot.
    quality_report_every: int = 16
    #: Per-queue quality SLO (reuses utils/timeseries.SloMonitor): a
    #: matched player is GOOD when the match quality is ≥ this target
    #: (0..1; 0 disables). Quality regressions then burn on /healthz
    #: exactly like latency SLOs — ``<queue>#quality`` monitor keys.
    quality_slo_target: float = 0.0
    #: Fraction of matched players that must meet the quality target.
    quality_slo_objective: float = 0.9


@dataclass(frozen=True)
class ForensicsConfig:
    """Incident forensics (ISSUE 18; utils/forensics.py): the causal
    event spine every lifecycle emission is stamped onto, plus the
    black-box auto-capture that freezes ring snapshots into
    schema-versioned incident bundles on trigger rules (SLO burn start,
    breaker trip, failover takeover, crash recovery, migration blackout
    over budget, autotuner oscillation). Surfaced at /debug/incidents."""

    #: Spine events kept in the process-wide causal ring.
    spine_ring: int = 4096
    #: Master switch for auto-capture (the spine itself always runs —
    #: it is the EventLog's ordering substrate and costs one counter).
    capture: bool = True
    #: Where bundles are persisted as JSON; "" keeps them in-proc only
    #: (/debug/incidents still serves the bounded ring).
    incident_dir: str = ""
    #: Bundles kept in the in-proc ring (newest wins).
    incident_ring: int = 16
    #: Bundle FILES kept under incident_dir (oldest pruned).
    retention_files: int = 32
    #: Per-trigger-class minimum seconds between captures — the burn-storm
    #: damper. Dropped captures are counted (incidents_dropped), never
    #: silent.
    min_interval_s: float = 5.0
    #: Spine events frozen per bundle (the incident window).
    spine_window: int = 512
    #: Telemetry-ring snapshots frozen per bundle.
    telemetry_tail: int = 32
    #: Slow-trace exemplars frozen per queue per bundle.
    trace_slice: int = 8
    #: Placement/autotune audit records frozen per bundle.
    audit_slice: int = 32
    #: Migration blackout budget (ms): a completed placement action whose
    #: measured blackout exceeds this triggers a capture. 0 disables the
    #: blackout trigger.
    blackout_budget_ms: float = 0.0
    #: Knob moves remembered per (queue, knob) for the autotuner
    #: oscillation detector (src→dst then dst→src within this window).
    oscillation_window: int = 8

    def enabled(self) -> bool:
        return self.capture


@dataclass(frozen=True)
class BatcherConfig:
    """Request windowing: collect a batch per queue, dispatch one kernel."""

    max_batch: int = 1024
    max_wait_ms: float = 5.0


@dataclass(frozen=True)
class AuthConfig:
    """Auth middleware. The reference checks each request's token against the
    platform's ``microservice-auth`` over AMQP RPC (SURVEY.md §2 C5); here the
    verifier is pluggable: ``"none"`` (off), ``"static"`` (shared-secret
    prefix), or ``"rpc"`` (round-trip over the broker to an auth queue)."""

    mode: str = "none"
    static_secret: str = "open-matchmaking"
    rpc_queue: str = "auth.token.verify"
    rpc_timeout_ms: float = 250.0


@dataclass(frozen=True)
class Config:
    queues: tuple[QueueConfig, ...] = (QueueConfig(),)
    engine: EngineConfig = field(default_factory=EngineConfig)
    broker: BrokerConfig = field(default_factory=BrokerConfig)
    batcher: BatcherConfig = field(default_factory=BatcherConfig)
    auth: AuthConfig = field(default_factory=AuthConfig)
    #: Deterministic fault-injection schedule (off by default — every field
    #: zero/empty means no chaos plumbing is touched on the hot path).
    chaos: ChaosConfig = field(default_factory=ChaosConfig)
    #: Admission control / load shedding / deadline propagation / graceful
    #: drain (off by default — see OverloadConfig.enabled()).
    overload: OverloadConfig = field(default_factory=OverloadConfig)
    #: Crash durability: write-ahead pool journal + hard-crash recovery
    #: (off by default — see DurabilityConfig.enabled()).
    durability: DurabilityConfig = field(default_factory=DurabilityConfig)
    #: Hot-standby journal replication + fenced failover (off by default
    #: — see ReplicationConfig.enabled(); requires durability).
    replication: ReplicationConfig = field(default_factory=ReplicationConfig)
    #: Real-transport DCN seams: socket replication link + remote lease
    #: service (ISSUE 20; "inproc" by default — zero sockets).
    net: NetConfig = field(default_factory=NetConfig)
    #: Flight recorder / debug endpoints (tracing on by default).
    observability: ObservabilityConfig = field(
        default_factory=ObservabilityConfig)
    #: Incident forensics: causal event spine + black-box bundle capture
    #: (ISSUE 18; spine always on, capture on by default).
    forensics: ForensicsConfig = field(default_factory=ForensicsConfig)
    #: Elastic queue→device placement control plane (off by default — see
    #: PlacementConfig.enabled()).
    placement: PlacementConfig = field(default_factory=PlacementConfig)
    #: Telemetry-driven online autotuner (off by default — see
    #: AutotuneConfig.enabled()).
    autotune: AutotuneConfig = field(default_factory=AutotuneConfig)
    #: Number of concurrent search workers draining batches (the reference's
    #: GenServer pool size analog — SURVEY.md §2 C7).
    workers: int = 2
    seed: int = 0
    #: Online invariant checking (no player in two matches — SURVEY.md §5
    #: "Race detection"). One dict op per matched player; on in tests.
    debug_invariants: bool = False
    #: Optional HTTP observability endpoint (0 disables).
    metrics_port: int = 0
    #: Bind host for the observability endpoint. The localhost default is
    #: safe for bare-metal; containers must set ``0.0.0.0`` (the Dockerfile
    #: does) or docker-compose port mappings can't reach /metrics.
    metrics_host: str = "127.0.0.1"

    # ---- loading -----------------------------------------------------------

    @staticmethod
    def from_dict(d: Mapping[str, Any]) -> "Config":
        kw: dict[str, Any] = {}
        if "queues" in d:
            kw["queues"] = tuple(
                QueueConfig(**{**q, "role_slots": tuple(q.get("role_slots", ()))})
                for q in d["queues"]
            )
        for name, cls in (
            ("engine", EngineConfig),
            ("broker", BrokerConfig),
            ("batcher", BatcherConfig),
            ("auth", AuthConfig),
            ("chaos", ChaosConfig),
            ("overload", OverloadConfig),
            ("durability", DurabilityConfig),
            ("replication", ReplicationConfig),
            ("net", NetConfig),
            ("observability", ObservabilityConfig),
            ("forensics", ForensicsConfig),
            ("placement", PlacementConfig),
            ("autotune", AutotuneConfig),
        ):
            if name in d:
                sub = dict(d[name])
                # Tolerate unknown/removed keys (e.g. engine.use_pallas,
                # removed round 4) instead of failing the whole boot: a
                # config written for an older build should degrade to a
                # warning, not a TypeError at startup.
                known = {f.name for f in dataclasses.fields(cls)}
                for extra in [k for k in sub if k not in known]:
                    import logging

                    logging.getLogger(__name__).warning(
                        "config: ignoring unknown %s.%s (removed or "
                        "misspelled)", name, extra)
                    del sub[extra]
                def tuplify(v: Any) -> Any:
                    # Recursive: chaos dup_seqs/partitions/fail_step_ranges
                    # are tuples OF tuples in JSON ([[seq, n], ...]).
                    return (tuple(tuplify(x) for x in v)
                            if isinstance(v, list) else v)

                for f in dataclasses.fields(cls):
                    if f.name in sub and isinstance(sub[f.name], list):
                        sub[f.name] = tuplify(sub[f.name])
                kw[name] = cls(**sub)
        for scalar in ("workers", "seed", "debug_invariants", "metrics_port",
                       "metrics_host"):
            if scalar in d:
                kw[scalar] = d[scalar]
        return Config(**kw)

    @staticmethod
    def from_json(path: str) -> "Config":
        with open(path) as f:
            return Config.from_dict(json.load(f))

    @staticmethod
    def from_env(prefix: str = "MM_") -> "Config":
        """Env-var overrides of the flat scalar knobs (reference parity for
        12-factor config; nested keys use ``MM_ENGINE_BACKEND`` style).

        Two structural keys serve the multi-process supervisor
        (service/multiproc.py) and are generally useful:

        - ``MM_CONFIG_JSON=<path>`` — load the FULL config tree from a JSON
          file first, then apply the other ``MM_*`` scalars on top (env
          wins — the supervisor overrides per-worker backend/ports this
          way).
        - ``MM_QUEUE_NAMES=a,b`` — serve only the named queues from that
          tree (a worker's partition).
        """
        env = {k[len(prefix):].lower(): v for k, v in os.environ.items() if k.startswith(prefix)}
        base: dict[str, Any] = {}
        if "config_json" in env:
            with open(env.pop("config_json")) as f:
                base = json.load(f)
        queue_names = env.pop("queue_names", None)
        if not env and not base and queue_names is None:
            return Config()
        d: dict[str, Any] = base
        for key, raw in env.items():
            try:
                val: Any = json.loads(raw)
            except (ValueError, json.JSONDecodeError):
                val = raw
            if key in ("workers", "seed", "debug_invariants", "metrics_port",
                       "metrics_host"):
                d[key] = val
                continue
            parts = key.split("_", 1)
            if len(parts) != 2:
                continue
            section, name = parts
            d.setdefault(section, {})[name] = val
        cfg = Config.from_dict(d)
        if queue_names is not None:
            names = [n for n in str(queue_names).split(",") if n]
            keep = tuple(q for q in cfg.queues if q.name in names)
            missing = set(names) - {q.name for q in keep}
            if missing:
                raise KeyError(f"MM_QUEUE_NAMES not in config: {sorted(missing)}")
            cfg = dataclasses.replace(cfg, queues=keep)
        return cfg

    def to_dict(self) -> dict[str, Any]:
        """JSON-ready tree (inverse of from_dict; tuples become lists)."""
        return dataclasses.asdict(self)

    def queue(self, name: str) -> QueueConfig:
        for q in self.queues:
            if q.name == name:
                return q
        raise KeyError(f"unknown queue {name!r}")
