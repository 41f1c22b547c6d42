"""The TPU engine: batched window matching over a device-resident pool.

This is the ``engine: "tpu"`` backend behind the ``Engine`` seam — the
rebuild's answer to the north star (BASELINE.json): instead of a sequential
per-request pool scan, a window of requests is admitted into the HBM pool and
matched by one jitted kernel step (see ``engine/kernels.py``).

Host/device split (SURVEY.md §7):

- Host (this class): slot allocation, request mirror (= checkpoint),
  bucketing windows to static shapes, mapping matched slot pairs back to
  requests. Single writer — windows per queue are serialized, which is the
  atomicity story: a matched player leaves the pool before the next window
  is dispatched (SURVEY.md §7 "Hard parts: atomicity").

Concurrency contract (what matchlint's guarded-by rule enforces on the
SERVICE side): this engine has NO internal locks and must only be driven
with the owning queue runtime's ``_engine_lock`` held — every public
entry (search*/rescan*/collect_ready/flush/expire/remove/restore/
heartbeat/speculate/spec_*) mutates the mirror and the token books (``_pending``,
``_open``, ``failed_tokens``, ``rescan_tokens``, ``window_marks``)
unguarded, and the host-sync readbacks in here (``np.asarray`` on device
handles in ``_materialize``, ``block_until_ready`` in warmup/probe) are
DESIGNED to run on a worker thread via ``asyncio.to_thread``, never on
the event loop (the blocking-call rule's allowance is that these are not
``async def`` bodies).
- Device: admission scatter, blockwise score+mask, streaming top-k, greedy
  conflict-free pairing, eviction scatter — one fused jitted step.

Team-balanced queues (BASELINE config #3) run on device via the batch
team-window kernel (``engine/teams.py``); role queues (config #5) run on
device for solo traffic via ``engine/role_kernels.py`` — single- or
multi-chip — delegating to the host oracle only while parties or
region/mode wildcards are present (and promoting back once they drain).
The 1v1 paths (configs #1/#2/#4) — the north-star hot path — run on device
single- or multi-chip.
"""

from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field

import numpy as np

from typing import Any, Sequence

import jax
import jax.numpy as jnp

from matchmaking_tpu.config import Config, QueueConfig
from matchmaking_tpu.core.pool import (
    BatchArrays,
    PlayerPool,
    band_edges_from_spec,
    pack_batch,
)
from matchmaking_tpu.engine import scoring
from matchmaking_tpu.engine.interface import (
    ColumnarOutcome,
    Engine,
    Match,
    SearchOutcome,
    empty_columnar_outcome,
)
from matchmaking_tpu.engine.kernels import (
    KernelSet,
    QualityAccumKernel,
    kernel_set,
    named_jit,
)
from matchmaking_tpu.engine.quality import (
    HostQualityAccum,
    QualitySpec,
    add_arrays,
    build_report,
    empty_arrays,
)
from matchmaking_tpu.service.contract import (
    RequestColumns,
    SearchRequest,
    new_match_id,
    new_match_ids,
)
from matchmaking_tpu.utils.trace import stage

logger = logging.getLogger(__name__)


def _stack(*xs: Any) -> Any:
    """K windows' results as one array: one device-to-host copy."""
    return jnp.stack(xs)


def _copy_async(h: Any) -> None:
    """Queue an async D2H for one device array (no-op for non-Arrays)."""
    try:
        h.copy_to_host_async()
    except AttributeError:  # pragma: no cover - non-Array types
        pass


@dataclass
class _ReadGroup:
    """K windows' result arrays awaiting ONE device→host transfer.

    Where each device→host transfer pays a fixed latency, result arrays
    of consecutive windows are stacked ON DEVICE (one tiny jitted stack) and
    shipped as a single transfer — result throughput scales ~k per transfer
    slot. Groups are keyed by result shape (same batch bucket), sealed when
    full, at collect time once older than ``readback_group_wait_ms``, or at
    flush."""

    handles: list | None
    created: float
    stacked: Any = None
    host: np.ndarray | None = None
    #: Wall-clock (time.time) at seal — the "readback_seal" stage mark for
    #: every window whose results ride this group's transfer.
    sealed_at: float = 0.0
    #: Partial group sealed loose (stale/flush): handles transfer
    #: individually, NO device stack — the jitted stack would compile per
    #: (count, shape) and stale seals run on the service EVENT LOOP, where
    #: a first-time XLA compile freezes every queue. Loose seals happen in
    #: lulls/flushes where transfer serialization doesn't matter anyway;
    #: only FULL multi-window groups (sealed during dispatch, off-loop)
    #: use the stack.
    loose: bool = False


class _GroupSlot:
    """One window's row within a _ReadGroup's stacked transfer."""

    __slots__ = ("group", "idx")

    def __init__(self, group: _ReadGroup, idx: int):
        self.group = group
        self.idx = idx


@dataclass
class _Pending:
    """One dispatched-but-uncollected request window."""

    token: int
    #: per device-chunk: (payload, (q_slot, c_slot, dist) device handles,
    #: now). payload is list[SearchRequest] (object path) or
    #: (RequestColumns, slots ndarray) (columnar path).
    chunks: list[tuple[Any, tuple[Any, Any, Any], float]] = field(
        default_factory=list
    )
    #: wall-clock at dispatch, for the turnaround span.
    created: float = 0.0
    #: rejections determined at dispatch time (pool_full, party, ...)
    outcome: SearchOutcome = field(default_factory=SearchOutcome)
    #: columnar-path outcome (set instead of ``outcome`` when columnar)
    columnar: "ColumnarOutcome | None" = None
    #: filled by the collector thread: numpy (q_slot, c_slot, dist) per chunk
    raw: list[tuple[np.ndarray, np.ndarray, np.ndarray]] | None = None
    #: collector-thread failure, re-raised on the caller thread at finalize
    error: BaseException | None = None
    #: Window-level flight-recorder stage marks, wall-clock (time.time),
    #: appended in time order: dispatch → (h2d, device_step)×chunks →
    #: readback_seal → collect. Handed to the service via
    #: ``TpuEngine.window_marks[token]`` at finalize and merged into every
    #: member request's trace — the per-window half of the per-stage
    #: histograms (SURVEY.md §5 tracing).
    marks: list[tuple[str, float]] = field(default_factory=list)


@dataclass
class _Speculation:
    """One precomputed speculative formation window (ISSUE 16), held OFF
    the books until cut-time validation: no mirror mutation, no _Pending,
    no token — only device handles. ``pool`` is the post-step device pool
    produced by the NON-donated spec step, so the engine's live
    ``_dev_pool`` handle stays valid as the bit-exact fallback basis; a
    commit adopts ``pool`` in O(1), a discard drops the handles and the
    only cost was idle-gap device cycles."""

    #: ``TpuEngine.pool_mutations`` at snapshot time — the validation
    #: token: the speculation is committable iff the counter still matches
    #: (O(1) — a sequence compare, never a pool scan).
    basis_seq: int
    #: The ``now`` every speculative step was evaluated at: a committed
    #: window is bit-identical to rescan ticks issued at this timestamp.
    spec_now: float
    #: time.time() at snapshot (the committed window's spec_snapshot mark).
    wall_t: float
    #: Post-step device pool (non-donated outputs, adopted at commit).
    pool: Any
    #: _Pending-shaped chunks: ((cols, slots), (out_handle,), spec_now).
    chunks: list[tuple[Any, tuple[Any, ...], float]]
    steps: int
    lanes_valid: int = 0
    lanes_padded: int = 0


# The module docstring's concurrency contract, machine-checkable (PR 4
# carry-over): this engine has NO internal locks — every public entry must
# be driven with the owning queue runtime's _engine_lock held. The
# lock-free list names the safe point reads (single attribute/len reads
# under the GIL, no mirror mutation) the service uses off-lock: admission
# occupancy, backpressure polling, /metrics scrapes.
# externally-serialized-by: _engine_lock
# lock-free: pool_size, inflight, pool_tier_counts, deadline_count, util_report, span_report, quality_report, formation_report, spec_report
class TpuEngine(Engine):
    def __init__(self, cfg: Config, queue: QueueConfig,
                 devices: "tuple[int, ...] | None" = None):
        super().__init__(cfg, queue)
        #: Elastic placement binding (ISSUE 11): logical indices into
        #: ``jax.devices()`` this engine's pool lives on. None = the
        #: pre-placement default.  Single-device engines COMMIT the pool
        #: arrays to the chosen device (jit follows committed operands);
        #: sharded engines build their pool mesh over exactly these ids.
        self.devices: tuple[int, ...] | None = (
            tuple(int(d) for d in devices) if devices else None)
        self._device = (jax.devices()[self.devices[0]]
                        if self.devices is not None
                        and cfg.engine.mesh_pool_axis <= 1
                        and len(self.devices) == 1 else None)
        # Recompile visibility (SURVEY.md §5): every engine-owning process
        # counts XLA backend compiles; a hot-path recompile is a latency
        # cliff that must show in /metrics and the bench JSON.
        from matchmaking_tpu.utils.metrics import CompileCounter

        CompileCounter.install()
        ec = cfg.engine
        # Config #5 role queues run on device for SOLO traffic (round 5 —
        # engine/role_kernels.py, single- or multi-chip); parties and
        # wildcards delegate to the host oracle via the same switch
        # team-queue wildcards use. Plain team queues (config #3) and all
        # 1v1 configs run on device, single- or multi-chip.
        self._role_device = queue.team_size > 1 and bool(queue.role_slots)
        self._team_device = queue.team_size > 1
        if self._role_device and ec.mesh_pool_axis > 1:
            from matchmaking_tpu.engine.role_kernels import (
                sharded_role_kernel_set,
            )

            self.kernels = sharded_role_kernel_set(
                capacity=ec.pool_capacity,
                team_size=queue.team_size,
                role_slots=tuple(queue.role_slots),
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                n_shards=ec.mesh_pool_axis,
                max_matches=ec.team_max_matches,
                rounds=ec.team_rounds,
                frontier_k=ec.team_ring_k,
                frontier_merge=ec.frontier_merge,
            )
        elif self._role_device:
            from matchmaking_tpu.engine.role_kernels import role_kernel_set

            self.kernels = role_kernel_set(
                capacity=ec.pool_capacity,
                team_size=queue.team_size,
                role_slots=tuple(queue.role_slots),
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                max_matches=ec.team_max_matches,
                rounds=ec.team_rounds,
            )
        elif self._team_device and ec.mesh_pool_axis > 1:
            from matchmaking_tpu.engine.teams import sharded_team_kernel_set

            self.kernels = sharded_team_kernel_set(
                capacity=ec.pool_capacity,
                team_size=queue.team_size,
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                n_shards=ec.mesh_pool_axis,
                max_matches=ec.team_max_matches,
                rounds=ec.team_rounds,
                frontier_k=ec.team_ring_k,
                frontier_merge=ec.frontier_merge,
            )
        elif self._team_device:
            from matchmaking_tpu.engine.teams import team_kernel_set

            self.kernels = team_kernel_set(
                capacity=ec.pool_capacity,
                team_size=queue.team_size,
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                max_matches=ec.team_max_matches,
                rounds=ec.team_rounds,
            )
        elif ec.mesh_pool_axis > 1:
            # Multi-chip: pool slots sharded over the mesh axis "pool";
            # windows matched with XLA collectives (engine/sharded.py).
            from matchmaking_tpu.engine.sharded import sharded_kernel_set

            self.kernels = sharded_kernel_set(
                capacity=ec.pool_capacity,
                top_k=ec.top_k,
                pool_block=ec.pool_block,
                glicko2=queue.glicko2,
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                n_shards=ec.mesh_pool_axis,
                ring=ec.ring_merge,
                pair_rounds=ec.pair_rounds,
                device_ids=self.devices,
                # bucketed alone implies frontier exchange at the default
                # ladder ceiling; an explicit bucket_frontier_k wins.
                bucket_frontier_k=(ec.bucket_frontier_k
                                   or (128 if ec.bucketed else 0)),
            )
        else:
            self.kernels = kernel_set(
                capacity=ec.pool_capacity,
                top_k=ec.top_k,
                pool_block=min(ec.pool_block, ec.pool_capacity),
                glicko2=queue.glicko2,
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                pair_rounds=ec.pair_rounds,
                prune_window_blocks=ec.prune_window_blocks,
                prune_chunk=ec.prune_chunk,
                bucketed=ec.bucketed,
            )
        self._dev_pool = self._fresh_device_pool()
        # Capacity may have been rounded up (sharding divisibility).
        # Rating-banded slot allocation (one band per pool block) keeps
        # block rating bounds tight for the pruned kernel; harmless (and
        # unused) for non-pruning paths, so it keys off band_spec alone.
        n_seg = getattr(self.kernels, "global_blocks",
                        getattr(self.kernels, "n_blocks", 0))
        band_blocks = getattr(self.kernels, "n_blocks", 0)
        if not band_blocks and (ec.bucketed or ec.bucket_frontier_k):
            # Sharded bucket frontier: one band per GLOBAL block keeps the
            # mirror's buckets rating-coherent. Plain sharded queues keep
            # the pre-ISSUE-14 behavior (band_spec inert — no silent
            # allocator switch on upgrade).
            band_blocks = getattr(self.kernels, "global_blocks", 0)
        edges = band_edges_from_spec(ec.band_spec, band_blocks)
        self._band_edges = edges
        #: Bucketed-formation host state (ISSUE 14): the mirror tracks
        #: per-segment (= device block / rating bucket) occupancy whenever
        #: a bucketed step family can consume it — the sharded frontier
        #: gate and /debug/placement read it O(segments), never a scan.
        self._formation_segments = (
            n_seg if (getattr(self.kernels, "bucketed", False)
                      or getattr(self.kernels, "bucket_frontier_k", 0))
            else 0)
        self.pool = PlayerPool(self.kernels.capacity, queue.rating_threshold,
                               band_edges=edges,
                               segments=self._formation_segments)
        #: Adaptive frontier-K ladder (ISSUE 14 satellite, PR 1 follow-up):
        #: powers of two up to the configured ceiling; the per-window pick
        #: is the smallest rung holding the observed peak per-bucket
        #: occupancy, and every change lands in the bounded move ring
        #: surfaced at /debug/placement.
        bfk = getattr(self.kernels, "bucket_frontier_k", 0)
        self._frontier_ladder: tuple[int, ...] = ()
        if bfk:
            rungs = [bfk]
            k = bfk // 2
            while k >= 8:
                rungs.append(k)
                k //= 2
            self._frontier_ladder = tuple(sorted(set(rungs)))
        self._frontier_k_active = 0
        #: Bounded move audit, a plain LIST (not a deque): /debug/placement
        #: copies it off the engine lock, and copying a list concurrently
        #: with the engine thread's append is a single GIL-held C op,
        #: where iterating a mutating deque raises.
        self.frontier_moves: list[dict] = []
        #: Formation-touch accounting (monotone; formation_report reads it
        #: lock-free): slots the bucketed steps actually read vs the flat
        #: O(P) equivalent, accumulated at finalize from result row 3.
        self.formation = {"touched_slots": 0.0, "total_slots": 0.0,
                          "windows": 0}
        #: Whether the most recent _step_fn pick was a bucketed variant —
        #: names the window's device mark (formation_bucketed vs
        #: device_step) for the attribution classes.
        self._last_step_bucketed = False
        #: Tells the service health timer this engine has idle
        #: housekeeping beyond delegation (the bucketed index re-tighten)
        #: — app._health_loop otherwise skips heartbeat() entirely for
        #: non-delegated queues.
        self.heartbeat_housekeeping = bool(
            getattr(self.kernels, "bucketed", False))
        self.buckets = tuple(sorted(ec.batch_buckets))
        # Wall-clock rebase: device times are float32 (128 s spacing at epoch
        # magnitude), so all device-visible times are relative to the first
        # timestamp this engine sees.
        self._t0: float | None = None
        # Every team-family queue starts on device; the host oracle takes
        # over only DYNAMICALLY (wildcards / role-queue parties) via
        # _maybe_delegate_team, and hands back via _maybe_repromote_team.
        self._team_delegate = None
        #: Lifecycle counters surfaced in /metrics (engine_counters):
        #: team_delegated / team_repromoted record every wildcard
        #: delegation round-trip (SURVEY.md §5 observability).
        self.counters: dict[str, int] = {}
        #: ``now``-domain timestamp of the last wildcard seen while
        #: delegated (gates re-promotion; see _maybe_repromote_team).
        self._delegate_last_wc = float("-inf")
        # Pipelined windows: dispatched, not yet finalized (FIFO), all on the
        # CALLER thread (single-writer mirror AND single client thread —
        # a separate collector thread's blocking device reads were observed
        # to serialize against dispatch, stalling every dispatch ≈ one full
        # step). D2H transfers are queued at dispatch time with
        # copy_to_host_async, so by the time a window is finalized its
        # results are usually already on host.
        import collections

        self._open = 0                      # dispatched, not yet finalized
        self._pending: collections.deque[_Pending] = collections.deque()
        self._next_token = 0
        # Readback grouping (see _ReadGroup): disabled for device team
        # queues (synchronous dispatch, different result shape per step
        # family) and k<=1.
        self._rb_k = 1 if self._team_device else max(1, ec.readback_group)
        self._rb_wait_s = ec.readback_group_wait_ms / 1e3
        self._rb_open: dict[tuple, _ReadGroup] = {}
        self._stack_fns: dict[tuple, Any] = {}
        #: Windows finalized out-of-band (wildcard delegation flushes while
        #: dispatching) — handed to the caller on the next collect/flush.
        self._done_early: list[tuple[int, Any]] = []
        #: First device failure since the last sync search(); async callers
        #: should check this after collect_ready()/flush().
        self.device_error: BaseException | None = None
        #: Tokens whose window failed on device (their outcome reports every
        #: request as queued — true, the mirror still holds them). Pipelined
        #: callers need the per-window attribution to nack exactly the failed
        #: window's deliveries; callers discard entries they consume.
        self.failed_tokens: set[int] = set()
        #: Tokens of in-flight rescan windows — lets a shared collector
        #: route their outcomes to the rescan publisher instead of the
        #: request/delivery bookkeeping. Callers discard what they consume.
        self.rescan_tokens: set[int] = set()
        #: True when rescans may be dispatched with windows in flight (the
        #: kernel set ships the no-admission rescan variant, or the team
        #: step is inherently admission-free). The service skips its
        #: pipeline drain — the round-4 rescan stall — when set.
        self.rescan_overlap = (
            self._team_device
            or hasattr(self.kernels, "search_step_packed_rescan"))
        #: Device-step budget for one overlapped rescan tick: a pool-sized
        #: tick split into ceil(window/bucket) chunks would queue tens of
        #: device steps ahead of traffic windows (the pipeline_depth
        #: backpressure counts PENDINGS, not chunks — ADVICE round-5 #1),
        #: so a tick dispatches at most pipeline_depth chunks and the
        #: oldest-first selection covers the rest on later ticks.
        self._rescan_chunk_cap = max(1, cfg.engine.pipeline_depth)
        #: Speculative formation (ISSUE 16). ``pool_mutations`` is the
        #: monotone validation clock: bumped by every operation that
        #: changes pool CONTENT or donates ``_dev_pool`` buffers (a
        #: non-donated jit may alias pass-through pool fields to its
        #: input's buffers, so a later donation of ``_dev_pool`` could
        #: invalidate a held speculative pool — ``_pool_mutated`` discards
        #: the speculation BEFORE any such call). ``_spec_validated_seq``
        #: is the freshness stamp ``spec_validate`` sets and every
        #: mutation clears: ``spec_commit`` refuses a token that was not
        #: validated after the last mutation (the commit-without-validate
        #: / validate-after-mutate orderings the speculation matchlint
        #: rule and the sanitizer twin catch).
        self.pool_mutations = 0
        self._spec: _Speculation | None = None
        self._spec_validated_seq: "int | None" = None
        #: Chaos fault hook (utils/chaos.py EngineChaosHook), attached by
        #: the queue runtime AFTER construction — the hook (and its step
        #: counters) outlives this engine instance across revives. None =
        #: no chaos. Covers SEARCH steps + probes only; admit/evict/restore
        #: are exempt so crash recovery itself cannot be failed.
        self.chaos_hook = None
        #: Finalized windows' stage marks, keyed by token — the service
        #: pops each token it settles and merges the marks into member
        #: traces. Bounded: entries nobody consumes (sync search(), rescan
        #: ticks on old builds) are evicted oldest-first at a fixed cap.
        self.window_marks: dict[int, list[tuple[str, float]]] = {}
        #: Lifecycle event log (utils/trace.EventLog), attached by the
        #: queue runtime like chaos_hook — delegations/re-promotions are
        #: engine-internal transitions the app can't observe directly.
        self.events = None
        #: Stage spans (SURVEY.md §5 tracing): cumulative seconds + counts;
        #: read via span_report(). Written only on the caller thread.
        self.spans = {
            "windows": 0, "requests": 0, "matches": 0,
            "dispatch_s": 0.0,   # search_*_async host time (pack + H2D + jit)
            "turnaround_s": 0.0, # dispatch → finalized (device + collect)
            "dedupe_s": 0.0, "alloc_s": 0.0, "pack_s": 0.0,
            "h2d_s": 0.0, "jit_s": 0.0,
            # collect_ready host time: readiness poll, device-to-host
            # materialisation and finalize (empty polls included)
            "collect_s": 0.0,
        }
        #: Device-utilization accounting (ISSUE 6): monotone busy/idle
        #: second counters — busy while >= 1 window is dispatched-but-
        #: unfinalized, idle otherwise — accrued at the open-count 0↔1
        #: transitions (the spans between transitions are uniformly one or
        #: the other by construction), plus batch-fill lane counts so
        #: "effective occupancy" weights windows by how full they were.
        #: Counters, not gauges: idle FRACTION over any interval is
        #: delta(idle) / delta(busy + idle) between two scrapes.
        self.util = {
            "busy_s": 0.0, "idle_s": 0.0, "readback_s": 0.0,
            "lanes_valid": 0, "lanes_padded": 0,
        }
        #: perf_counter at the last busy/idle transition; written only on
        #: the caller thread (same single-writer discipline as the mirror).
        self._util_mark = time.perf_counter()
        #: Match-quality & fairness accumulation (ISSUE 8). Plain 1v1
        #: kernel sets accumulate ON DEVICE (engine/kernels.
        #: QualityAccumKernel — one extra async dispatch per window over
        #: arrays already on device, zero host scans); team/role/sharded
        #: paths fall back to the exact host-side equivalent at finalize.
        #: Never both for one match: ``_quality is None`` gates the host
        #: fallback.
        self._q_spec = QualitySpec.from_config(cfg.observability)
        self._quality: QualityAccumKernel | None = None
        if not self._team_device and isinstance(self.kernels, KernelSet):
            self._quality = QualityAccumKernel(
                capacity=self.kernels.capacity,
                widen_per_sec=queue.widen_per_sec,
                max_threshold=queue.max_threshold,
                rating_edges=self._q_spec.rating_edges,
                n_quality=self._q_spec.n_quality,
                wait_edges=self._q_spec.wait_edges)
        #: Device-resident accumulator state (None when host-only). NOT
        #: donated through accum steps, so snapshot handles stay valid for
        #: the piggybacked async readback below.
        self._q_dev = (self._quality.init_state()
                       if self._quality is not None else None)
        #: Host-side accumulator for the paths with no device kernel
        #: (object/team finalize, sharded columnar finalize) — same bucket
        #: scheme, merged into quality_report().
        self._q_host_accum = HostQualityAccum(self._q_spec)
        #: Last materialized device-state snapshot (numpy) + the in-flight
        #: async D2H handles; refreshed every ``quality_report_every``
        #: finalized windows, forced at flush(). quality_report() reads
        #: ONLY these host arrays — never a device sync off the lock.
        self._q_host: dict[str, np.ndarray] | None = None
        self._q_sync_handles: dict[str, Any] | None = None
        self._q_sync_every = max(1, cfg.observability.quality_report_every)
        self._q_windows = 0

    def _chaos_step(self) -> None:
        """Scripted device-step fault point: called BEFORE any state is
        touched for a search-step chunk, so an injected failure leaves the
        mirror/pool exactly as a real dispatch-time crash would."""
        if self.chaos_hook is not None:
            self.chaos_hook.on_step()

    # ---- Engine API -------------------------------------------------------

    def search(self, requests: Sequence[SearchRequest], now: float) -> SearchOutcome:
        if self._team_delegate is not None:
            self._note_wildcards(requests, now)
            if not self._maybe_repromote_team(now):
                return self._team_delegate.search(requests, now)
        assert self._open == 0, (
            "sync search() with windows in flight — collect with flush() first"
        )
        self.search_async(requests, now)
        out = SearchOutcome()
        # flush() returns the full outcome (dispatch-time rejections
        # included), so the search_async return value is dropped.
        for tok, o in self.flush():
            self.window_marks.pop(tok, None)  # sync caller: nobody merges
            _merge_outcomes(out, o)
        if self.device_error is not None:
            err, self.device_error = self.device_error, None
            raise err
        return out

    # ---- pipelined window API ---------------------------------------------
    # Device windows are dispatched without waiting for results; the donated
    # pool chains them in order on device, so a later window can never see a
    # player an earlier window matched. The host mirror lags: slots release
    # at finalize time (they are never reallocated in between — the free
    # list only shrinks until release). Pipelining hides the host↔device
    # round trip, which otherwise puts a hard RTT floor under every window.

    def _submit(self, pending: _Pending) -> None:
        """Queue the window's D2H behind its execution and track it FIFO."""
        if self._rb_k > 1:
            pending.chunks = [
                (payload, tuple(self._rb_attach(h) for h in handles), now)
                for payload, handles, now in pending.chunks
            ]
        else:
            for chunk in pending.chunks:
                for h in chunk[1]:
                    _copy_async(h)
            if pending.chunks:
                # Ungrouped windows seal (queue their D2H) right here;
                # grouped windows get their seal mark from the group at
                # finalize time.
                pending.marks.append(("readback_seal", time.time()))
        if self._open == 0:
            now_pc = time.perf_counter()
            self.util["idle_s"] += max(0.0, now_pc - self._util_mark)
            self._util_mark = now_pc
        self._open += 1
        self._pending.append(pending)

    # ---- readback grouping --------------------------------------------------

    def _rb_attach(self, out: Any) -> _GroupSlot:
        key = (out.shape, str(out.dtype))
        g = self._rb_open.get(key)
        if g is None:
            g = _ReadGroup(handles=[], created=time.perf_counter())
            self._rb_open[key] = g
        assert g.handles is not None
        g.handles.append(out)
        slot = _GroupSlot(g, len(g.handles) - 1)
        if len(g.handles) >= self._rb_k:
            self._rb_seal(key, g, full=True)
        return slot

    def _rb_seal(self, key: tuple, g: _ReadGroup, full: bool = False) -> None:
        """Start the group's D2H: one stacked transfer for FULL multi-window
        groups (sealed during dispatch, off the event loop), bare per-handle
        transfers otherwise (see _ReadGroup.loose)."""
        self._rb_open.pop(key, None)
        g.sealed_at = time.time()
        handles = g.handles
        assert handles is not None
        if full and len(handles) > 1:
            g.handles = None
            fkey = (len(handles),) + key
            fn = self._stack_fns.get(fkey)
            if fn is None:
                fn = named_jit("readback_stack", _stack)
                self._stack_fns[fkey] = fn
            g.stacked = fn(*handles)
            _copy_async(g.stacked)
        else:
            g.loose = True
            for h in handles:
                _copy_async(h)

    def _rb_seal_stale(self, force: bool = False) -> None:
        """Seal partial groups that have waited past the wait budget (or
        all of them, at flush) so a traffic lull cannot strand results."""
        if not self._rb_open:
            return
        now = time.perf_counter()
        for key, g in list(self._rb_open.items()):
            if force or now - g.created >= self._rb_wait_s:
                self._rb_seal(key, g)

    @staticmethod
    def _handle_ready(h: Any) -> bool:
        if isinstance(h, _GroupSlot):
            g = h.group
            if g.loose:
                assert g.handles is not None
                return g.handles[h.idx].is_ready()
            return g.stacked is not None and g.stacked.is_ready()
        return h.is_ready()

    @staticmethod
    def _materialize(h: Any) -> np.ndarray:
        if isinstance(h, _GroupSlot):
            g = h.group
            if g.loose:
                assert g.handles is not None
                return np.asarray(g.handles[h.idx])
            if g.host is None:
                g.host = np.asarray(g.stacked)
            return g.host[h.idx]
        return np.asarray(h)

    def _is_ready(self, pending: _Pending) -> bool:
        try:
            return all(self._handle_ready(h)
                       for c in pending.chunks for h in c[1])
        except AttributeError:  # pragma: no cover - older jax arrays
            return True

    def _fetch(self, pending: _Pending) -> None:
        """Materialize results on host (already transferred in the common
        case); device failures are parked on the pending entry."""
        if pending.raw is not None:
            return
        try:
            pending.raw = [tuple(self._materialize(h) for h in c[1])
                           for c in pending.chunks]
        except BaseException as e:
            pending.error = e

    def search_async(self, requests: Sequence[SearchRequest],
                     now: float) -> tuple[int, SearchOutcome]:
        """Dispatch a window without waiting. Returns (token, outcome-so-far)
        — the outcome carries dispatch-time rejections only; the full
        outcome arrives via collect_ready()/flush() under the same token."""
        if self._team_delegate is not None:
            self._note_wildcards(requests, now)
            if not self._maybe_repromote_team(now):
                t_disp = time.time()
                out = self._team_delegate.search(requests, now)
                token = self._next_token
                self._next_token += 1
                pending = _Pending(token=token, outcome=out)
                # Delegated-oracle window: the whole step ran inline on the
                # host — two marks bound it for the flight recorder.
                pending.marks = [("dispatch", t_disp),
                                 ("oracle_step", time.time())]
                pending.raw = []
                self._submit(pending)
                return token, SearchOutcome()

        if self._maybe_delegate_team(requests, now):
            return self.search_async(requests, now)  # re-enter via delegate

        pending = _Pending(token=self._next_token)
        pending.marks.append(("dispatch", time.time()))
        self._next_token += 1
        fresh: list[SearchRequest] = []
        seen_ids: set[str] = set()
        for req in requests:
            if req.party_size > 1:
                pending.outcome.rejected.append((req, "party_not_supported"))
            elif req.id in self.pool or req.id in seen_ids:
                continue  # idempotent redelivery (in pool or already in flight)
            else:
                seen_ids.add(req.id)
                fresh.append(req)

        if fresh:
            self._pool_mutated()  # admission + donating step ahead
        max_bucket = self.buckets[-1]
        for start in range(0, len(fresh), max_bucket):
            self._dispatch(fresh[start:start + max_bucket], now, pending)
        self._submit(pending)
        return pending.token, SearchOutcome(
            rejected=list(pending.outcome.rejected))

    def search_columns_async(self, cols: RequestColumns, now: float) -> int:
        """Columnar fast path: dispatch a 1v1 window given as numpy columns
        (region/mode already interned via ``intern_columns``). Returns the
        window token; the full ColumnarOutcome (including dispatch-time
        rejections) arrives via collect_ready()/flush() under that token.

        Per-request Python work here is ONLY the id→slot dict membership
        (dedupe for at-least-once redelivery); everything else is
        vectorized numpy + one device dispatch per bucket chunk.
        """
        assert not self._team_device and self._team_delegate is None, (
            "columnar path is 1v1-only (team/role queues use the object API)"
        )
        token = self._next_token
        with stage("dispatch", self.spans, token=token):
            pending = _Pending(token=token, created=time.perf_counter())
            pending.marks.append(("dispatch", time.time()))
            pending.columnar = empty_columnar_outcome()
            self._next_token += 1

            ids = cols.ids.tolist()
            waiting = self.pool._slot_of
            with stage("dedupe", self.spans, token=token):
                if len(set(ids)) == len(ids):  # common case: no dups
                    keep = np.fromiter((i not in waiting for i in ids), bool,
                                       len(ids))
                else:
                    local: set[str] = set()
                    keep = np.empty(len(ids), bool)
                    for j, pid in enumerate(ids):
                        keep[j] = pid not in waiting and pid not in local
                        if keep[j]:
                            local.add(pid)
                if not keep.all():
                    cols = cols.take(keep)

            if len(cols):
                self._pool_mutated()  # admission + donating step ahead
            max_bucket = self.buckets[-1]
            for start in range(0, len(cols), max_bucket):
                self._dispatch_cols(cols.slice(start, start + max_bucket),
                                    now, pending)
            self._submit(pending)
            self.spans["requests"] += len(cols)
        return token

    def rescan_async(self, max_window: int, now: float) -> int | None:
        """Re-submit the longest-waiting players as a search window so that
        threshold widening can resolve between POOL members (matching is
        otherwise arrival-triggered). Returns a window token, or None when
        there is nothing to rescan (empty pool; device team queues with
        fewer than one match's worth of players) or the path is unsupported
        (host-oracle team/role queues, which re-form on arrival). Device
        team queues rescan via _rescan_team (pool-wide window formation
        with an all-invalid batch).

        Overlap-safe (when the kernel set ships the no-admission rescan
        variant — see kernels._rescan_step): lanes are validity-gated by
        the DEVICE-side active flag, so windows may be in flight and the
        tick may span MULTIPLE chunks covering up to ``max_window`` players
        (a later chunk cannot re-match players an earlier chunk retired) —
        capped at ``pipeline_depth`` chunks per tick so one tick cannot
        queue a pool's worth of device steps ahead of traffic windows;
        oldest-first selection rolls the remainder into later ticks.
        Kernel sets without the variant (sharded) keep the old contract:
        one chunk, pipeline drained by the caller. The resulting
        ColumnarOutcome's q_ids are the unmatched rescans — callers must
        NOT re-ack them as newly queued. Tokens are recorded in
        ``rescan_tokens`` so a collector can recognize them."""
        if self._team_delegate is not None:
            # The periodic rescan tick is also the re-promotion heartbeat
            # for an IDLE delegated queue: with no arrivals and no expiry
            # sweep, nothing else would ever notice the wildcards/parties
            # draining.
            if not self._maybe_repromote_team(now):
                return None  # still delegated: oracle re-forms on arrival
        if self._team_device:
            tok = self._rescan_team(now)
            if tok is not None:
                self.rescan_tokens.add(tok)
            return tok
        rescan_step = getattr(self.kernels, "search_step_packed_rescan", None)
        if rescan_step is None:
            # No no-admission variant: a rescan window would re-admit — from
            # the not-yet-finalized mirror — slots an in-flight step may
            # already have matched and evicted, resurrecting a matched
            # player into a double match. Callers must drain first, and the
            # tick covers one chunk.
            assert self._open == 0, (
                "rescan_async() with windows in flight — collect with "
                "flush() first"
            )
            max_window = min(max_window, self.buckets[-1])
        else:
            # Overlapped multi-chunk ticks are budgeted: at most
            # _rescan_chunk_cap device steps per tick, so a pool-wide
            # rescan can't starve traffic windows of device slots.
            max_window = min(max_window,
                             self._rescan_chunk_cap * self.buckets[-1])
        pool = self.pool
        if len(pool) == 0:
            return None
        slots_all = pool.waiting_slots()
        if slots_all.size > max_window:
            # Tier-aware selection (ISSUE 9 satellite, PR 7 follow-up):
            # when the tick can't cover the whole pool, rescan the
            # lowest-(tier, deadline) slots first — the EDF cut key over
            # the QoS mirror columns, oldest-first within ties — so a
            # near-deadline tier-0 waiter widens before a fresh tier-2
            # one. Untiered deadline-less pools (all zeros → all +inf)
            # reduce to the old oldest-first order exactly.
            enq = pool.m_enqueued[slots_all]
            dl = pool.m_deadline[slots_all]
            order = np.lexsort((enq, np.where(dl > 0.0, dl, np.inf),
                                pool.m_tier[slots_all]))[:max_window]
            chosen = np.sort(slots_all[order]).astype(np.int32)
        else:
            chosen = np.sort(slots_all).astype(np.int32)
        pending = _Pending(token=self._next_token,
                           created=time.perf_counter())
        pending.columnar = empty_columnar_outcome()
        self._next_token += 1
        self._pool_mutated()  # donating rescan steps ahead

        t0 = self._rel_base(now)
        top = self.buckets[-1]
        for start in range(0, chosen.size, top):
            self._chaos_step()
            slots = chosen[start:start + top]
            cols = RequestColumns(
                ids=pool.m_id[slots].copy(),
                rating=pool.m_rating[slots].copy(),
                rd=pool.m_rd[slots].copy(),
                region=pool.m_region[slots].copy(),
                mode=pool.m_mode[slots].copy(),
                threshold=pool.m_threshold[slots].copy(),
                enqueued_at=pool.m_enqueued[slots].copy(),
                reply_to=pool.m_reply[slots].copy(),
                correlation_id=pool.m_corr[slots].copy(),
            )
            bucket = self._bucket_for(slots.size)
            batch = pool.batch_arrays_cols(cols, slots, bucket, t0)
            step = (rescan_step if rescan_step is not None
                    else self._step_fn(batch))
            self._dev_pool, out = step(
                self._dev_pool, jnp.asarray(pack_batch(batch, now - t0))
            )
            # Rescan matches are real matches: they land in the quality
            # accounting like traffic windows.
            self._quality_accum_dispatch(out, now)
            self.util["lanes_valid"] += int(slots.size)
            self.util["lanes_padded"] += bucket
            pending.chunks.append(((cols, slots), (out,), now))
        self._submit(pending)
        self.rescan_tokens.add(pending.token)
        return pending.token

    def _rescan_team(self, now: float) -> int | None:
        """Device-team rescan: the team step's window formation is POOL-wide
        (the batch only admits), so dispatching an all-invalid batch re-runs
        match formation with CURRENT effective thresholds — without this,
        two waiting groups whose thresholds WIDENED into compatibility would
        never match under zero traffic (the same gap the 1v1 rescan closes;
        config #3 enables widening). Overlap-safe as-is: an all-invalid
        batch admits nothing, and match formation reads only the on-device
        pool, which chains in dispatch order behind in-flight windows."""
        if len(self.pool) < 2 * self.queue.team_size:
            return None
        self._chaos_step()
        bucket = self.buckets[0]
        # All lanes are the canonical padding (slot = capacity sentinel,
        # valid = False) — the same never-matching batch that batch_arrays
        # produces for an empty window.
        batch = self.pool.batch_arrays([], [], bucket)
        t0 = self._rel_base(now)
        pending = _Pending(token=self._next_token,
                           created=time.perf_counter())
        self._next_token += 1
        self._dev_pool, out = self._step_fn(batch)(
            self._dev_pool, jnp.asarray(self._pack(batch, now - t0)))
        pending.chunks.append(([], (out,), now))
        self._submit(pending)
        return pending.token

    # ---- speculative formation (ISSUE 16) ---------------------------------
    # Between cut windows the device sits idle (util_report's idle
    # fraction) while turnaround p50 is pinned to window cadence.
    # speculate() spends those cycles running the no-admission rescan step
    # — through a NON-donated jit of the same trace — over the resident
    # pool, holding the result handles and the post-step pool WITHOUT
    # touching the mirror, the token books, or ``_dev_pool``. At the next
    # cut the service validates in O(1) (mutation-sequence compare +
    # staleness bound) and either commits — adopt the precomputed pool,
    # submit the held chunks as a normal rescan-family window, O(delta):
    # the delta admits ride their own traffic window on the adopted pool —
    # or discards, in which case the full step runs on the untouched
    # ``_dev_pool`` bit-exactly as if no speculation ever happened.
    #
    # Bit-exactness of the commit path: the spec step is the SAME jitted
    # computation as search_step_packed_rescan (donation changes buffer
    # reuse, not math), its inputs are the same mirror columns and device
    # pool a cold rescan_async at ``spec_now`` would read, and validation
    # guarantees zero pool mutations since the snapshot — so a committed
    # speculation IS the rescan tick evaluated at ``spec_now``, chunk for
    # chunk, bit for bit (the equivalence soak in tests/test_speculation.py
    # pins this).

    def _pool_mutated(self) -> None:
        """Advance the validation clock and discard any pending
        speculation. MUST run before every operation that changes pool
        content or donates ``_dev_pool`` buffers (see __init__ note);
        zero-effect sweeps return early without calling this, so an idle
        pool keeps its speculation across expiry ticks."""
        self.pool_mutations += 1
        self._spec_validated_seq = None
        if self._spec is not None:
            self._spec = None
            self.counters["spec_wasted"] = (
                self.counters.get("spec_wasted", 0) + 1)

    def speculate(self, now: float) -> bool:
        """Precompute up to ``spec_max_steps`` chained no-admission
        formation steps over the resident pool (tier/deadline-ordered
        selection, same budget as a rescan tick) and park the result as
        the pending speculation. Chained steps run on the previous step's
        output pool at the SAME ``now`` — matched slots are device-active
        no-ops, leftover lanes get further pairing rounds — so a commit
        equals ``steps`` rescan ticks at ``spec_now``. No engine state is
        mutated; returns True when a speculation is pending (already or
        newly). Exempt from the chaos step hook like admit/evict: a
        discarded speculation is always safe, so there is no crash-path
        state to exercise."""
        ec = self.cfg.engine
        if not ec.spec_formation:
            return False
        if self._team_device or self._team_delegate is not None:
            return False
        spec_step = getattr(self.kernels, "search_step_packed_spec", None)
        if spec_step is None or self._dev_pool is None:
            return False
        if self._spec is not None:
            return True
        pool = self.pool
        if len(pool) < 2:
            return False
        max_window = self._rescan_chunk_cap * self.buckets[-1]
        slots_all = pool.waiting_slots()
        if slots_all.size > max_window:
            # Same EDF-flavored pick as rescan_async: near-deadline
            # low-tier waiters speculate first.
            enq = pool.m_enqueued[slots_all]
            dl = pool.m_deadline[slots_all]
            order = np.lexsort((enq, np.where(dl > 0.0, dl, np.inf),
                                pool.m_tier[slots_all]))[:max_window]
            chosen = np.sort(slots_all[order]).astype(np.int32)
        else:
            chosen = np.sort(slots_all).astype(np.int32)
        t0 = self._rel_base(now)
        top = self.buckets[-1]
        packed_chunks: list[tuple[Any, Any, int]] = []
        for start in range(0, chosen.size, top):
            slots = chosen[start:start + top]
            cols = RequestColumns(
                ids=pool.m_id[slots].copy(),
                rating=pool.m_rating[slots].copy(),
                rd=pool.m_rd[slots].copy(),
                region=pool.m_region[slots].copy(),
                mode=pool.m_mode[slots].copy(),
                threshold=pool.m_threshold[slots].copy(),
                enqueued_at=pool.m_enqueued[slots].copy(),
                reply_to=pool.m_reply[slots].copy(),
                correlation_id=pool.m_corr[slots].copy(),
            )
            bucket = self._bucket_for(slots.size)
            batch = pool.batch_arrays_cols(cols, slots, bucket, t0)
            packed_chunks.append(
                ((cols, slots), jnp.asarray(pack_batch(batch, now - t0)),
                 bucket))
        dev_pool = self._dev_pool  # non-donated: this handle stays live
        chunks: list[tuple[Any, tuple[Any, ...], float]] = []
        lanes_valid = lanes_padded = steps = 0
        for _pass in range(max(1, ec.spec_max_steps)):
            for payload, packed_dev, bucket in packed_chunks:
                dev_pool, out = spec_step(dev_pool, packed_dev)
                chunks.append((payload, (out,), now))
                lanes_valid += int(payload[1].size)
                lanes_padded += bucket
                steps += 1
        self.counters["spec_steps"] = (
            self.counters.get("spec_steps", 0) + steps)
        self._spec = _Speculation(
            basis_seq=self.pool_mutations, spec_now=now, wall_t=time.time(),
            pool=dev_pool, chunks=chunks, steps=steps,
            lanes_valid=lanes_valid, lanes_padded=lanes_padded)
        return True

    def spec_validate(self, now: float, max_age_s: float = 0.0) -> "int | None":
        """O(1) cut-time validation: the pending speculation's basis
        sequence must equal the live mutation clock (every admit/evict/
        expire/remove/restore/rebuild bumps it) and, when ``max_age_s`` >
        0, the snapshot must be younger than the bound (with widening on,
        a committed window is the rescan evaluated at ``spec_now`` — the
        bound caps how stale that evaluation may be). Failure discards the
        speculation (spec_miss) and returns None; success stamps the
        freshness token spec_commit requires."""
        s = self._spec
        if s is None:
            return None
        if (s.basis_seq != self.pool_mutations
                or (max_age_s > 0.0 and now - s.spec_now > max_age_s)):
            self._spec = None
            self._spec_validated_seq = None
            self.counters["spec_miss"] = (
                self.counters.get("spec_miss", 0) + 1)
            return None
        self._spec_validated_seq = s.basis_seq
        return s.basis_seq

    def spec_commit(self, token: int, now: float) -> "int | None":
        """Commit the validated speculation as a real rescan-family
        window: adopt the precomputed pool (O(1) — the old ``_dev_pool``
        handle is dropped, and nothing else referenced it), submit the
        held chunks as a normal _Pending, and register the token in
        ``rescan_tokens`` so the shared collector publishes the matches
        through the rescan path. ``token`` must be the value
        ``spec_validate`` returned with NO pool mutation in between — a
        stale or unvalidated token raises (the invariant the speculation
        lint rule + sanitizer twin enforce at call sites)."""
        s = self._spec
        if s is None:
            if token is None:
                return None  # nothing pending, nothing claimed — no-op
            # The caller holds a token but the speculation is gone: a pool
            # mutation slipped between spec_validate and spec_commit (the
            # validate-after-mutate ordering). Raising makes the ordering
            # bug deterministic instead of a silent dropped commit.
            raise RuntimeError(
                f"spec_commit token {token} refers to a discarded "
                f"speculation (pool_mutations={self.pool_mutations}) — a "
                f"pool mutation ran between spec_validate and spec_commit")
        if (self._spec_validated_seq is None
                or token != self._spec_validated_seq
                or token != s.basis_seq
                or token != self.pool_mutations):
            raise RuntimeError(
                f"spec_commit token {token} is not freshly validated "
                f"(validated={self._spec_validated_seq}, "
                f"basis={s.basis_seq}, pool_mutations="
                f"{self.pool_mutations}) — call spec_validate immediately "
                f"before spec_commit with no pool mutation in between")
        self._spec = None
        self._spec_validated_seq = None
        self.pool_mutations += 1  # the commit itself changes pool content
        self._dev_pool = s.pool
        pending = _Pending(token=self._next_token,
                           created=time.perf_counter())
        pending.columnar = empty_columnar_outcome()
        pending.marks.append(("spec_snapshot", s.wall_t))
        pending.marks.append(("spec_commit", time.time()))
        self._next_token += 1
        pending.chunks = list(s.chunks)
        if self._quality is not None:
            # Exact despite running post-adoption: the accumulator reads
            # only pool columns admission writes (rating/enqueue_t/
            # threshold) — match steps flip ``active`` alone, so the
            # adopted pool's columns equal the snapshot's bit for bit.
            for _payload, (out,), t in s.chunks:
                self._quality_accum_dispatch(out, t)
        self.util["lanes_valid"] += s.lanes_valid
        self.util["lanes_padded"] += s.lanes_padded
        self.counters["spec_hit"] = self.counters.get("spec_hit", 0) + 1
        self.counters["spec_committed_steps"] = (
            self.counters.get("spec_committed_steps", 0) + s.steps)
        self._submit(pending)
        self.rescan_tokens.add(pending.token)
        return pending.token

    def spec_invalidate(self, reason: str = "external") -> None:
        """Discard the pending speculation without advancing the mutation
        clock — the drain/checkpoint/restore/migration/revive hook. The
        held players are untouched (speculation owns no mirror state), so
        cancellation can never lose a player."""
        if self._spec is not None:
            self._spec = None
            self.counters["spec_wasted"] = (
                self.counters.get("spec_wasted", 0) + 1)
        self._spec_validated_seq = None

    def spec_report(self) -> "dict | None":
        """Speculation accounting (lock-free monotone-counter reads, like
        util_report): hit/miss/wasted outcomes, step totals, and the
        wasted-step fraction the bench A-B records."""
        if (self._team_device
                or not hasattr(self.kernels, "search_step_packed_spec")):
            return None
        c = self.counters
        hits = c.get("spec_hit", 0)
        miss = c.get("spec_miss", 0)
        wasted = c.get("spec_wasted", 0)
        steps = c.get("spec_steps", 0)
        committed = c.get("spec_committed_steps", 0)
        return {
            "spec_hit": hits,
            "spec_miss": miss,
            "spec_wasted": wasted,
            "spec_steps": steps,
            "spec_committed_steps": committed,
            "spec_pending": int(self._spec is not None),
            "spec_hit_rate": round(
                hits / max(1, hits + miss + wasted), 6),
            "spec_wasted_step_fraction": round(
                (steps - committed) / max(1, steps), 6),
        }

    def intern_columns(self, regions, modes) -> tuple[np.ndarray, np.ndarray]:
        """str sequences → interned int32 code arrays (pool-owned interners)."""
        rc, mc = self.pool.regions.code, self.pool.modes.code
        n = len(regions)
        return (np.fromiter((rc(r) for r in regions), np.int32, n),
                np.fromiter((mc(m) for m in modes), np.int32, n))

    def restore_columns(self, cols: RequestColumns, now: float) -> None:
        """Columnar restore: re-admit without matching (checkpoint path).
        Dedupes both against the pool and within the window (checkpoint
        files may carry duplicates after an at-least-once replay)."""
        waiting = self.pool._slot_of
        ids = cols.ids.tolist()
        seen: set[str] = set()
        keep = np.empty(len(ids), bool)
        for j, pid in enumerate(ids):
            keep[j] = pid not in waiting and pid not in seen
            if keep[j]:
                seen.add(pid)
        if not keep.all():
            cols = cols.take(keep)
        if len(cols):
            self._pool_mutated()  # re-admission mutates pool + donates
        bucket = self.buckets[-1]
        t0 = self._rel_base(now)
        for start in range(0, len(cols), bucket):
            chunk = cols.slice(start, start + bucket)
            slots = self.pool.allocate_columns(chunk)
            batch = self.pool.batch_arrays_cols(chunk, slots, bucket, t0)
            self._dev_pool = self.kernels.admit_packed(
                self._dev_pool, jnp.asarray(pack_batch(batch)))

    def _dispatch_cols(self, cols: RequestColumns, now: float,
                       pending: _Pending) -> None:
        """Columnar twin of _dispatch: admit + launch, no waiting."""
        if not len(cols):
            return
        self._chaos_step()
        free = self.pool.free_count()
        if len(cols) > free:
            assert pending.columnar is not None
            pending.columnar.rejected.extend(
                (pid, "pool_full") for pid in cols.ids[free:].tolist())
            cols = cols.slice(0, free)
            if not len(cols):
                return
        token = pending.token
        with stage("alloc", self.spans, token=token):
            slots = self.pool.allocate_columns(cols)
        bucket = self._bucket_for(len(cols))
        t0 = self._rel_base(now)
        with stage("pack", self.spans, token=token):
            batch = self.pool.batch_arrays_cols(cols, slots, bucket, t0)
            packed = pack_batch(batch, now - t0)
        with stage("h2d", self.spans, token=token):
            packed_dev = jnp.asarray(packed)
            pending.marks.append(("h2d", time.time()))
        with stage("launch", self.spans, "jit_s", token=token):
            self._dev_pool, out = self._step_fn(batch)(
                self._dev_pool, packed_dev
            )
            pending.marks.append(("formation_bucketed"
                                  if self._last_step_bucketed
                                  else "device_step", time.time()))
        with stage("quality_accum", token=token):
            self._quality_accum_dispatch(out, now)
        self.util["lanes_valid"] += len(cols)
        self.util["lanes_padded"] += bucket
        pending.chunks.append(((cols, slots), (out,), now))

    def span_report(self) -> dict[str, float]:
        """Per-window averages of the stage spans (ms)."""
        w = max(1, self.spans["windows"])
        return {
            "windows": self.spans["windows"],
            "requests": self.spans["requests"],
            "matches": self.spans["matches"],
            "dispatch_ms_avg": self.spans["dispatch_s"] / w * 1e3,
            "turnaround_ms_avg": self.spans["turnaround_s"] / w * 1e3,
            **{k.replace("_s", "_ms_avg"): v / w * 1e3
               for k, v in self.spans.items()
               if k in ("dedupe_s", "alloc_s", "pack_s", "h2d_s", "jit_s")},
        }

    def util_report(self) -> dict[str, float]:
        """Device-utilization counters (ISSUE 6): monotone busy/idle
        seconds (the CURRENT open-ended span is added read-only, so two
        scrapes delta cleanly without a dispatch in between), the
        h2d/step/readback split, and batch-fill-weighted effective
        occupancy. Read-only and thread-tolerant: floats read under the
        GIL, no mutation — /metrics may call this off the engine lock."""
        now_pc = time.perf_counter()
        open_span = max(0.0, now_pc - self._util_mark)
        busy = self.util["busy_s"] + (open_span if self._open else 0.0)
        idle = self.util["idle_s"] + (0.0 if self._open else open_span)
        lanes_valid = self.util["lanes_valid"]
        lanes_padded = self.util["lanes_padded"]
        return {
            "device_busy_s": round(busy, 6),
            "device_idle_s": round(idle, 6),
            "idle_fraction": round(idle / max(1e-9, busy + idle), 6),
            "h2d_s": round(self.spans["h2d_s"], 6),
            # host time of the step's launch (the device time is in a
            # profiler capture, by step name)
            "launch_s": round(self.spans["jit_s"], 6),
            "readback_s": round(self.util["readback_s"], 6),
            "windows": self.spans["windows"],
            "lanes_valid": lanes_valid,
            "lanes_padded": lanes_padded,
            "effective_occupancy": round(
                lanes_valid / max(1, lanes_padded), 6),
            # Commit-path share (ISSUE 16): fraction of finalized windows
            # that were speculative commits — the direct read on how much
            # of the window stream the idle-gap precompute carried.
            # Committed windows finalize through the normal collect path,
            # so they are counted in spans["windows"] like any other.
            "spec_commit_share": round(
                self.counters.get("spec_hit", 0)
                / max(1, self.spans["windows"]), 6),
        }

    # ---- hierarchical formation accounting (ISSUE 14) ---------------------

    def _formation_observe(self, packed_out: np.ndarray) -> None:
        """Fold one collected window's touched-slot row (bucketed result
        row 3; absent on flat 3-row results) into the monotone counters."""
        if packed_out.ndim < 2 or packed_out.shape[0] <= 3:
            return
        self.formation["touched_slots"] += float(packed_out[3, 0])
        self.formation["total_slots"] += float(self.kernels.capacity)
        self.formation["windows"] += 1

    def formation_report(self) -> "dict | None":
        """Hierarchical-formation state (ISSUE 14), served at
        /debug/placement: mode, per-bucket occupancy (the mirror's
        incremental segment counts), the touched-slot fraction over every
        collected bucketed window, and — under sharding — the adaptive
        frontier-K ladder, the currently chosen K, and the bounded move
        ring. None when no bucketed step family is configured. Lock-free:
        host ints/floats read under the GIL, like util_report()."""
        bucketed = getattr(self.kernels, "bucketed", False)
        if not bucketed and not self._frontier_ladder:
            return None
        total = self.formation["total_slots"]
        rep: dict = {
            "mode": "bucketed" if bucketed else "bucket_frontier",
            "buckets": self._formation_segments,
            "windows": self.formation["windows"],
            "touched_slots": self.formation["touched_slots"],
            "total_slots": total,
            "formation_touched_frac": (
                round(self.formation["touched_slots"] / total, 6)
                if total else None),
        }
        seg = self.pool.segment_counts()
        if seg is not None:
            rep["bucket_occupancy"] = seg.tolist()
            rep["peak_bucket_occupancy"] = self.pool.segment_max()
        if self._frontier_ladder:
            rep["frontier_ladder"] = list(self._frontier_ladder)
            rep["frontier_k"] = self._frontier_k_active
            rep["frontier_moves"] = list(self.frontier_moves)
            rep["frontier_steps"] = self.counters.get(
                "bucket_frontier_steps", 0)
            rep["frontier_fallbacks"] = self.counters.get(
                "bucket_frontier_fallback", 0)
        band = self.pool.band_report()
        if band is not None:
            rep["bands"] = band
        return rep

    def frontier_snapshot(self) -> "dict | None":
        """Adaptive frontier-K slice for the telemetry sampler (ISSUE 18
        satellite): the active rung plus the MONOTONE move counter — the
        bounded ``frontier_moves`` ring rotates, so trajectory deltas need
        the counter, not the ring length. None without a ladder. Lock-free
        host-int reads, same contract as util_report()."""
        if not self._frontier_ladder:
            return None
        return {"frontier_k": self._frontier_k_active,
                "frontier_k_moves": self.counters.get("frontier_k_moves", 0)}

    # ---- match-quality & fairness accumulation (ISSUE 8) ------------------

    def _quality_accum_dispatch(self, out: Any, now: float) -> None:
        """Fold one dispatched window's device outputs into the
        device-resident quality accumulator. One extra ASYNC dispatch over
        arrays already on device (the post-step pool columns + the step's
        own result array) — no host scan, no D2H, no sync; the matchlint
        ``perf`` rule covers this function by name."""
        if self._quality is None:
            return
        pool = self._dev_pool
        self._q_dev = self._quality.accum(
            self._q_dev, pool["rating"], pool["enqueue_t"],
            pool["threshold"], out, now - self._rel_base(now))

    def _quality_sync_finalize(self) -> None:
        """Piggyback the accumulator readback on window collection: every
        ``quality_report_every`` finalized windows, queue ONE async D2H of
        the state handles; a later finalize materializes them once the
        transfer has landed. The hot path never pays a synchronous device
        round trip for the quality report — it is at most N windows
        stale."""
        if self._quality is None:
            return
        pending = self._q_sync_handles
        if pending is not None:
            try:
                ready = all(h.is_ready() for h in pending.values())
            except AttributeError:  # pragma: no cover - non-Array types
                ready = True
            if ready:
                self._q_host = {k: np.asarray(v) for k, v in pending.items()}
                self._q_sync_handles = None
        self._q_windows += 1
        if (self._q_windows >= self._q_sync_every
                and self._q_sync_handles is None):
            self._q_windows = 0
            handles = dict(self._q_dev)
            for h in handles.values():
                _copy_async(h)
            self._q_sync_handles = handles

    def _quality_force_sync(self) -> None:
        """Blocking accumulator readback — flush()-time only (flush already
        blocks on every in-flight window), so tests/drain/checkpoint see
        exact totals."""
        if self._quality is None:
            return
        self._q_host = {k: np.asarray(v) for k, v in self._q_dev.items()}
        self._q_sync_handles = None
        self._q_windows = 0

    def quality_report(self) -> dict:
        """Per-rating-bucket quality/wait report over every match this
        engine formed (engine/quality.build_report shape): the last
        device-state snapshot + the host-side fallback accumulator + a
        live delegate's accumulator. Lock-free: host numpy arrays and an
        atomically-swapped snapshot dict only — /metrics may call this off
        the engine lock, like util_report()."""
        arrays = empty_arrays(self._q_spec)
        add_arrays(arrays, self._q_host_accum.arrays)
        add_arrays(arrays, self._q_host)
        d = self._team_delegate
        if d is not None and hasattr(d, "quality_accum"):
            add_arrays(arrays, d.quality_accum.arrays)
        return build_report(arrays, self._q_spec)

    def quality_checkpoint(self) -> "dict[str, np.ndarray] | None":
        """Merged quality-accumulator arrays for a revive/breaker handoff
        (ISSUE 9 satellite): the LAST materialized device snapshot + the
        host fallback accumulator + a live delegate's. Tries a blocking
        device readback first so the handoff is exact; a wedged device —
        the very thing the revive is for — falls back to the last async
        snapshot (at most ``quality_report_every`` windows stale), so
        /debug/quality counters stay monotone across the swap rather than
        resetting to zero."""
        try:
            self._quality_force_sync()
        except Exception:
            logger.warning("quality checkpoint: device readback failed; "
                           "using the last async snapshot")
        arrays = empty_arrays(self._q_spec)
        add_arrays(arrays, self._q_host_accum.arrays)
        add_arrays(arrays, self._q_host)
        d = self._team_delegate
        if d is not None and hasattr(d, "quality_accum"):
            add_arrays(arrays, d.quality_accum.arrays)
        return arrays

    def quality_restore(self, arrays: "dict[str, np.ndarray] | None") -> None:
        """Fold a predecessor engine's quality checkpoint into this
        engine's host accumulator (merged into every quality_report)."""
        if arrays is not None:
            add_arrays(self._q_host_accum.arrays, arrays)

    def inflight(self) -> int:
        """Windows dispatched but not yet finalized (caller-thread view)."""
        return self._open

    def collect_ready(self) -> list[tuple[int, SearchOutcome | ColumnarOutcome]]:
        """Finalize every window whose results have landed (non-blocking;
        FIFO — a ready window behind an unfinished one waits its turn).
        Columnar windows yield ColumnarOutcome; object windows SearchOutcome."""
        done: list[tuple[int, SearchOutcome | ColumnarOutcome]] = []
        with stage("collect", self.spans):
            if self._done_early:
                done, self._done_early = self._done_early, []
            if self._rb_k > 1:
                self._rb_seal_stale()
            while self._pending and self._is_ready(self._pending[0]):
                done.append(self._collect_one(self._pending.popleft()))
        return done

    def _collect_one(self, pending: _Pending
                     ) -> tuple[int, SearchOutcome | ColumnarOutcome]:
        """Materialize and finalize one landed window (``mm.finalize``)."""
        with stage("finalize", token=pending.token):
            self._fetch(pending)
            self._finalize(pending)
        return (pending.token,
                pending.columnar if pending.columnar is not None
                else pending.outcome)

    def flush(self) -> list[tuple[int, SearchOutcome | ColumnarOutcome]]:
        """Block until every in-flight window is collected and finalized."""
        done: list[tuple[int, SearchOutcome | ColumnarOutcome]] = []
        if self._done_early:
            done, self._done_early = self._done_early, []
        if self._rb_k > 1:
            self._rb_seal_stale(force=True)
        while self._pending:
            done.append(self._collect_one(self._pending.popleft()))
        # Every window is collected — refresh the quality snapshot so
        # drain/checkpoint/tests read exact totals (flush blocks anyway).
        self._quality_force_sync()
        return done

    def close(self) -> None:
        """Release engine resources (nothing to stop — single-threaded)."""

    def remove(self, player_id: str) -> SearchRequest | None:
        if self._team_delegate is not None:
            return self._team_delegate.remove(player_id)
        assert self._open == 0, (
            "remove() with windows in flight — collect with flush() first"
        )
        slot = self.pool.slot_of(player_id)
        if slot is None:
            return None
        req = self.pool.request_at(slot)
        self._pool_mutated()
        self.pool.release([slot])
        ev = np.full(self.kernels.evict_bucket, self.kernels.capacity, np.int32)
        ev[0] = slot
        self._dev_pool = self.kernels.evict(self._dev_pool, jnp.asarray(ev))
        return req

    def expire(self, now: float, timeout: float) -> list[SearchRequest]:
        """Vectorized timeout sweep over the columnar mirror: O(expired)
        object materialization, one batched device eviction per
        evict_bucket chunk. The base-class default would materialize a
        SearchRequest per WAITING player per sweep (~10-20 µs each — 1-2 s
        of event-loop-blocking work at the 100k north-star pool)."""
        if self._team_delegate is not None:
            out = self._team_delegate.expire(now, timeout)
            self._maybe_repromote_team(now)  # expiry may drain the last wildcard
            return out
        assert self._open == 0, (
            "expire() with windows in flight — collect with flush() first"
        )
        slots = self.pool.waiting_slots()
        if slots.size == 0:
            return []
        enq = self.pool.m_enqueued[slots]
        expired_slots = slots[(enq != 0.0) & (now - enq > timeout)]
        if expired_slots.size == 0:
            return []  # zero-effect sweep: speculation stays valid
        self._pool_mutated()
        reqs = [self.pool.request_at(int(s)) for s in expired_slots]
        self.pool.release(expired_slots)
        eb = self.kernels.evict_bucket
        for start in range(0, expired_slots.size, eb):
            chunk = expired_slots[start:start + eb]
            ev = np.full(eb, self.kernels.capacity, np.int32)
            ev[:chunk.size] = chunk
            self._dev_pool = self.kernels.evict(self._dev_pool, jnp.asarray(ev))
        return reqs

    def expire_deadlines(self, now: float) -> list[SearchRequest]:
        """Pool-resident deadline expiry (OverloadConfig.deadline_sweep_ms):
        vectorized sweep over the mirror's per-slot ``x-deadline`` column —
        O(expired) object materialization, one batched device eviction per
        evict_bucket chunk, exact to each waiter's own deadline instead of
        the coarse ``request_timeout_s`` granularity. Zero device work is
        spent matching an expired waiter: the sweep runs on host mirror
        columns and the only device call is the eviction scatter."""
        if self._team_delegate is not None:
            out = self._team_delegate.expire_deadlines(now)
            # Expiry may drain the last wildcard — same re-promotion
            # opportunity as the coarse timeout sweep (expire()).
            self._maybe_repromote_team(now)
            return out
        assert self._open == 0, (
            "expire_deadlines() with windows in flight — collect with "
            "flush() first"
        )
        slots = self.pool.waiting_slots()
        if slots.size == 0:
            return []
        dl = self.pool.m_deadline[slots]
        expired_slots = slots[(dl != 0.0) & (now >= dl)]
        if expired_slots.size == 0:
            return []  # zero-effect sweep: speculation stays valid
        self._pool_mutated()
        reqs = [self.pool.request_at(int(s)) for s in expired_slots]
        self.pool.release(expired_slots)
        eb = self.kernels.evict_bucket
        for start in range(0, expired_slots.size, eb):
            chunk = expired_slots[start:start + eb]
            ev = np.full(eb, self.kernels.capacity, np.int32)
            ev[:chunk.size] = chunk
            self._dev_pool = self.kernels.evict(self._dev_pool, jnp.asarray(ev))
        return reqs

    def pool_tier_counts(self, n_tiers: int) -> list[int]:
        if self._team_delegate is not None:
            return self._team_delegate.pool_tier_counts(n_tiers)
        return self.pool.tier_counts(n_tiers)

    def deadline_count(self) -> int:
        if self._team_delegate is not None:
            return self._team_delegate.deadline_count()
        return self.pool.deadline_count()

    def pool_size(self) -> int:
        if self._team_delegate is not None:
            return self._team_delegate.pool_size()
        return len(self.pool)

    def waiting(self) -> list[SearchRequest]:
        if self._team_delegate is not None:
            return self._team_delegate.waiting()
        return self.pool.waiting()

    def restore(self, requests: Sequence[SearchRequest], now: float) -> None:
        """Re-admit a checkpoint without matching (device state is a pure
        function of the mirror — SURVEY.md §5 checkpoint/resume)."""
        if self._team_delegate is not None:
            self._note_wildcards(requests, now)
            self._team_delegate.restore(requests, now)
            return
        if self._maybe_delegate_team(requests, now):  # checkpoint w/ wildcards
            self._team_delegate.restore(requests, now)
            return
        fresh = [r for r in requests if r.id not in self.pool]
        if fresh:
            self._pool_mutated()  # re-admission mutates pool + donates
        bucket = self.buckets[-1]
        for start in range(0, len(fresh), bucket):
            chunk = fresh[start:start + bucket]
            slots = self.pool.allocate(chunk)
            batch = self.pool.batch_arrays(chunk, slots, bucket, self._rel_base(now))
            self._dev_pool = self.kernels.admit_packed(
                self._dev_pool, jnp.asarray(self._pack(batch, 0.0, chunk)))

    # ---- internals --------------------------------------------------------

    def _maybe_delegate_team(self, requests: Sequence[SearchRequest],
                             now: float) -> bool:
        """Wildcard guard for device team queues (one-time switch).

        The device team kernel groups by EXACT (region, mode) code —
        wildcard players would only match other wildcards, silently
        diverging from the oracle's expand-into-every-group semantics
        (teams.py "Grouping semantics"). Rather than let that happen, the
        first wildcard request flips the whole queue to the host oracle:
        waiting players transfer to a CpuEngine delegate (enqueue times
        preserved), the device pool is dropped, and every later call
        routes through the delegate (the same path role/party queues use).
        """
        if not self._team_device or self._team_delegate is not None:
            return False
        from matchmaking_tpu.service.contract import is_wildcard

        if not any(self._device_blocker(r) for r in requests):
            return False
        logger.warning(
            "team queue %r: wildcard region/mode%s request received — the "
            "device kernel groups by exact codes%s, so this queue now "
            "delegates to the host oracle (exact oracle semantics; lower "
            "throughput). %s", self.queue.name,
            " or party" if self._role_device else "",
            " and packs solo units only" if self._role_device else "",
            "Solo requests with pinned region+mode stay on the device path."
            if self._role_device else
            "Pin region+mode on every request to stay on the device path.")
        from matchmaking_tpu.engine.cpu import CpuEngine

        if self._open:
            # Team queues dispatch through the pipelined API since round 4,
            # so a wildcard can arrive with windows in flight. The mirror
            # snapshot below must be post-match (an in-flight window may
            # still match players the mirror holds), so finalize them now;
            # their outcomes are stashed and returned to the caller by the
            # next collect_ready()/flush() under their original tokens.
            self._done_early.extend(self.flush())
        delegate = CpuEngine(self.cfg, self.queue)
        waiting = self.pool.waiting()
        if waiting:
            delegate.restore(waiting, now)
        self._team_delegate = delegate
        self._delegate_last_wc = now
        self.counters["team_delegated"] = (
            self.counters.get("team_delegated", 0) + 1)
        if self.events is not None:
            self.events.append("team_delegated", self.queue.name,
                               f"{len(waiting)} waiting transferred")
        # Device state is now dead weight; drop the HBM arrays and reset
        # the (no-longer-consulted) mirror.
        self._dev_pool = None
        self.pool = PlayerPool(self.kernels.capacity,
                               self.queue.rating_threshold)
        return True

    #: Quiet period (seconds, in the caller's ``now`` domain) a delegated
    #: device team queue must go without seeing a wildcard — in traffic OR
    #: still waiting in the pool — before it is promoted back to the device
    #: path. Bounds promote/demote thrash under alternating traffic: each
    #: transition rebuilds pool state, and the wildcard-presence scan is
    #: O(waiting), so both run at most once per quiet period.
    TEAM_REPROMOTE_QUIET_S = 5.0

    def _fresh_device_pool(self):
        """Empty device-resident pool arrays for the current kernel set —
        the single bootstrap used by __init__ AND re-promotion (sharded
        kernel sets place shards across the mesh; plain ones device_put).
        Kernel sets may declare extra columns beyond POOL_FIELDS (the role
        kernel's role_mask)."""
        init = PlayerPool.empty_device_arrays(self.kernels.capacity)
        for name, dt in getattr(self.kernels, "extra_pool_fields",
                                {}).items():
            init[name] = np.zeros(self.kernels.capacity, dt)
        if getattr(self.kernels, "bucketed", False):
            # Bucketed 1v1 sets carry the device bucket index INSIDE the
            # pool dict (kernels.INDEX_FIELDS) — empty-pool init here;
            # every admit/step/evict maintains it incrementally.
            init.update(self.kernels.init_index_arrays())
        place = getattr(self.kernels, "place_pool", None)
        if place is not None:
            return place(init)
        if self._device is not None:
            # Elastic placement (ISSUE 11): COMMIT the pool to the bound
            # device — every jitted step follows the committed operand, so
            # the whole engine runs where the controller put it.
            return jax.device_put({k: jnp.asarray(v)
                                   for k, v in init.items()}, self._device)
        return jax.device_put({k: jnp.asarray(v) for k, v in init.items()})

    def _pack(self, batch, now_rel: float,
              requests: Sequence[SearchRequest] = ()) -> np.ndarray:
        """pack_batch plus, for role kernels, the role_mask row (inserted
        before the trailing ``now`` row; padding lanes carry mask 0 —
        invalid either way)."""
        packed = pack_batch(batch, now_rel)
        if not getattr(self.kernels, "is_role", False):
            return packed
        masks = np.zeros((1, packed.shape[1]), np.float32)
        for j, req in enumerate(requests):
            masks[0, j] = self.kernels.mask_of(req.roles)
        return np.concatenate([packed[:8], masks, packed[8:]])

    def _device_blocker(self, req: SearchRequest) -> bool:
        """True if this request cannot be served by the device kernel:
        region/mode wildcards (exact-group semantics) for every team-family
        queue, plus parties on role queues (the device role kernel packs
        solo units only)."""
        from matchmaking_tpu.service.contract import is_wildcard

        return is_wildcard(req) or (self._role_device and req.party_size > 1)

    def _note_wildcards(self, requests: Sequence[SearchRequest],
                        now: float) -> None:
        """While delegated: record device-blocking arrivals (wildcards /
        role-queue parties — resets the quiet period that gates
        re-promotion)."""
        if any(self._device_blocker(r) for r in requests):
            self._delegate_last_wc = now

    def _maybe_repromote_team(self, now: float) -> bool:
        """Promote a wildcard-delegated device team queue back to the
        device path once the delegate has drained of wildcards (the inverse
        of _maybe_delegate_team — without it one stray wildcard downgrades
        a 100k-capable queue to the O(n·scan) oracle forever, round-4
        verdict weak #5). Conditions: quiet period elapsed since the last
        wildcard arrival AND an authoritative scan finds no wildcard still
        waiting (a missed one would silently break the device kernel's
        exact-group semantics). Waiting players transfer back with enqueue
        times preserved; returns True if the queue is now on device."""
        d = self._team_delegate
        if d is None or not self._team_device:
            return False
        if now - self._delegate_last_wc < self.TEAM_REPROMOTE_QUIET_S:
            return False
        # The oracle pool is unbounded; the device pool is not. A promotion
        # that cannot re-admit everyone would drop players (restore has no
        # partial-admission path), and one at EXACTLY-full capacity leaves
        # zero free slots — the next arrival batch then crashes restore
        # into the revive path (ADVICE round-5 #4). Require headroom for
        # one arrival batch (clamped for tiny test pools) before promoting;
        # otherwise stay delegated and re-check after the next quiet period.
        headroom = min(self.buckets[-1], self.kernels.capacity // 4)
        if d.pool_size() > self.kernels.capacity - headroom:
            self._delegate_last_wc = now
            return False
        if d.has_wildcards() or (self._role_device and d.has_parties()):
            # Still trapped: restart the quiet period so the O(n) scan
            # runs at most once per period.
            self._delegate_last_wc = now
            return False
        waiting = d.waiting()
        # The delegate's quality accounting must survive re-promotion — its
        # matches were this queue's matches.
        if hasattr(d, "quality_accum"):
            add_arrays(self._q_host_accum.arrays, d.quality_accum.arrays)
        self._team_delegate = None
        self._delegate_last_wc = float("-inf")
        self.pool = PlayerPool(self.kernels.capacity,
                               self.queue.rating_threshold,
                               band_edges=self._band_edges)
        self._dev_pool = self._fresh_device_pool()
        if waiting:
            self.restore(waiting, now)
        self.counters["team_repromoted"] = (
            self.counters.get("team_repromoted", 0) + 1)
        if self.events is not None:
            self.events.append("team_repromoted", self.queue.name,
                               f"{len(waiting)} waiting transferred")
        logger.info(
            "team queue %r: wildcard pool drained — promoted back to the "
            "device path (%d waiting players transferred)",
            self.queue.name, len(waiting))
        return True

    def warmup(self) -> None:
        """Compile every executable the serving path can reach — both step
        variants (see _step_fn) per batch bucket, plus the admit (restore)
        and evict (expire) entries — using all-padding windows: no valid
        lane, so nothing is admitted, matched, or evicted and pool state is
        semantically unchanged. Called at app start under
        ``EngineConfig.warm_start`` so no first-of-its-kind window pays an
        XLA compile inline on the serving path."""
        if self._team_delegate is not None:
            return
        assert self._open == 0, "warmup() with windows in flight"
        self._pool_mutated()  # warmup steps donate _dev_pool buffers
        variants = [self.kernels.search_step_packed]
        names = ["search_step_packed_nofilter",
                 "search_step_packed_rescan",
                 "search_step_packed_ring"]
        if self.cfg.engine.spec_formation:
            # The non-donated speculative twin is its own executable
            # (aliasing differs) — warm it only when speculation can run.
            names.append("search_step_packed_spec")
        for name in names:
            fn = getattr(self.kernels, name, None)
            if fn is not None:
                variants.append(fn)
        # Adaptive frontier ladder (ISSUE 14): every rung the per-window
        # pick can reach is a distinct executable.
        for k in self._frontier_ladder:
            variants.append(self.kernels.bucket_step(k))
        for bucket in self.buckets:
            batch = self.pool.batch_arrays([], [], bucket)
            packed = jnp.asarray(self._pack(batch, 0.0))
            for fn in variants:
                self._dev_pool, out = fn(self._dev_pool, packed)
                jax.block_until_ready(out)
            if self._quality is not None:
                # The quality accumulator compiles once per result shape
                # (bucket) too — an all-padding window adds nothing, so
                # warming it here is state-free.
                self._q_dev = self._quality.accum(
                    self._q_dev, self._dev_pool["rating"],
                    self._dev_pool["enqueue_t"],
                    self._dev_pool["threshold"], out, 0.0)
            admit = getattr(self.kernels, "admit_packed", None)
            if admit is not None:
                self._dev_pool = admit(self._dev_pool,
                                       jnp.asarray(self._pack(batch, 0.0)))
        evict = getattr(self.kernels, "evict", None)
        if evict is not None:
            ev = jnp.full(self.kernels.evict_bucket, self.kernels.capacity,
                          jnp.int32)
            self._dev_pool = evict(self._dev_pool, ev)
        jax.block_until_ready(self._dev_pool)

    def probe(self) -> None:
        """Half-open breaker probe: one end-to-end no-op device step
        (smallest bucket, all padding lanes — nothing admitted, matched, or
        evicted), blocked until the result lands. Exercises compile,
        dispatch, device execution and D2H for the hot step family; raises
        whatever the device raises. Scriptable via the chaos hook's probe
        stream, so fault soaks can pin probe-failure backoff."""
        if self.chaos_hook is not None:
            self.chaos_hook.on_probe()
        self._pool_mutated()  # the probe step donates _dev_pool buffers
        batch = self.pool.batch_arrays([], [], self.buckets[0])
        self._dev_pool, out = self._step_fn(batch)(
            self._dev_pool, jnp.asarray(self._pack(batch, 0.0)))
        jax.block_until_ready(out)

    def heartbeat(self, now: float) -> bool:
        """Health-timer tick: the idle re-promotion path for a
        wildcard-delegated team/role queue (ADVICE round-5 #3 — with
        ``rescan_interval_s=0`` and no expiry sweep, nothing else notices
        the wildcards draining under zero traffic). Bucketed 1v1 engines
        (``heartbeat_housekeeping``) additionally re-tighten the device
        bucket index here (one O(P) jitted scan, async dispatch):
        incremental bounds only WIDEN between rebuilds, so without this
        tick a drifting rating distribution degrades every window to the
        dense fallback with no recovery. Safe with windows in flight:
        ``_dev_pool`` holds the newest post-dispatch handles — nothing
        but the next step consumes them — so donating them to the
        rebuild just chains it behind the in-flight steps on device."""
        if self._team_delegate is not None:
            return self._maybe_repromote_team(now)
        if (self._dev_pool is not None
                and getattr(self.kernels, "bucketed", False)):
            self._pool_mutated()  # rebuild donates _dev_pool buffers
            self._dev_pool = self.kernels.index_rebuild(self._dev_pool)
        return False

    def _step_fn(self, batch):
        self._last_step_bucketed = getattr(self.kernels, "bucketed", False)
        return self._step_fn_pick(batch)

    def _step_fn_pick(self, batch):
        """Pick the compiled step variant for this window: the all-ANY
        variant (region/mode mask math compiled out — bit-exact when no
        window lane carries a filter, see kernels._score_block) or the full
        one. Host check is O(B) on the padded batch; padding lanes hold
        code 0 so they never force the filtered variant. Team/sharded
        kernel sets don't ship the variant — getattr falls back.

        Sharded team/role kernel sets may additionally ship the RING-scaled
        step (EngineConfig.team_ring_k): picked whenever the mirror's
        occupancy — an upper bound on every shard's active rows, since the
        mirror only releases slots after device eviction — fits the
        per-shard frontier, which is exactly the precondition under which
        the ring step is bit-identical to the replicated fallback. The
        choice is recorded in counters (team_ring_steps /
        team_ring_fallback) so a mis-sized frontier is visible, not silent."""
        ring = getattr(self.kernels, "search_step_packed_ring", None)
        if ring is not None:
            if len(self.pool) <= self.kernels.frontier_k:
                self.counters["team_ring_steps"] = (
                    self.counters.get("team_ring_steps", 0) + 1)
                return ring
            self.counters["team_ring_fallback"] = (
                self.counters.get("team_ring_fallback", 0) + 1)
        if self._frontier_ladder:
            # Sharded per-bucket frontier (ISSUE 14): pick the smallest
            # ladder K holding the observed peak per-bucket occupancy —
            # the mirror's segment counts are a conservative superset of
            # device-active (slots release only at finalize), which is
            # exactly the no-overflow precondition for bit-exactness.
            # Above the ceiling, fall back to the dense sharded step
            # (counted, never silent).
            occ = self.pool.segment_max()
            k = next((r for r in self._frontier_ladder if r >= occ), None)
            if k is not None:
                if k != self._frontier_k_active:
                    self.counters["frontier_k_moves"] = (
                        self.counters.get("frontier_k_moves", 0) + 1)
                    self.frontier_moves.append({
                        "t": time.time(), "from": self._frontier_k_active,
                        "to": k, "peak_bucket_occupancy": occ})
                    if len(self.frontier_moves) > 64:
                        del self.frontier_moves[:-64]
                    self._frontier_k_active = k
                self.counters["bucket_frontier_steps"] = (
                    self.counters.get("bucket_frontier_steps", 0) + 1)
                self._last_step_bucketed = True
                return self.kernels.bucket_step(k)
            self.counters["bucket_frontier_fallback"] = (
                self.counters.get("bucket_frontier_fallback", 0) + 1)
        nf = getattr(self.kernels, "search_step_packed_nofilter", None)
        if nf is not None and not batch.region.any() and not batch.mode.any():
            return nf
        return self.kernels.search_step_packed

    def _bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    def _rel_base(self, now: float) -> float:
        if self._t0 is None:
            self._t0 = now
        return self._t0

    def _dispatch(self, window: list[SearchRequest], now: float,
                  pending: _Pending) -> None:
        """Admit + launch the device step for one window; no waiting."""
        if not window:
            return
        self._chaos_step()
        # Admit only what fits; reject the overflow (the reference has no
        # capacity cap — ETS grows — so partial admission keeps us closest).
        free = self.pool.free_count()
        if len(window) > free:
            for req in window[free:]:
                pending.outcome.rejected.append((req, "pool_full"))
            window = window[:free]
            if not window:
                return
        slots = self.pool.allocate(window)
        bucket = self._bucket_for(len(window))
        t0 = self._rel_base(now)
        batch = self.pool.batch_arrays(window, slots, bucket, t0)
        packed_dev = jnp.asarray(self._pack(batch, now - t0, window))
        pending.marks.append(("h2d", time.time()))
        self._dev_pool, out = self._step_fn(batch)(
            self._dev_pool, packed_dev
        )
        pending.marks.append(("formation_bucketed"
                              if self._last_step_bucketed
                              else "device_step", time.time()))
        self._quality_accum_dispatch(out, now)
        self.util["lanes_valid"] += len(window)
        self.util["lanes_padded"] += bucket
        pending.chunks.append((list(window), (out,), now))

    def _finalize(self, pending: _Pending) -> None:
        """Map one window's collected results back to requests. Runs on the
        caller thread — the mirror stays single-writer.

        A collector-thread failure (device reset/OOM) must NOT raise here:
        raising mid-collect would drop outcomes already finalized in the
        same call (their players are released from the mirror — the Match
        would vanish). Instead the window's requests are reported as queued
        (true: the mirror still holds them, and recovery restores from the
        mirror) and the error is parked on ``device_error`` for the caller
        to check — sync ``search()`` re-raises it so the service's revive
        path fires."""
        self._open -= 1
        if self._open == 0:
            now_pc = time.perf_counter()
            self.util["busy_s"] += max(0.0, now_pc - self._util_mark)
            self._util_mark = now_pc
        # Quality-accumulator readback rides the collect path (async D2H
        # queued at a window cadence, materialized when it lands).
        self._quality_sync_finalize()
        if pending.created:
            self.spans["windows"] += 1
            self.spans["turnaround_s"] += time.perf_counter() - pending.created
        if self._rb_k > 1 and pending.chunks:
            # Grouped readback: the seal (one stacked D2H for k windows)
            # happened whenever the group filled or went stale — pull the
            # latest member group's seal time in as this window's mark.
            seal = max((h.group.sealed_at
                        for c in pending.chunks for h in c[1]
                        if isinstance(h, _GroupSlot)), default=0.0)
            if seal:
                pending.marks.append(("readback_seal", seal))
        t_collect = time.time()
        pending.marks.append(("collect", t_collect))
        # Readback split: seal (D2H queued) → collect is the transfer +
        # poll span; one monotone counter alongside the spans h2d/jit split.
        seal_t = next((t for name, t in reversed(pending.marks)
                       if name == "readback_seal"), None)
        if seal_t is not None:
            self.util["readback_s"] += max(0.0, t_collect - seal_t)
        self.window_marks[pending.token] = pending.marks
        while len(self.window_marks) > 512:
            # Unconsumed entries (sync callers, crashed windows) must not
            # accumulate forever; oldest-first eviction, insertion-ordered.
            self.window_marks.pop(next(iter(self.window_marks)))
        if pending.error is not None:
            self.device_error = pending.error
            self.failed_tokens.add(pending.token)
            for payload, _, _ in pending.chunks:
                if pending.columnar is not None:
                    cols, _slots = payload
                    pending.columnar.q_ids = np.concatenate(
                        [pending.columnar.q_ids, cols.ids])
                else:
                    pending.outcome.queued.extend(payload)
            return
        if pending.columnar is not None:
            self._finalize_columnar(pending)
            return
        out = pending.outcome
        if self._team_device:
            self._finalize_team(pending)
            return
        acc: list[tuple[float, float, float, float]] | None = (
            [] if self._quality is None else None)
        for (window, _, now), (packed_out,) in zip(
                pending.chunks, pending.raw or ()):
            self._formation_observe(packed_out)
            q_slot = packed_out[0].astype(np.int32)
            c_slot = packed_out[1].astype(np.int32)
            dist = packed_out[2]
            P = self.kernels.capacity
            matched_ids: set[str] = set()
            hit = q_slot < P
            if hit.any():
                qs_l = q_slot[hit].tolist()
                cs_l = c_slot[hit].tolist()
                d_l = dist[hit].tolist()
                for qs, cs, d in zip(qs_l, cs_l, d_l):
                    req_q = self.pool.request_at(qs)  # matchlint: ignore[perf] object 1v1 path — per-match materialization is its contract; the columnar hot path is scan-free
                    req_c = self.pool.request_at(cs)
                    matched_ids.add(req_q.id)
                    matched_ids.add(req_c.id)
                    # Quality from the pair's effective limits at match time
                    # (host has both requests; same formula as the oracle).
                    qual = scoring.quality(
                        d,
                        self.effective_threshold(req_q, now),
                        self.effective_threshold(req_c, now),
                    )
                    out.matches.append(
                        Match(match_id=new_match_id(),
                              teams=((req_q,), (req_c,)), quality=qual)
                    )
                    if acc is not None:
                        # Host quality fallback (no device accumulator on
                        # this kernel set): one sample per matched player.
                        for r in (req_q, req_c):
                            w = (max(0.0, now - r.enqueued_at)
                                 if r.enqueued_at else 0.0)
                            acc.append((r.rating, qual, w, d))
                self._pool_mutated()
                self.pool.release(qs_l)
                self.pool.release(cs_l)
            for req in window:
                if req.id not in matched_ids:
                    out.queued.append(req)
        if acc:
            self._q_host_accum.observe(
                rating=[a[0] for a in acc], quality=[a[1] for a in acc],
                wait_s=[a[2] for a in acc], spread=[a[3] for a in acc])

    def _eff_vec(self, thr: np.ndarray, enqueued: np.ndarray, now: float) -> np.ndarray:
        """Vectorized effective_threshold over mirror columns."""
        if self.queue.widen_per_sec <= 0.0:
            return thr
        waited = np.maximum(0.0, now - enqueued)
        return np.minimum(self.queue.max_threshold,
                          thr + self.queue.widen_per_sec * waited).astype(np.float32)

    def _finalize_columnar(self, pending: _Pending) -> None:
        """Columnar finalize: everything vectorized except match-id minting.
        Same semantics/formulas as the object path (quality from both sides'
        effective thresholds at match time)."""
        out = pending.columnar
        assert out is not None
        pool = self.pool
        for (payload, _, now), (packed_out,) in zip(
                pending.chunks, pending.raw or ()):
            cols, slots = payload
            self._formation_observe(packed_out)
            q_slot = packed_out[0].astype(np.int32)
            c_slot = packed_out[1].astype(np.int32)
            dist = packed_out[2]
            P = self.kernels.capacity
            hit = q_slot < P
            qs, cs, d = q_slot[hit], c_slot[hit], dist[hit]
            if qs.size:
                ids_a, ids_b = pool.m_id[qs], pool.m_id[cs]
                eff_a = self._eff_vec(pool.m_threshold[qs], pool.m_enqueued[qs], now)
                eff_b = self._eff_vec(pool.m_threshold[cs], pool.m_enqueued[cs], now)
                limit = np.minimum(eff_a, eff_b)
                quality = np.where(
                    limit > 0.0,
                    np.clip(1.0 - d / np.maximum(limit, 1e-30), 0.0, 1.0),
                    0.0,
                ).astype(np.float32)
                match_ids = new_match_ids(qs.size)
                enq_a, enq_b = pool.m_enqueued[qs], pool.m_enqueued[cs]
                # Engine-observed wait-at-match (ISSUE 8): this chunk's
                # DISPATCH time minus the slot's enqueue stamp — the number
                # the waited_ms response field and the quality/fairness
                # accounting carry (latency_ms additionally counts collect
                # + publish queueing and is stamped later, at publish).
                wait_a = np.where(enq_a != 0.0,
                                  np.maximum(0.0, now - enq_a), 0.0)
                wait_b = np.where(enq_b != 0.0,
                                  np.maximum(0.0, now - enq_b), 0.0)
                out.m_id_a = np.concatenate([out.m_id_a, ids_a])
                out.m_id_b = np.concatenate([out.m_id_b, ids_b])
                out.m_match_id = np.concatenate([out.m_match_id, match_ids])
                out.m_dist = np.concatenate([out.m_dist, d])
                out.m_quality = np.concatenate([out.m_quality, quality])
                out.m_reply_a = np.concatenate([out.m_reply_a, pool.m_reply[qs]])
                out.m_reply_b = np.concatenate([out.m_reply_b, pool.m_reply[cs]])
                out.m_corr_a = np.concatenate([out.m_corr_a, pool.m_corr[qs]])
                out.m_corr_b = np.concatenate([out.m_corr_b, pool.m_corr[cs]])
                out.m_enq_a = np.concatenate([out.m_enq_a, enq_a])
                out.m_enq_b = np.concatenate([out.m_enq_b, enq_b])
                out.m_wait_a = np.concatenate([out.m_wait_a, wait_a])
                out.m_wait_b = np.concatenate([out.m_wait_b, wait_b])
                out.m_tier_a = np.concatenate([out.m_tier_a, pool.m_tier[qs]])
                out.m_tier_b = np.concatenate([out.m_tier_b, pool.m_tier[cs]])
                if self._quality is None:
                    # Host quality fallback (sharded/no-device-accum kernel
                    # sets): the exact vectorized equivalent of the device
                    # scatter-add, over the same mirror columns.
                    self._q_host_accum.observe(
                        rating=np.concatenate([pool.m_rating[qs],
                                               pool.m_rating[cs]]),
                        quality=np.concatenate([quality, quality]),
                        wait_s=np.concatenate([wait_a, wait_b]),
                        spread=np.concatenate([d, d]))
                matched = np.concatenate([qs, cs])
                self._pool_mutated()
                pool.release(matched)
                queued_ids = cols.ids[~np.isin(slots, matched)]
            else:
                queued_ids = cols.ids
            out.q_ids = np.concatenate([out.q_ids, queued_ids])
        self.spans["matches"] += out.n_matches

    def _finalize_team(self, pending: _Pending) -> None:
        """Map team-kernel results (slots M×need, spread, limit) back to
        requests and split each window into two teams: snake split for plain
        team queues (scoring.snake_split — the device kernel validated the
        sum constraint with the same signed pattern, tie-order invariant),
        or the kernel's own cover split (role queues append a bitmask row —
        bit i set ⇔ rating-ordered member i is on team A, chosen by the
        oracle's base/swap-repair order in role_kernels._cover_split)."""
        out = pending.outcome
        need = self.kernels.need
        is_role = getattr(self.kernels, "is_role", False)
        for (window, _, now), (packed_out,) in zip(
                pending.chunks, pending.raw or ()):
            slots = packed_out[:need].T.astype(np.int32)
            spread = packed_out[need]
            limit = packed_out[need + 1]
            split = (packed_out[need + 2].astype(np.int32)
                     if is_role else None)
            P = self.kernels.capacity
            matched_ids: set[str] = set()
            hit = slots[:, 0] < P
            for m in np.nonzero(hit)[0].tolist():
                row = slots[m].tolist()
                # matchlint: ignore[perf] device team path — O(team) member materialization per formed match is its contract
                members = [self.pool.request_at(s) for s in row]
                matched_ids.update(r.id for r in members)
                if is_role:
                    bits = int(split[m])
                    team_a = tuple(members[i] for i in range(need)
                                   if bits >> i & 1)
                    team_b = tuple(members[i] for i in range(need)
                                   if not bits >> i & 1)
                else:
                    team_a, team_b = scoring.snake_split(members)
                thr = float(limit[m])
                qual = max(0.0, 1.0 - float(spread[m]) / thr) if thr > 0 else 0.0
                out.matches.append(
                    Match(match_id=new_match_id(),
                          teams=(tuple(team_a), tuple(team_b)), quality=qual)
                )
                self._q_host_accum.observe(
                    rating=[r.rating for r in members],
                    quality=qual,
                    wait_s=[(max(0.0, now - r.enqueued_at)
                             if r.enqueued_at else 0.0) for r in members],
                    spread=float(spread[m]))
                self.pool.release(row)
            for req in window:
                if req.id not in matched_ids:
                    out.queued.append(req)


def _merge_outcomes(into: SearchOutcome, other: SearchOutcome) -> None:
    into.matches.extend(other.matches)
    into.queued.extend(other.queued)
    into.timed_out.extend(other.timed_out)
    into.rejected.extend(other.rejected)


def _as_jnp(batch: BatchArrays) -> dict[str, jnp.ndarray]:
    return {
        "slot": jnp.asarray(batch.slot),
        "rating": jnp.asarray(batch.rating),
        "rd": jnp.asarray(batch.rd),
        "region": jnp.asarray(batch.region),
        "mode": jnp.asarray(batch.mode),
        "threshold": jnp.asarray(batch.threshold),
        "enqueue_t": jnp.asarray(batch.enqueue_t),
        "valid": jnp.asarray(batch.valid),
    }
