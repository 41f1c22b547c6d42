"""The player pool: a structure-of-arrays resident in device HBM.

This is the TPU-native replacement for the reference's ETS table (SURVEY.md
§2 C8): where the reference keeps queued players as rows in an in-memory BEAM
table scanned per request, we keep them as fixed-capacity parallel arrays in
HBM so a whole request window scores against every waiting player in one
vectorized kernel.

Design (SURVEY.md §7 step 1):

- **Fixed capacity P, static shapes.** Slots are recycled through a host-side
  free list; XLA never sees a dynamic pool size (recompile-free hot path).
- **Single-writer slot allocator on the host** (SURVEY.md §5 "Race
  detection"): all admissions/evictions flow through one `PlayerPool` object;
  the device arrays are updated only by the jitted step functions it calls.
- **Authoritative host mirror, columnar.** The host keeps every waiting
  request as parallel numpy columns (slot-indexed). Device state is a pure
  function of the mirror, which makes the mirror the checkpoint: on sidecar
  death, re-admit the mirror (SURVEY.md §5 "Checkpoint/resume").
  `SearchRequest` objects are materialized lazily (only for matched slots
  that need response objects) — the object layer costs ~10-20 µs/request,
  which would dwarf the ~1 ms device kernel at 10^5 requests/sec.
- **Vectorized free list**: a numpy stack with a head cursor; allocating a
  window is one slice, releasing is one slice store — no per-request Python.
- **String interning.** Wire-level region/game-mode strings are interned to
  int32 codes (0 = wildcard) so filter masks are integer compares on device.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import numpy as np

from matchmaking_tpu.service.contract import ANY, RequestColumns, SearchRequest

# Field definitions for the device SoA. Kept in one place so the kernels, the
# pool, and the sharded engine agree on array layout.
POOL_FIELDS: tuple[tuple[str, np.dtype], ...] = (
    ("rating", np.float32),
    ("rd", np.float32),          # Glicko-2 rating deviation
    ("region", np.int32),        # interned; 0 = ANY
    ("mode", np.int32),          # interned; 0 = ANY
    ("threshold", np.float32),   # base rating_threshold for this player
    ("enqueue_t", np.float32),   # seconds; widening input
    ("active", np.bool_),
)


class Interner:
    """str → dense int32 codes; code 0 is reserved for the ANY wildcard."""

    def __init__(self) -> None:
        self._codes: dict[str, int] = {ANY: 0}
        self._names: list[str] = [ANY]

    def code(self, name: str) -> int:
        c = self._codes.get(name)
        if c is None:
            c = len(self._names)
            self._codes[name] = c
            self._names.append(name)
        return c

    def name(self, code: int) -> str:
        return self._names[code]


class PoolFullError(RuntimeError):
    pass


@dataclass
class BatchArrays:
    """A padded request window, ready for the device (host numpy; the engine
    moves it with the step call). ``valid`` masks padding lanes."""

    slot: np.ndarray      # i32[B] — pre-allocated pool slot per request
    rating: np.ndarray    # f32[B]
    rd: np.ndarray        # f32[B]
    region: np.ndarray    # i32[B]
    mode: np.ndarray      # i32[B]
    threshold: np.ndarray # f32[B]
    enqueue_t: np.ndarray # f32[B]
    valid: np.ndarray     # bool[B]


class PlayerPool:
    """Host-side owner of the pool: slot allocator + authoritative mirror.

    The device arrays themselves live with the engine (they are jitted-step
    carry state); this class owns which slot means which player.
    """

    def __init__(self, capacity: int, default_threshold: float,
                 band_edges: Sequence[float] | None = None,
                 segments: int = 0):
        self.capacity = int(capacity)
        self.default_threshold = float(default_threshold)
        #: Incremental per-SEGMENT occupancy (ISSUE 14): the engine passes
        #: ``segments`` = its device block count, and every allocate/release
        #: keeps a per-block occupancy histogram by SLOT RANGE — the host
        #: twin of the device bucket index's counts, and the O(segments)
        #: gate the sharded bucket-frontier step checks per window (max
        #: per-bucket occupancy must fit the frontier K). 0 = untracked.
        self._segments = max(0, int(segments))
        self._seg_size = (self.capacity // self._segments
                          if self._segments else 0)
        self._seg_n = (np.zeros(self._segments, np.int64)
                       if self._segments else None)
        # Vectorized free list: pop from the END (head), so initial pops
        # yield slot 0, 1, 2, ... (kept for slot-order determinism in tests).
        self._free = np.arange(self.capacity - 1, -1, -1, dtype=np.int32)
        self._head = self.capacity  # number of free slots
        # Rating-banded mode: slots partitioned into contiguous bands, one
        # free stack per band; a player's slot comes from the band holding
        # its rating (spilling outward to the nearest non-full band). Keeps
        # each pool BLOCK's live rating interval narrow, which is what makes
        # the kernels' bit-exact block pruning effective (kernels.py
        # "_search_step_pruned"). ``band_edges`` are the len(R-1) ascending
        # rating boundaries; band b owns slots [b·P/R, (b+1)·P/R).
        self._band_edges: np.ndarray | None = None
        if band_edges is not None and len(band_edges) > 0:
            edges = np.asarray(band_edges, np.float64)
            if not np.all(np.diff(edges) > 0):
                raise ValueError("band_edges must be strictly ascending")
            r = edges.size + 1
            self._band_edges = edges
            self._band_start = np.array(
                [b * self.capacity // r for b in range(r + 1)], np.int64)
            # Stacks store descending so pops yield ascending slot order.
            self._band_free = [
                np.arange(self._band_start[b + 1] - 1,
                          self._band_start[b] - 1, -1, dtype=np.int32)
                for b in range(r)
            ]
            self._band_head = np.array(
                [s.size for s in self._band_free], np.int64)
        self._slot_of: dict[str, int] = {}                   # player id → slot
        # Columnar mirror (slot-indexed).
        self.m_id = np.full(self.capacity, None, dtype=object)
        self.m_rating = np.zeros(self.capacity, np.float32)
        self.m_rd = np.zeros(self.capacity, np.float32)
        self.m_region = np.zeros(self.capacity, np.int32)
        self.m_mode = np.zeros(self.capacity, np.int32)
        self.m_threshold = np.zeros(self.capacity, np.float32)  # resolved (no NaN)
        self.m_thr_override = np.zeros(self.capacity, np.bool_)
        self.m_enqueued = np.zeros(self.capacity, np.float64)
        self.m_reply = np.full(self.capacity, "", dtype=object)
        self.m_corr = np.full(self.capacity, "", dtype=object)
        #: QoS priority tier per slot (service/overload.py; 0 = untiered
        #: default) and absolute x-deadline per slot (wall-clock seconds;
        #: 0.0 = none). Host-mirror-only columns — the device kernels never
        #: see them: priority ordering happens at admission/window-cut time
        #: and expiry is a host sweep + batched device eviction.
        self.m_tier = np.zeros(self.capacity, np.int32)
        self.m_deadline = np.zeros(self.capacity, np.float64)
        #: Incremental per-tier occupancy counts (tier → waiting players):
        #: admission's partition check reads this per delivery, and an
        #: O(pool) bincount per delivery would put a 100k scan on the
        #: ingress hot path.
        self._tier_n: dict[int, int] = {}
        #: Waiting players carrying a nonzero deadline — the O(1) gate the
        #: sweep loop checks per tick so deadline-less traffic never pays
        #: a pipeline drain for an empty sweep.
        self._deadline_n = 0
        # Declared role sets (config #5 device path); None for the columnar
        # 1v1 ingress, which never carries roles.
        self.m_roles = np.full(self.capacity, None, dtype=object)
        self.regions = Interner()
        self.modes = Interner()

    # ---- introspection ----------------------------------------------------

    def __len__(self) -> int:
        return len(self._slot_of)

    def free_count(self) -> int:
        return self._head

    def __contains__(self, player_id: str) -> bool:
        return player_id in self._slot_of

    def slot_of(self, player_id: str) -> int | None:
        return self._slot_of.get(player_id)

    def request_at(self, slot: int) -> SearchRequest:
        """Materialize the SearchRequest for one occupied slot (lazy — the
        mirror is columnar; objects are built only where needed)."""
        return SearchRequest(
            id=self.m_id[slot],
            rating=float(self.m_rating[slot]),
            rating_deviation=float(self.m_rd[slot]),
            game_mode=self.modes.name(int(self.m_mode[slot])),
            region=self.regions.name(int(self.m_region[slot])),
            rating_threshold=(float(self.m_threshold[slot])
                              if self.m_thr_override[slot] else None),
            roles=tuple(self.m_roles[slot] or ()),
            reply_to=self.m_reply[slot],
            correlation_id=self.m_corr[slot],
            enqueued_at=float(self.m_enqueued[slot]),
            tier=int(self.m_tier[slot]),
            deadline_at=float(self.m_deadline[slot]),
        )

    def waiting(self) -> list[SearchRequest]:
        """Checkpoint payload: every waiting request (insertion-time data)."""
        return [self.request_at(s) for s in self._slot_of.values()]

    def waiting_slots(self) -> np.ndarray:
        return np.fromiter(self._slot_of.values(), np.int32, len(self._slot_of))

    def deadline_count(self) -> int:
        """Waiting players with a stamped deadline (O(1); incremental)."""
        return self._deadline_n

    def segment_counts(self) -> "np.ndarray | None":
        """Per-segment (= device pool block / rating bucket) occupancy,
        maintained incrementally by allocate/release — O(segments) read,
        never a pool scan. None when segment tracking is off."""
        return self._seg_n

    def segment_max(self) -> int:
        """Peak per-segment occupancy (the sharded bucket-frontier gate's
        one number). 0 when untracked or empty."""
        if self._seg_n is None:
            return 0
        return int(self._seg_n.max(initial=0))

    def _seg_add(self, slots: np.ndarray, sign: int) -> None:
        if self._seg_n is None or slots.size == 0:
            return
        seg = np.minimum(slots // self._seg_size, self._segments - 1)
        np.add.at(self._seg_n, seg, sign)

    def band_report(self) -> "dict | None":
        """Host allocator state of the rating-banded free lists (ISSUE 14
        'free-slot heads'): per-band free-slot head positions + band sizes.
        None when banding is off."""
        if self._band_edges is None:
            return None
        return {
            "bands": len(self._band_free),
            "free_heads": [int(h) for h in self._band_head],
            "band_sizes": [int(self._band_start[b + 1] - self._band_start[b])
                           for b in range(len(self._band_free))],
            "edges": [float(e) for e in self._band_edges],
        }

    def tier_counts(self, n_tiers: int) -> list[int]:
        """Waiting players per QoS tier (len ``n_tiers``; out-of-range
        tiers are clamped into the last bucket). O(n_tiers) — maintained
        incrementally by allocate/release, never scanned."""
        out = [0] * max(1, n_tiers)
        for t, n in self._tier_n.items():
            out[min(max(t, 0), len(out) - 1)] += n
        return out

    # ---- mutation (single writer) -----------------------------------------

    def allocate_columns(self, cols: RequestColumns) -> np.ndarray:
        """Assign slots to a columnar window and record it in the mirror.
        All stores are vectorized; the id checks and the id→slot dict update
        are the only per-row work (~50 ns/id).

        Ids must be unique within the window and absent from the pool
        (engines dedupe before allocating); violations raise BEFORE any
        mutation, so the pool state is never half-updated."""
        n = len(cols)
        if n > self._head:
            raise PoolFullError(
                f"pool exhausted: {n} requested, {self._head} free "
                f"(capacity {self.capacity})"
            )
        ids = cols.ids.tolist()
        if len(set(ids)) != n:
            raise ValueError("duplicate player id in window")
        if any(pid in self._slot_of for pid in ids):
            raise ValueError("player already in pool")
        if self._band_edges is not None:
            slots = self._take_banded(np.asarray(cols.rating, np.float64), n)
        else:
            slots = self._free[self._head - n:self._head][::-1].copy()
        self._head -= n
        self.m_id[slots] = cols.ids
        self.m_rating[slots] = cols.rating
        self.m_rd[slots] = cols.rd
        self.m_region[slots] = cols.region
        self.m_mode[slots] = cols.mode
        override = ~np.isnan(cols.threshold)
        self.m_thr_override[slots] = override
        self.m_threshold[slots] = np.where(override, cols.threshold,
                                           self.default_threshold)
        self.m_enqueued[slots] = cols.enqueued_at
        # Missing transport columns must CLEAR the slots (a recycled slot
        # would otherwise leak the previous occupant's reply queue and route
        # a response to an unrelated player).
        self.m_reply[slots] = "" if cols.reply_to is None else cols.reply_to
        self.m_corr[slots] = ("" if cols.correlation_id is None
                              else cols.correlation_id)
        # QoS columns: unconditional stores (missing columns must clear a
        # recycled slot, or a stale tier/deadline would misclassify the
        # new occupant) + the incremental per-tier occupancy counts.
        if cols.tier is None:
            self.m_tier[slots] = 0
            self._tier_n[0] = self._tier_n.get(0, 0) + n
        else:
            self.m_tier[slots] = cols.tier
            for t, c in zip(*np.unique(np.asarray(cols.tier, np.int64),
                                       return_counts=True)):
                self._tier_n[int(t)] = self._tier_n.get(int(t), 0) + int(c)
        if cols.deadline is None:
            self.m_deadline[slots] = 0.0
        else:
            dl = np.nan_to_num(np.asarray(cols.deadline, np.float64),
                               nan=0.0)
            self.m_deadline[slots] = dl
            self._deadline_n += int((dl != 0.0).sum())
        self._slot_of.update(zip(ids, slots.tolist()))
        self._seg_add(slots, 1)
        return slots

    def allocate(self, requests: Sequence[SearchRequest]) -> list[int]:
        """Object-path compatibility wrapper around allocate_columns."""
        for req in requests:
            if req.id in self._slot_of:
                raise ValueError(f"player {req.id!r} already in pool")
        cols = RequestColumns.from_requests(
            requests, self.regions.code, self.modes.code)
        slots = self.allocate_columns(cols).tolist()
        for s, req in zip(slots, requests):
            if req.roles:
                self.m_roles[s] = req.roles
        return slots

    def release(self, slots: Sequence[int] | np.ndarray) -> None:
        """Evict slots (matched / cancelled / timed out) from the mirror."""
        arr = np.unique(np.asarray(slots, dtype=np.int32))
        if arr.size == 0:
            return
        # np.unique guards intra-call duplicate slots; the occupancy mask
        # guards cross-call double-release (idempotent like a dict mirror).
        ids = self.m_id[arr]
        occupied = np.fromiter((i is not None for i in ids), bool, arr.size)
        arr = arr[occupied]
        if arr.size == 0:
            return
        for pid in ids[occupied].tolist():
            del self._slot_of[pid]
        self._seg_add(arr, -1)
        # Per-tier/deadline occupancy bookkeeping BEFORE clearing slots.
        for t, c in zip(*np.unique(self.m_tier[arr], return_counts=True)):
            self._tier_n[int(t)] = self._tier_n.get(int(t), 0) - int(c)
        self._deadline_n -= int((self.m_deadline[arr] != 0.0).sum())
        self.m_deadline[arr] = 0.0
        self.m_id[arr] = None
        self.m_roles[arr] = None
        if self._band_edges is not None:
            # Slots return to their HOME band (slot ranges are static), so
            # band occupancy self-heals as spilled players match out.
            bands = np.searchsorted(self._band_start, arr, side="right") - 1
            for b in np.unique(bands):
                sel = arr[bands == b][::-1]
                h = self._band_head[b]
                self._band_free[b][h:h + sel.size] = sel
                self._band_head[b] += sel.size
            self._head += arr.size
        else:
            self._free[self._head:self._head + arr.size] = arr
            self._head += arr.size

    def _take_banded(self, ratings: np.ndarray, n: int) -> np.ndarray:
        """Pop ``n`` slots by rating band; spill outward when a band is full.

        Vectorized per band present in the window (≤ R tiny numpy slices);
        the per-request Python loop runs only for spilled requests, which is
        rare until the pool nears capacity or the rating distribution drifts
        from the band edges."""
        band = np.digitize(ratings, self._band_edges)
        slots = np.empty(n, np.int32)
        for b in np.unique(band):
            idx = np.nonzero(band == b)[0]
            h = int(self._band_head[b])
            take = min(idx.size, h)
            if take:
                slots[idx[:take]] = self._band_free[b][h - take:h][::-1]
                self._band_head[b] = h - take
            for j in idx[take:]:
                bb = self._nearest_free_band(int(b))
                hh = int(self._band_head[bb])
                slots[j] = self._band_free[bb][hh - 1]
                self._band_head[bb] = hh - 1
        return slots

    def _nearest_free_band(self, b: int) -> int:
        r = len(self._band_free)
        for off in range(1, r):
            for cand in (b - off, b + off):
                if 0 <= cand < r and self._band_head[cand] > 0:
                    return cand
        raise PoolFullError("no free slot in any band")  # pragma: no cover
        # (unreachable: allocate_columns checks total free space upfront)

    # ---- array building ---------------------------------------------------

    def effective_base_threshold(self, req: SearchRequest) -> float:
        return req.rating_threshold if req.rating_threshold is not None else self.default_threshold

    def batch_arrays_cols(self, cols: RequestColumns, slots: np.ndarray,
                          bucket: int, t_offset: float = 0.0) -> BatchArrays:
        """Pack a columnar window into padded arrays of size ``bucket``.
        Padding lanes get slot = capacity (the sentinel the kernels treat as
        never-matching).

        ``t_offset`` rebases wall-clock timestamps: device times are float32,
        whose spacing at epoch magnitude (~1.7e9 s) is 128 s — far too coarse
        for threshold widening. The engine subtracts its start time so device
        times stay small (sub-millisecond spacing for a week-long process).
        """
        b = len(cols)
        assert b <= bucket
        arr = BatchArrays(
            slot=np.full(bucket, self.capacity, np.int32),
            rating=np.zeros(bucket, np.float32),
            rd=np.zeros(bucket, np.float32),
            region=np.zeros(bucket, np.int32),
            mode=np.zeros(bucket, np.int32),
            threshold=np.zeros(bucket, np.float32),
            enqueue_t=np.zeros(bucket, np.float32),
            valid=np.zeros(bucket, np.bool_),
        )
        if b:
            arr.slot[:b] = slots
            arr.rating[:b] = cols.rating
            arr.rd[:b] = cols.rd
            arr.region[:b] = cols.region
            arr.mode[:b] = cols.mode
            thr = np.where(np.isnan(cols.threshold), self.default_threshold,
                           cols.threshold)
            arr.threshold[:b] = thr
            # Rebase in float64 BEFORE the float32 store: epoch-magnitude
            # seconds only carry 128 s resolution in float32.
            arr.enqueue_t[:b] = cols.enqueued_at - t_offset
            arr.valid[:b] = True
        return arr

    def batch_arrays(self, requests: Sequence[SearchRequest], slots: Sequence[int],
                     bucket: int, t_offset: float = 0.0) -> BatchArrays:
        """Object-path compatibility wrapper around batch_arrays_cols."""
        cols = RequestColumns.from_requests(
            requests, self.regions.code, self.modes.code)
        return self.batch_arrays_cols(cols, np.asarray(slots, np.int32),
                                      bucket, t_offset)

    @staticmethod
    def empty_device_arrays(capacity: int) -> dict[str, np.ndarray]:
        """Initial HBM pool state (all slots inactive)."""
        return {name: np.zeros(capacity, dtype) for name, dtype in POOL_FIELDS}


def band_edges_from_spec(spec: str, n_bands: int) -> list[float] | None:
    """Parse an EngineConfig ``band_spec`` into ``n_bands - 1`` rating edges.

    Formats (JSON/env-friendly single string):
      ``""``                     — banding off (returns None)
      ``"uniform:LO:HI"``        — equal-width bands over [LO, HI]
      ``"gaussian:MEAN:STD"``    — equal-probability-mass bands under
                                   N(MEAN, STD) (stdlib NormalDist quantiles;
                                   matches a typical rating distribution so
                                   bands fill evenly and spilling stays rare)
    """
    if not spec:
        return None
    if n_bands < 2:
        return None
    kind, *params = spec.split(":")
    if kind == "uniform":
        lo, hi = float(params[0]), float(params[1])
        if not hi > lo:
            raise ValueError(f"uniform band_spec needs hi > lo: {spec!r}")
        step = (hi - lo) / n_bands
        return [lo + i * step for i in range(1, n_bands)]
    if kind == "gaussian":
        from statistics import NormalDist

        nd = NormalDist(float(params[0]), float(params[1]))
        return [nd.inv_cdf(i / n_bands) for i in range(1, n_bands)]
    raise ValueError(f"unknown band_spec kind: {spec!r}")


#: Row order of the packed batch (one f32[9, B] array per window — a single
#: host→device transfer instead of one per column). All rows are exact in
#: f32: slot ids and interner codes ≪ 2^24, valid is 0/1. Row 8 carries the rebased ``now``
#: scalar (broadcast across the row; kernels read [8, 0]).
PACKED_ROWS = ("slot", "rating", "rd", "region", "mode", "threshold",
               "enqueue_t", "valid")


def pack_batch(batch: BatchArrays, now: float = 0.0) -> np.ndarray:
    """BatchArrays (+ rebased now) → one f32[9, B] array (unpacked in-kernel)."""
    out = np.empty((len(PACKED_ROWS) + 1, batch.slot.shape[0]), np.float32)
    for i, name in enumerate(PACKED_ROWS):
        out[i] = getattr(batch, name)
    out[8] = now
    return out
