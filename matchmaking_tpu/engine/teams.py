"""Device-side 5v5 team-balanced matching (BASELINE config #3).

The oracle semantics (``engine/cpu.py:_try_team_window``): among mutually
region/mode-compatible waiting players, the contiguous rating-sorted window of
``2 * team_size`` players with minimal rating spread forms a match iff the
spread fits every member's effective threshold (min over the window). The
BASELINE config-#3 team-sum constraint (|sum_A − sum_B| ≤ threshold) is then
satisfied by construction: the snake split (A B B A A B B A ... by descending
rating) bounds the team-sum difference by the window spread
(scoring.snake_signs has the proof sketch; tests pin it).

The reference triggers one sequential scan per request (SURVEY.md §3 Entry 2);
the CPU oracle mirrors that one-match-per-arrival behavior. This module is
the TPU-native batch version: ONE jitted step admits a request window and
forms EVERY available match in the pool at once:

    admit (scatter) → stable two-pass argsort by (group, rating)
    → windowed spread / min-threshold via static shifts
    → parallel greedy selection of disjoint tightest windows
    → top-k extraction of winners → evict matched (scatter)

TPU-first notes:

- All shapes static: window width ``need = 2*team_size`` ≤ ~12, so every
  sliding-window reduction is ``need`` shifted element-wise ops — VPU-friendly,
  no gather loops, no data-dependent control flow.
- Sorting is ``jnp.argsort`` (XLA's bitonic/radix sort on TPU) — two stable
  passes give a lexicographic (group, rating) order without 64-bit keys
  (x64 is off on TPU).
- Window selection is the same fixed-round parallel-greedy scheme as
  ``kernels.greedy_pair``: a window wins a round iff it is the (spread, index)
  lexicographic minimum among the windows overlapping it; winners knock out
  their neighborhoods; ``rounds`` rounds retain everything a sequential
  tightest-first sweep would keep, up to pathological chains (which stay in
  the pool for the next step — same leftover semantics as the 1v1 kernel).

Grouping semantics: the device path groups by EXACT (region, mode) code —
wildcard players (code 0) form their own group and only match each other,
whereas the oracle expands wildcards into every concrete group
(non-transitive pairwise compatibility); that expansion is data-dependent
and host-shaped. This divergence is ENFORCED away rather than documented
away: ``TpuEngine._maybe_delegate_team`` flips a device team queue to the
host oracle (with a one-time warning) the moment a wildcard request
arrives, so device team matching only ever runs on all-concrete pools
where the two semantics coincide (pinned by
tests/test_teams_device.py::test_wildcard_requests_delegate_to_oracle).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from matchmaking_tpu.engine.kernels import (
    KernelSet,
    _effective_threshold,
    named_jit,
)

_BIG_I32 = jnp.int32(1 << 30)
_INF = jnp.float32(jnp.inf)


def extract_windows(won, need: int, max_matches: int, order, capacity: int):
    """Winner window starts → member slots: (slots i32[M, need], is_match
    bool[M], w i32[M]). Shared by the team and role kernels, single-device
    and sharded (order within M is irrelevant — winners are disjoint; the
    host sorts for determinism)."""
    score = jnp.where(won, -jnp.arange(won.shape[0], dtype=jnp.int32),
                      -_BIG_I32)
    topv, topi = jax.lax.top_k(score, max_matches)
    is_match = topv > -_BIG_I32
    w = jnp.where(is_match, topi, 0)
    member_pos = w[:, None] + jnp.arange(need, dtype=jnp.int32)[None, :]
    slots = order[member_pos]
    return jnp.where(is_match[:, None], slots, capacity), is_match, w


def sorted_group_order(pool: dict[str, Any]):
    """Stable lexicographic order by (group, rating); inactive last.

    Two stable passes: sort by rating, then by group — net effect is
    (group asc, rating asc, slot asc), matching the oracle's per-group
    rating sort (np.argsort stable). Shared by the single-device kernels
    and the sharded frontier compaction, which must produce the identical
    tie order for the ring path to be bit-exact."""
    group = pool["region"] * jnp.int32(1 << 15) + pool["mode"]
    group = jnp.where(pool["active"], group, _BIG_I32)
    p1 = jnp.argsort(pool["rating"], stable=True)
    p2 = jnp.argsort(group[p1], stable=True)
    return p1[p2], group


def pack_frontier(pool: dict[str, Any], fields: tuple[str, ...], k: int,
                  local_capacity: int, capacity: int):
    """Compact this shard's k best (group, rating)-sorted rows into ONE
    f32[len(fields)+1, k] buffer — the fixed-size candidate frontier the
    ring exchange ships instead of the full shard slice. The last row is
    the row's GLOBAL slot id (capacity sentinel for inactive padding).

    All packed values are f32-exact: region/mode codes < 2^15, role masks
    < 2^5, slot ids < capacity (asserted < 2^24 at kernel-set build).
    Active rows sort before inactive ones, so whenever this shard holds at
    most k active rows the frontier contains ALL of them, in the exact
    relative order the replicated global sort would give them — the no-
    overflow precondition the host checks before picking the ring step.
    Must run inside ``shard_map``."""
    from jax import lax

    from matchmaking_tpu.engine.sharded import AXIS

    offset = lax.axis_index(AXIS) * local_capacity
    order, _ = sorted_group_order(pool)
    top = order[:k]
    act = pool["active"][top]
    rows = [pool[f][top].astype(jnp.float32) for f in fields]
    gslot = jnp.where(act, top + offset, capacity).astype(jnp.float32)
    return jnp.stack(rows + [gslot])


def unpack_frontier(buf, fields: tuple[str, ...]):
    """Ring-gathered frontier buffers f32[n, len(fields)+1, k] → pool-dict
    columns of length n·k in canonical shard order, plus the global slot id
    column. Inverse of ``pack_frontier`` after ``ring_all_gather``."""
    n, c, k = buf.shape
    flat = jnp.moveaxis(buf, 1, 0).reshape(c, n * k)
    cols: dict[str, Any] = {}
    for i, f in enumerate(fields):
        if f == "active":
            cols[f] = flat[i] > 0.5
        elif f in ("region", "mode", "role_mask"):
            cols[f] = flat[i].astype(jnp.int32)
        else:
            cols[f] = flat[i]
    return cols, flat[len(fields)].astype(jnp.int32)


def merge_frontiers(buf, fields: tuple[str, ...], n_shards: int,
                    merge: str):
    """Ring-gathered frontier buffers → formation columns, by either
    consumer merge (ISSUE 14 satellite — the PR 1 follow-up):

    - ``"linear"``: today's path — concatenate all D·K rows in canonical
      shard order (``unpack_frontier``); the formation instance then sorts
      and forms windows over the O(K·D) buffer.
    - ``"tournament"``: pairwise tournament-tree top-K merge of the D
      already-sorted K-row frontiers (``sharded.tournament_merge_topk``) —
      the formation buffer shrinks to K rows and the merge working set is
      O(K·log D). Bit-exact vs linear under exactly the ring path's host
      gate (global active population ≤ K — every active row then survives
      every top-K truncation, in concat-sort order).

    Returns ``(columns dict, gslot i32)`` with length D·K (linear) or K
    (tournament)."""
    if merge != "tournament" or n_shards <= 1:
        return unpack_frontier(buf, fields)
    from matchmaking_tpu.engine.sharded import tournament_merge_topk

    ridx = fields.index("region")
    midx = fields.index("mode")
    aidx = fields.index("active")
    slot_row = len(fields)

    def key_fn(fb):
        act = fb[aidx] > 0.5
        group = jnp.where(
            act,
            fb[ridx].astype(jnp.int32) * jnp.int32(1 << 15)
            + fb[midx].astype(jnp.int32),
            _BIG_I32)
        return group, fb[0], fb[slot_row].astype(jnp.int32)

    merged = tournament_merge_topk([buf[i] for i in range(n_shards)],
                                   key_fn)
    return unpack_frontier(merged[None], fields)


def shard_localize(batch, local_capacity: int):
    """Global batch slot ids → this shard's local frame (non-local ids map
    to the local sentinel). Must run inside shard_map."""
    from jax import lax

    from matchmaking_tpu.engine.sharded import AXIS

    offset = lax.axis_index(AXIS) * local_capacity
    local = batch["slot"] - offset
    mine = (local >= 0) & (local < local_capacity)
    return dict(batch, slot=jnp.where(mine, local, local_capacity))


def shard_evict(local_kernel, pool, slots, local_capacity: int):
    """Evict this shard's slice of globally-indexed ``slots`` (sentinel for
    the rest). Must run inside shard_map."""
    from jax import lax

    from matchmaking_tpu.engine.sharded import AXIS

    offset = lax.axis_index(AXIS) * local_capacity
    local = slots.reshape(-1).astype(jnp.int32) - offset
    mine = (local >= 0) & (local < local_capacity)
    return local_kernel._evict(pool, jnp.where(mine, local, local_capacity))


class TeamKernelSet:
    """Compiled team-match step for one (pool geometry × queue config).

    Call surface mirrors ``KernelSet`` (admit / evict / search_step over the
    same pool dict + padded batch dict); ``search_step`` returns
    ``(pool', match_slots i32[M, need], spread f32[M], limit f32[M])`` where
    rows with ``match_slots[m, 0] == capacity`` are padding.
    """

    def __init__(self, *, capacity: int, team_size: int,
                 widen_per_sec: float, max_threshold: float,
                 max_matches: int = 1024, rounds: int = 16,
                 evict_bucket: int = 64):
        assert team_size > 1, "team kernel needs team_size > 1"
        self.capacity = capacity
        self.team_size = team_size
        self.need = 2 * team_size
        self.widen_per_sec = widen_per_sec
        self.max_threshold = max_threshold
        self.max_matches = min(max_matches, max(1, capacity // self.need))
        self.rounds = rounds
        self.evict_bucket = evict_bucket
        # Reuse the 1v1 kernel's admit/evict scatters (same pool layout).
        self._base = KernelSet(
            capacity=capacity, top_k=1, pool_block=min(256, capacity),
            glicko2=False, widen_per_sec=widen_per_sec,
            max_threshold=max_threshold, evict_bucket=evict_bucket,
        )
        self.admit = self._base.admit
        self.admit_packed = self._base.admit_packed
        self.evict = self._base.evict
        self.search_step = named_jit("team_search_step", self._search_step,
                                     donate_argnums=0)
        self.search_step_packed = named_jit(
            "team_search_step_packed", self._search_step_packed,
            donate_argnums=0)

    def _search_step_packed(self, pool, packed):
        """Packed team step: f32[9,B] in (see pool.PACKED_ROWS + now row),
        out stacked f32[need+2, M]: member slots (f32-exact), spread, limit."""
        from matchmaking_tpu.engine.kernels import unpack_batch

        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool, slots, spread, thr = self._search_step(pool, batch, now)
        out = jnp.concatenate([slots.T.astype(jnp.float32),
                               spread[None, :], thr[None, :]])
        return pool, out

    # ---- internals --------------------------------------------------------

    def _sorted_order(self, pool: dict[str, Any]):
        return sorted_group_order(pool)

    def _windows(self, pool: dict[str, Any], order, group, now):
        """Validity + stats for every window start w ∈ [0, P - need]."""
        need = self.need
        n_win = self.capacity - need + 1
        r_s = pool["rating"][order]
        g_s = group[order]
        a_s = pool["active"][order]
        thr_s = _effective_threshold(
            pool["threshold"][order], pool["enqueue_t"][order], now,
            self.widen_per_sec, self.max_threshold,
        )

        # Windowed reductions as `need` static shifts (need ≤ ~12). The
        # config-#3 team-sum constraint needs no term here: the snake
        # split's |sum_A - sum_B| telescopes to ≤ spread ≤ win_thr by
        # construction (see cpu.py:_try_team_window and scoring.snake_signs).
        win_thr = thr_s[:n_win]
        all_active = a_s[:n_win]
        for i in range(1, need):
            win_thr = jnp.minimum(
                win_thr, jax.lax.dynamic_slice_in_dim(thr_s, i, n_win))
            all_active = all_active & jax.lax.dynamic_slice_in_dim(a_s, i, n_win)
        spread = jax.lax.dynamic_slice_in_dim(r_s, need - 1, n_win) - r_s[:n_win]
        same_group = g_s[:n_win] == jax.lax.dynamic_slice_in_dim(g_s, need - 1, n_win)
        valid = (
            all_active & same_group & (g_s[:n_win] < _BIG_I32)
            & (spread <= win_thr)
        )
        return valid, spread, win_thr

    def _neigh_reduce(self, x, *, op, pad):
        """Reduce each position over its overlap neighborhood |Δw| < need
        (2·need−1 static shifts — windows overlap iff starts differ by <need)."""
        n = x.shape[0]
        out = x
        for d in range(1, self.need):
            right = jnp.concatenate([x[d:], jnp.full((d,), pad, x.dtype)])
            left = jnp.concatenate([jnp.full((d,), pad, x.dtype), x[:-d]])
            out = op(op(out, right), left)
        return out

    def _select_windows(self, valid, spread):
        """Fixed-round parallel greedy: disjoint windows, tightest-first."""
        n_win = valid.shape[0]
        idx = jnp.arange(n_win, dtype=jnp.int32)

        def body(_, state):
            valid, won = state
            sp = jnp.where(valid, spread, _INF)
            neigh_min = self._neigh_reduce(sp, op=jnp.minimum, pad=_INF)
            cand = valid & (sp <= neigh_min)
            ci = jnp.where(cand, idx, _BIG_I32)
            neigh_imin = self._neigh_reduce(ci, op=jnp.minimum, pad=_BIG_I32)
            winner = cand & (ci == neigh_imin)
            # Knock out every window overlapping a winner (winner included).
            hit = self._neigh_reduce(winner, op=jnp.logical_or, pad=False)
            return valid & ~hit, won | winner

        valid, won = jax.lax.fori_loop(
            0, self.rounds, body, (valid, jnp.zeros_like(valid)))
        return won

    def _search_step(self, pool: dict[str, Any], batch: dict[str, Any], now):
        """One team window step. Returns (pool', slots i32[M,need],
        spread f32[M], limit f32[M]); padding rows carry slot sentinel P."""
        pool = self._base._admit(pool, batch)
        order, group = self._sorted_order(pool)
        valid, spread, win_thr = self._windows(pool, order, group, now)
        won = self._select_windows(valid, spread)
        slots, is_match, w = extract_windows(
            won, self.need, self.max_matches, order, self.capacity)

        # Compare-masked eviction (scatter-free — see kernels.py header).
        pool = self._base._evict(pool, slots.reshape(-1))
        out_spread = jnp.where(is_match, spread[w], _INF)
        out_thr = jnp.where(is_match, win_thr[w], 0.0)
        return pool, slots, out_spread, out_thr


@functools.lru_cache(maxsize=None)
def team_kernel_set(capacity: int, team_size: int, widen_per_sec: float,
                    max_threshold: float, max_matches: int = 1024,
                    rounds: int = 16) -> TeamKernelSet:
    return TeamKernelSet(
        capacity=capacity, team_size=team_size, widen_per_sec=widen_per_sec,
        max_threshold=max_threshold, max_matches=max_matches, rounds=rounds,
    )


class ShardedTeamKernelSet:
    """Multi-chip team matching: pool sharded over mesh axis ``"pool"``.

    Team-window formation needs a GLOBAL (group, rating) sort, which does
    not decompose across shards the way 1v1 top-k does. Two device paths:

    - **Replicated fallback** (``search_step_packed``): each step
      ``all_gather``s the window-selection columns (6 × f32[P]) over ICI
      and runs selection REPLICATED — per-step ICI traffic and per-device
      window math are O(P) regardless of shard count.
    - **Ring-scaled** (``search_step_packed_ring``, built when
      ``frontier_k > 0``): each shard compacts its LOCAL (group, rating)-
      sorted slice into a fixed-size top-K candidate frontier
      (``pack_frontier``), the frontiers travel the ICI ring via
      ``ppermute`` (D−1 neighbor hops, O(K) rows per hop —
      ``sharded.ring_all_gather``), and the deterministic window selection
      runs on the D·K-row merged buffer: O(P/D) local compaction +
      O(K·D) exchange/formation instead of O(P). Whenever no shard holds
      more than K active rows the merged buffer contains exactly the
      global active rows in the replicated sort's order, so the selected
      windows are BIT-IDENTICAL to the fallback's (pinned by
      tests/test_teams_device.py::TestRingShardedTeams). The HOST picks
      the step per window: the mirror's occupancy upper-bounds every
      shard's active rows, so ``occupancy <= frontier_k`` guarantees no
      overflow; otherwise the window runs the replicated fallback
      (TpuEngine._step_fn; counters team_ring_steps / team_ring_fallback).

    Call surface mirrors TeamKernelSet's packed API so TpuEngine swaps it in
    when ``mesh_pool_axis > 1`` on a plain team queue.
    """

    #: Columns the window formation needs (gathered whole in the fallback,
    #: frontier-compacted in the ring path). The frontier adds one global-
    #: slot row on top.
    _GATHER = ("rating", "region", "mode", "threshold", "enqueue_t",
               "active")

    def __init__(self, *, capacity: int, team_size: int,
                 widen_per_sec: float, max_threshold: float, mesh,
                 max_matches: int = 1024, rounds: int = 16,
                 evict_bucket: int = 64, frontier_k: int = 0,
                 frontier_merge: str = "linear"):
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P

        from matchmaking_tpu.engine.sharded import AXIS

        self.mesh = mesh
        self.n_shards = mesh.devices.size
        if capacity % self.n_shards != 0:
            capacity += self.n_shards - capacity % self.n_shards
        if capacity >= (1 << 24):
            # Not an assert: under python -O a stripped check would let the
            # frontier pack slot ids into f32 rows past exactness and the
            # ring step would silently evict the wrong players.
            raise ValueError(
                f"capacity {capacity} >= 2**24: slot ids must stay f32-exact")
        self.capacity = capacity
        self.local_capacity = capacity // self.n_shards
        self.team_size = team_size
        self.need = 2 * team_size
        self.evict_bucket = evict_bucket
        # Global-window math on gathered columns (admit/evict unused there).
        self._global = TeamKernelSet(
            capacity=capacity, team_size=team_size,
            widen_per_sec=widen_per_sec, max_threshold=max_threshold,
            max_matches=max_matches, rounds=rounds)
        self.max_matches = self._global.max_matches
        # Local admit/evict on the shard slice.
        self._local = KernelSet(
            capacity=self.local_capacity, top_k=1,
            pool_block=min(256, self.local_capacity), glicko2=False,
            widen_per_sec=widen_per_sec, max_threshold=max_threshold,
            evict_bucket=evict_bucket)
        self._np = np
        #: Per-shard frontier row budget for the ring path (0 = ring off —
        #: replicated allgather only). The host routes a window to the ring
        #: step only when pool occupancy <= frontier_k.
        self.frontier_k = (min(max(frontier_k, self.need),
                               self.local_capacity)
                           if frontier_k > 0 else 0)
        #: Consumer merge for the ring-gathered frontiers: "linear"
        #: (concat all D·K rows) or "tournament" (tree top-K merge — the
        #: formation buffer shrinks to K rows; bit-exact under the same
        #: host gate). See ``merge_frontiers``.
        if frontier_merge not in ("linear", "tournament"):
            raise ValueError(
                f"unknown frontier_merge {frontier_merge!r} "
                "(expected 'linear' or 'tournament')")
        self.frontier_merge = frontier_merge

        pool_spec = {k: P(AXIS) for k in
                     ("rating", "rd", "region", "mode", "threshold",
                      "enqueue_t", "active")}
        rep = P()
        self.search_step_packed = named_jit(
            "team_sharded_search_step_packed",
            jax.shard_map(self._step_shard, mesh=mesh,
                          in_specs=(pool_spec, rep),
                          out_specs=(pool_spec, rep), check_vma=False),
            donate_argnums=0)
        if self.frontier_k:
            # Formation instance over the merged frontier buffer: D·K rows
            # on the linear merge, K on the tournament merge; max_matches
            # mirrors the fallback's so both steps share one output shape
            # (disjoint windows over the buffer can never exceed
            # rows // need, so the clamp loses no matches).
            form_rows = (self.frontier_k
                         if frontier_merge == "tournament"
                         else self.n_shards * self.frontier_k)
            self._ring_form = TeamKernelSet(
                capacity=form_rows,
                team_size=team_size, widen_per_sec=widen_per_sec,
                max_threshold=max_threshold, max_matches=self.max_matches,
                rounds=rounds)
            self.search_step_packed_ring = named_jit(
                "team_sharded_search_step_packed_ring",
                jax.shard_map(self._step_shard_ring, mesh=mesh,
                              in_specs=(pool_spec, rep),
                              out_specs=(pool_spec, rep), check_vma=False),
                donate_argnums=0)
        self.admit_packed = named_jit(
            "team_sharded_admit_packed",
            jax.shard_map(self._admit_shard, mesh=mesh,
                          in_specs=(pool_spec, rep), out_specs=pool_spec,
                          check_vma=False),
            donate_argnums=0)
        self.evict = named_jit(
            "team_sharded_evict",
            jax.shard_map(self._evict_shard, mesh=mesh,
                          in_specs=(pool_spec, rep), out_specs=pool_spec,
                          check_vma=False),
            donate_argnums=0)
        self._sharding = NamedSharding(mesh, P(AXIS))

    # ---- shard-local helpers (inside shard_map) ---------------------------

    def _localize(self, batch):
        return shard_localize(batch, self.local_capacity)

    def _admit_shard(self, pool, packed):
        from matchmaking_tpu.engine.kernels import unpack_batch

        return self._local._admit(pool, self._localize(unpack_batch(packed)))

    def _evict_shard(self, pool, slots):
        return shard_evict(self._local, pool, slots, self.local_capacity)

    def _step_shard(self, pool, packed):
        from jax import lax

        from matchmaking_tpu.engine.kernels import unpack_batch
        from matchmaking_tpu.engine.sharded import AXIS

        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool = self._local._admit(pool, self._localize(batch))

        # Gather the window-selection columns globally (tiled → f32/i32[P]).
        full = {f: lax.all_gather(pool[f], AXIS, tiled=True)
                for f in self._GATHER}
        g = self._global
        order, group = g._sorted_order(full)
        valid, spread, win_thr = g._windows(full, order, group, now)
        won = g._select_windows(valid, spread)
        slots, is_match, w = extract_windows(
            won, g.need, g.max_matches, order, self.capacity)

        # Evict this shard's slice of every matched slot.
        pool = shard_evict(self._local, pool, slots, self.local_capacity)

        out = jnp.concatenate([slots.T.astype(jnp.float32),
                               jnp.where(is_match, spread[w], _INF)[None, :],
                               jnp.where(is_match, win_thr[w], 0.0)[None, :]])
        return pool, out

    def _step_shard_ring(self, pool, packed):
        """Ring-scaled step: local frontier compaction → ppermute ring →
        deterministic selection on the merged D·K-row buffer. Valid only
        when no shard holds more than frontier_k active rows (host-gated);
        then bit-identical to ``_step_shard``."""
        from matchmaking_tpu.engine.kernels import unpack_batch
        from matchmaking_tpu.engine.sharded import ring_all_gather

        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool = self._local._admit(pool, self._localize(batch))

        frontier = pack_frontier(pool, self._GATHER, self.frontier_k,
                                 self.local_capacity, self.capacity)
        (buf,) = ring_all_gather((frontier,), self.n_shards)
        full, gslot = merge_frontiers(buf, self._GATHER, self.n_shards,
                                      self.frontier_merge)
        g = self._ring_form
        order, group = g._sorted_order(full)
        valid, spread, win_thr = g._windows(full, order, group, now)
        won = g._select_windows(valid, spread)
        slots_b, is_match, w = extract_windows(
            won, g.need, g.max_matches, order, g.capacity)
        # Buffer rows → global slot ids (row g.capacity = padding sentinel).
        gs = jnp.concatenate([gslot,
                              jnp.array([self.capacity], jnp.int32)])
        slots = gs[slots_b]
        pool = shard_evict(self._local, pool, slots, self.local_capacity)

        out = jnp.concatenate([slots.T.astype(jnp.float32),
                               jnp.where(is_match, spread[w], _INF)[None, :],
                               jnp.where(is_match, win_thr[w], 0.0)[None, :]])
        return pool, pad_match_columns(
            out, self.max_matches - g.max_matches, self.need, self.capacity)

    def comms_accounting(self) -> dict:
        return shard_comms_accounting(self)

    def place_pool(self, arrays):
        return {k: jax.device_put(jnp.asarray(v), self._sharding)
                for k, v in arrays.items()}


def shard_comms_accounting(ks) -> dict:
    """Per-device per-step ICI traffic + formation workload for a sharded
    team-family kernel set, derived from the ACTUAL buffer shapes the
    compiled steps move: the fallback all_gathers the len(_GATHER) pool
    columns at their POOL_FIELDS dtypes (active is 1-byte bool, the rest
    4-byte; each device receives every other shard's slice → O(P) bytes
    regardless of D); the ring ships one (len(_GATHER)+1, K) all-f32
    frontier per hop for D−1 hops → O(K·D) bytes, and its formation
    runs over P/D local + D·K merged rows instead of P. The bench's comms
    phase turns this into the O(P) vs O(P/D + K·D) table."""
    import numpy as np

    from matchmaking_tpu.core.pool import POOL_FIELDS

    cols = len(ks._GATHER)
    dtypes = dict(POOL_FIELDS)
    dtypes.update(getattr(ks, "extra_pool_fields", {}))
    row_bytes = sum(np.dtype(dtypes[f]).itemsize for f in ks._GATHER)
    acct = {
        "n_shards": ks.n_shards,
        "capacity": ks.capacity,
        "gather_cols": cols,
        "allgather": {
            "ici_recv_bytes": (ks.capacity - ks.local_capacity) * row_bytes,
            "formation_rows": ks.capacity,
        },
    }
    if ks.frontier_k:
        k = ks.frontier_k
        acct["ring"] = {
            "frontier_k": k,
            "ici_recv_bytes": (ks.n_shards - 1) * (cols + 1) * k * 4,
            "formation_rows": ks.local_capacity + ks.n_shards * k,
        }
    return acct


def pad_match_columns(out, pad: int, need: int, capacity: int,
                      extra_zero_rows: int = 0):
    """Pad a packed (need+2+extra, M) match result to M+pad columns carrying
    the canonical non-match sentinels (slots=capacity, spread=inf, the
    limit — and any extra rows — zero), so the ring step's output shape and
    padding rows are bit-identical to the replicated fallback's."""
    if pad <= 0:
        return out
    col = jnp.concatenate([
        jnp.full((need, pad), float(capacity), jnp.float32),
        jnp.full((1, pad), _INF, jnp.float32),
        jnp.zeros((1 + extra_zero_rows, pad), jnp.float32)])
    return jnp.concatenate([out, col], axis=1)


@functools.lru_cache(maxsize=None)
def sharded_team_kernel_set(capacity: int, team_size: int,
                            widen_per_sec: float, max_threshold: float,
                            n_shards: int, max_matches: int = 1024,
                            rounds: int = 16, frontier_k: int = 0,
                            frontier_merge: str = "linear",
                            ) -> ShardedTeamKernelSet:
    from matchmaking_tpu.engine.sharded import pool_mesh

    return ShardedTeamKernelSet(
        capacity=capacity, team_size=team_size, widen_per_sec=widen_per_sec,
        max_threshold=max_threshold, mesh=pool_mesh(n_shards),
        max_matches=max_matches, rounds=rounds, frontier_k=frontier_k,
        frontier_merge=frontier_merge,
    )
