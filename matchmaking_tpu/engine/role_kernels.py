"""Device-side role-queue matchmaking for SOLO players (BASELINE config #5).

Round-4 state: role/party queues ran the host oracle only (``engine/roles.py``
— O(n²·backtracking) per arrival), flagged by the round-4 verdict as the last
BASELINE config without a TPU path. This module is the device path for the
solo case; parties (and region/mode wildcards) still delegate to the oracle —
``TpuEngine._maybe_delegate_team`` flips the queue over (and back, once they
drain) exactly like team-queue wildcards.

Why solos reduce cleanly (derived from ``roles.try_party_match``; the device
path must be match-for-match identical to it):

- Every unit has size 1, so the first-fit-decreasing pack of a rating-sorted
  window always assigns the k lowest-rated members to team A and the next k
  to team B — and any window larger than ``need = 2·team_size`` packs the
  SAME first 2k members with a LARGER spread, so only minimal windows can
  ever win. The oracle's window slide therefore collapses to: for each start
  ``lo`` ascending, try the ``need`` consecutive sorted members.
- ``_window_feasible`` is a necessary-condition prefilter (a successful pack
  implies it), so the device path may skip it.
- A window is valid iff spread ≤ min effective threshold AND the base split
  (or the first swap-repair exchange, in the oracle's (i, j) scan order)
  gives BOTH teams a perfect member→role-slot assignment.
- Perfect assignment of k members to k role slots is decided by Hall's
  condition over DISTINCT roles (slots of one role are interchangeable):
  for every subset S of distinct roles, |{members eligible for some role in
  S}| ≥ slots(S). With D ≤ ~5 distinct roles that is ≤ 31 subset checks of
  dense bitmask math per team — a few shifted compares per window, no
  backtracking, no data-dependent control flow.

Pool layout = the standard POOL_FIELDS plus one extra column ``role_mask``
(i32 bitmask over the queue's distinct roles; declared-role members carry
their roles' bits, wildcard-role members carry ALL bits — mirroring
``roles._roles_cover``'s "no roles = eligible for everything"). The packed
batch gains one row for it (see ``pack_rows``).

Selection is leftmost-first (the oracle returns the FIRST valid window by
``lo``), unlike the plain team kernel's tightest-first — both use the same
fixed-round parallel-greedy neighborhood scheme.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from matchmaking_tpu.engine.kernels import (
    _ADMIT_FIELDS,
    _admit_block,
    unpack_batch,
)
from matchmaking_tpu.engine.teams import (
    TeamKernelSet,
    _BIG_I32,
    _INF,
    extract_windows,
    shard_evict,
    shard_localize,
)
from jax import lax


class RoleKernelSet(TeamKernelSet):
    """Compiled solo role-queue step. Call surface mirrors TeamKernelSet;
    ``search_step`` returns ``(pool', slots i32[M, need], spread f32[M],
    limit f32[M], split i32[M])`` where ``split`` bit i set ⇔ the i-th
    window member (rating order) is on team A. Packed output stacks
    ``need + 3`` rows (slots, spread, limit, split)."""

    is_role = True
    #: Extra device pool columns beyond POOL_FIELDS (engine bootstrap).
    extra_pool_fields = {"role_mask": np.int32}
    #: Packed batch rows: PACKED_ROWS + role_mask + now.
    pack_rows = 10

    def __init__(self, *, capacity: int, team_size: int,
                 role_slots: tuple[str, ...],
                 widen_per_sec: float, max_threshold: float,
                 max_matches: int = 1024, rounds: int = 16,
                 evict_bucket: int = 64):
        assert role_slots, "role kernel needs role_slots"
        assert len(role_slots) == team_size, (
            "role_slots must name one role per team member")
        super().__init__(capacity=capacity, team_size=team_size,
                         widen_per_sec=widen_per_sec,
                         max_threshold=max_threshold,
                         max_matches=max_matches, rounds=rounds,
                         evict_bucket=evict_bucket)
        # Distinct roles in sorted order → bit index (deterministic).
        self.distinct = tuple(sorted(set(role_slots)))
        self._bit = {r: i for i, r in enumerate(self.distinct)}
        d = len(self.distinct)
        self.full_mask = (1 << d) - 1
        # Static per-subset slot demand for ONE team.
        self._subsets = tuple(range(1, 1 << d))
        self._demand = tuple(
            sum(1 for r in role_slots if (1 << self._bit[r]) & s)
            for s in self._subsets)
        # Role-aware packed entries override the base jits.
        self.admit_packed = jax.jit(
            lambda pool, packed: self._admit_roles(
                pool, self._unpack(packed)[0]),
            donate_argnums=0)
        self.search_step = jax.jit(self._search_step, donate_argnums=0)
        self.search_step_packed = jax.jit(self._search_step_packed,
                                          donate_argnums=0)

    # ---- host helpers ------------------------------------------------------

    def mask_of(self, roles: tuple[str, ...]) -> int:
        """Member roles → eligibility bitmask (oracle semantics: no declared
        roles ⇒ eligible for every slot; out-of-vocabulary roles carry no
        bits)."""
        if not roles:
            return self.full_mask
        m = 0
        for r in roles:
            b = self._bit.get(r)
            if b is not None:
                m |= 1 << b
        return m

    # ---- device internals --------------------------------------------------

    @staticmethod
    def _unpack(packed):
        batch = unpack_batch(packed)
        batch["role_mask"] = packed[8].astype(jnp.int32)
        return batch, packed[9, 0]

    def _admit_roles(self, pool: dict[str, Any], batch: dict[str, Any]):
        """Standard admission extended with the role_mask column (mask ints
        ≪ 2^24 are f32-exact through the eq-matmul)."""
        blk = self._base.pool_block
        fields = (*_ADMIT_FIELDS, "role_mask")

        def body(_, blk_i):
            start = blk_i * blk
            block = {f: lax.dynamic_slice_in_dim(pool[f], start, blk)
                     for f in (*fields, "active")}
            return None, _admit_block(block, start, blk, batch,
                                      fields=fields)

        _, blocks = lax.scan(body, None,
                             jnp.arange(self._base.n_blocks, dtype=jnp.int32))
        return {f: blocks[f].reshape(self.capacity) for f in blocks}

    def _covers(self, masks):
        """Hall check for one team per window: masks i32[n_win, k] →
        bool[n_win]. For every nonempty subset S of distinct roles, the
        team needs ≥ demand(S) members eligible inside S."""
        ok = jnp.ones(masks.shape[0], bool)
        for s, dem in zip(self._subsets, self._demand):
            elig = ((masks & jnp.int32(s)) != 0).sum(axis=1)
            ok = ok & (elig >= dem)
        return ok

    def _cover_split(self, member_masks):
        """Oracle pack order over each window's ``need`` rating-sorted
        members: base split (low k → A), then swap-repair exchanges in
        (i, j) scan order; first split whose BOTH teams pass Hall wins.
        Returns (ok bool[n_win], split i32[n_win] bitmask, bit i = member i
        on team A)."""
        k = self.team_size
        a = member_masks[:, :k]                      # (n_win, k)
        b = member_masks[:, k:]
        base_bits = jnp.int32((1 << k) - 1)

        oks = [self._covers(a) & self._covers(b)]
        bits = [jnp.full(a.shape[0], base_bits, jnp.int32)]
        for i in range(k):
            for j in range(k):
                swapped_a = jnp.concatenate(
                    [a[:, :i], b[:, j:j + 1], a[:, i + 1:]], axis=1)
                swapped_b = jnp.concatenate(
                    [b[:, :j], a[:, i:i + 1], b[:, j + 1:]], axis=1)
                oks.append(self._covers(swapped_a) & self._covers(swapped_b))
                bits.append(jnp.full(
                    a.shape[0],
                    jnp.int32(((1 << k) - 1) ^ (1 << i) | (1 << (k + j))),
                    jnp.int32))
        ok_m = jnp.stack(oks, axis=1)                # (n_win, 1 + k²)
        bit_m = jnp.stack(bits, axis=1)
        prio = jnp.arange(ok_m.shape[1], dtype=jnp.int32)
        first = jnp.argmin(jnp.where(ok_m, prio, _BIG_I32), axis=1)
        ok = ok_m.any(axis=1)
        split = jnp.take_along_axis(bit_m, first[:, None], axis=1)[:, 0]
        return ok, jnp.where(ok, split, 0)

    def _windows_roles(self, pool, order, group, now):
        """Team-window validity + the role cover/split term."""
        valid, spread, win_thr = self._windows(pool, order, group, now)
        need = self.need
        n_win = self.capacity - need + 1
        m_s = pool["role_mask"][order]
        cols = [lax.dynamic_slice_in_dim(m_s, i, n_win)
                for i in range(need)]
        member_masks = jnp.stack(cols, axis=1)       # (n_win, need)
        cover_ok, split = self._cover_split(member_masks)
        return valid & cover_ok, spread, win_thr, split

    def _select_leftmost(self, valid):
        """Leftmost-first disjoint selection (the oracle returns the FIRST
        valid window by start index, not the tightest)."""
        n_win = valid.shape[0]
        idx = jnp.arange(n_win, dtype=jnp.int32)

        def body(_, state):
            valid, won = state
            ci = jnp.where(valid, idx, _BIG_I32)
            neigh_imin = self._neigh_reduce(ci, op=jnp.minimum, pad=_BIG_I32)
            winner = valid & (ci == neigh_imin)
            hit = self._neigh_reduce(winner, op=jnp.logical_or, pad=False)
            return valid & ~hit, won | winner

        _, won = jax.lax.fori_loop(
            0, self.rounds, body, (valid, jnp.zeros_like(valid)))
        return won

    def _search_step(self, pool: dict[str, Any], batch: dict[str, Any], now):
        pool = self._admit_roles(pool, batch)
        order, group = self._sorted_order(pool)
        valid, spread, win_thr, split = self._windows_roles(
            pool, order, group, now)
        won = self._select_leftmost(valid)
        slots, is_match, w = extract_windows(
            won, self.need, self.max_matches, order, self.capacity)
        pool = self._base._evict(pool, slots.reshape(-1))
        out_spread = jnp.where(is_match, spread[w], _INF)
        out_thr = jnp.where(is_match, win_thr[w], 0.0)
        out_split = jnp.where(is_match, split[w], 0)
        return pool, slots, out_spread, out_thr, out_split

    def _search_step_packed(self, pool, packed):
        """Packed role step: f32[10, B] in (PACKED_ROWS + role_mask + now),
        out f32[need + 3, M]: member slots, spread, limit, split bits."""
        batch, now = self._unpack(packed)
        pool, slots, spread, thr, split = self._search_step(pool, batch, now)
        out = jnp.concatenate([slots.T.astype(jnp.float32),
                               spread[None, :], thr[None, :],
                               split.astype(jnp.float32)[None, :]])
        return pool, out


@functools.lru_cache(maxsize=None)
def role_kernel_set(capacity: int, team_size: int,
                    role_slots: tuple[str, ...], widen_per_sec: float,
                    max_threshold: float, max_matches: int = 1024,
                    rounds: int = 16) -> RoleKernelSet:
    return RoleKernelSet(
        capacity=capacity, team_size=team_size, role_slots=role_slots,
        widen_per_sec=widen_per_sec, max_threshold=max_threshold,
        max_matches=max_matches, rounds=rounds,
    )


class ShardedRoleKernelSet:
    """Multi-chip solo role-queue matching: pool sharded over mesh axis
    ``"pool"`` — the same two paths as ShardedTeamKernelSet (teams.py):
    replicated window formation on all_gathered columns (fallback), and,
    when ``frontier_k > 0``, the ring-scaled variant that ppermutes a
    fixed-size per-shard candidate frontier instead (bit-identical while
    no shard holds more than K active rows; the host gates on occupancy —
    see the team class docstring). The role family adds the role_mask
    column to both the gather and the frontier. Call surface mirrors
    RoleKernelSet's packed API; TpuEngine swaps it in when
    ``mesh_pool_axis > 1`` on a role queue."""

    is_role = True
    extra_pool_fields = RoleKernelSet.extra_pool_fields
    pack_rows = RoleKernelSet.pack_rows

    _GATHER = ("rating", "region", "mode", "threshold", "enqueue_t",
               "active", "role_mask")

    def __init__(self, *, capacity: int, team_size: int,
                 role_slots: tuple[str, ...], widen_per_sec: float,
                 max_threshold: float, mesh, max_matches: int = 1024,
                 rounds: int = 16, evict_bucket: int = 64,
                 frontier_k: int = 0, frontier_merge: str = "linear"):
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
        # Global window/cover math on gathered columns.
        self._global = RoleKernelSet(
            capacity=capacity, team_size=team_size, role_slots=role_slots,
            widen_per_sec=widen_per_sec, max_threshold=max_threshold,
            max_matches=max_matches, rounds=rounds)
        self.max_matches = self._global.max_matches
        # Shard-local role-aware admit + evict.
        self._local = RoleKernelSet(
            capacity=self.local_capacity, team_size=team_size,
            role_slots=role_slots, widen_per_sec=widen_per_sec,
            max_threshold=max_threshold, max_matches=max_matches,
            rounds=rounds, evict_bucket=evict_bucket)
        self.frontier_k = (min(max(frontier_k, self.need),
                               self.local_capacity)
                           if frontier_k > 0 else 0)
        #: Frontier consumer merge: "linear" or "tournament" (see
        #: teams.merge_frontiers — same gate, same exactness argument).
        if frontier_merge not in ("linear", "tournament"):
            raise ValueError(
                f"unknown frontier_merge {frontier_merge!r} "
                "(expected 'linear' or 'tournament')")
        self.frontier_merge = frontier_merge

        pool_spec = {k: P(AXIS) for k in
                     ("rating", "rd", "region", "mode", "threshold",
                      "enqueue_t", "active", "role_mask")}
        rep = P()
        self.search_step_packed = jax.jit(
            jax.shard_map(self._step_shard, mesh=mesh,
                          in_specs=(pool_spec, rep),
                          out_specs=(pool_spec, rep), check_vma=False),
            donate_argnums=0)
        if self.frontier_k:
            form_rows = (self.frontier_k
                         if frontier_merge == "tournament"
                         else self.n_shards * self.frontier_k)
            self._ring_form = RoleKernelSet(
                capacity=form_rows,
                team_size=team_size, role_slots=role_slots,
                widen_per_sec=widen_per_sec, max_threshold=max_threshold,
                max_matches=self.max_matches, rounds=rounds)
            self.search_step_packed_ring = jax.jit(
                jax.shard_map(self._step_shard_ring, mesh=mesh,
                              in_specs=(pool_spec, rep),
                              out_specs=(pool_spec, rep), check_vma=False),
                donate_argnums=0)
        self.admit_packed = jax.jit(
            jax.shard_map(self._admit_shard, mesh=mesh,
                          in_specs=(pool_spec, rep), out_specs=pool_spec,
                          check_vma=False),
            donate_argnums=0)
        self.evict = jax.jit(
            jax.shard_map(self._evict_shard, mesh=mesh,
                          in_specs=(pool_spec, rep), out_specs=pool_spec,
                          check_vma=False),
            donate_argnums=0)
        self._sharding = NamedSharding(mesh, P(AXIS))

    def mask_of(self, roles: tuple[str, ...]) -> int:
        return self._global.mask_of(roles)

    # ---- shard-local (inside shard_map) ------------------------------------

    def _admit_shard(self, pool, packed):
        batch, _now = RoleKernelSet._unpack(packed)
        return self._local._admit_roles(
            pool, shard_localize(batch, self.local_capacity))

    def _evict_shard(self, pool, slots):
        return shard_evict(self._local._base, pool, slots,
                           self.local_capacity)

    def _step_shard(self, pool, packed):
        from jax import lax as _lax

        from matchmaking_tpu.engine.sharded import AXIS

        batch, now = RoleKernelSet._unpack(packed)
        pool = self._local._admit_roles(
            pool, shard_localize(batch, self.local_capacity))

        full = {f: _lax.all_gather(pool[f], AXIS, tiled=True)
                for f in self._GATHER}
        g = self._global
        order, group = g._sorted_order(full)
        valid, spread, win_thr, split = g._windows_roles(full, order, group,
                                                         now)
        won = g._select_leftmost(valid)
        slots, is_match, w = extract_windows(
            won, g.need, g.max_matches, order, self.capacity)
        pool = shard_evict(self._local._base, pool, slots,
                           self.local_capacity)

        out = jnp.concatenate([
            slots.T.astype(jnp.float32),
            jnp.where(is_match, spread[w], jnp.inf)[None, :],
            jnp.where(is_match, win_thr[w], 0.0)[None, :],
            jnp.where(is_match, split[w], 0).astype(jnp.float32)[None, :]])
        return pool, out

    def _step_shard_ring(self, pool, packed):
        """Ring-scaled role step: frontier compaction (incl. role_mask) →
        ppermute ring → replicated leftmost-first cover selection on the
        merged D·K-row buffer. Host-gated on occupancy <= frontier_k; then
        bit-identical to ``_step_shard``."""
        from matchmaking_tpu.engine.sharded import ring_all_gather
        from matchmaking_tpu.engine.teams import (
            merge_frontiers,
            pack_frontier,
            pad_match_columns,
        )

        batch, now = RoleKernelSet._unpack(packed)
        pool = self._local._admit_roles(
            pool, shard_localize(batch, self.local_capacity))

        frontier = pack_frontier(pool, self._GATHER, self.frontier_k,
                                 self.local_capacity, self.capacity)
        (buf,) = ring_all_gather((frontier,), self.n_shards)
        full, gslot = merge_frontiers(buf, self._GATHER, self.n_shards,
                                      self.frontier_merge)
        g = self._ring_form
        order, group = g._sorted_order(full)
        valid, spread, win_thr, split = g._windows_roles(full, order, group,
                                                         now)
        won = g._select_leftmost(valid)
        slots_b, is_match, w = extract_windows(
            won, g.need, g.max_matches, order, g.capacity)
        gs = jnp.concatenate([gslot,
                              jnp.array([self.capacity], jnp.int32)])
        slots = gs[slots_b]
        pool = shard_evict(self._local._base, pool, slots,
                           self.local_capacity)

        out = jnp.concatenate([
            slots.T.astype(jnp.float32),
            jnp.where(is_match, spread[w], jnp.inf)[None, :],
            jnp.where(is_match, win_thr[w], 0.0)[None, :],
            jnp.where(is_match, split[w], 0).astype(jnp.float32)[None, :]])
        return pool, pad_match_columns(
            out, self.max_matches - g.max_matches, self.need, self.capacity,
            extra_zero_rows=1)

    def comms_accounting(self) -> dict:
        """Same accounting as the team family's (teams.py
        shard_comms_accounting), with the extra role_mask column priced in
        via this class's _GATHER."""
        from matchmaking_tpu.engine.teams import shard_comms_accounting

        return shard_comms_accounting(self)

    def place_pool(self, arrays):
        return {k: jax.device_put(jnp.asarray(v), self._sharding)
                for k, v in arrays.items()}


@functools.lru_cache(maxsize=None)
def sharded_role_kernel_set(capacity: int, team_size: int,
                            role_slots: tuple[str, ...],
                            widen_per_sec: float, max_threshold: float,
                            n_shards: int, max_matches: int = 1024,
                            rounds: int = 16, frontier_k: int = 0,
                            frontier_merge: str = "linear",
                            ) -> ShardedRoleKernelSet:
    from matchmaking_tpu.engine.sharded import pool_mesh

    return ShardedRoleKernelSet(
        capacity=capacity, team_size=team_size, role_slots=role_slots,
        widen_per_sec=widen_per_sec, max_threshold=max_threshold,
        mesh=pool_mesh(n_shards), max_matches=max_matches, rounds=rounds,
        frontier_k=frontier_k, frontier_merge=frontier_merge,
    )
