"""Multi-chip matching: the pool sharded over a device mesh.

This is the rebuild's distributed story (SURVEY.md §2 "Distributed
communication backend", §5 "Long-context / sequence parallelism"): where the
reference scales by adding broker consumers on one BEAM node, here the pool's
slot dimension is sharded over a ``jax.sharding.Mesh`` axis ``"pool"`` and
each window is matched with XLA collectives over ICI:

1. every shard scores the (replicated) request window against its local pool
   slice and keeps the best candidate per pool block (fused max/argmax —
   no score materialization) — compute scales 1/n per chip;
2. the tiny B×n_blocks candidate lists are collected across shards, either
   with one ``all_gather`` (default; ≤ a few hundred KB) or with a
   ``ppermute`` ring in which each hop passes a neighbor's candidates —
   structurally ring attention with "scores" = masked −distance and
   "softmax" = the best-candidate reduction (SURVEY.md §5's long-context
   analog);
3. greedy pairing runs replicated on the merged lists (deterministic, so all
   shards agree), and each shard evicts its own slice of the matched slots.

The merged lists contain the global best candidate per request (the best
per block of its own shard), in canonical block order, so sharded and
single-device engines produce identical matches — pinned by tests on the
8-virtual-device CPU mesh.

Interface matches ``KernelSet`` (admit / evict / search_step over a pool
dict + padded batch dict), so ``TpuEngine`` swaps it in transparently when
``EngineConfig.mesh_pool_axis > 1``.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from matchmaking_tpu.engine.kernels import (
    _NEG_INF,
    KernelSet,
    _effective_threshold,
    greedy_pair,
    named_jit,
    unpack_batch,
)

AXIS = "pool"


def ring_all_gather(xs: tuple, n: int, *, axis_name: str = AXIS) -> tuple:
    """Collect each shard's arrays on every shard, in CANONICAL shard order,
    with a ``ppermute`` neighbor ring instead of one ``all_gather``.

    The ring-attention communication pattern shared by all three queue
    families (1v1 candidate merge, team/role frontier exchange): the
    ORIGINAL local arrays rotate one hop per step — D−1 hops, each talking
    only to a neighbor — and every received block is scattered into its
    source shard's slot, so the final buffers are identical on every shard.
    Per-hop ICI traffic is the size of ONE shard's arrays, independent of
    the global pool. Must run inside ``shard_map``.

    Returns one array per input with a leading shard axis: ``(n, *x.shape)``.
    """
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]
    outs = [jnp.zeros((n,) + x.shape, x.dtype).at[my].set(x) for x in xs]
    rots = list(xs)
    for h in range(1, n):
        rots = [lax.ppermute(r, axis_name, perm) for r in rots]
        src = (my - h) % n
        outs = [o.at[src].set(r) for o, r in zip(outs, rots)]
    return tuple(outs)


def tournament_merge_topk(bufs: list, key_fn):
    """Tournament-tree top-k merge of per-shard SORTED frontier buffers
    (ISSUE 14 — the PR 1 follow-up replacing the linear O(K·D) merge).

    ``bufs`` holds D buffers f32[C, k] (one per shard, canonical shard
    order), each already sorted by the 3-component lexicographic key
    ``key_fn(buf) -> (group i32[k], rating f32[k], gslot i32[k])`` —
    exactly the order ``teams.sorted_group_order`` gives a shard's
    frontier. Pairwise stable merges up a ⌈log2 D⌉-level tree, each node
    keeping only the top-k merged rows, so the merged working buffer is
    O(K·log D) across the tree instead of the O(K·D) concatenation the
    linear path sorts and forms windows over. Keys are recomputed from
    the merged ROWS at every level (never value-merged), so integer key
    components stay exact regardless of magnitude.

    Exactness contract (the ring step's host gate): whenever the GLOBAL
    active population fits in k rows, the merged top-k contains every
    active row in exactly the order the concat-and-sort linear merge
    yields — ties (equal group AND rating) resolve by global slot id,
    which is also the concat order (shard-ascending, slot-ascending
    within a shard). Each merge node is scatter-free: dense rank
    compares (k×k) + one-hot HIGHEST matmuls, the codebase's select
    idiom — every output column receives exactly one row across the two
    terms, so values are bit-preserved.

    Returns the merged f32[C, k] buffer (identical on every shard when
    the inputs are).
    """
    def lt(ka, kb):
        """Strict lexicographic (group, rating, gslot) less-than; ka
        components broadcast as columns, kb as rows."""
        ga, ra, sa = ka
        gb, rb, sb = kb
        return ((ga < gb)
                | ((ga == gb) & (ra < rb))
                | ((ga == gb) & (ra == rb) & (sa < sb)))

    def merge2(fa, fb):
        n = fa.shape[1]
        ka = tuple(c[:, None] for c in key_fn(fa))
        kb = tuple(c[None, :] for c in key_fn(fb))
        # Stable merge ranks: a-rows win ties (a is the lower-shard side,
        # matching concat order; ties beyond the full key are gslot-equal
        # inactive padding, where order is output-irrelevant).
        b_before_a = lt(kb, ka)                    # [i, j]: b_j < a_i
        pos_a = jnp.arange(n, dtype=jnp.int32) + b_before_a.sum(
            axis=1, dtype=jnp.int32)
        a_not_after_b = ~b_before_a                # [i, j]: a_i <= b_j
        pos_b = jnp.arange(n, dtype=jnp.int32) + a_not_after_b.sum(
            axis=0, dtype=jnp.int32)
        out_pos = jnp.arange(n, dtype=jnp.int32)
        sel_a = (pos_a[:, None] == out_pos[None, :]).astype(jnp.float32)
        sel_b = (pos_b[:, None] == out_pos[None, :]).astype(jnp.float32)
        return (jnp.matmul(fa, sel_a, precision=lax.Precision.HIGHEST)
                + jnp.matmul(fb, sel_b, precision=lax.Precision.HIGHEST))

    level = list(bufs)
    while len(level) > 1:
        nxt = [merge2(level[i], level[i + 1])
               for i in range(0, len(level) - 1, 2)]
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    return level[0]


def pool_mesh(n_devices: int, devices: list | None = None) -> Mesh:
    """A 1-D mesh over the pool axis (multi-host: pass jax.devices())."""
    devs = (devices or jax.devices())[:n_devices]
    if len(devs) < n_devices:
        raise ValueError(
            f"mesh_pool_axis={n_devices} but only {len(devs)} devices visible"
        )
    return Mesh(np.array(devs), (AXIS,))


class ShardedKernelSet:
    """Compiled sharded step functions; same call surface as KernelSet."""

    def __init__(self, *, capacity: int, top_k: int, pool_block: int,
                 glicko2: bool, widen_per_sec: float, max_threshold: float,
                 mesh: Mesh, ring: bool = False, evict_bucket: int = 64,
                 pair_rounds: int = 8, bucket_frontier_k: int = 0):
        self.mesh = mesh
        self.n_shards = mesh.devices.size
        if capacity % self.n_shards != 0:
            capacity += self.n_shards - capacity % self.n_shards
        self.capacity = capacity
        self.local_capacity = capacity // self.n_shards
        self.ring = ring
        self.evict_bucket = evict_bucket
        self.pair_rounds = pair_rounds
        # Per-shard compute reuses the single-device kernel internals on the
        # LOCAL slice (capacity = local_capacity). Block geometry is derived
        # from the GLOBAL capacity first: identical block boundaries are what
        # make sharded and single-device candidate lists — and therefore
        # matches — identical (test_sharded_equals_single_device). When the
        # global block doesn't fit the local slice (pool_block >
        # local_capacity), blocks shrink to the slice and the two engines'
        # fallback candidates may legally differ under contention (the best
        # candidate, and so oracle semantics, are unaffected).
        from matchmaking_tpu.engine.kernels import effective_pool_block

        global_block = effective_pool_block(capacity, pool_block, top_k)
        self.local = KernelSet(
            capacity=self.local_capacity, top_k=top_k,
            pool_block=min(global_block, self.local_capacity),
            glicko2=glicko2,
            widen_per_sec=widen_per_sec, max_threshold=max_threshold,
            exact_block=True,
        )
        self.top_k = self.local.top_k
        self.widen_per_sec = widen_per_sec
        self.max_threshold = max_threshold
        #: Per-bucket top-K frontier exchange (ISSUE 14): > 0 enables the
        #: bucketed sharded step family — each shard compacts every LOCAL
        #: pool block (= rating bucket) into its top-K active rows and ONLY
        #: those frontiers cross the shard boundary (ppermute ring), so ICI
        #: traffic is occupancy-shaped (O(nb·K·D)) and per-window formation
        #: scores O(B · nb·K) frontier rows instead of O(B · P). Bit-exact
        #: vs the flat/dense candidate lists whenever every bucket's active
        #: population fits K rows — the host checks the mirror's per-segment
        #: occupancy (PlayerPool.segment_max, a conservative superset of
        #: device-active) per window and falls back to ``search_step_packed``
        #: above it. The value here is the LADDER CEILING; compiled steps
        #: are cached per actual K (``bucket_step``), so the engine sizes K
        #: adaptively from observed occupancy without recompiling the pool.
        self.bucket_frontier_k = (min(max(1, bucket_frontier_k),
                                      self.local.pool_block)
                                  if bucket_frontier_k > 0 else 0)
        self._bucket_steps: dict[int, Any] = {}
        self.local_blocks = self.local.n_blocks
        self.global_blocks = self.n_shards * self.local.n_blocks

        pool_spec = {k: P(AXIS) for k in
                     ("rating", "rd", "region", "mode", "threshold",
                      "enqueue_t", "active")}
        rep = P()
        batch_spec = {k: rep for k in
                      ("slot", "rating", "rd", "region", "mode", "threshold",
                       "enqueue_t", "valid")}

        self.search_step = named_jit(
            "sharded_search_step",
            jax.shard_map(
                self._search_step_shard, mesh=mesh,
                in_specs=(pool_spec, batch_spec, rep),
                out_specs=(pool_spec, rep, rep, rep),
                check_vma=False,
            ),
            donate_argnums=0,
        )
        self.admit = named_jit(
            "sharded_admit",
            jax.shard_map(self._admit_shard, mesh=mesh,
                          in_specs=(pool_spec, batch_spec), out_specs=pool_spec,
                          check_vma=False),
            donate_argnums=0,
        )
        self.evict = named_jit(
            "sharded_evict",
            jax.shard_map(self._evict_shard, mesh=mesh,
                          in_specs=(pool_spec, rep), out_specs=pool_spec,
                          check_vma=False),
            donate_argnums=0,
        )
        # Packed I/O variants (one replicated f32[9,B] in / f32[3,B] out —
        # single H2D/D2H transfer per window; see pool.PACKED_ROWS).
        self.search_step_packed = named_jit(
            "sharded_search_step_packed",
            jax.shard_map(
                self._search_step_packed_shard, mesh=mesh,
                in_specs=(pool_spec, rep), out_specs=(pool_spec, rep),
                check_vma=False,
            ),
            donate_argnums=0,
        )
        self.admit_packed = named_jit(
            "sharded_admit_packed",
            jax.shard_map(
                lambda pool, packed: self._admit_shard(pool, unpack_batch(packed)),
                mesh=mesh, in_specs=(pool_spec, rep), out_specs=pool_spec,
                check_vma=False,
            ),
            donate_argnums=0,
        )

    def _search_step_packed_shard(self, pool, packed):
        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool, out_q, out_c, out_d = self._search_step_shard(pool, batch, now)
        out = jnp.stack([out_q.astype(jnp.float32),
                         out_c.astype(jnp.float32), out_d])
        return pool, out

    # ---- helpers (run per shard, inside shard_map) ------------------------

    def _localize_batch(self, batch: dict[str, Any]) -> dict[str, Any]:
        """Global slot ids → this shard's local ids (others → sentinel)."""
        offset = lax.axis_index(AXIS) * self.local_capacity
        local = batch["slot"] - offset
        mine = (local >= 0) & (local < self.local_capacity)
        return dict(batch, slot=jnp.where(mine, local, self.local_capacity))

    def _admit_shard(self, pool, batch):
        return self.local._admit(pool, self._localize_batch(batch))

    def _evict_shard(self, pool, slots):
        offset = lax.axis_index(AXIS) * self.local_capacity
        local = slots - offset
        mine = (local >= 0) & (local < self.local_capacity)
        return self.local._evict(pool, jnp.where(mine, local, self.local_capacity))

    def _global_merge(self, vals, gidx):
        """Concatenate per-shard best-per-block candidate lists on every
        shard, in CANONICAL shard order.

        Canonical order matters: greedy pairing breaks exact-score ties by
        candidate position, so a shard-dependent merge order would let tied
        candidates win on some shards and lose on others — the "replicated"
        pairing would then diverge across shards and desynchronize device
        state from the host mirror (exact distance ties are common with
        integer ratings). Because shard s's local blocks cover the global
        slot range [s·localP, (s+1)·localP) in order, the merged list is
        exactly the single-device kernel's best-per-block list whenever the
        block geometry matches — pinned by test_sharded_equals_single_device.
        """
        n = self.n_shards
        b, k = vals.shape
        if not self.ring:
            av = lax.all_gather(vals, AXIS)            # (n, B, k), axis order
            ai = lax.all_gather(gidx, AXIS)
        else:
            # Ring collect (shared shard-exchange helper — the same
            # ppermute ring the team/role frontier paths ride).
            av, ai = ring_all_gather((vals, gidx), n)
        av = jnp.moveaxis(av, 0, 1).reshape(b, n * k)
        ai = jnp.moveaxis(ai, 0, 1).reshape(b, n * k)
        return av, ai

    # ---- the sharded step -------------------------------------------------

    def _search_step_shard(self, pool, batch, now):
        lk = self.local
        offset = lax.axis_index(AXIS) * self.local_capacity

        # 1. Admit this shard's slice of the window.
        local_batch = self._localize_batch(batch)
        pool = lk._admit(pool, local_batch)

        # 2. Local best-per-block candidates against the local pool slice.
        #    The batch keeps its GLOBAL slot ids for self-masking: compare
        #    against global index.
        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )
        # Self-mask needs global ids: shift the batch slots into the local
        # frame (non-local ids land outside [0, local_capacity) and thus
        # never self-mask, which is correct — the self slot lives on exactly
        # one shard).
        vals, idxs_local = lk._candidates(
            dict(batch, slot=batch["slot"] - offset), q_thr_eff, pool, now
        )
        gidx = jnp.where(idxs_local >= self.local_capacity,
                         self.capacity, idxs_local + offset)

        # 3. Canonical-order global candidate lists on every shard
        #    (all_gather or ppermute ring).
        mv, mi = self._global_merge(vals, gidx)

        # 4. Replicated greedy pairing on global ids (deterministic — every
        #    shard computes the identical pairing, no broadcast needed).
        out_q, out_c, out_d = greedy_pair(mv, mi, batch["slot"], self.capacity,
                                          self.pair_rounds)

        # 5. Each shard evicts its slice of the matched slots (compare-masked
        #    via the local kernel's scatter-free eviction).
        matched = jnp.concatenate([out_q, out_c]) - offset
        mine = (matched >= 0) & (matched < self.local_capacity)
        pool = lk._evict(pool, jnp.where(mine, matched, self.local_capacity))
        return pool, out_q, out_c, out_d

    # ---- bucket-frontier step family (ISSUE 14) ---------------------------

    def bucket_step(self, k: int):
        """The compiled bucket-frontier step for frontier width ``k``
        (lazily compiled, cached per K — the adaptive-K ladder's entries).
        Same call surface as ``search_step_packed`` but the result is
        f32[4, B]: rows 0-2 the flat layout, row 3 the touched-slot count.
        Only valid while every bucket's live population fits ``k`` rows
        (host-gated via the mirror's per-segment occupancy)."""
        k = min(max(1, k), self.local.pool_block)
        fn = self._bucket_steps.get(k)
        if fn is None:
            pool_spec = {f: P(AXIS) for f in
                         ("rating", "rd", "region", "mode", "threshold",
                          "enqueue_t", "active")}
            rep = P()
            fn = named_jit(
                f"sharded_bucket_step_k{k}",
                jax.shard_map(
                    functools.partial(self._search_step_bucket_shard, k=k),
                    mesh=self.mesh, in_specs=(pool_spec, rep),
                    out_specs=(pool_spec, rep), check_vma=False),
                donate_argnums=0)
            self._bucket_steps[k] = fn
        return fn

    def _pack_block_frontier(self, pool, k: int):
        """Per-LOCAL-block top-k frontier: f32[nb_local, 8, k] rows =
        (rating, rd, region, mode, threshold, enqueue_t, active, gslot),
        active rows first in slot-ascending order (stable argsort of the
        inactive flag), padding rows carry the capacity sentinel. When a
        block holds ≤ k active rows the frontier contains ALL of them —
        the no-overflow precondition the host gate enforces. Must run
        inside shard_map."""
        lk = self.local
        blk = lk.pool_block
        offset = lax.axis_index(AXIS) * self.local_capacity
        fields = ("rating", "rd", "region", "mode", "threshold", "enqueue_t")

        def body(_, blk_i):
            start = blk_i * blk
            act = lax.dynamic_slice_in_dim(pool["active"], start, blk)
            top = jnp.argsort(~act, stable=True)[:k]
            rows = [lax.dynamic_slice_in_dim(pool[f], start, blk)[top]
                    .astype(jnp.float32) for f in fields]
            a = act[top]
            gslot = jnp.where(a, start + top + offset,
                              self.capacity).astype(jnp.float32)
            return None, jnp.stack(rows + [a.astype(jnp.float32), gslot])

        _, fr = lax.scan(body, None,
                         jnp.arange(lk.n_blocks, dtype=jnp.int32))
        return fr

    def _search_step_bucket_shard(self, pool, packed, k: int):
        """One window via per-bucket top-K frontier exchange: local admit →
        per-block frontier compaction (O(P/D) column reads) → ppermute ring
        (ONLY frontiers cross the shard boundary) → replicated bucket-local
        scoring over the merged nb_global·K frontier rows → replicated
        pairing → local eviction. Bit-exact vs the dense candidate lists
        while no bucket overflows K (host-gated)."""
        lk = self.local
        batch = unpack_batch(packed)
        now = packed[8, 0]
        b = batch["rating"].shape[0]
        offset = lax.axis_index(AXIS) * self.local_capacity

        pool = lk._admit(pool, self._localize_batch(batch))
        fr = self._pack_block_frontier(pool, k)
        (buf,) = ring_all_gather((fr,), self.n_shards)
        # (n, nb_local, 8, k) → (nb_global, 8, k) in canonical block order.
        fr_g = buf.reshape(self.global_blocks, 8, k)

        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )

        def body(_, fb):
            block = {"rating": fb[0], "rd": fb[1],
                     "region": fb[2].astype(jnp.int32),
                     "mode": fb[3].astype(jnp.int32),
                     "threshold": fb[4], "enqueue_t": fb[5],
                     "active": fb[6] > 0.5}
            gslot = fb[7].astype(jnp.int32)
            not_self = batch["slot"][:, None] != gslot[None, :]
            scores = lk._score_block(batch, q_thr_eff, block, 0, now,
                                     not_self=not_self)
            v, i = lk._block_best(scores)
            return None, (v, jnp.take(gslot, i))

        _, (vs, is_) = lax.scan(body, None, fr_g)
        vals = vs.T                                 # (B, nb_global)
        idxs = jnp.where(vals > _NEG_INF, is_.T, self.capacity)

        out_q, out_c, out_d = greedy_pair(vals, idxs, batch["slot"],
                                          self.capacity, self.pair_rounds)

        matched = jnp.concatenate([out_q, out_c]) - offset
        mine = (matched >= 0) & (matched < self.local_capacity)
        pool = lk._evict(pool, jnp.where(mine, matched, self.local_capacity))

        touched = jnp.float32(min(self.global_blocks * k, self.capacity))
        out = jnp.concatenate([
            jnp.stack([out_q.astype(jnp.float32),
                       out_c.astype(jnp.float32), out_d]),
            jnp.broadcast_to(touched, (1, b))])
        return pool, out

    # ---- placement --------------------------------------------------------

    def place_pool(self, arrays: dict[str, np.ndarray]) -> dict[str, jnp.ndarray]:
        sharding = NamedSharding(self.mesh, P(AXIS))
        return {k: jax.device_put(jnp.asarray(v), sharding)
                for k, v in arrays.items()}


@functools.lru_cache(maxsize=None)
def sharded_kernel_set(capacity: int, top_k: int, pool_block: int,
                       glicko2: bool, widen_per_sec: float,
                       max_threshold: float, n_shards: int,
                       ring: bool, pair_rounds: int = 8,
                       device_ids: "tuple[int, ...] | None" = None,
                       bucket_frontier_k: int = 0,
                       ) -> ShardedKernelSet:
    """``device_ids`` (elastic placement, ISSUE 11): the logical device
    indices the pool mesh spans — None keeps the pre-placement default
    (the first ``n_shards`` of ``jax.devices()``).  Part of the cache key:
    the same shape promoted onto a different chip pair is a different
    compiled set."""
    devices = None
    if device_ids is not None:
        if len(device_ids) != n_shards:
            raise ValueError(
                f"device_ids {device_ids} must match n_shards={n_shards}")
        all_devs = jax.devices()
        devices = [all_devs[i] for i in device_ids]
    return ShardedKernelSet(
        capacity=capacity, top_k=top_k, pool_block=pool_block, glicko2=glicko2,
        widen_per_sec=widen_per_sec, max_threshold=max_threshold,
        mesh=pool_mesh(n_shards, devices), ring=ring, pair_rounds=pair_rounds,
        bucket_frontier_k=bucket_frontier_k,
    )
