"""Jitted matching kernels — the TPU replacement for the reference hot loop.

The reference scans the ETS pool sequentially per request (SURVEY.md §3
Entry 2, the O(requests × pool) wall). Here one jitted step processes a whole
request window against the whole pool:

    fused blockwise admit+score+top-k (one pass over the pool)
    → dense greedy conflict-free pairing → compare-masked eviction

TPU-first design notes (SURVEY.md §7 step 2):

- **NO scatters anywhere.** XLA lowers generic scatters on TPU to a serial
  loop over updates (~3 µs/update ⇒ ~24 ms for a 1k-window admit, measured);
  every scatter here is replaced by dense compare/select/matmul forms that
  run on the VPU/MXU: admission is an equality-matrix matmul per pool block,
  eviction is a compare-reduce mask, pairing conflicts are a B×B matrix.
  Measured effect: 42 ms → ~1 ms per 1k-request window at P=128k.
- **One fused pass over the pool**: `lax.scan` over pool blocks does
  admit + score + streaming top-k together; the scan's stacked per-block
  outputs ARE the updated pool (blocks are disjoint slices, so
  `reshape(n_blocks·blk)` reassembles the arrays).
- **Exact 2-stage top-k**: per block, reduce 128-lane sublanes to their max,
  `lax.top_k` over sublane maxima, gather the winning sublanes, then top-k
  within them. Exact because an element of global rank ≤ k lives in a
  sublane whose max also has rank ≤ k. ~3× faster than top-k over the raw
  block (sort width shrinks 128×).
- **Static shapes everywhere**: pool capacity P, window bucket B, top-k K and
  pool block size are compile-time constants; XLA compiles each (B, queue
  config) pair once and the hot path never recompiles.
- **No data-dependent Python control flow**: the pairing loop is a
  `lax.fori_loop` with a fixed trip count; invalid lanes ride along masked.

Everything here is pure: (pool arrays, batch arrays, now) → (new pool
arrays, match arrays). Purity makes the device side race-free by
construction (SURVEY.md §5 "Race detection") and lets the sharded engine
reuse the same building blocks under `shard_map`.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from matchmaking_tpu.engine import scoring

_NEG_INF = jnp.float32(-jnp.inf)

#: Numeric pool fields admitted from a batch (active is handled separately).
_ADMIT_FIELDS = ("rating", "rd", "region", "mode", "threshold", "enqueue_t")

#: Device-resident bucket-index columns (ISSUE 14) carried INSIDE the pool
#: dict of a bucketed KernelSet, one element per pool block (= rating
#: bucket under band_spec): live occupancy, conservative rating bounds,
#: and the max rating deviation. Bounds only WIDEN incrementally
#: (admission merges the window's stats in; eviction leaves them) — always
#: a superset of the true live bounds, which is exactly what the span
#: math needs for bit-exactness; ``index_rebuild`` re-tightens them with
#: one O(P) scan off the hot path. Counts are exact (± per admitted /
#: evicted slot); the span math reads only the bounds — counts are the
#: index's occupancy half, kept current because (a) the one-hot
#: membership sums maintaining them are O(nb·B), noise next to the
#: window's O(B·W·blk) score work, (b) the counts==rebuild invariant is
#: what the tests pin the whole incremental maintenance against, and
#: (c) device-side per-bucket frontier-K sizing is the named follow-up
#: consumer.
INDEX_FIELDS = ("bidx_count", "bidx_min", "bidx_max", "bidx_rd")


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` under ``name``: the compiled module is
    ``jit_<name>`` and the host's launch events ``PjitFunction(<name>)``,
    so a profile says which step ran. Steps are named after the attribute
    that holds them; a lambda or ``functools.partial`` would otherwise
    trace as ``jit__lambda_`` / ``jit__unknown``."""
    def step(*args):
        return fn(*args)

    step.__name__ = step.__qualname__ = name
    return jax.jit(step, **jit_kwargs)


def unpack_batch(packed) -> dict[str, Any]:
    """f32[8, B] (see pool.PACKED_ROWS) → the batch dict the kernels use.
    One packed array = one H2D transfer per window instead of 8."""
    return {
        "slot": packed[0].astype(jnp.int32),
        "rating": packed[1],
        "rd": packed[2],
        "region": packed[3].astype(jnp.int32),
        "mode": packed[4].astype(jnp.int32),
        "threshold": packed[5],
        "enqueue_t": packed[6],
        "valid": packed[7] > 0,
    }


def _effective_threshold(thr, enqueue_t, now, widen_per_sec: float, max_threshold: float):
    """Config-gated threshold widening by wait time (SURVEY.md §2 C9)."""
    if widen_per_sec <= 0.0:
        return thr
    waited = jnp.maximum(0.0, now - enqueue_t)
    return jnp.minimum(jnp.float32(max_threshold), thr + jnp.float32(widen_per_sec) * waited)


# scoring.py is the semantic source of truth; its functions are plain
# broadcastable math, valid on jnp arrays inside jit (the glicko2 flag is a
# static Python bool, so tracing stays branch-free).
_pair_distance = scoring.distance


def _admit_block(pool_block: dict[str, Any], start, blk: int,
                 batch: dict[str, Any], eq=None,
                 fields: tuple[str, ...] = _ADMIT_FIELDS) -> dict[str, Any]:
    """Admission into one pool block, scatter-free.

    ``eq`` is the (blk, B) equality matrix between block positions and the
    window's slot ids (padding lanes carry the sentinel capacity ⇒ never
    equal); the hot-path scan passes it in so the SAME compare also serves
    scoring's self-mask (one B×P pass instead of two). Each real slot is
    unique, so ``eq @ vals`` selects exactly the admitted lane's values;
    int fields round-trip through f32 exactly (interner codes ≪ 2^24).
    Precision must be HIGHEST: the TPU MXU's DEFAULT f32 matmul multiplies
    in bf16, which would round admitted ratings to ~8-bit mantissa (±4 ELO
    at 1500 — corrupts matching near the threshold); with HIGHEST the
    0/1 × value products are exact and each output row has exactly one
    nonzero term, so the select is bit-exact.
    """
    pos = start + jnp.arange(blk, dtype=jnp.int32)
    if eq is None:
        eq = batch["slot"][None, :] == pos[:, None]
    hit = eq.any(axis=1)
    vals = jnp.stack(
        [batch[f].astype(jnp.float32) for f in fields], axis=1)
    scat = jnp.matmul(eq.astype(jnp.float32), vals,
                      precision=lax.Precision.HIGHEST)    # (blk, n_fields)
    out = {}
    for j, f in enumerate(fields):
        new = scat[:, j].astype(pool_block[f].dtype)
        out[f] = jnp.where(hit, new, pool_block[f])
    out["active"] = pool_block["active"] | hit
    return out


def effective_pool_block(capacity: int, pool_block: int, top_k: int,
                         *, min_blocks: bool = True) -> int:
    """The scan block size actually used for a pool geometry.

    Two constraints beyond the configured value:

    - it must divide ``capacity`` (uniform ``lax.scan``) — rounded down by
      halving;
    - candidate lists are best-per-block (``_block_best``), so their width
      IS ``capacity // pool_block``; a window's conflict losers need
      fallback candidates, which means small pools must split into at least
      ``min(top_k, capacity // 128)`` blocks (the 128 floor keeps blocks at
      lane width — below that more fallbacks aren't worth sub-lane blocks).

    Centralized so the sharded engine can derive the SAME geometry from the
    global capacity (see ShardedKernelSet) — identical block boundaries are
    what make sharded and single-device matches identical. The sharded
    engine passes ``min_blocks=False`` for its LOCAL kernels: its fallback
    width is ``n_shards × local_blocks``, already set by the global
    derivation, and a local re-derivation would shrink blocks and break the
    shared geometry.
    """
    pool_block = max(1, min(pool_block, capacity))
    while capacity % pool_block != 0:
        pool_block //= 2
    if min_blocks:
        # Each halving must re-establish divisibility: halving an odd
        # divisor (e.g. capacity=510, pool_block=255 → 127) would otherwise
        # return a non-divisor and the scan would cover n_blocks·blk ≠
        # capacity slots (trace-time reshape failure).
        need = min(top_k, max(1, capacity // 128))
        while capacity // pool_block < need and pool_block > 1:
            pool_block //= 2
            while capacity % pool_block != 0:
                pool_block //= 2
    return pool_block


def _mask_members(active, start, blk: int, slots) -> jnp.ndarray:
    """active & (position ∉ slots) — the scatter-free eviction mask.

    ``slots`` may contain the sentinel capacity (never equal to a block
    position)."""
    pos = start + jnp.arange(blk, dtype=jnp.int32)
    hit = (slots[None, :] == pos[:, None]).any(axis=1)
    return active & ~hit


def greedy_pair(vals, idxs, self_slot, capacity: int, rounds: int = 8,
                rid=None):
    """Parallel greedy conflict-free pairing over B×K candidate lists.

    A fixed number of proposal rounds (Luby-style parallel greedy matching —
    the TPU-friendly replacement for picking edges one at a time, which
    would be B sequential argmax steps):

    1. every live request proposes its best remaining candidate;
    2. a proposal survives iff NO conflicting proposal (sharing either
       endpoint slot) is lexicographically better (higher score, ties to the
       lower row index) — one dense B×B conflict matrix, no scatters;
    3. winners retire both endpoint slots (membership compares against the
       accumulated winner-slot lists); losers re-propose next round.

    The lexicographically-best live edge always wins, so every round forms
    ≥1 match while feasible edges remain; with K candidates per row,
    ``rounds`` ≈ K retains effectively everything a fully sequential greedy
    pass would form (leftovers stay in the pool for the next window — same
    semantics as exhausting the K-deep candidate list). Deterministic, so
    the sharded engine can run it replicated on every shard. A NumPy mirror
    of this exact scheme is the oracle in tests. Slot ids may be local
    (single device, ``capacity`` = P) or global (sharded, ``capacity`` =
    n·P_local) — ids < capacity are real, >= capacity are padding.

    Returns (q_slot i32[B], c_slot i32[B], dist f32[B]), row-indexed;
    unmatched lanes hold the sentinel ``capacity`` / +inf.

    ``rid`` overrides the tie-break row ids (default: row position). The
    pruned step runs pairing over a rating-SORTED window; passing the
    original lane ids keeps exact-tie resolution identical to the dense
    step, so sorting cannot change which edge wins a conflict.

    The loop exits as soon as no live proposal remains (every row matched,
    dead, or out of candidates). Early exit is output-exact: a round with
    no live proposal forms no match and changes no state, so skipping the
    remaining rounds returns bit-identical results — and pairing typically
    converges in ~3 rounds at the bench operating point (measured round-5:
    4096-window vs 100k pool forms 97% of its matches in round 1), so the
    default 8-round budget mostly buys no-op rounds at ~0.5 ms each. The
    exit predicate is data-dependent but replicated-consistent: the sharded
    engine runs this on identical merged candidates on every shard, so all
    shards take the same trip count.
    """
    b, k = vals.shape
    cap = jnp.int32(capacity)
    if rid is None:
        rid = jnp.arange(b, dtype=jnp.int32)
    not_diag = ~jnp.eye(b, dtype=bool)

    def cond(state):
        r, live_any, *_ = state
        return (r < rounds) & live_any

    def body(state):
        r, _, row_dead, cand_dead, out_q, out_c, out_d = state
        masked = jnp.where(cand_dead | row_dead[:, None], _NEG_INF, vals)
        bj = jnp.argmax(masked, axis=1)
        bv = jnp.take_along_axis(masked, bj[:, None], axis=1)[:, 0]
        bc = jnp.take_along_axis(idxs, bj[:, None], axis=1)[:, 0]
        live = bv > _NEG_INF
        # Dense conflict matrix: proposals sharing either endpoint.
        conflict = (
            (self_slot[:, None] == self_slot[None, :])
            | (self_slot[:, None] == bc[None, :])
            | (bc[:, None] == self_slot[None, :])
            | (bc[:, None] == bc[None, :])
        ) & live[None, :] & live[:, None] & not_diag
        better = (bv[None, :] > bv[:, None]) | (
            (bv[None, :] == bv[:, None]) & (rid[None, :] < rid[:, None]))
        win = live & ~(conflict & better).any(axis=1)

        out_q = jnp.where(win, self_slot, out_q)
        out_c = jnp.where(win, bc, out_c)
        out_d = jnp.where(win, -bv, out_d)
        # Retire both endpoints of every winner (sentinel for losers) with
        # dense compares against THIS round's winner slots: big dense beats
        # small irregular on TPU, where a sort+searchsorted+take alternative
        # serializes its small gathers.
        wq = jnp.where(win, self_slot, cap)
        wc = jnp.where(win, bc, cap)
        cand_dead = (cand_dead
                     | (idxs[:, :, None] == wq[None, None, :]).any(-1)
                     | (idxs[:, :, None] == wc[None, None, :]).any(-1))
        row_dead = (row_dead
                    | (self_slot[:, None] == wq[None, :]).any(-1)
                    | (self_slot[:, None] == wc[None, :]).any(-1))
        # Liveness for the NEXT round: any candidate still proposable.
        live_any = (jnp.where(cand_dead | row_dead[:, None], _NEG_INF, vals)
                    > _NEG_INF).any()
        return r + 1, live_any, row_dead, cand_dead, out_q, out_c, out_d

    init = (
        jnp.int32(0),
        jnp.bool_(True),
        jnp.zeros(b, jnp.bool_),
        jnp.zeros((b, k), jnp.bool_),
        jnp.full(b, capacity, jnp.int32),
        jnp.full(b, capacity, jnp.int32),
        jnp.full(b, jnp.inf, jnp.float32),
    )
    _, _, _, _, out_q, out_c, out_d = lax.while_loop(cond, body, init)
    return out_q, out_c, out_d


class KernelSet:
    """Compiled step functions for one (pool geometry × queue config).

    Parameters are static (baked into the compiled executables); per-call
    data is only arrays + the ``now`` scalar.
    """

    def __init__(self, *, capacity: int, top_k: int, pool_block: int,
                 glicko2: bool, widen_per_sec: float, max_threshold: float,
                 evict_bucket: int = 64, pair_rounds: int = 8,
                 exact_block: bool = False, prune_window_blocks: int = 0,
                 prune_chunk: int = 128, bucketed: bool = False):
        pool_block = effective_pool_block(capacity, pool_block, top_k,
                                          min_blocks=not exact_block)
        self.capacity = capacity
        self.top_k = min(top_k, pool_block)
        self.pool_block = pool_block
        self.n_blocks = capacity // pool_block
        self.glicko2 = glicko2
        self.widen_per_sec = widen_per_sec
        self.max_threshold = max_threshold
        self.evict_bucket = evict_bucket
        self.pair_rounds = pair_rounds
        # Hierarchical rating-bucketed formation (ISSUE 14): the pool dict
        # carries a per-block bucket index (INDEX_FIELDS) maintained
        # incrementally by every admit/evict/step, and window formation
        # derives its candidate spans from the index instead of the O(P)
        # per-window _live_stats scan — the span machinery (and its
        # bit-exactness argument) is the pruned step's.
        self.bucketed = bucketed
        if bucketed and prune_window_blocks <= 0:
            # Default span width: a quarter of the pool's blocks — wide
            # enough for mid-distribution chunks at the default threshold
            # under band_spec, still sub-O(P).
            prune_window_blocks = max(2, self.n_blocks // 4)
        # Rating-banded candidate pruning (bit-exact — see
        # _search_step_pruned). 0 disables; values ≥ n_blocks degenerate to
        # scoring every block through the pruned plumbing.
        self.prune_window_blocks = min(max(0, prune_window_blocks),
                                       self.n_blocks)
        self.prune_chunk = max(1, prune_chunk)

        if bucketed:
            step = self._search_step_bucketed
        elif self.prune_window_blocks:
            step = self._search_step_pruned
        else:
            step = self._search_step
        self._step_impl = step
        if bucketed:
            self.admit = named_jit("admit", self._admit_indexed,
                                   donate_argnums=0)
            self.evict = named_jit("evict", self._evict_indexed,
                                   donate_argnums=0)
            self.search_step = named_jit(
                "search_step",
                lambda pool, batch, now: self._search_step_bucketed(
                    pool, batch, now)[:4], donate_argnums=0)
            self.admit_packed = named_jit(
                "admit_packed",
                lambda pool, packed: self._admit_indexed(
                    pool, unpack_batch(packed)), donate_argnums=0)
            self.search_step_packed = named_jit(
                "search_step_packed", self._search_step_packed_bucketed,
                donate_argnums=0)
            self.search_step_packed_nofilter = named_jit(
                "search_step_packed_nofilter",
                functools.partial(self._search_step_packed_bucketed,
                                  skip_filters=True), donate_argnums=0)
            self.search_step_packed_rescan = named_jit(
                "search_step_packed_rescan",
                self._rescan_step_packed_bucketed, donate_argnums=0)
            # Speculative-formation twin (ISSUE 16): the SAME rescan trace
            # jitted WITHOUT donation — the caller's input pool handle must
            # survive as the bit-exact fallback basis while the speculative
            # output pool waits for cut-time validation. Identical math to
            # search_step_packed_rescan (donation changes buffer reuse, not
            # results), which is what the commit-equals-cold-rescan proof
            # leans on.
            self.search_step_packed_spec = named_jit(
                "search_step_packed_spec", self._rescan_step_packed_bucketed)
            self.index_rebuild = named_jit("index_rebuild",
                                           self._index_rebuild,
                                           donate_argnums=0)
            return
        self.admit = named_jit("admit", self._admit, donate_argnums=0)
        self.evict = named_jit("evict", self._evict, donate_argnums=0)
        self.search_step = named_jit("search_step", step, donate_argnums=0)
        # Packed I/O variants: one f32[8,B] in, one f32[3,B] out — a single
        # H2D and a single D2H transfer per window.
        self.admit_packed = named_jit(
            "admit_packed",
            lambda pool, packed: self._admit(pool, unpack_batch(packed)),
            donate_argnums=0)
        self.search_step_packed = named_jit(
            "search_step_packed", self._search_step_packed, donate_argnums=0)
        # All-ANY-window variant: identical outputs when no window lane
        # carries a region/mode constraint (see _score_block); ~40% fewer
        # per-cell mask ops in the dominant score scan. The engine selects
        # per window on the host. Each variant compiles on first use; a
        # deployment that must never pay that stall mid-serving sets
        # EngineConfig.warm_start, which compiles BOTH variants for every
        # bucket at app start (TpuEngine.warmup).
        self.search_step_packed_nofilter = named_jit(
            "search_step_packed_nofilter",
            functools.partial(self._search_step_packed, skip_filters=True),
            donate_argnums=0)
        # Rescan variant: NO admission, lane validity gated by the
        # device-side active flag (see _rescan_step). What makes rescans
        # overlap in-flight windows AND span multiple chunks safely.
        self.search_step_packed_rescan = named_jit(
            "search_step_packed_rescan", self._search_step_packed_rescan,
            donate_argnums=0)
        # Non-donated speculative twin — see the bucketed branch note.
        self.search_step_packed_spec = named_jit(
            "search_step_packed_spec", self._search_step_packed_rescan)

    def _search_step_packed(self, pool, packed, skip_filters: bool = False):
        """Packed window step: batch rows per pool.PACKED_ROWS plus a 9th row
        whose [0] element is the rebased ``now`` scalar; output stacks
        (q_slot, c_slot, dist) as f32[3, B] (slot ids ≪ 2^24 are f32-exact)."""
        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool, out_q, out_c, out_d = self._step_impl(pool, batch, now,
                                                    skip_filters)
        out = jnp.stack([out_q.astype(jnp.float32),
                         out_c.astype(jnp.float32), out_d])
        return pool, out

    def _rescan_step(self, pool: dict[str, Any], batch: dict[str, Any], now):
        """No-admission window step for rescans.

        The regular step's fused admission is what made rescans require a
        drained pipeline: a window built from the not-yet-finalized host
        mirror could re-admit (resurrect) a slot an in-flight window had
        already matched and evicted on device. Here nothing is admitted and
        every lane's validity is ANDed with the DEVICE-side active flag of
        its own slot, so a stale lane is simply a no-op — which makes it
        safe to (a) dispatch rescans while windows are in flight (steps
        chain in order on the donated pool) and (b) split one rescan tick
        into many chunks covering the whole pool (a later chunk cannot
        re-match players an earlier chunk retired). Scoring, pairing, and
        eviction are the dense step's; rescans are off the hot path, so no
        nofilter/pruned variants."""
        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )
        lane_act = jnp.take(pool["active"],
                            jnp.clip(batch["slot"], 0, self.capacity - 1))
        batch = dict(batch, valid=batch["valid"] & lane_act)
        vals, idxs = self._candidates(batch, q_thr_eff, pool, now)
        out_q, out_c, out_d = self.greedy_pair(vals, idxs, batch["slot"])
        pool = self._evict(pool, jnp.concatenate([out_q, out_c]))
        return pool, out_q, out_c, out_d

    def _search_step_packed_rescan(self, pool, packed):
        """Packed-I/O twin of _rescan_step (same layout as
        _search_step_packed)."""
        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool, out_q, out_c, out_d = self._rescan_step(pool, batch, now)
        out = jnp.stack([out_q.astype(jnp.float32),
                         out_c.astype(jnp.float32), out_d])
        return pool, out

    # ---- admission / eviction --------------------------------------------

    def _admit(self, pool: dict[str, Any], batch: dict[str, Any]) -> dict[str, Any]:
        """Admit a padded window (standalone path for restore(); the hot
        path fuses admission into the search scan)."""
        blk = self.pool_block

        def body(_, blk_i):
            start = blk_i * blk
            block = {f: lax.dynamic_slice_in_dim(pool[f], start, blk)
                     for f in (*_ADMIT_FIELDS, "active")}
            return None, _admit_block(block, start, blk, batch)

        _, blocks = lax.scan(body, None, jnp.arange(self.n_blocks, dtype=jnp.int32))
        return {f: blocks[f].reshape(self.capacity) for f in blocks}

    def _evict(self, pool: dict[str, Any], slots: jnp.ndarray) -> dict[str, Any]:
        blk = self.pool_block

        def body(_, blk_i):
            start = blk_i * blk
            a = lax.dynamic_slice_in_dim(pool["active"], start, blk)
            return None, _mask_members(a, start, blk, slots)

        _, blocks = lax.scan(body, None, jnp.arange(self.n_blocks, dtype=jnp.int32))
        return dict(pool, active=blocks.reshape(self.capacity))

    # ---- scoring ----------------------------------------------------------

    def _score_block(self, batch: dict[str, Any], q_thr_eff, block: dict[str, Any],
                     start, now, skip_filters: bool = False, not_self=None):
        """Masked scores of the window vs one pool block: f32[B, block].

        Block width comes from the arrays (not ``self.pool_block``): the
        pruned step scores window chunks against W-block spans in one call.

        ``skip_filters`` (static) drops the region/mode mask math — the
        B×blk compare/or chains are ~40% of the per-cell ops. Bit-exact
        whenever every WINDOW lane carries the ANY wildcard (code 0):
        ``(q==0) | ...`` is then identically true regardless of pool
        contents, so the masks it skips were all-ones. The engine checks
        the window on the host and picks the matching compiled variant."""
        blk = block["rating"].shape[0]
        d = _pair_distance(
            batch["rating"][:, None], block["rating"][None, :],
            batch["rd"][:, None], block["rd"][None, :], glicko2=self.glicko2,
        )
        c_thr_eff = _effective_threshold(block["threshold"], block["enqueue_t"],
                                         now, self.widen_per_sec, self.max_threshold)
        limit = jnp.minimum(q_thr_eff[:, None], c_thr_eff[None, :])

        if not_self is None:
            global_idx = start + jnp.arange(blk, dtype=jnp.int32)
            not_self = batch["slot"][:, None] != global_idx[None, :]

        valid = (
            block["active"][None, :] & batch["valid"][:, None]
            & not_self & (d <= limit)
        )
        if not skip_filters:
            q_reg, q_mod = batch["region"][:, None], batch["mode"][:, None]
            c_reg, c_mod = block["region"][None, :], block["mode"][None, :]
            region_ok = (q_reg == 0) | (c_reg == 0) | (q_reg == c_reg)
            mode_ok = (q_mod == 0) | (c_mod == 0) | (q_mod == c_mod)
            valid = valid & region_ok & mode_ok
        return jnp.where(valid, -d, _NEG_INF)

    @staticmethod
    def _block_best(scores):
        """Best candidate of f32[B, blk]: pure max+argmax reduces.

        No gathers, no sorts — XLA fuses the score computation straight
        into the reduction, so the (B, blk) score matrix is never
        materialized in HBM. Candidate LISTS come from stacking one best
        per pool block: distinct blocks ⇒ distinct slots, and the global
        best candidate (the oracle's pick) is always present, so pairing
        retains reference semantics; conflict losers fall back to their
        best in OTHER blocks (instead of the global runner-up), which only
        matters under contention where the oracle order is already
        arrival-dependent (see tests/test_oracle_equiv.py layer 2)."""
        return scores.max(axis=1), jnp.argmax(scores, axis=1)

    def _candidates(self, batch: dict[str, Any], q_thr_eff,
                    pool: dict[str, Any], now, skip_filters: bool = False):
        """Best-per-block candidate lists: (vals f32[B, n_blocks],
        idx i32[B, n_blocks]), fully fused (no score materialization).

        Standalone (no admission) — the sharded engine admits separately
        per shard; the single-device hot path fuses admission into the
        same scan in ``_search_step``. Unmatched lanes hold the sentinel
        ``capacity`` / −inf."""
        blk = self.pool_block

        def body(_, blk_i):
            start = blk_i * blk
            block = {f: lax.dynamic_slice_in_dim(pool[f], start, blk)
                     for f in (*_ADMIT_FIELDS, "active")}
            scores = self._score_block(batch, q_thr_eff, block, start, now,
                                       skip_filters)
            v, i = self._block_best(scores)
            return None, (v, (i + start).astype(jnp.int32))

        _, (vs, is_) = lax.scan(body, None,
                                jnp.arange(self.n_blocks, dtype=jnp.int32))
        vals = vs.T                                       # (B, n_blocks)
        idxs = jnp.where(vals > _NEG_INF, is_.T, self.capacity)
        return vals, idxs

    # ---- pairing ----------------------------------------------------------

    def greedy_pair(self, vals, idxs, self_slot):
        return greedy_pair(vals, idxs, self_slot, self.capacity, self.pair_rounds)

    # ---- the full step ----------------------------------------------------

    def _candidates_admitting(self, pool: dict[str, Any], batch: dict[str, Any],
                              q_thr_eff, now, skip_filters: bool = False):
        """The fused admit+score+block-best scan — THE dense hot path (also
        the pruned step's whole-window fallback). Returns (pool', vals
        f32[B, n_blocks], idxs i32[B, n_blocks]).

        A Pallas variant (engine/pallas_kernels.pallas_block_best) exists
        as a pinned reference: measured on v5e it ties this scan once both
        avoid materializing scores, and its separate admit pass costs
        ~20 µs of HBM traffic against a ~7.4 ms step (<1%), so it cannot
        clear the ≥15% bar that would justify a second production
        implementation of the hot op — the production gate was removed in
        round 4."""
        blk = self.pool_block

        def body(_, blk_i):
            start = blk_i * blk
            block = {f: lax.dynamic_slice_in_dim(pool[f], start, blk)
                     for f in (*_ADMIT_FIELDS, "active")}
            pos = start + jnp.arange(blk, dtype=jnp.int32)
            eq = batch["slot"][None, :] == pos[:, None]     # (blk, B)
            block = _admit_block(block, start, blk, batch, eq=eq)
            scores = self._score_block(batch, q_thr_eff, block, start, now,
                                       skip_filters, not_self=~eq.T)
            v, i = self._block_best(scores)
            return None, (block, v, (i + start).astype(jnp.int32))

        _, (blocks, vs, is_) = lax.scan(
            body, None, jnp.arange(self.n_blocks, dtype=jnp.int32))
        pool = {f: blocks[f].reshape(self.capacity) for f in blocks}
        vals = vs.T                                       # (B, n_blocks)
        idxs = jnp.where(vals > _NEG_INF, is_.T, self.capacity)
        return pool, vals, idxs

    def _search_step(self, pool: dict[str, Any], batch: dict[str, Any], now,
                     skip_filters: bool = False):
        """One window: fused admit+score+top-k pass → pair → evict matched.

        Returns (pool', q_slot[B], c_slot[B], dist[B]) with sentinel P /
        +inf in unmatched lanes. Match quality is computed on the host from
        the pair's requests (the host has both sides' exact thresholds).
        """
        b = batch["rating"].shape[0]
        blk = self.pool_block
        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )
        pool, vals, idxs = self._candidates_admitting(
            pool, batch, q_thr_eff, now, skip_filters)

        out_q, out_c, out_d = self.greedy_pair(vals, idxs, batch["slot"])

        # Evict both sides of every formed pair (compare-masked, no scatter).
        matched = jnp.concatenate([out_q, out_c])

        def evict_body(_, blk_i):
            start = blk_i * blk
            a = lax.dynamic_slice_in_dim(pool["active"], start, blk)
            return None, _mask_members(a, start, blk, matched)

        _, act_blocks = lax.scan(evict_body, None,
                                 jnp.arange(self.n_blocks, dtype=jnp.int32))
        pool = dict(pool, active=act_blocks.reshape(self.capacity))
        return pool, out_q, out_c, out_d

    # ---- rating-banded candidate pruning ----------------------------------
    #
    # The dense step scores every request against every pool slot — O(B·P)
    # pair compute per window, even though a request with threshold t can
    # only ever match candidates within rating distance t (ELO) or
    # t / g(rd_q, rd_c) (Glicko-2, g ≤ 1). The pruned step exploits that
    # WITHOUT changing a single output bit:
    #
    #   1. sort the window by rating (padding to the end), carrying original
    #      lane ids for tie-break/order restoration;
    #   2. cheap per-block bounds: an O(P) three-column pass over the live
    #      pool (_live_stats) merged with the window's own per-block bounds
    #      computed from slot ids alone (_incoming_stats) — together equal
    #      to post-admission bounds without doing the admission;
    #   3. TIER 1 — each sorted chunk of C requests scores ONLY a W-block
    #      contiguous span of the pool chosen from those bounds (dynamic
    #      start, static width — no recompiles);
    #      TIER 2 — admission is chunk-local too (_admit_chunked): chunk j
    #      admits its own C players into its span, O(B·W·blk) total instead
    #      of the dense pass's O(B·P) eq compares. Round 4 pruned scoring
    #      only and measured ~10% — full-pool admission was the floor.
    #   4. if any chunk's admissible span exceeds W blocks, the WHOLE window
    #      falls back to the dense fused admit+score scan via one lax.cond
    #      (same compiled step, no recompile, exact by construction).
    #
    # Bit-exactness argument: a block outside a chunk's span can contain no
    # admissible candidate for any request in the chunk (the span bound is
    # inflated past f32 rounding), so the dense scan would have produced
    # -inf for exactly the (row, block) cells the pruned scan leaves at
    # -inf; covered cells are computed by the same _score_block math. A
    # window player's own block always lies inside its chunk's span (its
    # rating is in both the block's merged bounds and the chunk's interval,
    # so the overlap test admits it at reach ≥ 0), hence chunk-local
    # admission admits every valid lane exactly once. The
    # candidate matrices are therefore identical, pairing (with original-id
    # tie-breaks) is identical, and the unsort is an exact one-hot matmul.
    # One caveat scopes the claim: the dense and pruned PROGRAMS compile the
    # shared scoring expression at different tile shapes, and a backend's
    # instruction selection (e.g. LLVM FMA contraction on the CPU test
    # backend) may round intermediates differently per shape — measured ≤1
    # ulp in distance on CPU, and bit-identical on the TPU backend. That
    # noise is a property of compiling the SAME math twice, not of pruning.
    #
    # Effectiveness depends on the HOST keeping ratings spatially coherent:
    # with PlayerPool rating bands aligned to pool blocks (band_spec), block
    # bounds are tight and W ≈ (2·threshold span)/band width ≪ n_blocks.
    # With the default LIFO allocator every block spans the whole rating
    # range and the step falls back to dense — correct, just not faster.

    def _sort_batch(self, batch: dict[str, Any], q_thr_eff):
        """Sort window lanes by rating (padding lanes to the end); returns
        (sorted batch, sorted q_thr_eff, original lane ids i32[B])."""
        b = batch["rating"].shape[0]
        key = jnp.where(batch["valid"], batch["rating"], jnp.inf)
        orig = jnp.arange(b, dtype=jnp.int32)
        (_, slot, rating, rd, region, mode, thr, enq, valid, qte, oi) = lax.sort(
            (key, batch["slot"], batch["rating"], batch["rd"], batch["region"],
             batch["mode"], batch["threshold"], batch["enqueue_t"],
             batch["valid"], q_thr_eff, orig),
            num_keys=1, is_stable=True)
        sb = dict(slot=slot, rating=rating, rd=rd, region=region, mode=mode,
                  threshold=thr, enqueue_t=enq, valid=valid)
        return sb, qte, oi

    def _live_stats(self, pool: dict[str, Any]):
        """Per-block rating bounds of the CURRENT pool (no admission):
        (min_r f32[n_blocks], max_r f32[n_blocks], max_rd f32[n_blocks]).
        Empty blocks carry (+inf, -inf, 0) — the overlap test then never
        selects them. O(P) reads of three columns only; the O(P·B)
        admission work happens per-span in _admit_chunked instead."""
        blk = self.pool_block

        def body(_, blk_i):
            start = blk_i * blk
            r = lax.dynamic_slice_in_dim(pool["rating"], start, blk)
            rd = lax.dynamic_slice_in_dim(pool["rd"], start, blk)
            act = lax.dynamic_slice_in_dim(pool["active"], start, blk)
            minr = jnp.min(jnp.where(act, r, jnp.inf))
            maxr = jnp.max(jnp.where(act, r, -jnp.inf))
            maxrd = jnp.max(jnp.where(act, rd, 0.0))
            return None, (minr, maxr, maxrd)

        _, (minr, maxr, maxrd) = lax.scan(
            body, None, jnp.arange(self.n_blocks, dtype=jnp.int32))
        return minr, maxr, maxrd

    def _incoming_stats(self, batch: dict[str, Any]):
        """Per-block rating bounds of the WINDOW being admitted, from slot
        ids alone: (min_r, max_r, max_rd) over valid lanes whose slot lies
        in each block. Merged with _live_stats this equals the
        post-admission bounds _chunk_windows needs — which is what makes
        chunk-local admission sound: any block receiving a window player
        then has bmin ≤ r ≤ bmax for that player's rating r, so the block
        always lands inside the player's own chunk's span (overlap with
        reach ≥ 0), and no admission can escape its chunk. Tiny dense op:
        (n_blocks, B) compares."""
        nb = self.n_blocks
        blk_of = batch["slot"] // self.pool_block          # sentinel → nb
        hit = (blk_of[None, :] == jnp.arange(nb, dtype=jnp.int32)[:, None]
               ) & batch["valid"][None, :]
        minr = jnp.min(jnp.where(hit, batch["rating"][None, :], jnp.inf),
                       axis=1)
        maxr = jnp.max(jnp.where(hit, batch["rating"][None, :], -jnp.inf),
                       axis=1)
        maxrd = jnp.max(jnp.where(hit, batch["rd"][None, :], 0.0), axis=1)
        return minr, maxr, maxrd

    def _admit_chunked(self, pool: dict[str, Any], sb: dict[str, Any],
                       dstart):
        """Chunk-local admission: chunk j admits its own C players into its
        W-block span only (their slots provably lie there — see
        _incoming_stats), via the same scatter-free eq-matmul as the dense
        path. O(B · W·blk) compares instead of the dense pass's O(B · P) —
        the second tier of the pruning: round 4 pruned scoring alone and
        measured that full-pool admission kept the win at ~10%. Sequential
        pool carry: spans overlap, but each slot is written exactly once
        (by its own chunk), so order cannot matter."""
        blk, w = self.pool_block, self.prune_window_blocks
        b = sb["rating"].shape[0]
        c = self._chunk_size(b)
        fields = (*_ADMIT_FIELDS, "active")

        def body(pool, j):
            ds = dstart[j] * blk
            span = {f: lax.dynamic_slice_in_dim(pool[f], ds, w * blk)
                    for f in fields}
            cb = {f: lax.dynamic_slice_in_dim(sb[f], j * c, c) for f in sb}
            span = _admit_block(span, ds, w * blk, cb)
            pool = dict(pool, **{
                f: lax.dynamic_update_slice_in_dim(pool[f], span[f], ds,
                                                   axis=0)
                for f in fields})
            return pool, None

        pool, _ = lax.scan(body, pool,
                           jnp.arange(b // c, dtype=jnp.int32))
        return pool

    def _chunk_size(self, b: int) -> int:
        c = max(1, min(self.prune_chunk, b))
        while b % c:
            c //= 2
        return c

    def _chunk_windows(self, sb, q_thr_eff, bmin, bmax, brd):
        """Per-chunk block-span starts + global feasibility.

        A (chunk, block) pair can hold an admissible edge only if the
        block's live rating interval, inflated by the chunk's worst-case
        reach E, overlaps the chunk's rating interval. E = max effective
        threshold (ELO) or that / g(max rd_chunk, max rd_block) (Glicko-2:
        g ≤ 1 and decreasing in rd, so the max-rd g lower-bounds every
        pair's g). The 0.1% + 0.5 inflation swamps f32 rounding in the
        kernel's distance math — a block excluded here scores -inf in the
        dense scan too."""
        b = sb["rating"].shape[0]
        c = self._chunk_size(b)
        n_chunks = b // c
        nb, w = self.n_blocks, self.prune_window_blocks
        v = sb["valid"].reshape(n_chunks, c)
        r = sb["rating"].reshape(n_chunks, c)
        cmin = jnp.min(jnp.where(v, r, jnp.inf), axis=1)
        cmax = jnp.max(jnp.where(v, r, -jnp.inf), axis=1)
        cthr = jnp.max(jnp.where(v, q_thr_eff.reshape(n_chunks, c), 0.0),
                       axis=1)
        if self.glicko2:
            crd = jnp.max(jnp.where(v, sb["rd"].reshape(n_chunks, c), 0.0),
                          axis=1)
            g = scoring.glicko_g(crd[:, None], brd[None, :])
            reach = cthr[:, None] / jnp.maximum(g, jnp.float32(1e-6))
        else:
            reach = jnp.broadcast_to(cthr[:, None], (n_chunks, nb))
        reach = reach * jnp.float32(1.001) + jnp.float32(0.5)
        ov = ((bmin[None, :] - reach <= cmax[:, None])
              & (bmax[None, :] + reach >= cmin[:, None]))
        idx = jnp.arange(nb, dtype=jnp.int32)
        first = jnp.min(jnp.where(ov, idx, nb), axis=1)
        last = jnp.max(jnp.where(ov, idx, -1), axis=1)
        width = jnp.maximum(last - first + 1, 0)
        feasible = jnp.all(width <= w)
        dstart = jnp.clip(jnp.minimum(first, nb - w), 0, nb - w)
        # Chunks with NO admissible block (width 0 ⇔ no valid lane: a
        # valid lane's own block always overlaps its chunk at reach ≥ 0)
        # park on the first busy chunk's span instead of the clip
        # fallback at the pool tail — their scan then re-reads slots a
        # busy chunk already touched, so padding chunks never widen the
        # touched-union (and never drag cold blocks into cache). Scoring
        # is -inf for them wherever they point, so outputs are unchanged.
        busy = width > 0
        common = dstart[jnp.argmax(busy)]
        dstart = jnp.where(busy, dstart,
                           jnp.where(busy.any(), common, 0))
        return dstart.astype(jnp.int32), feasible, width

    def _candidates_pruned(self, sb, q_thr_eff, pool, now, dstart,
                           skip_filters: bool = False):
        """Best-per-block candidates, scoring only each chunk's W-block span.

        Output shape/content identical to _candidates on the sorted batch:
        (vals f32[B, n_blocks], idxs i32[B, n_blocks]); blocks outside a
        chunk's span hold -inf / capacity — exactly what the dense scan
        yields for them (no admissible candidate there)."""
        blk, w, nb = self.pool_block, self.prune_window_blocks, self.n_blocks
        b = sb["rating"].shape[0]
        c = self._chunk_size(b)
        n_chunks = b // c

        def body(_, j):
            ds = dstart[j] * blk
            wpool = {f: lax.dynamic_slice_in_dim(pool[f], ds, w * blk)
                     for f in (*_ADMIT_FIELDS, "active")}
            cb = {f: lax.dynamic_slice_in_dim(sb[f], j * c, c) for f in sb}
            qte = lax.dynamic_slice_in_dim(q_thr_eff, j * c, c)
            scores = self._score_block(cb, qte, wpool, ds, now,
                                       skip_filters)       # (c, w·blk)
            sc = scores.reshape(c, w, blk)
            v = sc.max(-1)
            gi = (ds + jnp.arange(w, dtype=jnp.int32)[None, :] * blk
                  + jnp.argmax(sc, -1).astype(jnp.int32))
            cv = lax.dynamic_update_slice(
                jnp.full((c, nb), _NEG_INF), v, (0, dstart[j]))
            ci = lax.dynamic_update_slice(
                jnp.full((c, nb), jnp.int32(self.capacity)),
                jnp.where(v > _NEG_INF, gi, self.capacity), (0, dstart[j]))
            return None, (cv, ci)

        _, (cvs, cis) = lax.scan(body, None,
                                 jnp.arange(n_chunks, dtype=jnp.int32))
        return cvs.reshape(b, nb), cis.reshape(b, nb)

    def _search_step_pruned(self, pool: dict[str, Any], batch: dict[str, Any],
                            now, skip_filters: bool = False):
        """Bit-exact pruned window step (see the section comment above)."""
        b = batch["rating"].shape[0]
        blk = self.pool_block
        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )
        sb, qte, oi = self._sort_batch(batch, q_thr_eff)
        lmin, lmax, lrd = self._live_stats(pool)
        imin, imax, ird = self._incoming_stats(sb)
        bmin = jnp.minimum(lmin, imin)
        bmax = jnp.maximum(lmax, imax)
        brd = jnp.maximum(lrd, ird)
        dstart, feasible, _ = self._chunk_windows(sb, qte, bmin, bmax, brd)

        def pruned_path():
            p = self._admit_chunked(pool, sb, dstart)
            v, i = self._candidates_pruned(sb, qte, p, now, dstart,
                                           skip_filters)
            return p, v, i

        def dense_path():
            return self._candidates_admitting(pool, sb, qte, now,
                                              skip_filters)

        pool, vals, idxs = lax.cond(feasible, pruned_path, dense_path)
        s_q, s_c, s_d = greedy_pair(vals, idxs, sb["slot"], self.capacity,
                                    self.pair_rounds, rid=oi)
        out_q, out_c, out_d = self._unsort_matches(oi, s_q, s_c, s_d)

        # Eviction uses the sorted-order outputs — same slot set.
        pool = self._evict(pool, jnp.concatenate([s_q, s_c]))
        return pool, out_q, out_c, out_d

    def _unsort_matches(self, oi, s_q, s_c, s_d):
        """Sorted-order match outputs → original lane order with an exact
        one-hot matmul (the scatter-free idiom; gathers/scatters of B
        irregular elements serialize on TPU). HIGHEST keeps the 0/1 ×
        value products exact; +inf sentinels are encoded as -1 first
        (0·inf would poison rows with NaN), and dist ≥ 0 makes -1
        unambiguous. One definition for every sorted-window step (pruned,
        bucketed, bucketed rescan) — the encoding is bit-exactness-
        critical and must not diverge between copies."""
        b = oi.shape[0]
        onehot = (oi[None, :] == jnp.arange(b, dtype=jnp.int32)[:, None]
                  ).astype(jnp.float32)
        enc_d = jnp.where(jnp.isinf(s_d), jnp.float32(-1.0), s_d)
        stacked = jnp.stack(
            [s_q.astype(jnp.float32), s_c.astype(jnp.float32), enc_d], axis=1)
        un = jnp.matmul(onehot, stacked, precision=lax.Precision.HIGHEST)
        return (un[:, 0].astype(jnp.int32), un[:, 1].astype(jnp.int32),
                jnp.where(un[:, 2] < 0, jnp.inf, un[:, 2]))

    # ---- hierarchical rating-bucketed formation (ISSUE 14) -----------------
    #
    # The pruned step above is bit-exact but still pays one O(P) pass per
    # window: _live_stats re-derives every block's rating bounds from the
    # full pool columns before any span can be cut. The bucketed step
    # removes that last O(P) term by carrying the bounds as STATE — a
    # device-resident bucket index (INDEX_FIELDS inside the pool dict, one
    # row per pool block = rating bucket under band_spec) maintained
    # incrementally:
    #
    #   admit      → counts += per-block window hits; bounds WIDEN by the
    #                window's per-block stats (_incoming_stats)
    #   match/evict→ counts -= per-block matched hits; bounds untouched
    #   rebuild    → one exact O(P) scan (engine heartbeat / restore) that
    #                re-tightens the monotone-widening bounds
    #
    # Bit-exactness vs the flat/dense step carries over unchanged from the
    # pruned step's argument with one extra observation: the index bounds
    # are always a SUPERSET of the true live bounds (widen-only between
    # rebuilds), and a superset bound can only make spans wider — a block
    # excluded by a superset bound is excluded by the exact bound, so it
    # scores -inf in the dense scan too. Threshold widening composes the
    # same way: _chunk_windows computes reach from the effective (aged)
    # thresholds, so the candidate BUCKET SET expands as players age while
    # the per-window work stays proportional to the spans, not the pool.
    #
    # Formation cost per window: O(B·W·blk) score/admit + O(B·W·blk)
    # span-local eviction + O(nb) index update — no O(P) term anywhere on
    # the feasible path ("sub-O(P) window formation"). The packed step
    # reports the slots it actually touched (row 3), which bench surfaces
    # as ``formation_touched_frac``.

    def init_index_arrays(self) -> "dict[str, Any]":
        """Fresh (empty-pool) bucket-index columns, host numpy — merged
        into the device pool dict next to POOL_FIELDS by the engine."""
        import numpy as np

        nb = self.n_blocks
        return {
            "bidx_count": np.zeros(nb, np.int32),
            "bidx_min": np.full(nb, np.inf, np.float32),
            "bidx_max": np.full(nb, -np.inf, np.float32),
            "bidx_rd": np.zeros(nb, np.float32),
        }

    def _index_rebuild(self, pool: dict[str, Any]) -> dict[str, Any]:
        """Exact index from the live pool columns: one O(P) scan (the
        _live_stats pass + an occupancy count). Off the hot path — engine
        heartbeat and restore call it to re-tighten the widen-only bounds."""
        core = {k: v for k, v in pool.items() if k not in INDEX_FIELDS}
        blk = self.pool_block

        def body(_, blk_i):
            start = blk_i * blk
            act = lax.dynamic_slice_in_dim(core["active"], start, blk)
            return None, act.sum(dtype=jnp.int32)

        _, counts = lax.scan(body, None,
                             jnp.arange(self.n_blocks, dtype=jnp.int32))
        minr, maxr, maxrd = self._live_stats(core)
        return {**core, "bidx_count": counts, "bidx_min": minr,
                "bidx_max": maxr, "bidx_rd": maxrd}

    def _incoming_block_counts(self, batch: dict[str, Any]) -> jnp.ndarray:
        """i32[n_blocks]: valid window lanes landing in each block (slot
        sentinel ⇒ no block). Tiny dense one-hot sum, no scatters."""
        nb = self.n_blocks
        blk_of = batch["slot"] // self.pool_block
        hit = (blk_of[None, :] == jnp.arange(nb, dtype=jnp.int32)[:, None]
               ) & batch["valid"][None, :]
        return hit.sum(axis=1, dtype=jnp.int32)

    def _matched_block_counts(self, matched: jnp.ndarray) -> jnp.ndarray:
        """i32[n_blocks]: matched slots (< capacity) leaving each block."""
        nb = self.n_blocks
        blk_of = matched // self.pool_block
        hit = (blk_of[None, :] == jnp.arange(nb, dtype=jnp.int32)[:, None]
               ) & (matched < self.capacity)[None, :]
        return hit.sum(axis=1, dtype=jnp.int32)

    def _admit_indexed(self, pool: dict[str, Any],
                       batch: dict[str, Any]) -> dict[str, Any]:
        """Standalone admit (restore path) that keeps the index current:
        counts += per-block hits, bounds widen by the window's stats."""
        idx = {k: pool[k] for k in INDEX_FIELDS}
        core = self._admit({k: v for k, v in pool.items()
                            if k not in INDEX_FIELDS}, batch)
        imin, imax, ird = self._incoming_stats(batch)
        return {
            **core,
            "bidx_count": idx["bidx_count"]
            + self._incoming_block_counts(batch),
            "bidx_min": jnp.minimum(idx["bidx_min"], imin),
            "bidx_max": jnp.maximum(idx["bidx_max"], imax),
            "bidx_rd": jnp.maximum(idx["bidx_rd"], ird),
        }

    def _evict_indexed(self, pool: dict[str, Any],
                       slots: jnp.ndarray) -> dict[str, Any]:
        """Standalone evict (remove/expire path), index-aware: counts drop
        by the slots that were ACTIVE at call time (idempotent — a second
        evict of the same slot finds it inactive and counts nothing)."""
        was_act = jnp.take(pool["active"],
                           jnp.clip(slots, 0, self.capacity - 1))
        live = jnp.where(was_act & (slots < self.capacity), slots,
                         self.capacity)
        core = self._evict({k: v for k, v in pool.items()
                            if k not in INDEX_FIELDS}, slots)
        return {
            **core,
            "bidx_count": pool["bidx_count"]
            - self._matched_block_counts(live),
            "bidx_min": pool["bidx_min"],
            "bidx_max": pool["bidx_max"],
            "bidx_rd": pool["bidx_rd"],
        }

    def _evict_spans(self, core: dict[str, Any], dstart, n_chunks: int,
                     matched: jnp.ndarray) -> dict[str, Any]:
        """Span-local eviction: clear ``matched`` only within the chunks'
        W-block spans — every matched slot provably lies in one (a window
        player's own block is inside its chunk's span by the admission
        argument; a matched candidate came from its chunk's span). Spans
        may overlap; clearing is monotone, so the sequential carry makes
        repeats harmless."""
        blk, w = self.pool_block, self.prune_window_blocks

        def body(pool, j):
            ds = dstart[j] * blk
            a = lax.dynamic_slice_in_dim(pool["active"], ds, w * blk)
            a = _mask_members(a, ds, w * blk, matched)
            return dict(pool, active=lax.dynamic_update_slice_in_dim(
                pool["active"], a, ds, axis=0)), None

        core, _ = lax.scan(body, core,
                           jnp.arange(n_chunks, dtype=jnp.int32))
        return core

    def _search_step_bucketed(self, pool: dict[str, Any],
                              batch: dict[str, Any], now,
                              skip_filters: bool = False):
        """Index-driven window step: bit-exact vs flat (see the section
        comment), plus a 5th return — the pool slots formation touched."""
        b = batch["rating"].shape[0]
        n_chunks = b // self._chunk_size(b)
        idx = {k: pool[k] for k in INDEX_FIELDS}
        core = {k: v for k, v in pool.items() if k not in INDEX_FIELDS}
        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )
        sb, qte, oi = self._sort_batch(batch, q_thr_eff)
        imin, imax, ird = self._incoming_stats(sb)
        bmin = jnp.minimum(idx["bidx_min"], imin)
        bmax = jnp.maximum(idx["bidx_max"], imax)
        brd = jnp.maximum(idx["bidx_rd"], ird)
        dstart, feasible, _ = self._chunk_windows(sb, qte, bmin, bmax, brd)

        def pruned_path():
            p = self._admit_chunked(core, sb, dstart)
            v, i = self._candidates_pruned(sb, qte, p, now, dstart,
                                           skip_filters)
            return p, v, i

        def dense_path():
            return self._candidates_admitting(core, sb, qte, now,
                                              skip_filters)

        core, vals, idxs = lax.cond(feasible, pruned_path, dense_path)
        touched = self._touched_slots(feasible)
        s_q, s_c, s_d = greedy_pair(vals, idxs, sb["slot"], self.capacity,
                                    self.pair_rounds, rid=oi)
        out_q, out_c, out_d = self._unsort_matches(oi, s_q, s_c, s_d)

        matched = jnp.concatenate([s_q, s_c])
        core = lax.cond(
            feasible,
            lambda: self._evict_spans(core, dstart, n_chunks, matched),
            lambda: self._evict(core, matched))
        pool = {
            **core,
            "bidx_count": idx["bidx_count"]
            + self._incoming_block_counts(sb)
            - self._matched_block_counts(matched),
            "bidx_min": bmin, "bidx_max": bmax, "bidx_rd": brd,
        }
        return pool, out_q, out_c, out_d, touched

    def _touched_slots(self, feasible) -> jnp.ndarray:
        """Pool slots EACH WINDOW LANE's formation scored (f32 scalar,
        f32-exact: counts ≪ 2^24): W·blk on the feasible path — every lane
        scores only its chunk's span — vs the whole pool on the dense
        fallback, where every lane scores all P slots. The bench's
        ``formation_touched_frac`` is this over capacity: the per-lane
        candidate-restriction win (the union of spans across a
        rating-diverse window legitimately covers most buckets — every
        bucket is a candidate for SOMEONE — so per-lane, not union, is
        the number that shows sub-O(P) formation; the sharded frontier
        path reports its nb·K analog through the same row)."""
        per_lane = min(self.prune_window_blocks * self.pool_block,
                       self.capacity)
        return jnp.where(feasible, jnp.float32(per_lane),
                         jnp.float32(self.capacity))

    def _rescan_step_bucketed(self, pool: dict[str, Any],
                              batch: dict[str, Any], now):
        """No-admission bucketed rescan: validity is gated by the
        device-side active flag (same overlap-safety contract as
        _rescan_step), spans come from the index alone (no incoming —
        every lane is already pool-resident, so index bounds cover it),
        and only matched counts leave the index."""
        b = batch["rating"].shape[0]
        n_chunks = b // self._chunk_size(b)
        idx = {k: pool[k] for k in INDEX_FIELDS}
        core = {k: v for k, v in pool.items() if k not in INDEX_FIELDS}
        lane_act = jnp.take(core["active"],
                            jnp.clip(batch["slot"], 0, self.capacity - 1))
        batch = dict(batch, valid=batch["valid"] & lane_act)
        q_thr_eff = _effective_threshold(
            batch["threshold"], batch["enqueue_t"], now,
            self.widen_per_sec, self.max_threshold,
        )
        sb, qte, oi = self._sort_batch(batch, q_thr_eff)
        dstart, feasible, _ = self._chunk_windows(
            sb, qte, idx["bidx_min"], idx["bidx_max"], idx["bidx_rd"])
        touched = self._touched_slots(feasible)

        core, vals, idxs = lax.cond(
            feasible,
            lambda: (core,) + self._candidates_pruned(sb, qte, core, now,
                                                      dstart),
            lambda: (core,) + self._candidates(sb, qte, core, now))
        s_q, s_c, s_d = greedy_pair(vals, idxs, sb["slot"], self.capacity,
                                    self.pair_rounds, rid=oi)
        out_q, out_c, out_d = self._unsort_matches(oi, s_q, s_c, s_d)

        matched = jnp.concatenate([s_q, s_c])
        core = lax.cond(
            feasible,
            lambda: self._evict_spans(core, dstart, n_chunks, matched),
            lambda: self._evict(core, matched))
        pool = {
            **core,
            "bidx_count": idx["bidx_count"]
            - self._matched_block_counts(matched),
            "bidx_min": idx["bidx_min"], "bidx_max": idx["bidx_max"],
            "bidx_rd": idx["bidx_rd"],
        }
        return pool, out_q, out_c, out_d, touched

    def _pack_bucketed_out(self, out_q, out_c, out_d, touched):
        """(q, c, dist) + the touched-slots scalar → f32[4, B]: rows 0-2
        are the flat packed layout byte for byte; row 3 broadcasts the
        per-window touched count (read at [3, 0] on host)."""
        b = out_q.shape[0]
        return jnp.concatenate([
            jnp.stack([out_q.astype(jnp.float32),
                       out_c.astype(jnp.float32), out_d]),
            jnp.broadcast_to(touched, (1, b))])

    def _search_step_packed_bucketed(self, pool, packed,
                                     skip_filters: bool = False):
        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool, q, c, d, touched = self._search_step_bucketed(
            pool, batch, now, skip_filters)
        return pool, self._pack_bucketed_out(q, c, d, touched)

    def _rescan_step_packed_bucketed(self, pool, packed):
        batch = unpack_batch(packed)
        now = packed[8, 0]
        pool, q, c, d, touched = self._rescan_step_bucketed(pool, batch, now)
        return pool, self._pack_bucketed_out(q, c, d, touched)


class QualityAccumKernel:
    """Device-resident match-quality/wait accumulator (ISSUE 8).

    One tiny jitted step per dispatched window folds the step's OWN device
    outputs — ``(q_slot, c_slot, dist)`` — into per-queue device-resident
    histogram + count/sum arrays, conditioned on rating bucket. The matched
    slots' rating/enqueue/threshold columns are read from the POST-step
    pool: eviction only clears ``active`` (``KernelSet._evict`` is a mask,
    not a wipe), so the columns still hold the matched players' values.

    Hot-path cost: one extra async dispatch per window over arrays already
    on device — no host scan, no D2H, no sync. The state is NOT donated:
    it is a few KB, and keeping old handles valid is what lets the engine
    snapshot it with ``copy_to_host_async`` and materialize lazily at a
    later finalize (TpuEngine piggybacks the readback on the existing
    window-collect path instead of adding a transfer per window).

    Scatter-free like everything else here: histogram adds are dense
    one-hot compare matrices ((2B samples) × (R·N cells), both tiny) —
    the same idiom the admit/evict kernels use instead of XLA scatters.

    Bucket rules must match ``engine/quality.QualitySpec`` bit-for-bit on
    equal f32 inputs (side="right" searchsorted, floor(q·N) clip) — the
    device-vs-host reconciliation soak in tests/test_quality.py holds the
    two implementations together.
    """

    def __init__(self, *, capacity: int, widen_per_sec: float,
                 max_threshold: float, rating_edges, n_quality: int,
                 wait_edges):
        import numpy as np

        self.capacity = capacity
        self.widen_per_sec = widen_per_sec
        self.max_threshold = max_threshold
        self.n_rating = len(rating_edges) + 1
        self.n_quality = n_quality
        self.n_wait = len(wait_edges) + 1  # + overflow
        self._r_edges = np.asarray(rating_edges, np.float32)
        self._w_edges = np.asarray(wait_edges, np.float32)
        self.accum = named_jit("quality_accum", self._accum)

    def init_state(self) -> dict[str, jnp.ndarray]:
        r = self.n_rating
        return {
            "q_hist": jnp.zeros((r, self.n_quality), jnp.int32),
            "w_hist": jnp.zeros((r, self.n_wait), jnp.int32),
            "count": jnp.zeros(r, jnp.int32),
            "q_sum": jnp.zeros(r, jnp.float32),
            "w_sum": jnp.zeros(r, jnp.float32),
            "d_sum": jnp.zeros(r, jnp.float32),
        }

    def _accum(self, state, rating, enqueue_t, threshold, out, now):
        q_slot = out[0].astype(jnp.int32)
        c_slot = out[1].astype(jnp.int32)
        dist = out[2]
        b = q_slot.shape[0]
        cap = self.capacity
        hit = q_slot < cap
        idx = jnp.concatenate([jnp.clip(q_slot, 0, cap - 1),
                               jnp.clip(c_slot, 0, cap - 1)])
        valid = jnp.concatenate([hit, hit])
        r = jnp.take(rating, idx)
        enq = jnp.take(enqueue_t, idx)
        thr = jnp.take(threshold, idx)
        eff = _effective_threshold(thr, enq, now, self.widen_per_sec,
                                   self.max_threshold)
        # The pair's mutual limit — min of both sides' effective thresholds
        # at match time, the exact formula the host response path uses.
        limit = jnp.minimum(eff[:b], eff[b:])
        limit2 = jnp.concatenate([limit, limit])
        d2 = jnp.concatenate([dist, dist])
        # Sanitize BEFORE any masked arithmetic: unmatched lanes carry the
        # +inf dist sentinel, and 0 × inf is NaN, not 0.
        d2 = jnp.where(valid, d2, 0.0)
        quality = jnp.where(
            valid & (limit2 > 0.0),
            jnp.clip(1.0 - d2 / jnp.maximum(limit2, jnp.float32(1e-30)),
                     0.0, 1.0),
            0.0)
        wait = jnp.where(valid, jnp.maximum(0.0, now - enq), 0.0)

        rb = jnp.searchsorted(jnp.asarray(self._r_edges), r,
                              side="right").astype(jnp.int32)
        qb = jnp.clip((quality * self.n_quality).astype(jnp.int32), 0,
                      self.n_quality - 1)
        wb = jnp.searchsorted(jnp.asarray(self._w_edges), wait,
                              side="right").astype(jnp.int32)

        def hist_add(hist, col_idx, n_cols):
            flat = rb * n_cols + col_idx
            cells = jnp.arange(hist.size, dtype=jnp.int32)
            onehot = (flat[:, None] == cells[None, :]) & valid[:, None]
            return hist + onehot.sum(axis=0,
                                     dtype=hist.dtype).reshape(hist.shape)

        rows = ((rb[:, None] == jnp.arange(self.n_rating,
                                           dtype=jnp.int32)[None, :])
                & valid[:, None])
        rf = rows.astype(jnp.float32)
        return {
            "q_hist": hist_add(state["q_hist"], qb, self.n_quality),
            "w_hist": hist_add(state["w_hist"], wb, self.n_wait),
            "count": state["count"] + rows.sum(axis=0, dtype=jnp.int32),
            "q_sum": state["q_sum"] + (rf * quality[:, None]).sum(axis=0),
            "w_sum": state["w_sum"] + (rf * wait[:, None]).sum(axis=0),
            "d_sum": state["d_sum"] + (rf * d2[:, None]).sum(axis=0),
        }


@functools.lru_cache(maxsize=None)
def kernel_set(capacity: int, top_k: int, pool_block: int, glicko2: bool,
               widen_per_sec: float, max_threshold: float,
               pair_rounds: int = 8, prune_window_blocks: int = 0,
               prune_chunk: int = 128, bucketed: bool = False) -> KernelSet:
    """Cached KernelSet per static config (compile once per queue shape)."""
    return KernelSet(
        capacity=capacity, top_k=top_k, pool_block=pool_block, glicko2=glicko2,
        widen_per_sec=widen_per_sec, max_threshold=max_threshold,
        pair_rounds=pair_rounds, prune_window_blocks=prune_window_blocks,
        prune_chunk=prune_chunk, bucketed=bucketed,
    )
