"""Hierarchical rating-bucketed formation (ISSUE 14).

The bucketed step must be BIT-EXACT vs the flat/dense step on identical
pool state — the flat path is the oracle: the device bucket index only
changes WHICH blocks are scored (a superset-bounds argument on top of the
pruned step's span proof), never a single output bit. Same layering as
test_prune.py: randomized equivalence at the kernel seam (traffic +
rescan), the sharded per-bucket frontier vs the single-device dense
kernels at D=2/4, the tournament-tree frontier merge vs the linear merge,
then engine-level integration (adaptive frontier-K, formation_report,
the formation_bucketed mark).
"""

import numpy as np
import jax.numpy as jnp
import pytest

from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
from matchmaking_tpu.core.pool import PACKED_ROWS, PlayerPool
from matchmaking_tpu.engine.kernels import INDEX_FIELDS, KernelSet
from matchmaking_tpu.engine.tpu import TpuEngine
from matchmaking_tpu.service.contract import SearchRequest

pytestmark = pytest.mark.bucketed


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


P, B = 4096, 256
COMMON = dict(capacity=P, top_k=8, pool_block=256,
              widen_per_sec=1.0, max_threshold=200.0)


def _random_pool(rng, sorted_ratings: bool, active_frac=0.7):
    ratings = rng.normal(1500, 300, P).astype(np.float32)
    if sorted_ratings:                       # banded-allocator layout
        ratings = np.sort(ratings)
    return {
        "rating": ratings,
        "rd": rng.uniform(0, 200, P).astype(np.float32),
        "region": rng.integers(0, 3, P).astype(np.int32),
        "mode": rng.integers(0, 3, P).astype(np.int32),
        "threshold": rng.uniform(50, 150, P).astype(np.float32),
        "enqueue_t": rng.uniform(0, 10, P).astype(np.float32),
        "active": rng.random(P) < active_frac,
    }


def _empty_batch():
    return {
        "slot": np.full(B, P, np.int32),
        "rating": np.zeros(B, np.float32),
        "rd": np.zeros(B, np.float32),
        "region": np.zeros(B, np.int32),
        "mode": np.zeros(B, np.int32),
        "threshold": np.zeros(B, np.float32),
        "enqueue_t": np.zeros(B, np.float32),
        "valid": np.zeros(B, bool),
    }


def _random_batch(rng, pool, n_valid=200, banded=False):
    """Window into free slots; ``banded`` draws each lane's rating near its
    slot's block value (what the banded allocator produces in production —
    the layout under which spans stay narrow)."""
    batch = _empty_batch()
    free = np.where(~pool["active"])[0]
    if n_valid and free.size > n_valid:
        free = free[rng.choice(free.size, n_valid, replace=False)]
    free = np.sort(free).astype(np.int32)
    n = free.size
    batch["slot"][:n] = free
    if banded:
        batch["rating"][:n] = (pool["rating"][free]
                               + rng.normal(0, 5, n).astype(np.float32))
    else:
        batch["rating"][:n] = rng.normal(1500, 300, n).astype(np.float32)
    batch["rd"][:n] = rng.uniform(0, 200, n)
    batch["region"][:n] = rng.integers(0, 3, n)
    batch["mode"][:n] = rng.integers(0, 3, n)
    batch["threshold"][:n] = rng.uniform(50, 120, n)
    batch["enqueue_t"][:n] = rng.uniform(0, 10, n)
    batch["valid"][:n] = True
    return batch


def _with_index(ks: KernelSet, pool) -> dict:
    """Pool dict + an EXACT device bucket index (what the engine maintains
    incrementally; rebuilt here so each trial starts tight)."""
    jp = {k: jnp.asarray(v) for k, v in pool.items()}
    jp.update({k: jnp.asarray(v) for k, v in ks.init_index_arrays().items()})
    return ks.index_rebuild(jp)


def _rebuild_copy(ks: KernelSet, pool) -> dict:
    """index_rebuild on COPIES — the jitted rebuild donates its input, so
    comparing against the original requires fresh buffers."""
    return ks.index_rebuild({k: jnp.array(v) for k, v in pool.items()})


def _pack(batch, now: float) -> np.ndarray:
    packed = np.empty((9, B), np.float32)
    for i, name in enumerate(PACKED_ROWS):
        packed[i] = batch[name]
    packed[8] = now
    return packed


def _assert_same(dense_out, buck_pool, buck_out):
    (pd, qd, cd, dd) = dense_out
    np.testing.assert_array_equal(qd, buck_out[0].astype(np.int32))
    np.testing.assert_array_equal(cd, buck_out[1].astype(np.int32))
    hit = qd < P
    # 1-ulp tolerance on distances only: the two programs compile the
    # shared scoring math at different tile shapes (see test_prune).
    np.testing.assert_allclose(dd[hit], buck_out[2][hit], rtol=3e-7,
                               atol=0.0)
    for f in pd:
        np.testing.assert_array_equal(pd[f], np.asarray(buck_pool[f]),
                                      err_msg=f)


def _run_dense(ks, pool, batch, now):
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    jp = {k: jnp.asarray(v) for k, v in pool.items()}
    p, q, c, d = ks.search_step(jp, jb, jnp.float32(now))
    return ({f: np.asarray(v) for f, v in p.items()},
            np.asarray(q), np.asarray(c), np.asarray(d))


@pytest.mark.parametrize("glicko2", [False, True])
@pytest.mark.parametrize("widen", [0.0, 5.0])
def test_bucketed_step_bit_exact(rng, glicko2, widen):
    """Randomized banded-layout pools: identical outputs, and the
    incrementally-updated index counts equal a fresh exact rebuild's."""
    kw = dict(COMMON, widen_per_sec=widen)
    dense = KernelSet(glicko2=glicko2, **kw)
    buck = KernelSet(glicko2=glicko2, bucketed=True, prune_window_blocks=8,
                     prune_chunk=64, **kw)
    for trial in range(3):
        pool = _random_pool(rng, sorted_ratings=True)
        batch = _random_batch(rng, pool, banded=bool(trial % 2))
        now = 10.0 + trial
        d_out = _run_dense(dense, pool, batch, now)
        bp, out = buck.search_step_packed(_with_index(buck, pool),
                                          jnp.asarray(_pack(batch, now)))
        out = np.asarray(out)
        assert out.shape == (4, B)
        _assert_same(d_out, bp, out)
        assert (d_out[1] < P).sum() > 20   # the trial actually matched
        reb = _rebuild_copy(buck, bp)
        np.testing.assert_array_equal(np.asarray(bp["bidx_count"]),
                                      np.asarray(reb["bidx_count"]))


def test_bucketed_unbanded_pool_falls_back_dense(rng):
    """Random slot layout: every block spans the whole rating range, the
    dense-fallback cond fires (touched == capacity) — still bit-exact."""
    dense = KernelSet(glicko2=False, **COMMON)
    buck = KernelSet(glicko2=False, bucketed=True, prune_window_blocks=2,
                     prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=False)
    batch = _random_batch(rng, pool)
    d_out = _run_dense(dense, pool, batch, 12.0)
    bp, out = buck.search_step_packed(_with_index(buck, pool),
                                      jnp.asarray(_pack(batch, 12.0)))
    out = np.asarray(out)
    _assert_same(d_out, bp, out)
    assert out[3, 0] == P


def test_bucketed_hot_bucket_touches_fraction(rng):
    """Occupancy-skewed pool (one hot bucket): formation touches a narrow
    span around the hot band, far below the pool — and stays exact."""
    dense = KernelSet(glicko2=False, **COMMON)
    buck = KernelSet(glicko2=False, bucketed=True, prune_window_blocks=6,
                     prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True, active_frac=0.0)
    hot = slice(4 * 256, 6 * 256)           # blocks 4-5 only
    pool["active"][hot] = rng.random(512) < 0.9
    batch = _empty_batch()
    free = np.where(~pool["active"][hot])[0][:40] + hot.start
    free = free.astype(np.int32)
    n = free.size
    batch["slot"][:n] = free
    batch["rating"][:n] = (pool["rating"][free]
                           + rng.normal(0, 3, n).astype(np.float32))
    batch["rd"][:n] = rng.uniform(0, 100, n)
    batch["threshold"][:n] = rng.uniform(50, 100, n)
    batch["valid"][:n] = True
    d_out = _run_dense(dense, pool, batch, 5.0)
    bp, out = buck.search_step_packed(_with_index(buck, pool),
                                      jnp.asarray(_pack(batch, 5.0)))
    out = np.asarray(out)
    _assert_same(d_out, bp, out)
    assert (d_out[1] < P).sum() > 5
    assert out[3, 0] < P / 2                # sub-O(P): narrow hot span


def test_bucketed_widening_expands_candidate_buckets(rng):
    """Threshold widening grows the candidate BUCKET SET: the admissible
    span width (the number of buckets a chunk may reach) strictly grows
    as the same waiting players age — and the cut stays bit-exact vs
    dense at every age, including past the span budget (dense fallback)."""
    from matchmaking_tpu.engine.kernels import _effective_threshold

    dense = KernelSet(glicko2=False, **COMMON)
    buck = KernelSet(glicko2=False, bucketed=True, prune_window_blocks=12,
                     prune_chunk=32, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True, active_frac=0.0)
    mid = slice(6 * 256, 10 * 256)
    pool["active"][mid] = rng.random(4 * 256) < 0.5
    pool["threshold"][:] = 20.0
    pool["enqueue_t"][:] = 0.0
    batch = _empty_batch()
    free = np.where(~pool["active"][mid])[0][:60] + mid.start
    free = free.astype(np.int32)
    n = free.size
    batch["slot"][:n] = free
    batch["rating"][:n] = (pool["rating"][free]
                           + rng.normal(0, 2, n).astype(np.float32))
    batch["threshold"][:n] = 20.0
    batch["enqueue_t"][:n] = 0.0
    batch["valid"][:n] = True

    def span_widths(now: float) -> np.ndarray:
        """The kernel's own admissible-bucket widths for busy chunks."""
        jb = {k: jnp.asarray(v) for k, v in batch.items()}
        qte = _effective_threshold(jb["threshold"], jb["enqueue_t"],
                                   jnp.float32(now), buck.widen_per_sec,
                                   buck.max_threshold)
        sb, qte_s, _ = buck._sort_batch(jb, qte)
        jp = {k: jnp.asarray(v) for k, v in pool.items()}
        lmin, lmax, lrd = buck._live_stats(jp)
        imin, imax, ird = buck._incoming_stats(sb)
        _, _, width = buck._chunk_windows(
            sb, qte_s, jnp.minimum(lmin, imin), jnp.maximum(lmax, imax),
            jnp.maximum(lrd, ird))
        w = np.asarray(width)
        return w[w > 0]

    early, late = span_widths(1.0), span_widths(120.0)
    assert late.max() > early.max()         # aged players reach further
    touched = []
    for now in (1.0, 120.0):                # widen 1/s, cap 200
        d_out = _run_dense(dense, pool, batch, now)
        bp, out = buck.search_step_packed(_with_index(buck, pool),
                                          jnp.asarray(_pack(batch, now)))
        out = np.asarray(out)
        _assert_same(d_out, bp, out)
        touched.append(float(out[3, 0]))
    assert touched[0] < P                   # young cut stayed sub-pool


def test_bucketed_rescan_bit_exact(rng):
    """The no-admission bucketed rescan vs the flat rescan variant:
    identical matches + pool state, index counts stay exact."""
    flat = KernelSet(glicko2=False, **COMMON)
    buck = KernelSet(glicko2=False, bucketed=True, prune_window_blocks=8,
                     prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True)
    batch = _empty_batch()
    act = np.where(pool["active"])[0][:220].astype(np.int32)
    n = act.size
    batch["slot"][:n] = act
    batch["rating"][:n] = pool["rating"][act]
    batch["rd"][:n] = pool["rd"][act]
    batch["region"][:n] = pool["region"][act]
    batch["mode"][:n] = pool["mode"][act]
    batch["threshold"][:n] = pool["threshold"][act]
    batch["enqueue_t"][:n] = pool["enqueue_t"][act]
    # A few stale lanes (already-evicted slots) ride along masked.
    stale = np.where(~pool["active"])[0][:8].astype(np.int32)
    batch["slot"][n:n + 8] = stale
    batch["valid"][:n + 8] = True
    packed = _pack(batch, 14.0)
    jf = {k: jnp.asarray(v) for k, v in pool.items()}
    pf, outf = flat.search_step_packed_rescan(jf, jnp.asarray(packed))
    pb, outb = buck.search_step_packed_rescan(_with_index(buck, pool),
                                              jnp.asarray(packed))
    outf, outb = np.asarray(outf), np.asarray(outb)
    np.testing.assert_array_equal(outf[0], outb[0])
    np.testing.assert_array_equal(outf[1], outb[1])
    for f in pf:
        np.testing.assert_array_equal(np.asarray(pf[f]),
                                      np.asarray(pb[f]), err_msg=f)
    assert (outf[0].astype(np.int32) < P).sum() > 10
    reb = _rebuild_copy(buck, pb)
    np.testing.assert_array_equal(np.asarray(pb["bidx_count"]),
                                  np.asarray(reb["bidx_count"]))


def test_indexed_admit_evict_keep_counts_exact(rng):
    """The standalone indexed admit (restore path) and evict (remove/
    expire path) keep the device counts equal to an exact rebuild, and
    double-eviction counts nothing (idempotence)."""
    buck = KernelSet(glicko2=False, bucketed=True, prune_window_blocks=8,
                     prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True, active_frac=0.3)
    jp = _with_index(buck, pool)
    batch = _random_batch(rng, pool, n_valid=100)
    jp = buck.admit_packed(jp, jnp.asarray(_pack(batch, 0.0)))
    jp = {k: np.asarray(v) for k, v in jp.items()}
    reb = _rebuild_copy(buck, jp)
    np.testing.assert_array_equal(jp["bidx_count"],
                                  np.asarray(reb["bidx_count"]))
    # Bounds stay a superset of the exact rebuild's.
    assert (jp["bidx_min"] <= np.asarray(reb["bidx_min"]) + 1e-6).all()
    assert (jp["bidx_max"] >= np.asarray(reb["bidx_max"]) - 1e-6).all()
    ev = np.full(buck.evict_bucket, P, np.int32)
    victims = np.where(jp["active"])[0][:16].astype(np.int32)
    ev[:victims.size] = victims
    jp = buck.evict({k: jnp.asarray(v) for k, v in jp.items()},
                    jnp.asarray(ev))
    jp = {k: np.asarray(v) for k, v in jp.items()}
    reb = _rebuild_copy(buck, jp)
    np.testing.assert_array_equal(jp["bidx_count"],
                                  np.asarray(reb["bidx_count"]))
    jp2 = buck.evict({k: jnp.asarray(v) for k, v in jp.items()},
                     jnp.asarray(ev))      # double evict: no-op counts
    np.testing.assert_array_equal(np.asarray(jp2["bidx_count"]),
                                  jp["bidx_count"])


# ---- sharded per-bucket frontier -------------------------------------------


@pytest.mark.parametrize("n_shards", [2, 4])
def test_sharded_bucket_frontier_equals_dense(rng, n_shards):
    """D=2/4 bucket-frontier step vs the single-device dense kernels on
    identical (sparse) pool state: identical matches and pool state —
    only per-bucket top-K frontiers crossed the shard boundary."""
    from matchmaking_tpu.engine.sharded import ShardedKernelSet, pool_mesh

    sh = ShardedKernelSet(capacity=P, top_k=8, pool_block=256,
                          glicko2=False, widen_per_sec=1.0,
                          max_threshold=200.0, mesh=pool_mesh(n_shards),
                          bucket_frontier_k=64)
    dense = KernelSet(glicko2=False, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True, active_frac=0.012)
    batch = _random_batch(rng, pool, n_valid=100, banded=True)
    d_out = _run_dense(dense, pool, batch, 10.0)
    sp = sh.place_pool(dict(pool))
    p2, out = sh.bucket_step(64)(sp, jnp.asarray(_pack(batch, 10.0)))
    out = np.asarray(out)
    _assert_same(d_out, p2, out)
    assert (d_out[1] < P).sum() > 10
    assert out[3, 0] < P                    # occupancy-shaped formation


# ---- tournament-tree frontier merge ----------------------------------------


def test_tournament_merge_helper_matches_concat_sort(rng):
    """Unit: tree top-k merge of sorted frontiers == numpy concat +
    stable lexsort + truncate, including cross-shard ties."""
    from matchmaking_tpu.engine.sharded import tournament_merge_topk

    k, shards = 16, 4
    bufs, keys = [], []
    for s in range(shards):
        group = np.sort(rng.integers(0, 4, k)).astype(np.int32)
        rating = np.sort(rng.integers(0, 6, k)).astype(np.float32)
        order = np.lexsort((rating, group))
        gslot = (s * 100 + np.arange(k)).astype(np.int32)
        buf = np.stack([group[order].astype(np.float32),
                        rating[order], gslot.astype(np.float32)])
        bufs.append(jnp.asarray(buf))
        keys.append((buf[0].astype(np.int32), buf[1],
                     buf[2].astype(np.int32)))

    def key_fn(fb):
        return (fb[0].astype(jnp.int32), fb[1], fb[2].astype(jnp.int32))

    merged = np.asarray(tournament_merge_topk(bufs, key_fn))
    cat = np.concatenate([np.asarray(b) for b in bufs], axis=1)
    order = np.lexsort((cat[2], cat[1], cat[0]))[:k]
    np.testing.assert_array_equal(merged, cat[:, order])


@pytest.mark.parametrize("n_shards", [2, 4])
def test_role_ring_tournament_equals_linear(rng, n_shards):
    """The ROLE ring step with the tournament consumer merge (role_mask
    rides the frontier rows through the merge; the K-row _ring_form
    drives _windows_roles/_cover_split) is bit-identical to linear."""
    from matchmaking_tpu.engine.role_kernels import (
        ShardedRoleKernelSet,
        RoleKernelSet,
    )
    from matchmaking_tpu.engine.sharded import pool_mesh

    cap, bb, k = 512, 64, 32
    mk = dict(capacity=cap, team_size=2, role_slots=("tank", "dps"),
              widen_per_sec=0.5, max_threshold=200.0, max_matches=8,
              rounds=8, frontier_k=k)
    lin = ShardedRoleKernelSet(mesh=pool_mesh(n_shards), **mk)
    tour = ShardedRoleKernelSet(mesh=pool_mesh(n_shards),
                                frontier_merge="tournament", **mk)
    pool = {
        "rating": rng.normal(1500, 40, cap).astype(np.float32),
        "rd": rng.uniform(0, 200, cap).astype(np.float32),
        "region": np.ones(cap, np.int32),
        "mode": np.ones(cap, np.int32),
        "threshold": rng.uniform(100, 180, cap).astype(np.float32),
        "enqueue_t": rng.uniform(0, 5, cap).astype(np.float32),
        "active": np.zeros(cap, bool),
        "role_mask": np.zeros(cap, np.int32),
    }
    act = rng.choice(cap, k - 6, replace=False)
    pool["active"][act] = True
    pool["role_mask"][act] = rng.integers(1, 4, act.size)  # tank/dps/both
    batch = {f: np.zeros(bb, dt) for f, dt in
             [("slot", np.int32), ("rating", np.float32),
              ("rd", np.float32), ("region", np.int32),
              ("mode", np.int32), ("threshold", np.float32),
              ("enqueue_t", np.float32), ("valid", bool)]}
    batch["slot"][:] = cap
    free = np.where(~pool["active"])[0][:4].astype(np.int32)
    batch["slot"][:4] = free
    batch["rating"][:4] = rng.normal(1500, 40, 4).astype(np.float32)
    batch["region"][:4] = 1
    batch["mode"][:4] = 1
    batch["threshold"][:4] = 150.0
    batch["valid"][:4] = True
    packed = np.empty((9, bb), np.float32)
    for i, name in enumerate(PACKED_ROWS):
        packed[i] = batch[name]
    packed[8] = 8.0
    # Insert the role_mask row before the trailing now row (pack_rows).
    masks = np.zeros((1, bb), np.float32)
    masks[0, :4] = rng.integers(1, 4, 4)
    rpacked = np.concatenate([packed[:8], masks, packed[8:]])
    pl = lin.place_pool(dict(pool))
    pt = tour.place_pool(dict(pool))
    p1, o1 = lin.search_step_packed_ring(pl, jnp.asarray(rpacked))
    p2, o2 = tour.search_step_packed_ring(pt, jnp.asarray(rpacked))
    o1, o2 = np.asarray(o1), np.asarray(o2)
    np.testing.assert_array_equal(o1, o2)
    for f in p1:
        np.testing.assert_array_equal(np.asarray(p1[f]),
                                      np.asarray(p2[f]), err_msg=f)
    assert (o1[0] < cap).sum() >= 1         # a role match actually formed


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_team_ring_tournament_equals_linear(rng, n_shards):
    """The team ring step with the tournament consumer merge is
    bit-identical to the linear merge under the shared host gate."""
    from matchmaking_tpu.engine.sharded import pool_mesh
    from matchmaking_tpu.engine.teams import ShardedTeamKernelSet

    cap, bb, k = 1024, 64, 32
    mk = dict(capacity=cap, team_size=2, widen_per_sec=0.5,
              max_threshold=200.0, max_matches=16, rounds=8, frontier_k=k)
    lin = ShardedTeamKernelSet(mesh=pool_mesh(n_shards), **mk)
    tour = ShardedTeamKernelSet(mesh=pool_mesh(n_shards),
                                frontier_merge="tournament", **mk)
    pool = {
        "rating": rng.normal(1500, 40, cap).astype(np.float32),
        "rd": rng.uniform(0, 200, cap).astype(np.float32),
        "region": np.ones(cap, np.int32),
        "mode": np.ones(cap, np.int32),
        "threshold": rng.uniform(100, 180, cap).astype(np.float32),
        "enqueue_t": rng.uniform(0, 5, cap).astype(np.float32),
        "active": np.zeros(cap, bool),
    }
    pool["active"][rng.choice(cap, k - 6, replace=False)] = True
    batch = {f: np.zeros(bb, dt) for f, dt in
             [("slot", np.int32), ("rating", np.float32),
              ("rd", np.float32), ("region", np.int32),
              ("mode", np.int32), ("threshold", np.float32),
              ("enqueue_t", np.float32), ("valid", bool)]}
    batch["slot"][:] = cap
    free = np.where(~pool["active"])[0][:4].astype(np.int32)
    batch["slot"][:4] = free
    batch["rating"][:4] = rng.normal(1500, 40, 4).astype(np.float32)
    batch["region"][:4] = 1
    batch["mode"][:4] = 1
    batch["threshold"][:4] = 150.0
    batch["valid"][:4] = True
    packed = np.empty((9, bb), np.float32)
    for i, name in enumerate(PACKED_ROWS):
        packed[i] = batch[name]
    packed[8] = 8.0
    pl = lin.place_pool(dict(pool))
    pt = tour.place_pool(dict(pool))
    p1, o1 = lin.search_step_packed_ring(pl, jnp.asarray(packed))
    p2, o2 = tour.search_step_packed_ring(pt, jnp.asarray(packed))
    o1, o2 = np.asarray(o1), np.asarray(o2)
    np.testing.assert_array_equal(o1, o2)
    for f in p1:
        np.testing.assert_array_equal(np.asarray(p1[f]),
                                      np.asarray(p2[f]), err_msg=f)
    assert (o1[0] < cap).sum() >= 1         # formation actually formed


# ---- engine integration ----------------------------------------------------


def _engine(**kw) -> TpuEngine:
    ec = EngineConfig(backend="tpu", pool_capacity=4096, pool_block=256,
                      batch_buckets=(16, 64, 256),
                      band_spec="gaussian:1500:300", **kw)
    cfg = Config(engine=ec,
                 queues=(QueueConfig(rating_threshold=100.0,
                                     widen_per_sec=2.0,
                                     max_threshold=200.0),))
    return TpuEngine(cfg, cfg.queues[0])


def _feed(engine: TpuEngine):
    """Identical request stream incl. an expiry sweep + heartbeat (the
    index-rebuild tick) mid-stream; returns the sorted match set."""
    out = []
    local = np.random.default_rng(7)
    for w in range(6):
        reqs = [SearchRequest(id=f"w{w}_{i}",
                              rating=float(local.normal(1500, 300)),
                              enqueued_at=1000.0 + w)
                for i in range(120)]
        res = engine.search(reqs, now=1000.0 + w)
        out.extend((tuple(sorted(m.result().players)),
                    round(m.quality, 5)) for m in res.matches)
        if w == 3:
            engine.expire(1000.0 + w, 0.5)
            engine.heartbeat(1000.0 + w)
    return sorted(out)


def test_engine_bucketed_matches_flat():
    """Same stream + same banded allocator, bucketed vs flat kernels:
    identical match sets end-to-end through the engine (expiry + the
    heartbeat index rebuild included)."""
    flat = _feed(_engine())
    buck = _feed(_engine(bucketed=True, prune_window_blocks=8))
    assert len(flat) > 100
    assert flat == buck


def test_engine_sharded_bucket_frontier_matches_flat():
    """D=2 bucket-frontier engine == flat single-device engine, with the
    adaptive-K ladder choosing from observed occupancy and recording its
    moves."""
    flat = _feed(_engine())
    e = _engine(bucketed=True, mesh_pool_axis=2, bucket_frontier_k=64)
    sharded = _feed(e)
    assert flat == sharded
    rep = e.formation_report()
    assert rep["mode"] == "bucket_frontier"
    assert rep["frontier_k"] in rep["frontier_ladder"]
    assert rep["frontier_steps"] > 0
    assert len(e.frontier_moves) >= 1       # audit ring saw the sizing
    assert rep["formation_touched_frac"] < 0.35
    assert rep["bands"] is not None         # free-slot heads surfaced


def test_engine_frontier_fallback_above_ladder():
    """Occupancy above the ladder ceiling must fall back to the dense
    sharded step (counted) — and stay correct."""
    flat = _feed(_engine())
    e = _engine(bucketed=True, mesh_pool_axis=2, bucket_frontier_k=8)
    sharded = _feed(e)
    assert flat == sharded
    assert e.counters.get("bucket_frontier_fallback", 0) > 0


def test_formation_report_and_marks():
    """Flat engines report no formation state; bucketed engines report it
    and stamp formation_bucketed device marks for the attribution
    classes."""
    plain = _engine()
    assert plain.formation_report() is None
    e = _engine(bucketed=True, prune_window_blocks=8)
    reqs = [SearchRequest(id=f"p{i}", rating=1500.0 + i, enqueued_at=1.0)
            for i in range(40)]
    e.search_async(reqs, now=1.0)
    done = e.flush()
    assert done
    marks = [name for name, _ in e.window_marks[done[0][0]]]
    assert "formation_bucketed" in marks
    assert "device_step" not in marks
    rep = e.formation_report()
    assert rep["mode"] == "bucketed"
    assert rep["windows"] >= 1
    assert rep["touched_slots"] > 0
    from matchmaking_tpu.service.attribution import classify

    cat, kind = classify("h2d", "formation_bucketed")
    assert cat == "formation_bucketed" and kind == "work"
