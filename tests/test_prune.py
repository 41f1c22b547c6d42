"""Rating-banded candidate pruning: the pruned step must be BIT-EXACT vs the
dense step (kernels.py ``_search_step_pruned`` — skipped blocks are exactly
the blocks the dense scan scores to -inf), and the banded allocator must keep
slots rating-coherent while preserving pool-accounting invariants.

SURVEY.md §4 layering: randomized equivalence at the kernel seam, unit tests
for the host allocator, then an engine-level integration pass.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
from matchmaking_tpu.core.pool import PlayerPool, band_edges_from_spec
from matchmaking_tpu.engine.kernels import KernelSet
from matchmaking_tpu.engine.tpu import TpuEngine
from matchmaking_tpu.service.contract import SearchRequest


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


def _random_batch(rng, pool, n_valid=200):
    batch = {
        "slot": np.full(B, P, np.int32),
        "rating": np.zeros(B, np.float32),
        "rd": np.zeros(B, np.float32),
        "region": np.zeros(B, np.int32),
        "mode": np.zeros(B, np.int32),
        "threshold": np.zeros(B, np.float32),
        "enqueue_t": np.zeros(B, np.float32),
        "valid": np.zeros(B, bool),
    }
    free = np.where(~pool["active"])[0][:n_valid].astype(np.int32)
    n = free.size
    batch["slot"][:n] = free
    batch["rating"][:n] = rng.normal(1500, 300, n).astype(np.float32)
    batch["rd"][:n] = rng.uniform(0, 200, n)
    batch["region"][:n] = rng.integers(0, 3, n)
    batch["mode"][:n] = rng.integers(0, 3, n)
    batch["threshold"][:n] = rng.uniform(50, 150, n)
    batch["enqueue_t"][:n] = rng.uniform(0, 10, n)
    batch["valid"][:n] = True
    return batch


def _run_both(dense, pruned, pool, batch, now=12.0):
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    outs = []
    for ks in (dense, pruned):
        jp = {k: jnp.asarray(v) for k, v in pool.items()}
        p, q, c, d = ks.search_step(jp, jb, jnp.float32(now))
        outs.append((
            {f: np.asarray(v) for f, v in p.items()},
            np.asarray(q), np.asarray(c), np.asarray(d)))
    return outs


def _assert_identical(a, b):
    """Match decisions + pool state must be EXACTLY equal. Distances are
    compared to 1 ulp: pruning changes no math, but the dense and pruned
    programs compile the shared scoring expression at different tile shapes
    and the CPU test backend's instruction selection (FMA contraction) can
    round intermediates differently per shape."""
    (pa, qa, ca, da), (pb, qb, cb, db) = a, b
    np.testing.assert_array_equal(qa, qb)
    np.testing.assert_array_equal(ca, cb)
    np.testing.assert_allclose(da, db, rtol=3e-7, atol=0.0)
    for f in pa:
        np.testing.assert_array_equal(pa[f], pb[f], err_msg=f)


@pytest.mark.parametrize("glicko2", [False, True])
@pytest.mark.parametrize("widen", [0.0, 5.0])
def test_pruned_step_bit_exact(rng, glicko2, widen):
    """Randomized windows over a banded-layout pool: identical outputs."""
    kw = dict(COMMON, widen_per_sec=widen)
    dense = KernelSet(glicko2=glicko2, **kw)
    pruned = KernelSet(glicko2=glicko2, prune_window_blocks=6,
                       prune_chunk=64, **kw)
    for trial in range(4):
        pool = _random_pool(rng, sorted_ratings=True)
        batch = _random_batch(rng, pool)
        a, b = _run_both(dense, pruned, pool, batch, now=10.0 + trial)
        _assert_identical(a, b)
        assert (a[1] < P).sum() > 20  # the trial actually matched players


def test_pruned_step_bit_exact_unbanded_pool(rng):
    """Random (unbanded) slot layout: every block spans the whole rating
    range, so the dense fallback cond fires — still bit-exact."""
    dense = KernelSet(glicko2=False, **COMMON)
    pruned = KernelSet(glicko2=False, prune_window_blocks=2,
                       prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=False)
    batch = _random_batch(rng, pool)
    a, b = _run_both(dense, pruned, pool, batch)
    _assert_identical(a, b)


def test_pruned_step_degenerate_full_width(rng):
    """prune_window_blocks ≥ n_blocks: pruned plumbing, dense coverage."""
    dense = KernelSet(glicko2=True, **COMMON)
    pruned = KernelSet(glicko2=True, prune_window_blocks=10_000,
                       prune_chunk=32, **COMMON)
    assert pruned.prune_window_blocks == pruned.n_blocks
    pool = _random_pool(rng, sorted_ratings=True)
    batch = _random_batch(rng, pool)
    _assert_identical(*_run_both(dense, pruned, pool, batch))


def test_pruned_step_empty_and_padding(rng):
    """All-padding windows and empty pools exercise the ±inf stat
    sentinels."""
    dense = KernelSet(glicko2=False, **COMMON)
    pruned = KernelSet(glicko2=False, prune_window_blocks=4,
                       prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True)
    pool["active"][:] = False
    batch = _random_batch(rng, pool, n_valid=0)
    a, b = _run_both(dense, pruned, pool, batch)
    _assert_identical(a, b)
    assert (a[1] == P).all()


def test_wildcards_match_across_rating_span(rng):
    """Wildcard region/mode rows still only match within threshold — and the
    pruned step must keep them identical to dense even when their nearest
    rating neighbours are all region-filtered out (the README's 'window can
    be entirely filtered out' hazard: span pruning is by RATING reach, so
    filters can never hide an admissible candidate)."""
    dense = KernelSet(glicko2=False, **COMMON)
    pruned = KernelSet(glicko2=False, prune_window_blocks=6,
                       prune_chunk=64, **COMMON)
    pool = _random_pool(rng, sorted_ratings=True)
    # Region-striped pool: near-rating slots mostly belong to region 2.
    pool["region"][:] = 2
    pool["region"][::7] = 1
    pool["mode"][:] = 0
    batch = _random_batch(rng, pool)
    batch["region"][:] = 1          # can only match the sparse stripe
    batch["mode"][:] = 0
    a, b = _run_both(dense, pruned, pool, batch)
    _assert_identical(a, b)
    assert (a[1] < P).sum() > 0


# ---- banded allocator ------------------------------------------------------


def test_band_edges_from_spec():
    assert band_edges_from_spec("", 16) is None
    edges = band_edges_from_spec("uniform:0:1600", 16)
    assert len(edges) == 15 and edges[0] == 100.0 and edges[-1] == 1500.0
    g = band_edges_from_spec("gaussian:1500:300", 16)
    assert len(g) == 15
    assert all(b > a for a, b in zip(g, g[1:]))
    assert abs(g[7] - 1500.0) < 1e-6          # median band edge = mean
    with pytest.raises(ValueError):
        band_edges_from_spec("uniform:5:5", 8)
    with pytest.raises(ValueError):
        band_edges_from_spec("nope:1:2", 8)


def _req(i, rating):
    return SearchRequest(id=f"p{i}", rating=rating)


def test_banded_pool_places_by_rating():
    edges = band_edges_from_spec("uniform:0:1600", 16)
    pool = PlayerPool(160, 100.0, band_edges=edges)   # 10 slots per band
    slots = pool.allocate([_req(0, 50.0), _req(1, 850.0), _req(2, 1550.0)])
    assert 0 <= slots[0] < 10          # band 0
    assert 80 <= slots[1] < 90         # band 8
    assert 150 <= slots[2] < 160       # band 15
    # Release returns the slot to its home band for reuse.
    pool.release([slots[1]])
    slots2 = pool.allocate([_req(3, 820.0)])
    assert 80 <= slots2[0] < 90


def test_banded_pool_spills_to_nearest():
    edges = band_edges_from_spec("uniform:0:1600", 16)
    pool = PlayerPool(160, 100.0, band_edges=edges)
    same = pool.allocate([_req(i, 850.0) for i in range(12)])
    in_band = [s for s in same if 80 <= s < 90]
    spilled = [s for s in same if not 80 <= s < 90]
    assert len(in_band) == 10 and len(spilled) == 2
    # Spill lands in an adjacent band, not across the pool.
    assert all(70 <= s < 80 or 90 <= s < 100 for s in spilled)
    assert pool.free_count() == 160 - 12


def test_banded_pool_full_and_accounting():
    edges = band_edges_from_spec("uniform:0:1600", 4)
    pool = PlayerPool(8, 100.0, band_edges=edges)
    slots = pool.allocate([_req(i, 800.0) for i in range(8)])
    assert sorted(slots) == list(range(8))
    assert pool.free_count() == 0
    from matchmaking_tpu.core.pool import PoolFullError
    with pytest.raises(PoolFullError):
        pool.allocate([_req(99, 800.0)])
    pool.release(slots[:3])
    assert pool.free_count() == 3
    # Idempotent double release (mirrors the unbanded guarantee).
    pool.release(slots[:3])
    assert pool.free_count() == 3


# ---- engine integration ----------------------------------------------------


def _engine(prune: bool) -> TpuEngine:
    # band_spec on BOTH engines: slot placement must be identical so the
    # comparison isolates pruning (a different allocator legitimately
    # changes best-per-block candidate lists, hence contention outcomes).
    ec = EngineConfig(
        backend="tpu", pool_capacity=4096, pool_block=256,
        batch_buckets=(16, 64, 256),
        prune_window_blocks=6 if prune else 0,
        band_spec="gaussian:1500:300",
    )
    cfg = Config(engine=ec,
                 queues=(QueueConfig(rating_threshold=100.0,
                                     widen_per_sec=2.0, max_threshold=200.0),))
    return TpuEngine(cfg, cfg.queues[0])


def test_engine_pruned_matches_dense(rng):
    """Same request stream + same (banded) allocator, pruned vs dense
    kernels: identical match sets end-to-end through the engine."""
    e_dense, e_pruned = _engine(False), _engine(True)
    t = [1000.0]

    def feed(engine):
        out = []
        local = np.random.default_rng(7)      # identical stream per engine
        for w in range(6):
            reqs = [
                SearchRequest(id=f"w{w}_{i}",
                              rating=float(local.normal(1500, 300)),
                              enqueued_at=t[0] + w)
                for i in range(120)
            ]
            res = engine.search(reqs, now=t[0] + w)
            out.extend((tuple(sorted(m.result().players)),
                        round(m.quality, 5)) for m in res.matches)
        return sorted(out)

    md, mp = feed(e_dense), feed(e_pruned)
    assert len(md) > 100
    assert md == mp
