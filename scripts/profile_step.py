"""Component-level attribution of the device window step.

The roofline phase (bench.py) reports one aggregate ``device_step_ms``; this
script splits that number into its pipeline stages at the bench geometry so
optimization effort lands on the measured dominant term instead of the
assumed one (round-4 lesson: the pruning study cut scored pairs 2.7x for a
~10% step win because scoring was NOT dominant).

Stages timed as separately-jitted functions on synthetic-but-realistic data
(ratings N(1500, 300), threshold 100, ~100k active of 131072 slots):

    admit      fused admission scan alone (eq-matmul per block)
    cands      fused admit+score+block-best scan (the candidate pass)
    pair       greedy_pair alone on the candidate pass's real outputs
    pair_rN    greedy_pair at round counts 1/2/4/8 (per-round cost + where
               match formation actually saturates)
    evict      compare-masked eviction alone
    full       the production search_step_packed

Stage times overlap (cands includes admit; full includes everything): the
attribution reads full ~= cands + pair + evict, admit as a floor under
cands.

Run ON THE CHIP, from the repo root:
    PYTHONPATH=. python scripts/profile_step.py
"""

from __future__ import annotations

import argparse
import functools
import time

import numpy as np


def timeit(fn, *args, iters: int = 30, chain: bool = False):
    """Median-of-iters wall time of a jitted fn; pipelined loop, one sync."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    outs = None
    state = args
    for _ in range(iters):
        if chain:
            out = fn(*state[:1], *args[1:])
            state = (out[0],)
        else:
            out = fn(*args)
        outs = out
    jax.block_until_ready(outs)
    return (time.perf_counter() - t0) / iters


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--capacity", type=int, default=131_072)
    p.add_argument("--pool-block", type=int, default=8192)
    p.add_argument("--window", type=int, default=4096)
    p.add_argument("--pool", type=int, default=100_000)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--threshold", type=float, default=100.0)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from matchmaking_tpu.engine import kernels as K

    print("devices:", jax.devices())
    rng = np.random.default_rng(7)
    P, B = args.capacity, args.window

    ks = K.kernel_set(args.capacity, 8, args.pool_block, False, 0.0, 400.0)
    print(f"geometry: P={P} blk={ks.pool_block} n_blocks={ks.n_blocks} "
          f"B={B} rounds={ks.pair_rounds}")

    # Pool: args.pool active players in random slots.
    active = np.zeros(P, bool)
    occupied = rng.choice(P, size=args.pool, replace=False)
    active[occupied] = True
    pool = {
        "rating": jnp.asarray(
            rng.normal(1500.0, 300.0, P).astype(np.float32)),
        "rd": jnp.zeros(P, jnp.float32),
        "region": jnp.zeros(P, jnp.int32),
        "mode": jnp.zeros(P, jnp.int32),
        "threshold": jnp.full(P, np.float32(args.threshold)),
        "enqueue_t": jnp.zeros(P, jnp.float32),
        "active": jnp.asarray(active),
    }
    # Window: B fresh requests in B free slots.
    free = np.setdiff1d(np.arange(P, dtype=np.int32), occupied)[:B]
    packed = np.zeros((9, B), np.float32)
    packed[0] = free
    packed[1] = rng.normal(1500.0, 300.0, B).astype(np.float32)
    packed[5] = args.threshold
    packed[7] = 1.0
    packed = jnp.asarray(packed)

    batch = K.unpack_batch(packed)
    q_thr = batch["threshold"]

    admit = jax.jit(ks._admit)
    cands = jax.jit(functools.partial(ks._candidates, now=0.0))
    evict = jax.jit(ks._evict)
    full = jax.jit(ks._search_step_packed)

    res: dict[str, float] = {}
    res["admit"] = timeit(admit, pool, batch, iters=args.iters)
    res["cands"] = timeit(cands, batch, q_thr, pool, iters=args.iters)

    vals, idxs = jax.tree.map(np.asarray, cands(batch, q_thr, pool))
    vals, idxs = jnp.asarray(vals), jnp.asarray(idxs)
    n_cand = int((np.asarray(vals) > -np.inf).sum(1).mean())
    print(f"mean candidates/row: {n_cand}")

    for r in (1, 2, 4, 8):
        pair_r = jax.jit(functools.partial(
            K.greedy_pair, capacity=ks.capacity, rounds=r))
        res[f"pair_r{r}"] = timeit(pair_r, vals, idxs, batch["slot"],
                                   iters=args.iters)
        if r == ks.pair_rounds:
            q, c, d = pair_r(vals, idxs, batch["slot"])
            print(f"matches at rounds={r}: "
                  f"{int((np.asarray(q) < ks.capacity).sum())}/{B}")
    res["pair"] = res[f"pair_r{ks.pair_rounds}"]

    matched = jnp.concatenate([jnp.asarray(np.asarray(free)),
                               jnp.asarray(occupied[:B].astype(np.int32))])
    res["evict"] = timeit(evict, pool, matched, iters=args.iters)
    res["full"] = timeit(full, pool, packed, iters=args.iters, chain=True)
    full_nf = jax.jit(functools.partial(ks._search_step_packed,
                                        skip_filters=True))
    res["full_nofilter"] = timeit(full_nf, pool, packed, iters=args.iters,
                                  chain=True)

    print()
    for name, dt in res.items():
        print(f"{name:>10}: {dt * 1e3:8.3f} ms")
    acc = res["cands"] + res["pair"] + res["evict"]
    print(f"{'sum(c+p+e)':>10}: {acc * 1e3:8.3f} ms  "
          f"(full = {res['full'] * 1e3:.3f})")


if __name__ == "__main__":
    main()
