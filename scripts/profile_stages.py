#!/usr/bin/env python
"""Stage-level profiling for the TPU engine hot path (consolidates the
round-1 micro_bench{,2,3,4}.py quartet into one parameterized tool).

Modes (--mode):
  device    — pure device time of search_step_packed: chain N steps
              back-to-back (donated pool), block once, divide. No host
              work in the timed region.
  dispatch  — host-side cost of ONE cached jitted dispatch (call returns
              as soon as the work is enqueued), at several pipeline
              depths, to expose dispatch blocking / backpressure.
  window    — end-to-end window latency (dispatch → host collect) vs
              window size and depth, through the real TpuEngine.
  sweep     — matrix of (window, depth) → p50/p99 latency + matches/s,
              the operating-point picker for bench.py.

All timed phases repeat --reps times; min/median/max printed, so the
spread is visible. Run on the chip: ``chiprun -- python
scripts/profile_stages.py ...`` (JAX_PLATFORMS=cpu for a mechanics smoke).
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def make_columns(rng, n, start_id, now):
    from matchmaking_tpu.service.contract import RequestColumns

    return RequestColumns(
        ids=np.char.add("p", np.arange(start_id, start_id + n).astype(str)).astype(object),
        rating=rng.normal(1500.0, 300.0, size=n).astype(np.float32),
        rd=np.zeros(n, np.float32),
        region=np.zeros(n, np.int32),
        mode=np.zeros(n, np.int32),
        threshold=np.full(n, np.nan, np.float32),
        enqueued_at=np.full(n, now, np.float64),
    )


def build_engine(pool, capacity, window, pool_block=8192, buckets=None,
                 readback_group=1, prune_window_blocks=0, prune_chunk=128,
                 band_spec="", threshold=100.0):
    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine

    cfg = Config(
        queues=(QueueConfig(rating_threshold=threshold),),
        engine=EngineConfig(
            backend="tpu", pool_capacity=capacity, pool_block=pool_block,
            batch_buckets=tuple(buckets or (window,)), top_k=8,
            readback_group=readback_group,
            prune_window_blocks=prune_window_blocks, prune_chunk=prune_chunk,
            band_spec=band_spec,
        ),
    )
    engine = make_engine(cfg, cfg.queues[0])
    rng = np.random.default_rng(0)
    next_id = 0
    while engine.pool_size() < pool:
        chunk = min(pool - engine.pool_size(), 8192)
        engine.restore_columns(make_columns(rng, chunk, next_id, 0.0), 0.0)
        next_id += chunk
    return engine, rng, next_id


def mode_device(args):
    """Pure device time per step: chain steps with donated pool, sync once."""
    import jax
    import jax.numpy as jnp
    from matchmaking_tpu.core.pool import pack_batch

    engine, rng, next_id = build_engine(args.pool, args.capacity, args.window)
    k = engine.kernels
    # Build one packed batch on device; reuse it (admit rewrites same slots —
    # fine for timing; the step's cost does not depend on values).
    cols = make_columns(rng, args.window, next_id, 0.0)
    slots = engine.pool.allocate_columns(cols)
    batch = engine.pool.batch_arrays_cols(cols, slots, args.window, 0.0)
    packed = jnp.asarray(pack_batch(batch, 0.0))
    pool_dev = engine._dev_pool
    # warmup/compile
    pool_dev, out = k.search_step_packed(pool_dev, packed)
    out.block_until_ready()
    for rep in range(args.reps):
        t0 = time.perf_counter()
        outs = []
        for _ in range(args.iters):
            pool_dev, out = k.search_step_packed(pool_dev, packed)
            outs.append(out)
        outs[-1].block_until_ready()
        dt = time.perf_counter() - t0
        log(f"[device rep{rep}] {args.iters} chained steps: "
            f"{dt * 1e3:.1f} ms total, {dt / args.iters * 1e3:.3f} ms/step "
            f"(B={args.window}, P={k.capacity})")


def mode_prunecheck(args):
    """Rating-banded pruning vs dense at the same pool state: per-step device
    time for both, plus an on-chip bit-exactness check of one step's outputs
    (the pruned step's contract — kernels.py _search_step_pruned)."""
    import jax.numpy as jnp
    from matchmaking_tpu.core.pool import pack_batch
    from matchmaking_tpu.engine.kernels import kernel_set

    w = args.prune_window_blocks or 12
    engine, rng, next_id = build_engine(
        args.pool, args.capacity, args.window, pool_block=args.pool_block,
        prune_window_blocks=w, prune_chunk=args.prune_chunk,
        band_spec="gaussian:1500:300", threshold=args.threshold)
    pruned_k = engine.kernels
    dense_k = kernel_set(
        capacity=pruned_k.capacity, top_k=pruned_k.top_k,
        pool_block=pruned_k.pool_block, glicko2=pruned_k.glicko2,
        widen_per_sec=pruned_k.widen_per_sec,
        max_threshold=pruned_k.max_threshold,
        pair_rounds=pruned_k.pair_rounds)
    cols = make_columns(rng, args.window, next_id, 0.0)
    slots = engine.pool.allocate_columns(cols)
    batch = engine.pool.batch_arrays_cols(cols, slots, args.window, 0.0)
    packed = jnp.asarray(pack_batch(batch, 0.0))
    base_pool = engine._dev_pool

    # On-chip exactness: one step through each kernel from the same state.
    import jax

    p1, o1 = dense_k.search_step_packed(
        jax.tree.map(jnp.copy, base_pool), packed)
    p2, o2 = pruned_k.search_step_packed(
        jax.tree.map(jnp.copy, base_pool), packed)
    same_out = bool(jnp.array_equal(o1, o2, equal_nan=True))
    same_pool = all(bool(jnp.array_equal(p1[f], p2[f])) for f in p1)
    log(f"[prunecheck] outputs bit-identical: {same_out}, "
        f"pool bit-identical: {same_pool} "
        f"(B={args.window}, P={pruned_k.capacity}, "
        f"blocks={pruned_k.n_blocks}, W={pruned_k.prune_window_blocks})")

    # Both compiled variants per kernel: the bench hot path serves all-ANY
    # windows through the nofilter executable, so that pair is the one the
    # headline number sees; the filtered pair covers region/mode traffic.
    for name, k in (("dense", dense_k), ("pruned", pruned_k),
                    ("dense/nf", dense_k), ("pruned/nf", pruned_k)):
        step = (k.search_step_packed_nofilter if name.endswith("/nf")
                else k.search_step_packed)
        pool_dev = jax.tree.map(jnp.copy, base_pool)
        pool_dev, out = step(pool_dev, packed)
        out.block_until_ready()
        times = []
        for rep in range(args.reps):
            t0 = time.perf_counter()
            outs = []
            for _ in range(args.iters):
                pool_dev, out = step(pool_dev, packed)
                outs.append(out)
            outs[-1].block_until_ready()
            times.append((time.perf_counter() - t0) / args.iters * 1e3)
        log(f"[prunecheck {name}] ms/step min/med/max: "
            f"{min(times):.3f}/{statistics.median(times):.3f}/{max(times):.3f}")


def mode_rescanstall(args):
    """Throughput/latency dent of a rescan tick under sustained load:
    streams pipelined windows (depth 4) with one rescan every
    --rescan-every windows, comparing the round-5 OVERLAP discipline (the
    no-admission rescan step joins the pipelined stream) against the
    round-4 DRAIN discipline (flush the pipeline, rescan, flush again).
    The windows keep matching ~everything, so pool size is held by refill
    and the rescan itself finds nothing — isolating pure scheduling cost."""
    import statistics as st

    for discipline in ("overlap", "drain"):
        engine, rng, next_id = build_engine(
            args.pool, args.capacity, args.window,
            pool_block=args.pool_block, readback_group=args.readback_group)
        engine.warmup()   # all step variants incl. the rescan one: no
        # mid-measurement XLA compile can pollute either discipline.

        def refill(now):
            nonlocal next_id
            while engine.pool_size() < args.pool:
                chunk = min(args.pool - engine.pool_size(), 8192)
                engine.restore_columns(
                    make_columns(rng, chunk, next_id, now), now)
                next_id += chunk

        lat, matches = [], 0
        submit = {}
        t0 = time.perf_counter()

        def wall():
            return time.perf_counter() - t0

        def drainall():
            for tok, out in engine.flush():
                if tok in submit:
                    lat.append(time.perf_counter() - submit.pop(tok))
            engine.rescan_tokens.clear()

        n = args.iters * args.reps
        t_start = None
        for i in range(n + 5):
            if i == 5:
                t_start = time.perf_counter()
                matches = 0
            if i % args.rescan_every == 0 and i > 0:
                if discipline == "drain":
                    drainall()
                    engine.rescan_async(args.window, wall())
                    drainall()
                else:
                    engine.rescan_async(args.window, wall())
            cols = make_columns(rng, args.window, next_id, wall())
            next_id += args.window
            tok = engine.search_columns_async(cols, wall())
            submit[tok] = time.perf_counter()
            while engine.inflight() >= args.depth:
                got = engine.collect_ready()
                if not got:
                    time.sleep(0.0005)
                for tok2, out in got:
                    if tok2 in submit:
                        lat.append(time.perf_counter() - submit.pop(tok2))
                        matches += getattr(out, "n_matches", 0)
                    engine.rescan_tokens.discard(tok2)
            refill(wall())
        drainall()
        span = time.perf_counter() - t_start
        ls = sorted(lat)
        log(f"[rescanstall {discipline}] {matches / span:,.0f} matches/s, "
            f"window p50 {st.median(ls) * 1e3:.1f} ms, "
            f"p99 {ls[int(len(ls) * 0.99) - 1] * 1e3:.1f} ms "
            f"({n} windows, rescan every {args.rescan_every})")


def mode_dispatch(args):
    """Host cost of one cached dispatch at increasing numbers of
    already-enqueued (unconsumed) steps — exposes dispatch backpressure."""
    import jax.numpy as jnp
    from matchmaking_tpu.core.pool import pack_batch

    engine, rng, next_id = build_engine(args.pool, args.capacity, args.window)
    k = engine.kernels
    cols = make_columns(rng, args.window, next_id, 0.0)
    slots = engine.pool.allocate_columns(cols)
    batch = engine.pool.batch_arrays_cols(cols, slots, args.window, 0.0)
    packed_np = pack_batch(batch, 0.0)
    pool_dev = engine._dev_pool
    pool_dev, out = k.search_step_packed(pool_dev, jnp.asarray(packed_np))
    out.block_until_ready()

    for depth in (0, 1, 2, 4, 8):
        for rep in range(args.reps):
            out.block_until_ready()  # drain
            outs = []
            for _ in range(depth):  # pre-enqueue `depth` steps
                pool_dev, out = k.search_step_packed(pool_dev, jnp.asarray(packed_np))
                outs.append(out)
            t_h2d0 = time.perf_counter()
            packed_dev = jnp.asarray(packed_np)
            t_h2d1 = time.perf_counter()
            pool_dev, out = k.search_step_packed(pool_dev, packed_dev)
            t_disp = time.perf_counter()
            out.block_until_ready()
            t_sync = time.perf_counter()
            log(f"[dispatch depth={depth} rep{rep}] h2d={1e3*(t_h2d1-t_h2d0):.2f} ms "
                f"jit_call={1e3*(t_disp-t_h2d1):.2f} ms "
                f"sync_after={1e3*(t_sync-t_disp):.2f} ms")


def mode_window(args):
    run_point(args, args.window, args.depth, reps=args.reps, iters=args.iters)


def run_point(args, window, depth, reps, iters):
    if depth < args.readback_group:
        log(f"[warn] depth {depth} < readback-group {args.readback_group}: "
            f"groups never fill before the depth gate blocks — this point "
            f"measures wait-dominated stale seals")
    engine, rng, next_id = build_engine(
        args.pool, args.capacity, window,
        readback_group=args.readback_group)
    results = []
    for rep in range(reps):
        lats, matches, t0 = [], 0, time.perf_counter()
        submit = {}
        done_t = t0

        def handle(tok, out):
            nonlocal matches, done_t
            lats.append(time.perf_counter() - submit.pop(tok))
            matches += out.n_matches
            done_t = time.perf_counter()

        for i in range(iters):
            now = time.perf_counter() - t0
            cols = make_columns(rng, window, next_id, now)
            next_id += window
            tok = engine.search_columns_async(cols, now)
            submit[tok] = time.perf_counter()
            for tok2, out in engine.collect_ready():
                handle(tok2, out)
            while engine.inflight() >= depth:
                got = engine.collect_ready()
                if not got:
                    time.sleep(0.0002)
                for tok2, out in got:
                    handle(tok2, out)
            # refill
            deficit = args.pool - engine.pool_size()
            if deficit >= 8192:
                engine.restore_columns(
                    make_columns(rng, deficit, next_id, now), now)
                next_id += deficit
        for tok2, out in engine.flush():
            handle(tok2, out)
        span = done_t - t0
        lat_ms = np.sort(np.array(lats)) * 1e3
        mps = matches / span if span > 0 else 0
        results.append((mps, float(np.percentile(lat_ms, 50)),
                        float(np.percentile(lat_ms, 99))))
        log(f"[B={window} d={depth} rep{rep}] {mps:.0f} m/s "
            f"p50={results[-1][1]:.1f} ms p99={results[-1][2]:.1f} ms")
    results.sort()
    med = results[len(results) // 2]
    log(f"[B={window} d={depth} MEDIAN] {med[0]:.0f} m/s "
        f"p50={med[1]:.1f} p99={med[2]:.1f}")
    engine.close()
    return med


def mode_sweep(args):
    table = {}
    windows = [int(w) for w in args.sweep_windows.split(",")]
    depths = [int(d) for d in args.sweep_depths.split(",")]
    for window in windows:
        for depth in depths:
            table[(window, depth)] = run_point(
                args, window, depth, reps=args.reps, iters=args.iters)
    log("window depth mps p50 p99")
    for (w, d), (mps, p50, p99) in sorted(table.items()):
        log(f"{w:6d} {d:3d} {mps:8.0f} {p50:7.1f} {p99:7.1f}")


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--mode", choices=("device", "dispatch", "window", "sweep",
                                      "prunecheck", "rescanstall"),
                   default="device")
    p.add_argument("--rescan-every", type=int, default=10,
                   help="rescanstall: windows between rescan ticks")
    p.add_argument("--pool", type=int, default=100_000)
    p.add_argument("--capacity", type=int, default=131_072)
    p.add_argument("--window", type=int, default=2048)
    p.add_argument("--depth", type=int, default=4)
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--reps", type=int, default=3)
    p.add_argument("--sweep-windows", default="256,512,1024,2048")
    p.add_argument("--sweep-depths", default="1,2,3,4")
    p.add_argument("--readback-group", type=int, default=1,
                   help="device-side result grouping for window/sweep modes")
    p.add_argument("--pool-block", type=int, default=8192)
    p.add_argument("--prune-window-blocks", type=int, default=0,
                   help="prunecheck: span width W (0 → mode default)")
    p.add_argument("--prune-chunk", type=int, default=128)
    p.add_argument("--threshold", type=float, default=100.0,
                   help="queue rating_threshold; tighter values shrink the "
                        "admissible rating spans prunecheck measures")
    args = p.parse_args()
    import jax

    log(f"jax {jax.__version__} devices={jax.devices()}")
    dict(device=mode_device, dispatch=mode_dispatch,
         window=mode_window, sweep=mode_sweep,
         prunecheck=mode_prunecheck,
         rescanstall=mode_rescanstall)[args.mode](args)


if __name__ == "__main__":
    main()
