#!/usr/bin/env python
"""Headline benchmark: matches/sec + p99 match latency @ 100k-player pool.

BASELINE.json: the reference (Elixir GenServer pool, sequential ETS scan per
request) caps out around ~2k concurrently-queued players; the north star is
>=100k concurrent players matched at p99 < 50 ms on TPU. This harness:

1. TPU engine: pre-fills the device pool to POOL players (restore path — no
   matching), then streams windows of fresh requests through the full engine
   step (admit scatter -> blockwise score+mask -> streaming top-k -> greedy
   conflict-free pairing -> evict scatter -> D2H), refilling the pool between
   timed windows so every measurement sees a ~POOL-player pool.
2. CPU oracle (reference semantics) at its own viable operating point
   (~2k pool) for the vs_baseline ratio — the reference publishes no numbers
   (BASELINE.json published: {}), so the oracle stands in for it.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": <matches/sec>, "unit": ..., "vs_baseline": ...}
plus supporting fields (device, p99_ms, pool, cpu_mps, ...). Diagnostics go
to stderr. The measured run needs a TPU: with none it exits non-zero, and a
phase that fails fails the run. (The standalone soak and model-check modes
are host-side harnesses and run on any backend.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def require_tpu():
    """The devices this run measures. Exits non-zero when JAX finds no TPU:
    a number from another backend is not a device number, and there is no
    fallback to one."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"bench.py measures the TPU, but JAX found "
            f"{devices[0].platform!r} devices ({devices[0].device_kind})")
    return devices


#: Published HBM bandwidth per chip (bytes/s), keyed by JAX ``device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (819 GB/s per chip).
HBM_PEAK_BYTES_PER_S = {"TPU v5 lite": 819e9}


def hbm_peak(device_kind: str) -> float:
    """HBM peak of ``device_kind``; an unlisted kind is an error, not a
    default."""
    try:
        return HBM_PEAK_BYTES_PER_S[device_kind]
    except KeyError:
        raise SystemExit(
            f"no published HBM peak for device kind {device_kind!r}; add "
            f"it, with its source, to HBM_PEAK_BYTES_PER_S") from None


def device_label(devices) -> dict:
    """What every result line names: the device it ran on, as JAX reports
    it."""
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def make_requests(rng: np.random.Generator, n: int, start_id: int,
                  now: float, threshold: float | None = None):
    from matchmaking_tpu.service.contract import SearchRequest

    ratings = rng.normal(1500.0, 300.0, size=n)
    return [
        SearchRequest(
            id=f"p{start_id + i}",
            rating=float(ratings[i]),
            rating_threshold=threshold,
            enqueued_at=now,
        )
        for i in range(n)
    ]


def make_columns(rng: np.random.Generator, n: int, start_id: int, now: float):
    """Columnar window (the fast path the service batcher also produces)."""
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


def run_engine(engine, rng: np.random.Generator, *, pool_target: int,
               window: int, warmup: int, measured: int, label: str):
    """Stream windows through ``engine.search`` at a sustained pool size.

    Returns (matches_per_sec, per-window latencies in seconds, total matches).
    """
    next_id = 0
    now = 0.0

    def refill() -> None:
        nonlocal next_id, now
        deficit = pool_target - engine.pool_size()
        while deficit > 0:
            chunk = min(deficit, 4096)
            fillers = make_requests(rng, chunk, next_id, now)
            next_id += chunk
            engine.restore(fillers, now)
            deficit -= chunk

    refill()
    log(f"[{label}] pool filled to {engine.pool_size()}")

    latencies: list[float] = []
    total_matches = 0
    measured_time = 0.0
    for i in range(warmup + measured):
        reqs = make_requests(rng, window, next_id, now)
        next_id += window
        t0 = time.perf_counter()
        out = engine.search(reqs, now)
        dt = time.perf_counter() - t0
        now += max(dt, 1e-4)
        if i >= warmup:
            latencies.append(dt)
            total_matches += len(out.matches)
            measured_time += dt
        refill()

    mps = total_matches / measured_time if measured_time > 0 else 0.0
    return mps, latencies, total_matches


def run_engine_pipelined(engine, rng: np.random.Generator, *, pool_target: int,
                         window: int, warmup: int, measured: int, depth: int,
                         label: str, gen=None):
    """Stream windows through the pipelined API (``search_async`` +
    ``collect_ready``) keeping ≤ ``depth`` windows in flight.

    Latency per window = dispatch call → results collected on host (the
    end-to-end path a request sees past the batcher). Throughput is counted
    over the measured tokens' span.
    """
    gen = gen or make_columns
    next_id = 0
    wall0 = time.perf_counter()

    def wall() -> float:
        return time.perf_counter() - wall0

    def refill() -> None:
        nonlocal next_id
        deficit = pool_target - engine.pool_size()
        while deficit > 0:
            chunk = min(deficit, 8192)
            engine.restore_columns(gen(rng, chunk, next_id, wall()), wall())
            next_id += chunk
            deficit -= chunk

    refill()
    log(f"[{label}] pool filled to {engine.pool_size()}")

    submit_t: dict[int, float] = {}
    timed: dict[int, bool] = {}
    latencies: list[float] = []
    total_matches = 0
    t_start = None
    t_last = None

    def handle(token: int, out) -> None:
        nonlocal total_matches, t_last
        lat = time.perf_counter() - submit_t.pop(token)
        if timed.pop(token):
            latencies.append(lat)
            total_matches += out.n_matches
            t_last = time.perf_counter()

    for i in range(warmup + measured):
        cols = gen(rng, window, next_id, wall())
        next_id += window
        if i == warmup:
            t_start = time.perf_counter()
        tok = engine.search_columns_async(cols, wall())
        submit_t[tok] = time.perf_counter()
        timed[tok] = i >= warmup
        for tok2, out in engine.collect_ready():
            handle(tok2, out)
        while engine.inflight() >= depth:
            got = engine.collect_ready()
            if not got:
                time.sleep(0.0005)
            for tok2, out in got:
                handle(tok2, out)
        refill()
    for tok2, out in engine.flush():
        handle(tok2, out)

    span = (t_last - t_start) if (t_start and t_last and t_last > t_start) else 0.0
    mps = total_matches / span if span > 0 else 0.0
    return mps, latencies, total_matches


def roofline(engine, rng: np.random.Generator, *, window: int,
             iters: int = 30) -> dict:
    """Pure device-step cost + achieved-bandwidth roofline (no per-step D2H:
    steps chain on the donated pool, one sync at the end — isolates device
    time from readback latency).

    The blockwise score scan reads every pool column once per window, so
    pool-bytes/step is the HBM traffic floor; utilization is reported against
    the device kind's HBM peak (HBM_PEAK_BYTES_PER_S). Low utilization at a
    small window means the step is latency/compute-bound, not
    bandwidth-bound — both numbers plus pair-scores/s are recorded so
    regressions are attributable."""
    import jax
    import jax.numpy as jnp

    from matchmaking_tpu.core.pool import pack_batch

    cols = make_columns(rng, window, 10_000_000, 0.0)
    slots = engine.pool.allocate_columns(cols)
    batch = engine.pool.batch_arrays_cols(cols, slots, window, 0.0)
    packed = jnp.asarray(pack_batch(batch, 0.0))
    pool_dev = engine._dev_pool
    pool_bytes = sum(x.nbytes for x in jax.tree.leaves(pool_dev))
    step_bytes = pool_bytes + packed.nbytes
    k = engine.kernels
    # Same compiled variant the engine's hot path would pick for this
    # window (the all-ANY no-filter variant for the bench's requests).
    step = engine._step_fn(batch)
    pool_dev, out = step(pool_dev, packed)  # warm/compile
    out.block_until_ready()
    t0 = time.perf_counter()
    outs = []
    for _ in range(iters):
        pool_dev, out = step(pool_dev, packed)
        outs.append(out)
    outs[-1].block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    # The chained steps MATCH (retiring resident device-pool players the
    # host mirror still holds) and the donated pool buffers were consumed,
    # so the engine's mirror and device state have diverged: roofline must
    # be the engine's LAST use (bench_tpu calls it after the measured reps
    # and then discards the engine). Write the pool back + release the
    # scratch slots only so teardown paths stay functional.
    engine.pool.release(slots)
    engine._dev_pool = pool_dev
    peak = hbm_peak(jax.devices()[0].device_kind)
    return {
        "device_step_ms": round(dt * 1e3, 3),
        "hbm_bytes_per_step": step_bytes,
        "hbm_bytes_per_s": round(step_bytes / dt, 1),
        "hbm_util_of_peak": round(step_bytes / dt / peak, 4),
        "pair_scores_per_s": round(window * k.capacity / dt, 1),
    }


def _bucketed_geometry(capacity: int, pool_block: int,
                       window: int) -> dict:
    """EngineConfig extras for hierarchical bucketed formation (ISSUE 14):
    band-per-block allocation + a span budget of ~33% of the blocks.

    Sizing math (N(1500, 300) population, 100-ELO default threshold,
    equal-mass bands): a central player's admissible candidates are
    ~2·thr·φ(0)/σ ≈ 26.7% of the population mass — the irreducible
    candidate-bucket fraction — plus the sorted chunk's own mass
    (c/window of the window) and the f32 inflation. Chunks of
    ~window/64 keep the chunk-mass term under ~2% of the blocks, so a
    33% span budget leaves headroom over the ~29% requirement and every
    feasible window reports formation_touched_frac ≈ 1/3 ≪ 1 (the
    dense-fallback cond covers distribution drift)."""
    n_blocks = max(1, capacity // pool_block)
    return dict(
        bucketed=True,
        band_spec="gaussian:1500:300",
        prune_window_blocks=max(2, -(-n_blocks * 33 // 100)),
        prune_chunk=max(8, min(128, window // 64)),
    )


def bench_tpu(args) -> dict:
    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine

    cfg = Config(
        queues=(QueueConfig(rating_threshold=100.0),),
        engine=EngineConfig(
            backend="tpu",
            pool_capacity=args.capacity,
            pool_block=args.pool_block,
            batch_buckets=(16, 64, 256, args.window),
            top_k=8,
            readback_group=args.readback_group,
            **(_bucketed_geometry(args.capacity, args.pool_block,
                                  args.window)
               if args.bucketed else {}),
        ),
    )
    engine = make_engine(cfg, cfg.queues[0])
    rng = np.random.default_rng(0)
    # The measured phase runs ``repeats`` times and the MEDIAN run is
    # reported; all samples are logged, so the spread is visible.
    profiler_cm = None
    if args.profile_dir:
        import jax

        profiler_cm = jax.profiler.trace(args.profile_dir)
        profiler_cm.__enter__()
        log(f"[tpu] jax.profiler trace → {args.profile_dir}")

    from matchmaking_tpu.utils.metrics import CompileCounter

    runs = []
    compiles_after_warmup: int | None = None
    t0 = time.perf_counter()
    try:
        for rep in range(max(1, args.repeats)):
            if rep == 1:
                # Every bucket shape compiled during rep 0; any further
                # compile is a hot-path recompile (the p99 cliff SURVEY §5's
                # recompile counter exists to expose).
                compiles_after_warmup = CompileCounter.count()
            mps, lats, total = run_engine_pipelined(
                engine, rng, pool_target=args.pool, window=args.window,
                warmup=args.warmup, measured=args.windows, depth=args.depth,
                label=f"tpu rep{rep}")
            lat_ms = np.sort(np.asarray(lats)) * 1e3
            runs.append({
                "matches_per_sec": mps,
                "p50_ms": float(np.percentile(lat_ms, 50)),
                "p99_ms": float(np.percentile(lat_ms, 99)),
                "total_matches": total,
            })
            log(f"[tpu rep{rep}] {total} matches, {mps:.0f}/s, "
                f"p99 {runs[-1]['p99_ms']:.0f} ms")
    finally:
        # The failing run is exactly the one whose profile matters.
        if profiler_cm is not None:
            profiler_cm.__exit__(None, None, None)
    log(f"[tpu] {time.perf_counter() - t0:.1f}s total incl. fill/compile")
    if hasattr(engine, "span_report"):
        log(f"[tpu] spans: {engine.span_report()}")
    recompiles = (CompileCounter.count() - compiles_after_warmup
                  if compiles_after_warmup is not None else None)
    log(f"[tpu] xla compiles total={CompileCounter.count()} "
        f"hot-path recompiles={recompiles}")
    roof = {}
    if not args.skip_roofline:
        roof = roofline(engine, rng, window=args.window)
        log(f"[tpu] roofline: {roof}")
    runs.sort(key=lambda r: r["matches_per_sec"])
    median = runs[len(runs) // 2]
    formation = (engine.formation_report()
                 if hasattr(engine, "formation_report") else None)
    return {
        **median,
        "pool": args.pool,
        "window": args.window,
        "all_runs_mps": [round(r["matches_per_sec"], 1) for r in runs],
        "hot_path_recompiles": recompiles,
        "spans": (engine.span_report()
                  if hasattr(engine, "span_report") else {}),
        **({"formation_touched_frac":
            formation.get("formation_touched_frac")}
           if formation else {}),
        **roof,
    }


def bench_e2e(args) -> dict:
    """Service-level end-to-end latency (the BASELINE metric IS end-to-end:
    a player experiences broker→middleware→batcher→engine→reply). Poisson
    arrivals are published through the in-process broker with an
    ``x-first-received`` stamp; each matched reply carries ``latency_ms`` =
    reply-publish time minus that stamp — exactly the wire-visible match
    latency. The pool is pre-filled to the target via the restore path.

    Caveats recorded with the numbers: this host has ONE core, so the
    service's Python ingress shares it with engine host work — the
    sustainable arrival rate is host-bound, not device-bound."""
    import asyncio

    async def run() -> dict:
        from matchmaking_tpu.config import (
            BatcherConfig,
            BrokerConfig,
            Config,
            EngineConfig,
            ObservabilityConfig,
            OverloadConfig,
            QueueConfig,
        )
        from matchmaking_tpu.service.app import MatchmakingApp
        from matchmaking_tpu.service.broker import Properties
        from matchmaking_tpu.service.loadgen import parse_tier_mix
        from matchmaking_tpu.service.overload import stamp_tier
        from matchmaking_tpu.testing.drain import settle

        tier_mix = parse_tier_mix(getattr(args, "e2e_tier_mix", ""))
        cfg = Config(
            queues=(QueueConfig(rating_threshold=100.0,
                                send_queued_ack=False),),
            engine=EngineConfig(
                backend="tpu", pool_capacity=args.capacity,
                pool_block=args.pool_block,
                batch_buckets=(16, 64, 256, args.window), top_k=8,
                pipeline_depth=args.depth,
                readback_group=args.readback_group,
                warm_start=True),
            batcher=BatcherConfig(max_batch=args.window, max_wait_ms=3.0),
            broker=BrokerConfig(prefetch=max(8 * args.window, 4096)),
            # Overload mode (ISSUE 5): bound the waiting pool so the
            # saturation sweep measures ADMITTED-request latency under an
            # honest shed policy instead of unbounded queueing collapse.
            # Tiered mode (ISSUE 7, --e2e-tier-mix): priority classes +
            # EDF window cutting + lowest-tier-first eviction, so the
            # sweep rows show PER-TIER p99/shed under overload.
            overload=(OverloadConfig(
                max_waiting=args.e2e_max_waiting,
                # max+1, not len: a sparse mix ("0:0.5,3:0.5") must
                # configure enough tiers that x-tier 3 isn't clamped into
                # a higher-priority class (and its shed_requests_t3
                # counter actually exists to read).
                tiers=(max(tier_mix) + 1 if tier_mix else 1),
                edf=bool(tier_mix),
                shed_policy=("oldest" if tier_mix else "reject"))
                if args.e2e_max_waiting > 0 else
                OverloadConfig(tiers=(max(tier_mix) + 1 if tier_mix else 1),
                               edf=bool(tier_mix))),
            # Continuous telemetry + SLO monitoring (ISSUE 6): the BENCH
            # json records attainment and idle-fraction TRAJECTORIES, not
            # just the headline throughput rows. Short burn windows so a
            # few-second bench phase spans several evaluation windows.
            observability=ObservabilityConfig(
                slo_target_ms=float(args.e2e_slo_ms),
                slo_objective=0.99,
                slo_fast_window_s=2.0, slo_slow_window_s=10.0,
                snapshot_interval_s=0.5),
        )
        app = MatchmakingApp(cfg)
        await app.start()
        rt = app.runtime("matchmaking.search")
        rng = np.random.default_rng(3)

        def prefill():
            next_id = 30_000_000
            deficit = args.pool - rt.engine.pool_size()
            while deficit > 0:
                chunk = min(deficit, 8192)
                rt.engine.restore_columns(
                    make_columns(rng, chunk, next_id, time.time()),
                    time.time())
                next_id += chunk
                deficit -= chunk

        async with rt._engine_lock:
            await asyncio.to_thread(prefill)
        pool_start = rt.engine.pool_size()
        log(f"[e2e] pool prefilled to {pool_start}")

        reply_q = "bench.replies"
        app.broker.declare_queue(reply_q)
        lat_ms: list[float] = []
        match_ids: set[str] = set()
        #: Tiered mode: correlation id → assigned tier (the loadgen-side
        #: truth — no tier echo needed from the service) + per-tier
        #: matched latencies for the phase's per-tier p99 rows.
        tier_of_corr: dict[str, int] = {}
        tier_lat: dict[int, list[float]] = {t: [] for t in (tier_mix or ())}

        async def on_reply(delivery) -> None:
            d = json.loads(delivery.body)
            # Only measured-phase arrivals count: warmup players ("w...")
            # that match late carry early x-first-received stamps that
            # would inflate the percentiles; prefilled players have no
            # reply_to at all.
            if (d.get("status") == "matched"
                    and str(d.get("player_id", "")).startswith("e")):
                lat_ms.append(float(d.get("latency_ms", 0.0)))
                if tier_mix:
                    t = tier_of_corr.get(delivery.properties.correlation_id)
                    if t is not None:
                        tier_lat[t].append(float(d.get("latency_ms", 0.0)))
                # Distinct matches, not replies/2: most matches pair one
                # measured arrival with a prefilled (reply-less) player and
                # produce exactly ONE counted reply — halving reply count
                # would undercount the match rate by up to 2x.
                mid = (d.get("match") or {}).get("match_id")
                if mid:
                    match_ids.add(mid)

        app.broker.basic_consume(reply_q, on_reply, prefetch=1_000_000)

        # Warmup: compile every bucket shape outside the measured phase.
        wrng = np.random.default_rng(4)
        for k, burst in enumerate((8, 40, 160, args.window)):
            r = wrng.normal(1500.0, 300.0, size=burst)
            for j in range(burst):
                app.broker.publish(
                    cfg.broker.request_queue,
                    f'{{"id":"w{k}_{j}","rating":{r[j]:.2f}}}'.encode(),
                    Properties(reply_to=reply_q, correlation_id=f"w{k}_{j}",
                               headers={"x-first-received":
                                        f"{time.time():.6f}"}))
            await settle(app, rt, cfg.broker.request_queue,
                         reply_queues=(reply_q,))
        lat_ms.clear()
        log("[e2e] buckets warm; starting measured Poisson phases")

        async def poisson(rate: float, duration: float, tag: str) -> dict:
            """One measured Poisson arrival phase at ``rate`` req/s.
            Exponential gaps, submitted in micro-bursts so the event loop
            isn't woken per message on this 1-core host."""
            lat_ms.clear()
            match_ids.clear()
            tier_of_corr.clear()
            for rows in tier_lat.values():
                rows.clear()
            # Per-PHASE shed accounting: the counters are app-lifetime
            # monotone and every sweep row shares this app — absolute
            # reads would fold the headline + earlier rows' sheds into
            # each later row.
            shed0 = app.metrics.counters.get("shed_requests")
            expired0 = app.metrics.counters.get("expired_requests")
            tier_base = {
                t: (app.metrics.counters.get(f"shed_requests_t{t}"),
                    app.metrics.counters.get(f"expired_requests_t{t}"))
                for t in (tier_mix or ())}
            ratings = rng.normal(1500.0, 300.0,
                                 size=int(rate * duration * 2) + 16)
            tiers = (rng.choice(
                np.fromiter(tier_mix, np.int64, len(tier_mix)),
                size=ratings.size,
                p=np.fromiter(tier_mix.values(), np.float64, len(tier_mix)))
                if tier_mix else None)
            gaps = rng.exponential(1.0 / rate, size=ratings.size)
            t0 = time.perf_counter()
            sched = np.cumsum(gaps)
            i = 0
            while i < ratings.size and sched[i] <= duration:
                now_rel = time.perf_counter() - t0
                # publish everything whose scheduled arrival has passed
                while i < ratings.size and sched[i] <= min(now_rel, duration):
                    pid = f"e{tag}_{i}"
                    body = (f'{{"id":"{pid}","rating":{ratings[i]:.2f}}}'
                            ).encode()
                    headers = {"x-first-received": f"{time.time():.6f}"}
                    if tiers is not None:
                        t = int(tiers[i])
                        stamp_tier(headers, t)
                        tier_of_corr[pid] = t
                    app.broker.publish(
                        cfg.broker.request_queue, body,
                        Properties(reply_to=reply_q, correlation_id=pid,
                                   headers=headers))
                    i += 1
                if i < ratings.size and sched[i] > now_rel:
                    await asyncio.sleep(min(sched[i] - now_rel, 0.005))
            span = time.perf_counter() - t0
            # Snapshot BEFORE the drain: the sustained-rate criterion must
            # count only matches delivered while arrivals were still
            # flowing — replies landing during the drain are backlog being
            # worked off, and counting them against the arrival span would
            # make an oversaturated service look like it kept up.
            matched_in_span = len(lat_ms)
            matches_in_span = len(match_ids)
            # Drain: give in-flight windows + replies time to land (the
            # percentiles DO include drained replies — those are real
            # latencies of this phase's requests).
            if not await settle(app, rt, cfg.broker.request_queue,
                                reply_queues=(reply_q,)):
                log(f"[e2e {tag}] WARNING: backlog not drained — "
                    "later rows may be contaminated")
            arr = (np.sort(np.asarray(lat_ms)) if lat_ms
                   else np.array([0.0]))
            row = {
                "e2e_offered_req_s": rate,
                "e2e_requests": i,
                "e2e_rate_req_s": round(i / span, 1),
                "e2e_players_matched": len(lat_ms),
                "e2e_matched_per_s": round(matched_in_span / span, 1),
                "e2e_matches_per_sec": round(matches_in_span / span, 1),
                "e2e_p50_ms": round(float(np.percentile(arr, 50)), 3),
                "e2e_p99_ms": round(float(np.percentile(arr, 99)), 3),
                "e2e_drained": drained,
                "e2e_pool_end": rt.engine.pool_size(),
            }
            if args.e2e_max_waiting > 0:
                row["e2e_shed"] = int(
                    app.metrics.counters.get("shed_requests") - shed0)
                row["e2e_expired"] = int(
                    app.metrics.counters.get("expired_requests") - expired0)
            if tier_mix:
                # Per-tier p99/shed/expired (ISSUE 7): the row that shows
                # ordered degradation — tier 0 holding while the lowest
                # tier absorbs the shedding.
                row["e2e_tiers"] = {
                    str(t): {
                        "offered": (int((tiers[:i] == t).sum())
                                    if tiers is not None else 0),
                        "matched": len(tier_lat[t]),
                        "p99_ms": (round(float(np.percentile(
                            np.asarray(tier_lat[t]), 99)), 3)
                            if tier_lat[t] else None),
                        "shed": int(app.metrics.counters.get(
                            f"shed_requests_t{t}") - tier_base[t][0]),
                        "expired": int(app.metrics.counters.get(
                            f"expired_requests_t{t}") - tier_base[t][1]),
                    }
                    for t in sorted(tier_mix)
                }
            return row

        headline = await poisson(float(args.e2e_rate),
                                 float(args.e2e_seconds), "h")
        headline["e2e_pool_start"] = pool_start

        # Saturation sweep: escalate offered load to find the knee of the
        # single-process service (round-4 verdict #1: the engine does 64k
        # matches/s but the service was only proven at ~6k offered). The
        # knee is the highest offered rate the service still clears at
        # ≥90% (matched players/s vs offered arrivals/s).
        sweep_rows = []
        knee = None
        if args.e2e_rates:
            for r in (float(x) for x in args.e2e_rates.split(",")):
                async with rt._engine_lock:
                    await asyncio.to_thread(prefill)
                row = await poisson(r, float(args.e2e_sweep_seconds),
                                    f"k{int(r)}")
                log(f"[e2e sweep] {row}")
                sweep_rows.append(row)
                if row["e2e_matched_per_s"] >= 0.9 * r:
                    knee = max(knee or 0.0, r)

        # Snapshot the final /metrics-style report into the BENCH json:
        # future rounds get stage-level trajectories (per-stage latency
        # histograms, engine counters, broker stats), not just the headline
        # matches/s + p99 rows.
        from matchmaking_tpu.service.observability import build_report

        app.sample_telemetry()  # final trajectory point before teardown
        metrics_report = build_report(app)
        out = dict(headline)
        if sweep_rows:
            out["e2e_sweep"] = sweep_rows
            out["e2e_knee_req_s"] = knee
        # SLO attainment + device idle fraction (ISSUE 6): the measurement
        # substrate the hot-path rewrite and elastic placement consume.
        attr = app.attribution.snapshot()["queues"].get(
            cfg.broker.request_queue, {})
        out["e2e_slo_target_ms"] = float(args.e2e_slo_ms)
        out["e2e_slo_attainment"] = attr.get("slo_attainment")
        out["e2e_wait_fraction"] = attr.get("wait_fraction")
        # Per-category attribution shares (ISSUE 9): where the e2e span
        # went — publish_lag/encode/middleware/ingress vs device work —
        # recorded into the BENCH json so the hot-path trajectory
        # ("publish_lag + middleware/ingress share reduced") is diffable
        # round over round, not just the headline rate.
        out["e2e_attribution"] = {
            name: {"kind": cat.get("kind"), "share": cat.get("share"),
                   "p99_ms": cat.get("p99_ms")}
            for name, cat in (attr.get("categories") or {}).items()
        }
        # Consume/decode ingest share (ISSUE 12): the broker-consume +
        # wire-decode WORK fraction of the settled span — the number the
        # consume_batch seam exists to shrink. Recorded top-level so
        # bench_diff gates it direction-aware (lower is better).
        cats = attr.get("categories") or {}
        out["e2e_consume_share"] = round(
            sum((cats.get(c) or {}).get("share") or 0.0
                for c in ("consume", "decode")), 4)
        if hasattr(rt.engine, "util_report"):
            u = rt.engine.util_report()
            out["e2e_idle_fraction"] = u["idle_fraction"]
            out["e2e_effective_occupancy"] = u["effective_occupancy"]
        out["telemetry"] = app.telemetry.snapshot(
            limit=240, prefixes=("idle_frac", "slo_good", "slo_total",
                                 "pool_size", "stage_total_p99_ms",
                                 "effective_occupancy", "batch_fill"))
        out["metrics_report"] = metrics_report
        await app.stop()
        return out

    return asyncio.run(run())


def bench_quality_frontier(args) -> dict:
    """The measured quality-vs-latency frontier (ISSUE 8; ``--e2e-quality``).

    Sweeps the queue's ``rating_threshold`` (and optionally
    ``widen_per_sec``) across fresh single-queue apps at a fixed offered
    load, and records each point's match-quality distribution
    (p10/p50/mean), engine-observed wait-at-match p99, client-observed
    latency p99, and the per-rating-bucket disparity gaps — the Cinder-style
    fast-vs-fair tradeoff as ``e2e_frontier`` rows in the BENCH json.

    A STRICTER threshold buys closer-rated matches (mean rating distance
    falls) at the cost of longer waits; the monotone flags compare the
    extremes so a frontier that fails to trade is visible in the artifact.
    This is the baseline any future pluggable match-objective kernel
    (ROADMAP) must beat: better quality at equal wait, or equal quality
    faster.
    """
    import asyncio

    from matchmaking_tpu.config import (
        BatcherConfig,
        BrokerConfig,
        Config,
        EngineConfig,
        ObservabilityConfig,
        QueueConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.loadgen import offered_load

    thresholds = [float(x) for x in args.e2e_quality_thresholds.split(",")
                  if x.strip()]
    rate = float(args.e2e_quality_rate)
    seconds = float(args.e2e_quality_seconds)
    widen = float(args.e2e_quality_widen)
    # Small geometry on purpose: the frontier is a SHAPE measurement (how
    # quality trades against wait), not a throughput row.
    capacity = min(args.capacity, 8192)

    async def point(threshold: float, spec: bool = False) -> dict:
        cfg = Config(
            queues=(QueueConfig(rating_threshold=threshold,
                                widen_per_sec=widen,
                                max_threshold=max(400.0, threshold),
                                send_queued_ack=False),),
            engine=EngineConfig(backend="tpu", pool_capacity=capacity,
                                pool_block=min(args.pool_block, capacity),
                                batch_buckets=(16, 64, 256), top_k=8,
                                pipeline_depth=args.depth,
                                warm_start=True,
                                # Speculation axis (ISSUE 16): the same
                                # point with gap-cycle speculation on.
                                spec_formation=spec,
                                spec_interval_ms=10.0),
            batcher=BatcherConfig(max_batch=256, max_wait_ms=3.0),
            broker=BrokerConfig(prefetch=8192),
            observability=ObservabilityConfig(snapshot_interval_s=0.0,
                                              quality_report_every=4),
        )
        app = MatchmakingApp(cfg)
        await app.start()
        rt = app.runtime(cfg.broker.request_queue)
        res = await offered_load(
            app, cfg.broker.request_queue, rate=rate, duration=seconds,
            seed=11, quality_stats=True,
            rating_sigma=float(args.e2e_quality_sigma))
        # Exact engine totals for the disparity read: flush forces the
        # device-accumulator snapshot.
        async with rt._engine_lock:
            await asyncio.to_thread(rt.engine.flush)
        rep = (rt.engine.quality_report()
               if hasattr(rt.engine, "quality_report") else None) or {}
        sr = (rt.engine.spec_report()
              if spec and hasattr(rt.engine, "spec_report") else None) or {}
        await app.stop()
        qs = res.get("quality", {})
        return {
            "threshold": threshold,
            "spec_formation": spec,
            "spec_hit_rate": sr.get("spec_hit_rate"),
            "widen_per_sec": widen,
            "offered_req_s": rate,
            "matched": qs.get("matched", 0),
            "matched_per_s": res.get("matched_per_s"),
            "quality_mean": qs.get("quality_mean"),
            "quality_p10": qs.get("quality_p10"),
            "quality_p50": qs.get("quality_p50"),
            "wait_at_match_ms_p50": qs.get("waited_ms_p50"),
            "wait_at_match_ms_p99": qs.get("waited_ms_p99"),
            "latency_ms_p99": qs.get("latency_ms_p99"),
            "wait_gap_ms_mean": qs.get("wait_gap_ms_mean"),
            "spread_mean": rep.get("spread_mean"),
            "engine_wait_p90_s": rep.get("wait_p90_s"),
            "quality_disparity": rep.get("disparity", {}).get("quality_gap"),
            "wait_p90_disparity_s": rep.get("disparity",
                                            {}).get("wait_p90_gap_s"),
            "sent": res.get("sent", 0),
        }

    rows = []
    for thr in thresholds:
        row = asyncio.run(point(thr))
        log(f"[e2e-quality thr={thr:g}] {row}")
        rows.append(row)
    out: dict = {"e2e_frontier": rows}
    if args.e2e_quality_spec:
        # Speculation axis (ISSUE 16 satellite): the SAME sweep with
        # gap-cycle speculation on, kept in a separate row list so
        # bench_diff matches spec-on points against spec-on baselines.
        # The in-run gate: fairness must not pay for the overlap —
        # per-rating-bucket quality disparity at each threshold with
        # speculation on must stay within 10% (plus a small absolute
        # slack for near-zero gaps) of the spec-off point.
        spec_rows = []
        for thr in thresholds:
            row = asyncio.run(point(thr, spec=True))
            log(f"[e2e-quality thr={thr:g} spec=on] {row}")
            spec_rows.append(row)
        out["e2e_frontier_spec"] = spec_rows
        off_by_thr = {r["threshold"]: r for r in rows}
        gate: bool | None = None
        for sr_row in spec_rows:
            base = off_by_thr.get(sr_row["threshold"])
            if base is None:
                continue
            d_off = base.get("quality_disparity")
            d_on = sr_row.get("quality_disparity")
            if d_off is None or d_on is None:
                continue
            ok = d_on <= d_off + max(0.10 * d_off, 0.02)
            gate = ok if gate is None else (gate and ok)
            if not ok:
                log(f"[e2e-quality thr={sr_row['threshold']:g}] "
                    f"disparity regressed with speculation on: "
                    f"{d_off:.4f} -> {d_on:.4f}")
        if gate is not None:
            out["e2e_frontier_spec_disparity_ok"] = gate
    # Monotone-tradeoff flags between the sweep extremes (sorted by
    # threshold): stricter matching must buy a smaller mean rating
    # distance and cost a longer wait, or the frontier didn't trade.
    ordered = sorted((r for r in rows if r["matched"]),
                     key=lambda r: r["threshold"])
    if len(ordered) >= 2:
        lo, hi = ordered[0], ordered[-1]
        if lo.get("spread_mean") is not None and hi.get("spread_mean") is not None:
            out["e2e_frontier_spread_monotone"] = (
                lo["spread_mean"] <= hi["spread_mean"])
        if (lo.get("wait_at_match_ms_p50") is not None
                and hi.get("wait_at_match_ms_p50") is not None):
            out["e2e_frontier_wait_monotone"] = (
                lo["wait_at_match_ms_p50"] >= hi["wait_at_match_ms_p50"])
    return out


def bench_multiproc(args) -> dict:
    """Multi-process ingress scaling: N supervised self-driving workers
    (service/multiproc.WorkerSupervisor + service/loadgen), each running
    the FULL ingress path (broker → decode → middleware → batcher → engine
    → publish) against its own queue partition. No RabbitMQ exists in this
    environment, so workers drive themselves instead of sharing a network
    broker (loadgen.py docstring).

    Interpretation on THIS bench host (1 core): the aggregate is
    core-bound by construction — the N=1 row IS the per-process ingress
    ceiling, and the N=2 row pins that partitioned share-nothing workers
    add no coordination overhead beyond the core they fight over. On an
    M-core deployment the per-worker ceiling multiplies by min(M, N); the
    architecture (one pool owner per queue, AMQP routing by queue name)
    has no cross-worker communication to cap it."""
    import subprocess
    import sys
    import tempfile

    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.service.multiproc import WorkerSupervisor

    rows = []
    for n in (1, 2):
        cfg = Config(
            queues=tuple(QueueConfig(name=f"lg{i}", send_queued_ack=False)
                         for i in range(n)),
            engine=EngineConfig(backend="cpu", pool_capacity=4096),
        )
        outs = []
        extra = {}
        for i in range(n):
            fd, path = tempfile.mkstemp(prefix=f"mm_lg{i}_", suffix=".json")
            os.close(fd)
            outs.append(path)
            extra[i] = {
                "MM_LOADGEN_RATE": str(args.mp_rate),
                "MM_LOADGEN_SECONDS": str(args.mp_seconds),
                "MM_LOADGEN_DEADLINE_MS": str(args.mp_deadline_ms),
                "MM_LOADGEN_OUT": path,
                "JAX_PLATFORMS": "cpu",
            }
        sup = WorkerSupervisor(
            cfg, n,
            command=[sys.executable, "-m", "matchmaking_tpu.service.loadgen"],
            extra_env=extra)
        sup.start()
        try:
            for w in sup.workers:
                w.proc.wait(timeout=args.mp_seconds + 60)
        except subprocess.TimeoutExpired:
            log(f"[multiproc] worker fleet n={n} timed out")
        finally:
            sup.stop()
        results = []
        for path in outs:
            try:
                with open(path) as f:
                    results.append(json.load(f))
            except (OSError, json.JSONDecodeError):
                pass
            try:
                os.unlink(path)
            except OSError:
                pass
        row = {
            "workers": n,
            "completed": len(results),
            "offered_req_s_per_worker": float(args.mp_rate),
            "agg_sent_req_s": round(sum(r["sent_req_s"] for r in results), 1),
            "agg_matched_per_s": round(
                sum(r["matched_per_s"] for r in results), 1),
        }
        log(f"[multiproc] {row}")
        rows.append(row)
    return {"multiproc": rows, "multiproc_host_cores": os.cpu_count()}


def comms_accounting_rows(*, capacity: int = 65_536, team_size: int = 5,
                          frontier_k: int = 1024,
                          shard_counts=(2, 4, 8)) -> list[dict]:
    """The sharded team/role comms phase (ISSUE 1 tentpole artifact): for
    each mesh size D, build BOTH sharded paths and report per-device
    per-step ICI bytes + formation rows — allgather-replicated is O(P)
    regardless of D, the ppermute ring frontier is O(P/D + K·D). Each row
    also EXECUTES one step per path on an identical seeded pool and
    records whether the packed outputs are byte-identical, so the table
    is a measured artifact, not prose. Runs on any backend with >= D
    devices (tests/CI: the 8-virtual-device CPU mesh; set
    XLA_FLAGS=--xla_force_host_platform_device_count=8)."""
    import jax
    import jax.numpy as jnp

    from matchmaking_tpu.engine.role_kernels import ShardedRoleKernelSet
    from matchmaking_tpu.engine.sharded import pool_mesh
    from matchmaking_tpu.engine.teams import ShardedTeamKernelSet

    if frontier_k <= 0:
        raise SystemExit(
            "--comms needs --comms-frontier-k > 0: the comms phase compares "
            "the ring path against the allgather fallback, and the kernel "
            "sets only compile the ring step when frontier_k is set")
    n_dev = len(jax.devices())
    rows = []
    for D in shard_counts:
        if D > n_dev:
            rows.append({"n_shards": D, "skipped": f"only {n_dev} devices"})
            continue
        for family in ("team", "role"):
            if family == "team":
                ks = ShardedTeamKernelSet(
                    capacity=capacity, team_size=team_size,
                    widen_per_sec=0.0, max_threshold=400.0,
                    mesh=pool_mesh(D), max_matches=64,
                    frontier_k=frontier_k)
                pack_rows, mask_of = 9, None
            else:
                ks = ShardedRoleKernelSet(
                    capacity=capacity, team_size=team_size,
                    role_slots=("tank", "healer", "dps", "dps", "dps"),
                    widen_per_sec=0.0, max_threshold=400.0,
                    mesh=pool_mesh(D), max_matches=64,
                    frontier_k=frontier_k)
                pack_rows, mask_of = 10, ks.mask_of
            acct = ks.comms_accounting()
            # One executed step per path on an identical seeded pool:
            # occupancy under K per shard, so ring must be bit-identical.
            P = ks.capacity
            rng = np.random.default_rng(17)
            n_active = min(frontier_k, ks.local_capacity, 512)
            arrays = {
                "rating": np.zeros(P, np.float32),
                "rd": np.zeros(P, np.float32),
                "region": np.zeros(P, np.int32),
                "mode": np.zeros(P, np.int32),
                "threshold": np.full(P, 120.0, np.float32),
                "enqueue_t": np.zeros(P, np.float32),
                "active": np.zeros(P, bool),
            }
            arrays["rating"][:n_active] = rng.normal(1500.0, 60.0, n_active)
            arrays["region"][:n_active] = 1
            arrays["mode"][:n_active] = 1
            arrays["active"][:n_active] = True
            if mask_of is not None:
                arrays["role_mask"] = np.zeros(P, np.int32)
                arrays["role_mask"][:n_active] = [
                    [mask_of(("tank",)), mask_of(("healer",)),
                     mask_of(("dps",)), mask_of(())][i % 4]
                    for i in range(n_active)]
            packed = np.zeros((pack_rows, 16), np.float32)
            packed[0] = float(P)
            packed[pack_rows - 1] = 1.0
            t0 = time.perf_counter()
            _, out_rep = ks.search_step_packed(
                ks.place_pool(arrays), jnp.asarray(packed))
            out_rep = np.asarray(out_rep)
            t_rep = time.perf_counter() - t0
            t0 = time.perf_counter()
            _, out_ring = ks.search_step_packed_ring(
                ks.place_pool(arrays), jnp.asarray(packed))
            out_ring = np.asarray(out_ring)
            t_ring = time.perf_counter() - t0
            rows.append({
                "family": family, "n_shards": D, "capacity": P,
                "frontier_k": ks.frontier_k,
                "allgather_ici_recv_bytes": acct["allgather"]["ici_recv_bytes"],
                "ring_ici_recv_bytes": acct["ring"]["ici_recv_bytes"],
                "allgather_formation_rows": acct["allgather"]["formation_rows"],
                "ring_formation_rows": acct["ring"]["formation_rows"],
                "outputs_bit_identical": bool(
                    np.array_equal(out_rep, out_ring)),
                "matches_formed": int((out_rep[0] < P).sum()),
                "step_ms_allgather_cold": round(t_rep * 1e3, 1),
                "step_ms_ring_cold": round(t_ring * 1e3, 1),
            })
            log(f"[comms] {family} D={D}: gather "
                f"{acct['allgather']['ici_recv_bytes']} B vs ring "
                f"{acct['ring']['ici_recv_bytes']} B, formation rows "
                f"{acct['allgather']['formation_rows']} vs "
                f"{acct['ring']['formation_rows']}, bit_identical="
                f"{rows[-1]['outputs_bit_identical']}")
    return rows


def bench_consume_ab(args) -> dict:
    """Consume-share A/B (ISSUE 12 acceptance): the SAME seeded offered
    load through two fresh single-queue apps — ``consume_batch`` on vs
    off — recording each run's consume+decode ingest work (seconds, and
    share of the settled span). The acceptance bar is the ON config's
    consume/decode work per request down ≥ 2× at fixed offered load;
    ``work_reduction_x`` is that ratio, measured, in the artifact."""
    import asyncio

    from matchmaking_tpu.config import (
        BatcherConfig,
        BrokerConfig,
        Config,
        EngineConfig,
        ObservabilityConfig,
        QueueConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.loadgen import offered_load

    async def one(consume_batch: bool) -> dict:
        cfg = Config(
            queues=(QueueConfig(rating_threshold=100.0,
                                send_queued_ack=False),),
            engine=EngineConfig(
                backend="tpu", pool_capacity=8192, pool_block=2048,
                batch_buckets=(16, 64, 256), top_k=8,
                pipeline_depth=min(args.depth, 2), warm_start=True),
            batcher=BatcherConfig(max_batch=256, max_wait_ms=3.0),
            broker=BrokerConfig(prefetch=8192,
                                consume_batch=consume_batch),
            observability=ObservabilityConfig(snapshot_interval_s=0.0),
        )
        app = MatchmakingApp(cfg)
        await app.start()
        try:
            res = await offered_load(
                app, cfg.broker.request_queue,
                rate=float(args.e2e_ab_rate),
                duration=float(args.e2e_ab_seconds), seed=7)
            attr = app.attribution.snapshot()["queues"].get(
                cfg.broker.request_queue, {})
            cats = attr.get("categories") or {}
            work_s = sum((cats.get(c) or {}).get("total_s") or 0.0
                         for c in ("consume", "decode"))
            sent = max(1, res.get("sent", 1))
            return {
                "consume_batch": consume_batch,
                "share": round(sum(
                    (cats.get(c) or {}).get("share") or 0.0
                    for c in ("consume", "decode")), 4),
                "work_s": round(work_s, 6),
                "work_us_per_req": round(work_s / sent * 1e6, 3),
                "sent": res.get("sent"),
                "matched": res.get("players_matched"),
            }
        finally:
            await app.stop()

    async def run() -> dict:
        on = await one(True)
        off = await one(False)
        ratio = (off["work_s"] / on["work_s"]) if on["work_s"] else None
        return {"e2e_consume_ab": {
            "on": on, "off": off,
            "work_reduction_x": round(ratio, 2) if ratio else None,
            "rate_req_s": float(args.e2e_ab_rate),
            "seconds": float(args.e2e_ab_seconds),
        }}

    return asyncio.run(run())


def bench_spec_ab(args) -> dict:
    """Speculative-formation A/B (ISSUE 16 acceptance; ``--spec-ab``): the
    SAME seeded offered load through two fresh single-queue apps —
    ``spec_formation`` on vs off — at a widening-driven operating point
    (threshold strict at admit, ``widen_per_sec`` grows feasibility while
    players sit resident, rescan interval deliberately coarse). In the
    OFF run a pool-resident pair that becomes feasible mid-gap waits for
    the next rescan tick; in the ON run the gap loop has already
    precomputed the pairing and the cut commits it in O(delta) — the
    turnaround (engine-observed wait-at-match) p50/p99 must fall at the
    SAME offered load and the SAME window wait. The row also records the
    speculation economics: ``spec_hit_rate`` (validated commits over all
    speculation outcomes) and ``spec_wasted_step_fraction`` (speculative
    device steps whose windows were discarded — the overlap price).
    scripts/bench_diff.py gates all four direction-aware; on a chip-less
    abort the keys are simply absent and the gate skips them."""
    import asyncio

    from matchmaking_tpu.config import (
        BatcherConfig,
        BrokerConfig,
        Config,
        EngineConfig,
        ObservabilityConfig,
        QueueConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.loadgen import offered_load

    async def one(spec: bool) -> dict:
        cfg = Config(
            queues=(QueueConfig(
                # Strict at admit, feasible while resident: the regime
                # where gap-cycle speculation has work to steal.
                rating_threshold=25.0, widen_per_sec=50.0,
                max_threshold=400.0,
                # Coarse rescan on BOTH sides — the A/B isolates the
                # speculative overlap, not a rescan-frequency change.
                rescan_interval_s=0.5,
                send_queued_ack=False),),
            engine=EngineConfig(
                backend="tpu", pool_capacity=4096, pool_block=1024,
                batch_buckets=(16, 64, 256), top_k=8,
                pipeline_depth=min(args.depth, 2), warm_start=True,
                spec_formation=spec, spec_max_steps=2,
                spec_interval_ms=10.0),
            batcher=BatcherConfig(max_batch=256, max_wait_ms=3.0),
            broker=BrokerConfig(prefetch=8192),
            observability=ObservabilityConfig(snapshot_interval_s=0.0),
        )
        app = MatchmakingApp(cfg)
        await app.start()
        rt = app.runtime(cfg.broker.request_queue)
        res = await offered_load(
            app, cfg.broker.request_queue, rate=float(args.spec_ab_rate),
            duration=float(args.spec_ab_seconds), seed=13,
            quality_stats=True, rating_sigma=200.0)
        sr = (rt.engine.spec_report()
              if hasattr(rt.engine, "spec_report") else None) or {}
        await app.stop()
        qs = res.get("quality", {})
        return {
            "spec_formation": spec,
            "sent": res.get("sent"),
            "matched": res.get("players_matched"),
            "turnaround_ms_p50": qs.get("waited_ms_p50"),
            "turnaround_ms_p99": qs.get("waited_ms_p99"),
            "spec_hit": sr.get("spec_hit"),
            "spec_miss": sr.get("spec_miss"),
            "spec_wasted": sr.get("spec_wasted"),
            "spec_hit_rate": sr.get("spec_hit_rate"),
            "spec_wasted_step_fraction": sr.get(
                "spec_wasted_step_fraction"),
        }

    async def run() -> dict:
        on = await one(True)
        off = await one(False)
        return {
            "e2e_spec_ab": {
                "on": on, "off": off,
                "rate_req_s": float(args.spec_ab_rate),
                "seconds": float(args.spec_ab_seconds),
            },
            # Top-level scalars so bench_diff compares them like any
            # other headline (absent when the phase aborts → skipped).
            "spec_turnaround_ms_p50": on["turnaround_ms_p50"],
            "spec_turnaround_ms_p99": on["turnaround_ms_p99"],
            "spec_hit_rate": on["spec_hit_rate"],
            "spec_wasted_step_fraction": on["spec_wasted_step_fraction"],
        }

    return asyncio.run(run())


def bench_pool_scale(args) -> list:
    """Sub-O(P) formation sweep (ISSUE 14): a hierarchical rating-bucketed
    engine at growing synthetic pool scales (default 100k/300k/1M), one
    row per scale with throughput + ``formation_touched_frac`` — the pool
    slots each window lane's formation actually scored over the flat
    step's O(P). Geometry per scale: capacity ≈ 4/3 × pool rounded to the
    block, one rating band per block, span budget ~33% of the blocks
    (``_bucketed_geometry``). The pool is seeded by building the device
    arrays straight from the columnar mirror (one vectorized pass + an
    exact index rebuild) — admitting a million players through O(P)
    device admits would measure the fill, not formation.
    """
    import jax
    import jax.numpy as jnp

    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.core.pool import PlayerPool
    from matchmaking_tpu.engine.interface import make_engine

    scales = [int(s) for s in args.pool_scale.split(",") if s]
    window = args.pool_scale_window
    rows = []
    for pool_target in scales:
        # ~64+ blocks per scale: bucket resolution is what the span math
        # converts into a small touched fraction.
        pool_block = min(args.pool_block,
                         max(1024, 1 << (pool_target // 96).bit_length()))
        capacity = ((pool_target * 4 // 3 + pool_block - 1)
                    // pool_block) * pool_block
        geo = _bucketed_geometry(capacity, pool_block, window)
        cfg = Config(
            queues=(QueueConfig(rating_threshold=100.0),),
            engine=EngineConfig(
                backend="tpu", pool_capacity=capacity,
                pool_block=pool_block,
                batch_buckets=(16, 64, 256, window), top_k=8,
                readback_group=1, **geo,
            ),
        )
        engine = make_engine(cfg, cfg.queues[0])
        rng = np.random.default_rng(14)
        log(f"[pool-scale {pool_target}] capacity={capacity} "
            f"blocks={capacity // pool_block} "
            f"span_blocks={geo['prune_window_blocks']}")
        # Vectorized fill: mirror first (banded slot placement), then the
        # device columns in ONE device_put + one exact index rebuild.
        t0 = engine._rel_base(0.0)
        filled = 0
        while filled < pool_target:
            chunk = min(pool_target - filled, 65_536)
            engine.pool.allocate_columns(
                make_columns(rng, chunk, filled, 0.0))
            filled += chunk
        occ = engine.pool.waiting_slots()
        arrays = PlayerPool.empty_device_arrays(capacity)
        arrays["rating"][occ] = engine.pool.m_rating[occ]
        arrays["rd"][occ] = engine.pool.m_rd[occ]
        arrays["region"][occ] = engine.pool.m_region[occ]
        arrays["mode"][occ] = engine.pool.m_mode[occ]
        arrays["threshold"][occ] = engine.pool.m_threshold[occ]
        arrays["enqueue_t"][occ] = (engine.pool.m_enqueued[occ]
                                    - t0).astype(np.float32)
        arrays["active"][occ] = True
        arrays.update(engine.kernels.init_index_arrays())
        engine._dev_pool = engine.kernels.index_rebuild(
            {k: jnp.asarray(v) for k, v in arrays.items()})
        jax.block_until_ready(engine._dev_pool)
        log(f"[pool-scale {pool_target}] pool seeded "
            f"({engine.pool_size()} waiting)")
        # Steady-occupancy stream: no refill (pool_target=0 disables it) —
        # a few windows drain only matched players, << pool.
        mps, lats, total = run_engine_pipelined(
            engine, rng, pool_target=0, window=window, warmup=2,
            measured=args.pool_scale_windows, depth=2,
            label=f"pool-scale {pool_target}",
            # Fresh id space: the fill consumed p0..p<pool>, and duplicate
            # ids would be dedup-dropped into empty windows.
            gen=lambda r, n, sid, now: make_columns(
                r, n, sid + pool_target, now))
        rep = engine.formation_report() or {}
        lat_ms = np.sort(np.asarray(lats)) * 1e3
        rows.append({
            "pool": pool_target,
            "capacity": capacity,
            "blocks": capacity // pool_block,
            "span_blocks": geo["prune_window_blocks"],
            "window": window,
            "matches_per_sec": round(mps, 1),
            "p99_ms": (float(np.percentile(lat_ms, 99))
                       if lat_ms.size else None),
            "total_matches": total,
            "formation_touched_frac": rep.get("formation_touched_frac"),
            "formation_windows": rep.get("windows"),
        })
        log(f"[pool-scale {pool_target}] {rows[-1]}")
    return rows


def bench_cpu_oracle(args) -> dict:
    """Reference-semantics oracle at the reference's ~2k-player scale."""
    from matchmaking_tpu.config import Config, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine

    cfg = Config(queues=(QueueConfig(rating_threshold=100.0),))
    engine = make_engine(cfg, cfg.queues[0])
    rng = np.random.default_rng(1)
    mps, lats, total = run_engine(
        engine, rng, pool_target=args.cpu_pool, window=64,
        warmup=2, measured=args.cpu_windows, label="cpu")
    lat_ms = np.sort(np.asarray(lats)) * 1e3
    return {
        "matches_per_sec": mps,
        "p99_ms": float(np.percentile(lat_ms, 99)),
        "total_matches": total,
        "pool": args.cpu_pool,
    }


def bench_placement_soak(args) -> dict:
    """Elastic placement soak (ISSUE 11): seeded load streams through a
    hot queue while the control plane executes a scripted
    migrate → promote(D=2) → demote(D=1) → migrate-back cycle through the
    SAME audited path policy decisions take (PlacementController.force).
    Measures the migration blackout (max/mean, from the decision ring)
    and proves delivery accounting across the moves: zero lost (every
    submitted player matched or still waiting at the end) and zero
    duplicated terminal responses.

    Runs on whatever backend is initialized; on a CPU box the caller
    forces a 4-virtual-device host mesh, so the promote leg exercises the
    real sharded kernel set.  scripts/bench_diff.py gates
    placement_blackout_ms_max / placement_lost / placement_dup
    direction-aware (lower is better)."""
    import asyncio

    async def run() -> dict:
        from matchmaking_tpu.config import (
            BatcherConfig,
            Config,
            EngineConfig,
            OverloadConfig,
            PlacementConfig,
            QueueConfig,
        )
        from matchmaking_tpu.service.app import MatchmakingApp
        from matchmaking_tpu.service.broker import Properties
        from matchmaking_tpu.testing.drain import settle

        import jax

        n_dev = min(4, len(jax.devices()))
        if n_dev < 2:
            # An explicit error row, not a vacuous clean run: with one
            # device every scripted move would be refused and the
            # lost/dup/blackout gates would pass while measuring nothing.
            return {"error": "placement_soak_needs_2_devices",
                    "placement_devices": n_dev}
        window = int(args.placement_window)
        cfg = Config(
            queues=(QueueConfig(name="soak.hot", rating_threshold=200.0,
                                send_queued_ack=False),
                    QueueConfig(name="soak.cold", rating_threshold=200.0,
                                send_queued_ack=False)),
            engine=EngineConfig(backend="tpu",
                                pool_capacity=max(4 * window, 1024),
                                pool_block=max(window, 256),
                                batch_buckets=(16, 64, window), top_k=8),
            batcher=BatcherConfig(max_batch=window, max_wait_ms=3.0),
            overload=OverloadConfig(max_inflight=8 * window),
            placement=PlacementConfig(interval_s=3600.0, devices=n_dev,
                                      max_shard=2, cooldown_s=0.0),
        )
        app = MatchmakingApp(cfg)
        await app.start()
        rt = app.runtime("soak.hot")
        ctrl = app.placement
        reply_q = "soak.replies"
        app.broker.declare_queue(reply_q)
        matched: dict[str, int] = {}

        async def on_reply(delivery) -> None:
            d = json.loads(delivery.body)
            if d.get("status") == "matched":
                pid = str(d.get("player_id", ""))
                matched[pid] = matched.get(pid, 0) + 1

        app.broker.basic_consume(reply_q, on_reply, prefetch=1_000_000)

        rng = np.random.default_rng(int(args.placement_seed))
        rate = float(args.placement_rate)
        duration = float(args.placement_seconds)
        gap = 1.0 / max(1.0, rate)
        submitted = 0
        #: The scripted placement cycle, at fractions of the soak span.
        schedule = ([(0.2, ("migrate", (1,))),
                     (0.4, ("promote", (1, 2))),
                     (0.6, ("demote", (1,))),
                     (0.8, ("migrate", (0,)))]
                    if n_dev >= 3 else [(0.25, ("migrate", (1,))),
                                        (0.65, ("migrate", (0,)))])
        t0 = time.time()
        next_action = 0
        while time.time() - t0 < duration:
            frac = (time.time() - t0) / duration
            if next_action < len(schedule) and frac >= schedule[next_action][0]:
                kind, devices = schedule[next_action][1]
                next_action += 1
                await ctrl.force(kind, "soak.hot", devices,
                                 reason=f"soak script {kind}")
            burst = max(1, int(rate * 0.01))
            ratings = rng.normal(1500.0, 120.0, burst)
            for r in ratings:
                app.broker.publish(
                    "soak.hot",
                    f'{{"id":"s{submitted}","rating":{r:.2f}}}'.encode(),
                    Properties(reply_to=reply_q,
                               correlation_id=f"s{submitted}"))
                submitted += 1
            await asyncio.sleep(max(gap * burst, 0.001))
        # The cycle always completes: legs the load loop did not reach
        # (blackouts + a loaded box eat wall time) run now, against the
        # still-waiting pool — the blackout/lost/dup accounting must
        # cover the whole scripted cycle on every box speed.
        while next_action < len(schedule):
            kind, devices = schedule[next_action][1]
            next_action += 1
            await ctrl.force(kind, "soak.hot", devices,
                             reason=f"soak script {kind} (post-load)")
        # Drain: let in-flight work land.
        await settle(app, rt, "soak.hot", reply_queues=(reply_q,))
        waiting = {r.id for r in rt.engine.waiting()}
        dup = sum(n - 1 for n in matched.values() if n > 1)
        lost = submitted - len(matched) - len(waiting)
        snap = ctrl.snapshot()
        blackouts = [d["blackout_ms"] for d in snap["decisions"]
                     if d["status"] == "applied"]
        failed = [d for d in snap["decisions"]
                  if d["status"] in ("failed", "refused")]
        out = {
            "placement_soak_requests": submitted,
            "placement_soak_matched": len(matched),
            "placement_soak_waiting": len(waiting),
            "placement_migrations": len(blackouts),
            "placement_failed_actions": len(failed),
            "placement_blackout_ms_max": (round(max(blackouts), 3)
                                          if blackouts else None),
            "placement_blackout_ms_mean": (
                round(sum(blackouts) / len(blackouts), 3)
                if blackouts else None),
            "placement_lost": lost,
            "placement_dup": dup,
            "placement_devices": n_dev,
            "placement_final_binding": snap["live"]["soak.hot"]["devices"],
            "placement_decisions": snap["decisions"],
        }
        await app.stop()
        return out

    return asyncio.run(run())


def bench_crash_soak(args) -> dict:
    """Crash-restart soak (ISSUE 15, ``--crash-soak``): seeded load through
    N kill/recover cycles — each cycle boots a fresh app on the SAME
    journal directory, recovers the predecessor's hard-crash state,
    absorbs an at-least-once redelivery storm of every previous request,
    runs fresh deterministic load (designed pairs that match + singles
    that wait), and hard-crashes (``MatchmakingApp.crash()``: no drain, no
    clean marker, uncommitted buffers dropped). One cycle is a
    DEVICE-LOST cycle: a scripted ``ChaosConfig.device_lost`` fault mid-
    load demotes the D=2 sharded queue to its surviving device (measured
    blackout in the failover audit) before that cycle's crash.

    Emits ``crash_lost`` (waiting players missing after recovery),
    ``crash_dup`` (players seeing two distinct matches across the whole
    soak), ``crash_rto_ms_max/mean``, journal write amplification, the
    steady-state journal append overhead (fsync=window vs durability off
    at the same offered load), and — run twice — whether the recovery
    transcripts are bit-identical across runs. scripts/bench_diff.py
    gates the crash_* metrics direction-aware (lower is better)."""
    import asyncio
    import shutil
    import tempfile

    from matchmaking_tpu.config import (
        BatcherConfig,
        ChaosConfig,
        Config,
        DurabilityConfig,
        EngineConfig,
        QueueConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.broker import Properties
    from matchmaking_tpu.testing.drain import settle

    q = "crash.soak"
    pairs = int(args.crash_pairs)
    singles = int(args.crash_singles)
    n_cycles = max(1, int(args.crash_cycles))
    dl_cycle = n_cycles - 1  # the last cycle loses a device mid-load

    def cfg_for(cycle: int | None, durable: bool = True,
                overhead: bool = False) -> Config:
        chaos = (ChaosConfig(seed=int(args.crash_seed), queues=(q,),
                             device_lost_steps=(1,))
                 if cycle == dl_cycle and cycle is not None
                 else ChaosConfig())
        return Config(
            queues=(QueueConfig(name=q, rating_threshold=50.0,
                                dedup_ttl_s=3600.0,
                                send_queued_ack=False),),
            engine=EngineConfig(backend="tpu", pool_capacity=4096,
                                pool_block=512, batch_buckets=(16, 64),
                                top_k=8, mesh_pool_axis=2,
                                # Pre-compile every bucket at app start:
                                # first-of-a-shape XLA compiles otherwise
                                # land inside whichever phase runs first
                                # (once mismeasured as ~95% "journal
                                # overhead") and inside the recovery span
                                # (the RTO must measure replay, not
                                # compilation).
                                warm_start=True),
            batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
            # The soak cycles compact aggressively ON PURPOSE (snapshot
            # rotation is part of what each cycle must survive); the
            # OVERHEAD phase keeps the production budget so it measures
            # the steady-state append cost, not compaction churn.
            durability=(DurabilityConfig(
                journal_dir=args._crash_jdir, fsync="window",
                compact_records=(DurabilityConfig.compact_records
                                 if overhead
                                 else int(args.crash_compact_records)),
                compact_interval_s=0.1) if durable
                else DurabilityConfig()),
            chaos=chaos,
        )

    def cycle_load(cycle: int) -> "list[tuple[str, float]]":
        """Deterministic designed load: pairs at adjacent ratings (they
        MUST match, whatever the window composition) + far-apart singles
        (they can never match anything, this cycle or later) — the
        matched/waiting SETS are pure functions of the script, which is
        what makes the recovery transcript two-run bit-identical."""
        rows: list[tuple[str, float]] = []
        for i in range(pairs):
            base = 1000.0 + i * 200.0
            rows.append((f"c{cycle}p{2 * i}", base))
            rows.append((f"c{cycle}p{2 * i + 1}", base + 1.0))
        for i in range(singles):
            rows.append((f"c{cycle}s{i}", 50_000.0 + cycle * 10_000.0
                         + i * 1_000.0))
        # The contract rejects |rating| >= 1e5 at the middleware — a
        # single pushed past it would be silently dropped instead of
        # waiting, corrupting the lost/dup accounting. Refuse loudly.
        worst = max(r for _, r in rows)
        if worst >= 1e5:
            raise ValueError(
                f"--crash-cycles/--crash-singles too large: cycle {cycle} "
                f"would publish rating {worst} >= the contract bound 1e5 "
                f"(singles climb 10k per cycle from 50k)")
        # Seeded publish-order shuffle: the soak is order-insensitive by
        # design, and the shuffle proves it stays that way.
        rng = np.random.default_rng(int(args.crash_seed) + cycle)
        rng.shuffle(rows)
        return rows

    async def one_run(run_idx: int) -> dict:
        jdir = tempfile.mkdtemp(prefix=f"mm_crash_soak_r{run_idx}_")
        args._crash_jdir = jdir
        lost = 0
        rtos: list[float] = []
        transcripts: list[dict] = []
        match_of: dict[str, set[str]] = {}
        pre_waiting: set[str] = set()
        prev_rows: list[tuple[str, float]] = []
        write_amp = None
        failovers = 0
        failover_blackout_ms = None
        try:
            for cycle in range(n_cycles):
                app = MatchmakingApp(cfg_for(cycle))
                await app.start()
                rt = app.runtime(q)
                # Recovery accounting vs the pre-crash truth.
                recovered = {r.id for r in rt.engine.waiting()}
                lost += len(pre_waiting - recovered)
                if cycle > 0:
                    rto = app.metrics.gauges.get(f"crash_rto_ms[{q}]")
                    if rto is not None:
                        rtos.append(float(rto))
                    if rt.last_recovery is not None:
                        transcripts.append(
                            rt.last_recovery["transcript"])
                reply_q = f"crash.replies.{cycle}"
                app.broker.declare_queue(reply_q)

                async def on_reply(delivery) -> None:
                    d = json.loads(delivery.body)
                    if d.get("status") == "matched":
                        pid = str(d.get("player_id", ""))
                        mid = (d.get("match") or {}).get("match_id")
                        if pid and mid:
                            match_of.setdefault(pid, set()).add(mid)

                app.broker.basic_consume(reply_q, on_reply,
                                         prefetch=1_000_000)
                # At-least-once redelivery storm: EVERY previous-cycle
                # request again. Matched players must replay their cached
                # match (same id → no dup); waiting singles re-enter as
                # duplicate-enqueue no-ops.
                for pid, rating in prev_rows:
                    app.broker.publish(
                        q, f'{{"id":"{pid}","rating":{rating}}}'.encode(),
                        Properties(reply_to=reply_q, correlation_id=pid))
                # Fresh seeded load, paced so the batcher cuts several
                # windows (the device-lost cycle needs step index 1 to
                # exist mid-load, not after it).
                rows = cycle_load(cycle)
                gap = 1.0 / max(1.0, float(args.crash_rate))
                for k, (pid, rating) in enumerate(rows):
                    app.broker.publish(
                        q, f'{{"id":"{pid}","rating":{rating}}}'.encode(),
                        Properties(reply_to=reply_q, correlation_id=pid))
                    if k % 4 == 3:
                        await asyncio.sleep(gap * 4)
                ok = await settle(app, rt, q, matched_at_least=2 * pairs,
                                  reply_queues=(reply_q,))
                if not ok:
                    log(f"[crash-soak r{run_idx} c{cycle}] WARNING: "
                        f"quiesce timed out")
                if cycle == dl_cycle:
                    failovers += int(
                        app.metrics.counters.get("device_failovers"))
                    if rt.failover_log:
                        failover_blackout_ms = (
                            rt.failover_log[-1]["blackout_ms"])
                if rt.journal is not None and rt.journal.payload_bytes:
                    write_amp = round(rt.journal.bytes_written
                                      / rt.journal.payload_bytes, 3)
                pre_waiting = {r.id for r in rt.engine.waiting()}
                prev_rows = rows
                log(f"[crash-soak r{run_idx} c{cycle}] matched="
                    f"{app.metrics.counters.get('players_matched')} "
                    f"waiting={len(pre_waiting)} "
                    f"replays="
                    f"{app.metrics.counters.get('deduped_replays')}")
                await app.crash()
            # Final recovery check: one more boot proves the LAST crash
            # recovers too, then stops cleanly.
            app = MatchmakingApp(cfg_for(None))
            await app.start()
            rt = app.runtime(q)
            recovered = {r.id for r in rt.engine.waiting()}
            lost += len(pre_waiting - recovered)
            rto = app.metrics.gauges.get(f"crash_rto_ms[{q}]")
            if rto is not None:
                rtos.append(float(rto))
            if rt.last_recovery is not None:
                transcripts.append(rt.last_recovery["transcript"])
            await app.stop()
        finally:
            if not args.crash_keep_dirs:
                shutil.rmtree(jdir, ignore_errors=True)
        dup = sum(1 for ids in match_of.values() if len(ids) > 1)
        return {
            "lost": lost,
            "dup": dup,
            "rtos": rtos,
            "transcripts": transcripts,
            "matched_players": len(match_of),
            "write_amplification": write_amp,
            "failovers": failovers,
            "failover_blackout_ms": failover_blackout_ms,
        }

    async def rate_phase(durable: bool) -> "tuple[float, bool]":
        """Steady-state append-overhead measurement: the same designed
        paired load through a durability-on (fsync=window) vs -off app;
        the ratio of matched-players rates is the overhead. Returns
        ``(rate, drained)`` — a quiesce that times out folds up to 30 s
        of idle polling into the measured span, so the caller must treat
        the rate (and the overhead fraction built from it) as garbage
        rather than gate on it."""
        n = int(args.crash_overhead_pairs)
        args._crash_jdir = tempfile.mkdtemp(prefix="mm_crash_ovh_")
        try:
            app = MatchmakingApp(cfg_for(None, durable=durable,
                                         overhead=True))
            await app.start()
            rt = app.runtime(q)
            # Warm EVERY batch bucket outside the measured span: the
            # first cut of each window SHAPE pays its XLA compile, and
            # the compile cache is process-wide — the 2-player warmup
            # alone left the 64-bucket compile inside whichever phase ran
            # FIRST, which once mismeasured as ~95% "journal overhead".
            # A full-burst publish of max_batch pairs cuts one max-size
            # window and the remainder buckets; pairs at far-apart bases
            # all match and leave the pool before t0.
            # Base 80k: far from the measured load (≤ ~4.6k) but INSIDE
            # the contract's rating bound (|r| < 1e5 — a 100k base was
            # silently rejected_by_middleware wholesale, which unwarmed
            # the phase and left the compiles in the measured span).
            warm_pairs = 64
            for i in range(warm_pairs):
                base = 80_000.0 + i * 200.0
                for jj, r in enumerate((base, base + 1.0)):
                    app.broker.publish(
                        q,
                        f'{{"id":"w{2 * i + jj}","rating":{r}}}'.encode(),
                        Properties(reply_to="", correlation_id=""))
            warm_ok = await settle(app, rt, q,
                                   matched_at_least=2 * warm_pairs)
            matched0 = app.metrics.counters.get("players_matched")
            t0 = time.perf_counter()
            # Burst-published: the broker drains full bursts, so windows
            # fill to max_batch and the journal pays its one buffered
            # append + one fsync PER WINDOW — the steady-state shape.
            for i in range(n):
                base = 1000.0 + (i % 512) * 7.0
                for j, r in enumerate((base, base + 1.0)):
                    app.broker.publish(
                        q,
                        f'{{"id":"o{2 * i + j}","rating":{r}}}'.encode(),
                        Properties(reply_to="", correlation_id=""))
            ok = await settle(app, rt, q, matched_at_least=matched0 + 2 * n)
            span = time.perf_counter() - t0
            matched = app.metrics.counters.get("players_matched") - matched0
            await app.stop()
            if not (warm_ok and ok):
                log(f"[crash-soak overhead durable={durable}] WARNING: "
                    f"quiesce timed out (warm={warm_ok}, measured={ok}) — "
                    f"the span includes idle drain polling, overhead "
                    f"fraction withheld")
            return (matched / span if span > 0 else 0.0, warm_ok and ok)
        finally:
            shutil.rmtree(args._crash_jdir, ignore_errors=True)

    runs = [asyncio.run(one_run(i))
            for i in range(max(1, int(args.crash_runs)))]
    rate_on, on_ok = asyncio.run(rate_phase(True))
    rate_off, off_ok = asyncio.run(rate_phase(False))
    # A timed-out quiesce poisons the rate it measured: report the rates
    # (flagged in the log) but withhold the gated overhead fraction —
    # bench_diff skips None rather than flagging a phantom regression.
    overhead = (max(0.0, 1.0 - rate_on / rate_off)
                if rate_off > 0 and on_ok and off_ok else None)
    first = runs[0]
    identical = None
    if len(runs) >= 2:
        identical = all(
            json.dumps(r["transcripts"], sort_keys=True)
            == json.dumps(first["transcripts"], sort_keys=True)
            for r in runs[1:])
    rtos = [x for r in runs for x in r["rtos"]]
    return {
        "crash_cycles": n_cycles,
        "crash_runs": len(runs),
        "crash_lost": sum(r["lost"] for r in runs),
        "crash_dup": sum(r["dup"] for r in runs),
        "crash_rto_ms_max": round(max(rtos), 3) if rtos else None,
        "crash_rto_ms_mean": (round(sum(rtos) / len(rtos), 3)
                              if rtos else None),
        "crash_recoveries": len(rtos),
        "crash_matched_players": first["matched_players"],
        "crash_transcript_identical": identical,
        "crash_device_failovers": sum(r["failovers"] for r in runs),
        "crash_failover_blackout_ms": first["failover_blackout_ms"],
        "journal_write_amplification": first["write_amplification"],
        "crash_e2e_rate_on": round(rate_on, 1),
        "crash_e2e_rate_off": round(rate_off, 1),
        "crash_journal_overhead_frac": (round(overhead, 4)
                                        if overhead is not None else None),
    }


def bench_failover_soak(args) -> dict:
    """Failover soak (ISSUE 17, ``--failover-soak``): seeded load through
    N primary-kill/standby-takeover cycles on the CPU harness. Each cycle
    boots the current owner's app on a FRESH journal dir (a new "host"),
    attaches a warm standby over the in-process replication link, runs
    deterministic designed load (pairs that match + singles that wait)
    with the standby pumping, hard-kills the primary
    (``MatchmakingApp.crash()``), expires the lease on the authority's
    scriptable clock, promotes the standby (epoch bump = fencing), and
    boots the successor, which adopts the standby's shadow
    (``recover_from_replica`` — the measured ``failover_rto_ms``).

    Chaos: cycle 0 runs a scripted drop/dup/delay vocabulary on the
    stream's first seqs (retransmission must heal them — zero loss); the
    LAST cycle partitions the link at a quiesced seq boundary and
    publishes late singles behind the cut, so the kill lands with real
    replication lag — the lost players must stay ``<=`` the
    ``unacked_admit_players()`` bound measured at kill time, and the cut
    at a quiesced boundary keeps the lost SET framing-independent (the
    two-run transcript gate needs that).

    Emits ``failover_lost`` / ``failover_dup`` / ``failover_rto_ms`` /
    ``replication_lag_ms_p99`` (gated by scripts/bench_diff.py, lower is
    better; lost/dup under the zero-baseline rule) plus the lost bound,
    recovery count, and the two-run transcript identity pin.

    ``--transport`` (ISSUE 20): ``socket-loopback`` runs THIS script
    unchanged over real UDS sockets + a remote lease client (nemesis
    off) — the in-proc ≡ socket equivalence pin: the emitted
    ``failover_transcript_digest`` must be bit-identical to an inproc
    run on the same seed. ``socket`` dispatches to the cross-process
    soak (:func:`bench_failover_soak_proc`)."""
    import asyncio
    import hashlib
    import shutil
    import tempfile

    from matchmaking_tpu.config import (
        BatcherConfig,
        ChaosConfig,
        Config,
        DurabilityConfig,
        EngineConfig,
        QueueConfig,
        ReplicationConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.broker import Properties
    from matchmaking_tpu.service.replication import ReplicationHub
    from matchmaking_tpu.testing.drain import settle

    transport = getattr(args, "transport", "inproc")
    if transport == "socket":
        return bench_failover_soak_proc(args)

    q = "failover.soak"
    pairs = int(args.failover_pairs)
    singles = int(args.failover_singles)
    late_singles = int(args.failover_late_singles)
    n_cycles = max(1, int(args.failover_cycles))
    lag_cycle = n_cycles - 1  # the last kill lands with replication lag
    lease_s = float(args.failover_lease_s)
    loopback = transport == "socket-loopback"
    if loopback:
        # Real renewals ride the remote client's budgeted validity:
        # floor the lease so an XLA warm-up stall on the CPU harness
        # can't lapse it mid-boot. Transcripts are recovered-state
        # functions — lease duration never enters them, so the
        # equivalence pin against inproc (default lease) still holds.
        lease_s = max(lease_s, 2.0)

    def make_hub(chaos):
        if loopback:
            from matchmaking_tpu.net.link import SocketReplicationHub

            return SocketReplicationHub(lease_s=lease_s, chaos=chaos,
                                        seed=int(args.failover_seed))
        return ReplicationHub(lease_s=lease_s, chaos=chaos,
                              seed=int(args.failover_seed))

    def cfg_for(jdir: str, owner: str) -> Config:
        return Config(
            queues=(QueueConfig(name=q, rating_threshold=50.0,
                                dedup_ttl_s=3600.0,
                                send_queued_ack=False),),
            engine=EngineConfig(backend="tpu", pool_capacity=4096,
                                pool_block=512, batch_buckets=(16, 64),
                                top_k=8,
                                # warm_start: XLA compiles must land
                                # before the load, not inside the
                                # measured failover RTO.
                                warm_start=True),
            batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
            durability=DurabilityConfig(journal_dir=jdir, fsync="window"),
            replication=ReplicationConfig(role="primary", owner=owner),
        )

    def cycle_load(cycle: int) -> "list[tuple[str, float]]":
        """Designed load (the crash-soak recipe): adjacent-rating pairs
        MUST match whatever the window framing; far singles never can —
        the matched/waiting SETS are pure functions of the script."""
        rows: list[tuple[str, float]] = []
        for i in range(pairs):
            base = 1000.0 + i * 200.0
            rows.append((f"f{cycle}p{2 * i}", base))
            rows.append((f"f{cycle}p{2 * i + 1}", base + 1.0))
        for i in range(singles):
            rows.append((f"f{cycle}s{i}", 50_000.0 + cycle * 10_000.0
                         + i * 1_000.0))
        worst = max(r for _, r in rows)
        if worst >= 1e5:
            raise ValueError(
                f"--failover-cycles/--failover-singles too large: cycle "
                f"{cycle} would publish rating {worst} >= the contract "
                f"bound 1e5")
        rng = np.random.default_rng(int(args.failover_seed) + cycle)
        rng.shuffle(rows)
        return rows

    async def one_run(run_idx: int) -> dict:
        base_dir = tempfile.mkdtemp(prefix=f"mm_failover_r{run_idx}_")
        # One replication fabric per run: the lease authority, the
        # per-queue link, and the takeover handoff all survive the app
        # boots (they model the parts of the deployment that OUTLIVE a
        # host). Cycle 0's scripted drop/dup/delay seqs exercise the
        # at-least-once retransmission path; they must heal to zero loss.
        chaos = ChaosConfig(seed=int(args.failover_seed), queues=(q,),
                            repl_drop_seqs=(1,), repl_dup_seqs=(2,),
                            repl_delay_seqs=((3, 2),))
        # The repl_* script above is the IN-PROC link's vocabulary; the
        # loopback socket link ignores it (its faults are net_*, off
        # here) — the in-proc faults heal to zero effect by the quiesce
        # boundaries, which is exactly why the transcripts stay
        # bit-identical across transports.
        hub = make_hub(chaos)
        lost = 0
        lost_bound = 0
        over_bound = 0
        rtos: list[float] = []
        lag_p99s: list[float] = []
        transcripts: list[dict] = []
        match_of: dict[str, set[str]] = {}
        pre_waiting: set[str] = set()
        kill_bound = 0
        prev_rows: list[tuple[str, float]] = []
        owner = "host0"
        try:
            for cycle in range(n_cycles):
                if hasattr(hub, "cycle_reset"):
                    # Socket fabric: retire the previous host
                    # generation's link + standby listener so the fresh
                    # journal's restarted seqs aren't shadowed by the
                    # old cumulative ack watermark.
                    hub.cycle_reset(q)
                app = MatchmakingApp(
                    cfg_for(f"{base_dir}/host{cycle}", owner),
                    replication_hub=hub)
                await app.start()
                rt = app.runtime(q)
                recovered = {r.id for r in rt.engine.waiting()}
                cycle_lost = len(pre_waiting - recovered)
                lost += cycle_lost
                lost_bound += kill_bound
                over_bound += max(0, cycle_lost - kill_bound)
                if cycle_lost > kill_bound:
                    log(f"[failover-soak r{run_idx} c{cycle}] GATE: lost "
                        f"{cycle_lost} players but the unacked-tail bound "
                        f"at kill time was {kill_bound}")
                if cycle > 0:
                    rto = app.metrics.gauges.get(f"failover_rto_ms[{q}]")
                    if rto is not None:
                        rtos.append(float(rto))
                    if rt.last_recovery is not None:
                        transcripts.append(rt.last_recovery["transcript"])
                reply_q = f"failover.replies.{cycle}"
                app.broker.declare_queue(reply_q)

                async def on_reply(delivery) -> None:
                    d = json.loads(delivery.body)
                    if d.get("status") == "matched":
                        pid = str(d.get("player_id", ""))
                        mid = (d.get("match") or {}).get("match_id")
                        if pid and mid:
                            match_of.setdefault(pid, set()).add(mid)

                app.broker.basic_consume(reply_q, on_reply,
                                         prefetch=1_000_000)
                # The NEXT host's warm standby attaches before the load:
                # it receives the baseline plus every streamed record.
                standby = hub.standby(q, owner=f"host{cycle + 1}")
                # At-least-once redelivery storm of every previous-cycle
                # request: matched players must replay their cached match
                # (the dedup cache crossed hosts via the stream).
                for pid, rating in prev_rows:
                    app.broker.publish(
                        q, f'{{"id":"{pid}","rating":{rating}}}'.encode(),
                        Properties(reply_to=reply_q, correlation_id=pid))
                rows = cycle_load(cycle)
                gap = 1.0 / max(1.0, float(args.failover_rate))
                for k, (pid, rating) in enumerate(rows):
                    app.broker.publish(
                        q, f'{{"id":"{pid}","rating":{rating}}}'.encode(),
                        Properties(reply_to=reply_q, correlation_id=pid))
                    if k % 4 == 3:
                        await asyncio.sleep(gap * 4)
                ok = await settle(app, rt, q, matched_at_least=2 * pairs,
                                  reply_queues=(reply_q,), standby=standby)
                if not ok:
                    log(f"[failover-soak r{run_idx} c{cycle}] WARNING: "
                        f"quiesce timed out")
                if cycle == lag_cycle and late_singles > 0:
                    # Kill under lag: cut the link at the quiesced seq
                    # boundary (acked == sent here, so the held tail is
                    # exactly the late load — framing-independent), then
                    # publish singles the standby will never see.
                    repl = rt.replication
                    hub.link(q).partition(repl.sent_seq + 1)
                    for i in range(late_singles):
                        pid = f"f{cycle}L{i}"
                        app.broker.publish(
                            q,
                            f'{{"id":"{pid}","rating":{90_000.0 + i * 1_000.0}}}'
                            .encode(),
                            Properties(reply_to=reply_q,
                                       correlation_id=pid))
                    ok = await settle(app, rt, q, matched_at_least=2 * pairs,
                                      reply_queues=(reply_q,),
                                      standby=standby, replication=False)
                    if not ok:
                        log(f"[failover-soak r{run_idx} c{cycle}] "
                            f"WARNING: lag-cycle quiesce timed out")
                repl = rt.replication
                kill_bound = repl.unacked_admit_players()
                lat = app.metrics.latency.get(
                    f"replication_ack_lag[{q}]")
                if lat is not None and len(lat):
                    lag_p99s.append(lat.percentile(99) * 1e3)
                pre_waiting = {r.id for r in rt.engine.waiting()}
                prev_rows = rows
                log(f"[failover-soak r{run_idx} c{cycle}] matched="
                    f"{app.metrics.counters.get('players_matched')} "
                    f"waiting={len(pre_waiting)} lag={repl.lag()} "
                    f"bound={kill_bound} epoch={repl.epoch}")
                await app.crash()
                # Takeover after lease expiry on the authority's
                # scriptable clock (time is a caller-passed monotonic
                # value by design — no wall-clock sleep needed).
                standby.takeover(time.monotonic() + lease_s + 0.05)
                owner = standby.owner
            # Final successor: the last takeover must adopt too, then
            # stop cleanly (CLEAN record + lease release).
            if hasattr(hub, "cycle_reset"):
                hub.cycle_reset(q)
            app = MatchmakingApp(
                cfg_for(f"{base_dir}/host{n_cycles}", owner),
                replication_hub=hub)
            await app.start()
            rt = app.runtime(q)
            recovered = {r.id for r in rt.engine.waiting()}
            cycle_lost = len(pre_waiting - recovered)
            lost += cycle_lost
            lost_bound += kill_bound
            over_bound += max(0, cycle_lost - kill_bound)
            if cycle_lost > kill_bound:
                log(f"[failover-soak r{run_idx} final] GATE: lost "
                    f"{cycle_lost} players but the unacked-tail bound "
                    f"at kill time was {kill_bound}")
            rto = app.metrics.gauges.get(f"failover_rto_ms[{q}]")
            if rto is not None:
                rtos.append(float(rto))
            if rt.last_recovery is not None:
                transcripts.append(rt.last_recovery["transcript"])
            await app.stop()
        finally:
            if hasattr(hub, "close"):
                hub.close()
            if not args.failover_keep_dirs:
                shutil.rmtree(base_dir, ignore_errors=True)
        dup = sum(1 for ids in match_of.values() if len(ids) > 1)
        return {
            "lost": lost,
            "lost_bound": lost_bound,
            "over_bound": over_bound,
            "dup": dup,
            "rtos": rtos,
            "lag_p99s": lag_p99s,
            "transcripts": transcripts,
            "matched_players": len(match_of),
        }

    runs = [asyncio.run(one_run(i))
            for i in range(max(1, int(args.failover_runs)))]
    first = runs[0]
    identical = None
    if len(runs) >= 2:
        identical = all(
            json.dumps(r["transcripts"], sort_keys=True)
            == json.dumps(first["transcripts"], sort_keys=True)
            for r in runs[1:])
    rtos = [x for r in runs for x in r["rtos"]]
    lags = [x for r in runs for x in r["lag_p99s"]]
    digest = hashlib.sha256(
        json.dumps(first["transcripts"], sort_keys=True).encode()
    ).hexdigest()
    return {
        "failover_transport": transport,
        # The equivalence pin: an inproc run and a socket-loopback run
        # on the same seed must emit the SAME digest (check.sh compares).
        "failover_transcript_digest": digest,
        "failover_cycles": n_cycles,
        "failover_runs": len(runs),
        "failover_lost": sum(r["lost"] for r in runs),
        "failover_lost_bound": sum(r["lost_bound"] for r in runs),
        "failover_lost_over_bound": sum(r["over_bound"] for r in runs),
        "failover_dup": sum(r["dup"] for r in runs),
        "failover_rto_ms": round(max(rtos), 3) if rtos else None,
        "failover_rto_ms_mean": (round(sum(rtos) / len(rtos), 3)
                                 if rtos else None),
        "failover_recoveries": len(rtos),
        "failover_matched_players": first["matched_players"],
        "failover_transcript_identical": identical,
        "replication_lag_ms_p99": (round(max(lags), 3) if lags else None),
    }


def bench_failover_soak_proc(args) -> dict:
    """CROSS-PROCESS failover soak (ISSUE 20, ``--failover-soak
    --transport=socket``): the PR 17 invariants gated over real process
    and socket boundaries. The driver spawns a lease-service subprocess
    (the part of the deployment that outlives every host) and a chain of
    host subprocesses (``net/failover_proc.py``); each host attaches as
    the warm standby of the current primary over a UDS replication
    stream + remote lease RPCs, then the driver SIGKILLs the primary
    mid-tenure and the standby takes over after REAL lease expiry.

    Nemesis schedule: cycle 0's primary runs a scripted net fault script
    (drop + dup + delay + one MID-STREAM CONNECTION RESET on the fwd
    flow — the link must reconnect and converge by retransmission); the
    middle cycles are clean (any ``liveness_lost`` there is a heartbeat
    FALSE POSITIVE — zero-gated); the LAST cycle arms an ASYMMETRIC
    partition before the kill (the primary keeps streaming but goes deaf
    to acks and lease responses), so the driver can prove the primary
    SELF-FENCES within the lease budget — both seams probed refused —
    while the standby still catches up on the working direction.

    Gates (all emitted, zero-baseline in scripts/bench_diff.py): zero
    double matches across the merged per-host reply logs, losses <= the
    unacked-tail bound at each kill, fenced probes refused at both
    seams, >= 1 link reconnect after the scripted reset, zero heartbeat
    false positives in clean tenures, and two seeded runs bit-identical
    by takeover-transcript digest."""
    import hashlib
    import os
    import queue as queue_mod
    import shutil
    import subprocess
    import tempfile
    import threading

    q = "failover.soak"
    pairs = int(args.failover_pairs)
    singles = int(args.failover_singles)
    late_singles = int(args.failover_late_singles)
    n_cycles = max(2, int(args.failover_cycles))
    seed = int(args.failover_seed)
    rate = float(args.failover_rate)
    # Real clocks across processes: floor the lease above the worst XLA
    # warm-up stall so a compiling primary can't lapse it spuriously.
    lease_s = max(float(args.failover_lease_s), 2.0)

    def cycle_load(cycle: int) -> "list[list[Any]]":
        rows: "list[list[Any]]" = []
        for i in range(pairs):
            base = 1000.0 + i * 200.0
            rows.append([f"f{cycle}p{2 * i}", base])
            rows.append([f"f{cycle}p{2 * i + 1}", base + 1.0])
        for i in range(singles):
            rows.append([f"f{cycle}s{i}", 50_000.0 + cycle * 10_000.0
                         + i * 1_000.0])
        rng = np.random.default_rng(seed + cycle)
        rng.shuffle(rows)
        return rows

    class Child:
        """One subprocess + its JSON-line protocol (stdin commands,
        stdout events; a reader thread feeds a local queue)."""

        def __init__(self, name: str, argv: "list[str]"):
            self.name = name
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "matchmaking_tpu.net.failover_proc",
                 *argv],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            self.events: "queue_mod.Queue" = queue_mod.Queue()
            self._rid = 0
            threading.Thread(target=self._read, daemon=True).start()

        def _read(self) -> None:
            assert self.proc.stdout is not None
            for line in self.proc.stdout:
                line = line.strip()
                if line:
                    try:
                        self.events.put(json.loads(line))
                    except ValueError:
                        pass
            self.events.put(None)

        def _next(self, deadline: float) -> "dict | None":
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"{self.name}: protocol timeout")
            try:
                return self.events.get(timeout=remaining)
            except queue_mod.Empty:
                raise TimeoutError(f"{self.name}: protocol timeout")

        def wait_ev(self, ev: str, timeout: float = 120.0) -> dict:
            deadline = time.monotonic() + timeout
            while True:
                got = self._next(deadline)
                if got is None:
                    raise RuntimeError(
                        f"{self.name}: exited before {ev!r}")
                if got.get("ev") == ev:
                    return got

        def rpc(self, cmd: str, timeout: float = 120.0, **kw) -> dict:
            self._rid += 1
            assert self.proc.stdin is not None
            self.proc.stdin.write(
                json.dumps({"cmd": cmd, "id": self._rid, **kw}) + "\n")
            self.proc.stdin.flush()
            deadline = time.monotonic() + timeout
            while True:
                got = self._next(deadline)
                if got is None:
                    raise RuntimeError(f"{self.name}: died during {cmd!r}")
                if got.get("id") != self._rid:
                    continue
                if got.get("ev") == "error":
                    raise RuntimeError(
                        f"{self.name}: {cmd} failed: {got.get('error')}")
                return got

        def kill(self) -> None:
            self.proc.kill()  # SIGKILL — the crash under test

        def reap(self) -> None:
            try:
                self.proc.kill()
            except OSError:
                pass
            try:
                self.proc.wait(timeout=10)
            except Exception:
                pass

    def merge_match_of(into: "dict[str, set]", rep: dict) -> None:
        for pid, mids in (rep.get("match_of") or {}).items():
            into.setdefault(pid, set()).update(mids)

    def one_run(run_idx: int) -> dict:
        base = tempfile.mkdtemp(prefix=f"mm_netfo_r{run_idx}_")
        lease_addr = f"unix:{base}/lease.sock"
        fwd = f"repl:{q}:fwd"
        # Cycle 0's nemesis script: first-tx drop/dup/delay on early
        # record seqs plus a MID-STREAM reset — heals by reconnect +
        # retransmission, gated by link_reconnects >= 1 and zero loss.
        c0_chaos = json.dumps({
            "seed": seed, "queues": [q],
            "net_drop_frames": [[fwd, 2]], "net_dup_frames": [[fwd, 3]],
            "net_delay_frames": [[fwd, 4, 2]],
            "net_reset_frames": [[fwd, 6]]})

        def spawn_host(idx: int, chaos: "str | None" = None) -> Child:
            argv = ["host", "--name", f"host{idx}", "--queue", q,
                    "--lease-addr", lease_addr, "--lease-s", str(lease_s),
                    "--seed", str(seed)]
            if chaos:
                argv += ["--chaos", chaos]
            c = Child(f"host{idx}", argv)
            c.wait_ev("ready", timeout=180.0)
            return c

        children: "list[Child]" = []
        lease = Child("lease", ["lease", "--lease-addr", lease_addr,
                                "--lease-s", str(lease_s)])
        res = {"lost": 0, "lost_bound": 0, "over_bound": 0,
               "reconnects": 0, "hb_false_positives": 0,
               "fenced_probe_failures": 0, "rtos": [], "transcripts": []}
        match_of: "dict[str, set]" = {}
        try:
            lease.wait_ev("ready", timeout=180.0)
            primary = spawn_host(0, chaos=c0_chaos)
            children.append(primary)
            standby = spawn_host(1)
            children.append(standby)
            standby.rpc("standby", listen=f"unix:{base}/repl1.sock")
            primary.rpc("serve", target=f"unix:{base}/repl1.sock",
                        jdir=f"{base}/host0", timeout=300.0)
            prev_rows: "list[list[Any]]" = []
            for cycle in range(n_cycles):
                if prev_rows:
                    # At-least-once redelivery storm: matched players
                    # must replay their cached match on the NEW host.
                    primary.rpc("publish", rows=prev_rows, rate=rate)
                rows = cycle_load(cycle)
                primary.rpc("publish", rows=rows, rate=rate)
                qq = primary.rpc("quiesce", matched_at_least=2 * pairs,
                                 replication=True, timeout_s=60.0,
                                 timeout=90.0)
                if not qq.get("ok"):
                    log(f"[netfo r{run_idx} c{cycle}] WARNING: quiesce "
                        f"timed out")
                asym = cycle == n_cycles - 1 and late_singles > 0
                if asym:
                    # Asymmetric partition: the primary keeps streaming
                    # but goes DEAF to acks and lease responses.
                    primary.rpc("deafen", pattern=f"repl:{q}:ack")
                    primary.rpc("deafen", pattern="lease:")
                    late_rows = [[f"f{cycle}L{i}", 90_000.0 + i * 1_000.0]
                                 for i in range(late_singles)]
                    primary.rpc("publish", rows=late_rows, rate=rate)
                    primary.rpc("quiesce", matched_at_least=2 * pairs,
                                replication=False, timeout_s=30.0,
                                timeout=60.0)
                    # The fwd direction still works: the standby must
                    # catch up even while the primary sees no acks.
                    prep = primary.rpc("report")
                    deadline = time.monotonic() + 30.0
                    while True:
                        srep = standby.rpc("report")
                        if (srep.get("applied_seq", 0)
                                >= prep.get("sent_seq", 0)):
                            break
                        if time.monotonic() > deadline:
                            log(f"[netfo r{run_idx}] WARNING: standby "
                                f"never caught up under asym partition")
                            break
                        time.sleep(0.05)
                    # Fencing-over-RTT: with renewals unconfirmable the
                    # primary must fence ITSELF within the lease budget
                    # — both seams probed, refusal required.
                    probe = primary.rpc("probe",
                                        timeout_s=4 * lease_s + 10.0,
                                        timeout=4 * lease_s + 30.0)
                    if not (probe.get("publish_refused")
                            and probe.get("append_fenced")
                            and not probe.get("publish_leaked")):
                        res["fenced_probe_failures"] += 1
                        log(f"[netfo r{run_idx}] GATE: fenced probe "
                            f"leaked: {probe}")
                rep = primary.rpc("report")
                kill_bound = int(rep.get("kill_bound", 0))
                pre_waiting = set(rep.get("waiting", ()))
                merge_match_of(match_of, rep)
                link = rep.get("link", {})
                res["reconnects"] += int(link.get("reconnects", 0))
                if cycle != 0 and not asym:
                    # Clean tenure: any liveness_lost is a heartbeat
                    # FALSE POSITIVE (zero-gated).
                    srep = standby.rpc("report")
                    res["hb_false_positives"] += (
                        int(link.get("liveness_lost", 0))
                        + int(srep.get("standby_link", {})
                              .get("liveness_lost", 0)))
                log(f"[netfo r{run_idx} c{cycle}] matched="
                    f"{rep.get('matched')} waiting={len(pre_waiting)} "
                    f"bound={kill_bound} epoch={rep.get('epoch')} "
                    f"reconnects={link.get('reconnects', 0)}")
                primary.kill()
                to = standby.rpc("takeover", timeout_s=4 * lease_s + 30.0,
                                 timeout=4 * lease_s + 60.0)
                if cycle < n_cycles - 1:
                    nxt = spawn_host(cycle + 2)
                    children.append(nxt)
                    nxt.rpc("standby",
                            listen=f"unix:{base}/repl{cycle + 2}.sock")
                    target = f"unix:{base}/repl{cycle + 2}.sock"
                else:
                    # Last successor streams to a dead-end address (no
                    # listener will ever bind it) and must still stop
                    # cleanly: a missing standby degrades, never wedges.
                    target = f"unix:{base}/deadend.sock"
                sv = standby.rpc("serve", target=target,
                                 jdir=f"{base}/host{cycle + 1}",
                                 timeout=300.0)
                recovered = set(sv.get("recovered", ()))
                cycle_lost = len(pre_waiting - recovered)
                res["lost"] += cycle_lost
                res["lost_bound"] += kill_bound
                res["over_bound"] += max(0, cycle_lost - kill_bound)
                if cycle_lost > kill_bound:
                    log(f"[netfo r{run_idx} c{cycle}] GATE: lost "
                        f"{cycle_lost} > unacked-tail bound {kill_bound}")
                if sv.get("rto_ms") is not None:
                    res["rtos"].append(float(sv["rto_ms"]))
                if sv.get("transcript") is not None:
                    res["transcripts"].append(sv["transcript"])
                log(f"[netfo r{run_idx} c{cycle}] takeover epoch="
                    f"{to.get('epoch')} lost={cycle_lost} "
                    f"rto_ms={sv.get('rto_ms')}")
                primary, standby = standby, None
                if cycle < n_cycles - 1:
                    standby = children[-1]
                prev_rows = rows
            frep = primary.rpc("report")
            merge_match_of(match_of, frep)
            primary.rpc("stop", timeout=120.0)
        finally:
            for c in children:
                c.reap()
            lease.reap()
            if not args.failover_keep_dirs:
                shutil.rmtree(base, ignore_errors=True)
        res["dup"] = sum(1 for ids in match_of.values() if len(ids) > 1)
        res["matched_players"] = len(match_of)
        res["digest"] = hashlib.sha256(
            json.dumps(res["transcripts"], sort_keys=True).encode()
        ).hexdigest()
        return res

    runs = [one_run(i) for i in range(max(1, int(args.failover_runs)))]
    first = runs[0]
    identical = None
    if len(runs) >= 2:
        identical = all(r["digest"] == first["digest"] for r in runs[1:])
    rtos = [x for r in runs for x in r["rtos"]]
    return {
        "failover_transport": "socket",
        "socket_failover_cycles": n_cycles,
        "socket_failover_runs": len(runs),
        "socket_failover_lost": sum(r["lost"] for r in runs),
        "socket_failover_lost_bound": sum(r["lost_bound"] for r in runs),
        "socket_failover_lost_over_bound": sum(
            r["over_bound"] for r in runs),
        "socket_failover_dup": sum(r["dup"] for r in runs),
        "socket_failover_rto_ms": round(max(rtos), 3) if rtos else None,
        "socket_failover_rto_ms_mean": (round(sum(rtos) / len(rtos), 3)
                                        if rtos else None),
        "socket_failover_recoveries": len(rtos),
        "socket_failover_matched_players": first["matched_players"],
        "socket_link_reconnects": sum(r["reconnects"] for r in runs),
        "heartbeat_false_positive_count": sum(
            r["hb_false_positives"] for r in runs),
        "socket_fenced_probe_failures": sum(
            r["fenced_probe_failures"] for r in runs),
        "socket_failover_transcript_identical": identical,
        "failover_transcript_digest": first["digest"],
    }


def bench_incident_soak(args) -> dict:
    """Incident-forensics soak (ISSUE 18, ``--incident-soak``): a seeded
    flash crowd + scripted lease-expiry failover + hard crash, with the
    black-box recorder armed — every trigger class the script exercises
    (slo_burn, slo_burn_clear, failover, crash_recovery) must auto-capture
    at least one bundle, the event spine's deterministic transcript must
    be bit-identical across two runs (the bar every other soak meets),
    capture p99 must stay <= 50 ms with ZERO rate-limiter drops, and
    ``scripts/postmortem.py`` must reconstruct the takeover root chain
    (lease expiry → epoch bump → replay window → takeover → burn →
    burn clear) OFFLINE from the persisted bundle alone.

    Script per run, three app boots on one replication fabric:
    host0 (primary + warm standby) takes a paced flash crowd against a
    deliberately unmeetable SLO target — the burn fires mid-burst and
    clears when the crowd drains; host0 is hard-killed and the standby
    promoted at scripted lease expiry; host1 adopts the shadow (failover
    bundle), takes a second crowd (its OWN burn/clear — the bundle whose
    spine holds the whole takeover chain), then hard-crashes; host2
    reboots on host1's journal (crash_recovery bundle) and stops clean."""
    import asyncio
    import hashlib
    import importlib.util
    import shutil
    import tempfile

    from matchmaking_tpu.config import (
        BatcherConfig,
        Config,
        DurabilityConfig,
        EngineConfig,
        ForensicsConfig,
        ObservabilityConfig,
        QueueConfig,
        ReplicationConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.broker import Properties
    from matchmaking_tpu.service.replication import ReplicationHub
    from matchmaking_tpu.testing.drain import settle

    q = "incident.soak"
    pairs = int(args.incident_pairs)
    singles = int(args.incident_singles)
    lease_s = float(args.incident_lease_s)
    rate = max(1.0, float(args.incident_rate))
    #: The classes this script exercises — the >= 1-bundle-each gate.
    exercised = ("slo_burn", "slo_burn_clear", "failover", "crash_recovery")
    expected_chain = ["lease_expired", "epoch_bump", "replay_window",
                      "failover_takeover", "slo_burn", "slo_burn_clear"]

    def cfg_for(jdir: str, inc_dir: str, owner: "str | None") -> Config:
        return Config(
            queues=(QueueConfig(name=q, rating_threshold=50.0,
                                dedup_ttl_s=3600.0,
                                send_queued_ack=False),),
            engine=EngineConfig(backend="tpu", pool_capacity=4096,
                                pool_block=512, batch_buckets=(16, 64),
                                top_k=8, warm_start=True),
            # max_wait 5 ms >> the 1 ms SLO target below: EVERY settled
            # pair misses, so the flash crowd burns deterministically-in-
            # outcome (the burn EVENTS stay out of the transcript — only
            # their occurrence is gated, not their timing).
            batcher=BatcherConfig(max_batch=64, max_wait_ms=5.0),
            durability=DurabilityConfig(journal_dir=jdir, fsync="window"),
            observability=ObservabilityConfig(
                slo_target_ms=1.0, slo_objective=0.99,
                slo_fast_window_s=0.4, slo_slow_window_s=0.9,
                snapshot_interval_s=0.1, slow_trace_ms=1.0),
            forensics=ForensicsConfig(incident_dir=inc_dir,
                                      min_interval_s=0.25),
            replication=(ReplicationConfig(role="primary", owner=owner)
                         if owner else ReplicationConfig()),
        )

    def burst(host: int) -> "list[tuple[str, float]]":
        """The crash/failover-soak designed-load recipe: adjacent pairs
        MUST match whatever the framing; far singles never can."""
        rows: list[tuple[str, float]] = []
        for i in range(pairs):
            base = 1000.0 + i * 200.0
            rows.append((f"i{host}p{2 * i}", base))
            rows.append((f"i{host}p{2 * i + 1}", base + 1.0))
        for i in range(singles):
            rows.append((f"i{host}s{i}", 50_000.0 + host * 10_000.0
                         + i * 1_000.0))
        rng = np.random.default_rng(int(args.incident_seed) + host)
        rng.shuffle(rows)
        return rows

    async def publish_paced(app, reply_q: str, rows) -> None:
        for pid, rating in rows:
            app.broker.publish(
                q, f'{{"id":"{pid}","rating":{rating}}}'.encode(),
                Properties(reply_to=reply_q, correlation_id=pid))
            await asyncio.sleep(1.0 / rate)

    async def wait_capture(app, cls: str, timeout_s: float,
                           standby=None) -> bool:
        """Poll until the recorder has >= 1 bundle of ``cls`` (the
        telemetry loop keeps sampling / the burn monitors keep
        evaluating in the background)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if app.incidents.by_class.get(cls, 0) > 0:
                return True
            if standby is not None:
                standby.pump()
            await asyncio.sleep(0.05)
        return False

    def app_stats(app) -> "tuple[float | None, int]":
        lat = app.metrics.latency.get("incident_capture")
        p99 = (lat.percentile(99) * 1e3
               if lat is not None and len(lat) else None)
        return p99, app.incidents.dropped

    async def one_run(run_idx: int) -> dict:
        base_dir = tempfile.mkdtemp(prefix=f"mm_incident_r{run_idx}_")
        hub = ReplicationHub(lease_s=lease_s,
                             seed=int(args.incident_seed))
        by_class: dict[str, int] = {}
        transcripts: list[list] = []
        p99s: list[float] = []
        dropped = 0
        missed: list[str] = []
        clear_bundle_path = ""
        try:
            # -- host0: flash crowd -> burn -> clear -> hard kill -------
            app = MatchmakingApp(
                cfg_for(f"{base_dir}/host0", f"{base_dir}/inc0", "host0"),
                replication_hub=hub)
            await app.start()
            rt = app.runtime(q)
            reply_q = "incident.replies.0"
            app.broker.declare_queue(reply_q)
            app.broker.basic_consume(reply_q, lambda d: None,
                                     prefetch=1_000_000)
            standby = hub.standby(q, owner="host1")
            await publish_paced(app, reply_q, burst(0))
            if not await settle(app, rt, q, matched_at_least=2 * pairs,
                                standby=standby):
                log(f"[incident-soak r{run_idx} h0] WARNING: quiesce "
                    f"timed out")
            for cls, t in (("slo_burn", 3.0), ("slo_burn_clear", 5.0)):
                if not await wait_capture(app, cls, t, standby=standby):
                    missed.append(f"host0:{cls}")
            for k, v in app.incidents.by_class.items():
                by_class[k] = by_class.get(k, 0) + v
            p99, d = app_stats(app)
            if p99 is not None:
                p99s.append(p99)
            dropped += d
            transcripts.append(app.spine.transcript())
            await app.crash()
            standby.takeover(time.monotonic() + lease_s + 0.05)

            # -- host1: adoption (failover bundle) + its own burn/clear -
            app = MatchmakingApp(
                cfg_for(f"{base_dir}/host1", f"{base_dir}/inc1",
                        standby.owner),
                replication_hub=hub)
            await app.start()
            rt = app.runtime(q)
            reply_q = "incident.replies.1"
            app.broker.declare_queue(reply_q)
            app.broker.basic_consume(reply_q, lambda d: None,
                                     prefetch=1_000_000)
            if not await wait_capture(app, "failover", 2.0):
                missed.append("host1:failover")
            await publish_paced(app, reply_q, burst(1))
            # host1 is the terminal primary — no standby drains its
            # stream, so the replication-quiescence clause can't hold.
            if not await settle(app, rt, q, matched_at_least=2 * pairs,
                                replication=False):
                log(f"[incident-soak r{run_idx} h1] WARNING: quiesce "
                    f"timed out")
            for cls, t in (("slo_burn", 3.0), ("slo_burn_clear", 5.0)):
                if not await wait_capture(app, cls, t):
                    missed.append(f"host1:{cls}")
            for k, v in app.incidents.by_class.items():
                by_class[k] = by_class.get(k, 0) + v
            p99, d = app_stats(app)
            if p99 is not None:
                p99s.append(p99)
            dropped += d
            transcripts.append(app.spine.transcript())
            # The persisted burn-clear bundle is the postmortem artifact:
            # its spine window holds the whole takeover chain.
            for f in sorted(os.listdir(f"{base_dir}/inc1")):
                if f.endswith("_slo_burn_clear.json"):
                    clear_bundle_path = os.path.join(f"{base_dir}/inc1", f)
            await app.crash()

            # -- host2: reboot on host1's journal (crash_recovery) ------
            app = MatchmakingApp(
                cfg_for(f"{base_dir}/host1", f"{base_dir}/inc2", None))
            await app.start()
            if not await wait_capture(app, "crash_recovery", 2.0):
                missed.append("host2:crash_recovery")
            for k, v in app.incidents.by_class.items():
                by_class[k] = by_class.get(k, 0) + v
            p99, d = app_stats(app)
            if p99 is not None:
                p99s.append(p99)
            dropped += d
            transcripts.append(app.spine.transcript())
            await app.stop()

            # -- offline postmortem on the persisted bundle -------------
            analysis = None
            if clear_bundle_path:
                spec = importlib.util.spec_from_file_location(
                    "mm_postmortem",
                    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "scripts", "postmortem.py"))
                pm = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(pm)
                with open(clear_bundle_path, encoding="utf-8") as f:
                    bundle = json.load(f)
                analysis = pm.analyze(bundle)
        finally:
            if not args.incident_keep_dirs:
                shutil.rmtree(base_dir, ignore_errors=True)
        blob = json.dumps(transcripts, sort_keys=True,
                          separators=(",", ":")).encode("utf-8")
        log(f"[incident-soak r{run_idx}] by_class={by_class} "
            f"dropped={dropped} capture_p99="
            f"{round(max(p99s), 3) if p99s else None} missed={missed}")
        return {
            "by_class": by_class,
            "dropped": dropped,
            "p99s": p99s,
            "missed": missed,
            "transcripts": transcripts,
            "digest": hashlib.sha256(blob).hexdigest(),
            "analysis": analysis,
        }

    runs = [asyncio.run(one_run(i))
            for i in range(max(1, int(args.incident_runs)))]
    first = runs[0]
    identical = None
    if len(runs) >= 2:
        identical = all(r["digest"] == first["digest"] for r in runs[1:])
    p99s = [x for r in runs for x in r["p99s"]]
    analysis = first["analysis"]
    chain = (analysis or {}).get("root_chain_kinds") or []
    return {
        "incident_runs": len(runs),
        "incident_captured": sum(sum(r["by_class"].values()) for r in runs),
        "incident_by_class": first["by_class"],
        "incident_classes_missed": [m for r in runs for m in r["missed"]],
        "incident_classes_ok": all(
            all(r["by_class"].get(cls, 0) >= 1 for cls in exercised)
            for r in runs),
        "incident_dropped": sum(r["dropped"] for r in runs),
        "incident_capture_ms_p99": (round(max(p99s), 3) if p99s else None),
        "incident_transcript_identical": identical,
        "incident_spine_digest": first["digest"],
        "incident_bundle_valid": (analysis is not None
                                  and not analysis["problems"]),
        "incident_root_chain": chain,
        "incident_root_chain_ok": chain == expected_chain,
    }


async def _scenario_cell(args, scn) -> dict:
    """One matrix cell: a fresh single-queue app driven by one scenario's
    seeded population load, with the autotuner closing the loop (unless
    ``--scenario-no-autotune``). The cell artifact is the full
    observability story — telemetry-ring trajectory, attribution shares,
    per-tier SLO attainment, quality, shed/expired, autotuner audit —
    not just a throughput number."""
    from matchmaking_tpu.config import (
        AutotuneConfig,
        BatcherConfig,
        BrokerConfig,
        ChaosConfig,
        Config,
        EngineConfig,
        ObservabilityConfig,
        OverloadConfig,
        QueueConfig,
    )
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.loadgen import offered_load

    q = "matchmaking.search"
    tiers = scn.max_tier + 1 if scn.tiered else 1
    has_deadlines = any(c.deadline_ms > 0 for c in scn.cohorts)
    chaos = scn.chaos_config(q, seed=args.scenario_seed)
    slo_ms = float(args.scenario_slo_ms)
    cfg = Config(
        queues=(QueueConfig(rating_threshold=100.0,
                            send_queued_ack=False),),
        engine=EngineConfig(
            backend="tpu", pool_capacity=8192, pool_block=2048,
            batch_buckets=(16, 64, 256), top_k=8, pipeline_depth=2,
            warm_start=True),
        # The cell's STATIC base config is deliberately mid-range (window
        # wait included) — the point of the matrix is watching the tuner
        # move it per workload, and diffing the converged knobs.
        batcher=BatcherConfig(max_batch=256,
                              max_wait_ms=float(args.scenario_wait_ms)),
        broker=BrokerConfig(prefetch=8192),
        overload=OverloadConfig(
            max_waiting=int(args.scenario_max_waiting),
            tiers=tiers,
            deadline_sweep_ms=(25.0 if has_deadlines else 0.0)),
        chaos=chaos if chaos is not None else ChaosConfig(),
        observability=ObservabilityConfig(
            slo_target_ms=slo_ms, slo_objective=0.99,
            slo_fast_window_s=1.0, slo_slow_window_s=4.0,
            snapshot_interval_s=0.25),
        autotune=(AutotuneConfig() if args.scenario_no_autotune
                  else AutotuneConfig(interval_s=0.25,
                                      max_wait_ms_min=1.0)),
    )
    app = MatchmakingApp(cfg)
    try:
        await app.start()
        res = await offered_load(
            app, q, rate=0.0, duration=0.0, seed=args.scenario_seed,
            scenario=scn, rate_scale=float(args.scenario_rate_scale),
            time_scale=float(args.scenario_time_scale))
        app.sample_telemetry()  # final trajectory point before teardown
        attr_q = app.attribution.snapshot()["queues"].get(q, {})
        cats = attr_q.get("categories") or {}
        hist = app.metrics.stages.get(q, {}).get("total")
        cell: dict = {
            "scenario": scn.name,
            "seed": args.scenario_seed,
            "rate_scale": float(args.scenario_rate_scale),
            "time_scale": float(args.scenario_time_scale),
            "duration_s": res.get("duration_s"),
            "scenario_digest": res.get("scenario_digest"),
            "offered": res["sent"],
            "sent_req_s": res["sent_req_s"],
            "matched": res["players_matched"],
            "matched_per_s": res["matched_per_s"],
            "queued_acks": res["queued_acks"],
            "shed": res["shed_requests"],
            "expired": res["expired_requests"],
            "retries_sent": res.get("retries_sent", 0),
            "cohorts": res.get("cohorts"),
            "slo_target_ms": slo_ms,
            "slo_attainment": attr_q.get("slo_attainment"),
            "admitted_p99_ms": (round(hist.percentile(99) * 1e3, 3)
                                if hist is not None and hist.count
                                else None),
            "attribution": {
                name: {"kind": cat.get("kind"), "share": cat.get("share")}
                for name, cat in cats.items()
            },
        }
        if tiers > 1:
            per_tier = {}
            for t in range(tiers):
                good, total = app.attribution.slo_counts_tier(q, t)
                per_tier[str(t)] = {
                    "slo_good": good, "slo_total": total,
                    "attainment": (round(good / total, 4) if total
                                   else None),
                    "shed": int(app.metrics.counters.get(
                        f"shed_requests_t{t}")),
                    "expired": int(app.metrics.counters.get(
                        f"expired_requests_t{t}")),
                }
            cell["tiers"] = per_tier
        qentry = (app.quality.snapshot(q).get("queues") or {}).get(q)
        if qentry:
            tier_rows = qentry.get("tiers") or {}
            n_matched = qentry.get("matched_players") or 0
            # Matched-player-weighted aggregate over the tier rows (the
            # service ledger conditions on tier; the cell headline wants
            # the population view).
            q_sum = sum(r.get("quality_sum") or 0.0
                        for r in tier_rows.values())
            p10s = [r.get("quality_p10") for r in tier_rows.values()
                    if r.get("quality_p10") is not None]
            w99s = [r.get("wait_p99_s") for r in tier_rows.values()
                    if r.get("wait_p99_s") is not None]
            cell["quality"] = {
                "matched": n_matched,
                "quality_mean": (round(q_sum / n_matched, 6)
                                 if n_matched else None),
                "quality_p10": (round(min(p10s), 6) if p10s else None),
                "wait_p99_s": (round(max(w99s), 6) if w99s else None),
            }
        rt = app.runtime(q)
        if hasattr(rt.engine, "util_report"):
            u = rt.engine.util_report()
            cell["idle_fraction"] = u["idle_fraction"]
            cell["effective_occupancy"] = u["effective_occupancy"]
        cell["telemetry"] = app.telemetry.snapshot(
            limit=int(args.scenario_trajectory),
            prefixes=("idle_frac", "slo_good", "slo_total", "pool_size",
                      "stage_total_p99_ms", "batch_fill", "shed_total",
                      "expired_total"))
        if app.autotune is not None:
            cell["autotune"] = {
                "moves": app.autotune.moves,
                "failures": app.autotune.failures,
                "ticks": app.autotune.ticks,
                "knobs": app.autotune.knobs(),
                "trace": [list(row)
                          for row in app.autotune.decision_trace()],
            }
            if args.scenario_tuned_dir:
                os.makedirs(args.scenario_tuned_dir, exist_ok=True)
                path = os.path.join(args.scenario_tuned_dir,
                                    f"{scn.name}.json")
                with open(path, "w") as f:
                    json.dump(app.autotune.tuned_config(
                        scenario=scn.name, seed=args.scenario_seed),
                        f, indent=1, sort_keys=True)
                    f.write("\n")
                cell["tuned_config"] = path
        return cell
    finally:
        await app.stop()


def bench_modelcheck(args) -> dict:
    """Small-scope interleaving model check (ISSUE 19, ``--modelcheck``):
    bounded EXHAUSTIVE enumeration of action interleavings x fault
    injections over the real lease/replication/journal objects
    (analysis/modelcheck.py), no jax backend needed. Emits
    ``modelcheck_states_explored`` / ``modelcheck_violations`` /
    ``modelcheck_exhaustive`` (gated by scripts/bench_diff.py:
    violations under the zero-baseline rule) plus the minimized,
    digest-replayable counterexample when one exists."""
    from matchmaking_tpu.analysis.modelcheck import (
        ModelCheckConfig, run_modelcheck)

    cfg = ModelCheckConfig(
        queues=args.modelcheck_queues,
        depth=args.modelcheck_depth,
        admits=args.modelcheck_admits,
        settles=args.modelcheck_settles,
        faults=tuple(f for f in args.modelcheck_faults.split(",") if f),
        fault_budget=args.modelcheck_fault_budget,
        deadline_s=args.modelcheck_deadline_s or None,
    )
    return run_modelcheck(cfg)


def bench_modelcheck_mutations(args) -> dict:
    """Mutation gate for the model checker (ISSUE 19,
    ``--modelcheck-mutations``): break each fenced seam one at a time
    (skip the append fence, ack past the horizon, apply a gapped seq,
    publish from a stale epoch) and require every mutant to yield a
    minimized counterexample that replays bit-identically under its
    schedule digest — the checker's own falsifiability proof. Emits
    ``mutation_gate_passed`` (check.sh fails the build on False)."""
    from matchmaking_tpu.analysis.modelcheck import run_mutation_gate

    return run_mutation_gate()


def bench_scenario_matrix(args) -> dict:
    """The scenario observatory (ISSUE 13): run every requested scenario
    as one matrix cell — fresh app, seeded population load, autotuner
    closing the loop — and emit one artifact per cell. A cell that fails
    fails the run; bench_diff gates the cells (slo_attainment /
    admitted_p99_ms / quality, direction-aware, matched by scenario
    name)."""
    import asyncio

    from matchmaking_tpu.scenario import load_scenario, scenario_names

    spec = args.scenario_matrix
    names = (scenario_names() if spec == "all"
             else [n.strip() for n in spec.split(",") if n.strip()])
    cells: list[dict] = []
    for name in names:
        log(f"[scenario] cell {name}")
        cell = asyncio.run(_scenario_cell(args, load_scenario(name)))
        log(f"[scenario {name}] attainment="
            f"{cell.get('slo_attainment')} shed={cell.get('shed')} "
            f"admitted_p99={cell.get('admitted_p99_ms')} ms "
            f"autotune_moves="
            f"{(cell.get('autotune') or {}).get('moves')}")
        cells.append(cell)
    attainments = [c["slo_attainment"] for c in cells
                   if c.get("slo_attainment") is not None]
    return {
        "metric": (f"scenario-matrix worst-cell SLO attainment "
                   f"({len(cells)} cells)"),
        "value": (round(min(attainments), 4) if attainments else None),
        "unit": "attainment",
        "vs_baseline": None,
        "scenario_seed": args.scenario_seed,
        "scenario_matrix": cells,
    }


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pool", type=int, default=100_000,
                   help="sustained concurrent pool size (headline: 100k)")
    p.add_argument("--capacity", type=int, default=131_072)
    p.add_argument("--pool-block", type=int, default=8192)
    p.add_argument("--window", type=int, default=4096,
                   help="requests per timed search window")
    p.add_argument("--windows", type=int, default=50,
                   help="measured windows")
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--repeats", type=int, default=3,
                   help="repeat the measured phase; report the median run")
    p.add_argument("--profile-dir", default="",
                   help="write a jax.profiler trace of the measured phase "
                        "(view with tensorboard/xprof)")
    p.add_argument("--depth", type=int, default=4,
                   help="max in-flight windows. MUST be >= readback-group "
                        "for groups to fill before the depth gate blocks")
    p.add_argument("--readback-group", type=int, default=4,
                   help="stack k windows' results on device and transfer "
                        "them as ONE D2H (one transfer per k windows)")
    p.add_argument("--cpu-pool", type=int, default=2000,
                   help="CPU-oracle pool size (the reference's ~cap)")
    p.add_argument("--cpu-windows", type=int, default=20)
    p.add_argument("--skip-cpu", action="store_true")
    p.add_argument("--bucketed", action="store_true",
                   help="hierarchical rating-bucketed formation (ISSUE "
                        "14) for the engine phase: band-per-block "
                        "allocation + index-driven span formation "
                        "(bit-exact vs flat); the result row records "
                        "formation_touched_frac")
    p.add_argument("--pool-scale", default="",
                   help="sub-O(P) formation sweep (ISSUE 14): comma list "
                        "of synthetic pool sizes (e.g. "
                        "100000,300000,1000000) — one bucketed engine "
                        "per scale, rows under pool_scale with "
                        "matches_per_sec + formation_touched_frac "
                        "(bench_diff gates both per pool size)")
    p.add_argument("--pool-scale-window", type=int, default=512,
                   help="request window for the pool-scale sweep")
    p.add_argument("--pool-scale-windows", type=int, default=8,
                   help="measured windows per pool-scale cell")
    p.add_argument("--skip-roofline", action="store_true",
                   help="skip the chained device-step roofline phase")
    p.add_argument("--skip-e2e", action="store_true",
                   help="skip the service-level end-to-end latency phase")
    p.add_argument("--e2e-rate", type=float, default=6000.0,
                   help="Poisson arrival rate (req/s) for the e2e phase")
    p.add_argument("--e2e-seconds", type=float, default=6.0,
                   help="e2e phase duration")
    p.add_argument("--e2e-rates", default="12000,24000,48000,80000",
                   help="comma-separated offered rates for the saturation "
                        "sweep (finds the single-process knee); empty "
                        "string skips the sweep")
    p.add_argument("--e2e-max-waiting", type=int, default=0,
                   help="overload mode: bound the e2e phase's waiting pool "
                        "(OverloadConfig.max_waiting) so the saturation "
                        "sweep measures admitted-request latency under "
                        "explicit shedding (0 = unbounded, the default)")
    p.add_argument("--e2e-tier-mix", default="",
                   help="tiered QoS mode: per-class offered mix, e.g. "
                        "'0:0.2,1:0.5,2:0.3' — stamps seeded x-tier "
                        "headers, enables EDF cutting + lowest-tier-first "
                        "shedding, and emits per-tier p99/shed/expired "
                        "rows (e2e_tiers) in the BENCH json ('' = off)")
    p.add_argument("--e2e-quality", action="store_true",
                   help="quality/latency frontier phase (ISSUE 8): sweep "
                        "rating_threshold across fresh apps and record "
                        "per-point quality p10/p50/mean, wait-at-match "
                        "p99, and rating-bucket disparity as e2e_frontier "
                        "rows — the baseline any future match-objective "
                        "kernel must beat")
    p.add_argument("--e2e-quality-thresholds", default="25,50,100,200,400",
                   help="comma-separated rating_threshold sweep for the "
                        "frontier phase")
    p.add_argument("--e2e-quality-rate", type=float, default=600.0,
                   help="offered req/s per frontier point (kept modest: "
                        "the frontier is a shape measurement)")
    p.add_argument("--e2e-quality-seconds", type=float, default=3.0,
                   help="measured duration per frontier point")
    p.add_argument("--e2e-quality-widen", type=float, default=0.0,
                   help="widen_per_sec applied at every frontier point "
                        "(0 = pure threshold sweep)")
    p.add_argument("--e2e-quality-sigma", type=float, default=150.0,
                   help="iid rating stddev for frontier arrivals (diverse "
                        "ratings, NOT the loadgen's paired default — the "
                        "threshold must bite for quality/wait to trade)")
    p.add_argument("--e2e-ab-seconds", type=float, default=0.0,
                   help="consume-share A/B phase (ISSUE 12): run the same "
                        "seeded load through consume_batch=on and =off "
                        "apps for this many seconds each and record the "
                        "measured consume+decode work reduction "
                        "(e2e_consume_ab). 0 = skip (two extra app boots "
                        "+ warmups)")
    p.add_argument("--e2e-ab-rate", type=float, default=4000.0,
                   help="offered req/s for the consume-share A/B phase")
    p.add_argument("--e2e-quality-spec", action="store_true",
                   help="add the speculation axis to the --e2e-quality "
                        "frontier (ISSUE 16): rerun every threshold point "
                        "with spec_formation on (e2e_frontier_spec rows) "
                        "and gate the per-rating-bucket quality disparity "
                        "no worse than the spec-off point "
                        "(e2e_frontier_spec_disparity_ok)")
    p.add_argument("--spec-ab", action="store_true",
                   help="speculative-formation A/B phase (ISSUE 16): the "
                        "same seeded widening-driven load through "
                        "spec_formation=on and =off apps, recording "
                        "turnaround p50/p99, spec hit rate, and the "
                        "wasted-step fraction (e2e_spec_ab + top-level "
                        "spec_* columns gated by scripts/bench_diff.py)")
    p.add_argument("--spec-ab-rate", type=float, default=600.0,
                   help="offered req/s for the spec A/B phase (low on "
                        "purpose: idle window gaps are the regime the "
                        "speculative overlap exists to fill)")
    p.add_argument("--spec-ab-seconds", type=float, default=4.0,
                   help="duration of each spec A/B leg")
    p.add_argument("--e2e-sweep-seconds", type=float, default=4.0,
                   help="duration of each saturation-sweep step")
    p.add_argument("--e2e-slo-ms", type=float, default=250.0,
                   help="e2e SLO target (ms): a request is GOOD when served "
                        "within this end to end; the BENCH json records "
                        "attainment + burn trajectories "
                        "(ObservabilityConfig.slo_target_ms)")
    p.add_argument("--skip-multiproc", action="store_true",
                   help="skip the multi-process ingress phase")
    p.add_argument("--mp-rate", type=float, default=80000.0,
                   help="offered req/s per self-driving multiproc worker "
                        "(above the ~77k/s single-process ceiling so the "
                        "phase measures saturation, not the offered rate)")
    p.add_argument("--mp-seconds", type=float, default=4.0)
    p.add_argument("--mp-deadline-ms", type=float, default=0.0,
                   help="stamp x-deadline on every multiproc loadgen "
                        "request (overload mode; 0 = off)")
    p.add_argument("--latency", action="store_true",
                   help="latency mode: small window, depth 1, grouping "
                        "off — reports the measured p50/p99, then exits. "
                        "The p99 < 50 ms north star is a LATENCY claim; "
                        "the default mode optimizes throughput")
    p.add_argument("--latency-window", type=int, default=512)
    p.add_argument("--comms", action="store_true",
                   help="comms-accounting mode: build the sharded team/"
                        "role kernel sets at D=2/4/8, print one JSON row "
                        "per (family, D) with per-device per-step ICI "
                        "bytes + formation rows for the allgather vs ring "
                        "paths and an executed bit-exactness check, then "
                        "exit. Needs >= D devices: "
                        "on CPU set "
                        "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    p.add_argument("--comms-capacity", type=int, default=65_536)
    p.add_argument("--comms-frontier-k", type=int, default=1024)
    p.add_argument("--placement-soak", action="store_true",
                   help="elastic placement soak (ISSUE 11): seeded load "
                        "through a hot queue while a scripted migrate → "
                        "promote(D=2) → demote → migrate-back cycle runs "
                        "through the audited controller path; emits "
                        "placement_blackout_ms_* / placement_lost / "
                        "placement_dup (bench_diff gates them, lower is "
                        "better). Standalone mode: skips every other "
                        "phase; on a CPU box a 4-virtual-device host "
                        "mesh is forced so the promote leg is real")
    p.add_argument("--placement-rate", type=float, default=2000.0,
                   help="soak offered load (req/s)")
    p.add_argument("--placement-seconds", type=float, default=4.0)
    p.add_argument("--placement-window", type=int, default=256,
                   help="soak batcher window / top batch bucket")
    p.add_argument("--placement-seed", type=int, default=17)
    p.add_argument("--crash-soak", action="store_true",
                   help="crash-restart soak (ISSUE 15): seeded load "
                        "through N kill/recover cycles (in-process hard "
                        "crash: no drain, no clean journal marker, "
                        "uncommitted buffers dropped) incl. one "
                        "device-lost D=2→1 demotion cycle; emits "
                        "crash_lost / crash_dup / crash_rto_ms_* / "
                        "journal write amplification / steady-state "
                        "append overhead (bench_diff gates them, lower "
                        "is better). Standalone mode: skips every other "
                        "phase; forces a host mesh so the sharded leg is "
                        "real on a CPU box")
    p.add_argument("--crash-cycles", type=int, default=3,
                   help="kill/recover cycles per run (last one is the "
                        "device-lost cycle)")
    p.add_argument("--crash-runs", type=int, default=2,
                   help="full soak repetitions; >= 2 additionally pins "
                        "the recovery transcripts bit-identical across "
                        "runs")
    p.add_argument("--crash-pairs", type=int, default=6,
                   help="matching pairs per cycle (deterministic designed "
                        "load)")
    p.add_argument("--crash-singles", type=int, default=3,
                   help="never-matching singles per cycle (the waiting "
                        "pool recovery must carry)")
    p.add_argument("--crash-rate", type=float, default=800.0,
                   help="publish pacing for the cycle load (req/s)")
    p.add_argument("--crash-seed", type=int, default=23)
    p.add_argument("--crash-compact-records", type=int, default=64,
                   help="live-segment record budget before compaction — "
                        "small by default so the soak exercises snapshot "
                        "rotation every cycle")
    p.add_argument("--crash-overhead-pairs", type=int, default=600,
                   help="pairs for the steady-state append-overhead "
                        "phase (fsync=window vs durability off)")
    p.add_argument("--crash-keep-dirs", action="store_true",
                   help="keep the per-run journal directories for "
                        "inspection")
    p.add_argument("--failover-soak", action="store_true",
                   help="hot-standby failover soak (ISSUE 17): seeded "
                        "load through N primary-kill/standby-takeover "
                        "cycles over the in-process replication link — "
                        "lease-expiry-fenced takeover, successor adopts "
                        "the standby's shadow, the last kill lands with "
                        "real replication lag behind a scripted link "
                        "partition. Emits failover_lost / failover_dup / "
                        "failover_rto_ms / replication_lag_ms_p99 "
                        "(bench_diff gates them, lower is better; "
                        "lost/dup under the zero-baseline rule). "
                        "Standalone mode: skips every other phase")
    p.add_argument("--failover-cycles", type=int, default=3,
                   help="kill/takeover cycles per run (last one is the "
                        "kill-under-lag cycle)")
    p.add_argument("--failover-runs", type=int, default=2,
                   help="full soak repetitions; >= 2 additionally pins "
                        "the takeover transcripts bit-identical across "
                        "runs")
    p.add_argument("--failover-pairs", type=int, default=6,
                   help="matching pairs per cycle (deterministic "
                        "designed load)")
    p.add_argument("--failover-singles", type=int, default=3,
                   help="never-matching singles per cycle (the adopted "
                        "waiting pool must carry them across hosts)")
    p.add_argument("--failover-late-singles", type=int, default=2,
                   help="singles published BEHIND the lag-cycle link "
                        "partition — the bounded loss the kill-under-lag "
                        "gate measures (0 disables the lag cycle)")
    p.add_argument("--failover-rate", type=float, default=800.0,
                   help="publish pacing for the cycle load (req/s)")
    p.add_argument("--failover-seed", type=int, default=29)
    p.add_argument("--failover-lease-s", type=float, default=0.4,
                   help="lease duration on the in-process authority "
                        "(takeover expiry is scripted on the authority's "
                        "clock, so the soak never sleeps it out)")
    p.add_argument("--failover-keep-dirs", action="store_true",
                   help="keep the per-host journal directories for "
                        "inspection")
    p.add_argument("--transport", default="inproc",
                   choices=("inproc", "socket", "socket-loopback"),
                   help="--failover-soak replication fabric (ISSUE 20): "
                        "'inproc' = the PR 17 in-process link; "
                        "'socket-loopback' = the SAME soak script over "
                        "real UDS sockets + a remote lease client in one "
                        "process (nemesis off — the in-proc ≡ socket "
                        "equivalence pin: transcripts must be "
                        "bit-identical to inproc on the same seed); "
                        "'socket' = CROSS-PROCESS soak: lease service + "
                        "host chain as subprocesses, SIGKILL mid-load "
                        "under the scripted network nemesis (incl. one "
                        "asymmetric partition and one mid-stream reset)")
    p.add_argument("--incident-soak", action="store_true",
                   help="incident-forensics soak (ISSUE 18): seeded flash "
                        "crowd + scripted lease-expiry failover + hard "
                        "crash with the black-box recorder armed — every "
                        "exercised trigger class must capture a bundle, "
                        "the spine transcript must be bit-identical "
                        "across runs, capture p99 <= 50ms with zero "
                        "rate-limiter drops, and scripts/postmortem.py "
                        "must reconstruct the takeover root chain offline "
                        "from the persisted bundle alone. Standalone "
                        "mode: skips every other phase")
    p.add_argument("--incident-pairs", type=int, default=30,
                   help="matching pairs per flash crowd (sized so the "
                        "paced burst outlasts the slow burn window)")
    p.add_argument("--incident-singles", type=int, default=6,
                   help="never-matching singles per flash crowd")
    p.add_argument("--incident-rate", type=float, default=30.0,
                   help="publish pacing for the flash crowd (req/s); the "
                        "default keeps the burst > slo_slow_window_s so "
                        "the burn fires mid-burst")
    p.add_argument("--incident-runs", type=int, default=2,
                   help="soak repetitions; >= 2 additionally pins the "
                        "spine transcripts bit-identical across runs")
    p.add_argument("--incident-seed", type=int, default=31)
    p.add_argument("--incident-lease-s", type=float, default=0.5,
                   help="lease duration on the in-process authority "
                        "(takeover expiry is scripted on the authority's "
                        "clock)")
    p.add_argument("--incident-keep-dirs", action="store_true",
                   help="keep the per-run journal + incident directories "
                        "for inspection")
    p.add_argument("--modelcheck", action="store_true",
                   help="standalone: bounded exhaustive interleaving "
                        "model check of the lease/replication/failover "
                        "protocol on the REAL objects "
                        "(analysis/modelcheck.py) — no backend needed; "
                        "emits modelcheck_* metrics and a minimized "
                        "digest-replayable counterexample on violation")
    p.add_argument("--modelcheck-mutations", action="store_true",
                   help="standalone: the model checker's mutation gate — "
                        "break each fenced seam one at a time and "
                        "require a minimized counterexample per mutant "
                        "(mutation_gate_passed)")
    p.add_argument("--modelcheck-queues", type=int, default=2,
                   help="modelcheck scope: queues sharing one lease "
                        "authority")
    p.add_argument("--modelcheck-depth", type=int, default=6,
                   help="modelcheck scope: schedule length bound")
    p.add_argument("--modelcheck-admits", type=int, default=2,
                   help="modelcheck scope: admit windows per queue")
    p.add_argument("--modelcheck-settles", type=int, default=1,
                   help="modelcheck scope: terminal settles per queue")
    p.add_argument("--modelcheck-faults",
                   default="expire,crash,drop,dup",
                   help="modelcheck scope: comma list from "
                        "expire,crash,drop,dup,reorder,partition")
    p.add_argument("--modelcheck-fault-budget", type=int, default=2,
                   help="modelcheck scope: total fault actions per "
                        "schedule")
    p.add_argument("--modelcheck-deadline-s", type=float, default=0.0,
                   help="modelcheck wall-clock cap in seconds (0 = "
                        "none; hitting it reports exhaustive=false)")
    p.add_argument("--scenario-matrix", default="",
                   help="scenario observatory (ISSUE 13): run the named "
                        "population-model scenarios (comma list, or 'all' "
                        "for every configs/scenarios/*.json) as a soak "
                        "matrix — one fresh app per cell, seeded arrival "
                        "transcript, autotuner closing the loop — and "
                        "emit per-cell telemetry-trajectory + attribution "
                        "+ SLO/quality/shed artifacts (scenario_matrix "
                        "rows, gated by scripts/bench_diff.py). "
                        "Standalone mode: skips every other phase")
    p.add_argument("--scenario-seed", type=int, default=21,
                   help="arrival/chaos seed for every matrix cell")
    p.add_argument("--scenario-rate-scale", type=float, default=1.0,
                   help="multiply every scenario segment's offered rate")
    p.add_argument("--scenario-time-scale", type=float, default=1.0,
                   help="compress/stretch every scenario's curve "
                        "(0.5 = replay in half the time)")
    p.add_argument("--scenario-slo-ms", type=float, default=100.0,
                   help="per-cell SLO target (ms) — also the autotuner's "
                        "steering target")
    p.add_argument("--scenario-wait-ms", type=float, default=25.0,
                   help="each cell's STATIC batcher window wait; the "
                        "autotuner tightens it per workload (the knob "
                        "trajectory is the artifact's point)")
    p.add_argument("--scenario-max-waiting", type=int, default=2048,
                   help="per-cell admission waiting-pool cap "
                        "(OverloadConfig.max_waiting)")
    p.add_argument("--scenario-trajectory", type=int, default=120,
                   help="telemetry-ring snapshots embedded per cell")
    p.add_argument("--scenario-no-autotune", action="store_true",
                   help="run the matrix with static knobs (the baseline "
                        "the closed-loop win is measured against)")
    p.add_argument("--scenario-tuned-dir", default="",
                   help="write each cell's converged knob artifact to "
                        "<dir>/<scenario>.json (the configs/tuned/ "
                        "capacity artifacts)")
    args = p.parse_args()
    if args.modelcheck:
        # Standalone, pure host-side: the checker drives the real
        # replication objects under a virtual clock — no jax backend,
        # no broker, deterministic by construction.
        print(json.dumps(bench_modelcheck(args)), flush=True)
        return
    if args.modelcheck_mutations:
        print(json.dumps(bench_modelcheck_mutations(args)), flush=True)
        return
    if args.crash_soak:
        # Standalone like --placement-soak: the device-lost cycle needs a
        # D=2 mesh, so force >= 2 host devices before any jax import (a
        # no-op on a real TPU backend — the flag only affects the CPU
        # platform).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=2"
            ).strip()
        print(json.dumps(bench_crash_soak(args)), flush=True)
        return
    if args.failover_soak:
        # Standalone like --crash-soak: one queue, CPU-harness friendly
        # (no mesh needed — the failover axis is hosts, not devices).
        print(json.dumps(bench_failover_soak(args)), flush=True)
        return
    if args.incident_soak:
        # Standalone like --failover-soak: one queue, CPU-harness
        # friendly; the forensics axis is the event spine + recorder.
        print(json.dumps(bench_incident_soak(args)), flush=True)
        return
    if args.scenario_matrix:
        # Standalone: the matrix is its own artifact. Its cells serve
        # through TpuEngine and report latency, so they need the chip
        # (tests call bench_scenario_matrix in-process on the CPU).
        require_tpu()
        print(json.dumps(bench_scenario_matrix(args)), flush=True)
        return
    if args.placement_soak:
        # Before any jax import: the soak needs >= 2 devices for the
        # migrate legs (4 for the shard cycle).  The host-platform flag
        # is set UNCONDITIONALLY — it only affects the CPU platform, so
        # it is a no-op on a real TPU backend, and gating it on
        # JAX_PLATFORMS would leave a bare-env CPU box at 1 device where
        # every scripted action is refused and the gate passes vacuously.
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4"
            ).strip()
        print(json.dumps(bench_placement_soak(args)), flush=True)
        return
    if args.comms:
        for row in comms_accounting_rows(capacity=args.comms_capacity,
                                         frontier_k=args.comms_frontier_k):
            print(json.dumps(row), flush=True)
        return
    if args.latency:
        # Latency operating point: one small window in flight, no
        # grouping (grouping trades first-window latency for transfer
        # throughput), tighter batcher wait.
        args.window = args.latency_window
        args.depth = 1
        args.readback_group = 1
        args.skip_e2e = True
        args.skip_multiproc = True
        args.skip_cpu = True
    if args.depth < args.readback_group:
        log(f"[warn] depth {args.depth} < readback-group "
            f"{args.readback_group}: groups can never fill before the "
            f"depth gate blocks; grouping degrades to loose partial seals")

    devices = require_tpu()
    from matchmaking_tpu.utils.compile_cache import enable_compile_cache

    log(f"[cache] compile cache at {enable_compile_cache()}")
    import jax

    log(f"jax {jax.__version__} devices={devices}")

    tpu = bench_tpu(args)
    if args.latency:
        print(json.dumps({
            "metric": (f"p99 match latency @ {args.pool}-player pool "
                       "(1v1 ELO, latency preset)"),
            "value": round(tpu["p99_ms"], 3),
            "unit": "ms",
            "vs_baseline": None,
            "device": device_label(devices),
            "p50_ms": round(tpu["p50_ms"], 3),
            "p99_target_ms": 50.0,
            "window": args.window,
            "depth": 1,
            "readback_group": 1,
            "matches_per_sec": round(tpu["matches_per_sec"], 1),
            "device_step_ms": tpu.get("device_step_ms"),
        }), flush=True)
        return
    # A phase that fails fails the run: its exception ends the process
    # non-zero, never a logged line under an exit code of 0.
    e2e = {}
    if not args.skip_e2e:
        e2e = bench_e2e(args)
        log(f"[e2e] {e2e}")
    if args.e2e_quality:
        e2e.update(bench_quality_frontier(args))
    if args.e2e_ab_seconds > 0:
        e2e.update(bench_consume_ab(args))
    if args.spec_ab:
        e2e.update(bench_spec_ab(args))
    mp = bench_multiproc(args) if not args.skip_multiproc else {}
    pool_scale: list = (bench_pool_scale(args) if args.pool_scale else [])
    if args.skip_cpu:
        # None, not NaN: NaN is not valid RFC 8259 JSON and breaks strict
        # parsers on the driver side.
        cpu = {"matches_per_sec": None}
        vs = None
    else:
        cpu = bench_cpu_oracle(args)
        vs = (round(tpu["matches_per_sec"] / cpu["matches_per_sec"], 2)
              if cpu["matches_per_sec"] > 0 else None)

    result = {
        "metric": f"matches/sec @ {args.pool}-player pool (1v1 ELO)",
        "value": round(tpu["matches_per_sec"], 1),
        "unit": "matches/sec",
        "vs_baseline": vs,
        "device": device_label(devices),
        "p50_ms": round(tpu["p50_ms"], 3),
        "p99_ms": round(tpu["p99_ms"], 3),
        "p99_target_ms": 50.0,
        "pool": tpu["pool"],
        "window": tpu["window"],
        "total_matches": tpu["total_matches"],
        "all_runs_mps": tpu.get("all_runs_mps", []),
        **e2e,
        **mp,
        **({"pool_scale": pool_scale} if pool_scale else {}),
        # The headline sub-O(P) number (ISSUE 14): the largest measured
        # pool's touched fraction (max by pool, not CLI order — rounds
        # must gate like against like), falling back to the engine
        # phase's (present when --bucketed).
        **({"formation_touched_frac":
            (max(pool_scale,
                 key=lambda r: r.get("pool", 0))
             .get("formation_touched_frac")
             if pool_scale else tpu.get("formation_touched_frac"))}
           if (pool_scale or tpu.get("formation_touched_frac") is not None)
           else {}),
        "hot_path_recompiles": tpu.get("hot_path_recompiles"),
        "device_step_ms": tpu.get("device_step_ms"),
        "hbm_bytes_per_s": tpu.get("hbm_bytes_per_s"),
        "hbm_util_of_peak": tpu.get("hbm_util_of_peak"),
        "pair_scores_per_s": tpu.get("pair_scores_per_s"),
        "baseline": {
            "what": "CPU oracle (reference sequential-scan semantics) "
                    f"@ {args.cpu_pool}-player pool",
            "matches_per_sec": (None if cpu["matches_per_sec"] is None
                                else round(cpu["matches_per_sec"], 1)),
        },
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
