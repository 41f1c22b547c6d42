#!/usr/bin/env python
"""Per-config benchmarks: every BASELINE.json config gets a measured
matches/sec + p99 (round-3 verdict ask #5 — configs #2-#5 had correctness
tests but zero perf numbers).

Prints ONE JSON line per config and (with --out) rewrites the results table
in BENCH_CONFIGS.md. Configs:

1. elo_1v1              columnar pipelined engine path (same as bench.py)
2. multiqueue_filters   columnar with region/mode hard filters in-kernel
3. team_5v5             device team kernel (object API windows)
4. glicko2              columnar with rating-deviation-weighted distance
5. role_solo_device     device role kernel (round 5) — solo role traffic
                        at the team bench's scale
   role_party           host-side oracle (parties delegate there) — a
                        LADDER of pool sizes records its scale ceiling
                        (O(n^2) windows x backtracking by design)

Run from the repo root with PYTHONPATH=. on the chip, or with
JAX_PLATFORMS=cpu for a mechanics smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import (  # noqa: E402
    comms_accounting_rows,
    make_columns,
    run_engine_pipelined,
)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _pctls(lats_s):
    arr = np.sort(np.asarray(lats_s)) * 1e3
    return (round(float(np.percentile(arr, 50)), 3),
            round(float(np.percentile(arr, 99)), 3))


def make_columns_variant(rng, n, start_id, now, *, n_regions=0, n_modes=0,
                         rd=False):
    """Columnar window with optional region/mode codes and Glicko-2 RDs.
    Code 0 means wildcard in the kernel, so real codes start at 1."""
    cols = make_columns(rng, n, start_id, now)
    if n_regions:
        cols.region[:] = rng.integers(1, n_regions + 1, size=n).astype(np.int32)
    if n_modes:
        cols.mode[:] = rng.integers(1, n_modes + 1, size=n).astype(np.int32)
    if rd:
        cols.rd[:] = rng.uniform(50.0, 350.0, size=n).astype(np.float32)
    return cols


def bench_columnar_config(name, queue_kwargs, *, pool, capacity, window,
                          windows, depth, gen_kwargs):
    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine

    cfg = Config(
        queues=(QueueConfig(**queue_kwargs),),
        engine=EngineConfig(backend="tpu", pool_capacity=capacity,
                            pool_block=8192, top_k=8,
                            batch_buckets=(16, 64, 256, window)),
    )
    engine = make_engine(cfg, cfg.queues[0])
    rng = np.random.default_rng(11)
    mps, lats, total = run_engine_pipelined(
        engine, rng, pool_target=pool, window=window, warmup=3,
        measured=windows, depth=depth, label=name,
        gen=lambda r, n, s, t: make_columns_variant(r, n, s, t, **gen_kwargs))
    p50, p99 = _pctls(lats)
    return {"config": name, "matches_per_sec": round(mps, 1),
            "p50_ms": p50, "p99_ms": p99, "pool": pool, "window": window,
            "total_matches": total, "path": "device columnar pipelined"}


def bench_team_5v5(*, pool, capacity, window, windows, depth=2):
    """Device team kernel through the PIPELINED object API (search_async +
    collect_ready, ≤depth windows in flight — the path the service now
    runs); latency = dispatch → collected on host."""
    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine
    from matchmaking_tpu.service.contract import SearchRequest

    cfg = Config(
        queues=(QueueConfig(team_size=5, rating_threshold=120.0,
                            widen_per_sec=2.0, max_threshold=300.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=capacity,
                            team_max_matches=512,
                            batch_buckets=(16, 64, 256, window)),
    )
    engine = make_engine(cfg, cfg.queues[0])
    rng = np.random.default_rng(12)
    next_id = 0

    def reqs(n, now):
        nonlocal next_id
        out = [SearchRequest(id=f"t{next_id + i}",
                             rating=float(rng.normal(1500, 150)),
                             region="eu", game_mode="ranked",
                             enqueued_at=now)
               for i in range(n)]
        next_id += n
        return out

    def refill(now):
        deficit = pool - engine.pool_size()
        while deficit > 0:
            chunk = min(deficit, 4096)
            engine.restore(reqs(chunk, now), now)
            deficit -= chunk

    now = 1.0
    refill(now)
    log(f"[team_5v5] pool filled to {engine.pool_size()}")
    lats, players = [], 0
    submit_t, timed = {}, {}
    t_start = t_last = None

    def handle(tok, out):
        nonlocal players, t_last
        lat = time.perf_counter() - submit_t.pop(tok)
        if timed.pop(tok):
            lats.append(lat)
            players_here = sum(len(t) for m in out.matches for t in m.teams)
            players = players + players_here
            t_last = time.perf_counter()

    for i in range(3 + windows):
        window_reqs = reqs(window, now)
        if i == 3:
            t_start = time.perf_counter()
        tok, _ = engine.search_async(window_reqs, now)
        submit_t[tok] = time.perf_counter()
        timed[tok] = i >= 3
        now += 1e-3
        for tok2, out in engine.collect_ready():
            handle(tok2, out)
        while engine.inflight() >= depth:
            got = engine.collect_ready()
            if not got:
                time.sleep(0.0005)
            for tok2, out in got:
                handle(tok2, out)
        refill(now)
    for tok2, out in engine.flush():
        handle(tok2, out)
    span = (t_last - t_start) if (t_start and t_last and t_last > t_start) else 0.0
    p50, p99 = _pctls(lats)
    return {"config": "team_5v5",
            "matches_per_sec": round(players / 10.0 / span, 1) if span else 0.0,
            "players_matched_per_sec": round(players / span, 1) if span else 0.0,
            "p50_ms": p50, "p99_ms": p99, "pool": pool, "window": window,
            "path": f"device team kernel (pipelined depth={depth})"}


def bench_role_solo_device(*, pool, capacity, window, windows, depth=2):
    """Device role kernel (round 5 — engine/role_kernels.py) through the
    pipelined object API: solo players with declared roles at the team
    bench's scale. The round-4 host ladder ceiling was ~2-4k pool at 8 ms
    per arrival; this is the ≥10× device answer for solo traffic (parties
    still delegate — the ladder below keeps their honest oracle numbers).
    Role mix is dps-heavy (55% dps / 15% tank / 15% healer / 15% any) so
    matches gate on scarce roles like production."""
    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine
    from matchmaking_tpu.service.contract import SearchRequest

    roles = ("tank", "healer", "dps", "dps", "dps")
    cfg = Config(
        queues=(QueueConfig(team_size=5, rating_threshold=120.0,
                            role_slots=roles,
                            widen_per_sec=2.0, max_threshold=300.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=capacity,
                            team_max_matches=512,
                            batch_buckets=(16, 64, 256, window)),
    )
    engine = make_engine(cfg, cfg.queues[0])
    rng = np.random.default_rng(21)
    next_id = 0

    def reqs(n, now):
        nonlocal next_id
        picks = rng.random(n)
        out = []
        for i in range(n):
            if picks[i] < 0.55:
                rr = ("dps",)
            elif picks[i] < 0.70:
                rr = ("tank",)
            elif picks[i] < 0.85:
                rr = ("healer",)
            else:
                rr = ()
            out.append(SearchRequest(
                id=f"s{next_id + i}", rating=float(rng.normal(1500, 150)),
                region="eu", game_mode="ranked", roles=rr, enqueued_at=now))
        next_id += n
        return out

    def refill(now):
        deficit = pool - engine.pool_size()
        while deficit > 0:
            chunk = min(deficit, 4096)
            engine.restore(reqs(chunk, now), now)
            deficit -= chunk

    now = 1.0
    refill(now)
    log(f"[role_solo] pool filled to {engine.pool_size()}")
    lats, players = [], 0
    submit_t, timed = {}, {}
    t_start = t_last = None

    def handle(tok, out):
        nonlocal players, t_last
        lat = time.perf_counter() - submit_t.pop(tok)
        if timed.pop(tok):
            lats.append(lat)
            players += sum(len(t) for m in out.matches for t in m.teams)
            t_last = time.perf_counter()

    for i in range(3 + windows):
        window_reqs = reqs(window, now)
        if i == 3:
            t_start = time.perf_counter()
        tok, _ = engine.search_async(window_reqs, now)
        submit_t[tok] = time.perf_counter()
        timed[tok] = i >= 3
        now += 1e-3
        for tok2, out in engine.collect_ready():
            handle(tok2, out)
        while engine.inflight() >= depth:
            got = engine.collect_ready()
            if not got:
                time.sleep(0.0005)
            for tok2, out in got:
                handle(tok2, out)
        refill(now)
    for tok2, out in engine.flush():
        handle(tok2, out)
    span = (t_last - t_start) if (t_start and t_last and t_last > t_start) \
        else 0.0
    p50, p99 = _pctls(lats)
    return {"config": "role_solo_device",
            "matches_per_sec": round(players / 10.0 / span, 1) if span else 0.0,
            "players_matched_per_sec": round(players / span, 1) if span else 0.0,
            "p50_ms": p50, "p99_ms": p99, "pool": pool, "window": window,
            "path": f"device role kernel (pipelined depth={depth})"}


def bench_role_party_ladder(*, windows=8):
    """Host-oracle role/party path: latency vs pool size ladder → the
    measured scale ceiling (largest pool with p99 window < 250 ms).

    The pool is built the way role queues build up in PRODUCTION — via
    arrivals that cannot match yet (dps-heavy traffic waiting for scarce
    tanks/healers), NOT via restore(): a restored pool holds latent matches,
    which disables the arrival-focused fast path (roles.try_party_match
    ``focus``) and measures checkpoint-recovery mode instead of steady
    state. Measured windows mix all roles (25% two-player parties), so
    matches trigger on the scarce-role arrivals — the realistic steady
    state for this queue type."""
    from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
    from matchmaking_tpu.engine.interface import make_engine
    from matchmaking_tpu.service.contract import PartyMember, SearchRequest

    roles = ("tank", "healer", "dps", "dps", "dps")
    rng = np.random.default_rng(13)
    ladder = []
    ceiling = 0
    for pool in (500, 1000, 2000, 4000):
        cfg = Config(
            queues=(QueueConfig(team_size=5, rating_threshold=150.0,
                                role_slots=roles),),
            engine=EngineConfig(backend="tpu", pool_capacity=16384),
        )
        engine = make_engine(cfg, cfg.queues[0])
        next_id = 0

        def req(now, role=None):
            nonlocal next_id
            next_id += 1
            r = float(rng.normal(1500, 120))
            role = role or roles[rng.integers(0, 5)]
            if role != "dps" and rng.random() < 0.25:
                return SearchRequest(
                    id=f"r{next_id}", rating=r, roles=(role,),
                    party=(PartyMember(f"r{next_id}b", r + 10.0,
                                       roles=("dps",)),),
                    enqueued_at=now)
            return SearchRequest(id=f"r{next_id}", rating=r, roles=(role,),
                                 enqueued_at=now)

        now = 1.0

        def grow(target):
            nonlocal now
            # dps-only arrivals queue (role slots need tanks/healers) —
            # the pool grows through the ARRIVAL path, preserving the
            # greedy invariant the focused scan relies on.
            while engine.pool_size() < target:
                n_chunk = min(128, target - engine.pool_size())
                engine.search([req(now, role="dps")
                               for _ in range(n_chunk)], now)
                now += 1e-3

        grow(pool)
        lats, players = [], 0
        span = 0.0
        for i in range(2 + windows):
            batch = [req(now) for _ in range(64)]
            t0 = time.perf_counter()
            out = engine.search(batch, now)
            dt = time.perf_counter() - t0
            now += max(dt, 1e-4)
            if i >= 2:
                lats.append(dt)
                players += sum(len(t) for m in out.matches for t in m.teams)
                span += dt
            grow(pool)
        p50, p99 = _pctls(lats)
        per_arrival = round(p99 / 64.0, 3)
        ladder.append({"pool": pool, "p50_ms": p50, "p99_ms": p99,
                       "p99_per_arrival_ms": per_arrival,
                       "players_matched_per_sec":
                       round(players / span, 1) if span else 0.0})
        log(f"[role_party] pool={pool} p50={p50} p99={p99} "
            f"per-arrival={per_arrival}ms")
        if per_arrival < 8.0:
            ceiling = pool
    return {"config": "role_party",
            "path": "host oracle (arrival-focused greedy)",
            "window": 64, "ladder": ladder,
            "scale_ceiling_pool_at_8ms_per_arrival": ceiling,
            "p99_ms": ladder[-1]["p99_ms"] if ladder else None}


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--pool", type=int, default=100_000)
    p.add_argument("--capacity", type=int, default=131_072)
    p.add_argument("--team-pool", type=int, default=50_000)
    p.add_argument("--team-capacity", type=int, default=65_536)
    p.add_argument("--window", type=int, default=2048)
    p.add_argument("--windows", type=int, default=30)
    p.add_argument("--depth", type=int, default=2)
    p.add_argument("--team-window", type=int, default=1024)
    p.add_argument("--team-windows", type=int, default=15)
    p.add_argument("--configs", default="1,2,3,4,5",
                   help="comma-separated subset to run (6 = sharded "
                        "team/role comms accounting at D=2/4/8 — needs "
                        ">= 8 devices, e.g. the virtual CPU mesh)")
    p.add_argument("--comms-capacity", type=int, default=65_536)
    p.add_argument("--comms-frontier-k", type=int, default=1024)
    p.add_argument("--out", default="",
                   help="write/refresh BENCH_CONFIGS.md at this path")
    args = p.parse_args()

    import jax

    log(f"jax {jax.__version__} devices={jax.devices()}")
    which = {int(c) for c in args.configs.split(",")}
    results = []
    if 1 in which:
        results.append(bench_columnar_config(
            "elo_1v1", dict(rating_threshold=100.0), pool=args.pool,
            capacity=args.capacity, window=args.window, windows=args.windows,
            depth=args.depth, gen_kwargs={}))
    if 2 in which:
        results.append(bench_columnar_config(
            "multiqueue_filters", dict(rating_threshold=75.0),
            pool=args.pool, capacity=args.capacity, window=args.window,
            windows=args.windows, depth=args.depth,
            gen_kwargs=dict(n_regions=4, n_modes=2)))
    if 3 in which:
        results.append(bench_team_5v5(
            pool=args.team_pool, capacity=args.team_capacity,
            window=args.team_window, windows=args.team_windows))
    if 4 in which:
        results.append(bench_columnar_config(
            "glicko2", dict(rating_threshold=80.0, glicko2=True,
                            widen_per_sec=5.0, max_threshold=250.0),
            pool=args.pool, capacity=args.capacity, window=args.window,
            windows=args.windows, depth=args.depth,
            gen_kwargs=dict(rd=True)))
    if 5 in which:
        results.append(bench_role_solo_device(
            pool=args.team_pool, capacity=args.team_capacity,
            window=args.team_window, windows=args.team_windows))
        results.append(bench_role_party_ladder())
    if 6 in which:
        results.append({
            "config": "sharded_comms",
            "path": "allgather-replicated vs ppermute ring frontier",
            "rows": comms_accounting_rows(
                capacity=args.comms_capacity,
                frontier_k=args.comms_frontier_k),
        })

    for r in results:
        print(json.dumps(r), flush=True)

    if args.out:
        lines = [
            "# BENCH_CONFIGS — per-config measured performance",
            "",
            "Generated by `scripts/bench_configs.py` (see flags there for the",
            "operating points). One row per BASELINE.json config.",
            "",
            "| config | path | matches/s | p50 ms | p99 ms | pool | window |",
            "|---|---|---|---|---|---|---|",
        ]
        for r in results:
            if r["config"] == "sharded_comms":
                continue  # own section below
            if r["config"] == "role_party":
                best = r["ladder"][-1] if r["ladder"] else {}
                lines.append(
                    f"| role_party | {r['path']} | "
                    f"{best.get('players_matched_per_sec', '-')}/2 players "
                    f"| {best.get('p50_ms', '-')} | {best.get('p99_ms', '-')} "
                    f"| ladder (see below) | {r['window']} |")
            else:
                lines.append(
                    f"| {r['config']} | {r['path']} | "
                    f"{r['matches_per_sec']} | {r['p50_ms']} | {r['p99_ms']} "
                    f"| {r['pool']} | {r['window']} |")
        role = next((r for r in results if r["config"] == "role_party"), None)
        if role:
            lines += ["", "## role_party scale ladder (host oracle)", "",
                      "| pool | p50 ms | p99 ms | p99/arrival ms "
                      "| players matched/s |",
                      "|---|---|---|---|---|"]
            for row in role["ladder"]:
                lines.append(f"| {row['pool']} | {row['p50_ms']} | "
                             f"{row['p99_ms']} | "
                             f"{row['p99_per_arrival_ms']} | "
                             f"{row['players_matched_per_sec']} |")
            lines.append("")
            lines.append(
                f"Measured scale ceiling (p99 per-arrival < 8 ms): "
                f"**{role['scale_ceiling_pool_at_8ms_per_arrival']} "
                f"players**. Beyond that, role/party queues need sharding "
                f"by region/mode (the config-gated host oracle is not the "
                f"1v1 hot path by design).")
        comms = next((r for r in results if r["config"] == "sharded_comms"),
                     None)
        if comms:
            lines += ["", "## sharded team/role comms accounting "
                          "(allgather vs ring frontier)", "",
                      "| family | D | gather ICI B/dev/step | ring ICI "
                      "B/dev/step | gather rows | ring rows | bit-exact |",
                      "|---|---|---|---|---|---|---|"]
            for row in comms["rows"]:
                if "skipped" in row:
                    lines.append(f"| — | {row['n_shards']} | "
                                 f"{row['skipped']} | | | | |")
                    continue
                lines.append(
                    f"| {row['family']} | {row['n_shards']} | "
                    f"{row['allgather_ici_recv_bytes']} | "
                    f"{row['ring_ici_recv_bytes']} | "
                    f"{row['allgather_formation_rows']} | "
                    f"{row['ring_formation_rows']} | "
                    f"{row['outputs_bit_identical']} |")
        with open(args.out, "w") as f:
            f.write("\n".join(lines) + "\n")
        log(f"wrote {args.out}")


if __name__ == "__main__":
    main()
