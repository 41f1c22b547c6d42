#!/usr/bin/env python3
"""Chip smoke: the 1v1 service path, end to end, on the TPU.

Drives BASELINE config #1 (``configs/elo_1v1.json``, unchanged) through the
entry points a user calls — broker → middleware → batcher → ``TpuEngine``
→ response — at deployment size: a pool of 100,000 waiting players with
seeded N(1500, 300) ratings, then windows of ~1,024 search requests
published through ``MatchmakingClient``. Every response is checked, and a
contention-free tail must match exactly what the host oracle
(``CpuEngine``) matches on the same requests.

    python chip_smoke.py            # one chip: the served path
    python chip_smoke.py --chips 4  # four chips: the sharded pool
                                    # (mesh_pool_axis=4) against one device

There is no CPU mode: when JAX finds no TPU the script exits non-zero and
prints no result. The last line of a passing run is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The checks are plain functions over plain data, so tier-1 tests run them
on the CPU at toy size (``tests/test_chip_smoke.py``); only ``main()``
requires the chip.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(REPO, "configs", "elo_1v1.json")
#: Counters that must stay 0: each means the breaker swapped (or was about
#: to swap) the host oracle in for the device engine (service/app.py).
ORACLE_COUNTERS = ("engine_crashes", "breaker_trips",
                   "breaker_degraded_revives")
#: Statuses a served request may end in; anything else is a failure.
OK_STATUSES = ("matched", "queued")
#: Rating slack for the threshold check: the device compares f32 ratings.
F32_SLACK = 1e-2
#: Deployment size (BASELINE config #1): waiting players restored before
#: traffic, served windows, requests per window, contention-free tail pairs.
POOL = 100_000
WINDOWS = 3
WINDOW = 1024
TAIL_PAIRS = 64


# ---- the checks (plain data in, a list of failures out) --------------------

def check_responses(sent: list[str],
                    statuses: dict[str, list[str]]) -> list[str]:
    """Every request got at least one response, and none ended badly."""
    errors = []
    missing = [pid for pid in sent if not statuses.get(pid)]
    if missing:
        errors.append(f"{len(missing)} of {len(sent)} requests got no "
                      f"response (first: {missing[:3]})")
    bad = {pid: s for pid in sent for s in statuses.get(pid, ())
           if s not in OK_STATUSES}
    if bad:
        errors.append(f"{len(bad)} requests answered outside "
                      f"{OK_STATUSES}: {sorted(bad.items())[:3]}")
    return errors


def check_matches(matches: dict[str, tuple[str, ...]],
                  ratings: dict[str, float],
                  threshold: float) -> list[str]:
    """Every match is a pair within ``threshold`` (widening is 0), and no
    player id appears in two matches."""
    errors = []
    seen: collections.Counter = collections.Counter()
    for mid, players in matches.items():
        seen.update(players)
        if len(players) != 2:
            errors.append(f"match {mid} has {len(players)} players")
            continue
        a, b = players
        gap = abs(ratings[a] - ratings[b])
        if gap > threshold + F32_SLACK:
            errors.append(f"match {mid} ({a}, {b}) is {gap:.3f} apart, "
                          f"over the threshold {threshold}")
    doubled = sorted(pid for pid, n in seen.items() if n > 1)
    if doubled:
        errors.append(f"{len(doubled)} players in two matches "
                      f"(first: {doubled[:3]})")
    return errors


def check_pool_accounting(pool_size: int, filled: int, requests: int,
                          matches: int) -> list[str]:
    """Every request entered the pool and every match took two out."""
    want = filled + requests - 2 * matches
    if pool_size != want:
        return [f"pool holds {pool_size}, expected {filled} + {requests} "
                f"- 2 x {matches} = {want}"]
    return []


def check_oracle(device_pairs: set, oracle_pairs: set) -> list[str]:
    """The contention-free tail: the device's match set is the oracle's."""
    if device_pairs == oracle_pairs:
        return []
    return [f"tail match set differs from the host oracle: "
            f"{len(device_pairs - oracle_pairs)} only on the device, "
            f"{len(oracle_pairs - device_pairs)} only in the oracle"]


def check_device_engine(app, queue_name: str, platform: str) -> list[str]:
    """The queue still runs on the device engine — no hidden host oracle."""
    import jax

    from matchmaking_tpu.engine.tpu import TpuEngine

    engine = app.runtime(queue_name).engine
    errors = []
    if not isinstance(engine, TpuEngine):
        errors.append(f"queue {queue_name!r} runs {type(engine).__name__}, "
                      f"not TpuEngine")
    else:
        where = {d.platform for leaf in jax.tree.leaves(engine._dev_pool)
                 for d in leaf.devices()}
        if where != {platform}:
            errors.append(f"pool arrays live on {sorted(where)}, "
                          f"not {platform!r}")
    for name in ORACLE_COUNTERS:
        n = app.metrics.counters.get(name)
        if n:
            errors.append(f"{name} = {n:g}, must be 0")
    return errors


# ---- workload (seeded) ------------------------------------------------------

def fill_requests(rng, n: int, now: float):
    """The waiting pool: ``n`` players with N(1500, 300) ratings."""
    from matchmaking_tpu.service.contract import SearchRequest

    ratings = rng.normal(1500.0, 300.0, size=n)
    return [SearchRequest(id=f"pool{i}", rating=float(r), enqueued_at=now)
            for i, r in enumerate(ratings)]


def tail_players(rng, pairs: int, threshold: float) -> list[dict]:
    """Contention-free tail: pair k sits at 10,000 + 1,000·k, far outside
    the pool's band and ten thresholds from any other pair, so each request
    has exactly one feasible partner."""
    out = []
    for k in range(pairs):
        base = 10_000.0 + 1_000.0 * k
        gap = float(rng.uniform(0.0, 0.9 * threshold))
        out.append({"id": f"tail{k}a", "rating": base})
        out.append({"id": f"tail{k}b", "rating": base + gap})
    return out


def pairs_of(matches) -> set:
    return {frozenset(players) for players in matches}


# ---- the served path --------------------------------------------------------

async def _serve_window(client, players: list[dict], replies: list,
                        statuses: dict[str, list[str]],
                        matches: dict[str, tuple[str, ...]],
                        timeout_s: float) -> None:
    """Publish one window and wait for every request's first response."""
    sent = [(p["id"], client.submit(p)) for p in players]
    replies += sent

    async def first(reply_to: str) -> None:
        resp = await client.next_response(reply_to, timeout=timeout_s)
        if resp is not None:
            _note(resp, statuses, matches)

    await asyncio.gather(*(first(q) for _pid, q in sent))
    await _drain(client, replies, statuses, matches)


async def _drain(client, replies: list, statuses, matches) -> None:
    """Collect the responses already waiting: a request queued earlier is
    answered again when a later one matches it."""
    for _pid, q in replies:
        while client.broker.queue_depth(q):
            resp = await client.next_response(q, timeout=1.0)
            if resp is not None:
                _note(resp, statuses, matches)


def _note(resp, statuses, matches) -> None:
    statuses.setdefault(resp.player_id, []).append(resp.status)
    if resp.status == "matched" and resp.match is not None:
        matches[resp.match.match_id] = tuple(resp.match.players)


async def serve_smoke(cfg, *, pool: int, windows: int, window: int,
                      tail_pairs: int, seed: int, say=print,
                      timeout_s: float = 600.0) -> list[str]:
    """Boot the app on the in-process broker, fill the pool, serve
    ``windows`` windows of ``window`` requests plus the contention-free
    tail, and check everything. Returns the failures (empty = pass)."""
    import jax
    import numpy as np

    from matchmaking_tpu.engine.cpu import CpuEngine
    from matchmaking_tpu.native import codec
    from matchmaking_tpu.service.app import MatchmakingApp
    from matchmaking_tpu.service.client import MatchmakingClient
    from matchmaking_tpu.service.contract import SearchRequest
    from matchmaking_tpu.utils.metrics import CompileCounter

    queue = cfg.queues[0]
    rng = np.random.default_rng(seed)
    errors: list[str] = []

    t = time.perf_counter()
    app = MatchmakingApp(cfg)
    await app.start()
    say(f"boot_s={time.perf_counter() - t:.3f} "
        f"native_codec={codec.available()}")
    try:
        rt = app.runtime(queue.name)
        fill = fill_requests(rng, pool, time.time())
        ratings = {r.id: r.rating for r in fill}
        t = time.perf_counter()
        async with rt._engine_lock:
            rt.engine.restore(fill, time.time())
            jax.block_until_ready(rt.engine._dev_pool)
        say(f"fill_s={time.perf_counter() - t:.3f} "
            f"pool={rt.engine.pool_size()}")

        client = MatchmakingClient(app.broker, cfg.broker.request_queue)
        statuses: dict[str, list[str]] = {}
        matches: dict[str, tuple[str, ...]] = {}
        replies: list = []
        sent: list[str] = []
        for w in range(windows):
            players = [{"id": f"w{w}r{i}", "rating": float(r)}
                       for i, r in enumerate(
                           rng.normal(1500.0, 300.0, size=window))]
            ratings.update((p["id"], p["rating"]) for p in players)
            sent += [p["id"] for p in players]
            c0, m0 = CompileCounter.count(), len(matches)
            cs0 = CompileCounter.seconds()
            t = time.perf_counter()
            await _serve_window(client, players, replies, statuses, matches,
                                timeout_s)
            say(f"window={w} requests={len(players)} "
                f"wall_s={time.perf_counter() - t:.3f} "
                f"new_matches={len(matches) - m0} "
                f"compiles={CompileCounter.count() - c0} "
                f"compile_s={CompileCounter.seconds() - cs0:.3f}")

        tail = tail_players(rng, tail_pairs, queue.rating_threshold)
        ratings.update((p["id"], p["rating"]) for p in tail)
        sent += [p["id"] for p in tail]
        before = set(matches)
        t = time.perf_counter()
        await _serve_window(client, tail, replies, statuses, matches,
                            timeout_s)
        await asyncio.sleep(0.2)
        await _drain(client, replies, statuses, matches)
        tail_ids = {p["id"] for p in tail}
        device_tail = pairs_of(p for m, p in matches.items()
                               if m not in before and tail_ids & set(p))
        oracle = CpuEngine(cfg, queue).search(
            [SearchRequest(id=p["id"], rating=p["rating"]) for p in tail],
            now=0.0)
        oracle_tail = pairs_of(m.result().players for m in oracle.matches)
        say(f"tail pairs={tail_pairs} wall_s={time.perf_counter() - t:.3f} "
            f"device_matches={len(device_tail)} "
            f"oracle_matches={len(oracle_tail)}")

        errors += check_responses(sent, statuses)
        errors += check_matches(matches, ratings, queue.rating_threshold)
        errors += check_oracle(device_tail, oracle_tail)
        async with rt._engine_lock:
            pool_now = rt.engine.pool_size()
        errors += check_pool_accounting(pool_now, pool, len(sent),
                                        len(matches))
        errors += check_device_engine(app, queue.name,
                                      jax.devices()[0].platform)
        say(f"served requests={len(sent)} matches={len(matches)} "
            f"pool_after={pool_now} "
            f"xla_compiles={CompileCounter.count()} "
            + " ".join(f"{n}={app.metrics.counters.get(n):g}"
                       for n in ORACLE_COUNTERS))
    finally:
        await app.stop()
    return errors


# ---- the sharded pool against one device ------------------------------------

def sharded_compare(cfg, *, shards: int, pool: int, windows: int,
                    window: int, seed: int, say=print) -> list[str]:
    """The same seeded fill and windows through a pool sharded over
    ``shards`` devices (``mesh_pool_axis``) and through one engine on
    device 0; the two match sets must be identical."""
    import jax
    import numpy as np

    from matchmaking_tpu.engine.tpu import TpuEngine
    from matchmaking_tpu.service.contract import SearchRequest

    queue = cfg.queues[0]
    sharded_cfg = dataclasses.replace(
        cfg, engine=dataclasses.replace(cfg.engine, mesh_pool_axis=shards))
    engines = {"sharded": TpuEngine(sharded_cfg, queue),
               "single": TpuEngine(cfg, queue, devices=(0,))}
    errors = []
    mesh_devs = list(engines["sharded"].kernels.mesh.devices.flat)
    placed = {name: {d for leaf in jax.tree.leaves(e._dev_pool)
                     for d in leaf.devices()}
              for name, e in engines.items()}
    say(f"mesh devices={[d.id for d in mesh_devs]} "
        f"sharded pool on {sorted(d.id for d in placed['sharded'])}, "
        f"single pool on {sorted(d.id for d in placed['single'])}")
    if len(set(mesh_devs)) != shards or placed["sharded"] != set(mesh_devs):
        errors.append(f"sharded pool spans {len(placed['sharded'])} "
                      f"devices, not {shards}")
    if placed["single"] != {jax.devices()[0]}:
        errors.append("single-device pool is not on device 0")

    results = {}
    for name, engine in engines.items():
        rng = np.random.default_rng(seed)
        now = 1.0
        t = time.perf_counter()
        engine.restore(fill_requests(rng, pool, now), now)
        jax.block_until_ready(engine._dev_pool)
        say(f"{name}: fill_s={time.perf_counter() - t:.3f}")
        pairs = set()
        for w in range(windows):
            reqs = [SearchRequest(id=f"w{w}r{i}", rating=float(r),
                                  enqueued_at=now)
                    for i, r in enumerate(
                        rng.normal(1500.0, 300.0, size=window))]
            t = time.perf_counter()
            out = engine.search(reqs, now)
            pairs |= pairs_of(m.result().players for m in out.matches)
            say(f"{name}: window={w} wall_s={time.perf_counter() - t:.3f} "
                f"matches={len(out.matches)}")
        results[name] = (pairs, engine.pool_size())
    (sp, s_pool), (dp, d_pool) = results["sharded"], results["single"]
    say(f"sharded matches={len(sp)} pool={s_pool}; "
        f"single matches={len(dp)} pool={d_pool}; identical={sp == dp}")
    if sp != dp:
        errors.append(f"match sets differ: {len(sp - dp)} only sharded, "
                      f"{len(dp - sp)} only single-device")
    if s_pool != d_pool:
        errors.append(f"pool sizes differ: {s_pool} vs {d_pool}")
    return errors


# ---- entry point --------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the sharded-pool comparison")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {d0.platform!r} "
              f"({d0.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 2
    try:
        from matchmaking_tpu.config import Config
        from matchmaking_tpu.utils.compile_cache import (
            cache_entries,
            enable_compile_cache,
        )
    except ImportError as e:
        print(f"chip_smoke: run from the repo root ({e})", file=sys.stderr)
        return 2

    label = f"[{d0.platform} {d0.device_kind} x{args.chips}]"

    def say(msg: str) -> None:
        print(f"{label} {msg}", flush=True)

    cache = enable_compile_cache()
    say(f"device_kind={d0.device_kind} jax={jax.__version__} "
        f"compile_cache={cache} entries_before={cache_entries(cache)}")
    cfg = Config.from_json(CONFIG)
    t = time.perf_counter()
    if args.chips == 1:
        errors = asyncio.run(serve_smoke(
            cfg, pool=POOL, windows=WINDOWS, window=WINDOW,
            tail_pairs=TAIL_PAIRS, seed=args.seed, say=say))
    else:
        errors = sharded_compare(
            cfg, shards=args.chips, pool=POOL, windows=WINDOWS,
            window=WINDOW, seed=args.seed, say=say)
    say(f"total_s={time.perf_counter() - t:.3f} "
        f"entries_after={cache_entries(cache)}")
    for e in errors:
        say(f"FAIL {e}")
    if errors:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
