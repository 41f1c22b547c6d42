"""Drive one cell through the served path and record what it did.

The entry is the in-process broker's request queue of a ``MatchmakingApp``
built from the cell's configuration: every request goes broker →
middleware → batcher → ``TpuEngine`` → device → reply, as a client's
would. One run:

1. boots the app and restores the configuration's waiting pool (the
   engine's ``restore_columns`` under its lock);
2. warms up through the served path: one burst per batch bucket, each
   waited out, so every shape the window uses is compiled (or loaded from
   the compile cache) before it opens;
3. measures for ``seconds``: the open-loop generator publishes each
   request when it is due, and the client stamps each reply when it lands;
4. drains: waits until every request has an answer and the service is
   quiet, at most a minute past the close;
5. reads the program's counters, the device's memory peak and, in a
   traced run, the profiler trace of the window's last seconds, then stops
   the app.

Latency is counted from when a request was due, not from when it was
published, so a stalled generator shows in the tail (and its lag is
reported apart).
"""

from __future__ import annotations

import asyncio
import gc
import json
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from benchmark import trace as trace_mod
from benchmark import traffic
from benchmark.reference import Reply, RunData

#: Where the client reads its replies.
REPLY_QUEUE = "bench.replies"
#: How long past the window's close a late answer is waited for.
DRAIN_S = 60.0
#: How long one warm-up burst may take (a cold first run compiles here).
WARMUP_TIMEOUT_S = 900.0
#: Seconds of the window's end that a traced run records with the profiler.
TRACE_S = 2.0
#: Poll interval of the drain and warm-up waits.
POLL_S = 0.01


@dataclass
class RunRecord:
    """What one run measured, counted and received."""

    setup_s: float
    seconds: float
    attempted: int
    #: Per window request: when it was due (s from the window's start).
    due: np.ndarray
    #: Per window request: latency to its ``matched`` reply (ms), NaN if
    #: it was never matched.
    match_ms: np.ndarray
    #: Distinct matches whose replies landed inside the window.
    matches_in_window: int
    #: Window requests unanswered after the drain, or answered badly.
    failed: int
    data: RunData
    readings: dict[str, Any] = field(default_factory=dict)
    memory_peak_bytes: int = 0
    log: dict[str, Any] = field(default_factory=dict)


def _build_config(cell) -> Any:
    from matchmaking_tpu.config import Config

    return Config.from_dict(cell.config["service"])


#: The request body fields a restored pool holds as columns.
POOL_COLUMN_FIELDS = ("region", "game_mode", "rating_deviation")


def _pool_columns(pool: traffic.Arrivals, now: float, engine):
    """The waiting pool as the engine restores it. The kind's region and
    game mode enter through the engine's own interners (absent, each is
    the wildcard, code 0); its rating deviation as given."""
    from matchmaking_tpu.service.contract import (
        ANY,
        DEFAULT_RD,
        RequestColumns,
    )

    n = len(pool)
    f = pool.fields
    other = sorted(set(f) - set(POOL_COLUMN_FIELDS))
    if other:
        raise ValueError(f"a restored pool has no column for {other}")
    region, mode = np.zeros(n, np.int32), np.zeros(n, np.int32)
    if "region" in f or "game_mode" in f:
        wild = np.full(n, ANY, object)
        region, mode = engine.intern_columns(
            f.get("region", wild).tolist(), f.get("game_mode", wild).tolist())
    return RequestColumns(
        ids=np.asarray(pool.ids, dtype=object),
        rating=pool.rating.astype(np.float32),
        rd=np.asarray(f.get("rating_deviation", np.full(n, DEFAULT_RD)),
                      np.float32),
        region=region,
        mode=mode,
        threshold=np.full(n, np.nan, np.float32),
        enqueued_at=np.full(n, now, np.float64),
    )


class _Client:
    """Publishes requests and stamps the replies as they land. It acks
    every reply and keeps only bodies and ids (objects the garbage
    collector does not walk), so its own footprint stays small."""

    def __init__(self, app, queue: str):
        from matchmaking_tpu.service.broker import Properties

        self.app = app
        self.queue = queue
        self._props = Properties
        #: (receive time, reply bodies, correlation ids) per burst.
        self.bursts: list[tuple[float, list[bytes], list[str]]] = []
        app.broker.declare_queue(REPLY_QUEUE)
        self.answered: set[str] = set()
        self.tag = app.broker.basic_consume(
            REPLY_QUEUE, self._one, prefetch=1 << 30, batch_hint=True,
            batch_callback=self._burst)

    async def _burst(self, deliveries: list) -> None:
        t = time.perf_counter()
        ids = [d.properties.correlation_id for d in deliveries]
        self.bursts.append((t, [d.body for d in deliveries], ids))
        self.answered.update(ids)
        for d in deliveries:
            self.app.broker.ack(self.tag, d.delivery_tag)

    async def _one(self, delivery) -> None:
        await self._burst([delivery])

    def publish(self, pid: str, body: bytes) -> None:
        self.app.broker.publish(self.queue, body, self._props(
            reply_to=REPLY_QUEUE, correlation_id=pid))

    def replies(self) -> list[Reply]:
        out = []
        for t, bodies, ids in self.bursts:
            for pid, raw in zip(ids, bodies):
                body = json.loads(raw)
                m = body.get("match") or {}
                out.append(Reply(
                    to=pid, status=body.get("status", ""), t=t,
                    match_id=m.get("match_id", ""),
                    players=tuple(m.get("players", ())),
                    teams=tuple(tuple(x) for x in m.get("teams", ()))))
        return out

    def close(self) -> None:
        self.app.broker.basic_cancel(self.tag)


#: The program's counters of the garbage collector's pauses.
GC_COUNTERS = ("gc_s", "gc_pauses", "gc_pauses_gen2")


def _gc_counters(app) -> dict[str, float]:
    counters = app.metrics.counters.snapshot()
    return {k: counters.get(k, 0.0) for k in GC_COUNTERS}


def _gc_log(a: dict, b: dict) -> dict[str, float]:
    """The collector's pauses between two readings (log lines, not
    metrics: the host's stalls show in the tail, and these say whether
    the collector is one of them)."""
    return {"gc_pauses": int(b["gc_pauses"] - a["gc_pauses"]),
            "gc_ms_total": 1e3 * (b["gc_s"] - a["gc_s"]),
            "gc_gen2_pauses": int(b["gc_pauses_gen2"] - a["gc_pauses_gen2"])}


def _quiet(app, rt, queue: str) -> bool:
    """Nothing buffered at any stage of the service."""
    return (app.broker.queue_depth(queue) == 0
            and app.broker.queue_depth(REPLY_QUEUE) == 0
            and app.broker.handlers_idle()
            and rt.batcher.depth == 0
            and rt._flushing == 0
            and rt.engine.inflight() == 0)


async def _settle(app, rt, client: _Client, queue: str, ids,
                  timeout: float) -> bool:
    """Wait until every id has an answer and the service is quiet."""
    deadline = time.perf_counter() + timeout
    pending = set(ids) - client.answered
    while time.perf_counter() < deadline:
        pending -= client.answered
        if not pending and _quiet(app, rt, queue):
            return True
        await asyncio.sleep(POLL_S)
    return False


def _snapshot(app, rt, queue: str) -> dict[str, Any]:
    """The program's monotone counters at one instant."""
    from matchmaking_tpu.utils.metrics import CompileCounter

    attr = app.attribution.snapshot(queue)["queues"].get(queue, {})
    return {
        "t": time.perf_counter(),
        "categories": {k: {"total_s": v["total_s"], "traces": v["traces"]}
                       for k, v in attr.get("categories", {}).items()},
        "settled": attr.get("spans", 0),
        "statuses": dict(attr.get("statuses", {})),
        "spans": dict(rt.engine.spans),
        "lanes_padded": rt.engine.util.get("lanes_padded", 0),
        "compiles": CompileCounter.count(),
        "pool": rt.engine.pool_size(),
    }


def _delta(a: dict, b: dict) -> dict[str, Any]:
    """Counters between two snapshots."""
    cats = {}
    for k, v in b["categories"].items():
        u = a["categories"].get(k, {"total_s": 0.0, "traces": 0})
        cats[k] = {"total_s": v["total_s"] - u["total_s"],
                   "traces": v["traces"] - u["traces"]}
    return {
        "seconds": b["t"] - a["t"],
        "categories": cats,
        "settled": b["settled"] - a["settled"],
        "statuses": {k: v - a["statuses"].get(k, 0)
                     for k, v in b["statuses"].items()},
        "spans": {k: v - a["spans"].get(k, 0) for k, v in b["spans"].items()},
        "lanes_padded": b["lanes_padded"] - a["lanes_padded"],
        "compiles": b["compiles"] - a["compiles"],
    }


#: Counters that must stay 0: each means the service's breaker swapped
#: (or was about to swap) the host oracle in for the device engine.
ORACLE_COUNTERS = ("engine_crashes", "breaker_trips",
                   "breaker_degraded_revives")


def _off_device(app, rt, platform: str) -> int:
    """How often the queue left the device path: the oracle counters and
    the team queue's delegations to the host oracle (the engine was built
    by this run), plus one if its engine is no longer the device engine or
    its pool no longer lives on ``platform`` (a pool still delegated holds
    nothing there). A fault the service survives by falling back to the
    host oracle answers correctly, and must still fail the run."""
    import jax

    from matchmaking_tpu.engine.tpu import TpuEngine

    n = sum(int(app.metrics.counters.get(c) or 0) for c in ORACLE_COUNTERS)
    engine = rt.engine
    if not isinstance(engine, TpuEngine):
        return n + 1
    n += int(engine.counters.get("team_delegated", 0))
    where = {d.platform for leaf in jax.tree.leaves(engine._dev_pool)
             for d in leaf.devices()}
    return n + (where != {platform})


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


async def drive(cell, seed: int, seconds: float, *, t_process: float,
                traced: bool = False, say: Callable[[str], None] = print,
                ) -> RunRecord:
    """One run of ``cell``: see the module docstring."""
    import jax

    from matchmaking_tpu.service.app import MatchmakingApp

    cfg = _build_config(cell)
    qcfg = cfg.queues[0]
    kind = cell.kind
    queue = qcfg.name
    devices = jax.devices()[:cell.chips]
    app = MatchmakingApp(cfg)
    await app.start()
    client = None
    try:
        rt = app.runtime(queue)
        pool, = traffic.add_fields(
            [traffic.waiting_pool(cell.config, seed)], kind, cell.config,
            seed, traffic.STREAM_POOL_FIELDS)
        t = time.perf_counter()
        async with rt._engine_lock:
            rt.engine.restore_columns(
                _pool_columns(pool, time.time(), rt.engine), time.time())
            jax.block_until_ready(rt.engine._dev_pool)
        say(f"restored pool={rt.engine.pool_size()} "
            f"in {time.perf_counter() - t:.3f}s")

        client = _Client(app, queue)
        sent: list[traffic.Arrivals] = []
        sent_ids: list[str] = []
        sent_t: list[np.ndarray] = []
        for burst in traffic.add_fields(
                traffic.warmup_bursts(cell.config, cell.traffic, seed), kind,
                cell.config, seed, traffic.STREAM_WARMUP_FIELDS):
            t = time.perf_counter()
            for pid, body in zip(burst.ids, burst.bodies()):
                client.publish(pid, body)
            sent.append(burst)
            sent_ids += burst.ids
            sent_t.append(np.full(len(burst), t))
            # An unanswered warm-up request is not waited for beyond the
            # limit: the reference counts it with the window's.
            done = await _settle(app, rt, client, queue, burst.ids,
                                 WARMUP_TIMEOUT_S)
            say(f"warm-up burst={len(burst)} answered={done} "
                f"took {time.perf_counter() - t:.3f}s")

        arr, = traffic.add_fields(
            [traffic.window_arrivals(cell.traffic, seed, seconds)], kind,
            cell.config, seed, traffic.STREAM_WINDOW_FIELDS)
        bodies = arr.bodies()
        ids = arr.ids
        due = arr.due
        n = len(arr)
        pub = np.zeros(n)
        trace_at = seconds - min(TRACE_S, seconds / 2) if traced else None
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
        marks: dict[str, dict] = {}
        s0 = _snapshot(app, rt, queue)

        # Every window starts from the same collector state: set-up's
        # garbage collected, so full collections in the window fall where
        # the window's own work puts them, the same from run to run.
        gc.collect()
        gc0 = _gc_counters(app)
        t0 = time.perf_counter()
        setup_s = t0 - t_process
        i = 0
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                client.publish(ids[i], bodies[i])
                pub[i] = now
                i += 1
            if trace_at is not None and now >= trace_at and "a" not in marks:
                marks["a"] = _snapshot(app, rt, queue)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                marks["trace_start"] = {"t": time.perf_counter()}
            if now >= seconds:
                break
            wake = [seconds, due[i] if i < n else seconds]
            if trace_at is not None and "a" not in marks:
                wake.append(trace_at)
            await asyncio.sleep(max(0.0, min(wake) - now))
        t_close = time.perf_counter()
        gc1 = _gc_counters(app)
        s1 = _snapshot(app, rt, queue)
        trace_s = None
        if traced:
            jax.profiler.stop_trace()
            trace_s = t_close - marks["trace_start"]["t"]
        got_in_window = [b for t, bs, _ in client.bursts if t <= t_close
                         for b in bs]
        sent.append(arr)
        sent_ids += arr.ids
        sent_t.append(t0 + pub)

        drained = await _settle(app, rt, client, queue, sent_ids, DRAIN_S)
        t_drained = time.perf_counter() - t_close
        async with rt._engine_lock:
            pool_end = rt.engine.pool_size()
            # A pool that left the device (a delegated team queue) holds
            # no one there: the reference's pool gap then fails the run.
            dev_pool = getattr(rt.engine, "_dev_pool", None)
            device_pool_end = 0 if dev_pool is None else int(np.asarray(
                jax.device_get(dev_pool["active"])).sum())
        off_device = _off_device(app, rt, devices[0].platform)
        memory = _memory_peak(devices)
        lag = pub - due
        log = {
            "pool_start": s0["pool"], "pool_close": s1["pool"],
            "pool_end": pool_end, "drained": drained,
            "drain_s": round(t_drained, 3),
            "compiles_in_window": s1["compiles"] - s0["compiles"],
            "windows": (s1["spans"].get("windows", 0)
                        - s0["spans"].get("windows", 0)),
            "generator_lag_ms_mean": float(lag.mean() * 1e3) if n else 0.0,
            "generator_lag_ms_max": float(lag.max() * 1e3) if n else 0.0,
            **_gc_log(gc0, gc1),
        }
        readings = {
            "chips": cell.chips,
            "slots_per_chip": cfg.engine.pool_capacity // max(
                1, cfg.engine.mesh_pool_axis),
            "device_kind": devices[0].device_kind,
            # Spans and counters of the untraced part of the window.
            "window": _delta(s0, marks.get("a", s1)),
        }
        if traced:
            readings["traced"] = _delta(marks["a"], s1)
            readings["trace_s"] = trace_s
    finally:
        if client is not None:
            client.close()
        await app.stop()

    if traced:
        devs, host, planes = trace_mod.load_profile(trace_dir, cell.chips)
        readings["trace"] = trace_mod.reduce_trace(devs, host, trace_s)
        readings["trace_planes"] = planes
        shutil.rmtree(trace_dir, ignore_errors=True)

    replies = client.replies()
    data = RunData(
        threshold=float(qcfg.rating_threshold),
        pool_ids=pool.ids, pool_rating=pool.rating,
        sent_ids=sent_ids,
        sent_rating=np.concatenate([a.rating for a in sent]),
        sent_t=np.concatenate(sent_t), replies=replies,
        pool_size_end=pool_end, device_pool_size_end=device_pool_end,
        off_device=off_device, team_size=int(qcfg.team_size),
        role_slots=tuple(qcfg.role_slots), pool_fields=pool.fields,
        sent_fields={k: np.concatenate([a.fields[k] for a in sent])
                     for k in arr.fields})

    # Latency of each window request to its matched reply, from its due
    # time; distinct matches answered inside the window.
    index = {pid: j for j, pid in enumerate(arr.ids)}
    match_ms = np.full(n, np.nan)
    bad = set()
    for rep in replies:
        j = index.get(rep.to)
        if j is None:
            continue
        if rep.status == "matched" and np.isnan(match_ms[j]):
            match_ms[j] = (rep.t - (t0 + due[j])) * 1e3
        elif rep.status != "queued":
            bad.add(rep.to)
    answered = {rep.to for rep in replies}
    failed = len(bad | {pid for pid in arr.ids if pid not in answered})
    ms = match_ms[~np.isnan(match_ms)]
    if len(ms):
        log["match_ms_p50_p90_p95_p99"] = [
            float(np.percentile(ms, q)) for q in (50, 90, 95, 99)]
    in_window = set()
    for raw in got_in_window:
        body = json.loads(raw)
        if body.get("status") == "matched":
            in_window.add((body.get("match") or {}).get("match_id"))
    return RunRecord(
        setup_s=setup_s, seconds=seconds, attempted=n, due=due,
        match_ms=match_ms,
        matches_in_window=len(in_window), failed=failed, data=data,
        readings=readings, memory_peak_bytes=memory, log=log)
