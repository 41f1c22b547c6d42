"""The program's spans on the profiler's clock (utils/trace.py ``stage`` and
``ProcessHooks``) and the names of its jitted steps.

The capture test drives the served path (broker → batcher → TpuEngine on
the CPU backend → replies) for a short window under ``jax.profiler`` and
reads the ``.xplane.pb`` back: the host stages' ``mm.*`` spans are there,
nest as the engine and the runtime open them, carry their window's token,
and hold the flight recorder's marks of that window (one clock).
"""

import asyncio
import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from benchmark.trace import load_profile
from matchmaking_tpu.config import Config
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.broker import Properties
from matchmaking_tpu.utils.trace import ProcessHooks, stage

QUEUE = "mm.trace"
REPLIES = "mm.trace.replies"
#: How far a mark may fall outside the span that holds it (s).
CLOCK_SLACK_S = 0.5e-3


def _config() -> Config:
    return Config.from_dict({
        "queues": [{"name": QUEUE, "rating_threshold": 100.0}],
        "engine": {"backend": "tpu", "pool_capacity": 2048,
                   "pool_block": 512, "batch_buckets": [16, 128]},
    })


class _Replies:
    def __init__(self, app):
        self.app = app
        self.ids: set[str] = set()
        app.broker.declare_queue(REPLIES)
        self.tag = app.broker.basic_consume(REPLIES, self._one,
                                            prefetch=1 << 20)

    async def _one(self, delivery) -> None:
        self.ids.add(delivery.properties.correlation_id)
        self.app.broker.ack(self.tag, delivery.delivery_tag)

    def publish(self, pid: str, rating: float) -> None:
        self.app.broker.publish(
            QUEUE, f'{{"id":"{pid}","rating":{rating:.2f}}}'.encode(),
            Properties(reply_to=REPLIES, correlation_id=pid))

    async def wait(self, ids, timeout: float = 60.0) -> None:
        deadline = time.perf_counter() + timeout
        while not set(ids) <= self.ids:
            assert time.perf_counter() < deadline, "replies did not land"
            await asyncio.sleep(0.005)


async def _serve_under_profiler(log_dir: str):
    """Warm the served path up, then capture a burst of searchers. Returns
    the app's settled traces of the captured burst."""
    app = MatchmakingApp(_config())
    await app.start()
    try:
        client = _Replies(app)
        warm = [f"w{i}" for i in range(40)]
        for i, pid in enumerate(warm):
            client.publish(pid, 1500.0 + (i // 2))
        await client.wait(warm)
        burst = [f"p{i}" for i in range(60)]
        jax.profiler.start_trace(log_dir)
        try:
            for i, pid in enumerate(burst):
                client.publish(pid, 2000.0 + (i // 2))
                if i % 10 == 9:
                    await asyncio.sleep(0.01)
            await client.wait(burst)
        finally:
            jax.profiler.stop_trace()
        traces = [t for t in app.recorder._recent[QUEUE]
                  if t.correlation_id in set(burst)]
    finally:
        await app.stop()
    return traces


def _host_events_with_args(log_dir: str):
    """(name, start, end, args) of every ``mm.*`` host event, on the wall
    clock: the capture stores times relative to its profile_start_time."""
    path = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    data = jax.profiler.ProfileData.from_file(path)
    t0 = None
    for plane in data.planes:
        for name, value in plane.stats:
            if name == "profile_start_time":
                t0 = value * 1e-9
    assert t0 is not None
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mm."):
                    out.append((ev.name, t0 + ev.start_ns * 1e-9,
                                t0 + (ev.start_ns + ev.duration_ns) * 1e-9,
                                dict(ev.stats), line.name))
    return out


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _outside(t: float, span) -> float:
    """How far the instant ``t`` lies outside ``span`` (0 inside it)."""
    return max(0.0, span[1] - t, t - span[2])


def test_served_path_spans_nest_share_a_token_and_hold_the_marks(tmp_path):
    traces = asyncio.run(_serve_under_profiler(str(tmp_path)))
    _devices, host, _planes = load_profile(str(tmp_path), 0)
    names = {ev.name for ev in host}
    for span in ("mm.ingest", "mm.flush", "mm.dispatch", "mm.dedupe",
                 "mm.alloc", "mm.pack", "mm.h2d", "mm.launch",
                 "mm.quality_accum", "mm.collect", "mm.finalize",
                 "mm.egress", "mm.encode", "mm.publish", "mm.settle",
                 "mm.loop_idle"):
        assert span in names, (span, sorted(n for n in names
                                            if n.startswith("mm.")))

    events = _host_events_with_args(str(tmp_path))
    by_name: dict[str, list] = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)

    def parent(ev, name):
        """The ``name`` span on the same thread that holds ``ev``."""
        found = [p for p in by_name[name] if p[4] == ev[4] and _inside(ev, p)]
        assert found, (ev, name)
        return found[0]

    # Nesting as the engine and the runtime open them, one token a window.
    for child in ("mm.dedupe", "mm.alloc", "mm.pack", "mm.h2d",
                  "mm.launch", "mm.quality_accum"):
        for ev in by_name[child]:
            assert parent(ev, "mm.dispatch")[3]["token"] == ev[3]["token"]
    for ev in by_name["mm.finalize"]:
        parent(ev, "mm.collect")
    for child in ("mm.encode", "mm.publish", "mm.settle"):
        for ev in by_name[child]:
            assert parent(ev, "mm.egress")[3]["token"] == ev[3]["token"]

    # A matched request of the burst: its window's marks fall inside that
    # window's spans, found by the token of the dispatch that holds the
    # dispatch mark.
    matched = [t for t in traces if t.status == "matched"]
    assert matched
    checked = 0
    for tr in matched:
        marks = dict(tr.marks)
        if not {"dispatch", "h2d", "device_step", "collect"} <= set(marks):
            continue
        holding = min(by_name["mm.dispatch"],
                      key=lambda d: _outside(marks["dispatch"], d))
        assert _outside(marks["dispatch"], holding) <= CLOCK_SLACK_S
        token = holding[3]["token"]
        for mark, span in (("h2d", "mm.h2d"), ("device_step", "mm.launch"),
                           ("collect", "mm.finalize")):
            spans = [s for s in by_name[span] if s[3].get("token") == token]
            assert spans, (span, token)
            assert min(_outside(marks[mark], s) for s in spans) \
                <= CLOCK_SLACK_S, (mark, marks[mark], spans)
        assert any(e[3].get("token") == token for e in by_name["mm.egress"])
        checked += 1
    assert checked > 0


def test_stage_with_the_profiler_off_only_adds_to_its_counter():
    sink = {"pack_s": 0.0, "jit_s": 0.0}
    with stage("pack", sink, token=7):
        time.sleep(0.002)
    with stage("launch", sink, "jit_s", token=7):
        pass
    with stage("quality_accum", token=7):
        pass
    assert set(sink) == {"pack_s", "jit_s"}
    assert 0.002 <= sink["pack_s"] < 1.0
    assert 0.0 <= sink["jit_s"] < sink["pack_s"]


def test_stage_counts_the_time_of_a_span_that_raises():
    sink = {"collect_s": 0.0}
    with pytest.raises(ValueError):
        with stage("collect", sink):
            time.sleep(0.001)
            raise ValueError("device lost")
    assert sink["collect_s"] >= 0.001


def _hooked(loop) -> bool:
    return ProcessHooks.installed(loop)


async def _two_apps_come_and_go():
    loop = asyncio.get_running_loop()
    cfg = Config.from_dict({"queues": [{"name": QUEUE}],
                            "engine": {"backend": "cpu"}})
    a, b = MatchmakingApp(cfg), MatchmakingApp(cfg)
    assert not _hooked(loop)
    await a.start()
    await b.start()
    assert _hooked(loop)
    gc.collect()
    await asyncio.sleep(0.02)
    for app in (a, b):
        c = app.metrics.counters
        assert c.get("gc_pauses") >= 1 and c.get("gc_pauses_gen2") >= 1
        assert c.get("gc_s") >= c.get("gc_s_gen2") > 0.0
        assert c.get("loop_idle_s") > 0.0
        assert "loop_idle_s" in app.metrics.report()["counters"]
    await a.stop()
    assert _hooked(loop)
    await b.stop()
    assert not _hooked(loop)
    assert (ProcessHooks._on_gc in gc.callbacks) == bool(ProcessHooks._sinks)
    idle = b.metrics.counters.get("loop_idle_s")
    await asyncio.sleep(0.02)
    assert b.metrics.counters.get("loop_idle_s") == idle


def test_stall_hooks_are_in_place_while_an_app_runs_and_gone_after():
    asyncio.run(_two_apps_come_and_go())


async def _lag_under_a_stall():
    app = MatchmakingApp(_config())
    await app.start()
    try:
        def lag():
            snap = app.attribution.snapshot(QUEUE)["queues"].get(QUEUE, {})
            cat = snap.get("categories", {}).get("loop_lag")
            return (cat["total_s"], cat["traces"]) if cat else (0.0, 0)

        await asyncio.sleep(0.1)
        s0, n0 = lag()
        for _ in range(5):
            time.sleep(0.05)  # a blocking call on the event loop
            await asyncio.sleep(0.02)
        s1, n1 = lag()
    finally:
        await app.stop()
    return s0, n0, s1, n1


def test_loop_lag_grows_when_a_blocking_call_stalls_the_loop():
    s0, n0, s1, n1 = asyncio.run(_lag_under_a_stall())
    assert n1 > n0
    # Each stall delays a collector wake-up by most of its 50 ms.
    assert s1 - s0 >= 5 * 0.03


def _jitted(obj):
    return {attr: v for attr, v in vars(obj).items()
            if callable(v) and hasattr(v, "lower") and hasattr(v, "trace")}


def test_no_step_of_a_kernel_set_traces_as_unknown_or_lambda():
    from matchmaking_tpu.engine.kernels import KernelSet, QualityAccumKernel
    from matchmaking_tpu.engine.role_kernels import (
        role_kernel_set,
        sharded_role_kernel_set,
    )
    from matchmaking_tpu.engine.sharded import sharded_kernel_set
    from matchmaking_tpu.engine.teams import (
        sharded_team_kernel_set,
        team_kernel_set,
    )

    flat = KernelSet(capacity=256, top_k=4, pool_block=64, glicko2=False,
                     widen_per_sec=0.0, max_threshold=400.0)
    sets = [
        flat,
        KernelSet(capacity=256, top_k=4, pool_block=64, glicko2=False,
                  widen_per_sec=0.0, max_threshold=400.0, bucketed=True),
        sharded_kernel_set(256, 4, 32, False, 0.0, 400.0, n_shards=2,
                           ring=False),
        team_kernel_set(64, 2, 0.0, 400.0, max_matches=8),
        sharded_team_kernel_set(64, 2, 0.0, 400.0, n_shards=2,
                                max_matches=8, frontier_k=4),
        role_kernel_set(64, 2, ("tank", "dps"), 0.0, 400.0, max_matches=8),
        sharded_role_kernel_set(64, 2, ("tank", "dps"), 0.0, 400.0,
                                n_shards=2, max_matches=8, frontier_k=4),
        QualityAccumKernel(capacity=256, widen_per_sec=0.0,
                           max_threshold=400.0,
                           rating_edges=(0.0, 1500.0, 3000.0), n_quality=4,
                           wait_edges=(0.0, 1.0, 10.0)),
    ]
    seen = 0
    for ks in sets:
        for attr, fn in _jitted(ks).items():
            seen += 1
            name = fn.__name__
            assert name not in ("<lambda>", "unknown"), (type(ks), attr)
            assert not name.startswith("_"), (type(ks), attr, name)
            assert name.endswith(attr) or attr == "accum", (attr, name)
    assert seen >= 25
    # The compiled module carries the name: the former lambda and partial.
    from matchmaking_tpu.core.pool import PlayerPool

    pool = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
            for k, v in PlayerPool.empty_device_arrays(256).items()}
    packed = jax.ShapeDtypeStruct((9, 16), np.float32)
    for attr in ("admit_packed", "search_step_packed_nofilter"):
        text = getattr(flat, attr).lower(pool, packed).as_text()
        assert f"jit_{attr}" in text
        assert "jit__lambda_" not in text and "jit__unknown" not in text
