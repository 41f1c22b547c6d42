"""Multi-process ingress supervisor (service/multiproc.py): queue
partitioning, config snapshot/filter plumbing, one_for_one restart
semantics with backoff + budget, and a real two-worker serve boot."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from matchmaking_tpu.config import Config, EngineConfig, QueueConfig
from matchmaking_tpu.service.multiproc import WorkerSupervisor, partition_queues


def test_partition_round_robin():
    assert partition_queues(["a", "b", "c", "d", "e"], 2) == [
        ["a", "c", "e"], ["b", "d"]]
    assert partition_queues(["a"], 4) == [["a"]]        # extra workers drop
    assert partition_queues(["a", "b"], 2) == [["a"], ["b"]]
    with pytest.raises(ValueError):
        partition_queues(["a"], 0)


def test_config_json_and_queue_filter(tmp_path, monkeypatch):
    cfg = Config(queues=(QueueConfig(name="ranked", rating_threshold=80.0),
                         QueueConfig(name="casual"),
                         QueueConfig(name="teams", team_size=5)),
                 engine=EngineConfig(backend="tpu", pool_capacity=512),
                 metrics_port=9100)
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(cfg.to_dict()))
    for k in list(os.environ):
        if k.startswith("MM_"):
            monkeypatch.delenv(k)
    monkeypatch.setenv("MM_CONFIG_JSON", str(path))
    monkeypatch.setenv("MM_QUEUE_NAMES", "ranked,teams")
    monkeypatch.setenv("MM_ENGINE_BACKEND", "cpu")   # env wins over the file
    loaded = Config.from_env()
    assert [q.name for q in loaded.queues] == ["ranked", "teams"]
    assert loaded.queues[0].rating_threshold == 80.0
    assert loaded.queues[1].team_size == 5
    assert loaded.engine.backend == "cpu"            # override applied
    assert loaded.engine.pool_capacity == 512        # file value kept
    assert loaded.metrics_port == 9100
    monkeypatch.setenv("MM_QUEUE_NAMES", "ranked,nope")
    with pytest.raises(KeyError):
        Config.from_env()


def _cfg(n_queues=4, backend="cpu", **kw):
    return Config(queues=tuple(QueueConfig(name=f"q{i}")
                               for i in range(n_queues)),
                  engine=EngineConfig(backend=backend), **kw)


def _fast_children(sup):
    """Pin every worker to the CPU backend: a child that reaches for a
    chip would fail (or wait on it) where none is free. (The real
    serve-boot test does the same.)"""
    for w in sup.workers:
        w.env["JAX_PLATFORMS"] = "cpu"
    return sup


def test_supervisor_parent_never_imports_jax():
    """One process per chip: the supervisor parent must not bring up a JAX
    backend, or it would hold the chip its device worker needs."""
    code = (
        "import sys\n"
        "from matchmaking_tpu.config import Config, EngineConfig, QueueConfig\n"
        "from matchmaking_tpu.service.multiproc import WorkerSupervisor\n"
        "cfg = Config(queues=(QueueConfig(name='a'), QueueConfig(name='b')),\n"
        "             engine=EngineConfig(backend='tpu'))\n"
        "sup = WorkerSupervisor(cfg, 2, command=['true'])\n"
        "sup.stop()\n"
        "assert 'jax' not in sys.modules, 'supervisor imported jax'\n"
        "print('NOJAX')\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=120,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "NOJAX" in proc.stdout


def test_supervisor_env_partitioning():
    sup = WorkerSupervisor(_cfg(5, backend="tpu", metrics_port=9200), 2,
                           command=["true"])
    try:
        assert len(sup.workers) == 2
        w0, w1 = sup.workers
        assert w0.env["MM_QUEUE_NAMES"] == "q0,q2,q4"
        assert w1.env["MM_QUEUE_NAMES"] == "q1,q3"
        # Device ownership: only worker 0 keeps the tpu backend.
        assert "MM_ENGINE_BACKEND" not in w0.env
        assert w1.env["MM_ENGINE_BACKEND"] == "cpu"
        assert w1.env["JAX_PLATFORMS"] == "cpu"
        assert w0.env["MM_METRICS_PORT"] == "9200"
        assert w1.env["MM_METRICS_PORT"] == "9201"
        # The snapshot is a loadable full config tree.
        snap = json.loads(open(w0.env["MM_CONFIG_JSON"]).read())
        assert [q["name"] for q in snap["queues"]] == [f"q{i}"
                                                       for i in range(5)]
    finally:
        sup.stop()


def test_supervisor_restarts_with_budget():
    """A crash-looping worker is restarted with growing backoff, then the
    supervisor fails fast once the restart intensity is exceeded (OTP
    max_restarts within max_seconds; here all crashes land in one window)."""
    sup = _fast_children(WorkerSupervisor(
        _cfg(1), 1, max_restarts=2, backoff_initial_s=0.01,
        command=[sys.executable, "-c", "import sys; sys.exit(3)"]))
    try:
        sup.start()
        deadline = time.monotonic() + 10.0
        with pytest.raises(RuntimeError, match="exceeded 2 restarts"):
            while time.monotonic() < deadline:
                sup.poll()
                time.sleep(0.02)
        w = sup.workers[0]
        assert w.restarts == 3                     # 2 budgeted + the fatal one
        assert w.backoff >= 0.02                   # exponential growth
    finally:
        sup.stop()


def test_restart_window_forgives_spaced_crashes():
    """Crashes spaced wider than the sliding window never trip the budget:
    the worker keeps being revived even after far more than max_restarts
    lifetime crashes (the OTP intensity semantics, not a lifetime cap)."""
    sup = _fast_children(WorkerSupervisor(
        _cfg(1), 1, max_restarts=1, restart_window_s=0.05,
        backoff_initial_s=0.1,   # backoff > window => crashes never cluster
        command=[sys.executable, "-c", "import sys; sys.exit(3)"]))
    try:
        sup.start()
        deadline = time.monotonic() + 1.5
        while time.monotonic() < deadline:
            sup.poll()                             # must never raise
            time.sleep(0.02)
        assert sup.workers[0].restarts > 1         # lifetime total exceeded
    finally:
        sup.stop()


def test_empty_queue_config_rejected():
    with pytest.raises(ValueError, match="no queues"):
        WorkerSupervisor(Config(queues=(), engine=EngineConfig()), 2,
                         command=["true"])


def test_device_worker_out_of_range_warns(caplog):
    """device_worker beyond the collapsed partition list means NO process
    keeps the accelerator backend — the supervisor must say so."""
    import logging
    with caplog.at_level(logging.WARNING,
                         logger="matchmaking_tpu.service.multiproc"):
        sup = WorkerSupervisor(_cfg(2, backend="tpu"), 2, device_worker=7,
                               command=["true"])
        sup.stop()
    assert any("device_worker=7" in r.message for r in caplog.records)
    assert all(w.env.get("MM_ENGINE_BACKEND") == "cpu" for w in sup.workers)


def test_supervisor_healthy_worker_not_restarted():
    sup = WorkerSupervisor(
        _cfg(1), 1,
        command=[sys.executable, "-c", "import time; time.sleep(60)"])
    try:
        sup.start()
        for _ in range(5):
            sup.poll()
            time.sleep(0.02)
        assert sup.workers[0].restarts == 0
        assert sup.alive_count() == 1
        pid = sup.workers[0].proc.pid
        sup.poll()
        assert sup.workers[0].proc.pid == pid      # same process, no churn
    finally:
        sup.stop()
    assert sup.alive_count() == 0


def test_supervisor_stop_kills_sigterm_ignorers():
    sup = WorkerSupervisor(
        _cfg(1), 1,
        command=[sys.executable, "-c",
                 "import signal, time; signal.signal(signal.SIGTERM, "
                 "signal.SIG_IGN); time.sleep(60)"])
    sup.start()
    time.sleep(0.3)                                # let the handler install
    t0 = time.monotonic()
    sup.stop(term_timeout_s=0.5)
    assert time.monotonic() - t0 < 5.0
    assert sup.alive_count() == 0


def test_two_real_serve_workers_boot_and_stop():
    """End-to-end: two REAL serve processes (fresh interpreters, cpu
    engines, in-proc broker — no external RabbitMQ in this harness) boot
    from the snapshot, partition the queues, and exit 0 on SIGTERM."""
    cfg = Config(queues=(QueueConfig(name="ranked"), QueueConfig(name="casual")),
                 engine=EngineConfig(backend="cpu"))
    sup = WorkerSupervisor(cfg, 2)
    for w in sup.workers:
        w.env["JAX_PLATFORMS"] = "cpu"
        w.env["MM_BROKER_URL"] = "inproc://"
    try:
        sup.start()
        # Give both interpreters time to import jax and reach serve()'s
        # wait loop; any boot crash shows up as a restart.
        t0 = time.monotonic()
        while time.monotonic() - t0 < 8.0:
            sup.poll()
            assert all(w.restarts == 0 for w in sup.workers), \
                "a serve worker crashed at boot"
            time.sleep(0.2)
        assert sup.alive_count() == 2
        procs = [w.proc for w in sup.workers]
        for p in procs:
            p.terminate()
        for p in procs:
            assert p.wait(timeout=30.0) == 0       # clean SIGTERM shutdown
    finally:
        sup.stop()


def test_loadgen_worker_under_supervisor(tmp_path):
    """The multiproc bench contract: a supervised self-driving loadgen
    worker (service/loadgen.py) boots from the config snapshot, offers its
    Poisson load to its own in-proc broker, writes a JSON result, and
    exits 0."""
    out = tmp_path / "lg.json"
    cfg = Config(queues=(QueueConfig(name="lg0", send_queued_ack=False),),
                 engine=EngineConfig(backend="cpu", pool_capacity=1024))
    sup = _fast_children(WorkerSupervisor(
        cfg, 1,
        command=[sys.executable, "-m", "matchmaking_tpu.service.loadgen"],
        extra_env={0: {"MM_LOADGEN_RATE": "3000",
                       "MM_LOADGEN_SECONDS": "1.0",
                       "MM_LOADGEN_OUT": str(out)}}))
    sup.start()
    try:
        assert sup.workers[0].proc.wait(timeout=60) == 0
    finally:
        sup.stop()
    r = json.loads(out.read_text())
    assert r["queue"] == "lg0"
    assert r["sent"] > 1000
    # Paired consecutive ratings: nearly everything matches immediately.
    assert r["players_matched"] >= 0.9 * r["sent"]
