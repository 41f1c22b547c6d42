"""Multi-process ingress: an OTP-style supervisor over per-queue worker
processes.

A single service process is bound by one core: decode + middleware +
batcher all share the asyncio loop. The reference's scaling story is "add more consumers" — more
OS processes competing on the same AMQP broker. This module is that story
for the rebuild (SURVEY.md §2 "AMQP consumer", §5 "Failure detection"):

- **Queue partitioning**: the config's queues are split round-robin across
  N workers; each worker process runs the ordinary ``service.app serve``
  entrypoint against the SAME broker URL, serving only its partition
  (``MM_QUEUE_NAMES``). Queue-level sharding keeps each player pool owned
  by exactly one process — the single-writer-per-queue invariant that makes
  the engines race-free holds across the fleet, and AMQP routes by queue
  name so no extra router process is needed.
- **Device ownership**: exactly one worker (``device_worker``, default 0)
  inherits the configured engine backend; the rest are forced to the CPU
  engine. A TPU chip has one owning process; on multi-chip hosts, point
  more workers at devices via per-worker env overrides (``extra_env``).
- **Supervision**: one_for_one restarts with exponential backoff and a
  *time-windowed* restart intensity per worker (OTP's ``max_restarts``
  within ``max_seconds``): a crashing worker is restarted with backoff; a
  worker that crashes more than ``max_restarts`` times inside a sliding
  ``restart_window_s`` takes the whole supervisor down (fail fast).
  Crashes spaced out over a long healthy uptime fall out of the window and
  do NOT accumulate toward the budget.
  The engines themselves already revive from the host mirror inside a
  worker (service/app.py); this layer covers whole-process death, where the
  broker's unacked deliveries are redelivered to the restarted worker.
- **Observability**: worker i serves /metrics on ``metrics_port + i`` when
  a base port is configured.

Each worker is a REAL subprocess (own interpreter, own JAX runtime, own
GIL) spawned from the supervisor's config snapshot (``MM_CONFIG_JSON``) —
not a fork: JAX backends and asyncio loops do not survive forking.
"""

from __future__ import annotations

import atexit
import json
import logging
import os
import signal
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field

from matchmaking_tpu.config import Config

log = logging.getLogger(__name__)


def partition_queues(names: list[str], workers: int) -> list[list[str]]:
    """Round-robin queue names over ``workers`` partitions; empty partitions
    are dropped (more workers than queues just means fewer workers)."""
    if workers < 1:
        raise ValueError("workers must be >= 1")
    parts: list[list[str]] = [[] for _ in range(min(workers, len(names)))]
    for i, n in enumerate(names):
        parts[i % len(parts)].append(n)
    return parts


@dataclass
class _Worker:
    idx: int
    queue_names: list[str]
    env: dict[str, str]
    proc: subprocess.Popen | None = None
    restarts: int = 0
    #: monotonic timestamps of recent crashes — the sliding restart-intensity
    #: window (OTP max_restarts/max_seconds, not a lifetime budget).
    restart_times: list[float] = field(default_factory=list)
    #: monotonic deadline before which a restart must wait (backoff).
    next_start: float = 0.0
    backoff: float = 0.0
    stats: dict = field(default_factory=dict)


class WorkerSupervisor:
    """Spawn + supervise the worker fleet (see module docstring)."""

    def __init__(self, cfg: Config, workers: int, *,
                 device_worker: int = 0,
                 max_restarts: int = 5,
                 restart_window_s: float = 60.0,
                 backoff_initial_s: float = 0.5,
                 backoff_max_s: float = 30.0,
                 extra_env: dict[int, dict[str, str]] | None = None,
                 command: list[str] | None = None):
        """``command`` overrides the child argv (tests use stubs); the
        default runs the ordinary serve entrypoint in a fresh interpreter.
        ``extra_env[i]`` adds/overrides env for worker i (e.g. a device
        pinning for multi-chip hosts). The supervisor fails fast only when
        a worker crashes more than ``max_restarts`` times within a sliding
        ``restart_window_s`` (OTP restart intensity)."""
        self.cfg = cfg
        self.max_restarts = max_restarts
        self.restart_window_s = restart_window_s
        self.backoff_initial_s = backoff_initial_s
        self.backoff_max_s = backoff_max_s
        self._stopping = False
        self._cfg_path: str | None = None

        names = [q.name for q in cfg.queues]
        if not names:
            raise ValueError("config has no queues: a zero-worker "
                             "supervisor would idle forever")
        if len(set(names)) != len(names):
            raise ValueError("queue names must be unique for partitioning")
        parts = partition_queues(names, workers)
        if cfg.engine.backend != "cpu" and not (0 <= device_worker < len(parts)):
            log.warning(
                "device_worker=%d is outside the %d collapsed partitions: "
                "NO worker keeps engine backend %r — all run cpu",
                device_worker, len(parts), cfg.engine.backend)
        if command is None:
            command = [sys.executable, "-m", "matchmaking_tpu.service.app",
                       "serve"]
        self.command = command

        fd, self._cfg_path = tempfile.mkstemp(prefix="mm_cfg_",
                                              suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump(cfg.to_dict(), f)
        # stop() is the normal cleanup path; atexit covers abnormal exits
        # (exception before run()'s finally) so the snapshot never leaks.
        atexit.register(self._cleanup_snapshot)

        # The supervisor itself never imports JAX (this module needs only
        # the config tree), so it holds no device: the chip stays free for
        # the one worker that owns it.
        self.workers: list[_Worker] = []
        for i, qnames in enumerate(parts):
            env = dict(os.environ)
            env["MM_CONFIG_JSON"] = self._cfg_path
            env["MM_QUEUE_NAMES"] = ",".join(qnames)
            if i != device_worker and cfg.engine.backend != "cpu":
                # One process per chip: only device_worker may bring up
                # the accelerator backend. A CPU-engine worker that still
                # initialised JAX's default (TPU) backend would fail, or
                # wait on the chip its sibling holds.
                env["MM_ENGINE_BACKEND"] = "cpu"
                env["JAX_PLATFORMS"] = "cpu"
            if cfg.metrics_port:
                env["MM_METRICS_PORT"] = str(cfg.metrics_port + i)
            env.update((extra_env or {}).get(i, {}))
            self.workers.append(_Worker(idx=i, queue_names=qnames, env=env))

    # ---- lifecycle ---------------------------------------------------------

    def _spawn(self, w: _Worker) -> None:
        w.proc = subprocess.Popen(self.command, env=w.env)
        log.info("worker %d up (pid %d, queues %s)", w.idx, w.proc.pid,
                 ",".join(w.queue_names))

    def start(self) -> None:
        for w in self.workers:
            self._spawn(w)

    def poll(self) -> None:
        """One supervision pass: restart dead workers whose backoff expired;
        raise RuntimeError when a worker exceeds the restart intensity
        (``max_restarts`` crashes within ``restart_window_s``)."""
        now = time.monotonic()
        for w in self.workers:
            if w.proc is not None and w.proc.poll() is None:
                continue
            rc = w.proc.returncode if w.proc is not None else None
            if w.proc is not None:
                w.proc = None
                w.restarts += 1
                w.restart_times.append(now)
                w.restart_times = [t for t in w.restart_times
                                   if now - t <= self.restart_window_s]
                recent = len(w.restart_times)
                w.backoff = min(self.backoff_max_s,
                                self.backoff_initial_s * (2 ** (recent - 1)))
                w.next_start = now + w.backoff
                log.warning(
                    "worker %d exited rc=%s; restart %d in window/%d "
                    "(lifetime %d) in %.1fs", w.idx, rc, recent,
                    self.max_restarts, w.restarts, w.backoff)
                # OTP restart intensity: fail fast only on crashes
                # clustered inside the window, not a lifetime total.
                if recent > self.max_restarts:
                    raise RuntimeError(
                        f"worker {w.idx} exceeded {self.max_restarts} "
                        f"restarts within {self.restart_window_s:.0f}s")
            if now >= w.next_start:
                self._spawn(w)

    def run(self, stop_signals=(signal.SIGTERM, signal.SIGINT),
            poll_interval_s: float = 0.2) -> None:
        """Blocking supervise-until-signalled loop (the CLI entrypoint)."""
        stop = {"flag": False}

        def _handler(signum, frame):
            stop["flag"] = True

        old = {s: signal.signal(s, _handler) for s in stop_signals}
        try:
            self.start()
            while not stop["flag"]:
                self.poll()
                time.sleep(poll_interval_s)
        finally:
            for s, h in old.items():
                signal.signal(s, h)
            self.stop()

    def stop(self, term_timeout_s: float = 10.0) -> None:
        """SIGTERM everyone, wait, SIGKILL stragglers, clean the snapshot."""
        self._stopping = True
        live = [w for w in self.workers if w.proc is not None
                and w.proc.poll() is None]
        for w in live:
            try:
                w.proc.terminate()
            except OSError:  # pragma: no cover - already-dead race
                pass
        deadline = time.monotonic() + term_timeout_s
        for w in live:
            try:
                w.proc.wait(timeout=max(0.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                log.error("worker %d ignored SIGTERM; killing", w.idx)
                w.proc.kill()
                w.proc.wait()
        self._cleanup_snapshot()

    def _cleanup_snapshot(self) -> None:
        atexit.unregister(self._cleanup_snapshot)
        if self._cfg_path:
            try:
                os.unlink(self._cfg_path)
            except OSError:
                pass
            self._cfg_path = None

    def alive_count(self) -> int:
        return sum(1 for w in self.workers
                   if w.proc is not None and w.proc.poll() is None)


def main(argv: list[str] | None = None) -> None:
    import argparse

    p = argparse.ArgumentParser(
        description="Multi-process matchmaking service: partition the "
                    "config's queues over N supervised worker processes "
                    "sharing one AMQP broker.")
    p.add_argument("--workers", type=int, default=max(1, os.cpu_count() or 1))
    p.add_argument("--device-worker", type=int, default=0,
                   help="worker index that keeps the configured engine "
                        "backend (others run the CPU engine)")
    p.add_argument("--max-restarts", type=int, default=5)
    p.add_argument("--restart-window-s", type=float, default=60.0,
                   help="sliding window for the restart intensity: fail "
                        "fast only on > max-restarts crashes within it")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    cfg = Config.from_env()
    sup = WorkerSupervisor(cfg, args.workers,
                           device_worker=args.device_worker,
                           max_restarts=args.max_restarts,
                           restart_window_s=args.restart_window_s)
    log.info("supervising %d workers over %d queues", len(sup.workers),
             len(cfg.queues))
    sup.run()


if __name__ == "__main__":
    main()
