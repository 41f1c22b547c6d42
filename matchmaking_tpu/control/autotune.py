"""The online autotuner: telemetry → knob policy → audited knob move.

The closed loop ISSUE 13 builds on top of the PR 6 observability substrate:
one supervised asyncio tick loop per app (``AutotuneConfig.interval_s``,
the same shape as :class:`~matchmaking_tpu.control.controller.
PlacementController`'s) that each tick assembles a :class:`TuneView` from
what the service already exports — the telemetry ring's
``stage_total_p99_ms[q]`` / ``batch_fill[q]`` / ``idle_frac[q]`` series,
reset-hardened ``shed_total[q]`` deltas (utils/timeseries.Delta), the SLO
burn monitors — asks the pure :meth:`AutoTuner.plan` for at most ONE knob
move, applies it through the runtime's live-knob seams
(``Batcher.max_wait_ms``, ``_QueueRuntime.pipeline_depth`` /
``set_edf()``, ``AdmissionController.set_fraction()``), and records the
decision — driving signals, from→to, and the observed effect one tick
later — in a bounded audit ring served at ``/debug/autotune``.

Safety model (see AutotuneConfig): every move clamps to the declared safe
ranges; one move per tick so each effect is observable before the next
decision; the window-wait and EDF knobs are one-way ratchets (tighten /
switch on only — widening back is a latency-for-fill tradeoff the frontier
bench owns offline); the credit-fraction knob is refused while
``OverloadConfig.adaptive`` owns the fraction. ``plan`` is a pure function
of the view (no RNG, no clock reads), so a deterministic signal trajectory
replays a bit-identical decision trace — what the seeded acceptance test
(tests/test_autotune.py) and the scenario-matrix smoke pin.

``tuned_config()`` exports the converged knob values as a committed
capacity artifact (``configs/tuned/<scenario>.json`` — written by
``service.loadgen.scenario_matrix``): the "at this workload, run these
knobs" half of the capacity-planning story.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from collections import deque
from typing import Any

from matchmaking_tpu.config import AutotuneConfig

log = logging.getLogger(__name__)

#: Knob names (the audit vocabulary).
MAX_WAIT_MS = "max_wait_ms"
EDF = "edf"
PIPELINE_DEPTH = "pipeline_depth"
CREDIT_FRACTION = "credit_fraction"


@dataclasses.dataclass
class QueueTune:
    """One queue's signal row inside a :class:`TuneView` — everything the
    policy may read, nothing it may not (no clocks, no RNG)."""

    p99_ms: float = 0.0          # rolling stage-total p99 (telemetry ring)
    burning: bool = False        # any SLO monitor (latency/tier/quality)
    batch_fill: float = 0.0
    idle_frac: float = 1.0
    shed_rate: float = 0.0       # reset-hardened delta over the tick span
    has_deadlines: bool = False  # any pool-resident/cached deadline seen
    # Current knob values (the policy steps from these).
    max_wait_ms: float = 0.0
    edf: bool = False
    pipeline_depth: int = 1
    credit_fraction: float = 1.0
    # Capability flags (which knobs exist on this queue).
    pipelined: bool = False
    admission: bool = False
    adaptive: bool = False       # OverloadConfig.adaptive owns the fraction


@dataclasses.dataclass
class TuneView:
    queues: dict[str, QueueTune]


@dataclasses.dataclass
class KnobMove:
    """One planned move (the policy's output)."""

    queue: str
    knob: str
    src: Any
    dst: Any
    reason: str
    signals: dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class KnobDecision:
    """One audit record: what moved, on which signals, and what happened
    to the queue one tick later."""

    seq: int
    t: float
    tick: int
    queue: str
    knob: str
    src: Any
    dst: Any
    reason: str
    signals: dict[str, Any] = dataclasses.field(default_factory=dict)
    status: str = "applied"          # applied | failed
    #: Filled ONE TICK LATER: the same headline signals re-read, so the
    #: ring shows decision → observed effect pairs.
    effect: "dict[str, Any] | None" = None
    detail: str = ""

    def to_dict(self) -> dict[str, Any]:
        return {
            "seq": self.seq,
            "t": round(self.t, 3),
            "tick": self.tick,
            "queue": self.queue,
            "knob": self.knob,
            "from": self.src,
            "to": self.dst,
            "reason": self.reason,
            "signals": self.signals,
            "status": self.status,
            "effect": self.effect,
            "detail": self.detail,
        }

    def trace_row(self) -> tuple:
        """The wall-clock-free decision identity: what replay-identity
        assertions compare (t and effect are measurements, not
        decisions)."""
        return (self.seq, self.queue, self.knob, self.src, self.dst,
                self.reason, self.status)


class AutoTuner:
    """Owns the knob policy, the audit ring, and the tick loop."""

    def __init__(self, app, cfg: AutotuneConfig):
        self.app = app
        self.cfg = cfg
        self.decisions: deque[KnobDecision] = deque(
            maxlen=max(1, cfg.decision_ring))
        self._seq = 0
        self.ticks = 0
        self.moves = 0
        self.failures = 0
        self._task: "asyncio.Task | None" = None
        #: Last decision per queue (effect fill + settle gate), and the
        #: tick it landed on.
        self._last: dict[str, KnobDecision] = {}
        self._last_tick: dict[str, int] = {}
        #: Calm-streak counter per queue (relax gate).
        self._calm: dict[str, int] = {}
        target = cfg.target_p99_ms
        if target <= 0:
            target = app.cfg.observability.slo_target_ms
        #: The steering target; a zero here disables tighten/relax (no
        #: target to steer to — the tuner still serves /debug/autotune).
        self.target_p99_ms = float(target)

    # ---- signals -----------------------------------------------------------

    def signal_view(self, now: float) -> TuneView:
        """The policy's input, assembled from the telemetry ring (latest
        snapshot + reset-hardened shed deltas), the burn monitors, and the
        runtimes' live knob values. Read-only against the same unguarded
        surface /metrics scrapes."""
        ring = self.app.telemetry
        latest = ring.latest()
        vals: dict[str, float] = latest["values"] if latest else {}
        monitors = getattr(self.app, "_slo_monitors", {})
        span = max(2.0 * self.cfg.interval_s, 2.0)
        out: dict[str, QueueTune] = {}
        for name, rt in self.app._runtimes.items():
            burning = any(
                mon.burning for key, mon in monitors.items()
                if key == name or key.startswith(name + "@t")
                or key == name + "#quality")
            shed = ring.delta(f"shed_total[{name}]", span, now)
            admission = rt.admission is not None
            deadlines = bool(
                admission and (self.app.cfg.overload.default_deadline_ms > 0
                               or rt.engine.deadline_count() > 0))
            out[name] = QueueTune(
                p99_ms=float(vals.get(f"stage_total_p99_ms[{name}]", 0.0)),
                burning=burning,
                batch_fill=float(vals.get(f"batch_fill[{name}]", 0.0)),
                idle_frac=float(vals.get(f"idle_frac[{name}]", 1.0)),
                shed_rate=(round(shed[0] / shed[1], 4)
                           if shed is not None and shed[1] > 0 else 0.0),
                has_deadlines=deadlines,
                max_wait_ms=rt.batcher.max_wait_ms,
                edf=rt.edf_on,
                pipeline_depth=rt.pipeline_depth,
                credit_fraction=(rt.admission.credit_fraction
                                 if admission else 1.0),
                pipelined=rt._pipelined,
                admission=admission,
                adaptive=(admission and self.app.cfg.overload.adaptive),
            )
        return TuneView(queues=out)

    # ---- the policy (pure) -------------------------------------------------

    def plan(self, view: TuneView, tick: int) -> "KnobMove | None":
        """At most one knob move for this tick. Pure function of
        ``(view, tick, prior decisions)`` — no clocks, no RNG — so a
        deterministic signal trajectory replays bit-identically.

        Per queue (sorted; first eligible move wins): while the queue runs
        HOT (p99 above target, or burning), walk the tighten ladder —
        window wait down, EDF on, pipeline depth down, credit fraction
        down. While it stays CALM (p99 under half target, not burning) for
        ``settle_ticks`` straight ticks, walk the relax ladder — fraction
        back toward 1.0, then depth back up. Window wait and EDF never
        relax (ratchets — see the config docstring)."""
        cfg = self.cfg
        target = self.target_p99_ms
        if target <= 0:
            return None
        # Calm streaks advance for EVERY queue, every tick, BEFORE move
        # selection — a hot tick must reset a queue's streak even when
        # another queue's move ends the selection loop early, or a
        # relax move could fire on a queue that was hot mid-window.
        for name in sorted(view.queues):
            q = view.queues[name]
            calm = (not q.burning and q.p99_ms > 0
                    and q.p99_ms < target / 2.0)
            self._calm[name] = self._calm.get(name, 0) + 1 if calm else 0
        for name in sorted(view.queues):
            q = view.queues[name]
            # Effect-settling gate: a queue's last move must have had
            # settle_ticks ticks for its effect to reach the ring.
            if tick - self._last_tick.get(name, -10**9) < cfg.settle_ticks:
                continue
            hot = q.burning or (q.p99_ms > 0 and q.p99_ms > target)
            sig = {"p99_ms": round(q.p99_ms, 3), "burning": q.burning,
                   "batch_fill": round(q.batch_fill, 4),
                   "idle_frac": round(q.idle_frac, 4),
                   "shed_rate": q.shed_rate, "target_p99_ms": target}
            if hot:
                if q.max_wait_ms > cfg.max_wait_ms_min:
                    dst = max(cfg.max_wait_ms_min,
                              round(q.max_wait_ms * cfg.wait_step, 4))
                    return KnobMove(name, MAX_WAIT_MS, q.max_wait_ms, dst,
                                    "p99 above target: window wait is "
                                    "latency paid by every request", sig)
                if q.admission and q.has_deadlines and not q.edf:
                    return KnobMove(name, EDF, False, True,
                                    "p99 above target with deadlines "
                                    "present: cut windows earliest-"
                                    "deadline-first", sig)
                if q.pipelined and q.pipeline_depth > cfg.pipeline_depth_min:
                    return KnobMove(name, PIPELINE_DEPTH, q.pipeline_depth,
                                    q.pipeline_depth - 1,
                                    "p99 above target at the window-wait "
                                    "floor: in-flight windows are queued "
                                    "latency", sig)
                if (q.admission and not q.adaptive
                        and q.credit_fraction > cfg.credit_fraction_min):
                    dst = max(cfg.credit_fraction_min,
                              round(q.credit_fraction * cfg.fraction_step,
                                    4))
                    return KnobMove(name, CREDIT_FRACTION,
                                    q.credit_fraction, dst,
                                    "still hot with every latency knob "
                                    "floored: shed earlier, honestly", sig)
                continue
            if self._calm.get(name, 0) >= cfg.settle_ticks:
                if (q.admission and not q.adaptive
                        and q.credit_fraction < 1.0):
                    dst = min(1.0, round(
                        q.credit_fraction / cfg.fraction_step, 4))
                    return KnobMove(name, CREDIT_FRACTION,
                                    q.credit_fraction, dst,
                                    "calm: restore admission capacity "
                                    "first", sig)
                if (q.pipelined and q.pipeline_depth
                        < self._depth_cap(name)):
                    return KnobMove(name, PIPELINE_DEPTH, q.pipeline_depth,
                                    q.pipeline_depth + 1,
                                    "calm: restore pipeline throughput",
                                    sig)
        return None

    def _depth_cap(self, queue: str) -> int:
        """The relax ceiling for pipeline depth: the engine config's
        boot-time depth (the safe range's upper bound is what the operator
        sized buffers for)."""
        return self.app.cfg.engine.pipeline_depth

    # ---- one tick ----------------------------------------------------------

    def step(self, now: float | None = None,
             view: TuneView | None = None) -> "dict[str, Any] | None":
        """One tick: fill the previous decision's observed effect, plan,
        apply at most one move, audit. Public so tests and the bench
        matrix can drive deterministic tick sequences without the
        wall-clock loop; ``view`` injection is the simulation seam.
        Synchronous on purpose — every knob write is an event-loop-
        confined attribute store."""
        now = time.time() if now is None else now
        self.ticks += 1
        view = view if view is not None else self.signal_view(now)
        # Observed effect: the headline signals one tick after each
        # queue's latest decision.
        for name, decision in self._last.items():
            if decision.effect is None and name in view.queues:
                q = view.queues[name]
                decision.effect = {
                    "p99_ms": round(q.p99_ms, 3),
                    "burning": q.burning,
                    "batch_fill": round(q.batch_fill, 4),
                    "shed_rate": q.shed_rate,
                }
        move = self.plan(view, self.ticks)
        if move is None:
            return None
        self._seq += 1
        decision = KnobDecision(
            seq=self._seq, t=now, tick=self.ticks, queue=move.queue,
            knob=move.knob, src=move.src, dst=move.dst, reason=move.reason,
            signals=move.signals)
        try:
            applied = self._apply(move)
        except Exception as e:
            self.failures += 1
            decision.status = "failed"
            decision.detail = repr(e)
            log.exception("autotune move failed: %s", move)
        else:
            self.moves += 1
            decision.dst = applied
            self.app.events.append(
                "autotune_" + move.knob, move.queue,
                f"{move.src} -> {applied}: {move.reason}",
                component="control",
                refs={"decision": decision.seq, "knob": move.knob,
                      "src": str(move.src), "dst": str(applied)})
            self.app.metrics.counters.inc("autotune_moves")
            self.app.metrics.set_gauge(
                f"autotune_{move.knob}[{move.queue}]",
                float(applied) if not isinstance(applied, bool)
                else float(bool(applied)))
        self.decisions.append(decision)
        self._last[move.queue] = decision
        self._last_tick[move.queue] = self.ticks
        self._calm[move.queue] = 0
        return decision.to_dict()

    def _apply(self, move: KnobMove):
        """Write one knob through the runtime's live seam; returns the
        value actually applied (the seams clamp)."""
        rt = self.app._runtimes[move.queue]
        if move.knob == MAX_WAIT_MS:
            rt.batcher.max_wait_ms = float(move.dst)
            return rt.batcher.max_wait_ms
        if move.knob == EDF:
            rt.set_edf(bool(move.dst))
            return rt.edf_on
        if move.knob == PIPELINE_DEPTH:
            rt.pipeline_depth = max(1, int(move.dst))
            return rt.pipeline_depth
        if move.knob == CREDIT_FRACTION:
            return rt.admission.set_fraction(float(move.dst))
        raise ValueError(f"unknown knob {move.knob!r}")

    # ---- the loop ----------------------------------------------------------

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        if self._task is not None:
            task, self._task = self._task, None
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass
            except Exception:
                log.exception("autotune loop raised during stop")

    async def _loop(self) -> None:
        """Supervised: one bad tick must not end the tuner."""
        interval = self.cfg.interval_s
        while True:
            await asyncio.sleep(interval)
            try:
                self.step()
            except asyncio.CancelledError:
                raise
            except Exception:
                log.exception("autotune tick failed; retrying")
                self.app.metrics.counters.inc("autotune_tick_errors")

    # ---- observability / artifacts ----------------------------------------

    def knobs(self) -> dict[str, dict[str, Any]]:
        """Current live knob values per queue."""
        out: dict[str, dict[str, Any]] = {}
        for name, rt in sorted(self.app._runtimes.items()):
            out[name] = {
                MAX_WAIT_MS: rt.batcher.max_wait_ms,
                EDF: rt.edf_on,
                PIPELINE_DEPTH: rt.pipeline_depth,
                CREDIT_FRACTION: (rt.admission.credit_fraction
                                  if rt.admission is not None else None),
            }
        return out

    def decision_trace(self) -> list[tuple]:
        """Wall-clock-free decision identity rows (replay assertions)."""
        return [d.trace_row() for d in self.decisions]

    def snapshot(self, history: int = 0) -> dict[str, Any]:
        """JSON-ready state for /debug/autotune."""
        rows = [d.to_dict() for d in self.decisions]
        if history:
            rows = rows[-history:]
        return {
            "interval_s": self.cfg.interval_s,
            "target_p99_ms": self.target_p99_ms,
            "ticks": self.ticks,
            "moves": self.moves,
            "failures": self.failures,
            "ranges": {
                MAX_WAIT_MS: [self.cfg.max_wait_ms_min,
                              self.cfg.max_wait_ms_max],
                PIPELINE_DEPTH: [self.cfg.pipeline_depth_min,
                                 self.app.cfg.engine.pipeline_depth],
                CREDIT_FRACTION: [self.cfg.credit_fraction_min, 1.0],
            },
            "knobs": self.knobs(),
            "decisions": rows,
        }

    def tuned_config(self, scenario: str = "", seed: "int | None" = None,
                     ) -> dict[str, Any]:
        """The best-found-config artifact (``configs/tuned/<scenario>.json``
        — committed by the bench matrix): the converged knob values, the
        decision count that produced them, and the driving target."""
        return {
            "scenario": scenario,
            "seed": seed,
            "target_p99_ms": self.target_p99_ms,
            "generated_by": "loadgen.scenario_matrix (AutoTuner)",
            "moves": self.moves,
            "knobs": self.knobs(),
        }
