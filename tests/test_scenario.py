"""Population-model load scenarios (`scenario` marker — ISSUE 13).

- Transcript determinism: same (seed, scenario, scales) → bit-identical
  arrival transcript (times, ratings, cohorts, tiers, deadlines, retry
  flags) AND an identical incident→ChaosConfig script, across builds.
- Legacy reduction: scenario="steady" drives ``offered_load()`` into the
  exact publish sequence — bodies, correlation ids, headers — the
  pre-scenario loadgen produces, byte for byte.
- Curve shapes: flash multiplies the peak window's arrival density, ramps
  ramp, cohort mixtures land their rating means and QoS columns.
- Client retry-on-shed: flagged cohort members re-publish once after a
  shed, accounted per cohort.
- The 2-cell seeded mini-matrix smoke (scripts/check.sh runs this suite
  by marker): ``loadgen.scenario_matrix`` in-process —
  artifact schema, autotuner audit ring non-empty on the overloaded cell,
  a failed cell failing the run, and replay identity of the scenario digests
  across two matrix runs.
"""

import asyncio
import json

import numpy as np
import pytest

from matchmaking_tpu.config import (
    BatcherConfig,
    Config,
    EngineConfig,
    ObservabilityConfig,
    OverloadConfig,
    QueueConfig,
)
from matchmaking_tpu.scenario import (
    Cohort,
    Incident,
    Scenario,
    Segment,
    load_scenario,
    scenario_names,
)
from matchmaking_tpu.service.app import MatchmakingApp
from matchmaking_tpu.service.loadgen import offered_load, scenario_matrix

pytestmark = pytest.mark.scenario

EXPECTED_SCENARIOS = {"steady", "diurnal", "flash-crowd", "skewed-ladder",
                      "retry-storm", "mixed-tier-peak"}


def _small_cfg(**over) -> Config:
    return Config(
        queues=(QueueConfig(rating_threshold=100.0,
                            send_queued_ack=False),),
        engine=EngineConfig(backend="cpu", pool_capacity=4096),
        batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.0),
        **over)


# ---- determinism -----------------------------------------------------------

def test_committed_library_loads_and_transcripts_replay_bit_identical():
    """Every committed scenario builds, and two builds with the same
    (seed, scenario, scales) produce EQUAL transcripts — every
    per-arrival fact plus the incident script — and equal digests."""
    names = scenario_names()
    assert EXPECTED_SCENARIOS <= set(names), names
    for name in names:
        s = load_scenario(name)
        a = s.build_arrivals(21, rate_scale=0.8, time_scale=0.5)
        b = s.build_arrivals(21, rate_scale=0.8, time_scale=0.5)
        assert len(a) > 50, name
        assert a.transcript() == b.transcript(), name
        assert a.digest() == b.digest(), name
        # A different seed moves the transcript (no degenerate constants).
        c = s.build_arrivals(22, rate_scale=0.8, time_scale=0.5)
        assert c.digest() != a.digest(), name


def test_steady_scenario_reduces_to_legacy_offered_load_byte_for_byte():
    """The satellite pin: scenario="steady" (time-scaled to the legacy
    call's duration) publishes the EXACT request sequence the legacy
    ``offered_load(rate=400, duration=2)`` publishes — same bodies, same
    correlation ids, same headers, same order."""
    sent: dict[str, list] = {}

    async def run(mode: str) -> None:
        app = MatchmakingApp(_small_cfg())
        log: list = []
        orig = app.broker.publish

        def recording_publish(queue, body, props=None):
            if queue == "matchmaking.search":
                log.append((bytes(body), props.correlation_id,
                            dict(props.headers or {})))
            return orig(queue, body, props)

        app.broker.publish = recording_publish
        await app.start()
        try:
            if mode == "legacy":
                await offered_load(app, "matchmaking.search", rate=400.0,
                                   duration=2.0, seed=5)
            else:
                s = load_scenario("steady")
                assert s.is_trivial()
                # steady.json is 4 s @ 400/s; half time = the legacy call.
                await offered_load(app, "matchmaking.search", rate=0.0,
                                   duration=0.0, seed=5, scenario=s,
                                   time_scale=0.5)
        finally:
            await app.stop()
        sent[mode] = log

    asyncio.run(run("legacy"))
    asyncio.run(run("steady"))
    assert len(sent["legacy"]) > 300
    assert sent["legacy"] == sent["steady"]


def test_trivial_build_matches_legacy_rng_order_exactly():
    """The RNG-order contract behind the byte identity, pinned at the
    array level: ratings (paired repeat) first, then exponential gaps."""
    s = load_scenario("steady")
    a = s.build_arrivals(7)
    rate, dur = s.segments[0].rate, s.segments[0].duration_s
    rng = np.random.default_rng(7)
    n_max = int(rate * dur * 2) + 16
    ratings = np.repeat(rng.normal(1500.0, 300.0, size=n_max // 2 + 1), 2)
    sched = np.cumsum(rng.exponential(1.0 / rate, size=n_max))
    n = int((sched <= dur).sum())
    assert np.array_equal(a.t, sched[:n])
    assert np.array_equal(a.rating, ratings[:n])


# ---- curve + population shapes ---------------------------------------------

def test_flash_crowd_curve_multiplies_peak_density():
    s = load_scenario("flash-crowd")
    a = s.build_arrivals(3)
    base = ((a.t >= 0.0) & (a.t < 2.0)).sum() / 2.0
    peak = ((a.t >= 3.0) & (a.t < 5.0)).sum() / 2.0
    assert 3.5 < peak / base < 6.5, (base, peak)
    # Every arrival carries the cohort deadline (overload-path food).
    assert (a.deadline_s == 2.0).all()


def test_ramp_and_cohort_mixture_shapes():
    s = load_scenario("mixed-tier-peak")
    a = s.build_arrivals(9)
    # Ramp 200→900 over 3 s: the last ramp second is denser than the
    # first.
    first = ((a.t >= 0.0) & (a.t < 1.0)).sum()
    last = ((a.t >= 2.0) & (a.t < 3.0)).sum()
    assert last > 2 * first
    # Tier columns follow the cohorts, and weights are roughly honored.
    assert set(np.unique(a.tier).tolist()) == {0, 1, 2}
    frac1 = float((a.tier == 1).mean())
    assert 0.35 < frac1 < 0.65
    # Skewed ladder: cohort rating means separate.
    sk = load_scenario("skewed-ladder")
    b = sk.build_arrivals(4)
    means = [float(b.rating[b.cohort == j].mean()) for j in range(3)]
    assert means[0] < 1300 < means[1] < 1800 < means[2]


def test_incidents_ride_the_chaos_schedule():
    s = load_scenario("retry-storm")
    chaos = s.chaos_config("mm.q", seed=13)
    assert chaos is not None and chaos.queues == ("mm.q",)
    assert chaos.dup_seqs == tuple((seq, 2) for seq in range(900, 908))
    # The full incident vocabulary maps onto the scripted fields.
    s2 = Scenario(name="inc", segments=(Segment(),), cohorts=(Cohort(),),
                  incidents=(
                      Incident(kind="drop", at=5, count=3),
                      Incident(kind="partition", at=10, until=20),
                      Incident(kind="engine_fault", at=2, count=2),
                      Incident(kind="probe_fail", count=1),
                  ))
    c2 = s2.chaos_config("q")
    assert c2.drop_seqs == (5, 6, 7)
    assert c2.partitions == ((10, 20),)
    assert c2.fail_step_ranges == ((2, 4),)
    assert c2.fail_probes == 1
    with pytest.raises(ValueError):
        Scenario(name="bad", incidents=(Incident(kind="nope"),)
                 ).chaos_config("q")
    # No incidents → no chaos plumbing at all.
    assert load_scenario("steady").chaos_config("q") is None


def test_scenario_spec_roundtrip_and_unknown_key_rejected():
    for name in scenario_names():
        s = load_scenario(name)
        assert Scenario.from_dict(s.to_dict()) == s
    with pytest.raises(ValueError, match="unknown"):
        Scenario.from_dict({"name": "x",
                            "segments": [{"kind": "steady", "rat": 1}]})
    with pytest.raises(FileNotFoundError):
        load_scenario("no-such-scenario")
    # Malformed specs fail at CONSTRUCTION with a speakable error, not
    # deep inside build_arrivals as a numpy crash.
    with pytest.raises(ValueError, match="segment"):
        Scenario.from_dict({"name": "x", "segments": []})
    with pytest.raises(ValueError, match="cohort"):
        Scenario.from_dict({"name": "x", "cohorts": []})
    with pytest.raises(ValueError, match="no mass"):
        Scenario(name="x", cohorts=(Cohort(weight=0.0),))
    with pytest.raises(ValueError, match="duration"):
        Scenario(name="x", segments=(Segment(duration_s=0.0),))
    with pytest.raises(ValueError, match="kind"):
        Scenario(name="x", segments=(Segment(kind="square"),))


# ---- loadgen behavior ------------------------------------------------------

async def test_retry_on_shed_republishes_once_and_accounts_per_cohort():
    s = Scenario(
        name="shedder",
        segments=(Segment(kind="steady", duration_s=1.2, rate=300.0),),
        cohorts=(Cohort(name="impatient", rating_sigma=4000.0,
                        retry_on_shed=1.0, retry_delay_s=0.05),))
    # Unmatchable-ish ratings + a tiny waiting cap → most arrivals shed.
    cfg = Config(
        queues=(QueueConfig(rating_threshold=1.0,
                            send_queued_ack=False),),
        engine=EngineConfig(backend="cpu", pool_capacity=4096),
        batcher=BatcherConfig(max_batch=64, max_wait_ms=2.0),
        overload=OverloadConfig(max_waiting=8, retry_after_ms=50.0),
        observability=ObservabilityConfig(snapshot_interval_s=0.0))
    app = MatchmakingApp(cfg)
    await app.start()
    try:
        r = await offered_load(app, "matchmaking.search", rate=0.0,
                               duration=0.0, seed=4, scenario=s)
    finally:
        await app.stop()
    assert r["shed"] > 0
    assert r["retries_sent"] > 0
    row = r["cohorts"]["impatient"]
    assert row["retries"] == r["retries_sent"]
    # Every request and every retry got its own reply (shed again or
    # served), except the ≤ max_waiting players legitimately parked in
    # the pool at the end (admitted, unmatched, no timeout configured —
    # their terminal reply never comes by design).
    gap = r["sent"] + r["retries_sent"] - r["replies"]
    assert 0 <= gap <= 8, r
    # One retry per shed ARRIVAL, never retries-of-retries.
    assert r["retries_sent"] <= r["sent"]


async def test_scenario_mode_rejects_conflicting_models():
    app = MatchmakingApp(_small_cfg())
    await app.start()
    try:
        with pytest.raises(ValueError, match="scenario mode"):
            await offered_load(app, "matchmaking.search", rate=0.0,
                               duration=0.0, seed=1,
                               scenario=load_scenario("steady"),
                               tier_mix={0: 1.0})
    finally:
        await app.stop()


# ---- the mini-matrix smoke (check.sh section) ------------------------------

def _matrix_args(**over):
    kw = dict(
        matrix="steady,flash-crowd",
        seed=21,
        rate_scale=0.6,
        time_scale=0.4,
        # The SLO target (also the tuner's p99 target) sits below the
        # cells' own 25 ms window wait, so the flash crowd runs hot on a
        # host of any speed and the tuner has a reason to move.
        slo_ms=20.0,
        wait_ms=25.0,
        max_waiting=2048,
        trajectory=60,
        no_autotune=False,
        tuned_dir="",
    )
    kw.update(over)
    return kw


_CELL_SCHEMA_KEYS = {
    "scenario", "seed", "duration_s", "scenario_digest", "offered",
    "matched", "shed", "expired", "slo_attainment", "admitted_p99_ms",
    "attribution", "telemetry", "autotune", "cohorts",
}


def test_mini_matrix_smoke_schema_audit_and_replay_identity(tmp_path):
    """The check.sh gate: a seeded 2-cell matrix through
    ``loadgen.scenario_matrix``, twice. Asserts the trajectory
    artifact schema, a non-empty autotuner audit ring on the overloaded
    cell, a written tuned-config artifact, and replay identity — the
    seeded scenario digests (the full arrival+incident transcript) must
    agree bit for bit across the two runs."""
    out1 = scenario_matrix(**_matrix_args(tuned_dir=str(tmp_path)))
    out2 = scenario_matrix(**_matrix_args())
    for out in (out1, out2):
        cells = out["scenario_matrix"]
        assert [c["scenario"] for c in cells] == ["steady", "flash-crowd"]
        for cell in cells:
            assert _CELL_SCHEMA_KEYS <= set(cell), sorted(cell)
            assert cell["telemetry"], "trajectory tail missing"
            assert cell["offered"] > 50 and cell["matched"] > 0
            snap_keys = set()
            for snap in cell["telemetry"]:
                snap_keys |= set(snap["values"])
            assert any(k.startswith("stage_total_p99_ms[")
                       for k in snap_keys)
            assert any(k.startswith("pool_size[") for k in snap_keys)
        assert out["value"] is not None  # worst-cell attainment
    # The overloaded flash-crowd cell must have driven the tuner: audit
    # ring non-empty, window wait tightened off the static 25 ms.
    flash1 = out1["scenario_matrix"][1]
    tune = flash1["autotune"]
    assert tune["moves"] > 0 and tune["trace"], tune
    assert tune["knobs"]["matchmaking.search"]["max_wait_ms"] < 25.0
    # Tuned-config artifact written for every cell.
    tuned = json.loads((tmp_path / "flash-crowd.json").read_text())
    assert tuned["scenario"] == "flash-crowd"
    assert tuned["knobs"]["matchmaking.search"]["max_wait_ms"] < 25.0
    # Replay identity: the seeded transcripts agree across runs, per cell.
    for c1, c2 in zip(out1["scenario_matrix"], out2["scenario_matrix"]):
        assert c1["scenario_digest"] == c2["scenario_digest"]
        assert c1["offered"] == c2["offered"]


def test_matrix_failed_cell_fails_the_run():
    """A broken cell (an unknown scenario here) fails the whole matrix:
    no cell is recorded as aborted and no result comes back."""
    with pytest.raises(FileNotFoundError, match="no-such-scenario"):
        scenario_matrix(**_matrix_args(matrix="no-such-scenario,steady",
                                       time_scale=0.25))
