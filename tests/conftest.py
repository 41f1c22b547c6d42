"""Test harness config.

Forces JAX onto the host CPU backend with 8 virtual devices BEFORE jax is
imported anywhere, so sharding/collective code paths (mesh axis ``pool``) are
exercised without TPU hardware (SURVEY.md §4 "For the rebuild"). The
benchmark and chip_smoke.py use the real TPU; tests use this virtual mesh.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import asyncio
import inspect

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(1234)


# Minimal async-test support (pytest-asyncio is not in this image): any
# ``async def test_*`` runs under asyncio.run with its sync fixtures.
def pytest_configure(config):
    config.addinivalue_line("markers", "asyncio: run coroutine test in an event loop")
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection test (deterministic ChaosSchedule; "
        "the fast ones run in tier-1, soaks additionally carry `slow`)")
    config.addinivalue_line(
        "markers", "slow: long soak — excluded from the tier-1 `-m 'not "
        "slow'` run")
    config.addinivalue_line(
        "markers", "lint: static-analysis gate (`pytest -m lint` runs "
        "matchlint as a test node; part of tier-1)")
    config.addinivalue_line(
        "markers", "qos: tiered-QoS suite (priority classes / EDF window "
        "cutting / pool-resident deadline expiry — scripts/check.sh runs "
        "it by marker; the fast ones are tier-1, soaks additionally "
        "carry `slow`)")
    config.addinivalue_line(
        "markers", "overload: overload-control suite (admission/shed/"
        "deadline/drain — scripts/check.sh runs it by marker; the fast "
        "ones are tier-1, soaks additionally carry `slow`)")
    config.addinivalue_line(
        "markers", "quality: match-quality & fairness suite (device-vs-"
        "host accumulator reconciliation / disparity / quality SLO / "
        "waited_ms wire contract — scripts/check.sh runs it by marker; "
        "the fast ones are tier-1, soaks additionally carry `slow`)")
    config.addinivalue_line(
        "markers", "placement: elastic placement control-plane suite "
        "(queue→device migration / elastic sharding / dispatch "
        "arbitration — scripts/check.sh runs it by marker; the fast ones "
        "are tier-1, soaks additionally carry `slow`)")
    config.addinivalue_line(
        "markers", "ingress: consume-batch / sharded-ingress suite "
        "(burst-callback broker seam, consume-time decode, equivalence "
        "soaks consume_batch on/off and shards 1/4 — scripts/check.sh "
        "runs it by marker; part of tier-1)")
    config.addinivalue_line(
        "markers", "scenario: population-model load scenarios + online "
        "autotuner suite (ISSUE 13: transcript determinism, steady≡legacy "
        "byte identity, the seeded closed-loop autotune acceptance, the "
        "2-cell mini-matrix smoke — scripts/check.sh runs it by marker; "
        "part of tier-1)")
    config.addinivalue_line(
        "markers", "codec: native-codec parity fuzz (byte/field equality "
        "vs the Python contract module over a seeded corpus — "
        "scripts/check.sh runs it by marker after rebuilding "
        "libmmcodec.so from source; part of tier-1)")
    config.addinivalue_line(
        "markers", "bucketed: hierarchical rating-bucketed formation "
        "suite (ISSUE 14: bucketed↔flat bit-exactness at D=1/2/4, "
        "occupancy skew, widening boundary, tournament-vs-linear frontier "
        "merge, adaptive frontier-K — scripts/check.sh runs it by marker; "
        "part of tier-1)")
    config.addinivalue_line(
        "markers", "durability: crash-durability suite (ISSUE 15: "
        "write-ahead journal framing/replay, hard-crash recovery edges "
        "incl. corruption fixtures + compaction crash points, the "
        "two-run bit-identical recovery transcript, device-loss "
        "failover, and the sanitizer's journal twin — scripts/check.sh "
        "runs it by marker plus a 2-cycle crash-soak smoke; part of "
        "tier-1)")
    config.addinivalue_line(
        "markers", "replication: hot-standby replication suite (ISSUE "
        "17: lease/epoch fencing, the at-least-once stream link under "
        "scripted faults, the standby applier, the service stream round "
        "trip, cross-host failover with the fenced ex-primary "
        "regression, the sanitizer's replication twin, and the offline "
        "journal inspector — scripts/check.sh runs it by marker plus a "
        "2-cycle failover-soak smoke; part of tier-1)")
    config.addinivalue_line(
        "markers", "protocol: protocol-conformance suite (ISSUE 19: the "
        "matchlint protocol rule's fixture positives/negatives plus the "
        "small-scope interleaving model checker — clean exhaustive runs "
        "on the real lease/replication/journal objects and the seeded "
        "mutation gate — scripts/check.sh runs it by marker plus the "
        "committed-scope modelcheck smoke; the fast scopes are tier-1)")
    config.addinivalue_line(
        "markers", "net: real-transport DCN suite (ISSUE 20: frame "
        "codec fuzz — torn frames at every byte offset, hostile length "
        "prefixes, CRC flips, interleaved heartbeats — the socket "
        "replication link end-to-end over UDS with QueueReplication + "
        "StandbyApplier unchanged, deterministic network nemesis "
        "scripts, the remote lease client's renewal-in-flight-at-expiry "
        "refusal, and the sanitizer's ack-beyond-received twin over a "
        "real socket — scripts/check.sh runs it by marker plus a "
        "2-cycle cross-process socket failover smoke and the in-proc ≡ "
        "socket transcript-equivalence pin; part of tier-1)")
    config.addinivalue_line(
        "markers", "forensics: incident-forensics suite (ISSUE 18: the "
        "causal event spine's monotone seq under threads, black-box "
        "trigger/rate-limit/reentrancy capture, bundle schema "
        "validation, /debug/incidents + prom exposition mid-failover, "
        "capture-during-drain non-interference, the offline postmortem "
        "root chain, and the journal LSN-range slicer — "
        "scripts/check.sh runs it by marker plus committed-example "
        "bundle validation; part of tier-1)")


@pytest.fixture
def sanitizer():
    """Runtime async sanitizer (matchmaking_tpu/testing/sanitizer.py):
    while the test runs, every ``asyncio.Lock()`` the service creates is
    instrumented — lock-order inversions, non-sanctioned awaits under a
    lock, and event-loop stalls are collected and asserted empty at
    teardown. The 2.0 s stall threshold leaves headroom for the CPU test
    mesh's cold-cache XLA compiles (GIL-holding host slices of to_thread
    work can stall the loop once per fresh process); a real on-loop bug —
    time.sleep, a sync device readback — stalls far longer and on every
    window, not once."""
    from matchmaking_tpu.testing.sanitizer import AsyncSanitizer

    san = AsyncSanitizer(stall_threshold_s=2.0)
    with san.installed():
        yield san
    san.assert_clean()


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None
