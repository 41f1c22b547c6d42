"""Small-scope interleaving model checker (ISSUE 19): the explorer's
generic machinery on a toy world, and the protocol world end-to-end —
clean exhaustive runs on the real objects, every seeded mutant caught
with a minimized digest-replayable counterexample.

The fast scopes here are tier-1; the committed CI smoke scope (depth 6)
runs in scripts/check.sh through ``analysis.modelcheck.run_modelcheck``.
"""

import dataclasses

import pytest

from matchmaking_tpu.analysis.modelcheck import (
    MUTANTS, ModelCheckConfig, mutation_gate_config, run_modelcheck,
    run_mutation_gate)
from matchmaking_tpu.testing.scheduler import Explorer, schedule_digest

pytestmark = pytest.mark.protocol


# ---- the generic explorer on a toy world -----------------------------------

class _CounterWorld:
    """Two independent counters ('a', 'b'), each incrementable to 3; the
    world is 'violated' when counter a reaches a configured trip value
    AFTER a longer decoy prefix — exercises minimization."""

    ACTIONS = ("inc@a", "inc@b")

    def __init__(self, trip_at=None):
        self.vals = {"a": 0, "b": 0}
        self.trip_at = trip_at

    def enabled(self):
        return [k for k in self.ACTIONS
                if self.vals[k.partition("@")[2]] < 3]

    def step(self, key):
        slot = key.partition("@")[2]
        self.vals[slot] += 1
        return f"{slot} -> {self.vals[slot]}"

    def check(self):
        if self.trip_at is not None and self.vals["a"] >= self.trip_at:
            return f"counter a reached {self.vals['a']}"
        return None

    def digest(self):
        return (self.vals["a"], self.vals["b"])

    def slot(self, key):
        return key.partition("@")[2]

    def index(self, key):
        return self.ACTIONS.index(key)

    def close(self):
        pass


def test_explorer_enumerates_exhaustively_with_dedup_and_por():
    ex = Explorer(_CounterWorld, max_depth=6)
    res = ex.explore()
    assert res.violation is None
    assert res.exhaustive
    # The reachable state space is exactly the 4x4 counter grid.
    assert res.states == 16
    assert res.pruned_por > 0


def test_explorer_por_preserves_the_reachable_state_space():
    full = Explorer(_CounterWorld, max_depth=6, por=False).explore()
    reduced = Explorer(_CounterWorld, max_depth=6, por=True).explore()
    assert full.exhaustive and reduced.exhaustive
    assert full.states == reduced.states
    assert reduced.nodes < full.nodes


def test_explorer_minimizes_to_the_shortest_failing_schedule():
    ex = Explorer(lambda: _CounterWorld(trip_at=2), max_depth=6)
    res = ex.explore()
    assert res.violation == "counter a reached 2"
    # Decoy inc@b steps are minimized away: two a-increments suffice.
    assert res.schedule == ["inc@a", "inc@a"]
    assert len(res.timeline) == 3 and "VIOLATION" in res.timeline[-1]
    assert res.digest == ""  # digest is the caller's (scope-salted) job


def test_schedule_digest_is_scope_salted():
    sched = ["inc@a", "inc@a"]
    assert (schedule_digest(sched, {"depth": 4})
            != schedule_digest(sched, {"depth": 5}))
    assert (schedule_digest(sched, {"depth": 4})
            == schedule_digest(list(sched), {"depth": 4}))


# ---- the protocol world on the real objects --------------------------------

def _small(**over):
    base = ModelCheckConfig(queues=1, depth=4, admits=2, settles=1,
                            faults=("expire", "drop"), fault_budget=2)
    return dataclasses.replace(base, **over)


def test_protocol_clean_at_single_queue_scope():
    rep = run_modelcheck(_small())
    assert rep["modelcheck_violations"] == 0
    assert rep["modelcheck_exhaustive"]
    assert rep["modelcheck_states_explored"] > 50


def test_protocol_clean_at_two_queue_scope_with_crash_and_dup():
    rep = run_modelcheck(ModelCheckConfig(
        queues=2, depth=4, faults=("expire", "crash", "drop", "dup"),
        fault_budget=2))
    assert rep["modelcheck_violations"] == 0
    assert rep["modelcheck_exhaustive"]
    # Two queues share one authority; POR must still fire across them.
    assert rep["modelcheck_pruned_por"] > 0


def test_stale_epoch_resume_is_refused_not_violating():
    """The fenced ex-primary resuming WITHOUT a crash (expire ->
    takeover -> admit/publish) must be refused by the fences — replaying
    that exact schedule shows refusals and no violation."""
    rep = run_modelcheck(
        _small(settles=1),
        replay=["settle@q0", "expire@q0", "takeover@q0", "admit@q0",
                "publish@q0"])
    assert rep["modelcheck_violations"] == 0
    timeline = "\n".join(rep["modelcheck_timeline"])
    assert "admit refused: journal append fenced" in timeline
    assert "publish q0-t1 refused: epoch superseded" in timeline


@pytest.mark.parametrize("mutant", MUTANTS)
def test_every_seeded_mutant_yields_a_minimized_counterexample(mutant):
    cfg = dataclasses.replace(mutation_gate_config(), mutation=mutant)
    rep = run_modelcheck(cfg)
    assert rep["modelcheck_violations"] == 1
    assert 1 <= len(rep["modelcheck_schedule"]) <= cfg.depth
    assert rep["modelcheck_schedule_digest"]
    # The counterexample replays bit-identically from its schedule.
    rerun = run_modelcheck(cfg, replay=rep["modelcheck_schedule"])
    assert rerun["modelcheck_violation"] == rep["modelcheck_violation"]
    assert (rerun["modelcheck_schedule_digest"]
            == rep["modelcheck_schedule_digest"])


def test_mutation_gate_passes_and_reports_per_mutant_evidence():
    gate = run_mutation_gate()
    assert gate["mutation_gate_passed"]
    assert gate["mutation_gate_baseline_clean"]
    assert set(gate["mutation_gate_mutants"]) == set(MUTANTS)
    for rec in gate["mutation_gate_mutants"].values():
        assert rec["caught"] and rec["replay_ok"]
        assert rec["timeline"][-1].startswith("VIOLATION")


def test_counterexample_timeline_reads_as_a_causal_spine():
    cfg = dataclasses.replace(mutation_gate_config(),
                              mutation="skip-append-fence")
    rep = run_modelcheck(cfg)
    tl = rep["modelcheck_timeline"]
    assert tl[0].startswith("step 1:")
    assert any("lease expired" in ln for ln in tl)
    assert any("took over" in ln for ln in tl)
    assert "ex-primary produced an externally visible effect" in tl[-1]
