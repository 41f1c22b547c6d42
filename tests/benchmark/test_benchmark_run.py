"""The benchmark's harness end to end on the CPU, at a size a test holds.

Each test drives the real served path (broker → middleware → batcher →
engine → reply) through ``benchmark.run.run_cell`` with the cell's
configuration cut to a tiny pool. The chip check of the command line is
skipped: these runs say nothing about speed, only that the harness drives
the cell, reads its numbers and decides ``correct``.

The fault tests break the timed path underneath and see ``correct`` come
out false: a step that returns its state unchanged, half of each window
left out, the exchange between chips left out, and an answer altered
where the device produces it.
"""

import copy
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, reference, serve
from benchmark.run import main, result_line, run_cell

SEED = 2**33 + 17
SECONDS = 0.5
RATE = 300.0


def tiny(name: str, rate: float = RATE):
    """The cell ``name`` with its pool cut to a thousand slots a chip, and
    two batch buckets (each compiles on the CPU)."""
    cell = cells.find_cell(name)
    cfg = copy.deepcopy(cell.config)
    cfg["waiting"]["count"] = 700 * cell.chips
    cfg["service"]["engine"].update(pool_capacity=2048 * cell.chips,
                                    pool_block=512,
                                    batch_buckets=[16, 128])
    return dataclasses.replace(cell, config=cfg,
                               traffic=dict(cell.traffic, rate=rate))


def drive(cell, seed: int = SEED):
    return run_cell(cell, seed, SECONDS, False, t_process=time.perf_counter())


@pytest.fixture
def broken_path(monkeypatch):
    """A broken run leaves requests unanswered: wait for them briefly. The
    program caches its kernel sets by geometry; a broken one must neither
    be served from that cache nor be left in it for later tests."""
    from matchmaking_tpu.engine.kernels import kernel_set
    from matchmaking_tpu.engine.sharded import sharded_kernel_set

    monkeypatch.setattr(serve, "DRAIN_S", 1.5)
    monkeypatch.setattr(serve, "WARMUP_TIMEOUT_S", 1.5)
    kernel_set.cache_clear()
    sharded_kernel_set.cache_clear()
    yield
    kernel_set.cache_clear()
    sharded_kernel_set.cache_clear()


@pytest.mark.parametrize("name", ["elo_1v1.search", "elo_1v1.paired",
                                  "elo_1v1_x4.search"])
def test_clean_run_is_correct_and_its_line_follows_the_contract(name):
    cell = tiny(name)
    run, numbers = drive(cell)
    assert not reference.failures(numbers), numbers
    assert run.attempted == round(RATE * SECONDS)
    assert run.failed == 0
    assert run.log["compiles_in_window"] == 0
    spec = cells.benchmark_spec()
    line = result_line(cell, run, numbers, False,
                       {"platform": "cpu", "kind": "cpu", "count": 1},
                       cells.metric_units(spec))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True
    assert set(line["metrics"]) == set(cell.end_to_end)
    for m in line["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert line["checks"]["skipped_max"][1] == reference.LIMITS["skipped_max"]


def test_a_generator_stall_shows_in_the_tail(monkeypatch):
    """Latency counts from when a request was due: a stall of the
    generator (here, 0.3 s blocked on one publish) is waited by every
    request due meanwhile, and the tail shows it."""
    calls = {"n": 0}
    publish = serve._Client.publish

    def stalling(self, pid, body):
        calls["n"] += 1
        if pid == "r50":
            time.sleep(0.3)
        publish(self, pid, body)

    monkeypatch.setattr(serve._Client, "publish", stalling)
    run, numbers = drive(tiny("elo_1v1.search"))
    assert not reference.failures(numbers)
    ms = run.match_ms[~np.isnan(run.match_ms)]
    assert np.percentile(ms, 99) > 200.0
    assert run.log["generator_lag_ms_max"] > 250.0


def _broken_kernels(monkeypatch, wrap):
    """Every KernelSet built from now on has its window steps wrapped."""
    from matchmaking_tpu.engine.kernels import KernelSet

    init = KernelSet.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        step = self._search_step_packed
        self.search_step_packed = jax.jit(wrap(
            lambda pool, packed: step(pool, packed)))
        self.search_step_packed_nofilter = jax.jit(wrap(
            lambda pool, packed: step(pool, packed, True)))

    monkeypatch.setattr(KernelSet, "__init__", patched)


def fault_state_unchanged(monkeypatch):
    def wrap(step):
        def frozen(pool, packed):
            _, out = step(pool, packed)
            return pool, out
        return frozen
    _broken_kernels(monkeypatch, wrap)
    return "elo_1v1.search"


def fault_answer_altered(monkeypatch):
    def wrap(step):
        def altered(pool, packed):
            pool, out = step(pool, packed)
            cap = pool["active"].shape[0]
            c = out[1]
            moved = jnp.where(c < cap, jnp.mod(c + 7, cap), c)
            return pool, out.at[1].set(moved)
        return altered
    _broken_kernels(monkeypatch, wrap)
    return "elo_1v1.search"


def fault_half_window(monkeypatch):
    from matchmaking_tpu.engine.tpu import TpuEngine

    dispatch = TpuEngine.search_columns_async

    def half(self, cols, now):
        return dispatch(self, cols.slice(0, (len(cols) + 1) // 2), now)

    monkeypatch.setattr(TpuEngine, "search_columns_async", half)
    return "elo_1v1.search"


def fault_exchange_left_out(monkeypatch):
    from matchmaking_tpu.engine.sharded import ShardedKernelSet

    monkeypatch.setattr(ShardedKernelSet, "_global_merge",
                        lambda self, vals, gidx: (vals, gidx))
    return "elo_1v1_x4.search"


@pytest.mark.parametrize("fault", [
    fault_state_unchanged, fault_half_window, fault_exchange_left_out,
    fault_answer_altered])
def test_a_broken_timed_path_is_not_correct(monkeypatch, broken_path,
                                            fault):
    name = fault(monkeypatch)
    run, numbers = drive(tiny(name))
    assert reference.failures(numbers), numbers


def test_without_a_tpu_the_command_prints_no_result(monkeypatch, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    rc = main(["--workload", "elo_1v1.search", "--seed", "1",
               "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
