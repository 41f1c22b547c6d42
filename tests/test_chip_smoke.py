"""chip_smoke.py's checks at toy size on the CPU, and the no-chip refusals.

The script itself needs the chip (the driver runs it there); what runs here
is everything it checks with, over the same app/engine entry points, plus
the contract that with no TPU it exits non-zero and prints no result.
"""

import asyncio
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from matchmaking_tpu.config import Config, EngineConfig, QueueConfig  # noqa: E402


def _toy_cfg() -> Config:
    return Config(
        queues=(QueueConfig(name="matchmaking.search",
                            rating_threshold=100.0),),
        engine=EngineConfig(backend="tpu", pool_capacity=2048,
                            pool_block=256, batch_buckets=(16, 64, 256)))


def test_toy_served_path_passes_every_check():
    lines = []
    errors = asyncio.run(chip_smoke.serve_smoke(
        _toy_cfg(), pool=1200, windows=3, window=200, tail_pairs=8, seed=3,
        say=lines.append, timeout_s=120.0))
    assert errors == [], errors
    assert any(ln.startswith("tail pairs=8") and "device_matches=8" in ln
               for ln in lines), lines
    assert sum(ln.startswith("window=") for ln in lines) == 3


def test_check_matches_catches_a_forged_double_match():
    ratings = {"a": 1500.0, "b": 1550.0, "c": 1520.0}
    good = {"m1": ("a", "b")}
    assert chip_smoke.check_matches(good, ratings, 100.0) == []
    forged = {"m1": ("a", "b"), "m2": ("a", "c")}
    errors = chip_smoke.check_matches(forged, ratings, 100.0)
    assert any("two matches" in e for e in errors), errors
    wide = {"m1": ("a", "b")}
    assert chip_smoke.check_matches(wide, ratings, 40.0)


def test_accounting_oracle_and_response_checks():
    assert chip_smoke.check_pool_accounting(98, 100, 2, 2) == []
    assert chip_smoke.check_pool_accounting(99, 100, 2, 2)
    pair = frozenset(("x", "y"))
    assert chip_smoke.check_oracle({pair}, {pair}) == []
    assert chip_smoke.check_oracle(set(), {pair})
    assert chip_smoke.check_responses(["x"], {"x": ["queued", "matched"]}) == []
    assert chip_smoke.check_responses(["x", "y"], {"x": ["matched"]})
    assert chip_smoke.check_responses(["x"], {"x": ["shed"]})


def test_sharded_pool_matches_single_device_at_toy_size():
    """The --chips 4 comparison on the 4-virtual-device CPU mesh."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    lines = []
    errors = chip_smoke.sharded_compare(
        _toy_cfg(), shards=4, pool=1200, windows=2, window=200, seed=5,
        say=lines.append)
    assert errors == [], errors
    assert any("identical=True" in ln for ln in lines), lines


@pytest.mark.parametrize("argv", [
    ["chip_smoke.py"], ["chip_smoke.py", "--chips", "4"]])
def test_no_chip_means_nonzero_exit_and_no_result(argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """Copied away from the repo, the script cannot pass (here it fails on
    the device check first; on a chip, on the missing package)."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


_CACHE_PROBE = """
import jax, jax.numpy as jnp
from matchmaking_tpu.utils.compile_cache import (
    DEFAULT_DIR, cache_entries, enable_compile_cache)
d = enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == d, (
    jax.config.jax_compilation_cache_dir, d)
if COMPILE:
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(d, DEFAULT_DIR, cache_entries(d))
"""


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_is_placed_from_outside(tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR wins and the cache lands there; unset,
    the directory is one fixed path inside the checkout (.gitignore'd).
    Runs in a child: tests never turn the cache on in their own process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if from_env:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    code = _CACHE_PROBE.replace("COMPILE", str(from_env))
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    where, default, entries = proc.stdout.split()
    assert default == os.path.join(REPO, ".jax_cache")
    if from_env:
        assert where == str(tmp_path / "cc")
        assert int(entries) >= 1
    else:
        assert where == default
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
