"""Multi-host DCN smoke test (SURVEY.md §2/§5 "DCN via standard JAX
multi-host runtime"): TWO real OS processes join via
``jax.distributed.initialize`` (gloo collectives over localhost on the CPU
backend) and run the FULL sharded packed window step over a pool mesh that
spans both processes — the exact code path a TPU pod runs across hosts.
"""

import os
import subprocess
import sys

import pytest

from matchmaking_tpu.engine.distributed import cpu_collectives_supported

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Capability gate: the 2-process run needs (a) a jaxlib with gloo CPU
#: collectives (init_distributed selects them; older builds fail every
#: cross-process op with "Multiprocess computations aren't implemented on
#: the CPU backend") and (b) at least 2 cores so the ranks can make
#: synchronous progress through the collective barriers instead of
#: timing out. MM_FORCE_DCN_TEST=1 overrides both checks.
_FORCED = os.environ.get("MM_FORCE_DCN_TEST", "") not in ("", "0")
pytestmark = pytest.mark.skipif(
    not _FORCED and not (cpu_collectives_supported()
                         and (os.cpu_count() or 1) >= 2),
    reason="multiprocess DCN-on-CPU needs a gloo-collectives jaxlib and "
           ">=2 cores (set MM_FORCE_DCN_TEST=1 to force)")

WORKER = r"""
import os, sys
import jax

from matchmaking_tpu.engine.distributed import (
    dcn_configured, global_pool_mesh, init_distributed)

assert dcn_configured()
rank, nprocs = init_distributed()
assert nprocs == 2, nprocs
assert jax.device_count() == 2, jax.devices()
assert jax.local_device_count() == 1

import numpy as np
import jax.numpy as jnp
from matchmaking_tpu.core.pool import PlayerPool
from matchmaking_tpu.engine.sharded import ShardedKernelSet
from __graft_entry__ import _example_packed

mesh = global_pool_mesh()
ks = ShardedKernelSet(capacity=32, top_k=4, pool_block=16, glicko2=False,
                      widen_per_sec=0.0, max_threshold=400.0, mesh=mesh)
pool = ks.place_pool(PlayerPool.empty_device_arrays(ks.capacity))
ratings = [1500.0 + 3.0 * i for i in range(12)]
packed = jnp.asarray(_example_packed(ks.capacity, 16, ratings, now=0.5))
pool, out = ks.search_step_packed(pool, packed)
jax.block_until_ready((pool, out))
q_slot = np.asarray(out[0]).astype(np.int32)
matched = int((q_slot < ks.capacity).sum())
assert matched >= len(ratings) // 2 - 1, f"only {matched} paired"
print(f"DCN_OK rank={rank}/{nprocs} devices={jax.device_count()} "
      f"paired={matched}", flush=True)
"""


def test_two_process_dcn_sharded_step():
    port = 20000 + (os.getpid() % 20000)
    env = dict(os.environ)
    # One CPU device per process → the 2-device mesh REQUIRES cross-process
    # collectives (nothing can fall back to a single host's devices).
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["MM_DCN_COORDINATOR"] = f"127.0.0.1:{port}"
    env["MM_DCN_NUM_PROCESSES"] = "2"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    procs = []
    for rank in range(2):
        penv = dict(env)
        penv["MM_DCN_PROCESS_ID"] = str(rank)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", WORKER], env=penv, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((rank, p.returncode, out, err))
    for rank, rc, out, err in outs:
        assert rc == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"DCN_OK rank={rank}/2 devices=2" in out, out
        assert "paired=" in out
