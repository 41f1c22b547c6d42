"""The main path's kernels, compiled by the TPU compiler for a described
v5e that is not attached (on-chip-measurement guide, §2).

Nothing here runs on a chip and nothing here is a timing: a compile that
passes says only that the chip's compiler accepts the program at its real
widths and that it fits the device's memory. The topology is described
inside a fixture (never at import, in a ``skipif`` or in ``parametrize``),
because only one process at a time may load the TPU library, and every
test worker imports this file.

``TpuEngine`` places its pool from ``jax.devices()`` — the CPU here — so
these tests compile the kernel sets' jitted functions directly, with
shapes that carry the described devices' shardings.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from matchmaking_tpu.core.pool import PACKED_ROWS, PlayerPool

#: BASELINE config #1's geometry (configs/elo_1v1.json) and a 4,096-request
#: window.
CAPACITY = 131_072
POOL_BLOCK = 8192
WINDOW = 4096
#: HBM of one v5e chip (Google Cloud documentation, "TPU v5e").
HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _pool_shapes(capacity: int, sharding, extra: dict | None = None):
    arrays = PlayerPool.empty_device_arrays(capacity)
    for name, dtype in (extra or {}).items():
        arrays[name] = np.zeros(capacity, dtype)
    return {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=sharding)
            for k, v in arrays.items()}


def _packed_shape(rows: int, window: int, sharding):
    return jax.ShapeDtypeStruct((rows, window), jnp.float32,
                                sharding=sharding)


def _compile(fn, *shapes):
    compiled = fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
    return compiled


def _elo_kernels(glicko2: bool = False):
    from matchmaking_tpu.engine.kernels import KernelSet

    return KernelSet(capacity=CAPACITY, top_k=8, pool_block=POOL_BLOCK,
                     glicko2=glicko2, widen_per_sec=0.0,
                     max_threshold=400.0)


@pytest.mark.parametrize("variant", [
    "search_step_packed", "search_step_packed_nofilter",
    "search_step_packed_rescan"])
def test_1v1_step_compiles_for_v5e(one_chip, variant):
    ks = _elo_kernels()
    _compile(getattr(ks, variant), _pool_shapes(CAPACITY, one_chip),
             _packed_shape(len(PACKED_ROWS) + 1, WINDOW, one_chip))


def test_glicko2_step_compiles_for_v5e(one_chip):
    ks = _elo_kernels(glicko2=True)
    _compile(ks.search_step_packed, _pool_shapes(CAPACITY, one_chip),
             _packed_shape(len(PACKED_ROWS) + 1, WINDOW, one_chip))


def test_team_step_compiles_for_v5e(one_chip):
    """configs/team_5v5.json's geometry, at the top default batch bucket."""
    from matchmaking_tpu.engine.teams import team_kernel_set

    ks = team_kernel_set(capacity=65_536, team_size=5, widen_per_sec=2.0,
                         max_threshold=300.0, max_matches=512)
    _compile(ks.search_step_packed, _pool_shapes(ks.capacity, one_chip),
             _packed_shape(len(PACKED_ROWS) + 1, 1024, one_chip))


def test_role_step_compiles_for_v5e(one_chip):
    """configs/role_party.json's geometry, at the top default batch bucket."""
    from matchmaking_tpu.engine.role_kernels import role_kernel_set

    ks = role_kernel_set(capacity=16_384, team_size=5,
                         role_slots=("tank", "healer", "dps", "dps", "dps"),
                         widen_per_sec=0.0, max_threshold=400.0)
    _compile(ks.search_step_packed,
             _pool_shapes(ks.capacity, one_chip, ks.extra_pool_fields),
             _packed_shape(ks.pack_rows, 1024, one_chip))


def test_pallas_block_best_compiles_to_a_tpu_kernel(one_chip):
    import functools

    from matchmaking_tpu.engine.pallas_kernels import (
        LANES,
        POOL_ROWS,
        pallas_block_best,
    )

    fn = jax.jit(functools.partial(
        pallas_block_best, super_blk=POOL_BLOCK, sub_blk=512, b_tile=256,
        capacity=CAPACITY, glicko2=False, widen_per_sec=0.0,
        max_threshold=400.0))
    compiled = _compile(
        fn,
        jax.ShapeDtypeStruct((len(POOL_ROWS), CAPACITY), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((WINDOW, LANES), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()


def test_sharded_1v1_step_compiles_for_four_chips(topo):
    from matchmaking_tpu.engine.sharded import AXIS, ShardedKernelSet

    mesh = Mesh(np.array(topo.devices[:4]), (AXIS,))
    ks = ShardedKernelSet(capacity=CAPACITY, top_k=8, pool_block=POOL_BLOCK,
                          glicko2=False, widen_per_sec=0.0,
                          max_threshold=400.0, mesh=mesh)
    compiled = _compile(
        ks.search_step_packed,
        _pool_shapes(CAPACITY, NamedSharding(mesh, P(AXIS))),
        _packed_shape(len(PACKED_ROWS) + 1, WINDOW,
                      NamedSharding(mesh, P())))
    hlo = compiled.as_text()
    assert "all-gather" in hlo or "collective-permute" in hlo
