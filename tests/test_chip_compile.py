"""The two device programs compile for a described TPU v5e chip at the
full-GPU grid's shapes, and fit its 16 GB of HBM.

Nothing runs here: the chip is described (``jax.experimental.topologies``),
not attached, so these tests say nothing about results or times.  They
guard the compiler's verdict — shapes, memory — on every change.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers each import every test file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.isa import MachineConfig
from repro.engine.adapters import _jitted_batch_runner, batch_class
from repro.engine.mechanisms.sm_jax import (_GTO, _cell_scheduler,
                                            _latency_tables, _out_capacity)
from repro.timing import CycleConfig

HBM_BYTES = 16 * 10**9            # one TPU v5e chip
CFG = MachineConfig(n_threads=32, max_steps=8192)
N_CELLS, N_WARPS = 68, 32         # 68 SMs (TU102) x 32 resident warps
PAD_LEN = 32                      # the suite's padding class
N_ROWS = batch_class(N_CELLS * N_WARPS)
OUT_CAP = _out_capacity(N_WARPS * CFG.max_steps)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip can be written to JAX's persistent
    cache but never read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)


def test_hanoi_batch_compiles_for_v5e(sds, no_persistent_cache):
    """The hanoi_jax batch program at the grid's unique-row batch class."""
    W = CFG.n_threads
    compiled = _jitted_batch_runner(CFG, True).lower(
        sds((N_ROWS, PAD_LEN, 8), jnp.int32),
        sds((N_ROWS, PAD_LEN), jnp.bool_),
        sds((N_ROWS, W, CFG.n_regs), jnp.int32),
        sds((N_ROWS, CFG.mem_size), jnp.int32),
        sds((N_ROWS, W), jnp.int32)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES


def test_sm_grid_scheduler_compiles_for_v5e(sds, no_persistent_cache):
    """The sm_jax issue scheduler over the whole grid (GTO), every warp
    distinct, at the slot capacity of warps that run out of fuel."""
    lat, is_mem = _latency_tables(CycleConfig(scoreboard=False))
    fn = jax.jit(jax.vmap(_cell_scheduler(N_WARPS, OUT_CAP, _GTO, lat,
                                          is_mem),
                          in_axes=(0, 0, None, None, None)))
    compiled = fn.lower(
        sds((N_CELLS, N_WARPS), jnp.int32),
        sds((N_CELLS, N_WARPS), jnp.int32),
        sds((N_ROWS, PAD_LEN), jnp.int32),
        sds((N_ROWS, CFG.max_steps), jnp.int32),
        sds((N_ROWS, CFG.max_steps), jnp.uint32)).compile()
    assert 0 < _device_bytes(compiled) < HBM_BYTES
