"""Compile the served executors for a described TPU v5e, with no chip.

The TPU compiler is installed beside JAX, and compiles for a chip that is
described rather than attached.  These tests hold the Pallas TMFU kernel
(Mosaic) and the jnp VM to what the chip's compiler accepts at serving
shapes: tile 128 and 512, f32 and int32, a bank of 8 and a bank at the
SMEM limit (``MAX_BANK_CAPACITY``).  Nothing runs, so results are checked
elsewhere (interpret mode in test_kernel_tmfu.py, ``chip_smoke.py`` on
the chip).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and a test session with several
workers must collect the same tests in every worker.
"""

import os
import warnings

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.bank import DEFAULT_MAX_OUTPUTS
from repro.core.isa import IM_DEPTH, RF_DEPTH
from repro.core.vm import S_MAX, vm_exec_multi_donated
from repro.kernels.tmfu.kernel import (MAX_BANK_CAPACITY, MAX_LAUNCH_TILES,
                                       tmfu_pipeline_rf,
                                       tmfu_pipeline_rf_multi,
                                       tmfu_pipeline_rf_multi_donated)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no chip compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """A v5e device sharding, with the persistent compile cache off: an
    entry written for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _bank_and_tiles(sharding, n_bank, n_tiles, tile, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    words = [sds((n_bank, S_MAX, IM_DEPTH), jnp.int32) for _ in range(4)]
    return words, sds((n_tiles,), jnp.int32), sds((n_tiles, RF_DEPTH, tile),
                                                  dtype)


@pytest.mark.parametrize("donated", [False, True], ids=["plain", "donated"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32],
                         ids=["f32", "i32"])
@pytest.mark.parametrize("tile", [128, 512])
@pytest.mark.parametrize("n_bank,n_tiles", [(8, 64),
                                            (MAX_BANK_CAPACITY,
                                             MAX_LAUNCH_TILES)],
                         ids=["bank8", "bank_limit"])
def test_multi_kernel_compiles_with_mosaic(one_chip, n_bank, n_tiles, tile,
                                           dtype, donated):
    fn = tmfu_pipeline_rf_multi_donated if donated else tmfu_pipeline_rf_multi
    words, ids, x = _bank_and_tiles(one_chip, n_bank, n_tiles, tile, dtype)
    compiled = fn.lower(*words, ids, x, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bank_over_limit_overflows_smem(one_chip):
    """``MAX_BANK_CAPACITY`` is exact: one more context (padded to the next
    eight SMEM rows) does not fit beside a full launch's tile ids."""
    words, ids, x = _bank_and_tiles(one_chip, MAX_BANK_CAPACITY + 1,
                                    MAX_LAUNCH_TILES, 128, jnp.float32)
    with pytest.raises(Exception, match="(?i)smem"):
        tmfu_pipeline_rf_multi.lower(*words, ids, x,
                                     interpret=False).compile()


def test_single_context_kernel_compiles_with_mosaic(one_chip):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    words = [sds((S_MAX, IM_DEPTH), jnp.int32) for _ in range(4)]
    compiled = tmfu_pipeline_rf.lower(
        *words, sds((RF_DEPTH, 1024), jnp.float32), block_batch=512,
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_jnp_vm_bucket_compiles(one_chip):
    words, ids, x = _bank_and_tiles(one_chip, 8, 64, 128, jnp.float32)
    words[3] = jax.ShapeDtypeStruct((8, S_MAX, IM_DEPTH), jnp.float32,
                                    sharding=one_chip)
    out_idx = jax.ShapeDtypeStruct((8, DEFAULT_MAX_OUTPUTS), jnp.int32,
                                   sharding=one_chip)
    with warnings.catch_warnings():
        # the VM's [G, max_outputs, tile] result is narrower than the
        # donated stack, so XLA frees the donation instead of aliasing it
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not usable")
        compiled = vm_exec_multi_donated.lower(tuple(words), out_idx, ids,
                                               x).compile()
    assert compiled.as_text()
