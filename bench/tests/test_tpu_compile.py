"""The fleet cells' step-kernel shapes compile for a described TPU v5e.

G = 173 FSM slices x nb = 14 blocks, a shared 311-instruction program
(16-bit multiply-accumulate) and a per-slot stack padded to 320.
Nothing runs; the topology is described inside a fixture, and the
persistent compilation cache is off around the compiles.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.comefa import isa
from repro.core.comefa.engine_packed import N_WORDS
from repro.kernels import comefa_step

G, NB = 173, 14


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("per_slot,t", [(False, 311), (True, 320)])
def test_fleet_step_kernel_compiles_for_v5e(one_chip, per_slot, t):
    f = isa.N_ENGINE_FIELDS

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [spec((G, NB, isa.N_ROWS, N_WORDS), jnp.uint32),
            spec((G, NB, N_WORDS), jnp.uint32),
            spec((G, NB, N_WORDS), jnp.uint32),
            spec((G, t, f) if per_slot else (t, f), jnp.int32)]
    compiled = jax.jit(lambda m, c, k, p: comefa_step.run_packed(
        m, c, k, p, chain=False, per_slot=per_slot, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
