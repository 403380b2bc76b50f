"""Ahead-of-time compiles of the main-path kernels for a described TPU v5e.

Nothing runs: each case lowers and compiles a kernel at its real size for
a v5e chip that is described, not attached, so Mosaic refuses here what
it would refuse on the chip (unaligned blocks, unsupported casts, too much
VMEM).  The topology is described inside a fixture, never at import: only
the test worker given this file loads the TPU compiler.  The persistent
compilation cache is off around the compiles - an entry compiled for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.comefa import isa
from repro.core.comefa.engine_packed import N_WORDS
from repro.kernels import comefa_step

G = 8


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


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("chain", [False, True])
@pytest.mark.parametrize("per_slot", [False, True])
@pytest.mark.parametrize("nb", [1, 8, 16])
def test_comefa_step_compiles_for_v5e(one_chip, nb, per_slot, chain):
    """The Pallas step kernel: G=8 slots x nb blocks (nb=16 is the block
    count of a 2560-wide projection, nb=1 a default `ComefaArray`),
    shared and per-slot programs."""
    t = 288                                  # a mul16 program, 32-padded
    f = isa.N_ENGINE_FIELDS
    prog = (G, t, f) if per_slot else (t, f)
    args = [_spec((G, nb, isa.N_ROWS, N_WORDS), jnp.uint32, one_chip),
            _spec((G, nb, N_WORDS), jnp.uint32, one_chip),
            _spec((G, nb, N_WORDS), jnp.uint32, one_chip),
            _spec(prog, jnp.int32, one_chip)]
    compiled = jax.jit(lambda m, c, k, p: comefa_step.run_packed(
        m, c, k, p, chain=chain, per_slot=per_slot, interpret=False)
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_linear_compiles_for_v5e(one_chip):
    """`linear`'s packed XLA branch (unpack-and-dot, no linear hook) at a
    smollm-360m FFN projection, w4: K=960 packs into 30 uint32 words."""
    from repro import configs
    from repro.models import common
    k, n, bits = 960, 2560, 4
    cfg = configs.get("smollm-360m")
    args = [_spec((8, k), jnp.bfloat16, one_chip),
            _spec((bits, k // 32, n), jnp.uint32, one_chip),
            _spec((1, n), jnp.float32, one_chip)]
    compiled = jax.jit(lambda x, w, s: common.linear(
        {"packed": w, "scale": s}, x, cfg)).lower(*args).compile()
    assert compiled.out_info.shape == (8, n)
    assert compiled.out_info.dtype == jnp.bfloat16


@pytest.mark.parametrize("k,n", [(960, 320), (960, 960), (960, 2560),
                                 (2560, 960)])
def test_gemv_placement_compiles_for_v5e(one_chip, k, n):
    """The batched GEMV's device-side placement at smollm-360m's
    projection shapes (int4 x int8, 4 slots): the weight and activation
    plane builders, and one tile's two-range row write into packed state."""
    from repro.core.comefa import engine_packed, grid, schedule
    from repro.kernels import comefa_sim
    from repro.serve.comefa_exec import acc_bits_for
    g, wb, xb = 4, 4, 8
    acc = acc_bits_for(wb, xb, k)
    plan = schedule.cached_plan_gemv(
        k, n, wb, xb, acc,
        k_tile=min(k, comefa_sim.gemv_batched_k_tile(wb, xb, acc)))
    pack = engine_packed.get_engine("packed-xla").pack_rows
    w_planes = jax.eval_shape(comefa_sim._weight_planer(pack, plan),
                              jax.ShapeDtypeStruct((k, n), jnp.uint8))
    x_planes = jax.eval_shape(comefa_sim._x_planer(pack, plan),
                              jax.ShapeDtypeStruct((g, k), jnp.uint8))
    assert len(w_planes) == len(x_planes) == plan.n_tiles
    comefa_sim._weight_planer(pack, plan).lower(
        _spec((k, n), jnp.uint8, one_chip)).compile()
    comefa_sim._x_planer(pack, plan).lower(
        _spec((g, k), jnp.uint8, one_chip)).compile()
    x_base = comefa_sim._gemv_batched_layout(plan)[0].base
    planes = tuple(_spec(p.shape, p.dtype, one_chip)
                   for p in (w_planes[0], x_planes[0]))
    grid._write_row_ranges.lower(
        _spec((g, plan.n_blocks, isa.N_ROWS, N_WORDS), jnp.uint32, one_chip),
        planes, bases=(comefa_sim._weight_bases(plan)[0], x_base)).compile()
