"""Bit-plane packing (`quant.bitplane`) and the packed projection that
consumes it (`models.common.linear`'s XLA unpack-and-dot): round trips,
quantization bounds, and allclose vs the pure-jnp oracle in
`kernels.ref` across shape/dtype sweeps, plus hypothesis properties."""
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    # no hypothesis in this environment (the container image has no pip):
    # fall back to the deterministic seeded sampler so this module RUNS
    # instead of perpetually skipping (see tests/_minihyp.py)
    from _minihyp import given, settings, strategies as st

from repro import configs
from repro.kernels import ref
from repro.models import common as cm
from repro.quant import bitplane as bp

RNG = np.random.default_rng(42)


# ---------------------------------------------------------------------------
# packing round trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 3, 4, 8])
@pytest.mark.parametrize("shape,axis", [((64, 16), 0), ((32, 64), 1),
                                        ((128,), 0)])
def test_pack_unpack_roundtrip(bits, shape, axis):
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    q = jnp.asarray(RNG.integers(lo, hi + 1, size=shape), jnp.int32)
    packed = bp.pack(q, bits, axis=axis)
    assert packed.dtype == jnp.uint32
    assert packed.shape[0] == bits
    back = bp.unpack(packed, bits, axis=axis)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


@given(bits=st.integers(2, 8), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_property(bits, seed):
    rng = np.random.default_rng(seed)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    q = jnp.asarray(rng.integers(lo, hi + 1, size=(64, 8)), jnp.int32)
    back = bp.unpack(bp.pack(q, bits, axis=0), bits, axis=0)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(q))


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_pack_unpack_full_signed_range_inner_axis(bits):
    """Regression: pack() on axis != 0 over the FULL signed range.

    Every representable value appears - including the asymmetric minimum
    -2^(b-1), whose two's-complement pattern exercises the MSB plane -
    packed along an inner axis, where the hoisted lane-weight vector must
    broadcast against the leading axes rather than align by position.
    """
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    vals = np.arange(lo, hi + 1, dtype=np.int32)
    # width: every value at least once, padded to a multiple of 32 lanes
    q = np.tile(vals, (3, max(1, 32 // len(vals))))
    assert q.shape[1] % 32 == 0
    assert q.min() == lo and q.max() == hi
    packed = bp.pack(jnp.asarray(q), bits, axis=1)
    back = bp.unpack(packed, bits, axis=1)
    np.testing.assert_array_equal(np.asarray(back), q)


def test_quantize_bounds_and_scale():
    w = jnp.asarray(RNG.normal(size=(64, 32)) * 3, jnp.float32)
    for bits in (2, 4, 8):
        q, s = bp.quantize(w, bits, axis=0)
        qmax = 2 ** (bits - 1)
        assert int(jnp.max(q)) <= qmax - 1 and int(jnp.min(q)) >= -qmax
        err = jnp.abs(bp.dequantize(q, s) - w)
        assert float(err.max()) <= float(s.max())   # within one step


# ---------------------------------------------------------------------------
# packed projection: `linear`'s XLA unpack-and-dot (no linear hook)
# ---------------------------------------------------------------------------

CFG = configs.get("smollm-360m")


def packed_linear(x, packed, scale):
    return cm.linear({"packed": packed, "scale": scale}, x, CFG)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (16, 256, 128),
                                   (128, 128, 256), (1, 384, 128)])
def test_packed_linear_vs_ref(bits, m, k, n):
    x = jnp.asarray(RNG.normal(size=(m, k)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(k, n)), jnp.float32)
    packed, scale = bp.quantize_pack(w, bits, axis=0)
    y = packed_linear(x, packed, scale)
    y_ref = ref.bitplane_matmul_ref(x, packed, scale, bits=bits)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_packed_linear_dtypes(dtype):
    x = jnp.asarray(RNG.normal(size=(16, 128)), dtype)
    w = jnp.asarray(RNG.normal(size=(128, 128)), jnp.float32)
    packed, scale = bp.quantize_pack(w, 4, axis=0)
    y = packed_linear(x, packed, scale)
    assert y.dtype == dtype
    y_ref = np.asarray(ref.bitplane_matmul_ref(x.astype(jnp.float32),
                                               packed, scale, bits=4))
    # the projection dequantizes the weights in x's dtype and returns
    # that dtype: each weight and each output is rounded once to it
    w_deq = np.asarray(bp.unpack(packed, 4, axis=0) * scale)
    mag = np.abs(np.asarray(x, np.float32)) @ np.abs(w_deq)
    bound = float(jnp.finfo(dtype).eps) * (mag + np.abs(y_ref))
    err = np.abs(np.asarray(y, np.float32) - y_ref)
    assert (err <= bound).all(), float((err / bound).max())


def test_packed_linear_approximates_dense():
    x = jnp.asarray(RNG.normal(size=(16, 256)), jnp.float32)
    w = jnp.asarray(RNG.normal(size=(256, 128)), jnp.float32)
    dense = x @ w

    def rel_err(bits):
        y = packed_linear(x, *bp.quantize_pack(w, bits, axis=0))
        return float(jnp.linalg.norm(y - dense) / jnp.linalg.norm(dense))

    rel, rel2 = rel_err(8), rel_err(2)
    assert rel < 0.01                       # 8-bit: <1% relative error
    assert rel < rel2 < 1.0                 # precision-agnostic degradation


@given(bits=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_packed_linear_exact_on_integers(bits, seed):
    """With integer x and scale 1, the projection must be *exact*."""
    rng = np.random.default_rng(seed)
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    q = jnp.asarray(rng.integers(lo, hi + 1, size=(128, 128)), jnp.int32)
    x = jnp.asarray(rng.integers(-8, 8, size=(8, 128)), jnp.float32)
    packed = bp.pack(q, bits, axis=0)
    scale = jnp.ones((1, 128), jnp.float32)
    y = packed_linear(x, packed, scale)
    expect = ref.bitplane_matmul_ref(x, packed, scale, bits=bits)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(expect))
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(x @ q.astype(jnp.float32)))
