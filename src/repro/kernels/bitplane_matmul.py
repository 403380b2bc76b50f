"""Bit-plane matmul Pallas kernel: fp activations x w-bit packed weights.

This is the MXU-native adaptation of CoMeFa's OOOR GEMV (paper Sec. III-I):
the *weights* live in the array in bit-transposed form ("pinned transposed
into CoMeFa RAM blocks"), the activation operand streams past at full
precision.  On TPU we re-block the bit-serial column MACs onto the systolic
array: each weight bit-plane is a binary matrix, so

    y = x @ W  =  sum_i  c_i * (x @ plane_i) * scale       (c_i = +/-2^i)

runs as `bits` MXU matmuls whose operand was fetched from HBM at w bits per
weight instead of 16 - the "storage is the compute operand" property that
makes this kernel win on memory-bound (decode/GEMV) shapes by ~16/w.

VMEM tiling: x block [bm, bk] and all `bits` packed planes of a [bk, bn]
weight tile ([bits, bk/32, bn] uint32) are resident per grid step; the
unpack (repeat + shift + mask, the in-register swizzle of paper Fig 7) is
VPU work fully overlapped with the MXU plane-matmuls at bk >= 128.  Grid is
(M/bm, N/bn, K/bk) with a [bm, bn] f32 VMEM accumulator; K is innermost so
the accumulator stays resident (output-stationary, like the CoMeFa
accumulator rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..quant.bitplane import LANES, coeffs


def _unpack_block(packed: jax.Array, bk: int, dtype) -> jax.Array:
    """[bk/32, bn] uint32 planes -> [bk, bn] {0,1} matrix of `dtype`."""
    rep = jnp.repeat(packed, LANES, axis=0)                    # [bk, bn]
    sh = jax.lax.broadcasted_iota(jnp.uint32, (bk, 1), 0) % LANES
    # Mosaic has no uint32 -> float cast; the {0,1} bits go through int32
    return ((rep >> sh) & 1).astype(jnp.int32).astype(dtype)


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, bits: int,
            plane_coeffs: tuple, out_dtype):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                             # [bm, bk]
    bk = x.shape[1]
    acc = acc_ref[...]
    for i in range(bits):                                      # static unroll
        plane = _unpack_block(w_ref[i], bk, x.dtype)           # [bk, bn]
        acc += plane_coeffs[i] * jax.lax.dot_general(
            x, plane, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    acc_ref[...] = acc

    @pl.when(k == nk - 1)
    def _():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "bm", "bn", "bk", "interpret", "out_dtype"))
def bitplane_matmul(x: jax.Array, w_packed: jax.Array, scale: jax.Array,
                    *, bits: int, bm: int = 128, bn: int = 128,
                    bk: int = 128, interpret: bool = False,
                    out_dtype=jnp.float32) -> jax.Array:
    """y[M,N] = x[M,K] @ dequant(w_packed, scale).

    w_packed: uint32 [bits, K/32, N] from `quant.bitplane.pack` (axis=0 on
    the [K, N] int matrix).  scale: f32 [1, N] per-output-channel.
    Shapes must be multiples of the block sizes (ops.py pads otherwise).
    """
    m, kdim = x.shape
    n = w_packed.shape[2]
    assert w_packed.shape == (bits, kdim // LANES, n)
    assert kdim % bk == 0 and m % bm == 0 and n % bn == 0
    assert bk % LANES == 0
    plane_coeffs = tuple(float(c) for c in coeffs(bits))

    grid = (m // bm, n // bn, kdim // bk)
    return pl.pallas_call(
        functools.partial(_kernel, bits=bits, plane_coeffs=plane_coeffs,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bits, bk // LANES, bn), lambda i, j, k: (0, k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w_packed, scale)
